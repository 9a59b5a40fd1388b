"""Backtrace steps the routing kernel walked per wavefront:
`stats()["route_walk_steps"]` (the reached targets' entry distances,
summed over every grid of every net slot, added by the session per
layout bucket) over `stats()["route_wavefronts"]` (grid-slots with a
live net), over the traced part of the window where there is one, else
over the whole window.  Nothing where the service keeps no such
counters (a program whose walk is not in the kernel, or no wavefront
ran on the device)."""


def read(ctx):
    win = ctx["window"]
    stats = win.traced_stats or win.stats
    n = stats.get("route_wavefronts", 0)
    if "route_walk_steps" not in stats or not n:
        return None
    return stats["route_walk_steps"] / n
