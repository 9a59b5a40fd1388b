"""Relaxation sweeps the routing wavefront kernel ran per wavefront:
`stats()["route_wavefront_iters"]` (sweeps summed over every grid of
every net slot, added by the session per layout bucket) over
`stats()["route_wavefronts"]` (grid-slots with a live net), over the
traced part of the window where there is one, else over the
whole window.  Nothing where the service keeps no such counters (a
program without them, or no wavefront ran on the device)."""


def read(ctx):
    win = ctx["window"]
    stats = win.traced_stats or win.stats
    n = stats.get("route_wavefronts", 0)
    if "route_wavefront_iters" not in stats or not n:
        return None
    return stats["route_wavefront_iters"] / n
