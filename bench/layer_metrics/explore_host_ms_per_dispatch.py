"""Host milliseconds per explore dispatch outside the blocking fetch:
launch (operand stacking, the program calls) plus post-processing of
the fronts (`stats()["explore_host_s"]`, added by the session per
dispatch), over `stats()["explorer_dispatches"]`, over the traced part
of the window where there is one, else over the whole window.
Nothing where the service keeps no such counter."""


def read(ctx):
    win = ctx["window"]
    stats = win.traced_stats or win.stats
    n = stats.get("explorer_dispatches", 0)
    if "explore_host_s" not in stats or not n:
        return None
    return 1000.0 * stats["explore_host_s"] / n
