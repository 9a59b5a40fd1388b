"""Layout stage busy seconds per laid-out design delivered in the window
(`DesignService.stats()["stage_busy_s"]["layout"]`, the stage's own
host-clock busy time, summed over the window's jobs)."""


def read(ctx):
    win = ctx["window"]
    busy = win.stats.get("stage_busy_s", {}).get("layout")
    if not win.designs or busy is None:
        return None
    return busy / win.designs
