"""Laid-out designs delivered (the rows of the distilled fronts) per
second of the window, up to the end of the last job it counts (host
clock)."""


def read(ctx):
    win = ctx["window"]
    return win.designs / win.seconds if win.designs else None
