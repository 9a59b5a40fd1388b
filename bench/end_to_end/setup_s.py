"""Seconds from the process's start to the window's: JAX's start, the
programs compiled or loaded from the persistent cache, the warm-up of
the cell's shapes (host clock)."""


def read(ctx):
    return ctx["setup_s"]
