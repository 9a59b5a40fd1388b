"""Programs JAX had to build inside the measured window, compiled or
loaded from the persistent compilation cache (backend-compile events,
`compile_clock.CompileClock`)."""


def read(ctx):
    return ctx["compiles_in_window"]
