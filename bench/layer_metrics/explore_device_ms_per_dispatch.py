"""Device milliseconds per execution of the explore program
(`core/batched_explorer.sweep_program`), over the traced part of the
window."""


def read(ctx):
    t = ctx["trace"]
    s = sum(v for k, v in t["module_s"].items() if "sweep_program" in k)
    n = sum(v for k, v in t["module_n"].items() if "sweep_program" in k)
    return 1000.0 * s / n if s and n else None
