"""Share of the device's busy time spent in the routing program
(`eda/batched_flow._route_program`: the scan engine with the wavefront
kernel inside), over the traced part of the window, in percent."""


def read(ctx):
    t = ctx["trace"]
    route = sum(v for k, v in t["module_s"].items() if "_route_program" in k)
    if not route or not t["busy_s"]:
        return None
    return 100.0 * route / t["busy_s"]
