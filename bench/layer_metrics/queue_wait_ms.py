"""Milliseconds a request waited in the service's queues before its
explore stage began, per request: submit -> admission
(`stats()["admit_wait_s"]`, added as the pump admits a batch) plus
admission -> explore start (`stats()["explore_wait_s"]`, added as the
explore stage takes the batch up), over
`stats()["service_batch_requests"]`, over the traced part of the window
where the driver records one, else over the whole window.  Nothing
where the service keeps no such counters."""


def read(ctx):
    win = ctx["window"]
    stats = win.traced_stats or win.stats
    n = stats.get("service_batch_requests", 0)
    if "admit_wait_s" not in stats or not n:
        return None
    waited = stats["admit_wait_s"] + stats.get("explore_wait_s", 0.0)
    return 1000.0 * waited / n
