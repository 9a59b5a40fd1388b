"""Requests per explore dispatch: the coalescing the service's admission
pump achieved (`stats()["service_batch_requests"]` over
`stats()["explorer_dispatches"]`), over the traced part of the window
where the driver records one, else over the whole window."""


def read(ctx):
    win = ctx["window"]
    stats = win.traced_stats or win.stats
    n = stats.get("explorer_dispatches", 0)
    return stats.get("service_batch_requests", 0) / n if n else None
