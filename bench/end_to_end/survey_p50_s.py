"""Median latency, from the time a request fell due to its collected
artifact, over every request of the window (host clock)."""
import loadgen


def read(ctx):
    lat = ctx["window"].latency_s
    return loadgen.percentile(lat, 50) if lat else None
