"""95th percentile of the latencies of every query completed in the
window (linear interpolation between order statistics)."""
from bench.harness import p95


def read(ctx):
    lat = ctx.latencies
    return p95(lat) if lat else None
