"""Geometric mean of the latencies of every query completed in the
window (TPC-H's power statistic: short and long query types weigh
alike)."""
from bench.harness import geomean


def read(ctx):
    lat = ctx.latencies
    return geomean(lat) if lat else None
