"""Megabytes (10^6 bytes) shipped from storage to compute per query:
``StreamRun.real_net_bytes`` (pushdown results and pushed-back raw
columns), per call of the traced window."""


def read(ctx):
    calls = ctx.calls
    if not calls:
        return None
    return sum(r.net_bytes for r in calls) / 1e6 / len(calls)
