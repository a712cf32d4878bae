"""Device busy time per query: the union of the device-op intervals of
the profiler trace over the traced stretch, per call in it."""


def read(ctx):
    if ctx.device is None or not ctx.calls:
        return None
    return 1e3 * ctx.device.busy_s / len(ctx.calls)
