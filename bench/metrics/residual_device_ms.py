"""Device side of the tensor residual per query: the summed duration of
the ``residual_device`` spans, from each jitted stage call until its
guard flags are on the host (device work and any wait behind the other
clients' stages), per call of the traced window."""
from bench import span_reduce


def read(ctx):
    return span_reduce.per_call_ms(ctx, "residual_device")
