"""Host-to-device transfer of the tensor residual's inputs per query: the
summed duration of the ``residual_h2d`` spans (traced, each waits for its
copy), per call of the traced window."""
from bench import span_reduce


def read(ctx):
    return span_reduce.per_call_ms(ctx, "residual_h2d")
