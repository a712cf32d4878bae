"""Host prep of the tensor residual per query: the summed duration of the
``residual_prep`` spans (prep chains, bucket padding, join lookup
tables), per call of the traced window."""
from bench import span_reduce


def read(ctx):
    return span_reduce.per_call_ms(ctx, "residual_prep")
