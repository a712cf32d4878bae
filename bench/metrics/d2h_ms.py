"""Readback of the tensor residual's outputs per query: the summed
duration of the ``residual_d2h`` spans, per call of the traced window."""
from bench import span_reduce


def read(ctx):
    return span_reduce.per_call_ms(ctx, "residual_d2h")
