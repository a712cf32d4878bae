"""Host work of the tensor residual's PyOp stages per query (Q15's and
Q22's host functions between two jitted stages, with the host tables
they read): the summed duration of the ``residual_pyop`` spans, per call
of the traced window. Silent on a program without the span."""
from bench import span_reduce


def read(ctx):
    return span_reduce.per_call_ms(ctx, "residual_pyop")
