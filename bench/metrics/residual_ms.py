"""Residual per query: the summed duration of the ``residual_compute``
spans, per call of the traced window."""


def read(ctx):
    return ctx.per_call_ms(ctx.span_seconds("residual_compute"))
