"""Storage execute per query: the summed duration of the
``storage_execute`` spans (pool threads overlap, so this is busy time,
not wall time), per call of the traced window."""


def read(ctx):
    return ctx.per_call_ms(ctx.span_seconds("storage_execute"))
