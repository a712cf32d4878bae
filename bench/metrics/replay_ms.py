"""Pushback ship and compute-side replay per query: the summed duration
of the ``pushback_ship`` and ``compute_replay`` spans, per call of the
traced window (0 where nothing was pushed back)."""


def read(ctx):
    return ctx.per_call_ms(ctx.span_seconds("pushback_ship",
                                            "compute_replay"))
