"""Planning and arbitration per query: ``plan_requests`` and
``arbitrate`` span time (``repro.obs``), per call of the traced window."""


def read(ctx):
    return ctx.per_call_ms(ctx.span_seconds("plan_requests", "arbitrate"))
