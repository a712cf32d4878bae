"""Share of the traced stretch in which the device is idle while the
compute host works for a query: while a ``compute_replay``, ``merge``,
``residual_prep``, ``residual_h2d`` or ``residual_d2h`` span is open, in
percent. Read only where the program has the residual's step spans."""
from bench import span_reduce

HOST_SPANS = ("compute_replay", "merge", "residual_prep", "residual_h2d",
              "residual_d2h")


def read(ctx):
    if span_reduce.seconds(ctx, "residual_prep") is None:
        return None
    return span_reduce.idle_share_while_open(ctx, HOST_SPANS)
