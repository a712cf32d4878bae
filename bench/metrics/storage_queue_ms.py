"""Wait for a storage slot per query: the summed duration of the
``worker_queue`` spans (a request's time in a storage worker's queue,
from frame read to a slot's pickup), per call of the traced window."""
from bench import span_reduce


def read(ctx):
    return span_reduce.per_call_ms(ctx, "worker_queue")
