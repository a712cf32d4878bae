"""Seconds from process start to the window: data and catalog, storage
workers and partition shipping, the observe pass and jitted stages of
each query, the warm-up round."""


def read(ctx):
    return ctx.setup_s
