"""Queries completed inside the window, per second of the window."""


def read(ctx):
    return len(ctx.window.completed) / ctx.window.seconds
