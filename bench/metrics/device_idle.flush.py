"""Share of the traced window in which no operation ran on the device."""
from bench import trace as T


def read(ctx):
    return T.idle_percent(ctx.trace)
