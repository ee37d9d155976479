"""Device time per flush of the jitted local-update program (the
``jit_clients`` module: every client of the flush, all local steps)."""
from bench import trace as T


def read(ctx):
    flushes = T.count_spans(ctx.trace.spans, "bench.flush", ctx.trace.window)
    ns = T.module_time(ctx.trace, "jit_clients")
    if not flushes or not ns:
        return None
    return ns / 1e6 / flushes
