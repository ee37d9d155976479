"""Device time per admission: the programs that ran inside the benchmark's
``bench.admit`` spans (the engine's fused admission call, blocked on)."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    n = T.count_spans(tr.spans, "bench.admit", tr.window)
    ns = T.time_in_spans(tr.modules[0], tr.spans, "bench.admit") if tr.n_devices else 0
    if not n or not ns:
        return None
    return ns / 1e6 / n
