"""Device time per flush of the operations that ran inside the benchmark's
``bench.aggregate`` spans (every ingest, divergence, EMA and fold-in
operation of the server's flush, whichever programs run them)."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    flushes = T.count_spans(tr.spans, "bench.flush", tr.window)
    ns = T.time_in_spans(tr.ops[0], tr.spans, "bench.aggregate") if tr.n_devices else 0
    if not flushes or not ns:
        return None
    return ns / 1e6 / flushes
