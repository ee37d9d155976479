"""Host time per flush inside the benchmark's ``bench.aggregate`` spans:
the server's whole fold-in of a cohort (``_flush_arrays``: cohort weights,
the int8 ingest, divergence, EMA, the model update, the magnitude
statistics), eager dispatches and host syncs included."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    flushes = T.count_spans(tr.spans, "bench.flush", tr.window)
    ns = T.span_ns(tr.spans, "bench.aggregate", tr.window)
    if not flushes or not ns:
        return None
    return ns / 1e6 / flushes
