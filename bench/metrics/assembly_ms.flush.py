"""Host time per flush spent assembling the cohort's local update: inside
the benchmark's ``bench.cohort_update`` spans and outside the
``bench.local_update`` spans nested in them (each client's batches drawn
and gathered on the host, stacked and copied to the device, the start
models taken from the snapshot ring)."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    flushes = T.count_spans(tr.spans, "bench.flush", tr.window)
    ns = (T.span_ns(tr.spans, "bench.cohort_update", tr.window)
          - T.span_ns(tr.spans, "bench.local_update", tr.window))
    if not flushes or ns <= 0:
        return None
    return ns / 1e6 / flushes
