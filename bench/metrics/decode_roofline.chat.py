"""Least time of the traced window's decode steps over their device time.
Least bytes of a step: base weights once, each distinct adapter of the
active rows once, their live KV positions read and new ones written, SSD
state and conv window read and written; over peak bandwidth."""
from bench import flops
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    ns = T.time_in_spans(tr.modules[0], tr.spans, "bench.decode") if tr.n_devices else 0
    steps = [s for s in ctx.cell.steps if ctx.in_window(s.t0)]
    if not ns or not steps:
        return None
    least = sum(flops.hymba_decode_step_bytes(ctx.cfg, s.rows, s.sum_ctx,
                                              s.distinct) for s in steps)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / (ns / 1e9)
