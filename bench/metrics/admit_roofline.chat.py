"""Least time of the traced window's admissions over their device time.
Least time of a P-token prefill with one adapter, whatever implements it:
max(FLOPs / peak, (weights once + adapter + cache row) / bandwidth)."""
from bench import flops
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    ns = T.time_in_spans(tr.modules[0], tr.spans, "bench.admit") if tr.n_devices else 0
    admits = [p for a, b, p in ctx.cell.admits if ctx.in_window(a)]
    if not ns or not admits:
        return None
    least = sum(flops.hymba_prefill_min_s(ctx.cfg, p, ctx.peaks) for p in admits)
    return 100.0 * least / (ns / 1e9)
