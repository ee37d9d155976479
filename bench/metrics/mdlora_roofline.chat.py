"""Least time of the traced window's gathered multi-adapter projections in
the decode steps (three per layer and step: wq, wv and the fused wo) over
the device time of the kernel's operations inside the ``bench.decode``
spans. Least bytes of a call: the shared w0 once, each distinct adapter's
a and b once, the inputs and outputs."""
from bench import flops
from bench import trace as T

KERNEL = "_matmul_multi_jit"  # the kernel's call in the decode program


def read(ctx):
    tr = ctx.trace
    if not tr.n_devices:
        return None
    ops = T.matching(T.clip(tr.ops[0], tr.window), lambda n: n.startswith(KERNEL))
    ns = T.time_in_spans(ops, tr.spans, "bench.decode")
    steps = [s for s in ctx.cell.steps if ctx.in_window(s.t0)]
    if not ns or not steps:
        return None
    cfg, pk = ctx.cfg, ctx.peaks
    rows, r, L = cfg["slots"], cfg["lora_rank"], cfg["n_layers"]
    least = 0.0
    for s in steps:
        for din, dout in flops.hymba_lora_shapes(cfg).values():
            b = flops.mdlora_call_bytes(din, dout, r, rows, s.distinct_all)
            f = flops.mdlora_call_flops(din, dout, r, rows)
            least += L * max(b / pk["hbm_bytes_per_s"], f / pk["bf16_flops_per_s"])
    return 100.0 * least / (ns / 1e9)
