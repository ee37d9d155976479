"""Required FLOPs of the tokens produced in the traced window over (window x
the chip's bf16 peak). A token's FLOPs: every weight it multiplies (with
its own adapter) and attention over its live positions; an admission
counts its whole prompt (``bench/flops.hymba_served_flops``)."""
from bench import flops


def read(ctx):
    steps = [s for s in ctx.cell.steps if ctx.in_window(s.t0) and s.t1 <= ctx.t1]
    admits = [p for a, b, p in ctx.cell.admits if ctx.in_window(a) and b <= ctx.t1]
    if not steps:
        return None
    total = flops.hymba_served_flops(ctx.cfg, steps, admits)
    return 100.0 * total / ((ctx.t1 - ctx.t0) * ctx.peaks["bf16_flops_per_s"])
