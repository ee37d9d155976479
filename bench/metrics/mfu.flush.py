"""Required model FLOPs of the client updates completed in the traced
window over (window x the chip's bf16 peak). Required: forward, input
gradients and LoRA weight gradients through the encoders of the modalities
each client holds, plus fusion and head (``bench/flops.py``)."""
from bench import flops


def read(ctx):
    cell = ctx.cell
    first = cell.n_first
    done = [i for i, (a, b) in enumerate(cell.flush_times)
            if ctx.in_window(a) and b <= ctx.t1]
    if not done:
        return None
    total = 0.0
    for i in done:
        for held in cell.cohorts[first + i]["mmask"]:
            total += flops.relief_update_flops(ctx.cfg, [bool(h) for h in held])
    return 100.0 * total / ((ctx.t1 - ctx.t0) * ctx.peaks["bf16_flops_per_s"])
