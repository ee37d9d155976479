"""Host time per flush in the program's ``fed.uplink`` spans: the cohort's
deltas quantized to int8 (``dist.quantize_int8_stacked``, eager)."""
from bench import program as P


def read(ctx):
    w = P.window(ctx)
    n = w.count("fed.flush") if w else 0
    if not n or not w.count("fed.uplink"):
        return None
    return w.ms("fed.uplink") / n
