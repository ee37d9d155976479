"""Host time per flush in the program's ``fed.stats`` spans: the server's
diagnostics after the fold-in (divergence and EMA read to the host, the
magnitude EMA with its dequantized [K, ...] stack). The first host read
there also waits for the ingest's device work."""
from bench import program as P


def read(ctx):
    w = P.window(ctx)
    n = w.count("fed.flush") if w else 0
    if not n or not w.count("fed.stats"):
        return None
    return w.ms("fed.stats") / n
