"""95th percentile, over the requests whose admission started in the
traced window, of admission start minus submission: the wait in
``ServingEngine.queue``, from the engine's own request records."""
import numpy as np

from bench import program as P


def read(ctx):
    w = P.window(ctx)
    stats = getattr(getattr(ctx.cell, "eng", None), "stats", None)
    if w is None or stats is None:
        return None
    waits = [r.queue_wait for r in stats.ring("request") if w.inside(r.admit)]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 95))
