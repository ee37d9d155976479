"""Host time per engine step in the program's ``serve.step`` spans outside
its ``serve.admit`` and ``serve.decode`` spans: the slot bookkeeping,
token hand-back and retirement of ``ServingEngine.step``."""
from bench import program as P


def read(ctx):
    w = P.window(ctx)
    n = w.count("serve.step") if w else 0
    if not n:
        return None
    return (w.ms("serve.step") - w.ms("serve.admit")
            - w.ms("serve.decode")) / n
