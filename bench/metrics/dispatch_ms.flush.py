"""Host time per flush in the program's ``fed.run`` spans outside its
``fed.flush`` spans: the vectorized event loop (``peek_window``, ``claim``,
``_absorb``), allocation and redispatch (``_dispatch_vec``) and the
accounting of completions."""
from bench import program as P


def read(ctx):
    w = P.window(ctx)
    n = w.count("fed.flush") if w else 0
    if not n:
        return None
    return (w.ms("fed.run") - w.ms("fed.flush")) / n
