"""Programs the backend compiled inside the traced window, as the program
counts them (``repro.obs.compiles``); none should."""
from bench import program as P


def read(ctx):
    w = P.window(ctx)
    return None if w is None else w.compiles()
