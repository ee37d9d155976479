"""jit'd wrapper for the SSD kernel (impl switch: pallas on TPU, xla ref).

``interpret=None`` resolves to the backend default (interpret only on CPU —
see kernels/runtime.py).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.runtime import resolve_interpret
from repro.kernels.ssd.kernel import ssd_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "impl", "interpret",
                                             "bh"))
def ssd(x, dt, A_log, Bm, Cm, chunk: int = 128, initial_state=None,
        impl: str = "pallas", interpret: bool | None = None, bh: int = 8):
    if impl == "pallas":
        assert initial_state is None, "kernel path starts from zero state"
        return ssd_pallas(x, dt, A_log, Bm, Cm, chunk=chunk, bh=bh,
                          interpret=resolve_interpret(interpret))
    from repro.kernels.ssd.ref import ssd_ref
    return ssd_ref(x, dt, A_log, Bm, Cm, chunk, initial_state)
