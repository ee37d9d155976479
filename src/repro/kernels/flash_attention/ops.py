"""Public attention op with implementation switch.

``flash_attention(..., impl="pallas")`` is the TPU deployment path; the
model code calls this wrapper so the dry-run (CPU) lowers the XLA oracle
while TPU builds get the tiled kernel. ``interpret=None`` resolves to the
backend default (interpret only on CPU — see kernels/runtime.py).
"""
from __future__ import annotations

import numpy as np

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.runtime import resolve_interpret


def flash_attention(q, k, v, q_pos, kv_pos, window=None, softcap=None,
                    impl: str = "pallas", interpret: bool | None = None,
                    bq: int = 512, bt: int = 512):
    if window is None:
        window = np.iinfo(np.int32).max
    if impl == "pallas":
        return flash_attention_pallas(q, k, v, q_pos, kv_pos, window,
                                      softcap, bq=bq, bt=bt,
                                      interpret=resolve_interpret(interpret))
    return flash_attention_ref(q, k, v, q_pos, kv_pos, window, softcap)
