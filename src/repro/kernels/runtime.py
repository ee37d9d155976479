"""Backend rules shared by the kernel wrappers and the entry points.

Interpret mode. Every Pallas kernel in this repo has an ``interpret``
switch. Interpret mode is correct everywhere but orders of magnitude slower
than a compiled kernel — it exists so the CPU-only CI container can exercise
the kernel code paths. The rule is one line: interpret exactly when the
active JAX backend has no Mosaic/Triton lowering (i.e. CPU). Callers pass
``interpret=None`` to get that default and only override it in tests.

Tiling. The TPU compiler accepts a block only if each of its last two dims
is a multiple of the hardware tile — 128 lanes for the last dim, the
dtype's sublane count (8 for 32-bit, 16 for bf16, 32 for int8) for the one
before it — or equals the whole array dim. ``legal_tile`` picks block sizes
by that rule and ``check_tile`` guards the kernel entry, so interpret-mode
tests run the very tilings the chip compiles.

Compile cache. ``enable_compile_cache`` is called by every entry point:
JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says (JAX reads that variable itself) or, when it is unset, to the fixed
``<repo>/.jax_cache`` — a path that never moves, so later runs hit.
"""
from __future__ import annotations

import functools
import os
import pathlib

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# scoped-VMEM default of the TPU compiler; kernels whose double-buffered
# blocks need more ask for it explicitly (v5e has 128 MiB of VMEM)
_VMEM_DEFAULT = 16 * 2**20
_VMEM_CAP = 100 * 2**20

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


@functools.cache
def default_interpret() -> bool:
    """True iff the active backend needs Pallas interpret mode (CPU)."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> backend default; explicit bools pass through."""
    return default_interpret() if interpret is None else bool(interpret)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; -> its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def sublane(dtype) -> int:
    """Second-minor hardware tile of ``dtype`` (8 rows of 32-bit words)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def legal_tile(dim: int, cap: int, align: int = LANE) -> int:
    """Largest legal block size <= ``cap`` along a ``dim``-long axis, or the
    smallest legal one when none fits under ``cap``. Legal = a multiple of
    ``align`` that divides ``dim``, or ``dim`` itself."""
    dim, cap = int(dim), int(cap)
    legal = [t for t in range(align, dim, align) if dim % t == 0] + [dim]
    below = [t for t in legal if t <= cap]
    return max(below) if below else min(legal)


def check_tile(dim: int, tile: int, align: int = LANE,
               name: str = "block") -> None:
    if dim % tile or (tile % align and tile != dim):
        raise ValueError(f"{name}={tile} is not a legal TPU block for a "
                         f"dim of {dim}: use a multiple of {align} dividing "
                         f"it, or the whole dim")


def compiler_params(semantics: tuple[str, ...], block_bytes: int):
    """Mosaic parameters; raises the scoped-VMEM limit when the
    double-buffered blocks (plus headroom for temporaries) need it."""
    need = 3 * int(block_bytes)
    limit = min(need, _VMEM_CAP) if need > _VMEM_DEFAULT else None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)
