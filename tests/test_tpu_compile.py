"""The main-path Pallas kernels, compiled for a described TPU v5e chip.

Interpret mode (tests/test_kernels.py) checks results but not the TPU
compiler's rules: block tiling, VMEM limits, layouts. These tests hand each
kernel of the training flush and the serving decode, at the real widths, to
the chip's compiler through a described ``v5e:2x2`` topology — nothing runs
and no chip is needed — and check that a Mosaic kernel comes out
(``tpu_custom_call``), not an error. Every block size the autotuner may pick
for the shape is compiled, since on a chip it times each of them.

The topology is described only inside the module fixture: the TPU library
may be loaded by one process at a time, so it must not happen at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cohort_agg.autotune import candidate_bds, mdlora_candidates
from repro.kernels.cohort_agg.kernel import (cohort_agg_divergence_pallas,
                                             cohort_agg_divergence_quant_pallas)
from repro.kernels.mdlora.kernel import (mdlora_matmul_multi_pallas,
                                         mdlora_matmul_pallas)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# PAMAP2_B2 fusion leaf (d_feat 32 x 3 + 16 -> D = 112, rank 8) with a
# 64-client flush, and a 1024-row fusion leaf
@pytest.mark.parametrize("N,D,r", [(64, 112, 8), (64, 1024, 8)])
@pytest.mark.parametrize("quant", [False, True], ids=["fp32", "int8"])
def test_cohort_agg_compiles(one_chip, N, D, r, quant):
    for bd in candidate_bds(D, r):
        if quant:
            def fn(q, s, W, C, t, bd=bd):
                return cohort_agg_divergence_quant_pallas(q, s, W, C, t, 0.5,
                                                          bd=bd)
            shapes = [((N, D, r), jnp.int8), ((N,), jnp.float32),
                      ((N, D), jnp.float32), ((N, D), jnp.float32),
                      ((N,), jnp.float32)]
        else:
            def fn(d, W, C, bd=bd):
                return cohort_agg_divergence_pallas(d, W, C, bd=bd)
            shapes = [((N, D, r), jnp.float32), ((N, D), jnp.float32),
                      ((N, D), jnp.float32)]
        assert "tpu_custom_call" in _compile_text(fn, one_chip, *shapes), bd


def test_mdlora_compiles(one_chip):
    T, D, F, r = 256, 2048, 2048, 8
    for bt, bf, bd in mdlora_candidates(T, D, F, r, multi=False):
        def fn(x, w0, a, b, m, bt=bt, bf=bf, bd=bd):
            return mdlora_matmul_pallas(x, w0, a, b, m, 2.0, bt=bt, bf=bf,
                                        bd=bd)
        text = _compile_text(fn, one_chip, ((T, D), jnp.bfloat16),
                             ((D, F), jnp.bfloat16), ((D, r), jnp.float32),
                             ((r, F), jnp.float32), ((D,), jnp.float32))
        assert "tpu_custom_call" in text, (bt, bf, bd)


# decode-time LoRA targets (wq, wv, wo) of the serving archs: bf16
# activations and base weights, fp32 adapter store of 4 slots
@pytest.mark.parametrize("D,F", [(1600, 1600), (1600, 320), (4800, 1600),
                                 (5120, 5120), (5120, 1280)],
                         ids=["hymba-wq", "hymba-wv", "hymba-wo",
                              "phi3-wq", "phi3-wv"])
def test_mdlora_multi_compiles(one_chip, D, F):
    B, r, A = 8, 8, 4
    for _, bf, bd in mdlora_candidates(B, D, F, r, multi=True):
        def fn(idx, x, w0, a, b, m, bf=bf, bd=bd):
            return mdlora_matmul_multi_pallas(x, w0, a, b, idx, m, 2.0,
                                              bf=bf, bd=bd)
        text = _compile_text(fn, one_chip, ((B,), jnp.int32),
                             ((B, D), jnp.bfloat16), ((D, F), jnp.bfloat16),
                             ((A, D, r), jnp.float32), ((A, r, F), jnp.float32),
                             ((B, D), jnp.float32))
        assert "tpu_custom_call" in text, (bf, bd)
