"""Operation and byte counts against hand counts, and the peak table."""
import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_relief_encoder_hand_count():
    cfg = _cfg("pamap2_b2")
    n, d, ff, r, P, C, L = 16, 128, 256, 8, 16, 3, 4
    patch = 2 * n * P * C * d  # data input: forward only
    frozen = 2 * n * (4 * d * d + 3 * d * ff)  # forward; input grad again
    attn = 4 * n * n * d  # x3: forward, both operand grads
    lora = 2 * n * r * ((d + d) + (d + d) + (d + ff))  # x3
    proj = 2 * 2 * d * 32
    want = patch + L * (2 * frozen + 3 * attn + 3 * lora) + proj
    assert want == 46_481_408
    assert flops.relief_encoder_flops(cfg, C, 32) == want


def test_relief_update_counts_only_held_encoders():
    cfg = _cfg("pamap2_b2")
    full = flops.relief_sample_flops(cfg, [True] * 4)
    low = flops.relief_sample_flops(cfg, [True, False, False, False])
    enc = [flops.relief_encoder_flops(cfg, m["channels"], m["d_feat"])
           for m in cfg["modalities"]]
    assert full - low == pytest.approx(sum(enc[1:]))
    assert flops.relief_update_flops(cfg, [True] * 4) == 640 * full


def test_hymba_hand_counts():
    cfg = _cfg("hymba_1_5b")
    layer_mm = (1600 * 1600 + 2 * 1600 * 320 + 1600 * 6482 + 4800 * 1600
                + 3 * 1600 * 5504)
    assert layer_mm == 48_054_400
    assert flops.hymba_layer_matmul_params(cfg) == layer_mm
    layer = layer_mm + 4 * 3232 + 3232 + 3 * 50 + 3200 + 2 * 1600
    assert flops.hymba_base_bytes(cfg) == 2 * (32 * layer + 32001 * 1600 + 1600)
    assert flops.hymba_base_bytes(cfg) == 3_179_341_440
    lora = 8 * (3200 + 1920 + 6400)
    assert flops.hymba_adapter_bytes(cfg) == 4 * 32 * lora == 11_796_480
    tok1 = 32 * (2 * layer_mm + 2 * lora + 4 * 1600 + 2 * 4 * 3232
                 + 4 * 50 * 64 * 16) + 2 * 1600 * 32001
    assert flops.hymba_token_flops(cfg, 1) == tok1 == 3_191_368_832
    # attention grows with the live positions, up to the window
    step = 32 * 4 * 1600
    assert flops.hymba_token_flops(cfg, 11) - tok1 == 10 * step
    assert flops.hymba_token_flops(cfg, 5000) == flops.hymba_token_flops(
        cfg, 1024)


def test_served_flops_sum_the_tokens():
    from types import SimpleNamespace as Step

    cfg = _cfg("hymba_1_5b")
    steps = [Step(rows=2, sum_ctx=7), Step(rows=1, sum_ctx=4)]
    want = (flops.hymba_token_flops(cfg, 3) + flops.hymba_token_flops(cfg, 4)
            + flops.hymba_token_flops(cfg, 4)
            + sum(flops.hymba_token_flops(cfg, t + 1) for t in range(5)))
    assert flops.hymba_served_flops(cfg, steps, [5]) == pytest.approx(want)


def test_hymba_step_and_kernel_bytes():
    cfg = _cfg("hymba_1_5b")
    kv = 32 * 2 * 320 * 2
    assert flops.hymba_kv_bytes_per_pos(cfg) == kv
    state = 32 * (50 * 64 * 16 * 4 + 3 * 3232 * 2)
    assert flops.hymba_state_bytes(cfg) == state
    got = flops.hymba_decode_step_bytes(cfg, 2, 30, 2)
    want = (3_179_341_440 + 2 * 11_796_480 + 30 * kv + 2 * kv + 4 * state)
    assert got == want
    assert flops.mdlora_call_bytes(4800, 1600, 8, 32, 5) == (
        4800 * 1600 * 2 + 5 * 8 * 6400 * 4 + 32 * 6400 * 2)
    pk = flops.peaks("TPU v5 lite")
    # a short prefill is bound by reading the weights once
    t = flops.hymba_prefill_min_s(cfg, 16, pk)
    assert t == pytest.approx((3_179_341_440 + 11_796_480 + 16 * kv + state)
                              / 819e9)
