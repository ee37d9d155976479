"""Whole runs of each kind of cell on the CPU at a small size, the chip
check skipped: the sound program agrees with the plain reference, and
``correct`` comes out false with each fault the cell can have planted in
the timed path, and with the lower-precision control in the program's
place."""
import time

import pytest

from bench import harness

SMALL_FLUSH = {
    "n_clients": 16, "windows_per_subject": 16, "enc_layers": 2, "enc_d": 32,
    "enc_ff": 64, "d_fused": 64, "local_epochs": 1, "steps_per_epoch": 2,
    "modalities": [{"name": "acc", "channels": 3, "d_feat": 16, "kind": "imu"},
                   {"name": "gyro", "channels": 3, "d_feat": 16, "kind": "imu"},
                   {"name": "mag", "channels": 3, "d_feat": 16, "kind": "imu"},
                   {"name": "hr", "channels": 1, "d_feat": 8, "kind": "pulse"}]}
SMALL_LM = {
    "n_layers": 2, "d_model": 64, "n_heads": 5, "n_kv_heads": 1,
    "head_dim": 16, "d_ff": 128, "vocab": 97, "ssm_state": 8,
    "ssm_head_dim": 16, "d_inner": 64, "ssd_chunk": 16, "sliding_window": 16,
    "q_chunk": 16, "remat": "none", "n_adapters": 8, "slots": 4,
    "lora_impl": "xla"}
CHAT = {"rate_per_s": 20.0, "warmup_s": 0.5,
        "prompt": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                   "buckets": [3, 5]},
        "output": {"dist": "lognormal", "median": 6, "sigma": 0.6, "min": 2,
                   "max": 12}}
CLOSED = CHAT | {"loop": "closed", "outstanding": 6, "max_completions_per_s": 8,
                 "output": {"dist": "uniform", "min": 8, "max": 20}}

# case -> (workload, configuration override, traffic override, seconds)
CASES = {
    "flush": ("flush.pamap2_b2.k64", SMALL_FLUSH, {"buffer_size": 4}, 1.0),
    "chat": ("serve.hymba.chat", SMALL_LM, CHAT, 2.0),
    "closed_loop": ("serve.hymba.chat", SMALL_LM, CLOSED, 2.0),
}


def _run(case, fault=None, control=False, seed=2**31 + 99):
    workload, cfg, tr, seconds = CASES[case]
    opts = harness.Options(require_tpu=False, compile_cache=False,
                           fault=fault, control=control, cfg_override=cfg,
                           traffic_override=tr)
    return harness.run_cell(workload, seed, seconds, False,
                            time.perf_counter(), opts)


@pytest.mark.parametrize("case", sorted(CASES))
def test_sound_program_is_correct(case):
    rec = _run(case)
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert rec["device"]["platform"] == "cpu"
    assert set(rec["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("case,fault", [
    ("flush", "unchanged"),
    ("flush", "half_batch"),
    ("flush", "gates"),
    ("chat", "token"),
    ("closed_loop", "token"),
])
def test_fault_makes_correct_false(case, fault):
    rec = _run(case, fault=fault)
    assert not rec["correct"], rec["checks"]
    if fault == "gates":  # the allocation check alone catches it
        assert rec["checks"]["gates_wrong"]["value"] > 0


@pytest.mark.parametrize("case", ["flush"])
def test_control_is_not_correct(case):
    """bfloat16 local training in place of the program (float32)."""
    rec = _run(case, control=True)
    assert not rec["correct"], rec["checks"]


def test_serving_control_reads_wider():
    """int8 weights in place of the program's bfloat16 ones. At this size
    the gap stays under the cell's limit (set at full size on the chip,
    where the control reads 3-5x the program); here it only has to read
    wider than the program on the same requests."""
    prog = _run("chat")["checks"]["mean_logit_gap"]["value"]
    ctl = _run("chat", control=True)["checks"]["mean_logit_gap"]["value"]
    assert ctl > prog > 0
