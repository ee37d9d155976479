"""The flush reference's allocation rule (paper Eq. 7 and IV-B2b) on
hand-built cohorts: budgets per device type, mandatory fusion blocks,
accessible groups, and the groups of highest smoothed divergence."""
import numpy as np

from bench.refs import relief_flush as R

MODS = [{"name": n} for n in ("acc", "gyro")]
GROUPS = ["A_acc", "A_gyro", "B_shared", "E_acc_L0", "E_gyro_L0", "H_w1"]


def _cfg(mid_tops):
    return {"modalities": MODS, "fleet": [1, 1],
            "devices": [{"name": "full", "tops": 60, "modalities": ["acc", "gyro"]},
                        {"name": "mid", "tops": mid_tops, "modalities": ["acc"]}]}


def test_budgets_follow_eq7():
    # full: all 6 groups in T*; mid (acc: A_acc, B, E_acc_L0, H_w1 = 4
    # accessible) at 30 of 60 TOPS trains floor(6 * 30 / 60) = 3
    assert R.budgets(_cfg(30), GROUPS) == {frozenset({0, 1}): (6, 6),
                                           frozenset({0}): (3, 4)}
    # too slow for more than its mandatory block
    assert R.budgets(_cfg(5), GROUPS)[frozenset({0})] == (1, 4)


def _cohort(S, held, version=1):
    return {"S": np.array([S]), "mmask": np.array([held]),
            "versions": np.array([version])}


def test_allocation_errors_count_rule_breaks():
    cfg = _cfg(30)
    dbar = [dict.fromkeys(GROUPS, 1e-6),
            {"A_acc": 1, "A_gyro": 1, "B_shared": 5.0, "E_acc_L0": 1.0,
             "E_gyro_L0": 1, "H_w1": 3.0}]
    good = _cohort([1, 0, 1, 0, 0, 1], [1, 0])  # A_acc + top 2 (B, H)
    assert R.allocation_errors(cfg, GROUPS, [good], dbar) == 0
    worse = _cohort([1, 0, 1, 1, 0, 0], [1, 0])  # E_acc over H_w1
    assert R.allocation_errors(cfg, GROUPS, [worse], dbar) == 1
    # at version 0 every score ties: any accessible choice of the budget
    assert R.allocation_errors(cfg, GROUPS, [_cohort([1, 0, 1, 1, 0, 0],
                                                     [1, 0], 0)], dbar) == 0
    no_mand = _cohort([0, 0, 1, 1, 0, 1], [1, 0])
    absent = _cohort([1, 1, 1, 0, 0, 0], [1, 0])  # gyro is not held
    full = _cohort([1, 1, 1, 1, 1, 0], [1, 1])  # budget 6, 5 on
    assert R.allocation_errors(cfg, GROUPS, [no_mand, absent, full], dbar) == 3
