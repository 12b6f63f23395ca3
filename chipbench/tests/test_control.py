"""The control at a size a test run can hold: the reference computed in
float8 in the program's place, and each fault planted in the reference, has
to fail the limits that sound runs pass. On the chip, at the cells' own
size, ``chipbench/control.py`` reads the same on three seeds or more."""

import pytest

from chipbench import check, control
from conftest import TINY_SHAPES, TINY_TRAFFIC
from test_harness_end_to_end import TINY_LIMITS

STEP_LIMITS = {k: TINY_LIMITS[k] for k in ("loss_gap", "grad_gap", "delta_gap_median")}


@pytest.fixture(scope="module")
def readings():
    return {seed: control.readings(TINY_SHAPES, TINY_TRAFFIC, seed) for seed in (21, 22, 23)}


@pytest.mark.parametrize("case", control.MUST_FAIL)
def test_control_and_faults_are_not_correct(readings, case):
    for seed, r in readings.items():
        verdicts, wrong = control.judge(r, TINY_LIMITS)
        assert verdicts[case]["correct"] is False and verdicts[case]["over"], (seed, case, r[case])
        assert not wrong, (seed, wrong)


def test_stated_precision_is_correct(readings):
    for seed, r in readings.items():
        ok, compared = check.verdict(r["bfloat16"], STEP_LIMITS)
        assert ok, (seed, compared)


def test_judge_names_a_control_that_passes(readings):
    loose = dict(TINY_LIMITS, loss_gap=1.0, grad_gap=1.0, delta_gap_median=1.0)
    _, wrong = control.judge(next(iter(readings.values())), loose)
    assert "float8" in wrong and "sync_left_out" not in wrong


def test_state_unchanged_reads_one(readings):
    for r in readings.values():
        assert r["state_unchanged"]["grad_gap"] == pytest.approx(1.0)
        assert r["state_unchanged"]["delta_gap_median"] == pytest.approx(1.0)
