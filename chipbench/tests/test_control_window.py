"""The window trunk's control at a size a test run can hold: ``readings``
goes case by case over the seeds with one ``ReferenceStep`` a case, and
``judge`` holds each case to the limits. On the chip, at the cell's own
size, ``chipbench/control_window.py`` reads the same on its seeds."""

from chipbench import control_window
from test_window_harness_end_to_end import LIMITS, TINY, TRAFFIC


def test_the_control_and_the_faults_fail():
    cases = ("float8", "gate_one", "half_batch", "heads_regrouped")
    out = control_window.readings(TINY, TRAFFIC, [21, 22], cases=cases, block_rows=11)
    assert set(out) == {21, 22}
    for seed, by_case in out.items():
        assert set(by_case) == {"state_unchanged", *cases}
        verdicts, wrong = control_window.judge(by_case, LIMITS)
        assert not wrong, (seed, wrong, by_case)
        assert not verdicts["gate_one"]["correct"] and not verdicts["half_batch"]["correct"]
        assert by_case["state_unchanged"]["grad_gap"] == by_case["state_unchanged"]["experts_grad_gap"] == 1.0
        assert all(by_case[c]["router_bias_grad"] == 0 for c in by_case)   # the bias takes no gradient
    loose = dict(LIMITS, loss_gap=1.0, grad_gap=2.0, experts_grad_gap=2.0, router_bias_grad=1.0, delta_gap_median=2.0)
    _, wrong = control_window.judge(out[21], loose)
    assert set(wrong) == {"float8", "gate_one", "half_batch", "heads_regrouped", "state_unchanged"}
