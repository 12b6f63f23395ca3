"""The latent trunk's control at a size a test run can hold: ``readings``
goes case by case over the seeds with one ``ReferenceStep`` a case, and
``judge`` holds each case to the limits. On the chip, at the cell's own
size, ``chipbench/control_latent.py`` reads the same on three seeds."""

from chipbench import control_latent
from test_latent_harness_end_to_end import LIMITS, TINY, TRAFFIC


def test_the_control_and_a_fault_fail_and_the_stated_precision_passes():
    cases = ("float8", "bfloat16", "plain_rope", "half_batch", "bias_in_weights")
    out = control_latent.readings(TINY, TRAFFIC, [21, 22], cases=cases, block_rows=11)
    assert set(out) == {21, 22}
    for seed, by_case in out.items():
        assert set(by_case) == {"state_unchanged", *cases}
        verdicts, wrong = control_latent.judge(by_case, LIMITS)
        assert not wrong, (seed, wrong, by_case)
        assert verdicts["bfloat16"]["correct"] and not verdicts["plain_rope"]["correct"]   # (at the tiny size)
        assert by_case["state_unchanged"]["grad_gap"] == by_case["state_unchanged"]["experts_grad_gap"] == 1.0
        # the selection bias takes a gradient only where it is counted into the weights
        assert by_case["bias_in_weights"]["router_bias_grad"] > 0
        assert all(by_case[c]["router_bias_grad"] == 0 for c in by_case if c != "bias_in_weights")
        assert "router_bias_grad" in verdicts["bias_in_weights"]["over"]
    loose = dict(LIMITS, loss_gap=1.0, grad_gap=2.0, experts_grad_gap=2.0, router_bias_grad=1.0, delta_gap_median=2.0)
    _, wrong = control_latent.judge(out[21], loose)
    assert set(wrong) == {"float8", "plain_rope", "half_batch", "bias_in_weights", "state_unchanged"}
