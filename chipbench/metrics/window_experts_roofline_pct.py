"""The grouped products' share of their roofline in the window trunk,
computed as ``latent_experts_roofline_pct`` is: the least time the chip
could take for what the routed layers' three products require in a step
(``chipbench/flops_window_trunk.py``, which takes the count from
``flops_latent_trunk.py``: forward and both gradients, nothing recomputed,
over the (token, choice) pairs that fell on held experts by the program's
own counter, the gauge ``moe.absent_share`` of the last round), divided by
the device time of the scope ``moe_experts`` in a step. The least time is
the larger of operations over the bf16 peak and bytes over the published
memory bandwidth: at 1,760 rows an expert of 512 the operations bound it
(4.25e12 operations are 22 ms, 1.28e10 bytes 16 ms). The scope's
time holds the forward rematerialised in the backward pass and the selects
that zero unwritten rows, which are not required work. Source: device trace.
Layer: window trunk. Moves ``train_samples_per_s``."""

from chipbench import flops_window_trunk, trace_scopes


def read(run: dict):
    ms = trace_scopes.scope_ms_per_step(run, ("moe_experts",))
    if not (ms and run.get("peaks") and run.get("hbm_bytes_per_s") and run.get("trunk") and run.get("routing")):
        return None
    chips = run["device"]["count"]
    held_share = 1.0 - run["routing"]["absent_share"]
    least_s = max(
        flops_window_trunk.experts_flops_per_step(run["shapes"], run["trunk"], held_share)
        / (run["peaks"]["bf16_flops_per_s"] * chips),
        flops_window_trunk.experts_bytes_per_step(run["shapes"], run["trunk"], held_share)
        / (run["hbm_bytes_per_s"] * chips),
    )
    return 100.0 * least_s / (ms / 1e3)
