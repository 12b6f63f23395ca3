"""The grouped products' share of their roofline in the latent trunk,
whatever implements them: the least time the chip could take for what the
routed layers' three products require in a step
(``chipbench/flops_latent_trunk.py``: forward and both gradients, nothing
recomputed, over the (token, choice) pairs that fell on held experts by the
program's own counter, the gauge ``moe.absent_share`` of the last round: it
reads 0.87 to 0.89 from seed to seed where a uniform router gives 0.875),
divided by the device time of the scope ``moe_experts`` in a step. The least time is the larger of
operations over the bf16 peak and bytes over the published memory bandwidth
(``chipbench/peaks_memory.py``): at 344 rows an expert the two are within a
per cent of each other (3.69 ms each at the cell's shapes), so neither
vanishes. The scope's time holds the forward rematerialised in the backward
pass and the selects that zero unwritten rows, which are not required work.
Source: device trace. Layer: latent trunk. Moves ``train_samples_per_s``."""

from chipbench import flops_latent_trunk, trace_scopes


def read(run: dict):
    ms = trace_scopes.scope_ms_per_step(run, ("moe_experts",))
    if not (ms and run.get("peaks") and run.get("hbm_bytes_per_s") and run.get("trunk") and run.get("routing")):
        return None
    chips = run["device"]["count"]
    held_share = 1.0 - run["routing"]["absent_share"]
    least_s = max(
        flops_latent_trunk.experts_flops_per_step(run["shapes"], run["trunk"], held_share)
        / (run["peaks"]["bf16_flops_per_s"] * chips),
        flops_latent_trunk.experts_bytes_per_step(run["shapes"], run["trunk"], held_share)
        / (run["hbm_bytes_per_s"] * chips),
    )
    return 100.0 * least_s / (ms / 1e3)
