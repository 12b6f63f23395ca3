"""The grouped products' share of their roofline, whatever implements them:
the operations the expert layers' three products require in a step
(``chipbench/flops_moe_trunk.py: experts_flops_per_step``: forward and both
gradients over the pairs expected on held experts, nothing recomputed) over
the chip's bf16 peak, divided by the device time of the scope
``moe_experts`` in a step. At 4,125 tokens an expert the products are bound
by compute, not by bytes (2,560 x 768 weights of an expert are read once for
thousands of rows), so the roofline is operations over peak. The scope's
time holds the forwards rematerialised in the backward pass, which are not
required work: with both remats on, 3 of every 5 products executed count.
Source: device trace. Layer: sparse-expert trunk. Moves
``train_samples_per_s``."""

from chipbench import flops_moe_trunk, trace_scopes


def read(run: dict):
    ms = trace_scopes.scope_ms_per_step(run, ("moe_experts",))
    if not ms or not run.get("peaks") or not run.get("trunk"):
        return None
    least_s = (flops_moe_trunk.experts_flops_per_step(run["shapes"], run["trunk"])
               / (run["peaks"]["bf16_flops_per_s"] * run["device"]["count"]))
    return 100.0 * least_s / (ms / 1e3)
