"""The blocked attention core's share of its roofline, whatever implements
it: the least time the chip could take for what the core requires in a step
(``chipbench/flops_window_trunk.py``: scores and context over the causal
band's pairs only, forward and both gradients, nothing recomputed; reading
q, k, v, writing the context, and the backward's like), divided by the
device time of the scope ``attention_core`` in a step. The least time is the
larger of operations over the bf16 peak and bytes over the published memory
bandwidth (``chipbench/peaks_memory.py``): at 1,024-token texts the
operations bound it (10.6e12 operations are 54 ms, 2.8e10 bytes 35 ms). The
scope's time holds the forwards rematerialised in the backward pass, the
score elements of visited blocks outside the band, and every pass an
implementation makes over its scores in memory, none of which is required
work. Source: device trace. Layer: window trunk. Moves
``train_samples_per_s``."""

from chipbench import flops_window_trunk, trace_scopes


def read(run: dict):
    ms = trace_scopes.scope_ms_per_step(run, ("attention_core",))
    if not (ms and run.get("peaks") and run.get("hbm_bytes_per_s") and run.get("trunk")):
        return None
    chips = run["device"]["count"]
    least_s = max(
        flops_window_trunk.core_flops_per_step(run["shapes"], run["trunk"])
        / (run["peaks"]["bf16_flops_per_s"] * chips),
        flops_window_trunk.core_bytes_per_step(run["shapes"], run["trunk"])
        / (run["hbm_bytes_per_s"] * chips),
    )
    return 100.0 * least_s / (ms / 1e3)
