"""The whole step's share of the chip's peak in a cell whose news tower is
the gated grouped-query trunk with window and full layers, computed as
``trunk_step_mfu_pct`` is: the operations that the steps executed whole
inside the traced window require (``chipbench/flops_window_trunk.py``, from
the cell's shapes and the trunk's published sizes alone: the band's pairs
only in the attention core, recomputed forwards not counted) over the traced
window's wall time and the chips' bf16 peak. Host time and idle gaps are
inside the window, so it cannot pass 100%. Source: device trace. Layer:
window trunk. Moves ``train_samples_per_s``."""

from chipbench import flops_window_trunk


def read(run: dict):
    trace = run.get("trace")
    if not trace or not run.get("peaks") or not run.get("trunk"):
        return None
    m = trace["modules"].get(run["module_names"].get("train_step"))
    if not m or not m["count"]:
        return None
    done = flops_window_trunk.train_step_flops(run["shapes"], run["trunk"]) * m["count"]
    peak = run["peaks"]["bf16_flops_per_s"] * run["device"]["count"]
    return 100.0 * done / trace["window_s"] / peak
