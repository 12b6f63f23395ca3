"""The whole step's share of the chip's peak: the operations that the
forward and backward passes of the steps that ran in the traced window
(counted from the trace: executions of the step's module) require
(``chipbench/flops.py``, from the cell's shapes alone) over the traced
window's wall time and the chips' bf16 peak (``chipbench/peaks.py``).
Host time, the round-end sync and idle gaps are inside the window, so this
bounds every kernel's claim: it cannot pass 100%. Source: device trace (the
window is the trace's own). Layer: train step. Moves
``train_samples_per_s``."""

from chipbench import flops


def read(run: dict):
    trace = run.get("trace")
    if not trace or not run.get("peaks"):
        return None
    m = trace["modules"].get(run["module_names"].get("train_step"))
    if not m or not m["count"]:
        return None
    # ``count``: executions of the step's module that lie whole inside the
    # traced window, the mean over chips
    done = flops.train_step_flops(run["shapes"]) * m["count"]
    peak = run["peaks"]["bf16_flops_per_s"] * run["device"]["count"]
    return 100.0 * done / trace["window_s"] / peak
