"""Share of the train step's device time under none of the scopes the
harness reduces by: 100 x ``trace["scopes"][""]`` (device seconds of the
traced window whose op path names no scope of the harness's ``SCOPES``) over
the step module's seconds in the window. The gauge of what a per-scope
metric cannot see: it falls when the harness's list grows to the scopes the
program names (``fedrec_tpu/train/step.py: DEVICE_SCOPES``). A run whose
harness reduces no scopes gives nothing to read. Source: device trace (ops'
metadata, by innermost named scope). Layer: train step. Moves
``train_samples_per_s``."""


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("scopes"):
        return None
    m = trace["modules"].get(run["module_names"].get("train_step"))
    if not m or not m["seconds"]:
        return None
    return 100.0 * trace["scopes"].get("", 0.0) / m["seconds"]
