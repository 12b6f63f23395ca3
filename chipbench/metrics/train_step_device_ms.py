"""Device time of one execution of the XLA module the train step compiles
to (mean over the traced rounds' executions and over chips). Source: device
trace, line ``XLA Modules``. Layer: train step. Moves
``train_samples_per_s``."""


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    m = trace["modules"].get(run["module_names"].get("train_step"))
    if not m or not m["count"]:
        return None
    return m["seconds"] / m["count"] * 1e3
