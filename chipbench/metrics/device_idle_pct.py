"""Share of the traced window in which no op ran on the device: 1 - (union
of the op intervals) / window, the mean over chips. Source: device trace,
line ``XLA Ops``. Layer: device. Moves ``train_samples_per_s``."""


def read(run: dict):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
