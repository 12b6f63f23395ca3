"""Device time of the round-end parameter sync's XLA module, per round.
Source: device trace, line ``XLA Modules``. Layer: round-end sync. Moves
``train_samples_per_s``. Only cells whose configuration averages parameters
at the end of a round have it."""


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    m = trace["modules"].get(run["module_names"].get("param_sync"))
    if not m or not m["count"]:
        return None
    return m["seconds"] / m["count"] * 1e3
