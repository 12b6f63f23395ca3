"""Host time a step waits for its batch: the round loop's ``batch_build``
and ``h2d`` spans in the traced rounds, summed, per step. Source: program
spans. Layer: round loop. Moves ``train_samples_per_s``."""


def read(run: dict):
    spans = run.get("traced_spans")
    if not spans:
        return None
    steps = sum(1 for s in spans if s["name"] == "dispatch")
    build = sum(s["end_ns"] - s["start_ns"] for s in spans if s["name"] in ("batch_build", "h2d"))
    return build / 1e6 / steps if steps else None
