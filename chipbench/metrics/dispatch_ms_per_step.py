"""Host time to ENQUEUE a step: the round loop's ``dispatch`` spans in the
traced rounds, per step. It is the cost of the call into the compiled
program on the host (argument handling, donation, launch), not the device's
time: JAX returns before the device finishes. Source: program spans. Layer:
round loop. Moves ``train_samples_per_s``."""


def read(run: dict):
    spans = [s for s in run.get("traced_spans") or [] if s["name"] == "dispatch"]
    if not spans:
        return None
    return sum(s["end_ns"] - s["start_ns"] for s in spans) / 1e6 / len(spans)
