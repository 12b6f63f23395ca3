"""Host time of a round's EPILOGUE, with the chip idle: the round loop's
``round_epilogue`` spans in the traced rounds, their mean in ms. The span
runs from ``round_end``'s close to ``fed_round``'s: the round's kept device
arrays let go (the round loop's frame dies here), the round's evaluation
when one is due (a span of its own inside this one; no cell runs one), the
device-memory sample, the perf monitor's components, the one digest of the
round's spans. A program whose round loop has no such span gives nothing to
read. Source: program spans. Layer: round loop. Moves
``train_samples_per_s``."""


def read(run: dict):
    spans = [s for s in run.get("traced_spans") or [] if s["name"] == "round_epilogue"]
    if not spans:
        return None
    return sum(s["end_ns"] - s["start_ns"] for s in spans) / 1e6 / len(spans)
