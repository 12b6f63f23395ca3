"""Host time of a round's PROLOGUE, with the chip idle: the round loop's
``round_prologue`` spans in the traced rounds, their mean in ms. The span
opens with ``fed_round`` and closes where the round's first batch is asked
for (the round's weights and their read-back, the flight recorder's chunk,
the encode size, the table and the epoch's iterator); the previous round
ended in ``block_until_ready``, so the device waits for all of it, and the
idleness under it reads as ``round_other`` in ``breakdown``. A program whose
round loop has no such span gives nothing to read. Source: program spans.
Layer: round loop. Moves ``train_samples_per_s``."""


def read(run: dict):
    spans = [s for s in run.get("traced_spans") or [] if s["name"] == "round_prologue"]
    if not spans:
        return None
    return sum(s["end_ns"] - s["start_ns"] for s in spans) / 1e6 / len(spans)
