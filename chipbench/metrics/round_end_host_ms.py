"""Host time of a round's END, with the chip idle: the round loop's
``round_end`` spans in the traced rounds, their mean in ms. The span opens
once the round's last device program is known done and closes when the
round's bookkeeping is over (the one read of the steps' metrics, the loss,
the health digest, the routing counters), so the device waits for all of
it; the idleness under it reads as ``round_other`` in ``breakdown``. A
program whose round loop has no such span gives nothing to read. Source:
program spans. Layer: round loop. Moves ``train_samples_per_s``."""


def read(run: dict):
    spans = [s for s in run.get("traced_spans") or [] if s["name"] == "round_end"]
    if not spans:
        return None
    return sum(s["end_ns"] - s["start_ns"] for s in spans) / 1e6 / len(spans)
