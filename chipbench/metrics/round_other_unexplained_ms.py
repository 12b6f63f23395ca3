"""Device idleness inside a round that no chip-idle span accounts for: the
trace's ``idle_by_host_activity["round_other"]`` (idle seconds of the traced
window inside ``fed_round`` and under none of ``batch_build`` / ``h2d`` /
``dispatch`` / ``aggregate``) per traced round, less the mean per round of
the ``round_prologue`` + ``round_end`` + ``round_epilogue`` spans, in ms.
The chip is idle under all three by construction: the round before ended in
``block_until_ready``, so its queue is empty until the first step is
dispatched. Signed: positive is idleness under some other host code
(``step_keep``, ``device_wait``, what no span names), negative a device
that was still busy under one of the three. The one number that joins the
device's clock and the program's. A program without the spans gives nothing
to read. Source: program spans (and the device trace's gaps). Layer: round
loop. Moves ``train_samples_per_s``."""

NAMES = ("round_prologue", "round_end", "round_epilogue")


def read(run: dict):
    trace, spans = run.get("trace"), run.get("traced_spans") or []
    rounds = sum(1 for s in spans if s["name"] == "fed_round")
    if not trace or not rounds or not any(s["name"] == "round_prologue" for s in spans):
        return None
    idle_ns = trace["idle_by_host_activity"].get("round_other", 0.0) * 1e9
    named_ns = sum(s["end_ns"] - s["start_ns"] for s in spans if s["name"] in NAMES)
    return (idle_ns - named_ns) / 1e6 / rounds
