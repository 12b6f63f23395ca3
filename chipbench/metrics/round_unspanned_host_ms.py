"""Host time inside a round that the program's tracer cannot name: per
traced ``fed_round`` span, its duration less the union of the spans that
lie inside it (an interval under two spans counts once); the mean over the
traced rounds in ms. Read only from a program whose round loop tiles the
round (one that records ``round_prologue``): without the tiling the number
would be most of the round, and says nothing. Source: program spans. Layer:
round loop. Moves ``train_samples_per_s``."""

from chipbench import trace_reduce


def read(run: dict):
    spans = run.get("traced_spans") or []
    rounds = [s for s in spans if s["name"] == "fed_round"]
    if not rounds or not any(s["name"] == "round_prologue" for s in spans):
        return None
    left = 0.0
    for r in rounds:
        inside = [(s["start_ns"], s["end_ns"]) for s in spans
                  if s is not r and s["start_ns"] >= r["start_ns"] and s["end_ns"] <= r["end_ns"]]
        left += r["end_ns"] - r["start_ns"] - trace_reduce.total(trace_reduce.union(inside))
    return left / 1e6 / len(rounds)
