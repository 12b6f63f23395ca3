"""News rows a client-step gathers and encodes, in per cent of its news
slots: 100 x the mean over the window's ``dispatch`` spans of ``rows`` /
``slots``, the two arguments the round loop puts on the span when it feeds
the step its host-side dedup (``rows`` is the compiled encode size R, or the
full size for a step whose distinct count exceeded it; ``slots`` is
B x (C + H), both per client). Beside ``distinct_news_pct``, which is the
traffic's own share, it shows the room the chosen size leaves. A program
whose ``dispatch`` spans carry no such arguments (the step dedups on the
device at the slot count) gives nothing to read. Source: program spans.
Layer: round loop. Moves ``train_samples_per_s``."""


def read(run: dict):
    shares = [
        s["args"]["rows"] / s["args"]["slots"]
        for s in run.get("spans") or []
        if s["name"] == "dispatch" and "rows" in s.get("args", {})
    ]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
