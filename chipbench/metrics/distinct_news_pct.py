"""Distinct news among the news slots of one client-step, in per cent: the
mean over the recorded steps and clients of (distinct ids) / (B x (C + H))
in the batches the round loop fed the compiled step. It is a property of
the traffic (its popularity law) as the batcher dealt it, not of the chip:
``100 - this`` is the share of the text head's slots that a dedup with a
tight cap would not have to encode, and the share by which
``train_step_mfu_pct``'s count of every slot exceeds the strictly necessary
work. Source: program counter (ids counted in what the program's batcher
fed). Layer: round loop. Moves ``train_samples_per_s``."""


def read(run: dict):
    share = run.get("distinct_news_share")
    return None if share is None else 100.0 * share
