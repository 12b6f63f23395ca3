"""Score elements the window trunk's blocked attention core computes, over
the ``L^2`` of a head's dense square, in per cent: the program's gauge
``trunk.attention_scores_computed_share`` by kind of layer (a full layer's
blocks up to the diagonal, a window layer's blocks inside its band; whole
blocks, so above the band's own 50.0% and 37.5% at 1,024 tokens), weighted
by each held layer's query heads (``corpus_window.trunk_of``'s
``layer_kinds`` and ``heads_per_layer``). What the core's passes over its
scores cost by; a dense core reads 100. Source: program counter. Layer:
window trunk. Moves ``train_samples_per_s``."""


def read(run: dict):
    share, trunk = run.get("attention_share"), run.get("trunk")
    if not share or not trunk:
        return None
    heads = trunk["heads_per_layer"]
    computed = sum(h * share[kind] for h, kind in zip(heads, trunk["layer_kinds"]))
    return 100.0 * computed / sum(heads)
