"""Operations one train step requires when the news tower is the gated
grouped-query trunk with window and full layers, from the configuration's
shapes alone (``shapes`` and the trunk group ``corpus_window.trunk_of`` reads
off the file). The yardstick behind ``window_trunk_step_mfu_pct`` and
``attention_core_roofline_pct``; it reads nothing of the program (no block
size, no chunk, no remat, no cap on distinct news).

Every one of the ``B * (C + H)`` news slots is one text of ``L`` tokens
through the trunk. A multiply-add is 2 operations. The whole tower trains
(the embedding too), so every product needs the forward and both gradients:
x3. Recomputed forwards (remat) are not required work and are not counted.

Per token and layer ``l`` of ``H_l`` query heads, forward:

  projections  q and the output at H_l heads, k and v at kv_heads, the gate:
               2 d (2 H_l hd + 2 kv hd + H_l)
  core         the BAND's (query, key) pairs only, each 2 hd for the score
               and 2 hd for the context, a head: a full layer's text has
               L (L + 1) / 2 pairs, a window layer's sum_i min(i + 1, window)
               (393,472 of the 524,800 at L = 1,024, window 512)
  dense        layers before ``dense_layers``: 3 products of 2 d dense_dim
  router       the others: 2 d experts
  shared       3 products of 2 d shared_dim
  experts      3 products of 2 d expert_dim over the (token, choice) pairs
               that fall on held experts, at their EXPECTED count under a
               uniform router (the measured count is the program's counter)

Head and user tower as ``flops_moe_trunk.py`` counts them. Elementwise work
(norms, rotary, softmax, softplus, silu, Adam) and the gathers are not
counted. The grouped products' operations and bytes are
``flops_latent_trunk.py``'s (the same three products over the same kind of
held share; it reads the trunk group's ``dim``, ``expert_dim``, ``experts``,
``experts_held``, ``experts_per_token``, ``layers`` and ``dense_layers``).

``core_bytes_per_step``: what the core must move at the least, for the
roofline's memory side: forward it reads q, k, v and writes the context;
backward it reads q, k, v, the context and its cotangent and writes the
three gradients; bfloat16. No score ever has to leave the chip's fast memory.
"""

from __future__ import annotations

from chipbench import flops
from chipbench.flops_latent_trunk import (  # noqa: F401 - the metrics' readers take them from here
    experts_bytes_per_step, experts_flops_per_step,
)
from chipbench.flops_moe_trunk import head_flops_per_slot, tokens_per_step

BF16 = 2


def band_pairs(length: int, window: int | None) -> int:
    """(query, key) pairs of one text a head: key t <= query i, and within
    ``window`` of it where there is one."""
    if window is None or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def _window_of(t: dict, layer: int) -> int | None:
    return t["sliding_window"] if t["layer_kinds"][layer] == "window" else None


def layer_flops_per_token(t: dict, layer: int, title_len: int) -> dict:
    """Forward operations of layer ``layer`` for one token, by part."""
    d, hd, heads, kv = t["dim"], t["head_dim"], t["heads_per_layer"][layer], t["kv_heads"]
    parts = {
        "projections": 2.0 * d * (2 * heads * hd + 2 * kv * hd + heads),
        "core": heads * band_pairs(title_len, _window_of(t, layer)) / title_len * 4.0 * hd,
    }
    if layer < t["dense_layers"]:
        parts["dense"] = 3 * 2.0 * d * t["dense_dim"]
        return parts
    parts["router"] = 2.0 * d * t["experts"]
    parts["shared"] = 3 * 2.0 * d * t["shared_dim"]
    parts["experts"] = 3 * 2.0 * d * t["expert_dim"] * t["experts_per_token"] * t["experts_held"] / t["experts"]
    return parts


def trunk_flops_per_token(t: dict, title_len: int) -> float:
    """Forward operations of all held layers for one token."""
    return sum(sum(layer_flops_per_token(t, layer, title_len).values()) for layer in range(t["layers"]))


def core_flops_per_step(shapes: dict, trunk: dict) -> float:
    """The attention core alone (scores and context over the band's pairs):
    forward and both gradients, all layers."""
    per_token = sum(layer_flops_per_token(trunk, layer, shapes["title_len"])["core"]
                    for layer in range(trunk["layers"]))
    return 3.0 * per_token * tokens_per_step(shapes)


def core_bytes_per_step(shapes: dict, trunk: dict) -> float:
    """Bytes the core moves at the least: 6 passes over a query-wide array
    (q and the context forward; q, the context, its cotangent and q's
    gradient backward) and 6 over a key/value-wide one, bfloat16, a layer."""
    t = trunk
    per_token = sum(6 * (heads + t["kv_heads"]) * t["head_dim"] * BF16 for heads in t["heads_per_layer"])
    return float(per_token) * tokens_per_step(shapes)


def train_step_flops(shapes: dict, trunk: dict) -> float:
    """Required operations of ONE step of the whole cell (all clients)."""
    s = shapes
    if s["bert_hidden"] != trunk["dim"] or s["attn_hidden"] * 2 != trunk["dim"]:
        raise ValueError("the head's widths are not the trunk's")
    slots = s["clients"] * s["batch_per_client"] * (s["candidates"] + s["history"])
    return (3.0 * trunk_flops_per_token(trunk, s["title_len"]) * tokens_per_step(s)
            + 3.0 * head_flops_per_slot(s) * slots
            + flops.user_tower_flops_per_sample(s) * flops.samples_per_step(s))
