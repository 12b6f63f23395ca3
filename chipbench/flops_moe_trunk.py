"""Operations one train step requires when the news tower is a sparse-expert
decoder trunk, from the configuration's shapes alone (``shapes`` and the
trunk group ``corpus_tokens.trunk_of`` reads off the file). The yardstick
behind ``trunk_step_mfu_pct`` and ``moe_experts_roofline_pct``; it reads
nothing of the program (no cap on distinct news, no remat, no chunk size).

Every one of the ``B * (C + H)`` news slots is one title of ``L`` tokens
through the trunk. A multiply-add is 2 operations. The whole tower trains
(the embedding too), so every product needs the forward and both gradients:
x3. Recomputed forwards (remat) are not required work and are not counted.

Per token and layer, forward:

  q, k, v    2 d (heads + 2 kv_heads) head_dim
  o          2 heads head_dim d
  core       causal: a title's L (L + 1) / 2 (query, key) pairs, each
             2 head_dim for the score and 2 head_dim for the context, a
             query head; the window (4,096) never binds at L = 50
  router     2 d experts
  experts    3 products of 2 d expert_dim over the (token, choice) pairs
             that fall on held experts, at their EXPECTED count under a
             uniform router: experts_per_token x experts_held / experts a
             token (the measured count is the ``moe.expert_tokens`` counter;
             the share on absent experts is printed beside it)

Head, per slot: fc1 L x d x d/2, fc2 L x d/2, pool L x d, fc d x D, all x3
(the token states are now computed, so fc1 needs its input gradient too).
User tower, per sample: ``flops.user_tower_flops_per_sample``. Elementwise
work (norms, rotary, softmax, relu, Adam) and the gathers are not counted.
"""

from __future__ import annotations

from chipbench import flops


def layer_flops_per_token(t: dict, title_len: int) -> dict:
    """Forward operations of one layer for one token, by part."""
    d, hd = t["dim"], t["head_dim"]
    pairs_per_token = (title_len + 1) / 2.0
    return {
        "projections": 2.0 * d * (t["heads"] + 2 * t["kv_heads"]) * hd + 2.0 * t["heads"] * hd * d,
        "core": t["heads"] * pairs_per_token * 4.0 * hd,
        "router": 2.0 * d * t["experts"],
        "experts": 3 * 2.0 * d * t["expert_dim"] * t["experts_per_token"] * t["experts_held"] / t["experts"],
    }


def head_flops_per_slot(s: dict) -> float:
    L, d, a, D = s["title_len"], s["bert_hidden"], s["attn_hidden"], s["news_dim"]
    return 2.0 * L * d * a + 2.0 * L * a + 2.0 * L * d + 2.0 * d * D


def tokens_per_step(shapes: dict) -> int:
    s = shapes
    return s["clients"] * s["batch_per_client"] * (s["candidates"] + s["history"]) * s["title_len"]


def experts_flops_per_step(shapes: dict, trunk: dict) -> float:
    """The grouped products alone: forward and both gradients, all layers."""
    per_token = layer_flops_per_token(trunk, shapes["title_len"])["experts"]
    return 3.0 * trunk["layers"] * per_token * tokens_per_step(shapes)


def train_step_flops(shapes: dict, trunk: dict) -> float:
    """Required operations of ONE step of the whole cell (all clients)."""
    s = shapes
    if s["bert_hidden"] != trunk["dim"] or s["attn_hidden"] * 2 != trunk["dim"]:
        raise ValueError("the head's widths are not the trunk's")
    layer = sum(layer_flops_per_token(trunk, s["title_len"]).values())
    slots = s["clients"] * s["batch_per_client"] * (s["candidates"] + s["history"])
    samples = flops.samples_per_step(s)
    return (3.0 * trunk["layers"] * layer * tokens_per_step(s)
            + 3.0 * head_flops_per_slot(s) * slots
            + flops.user_tower_flops_per_sample(s) * samples)
