"""Operations one train step requires when the news tower is the
latent-attention trunk with a multi-stream residual, from the
configuration's shapes alone (``shapes`` and the trunk group
``corpus_latent.trunk_of`` reads off the file). The yardstick behind
``latent_trunk_step_mfu_pct`` and ``latent_experts_roofline_pct``; it reads
nothing of the program (no cap on distinct news, no remat, no chunk size).

Every one of the ``B * (C + H)`` news slots is one title of ``L`` tokens
through the trunk. A multiply-add is 2 operations. The whole tower trains
(the embedding too), so every product needs the forward and both gradients:
x3. Recomputed forwards (remat) are not required work and are not counted.

Per token and layer, forward:

  projections  the two bottlenecks and the output: 2 x (d q_rank + q_rank
               heads (nope + rope) + d (kv_rank + rope) + kv_rank heads
               (nope + v) + heads v d)
  core         causal: a title's L (L + 1) / 2 (query, key) pairs, each
               2 (nope + rope) for the score and 2 v for the context, a head
  mixers       two a layer, each: the three maps 2 n d (n + n + n^2), the
               stream mixing 2 n^2 d, reading and writing back 2 x 2 n d
  dense        layers before ``dense_layers``: 3 products of 2 d dense_dim
  router       the others: 2 d experts
  shared       3 products of 2 d (shared_experts x expert_dim)
  experts      3 products of 2 d expert_dim over the (token, choice) pairs
               that fall on held experts, at their EXPECTED count under a
               uniform router: experts_per_token x experts_held / experts a
               token (the measured count is the ``moe.expert_tokens`` counter)

Head and user tower as ``flops_moe_trunk.py`` counts them. Elementwise work
(norms, rotary, softmax, silu, Sinkhorn's divisions, Adam) and the gathers
are not counted.

``experts_bytes_per_step``: what the grouped products must move, for the
roofline's memory side. At 344 rows an expert the weights no longer vanish
beside the rows: each of a product's three passes (forward, the rows'
gradient, the weights' gradient) reads two of {rows in, rows out, the held
experts' weights} and writes the third, in bfloat16.
"""

from __future__ import annotations

from chipbench import flops
from chipbench.flops_moe_trunk import head_flops_per_slot, tokens_per_step

BF16 = 2


def mixer_flops_per_token(t: dict) -> float:
    n, d = t["streams"], t["dim"]
    return 2.0 * n * d * (n + n + n * n) + 2.0 * n * n * d + 2 * 2.0 * n * d


def layer_flops_per_token(t: dict, title_len: int, routed: bool) -> dict:
    """Forward operations of one layer for one token, by part."""
    d, heads = t["dim"], t["heads"]
    qk, v = t["nope_dim"] + t["rope_dim"], t["v_dim"]
    parts = {
        "projections": 2.0 * (d * t["q_rank"] + t["q_rank"] * heads * qk + d * (t["kv_rank"] + t["rope_dim"])
                              + t["kv_rank"] * heads * (t["nope_dim"] + v) + heads * v * d),
        "core": heads * (title_len + 1) / 2.0 * (2.0 * qk + 2.0 * v),
        "mixers": 2 * mixer_flops_per_token(t),
    }
    if not routed:
        parts["dense"] = 3 * 2.0 * d * t["dense_dim"]
        return parts
    parts["router"] = 2.0 * d * t["experts"]
    parts["shared"] = 3 * 2.0 * d * t["shared_experts"] * t["expert_dim"]
    parts["experts"] = 3 * 2.0 * d * t["expert_dim"] * held_pairs_per_token(t)
    return parts


def held_pairs_per_token(t: dict, held_share: float | None = None) -> float:
    """(token, choice) pairs a token sends to held experts: ``held_share`` of
    its choices, by default the share a uniform router gives the held."""
    share = t["experts_held"] / t["experts"] if held_share is None else held_share
    return t["experts_per_token"] * share


def routed_layers(t: dict) -> int:
    return t["layers"] - t["dense_layers"]


def trunk_flops_per_token(t: dict, title_len: int) -> float:
    """Forward operations of all held layers for one token."""
    return (t["dense_layers"] * sum(layer_flops_per_token(t, title_len, False).values())
            + routed_layers(t) * sum(layer_flops_per_token(t, title_len, True).values()))


def experts_flops_per_step(shapes: dict, trunk: dict, held_share: float | None = None) -> float:
    """The grouped products alone: forward and both gradients, all routed
    layers; over the pairs expected on held experts, or over ``held_share``
    of all pairs where the program's counter gives the measured share."""
    t = trunk
    per_token = 3 * 2.0 * t["dim"] * t["expert_dim"] * held_pairs_per_token(t, held_share)
    return 3.0 * routed_layers(t) * per_token * tokens_per_step(shapes)


def experts_bytes_per_step(shapes: dict, trunk: dict, held_share: float | None = None) -> float:
    """Bytes the grouped products move at the least: 3 products x 3 passes a
    routed layer, each pass rows x (in + out) + held x in x out, bfloat16."""
    t = trunk
    rows = held_pairs_per_token(t, held_share) * tokens_per_step(shapes)
    one_pass = BF16 * (rows * (t["dim"] + t["expert_dim"]) + t["experts_held"] * t["dim"] * t["expert_dim"])
    return 9.0 * routed_layers(t) * one_pass


def train_step_flops(shapes: dict, trunk: dict) -> float:
    """Required operations of ONE step of the whole cell (all clients)."""
    s = shapes
    if s["bert_hidden"] != trunk["dim"] or s["attn_hidden"] * 2 != trunk["dim"]:
        raise ValueError("the head's widths are not the trunk's")
    slots = s["clients"] * s["batch_per_client"] * (s["candidates"] + s["history"])
    return (3.0 * trunk_flops_per_token(trunk, s["title_len"]) * tokens_per_step(s)
            + 3.0 * head_flops_per_slot(s) * slots
            + flops.user_tower_flops_per_sample(s) * flops.samples_per_step(s))
