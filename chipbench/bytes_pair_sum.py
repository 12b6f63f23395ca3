"""Bytes the expert layer's pair sum (``fedrec_tpu/ops/pair_sum.py``) must
move in one train step, from the configuration's shapes, the trunk group
and the share of (token, choice) pairs that fell on held experts by the
program's own gauge (``moe.absent_share`` of the last round). The yardstick
behind ``pair_sum_roofline_pct``; it reads nothing of the program's blocks,
copies or buffer sizes.

A routed layer runs two sums over every chunk of its tokens, which together
are all ``tokens_per_step`` tokens: the combine's forward (the experts'
outputs weighted by the router) and the route's backward (the rows'
cotangents back to their tokens). Each reads the rows of the pairs on held
experts, ``k x held_share`` a token of ``d`` elements, reads ``k`` int32 row
indices a token, and writes one row a token; the combine also reads ``k``
float32 weights a token. bfloat16 rows. A forward rematerialised in the
backward pass is not required work and is not counted.
"""

from __future__ import annotations

from chipbench.flops_moe_trunk import tokens_per_step

BF16 = 2
INT32 = FLOAT32 = 4


def routed_layers(trunk: dict) -> int:
    """Layers with an expert layer: all but the leading dense ones."""
    return trunk["layers"] - trunk.get("dense_layers", 0)


def bytes_per_token(trunk: dict, held_share: float) -> dict:
    """Bytes of one token through each of the two sums of one layer."""
    k, d = trunk["experts_per_token"], trunk["dim"]
    one = k * held_share * d * BF16 + d * BF16 + k * INT32
    return {"combine": one + k * FLOAT32, "route_backward": one}


def bytes_per_step(shapes: dict, trunk: dict, held_share: float) -> float:
    """Both sums, every routed layer, every token of the step."""
    per_token = sum(bytes_per_token(trunk, held_share).values())
    return float(per_token) * routed_layers(trunk) * tokens_per_step(shapes)
