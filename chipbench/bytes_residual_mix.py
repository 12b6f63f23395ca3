"""Bytes the latent trunk's residual mixers (the scope ``residual_mix``) must
move in one train step, from the configuration's shapes and the trunk group
alone. The yardstick behind ``residual_mix_roofline_pct``; it reads nothing
of the program's blocks, kernels or buffers, so it counts the same whatever
implements the layer.

A token's state is ``n`` streams of ``d``, bfloat16; the sublayer's input
``x_in`` is float32 (the configuration's ``precision``: the mixers run in
float32), its output ``out`` bfloat16. Around each of the ``2 x layers``
sublayers, once forward and once backward (a forward rematerialised in the
backward pass is not required work and is not counted), a token needs:

    forward, read-in:     X read (2nd)             x_in written (4d)
    forward, write-back:  X and out read (2nd + 2d)  X_next written (2nd)
    backward:             dX_next, X, out and dx_in read (2nd + 2nd + 2d + 4d)
                          dX and d_out written (2nd + 2d)

``(12 n + 14) d`` bytes: ``62 d`` at four streams. The per-token maps
(``2n + n^2`` floats) and the mixer's weights are left out: under a
hundredth of it. Before the first layer the embedding is tiled into the
streams and after the last they are summed: each reads one side and writes
the other, forward and backward, ``4 (n + 1) 2d`` bytes a token.
"""

from __future__ import annotations

from chipbench.flops_moe_trunk import tokens_per_step

BF16 = 2
FLOAT32 = 4


def sublayer_bytes_per_token(trunk: dict) -> dict:
    """Bytes of one token through one sublayer's mixer, by pass."""
    n, d = trunk["streams"], trunk["dim"]
    state = n * d * BF16
    return {
        "read_in": state + d * FLOAT32,
        "write_back": 2 * state + d * BF16,
        "backward": 3 * state + d * BF16 + d * FLOAT32 + d * BF16,
    }


def ends_bytes_per_token(trunk: dict) -> int:
    """The opening tile and the closing merge, forward and backward."""
    return 4 * (trunk["streams"] + 1) * trunk["dim"] * BF16


def bytes_per_step(shapes: dict, trunk: dict) -> float:
    """Every mixer of every layer, the tile and the merge, every token of
    the step."""
    per_token = (2 * trunk["layers"] * sum(sublayer_bytes_per_token(trunk).values())
                 + ends_bytes_per_token(trunk))
    return float(per_token) * tokens_per_step(shapes)
