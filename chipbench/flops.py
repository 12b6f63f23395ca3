"""Operations one train step requires, from the cell's published shapes alone.

The yardstick behind ``train_step_mfu_pct``. It reads the ``shapes`` of
the configuration file and nothing of the program: no cap on distinct
news, no dispatch mode, no knob a later PR may turn. A change to how many
news slots the compiled step really encodes therefore does not move it.

Every one of the ``B * (C + H)`` news slots of a client's batch counts as
one title to encode. Duplicate news inside a batch make that an
over-count of the strictly necessary work (a step that encodes each
distinct news once does less); "count distinct news per client-step from
the traffic" is an open question in ``PERF.md``.

What is counted, per client and step (a multiply-add is 2 operations):

  text head, per news slot (the trunk's token states are frozen inputs,
  so no gradient flows into them):
    fc1   L x Dh x A        forward + weight gradient           x2
    fc2   L x A x 1         forward + weight + input gradient   x3
    pool  L x Dh            forward + gradient of the weights   x2
    fc    Dh x D            forward + weight + input gradient   x3
  user tower, per sample (its inputs are the text head's outputs, so
  every matmul needs both gradients):                           x3
    q/k/v 3 x H x D x D; scores and context 2 x heads x H x H x dk;
    pool  H x D x Q, H x Q, H x D; score C x D

This differs from ``fedrec_tpu/obs/perf.py: flops_per_train_step`` (commit
4ba1c0d), which multiplies the whole forward by 3 and reads the program's
unique-news cap: the x3 charges the step for an input gradient of fc1 that
no implementation needs (the table is frozen), which is 98% of the text
head and would put 1.46x the required work under the name of a
utilisation. Elementwise work (tanh, exp, Adam) is not counted.
"""

from __future__ import annotations

SHAPE_KEYS = (
    "clients", "batch_per_client", "candidates", "history", "title_len",
    "bert_hidden", "attn_hidden", "news_dim", "heads", "head_dim", "query_dim",
)


def text_head_flops_per_slot(s: dict) -> float:
    L, Dh, A, D = s["title_len"], s["bert_hidden"], s["attn_hidden"], s["news_dim"]
    fc1 = 2 * L * Dh * A
    fc2 = 2 * L * A
    pool = 2 * L * Dh
    fc = 2 * Dh * D
    return 2 * fc1 + 3 * fc2 + 2 * pool + 3 * fc


def user_tower_flops_per_sample(s: dict) -> float:
    H, D, C, Q = s["history"], s["news_dim"], s["candidates"], s["query_dim"]
    heads, dk = s["heads"], s["head_dim"]
    qkv = 3 * 2 * H * D * (heads * dk)
    attn = 2 * 2 * heads * H * H * dk
    pool = 2 * H * (heads * dk) * Q + 2 * H * Q + 2 * H * (heads * dk)
    score = 2 * C * D
    return 3 * (qkv + attn + pool + score)


def train_step_flops(shapes: dict) -> float:
    """Required operations of ONE step of the whole cell (all clients)."""
    missing = [k for k in SHAPE_KEYS if k not in shapes]
    if missing:
        raise KeyError(f"configuration shapes lack {missing}")
    s = shapes
    B = s["batch_per_client"]
    slots = B * (s["candidates"] + s["history"])
    per_client = slots * text_head_flops_per_slot(s) + B * user_tower_flops_per_sample(s)
    return float(s["clients"] * per_client)


def samples_per_step(shapes: dict) -> int:
    return int(shapes["clients"] * shapes["batch_per_client"])
