"""Seeded inputs of a training cell: the click corpus, the token-state
catalog and the model's first weights, all made from ``--seed``.

One general generator reads a traffic file (``chipbench/traffic/*.json``)
and a configuration's ``shapes``; a new traffic mix is a new data file.
The program receives only what is generated: a ``MindData`` record, the
device-resident table and the weights.

The corpus and table generators are copies, kept here so that no later PR
can change them, of ``fedrec_tpu/data/mind.py: make_synthetic_mind`` and
``fedrec_tpu/cli/run.py: random_token_states`` at commit 4ba1c0d, with
these changes: driven by the seed; vectorised; every seed draws the same
sizes (history length and negative-pool size are fixed by the traffic
file, only the ids differ); clicks follow the traffic file's popularity
law, not the uniform draw of the original; the table is filled by one
jitted call.
"""

from __future__ import annotations

import numpy as np


def seed_words(seed: int, n: int = 4) -> list[int]:
    """``n`` non-negative 31-bit words from any whole-number seed (seeds a
    little over 2**31 do not fit the int32 that PRNGKey takes)."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(w) & 0x7FFFFFFF for w in state]


def popularity_law(traffic: dict, n_ranks: int) -> np.ndarray:
    """The traffic file's popularity law as probabilities of ranks 1..n_ranks:
    Zipf-Mandelbrot, p(r) proportional to 1 / (r + offset) ** exponent
    (exponent 0 is the uniform law)."""
    law = traffic["popularity"]
    if law["law"] != "zipf_mandelbrot":
        raise ValueError(f"unknown popularity law {law['law']!r}")
    r = np.arange(1, n_ranks + 1, dtype=np.float64)
    p = (r + float(law["offset"])) ** -float(law["exponent"])
    return p / p.sum()


def _draw_ranks(rng, cdf: np.ndarray, shape) -> np.ndarray:
    """0-based ranks by inversion of the law's cumulative distribution."""
    return np.minimum(np.searchsorted(cdf, rng.random(shape), side="right"), len(cdf) - 1)


def _first_distinct(draws: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Per row the first ``k`` distinct values in order of appearance, and
    which rows held ``k`` of them."""
    n, m = draws.shape
    order = np.argsort(draws, axis=1, kind="stable")
    ranked = np.take_along_axis(draws, order, axis=1)
    first_sorted = np.ones((n, m), dtype=bool)
    first_sorted[:, 1:] = ranked[:, 1:] != ranked[:, :-1]
    first = np.zeros((n, m), dtype=bool)
    np.put_along_axis(first, order, first_sorted, axis=1)
    front = np.argsort(~first, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(draws, front, axis=1), first.sum(axis=1) >= k


def make_click_corpus(traffic: dict, shapes: dict, seed: int) -> dict:
    """The corpus as plain arrays and the reference's record format.

    Returns ``news_tokens`` (N, 2, L) int64, ``nid2index``, and
    ``train_samples`` as ``[uidx, pos_nid, neg_nids, history_nids, uid]``.
    Row 0 is ``<unk>``. A seeded permutation gives every news its popularity
    rank (a catalog's row order says nothing of popularity). Clicks follow
    the traffic file's popularity law: a history is ``history_len`` distinct
    news drawn by it without replacement (a user clicks a news once), the
    positive one more draw; the negatives of an impression are uniform over
    the rest of the catalog. The ``popular_frac`` most popular rows are
    marked in ``popular_rows``: their token states share one offset, a
    signal the towers can learn.
    """
    n_news = int(traffic["num_news"])
    n_samples = int(traffic["samples_per_round"])
    his_len = int(traffic["history_len"])
    pool = int(traffic["negative_pool"])
    title_len = int(shapes["title_len"])
    n_popular = max(1, int(traffic["popular_frac"] * n_news))
    if his_len != shapes["history"]:
        raise ValueError("traffic history_len differs from the configuration's history")
    if n_news - 1 < max(2 * his_len, 3):
        raise ValueError("the catalog is too small for distinct histories")
    rng = np.random.default_rng(seed_words(seed, 4))

    news_tokens = np.zeros((n_news, 2, title_len), dtype=np.int64)
    news_tokens[1:, 0, :] = rng.integers(1000, 30522, size=(n_news - 1, title_len))
    news_tokens[1:, 1, :] = 1

    rank_to_id = 1 + rng.permutation(n_news - 1)
    cdf = np.cumsum(popularity_law(traffic, n_news - 1))
    history = np.zeros((n_samples, his_len), dtype=np.int64)
    todo = np.arange(n_samples)
    while todo.size:          # rows short of distinct clicks draw again
        rows, full = _first_distinct(_draw_ranks(rng, cdf, (todo.size, 2 * his_len + 16)), his_len)
        history[todo[full]] = rank_to_id[rows[full]]
        todo = todo[~full]
    pos = rank_to_id[_draw_ranks(rng, cdf, n_samples)]
    negs = rng.integers(1, n_news - 1, size=(n_samples, pool))
    negs += negs >= pos[:, None]          # uniform over every id but the positive
    popular_rows = np.zeros(n_news, dtype=bool)
    popular_rows[rank_to_id[:n_popular]] = True

    nids = np.array(["<unk>"] + [f"N{i}" for i in range(1, n_news)], dtype=object)
    nid2index = {nid: i for i, nid in enumerate(nids)}
    his_nids, neg_nids, pos_nids = nids[history], nids[negs], nids[pos]
    samples = [
        [s, pos_nids[s], list(neg_nids[s]), list(his_nids[s]), f"U{s}"]
        for s in range(n_samples)
    ]
    return {
        "news_tokens": news_tokens, "nid2index": nid2index,
        "train_samples": samples,
        "pos": pos, "negs": negs, "history": history, "popular_rows": popular_rows,
    }


def distinct_share(batches: list) -> float:
    """Mean over steps and clients of (distinct news ids) / (news slots) in
    what a client-step was fed: ``candidates`` (K, B, C) and ``history``
    (K, B, H) per step."""
    shares = []
    for b in batches:
        cand, his = np.asarray(b["candidates"]), np.asarray(b["history"])
        for c in range(cand.shape[0]):
            ids = np.concatenate([cand[c].reshape(-1), his[c].reshape(-1)])
            shares.append(np.unique(ids).size / ids.size)
    return float(np.mean(shares))


def make_token_states(traffic: dict, shapes: dict, seed: int, dtype, popular_rows):
    """Random ``(N, L, Dh)`` trunk token states, made on the device in the
    table's dtype by one jitted call, 2,048 rows at a time inside it (a
    float32 draw of the whole MIND-small table would be 10 GB). The rows
    marked in ``popular_rows`` (N,) share one offset direction, as the
    popular rows of ``random_token_states`` do."""
    import jax
    import jax.numpy as jnp

    n_news = int(traffic["num_news"])
    title_len, hidden = int(shapes["title_len"]), int(shapes["bert_hidden"])
    chunk = min(2048, n_news)
    if n_news % chunk:
        raise ValueError(f"num_news {n_news} is not a multiple of {chunk}")
    w = seed_words(seed, 4)

    @jax.jit
    def build(k0, k1, popular):
        root = jax.random.fold_in(jax.random.PRNGKey(k0), k1)
        offset = jax.random.normal(jax.random.fold_in(root, n_news), (hidden,))
        offset = offset / jnp.linalg.norm(offset) * jnp.sqrt(hidden / 8.0)

        def one(start_and_mark):
            start, marked = start_and_mark
            block = jax.random.normal(
                jax.random.fold_in(root, start), (chunk, title_len, hidden)
            )
            rows = start + jnp.arange(chunk)
            block = block + jnp.where(marked[:, None, None], offset, 0.0)
            block = jnp.where((rows == 0)[:, None, None], 0.0, block)
            return block.astype(dtype)

        starts = jnp.arange(0, n_news, chunk, dtype=jnp.int32)
        marks = popular.reshape(-1, chunk)
        return jax.lax.map(one, (starts, marks)).reshape(n_news, title_len, hidden)

    return build(jnp.int32(w[0]), jnp.int32(w[1]), jnp.asarray(popular_rows, dtype=bool))


def make_weights(shapes: dict, seed: int):
    """The model's first weights as float32 trees under the parameter names
    of the two towers, made on the device by one jitted call. Kernels are
    normal with variance 1/fan_in; biases are small and NOT zero, so that
    every bias path carries a gradient the reference can be compared on."""
    import jax
    import jax.numpy as jnp

    Dh, A, D = shapes["bert_hidden"], shapes["attn_hidden"], shapes["news_dim"]
    d_att = shapes["heads"] * shapes["head_dim"]
    Q = shapes["query_dim"]
    dense_shapes = {
        ("news", "pool", "att_fc1"): (Dh, A),
        ("news", "pool", "att_fc2"): (A, 1),
        ("news", "fc"): (Dh, D),
        ("user", "self_attn", "w_q"): (D, d_att),
        ("user", "self_attn", "w_k"): (D, d_att),
        ("user", "self_attn", "w_v"): (D, d_att),
        ("user", "pool", "att_fc1"): (d_att, Q),
        ("user", "pool", "att_fc2"): (Q, 1),
    }
    w = seed_words(seed, 4)

    @jax.jit
    def build(k2, k3):
        root = jax.random.fold_in(jax.random.PRNGKey(k2), k3)
        trees: dict = {"news": {}, "user": {}}
        for i, (path, (fan_in, fan_out)) in enumerate(dense_shapes.items()):
            kk, kb = jax.random.split(jax.random.fold_in(root, i))
            leaf = {
                "kernel": jax.random.normal(kk, (fan_in, fan_out)) / jnp.sqrt(fan_in),
                "bias": 0.02 * jax.random.normal(kb, (fan_out,)),
            }
            node = trees
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf
        return trees["user"], trees["news"]

    return build(jnp.int32(w[2]), jnp.int32(w[3]))
