"""Seeded inputs of a training cell whose news tower reads token ids
(``kind: training_rounds_tokens``): the token-id table and the first
weights of a sparse-expert trunk, made from ``--seed``. The click corpus,
the head's and the user tower's first weights are ``corpus.py``'s.

``trunk_of`` reads the trunk's sizes off the configuration file: the
published keys of the model's ``config.json`` at its top level (widths
unchanged; the counts of layers, experts and vocabulary rows are what is
HELD here, the published counts stand under ``published``) and the
deployment's share under ``held``. The reference and the operation counts
take the trunk from it and from nothing of the program.
"""

from __future__ import annotations

import numpy as np

from chipbench import corpus


def trunk_of(config: dict) -> dict:
    """The trunk group ``reference_moe_trunk.py`` and ``flops_moe_trunk.py``
    read, from the configuration file's published keys."""
    period = _period(config["sliding_window_layout"], config["rope_layout"])
    held = config["held"]
    return {
        "dim": int(config["hidden_size"]), "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]), "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "experts": int(config["published"]["moe_num_primary_experts"]),
        "experts_per_token": int(config["moe_num_active_primary_experts"]),
        "expert_dim": int(config["moe_ffn_hidden_size"]),
        "rms_norm_eps": float(config["rms_norm_eps"]), "rope_theta": float(config["rope_theta"]),
        "sliding_window": int(config["sliding_window_size"]), "global_every": period,
        "first_expert": int(held["first_expert"]), "experts_held": int(config["moe_num_primary_experts"]),
        "vocab_first": int(held["vocab_first"]), "vocab_held": int(config["vocab_size"]),
    }


def _period(window_layout: list, rope_layout: list) -> int:
    """Layers come in periods of one global layer (0: full causal mask, no
    positional encoding) and ``period - 1`` sliding ones (1: window, rotary)."""
    if list(window_layout) != list(rope_layout) or window_layout[0] != 0:
        raise ValueError("the reference knows layers that are global and position-free, or sliding and rotary")
    period = 1 + next((i for i, v in enumerate(window_layout[1:]) if v == 0), len(window_layout) - 1)
    if any(v != (0 if i % period == 0 else 1) for i, v in enumerate(window_layout)):
        raise ValueError("the layer layout is not one global layer every period")
    return period


def make_token_table(traffic: dict, shapes: dict, trunk: dict, seed: int) -> np.ndarray:
    """(N, 2, L) int64 [token ids; attention mask]: ids uniform over the held
    vocabulary rows, every title full length (the traffic file's
    ``token_ids``)."""
    if traffic["token_ids"] != {"law": "uniform_over_held_rows", "mask": "full"}:
        raise ValueError(f"unknown token_ids {traffic['token_ids']!r}")
    n_news, title_len = int(traffic["num_news"]), int(shapes["title_len"])
    rng = np.random.default_rng(corpus.seed_words(seed, 8)[6:])
    table = np.ones((n_news, 2, title_len), dtype=np.int64)
    table[:, 0, :] = rng.integers(trunk["vocab_first"], trunk["vocab_first"] + trunk["vocab_held"],
                                  size=(n_news, title_len))
    return table


def make_weights(shapes: dict, trunk: dict, seed: int):
    """First weights as float32 trees: the user tower, and the news tower
    ``{"trunk", "head"}`` under the program's parameter names. Kernels are
    normal with variance 1/fan_in, the embedding normal(0, 1), the norms'
    scales 1 + 0.1 normal (not 1, so that each carries a gradient that is
    not its input's alone)."""
    import jax
    import jax.numpy as jnp

    user, head = corpus.make_weights(shapes, seed)
    t = trunk
    d, f, held = t["dim"], t["expert_dim"], t["experts_held"]
    q, kv = t["heads"] * t["head_dim"], t["kv_heads"] * t["head_dim"]
    w = corpus.seed_words(seed, 10)

    @jax.jit
    def build(k0, k1):
        root = jax.random.fold_in(jax.random.PRNGKey(k0), k1)
        count = iter(range(1 << 20))
        normal = lambda shape, std=1.0: std * jax.random.normal(  # noqa: E731
            jax.random.fold_in(root, next(count)), shape)
        kernel = lambda *shape: normal(shape, shape[-2] ** -0.5)  # noqa: E731
        scale = lambda: 1.0 + normal((d,), 0.1)  # noqa: E731
        out = {"embedding": normal((t["vocab_held"], d)), "final_norm": {"scale": scale()}}
        for layer in range(t["layers"]):
            out[f"layer_{layer}"] = {
                "attn_norm": {"scale": scale()}, "ffn_norm": {"scale": scale()},
                "router": kernel(d, t["experts"]),
                "attn": {"q_proj": {"kernel": kernel(d, q)}, "k_proj": {"kernel": kernel(d, kv)},
                         "v_proj": {"kernel": kernel(d, kv)}, "o_proj": {"kernel": kernel(q, d)}},
                "experts": {"w_gate": kernel(held, d, f), "w_up": kernel(held, d, f),
                            "w_down": kernel(held, f, d)},
            }
        return out

    return user, {"trunk": build(jnp.int32(w[8]), jnp.int32(w[9])), "head": head}
