"""Seeded inputs of a training cell whose news tower is the latent-attention
trunk with a multi-stream residual (``kind: training_rounds_tokens_latent``):
the first weights, made from ``--seed``; the token-id table is
``corpus_tokens.make_token_table``'s, the click corpus, the head's and the
user tower's first weights are ``corpus.py``'s.

``trunk_of`` reads the trunk's sizes off the configuration file: the
published keys of the model's ``config.json`` at its top level (widths
unchanged; the counts of layers, leading dense layers, routed experts and
vocabulary rows are what is HELD here, the published counts stand under
``published``) and the deployment's share under ``held``. The reference and
the operation counts take the trunk from it and from nothing of the program.
"""

from __future__ import annotations

import functools
import json

from chipbench import corpus
from chipbench.corpus_tokens import make_token_table  # noqa: F401 - the harness takes it from here


def trunk_of(config: dict) -> dict:
    """The trunk group ``reference_latent_trunk.py`` and
    ``flops_latent_trunk.py`` read, from the configuration file's published keys."""
    if (config["scoring_func"], config["topk_method"], config["n_group"], config["topk_group"]) != (
            "sigmoid", "noaux_tc", 1, 1):
        raise ValueError("the reference knows a sigmoid router with a selection bias and no groups")
    if not config["norm_topk_prob"] or config["hidden_act"] != "silu" or config["attention_bias"]:
        raise ValueError("the reference knows normalised weights, SwiGLU and no bias")
    if config["rope_scaling"]["type"] != "yarn" or config["moe_layer_freq"] != 1:
        raise ValueError("the reference knows YaRN frequencies and a routed layer at every depth")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention gives every query head a key/value head of its own")
    held = config["held"]
    return {
        "dim": int(config["hidden_size"]), "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]), "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]), "kv_rank": int(config["kv_lora_rank"]),
        "nope_dim": int(config["qk_nope_head_dim"]), "rope_dim": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]), "dense_dim": int(config["intermediate_size"]),
        "experts": int(config["published"]["n_routed_experts"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
        "expert_dim": int(config["moe_intermediate_size"]),
        "shared_experts": int(config["n_shared_experts"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "streams": int(config["hc_mult"]), "sinkhorn_iters": int(config["hc_sinkhorn_iters"]),
        "hc_eps": float(config["hc_eps"]),
        "res_clamp_min": float(config["mhc_h_res_clamp_min"]),
        "res_clamp_max": float(config["mhc_h_res_clamp_max"]),
        "rms_norm_eps": float(config["rms_norm_eps"]), "rope_theta": float(config["rope_theta"]),
        "rope": dict(config["rope_scaling"]),
        "first_expert": int(held["first_expert"]), "experts_held": int(config["n_routed_experts"]),
        "vocab_first": int(held["vocab_first"]), "vocab_held": int(config["vocab_size"]),
    }


# The selection bias's spread. Sigmoid scores of 64 experts lie about 0.025
# apart around a token's 4th and 5th, so a bias of normal(0, 0.02) changes
# about every second token's choice. ISSUE 33 wrote 0.1: then the eight held
# experts' biases decide how many of ALL choices fall on them (the share on
# absent experts read 0.81 to 0.91 from seed to seed for an even 0.875), the
# rows in the grouped products vary two-fold, and `train_samples_per_s`
# spread by 0.56% over ten seeds, more than half its bound (PERF.md, PR 33).
ROUTER_BIAS_STD = 0.02


def leaf_specs(trunk: dict) -> dict:
    """The trunk's parameter tree under the program's names, each leaf as
    (shape, standard deviation, mean) of the normal law it is drawn from."""
    t = trunk
    d, n, heads = t["dim"], t["streams"], t["heads"]
    kernel = lambda *shape: (shape, shape[-2] ** -0.5, 0.0)  # noqa: E731
    dense = lambda *shape: {"kernel": kernel(*shape)}  # noqa: E731
    scale = lambda width=d: {"scale": ((width,), 0.1, 1.0)}  # noqa: E731
    gated = lambda width: {"gate_proj": dense(d, width), "up_proj": dense(d, width),  # noqa: E731
                           "down_proj": dense(width, d)}
    mixer = lambda: {  # noqa: E731
        "norm": scale(n * d), "proj_pre": kernel(n * d, n), "proj_post": kernel(n * d, n),
        "proj_res": kernel(n * d, n * n), "alpha": ((3,), 0.1, 1.0),
        "bias_pre": ((n,), 0.5, 0.0), "bias_post": ((n,), 0.5, 0.0), "bias_res": ((n, n), 0.5, 0.0)}
    out = {"embedding": ((t["vocab_held"], d), 1.0, 0.0), "final_norm": scale()}
    for layer in range(t["layers"]):
        out[f"layer_{layer}_attn"] = {
            "mixer": mixer(), "norm": scale(),
            "attn": {"q_a_proj": dense(d, t["q_rank"]), "q_a_norm": scale(t["q_rank"]),
                     "q_b_proj": dense(t["q_rank"], heads * (t["nope_dim"] + t["rope_dim"])),
                     "kv_a_proj": dense(d, t["kv_rank"] + t["rope_dim"]),
                     "kv_a_norm": scale(t["kv_rank"]),
                     "kv_b_proj": dense(t["kv_rank"], heads * (t["nope_dim"] + t["v_dim"])),
                     "o_proj": dense(heads * t["v_dim"], d)},
        }
        if layer < t["dense_layers"]:
            ffn = gated(t["dense_dim"])
        else:
            f, held = t["expert_dim"], t["experts_held"]
            ffn = {"router": kernel(d, t["experts"]), "router_bias": ((t["experts"],), ROUTER_BIAS_STD, 0.0),
                   "experts": {"w_gate": kernel(held, d, f), "w_up": kernel(held, d, f),
                               "w_down": kernel(held, f, d)},
                   "shared_expert": gated(t["shared_experts"] * f)}
        out[f"layer_{layer}_ffn"] = {"mixer": mixer(), "norm": scale(), "ffn": ffn}
    return out


@functools.lru_cache(maxsize=2)
def _trunk_builder(trunk_json: str):
    """The compiled program that draws a trunk's leaves from two seed words,
    kept for the process: leaves of one shape are drawn together, one draw a
    shape and not one a leaf (170 draws took the chip's compiler a minute of
    every run)."""
    import jax

    specs = leaf_specs(json.loads(trunk_json))
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=lambda x: isinstance(x, tuple))
    by_shape: dict[tuple, list[int]] = {}
    for i, (shape, _, _) in enumerate(leaves):
        by_shape.setdefault(shape, []).append(i)

    @jax.jit
    def build(k0, k1):
        root = jax.random.fold_in(jax.random.PRNGKey(k0), k1)
        out = [None] * len(leaves)
        for g, (shape, members) in enumerate(by_shape.items()):
            drawn = jax.random.normal(jax.random.fold_in(root, g), (len(members),) + shape)
            for row, i in enumerate(members):
                _, std, mean = leaves[i]
                out[i] = mean + std * drawn[row]
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def make_weights(shapes: dict, trunk: dict, seed: int):
    """First weights as float32 trees: the user tower, and the news tower
    ``{"trunk", "head"}`` under the program's parameter names. Kernels are
    normal with variance 1/fan_in (the mixers' three maps over the n*d
    vector too, so that the matrices differ from token to token), the
    embedding normal(0, 1), the norms' scales 1 + 0.1 normal, the mixers'
    scalars 1 + 0.1 normal and their biases normal(0, 0.5), the router's
    selection bias normal(0, ``ROUTER_BIAS_STD``)."""
    import jax.numpy as jnp

    user, head = corpus.make_weights(shapes, seed)
    w = corpus.seed_words(seed, 10)
    build = _trunk_builder(json.dumps(trunk, sort_keys=True))
    return user, {"trunk": build(jnp.int32(w[8]), jnp.int32(w[9])), "head": head}
