"""Seeded inputs of a training cell whose news tower is the gated
grouped-query trunk with window and full layers (``kind:
training_rounds_tokens_window``): the first weights, made from ``--seed``;
the token-id table is ``corpus_tokens.make_token_table``'s, the click
corpus, the head's and the user tower's first weights are ``corpus.py``'s.

``trunk_of`` reads the trunk's sizes off the configuration file: the
published keys of the model's ``config.json`` at its top level (widths
unchanged; the counts of layers, routed experts and vocabulary rows are what
is HELD here, the published counts stand under ``published``) and the
deployment's share under ``held``. The reference and the operation counts
take the trunk from it and from nothing of the program.
"""

from __future__ import annotations

import functools
import json

from chipbench import corpus
from chipbench.corpus_tokens import make_token_table  # noqa: F401 - the harness takes it from here

KIND_OF = {"full_attention": "full", "sliding_attention": "window"}


def trunk_of(config: dict) -> dict:
    """The trunk group ``reference_window_trunk.py`` and
    ``flops_window_trunk.py`` read, from the configuration file's published keys."""
    layers = int(config["num_hidden_layers"])
    if not (len(config["layer_types"]) == len(config["mlp_layer_types"])
            == len(config["num_attention_heads_per_layer"]) == layers):
        raise ValueError("the three per-layer lists do not have one entry a held layer")
    if config["gating"] is not True or config["attention_bias"] or config["moe_apply_router_weight_on_input"]:
        raise ValueError("the reference knows a gate a head on attention's output, no bias, "
                         "and router weights on the experts' outputs")
    ffn = list(config["mlp_layer_types"])
    dense = next((i for i, kind in enumerate(ffn) if kind != "dense"), layers)
    if any(kind != "sparse" for kind in ffn[dense:]):
        raise ValueError("the reference knows leading dense layers, then routed ones")
    rope = config["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or window["rope_type"] != "default" or window["partial_rotary_factor"] != 1:
        raise ValueError("the reference knows YaRN in full layers and plain whole-head rotary in window layers")
    kinds = [KIND_OF[kind] for kind in config["layer_types"]]
    heads = [int(h) for h in config["num_attention_heads_per_layer"]]
    by_kind = {kind: {h for k, h in zip(kinds, heads) if k == kind} for kind in set(kinds)}
    if any(len(counts) != 1 for counts in by_kind.values()) or by_kind["full"] != {int(config["num_attention_heads"])}:
        raise ValueError("the reference knows one head count a kind of layer, the full layers' num_attention_heads")
    if config["shared_expert_intermediate_size"] % config["moe_intermediate_size"]:
        raise ValueError("the shared expert is not a whole number of routed experts wide")
    held = config["held"]
    return {
        "dim": int(config["hidden_size"]), "layers": layers, "dense_layers": dense,
        "layer_kinds": kinds, "heads_per_layer": heads,
        "kv_heads": int(config["num_key_value_heads"]), "head_dim": int(config["head_dim"]),
        "sliding_window": int(config["sliding_window"]), "dense_dim": int(config["intermediate_size"]),
        "experts": int(config["published"]["num_experts"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
        "expert_dim": int(config["moe_intermediate_size"]),
        "shared_dim": int(config["shared_expert_intermediate_size"]),
        "routed_scale": float(config["moe_routed_scaling_factor"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "window_rope_theta": float(window["rope_theta"]),
        "full_rope_theta": float(full["rope_theta"]),
        "full_rotary_share": float(full["partial_rotary_factor"]),
        "rope": {k: full[k] for k in ("factor", "original_max_position_embeddings", "beta_fast",
                                      "beta_slow", "attention_factor")},
        "first_expert": int(held["first_expert"]), "experts_held": int(config["num_experts"]),
        "vocab_first": int(held["vocab_first"]), "vocab_held": int(config["vocab_size"]),
    }


# The selection bias's spread, set as ``corpus_latent.ROUTER_BIAS_STD`` was:
# sigmoid scores of 256 experts lie about 0.005 apart around a token's 8th
# and 9th, so a bias of normal(0, 0.005) changes about every second token's
# choice without deciding how many of ALL choices fall on the 32 held.
ROUTER_BIAS_STD = 0.005


def leaf_specs(trunk: dict) -> dict:
    """The trunk's parameter tree under the program's names, each leaf as
    (shape, standard deviation, mean) of the normal law it is drawn from."""
    t = trunk
    d, hd, kv = t["dim"], t["head_dim"], t["kv_heads"]
    kernel = lambda *shape: (shape, shape[-2] ** -0.5, 0.0)  # noqa: E731
    dense = lambda *shape: {"kernel": kernel(*shape)}  # noqa: E731
    scale = lambda width=d: {"scale": ((width,), 0.1, 1.0)}  # noqa: E731
    gated = lambda width: {"gate_proj": dense(d, width), "up_proj": dense(d, width),  # noqa: E731
                           "down_proj": dense(width, d)}
    out = {"embedding": ((t["vocab_held"], d), 1.0, 0.0), "final_norm": scale()}
    for layer in range(t["layers"]):
        heads = t["heads_per_layer"][layer]
        out[f"layer_{layer}_attn"] = {"chunk": {
            "norm": scale(), "q_proj": dense(d, heads * hd), "k_proj": dense(d, kv * hd),
            "v_proj": dense(d, kv * hd), "o_proj": dense(heads * hd, d),
            "q_norm": scale(hd), "k_norm": scale(hd), "gate": kernel(d, heads),
        }}
        if layer < t["dense_layers"]:
            out[f"layer_{layer}_ffn"] = {"chunk": {"norm": scale(), "ffn": gated(t["dense_dim"])}}
        else:
            f, held = t["expert_dim"], t["experts_held"]
            out[f"layer_{layer}_ffn"] = {"chunk": {"norm": scale(), "ffn": {
                "router": kernel(d, t["experts"]), "router_bias": ((t["experts"],), ROUTER_BIAS_STD, 0.0),
                "experts": {"w_gate": kernel(held, d, f), "w_up": kernel(held, d, f),
                            "w_down": kernel(held, f, d)},
                "shared_expert": gated(t["shared_dim"])}}}
    return out


@functools.lru_cache(maxsize=2)
def _trunk_builder(trunk_json: str):
    """The compiled program that draws a trunk's leaves from two seed words,
    kept for the process: leaves of one shape are drawn together, one draw a
    shape and not one a leaf (``corpus_latent._trunk_builder`` says why)."""
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
    normal with variance 1/fan_in (the gate's too: softplus of a unit
    normal, so that the gates differ from head to head and token to token),
    the embedding normal(0, 1), the norms' scales 1 + 0.1 normal (the q and k
    norms' 128-scales too), the router's selection bias normal(0,
    ``ROUTER_BIAS_STD``)."""
    import jax.numpy as jnp

    user, head = corpus.make_weights(shapes, seed)
    w = corpus.seed_words(seed, 10)
    build = _trunk_builder(json.dumps(trunk, sort_keys=True))
    return user, {"trunk": build(jnp.int32(w[8]), jnp.int32(w[9])), "head": head}
