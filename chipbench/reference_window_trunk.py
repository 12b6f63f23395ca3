"""The plain reference of the step whose news tower is a gated grouped-query
decoder trunk with window and full layers: float32 ``jax.numpy`` at
``highest`` matmul precision, written from the layer's equations (the
``laguna`` key set, read with its public implementation in ``transformers``),
with a dense ``L x L`` masked softmax a text, every query head's keys and
values repeated out, a dense loop over the held experts, no blocked core, no
chunks, no sort, no kernel, no remat, no dedup and no optimizer library. It
imports nothing of the program; the head, the user tower, the click loss are
``chipbench/reference.py``'s, Adam's donating form and the plain rotary
``reference_moe_trunk.py``'s, YaRN's frequencies, the sigmoid router, the
held experts' loop and SwiGLU ``reference_latent_trunk.py``'s.

Pre-norm; layer ``l`` of kind ``layer_kinds[l]`` with ``H =
heads_per_layer[l]`` query heads over ``kv_heads`` key/value heads of
``head_dim``, no bias anywhere. With ``h = rms_norm(x; g1)``::

    q, k, v = h Wq, h Wk, h Wv
    q, k    = rms_norm(q; gq), rms_norm(k; gk)    over the head's dimensions
    window:   rotary (half-split pairs), theta 10,000, the whole head
    full:     the head's first ``full_rotary_share`` rotated by YaRN's
              frequencies for that width (theta 500,000; ``freq_i = inv_i /
              factor * ramp_i + inv_i * (1 - ramp_i)``, ``ramp`` linear between
              the correction dims of ``beta_fast`` and ``beta_slow``), cos and
              sin times ``attention_factor``; the rest passes unrotated
    s_it    = q_i . k_t / sqrt(head_dim); t <= i, key t a real token and, in
              a window layer, i - t < sliding_window; query head j reads
              key/value head j // (H / kv_heads)
    ctx     = softmax(s) v                     (0 for a query with no allowed
                                               key at all)
    g       = softplus(h Wg)                   (d -> H, float32)
    x       = x + (g_j ctx_j, heads side by side) Wo

then, with ``u = rms_norm(x; g2)``: the first ``dense_layers`` layers ``x +
Wdown(silu(Wgate u) * Wup u)``; the others::

    s   = sigmoid(u Wr)                      (float32, over ALL experts)
    I   = top_k(s + b)
    w_e = routed_scale * s_e / (sum over I of s + 1e-20)
    x   = x + sum over e in I, e held here, of w_e SwiGLU_e(u) + SwiGLU_shared(u)

and a final norm after the last layer. What absent experts would add is left
out, as in the program; an id outside the held vocabulary rows embeds to zero.

Departures from the published description, each the configuration file's
(``departures``, ``assumed``): no language-model head; the selection bias
stays at its first value; a query with no allowed key (a padded position of
a window layer more than a window past its text's last real token) has a
context of 0, where a softmax over nothing but masked scores would be
uniform over every key.

The ``trunk`` argument is the group ``corpus_window.trunk_of`` reads off the
configuration file. Every news slot of the batch is encoded, in blocks of
texts (texts do not attend to each other, so that is exact): at 1,024 tokens
one text's five layers of ``H x L x L`` scores with nothing rematerialised
are 3 GB, so a block is ONE text beside the 10.7 GB of parameters, gradient
and Adam's moments.

``precision`` rounds the operands of every matrix product but the router's
and the gate's (``float32``: not at all; ``bfloat16``: what the
configuration states; ``float8``: the control). ``fault`` plants one error:

  ``window_ignored``    window layers read every key up to the query
  ``whole_head_rotary`` full layers rotate the whole head (YaRN for that width)
  ``gate_one``          the gate on attention's output is 1
  ``heads_regrouped``   full layers' heads grouped as window layers' are
                        (query head j reads key/value head j // 8 of 48 / 8 = 6)
  ``drop_last_choice``  the k-th chosen expert's output is left out
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as base
from chipbench.reference_latent_trunk import gated_ffn, held_experts, routing, yarn_frequencies
from chipbench.reference_moe_trunk import adam_init, adam_update, rms_norm, rotary

PRECISIONS = ("float32", "bfloat16", "float8")
FAULTS = (None, "window_ignored", "whole_head_rotary", "gate_one", "heads_regrouped",
          "drop_last_choice")
HI = jax.lax.Precision.HIGHEST


def rotate(x, t, kind, fault=None):
    """x (n, L, heads, head_dim) by its layer's law."""
    if kind == "window":
        return rotary(x, t["window_rope_theta"])
    width = x.shape[-1] if fault == "whole_head_rotary" else int(x.shape[-1] * t["full_rotary_share"])
    half = width // 2
    freq = yarn_frequencies({"rope": t["rope"], "rope_theta": t["full_rope_theta"]}, width)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos = (jnp.cos(angle) * t["rope"]["attention_factor"])[None, :, None, :]
    sin = (jnp.sin(angle) * t["rope"]["attention_factor"])[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., width:]], axis=-1)


def attention(p, h, mask, t, layer, rnd, fault):
    n, L, _ = h.shape
    kind, heads = t["layer_kinds"][layer], t["heads_per_layer"][layer]
    kv, hd, eps = t["kv_heads"], t["head_dim"], t["rms_norm_eps"]
    mm = partial(base._mm, rnd=rnd)
    q = mm("nld,de->nle", h, p["q_proj"]["kernel"]).reshape(n, L, heads, hd)
    k = mm("nld,de->nle", h, p["k_proj"]["kernel"]).reshape(n, L, kv, hd)
    v = mm("nld,de->nle", h, p["v_proj"]["kernel"]).reshape(n, L, kv, hd)
    q, k = rms_norm(q, p["q_norm"]["scale"], eps), rms_norm(k, p["k_norm"]["scale"], eps)
    q, k = rotate(q, t, kind, fault), rotate(k, t, kind, fault)
    group = heads // kv
    if fault == "heads_regrouped" and kind == "full":
        group = max(t["heads_per_layer"]) // kv
    kv_of_head = np.arange(heads) // group          # query head j reads kv head j // group
    k, v = k[:, :, kv_of_head], v[:, :, kv_of_head]
    scores = mm("nqhd,nshd->nhqs", q, k) / np.sqrt(hd)
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    allowed = j <= i
    if kind == "window" and fault != "window_ignored":
        allowed &= (i - j) < t["sliding_window"]
    allowed = allowed[None, None] & (mask[:, None, None, :] > 0)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
    # a query with no allowed key (a padded position more than a window past
    # its text's last real token) reads nothing
    probs = jnp.where(jnp.any(allowed, axis=-1, keepdims=True), probs, 0.0)
    ctx = mm("nhqs,nshd->nqhd", probs, v)
    if fault != "gate_one":
        gate = jax.nn.softplus(jnp.einsum("nld,dh->nlh", h, p["gate"], precision=HI))
        ctx = ctx * gate[..., None]
    return mm("nle,ed->nld", ctx.reshape(n, L, heads * hd), p["o_proj"]["kernel"])


def routed_ffn(p, u, t, rnd, fault=None):
    n, L, d = u.shape
    flat = u.reshape(n * L, d)
    idx, weight = routing(p, flat, t, fault)
    y = held_experts(p["experts"], flat, idx, weight, t, rnd)
    return y.reshape(n, L, d) + gated_ffn(p["shared_expert"], u, rnd)


def decoder_layer(params, x, mask, t, layer, rnd, fault):
    pa, pf = params[f"layer_{layer}_attn"]["chunk"], params[f"layer_{layer}_ffn"]["chunk"]
    eps = t["rms_norm_eps"]
    x = x + attention(pa, rms_norm(x, pa["norm"]["scale"], eps), mask, t, layer, rnd, fault)
    if layer < t["dense_layers"]:
        return x + gated_ffn(pf["ffn"], rms_norm(x, pf["norm"]["scale"], eps), rnd)
    return x + routed_ffn(pf["ffn"], rms_norm(x, pf["norm"]["scale"], eps), t, rnd, fault)


def token_states(trunk_params, tokens, t, rnd=lambda x: x, fault=None):
    """tokens (n, 2, L) [ids; mask] -> (n, L, d) float32 states."""
    ids, mask = tokens[:, 0], tokens[:, 1]
    local = ids - t["vocab_first"]
    held = (local >= 0) & (local < t["vocab_held"])
    rows = trunk_params["embedding"][jnp.clip(local, 0, t["vocab_held"] - 1)]
    x = jnp.where(held[..., None], rows, 0.0)
    for layer in range(t["layers"]):
        x = decoder_layer(trunk_params, x, mask, t, layer, rnd, fault)
    return rms_norm(x, trunk_params["final_norm"]["scale"], t["rms_norm_eps"])


def encode_news(news_params, tokens, t, rnd=lambda x: x, fault=None):
    """(n, 2, L) token rows -> (n, D) news vectors: trunk, then the head."""
    states = token_states(news_params["trunk"], tokens, t, rnd, fault)
    return base.encode_news(news_params["head"], states, rnd)


class ReferenceStep:
    """Loss and gradients of one client-step, in blocks of ``block_rows``
    texts (``reference_latent_trunk.ReferenceStep``'s shape, this trunk's
    ``encode_news``)."""

    def __init__(self, shapes: dict, trunk: dict, precision: str = "float32",
                 fault: str | None = None, block_rows: int = 1):
        if precision not in PRECISIONS or fault not in FAULTS:
            raise ValueError(f"precision one of {PRECISIONS}, fault one of {FAULTS}")
        self.block = int(block_rows)
        rnd = base._ROUND[precision]
        heads = int(shapes["heads"])

        @jax.jit
        def enc(news_params, table, ids):
            return encode_news(news_params, table[ids], trunk, rnd, fault)

        @partial(jax.jit, donate_argnums=4)
        def enc_vjp(news_params, table, ids, ct, so_far):
            """The block's gradient added to the blocks' before it (given up:
            a gradient of the trunk is a sixth of the chip)."""
            _, pull = jax.vjp(lambda p: encode_news(p, table[ids], trunk, rnd, fault), news_params)
            return jax.tree_util.tree_map(jnp.add, so_far, pull(ct)[0])

        @partial(jax.jit, static_argnums=(2, 3))
        def user(user_params, vecs, batch, cands):
            return jax.value_and_grad(
                lambda p, v: base.user_loss(p, v, batch, cands, heads, rnd), argnums=(0, 1)
            )(user_params, vecs)

        self._enc, self._enc_vjp, self._user = enc, enc_vjp, user

    def loss_and_grads(self, user_params, news_params, table, candidates, history):
        """candidates (B, C), history (B, H) int arrays of ONE client."""
        with jax.default_matmul_precision("highest"):
            b, c = candidates.shape
            ids = jnp.concatenate([candidates.reshape(-1), history.reshape(-1)]).astype(jnp.int32)
            n = ids.shape[0]
            pad = (-n) % self.block
            ids_p = jnp.pad(ids, (0, pad)).reshape(-1, self.block)
            vecs = jnp.concatenate([self._enc(news_params, table, blk) for blk in ids_p])[:n]
            loss, (g_user, g_vecs) = self._user(user_params, vecs, b, c)
            ct = jnp.pad(g_vecs, ((0, pad), (0, 0))).reshape(ids_p.shape[0], self.block, -1)
            g_news = jax.tree_util.tree_map(jnp.zeros_like, news_params)
            for blk, ct_blk in zip(ids_p, ct):
                g_news = self._enc_vjp(news_params, table, blk, ct_blk, g_news)
            return loss, g_user, g_news


def follow_steps(shapes: dict, trunk: dict, user_params, news_params, table, batches: list,
                 lr: float, precision: str = "float32", fault: str | None = None,
                 block_rows: int = 1, step: ReferenceStep | None = None,
                 keep: slice | None = None) -> dict:
    """Drive every client through ``batches`` from the common first weights;
    arguments and result as ``reference_latent_trunk.follow_steps``."""
    step = step or ReferenceStep(shapes, trunk, precision, fault, block_rows)
    n_clients = int(np.asarray(batches[0]["candidates"]).shape[0])
    losses = np.zeros((len(batches), n_clients))
    first_grads, deltas = [], []
    host = lambda tree: jax.tree_util.tree_map(np.asarray, tree)  # noqa: E731
    # on the host, so that no second copy of the parameters lies on the chip
    change = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: np.asarray(a) - np.asarray(b), new, old)
    for c in range(n_clients):
        # a copy of the first weights: the update gives its inputs up
        u, n = jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), (user_params, news_params))
        # Adam's two moments of the news tower (5.4 GB) wait on the host
        # while a step's gradient is computed: one text's backward holds
        # 11.4 GB beside the parameters (my CPU compile, PR 35)
        su, sn = adam_init(u), None
        for i, b in enumerate(batches):
            cand, his = np.asarray(b["candidates"][c]), np.asarray(b["history"][c])
            if keep is not None:
                cand, his = cand[keep], his[keep]
            loss, gu, gn = step.loss_and_grads(u, n, table, jnp.asarray(cand), jnp.asarray(his))
            losses[i, c] = float(loss)
            if i == 0:
                first_grads.append(host({"user": gu, "news": gn}))
            u, su = adam_update(u, gu, su, lr)
            n, sn = adam_update(n, gn, sn or adam_init(n), lr)
            del gu, gn
            if i + 1 < len(batches):
                sn = {"mu": host(sn["mu"]), "nu": host(sn["nu"]), "t": sn["t"]}
        deltas.append({"user": change(u, user_params), "news": change(n, news_params)})
    return {"losses": losses, "first_grads": first_grads, "deltas": deltas}
