"""The plain reference of the step whose news tower is a sparse-expert
decoder trunk: float32 ``jax.numpy`` at ``highest`` matmul precision, written
from the layer's published equations, with a dense loop over the experts, no
sort, no kernel, no remat, no dedup and no optimizer library. It imports
nothing of the program; the head, the user tower, the click loss and Adam are
``chipbench/reference.py``'s.

One layer, for tokens ``x`` (T x d) of one title (positions 0..L-1)::

    h   = RMSNorm(x; g1)
    r   = h Wr                        (d x E: over ALL experts)
    S,I = top_k(r);  p = softmax(S)   (over the k selected)
    q,k,v = h Wq, h Wk, h Wv;  rotary(theta) on q,k in sliding layers only
    a   = softmax(q k^T / sqrt(D) + mask_l) v, query head h reads key/value
          head h // (heads / kv_heads);  x' = x + a Wo
    u   = RMSNorm(x'; g2)
    y   = sum over e in I, e held here, of p_e Wdown_e(relu(Wgate_e u) * Wup_e u)
    out = x' + y

Layer ``l`` with ``l % global_every == 0`` is global: full causal mask, no
positional encoding. The others are sliding: causal, keys ``j`` with
``i - j < window``, rotary over the whole head (half-split pairing). Then a
final RMSNorm, the additive head over the token states, the user tower and
the loss. What absent experts would add is left out, as in the program; an
id outside the held vocabulary rows embeds to zero.

The ``trunk`` argument is the configuration file's ``trunk`` group: ``dim``,
``layers``, ``heads``, ``kv_heads``, ``head_dim``, ``experts``,
``experts_per_token``, ``expert_dim``, ``rms_norm_eps``, ``rope_theta``,
``sliding_window``, ``global_every``, ``first_expert``, ``experts_held``,
``vocab_first``, ``vocab_held``.

Every news slot of the batch is encoded, in blocks of titles (titles do not
attend to each other, so that is exact): first the news vectors block by
block, then loss and gradients of the user tower and of the vectors, then
the news tower's gradient block by block from the vectors' cotangents.

``precision`` rounds the operands of every matrix product but the router's
(``float32``: not at all; ``bfloat16``: what the configuration states;
``float8``: the control, e4m3 operands going forward, e5m2 cotangents
coming back). The router stays float32 in all of them, as the configuration
states it. ``fault`` plants one error in the equations:

  ``drop_last_choice``   the k-th chosen expert's output is left out
  ``rotary_everywhere``  rotary applied in the global layers too
  ``ignore_window``      sliding layers read every earlier key
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as base

PRECISIONS = ("float32", "bfloat16", "float8")
FAULTS = (None, "drop_last_choice", "rotary_everywhere", "ignore_window")
HI = jax.lax.Precision.HIGHEST


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x (n, L, heads, D): rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, h, mask, t, is_global, rnd, fault):
    n, L, _ = h.shape
    heads, kv, hd = t["heads"], t["kv_heads"], t["head_dim"]
    mm = partial(base._mm, rnd=rnd)
    q = mm("nld,de->nle", h, p["q_proj"]["kernel"]).reshape(n, L, heads, hd)
    k = mm("nld,de->nle", h, p["k_proj"]["kernel"]).reshape(n, L, kv, hd)
    v = mm("nld,de->nle", h, p["v_proj"]["kernel"]).reshape(n, L, kv, hd)
    if not is_global or fault == "rotary_everywhere":
        q, k = rotary(q, t["rope_theta"]), rotary(k, t["rope_theta"])
    k = jnp.repeat(k, heads // kv, axis=2)        # query head h reads kv head h // group
    v = jnp.repeat(v, heads // kv, axis=2)
    scores = mm("nqhd,nshd->nhqs", q, k) / np.sqrt(hd)
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    allowed = j <= i
    if not is_global and fault != "ignore_window":
        allowed &= (i - j) < t["sliding_window"]
    allowed = allowed[None, None] & (mask[:, None, None, :] > 0)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
    ctx = mm("nhqs,nshd->nqhd", probs, v).reshape(n, L, heads * hd)
    return mm("nle,ed->nld", ctx, p["o_proj"]["kernel"])


def held_experts(p, u, idx, weight, t, rnd):
    """Dense loop over the held experts: every token through every held
    expert, weighted by the token's router weight for it (0 if not chosen)."""
    mm = partial(base._mm, rnd=rnd)

    def one_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(idx == t["first_expert"] + e, weight, 0.0), axis=-1)
        hidden = jax.nn.relu(mm("td,df->tf", u, w_gate)) * mm("td,df->tf", u, w_up)
        return y + w[:, None] * mm("tf,fd->td", hidden, w_down), None

    # (a loop the compiler sees once: unrolled, sixteen experts a layer
    # take minutes to compile at float32)
    experts = (jnp.arange(t["experts_held"]), p["w_gate"], p["w_up"], p["w_down"])
    return jax.lax.scan(one_expert, jnp.zeros_like(u), experts)[0]


def decoder_layer(p, x, mask, t, layer, rnd, fault):
    n, L, d = x.shape
    h = rms_norm(x, p["attn_norm"]["scale"], t["rms_norm_eps"])
    logits = jnp.einsum("td,de->te", h.reshape(n * L, d), p["router"], precision=HI)
    top, idx = jax.lax.top_k(logits, t["experts_per_token"])
    weight = jax.nn.softmax(top, axis=-1)
    if fault == "drop_last_choice":
        weight = weight.at[:, -1].set(0.0)
    x = x + attention(p["attn"], h, mask, t, layer % t["global_every"] == 0, rnd, fault)
    u = rms_norm(x, p["ffn_norm"]["scale"], t["rms_norm_eps"])
    y = held_experts(p["experts"], u.reshape(n * L, d), idx, weight, t, rnd)
    return x + y.reshape(n, L, d)


def token_states(trunk_params, tokens, t, rnd=lambda x: x, fault=None):
    """tokens (n, 2, L) [ids; mask] -> (n, L, d) float32 states."""
    ids, mask = tokens[:, 0], tokens[:, 1]
    local = ids - t["vocab_first"]
    held = (local >= 0) & (local < t["vocab_held"])
    rows = trunk_params["embedding"][jnp.clip(local, 0, t["vocab_held"] - 1)]
    x = jnp.where(held[..., None], rows, 0.0)
    for layer in range(t["layers"]):
        x = decoder_layer(trunk_params[f"layer_{layer}"], x, mask, t, layer, rnd, fault)
    return rms_norm(x, trunk_params["final_norm"]["scale"], t["rms_norm_eps"])


def encode_news(news_params, tokens, t, rnd=lambda x: x, fault=None):
    """(n, 2, L) token rows -> (n, D) news vectors: trunk, then the head."""
    states = token_states(news_params["trunk"], tokens, t, rnd, fault)
    return base.encode_news(news_params["head"], states, rnd)


class ReferenceStep:
    """Loss and gradients of one client-step, in blocks of ``block_rows`` titles."""

    def __init__(self, shapes: dict, trunk: dict, precision: str = "float32",
                 fault: str | None = None, block_rows: int = 55):
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
            a gradient of the trunk is a fifth of the chip)."""
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


@partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_leaf(p, g, mu, nu, t, lr):
    """``reference._adam_leaf`` with the old parameter and moments given up:
    the trunk's parameters and their two moments are most of the chip."""
    mu = base.ADAM_B1 * mu + (1.0 - base.ADAM_B1) * g
    nu = base.ADAM_B2 * nu + (1.0 - base.ADAM_B2) * g * g
    mhat = mu / (1.0 - base.ADAM_B1 ** t)
    nhat = nu / (1.0 - base.ADAM_B2 ** t)
    return p - lr * mhat / (jnp.sqrt(nhat) + base.ADAM_EPS), mu, nu


def adam_init(params):
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)  # noqa: E731
    return {"mu": zeros(), "nu": zeros(), "t": 0}       # two buffers: both are given up


def adam_update(params, grads, state, lr: float):
    t = state["t"] + 1
    out = jax.tree_util.tree_map(
        lambda p, g, m, v: _adam_leaf(p, g, m, v, jnp.float32(t), jnp.float32(lr)),
        params, grads, state["mu"], state["nu"],
    )
    is_triple = lambda x: isinstance(x, tuple)  # noqa: E731
    pick = lambda i: jax.tree_util.tree_map(lambda o: o[i], out, is_leaf=is_triple)  # noqa: E731
    return pick(0), {"mu": pick(1), "nu": pick(2), "t": t}


def follow_steps(shapes: dict, trunk: dict, user_params, news_params, table, batches: list,
                 lr: float, precision: str = "float32", fault: str | None = None,
                 block_rows: int = 55) -> dict:
    """Drive every client through ``batches`` (a list of steps, each with
    ``candidates`` (K, B, C) and ``history`` (K, B, H)) from the common first
    weights (host or device trees; one copy of them goes to the chip);
    ``table`` is the (N, 2, L) int32 token table. Returns what
    ``reference.follow_steps`` returns, as float32 numpy trees: per client
    the losses, the first gradient and the parameters' change."""
    step = ReferenceStep(shapes, trunk, precision, fault, block_rows)
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
        su, sn = adam_init(u), adam_init(n)
        for i, b in enumerate(batches):
            loss, gu, gn = step.loss_and_grads(
                u, n, table, jnp.asarray(b["candidates"][c]), jnp.asarray(b["history"][c]))
            losses[i, c] = float(loss)
            if i == 0:
                first_grads.append(host({"user": gu, "news": gn}))
            u, su = adam_update(u, gu, su, lr)
            n, sn = adam_update(n, gn, sn, lr)
            del gu, gn
        deltas.append({"user": change(u, user_params), "news": change(n, news_params)})
    return {"losses": losses, "first_grads": first_grads, "deltas": deltas}
