"""The plain reference of the step whose news tower is a latent-attention
decoder trunk with a multi-stream residual: float32 ``jax.numpy`` at
``highest`` matmul precision, written from the layer's equations (the
DeepSeek-V3 key set plus manifold-constrained hyper-connections,
arXiv:2512.24880), with a dense loop over the held experts, Sinkhorn as a
plain loop of its iterations, no sort, no kernel, no remat, no dedup and no optimizer library.
It imports nothing of the program; the head, the user tower, the click loss
and Adam are ``chipbench/reference.py``'s (Adam's donating form is
``reference_moe_trunk.py``'s).

Per token the state is ``X`` (n x d), the embedding in all n streams at the
start. Every layer is two sublayers ``F`` (attention, then feed-forward),
each wrapped by its own mixer, in float32::

    z      = rms_norm(vec(X); g)                  (the n*d vector)
    Hpre~  = a_pre  * (z P_pre)  + b_pre
    Hpost~ = a_post * (z P_post) + b_post
    Hres~  = a_res  * mat(z P_res) + b_res        (n x n, row-major)
    Hpre   = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
    M      = exp(clip(Hres~, lo, hi));  iters times:
             M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    x_in   = Hpre X
    X_next = M X + Hpost^T F(x_in)

After the last layer the streams are summed, then the final norm. Attention,
with ``h = rms_norm(x_in; g1)``, positions 0..L-1 of a title::

    cq       = rms_norm(h Wqa; gq)
    [qn|qr]  = cq Wqb    -> heads x (nope | rope)
    [ckv|kr] = h Wkva;   ckv = rms_norm(ckv; gkv)
    [kn|v]   = ckv Wkvb  -> heads x (nope | v)
    qr, kr   = rope(qr), rope(kr)        (kr: ONE key shared by the heads)
    s_ij     = (qn_i . kn_j + qr_i . kr_j) * (nope + rope)^-0.5 * m^2,
               j <= i and key j a real token
    F        = (softmax(s) v, heads side by side) Wo

``rope``: half-split pairs, YaRN frequencies ``freq_i = inv_i / factor *
ramp_i + inv_i * (1 - ramp_i)`` with ``inv_i = theta^(-2i/rope)`` and
``ramp`` the linear ramp between the correction dims of ``beta_fast`` and
``beta_slow``; ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; cos and sin
scaled by ``mscale / mscale_all_dim``'s two ``m``. Feed-forward, with ``u =
rms_norm(x_in; g2)``: the first ``dense_layers`` layers ``Wdown(silu(Wgate
u) * Wup u)``; the others::

    s   = sigmoid(u Wr)                      (float32, over ALL experts)
    I   = top_k(s + b)
    w_e = routed_scale * s_e / (sum over I of s + 1e-20)
    F   = sum over e in I, e held here, of w_e Wdown_e(silu(Wgate_e u) * Wup_e u)
          + Shared(u)

What absent experts would add is left out, as in the program; an id outside
the held vocabulary rows embeds to zero.

The ``trunk`` argument is the group ``corpus_latent.trunk_of`` reads off
the configuration file. Every news slot of the batch is encoded, in blocks
of titles (titles do not attend to each other, so that is exact), as
``reference_moe_trunk.py`` does it.

``precision`` rounds the operands of every matrix product but the router's
and the mixers' (``float32``: not at all; ``bfloat16``: what the
configuration states; ``float8``: the control). ``fault`` plants one error:

  ``drop_last_choice``        the k-th chosen expert's output is left out
  ``bias_in_weights``         the selection bias b counted into the weights
  ``one_sinkhorn_iteration``  1 iteration for ``sinkhorn_iters``
  ``plain_rope``              no YaRN blend of the frequencies, and m = 1
  ``rope_on_all_dims``        the position-free dimensions rotated too
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as base
from chipbench.reference_moe_trunk import adam_init, adam_update, rms_norm

PRECISIONS = ("float32", "bfloat16", "float8")
FAULTS = (None, "drop_last_choice", "bias_in_weights", "one_sinkhorn_iteration",
          "plain_rope", "rope_on_all_dims")
HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ rotary
def yarn_frequencies(t: dict, width: int, fault=None) -> np.ndarray:
    """(width / 2,) rotary frequencies of a ``width``-wide rotary part."""
    r = t["rope"]
    half = width // 2
    inv = float(t["rope_theta"]) ** (-np.arange(half, dtype=np.float64) / half)
    if fault == "plain_rope":
        return inv.astype(np.float32)

    def correction_dim(turns):
        return width * math.log(r["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (
            2 * math.log(t["rope_theta"]))

    low = max(math.floor(correction_dim(r["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(r["beta_slow"])), width - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (inv / r["factor"] * ramp + inv * (1.0 - ramp)).astype(np.float32)


def _m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(x, t, fault=None):
    """x (n, L, ..., width): rotate pairs (i, i + width/2) by position * freq_i."""
    half = x.shape[-1] // 2
    r = t["rope"]
    scale = 1.0 if fault == "plain_rope" else _m(r["factor"], r["mscale"]) / _m(r["factor"], r["mscale_all_dim"])
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * yarn_frequencies(t, x.shape[-1], fault)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = (jnp.cos(angle) * scale).reshape(shape), (jnp.sin(angle) * scale).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------- sublayers
def attention(p, h, mask, t, rnd, fault):
    n, L, _ = h.shape
    heads, nope, rdim, vdim = t["heads"], t["nope_dim"], t["rope_dim"], t["v_dim"]
    mm = partial(base._mm, rnd=rnd)
    eps = t["rms_norm_eps"]
    cq = rms_norm(mm("nld,dr->nlr", h, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"], eps)
    q = mm("nlr,re->nle", cq, p["q_b_proj"]["kernel"]).reshape(n, L, heads, nope + rdim)
    kv_a = mm("nld,dr->nlr", h, p["kv_a_proj"]["kernel"])
    ckv = rms_norm(kv_a[..., : t["kv_rank"]], p["kv_a_norm"]["scale"], eps)
    kr = kv_a[..., t["kv_rank"]:]
    kv = mm("nlr,re->nle", ckv, p["kv_b_proj"]["kernel"]).reshape(n, L, heads, nope + vdim)
    qn, qr, kn, v = q[..., :nope], q[..., nope:], kv[..., :nope], kv[..., nope:]
    qr, kr = rope(qr, t, fault), rope(kr, t, fault)
    if fault == "rope_on_all_dims":
        qn, kn = rope(qn, t, "plain_rope"), rope(kn, t, "plain_rope")
    m = 1.0 if fault == "plain_rope" else _m(t["rope"]["factor"], t["rope"]["mscale_all_dim"])
    scores = (mm("nqhd,nshd->nhqs", qn, kn) + mm("nqhd,nsd->nhqs", qr, kr)) * (
        (nope + rdim) ** -0.5 * m * m)
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    allowed = (j <= i)[None, None] & (mask[:, None, None, :] > 0)
    probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
    ctx = mm("nhqs,nshd->nqhd", probs, v).reshape(n, L, heads * vdim)
    return mm("nle,ed->nld", ctx, p["o_proj"]["kernel"])


def gated_ffn(p, u, rnd):
    mm = partial(base._mm, rnd=rnd)
    hidden = jax.nn.silu(mm("...d,df->...f", u, p["gate_proj"]["kernel"])) * mm(
        "...d,df->...f", u, p["up_proj"]["kernel"])
    return mm("...f,fd->...d", hidden, p["down_proj"]["kernel"])


def routing(p, u, t, fault=None):
    """u (T, d) -> chosen experts (T, k) and their weights (T, k), float32."""
    scores = jax.nn.sigmoid(jnp.einsum("td,de->te", u, p["router"], precision=HI))
    biased = scores + p["router_bias"]
    _, idx = jax.lax.top_k(biased, t["experts_per_token"])
    picked = jnp.take_along_axis(biased if fault == "bias_in_weights" else scores, idx, axis=-1)
    weight = t["routed_scale"] * picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    if fault == "drop_last_choice":
        weight = weight.at[:, -1].set(0.0)
    return idx, weight


def held_experts(p, u, idx, weight, t, rnd):
    """Dense loop over the held experts: every token through every held
    expert, weighted by the token's router weight for it (0 if not chosen)."""
    mm = partial(base._mm, rnd=rnd)

    def one_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(idx == t["first_expert"] + e, weight, 0.0), axis=-1)
        hidden = jax.nn.silu(mm("td,df->tf", u, w_gate)) * mm("td,df->tf", u, w_up)
        return y + w[:, None] * mm("tf,fd->td", hidden, w_down), None

    experts = (jnp.arange(t["experts_held"]), p["w_gate"], p["w_up"], p["w_down"])
    return jax.lax.scan(one_expert, jnp.zeros_like(u), experts)[0]


def routed_ffn(p, u, t, rnd, fault):
    n, L, d = u.shape
    flat = u.reshape(n * L, d)
    idx, weight = routing(p, flat, t, fault)
    y = held_experts(p["experts"], flat, idx, weight, t, rnd)
    return y.reshape(n, L, d) + gated_ffn(p["shared_expert"], u, rnd)


def mixer_maps(p, x, t, fault=None):
    """x (..., n, d) -> Hpre (..., n), Hpost (..., n), M (..., n, n)."""
    n = t["streams"]
    z = rms_norm(x.reshape(x.shape[:-2] + (-1,)), p["norm"]["scale"], t["rms_norm_eps"])
    a_pre, a_post, a_res = p["alpha"][0], p["alpha"][1], p["alpha"][2]
    lin = lambda w: jnp.einsum("...c,cm->...m", z, w, precision=HI)  # noqa: E731
    pre = jax.nn.sigmoid(a_pre * lin(p["proj_pre"]) + p["bias_pre"])
    post = 2.0 * jax.nn.sigmoid(a_post * lin(p["proj_post"]) + p["bias_post"])
    res = a_res * lin(p["proj_res"]).reshape(x.shape[:-2] + (n, n)) + p["bias_res"]
    m = jnp.exp(jnp.clip(res, t["res_clamp_min"], t["res_clamp_max"]))
    def iteration(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + t["hc_eps"])      # columns
        return m / (jnp.sum(m, axis=-1, keepdims=True) + t["hc_eps"])   # rows

    # (a loop the compiler sees once: unrolled, twenty iterations of ten
    # sublayers and their backward take it minutes)
    iters = 1 if fault == "one_sinkhorn_iteration" else t["sinkhorn_iters"]
    return pre, post, jax.lax.fori_loop(0, iters, iteration, m)


def mixed_sublayer(p, x, t, f, fault=None):
    """x (n_titles, L, n, d); ``f`` maps the normed (n_titles, L, d) input
    to the sublayer's output."""
    pre, post, m = mixer_maps(p["mixer"], x, t, fault)
    x_in = jnp.einsum("...n,...nd->...d", pre, x, precision=HI)
    out = f(rms_norm(x_in, p["norm"]["scale"], t["rms_norm_eps"]))
    return (jnp.einsum("...ij,...jd->...id", m, x, precision=HI)
            + post[..., :, None] * out[..., None, :])


def decoder_layer(params, x, mask, t, layer, rnd, fault):
    pa, pf = params[f"layer_{layer}_attn"], params[f"layer_{layer}_ffn"]
    x = mixed_sublayer(pa, x, t, lambda h: attention(pa["attn"], h, mask, t, rnd, fault), fault)
    if layer < t["dense_layers"]:
        ffn = lambda u: gated_ffn(pf["ffn"], u, rnd)  # noqa: E731
    else:
        ffn = lambda u: routed_ffn(pf["ffn"], u, t, rnd, fault)  # noqa: E731
    return mixed_sublayer(pf, x, t, ffn, fault)


def token_states(trunk_params, tokens, t, rnd=lambda x: x, fault=None):
    """tokens (n, 2, L) [ids; mask] -> (n, L, d) float32 states."""
    ids, mask = tokens[:, 0], tokens[:, 1]
    local = ids - t["vocab_first"]
    held = (local >= 0) & (local < t["vocab_held"])
    rows = trunk_params["embedding"][jnp.clip(local, 0, t["vocab_held"] - 1)]
    x = jnp.where(held[..., None], rows, 0.0)
    x = jnp.repeat(x[:, :, None, :], t["streams"], axis=2)
    for layer in range(t["layers"]):
        x = decoder_layer(trunk_params, x, mask, t, layer, rnd, fault)
    return rms_norm(jnp.sum(x, axis=2), trunk_params["final_norm"]["scale"], t["rms_norm_eps"])


def encode_news(news_params, tokens, t, rnd=lambda x: x, fault=None):
    """(n, 2, L) token rows -> (n, D) news vectors: trunk, then the head."""
    states = token_states(news_params["trunk"], tokens, t, rnd, fault)
    return base.encode_news(news_params["head"], states, rnd)


class ReferenceStep:
    """Loss and gradients of one client-step, in blocks of ``block_rows`` titles."""

    def __init__(self, shapes: dict, trunk: dict, precision: str = "float32",
                 fault: str | None = None, block_rows: int = 10):
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
                 block_rows: int = 10, step: ReferenceStep | None = None,
                 keep: slice | None = None) -> dict:
    """Drive every client through ``batches`` (a list of steps, each with
    ``candidates`` (K, B, C) and ``history`` (K, B, H)) from the common first
    weights (host or device trees; one copy of them goes to the chip);
    ``table`` is the (N, 2, L) int32 token table. Returns what
    ``reference.follow_steps`` returns, as float32 numpy trees: per client
    the losses, the first gradient and the parameters' change. ``step``: a
    ``ReferenceStep`` built before, whose compiled programs are then used
    again (the chip's compiler takes two minutes over them). ``keep``: the
    rows of each client's batch that the step uses (a fault to plant: a step
    that sees half its batch), as ``reference.follow_steps`` has it."""
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
        su, sn = adam_init(u), adam_init(n)
        for i, b in enumerate(batches):
            cand, his = np.asarray(b["candidates"][c]), np.asarray(b["history"][c])
            if keep is not None:
                cand, his = cand[keep], his[keep]
            loss, gu, gn = step.loss_and_grads(u, n, table, jnp.asarray(cand), jnp.asarray(his))
            losses[i, c] = float(loss)
            if i == 0:
                first_grads.append(host({"user": gu, "news": gn}))
            u, su = adam_update(u, gu, su, lr)
            n, sn = adam_update(n, gn, sn, lr)
            del gu, gn
        deltas.append({"user": change(u, user_params), "news": change(n, news_params)})
    return {"losses": losses, "first_grads": first_grads, "deltas": deltas}
