"""The plain reference of the two-tower step: float32 ``jax.numpy``, written
from the equations of the source's ``encoder.py`` / ``attention.py`` /
``model.py`` (as ``fedrec_tpu/models`` implements them), with no kernels, no
dedup, no vmap over clients and no optimizer library. It imports nothing of
the program and takes nothing the program made: the table, the weights and
the corpus come from the harness's own generators, the batches are the ids
the timed path was fed.

    text head   e = tanh(x W1 + b1); a = softmax_L(e w2 + b2) (exp-normalised
                with the source's +1e-8); n = (sum_L a x) Wf + bf
    user tower  q,k,v = h Wq+bq, h Wk+bk, h Wv+bv in 20 heads of 20;
                ctx = softmax(q k^T / sqrt(20)) v, no output projection;
                u = additive pool of ctx (same form as the text head's)
    score/loss  s = cand . u; loss = mean CE(sigmoid(s), slot 0)  [sic: the
                source feeds sigmoid outputs to the cross-entropy]
    update      Adam(lr 5e-5, b1 .9, b2 .999, eps 1e-8) per client, per tower

Every news slot of the batch is encoded (B x (C + H) titles), in blocks of
rows so that the float32 gather fits beside the table: first the news
vectors block by block, then loss and gradients of the user tower and of
the vectors, then the text head's gradient block by block from the vectors'
cotangents. That is exact, not an approximation.

``precision`` picks how the operands of every matrix product are rounded:
``float32`` (the reference proper, products at ``highest``), ``bfloat16``
(what the configurations state) and ``float8`` (the control, the step below bfloat16, in the usual
hybrid recipe of float8 training: per-tensor scaled e4m3 operands going
forward, e5m2 cotangents coming back).
``bfloat16_all`` is a witness, not a control: bfloat16 arithmetic
throughout, as ``model.dtype=bfloat16`` computes it (float32 parameters
cast on use, every result and every cotangent a bfloat16 value, the scores
cast to float32 for the loss); it shows which gaps of a sound bfloat16
program are rounding of activations (``PERF.md``, section 2).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
PRECISIONS = ("float32", "bfloat16", "float8", "bfloat16_all")


def _fake_fp8(x, dtype=jnp.float8_e4m3fn, top=448.0):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _fake_fp8(x)


def _fp8_fwd(x):
    return _fake_fp8(x), None


def _fp8_bwd(_, g):
    return (_fake_fp8(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


@jax.custom_vjp
def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _bf16_fwd(x):
    return _bf16(x), None


def _bf16_bwd(_, g):
    return (g.astype(jnp.bfloat16).astype(jnp.float32),)


_bf16.defvjp(_bf16_fwd, _bf16_bwd)

_ROUND = {"float32": lambda x: x, "bfloat16": _bf16, "float8": _fp8,
          "bfloat16_all": lambda x: x}


def _mm(spec: str, a, b, rnd):
    return jnp.einsum(spec, rnd(a), rnd(b), precision=jax.lax.Precision.HIGHEST)


def _softmax_eps(logits, axis):
    w = jnp.exp(logits - jnp.max(logits, axis=axis, keepdims=True))
    return w / (jnp.sum(w, axis=axis, keepdims=True) + 1e-8)


def _additive_pool(p, x, rnd):
    e = jnp.tanh(_mm("...ld,dh->...lh", x, p["att_fc1"]["kernel"], rnd) + p["att_fc1"]["bias"])
    logits = _mm("...lh,ho->...lo", e, p["att_fc2"]["kernel"], rnd)[..., 0] + p["att_fc2"]["bias"][0]
    alpha = _softmax_eps(logits, -1)
    return _mm("...l,...ld->...d", alpha, x, rnd)


def encode_news(news_params, states, rnd):
    """(n, L, Dh) float32 token states -> (n, D) news vectors."""
    pooled = _additive_pool(news_params["pool"], states, rnd)
    return _mm("nd,de->ne", pooled, news_params["fc"]["kernel"], rnd) + news_params["fc"]["bias"]


def user_loss(user_params, vecs, batch: int, cands: int, heads: int, rnd):
    """vecs: (B*C + B*H, D), candidate slots first. Mean loss over the batch."""
    d = vecs.shape[-1]
    cand = vecs[: batch * cands].reshape(batch, cands, d)
    his = vecs[batch * cands:].reshape(batch, -1, d)
    sa = user_params["self_attn"]

    def proj(name):
        y = _mm("bhd,de->bhe", his, sa[name]["kernel"], rnd) + sa[name]["bias"]
        return y.reshape(batch, his.shape[1], heads, -1)

    q, k, v = proj("w_q"), proj("w_k"), proj("w_v")
    scores = _mm("bqhd,bkhd->bhqk", q, k, rnd) / float(np.sqrt(q.shape[-1]))
    attn = _softmax_eps(scores, -1)
    ctx = _mm("bhqk,bkhd->bqhd", attn, v, rnd).reshape(batch, his.shape[1], -1)
    user = _additive_pool(user_params["pool"], ctx, rnd)
    s = _mm("bcd,bd->bc", cand, user, rnd).astype(jnp.float32)
    probs = jax.nn.sigmoid(s)
    per_row = -jax.nn.log_softmax(probs, axis=-1)[:, 0]
    return jnp.mean(per_row)


class ReferenceStep:
    """Loss and gradients of one client-step, in blocks of ``block_rows`` news."""

    def __init__(self, shapes: dict, precision: str = "float32", block_rows: int = 3520):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        self.shapes = shapes
        self.block = int(block_rows)
        rnd = _ROUND[precision]
        heads = int(shapes["heads"])
        # the type every operation computes in; parameters stay float32 and
        # are cast where they are used, so their gradients come back float32
        dt = jnp.bfloat16 if precision == "bfloat16_all" else jnp.float32
        cast = lambda tree: jax.tree_util.tree_map(lambda x: x.astype(dt), tree)  # noqa: E731

        @jax.jit
        def enc(news_params, table, ids):
            return encode_news(cast(news_params), table[ids].astype(dt), rnd)

        @jax.jit
        def enc_vjp(news_params, table, ids, ct):
            _, pull = jax.vjp(
                lambda p: encode_news(cast(p), table[ids].astype(dt), rnd),
                news_params,
            )
            return pull(ct)[0]

        @partial(jax.jit, static_argnums=(2, 3))
        def user(user_params, vecs, batch, cands):
            return jax.value_and_grad(
                lambda p, v: user_loss(cast(p), v, batch, cands, heads, rnd), argnums=(0, 1)
            )(user_params, vecs)

        self._enc, self._enc_vjp, self._user = enc, enc_vjp, user

    def loss_and_grads(self, user_params, news_params, table, candidates, history):
        """candidates (B, C), history (B, H) int arrays of ONE client."""
        b, c = candidates.shape
        ids = jnp.concatenate([candidates.reshape(-1), history.reshape(-1)]).astype(jnp.int32)
        n = ids.shape[0]
        pad = (-n) % self.block
        ids_p = jnp.pad(ids, (0, pad)).reshape(-1, self.block)
        vecs = jnp.concatenate(
            [self._enc(news_params, table, blk) for blk in ids_p]
        )[:n]
        loss, (g_user, g_vecs) = self._user(user_params, vecs, b, c)
        ct = jnp.pad(g_vecs, ((0, pad), (0, 0))).reshape(ids_p.shape[0], self.block, -1)
        g_news = None
        for blk, ct_blk in zip(ids_p, ct):
            g = self._enc_vjp(news_params, table, blk, ct_blk)
            g_news = g if g_news is None else jax.tree_util.tree_map(jnp.add, g_news, g)
        return loss, g_user, g_news


def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros, "t": 0}


@jax.jit
def _adam_leaf(p, g, mu, nu, t, lr):
    mu = ADAM_B1 * mu + (1.0 - ADAM_B1) * g
    nu = ADAM_B2 * nu + (1.0 - ADAM_B2) * g * g
    mhat = mu / (1.0 - ADAM_B1 ** t)
    nhat = nu / (1.0 - ADAM_B2 ** t)
    return p - lr * mhat / (jnp.sqrt(nhat) + ADAM_EPS), mu, nu


def adam_update(params, grads, state, lr: float):
    t = state["t"] + 1
    out = jax.tree_util.tree_map(
        lambda p, g, m, v: _adam_leaf(p, g, m, v, jnp.float32(t), jnp.float32(lr)),
        params, grads, state["mu"], state["nu"],
    )
    is_triple = lambda x: isinstance(x, tuple)  # noqa: E731
    pick = lambda i: jax.tree_util.tree_map(lambda o: o[i], out, is_leaf=is_triple)  # noqa: E731
    return pick(0), {"mu": pick(1), "nu": pick(2), "t": t}


def follow_steps(
    shapes: dict, user_params, news_params, table, batches: list, lr: float,
    precision: str = "float32", keep: slice | None = None,
) -> dict:
    """Drive every client through ``batches`` (a list of steps, each with
    ``candidates`` (K, B, C) and ``history`` (K, B, H)) from the common first
    weights. ``keep``: the rows of each client's batch that the step uses
    (the half-batch fault of the control tests; None = all).

    Returns per client: the losses of each step, the first gradient, and the
    parameters' change after the last step, as numpy float64 trees."""
    step = ReferenceStep(shapes, precision)
    n_clients = int(np.asarray(batches[0]["candidates"]).shape[0])
    losses = np.zeros((len(batches), n_clients))
    first_grads, deltas = [], []
    host = lambda t: jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), t)  # noqa: E731
    for c in range(n_clients):
        u, n = user_params, news_params
        su, sn = adam_init(u), adam_init(n)
        for i, b in enumerate(batches):
            cand = jnp.asarray(b["candidates"][c])
            his = jnp.asarray(b["history"][c])
            if keep is not None:
                cand, his = cand[keep], his[keep]
            loss, gu, gn = step.loss_and_grads(u, n, table, cand, his)
            losses[i, c] = float(loss)
            if i == 0:
                first_grads.append(host({"user": gu, "news": gn}))
            u, su = adam_update(u, gu, su, lr)
            n, sn = adam_update(n, gn, sn, lr)
        deltas.append(host({
            "user": jax.tree_util.tree_map(jnp.subtract, u, user_params),
            "news": jax.tree_util.tree_map(jnp.subtract, n, news_params),
        }))
    return {"losses": losses, "first_grads": first_grads, "deltas": deltas}
