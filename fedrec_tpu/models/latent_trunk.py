"""Latent-attention decoder trunk with a multi-stream residual, read as a
text encoder (Flax linen).

A causal decoder in the DeepSeek-V3 key set (latent attention, a sigmoid
router with a selection bias, a shared expert, leading dense layers, SwiGLU)
whose residual path is not a sum: a token carries ``n_streams`` copies of
the hidden state, mixed at every sublayer by a doubly stochastic matrix made
from the token itself (manifold-constrained hyper-connections,
arXiv:2512.24880). Token ids -> embedding in every stream -> decoder layers
-> sum of the streams -> final RMSNorm -> per-token states, which
``models.bert.TextEncoder`` pools with the repo's additive head. Trained in
loop by the click loss (``text_encoder_mode='finetune'``); no language-model
head and no multi-token-prediction module are built.

Per token the state is ``X`` (n x d). A layer is two sublayers ``F``
(attention, then feed-forward), each wrapped by its own mixer, in float32::

    z      = RMSNorm(vec(X); g)                        (the n*d vector)
    Hpre~  = a_pre  * (z P_pre)  + b_pre               (n)
    Hpost~ = a_post * (z P_post) + b_post              (n)
    Hres~  = a_res  * mat(z P_res) + b_res             (n x n)
    Hpre   = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
    M      = exp(clip(Hres~, lo, hi));  sinkhorn_iters times:
             M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    x_in   = Hpre X                                    (one vector into F)
    X_next = M X + Hpost^T F(x_in)

Attention, with ``h = RMSNorm(x_in; g1)``, positions 0..L-1 of a title::

    cq       = RMSNorm(h Wqa; gq)
    [qn|qr]  = cq Wqb   -> heads x (nope_dim | rope_dim)
    [ckv|kr] = h Wkva;  ckv = RMSNorm(ckv; gkv)
    [kn|v]   = ckv Wkvb -> heads x (nope_dim | v_dim)
    qr, kr   = rope(qr), rope(kr)        kr: ONE key shared by all heads
    s_ij     = (qn_i . kn_j + qr_i . kr_j) * (nope+rope)^-0.5 * m^2, causal
    F        = (softmax(s) v, heads side by side) Wo

``rope`` uses YaRN's frequencies (``yarn_inv_freq``), half-split pairing; ``m``
is ``yarn_mscale``. Feed-forward, with ``u = RMSNorm(x_in; g2)``: the first
``n_dense_layers`` layers ``Wdown(silu(Wgate u) * Wup u)``; the others::

    s   = sigmoid(u Wr)                          router over ALL experts, float32
    I   = top_k(s + b)                           b: selection bias, no gradient
    w_e = routed_scale * s_e / (sum over I of s + 1e-20)
    F   = sum over e in I, e HELD HERE, of w_e Wdown_e(silu(Wgate_e u) * Wup_e u)
          + Shared(u)

The held experts' part is ``sparse_trunk.HeldExperts`` (sort by expert,
grouped products over the true group sizes, no token dropped), told which
experts it holds as there; what absent experts would add is left out. Its
sorted buffer has ``sparse_trunk.buffer_rows``' two sizes: with 8 of 64
experts held a layer runs through a quarter of the worst case's rows
whenever the router sent here at most twice the even share of the pairs, and
at the worst case's size, exactly, when it sent more (``full_size_chunks``
counts those layers). The shared expert is computed whole. The embedding
holds ``vocab_held`` rows from ``vocab_first``; an id outside the slice
embeds to zero.

Rematerialisation (``remat=True``) is per sublayer, mixer included: the
backward pass keeps each sublayer's input ``X`` and runs its forward once
more, except the held experts', whose output is kept beside ``X``
(``HeldExperts`` runs its own forward again beside its transposes). Nothing
else is rematerialised or chunked beyond ``HeldExperts``' own chunks.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from fedrec_tpu.models.sparse_trunk import (
    HELD_EXPERTS_OUTPUT, HeldExperts, RMSNorm, attention_allowed,
)

HI = lax.Precision.HIGHEST


@dataclass(frozen=True)
class LatentTrunkConfig:
    """Architecture knobs; defaults = ``Xing4.0-29B-A4B``
    (huggingface.co/XingChen-AGI/Xing4.0-29B-A4B, config.json), whole: every
    expert and every vocabulary row held here. Its experts are SwiGLU: this
    trunk hands ``jax.nn.silu`` to ``HeldExperts``."""

    vocab_size: int = 131072
    dim: int = 3584                    # hidden_size
    n_layers: int = 40
    n_dense_layers: int = 2            # first_k_dense_replace
    n_heads: int = 32
    q_rank: int = 768                  # q_lora_rank
    kv_rank: int = 512                 # kv_lora_rank
    nope_dim: int = 128                # qk_nope_head_dim
    rope_dim: int = 64                 # qk_rope_head_dim
    v_dim: int = 128                   # v_head_dim
    dense_dim: int = 9216              # intermediate_size
    n_experts: int = 64                # n_routed_experts
    experts_per_token: int = 4
    expert_dim: int = 1024             # moe_intermediate_size
    n_shared_experts: int = 1
    routed_scale: float = 2.0          # routed_scaling_factor
    n_streams: int = 4                 # hc_mult
    sinkhorn_iters: int = 20           # hc_sinkhorn_iters
    hc_eps: float = 1e-6
    res_clamp: tuple[float, float] = (-30.0, 30.0)   # mhc_h_res_clamp_min/max
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # rope_scaling (type yarn)
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # this chip's share of a layer
    first_expert: int = 0
    experts_held: int = 64
    vocab_first: int = 0
    vocab_held: int = 131072

    def __post_init__(self):
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"{self.n_dense_layers} leading dense layers are not among "
                f"the {self.n_layers} layers"
            )
        if self.rope_dim % 2:
            raise ValueError(f"rotary pairs need an even rope_dim, not {self.rope_dim}")
        if not 0 < self.experts_held <= self.n_experts - self.first_expert:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.experts_held - 1} "
                f"are not among the {self.n_experts} of a layer"
            )
        if not 0 < self.vocab_held <= self.vocab_size - self.vocab_first:
            raise ValueError(
                f"vocabulary rows {self.vocab_first}..{self.vocab_first + self.vocab_held - 1} "
                f"are not among the {self.vocab_size}"
            )

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.n_dense_layers


def latent_trunk_config_from(model_cfg) -> LatentTrunkConfig:
    """LatentTrunkConfig from a ``ModelConfig``: the depth, the share held
    and the widths tests shrink come from it, the rest is as published."""
    base = LatentTrunkConfig()
    return LatentTrunkConfig(
        dim=model_cfg.bert_hidden,
        n_layers=model_cfg.trunk_layers,
        n_dense_layers=model_cfg.trunk_dense_layers,
        n_heads=model_cfg.trunk_heads,
        expert_dim=model_cfg.trunk_ffn,
        first_expert=model_cfg.trunk_first_expert,
        experts_held=model_cfg.trunk_experts_held or base.n_experts,
        vocab_held=model_cfg.trunk_vocab,
    )


# ------------------------------------------------------------------ rotary
def yarn_inv_freq(
    rope_dim: int, theta: float, factor: float, original_max: int,
    beta_fast: float, beta_slow: float,
) -> jnp.ndarray:
    """The rotary frequencies under YaRN: (rope_dim / 2,). Frequencies that
    turn more than ``beta_fast`` times inside the original context stay,
    those that turn less than ``beta_slow`` times are divided by ``factor``,
    a linear ramp blends the ones between. Nothing in it reads a position."""
    half = rope_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)

    def correction_dim(turns: float) -> float:
        return (rope_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rope_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return inv / factor * ramp + inv * (1 - ramp)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_mscale(c: LatentTrunkConfig) -> tuple[float, float]:
    """(m, cos/sin scale): the softmax scale takes ``m^2`` with ``m`` from
    ``mscale_all_dim``; cos and sin are scaled by the ratio of the two."""
    m = _mscale(c.rope_factor, c.rope_mscale_all_dim)
    return m, _mscale(c.rope_factor, c.rope_mscale) / m


def rope(x: jnp.ndarray, c: LatentTrunkConfig) -> jnp.ndarray:
    """x (N, L, ..., rope_dim): half-split pairs rotated by position x
    YaRN frequency, position = index along L."""
    half = c.rope_dim // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * yarn_inv_freq(
        c.rope_dim, c.rope_theta, c.rope_factor, c.rope_original_max,
        c.rope_beta_fast, c.rope_beta_slow,
    )
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    scale = yarn_mscale(c)[1]
    cos = (jnp.cos(angle) * scale).reshape(shape)
    sin = (jnp.sin(angle) * scale).reshape(shape)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ------------------------------------------------------------- sublayers' F
class _LatentAttention(nn.Module):
    """Causal self-attention through low-rank bottlenecks, no bias: queries
    through ``q_rank``, keys and values through ``kv_rank``, both normed; a
    head's key is ``nope_dim`` position-free dimensions of its own beside one
    ``rope_dim``-wide rotary key shared by every head."""

    cfg: LatentTrunkConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        c = self.cfg
        n, L, _ = h.shape
        heads, nope, rdim = c.n_heads, c.nope_dim, c.rope_dim
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name
        )
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)  # noqa: E731
        cq = norm("q_a_norm")(dense(c.q_rank, "q_a_proj")(h))
        q = dense(heads * (nope + rdim), "q_b_proj")(cq).reshape(n, L, heads, nope + rdim)
        kv_a = dense(c.kv_rank + rdim, "kv_a_proj")(h)
        ckv = norm("kv_a_norm")(kv_a[..., : c.kv_rank])
        kr = rope(kv_a[..., c.kv_rank:], c)                        # (n, L, rdim)
        kv = dense(heads * (nope + c.v_dim), "kv_b_proj")(ckv).reshape(
            n, L, heads, nope + c.v_dim
        )
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], c)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(kr[:, :, None, :], (n, L, heads, rdim))],
            axis=-1,
        )
        scale = (nope + rdim) ** -0.5 * yarn_mscale(c)[0] ** 2
        scores = jnp.einsum(
            "nqhd,nshd->nhqs", q, k, preferred_element_type=jnp.float32
        ) * scale
        allowed = attention_allowed(mask, None)[:, 0]               # (n, 1, L, L)
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        ctx = jnp.einsum("nhqs,nshd->nqhd", probs.astype(self.dtype), kv[..., nope:])
        return dense(c.dim, "o_proj")(ctx.reshape(n, L, heads * c.v_dim))


class _GatedFFN(nn.Module):
    """SwiGLU feed-forward, no bias: the leading dense layers' and the
    shared expert's."""

    dim: int
    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> jnp.ndarray:
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name
        )
        hidden = jax.nn.silu(dense(self.width, "gate_proj")(u)) * dense(self.width, "up_proj")(u)
        return dense(self.dim, "down_proj")(hidden)


def route_sigmoid(
    u: jnp.ndarray, w_router: jnp.ndarray, bias: jnp.ndarray, k: int, scale: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Router in float32 whatever the model's dtype: (T, d) -> the chosen
    experts (T, k) int32 and their weights (T, k). The scores are sigmoids;
    the choice is the top k of score + bias, the weights are the chosen
    scores over their sum, times ``scale``: the bias moves the choice and is
    not in the weights. The choice is not differentiated, so the bias takes
    no gradient. The chosen scores are read back through a one-hot product
    (``sparse_trunk.route`` says why)."""
    logits = jnp.einsum(
        "td,de->te", u.astype(jnp.float32), w_router.astype(jnp.float32), precision=HI
    )
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(lax.stop_gradient(scores + bias), k)
    chosen = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
    top = jnp.einsum("tke,te->tk", chosen, scores, precision=HI)
    weights = scale * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weights


class _RoutedFFN(nn.Module):
    """The feed-forward of a routed layer over tokens ``u`` (..., d): router,
    the experts held here, the shared expert."""

    cfg: LatentTrunkConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        c = self.cfg
        tokens = u.reshape(-1, c.dim)
        with jax.named_scope("moe_route"):
            w_router = self.param(
                "router", nn.initializers.lecun_normal(), (c.dim, c.n_experts)
            )
            bias = self.param("router_bias", nn.initializers.zeros, (c.n_experts,))
            idx, weights = route_sigmoid(
                tokens, w_router, bias, c.experts_per_token, c.routed_scale
            )
        y, counts, full_size = HeldExperts(c, jax.nn.silu, self.dtype, name="experts")(
            tokens, idx, weights
        )
        with jax.named_scope("shared_expert"):
            shared = _GatedFFN(
                c.dim, c.n_shared_experts * c.expert_dim, self.dtype, name="shared_expert"
            )(u)
        return y.reshape(u.shape) + shared, counts, full_size


# ---------------------------------------------------------------- the mixer
def _sinkhorn_step(m: jnp.ndarray, eps: float) -> jnp.ndarray:
    """One Sinkhorn iteration on (n, n, T): the columns, then the rows."""
    m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m / (jnp.sum(m, axis=1, keepdims=True) + eps)


class _Mixer(nn.Module):
    """The three maps of one sublayer's residual mixing, from the token's own
    state (T, n*d): ``pre`` (n, T) reads the streams into the sublayer, ``post``
    (n, T) writes its output back, ``res`` (n, n, T) mixes the streams, made
    doubly stochastic by Sinkhorn's iterations. Tokens lie along the last
    axis so that the n x n arithmetic runs over whole lanes. Float32
    throughout. Also returns how far from doubly stochastic ``res`` still
    is: the largest |sum - 1| of a row or column over the tokens."""

    cfg: LatentTrunkConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
        c = self.cfg
        n, width = c.n_streams, c.n_streams * c.dim
        z = RMSNorm(c.rms_norm_eps, jnp.float32, name="norm")(x)
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal")
        proj = jnp.concatenate([
            self.param("proj_pre", init, (width, n)),
            self.param("proj_post", init, (width, n)),
            self.param("proj_res", init, (width, n * n)),
        ], axis=-1)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,))
        bias_pre = self.param("bias_pre", nn.initializers.zeros, (n,))
        bias_post = self.param("bias_post", nn.initializers.zeros, (n,))
        # at rest the streams keep to themselves: Sinkhorn of exp(4 I) is
        # near the identity
        bias_res = self.param(
            "bias_res", lambda _, shape: 4.0 * jnp.eye(*shape), (n, n)
        )
        raw = jnp.einsum("tc,cm->mt", z, proj, precision=HI)        # (n + n + n*n, T)
        pre = jax.nn.sigmoid(alpha[0] * raw[:n] + bias_pre[:, None])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[n: 2 * n] + bias_post[:, None])
        res = alpha[2] * raw[2 * n:].reshape(n, n, -1) + bias_res[:, :, None]
        m = jnp.exp(jnp.clip(res, *c.res_clamp))
        m = lax.fori_loop(0, c.sinkhorn_iters, lambda _, m: _sinkhorn_step(m, c.hc_eps), m)
        off = lax.stop_gradient(m)
        err = jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(off, axis=0) - 1.0)),
            jnp.max(jnp.abs(jnp.sum(off, axis=1) - 1.0)),
        )
        return pre, post, m, err


class _Sublayer(nn.Module):
    """One mixed sublayer: ``X -> M X + Hpost^T F(Hpre X)`` with ``F`` the
    attention (``kind='attention'``), the dense feed-forward (``'dense'``)
    or the routed one (``'routed'``). The state is (T, n*d): a token's
    streams side by side along the last axis, tokens of all titles along the
    first, so that no array has a 4-long axis among its last two (a TPU
    tiles those by 8 x 128). Returns the new state, the mixer's distance
    from doubly stochastic and, from a routed sublayer, the tokens on each
    held expert and the chunks that ran at the sorted buffer's full size."""

    cfg: LatentTrunkConfig
    kind: str
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
        c = self.cfg
        n, d = c.n_streams, c.dim
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)  # noqa: E731
        per_token = lambda w: w[:, None]                         # noqa: E731 - (T,) -> (T, 1)
        with jax.named_scope("residual_mix"):
            pre, post, m, err = _Mixer(c, name="mixer")(x)
            streams = [x[:, j * d: (j + 1) * d].astype(jnp.float32) for j in range(n)]
            # float32 into the sublayer's norm: rounded to bfloat16 first,
            # x_in is no longer parallel to the streams where they are all
            # alike (the first sublayer), and the norm's backward leaves
            # `pre` a gradient of pure rounding where the true one is nought
            x_in = sum(per_token(pre[j]) * streams[j] for j in range(n))
        extra = ()
        if self.kind == "attention":
            with jax.named_scope("latent_attention"):
                h = norm("norm")(x_in).reshape(mask.shape + (d,))
                out = _LatentAttention(c, self.dtype, name="attn")(h, mask).reshape(-1, d)
        elif self.kind == "dense":
            with jax.named_scope("dense_ffn"):
                out = _GatedFFN(d, c.dense_dim, self.dtype, name="ffn")(norm("norm")(x_in))
        else:
            with jax.named_scope("moe_route"):
                u = norm("norm")(x_in)
            out, *extra = _RoutedFFN(c, self.dtype, name="ffn")(u)
        with jax.named_scope("residual_mix"):
            out32 = out.astype(jnp.float32)
            mixed = jnp.concatenate([
                sum(per_token(m[i, j]) * streams[j] for j in range(n))
                + per_token(post[i]) * out32
                for i in range(n)
            ], axis=-1).astype(self.dtype)
        return (mixed, err, *extra)


class LatentMoETrunk(nn.Module):
    """Token ids + attention mask -> per-token states (N, L, dim), and the
    trunk's counters: ``expert_tokens`` (routed layers, experts_held) int32,
    the (token, choice) pairs that fell on each held expert;
    ``absent_share``, the share of all pairs that fell on absent experts;
    ``full_size_chunks``, how many (layer, chunk)s ran at the sorted buffer's
    full size (``sparse_trunk.buffer_rows``), int32;
    ``residual_mix_err``, the largest distance from 1 of a row or
    column sum of a mixing matrix after its last Sinkhorn iteration, over
    tokens and sublayers."""

    cfg: LatentTrunkConfig = LatentTrunkConfig()
    dtype: jnp.dtype = jnp.float32
    remat: bool = False               # jax.checkpoint each sublayer

    @nn.compact
    def __call__(
        self, input_ids: jnp.ndarray, attention_mask: jnp.ndarray
    ) -> tuple[jnp.ndarray, dict]:
        c = self.cfg
        with jax.named_scope("trunk_embed"):
            table = self.param(
                "embedding", nn.initializers.normal(0.02), (c.vocab_held, c.dim)
            )
            local = input_ids.reshape(-1) - c.vocab_first
            held = (local >= 0) & (local < c.vocab_held)
            rows = table[jnp.clip(local, 0, c.vocab_held - 1)]
            x = jnp.where(held[:, None], rows, 0).astype(self.dtype)
        with jax.named_scope("residual_mix"):
            x = jnp.tile(x, (1, c.n_streams))
        # the held experts' output is kept: their forward, which the choice
        # between the buffer's sizes runs again beside its transposes, is
        # then not run a third time for the sake of ``post``'s gradient
        keep = jax.checkpoint_policies.save_only_these_names(HELD_EXPERTS_OUTPUT)
        sublayer = nn.remat(_Sublayer, policy=keep) if self.remat else _Sublayer
        routed, errs = [], []
        for i in range(c.n_layers):
            ffn = "dense" if i < c.n_dense_layers else "routed"
            x, err = sublayer(c, "attention", self.dtype, name=f"layer_{i}_attn")(
                x, attention_mask
            )
            x, err_ffn, *of_layer = sublayer(c, ffn, self.dtype, name=f"layer_{i}_ffn")(
                x, attention_mask
            )
            errs += [err, err_ffn]
            if of_layer:
                routed.append(of_layer)
        with jax.named_scope("residual_mix"):
            x = jnp.sum(
                x.reshape(-1, c.n_streams, c.dim), axis=1, dtype=jnp.float32
            ).astype(self.dtype)
        x = RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")(x)
        x = x.reshape(input_ids.shape + (c.dim,))
        counters = {"residual_mix_err": jnp.max(jnp.stack(errs))}
        if routed:
            expert_tokens, full_size = map(jnp.stack, zip(*routed))
            pairs = c.n_routed_layers * input_ids.size * c.experts_per_token
            counters["expert_tokens"] = expert_tokens
            counters["absent_share"] = 1.0 - jnp.sum(expert_tokens) / jnp.float32(pairs)
            counters["full_size_chunks"] = jnp.sum(full_size)
        return x, counters
