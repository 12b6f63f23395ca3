"""Gated grouped-query decoder trunk whose window and full layers differ in
head count, rotary and mask, read as a text encoder (Flax linen).

A causal decoder in the ``laguna`` key set: token ids -> embedding ->
pre-norm decoder layers -> final RMSNorm -> per-token states, which
``models.bert.TextEncoder`` pools with the repo's additive head. Trained in
loop by the click loss (``text_encoder_mode='finetune'``) over texts longer
than the window; no language-model head is built.

A layer's KIND (``full`` every ``full_every``-th layer from layer 0,
``window`` the others) decides four things at once: the number of query
heads ``H`` (48 / 64 over 8 key/value heads of 128), the rotary law, the
share of the head it rotates, and the mask. With ``h = RMSNorm(x; g1)``::

    q, k, v = h Wq, h Wk, h Wv            q: H heads, k and v: 8
    q, k    = RMSNorm_128(q; gq), RMSNorm_128(k; gk)      over a head
    window:   rotary over the whole head, theta 10,000
    full:     rotary over the head's first half, YaRN's frequencies for that
              width (theta 500,000), cos and sin times ``attention_factor``;
              the second half passes unrotated
    s_it    = q_i . k_t / sqrt(128), t <= i, key t a real token and, in a
              window layer, i - t < 512; head j reads key/value head j // (H / 8)
    ctx     = softmax(s) v
    gate    = softplus(h Wg)               (2048 -> H, float32: a scalar a
                                            head a token)
    x'      = x + (gate_j ctx_j, heads side by side) Wo

The core (scores, softmax, values) is ``ops.chunked_attention``: blocked,
grouped, visiting only the key blocks inside a query block's band; no
``L x L`` array exists, forward or backward. Feed-forward, with ``u =
RMSNorm(x'; g2)``: the first ``n_dense_layers`` layers ``Wdown(silu(Wgate u)
* Wup u)``; the others ``latent_trunk._RoutedFFN``: a sigmoid router with a
selection bias over ALL experts, the chosen scores normalised and scaled,
the experts HELD here (``sparse_trunk.HeldExperts``, told its share as
there) and a shared expert computed whole. The embedding holds
``vocab_held`` rows from ``vocab_first``; an id outside the slice embeds to
zero.

Memory. Every sublayer runs over equal chunks of whole texts
(``_OverTextChunks``; texts do not attend to each other, so that is exact),
each chunk rematerialised (``remat=True``): the backward keeps a sublayer's
input and runs one chunk's forward again, so the 8,192-wide queries, their
float32 normed and rotated copies and the context exist for one chunk at a
time. Attention and the dense feed-forward take at most
``TEXT_CHUNK_TOKENS`` tokens a chunk (one 1,024-token text), the routed
feed-forward, router included (its one-hot read-back of the chosen scores is
(tokens, 8, 256) float32), at most ``ROUTED_CHUNK_TOKENS`` (11 such texts:
its grouped products want rows), its held experts' output kept by name
beside the chunk's input (``latent_trunk`` says why); at 11,264 tokens a
chunk ``HeldExperts`` needs no chunks of its own.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from fedrec_tpu.models.latent_trunk import _GatedFFN, _RoutedFFN, yarn_inv_freq
from fedrec_tpu.models.sparse_trunk import HELD_EXPERTS_OUTPUT, RMSNorm, rotary
from fedrec_tpu.ops.chunked_attention import chunked_attention, scores_computed_share

HI = lax.Precision.HIGHEST
KINDS = ("full", "window")
# the blocked core's query block, and the longest run of keys it takes in
# one softmax pass (a text's whole band: XLA's passes over a block's scores
# cost by their elements, and a second run would add the accumulator's)
CORE_BLOCK_Q = 128
CORE_BLOCK_K = 1024
# attention and the dense feed-forward take whole texts in chunks of at most
# TEXT_CHUNK_TOKENS tokens, the routed feed-forward of at most
# ROUTED_CHUNK_TOKENS. One 1,024-token text a chunk for the first two: the
# chip ran a step of 55 such texts in 1.48 s at 1 text a chunk, 1.76 s at 5
# and 2.07 s at 11 (a chunk's float32 passes over 8,192-wide queries and
# over a query block's scores stay near the chip's fast memory), while the
# routed chunks want rows for their grouped products: 11 texts a chunk gave
# an expert 352 rows and the step 0.13 s over 5 (PERF.md section 6, PR 35)
TEXT_CHUNK_TOKENS = 1024
ROUTED_CHUNK_TOKENS = 16384


@dataclass(frozen=True)
class WindowTrunkConfig:
    """Architecture knobs; defaults = ``Laguna-XS.2``
    (huggingface.co/poolside/Laguna-XS.2, config.json), whole: every expert
    and every vocabulary row held here. Its experts are SwiGLU."""

    vocab_size: int = 100352
    dim: int = 2048                    # hidden_size
    n_layers: int = 40
    n_dense_layers: int = 1            # leading "dense" entries of mlp_layer_types
    full_heads: int = 48               # num_attention_heads (full layers)
    window_heads: int = 64             # num_attention_heads_per_layer (window layers)
    n_kv_heads: int = 8
    head_dim: int = 128
    full_every: int = 4                # layer_types' period: full, then 3 window
    sliding_window: int = 512
    dense_dim: int = 8192              # intermediate_size
    n_experts: int = 256               # num_experts
    experts_per_token: int = 8
    expert_dim: int = 512              # moe_intermediate_size
    n_shared_experts: int = 1          # shared_expert_intermediate_size / expert_dim
    routed_scale: float = 2.5          # moe_routed_scaling_factor
    rms_norm_eps: float = 1e-6
    # rope_parameters.sliding_attention (type default, the whole head)
    window_rope_theta: float = 10000.0
    # rope_parameters.full_attention (type yarn)
    full_rope_theta: float = 500000.0
    full_rotary_share: float = 0.5     # partial_rotary_factor
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 64.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.4158883083359672
    # this chip's share of a layer
    first_expert: int = 0
    experts_held: int = 256
    vocab_first: int = 0
    vocab_held: int = 100352

    def __post_init__(self):
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"{self.n_dense_layers} leading dense layers are not among "
                f"the {self.n_layers} layers"
            )
        for kind in KINDS:
            if self.heads(kind) % self.n_kv_heads:
                raise ValueError(
                    f"{self.heads(kind)} query heads of a {kind} layer do not group "
                    f"over {self.n_kv_heads} key/value heads"
                )
        if self.full_rotary_dim % 2 or self.head_dim % 2:
            raise ValueError(
                f"rotary pairs need an even width, not {self.full_rotary_dim} of {self.head_dim}"
            )
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

    def kind(self, layer: int) -> str:
        return "full" if layer % self.full_every == 0 else "window"

    def heads(self, kind: str) -> int:
        return self.full_heads if kind == "full" else self.window_heads

    @property
    def full_rotary_dim(self) -> int:
        return int(self.head_dim * self.full_rotary_share)

    @property
    def n_routed_layers(self) -> int:
        return self.n_layers - self.n_dense_layers


def window_trunk_config_from(model_cfg) -> WindowTrunkConfig:
    """WindowTrunkConfig from a ``ModelConfig``: the depth, the share held
    and the widths tests shrink come from it (``trunk_heads`` is the full
    layers' count), the rest is as published."""
    base = WindowTrunkConfig()
    return WindowTrunkConfig(
        dim=model_cfg.bert_hidden,
        n_layers=model_cfg.trunk_layers,
        n_dense_layers=model_cfg.trunk_dense_layers,
        full_heads=model_cfg.trunk_heads,
        expert_dim=model_cfg.trunk_ffn,
        first_expert=model_cfg.trunk_first_expert,
        experts_held=model_cfg.trunk_experts_held or base.n_experts,
        vocab_held=model_cfg.trunk_vocab,
    )


def required_flops_per_token(c: WindowTrunkConfig, length: int) -> float:
    """Forward operations the trunk's products require for one token of a
    text of ``length`` tokens (a multiply-add is 2): projections and gate,
    the attention core over the causal band's pairs only, the dense or the
    routed feed-forward with the pairs expected on held experts under a
    uniform router. What ``obs.perf.flops_per_train_step`` prices the trunk
    by; nothing recomputed is in it."""
    d, hd, kv = c.dim, c.head_dim, c.n_kv_heads
    total = 0.0
    for layer in range(c.n_layers):
        kind = c.kind(layer)
        heads = c.heads(kind)
        w = min(c.sliding_window, length) if kind == "window" else length
        pairs = w * (w + 1) // 2 + (length - w) * w
        total += 2.0 * d * (2 * heads * hd + 2 * kv * hd + heads) + heads * pairs / length * 4.0 * hd
        if layer < c.n_dense_layers:
            total += 3 * 2.0 * d * c.dense_dim
        else:
            on_held = c.experts_per_token * c.experts_held / c.n_experts
            total += 2.0 * d * c.n_experts + 3 * 2.0 * d * c.expert_dim * (c.n_shared_experts + on_held)
    return total


# ------------------------------------------------------------------ rotary
def rotate(x: jnp.ndarray, c: WindowTrunkConfig, kind: str) -> jnp.ndarray:
    """x (N, L, ..., head_dim) by its layer's law, position = index along L.
    A window layer: ``sparse_trunk.rotary`` over the whole head. A full
    layer: the first ``full_rotary_dim`` dimensions by YaRN's frequencies
    for that width, cos and sin times the attention factor; the rest
    untouched."""
    if kind == "window":
        return rotary(x, c.window_rope_theta)
    width = c.full_rotary_dim
    half = width // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * yarn_inv_freq(
        width, c.full_rope_theta, c.rope_factor, c.rope_original_max,
        c.rope_beta_fast, c.rope_beta_slow,
    )
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos = (jnp.cos(angle) * c.rope_attention_factor).reshape(shape)
    sin = (jnp.sin(angle) * c.rope_attention_factor).reshape(shape)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:width]
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype), (x2 * cos + x1 * sin).astype(x.dtype),
         x[..., width:]], axis=-1,
    )


def _text_chunks(texts: int, length: int, most: int) -> int:
    """The fewest equal chunks of whole texts of at most ``most`` tokens
    (one text a chunk where a single text is longer)."""
    return next(
        c for c in range(1, texts + 1)
        if texts % c == 0 and (texts // c * length <= most or c == texts)
    )


# ---------------------------------------------------------------- sublayers
class _Attention(nn.Module):
    """``x + Wo(gate * core(q, k, v))`` of the normed input for one chunk of
    whole texts; ``kind`` gives the heads, the rotary and the mask."""

    cfg: WindowTrunkConfig
    kind: str
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        c, kind = self.cfg, self.kind
        n, L, _ = x.shape
        heads, kv, hd = c.heads(kind), c.n_kv_heads, c.head_dim
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name
        )
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)  # noqa: E731
        with jax.named_scope("window_attention"):
            h = norm("norm")(x)
            q = norm("q_norm")(dense(heads * hd, "q_proj")(h).reshape(n, L, heads, hd))
            k = norm("k_norm")(dense(kv * hd, "k_proj")(h).reshape(n, L, kv, hd))
            v = dense(kv * hd, "v_proj")(h).reshape(n, L, kv, hd)
            q, k = rotate(q, c, kind), rotate(k, c, kind)
            with jax.named_scope("attention_core"):
                ctx = chunked_attention(
                    q, k, v, mask, CORE_BLOCK_Q, CORE_BLOCK_K, causal=True,
                    window=c.sliding_window if kind == "window" else None,
                )
            w_gate = self.param("gate", nn.initializers.lecun_normal(), (c.dim, heads))
            gate = jax.nn.softplus(jnp.einsum(
                "nld,dh->nlh", h.astype(jnp.float32), w_gate.astype(jnp.float32), precision=HI
            ))
            ctx = (ctx.astype(jnp.float32) * gate[..., None]).astype(self.dtype)
            return x + dense(c.dim, "o_proj")(ctx.reshape(n, L, heads * hd))


class _DenseFFN(nn.Module):
    """``x + SwiGLU(norm(x))`` at the dense width, for one chunk of texts."""

    cfg: WindowTrunkConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        c = self.cfg
        with jax.named_scope("dense_ffn"):
            u = RMSNorm(c.rms_norm_eps, self.dtype, name="norm")(x)
            return x + _GatedFFN(c.dim, c.dense_dim, self.dtype, name="ffn")(u)


class _RoutedFFNSublayer(nn.Module):
    """``x + routed(norm(x))`` for one chunk of texts, with the tokens on
    each held expert and whether the chunk ran at the sorted buffer's full
    size. The router's one-hot read-back is (tokens, 8, 256) float32: over
    all 56,320 tokens of a step it and its transposes would be gigabytes."""

    cfg: WindowTrunkConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
        c = self.cfg
        with jax.named_scope("moe_route"):
            u = RMSNorm(c.rms_norm_eps, self.dtype, name="norm")(x)
        out, counts, full_size = _RoutedFFN(c, self.dtype, name="ffn")(u)
        return x + out, counts, full_size


class _OverTextChunks(nn.Module):
    """``body`` (a sublayer over (texts, L, d) and their mask) run over equal
    chunks of whole texts, one after the other, each chunk rematerialised in
    the backward pass (``remat``: the chunk's input is kept, and whatever
    ``policy`` names); the parameters are the body's own. Returns the new
    state and, summed over the chunks, whatever else the body returns."""

    body: type
    args: tuple
    most: int                         # tokens a chunk, at most
    remat: bool
    policy: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask: jnp.ndarray) -> tuple[jnp.ndarray, ...]:
        n, L, d = x.shape
        chunks = _text_chunks(n, L, self.most)
        body = self.body
        if self.remat:
            body = nn.remat(body, prevent_cse=chunks == 1, policy=self.policy)
        as_tuple = lambda out: out if isinstance(out, tuple) else (out,)  # noqa: E731
        if chunks == 1:
            return as_tuple(body(*self.args, name="chunk")(x, mask))

        def step(module, carry, xs):
            return carry, as_tuple(module(*xs))

        scan = nn.scan(
            step, variable_broadcast="params", split_rngs={"params": False},
        )
        split = lambda a: a.reshape((chunks, n // chunks) + a.shape[1:])  # noqa: E731
        # the loop's own ops (a chunk's slice in, its rows stacked out); the
        # body's ops keep their innermost scopes
        with jax.named_scope("chunk_stack"):
            _, (y, *counters) = scan(
                body(*self.args, name="chunk"), None, (split(x), split(mask))
            )
            return (y.reshape(n, L, d), *(jnp.sum(v, axis=0) for v in counters))


class WindowMoETrunk(nn.Module):
    """Token ids + attention mask -> per-token states (N, L, dim), and the
    trunk's counters: ``expert_tokens`` (routed layers, experts_held) int32,
    the (token, choice) pairs that fell on each held expert;
    ``absent_share``, the share of all pairs that fell on absent experts;
    ``full_size_chunks``, how many (layer, chunk)s ran at the sorted buffer's
    full size (``sparse_trunk.buffer_rows``), int32;
    ``attention_scores_computed_share`` (2,): the score elements the blocked
    core computes over the ``L^2`` of a head's square, in a full layer and
    in a window layer (``KINDS``' order)."""

    cfg: WindowTrunkConfig = WindowTrunkConfig()
    dtype: jnp.dtype = jnp.float32
    remat: bool = False               # jax.checkpoint each sublayer('s chunk)

    @nn.compact
    def __call__(
        self, input_ids: jnp.ndarray, attention_mask: jnp.ndarray
    ) -> tuple[jnp.ndarray, dict]:
        c = self.cfg
        with jax.named_scope("trunk_embed"):
            table = self.param(
                "embedding", nn.initializers.normal(0.02), (c.vocab_held, c.dim)
            )
            local = input_ids - c.vocab_first
            held = (local >= 0) & (local < c.vocab_held)
            rows = table[jnp.clip(local, 0, c.vocab_held - 1)]
            x = jnp.where(held[..., None], rows, 0).astype(self.dtype)
        # the held experts' output is kept beside a routed chunk's input
        # (``latent_trunk.LatentMoETrunk`` says why)
        keep = jax.checkpoint_policies.save_only_these_names(HELD_EXPERTS_OUTPUT)
        over_chunks = lambda body, args, most, i, part, policy=None: _OverTextChunks(  # noqa: E731
            body, args, most, self.remat, policy, name=f"layer_{i}_{part}"
        )
        routed = []
        for i in range(c.n_layers):
            x, = over_chunks(_Attention, (c, c.kind(i), self.dtype), TEXT_CHUNK_TOKENS, i, "attn")(
                x, attention_mask
            )
            if i < c.n_dense_layers:
                x, = over_chunks(_DenseFFN, (c, self.dtype), TEXT_CHUNK_TOKENS, i, "ffn")(
                    x, attention_mask
                )
            else:
                x, *of_layer = over_chunks(
                    _RoutedFFNSublayer, (c, self.dtype), ROUTED_CHUNK_TOKENS, i, "ffn", keep
                )(x, attention_mask)
                routed.append(of_layer)
        x = RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")(x)
        L = input_ids.shape[-1]
        counters = {"attention_scores_computed_share": jnp.asarray([
            scores_computed_share(
                L, min(CORE_BLOCK_Q, L), True, c.sliding_window if kind == "window" else None
            ) for kind in KINDS
        ], jnp.float32)}
        if routed:
            expert_tokens, full_size = map(jnp.stack, zip(*routed))
            pairs = c.n_routed_layers * input_ids.size * c.experts_per_token
            counters["expert_tokens"] = expert_tokens
            counters["absent_share"] = 1.0 - jnp.sum(expert_tokens) / jnp.float32(pairs)
            counters["full_size_chunks"] = jnp.sum(full_size)
        return x, counters
