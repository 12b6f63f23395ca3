"""Sparse-expert decoder trunk, read as a text encoder (Flax linen).

A causal decoder whose feed-forward is a routed mixture of ReGLU experts:
token ids -> decoder layers -> final RMSNorm -> per-token states, which
``models.bert.TextEncoder`` pools with the repo's additive head. Trained in
loop by the click loss (``text_encoder_mode='finetune'``); no language-model
head is built (nothing in the click loss reads logits).

One layer, for tokens ``x`` (T x d)::

    h   = RMSNorm(x; g1)
    r   = h Wr                      router over ALL experts, reads the
    S,I = top_k(r); p = softmax(S)  pre-attention normed input; float32
    q,k,v = h Wq, h Wk, h Wv        rotary on q,k in sliding layers only
    a   = softmax(q k^T / sqrt(D) + mask_l) v   grouped-query
    x'  = x + a Wo
    u   = RMSNorm(x'; g2)
    y   = sum over e in I, e HELD HERE, of p_e Wdown_e(relu(Wgate_e u) * Wup_e u)
    out = x' + y

Layer ``l`` with ``l % global_every == 0`` is global (full causal mask, no
positional encoding); the others are sliding (causal, keys ``j`` with
``i - j < sliding_window``, rotary over the whole head).

The expert layer is told which experts it holds (``first_expert``,
``experts_held``): it routes over all of them and computes its own experts'
part of the result, as one chip of an expert-parallel group does before the
exchange. What the absent experts would add is left out; nothing stands in
for the absent chips. The embedding likewise holds ``vocab_held`` rows from
``vocab_first``; an id outside the slice embeds to zero.

No token is dropped: the assignments are sorted by expert and the grouped
products (``grouped_matmul``: ``jax.lax.ragged_dot``, XLA:TPU's grouped
matmul kernel) run over the true group sizes. Shapes are static, so the
sorted buffer has one of two sizes (``buffer_rows``). The full size is the
worst case, every choice of every token on a held expert. The small size
follows the share of the experts held: ``EVEN_SHARE_ROOM`` (twice) what an
even router sends here, a half of the full size with 16 of 64 experts held,
a quarter with 8 of 64. Every pass over the buffer (the row gathers, the
selects that zero its unwritten rows, the products' outputs and all their
transposes) costs by its rows, whatever they hold. What runs over a token's
k choices (the weighted sum of the products' output, and the cotangents'
way back to the tokens) is one Pallas kernel, ``ops.pair_sum``: it copies
the held groups' rows once, fetches for each token only the rows of its
pairs on held experts (a quarter or an eighth of the pairs; the rest are
not read), sums them in float32 in choice order and writes the token's row
once. No array is laid out pair by pair (``to_expert_order``,
``weighted_sum``). The backward pass takes the way back to the tokens last,
after every weight's gradient (``_at_rows_bwd``), so the kernel's copy of
the buffer's cotangent is never live beside the products' intermediates.
A chunk whose router sent more pairs here than the small size holds runs at
the full size, one ``lax.cond`` between the two sizes of the same body on
the count the sort gives (``_either_size``), so a router that drifts is
served exactly, at the worst case's cost; ``full_size_chunks`` counts those
chunks. Twice, because the share on the held experts read within 0.7 to 1.5
of the even share over the benchmark's seeds (PERF.md section 6, PR 33 and
34). Where half the experts or more are held the two sizes are one and there
is no conditional. Under an in-device cohort's ``vmap`` the count is per
client, the conditional becomes a select and both sizes run for every
client: exact, and slower than one size by the small one (test widths only
so far).

The layer works through the tokens in chunks of at most ``MAX_CHUNK_TOKENS``,
each chunk rematerialised in the backward pass: memory is bounded by one
chunk's worst case, compute follows the rows really there.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from fedrec_tpu.ops.pair_sum import pair_sum

# the expert layer's sorted buffer is whole tiles of ROW_TILE rows: at its
# full size chunk x experts_per_token rows, at its small size EVEN_SHARE_ROOM
# times the rows an even router fills
MAX_CHUNK_TOKENS = 16384
ROW_TILE = 512
EVEN_SHARE_ROOM = 2
# the name ``HeldExperts`` gives its output for a caller's remat policy
HELD_EXPERTS_OUTPUT = "held_experts_output"


@dataclass(frozen=True)
class SparseTrunkConfig:
    """Architecture knobs; defaults = ``SmallThinker-21BA3B-Instruct``
    (huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct, config.json),
    whole: every expert and every vocabulary row held here. Its experts are
    ReGLU: this trunk hands ``jax.nn.relu`` to ``HeldExperts``."""

    vocab_size: int = 151936
    dim: int = 2560                    # hidden_size
    n_layers: int = 52
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 64                # moe_num_primary_experts
    experts_per_token: int = 6         # moe_num_active_primary_experts
    expert_dim: int = 768              # moe_ffn_hidden_size
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    sliding_window: int = 4096
    global_every: int = 4              # sliding_window_layout / rope_layout period
    # this chip's share of a layer
    first_expert: int = 0
    experts_held: int = 64
    vocab_first: int = 0
    vocab_held: int = 151936

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} query heads do not group over "
                f"{self.n_kv_heads} key/value heads"
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

    def is_global(self, layer: int) -> bool:
        return layer % self.global_every == 0


def sparse_trunk_config_from(model_cfg) -> SparseTrunkConfig:
    """SparseTrunkConfig from a ``ModelConfig``: the depth, the share held
    and the widths tests shrink come from it, the rest is as published."""
    base = SparseTrunkConfig()
    return SparseTrunkConfig(
        dim=model_cfg.bert_hidden,
        n_layers=model_cfg.trunk_layers,
        n_heads=model_cfg.trunk_heads,
        expert_dim=model_cfg.trunk_ffn,
        first_expert=model_cfg.trunk_first_expert,
        experts_held=model_cfg.trunk_experts_held or base.n_experts,
        vocab_held=model_cfg.trunk_vocab,
    )


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding over the whole head, half-split pairing
    (``rotate_half``): x is (N, L, ..., D), position = index along L."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def attention_allowed(
    mask: jnp.ndarray, window: int | None
) -> jnp.ndarray:
    """(N, L) key mask -> (N, 1, 1, L, L) bool: query i may read key j when
    j <= i, key j is a real token and, in a sliding layer, i - j < window."""
    L = mask.shape[-1]
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    allowed = j <= i
    if window is not None:
        allowed &= (i - j) < window
    return allowed[None, None, None] & (mask[:, None, None, None, :] > 0)


class _Attention(nn.Module):
    """Grouped-query causal self-attention, no bias. Query head ``h`` reads
    key/value head ``h // (n_heads / n_kv_heads)``."""

    cfg: SparseTrunkConfig
    is_global: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        c = self.cfg
        n, L, _ = h.shape
        kv, group, hd = c.n_kv_heads, c.n_heads // c.n_kv_heads, c.head_dim
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name
        )
        q = dense(c.n_heads * hd, "q_proj")(h).reshape(n, L, kv, group, hd)
        k = dense(kv * hd, "k_proj")(h).reshape(n, L, kv, hd)
        v = dense(kv * hd, "v_proj")(h).reshape(n, L, kv, hd)
        if not self.is_global:
            q, k = rotary(q, c.rope_theta), rotary(k, c.rope_theta)
        scores = jnp.einsum(
            "nqkgd,nskd->nkgqs", q, k, preferred_element_type=jnp.float32
        ) / jnp.sqrt(jnp.float32(hd))
        allowed = attention_allowed(
            mask, None if self.is_global else c.sliding_window
        )
        probs = jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1)
        ctx = jnp.einsum("nkgqs,nskd->nqkgd", probs.astype(self.dtype), v)
        return dense(c.dim, "o_proj")(ctx.reshape(n, L, c.n_heads * hd))


def route(
    h: jnp.ndarray, w_router: jnp.ndarray, k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Router in float32 whatever the model's dtype, so that the choice of
    experts does not turn on a rounding of ``w_router``: (T, d) -> the
    chosen experts (T, k) int32 and their weights (T, k), a softmax over
    the k chosen. The chosen logits are read back through a one-hot
    product, whose transpose is a product too (``top_k``'s is a scatter,
    which a TPU runs row by row)."""
    logits = jnp.einsum(
        "td,de->te", h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    _, idx = lax.top_k(lax.stop_gradient(logits), k)
    chosen = jax.nn.one_hot(idx, logits.shape[-1], dtype=logits.dtype)
    top = jnp.einsum("tke,te->tk", chosen, logits, precision=lax.Precision.HIGHEST)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


# Rows move between token order and expert order (the (token, choice) pairs,
# token t's choice c being pair t*k + c, sorted by held expert, then the
# absent ones, then padding) and no array is ever laid out in pair order:
# ``order`` (rows,) names the pair of every row of the buffer, ``back`` (k, T),
# choice-major, the row of every pair, and ``held`` the rows of the held
# groups. (A (T, k, d) array has k = 4, 6 or 8 rows in a tile of 8 or 16, and
# every reshape between it and (T*k, d) moves all of it: PERF.md section 6.)
# What runs over a token's k choices is ``ops.pair_sum``: the rows of
# the pairs on held experts, read and summed a token at a time, one row
# written a token; a row at or past ``held`` is not read (the products leave
# it unwritten). Each derivative is a gather or a pair sum too, where the
# transpose of a gather is a scatter (which a TPU runs row by row). The
# buffer may be cut short of the absent pairs (``buffer_rows``): ``order`` is
# cut to its rows, and a ``back`` that points past them is past ``held`` too.
@jax.custom_vjp
def to_expert_order(u, order, back, held):
    """(T, d) token rows -> (rows, d): row i is the token of pair order[i]."""
    return u[jnp.minimum(order // back.shape[0], u.shape[0] - 1)]


def _to_expert_order_bwd(res, ct):
    back, held = res
    return pair_sum(ct, back, None, held), None, None, None


to_expert_order.defvjp(
    lambda u, order, back, held: (to_expert_order(u, order, back, held), (back, held)),
    _to_expert_order_bwd,
)


@jax.custom_vjp
def weighted_sum(down, p, order, back, held):
    """(rows, d) in expert order, weights (T, k) -> (T, d):
    ``y[t] = sum_c p[t, c] * down[back[c, t]]`` over the pairs in the held
    groups, the products of the rows' dtype summed in float32."""
    return pair_sum(down, back, p, held)


def _weighted_sum_bwd(res, ct):
    down, p, order, back = res
    rows, (t, k) = order.shape[0], p.shape
    # a row of the buffer takes its own pair's weight and its token's
    # cotangent; the padding entries (they sort last, past every pair) none
    pair = jnp.minimum(order, t * k - 1)
    weight = jnp.where(order < t * k, p.reshape(-1)[pair], 0).astype(ct.dtype)
    of_token = ct[pair // k].astype(jnp.float32)
    d_down = of_token * weight.astype(jnp.float32)[:, None]
    # <ct[t], down[row of pair (t, c)]>, summed over d row by row of the
    # buffer and read back pair by pair: scalars, not rows
    along = jnp.sum(of_token * down.astype(jnp.float32), axis=-1)
    d_p = jnp.where(back < rows, along[jnp.minimum(back, rows - 1)], 0).T
    return d_down.astype(ct.dtype), d_p.astype(p.dtype), None, None, None


weighted_sum.defvjp(
    lambda down, p, order, back, held: (pair_sum(down, back, p, held), (down, p, order, back)),
    _weighted_sum_bwd,
)


def buffer_rows(pairs: int, held: int, n_experts: int) -> tuple[int, int]:
    """The sorted buffer's two sizes for ``pairs`` (token, choice) pairs with
    ``held`` of ``n_experts`` experts here, in whole row tiles: (small, full).
    Full holds every pair; small holds ``EVEN_SHARE_ROOM`` times the pairs an
    even router sends to the held experts, and never more than full."""
    tiles = lambda rows: -(-rows // ROW_TILE) * ROW_TILE  # noqa: E731
    full = tiles(pairs)
    return min(tiles(-(-EVEN_SHARE_ROOM * pairs * held // n_experts)), full), full


def _chunks(tokens: int) -> int:
    """The fewest equal chunks of at most MAX_CHUNK_TOKENS tokens."""
    return next(
        c for c in range(1, tokens + 1)
        if tokens % c == 0 and tokens // c <= MAX_CHUNK_TOKENS
    )


# The grouped product: rows of ``x`` in consecutive groups of ``sizes``, group
# g times ``w[g]``. XLA:TPU compiles ``lax.ragged_dot`` to its grouped-matmul
# kernel but refuses it a batch dimension, which is what ``vmap`` over an
# in-device cohort of clients gives it; so under ``vmap`` the product and its
# transposes run client by client (``lax.map``), everything around them stays
# batched. The loop is not differentiated: the cotangents are stated here.
_product = jax.custom_batching.sequential_vmap(lax.ragged_dot)


@jax.custom_batching.sequential_vmap
def _product_transposes(x, w, sizes, ct):
    _, pull = jax.vjp(lambda x, w: lax.ragged_dot(x, w, sizes), x, w)
    return pull(ct)


@jax.custom_vjp
def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray) -> jnp.ndarray:
    """(rows, d) x (groups, d, f) -> (rows, f); rows past the groups are
    left unwritten."""
    return _product(x, w, sizes)


grouped_matmul.defvjp(
    lambda x, w, sizes: (_product(x, w, sizes), (x, w, sizes)),
    lambda res, ct: (*_product_transposes(*res, ct), None),
)


def _route(rows, ints):
    """The buffer's ``order`` cut to ``rows``, ``back``, the held groups' sizes
    within the buffer, the rows they fill (``held``) and which rows those are.
    The cut is a no-op wherever this size's result is used; under a cohort's
    vmap both sizes run, and the groups of a client whose pairs overflow this
    one must not reach past the buffer."""
    order, back, sizes = ints
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    return order[:rows], back, jnp.diff(ends, prepend=0), ends[-1], (jnp.arange(rows) < ends[-1])[:, None]


def _to_expert_order(u, route):
    """The buffer's rows, those past the held groups zero."""
    order, back, _, held, here = route
    return jnp.where(here, to_expert_order(u, order, back, held), 0)


def _experts(activation, route, xs, p, w_gate, w_up, w_down):
    order, back, sizes, held, here = route
    with jax.named_scope("moe_experts"):
        gate = jnp.where(here, grouped_matmul(xs, w_gate, sizes), 0)
        up = jnp.where(here, grouped_matmul(xs, w_up, sizes), 0)
        down = grouped_matmul(activation(gate) * up, w_down, sizes)
        down = jnp.where(here, down, 0)
    with jax.named_scope("moe_combine"):
        return weighted_sum(down, p, order, back, held)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _at_rows(rows, activation, ints, u, p, w_gate, w_up, w_down):
    """One chunk's output through a sorted buffer of ``rows`` rows: the body
    of ``held_experts_output`` at one of its sizes. Rows past the held groups
    are pairs on absent experts (and padding): a grouped product leaves them
    unwritten, so they are zeroed on the way in and out (and, by the same
    selects transposed, in the backward pass, but for the way back to the
    tokens, which does not read them)."""
    with jax.named_scope("moe_route"):
        route = _route(rows, ints)
        xs = _to_expert_order(u, route)
    return _experts(activation, route, xs, p, w_gate, w_up, w_down)


def _at_rows_bwd(rows, activation, res, ct):
    """The forward again, its transposes, and the tokens' cotangent as the
    pair sum of the rows' (``to_expert_order``'s transpose). The sum waits
    for every weight's gradient: it copies the held rows of the buffer's
    cotangent, and taken earlier that copy would be live beside the grouped
    products' intermediates and the weights' transposed copies (one more
    buffer-sized array at the chunk's peak)."""
    ints, u, *floats = res
    with jax.named_scope("moe_route"):
        route = _route(rows, ints)
        xs = _to_expert_order(u, route)
    d_xs, *d_floats = jax.vjp(partial(_experts, activation, route), xs, *floats)[1](ct)
    d_xs, d_floats = lax.optimization_barrier((d_xs, d_floats))
    with jax.named_scope("moe_route"):
        # the rows past the held groups are not read: their select's
        # transpose is left out
        order, back, _, held, _ = route
        d_u, = jax.vjp(lambda u: to_expert_order(u, order, back, held), u)[1](d_xs)
    return None, d_u, *d_floats


_at_rows.defvjp(
    lambda rows, activation, ints, *floats: (_at_rows(rows, activation, ints, *floats), (ints, *floats)),
    _at_rows_bwd,
)


# The choice between the buffer's two sizes, differentiated by hand: left to
# JAX, the forward conditional would hand the backward one every residual of
# BOTH branches, the branch not taken filling the other's with zeros at their
# size. Here the forward keeps the chunk's inputs and the backward
# conditional runs the taken size's forward again beside its transposes,
# which is what the chunk's (and the layer's) rematerialisation does anyway.
@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _either_size(sizes, activation, overflow, ints, *floats):
    small, full = sizes
    return lax.cond(
        overflow,
        partial(_at_rows, full, activation),
        partial(_at_rows, small, activation),
        ints, *floats,
    )


def _either_size_bwd(sizes, activation, res, ct):
    overflow, ints, floats = res

    def transposes(rows):
        def run(ints, floats, ct):
            return _at_rows_bwd(rows, activation, (ints, *floats), ct)[1:]
        return run

    small, full = sizes
    grads = lax.cond(overflow, transposes(full), transposes(small), ints, floats, ct)
    return (None, None, *grads)


_either_size.defvjp(
    lambda sizes, activation, overflow, ints, *floats: (
        _either_size(sizes, activation, overflow, ints, *floats),
        (overflow, ints, floats),
    ),
    _either_size_bwd,
)


def held_experts_output(
    u: jnp.ndarray, idx: jnp.ndarray, p: jnp.ndarray,
    w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
    first_expert: int, n_experts: int,
    activation: Callable[[jnp.ndarray], jnp.ndarray],
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the layer's output for one chunk of tokens.

    ``u`` (T, d) normed inputs, ``idx`` / ``p`` (T, k) every token's chosen
    experts and their weights, ``w_*`` the held experts' weights
    ``(held, ...)`` in the dtype to compute in, ``n_experts`` the layer's
    experts, held and absent. An expert is
    ``down(activation(gate u) * up u)``: ``jax.nn.relu`` from the
    sparse-expert trunk (ReGLU, SmallThinker), ``jax.nn.silu`` from the
    latent trunk (SwiGLU, ``models/latent_trunk.py``). Returns ``y`` (T, d),
    the tokens routed to each held expert, (held,) int32, and whether the
    chunk's pairs overflowed the buffer's small size and ran at the full
    one, int32 (0 where the two sizes are one).

    The (token, choice) pairs are sorted by held expert, pairs on absent
    experts last; the three grouped products run over the held groups, in a
    buffer of ``buffer_rows``' small size where the held pairs fit, else of
    its full size (``_at_rows`` at either).
    """
    t, k = idx.shape
    held = w_gate.shape[0]
    small, full = buffer_rows(t * k, held, n_experts)
    with jax.named_scope("moe_route"):
        local = idx.reshape(-1) - first_expert
        group = jnp.where((local >= 0) & (local < held), local, held)
        # whole row tiles: the grouped-matmul kernel is several times slower
        # on a row count that is not a multiple of its tile
        group = jnp.pad(group, (0, full - t * k), constant_values=held)
        order = jnp.argsort(group, stable=True)
        back = jnp.argsort(order)[: t * k].reshape(t, k).T
        sizes = jnp.sum(
            group[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32
        )
    ints, floats = (order, back, sizes), (u, p, w_gate, w_up, w_down)
    if small == full:
        return _at_rows(full, activation, ints, *floats), sizes, jnp.int32(0)
    overflow = jnp.sum(sizes) > small
    y = _either_size((small, full), activation, overflow, ints, *floats)
    return y, sizes, overflow.astype(jnp.int32)


class HeldExperts(nn.Module):
    """The experts held here, gated and without bias, for any trunk whose
    configuration names ``dim``, ``expert_dim``, ``n_experts``,
    ``first_expert`` and ``experts_held``: tokens ``u`` (T, d) with their
    chosen experts and weights -> their part of the output, the tokens on
    each held expert and the chunks that ran at the buffer's full size."""

    cfg: Any
    activation: Callable[[jnp.ndarray], jnp.ndarray]
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self, u: jnp.ndarray, idx: jnp.ndarray, p: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        c = self.cfg
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,)
        )
        held, d, f = c.experts_held, c.dim, c.expert_dim
        # the copies of the three stacks in the dtype to compute in
        with jax.named_scope("weight_cast"):
            weights = tuple(
                self.param(name, init, shape).astype(self.dtype)
                for name, shape in (
                    ("w_gate", (held, d, f)), ("w_up", (held, d, f)),
                    ("w_down", (held, f, d)),
                )
            )

        def chunk(args):
            return held_experts_output(
                *args, *weights, c.first_expert, c.n_experts, self.activation
            )

        n = _chunks(u.shape[0])
        if n == 1:
            y, sizes, full_size = chunk((u, idx, p))
        else:
            split = lambda x: x.reshape(n, -1, x.shape[-1])  # noqa: E731
            # the loop's own ops (a chunk's slice in, its rows stacked
            # out); the chunk's ops keep their innermost scopes
            with jax.named_scope("chunk_stack"):
                y, sizes, full_size = lax.map(
                    jax.checkpoint(chunk), (split(u), split(idx), split(p))
                )
                y, sizes, full_size = y.reshape(u.shape), jnp.sum(sizes, axis=0), jnp.sum(full_size)
        return checkpoint_name(y, HELD_EXPERTS_OUTPUT), sizes, full_size


class _DecoderLayer(nn.Module):
    cfg: SparseTrunkConfig
    is_global: bool
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, mask: jnp.ndarray
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        c = self.cfg
        n, L, d = x.shape
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)  # noqa: E731
        with jax.named_scope("trunk_attention"):
            h = norm("attn_norm")(x)
        with jax.named_scope("moe_route"):
            w_router = self.param(
                "router", nn.initializers.lecun_normal(), (d, c.n_experts)
            )
            idx, p = route(h.reshape(n * L, d), w_router, c.experts_per_token)
        with jax.named_scope("trunk_attention"):
            x = x + _Attention(c, self.is_global, self.dtype, name="attn")(h, mask)
        with jax.named_scope("moe_route"):
            u = norm("ffn_norm")(x)
        y, counts, full_size = HeldExperts(c, jax.nn.relu, self.dtype, name="experts")(
            u.reshape(n * L, d), idx, p
        )
        return x + y.reshape(n, L, d), counts, full_size


class SparseExpertTrunk(nn.Module):
    """Token ids + attention mask -> per-token states (N, L, dim), and what
    the routers did: ``expert_tokens`` (layers, experts_held) int32, the
    (token, choice) pairs that fell on each held expert; ``absent_share``,
    the share of all pairs that fell on absent experts; ``full_size_chunks``,
    how many (layer, chunk)s ran at the sorted buffer's full size, int32."""

    cfg: SparseTrunkConfig = SparseTrunkConfig()
    dtype: jnp.dtype = jnp.float32
    remat: bool = False               # jax.checkpoint each layer

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
        layer_cls = nn.remat(_DecoderLayer) if self.remat else _DecoderLayer
        counts, full_size = [], []
        for i in range(c.n_layers):
            x, n, full = layer_cls(c, c.is_global(i), self.dtype, name=f"layer_{i}")(
                x, attention_mask
            )
            counts.append(n)
            full_size.append(full)
        x = RMSNorm(c.rms_norm_eps, self.dtype, name="final_norm")(x)
        expert_tokens = jnp.stack(counts)
        pairs = c.n_layers * input_ids.size * c.experts_per_token
        routing = {
            "expert_tokens": expert_tokens,
            "absent_share": 1.0 - jnp.sum(expert_tokens) / jnp.float32(pairs),
            "full_size_chunks": jnp.sum(jnp.stack(full_size)),
        }
        return x, routing
