"""Fused Pallas kernels for the train step's hot chain (ISSUE 8).

The last chip measurement of the ISOLATED kernels (jax 0.4.37; its artifact
is no longer in the tree) had them lose at the reference scale: at H=50 the
flash-attention kernel was 50x slower than XLA dense (1.89 ms vs 0.038 ms
fwd) because per-call overhead dominates ops
this small. The only way a kernel wins here is by fusing the WHOLE chain
and amortizing one launch across it. Two kernels cover the step's hot path:

  * :func:`fused_gather_encode` — frozen-table embedding gather + text-head
    encode in ONE kernel: the per-batch unique news ids ride a scalar-
    prefetch grid, so each grid step DMAs exactly one ``token_states[id]``
    row HBM->VMEM (double-buffered by the Pallas pipeline) and streams it
    straight into the additive-attention pool + output projection. The
    (U, T, Dh) gather result never round-trips HBM as a materialized
    activation — forward OR backward (the trunk is frozen: the custom VJP
    produces head-parameter cotangents only and never touches the table).
  * :func:`fused_history_score` — the user tower + scorer in ONE kernel
    per row-block: Q/K/V projections, per-head attention over the (H, D)
    history, additive pooling to the user vector, and dot-scoring of the
    1+C candidate vectors, all in one VMEM residency. bf16 operands hit
    the MXU at native rate; every accumulation is f32.

Numerics contract (the trajectory pin in ``tests/test_fused_hot_path.py``):
the kernels reproduce the module chain's EXACT normalization semantics —
max-subtracted exp, mask multiplied AFTER exp, ``+ 1e-8`` on the
denominator (``attention.py::_masked_normalize`` with ``stable=True``) —
so a fully-masked history row pools to ~0 exactly like the jnp path. Under
float32 the fused chain matches the dense chain to float roundoff
(identical op sequence; reassociation across padded tiles is the only
difference). Under bfloat16 the kernels are tolerance-banded and MORE
precise than the dense chain: the module requantizes to bf16 after every
Dense/softmax, while the kernels keep f32 through every normalization and
requantize only at the same four points the module casts activations
(q/k/v, ctx, e, outputs). The backward treats the stabilization max as a
constant (standard flash-kernel practice); the jnp path routes an
O(1e-8)-relative subgradient through ``jnp.max`` — below every test
tolerance.

Gradient ledger — two parameters have MATHEMATICALLY zero gradients:
the key-projection bias (it shifts every score in a softmax row
uniformly — shift-invariant) and the pool fc2 bias (a constant shift on
pool logits). Autodiff on the dense path yields pure float-cancellation
noise there (~1e-7 relative), which Adam amplifies into noise-scale
parameter drift; the fused backward produces its own (different) noise
for the key bias and an EXACT zero for the fc2 bias (it is not a kernel
input — its true gradient is identically zero). Trajectory pins
therefore compare those two leaves at a noise bound, not the tight
tolerance; every functional output is unaffected (exact invariance).

Backward design: a blocked custom VJP, like ``flash_attention``'s — but
where the flash backward must carry a log-sum-exp residual because K/V
stream through the grid in blocks, the hot chain at H=50 holds the whole
history in one VMEM block, so the lse residual degenerates to "recompute
the one-block softmax" (one max+sum next to the dots the backward rebuilds
anyway). The backward kernels therefore recompute forward intermediates
per row-block and accumulate parameter cotangents across the sequential
grid; the lse-residual machinery stays in ``attention_kernels.py`` where
blocking over keys makes it load-bearing (H >= 2048).

Both kernels run in interpret mode off-TPU so tier-1 exercises the same
code path; interpret executes the grid as a host loop (~ms/step), which is
fine at test scale and is why the CPU bench legs run at reduced U.

What the chip's compiler says (``tests/test_chip_compile.py`` compiles
these kernels for a described v5e at the flagship's shapes): the
gather+encode kernel compiles, forward and backward, with its (1, 50, Dh)
table block (equal to the array's last two dims, which Mosaic accepts); its
per-id output and cotangent rows move in 8-row resident blocks because a
(1, Dp) block is refused. The history-score kernel's forward compiles. Its
BACKWARD is refused — it is written as einsums with no MXU form (outer
products, two contracted axes) and needs a rewrite — so
``model.fuse_hot_path=true`` cannot train on the chip today; ROADMAP S5
decides. None of these kernels has been run or timed on a chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedrec_tpu.ops.attention_kernels import (
    VMEM_BYTES,
    _CompilerParams,
    _interpret,
    _iter_pallas_calls,
    _LANE,
    _pad_to,
    _pallas_call_buffer_bytes,
)

_NEG_INF = -1e9
_EPS = 1e-8  # the module's denominator epsilon (attention.py:41)
# the gather kernel's per-id (1, Dp) output / cotangent rows move in f32
# blocks of this many rows (the f32 sublane tile); the id axis is padded to it
_ROWS = 8


def _sub_mult(dtype) -> int:
    """Sublane pad multiple per dtype (pallas_guide.md tiling table)."""
    return 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8


def _lane_pad(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad the last dim of an in-kernel value up to ``width``."""
    if x.shape[-1] == width:
        return x
    pad = jnp.zeros(x.shape[:-1] + (width - x.shape[-1],), x.dtype)
    return jnp.concatenate([x, pad], axis=-1)


def _row_dot(a: jnp.ndarray, w_row: jnp.ndarray) -> jnp.ndarray:
    """(M, K) . (1, K) -> (M,) in f32 on the VPU. As a ``dot_general`` with
    one output column Mosaic's lowering of the mixed bf16 -> f32 product
    fails verification; the f32 products of bf16 operands are exact, so
    only the summation order differs from the MXU form."""
    return jnp.sum(a.astype(jnp.float32) * w_row.astype(jnp.float32), axis=-1)


def _masked_softmax(
    logits: jnp.ndarray, mask: jnp.ndarray, pad_from: int
) -> jnp.ndarray:
    """The module's exp-normalization, f32, on (..., L) logits.

    ``pad_from``: first PADDED slot along the last axis — padded slots are
    forced to -inf BEFORE the max so the stabilizer matches the module's
    (which sees only real slots, masked-but-real slots included, exactly
    like this); ``mask`` multiplies AFTER exp, and the denominator carries
    the module's ``+ 1e-8`` — a fully-masked row therefore yields exactly
    the jnp path's ~0 weights instead of a uniform distribution.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    logits = jnp.where(iota >= pad_from, _NEG_INF, logits)
    m = jnp.max(logits, axis=-1, keepdims=True)
    w = jnp.exp(logits - m) * mask
    return w / (jnp.sum(w, axis=-1, keepdims=True) + _EPS)


# ===================================================== fused gather + encode
def _gather_encode_fwd_kernel(
    ids_ref, row_ref, w1_ref, b1_ref, w2_ref, fcw_ref, fcb_ref, o_ref,
):
    """One unique news id per grid step: the scalar-prefetch index map has
    already DMA'd ``token_states[ids[i]]`` into ``row_ref`` (the pipeline
    double-buffers the next row's copy behind this step's compute), so the
    kernel goes token states -> pooled -> news vector without the gather
    ever existing outside VMEM."""
    x = row_ref[0]                                       # (T, Dh) operand dtype
    t = x.shape[0]
    e = jnp.tanh(
        jax.lax.dot_general(
            x, w1_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        + b1_ref[0][None, :].astype(jnp.float32)
    ).astype(x.dtype)                                    # (T, Ah)
    # fc2's bias is a softmax-invariant constant shift under the max-
    # subtracted form — omitted exactly like additive_pool's kernel
    lg = _row_dot(e, w2_ref[:]).reshape(1, t)            # (1, T) f32
    ones = jnp.ones((1, t), jnp.float32)                 # reference: no token mask
    alpha = _masked_softmax(lg, ones, t).astype(x.dtype)
    pooled = jax.lax.dot_general(
        alpha, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)                                    # (1, Dh)
    out = jax.lax.dot_general(
        pooled, fcw_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + fcb_ref[0][None, :].astype(jnp.float32)
    # the output block holds _ROWS consecutive ids' vectors and stays in
    # VMEM until the block index moves on: Mosaic takes no (1, Dp) block
    o_ref[pl.ds(pl.program_id(0) % _ROWS, 1), :] = out   # (1, Dp) f32


def _gather_encode_bwd_kernel(
    ids_ref, row_ref, w1_ref, b1_ref, w2_ref, fcw_ref, fcb_ref, g_ref,
    dw1_ref, db1_ref, dw2_ref, dfcw_ref, dfcb_ref,
):
    """Blocked backward, one unique row per sequential grid step: re-gathers
    the row through the same scalar-prefetch pipeline, recomputes the
    one-block pool (see module docstring: the lse residual degenerates
    here), and ACCUMULATES head-parameter cotangents into constant-index
    output blocks. No table cotangent exists anywhere — the frozen-trunk
    ``stop_gradient`` is structural, not an op XLA must simplify away."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw1_ref[:] = jnp.zeros_like(dw1_ref)
        db1_ref[:] = jnp.zeros_like(db1_ref)
        dw2_ref[:] = jnp.zeros_like(dw2_ref)
        dfcw_ref[:] = jnp.zeros_like(dfcw_ref)
        dfcb_ref[:] = jnp.zeros_like(dfcb_ref)

    x = row_ref[0]                                       # (T, Dh)
    t = x.shape[0]
    x32 = x.astype(jnp.float32)
    e32 = jnp.tanh(
        jax.lax.dot_general(
            x, w1_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        + b1_ref[0][None, :].astype(jnp.float32)
    )
    e = e32.astype(x.dtype)
    lg = _row_dot(e, w2_ref[:]).reshape(1, t)
    ones = jnp.ones((1, t), jnp.float32)
    alpha = _masked_softmax(lg, ones, t)                 # (1, T) f32
    pooled = jax.lax.dot_general(
        alpha.astype(x.dtype), x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (1, Dh) f32

    g = g_ref[pl.ds(i % _ROWS, 1), :]                    # (1, Dp) f32
    dfcb_ref[:] += g
    dfcw_ref[:] += jax.lax.dot_general(
        pooled, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (Dh, Dp)
    dpooled = jax.lax.dot_general(
        g, fcw_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (1, Dh)
    dalpha = jax.lax.dot_general(
        dpooled, x32, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (1, T)
    dlg = alpha * (dalpha - jnp.sum(alpha * dalpha, axis=-1, keepdims=True))
    dw2_ref[:] += jax.lax.dot_general(
        dlg, e32, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (1, Ah)
    de = jax.lax.dot_general(
        dlg, w2_ref[:].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (T, Ah)
    dpre = de * (1.0 - e32 * e32)
    dw1_ref[:] += jax.lax.dot_general(
        x32, dpre, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (Dh, Ah)
    db1_ref[:] += jnp.sum(dpre, axis=0, keepdims=True)


def _gather_encode_specs(t, dh_dim, ahp, dp):
    """Input specs shared by the fwd and bwd pallas_calls: the table row
    selected by the scalar-prefetch id, then the (padded) head params."""
    return [
        pl.BlockSpec((1, t, dh_dim), lambda i, ids: (ids[i], 0, 0)),
        pl.BlockSpec((dh_dim, ahp), lambda i, ids: (0, 0)),
        pl.BlockSpec((1, ahp), lambda i, ids: (0, 0)),
        pl.BlockSpec((1, ahp), lambda i, ids: (0, 0)),
        pl.BlockSpec((dh_dim, dp), lambda i, ids: (0, 0)),
        pl.BlockSpec((1, dp), lambda i, ids: (0, 0)),
    ]


def _gather_encode_pads(table, w1, b1, w2, fcw, fcb):
    dt = table.dtype
    w1p = _pad_to(w1, 1, _LANE).astype(dt)
    b1p = _pad_to(b1.reshape(1, -1), 1, _LANE).astype(dt)
    w2p = _pad_to(w2.reshape(1, -1), 1, _LANE).astype(dt)
    fcwp = _pad_to(fcw, 1, _LANE).astype(dt)
    fcbp = _pad_to(fcb.reshape(1, -1), 1, _LANE).astype(dt)
    return w1p, b1p, w2p, fcwp, fcbp


@jax.custom_vjp
def _gather_encode(table, uniq, w1, b1, w2, fcw, fcb):
    t, dh_dim = table.shape[1], table.shape[2]
    u = uniq.shape[0]
    uniq = _pad_to(uniq, 0, _ROWS)                       # pad ids: row 0
    up = uniq.shape[0]
    w1p, b1p, w2p, fcwp, fcbp = _gather_encode_pads(table, w1, b1, w2, fcw, fcb)
    dp = fcwp.shape[1]
    out = pl.pallas_call(
        _gather_encode_fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(up,),
            in_specs=_gather_encode_specs(t, dh_dim, w1p.shape[1], dp),
            out_specs=pl.BlockSpec((_ROWS, dp), lambda i, ids: (i // _ROWS, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((up, dp), jnp.float32),
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(uniq, table, w1p, b1p, w2p, fcwp, fcbp)
    return out[:u, : fcw.shape[1]].astype(table.dtype)


def _gather_encode_fwd(table, uniq, w1, b1, w2, fcw, fcb):
    out = _gather_encode(table, uniq, w1, b1, w2, fcw, fcb)
    return out, (table, uniq, w1, b1, w2, fcw, fcb)


def _gather_encode_bwd(res, g):
    table, uniq, w1, b1, w2, fcw, fcb = res
    t, dh_dim = table.shape[1], table.shape[2]
    ids = _pad_to(uniq, 0, _ROWS)
    u = ids.shape[0]
    w1p, b1p, w2p, fcwp, fcbp = _gather_encode_pads(table, w1, b1, w2, fcw, fcb)
    ahp, dp = w1p.shape[1], fcwp.shape[1]
    # (Up, Dp), pads zero: a padded id's row contributes nothing
    gp = _pad_to(_pad_to(g.astype(jnp.float32), 1, _LANE), 0, _ROWS)
    specs = _gather_encode_specs(t, dh_dim, ahp, dp)
    # the cotangent rows of _ROWS consecutive ids, resident like the output
    specs.append(pl.BlockSpec((_ROWS, dp), lambda i, ids: (i // _ROWS, 0)))
    dw1, db1, dw2, dfcw, dfcb = pl.pallas_call(
        _gather_encode_bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(u,),
            in_specs=specs,
            out_specs=(
                pl.BlockSpec((dh_dim, ahp), lambda i, ids: (0, 0)),
                pl.BlockSpec((1, ahp), lambda i, ids: (0, 0)),
                pl.BlockSpec((1, ahp), lambda i, ids: (0, 0)),
                pl.BlockSpec((dh_dim, dp), lambda i, ids: (0, 0)),
                pl.BlockSpec((1, dp), lambda i, ids: (0, 0)),
            ),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((dh_dim, ahp), jnp.float32),
            jax.ShapeDtypeStruct((1, ahp), jnp.float32),
            jax.ShapeDtypeStruct((1, ahp), jnp.float32),
            jax.ShapeDtypeStruct((dh_dim, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
        ),
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(ids, table, w1p, b1p, w2p, fcwp, fcbp, gp)
    ah, d = w1.shape[1], fcw.shape[1]
    # the frozen table's cotangent is symbolically dropped by the caller's
    # stop_gradient; the zeros here are DCE'd, never materialized
    return (
        jnp.zeros_like(table),
        np.zeros(uniq.shape, jax.dtypes.float0),
        dw1[:, :ah].astype(w1.dtype),
        db1[0, :ah].astype(b1.dtype),
        dw2[0, :ah].astype(w2.dtype),
        dfcw[:, :d].astype(fcw.dtype),
        dfcb[0, :d].astype(fcb.dtype),
    )


_gather_encode.defvjp(_gather_encode_fwd, _gather_encode_bwd)


def fused_gather_encode(
    token_states: jnp.ndarray,
    uniq: jnp.ndarray,
    news_params: dict,
    dtype=None,
) -> jnp.ndarray:
    """Fused frozen-table gather + additive text head: (N, T, Dh) table +
    (U,) unique ids -> (U, news_dim) news vectors.

    ``news_params`` is the additive ``TextHead`` tree
    (``{"pool": {"att_fc1", "att_fc2"}, "fc"}``). Operands are cast to
    ``dtype`` (default: the table's dtype) before the kernel — the same
    quantization points as ``nn.Dense(dtype=...)`` on the module path.
    """
    p1 = news_params["pool"]["att_fc1"]
    p2 = news_params["pool"]["att_fc2"]
    fc = news_params["fc"]
    dt = jnp.dtype(dtype or token_states.dtype)
    return _gather_encode(
        token_states.astype(dt),
        uniq,
        p1["kernel"].astype(dt),
        p1["bias"].astype(dt),
        p2["kernel"][:, 0].astype(dt),
        fc["kernel"].astype(dt),
        fc["bias"].astype(dt),
    )


# ================================================ fused history-attn + score
def _score_block_b(block_b, hp, dp, qp, cp, itemsize, backward: bool):
    """Shrink the row-block so one program's block operands + f32
    temporaries stay inside a conservative VMEM budget (the same guard
    ``_pool_forward`` applies; the traced model below is the test-time
    check, this is the runtime one)."""
    per_row = (
        hp * dp * (itemsize + 4 * 4)       # x block + f32 q/k/v/ctx temps
        + 2 * hp * hp * 4                  # one head's s/w
        + hp * qp * 4                      # e
        + cp * dp * itemsize               # cand block
    )
    if backward:
        per_row += hp * hp * 4 * 24        # per-head attention maps kept live
        per_row += 3 * hp * dp * 4         # dq/dk/dv
    budget = (6 << 20) if not backward else (7 << 20)
    return max(1, min(block_b, budget // per_row))


def _hist_forward_core(
    x_ref, mask_ref, wq_ref, bq_ref, wk_ref, bk_ref, wv_ref, bv_ref,
    pw1_ref, pb1_ref, pw2_ref, *, nh: int, dh: int, h: int, keep_attn: bool,
):
    """Shared forward math for the fused score kernels (fwd + recompute in
    bwd): projections -> per-head masked attention -> additive pool.

    Quantization points mirror the module chain exactly: every Dense-like
    output is cast back to the operand dtype (identity under f32), every
    normalization runs in f32. Returns the f32 attention maps per head only
    when the backward asks (``keep_attn``)."""
    bb, hp, dp = x_ref.shape
    dt = x_ref.dtype
    d = nh * dh
    x2 = x_ref[:].reshape(bb * hp, dp)

    def proj(w_ref, b_ref):
        y = jax.lax.dot_general(
            x2, w_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + b_ref[0][None, :].astype(jnp.float32)
        return y.astype(dt).reshape(bb, hp, dp)

    qa, ka, va = proj(wq_ref, bq_ref), proj(wk_ref, bk_ref), proj(wv_ref, bv_ref)
    mask = mask_ref[:, 0, :hp].astype(jnp.float32)       # (bb, hp)
    kmask = mask[:, None, :]
    scale = jnp.sqrt(jnp.float32(dh))
    ctx_heads, attn_heads = [], []
    for head in range(nh):
        sl = slice(head * dh, (head + 1) * dh)
        qh, kh, vh = qa[:, :, sl], ka[:, :, sl], va[:, :, sl]
        s = jax.lax.dot_general(
            qh, kh, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) / scale                                        # (bb, hp, hp)
        a = _masked_softmax(s, kmask, h)
        if keep_attn:
            attn_heads.append(a)
        ctx_heads.append(
            jax.lax.dot_general(
                a.astype(dt), vh, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ).astype(dt)
        )
    ctx = jnp.concatenate(ctx_heads, axis=-1)            # (bb, hp, d)
    e32 = jnp.tanh(
        jax.lax.dot_general(
            ctx.reshape(bb * hp, d), pw1_ref[:d, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        + pb1_ref[0][None, :].astype(jnp.float32)
    )                                                    # (bb*hp, Qp)
    lg = _row_dot(e32.astype(dt), pw2_ref[:]).reshape(bb, hp)
    alpha = _masked_softmax(lg, mask, h)                 # (bb, hp) f32
    # (bb, 1, hp) x (bb, hp, d): Mosaic takes no dot whose left operand has
    # only batch and contracting dimensions, so alpha carries a unit row
    user = jax.lax.dot_general(
        alpha[:, None, :].astype(dt), ctx, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )[:, 0, :]                                           # (bb, d) f32
    return qa, ka, va, attn_heads, ctx, e32, alpha, user


def _hist_score_fwd_kernel(
    x_ref, cand_ref, mask_ref, wq_ref, bq_ref, wk_ref, bk_ref, wv_ref,
    bv_ref, pw1_ref, pb1_ref, pw2_ref, scores_ref, user_ref, *, nh, dh, h,
):
    dt = x_ref.dtype
    d = nh * dh
    *_, _, _, _, user = _hist_forward_core(
        x_ref, mask_ref, wq_ref, bq_ref, wk_ref, bk_ref, wv_ref, bv_ref,
        pw1_ref, pb1_ref, pw2_ref, nh=nh, dh=dh, h=h, keep_attn=False,
    )
    user_dt = user.astype(dt)
    sc = jax.lax.dot_general(
        cand_ref[:, :, :d], user[:, None, :].astype(dt),
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )[:, :, 0]                                           # (bb, Cp)
    scores_ref[:] = _lane_pad(sc.astype(dt), scores_ref.shape[1])
    user_ref[:] = _lane_pad(user_dt, user_ref.shape[1])


def _hist_score_bwd_kernel(
    x_ref, cand_ref, mask_ref, wq_ref, bq_ref, wk_ref, bk_ref, wv_ref,
    bv_ref, pw1_ref, pb1_ref, pw2_ref, gsc_ref, guser_ref,
    dx_ref, dcand_ref, dwq_ref, dbq_ref, dwk_ref, dbk_ref, dwv_ref,
    dbv_ref, dpw1_ref, dpb1_ref, dpw2_ref, *, nh, dh, h, c,
):
    """Blocked backward: recompute the row-block's forward (module
    docstring: at H=50 the whole history is one block, so recompute IS the
    degenerate lse-residual path), then walk the chain backward producing
    per-block dx/dcand and accumulating parameter cotangents across the
    sequential grid."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        for ref in (
            dwq_ref, dbq_ref, dwk_ref, dbk_ref, dwv_ref, dbv_ref,
            dpw1_ref, dpb1_ref, dpw2_ref,
        ):
            ref[:] = jnp.zeros_like(ref)

    bb, hp, dp = x_ref.shape
    d = nh * dh
    qa, ka, va, attn, ctx, e32, alpha, user = _hist_forward_core(
        x_ref, mask_ref, wq_ref, bq_ref, wk_ref, bk_ref, wv_ref, bv_ref,
        pw1_ref, pb1_ref, pw2_ref, nh=nh, dh=dh, h=h, keep_attn=True,
    )
    ctx32 = ctx.astype(jnp.float32)
    cand32 = cand_ref[:, :, :d].astype(jnp.float32)      # (bb, Cp, d)
    gs = gsc_ref[:, 0, :c].astype(jnp.float32)           # (bb, C)
    gu = guser_ref[:, 0, :d].astype(jnp.float32)         # (bb, d)

    # ---- scorer
    dcand = jnp.einsum("bc,bd->bcd", gs, user)           # (bb, C, d)
    du = jnp.einsum("bc,bcd->bd", gs, cand32[:, :c, :]) + gu

    # ---- additive pool
    dalpha = jnp.einsum("bd,bhd->bh", du, ctx32)
    dctx = alpha[:, :, None] * du[:, None, :]            # (bb, hp, d)
    dlg = alpha * (dalpha - jnp.sum(alpha * dalpha, axis=-1, keepdims=True))
    e3 = e32.reshape(bb, hp, -1)                         # (bb, hp, Qp)
    dpw2_ref[:] += jnp.sum(
        jnp.einsum("bh,bhq->bq", dlg, e3), axis=0, keepdims=True
    )
    de = dlg[:, :, None] * pw2_ref[0][None, None, :].astype(jnp.float32)
    dpre = de * (1.0 - e3 * e3)                          # (bb, hp, Qp)
    dpw1 = jnp.einsum("bhd,bhq->dq", ctx32, dpre)        # (d, Qp)
    if dpw1.shape[0] < dpw1_ref.shape[0]:                # rows pad -> (Dp, Qp)
        dpw1 = jnp.concatenate(
            [dpw1, jnp.zeros((dpw1_ref.shape[0] - d, dpw1.shape[1]),
                             jnp.float32)],
            axis=0,
        )
    dpw1_ref[:] += dpw1
    dpb1_ref[:] += jnp.sum(dpre, axis=(0, 1))[None, :]
    dctx = dctx + jnp.einsum(
        "bhq,dq->bhd", dpre, pw1_ref[:d, :].astype(jnp.float32)
    )

    # ---- per-head attention (attn maps recomputed in the shared core)
    scale = jnp.sqrt(jnp.float32(dh))
    dq_heads, dk_heads, dv_heads = [], [], []
    for head in range(nh):
        sl = slice(head * dh, (head + 1) * dh)
        a = attn[head]                                   # (bb, hp, hp) f32
        vh = va[:, :, sl].astype(jnp.float32)
        qh = qa[:, :, sl].astype(jnp.float32)
        kh = ka[:, :, sl].astype(jnp.float32)
        dctx_h = dctx[:, :, sl]
        dv_heads.append(jnp.einsum("bqk,bqd->bkd", a, dctx_h))
        da = jnp.einsum("bqd,bkd->bqk", dctx_h, vh)
        ds = a * (da - jnp.sum(a * da, axis=-1, keepdims=True)) / scale
        dq_heads.append(jnp.einsum("bqk,bkd->bqd", ds, kh))
        dk_heads.append(jnp.einsum("bqk,bqd->bkd", ds, qh))
    dq = _lane_pad(jnp.concatenate(dq_heads, axis=-1), dp).reshape(bb * hp, dp)
    dk = _lane_pad(jnp.concatenate(dk_heads, axis=-1), dp).reshape(bb * hp, dp)
    dv = _lane_pad(jnp.concatenate(dv_heads, axis=-1), dp).reshape(bb * hp, dp)

    # ---- projections
    x32 = x_ref[:].astype(jnp.float32).reshape(bb * hp, dp)
    dx = jnp.zeros((bb * hp, dp), jnp.float32)
    for dy, w_ref, dw_ref, db_ref in (
        (dq, wq_ref, dwq_ref, dbq_ref),
        (dk, wk_ref, dwk_ref, dbk_ref),
        (dv, wv_ref, dwv_ref, dbv_ref),
    ):
        dw_ref[:] += jax.lax.dot_general(
            x32, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        db_ref[:] += jnp.sum(dy, axis=0, keepdims=True)
        dx = dx + jax.lax.dot_general(
            dy, w_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    dx_ref[:] = dx.reshape(bb, hp, dp)
    dcand_ref[:] = _lane_pad(
        jnp.pad(dcand, ((0, 0), (0, dcand_ref.shape[1] - c), (0, 0))), dp
    )


def _hist_score_pads(x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1, pw2,
                     block_b):
    """One padding policy for the fwd and bwd calls: lane dims to 128,
    sequence dims to the dtype's sublane multiple, rows to the block."""
    dt = x.dtype
    sm = _sub_mult(dt)
    xp = _pad_to(_pad_to(_pad_to(x, 0, block_b), 1, sm), 2, _LANE)
    candp = _pad_to(_pad_to(_pad_to(cand, 0, block_b), 1, sm), 2, _LANE)
    hm = xp.shape[1] + (-xp.shape[1]) % _LANE
    maskp = _pad_to(_pad_to(mask.astype(jnp.float32), 0, block_b), 1, hm)
    maskp = maskp[:, None, :]                            # (np, 1, Hm)
    wqp = _pad_to(_pad_to(wq, 0, _LANE), 1, _LANE).astype(dt)
    wkp = _pad_to(_pad_to(wk, 0, _LANE), 1, _LANE).astype(dt)
    wvp = _pad_to(_pad_to(wv, 0, _LANE), 1, _LANE).astype(dt)
    bqp = _pad_to(bq.reshape(1, -1), 1, _LANE).astype(dt)
    bkp = _pad_to(bk.reshape(1, -1), 1, _LANE).astype(dt)
    bvp = _pad_to(bv.reshape(1, -1), 1, _LANE).astype(dt)
    pw1p = _pad_to(_pad_to(pw1, 0, _LANE), 1, _LANE).astype(dt)
    pb1p = _pad_to(pb1.reshape(1, -1), 1, _LANE).astype(dt)
    pw2p = _pad_to(pw2.reshape(1, -1), 1, _LANE).astype(dt)
    return xp, candp, maskp, wqp, bqp, wkp, bkp, wvp, bvp, pw1p, pb1p, pw2p


def _hist_score_wspecs(dp, qp):
    """BlockSpecs of the 9 (padded) parameter operands — constant index
    maps, so the pipeline keeps them VMEM-resident across row-blocks."""
    return [
        pl.BlockSpec((dp, dp), lambda i: (0, 0)),
        pl.BlockSpec((1, dp), lambda i: (0, 0)),
        pl.BlockSpec((dp, dp), lambda i: (0, 0)),
        pl.BlockSpec((1, dp), lambda i: (0, 0)),
        pl.BlockSpec((dp, dp), lambda i: (0, 0)),
        pl.BlockSpec((1, dp), lambda i: (0, 0)),
        pl.BlockSpec((dp, qp), lambda i: (0, 0)),
        pl.BlockSpec((1, qp), lambda i: (0, 0)),
        pl.BlockSpec((1, qp), lambda i: (0, 0)),
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(12, 13))
def _hist_score(x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1, pw2,
                nh, block_b):
    return _hist_score_forward(
        x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1, pw2, nh, block_b
    )


def _hist_score_forward(x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1,
                        pw2, nh, block_b):
    n, h, d = x.shape
    c = cand.shape[1]
    dh = d // nh
    dt = x.dtype
    bb = _score_block_b(
        block_b,
        h + (-h) % _sub_mult(dt),
        d + (-d) % _LANE,
        pw1.shape[1] + (-pw1.shape[1]) % _LANE,
        c + (-c) % _sub_mult(dt),
        dt.itemsize,
        backward=False,
    )
    padded = _hist_score_pads(
        x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1, pw2, bb
    )
    xp, candp, maskp = padded[:3]
    np_, hp, dp = xp.shape
    cp, qp = candp.shape[1], padded[9].shape[1]
    cs = cp + (-cp) % _LANE
    scores, user = pl.pallas_call(
        functools.partial(_hist_score_fwd_kernel, nh=nh, dh=dh, h=h),
        grid=(np_ // bb,),
        in_specs=[
            pl.BlockSpec((bb, hp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, cp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, 1, maskp.shape[2]), lambda i: (i, 0, 0)),
            *_hist_score_wspecs(dp, qp),
        ],
        out_specs=(
            pl.BlockSpec((bb, cs), lambda i: (i, 0)),
            pl.BlockSpec((bb, dp), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((np_, cs), dt),
            jax.ShapeDtypeStruct((np_, dp), dt),
        ),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=_interpret(),
    )(*padded)
    return scores[:n, :c], user[:n, :d]


def _hist_score_vjp_fwd(x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1,
                        pw2, nh, block_b):
    out = _hist_score_forward(
        x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1, pw2, nh, block_b
    )
    return out, (x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1, pw2)


def _hist_score_vjp_bwd(nh, block_b, res, g):
    x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1, pw2 = res
    gsc, guser = g
    n, h, d = x.shape
    c = cand.shape[1]
    dh = d // nh
    dt = x.dtype
    bb = _score_block_b(
        block_b,
        h + (-h) % _sub_mult(dt),
        d + (-d) % _LANE,
        pw1.shape[1] + (-pw1.shape[1]) % _LANE,
        c + (-c) % _sub_mult(dt),
        dt.itemsize,
        backward=True,
    )
    padded = _hist_score_pads(
        x, cand, mask, wq, bq, wk, bk, wv, bv, pw1, pb1, pw2, bb
    )
    xp, candp, maskp = padded[:3]
    np_, hp, dp = xp.shape
    cp, qp = candp.shape[1], padded[9].shape[1]
    cs = cp + (-cp) % _LANE
    # per-row cotangents ride a unit middle axis like the mask: a (bb, W)
    # block with bb below the sublane multiple is refused, (bb, 1, W) is not
    gscp = _pad_to(_pad_to(gsc.astype(jnp.float32), 0, bb), 1, cs)[:, None, :]
    guserp = _pad_to(_pad_to(guser.astype(jnp.float32), 0, bb), 1, dp)[:, None, :]
    outs = pl.pallas_call(
        functools.partial(_hist_score_bwd_kernel, nh=nh, dh=dh, h=h, c=c),
        grid=(np_ // bb,),
        in_specs=[
            pl.BlockSpec((bb, hp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, cp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, 1, maskp.shape[2]), lambda i: (i, 0, 0)),
            *_hist_score_wspecs(dp, qp),
            pl.BlockSpec((bb, 1, cs), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, 1, dp), lambda i: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bb, hp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((bb, cp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((dp, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((dp, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((dp, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((dp, qp), lambda i: (0, 0)),
            pl.BlockSpec((1, qp), lambda i: (0, 0)),
            pl.BlockSpec((1, qp), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((np_, hp, dp), jnp.float32),
            jax.ShapeDtypeStruct((np_, cp, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((dp, qp), jnp.float32),
            jax.ShapeDtypeStruct((1, qp), jnp.float32),
            jax.ShapeDtypeStruct((1, qp), jnp.float32),
        ),
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
    )(*padded, gscp, guserp)
    dx, dcand, dwq, dbq, dwk, dbk, dwv, dbv, dpw1, dpb1, dpw2 = outs
    q = pw1.shape[1]
    return (
        dx[:n, :h, :d].astype(x.dtype),
        dcand[:n, :c, :d].astype(cand.dtype),
        jnp.zeros_like(mask),
        dwq[:d, :d].astype(wq.dtype),
        dbq[0, :d].astype(bq.dtype),
        dwk[:d, :d].astype(wk.dtype),
        dbk[0, :d].astype(bk.dtype),
        dwv[:d, :d].astype(wv.dtype),
        dbv[0, :d].astype(bv.dtype),
        dpw1[:d, :q].astype(pw1.dtype),
        dpb1[0, :q].astype(pb1.dtype),
        dpw2[0, :q].astype(pw2.dtype),
    )


_hist_score.defvjp(_hist_score_vjp_fwd, _hist_score_vjp_bwd)


def _flatten_params(attn_params: dict, pool_params: dict, dt):
    return tuple(
        p.astype(dt)
        for p in (
            attn_params["w_q"]["kernel"], attn_params["w_q"]["bias"],
            attn_params["w_k"]["kernel"], attn_params["w_k"]["bias"],
            attn_params["w_v"]["kernel"], attn_params["w_v"]["bias"],
            pool_params["att_fc1"]["kernel"], pool_params["att_fc1"]["bias"],
            pool_params["att_fc2"]["kernel"][:, 0],
        )
    )


def fused_history_score(
    his_vecs: jnp.ndarray,
    cand_vecs: jnp.ndarray,
    mask: jnp.ndarray | None,
    attn_params: dict,
    pool_params: dict,
    num_heads: int,
    block_b: int = 8,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused user tower + scorer: (..., H, D) history (post-dropout) and
    (..., C, D) candidates -> ((..., C) scores, (..., D) user vector).

    ``attn_params``/``pool_params``: the ``self_attn``/``pool`` subtrees of
    ``UserEncoder`` (fc2's bias is a softmax-invariant shift — omitted, its
    gradient is exactly zero either way). ``mask``: optional (..., H) key
    mask, 1 = real click; fully-masked rows pool to ~0 exactly like the
    module path's multiply-after-exp epsilon semantics.
    """
    *batch, h, d = his_vecs.shape
    c = cand_vecs.shape[-2]
    n = 1
    for b in batch:
        n *= b
    dt = his_vecs.dtype
    xf = his_vecs.reshape(n, h, d)
    cf = cand_vecs.astype(dt).reshape(n, c, d)
    mf = (
        jnp.ones((n, h), jnp.float32)
        if mask is None
        else mask.reshape(n, h).astype(jnp.float32)
    )
    flat = _flatten_params(attn_params, pool_params, dt)
    scores, user = _hist_score(xf, cf, mf, *flat, num_heads, block_b)
    return scores.reshape(*batch, c), user.reshape(*batch, d)


def fused_user_vector(
    his_vecs: jnp.ndarray,
    mask: jnp.ndarray | None,
    attn_params: dict,
    pool_params: dict,
    num_heads: int,
    block_b: int = 8,
) -> jnp.ndarray:
    """The serving/eval entry: attention + pool fused, no candidates —
    ``serve.py``'s full-catalog matmul then runs on the kernel's user
    vector (one launch per request batch instead of the 5-op chain)."""
    *batch, h, d = his_vecs.shape
    dummy = jnp.zeros((*batch, 1, d), his_vecs.dtype)
    _, user = fused_history_score(
        his_vecs, dummy, mask, attn_params, pool_params, num_heads, block_b
    )
    return user


# ================================================== VMEM working-set model
def _traced_call_bytes(fn, *args) -> int:
    """Largest single pallas_call's buffered-block+scratch bytes in
    ``fn``'s jaxpr (grid-varying blocks x2 for pipeline double-buffering,
    constant-index blocks x1), via the shared traced-grid-mapping walk —
    the same machinery ``flash_vmem_working_set`` uses, so a BlockSpec
    regression in the fused kernels is catchable on CPU."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    per_call = []
    for eqn in _iter_pallas_calls(jaxpr.jaxpr):
        block, scratch = _pallas_call_buffer_bytes(eqn)
        per_call.append(block + scratch)
    if not per_call:
        raise AssertionError("no pallas_call in traced fn — fusion not routed")
    return max(per_call)


def fused_score_vmem_working_set(
    batch: int = 1024,
    his: int = 50,
    news_dim: int = 400,
    cands: int = 5,
    num_heads: int = 20,
    query_dim: int = 200,
    dtype=jnp.bfloat16,
    block_b: int = 8,
) -> dict:
    """Per-program VMEM working set of the fused history-attention+score
    kernel (fwd and bwd), bytes: traced block operands (x2 pipeline) plus
    the f32 temporaries the kernel body materializes (q/k/v/ctx copies,
    one head's score map — all heads' maps in the backward — e, and the
    dq/dk/dv assembly). Same contract as ``flash_vmem_working_set``:
    derived from the TRACED grid mappings so a layout regression fails on
    CPU without hardware."""
    dt = jnp.dtype(dtype)
    x = jax.ShapeDtypeStruct((batch, his, news_dim), dt)
    cand = jax.ShapeDtypeStruct((batch, cands, news_dim), dt)
    mask = jax.ShapeDtypeStruct((batch, his), jnp.float32)
    d = news_dim
    params = tuple(
        jax.ShapeDtypeStruct(s, dt)
        for s in [(d, d), (d,)] * 3 + [(d, query_dim), (query_dim,), (query_dim,)]
    )
    hp = his + (-his) % _sub_mult(dt)
    dp = d + (-d) % _LANE
    qp = query_dim + (-query_dim) % _LANE
    cp = cands + (-cands) % _sub_mult(dt)

    def temps(bb: int, backward: bool) -> int:
        t = 4 * bb * hp * dp * 4 + 2 * bb * hp * hp * 4 + bb * hp * qp * 4
        if backward:
            t += num_heads * bb * hp * hp * 4   # kept attention maps
            t += (3 + 1) * bb * hp * dp * 4     # dq/dk/dv + dctx
        return t

    bb_f = _score_block_b(block_b, hp, dp, qp, cp, dt.itemsize, False)
    bb_b = _score_block_b(block_b, hp, dp, qp, cp, dt.itemsize, True)
    fwd = _traced_call_bytes(
        lambda *a: _hist_score_forward(*a, num_heads, block_b), x, cand, mask,
        *params,
    ) + temps(bb_f, False)

    def loss(*a):
        s, _ = _hist_score(*a, num_heads, block_b)
        return jnp.sum(s.astype(jnp.float32))

    bwd_jaxpr_fn = jax.grad(loss, argnums=tuple(range(3, 12)))
    bwd = 0
    jaxpr = jax.make_jaxpr(bwd_jaxpr_fn)(x, cand, mask, *params)
    for eqn in _iter_pallas_calls(jaxpr.jaxpr):
        block, scratch = _pallas_call_buffer_bytes(eqn)
        bwd = max(bwd, block + scratch)
    bwd += temps(bb_b, True)
    worst = max(fwd, bwd)
    return {"forward": fwd, "backward": bwd, "worst": worst,
            "fits": worst <= VMEM_BYTES}


def fused_gather_encode_vmem_working_set(
    unique: int = 4096,
    title: int = 50,
    bert_hidden: int = 768,
    news_dim: int = 400,
    dtype=jnp.bfloat16,
) -> dict:
    """Per-program VMEM working set of the fused gather+encode kernel.

    The whole point of the scalar-prefetch layout is that ONE table row
    (not the (U, T, Dh) gather) is VMEM-resident per program — this model
    pins that: the traced block bytes are dominated by the head params and
    one (T, Dh) row, independent of U."""
    dt = jnp.dtype(dtype)
    ah = bert_hidden // 2
    table = jax.ShapeDtypeStruct((max(unique, 8), title, bert_hidden), dt)
    uniq = jax.ShapeDtypeStruct((unique,), jnp.int32)
    params = tuple(
        jax.ShapeDtypeStruct(s, dt)
        for s in [
            (bert_hidden, ah), (ah,), (ah,), (bert_hidden, news_dim),
            (news_dim,),
        ]
    )
    fwd_t = title * (ah + (-ah) % _LANE) * 4 * 2 + title * bert_hidden * 4
    fwd = _traced_call_bytes(
        lambda *a: _gather_encode(*a), table, uniq, *params
    ) + fwd_t

    def loss(t_, u_, *p):
        return jnp.sum(_gather_encode(t_, u_, *p).astype(jnp.float32))

    bwd = 0
    jaxpr = jax.make_jaxpr(
        jax.grad(loss, argnums=tuple(range(2, 7)))
    )(table, uniq, *params)
    for eqn in _iter_pallas_calls(jaxpr.jaxpr):
        block, scratch = _pallas_call_buffer_bytes(eqn)
        bwd = max(bwd, block + scratch)
    bwd += 3 * fwd_t
    worst = max(fwd, bwd)
    return {"forward": fwd, "backward": bwd, "worst": worst,
            "fits": worst <= VMEM_BYTES}
