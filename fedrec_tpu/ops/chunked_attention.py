"""Blocked attention: no ``L x L`` array, forward or backward. Pure XLA.

The one blocked core of the repo. Two callers:

  * the user encoder's long click histories (``models/attention.py``,
    ``attn_impl='chunked'``): bidirectional, a key mask, as many key/value
    heads as query heads. The reference materializes dense ``(bz, heads, L,
    L)`` scores (reference ``attention.py:38-44``): fine at its L=50,
    85 GB at L=4096 x B=64 x 20 heads.
  * the window trunk's text layers (``models/window_trunk.py``): causal,
    grouped heads (``H`` query heads over ``Hkv`` key/value heads, the keys
    and values never repeated), and in its window layers a sliding band.

Queries go in blocks of ``block_q``. A query block reads only the keys its
band allows: every key without ``causal``; keys up to its last query with
it; and with ``window`` no key further back than ``window - 1`` from its
first query (the band's start rounded down to a whole block, so a window
layer visits at most ``window / block_q + 1`` blocks a query block, a causal
one the blocks up to the diagonal). Inside that run of keys the softmax is
online over runs of at most ``block_k`` keys; the band's mask is applied
elementwise to the runs that cross the diagonal or the band's far edge and
to no other. Each query block is a ``jax.checkpoint``: the backward pass
computes its scores a second time from (q, k, v) and stores none, so the
largest array alive is one query block's ``block_q x block_k`` scores a head.
All slices are static (a block's cotangent goes back by a pad, not a
scatter); the program grows with (L / block_q) x (band / block_k).

Numerics: scores and softmax in float32, the two products on the MXU in the
inputs' dtype with float32 accumulation; a masked key is ``-1e30`` before
the softmax; a query with no allowed key at all (a fully padded row, or a
causal text whose first tokens are padding) returns 0, as
``flash_attention`` in ``ops/attention_kernels.py`` does.

The last chip measurement of the bidirectional form (jax 0.4.37, not
repeated on the current tree: ROADMAP S4) was that XLA's fused dense path
beats it at every size that fits, so ``attn_impl='auto'`` takes it only past
``attn_chunk_threshold``; multi-chip long context is ``parallel/ring.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def key_band(
    q0: int, q1: int, lk: int, causal: bool, window: int | None, block_q: int
) -> tuple[int, int]:
    """The run of keys [lo, hi) that queries [q0, q1) may read at all."""
    if not causal:
        return 0, lk
    lo = 0 if window is None else max(q0 - window + 1, 0) // block_q * block_q
    return lo, q1


def scores_computed_share(
    length: int, block_q: int, causal: bool = True, window: int | None = None
) -> float:
    """Score elements the blocked core computes for a text of ``length``
    tokens, over the ``length^2`` of the dense square, a head."""
    computed = 0
    for q0 in range(0, length, block_q):
        q1 = min(q0 + block_q, length)
        lo, hi = key_band(q0, q1, length, causal, window, block_q)
        computed += (q1 - q0) * (hi - lo)
    return computed / float(length * length)


def _query_block(qb, kb, vb, bias, *, q0, k0, causal, window, block_k, scale):
    """One query block against its run of keys: qb (B, bq, Hkv, G, D), kb /
    vb (B, run, Hkv, D), bias (B, run) float32 or None; ``q0`` / ``k0`` the
    positions of the first query and key. Returns (B, bq, Hkv, G, Dv)
    float32."""
    bq = qb.shape[1]
    m = l = acc = None
    for r0 in range(0, kb.shape[1], block_k):
        ks, vs = kb[:, r0:r0 + block_k], vb[:, r0:r0 + block_k]
        first, last = k0 + r0, k0 + r0 + ks.shape[1] - 1
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qb, ks, preferred_element_type=jnp.float32
        ) * scale
        if bias is not None:
            s = s + bias[:, None, None, None, r0:r0 + block_k]
        # elementwise only where the run crosses the diagonal or the band's
        # far edge: a run wholly inside the band needs no mask of its own
        crosses = causal and (
            last > q0 or (window is not None and q0 + bq - 1 - first >= window)
        )
        if crosses:
            i = q0 + jnp.arange(bq)[:, None]
            t = first + jnp.arange(ks.shape[1])[None, :]
            allowed = t <= i
            if window is not None:
                allowed &= (i - t) < window
            s = jnp.where(allowed, s, _NEG_INF)
        m_run = jnp.max(s, axis=-1)
        m_new = m_run if m is None else jnp.maximum(m, m_run)
        p = jnp.exp(s - m_new[..., None])
        pv = jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32,
        )
        if m is None:
            l, acc = jnp.sum(p, axis=-1), pv
        else:
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + pv
        m = m_new
    # a query whose every key was masked holds exp(0) for each of them
    seen = (m > 0.5 * _NEG_INF)[..., None]
    out = jnp.where(seen, acc / l[..., None], 0.0)
    return out.transpose(0, 3, 1, 2, 4)


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    block_q: int = 256,
    block_k: int = 512,
    causal: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """Multi-head attention, (..., L, H, D) layout like the Flax module.

    ``q``: (..., Lq, H, Dk); ``k``/``v``: (..., Lk, Hkv, Dk/Dv) with ``H`` a
    multiple of ``Hkv`` (query head ``j`` reads key/value head ``j // (H /
    Hkv)``); ``mask``: optional (..., Lk) key mask (1 = attend). ``causal``:
    query ``i`` reads key ``t <= i`` (self-attention: ``Lq == Lk``), and
    with ``window`` only ``i - t < window``. Returns (..., Lq, H, Dv).
    """
    *batch, lq, h, dk = q.shape
    lk, hkv, dv = k.shape[-3], k.shape[-2], v.shape[-1]
    if h % hkv:
        raise ValueError(f"{h} query heads do not group over {hkv} key/value heads")
    if causal and lq != lk:
        raise ValueError(f"a causal mask needs as many queries as keys, not {lq} and {lk}")
    if window is not None and not causal:
        raise ValueError("a window is the causal band's width: it needs causal=True")
    bsz = 1
    for b in batch:
        bsz *= b
    qf = q.reshape(bsz, lq, hkv, h // hkv, dk)
    kf = k.reshape(bsz, lk, hkv, dk)
    vf = v.reshape(bsz, lk, hkv, dv)
    bias = None
    if mask is not None:
        bias = jnp.where(mask.reshape(bsz, lk) > 0, 0.0, _NEG_INF).astype(jnp.float32)
    block_q, block_k = min(block_q, max(lq, 1)), min(block_k, max(lk, 1))

    blocks = []
    for q0 in range(0, lq, block_q):
        q1 = min(q0 + block_q, lq)
        lo, hi = key_band(q0, q1, lk, causal, window, block_q)
        # checkpointed: the backward computes this block's scores again from
        # (q, k, v) instead of keeping (block_q, run) residuals a block
        block = jax.checkpoint(partial(
            _query_block, q0=q0, k0=lo, causal=causal, window=window,
            block_k=block_k, scale=1.0 / (dk**0.5),
        ))
        blocks.append(block(
            qf[:, q0:q1], kf[:, lo:hi], vf[:, lo:hi],
            None if bias is None else bias[:, lo:hi],
        ))
    out = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    return out.astype(q.dtype).reshape(*batch, lq, h, dv)
