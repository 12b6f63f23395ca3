"""Blockwise (chunked) attention — O(L) memory long-context path, pure XLA.

The reference materializes dense ``(bz, heads, L, L)`` attention scores
(reference ``attention.py:38-44``); fine at its fixed L=50, impossible for
long histories (L=4096 at B=64 x 20 heads = 85 GB of scores). The last
chip measurement (jax 0.4.37, not repeated on the current tree: ROADMAP S4)
was that XLA's fused dense path beats our Pallas flash kernel at every size
that FITS — the 20-dim heads pad
to 128 lanes in a hand kernel, wasting 6.4x MXU/bandwidth, while XLA packs
them. So the long-context strategy is:

  * L <= ~1k: dense XLA (fastest, fits)
  * beyond:   THIS module — ``lax.scan`` over query/key blocks with an
    online softmax, ``jax.checkpoint`` on the block body so the backward
    re-computes block scores instead of storing them (Blockwise Parallel
    Transformer style). Everything stays inside one jit region; each block
    matmul is MXU-sized; nothing O(L^2) is ever resident.
  * multi-chip: ring/Ulysses sequence parallelism (``parallel/ring.py``).

Numerics match ``flash_attention`` in ``ops/attention_kernels.py``: stable
softmax, additive -1e9 key bias for the mask, fully-masked rows return 0
(the jnp path's ``alpha * mask / (sum + 1e-8)`` semantics, reference
``attention.py:41``).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e9


def _pad_axis(x: jnp.ndarray, axis: int, mult: int, value: float = 0.0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def chunked_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    block_q: int = 256,
    block_k: int = 512,
) -> jnp.ndarray:
    """Multi-head attention, (..., L, H, D) layout like the Flax module.

    ``q``: (..., Lq, H, Dk); ``k``/``v``: (..., Lk, H, Dv); ``mask``:
    optional (..., Lk) key mask (1 = attend). Returns (..., Lq, H, Dv).
    Peak memory is O(block_q * block_k) scores per step instead of O(L^2).
    """
    *batch, lq, h, dk = q.shape
    lk, dv = k.shape[-3], v.shape[-1]
    bsz = 1
    for b in batch:
        bsz *= b
    qf = q.reshape(bsz, lq, h, dk)
    kf = k.reshape(bsz, lk, h, dk)
    vf = v.reshape(bsz, lk, h, dv)

    if mask is None:
        bias = jnp.zeros((bsz, lk), jnp.float32)
    else:
        bias = jnp.where(mask.reshape(bsz, lk) > 0, 0.0, _NEG_INF).astype(
            jnp.float32
        )

    block_q = min(block_q, max(lq, 1))
    block_k = min(block_k, max(lk, 1))

    # pad; padded keys carry -inf bias so they never win the softmax
    qp = _pad_axis(qf, 1, block_q)
    kp = _pad_axis(kf, 1, block_k)
    vp = _pad_axis(vf, 1, block_k)
    biasp = _pad_axis(bias, 1, block_k, value=_NEG_INF)
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k

    # (n, bsz, block, ...) chunk-leading layouts for scan
    qc = qp.reshape(bsz, nq, block_q, h, dk).transpose(1, 0, 2, 3, 4)
    kc = kp.reshape(bsz, nk, block_k, h, dk).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(bsz, nk, block_k, h, dv).transpose(1, 0, 2, 3, 4)
    bc = biasp.reshape(bsz, nk, block_k).transpose(1, 0, 2)

    scale = 1.0 / (dk**0.5)

    def attend_q_chunk(qb):
        qbf = qb.astype(jnp.float32)

        # checkpointed: the backward re-computes this block's scores from
        # (qb, kb, vb) instead of storing (block_q, block_k) residuals per
        # step — the whole point of the blockwise formulation
        @jax.checkpoint
        def kv_step(carry, inputs):
            m, l, acc = carry
            kb, vb, bb = inputs
            s = (
                jnp.einsum(
                    "bqhd,bkhd->bhqk", qbf, kb,
                    preferred_element_type=jnp.float32,
                )
                * scale
                + bb[:, None, None, :]
            )
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((bsz, h, block_q), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((bsz, h, block_q), jnp.float32)
        acc0 = jnp.zeros((bsz, h, block_q, dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, acc0), (kc, vc, bc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (bsz, h, block_q, dv)
        return out.transpose(0, 2, 1, 3)  # (bsz, block_q, h, dv)

    out = lax.map(attend_q_chunk, qc)  # (nq, bsz, block_q, h, dv)
    out = out.transpose(1, 0, 2, 3, 4).reshape(bsz, nq * block_q, h, dv)
    out = out[:, :lq].astype(q.dtype)

    if mask is not None:
        has_valid = (mask.reshape(bsz, lk).sum(-1) > 0).astype(out.dtype)
        out = out * has_valid[:, None, None, None]
    return out.reshape(*batch, lq, h, dv)
