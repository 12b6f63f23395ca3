"""The sum over a token's (token, choice) pairs that fall on held experts, as
one Pallas TPU kernel: ``pair_sum``.

The expert layer (``models/sparse_trunk.py``) keeps its rows in expert
order, a sorted buffer whose first ``held`` rows are the pairs on the experts
held here. Twice a layer something runs over a token's k choices and writes
one row a token: the weighted sum of the experts' outputs (the combine) and
the way back of the cotangents to the tokens (``to_expert_order``'s
derivative). Done as k XLA gathers of ``(T, d)`` rows, every pair costs a
whole row read and written, though only the pairs on held experts (a
quarter, an eighth) carry a row that is not zero. This kernel reads only
those rows and writes each token once::

    y[t] = sum over c = 0..k-1 with back[c, t] < held of w[t, c] * src[back[c, t]]

A DMA moves whole tiles, and in XLA's (8, 128) tiles one row of ``src`` is a
part of every tile it crosses: a first kernel (``pair_sum_rows``) copies the
held rows into 32-bit words, ``(rows, 1, words)``, one tile high a row
(bfloat16 the row's two halves to a word). The sum's grid runs over blocks of
tokens, one step more than there are blocks. A block's table (each token's
count of held pairs and their rows in choice order, the j-th in slot j) comes
to SMEM through the pipeline; at step s the scalar core starts one row DMA
for each held pair of block s into one half of the slots, then waits for
block s - 1's rows, started a step earlier into the other half, and the
vector units sum those: the copies of one block fly while the previous one
is summed. The sum runs in float32 in choice order, over the slots up to the
largest count of each 16-token tile (those past a token's own count masked),
and casts to ``src.dtype`` once, as the k gathers summed it: the same
products in the same order, the pairs left out having added an exact zero. A
token with no held pair reads zeros. Off the TPU both kernels run
interpreted (``attention_kernels._interpret``, the repo's one switch); under
a cohort's ``vmap`` they run client by client.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedrec_tpu.ops import attention_kernels

# bytes of VMEM a block's gathered slots may take (two blocks' are in VMEM at
# once), the tokens a block holds at most, and the rows a step of the copy
# moves; a block is whole tiles of TILE tokens, summed LANES words of a row at
# a time, and the scalar core starts the copies of UNROLL tokens a loop step
# (each measured on a v5e: PERF.md section 6)
SLOT_BYTES = 4 * 1024 * 1024
MAX_BLOCK = 512
COPY_ROWS = 256
TILE = 16
LANES = 1024
UNROLL = 4
WAIT_ROWS = 16
VMEM_LIMIT = 2 * SLOT_BYTES + 16 * 1024 * 1024
_HI = np.uint32(0xFFFF0000)     # a word's upper half


def token_block(tokens: int, choices: int, width: int, itemsize: int) -> int:
    """Tokens a grid step takes: the most, a power of two, whose k slots of
    rows fit ``SLOT_BYTES``, and no more than the tokens rounded up."""
    block = MAX_BLOCK
    while block > TILE and choices * block * width * itemsize > SLOT_BYTES:
        block //= 2
    return min(block, -(-tokens // TILE) * TILE)


def _parts(dtype) -> int:
    """Values of ``dtype`` a 32-bit word holds."""
    parts = {2: 2, 4: 1}.get(jnp.dtype(dtype).itemsize)
    if parts is None:
        raise ValueError(f"pair_sum sums rows of 16 or 32 bits, not {jnp.dtype(dtype)}")
    return parts


def _copy_kernel(held_ref, src_ref, out_ref):
    x = src_ref[...]
    if _parts(x.dtype) == 1:
        words = lax.bitcast_convert_type(x, jnp.uint32)
    else:
        half = x.shape[1] // 2
        bits = lambda part: lax.bitcast_convert_type(part.astype(jnp.float32), jnp.uint32)  # noqa: E731
        words = (bits(x[:, half:]) & _HI) | (bits(x[:, :half]) >> 16)
    out_ref[:, 0, :] = words


def _one_tile_a_row(src, held, interpret):
    """The first ``held`` rows of ``src`` as ``(rows, 1, words)`` uint32, one
    (1, 128) tile high a row, so that a DMA may move one; the rows past
    ``held`` are left unwritten (the steps past the last held row keep the
    block they had, and the pipeline moves nothing for them)."""
    rows, width = src.shape
    words = width // _parts(src.dtype)
    block = min(COPY_ROWS, -(-rows // TILE) * TILE)

    def at(i, held):
        return jnp.minimum(i, jnp.maximum(held[0] - 1, 0) // block)

    out = pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 1, words), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-rows // block),),
            in_specs=[pl.BlockSpec((block, width), lambda i, held: (at(i, held), 0))],
            out_specs=pl.BlockSpec((block, 1, words), lambda i, held: (at(i, held), 0, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="pair_sum_rows",
    )(held, src)
    # left to XLA, a buffer this size may be placed in VMEM beside the sum's
    # own slots, which then do not fit (the interpreter knows no such space)
    return out if interpret else pltpu.with_memory_space_constraint(out, pltpu.HBM)


def _sum_kernel(totals_ref, fullest_ref, table_ref, count_ref, *refs,
                choices, block, blocks, parts, weighted):
    *weights, src_ref, out_ref, slots, sems = refs
    w_ref = weights[0] if weighted else None
    words = slots.shape[2]
    span = choices * block          # the slots of one block: one half of ``slots``
    step = pl.program_id(0)

    def start(side):
        def one_token(t):
            def one(j, carry):
                pltpu.make_async_copy(
                    src_ref.at[pl.ds(table_ref[1 + j, t], 1)],
                    slots.at[pl.ds(side * span + j * block + t, 1)], sems.at[side],
                ).start()
                return carry

            lax.fori_loop(0, table_ref[0, t], one, 0)

        def tokens(s, carry):
            for u in range(UNROLL):
                one_token(s * UNROLL + u)
            return carry

        lax.fori_loop(0, block // UNROLL, tokens, 0)

    def wait(side, rows):
        # a wait takes as many bytes as its copy moves: WAIT_ROWS rows at a time
        many = min(WAIT_ROWS, src_ref.shape[0], span)

        def waits(n):
            def one(_, carry):
                pltpu.make_async_copy(
                    src_ref.at[pl.ds(0, n)], slots.at[pl.ds(0, n)], sems.at[side]).wait()
                return carry
            return one

        lax.fori_loop(0, rows // many, waits(many), 0)
        lax.fori_loop(0, rows % many, waits(1), 0)

    def tile(side):
        def body(m, carry):
            at = pl.multiple_of(m * TILE, TILE)
            count = count_ref[pl.ds(at, TILE), :]
            weight = w_ref[pl.ds(at, TILE), :] if weighted else None
            # the tile's largest count: slots past it hold no token's pair
            fullest = fullest_ref[(step - 1) * (block // TILE) + m]
            for lanes in range(0, words, LANES):
                cols = slice(lanes, min(lanes + LANES, words))

                def add(acc, j, cols=cols):
                    w = slots[pl.ds(side * span + j * block + at, TILE), 0, cols]
                    if parts == 1:
                        vals = [lax.bitcast_convert_type(w, jnp.float32)]
                    else:
                        vals = [lax.bitcast_convert_type(w << 16, jnp.float32),
                                lax.bitcast_convert_type(w & _HI, jnp.float32)]
                    for p, v in enumerate(vals):
                        if weighted:
                            v = v * weight[:, j : j + 1]
                        acc[p] = acc[p] + jnp.where(count > j, v, 0.0)
                    return acc

                zero = jnp.zeros((TILE, cols.stop - lanes), jnp.float32)
                acc = add([zero] * parts, 0)
                for j in range(1, choices):
                    acc = lax.cond(fullest > j, functools.partial(add, j=j), lambda a: a, acc)
                for p, a in enumerate(acc):
                    out_ref[pl.ds(at, TILE), pl.ds(p * words + lanes, cols.stop - lanes)] = (
                        a.astype(out_ref.dtype))
            return carry

        lax.fori_loop(0, block // TILE, body, 0)

    # step s starts the row copies of block s into one half of the slots and
    # sums block s - 1 out of the other, whose copies flew meanwhile
    @pl.when(step < blocks)
    def _():
        start(step % 2)

    @pl.when(step > 0)
    def _():
        side = (step - 1) % 2
        wait(side, totals_ref[step - 1])
        tile(side)


@functools.partial(jax.jit, static_argnames="weighted")
def _pair_sum(src, back, w, held, *, weighted: bool):
    (choices, tokens), (_, width) = back.shape, src.shape
    parts = _parts(src.dtype)
    block = token_block(tokens, choices, width, src.dtype.itemsize)
    blocks = -(-tokens // block)
    pad = lambda x: jnp.pad(  # noqa: E731
        x, [(0, 0)] * (x.ndim - 1) + [(0, blocks * block - tokens)])
    take = back < held
    count = pad(jnp.sum(take, axis=0, dtype=jnp.int32))
    # (choice c, slot j): token t's choice c is its j-th held pair
    in_slot = take[:, None] & (jnp.cumsum(take, axis=0)[:, None] - 1 == jnp.arange(choices)[None, :, None])
    ranked = pad(jnp.sum(jnp.where(in_slot, back[:, None], 0), axis=0))
    table = jnp.concatenate([count[None], ranked])
    totals = jnp.sum(count.reshape(blocks, block), axis=1)
    fullest = jnp.max(count.reshape(-1, TILE), axis=1)
    held = jnp.reshape(held, (1,)).astype(jnp.int32)
    interpret = attention_kernels._interpret()
    # grid step s takes block s's table and sums block s - 1
    last = lambda s, *_: (jnp.maximum(s - 1, 0), 0)  # noqa: E731
    inputs = [table, count[:, None]]
    specs = [pl.BlockSpec((choices + 1, block), lambda s, *_: (0, jnp.minimum(s, blocks - 1)),
                          memory_space=pltpu.SMEM),
             pl.BlockSpec((block, 1), last)]
    if weighted:
        # the j-th held pair's weight in column j, rounded to the rows' dtype
        # as the products are taken in it
        weight = jnp.where(take, w.T, 0).astype(src.dtype).astype(jnp.float32)
        inputs.append(pad(jnp.sum(jnp.where(in_slot, weight[:, None], 0.0), axis=0)).T)
        specs.append(pl.BlockSpec((block, choices), last))
    inputs.append(_one_tile_a_row(src, held, interpret))
    specs.append(pl.BlockSpec(memory_space=pltpu.HBM))
    words = width // parts
    return pl.pallas_call(
        functools.partial(_sum_kernel, choices=choices, block=block, blocks=blocks, parts=parts,
                          weighted=weighted),
        out_shape=jax.ShapeDtypeStruct((tokens, width), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(blocks + 1,),
            in_specs=specs,
            out_specs=pl.BlockSpec((block, width), last),
            scratch_shapes=[
                pltpu.VMEM((2 * choices * block, 1, words), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="pair_sum",
    )(totals, fullest, *inputs)


_weighted = jax.custom_batching.sequential_vmap(functools.partial(_pair_sum, weighted=True))
_unweighted = jax.custom_batching.sequential_vmap(
    lambda src, back, held: _pair_sum(src, back, None, held, weighted=False)
)


def pair_sum(src: jnp.ndarray, back: jnp.ndarray, w: jnp.ndarray | None,
             held: jnp.ndarray) -> jnp.ndarray:
    """(rows, d) rows, (k, T) int32 the row of each (token, choice) pair,
    weights (T, k) or None, the count of rows read ``held`` (int32) ->
    (T, d) in ``src``'s dtype: ``y[t] = sum_c w[t, c] * src[back[c, t]]``
    over the pairs with ``back[c, t] < held``, in float32 and choice order.
    Not differentiated: the callers state their own derivatives."""
    if w is None:
        return _unweighted(src, back, held)
    return _weighted(src, back, w, held)
