"""Mesh-sharded news catalog (``shard.table``): the token-state table
row-sharded across the client mesh axis, with an in-step fixed-shape
owner-bucketed gather.

Today the frozen ``token_states`` table is replicated on every device, so
catalog size is capped by single-device HBM (ROADMAP item 2). Here the
table lives row-sharded — device *s* of *S* holds rows
``[s*R, (s+1)*R)`` — and the step's unique-news gather becomes a
four-phase exchange, every shape static so nothing retraces:

    1. BUCKET   each client's ``(U,)`` unique ids by owner shard
                (``owner = id // R``) into an ``(S, U)`` request buffer —
                bucket capacity U is the worst case (all ids on one
                shard), so no id can ever be dropped;
    2. A2A OUT  ``lax.all_to_all`` the id buckets: shard *s* receives the
                ``(S, U)`` requests destined to it;
    3. GATHER   each shard answers from its local rows
                (``local[req - s*R]``) — an ordinary local gather;
    4. A2A BACK the ``(S, U, ...)`` answer rows return to their
                requesters, which scatter them back to the original id
                order (the sort permutation inverts exactly).

The result is bit-identical to ``full_table[ids]`` for every id in
``[0, num_rows)`` (pinned in ``tests/test_shard_table.py``), so the
train step's downstream math — dedup inverse scatter, text-head encode,
``data.gather_chunk`` tiling — is untouched.
Capacity scales linearly with devices: ``rows_per_device = ceil(N / S)``.

Why fixed shapes: a "send only what each shard needs" exchange would put
a data-dependent dimension inside the compiled step (retrace per
batch). The ``(S, U)`` worst-case
bucket wastes wire on padding slots, which is exactly what the round
loop's host-side dedup bounds: ``U`` is the encode size it chose from the
traffic (``train/step.py: host_news_dedup``), not the slot count.
docs/DESIGN.md §5i.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "TableSpec",
    "ShardedNewsTable",
    "owner_bucketed_gather",
    "a2a_bytes_per_gather",
    "lost_row_mask",
    "recover_table_rows",
    "reshard_table",
]


@dataclass(frozen=True)
class TableSpec:
    """Static layout of a sharded table — what the step builders compile
    against (all ints, so it can never introduce a dynamic shape)."""

    axis: str             # mesh axis the rows shard over
    num_shards: int       # devices along that axis
    rows_per_shard: int   # padded_rows / num_shards
    num_rows: int         # the REAL catalog rows (ids are < this)

    @property
    def padded_rows(self) -> int:
        return self.num_shards * self.rows_per_shard


@dataclass(frozen=True)
class ShardedNewsTable:
    """The at-rest sharded table: ``rows`` is the zero-padded
    ``(padded_rows, ...)`` array committed to
    ``NamedSharding(mesh, P(axis))`` — dim 0 split across the mesh — plus
    the :class:`TableSpec` the compiled programs need."""

    rows: jax.Array
    spec: TableSpec

    @classmethod
    def create(
        cls,
        table: Any,
        mesh: Mesh,
        axis: str,
        dtype: Any = None,
    ) -> "ShardedNewsTable":
        """Pad ``table`` (N, ...) to a multiple of the axis size and commit
        it row-sharded. Padding rows are zeros and unreachable (ids are
        < N); ``shard.table_occupancy`` reports N / padded."""
        arr = np.asarray(table)
        if dtype is not None:
            arr = arr.astype(np.dtype(dtype))
        num_shards = int(mesh.shape[axis])
        n = arr.shape[0]
        pad = (-n) % num_shards
        if pad:
            arr = np.concatenate(
                [arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)]
            )
        spec = TableSpec(
            axis=axis,
            num_shards=num_shards,
            rows_per_shard=arr.shape[0] // num_shards,
            num_rows=n,
        )
        rows = jax.device_put(arr, NamedSharding(mesh, P(axis)))
        return cls(rows=rows, spec=spec)


def owner_bucketed_gather(
    local_rows: jnp.ndarray, ids: jnp.ndarray, spec: TableSpec
) -> jnp.ndarray:
    """Inside a ``shard_map`` block: gather ``full_table[ids]`` from the
    row-sharded table via the fixed-shape owner-bucketed exchange above.

    ``local_rows`` is this device's ``(rows_per_shard, ...)`` block,
    ``ids`` any ``(U,)`` int vector of global row ids in
    ``[0, num_rows)``; returns ``(U, ...)`` rows in ``ids`` order, exact.
    Degenerates to a plain local gather at ``num_shards == 1`` (the
    ``all_to_all`` over a size-1 axis is the identity).
    """
    u = ids.shape[0]
    s, r = spec.num_shards, spec.rows_per_shard
    owner = jnp.clip(ids // r, 0, s - 1).astype(jnp.int32)
    # stable sort by owner: contiguous per-owner runs whose in-run rank is
    # the bucket slot — the permutation is inverted exactly on the way back
    order = jnp.argsort(owner, stable=True)
    sorted_ids = ids[order]
    sorted_owner = owner[order]
    first = jnp.searchsorted(sorted_owner, sorted_owner, side="left")
    rank = jnp.arange(u, dtype=jnp.int32) - first.astype(jnp.int32)
    send = (
        jnp.zeros((s, u), ids.dtype).at[sorted_owner, rank].set(sorted_ids)
    )
    # phase 2: row d of `send` travels to shard d; we receive (S, U)
    # requests, row s' = the ids shard s' wants from OUR rows
    req = lax.all_to_all(send, spec.axis, split_axis=0, concat_axis=0, tiled=True)
    my_base = lax.axis_index(spec.axis).astype(req.dtype) * r
    local_idx = jnp.clip(req - my_base, 0, r - 1)
    answers = local_rows[local_idx]  # (S, U, ...)
    # phase 4: answers[s'] returns to shard s'; recv[d] = our requested
    # rows as held by shard d
    recv = lax.all_to_all(
        answers, spec.axis, split_axis=0, concat_axis=0, tiled=True
    )
    gathered_sorted = recv[sorted_owner, rank]
    inv = jnp.argsort(order, stable=True)
    return gathered_sorted[inv]


def lost_row_mask(spec: TableSpec, lost_shards) -> np.ndarray:
    """``(num_rows,)`` bool: True for the TRUE catalog rows whose owner
    shard is in ``lost_shards`` — the rows a dead host/device took with it
    under the ``[s*R, (s+1)*R)`` row-sharded layout.  Padding rows are
    outside ``num_rows`` and never appear."""
    owner = np.arange(spec.num_rows) // spec.rows_per_shard
    return np.isin(owner, np.asarray(sorted(set(int(s) for s in lost_shards))))


def recover_table_rows(
    surviving_rows: Any,
    lost_shards,
    spec: TableSpec,
    checkpoint_rows: Any,
) -> tuple[np.ndarray, int]:
    """Rebuild the full TRUE-row table after a shrink lost some shards.

    ``surviving_rows`` is a host copy of the old ``(padded_rows, ...)``
    sharded buffer in which the ``lost_shards`` blocks are gone (garbage,
    zeros — whatever the dead owner left unreachable); ``checkpoint_rows``
    is the last :func:`~fedrec_tpu.train.checkpoint.save_table_checkpoint`
    table (unpadded ``(num_rows, ...)``).  Lost rows are refilled from the
    checkpoint, surviving rows are kept LIVE (bit-identical to what the
    survivors held), and the result is the exact ``(num_rows, ...)`` table
    ready for :meth:`ShardedNewsTable.create` on the new, smaller mesh.
    Returns ``(full_rows, rows_recovered)``.

    Raises when a lost row has no checkpoint to come back from — losing
    catalog rows silently is the pre-elastic failure this replaces.

    Call-site note: the COORDINATOR deployment's elastic recovery reloads
    the whole table (each host builds its local sharded table from the
    full token source / ``load_table_checkpoint``), so this partial-rows
    path serves the single-process multi-device loss case and pins the
    no-rows-lost acceptance contract (``tests/test_membership.py``).
    """
    surviving = np.asarray(surviving_rows)[: spec.num_rows]
    mask = lost_row_mask(spec, lost_shards)
    if not mask.any():
        return surviving.copy(), 0
    if checkpoint_rows is None:
        raise ValueError(
            f"{int(mask.sum())} catalog rows lived on lost shard(s) "
            f"{sorted(set(int(s) for s in lost_shards))} and no table "
            "checkpoint exists to recover them from — save one with "
            "train.checkpoint.save_table_checkpoint (the Trainer does at "
            "save cadence under shard.table) or re-supply the token source"
        )
    ckpt = np.asarray(checkpoint_rows)
    if ckpt.shape[0] < spec.num_rows:
        raise ValueError(
            f"table checkpoint holds {ckpt.shape[0]} rows but the catalog "
            f"has {spec.num_rows}; it cannot recover the lost shards"
        )
    full = surviving.copy()
    full[mask] = ckpt[: spec.num_rows][mask]
    return full, int(mask.sum())


def reshard_table(
    full_rows: Any, mesh: Mesh, axis: str, dtype: Any = None
) -> ShardedNewsTable:
    """Commit a recovered full-row table to a (re-formed) mesh — the
    shrink-and-continue tail of :func:`recover_table_rows`.  Identical to
    :meth:`ShardedNewsTable.create` (padding recomputed for the NEW shard
    count), named separately so reshard call sites read as what they are."""
    return ShardedNewsTable.create(full_rows, mesh, axis, dtype=dtype)


def a2a_bytes_per_gather(
    unique_slots: int, row_shape: tuple, row_dtype: Any, spec: TableSpec
) -> int:
    """Modeled interconnect bytes of ONE owner-bucketed gather across the
    whole mesh: the (S, U) id buckets out plus the (S, U, row) answers
    back, summed over the S participating devices. Static per compiled
    batch shape — the ``shard.a2a_bytes_total`` counter advances by this
    per dispatched step."""
    s, u = spec.num_shards, unique_slots
    id_bytes = 4  # int32 ids
    row_bytes = int(np.prod(row_shape)) * np.dtype(row_dtype).itemsize
    per_device = s * u * (id_bytes + row_bytes)
    return per_device * s
