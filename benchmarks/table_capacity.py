"""Catalog capacity: rows-per-device x devices under the sharded table.

The replicated token-state table caps catalog size at single-device HBM
(ROADMAP item 2: MIND-small fits, a production million-item catalog does
not). ``shard.table`` row-shards it over the mesh, so capacity scales
linearly with devices. This benchmark banks that frontier:

1. **Modeled frontier** — max catalog rows per HBM budget x device
   count, replicated vs sharded, at the flagship row shape
   (``max_title_len x bert_hidden``, bf16 and f32) — plain arithmetic,
   labeled as such, so the sizing runbook (docs/OPERATIONS.md §3e) has
   numbers to point at.
2. **Measured leg** — on the LOCAL backend (8 fake CPU devices when no
   accelerator; the real slice otherwise): a :class:`ShardedNewsTable`
   is committed, per-device resident rows are asserted equal to
   ``padded_rows / devices`` from the actual addressable shards, the
   owner-bucketed ``all_to_all`` gather is checked BIT-IDENTICAL to the
   dense ``table[ids]``, and both gathers are timed (warm, readback-
   synchronized). CPU timings say nothing about chip speed — the row is
   labeled — but the exactness and residency claims are backend-exact.

Writes ``benchmarks/table_capacity.json`` (provenance-stamped) and
prints one JSON line.

    python benchmarks/table_capacity.py       # or: make table-capacity
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

_INNER = "FEDREC_TABLE_CAPACITY_INNER"

GIB = 1024**3
# flagship row shape (DataConfig.max_title_len x ModelConfig.bert_hidden)
ROW_SHAPE = (50, 768)
HBM_BUDGETS_GIB = (16, 32)
DEVICE_COUNTS = (1, 4, 8, 32, 64, 256)


def modeled_frontier() -> dict:
    out: dict = {"row_shape": list(ROW_SHAPE), "rows": []}
    for dtype, itemsize in (("bfloat16", 2), ("float32", 4)):
        row_bytes = int(np.prod(ROW_SHAPE)) * itemsize
        for budget in HBM_BUDGETS_GIB:
            per_dev = (budget * GIB) // row_bytes
            for n_dev in DEVICE_COUNTS:
                out["rows"].append({
                    "dtype": dtype,
                    "row_bytes": row_bytes,
                    "hbm_gib_per_device": budget,
                    "devices": n_dev,
                    "max_rows_replicated": int(per_dev),
                    "max_rows_sharded": int(per_dev * n_dev),
                })
    return out


def measured_leg() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from jax import shard_map
    from fedrec_tpu.shard.table import (
        ShardedNewsTable, a2a_bytes_per_gather, owner_bucketed_gather,
    )

    devices = jax.devices()
    s = len(devices)
    mesh = Mesh(np.array(devices).reshape(s), ("clients",))
    rng = np.random.default_rng(0)
    # small rows on CPU sim; the claim being measured is exactness +
    # residency + relative exchange cost, not chip throughput
    n, l, d = 4096 + 3, 12, 64  # +3: non-divisible (padding path)
    u = 256
    full = rng.standard_normal((n, l, d)).astype(np.float32)
    tab = ShardedNewsTable.create(full, mesh, "clients")

    resident = sorted({sh.data.shape[0] for sh in tab.rows.addressable_shards})
    assert resident == [tab.spec.rows_per_shard], resident
    assert tab.spec.rows_per_shard == tab.spec.padded_rows // s

    ids = rng.integers(0, n, (s, u)).astype(np.int32)
    ids_sharded = jax.device_put(ids, NamedSharding(mesh, P("clients")))

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P("clients"), P("clients")), out_specs=P("clients"),
        check_vma=False,
    )
    def sharded_gather(rows, ids_blk):
        return owner_bucketed_gather(rows, ids_blk[0], tab.spec)[None]

    @partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P("clients")), out_specs=P("clients"),
        check_vma=False,
    )
    def dense_gather(rows, ids_blk):
        return rows[ids_blk[0]][None]

    g_sharded = jax.jit(sharded_gather)
    g_dense = jax.jit(dense_gather)
    table_rep = jnp.asarray(full)

    out_s = np.asarray(g_sharded(tab.rows, ids_sharded))
    out_d = np.asarray(g_dense(table_rep, ids_sharded))
    np.testing.assert_array_equal(out_s, full[ids])
    np.testing.assert_array_equal(out_d, full[ids])

    def timed(fn, *args, iters=20) -> float:
        fn(*args)  # warm (compile)
        t0 = time.perf_counter()
        last = None
        for _ in range(iters):
            last = fn(*args)
        jax.block_until_ready(last)
        return (time.perf_counter() - t0) / iters

    dt_sharded = timed(g_sharded, tab.rows, ids_sharded)
    dt_dense = timed(g_dense, table_rep, ids_sharded)
    platform = devices[0].platform
    return {
        "platform": platform,
        "devices": s,
        "catalog_rows": n,
        "row_shape": [l, d],
        "unique_ids_per_client": u,
        "rows_per_device_sharded": tab.spec.rows_per_shard,
        "rows_per_device_replicated": n,
        "table_occupancy": round(n / tab.spec.padded_rows, 6),
        "gather_exact_vs_dense": True,  # assert above raised otherwise
        "sharded_gather_ms": round(dt_sharded * 1e3, 3),
        "dense_gather_ms": round(dt_dense * 1e3, 3),
        "a2a_bytes_per_gather": a2a_bytes_per_gather(
            u, (l, d), np.float32, tab.spec
        ),
        "timing_note": (
            "exactness/residency are backend-exact; the ms rows are "
            f"{platform} timings of the exchange vs the dense gather at "
            "toy shapes — never quote them as chip numbers"
        ),
    }


def main() -> int:
    from fedrec_tpu.hostenv import fake_device_count

    if (
        os.environ.get(_INNER) is None
        and os.environ.get("JAX_PLATFORMS", "cpu") == "cpu"
        and (fake_device_count() or 1) < 2
    ):
        # CPU backend with a single device: re-exec with an 8-device fake
        # mesh so the measured leg exercises a real multi-shard exchange
        from fedrec_tpu.hostenv import cpu_host_env

        env = cpu_host_env(8)
        env[_INNER] = "1"
        os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)], env)

    out = {
        "metric": "fedrec_table_capacity",
        "modeled_frontier": modeled_frontier(),
        "measured": measured_leg(),
    }
    from fedrec_tpu.utils.provenance import provenance

    out["provenance"] = provenance()
    (HERE / "table_capacity.json").write_text(json.dumps(out, indent=2))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
