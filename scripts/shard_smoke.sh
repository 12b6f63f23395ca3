#!/bin/bash
# Sharding smoke (ISSUE-11 acceptance scenarios), CPU-only:
#
#   1. 2-PROCESS GLOO EXCHANGE + FULL STEP: a REAL two-process world
#      (jax.distributed + gloo CPU collectives, the coordinator
#      deployment's rendezvous), 2 x 4 fake devices = one global
#      8-device mesh, with the token-state table row-sharded across
#      BOTH processes' devices — rows/device == padded/8 asserted from
#      the addressable shards — the owner-bucketed all_to_all gather
#      crossing the process boundary over real gloo TCP (rows
#      BIT-IDENTICAL to `full_table[ids]`), and the FULL federated
#      train step through the sharded catalog, with both processes'
#      results asserted bit-equal. (The full-step leg was previously
#      blocked on a gloo transport flake — a TCP pair dying at the
#      first collective, the same pair.cc error that failed
#      tests/test_multihost_world.py at HEAD; the bounded
#      rendezvous-retry + transport probe in initialize_distributed
#      now turns that flake into a retried bring-up.)
#   2. SHARDED-TABLE STEP EQUALITY: the federated train step through
#      the sharded catalog on the 8-device mesh must be BIT-IDENTICAL
#      to the replicated-table step (the degenerate-config equality).
#   3. FSDP STEP EQUALITY: a (clients=4, fsdp=2) mesh with the at-rest
#      state sharded per the size-aware policy — step + round-end sync
#      bit-identical to the 1-D replicated baseline, and the at-rest
#      buffers actually sharded (per-device bytes < replicated).
#
#   scripts/shard_smoke.sh     # or: make shard-smoke
#
# Artifacts land under /tmp/fedrec_shard_smoke for inspection.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=${SHARD_SMOKE_DIR:-/tmp/fedrec_shard_smoke}
rm -rf "$OUT"
mkdir -p "$OUT"

free_port() {
    python - <<'PY'
import socket
s = socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()
PY
}

# ---------------------------------------------- leg 1: 2-process gloo world
cat > "$OUT/gloo_worker.py" <<'PYEOF'
import os, sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()

from functools import partial

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from fedrec_tpu.parallel.multihost import initialize_distributed
from fedrec_tpu.shard.table import ShardedNewsTable, owner_bucketed_gather

port, pid = sys.argv[1], int(sys.argv[2])
initialize_distributed(f"127.0.0.1:{port}", 2, pid)
assert jax.device_count() == 8, "global world must see 2x4 devices"

mesh = Mesh(np.array(jax.devices()).reshape(8), ("clients",))
rng = np.random.default_rng(0)
N, L, D = 1000, 12, 48  # not divisible by 8: padding path
full = rng.standard_normal((N, L, D)).astype(np.float32)
tab = ShardedNewsTable.create(full, mesh, "clients")
assert tab.spec.rows_per_shard == tab.spec.padded_rows // 8
local_rows = {s.data.shape[0] for s in tab.rows.addressable_shards}
assert local_rows == {tab.spec.rows_per_shard}, local_rows

U = 64
ids = rng.integers(0, N, (8, U)).astype(np.int32)


@partial(
    shard_map, mesh=mesh,
    in_specs=(P("clients"), P("clients")), out_specs=P("clients"),
    check_vma=False,
)
def gather(rows, ids_blk):
    return owner_bucketed_gather(rows, ids_blk[0], tab.spec)[None]


out = jax.jit(gather)(
    tab.rows, jax.device_put(ids, NamedSharding(mesh, P("clients")))
)
rep = jax.jit(lambda t: t, out_shardings=NamedSharding(mesh, P()))(out)
np.testing.assert_array_equal(np.asarray(rep), full[ids])
print(
    f"GLOO_GATHER_OK {pid} rows/dev={tab.spec.rows_per_shard} "
    f"ids/client={U}",
    flush=True,
)

# ---- full-step leg: the federated train step THROUGH the sharded
# catalog across the 2-process world (identical deterministic setup on
# both processes; each process_put slices out its addressable shards)
from pathlib import Path

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.fed import get_strategy
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.train import build_fed_train_step
from fedrec_tpu.train.state import init_client_state, replicate_state

outdir = Path(sys.argv[3])
cfg = ExperimentConfig()
cfg.model.news_dim = 32
cfg.model.num_heads = 4
cfg.model.head_dim = 8
cfg.model.query_dim = 16
cfg.model.bert_hidden = D
cfg.model.text_encoder_mode = "head"
cfg.model.dropout_rate = 0.0
cfg.data.max_his_len = 10
cfg.data.max_title_len = L
cfg.data.batch_size = 8
cfg.fed.num_clients = 8
cfg.shard.table = True

model = NewsRecommender(cfg.model)
st = replicate_state(
    init_client_state(model, cfg, jax.random.PRNGKey(0), N, L),
    8, jax.random.PRNGKey(1),
)


def to_global(x, spec=P("clients")):
    # make_array_from_callback builds each process's addressable shards
    # LOCALLY from the (identical, same-seed) host value — zero
    # collectives. device_put against a multi-host sharding would issue
    # a cross-process value-check broadcast PER LEAF, and concurrent
    # small broadcasts are exactly where this rig's gloo transport
    # desyncs (pair.cc preamble mismatches).
    x = np.asarray(x)
    return jax.make_array_from_callback(
        x.shape, NamedSharding(mesh, spec), lambda idx: x[idx]
    )


st = jax.tree_util.tree_map(to_global, st)
rng2 = np.random.default_rng(7)
b = cfg.data.batch_size
batch = {
    "candidates": rng2.integers(
        0, N, (8, b, 1 + cfg.data.npratio)
    ).astype(np.int32),
    "history": rng2.integers(
        0, N, (8, b, cfg.data.max_his_len)
    ).astype(np.int32),
    "labels": np.zeros((8, b), np.int32),
}
batch = {k: to_global(v) for k, v in batch.items()}
step = build_fed_train_step(
    model, cfg, get_strategy("param_avg"), mesh, mode="joint",
    sharded_table=tab.spec,
)
out_state, metrics = step(st, batch, tab.rows)
rep_step = jax.jit(lambda t: t, out_shardings=NamedSharding(mesh, P()))(
    (out_state.user_params, out_state.news_params, metrics["loss"])
)
flat_u = np.concatenate(
    [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(rep_step[0])]
)
flat_n = np.concatenate(
    [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(rep_step[1])]
)
loss = np.asarray(rep_step[2])
assert np.isfinite(loss).all(), loss
np.savez(outdir / f"step_{pid}.npz", user=flat_u, news=flat_n, loss=loss)
print(f"GLOO_STEP_OK {pid} loss_mean={float(loss.mean()):.5f}", flush=True)
PYEOF

run_worker() {
    env -u XLA_FLAGS JAX_PLATFORMS=cpu \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python "$OUT/gloo_worker.py" "$2" "$1" "$OUT" \
        > "$OUT/gloo_worker_$1.log" 2>&1
}

# Bounded whole-world retry: the rig's gloo transport can drop a TCP
# pair MID-RUN (pair.cc read/framing errors), which no in-process retry
# can recover — the coordination runtime is poisoned. Bring-up flakes
# are already retried inside initialize_distributed (transport probe +
# port schedule); a mid-run pair death relaunches BOTH workers on a
# fresh port. Only the gloo transport signature retries — any other
# failure is a real regression and fails immediately.
LEG_OK=0
for ATTEMPT in 1 2 3; do
    PORT=$(free_port)
    rm -f "$OUT"/step_*.npz
    run_worker 0 "$PORT" & P0=$!
    run_worker 1 "$PORT" & P1=$!
    FAIL=0
    wait "$P0" || FAIL=1
    wait "$P1" || FAIL=1
    if [ "$FAIL" -eq 0 ]; then
        LEG_OK=1
        break
    fi
    if [ "$ATTEMPT" -lt 3 ] \
        && grep -qE "pair\.cc|[Gg]loo" "$OUT"/gloo_worker_*.log; then
        echo "[shard-smoke] gloo transport flake (attempt $ATTEMPT);" \
             "relaunching the 2-process world on a fresh port"
        continue
    fi
    break
done
if [ "$LEG_OK" -ne 1 ]; then
    echo "[shard-smoke] 2-process gloo leg FAILED — worker logs:"
    cat "$OUT"/gloo_worker_*.log
    exit 1
fi
grep -h "GLOO_GATHER_OK" "$OUT"/gloo_worker_*.log
grep -h "GLOO_STEP_OK" "$OUT"/gloo_worker_*.log

# the 2-process step leg's results are bit-equal across processes
python - <<PYEOF
import numpy as np
a = np.load("$OUT/step_0.npz")
b = np.load("$OUT/step_1.npz")
np.testing.assert_array_equal(a["user"], b["user"])
np.testing.assert_array_equal(a["news"], b["news"])
np.testing.assert_array_equal(a["loss"], b["loss"])
print("[shard-smoke] 2-process full-step bit-equality OK")
PYEOF

# ------------------------------- legs 2+3: step equality on the 8-dev mesh
env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python - <<'PYEOF'
import numpy as np
import jax
import jax.numpy as jnp

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.fed import get_strategy
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.parallel import client_mesh, fed_mesh, shard_batch
from fedrec_tpu.shard import (
    ShardedNewsTable, fsdp_state_shardings,
)
from fedrec_tpu.train import build_fed_train_step, build_param_sync
from fedrec_tpu.train.state import init_client_state, replicate_state


def tiny_cfg(**over):
    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.model.text_encoder_mode = "head"
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.data.batch_size = 8
    for k, v in over.items():
        section, key = k.split("__")
        setattr(getattr(cfg, section), key, v)
    return cfg


def setup(cfg, num_news=100, seed=0):
    rng = np.random.default_rng(seed)
    ts = rng.standard_normal(
        (num_news, cfg.data.max_title_len, cfg.model.bert_hidden)
    ).astype(np.float32)
    model = NewsRecommender(cfg.model)
    st = replicate_state(
        init_client_state(
            model, cfg, jax.random.PRNGKey(0), num_news,
            cfg.data.max_title_len,
        ),
        cfg.fed.num_clients, jax.random.PRNGKey(1),
    )
    b = cfg.data.batch_size
    batch = {
        "candidates": rng.integers(
            0, num_news, (cfg.fed.num_clients, b, 1 + cfg.data.npratio)
        ).astype(np.int32),
        "history": rng.integers(
            0, num_news, (cfg.fed.num_clients, b, cfg.data.max_his_len)
        ).astype(np.int32),
        "labels": np.zeros((cfg.fed.num_clients, b), np.int32),
    }
    return model, ts, st, batch


def leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


# ---- leg 2: sharded catalog == dense
cfg = tiny_cfg(fed__num_clients=8)
model, ts, st0, batch = setup(cfg)
mesh = client_mesh(8)
tab = ShardedNewsTable.create(ts, mesh, "clients")

step_d = build_fed_train_step(
    model, cfg, get_strategy("param_avg"), mesh, mode="joint"
)
step_s = build_fed_train_step(
    model, cfg, get_strategy("param_avg"), mesh, mode="joint",
    sharded_table=tab.spec,
)
_, _, st0b, _ = setup(cfg)
sd, md = step_d(st0, shard_batch(mesh, batch), jnp.asarray(ts))
ss, ms = step_s(st0b, shard_batch(mesh, batch), tab.rows)
np.testing.assert_array_equal(np.asarray(md["loss"]), np.asarray(ms["loss"]))
for a, b in zip(leaves(sd.user_params), leaves(ss.user_params)):
    np.testing.assert_array_equal(a, b)
print("STEP_EQUALITY_OK per-batch")

# ---- leg 3: fsdp at-rest sharding == 1-D replicated baseline
cfg_f = tiny_cfg(fed__num_clients=4)
cfg_f.shard.fsdp = 2
cfg_f.shard.fsdp_min_size_mb = 0.0
mesh_f = fed_mesh(cfg_f)
model_f, ts_f, st_f0, batch_f = setup(cfg_f, seed=3)
shardings = fsdp_state_shardings(st_f0, mesh_f, cfg_f)
placed = jax.tree_util.tree_map(
    lambda x, s: jax.device_put(jnp.asarray(x), s), st_f0, shardings
)
rep_bytes = sum(x.nbytes for x in leaves(st_f0))
local_bytes = sum(
    max(s.data.nbytes for s in x.addressable_shards)
    for x in jax.tree_util.tree_leaves(placed)
)
assert local_bytes < rep_bytes, (local_bytes, rep_bytes)
step_f = build_fed_train_step(
    model_f, cfg_f, get_strategy("param_avg"), mesh_f, mode="joint",
    state_shardings=shardings,
)
sync_f = build_param_sync(
    cfg_f, mesh_f, get_strategy("param_avg"), state_shardings=shardings
)
sf, mf = step_f(placed, shard_batch(mesh_f, batch_f), jnp.asarray(ts_f))
sf = sync_f(sf, jnp.ones((4,), jnp.float32))

cfg_b = tiny_cfg(fed__num_clients=4)
mesh_b = client_mesh(4, max_devices=4)
model_b, ts_b, st_b0, _ = setup(cfg_b, seed=3)
step_b = build_fed_train_step(
    model_b, cfg_b, get_strategy("param_avg"), mesh_b, mode="joint"
)
sync_b = build_param_sync(cfg_b, mesh_b, get_strategy("param_avg"))
sb, mb2 = step_b(st_b0, shard_batch(mesh_b, batch_f), jnp.asarray(ts_b))
sb = sync_b(sb, jnp.ones((4,), jnp.float32))
np.testing.assert_array_equal(np.asarray(mf["loss"]), np.asarray(mb2["loss"]))
for a, b in zip(leaves(sf.user_params), leaves(sb.user_params)):
    np.testing.assert_array_equal(a, b)
print(f"FSDP_EQUALITY_OK bytes/dev={local_bytes} replicated={rep_bytes}")
PYEOF

echo "[shard-smoke] OK"
