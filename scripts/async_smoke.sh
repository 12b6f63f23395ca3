#!/bin/bash
# Buffered-async aggregation smoke (agg.mode=async across processes),
# CPU-only:
#
#   An agg.server commit authority (quorum 3 of world 4) + 4 async
#   workers, each a single-process Trainer pushing round deltas over the
#   fleet wire — worker 3 chaos-delayed 4s per push. Must prove:
#
#     1. QUORUM COMMIT: the global advances one version per round on the
#        3 on-time workers alone — the straggler is still sleeping when
#        the commit fires (>= ROUNDS commits total);
#     2. LATE FOLD: the straggler's delayed contribution lands in the
#        buffer and folds staleness-weighted into a LATER commit
#        (late_folds >= 1), never dropped while within agg.staleness_cap;
#     3. GATE -> ~0: the straggler's marginal commit gate (the async
#        analogue of the barrier's critical-path gate_ms) stays ~0 — a
#        barrier deployment would have charged it the full 4s straggle
#        every round;
#     4. FLEET: `fedrec-obs fleet` merges the commit authority's obs
#        artifacts with the workers' and renders the Aggregation panel
#        (commits / late folds / per-worker gate before-vs-after) AND
#        the Wire panel (per-edge RTT/offsets, the queue/wire/fold
#        commit decomposition, the straggler's push edge on the table);
#        `fedrec-obs fleet-trace` merges a trace whose wire flow arrows
#        causally link a worker's push into the authority's commit and
#        the commit into a worker's adoption — across process tracks;
#     5. PERSIST: the pending buffer survives on disk (agg_buffer.npz in
#        --state-dir) after the service stops;
#     6. FLEET WATCH: a live telemetry collector (--watch) receives
#        every worker's round pushes; its fleet-level watch rules must
#        catch worker 3 as a persistent straggler from push inter-arrival
#        gaps alone (the chaos sleep sits at the push boundary, OUTSIDE
#        train.round_seconds) and write a firing fleet:straggler:3 alert
#        record to the collector's worker_fleet log;
#     7. COUNTSKETCH: a second 2-worker cluster pushes
#        fed.dcn_compress=countsketch — the commit authority folds the
#        raw sketches in sketch space (version still advances one per
#        round) and the measured per-push wire bytes land well under the
#        dense leg's (the aggregated-end compression claim, on the real
#        wire).
#
#   scripts/async_smoke.sh     # or: make async-smoke
#
# Artifacts land under /tmp/fedrec_async_smoke for inspection.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=${ASYNC_SMOKE_DIR:-/tmp/fedrec_async_smoke}
rm -rf "$OUT"
mkdir -p "$OUT"

APORT=$(python - <<'PY'
import socket
s = socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()
PY
)
CPORT=$(python - <<'PY'
import socket
s = socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()
PY
)

ROUNDS=3
STRAGGLE_MS=4000

# --------------------------------------------------- the commit authority
env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m fedrec_tpu.agg.server "127.0.0.1:$APORT" \
    --quorum 3 --world 4 \
    --obs-dir "$OUT/obs/worker_aggserver" \
    --state-dir "$OUT/aggstate" \
    > "$OUT/aggserver.log" 2>&1 &
AGG_PID=$!

# --------------------------- the live telemetry collector (fleet watch):
# --straggler-evals 2 because 3 rounds give worker 3 only 2 push gaps —
# both breach (4s sleep vs the trio's sub-second cadence), so the rule
# confirms and fires on the last push. JAX is never imported here.
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m fedrec_tpu.obs.fleet "127.0.0.1:$CPORT" \
    --dir "$OUT/collector" --watch --straggler-evals 2 \
    > "$OUT/collector.log" 2>&1 &
COLL_PID=$!
cleanup() { kill "$AGG_PID" "$COLL_PID" 2>/dev/null || true; }
trap cleanup EXIT
sleep 1

# ------------------------------------------------------- 4 async workers
run_worker() {
    local extra=()
    if [ "$1" = 3 ]; then
        # the scripted straggler: sleeps at the push boundary, so every
        # commit it could have gated fires without it
        extra=(--set chaos.enabled=true --set "chaos.straggle_ms=$STRAGGLE_MS")
    fi
    env -u XLA_FLAGS JAX_PLATFORMS=cpu \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python -m fedrec_tpu.cli.run "$ROUNDS" 8 10 \
        --agg-server "127.0.0.1:$APORT" --worker-id "$1" \
        --strategy param_avg --clients 1 \
        --synthetic --synthetic-train 256 --synthetic-news 64 \
        --set model.bert_hidden=48 --set data.max_his_len=10 \
        --set data.max_title_len=12 --set model.news_dim=32 \
        --set model.num_heads=4 --set model.head_dim=8 \
        --set model.query_dim=16 \
        --set "train.snapshot_dir=$OUT/d$1" \
        --set "train.eval_every=$ROUNDS" \
        --set optim.user_lr=0.001 --set optim.news_lr=0.001 \
        --set "obs.dir=$OUT/obs" \
        --set "obs.fleet.collector=127.0.0.1:$CPORT" \
        "${extra[@]}" \
        > "$OUT/worker_$1.log" 2>&1
}

PIDS=()
for wid in 0 1 2 3; do
    run_worker "$wid" & PIDS+=($!)
done
FAIL=0
for i in 0 1 2 3; do
    wait "${PIDS[$i]}" || { echo "[async-smoke] worker $i FAILED"; FAIL=1; }
done
if [ "$FAIL" -ne 0 ]; then
    echo "[async-smoke] logs:"
    tail -n 40 "$OUT"/worker_*.log "$OUT/aggserver.log"
    exit 1
fi

# ------------------------------------------- [1-3] commit-log assertions
env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    OUT="$OUT" APORT="$APORT" ROUNDS="$ROUNDS" STRAGGLE_MS="$STRAGGLE_MS" \
    python - <<'PY'
import json
import os

from fedrec_tpu.obs.fleet import request_json_line

out = os.environ["OUT"]
rounds = int(os.environ["ROUNDS"])
straggle_ms = float(os.environ["STRAGGLE_MS"])
st = request_json_line(
    "127.0.0.1", int(os.environ["APORT"]), {"cmd": "status"}, timeout_s=10.0
)
print("[async-smoke] aggserver status:", json.dumps(st))

# 1. quorum commit: one version per round from the on-time trio (the
# straggler's pushes can only ADD commits, never block one)
assert st["version"] >= rounds, st
assert {"0", "1", "2", "3"} <= set(st["workers"]), st
commits = st["commits"]
assert len(commits) == st["version"], commits
# every commit fired at exactly quorum (3 distinct pending) or more
assert all(c["quorum"] >= 3 for c in commits), commits

# 2. late fold: the straggler's delayed delta folded with staleness > 0
late = sum(c["late_folds"] for c in commits)
assert late >= 1, f"no late folds in {commits}"
assert sum(c["stale_drops"] for c in commits) == 0, \
    "a within-cap contribution was dropped"

# 3. gate -> ~0: worker 3 is charged (almost) nothing. The barrier
# would charge it ~straggle_ms EVERY round; async charges it only when
# it happens to close a quorum, a race window of one push (< half the
# straggle even then).
w3_gates = [c["gate_ms"] for c in commits if c["closer"] == "3"]
w3_total = sum(w3_gates)
assert w3_total < straggle_ms / 2, (
    f"straggler charged {w3_total:.0f} ms across {len(w3_gates)} commit(s)"
)
barrier_cost = straggle_ms * rounds
print(f"[async-smoke] straggler gate: {w3_total:.0f} ms async vs "
      f"~{barrier_cost:.0f} ms the barrier would have charged")

# bank the dense per-push wire bytes for the countsketch leg's comparison
pushes = st.get("push_counts") or {}
per_push = {
    w: st["push_bytes"][w] / max(pushes.get(w, 1), 1)
    for w in st.get("push_bytes", {})
}
assert per_push, f"server counted no push bytes: {st}"
with open(os.path.join(out, "push_bytes_dense.json"), "w") as f:
    json.dump(per_push, f)
PY

# straggler really straggled (the chaos knob engaged)
grep -q "straggling" "$OUT/worker_3.log" \
    || { echo "[async-smoke] worker 3 never straggled"; exit 1; }

# ---------------------------------------- [6] fleet watch at the collector:
# the persistent-straggler rule must have caught worker 3 from its push
# cadence alone and written a firing alert record to the fleet log
FLEET_LOG="$OUT/collector/worker_fleet/metrics.jsonl"
test -s "$FLEET_LOG" \
    || { echo "[async-smoke] collector wrote no fleet watch log"; \
         tail -20 "$OUT/collector.log"; exit 1; }
grep '"kind": "alert"' "$FLEET_LOG" | grep '"key": "fleet:straggler:3"' \
    | grep -q '"event": "firing"' \
    || { echo "[async-smoke] fleet rule never fired on the straggler"; \
         cat "$FLEET_LOG"; exit 1; }
# ...and stayed quiet about the on-time trio
if grep '"event": "firing"' "$FLEET_LOG" \
    | grep -qE '"key": "fleet:straggler:[012]"'; then
    echo "[async-smoke] fleet rule flagged an on-time worker"; exit 1
fi
echo "[async-smoke] fleet watch caught the straggler:"
grep '"key": "fleet:straggler:3"' "$FLEET_LOG" | head -1
kill -TERM "$COLL_PID" 2>/dev/null || true
wait "$COLL_PID" 2>/dev/null || true

# ------------------------------------------------ stop the service (flushes
# its obs artifacts + the buffer sidecar on the way down)
kill -TERM "$AGG_PID"
wait "$AGG_PID" 2>/dev/null || true

# ---------------------------------------------------- [5] buffer persisted
test -s "$OUT/aggstate/agg_buffer.npz" \
    || { echo "[async-smoke] no persisted buffer sidecar"; exit 1; }

# ------------------------------------------------------- [4] the fleet leg
env \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m fedrec_tpu.cli.obs fleet "$OUT/obs" > "$OUT/fleet_report.txt"
env \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m fedrec_tpu.cli.obs fleet "$OUT/obs" --json \
    > "$OUT/fleet_report.json"

env \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    OUT="$OUT" ROUNDS="$ROUNDS" STRAGGLE_MS="$STRAGGLE_MS" \
    python - <<'PY'
import json
import os
from pathlib import Path

out = Path(os.environ["OUT"])
rounds = int(os.environ["ROUNDS"])
straggle_ms = float(os.environ["STRAGGLE_MS"])

rep = json.loads((out / "fleet_report.json").read_text())
workers = set(rep["workers"])
assert {"0", "1", "2", "3", "aggserver"} <= workers, workers

agg = rep.get("agg") or {}
assert "aggserver" in agg, f"no agg section for the commit authority: {agg}"
srv = agg["aggserver"]
assert srv.get("role") == "agg_server", srv
assert srv.get("commits", 0) >= rounds, srv
assert srv.get("late_folds", 0) >= 1, srv
gates = srv.get("worker_gate_ms") or {}
assert "3" in gates, gates
assert gates["3"] < straggle_ms / 2, (
    f"fleet report charges the straggler {gates['3']:.0f} ms"
)
# the workers' own push accounting made it into the merge
pushed = [w for w, aw in agg.items() if aw.get("pushes", 0) >= rounds]
assert len(pushed) >= 4, f"workers with >= {rounds} pushes: {pushed}"

text = (out / "fleet_report.txt").read_text()
assert "## Aggregation" in text, "no Aggregation panel in the fleet text"
assert "gate_ms before" in text, "no before/after gate panel"
print("[async-smoke] fleet leg OK "
      f"(straggler gate {gates['3']:.0f} ms in the merged report)")
PY

# ------------------------------------------------- [4b] the wire leg:
# the merged trace carries cross-process flow arrows (a worker's push
# causally linked into the authority's commit, the commit linked into a
# worker's adoption) and the fleet report carries the Wire panel with
# the chaos-delayed worker's edge on it
env \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m fedrec_tpu.cli.obs fleet-trace "$OUT/obs" \
    -o "$OUT/fleet_trace.json"

env \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    OUT="$OUT" \
    python - <<'PY'
import json
import os
from collections import defaultdict
from pathlib import Path

out = Path(os.environ["OUT"])
doc = json.loads((out / "fleet_trace.json").read_text())
events = doc["traceEvents"]
pid_of = doc["otherData"]["workers"]          # wid -> pid
agg_pid = pid_of["aggserver"]
worker_pids = {p for w, p in pid_of.items() if w != "aggserver"}

# cross-process flow arrows survived the merge
flows = [e for e in events if e.get("cat") == "wire"]
assert flows, "no wire flow events in the merged trace"
by_id = defaultdict(list)
for e in flows:
    by_id[e["id"]].append(e)
cross = {i for i, evs in by_id.items() if len({e["pid"] for e in evs}) >= 2}
assert cross, "no flow id crosses two process tracks"

# a worker push linked INTO the authority (start on a worker pid,
# finish on the agg pid), and a commit linked OUT to an adopting worker
push_arrows = [
    i for i, evs in by_id.items()
    if any(e["ph"] == "s" and e["pid"] in worker_pids for e in evs)
    and any(e["ph"] == "f" and e["pid"] == agg_pid for e in evs)
]
adopt_arrows = [
    i for i, evs in by_id.items()
    if any(e["ph"] == "s" and e["pid"] == agg_pid for e in evs)
    and any(e["ph"] == "f" and e["pid"] in worker_pids for e in evs)
]
assert push_arrows, "no flow arrow from a worker push into the authority"
assert adopt_arrows, "no flow arrow from the authority out to an adoption"
commits = [e for e in events
           if e.get("name") == "agg.commit" and e.get("pid") == agg_pid]
adopts = [e for e in events if e.get("name") == "agg.adopt"]
assert commits, "no agg.commit spans on the authority's track"
assert adopts, "no agg.adopt spans on any worker track"

# the Wire panel made it into the fleet report, straggler edge included
rep = json.loads((out / "fleet_report.json").read_text())
wire = rep.get("wire") or {}
edges = wire.get("edges") or {}
w3 = edges.get("3") or []
assert any(e.get("peer") == "aggserver" and e.get("op") == "push"
           for e in w3), f"no worker-3 push edge in the Wire panel: {edges}"
assert wire.get("offsets_ms"), "no per-edge clock offsets in the report"
decomp = wire.get("commit_decomposition") or {}
assert decomp.get("queue_ms") is not None, decomp
assert decomp.get("edges"), decomp

text = (out / "fleet_report.txt").read_text()
assert "## Wire" in text, "no Wire panel in the fleet text"
assert "slowest edge" in text, "no slowest-edge callout"
print(f"[async-smoke] wire leg OK ({len(cross)} cross-process flow "
      f"arrow(s), {len(push_arrows)} push->commit, "
      f"{len(adopt_arrows)} commit->adopt)")
PY

# -------------------------------------------- [7] the countsketch leg:
# a fresh 2-worker cluster pushing sketch-coded deltas — commits advance
# and the wire bytes shrink ~1/sketch_width vs the dense leg
SPORT=$(python - <<'PY'
import socket
s = socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()
PY
)
env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m fedrec_tpu.agg.server "127.0.0.1:$SPORT" \
    --quorum 2 --world 2 --sketch-seed 0 \
    --obs-dir "$OUT/obs_sk/worker_aggserver" \
    --state-dir "$OUT/aggstate_sk" \
    > "$OUT/aggserver_sk.log" 2>&1 &
SK_PID=$!
cleanup() { kill "$AGG_PID" "$COLL_PID" "$SK_PID" 2>/dev/null || true; }
sleep 1

run_sketch_worker() {
    env -u XLA_FLAGS JAX_PLATFORMS=cpu \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python -m fedrec_tpu.cli.run "$ROUNDS" 8 10 \
        --agg-server "127.0.0.1:$SPORT" --worker-id "$1" \
        --strategy param_avg --clients 1 \
        --synthetic --synthetic-train 256 --synthetic-news 64 \
        --set model.bert_hidden=48 --set data.max_his_len=10 \
        --set data.max_title_len=12 --set model.news_dim=32 \
        --set model.num_heads=4 --set model.head_dim=8 \
        --set model.query_dim=16 \
        --set fed.dcn_compress=countsketch \
        --set fed.dcn_sketch_width=0.1 --set fed.dcn_sketch_seed=0 \
        --set "train.snapshot_dir=$OUT/sk$1" \
        --set "train.eval_every=$ROUNDS" \
        --set optim.user_lr=0.001 --set optim.news_lr=0.001 \
        --set "obs.dir=$OUT/obs_sk" \
        > "$OUT/worker_sk_$1.log" 2>&1
}

SK_PIDS=()
for wid in 0 1; do
    run_sketch_worker "$wid" & SK_PIDS+=($!)
done
SK_FAIL=0
for i in 0 1; do
    wait "${SK_PIDS[$i]}" || { echo "[async-smoke] sketch worker $i FAILED"; SK_FAIL=1; }
done
if [ "$SK_FAIL" -ne 0 ]; then
    echo "[async-smoke] sketch leg logs:"
    tail -n 40 "$OUT"/worker_sk_*.log "$OUT/aggserver_sk.log"
    exit 1
fi

env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    OUT="$OUT" SPORT="$SPORT" ROUNDS="$ROUNDS" \
    python - <<'PY'
import json
import os

from fedrec_tpu.obs.fleet import request_json_line

out = os.environ["OUT"]
rounds = int(os.environ["ROUNDS"])
st = request_json_line(
    "127.0.0.1", int(os.environ["SPORT"]), {"cmd": "status"}, timeout_s=10.0
)
print("[async-smoke] sketch aggserver status:", json.dumps(st))

# sketch-coded pushes still commit: one version per round at quorum 2
assert st["version"] >= rounds, st
assert all(c["quorum"] >= 2 for c in st["commits"]), st["commits"]

# the wire shrank: per-push bytes well under the dense leg's. Width 0.1
# prices ~10x on big towers; the smoke model's many tiny leaves round
# m = max(1, round(width*n)) up and pay npz framing per leaf, so ~4-5x
# is the honest figure here — 4x is the floor only a broken encoder
# misses (base64 framing is identical on both legs).
dense = json.load(open(os.path.join(out, "push_bytes_dense.json")))
dense_per = sum(dense.values()) / len(dense)
counts = st["push_counts"]
sk_per = sum(st["push_bytes"][w] / max(counts.get(w, 1), 1)
             for w in st["push_bytes"]) / len(st["push_bytes"])
assert sk_per * 4 < dense_per, (
    f"countsketch pushes {sk_per:.0f} B/push vs dense {dense_per:.0f} "
    "B/push — expected ~10x smaller"
)
print(f"[async-smoke] countsketch uplink {sk_per:.0f} B/push vs dense "
      f"{dense_per:.0f} B/push ({dense_per / sk_per:.1f}x smaller)")
PY

kill -TERM "$SK_PID"
wait "$SK_PID" 2>/dev/null || true

# ------------------------------------------- [8] the fault-injection leg:
# a fresh 2-worker cluster where the WIRE itself misbehaves — worker 0
# dials the authority through an in-process chaos proxy that drops 30%
# of its connections and tears two mid-run windows mid-message; worker 1's
# proxy DUPLICATES every push (the lost-ack re-delivery case) — and the
# authority is SIGTERM-killed mid-run for a 10 s outage, then respawned
# from its state sidecars on the same port. Must prove: both workers
# still exit 0 (parked pushes, stale progress, re-hello on the
# incarnation bump), the respawn resumes the committed global, the
# commit version keeps advancing past the pre-kill version (no lost
# commit), and every duplicated delivery is detected by the push ledger
# instead of double-folded.
FPORT=$(python - <<'PY'
import socket
s = socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()
PY
)
ROUNDS_F=12
spawn_fault_authority() {
    env JAX_PLATFORMS=cpu \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python -m fedrec_tpu.agg.server "127.0.0.1:$FPORT" \
        --quorum 2 --world 2 \
        --obs-dir "$OUT/obs_fault/worker_aggserver" \
        --state-dir "$OUT/aggstate_fault" \
        >> "$OUT/aggserver_fault.log" 2>&1 &
    FAULT_PID=$!
}
spawn_fault_authority
cleanup() { kill "$AGG_PID" "$COLL_PID" "$SK_PID" "$FAULT_PID" 2>/dev/null || true; }
sleep 1

run_fault_worker() {
    local faults="$2" seed="$3"
    env -u XLA_FLAGS JAX_PLATFORMS=cpu \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python -m fedrec_tpu.cli.run "$ROUNDS_F" 8 10 \
        --agg-server "127.0.0.1:$FPORT" --worker-id "$1" \
        --strategy param_avg --clients 1 \
        --synthetic --synthetic-train 256 --synthetic-news 64 \
        --set model.bert_hidden=48 --set data.max_his_len=10 \
        --set data.max_title_len=12 --set model.news_dim=32 \
        --set model.num_heads=4 --set model.head_dim=8 \
        --set model.query_dim=16 \
        --set "train.snapshot_dir=$OUT/f$1" \
        --set "train.eval_every=$ROUNDS_F" \
        --set optim.user_lr=0.001 --set optim.news_lr=0.001 \
        --set "obs.dir=$OUT/obs_fault" \
        --set chaos.enabled=true --set chaos.straggle_ms=1200 \
        --set "chaos.wire_faults=$faults" --set "chaos.wire_seed=$seed" \
        --set agg.worker_timeout_s=6 --set agg.worker_global_wait_s=6 \
        --set agg.worker_rpc_attempts=6 \
        > "$OUT/worker_f$1.log" 2>&1
}

F_PIDS=()
run_fault_worker 0 'drop@*:0.3,tear@10-14,tear@20-24' 1 & F_PIDS+=($!)
run_fault_worker 1 'dup@*' 2 & F_PIDS+=($!)

# wait for the first commits, then SIGTERM the authority mid-run
V_KILL=$(env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    FPORT="$FPORT" python - <<'PY'
import os
import time

from fedrec_tpu.obs.fleet import request_json_line

deadline = time.monotonic() + 120
v = -1
while time.monotonic() < deadline:
    try:
        st = request_json_line(
            "127.0.0.1", int(os.environ["FPORT"]), {"cmd": "status"},
            timeout_s=5.0,
        )
        v = int(st["version"])
        if v >= 2:
            break
    except (OSError, ValueError):
        pass
    time.sleep(0.3)
print(v)
PY
)
[ "$V_KILL" -ge 2 ] \
    || { echo "[async-smoke] fault leg never reached v2 before the kill"; \
         tail -n 40 "$OUT"/worker_f*.log "$OUT/aggserver_fault.log"; exit 1; }
kill -TERM "$FAULT_PID"
wait "$FAULT_PID" 2>/dev/null || true
echo "[async-smoke] fault leg: authority killed at v$V_KILL, 10 s outage"
sleep 10
spawn_fault_authority
grep -q "resumed committed global" "$OUT/aggserver_fault.log" || sleep 2

F_FAIL=0
for i in 0 1; do
    wait "${F_PIDS[$i]}" || { echo "[async-smoke] fault worker $i FAILED"; F_FAIL=1; }
done
if [ "$F_FAIL" -ne 0 ]; then
    echo "[async-smoke] fault leg logs:"
    tail -n 40 "$OUT"/worker_f*.log "$OUT/aggserver_fault.log"
    exit 1
fi

# the respawn resumed the persisted committed global (not a cold init)
grep -q "resumed committed global" "$OUT/aggserver_fault.log" \
    || { echo "[async-smoke] respawned authority never resumed the sidecar"; \
         cat "$OUT/aggserver_fault.log"; exit 1; }

env JAX_PLATFORMS=cpu \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    OUT="$OUT" FPORT="$FPORT" V_KILL="$V_KILL" \
    python - <<'PY'
import json
import os

from fedrec_tpu.obs.fleet import request_json_line

v_kill = int(os.environ["V_KILL"])
st = request_json_line(
    "127.0.0.1", int(os.environ["FPORT"]), {"cmd": "status"}, timeout_s=10.0
)
print("[async-smoke] fault aggserver status:", json.dumps(st)[:400])

# no lost commit: the restored authority advertises incarnation 2 and the
# version kept advancing PAST the pre-kill version once the workers'
# parked pushes drained
assert st["incarnation"] == 2, st["incarnation"]
assert st["version"] > v_kill, (
    f"version stuck at v{st['version']} after restart at v{v_kill}"
)
assert all(c["quorum"] >= 2 for c in st["commits"]), st["commits"]

# no double-fold: worker 1's edge duplicated every push in flight — the
# ledger must have answered `duplicate` for the re-deliveries instead of
# folding them twice
assert st["push_dups"] >= 1, (
    f"dup@* edge produced no detected duplicates: {st['push_dups']}"
)
print(f"[async-smoke] fault leg OK (v{v_kill} -> v{st['version']} across "
      f"the outage, {st['push_dups']} duplicate push(es) detected, "
      "0 double-folded)")
PY

kill -TERM "$FAULT_PID"
wait "$FAULT_PID" 2>/dev/null || true

echo "[async-smoke] OK"
