#!/bin/bash
# Elastic-federation smoke (ISSUE-12 acceptance), CPU-only:
#
#   A 4-process gloo world under elastic membership
#   (fedrec_tpu.parallel.membership) loses one peer to a chaos kill
#   mid-run and must
#
#     1. SHRINK-AND-CONTINUE: the survivors re-form as membership epoch 1
#        at world 3 and keep federating (NOT 4 standalone forks — the
#        pre-elastic failure mode);
#     2. REJOIN: the killed peer's supervisor respawns it (held off by
#        chaos.rejoin_delay_s so the shrink is observable first); its
#        join knocks on the healthy epoch, the server broadcasts the
#        reformation at a round boundary, and epoch 2 re-forms at
#        world 4;
#     3. FINISH: the full-complement world completes every round and the
#        final evaluation runs;
#     4. ACCOUNT: the membership service's counters match the script —
#        exactly one shrink, exactly one rejoin, epoch history
#        world 4 -> 3 -> 4;
#     5. FLEET (ISSUE-13 acceptance): the per-worker obs artifacts +
#        round-cadence telemetry pushes (collector riding the membership
#        port) merge into ONE Perfetto trace whose per-worker tracks show
#        the kill -> shrink -> rejoin sequence as membership instants,
#        and `fedrec-obs fleet` names a critical-path worker for every
#        round — from the offline worker_* merge AND the collector dir.
#
#   scripts/elastic_smoke.sh     # or: make elastic-smoke
#
# Artifacts land under /tmp/fedrec_elastic_smoke for inspection.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=${ELASTIC_SMOKE_DIR:-/tmp/fedrec_elastic_smoke}
rm -rf "$OUT"
mkdir -p "$OUT"

MPORT=$(python - <<'PY'
import socket
s = socket.socket(); s.bind(("127.0.0.1", 0)); print(s.getsockname()[1]); s.close()
PY
)

ROUNDS=10

# ------------------------------------------------ the membership service
env \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    python -m fedrec_tpu.parallel.membership "127.0.0.1:$MPORT" \
    --target-world 4 \
    --obs-dir "$OUT/obs/worker_membership" \
    --telemetry-dir "$OUT/pushed" \
    > "$OUT/membership.log" 2>&1 &
MEM_PID=$!
cleanup() { kill "$MEM_PID" 2>/dev/null || true; }
trap cleanup EXIT
sleep 1

# --------------------------------------------------- 4 supervised workers
run_worker() {
    env -u XLA_FLAGS JAX_PLATFORMS=cpu \
        FEDREC_SUPERVISE_MAX=12 \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python -m fedrec_tpu.cli.coordinator "$ROUNDS" 8 1 \
        --supervise \
        --membership "127.0.0.1:$MPORT" \
        --num-processes 4 --process-id "$1" \
        --synthetic --synthetic-train 960 --synthetic-news 64 \
        --clients 1 --server-trains \
        --collective-timeout 15 \
        --set model.bert_hidden=48 --set data.max_his_len=10 \
        --set data.max_title_len=12 --set model.news_dim=32 \
        --set model.num_heads=4 --set model.head_dim=8 \
        --set model.query_dim=16 \
        --set "train.snapshot_dir=$OUT/d$1" \
        --set "train.eval_every=$ROUNDS" \
        --set fed.weight_by_samples=true \
        --set optim.user_lr=0.001 --set optim.news_lr=0.001 \
        --set chaos.enabled=true \
        --set chaos.kill_round=2 --set chaos.kill_process=2 \
        --set chaos.rejoin_delay_s=15 \
        --set fed.elastic.lease_ms=5000 \
        --set fed.elastic.heartbeat_ms=1000 \
        --set fed.elastic.formation_grace_ms=6000 \
        --set "obs.dir=$OUT/obs" \
        --set "obs.fleet.collector=127.0.0.1:$MPORT" \
        > "$OUT/worker_$1.log" 2>&1
}

PIDS=()
for pid in 0 1 2 3; do
    run_worker "$pid" & PIDS+=($!)
done
FAIL=0
for i in 0 1 2 3; do
    wait "${PIDS[$i]}" || { echo "[elastic-smoke] worker $i FAILED"; FAIL=1; }
done
if [ "$FAIL" -ne 0 ]; then
    echo "[elastic-smoke] worker logs:"
    tail -n 40 "$OUT"/worker_*.log
    exit 1
fi

# --------------------------------------------------------- the assertions
env \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    OUT="$OUT" MPORT="$MPORT" ROUNDS="$ROUNDS" \
    python - <<'PY'
import json
import os
from pathlib import Path

from fedrec_tpu.parallel.membership import MembershipClient

out = Path(os.environ["OUT"])
rounds = int(os.environ["ROUNDS"])
st = MembershipClient(
    f"127.0.0.1:{os.environ['MPORT']}", worker_id="_smoke"
).status()
print("[elastic-smoke] membership status:", json.dumps(st))
hist = [h["world"] for h in st["epoch_history"]]

# 1. the initial epoch formed at the full complement
assert hist and hist[0] == 4, hist
# 2. shrink-and-continue: exactly one shrink, to world 3
assert st["shrinks"] == 1, st
assert 3 in hist, hist
# 3. rejoin: exactly one, and the world grew back to 4
assert st["rejoins"] == 1, st
assert hist[-1] == 4, hist
assert hist == [4, 3, 4], hist
# the dead peer's lease expired exactly once
assert st["lease_misses"] >= 1, st

w2 = (out / "worker_2.log").read_text()
assert "dying at round 2" in w2, "the chaos kill never fired"
assert w2.count("dying at round 2") == 1, "marker guard failed"
assert "holding off its rejoin" in w2, "chaos.rejoin_delay_s never applied"

# shrink-and-continue really federated (epoch 1 ran at world 3): some
# worker joined a rank/3 seat
joined3 = any(
    "/3 (coordinator" in (out / f"worker_{i}.log").read_text()
    for i in range(4)
)
assert joined3, "no worker ever joined a world-3 epoch"

# the reformation barrier fired (workers left for reform, not crash)
reforms = sum(
    (out / f"worker_{i}.log").read_text().count("for reformation")
    for i in range(4)
)
assert reforms >= 3, f"expected a world-wide reformation, saw {reforms}"

# 4. the run FINISHED at the full world: the server trained the final
# round and the final evaluation ran
w0 = (out / "worker_0.log").read_text()
final_rounds = set()
evaled = False
for line in w0.splitlines():
    if '"training_loss"' in line:
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        final_rounds.add(int(rec["round"]))
        if (rec.get("auc") is not None or rec.get("val_auc") is not None
                or rec.get("valid_auc") is not None):
            evaled = True
assert (rounds - 1) in final_rounds, sorted(final_rounds)
assert evaled, "the final evaluation never ran"
print("[elastic-smoke] counters + logs match the script")
PY

# ------------------------------------------------------- [5] the fleet leg
echo "[elastic-smoke] fleet leg: merged trace + critical-path report"
obs_cli() {
    env \
        PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
        python -m fedrec_tpu.cli.obs "$@"
}
obs_cli fleet "$OUT/obs" > "$OUT/fleet_report.txt"
obs_cli fleet "$OUT/obs" --json > "$OUT/fleet_report.json"
obs_cli fleet-trace "$OUT/obs" -o "$OUT/fleet_trace.json"
obs_cli fleet "$OUT/pushed" --json > "$OUT/fleet_pushed.json"

env \
    PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" \
    OUT="$OUT" ROUNDS="$ROUNDS" \
    python - <<'PY'
import json
import os
from pathlib import Path

out = Path(os.environ["OUT"])
rounds = int(os.environ["ROUNDS"])

# -- the offline worker_* merge: every worker + the service discovered
rep = json.loads((out / "fleet_report.json").read_text())
workers = set(rep["workers"])
assert {"0", "1", "2", "3", "membership"} <= workers, workers
assert rep["workers"]["membership"]["role"] == "membership_service"

# -- membership timeline: kill -> shrink -> rejoin reads off the report
hist = [h["world"] for h in rep["membership"]["epoch_history"]]
assert hist == [4, 3, 4], hist
assert rep["membership"]["shrinks"] == 1, rep["membership"]
assert rep["membership"]["rejoins"] == 1, rep["membership"]

# -- a named critical-path worker for EVERY round (the acceptance bar)
by_round = {r["round"]: r for r in rep["rounds"]}
for r in range(rounds):
    assert r in by_round, f"round {r} missing from the fleet report"
    row = by_round[r]
    assert row["critical_worker"] in {"0", "1", "2", "3"}, row
    assert row["round_ms"] > 0, row
assert rep["critical_path"], "no times-on-critical-path totals"

# -- the merged trace: one doc, >= 5 tracks, kill/shrink/rejoin instants
doc = json.loads((out / "fleet_trace.json").read_text())
assert len(doc["otherData"]["workers"]) >= 5, doc["otherData"]["workers"]
evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
ts = [e["ts"] for e in evs]
assert ts == sorted(ts), "merged trace ts not monotonic"
names = [e["name"] for e in evs]
formed = [e for e in evs if e["name"] == "membership_epoch_formed"]
assert [f["args"]["world"] for f in formed] == [4, 3, 4], formed
expired = [e for e in evs if e["name"] == "membership_lease_expired"]
assert any(e["args"]["worker"] == "2" for e in expired), \
    "the chaos-killed worker's lease expiry is not in the merged trace"
assert "membership_worker_join" in names
assert "fed_round" in names
# per-worker tracks really carry the correlation keys
fr = [e for e in evs if e["name"] == "fed_round"]
assert {e["args"].get("worker") for e in fr} >= {"0", "1", "3"}, \
    "fed_round spans lost their worker labels"

# -- the collector got round-cadence pushes and renders the same story
pushed = json.loads((out / "fleet_pushed.json").read_text())
assert {"0", "1", "2", "3"} <= set(pushed["workers"]), pushed["workers"]
assert pushed["rounds"], "no rounds in the collector-side report"
# the killed worker's pre-kill rounds survived ONLY via pushes: its
# epoch-0 spans must be present in the collector merge
w2_rounds = {r["round"] for r in pushed["rounds"] if "2" in r["workers"]}
assert 0 in w2_rounds or 1 in w2_rounds, \
    "worker 2's pre-kill rounds never reached the collector"

# -- counter continuity: a respawned worker's totals resumed (monotone)
from fedrec_tpu.obs.report import load_jsonl, snapshot_value
_, snaps = load_jsonl(out / "obs" / "worker_2" / "metrics.jsonl")
totals = [
    v for s in snaps
    if (v := snapshot_value(s, "train.rounds_total")) is not None
]
assert totals == sorted(totals), f"worker 2 totals not monotone: {totals}"
assert totals and totals[-1] >= rounds - 2, totals

print("[elastic-smoke] fleet leg OK "
      f"({len(rep['rounds'])} rounds attributed, "
      f"{len(workers)} workers merged)")
PY

echo "[elastic-smoke] OK"
