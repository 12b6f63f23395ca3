#!/bin/bash
# Observability smoke (ISSUE-3 + ISSUE-4 acceptance scenarios), CPU-only:
#
#   1. a 2-round synthetic training run with obs.dir set (+ DP so the
#      epsilon gauge is live, + prefetch so queue health is live),
#   2. a short serve_load run with --obs-dir,
#   3. assert each produced the artifact trio — registry-snapshot JSONL,
#      a valid Perfetto/Chrome trace with >= 4 distinct span names, a
#      Prometheus exposition carrying serve p50/p99 + prefetch queue
#      depth + privacy.epsilon_spent — and that fedrec-obs renders both
#      into run reports,
#   4. a forced-NaN micro-run (inf lr for step 1): the numeric sentry
#      must abort the run, the flight recorder must dump the offending
#      batch + state + manifest + registry snapshot under
#      obs.dir/flightrec/, and `fedrec-obs replay` must reproduce the
#      non-finite step on CPU (exit 0 = REPRODUCED),
#   5. the model-quality smoke (scripts/quality_smoke.sh): sliced-eval
#      gauges + Quality report section, the store drift-probe leg, and
#      the forced quality-gate regression failure,
#   6. the perf leg: the training run of (1) carries obs.perf.enabled +
#      a capture window on round 1 — assert the Perf report section,
#      `fedrec-obs perf` exit 0, the capture-window trace landing inside
#      obs.dir with its metrics.jsonl pointer record, then the
#      perf-regression gate: bank a fresh baseline, pass a clean check,
#      and prove --demo-regression fails naming the lane,
#   7. the watch leg (scripts/watch_smoke.sh): a forced SLO breach must
#      fire and resolve, an unmeetable SLO must keep `fedrec-obs alerts`
#      / `tail --once` at exit 1, and the disabled path must leave zero
#      watch footprint.
#
#   scripts/obs_smoke.sh     # or: make obs-smoke
#
# Artifacts land under /tmp/fedrec_obs_smoke for inspection.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=${OBS_SMOKE_DIR:-/tmp/fedrec_obs_smoke}
rm -rf "$OUT"
mkdir -p "$OUT"

run() {
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" "$@"
}

echo "== [1/7] 2-round CPU training run (DP + prefetch) =="
run python -m fedrec_tpu.cli.run 2 16 2 --strategy param_avg --clients 8 \
    --synthetic --synthetic-train 512 --synthetic-news 128 \
    --mode joint --dp-epsilon 10 \
    --obs-dir "$OUT/train" \
    --set obs.perf.enabled=1 --set obs.perf.capture_rounds=1 \
    --set data.prefetch_batches=2 \
    --set model.news_dim=32 --set model.num_heads=4 --set model.head_dim=8 \
    --set model.query_dim=16 --set model.bert_hidden=48 \
    --set data.max_his_len=10 --set data.max_title_len=12 \
    --set train.snapshot_dir="$OUT/train_snap" --set train.eval_every=1 \
    --set train.eval_protocol=sampled > "$OUT/train.log" 2>&1 \
    || { tail -30 "$OUT/train.log"; exit 1; }

echo "== [2/7] serve_load run =="
run python benchmarks/serve_load.py --num-news 2000 --his-len 10 \
    --clients 4 --rate 50 --duration 2 --out obs_smoke_serve_load.json \
    --obs-dir "$OUT/serve" > "$OUT/serve.log" 2>&1 \
    || { tail -30 "$OUT/serve.log"; exit 1; }
rm -f benchmarks/obs_smoke_serve_load.json

echo "== [3/7] artifact assertions =="
for d in train serve; do
    for f in metrics.jsonl trace.json prometheus.txt; do
        [ -s "$OUT/$d/$f" ] || { echo "MISSING $OUT/$d/$f"; exit 1; }
    done
done

python - "$OUT" <<'EOF'
import json, sys
out = sys.argv[1]

for run in ("train", "serve"):
    doc = json.load(open(f"{out}/{run}/trace.json"))
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert len(names) >= 4, f"{run}: want >=4 span names, got {sorted(names)}"
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts), f"{run}: trace ts not monotonic"
    snaps = [json.loads(l) for l in open(f"{out}/{run}/metrics.jsonl")
             if '"registry_snapshot"' in l]
    assert snaps, f"{run}: no registry snapshot in metrics.jsonl"
    print(f"  {run}: {len(evs)} events, span names ok: {sorted(names)[:6]}...")

train_prom = open(f"{out}/train/prometheus.txt").read()
serve_prom = open(f"{out}/serve/prometheus.txt").read()
for needle, hay, which in (
    ("privacy.epsilon_spent", train_prom, "train"),
    ("data_prefetch_queue_depth", train_prom, "train"),
    ("serve_p50_ms", serve_prom, "serve"),
    ("serve_p99_ms", serve_prom, "serve"),
    ("serve_queue_depth", serve_prom, "serve"),
):
    assert needle in hay, f"{which} prometheus.txt missing {needle}"
print("  prometheus expositions carry p50/p99, queue depth, epsilon_spent")
EOF

echo "== run reports =="
python -m fedrec_tpu.cli.obs report "$OUT/train"
python -m fedrec_tpu.cli.obs report "$OUT/serve"

echo "== fleet leg (single-worker degenerate) =="
# fedrec-obs fleet/fleet-trace must degrade gracefully to one obs dir:
# every round attributed to worker 0, the merged trace valid Perfetto
python -m fedrec_tpu.cli.obs fleet "$OUT/train" --json > "$OUT/fleet.json"
python -m fedrec_tpu.cli.obs fleet-trace "$OUT/train" \
    -o "$OUT/fleet_trace.json" > /dev/null
python - "$OUT" <<'EOF'
import json, sys
out = sys.argv[1]
rep = json.load(open(f"{out}/fleet.json"))
assert set(rep["workers"]) == {"0"}, rep["workers"]
assert len(rep["rounds"]) == 2, rep.get("rounds")
assert all(r["critical_worker"] == "0" and r["gate_ms"] == 0.0
           for r in rep["rounds"]), rep["rounds"]
doc = json.load(open(f"{out}/fleet_trace.json"))
evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
ts = [e["ts"] for e in evs]
assert ts == sorted(ts), "merged trace ts not monotonic"
assert any(e["name"] == "fed_round" and e["args"].get("worker") == "0"
           for e in evs), "fed_round spans lost their worker label"
print("  fleet: 2 rounds attributed to worker 0, merged trace valid")
EOF

echo "== [4/7] forced-NaN flight-recorder round-trip =="
# inf lr: the first optimizer update goes non-finite, the sentry trips,
# the run must ABORT (nonzero exit) after dumping forensics
if run python -m fedrec_tpu.cli.run 2 16 1000 --strategy param_avg --clients 8 \
    --synthetic --synthetic-train 256 --synthetic-news 64 --mode joint \
    --obs-dir "$OUT/nan" \
    --set optim.user_lr=inf \
    --set model.news_dim=32 --set model.num_heads=4 --set model.head_dim=8 \
    --set model.query_dim=16 --set model.bert_hidden=48 \
    --set data.max_his_len=10 --set data.max_title_len=12 \
    --set train.snapshot_dir="$OUT/nan_snap" --set train.eval_every=1000 \
    > "$OUT/nan.log" 2>&1; then
    echo "forced-NaN run exited 0 — the sentry did not abort"; exit 1
fi
grep -q "training-health trigger \[nonfinite\]" "$OUT/nan.log" \
    || { echo "no nonfinite trigger in nan.log"; tail -20 "$OUT/nan.log"; exit 1; }
for f in manifest.json state.msgpack registry.json table.npy batch_000.npz; do
    [ -s "$OUT/nan/flightrec/$f" ] || { echo "MISSING flightrec/$f"; exit 1; }
done
# the dump must replay deterministically on CPU and reproduce the flag
run python -m fedrec_tpu.cli.obs replay "$OUT/nan" > "$OUT/replay.log" 2>&1 \
    || { echo "replay did not reproduce the non-finite step"; \
         tail -20 "$OUT/replay.log"; exit 1; }
grep -q "REPRODUCED" "$OUT/replay.log" \
    || { echo "replay verdict missing"; tail -5 "$OUT/replay.log"; exit 1; }
echo "  forced-NaN: abort + complete flightrec dump + replay REPRODUCED"

echo "== [5/7] model-quality smoke (scripts/quality_smoke.sh) =="
QUALITY_SMOKE_DIR="$OUT/quality" bash scripts/quality_smoke.sh

echo "== [6/7] perf telemetry + perf-regression gate =="
# the training run of leg 1 carried obs.perf.enabled + capture_rounds=1:
# the report must render a Perf section, the perf verb must exit 0, and
# the capture window's jax.profiler trace must have landed in obs.dir
# with a pointer record in metrics.jsonl
# (report to a file, then grep: `| grep -q` would close the pipe early
# and kill the renderer with SIGPIPE under pipefail)
python -m fedrec_tpu.cli.obs report "$OUT/train" > "$OUT/report_perf.txt"
grep -q "^## Perf" "$OUT/report_perf.txt" \
    || { echo "no Perf section in the run report"; exit 1; }
run python -m fedrec_tpu.cli.obs perf "$OUT/train" > "$OUT/perf.log" \
    || { echo "fedrec-obs perf failed"; tail -20 "$OUT/perf.log"; exit 1; }
grep -q "Roofline verdicts" "$OUT/perf.log" \
    || { echo "perf verb missing the roofline table"; exit 1; }
ls -d "$OUT"/train/perf_capture_r* > /dev/null 2>&1 \
    || { echo "no capture-window trace under $OUT/train"; exit 1; }
grep -q '"kind": "perf_capture"' "$OUT/train/metrics.jsonl" \
    || { echo "no perf_capture pointer record in metrics.jsonl"; exit 1; }
echo "  perf: report section + verb + capture window + pointer record ok"

# the gate: bank a fresh seeded baseline, pass a clean re-check, then
# prove the forced-regression mode exits nonzero NAMING the lane
run python benchmarks/perf_gate.py --bank --out "$OUT/perf_gate.json" \
    > "$OUT/perf_gate.log" 2>&1 \
    || { tail -20 "$OUT/perf_gate.log"; exit 1; }
run python benchmarks/perf_gate.py --check --out "$OUT/perf_gate.json" \
    >> "$OUT/perf_gate.log" 2>&1 \
    || { echo "clean perf-gate check failed"; tail -20 "$OUT/perf_gate.log"; exit 1; }
if run python benchmarks/perf_gate.py --check --out "$OUT/perf_gate.json" \
    --demo-regression steps_per_sec >> "$OUT/perf_gate.log" 2>&1; then
    echo "forced perf regression did NOT fail the gate"; exit 1
fi
grep -q "REGRESSION lane steps_per_sec" "$OUT/perf_gate.log" \
    || { echo "gate failure did not name the lane"; tail -5 "$OUT/perf_gate.log"; exit 1; }
echo "  perf gate: banked + clean pass + forced regression names the lane"

echo "== [7/7] continuous-watch smoke (scripts/watch_smoke.sh) =="
WATCH_SMOKE_DIR="$OUT/watch" bash scripts/watch_smoke.sh
echo "OBS_SMOKE=PASS"
