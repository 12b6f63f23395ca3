#!/bin/bash
# Continuous-watch smoke (ISSUE-19 acceptance scenario), CPU-only:
#
#   1. FIRE -> RESOLVE: a 3-round synthetic run with an SLO the round-0
#      JIT compile breaches (train.round_seconds:p95<2.5 — compile costs
#      seconds, steady-state rounds are sub-second) and 1-evaluation
#      windows/confirmation. The alert must FIRE naming the SLO, the
#      metric and the worker, then RESOLVE once compiled rounds pass;
#      `fedrec-obs alerts` renders both transitions and exits 0, the run
#      report carries the Alerts panel, the prometheus exposition the
#      alert.* instruments.
#   2. STAYS FIRING: the same run against an unmeetable SLO (<1e-9) —
#      the alert never resolves; `fedrec-obs alerts` and
#      `fedrec-obs tail --once` must exit 1 (the CI-able contract).
#   3. DISABLED PATH: obs.slo left at its default (false) — no
#      {"kind":"alert"} record, no alert_* instrument in the exposition.
#
#   scripts/watch_smoke.sh     # or: make watch-smoke
#
# Artifacts land under /tmp/fedrec_watch_smoke for inspection.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=${WATCH_SMOKE_DIR:-/tmp/fedrec_watch_smoke}
rm -rf "$OUT"
mkdir -p "$OUT"

run() {
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" "$@"
}

TINY=(--set model.news_dim=32 --set model.num_heads=4 --set model.head_dim=8
      --set model.query_dim=16 --set model.bert_hidden=48
      --set data.max_his_len=10 --set data.max_title_len=12)

echo "== [1/3] forced breach: fire on the compile round, resolve after =="
run python -m fedrec_tpu.cli.run 3 16 3 --strategy param_avg --clients 8 \
    --synthetic --synthetic-train 256 --synthetic-news 128 --mode joint \
    --obs-dir "$OUT/obs" "${TINY[@]}" \
    --set train.snapshot_dir="$OUT/snap" \
    --set obs.slo.enabled=true \
    --set "obs.slo.objectives=round_time:train.round_seconds:p95<2.5" \
    --set obs.slo.fast_window=1 --set obs.slo.slow_window=2 \
    --set obs.watch.pending_for=1 --set obs.watch.resolve_after=1 \
    > "$OUT/train.log" 2>&1 || { tail -30 "$OUT/train.log"; exit 1; }

python - "$OUT" <<'EOF'
import json, sys
out = sys.argv[1]
recs = [json.loads(l) for l in open(f"{out}/obs/metrics.jsonl")]
alerts = [r for r in recs if r.get("kind") == "alert"
          and r.get("key") == "slo:round_time"]
events = [r["event"] for r in alerts]
assert "firing" in events and "resolved" in events, (
    f"want a full fire->resolve lifecycle, got {events}")
fire = next(r for r in alerts if r["event"] == "firing")
# the alert names the SLO, the metric, and the offending worker
assert fire["labels"]["slo"] == "round_time", fire
assert fire["labels"]["metric"] == "train.round_seconds", fire
assert fire["labels"].get("worker") is not None, fire
assert "SLO round_time burning" in fire["summary"], fire
assert fire["value"] > 2.5, fire              # the compile-round p95
print(f"  lifecycle ok: {events}; fired at p95={fire['value']:.2f}s "
      f"on worker {fire['labels']['worker']}")
EOF

# the exit contract, quiet side: everything resolved -> 0
run python -m fedrec_tpu.cli.obs alerts "$OUT/obs" > "$OUT/alerts.txt"
grep -q "FIRING" "$OUT/alerts.txt" && grep -q "RESOLVED" "$OUT/alerts.txt" \
    || { echo "alerts timeline missing transitions"; cat "$OUT/alerts.txt"; exit 1; }

# surfaces: the Alerts panel in the run report, alert.* in the exposition
python -m fedrec_tpu.cli.obs report "$OUT/obs" > "$OUT/report.txt"
grep -q "^## Alerts" "$OUT/report.txt" \
    || { echo "no Alerts panel in the run report"; exit 1; }
grep -q "alert_transitions_total" "$OUT/obs/prometheus.txt" \
    || { echo "no alert.* instruments in the exposition"; exit 1; }
echo "  surfaces ok: alerts verb exit 0, report panel, prometheus rows"

echo "== [2/3] unmeetable SLO: stays firing, alerts/tail exit 1 =="
run python -m fedrec_tpu.cli.run 2 16 3 --strategy param_avg --clients 8 \
    --synthetic --synthetic-train 256 --synthetic-news 128 --mode joint \
    --obs-dir "$OUT/obs_hot" "${TINY[@]}" \
    --set train.snapshot_dir="$OUT/snap_hot" \
    --set obs.slo.enabled=true \
    --set "obs.slo.objectives=round_time:train.round_seconds:p95<1e-9" \
    --set obs.slo.fast_window=1 --set obs.slo.slow_window=2 \
    --set obs.watch.pending_for=1 --set obs.watch.resolve_after=1 \
    > "$OUT/train_hot.log" 2>&1 || { tail -30 "$OUT/train_hot.log"; exit 1; }

set +e
run python -m fedrec_tpu.cli.obs alerts "$OUT/obs_hot" > "$OUT/alerts_hot.txt"
RC_ALERTS=$?
run python -m fedrec_tpu.cli.obs tail "$OUT/obs_hot" --once > /dev/null
RC_TAIL=$?
set -e
[ "$RC_ALERTS" -eq 1 ] \
    || { echo "alerts exit $RC_ALERTS while firing (want 1)"; exit 1; }
[ "$RC_TAIL" -eq 1 ] \
    || { echo "tail --once exit $RC_TAIL while firing (want 1)"; exit 1; }
grep -q "slo:round_time" "$OUT/alerts_hot.txt" \
    || { echo "active table missing the firing SLO"; exit 1; }
echo "  exit contract ok: alerts=1, tail --once=1 while firing"

echo "== [3/3] disabled path: no alert records, no alert.* instruments =="
run python -m fedrec_tpu.cli.run 1 16 3 --strategy param_avg --clients 8 \
    --synthetic --synthetic-train 256 --synthetic-news 128 --mode joint \
    --obs-dir "$OUT/obs_off" "${TINY[@]}" \
    --set train.snapshot_dir="$OUT/snap_off" \
    > "$OUT/train_off.log" 2>&1 || { tail -30 "$OUT/train_off.log"; exit 1; }
if grep -q '"kind": "alert"' "$OUT/obs_off/metrics.jsonl"; then
    echo "disabled run emitted alert records"; exit 1
fi
if grep -q "alert_" "$OUT/obs_off/prometheus.txt"; then
    echo "disabled run registered alert.* instruments"; exit 1
fi
echo "  disabled path ok: zero watch footprint"
echo "WATCH_SMOKE=PASS"
