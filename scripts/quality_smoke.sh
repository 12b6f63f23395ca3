#!/bin/bash
# Model-quality observability smoke (ISSUE-14 acceptance scenarios), CPU:
#
#   1. a seeded 2-round synthetic training run with obs.quality.enabled:
#      asserts the sliced-eval gauges land in prometheus.txt
#      (eval_auc{slice=...}, eval_ece), the run report renders a Quality
#      section, and `fedrec-obs quality` renders the per-slice table;
#   2. a serve probe leg: an EmbeddingStore with the drift probe armed
#      publishes a healthy swap (zero drift) and a corrupted-table push —
#      the corrupted push must surface non-zero serve.drift_* metrics
#      BEFORE the swap, and the admin metrics dict must carry them;
#   3. a forced-regression gate leg: a fresh baseline is banked into a
#      scratch dir, a clean check passes (exit 0), and a seeded
#      perturbation of one category bucket must FAIL the gate (exit 1)
#      naming the slice.
#
#   scripts/quality_smoke.sh     # or: make quality-smoke
#
# Artifacts land under /tmp/fedrec_quality_smoke for inspection.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=${QUALITY_SMOKE_DIR:-/tmp/fedrec_quality_smoke}
rm -rf "$OUT"
mkdir -p "$OUT"

run() {
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" "$@"
}

echo "== [1/3] 2-round CPU training run with obs.quality =="
run python -m fedrec_tpu.cli.run 2 16 2 --strategy param_avg --clients 8 \
    --synthetic --synthetic-train 512 --synthetic-news 128 \
    --mode joint \
    --obs-dir "$OUT/train" \
    --set obs.quality.enabled=1 --set obs.quality.hist_len_edges=4,7 \
    --set model.news_dim=32 --set model.num_heads=4 --set model.head_dim=8 \
    --set model.query_dim=16 --set model.bert_hidden=48 \
    --set data.max_his_len=10 --set data.max_title_len=12 \
    --set train.snapshot_dir="$OUT/train_snap" --set train.eval_every=1 \
    --set train.eval_protocol=full > "$OUT/train.log" 2>&1 \
    || { tail -30 "$OUT/train.log"; exit 1; }

grep -q 'eval_auc{slice="all"}' "$OUT/train/prometheus.txt" \
    || { echo "prometheus.txt missing eval_auc{slice=all}"; exit 1; }
grep -q 'eval_auc{slice="category=b0"}' "$OUT/train/prometheus.txt" \
    || { echo "prometheus.txt missing category slice gauges"; exit 1; }
grep -q 'eval_ece' "$OUT/train/prometheus.txt" \
    || { echo "prometheus.txt missing eval_ece"; exit 1; }
python -m fedrec_tpu.cli.obs report "$OUT/train" > "$OUT/report.txt"
grep -q '^## Quality' "$OUT/report.txt" \
    || { echo "run report missing Quality section"; exit 1; }
python -m fedrec_tpu.cli.obs quality "$OUT/train" > "$OUT/quality.txt" \
    || { echo "fedrec-obs quality failed"; cat "$OUT/quality.txt"; exit 1; }
grep -q 'category=b' "$OUT/quality.txt" \
    || { echo "quality report missing slice table"; exit 1; }
SLICES=$(python -m fedrec_tpu.cli.obs quality "$OUT/train" --json \
    | python -c 'import json,sys; print(len(json.load(sys.stdin)["slices"]))')
[ "$SLICES" -ge 8 ] || { echo "want >= 8 slices, got $SLICES"; exit 1; }
echo "  train: Quality section + $SLICES slice gauges + ece rendered"

echo "== [2/3] serve drift-probe leg =="
run python - "$OUT" <<'EOF'
import sys

import numpy as np

from fedrec_tpu.obs import dump_artifacts, get_registry
from fedrec_tpu.serving.store import EmbeddingStore

out = sys.argv[1]
store = EmbeddingStore()
store.enable_drift_probe(num_probes=32, topk=10, seed=0)
rng = np.random.default_rng(0)
vecs = rng.standard_normal((2000, 32)).astype(np.float32)

store.publish(vecs, {"w": 1}, source="initial")
store.publish(vecs.copy(), {"w": 1}, source="healthy-refresh")
m = store.metrics()
assert m["drift_score_shift_mean"] == 0.0, m
assert m["drift_topk_jaccard"] == 1.0 and m["drift_rank_churn"] == 0.0, m
print("  healthy swap: zero drift, jaccard 1.0")

# a corrupted table push: the probe must flag it BEFORE it serves
corrupt = vecs + 3.0 * rng.standard_normal(vecs.shape).astype(np.float32)
store.publish(corrupt, {"w": 1}, source="corrupted")
m = store.metrics()
assert m["drift_score_shift_mean"] > 0, m
assert m["drift_rank_churn"] > 0.2, m
reg = get_registry()
assert reg.get("serve.drift_checks_total").value() == 2
dump_artifacts(f"{out}/serve")
print(f"  corrupted push: |Δscore| mean={m['drift_score_shift_mean']:.3f}, "
      f"rank churn={m['drift_rank_churn']:.3f} (surfaced pre-swap)")
EOF
grep -q 'serve_drift_rank_churn' "$OUT/serve/prometheus.txt" \
    || { echo "serve prometheus.txt missing drift gauges"; exit 1; }

echo "== [3/3] quality-regression gate: bank, pass, forced failure =="
run python benchmarks/quality_gate.py --bank --out "$OUT/quality_gate.json" \
    > "$OUT/gate_bank.log" 2>&1 \
    || { tail -10 "$OUT/gate_bank.log"; exit 1; }
grep -q 'QUALITY_GATE=BANKED' "$OUT/gate_bank.log"
run python benchmarks/quality_gate.py --check --out "$OUT/quality_gate.json" \
    > "$OUT/gate_pass.log" 2>&1 \
    || { echo "clean gate check failed"; tail -10 "$OUT/gate_pass.log"; exit 1; }
grep -q 'QUALITY_GATE=PASS' "$OUT/gate_pass.log"
if run python benchmarks/quality_gate.py --check --perturb-bucket 0 \
    --out "$OUT/quality_gate.json" > "$OUT/gate_fail.log" 2>&1; then
    echo "perturbed gate check exited 0 — the regression went undetected"
    tail -10 "$OUT/gate_fail.log"
    exit 1
fi
grep -q 'QUALITY_GATE=FAIL' "$OUT/gate_fail.log"
grep -q 'REGRESSION slice category=b0' "$OUT/gate_fail.log" \
    || { echo "gate failure did not name the perturbed slice"; \
         tail -10 "$OUT/gate_fail.log"; exit 1; }
echo "  gate: banked + clean pass + forced regression caught (category=b0)"
echo "QUALITY_SMOKE=PASS"
