#!/bin/bash
# Chaos smoke (ISSUE-5 acceptance scenarios), CPU-only:
#
#   1. FAULT-FREE BASELINE: a 3-round trimmed-mean run; final loss banked.
#   2. CHAOS RUN: the same config under a seeded FaultPlan — 30% dropout +
#      one nan-update client + one x100 scale-poison client — with
#      coordinate-wise trimmed mean (trim_k=2: two byzantine clients).
#      Must complete all rounds with FINITE losses, and `fedrec-obs
#      report` must render a Robustness section with the injected-fault
#      counts.
#   3. DETERMINISM: re-run the same plan; the per-round training_loss
#      trajectory must be BIT-IDENTICAL.
#   4. RECOVERY: an injected nan-update with fed.robust.recover=true —
#      quarantine + rollback + a completed run (no flight-recorder
#      abort), rollback visible in the registry counters.
#   5. POPULATION (ISSUE-6): 1024 logical clients sampled 64/round onto
#      the 8x8 slot mesh under 20% seeded dropout + lognormal straggle +
#      a 200ms round deadline and a 16-report quorum — must survive all
#      rounds with finite losses, over-selection visible (80 sampled),
#      dropouts/deadline-cuts counted, quorum held, and the whole run
#      (losses AND churn counters) bit-identical on re-run.
#   6. COMPRESSED (ISSUE-7): the population scenario with the sign1bit
#      update codec (error feedback on) + trimmed-mean aggregation —
#      robust x compress via decode-before-reduce. Must survive with
#      finite losses, bank measured uplink bytes (Communication section
#      in the report, ratio > 20x), and replay bit-identically from the
#      chaos seed.
#
#   scripts/chaos_smoke.sh     # or: make chaos-smoke
#
# Artifacts land under /tmp/fedrec_chaos_smoke for inspection.
set -euo pipefail
cd "$(dirname "$0")/.."

OUT=${CHAOS_SMOKE_DIR:-/tmp/fedrec_chaos_smoke}
rm -rf "$OUT"
mkdir -p "$OUT"

run() {
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" "$@"
}

SMALL=(
    --set model.news_dim=32 --set model.num_heads=4 --set model.head_dim=8
    --set model.query_dim=16 --set model.bert_hidden=48
    --set data.max_his_len=10 --set data.max_title_len=12
    --set train.eval_every=1000 --set train.eval_protocol=sampled
    --set fed.robust.method=trimmed_mean
)
CHAOS=(
    --set chaos.enabled=true --set chaos.seed=7 --set chaos.drop_rate=0.3
    --set "chaos.faults=nan@*:3,scale@*:5x100"
    --set fed.robust.trim_k=2
    --set obs.health.abort_on_nonfinite=false
)

echo "== [1/6] fault-free trimmed-mean baseline =="
run python -m fedrec_tpu.cli.run 3 8 10 --strategy param_avg --clients 8 \
    --mode joint --synthetic --synthetic-train 256 --synthetic-news 64 \
    --obs-dir "$OUT/baseline" "${SMALL[@]}" \
    --set train.snapshot_dir="$OUT/base_snap" \
    > "$OUT/baseline.log" 2>&1 || { tail -30 "$OUT/baseline.log"; exit 1; }

echo "== [2/6] chaos run: 30% dropout + nan client + x100 poison client =="
run python -m fedrec_tpu.cli.run 3 8 10 --strategy param_avg --clients 8 \
    --mode joint --synthetic --synthetic-train 256 --synthetic-news 64 \
    --obs-dir "$OUT/chaos_a" "${SMALL[@]}" "${CHAOS[@]}" \
    --set train.snapshot_dir="$OUT/chaos_a_snap" \
    > "$OUT/chaos_a.log" 2>&1 || { tail -30 "$OUT/chaos_a.log"; exit 1; }

echo "== [3/6] determinism: same plan, bit-identical trajectory =="
run python -m fedrec_tpu.cli.run 3 8 10 --strategy param_avg --clients 8 \
    --mode joint --synthetic --synthetic-train 256 --synthetic-news 64 \
    --obs-dir "$OUT/chaos_b" "${SMALL[@]}" "${CHAOS[@]}" \
    --set train.snapshot_dir="$OUT/chaos_b_snap" \
    > "$OUT/chaos_b.log" 2>&1 || { tail -30 "$OUT/chaos_b.log"; exit 1; }

echo "== [4/6] recovery: nan client + fed.robust.recover=true =="
run python -m fedrec_tpu.cli.run 4 8 10 --strategy param_avg --clients 8 \
    --mode joint --synthetic --synthetic-train 256 --synthetic-news 64 \
    --obs-dir "$OUT/recover" "${SMALL[@]}" \
    --set chaos.enabled=true --set "chaos.faults=nan@1:3" \
    --set fed.robust.recover=true \
    --set train.snapshot_dir="$OUT/recover_snap" \
    > "$OUT/recover.log" 2>&1 || { tail -30 "$OUT/recover.log"; exit 1; }

POP=(
    --set fed.population.num_clients=1024
    --set fed.population.over_select=1.25
    --set fed.population.round_deadline_ms=200
    --set fed.population.min_reports=16
    --set fed.population.seed=11
    --set chaos.enabled=true --set chaos.seed=13
    --set chaos.pop_drop_rate=0.2 --set chaos.pop_straggle_ms=50
)

echo "== [5/6] population: 1024 logical clients, 64/round, 20% dropout =="
run python -m fedrec_tpu.cli.run 3 2 10 --strategy param_avg --clients 64 \
    --mode joint --synthetic --synthetic-train 2048 --synthetic-news 64 \
    --obs-dir "$OUT/pop_a" "${SMALL[@]}" "${POP[@]}" \
    --set train.snapshot_dir="$OUT/pop_a_snap" \
    > "$OUT/pop_a.log" 2>&1 || { tail -30 "$OUT/pop_a.log"; exit 1; }
run python -m fedrec_tpu.cli.run 3 2 10 --strategy param_avg --clients 64 \
    --mode joint --synthetic --synthetic-train 2048 --synthetic-news 64 \
    --obs-dir "$OUT/pop_b" "${SMALL[@]}" "${POP[@]}" \
    --set train.snapshot_dir="$OUT/pop_b_snap" \
    > "$OUT/pop_b.log" 2>&1 || { tail -30 "$OUT/pop_b.log"; exit 1; }

COMPRESS=(
    --set fed.dcn_compress=sign1bit
    --set fed.robust.trim_k=1
)

echo "== [6/6] compressed: sign1bit + trimmed_mean + population dropout =="
run python -m fedrec_tpu.cli.run 3 2 10 --strategy param_avg --clients 64 \
    --mode joint --synthetic --synthetic-train 2048 --synthetic-news 64 \
    --obs-dir "$OUT/comp_a" "${SMALL[@]}" "${POP[@]}" "${COMPRESS[@]}" \
    --set train.snapshot_dir="$OUT/comp_a_snap" \
    > "$OUT/comp_a.log" 2>&1 || { tail -30 "$OUT/comp_a.log"; exit 1; }
run python -m fedrec_tpu.cli.run 3 2 10 --strategy param_avg --clients 64 \
    --mode joint --synthetic --synthetic-train 2048 --synthetic-news 64 \
    --obs-dir "$OUT/comp_b" "${SMALL[@]}" "${POP[@]}" "${COMPRESS[@]}" \
    --set train.snapshot_dir="$OUT/comp_b_snap" \
    > "$OUT/comp_b.log" 2>&1 || { tail -30 "$OUT/comp_b.log"; exit 1; }

run python - "$OUT" <<'EOF'
import json, math, sys
from pathlib import Path

out = Path(sys.argv[1])

def losses(d):
    rows = {}
    for line in (out / d / "metrics.jsonl").read_text().splitlines():
        try:
            r = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(r, dict) and "training_loss" in r and "round" in r:
            rows[int(r["round"])] = r["training_loss"]
    return [rows[k] for k in sorted(rows)]

base, a, b = losses("baseline"), losses("chaos_a"), losses("chaos_b")
assert len(a) == 3 and all(map(math.isfinite, a)), f"chaos run not finite: {a}"
assert a == b, f"chaos trajectory not bit-identical:\n{a}\n{b}"
assert all(map(math.isfinite, base))
# robust run's loss within shouting distance of the fault-free baseline
assert abs(a[-1] - base[-1]) < 0.25, (a[-1], base[-1])

from fedrec_tpu.obs.report import build_report, load_jsonl
records, snaps = load_jsonl(out / "chaos_a" / "metrics.jsonl")
rb = build_report(records, snaps).get("robustness")
assert rb and rb.get("robust_method") == "trimmed_mean", rb
fi = rb.get("faults_injected", {})
assert fi.get("nan", 0) >= 3 and fi.get("scale", 0) >= 3 and fi.get("drop", 0) >= 1, fi

rec_records, rec_snaps = load_jsonl(out / "recover" / "metrics.jsonl")
rrb = build_report(rec_records, rec_snaps)["robustness"]
assert rrb.get("rollbacks", 0) >= 1 and rrb.get("quarantines", 0) >= 1, rrb
rec = losses("recover")
assert len(rec) == 4 and all(map(math.isfinite, rec)), rec
import math as _math
pa, pb = losses("pop_a"), losses("pop_b")
assert len(pa) == 3 and all(map(_math.isfinite, pa)), f"population run not finite: {pa}"
assert pa == pb, f"population trajectory not bit-identical:\n{pa}\n{pb}"

def pop_part(d):
    records, snaps = load_jsonl(out / d / "metrics.jsonl")
    return build_report(records, snaps).get("participation")

part_a, part_b = pop_part("pop_a"), pop_part("pop_b")
assert part_a and part_a["population"] == 1024, part_a
assert part_a["cohort_sampled"] == 80, part_a           # ceil(64 * 1.25)
assert part_a["cohort_reporting"] >= 16, part_a         # quorum held
assert part_a.get("dropouts", 0) > 0, part_a            # churn visible
assert part_a == part_b, f"population churn not bit-identical:\n{part_a}\n{part_b}"

# leg 6: sign1bit + trimmed_mean + population dropout (robust x compress)
ca, cb = losses("comp_a"), losses("comp_b")
assert len(ca) == 3 and all(map(_math.isfinite, ca)), f"compressed run not finite: {ca}"
assert ca == cb, f"compressed trajectory not bit-identical:\n{ca}\n{cb}"

def comm_section(d):
    records, snaps = load_jsonl(out / d / "metrics.jsonl")
    return build_report(records, snaps).get("communication")

comm = comm_section("comp_a")
assert comm and comm["bytes_up"].get("cohort", 0) > 0, comm   # measured uplink
assert comm["compression_ratio"] > 20, comm                   # ~32x sign1bit
assert comm == comm_section("comp_b"), "compressed byte accounting not bit-identical"
crb = None
records_c, snaps_c = load_jsonl(out / "comp_a" / "metrics.jsonl")
crb = build_report(records_c, snaps_c).get("robustness")
assert crb and crb.get("robust_method") == "trimmed_mean", crb  # decode-before-reduce ran

print("chaos smoke OK")
print(f"  baseline   losses: {base}")
print(f"  chaos      losses: {a}  (bit-identical on re-run)")
print(f"  recovery   losses: {rec}  rollbacks={rrb['rollbacks']:.0f} quarantines={rrb['quarantines']:.0f}")
print(f"  population losses: {pa}  (bit-identical on re-run)")
print(f"  compressed losses: {ca}  (sign1bit+trimmed_mean, bit-identical on re-run; "
      f"uplink {comm['bytes_up']['cohort']/2**20:.2f} MB at {comm['compression_ratio']:.0f}x)")
print(f"  population churn : sampled={part_a['cohort_sampled']:.0f} reporting={part_a['cohort_reporting']:.0f} "
      f"dropouts={part_a.get('dropouts', 0):.0f} deadline_cuts={part_a.get('deadline_cuts', 0):.0f} "
      f"coverage={part_a.get('coverage', 0):.1%}")
EOF

echo "chaos smoke PASSED; artifacts in $OUT"
