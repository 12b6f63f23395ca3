"""Aggregation-scale frontier: round time vs cohort size, flat vs
hierarchical vs async, on the REAL ``fedrec_tpu.agg`` reduce kernels.

The round-barrier cost model at pod scale has three regimes:

* **flat**    — every logical client reports to one reducer; the round
                waits for the SLOWEST report (max of the chaos lognormal
                latency draw) and then pays one robust reduce over the
                full (C, D) contribution stack.
* **hier**    — clients pre-aggregate per host (groups of
                ``HOST_GROUP``, concurrent across hosts → wall cost is
                the slowest GROUP, not the sum), then a fanout-2 sparse
                tree reduces the per-host stack over DCN
                (``agg.hierarchy.tree_reduce_np``; wall cost is the tree
                CRITICAL PATH — per level, groups run concurrently).
                Still barriered on the slowest report, but the reduce
                leaves the linear regime: round time goes sub-linear in
                cohort size.
* **async**   — the commit fires at quorum K = ceil(QUORUM_FRAC x C)
                (``agg.commit.fold_commit`` over the K on-time entries):
                the round pays the K-quantile of the latency draw, not
                the max. The banked ``gate_saved_ms`` lane is the
                straggler tail the quorum cut off.

The async lanes also bank an **uplink-bytes column**: the wire cost of
the K on-time contributions dense vs countsketch-encoded, priced from
REAL ``fedrec_tpu.comms.encode_leaf`` payload buffers (payload size is
shape-deterministic, so one encode per leaf prices every contribution).
The structural check requires async+sketch < async-dense at 10k+.

Latency draws ride the production population engine
(``fed.chaos.population_report``: seeded lognormal, median
``chaos.pop_straggle_ms``) so the tail shape matches what the trainer's
deadline/quorum machinery actually sees. Reduce/fold times are measured
on synthetic (C, D) stacks with the real kernels; latency lanes are
bit-deterministic (seeded), timing lanes carry a measured spread.

Structural checks — run EVERY time, bank or check (they are the
acceptance criteria, not regression guards):

* hierarchical round time is SUB-LINEAR in cohort size at 10k+ clients
  (growing the cohort 10x must grow the round < 10x);
* async round time beats flat at every cohort size (the quorum cut is
  real).

Usage:
    python benchmarks/agg_scale.py            # bank if absent, else check
    python benchmarks/agg_scale.py --bank     # (re)bank the baseline
    python benchmarks/agg_scale.py --check    # check only (exit 2 if no baseline)
    python benchmarks/agg_scale.py --chip     # also time the on-device flat
                                              # mean; writes agg_scale_tpu.json

Writes ``benchmarks/agg_scale.json`` (provenance-stamped); exit 0 =
pass/banked, 1 = regression/structural failure, 2 = usage/missing-baseline.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

COHORTS = (1_000, 10_000, 100_000)   # logical clients
HOST_GROUP = 256                     # clients pre-aggregated per host
FANOUT = 2                           # cross-host DCN tree fanout
QUORUM_FRAC = 0.8                    # async commit quorum fraction
LEAF_DIMS = ((48,), (16,))           # synthetic per-client contribution
STRAGGLE_MS = 200.0                  # lognormal median report latency
STRAGGLE_SIGMA = 0.7
SKETCH_WIDTH = 0.1                   # fed.dcn_sketch_width for the uplink lane
SKETCH_CODEC = "countsketch"
SUBLINEAR_FROM = 10_000              # the acceptance bound applies at 10k+
REL_FLOOR = 1.0                      # timing lanes may regress 2x (they are
                                     # µs..ms host reduces on a shared rig)
ABS_FLOOR_MS = 0.5


def _latencies(cohort: int) -> np.ndarray:
    """The production latency draw: chaos population engine, seeded."""
    from fedrec_tpu.config import ChaosConfig
    from fedrec_tpu.fed.chaos import FaultPlan, population_report

    ccfg = ChaosConfig()
    ccfg.enabled = True
    ccfg.seed = 0
    ccfg.pop_straggle_ms = STRAGGLE_MS
    ccfg.pop_straggle_sigma = STRAGGLE_SIGMA
    plan = FaultPlan(ccfg, cohort)
    _, latency = population_report(plan, 0, np.arange(cohort))
    return latency


def _stacks(cohort: int) -> list[np.ndarray]:
    rng = np.random.default_rng([1, cohort])
    return [
        rng.standard_normal((cohort,) + d).astype(np.float32)
        for d in LEAF_DIMS
    ]


def _timed(fn, repeats: int) -> tuple[float, float]:
    """(best_ms, spread_ms) over ``repeats`` calls."""
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return min(times), max(times) - min(times)


def measure_cohort(cohort: int, repeats: int) -> dict:
    """One frontier row: flat/hier/async round-time model + components."""
    from fedrec_tpu.agg.buffer import BufferEntry
    from fedrec_tpu.agg.commit import CommitPolicy, fold_commit
    from fedrec_tpu.agg.hierarchy import tree_critical_path_ms, tree_reduce_np
    from fedrec_tpu.fed.robust import robust_reduce_tree_np

    lat = _latencies(cohort)
    stacks = _stacks(cohort)
    w = np.ones(cohort)
    fallback = [np.zeros(d, np.float32) for d in LEAF_DIMS]
    max_lat = float(lat.max())

    # ---- flat: one robust reduce over the full contribution stack
    flat_ms, flat_spread = _timed(
        lambda: robust_reduce_tree_np(
            stacks, w, "trimmed_mean", trim_k=1, fallback_tree=fallback
        ),
        repeats,
    )

    # ---- hierarchical: per-host pre-aggregate (concurrent across hosts
    # -> wall = slowest group) + cross-host critical-path tree
    hosts = list(range(0, cohort, HOST_GROUP))
    host_ms = 0.0
    host_leaves: list[list[np.ndarray]] = []
    host_w = np.empty(len(hosts))
    for hi, start in enumerate(hosts):
        idx = slice(start, min(start + HOST_GROUP, cohort))
        t0 = time.perf_counter()
        reduced = robust_reduce_tree_np(
            [s[idx] for s in stacks], w[idx], "trimmed_mean",
            trim_k=1, fallback_tree=fallback,
        )
        host_ms = max(host_ms, (time.perf_counter() - t0) * 1e3)
        host_leaves.append(list(reduced))
        host_w[hi] = w[idx].sum()
    host_stacks = [
        np.stack([h[j] for h in host_leaves], axis=0)
        for j in range(len(LEAF_DIMS))
    ]
    stats: dict = {}
    tree_reduce_np(
        host_stacks, host_w, FANOUT, "trimmed_mean", trim_k=1,
        fallback_tree=fallback, stats=stats,
    )
    tree_ms = tree_critical_path_ms(stats)

    # ---- async: commit at quorum K — pay the K-quantile latency, then
    # the buffered fold over the K on-time entries
    k = max(1, int(np.ceil(QUORUM_FRAC * cohort)))
    order = np.argsort(lat, kind="stable")
    quorum_lat = float(lat[order[k - 1]])
    on_time = order[:k]
    entries = [
        BufferEntry(
            worker=str(int(c)), round=0, epoch=0, based_on=0,
            weight=1.0, arrival_ms=float(lat[c]),
            leaves=[s[c] for s in stacks],
        )
        for c in on_time
    ]
    policy = CommitPolicy(quorum=k, staleness_cap=2)
    fold_ms, fold_spread = _timed(
        lambda: fold_commit(fallback, entries, 0, policy, method="mean"),
        max(1, repeats - 1),
    )

    # ---- async uplink bytes: the K on-time contributions over the wire,
    # dense f32 vs sketch-encoded — priced from REAL encode_leaf payload
    # buffers (payload size is shape-deterministic: one encode per leaf
    # prices every contribution of that shape)
    from fedrec_tpu.comms import encode_leaf, payload_nbytes

    sample = [s[0] for s in stacks]
    dense_per = sum(4 * x.size for x in sample)
    sketch_per = sum(
        payload_nbytes(encode_leaf(
            x, SKETCH_CODEC, sketch_width=SKETCH_WIDTH, leaf_id=j,
        ))
        for j, x in enumerate(sample)
    )

    return {
        "cohort": cohort,
        "hosts": len(hosts),
        "quorum": k,
        # deterministic (seeded draw) lanes
        "max_latency_ms": round(max_lat, 3),
        "quorum_latency_ms": round(quorum_lat, 3),
        "gate_saved_ms": round(max_lat - quorum_lat, 3),
        # timing lanes (best-of-repeats + spread)
        "flat_reduce_ms": round(flat_ms, 3),
        "flat_reduce_spread_ms": round(flat_spread, 3),
        "hier_host_ms": round(host_ms, 3),
        "hier_tree_ms": round(tree_ms, 3),
        "async_fold_ms": round(fold_ms, 3),
        "async_fold_spread_ms": round(fold_spread, 3),
        # the frontier itself
        "flat_round_ms": round(max_lat + flat_ms, 3),
        "hier_round_ms": round(max_lat + host_ms + tree_ms, 3),
        "async_round_ms": round(quorum_lat + fold_ms, 3),
        # uplink-bytes column: the K on-time pushes, dense vs sketch
        # (deterministic — real encoded payload sizes x quorum)
        "async_uplink_dense_mb": round(k * dense_per / (1024 * 1024), 4),
        "async_uplink_sketch_mb": round(k * sketch_per / (1024 * 1024), 4),
        "uplink_bytes_per_push_dense": int(dense_per),
        "uplink_bytes_per_push_sketch": int(sketch_per),
    }


def structural_check(rows: list[dict]) -> list[str]:
    """The acceptance criteria, proven on every run."""
    problems = []
    by_c = {r["cohort"]: r for r in rows}
    cohorts = sorted(by_c)
    for c1, c2 in zip(cohorts, cohorts[1:]):
        if c2 < SUBLINEAR_FROM:
            continue
        growth = by_c[c2]["hier_round_ms"] / max(by_c[c1]["hier_round_ms"], 1e-9)
        if growth >= c2 / c1:
            problems.append(
                f"hier_round_ms grew {growth:.2f}x from {c1} to {c2} clients "
                f"(>= the {c2 // c1}x cohort growth — not sub-linear)"
            )
    for r in rows:
        if r["async_round_ms"] >= r["flat_round_ms"]:
            problems.append(
                f"async_round_ms {r['async_round_ms']} >= flat_round_ms "
                f"{r['flat_round_ms']} at {r['cohort']} clients — the "
                "quorum cut saved nothing"
            )
        if (r["cohort"] >= SUBLINEAR_FROM
                and r["async_uplink_sketch_mb"] >= r["async_uplink_dense_mb"]):
            problems.append(
                f"async_uplink_sketch_mb {r['async_uplink_sketch_mb']} >= "
                f"async_uplink_dense_mb {r['async_uplink_dense_mb']} at "
                f"{r['cohort']} clients — the sketch uplink saved nothing"
            )
    return problems


_EXACT = (
    "max_latency_ms", "quorum_latency_ms", "gate_saved_ms",
    "async_uplink_dense_mb", "async_uplink_sketch_mb",
)
_TIMING = (
    "flat_reduce_ms", "hier_host_ms", "hier_tree_ms", "async_fold_ms",
)


def check(baseline: dict, rows: list[dict]) -> int:
    regressions = []
    base_by_c = {r["cohort"]: r for r in baseline["rows"]}
    for row in rows:
        base = base_by_c.get(row["cohort"])
        if base is None:
            regressions.append(
                f"cohort {row['cohort']} missing from the baseline — "
                "scenario drifted; re-bank deliberately (--bank)"
            )
            continue
        for lane in _EXACT:
            if base.get(lane) is None:
                regressions.append(
                    f"cohort {row['cohort']} {lane}: missing from the "
                    "baseline — scenario drifted; re-bank deliberately "
                    "(--bank)"
                )
                continue
            if abs(row[lane] - base[lane]) > 1e-6 * max(abs(base[lane]), 1.0):
                regressions.append(
                    f"cohort {row['cohort']} {lane}: {base[lane]} -> "
                    f"{row[lane]} — the seeded latency draw changed; "
                    "re-bank deliberately (--bank) if intended"
                )
        for lane in _TIMING:
            allowed = max(REL_FLOOR * base[lane], ABS_FLOOR_MS)
            if row[lane] - base[lane] > allowed:
                regressions.append(
                    f"cohort {row['cohort']} {lane}: {base[lane]:.3g} -> "
                    f"{row[lane]:.3g} ms (regressed > allowed {allowed:.3g})"
                )
    if regressions:
        print("AGG_SCALE=FAIL")
        for r in regressions:
            print(f"  REGRESSION {r}")
        return 1
    print(f"AGG_SCALE=PASS ({len(rows)} cohort row(s) within threshold)")
    return 0


def chip_leg(out_path: Path, repeats: int) -> None:
    """On-device flat mean over the largest cohort stack — the DCN-free
    upper bound a chip window can compare the host kernels against."""
    import jax
    import jax.numpy as jnp

    from fedrec_tpu.utils.provenance import provenance

    cohort = COHORTS[-1]
    stacks = [jnp.asarray(s) for s in _stacks(cohort)]
    w = jnp.ones(cohort)

    @jax.jit
    def device_mean(stacks, w):
        return [jnp.einsum("p,p...->...", w, s) / w.sum() for s in stacks]

    jax.block_until_ready(device_mean(stacks, w))  # compile
    best, spread = _timed(
        lambda: jax.block_until_ready(device_mean(stacks, w)), repeats
    )
    out_path.write_text(json.dumps({
        "kind": "agg_scale_chip",
        "cohort": cohort,
        "device_flat_mean_ms": round(best, 3),
        "spread_ms": round(spread, 3),
        "provenance": provenance(),
    }, indent=2))
    print(f"agg_scale: device flat mean over {cohort} x "
          f"{sum(int(np.prod(d)) for d in LEAF_DIMS)} params: {best:.3f} ms "
          f"-> {out_path}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bank", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--chip", action="store_true",
                    help="also time the on-device flat mean "
                         "(writes agg_scale_tpu.json)")
    ap.add_argument("--out", default=str(HERE / "agg_scale.json"))
    args = ap.parse_args()

    # host-side measurement: never take the chip — except the explicit
    # --chip leg, which exists to use it. This process has not imported jax
    # yet, so the child is the only one that will.
    if not args.chip:
        from fedrec_tpu.hostenv import cpu_host_env

        if os.environ.get("JAX_PLATFORMS") != "cpu":
            return subprocess.run(
                [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
                env=cpu_host_env(),
            ).returncode

    out_path = Path(args.out)
    if not args.bank and not args.check:
        args.bank = not out_path.exists()
        args.check = not args.bank

    repeats = max(args.repeats, 1)
    rows = []
    for cohort in COHORTS:
        row = measure_cohort(cohort, repeats)
        rows.append(row)
        print(
            f"agg_scale: C={cohort:>6}  flat={row['flat_round_ms']:>9.1f} ms  "
            f"hier={row['hier_round_ms']:>9.1f} ms  "
            f"async={row['async_round_ms']:>9.1f} ms  "
            f"(gate saved {row['gate_saved_ms']:.0f} ms, "
            f"quorum {row['quorum']})"
        )

    problems = structural_check(rows)
    if problems:
        print("AGG_SCALE=FAIL (structural)")
        for p in problems:
            print(f"  FAILED {p}")
        return 1

    if args.chip:
        chip_leg(HERE / "agg_scale_tpu.json", repeats)

    if args.bank:
        from fedrec_tpu.utils.provenance import provenance

        out_path.write_text(json.dumps({
            "kind": "agg_scale",
            "scenario": {
                "cohorts": list(COHORTS),
                "host_group": HOST_GROUP,
                "fanout": FANOUT,
                "quorum_frac": QUORUM_FRAC,
                "leaf_dims": [list(d) for d in LEAF_DIMS],
                "straggle_ms": STRAGGLE_MS,
                "straggle_sigma": STRAGGLE_SIGMA,
                "sketch_width": SKETCH_WIDTH,
                "sketch_codec": SKETCH_CODEC,
                "method": "trimmed_mean (flat/hier), mean fold (async)",
                "repeats": repeats,
            },
            "threshold": {
                "rel_floor": REL_FLOOR, "abs_floor_ms": ABS_FLOOR_MS,
                "sublinear_from": SUBLINEAR_FROM,
            },
            "rows": rows,
            "provenance": provenance(),
        }, indent=2))
        print(f"AGG_SCALE=BANKED ({len(rows)} cohort rows -> {out_path})")
        return 0

    if not out_path.exists():
        print(
            f"agg_scale: no baseline at {out_path} — bank one first "
            "(python benchmarks/agg_scale.py --bank)", file=sys.stderr,
        )
        return 2
    return check(json.loads(out_path.read_text()), rows)


if __name__ == "__main__":
    raise SystemExit(main())
