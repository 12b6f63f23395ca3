"""Perf-regression gate: banked seeded-CPU perf baseline + noise-aware check.

The chip benches (``bench.py``, ``benchmarks/step_profile.py``) certify
absolute speed but need a TPU window; the systems smokes never look at
performance at all — so a CPU-visible perf regression (a slow import in
the hot loop, a batcher slowdown, an accidental per-step host sync, a
FLOPs-model drift) lands silently and waits for the next chip window to
be noticed.  This gate banks a provenance-stamped perf artifact from a
small fully seeded CPU scenario and fails — NAMING THE LANE — when any
lane regresses beyond a noise-aware threshold against the banked
baseline.  It is the perf analog of ``benchmarks/quality_gate.py``.

Lanes (the flagship joint step at toy scale, everything seeded):

* ``steps_per_sec``        — compiled per-batch train-step throughput
                             (best of ``--repeats`` timed chains)
* ``batch_build_ms``       — host batch assembly (TrainBatcher epoch)
* ``h2d_ms``               — host->device transfer of one built batch
* ``dispatch_gap_sync_ms`` — host gap between dispatches of a
                             build->transfer->dispatch loop against a
                             sleep-simulated off-host device (the
                             interval the device queue would sit empty)
* ``dispatch_gap_prefetch_ms`` — the same loop behind the bounded
                             prefetcher (``data.prefetch_batches``);
                             its regression means the overlap machinery
                             stopped hiding the build
* ``flops_per_step``       — the ANALYTIC step-FLOPs model
                             (``fedrec_tpu.obs.perf``), exact: any
                             change fails until deliberately re-banked
                             (an un-noticed model drift would silently
                             re-price every banked MFU claim)

Noise policy: timing lanes are measured ``--repeats`` times; the banked
artifact records each lane's best value AND its absolute spread
(max-min).  A check fails a timing lane only when it regresses by more
than ``max(REL_FLOOR x baseline, min(NOISE_K x max(spread_bank,
spread_now), NOISE_CAP x baseline), ABS_FLOOR)`` — generous on a
time-sliced CI host, still tight enough to catch a 2x host-pipeline
regression, and the noise term is CAPPED so a pathologically jittery
window can never excuse an arbitrary regression.  The exact lane
allows zero drift.

Usage:
    python benchmarks/perf_gate.py            # bank if absent, else check
    python benchmarks/perf_gate.py --bank     # (re)bank the baseline
    python benchmarks/perf_gate.py --check    # check only (exit 2 if no baseline)
    python benchmarks/perf_gate.py --check --demo-regression steps_per_sec
        # forced-failure demonstration: the named lane's measurement is
        # adversely corrupted 10x (marked "simulated") -> the gate must
        # exit 1 naming it (the obs-smoke's forced-failure leg)

Writes ``benchmarks/perf_gate.json`` (provenance-stamped); exit 0 =
pass/banked, 1 = regression, 2 = usage/missing-baseline.
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

REL_FLOOR = 0.5       # a timing lane may regress 50% before failing...
NOISE_K = 4.0         # ...or 4x its measured spread, whichever is larger...
NOISE_CAP = 0.8       # ...but the noise term never exceeds 80% of the
                      # baseline: a day so noisy that 4x spread would
                      # excuse ANY regression must not neuter the gate
                      # (and the 10x --demo-regression stays deterministic)
ABS_FLOOR_MS = 0.5    # near-zero ms lanes get an absolute grace floor
DEMO_FACTOR = 10.0    # --demo-regression corruption (90% regression)
SIM_TAU_S = 0.002     # the sleep-simulated off-host device interval


def _gate_cfg():
    from fedrec_tpu.config import ExperimentConfig

    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = 48
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = 12
    cfg.data.batch_size = 16
    cfg.fed.num_clients = 1
    return cfg


def measure_lanes(repeats: int = 3) -> dict:
    """The one seeded scenario both bank and check execute.  Returns
    ``{lane: {"value", "unit", "direction", "spread", "kind"}}`` —
    ``direction`` says which way is worse, ``spread`` is the absolute
    max-min over repeats (the noise the threshold adapts to)."""
    import jax
    import jax.numpy as jnp

    from fedrec_tpu.data.batcher import IndexedSamples, TrainBatcher
    from fedrec_tpu.data.prefetch import Prefetcher
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.obs.perf import flops_per_train_step
    from fedrec_tpu.parallel import client_mesh, shard_batch
    from fedrec_tpu.train import build_fed_train_step
    from fedrec_tpu.train.state import init_client_state, replicate_state
    from fedrec_tpu.train.step import commit_token_table

    cfg = _gate_cfg()
    num_news, L = 128, cfg.data.max_title_len
    B, C, H = cfg.data.batch_size, 1 + cfg.data.npratio, cfg.data.max_his_len
    rng = np.random.default_rng(0)
    token_states = jnp.asarray(
        rng.standard_normal((num_news, L, cfg.model.bert_hidden)),
        jnp.float32,
    )
    model = NewsRecommender(cfg.model)
    mesh = client_mesh(1)
    # where the joint step states its table rests (train/step.py)
    token_states, _ = commit_token_table(token_states, mesh)
    step = build_fed_train_step(
        model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
    )
    state = replicate_state(
        init_client_state(model, cfg, jax.random.PRNGKey(0), num_news, L),
        1, jax.random.PRNGKey(1),
    )

    def make_batch(seed: int):
        r = np.random.default_rng(seed)
        return shard_batch(mesh, {
            "candidates": r.integers(0, num_news, (1, B, C)).astype(np.int32),
            "history": r.integers(0, num_news, (1, B, H)).astype(np.int32),
            "labels": np.zeros((1, B), np.int32),
        })

    batches = [make_batch(s) for s in range(4)]

    # ---- lane: steps_per_sec (compile + warm first, then timed chains)
    metrics = None
    for i in range(2):
        state, metrics = step(state, batches[i % 4], token_states)
    np.asarray(metrics["loss"])
    K = 6
    rates = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for i in range(K):
            state, metrics = step(state, batches[i % 4], token_states)
        np.asarray(metrics["loss"])  # readback = real synchronization
        rates.append(K * B / (time.perf_counter() - t0))

    # ---- lanes: batch_build_ms / h2d_ms (the host input pipeline)
    n = 4 * B
    pool = 12
    ix = IndexedSamples(
        pos=rng.integers(0, num_news, n).astype(np.int32),
        neg_pools=rng.integers(0, num_news, (n, pool)).astype(np.int32),
        neg_lens=np.full(n, pool, np.int32),
        history=rng.integers(0, num_news, (n, H)).astype(np.int32),
        his_len=np.full(n, H, np.int32),
    )
    batcher = TrainBatcher(ix, B, npratio=C - 1, seed=0)
    builds = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        cnt = sum(1 for _ in batcher.epoch_batches(0))
        builds.append((time.perf_counter() - t0) / max(cnt, 1) * 1e3)
    b0 = next(iter(batcher.epoch_batches(1)))

    def put(b):
        return (jnp.asarray(b.candidates), jnp.asarray(b.history))

    jax.block_until_ready(put(b0))
    h2ds = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(put(b0))
        h2ds.append((time.perf_counter() - t0) / 5 * 1e3)

    # ---- lanes: dispatch gap against a sleep-simulated off-host device
    # (sleep releases the GIL and the core, so the prefetcher's producer
    # can actually run ahead — same model step_profile.py uses on CPU
    # hosts, where real overlap is physically impossible on one core)
    def gen(limit: int):
        e, count = 2, 0
        while count < limit:
            for b in batcher.epoch_batches(e):
                yield b
                count += 1
                if count >= limit:
                    return
            e += 1

    def gap_loop(source) -> float:
        # bounded by the source generator itself (gen(K_sim) yields
        # exactly K_sim batches)
        gaps = []
        t_prev = None
        for args in source:
            t_ready = time.perf_counter()
            if t_prev is not None:
                gaps.append(t_ready - t_prev)
            time.sleep(SIM_TAU_S)  # the simulated off-host dispatch
            t_prev = time.perf_counter()
        return float(np.mean(gaps)) * 1e3

    K_sim = 8
    sync_gaps, pf_gaps = [], []
    for _ in range(repeats):
        sync_gaps.append(gap_loop(put(b) for b in gen(K_sim)))
        pf = Prefetcher(gen(K_sim), depth=2, transform=put)
        pf_gaps.append(gap_loop(pf))

    def lane(vals, unit, direction, kind="timing", best=min):
        return {
            "value": round(best(vals), 4),
            "unit": unit,
            "direction": direction,
            "spread": round(max(vals) - min(vals), 4),
            "kind": kind,
        }

    return {
        "steps_per_sec": lane(rates, "samples/sec", "lower_is_worse",
                              best=max),
        "batch_build_ms": lane(builds, "ms", "higher_is_worse"),
        "h2d_ms": lane(h2ds, "ms", "higher_is_worse"),
        "dispatch_gap_sync_ms": lane(sync_gaps, "ms", "higher_is_worse"),
        "dispatch_gap_prefetch_ms": lane(pf_gaps, "ms", "higher_is_worse"),
        "flops_per_step": {
            "value": flops_per_train_step(cfg, B, num_news),
            "unit": "flops",
            "direction": "any_change",
            "spread": 0.0,
            "kind": "exact",
        },
    }


def allowed_regression(base: dict, now: dict) -> float:
    """How much a timing lane may move in its bad direction: the larger
    of REL_FLOOR x baseline, NOISE_K x the larger measured spread
    (capped at NOISE_CAP x baseline so a pathologically noisy window
    cannot excuse arbitrary regressions), and (for ms lanes) an
    absolute grace floor."""
    bval = abs(float(base["value"]))
    noise = NOISE_K * max(
        float(base.get("spread", 0)), float(now.get("spread", 0))
    )
    allowed = max(REL_FLOOR * bval, min(noise, NOISE_CAP * bval))
    if base.get("unit") == "ms":
        allowed = max(allowed, ABS_FLOOR_MS)
    return allowed


def check(baseline: dict, lanes: dict) -> int:
    regressions: list[str] = []
    gated = 0
    for name, base in baseline["lanes"].items():
        now = lanes.get(name)
        if now is None:
            regressions.append(
                f"lane {name}: present in the baseline but MISSING from "
                "this run — the gate scenario drifted; re-bank "
                "deliberately (--bank) if that was intended"
            )
            continue
        gated += 1
        bval, nval = float(base["value"]), float(now["value"])
        if base["kind"] == "exact":
            if abs(nval - bval) > 1e-6 * max(abs(bval), 1.0):
                regressions.append(
                    f"lane {name}: {bval:.6g} -> {nval:.6g} — the analytic "
                    "FLOPs model changed; every banked MFU claim reprices. "
                    "Re-bank deliberately (--bank) if the model change is "
                    "intended"
                )
            continue
        drop = bval - nval if base["direction"] == "lower_is_worse" \
            else nval - bval
        allowed = allowed_regression(base, now)
        if drop > allowed:
            sim = " [SIMULATED]" if now.get("simulated") else ""
            regressions.append(
                f"lane {name}: {bval:.4g} -> {nval:.4g} {base['unit']} "
                f"(regressed {drop:.4g} > allowed {allowed:.4g}){sim}"
            )
    if regressions:
        print("PERF_GATE=FAIL")
        for r in regressions:
            print(f"  REGRESSION {r}")
        print(
            f"  ({gated} lane(s) gated; baseline banked "
            f"{baseline.get('provenance', {}).get('measured_at', '?')} at "
            f"commit {baseline.get('provenance', {}).get('commit', '?')}. "
            "A real change that moves a lane must re-bank with --bank; "
            "see docs/OPERATIONS.md §7e.)"
        )
        return 1
    print(f"PERF_GATE=PASS ({gated} lane(s) within threshold)")
    return 0


def bank(out_path: Path, lanes: dict, repeats: int) -> dict:
    from fedrec_tpu.utils.provenance import provenance

    artifact = {
        "kind": "perf_gate",
        "scenario": {
            "step": "joint-mode per-batch train step, B=16, 128-news "
                    "corpus, toy dims (see _gate_cfg), seed 0",
            "host": "TrainBatcher epoch build + h2d of one batch + "
                    f"sleep-simulated ({SIM_TAU_S * 1e3:g} ms) off-host "
                    "dispatch loop, sync vs prefetch depth 2",
            "repeats": repeats,
        },
        "threshold": {
            "rel_floor": REL_FLOOR, "noise_k": NOISE_K,
            "noise_cap": NOISE_CAP, "abs_floor_ms": ABS_FLOOR_MS,
        },
        "lanes": lanes,
        "provenance": provenance(),
    }
    out_path.write_text(json.dumps(artifact, indent=2))
    return artifact


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bank", action="store_true",
                    help="(re)bank the baseline artifact")
    ap.add_argument("--check", action="store_true",
                    help="check against the banked baseline (exit 2 if absent)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed repeats per timing lane (best banked)")
    ap.add_argument("--demo-regression", default=None, metavar="LANE",
                    help="adversely corrupt LANE's measurement 10x "
                         "(forced-regression demonstration)")
    ap.add_argument("--out", default=str(HERE / "perf_gate.json"),
                    help="baseline artifact path")
    args = ap.parse_args()

    # host-side CPU measurement: never take the chip. This process has not
    # imported jax yet, so the child is the only one that will.
    from fedrec_tpu.hostenv import cpu_host_env

    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
            env=cpu_host_env(),
        ).returncode

    out_path = Path(args.out)
    if not args.bank and not args.check:
        # default: bank when absent, else check — the `make perf-gate` mode
        args.bank = not out_path.exists()
        args.check = not args.bank
    # AFTER defaulting: the default path with no baseline resolves to a
    # bank, which must refuse a corrupted run exactly like an explicit
    # --bank (a simulated-regression baseline would gate against garbage)
    if args.bank and args.demo_regression is not None:
        print("perf_gate: refusing to BANK a demo-regression run — the "
              "baseline must describe the healthy scenario", file=sys.stderr)
        return 2

    lanes = measure_lanes(repeats=max(args.repeats, 1))
    if args.demo_regression is not None:
        lane = lanes.get(args.demo_regression)
        if lane is None:
            print(
                f"perf_gate: unknown lane {args.demo_regression!r} "
                f"(lanes: {', '.join(sorted(lanes))})", file=sys.stderr,
            )
            return 2
        # adverse 10x corruption — past any noise allowance (capped at
        # NOISE_CAP) AND, for ms lanes, past the absolute grace floor (a
        # tiny banked h2d_ms times 10 could otherwise hide under
        # ABS_FLOOR_MS) — marked so the failure line says SIMULATED
        if lane["direction"] == "lower_is_worse":
            lane["value"] = lane["value"] / DEMO_FACTOR
        else:
            lane["value"] = max(
                lane["value"] * DEMO_FACTOR,
                lane["value"] + DEMO_FACTOR * ABS_FLOOR_MS,
            )
        lane["simulated"] = True
    for name in sorted(lanes):
        la = lanes[name]
        print(f"perf_gate: {name} = {la['value']:.6g} {la['unit']} "
              f"(spread {la['spread']:.4g})")

    if args.bank:
        bank(out_path, lanes, max(args.repeats, 1))
        print(f"PERF_GATE=BANKED ({len(lanes)} lanes -> {out_path})")
        return 0

    if not out_path.exists():
        print(
            f"perf_gate: no baseline at {out_path} — bank one first "
            "(python benchmarks/perf_gate.py --bank)", file=sys.stderr,
        )
        return 2
    baseline = json.loads(out_path.read_text())
    return check(baseline, lanes)


if __name__ == "__main__":
    raise SystemExit(main())
