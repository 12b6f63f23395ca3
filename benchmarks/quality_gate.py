"""Quality-regression gate: banked sliced-eval baseline + noise-aware check.

Corpus-wide eval means can absorb a badly regressed stratum without
moving (a -10% category hiding inside a +1% mean); the systems smokes
never look at accuracy at all.  This gate banks a provenance-stamped
SLICED eval artifact from a fully seeded CPU run and fails — naming the
slice — when any slice's AUC regresses beyond a noise-aware threshold
against the banked baseline.

The run: a topic-structured synthetic corpus with a RECOVERABLE ranking
signal (``make_synthetic_mind_topics`` — known AUC ceiling), a short
seeded federated training (param_avg), one full-pool sliced eval through
the ``obs.quality`` layer.  Everything is seeded, so a healthy re-run
reproduces the banked numbers almost exactly; the per-slice threshold

    allowed_drop(n) = max(MIN_DROP, Z / sqrt(n))

(MIN_DROP = 0.02, Z = 0.5) absorbs platform jitter on thin slices
(n = 100 -> 0.05) while staying tight on fat ones (n = 400 -> 0.025) —
the binomial standard error of an AUC estimate shrinks as 1/sqrt(n), so
a fixed absolute threshold would either mask fat-slice regressions or
flake on thin ones.

Usage:
    python benchmarks/quality_gate.py           # bank if absent, else check
    python benchmarks/quality_gate.py --bank    # (re)bank the baseline
    python benchmarks/quality_gate.py --check   # check only (exit 2 if no baseline)
    python benchmarks/quality_gate.py --check --perturb-bucket 0
        # seeded perturbation: corrupt category-bucket-0 news states at
        # EVAL time -> that slice regresses -> the gate must exit 1
        # naming it (the quality-smoke's forced-failure leg)

Writes ``benchmarks/quality_gate.json`` (provenance-stamped); exit 0 =
pass/banked, 1 = regression, 2 = usage/missing-baseline.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

MIN_DROP = 0.02
Z = 0.5
MIN_COUNT = 20  # slices thinner than this are reported, never gated on


def allowed_drop(n: float) -> float:
    return max(MIN_DROP, Z / max(n, 1.0) ** 0.5)


def run_sliced_eval(
    perturb_bucket: int | None, seed: int = 0, async_mode: bool = False,
) -> dict:
    """The one seeded scenario both bank and check execute: short topic-
    corpus training + a full-pool sliced eval; returns the quality digest.

    ``perturb_bucket`` corrupts the token states of every news id hashing
    into that category bucket AT EVAL TIME (training stays identical), so
    exactly the banked scenario runs with one stratum's representations
    broken — the regression the gate exists to catch.

    ``async_mode`` re-runs the SAME scenario under ``agg.mode="async"``
    (quorum 3 of 4, chaos lognormal report latencies so one client per
    round genuinely arrives late and folds with staleness weighting):
    the buffered-commit trajectory must stay within the banked sync
    baseline's noise threshold — the gate's proof that going async did
    not cost model quality."""
    import tempfile

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import make_synthetic_mind_topics
    from fedrec_tpu.obs import MetricsRegistry, set_registry
    from fedrec_tpu.obs.quality import category_buckets_of
    from fedrec_tpu.train.trainer import Trainer

    num_news, title_len, bert_hidden = 256, 12, 48
    data, token_states = make_synthetic_mind_topics(
        num_news=num_news, num_train=2048, num_valid=512,
        title_len=title_len, bert_hidden=bert_hidden, num_topics=8,
        his_len_range=(2, 10), neg_pool_range=(4, 10), seed=seed,
    )

    cfg = ExperimentConfig()
    cfg.model.news_dim = 32
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    cfg.model.bert_hidden = bert_hidden
    cfg.data.max_his_len = 10
    cfg.data.max_title_len = title_len
    cfg.data.batch_size = 32
    cfg.fed.num_clients = 4
    cfg.fed.rounds = 2
    cfg.fed.strategy = "param_avg"
    cfg.optim.user_lr = cfg.optim.news_lr = 5e-3
    cfg.train.seed = seed
    cfg.train.snapshot_dir = ""
    cfg.train.eval_every = 1_000_000  # eval run explicitly below, post-training
    cfg.train.eval_protocol = "full"
    cfg.obs.quality.enabled = True
    cfg.obs.quality.seed = seed
    cfg.obs.quality.hist_len_edges = "4,7"
    if async_mode:
        cfg.agg.mode = "async"
        cfg.agg.quorum = 3
        cfg.agg.staleness_cap = 2
        cfg.chaos.enabled = True
        cfg.chaos.seed = seed
        cfg.chaos.pop_straggle_ms = 50.0  # latency draw only (no drops):
        # orders the quorum so the slowest client buffers late each round

    old_reg = set_registry(MetricsRegistry())
    try:
        with tempfile.TemporaryDirectory() as tmp:
            cfg.train.snapshot_dir = str(Path(tmp) / "snap")
            trainer = Trainer(cfg, data, token_states)
            trainer.run()
            if perturb_bucket is not None:
                # seeded EVAL-TIME corruption of one category stratum:
                # training above was byte-identical to the banked run; only
                # the feature-table rows of bucket-B news ids are now
                # noised, so exactly that slice's representations break
                cats = category_buckets_of(
                    np.arange(num_news), cfg.obs.quality.category_buckets,
                    cfg.obs.quality.seed,
                )
                rows = np.flatnonzero(cats == perturb_bucket)
                noisy = np.asarray(trainer.token_states).copy()
                noisy[rows] += 5.0 * np.random.default_rng(seed + 1).standard_normal(
                    noisy[rows].shape
                ).astype(noisy.dtype)
                import jax.numpy as jnp

                trainer.token_states = jnp.asarray(noisy)
                trainer._table = None  # force the corpus re-encode
            q = trainer._begin_quality_eval()
            corpus = trainer.evaluate_full(_quality=q)
            trainer._finish_quality_eval(cfg.fed.rounds - 1, q, corpus)
        return {
            "slices": trainer.quality.last_slices,
            "skipped": trainer.quality.last_skipped,
            "corpus": corpus,
            "ece": (trainer.quality.last_distribution or {}).get("ece"),
            "separation": (trainer.quality.last_distribution or {}).get(
                "separation"
            ),
        }
    finally:
        set_registry(old_reg)


def bank(out_path: Path, digest: dict) -> dict:
    from fedrec_tpu.utils.provenance import provenance

    artifact = {
        "kind": "quality_gate",
        "scenario": {
            "corpus": "make_synthetic_mind_topics(num_news=256, "
                      "num_train=2048, num_valid=512, num_topics=8, seed=0)",
            "training": "param_avg, 4 clients, 2 rounds, seed 0",
            "protocol": "full-pool sliced eval (obs.quality, seed 0)",
        },
        "threshold": {"min_drop": MIN_DROP, "z": Z, "min_count": MIN_COUNT},
        **digest,
        "provenance": provenance(),
    }
    out_path.write_text(json.dumps(artifact, indent=2))
    return artifact


def check(baseline: dict, digest: dict) -> int:
    regressions: list[str] = []
    thin: list[str] = []
    gated = 0
    for name, base in baseline["slices"].items():
        n = float(base.get("count", 0))
        new = digest["slices"].get(name)
        if n < MIN_COUNT:
            thin.append(name)
            continue
        if new is None:
            regressions.append(
                f"slice {name}: present in the baseline (n={n:.0f}, "
                f"auc={base['auc']:.4f}) but MISSING from this run — the "
                "slice definitions drifted; re-bank deliberately "
                "(--bank) if that was intended"
            )
            continue
        gated += 1
        drop = float(base["auc"]) - float(new["auc"])
        allowed = allowed_drop(n)
        if drop > allowed:
            regressions.append(
                f"slice {name}: auc {base['auc']:.4f} -> {new['auc']:.4f} "
                f"(drop {drop:.4f} > allowed {allowed:.4f} at n={n:.0f})"
            )
    if regressions:
        print("QUALITY_GATE=FAIL")
        for r in regressions:
            print(f"  REGRESSION {r}")
        print(
            f"  ({gated} slice(s) gated; baseline banked "
            f"{baseline.get('provenance', {}).get('measured_at', '?')} at "
            f"commit {baseline.get('provenance', {}).get('commit', '?')}. "
            "A real model change that moves slices must re-bank with "
            "--bank; see docs/OPERATIONS.md §7d.)"
        )
        return 1
    corpus = digest.get("corpus", {})
    print(
        f"QUALITY_GATE=PASS ({gated} slice(s) within threshold"
        + (f", {len(thin)} thin slice(s) reported only" if thin else "")
        + (f"; corpus auc {corpus['auc']:.4f}" if "auc" in corpus else "")
        + ")"
    )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bank", action="store_true",
                    help="(re)bank the baseline artifact")
    ap.add_argument("--check", action="store_true",
                    help="check against the banked baseline (exit 2 if absent)")
    ap.add_argument("--perturb-bucket", type=int, default=None, metavar="B",
                    help="corrupt category-bucket-B news states at eval "
                         "time (forced-regression demonstration)")
    ap.add_argument("--out", default=str(HERE / "quality_gate.json"),
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
        # default: bank when absent, else check — the `make quality-gate` mode
        args.bank = not out_path.exists()
        args.check = not args.bank
    # AFTER defaulting: the default path with no baseline resolves to a
    # bank, which must refuse a perturbed run exactly like an explicit
    # --bank (a corrupted baseline would make the gate pass forever)
    if args.bank and args.perturb_bucket is not None:
        print("quality_gate: refusing to BANK a perturbed run — the "
              "baseline must describe the healthy scenario", file=sys.stderr)
        return 2

    digest = run_sliced_eval(args.perturb_bucket)
    live = {
        name for name, m in digest["slices"].items()
        if m.get("count", 0) >= MIN_COUNT
    }
    print(
        f"quality_gate: {len(digest['slices'])} slice(s) evaluated "
        f"({len(live)} with n>={MIN_COUNT}), corpus auc "
        f"{digest['corpus'].get('auc', float('nan')):.4f}"
    )

    if args.bank:
        if len(live) < 8:
            print(
                f"quality_gate: only {len(live)} gateable slice(s) "
                f"(need >= 8) — the scenario is too thin to bank",
                file=sys.stderr,
            )
            return 2
        bank(out_path, digest)
        print(f"QUALITY_GATE=BANKED ({len(live)} gateable slices -> {out_path})")
        return 0

    if not out_path.exists():
        print(
            f"quality_gate: no baseline at {out_path} — bank one first "
            "(python benchmarks/quality_gate.py --bank)", file=sys.stderr,
        )
        return 2
    baseline = json.loads(out_path.read_text())
    rc = check(baseline, digest)
    if rc != 0 or args.perturb_bucket is not None:
        return rc
    # ---- async leg: the same scenario trained under agg.mode=async
    # (quorum 3/4, lognormal report latencies -> one genuinely late,
    # staleness-weighted fold per round), checked against the SAME sync
    # baseline — the buffered commit must not cost model quality beyond
    # the noise threshold. Skipped for the perturb demonstration (the
    # forced failure already proved the gate bites).
    print("quality_gate: async-mode leg (agg.mode=async, quorum 3/4, "
          "staleness-weighted late folds)")
    async_digest = run_sliced_eval(None, async_mode=True)
    print(
        f"quality_gate[async]: corpus auc "
        f"{async_digest['corpus'].get('auc', float('nan')):.4f}"
    )
    return check(baseline, async_digest)


if __name__ == "__main__":
    raise SystemExit(main())
