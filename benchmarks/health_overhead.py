"""Measure the in-graph numeric sentry's step-time overhead.

The health sentry (``obs.health.sentry``) adds per-client grad/update/
param global norms + a non-finite flag to every train step's metrics.
Those are a handful of reductions over tensors the step already holds in
registers/HBM, so the contract is **< 2% steady-state step-time
regression** — this bench measures it (same model, same batches, sentry
on vs off, median steady-state step wall time).

    python benchmarks/health_overhead.py [--batch 64] [--steps 30]

Writes a JSON verdict to --out (default: print only).  CPU numbers bound
the chip numbers from above: the sentry's reductions are a fixed small
FLOP count while the step's matmuls scale with the model, so the
fraction only shrinks on a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import jax

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def build(sentry: bool, args):
    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import TrainBatcher, index_samples, make_synthetic_mind
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.parallel import client_mesh, shard_batch
    from fedrec_tpu.train import build_fed_train_step
    from fedrec_tpu.train.state import init_client_state, replicate_state
    from fedrec_tpu.train.step import commit_token_table

    cfg = ExperimentConfig()
    cfg.model.news_dim = 64
    cfg.model.num_heads = 8
    cfg.model.head_dim = 8
    cfg.model.query_dim = 32
    cfg.model.bert_hidden = 96
    cfg.data.max_his_len = 20
    cfg.data.max_title_len = 16
    cfg.data.batch_size = args.batch
    cfg.fed.num_clients = args.clients
    cfg.obs.health.sentry = sentry

    data = make_synthetic_mind(
        num_news=512, num_train=4096, num_valid=32,
        title_len=cfg.data.max_title_len,
        his_len_range=(2, cfg.data.max_his_len), seed=0,
    )
    ix = index_samples(data.train_samples, data.nid2index, cfg.data.max_his_len)
    batcher = TrainBatcher(ix, cfg.data.batch_size, cfg.data.npratio, seed=0)
    rng = np.random.default_rng(0)
    token_states = rng.standard_normal(
        (512, cfg.data.max_title_len, cfg.model.bert_hidden)
    ).astype(np.float32)
    model = NewsRecommender(cfg.model)
    state0 = init_client_state(
        model, cfg, jax.random.PRNGKey(0), 512, cfg.data.max_title_len
    )
    stacked = replicate_state(state0, cfg.fed.num_clients, jax.random.PRNGKey(1))
    mesh = client_mesh(cfg.fed.num_clients)
    step = build_fed_train_step(
        model, cfg, get_strategy("grad_avg"), mesh, mode="joint"
    )
    batches = []
    for b in batcher.epoch_batches_sharded(cfg.fed.num_clients, 0):
        batches.append(shard_batch(mesh, {
            "candidates": b.candidates, "history": b.history, "labels": b.labels,
        }))
        if len(batches) >= args.warmup + args.steps:
            break
    # where the joint step states its table rests (train/step.py)
    return step, stacked, batches, commit_token_table(token_states, mesh)[0]


def time_steps_state(step, state, batches, table, n: int):
    """Run n untimed steps (compile + cache warmup); returns the state."""
    for i in range(n):
        state, metrics = step(state, batches[i % len(batches)], table)
    jax.block_until_ready(metrics["mean_loss"])
    return state


def time_block(step, state, batches, table, n: int):
    """Time n steady-state steps (cycling the epoch's batches — donation
    is off, so re-dispatching a batch is safe); returns (times, state)."""
    times = []
    for i in range(n):
        batch = batches[i % len(batches)]
        t0 = time.perf_counter()
        state, metrics = step(state, batch, table)
        jax.block_until_ready(metrics["mean_loss"])
        times.append(time.perf_counter() - t0)
    return times, state


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    # default 256: the flagship-relevant batch (the PR-2 MFU work centers
    # on large batches); --batch 64 shows the toy-scale worst case where
    # the sentry's fixed cost is a visible fraction of a tiny CPU step
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    results = {}
    # build both variants first, then INTERLEAVE timing blocks: host-load
    # drift hits both variants equally instead of whichever ran second
    arms = {s: build(s, args) for s in (False, True)}
    states = {s: arms[s][1] for s in arms}
    samples: dict[bool, list[float]] = {False: [], True: []}
    block = 5
    for s in arms:  # warmup both compiles before any timed block
        step, _, batches, table = arms[s]
        states[s] = time_steps_state(
            step, states[s], batches, table, args.warmup
        )
    block_medians: dict[bool, list[float]] = {False: [], True: []}
    for k in range(max(args.steps // block, 1)):
        # alternate arm order per block so periodic host load cannot bias
        # whichever arm habitually runs second
        order = (False, True) if k % 2 == 0 else (True, False)
        for s in order:
            step, _, batches, table = arms[s]
            ts, states[s] = time_block(
                step, states[s], batches, table, block
            )
            samples[s].extend(ts)
            block_medians[s].append(float(np.median(ts)))
    for s in (False, True):
        ts = samples[s]
        results["sentry_on" if s else "sentry_off"] = {
            "median_ms": round(float(np.median(ts)) * 1e3, 3),
            "mean_ms": round(float(np.mean(ts)) * 1e3, 3),
            "min_ms": round(float(np.min(ts)) * 1e3, 3),
            "steps": len(ts),
        }
    off = results["sentry_off"]["median_ms"]
    on = results["sentry_on"]["median_ms"]
    results["overhead_pct_median"] = round((on - off) / off * 100.0, 2)
    # min-of-steps: each arm's best step had the least host interference
    off_min = results["sentry_off"]["min_ms"]
    on_min = results["sentry_on"]["min_ms"]
    results["overhead_pct_min"] = round((on_min - off_min) / off_min * 100.0, 2)
    # THE headline estimator: median of per-adjacent-block-pair deltas —
    # each pair ran back to back, so slow host-load drift cancels within
    # the pair instead of biasing whole-run aggregates
    deltas = [
        (a - b) / b * 100.0
        for a, b in zip(block_medians[True], block_medians[False])
    ]
    results["overhead_pct"] = round(float(np.median(deltas)), 2)
    results["paired_block_deltas_pct"] = [round(d, 2) for d in deltas]
    results["pass_lt_2pct"] = results["overhead_pct"] < 2.0
    results["batch"] = args.batch
    results["clients"] = args.clients
    results["platform"] = jax.devices()[0].platform
    print(json.dumps(results, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
