"""Quickest proof that the system still starts on the chip.

    python chip_smoke.py            # one TPU chip: trainer phase, then server phase
    python chip_smoke.py --chips 4  # four chips: one client per chip vs the
                                    # same clients as a cohort on one device

Drives the flagship model (published widths, joint step, bf16) through the
entry points a user calls: ``fedrec_tpu.cli.run`` builds the config, the
synthetic MIND-shaped corpus and the device-resident token-state catalog,
``Trainer(...).run()`` trains and validates; ``fedrec_tpu.cli.serve`` builds
and warms the ``ServingService`` and ``serve_forever`` answers TCP/JSON-lines
requests across a hot swap. One process; nothing here falls back to the CPU.
The last line of stdout is ``{"ok": true, "device": {...}}``; any failure is
a non-zero exit with the reason on stderr and no such line.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import math
import os
import shutil
import signal
import socket
import sys
import time
from importlib import metadata
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
# snapshots of the trainer phases; wiped at start so no run resumes another
WORK_DIR = HERE / ".chip_smoke"

CATALOG_ROWS = 65_536       # MIND-small: 65k news x 50 tokens x 768 bf16
MIN_CATALOG_ROWS = 16_384
TRAIN_SAMPLES = 16_384      # 32 steps a round at 8 clients x B=64

# the flagship at ModelConfig's published widths: nothing about the model is
# set here but the step mode and the compute dtype a TPU user picks
FLAGSHIP = ["--mode", "joint", "--synthetic", "--set", "model.dtype=bfloat16"]

# loss agreement between one-client-per-chip and the one-device cohort.
# Both run the same bf16 program per client; what differs is the reduction
# order of the round-end average (cross-chip all-reduce vs in-device mean)
# and XLA's fusion choices for a 1-client vs a 4-client block under the
# chip's default (bf16-pass) matmul precision. The CPU float32 test holds
# 1e-5 (tests/test_cohorts.py). Measured on 4 x v5e (PR 22): 4.2e-5 on the
# first round's loss and 2.2e-4 on the second, as the two trajectories
# drift apart; the bound is ten times that.
FOUR_CHIP_LOSS_RTOL = 2e-3


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def fail(reason: str):
    print(f"chip_smoke: FAILED: {reason}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond: bool, reason: str) -> None:
    if not cond:
        fail(reason)


def device_report() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def result_line(device: dict) -> str:
    return json.dumps({"ok": True, "device": device})


def train_argv(rows: int, clients: int, samples: int) -> list[str]:
    """What a user types after ``fedrec-run``: 2 rounds, B=64 per client, a
    snapshot at the end, validation on the last round."""
    return [
        "2", "64", "2", "--strategy", "param_avg", "--clients", str(clients),
        *FLAGSHIP,
        "--synthetic-news", str(rows), "--synthetic-train", str(samples),
        "--set", "train.eval_every=2",
    ]


def span_seconds(events: list[dict], name: str) -> list[float]:
    return [ev["dur"] / 1e6 for ev in events if ev.get("name") == name]


def run_trainer(argv: list[str], name: str, mesh=None):
    """``fedrec-run``'s own path: parse, build inputs, ``Trainer.run()``,
    with the run's snapshots under ``WORK_DIR / name``. Returns the trainer,
    its history, the run's events, the seconds the inputs took and the
    shapes of the last batch the step was fed."""
    import jax

    from fedrec_tpu.cli import run as run_cli
    from fedrec_tpu.obs import get_tracer
    from fedrec_tpu.train.trainer import Trainer

    argv = [*argv, "--set", f"train.snapshot_dir={WORK_DIR / name}"]
    t0 = time.perf_counter()
    inputs = run_cli.load_inputs(run_cli.build_parser().parse_args(argv))
    check(inputs is not None, "cli.run.load_inputs refused the arguments")
    cfg, data, token_states = inputs
    token_states.block_until_ready()
    t_inputs = time.perf_counter() - t0
    mark = get_tracer().event_count()
    trainer = Trainer(cfg, data, token_states, mesh=mesh)
    # the trainer holds its own committed copy of the table; ours would be a
    # second 5 GB on the chip for the whole run
    del inputs, token_states
    # what the step is fed, kept as shapes: check_table_at_rest lowers the
    # same program again
    step = trainer.train_step
    fed: dict = {}

    def keeping_shapes(state, batch, table):
        fed["batch"] = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            batch,
        )
        return step(state, batch, table)

    trainer.train_step = keeping_shapes
    history = trainer.run()
    trainer.train_step = step
    return trainer, history, trainer.tracer.events_since(mark), t_inputs, fed["batch"]


HLO_DTYPES = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}


def table_copies(hlo_text: str, table) -> list[str]:
    """The ``copy`` instructions of an optimised HLO whose result has the
    table's shape: a program that relays the whole table out again."""
    import re

    shape = f"{HLO_DTYPES[str(table.dtype)]}[{','.join(map(str, table.shape))}]"
    pattern = re.compile(r"= " + re.escape(shape) + r"\S* copy\(")
    return [ln.strip()[:200] for ln in hlo_text.splitlines() if pattern.search(ln)]


def check_table_at_rest(trainer, events: list[dict], batch, phase: str) -> dict:
    """The token-state table rests in the layout the step's gather reads,
    set once when the trainer took it, and the compiled step does not
    rewrite it (PERF.md section 5: a TPU's own layout for (N, 50, 768)
    costs every step a copy of the whole table)."""
    table = trainer.token_states
    commits = [ev.get("args", {}) for ev in events if ev.get("name") == "table_commit"]
    check(len(commits) == 1, f"{len(commits)} table_commit spans, expected 1")
    compiled = trainer.train_step.__wrapped__.lower(
        trainer.state, batch, table
    ).compile()
    stated = compiled.input_formats[0][2]
    copies = table_copies(compiled.as_text(), table)
    out = {
        "table_format": str(table.format),
        "table_commit": commits[0],
        "table_bytes_on_device": [
            int(s.data.on_device_size_in_bytes()) for s in table.addressable_shards
        ],
        "step_states_table_layout": str(stated.layout),
    }
    for k, v in out.items():
        say(f"{phase}.{k}", v)
    check(
        tuple(stated.layout.major_to_minor) == tuple(table.format.layout.major_to_minor),
        f"the step is compiled for {stated.layout}, the table rests in "
        f"{table.format.layout}",
    )
    check(not copies, f"the compiled step copies the whole table: {copies}")
    return out


def check_history(history, rounds: int) -> list[float]:
    losses = [float(h.train_loss) for h in history]
    check(len(losses) == rounds, f"{len(losses)} rounds ran, expected {rounds}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    return losses


def train_phase(argv: list[str]) -> dict:
    """8-client ``param_avg`` cohort on one device, two rounds, validation
    at the end. Returns what was observed."""
    trainer, history, events, t_inputs, batch = run_trainer(argv, "train")
    cfg = trainer.cfg
    losses = check_history(history, cfg.fed.rounds)
    check(losses[-1] < losses[0],
          f"loss did not fall over the run: {losses}")
    val = history[-1].val_metrics
    check(bool(val) and math.isfinite(val.get("auc", float("nan"))),
          f"no finite validation AUC at the last round: {val}")
    check(0.0 <= val["auc"] <= 1.0, f"AUC out of range: {val['auc']}")
    secs = span_seconds(events, "fed_round")
    table = trainer.token_states
    out = {
        "catalog_rows": int(table.shape[0]),
        "catalog_bytes_on_device": int(table.nbytes),
        "catalog_dtype": str(table.dtype),
        "clients": cfg.fed.num_clients,
        "batch_per_client": cfg.data.batch_size,
        "samples_per_round": trainer.num_local_samples,
        "steps_per_round": len(span_seconds(events, "dispatch")) // len(secs),
        "inputs_seconds": round(t_inputs, 2),
        "first_round_seconds_with_compile": round(secs[0], 2),
        "later_round_seconds": [round(s, 3) for s in secs[1:]],
        "round_losses": losses,
        "final_loss": losses[-1],
        "val_auc": val["auc"],
    }
    for k, v in out.items():
        say(f"train.{k}", v)
    out.update(check_table_at_rest(trainer, events, batch, "train"))
    return out


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _check_response(resp: dict, rid: int, top_k: int, num_news: int) -> None:
    check("error" not in resp, f"request {rid} answered with an error: {resp}")
    check(resp.get("id") == rid, f"response id {resp.get('id')} != {rid}")
    ids, scores = resp.get("ids"), resp.get("scores")
    check(isinstance(ids, list) and isinstance(scores, list)
          and len(ids) == len(scores) == top_k,
          f"request {rid}: malformed ids/scores: {resp}")
    check(all(isinstance(i, int) and 0 <= i < num_news for i in ids),
          f"request {rid}: ids outside the catalog: {ids}")
    check(all(math.isfinite(s) for s in scores)
          and scores == sorted(scores, reverse=True),
          f"request {rid}: scores not finite and descending: {scores}")
    check(resp.get("deadline_met") is True,
          f"request {rid} missed its deadline: {resp}")


async def _drive_server(service, host: str, port: int, n_requests: int) -> dict:
    """``serve_forever`` and its clients on one event loop: a stream of
    requests in concurrent waves, a hot swap of the store half way, a
    metrics round trip, then the signal a user would send."""
    import jax

    from fedrec_tpu.serving import serve_forever
    from fedrec_tpu.serving.client import ServingClientPool

    server = asyncio.ensure_future(
        serve_forever(service, host=host, port=port, metrics_every_s=3600.0)
    )
    pool = ServingClientPool(host, port, size=8, request_timeout_ms=120_000.0)
    gen0 = service.store.current()
    num_news, top_k = gen0.num_news, service.top_k
    his_len = service.batcher.history_len
    rng = np.random.default_rng(0)
    responses: list[dict] = []

    async def wave(first_id: int, n: int) -> None:
        reqs = [
            {"id": first_id + i, "deadline_ms": 120_000,
             "history": rng.integers(1, num_news, size=his_len).tolist()}
            for i in range(n)
        ]
        got = await asyncio.gather(*(pool.handle(r) for r in reqs))
        for r, resp in zip(reqs, got):
            _check_response(resp, r["id"], top_k, num_news)
        responses.extend(got)

    try:
        half = n_requests // 2
        for first in range(0, half, 8):
            await wave(first, min(8, half - first))
        # hot swap: a new generation of the same catalog size is published
        # on the live store; batches in flight keep the generation they took
        new_table = jax.random.normal(
            jax.random.PRNGKey(1), gen0.news_vecs.shape, gen0.news_vecs.dtype
        )
        gen1 = service.store.publish(
            new_table, gen0.user_params, source="chip_smoke-swap"
        )
        for first in range(half, n_requests, 8):
            await wave(first, min(8, n_requests - first))
        metrics = (await pool.admin("metrics", deadline_ms=30_000)).get("metrics")
        check(isinstance(metrics, dict) and "p50_ms" in metrics,
              f"malformed metrics reply: {metrics}")
    finally:
        await pool.close()
        # clean shutdown the way an operator does it: serve_forever turns
        # the signal into a drain
        os.kill(os.getpid(), signal.SIGINT)
        await asyncio.wait_for(server, timeout=60)

    gens = [r["generation"] for r in responses]
    check(gens[:half] == [gen0.generation] * half,
          f"pre-swap requests not served from generation {gen0.generation}")
    check(gens[half:] == [gen1.generation] * (n_requests - half),
          f"post-swap requests not served from generation {gen1.generation}")
    check(metrics.get("swap_count", 0) >= 1 and
          metrics.get("generation") == gen1.generation,
          f"metrics do not show the swap: {metrics}")
    lat = sorted(r["latency_ms"] for r in responses)
    return {
        "requests_answered": len(responses),
        "client_p50_ms": lat[len(lat) // 2],
        "client_max_ms": lat[-1],
        "server_p50_ms": metrics["p50_ms"],
        "generations_served": sorted(set(gens)),
        "swap_count": metrics["swap_count"],
    }


def serve_phase(argv: list[str], n_requests: int = 48) -> dict:
    """``fedrec-serve``'s own path: build + warm the service from CLI
    arguments, then listen and answer on an ephemeral local port (the
    arguments' ``--port`` is not used)."""
    from fedrec_tpu.cli import serve as serve_cli
    from fedrec_tpu.config import ExperimentConfig

    args = serve_cli.build_parser().parse_args(argv)
    cfg = ExperimentConfig()
    cfg.apply_overrides(args.overrides)
    t0 = time.perf_counter()
    service = serve_cli.build_service(args, cfg)
    check(service is not None, "cli.serve.build_service refused the arguments")
    out = {
        "catalog_rows": service.store.current().num_news,
        "build_and_warmup_seconds": round(time.perf_counter() - t0, 2),
        "warmup_seconds_per_bucket": {
            b: round(s, 3) for b, s in service.warmup_seconds.items()
        },
    }
    check(set(service.warmup_seconds) == set(service.batcher.batch_sizes),
          "warmup did not run every batch bucket")
    out.update(asyncio.run(
        _drive_server(service, args.host, _free_port(), n_requests)
    ))
    for k, v in out.items():
        say(f"serve.{k}", v)
    return out


def four_chip_phase(argv: list[str], rtol: float = FOUR_CHIP_LOSS_RTOL) -> dict:
    """One client per chip over the ``clients`` mesh against the same
    clients, same seed, as a cohort on one device."""
    import jax

    from fedrec_tpu.parallel import client_mesh

    trainer, history, events, _, batch = run_trainer(argv, "per_chip")
    cfg = trainer.cfg
    n = cfg.fed.num_clients
    check(trainer.mesh.size == n,
          f"mesh has {trainer.mesh.size} devices for {n} clients")
    losses = check_history(history, cfg.fed.rounds)
    leaves = jax.tree_util.tree_leaves(
        (trainer.state.user_params, trainer.state.news_params)
    )
    homes = {
        frozenset(s.device.id for s in x.addressable_shards) for x in leaves
    }
    check(all(len(h) == n for h in homes),
          f"client state is not spread over {n} distinct devices: {homes}")
    check(len(trainer.token_states.sharding.device_set) == n,
          "the token-state table is not resident on every chip")
    host = [np.asarray(x) for x in leaves]
    check(all((x == x[0:1]).all() for x in host),
          "clients differ after the round-end sync")
    check_table_at_rest(trainer, events, batch, "four_chip")
    auc = history[-1].val_metrics.get("auc")
    secs = span_seconds(events, "fed_round")
    # release the per-chip run's state and replicated table before the
    # comparison run builds its own on device 0
    del trainer, leaves, history
    gc.collect()

    ref, ref_history, ref_events, _, _ = run_trainer(
        argv, "cohort", mesh=client_mesh(n, max_devices=1)
    )
    check(ref.mesh.size == 1, "the comparison run is not on one device")
    ref_losses = check_history(ref_history, cfg.fed.rounds)
    ref_auc = ref_history[-1].val_metrics.get("auc")
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    out = {
        "clients": n,
        "devices_holding_client_state": n,
        "per_chip_losses": losses,
        "cohort_losses": ref_losses,
        "loss_rel_diff": rel,
        "loss_rtol": rtol,
        "per_chip_auc": auc,
        "cohort_auc": ref_auc,
        "per_chip_round_seconds": [round(s, 3) for s in secs],
        "cohort_round_seconds": [
            round(s, 3) for s in span_seconds(ref_events, "fed_round")
        ],
    }
    for k, v in out.items():
        say(f"four_chip.{k}", v)
    check(max(rel) <= rtol,
          f"per-chip and cohort losses differ by {max(rel):.3e} > {rtol}")
    return out


def _is_oom(e: Exception) -> bool:
    return "RESOURCE_EXHAUSTED" in str(e) or isinstance(e, MemoryError)


def train_phase_at_largest_catalog() -> dict:
    """The MIND-small catalog, or the largest power of two of rows that
    host and device memory allow, never below MIN_CATALOG_ROWS. Widths are
    never cut."""
    rows = CATALOG_ROWS
    while True:
        try:
            return train_phase(train_argv(rows, 8, TRAIN_SAMPLES))
        except Exception as e:  # noqa: BLE001 — re-raised unless out of memory
            if not _is_oom(e) or rows // 2 < MIN_CATALOG_ROWS:
                raise
            reason = str(e).splitlines()[0][:300]
        gc.collect()
        say("train.catalog_cut",
            f"{rows} rows ran out of memory ({reason}); retrying at {rows // 2}")
        rows //= 2


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    device = device_report()
    if device["platform"] != "tpu":
        fail(f"needs a TPU, but JAX's first device is {device['platform']!r} "
             f"({device['kind']}); this script never runs on another backend")
    if device["count"] != args.chips:
        fail(f"--chips {args.chips} needs exactly {args.chips} device(s), "
             f"JAX reports {device['count']}")
    cache_dir = enable_compile_cache()  # before the first compile
    cache_events = {"hits": 0, "misses": 0}

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    say("versions", {p: metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu", "flax")})
    say("device", device)
    say("compile_cache_dir", cache_dir)
    shutil.rmtree(WORK_DIR, ignore_errors=True)

    if args.chips == 4:
        four_chip_phase(train_argv(CATALOG_ROWS, 4, TRAIN_SAMPLES // 2))
    else:
        train_phase_at_largest_catalog()
        # the phases share one process and one chip: everything the trainer
        # phase held on the device (the 5 GB table, eight clients' state, its
        # compiled programs' buffers) is unreferenced once train_phase has
        # returned, and is collected here, before the server phase publishes
        # its own catalog
        gc.collect()
        serve_phase(["--synthetic", str(CATALOG_ROWS)])
    say("peak_bytes_in_use",
        [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()])
    say("compile_cache_events", cache_events)
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
