"""One run of one training cell: set-up, warm-up, the measured window, the
traced rounds, the comparison with the reference, the result line.

The path is ``fedrec-run``'s own (``chip_smoke.run_trainer`` proved it on
the chip in PR 22) with a clock, a seed and a trace: build the
``ExperimentConfig`` from the configuration file's overrides, make the
corpus, the table and the first weights from ``--seed``, construct
``Trainer(cfg, data, table)``, then drive ``Trainer.train_round`` for
consecutive rounds. Set-up builds ONE trainer; its warm-up round goes
through the same call and feed as the window's rounds and is the one the
reference follows; the same object then runs the window.
"""

from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from chipbench import cells, check, corpus, flops, peaks, trace_reduce

FOLLOWED_STEPS = 3
CLOCK_SPAN = "chipbench_clock"
WORK_DIR = "chipbench_out"                 # traces, under the checkout; in .gitignore


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def device_report() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def require_chips(chips: int) -> dict:
    device = device_report()
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chipbench: needs a TPU, JAX's first device is {device['platform']!r} "
            f"({device['kind']}); no result"
        )
    if device["count"] != chips:
        raise SystemExit(
            f"chipbench: the cell asks for {chips} chip(s), JAX reports "
            f"{device['count']}; no result"
        )
    return device


def place_compile_cache() -> str:
    """JAX's persistent cache at the fixed place the program's entry points
    use (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), with
    every program kept, however fast it compiled."""
    import jax

    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CacheCounter:
    """Counts JAX's compilation-cache events, as ``chip_smoke.py`` does."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def build_config(config: dict, seed: int):
    """``ExperimentConfig`` with the configuration file's overrides; the
    program's own seeds follow ``--seed``."""
    from fedrec_tpu.config import ExperimentConfig

    cfg = ExperimentConfig()
    cfg.apply_overrides(list(config["overrides"]))
    w = corpus.seed_words(seed, 6)
    cfg.data.seed, cfg.train.seed = w[4], w[5]
    s = config["shapes"]
    stated = {
        "clients": cfg.fed.num_clients, "batch_per_client": cfg.data.batch_size,
        "candidates": 1 + cfg.data.npratio, "history": cfg.data.max_his_len,
        "title_len": cfg.data.max_title_len, "bert_hidden": cfg.model.bert_hidden,
        "attn_hidden": cfg.model.bert_hidden // 2, "news_dim": cfg.model.news_dim,
        "heads": cfg.model.num_heads, "head_dim": cfg.model.head_dim,
        "query_dim": cfg.model.query_dim,
    }
    differs = {k: (s[k], v) for k, v in stated.items() if s[k] != v}
    if differs:
        raise ValueError(f"the configuration's shapes differ from what its overrides build: {differs}")
    return cfg


def build_trainer(cfg, data, table):
    """The program under test, constructed as ``fedrec-run`` constructs it."""
    from fedrec_tpu.train.trainer import Trainer

    return Trainer(cfg, data, table)


def _client_trees(user_tree, news_tree, n_clients: int) -> list:
    """Stacked (K, ...) device trees -> per client {"user", "news"} on the host."""
    import jax

    u = jax.tree_util.tree_map(np.asarray, user_tree)
    n = jax.tree_util.tree_map(np.asarray, news_tree)
    pick = lambda t, c: jax.tree_util.tree_map(lambda x: x[c], t)  # noqa: E731
    return [{"user": pick(u, c), "news": pick(n, c)} for c in range(n_clients)]


def _first_moment(opt_state):
    import jax

    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(x, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state in the optimizer state, found {len(found)}")
    return found[0].mu


class StepRecorder:
    """Wraps the trainer's compiled step and sync for the warm-up round:
    keeps what the first steps were fed and what they returned, copied on
    the device before the next step donates the state."""

    def __init__(self, trainer, steps: int):
        import jax
        import jax.numpy as jnp

        self.trainer, self.steps = trainer, steps
        self.batches: list = []
        self.losses: list = []
        self.first_mu = None
        self.params_after = None
        self.sync_before = self.sync_after = None
        self._step, self._sync = trainer.train_step, trainer.param_sync
        copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731

        def step(state, batch, table):
            new_state, metrics = self._step(state, batch, table)
            i = len(self.losses)
            if i < self.steps:
                self.batches.append({k: batch[k] for k in ("candidates", "history")})
                self.losses.append(metrics["loss"])
                if i == 0:
                    self.first_mu = copy((_first_moment(new_state.opt_user),
                                          _first_moment(new_state.opt_news)))
                if i == self.steps - 1:
                    self.params_after = copy((new_state.user_params, new_state.news_params))
            return new_state, metrics

        def sync(state, *rest):
            out = self._sync(state, *rest)
            if self.sync_before is None:
                self.sync_before = copy((state.user_params, state.news_params))
                self.sync_after = copy((out.user_params, out.news_params))
            return out

        trainer.train_step, trainer.param_sync = step, sync

    def remove(self) -> None:
        self.trainer.train_step, self.trainer.param_sync = self._step, self._sync

    def to_host(self, user0, news0, n_clients: int) -> dict:
        import jax

        if len(self.losses) < self.steps or self.params_after is None:
            raise RuntimeError(f"the warm-up round ran {len(self.losses)} steps, fewer than {self.steps}")
        sub = lambda a, b: jax.tree_util.tree_map(lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64)[None], a, b)  # noqa: E731
        out = {
            "batches": [{k: np.asarray(v) for k, v in b.items()} for b in self.batches],
            "losses": np.stack([np.asarray(l, np.float64).reshape(-1) for l in self.losses]),
            "first_mu": _client_trees(*self.first_mu, n_clients),
            "deltas": _client_trees(sub(self.params_after[0], user0),
                                    sub(self.params_after[1], news0), n_clients),
        }
        if self.sync_before is not None:
            out["sync_before"] = _client_trees(*self.sync_before, n_clients)
            out["sync_after"] = _client_trees(*self.sync_after, n_clients)
        return out


def check_batches(recorded: list, corp: dict, shapes: dict) -> int:
    """How many rows of the recorded batches are not rows of the corpus (the
    positive in slot 0, negatives from its pool, its own history)."""
    by_history = {row.tobytes(): i for i, row in enumerate(corp["history"].astype(np.int32))}
    bad = 0
    for b in recorded:
        cand = b["candidates"].reshape(-1, shapes["candidates"])
        his = b["history"].reshape(-1, shapes["history"]).astype(np.int32)
        for c_row, h_row in zip(cand, his):
            i = by_history.get(h_row.tobytes())
            if i is None or c_row[0] != corp["pos"][i] or not set(c_row[1:]) <= set(corp["negs"][i]):
                bad += 1
    return bad


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, need_tpu: bool = True, bench_dir: Path = cells.BENCH_DIR,
             cell: dict | None = None, keep_trace: Path | None = None) -> dict:
    """One run; returns the result line as a dict. ``cell`` and ``keep_trace``
    are for ``tools/record_fixture.py`` (a cell with another traffic file; a
    copy of the trace and of what the reduction was given)."""
    cell = cell or cells.load_cell(root, workload, bench_dir)
    config, traffic, shapes = cell["config"], cell["traffic"], cell["config"]["shapes"]
    import jax
    import jax.numpy as jnp

    device = require_chips(cell["chips"]) if need_tpu else device_report()
    cache_dir = place_compile_cache() if need_tpu else None
    cache = CacheCounter()
    say(f"{workload} seed {seed} on {device}; compile cache {cache_dir}")

    # ---- set-up: inputs from the seed, the trainer, one warm-up round
    from fedrec_tpu.data.mind import MindData

    cfg = build_config(config, seed)
    corp = corpus.make_click_corpus(traffic, shapes, seed)
    data = MindData(corp["news_tokens"], corp["nid2index"], corp["train_samples"], [])
    table = corpus.make_token_states(traffic, shapes, seed, jnp.dtype(cfg.model.dtype),
                                     corp["popular_rows"])
    user0, news0 = corpus.make_weights(shapes, seed)
    trainer = build_trainer(cfg, data, table)
    trainer.set_global_params(user0, news0)
    n_clients = int(shapes["clients"])
    steps_per_round = traffic["samples_per_round"] // flops.samples_per_step(shapes)
    recorder = StepRecorder(trainer, FOLLOWED_STEPS)
    warm = trainer.train_round(0)
    jax.block_until_ready(trainer.state)
    recorder.remove()
    misses_in_setup = cache.misses
    setup_s = time.perf_counter() - t_start

    # ---- the window: whole rounds until --seconds have passed
    tracer = trainer.tracer
    mark = tracer.event_count()
    # one zero-length span at a known perf_counter reading puts the tracer's
    # own epoch on the host clock
    clock_ns = time.perf_counter_ns()
    tracer.add_span(CLOCK_SPAN, 0.0)
    n_trace = int(traffic.get("traced_rounds", 2))
    trace_from = 1 if trace else None    # trace from the window's 2nd round
    trace_dir = root / WORK_DIR / f"trace-{workload}-{seed}"
    traced = None
    rounds, failed, losses, round_s = 0, 0, [float(warm.train_loss)], []
    t0 = time.perf_counter()
    t_end = t0
    while time.perf_counter() - t0 < seconds:
        r = rounds + 1
        if trace_from is not None and rounds == trace_from and traced is None:
            traced = _start_trace(trace_dir)
        try:
            result = trainer.train_round(r)
            jax.block_until_ready(trainer.state)
        except Exception as e:  # noqa: BLE001 - a failed round is counted, then the run ends
            say(f"round {r} failed: {type(e).__name__}: {e}")
            failed += 1
            break
        round_s.append(time.perf_counter() - t_end)
        t_end = time.perf_counter()
        rounds += 1
        losses.append(float(result.train_loss))
        if traced is not None and "t1_ns" not in traced and rounds == trace_from + n_trace:
            _stop_trace(traced)
    if traced is not None and "t1_ns" not in traced:
        _stop_trace(traced)
    window_s = t_end - t0
    compiled_in_window = cache.misses - misses_in_setup
    events = tracer.events_since(mark)
    # the runtime keeps a compiled program's temporaries in a reservation of
    # its own, outside ``peak_bytes_in_use``: the chip's peak is both together
    memory = [d.memory_stats() or {} for d in jax.devices()]
    peak_bytes = max(
        m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0) for m in memory
    )
    say(f"memory_stats of device 0 after the window: {memory[0]}")
    samples = rounds * steps_per_round * flops.samples_per_step(shapes)

    # ---- free the program, then the reference over the recorded steps
    # names of the XLA modules the compiled programs show under in a trace
    # (``jit_<function>`` of train/step.py), stated by the configuration
    module_names = dict(config.get("device_modules", {}))
    syncs = bool(trainer.strategy.sync_params_every_round)
    lr = (float(cfg.optim.user_lr), float(cfg.optim.news_lr))
    if lr[0] != lr[1]:
        raise ValueError("the reference follows one learning rate for both towers")
    program = recorder.to_host(user0, news0, n_clients)
    del trainer, recorder, warm, data
    gc.collect()
    numbers, compared = _compare_with_reference(
        program, shapes, user0, news0, table, lr[0], syncs)
    numbers["bad_batch_rows"] = float(check_batches(program["batches"], corp, shapes))
    numbers["rounds_failed"] = float(failed)
    numbers["nonfinite_losses"] = float(sum(not math.isfinite(x) for x in losses))
    numbers["compiled_in_window"] = float(compiled_in_window)
    say(f"all numbers read: {json.dumps(numbers)}")
    correct, beside = check.verdict(numbers, cell["limits"])
    correct = correct and rounds >= 1
    say(check.leaf_table(compared))

    run = {
        "cell": cell, "shapes": shapes, "device": device,
        "setup_s": setup_s, "window_s": window_s, "rounds": rounds, "samples": samples,
        "steps_per_round": steps_per_round, "peak_bytes": peak_bytes,
        "cache": {"hits": cache.hits, "misses_in_setup": misses_in_setup,
                  "misses_in_window": compiled_in_window},
        "spans": _spans_ns(events, clock_ns), "module_names": module_names,
        "peaks": peaks.chip_peaks(device["kind"]) if device["platform"] == "tpu" else None,
        "trace": None, "distinct_news_share": corpus.distinct_share(program["batches"]),
    }
    if traced is not None:
        run["trace"], run["traced_spans"] = _reduce_traced_rounds(
            trace_dir, traced, run["spans"], module_names, keep_trace)

    metrics = _end_to_end(run) if not trace else _per_layer(run)
    line = {
        "correct": bool(correct), "attempted": rounds + failed, "failed": failed,
        "metrics": metrics,
        "device": {**device, "memory_peak_bytes": int(peak_bytes)},
    }
    if run["trace"] is not None:
        line["device"]["busy_s"] = run["trace"]["busy_s"]
        line["device"]["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {
            "device_ops": trace_reduce.top(run["trace"]["ops"]),
            "idle_gaps": trace_reduce.top(run["trace"]["idle_by_host_activity"]),
        }
    say("window: " + json.dumps({"rounds": rounds, "seconds": window_s, "round_seconds": round_s,
                                 "first_loss": losses[0], "last_loss": losses[-1],
                                 "distinct_news_share": run["distinct_news_share"]}))
    line["compared"] = beside
    for name, c in beside.items():
        say(f"compared {name}: {c['value']} (limit {c['limit']})")
    return line


def _compare_with_reference(program: dict, shapes: dict, user0, news0, table,
                            lr: float, syncs: bool) -> tuple[dict, dict]:
    """The reference over the recorded steps, and the gaps to what the
    program produced. Runs once the program's state is freed."""
    from chipbench import reference

    t_ref = time.perf_counter()
    ref = reference.follow_steps(shapes, user0, news0, table, program["batches"], lr)
    compared = check.compare_steps(program, ref)
    numbers = dict(compared["numbers"])
    if syncs:
        before = program["sync_before"]
        numbers["sync_gap"] = check.sync_gap(before, program["sync_after"])
        # what a sync that handed every client the first client's parameters
        # would read: the upper reading of sync_gap, printed, never limited
        say("sync_gap of a broadcast in place of the mean: "
            f"{check.sync_gap(before, [before[0]] * len(before))}")
    say(f"reference and comparison took {time.perf_counter() - t_ref:.1f} s; "
        f"worst leaves {compared['worst_leaf']}")
    return numbers, compared


def _reduce_traced_rounds(trace_dir: Path, traced: dict, spans: list[dict],
                          module_names: dict, keep_trace: Path | None) -> tuple[dict, list]:
    """The trace of the traced rounds, reduced, with the program's spans
    that lie inside it; the trace's files are removed."""
    xplane = trace_reduce.find_xplane(trace_dir)
    inside = [{k: s[k] for k in ("name", "start_ns", "end_ns")} for s in spans
              if s["start_ns"] >= traced["t0_ns"] and s["end_ns"] <= traced["t1_ns"]]
    if keep_trace is not None:
        keep_trace.mkdir(parents=True, exist_ok=True)
        shutil.copy(xplane, keep_trace / "trace.xplane.pb")
        (keep_trace / "host_spans.json").write_text(json.dumps(inside))
    reduced = trace_reduce.reduce_trace(trace_reduce.read_trace(xplane), inside)
    for role, name in module_names.items():
        if name not in reduced["modules"]:
            say(f"the trace holds no module {name!r} ({role}); it holds "
                f"{sorted(reduced['modules'])}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced, inside


def _start_trace(trace_dir: Path) -> dict:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(trace_reduce.MARK_BEGIN, t_ns=t):
        pass
    return {"t0_ns": t}


def _stop_trace(traced: dict) -> None:
    import jax

    t = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(trace_reduce.MARK_END, t_ns=t):
        pass
    traced["t1_ns"] = t
    jax.profiler.stop_trace()


def _spans_ns(events: list[dict], clock_ns: int) -> list[dict]:
    """The program's tracer events (microseconds from its own epoch) as spans
    on ``perf_counter_ns``, by the harness's clock span."""
    at = next(ev["ts"] for ev in events if ev.get("name") == CLOCK_SPAN)
    tracer_t0_ns = clock_ns - int(at * 1e3)
    out = []
    for ev in events:
        if ev.get("ph") != "X" or ev.get("name") == CLOCK_SPAN:
            continue
        start = tracer_t0_ns + int(ev["ts"] * 1e3)
        out.append({"name": ev["name"], "start_ns": start,
                    "end_ns": start + int(ev["dur"] * 1e3), "args": ev.get("args", {})})
    return out


def _end_to_end(run: dict) -> dict:
    values = {
        "train_samples_per_s": run["samples"] / run["window_s"] if run["rounds"] else None,
        "setup_s": run["setup_s"],
    }
    out = {}
    for m in run["cell"]["end_to_end"]:
        if m["name"] not in values:
            raise KeyError(f"this harness does not measure the end-to-end metric {m['name']!r}")
        if values[m["name"]] is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def _per_layer(run: dict) -> dict:
    out = {}
    for m in run["cell"]["per_layer"]:
        value = cells.load_reader(run["cell"]["bench_dir"], m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def print_result(line: dict) -> None:
    """The one last line of standard output (``compared`` is its last key)."""
    print(json.dumps(line), flush=True)
