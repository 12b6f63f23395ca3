"""One run of one training cell whose news tower reads token ids through the
gated grouped-query trunk with window and full layers, trained in loop over
texts longer than its window (``kind: training_rounds_tokens_window``):
set-up, warm-up, the measured window, the traced rounds, the comparison with
the reference, the result line.

The path is ``harness_training_rounds_tokens.py``'s, bound to this trunk as
``harness_training_rounds_tokens_latent.py`` binds it to its own: the
trunk's sizes off the configuration file by ``corpus_window.trunk_of``, the
first weights by ``corpus_window.make_weights``, the reference
``reference_window_trunk.follow_steps``, the scopes this trunk names. It
fills the same ``run`` keys, so the accepted metrics' readers work
unchanged, with ``trunk`` (the trunk's sizes), ``routing`` (the program's
routing gauges), ``attention_share`` (the program's gauge of the score
elements its blocked core computes, by kind of layer), ``hbm_bytes_per_s``
(the chip's published bandwidth) and ``trace["scopes"]`` (device time by
named scope).
"""

from __future__ import annotations

import gc
import json
import math
import shutil
import time
from pathlib import Path

import numpy as np

from chipbench import (
    cells, check, corpus, corpus_window, flops, peaks, peaks_memory, trace_reduce, trace_scopes,
)
from chipbench.harness_training_rounds import (  # noqa: F401 - print_result: run.py's
    CLOCK_SPAN, FOLLOWED_STEPS, WORK_DIR, CacheCounter, _end_to_end, _per_layer,
    _reduce_traced_rounds, _spans_ns, _start_trace, _stop_trace, build_trainer, check_batches,
    device_report, place_compile_cache, print_result, require_chips,
)
from chipbench.harness_training_rounds_tokens import HostStepRecorder, _routing, say
from chipbench.harness_training_rounds_tokens_latent import routed_numbers

# the trunk group's keys (corpus_window.trunk_of) that the program's
# WindowTrunkConfig spells otherwise
PROGRAM_FIELD = {
    "layers": "n_layers", "dense_layers": "n_dense_layers", "kv_heads": "n_kv_heads",
    "experts": "n_experts",
}
ROPE_FIELD = {
    "factor": "rope_factor", "original_max_position_embeddings": "rope_original_max",
    "beta_fast": "rope_beta_fast", "beta_slow": "rope_beta_slow",
    "attention_factor": "rope_attention_factor",
}
SCOPES = ("trunk_embed", "window_attention", "attention_core", "dense_ffn", "shared_expert",
          "moe_route", "moe_experts", "moe_combine", "text_head")
# texts a block of the reference: one text's five layers of heads x 1,024 x
# 1,024 float32 scores and probabilities with nothing rematerialised are
# 3 GB, beside 10.7 GB of parameters, gradient and Adam's moments
REFERENCE_BLOCK_ROWS = 1
SHARE_GAUGE = "trunk.attention_scores_computed_share"


def build_config(config: dict, trunk: dict, seed: int):
    """``ExperimentConfig`` with the configuration file's overrides; the
    program's own seeds follow ``--seed``. Refuses a file whose published
    keys and overrides build different models. Runs before anything touches
    the device: a program without the trunk fails here, at once."""
    from fedrec_tpu.config import ExperimentConfig

    cfg = ExperimentConfig()
    cfg.apply_overrides(list(config["overrides"]))
    w = corpus.seed_words(seed, 6)
    cfg.data.seed, cfg.train.seed = w[4], w[5]
    from fedrec_tpu.models.window_trunk import window_trunk_config_from

    built = window_trunk_config_from(cfg.model)
    s = config["shapes"]
    stated = {
        "clients": cfg.fed.num_clients, "batch_per_client": cfg.data.batch_size,
        "candidates": 1 + cfg.data.npratio, "history": cfg.data.max_his_len,
        "title_len": cfg.data.max_title_len, "bert_hidden": built.dim,
        "attn_hidden": built.dim // 2, "news_dim": cfg.model.news_dim,
        "heads": cfg.model.num_heads, "head_dim": cfg.model.head_dim,
        "query_dim": cfg.model.query_dim,
    }
    differs = {k: (s[k], v) for k, v in stated.items() if s[k] != v}
    layers = range(built.n_layers)
    built_trunk = {k: getattr(built, PROGRAM_FIELD.get(k, k)) for k in trunk
                   if k not in ("rope", "layer_kinds", "heads_per_layer", "shared_dim")}
    built_trunk["layer_kinds"] = [built.kind(i) for i in layers]
    built_trunk["heads_per_layer"] = [built.heads(built.kind(i)) for i in layers]
    built_trunk["shared_dim"] = built.n_shared_experts * built.expert_dim
    differs.update({f"trunk.{k}": (trunk[k], v) for k, v in built_trunk.items() if trunk[k] != v})
    differs.update({f"trunk.rope.{k}": (trunk["rope"][k], getattr(built, f))
                    for k, f in ROPE_FIELD.items() if trunk["rope"][k] != getattr(built, f)})
    if differs:
        raise ValueError(f"the configuration's published keys differ from what its overrides build: {differs}")
    return cfg


def _attention_share(trainer) -> dict | None:
    """The blocked core's gauge the program publishes, as the last round set
    it: {kind of layer: share}; None from a program that publishes none."""
    cell = trainer.registry.snapshot()["metrics"].get(SHARE_GAUGE)
    if not cell or not cell["values"]:
        return None
    return {v["labels"]["kind"]: float(v["value"]) for v in cell["values"]}


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, need_tpu: bool = True, bench_dir: Path = cells.BENCH_DIR,
             cell: dict | None = None, keep_trace: Path | None = None) -> dict:
    """One run; returns the result line as a dict (arguments as
    ``harness_training_rounds.run_cell``)."""
    cell = cell or cells.load_cell(root, workload, bench_dir)
    config, traffic, shapes = cell["config"], cell["traffic"], cell["config"]["shapes"]
    trunk = corpus_window.trunk_of(config)
    cfg = build_config(config, trunk, seed)
    import jax
    import jax.numpy as jnp

    device = require_chips(cell["chips"]) if need_tpu else device_report()
    cache_dir = place_compile_cache() if need_tpu else None
    cache = CacheCounter()
    say(f"{workload} seed {seed} on {device}; compile cache {cache_dir}")

    # ---- set-up: inputs from the seed, the trainer, one warm-up round
    from fedrec_tpu.data.mind import MindData

    corp = corpus.make_click_corpus(traffic, shapes, seed)
    tokens = corpus_window.make_token_table(traffic, shapes, trunk, seed)
    data = MindData(tokens, corp["nid2index"], corp["train_samples"], [])
    say("corpus and token table made")
    # the first weights wait on the host while the trainer is built: beside
    # the trainer's own state a second copy on the chip does not fit
    user0, news0 = jax.tree_util.tree_map(np.asarray, corpus_window.make_weights(shapes, trunk, seed))
    say("first weights made")
    trainer = build_trainer(cfg, data, None)
    say("trainer built")
    trainer.set_global_params(user0, news0)
    n_clients = int(shapes["clients"])
    steps_per_round = traffic["samples_per_round"] // flops.samples_per_step(shapes)
    say("first weights set")
    recorder = HostStepRecorder(trainer, FOLLOWED_STEPS)
    warm = trainer.train_round(0)
    jax.block_until_ready(trainer.state)
    recorder.remove()
    say("warm-up round done")
    misses_in_setup = cache.misses
    setup_s = time.perf_counter() - t_start

    # ---- the window: whole rounds until --seconds have passed
    tracer = trainer.tracer
    mark = tracer.event_count()
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
    memory = [d.memory_stats() or {} for d in jax.devices()]
    peak_bytes = max(
        m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0) for m in memory
    )
    say(f"memory_stats of device 0 after the window: {memory[0]}")
    samples = rounds * steps_per_round * flops.samples_per_step(shapes)
    routing, share = _routing(trainer), _attention_share(trainer)
    say(f"routing gauges of the last round: {routing}; {SHARE_GAUGE} {share}")

    # ---- free the program, then the reference over the recorded steps
    module_names = dict(config.get("device_modules", {}))
    lr = (float(cfg.optim.user_lr), float(cfg.optim.news_lr))
    if lr[0] != lr[1]:
        raise ValueError("the reference follows one learning rate for both towers")
    if trainer.strategy.sync_params_every_round:
        raise ValueError("this harness follows one client; a round-end sync is not compared")
    program = recorder.to_host(user0, news0, n_clients)
    # the registry's collectors may hold the trainer: let its state go first
    trainer.state = None
    del trainer, recorder, warm, data
    gc.collect()
    say("program's state freed: " + json.dumps({k: (jax.devices()[0].memory_stats() or {}).get(k)
                                                 for k in ("bytes_in_use", "bytes_reserved")}))
    numbers, compared = _compare_with_reference(
        program, shapes, trunk, user0, news0, jnp.asarray(tokens, jnp.int32), lr[0])
    numbers["bad_batch_rows"] = float(check_batches(program["batches"], corp, shapes))
    numbers["rounds_failed"] = float(failed)
    numbers["nonfinite_losses"] = float(sum(not math.isfinite(x) for x in losses))
    numbers["compiled_in_window"] = float(compiled_in_window)
    say(f"all numbers read: {json.dumps(numbers)}")
    correct, beside = check.verdict(numbers, cell["limits"])
    correct = correct and rounds >= 1
    say(check.leaf_table(compared))

    on_tpu = device["platform"] == "tpu"
    run = {
        "cell": cell, "shapes": shapes, "trunk": trunk, "device": device,
        "setup_s": setup_s, "window_s": window_s, "rounds": rounds, "samples": samples,
        "steps_per_round": steps_per_round, "peak_bytes": peak_bytes,
        "cache": {"hits": cache.hits, "misses_in_setup": misses_in_setup,
                  "misses_in_window": compiled_in_window},
        "spans": _spans_ns(events, clock_ns), "module_names": module_names,
        "peaks": peaks.chip_peaks(device["kind"]) if on_tpu else None,
        "hbm_bytes_per_s": peaks_memory.hbm_bytes_per_s(device["kind"]) if on_tpu else None,
        "trace": None, "distinct_news_share": corpus.distinct_share(program["batches"]),
        "routing": routing, "attention_share": share,
    }
    if traced is not None:
        kept = keep_trace or trace_dir.with_name(trace_dir.name + "-kept")
        run["trace"], run["traced_spans"] = _reduce_traced_rounds(
            trace_dir, traced, run["spans"], module_names, kept)
        run["trace"]["scopes"] = trace_scopes.reduce_scopes(kept / "trace.xplane.pb", SCOPES)
        say(f"device seconds by scope in the traced rounds: {json.dumps(run['trace']['scopes'])}")
        if keep_trace is None:
            shutil.rmtree(kept, ignore_errors=True)

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
            "device_scopes": trace_reduce.top(run["trace"]["scopes"]),
            "idle_gaps": trace_reduce.top(run["trace"]["idle_by_host_activity"]),
        }
    say("window: " + json.dumps({"rounds": rounds, "seconds": window_s, "round_seconds": round_s,
                                 "first_loss": losses[0], "last_loss": losses[-1],
                                 "distinct_news_share": run["distinct_news_share"]}))
    line["compared"] = beside
    for name, c in beside.items():
        say(f"compared {name}: {c['value']} (limit {c['limit']})")
    return line


def _compare_with_reference(program: dict, shapes: dict, trunk: dict, user0, news0, tokens,
                            lr: float) -> tuple[dict, dict]:
    """The reference over the recorded steps, and the gaps to what the
    program produced. Runs once the program's state is freed."""
    from chipbench import reference_window_trunk

    t_ref = time.perf_counter()
    ref = reference_window_trunk.follow_steps(
        shapes, trunk, user0, news0, tokens, program["batches"], lr,
        block_rows=REFERENCE_BLOCK_ROWS)
    compared = check.compare_steps(program, ref)
    say(f"reference and comparison took {time.perf_counter() - t_ref:.1f} s; "
        f"worst leaves {compared['worst_leaf']}")
    return {**compared["numbers"], **routed_numbers(compared)}, compared
