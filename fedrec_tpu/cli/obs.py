"""``fedrec-obs`` — render and replay a run's observability artifacts.

Consumes the artifact trio every instrumented entry point writes
(Trainer with ``obs.dir``, ``fedrec-serve --obs-dir``,
``benchmarks/serve_load.py --obs-dir``):

* ``metrics.jsonl``   — MetricLogger records + registry snapshots
  (plus ``metrics.jsonl.1`` when ``obs.jsonl_max_mb`` rotated the log;
  rotated files are read first, in order)
* ``trace.json``      — Chrome-trace/Perfetto host spans
* ``prometheus.txt``  — final text exposition

plus the flight-recorder dump (``flightrec/``) the training-health
sentry writes on a non-finite/divergence trigger.

Subcommands:

  fedrec-obs report <dir | metrics.jsonl> [--trace trace.json] [--json]
      One-page run report: round throughput, loss trajectory, serve
      p50/p99, prefetch stalls, epsilon-spent trajectory, health +
      recompile counters, cap-overflow counts, host-span summary.

  fedrec-obs prom <dir | metrics.jsonl>
      Re-render the LAST registry snapshot in the event log as a
      Prometheus text exposition (for a run that predates, or lost, its
      prometheus.txt).

  fedrec-obs quality <dir | metrics.jsonl> [--json]
      Model-quality report off the last registry snapshot: every eval
      slice's AUC/MRR/NDCG + impression count (ascending AUC, so the
      worst stratum leads), the calibration reliability table + ECE,
      score separation, per-client AUC with the quality-outlier count,
      and the serving store's last pre-swap drift verdict.  Exit 2 when
      the run carried no quality telemetry (obs.quality.enabled=false).

  fedrec-obs perf <dir | metrics.jsonl> [--json]
      Performance report off the obs.perf telemetry: last-round
      throughput/MFU/HBM fraction, the per-round roofline-verdict
      counts (canonical verdict strings), the host phase table
      (batch_build/h2d/dispatch/aggregate/eval), the MFU trend over the
      last rounds, HBM bytes by component, the compile-cost
      (``cost_analysis``) table, and pointers to captured profiler
      traces.  Exit 2 when the run carried no perf telemetry
      (obs.perf.enabled=false).

  fedrec-obs replay <dir | flightrec dir> [--max-steps N] [--json]
      Re-execute the flight-recorder dump's recorded steps on CPU from
      the dumped round-entry state — deterministically confirming (and
      bisecting to) the step that went non-finite.  Exit 0 when the
      dump's trigger is reproduced, 1 when it is not.

  fedrec-obs fleet <dir> [--json]
      Fleet-wide report over a directory of ``worker_*`` obs dirs (the
      shared ``obs.dir`` of an elastic/coordinator run, or a collector's
      ``--telemetry-dir``): per-worker identity/epoch/rounds, the
      membership timeline, per-round straggler/critical-path attribution
      (which worker gated each round's barrier, and in which phase), and
      per-worker DCN bytes.  A single obs dir degrades to one worker.

  fedrec-obs fleet-trace <dir> [-o merged.json]
      ONE merged Chrome/Perfetto trace over every worker: a track per
      worker, clocks aligned via the shared round barrier (each
      ``fed_round`` N is a common event), membership epoch changes /
      lease expiries / joins / quarantines rendered as instants.

  fedrec-obs alerts <dir | metrics.jsonl> [--json]
      Alert timeline + active table off the ``{"kind":"alert"}``
      lifecycle records (one obs dir, or every ``worker_*`` log under a
      shared/collector dir — the fleet rules' ``worker_fleet`` included).
      Exit 1 while any alert is still firing at the end of the log(s),
      0 after everything resolved — scriptable as a gate.

  fedrec-obs tail <dir | metrics.jsonl> [--once] [--interval S]
      Live-follow the event log(s), printing each alert transition as it
      lands (rotation-aware).  ``--once`` prints the transitions already
      recorded and exits with the ``alerts`` exit-code contract.

``report``/``prom``/``fleet``/``fleet-trace`` import no JAX — usable on
any box the artifacts were copied to; ``replay`` imports JAX lazily (and
pins ``JAX_PLATFORMS=cpu`` unless the environment already chose a
platform).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

from fedrec_tpu.obs.registry import snapshot_to_prometheus
from fedrec_tpu.obs.report import (
    build_report,
    load_jsonl,
    load_trace,
    render_text,
)


def _fail(msg: str) -> int:
    print(f"fedrec-obs: {msg}", file=sys.stderr)
    return 2


def _resolve(path_arg: str) -> tuple[Path, Path | None]:
    """A directory (the obs.dir layout) or an explicit metrics.jsonl path
    -> (metrics_path, trace_path_or_None)."""
    p = Path(path_arg)
    if p.is_dir():
        metrics = p / "metrics.jsonl"
        trace = p / "trace.json"
        return metrics, (trace if trace.exists() else None)
    return p, None


def _load_event_log(metrics_path: Path):
    """load_jsonl with operator-grade failure messages instead of
    tracebacks; returns (records, snapshots) or an int exit code."""
    if not metrics_path.exists() and not Path(str(metrics_path) + ".1").exists():
        parent = metrics_path.parent
        hint = (
            " (the directory does not exist — check the obs dir path)"
            if not parent.exists()
            else " (directory exists but holds no event log — was the run "
                 "started with obs.dir / --obs-dir?)"
        )
        return _fail(f"no event log at {metrics_path}{hint}")
    try:
        return load_jsonl(metrics_path)
    except OSError as e:
        return _fail(f"cannot read {metrics_path}: {e}")


def _cmd_report(args) -> int:
    metrics_path, trace_path = _resolve(args.path)
    if args.trace:
        trace_path = Path(args.trace)
        if not trace_path.exists():
            return _fail(f"no trace file at {trace_path}")
    loaded = _load_event_log(metrics_path)
    if isinstance(loaded, int):
        return loaded
    records, snapshots = loaded
    trace_events = None
    if trace_path:
        try:
            trace_events = load_trace(trace_path)
        except (OSError, json.JSONDecodeError, KeyError) as e:
            print(f"fedrec-obs: skipping unreadable trace {trace_path}: {e}",
                  file=sys.stderr)
    report = build_report(records, snapshots, trace_events)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render_text(report))
    return 0


def _cmd_prom(args) -> int:
    metrics_path, _ = _resolve(args.path)
    loaded = _load_event_log(metrics_path)
    if isinstance(loaded, int):
        return loaded
    _, snapshots = loaded
    if not snapshots:
        return _fail(
            f"no registry snapshot in {metrics_path} (the run may have "
            "died before its first obs.snapshot_every round)"
        )
    # the SAME renderer the live {"cmd": "prometheus"} endpoint uses —
    # offline output cannot drift from the wire exposition
    print(snapshot_to_prometheus(snapshots[-1]), end="")
    return 0


# ----------------------------------------------------------------- quality
def _cmd_quality(args) -> int:
    from fedrec_tpu.obs.report import quality_detail_from_snapshot

    metrics_path, _ = _resolve(args.path)
    loaded = _load_event_log(metrics_path)
    if isinstance(loaded, int):
        return loaded
    _, snapshots = loaded
    if not snapshots:
        return _fail(
            f"no registry snapshot in {metrics_path} (the run may have "
            "died before its first obs.snapshot_every round)"
        )
    detail = quality_detail_from_snapshot(snapshots[-1])
    if not detail:
        return _fail(
            f"no quality telemetry in {metrics_path} — was the run "
            "started with obs.quality.enabled=1 (sliced eval; on "
            "fedrec-serve it also arms the drift probe, "
            "obs.quality.probe_users)?"
        )
    if args.json:
        print(json.dumps(detail, indent=2))
        return 0
    lines = ["# fedrec_tpu quality report", ""]
    slices = detail.get("slices")
    if slices:
        lines.append("## Eval slices (last eval, ascending AUC)")
        lines.append(
            f"{'slice':<20} {'auc':>8} {'mrr':>8} {'ndcg5':>8} "
            f"{'ndcg10':>8} {'count':>7}"
        )
        ordered = sorted(
            slices.items(), key=lambda kv: kv[1].get("auc", float("inf"))
        )
        for name, m in ordered:
            lines.append(
                f"{name:<20} {m.get('auc', float('nan')):>8.4f} "
                f"{m.get('mrr', float('nan')):>8.4f} "
                f"{m.get('ndcg5', float('nan')):>8.4f} "
                f"{m.get('ndcg10', float('nan')):>8.4f} "
                f"{int(m.get('count', 0)):>7}"
            )
        if detail.get("slices_skipped"):
            lines.append(
                f"(+ {int(detail['slices_skipped'])} slice evaluations "
                "skipped: empty/degenerate strata)"
            )
        lines.append("")
    if "ece" in detail or "score_separation" in detail:
        lines.append("## Scores & calibration")
        if "score_separation" in detail:
            dp = (
                f", d'={detail['score_dprime']:.3f}"
                if "score_dprime" in detail else ""
            )
            lines.append(
                f"separation: {detail['score_separation']:.4f}{dp}"
            )
        if "ece" in detail:
            lines.append(f"ece: {detail['ece']:.4f}")
        for row in detail.get("calibration", []):
            if row.get("count"):
                lines.append(
                    f"  bin {row['bin']}: conf="
                    f"{row.get('confidence', float('nan')):.3f} "
                    f"acc={row.get('accuracy', float('nan')):.3f} "
                    f"n={int(row['count'])}"
                )
        lines.append("")
    if "client_auc" in detail:
        lines.append("## Per-client AUC")
        lines.append(", ".join(
            f"c{c}={v:.4f}" for c, v in detail["client_auc"].items()
        ))
        if detail.get("quality_outlier_client_evals"):
            lines.append(
                "quality-outlier client-evals: "
                f"{int(detail['quality_outlier_client_evals'])}"
            )
        lines.append("")
    drift = detail.get("drift")
    if drift:
        lines.append("## Serving drift (last pre-swap probe)")
        if "score_shift_mean" in drift:
            lines.append(
                f"|Δscore| mean={drift['score_shift_mean']:.4g} "
                f"max={drift.get('score_shift_max', 0):.4g}"
            )
        if "topk_jaccard" in drift:
            lines.append(
                f"top-k jaccard={drift['topk_jaccard']:.3f} "
                f"(churn {drift.get('rank_churn', 0):.3f}) over "
                f"{int(drift.get('checks', 0))} check(s)"
            )
        lines.append("")
    print("\n".join(lines))
    return 0


# -------------------------------------------------------------------- perf
def _cmd_perf(args) -> int:
    from fedrec_tpu.obs.report import perf_detail_from_snapshot

    metrics_path, trace_path = _resolve(args.path)
    loaded = _load_event_log(metrics_path)
    if isinstance(loaded, int):
        return loaded
    records, snapshots = loaded
    if not snapshots:
        return _fail(
            f"no registry snapshot in {metrics_path} (the run may have "
            "died before its first obs.snapshot_every round)"
        )
    detail = perf_detail_from_snapshot(snapshots[-1])
    if not detail:
        return _fail(
            f"no perf telemetry in {metrics_path} — was the run started "
            "with obs.perf.enabled=1 (live MFU/roofline gauges, "
            "compile-cost telemetry, HBM attribution)?"
        )
    # the MFU/verdict trend rides the per-round MetricLogger records
    trend = [
        (r.get("round"), r.get("perf.mfu"), r.get("perf.samples_per_sec"),
         r.get("perf.verdict"))
        for r in records
        if "perf.samples_per_sec" in r and "round" in r
    ]
    captures = [
        r for r in records
        if r.get("kind") in ("perf_capture", "profile_trace")
    ]
    phases = None
    if trace_path:
        try:
            from fedrec_tpu.obs.fleet import ROUND_PHASES
            from fedrec_tpu.obs.report import span_summary

            # the same rollup build_report's span table uses, filtered to
            # the round phases — the two views cannot drift on one trace
            phases = span_summary(load_trace(trace_path), names=ROUND_PHASES)
        except (OSError, json.JSONDecodeError, KeyError) as e:
            print(f"fedrec-obs: skipping unreadable trace {trace_path}: {e}",
                  file=sys.stderr)
            phases = None
    if args.json:
        doc = dict(detail)
        if trend:
            doc["trend"] = [
                {"round": r, "mfu": m, "samples_per_sec": s, "verdict": v}
                for r, m, s, v in trend
            ]
        if captures:
            # NOT "captures": perf_detail_from_snapshot already uses that
            # key for the numeric counter — a consumer must never see the
            # key's type flip between runs
            doc["capture_records"] = captures
        if phases:
            doc["phases"] = phases
        print(json.dumps(doc, indent=2))
        return 0
    lines = ["# fedrec_tpu perf report", ""]
    head = []
    if "samples_per_sec" in detail:
        head.append(f"throughput: {detail['samples_per_sec']:.1f} samples/s")
    if "mfu" in detail:
        head.append(f"mfu: {detail['mfu']:.4f}")
    if "hbm_fraction" in detail:
        head.append(f"hbm: {detail['hbm_fraction']:.3f} of peak")
    if head:
        lines.append(", ".join(head) + " (last round)")
    if "verdict_rounds" in detail:
        from fedrec_tpu.obs.perf import ROOFLINE_VERDICTS

        lines.append("")
        lines.append("## Roofline verdicts")
        for key, n in sorted(detail["verdict_rounds"].items()):
            lines.append(
                f"  {int(n):>4} round(s)  {ROOFLINE_VERDICTS.get(key, key)}"
            )
    if phases:
        lines.append("")
        lines.append("## Phase table (host spans)")
        lines.append(f"{'phase':<14} {'count':>7} {'total_ms':>10} {'mean_ms':>9}")
        for name, p in phases.items():
            lines.append(
                f"{name:<14} {p['count']:>7} {p['total_ms']:>10.1f} "
                f"{p['mean_ms']:>9.2f}"
            )
    if trend:
        lines.append("")
        lines.append("## Trend (last 8 rounds)")
        for r, m, s, v in trend[-8:]:
            mfu_s = f" mfu={m:.4f}" if m is not None else ""
            lines.append(
                f"  r{int(r)}: {s:.1f} samples/s{mfu_s}"
                + (f" [{v}]" if v else "")
            )
    if "hbm_components" in detail:
        lines.append("")
        lines.append("## HBM by component (descending)")
        for name, v in sorted(
            detail["hbm_components"].items(), key=lambda kv: -kv[1]
        ):
            lines.append(f"  {name:<12} {v / (1024 * 1024):>10.1f} MB")
    if "compile_cost" in detail:
        lines.append("")
        lines.append("## Compile cost (xla cost_analysis)")
        lines.append(
            f"{'fn':<20} {'gflops':>10} {'MB_accessed':>12} {'intensity':>10}"
        )
        for fn, c in detail["compile_cost"].items():
            gf = c.get("flops")
            mb = c.get("bytes_accessed")
            ai = c.get("arithmetic_intensity")
            lines.append(
                f"{fn:<20} "
                f"{(gf / 1e9 if gf is not None else float('nan')):>10.2f} "
                f"{(mb / 1e6 if mb is not None else float('nan')):>12.2f} "
                f"{(ai if ai is not None else float('nan')):>10.1f}"
            )
    if captures:
        lines.append("")
        lines.append("## Captured traces")
        for c in captures:
            tag = c.get("kind")
            rnd = c.get("round")
            lines.append(
                f"  {tag}" + (f" r{int(rnd)}" if rnd is not None else "")
                + f": {c.get('logdir')}"
            )
    print("\n".join(lines))
    return 0


# ------------------------------------------------------------------- fleet
def _load_fleet(path_arg: str):
    from fedrec_tpu.obs.fleet import load_fleet_dir

    try:
        return load_fleet_dir(path_arg)
    except FileNotFoundError as e:
        return _fail(str(e))


def _cmd_fleet(args) -> int:
    from fedrec_tpu.obs.fleet import build_fleet_report, render_fleet_text

    workers = _load_fleet(args.path)
    if isinstance(workers, int):
        return workers
    report = build_fleet_report(workers)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(render_fleet_text(report))
    return 0


def _cmd_fleet_trace(args) -> int:
    from fedrec_tpu.obs.fleet import build_fleet_trace

    workers = _load_fleet(args.path)
    if isinstance(workers, int):
        return workers
    doc = build_fleet_trace(workers)
    out = Path(args.out) if args.out else Path(args.path) / "fleet_trace.json"
    try:
        with open(out, "w") as f:
            json.dump(doc, f)
    except OSError as e:
        return _fail(f"cannot write merged trace to {out}: {e}")
    n_ev = sum(1 for e in doc["traceEvents"] if e.get("ph") != "M")
    print(
        f"merged {n_ev} events from {len(doc['otherData']['workers'])} "
        f"worker track(s) -> {out} (load in https://ui.perfetto.dev)"
    )
    return 0


# ------------------------------------------------------------------ alerts
def _alert_sources(path_arg: str) -> list[tuple[str | None, Path]]:
    """-> [(worker_or_None, metrics_path)]: every ``worker_*`` log under
    a shared/collector dir, or the single obs-dir / file log."""
    p = Path(path_arg)
    if p.is_dir():
        wdirs = sorted(d for d in p.glob("worker_*") if d.is_dir())
        if wdirs:
            return [
                (d.name[len("worker_"):], d / "metrics.jsonl")
                for d in wdirs
            ]
        return [(None, p / "metrics.jsonl")]
    return [(None, p)]


def _load_alert_logs(path_arg: str):
    """-> (timeline, active) across every source log, or an int exit
    code.  Alert keys are scoped per source so two workers' ``slo:x``
    lifecycles never collapse into one."""
    from fedrec_tpu.obs.watch import active_alerts, alert_records

    sources = _alert_sources(path_arg)
    timeline: list[dict] = []
    active: list[dict] = []
    found_log = False
    for worker, mp in sources:
        if not mp.exists() and not Path(str(mp) + ".1").exists():
            continue
        try:
            records, _ = load_jsonl(mp)
        except OSError as e:
            return _fail(f"cannot read {mp}: {e}")
        found_log = True
        recs = alert_records(records)
        if worker is not None:
            for r in recs:
                r.setdefault("labels", {}).setdefault("worker", worker)
        timeline.extend(recs)
        active.extend(active_alerts(recs))
    if not found_log:
        return _fail(
            f"no event log under {path_arg} (was the run started with "
            "obs.dir / --obs-dir, and obs.slo.enabled to record alerts?)"
        )
    timeline.sort(key=lambda r: r.get("ts", 0.0))
    return timeline, active


def _format_alert_line(rec: dict) -> str:
    import time as _time

    ts = _time.strftime("%H:%M:%S", _time.localtime(rec.get("ts", 0.0)))
    worker = (rec.get("labels") or {}).get("worker")
    wtxt = f" worker={worker}" if worker is not None else ""
    return (
        f"{ts} {rec.get('event', '?').upper():<8} "
        f"{rec.get('severity', '?'):<8} {rec.get('key', '?')}{wtxt}"
        f"  {rec.get('summary', '')}"
    )


def _cmd_alerts(args) -> int:
    loaded = _load_alert_logs(args.path)
    if isinstance(loaded, int):
        return loaded
    timeline, active = loaded
    if args.json:
        print(json.dumps({"timeline": timeline, "active": active}, indent=2))
        return 1 if active else 0
    print("# Alert timeline")
    if timeline:
        for rec in timeline:
            print(_format_alert_line(rec))
    else:
        print("(no alert transitions recorded)")
    print()
    print("# Active alerts")
    if active:
        for rec in sorted(active, key=lambda r: r.get("ts", 0.0)):
            print(_format_alert_line(rec))
    else:
        print("(none — everything resolved)")
    # the scriptable contract: firing -> 1, quiet -> 0 (errors exit 2)
    return 1 if active else 0


def _cmd_tail(args) -> int:
    import time as _time

    if args.once:
        loaded = _load_alert_logs(args.path)
        if isinstance(loaded, int):
            return loaded
        timeline, active = loaded
        for rec in timeline:
            print(_format_alert_line(rec))
        return 1 if active else 0
    sources = _alert_sources(args.path)
    offsets: dict[Path, int] = {}
    print(
        f"fedrec-obs: following {len(sources)} log(s) under {args.path} "
        "(ctrl-c to stop)",
        file=sys.stderr,
    )
    try:
        while True:
            for worker, mp in sources:
                try:
                    size = mp.stat().st_size
                except OSError:
                    continue
                pos = offsets.get(mp, 0)
                if size < pos:
                    pos = 0  # the log rotated under us: re-read from top
                if size == pos:
                    continue
                try:
                    with open(mp, "rb") as f:
                        f.seek(pos)
                        chunk = f.read()
                except OSError:
                    continue
                # consume only COMPLETE lines; a partially-flushed tail
                # stays unread until the writer finishes it
                nl = chunk.rfind(b"\n")
                if nl < 0:
                    continue
                offsets[mp] = pos + nl + 1
                for line in chunk[: nl + 1].splitlines():
                    try:
                        rec = json.loads(line)
                    except (json.JSONDecodeError, UnicodeDecodeError):
                        continue
                    if rec.get("kind") != "alert":
                        continue
                    if worker is not None:
                        rec.setdefault("labels", {}).setdefault(
                            "worker", worker
                        )
                    print(_format_alert_line(rec), flush=True)
            _time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


# ------------------------------------------------------------------ replay
def _resolve_flightrec(path_arg: str) -> Path | None:
    """obs dir / flightrec dir / manifest.json path -> flightrec dir."""
    p = Path(path_arg)
    if p.name == "manifest.json":
        p = p.parent
    if (p / "manifest.json").exists():
        return p
    if (p / "flightrec" / "manifest.json").exists():
        return p / "flightrec"
    return None


def _cmd_replay(args) -> int:
    flight_dir = _resolve_flightrec(args.path)
    if flight_dir is None:
        return _fail(
            f"no flight-recorder dump under {args.path} — expected "
            "<obs.dir>/flightrec/manifest.json (dumps are written when the "
            "health sentry trips with obs.dir set and "
            "obs.health.flight_recorder on)"
        )
    try:
        manifest = json.loads((flight_dir / "manifest.json").read_text())
    except (OSError, json.JSONDecodeError) as e:
        return _fail(f"unreadable manifest at {flight_dir}/manifest.json: {e}")
    if manifest.get("kind") != "flight_recorder_dump":
        return _fail(f"{flight_dir}/manifest.json is not a flight-recorder dump")
    if not manifest.get("records"):
        return _fail(
            "the dump holds no batch records (the trigger fired before any "
            "step was recorded); nothing to replay"
        )
    if manifest.get("state_file") is None:
        return _fail("the dump holds no state checkpoint; cannot replay")
    if manifest.get("table_file") is None:
        return _fail(
            "the dump omitted the feature table "
            f"(skipped at {manifest.get('table_skipped_mb', '?')} MB — raise "
            "obs.health.dump_table_max_mb); cannot replay"
        )

    # replay runs on CPU wherever the operator is, unless they chose
    # a platform explicitly — set BEFORE the first jax import
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    import jax
    from flax import serialization

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.fed.strategies import get_strategy
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.parallel.mesh import client_mesh, shard_fed_batch
    from fedrec_tpu.train.state import init_client_state, replicate_state
    from fedrec_tpu.train.step import (
        build_fed_train_step,
        build_param_sync,
        commit_token_table,
    )

    cfg = ExperimentConfig.from_dict(manifest["config"])
    if cfg.fed.seq_shards > 1:
        return _fail(
            "the dump was recorded with fed.seq_shards > 1; sequence-"
            "parallel steps need a multi-device mesh and cannot replay on "
            "one CPU device"
        )
    # replay is per-batch and file-free — neutralize every knob that
    # would donate a reused batch or write artifacts
    cfg.train.donate_batch = False
    cfg.data.prefetch_batches = 0
    cfg.obs.dir = ""
    cfg.obs.health.sentry = True  # the sentinel IS the replay's verdict

    # one CPU device hosting the whole client cohort: cohort vmapping makes
    # the collective math identical to the original packing (train.step)
    mesh = client_mesh(cfg.fed.num_clients, cfg.fed.mesh_axis, max_devices=1)
    model = NewsRecommender(cfg.model)
    strategy = get_strategy(cfg.fed.strategy)
    template = replicate_state(
        init_client_state(
            model, cfg, jax.random.PRNGKey(0),
            int(manifest["num_news"]), int(manifest["title_len"]),
        ),
        cfg.fed.num_clients,
        jax.random.PRNGKey(1),
    )
    try:
        state = serialization.from_bytes(
            template, (flight_dir / manifest["state_file"]).read_bytes()
        )
    except (OSError, ValueError) as e:
        return _fail(f"cannot restore the dumped state: {e}")
    # a joint step's token states go where the step states they rest; the
    # other modes' tables come back as they are (train/step.py)
    table, _ = commit_token_table(
        np.load(flight_dir / manifest["table_file"]), mesh
    )

    step = build_fed_train_step(
        model, cfg, strategy, mesh, mode=manifest.get("mode") or None
    )
    sync = (
        build_param_sync(cfg, mesh, strategy)
        if strategy.sync_params_every_round
        else None
    )
    from fedrec_tpu.train.step import compressed_sync_active

    # codec syncs (fed.dcn_compress != none) compress ROUND DELTAS: track
    # each round's entry params so a dump that spans rounds (an older
    # run's) replays the exact compressed trajectory. Host copies — the
    # step donates state buffers.
    sync_takes_entry = sync is not None and compressed_sync_active(cfg, strategy)

    def _entry_copy(st):
        return jax.tree_util.tree_map(
            np.asarray, (st.user_params, st.news_params)
        )

    entry = _entry_copy(state) if sync_takes_entry else None
    weights = {int(k): np.asarray(v) for k, v in manifest.get("weights", {}).items()}

    records = sorted(manifest["records"], key=lambda r: (r["round"], r["step"]))
    trigger = manifest.get("trigger", {})
    max_steps = args.max_steps or len(records)
    out_rows: list[dict] = []
    first_bad: dict | None = None
    prev_round = records[0]["round"]
    for i, rec in enumerate(records[:max_steps]):
        if rec["round"] != prev_round:
            if sync is not None and prev_round in weights:
                # re-apply the recorded round-end participation sync so a
                # dump that spans rounds replays the exact trajectory
                if sync_takes_entry:
                    state = sync(state, np.asarray(weights[prev_round]), *entry)
                else:
                    state = sync(state, np.asarray(weights[prev_round]))
            if sync_takes_entry:
                entry = _entry_copy(state)
            prev_round = rec["round"]
        try:
            batch = dict(np.load(flight_dir / rec["file"]))
        except OSError as e:
            return _fail(f"cannot read batch record {rec['file']}: {e}")
        state, metrics = step(state, shard_fed_batch(mesh, batch, cfg), table)
        row = {
            "round": rec["round"],
            "step": rec["step"],
            "loss": float(np.asarray(metrics["mean_loss"]).reshape(-1)[0]),
            "grad_norm_max": float(np.max(np.asarray(metrics["health.grad_norm"]))),
            "update_norm_max": float(
                np.max(np.asarray(metrics["health.update_norm"]))
            ),
            "param_norm_max": float(
                np.max(np.asarray(metrics["health.param_norm"]))
            ),
            "nonfinite": int(np.asarray(metrics["health.nonfinite"]).sum()),
        }
        out_rows.append(row)
        if not args.json:
            print(
                f"round {row['round']} step {row['step']}: "
                f"loss={row['loss']:.6g} grad={row['grad_norm_max']:.4g} "
                f"update={row['update_norm_max']:.4g} "
                f"param={row['param_norm_max']:.4g} "
                f"nonfinite={row['nonfinite']}"
            )
        if row["nonfinite"] > 0:
            first_bad = row
            break

    reproduced = first_bad is not None
    verdict = {
        "trigger": trigger,
        "steps_replayed": len(out_rows),
        "reproduced_nonfinite": reproduced,
        "first_nonfinite": first_bad,
        "rows": out_rows,
    }
    if args.json:
        print(json.dumps(verdict, indent=2))
    elif reproduced:
        print(
            f"REPRODUCED: non-finite step at round {first_bad['round']} "
            f"step {first_bad['step']} (trigger was "
            f"{trigger.get('kind')} at round {trigger.get('round')} "
            f"step {trigger.get('step')})"
        )
    elif trigger.get("kind") == "nonfinite":
        print(
            "NOT REPRODUCED: no replayed step went non-finite — platform "
            "numerics may differ from the recording host, or the ring "
            "dropped the poisoning step (ring_complete="
            f"{manifest.get('ring_complete')})"
        )
    else:
        print(
            f"no non-finite step (trigger was {trigger.get('kind')!r}); "
            "the norm trajectory above is the evidence"
        )
    if trigger.get("kind") == "nonfinite":
        return 0 if reproduced else 1
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="fedrec-obs", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)
    rep = sub.add_parser("report", help="render the one-page run report")
    rep.add_argument("path", help="obs dir or metrics.jsonl path")
    rep.add_argument("--trace", default=None, help="explicit trace.json path")
    rep.add_argument("--json", action="store_true",
                     help="machine-readable report instead of text")
    rep.set_defaults(fn=_cmd_report)
    prom = sub.add_parser(
        "prom", help="Prometheus exposition from the last registry snapshot"
    )
    prom.add_argument("path", help="obs dir or metrics.jsonl path")
    prom.set_defaults(fn=_cmd_prom)
    qu = sub.add_parser(
        "quality",
        help="model-quality report: per-slice eval metrics, calibration, "
             "per-client AUC, serving drift (obs.quality telemetry)",
    )
    qu.add_argument("path", help="obs dir or metrics.jsonl path")
    qu.add_argument("--json", action="store_true",
                    help="machine-readable detail instead of text")
    qu.set_defaults(fn=_cmd_quality)
    pf = sub.add_parser(
        "perf",
        help="performance report: MFU trend + roofline verdicts, phase "
             "table, HBM attribution, compile-cost table (obs.perf "
             "telemetry)",
    )
    pf.add_argument("path", help="obs dir or metrics.jsonl path")
    pf.add_argument("--json", action="store_true",
                    help="machine-readable detail instead of text")
    pf.set_defaults(fn=_cmd_perf)
    rp = sub.add_parser(
        "replay",
        help="re-execute a flight-recorder dump on CPU to confirm/bisect",
    )
    rp.add_argument("path", help="obs dir, flightrec dir, or manifest.json")
    rp.add_argument("--max-steps", type=int, default=0,
                    help="replay at most N recorded steps (0 = all)")
    rp.add_argument("--json", action="store_true",
                    help="machine-readable verdict")
    rp.set_defaults(fn=_cmd_replay)
    fl = sub.add_parser(
        "fleet",
        help="fleet-wide report over worker_* obs dirs (straggler/"
             "critical-path attribution, membership timeline, DCN bytes)",
    )
    fl.add_argument("path", help="shared obs dir / collector dir / one "
                                 "worker's obs dir")
    fl.add_argument("--json", action="store_true",
                    help="machine-readable report instead of text")
    fl.set_defaults(fn=_cmd_fleet)
    ft = sub.add_parser(
        "fleet-trace",
        help="merge every worker's spans into ONE clock-aligned "
             "Chrome/Perfetto trace with per-worker tracks",
    )
    ft.add_argument("path", help="shared obs dir / collector dir / one "
                                 "worker's obs dir")
    ft.add_argument("-o", "--out", default=None,
                    help="output path (default <dir>/fleet_trace.json)")
    ft.set_defaults(fn=_cmd_fleet_trace)
    al = sub.add_parser(
        "alerts",
        help="alert timeline + active table off the {\"kind\":\"alert\"} "
             "records; exit 1 while any alert is still firing",
    )
    al.add_argument("path", help="obs dir, collector/shared dir, or "
                                 "metrics.jsonl path")
    al.add_argument("--json", action="store_true",
                    help="machine-readable {timeline, active} instead of "
                         "text (same exit-code contract)")
    al.set_defaults(fn=_cmd_alerts)
    tl = sub.add_parser(
        "tail",
        help="live-follow the event log(s), printing alert transitions "
             "as they land",
    )
    tl.add_argument("path", help="obs dir, collector/shared dir, or "
                                 "metrics.jsonl path")
    tl.add_argument("--once", action="store_true",
                    help="print the recorded transitions and exit with "
                         "the alerts exit-code contract")
    tl.add_argument("--interval", type=float, default=1.0,
                    help="poll interval seconds (default 1.0)")
    tl.set_defaults(fn=_cmd_tail)
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
