"""Long-lived serving driver: ``fedrec-serve``.

Where ``fedrec-recommend`` is a one-shot batch job (restore -> encode ->
emit JSONL -> exit), this starts the online subsystem
(:mod:`fedrec_tpu.serving`): a TCP/JSON-lines server whose embedding
store can be hot-swapped from new training checkpoints while requests
are in flight (``{"cmd": "refresh", ...}`` on any connection).

Usage:
  # real artifacts (reference UserData layout + a training snapshot dir):
  fedrec-serve --data-dir UserData --snapshot-dir snapshots --port 7607

  # synthetic catalog, no artifacts needed (smoke / load testing):
  fedrec-serve --synthetic 65000 --port 7607

  # million-item mode: two-stage retrieval kicks in past --exact-threshold
  fedrec-serve --synthetic 1000000 --clusters 1024 --n-probe 64
"""

from __future__ import annotations

import argparse
import asyncio
import sys
from pathlib import Path

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=7607)
    p.add_argument("--top-k", type=int, default=10)
    p.add_argument("--keep-history", action="store_true",
                   help="allow already-clicked news in responses")
    # ---- batching
    p.add_argument("--batch-sizes", default="1,8,32,128",
                   help="fixed padded batch buckets (comma-separated)")
    p.add_argument("--flush-ms", type=float, default=2.0,
                   help="max coalescing wait for the oldest pending request")
    p.add_argument("--max-queue", type=int, default=1024,
                   help="queue-depth backpressure threshold")
    # ---- retrieval
    p.add_argument("--clusters", type=int, default=0,
                   help="k-means coarse clusters (0 = exact full-catalog scoring)")
    p.add_argument("--n-probe", type=int, default=8)
    p.add_argument("--exact-threshold", type=int, default=4096,
                   help="catalogs at/below this size always use exact scoring")
    p.add_argument("--shard-store", action="store_true",
                   help="row-shard the embedding store across this "
                        "process's devices (fedrec_tpu.shard): per-device "
                        "HBM holds catalog/devices rows, the exact scorer "
                        "reads the sharded table transparently. Exact "
                        "retrieval only (incompatible with --clusters)")
    # ---- model / data sources
    p.add_argument("--synthetic", type=int, default=0, metavar="N",
                   help="serve a random N-item catalog with fresh-init params "
                        "(no artifacts needed; scores are meaningless)")
    p.add_argument("--data-dir", default=None,
                   help="reference UserData layout (required unless "
                        "--synthetic)")
    p.add_argument("--snapshot-dir", default=None)
    p.add_argument("--token-states", default=None,
                   help="(N, L, bert_hidden) .npy of cached trunk states")
    p.add_argument("--metrics-every", type=float, default=30.0,
                   help="seconds between metric JSON lines on stdout")
    p.add_argument("--obs-dir", default=None,
                   help="write observability artifacts here (metrics.jsonl "
                        "event log, trace.json host spans, prometheus.txt "
                        "exposition); render with fedrec-obs report")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="SECTION.KEY=VALUE")
    return p


def _synthetic_service(args, cfg):
    """Random catalog + fresh-init user params: every serving code path
    (batching, retrieval, swap) without any training artifact."""
    import jax
    import jax.numpy as jnp

    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.serving import EmbeddingStore, ServingService

    model = NewsRecommender(cfg.model)
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        rng.standard_normal((args.synthetic, cfg.model.news_dim)), jnp.float32
    )
    dummy = jnp.zeros((1, cfg.data.max_his_len, cfg.model.news_dim), jnp.float32)
    user_params = model.init(
        jax.random.PRNGKey(0), dummy, method=NewsRecommender.encode_user
    )["params"]["user_encoder"]
    store = EmbeddingStore()
    if args.shard_store:
        from fedrec_tpu.serving.store import publish_sharded

        publish_sharded(store, table, user_params, source="synthetic")
    else:
        store.publish(table, user_params, source="synthetic")
    return _service(args, cfg, model, store, id_map=None)


def _checkpoint_service(args, cfg):
    from fedrec_tpu.data import load_mind_artifacts
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.serving.store import EmbeddingStore, publish_from_checkpoint

    if args.data_dir is None:
        print("[serve] ERROR: --data-dir is required unless --synthetic",
              file=sys.stderr)
        return None
    snap_dir = args.snapshot_dir or cfg.train.snapshot_dir
    data = load_mind_artifacts(args.data_dir)
    token_path = args.token_states or str(Path(args.data_dir) / "token_states.npy")
    if not Path(token_path).exists():
        print(f"[serve] ERROR: no token states at {token_path}; export them or "
              "pass --token-states (or use --synthetic for a smoke catalog)",
              file=sys.stderr)
        return None
    token_states = np.load(token_path)
    index2nid = {i: n for n, i in data.nid2index.items()}
    valid = np.zeros(data.num_news, bool)
    valid[[i for i in index2nid if 0 <= i < data.num_news]] = True
    model = NewsRecommender(cfg.model)
    store = EmbeddingStore()
    gen = publish_from_checkpoint(
        store, model, snap_dir, token_states, valid_mask=valid,
        dtype=cfg.model.dtype, shard=args.shard_store,
    )
    print(f"[serve] generation 0 from {gen.source} round {gen.round}",
          file=sys.stderr)
    return _service(args, cfg, model, store, id_map=index2nid)


def _service(args, cfg, model, store, id_map):
    from fedrec_tpu.serving import ServingService

    return ServingService(
        model,
        store,
        history_len=cfg.data.max_his_len,
        top_k=args.top_k,
        exclude_history=not args.keep_history,
        batch_sizes=tuple(int(b) for b in args.batch_sizes.split(",")),
        flush_ms=args.flush_ms,
        max_queue=args.max_queue,
        num_clusters=args.clusters,
        n_probe=args.n_probe,
        exact_threshold=args.exact_threshold,
        id_map=id_map,
    )


def build_service(args, cfg):
    """The warmed-up :class:`ServingService` for parsed arguments, or
    ``None`` (after printing why) when they name no servable catalog."""
    if args.shard_store and args.clusters:
        print(
            "[serve] ERROR: --shard-store pairs with exact retrieval only "
            "(the k-means member lists are host-built per cluster); drop "
            "--clusters or --shard-store",
            file=sys.stderr,
        )
        return None
    service = (
        _synthetic_service(args, cfg) if args.synthetic
        else _checkpoint_service(args, cfg)
    )
    if service is None:
        return None
    if cfg.obs.quality.enabled and cfg.obs.quality.probe_users > 0:
        # pre-swap drift probe: every {"cmd":"refresh"} hot-swap scores
        # the pinned probe set against both generations first, so a bad
        # table push surfaces serve.drift_* before it serves traffic
        service.store.enable_drift_probe(
            num_probes=cfg.obs.quality.probe_users,
            topk=cfg.obs.quality.probe_topk,
            seed=cfg.obs.quality.seed,
        )
    service.warmup()  # compile every bucket before accepting traffic
    return service


def main(argv: list[str] | None = None) -> int:
    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = build_parser().parse_args(argv)

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.serving import serve_forever
    from fedrec_tpu.utils.logging import MetricLogger

    cfg = ExperimentConfig()
    cfg.apply_overrides(args.overrides)
    service = build_service(args, cfg)
    if service is None:
        return 2
    import os as _os

    from fedrec_tpu.obs import ensure_fleet_identity, get_tracer

    # spans are only worth their memory when something will save them:
    # without --obs-dir this process never writes trace.json, so recording
    # per-request spans would just fill the bounded buffer with dead weight
    get_tracer().enabled = bool(args.obs_dir)
    # fleet correlation keys: serving spans/snapshots join the fleet's
    # training artifacts by worker id (FEDREC_WORKER_ID when the operator
    # co-locates a server with a training worker)
    ensure_fleet_identity(worker=_os.environ.get("FEDREC_WORKER_ID") or "serve")
    jsonl = None
    if args.obs_dir:
        from pathlib import Path as _Path

        _Path(args.obs_dir).mkdir(parents=True, exist_ok=True)
        jsonl = str(_Path(args.obs_dir) / "metrics.jsonl")
    logger = MetricLogger(jsonl_path=jsonl, jsonl_max_mb=cfg.obs.jsonl_max_mb)
    if cfg.obs.slo.enabled:
        # heartbeat-cadence watch: SLOs over the serve.* keys (p99, queue
        # depth, staleness) evaluate in serve_forever's beat; the admin
        # {"cmd":"alerts"} and fedrec-obs alerts read the same engine
        from fedrec_tpu.obs.watch import Watch

        service.watch = Watch(
            cfg.obs.slo, cfg.obs.watch,
            registry=service.registry,
            jsonl_path=jsonl,
            jsonl_max_mb=cfg.obs.jsonl_max_mb,
        )
    try:
        asyncio.run(serve_forever(
            service, host=args.host, port=args.port,
            metrics_every_s=args.metrics_every, logger=logger,
            obs_dir=args.obs_dir, jsonl_max_mb=cfg.obs.jsonl_max_mb,
        ))
    except KeyboardInterrupt:
        print("[serve] interrupted; shutting down", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
