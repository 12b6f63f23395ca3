"""Serving x observability: the `{"cmd": "metrics"}` wire contract stays a
superset of its pre-registry keys, the new `{"cmd": "prometheus"}` admin
command exposes the registry, request-lifecycle spans get recorded, and a
stopped service detaches its collector from the process registry."""

from __future__ import annotations

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.obs import MetricsRegistry, Tracer, set_registry, set_tracer
from fedrec_tpu.serving import EmbeddingStore, ServingService, start_server, stop_server

N, D, H, TOP_K = 200, 32, 10, 5

# the serving admin metrics() keys as of the registry migration — the wire
# contract dashboards already scrape.  metrics() must stay a SUPERSET.
PRE_PR_METRIC_KEYS = {
    # ServingService
    "uptime_sec", "latency_count", "p50_ms", "p99_ms",
    # MicroBatcher
    "served", "rejected", "deadline_missed", "batches", "batches_by_size",
    "mean_occupancy", "queue_depth",
    # EmbeddingStore
    "generation", "swap_count", "round", "source", "num_news",
    "staleness_sec",
}


@pytest.fixture()
def fresh_obs():
    """Isolated registry/tracer so counters assert exactly."""
    reg, tr = MetricsRegistry(), Tracer()
    old_reg, old_tr = set_registry(reg), set_tracer(tr)
    try:
        yield reg, tr
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)


def _service(registry=None):
    cfg = ExperimentConfig()
    cfg.model.bert_hidden = 32
    cfg.model.news_dim = D
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    model = NewsRecommender(cfg.model)
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
    dummy = jnp.zeros((1, H, D), jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0), dummy, method=NewsRecommender.encode_user
    )["params"]["user_encoder"]
    store = EmbeddingStore(registry=registry)
    store.publish(table, params, round=1, source="synthetic")
    return ServingService(
        model, store, history_len=H, top_k=TOP_K, batch_sizes=(1, 8),
        flush_ms=1.0, registry=registry,
    )


def test_metrics_cmd_is_superset_of_pre_pr_keys(fresh_obs):
    reg, tr = fresh_obs
    service = _service(registry=reg)
    service.warmup()

    async def main():
        server = await start_server(service, port=0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)

        async def rpc(req):
            writer.write((json.dumps(req) + "\n").encode())
            await writer.drain()
            return json.loads(await reader.readline())

        for i in range(6):
            await rpc({"id": i, "history": [1 + i, 2 + i]})
        met = (await rpc({"cmd": "metrics"}))["metrics"]
        prom = (await rpc({"cmd": "prometheus"}))["prometheus"]
        writer.close()
        await stop_server(server, service)
        return met, prom

    met, prom = asyncio.run(main())
    missing = PRE_PR_METRIC_KEYS - set(met)
    assert not missing, f"metrics() lost pre-PR keys: {sorted(missing)}"
    assert met["served"] >= 6 and met["p50_ms"] is not None

    # the admin prometheus exposition carries the serving essentials
    for needle in ("serve_p50_ms", "serve_p99_ms", "serve_queue_depth",
                   "serve_requests_total", "serve_latency_ms_bucket",
                   "serve_generation"):
        assert needle in prom, f"prometheus exposition missing {needle}"
    # dotted originals greppable via HELP
    assert "serve.p50_ms" in prom

    # registry counters agree with the wire dict
    assert reg.counter("serve.requests_total").value() == met["served"]

    # request-lifecycle spans: enqueue->batch->dispatch->reply all present
    names = {e["name"] for e in tr.events()}
    assert {"serve.queue_wait", "serve.dispatch", "serve.reply",
            "serve.request"} <= names


def test_stopped_service_detaches_collector(fresh_obs):
    reg, _ = fresh_obs
    service = _service(registry=reg)

    async def main():
        await service.start()
        await service.handle({"id": 0, "history": [3]})
        await service.stop()

    asyncio.run(main())
    assert service._collect not in reg._collectors
    # final collect ran at stop: p50 gauge carries the last number
    assert reg.gauge("serve.p50_ms").value() is not None


def test_store_publish_updates_gauges(fresh_obs):
    reg, _ = fresh_obs
    store = EmbeddingStore(registry=reg)
    store.publish(np.zeros((7, 4), np.float32), {"w": np.zeros(2)})
    assert reg.gauge("serve.generation").value() == 0
    assert reg.gauge("serve.num_news").value() == 7
    store.publish(np.zeros((9, 4), np.float32), {"w": np.zeros(2)})
    assert reg.gauge("serve.generation").value() == 1
    assert reg.gauge("serve.swap_count").value() == 1
    assert reg.gauge("serve.num_news").value() == 9
