"""Resilient serving client: retry/backoff/deadline semantics + the
server-restart-mid-run survival story (ISSUE 5 satellite: a restart
degrades to elevated latency / counted errors, never a crashed driver)."""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.obs import MetricsRegistry, set_registry
from fedrec_tpu.serving import (
    EmbeddingStore,
    ServingClient,
    ServingClientPool,
    ServingService,
    ServingUnavailable,
    start_server,
    stop_server,
)

N, D, H = 200, 32, 8


def _service():
    set_registry(MetricsRegistry())
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
    store = EmbeddingStore()
    store.publish(table, params, source="synthetic")
    svc = ServingService(
        model, store, history_len=H, top_k=5, batch_sizes=(1, 8),
        flush_ms=1.0, max_queue=256,
    )
    svc.warmup()
    return svc


# ------------------------------------------------------------- unit: backoff
def test_backoff_is_exponential_capped_and_jittered():
    c = ServingClient("127.0.0.1", 1, backoff_base_ms=50, backoff_max_ms=400,
                      seed=0)
    caps = [min(400, 50 * 2 ** a) / 1e3 for a in range(6)]
    draws = [[c.backoff_delay_s(a) for _ in range(200)] for a in range(6)]
    for a, (cap, ds) in enumerate(zip(caps, draws)):
        assert all(0.0 <= d <= cap for d in ds), f"attempt {a}"
    # full jitter: draws actually spread (not a fixed schedule)
    assert np.std(draws[3]) > 0.01
    # the cap binds: attempt 5's ceiling equals attempt 3's (400ms)
    assert max(draws[5]) <= 0.4 + 1e-9


def test_unreachable_server_returns_unavailable_not_raise():
    async def go():
        c = ServingClient("127.0.0.1", 1, request_timeout_ms=300,
                          backoff_base_ms=10, backoff_max_ms=50, seed=1)
        resp = await c.request({"history": [1, 2]})
        assert resp["error"] in ("unavailable", "deadline")
        with pytest.raises(ServingUnavailable):
            await c.request_or_raise({"history": [1, 2]})
        await c.close()

    asyncio.run(go())


def test_deadline_enforced_client_side():
    """A server that never answers: the per-request deadline bounds the
    call instead of hanging it."""

    async def go():
        async def black_hole(reader, writer):
            await reader.read()  # never answers; ends when the client hangs up
            writer.close()

        server = await asyncio.start_server(black_hole, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        c = ServingClient("127.0.0.1", port, request_timeout_ms=200, seed=2)
        t0 = asyncio.get_event_loop().time()
        resp = await c.request({"history": [1]})
        elapsed = asyncio.get_event_loop().time() - t0
        assert resp == {"error": "deadline"}
        assert elapsed < 2.0
        await c.close()
        server.close()
        await server.wait_closed()

    asyncio.run(go())


# ----------------------------------------------------- integration: restart
def test_server_restart_mid_run_degrades_not_fails():
    async def go():
        svc = _service()
        server = await start_server(svc, port=0)
        port = server.sockets[0].getsockname()[1]
        pool = ServingClientPool(
            "127.0.0.1", port, size=2, request_timeout_ms=4000,
            backoff_base_ms=20, backoff_max_ms=200,
        )

        async def fire(n):
            out = []
            for i in range(n):
                out.append(await pool.handle({"id": i, "history": [1, 2, 3]}))
            return out

        before = await fire(8)
        assert all("error" not in r for r in before)
        assert all(r["ids"] for r in before)

        # hard restart: close the listener AND the service, then bring a
        # fresh service up on the SAME port while the pool is mid-use
        await stop_server(server, svc)

        # requests during the outage fail SOFT (error responses, no raise)
        c_down = ServingClient("127.0.0.1", port, request_timeout_ms=250,
                               backoff_base_ms=10, backoff_max_ms=50, seed=3)
        down = await c_down.request({"history": [1]})
        assert down["error"] in ("unavailable", "deadline")
        await c_down.close()

        svc2 = _service()
        server2 = await start_server(svc2, host="127.0.0.1", port=port)

        # the SAME pool reconnects (backoff) and serves again
        after = await fire(8)
        assert all("error" not in r for r in after), after
        assert pool.retry_metrics()["reconnects"] >= 1 or all(
            "error" not in r for r in after
        )
        # client-side latency/deadline stamping in remote mode
        assert all("latency_ms" in r and r["deadline_met"] for r in after)

        mt = await pool.admin("metrics", deadline_ms=2000)
        assert "metrics" in mt

        await pool.close()
        await stop_server(server2, svc2)

    asyncio.run(go())
