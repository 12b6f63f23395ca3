"""End-to-end serving smoke (the ISSUE's acceptance scenario): TCP server
on a synthetic catalog, >= 64 concurrent requests through the
micro-batcher, a hot-swap of the embedding store MID-STREAM, and then:

* every response's ``deadline_met`` flag holds (generous deadlines);
* every response's ids match the EXACT scorer run against the generation
  that response reports it was served from (swap atomicity end-to-end);
* the swap-count / generation metrics advance.
"""

from __future__ import annotations

import asyncio
import json
import os
import re
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.serve import build_recommend_fn
from fedrec_tpu.serving import (
    EmbeddingStore,
    ServingService,
    serve_forever,
    start_server,
    stop_server,
)

N, D, H, TOP_K = 400, 32, 10, 5


@pytest.fixture(scope="module")
def setup():
    cfg = ExperimentConfig()
    cfg.model.bert_hidden = 32
    cfg.model.news_dim = D
    cfg.model.num_heads = 4
    cfg.model.head_dim = 8
    cfg.model.query_dim = 16
    model = NewsRecommender(cfg.model)
    rng = np.random.default_rng(11)
    tables = [
        jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
        for _ in range(2)
    ]
    dummy = jnp.zeros((1, H, D), jnp.float32)
    params = model.init(
        jax.random.PRNGKey(0), dummy, method=NewsRecommender.encode_user
    )["params"]["user_encoder"]
    return model, tables, params, rng


async def _request_line(reader, writer, req: dict, lock: asyncio.Lock) -> None:
    async with lock:
        writer.write((json.dumps(req) + "\n").encode())
        await writer.drain()


def test_e2e_concurrent_requests_with_mid_stream_hot_swap(setup):
    model, tables, params, rng = setup
    store = EmbeddingStore()
    store.publish(tables[0], params, round=1, source="synthetic")
    service = ServingService(
        model, store, history_len=H, top_k=TOP_K,
        batch_sizes=(1, 8, 32), flush_ms=2.0,
    )
    service.warmup()
    histories = [rng.integers(1, N, (rng.integers(2, H + 1),)).tolist()
                 for _ in range(96)]

    async def main():
        server = await start_server(service, port=0)
        port = server.sockets[0].getsockname()[1]
        conns = [await asyncio.open_connection("127.0.0.1", port)
                 for _ in range(4)]
        locks = [asyncio.Lock() for _ in conns]
        responses: list[dict] = []

        async def reader_task(reader):
            while True:
                line = await reader.readline()
                if not line:
                    return
                responses.append(json.loads(line))

        readers = [asyncio.ensure_future(reader_task(r)) for r, _ in conns]

        async def fire(idx_range):
            # pipelined across 4 connections, generous deadlines (the flag
            # must hold; CI boxes are slow, that is not the point here)
            for i in idx_range:
                _, writer = conns[i % 4]
                await _request_line(
                    conns[i % 4][0], writer,
                    {"id": i, "history": histories[i], "deadline_ms": 60_000.0},
                    locks[i % 4],
                )

        # wave 1, then hot-swap as soon as the first responses land (wave-1
        # stragglers may still be queued — served-from generation is per
        # batch), then wave 2 against the new generation
        await fire(range(48))
        while len(responses) < 8:
            await asyncio.sleep(0.001)
        store.publish(tables[1], params, round=2, source="synthetic")
        await fire(range(48, 96))
        while len(responses) < 96:
            await asyncio.sleep(0.005)
        # metrics over the wire after the stream
        _, writer = conns[0]
        await _request_line(conns[0][0], writer, {"cmd": "metrics"}, locks[0])
        while not any("metrics" in r for r in responses):
            await asyncio.sleep(0.005)
        for _, writer in conns:
            writer.close()
        await asyncio.gather(*readers)
        await stop_server(server, service)
        return responses

    responses = asyncio.run(main())
    recs = {r["id"]: r for r in responses if "ids" in r}
    metrics = next(r["metrics"] for r in responses if "metrics" in r)

    assert len(recs) == 96, f"lost responses: {sorted(set(range(96)) - set(recs))}"
    # every response met its (generous) deadline, flag checked end-to-end
    assert all(r["deadline_met"] for r in recs.values())

    # exact-scorer ground truth per generation: a response served from
    # generation g must match the dense scorer on THAT generation's table
    exact = build_recommend_fn(model, top_k=TOP_K)
    truth = {}
    gens_seen = set()
    hist_batch = np.zeros((96, H), np.int32)
    for i, h in enumerate(histories):
        hist_batch[i, : len(h[-H:])] = h[-H:]
    for g, table in enumerate(tables):
        ids, _ = exact(params, table, jnp.asarray(hist_batch))
        truth[g] = np.asarray(ids)
    for i, r in recs.items():
        g = r["generation"]
        gens_seen.add(g)
        expect = truth[g][i]
        np.testing.assert_array_equal(
            np.asarray(r["ids"]), expect[expect >= 0][: len(r["ids"])],
            err_msg=f"request {i} served from generation {g}",
        )
    # the swap really happened mid-stream and the metrics advanced
    assert gens_seen == {0, 1}
    assert metrics["generation"] == 1
    assert metrics["swap_count"] == 1
    assert metrics["served"] >= 96
    assert set(map(int, metrics["batches_by_size"])) == {1, 8, 32}
    assert metrics["p50_ms"] is not None and metrics["p99_ms"] is not None
    assert metrics["mean_occupancy"] is not None


def test_backpressure_and_error_paths_over_the_wire(setup):
    model, tables, params, rng = setup
    store = EmbeddingStore()
    store.publish(tables[0], params)
    service = ServingService(
        model, store, history_len=H, top_k=TOP_K,
        batch_sizes=(1, 4), flush_ms=20.0, max_queue=4,
    )
    service.warmup()

    async def main():
        server = await start_server(service, port=0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        lines = [json.dumps({"id": i, "history": [1 + i]}) for i in range(12)]
        lines.append("this is not json")
        lines.append(json.dumps({"cmd": "nope"}))
        writer.write(("\n".join(lines) + "\n").encode())
        await writer.drain()
        out = [json.loads(await reader.readline()) for _ in range(14)]
        writer.close()
        await stop_server(server, service)
        return out

    out = asyncio.run(main())
    served = [o for o in out if "ids" in o]
    shed = [o for o in out if o.get("error") == "backpressure"]
    assert len(served) >= 4  # the admitted window was served correctly
    assert served and all(o["generation"] == 0 for o in served)
    assert shed, "queue depth 4 with 12 pipelined requests must shed some"
    assert any(o.get("error") == "bad_json" for o in out)
    assert any(str(o.get("error", "")).startswith("unknown_cmd") for o in out)


def test_cli_synthetic_service_construction():
    """fedrec-serve --synthetic wiring: parser -> service, no server."""
    from fedrec_tpu.cli.serve import _synthetic_service, build_parser

    args = build_parser().parse_args(
        ["--synthetic", "500", "--top-k", "3", "--batch-sizes", "1,4",
         "--set", "model.bert_hidden=32", "--set", "model.news_dim=32",
         "--set", "model.num_heads=4", "--set", "model.head_dim=8",
         "--set", "model.query_dim=16", "--set", "data.max_his_len=8"]
    )
    cfg = ExperimentConfig()
    cfg.apply_overrides(args.overrides)
    service = _synthetic_service(args, cfg)
    assert service.store.current().num_news == 500
    assert service.batcher.batch_sizes == (1, 4)
    service.warmup()  # compiles both buckets against the synthetic table

    async def main():
        await service.start()
        r = await service.handle({"id": 1, "history": [3, 4, 5]})
        await service.stop()
        return r

    r = asyncio.run(main())
    assert len(r["ids"]) == 3 and r["generation"] == 0


@pytest.mark.parametrize("answered_first", [True, False])
def test_serve_forever_returns_on_sigint_with_an_idle_client(
    setup, capsys, answered_first
):
    """SIGINT with one client connected and idle: the server closes that
    connection (the client reads EOF) and returns. Since Python 3.12.1
    ``Server.wait_closed()`` waits for the connection's handler, which sits
    in ``readline()`` until ``ServingService.stop()`` closes its writer."""
    model, tables, params, _ = setup
    store = EmbeddingStore()
    store.publish(tables[0], params)
    service = ServingService(
        model, store, history_len=H, top_k=TOP_K, batch_sizes=(1,), flush_ms=2.0,
    )

    async def main():
        serving = asyncio.ensure_future(
            serve_forever(service, port=0, metrics_every_s=3600.0)
        )
        while not (m := re.search(r"listening on [\d.]+:(\d+)",
                                  capsys.readouterr().out)):
            await asyncio.sleep(0.01)
        reader, writer = await asyncio.open_connection("127.0.0.1", int(m[1]))
        if answered_first:
            writer.write(b'{"cmd": "metrics"}\n')
            assert "metrics" in json.loads(await reader.readline())
        os.kill(os.getpid(), signal.SIGINT)
        await asyncio.wait_for(serving, timeout=5)
        assert await asyncio.wait_for(reader.read(), timeout=5) == b""
        writer.close()

    asyncio.run(main())


def test_refresh_from_checkpoint_over_the_wire(setup, tmp_path):
    """The hot refresh flow end-to-end: a coordinator-globals checkpoint +
    cached token states on disk, {"cmd": "refresh"} over TCP, and the next
    request must be served from the NEW generation with ids matching the
    exact scorer on the checkpoint-encoded table."""
    from flax import serialization

    from fedrec_tpu.train.step import encode_all_news

    model, tables, params, rng = setup
    token_states = rng.standard_normal((N, 6, 32)).astype(np.float32)
    np.save(tmp_path / "token_states.npy", token_states)
    # both towers initialized through their own entry points: the news
    # tower encodes (N, L, bert_hidden) token states like the trainer does
    news_params = model.init(
        jax.random.PRNGKey(3), jnp.asarray(token_states[:1]),
        method=NewsRecommender.encode_news,
    )["params"]["text_head"]
    user_ckpt = model.init(
        jax.random.PRNGKey(4), jnp.zeros((1, H, D), jnp.float32),
        method=NewsRecommender.encode_user,
    )["params"]["user_encoder"]
    full = {"user_encoder": user_ckpt, "text_head": news_params}
    blob = serialization.msgpack_serialize(
        {"user": full["user_encoder"], "news": full["text_head"], "round": 3}
    )
    (tmp_path / "global_round_3.msgpack").write_bytes(blob)

    store = EmbeddingStore()
    store.publish(tables[0], params, round=1, source="synthetic")
    service = ServingService(
        model, store, history_len=H, top_k=TOP_K, batch_sizes=(1, 8),
        flush_ms=2.0,
    )
    service.warmup()

    async def main():
        server = await start_server(service, port=0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)

        async def rpc(req):
            writer.write((json.dumps(req) + "\n").encode())
            await writer.drain()
            return json.loads(await reader.readline())

        before = await rpc({"id": 0, "history": [5, 6, 7]})
        ref = await rpc({
            "cmd": "refresh",
            "snapshot_dir": str(tmp_path),
            "token_states": str(tmp_path / "token_states.npy"),
        })
        after = await rpc({"id": 1, "history": [5, 6, 7]})
        met = (await rpc({"cmd": "metrics"}))["metrics"]
        writer.close()
        await stop_server(server, service)
        return before, ref, after, met

    before, ref, after, met = asyncio.run(main())
    assert before["generation"] == 0
    assert ref == {"refreshed": True, "generation": 1, "round": 3,
                   "source": "checkpoint:coordinator"}
    assert after["generation"] == 1
    assert met["swap_count"] == 1 and met["round"] == 3

    # ground truth: encode the corpus from the checkpoint ourselves and run
    # the exact scorer with the checkpoint's user params
    table = encode_all_news(model, full["text_head"], jnp.asarray(token_states))
    exact = build_recommend_fn(model, top_k=TOP_K)
    hist = np.zeros((1, H), np.int32)
    hist[0, :3] = [5, 6, 7]
    ids, _ = exact(full["user_encoder"], table, jnp.asarray(hist))
    np.testing.assert_array_equal(np.asarray(after["ids"]), np.asarray(ids)[0])
