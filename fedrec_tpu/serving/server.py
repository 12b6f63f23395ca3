"""Long-lived TCP/JSON-lines recommendation service.

Wire protocol (one JSON object per line, newline-terminated, responses
carry the request's ``id`` back so clients may pipeline):

* request  ``{"id": 7, "history": [12, 94, ...], "top_k": 10,
  "deadline_ms": 50}`` →
  response ``{"id": 7, "ids": [...], "scores": [...], "generation": 3,
  "deadline_met": true, "latency_ms": 4.1}``
  (plus ``"news": [nid, ...]`` when the service holds an id map);
* admin    ``{"cmd": "metrics"}`` → ``{"metrics": {...}}``;
* admin    ``{"cmd": "prometheus"}`` → ``{"prometheus": "<text exposition>"}``
  (the whole obs registry in Prometheus text format, docs/OBSERVABILITY.md);
* admin    ``{"cmd": "refresh", "snapshot_dir": "...",
  "token_states": "...npy"}`` → hot-swap the embedding store from a
  training checkpoint and report the new generation;
* errors   ``{"id": ..., "error": "backpressure" | "bad_json" | ...}``.

The service composes the three serving pieces: every batch flush grabs
ONE :class:`~fedrec_tpu.serving.store.Generation` snapshot and scores the
whole batch against it (swap-atomicity: no request ever sees a torn
generation), through a per-generation retrieval function (two-stage past
the exact threshold, dense below it).  Per-generation compiled functions
are cached two generations deep, so responses for the outgoing
generation keep flowing while the incoming one warms up.

Metrics are JSON-lines through :class:`fedrec_tpu.utils.logging.MetricLogger`
(the training side's schema): ``serve.p50_ms`` / ``serve.p99_ms``,
``serve.mean_occupancy``, ``serve.swap_count``, ``serve.generation``,
``serve.staleness_sec``, plus batcher counters.
"""

from __future__ import annotations

import asyncio
import json
import time
from collections import deque
from functools import partial
from pathlib import Path
from typing import Any

import numpy as np

from fedrec_tpu.obs import get_registry
from fedrec_tpu.obs import wire
from fedrec_tpu.serving.batcher import Backpressure, MicroBatcher
from fedrec_tpu.serving.retrieval import build_index, build_two_stage_fn
from fedrec_tpu.serving.store import EmbeddingStore, EmptyStoreError

_FN_CACHE_GENERATIONS = 2


class ServingService:
    """batcher -> store -> retrieval, one object an event loop can own."""

    def __init__(
        self,
        model,
        store: EmbeddingStore,
        history_len: int,
        top_k: int = 10,
        exclude_history: bool = True,
        batch_sizes=(1, 8, 32, 128),
        flush_ms: float = 2.0,
        max_queue: int = 1024,
        num_clusters: int = 0,
        n_probe: int = 8,
        exact_threshold: int = 4096,
        id_map: dict[int, str] | None = None,
        latency_window: int = 8192,
        registry=None,
        watch=None,
    ):
        self.model = model
        self.store = store
        # in-process watch layer (fedrec_tpu.obs.watch.Watch, built by the
        # CLI when obs.slo.enabled): evaluated at heartbeat cadence in
        # serve_forever, fed drift-probe results on refresh, surfaced via
        # the admin {"cmd": "alerts"}. None = exact pre-watch behavior.
        self.watch = watch
        self.top_k = int(top_k)
        self.exclude_history = exclude_history
        self.num_clusters = int(num_clusters)
        self.n_probe = int(n_probe)
        self.exact_threshold = int(exact_threshold)
        self.id_map = id_map
        self.registry = registry or get_registry()
        self.batcher = MicroBatcher(
            self._score_batch,
            history_len=history_len,
            batch_sizes=batch_sizes,
            flush_ms=flush_ms,
            max_queue=max_queue,
            registry=self.registry,
        )
        self._fns: dict[int, Any] = {}
        # bucket -> seconds its first (compiling) call took in the latest
        # warmed scorer build
        self.warmup_seconds: dict[int, float] = {}
        self._latencies: deque[float] = deque(maxlen=latency_window)
        self._conns: set = set()  # live TCP writers; closed on stop()
        self._started_at = time.time()
        # derived gauges refreshed lazily at snapshot/exposition time (a
        # registry collector): percentile math per scrape, not per request
        self._g_p50 = self.registry.gauge("serve.p50_ms", "median serve latency")
        self._g_p99 = self.registry.gauge("serve.p99_ms", "p99 serve latency")
        self._g_occ = self.registry.gauge(
            "serve.mean_occupancy", "mean real-requests/bucket over served batches"
        )
        self._g_staleness = self.registry.gauge(
            "serve.staleness_sec", "seconds since the serving generation was published"
        )
        self._g_uptime = self.registry.gauge("serve.uptime_sec", "service uptime")
        self.registry.register_collector(self._collect)

    def _collect(self) -> None:
        lat = np.asarray(self._latencies, np.float64)
        if lat.size:
            self._g_p50.set(float(np.percentile(lat, 50)))
            self._g_p99.set(float(np.percentile(lat, 99)))
        occ = self.batcher.metrics().get("mean_occupancy")
        if occ is not None:
            self._g_occ.set(occ)
        staleness = self.store.metrics().get("staleness_sec")
        if staleness is not None:
            self._g_staleness.set(staleness)
        self._g_uptime.set(time.time() - self._started_at)

    # ------------------------------------------------------------ lifecycle
    async def start(self) -> None:
        await self.batcher.start()

    async def stop(self) -> None:
        await self.batcher.stop()
        # close surviving connections: a stopped service answering
        # "batcher not started" errors forever would pin well-behaved
        # retrying clients (fedrec_tpu.serving.client) to a dead endpoint —
        # an explicit close makes them back off and reconnect to whatever
        # replaces us
        for w in list(self._conns):
            try:
                w.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        self._conns.clear()
        # one final refresh so post-stop exposition/artifact dumps carry the
        # service's last numbers, then detach: a stopped service must not
        # keep publishing through the process registry (tests build many
        # short-lived services)
        self._collect()
        self.registry.unregister_collector(self._collect)

    def warmup(self) -> None:
        """Compile every batch bucket against the current generation so the
        first real requests don't pay XLA compile latency."""
        gen = self.store.current()
        self._cache_fn(
            gen.generation,
            self._build_fn(gen.news_vecs, gen.valid_mask, gen.user_params),
        )

    # ------------------------------------------------------------ scoring
    def _build_fn(self, news_vecs, valid_mask, user_params=None):
        """Index + compiled scorer for one generation's arrays; with
        ``user_params`` given, also run every batch bucket once so the jit
        cache is hot before the function serves traffic."""
        index = build_index(
            news_vecs,
            num_clusters=self.num_clusters,
            n_probe=self.n_probe,
            valid_mask=valid_mask,
            exact_threshold=self.exact_threshold,
        )
        fn = build_two_stage_fn(
            self.model,
            index,
            top_k=self.top_k,
            exclude_history=self.exclude_history,
        )
        if user_params is not None:
            for b in self.batcher.batch_sizes:
                t0 = time.perf_counter()
                hist = np.zeros((b, self.batcher.history_len), np.int32)
                np.asarray(fn(user_params, hist)[0])
                self.warmup_seconds[b] = time.perf_counter() - t0
        return fn

    def _cache_fn(self, generation: int, fn) -> None:
        self._fns[generation] = fn
        for g in sorted(self._fns)[:-_FN_CACHE_GENERATIONS]:
            del self._fns[g]

    def _fn_for(self, gen):
        """Lazy path: generations published directly on the store (tests,
        in-process swaps) build their scorer on first use.  The refresh
        command never takes this path — it pre-builds off the loop."""
        fn = self._fns.get(gen.generation)
        if fn is None:
            fn = self._build_fn(gen.news_vecs, gen.valid_mask)
            self._cache_fn(gen.generation, fn)
        return fn

    def _score_batch(self, hist: np.ndarray):
        """Batcher callback: one generation snapshot per batch — the
        atomic-swap contract lives in this single ``current()`` read."""
        gen = self.store.current()
        fn = self._fn_for(gen)
        ids, scores = fn(gen.user_params, hist)
        return np.asarray(ids), np.asarray(scores), gen.generation

    # ------------------------------------------------------------ requests
    async def handle(self, req: dict) -> dict:
        if not isinstance(req, dict):
            return {"error": "bad_request"}
        if "cmd" in req:
            return await self._admin(req)
        rid = req.get("id")
        try:
            result = await self.batcher.submit(
                req.get("history") or [], deadline_ms=req.get("deadline_ms")
            )
        except Backpressure:
            return {"id": rid, "error": "backpressure"}
        except EmptyStoreError:
            return {"id": rid, "error": "no_generation"}
        except Exception as e:  # noqa: BLE001 — per-request error isolation
            return {"id": rid, "error": f"{type(e).__name__}: {e}"}
        self._latencies.append(result.latency_ms)
        keep = result.ids >= 0
        want = req.get("top_k")
        if isinstance(want, bool):  # JSON true/false is not a count
            want = None
        if isinstance(want, int) and want >= 0:
            keep &= np.arange(result.ids.shape[0]) < want
        ids = [int(i) for i in result.ids[keep]]
        resp = {
            "id": rid,
            "ids": ids,
            "scores": [round(float(s), 5) for s in result.scores[keep]],
            "generation": result.generation,
            "deadline_met": result.deadline_met,
            "latency_ms": round(result.latency_ms, 3),
        }
        if want is not None and want > self.top_k:
            # the scorer is compiled at the service's --top-k; say the cap
            # applied rather than letting a short list read as "catalog
            # exhausted"
            resp["top_k_capped"] = self.top_k
        if self.id_map is not None:
            resp["news"] = [self.id_map.get(i, str(i)) for i in ids]
        return resp

    async def _admin(self, req: dict) -> dict:
        cmd = req.get("cmd")
        if cmd == "metrics":
            return {"metrics": self.metrics()}
        if cmd == "alerts":
            # active + recent alerts from the in-process watch; an
            # un-watched server answers the empty shape, not an error —
            # the command is part of the admin contract either way
            # (strict-superset pin in tests/test_watch.py)
            if self.watch is not None:
                return {"alerts": self.watch.engine.snapshot_state()}
            return {"alerts": {"active": [], "recent": []}}
        if cmd == "prometheus":
            # text exposition over the admin protocol: a scraper sidecar
            # (or curl | promtool) gets the full registry, not just the
            # serving keys — the one-line Prometheus integration
            return {"prometheus": self.registry.to_prometheus()}
        if cmd == "refresh":
            try:
                prepared = await asyncio.get_running_loop().run_in_executor(
                    None, partial(self._prepare_refresh, req)
                )
            except Exception as e:  # noqa: BLE001 — refresh must not kill serving
                return {"error": f"refresh_failed: {type(e).__name__}: {e}"}
            # publish + scorer-cache insert together ON the event loop: the
            # expensive work (checkpoint load, corpus encode, index build,
            # per-bucket compiles) already happened in the executor, so the
            # swap itself is two reference assignments no batch flush can
            # interleave with — a swap costs a warmup, never an outage
            table, user_params, valid_mask, round_, source, fn = prepared
            gen = self.store.publish(
                table, user_params, valid_mask=valid_mask,
                round=round_, source=source,
            )
            self._cache_fn(gen.generation, fn)
            if self.watch is not None:
                # unified trigger path: a drift-probe breach on this swap
                # pulses the serve:drift alert (scored at the next beat)
                self.watch.ingest_drift(self.store.metrics())
            return {"refreshed": True, "generation": gen.generation,
                    "round": gen.round, "source": gen.source}
        return {"error": f"unknown_cmd: {cmd}"}

    def _prepare_refresh(self, req: dict):
        """Checkpoint -> encode -> index build -> bucket warmup, all off the
        event loop.  Returns everything `_admin` needs for the (cheap,
        on-loop) publish; in-flight batches keep serving the old generation
        from its cached scorer throughout."""
        import jax.numpy as jnp

        from fedrec_tpu.serving.store import load_checkpoint_params
        from fedrec_tpu.train.step import encode_all_news

        token_states = np.load(req["token_states"])
        user_params, news_params, round_, kind = load_checkpoint_params(
            req["snapshot_dir"]
        )
        table = encode_all_news(
            self.model, news_params,
            jnp.asarray(token_states, jnp.dtype(req.get("dtype", "float32"))),
        )
        if "valid_mask" in req:
            valid_mask = np.load(req["valid_mask"]).astype(bool)
            if valid_mask.shape[0] != table.shape[0]:
                raise ValueError(
                    f"valid_mask length {valid_mask.shape[0]} != catalog "
                    f"{table.shape[0]}"
                )
        else:
            # reuse the serving mask only while the catalog size is
            # unchanged — a grown/shrunk corpus would shape-error (or,
            # same-size reordered, silently validate the WRONG rows), so a
            # refresh that changes N must ship its own mask or serve all
            valid_mask = self.store.current().valid_mask
            if valid_mask is not None and valid_mask.shape[0] != table.shape[0]:
                valid_mask = None
        fn = self._build_fn(table, valid_mask, user_params)
        return table, user_params, valid_mask, round_, f"checkpoint:{kind}", fn

    # ------------------------------------------------------------ metrics
    def metrics(self) -> dict:
        lat = np.asarray(self._latencies, np.float64)
        out = {
            "uptime_sec": round(time.time() - self._started_at, 1),
            "latency_count": int(lat.size),
            "p50_ms": round(float(np.percentile(lat, 50)), 3) if lat.size else None,
            "p99_ms": round(float(np.percentile(lat, 99)), 3) if lat.size else None,
        }
        out.update(self.batcher.metrics())
        out.update(self.store.metrics())
        return out

    def log_metrics(self, logger, step: int) -> None:
        """Emit the metric snapshot through the training side's
        MetricLogger schema (``serve.``-prefixed keys)."""
        logger.log(step, {f"serve.{k}": v for k, v in self.metrics().items()
                          if not isinstance(v, dict)})


# ---------------------------------------------------------------- TCP layer
async def _handle_conn(service: ServingService, reader, writer) -> None:
    write_lock = asyncio.Lock()
    tasks: set[asyncio.Task] = set()
    service._conns.add(writer)

    async def one(raw: bytes) -> None:
        # wire envelope (obs.wire): stripped BEFORE dispatch so unknown
        # envelope keys never reach handle(); the reply echoes one ONLY
        # when the request carried one (old clients see pre-envelope
        # bytes).  contextvars make the serve ctx task-local here.
        recv_ts = time.time()
        env = reply_env = None
        try:
            req = json.loads(raw)
        except json.JSONDecodeError:
            resp: dict = {"error": "bad_json"}
        else:
            req, env = wire.unwrap_envelope(req)
            if env is None:
                resp = await service.handle(req)
            else:
                token = wire.enter_serve(env, recv_ts)
                try:
                    resp = await service.handle(req)
                    reply_env = wire.server_reply_envelope(env, recv_ts)
                finally:
                    wire.exit_serve(token)
                if isinstance(resp, dict):
                    resp = {**resp, wire.WIRE_KEY: reply_env}
        out = (json.dumps(resp) + "\n").encode()
        async with write_lock:
            writer.write(out)
            try:
                await writer.drain()
            except ConnectionError:
                pass
        if env is not None and reply_env is not None:
            wire.record_server_exchange(
                env, reply_env, op=str(env.get("op") or "score"),
                bytes_recvd=len(raw), bytes_sent=len(out),
            )

    while True:
        try:
            line = await reader.readline()
        except (asyncio.LimitOverrunError, ValueError):
            # request line beyond the stream limit (see _LINE_LIMIT): answer
            # with an explicit error instead of tearing the connection down
            # silently; the stream is no longer line-synchronized, so close
            async with write_lock:
                writer.write(b'{"error": "line_too_long"}\n')
                try:
                    await writer.drain()
                except ConnectionError:
                    pass
            break
        if not line:
            break
        if line.strip():
            # task-per-request: requests on one connection pipeline through
            # the batcher instead of serializing on each other's latency
            t = asyncio.ensure_future(one(line))
            tasks.add(t)
            t.add_done_callback(tasks.discard)
    if tasks:
        await asyncio.gather(*tasks, return_exceptions=True)
    service._conns.discard(writer)
    try:
        writer.close()
        await writer.wait_closed()
    except ConnectionError:
        pass


# request lines carry full click histories; asyncio's 64 KiB default would
# cut off a few-thousand-click history mid-line
_LINE_LIMIT = 1 << 20


async def start_server(
    service: ServingService, host: str = "127.0.0.1", port: int = 0
):
    """Start the batcher and the TCP listener; returns the asyncio server
    (``server.sockets[0].getsockname()`` has the bound port when 0)."""
    await service.start()
    return await asyncio.start_server(
        partial(_handle_conn, service), host, port, limit=_LINE_LIMIT
    )


async def stop_server(
    server: asyncio.AbstractServer, service: ServingService
) -> None:
    """The one shutdown order: stop accepting, stop the service (drains the
    batcher and closes every live connection), then wait for the handlers.
    Since Python 3.12.1 ``Server.wait_closed()`` waits for every connection
    handler, so it must come after the close of their connections."""
    server.close()
    await service.stop()
    await server.wait_closed()


async def serve_forever(
    service: ServingService,
    host: str = "127.0.0.1",
    port: int = 7607,
    metrics_every_s: float = 30.0,
    logger=None,
    obs_dir: str | None = None,
    jsonl_max_mb: float = 0.0,
) -> None:
    """CLI entry loop: listen until SIGINT/SIGTERM, logging metrics
    periodically.  Shutdown is graceful BY CONSTRUCTION: the signal only
    sets an event, so the in-flight batch completes, the listener closes,
    the batcher drain fails queued requests cleanly and live connections
    are closed (``stop_server``) — instead of the default handler tearing
    the loop down mid-batch."""
    import signal

    if obs_dir is not None:
        Path(obs_dir).mkdir(parents=True, exist_ok=True)
    server = await start_server(service, host, port)
    addr = server.sockets[0].getsockname()
    print(f"[serve] listening on {addr[0]}:{addr[1]}", flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except (NotImplementedError, RuntimeError):  # non-main thread / win
            pass
    step = 0

    async def beat() -> None:
        nonlocal step
        while True:
            await asyncio.sleep(metrics_every_s)
            step += 1
            if logger is not None:
                service.log_metrics(logger, step)
            if service.watch is not None:
                # heartbeat-cadence watch tick, fed the serve.* metric
                # snapshot so SLOs over serve.p99_ms etc. read fresh
                # values without waiting on a registry collector pass
                service.watch.evaluate(record={
                    f"serve.{k}": v for k, v in service.metrics().items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)
                })
            if obs_dir is not None:
                # periodic registry snapshots make the event log useful
                # even when the server is killed rather than signalled;
                # size-rotate first so a long-lived server cannot fill
                # the disk (obs.jsonl_max_mb)
                from fedrec_tpu.obs import rotate_jsonl

                rotate_jsonl(Path(obs_dir) / "metrics.jsonl", jsonl_max_mb)
                service.registry.write_snapshot(Path(obs_dir) / "metrics.jsonl")

    heartbeat = asyncio.ensure_future(beat())
    try:
        await stop.wait()
        print("[serve] signal received; draining", flush=True)
    finally:
        heartbeat.cancel()
        await stop_server(server, service)
        if obs_dir is not None:
            from fedrec_tpu.obs import dump_artifacts

            paths = dump_artifacts(obs_dir, registry=service.registry)
            print(f"[serve] obs artifacts in {obs_dir}: "
                  f"{', '.join(sorted(paths))}", flush=True)
