"""Async worker loop — one process's side of the buffered-commit wire.

An async worker is a SINGLE-process Trainer (no collective world — the
all-process barrier is exactly what async mode removes) that, per
round:

  1. trains its local round (``Trainer.train_round_recovering``),
  2. computes its contribution DELTA against the global version it
     trained from,
  3. pushes the delta to the :mod:`~fedrec_tpu.agg.server` commit
     authority (after the scripted chaos delay, when this worker is the
     smoke's straggler — ``chaos.straggle_ms`` is the host-side
     straggle knob and sleeps here, at the push boundary).  With
     ``fed.dcn_compress`` set, the push ships ENCODED per-leaf payloads
     instead of dense leaves: linear sketches go up raw (the server
     folds them in sketch space), per-contribution codecs go up with
     this worker's locally-held error-feedback residual already folded
     in — the residual lives at the encoding edge, banked against the
     version the push was based on, and what the encode drops this
     round rides the next round's delta,
  4. polls for a NEWER committed global (bounded wait — on timeout the
     worker proceeds from its own params and its next push simply
     carries higher staleness; that is the async contract, not an
     error) and adopts it via ``set_global_params``.

Because every worker seeds identically (same config, same
``train.seed``), the first worker's ``init`` push IS the version-0
global; the others verify against it by adopting it.

Partition tolerance (ROADMAP 1(c)) rides
:class:`fedrec_tpu.parallel.rpc.FleetRpc`: every exchange retries
transport failures inside the ``agg.worker_*`` budgets with full-jitter
backoff and a per-edge circuit breaker.  When the authority stays
unreachable the worker DEGRADES instead of crashing — each contribution
it cannot deliver parks on an unacked list (its client-generated
``push_id`` is reused verbatim on the retry, so the authority's ledger
can never fold it twice) and training continues, until the wire has
been silent longer than ``agg.worker_unreachable_budget_s``; then it
raises :class:`~fedrec_tpu.parallel.rpc.AuthorityUnreachable` and the
CLI exits rc-75 for the supervisor.  When the authority RESTARTS the
worker notices the incarnation bump in any reply, re-hellos, flushes
the unacked backlog, and adopts the restored committed global
(``agg.resyncs_total`` counts these) — acked history is never
re-trained, and a push the restore left behind ("rebase" error reply:
its base is ahead of the restored global) is dropped in favor of
adopting the authority's current truth.
"""

from __future__ import annotations

import time
import zlib

import jax
import numpy as np

__all__ = ["run_async_worker"]


def _flatten_params(trainer) -> tuple[list[np.ndarray], object]:
    user_params, news_params = trainer._client0_params()
    leaves, treedef = jax.tree_util.tree_flatten((user_params, news_params))
    return [np.asarray(x) for x in leaves], treedef


def run_async_worker(
    trainer,
    server: str,
    worker_id: str,
    timeout_s: float | None = None,
    poll_s: float | None = None,
    global_wait_s: float | None = None,
) -> list:
    """Drive ``trainer`` for its configured rounds against the commit
    authority at ``server`` ("HOST:PORT").  Returns the round history
    (same shape as ``Trainer.run``).  The keyword knobs default to the
    ``agg.worker_*`` config values; explicit arguments win (tests pin
    tight deadlines without a config round-trip).  Raises
    :class:`~fedrec_tpu.parallel.rpc.AuthorityUnreachable` when the
    authority stays dark past ``agg.worker_unreachable_budget_s``."""
    from fedrec_tpu.agg.server import (
        decode_leaves,
        encode_leaves,
        encode_payloads,
    )
    from fedrec_tpu.comms import (
        codec_caps,
        decode_leaf,
        encode_leaf,
        payload_nbytes,
        validate_codec,
    )
    from fedrec_tpu.obs import wire
    from fedrec_tpu.parallel.rpc import (
        AuthorityUnreachable,
        FleetRpc,
        RpcPolicy,
        new_push_id,
    )

    cfg = trainer.cfg
    host, port_s = server.rsplit(":", 1)
    port = int(port_s)
    if timeout_s is None:
        timeout_s = float(cfg.agg.worker_timeout_s)
    if poll_s is None:
        poll_s = float(cfg.agg.worker_poll_s)
    if global_wait_s is None:
        global_wait_s = float(cfg.agg.worker_global_wait_s)
    unreachable_budget_s = float(cfg.agg.worker_unreachable_budget_s)
    codec = cfg.fed.dcn_compress
    if codec != "none":
        # "auto" never reaches here (the trainer guard pins async to
        # concrete codecs); a bad name fails before any training
        validate_codec(codec)
    use_ef = (
        codec != "none"
        and codec_caps(codec).supports_error_feedback
        and cfg.fed.dcn_error_feedback
    )
    ef_residual: list | None = None   # this edge's banked encode error

    rpc = FleetRpc(host, port, RpcPolicy(
        connect_timeout_s=cfg.agg.worker_connect_timeout_s,
        read_timeout_s=timeout_s,
        attempts=cfg.agg.worker_rpc_attempts,
        backoff_base_ms=cfg.agg.worker_backoff_ms,
        backoff_max_ms=cfg.agg.worker_backoff_cap_ms,
        # the bounded poll loop IS the retry for `global`; re-dialing
        # inside one poll tick would double-spend the wait budget
        op_attempts={"global": 1},
        # probe a dead authority at least about once per round: an open
        # breaker makes the round loop fail fast, so the reset window is
        # what paces recovery detection — cap it at the per-round wait
        breaker_reset_s=min(10.0, global_wait_s),
        # decorrelate the fleet's jitter streams without per-worker config
        seed=zlib.crc32(worker_id.encode()),
    ))

    g_version = trainer.registry.gauge(
        "agg.global_version",
        "committed global version this worker last adopted",
    )
    g_staleness = trainer.registry.gauge(
        "agg.staleness",
        "commits the global had advanced past this worker's base when it "
        "pushed (worker-side view)",
    )
    c_pushes = trainer.registry.counter(
        "agg.pushes_total", "contribution deltas this worker pushed"
    )
    c_uplink = trainer.registry.counter(
        "agg.uplink_bytes_total",
        "encoded contribution bytes this worker pushed (measured payload "
        "buffers, pre-base64) — the async uplink the codec compresses",
    )
    c_resyncs = trainer.registry.counter(
        "agg.resyncs_total",
        "re-hello/re-adopt cycles after an authority incarnation bump or "
        "rebase reply (the crash-recovery handshake; 0 when the authority "
        "never restarted)",
    )

    epoch = 0
    incarnation: int | None = None
    # contributions the wire failed to deliver: each req keeps its
    # push_id, so the eventual retry is idempotent at the authority
    unacked: list[dict] = []
    version = 0
    base: list[np.ndarray] = []
    treedef = None

    def note_incarnation(resp: dict) -> bool:
        """Adopt the authority's advertised incarnation; True when it
        BUMPED (the authority restarted since our last exchange)."""
        nonlocal incarnation
        adv = resp.get("incarnation")
        if adv is None:
            return False
        adv = int(adv)
        bumped = incarnation is not None and adv != incarnation
        incarnation = adv
        return bumped

    def check_budget(cause: Exception | None = None) -> None:
        silent = rpc.unreachable_for()
        if silent > unreachable_budget_s:
            raise AuthorityUnreachable(
                f"commit authority {rpc.peer} unreachable for "
                f"{silent:.0f}s (budget agg.worker_unreachable_budget_s="
                f"{unreachable_budget_s:g}s, {len(unacked)} unacked "
                "pushes parked) — exiting rc-75 for the supervisor"
            ) from cause

    def flush_unacked() -> bool:
        """Re-deliver parked pushes in arrival order; stops at the first
        transport failure (the wire is still down — keep them parked).
        True when any reply advertised a BUMPED incarnation (the
        authority restarted: the round loop should resync; the resync
        path itself ignores the return — it is already the handshake)."""
        bumped = False
        while unacked:
            req = unacked[0]
            try:
                resp = rpc.call(req, op="push")
            except OSError as e:
                check_budget(e)
                return bumped
            except ValueError:
                # the authority answered and refused (restored global is
                # behind this push's base, or the entry can no longer
                # fold) — this contribution is unfoldable, drop it
                print(
                    f"[agg-worker {worker_id}] dropping unacked push "
                    f"{req.get('push_id', '?')} (authority refused it "
                    "after restart)",
                    flush=True,
                )
                unacked.pop(0)
                continue
            unacked.pop(0)
            bumped = note_incarnation(resp) or bumped
            if resp.get("duplicate"):
                print(
                    f"[agg-worker {worker_id}] push "
                    f"{req.get('push_id', '?')} was already folded "
                    "(idempotent retry)",
                    flush=True,
                )
        return bumped

    def resync(reason: str) -> bool:
        """The crash-recovery handshake: re-hello, flush the unacked
        backlog, adopt the authority's current committed global.  True
        when a global was adopted (the round loop must not clobber
        ``base`` afterwards).  Best-effort on a dead wire — the degrade
        budget is the backstop."""
        nonlocal version, base
        c_resyncs.inc()
        print(
            f"[agg-worker {worker_id}] resyncing with {rpc.peer} "
            f"({reason})",
            flush=True,
        )
        try:
            hello = rpc.call(
                {"cmd": "hello", "worker": worker_id, "epoch": epoch},
                op="hello",
            )
            note_incarnation(hello)
            flush_unacked()
            resp = rpc.call({"cmd": "global", "since": -1}, op="global")
        except OSError as e:
            check_budget(e)
            return False
        note_incarnation(resp)
        if "payload" in resp:
            base = decode_leaves(resp["payload"])
            version = int(resp["version"])
            _adopt(trainer, treedef, base)
            g_version.set(float(version))
            return True
        return False

    # ----------------------------------------------------------- bootstrap
    # without a hello + a version-0 global there is nothing to train
    # against, so bootstrap failures are immediately rc-75 material — the
    # supervisor respawns us against a (re)started authority
    try:
        hello = rpc.call(
            {"cmd": "hello", "worker": worker_id, "epoch": epoch}, op="hello"
        )
        note_incarnation(hello)
        version = int(hello["version"])
        leaves, treedef = _flatten_params(trainer)
        if not hello.get("have_global"):
            rpc.call({
                "cmd": "init", "worker": worker_id,
                "payload": encode_leaves(leaves),
            }, op="init")
        resp = rpc.call({"cmd": "global", "since": -1}, op="global")
    except OSError as e:
        raise AuthorityUnreachable(
            f"commit authority {rpc.peer} unreachable during bootstrap "
            f"({e}) — exiting rc-75 for the supervisor"
        ) from e
    note_incarnation(resp)
    if "payload" in resp:
        base = decode_leaves(resp["payload"])
        version = int(resp["version"])
        _adopt(trainer, treedef, base)
    else:
        base = leaves

    straggle_s = (
        cfg.chaos.straggle_ms / 1e3
        if cfg.chaos.enabled and cfg.chaos.straggle_ms > 0
        else 0.0
    )
    history = []
    for round_idx in range(trainer.start_round, cfg.fed.rounds):
        # train_round_recovering already commits the population schedule
        # and ticks quarantine; _after_round is the run()-loop half
        # (logging, cadence snapshots, fleet push) we replicate here
        result = trainer.train_round_recovering(round_idx)
        history.append(result)
        trainer._after_round(result)

        adopted_this_round = False
        after, _ = _flatten_params(trainer)
        delta = [a - b for a, b in zip(after, base)]
        if codec == "none":
            wire_payload = encode_leaves(delta)
            c_uplink.inc(float(sum(np.asarray(d).nbytes for d in delta)))
        else:
            # the error-feedback residual lives HERE, at the encoding
            # edge: fold last round's dropped mass into this round's
            # delta before encoding, bank what this encode drops
            acc = (
                [d + r for d, r in zip(delta, ef_residual)]
                if use_ef and ef_residual is not None
                else delta
            )
            payloads = [
                encode_leaf(
                    a, codec, cfg.fed.dcn_topk_ratio,
                    sketch_width=cfg.fed.dcn_sketch_width,
                    sketch_seed=cfg.fed.dcn_sketch_seed, leaf_id=j,
                )
                for j, a in enumerate(acc)
            ]
            if use_ef:
                ef_residual = [
                    a - decode_leaf(p, codec, a.shape, leaf_id=j)
                    for j, (a, p) in enumerate(zip(acc, payloads))
                ]
            wire_payload = encode_payloads(payloads)
            c_uplink.inc(float(sum(payload_nbytes(p) for p in payloads)))
        # the push request captures based_on NOW — the version this
        # round's delta was actually computed against — because the
        # backlog flush below can resync and advance `version` under us
        push_req = {
            "cmd": "push", "worker": worker_id, "round": round_idx,
            "epoch": epoch, "based_on": version, "weight": 1.0,
            "payload": wire_payload, "codec": codec,
            # generated once per contribution; a retry reuses it verbatim
            "push_id": new_push_id(worker_id, round_idx),
        }
        if straggle_s > 0:
            print(
                f"[agg-worker {worker_id}] straggling "
                f"{straggle_s:.1f}s before the round-{round_idx} push",
                flush=True,
            )
            time.sleep(straggle_s)

        # any backlog first (arrival order), so a recovered wire folds
        # contributions oldest-first and this round's push lands last;
        # a bump seen here means the authority restarted while we were
        # degraded — run the recovery handshake before the fresh push
        if unacked and flush_unacked():
            adopted_this_round = resync("incarnation bump") \
                or adopted_this_round
        with trainer.tracer.span("agg.push", round=round_idx,
                                 based_on=version):
            try:
                resp = rpc.call(push_req, op="push")
            except OSError as e:
                # the wire is down: park the contribution (same push_id
                # on the eventual retry) and keep training degraded
                unacked.append(push_req)
                print(
                    f"[agg-worker {worker_id}] authority unreachable for "
                    f"round-{round_idx} push ({e.__class__.__name__}); "
                    f"parked ({len(unacked)} unacked), training on",
                    flush=True,
                )
                check_budget(e)
                resp = None
            except ValueError as e:
                if "rebase" in str(e) or "ahead of" in str(e):
                    # the authority restarted BEHIND us: our base version
                    # no longer exists, so this delta is unfoldable —
                    # drop it and adopt the restored global
                    print(
                        f"[agg-worker {worker_id}] round-{round_idx} push "
                        f"refused ({e}); dropping it and resyncing",
                        flush=True,
                    )
                    adopted_this_round = resync("rebase reply")
                    resp = None
                else:
                    raise
        if resp is not None:
            c_pushes.inc()
            g_staleness.set(float(max(0, int(resp["version"]) - version)))
            if note_incarnation(resp):
                # the restarted authority ACCEPTED this push; re-hello
                # and adopt its restored global before the next round
                adopted_this_round = resync("incarnation bump") \
                    or adopted_this_round

        # bounded wait for a commit NEWER than our base; timing out is
        # the async contract (train on, push staler next round)
        deadline = time.monotonic() + global_wait_s
        new_version, payload, commit_flow = version, None, None
        while time.monotonic() < deadline:
            try:
                resp = rpc.call(
                    {"cmd": "global", "since": version}, op="global"
                )
            except OSError as e:
                # a dead wire makes the poll pointless — proceed stale
                # now, the next round's flush/push probes recovery
                check_budget(e)
                break
            if note_incarnation(resp):
                adopted_this_round = resync("incarnation bump") \
                    or adopted_this_round
                break
            if "payload" in resp:
                new_version, payload = int(resp["version"]), resp["payload"]
                # the commit's flow id rides the reply ENVELOPE: finish
                # the server's commit arrow inside our adoption span
                reply_env = wire.last_reply_envelope()
                if reply_env is not None:
                    commit_flow = reply_env.get("commit_flow")
                break
            time.sleep(poll_s)
        if payload is not None:
            with trainer.tracer.span("agg.adopt", version=new_version,
                                     round=round_idx):
                if commit_flow is not None:
                    trainer.tracer.flow("in", int(commit_flow))
                base = decode_leaves(payload)
                version = new_version
                _adopt(trainer, treedef, base)
            g_version.set(float(version))
        elif not adopted_this_round:
            base = after
            print(
                f"[agg-worker {worker_id}] no commit within "
                f"{global_wait_s:.0f}s after round {round_idx}; "
                "proceeding stale",
                flush=True,
            )

    # one last delivery attempt for anything still parked — after this
    # the contribution is gone with the process, so say so
    if unacked:
        flush_unacked()
        if unacked:
            print(
                f"[agg-worker {worker_id}] exiting with {len(unacked)} "
                "undelivered pushes (authority still unreachable)",
                flush=True,
            )

    # the run()-loop's exit-path bookkeeping: artifacts + final push.
    # One bounded retry each — the exit path is the last chance to bank
    # the round history, so a transient FS/wire hiccup gets a second try
    if trainer._obs_dir is not None:
        from fedrec_tpu.obs import dump_artifacts

        for attempt in (0, 1):
            try:
                dump_artifacts(
                    trainer._obs_dir, registry=trainer.registry,
                    tracer=trainer.tracer,
                )
                break
            except OSError as e:
                if attempt == 0:
                    time.sleep(0.5)
                    continue
                print(f"[agg-worker {worker_id}] could not write obs "
                      f"artifacts: {e}", flush=True)
    if trainer.fleet_pusher is not None:
        trainer.fleet_pusher.push(final=True)
    try:
        trainer.logger.finish()
    except Exception as e:  # noqa: BLE001 — a flush error must not fail the run
        print(f"[agg-worker {worker_id}] logger.finish failed: {e}",
              flush=True)
    return history


def _adopt(trainer, treedef, leaves: list[np.ndarray]) -> None:
    user_params, news_params = jax.tree_util.tree_unflatten(treedef, leaves)
    trainer.set_global_params(user_params, news_params)
