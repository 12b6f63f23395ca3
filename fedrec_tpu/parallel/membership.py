"""Elastic membership: epoch-based world formation over heartbeat leases.

The coordinator deployment's world size was STATIC: ``--num-processes N``
is baked into the jax.distributed rendezvous, so a dead peer either
degrades every survivor to standalone training (N independent forks — the
pre-elastic behavior documented in ``parallel/multihost.py``) or, under
``--supervise``, the whole world respawn-loops until the dead peer comes
back. Neither shrinks. Production federated systems treat membership as
dynamic (PAPERS.md: "Scaling Federated Learning for Fine-tuning of Large
Language Models"); this module supplies the missing control plane:

* :class:`MembershipServer` — a tiny threaded TCP JSON-lines service (the
  same wire idiom as the serving admin channel) owning a **monotonically
  increasing membership epoch**. Each worker holds a heartbeat **lease**;
  an expired lease marks the current epoch stale. Epoch *e+1* forms from
  the workers that have (re-)joined: immediately when the full target
  complement is back, or after ``formation_grace_ms`` with at least
  ``min_world`` joiners (the **shrink-and-continue** path). A join that
  arrives while an epoch is healthy flags a **reform**, which the epoch's
  rank-0 worker broadcasts to the whole world at the next round boundary
  (the rejoin path — see ``CoordinatorRuntime.start_round``).

* :class:`MembershipClient` — blocking calls (``join``/``heartbeat``/
  ``leave``/``status``) plus a daemon lease-renewal thread. The join
  assignment carries ``(epoch, rank, world, coordinator_address)``; the
  coordinator address is the rank-0 worker's OWN pre-bound candidate, a
  FRESH port per epoch, so a respawned worker can never re-exec into the
  previous (dying) world's rendezvous — the failure the pre-elastic
  supervisor could only retry through.

Ranks are dense ``0..world-1``, assigned by sorting stable worker ids
(numeric ids numerically), so a surviving worker keeps the lowest ranks
and the server role (rank 0) moves only when the previous rank-0 died.
Worker identity is the supervisor-stable ``--process-id``; snapshots are
keyed by it (``local_state_w<ID>``), not by the per-epoch rank.

The degenerate contract: a deployment that never passes ``--membership``
never touches this module — byte-identical behavior to the fixed world.

Run standalone (the elastic smoke's service process)::

    python -m fedrec_tpu.parallel.membership 127.0.0.1:9123 \
        --target-world 4 --lease-ms 6000 --formation-grace-ms 4000
"""

from __future__ import annotations

import socket
import threading
import time
from dataclasses import dataclass, field


def _now() -> float:
    return time.monotonic()


def free_port(host: str = "127.0.0.1") -> int:
    """An OS-assigned free TCP port on ``host`` — the joiner's coordinator
    candidate. The tiny bind->release race (another process grabbing the
    port before jax binds it) is covered by the bounded rendezvous retry:
    a failed bring-up re-joins and draws a fresh port."""
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


@dataclass(frozen=True)
class EpochAssignment:
    """One worker's seat in one membership epoch."""

    epoch: int
    rank: int
    world: int
    coordinator: str        # host:port of THIS epoch's jax rendezvous
    lease_ms: float
    heartbeat_ms: float

    def to_dict(self) -> dict:
        return {
            "epoch": self.epoch, "rank": self.rank, "world": self.world,
            "coordinator": self.coordinator, "lease_ms": self.lease_ms,
            "heartbeat_ms": self.heartbeat_ms,
        }


def _rank_order(worker_ids) -> list[str]:
    """Dense rank assignment: numeric ids sort numerically (worker "0"
    keeps rank 0 while it lives), non-numeric ids lexically after."""
    def key(w: str):
        try:
            return (0, int(w), w)
        except ValueError:
            return (1, 0, w)

    return sorted(worker_ids, key=key)


@dataclass
class _Member:
    worker: str
    expires_at: float
    rank: int


@dataclass
class _Joiner:
    worker: str
    coord_candidate: str
    arrived_at: float
    event: threading.Event = field(default_factory=threading.Event)
    assignment: EpochAssignment | None = None


class MembershipServer:
    """The epoch/lease bookkeeper. One instance per federation.

    Thread model: one listener thread accepts connections and answers each
    request inline (requests are tiny; ``join`` parks the connection's
    thread on an event until formation), plus one reaper thread that
    expires leases and closes formation windows. All state behind one
    lock; formation is the only compound transition.
    """

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        target_world: int = 0,
        min_world: int | None = None,
        lease_ms: float | None = None,
        heartbeat_ms: float | None = None,
        formation_grace_ms: float | None = None,
        collector=None,
        obs_dir: str | None = None,
    ):
        self.host = host
        self.port = port
        self.target_world = int(target_world)
        # fleet telemetry riding the membership port (one control-plane
        # address per federation): a fedrec_tpu.obs.fleet
        # TelemetryCollector answers telemetry_push/telemetry_status here
        self.collector = collector
        # the service's OWN obs artifact trio (metrics.jsonl/trace.json/
        # prometheus.txt) — its shrink/rejoin/lease counters used to be
        # visible only second-hand through worker mirror gauges
        self.obs_dir = obs_dir
        # None = adopt from the first join request that carries a policy
        # (the workers' shared ``fed.elastic`` section is then the ONE
        # source of lease/formation policy); an explicit server-side value
        # wins over every joiner
        self._min_world = min_world
        self._lease_ms = lease_ms
        self._heartbeat_ms = heartbeat_ms
        self._formation_grace_ms = formation_grace_ms
        self._lock = threading.Lock()
        self.epoch = -1                       # no world formed yet
        self._members: dict[str, _Member] = {}
        self._joiners: dict[str, _Joiner] = {}
        self._window_opened: float | None = None
        self._reform_needed = False
        # ---- counters the status/report surface exposes
        self.shrinks = 0
        self.rejoins = 0
        self.lease_misses = 0
        self.epoch_history: list[dict] = []   # [{"epoch": e, "world": n}]
        self._srv: socket.socket | None = None
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._instrument()

    # ------------------------------------------------- effective policy
    @property
    def min_world(self) -> int:
        return max(int(self._min_world or 1), 1)

    @property
    def lease_ms(self) -> float:
        return float(self._lease_ms or 15000.0)

    @property
    def heartbeat_ms(self) -> float:
        return float(self._heartbeat_ms or 5000.0)

    @property
    def formation_grace_ms(self) -> float:
        return float(self._formation_grace_ms or 10000.0)

    def _adopt_policy_locked(self, policy: dict) -> None:
        """Fill any server-side ``None`` policy knob from a joiner's
        ``fed.elastic`` section — first writer wins, explicit server
        flags always win (they are not ``None``)."""
        if self._lease_ms is None and policy.get("lease_ms"):
            self._lease_ms = float(policy["lease_ms"])
        if self._heartbeat_ms is None and policy.get("heartbeat_ms"):
            self._heartbeat_ms = float(policy["heartbeat_ms"])
        if (
            self._formation_grace_ms is None
            and policy.get("formation_grace_ms")
        ):
            self._formation_grace_ms = float(policy["formation_grace_ms"])
        if self._min_world is None and policy.get("min_world"):
            self._min_world = int(policy["min_world"])

    # --------------------------------------------------------------- obs
    def _instrument(self) -> None:
        """The service's registry instruments — REAL monotonic counters
        in its own process (the worker-side mirror gauges these replace
        under-reported across worker respawns; see
        docs/OBSERVABILITY.md, Membership)."""
        from fedrec_tpu.obs import get_registry, get_tracer

        reg = get_registry()
        self._tracer = get_tracer()
        self._m_shrinks = reg.counter(
            "fed.membership_shrinks_total",
            "epochs that formed SMALLER than their predecessor "
            "(shrink-and-continue events; service-owned)",
        )
        self._m_rejoins = reg.counter(
            "fed.membership_rejoins_total",
            "workers that re-entered a later epoch after missing one "
            "(service-owned)",
        )
        self._m_lease_misses = reg.counter(
            "fed.membership_lease_misses_total",
            "heartbeat leases the service expired — the failure detector "
            "firing (service-owned)",
        )
        self._g_epoch = reg.gauge(
            "fed.membership_epoch",
            "membership epoch this worker's world formed at",
        )
        self._g_world = reg.gauge(
            "fed.membership_world",
            "world size of this worker's membership epoch",
        )

    def dump_obs(self) -> None:
        """Write/refresh the service's artifact trio (no-op without
        ``obs_dir``); called on membership-state changes by the
        standalone main loop and on shutdown, so the membership timeline
        is inspectable while the federation is still running.  The event
        log is size-rotated (one ``.1`` level, same policy as
        ``obs.jsonl_max_mb``) so a long-lived control plane cannot grow
        it without bound."""
        if not self.obs_dir:
            return
        from pathlib import Path

        from fedrec_tpu.obs import dump_artifacts, rotate_jsonl

        try:
            rotate_jsonl(Path(self.obs_dir) / "metrics.jsonl", 64.0)
            dump_artifacts(self.obs_dir)
        except OSError:
            pass  # a full disk must not take the control plane down

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "MembershipServer":
        srv = socket.create_server((self.host, self.port))
        srv.settimeout(0.5)
        self._srv = srv
        self.port = srv.getsockname()[1]
        t = threading.Thread(target=self._accept_loop, daemon=True)
        r = threading.Thread(target=self._reaper_loop, daemon=True)
        t.start()
        r.start()
        self._threads = [t, r]
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._srv is not None:
            try:
                self._srv.close()
            except OSError:
                pass
        for t in self._threads:
            t.join(timeout=2.0)
        # release any parked joiners so their worker threads exit
        with self._lock:
            for j in self._joiners.values():
                j.event.set()
        self.dump_obs()

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    # ------------------------------------------------------------- serving
    def _accept_loop(self) -> None:
        assert self._srv is not None
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True
            ).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        # ONE wire-protocol implementation for the control plane: the
        # shared JSON-lines exchange (obs.fleet also fronts the telemetry
        # collector with it, so the two servers cannot drift). The long
        # timeout is membership-specific: a ``join`` parks the
        # connection's thread until epoch formation.
        from fedrec_tpu.obs.fleet import serve_json_line

        serve_json_line(conn, self._handle, timeout_s=300.0)

    def _handle(self, req: dict) -> dict:
        cmd = req.get("cmd")
        if cmd in ("telemetry_push", "telemetry_status"):
            if self.collector is None:
                return {
                    "error": "no telemetry collector attached — start the "
                             "service with --telemetry-dir (or run a "
                             "standalone obs.fleet CollectorServer)"
                }
            return self.collector.handle(req)
        if cmd == "heartbeat":
            return self._heartbeat(str(req["worker"]), int(req.get("epoch", -1)))
        if cmd == "join":
            return self._join(
                str(req["worker"]), str(req.get("coord", "")),
                req.get("policy") or {},
            )
        if cmd == "leave":
            return self._leave(str(req["worker"]))
        if cmd == "status":
            return self.status()
        return {"error": f"unknown cmd {cmd!r}"}

    # ------------------------------------------------------------ protocol
    def _heartbeat(self, worker: str, epoch: int) -> dict:
        with self._lock:
            m = self._members.get(worker)
            if m is not None and epoch == self.epoch:
                m.expires_at = _now() + self.lease_ms / 1e3
            # a heartbeat from a stale epoch gets reform=True: that worker
            # missed a formation and must leave/rejoin
            reform = self._reform_needed or epoch != self.epoch
            return {"epoch": self.epoch, "reform": bool(reform)}

    def _join(self, worker: str, coord: str, policy: dict) -> dict:
        self._tracer.instant("membership_worker_join", worker=str(worker))
        with self._lock:
            self._adopt_policy_locked(policy)
            j = _Joiner(worker=worker, coord_candidate=coord, arrived_at=_now())
            self._joiners[worker] = j
            # joining supersedes any live lease (the worker left its world)
            self._members.pop(worker, None)
            if self._members and self.epoch >= 0:
                # someone knocking while members are still live: a NEW
                # worker wanting in, or a member's fast respawn whose old
                # incarnation died before its lease expired — either way
                # the live world must reform at its next round boundary
                # (during a mass reformation this is a no-op: the flag is
                # already set and formation clears it)
                self._reform_needed = True
            if self._window_opened is None:
                self._window_opened = _now()
            self._maybe_form_locked()
        # park outside the lock until formation (or stop/supersession)
        deadline = _now() + 3600.0
        while not j.event.wait(timeout=0.2):
            if self._stop.is_set() or _now() > deadline:
                return {"error": "membership server stopping"}
            with self._lock:
                if j.event.is_set():
                    # the epoch formed between the wait's timeout and this
                    # lock: formation clears _joiners, which is not a
                    # supersession
                    break
                if self._joiners.get(worker) is not j:
                    # the worker timed out client-side and re-joined: the
                    # NEW join owns the seat; this connection's thread must
                    # exit instead of polling the lock for up to an hour
                    return {"error": "join superseded by a newer join "
                                     "from this worker"}
                self._maybe_form_locked()
        if j.assignment is None:
            return {"error": "membership server stopping"}
        return j.assignment.to_dict()

    def _leave(self, worker: str) -> dict:
        with self._lock:
            self._members.pop(worker, None)
            j = self._joiners.pop(worker, None)
            if j is not None:
                j.event.set()
            # a clean leave of the FINAL member is a finished run, not a
            # death: no reform, no shrink accounting
            return {"ok": True, "epoch": self.epoch}

    # ----------------------------------------------------------- formation
    def _expected_world(self) -> int:
        """How many joiners formation waits for before the grace window
        closes: the full target complement (every configured worker back)
        or, once a smaller epoch exists, everyone known-alive."""
        if self.target_world > 0:
            return self.target_world
        return max(len(self._members) + len(self._joiners), self.min_world)

    def _maybe_form_locked(self) -> None:
        n = len(self._joiners)
        if n == 0 or self._window_opened is None:
            return
        window_s = self.formation_grace_ms / 1e3
        full = n >= self._expected_world()
        # live members that have NOT re-joined yet: forming now would
        # orphan them mid-round — wait for them to reach their boundary
        # (their leases go stale if they died; the reaper prunes them)
        missing_live = [w for w in self._members if w not in self._joiners]
        if not full and (missing_live or _now() - self._window_opened < window_s):
            return
        if n < self.min_world:
            return
        self._form_locked()

    def _form_locked(self) -> None:
        joiners = dict(self._joiners)
        order = _rank_order(joiners)
        prev = self.epoch_history[-1] if self.epoch_history else None
        prev_world = prev["world"] if prev else 0
        prev_set = set(prev.get("workers", ())) if prev else set()
        self.epoch += 1
        world = len(order)
        coordinator = joiners[order[0]].coord_candidate or "127.0.0.1:0"
        expires = _now() + self.lease_ms / 1e3
        self._members = {
            w: _Member(worker=w, expires_at=expires, rank=r)
            for r, w in enumerate(order)
        }
        if self.epoch > 0:
            if world < prev_world:
                self.shrinks += 1
                self._m_shrinks.inc()
            rejoined = set(order) - prev_set
            if prev_set and rejoined:
                self.rejoins += len(rejoined)
                self._m_rejoins.inc(len(rejoined))
        self.epoch_history.append(
            {"epoch": self.epoch, "world": world, "workers": list(order)}
        )
        self._g_epoch.set(float(self.epoch))
        self._g_world.set(float(world))
        # the formation instant is the merged fleet trace's membership
        # timeline (kill -> shrink -> rejoin reads straight off the track)
        self._tracer.instant(
            "membership_epoch_formed",
            epoch=self.epoch, world=world, workers=list(order),
        )
        self._joiners.clear()
        self._window_opened = None
        self._reform_needed = False
        for r, w in enumerate(order):
            j = joiners[w]
            j.assignment = EpochAssignment(
                epoch=self.epoch, rank=r, world=world,
                coordinator=coordinator, lease_ms=self.lease_ms,
                heartbeat_ms=self.heartbeat_ms,
            )
            j.event.set()

    def _reaper_loop(self) -> None:
        while not self._stop.wait(
            # per-iteration: the lease policy may arrive with the first join
            timeout=max(self.lease_ms / 4e3, 0.05)
        ):
            with self._lock:
                now = _now()
                dead = [w for w, m in self._members.items()
                        if m.expires_at < now]
                for w in dead:
                    del self._members[w]
                    self.lease_misses += 1
                    self._m_lease_misses.inc()
                    self._tracer.instant(
                        "membership_lease_expired", worker=str(w)
                    )
                    self._reform_needed = True
                self._maybe_form_locked()

    # -------------------------------------------------------------- status
    def status(self) -> dict:
        with self._lock:
            return {
                "epoch": self.epoch,
                "world": len(self._members),
                "members": sorted(self._members),
                "pending": sorted(self._joiners),
                "reform_pending": bool(self._reform_needed),
                "shrinks": self.shrinks,
                "rejoins": self.rejoins,
                "lease_misses": self.lease_misses,
                "epoch_history": [
                    {"epoch": h["epoch"], "world": h["world"]}
                    for h in self.epoch_history
                ],
            }


# ------------------------------------------------------------------ client
class MembershipError(RuntimeError):
    """The membership service refused or could not answer a request."""


class MembershipClient:
    """One worker's view of the membership service.

    All calls are one-shot request/response over a fresh TCP connection
    (the service is a control plane at round cadence, not a data path).
    ``start_heartbeat`` runs the lease-renewal daemon; ``reform_pending``
    is the latched flag the epoch's rank-0 worker reads at each round
    boundary to trigger the reformation broadcast.
    """

    def __init__(
        self,
        address: str,
        worker_id: str,
        join_timeout_s: float = 180.0,
        rpc_timeout_s: float = 10.0,
    ):
        host, port = address.rsplit(":", 1)
        self.host, self.port = host, int(port)
        self.worker_id = str(worker_id)
        self.join_timeout_s = float(join_timeout_s)
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.assignment: EpochAssignment | None = None
        self._reform = threading.Event()
        self._stop = threading.Event()
        self._hb_thread: threading.Thread | None = None
        self.heartbeat_failures = 0

    # ---------------------------------------------------------------- rpcs
    def _call(self, req: dict, timeout_s: float | None = None) -> dict:
        timeout = timeout_s if timeout_s is not None else self.rpc_timeout_s
        # the shared client wire helper (obs.fleet also pushes telemetry
        # with it): transport failures surface as OSError, protocol /
        # {"error": ...} replies as ValueError -> MembershipError.  The
        # helper also carries the obs.wire trace-context envelope, so
        # every control-plane edge (join/heartbeat/leave) gets per-edge
        # RTT + clock-offset telemetry for free
        from fedrec_tpu.obs.fleet import request_json_line

        try:
            return request_json_line(self.host, self.port, req, timeout)
        except ValueError as e:
            raise MembershipError(str(e)) from e

    def _local_host_toward_service(self) -> str:
        """The local interface address that ROUTES TO the membership
        service — the right host to advertise in this worker's
        jax-rendezvous candidate. Loopback only when the service itself is
        on loopback; on a multi-machine federation this is the worker's
        routable address, so a non-rank-0 peer can actually reach the
        epoch's coordinator."""
        try:
            with socket.create_connection(
                (self.host, self.port), timeout=self.rpc_timeout_s
            ) as s:
                return s.getsockname()[0]
        except OSError:
            return "127.0.0.1"

    def join(
        self,
        coord_candidate: str | None = None,
        policy: dict | None = None,
    ) -> EpochAssignment:
        """Block until the next epoch forms and return this worker's seat.
        ``coord_candidate`` is a ``host:port`` this worker could host the
        jax rendezvous on (rank 0's candidate wins); defaults to a fresh
        port on the interface that routes to the membership service.
        ``policy`` carries the worker's ``fed.elastic`` lease/formation
        knobs — a server started without explicit flags adopts the first
        joiner's policy, so the config section is the one source of truth
        in the common deployment."""
        if coord_candidate is None:
            adv = self._local_host_toward_service()
            coord_candidate = f"{adv}:{free_port(adv)}"
        resp = self._call(
            {
                "cmd": "join", "worker": self.worker_id,
                "coord": coord_candidate, "policy": policy or {},
            },
            timeout_s=self.join_timeout_s,
        )
        self.assignment = EpochAssignment(
            epoch=int(resp["epoch"]), rank=int(resp["rank"]),
            world=int(resp["world"]), coordinator=str(resp["coordinator"]),
            lease_ms=float(resp["lease_ms"]),
            heartbeat_ms=float(resp["heartbeat_ms"]),
        )
        self._reform.clear()
        return self.assignment

    def heartbeat(self) -> dict:
        epoch = self.assignment.epoch if self.assignment else -1
        resp = self._call(
            {"cmd": "heartbeat", "worker": self.worker_id, "epoch": epoch}
        )
        if resp.get("reform"):
            self._reform.set()
        return resp

    def leave(self) -> None:
        try:
            self._call({"cmd": "leave", "worker": self.worker_id})
        except (OSError, MembershipError):
            pass  # a dead service cannot block a clean exit

    def status(self) -> dict:
        return self._call({"cmd": "status"})

    # ----------------------------------------------------------- heartbeat
    def start_heartbeat(self) -> None:
        """Renew the lease every ``heartbeat_ms`` on a daemon thread,
        beginning with an IMMEDIATE renewal: leases start ticking at epoch
        formation, and the jax rendezvous between join and the first
        round (transport probe included) can outlast ``lease_ms`` — call
        this right after :meth:`join`, before the rendezvous, or a slow
        bring-up reads as a death and reforms the world it just formed.
        A failed renewal counts ``heartbeat_failures`` (the worker-side
        ``fed.lease_heartbeat_failures`` gauge) but never raises — a
        transiently unreachable service must not kill training; the
        server-side lease expiry is the authoritative failure detector."""
        if self._hb_thread is not None:
            return
        interval = (
            self.assignment.heartbeat_ms / 1e3 if self.assignment else 5.0
        )

        def loop():
            while True:
                try:
                    self.heartbeat()
                except (OSError, MembershipError, ValueError):
                    self.heartbeat_failures += 1
                if self._stop.wait(timeout=interval):
                    return

        self._hb_thread = threading.Thread(target=loop, daemon=True)
        self._hb_thread.start()

    @property
    def reform_pending(self) -> bool:
        return self._reform.is_set()

    def close(self) -> None:
        self._stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2.0)
            self._hb_thread = None


def elastic_policy(elastic_cfg) -> dict:
    """The ``fed.elastic`` knobs a worker ships in its join request."""
    return {
        "lease_ms": float(elastic_cfg.lease_ms),
        "heartbeat_ms": float(elastic_cfg.heartbeat_ms),
        "formation_grace_ms": float(elastic_cfg.formation_grace_ms),
        "min_world": int(elastic_cfg.min_world),
    }


def publish_membership_metrics(
    assignment: EpochAssignment | None = None,
    client: "MembershipClient | None" = None,
    reforms: int = 0,
) -> None:
    """THE one registration site for the worker-side membership metrics
    (docs/OBSERVABILITY.md, Membership): the epoch/world gauges from this
    worker's seat, this worker's failed lease renewals, and its reform
    departures.  The service-owned totals (shrinks / rejoins / lease
    misses) live as REAL counters in the service's own obs artifact trio
    (``--obs-dir`` on the standalone service) — the pre-PR-13 workaround
    of mirroring them into each worker as gauges is retired: worker
    registries restart on respawn while the service's history does not,
    and the fleet report reads the service's artifacts directly.
    """
    from fedrec_tpu.obs import get_registry

    reg = get_registry()
    if assignment is not None:
        reg.gauge(
            "fed.membership_epoch",
            "membership epoch this worker's world formed at",
        ).set(float(assignment.epoch))
        reg.gauge(
            "fed.membership_world",
            "world size of this worker's membership epoch",
        ).set(float(assignment.world))
    if client is not None:
        reg.gauge(
            "fed.lease_heartbeat_failures",
            "lease renewals THIS worker failed to deliver",
        ).set(float(client.heartbeat_failures))
    if reforms:
        reg.counter(
            "fed.membership_reforms_total",
            "reformation departures this worker performed (save, leave, "
            "rejoin at the next epoch)",
        ).inc(float(reforms))


def main(argv: list[str] | None = None) -> int:
    """Standalone service process (the elastic smoke's control plane)."""
    import argparse

    parser = argparse.ArgumentParser(
        description="fedrec elastic-membership service"
    )
    parser.add_argument("address", metavar="HOST:PORT")
    parser.add_argument("--target-world", type=int, default=0,
                        help="full complement: forming waits for this many "
                             "joiners before the grace window applies")
    # policy flags default to None = adopt the first joiner's fed.elastic
    # section; pass them explicitly to pin policy server-side
    parser.add_argument("--min-world", type=int, default=None)
    parser.add_argument("--lease-ms", type=float, default=None)
    parser.add_argument("--heartbeat-ms", type=float, default=None)
    parser.add_argument("--formation-grace-ms", type=float, default=None)
    parser.add_argument("--obs-dir", default=None,
                        help="write the service's OWN obs artifact trio "
                             "here (refreshed every few seconds and on "
                             "shutdown) — the authoritative membership "
                             "timeline the fleet report/trace reads; name "
                             "it worker_membership under the fleet's "
                             "shared obs root so fedrec-obs fleet "
                             "discovers it")
    parser.add_argument("--telemetry-dir", default=None,
                        help="also act as the fleet telemetry collector "
                             "(fedrec_tpu.obs.fleet) on THIS port: "
                             "workers' obs.fleet.collector pushes land "
                             "as worker_* dirs under this directory")
    parser.add_argument("--watch", action="store_true",
                        help="evaluate the fleet-level watch rules "
                             "(fedrec_tpu.obs.watch.FleetRules: persistent "
                             "straggler, world below target, quorum-wait "
                             "growth, stalled commit) against incoming "
                             "telemetry pushes and the membership world; "
                             "alert records land under the telemetry dir's "
                             "worker_fleet/ (needs --telemetry-dir)")
    args = parser.parse_args(argv)
    host, port = args.address.rsplit(":", 1)
    collector = None
    if args.telemetry_dir:
        from fedrec_tpu.obs.fleet import TelemetryCollector

        collector = TelemetryCollector(args.telemetry_dir)
    rules = None
    if args.watch and collector is not None:
        from pathlib import Path

        from fedrec_tpu.obs.watch import FleetRules

        fleet_dir = Path(args.telemetry_dir) / "worker_fleet"
        fleet_dir.mkdir(parents=True, exist_ok=True)
        rules = FleetRules(
            target_world=args.target_world,
            jsonl_path=fleet_dir / "metrics.jsonl",
        )
        collector.rules = rules
    if args.obs_dir:
        from fedrec_tpu.obs.fleet import set_fleet_identity

        set_fleet_identity(worker="membership")
    server = MembershipServer(
        host=host, port=int(port),
        target_world=args.target_world, min_world=args.min_world,
        lease_ms=args.lease_ms, heartbeat_ms=args.heartbeat_ms,
        formation_grace_ms=args.formation_grace_ms,
        collector=collector, obs_dir=args.obs_dir,
    ).start()
    print(f"[membership] serving on {server.address}", flush=True)

    # a SIGTERM'd service (the smoke's cleanup kill) must still run the
    # finally below — the final artifact dump is the membership timeline
    import signal

    def _term(signum, frame):  # noqa: ARG001 — signal handler signature
        raise SystemExit(0)

    try:
        signal.signal(signal.SIGTERM, _term)
    except (ValueError, OSError):
        pass  # not the main thread / unsupported platform: best effort
    try:
        # change-driven artifact refresh: a snapshot line per membership
        # EVENT (join/leave/expiry/formation), not per poll tick — an
        # idle federation's event log stays flat
        last_status = None
        while True:
            time.sleep(5)
            status = (
                server.status() if (args.obs_dir or rules is not None)
                else None
            )
            if rules is not None and status is not None:
                # the world-below-target rule only the membership service
                # can evaluate: it owns the authoritative world count
                rules.observe_world(status["world"])
            if args.obs_dir and status != last_status:
                server.dump_obs()
                last_status = status
    except (KeyboardInterrupt, SystemExit):
        pass
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
