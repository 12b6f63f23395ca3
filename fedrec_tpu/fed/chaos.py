"""Deterministic fault injection: a seeded plan of client & host faults.

At production scale client failure is the steady state, not the exception
(FedJAX, arxiv 2108.02117, treats client subsampling/failure as a
first-class simulation primitive) — so the robustness machinery needs a
way to be *exercised*, reproducibly. A :class:`FaultPlan` is a pure
function of ``(chaos config, round index)``: the same plan produces the
same faults on every run and on every rollback replay, so

* a chaos run is bit-identical when re-run (the acceptance bar for the
  chaos smoke), and
* the Trainer's quarantine/rollback replay re-encounters the exact fault
  it rolled back from, proving the quarantine — not luck — saved the
  round.

Client-side faults come in two flavors:

* **participation faults** (``drop``, ``straggle``): the client's round
  weight is forced to 0 — it trains but its contribution is excluded,
  exactly the failure mode the reference dies on
  (Final_Report.pdf VII.a). Stragglers can additionally cost a host-side
  delay (``straggle_ms``).
* **update faults** (``nan``, ``scale``, ``flip``): applied as masks at
  the optimizer-update boundary INSIDE the jitted step. The per-client
  ``(code, scale)`` vectors ride the batch dict as ``chaos.code`` /
  ``chaos.scale`` arrays, so the step compiles the fault arithmetic and
  the flight recorder's batch ring captures them — ``fedrec-obs replay`` re-injects
  the fault for free.

Host-level faults (``kill_round``/``kill_process``, guarded by an
on-disk marker so a resumed world doesn't re-die; ``torn_snapshot_round``)
live in the coordinator CLI, which reads the same config section.

**Wire-level faults** (``chaos.wire_faults`` + ``chaos.wire_seed``)
exercise the TRANSPORT instead of the update math: a seeded
:class:`WireFaultPlan` drives a :class:`ChaosProxy` — a TCP
man-in-the-middle fronting the commit authority or membership service —
that drops, delays, tears mid-message, duplicates, or fully partitions
the one-shot JSON-lines exchanges passing through it, per connection and
per time window.  Fault draws are pure in ``(wire_seed, connection
index)``, so a churn soak's fault schedule replays bit-identically; with
no plan (or outside every window) the proxy forwards every byte
VERBATIM — the passthrough is pinned byte-identical in
``tests/test_rpc.py``, so chaos-off runs cannot differ by construction.
"""

from __future__ import annotations

import socket
import threading
import time
from dataclasses import dataclass
from typing import Any

import numpy as np

# update-fault codes carried in the batch's chaos.code vector; 0 = none
FAULT_CODES = {"nan": 1, "scale": 2, "flip": 3}


@dataclass(frozen=True)
class RoundFaults:
    """One round's resolved faults (pure function of plan + round)."""

    weight_mask: np.ndarray            # (C,) float32 0/1 — drop+straggle
    codes: np.ndarray                  # (C,) int32 update-fault codes
    scales: np.ndarray                 # (C,) float32 (code==scale multiplier)
    dropped: tuple = ()
    straggled: tuple = ()
    injected: tuple = ()               # ((kind, client), ...) update faults

    @property
    def any(self) -> bool:
        return bool(
            self.dropped or self.straggled or self.injected
        )


def parse_faults(spec: str, num_clients: int) -> list[tuple[str, int | None, int, float]]:
    """Parse the ``faults`` DSL: comma list of ``kind@round:client[xscale]``
    (``round`` may be ``*`` = every round). Raises on malformed entries so a
    typo'd plan fails at build time, not silently fault-free."""
    out: list[tuple[str, int | None, int, float]] = []
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        try:
            kind, rest = item.split("@", 1)
            round_s, client_s = rest.split(":", 1)
            scale = 1.0
            if "x" in client_s:
                client_s, scale_s = client_s.split("x", 1)
                scale = float(scale_s)
            rnd = None if round_s == "*" else int(round_s)
            client = int(client_s)
        except ValueError:
            raise ValueError(
                f"chaos.faults entry {item!r} is not "
                "'kind@round:client[xscale]' (e.g. 'nan@2:3,scale@*:5x100')"
            ) from None
        if kind not in FAULT_CODES:
            raise ValueError(
                f"chaos.faults entry {item!r}: unknown kind {kind!r}; "
                f"expected one of {sorted(FAULT_CODES)}"
            )
        if not 0 <= client < num_clients:
            raise ValueError(
                f"chaos.faults entry {item!r}: client {client} out of range "
                f"[0, {num_clients})"
            )
        out.append((kind, rnd, client, scale))
    return out


class FaultPlan:
    """Seeded, deterministic per-round fault schedule.

    ``round_faults(r)`` is idempotent: the random drop/straggle draws are
    derived from ``default_rng([seed, r])``, never from mutable state, so
    rollback replays and re-runs see identical faults.
    """

    def __init__(self, chaos_cfg: Any, num_clients: int):
        self.cfg = chaos_cfg
        self.num_clients = int(num_clients)
        self.seed = int(chaos_cfg.seed)
        self.drop_rate = float(chaos_cfg.drop_rate)
        self.straggle_rate = float(chaos_cfg.straggle_rate)
        self.specs = parse_faults(chaos_cfg.faults, self.num_clients)

    def round_faults(self, round_idx: int) -> RoundFaults:
        c = self.num_clients
        mask = np.ones((c,), np.float32)
        dropped: list[int] = []
        straggled: list[int] = []
        if self.drop_rate > 0 or self.straggle_rate > 0:
            rng = np.random.default_rng([self.seed, int(round_idx)])
            u = rng.random(c)
            # one draw decides both: [0, drop) drops, [drop, drop+straggle)
            # straggles — so the rates compose without double-failing
            for i in range(c):
                if u[i] < self.drop_rate:
                    dropped.append(i)
                    mask[i] = 0.0
                elif u[i] < self.drop_rate + self.straggle_rate:
                    straggled.append(i)
                    mask[i] = 0.0
        codes = np.zeros((c,), np.int32)
        scales = np.ones((c,), np.float32)
        injected: list[tuple[str, int]] = []
        for kind, rnd, client, scale in self.specs:
            if rnd is not None and rnd != round_idx:
                continue
            codes[client] = FAULT_CODES[kind]
            scales[client] = np.float32(scale)
            injected.append((kind, client))
        return RoundFaults(
            weight_mask=mask,
            codes=codes,
            scales=scales,
            dropped=tuple(dropped),
            straggled=tuple(straggled),
            injected=tuple(injected),
        )

    def batch_keys(self, round_idx: int) -> dict[str, np.ndarray]:
        """The per-client fault vectors a chaos-enabled step expects in
        every batch dict (``train.step`` applies them at the update
        boundary)."""
        rf = self.round_faults(round_idx)
        return {"chaos.code": rf.codes, "chaos.scale": rf.scales}

    # ---------------------------------------------- population-level faults
    def is_flaky(self, client_id: int) -> bool:
        """Whether a LOGICAL client belongs to the seeded flaky cohort —
        a fixed ``pop_flaky_fraction`` subset of the population whose
        per-round dropout probability is ``pop_flaky_drop_rate`` instead
        of ``pop_drop_rate`` (chronically bad connectivity, not bad
        luck). Pure in ``(seed, client_id)``: flakiness is a property of
        the client, stable across rounds and replays."""
        frac = float(getattr(self.cfg, "pop_flaky_fraction", 0.0))
        if frac <= 0:
            return False
        u = np.random.default_rng([self.seed, int(client_id), 0xF1A]).random()
        return bool(u < frac)

    def population_report(
        self, round_idx: int, client_ids, attempt: int = 0
    ) -> tuple[np.ndarray, np.ndarray]:
        """Simulate one round's reporting behavior for sampled LOGICAL
        clients: ``(dropped, latency_ms)`` — ``dropped[i]`` True when
        client ``client_ids[i]`` never starts (over-selection's target),
        ``latency_ms[i]`` its simulated report latency (the round
        deadline's target; 0 when ``pop_straggle_ms`` is off).

        Deterministic per ``(seed, round_idx, attempt, client_id)``: the
        same client gets the same fate in both the cohort-packing draw and
        the per-round weight computation, replays are bit-identical, and a
        quorum re-draw (``attempt`` bump) rolls genuinely fresh dice.
        """
        return population_report(self, round_idx, client_ids, attempt)


def rejoin_holdoff(chaos_cfg: Any, worker_id: int, marker_dir) -> float:
    """Kill->shrink->rejoin scripting for the elastic deployment: the
    seconds a respawned, chaos-killed worker should wait BEFORE rejoining
    the membership service (``chaos.rejoin_delay_s``), or 0.

    Marker-guarded like the kill itself: only the worker named by
    ``chaos.kill_process``, only AFTER its kill marker exists (it actually
    died), and only ONCE (``chaos_rejoin_delayed_p<ID>`` written on the
    first holdoff) — later reform-driven respawns of the same worker
    rejoin immediately. The holdoff is what makes the shrink epoch
    observable before the rejoin epoch: without it a fast respawn races
    straight back into the survivors' formation window and the world
    re-forms at full size in one step.
    """
    from pathlib import Path

    if (
        not getattr(chaos_cfg, "enabled", False)
        or float(getattr(chaos_cfg, "rejoin_delay_s", 0.0)) <= 0
        or int(getattr(chaos_cfg, "kill_process", -1)) != int(worker_id)
    ):
        return 0.0
    marker_dir = Path(marker_dir)
    killed = marker_dir / f"chaos_killed_p{int(worker_id)}"
    delayed = marker_dir / f"chaos_rejoin_delayed_p{int(worker_id)}"
    if not killed.exists() or delayed.exists():
        return 0.0
    marker_dir.mkdir(parents=True, exist_ok=True)
    delayed.write_text(str(chaos_cfg.rejoin_delay_s))
    return float(chaos_cfg.rejoin_delay_s)


def population_report(
    plan: "FaultPlan | None", round_idx: int, client_ids, attempt: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """Module-level variant tolerating ``plan=None`` (chaos disabled):
    nobody drops, everybody reports instantly."""
    ids = np.asarray(client_ids, np.int64)
    dropped = np.zeros(ids.shape, bool)
    latency = np.zeros(ids.shape, np.float64)
    if plan is None:
        return dropped, latency
    cfg = plan.cfg
    drop_rate = float(getattr(cfg, "pop_drop_rate", 0.0))
    flaky_rate = float(getattr(cfg, "pop_flaky_drop_rate", 0.5))
    straggle_ms = float(getattr(cfg, "pop_straggle_ms", 0.0))
    straggle_sigma = float(getattr(cfg, "pop_straggle_sigma", 1.0))
    any_flaky = float(getattr(cfg, "pop_flaky_fraction", 0.0)) > 0
    if drop_rate <= 0 and not any_flaky and straggle_ms <= 0:
        return dropped, latency
    for i, cid in enumerate(ids):
        rng = np.random.default_rng(
            [plan.seed, int(round_idx), int(attempt), int(cid), 0x90B]
        )
        p = flaky_rate if (any_flaky and plan.is_flaky(int(cid))) else drop_rate
        dropped[i] = rng.random() < p
        if straggle_ms > 0:
            # lognormal with median = pop_straggle_ms: half the population
            # reports faster, the heavy tail is what deadlines cut
            latency[i] = straggle_ms * rng.lognormal(0.0, straggle_sigma)
    return dropped, latency


# ======================================================================
# wire-level fault injection (chaos.wire_faults): seeded network faults
# applied by a chaos TCP proxy fronting a JSON-lines service
# ======================================================================

# transport fault kinds and their default argument (probability for
# drop, milliseconds for delay, copies for dup; tear/partition take none)
WIRE_FAULT_KINDS = {
    "drop": 1.0,        # refuse the connection (arg = probability)
    "delay": 100.0,     # hold the request this many ms before forwarding
    "tear": 0.0,        # forward HALF the request bytes, then hang up
    "dup": 2.0,         # deliver the request arg times upstream
    "partition": 0.0,   # full partition: nothing gets through the window
}


def parse_wire_faults(spec: str) -> list[tuple[str, float, float, float]]:
    """Parse the ``chaos.wire_faults`` DSL: comma list of
    ``kind@start[-end][:arg]`` — ``start``/``end`` are seconds since the
    proxy started, ``*`` means always, a single time ``t`` means the
    one-second window ``[t, t+1)``.  Returns ``(kind, start_s, end_s,
    arg)`` tuples; raises ``ValueError`` on malformed entries so a
    typo'd plan fails at build time, not silently fault-free."""
    out: list[tuple[str, float, float, float]] = []
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        try:
            kind, rest = item.split("@", 1)
            arg_s = None
            if ":" in rest:
                rest, arg_s = rest.split(":", 1)
            if rest == "*":
                start, end = 0.0, float("inf")
            elif "-" in rest:
                start_s, end_s = rest.split("-", 1)
                start, end = float(start_s), float(end_s)
            else:
                start = float(rest)
                end = start + 1.0
            arg = (
                float(arg_s) if arg_s is not None
                else WIRE_FAULT_KINDS.get(kind, 0.0)
            )
        except ValueError:
            raise ValueError(
                f"chaos.wire_faults entry {item!r} is not "
                "'kind@start[-end][:arg]' (e.g. 'tear@2-4,dup@5-8,"
                "partition@20-30,drop@*:0.3')"
            ) from None
        if kind not in WIRE_FAULT_KINDS:
            raise ValueError(
                f"chaos.wire_faults entry {item!r}: unknown kind {kind!r}; "
                f"expected one of {sorted(WIRE_FAULT_KINDS)}"
            )
        if end <= start:
            raise ValueError(
                f"chaos.wire_faults entry {item!r}: empty window "
                f"[{start:g}, {end:g})"
            )
        out.append((kind, start, end, arg))
    return out


class WireFaultPlan:
    """Seeded, deterministic wire-fault schedule for one proxy.

    ``actions(t_s, conn_idx)`` resolves which faults apply to the
    ``conn_idx``-th accepted connection at ``t_s`` seconds since proxy
    start.  Probabilistic draws (``drop`` with ``arg < 1``) come from
    ``default_rng([seed, conn_idx])`` — pure in the inputs, so the same
    soak re-runs against the identical fault schedule."""

    def __init__(self, spec: str, seed: int = 0):
        self.spec = str(spec)
        self.seed = int(seed)
        self.entries = parse_wire_faults(self.spec)

    def actions(self, t_s: float, conn_idx: int) -> list[tuple[str, float]]:
        out: list[tuple[str, float]] = []
        rng = None
        for kind, start, end, arg in self.entries:
            if not start <= t_s < end:
                continue
            if kind == "drop" and arg < 1.0:
                if rng is None:
                    rng = np.random.default_rng([self.seed, int(conn_idx)])
                if rng.random() >= arg:
                    continue
            out.append((kind, arg))
        return out


class ChaosProxy:
    """A chaos TCP man-in-the-middle for one-shot JSON-lines exchanges.

    Listens on ``address`` and forwards each accepted connection's
    single request line to ``upstream``, then the reply line back —
    BYTE-VERBATIM when no fault applies (pinned in tests/test_rpc.py:
    chaos off can never change the wire).  When the plan fires:

    * ``partition`` / ``drop`` — the client's connection is closed
      before any byte crosses (a black-holed edge),
    * ``delay`` — the request is held ``arg`` ms before forwarding,
    * ``tear`` — HALF the request bytes reach the upstream, then both
      sides are hung up (the torn-mid-message case the push ledger and
      same-(worker, round) replacement must absorb),
    * ``dup`` — the request is delivered ``arg`` times as separate
      upstream exchanges; the client gets the FIRST reply (duplicated
      delivery after a lost ack — the idempotent ``push_id`` case).

    Faults count into ``chaos.wire_faults_total`` (labelled by kind) and
    the local ``injected`` dict for artifact banking."""

    _POLL_S = 0.2

    def __init__(
        self,
        upstream_host: str,
        upstream_port: int,
        plan: WireFaultPlan | None = None,
        host: str = "127.0.0.1",
        port: int = 0,
        timeout_s: float = 30.0,
    ):
        self.upstream = (str(upstream_host), int(upstream_port))
        self.plan = plan
        self.timeout_s = float(timeout_s)
        self.injected: dict[str, int] = {}
        self._sock = socket.create_server((host, int(port)))
        self._sock.settimeout(self._POLL_S)
        self.host, self.port = self._sock.getsockname()[:2]
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._conn_idx = 0
        self._t0 = time.monotonic()

    @property
    def address(self) -> str:
        return f"{self.host}:{self.port}"

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "ChaosProxy":
        self._t0 = time.monotonic()
        self._thread = threading.Thread(
            target=self._accept_loop, name="chaos-proxy", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        try:
            self._sock.close()
        except OSError:
            pass

    # ------------------------------------------------------------- plumbing
    def _count(self, kind: str) -> None:
        self.injected[kind] = self.injected.get(kind, 0) + 1
        from fedrec_tpu.obs import get_registry

        get_registry().counter(
            "chaos.wire_faults_total",
            "transport faults the chaos proxy injected, by kind "
            "(seeded plan: chaos.wire_faults / chaos.wire_seed)",
            labels=("kind",),
        ).inc(kind=kind)

    @staticmethod
    def _read_line(conn: socket.socket) -> bytes:
        """The full request (through its newline) as raw bytes."""
        buf = b""
        while b"\n" not in buf:
            chunk = conn.recv(1 << 20)
            if not chunk:
                break
            buf += chunk
        return buf

    def _exchange_upstream(self, payload: bytes) -> bytes:
        with socket.create_connection(
            self.upstream, timeout=self.timeout_s
        ) as up:
            up.settimeout(self.timeout_s)
            up.sendall(payload)
            return self._read_line(up)

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            idx, self._conn_idx = self._conn_idx, self._conn_idx + 1
            t_s = time.monotonic() - self._t0
            threading.Thread(
                target=self._handle, args=(conn, idx, t_s), daemon=True
            ).start()

    def _handle(self, conn: socket.socket, idx: int, t_s: float) -> None:
        actions = (
            dict(self.plan.actions(t_s, idx)) if self.plan is not None else {}
        )
        try:
            with conn:
                conn.settimeout(self.timeout_s)
                if "partition" in actions or "drop" in actions:
                    # black hole: the client sees a reset/empty reply and
                    # its resilient RPC retries into the backoff budget
                    self._count(
                        "partition" if "partition" in actions else "drop"
                    )
                    return
                payload = self._read_line(conn)
                if not payload:
                    return
                if "delay" in actions:
                    self._count("delay")
                    time.sleep(actions["delay"] / 1e3)
                if "tear" in actions:
                    # half the request reaches the peer, then both sides
                    # hang up: the peer sees no full line (sends nothing),
                    # the client sees an ack-less close (OSError)
                    self._count("tear")
                    try:
                        with socket.create_connection(
                            self.upstream, timeout=self.timeout_s
                        ) as up:
                            up.sendall(payload[: max(len(payload) // 2, 1)])
                    except OSError:
                        pass
                    return
                copies = int(actions.get("dup", 1)) if "dup" in actions else 1
                if copies > 1:
                    self._count("dup")
                reply = b""
                for i in range(max(copies, 1)):
                    try:
                        got = self._exchange_upstream(payload)
                    except OSError:
                        got = b""
                    if i == 0:
                        reply = got
                if reply:
                    conn.sendall(reply)
        except OSError:
            pass
