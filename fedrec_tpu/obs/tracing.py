"""Host-side span tracer emitting Chrome-trace / Perfetto JSON.

``jax.profiler`` answers "what did the DEVICE do" (XLA ops, HBM, MXU
occupancy); it says nothing about the host-side round structure — batch
build vs H2D vs compiled dispatch vs aggregation vs eval — or the
serving request lifecycle (enqueue -> batch -> dispatch -> reply).  This
tracer records those as wall-clock spans and writes them in the Chrome
trace event format (``{"traceEvents": [...]}``), which both
``chrome://tracing`` and https://ui.perfetto.dev load directly.

Correlating host and device: the saved file's ``otherData`` carries the
tracer's epoch on ``time.perf_counter_ns``' scale
(``epoch_perf_counter_ns``), and every device trace the program starts
(``utils.profiling.start_device_trace``) is stamped with ``fedrec_clock``
annotations that carry the same clock's reading — so any span of
``trace.json`` can be placed on a captured device trace's timeline. The
Trainer additionally wraps every round in a
``jax.profiler.StepTraceAnnotation("fed_round", step_num=...)``, which
gives the XLA steps the host spans' round numbers.

Properties:

* **Cheap when idle**: recording a span is a clock read + a list append
  under a lock (~1 us); there is no I/O until ``save()``.
* **Bounded**: at most ``capacity`` events are kept (earliest win —
  the round structure of a run's HEAD is worth more than its tail);
  everything past that increments ``dropped`` and the count is stamped
  into the saved file's ``otherData``.
* **Timestamps are monotonic** (``time.perf_counter`` relative to the
  tracer's epoch, in microseconds) and ``save()`` sorts events, so the
  exported ``ts`` sequence is non-decreasing — the schema property the
  tests pin.

Spans whose duration was measured on a different clock (e.g. the
batcher's ``time.monotonic`` enqueue stamps) use :meth:`Tracer.add_span`
with an explicit duration; only the END is placed on the tracer clock.
"""

from __future__ import annotations

import contextlib
import json
import os
import statistics
import sys
import threading
import time
from collections import deque
from typing import Any, Iterator


class _Open:
    """What :meth:`Tracer.span` yields: ``end`` is the reading of the
    tracer's clock that closed the span (None while it is open). Handed to
    the next span's ``since``, the two tile: nothing lies between them."""

    __slots__ = ("end",)

    def __init__(self):
        self.end: float | None = None


class Tracer:
    """Bounded in-memory recorder of Chrome-trace events."""

    def __init__(self, capacity: int = 200_000, clock=time.perf_counter):
        self.capacity = int(capacity)
        # enabled=False makes every record a no-op that also skips the drop
        # counter — the switch for processes that will never save a trace
        # (e.g. fedrec-serve without --obs-dir), so per-request spans cost
        # neither memory nor lock traffic there
        self.enabled = True
        self._clock = clock
        self._t0 = clock()
        self._epoch_unix = time.time()
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self.dropped = 0
        self._pid = os.getpid()
        # fleet correlation keys (obs.fleet.set_fleet_identity) merged
        # into every recorded event's args — worker/rank/membership_epoch
        # labels that make multi-process traces joinable offline
        self._context: dict[str, Any] = {}

    @property
    def epoch_unix(self) -> float:
        """Wall-clock time at this tracer's epoch — the anchor the fleet
        merger uses for coarse cross-process clock alignment."""
        return self._epoch_unix

    def set_context(self, **kv: Any) -> None:
        """Replace the label set stamped into every subsequent event's
        args (explicit per-event args win on key collision)."""
        self._context = {k: v for k, v in kv.items() if v is not None}

    # ------------------------------------------------------------- clock
    def now(self) -> float:
        """Seconds on the tracer clock (pair with :meth:`add_span`)."""
        return self._clock()

    def _us(self, t: float) -> float:
        return (t - self._t0) * 1e6

    # ----------------------------------------------------------- record
    def _append(self, ev: dict) -> None:
        if not self.enabled:
            return
        if self._context:
            ev["args"] = {**self._context, **ev.get("args", {})}
        with self._lock:
            if len(self._events) >= self.capacity:
                self.dropped += 1
                return
            self._events.append(ev)

    @contextlib.contextmanager
    def span(
        self, name: str, since: float | None = None, **args: Any
    ) -> Iterator[_Open]:
        """Record the enclosed block as one complete ("X") event. ``since``:
        an earlier reading of the tracer's clock to open the span at (the
        close of the span before: what the host did in between, the
        tracer's own bookkeeping included, is then inside this one)."""
        opened = _Open()
        if not self.enabled:
            try:
                yield opened
            finally:
                opened.end = self._clock()
            return
        start = self._clock() if since is None else since
        try:
            yield opened
        except BaseException as e:
            args = {**args, "error": type(e).__name__}
            raise
        finally:
            end = opened.end = self._clock()
            self._append({
                "name": name,
                "ph": "X",
                "ts": self._us(start),
                "dur": (end - start) * 1e6,
                "pid": self._pid,
                "tid": threading.get_ident() % 0x7FFFFFFF,
                **({"args": args} if args else {}),
            })

    def add_span(
        self, name: str, dur_s: float, end: float | None = None, **args: Any
    ) -> None:
        """Record a span of known duration ending at ``end`` (tracer-clock
        seconds, default now).  For intervals whose start was stamped on a
        DIFFERENT monotonic clock: only the duration crosses over, so no
        cross-clock timestamp arithmetic can skew the timeline."""
        if not self.enabled:
            return
        end = self._clock() if end is None else end
        dur_s = max(float(dur_s), 0.0)
        self._append({
            "name": name,
            "ph": "X",
            "ts": self._us(end - dur_s),
            "dur": dur_s * 1e6,
            "pid": self._pid,
            "tid": threading.get_ident() % 0x7FFFFFFF,
            **({"args": args} if args else {}),
        })

    _FLOW_PH = {"out": "s", "step": "t", "in": "f"}

    def flow(
        self,
        direction: str,
        flow_id: int,
        name: str = "wire",
        ts: float | None = None,
        **args: Any,
    ) -> None:
        """Record a Chrome-trace flow event (``ph`` s/t/f) — the arrow
        primitive that links spans causally ACROSS processes in the
        merged fleet trace.  ``direction`` is "out" (start), "step"
        (intermediate) or "in" (finish); events sharing ``flow_id`` (and
        the fixed "wire" category) form one arrow.  ``ts`` places the
        event (tracer-clock seconds, default now) — it must fall inside
        the span the arrow should bind to on this thread."""
        ph = self._FLOW_PH[direction]
        ev = {
            "name": name,
            "cat": "wire",
            "ph": ph,
            "id": int(flow_id),
            "ts": self._us(self._clock() if ts is None else ts),
            "pid": self._pid,
            "tid": threading.get_ident() % 0x7FFFFFFF,
            **({"args": args} if args else {}),
        }
        if ph == "f":
            ev["bp"] = "e"  # bind to the enclosing slice, not the next
        self._append(ev)

    def instant(self, name: str, **args: Any) -> None:
        self._append({
            "name": name,
            "ph": "i",
            "s": "t",
            "ts": self._us(self._clock()),
            "pid": self._pid,
            "tid": threading.get_ident() % 0x7FFFFFFF,
            **({"args": args} if args else {}),
        })

    # ------------------------------------------------------------ export
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def event_count(self) -> int:
        """How many events are recorded — pair with :meth:`events_since`
        for incremental readers (obs.perf digests only the spans of the
        round that just ended) without copying the whole ring each
        round."""
        with self._lock:
            return len(self._events)

    def events_since(self, start: int) -> list[dict]:
        """The events recorded at index ``start`` onward (a prior
        :meth:`event_count` reading)."""
        with self._lock:
            return list(self._events[start:])

    def to_chrome(self) -> dict:
        """Chrome trace event JSON object; events sorted by ``ts`` so the
        exported timeline is monotonic."""
        evs = sorted(self.events(), key=lambda e: e["ts"])
        return {
            "traceEvents": evs,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "fedrec_tpu.obs",
                "epoch_unix": self._epoch_unix,
                # ts 0 on time.perf_counter_ns' scale: a span's
                # perf_counter_ns is this + ts * 1e3, which a device
                # trace's ``fedrec_clock`` annotations turn into trace time
                "epoch_perf_counter_ns": int(self._t0 * 1e9),
                "dropped_events": self.dropped,
            },
        }

    def save(self, path) -> dict:
        """Write the Perfetto/Chrome-trace JSON; returns what was written."""
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f)
        return doc

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0


# ------------------------------------------------------------ round digest
def span_seconds(events: list[dict]) -> dict[str, float]:
    """Seconds of the complete ("X") spans of ``events``, by name."""
    out: dict[str, float] = {}
    for ev in events:
        if ev.get("ph") == "X":
            out[ev["name"]] = out.get(ev["name"], 0.0) + float(ev["dur"]) / 1e6
    return out


def union_seconds(events: list[dict], lo_us: float, hi_us: float) -> float:
    """Seconds of [lo, hi] that the complete spans of ``events`` cover, an
    interval counted once however many spans (nested ones, another
    thread's) lie over it."""
    covered, at = 0.0, lo_us
    for a, b in sorted(
        (ev["ts"], ev["ts"] + ev["dur"]) for ev in events if ev.get("ph") == "X"
    ):
        a, b = max(a, at), min(b, hi_us)
        if b > a:
            covered, at = covered + (b - a), b
    return covered / 1e6


class RoundDigest:
    """One sum of a round's spans by name, for every consumer of it, and
    the record of a round that stalled.

    The Trainer calls :meth:`begin` where ``fed_round`` opens and
    :meth:`close` at the end of ``round_epilogue``. ``close`` returns seconds by
    span name over the round so far, plus ``unspanned``: the round's time
    under none of its spans. It observes them on
    ``train.round_span_seconds{span}``, keeps the last :data:`KEEP` rounds,
    and once :data:`MIN_KEPT` are kept names a slow round: one whose wall
    time (its ``eval`` span apart, which comes by cadence) exceeds the
    trailing median by both :data:`SLOW_SHARE` and :data:`SLOW_SECONDS`
    gets a ``slow_round`` instant, a count on
    ``train.slow_rounds_total{span}`` and one line on stderr, ``span``
    being the one whose excess over its OWN trailing median is largest.
    Sound rounds of one program lie within a percent of each other; the
    stalls this is for were +26% and +107% (PERF.md question 21)."""

    KEEP, MIN_KEPT = 8, 3
    SLOW_SHARE, SLOW_SECONDS = 0.10, 0.050

    def __init__(self, tracer: Tracer, registry: Any):
        self.tracer = tracer
        self._kept: deque[dict[str, float]] = deque(maxlen=self.KEEP)
        self._mark = 0
        self._t_open = 0.0
        self._m_seconds = registry.histogram(
            "train.round_span_seconds",
            "host seconds of a round under each of fed_round's spans "
            "(round_prologue, batch_build, h2d, dispatch, step_keep, "
            "aggregate, device_wait, round_end, eval, round_epilogue so "
            "far) and under none of them (span=unspanned)",
            labels=("span",),
            buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                     0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0, 100.0),
        )
        self._m_slow = registry.counter(
            "train.slow_rounds_total",
            "rounds whose wall time exceeded the trailing median of the "
            "last 8 by 10% and 50 ms, by the span that grew most",
            labels=("span",),
        )

    def begin(self) -> None:
        self._mark = self.tracer.event_count()
        self._t_open = self.tracer.now()

    def close(self, round_idx: int, epilogue_open: float) -> dict[str, float]:
        """``epilogue_open``: the tracer-clock start of the round's
        ``round_epilogue``, which is recorded after this call: it counts up
        to now, and less the ``eval`` inside it."""
        tracer = self.tracer
        if not tracer.enabled:
            return {}
        now = tracer.now()
        events = tracer.events_since(self._mark)
        events.append({
            "name": "round_epilogue", "ph": "X",
            "ts": tracer._us(epilogue_open), "dur": (now - epilogue_open) * 1e6,
        })
        sums = span_seconds(events)
        sums["round_epilogue"] -= sums.get("eval", 0.0)
        wall = now - self._t_open
        sums["unspanned"] = max(
            wall - union_seconds(events, tracer._us(self._t_open), tracer._us(now)), 0.0
        )
        for name, seconds in sums.items():
            self._m_seconds.observe(seconds, span=name)
        wall -= sums.get("eval", 0.0)
        if len(self._kept) >= self.MIN_KEPT:
            median = statistics.median(k["wall"] for k in self._kept)
            excess = wall - median
            if excess > self.SLOW_SECONDS and excess > self.SLOW_SHARE * median:
                grew = {
                    name: seconds - statistics.median(k.get(name, 0.0) for k in self._kept)
                    for name, seconds in sums.items() if name != "eval"
                }
                span = max(grew, key=grew.get)
                tracer.instant(
                    "slow_round", round=round_idx, span=span,
                    excess_ms=excess * 1e3, wall_ms=wall * 1e3,
                )
                self._m_slow.inc(span=span)
                print(
                    f"[obs] WARNING: round {round_idx} took {wall * 1e3:.1f} ms, "
                    f"{excess * 1e3:.1f} ms over the median of the last "
                    f"{len(self._kept)}; the span that grew most is {span!r} "
                    f"(+{grew[span] * 1e3:.1f} ms)",
                    file=sys.stderr, flush=True,
                )
        self._kept.append({**sums, "wall": wall})
        return sums


# ------------------------------------------------------------- global default
_default_tracer = Tracer()
_default_lock = threading.Lock()


def get_tracer() -> Tracer:
    """The process-wide default tracer every subsystem records into."""
    return _default_tracer


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the default tracer (tests); returns the previous one."""
    global _default_tracer
    with _default_lock:
        prev = _default_tracer
        _default_tracer = tracer
        return prev
