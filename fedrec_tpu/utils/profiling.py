"""Profiling helpers: ``jax.profiler`` traces around the hot loop.

The reference has no profiling subsystem (SURVEY.md section 5.1 — only print
statements and a vestigial counter pair, reference ``model.py:31-32``); here
a context manager wraps any region in a TensorBoard-compatible trace.

``profile_if`` yields the logdir the trace lands in (None when disabled),
so callers can report/stamp where the artifact went instead of hardcoding
the default path a second time.  Host-side round structure goes through
:mod:`fedrec_tpu.obs.tracing` instead; the Trainer annotates each round
with ``jax.profiler.StepTraceAnnotation("fed_round", step_num=...)`` so
the device trace captured here is round-addressable.

Every device trace the program takes starts and stops HERE
(:func:`start_device_trace` / :func:`stop_device_trace`: ``profile_if``
below and ``obs.perf.PerfMonitor``'s capture windows), and carries the
host's clock: a :data:`CLOCK_MARK` annotation right after the start and
right before the stop, each with ``t_ns`` = ``time.perf_counter_ns()``.
An annotation's own start on the trace's timeline less its ``t_ns`` is the
one offset that places any span of the obs tracer's ``trace.json``
(``otherData.epoch_perf_counter_ns`` + ``ts``) on the device trace.
"""

from __future__ import annotations

import contextlib
import time

import jax

CLOCK_MARK = "fedrec_clock"


def _stamp_clock() -> None:
    with jax.profiler.TraceAnnotation(CLOCK_MARK, t_ns=time.perf_counter_ns()):
        pass


def start_device_trace(logdir) -> None:
    jax.profiler.start_trace(str(logdir))
    _stamp_clock()


def stop_device_trace() -> None:
    _stamp_clock()
    jax.profiler.stop_trace()


@contextlib.contextmanager
def profile_if(enabled: bool, logdir: str | None = None):
    """Wrap the block in a ``jax.profiler`` trace when ``enabled``.

    Yields the logdir path (the handle on the written trace) when
    enabled, None when not — a no-trace region never looks like it
    produced an artifact.  ``logdir=None`` falls back to the historical
    ``/tmp/fedrec_tpu_trace`` default; the Trainer routes it into
    ``obs.dir/jax_profile`` when an obs dir is configured (and points to
    it from ``metrics.jsonl``) so a captured trace is discoverable from
    the artifact trio instead of hiding in /tmp.
    """
    if not enabled:
        yield None
        return
    logdir = logdir or "/tmp/fedrec_tpu_trace"
    start_device_trace(logdir)
    try:
        yield logdir
    finally:
        stop_device_trace()
