"""Performance observability: the ONE home of the repo's efficiency math.

Before this module, every efficiency number lived in an offline bench
script: the peak-FLOPs table and the analytic step-FLOPs model were
private to ``bench.py``, the chip roofline peaks and the
compute/HBM/input-bound verdict private to ``benchmarks/step_profile.py``
— so a production run published no MFU, no bytes-accessed, no roofline
verdict, and an efficiency regression stayed invisible until someone
remembered to run the bench.  This module centralizes:

* **Chip peaks + analytic FLOPs model** — :data:`CHIP_PEAKS` /
  :data:`PEAK_FLOPS` and :func:`flops_per_train_step`, imported back by
  ``bench.py`` and ``benchmarks/step_profile.py`` (one definition serving
  the bench headline, the offline roofline, and the live gauges).
* **Roofline verdict, one spelling** — :func:`roofline_verdict` returns
  the (short key, canonical string) pair; ``step_profile.py`` and the
  live per-round gauges share the exact strings, so the artifacts and
  the telemetry can never desync on the words readers grep for.
* **Compile-cost telemetry** — :class:`CostAnalysisRecorder`, hooked
  into :class:`~fedrec_tpu.obs.device.CompileWatchdog`: every watched
  compilation additionally records the compiled executable's
  ``cost_analysis()`` (FLOPs, bytes accessed, arithmetic intensity)
  into ``xla.cost_*`` gauges — degrading gracefully on backends that
  return ``None`` or partial dicts (gauges skip, never raise).
* **HBM attribution** — :func:`live_array_components` groups
  ``jax.live_arrays()`` bytes by component (params / optimizer state /
  news table / batch buffers / other) into
  ``hbm.component_bytes{component=…}`` gauges at round cadence.
* **The live monitor** — :class:`PerfMonitor`: per-round
  ``perf.mfu`` / ``perf.samples_per_sec`` / roofline-verdict gauges
  computed from the Trainer's existing ``batch_build``/``h2d``/
  ``dispatch`` span timings, plus triggered ``jax.profiler`` capture
  windows (``obs.perf.capture_rounds`` and the efficiency-drop trigger)
  landing inside ``obs.dir`` with a pointer record in ``metrics.jsonl``.

Everything is behind ``obs.perf.enabled`` (default OFF): a disabled run
constructs none of this and executes the byte-identical pre-perf
programs.  ``jax`` is imported lazily inside functions so the obs
package stays importable on artifact-reading boxes with no JAX.

Metric catalogue: ``docs/OBSERVABILITY.md`` §2 (Perf).  Operator
runbook for an MFU drop / input-bound round: ``docs/OPERATIONS.md`` §7e.
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Any

from fedrec_tpu.obs.fleet import ROUND_PHASES
from fedrec_tpu.obs.registry import MetricsRegistry, get_registry

# ---------------------------------------------------------------- chip peaks
# chip-name fragment -> (bf16 peak FLOP/s, f32 peak FLOP/s, HBM bytes/s).
# THE table: bench.py's MFU headline, step_profile.py's roofline fractions
# and the live perf.mfu gauge all read these same numbers.
CHIP_PEAKS: dict[str, tuple[float, float, float]] = {
    "v5 lite": (197e12, 49e12, 819e9),   # v5e
    "v5e": (197e12, 49e12, 819e9),
    "v4": (275e12, 137e12, 1228e9),
    "v5p": (459e12, 229e12, 2765e9),
    "v6": (918e12, 459e12, 1640e9),      # trillium
}

# bench.py's historical shape: fragment -> (bf16, f32) FLOP/s only
PEAK_FLOPS: dict[str, tuple[float, float]] = {
    k: (v[0], v[1]) for k, v in CHIP_PEAKS.items()
}


def chip_peaks(device_kind: str) -> tuple[float, float, float] | None:
    """(bf16 FLOP/s, f32 FLOP/s, HBM bytes/s) for a device-kind string.
    ``None`` is for the CPU only: a TPU that is not in the table is an
    error, since every MFU / roofline figure would silently vanish."""
    kind = (device_kind or "").lower()
    peaks = next((v for frag, v in CHIP_PEAKS.items() if frag in kind), None)
    if peaks is None and "tpu" in kind:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth known for TPU device kind "
            f"{device_kind!r}; add it to fedrec_tpu.obs.perf.CHIP_PEAKS"
        )
    return peaks


def peak_flops(device_kind: str, dtype: str) -> float | None:
    """The matmul peak the MFU denominator uses, or ``None`` off-chip."""
    peaks = chip_peaks(device_kind)
    if peaks is None:
        return None
    return peaks[0] if dtype == "bfloat16" else peaks[1]


# ------------------------------------------------------------- flops model
def flops_per_train_step(
    cfg, batch_size: int, num_news: int, encode_rows: int = 0
) -> float:
    """Analytic matmul FLOPs for one joint-mode train step (fwd + bwd),
    PER CLIENT at per-client batch ``batch_size``. ``encode_rows``: the size
    R the round loop compiled the step's text tower at (the
    ``train.encode_rows`` gauge); 0 where the step dedups on the device and
    encodes every slot.

    Counts the dominating dense ops; backward ~= 2x forward for matmuls.
    Moved here from ``bench.py`` (which imports it back) so the bench
    headline, the step_profile roofline and the live ``perf.mfu`` gauge
    can never drift onto different FLOPs models.
    """
    B = batch_size
    C = 1 + cfg.data.npratio
    H = cfg.data.max_his_len
    L = cfg.data.max_title_len
    Dh = cfg.model.bert_hidden
    D = cfg.model.news_dim
    heads, dk = cfg.model.num_heads, cfg.model.head_dim
    Q = cfg.model.query_dim

    # news rows the compiled step encodes: the model never counts text-tower
    # work the step skipped, so a smaller R lowers the count, not the MFU
    size = min(B * (C + H), num_news)
    if encode_rows:
        size = min(size, encode_rows)
    att_hidden = Dh // 2               # text-head additive attention hidden
    text = size * (2 * L * Dh * att_hidden + 2 * L * att_hidden + 2 * Dh * D)
    mha = B * (3 * 2 * H * D * D + 2 * 2 * heads * H * H * dk + 2 * H * D)
    pool = B * (2 * H * D * Q + 2 * H * Q)
    score = B * 2 * C * D
    fwd = text + mha + pool + score
    if (
        cfg.model.text_encoder_mode == "finetune"
        and cfg.model.text_trunk == "window_moe"
    ):
        # the one trunk this model prices (the others' steps are counted at
        # their head alone): its required products a token, every encoded
        # text's L tokens through it
        from fedrec_tpu.models.window_trunk import (
            required_flops_per_token, window_trunk_config_from,
        )

        fwd += size * L * required_flops_per_token(
            window_trunk_config_from(cfg.model), L
        )
    return 3.0 * fwd  # fwd + ~2x fwd for backward


# --------------------------------------------------------- roofline verdict
# ONE spelling of every verdict string: step_profile.py's artifacts and
# the live per-round records must never desync on the words readers and
# docs grep for.  Short keys label the perf.roofline_rounds_total counter
# (Prometheus label values want to stay compact).
VERDICT_INPUT_BOUND = (
    "input-bound: host batch build + transfer >= the device step; "
    "overlap the pipeline (data.prefetch_batches)"
)
VERDICT_MEMORY_BOUND = "memory-bound"
VERDICT_COMPUTE_BOUND = "compute-bound"
VERDICT_HEADROOM = (
    "neither peak approached: dispatch/latency/fusion headroom"
)
VERDICT_DEVICE_BOUND = (
    "device-bound on this backend (host pipeline subdominant; roofline "
    "fractions need a chip run)"
)

ROOFLINE_VERDICTS: dict[str, str] = {
    "input": VERDICT_INPUT_BOUND,
    "memory": VERDICT_MEMORY_BOUND,
    "compute": VERDICT_COMPUTE_BOUND,
    "headroom": VERDICT_HEADROOM,
    "device": VERDICT_DEVICE_BOUND,
}


def roofline_verdict(
    input_bound: bool,
    mfu: float | None = None,
    hbm_fraction: float | None = None,
) -> tuple[str, str]:
    """(short key, canonical string) of the roofline verdict.

    A starved device is input-bound no matter what its roofline fractions
    say.  ``mfu=None`` means no chip peaks are known (CPU backend) — the
    verdict is then device-bound-pending-a-chip-run rather than a
    fraction claim.  Thresholds match ``benchmarks/step_profile.py``'s
    historical artifact semantics (0.6 of either peak).
    """
    if input_bound:
        return "input", VERDICT_INPUT_BOUND
    if mfu is None:
        return "device", VERDICT_DEVICE_BOUND
    if hbm_fraction is not None and hbm_fraction >= 0.6:
        return "memory", VERDICT_MEMORY_BOUND
    if mfu >= 0.6:
        return "compute", VERDICT_COMPUTE_BOUND
    return "headroom", VERDICT_HEADROOM


# ------------------------------------------------------- compile-cost gauges
def analyze_compiled_cost(fn, args: tuple, kwargs: dict | None) -> list[dict] | None:
    """``fn.lower(*args, **kwargs).compile().cost_analysis()`` normalized
    to a list of dicts — or ``None`` when the callable cannot be lowered
    (plain wrapper), the backend returns nothing, or anything raises.
    Never raises: compile-cost telemetry must not perturb training."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        cost = lower(*args, **(kwargs or {})).compile().cost_analysis()
    except Exception:  # noqa: BLE001 — any backend failure is "no data"
        return None
    if cost is None:
        return None
    if isinstance(cost, dict):
        return [cost]
    # older jaxlibs return one dict per executable; a watched fn that
    # dispatches several executables returns several
    try:
        entries = [c for c in cost if isinstance(c, dict)]
    except TypeError:
        return None
    return entries or None


class CostAnalysisRecorder:
    """Publishes a watched compilation's ``cost_analysis()`` into gauges.

    Plugged into :class:`~fedrec_tpu.obs.device.CompileWatchdog` via its
    ``cost_cb`` hook: after any watched call during which a NEW
    compilation fired, the watchdog invokes this with the callable and
    its args.  Partial dicts (a backend reporting flops but not bytes)
    publish what exists and skip the rest; multi-executable results sum
    the keys that are present.  A fully absent analysis only counts on
    the ``outcome="unavailable"`` cell — gauges skip, never raise, and
    the watched call's result is never touched."""

    _FLOPS_KEY = "flops"
    _BYTES_KEY = "bytes accessed"

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = registry or get_registry()
        self._g_flops = self.registry.gauge(
            "xla.cost_flops",
            "XLA cost_analysis FLOPs of the last-compiled executable, by "
            "watched callable",
            labels=("fn",),
        )
        self._g_bytes = self.registry.gauge(
            "xla.cost_bytes_accessed",
            "XLA cost_analysis bytes accessed (HBM traffic model) of the "
            "last-compiled executable, by watched callable",
            labels=("fn",),
        )
        self._g_intensity = self.registry.gauge(
            "xla.cost_arithmetic_intensity",
            "cost_analysis flops / bytes accessed — compare against the "
            "chip ridge intensity to see which roofline wall is closer",
            labels=("fn",),
        )
        self._c_analyses = self.registry.counter(
            "xla.cost_analyses_total",
            "cost_analysis attempts after watched compilations, by "
            "callable and outcome (ok / unavailable)",
            labels=("fn", "outcome"),
        )

    def __call__(self, fn, args: tuple, kwargs: dict | None, name: str) -> None:
        try:
            entries = analyze_compiled_cost(fn, args, kwargs)
            if not entries:
                self._c_analyses.inc(fn=name, outcome="unavailable")
                return
            # presence, not truthiness: a copy/broadcast program's
            # legitimate 0.0-FLOPs reading is DATA, not a missing key
            flops_vals = [
                float(e[self._FLOPS_KEY]) for e in entries
                if isinstance(e.get(self._FLOPS_KEY), (int, float))
            ]
            byte_vals = [
                float(e[self._BYTES_KEY]) for e in entries
                if isinstance(e.get(self._BYTES_KEY), (int, float))
            ]
            flops = sum(flops_vals) if flops_vals else None
            nbytes = sum(byte_vals) if byte_vals else None
            if flops is None and nbytes is None:
                self._c_analyses.inc(fn=name, outcome="unavailable")
                return
            if flops is not None:
                self._g_flops.set(flops, fn=name)
            if nbytes is not None:
                self._g_bytes.set(nbytes, fn=name)
            if flops is not None and nbytes:  # nbytes > 0: division guard
                self._g_intensity.set(flops / nbytes, fn=name)
            self._c_analyses.inc(fn=name, outcome="ok")
        except Exception:  # noqa: BLE001 — telemetry must never raise
            pass

    def bytes_accessed(self, name: str) -> float | None:
        """Last-recorded bytes-accessed for a watched callable (the live
        HBM-fraction numerator), or None."""
        return self._g_bytes.value(fn=name)


# ----------------------------------------------------------- HBM attribution
def live_array_components(
    components: dict[str, Any],
    registry: MetricsRegistry | None = None,
    tracer: Any = None,
    **annotations: Any,
) -> dict[str, float]:
    """Group every live device array's bytes by component.

    ``components`` maps a component name (``params`` / ``optimizer`` /
    ``news_table`` / ``batch``) to the pytree whose leaves define it;
    classification is by leaf IDENTITY against ``jax.live_arrays()``, so
    a donated/deleted buffer simply stops being live and drops out.
    Everything unclaimed lands in ``other`` (rng keys, eval tables,
    XLA temporaries that surface as arrays).  Bytes are the arrays'
    logical ``nbytes`` — per-device resident bytes divide by the mesh
    axis the leaf is sharded over, which ``device.memory_stats`` (the
    companion gauge) already reports in aggregate.

    Publishes ``hbm.component_bytes{component=…}`` gauges (+ one trace
    instant) and returns the totals.  Never raises; returns ``{}`` when
    ``jax.live_arrays`` is unavailable."""
    registry = registry or get_registry()
    try:
        import jax

        sets: dict[str, set[int]] = {}
        for name, tree in components.items():
            if tree is None:
                continue
            sets[name] = {
                id(leaf)
                for leaf in jax.tree_util.tree_leaves(tree)
                if hasattr(leaf, "dtype")
            }
        totals: dict[str, float] = dict.fromkeys([*sets, "other"], 0.0)
        for arr in jax.live_arrays():
            try:
                nb = float(arr.size) * arr.dtype.itemsize
            except Exception:  # noqa: BLE001 — a dying buffer mid-walk
                continue
            bucket = next(
                (name for name, ids in sets.items() if id(arr) in ids),
                "other",
            )
            totals[bucket] += nb
    except Exception:  # noqa: BLE001 — attribution is best-effort telemetry
        return {}
    gauge = registry.gauge(
        "hbm.component_bytes",
        "live device-array bytes by component (params / optimizer / "
        "news_table / batch / other), sampled at round boundaries",
        labels=("component",),
    )
    for name, nb in totals.items():
        gauge.set(nb, component=name)
    if tracer is not None:
        tracer.instant(
            "hbm_components",
            **{k: int(v) for k, v in totals.items()},
            **annotations,
        )
    return totals


# ------------------------------------------------------------ capture window
def parse_capture_rounds(spec: str) -> tuple[int, int] | None:
    """``"N"`` -> rounds [N, N+1); ``"N:K"`` -> rounds [N, N+K); empty ->
    None.  Raises ValueError on anything else (caught at config time)."""
    spec = (spec or "").strip()
    if not spec:
        return None
    parts = spec.split(":")
    try:
        if len(parts) == 1:
            return int(parts[0]), 1
        if len(parts) == 2:
            start, length = int(parts[0]), int(parts[1])
            if length < 1:
                raise ValueError
            return start, length
    except ValueError:
        pass
    raise ValueError(
        f"cannot parse capture window {spec!r}: expected 'N' (one round) "
        "or 'N:K' (rounds [N, N+K), K >= 1)"
    )


def append_jsonl_record(path, record: dict) -> None:
    """Append one pointer record to a metrics.jsonl event log (the
    discoverability contract for captured traces: the artifact trio
    names every sidecar it produced).  Best-effort — never raises."""
    try:
        with open(path, "a") as f:
            f.write(json.dumps(record) + "\n")
    except OSError:
        pass


class PerfMonitor:
    """Per-round efficiency gauges + triggered capture windows.

    Constructed by the Trainer only when ``obs.perf.enabled``; takes the
    round's ``batch_build``/``h2d``/``dispatch``/``aggregate``/``eval``
    span timings from the Trainer's one digest of the round's spans (the
    same spans the trace artifact carries — no second clock, no second
    pass over the events), prices the round with the
    analytic FLOPs model, and publishes:

    * ``perf.samples_per_sec`` / ``perf.mfu`` / ``perf.hbm_fraction``
      (the MFU/HBM gauges only when the chip peaks are known; the HBM
      fraction additionally needs a ``cost_analysis`` bytes-accessed
      reading for the step program),
    * ``perf.host_ms_per_step`` / ``perf.dispatch_ms_per_step``,
    * ``perf.roofline_rounds_total{verdict=…}`` — the per-round verdict,
      short keys; canonical strings in :data:`ROOFLINE_VERDICTS`.

    Capture windows: ``obs.perf.capture_rounds`` wraps rounds [N, N+K)
    in a ``jax.profiler`` trace under ``obs.dir/perf_capture_rNNNN``;
    ``obs.perf.capture_drop`` arms a one-round capture whenever a
    round's samples/s falls that fraction below the trailing-window
    mean.  Start/stop failures (e.g. a ``train.profile`` trace already
    active) count on ``perf.capture_failures_total`` — never raise."""

    # THE round-phase span names — shared with the fleet straggler
    # attribution so the two digests can never disagree on which spans
    # count as round work
    PHASES = ROUND_PHASES
    MAX_TRIGGERED_CAPTURES = 3

    def __init__(
        self,
        pcfg,
        cfg,
        num_news: int,
        registry: MetricsRegistry | None = None,
        tracer: Any = None,
        obs_dir: Any = None,
        device_kind: str | None = None,
    ):
        from fedrec_tpu.obs.tracing import get_tracer

        self.pcfg = pcfg
        self.cfg = cfg
        self.registry = registry or get_registry()
        self.tracer = tracer or get_tracer()
        self.obs_dir = Path(obs_dir) if obs_dir else None
        if device_kind is None:
            import jax

            device_kind = getattr(jax.devices()[0], "device_kind", "")
        self.peak_fl = peak_flops(device_kind, cfg.model.dtype)
        peaks = chip_peaks(device_kind)
        self.peak_bw = peaks[2] if peaks else None
        self._num_news = num_news
        self.flops_per_step = flops_per_train_step(
            cfg, cfg.data.batch_size, num_news
        )
        self.samples_per_step = cfg.fed.num_clients * cfg.data.batch_size
        self.cost = CostAnalysisRecorder(self.registry)

        self._g_step_flops = self.registry.gauge(
            "perf.step_flops",
            "analytic matmul FLOPs of one train step PER CLIENT "
            "(flops_per_train_step — the same model bench.py certifies "
            "MFU with)",
        )
        self._g_step_flops.set(self.flops_per_step)
        self._g_samples = self.registry.gauge(
            "perf.samples_per_sec",
            "training throughput of the last round (samples = clients x "
            "batch x steps over the round's wall time)",
        )
        self._g_mfu = self.registry.gauge(
            "perf.mfu",
            "model FLOPs utilization of the last round (analytic FLOPs / "
            "wall / chip matmul peak); absent off-chip",
        )
        self._g_hbm_fraction = self.registry.gauge(
            "perf.hbm_fraction",
            "cost_analysis bytes accessed / wall / chip HBM peak of the "
            "last round; needs chip peaks",
        )
        self._g_host_ms = self.registry.gauge(
            "perf.host_ms_per_step",
            "host input pipeline (batch_build + h2d span time) per "
            "dispatched step, last round",
        )
        self._g_dispatch_ms = self.registry.gauge(
            "perf.dispatch_ms_per_step",
            "device dispatch span time per dispatched step, last round",
        )
        self._c_verdicts = self.registry.counter(
            "perf.roofline_rounds_total",
            "rounds by roofline verdict (input / memory / compute / "
            "headroom / device — canonical strings in obs.perf)",
            labels=("verdict",),
        )
        self._c_untraced = self.registry.counter(
            "perf.untraced_rounds_total",
            "rounds whose phase spans were lost to the tracer capacity "
            "bound (obs.trace_capacity) — no roofline verdict or per-step "
            "phase gauges are published for them, rather than wrong ones",
        )
        self._c_captures = self.registry.counter(
            "perf.captures_total",
            "jax.profiler capture windows started, by reason "
            "(configured / efficiency_drop)",
            labels=("reason",),
        )
        self._c_capture_failures = self.registry.counter(
            "perf.capture_failures_total",
            "capture windows that failed to start/stop (e.g. another "
            "profiler trace already active) — counted, never raised",
        )

        self._steps_counter = self.registry.counter(
            "train.steps_total", "train-step batches dispatched"
        )
        self._mark_steps = 0.0
        self._mark_dropped = 0
        self._rates: list[float] = []
        self._window = parse_capture_rounds(pcfg.capture_rounds)
        self._drop = float(pcfg.capture_drop or 0.0)
        if self.obs_dir is None and (self._window is not None or self._drop > 0):
            # fail fast, not silently-never-capture: an explicitly
            # requested window writes its trace + pointer record into the
            # obs artifact directory, so one must exist
            raise ValueError(
                "obs.perf.capture_rounds / obs.perf.capture_drop need "
                "obs.dir set: the jax.profiler trace and its "
                "metrics.jsonl pointer record land in the obs artifact "
                "directory"
            )
        self._drop_window = max(int(pcfg.capture_window), 2)
        self._pending_trigger = False
        self._triggered = 0
        # when the watch layer is live (obs.slo.enabled) the drop trigger
        # routes through the alert engine instead of arming directly:
        # Watch.bind_perf sets the hook and arms via arm_capture() off the
        # alert's firing transition (one lifecycle, no private flag)
        self.watch_hook = None
        self._active: dict | None = None
        self.last_round: dict | None = None

    # ------------------------------------------------------------- rounds
    def set_encode_rows(self, rows: int) -> None:
        """The round loop chose (or re-derived) the step's encode size R:
        price the text tower at it from here on."""
        self.flops_per_step = flops_per_train_step(
            self.cfg, self.cfg.data.batch_size, self._num_news, rows
        )
        self._g_step_flops.set(self.flops_per_step)

    def begin_round(self) -> None:
        """Mark the step-counter and dropped-span positions a round's
        digest diffs against; call at round entry."""
        self._mark_steps = self._steps_counter.value()
        self._mark_dropped = self.tracer.dropped

    def observe_round(
        self, round_idx: int, wall_s: float, span_seconds: dict[str, float]
    ) -> dict[str, Any]:
        """Digest the round that just finished: publish the gauges and
        return the per-round log keys (``perf.samples_per_sec`` /
        ``perf.mfu`` / ``perf.verdict``). ``span_seconds``: the round's
        spans summed by name, the Trainer's one digest of them
        (``obs.tracing.RoundDigest``)."""
        steps = self._steps_counter.value() - self._mark_steps
        # a saturated tracer ring (obs.trace_capacity) drops NEW spans —
        # this round's phase sums would then be silently empty, and an
        # input-bound round would masquerade as 'headroom'. Missing data
        # publishes NO verdict, never a wrong one.
        traced = self.tracer.dropped == self._mark_dropped
        phases = {p: span_seconds.get(p, 0.0) for p in self.PHASES}
        out: dict[str, Any] = {}
        # the eval span is excluded from the efficiency denominators so an
        # eval-cadence round's MFU/throughput stays comparable to a
        # train-only round's (the eval cost is still visible: it has its
        # own span row in the trace and the report's span table). Only
        # when the spans are trustworthy — a partially-recorded eval span
        # on an untraced round would under-subtract
        wall_s = max(
            float(wall_s) - (phases["eval"] if traced else 0.0), 1e-9
        )
        host_s = phases["batch_build"] + phases["h2d"]
        disp_s = phases["dispatch"]
        if steps > 0 and traced:
            self._g_host_ms.set(host_s / steps * 1e3)
            self._g_dispatch_ms.set(disp_s / steps * 1e3)
        rate = steps * self.samples_per_step / wall_s
        self._g_samples.set(rate)
        out["perf.samples_per_sec"] = round(rate, 2)
        mfu = None
        if self.peak_fl is not None and steps > 0:
            flops = steps * self.cfg.fed.num_clients * self.flops_per_step
            mfu = flops / wall_s / self.peak_fl
            self._g_mfu.set(mfu)
            out["perf.mfu"] = round(mfu, 6)
        hbm_fraction = None
        if self.peak_bw is not None and steps > 0:
            nbytes = self.cost.bytes_accessed("train_step")
            if nbytes:
                hbm_fraction = steps * nbytes / wall_s / self.peak_bw
                self._g_hbm_fraction.set(hbm_fraction)
                out["perf.hbm_fraction"] = round(hbm_fraction, 6)
        if traced:
            # input-bound exactly as step_profile judges it: the host
            # pipeline costs at least as much as the device step it feeds
            input_bound = disp_s > 0 and host_s >= disp_s
            key, _ = roofline_verdict(input_bound, mfu, hbm_fraction)
            self._c_verdicts.inc(verdict=key)
            out["perf.verdict"] = key
        else:
            self._c_untraced.inc()
        self.last_round = {"round": round_idx, **out}
        # efficiency-drop trigger: a round well below the trailing mean
        # arms a capture of the NEXT round (this one is already gone).
        # Untraced rounds stay out of the trigger AND the trailing mean —
        # their eval-uncorrected rate is not comparable, and a spurious
        # trigger would burn one of the bounded captures
        if traced:
            if (
                self._drop > 0
                and self._triggered < self.MAX_TRIGGERED_CAPTURES
            ):
                trailing = self._rates[-self._drop_window:]
                if len(trailing) >= 2:
                    mean = sum(trailing) / len(trailing)
                    if mean > 0 and rate < (1.0 - self._drop) * mean:
                        if self.watch_hook is not None:
                            self.watch_hook(round_idx, rate, mean)
                        else:
                            self._pending_trigger = True
            self._rates.append(rate)
        return out

    # ------------------------------------------------------------ capture
    def arm_capture(self) -> bool:
        """Arm a triggered capture of the next round (the watch layer's
        entry point: called when the efficiency-drop alert fires).
        Returns False once the triggered-capture budget is spent."""
        if self._triggered >= self.MAX_TRIGGERED_CAPTURES:
            return False
        self._pending_trigger = True
        return True

    def capture_before_round(self, round_idx: int) -> str | None:
        """Start a capture window when round ``round_idx`` lies in one:
        the configured [N, N+K) window, or a pending efficiency-drop
        trigger.  Returns the logdir when a window started."""
        if self._active is not None or self.obs_dir is None:
            return None
        reason = None
        end = round_idx + 1
        if self._window is not None:
            start, length = self._window
            if start <= round_idx < start + length:
                reason, end = "configured", start + length
        if reason is None and self._pending_trigger:
            reason = "efficiency_drop"
            self._pending_trigger = False
            self._triggered += 1
        if reason is None:
            return None
        logdir = self.obs_dir / f"perf_capture_r{round_idx:04d}"
        try:
            from fedrec_tpu.utils.profiling import start_device_trace

            start_device_trace(logdir)
        except Exception:  # noqa: BLE001 — e.g. train.profile already tracing
            self._c_capture_failures.inc()
            return None
        self._active = {
            "round": round_idx,
            "end": end,
            "logdir": str(logdir),
            "reason": reason,
        }
        self._c_captures.inc(reason=reason)
        return str(logdir)

    def capture_after_round(self, last_round_idx: int) -> None:
        """Close the active window once its last round completed."""
        if self._active is not None and last_round_idx >= self._active["end"] - 1:
            self._stop_capture(last_round_idx)

    def close(self) -> None:
        """Stop any still-open window (run end / failing exit path) so a
        capture is never left dangling across process exit."""
        if self._active is not None:
            self._stop_capture(self._active["end"] - 1)

    def _stop_capture(self, last_round_idx: int) -> None:
        active, self._active = self._active, None
        try:
            from fedrec_tpu.utils.profiling import stop_device_trace

            stop_device_trace()
        except Exception:  # noqa: BLE001
            self._c_capture_failures.inc()
            return
        if self.obs_dir is not None:
            append_jsonl_record(self.obs_dir / "metrics.jsonl", {
                "kind": "perf_capture",
                "round": active["round"],
                "last_round": last_round_idx,
                "reason": active["reason"],
                "logdir": active["logdir"],
                "ts": time.time(),
            })
