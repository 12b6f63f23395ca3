"""Benchmark: flagship federated train-step throughput on the local accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/sec", "vs_baseline": N, ...}

Measured workload — identical math and shapes to the recorded torch-CPU
reference-equivalent baseline (``benchmarks/torch_baseline.py``, results in
``benchmarks/baseline_host.json``): per-batch training of the two-tower
recommender (trainable text head over cached frozen-trunk token states +
20-head user encoder + sigmoid-CE), B=64 impressions, 5 candidates, 50-item
history, 50-token titles. The reference's federated deployment runs this math
per-sample in torch/gloo on CPU nodes (reference ``README.md:13,86``,
``model.py:41-61``); ours is one jitted XLA program on the TPU chip.

The run additionally reports:
  * an analytic MFU estimate (the step's matmul FLOPs are statically known),
  * a large-batch throughput (B=512 == the 8-client grad-avg equivalent:
    with per-step gradient averaging all clients stay in lockstep, so 8
    clients x B=64 on one chip is mathematically one B=512 step),
  * a full batch-size sweep, whose BEST row becomes the headline ``value``
    (the B=64 point is dominated by per-step dispatch overhead); the B=64
    rows are retained under ``b64_*``.

One process, on the device JAX gives it: the result names that device
(``platform`` / ``device_kind`` / ``device_count``), a leg that raises ends
the run non-zero, and a platform other than ``tpu`` is an error — except
under the CPU test hook ``FEDREC_BENCH_SMOKE=1`` (tiny shapes, never a
number to quote). What this measures is roadmap S0's to redefine.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import numpy as np

# THE peak-FLOPs table and analytic step-FLOPs model live in
# fedrec_tpu.obs.perf (one definition serving this bench's headline MFU,
# step_profile.py's roofline, the live perf.mfu gauge and the banked
# perf gate); imported back under the historical names so downstream
# readers of bench.py keep working.
from fedrec_tpu.obs.perf import (  # noqa: E402
    PEAK_FLOPS as _PEAK_FLOPS,  # noqa: F401 — tests pin the one-table identity
    flops_per_train_step as _flops_per_train_step,
    peak_flops,
)


def _baseline_ratios(
    baseline_path: Path, rate: float, our_sweep: dict | None = None
) -> dict:
    """Both cross-platform ratios, same convention on every path.

    vs_baseline: conservative — divides by the torch baseline's best
    measured rate over ITS B sweep INCLUDING the dedup-granted rows (an
    optimization the reference lacks; reported via baseline_rate_used).
    vs_reference_no_dedup: the reference-equivalent no-dedup rate (the
    reference re-encodes per sample, model.py:41-61).

    Clamp rule (ADVICE r3): when our sweep extends past the largest B the
    baseline measured, the ratio numerator is our best rate among rows the
    baseline also measured — never a row whose baseline counterpart is an
    unmeasured assumption. The clamp becomes a no-op once
    ``benchmarks/torch_baseline.py --extend`` fills the baseline sweep to
    the same max B. Module-level (not nested in main) so the policy is
    unit-testable: tests/test_bench_policy.py.
    """
    if not baseline_path.exists():
        return {}
    base = json.loads(baseline_path.read_text())
    base_sweep = base.get("b_sweep_samples_per_sec") or {}
    base_rate = max([base["samples_per_sec"], *base_sweep.values()])
    ref_rates = [
        v for k, v in base_sweep.items() if not k.endswith("_dedup")
    ] or [base["samples_per_sec"]]
    fields: dict = {}
    cmp_rate = rate
    base_max_b = max((int(k.split("_")[0]) for k in base_sweep), default=None)
    if our_sweep and base_max_b is not None:
        eligible = [v for k, v in our_sweep.items() if int(k) <= base_max_b]
        if eligible and max(eligible) < rate:
            cmp_rate = max(eligible)
            fields["ratio_rate_used"] = cmp_rate
            fields["ratio_clamped_to_b"] = base_max_b
        elif not eligible:
            # no measured row in the baseline's range at all (every small-B
            # point failed this window) — the ratio then compares beyond
            # the baseline's measured range; say so rather than silently
            # reinstating the unmeasured-baseline assumption
            fields["ratio_beyond_baseline_range"] = True
    fields.update(
        {
            "vs_baseline": round(cmp_rate / base_rate, 2),
            "baseline_rate_used": base_rate,
            "vs_reference_no_dedup": round(cmp_rate / max(ref_rates), 2),
        }
    )
    return fields


def _promote_best_sweep_row(out: dict, sweep: dict, flops_of, peak, ratios) -> None:
    """Headline = the best sweep row, UNCONDITIONALLY once any sweep row
    exists (module docstring: B=64 is dispatch-bound and noisy; large-B rows
    are compute-bound and stable — so even a B=64 reading that beats every
    sweep row is not a better number; ADVICE r3). Idempotent and called
    after EVERY sweep point — the B=64 host-deduped row is captured into b64_*
    exactly once, on first promotion. ``flops_of(b)`` returns analytic step FLOPs at batch
    ``b``; ``ratios(rate, our_sweep=...)`` returns the baseline-ratio
    fields. Module-level so the policy is unit-testable.
    """
    if not sweep:
        return
    best_b = max(sweep, key=lambda k: sweep[k])
    best_rate = sweep[best_b]
    if out.get("headline_source") == "flagship_b64":
        out["b64_samples_per_sec"] = out["value"]
        out["b64_sec_per_step"] = out["sec_per_step"]
        out["b64_encode_rows"] = out["encode_rows"]
        out["b64_flops_per_step"] = out.get("flops_per_step")
        if "mfu_estimate" in out:
            out["b64_mfu_estimate"] = out["mfu_estimate"]
    bb = int(best_b)
    dt_best = bb / best_rate
    out["value"] = best_rate
    out["batch_size"] = bb
    out["sec_per_step"] = round(dt_best, 6)
    out["encode_rows"] = 0  # sweep rows dedup on the device, at the slot count
    out["headline_source"] = "b_sweep_uncapped"
    # clamp candidates: the sweep rows plus the B=64 flagship (a measured,
    # dispatch-bound — hence conservative — point inside the baseline's
    # range, so a window where every small-B sweep point failed still
    # clamps to a measured row instead of comparing beyond the baseline)
    candidates = dict(sweep)
    if out.get("b64_samples_per_sec") is not None:
        candidates.setdefault("64", out["b64_samples_per_sec"])
    # the ratio fields are recomputed whole each promotion: drop any stale
    # clamp annotations from an earlier promotion where the clamp bit
    for stale in (
        "ratio_rate_used", "ratio_clamped_to_b", "ratio_beyond_baseline_range",
    ):
        out.pop(stale, None)
    out.update(ratios(best_rate, our_sweep=candidates))
    # flops are analytic (no peak needed); mfu needs the chip's peak
    out["flops_per_step"] = flops_of(bb)
    if peak is not None:
        out["mfu_estimate"] = round(out["flops_per_step"] / dt_best / peak, 4)
    else:
        out.pop("mfu_estimate", None)
    out["headline_note"] = (
        "headline is the best row of the B sweep (uncapped step; "
        "headline_source=b_sweep_uncapped): at B=64 the step is "
        "dispatch-bound, not chip-bound. vs_baseline divides by "
        "the torch-CPU baseline's best measured rate over ITS B sweep "
        "INCLUDING dedup-granted rows (baseline_rate_used — an "
        "optimization the reference lacks, granted to keep the ratio "
        "conservative); vs_reference_no_dedup uses the no-dedup "
        "reference-equivalent rate. When our sweep extends past the "
        "baseline's largest measured B, both ratios use our best rate "
        "among Bs the baseline also measured "
        "(ratio_rate_used/ratio_clamped_to_b appear when the clamp "
        "bites). b64_* fields keep the round-1/2 flagship point."
    )


def main() -> None:
    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.fed import get_strategy
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.parallel import client_mesh, shard_batch
    from fedrec_tpu.train import build_fed_train_step
    from fedrec_tpu.train.state import init_client_state, replicate_state
    from fedrec_tpu.train.step import commit_token_table

    device = jax.devices()[0]
    platform = device.platform
    on_tpu = platform == "tpu"
    # FEDREC_BENCH_SMOKE=1 (CPU-only test hook): tiny shapes + short chains
    # so an integration test of the output path finishes in seconds.
    # Deliberately IGNORED on TPU — a real-chip number must never be
    # produced at smoke scale.
    smoke = (not on_tpu) and os.environ.get("FEDREC_BENCH_SMOKE") == "1"
    if not on_tpu and not smoke:
        sys.stderr.write(
            f"[bench] needs a TPU: JAX's first device is {platform!r} "
            f"({getattr(device, 'device_kind', '?')}). This benchmark never "
            "measures another backend (FEDREC_BENCH_SMOKE=1 is the CPU test "
            "hook).\n"
        )
        sys.exit(1)

    cfg = ExperimentConfig()
    cfg.fed.num_clients = 1
    cfg.data.batch_size = 64
    if on_tpu:
        cfg.model.dtype = "bfloat16"  # MXU-native; params/opt stay f32
    num_news, L = 4096, cfg.data.max_title_len
    if smoke:
        cfg.data.batch_size = 8
        num_news = 256
    # FEDREC_BENCH_TRACE=1: stderr progress markers inside measure() — the
    # tool that located a chain-growth explosion; costs nothing when off
    if os.environ.get("FEDREC_BENCH_TRACE") == "1":
        _tt0 = time.time()

        def _tr(msg: str) -> None:
            sys.stderr.write(f"[trace {time.time() - _tt0:7.1f}s] {msg}\n")
            sys.stderr.flush()

        _tr(f"shapes B={cfg.data.batch_size} num_news={num_news} smoke={smoke}")
    else:
        def _tr(msg: str) -> None:
            pass
    B, C, H = cfg.data.batch_size, 1 + cfg.data.npratio, cfg.data.max_his_len

    rng = np.random.default_rng(0)
    # feature table in the COMPUTE dtype (bf16 on TPU): halves the gather's
    # HBM traffic and keeps the text tower MXU-native end to end (round-2
    # bench fed f32 states into a bf16 step — VERDICT r2 Weak #2)
    token_states = jnp.asarray(
        rng.standard_normal((num_news, L, cfg.model.bert_hidden)),
        dtype=jnp.dtype(cfg.model.dtype),
    )
    model = NewsRecommender(cfg.model)
    mesh = client_mesh(1)
    # the layout the joint steps state for their table argument: committed
    # once here, or every dispatch would relay the table out
    token_states, _ = commit_token_table(token_states, mesh)
    step = build_fed_train_step(model, cfg, get_strategy("grad_avg"), mesh, mode="joint")

    def host_batch(seed: int, bsz: int, n_clients: int = 1) -> dict:
        r = np.random.default_rng(seed)
        return {
            "candidates": r.integers(
                0, num_news, (n_clients, bsz, C)
            ).astype(np.int32),
            "history": r.integers(
                0, num_news, (n_clients, bsz, H)
            ).astype(np.int32),
            "labels": np.zeros((n_clients, bsz), np.int32),
        }

    def make_batch(seed: int, bsz: int, n_clients: int = 1):
        return shard_batch(mesh, host_batch(seed, bsz, n_clients))

    def measure(bsz: int, iters: int, warmup: int = 3, the_step=None,
                feats=None, n_clients: int = 1, the_cfg=None,
                batch_maker=None):
        """Overhead-corrected sec/step: ``fedrec_tpu.utils.chain_timer``'s
        differenced 1x/2x chains, each ending in a host readback. This call
        site's policy: 4 attempts, strict raise when the delta never clears
        the 0.3 s floor."""
        from fedrec_tpu.utils.chain_timer import differenced_chain_seconds

        the_step = the_step or step
        feats = token_states if feats is None else feats
        state0 = init_client_state(
            model, the_cfg or cfg, jax.random.PRNGKey(0), num_news, L
        )
        stacked = replicate_state(state0, n_clients, jax.random.PRNGKey(1))
        mk = batch_maker or make_batch
        batches = [mk(s, bsz, n_clients) for s in range(8)]

        def chain(k: int) -> float:
            nonlocal stacked
            t0 = time.perf_counter()
            metrics = None
            for i in range(k):
                stacked, metrics = the_step(stacked, batches[i % 8], feats)
            np.asarray(metrics["loss"])  # readback = real synchronization
            return time.perf_counter() - t0

        _tr(f"measure(bsz={bsz}, iters={iters}) warmup start")
        chain(warmup)  # compile + steady-state
        _tr("warmup done")
        return differenced_chain_seconds(
            chain, iters, attempts=4, accept_positive_at_cap=False,
            label=f"step (B={bsz})", trace=_tr,
        )

    # Flagship step: the dedup runs on the host, as the Trainer's round loop
    # runs it (train/step.py: host_news_dedup). The B=64 batch gathers
    # B*(C+H)=3,520 slots but holds ~2.4k distinct ids; the step encodes the
    # size the code derives from the timed batches' own counts, and a batch
    # that exceeded it would be served at the full size, so the math is exact.
    from fedrec_tpu.train.step import (
        encode_rows_for,
        host_news_dedup,
        most_distinct_news,
    )

    flagship_rows = encode_rows_for(
        max(
            most_distinct_news(hb["candidates"], hb["history"])
            for hb in (host_batch(s, B) for s in range(8))
        ),
        min(B * (C + H), num_news),
    )

    def make_deduped_batch(seed: int, bsz: int, n_clients: int = 1):
        hb = host_batch(seed, bsz, n_clients)
        entries, _ = host_news_dedup(
            hb["candidates"], hb["history"], flagship_rows, num_news
        )
        return shard_batch(mesh, {**hb, **entries})

    dt = measure(
        B,
        iters=2 if smoke else 50,
        warmup=2 if smoke else 3,
        batch_maker=make_deduped_batch,
    )
    samples_per_sec = B / dt

    out = {
        "metric": "fedrec_train_step_throughput",
        "value": round(samples_per_sec, 2),
        "unit": "samples/sec",
        "vs_baseline": None,
        "platform": platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "dtype": cfg.model.dtype,
        "sec_per_step": round(dt, 6),
        "batch_size": B,
        "encode_rows": flagship_rows,
        "headline_source": "flagship_b64",
        "baseline": "torch-cpu reference-equivalent, see benchmarks/baseline_host.json",
    }
    if smoke:
        out["smoke"] = (
            "FEDREC_BENCH_SMOKE test artifact: tiny shapes/short chains — "
            "exists only to integration-test the output path; never quote"
        )

    baseline_path = Path(__file__).parent / "benchmarks" / "baseline_host.json"

    def baseline_ratios(rate: float, our_sweep: dict | None = None) -> dict:
        return _baseline_ratios(baseline_path, rate, our_sweep)

    out.update(baseline_ratios(samples_per_sec))

    if on_tpu:
        flops = _flops_per_train_step(cfg, B, num_news, flagship_rows)
        peak = peak_flops(device.device_kind, cfg.model.dtype)
        out["mfu_estimate"] = round(flops / dt / peak, 4)
        out["flops_per_step"] = flops

        # device-side dedup at the slot count, B=64: continuity with the
        # round-1/2 headline (whose flagship encoded every slot)
        dt_unc = measure(B, iters=50)
        out["uncapped_samples_per_sec"] = round(B / dt_unc, 2)

        # batch-size sweep (VERDICT r2 item 3): where is the throughput
        # knee? Device-side dedup (at B>=128 the dedup bound is num_news
        # anyway). B=512 is the 8-client
        # grad-avg equivalent: with per-step gradient averaging all clients
        # stay in lockstep, so 8 clients x B=64 on one chip is
        # mathematically one B=512 step.
        sweep: dict[str, float] = {}
        # sweep rows only (NOT seeded from the B=64 row: that row is
        # dispatch-bound and noisy — a high B=64 reading must not
        # masquerade as "best over sweep")
        best_mfu, best_mfu_b = 0.0, None

        def promote_best_sweep_row() -> None:
            _promote_best_sweep_row(
                out,
                sweep,
                flops_of=lambda b: _flops_per_train_step(cfg, b, num_news),
                peak=peak,
                ratios=baseline_ratios,
            )

        for bsz in (128, 256, 512, 1024, 2048, 4096, 8192):
            dt_b = measure(bsz, iters=20)
            sweep[str(bsz)] = round(bsz / dt_b, 2)
            if bsz == 512:
                out["clients8_samples_per_sec"] = round(bsz / dt_b, 2)
            mfu_b = _flops_per_train_step(cfg, bsz, num_news) / dt_b / peak
            if mfu_b > best_mfu:
                best_mfu, best_mfu_b = mfu_b, bsz
            out["b_sweep_samples_per_sec"] = sweep
            out["mfu_best_over_sweep"] = round(best_mfu, 4)
            out["mfu_best_b"] = best_mfu_b
            promote_best_sweep_row()

        # TRUE 8-client federation on the one chip via a k=8 cohort (vmap
        # over clients, grad-avg collective inside): measures the actual
        # federated program, not the B=512 lockstep-equivalence argument.
        cfg8 = copy.deepcopy(cfg)
        cfg8.fed.num_clients = 8
        step8 = build_fed_train_step(
            model, cfg8, get_strategy("grad_avg"), mesh, mode="joint"
        )
        dt8 = measure(
            B, iters=20, the_step=step8, n_clients=8, the_cfg=cfg8
        )
        out["cohort8_samples_per_sec"] = round(8 * B / dt8, 2)

        # decoupled (reference-parity) mode: the text tower leaves the step —
        # news vecs come from a precomputed (N, D) table gather; this is the
        # per-batch cost the reference's epoch structure actually implies.
        from fedrec_tpu.train import encode_all_news

        p0 = init_client_state(model, cfg, jax.random.PRNGKey(0), num_news, L)
        table = encode_all_news(model, p0.news_params, token_states)
        step_d = build_fed_train_step(
            model, cfg, get_strategy("grad_avg"), mesh, mode="decoupled"
        )
        dt_d = measure(B, iters=100, the_step=step_d, feats=table)
        out["decoupled_samples_per_sec"] = round(B / dt_d, 2)
        # decoupled at the 8-client lockstep batch: the per-batch cost
        # the reference's epoch structure implies, at real utilization
        dt_d8 = measure(512, iters=50, the_step=step_d, feats=table)
        out["decoupled_clients8_samples_per_sec"] = round(512 / dt_d8, 2)

        # sharded catalog (shard.table, ISSUE 11): the same joint step with
        # the token-state table row-sharded over a multi-device client mesh
        # and gathered via the owner-bucketed all_to_all exchange, against
        # the config-matched replicated-table step on the SAME mesh — what
        # one step pays for linear catalog capacity. Needs >= 2 devices.
        n_dev = len(jax.devices())
        if n_dev >= 2:
            from fedrec_tpu.shard.table import ShardedNewsTable

            n_sh = min(4, n_dev)
            cfg_sh = copy.deepcopy(cfg)
            cfg_sh.fed.num_clients = n_sh
            mesh_sh = client_mesh(n_sh)
            tab = ShardedNewsTable.create(
                np.asarray(token_states), mesh_sh, cfg_sh.fed.mesh_axis
            )
            step_rep = build_fed_train_step(
                model, cfg_sh, get_strategy("grad_avg"), mesh_sh,
                mode="joint",
            )
            step_sh = build_fed_train_step(
                model, cfg_sh, get_strategy("grad_avg"), mesh_sh,
                mode="joint", sharded_table=tab.spec,
            )

            def make_mesh_batch(seed: int, bsz: int, n_clients: int = 1):
                return make_batch(seed, bsz, n_clients=n_sh)

            dt_rep = measure(
                B, iters=10, the_step=step_rep, n_clients=n_sh,
                the_cfg=cfg_sh, batch_maker=make_mesh_batch,
            )
            dt_sh = measure(
                B, iters=10,
                the_step=lambda st, b, t: step_sh(st, b, tab.rows),
                n_clients=n_sh, the_cfg=cfg_sh,
                batch_maker=make_mesh_batch,
            )
            out["sharded_gather"] = {
                "devices": n_sh,
                "rows_per_device": tab.spec.rows_per_shard,
                "replicated_samples_per_sec": round(n_sh * B / dt_rep, 2),
                "sharded_samples_per_sec": round(n_sh * B / dt_sh, 2),
                "sharded_vs_replicated": round(dt_rep / dt_sh, 3),
                "note": (
                    "capacity lever, not a speed lever: the sharded "
                    "row buys rows/device = N/devices at this step-"
                    "time ratio (docs/OPERATIONS.md §3e)"
                ),
            }

    from fedrec_tpu.utils.provenance import provenance

    out["provenance"] = provenance()
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
