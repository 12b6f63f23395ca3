"""Decompose the flagship joint train step's time on the real chip — and
turn it into a roofline verdict (VERDICT r3 #2).

Times each component of the joint step with the differenced chain timer
(``pallas_bench._time``) at B=64 (the flagship continuity point) AND at the
throughput-optimal B=1024: token-state gather, unique-ids dedup, text tower
fwd / fwd+bwd, user tower fwd / fwd+bwd, and the full step. For the full
step it also computes an explicit FLOPs + HBM-bytes model and reports, per
batch size:

  * achieved FLOP/s as a fraction of the chip's matmul peak (the MFU), and
  * achieved HBM GB/s as a fraction of peak bandwidth,

so the artifact SAYS whether the 0.11–0.23 MFU window is a memory-bound
ceiling (bandwidth fraction high) or unclaimed headroom (both fractions
low → dispatch/latency/fusion problem). Assumptions of the bytes model are
recorded in the artifact: the timed program is grad-only (no optimizer
update, so no param/moment traffic), token states read twice (fwd + bwd
recompute), activations touched twice.

Per B the artifact ALSO carries ``host_pipeline`` rows (the input side of
the cliff attribution): host batch-build time, host→device transfer time,
and the per-step wall time of a build→transfer→dispatch loop run
synchronously vs through the bounded ``data.prefetch_batches`` prefetcher —
the difference is the measured dispatch-gap reduction the overlapped
input pipeline buys. The bound verdict then classifies each B as
compute-bound, HBM-bound, input-bound (host pipeline ≥ device step), or
unclaimed dispatch/latency/fusion headroom.

Run on TPU:  python benchmarks/step_profile.py
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

_REPO = str(Path(__file__).resolve().parent.parent)
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from pallas_bench import _time  # noqa: E402  (same honest timer)

# peaks, verdict spellings and the analytic FLOPs model are shared with
# bench.py's headline MFU and the live per-round gauges via ONE module
# (fedrec_tpu.obs.perf) — the artifacts, the bench and the telemetry can
# never desync on a number or a verdict string
from fedrec_tpu.obs.perf import (  # noqa: E402
    CHIP_PEAKS as _PEAKS,
    flops_per_train_step as _flops_per_train_step,
    roofline_verdict,
)

def _host_pipeline_rows(
    step_fn, B: int, C: int, H: int, num_news: int, on_cpu: bool
) -> dict:
    """Measure the INPUT side of the step: host batch build, host→device
    transfer, and the dispatch gap of a synchronous build→transfer→dispatch
    loop vs the same loop behind the bounded prefetcher
    (``fedrec_tpu.data.prefetch``). ``step_fn(candidates, history)`` must be
    a compiled, already-warm device program returning a scalar.

    Both loop timings end in ONE host readback, so the
    fixed chain round-trip constant is shared and the sync−prefetch
    DIFFERENCE (the dispatch-gap reduction) is meaningful even where
    absolute per-step walls are not.
    """
    # NOT `as _time`: module scope already binds _time to pallas_bench's
    # chain timer, and shadowing it with the stdlib module is a trap for
    # anyone moving timing code between here and main()
    import time as _t

    import jax
    import jax.numpy as jnp

    from fedrec_tpu.data.batcher import IndexedSamples, TrainBatcher
    from fedrec_tpu.data.prefetch import Prefetcher

    rng = np.random.default_rng(7)
    n = max(4 * B, 256)
    pool = 20
    ix = IndexedSamples(
        pos=rng.integers(0, num_news, n).astype(np.int32),
        neg_pools=rng.integers(0, num_news, (n, pool)).astype(np.int32),
        neg_lens=np.full(n, pool, np.int32),
        history=rng.integers(0, num_news, (n, H)).astype(np.int32),
        his_len=np.full(n, H, np.int32),
    )
    batcher = TrainBatcher(ix, B, npratio=C - 1, seed=0)

    # host batch build: a full epoch of real builds (shuffle + negative
    # sampling + packing), wall per batch
    t0 = _t.perf_counter()
    cnt = sum(1 for _ in batcher.epoch_batches(0))
    build_ms = (_t.perf_counter() - t0) / max(cnt, 1) * 1e3

    # host->device transfer of one built batch (sync'd per rep)
    b0 = next(iter(batcher.epoch_batches(1)))

    def put(b):
        return (jnp.asarray(b.candidates), jnp.asarray(b.history))

    jax.block_until_ready(put(b0))
    reps = 5 if on_cpu else 20
    t0 = _t.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(put(b0))
    h2d_ms = (_t.perf_counter() - t0) / reps * 1e3

    # dispatch gap: K steps of build -> transfer -> dispatch. The gap is
    # measured DIRECTLY as the host-side latency between a dispatch
    # returning and the next batch being ready to dispatch — the interval
    # the device's program queue sits empty because the host is busy
    # building input. Robust on any host (it times only host intervals,
    # never device completion); the end-to-end walls ride along as
    # secondary rows for the chip run, where device time is off-host and
    # the wall difference becomes meaningful too.
    K = 8 if on_cpu else 48

    def gen(limit: int):
        e, count = 2, 0
        while count < limit:
            for b in batcher.epoch_batches(e):
                yield b
                count += 1
                if count >= limit:
                    return
            e += 1

    def gap_loop(fn, source, n_steps, readback=True) -> tuple[float, float]:
        """(wall ms/step, mean host gap ms between dispatches)."""
        gaps = []
        dep = None
        t_prev = None
        t0 = _t.perf_counter()
        for args in source:
            t_ready = _t.perf_counter()
            if t_prev is not None:
                gaps.append(t_ready - t_prev)
            dep = fn(*args)
            t_prev = _t.perf_counter()
        if readback:
            np.asarray(dep)  # readback = real synchronization
        wall = (_t.perf_counter() - t0) / n_steps * 1e3
        return wall, float(np.mean(gaps)) * 1e3

    sync_wall, sync_gap = gap_loop(step_fn, (put(b) for b in gen(K)), K)
    pf = Prefetcher(gen(K), depth=2, transform=put)
    prefetch_wall, prefetch_gap = gap_loop(step_fn, pf, K)

    rows = {
        "batch_build_ms": round(build_ms, 4),
        "h2d_ms": round(h2d_ms, 4),
        "pipeline_steps": K,
        "prefetch_depth": 2,
        "dispatch_gap_sync_ms": round(sync_gap, 4),
        "dispatch_gap_prefetch_ms": round(prefetch_gap, 4),
        "sync_wall_ms_per_step": round(sync_wall, 4),
        "prefetch_wall_ms_per_step": round(prefetch_wall, 4),
        "note": (
            "dispatch_gap_* is the host-side latency between a dispatch "
            "returning and the next batch being ready (build+transfer on "
            "the sync path; queue-get on the prefetch path) — the time the "
            "device program queue would sit empty. The *_wall rows are "
            "end-to-end (one shared final-readback constant). On a 1-core "
            "CPU backend the producer thread is starved while XLA owns the "
            "core (no spare cycles = no overlap, by physics), so there the "
            "headline reduction comes from the offhost_sim_* rows: the "
            "same loops against a time.sleep device interval, which "
            "releases the core exactly like an off-host accelerator does"
        ),
    }

    if on_cpu:
        # off-host device simulation: sleep releases the GIL and the core,
        # so the producer can actually run ahead — the faithful model of
        # an accelerator whose compute happens off-host
        tau_s = 0.002
        K_sim = 16

        def sim_step(*args):
            _t.sleep(tau_s)
            return 0.0

        _, sim_sync_gap = gap_loop(
            sim_step, (put(b) for b in gen(K_sim)), K_sim, readback=False
        )
        pf2 = Prefetcher(gen(K_sim), depth=2, transform=put)
        _, sim_prefetch_gap = gap_loop(sim_step, pf2, K_sim, readback=False)
        rows["offhost_sim_tau_ms"] = tau_s * 1e3
        rows["offhost_sim_gap_sync_ms"] = round(sim_sync_gap, 4)
        rows["offhost_sim_gap_prefetch_ms"] = round(sim_prefetch_gap, 4)
        rows["dispatch_gap_reduction_ms"] = round(
            sim_sync_gap - sim_prefetch_gap, 4
        )
        rows["dispatch_gap_reduction_source"] = "offhost_sim"
    else:
        rows["dispatch_gap_reduction_ms"] = round(sync_gap - prefetch_gap, 4)
        rows["dispatch_gap_reduction_source"] = "measured_device"
    return rows


def main() -> int:
    import argparse

    import jax
    import jax.numpy as jnp

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.models import NewsRecommender, score_loss
    from fedrec_tpu.train.step import _batch_news_vecs

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--cpu", action="store_true",
                   help="profile the step on the CPU with plain local "
                        "timing")
    args = p.parse_args()

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu and not args.cpu:
        print("needs the TPU (honest timing assumptions); pass --cpu to "
              "profile the CPU-fallback step", file=sys.stderr)
        return 1

    cfg = ExperimentConfig()
    cfg.model.dtype = "float32" if on_cpu else "bfloat16"
    num_news, L = 4096, cfg.data.max_title_len
    C, H = 1 + cfg.data.npratio, cfg.data.max_his_len
    Dh, D = cfg.model.bert_hidden, cfg.model.news_dim
    dt_bytes = 4 if cfg.model.dtype == "float32" else 2

    rng = np.random.default_rng(0)
    token_states = jnp.asarray(
        rng.standard_normal((num_news, L, Dh), dtype=np.float32),
        jnp.dtype(cfg.model.dtype),
    )
    model = NewsRecommender(cfg.model)
    dummy_cand = jnp.zeros((1, C, D), jnp.dtype(cfg.model.dtype))
    dummy_his = jnp.zeros((1, H, D), jnp.dtype(cfg.model.dtype))
    variables = model.init(
        jax.random.PRNGKey(0), token_states[:1], dummy_cand, dummy_his,
        method=NewsRecommender.init_both_towers,
    )
    text_p = variables["params"]["text_head"]
    user_p = variables["params"]["user_encoder"]
    kind = getattr(jax.devices()[0], "device_kind", "").lower()
    peaks = next((v for f, v in _PEAKS.items() if f in kind), None)

    def flops_of(B: int, U: int) -> float:
        return _flops_per_train_step(cfg, B, num_news)

    def bytes_of(B: int, U: int) -> float:
        """HBM traffic model for the TIMED program — full_fwd_bwd, a
        grad-only step with NO optimizer update, so no params/Adam-moment
        traffic is charged (assumptions in the module docstring; recorded
        in the artifact). Param/grad reads are negligible next to the
        token-state traffic (~100 KB vs hundreds of MB)."""
        token_reads = 2 * U * L * Dh * dt_bytes          # fwd + bwd recompute
        text_acts = 2 * U * (L * att_hidden_bytes() + D * dt_bytes)
        user_acts = 2 * B * (C + H) * D * dt_bytes * 3   # vecs, attn ctx, pool
        return token_reads + text_acts + user_acts

    def att_hidden_bytes() -> int:
        return (Dh // 2) * dt_bytes

    from fedrec_tpu.utils.provenance import provenance, write_artifact

    # CPU profiles land in their own artifact so a future chip run never
    # gets shadowed (and vice versa)
    name = "step_profile_cpu.json" if on_cpu else "step_profile.json"

    out_all = {}

    def _stamp(partial: bool) -> None:
        # incremental banking: every completed row must survive a run that
        # is killed mid-way; a complete artifact carries no "partial".
        write_artifact(Path(__file__).with_name(name), {
            "dtype": cfg.model.dtype,
            "batches": out_all,
            "bytes_model_assumptions": (
                "timed program is grad-only (no optimizer update, so no "
                "param/Adam-moment traffic); token states charged 2x (the "
                "gather read + the backward's re-read of the saved result: "
                "the gather is stop_gradient-ed and tagged "
                "checkpoint_name('token_gather') in train/step.py, so no "
                "cotangent scatter into the table exists and remat policies "
                "can keep it saved rather than re-gathered); text/user "
                "activations touched 2x; weight/grad reads ignored "
                "(~100 KB vs hundreds of MB); gather index traffic ignored"
            ),
            "provenance": provenance(),
        }, partial)

    batches = (64,) if on_cpu else (64, 1024, 4096)
    for B in batches:
        try:
            candidates = jnp.asarray(
                rng.integers(0, num_news, (B, C)).astype(np.int32)
            )
            history = jnp.asarray(
                rng.integers(0, num_news, (B, H)).astype(np.int32)
            )
            labels = jnp.zeros((B,), jnp.int32)
            size = B * (C + H)
            U = min(size, num_news)
            flat_ids = jnp.concatenate(
                [candidates.reshape(-1), history.reshape(-1)]
            )

            # ---- components (first arg is the one _time perturbs/chains on)
            def gather_only(ts):
                uniq, inv = jnp.unique(flat_ids, size=U, fill_value=0,
                                       return_inverse=True)
                return ts[uniq].sum()

            def unique_only(ids_f32):
                # float so the chain perturbation type-checks; cast back
                uniq, inv = jnp.unique(ids_f32.astype(jnp.int32), size=U,
                                       fill_value=0, return_inverse=True)
                return uniq.sum() + inv.sum()

            def text_fwd(ts):
                uniq, _ = jnp.unique(flat_ids, size=U, fill_value=0,
                                     return_inverse=True)
                return model.apply({"params": {"text_head": text_p}}, ts[uniq],
                                   method=NewsRecommender.encode_news).sum()

            def text_fwd_bwd(ts):
                def loss(p):
                    uniq, _ = jnp.unique(flat_ids, size=U, fill_value=0,
                                         return_inverse=True)
                    return model.apply({"params": {"text_head": p}}, ts[uniq],
                                       method=NewsRecommender.encode_news).sum()
                g = jax.grad(loss)(text_p)
                # sum EVERY leaf: a single bias-grad leaf can be input-
                # independent, letting XLA fold the chained body to a constant
                return sum(l.sum() for l in jax.tree_util.tree_leaves(g))

            cand_vecs, his_vecs = _batch_news_vecs(
                model, text_p, token_states, candidates, history
            )

            # the chain timer perturbs the FIRST argument; it must be the
            # HISTORY vecs — the self-attention (the user tower's dominant
            # cost) runs over his_vecs alone, and with cand_vecs as the
            # perturbed arg XLA hoists the whole loop-invariant attention out
            # of the chain (measured: 0.019 ms "user_fwd" on CPU)
            def user_fwd(hv):
                return model.apply(
                    {"params": {"user_encoder": user_p}}, cand_vecs, hv
                ).sum()

            def user_fwd_bwd(hv):
                def loss(p):
                    scores = model.apply(
                        {"params": {"user_encoder": p}}, cand_vecs, hv
                    )
                    return score_loss(scores, labels)
                g = jax.grad(loss)(user_p)
                return sum(l.sum() for l in jax.tree_util.tree_leaves(g))

            def full_fwd_bwd(ts):
                def loss(ps):
                    cv, hv = _batch_news_vecs(
                        model, ps["text"], ts, candidates, history
                    )
                    scores = model.apply(
                        {"params": {"user_encoder": ps["user"]}}, cv, hv
                    )
                    return score_loss(scores, labels)
                g = jax.grad(loss)({"text": text_p, "user": user_p})
                return sum(l.sum() for l in jax.tree_util.tree_leaves(g))

            comps = {
                "unique_only": (unique_only, flat_ids.astype(jnp.float32)),
                "gather_only": (gather_only, token_states),
                "text_fwd": (text_fwd, token_states),
                "text_fwd_bwd": (text_fwd_bwd, token_states),
                "user_fwd": (user_fwd, his_vecs),
                "user_fwd_bwd": (user_fwd_bwd, his_vecs),
                "full_fwd_bwd": (full_fwd_bwd, token_states),
            }
            if B == 64:
                # the FLAGSHIP configuration: the dedup done on the host, at
                # the size the round loop would choose (bench.py)
                from fedrec_tpu.train.step import (
                    NEWS_INVERSE, NEWS_ROWS, encode_rows_for, host_news_dedup,
                    most_distinct_news,
                )

                cand_np = np.asarray(candidates)[None]
                his_np = np.asarray(history)[None]
                entries, _ = host_news_dedup(
                    cand_np, his_np,
                    encode_rows_for(most_distinct_news(cand_np, his_np), U),
                    num_news,
                )
                host_dedup = (
                    jnp.asarray(entries[NEWS_ROWS][0]),
                    jnp.asarray(entries[NEWS_INVERSE][0]),
                )

                def full_fwd_bwd_host_dedup(ts):
                    def loss(ps):
                        cv, hv = _batch_news_vecs(
                            model, ps["text"], ts, candidates, history,
                            host_dedup=host_dedup,
                        )
                        scores = model.apply(
                            {"params": {"user_encoder": ps["user"]}}, cv, hv
                        )
                        return score_loss(scores, labels)
                    g = jax.grad(loss)({"text": text_p, "user": user_p})
                    return sum(l.sum() for l in jax.tree_util.tree_leaves(g))

                comps["full_fwd_bwd_host_dedup"] = (
                    full_fwd_bwd_host_dedup, token_states
                )

            res = {}
            entry = {"components_ms": res}
            out_all[str(B)] = entry
            for comp_name, (fn, arg0) in comps.items():
                t = _time(jax.jit(fn), arg0, iters=3 if on_cpu else 30)
                res[comp_name] = round(t * 1e3, 4)
                print(f"B={B:5d} {comp_name:22s} {t*1e3:9.3f} ms", flush=True)
                _stamp(partial=True)
            if on_cpu:
                # seconds-long CPU components at iters=3 on a shared 1-core
                # host carry ~±10% run-to-run noise — enough for a component
                # to read slower than the full step it decomposes; say so in
                # the artifact rather than pay minutes per extra iteration
                entry["cpu_noise_note"] = (
                    "components measured at iters=3 on a 1-core host: ~±10% "
                    "noise, so component/full-step shares are indicative "
                    "only; compute shares from the chip artifact "
                    "(step_profile.json)"
                )

            # ---- host pipeline (the input side of the cliff attribution)
            def step_pipe(cand, his):
                def loss(ps):
                    cv, hv = _batch_news_vecs(
                        model, ps["text"], token_states, cand, his
                    )
                    scores = model.apply(
                        {"params": {"user_encoder": ps["user"]}}, cv, hv
                    )
                    return score_loss(scores, labels)
                g = jax.grad(loss)({"text": text_p, "user": user_p})
                return sum(l.sum() for l in jax.tree_util.tree_leaves(g))

            step_pipe = jax.jit(step_pipe)
            np.asarray(step_pipe(candidates, history))  # compile + warm
            entry["host_pipeline"] = _host_pipeline_rows(
                step_pipe, B, C, H, num_news, on_cpu
            )
            host_ms = (
                entry["host_pipeline"]["batch_build_ms"]
                + entry["host_pipeline"]["h2d_ms"]
            )
            entry["host_per_step_ms"] = round(host_ms, 4)
            print(
                f"B={B:5d} host pipeline: build "
                f"{entry['host_pipeline']['batch_build_ms']:.2f} ms, h2d "
                f"{entry['host_pipeline']['h2d_ms']:.2f} ms, dispatch-gap "
                f"reduction "
                f"{entry['host_pipeline']['dispatch_gap_reduction_ms']:.2f} "
                "ms/step (prefetch depth 2)",
                flush=True,
            )
            _stamp(partial=True)

            # roofline for the full step at this B
            t_full = res["full_fwd_bwd"] / 1e3
            fl, by = flops_of(B, U), bytes_of(B, U)
            entry["model_flops"] = fl
            entry["model_hbm_bytes"] = by
            entry["arithmetic_intensity"] = round(fl / by, 2)
            # a starved device is input-bound no matter what its roofline
            # fractions say: the host cannot feed batches as fast as the
            # device retires them
            input_bound = host_ms >= res["full_fwd_bwd"]
            if peaks is not None:
                peak_fl = peaks[0] if cfg.model.dtype == "bfloat16" else peaks[1]
                peak_bw = peaks[2]
                entry["mfu"] = round(fl / t_full / peak_fl, 4)
                entry["hbm_fraction"] = round(by / t_full / peak_bw, 4)
                entry["ridge_intensity"] = round(peak_fl / peak_bw, 1)
                _, bound = roofline_verdict(
                    input_bound, mfu=entry["mfu"],
                    hbm_fraction=entry["hbm_fraction"],
                )
                entry["verdict"] = bound
                print(f"B={B:5d} roofline: MFU {entry['mfu']:.3f}, "
                      f"HBM {entry['hbm_fraction']:.3f} of peak -> {bound}",
                      flush=True)
            else:
                _, entry["verdict"] = roofline_verdict(input_bound)
            _stamp(partial=True)
        except Exception as e:  # noqa: BLE001
            # a deterministic per-B failure (e.g. an OOM at the new large-B
            # leg) must not leave the artifact permanently partial — record
            # the skip and let the run COMPLETE so the queue item banks
            out_all[str(B)] = {"skipped": f"{type(e).__name__}: {str(e)[:160]}"}
            print(f"B={B:5d} SKIPPED: {type(e).__name__}: {str(e)[:140]}",
                  flush=True)
            _stamp(partial=True)

    _stamp(partial=False)
    return 0



if __name__ == "__main__":
    raise SystemExit(main())
