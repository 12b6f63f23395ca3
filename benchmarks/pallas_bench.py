"""Microbenchmark: attention implementations on the real TPU.

Three-way comparison at reference scale (H=50), long-context (H=1024), and
beyond-dense scales (H=2048 needs a ~21 GB dense score tensor, H=4096 ~85 GB
— on a 16 GB v5e those OOMs are recorded as the datapoint; pallas/chunked
run O(L) end to end, incl. the blocked flash backward):

  * XLA dense attention   (the ``attn_impl='dense'`` model path)
  * Pallas flash kernel   (``'pallas'``)
  * blockwise lax.scan    (``'chunked'``, the O(L)-memory long-context path)

plus ``additive_pool`` (Pallas vs XLA) at the two sizes that fit. Emits one
markdown table (stdout) and ``benchmarks/pallas_bench.json`` — the evidence
behind the ``model.attn_impl`` defaults: enable an implementation only where
it wins on real hardware (VERDICT round 1, item 5).

Off-TPU the kernels run in interpret mode, which measures nothing useful —
the script refuses to run unless a TPU backend is live (or --force).
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import sys

import numpy as np

_REPO = str(Path(__file__).resolve().parent.parent)
if _REPO not in sys.path:  # runnable as `python benchmarks/pallas_bench.py`
    sys.path.insert(0, _REPO)


def _time(fn, *args, iters: int = 30) -> float:
    """Per-call seconds of a device op.

    The op runs INSIDE one jitted ``lax.scan`` with a scalar data
    dependency between iterations, synchronization is a host readback, and
    the fixed per-chain cost cancels by differencing a 2x-length chain.
    The differencing protocol (and its caveats) lives in ONE place —
    ``fedrec_tpu.utils.chain_timer`` — shared with ``bench.py measure()``;
    this call site keeps its historical policy bits: 6 attempts, and at
    the 2000-iter cap any positive delta is accepted (op chains hit the
    cap on fast ops where the capped delta is still meaningful).
    """
    import jax
    import jax.numpy as jnp

    from fedrec_tpu.utils.chain_timer import differenced_chain_seconds

    def looped(n):
        @jax.jit
        def run(*args):
            first, rest = args[0], args[1:]

            def body(carry, _):
                out = fn(first + carry, *rest)
                z = sum(jnp.sum(l) for l in jax.tree_util.tree_leaves(out))
                # NOT z*0: x*0 is statically zero, so XLA's algebraic
                # simplifier folds the carry, sees a loop-invariant body,
                # hoists it out of the scan, and the chain times as ~0 ms
                # (observed on CPU for grad components). A tiny non-zero
                # multiplier keeps the data dependency real while leaving
                # the op's inputs numerically unchanged.
                return (z.astype(jnp.float32) * 1e-30).astype(first.dtype), None

            carry, _ = jax.lax.scan(
                body, jnp.zeros((), first.dtype), None, length=n
            )
            return carry

        return run

    def chain(n: int) -> float:
        run = looped(n)
        np.asarray(run(*args))  # compile + warm
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            np.asarray(run(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    return differenced_chain_seconds(
        chain, iters, attempts=6, accept_positive_at_cap=True, label="op"
    )


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--force", action="store_true", help="run off-TPU anyway")
    parser.add_argument("--batch", type=int, default=64)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from fedrec_tpu.ops.attention_kernels import additive_pool, flash_attention
    from fedrec_tpu.ops.chunked_attention import chunked_attention

    platform = jax.devices()[0].platform
    if platform == "cpu" and not args.force:
        print("refusing to microbench Pallas kernels off-TPU (interpret mode); "
              "pass --force to override")
        return 1

    skips: dict[str, str] = {}

    def try_time(label, fn, *a):
        """None when the variant fails — dense at H=4096 needs an 85 GB score
        tensor, and that OOM IS the datapoint. The exception class+message is
        recorded per label so a jitter RuntimeError or a kernel bug is never
        mistaken for an OOM in the evidence JSON."""
        try:
            return _time(fn, *a)
        except Exception as e:  # noqa: BLE001
            reason = f"{type(e).__name__}: {str(e)[:160]}"
            skips[label] = reason
            print(f"    [skip] {label}: {reason[:140]}")
            return None

    B, heads, dk, D, hidden = args.batch, 20, 20, 400, 200
    rows = []

    from fedrec_tpu.utils.provenance import provenance, write_artifact

    def _stamp(partial: bool) -> None:
        # incremental banking: every measured row must survive a run that
        # is killed mid-way; a partial artifact is still labeled evidence.
        write_artifact(Path(__file__).with_name("pallas_bench.json"), {
            "platform": platform, "batch": B,
            "rows": [
                {"op": name, "H": H,
                 "xla_ms": t_x and t_x * 1e3,
                 "pallas_ms": t_p and t_p * 1e3,
                 "chunked_ms": t_c and t_c * 1e3,
                 # dtype tags feed the evidence-driven attn_impl="auto"
                 # resolver (fedrec_tpu.ops.autotune) per (H, dtype) regime
                 "dtype": rest[0] if rest else "float32"}
                for name, H, t_x, t_p, t_c, *rest in rows
            ],
            "skipped": skips, "provenance": provenance(),
        }, partial)

    for H in (50, 1024, 2048, 4096):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((B, H, heads, dk)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((B, H, heads, dk)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((B, H, heads, dk)).astype(np.float32))
        mask = jnp.asarray((rng.random((B, H)) > 0.1).astype(np.float32))

        def dense_attn(q, k, v, mask):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(dk))
            s = jnp.where(mask[:, None, None, :] > 0, s, -1e9)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        pallas_attn = jax.jit(lambda q, k, v, m: flash_attention(q, k, v, m))
        chunk_attn = jax.jit(lambda q, k, v, m: chunked_attention(q, k, v, m))
        xla_attn = jax.jit(dense_attn)

        def g_of(fn):
            return jax.jit(
                lambda q, k, v, m: jax.grad(lambda q: fn(q, k, v, m).sum())(q)
            )

        rows.append(("attention fwd", H,
                     try_time(f"xla/fwd/{H}", xla_attn, q, k, v, mask),
                     try_time(f"pallas/fwd/{H}", pallas_attn, q, k, v, mask),
                     try_time(f"chunked/fwd/{H}", chunk_attn, q, k, v, mask)))
        rows.append(("attention fwd+bwd", H,
                     try_time(f"xla/bwd/{H}", g_of(dense_attn), q, k, v, mask),
                     try_time(f"pallas/bwd/{H}", g_of(flash_attention), q, k, v, mask),
                     try_time(f"chunked/bwd/{H}", g_of(chunked_attention), q, k, v, mask)))
        _stamp(partial=True)

        if H <= 1024:
            # bf16 rows at the training-relevant sizes: the production TPU
            # dtype (bench.py trains bf16), without which the
            # evidence-driven attn_impl="auto" resolver (ops/autotune.py,
            # exact (H, dtype) match) could never fire for bf16 models
            qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
            rows.append((
                "attention fwd", H,
                try_time(f"xla/fwd16/{H}", xla_attn, qb, kb, vb, mask),
                try_time(f"pallas/fwd16/{H}", pallas_attn, qb, kb, vb, mask),
                try_time(f"chunked/fwd16/{H}", chunk_attn, qb, kb, vb, mask),
                "bfloat16",
            ))

            def g16_of(fn):
                return jax.jit(lambda q, k, v, m: jax.grad(
                    lambda q: fn(q, k, v, m).astype(jnp.float32).sum()
                )(q))

            rows.append((
                "attention fwd+bwd", H,
                try_time(f"xla/bwd16/{H}", g16_of(dense_attn), qb, kb, vb, mask),
                try_time(f"pallas/bwd16/{H}", g16_of(flash_attention), qb, kb, vb, mask),
                try_time(f"chunked/bwd16/{H}", g16_of(chunked_attention), qb, kb, vb, mask),
                "bfloat16",
            ))
            _stamp(partial=True)

        if H >= 2048:
            continue  # pool is O(L)-memory everywhere; 2 sizes suffice
        x = jnp.asarray(rng.standard_normal((B, H, D)).astype(np.float32))
        w1 = jnp.asarray(rng.standard_normal((D, hidden)).astype(np.float32) * 0.05)
        b1 = jnp.zeros((hidden,), jnp.float32)
        w2 = jnp.asarray(rng.standard_normal((hidden,)).astype(np.float32) * 0.05)

        def dense_pool(x, w1, b1, w2, mask):
            e = jnp.tanh(jnp.einsum("nld,dh->nlh", x, w1) + b1)
            logits = jnp.einsum("nlh,h->nl", e, w2) + jnp.where(mask > 0, 0.0, -1e9)
            alpha = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("nl,nld->nd", alpha, x)

        pallas_pool = jax.jit(lambda x, m: additive_pool(x, w1, b1, w2, m))
        xla_pool = jax.jit(lambda x, m: dense_pool(x, w1, b1, w2, m))
        rows.append(("additive_pool fwd", H,
                     try_time(f"xla/pool_fwd/{H}", xla_pool, x, mask),
                     try_time(f"pallas/pool_fwd/{H}", pallas_pool, x, mask), None))
        rows.append((
            "additive_pool fwd+bwd", H,
            try_time(f"xla/pool_bwd/{H}", jax.jit(lambda x, m: jax.grad(
                lambda x: dense_pool(x, w1, b1, w2, m).sum())(x)), x, mask),
            try_time(f"pallas/pool_bwd/{H}", jax.jit(lambda x, m: jax.grad(
                lambda x: additive_pool(x, w1, b1, w2, m).sum())(x)), x, mask),
            None,
        ))
        _stamp(partial=True)

    # ---- fused hot-path kernels (ISSUE 8): the WHOLE chain at training
    # scale, where isolated kernels provably lose to launch overhead (the
    # H=50 rows above are the evidence). xla_ms = the dense module chain,
    # pallas_ms = the fused kernel — one launch amortized across
    # gather+encode / qkv+attention+pool+score. bf16: the production chip
    # dtype (bf16 operands, f32 accumulation in the kernels).
    from fedrec_tpu.ops.fused_hot_path import (
        fused_gather_encode, fused_history_score,
    )

    H50, C, T, Dh, Ah = 50, 5, 50, 768, 384
    for Bf in (256, 1024):
        rng = np.random.default_rng(1)
        dt = jnp.bfloat16
        x = jnp.asarray(rng.standard_normal((Bf, H50, D)), dt)
        cand = jnp.asarray(rng.standard_normal((Bf, C, D)), dt)
        ap = {
            k: {"kernel": jnp.asarray(
                    rng.standard_normal((D, D)) * 0.05, jnp.float32),
                "bias": jnp.zeros((D,), jnp.float32)}
            for k in ("w_q", "w_k", "w_v")
        }
        pp = {
            "att_fc1": {"kernel": jnp.asarray(
                            rng.standard_normal((D, hidden)) * 0.05,
                            jnp.float32),
                        "bias": jnp.zeros((hidden,), jnp.float32)},
            "att_fc2": {"kernel": jnp.asarray(
                            rng.standard_normal((hidden, 1)) * 0.05,
                            jnp.float32),
                        "bias": jnp.zeros((1,), jnp.float32)},
        }

        def dense_chain(x, cand):
            q, k, v = (
                (x @ ap[n]["kernel"].astype(dt) + ap[n]["bias"].astype(dt))
                .reshape(Bf, H50, heads, dk)
                for n in ("w_q", "w_k", "w_v")
            )
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.asarray(dk, dt)
            )
            s = s - jnp.max(s, axis=-1, keepdims=True)
            a = jnp.exp(s)
            a = a / (jnp.sum(a, axis=-1, keepdims=True) + 1e-8)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(Bf, H50, D)
            e = jnp.tanh(
                ctx @ pp["att_fc1"]["kernel"].astype(dt)
                + pp["att_fc1"]["bias"].astype(dt)
            )
            lg = (e @ pp["att_fc2"]["kernel"].astype(dt))[..., 0]
            lg = lg - jnp.max(lg, axis=-1, keepdims=True)
            al = jnp.exp(lg)
            al = al / (jnp.sum(al, axis=-1, keepdims=True) + 1e-8)
            user = jnp.einsum("bh,bhd->bd", al, ctx)
            return jnp.einsum("bcd,bd->bc", cand, user)

        fused_chain = lambda x, cand: fused_history_score(  # noqa: E731
            x, cand, None, ap, pp, heads
        )[0]
        rows.append((
            f"hist attn+pool+score fwd (B={Bf})", H50,
            try_time(f"xla/fused_fwd/{Bf}", jax.jit(dense_chain), x, cand),
            try_time(f"pallas/fused_fwd/{Bf}", jax.jit(fused_chain), x, cand),
            None, "bfloat16",
        ))

        def g_of_chain(fn):
            return jax.jit(lambda x, c: jax.grad(
                lambda x: fn(x, c).astype(jnp.float32).sum()
            )(x))

        rows.append((
            f"hist attn+pool+score fwd+bwd (B={Bf})", H50,
            try_time(f"xla/fused_bwd/{Bf}", g_of_chain(dense_chain), x, cand),
            try_time(f"pallas/fused_bwd/{Bf}", g_of_chain(fused_chain), x, cand),
            None, "bfloat16",
        ))
        _stamp(partial=True)

    # gather+encode at the flagship unique-cap scale (one leg: U is the
    # lever, not B)
    rngU = np.random.default_rng(2)
    U = 2560
    dtg = jnp.bfloat16
    table = jnp.asarray(rngU.standard_normal((4096, T, Dh)), dtg)
    uniq = jnp.asarray(rngU.permutation(4096)[:U].astype(np.int32))
    np_ = {
        "pool": {
            "att_fc1": {"kernel": jnp.asarray(
                            rngU.standard_normal((Dh, Ah)) * 0.05,
                            jnp.float32),
                        "bias": jnp.zeros((Ah,), jnp.float32)},
            "att_fc2": {"kernel": jnp.asarray(
                            rngU.standard_normal((Ah, 1)) * 0.05,
                            jnp.float32),
                        "bias": jnp.zeros((1,), jnp.float32)},
        },
        "fc": {"kernel": jnp.asarray(
                   rngU.standard_normal((Dh, D)) * 0.05, jnp.float32),
               "bias": jnp.zeros((D,), jnp.float32)},
    }

    def dense_gather_encode(uniq_ids, tbl):
        states = tbl[uniq_ids]
        p1 = np_["pool"]["att_fc1"]
        e = jnp.tanh(
            jnp.einsum("utd,dh->uth", states, p1["kernel"].astype(dtg))
            + p1["bias"].astype(dtg)
        )
        lg = jnp.einsum(
            "uth,h->ut", e, np_["pool"]["att_fc2"]["kernel"][:, 0].astype(dtg)
        )
        lg = lg - jnp.max(lg, axis=-1, keepdims=True)
        a = jnp.exp(lg)
        a = a / (jnp.sum(a, axis=-1, keepdims=True) + 1e-8)
        pooled = jnp.einsum("ut,utd->ud", a, states)
        return pooled @ np_["fc"]["kernel"].astype(dtg) + np_["fc"][
            "bias"].astype(dtg)

    rows.append((
        f"gather+encode fwd (U={U})", T,
        try_time(
            "xla/gather_fwd",
            jax.jit(lambda u: dense_gather_encode(u, table)), uniq,
        ),
        try_time(
            "pallas/gather_fwd",
            jax.jit(lambda u: fused_gather_encode(table, u, np_)), uniq,
        ),
        None, "bfloat16",
    ))
    _stamp(partial=True)

    def fmt(t):
        return f"{t*1e3:.3f}" if t is not None else "OOM/–"

    print(f"\n## attention impls on {platform} "
          f"({getattr(jax.devices()[0], 'device_kind', '?')}), B={B}\n")
    print("| op | H | xla dense ms | pallas ms | chunked ms |")
    print("|---|---|---|---|---|")
    for name, H, t_x, t_p, t_c, *_rest in rows:
        print(f"| {name} | {H} | {fmt(t_x)} | {fmt(t_p)} | {fmt(t_c)} |")

    _stamp(partial=False)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
