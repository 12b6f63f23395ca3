"""Per-round communication cost vs the reference's 268 MB state_dict ships.

The reference transfers the FULL model state_dict — frozen DistilBERT trunk
included — from every client every round over raw TCP (~268 MB/client/round,
Final_Report.pdf §VII.b; the weight fan-out broadcasts the same bytes back,
reference ``server.py:76-77``/``client.py:191-210``). This framework never
moves the frozen trunk: only the two trainable towers cross the wire, as XLA
collectives over ICI/DCN.

Two measurements, both from REAL buffers (no dtype arithmetic):

1. **Flagship payload bytes** — the actual flagship param trees, per
   strategy and per update codec (``fed.dcn_compress``): each codec row
   encodes the real trainable trees through :mod:`fedrec_tpu.comms` and
   reports the encoded buffer sizes ``process_allgather`` would ship,
   with the client->server reduction vs dense f32. The benchmark FAILS
   if the codec contract (>=4x int8, >=20x sign1bit/topk) doesn't hold
   on the measured buffers.
2. **Bytes-per-round x time-to-AUC tradeoff** — one short CPU training
   run per codec on the topic-structured synthetic corpus (recoverable
   ranking signal, known AUC ceiling): per-codec measured uplink bytes
   per client-round (read back from the ``fed.dcn_bytes_up_total``
   registry counter the Trainer banks from a real wire-codec encode),
   wall seconds and rounds to the target AUC, and the final AUC. Skipped
   with ``--no-train`` (byte table only).

Writes ``benchmarks/comm_cost.json`` (provenance-stamped) and prints one
JSON line. CPU-exact — no TPU needed.

    python benchmarks/comm_cost.py            # or: make comm-cost
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# Final_Report.pdf §VII.b: ~268 MB client->server state_dict upload per
# round; the server broadcast fans the same bytes back (server.py:76-77),
# so a full round moves ~2x that per client. All figures below count BOTH
# directions on both sides, so the reduction factors compare like with like.
REFERENCE_UP_MB = 268.0
REFERENCE_ROUND_MB = 2 * REFERENCE_UP_MB

MB = 1024 * 1024

# codec contract on the measured client->server buffers (ISSUE 7/17
# acceptance): the benchmark fails rather than bank a violating artifact.
# int8's exact measured ratio is 4n/(n+4t) for t tensors of n total
# elements — asymptotically 4x, a hair under on real trees because each
# tensor ships one f32 scale; the threshold tolerates exactly that
# overhead (0.5% on the flagship trees) and nothing else. The linear
# sketches ship ~width x dense f32 (one f32 bucket array per leaf), so
# the default width 0.1 prices ~10x; the contract floor is 8x to absorb
# the small-leaf rounding (m = max(1, round(width * n)) per leaf).
MIN_REDUCTION = {
    "int8": 3.98, "sign1bit": 20.0, "topk": 20.0,
    "countsketch": 8.0, "randproj": 8.0,
}


def tree_bytes(tree) -> int:
    import jax

    return int(
        sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))
    )


def codec_rows(trainable_tree, topk_ratio: float, sketch_width: float) -> dict:
    """Encode the REAL flagship trainable trees through every registered
    codec; report measured wire-buffer bytes and the up-direction
    reduction vs dense f32. Raises if the codec contract is violated."""
    from fedrec_tpu.comms import CODECS, encode_tree, tree_dense_nbytes

    dense = tree_dense_nbytes(trainable_tree)
    rows = {}
    for codec in CODECS:
        if codec == "none":
            up = dense
        else:
            up = encode_tree(
                trainable_tree, codec, topk_ratio, sketch_width=sketch_width
            ).nbytes()
        reduction = dense / up
        rows[codec] = {
            "up_mb_per_client": round(up / MB, 4),
            "down_mb_per_client": round(dense / MB, 4),  # fan-out stays f32
            "round_mb_per_client": round((up + dense) / MB, 4),
            "reduction_up_vs_dense": round(reduction, 1),
        }
        want = MIN_REDUCTION.get(codec, 1.0)
        if reduction < want:
            raise SystemExit(
                f"codec contract violated: {codec} measured "
                f"{reduction:.1f}x client->server reduction on the real "
                f"encoded buffers (< {want}x)"
            )
    return rows


def run_codec_tradeoff(
    codecs, rounds: int, target_auc: float, topk_ratio: float,
    sketch_width: float,
) -> dict:
    """One short CPU training run per codec on the topic-structured
    synthetic corpus: measured uplink bytes per client-round (from the
    registry counter the Trainer banks off a real wire-codec encode) x
    measured time/rounds to the target AUC."""
    import jax  # noqa: F401 — backend initialized before Trainer imports

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import make_synthetic_mind_topics
    from fedrec_tpu.obs import MetricsRegistry, set_registry
    from fedrec_tpu.obs.report import load_jsonl
    from fedrec_tpu.train.trainer import Trainer

    num_news, title_len, bert_hidden = 200, 12, 48
    data, token_states = make_synthetic_mind_topics(
        num_news=num_news, num_train=2048, num_valid=256,
        title_len=title_len, bert_hidden=bert_hidden, num_topics=8,
        his_len_range=(4, 10), neg_pool_range=(4, 10), seed=0,
    )
    out: dict = {}
    for codec in codecs:
        cfg = ExperimentConfig()
        cfg.model.news_dim = 32
        cfg.model.num_heads = 4
        cfg.model.head_dim = 8
        cfg.model.query_dim = 16
        cfg.model.bert_hidden = bert_hidden
        cfg.data.max_his_len = 10
        cfg.data.max_title_len = title_len
        cfg.data.batch_size = 32
        cfg.fed.num_clients = 4
        cfg.fed.rounds = rounds
        cfg.fed.strategy = "param_avg"
        cfg.fed.dcn_compress = codec
        cfg.fed.dcn_topk_ratio = topk_ratio
        cfg.fed.dcn_sketch_width = sketch_width
        cfg.optim.user_lr = cfg.optim.news_lr = 5e-3
        cfg.train.seed = 0
        cfg.train.snapshot_dir = ""
        cfg.train.eval_every = 1
        cfg.train.eval_protocol = "full"

        # fresh registry per run: the byte counters must attribute to
        # THIS codec's run only
        old_reg = set_registry(MetricsRegistry())
        try:
            with tempfile.TemporaryDirectory() as tmp:
                cfg.obs.dir = tmp
                trainer = Trainer(cfg, data, token_states)
                t0 = time.perf_counter()
                history = trainer.run()
                wall_s = time.perf_counter() - t0
                records, _ = load_jsonl(Path(tmp) / "metrics.jsonl")
            from fedrec_tpu.obs import get_registry

            reg = get_registry()
            up_counter = reg.get("fed.dcn_bytes_up_total")
            up_total = (
                up_counter.value(path="cohort") if up_counter is not None else 0.0
            )
            if codec == "none":
                # the none codec ships dense f32 — the real buffer size of
                # the trainable trees (the Trainer doesn't count an
                # uncompressed uplink; price it from the same trees)
                from fedrec_tpu.comms import tree_dense_nbytes

                host = jax.tree_util.tree_map(
                    np.asarray, trainer._client0_params()
                )
                up_per_client_round = tree_dense_nbytes(host)
            else:
                up_per_client_round = up_total / (rounds * cfg.fed.num_clients)
        finally:
            set_registry(old_reg)

        # unified key scheme (val_auc); legacy valid_auc kept readable so
        # the helper also digests pre-rename event logs
        aucs = [
            (int(r["round"]), float(r.get("val_auc", r.get("valid_auc"))))
            for r in sorted(records, key=lambda r: r.get("round", 0))
            if ("val_auc" in r or "valid_auc" in r) and "round" in r
        ]
        elapsed = {
            int(r["round"]): float(r["elapsed_sec"])
            for r in records
            if "round" in r and "elapsed_sec" in r
        }
        hit = next((r for r, a in aucs if a >= target_auc), None)
        row = {
            "up_mb_per_client_round": round(up_per_client_round / MB, 4),
            "final_auc": round(aucs[-1][1], 4) if aucs else None,
            "rounds_run": len(history),
            "wall_s_total": round(wall_s, 2),
            "target_auc": target_auc,
            "rounds_to_target": None if hit is None else hit + 1,
            "time_to_auc_s": (
                None if hit is None or hit not in elapsed
                else round(elapsed[hit], 2)
            ),
        }
        out[codec] = row
        print(f"[comm_cost] {codec}: {json.dumps(row)}", file=sys.stderr)
    return out


def main() -> int:
    import os
    import subprocess

    from fedrec_tpu.hostenv import cpu_host_env

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--no-train", action="store_true",
                    help="skip the per-codec time-to-AUC training runs "
                         "(byte table only)")
    ap.add_argument("--rounds", type=int, default=8,
                    help="rounds per codec tradeoff run")
    ap.add_argument("--target-auc", type=float, default=0.55,
                    help="time-to-AUC threshold on the synthetic corpus")
    ap.add_argument("--topk-ratio", type=float, default=0.01)
    ap.add_argument("--sketch-width", type=float, default=0.1,
                    help="linear-sketch size ratio (fed.dcn_sketch_width)")
    args = ap.parse_args()

    # a host-side measurement: it must not take the chip. This process has
    # not imported jax yet, so the child is the only one that will.
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        env = cpu_host_env()
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + sys.argv[1:], env=env
        ).returncode

    import jax

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.train.state import init_client_state

    cfg = ExperimentConfig()  # flagship: 400-d towers over a 768-d trunk
    model = NewsRecommender(cfg.model)
    state = init_client_state(
        model, cfg, jax.random.PRNGKey(0), num_news=64,
        title_len=cfg.data.max_title_len,
    )
    user_b = tree_bytes(state.user_params)
    news_b = tree_bytes(state.news_params)
    trainable = user_b + news_b
    host_trees = jax.tree_util.tree_map(
        np.asarray, (state.user_params, state.news_params)
    )
    codecs = codec_rows(host_trees, args.topk_ratio, args.sketch_width)

    # the obs.wire trace-context envelope rides every async push request
    # (ISSUE 18): measure its cost on a representative push frame and
    # fail rather than bank an artifact where telemetry framing is a
    # material fraction of the payload it accounts
    from fedrec_tpu.obs.wire import envelope_overhead_bytes

    push_req = {"cmd": "push", "worker": "0", "round": 0, "based_on": 0}
    env_overhead = envelope_overhead_bytes(push_req)
    env_pct = 100.0 * env_overhead / trainable
    if env_pct >= 2.0:
        raise SystemExit(
            f"wire envelope overhead {env_overhead} B is {env_pct:.2f}% of "
            f"the dense push payload ({trainable} B) — contract is < 2%"
        )

    # steps per round at the reference's federated deployment scale:
    # MIND-small ~ 230k train impressions over 9 clients, batch 64
    steps = int(np.ceil(230_000 / 9 / cfg.data.batch_size))

    out = {
        "metric": "comm_bytes_per_client_per_round",
        "unit": "MB (both directions)",
        "trainable_params_mb": round(trainable / MB, 3),
        "user_tower_mb": round(user_b / MB, 3),
        "text_head_mb": round(news_b / MB, 3),
        "reference_up_mb": REFERENCE_UP_MB,
        "reference_round_mb": REFERENCE_ROUND_MB,
        "strategies": {
            # FedAvg: one param payload per round (each direction)
            "param_avg": round(2 * trainable / MB, 3),
            # hub-and-spoke: server fan-out + client fan-in, params once each
            "coordinator": round(2 * trainable / MB, 3),
            # DDP parity: one grad payload every step
            "grad_avg": round(steps * trainable / MB, 3),
        },
        # per-codec MEASURED wire buffers of the flagship trainable trees
        # (fed.dcn_compress; fan-out full precision in every mode)
        "codecs": codecs,
        "codec_topk_ratio": args.topk_ratio,
        "codec_sketch_width": args.sketch_width,
        # measured obs.wire envelope framing cost per request vs the
        # dense push payload (contract: < 2%, enforced above)
        "wire_envelope_overhead_bytes": env_overhead,
        "wire_envelope_overhead_pct_of_dense_push": round(env_pct, 6),
        "grad_avg_steps_per_round": steps,
        # both-direction / both-direction — like for like
        "reduction_vs_reference": {
            "param_avg": round(REFERENCE_ROUND_MB / (2 * trainable / MB), 1),
            "coordinator": round(REFERENCE_ROUND_MB / (2 * trainable / MB), 1),
            **{
                f"coordinator_{c}": round(
                    REFERENCE_ROUND_MB / codecs[c]["round_mb_per_client"], 1
                )
                for c in codecs
                if c != "none"
            },
        },
        "note": (
            "payload bytes of the actual flagship param trees, both "
            "directions on both sides; codec rows are measured encoded "
            "buffer sizes (fedrec_tpu.comms), not dtype arithmetic. The "
            "frozen DistilBERT trunk (the bulk of the reference's 268 MB "
            "per direction) never crosses the wire here. grad_avg trades "
            "round payload for per-step sync, riding ICI instead of EC2 "
            "TCP."
        ),
    }
    if not args.no_train:
        from fedrec_tpu.comms import CODECS

        out["codec_tradeoff"] = run_codec_tradeoff(
            CODECS, args.rounds, args.target_auc, args.topk_ratio,
            args.sketch_width,
        )
        out["codec_tradeoff_note"] = (
            "one short CPU run per codec on the topic-structured synthetic "
            "corpus (2048 impressions, 4 clients, full-pool eval every "
            "round): uplink MB per client-round read back from the "
            "fed.dcn_bytes_up_total registry counter (banked from a real "
            "wire-codec encode), wall seconds to the first round whose "
            "full-pool AUC reaches target_auc"
        )
    from fedrec_tpu.utils.provenance import provenance

    out["provenance"] = provenance()
    (HERE / "comm_cost.json").write_text(json.dumps(out, indent=2))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
