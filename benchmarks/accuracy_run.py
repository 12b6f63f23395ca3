"""Accuracy-loop experiment: train to convergence, report a learning curve.

VERDICT round-1 item 4: nothing in the repo had ever trained toward a real
ranking number. Real MIND needs the raw tsv download (zero egress here —
the preprocessing pipeline for it exists in ``fedrec_tpu/data/preprocess.py``),
so this trains on the largest corpus obtainable offline: the topic-structured
synthetic generator (``make_synthetic_mind_topics``) whose Bayes-optimal
full-pool AUC is known by construction (~0.90 at defaults) and empirically
bounded by an oracle scorer. Metrics use the deterministic full-pool protocol
(the one behind the reference's published table, reference
``evaluation_functions.py:33-47``; published numbers ``README.md:70-80``).

Legs (``--all`` runs each as a subprocess with its own platform env):

  * ``central``  — flagship single-chip run at reference scale (768-d trunk
    states, 50-token titles, 50k impressions) on the device JAX gives it
    (``FEDREC_ACC_CPU=1`` picks the CPU-scaled corpus).
  * ``fed``      — 8-client federation on a fake CPU mesh (small corpus):
    local vs param_avg vs grad_avg vs param_avg+DP(eps=10), plus a
    32-client cohort run (4 clients per device) — shows federation/DP
    cost on accuracy. Direct ``--leg fed/adressa/finetune`` invocations
    self-re-exec onto the 8-device CPU mesh; set ``FEDREC_ACC_INNER=1``
    to keep your own environment (e.g. a live multi-device accelerator).
  * ``adressa``  — second dataset family (reference published Adressa AUC
    72.04, ``README.md:76-80``): synthetic event LOG with a lexical topic
    signal, run through the real Adressa pipeline (parse -> tokenize ->
    chronological split) + frozen-random-trunk token states.
  * ``finetune`` — BASELINE config 5: the FULL text trunk trains in-loop
    from raw tokens (no cached states) on the lexical Adressa corpus.
  * ``report``   — collect ``benchmarks/accuracy_*.json`` into RESULTS.md.

Usage:  python benchmarks/accuracy_run.py --all
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if str(REPO) not in sys.path:  # runnable as `python benchmarks/accuracy_run.py`
    sys.path.insert(0, str(REPO))


def _prov() -> dict:
    from fedrec_tpu.utils.provenance import provenance

    return provenance()


# --------------------------------------------------------------------- data
def _central_corpus():
    from fedrec_tpu.data import make_synthetic_mind_topics

    if os.environ.get("FEDREC_ACC_SMOKE"):  # fast correctness pass of the glue
        return make_synthetic_mind_topics(
            num_news=256, num_train=400, num_valid=100, title_len=8,
            bert_hidden=768, his_len_range=(3, 10), seed=7,
        )
    if os.environ.get("FEDREC_ACC_CPU"):
        # CPU-feasible scale, chosen explicitly; the report records the
        # actual dims used
        return make_synthetic_mind_topics(
            num_news=2048, num_train=12_000, num_valid=2_000, title_len=16,
            bert_hidden=192, his_len_range=(5, 30), seed=7,
        )
    return make_synthetic_mind_topics(
        num_news=4096,
        num_train=50_000,
        num_valid=5_000,
        title_len=50,
        bert_hidden=768,
        seed=7,
    )


def _small_corpus():
    from fedrec_tpu.data import make_synthetic_mind_topics

    return make_synthetic_mind_topics(
        num_news=1024,
        num_train=8_000,
        num_valid=2_000,
        title_len=12,
        bert_hidden=96,
        his_len_range=(5, 20),
        seed=11,
    )


def oracle_auc(data, states) -> float:
    """Full-pool AUC of a cheating reference scorer: cosine(candidate
    centroid, mean history centroid) on the raw trunk states. A strong
    baseline the model should approach; a LEARNED pooling can legitimately
    exceed it (uniform token averaging is not optimal)."""
    cent = np.asarray(states, np.float32).mean(axis=1)
    cent /= np.linalg.norm(cent, axis=1, keepdims=True) + 1e-9
    n2i = data.nid2index
    aucs = []
    for _, pos, negs, his, _ in data.valid_samples:
        hv = cent[[n2i[h] for h in his]].mean(0)
        s_pos = float(hv @ cent[n2i[pos]])
        s_neg = cent[[n2i[x] for x in negs]] @ hv
        aucs.append(
            (np.sum(s_pos > s_neg) + 0.5 * np.sum(s_pos == s_neg)) / len(s_neg)
        )
    return float(np.mean(aucs))


def _adressa_corpus(num_users: int, num_news: int, event_seed: int, prep_seed: int):
    """Synthetic Adressa event log -> artifacts through the REAL adapter
    (shared by the adressa and finetune legs)."""
    import tempfile

    from fedrec_tpu.data import make_synthetic_adressa_events, preprocess_adressa

    events = make_synthetic_adressa_events(
        num_users=num_users, num_news=num_news, seed=event_seed
    )
    with tempfile.TemporaryDirectory() as tmpdir:
        tmp = Path(tmpdir) / "events.jsonl"
        with open(tmp, "w") as fh:
            for ev in events:
                fh.write(json.dumps(ev) + "\n")
        data = preprocess_adressa(
            [tmp], out_dir=None, max_title_len=12, neg_pool_size=20,
            valid_frac=0.15, seed=prep_seed,
        )
    return events, data


# --------------------------------------------------------------------- legs
def _train(cfg, data, states, on_round=None):
    """Round loop with an optional per-round callback, so callers persist
    partial curves instead of losing a long run killed at round N-1."""
    from fedrec_tpu.train.trainer import Trainer

    t0 = time.time()
    trainer = Trainer(cfg, data, states, snapshot_dir=None)
    out = {"wall_s": 0.0, "curve": []}
    for round_idx in range(cfg.fed.rounds):
        r = trainer.train_round(round_idx)
        out["curve"].append(
            {
                "round": r.round_idx,
                "train_loss": round(r.train_loss, 5),
                **{k: round(v, 5) for k, v in r.val_metrics.items()},
            }
        )
        out["wall_s"] = round(time.time() - t0, 1)
        print(json.dumps(out["curve"][-1]), flush=True)
        if on_round is not None:
            on_round(out)
    trainer.logger.finish()
    return out


def leg_central(rounds: int) -> None:
    import jax

    from fedrec_tpu.config import ExperimentConfig

    platform = jax.devices()[0].platform
    data, states = _central_corpus()
    hidden = states.shape[-1]

    cfg = ExperimentConfig()
    cfg.model.text_encoder_mode = "head"
    cfg.model.bert_hidden = hidden
    if hidden < 768:  # CPU-scale corpus -> proportionally scaled model
        cfg.model.news_dim = 128
        cfg.model.num_heads = 16
        cfg.model.head_dim = 8
        cfg.model.query_dim = 64
    cfg.data.max_title_len = data.title_len
    if platform != "cpu":
        cfg.model.dtype = "bfloat16"
    cfg.fed.strategy = "local"
    cfg.fed.num_clients = 1
    cfg.fed.rounds = rounds
    # the reference's lr 5e-5 assumes ~8 h of training; this demo runs a
    # bounded number of rounds, so use a proportionally larger Adam lr
    # (recorded in the output JSON — an accuracy-loop choice, not parity)
    cfg.optim.user_lr = cfg.optim.news_lr = 5e-4
    cfg.train.eval_protocol = "full"
    cfg.train.eval_every = 1
    cfg.train.snapshot_dir = ""
    cfg.train.resume = False

    out = {
        "leg": "central",
        "platform": platform,
        "device": getattr(jax.devices()[0], "device_kind", platform),
        "corpus": {
            "num_news": data.num_news,
            "train": len(data.train_samples),
            "valid": len(data.valid_samples),
            "bert_hidden": hidden,
        },
        "oracle_auc": round(oracle_auc(data, states), 4),
        "rounds_requested": rounds,
        "config": {"mode": "head", "dtype": cfg.model.dtype,
                   "lr": cfg.optim.user_lr, "batch": cfg.data.batch_size},
    }

    out["provenance"] = _prov()

    def persist(partial):
        (HERE / "accuracy_central.json").write_text(
            json.dumps({**out, **partial}, indent=2)
        )

    result = _train(cfg, data, states, on_round=persist)
    persist(result)
    print(json.dumps({"leg": "central", "platform": platform,
                      "oracle_auc": out["oracle_auc"],
                      "wall_s": result["wall_s"]}))


def leg_bf16(rounds: int) -> None:
    """Dtype-tolerance leg (VERDICT r2 item 9): the SAME corpus and config
    trained twice — float32 vs bfloat16 (params/opt stay f32; compute and
    the token-state table take the dtype, exactly like the TPU bench) —
    asserting the final full-pool AUC agrees within a stated tolerance.
    The TPU bench advertises bfloat16; this leg is the accuracy proof for
    that dtype. CPU runs use the small corpus (XLA:CPU bf16 is slow)."""
    import jax

    from fedrec_tpu.config import ExperimentConfig

    platform = jax.devices()[0].platform
    if os.environ.get("FEDREC_ACC_SMOKE"):
        from fedrec_tpu.data import make_synthetic_mind_topics

        data, states = make_synthetic_mind_topics(
            num_news=256, num_train=400, num_valid=100, title_len=8,
            bert_hidden=96, his_len_range=(3, 10), seed=7,
        )
    elif platform == "cpu":
        data, states = _small_corpus()
    else:
        data, states = _central_corpus()
    hidden = states.shape[-1]

    def cfg_for(dtype: str) -> ExperimentConfig:
        cfg = ExperimentConfig()
        cfg.model.text_encoder_mode = "head"
        cfg.model.bert_hidden = hidden
        if hidden < 768:  # CPU-scale corpus -> proportionally scaled model
            cfg.model.news_dim = 128
            cfg.model.num_heads = 16
            cfg.model.head_dim = 8
            cfg.model.query_dim = 64
        cfg.data.max_title_len = data.title_len
        cfg.model.dtype = dtype
        cfg.fed.strategy = "local"
        cfg.fed.num_clients = 1
        cfg.fed.rounds = rounds
        cfg.optim.user_lr = cfg.optim.news_lr = 5e-4
        cfg.train.eval_protocol = "full"
        cfg.train.eval_every = 1
        cfg.train.snapshot_dir = ""
        cfg.train.resume = False
        return cfg

    tolerance = 0.02
    out = {
        "leg": "bf16",
        "platform": platform,
        "device": getattr(jax.devices()[0], "device_kind", platform),
        "corpus": {
            "num_news": data.num_news,
            "train": len(data.train_samples),
            "valid": len(data.valid_samples),
            "bert_hidden": hidden,
        },
        "oracle_auc": round(oracle_auc(data, states), 4),
        "rounds_requested": rounds,
        "tolerance_auc": tolerance,
        "runs": {},
    }
    out["provenance"] = _prov()

    from fedrec_tpu.utils.provenance import write_artifact

    def persist(final: bool = False) -> None:
        # incremental, but write_artifact stages non-final stamps in an
        # .inprogress sidecar until BOTH dtypes finished and the tolerance
        # verdict is in — a half-trained comparison must not read as the
        # dtype-safety proof, and a killed re-run must not clobber
        # previously banked complete evidence
        write_artifact(HERE / "accuracy_bf16.json", out, not final)

    for dtype in ("float32", "bfloat16"):
        print(f"[bf16-leg] training dtype={dtype}", flush=True)
        res = _train(cfg_for(dtype), data, states, on_round=lambda p: persist())
        out["runs"][dtype] = res
        persist()

    f32_auc = out["runs"]["float32"]["curve"][-1]["auc"]
    bf16_auc = out["runs"]["bfloat16"]["curve"][-1]["auc"]
    out["final_auc"] = {"float32": f32_auc, "bfloat16": bf16_auc}
    out["auc_delta"] = round(abs(f32_auc - bf16_auc), 5)
    out["within_tolerance"] = out["auc_delta"] <= tolerance
    persist(final=True)
    print(json.dumps({"leg": "bf16", "auc_f32": f32_auc, "auc_bf16": bf16_auc,
                      "delta": out["auc_delta"],
                      "within_tolerance": out["within_tolerance"]}))
    if not out["within_tolerance"]:
        raise SystemExit(
            f"bf16 final AUC diverged from f32 by {out['auc_delta']} "
            f"(> {tolerance}) — the bench dtype is not accuracy-safe"
        )


def _small_corpus_base_cfg():
    """The tuned harness recipe shared by the fed and dp legs: the
    `_small_corpus` model geometry + the full-pool eval tail. ONE
    definition, so the dp leg's anchor can never silently drift from the
    fed leg's operating point (they are compared against each other in
    the report)."""
    from fedrec_tpu.config import ExperimentConfig

    cfg = ExperimentConfig()
    cfg.model.news_dim = 64
    cfg.model.num_heads = 8
    cfg.model.head_dim = 8
    cfg.model.query_dim = 32
    cfg.model.bert_hidden = 96
    cfg.data.max_title_len = 12
    cfg.data.max_his_len = 20
    cfg.train.eval_protocol = "full"
    cfg.train.eval_every = 1
    cfg.train.snapshot_dir = ""
    cfg.train.resume = False
    return cfg


# Row spec: name -> (strategy[+server_opt], clients, text_encoder_mode[+tower]).
# DP rows live in the dedicated dp leg (leg_dp -> accuracy_dp.json): the r3
# rows here trained the DP estimator with the non-DP hyperparameters and were
# noise-crushed to ~random (VERDICT r3 #4).
FED_ROWS = {
    "local_1client": ("local", 1, "head"),
    # the reference's actual epoch structure: user tower trains on a
    # precomputed news-vec table, text head updates from accumulated
    # embedding grads at epoch end (reference model.py:66-90)
    "decoupled_1client": ("local", 1, "table"),
    "param_avg_8": ("param_avg", 8, "head"),
    # FedAvgM (server momentum over round deltas, Reddi et al. 2021) —
    # beyond-parity: the reference only has the plain mean
    "param_avg_8_fedavgm": ("param_avg+fedavgm", 8, "head"),
    "grad_avg_8": ("grad_avg", 8, "head"),
    # BASELINE north-star client count via cohorts (32 clients on the
    # 8-device rig -> 4 per device; packing-independent semantics
    # pinned by tests/test_cohorts.py)
    "param_avg_32_cohort": ("param_avg", 32, "head"),
    # second model family: recurrent (LSTUR-style) user tower
    "gru_tower_8": ("param_avg", 8, "head+gru"),
    # third model family: CNN text head (NAML-style, Wu et al. 2019).
    # Shared lr 1e-2 is also its own sweep optimum (5e-3 -> 0.759,
    # 2e-2 diverges); it trails the additive head (~0.77 vs 0.80) on this
    # corpus BY CONSTRUCTION — the synthetic token states carry no
    # token-order signal for the conv window to read
    "cnn_head_8": ("param_avg", 8, "head+cnn"),
}


def fed_row_cfg(name: str, rounds: int):
    """Pure per-row config construction for the fed leg.

    Extracted so routing regressions are caught by asserting on the
    RETURNED config values (tests/test_accuracy_harness.py) instead of
    grepping leg_fed's source — a reordered assignment that keeps the
    literal strings must still fail the tests.
    """
    strategy, clients, mode = FED_ROWS[name]
    cfg = _small_corpus_base_cfg()
    if strategy.endswith("+fedavgm"):
        strategy = strategy.split("+")[0]
        cfg.fed.server_opt = "sgd"
        cfg.fed.server_lr = 1.0
        # momentum 0.5 at the SHARED local lr: the best point of the r5
        # (server_lr x momentum x local lr) sweep — 0.797 vs 0.800 plain.
        # m=0.9 needs crippled locals (5e-4 -> 0.721) or a shrunk server
        # step (s0.3 -> 0.755); FedAdam peaks at 0.768; nothing BEATS the
        # plain mean on this corpus, so PARITY.md marks the feature
        # "available, not recommended at this scale" (VERDICT r4 #4)
        cfg.fed.server_momentum = 0.5
    if mode.endswith("+gru"):
        mode = mode.split("+")[0]
        cfg.model.user_tower = "gru"
    if mode.endswith("+cnn"):
        mode = mode.split("+")[0]
        cfg.model.text_head_arch = "cnn"
    cfg.model.text_encoder_mode = mode
    cfg.fed.strategy = strategy
    cfg.fed.num_clients = clients
    cfg.fed.rounds = rounds
    # lr 1e-2: the r4 sweep optimum on this corpus (5e-4 -> 0.667,
    # 1e-2 -> 0.80 for the 8-client row); one shared lr keeps the
    # federation-mode comparison fair. One row runs at its own measured
    # operating point (noted in the report): local_1client takes 8x the
    # optimizer steps per round of the federated rows, and lr 1e-2
    # collapses it after round 2 (AUC 0.72 -> 0.50); its sweep optimum
    # is 2e-3. (The fedavgm row ran conservative 5e-4 locals through r4;
    # the r5 sweep found momentum 0.5 at the SHARED lr strictly better —
    # see the fedavgm block above.)
    cfg.optim.user_lr = cfg.optim.news_lr = 1e-2
    if name == "local_1client":
        cfg.optim.user_lr = cfg.optim.news_lr = 2e-3
    if clients == 32:
        # step equalization (VERDICT r3 #5): a 32-client split leaves
        # each client 1/4 the per-round local steps of the 8-client
        # rows (250 samples -> 3 steps/epoch vs 15); 4 local epochs
        # restores the update count, closing the gap to the 8-client
        # row from 0.17 to ~0.006 AUC on this corpus
        cfg.fed.local_epochs = 4
    return cfg


def leg_fed(rounds: int) -> None:
    import jax

    data, states = _small_corpus()
    runs = {}
    for name in FED_ROWS:
        cfg = fed_row_cfg(name, rounds)
        runs[name] = _train(cfg, data, states)
        print(f"[fed] {name}: final "
              f"{runs[name]['curve'][-1] if runs[name]['curve'] else '?'}")

    out = {
        "leg": "fed",
        "platform": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "corpus": {
            "num_news": data.num_news,
            "train": len(data.train_samples),
            "valid": len(data.valid_samples),
            "bert_hidden": 96,
        },
        "oracle_auc": round(oracle_auc(data, states), 4),
        "runs": runs,
    }
    out["provenance"] = _prov()
    (HERE / "accuracy_fed.json").write_text(json.dumps(out, indent=2))


# DP leg rows: eps=None is a non-private anchor; scope/batch default to the
# tuned recipe's ("all", 64). Finalized from the round-5 probe sweep
# (/tmp/dp_tune_r5.py pattern — see docs/DP.md for the measured outcomes).
DP_ROWS: dict[str, dict] = {
    "nodp_tuned": {"eps": None},
    "dp_eps50": {"eps": 50.0},
    "dp_eps10": {"eps": 10.0},
    "dp_eps3": {"eps": 3.0},
    # dp_scope='user' lever + its honest ceiling: non-private training with
    # the text head frozen — the scope's utility can never exceed this
    "nodp_user_frozen": {"eps": None, "scope": "user"},
    "dp_eps10_user": {"eps": 10.0, "scope": "user"},
    # batch lever: sigma*C/B per-step noise shrinks 2.5x at B=256, but the
    # accountant's sigma grows with q and the step count falls 4x — the
    # probe measured a net LOSS at every B tried (docs/DP.md section 4)
    "dp_eps10_b256": {"eps": 10.0, "batch": 256},
}


def dp_row_cfg(name: str, rounds: int, n_train: int):
    """Pure per-row config for the dp leg (same testable-construction
    pattern as :func:`fed_row_cfg`)."""
    from fedrec_tpu.privacy import calibrate_from_config

    spec = DP_ROWS[name]
    eps = spec.get("eps")
    cfg = _small_corpus_base_cfg()
    cfg.model.text_encoder_mode = "head"
    cfg.data.batch_size = spec.get("batch", 64)
    cfg.fed.strategy = "grad_avg"
    cfg.fed.num_clients = 8
    cfg.fed.rounds = rounds
    cfg.fed.local_epochs = 2
    cfg.optim.user_lr = cfg.optim.news_lr = 1e-2
    per_client = n_train // cfg.fed.num_clients
    steps_per_epoch = max(per_client // cfg.data.batch_size, 1)
    cfg.optim.lr_schedule = "cosine"
    cfg.optim.decay_steps = steps_per_epoch * rounds * cfg.fed.local_epochs
    scope = spec.get("scope", "all")
    if eps is not None:
        cfg.privacy.enabled = True
        cfg.privacy.epsilon = eps
        cfg.privacy.clip_norm = 1.0
        cfg.privacy.dp_scope = scope
        # budget the accountant for the steps this run actually takes
        cfg.privacy.accountant_epochs = rounds * cfg.fed.local_epochs
        cfg.privacy.sigma = calibrate_from_config(cfg, n_train)
    elif scope == "user":
        # frozen-head ceiling: the DP machinery with sigma ~ 0 and an
        # inactive clip IS the non-private user-only trainer
        # (tests/test_privacy.py pins the sigma->0 equivalence)
        cfg.privacy.enabled = True
        cfg.privacy.mechanism = "dpsgd"
        cfg.privacy.dp_scope = "user"
        cfg.privacy.clip_norm = 1e6
        cfg.privacy.sigma = 1e-12
    return cfg


def leg_dp(rounds: int) -> None:
    """Privacy-utility sweep with DP-TUNED hyperparameters (VERDICT r3 #4).

    The r3 DP rows trained the DP-SGD estimator with the non-DP recipe
    (Adam lr 5e-4, param_avg, C=2) and landed at ~random AUC. The failure
    mode was measured, not guessed (see docs/DP.md): per-step noise-vector
    norm ~20x the mean-gradient norm, and Adam's second moment normalizes
    by the NOISE scale, shrinking the per-parameter update to
    lr * (per-param SNR) — so at lr 5e-4 the model barely moves in the
    budgeted steps. The tuned recipe measured here:

      * ``grad_avg``: the per-step pmean over 8 clients averages 8
        INDEPENDENT noise draws — sqrt(8) noise reduction at the SAME
        local-DP guarantee (each client noises before the collective).
      * clip C=1.0 (just under the observed per-example norm median).
      * Adam lr 1e-2 (the empirical optimum of the lr sweep; 2e-2
        diverges), cosine-decayed over the full step budget — injected
        noise variance scales with lr^2, so the small late lr averages
        the noise out (worth +0.03 AUC at eps=50 over constant lr).
      * 32 rounds x 2 local epochs (DP gains from more steps under decay
        where the constant-lr run plateaus), accountant budgeting exactly
        the steps trained.

    Rows: non-private anchor at the SAME tuned recipe (the honest
    comparison bar — non-DP also improves under it) + eps in {50, 10, 3},
    plus the round-5 levers (VERDICT r4 #3): ``dp_scope='user'`` with its
    frozen-head non-private ceiling row, and large-batch rows (sigma*C/B
    noise-on-the-mean shrinks faster than the accountant's sigma grows
    with the sampling rate q). Writes ``accuracy_dp.json``.
    """
    import jax

    data, states = _small_corpus()
    runs = {}
    # FEDREC_DP_ROWS subset (the on-TPU proof runs only the tuned anchor +
    # eps=10 row; the full sweep is the CPU artifact's job).
    # Validated UP FRONT — a typo must fail before training, not after an
    # hour of chip window; the anchor row is required (every downstream
    # field is relative to it) and auto-included.
    row_filter = [
        r for r in os.environ.get("FEDREC_DP_ROWS", "").split(",") if r
    ]
    unknown = [r for r in row_filter if r not in DP_ROWS]
    if unknown:
        raise SystemExit(
            f"FEDREC_DP_ROWS names unknown rows {unknown}; known: "
            f"{sorted(DP_ROWS)}"
        )
    if row_filter and "nodp_tuned" not in row_filter:
        row_filter.insert(0, "nodp_tuned")
    rows = (
        {n: DP_ROWS[n] for n in row_filter} if row_filter else DP_ROWS
    )

    from fedrec_tpu.utils.provenance import write_artifact

    # only the FULL sweep on the cpu rig may update the canonical artifact
    # the report reads. A chip run (VERDICT r4 #7), or any run that carries
    # the row subset, goes to its own file.
    full_cpu = not row_filter and jax.devices()[0].platform == "cpu"
    name = "accuracy_dp.json" if full_cpu else "accuracy_dp_tpu.json"

    out = {
        "leg": "dp",
        "platform": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "corpus": {
            "num_news": data.num_news,
            "train": len(data.train_samples),
            "valid": len(data.valid_samples),
            "bert_hidden": 96,
        },
        "recipe": {
            "strategy": "grad_avg", "clients": 8, "clip_norm": 1.0,
            "lr": 1e-2, "lr_schedule": "cosine", "local_epochs": 2,
            "rounds": rounds, "delta": 1e-5,
        },
        "oracle_auc": round(oracle_auc(data, states), 4),
        "runs": runs,
    }

    def persist(partial: bool) -> None:
        # per-row incremental banking: a run killed mid-leg keeps the rows
        # already trained as labeled evidence. write_artifact stages
        # partial stamps in an .inprogress sidecar, so a killed RE-run can
        # never destroy previously banked complete evidence.
        out["provenance"] = _prov()
        write_artifact(HERE / name, out, partial)

    for row_name, spec in rows.items():
        cfg = dp_row_cfg(row_name, rounds, len(data.train_samples))
        runs[row_name] = _train(cfg, data, states)
        runs[row_name]["epsilon"] = spec.get("eps")
        runs[row_name]["sigma"] = (
            round(cfg.privacy.sigma, 4) if spec.get("eps") else 0.0
        )
        runs[row_name]["dp_scope"] = cfg.privacy.dp_scope
        runs[row_name]["batch_size"] = cfg.data.batch_size
        print(f"[dp] {row_name}: final "
              f"{runs[row_name]['curve'][-1] if runs[row_name]['curve'] else '?'}")
        persist(partial=True)

    anchor = runs["nodp_tuned"]["curve"][-1]["auc"]
    out["nodp_anchor_auc"] = anchor
    out["gap_to_anchor"] = {
        n: round(anchor - r["curve"][-1]["auc"], 4)
        for n, r in runs.items()
        if DP_ROWS[n].get("eps") is not None and r["curve"]
    }
    if "nodp_user_frozen" in runs and runs["nodp_user_frozen"]["curve"]:
        # the scope lever's hard ceiling, stated next to the rows it bounds
        out["user_frozen_ceiling_auc"] = (
            runs["nodp_user_frozen"]["curve"][-1]["auc"]
        )
    persist(partial=False)


def leg_adressa(rounds: int) -> None:
    """Second dataset family, end-to-end through the REAL adapter: synthetic
    JSON-lines event log -> ``preprocess_adressa`` (tokenizer, news index,
    chronological per-user split, corpus-sampled negative pools) ->
    token-derived trunk states -> train -> full-pool metrics."""
    import jax

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import token_states_from_tokens

    smoke = bool(os.environ.get("FEDREC_ACC_SMOKE"))
    events, data = _adressa_corpus(
        num_users=200 if smoke else 3_000,
        num_news=400 if smoke else 2_000,
        event_seed=1, prep_seed=2,
    )
    states = token_states_from_tokens(data.news_tokens, bert_hidden=96, seed=3)

    cfg = ExperimentConfig()
    cfg.model.text_encoder_mode = "head"
    cfg.model.bert_hidden = 96
    cfg.model.news_dim = 128
    cfg.model.num_heads = 16
    cfg.model.head_dim = 8
    cfg.model.query_dim = 64
    cfg.data.max_title_len = data.title_len
    cfg.data.max_his_len = 30
    cfg.fed.strategy = "local"
    cfg.fed.num_clients = 1
    cfg.fed.rounds = rounds
    cfg.optim.user_lr = cfg.optim.news_lr = 5e-4  # see leg_central
    cfg.train.eval_protocol = "full"
    cfg.train.eval_every = 1
    cfg.train.snapshot_dir = ""
    cfg.train.resume = False

    out = {
        "leg": "adressa",
        "platform": jax.devices()[0].platform,
        "corpus": {
            "num_news": data.num_news,
            "train": len(data.train_samples),
            "valid": len(data.valid_samples),
            "events": len(events),
            "bert_hidden": 96,
        },
        "oracle_auc": round(oracle_auc(data, states), 4),
        "rounds_requested": rounds,
        "config": {"mode": "head", "dtype": cfg.model.dtype,
                   "lr": cfg.optim.user_lr, "batch": cfg.data.batch_size},
    }

    out["provenance"] = _prov()

    def persist(partial):
        (HERE / "accuracy_adressa.json").write_text(
            json.dumps({**out, **partial}, indent=2)
        )

    result = _train(cfg, data, states, on_round=persist)
    persist(result)
    print(json.dumps({"leg": "adressa", "oracle_auc": out["oracle_auc"],
                      "wall_s": result["wall_s"]}))


def leg_finetune(rounds: int) -> None:
    """BASELINE config 5 at benchmark scale: the FULL text trunk trains
    in-loop from raw tokens (no cached states anywhere). The lexical topic
    corpus carries its signal in the tokens, so a from-scratch tiny trunk
    must learn the topical structure end-to-end — embeddings, transformer
    block, pooling head, and user tower together."""
    import jax

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import token_states_from_tokens

    smoke = bool(os.environ.get("FEDREC_ACC_SMOKE"))
    _, data = _adressa_corpus(
        num_users=150 if smoke else 1_200,
        num_news=300 if smoke else 800,
        event_seed=21, prep_seed=22,
    )

    cfg = ExperimentConfig()
    cfg.model.text_encoder_mode = "finetune"
    cfg.model.bert_hidden = 64
    cfg.model.trunk_layers = 2
    cfg.model.trunk_heads = 4
    cfg.model.trunk_ffn = 128
    cfg.model.trunk_vocab = 30_522       # hashing-tokenizer id space
    cfg.model.news_dim = 64
    cfg.model.num_heads = 8
    cfg.model.head_dim = 8
    cfg.model.query_dim = 32
    cfg.data.max_title_len = data.title_len
    cfg.data.max_his_len = 20
    cfg.fed.strategy = "local"
    cfg.fed.num_clients = 1
    cfg.fed.rounds = rounds
    cfg.optim.user_lr = cfg.optim.news_lr = 1e-3
    # standard logit CE: the reference's CE-over-sigmoid quirk
    # (model.py:123-126, kept as the parity default) compresses logits into
    # [0,1] and starves a from-scratch trunk of gradient — it never escapes
    # the ln(5) plateau in a bounded-round demo
    cfg.model.sigmoid_before_ce = False
    cfg.train.eval_protocol = "full"
    cfg.train.eval_every = 1
    cfg.train.snapshot_dir = ""
    cfg.train.resume = False

    # oracle on token-derived states: same lexical ceiling the trunk chases
    states = token_states_from_tokens(data.news_tokens, bert_hidden=64, seed=23)
    out = {
        "leg": "finetune",
        "platform": jax.devices()[0].platform,
        "corpus": {
            "num_news": data.num_news,
            "train": len(data.train_samples),
            "valid": len(data.valid_samples),
            "trunk": f"{cfg.model.trunk_layers}x{cfg.model.bert_hidden}",
        },
        "oracle_auc": round(oracle_auc(data, states), 4),
        "rounds_requested": rounds,
        "config": {"mode": "finetune", "dtype": cfg.model.dtype,
                   "lr": cfg.optim.user_lr, "batch": cfg.data.batch_size},
    }

    out["provenance"] = _prov()

    def persist(partial):
        (HERE / "accuracy_finetune.json").write_text(
            json.dumps({**out, **partial}, indent=2)
        )

    result = _train(cfg, data, None, on_round=persist)
    persist(result)
    print(json.dumps({"leg": "finetune", "oracle_auc": out["oracle_auc"],
                      "wall_s": result["wall_s"]}))


# ------------------------------------------------------------------- report
_CURVE_HEADER = [
    "| round | train loss | AUC | MRR | NDCG@5 | NDCG@10 |",
    "|---|---|---|---|---|---|",
]


def _curve_rows(curve: list[dict]) -> list[str]:
    return [
        f"| {row['round']} | {row['train_loss']:.4f} | {row.get('auc', float('nan')):.4f} "
        f"| {row.get('mrr', float('nan')):.4f} | {row.get('ndcg5', float('nan')):.4f} "
        f"| {row.get('ndcg10', float('nan')):.4f} |"
        for row in curve
    ]


def _partial_note(leg: dict) -> str:
    """'(PARTIAL: ...)' when a persisted curve is shorter than requested —
    a killed run is truncated mid-leg and the report must say so."""
    requested = leg.get("rounds_requested", len(leg["curve"]))
    if len(leg["curve"]) >= requested:
        return ""
    return (
        f" (PARTIAL: run truncated at round {leg['curve'][-1]['round']} "
        f"of {requested})"
    )


def write_report() -> None:
    """Collect whichever leg JSONs exist into RESULTS.md (a failed run can
    leave one leg missing — report the evidence that exists)."""
    def _load_complete(fname: str):
        # an artifact flagged "partial" (incremental stamp of a run that
        # never finished) lacks the leg's summary fields — reporting it
        # would KeyError mid-report or publish a half-trained comparison
        path = HERE / fname
        if not path.exists():
            return None
        d = json.loads(path.read_text())
        if d.get("partial"):
            print(f"[report] skipping {fname}: partial (run never "
                  "completed); re-run the leg", file=sys.stderr)
            return None
        return d

    central = _load_complete("accuracy_central.json")
    fed = _load_complete("accuracy_fed.json")
    dp = _load_complete("accuracy_dp.json")
    adressa = _load_complete("accuracy_adressa.json")
    finetune = _load_complete("accuracy_finetune.json")
    bf16 = _load_complete("accuracy_bf16.json")
    if all(x is None for x in (central, fed, dp, adressa, finetune, bf16)):
        raise SystemExit("no accuracy_*.json found; run the legs first")

    lines = [
        "# RESULTS — end-to-end accuracy loop",
        "",
        "Deterministic **full-negative-pool** evaluation (the protocol behind",
        "the reference's published MIND table, reference",
        "`evaluation_functions.py:33-47`): AUC / MRR / NDCG@5 / NDCG@10 averaged",
        "over every validation impression's entire pool. Data is the",
        "topic-structured synthetic corpus (`make_synthetic_mind_topics`) — the",
        "largest corpus obtainable offline (real MIND needs the tsv download;",
        "the preprocessing for it is `fedrec_tpu/data/preprocess.py`). The",
        "corpus has a *known* recoverable signal, quantified by an oracle",
        "cosine scorer on the raw trunk states.",
    ]
    if central is not None:
        lines += [
            "",
            "## 1. Flagship centralized run",
            "",
            f"Platform **{central['platform']}** ({central['device']}), mode",
            "`head` (trainable text head over cached trunk states), dtype",
            f"`{central['config']['dtype']}`, lr {central['config']['lr']},",
            f"batch {central['config']['batch']}. Corpus: {central['corpus']['train']:,}",
            f"train / {central['corpus']['valid']:,} valid impressions over",
            f"{central['corpus']['num_news']:,} news,",
            f"{central['corpus']['bert_hidden']}-d trunk states.",
            f"Oracle reference scorer AUC: **{central['oracle_auc']:.4f}**.",
            f"Wall-clock: {central['wall_s']}s.",
            "",
            *_CURVE_HEADER,
        ]
        lines += _curve_rows(central["curve"])
        last = central["curve"][-1]
        frac = last.get("auc", 0.0) / max(central["oracle_auc"], 1e-9)
        lines += [
            "",
            f"Final AUC {last.get('auc', float('nan')):.4f} = "
            f"**{100 * frac:.1f}% of the oracle reference scorer** "
            f"(random = 0.5; a learned pooling can exceed the oracle's "
            f"uniform token average).{_partial_note(central)}",
        ]
    if fed is not None:
        lines += [
            "",
            "## 2. Federation and privacy cost (8-client CPU mesh)",
            "",
            f"Same protocol on a small corpus ({fed['corpus']['train']:,} train /",
            f"{fed['corpus']['valid']:,} valid, {fed['corpus']['num_news']:,} news,",
            f"96-d states), {fed['n_devices']}-device fake mesh. Oracle AUC:",
            f"**{fed['oracle_auc']:.4f}**.",
            "",
            "| run | final AUC | final MRR | final NDCG@10 | wall s |",
            "|---|---|---|---|---|",
        ]
        for name, run in fed["runs"].items():
            c = run["curve"][-1]
            lines.append(
                f"| {name} | {c.get('auc', float('nan')):.4f} | {c.get('mrr', float('nan')):.4f} "
                f"| {c.get('ndcg10', float('nan')):.4f} | {run['wall_s']} |"
            )
        if any(n.endswith("_cohort") for n in fed["runs"]):
            lines += [
                "",
                "`param_avg_32_cohort` runs the BASELINE north-star client",
                "count via in-device cohorts (32 clients on the 8-device",
                "mesh, 4 per device; `tests/test_cohorts.py` pins the",
                "packing-independence). It trains 4 local epochs per round:",
                "a 32-way split leaves each client 1/4 the per-round local",
                "steps of the 8-client rows, and equalizing the update",
                "count closes the r3 gap (0.55 vs 0.67 then) to within",
                "~0.006 AUC of the 8-client row — standard FedAvg data",
                "scaling, not a cohort artifact: the same 32-client run on",
                "32 devices computes bit-equal collectives.",
                "",
                "`local_1client` runs at its own measured operating point",
                "(lr 2e-3): one client takes 8x the optimizer steps per",
                "round and collapses at the shared lr.",
                "`param_avg_8_fedavgm` runs server momentum 0.5 at the",
                "SHARED lr — the best point of the r5 (server_lr x",
                "momentum x local lr) sweep; no FedOpt point beat the",
                "plain mean once local lrs were tuned, so the feature is",
                "marked available-not-recommended at this scale",
                "(PARITY.md; m=0.9 needs crippled 5e-4 locals -> 0.721).",
            ]
    if dp is not None:
        r = dp["recipe"]
        lines += [
            "",
            "## 2b. Privacy-utility tradeoff (DP-tuned recipe)",
            "",
            "DP-SGD sweep with hyperparameters tuned FOR the DP estimator",
            f"(`{r['strategy']}`, {r['clients']} clients, clip C={r['clip_norm']},",
            f"Adam lr {r['lr']}, {r['rounds']} rounds; accountant budgets the",
            f"steps actually trained, delta={r['delta']}). The non-private",
            "anchor uses the SAME tuned lr — the honest bar, since non-DP",
            "training also improves under the lr sweep. Why the r3 rows were",
            "~random and what changed: docs/DP.md.",
            "",
            "| run | epsilon | scope | B | sigma | final AUC | gap to non-DP |",
            "|---|---|---|---|---|---|---|",
        ]
        for name, run in dp["runs"].items():
            c = run["curve"][-1] if run["curve"] else {}
            gap = dp["gap_to_anchor"].get(name)
            lines.append(
                f"| {name} | {run.get('epsilon') or '—'} "
                f"| {run.get('dp_scope', 'all')} | {run.get('batch_size', 64)} "
                f"| {run.get('sigma', 0)} "
                f"| {c.get('auc', float('nan')):.4f} "
                f"| {f'{gap:+.4f}' if gap is not None else '—'} |"
            )
        lines += [
            "",
            f"Oracle AUC {dp['oracle_auc']:.4f}; non-DP tuned anchor "
            f"{dp['nodp_anchor_auc']:.4f}.",
        ]
        ceil = dp.get("user_frozen_ceiling_auc")
        eps10 = dp["runs"].get("dp_eps10", {}).get("curve") or []
        if ceil is not None and eps10:
            floor = eps10[-1]["auc"]
            lines += [
                "",
                "The round-5 levers (noise-dimension shrink via "
                "`privacy.dp_scope='user'`, batch scaling) are measured "
                "and both LOSE at this per-client data scale — "
                f"`nodp_user_frozen` ({ceil:.4f}) is the non-private "
                "ceiling of any user-tower-only scheme, and full-model DP "
                f"at eps=10 ({floor:.4f}) sits {ceil - floor:+.4f} from "
                "it. That eps=10 number is the measured floor here; the "
                "full argument is in docs/DP.md.",
            ]
    if adressa is not None:
        lines += [
            "",
            "## 3. Second dataset family: Adressa pipeline",
            "",
            "Synthetic Adressa-format event log (lexical topic signal) run",
            "through the REAL adapter — `parse_adressa_events` →",
            "tokenizer → `build_news_index` → chronological per-user split →",
            "corpus-sampled negative pools (`fedrec_tpu/data/adressa.py`) —",
            "then trained on token-derived frozen-random-trunk states",
            f"(`token_states_from_tokens`). Corpus: {adressa['corpus']['events']:,}",
            f"events → {adressa['corpus']['train']:,} train /",
            f"{adressa['corpus']['valid']:,} valid samples over",
            f"{adressa['corpus']['num_news']:,} news. Oracle AUC:",
            f"**{adressa['oracle_auc']:.4f}**. Wall-clock: {adressa['wall_s']}s.",
            "",
            *_CURVE_HEADER,
        ]
        lines += _curve_rows(adressa["curve"])
        last_a = adressa["curve"][-1]
        lines += [
            "",
            f"Final AUC {last_a.get('auc', float('nan')):.4f} "
            f"({100 * last_a.get('auc', 0.0) / max(adressa['oracle_auc'], 1e-9):.1f}% "
            "of the oracle; reference published Adressa AUC 72.04 on the real "
            f"corpus, `README.md:78`).{_partial_note(adressa)}",
        ]
    if finetune is not None:
        lines += [
            "",
            "## 4. In-loop trunk fine-tuning (BASELINE config 5)",
            "",
            "The FULL text trunk",
            f"({finetune['corpus']['trunk']} transformer, from scratch) trains",
            "in-loop from raw tokens — no cached states anywhere — on the",
            f"lexical Adressa corpus ({finetune['corpus']['train']:,} train /",
            f"{finetune['corpus']['valid']:,} valid over",
            f"{finetune['corpus']['num_news']:,} news). Oracle (token-derived",
            f"states): **{finetune['oracle_auc']:.4f}**. Wall-clock:",
            f"{finetune['wall_s']}s.",
            "",
            *_CURVE_HEADER,
        ]
        lines += _curve_rows(finetune["curve"])
        last_f = finetune["curve"][-1]
        lines += [
            "",
            f"Final AUC {last_f.get('auc', float('nan')):.4f} "
            f"({100 * last_f.get('auc', 0.0) / max(finetune['oracle_auc'], 1e-9):.1f}% "
            f"of the oracle).{_partial_note(finetune)}",
        ]
    lines += [
        "",
        *([
            "",
            "## Dtype tolerance (bfloat16 vs float32)",
            "",
            f"Same corpus/config trained in both dtypes on "
            f"**{bf16['platform']}** ({bf16['device']}); final full-pool "
            f"AUC — f32 **{bf16['final_auc']['float32']:.4f}** vs bf16 "
            f"**{bf16['final_auc']['bfloat16']:.4f}** "
            f"(delta {bf16['auc_delta']:.4f}, tolerance "
            f"{bf16['tolerance_auc']}): "
            + ("**within tolerance** — the dtype the TPU bench advertises "
               "is accuracy-safe." if bf16.get("within_tolerance")
               else "**OUT OF TOLERANCE** — investigate before trusting "
                    "bf16 numbers."),
        ] if bf16 is not None and "final_auc" in bf16 else []),
        "Full per-round curves: `benchmarks/accuracy_central.json`,",
        "`benchmarks/accuracy_fed.json`, `benchmarks/accuracy_adressa.json`,",
        "`benchmarks/accuracy_finetune.json`.",
        "Reproduce: `python benchmarks/accuracy_run.py --all`.",
        "",
    ]
    (REPO / "RESULTS.md").write_text("\n".join(lines))
    print(f"wrote {REPO / 'RESULTS.md'}")


# --------------------------------------------------------------------- main
def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--leg", choices=["central", "fed", "dp", "adressa",
                                     "finetune", "bf16", "report"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--rounds", type=int, default=16)
    p.add_argument("--fed-rounds", type=int, default=10)
    p.add_argument("--dp-rounds", type=int, default=32)
    p.add_argument("--adressa-rounds", type=int, default=10)
    p.add_argument("--finetune-rounds", type=int, default=12)
    p.add_argument("--bf16-rounds", type=int, default=8)
    args = p.parse_args()

    if args.all:
        from fedrec_tpu.hostenv import cpu_host_env

        # the central and bf16 legs run on whatever device JAX gives their
        # child process (set FEDREC_ACC_CPU=1 yourself for the CPU-scaled
        # corpus). This orchestrating process never imports jax, so each
        # child is the only process on its device while it runs.
        env_central = dict(os.environ)

        env_fed = cpu_host_env(8)
        env_fed["FEDREC_ACC_INNER"] = "1"  # children skip the self-harden re-exec
        # an ambient row filter (debugging) must not turn the
        # canonical full-sweep artifacts into subsets
        env_fed.pop("FEDREC_DP_ROWS", None)
        me = str(HERE / "accuracy_run.py")
        central_cmd = [
            sys.executable, me, "--leg", "central", "--rounds", str(args.rounds)
        ]
        rc = subprocess.run(central_cmd, env=env_central, cwd=REPO).returncode
        if rc != 0:
            return rc
        for cmd, env in (
            ([sys.executable, me, "--leg", "fed", "--rounds", str(args.fed_rounds)],
             env_fed),
            ([sys.executable, me, "--leg", "dp",
              "--dp-rounds", str(args.dp_rounds)], env_fed),
            ([sys.executable, me, "--leg", "adressa",
              "--rounds", str(args.adressa_rounds)], env_fed),
            ([sys.executable, me, "--leg", "finetune",
              "--rounds", str(args.finetune_rounds)], env_fed),
            ([sys.executable, me, "--leg", "report"], dict(os.environ)),
        ):
            rc = subprocess.run(cmd, env=env, cwd=REPO).returncode
            if rc != 0:
                return rc

        # dtype-tolerance leg AFTER the report chain, on the central leg's
        # device (the chip is the dtype's native home)
        rc = subprocess.run(
            [sys.executable, me, "--leg", "bf16",
             "--bf16-rounds", str(args.bf16_rounds)],
            env=env_central, cwd=REPO,
        ).returncode
        if rc != 0:
            return rc
        # regenerate the report so it includes the bf16 section
        return subprocess.run(
            [sys.executable, me, "--leg", "report"],
            env=dict(os.environ), cwd=REPO,
        ).returncode

    if (
        args.leg in ("fed", "dp", "adressa", "finetune")
        and os.environ.get("FEDREC_ACC_INNER") != "1"
    ):
        # These legs are DESIGNED for the 8-device fake CPU mesh (the
        # multi-client simulation rig), so they re-exec themselves onto it
        # exactly like --all does for its children. Operators who really want a leg on a live
        # multi-device accelerator can set FEDREC_ACC_INNER=1 to skip the
        # re-exec and keep their own environment.
        from fedrec_tpu.hostenv import cpu_host_env

        env = cpu_host_env(8)
        env["FEDREC_ACC_INNER"] = "1"
        os.execve(sys.executable, [sys.executable, *sys.argv], env)

    if args.leg == "central":
        leg_central(args.rounds)
    elif args.leg == "bf16":
        leg_bf16(args.bf16_rounds)
    elif args.leg == "fed":
        leg_fed(args.rounds)
    elif args.leg == "dp":
        leg_dp(args.dp_rounds)
    elif args.leg == "adressa":
        leg_adressa(args.rounds)
    elif args.leg == "finetune":
        leg_finetune(args.rounds)
    elif args.leg == "report":
        write_report()
    else:
        p.error("pass --leg or --all")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
