"""Serving driver: trained snapshot -> top-k recommendations per user.

The reference framework ends at validation; an operator who trained a model
has no way to USE it. This driver closes that gap: restore the latest
snapshot, encode the news corpus once, and emit JSON-lines
``{"uid": ..., "news": [nid, ...], "scores": [...]}`` for every known user
(or a ``--uids`` subset), batched through the jitted full-catalog scorer
(:mod:`fedrec_tpu.serve`).

Each user's history is their LONGEST recorded click history across train +
valid samples (samples carry cumulative histories, so longest = latest).

Usage:
  python -m fedrec_tpu.cli.recommend --data-dir UserData \\
      --snapshot-dir snapshots [--top-k 10] [--out recs.jsonl] \\
      [--uids U123 U456] [--set section.key=value]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data-dir", required=True,
                   help="reference UserData/ artifact layout")
    p.add_argument("--token-states", default=None,
                   help="(N, L, bert_hidden) .npy of cached trunk states")
    p.add_argument("--snapshot-dir", default=None,
                   help="orbax snapshot tree (default: train.snapshot_dir)")
    p.add_argument("--top-k", type=int, default=10)
    p.add_argument("--keep-history", action="store_true",
                   help="allow already-clicked news in the output")
    p.add_argument("--out", default="-", help="output JSONL path ('-' = stdout)")
    p.add_argument("--uids", nargs="*", default=None,
                   help="subset of user ids (default: every known user)")
    p.add_argument("--allow-random-states", action="store_true",
                   help="permit serving with RANDOM trunk token states when "
                        "token_states.npy is missing (smoke/testing only — "
                        "the scores are meaningless)")
    p.add_argument("--batch-users", type=int, default=256)
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="SECTION.KEY=VALUE")
    return p


def collect_histories(data, max_his_len: int) -> dict[str, list[str]]:
    """uid -> longest recorded history (sample schema: [uidx, pos, negs,
    history, uid], reference ``dataset.py:81``)."""
    best: dict[str, list[str]] = {}
    for sample in list(data.train_samples) + list(data.valid_samples):
        _, _, _, his, uid = sample
        if len(his) >= len(best.get(uid, ())):
            best[uid] = list(his)
    return {u: h[-max_his_len:] for u, h in best.items()}


def main(argv: list[str] | None = None) -> int:
    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = build_parser().parse_args(argv)

    import jax
    import jax.numpy as jnp

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import load_mind_artifacts
    from fedrec_tpu.models import NewsRecommender
    from fedrec_tpu.serve import build_recommend_fn
    from fedrec_tpu.train.step import encode_all_news, encode_corpus_tokens

    cfg = ExperimentConfig()
    cfg.apply_overrides(args.overrides)
    snap_dir = args.snapshot_dir or cfg.train.snapshot_dir

    # serve with the TRAINING run's resolved config when it was persisted
    # next to the snapshots (Trainer/coordinator write config.json): a
    # template-free restore otherwise trusts the operator to repeat every
    # --set, and a mismatch yields an opaque shape error — or, worse,
    # silently different scores for shape-compatible knobs like max_his_len
    # (ADVICE r2). Explicit CLI --set still wins on top.
    cfg_path = Path(snap_dir) / "config.json"
    if cfg_path.exists():
        try:
            cfg = ExperimentConfig.from_dict(json.loads(cfg_path.read_text()))
            cfg.apply_overrides(args.overrides)
            print(f"[recommend] using training config {cfg_path}",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — any malformed file degrades
            # to the unverified-defaults path instead of crashing serving
            print(f"[recommend] ignoring unreadable {cfg_path}: {e}",
                  file=sys.stderr)
    else:
        print("[recommend] no config.json next to the snapshot — model "
              "hyperparameters come from defaults + --set and are NOT "
              "verified against the training run", file=sys.stderr)

    # orbax trees (fedrec-run) and coordinator msgpack globals can coexist
    # in one directory; the shared restore policy (most recently WRITTEN
    # wins, host arrays, client-0 extraction) lives in
    # fedrec_tpu.serving.store so the one-shot CLI and the long-lived
    # server can never restore different checkpoints from the same dir
    from fedrec_tpu.serving.store import load_checkpoint_params

    try:
        user_params, news_params, round_, kind = load_checkpoint_params(
            snap_dir, log=lambda m: print(f"[recommend] {m}", file=sys.stderr)
        )
    except FileNotFoundError as e:
        print(f"[recommend] {e} — train first (fedrec-run / "
              "fedrec-coordinator) or pass --snapshot-dir", file=sys.stderr)
        return 2
    print(f"[recommend] serving {kind} snapshot"
          + (f" (round {round_})" if round_ is not None else ""),
          file=sys.stderr)

    data = load_mind_artifacts(args.data_dir)
    model = NewsRecommender(cfg.model)
    mode = cfg.model.text_encoder_mode
    if mode == "finetune":
        from fedrec_tpu.models.bert import make_text_encoder

        table = encode_corpus_tokens(
            make_text_encoder(cfg.model), news_params,
            jnp.asarray(data.news_tokens, jnp.int32),
        )
    else:
        token_path = args.token_states or str(
            Path(args.data_dir) / "token_states.npy"
        )
        if Path(token_path).exists():
            token_states = np.load(token_path)
        elif args.allow_random_states:
            print(f"[recommend] no token states at {token_path}; using RANDOM "
                  "states (--allow-random-states) — scores are meaningless",
                  file=sys.stderr)
            token_states = np.random.default_rng(0).standard_normal(
                (data.num_news, data.title_len, cfg.model.bert_hidden)
            ).astype(np.float32)
        else:
            # hard error (ADVICE r2): silently substituting random trunk
            # states produced normal-looking JSONL an operator could ship
            print(f"[recommend] ERROR: no token states at {token_path}. "
                  "Export them (fedrec_tpu.models.bert) or pass "
                  "--token-states; use --allow-random-states only for "
                  "smoke tests.", file=sys.stderr)
            return 2
        table = encode_all_news(
            model, news_params,
            jnp.asarray(token_states, jnp.dtype(cfg.model.dtype)),
        )

    histories = collect_histories(data, cfg.data.max_his_len)
    uids = sorted(histories) if args.uids is None else args.uids
    missing = [u for u in uids if u not in histories]
    if missing:
        print(f"[recommend] {len(missing)} unknown uid(s) skipped: "
              f"{missing[:5]}...", file=sys.stderr)
        uids = [u for u in uids if u in histories]
    if not uids:
        print("[recommend] no users to serve", file=sys.stderr)
        return 2

    index2nid = {i: n for n, i in data.nid2index.items()}
    # real artifacts can carry more token rows than mapped nids (the
    # reference demo shard: 225 rows, 139 ids) — never recommend the unmapped
    valid = np.zeros(data.num_news, bool)
    valid[[i for i in index2nid if 0 <= i < data.num_news]] = True
    if len(jax.devices()) > 1:
        # ride the mesh: catalog + score matrix sharded over every device,
        # local top-k + all_gather merge (serve.build_recommend_fn_sharded)
        from fedrec_tpu.parallel import client_mesh
        from fedrec_tpu.serve import build_recommend_fn_sharded

        mesh = client_mesh(len(jax.devices()))
        fn = build_recommend_fn_sharded(
            model, mesh, top_k=args.top_k,
            exclude_history=not args.keep_history, valid_mask=valid,
        )
        print(f"[recommend] catalog scoring sharded over {mesh.size} devices",
              file=sys.stderr)
    else:
        fn = build_recommend_fn(
            model, top_k=args.top_k,
            exclude_history=not args.keep_history, valid_mask=valid,
        )

    out_fh = sys.stdout if args.out == "-" else open(args.out, "w")
    h_len = cfg.data.max_his_len
    bu = args.batch_users
    for start in range(0, len(uids), bu):
        chunk = uids[start : start + bu]
        hist = np.zeros((bu, h_len), np.int32)  # static shape: one compile
        for r, uid in enumerate(chunk):
            ids = [data.nid2index.get(n, 0) for n in histories[uid]]
            hist[r, : len(ids)] = ids
        ids_out, scores_out = fn(user_params, table, hist)
        ids_out, scores_out = np.asarray(ids_out), np.asarray(scores_out)
        for r, uid in enumerate(chunk):
            keep = ids_out[r] >= 0
            out_fh.write(json.dumps({
                "uid": uid,
                "news": [index2nid[int(i)] for i in ids_out[r][keep]],
                "scores": [round(float(s), 5) for s in scores_out[r][keep]],
            }) + "\n")
    if out_fh is not sys.stdout:
        out_fh.close()
        print(f"[recommend] wrote {len(uids)} users to {args.out}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
