"""Unified CLI driver — replaces the reference's four entry scripts.

Reference drivers and their equivalents here (positional args kept
compatible with ``torchrun ... <script> epochs batch save_every``,
reference ``main.py:178-184``):

  * ``main.py`` (DDP simulation)            -> ``--strategy grad_avg``
  * ``Gradient_Averaging_main.py``          -> ``--strategy grad_avg``
  * ``Parameter_Averaging_main.py``         -> ``--strategy param_avg``
  * ``client.py``/``server.py`` coordinator -> ``--strategy coordinator``
    (multi-host; see fedrec_tpu.parallel.multihost)

Usage:
  python -m fedrec_tpu.cli.run EPOCHS BATCH SAVE_EVERY \
      [--strategy param_avg] [--clients 8] [--data-dir UserData] \
      [--dp-epsilon 10] [--set section.key=value ...]

Unlike the reference there is no torchrun/c10d rendezvous to stand up: the
clients are mesh slots of one SPMD program (single host) or
``jax.distributed``-initialized processes (multi-host).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("total_epochs", type=int, help="global rounds (reference argv 1)")
    p.add_argument("batch_size", type=int, help="per-client batch size (argv 2)")
    p.add_argument("save_every", type=int, help="snapshot cadence in rounds (argv 3)")
    p.add_argument("--strategy", default="param_avg",
                   choices=["local", "grad_avg", "param_avg", "coordinator"])
    p.add_argument("--clients", type=int, default=None,
                   help="default: all visible devices")
    p.add_argument("--data-dir", default=None,
                   help="directory with bert_news_index.npy etc. (default: "
                        "data.data_dir; must exist unless --synthetic)")
    p.add_argument("--token-states", default=None,
                   help="path to cached (N, L, H) trunk token states .npy; "
                        "default <data-dir>/token_states.npy. Required with "
                        "--data-dir artifacts; with --synthetic a missing "
                        "file means random states made on the device")
    p.add_argument("--dp-epsilon", type=float, default=0.0,
                   help="enable LDP with this epsilon (reference argv 4; 0 = off)")
    p.add_argument("--local-epochs", type=int, default=1)
    p.add_argument("--participation", type=float, default=1.0)
    p.add_argument("--mode", default=None, choices=[None, "joint", "decoupled"])
    p.add_argument("--synthetic", action="store_true",
                   help="use synthetic data instead of --data-dir artifacts")
    p.add_argument("--synthetic-train", type=int, default=2048,
                   help="synthetic corpus size (train samples)")
    p.add_argument("--synthetic-news", type=int, default=512,
                   help="synthetic corpus size (distinct news)")
    p.add_argument("--obs-dir", default=None,
                   help="write observability artifacts here (shorthand for "
                        "--set obs.dir=...); render with fedrec-obs report")
    p.add_argument("--agg-server", default=None, metavar="HOST:PORT",
                   help="async federation (agg.mode=async across processes): "
                        "drive rounds against this fedrec_tpu.agg.server "
                        "commit authority instead of a collective world")
    p.add_argument("--worker-id", default=None,
                   help="this worker's name on the agg server / in the "
                        "fleet report (required with --agg-server)")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="SECTION.KEY=VALUE")
    return p


# share of the synthetic catalog that positives are drawn from
# (make_synthetic_mind's popularity signal); random_token_states marks the
# same rows so the signal is visible to the text tower
_SYNTHETIC_POPULAR_FRAC = 0.2


def make_synthetic_from_args(args, cfg):
    """Shared synthetic-corpus construction for the run and coordinator
    drivers (one definition of the valid-set sizing)."""
    from fedrec_tpu.data import make_synthetic_mind

    return make_synthetic_mind(
        num_news=args.synthetic_news, num_train=args.synthetic_train,
        num_valid=max(args.synthetic_train // 8, 32),
        title_len=cfg.data.max_title_len,
        popular_frac=_SYNTHETIC_POPULAR_FRAC,
    )


def random_token_states(
    num_news: int, title_len: int, hidden: int, dtype, popular_frac: float
):
    """Random ``(num_news, title_len, hidden)`` trunk states for synthetic
    runs, made on the device in the table's dtype a chunk of rows at a time.

    The host never holds the table and the device holds it once, so a
    MIND-small catalog (65,536 x 50 x 768 bf16, 5.0 GB) is reachable; a
    float64 host draw of the same table is ~20 GB. The rows
    ``make_synthetic_mind`` draws positives from (ids 1..popular) share one
    offset direction, which gives the text tower something to learn.
    """
    from functools import partial

    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    chunk_rows = min(2048, num_news)
    n_popular = int(popular_frac * num_news)
    root = jax.random.PRNGKey(0)
    offset = jax.random.normal(jax.random.fold_in(root, num_news), (hidden,))
    offset = offset / jnp.linalg.norm(offset) * jnp.sqrt(hidden / 8.0)

    @partial(jax.jit, donate_argnums=0)
    def fill(buf, start):
        block = jax.random.normal(
            jax.random.fold_in(root, start), (chunk_rows, title_len, hidden)
        )
        rows = start + jnp.arange(chunk_rows)
        popular = ((rows >= 1) & (rows <= n_popular))[:, None, None]
        block = block + jnp.where(popular, offset, 0.0)
        return jax.lax.dynamic_update_slice(
            buf, block.astype(dtype), (start, 0, 0)
        )

    buf = jnp.zeros((num_news, title_len, hidden), dtype)
    # a short last chunk is re-anchored so that it ends at the last row
    for start in range(0, num_news, chunk_rows):
        buf = fill(buf, jnp.int32(min(start, num_news - chunk_rows)))
    return buf


def load_inputs(args):
    """``(cfg, data, token_states)`` for parsed arguments: everything
    ``Trainer`` needs. Returns ``None`` (after printing why) when the
    arguments name no usable data."""
    import jax

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import load_mind_artifacts

    cfg = ExperimentConfig()
    cfg.fed.rounds = args.total_epochs
    cfg.data.batch_size = args.batch_size
    cfg.train.save_every = args.save_every
    cfg.fed.strategy = args.strategy
    cfg.fed.local_epochs = args.local_epochs
    cfg.fed.participation = args.participation
    cfg.fed.num_clients = args.clients or len(jax.local_devices())
    if args.mode:
        cfg.model.text_encoder_mode = "table" if args.mode == "decoupled" else "head"
    if args.obs_dir:
        cfg.obs.dir = args.obs_dir
    # record the data source IN the config (snapshot config.json is the
    # provenance record of what a run trained on); --set data.* overrides
    # below still win over the CLI flags
    if args.data_dir is not None:
        cfg.data.data_dir = args.data_dir
    if args.synthetic:
        cfg.data.dataset = "synthetic"
    cfg.apply_overrides(args.overrides)

    synthetic = cfg.data.dataset == "synthetic"
    if synthetic:
        data = make_synthetic_from_args(args, cfg)
    elif not Path(cfg.data.data_dir).is_dir():
        print(f"[run] ERROR: no data directory {cfg.data.data_dir!r}; pass "
              "--data-dir (or --synthetic for a generated corpus)",
              file=sys.stderr)
        return None
    else:
        # "mind" and "adressa" share the artifact schema (the Adressa
        # preprocessor writes the exact UserData/ layout), so one loader
        # serves both dataset families
        data = load_mind_artifacts(cfg.data.data_dir)

    token_path = args.token_states or str(Path(cfg.data.data_dir) / "token_states.npy")
    if Path(token_path).exists():
        token_states = np.load(token_path)
    elif synthetic:
        token_states = random_token_states(
            data.num_news, data.title_len, cfg.model.bert_hidden,
            cfg.model.dtype, _SYNTHETIC_POPULAR_FRAC,
        )
    else:
        print(
            f"[run] ERROR: no token states at {token_path}; precompute them "
            "with fedrec_tpu.models.bert or pass --token-states (or use "
            "--synthetic for a random catalog)",
            file=sys.stderr,
        )
        return None
    return cfg, data, token_states


def main(argv: list[str] | None = None) -> int:
    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = build_parser().parse_args(argv)
    inputs = load_inputs(args)
    if inputs is None:
        return 2
    cfg, data, token_states = inputs

    from fedrec_tpu.privacy import calibrate_from_config
    from fedrec_tpu.train.trainer import Trainer

    if args.dp_epsilon > 0:
        cfg.privacy.enabled = True
        cfg.privacy.epsilon = args.dp_epsilon
        if cfg.model.text_encoder_mode == "table":
            # decoupled path: reference-parity noise-only mechanism (the
            # reference's sigma-from-Opacus + unclipped noise, client.py:87-89,
            # 271-281 — carries no rigorous epsilon; see fedrec_tpu.privacy)
            cfg.privacy.mechanism = "ldp_news"
            print(
                "[run] decoupled mode: using ldp_news (reference-parity, "
                "no rigorous epsilon); use --mode joint for real DP-SGD",
                file=sys.stderr,
            )
        cfg.privacy.sigma = calibrate_from_config(cfg, len(data.train_samples))
        print(
            f"[run] DP enabled: eps={cfg.privacy.epsilon} delta={cfg.privacy.delta} "
            f"sigma={cfg.privacy.sigma:.4f} clip={cfg.privacy.clip_norm}",
            file=sys.stderr,
        )

    if args.agg_server:
        if not args.worker_id:
            print("[run] --agg-server requires --worker-id", file=sys.stderr)
            return 2
        # async deployment: the round barrier is the agg server's quorum
        # commit, not a collective. The TRAINER stays in flat mode (its
        # local 1-client sync is the identity; the buffered commit lives
        # server-side) — agg.mode="async" is the IN-process simulation
        # knob for cohort deployments, not this wire path.
        from fedrec_tpu.obs.fleet import set_fleet_identity

        set_fleet_identity(worker=str(args.worker_id))
        if cfg.obs.dir:
            # the worker_* layout `fedrec-obs fleet` merges (same
            # discipline as the coordinator CLI)
            cfg.obs.dir = str(Path(cfg.obs.dir) / f"worker_{args.worker_id}")
        trainer = Trainer(cfg, data, token_states)

        from fedrec_tpu.agg.worker import run_async_worker
        from fedrec_tpu.parallel.rpc import AuthorityUnreachable

        # wire-level fault injection: a seeded chaos TCP proxy fronts
        # the authority and this worker dials THROUGH it, so torn
        # connections / duplicated pushes / partitions exercise the
        # resilient-RPC path on a real socket (scripts/async_smoke.sh's
        # fault leg). With the spec empty no proxy is built at all.
        proxy = None
        if cfg.chaos.wire_faults:
            if not cfg.chaos.enabled:
                raise ValueError(
                    "wire fault injection requires chaos.enabled=true "
                    "(chaos.wire_faults is part of the chaos plan)"
                )
            from fedrec_tpu.fed.chaos import ChaosProxy, WireFaultPlan

            up_host, up_port = args.agg_server.rsplit(":", 1)
            proxy = ChaosProxy(
                up_host, int(up_port),
                plan=WireFaultPlan(
                    cfg.chaos.wire_faults, seed=cfg.chaos.wire_seed
                ),
            )
            proxy.start()
            print(
                f"[run] chaos wire proxy {proxy.address} -> "
                f"{args.agg_server} ({cfg.chaos.wire_faults})",
                file=sys.stderr,
            )
        try:
            history = run_async_worker(
                trainer,
                proxy.address if proxy is not None else args.agg_server,
                args.worker_id,
            )
        except AuthorityUnreachable as e:
            # degrade, don't crash: rc-75 tells the PR-5 supervisor to
            # respawn this worker against the (re)started authority
            print(f"[run] {e}", file=sys.stderr)
            return e.returncode
        finally:
            if proxy is not None:
                proxy.stop()
    else:
        trainer = Trainer(cfg, data, token_states)
        # the trainer keeps its own committed copy of the table; a device
        # array of ours would be a second 5 GB on the chip for the whole run
        del inputs, token_states
        history = trainer.run()
    if history and history[-1].val_metrics:
        m = history[-1].val_metrics
        print(
            f"final: loss={history[-1].train_loss:.4f} "
            f"auc={m.get('auc', float('nan')):.4f} "
            f"ndcg10={m.get('ndcg10', float('nan')):.4f}"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
