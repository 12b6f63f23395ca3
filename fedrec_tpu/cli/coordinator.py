"""Coordinator deployment driver — the reference's client.py/server.py pair.

One script for both roles (the reference needs two divergent scripts plus a
raw-TCP side channel; see SURVEY.md section 2.3). Each participating host
runs:

  python -m fedrec_tpu.cli.coordinator ROUNDS BATCH SAVE_EVERY \
      --coordinator HOST:PORT --num-processes N --process-id I \
      [--dp-epsilon 10] [--server-trains] [--set section.key=value ...]

Process 0 is the aggregation server (reference uses rank 1,
``client.py:257``). Round loop parity:

  * continue/stop flag broadcast  (reference ``server.py:74,105``)
  * server weight fan-out          (``server.py:76-77``) — one pytree
    broadcast over DCN, not per-tensor gloo broadcasts + TCP files
  * local training epochs          (``client.py:284``)
  * participation-weighted gather  (``server.py:80-103``) — clients that
    miss a round simply contribute weight 0 instead of killing the job
    (fixes Final_Report.pdf VII.a)

Runs standalone too (single process): degrades to local FedAvg.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

import numpy as np

from fedrec_tpu.cli.run import build_parser

# EX_TEMPFAIL: a supervised worker's "world broken, relaunch me" status —
# the supervisor respawns the FULL distributed invocation, which
# re-rendezvouses and resumes from local snapshots (the elastic path)
RESPAWN_EXIT = 75


def _argv_value(tokens: list[str], flag: str) -> str | None:
    """The value of ``--flag X`` / ``--flag=X`` in an argv slice, or None."""
    for i, tok in enumerate(tokens):
        if tok == flag and i + 1 < len(tokens):
            return tokens[i + 1]
        if tok.startswith(flag + "="):
            return tok.split("=", 1)[1]
    return None


def _membership_status(address: str) -> dict | None:
    """Best-effort status query against the membership service (the
    supervisor's handshake source); None when unreachable."""
    try:
        from fedrec_tpu.parallel.membership import MembershipClient

        return MembershipClient(
            address, worker_id="_supervisor", rpc_timeout_s=5.0
        ).status()
    except Exception:  # noqa: BLE001 — a down service must not stop respawns
        return None


def _supervise(argv: list[str]) -> int:
    """``--supervise``: wrap the worker in an auto-respawn loop.

    The worker runs as a child process; whenever it dies abnormally — a
    crash/kill (negative returncode), or the deliberate
    :data:`RESPAWN_EXIT` a supervised worker uses when its world breaks —
    the supervisor relaunches the identical invocation after a jittered
    backoff. Every relaunch re-rendezvouses at the same coordinator
    address and resumes from the local snapshots (counter negotiation +
    ``sync_from_server`` integrate even a worker that never saved), so a
    killed peer turns test_elastic's manual stop-the-world restart story
    into zero operator actions: run every host with ``--supervise`` and
    the run finishes.

    The first respawn waits about the worker's ``--collective-timeout``:
    the surviving peers need that long to notice the broken world, exit
    with :data:`RESPAWN_EXIT` themselves, and free the coordination
    service address for the new world. ``FEDREC_SUPERVISE_MAX`` (default
    20) bounds the respawn budget; ``FEDREC_WORKER_PIDFILE`` (if set)
    receives the live worker's pid, so chaos tooling can kill it.

    Elastic handshake (``--membership``): before every (re)spawn the
    supervisor queries the membership service and hands the child the
    CURRENT epoch via ``FEDREC_MEMBERSHIP_EPOCH`` — and when the service
    shows a reformation already in progress (epoch advanced since the
    child started, joiners parked, or reform pending) the backoff is cut
    to ~1s: the rc-75 exit IS the reformation protocol, so making the
    child wait out a crash-grade backoff would stall the forming epoch
    for every other member. Without the handshake a respawned child
    re-execs into whatever rendezvous it last knew — the dead world —
    and loops.
    """
    import random
    import subprocess
    import time

    keep = [t for t in argv if t != "--supervise"]
    env = dict(os.environ, FEDREC_SUPERVISED="1")
    pidfile = os.environ.get("FEDREC_WORKER_PIDFILE")
    membership_addr = _argv_value(keep, "--membership")
    last_epoch: int | None = None
    base_delay = 5.0
    for i, tok in enumerate(keep):
        val = None
        if tok == "--collective-timeout" and i + 1 < len(keep):
            val = keep[i + 1]
        elif tok.startswith("--collective-timeout="):
            val = tok.split("=", 1)[1]
        if val is not None:
            try:
                base_delay = max(2.0, min(float(val), 30.0))
            except ValueError:
                pass
    max_respawns = int(os.environ.get("FEDREC_SUPERVISE_MAX", "20"))
    rng = random.Random(os.getpid())
    attempt = 0
    while True:
        if membership_addr:
            st = _membership_status(membership_addr)
            if st is not None:
                env["FEDREC_MEMBERSHIP_EPOCH"] = str(st["epoch"])
                last_epoch = int(st["epoch"])
        proc = subprocess.Popen(
            [sys.executable, "-m", "fedrec_tpu.cli.coordinator", *keep],
            env=env,
        )
        if pidfile:
            try:
                Path(pidfile).write_text(str(proc.pid))
            except OSError:
                pass
        rc = proc.wait()
        if rc == 0:
            if attempt:
                print(f"[supervisor] worker finished after {attempt} respawn(s)")
            return 0
        # only RETRYABLE statuses respawn: a signal/crash (rc < 0), the
        # deliberate RESPAWN_EXIT a supervised worker uses for a broken
        # world (which also covers rendezvous races — see main()), or the
        # chaos kill's os._exit(137). A deterministic failure (config
        # error rc=1, argparse rc=2) would fail identically 20 times —
        # surface it immediately instead.
        if rc > 0 and rc not in (RESPAWN_EXIT, 137):
            print(
                f"[supervisor] worker exited rc={rc} (non-retryable); "
                "not respawning",
                flush=True,
            )
            return rc
        attempt += 1
        if attempt > max_respawns:
            print(
                f"[supervisor] giving up after {max_respawns} respawns "
                f"(last rc={rc})",
                flush=True,
            )
            return rc if rc > 0 else 1
        delay = min(base_delay * (1.5 ** min(attempt - 1, 6)), 60.0)
        delay *= 0.5 + rng.random()  # jitter: desynchronize peer supervisors
        if membership_addr:
            st = _membership_status(membership_addr)
            reforming = st is not None and (
                st.get("reform_pending")
                or st.get("pending")
                or (last_epoch is not None and int(st["epoch"]) != last_epoch)
            )
            if reforming:
                # the exit was the reformation protocol, not a crash: the
                # forming epoch is waiting on this worker's join
                delay = 0.5 + rng.random()
        print(
            f"[supervisor] worker exited rc={rc}; respawn "
            f"{attempt}/{max_respawns} in {delay:.1f}s",
            flush=True,
        )
        time.sleep(delay)


def apply_process_sharding(cfg, rt, server_trains: bool) -> None:
    """Default ``data.num_shards``/``data.shard_index`` from the runtime so
    each process trains a DISJOINT slice of the corpus — the reference's
    per-rank ``DistributedSampler`` (reference ``main.py:166``,
    ``client.py:243-249``). Explicit ``--set data.num_shards=...`` wins —
    including ``data.num_shards=1``, which opts OUT (every host trains the
    full corpus, the pre-sharding behavior).

    With a non-training server (the reference deployment), shards are dealt
    across the ``N-1`` training clients only; the reference shards across
    the whole world, stranding the server's slice.
    """
    if rt.num_processes <= 1 or cfg.data.num_shards != 0:
        return
    if server_trains:
        cfg.data.num_shards = rt.num_processes
        cfg.data.shard_index = rt.process_id
    else:
        cfg.data.num_shards = max(rt.num_processes - 1, 1)
        # the server (process 0) holds shard 0 but never trains on it
        cfg.data.shard_index = max(rt.process_id - 1, 0)


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    parser.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                        help="rendezvous address (omit for single-process)")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--server-trains", action="store_true",
                        help="process 0 also trains (reference server does not)")
    parser.add_argument("--collective-timeout", type=float, default=300.0,
                        help="seconds before a hung DCN collective marks the "
                             "world broken and this host finishes standalone "
                             "(0 = wait forever, the reference's behavior)")
    parser.add_argument("--resume-local-state", default=None, metavar="PATH",
                        help="internal: resume standalone from a per-process "
                             "msgpack state (degraded-mode respawn)")
    parser.add_argument("--supervise", action="store_true",
                        help="run the worker under an auto-respawn "
                             "supervisor: a died/killed worker (or a broken "
                             "world) relaunches and rejoins through the "
                             "elastic resume path without operator action")
    parser.add_argument("--membership", default=None, metavar="HOST:PORT",
                        help="elastic membership service "
                             "(fedrec_tpu.parallel.membership): the world "
                             "size becomes a membership EPOCH — peer loss "
                             "shrinks-and-continues, a respawned peer "
                             "rejoins at the next epoch boundary. "
                             "--process-id is then the stable worker "
                             "identity; requires --supervise")
    original_argv = list(sys.argv[1:] if argv is None else argv)
    args = parser.parse_args(argv)
    if args.supervise:
        return _supervise(original_argv)  # stays off jax: its child owns the device
    supervised = os.environ.get("FEDREC_SUPERVISED") == "1"

    from fedrec_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from fedrec_tpu.parallel.multihost import (
        REFORM_SIGNAL,
        CoordinatorRuntime,
        initialize_distributed,
    )

    membership = None
    assignment = None
    if args.membership is not None:
        if args.process_id is None:
            parser.error("--membership requires --process-id (the stable "
                         "worker identity snapshots are keyed by)")
        if not supervised:
            parser.error(
                "--membership requires --supervise: reforming an epoch "
                "LEAVES the process (rc 75) and only the supervisor can "
                "rejoin it at the next epoch"
            )
        from fedrec_tpu.config import ExperimentConfig as _PreCfg
        from fedrec_tpu.fed.chaos import rejoin_holdoff
        from fedrec_tpu.parallel.membership import (
            MembershipClient,
            MembershipError,
            elastic_policy,
            publish_membership_metrics,
        )

        # elastic + chaos knobs are needed BEFORE the full config build
        # (which touches jax and must wait for the rendezvous); config
        # parsing itself is jax-free
        pre_cfg = _PreCfg()
        pre_cfg.apply_overrides(args.overrides)
        el = pre_cfg.fed.elastic
        holdoff = rejoin_holdoff(
            pre_cfg.chaos, args.process_id,
            Path(pre_cfg.train.snapshot_dir or "snapshots"),
        )
        if holdoff > 0:
            import time as _time

            print(
                f"[chaos] worker {args.process_id} holding off its rejoin "
                f"{holdoff:.0f}s (chaos.rejoin_delay_s) so the survivors' "
                "shrunk epoch forms first",
                flush=True,
            )
            _time.sleep(holdoff)
        membership = MembershipClient(
            args.membership, worker_id=str(args.process_id),
            join_timeout_s=el.join_timeout_s,
        )
        handed = os.environ.get("FEDREC_MEMBERSHIP_EPOCH")
        try:
            assignment = membership.join(policy=elastic_policy(el))
        except (OSError, MembershipError, ValueError) as e:
            # a join that cannot complete (service briefly down, formation
            # waiting on a member that has not reached its boundary yet)
            # is retryable by definition under supervision
            print(
                f"[membership] worker {args.process_id} join failed "
                f"({type(e).__name__}: {e}); exiting for retry "
                f"(rc {RESPAWN_EXIT})",
                flush=True,
            )
            sys.exit(RESPAWN_EXIT)
        print(
            f"[membership] worker {args.process_id} joined epoch "
            f"{assignment.epoch} as rank {assignment.rank}/"
            f"{assignment.world} (coordinator {assignment.coordinator}"
            + (f"; supervisor handed epoch {handed}" if handed else "")
            + ")",
            flush=True,
        )
        # heartbeats start BEFORE the rendezvous: leases began ticking at
        # formation, and bring-up (transport probe included) can outlast
        # lease_ms — a late first renewal would read as a death and
        # reform the world that just formed
        membership.start_heartbeat()
        publish_membership_metrics(assignment=assignment, client=membership)

    coordinator_address = args.coordinator
    world_processes = args.num_processes
    world_rank = args.process_id
    if assignment is not None:
        coordinator_address = assignment.coordinator
        world_processes = assignment.world
        world_rank = assignment.rank

    if coordinator_address is not None:
        # supervised relaunches get a BOUNDED rendezvous: a respawn racing
        # the old (dying) world must fail fast and let the supervisor retry
        init_timeout = None
        if supervised and args.collective_timeout:
            init_timeout = max(30.0, min(args.collective_timeout * 2, 120.0))
        try:
            initialize_distributed(
                coordinator_address, world_processes, world_rank,
                initialization_timeout=init_timeout,
            )
        except Exception as e:  # noqa: BLE001 — supervised rendezvous
            # failures are RETRYABLE by definition (a respawn racing the
            # dying world); exit with the retryable status so the
            # supervisor relaunches, instead of rc=1 (non-retryable)
            if not supervised:
                raise
            print(
                f"[coordinator] supervised rendezvous failed "
                f"({type(e).__name__}: {e}); exiting for retry "
                f"(rc {RESPAWN_EXIT})",
                flush=True,
            )
            sys.exit(RESPAWN_EXIT)

    import jax

    from fedrec_tpu.config import ExperimentConfig
    from fedrec_tpu.data import load_mind_artifacts
    from fedrec_tpu.privacy import calibrate_from_config
    from fedrec_tpu.train.trainer import Trainer

    cfg = ExperimentConfig()
    cfg.fed.rounds = args.total_epochs
    cfg.data.batch_size = args.batch_size
    cfg.train.save_every = args.save_every
    # local aggregation within each host's mesh stays param_avg; cross-host
    # aggregation goes through the coordinator runtime
    cfg.fed.strategy = "param_avg"
    cfg.fed.local_epochs = args.local_epochs
    cfg.fed.num_clients = args.clients or len(jax.local_devices())
    # record the data source IN the config (config.json provenance);
    # --set data.* overrides below still win over the CLI flags
    if args.data_dir is not None:
        cfg.data.data_dir = args.data_dir
    if args.synthetic:
        cfg.data.dataset = "synthetic"
    cfg.apply_overrides(args.overrides)

    if membership is not None:
        cfg.fed.elastic.enabled = True  # config.json provenance
    elif cfg.fed.elastic.enabled:
        raise ValueError(
            "fed.elastic.enabled is set but no membership service was "
            "given: pass --membership HOST:PORT (and run under "
            "--supervise) — the epoch layer cannot form without the "
            "lease service"
        )

    if cfg.fed.dcn_compress == "auto":
        # the adaptive per-leaf map is pinned from the Trainer's in-graph
        # warmup telemetry; the coordinator wire path has no warmup window
        # yet, so a concrete codec must be named per deployment
        raise ValueError(
            "fed.dcn_compress='auto' needs the trainer's warmup telemetry "
            "and is not available on the coordinator path; pin a concrete "
            "codec (int8/sign1bit/topk/countsketch/randproj) per deployment"
        )
    if cfg.fed.robust.method != "mean" and cfg.fed.dcn_compress != "none":
        # robust x compress is LEGAL for every per-contribution codec: the
        # gather decodes each contribution per process BEFORE any reduction
        # (decode-before-reduce, fedrec_tpu.comms), so trimmed-mean/median
        # judge clients, not quantization noise. The fail-fast survives for
        # the LINEAR sketches, whose contributions only exist pre-aggregated
        # (capability table: decodes_per_contribution=False) — checked HERE
        # (same policy as validate_compress): raised lazily inside the
        # aggregation collective, it would be misread by the watchdog as a
        # peer failure and silently degrade every host to standalone
        # training.
        from fedrec_tpu.comms import codec_caps

        if not codec_caps(cfg.fed.dcn_compress).decodes_per_contribution:
            raise ValueError(
                f"fed.robust.method={cfg.fed.robust.method!r} needs "
                "per-contribution decode, which codec "
                f"{cfg.fed.dcn_compress!r} cannot provide (order statistics "
                "judge CLIENTS, and sketch collisions mix every client's "
                "coordinates before any decode exists); use one of the "
                "decodable codecs (int8/sign1bit/topk) or "
                "fed.robust.method='mean'"
            )
    rt = CoordinatorRuntime(
        collective_timeout_s=args.collective_timeout or None,
        compress=cfg.fed.dcn_compress,
        robust=cfg.fed.robust,
        topk_ratio=cfg.fed.dcn_topk_ratio,
        error_feedback=cfg.fed.dcn_error_feedback,
        sketch_width=cfg.fed.dcn_sketch_width,
        sketch_seed=cfg.fed.dcn_sketch_seed,
        # cross-device round deadline: bound the round-end report gather
        # (fed.population.round_deadline_ms) so a straggling peer costs a
        # bounded wait, never a wedged run. NOTE this is a REAL wall-clock
        # bound on the DCN all-gather (a miss degrades this host to
        # standalone for the remaining rounds — collectives are ordered
        # and a partial gather cannot be resumed), so on a coordinator
        # deployment size it to real gather time, not to the simulated
        # straggle scale the in-process deadline cuts against
        round_deadline_s=(
            cfg.fed.population.round_deadline_ms / 1e3
            if cfg.fed.population.round_deadline_ms > 0 else None
        ),
        membership=membership,
        epoch=assignment.epoch if assignment is not None else 0,
        # agg.mode=hierarchical: per-host robust pre-aggregate + tiered
        # cross-host reduce (mean deliberately lowers to the flat
        # collective — see aggregate_from_hosts)
        agg=cfg.agg,
    )
    apply_process_sharding(cfg, rt, args.server_trains)

    if cfg.data.dataset == "synthetic":
        from fedrec_tpu.cli.run import make_synthetic_from_args

        data = make_synthetic_from_args(args, cfg)
    else:
        # "mind" and "adressa" share the artifact schema, one loader both
        data = load_mind_artifacts(cfg.data.data_dir)

    token_path = args.token_states or str(Path(cfg.data.data_dir) / "token_states.npy")
    if Path(token_path).exists():
        token_states = np.load(token_path)
    else:
        token_states = None
        if membership is not None and cfg.shard.table:
            # sharded-catalog recovery: a (re)joined worker whose token
            # source is gone reloads the frozen rows from the last table
            # checkpoint (save cadence below) instead of losing them —
            # the no-rows-lost half of shrink-and-continue
            from fedrec_tpu.train.checkpoint import load_table_checkpoint

            token_states = load_table_checkpoint(
                Path(cfg.train.snapshot_dir or "snapshots")
            )
            if token_states is not None:
                from fedrec_tpu.obs import get_registry

                get_registry().counter(
                    "shard.reshard_rows_recovered_total",
                    "catalog rows reloaded from the table checkpoint "
                    "across membership epoch changes",
                ).inc(float(token_states.shape[0]))
                print(
                    f"[membership] worker {args.process_id} recovered "
                    f"{token_states.shape[0]} catalog rows from the table "
                    "checkpoint"
                )
        if token_states is None and cfg.data.dataset != "synthetic":
            print(f"[coordinator] ERROR: no token states at {token_path}; "
                  "precompute them or pass --token-states (or use "
                  "--synthetic for a random catalog)", file=sys.stderr)
            return 2
        if token_states is None:
            # every process draws the same table from the same seed
            token_states = np.random.default_rng(0).standard_normal(
                (data.num_news, data.title_len, cfg.model.bert_hidden),
                dtype=np.float32,
            )

    if args.dp_epsilon > 0:
        cfg.privacy.enabled = True
        cfg.privacy.epsilon = args.dp_epsilon
        # calibrate against this HOST's actual training-set size: process
        # sharding shrinks the local data, and a global-count calibration
        # would underestimate the sample rate q and under-noise every round
        # (privacy loss would exceed the configured epsilon)
        n_local = len(data.train_samples)
        if cfg.data.num_shards > 1:
            # shard length by arithmetic: process_shard_indices deals
            # perm[shard_index::num_shards] over n rows (index_samples is
            # 1:1 with train_samples), so the count is independent of the
            # permutation — no need to materialize it here
            n_local = -(-(n_local - cfg.data.shard_index) // cfg.data.num_shards)
        cfg.privacy.sigma = calibrate_from_config(cfg, n_local)

    # ---- fleet observability (fedrec_tpu.obs.fleet): stamp this
    # worker's stable id + per-epoch rank/epoch into every span,
    # snapshot and JSONL record; give each worker its OWN obs subdir
    # (the worker_* layout `fedrec-obs fleet` merges); and re-seed the
    # registry's counters from the persisted baseline so a respawned
    # worker's totals resume instead of resetting
    from fedrec_tpu.obs.fleet import (
        restore_counter_baseline,
        set_fleet_identity,
    )

    # snapshot/artifact identity: under elastic membership the STABLE
    # worker id (ranks are re-dealt every epoch, so rank-keyed files
    # would adopt a different worker's state after a reshuffle); the
    # rank otherwise — THE one definition, shared by the obs worker dir,
    # the state_suffix snapshot naming and the chaos-kill target below
    ident = int(args.process_id) if membership is not None else rt.process_id
    set_fleet_identity(
        worker=str(ident),
        rank=rt.process_id,
        epoch=assignment.epoch if assignment is not None else None,
    )
    if cfg.obs.dir and (rt.num_processes > 1 or membership is not None):
        cfg.obs.dir = str(Path(cfg.obs.dir) / f"worker_{ident}")
    if cfg.obs.dir and membership is not None:
        restore_counter_baseline(Path(cfg.obs.dir))
    if assignment is not None:
        from fedrec_tpu.obs import get_tracer

        get_tracer().instant(
            "membership_join", epoch=assignment.epoch,
            rank=assignment.rank, world=assignment.world,
        )

    trains = args.server_trains or not rt.is_server or rt.num_processes == 1
    local_snap = None
    # a degraded-mode respawn is a standalone process that must keep the
    # multi-process msgpack snapshot flavor (it continues ITS shard's run);
    # so must an elastic world shrunk to 1 — the next epoch may grow back
    msgpack_snapshots = (
        rt.num_processes > 1 or args.resume_local_state
        or membership is not None
    )
    # state files key on the same stable identity (`ident`, defined with
    # the fleet-observability block above)
    state_suffix = (
        f"w{args.process_id}" if membership is not None
        else f"p{rt.process_id}"
    )
    if msgpack_snapshots:
        # orbax snapshots assume whole-world coordination; in the coordinator
        # deployment each process instead flax-serializes its FULL local
        # state (params + opt state + PRNG) per save cadence, and the server
        # additionally persists the global model per round (the reference's
        # model.pt / received_model_{i}.pt artifacts, client.py:288 /
        # server.py:27 — which lose client opt state on restart; ours don't)
        snapshot_dir = Path(cfg.train.snapshot_dir or "snapshots")
        cfg.train.snapshot_dir = ""
    trainer = Trainer(cfg, data, token_states)
    if rt.num_processes > 1 and rt.is_server:
        # resolved config next to the snapshots for serving (fedrec-recommend
        # reads it back — same contract as Trainer's orbax path; ADVICE r2).
        # Server-only + atomic: per-process configs differ (shard_index,
        # sigma) and concurrent non-atomic writes to a shared dir could tear
        # the JSON a concurrently-running fedrec-recommend reads; serving
        # always restores the SERVER's globals, so its config is the truth
        from fedrec_tpu.train.checkpoint import atomic_write_bytes

        snapshot_dir.mkdir(parents=True, exist_ok=True)
        atomic_write_bytes(
            snapshot_dir / "config.json", cfg.to_json().encode()
        )
    if cfg.data.num_shards > 1:
        print(
            f"[coordinator] process {rt.process_id} data shard "
            f"{cfg.data.shard_index + 1}/{cfg.data.num_shards}: "
            f"{trainer.num_local_samples} samples"
        )

    codec_snap = None
    if msgpack_snapshots and rt.codec_state is not None:
        # biased-codec (sign1bit/topk) error-feedback residual: THIS
        # process's wire-endpoint EF state, persisted at save cadence so a
        # resumed run keeps carrying the mass its encodes dropped. A
        # missing/corrupt sidecar just starts the residual from zero — the
        # same bounded-staleness contract as a fresh logical client.
        codec_snap = snapshot_dir / f"codec_state_{state_suffix}.npz"
        if cfg.train.resume and codec_snap.exists():
            from fedrec_tpu.comms import load_codec_state

            try:
                rt.codec_state, ef_round = load_codec_state(
                    codec_snap.read_bytes(), trainer._client0_params()
                )
                print(
                    f"[coordinator] process {rt.process_id} resumed codec "
                    f"residual from round {ef_round}"
                )
            except Exception as e:  # noqa: BLE001 — a torn sidecar must
                # not kill the resume; dropping a residual only costs the
                # one round's banked encode error
                print(
                    f"[coordinator] process {rt.process_id} codec residual "
                    f"sidecar unreadable ({type(e).__name__}: {e}); "
                    "starting the residual from zero"
                )

    server_optimizer = None
    if msgpack_snapshots:
        from flax import serialization

        local_snap = (
            Path(args.resume_local_state)
            if args.resume_local_state
            else snapshot_dir / f"local_state_{state_suffix}.msgpack"
        )
        if cfg.train.resume and local_snap.exists():
            import time as _time

            reshard_t0 = _time.perf_counter()
            template = {"state": trainer.state, "round": 0}
            try:
                restored = serialization.from_bytes(
                    template, local_snap.read_bytes()
                )
                from fedrec_tpu.train.checkpoint import verify_state_tree

                verify_state_tree(restored["state"])
            except Exception as e:  # noqa: BLE001 — a torn/corrupt snapshot
                # must not kill the resume: this shard restarts fresh and is
                # re-integrated by the server's round negotiation + fan-out
                # (the same path a brand-new elastic host takes)
                print(
                    f"[coordinator] process {rt.process_id} local snapshot "
                    f"{local_snap.name} is corrupt/torn "
                    f"({type(e).__name__}: {e}); starting this shard fresh — "
                    "the server's fan-out re-integrates it next round"
                )
                restored = None
            if restored is not None:
                trainer.adopt_state(restored["state"])
                trainer.start_round = int(restored["round"]) + 1
                print(
                    f"[coordinator] process {rt.process_id} resumed local state "
                    f"at round {trainer.start_round - 1}"
                )
            if membership is not None:
                # epoch-boundary reshard: the restore above re-committed
                # the hand-off state to THIS epoch's mesh/world layout
                # (Trainer._place_state re-derives placement, the data
                # shards re-dealt at apply_process_sharding) — publish how
                # long the hand-off cost
                from fedrec_tpu.obs import get_registry

                get_registry().gauge(
                    "shard.reshard_seconds",
                    "wall seconds the last membership-epoch state "
                    "hand-off took (restore + re-placement)",
                ).set(_time.perf_counter() - reshard_t0)
        if membership is not None and cfg.train.resume:
            # participation-ledger continuity across epochs: the per-worker
            # population sidecar re-adopts with resize tolerance (the
            # re-formed world may deal different local data)
            pop_snap = snapshot_dir / f"population_state_{state_suffix}.msgpack"
            if pop_snap.exists() and trainer._pop_engine:
                try:
                    pop_round = trainer.adopt_population_sidecar(
                        pop_snap.read_bytes(), resize=True
                    )
                    print(
                        f"[membership] worker {args.process_id} carried its "
                        f"participation ledger from round {pop_round}"
                    )
                except Exception as e:  # noqa: BLE001 — a torn sidecar
                    # costs history, never the resume
                    print(
                        f"[membership] population sidecar unreadable "
                        f"({type(e).__name__}: {e}); ledger restarts fresh"
                    )
        if cfg.fed.server_opt != "none":
            # cross-host FedOpt is hub-and-spoke: ONLY the server holds and
            # steps the optimizer (the FedOpt paper's topology); clients
            # adopt the plain mean this round and receive the server's
            # post-opt global at the next round's fan-out. Optimizer state
            # therefore never needs to agree across hosts — a client
            # resuming from a stale snapshot cannot desync it. The per-host
            # trainer must not also step its own server optimizer on the
            # in-process mean (double application). A degraded-mode respawn
            # (single process, resume_local_state) is still a CLIENT: it
            # must not start stepping FedOpt locally either.
            trainer.server_opt = None
            if rt.is_server and rt.num_processes > 1:
                from fedrec_tpu.fed.strategies import ServerOptimizer

                server_optimizer = ServerOptimizer(
                    cfg.fed.server_opt, cfg.fed.server_lr, cfg.fed.server_momentum
                )
                opt_snap = snapshot_dir / "server_opt_state.msgpack"
                if cfg.train.resume and opt_snap.exists():
                    loaded_round = server_optimizer.load_state(
                        opt_snap.read_bytes(), trainer._client0_params()
                    )
                    if loaded_round != trainer.start_round - 1:
                        print(
                            f"[coordinator] server_opt sidecar is from round "
                            f"{loaded_round}, local snapshot from round "
                            f"{trainer.start_round - 1} — momentum may be "
                            "skewed for the first resumed round"
                        )

    def respawn_standalone() -> None:
        """Degraded CLIENT: leave the broken distributed runtime entirely.

        A degraded client cannot keep living inside the old process. Two
        failure modes were observed on a 4-process peer-kill run: (1) the
        XLA coordination client's error poller fatally terminates the
        process the moment the service (hosted by process 0, itself
        degraded and exiting) goes away; (2) the watchdog's abandoned
        collective thread stays blocked inside the runtime and holds its
        execution lock, so ANY further device op — even serializing state
        for a snapshot — deadlocks until the broken collective errors
        out. The only safe move is device-free: exec a standalone
        continuation of the same command (fresh process, no distributed
        runtime) that resumes this shard from the last SAVED snapshot.
        The round in flight when the world broke is simply re-trained
        standalone. The SERVER owns the coordination service and finishes
        degraded in-process (finalize's os._exit skips broken teardown).

        Under a supervisor (``--supervise``) the policy changes: every
        degraded process — server included — exits device-free with
        RESPAWN_EXIT so its supervisor relaunches the full distributed
        invocation; the relaunched world re-rendezvouses and resumes from
        local snapshots. The server's exit is what frees the coordination
        service address for the new world.
        """
        if rt.num_processes == 1:
            return
        if supervised:
            print(
                f"[coordinator] process {rt.process_id} world degraded "
                f"under supervision — exiting for re-rendezvous "
                f"(rc {RESPAWN_EXIT})",
                flush=True,
            )
            # obs flush is DEVICE-FREE (registry/tracer are host JSON),
            # so it is safe on the degraded path — without it, every
            # span this incarnation recorded before the world broke
            # would vanish from the fleet merge
            if trainer.fleet_pusher is not None:
                trainer.fleet_pusher.push(final=True)
            _dump_obs_artifacts()
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(RESPAWN_EXIT)
        if rt.is_server or local_snap is None:
            return
        world_flags = {"--coordinator", "--num-processes", "--process-id",
                       "--collective-timeout", "--resume-local-state"}
        keep: list[str] = []
        skip_value = False
        for tok in original_argv:
            if skip_value:
                skip_value = False
                continue
            base = tok.split("=", 1)[0]
            if base in world_flags:
                skip_value = "=" not in tok
                continue
            if base == "--server-trains":
                continue
            keep.append(tok)
        cmd = [
            sys.executable, "-m", "fedrec_tpu.cli.coordinator", *keep,
            "--resume-local-state", str(local_snap),
            "--set", f"data.num_shards={cfg.data.num_shards}",
            "--set", f"data.shard_index={cfg.data.shard_index}",
        ]
        print(
            f"[coordinator] process {rt.process_id} world degraded — "
            f"respawning standalone, resuming from "
            f"{local_snap.name if local_snap.exists() else 'scratch'}",
            flush=True,
        )
        sys.stdout.flush()
        sys.stderr.flush()
        os.execv(sys.executable, cmd)

    def _dump_obs_artifacts() -> None:
        """Flush the registry/trace into this worker's obs dir on the
        coordinator CLI's exit paths (reform + finish): unlike
        Trainer.run, this loop never writes registry snapshots itself,
        so without a final dump the membership/reshard gauges would
        never reach the artifacts `fedrec-obs report` reads.  Elastic
        workers tag the trace with their membership epoch
        (``trace_e<N>.json``) so each incarnation's spans survive the
        respawn that overwrites ``trace.json``, and persist the counter
        baseline the next incarnation resumes from."""
        if not cfg.obs.dir:
            return
        from fedrec_tpu.obs import dump_artifacts, save_counter_baseline

        try:
            dump_artifacts(
                Path(cfg.obs.dir),
                trace_tag=f"e{rt.epoch}" if membership is not None else None,
            )
            if membership is not None:
                save_counter_baseline(Path(cfg.obs.dir), epoch=rt.epoch)
        except OSError as e:
            print(f"[coordinator] obs artifact dump failed: {e}")

    def save_elastic_sidecars(round_tag: int) -> None:
        """Membership-mode extras that ride every state save: the
        per-worker population sidecar (participation-ledger continuity
        across epochs) and the one-time table checkpoint (the sharded
        catalog's row-recovery source)."""
        if membership is None:
            return
        from fedrec_tpu.train.checkpoint import (
            NEWS_TABLE_CHECKPOINT,
            atomic_write_bytes,
            save_table_checkpoint,
        )

        pop_blob = trainer.population_sidecar_bytes(round_tag)
        if pop_blob is not None:
            atomic_write_bytes(
                snapshot_dir / f"population_state_{state_suffix}.msgpack",
                pop_blob,
            )
        if cfg.shard.table and not (
            snapshot_dir / NEWS_TABLE_CHECKPOINT
        ).exists():
            save_table_checkpoint(snapshot_dir, token_states)
        if cfg.obs.dir:
            # counter-baseline continuity rides the save cadence too: a
            # worker killed BETWEEN reformations (the chaos-kill path,
            # which never reaches a clean dump) still resumes its totals
            # from the last cadence save
            from fedrec_tpu.obs.fleet import save_counter_baseline

            try:
                save_counter_baseline(Path(cfg.obs.dir), epoch=rt.epoch)
            except OSError:
                pass

    def reform_handoff(next_round: int) -> None:
        """The reformation barrier's worker half: every member received
        :data:`REFORM_SIGNAL` in the SAME round broadcast, so the whole
        world executes this at one boundary — save the full local state
        (round-tagged hand-off snapshot the next epoch resumes from,
        bit-identical for the unchanged part of the world), tear the old
        runtime down while it is still healthy, and exit with the
        retryable status so the supervisor rejoins the forming epoch."""
        print(
            f"[membership] worker {args.process_id} leaving epoch "
            f"{rt.epoch} at round boundary {next_round} for reformation",
            flush=True,
        )
        trainer.tracer.instant(
            "membership_reform", epoch=rt.epoch, round=next_round
        )
        if local_snap is not None:
            from flax import serialization
            from fedrec_tpu.train.checkpoint import atomic_write_bytes

            snapshot_dir.mkdir(parents=True, exist_ok=True)
            atomic_write_bytes(
                local_snap,
                serialization.to_bytes(
                    {"state": trainer.state, "round": next_round - 1}
                ),
            )
            if server_optimizer is not None:
                atomic_write_bytes(
                    snapshot_dir / "server_opt_state.msgpack",
                    server_optimizer.state_bytes(next_round - 1),
                )
            if codec_snap is not None:
                from fedrec_tpu.comms import codec_state_bytes

                atomic_write_bytes(
                    codec_snap, codec_state_bytes(rt.codec_state, next_round - 1)
                )
            save_elastic_sidecars(next_round - 1)
        from fedrec_tpu.parallel.membership import publish_membership_metrics

        publish_membership_metrics(reforms=1, client=membership)
        if trainer.fleet_pusher is not None:
            trainer.fleet_pusher.push(final=True)
        _dump_obs_artifacts()
        trainer.logger.finish()
        # the world is HEALTHY here (the reform broadcast just completed),
        # so the synchronized teardown applies: coordination service and
        # gloo pairs close cleanly before every member leaves
        rt._synchronized_shutdown()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(RESPAWN_EXIT)

    round_idx = trainer.start_round
    while True:
        # negotiate the round: everyone adopts the SERVER's counter (a host
        # resumed from a stale snapshot would otherwise desync batch seeds,
        # save cadence, and snapshot labels)
        server_round = rt.start_round(round_idx, cfg.fed.rounds)
        if rt.degraded:
            respawn_standalone()
        if server_round == REFORM_SIGNAL:
            reform_handoff(round_idx)
        if server_round < 0:
            break
        round_idx = server_round
        # host-level chaos fault: deterministic peer kill at round entry —
        # the surviving peers block in the next collective until their
        # watchdogs degrade them (supervised: the whole world relaunches).
        # Marker-guarded so the resumed/relaunched world doesn't re-die
        # when it re-reaches the same round.
        if (
            cfg.chaos.enabled
            and cfg.chaos.kill_round == round_idx
            # under elastic membership the kill targets the STABLE worker
            # identity (ranks re-deal every epoch)
            and cfg.chaos.kill_process == ident
        ):
            marker_dir = (
                snapshot_dir if msgpack_snapshots
                else Path(cfg.train.snapshot_dir or "snapshots")
            )
            marker_dir.mkdir(parents=True, exist_ok=True)
            marker = marker_dir / f"chaos_killed_p{ident}"
            if not marker.exists():
                marker.write_text(str(round_idx))
                print(
                    f"[chaos] process {rt.process_id} dying at round "
                    f"{round_idx} (chaos.kill_round)",
                    flush=True,
                )
                os._exit(137)
        # server fan-out: everyone adopts the global model
        u0, n0 = trainer._client0_params()
        u, n = rt.sync_from_server((u0, n0))
        if rt.degraded:
            respawn_standalone()
        trainer.set_global_params(u, n)
        round_start_global = (u, n)

        result = None
        if trains:
            # train_round_recovering: identical to train_round unless
            # fed.robust.recover, which quarantines/rolls back IN-host;
            # cross-host, a quarantined cohort still reports its (robust)
            # local aggregate — host-level exclusion is participation
            result = trainer.train_round_recovering(round_idx)

        # gather: participation weight 0 for a non-training server; with
        # fed.weight_by_samples each client counts by its shard size
        # (classic FedAvg) instead of the reference's unweighted key-wise
        # mean over unequal shards (server.py:37-55)
        u0, n0 = trainer._client0_params()
        # weigh by the TRUE local shard size (classic FedAvg n_k) — before
        # process sharding every host reported the identical global count,
        # which made the weighting degenerate
        w = float(trainer.num_local_samples) if cfg.fed.weight_by_samples else 1.0
        # round_start_global switches int8 compression to delta
        # quantization (every process holds the identical round-start
        # global from the fan-out above)
        u, n = rt.aggregate(
            (u0, n0), participated=trains, weight=w, base=round_start_global
        )
        if rt.degraded:
            # device-free exit NOW: the abandoned collective blocks any
            # further device op (incl. set_global_params below); the round
            # in flight is re-trained by the standalone continuation
            respawn_standalone()
        if server_optimizer is not None:
            # server-only (hub-and-spoke): clients adopt the plain mean this
            # round and receive the server's post-opt global at the next
            # round's fan-out
            u, n = server_optimizer.step(round_start_global, (u, n))
        trainer.set_global_params(u, n)

        # the coordinator loop completes rounds OUTSIDE Trainer.run, so
        # the rounds counter advances here — Trainer._after_round (its
        # only other inc site) never runs in this deployment, which left
        # coordinator workers' round totals frozen at zero
        trainer.registry.counter("train.rounds_total").inc()
        if result is not None:
            log = {"round": round_idx, "training_loss": result.train_loss}
            log.update(result.val_metrics)
            trainer.logger.log(round_idx, log)
        if (round_idx + 1) % cfg.train.save_every == 0:
            if trainer.snapshots is not None:
                # blocking under FedOpt so the sidecar never outruns the
                # orbax snapshot it pairs with (see Trainer.run)
                trainer.snapshots.save(
                    round_idx, trainer.state, wait=trainer.server_opt is not None
                )
                if trainer.server_opt is not None:
                    from fedrec_tpu.train.checkpoint import atomic_write_bytes

                    atomic_write_bytes(
                        trainer.snapshots.directory / "server_opt_state.msgpack",
                        trainer.server_opt.state_bytes(round_idx),
                    )
            elif local_snap is not None:
                from flax import serialization

                from fedrec_tpu.train.checkpoint import (
                    atomic_write_bytes,
                    coordinator_globals,
                )

                snapshot_dir.mkdir(parents=True, exist_ok=True)
                # atomic writes: a concurrently-running fedrec-recommend
                # must never read a torn snapshot
                atomic_write_bytes(
                    local_snap,
                    serialization.to_bytes(
                        {"state": trainer.state, "round": round_idx}
                    ),
                )
                if (
                    cfg.chaos.enabled
                    and cfg.chaos.torn_snapshot_round == round_idx
                ):
                    # host-level chaos fault: simulate a crash mid-write by
                    # truncating the snapshot we just wrote — the resume
                    # path must survive it (fresh shard + server fan-out)
                    blob = local_snap.read_bytes()
                    local_snap.write_bytes(blob[: max(len(blob) // 2, 1)])
                    print(
                        f"[chaos] process {rt.process_id} tore its local "
                        f"snapshot at round {round_idx}",
                        flush=True,
                    )
                if server_optimizer is not None:
                    # server-only state (hub-and-spoke FedOpt), round-tagged
                    atomic_write_bytes(
                        snapshot_dir / "server_opt_state.msgpack",
                        server_optimizer.state_bytes(round_idx),
                    )
                if codec_snap is not None:
                    # per-process EF residual rides the save cadence next
                    # to the local state it pairs with
                    from fedrec_tpu.comms import codec_state_bytes

                    atomic_write_bytes(
                        codec_snap,
                        codec_state_bytes(rt.codec_state, round_idx),
                    )
                save_elastic_sidecars(round_idx)
                if rt.is_server and rt.num_processes > 1:
                    # a degraded-mode respawn (single process) is a CLIENT
                    # continuation — its params are NOT the global model
                    atomic_write_bytes(
                        snapshot_dir / f"global_round_{round_idx}.msgpack",
                        serialization.to_bytes(
                            {"user": u, "news": n, "round": round_idx}
                        ),
                    )
                    # retention: mirror orbax's max_to_keep=3 — the reference
                    # leaves received_model_{i}.pt files piling up forever
                    # (server.py:27)
                    for old in coordinator_globals(snapshot_dir)[:-3]:
                        old.unlink(missing_ok=True)
        if trainer.fleet_pusher is not None:
            # the coordinator loop drives rounds itself (Trainer._after_round
            # never runs here), so the round-cadence telemetry push lands at
            # this boundary instead
            trainer.fleet_pusher.maybe_push(round_idx)
        round_idx += 1

    print(f"[coordinator] process {rt.process_id} done after {round_idx} rounds")
    if trainer.snapshots is not None:
        trainer.snapshots.wait()  # settle async saves before any exit path
    if membership is not None:
        # a finished run LEAVES (no lease to expire, no reform): the
        # service's final status must read completion, not death
        from fedrec_tpu.parallel.membership import publish_membership_metrics

        publish_membership_metrics(client=membership)
        membership.leave()
        membership.close()
    if trainer.fleet_pusher is not None:
        trainer.fleet_pusher.push(final=True)
    _dump_obs_artifacts()
    trainer.logger.finish()  # before finalize: os._exit skips teardown
    rt.finalize(0)  # no-op unless the world broke mid-run (then exits here)
    return 0


if __name__ == "__main__":
    sys.exit(main())
