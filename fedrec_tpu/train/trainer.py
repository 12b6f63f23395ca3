"""The single Trainer — ends the reference's 4-way copy-paste.

The reference duplicates its Trainer + train step + grad processors across
``main.py:95-139``, ``Gradient_Averaging_main.py:96-149``,
``Parameter_Averaging_main.py:96-151`` and ``client.py:105-189`` with small
diffs (SURVEY.md section 1, "Key structural fact"). Here one Trainer drives
every mode; the differences are a ``FedStrategy`` object and config flags.

Round structure (generalizes all reference drivers):

  for round in rounds:                      # server.py:72 round loop
      draw participation mask               # fixes Final_Report VII.a dropout
      for local_epoch in local_epochs:      # client local training
          for batch in sharded batches:     # jitted SPMD step, ICI collectives
              step()
          if decoupled: news_update()       # model.py:66-90 update() parity
      if strategy.sync_params_every_round:
          param_sync(mask)                  # Parameter_Averaging_main.py:144-148
      evaluate(); log; snapshot every save_every  # main.py:138-139
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.data.batcher import IndexedSamples, TrainBatcher, index_samples
from fedrec_tpu.data.mind import MindData
from fedrec_tpu.data.prefetch import maybe_prefetch
from fedrec_tpu.fed.strategies import get_strategy
from fedrec_tpu.models import NewsRecommender
from fedrec_tpu.parallel.mesh import (
    client_sharding,
    fed_mesh,
    shard_fed_batch,
)
from fedrec_tpu.train.checkpoint import SnapshotManager
from fedrec_tpu.train.state import init_stacked_state
from fedrec_tpu.train.step import (
    batch_host_dedup,
    build_eval_step,
    build_fed_train_step,
    build_full_eval_step,
    build_full_eval_step_sharded,
    build_news_update_step,
    build_param_sync,
    compressed_sync_active,
    build_corpus_encode,
    commit_token_table,
    encode_rows_for,
    host_news_dedup,
    most_distinct_news,
)
from fedrec_tpu.obs import (
    CompileWatchdog,
    FlightRecorder,
    HealthMonitor,
    TrainingHealthError,
    dump_artifacts,
    get_registry,
    get_tracer,
    rotate_jsonl,
    sample_device_memory,
)
from fedrec_tpu.obs.tracing import RoundDigest
from fedrec_tpu.utils.logging import MetricLogger
from fedrec_tpu.utils.profiling import profile_if


@dataclass
class RoundResult:
    round_idx: int
    train_loss: float
    val_metrics: dict[str, float] = field(default_factory=dict)


class RoundRecovery(Exception):
    """Internal control flow for quarantine-and-rollback recovery
    (``fed.robust.recover``): raised by the round-end health check instead
    of the hard abort, caught by ``Trainer.run``, which quarantines the
    offending client, restores the round-entry state, and replays."""

    def __init__(self, trigger: dict):
        super().__init__(
            f"recoverable health trigger [{trigger.get('kind')}] "
            f"client {trigger.get('client')} round {trigger.get('round')}"
        )
        self.trigger = trigger


class Trainer:
    """Federated trainer over a clients mesh.

    ``token_states``: (N_news, L, bert_hidden) cached frozen-trunk token
    states (see ``fedrec_tpu.models.bert`` for producing them from a real
    DistilBERT, or pass synthetic states for smoke runs).
    """

    def __init__(
        self,
        cfg: ExperimentConfig,
        data: MindData,
        token_states: np.ndarray,
        snapshot_dir: str | None = None,
        mesh: Any = None,
    ):
        self.cfg = cfg
        self.data = data
        self.model = NewsRecommender(cfg.model)
        self.strategy = get_strategy(cfg.fed.strategy)
        # ---- robustness (fed.robust + chaos): validate up front — a robust
        # method or recovery mode that would silently never apply is a
        # misconfiguration, not a preference (same policy as server_opt)
        from fedrec_tpu.fed.robust import validate_robust_method

        rb = cfg.fed.robust
        validate_robust_method(rb.method)
        if rb.method != "mean" and not self.strategy.sync_params_every_round:
            raise ValueError(
                f"fed.robust.method={rb.method!r} requires a strategy that "
                "syncs params every round (param_avg or coordinator); "
                f"fed.strategy={cfg.fed.strategy!r} never aggregates params, "
                "so the robust aggregator would silently never run"
            )
        if rb.recover:
            if not self.strategy.sync_params_every_round:
                raise ValueError(
                    "fed.robust.recover=true requires a param-syncing "
                    "strategy (param_avg or coordinator): quarantine works "
                    "by zeroing the client's aggregation weight"
                )
            if not cfg.obs.health.sentry:
                raise ValueError(
                    "fed.robust.recover=true requires obs.health.sentry: "
                    "recovery is driven by the in-graph health vectors"
                )
        # ---- update-compression codec (fed.dcn_compress, fedrec_tpu.comms):
        # validated up front like robust/server_opt — a codec that would
        # silently never run is a misconfiguration, not a preference.
        # "auto" is the adaptive per-leaf mode: a concrete codec per leaf
        # is pinned from warmup telemetry (see _pin_auto_codec_map).
        from fedrec_tpu.comms import codec_caps, validate_codec

        if cfg.fed.dcn_compress != "auto":
            validate_codec(cfg.fed.dcn_compress)
        if (
            cfg.fed.dcn_compress != "none"
            and not self.strategy.sync_params_every_round
        ):
            raise ValueError(
                f"fed.dcn_compress={cfg.fed.dcn_compress!r} requires a "
                "strategy that syncs params every round (param_avg or "
                f"coordinator); fed.strategy={cfg.fed.strategy!r} never "
                "ships a round update, so the codec would silently never "
                "run (per-step grad_avg traffic is not compressed)"
            )
        if cfg.shard.fsdp > 1 and cfg.fed.dcn_compress == "topk":
            raise ValueError(
                "fed.dcn_compress='topk' with shard.fsdp>1 is not "
                "supported: the per-tensor top-k selection materializes "
                "every gathered dense delta at the sync boundary, exactly "
                "the full-size residency shard.fsdp exists to avoid — use "
                "int8/sign1bit or shard.fsdp=1"
            )
        if (
            rb.method != "mean"
            and cfg.fed.dcn_compress not in ("none", "auto")
            and not codec_caps(cfg.fed.dcn_compress).decodes_per_contribution
        ):
            raise ValueError(
                f"fed.robust.method={rb.method!r} needs per-contribution "
                f"decode, which codec {cfg.fed.dcn_compress!r} cannot "
                "provide (its contributions only exist pre-aggregated: "
                "order statistics judge CLIENTS, and sketch collisions mix "
                "every client's coordinates before any decode exists); use "
                "one of the decodable codecs (int8/sign1bit/topk) or "
                "fed.robust.method='mean'"
            )
        if cfg.fed.dcn_compress == "auto":
            if rb.method != "mean":
                raise ValueError(
                    "fed.dcn_compress='auto' requires "
                    "fed.robust.method='mean': the pinned per-leaf map may "
                    "select a linear sketch, whose contributions only exist "
                    "pre-aggregated (no per-contribution decode for order "
                    "statistics)"
                )
            if cfg.fed.dcn_auto_warmup < 1:
                raise ValueError(
                    f"fed.dcn_auto_warmup={cfg.fed.dcn_auto_warmup} must "
                    "be >= 1: the per-leaf map derives from at least one "
                    "observed round delta"
                )
            if cfg.shard.fsdp > 1:
                raise ValueError(
                    "fed.dcn_compress='auto' with shard.fsdp>1 is not "
                    "supported: the pinned map may select 'topk', which "
                    "materializes every gathered dense delta at the sync "
                    "boundary — pin a concrete fsdp-safe codec "
                    "(int8/sign1bit/countsketch/randproj) instead"
                )
        # ---- aggregation topology (agg.*, fedrec_tpu.agg): validated up
        # front like robust/codec — a mode that would silently never apply
        # is a misconfiguration, not a preference
        if cfg.agg.mode not in ("flat", "hierarchical", "async"):
            raise ValueError(
                f"unknown agg.mode {cfg.agg.mode!r}; expected 'flat', "
                "'hierarchical', or 'async'"
            )
        if cfg.agg.tree_fanout < 2:
            raise ValueError(
                f"agg.tree_fanout={cfg.agg.tree_fanout} must be >= 2"
            )
        if cfg.agg.staleness_cap < 0:
            raise ValueError(
                f"agg.staleness_cap={cfg.agg.staleness_cap} must be >= 0"
            )
        if cfg.agg.quorum < 0 or cfg.agg.quorum > cfg.fed.num_clients:
            raise ValueError(
                f"agg.quorum={cfg.agg.quorum} must be in "
                f"[0, fed.num_clients={cfg.fed.num_clients}] "
                "(0 = all-reporting)"
            )
        if cfg.agg.mode != "flat" and not self.strategy.sync_params_every_round:
            raise ValueError(
                f"agg.mode={cfg.agg.mode!r} requires a strategy that syncs "
                "params every round (param_avg or coordinator); "
                f"fed.strategy={cfg.fed.strategy!r} never aggregates, so "
                "the aggregation topology would silently never apply"
            )
        if cfg.agg.mode == "async":
            if cfg.fed.dcn_compress == "auto":
                raise ValueError(
                    "agg.mode='async' is incompatible with "
                    "fed.dcn_compress='auto': buffered entries may outlive "
                    "the warmup window, so the per-leaf map could change "
                    "between a push and its fold — pin a concrete codec "
                    "(every registered codec composes: linear sketches "
                    "fold in sketch space, per-contribution codecs decode "
                    "at push time with per-edge error feedback)"
                )
            # every CONCRETE codec composes with the buffered commit —
            # the capability table says how: is_linear folds in sketch
            # space under the same staleness weights; otherwise
            # decodes_per_contribution decodes at push time (per-edge EF
            # residuals ride the buffer sidecar)
        # the host-side tiered reduce only engages for NON-linear robust
        # methods: a tree of (sum(w*x), sum(w)) partials with one final
        # divide IS the flat weighted mean algebraically, so
        # hierarchical+mean lowers to the unchanged in-graph collective
        # and stays bit-identical by construction (tests/test_agg.py)
        self._agg_async = cfg.agg.mode == "async"
        self._agg_hier_host = (
            cfg.agg.mode == "hierarchical" and rb.method != "mean"
        )
        self._agg_version = 0
        self.agg_buffer = None
        if self._agg_async:
            from fedrec_tpu.agg import AggBuffer, CommitPolicy

            self.agg_buffer = AggBuffer()
            self._agg_policy = CommitPolicy(
                quorum=cfg.agg.quorum, staleness_cap=cfg.agg.staleness_cap
            )
        self.chaos = None
        if cfg.chaos.enabled:
            from fedrec_tpu.fed.chaos import FaultPlan

            self.chaos = FaultPlan(cfg.chaos, cfg.fed.num_clients)
        # quarantine ledger: client -> rounds left excluded; retries count
        # rollback/replay attempts for the CURRENT round (reset on advance)
        self._quarantine: dict[int, int] = {}
        self._round_retries = 0
        self._recovery_state = None
        self._recovery_opt_state = None
        self.server_opt = None
        if cfg.fed.server_opt != "none":
            if not self.strategy.sync_params_every_round:
                # fail fast (ADVICE r2, mirroring validate_compress): the
                # server optimizer steps round deltas at param-sync time, so
                # under local/grad_avg a requested FedAdam would silently
                # never run
                raise ValueError(
                    f"fed.server_opt={cfg.fed.server_opt!r} requires a "
                    "strategy that syncs params every round (param_avg or "
                    f"coordinator); fed.strategy={cfg.fed.strategy!r} never "
                    "would apply it"
                )
            from fedrec_tpu.fed.strategies import ServerOptimizer

            self.server_opt = ServerOptimizer(
                cfg.fed.server_opt, cfg.fed.server_lr, cfg.fed.server_momentum
            )
        # ``mesh`` overrides the layout fed_mesh derives from the config and
        # the visible devices (e.g. the same clients packed onto fewer chips)
        self.mesh = fed_mesh(cfg) if mesh is None else mesh
        self.mode = {"table": "decoupled", "head": "joint", "finetune": "finetune"}.get(
            cfg.model.text_encoder_mode, "joint"
        )
        if cfg.train.eval_protocol not in ("sampled", "full", "last4"):
            raise ValueError(
                f"unknown train.eval_protocol {cfg.train.eval_protocol!r}; "
                "expected 'sampled', 'full', or 'last4'"
            )

        self.text_encoder = None
        self.news_tokens: jnp.ndarray | None = None
        if self.mode == "finetune":
            # in-loop trunk training reads raw token rows, not cached states
            from fedrec_tpu.models.bert import make_text_encoder

            self.text_encoder = make_text_encoder(cfg.model)
            self.news_tokens = jnp.asarray(data.news_tokens, jnp.int32)
            self.token_states = None
        else:
            self.token_states = jnp.asarray(
                token_states, dtype=jnp.dtype(cfg.model.dtype)
            )

        # ---- sharding subsystem (fedrec_tpu.shard, docs/DESIGN.md §5i):
        # (1) shard.table — the token-state catalog row-sharded over the
        # client mesh axis; steps gather via the owner-bucketed all_to_all
        # exchange, so catalog capacity scales with devices. (2) shard.fsdp
        # — at-rest client state (params + optimizer moments + accumulators)
        # sharded across the fsdp mesh axis per the size-aware policy,
        # derived from the ABSTRACT state via jax.eval_shape so placement
        # is known before any builder compiles. Both default off, and off
        # means the byte-identical pre-shard programs.
        self.table_spec = None
        if cfg.shard.table:
            from fedrec_tpu.shard.table import ShardedNewsTable, TableSpec

            if self.token_states is not None:
                tab = ShardedNewsTable.create(
                    self.token_states, self.mesh, cfg.fed.mesh_axis
                )
                self.token_states = tab.rows
                self.table_spec = tab.spec
            else:
                # finetune mode holds a token table, not cached states; the
                # step builder below fails fast on the mode — this spec
                # exists only to reach that guard
                n = int(self.news_tokens.shape[0])
                s = int(self.mesh.shape[cfg.fed.mesh_axis])
                self.table_spec = TableSpec(
                    cfg.fed.mesh_axis, s, -(-n // s), n
                )
        if self.token_states is not None:
            # the steps read the table replicated (in_spec P()), or its row
            # blocks under shard.table (P(axis)), in the layout their gather
            # reads: commit it to every device ONCE, or each dispatch copies
            # it from device 0 and each step rewrites all of it
            self.token_states = self._commit_token_states(self.token_states)
        self._state_shardings = None
        if cfg.shard.fsdp > 1:
            from fedrec_tpu.shard.policy import fsdp_state_shardings

            abstract_state = jax.eval_shape(
                lambda: init_stacked_state(
                    self.model, cfg, jax.random.PRNGKey(cfg.train.seed),
                    jax.random.PRNGKey(cfg.train.seed + 1),
                    data.num_news, data.title_len,
                )
            )
            self._state_shardings = fsdp_state_shardings(
                abstract_state, self.mesh, cfg
            )

        train_ix = index_samples(data.train_samples, data.nid2index, cfg.data.max_his_len)
        if cfg.data.num_shards > 1:
            # coordinator deployment: this process trains only its disjoint
            # shard (reference DistributedSampler-by-rank, main.py:166)
            from fedrec_tpu.data.batcher import process_shard_indices

            train_ix = train_ix.take(
                process_shard_indices(
                    len(train_ix), cfg.data.num_shards,
                    cfg.data.shard_index, cfg.data.seed,
                )
            )
        # true local sample count — what fed.weight_by_samples must weigh
        self.num_local_samples = len(train_ix)
        batcher_cls = TrainBatcher
        if cfg.data.native_loader:
            from fedrec_tpu.data import native_batcher

            if native_batcher.is_available():
                batcher_cls = native_batcher.NativeTrainBatcher
            else:
                print("[trainer] native loader unavailable; using Python batcher")
        self.batcher = batcher_cls(
            train_ix,
            cfg.data.batch_size,
            cfg.data.npratio,
            shuffle=cfg.data.shuffle,
            drop_remainder=cfg.data.drop_remainder,
            seed=cfg.data.seed,
        )
        self.valid_ix: IndexedSamples | None = None
        if data.valid_samples:
            self.valid_ix = index_samples(
                data.valid_samples, data.nid2index, cfg.data.max_his_len
            )
        self.train_ix = train_ix  # the population's shard substrate

        # ---- cross-device cohort engine (fed.population): logical-client
        # population sampled onto the fixed device slots each round.
        # _pop_engine: any population config (bookkeeping + quorum/deadline);
        # _pop_sampling: population STRICTLY above the slot count — real
        # per-round sampling with per-client data shards and sidecar
        # load/unload. population == slots is the degenerate (cross-silo)
        # config: identity cohorts, the legacy data path, bit-identical
        # trajectory (tests/test_population.py).
        from pathlib import Path as _Path

        pcfg = cfg.fed.population
        self._pop_engine = pcfg.num_clients > 0
        self._pop_sampling = pcfg.num_clients > cfg.fed.num_clients
        self.population = None
        self.cohort_sampler = None
        self._current_plan = None
        self._pop_pending: dict[int, tuple] = {}
        self._pop_attempts: dict[int, int] = {}
        self.cohort_history: list[tuple[int, tuple]] = []
        self._slot_occupants = np.arange(cfg.fed.num_clients, dtype=np.int64)
        self._slot_writeback = np.ones(cfg.fed.num_clients, bool)
        self._recovery_occupants = None
        self._pop_template = None
        if self._pop_engine:
            from fedrec_tpu.fed.population import ClientPopulation
            from fedrec_tpu.fed.sampling import (
                CohortSampler,
                validate_sampler_mode,
            )

            validate_sampler_mode(pcfg.sampler)
            if pcfg.num_clients < cfg.fed.num_clients:
                raise ValueError(
                    f"fed.population.num_clients={pcfg.num_clients} is below "
                    f"the device-slot count fed.num_clients="
                    f"{cfg.fed.num_clients}; the population must cover every "
                    "slot (== slots is the degenerate cross-silo config)"
                )
            if pcfg.over_select < 1.0:
                raise ValueError(
                    f"fed.population.over_select={pcfg.over_select} must be "
                    ">= 1.0 (1.0 = no over-selection)"
                )
            if pcfg.client_state not in ("persist", "reset"):
                raise ValueError(
                    f"fed.population.client_state={pcfg.client_state!r}; "
                    "expected 'persist' or 'reset'"
                )
            if pcfg.min_reports > cfg.fed.num_clients:
                raise ValueError(
                    f"fed.population.min_reports={pcfg.min_reports} exceeds "
                    f"the slot count {cfg.fed.num_clients}: the quorum could "
                    "never be met"
                )
            if self._pop_sampling:
                if not self.strategy.sync_params_every_round:
                    raise ValueError(
                        "fed.population sampling (num_clients above the slot "
                        "count) requires a param-syncing strategy (param_avg "
                        "or coordinator): sampled-in clients adopt the "
                        f"global at round end; fed.strategy="
                        f"{cfg.fed.strategy!r} never distributes one"
                    )
                if cfg.fed.participation < 1.0:
                    raise ValueError(
                        "fed.participation < 1.0 composes with the FIXED "
                        "cohort only; under fed.population sampling the "
                        "cohort draw IS the participation policy — leave "
                        "fed.participation at 1.0"
                    )
            spill = pcfg.spill_dir or None
            if not spill:
                snap = snapshot_dir or cfg.train.snapshot_dir
                spill = str(_Path(snap) / "popspill") if snap else None
            self.population = ClientPopulation(
                pcfg.num_clients,
                len(train_ix),
                data_seed=cfg.data.seed,
                batch_size=cfg.data.batch_size if self._pop_sampling else 0,
                resident_cap=pcfg.resident_cap,
                spill_dir=spill,
            )
            self.cohort_sampler = CohortSampler(
                pcfg.num_clients,
                pcfg.sampler,
                pcfg.seed,
                sample_counts=self.population.sample_counts,
            )

        # jitted programs. Batch-buffer donation (train.donate_batch) is
        # safe HERE because every dispatch device_puts fresh arrays; the
        # builders default it off for direct callers that reuse batches.
        self.train_step = build_fed_train_step(
            self.model, cfg, self.strategy, self.mesh, mode=self.mode,
            donate_batch=cfg.train.donate_batch,
            sharded_table=self.table_spec,
            state_shardings=self._state_shardings,
        )
        # The joint step's dedup runs on the host (train/step.py:
        # host_news_dedup): only the host holds a step's ids before it
        # dispatches. The encode size
        # R is chosen from the first round's own batches before the first
        # dispatch (_choose_encode_rows): None until then, and for good on
        # the paths whose step dedups on the device at the slot count.
        self._host_dedup = (
            self.mode == "joint"
            and cfg.fed.seq_shards <= 1
            and not (cfg.privacy.enabled and cfg.privacy.mechanism == "dpsgd")
        )
        self._encode_rows: int | None = None
        self._encode_full = 0  # min(B*(C+H), N), which every count fits
        self.news_update = build_news_update_step(
            self.model, cfg, self.mesh, self.strategy,
            state_shardings=self._state_shardings,
        )
        # cached-trunk corpus encode (decoupled table refresh, evaluation,
        # the serving export): reads the committed table where it lies
        self._corpus_encode = build_corpus_encode(
            self.model, self.mesh, self._token_table_spec()
        )
        # fed.dcn_compress="auto": until the warmup window pins the real
        # map, the codec-sync body runs with an all-"none" map (dense sync
        # through the codec program SHAPE, so the pin only swaps leaf
        # constants, never the calling convention) — _make_local_sync
        # derives that warmup default from codec="auto" + leaf_codecs=None
        self.param_sync = build_param_sync(
            cfg, self.mesh, self.strategy,
            state_shardings=self._state_shardings,
        )
        # codec syncs take the round-ENTRY params (the delta base) as extra
        # args — captured per round before the first buffer-donating step
        self._sync_takes_entry = compressed_sync_active(cfg, self.strategy)
        self.eval_step = build_eval_step(self.model, cfg)
        # full-pool eval sharded over the mesh when there is one: same
        # per-impression math, 1/mesh.size of the eval wall time (the
        # full-pool pass is the eval bottleneck at MIND scale)
        self.full_eval_step = (
            build_full_eval_step_sharded(self.model, cfg, self.mesh)
            if self.mesh.size > 1
            else build_full_eval_step(self.model, cfg)
        )
        # quality-instrumented twin (obs.quality.enabled): same scoring
        # math plus fixed-shape score/calibration partial sums. A separate
        # compiled program so the DISABLED path keeps the exact pre-quality
        # program (byte-identical trajectories, tests/test_quality.py).
        self.full_eval_step_q = None
        if cfg.obs.quality.enabled:
            qspec = (
                int(cfg.obs.quality.score_bins),
                float(cfg.obs.quality.score_range),
                int(cfg.obs.quality.ece_bins),
            )
            self.full_eval_step_q = (
                build_full_eval_step_sharded(
                    self.model, cfg, self.mesh, quality=qspec
                )
                if self.mesh.size > 1
                else build_full_eval_step(self.model, cfg, quality=qspec)
            )

        # state (pre-sharded so the first step doesn't retrace)
        stacked = init_stacked_state(
            self.model,
            cfg,
            jax.random.PRNGKey(cfg.train.seed),
            jax.random.PRNGKey(cfg.train.seed + 1),
            data.num_news,
            data.title_len,
        )
        self.state = self._place_state(stacked)
        del stacked
        if self._pop_engine:
            # the pristine sidecar template a never-before-selected (or
            # quarantine-healed) logical client starts from: slot 0's
            # freshly-initialized non-param leaves, captured BEFORE any
            # restore/training touches the state (rng is re-derived per
            # client in _template_sidecar)
            from fedrec_tpu.fed.population import SIDECAR_FIELDS

            host0 = jax.tree_util.tree_map(np.asarray, self.state)
            self._pop_template = {
                f: jax.tree_util.tree_map(
                    lambda x: np.array(x[0]), getattr(host0, f)
                )
                for f in SIDECAR_FIELDS
            }

        self.start_round = 0
        self.snapshots: SnapshotManager | None = None
        if snapshot_dir or cfg.train.snapshot_dir:
            self.snapshots = SnapshotManager(snapshot_dir or cfg.train.snapshot_dir)
            if cfg.train.resume and self.snapshots.latest_round() is not None:
                # validate BEFORE the current cfg is persisted below — the
                # incumbent config.json is the record of what the snapshot
                # was trained with, and must be read before being replaced
                self._check_snapshot_config(cfg)
                try:
                    self.state = self.snapshots.restore(self.state)
                except Exception as e:
                    # the raw orbax tree-structure error names pytree paths,
                    # not the config knob that caused them (ADVICE r3) —
                    # name the likely culprits
                    raise RuntimeError(
                        f"snapshot restore from {self.snapshots.directory} "
                        f"failed ({type(e).__name__}; chained below). If the "
                        "error names pytree paths/shapes, the usual cause is "
                        "a model-config change since the snapshot was "
                        "written (model.user_tower picks a different "
                        "parameter family; news_dim/num_heads/trunk_* change "
                        "shapes) — compare the snapshot's config.json with "
                        "this run's --set flags. Otherwise the checkpoint "
                        "itself may be incomplete or corrupt; point "
                        "train.snapshot_dir at a fresh directory to start "
                        "over."
                    ) from e
                # re-commit to the at-rest layout: a snapshot gathered to
                # host on save (shard.fsdp) must land back sharded
                self.state = self._place_state(self.state)
                # last_restored_round, not latest_round(): a corrupt newest
                # snapshot falls back to the previous retained one, and the
                # resumed counter must match the state that actually loaded
                restored = self.snapshots.last_restored_round
                if restored is None:
                    restored = int(self.snapshots.latest_round())
                self.start_round = int(restored) + 1
                print(f"[trainer] resumed from snapshot at round {self.start_round - 1}")
                if self.server_opt is not None:
                    # FedOpt buffers live host-side; restore the sidecar so
                    # a resumed run is bit-identical to an uninterrupted one
                    sidecar = self.snapshots.directory / "server_opt_state.msgpack"
                    if not sidecar.exists():
                        print(
                            "[trainer] WARNING: resuming a fed.server_opt run "
                            f"without {sidecar.name} — momentum/adaptivity "
                            "buffers restart from zero, so the resumed "
                            "trajectory will differ from an uninterrupted one"
                        )
                    if sidecar.exists():
                        loaded_round = self.server_opt.load_state(
                            sidecar.read_bytes(), self._client0_params()
                        )
                        if loaded_round != self.start_round - 1:
                            print(
                                f"[trainer] server_opt sidecar from round "
                                f"{loaded_round} != snapshot round "
                                f"{self.start_round - 1}; momentum may be "
                                "skewed for the first resumed round"
                            )
                if self._pop_engine:
                    # the cohort engine's schedule-defining state: sampler
                    # fairness counters + participation ledger + slot
                    # occupancy — restoring it makes rounds r+1.. sample
                    # IDENTICAL cohorts to an uninterrupted run
                    from fedrec_tpu.train.checkpoint import (
                        POPULATION_SIDECAR,
                        load_population_state,
                    )

                    pop_sidecar = self.snapshots.directory / POPULATION_SIDECAR
                    if pop_sidecar.exists():
                        pst = load_population_state(pop_sidecar.read_bytes())
                        self.cohort_sampler.load_state_dict(pst["sampler"])
                        self.population.ledger.load_state_dict(pst["ledger"])
                        self._slot_occupants = np.asarray(
                            pst["slot_occupants"], np.int64
                        )
                        self._slot_writeback = np.asarray(
                            pst["slot_writeback"], bool
                        )
                        if pst["round"] != self.start_round - 1:
                            print(
                                f"[trainer] population sidecar from round "
                                f"{pst['round']} != snapshot round "
                                f"{self.start_round - 1}; the cohort "
                                "schedule may be skewed for the first "
                                "resumed rounds"
                            )
                    elif self._pop_sampling:
                        print(
                            "[trainer] WARNING: resuming a fed.population "
                            f"run without {POPULATION_SIDECAR} — the "
                            "sampler/ledger restart fresh, so the resumed "
                            "cohort schedule will differ from an "
                            "uninterrupted run"
                        )
                if self._agg_async:
                    # pending late contributions survive the restart; a
                    # missing/foreign/mismatched sidecar starts empty
                    # (late updates are droppable by design — the commit
                    # version still resumes so staleness stays coherent)
                    from fedrec_tpu.agg.buffer import (
                        AGG_BUFFER_SIDECAR,
                        AggBuffer,
                    )

                    agg_sidecar = self.snapshots.directory / AGG_BUFFER_SIDECAR
                    if agg_sidecar.exists():
                        try:
                            buf, tag, ver = AggBuffer.load_state(
                                agg_sidecar.read_bytes()
                            )
                        except ValueError as e:
                            print(
                                "[trainer] ignoring unreadable agg-buffer "
                                f"sidecar: {e}"
                            )
                        else:
                            self._agg_version = ver
                            if tag == self.start_round - 1:
                                self.agg_buffer = buf
                                if len(buf):
                                    print(
                                        f"[trainer] restored {len(buf)} "
                                        "pending async contribution(s) at "
                                        f"commit version {ver}"
                                    )
                            else:
                                print(
                                    "[trainer] agg-buffer sidecar from round "
                                    f"{tag} != snapshot round "
                                    f"{self.start_round - 1}; starting with "
                                    "an empty buffer (pending late updates "
                                    "dropped)"
                                )
                    else:
                        print(
                            "[trainer] resuming an agg.mode=async run "
                            f"without {AGG_BUFFER_SIDECAR} — pending late "
                            "contributions (if any) are lost and the commit "
                            "version restarts"
                        )
            try:
                # resolved config rides with the snapshots so serving can
                # rebuild the exact model without the operator re-typing
                # every --set (fedrec-recommend reads it back; ADVICE r2).
                # Atomic: a concurrently-serving fedrec-recommend must never
                # read a torn file. Written AFTER the resume path above so
                # the incumbent config.json — the record of what an existing
                # snapshot was trained with — is validated before replacement
                from fedrec_tpu.train.checkpoint import atomic_write_bytes

                atomic_write_bytes(
                    self.snapshots.directory / "config.json",
                    cfg.to_json().encode(),
                )
            except OSError as e:
                print(f"[trainer] could not persist config.json: {e}")

        self.best_snapshots: SnapshotManager | None = None
        self._best_auc: float | None = None
        if self.snapshots is not None and cfg.train.keep_best:
            import json as _json

            best_dir = self.snapshots.directory / "best"
            self.best_snapshots = SnapshotManager(best_dir, max_to_keep=1)
            marker = best_dir / "best.json"
            if marker.exists():
                # resumed run: the incumbent best must never be replaced
                # by a worse later round
                try:
                    m = _json.loads(marker.read_text())
                    best_round, best_auc = int(m["round"]), float(m["auc"])
                except (OSError, ValueError, KeyError, TypeError):
                    best_round = best_auc = None
                stored = self.best_snapshots.latest_round()
                if best_round is not None and stored == best_round:
                    self._best_auc = best_auc
                elif stored is not None or best_round is not None:
                    # torn state (crash between the snapshot save and the
                    # marker write): the stored snapshot's AUC is unknown,
                    # so let the next improvement rewrite both coherently
                    print(
                        "[trainer] best-snapshot marker/round mismatch "
                        f"(marker {best_round}, stored {stored}); best-AUC "
                        "tracking restarts this run"
                    )

        # ---- observability (fedrec_tpu.obs): registry instruments, host
        # spans, and the obs.dir artifact trio (metrics.jsonl / trace.json /
        # prometheus.txt). The registry/tracer always record in memory;
        # files only when obs.dir is set.
        from pathlib import Path

        self._obs_dir: Path | None = None
        jsonl_path = None
        if cfg.obs.dir:
            self._obs_dir = Path(cfg.obs.dir)
            self._obs_dir.mkdir(parents=True, exist_ok=True)
            jsonl_path = str(self._obs_dir / "metrics.jsonl")
        self.registry = get_registry()
        self.tracer = get_tracer()
        self.tracer.capacity = cfg.obs.trace_capacity
        # fleet correlation keys (fedrec_tpu.obs.fleet): every span,
        # registry snapshot and MetricLogger record carries worker/rank
        # labels so multi-process artifacts are joinable — the
        # coordinator CLI stamps the stable elastic identity first and
        # this is then a no-op
        from fedrec_tpu.obs.fleet import ensure_fleet_identity

        ensure_fleet_identity(
            worker=str(jax.process_index()), rank=jax.process_index()
        )
        # wire-layer observability (obs.wire): envelope on/off + offset
        # window for every TCP exchange this process makes
        from fedrec_tpu.obs.wire import configure_wire

        configure_wire(
            enabled=cfg.obs.wire.enabled, window=cfg.obs.wire.window
        )
        self._m_rounds = self.registry.counter(
            "train.rounds_total", "federated rounds completed"
        )
        self._m_steps = self.registry.counter(
            "train.steps_total", "train-step batches dispatched"
        )
        self._m_round_loss = self.registry.gauge(
            "train.round_loss", "mean train loss of the last round"
        )
        self._m_round_secs = self.registry.histogram(
            "train.round_seconds", "wall seconds per federated round",
            buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                     100.0, 250.0, 500.0, 1000.0),
        )
        # the one digest of a round's spans (train.round_span_seconds{span},
        # the slow-round record): obs.perf reads the same sums
        self._round_digest = RoundDigest(self.tracer, self.registry)
        self._g_encode_rows = self.registry.gauge(
            "train.encode_rows",
            "news rows a client-step gathers and encodes: the size R the "
            "round loop chose for its host-side dedup (0 = the step dedups "
            "on the device, at the slot count)",
        )
        self._g_encode_rows.set(0.0)
        self._m_full_size_steps = self.registry.counter(
            "train.encode_full_size_steps_total",
            "steps whose distinct news exceeded train.encode_rows and were "
            "served, exactly, at the full size min(B*(C+H), N)",
        )
        # per-step fusion gauge: how many fused Pallas hot-path kernels the
        # compiled step launches (model.fuse_hot_path; 2 = gather+encode
        # AND attention+pool+score, 1 = scoring kernel only — cnn text
        # head keeps the dense gather — 0 = dense step). A reader of a
        # prometheus scrape can tell WHICH program produced the step
        # timings next to it (docs/OBSERVABILITY.md).
        fuse_on = getattr(cfg.model, "fuse_hot_path", False)
        fused_n = 0
        if fuse_on:
            # the gather+encode kernel runs only where the frozen-table
            # gather exists: joint mode ("head") with the additive head
            fused_n = 1 + int(
                cfg.model.text_encoder_mode == "head"
                and getattr(cfg.model, "text_head_arch", "additive")
                == "additive"
            )
        self._g_fused = self.registry.gauge(
            "model.fused_hot_path_kernels",
            "fused Pallas kernels per train step (0 = dense path)",
        )
        self._g_fused.set(fused_n)
        # ---- sharding instruments (fedrec_tpu.shard; fedrec-obs report's
        # Sharding section): always registered, zero-valued when the
        # subsystem is off so the section simply doesn't render
        self._g_fsdp_shards = self.registry.gauge(
            "shard.fsdp_shards",
            "fsdp mesh-axis size the at-rest state shards over (1 = "
            "replicated layout)",
        )
        self._g_fsdp_shards.set(float(max(cfg.shard.fsdp, 1)))
        self._g_state_bytes = self.registry.gauge(
            "shard.state_bytes_per_device",
            "at-rest client-state bytes ONE device holds under the active "
            "sharding policy (params + optimizer moments + accumulators)",
        )
        self._g_table_rows = self.registry.gauge(
            "shard.table_rows_per_device",
            "news-catalog rows resident per device (= catalog rows under "
            "the replicated layout; padded_rows / shards under shard.table)",
        )
        self._g_table_occ = self.registry.gauge(
            "shard.table_occupancy",
            "real catalog rows / padded sharded rows (1.0 = no padding "
            "waste; only below 1 when devices don't divide the catalog)",
        )
        self._g_remote_rows = self.registry.gauge(
            "shard.remote_gather_rows",
            "worst-case rows crossing the interconnect per sharded-gather "
            "step across the mesh (shards x unique slots; 0 = table "
            "replicated, no remote gather)",
        )
        self._m_a2a_bytes = self.registry.counter(
            "shard.a2a_bytes_total",
            "modeled owner-bucketed all_to_all bytes of the sharded-table "
            "gather (id buckets out + answer rows back, whole mesh), "
            "advanced per dispatched step",
        )
        self._a2a_bytes_per_step = 0
        if self.table_spec is not None:
            spec = self.table_spec
            worst = cfg.data.batch_size * (
                1 + cfg.data.npratio + cfg.data.max_his_len
            )
            self._price_table_exchange(min(worst, spec.num_rows))
            self._g_table_rows.set(float(spec.rows_per_shard))
            self._g_table_occ.set(spec.num_rows / spec.padded_rows)
        elif self.token_states is not None:
            self._g_table_rows.set(float(self.token_states.shape[0]))
            self._g_table_occ.set(1.0)
        if self._state_shardings is not None:
            from fedrec_tpu.shard.policy import shard_bytes_per_device

            self._g_state_bytes.set(
                float(shard_bytes_per_device(self.state, self._state_shardings))
            )
        else:
            # replicated layout: every leaf is still dim-0 split over the
            # clients axis (client_sharding), so ONE device's share is the
            # total over that axis size — the same per-device accounting
            # the fsdp branch reports, keeping the gauge comparable when
            # an operator flips shard.fsdp on
            total = sum(
                float(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(self.state)
            )
            self._g_state_bytes.set(
                total / int(self.mesh.shape[cfg.fed.mesh_axis])
            )
        # ---- robustness instruments (fedrec-obs report's Robustness
        # section reads these): always registered — zero-valued when the
        # features are off, so the section simply doesn't render
        self._m_robust_rounds = self.registry.counter(
            "fed.robust_rounds_total",
            "round-end aggregations performed, labeled by robust method",
            labels=("method",),
        )
        self._m_quarantines = self.registry.counter(
            "fed.quarantines_total",
            "clients quarantined by the recovery path (weight 0 for "
            "fed.robust.quarantine_rounds rounds)",
        )
        self._m_rollbacks = self.registry.counter(
            "fed.rollbacks_total",
            "round rollback/replay cycles performed by the recovery path",
        )
        self._g_quarantined = self.registry.gauge(
            "fed.quarantine_active", "clients currently quarantined"
        )
        self._m_chaos = self.registry.counter(
            "chaos.faults_total",
            "faults injected by the chaos FaultPlan, labeled by kind "
            "(drop/straggle/nan/scale/flip); rollback replays re-count",
            labels=("kind",),
        )
        # ---- aggregation-topology instruments (fedrec_tpu.agg; the fleet
        # report's Aggregation section): always registered, zero-valued
        # under agg.mode='flat' so the section simply doesn't render
        self._m_agg_commits = self.registry.counter(
            "agg.commits_total",
            "async quorum commits performed (global version bumps)",
        )
        self._m_agg_late = self.registry.counter(
            "agg.late_folds_total",
            "buffered contributions folded with staleness > 0",
        )
        self._m_agg_stale = self.registry.counter(
            "agg.stale_drops_total",
            "buffered contributions dropped past agg.staleness_cap",
        )
        self._g_agg_staleness = self.registry.gauge(
            "agg.staleness",
            "mean staleness (commits behind) of the last commit's folds",
        )
        self._g_agg_version = self.registry.gauge(
            "agg.adopted_version",
            "global model version this worker last adopted (async commit "
            "counter; 0 until a first commit) — the fleet stalled-commit "
            "rule watches it against train.rounds_total",
        )
        # a restored agg-buffer sidecar already adopted a version above
        self._g_agg_version.set(float(self._agg_version))
        self._g_agg_quorum_wait = self.registry.gauge(
            "agg.quorum_wait_ms",
            "first-report -> quorum-close time of the last async commit "
            "(what the commit waited, vs the barrier's slowest reporter)",
        )
        self._g_agg_gate_saved = self.registry.gauge(
            "agg.gate_saved_ms",
            "slowest-report latency minus the quorum-close latency of the "
            "last async commit — the barrier wait the quorum removed",
        )
        self._g_agg_pending = self.registry.gauge(
            "agg.buffer_pending",
            "contributions in the async buffer awaiting a later commit",
        )
        self._g_agg_tier_ms = self.registry.gauge(
            "agg.tier_reduce_ms",
            "per-level-max tier-reduce time of the last hierarchical "
            "round, summed over levels (the tree's parallel critical path)",
        )
        # ---- cohort-engine instruments (fedrec-obs report's Participation
        # section): zero-valued when fed.population is off
        self._g_pop_size = self.registry.gauge(
            "fed.population_clients",
            "configured logical-client population (0 = cross-silo)",
        )
        self._g_pop_size.set(float(cfg.fed.population.num_clients))
        self._g_cohort_sampled = self.registry.gauge(
            "fed.cohort_sampled",
            "clients drawn for the current round, over-selection included",
        )
        self._g_cohort_reporting = self.registry.gauge(
            "fed.cohort_reporting",
            "clients whose round weight survived dropout and the deadline",
        )
        self._m_pop_drops = self.registry.counter(
            "fed.pop_dropouts_total",
            "sampled clients that dropped out of their round",
        )
        self._m_deadline_cuts = self.registry.counter(
            "fed.deadline_cuts_total",
            "clients cut at the round deadline (weight 0, work discarded)",
        )
        self._m_quorum_replays = self.registry.counter(
            "fed.quorum_replays_total",
            "rounds discarded below min_reports and replayed with a "
            "fresh cohort draw",
        )
        self._m_cohort_swaps = self.registry.counter(
            "fed.cohort_slot_swaps_total",
            "device-slot sidecar load/unload operations (cohort churn)",
        )
        self._g_pop_coverage = self.registry.gauge(
            "fed.population_coverage",
            "fraction of the population selected at least once",
        )
        # ---- communication instruments (fed.dcn_compress,
        # fedrec_tpu.comms): byte counters labeled by path — "cohort" is
        # the in-graph simulated client uplink (bytes measured from a real
        # wire-codec encode of the param trees, not dtype arithmetic),
        # "dcn" the coordinator's actual cross-host gather (counted in
        # parallel.multihost). Registered always; zero-valued (and the
        # report section silent) when no codec is active.
        self._m_bytes_up = self.registry.counter(
            "fed.dcn_bytes_up_total",
            "client->server round-update bytes shipped, by path "
            "(cohort = simulated in-graph uplink, dcn = real cross-host "
            "gather)",
            labels=("path",),
        )
        self._m_bytes_down = self.registry.counter(
            "fed.dcn_bytes_down_total",
            "server->client fan-out bytes (full precision in every mode), "
            "by path",
            labels=("path",),
        )
        self._g_comp_ratio = self.registry.gauge(
            "fed.dcn_compression_ratio",
            "dense/encoded byte ratio of one client's round-update payload "
            "under the active codec",
        )
        self._codec_bytes_per_client: int | None = None
        self._dense_bytes_per_client: int | None = None
        # fed.dcn_compress="auto": the per-leaf codec map, pinned once
        # after the warmup window (None while warming up — the sync body
        # runs with an all-"none" map until the pin, then recompiles)
        self._auto_leaf_codecs: list | None = None
        if cfg.fed.dcn_compress not in ("none", "auto"):
            self._price_codec()
        # spent-epsilon trajectory: one gauge per round, next to loss/AUC.
        # Only the rigorous mechanism gets a trajectory — ldp_news carries
        # no (epsilon, delta) statement to spend against (docs/DP.md).
        self._eps_schedule = None
        if (
            cfg.privacy.enabled
            and cfg.privacy.mechanism == "dpsgd"
            and cfg.privacy.sigma > 0
        ):
            from fedrec_tpu.privacy import round_epsilon_schedule

            # num_local_samples is this process's shard — the same n the
            # CLI drivers calibrated sigma against (cli/run.py passes the
            # full corpus, cli/coordinator.py its local shard)
            self._eps_schedule = round_epsilon_schedule(cfg, self.num_local_samples)
            self._m_eps = self.registry.gauge(
                "privacy.epsilon_spent",
                "(epsilon, delta)-DP spent after the completed rounds",
            )

        self.logger = MetricLogger(
            use_wandb=cfg.train.wandb,
            project=cfg.train.wandb_project,
            run_name=cfg.train.run_name,
            jsonl_path=jsonl_path,
            registry=self.registry,
            jsonl_max_mb=cfg.obs.jsonl_max_mb,
        )
        # round-cadence fleet telemetry (obs.fleet.collector): registry
        # snapshots + completed spans pushed to the fleet collector; push
        # failures are counted, never raised, and the obs.dir artifacts
        # stay the lossless offline source
        self.fleet_pusher = None
        if cfg.obs.fleet.collector:
            from fedrec_tpu.obs.fleet import FleetPusher

            self.fleet_pusher = FleetPusher(
                cfg.obs.fleet.collector,
                registry=self.registry,
                tracer=self.tracer,
                timeout_s=cfg.obs.fleet.push_timeout_s,
                push_every=cfg.obs.fleet.push_every,
            )

        # ---- training-health flight recorder (fedrec_tpu.obs.health) +
        # device watchdogs (fedrec_tpu.obs.device). The monitor digests the
        # in-graph sentry's per-client health vectors at round cadence; the
        # recorder keeps the last-N batches + the round-entry state so a
        # non-finite trigger dumps a replayable forensic bundle.
        hcfg = cfg.obs.health
        self.health = HealthMonitor(hcfg, registry=self.registry)
        # ---- model-quality observability (fedrec_tpu.obs.quality): the
        # sliced-eval publisher + per-client quality digest. Slice
        # definitions are built lazily at the first eval (valid_ix is
        # fixed for the run) and reused by every eval and the banked
        # quality gate.
        self.quality = None
        self._slice_defs = None
        if cfg.obs.quality.enabled:
            from fedrec_tpu.obs.quality import QualityMonitor

            self.quality = QualityMonitor(cfg.obs.quality, registry=self.registry)
        self.flightrec: FlightRecorder | None = None
        if self._obs_dir is not None and hcfg.flight_recorder:
            self.flightrec = FlightRecorder(
                ring_size=hcfg.ring_size,
                dump_policy=hcfg.dump_policy,
                dump_table_max_mb=hcfg.dump_table_max_mb,
            )
        # ---- performance observability (fedrec_tpu.obs.perf): live MFU /
        # samples-per-sec / roofline-verdict gauges off the round's span
        # timings, compile-cost telemetry via the watchdog hook, HBM
        # attribution at round cadence, triggered capture windows.
        # Default OFF — nothing below is constructed and the watchdog
        # keeps its exact pre-perf behavior (cost_cb=None).
        self.perf = None
        self._perf_last_batch = None
        # retain the last sharded batch ONLY when the HBM-attribution
        # pass will actually read it — a pinned (clients, B, ...) batch
        # with no consumer would hold device memory across rounds for
        # nothing
        self._perf_keep_batch = False
        if cfg.obs.perf.enabled:
            from fedrec_tpu.obs.perf import PerfMonitor

            self.perf = PerfMonitor(
                cfg.obs.perf, cfg, data.num_news,
                registry=self.registry, tracer=self.tracer,
                obs_dir=self._obs_dir,
            )
            self._perf_keep_batch = cfg.obs.perf.hbm_components
        # ---- continuous watch layer (fedrec_tpu.obs.watch): declarative
        # SLO burn rates + the streaming anomaly detector + the unified
        # alert lifecycle, evaluated once per round in _after_round with
        # the round's MetricLogger record. Default OFF — nothing below is
        # constructed, no alert.* instrument registers, and the legacy
        # trigger paths keep their exact pre-watch behavior (the
        # byte-identity pin in tests/test_watch.py).
        self.watch = None
        if cfg.obs.slo.enabled:
            from fedrec_tpu.obs.watch import Watch

            self.watch = Watch(
                cfg.obs.slo, cfg.obs.watch,
                registry=self.registry, tracer=self.tracer,
                jsonl_path=jsonl_path,
                jsonl_max_mb=cfg.obs.jsonl_max_mb,
            )
            if self.perf is not None:
                self.watch.bind_perf(self.perf)
            if self.fleet_pusher is not None:
                # alert transition records ride the existing telemetry
                # envelope so the collector sees every worker's alerts
                self.fleet_pusher.engine = self.watch.engine
        self.watchdog = CompileWatchdog(
            registry=self.registry,
            storm_threshold=hcfg.storm_threshold,
            storm_window_s=hcfg.storm_window_s,
            cost_cb=(
                self.perf.cost
                if self.perf is not None and cfg.obs.perf.compile_cost
                else None
            ),
        )
        self.watchdog.install()
        # every jitted program goes through the watchdog so each XLA
        # compile carries (callable, arg shapes) provenance — the steady-
        # shape paths must show exactly one compile per signature
        self.train_step = self.watchdog.watch(self.train_step, "train_step")
        self.eval_step = self.watchdog.watch(self.eval_step, "eval_step")
        self.full_eval_step = self.watchdog.watch(
            self.full_eval_step, "full_eval_step"
        )
        if self.full_eval_step_q is not None:
            self.full_eval_step_q = self.watchdog.watch(
                self.full_eval_step_q, "full_eval_step_q"
            )
        self.param_sync = self.watchdog.watch(self.param_sync, "param_sync")

        self._table: jnp.ndarray | None = None  # decoupled-mode news-vec table
        self._adopt_fn = None  # lazy compiled set_global_params program
        self.last_per_client_metrics: list[dict[str, float]] | None = None

    # ------------------------------------------------------------------
    def _check_snapshot_config(self, cfg) -> None:
        """Fail with a guided message when resuming under a model config
        whose parameter tree cannot match the snapshot's (ADVICE r3: the
        raw orbax tree-structure error names pytree paths, not the knob).
        Reads the config.json the snapshot-writing run persisted; absent or
        unreadable → silently skip (the restore itself still validates
        structure, and older snapshot dirs predate config.json).
        """
        import json as _json

        cfg_path = self.snapshots.directory / "config.json"
        try:
            saved = _json.loads(cfg_path.read_text()).get("model", {})
        except (OSError, ValueError):
            return
        # the knobs that change the parameter TREE (family or shapes) —
        # a mismatch is certain restore failure, so fail with guidance.
        # trunk_* shape the tree only when the snapshot actually holds trunk
        # params (text_encoder_mode="finetune", train/state.py); bert_hidden
        # only when a text head exists (mode != "table", where news vecs are
        # a precomputed table and no bert-width param is in the tree)
        tree_knobs = [
            "user_tower", "news_dim", "num_heads", "head_dim", "query_dim",
            "text_encoder_mode",
        ]
        saved_mode = saved.get("text_encoder_mode")
        if saved_mode != "table":
            # the text-head family + its conv width shape the text_head
            # subtree exactly like user_tower shapes the user_encoder one
            tree_knobs += ["bert_hidden", "text_head_arch", "cnn_kernel"]
        if saved_mode == "finetune":
            tree_knobs += [
                "text_trunk", "trunk_layers", "trunk_dense_layers",
                "trunk_heads", "trunk_ffn", "trunk_vocab",
            ]
        diffs = [
            (k, saved[k], getattr(cfg.model, k))
            for k in tree_knobs
            if k in saved and saved[k] != getattr(cfg.model, k)
        ]
        if diffs:
            detail = "; ".join(
                f"model.{k}: snapshot={s!r} vs this run={c!r}"
                for k, s, c in diffs
            )
            raise ValueError(
                f"cannot resume from {self.snapshots.directory}: the "
                f"snapshot was trained under a different model config "
                f"({detail}). Re-run with the snapshot's settings (its "
                "config.json has the full record) or point "
                "train.snapshot_dir at a fresh directory."
            )

    def _place_state(self, state: Any) -> Any:
        """Commit a full state pytree to its at-rest layout: the per-leaf
        FSDP shardings when ``shard.fsdp > 1`` (``shard.policy``), else the
        classic leading-dim client sharding — THE one placement rule, used
        by init, restore and adopt so a resumed run can never come back in
        a layout the compiled programs would silently re-shard every step."""
        if self._state_shardings is not None:
            return jax.tree_util.tree_map(
                lambda x, s: jax.device_put(jnp.asarray(x), s),
                state, self._state_shardings,
            )
        sharding = client_sharding(self.mesh, self.cfg.fed.mesh_axis)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), sharding), state
        )

    def _client0_params(self) -> tuple[Any, Any]:
        u = jax.tree_util.tree_map(lambda x: x[0], self.state.user_params)
        n = jax.tree_util.tree_map(lambda x: x[0], self.state.news_params)
        return u, n

    def _price_codec(self) -> None:
        """Price the per-client uplink from ONE real wire encode (payload
        sizes are static per codec × shapes) and publish the overall +
        per-leaf compression-ratio cells. Re-run when the ``auto``
        per-leaf map pins (the payload sizes change with the map)."""
        from fedrec_tpu.comms import (
            encode_tree,
            leaf_names,
            payload_nbytes,
            tree_dense_nbytes,
        )

        cfg = self.cfg
        host_params = jax.tree_util.tree_map(
            np.asarray, self._client0_params()
        )
        enc = encode_tree(
            host_params,
            cfg.fed.dcn_compress,
            cfg.fed.dcn_topk_ratio,
            sketch_width=cfg.fed.dcn_sketch_width,
            sketch_seed=cfg.fed.dcn_sketch_seed,
            leaf_codecs=self._auto_leaf_codecs,
        )
        self._codec_bytes_per_client = enc.nbytes()
        self._dense_bytes_per_client = tree_dense_nbytes(host_params)
        self._g_comp_ratio.set(
            self._dense_bytes_per_client
            / max(self._codec_bytes_per_client, 1)
        )
        ratio_leaf = self.registry.gauge(
            "fed.dcn_compression_ratio_leaf",
            "dense/encoded byte ratio of one round-update tensor, by leaf",
            labels=("leaf",),
        )
        for name, payload, shape in zip(
            leaf_names(host_params), enc.payloads, enc.shapes
        ):
            dense_b = 4 * int(np.prod(shape)) if shape else 4
            ratio_leaf.set(
                dense_b / max(payload_nbytes(payload), 1), leaf=name
            )

    # tensors at or below this size stay uncompressed under "auto":
    # scalars/norm vectors, where codec overhead exceeds the dense bytes
    _AUTO_DENSE_FLOOR = 64

    def _pin_auto_codec_map(self, round_idx: int, sync_entry: Any) -> None:
        """``fed.dcn_compress='auto'``: derive the per-leaf codec map from
        the warmup window's GLOBAL round delta (round-entry global vs the
        post-sync global — identical on every process, so the pin needs no
        broadcast and replays deterministically from the seed), rebuild
        the compiled sync around it, re-price the uplink, and record the
        map in provenance (``codec_map.json`` beside the obs artifacts).

        Selection per leaf: tensors ≤ the dense floor stay "none"
        (codec overhead exceeds the payload); otherwise the measured
        reconstruction error of topk (at ``fed.dcn_topk_ratio``) and
        countsketch (at ``fed.dcn_sketch_width``) on the warmup delta
        decides — sparse, concentrated deltas reconstruct better under
        topk; dense towers under the sketch. Held fixed thereafter."""
        from fedrec_tpu.comms import decode_leaf, encode_leaf, leaf_names

        cfg = self.cfg
        entry0 = jax.tree_util.tree_map(
            lambda x: np.asarray(x[0], np.float32), sync_entry
        )
        post0 = jax.tree_util.tree_map(
            lambda x: np.asarray(x, np.float32), self._client0_params()
        )
        delta = jax.tree_util.tree_map(lambda p, e: p - e, post0, entry0)
        flat, _ = jax.tree_util.tree_flatten(delta)
        names = leaf_names(delta)
        chosen: list[str] = []
        detail: list[dict] = []
        for i, (name, d) in enumerate(zip(names, flat)):
            if d.size <= self._AUTO_DENSE_FLOOR:
                chosen.append("none")
                detail.append({"leaf": name, "codec": "none", "n": int(d.size)})
                continue
            errs = {}
            for cand in ("topk", "countsketch"):
                rec = decode_leaf(
                    encode_leaf(
                        d, cand, cfg.fed.dcn_topk_ratio,
                        sketch_width=cfg.fed.dcn_sketch_width,
                        sketch_seed=cfg.fed.dcn_sketch_seed,
                        leaf_id=i,
                    ),
                    cand, d.shape,
                    sketch_seed=cfg.fed.dcn_sketch_seed, leaf_id=i,
                )
                errs[cand] = float(np.sqrt(np.mean((rec - d) ** 2)))
            pick = "topk" if errs["topk"] <= errs["countsketch"] else "countsketch"
            chosen.append(pick)
            detail.append({
                "leaf": name, "codec": pick, "n": int(d.size),
                "rmse_topk": errs["topk"],
                "rmse_countsketch": errs["countsketch"],
            })
        self._auto_leaf_codecs = chosen
        # rebuild the compiled sync around the pinned map (same calling
        # convention — the warmup body already ran the 4-arg codec shape)
        from fedrec_tpu.train.step import build_param_sync

        self.param_sync = self.watchdog.watch(
            build_param_sync(
                cfg, self.mesh, self.strategy,
                state_shardings=self._state_shardings,
                leaf_codecs=chosen,
            ),
            "param_sync",
        )
        self._price_codec()
        summary = {
            "pinned_at_round": int(round_idx),
            "warmup_rounds": int(cfg.fed.dcn_auto_warmup),
            "sketch_width": float(cfg.fed.dcn_sketch_width),
            "sketch_seed": int(cfg.fed.dcn_sketch_seed),
            "topk_ratio": float(cfg.fed.dcn_topk_ratio),
            "map": {n: c for n, c in zip(names, chosen)},
            "detail": detail,
        }
        import json

        if self._obs_dir is not None:
            with open(self._obs_dir / "codec_map.json", "w") as f:
                json.dump(summary, f, indent=2, sort_keys=True)
        if self.logger is not None:
            # a JSON string survives the logger's stringification — the
            # report parses it back into the auto_codec_map row
            self.logger.log(round_idx, {
                "dcn_auto_map_pinned": json.dumps(
                    {n: c for n, c in zip(names, chosen)}, sort_keys=True
                ),
            })
        counts: dict[str, int] = {}
        for c in chosen:
            counts[c] = counts.get(c, 0) + 1
        print(
            f"[trainer] fed.dcn_compress=auto pinned per-leaf codec map "
            f"after round {round_idx}: "
            + ", ".join(f"{c}×{k}" for c, k in sorted(counts.items()))
            + (
                f" (codec_map.json in {self._obs_dir})"
                if self._obs_dir is not None else ""
            ),
            flush=True,
        )

    def _client_params(self, client: int) -> tuple[Any, Any]:
        u = jax.tree_util.tree_map(lambda x: x[client], self.state.user_params)
        n = jax.tree_util.tree_map(lambda x: x[client], self.state.news_params)
        return u, n

    def _clients_in_sync(self) -> bool:
        """True when every client holds bitwise-identical parameters.

        Decides whether evaluation may use the client-0 fast path: after a
        ``param_avg``/coordinator sync (everyone adopts the aggregate) and
        under ``grad_avg`` (per-step pmean keeps clients in lockstep) this
        is True; under ``local`` — or after a zero-participation round,
        which keeps local params — clients diverge and client 0 would NOT
        be "the model" (VERDICT r2 Weak #3)."""
        leaves = jax.tree_util.tree_leaves(
            (self.state.user_params, self.state.news_params)
        )
        # ONE readback: per-leaf bools would each be a host sync
        return bool(jnp.all(jnp.stack([jnp.all(x == x[0:1]) for x in leaves])))

    def _corpus_for(self, news_params: Any, client: int) -> jnp.ndarray:
        # only the decoupled mode caches a (client-0) table that a non-zero
        # client must bypass; every other path is client-agnostic
        if client != 0 and self.mode == "decoupled":
            return self._encode_states(news_params)
        return self._encode_corpus(news_params)

    def _aggregate_eval(self, eval_one) -> dict[str, float]:
        """Client-0 metrics when clients are in sync; otherwise the MEAN of
        per-client metrics (the documented aggregate — the reference's
        semantics are per-client validation, ``client.py:149-171``). The
        per-client breakdown is kept on ``self.last_per_client_metrics``."""
        if self.cfg.fed.num_clients == 1 or self._clients_in_sync():
            self.last_per_client_metrics = None
            return eval_one(0)
        per = [eval_one(c) for c in range(self.cfg.fed.num_clients)]
        self.last_per_client_metrics = per
        return {k: float(np.mean([m[k] for m in per])) for k in per[0]}

    def adopt_state(self, state: Any) -> None:
        """Install a restored full state pytree (params + opt + PRNG) with
        the trainer's at-rest layout (``_place_state``) — the multi-process
        resume path, where snapshots are flax-serialized per host rather
        than orbax-managed."""
        self.state = self._place_state(state)
        self._table = None  # params changed; a cached decoupled table is stale

    def population_sidecar_bytes(self, round_idx: int) -> bytes | None:
        """The cohort engine's schedule-defining state (sampler fairness
        counters + participation ledger + slot occupancy), serialized for
        persistence — or ``None`` when no population engine is active.
        The orbax path writes ``population_state.msgpack`` itself
        (:meth:`_after_round`); the coordinator deployment persists this
        per WORKER next to its local msgpack snapshot so an elastic
        epoch change can carry participation history across the
        re-formed world."""
        if not self._pop_engine:
            return None
        from fedrec_tpu.train.checkpoint import population_state_bytes

        return population_state_bytes(
            self.cohort_sampler.state_dict(),
            self.population.ledger.state_dict(),
            self._slot_occupants,
            self._slot_writeback,
            round_idx,
        )

    def adopt_population_sidecar(self, blob: bytes, resize: bool = False) -> int:
        """Restore a population sidecar; returns its round tag.

        ``resize=False`` demands exact population/slot agreement (the
        fixed-world resume). ``resize=True`` is elastic-membership
        continuity: the LEDGER adopts with prefix-copy resize semantics
        (:meth:`ParticipationLedger.load_state_dict`), while sampler
        fairness state and slot occupancy are adopted only when their
        shapes still match — an epoch's re-deal otherwise restarts them
        fresh (documented divergence: the cohort *schedule* re-anchors at
        the new world, the participation *history* does not reset)."""
        if not self._pop_engine:
            raise ValueError(
                "adopt_population_sidecar needs an active fed.population "
                "engine (fed.population.num_clients > 0)"
            )
        from fedrec_tpu.train.checkpoint import load_population_state

        pst = load_population_state(blob)
        try:
            self.cohort_sampler.load_state_dict(pst["sampler"])
        except ValueError:
            if not resize:
                raise
            print(
                "[trainer] population sampler state does not fit the "
                "re-formed world; fairness counters restart fresh "
                "(ledger continuity is preserved)"
            )
        self.population.ledger.load_state_dict(pst["ledger"], resize=resize)
        occ = np.asarray(pst["slot_occupants"], np.int64)
        wb = np.asarray(pst["slot_writeback"], bool)
        if occ.shape == self._slot_occupants.shape:
            self._slot_occupants = occ.copy()
            self._slot_writeback = wb.copy()
        elif not resize:
            raise ValueError(
                f"population sidecar slot count {occ.shape} does not match "
                f"the configured {self._slot_occupants.shape} slots"
            )
        return int(pst["round"])

    def set_global_params(self, user_params: Any, news_params: Any) -> None:
        """Adopt externally-aggregated parameters on every local client.

        Used by the coordinator deployment: the server's weight fan-out
        (reference ``server.py:76-77`` / ``client.py:261-264``) lands here.
        """
        # ONE compiled program replaces a per-leaf broadcast+device_put storm:
        # each mesh shard swaps its param slices for the (replicated) new
        # globals, so the state keeps its client sharding and the round
        # boundary issues a single dispatch (the transfer storm both wastes
        # TPU dispatch and, on single-core XLA:CPU rigs, can starve the next
        # round's collective rendezvous into its termination deadline)
        if self._adopt_fn is None:
            from functools import partial

            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            axis = self.cfg.fed.mesh_axis

            @partial(
                shard_map,
                mesh=self.mesh,
                in_specs=(P(axis), P(), P()),
                out_specs=P(axis),
                check_vma=False,
            )
            def adopt(stacked, u, n):
                # the block may hold a COHORT of k clients (clients > devices,
                # see train.step.cohort_axes) — every client in the block
                # adopts the globals; opt states and rngs stay per-client.
                # (The block-of-1 x[0]/x[None] form this replaces silently
                # collapsed cohort states to one client.)
                kb = stacked.step.shape[0]
                bu = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x, (kb,) + x.shape), u
                )
                bn = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x, (kb,) + x.shape), n
                )
                return stacked.replace(user_params=bu, news_params=bn)

            self._adopt_fn = jax.jit(adopt, donate_argnums=(0,))
        self.state = self._adopt_fn(
            self.state,
            jax.tree_util.tree_map(jnp.asarray, user_params),
            jax.tree_util.tree_map(jnp.asarray, news_params),
        )
        if self.mode == "decoupled":
            self._refresh_table()

    def _replicate_table(self, table: jnp.ndarray) -> jnp.ndarray:
        """Pin a news-vector table to the one replicated layout the train
        step expects (in_spec ``P()``). The decoupled round alternates table
        sources (sharded refresh vs per-client update slice); without a
        common layout each source would key its own compile of the step."""
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(table, NamedSharding(self.mesh, PartitionSpec()))

    def _token_table_spec(self):
        """The token-state table's at-rest partitioning: row blocks over
        the clients axis under ``shard.table``, else replicated."""
        from jax.sharding import PartitionSpec

        if self.table_spec is not None:
            return PartitionSpec(self.cfg.fed.mesh_axis)
        return PartitionSpec()

    def _commit_token_states(self, table: jnp.ndarray) -> jnp.ndarray:
        """Commit the token-state table to the at-rest format every program
        that takes it states (``train.step.token_table_format``): one
        ``table_commit`` span says which layout it was found in and which it
        rests in now. The caller's array is left alone; the copy made here
        lives as long as this trainer does."""
        tracer = get_tracer()
        t0 = tracer.now()
        table, did = commit_token_table(
            table, self.mesh, self._token_table_spec()
        )
        jax.block_until_ready(table)
        tracer.add_span(
            "table_commit", tracer.now() - t0, bytes=int(table.nbytes), **did
        )
        return table

    def _refresh_table(self) -> jnp.ndarray:
        _, news_params = self._client0_params()
        self._table = self._encode_states(news_params)
        return self._table

    def _encode_states(self, news_params) -> jnp.ndarray:
        """Cached-trunk corpus encode, sharded over all mesh devices when
        there are several (per-round refresh is the eval-path bottleneck at
        corpus scale). The result is pinned replicated so every consumer —
        train step (in_spec ``P()``), per-batch eval gathers, serving
        export — pays the post-encode all-gather exactly once here."""
        vecs = self._corpus_encode(news_params, self.token_states)
        if self.table_spec is not None:
            # sharded catalog: the at-rest rows are already P(clients) and
            # padded, so the sharded encode reshards nothing; only the REAL
            # rows leave (eval/serving index by catalog id)
            vecs = vecs[: self.table_spec.num_rows]
        return self._replicate_table(vecs) if self.mesh.size > 1 else vecs

    def _encode_corpus(self, news_params) -> jnp.ndarray:
        """(N, D) news-vector table from client params, any text-encoder mode."""
        if self.mode == "finetune":
            from fedrec_tpu.train.step import encode_corpus_tokens

            return encode_corpus_tokens(self.text_encoder, news_params, self.news_tokens)
        if self.mode == "decoupled" and self._table is not None:
            # the round loop (news_update / _refresh_table / set_global_params)
            # just rebuilt this table from the same client-0 params — a second
            # full-corpus encode per eval round would double the exact cost
            # the sharded encode exists to cut
            return self._table
        return self._encode_states(news_params)

    def export_for_serving(self) -> tuple[Any, jnp.ndarray]:
        """``(user_params, (N, D) news-vector table)`` of client 0 — the
        handoff to :mod:`fedrec_tpu.serve` (after ``param_avg``/coordinator
        aggregation all clients hold identical parameters). Warns loudly
        when clients have diverged (``local``, zero-participation round):
        client 0 is then ONE client's model, not "the model" — same
        resolution rule as :meth:`evaluate` (VERDICT r2 Weak #3)."""
        if self.cfg.fed.num_clients > 1 and not self._clients_in_sync():
            print(
                "[trainer] WARNING: exporting client 0 for serving while "
                "clients hold DIVERGED parameters (local strategy or an "
                "unsynced round) — run a param sync first, or serve "
                "per-client models deliberately"
            )
        user_params, news_params = self._client0_params()
        return user_params, self._encode_corpus(news_params)

    def _feature_table(self) -> jnp.ndarray:
        if self.mode == "finetune":
            return self.news_tokens
        if self.mode == "joint":
            return self.token_states
        if self._table is None:
            self._refresh_table()
        return self._table

    # ------------------------------------------------------------------
    def _epoch_batches_source(self, epoch_idx: int):
        """One local epoch's stacked (slots, B, ...) batches. Fixed world:
        the legacy batcher re-deals the whole (local) corpus over the
        client slots each epoch. Sampled world (``fed.population`` above
        the slot count): slot *j* iterates the CURRENT cohort's client
        ``j``'s own static shard — data follows the client, the premise of
        cross-device federation."""
        if self._pop_sampling:
            return self.population.cohort_epoch_batches(
                self._current_plan.slot_clients, self.train_ix,
                self.cfg.data, epoch_idx,
            )
        return self.batcher.epoch_batches_sharded(
            self.cfg.fed.num_clients, epoch_idx
        )

    def _epoch_batch_iter(
        self,
        epoch_idx: int,
        extra: dict | None = None,
        distinct: list | None = None,
    ):
        """Epoch batches as step-ready dicts, built ahead on a bounded
        producer thread when ``data.prefetch_batches`` > 0 — batch t+1
        assembles (shuffle, negative sampling, packing, dedup) while step t
        runs on device, closing the dispatch gap the step_profile
        host-pipeline rows measure. Off (0) = plain inline iteration,
        identical batches either way (tests/test_prefetch.py). ``extra``
        (the round's chaos fault vectors) is merged into every batch dict.
        Once the run's encode size is chosen, every batch also carries its
        clients' distinct news ids and their inverse
        (``train.step.host_news_dedup``), and ``distinct`` receives each
        step's largest distinct count (read by the caller after the epoch)."""
        extra = extra or {}
        rows = self._encode_rows
        n_news = self._num_news() if rows is not None else None

        def transform(b):
            batch = {
                "candidates": b.candidates,
                "history": b.history,
                "labels": b.labels,
                **extra,
            }
            if rows is not None:
                entries, most = host_news_dedup(
                    b.candidates, b.history, rows, n_news
                )
                batch.update(entries)
                if distinct is not None:
                    distinct.append(most)
            return batch

        return maybe_prefetch(
            self._epoch_batches_source(epoch_idx),
            self.cfg.data.prefetch_batches,
            transform=transform,
        )

    # how many of a round's first steps the encode size is chosen from. A
    # sample, not a round: the largest of 32 counts lies about 2 sd above
    # their mean and the largest of a million about 5, so with counts whose
    # sd is under 1% of the mean step.ENCODE_ROOM covers a run of any
    # length, and a step past it is served at the full size all the same.
    # The batcher is deterministic by epoch, but building a step's batch to
    # count it costs the host 1.5-4 ms (PERF.md section 5), so a long epoch
    # is not counted whole.
    ENCODE_ROWS_STEPS = 32

    def _num_news(self) -> int:
        """Catalog rows the joint step's dedup is bounded by (the GLOBAL
        count under ``shard.table``, whose local block is a slice)."""
        if self.table_spec is not None:
            return self.table_spec.num_rows
        return int(self.token_states.shape[0])

    def _choose_encode_rows(self, epoch_idx: int) -> None:
        """Choose the run's encode size R before its first dispatch: the
        largest distinct count over the clients of the epoch's first steps,
        with room (``train.step.encode_rows_for``). One size serves every
        client of a cohort and every device of a clients mesh."""
        most = slots = 0
        for _, b in zip(
            range(self.ENCODE_ROWS_STEPS), self._epoch_batches_source(epoch_idx)
        ):
            most = max(most, most_distinct_news(b.candidates, b.history))
            slots = b.candidates[0].size + b.history[0].size
        if slots:
            self._encode_full = min(slots, self._num_news())
            self._set_encode_rows(most)

    def _set_encode_rows(self, most: int) -> None:
        self._encode_rows = encode_rows_for(most, self._encode_full)
        self._g_encode_rows.set(float(self._encode_rows))
        if self.perf is not None:
            self.perf.set_encode_rows(self._encode_rows)
        if self.table_spec is not None:
            self._price_table_exchange(self._encode_rows)

    def _price_table_exchange(self, rows: int) -> None:
        """The sharded-gather wire model at ``rows`` ids a gather."""
        from fedrec_tpu.shard.table import a2a_bytes_per_gather

        self._a2a_bytes_per_step = a2a_bytes_per_gather(
            rows, tuple(self.token_states.shape[1:]),
            self.token_states.dtype, self.table_spec,
        )
        self._g_remote_rows.set(float(self.table_spec.num_shards * rows))

    # ------------------------------------------------- health / forensics
    def _host_state(self) -> Any:
        """Host (numpy) copy of the full stacked client state — the flight
        recorder's round-entry checkpoint. Device buffers may be donated
        away by the time a trigger fires, so the copy is eager."""
        return jax.tree_util.tree_map(np.asarray, self.state)

    def _entry_state(self) -> Any:
        """The round-entry state the flight recorder keeps — None
        when obs.health.snapshot_state is off (the per-round D2H copy is
        the recorder's dominant cost at large model x cohort scale; dumps
        then carry the batch ring but cannot replay)."""
        return self._host_state() if self.cfg.obs.health.snapshot_state else None

    def _dump_meta(self) -> dict:
        return {
            "num_news": self.data.num_news,
            "title_len": self.data.title_len,
            "mode": self.mode,
            "num_local_samples": self.num_local_samples,
        }

    def _check_health(
        self,
        start_round: int,
        health_rows: list[dict] | None = None,
        round_losses: tuple | list = (),
    ) -> None:
        """Digest one round's sentry arrays through the HealthMonitor; on
        a trigger, dump the flight recorder and (for a non-finite sentinel
        under abort_on_nonfinite) raise TrainingHealthError.
        ``health_rows`` are HOST arrays, one dict a step: the round's end
        gathered them with the losses in its one read."""
        if not self.cfg.obs.health.sentry:
            return
        if not health_rows:
            return
        # one (clients,) row a step -> the monitor's (rounds, steps, clients)
        arrays = {
            k: np.stack([r[k] for r in health_rows])[None]
            for k in health_rows[0]
        }
        trigger = self.health.check(
            start_round, arrays, list(round_losses),
            ignore_clients=set(self._quarantine),
        )
        if self.watch is not None:
            # unified trigger path: the health monitor's verdicts pulse
            # through the alert engine (scored at the round's evaluate)
            self.watch.ingest_health_trigger(trigger)
            self.watch.ingest_health_outliers(self.health.last_outliers)
        # ---- quarantine-and-rollback (fed.robust.recover): a non-finite
        # update or an outlier client becomes a RECOVERABLE trigger while
        # retries remain — run() quarantines the client, restores the
        # round-entry state, and replays. Quarantined clients were already
        # excluded above, so a replay cannot re-trigger on the same client;
        # retries bound how many DISTINCT bad clients one round may shed
        # before the existing dump-and-abort takes over.
        rb = self.cfg.fed.robust
        if rb.recover:
            cand = (
                trigger
                if trigger is not None and trigger.get("kind") == "nonfinite"
                else None
            )
            if cand is None and self.health.last_outliers:
                cand = {
                    "kind": "outlier",
                    **max(
                        self.health.last_outliers,
                        key=lambda o: o["update_norm"],
                    ),
                }
            if (
                cand is not None
                and cand.get("client") is not None
                and self._round_retries < rb.max_retries
            ):
                raise RoundRecovery(cand)
        if trigger is None:
            return
        dump_dir = self._dump_flightrec(trigger)
        kind = trigger["kind"]
        where = f"round {trigger.get('round')}"
        if trigger.get("step") is not None:
            where += f" step {trigger['step']} client {trigger.get('client')}"
        detail = trigger.get("detail") or {
            k: trigger[k] for k in ("round_loss", "trailing_mean")
            if k in trigger
        }
        if dump_dir:
            hint = (
                f" Forensics dumped to {dump_dir} — confirm with "
                f"`fedrec-obs replay {dump_dir}`."
            )
        elif self.flightrec is not None:
            hint = (
                " Flight-recorder dump suppressed by "
                f"obs.health.dump_policy={self.cfg.obs.health.dump_policy!r}"
                f" (earlier dump: {self.flightrec.last_dump_dir})."
            )
        else:
            hint = (
                " Set obs.dir (+ obs.health.flight_recorder) for a "
                "replayable dump."
            )
        msg = (
            f"training-health trigger [{kind}] at {where}: {detail}.{hint}"
        )
        if kind == "nonfinite" and self.cfg.obs.health.abort_on_nonfinite:
            raise TrainingHealthError(msg)
        print(f"[trainer] WARNING: {msg}")

    # ------------------------------------------- quarantine & rollback
    def train_round_recovering(self, round_idx: int) -> RoundResult:
        """One round under the quarantine/rollback policy —
        the coordinator driver's per-round entry point (``run`` applies
        the same policy). Without
        ``fed.robust.recover`` this is exactly :meth:`train_round`."""
        from fedrec_tpu.fed.population import QuorumFailure

        while True:
            self._capture_recovery_state()
            try:
                result = self.train_round(round_idx)
            except RoundRecovery as e:
                self._rollback_and_quarantine(e.trigger, round_idx)
                continue
            except QuorumFailure as e:
                self._handle_quorum_failure(e, round_idx)
                continue
            self._round_retries = 0
            self._commit_population(round_idx)
            self._tick_quarantine()
            return result

    def _capture_recovery_state(self) -> None:
        """Snapshot the rollback target at round entry: the full
        client state (host copy), plus the FedOpt buffers — the server
        optimizer steps at round end, so replaying a rolled-back round
        without restoring them would double-apply momentum."""
        if not self.cfg.fed.robust.recover:
            return
        self._recovery_state = self._host_state()
        if self._pop_engine:
            # occupancy must roll back WITH the state: a replayed round
            # re-installs its cohort against the restored slots, and a
            # stale occupancy map would write one client's sidecar back
            # under another's id
            self._recovery_occupants = (
                self._slot_occupants.copy(),
                self._slot_writeback.copy(),
            )
        if self.server_opt is not None:
            import copy

            self._recovery_opt_state = copy.deepcopy(self.server_opt._state)

    def _rollback_and_quarantine(self, trigger: dict, round_idx: int) -> None:
        """Apply one recovery cycle (``fed.robust.recover``): quarantine the
        offending client, restore the round-entry state, and let ``run``
        replay the round. Published to the registry and stamped into the
        trace as a ``rollback`` event; the replayed round's ``fed_round``
        span carries the active quarantine set."""
        cfg = self.cfg
        client = int(trigger["client"])
        kind = str(trigger.get("kind"))
        self._round_retries += 1
        logical = None
        if self._pop_sampling and self._current_plan is not None:
            # the sentry flags a SLOT; quarantine the LOGICAL client that
            # occupied it this round — the sampler excludes it from draws
            # until the expiry round, and its (possibly poisoned) sidecar
            # is reset so the healed rejoin restarts from the template
            logical = int(self._current_plan.slot_clients[client])
            self.population.ledger.quarantine(
                logical, round_idx + cfg.fed.robust.quarantine_rounds
            )
            self.population.reset_sidecar(logical)
            self._pop_pending = {
                k: v for k, v in self._pop_pending.items() if k < round_idx
            }
            self._g_quarantined.set(
                float(len(self.population.ledger.quarantined))
            )
        else:
            self._quarantine[client] = max(
                self._quarantine.get(client, 0),
                cfg.fed.robust.quarantine_rounds,
            )
            self._g_quarantined.set(float(len(self._quarantine)))
        self._m_quarantines.inc()
        self._m_rollbacks.inc()
        self.tracer.add_span(
            "rollback", dur_s=0.0,
            round=int(trigger.get("round") or round_idx),
            client=client if logical is None else logical,
            kind=kind, retry=self._round_retries,
        )
        who = (
            f"client {client}" if logical is None
            else f"logical client {logical} (slot {client})"
        )
        print(
            f"[trainer] WARNING: health trigger [{kind}] on {who} "
            f"at round {trigger.get('round')} — quarantining it for "
            f"{cfg.fed.robust.quarantine_rounds} round(s), rolling back to "
            f"the round-{round_idx} entry state and replaying (retry "
            f"{self._round_retries}/{cfg.fed.robust.max_retries})"
        )
        self.adopt_state(self._recovery_state)
        if self._pop_engine and self._recovery_occupants is not None:
            self._slot_occupants = self._recovery_occupants[0].copy()
            self._slot_writeback = self._recovery_occupants[1].copy()
            if logical is not None:
                # the quarantined client's sidecar was reset above; without
                # this, the replay's _install_cohort would write its
                # restored (possibly poisoned) sidecar straight back and
                # the healed rejoin would NOT restart from the template
                self._slot_writeback[self._slot_occupants == logical] = False
        if self.server_opt is not None:
            import copy

            self.server_opt._state = copy.deepcopy(self._recovery_opt_state)

    def _round_span_args(self) -> dict:
        """Extra fed_round span attributes while recovery is active, so the
        trace shows which rounds ran with clients excluded / as replays."""
        args: dict = {}
        if self._quarantine:
            args["quarantined"] = sorted(self._quarantine)
        if self._pop_sampling and self._current_plan is not None:
            args["cohort"] = int(self._current_plan.slot_real.sum())
            if self.population.ledger.quarantined:
                args["quarantined"] = sorted(
                    self.population.ledger.quarantined
                )
        if self._round_retries:
            args["replay_retry"] = self._round_retries
        if self._codec_bytes_per_client is not None:
            # byte attrs ride the fed_round span: what ONE client's update
            # costs on the wire under the active codec, vs dense
            args["codec"] = self.cfg.fed.dcn_compress
            args["codec_bytes_per_client"] = self._codec_bytes_per_client
            args["dense_bytes_per_client"] = self._dense_bytes_per_client
        return args

    def _tick_quarantine(self) -> None:
        """Advance the quarantine ledger by one completed round; expired
        clients rejoin HEALED (params reset to the global, optimizer
        moments zeroed) — their own state may still be NaN-poisoned, and
        un-healed Adam moments would re-trigger the same quarantine the
        moment it expires."""
        if not self._quarantine:
            return
        expired = []
        for c in list(self._quarantine):
            self._quarantine[c] -= 1
            if self._quarantine[c] <= 0:
                expired.append(c)
                del self._quarantine[c]
        self._g_quarantined.set(float(len(self._quarantine)))
        for c in expired:
            self._heal_client(c)

    def _heal_client(self, client: int) -> None:
        cfg = self.cfg
        donor = next(
            (
                c
                for c in range(cfg.fed.num_clients)
                if c != client and c not in self._quarantine
            ),
            None,
        )
        if donor is None:
            return

        def fix(tree, from_donor: bool):
            def one(x):
                x = np.array(x)
                if x.ndim >= 1 and x.shape[0] == cfg.fed.num_clients:
                    x[client] = x[donor] if from_donor else 0
                return x

            return jax.tree_util.tree_map(one, tree)

        host = self._host_state()
        self.adopt_state(
            host.replace(
                user_params=fix(host.user_params, True),
                news_params=fix(host.news_params, True),
                opt_user=fix(host.opt_user, False),
                opt_news=fix(host.opt_news, False),
                news_grad_accum=fix(host.news_grad_accum, False),
                # a healed client must not replay a poisoned codec
                # residual — same contract as the optimizer moments
                ef_residual=fix(host.ef_residual, False),
            )
        )
        print(
            f"[trainer] quarantine expired for client {client}: rejoined "
            "with global params and fresh optimizer state"
        )

    def _dump_flightrec(self, trigger: dict):
        if self.flightrec is None:
            return None
        try:
            table = np.asarray(self._feature_table())
        except Exception:  # noqa: BLE001 — forensics must not mask the trigger
            table = None
        try:
            return self.flightrec.dump(
                self._obs_dir / "flightrec",
                trigger,
                cfg=self.cfg,
                registry=self.registry,
                table=table,
                meta=self._dump_meta(),
            )
        except Exception as e:  # noqa: BLE001
            print(f"[trainer] flight-recorder dump failed: "
                  f"{type(e).__name__}: {e}")
            return None

    def _flightrec_on_exception(self, e: BaseException) -> None:
        """Last-chance forensics: a run dying to an exception that never
        reached a round-end health check (a dispatch error) still dumps its
        batch ring + round-entry state."""
        if self.flightrec is None or self.flightrec.dump_count > 0:
            return
        if not isinstance(e, Exception):
            return  # KeyboardInterrupt/SystemExit: exit fast, no dump
        self._dump_flightrec({
            "kind": "exception",
            "error": type(e).__name__,
            "message": str(e)[:500],
            "round": None,
            "step": None,
        })

    def _mask_rng(self, round_idx: int) -> jax.Array:
        """THE per-round participation-mask key."""
        return jax.random.PRNGKey(
            hash((self.cfg.train.seed, round_idx)) & 0x7FFFFFFF
        )

    def _round_weights(self, round_idx: int) -> np.ndarray:
        """THE per-round aggregation weights, one composition:

        * fixed-world (no ``fed.population``): participation mask × chaos
          slot drop/straggle mask × quarantine exclusion — without chaos
          or quarantine exactly the participation mask (value-identical
          to the pre-robust trajectory);
        * cohort engine: the plan's per-slot report simulation (pads,
          per-round dropouts, deadline cuts — :func:`plan_round_weights`)
          × the same participation/chaos-slot composition, with the
          quorum policy enforced on the FINAL reporting count (a
          :class:`QuorumFailure` here is raised before any state
          mutation, so the discarded round IS its entry state).
        """
        cfg = self.cfg
        from fedrec_tpu.fed.strategies import participation_mask

        plan = self._current_plan if self._pop_engine else None
        events = None
        if plan is not None:
            from fedrec_tpu.fed.population import plan_round_weights

            w, events = plan_round_weights(
                plan, round_idx, cfg.fed.population.round_deadline_ms,
                chaos=self.chaos,
            )
            if plan.start_dropped.size:
                # start-drops never reached a slot; the ledger still owes
                # them a dropped round (over-selection's raison d'etre)
                events["dropped"] = np.unique(
                    np.concatenate([events["dropped"], plan.start_dropped])
                )
            if cfg.fed.participation < 1.0:
                # degenerate-population composition: the legacy fraction
                # still applies when the cohort is the fixed world
                w = w * np.asarray(
                    participation_mask(
                        self._mask_rng(round_idx), cfg.fed.num_clients,
                        cfg.fed.participation,
                    ),
                    np.float32,
                )
        else:
            w = np.asarray(
                participation_mask(
                    self._mask_rng(round_idx), cfg.fed.num_clients,
                    cfg.fed.participation,
                ),
                np.float32,
            )
        if self.chaos is not None:
            rf = self.chaos.round_faults(round_idx)
            w = w * rf.weight_mask
            for kind, count in (
                ("drop", len(rf.dropped)), ("straggle", len(rf.straggled)),
            ):
                if count:
                    self._m_chaos.inc(count, kind=kind)
            for kind, _client in rf.injected:
                self._m_chaos.inc(kind=kind)
            if rf.straggled and cfg.chaos.straggle_ms > 0:
                import time as _time

                _time.sleep(cfg.chaos.straggle_ms / 1e3)
        if not self._pop_sampling:
            # slot-keyed quarantine (legacy + degenerate population); the
            # sampling engine excludes quarantined LOGICAL clients at the
            # cohort draw instead
            for c in self._quarantine:
                if 0 <= c < w.shape[0]:
                    w[c] = 0.0
        if plan is not None:
            from fedrec_tpu.fed.population import QuorumFailure

            # ledger truth = the FINAL weights (slot chaos included)
            keep = (w > 0) & plan.slot_real
            events["reported"] = np.unique(plan.slot_clients[keep])
            # any real client whose weight hit zero for a reason the
            # pop-level simulation didn't see (slot chaos, participation
            # mask, slot quarantine) still owes the ledger a dropped
            # round — otherwise selected > reported+dropped+cut and the
            # sizing runbook's dropout metrics under-count real churn
            lost = (
                set(np.unique(plan.slot_clients[plan.slot_real & ~keep]).tolist())
                - set(events["reported"].tolist())
                - set(np.asarray(events["deadline_cut"]).tolist())
                - set(np.asarray(events["dropped"]).tolist())
            )
            if lost:
                events["dropped"] = np.unique(np.concatenate([
                    np.asarray(events["dropped"], np.int64),
                    np.asarray(sorted(lost), np.int64),
                ]))
            self._pop_pending[round_idx] = (plan, events)
            reporting = int(events["reported"].size)
            self._g_cohort_reporting.set(float(reporting))
            mr = cfg.fed.population.min_reports
            if 0 < mr and reporting < mr:
                raise QuorumFailure(round_idx, reporting, mr, plan.attempt)
        return w

    # ------------------------------------------------- cohort engine
    def _ensure_cohort(self, round_idx: int) -> None:
        """Sample and install the cohort for ``round_idx``. Re-entrant:
        a rollback or quorum replay re-derives the plan — same
        ``(seed, round, attempt)`` minus newly-quarantined clients —
        and the install no-ops when the occupancy is unchanged."""
        if not self._pop_engine:
            return
        from fedrec_tpu.fed.population import build_cohort_plan

        pcfg = self.cfg.fed.population
        exclude = (
            self.population.ledger.active_quarantine(round_idx)
            if self._pop_sampling
            else ()
        )
        plan = build_cohort_plan(
            self.cohort_sampler,
            self.cfg.fed.num_clients,
            round_idx,
            pcfg.over_select,
            chaos=self.chaos,
            exclude=exclude,
            attempt=self._pop_attempts.get(round_idx, 0),
            pack=self._pop_sampling,
        )
        self._current_plan = plan
        self._g_cohort_sampled.set(float(len(plan.sampled)))
        if self._pop_sampling:
            self._install_cohort(plan)

    def _template_sidecar(self, client_id: int) -> dict:
        """The pristine sidecar a first-time (or healed) client starts
        from: zeroed optimizer moments + step 0 + a per-client PRNG fold
        (logical clients get their own deterministic noise streams,
        disjoint from the slot-init splits)."""
        t = {
            f: jax.tree_util.tree_map(np.array, v)
            for f, v in self._pop_template.items()
        }
        t["rng"] = np.asarray(
            jax.random.fold_in(
                jax.random.PRNGKey(self.cfg.train.seed + 1),
                (1 << 24) + int(client_id),
            )
        )
        return t

    def _install_cohort(self, plan) -> None:
        """Load/unload around the round: write rotating-out occupants'
        sidecars (optimizer states, PRNG, step, grad accumulator) back to
        the population store, load the incoming clients' sidecars (or the
        template on first selection) into their slots. Parameters are NOT
        touched — after a param-avg sync every slot holds the global, which
        is exactly what a sampled-in client adopts. Pad slots (weight 0)
        load their duplicate's sidecar but never write back."""
        from fedrec_tpu.fed.population import SIDECAR_FIELDS

        slots = self.cfg.fed.num_clients
        persist = self.cfg.fed.population.client_state == "persist"
        new_occ = np.asarray(plan.slot_clients, np.int64)
        new_wb = (plan.slot_real & persist).astype(bool)
        changed = [
            j for j in range(slots) if self._slot_occupants[j] != new_occ[j]
        ]
        if not changed:
            self._slot_writeback = new_wb
            return
        # only the sidecar subtrees cross the host boundary — params and
        # the rest of the state never change across an install (the
        # post-sync global IS what a sampled-in client adopts), so a
        # cohort swap costs sidecar-sized transfers, not a full-model
        # D2H/H2D round-trip per round. np.array: writable host copies.
        fields = {
            f: jax.tree_util.tree_map(np.array, getattr(self.state, f))
            for f in SIDECAR_FIELDS
        }
        if persist:
            # write back EVERY persisted occupant, not only changed slots:
            # a client can stay at its old index as a weight-0 pad while
            # being re-packed real into a DIFFERENT slot — the store copy
            # must be its freshest sidecar or the new slot loads stale
            # moments and the round's training is silently discarded
            for j in range(slots):
                if self._slot_writeback[j]:
                    self.population.put_sidecar(
                        int(self._slot_occupants[j]),
                        {
                            f: jax.tree_util.tree_map(
                                lambda x, _j=j: x[_j].copy(), fields[f]
                            )
                            for f in SIDECAR_FIELDS
                        },
                    )
        for j in changed:
            cid = int(new_occ[j])
            sc = self.population.get_sidecar(cid) if persist else None
            if sc is None:
                sc = self._template_sidecar(cid)
            for f in SIDECAR_FIELDS:
                def put(dst, src, _j=j):
                    dst[_j] = src
                    return dst

                jax.tree_util.tree_map(put, fields[f], sc[f])
        self._m_cohort_swaps.inc(len(changed))
        if self._state_shardings is not None:
            # fsdp at rest: each sidecar field re-commits to its policy
            # layout, not the flat client sharding
            self.state = self.state.replace(**{
                f: jax.tree_util.tree_map(
                    lambda x, s: jax.device_put(jnp.asarray(x), s),
                    fields[f], getattr(self._state_shardings, f),
                )
                for f in SIDECAR_FIELDS
            })
        else:
            sharding = client_sharding(self.mesh, self.cfg.fed.mesh_axis)
            self.state = self.state.replace(**{
                f: jax.tree_util.tree_map(
                    lambda x: jax.device_put(jnp.asarray(x), sharding),
                    fields[f],
                )
                for f in SIDECAR_FIELDS
            })
        self._slot_occupants = new_occ.copy()
        self._slot_writeback = new_wb

    def _commit_population(self, round_idx: int) -> None:
        """Commit one COMPLETED round into the sampler's fairness state
        and the participation ledger — called only once the round's
        results are accepted, so rolled-back and quorum-discarded rounds
        never skew the schedule."""
        if not self._pop_engine:
            return
        pending = self._pop_pending.pop(round_idx, None)
        if pending is None:
            return
        plan, events = pending
        self.cohort_sampler.record(plan.sampled)
        self.population.ledger.commit(plan.sampled, events)
        for key, ctr in (
            ("dropped", self._m_pop_drops),
            ("deadline_cut", self._m_deadline_cuts),
        ):
            n = int(np.asarray(events.get(key, ())).size)
            if n:
                ctr.inc(n)
        self._pop_attempts.pop(round_idx, None)
        self._g_pop_coverage.set(self.population.ledger.coverage())
        if self._pop_sampling:
            self._g_quarantined.set(
                float(len(self.population.ledger.quarantined))
            )
        self.cohort_history.append(
            (
                round_idx,
                tuple(int(c) for c in plan.slot_clients[plan.slot_real]),
            )
        )

    def _handle_quorum_failure(self, e, round_idx: int) -> None:
        """One quorum-replay cycle: discard the round's pending ledger
        events, bump the draw attempt for the round (fresh cohort
        + fresh fault dice next pass), abort once retries are exhausted.
        The failure is raised before any dispatch, so 'replay from the
        round-entry state' needs no state restore — the entry state was
        never left."""
        pcfg = self.cfg.fed.population
        self._pop_pending = {
            k: v for k, v in self._pop_pending.items() if k < round_idx
        }
        attempts = self._pop_attempts.get(e.round_idx, 0) + 1
        self._m_quorum_replays.inc()
        self.tracer.add_span(
            "quorum_replay", dur_s=0.0, round=e.round_idx,
            reporting=e.reporting, attempt=attempts,
        )
        # a re-draw only helps if SOMETHING consumes the attempt counter:
        # the cohort draw (sampled world) or the population-level fault
        # dice. In the degenerate world without those, every replay
        # recomputes byte-identical weights (slot chaos and the
        # participation mask are keyed on round only) — burning retries
        # would just delay the same abort.
        ch = self.cfg.chaos
        attempt_sensitive = self._pop_sampling or (
            self.chaos is not None
            and (
                ch.pop_drop_rate > 0
                or ch.pop_flaky_fraction > 0
                or (ch.pop_straggle_ms > 0 and pcfg.round_deadline_ms > 0)
            )
        )
        if attempts > pcfg.quorum_retries or not attempt_sensitive:
            futile = (
                "" if attempt_sensitive else
                " (a fixed-world cohort with no population-level fault "
                "dice replays identically — retries skipped)"
            )
            raise RuntimeError(
                f"round {e.round_idx} failed quorum "
                f"({e.reporting} reporting < min_reports="
                f"{pcfg.min_reports}) on {attempts} consecutive cohort "
                f"draws{futile} — the population's dropout rate cannot "
                "sustain this quorum. Lower fed.population.min_reports, "
                "raise over_select, or relax the deadline "
                "(docs/OPERATIONS.md, 'sizing a cohort')."
            ) from e
        self._pop_attempts[e.round_idx] = attempts
        print(
            f"[trainer] WARNING: quorum failure at round {e.round_idx} "
            f"({e.reporting} < {pcfg.min_reports}); discarding the round "
            f"and replaying with a fresh cohort draw (attempt {attempts}/"
            f"{pcfg.quorum_retries})"
        )

    def _count_uplink(self, weights_np: np.ndarray) -> None:
        """Bank one synced round's modeled wire traffic: each REPORTING
        client ships one encoded update up, every client receives one
        dense fan-out down. Bytes come from a real
        wire-codec encode of the param trees (init-time; payload sizes are
        static per codec × shapes). No-op without an active codec."""
        if self._codec_bytes_per_client is None:
            return
        reporting = int((np.asarray(weights_np) > 0).sum())
        self._m_bytes_up.inc(
            float(self._codec_bytes_per_client * reporting), path="cohort"
        )
        self._m_bytes_down.inc(
            float(
                self._dense_bytes_per_client * self.cfg.fed.num_clients
            ),
            path="cohort",
        )

    def _uplink_span_args(self, weights_np: np.ndarray) -> dict:
        """Byte attrs for the aggregate span under an active codec."""
        if self._codec_bytes_per_client is None:
            return {}
        w = np.asarray(weights_np).reshape(-1, self.cfg.fed.num_clients)
        return {
            "codec": self.cfg.fed.dcn_compress,
            "bytes_up": int(self._codec_bytes_per_client * (w > 0).sum()),
            "bytes_down": int(
                self._dense_bytes_per_client
                * self.cfg.fed.num_clients
                * w.shape[0]
            ),
        }

    def _count_steps(self, n: int) -> None:
        """Step counter + the sharded-gather wire model: every dispatched
        step moves one owner-bucketed exchange across the mesh when the
        catalog is sharded (``shard.a2a_bytes_total``; 0 bytes/step when
        ``shard.table`` is off)."""
        self._m_steps.inc(n)
        if self._a2a_bytes_per_step:
            self._m_a2a_bytes.inc(float(n * self._a2a_bytes_per_step))

    def _perf_sample_components(self, round_idx: int) -> None:
        """HBM attribution at round cadence (obs.perf.hbm_components):
        bucket ``jax.live_arrays()`` bytes into params / optimizer /
        news_table / batch / other gauges.  Classification is by leaf
        identity against the CURRENT state pytrees, so donated buffers
        (no longer live) simply drop out."""
        if self.perf is None or not self.cfg.obs.perf.hbm_components:
            return
        from fedrec_tpu.obs.perf import live_array_components

        st = self.state
        table = self.token_states
        if table is None:
            table = self.news_tokens if self.mode == "finetune" else self._table
        live_array_components(
            {
                "params": (st.user_params, st.news_params),
                "optimizer": (st.opt_user, st.opt_news),
                "news_table": table,
                "batch": self._perf_last_batch,
            },
            registry=self.registry,
            tracer=self.tracer,
            fed_round=round_idx,
        )

    def _chaos_batch_keys(self, round_idx: int) -> dict | None:
        """Per-client fault vectors every chaos-enabled batch must carry
        (``train.step`` applies them at the update boundary)."""
        return (
            self.chaos.batch_keys(round_idx) if self.chaos is not None else None
        )

    def train_round(self, round_idx: int) -> RoundResult:
        """One federated round, wrapped in a ``fed_round`` host span AND a
        ``jax.profiler.StepTraceAnnotation`` carrying the same round
        number. The span's children tile it, each with the round's number:
        ``round_prologue``, a step's ``batch_build`` / ``h2d`` / ``dispatch``
        / ``step_keep`` (each with the step's index in the round: the n-th
        execution of the step's program in that round on a device trace),
        ``aggregate``, ``device_wait``, ``round_end``, ``round_epilogue``
        (from ``round_end``'s close to the round's; ``eval``, when due,
        inside it); what is left under none of them is the ``unspanned`` of
        the round's digest (``obs.tracing.RoundDigest``)."""
        import time as _time

        t0 = _time.perf_counter()
        # cohort first (and before the span, whose args describe it): the
        # draw + sidecar install define who this round even is
        self._ensure_cohort(round_idx)
        if self.perf is not None:
            self.perf.begin_round()
        tracer = self.tracer
        with tracer.span(
            "fed_round", step_num=round_idx, num_rounds=1,
            **self._round_span_args(),
        ), jax.profiler.StepTraceAnnotation("fed_round", step_num=round_idx):
            self._round_digest.begin()
            # the epilogue runs from round_end's close: the inner frame's
            # death (a round's few hundred device arrays let go) is in it
            result, t_epilogue = self._train_round_inner(round_idx)
            self._eval_if_due(result)
            # HBM gauges at the round boundary, attributed (as an instant
            # event) to this fed_round span; no-op on allocator-less CPU
            sample_device_memory(self.registry, tracer, fed_round=round_idx)
            self._perf_sample_components(round_idx)
            span_seconds = self._round_digest.close(round_idx, t_epilogue)
            now = tracer.now()
            tracer.add_span(
                "round_epilogue", dur_s=now - t_epilogue, end=now,
                round=round_idx,
            )
        wall = _time.perf_counter() - t0
        self._m_round_secs.observe(wall)
        if self.perf is not None:
            self.perf.observe_round(round_idx, wall, span_seconds)
        return result

    def _train_round_inner(self, round_idx: int) -> tuple[RoundResult, float]:
        """The round up to ``round_end``'s close, and that close on the
        tracer's clock."""
        cfg = self.cfg
        tracer = self.tracer
        # the prologue: everything up to where the first batch is asked for
        t_open = tracer.now()
        weights_np = self._round_weights(round_idx)
        weights = jnp.asarray(weights_np)
        chaos_extra = self._chaos_batch_keys(round_idx)
        sync_entry = None
        if self._sync_takes_entry:
            # the codec sync compresses ROUND DELTAS, so it needs the
            # round-entry param trees. Copied (not referenced): the step
            # dispatches below donate the state buffers, so a live alias
            # would be invalidated by the first step of the epoch.
            sync_entry = jax.tree_util.tree_map(
                jnp.copy, (self.state.user_params, self.state.news_params)
            )
        if self.flightrec is not None:
            self.flightrec.start_chunk(
                round_idx, self._entry_state(),
                {round_idx: weights_np},
            )

        round_start_global = None
        if (
            self.server_opt is not None
            or self._agg_async
            or self._agg_hier_host
        ):
            # all clients hold identical params at round entry (initial
            # replication / previous sync); client 0 IS the global model.
            # Materialized to host: the server step is a round-boundary op,
            # and the readback doubles as a barrier that keeps the device
            # program queue shallow (async dispatch of per-round reshard +
            # broadcast programs can otherwise pile up far enough to trip
            # XLA:CPU's 40 s collective-rendezvous termination deadline)
            round_start_global = jax.tree_util.tree_map(
                np.asarray, self._client0_params()
            )

        # what the round's end reads of every step's metrics, as device
        # arrays on their way to the host: the in-graph mean loss, the
        # per-client loss cells (the NaN-robust fallback), the sentry aux
        # vectors and a routed trunk's counters (``moe.*``, ``trunk.*``)
        losses: list = []
        kept = {
            "losses": losses, "raw_losses": [],
            "health_rows": [], "routing_rows": [],
        }
        # each host-deduped step's largest distinct count over its clients
        distinct: list[int] = []
        kept_arrays = 0

        def keep_metrics(metrics) -> int:
            losses.append(metrics["mean_loss"])
            kept["raw_losses"].append(metrics["loss"])
            started = [metrics["mean_loss"], metrics["loss"]]
            row = {k: v for k, v in metrics.items() if k.startswith("health.")}
            if row:
                kept["health_rows"].append(row)
                started.extend(row.values())
            row = {
                k: v for k, v in metrics.items()
                if k.startswith(("moe.", "trunk."))
            }
            if row:
                kept["routing_rows"].append(row)
                started.extend(row.values())
            # a step's few hundred bytes leave the chip behind the step,
            # while the next steps run: nothing waits here, nothing is
            # enqueued on the device, and the round's end finds them on
            # the host
            for leaf in started:
                leaf.copy_to_host_async()
            return len(started)

        if self._host_dedup and self._encode_rows is None:
            self._choose_encode_rows(round_idx * cfg.fed.local_epochs)
        step_in_round = 0
        for local_epoch in range(cfg.fed.local_epochs):
            epoch_idx = round_idx * cfg.fed.local_epochs + local_epoch
            table = self._feature_table()
            it = self._epoch_batch_iter(epoch_idx, chaos_extra, distinct)
            src = iter(it)
            t_build = tracer.now()
            # a later local epoch's table and iterator are its own opening
            tracer.add_span(
                "round_prologue" if local_epoch == 0 else "epoch_open",
                dur_s=t_build - t_open, end=t_build, round=round_idx,
            )
            try:
                while True:
                    # the consumer-side wait IS the batch-build cost when
                    # prefetch is off, and the residual (unhidden) build
                    # cost when it is on — either way the span to watch
                    try:
                        batch = next(src)
                    except StopIteration:
                        break
                    # a step's four spans tile it: each opens at the
                    # reading that closed the one before, so the few
                    # microseconds between two of them (the tracer's own
                    # bookkeeping, the recorder, the counter, the dedup
                    # entries' lookup) lie inside the later one
                    step = {"round": round_idx, "step": step_in_round}
                    t_built = tracer.now()
                    tracer.add_span(
                        "batch_build", dur_s=t_built - t_build, end=t_built,
                        epoch=epoch_idx, **step,
                    )
                    with tracer.span("h2d", since=t_built, n=1, **step) as moved:
                        # before the dispatch, so that a step that raises
                        # is in the recorder's dump and in the count
                        if self.flightrec is not None:
                            self.flightrec.record(
                                batch, round_idx, epoch_idx, step_in_round
                            )
                        step_in_round += 1
                        self._count_steps(1)
                        sharded = shard_fed_batch(self.mesh, batch, cfg)
                    # what the step will encode, by the step's own rule
                    entries = batch_host_dedup(sharded)
                    encode = (
                        {"rows": entries[0].shape[-1],
                         "slots": entries[1].shape[-1]}
                        if entries else {}
                    )
                    with tracer.span(
                        "dispatch", since=moved.end, kind="step", n=1,
                        **encode, **step,
                    ) as sent:
                        self.state, metrics = self.train_step(
                            self.state, sharded, table
                        )
                    # the step's bookkeeping on the host, behind its enqueue
                    if self._perf_keep_batch:
                        self._perf_last_batch = sharded
                    arrays = keep_metrics(metrics)
                    kept_arrays += arrays
                    t_build = tracer.now()
                    tracer.add_span(
                        "step_keep", dur_s=t_build - sent.end, end=t_build,
                        arrays=arrays, **step,
                    )
            finally:
                # a dispatch error mid-epoch must not leak the producer
                # thread (Prefetcher.close is idempotent; bare generators
                # close harmlessly)
                close = getattr(it, "close", None)
                if close is not None:
                    close()
            if self.mode == "decoupled":
                with tracer.span("news_update", round=round_idx, epoch=epoch_idx):
                    self.state, tables = self.news_update(
                        self.state, self.token_states
                    )
                    self._table = self._replicate_table(
                        jax.tree_util.tree_map(lambda x: x[0], tables)
                    )
            t_open = tracer.now()

        if self.strategy.sync_params_every_round and (
            self._agg_async or self._agg_hier_host
        ):
            # host-side aggregation topologies (agg.mode): the in-graph
            # param_sync never runs — per-client params come to host and
            # the commit/tree reduce replaces the flat collective.
            # (hierarchical + method="mean" is NOT this path: it lowers to
            # the unchanged flat collective below, bit-identical.)
            with tracer.span(
                "aggregate", round=round_idx, method=cfg.fed.robust.method,
                mode=cfg.agg.mode, **self._uplink_span_args(weights_np),
            ):
                # drain the round's step backlog via a data dependency
                # before the cross-device host gather (same XLA:CPU
                # rendezvous-deadline rationale as the FedOpt branch)
                if losses:
                    jax.block_until_ready(losses[-1])
                if self._agg_async:
                    self._agg_async_commit(
                        round_idx, weights_np, round_start_global
                    )
                else:
                    self._agg_hier_sync(
                        round_idx, weights_np, round_start_global
                    )
            self._m_robust_rounds.inc(method=cfg.fed.robust.method)
            self._count_uplink(weights_np)
        elif self.strategy.sync_params_every_round:
            with tracer.span(
                "aggregate", round=round_idx, method=cfg.fed.robust.method,
                **self._uplink_span_args(weights_np),
            ):
                if sync_entry is not None:
                    self.state = self.param_sync(
                        self.state, weights, *sync_entry
                    )
                else:
                    self.state = self.param_sync(self.state, weights)
            self._m_robust_rounds.inc(method=cfg.fed.robust.method)
            self._count_uplink(weights_np)
            if (
                cfg.fed.dcn_compress == "auto"
                and self._auto_leaf_codecs is None
                and sync_entry is not None
                and round_idx + 1 >= cfg.fed.dcn_auto_warmup
            ):
                self._pin_auto_codec_map(round_idx, sync_entry)
            if self.server_opt is not None:
                # FedOpt: the weighted mean is a proposal, not the new model —
                # the server optimizer steps the global from round_start
                # toward it (set_global_params rebroadcasts to all clients
                # and refreshes the decoupled table).
                # Drain the round's step backlog FIRST via a data dependency:
                # the client-0 slice below is a cross-device gather, and
                # dispatching it behind a full epoch of queued steps leaves
                # its rendezvous open for the whole backlog — on a time-
                # sliced XLA:CPU rig that trips the 40 s collective
                # termination deadline (observed; steps drain incrementally
                # through per-value readbacks everywhere else).
                with tracer.span("server_step", round=round_idx):
                    if losses:
                        jax.block_until_ready(losses[-1])
                    mean = jax.tree_util.tree_map(
                        np.asarray, self._client0_params()
                    )
                    new_u, new_n = self.server_opt.step(round_start_global, mean)
                    self.set_global_params(
                        jax.tree_util.tree_map(jnp.asarray, new_u),
                        jax.tree_util.tree_map(jnp.asarray, new_n),
                    )
            elif self.mode == "decoupled":
                with tracer.span("table_refresh", round=round_idx):
                    self._refresh_table()

        # the round's last device programs (the sync or the last step, and
        # a decoupled round's table refresh): the host waits, the chip works
        with tracer.span("device_wait", round=round_idx) as waited:
            jax.block_until_ready((self.state, self._table))
        # the round's end, the host's alone: the chip waits for it
        with tracer.span(
            "round_end", since=waited.end, round=round_idx,
            arrays=kept_arrays, reads=1,
        ) as ended:
            # ONE read: every step but the last sent its arrays long ago
            host = jax.device_get(kept)
            # the round's loss: the flat mean over every (step, client) cell
            train_loss = self._round_loss_mean(
                np.stack(host["losses"]), np.stack(host["raw_losses"])
            )
            # sentry digest FIRST: a non-finite sentinel is the root cause
            # the operator needs (and dumps the flight recorder) before any
            # other abort gets to describe the same broken round differently
            self._check_health(
                round_idx, health_rows=host["health_rows"],
                round_losses=[train_loss],
            )
            full_size = sum(most > self._encode_rows for most in distinct)
            if full_size:
                # those steps were served exactly, at the full size; the
                # traffic has outgrown R, so the next round's is chosen from
                # this one's
                self._m_full_size_steps.inc(full_size)
                self._set_encode_rows(max(distinct))
            if host["routing_rows"]:
                self._publish_routing(host["routing_rows"])
        return RoundResult(round_idx, train_loss), ended.end

    def _publish_routing(self, rows: list[dict]) -> None:
        """The round's counters of a routed trunk to the registry, from the
        host arrays the round's end gathered: whatever the trunk returned
        with the step's metrics (``models.bert.TextEncoder``), so a trunk
        registers its gauges by returning them. ``moe.expert_tokens`` is
        (..., routed layers, held) per step entry, leading axes steps and
        clients; ``layer`` counts the routed layers from 0."""
        reg = self.registry
        if "moe.expert_tokens" in rows[0]:
            tokens = np.concatenate([
                r["moe.expert_tokens"].reshape(
                    (-1,) + r["moe.expert_tokens"].shape[-2:]
                )
                for r in rows
            ])  # (steps x clients, layers, held)
            m_tokens = reg.counter(
                "moe.expert_tokens_total",
                "(token, choice) pairs routed to each held expert",
                labels=("layer", "expert"),
            )
            first = self.cfg.model.trunk_first_expert
            for (layer, expert), n in np.ndenumerate(tokens.sum(axis=0)):
                m_tokens.inc(float(n), layer=layer, expert=first + expert)
            reg.counter(
                "moe.full_size_chunks_total",
                "(layer, chunk)s whose pairs on the held experts overflowed "
                "the sorted buffer's small size and ran at the full one",
            ).inc(float(sum(np.sum(r["moe.full_size_chunks"]) for r in rows)))
            reg.gauge(
                "moe.absent_share",
                "share of the last round's (token, choice) pairs that fell "
                "on experts not held here",
            ).set(float(np.mean(
                [np.mean(r["moe.absent_share"]) for r in rows]
            )))
            reg.gauge(
                "moe.expert_load_max_over_mean",
                "last round's mean over steps, clients and layers of the "
                "fullest held expert's tokens over the held experts' mean",
            ).set(float(np.mean(
                tokens.max(axis=-1) / np.maximum(tokens.mean(axis=-1), 1e-9)
            )))
        if "trunk.residual_mix_err" in rows[0]:
            reg.gauge(
                "trunk.residual_mix_err_max",
                "last round's largest distance from 1 of a row or column "
                "sum of a residual mixing matrix after its last Sinkhorn "
                "iteration, over tokens, sublayers, steps and clients",
            ).set(float(max(
                np.max(r["trunk.residual_mix_err"]) for r in rows
            )))
        if "trunk.attention_scores_computed_share" in rows[0]:
            from fedrec_tpu.models.window_trunk import KINDS

            g_share = reg.gauge(
                "trunk.attention_scores_computed_share",
                "score elements the blocked attention core computed over "
                "the L^2 of a head's dense square, by kind of layer",
                labels=("kind",),
            )
            share = rows[-1]["trunk.attention_scores_computed_share"]
            for kind, value in zip(KINDS, share.reshape(-1, len(KINDS))[0]):
                g_share.set(float(value), kind=kind)

    # ------------------------------------------- aggregation topologies
    def _agg_param_stacks(self) -> tuple[Any, Any]:
        """Every client's (user, news) params to host as (C, ...) leaf
        stacks — the raw material of the host-side topologies (the state
        keeps its leading clients axis, so one fetch covers the cohort)."""
        return jax.tree_util.tree_map(
            np.asarray, (self.state.user_params, self.state.news_params)
        )

    def _agg_hier_sync(
        self, round_idx: int, weights_np: np.ndarray, round_start_global: Any
    ) -> None:
        """Hierarchical robust sync (agg.mode='hierarchical' with a
        non-mean fed.robust method): the cohort's contributions reduce up
        an agg.tree_fanout tree, the robust method applied PER TIER — the
        trajectory this produces genuinely diverges from the flat robust
        reduce (documented in docs/DESIGN.md; bounded-delta pinned).  The
        topology is rebuilt from the live cohort every round, so a
        membership shrink/rejoin reforms the tree by construction."""
        from fedrec_tpu.agg.hierarchy import (
            tree_critical_path_ms,
            tree_reduce_np,
        )

        cfg = self.cfg
        if float(np.sum(weights_np)) == 0.0:
            return  # nobody reported: every client keeps its local params
        stacks = self._agg_param_stacks()
        stats: dict = {}
        reduced = tree_reduce_np(
            stacks,
            weights_np,
            cfg.agg.tree_fanout,
            cfg.fed.robust.method,
            trim_k=cfg.fed.robust.trim_k,
            clip_norm=cfg.fed.robust.clip_norm,
            fallback_tree=round_start_global,
            stats=stats,
        )
        self._g_agg_tier_ms.set(tree_critical_path_ms(stats))
        new_u, new_n = reduced
        if self.server_opt is not None:
            # FedOpt sees the tree's output exactly where it saw the flat
            # mean: a proposal the server optimizer steps toward
            new_u, new_n = self.server_opt.step(
                round_start_global, (new_u, new_n)
            )
        self.set_global_params(
            jax.tree_util.tree_map(jnp.asarray, new_u),
            jax.tree_util.tree_map(jnp.asarray, new_n),
        )

    def _agg_async_commit(
        self, round_idx: int, weights_np: np.ndarray, round_start_global: Any
    ) -> None:
        """In-process buffered quorum commit (agg.mode='async' on a cohort
        deployment): per-slot report latencies come from the SAME seeded
        chaos distribution the population engine uses, the agg.quorum
        earliest reporters commit NOW, and the stragglers' deltas land in
        the buffer to fold staleness-weighted into the next commit — the
        cohort-simulation twin of the agg/server.py wire deployment."""
        from fedrec_tpu.agg.buffer import BufferEntry
        from fedrec_tpu.agg.commit import encode_contribution, fold_commit
        from fedrec_tpu.fed.chaos import population_report

        cfg = self.cfg
        part = np.flatnonzero(weights_np > 0)
        if part.size == 0:
            return  # nobody reported: no commit, clients keep local params
        client_ids = np.asarray(self._slot_occupants)
        _, latency = population_report(self.chaos, round_idx, client_ids)
        latency = np.asarray(latency, np.float64)

        base_leaves, treedef = jax.tree_util.tree_flatten(round_start_global)
        stack_leaves = jax.tree_util.tree_flatten(self._agg_param_stacks())[0]

        k = self._agg_policy.quorum_for(int(part.size))
        order = part[np.argsort(latency[part], kind="stable")]
        on_time, late = order[:k], order[k:]
        quorum_lat = float(latency[order[k - 1]])
        max_lat = float(latency[order[-1]])

        codec = cfg.fed.dcn_compress

        def entry(slot: int) -> BufferEntry:
            wid = str(int(client_ids[slot]))
            leaves = [
                np.asarray(s[slot] - b)
                for s, b in zip(stack_leaves, base_leaves)
            ]
            ecodec = "none"
            if codec != "none":
                # per-contribution codecs decode at push with this
                # edge's banked error-feedback residual (riding the
                # buffer sidecar, so it survives checkpoint/restore);
                # linear sketches buffer raw and fold in sketch space
                banked = (
                    self.agg_buffer.residual_for(wid)
                    if cfg.fed.dcn_error_feedback
                    else None
                )
                leaves, ecodec, new_res, _ = encode_contribution(
                    leaves,
                    codec,
                    topk_ratio=cfg.fed.dcn_topk_ratio,
                    sketch_width=cfg.fed.dcn_sketch_width,
                    sketch_seed=cfg.fed.dcn_sketch_seed,
                    residual_leaves=banked,
                )
                if new_res is not None and cfg.fed.dcn_error_feedback:
                    self.agg_buffer.bank_residual(
                        wid, self._agg_version, new_res
                    )
            return BufferEntry(
                worker=wid,
                round=round_idx,
                epoch=self.agg_buffer.epoch,
                based_on=self._agg_version,
                weight=float(weights_np[slot]),
                arrival_ms=float(latency[slot]),
                leaves=leaves,
                codec=ecodec,
            )

        # prior rounds' stragglers fold into THIS commit (staleness >= 1)
        commit_entries = self.agg_buffer.take_all()
        commit_entries += [entry(int(s)) for s in on_time]
        # the stragglers' entries MUST capture the pre-commit version:
        # their deltas are against round_start_global, so based_on has to
        # be the version that global carried — building them after the
        # bump would under-count their staleness by one commit (full
        # instead of 1/(1+s) weight, cap off by one)
        late_entries = [entry(int(s)) for s in late]
        new_leaves, stats = fold_commit(
            base_leaves,
            commit_entries,
            self._agg_version,
            self._agg_policy,
            method=cfg.fed.robust.method,
            trim_k=cfg.fed.robust.trim_k,
            clip_norm=cfg.fed.robust.clip_norm,
            sketch_seed=cfg.fed.dcn_sketch_seed,
        )
        self._agg_version = stats.version
        self._g_agg_version.set(float(stats.version))
        for e in late_entries:
            self.agg_buffer.add(e)

        self._m_agg_commits.inc()
        self._m_agg_late.inc(float(stats.late_folds))
        self._m_agg_stale.inc(float(stats.stale_drops))
        self._g_agg_staleness.set(stats.mean_staleness)
        self._g_agg_quorum_wait.set(quorum_lat - float(latency[order[0]]))
        self._g_agg_gate_saved.set(max_lat - quorum_lat)
        self._g_agg_pending.set(float(len(self.agg_buffer)))

        new_u, new_n = jax.tree_util.tree_unflatten(treedef, new_leaves)
        if self.server_opt is not None:
            # identical update semantics: the commit output is a proposal,
            # exactly like the flat weighted mean (a zero-staleness
            # all-reporting commit IS that mean)
            new_u, new_n = self.server_opt.step(
                round_start_global, (new_u, new_n)
            )
        self.set_global_params(
            jax.tree_util.tree_map(jnp.asarray, new_u),
            jax.tree_util.tree_map(jnp.asarray, new_n),
        )

    @staticmethod
    def _round_loss_mean(mean_cells: np.ndarray, loss_cells: np.ndarray) -> float:
        """The round's train loss. Healthy rounds: the flat mean over the
        in-graph pmean cells — bit-identical to pre-robust reporting. When
        any cell is non-finite (a chaos/quarantined client), the pmean is
        NaN for EVERY client (the collective blends the poison), so the
        metric falls back to the mean over the finite PER-CLIENT loss
        cells: a NaN client's cells are the health sentry's signal
        (counted there), not the cohort's progress metric."""
        mean_cells = mean_cells.reshape(-1)
        if np.isfinite(mean_cells).all():
            return float(mean_cells.mean())
        loss_cells = loss_cells.reshape(-1)
        finite = loss_cells[np.isfinite(loss_cells)]
        return float(finite.mean()) if finite.size else float("nan")

    def _eval_if_due(self, result: RoundResult) -> None:
        """Round-cadence evaluation (train.eval_every)."""
        if self.valid_ix is None:
            return
        if (result.round_idx + 1) % self.cfg.train.eval_every != 0:
            return
        protocol = self.cfg.train.eval_protocol  # validated in __init__
        with self.tracer.span(
            "eval", round=result.round_idx, protocol=protocol
        ):
            # sliced-eval telemetry rides the full-pool protocols only —
            # the sampled protocol re-draws negatives per epoch, so its
            # per-slice numbers would carry sampling noise the banked
            # quality gate could never threshold against
            q = None
            if self.quality is not None and protocol in ("full", "last4"):
                q = self._begin_quality_eval()
            if protocol == "full":
                result.val_metrics = self.evaluate_full(_quality=q)
            elif protocol == "last4":
                result.val_metrics = self.evaluate_full(last_k=4, _quality=q)
            else:
                result.val_metrics = self.evaluate()
            if q is not None:
                self._finish_quality_eval(
                    result.round_idx, q, result.val_metrics
                )

    def evaluate(self, client: int | None = None) -> dict[str, float]:
        """Mean validation metrics over all impressions (fixes the reference's
        last-sample-only bug, ``client.py:171``).

        ``client=None`` (default) resolves the evaluation target explicitly:
        the client-0 fast path when all clients are in sync, else the mean
        of per-client metrics (see :meth:`_aggregate_eval` — VERDICT r2
        Weak #3). Pass an explicit ``client`` index to score one client.

        Candidates are 1 positive + ``npratio`` sampled negatives (the
        reference's per-epoch ``validate``, ``client.py:149-171``); batches
        keep one static shape, with the final batch's wrap-around padding
        trimmed from the mean. For the deterministic published-table protocol
        use :meth:`evaluate_full`.
        """
        assert self.valid_ix is not None, "no validation samples"
        if client is None:
            return self._aggregate_eval(lambda c: self.evaluate(client=c))
        user_params, news_params = self._client_params(client)
        table = self._corpus_for(news_params, client)
        n = len(self.valid_ix)
        bsz = min(n, 256)
        vb = TrainBatcher(
            self.valid_ix,
            batch_size=bsz,
            npratio=self.cfg.data.npratio,
            shuffle=False,
            drop_remainder=False,
            seed=0,
        )
        sums: dict[str, float] = {}
        count = 0
        for batch in vb.epoch_batches(0):
            out = self.eval_step(
                user_params,
                table,
                {
                    "candidates": batch.candidates,
                    "history": batch.history,
                    "labels": batch.labels,
                },
            )
            valid_n = min(bsz, n - count)  # trim wrap-around pad rows
            for k, v in out.items():
                sums[k] = sums.get(k, 0.0) + float(jnp.sum(v[:valid_n]))
            count += valid_n
        return {k: v / count for k, v in sums.items()}

    def evaluate_full(
        self,
        last_k: int | None = None,
        client: int | None = None,
        _quality: dict | None = None,
    ) -> dict[str, float]:
        """Deterministic evaluation over each impression's FULL negative pool.

        The protocol behind the reference's published MIND table (AUC 68.42
        etc. — full-pool ``evaluation_split``, reference
        ``evaluation_functions.py:33-47``). ``last_k`` keeps only each pool's
        LAST k negatives — ``last_k=4`` reproduces the reference client's
        deterministic per-round validation slice (``client.py:159-160``).

        ``client=None`` resolves like :meth:`evaluate`: client-0 fast path
        when clients are in sync, else mean of per-client metrics.

        Impressions with an empty (post-slice) pool are skipped, as the
        reference's try/except does. One compile: static (B, P) shapes with
        padding masked out of every mean.

        ``_quality`` (``_begin_quality_eval``'s session dict) routes the
        pass through the quality-instrumented eval step and folds each
        batch's per-impression metrics into the slice accumulator and the
        score/calibration sums.  Diverged cohorts accumulate EVERY
        client's pass into the one session — each client scores the same
        impression set, so pooling equals the mean-of-means the corpus
        metric reports.  ``None`` (the default, and always when
        ``obs.quality.enabled=false``) runs the pre-quality program
        untouched.
        """
        assert self.valid_ix is not None, "no validation samples"
        if client is None:
            return self._aggregate_eval(
                lambda c: self.evaluate_full(
                    last_k=last_k, client=c, _quality=_quality
                )
            )
        user_params, news_params = self._client_params(client)
        table = self._corpus_for(news_params, client)

        ix = self.valid_ix
        n = len(ix)
        pools = ix.neg_pools
        lens = ix.neg_lens.astype(np.int64)
        if last_k is not None:
            # keep each pool's last k real negatives, left-aligned: row i
            # becomes pools[i, max(0, len-k) : len] (+ right padding)
            p = min(last_k, pools.shape[1])
            start = np.maximum(lens - p, 0)[:, None]
            idx = np.minimum(start + np.arange(p)[None, :], pools.shape[1] - 1)
            pools = np.take_along_axis(pools, idx, axis=1)
            lens = np.minimum(lens, p)
        P = max(1, pools.shape[1])
        mask = (np.arange(P)[None, :] < lens[:, None]).astype(np.float32)

        bsz = min(n, 256)
        if self.mesh.size > 1:
            # the sharded step splits the batch axis over the mesh evenly
            bsz = max(self.mesh.size, bsz - bsz % self.mesh.size)
        pad = (-n) % bsz
        def _pad(a):
            return np.concatenate([a, np.repeat(a[:1], pad, axis=0)]) if pad else a

        pos_a = _pad(ix.pos)
        pools_a = _pad(pools.astype(np.int32))
        mask_a = _pad(mask)
        his_a = _pad(ix.history)
        keep_a = _pad((lens > 0).astype(np.float32))
        if pad:
            keep_a[n:] = 0.0  # padded rows never count

        step = self.full_eval_step if _quality is None else self.full_eval_step_q
        if _quality is not None:
            # one pass per evaluated client: _finish_quality_eval divides
            # the pooled counts back down so published impression counts
            # stay per-validation-set (the n the noise threshold is quoted
            # against), not ×clients on a diverged cohort
            _quality["passes"] = _quality.get("passes", 0) + 1
        sums = {k: 0.0 for k in ("auc", "mrr", "ndcg5", "ndcg10")}
        kept = 0.0
        for b in range(0, n + pad, bsz):
            sl = slice(b, b + bsz)
            batch = {
                "pos": pos_a[sl],
                "neg_pools": pools_a[sl],
                "neg_mask": mask_a[sl],
                "history": his_a[sl],
            }
            if _quality is not None:
                batch["keep"] = keep_a[sl]
            out = step(user_params, table, batch)
            w = keep_a[sl]
            for k in sums:
                sums[k] += float(jnp.sum(out[k] * w))
            kept += float(w.sum())
            if _quality is not None:
                from fedrec_tpu.eval.metrics import QUALITY_SUM_KEYS

                _quality["acc"].add(
                    b, {k: np.asarray(out[k]) for k in sums}, np.asarray(w)
                )
                qs = _quality["sums"]
                for k in QUALITY_SUM_KEYS:
                    qs[k] = qs.get(k, 0.0) + np.asarray(out[k], np.float64)
        if kept == 0:
            raise ValueError("no impression has a non-empty negative pool")
        return {k: v / kept for k, v in sums.items()}

    # ------------------------------------------------------- quality layer
    def _begin_quality_eval(self) -> dict:
        """One sliced-eval session: the slice accumulator (definitions
        built once per run — fixed, seeded) plus the score/calibration
        partial-sum dict the eval loop folds batches into."""
        from fedrec_tpu.obs.quality import (
            SlicedEvalAccumulator,
            build_slice_defs,
        )

        if self._slice_defs is None:
            self._slice_defs = build_slice_defs(
                self.valid_ix, self.cfg.obs.quality
            )
        return {
            "acc": SlicedEvalAccumulator(self._slice_defs, len(self.valid_ix)),
            "sums": {},
        }

    def _finish_quality_eval(
        self, round_idx: int, q: dict, val_metrics: dict[str, float]
    ) -> None:
        """Publish the session: per-slice gauges (+ skip counter), the
        corpus quartet under ``slice="all"``, the score/calibration
        digest, and the per-client quality-outlier digest (informational —
        composes with quarantine's ignore set, never triggers it)."""
        slices, skipped = q["acc"].finalize()
        # a diverged cohort pooled every client's pass into the session:
        # the weighted MEANS are invariant (each pass covers the same
        # impression set), but the raw counts/sums are ×passes — scale
        # them back so every published n means validation impressions
        passes = max(int(q.get("passes", 1)), 1)
        if passes > 1:
            for m in slices.values():
                m["count"] /= passes
            q["sums"] = {k: v / passes for k, v in q["sums"].items()}
        self.quality.publish_slices(slices, skipped)
        # the category family partitions the impression set, so its counts
        # sum to the kept (scoreable) total — the honest n for slice="all"
        kept = sum(
            m["count"] for n, m in slices.items() if n.startswith("category=")
        ) or float(len(self.valid_ix))
        self.quality.publish_corpus(val_metrics, count=kept)
        if q["sums"]:
            self.quality.publish_distribution(q["sums"])
        if self.cfg.obs.quality.per_client:
            outliers = self.quality.digest_clients(
                round_idx,
                self.last_per_client_metrics,
                ignore_clients=set(self._quarantine),
                shared=val_metrics,
            )
            # surfaced on the HealthMonitor next to the norm-based flags
            # (one triage surface); informational — never a trigger
            self.health.last_quality_outliers = outliers
            if self.watch is not None:
                self.watch.ingest_quality_outliers(outliers)

    # ------------------------------------------------------------------
    def run(self) -> list[RoundResult]:
        cfg = self.cfg
        history: list[RoundResult] = []
        from fedrec_tpu.fed.population import QuorumFailure

        # train.profile traces land inside obs.dir when one is configured
        # (discoverable next to the artifact trio) instead of the
        # hardcoded /tmp default; the logdir is pointed to from
        # metrics.jsonl either way a trace was captured
        profile_logdir = (
            str(self._obs_dir / "jax_profile")
            if cfg.train.profile and self._obs_dir is not None
            else None
        )
        try:
            with profile_if(cfg.train.profile, profile_logdir) as plogdir:
                if plogdir is not None and self._obs_dir is not None:
                    import time as _time

                    from fedrec_tpu.obs.perf import append_jsonl_record

                    append_jsonl_record(self._obs_dir / "metrics.jsonl", {
                        "kind": "profile_trace",
                        "logdir": plogdir,
                        "ts": _time.time(),
                    })
                round_idx = self.start_round
                while round_idx < cfg.fed.rounds:
                    if self.perf is not None:
                        # capture windows open at the round boundary — a
                        # window that holds this round starts a
                        # jax.profiler trace under obs.dir
                        self.perf.capture_before_round(round_idx)
                    # rollback target: the state every client held at
                    # round entry — one blocking host copy per round
                    # is the price of replayability (same cost profile as
                    # obs.health.snapshot_state); no-op unless recover
                    self._capture_recovery_state()
                    try:
                        result = self.train_round(round_idx)
                    except RoundRecovery as e:
                        self._rollback_and_quarantine(e.trigger, round_idx)
                        continue  # replay the same round
                    except QuorumFailure as e:
                        # raised BEFORE any dispatch (weights are built at
                        # round entry), so the round's entry state
                        # was never left — replay is a fresh cohort draw
                        self._handle_quorum_failure(e, round_idx)
                        continue
                    self._round_retries = 0
                    history.append(result)
                    # commit BEFORE _after_round: a save-cadence
                    # snapshot's population sidecar must describe the
                    # schedule INCLUDING this round
                    self._commit_population(round_idx)
                    self._after_round(result)
                    self._tick_quarantine()
                    if self.perf is not None:
                        # the window closes AFTER the round's host-side
                        # bookkeeping so checkpoint/eval cost is captured
                        self.perf.capture_after_round(round_idx)
                    round_idx += 1
            if self.snapshots is not None:
                self.snapshots.wait()  # settle async saves before handing back
        except BaseException as e:
            # forensics on EVERY failing exit path: an exception that never
            # reached a round-end health check (a dispatch error) still
            # dumps the batch ring + round-entry state
            self._flightrec_on_exception(e)
            raise
        finally:
            # a still-open perf capture window must stop (and write its
            # pointer record) on every exit path, before the artifact
            # dump below appends the final registry snapshot — and the
            # retained HBM-attribution batch must not outlive the run
            if self.perf is not None:
                self.perf.close()
                self._perf_last_batch = None
            # artifacts on EVERY exit path: a run that died to a
            # mid-round error is exactly the run whose
            # trace/registry state is needed — and the failing round never
            # reached its _after_round snapshot
            if self._obs_dir is not None:
                try:
                    paths = dump_artifacts(
                        self._obs_dir, registry=self.registry,
                        tracer=self.tracer,
                    )
                    print(
                        f"[trainer] obs artifacts: {paths['metrics']} "
                        f"{paths['trace']} {paths['prometheus']}"
                    )
                except Exception as e:  # noqa: BLE001 — never mask the training error
                    print(f"[trainer] could not write obs artifacts: "
                          f"{type(e).__name__}: {e}")
            if self.fleet_pusher is not None:
                # final push on every exit path (never raises; a dead
                # collector only counts a failure)
                self.fleet_pusher.push(final=True)
            try:
                self.logger.finish()
            except Exception as e:  # noqa: BLE001 — a wandb flush error must
                # not displace the exception that actually ended training
                print(f"[trainer] logger.finish failed: "
                      f"{type(e).__name__}: {e}")
        return history

    def _after_round(self, result: RoundResult) -> None:
        """Per-round host bookkeeping: metric logging, best-AUC snapshot,
        cadence snapshots (+ FedOpt sidecar)."""
        cfg = self.cfg
        round_idx = result.round_idx
        self._m_rounds.inc()
        self._m_round_loss.set(result.train_loss)
        log = {"round": round_idx, "training_loss": result.train_loss}
        if self._eps_schedule is not None:
            # rounds completed so far INCLUDING resumed ones: the privacy
            # budget composes over the whole trajectory, not this process's
            # uptime
            eps = self._eps_schedule(round_idx + 1)
            self._m_eps.set(eps)
            log["privacy.epsilon_spent"] = round(eps, 6)
        if self.perf is not None and self.perf.last_round is not None:
            # the latest round digest rides the per-round record — the
            # MFU trend fedrec-obs perf renders
            log.update({
                k: v for k, v in self.perf.last_round.items() if k != "round"
            })
        if result.val_metrics:
            # ONE key scheme (val_<metric>), Prometheus-sanitizable as-is —
            # the historical valid_auc/valid_mrr vs val_ndcg@5 mix forced
            # every reader to know both spellings and the '@' keys to be
            # mangled on exposition. fedrec-obs report keeps a legacy-key
            # fallback so pre-rename artifacts still render.
            named = {
                "validation_loss": result.val_metrics.get("loss"),
                "val_auc": result.val_metrics.get("auc"),
                "val_mrr": result.val_metrics.get("mrr"),
                "val_ndcg5": result.val_metrics.get("ndcg5"),
                "val_ndcg10": result.val_metrics.get("ndcg10"),
            }
            # the full-pool protocols have no loss key — omit, don't
            # log null
            log.update({k: v for k, v in named.items() if v is not None})
        self.logger.log(round_idx, log)
        auc = (
            result.val_metrics.get("auc")
            if result.val_metrics else None
        )
        if (
            self.best_snapshots is not None
            and auc is not None
            and (self._best_auc is None or auc > self._best_auc)
        ):
            import json as _json

            from fedrec_tpu.train.checkpoint import atomic_write_bytes

            # a failed best-write must not kill training (the
            # round-cadence config.json persistence has the same
            # policy) and must not advance _best_auc — a later
            # round between the persisted and the failed best
            # still deserves a save
            try:
                # blocking: the marker must never describe a
                # snapshot that is still in flight
                with self.tracer.span(
                    "checkpoint", round=round_idx, kind="best"
                ):
                    self.best_snapshots.save(
                        round_idx, self.state, wait=True
                    )
                atomic_write_bytes(
                    self.best_snapshots.directory / "best.json",
                    _json.dumps(
                        {"round": round_idx, "auc": float(auc)}
                    ).encode(),
                )
                atomic_write_bytes(
                    self.best_snapshots.directory / "config.json",
                    cfg.to_json().encode(),
                )
                self._best_auc = float(auc)
            except OSError as e:
                print(
                    f"[trainer] could not persist best snapshot "
                    f"at round {round_idx}: {e}"
                )
        if self.snapshots is not None and (
            (round_idx + 1) % cfg.train.save_every == 0
            or round_idx == cfg.fed.rounds - 1
        ):
            # blocking save under FedOpt: the sidecar must never be
            # newer than the orbax snapshot it pairs with (a crash
            # between an async save and the sidecar write would
            # resume round-r momentum against round r-k params)
            with self.tracer.span(
                "checkpoint", round=round_idx, kind="cadence"
            ):
                # blocking also under the cohort engine: the population
                # sidecar (like FedOpt's) must never be newer than the
                # snapshot it pairs with, or a crash between the two
                # resumes round-r cohort schedule against round r-k params
                self.snapshots.save(
                    round_idx, self.state,
                    wait=self.server_opt is not None or self._pop_engine
                    or self._agg_async,
                )
                if self.server_opt is not None:
                    from fedrec_tpu.train.checkpoint import atomic_write_bytes

                    atomic_write_bytes(
                        self.snapshots.directory / "server_opt_state.msgpack",
                        self.server_opt.state_bytes(round_idx),
                    )
                if self._agg_async:
                    # buffered late contributions pair with THIS snapshot:
                    # same blocking discipline as the FedOpt sidecar (the
                    # sidecar must never be newer than the snapshot, or a
                    # crash between the two would fold round-r late deltas
                    # against round r-k params on resume)
                    from fedrec_tpu.agg.buffer import AGG_BUFFER_SIDECAR
                    from fedrec_tpu.train.checkpoint import atomic_write_bytes

                    atomic_write_bytes(
                        self.snapshots.directory / AGG_BUFFER_SIDECAR,
                        self.agg_buffer.state_bytes(
                            round_idx, self._agg_version
                        ),
                    )
                if self._pop_engine:
                    from fedrec_tpu.train.checkpoint import (
                        POPULATION_SIDECAR,
                        atomic_write_bytes,
                        population_state_bytes,
                    )

                    atomic_write_bytes(
                        self.snapshots.directory / POPULATION_SIDECAR,
                        population_state_bytes(
                            self.cohort_sampler.state_dict(),
                            self.population.ledger.state_dict(),
                            self._slot_occupants,
                            self._slot_writeback,
                            round_idx,
                        ),
                    )
                if self.table_spec is not None and self.token_states is not None:
                    # sharded-catalog recovery source: the TRUE rows,
                    # host-gathered, written ONCE (the table is frozen in
                    # table/head modes) — a shrink that loses a shard's
                    # row blocks reloads them from here instead of losing
                    # them (shard.table.recover_table_rows)
                    from fedrec_tpu.train.checkpoint import (
                        NEWS_TABLE_CHECKPOINT,
                        gather_for_save,
                        save_table_checkpoint,
                    )

                    tbl_path = (
                        self.snapshots.directory / NEWS_TABLE_CHECKPOINT
                    )
                    if not tbl_path.exists():
                        rows = np.asarray(
                            gather_for_save(self.token_states)
                        )[: self.table_spec.num_rows]
                        save_table_checkpoint(self.snapshots.directory, rows)
        if (
            self._obs_dir is not None
            and (round_idx + 1) % max(cfg.obs.snapshot_every, 1) == 0
        ):
            # size-based rotation before the append (obs.jsonl_max_mb):
            # snapshots are the event log's bulk on long runs
            rotate_jsonl(self._obs_dir / "metrics.jsonl", cfg.obs.jsonl_max_mb)
            self.registry.write_snapshot(self._obs_dir / "metrics.jsonl")
        if self.watch is not None:
            # one watch tick per round, fed the round's log record, BEFORE
            # the fleet push so this round's transitions ride this push
            self.watch.evaluate(record=log)
        if self.fleet_pusher is not None:
            self.fleet_pusher.maybe_push(round_idx)
