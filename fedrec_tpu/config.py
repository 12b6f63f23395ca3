"""Configuration system for fedrec_tpu.

The reference configures each driver through bare positional ``sys.argv``
(reference ``main.py:178-184``, ``client.py:297-305``, ``server.py:108-113``)
plus hardcoded constants scattered through the code (lr 5e-5 ``model.py:22-23``;
npratio=4 / max_his_len=50 ``dataset.py:8-9``; DP constants C=2, delta=1e-5
``client.py:220-224``). Here everything is a typed dataclass tree with
``key=value`` CLI overrides and asdict round-tripping for checkpoint metadata.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any


@dataclass
class DataConfig:
    """Dataset and sampling knobs (reference ``dataset.py:8-9,69-86``)."""

    data_dir: str = "UserData"
    dataset: str = "mind"              # "mind" | "adressa" | "synthetic"
    npratio: int = 4                   # negatives per impression
    max_his_len: int = 50              # click-history cap (pad id 0 = <unk>)
    max_title_len: int = 50            # tokens per news text (a title: 50; a body: 1,024)
    batch_size: int = 64
    shuffle: bool = True
    seed: int = 0
    drop_remainder: bool = True        # static shapes under jit
    # host-side batch assembly in the native C++ engine (threaded; see
    # native/fedrec_data.cpp). Falls back to the Python batcher if the
    # library is unavailable.
    native_loader: bool = False
    # cross-PROCESS disjoint data sharding (coordinator deployment): this
    # host trains shard `shard_index` of `num_shards` equal-as-possible
    # slices dealt from a (data.seed)-seeded permutation. The coordinator
    # CLI defaults these from (process_id, num_processes) so each host
    # trains disjoint data — the reference's DistributedSampler-by-rank
    # (reference main.py:166, client.py:243-249). 0 = unset (the
    # coordinator auto-shards); an EXPLICIT num_shards=1 opts out — every
    # host trains the full corpus even multi-process.
    num_shards: int = 0
    shard_index: int = 0
    # tile the unique token-state gather + text encode in lax.map chunks of
    # this many rows, with the chunk body rematerialized in backward: the
    # (unique, L, bert_hidden) gather result is never materialized in HBM
    # beyond one chunk (peak activation memory drops from O(unique*L*Dh) to
    # O(chunk*L*Dh)), at the price of re-gathering in backward. Exact same
    # math (row-wise encode). 0 = off; only bites when unique slots > chunk.
    gather_chunk: int = 0
    # bounded host-side prefetch: build batch t+1 on a producer thread while
    # step t runs on device, keeping the dispatch queue non-empty across an
    # epoch. Value = queue depth (2 = classic double buffering); 0 = off.
    # Batch order and contents are identical with prefetch on or off
    # (tests/test_prefetch.py).
    prefetch_batches: int = 0


@dataclass
class ModelConfig:
    """Two-tower model hyperparameters (reference ``encoder.py``, ``attention.py``)."""

    news_dim: int = 400                # news/user embedding dim
    num_heads: int = 20                # user-encoder MHA heads
    head_dim: int = 20                 # d_k = d_v
    query_dim: int = 200               # additive-attention query hidden
    dropout_rate: float = 0.2
    # user-tower family:
    #   "mha" — self-attention encoder (reference parity, encoder.py:36-56)
    #   "gru" — recurrent encoder (LSTUR-family, An et al. 2019): GRU over
    #           the click sequence + additive-attention pooling of the
    #           hidden states. Order-aware where MHA+pool is permutation-
    #           equivariant; lax.scan-based, so jit-friendly on TPU. Not
    #           combinable with fed.seq_shards>1 (sequence parallelism is
    #           attention-specific).
    user_tower: str = "mha"
    # text-head family (the trainable tail over frozen trunk token states):
    #   "additive" — additive attention + linear (reference encoder.py:20-29)
    #   "cnn"      — Conv1D + ReLU + additive pooling (NAML family, Wu et
    #                al. 2019). head/table modes only; finetune keeps the
    #                additive head.
    text_head_arch: str = "additive"
    cnn_kernel: int = 3                # CNN head context window
    bert_hidden: int = 768             # DistilBERT hidden size
    # "table"    — gather a precomputed news-embedding table (fast path)
    # "head"     — frozen-trunk token states + trainable additive-attn/linear head
    # "finetune" — full DistilBERT fine-tuned in-loop (BASELINE config 5)
    text_encoder_mode: str = "table"
    # trunk family for "finetune" mode:
    #   "distilbert"    — dense bidirectional post-LN trunk (models/bert.py)
    #   "sparse_expert" — causal decoder with routed ReGLU experts, grouped-
    #                     query attention, global and sliding layers
    #                     (models/sparse_trunk.py: SparseTrunkConfig holds
    #                     the published widths; bert_hidden, trunk_layers,
    #                     trunk_heads, trunk_ffn (one expert's width) and
    #                     trunk_vocab (vocabulary rows held) are read from
    #                     here, as for distilbert)
    #   "latent_moe"    — causal decoder with latent (low-rank) attention,
    #                     a four-stream residual mixed by doubly stochastic
    #                     matrices, leading dense layers, then a sigmoid
    #                     router over SwiGLU experts beside a shared expert
    #                     (models/latent_trunk.py: LatentTrunkConfig holds
    #                     the published widths; reads the same fields, with
    #                     trunk_ffn one routed expert's width, and
    #                     trunk_dense_layers)
    #   "window_moe"    — causal decoder whose full and window layers differ
    #                     in head count, rotary and mask (grouped-query
    #                     attention with normed q and k and a softplus gate
    #                     a head, over a blocked core that holds no L x L
    #                     array), a leading dense layer, then a sigmoid
    #                     router over SwiGLU experts beside a shared expert;
    #                     for texts longer than its window
    #                     (models/window_trunk.py: WindowTrunkConfig holds
    #                     the published widths, the two head counts and the
    #                     layer kinds; reads the same fields, with
    #                     trunk_heads the full layers' query heads)
    text_trunk: str = "distilbert"
    # routed trunks only: the share of every layer's experts held here (one
    # chip of an expert-parallel group): ids first..first+held-1 of the
    # family's experts; the router still scores all of them. 0 held = all.
    trunk_first_expert: int = 0
    trunk_experts_held: int = 0
    # latent_moe and window_moe: the leading layers whose feed-forward is
    # dense (published: latent_moe's first_k_dense_replace = 2, window_moe's
    # one leading "dense" entry of mlp_layer_types); the others are routed
    trunk_dense_layers: int = 2
    # trunk architecture for "finetune" mode (defaults = distilbert-base;
    # shrink for tests). dim is bert_hidden above.
    trunk_layers: int = 6
    trunk_heads: int = 12
    trunk_ffn: int = 3072
    trunk_vocab: int = 30522
    trunk_dropout: float = 0.1         # trunk hidden+attention dropout (HF default)
    trunk_remat: bool = True           # jax.checkpoint per block (HBM for FLOPs)
    # numerics: the reference uses unstabilized exp-normalization
    # (``attention.py:19,39``) — a defect; we default to stable softmax and keep
    # the knob for bit-parity experiments.
    stable_softmax: bool = True
    # score->loss parity: CE over sigmoid(scores) (reference ``model.py:123-126``)
    sigmoid_before_ce: bool = True
    dtype: str = "float32"             # compute dtype for encoders ("bfloat16" on TPU)
    # Route hot ops through the ISOLATED Pallas kernels. EXPERIMENTAL
    # OPT-IN: in the last chip measurement (jax 0.4.37; its artifact is no
    # longer in the tree, and nothing is measured on the current one —
    # ROADMAP S4) the XLA dense path won at every size that fit (20-dim
    # heads pad to 128 lanes), and in the one regime needing O(L)
    # attention — training at H>=2048, dense fwd+bwd OOM — pallas was
    # AHEAD of the chunked scan (255 vs 299 ms fwd+bwd at H=2048). For the reference H=50 scale the
    # measured answer is fuse_hot_path below — isolated kernels lose to
    # per-call overhead there (50x at H=50 fwd); only a fused chain can
    # amortize the launch.
    use_pallas: bool = False
    # Fuse the step's hot chain into two Pallas kernels
    # (fedrec_tpu.ops.fused_hot_path): (1) frozen-table gather + text-head
    # encode — token rows stream HBM->VMEM per unique id, the (U, T, Dh)
    # gather never materializes; (2) user-tower QKV + per-head attention +
    # additive pool + candidate scoring in one VMEM residency (serving's
    # encode_user reuses it). bf16 operands / f32 accumulation; exact
    # module epsilon semantics; blocked custom VJPs; interpret-mode CPU
    # fallback so tier-1 runs the same code path. Requires
    # user_tower='mha' + stable_softmax; kernel (1) additionally needs
    # text_head_arch='additive' (cnn heads keep the dense gather+encode).
    # Not combinable with seq_shards>1, in-device cohorts (k>1), or
    # per-example DP-SGD — the step builders fail fast. docs/DESIGN.md §5h.
    fuse_hot_path: bool = False
    # user-encoder self-attention implementation:
    #   "auto"    — EVIDENCE-DRIVEN when benchmarks/pallas_bench.py has
    #               written a provenance-clean artifact for the current
    #               jax version (none is in the tree today) and a TPU
    #               backend is live: the measured
    #               winner for the nearest (H, dtype) regime is picked
    #               (fedrec_tpu.ops.autotune). Otherwise the static
    #               defaults: dense XLA up to attn_chunk_threshold history
    #               items, then blockwise lax.scan (O(L) memory); pallas
    #               if use_pallas (explicit opt-in still wins over
    #               evidence).
    #   "dense" | "chunked" | "pallas" — force one path
    attn_impl: str = "auto"
    attn_chunk_threshold: int = 1024


@dataclass
class OptimConfig:
    """Reference uses two inner Adams at lr 5e-5 (``model.py:22-23``)."""

    user_lr: float = 5e-5
    news_lr: float = 5e-5
    optimizer: str = "adam"
    grad_clip_norm: float = 0.0        # 0 = off (DP clipping is separate)
    # "constant" | "cosine" (optax.cosine_decay_schedule over decay_steps
    # optimizer updates, floored at lr * lr_min_frac). Set decay_steps =
    # rounds * local_epochs * steps_per_epoch; 0 disables the schedule.
    lr_schedule: str = "constant"
    decay_steps: int = 0
    lr_min_frac: float = 0.1


@dataclass
class RobustConfig:
    """Byzantine-robust aggregation + quarantine/rollback recovery.

    ``method`` selects the round-end aggregator (``fedrec_tpu.fed.robust``),
    compiled INTO the same shard_map program as the plain FedAvg sync so it
    composes with DP noise (applied per client, pre-sync) and FedOpt server
    optimizers (which step the post-aggregation global):

      * "mean"         — participation-weighted mean (FedAvg; the default,
                         bit-identical to pre-robust behavior)
      * "clip"         — norm-clipped mean: each client's deviation from the
                         coordinate-wise cohort median is clipped to
                         ``clip_norm`` (global L2 over both towers) before
                         the weighted mean; non-finite contributions clip
                         to zero. Bounds any one client's round influence
                         by clip_norm / num_participants.
      * "trimmed_mean" — coordinate-wise: drop the ``trim_k`` highest and
                         lowest finite participant values per coordinate,
                         mean the rest (unweighted over kept participants)
      * "median"       — coordinate-wise median over finite participants

    ``recover`` turns the PR-4 health sentry's detection into reaction:
    on a non-finite update or an outlier client (round-mean update-norm >
    ``obs.health.outlier_k`` x cohort median) the Trainer quarantines the
    client (participation weight 0 for ``quarantine_rounds`` rounds),
    rolls the cohort back to the round-entry state, and replays the round
    — up to ``max_retries`` distinct quarantines per round, then the
    existing flight-recorder abort. A quarantined client rejoins healed:
    params reset to the global, optimizer moments zeroed.
    """

    method: str = "mean"               # "mean" | "clip" | "trimmed_mean" | "median"
    trim_k: int = 1                    # coords trimmed from EACH end (trimmed_mean)
    clip_norm: float = 10.0            # global-L2 clip for method="clip"
    recover: bool = False              # quarantine + rollback instead of abort
    quarantine_rounds: int = 3         # rounds a flagged client sits out
    max_retries: int = 2               # rollback/replay attempts per round


@dataclass
class PopulationConfig:
    """Cross-device cohort engine (``fedrec_tpu.fed.population``).

    Separates *logical clients* (``num_clients`` of them, per-client state
    kept host-side) from the physical device slots (``fed.num_clients``,
    the mesh's cohort layout): each round a seeded
    :class:`~fedrec_tpu.fed.sampling.CohortSampler` draws
    ``ceil(slots * over_select)`` logical clients, the survivors of the
    (chaos-simulated) dropout are packed into the slots, and clients whose
    simulated report latency exceeds ``round_deadline_ms`` are cut with
    participation weight 0.  Below ``min_reports`` reporting clients the
    round is discarded and replayed with a fresh draw (the quorum policy);
    ``quorum_retries`` bounds the re-draws before the run aborts.

    ``num_clients == fed.num_clients`` is the degenerate (cross-silo)
    configuration: every client is selected every round, the data path and
    trajectory are bit-identical to a run without a population section
    (pinned in ``tests/test_population.py``).  ``num_clients`` above the
    slot count turns on real per-round sampling: each logical client then
    OWNS a static, seeded, equal-size shard of the corpus (non-IID-ready),
    and its optimizer sidecar persists across selections
    (``client_state="persist"``) or resets to the template each time
    (``"reset"`` — stateless cross-device semantics).
    """

    num_clients: int = 0               # 0 = off; == slots = degenerate; > slots = sampled
    sampler: str = "uniform"           # "uniform" | "weighted" | "skew"
    seed: int = 0                      # cohort-draw seed (schedule identity)
    over_select: float = 1.0           # sample ceil(slots * over_select) candidates
    round_deadline_ms: float = 0.0     # report-latency cut; 0 = no deadline
    min_reports: int = 0               # quorum: fewer reporters discards the round
    quorum_retries: int = 3            # re-draws per round before aborting
    client_state: str = "persist"      # "persist" sidecars across selections | "reset"
    # sidecar residency: how many clients' optimizer sidecars stay in host
    # RAM; above the cap the least-recently-selected spill to disk
    # (``spill_dir``, default <snapshot_dir>/popspill). 0 = unbounded.
    resident_cap: int = 0
    spill_dir: str = ""


@dataclass
class ElasticConfig:
    """Elastic membership (``fedrec_tpu.parallel.membership``).

    Activated by ``fedrec-coordinator --membership HOST:PORT`` (which sets
    ``enabled``): the deployment's world size stops being the static
    ``--num-processes`` and becomes a *membership epoch* maintained by a
    lease service. A dead peer shrinks the world at the next epoch
    boundary (shrink-and-continue — survivors keep federating instead of
    each degrading to standalone); a supervisor-respawned peer rejoins at
    the next boundary and the world grows back. A run whose membership
    never changes is bit-identical to the fixed world.

    ``lease_ms`` is how long a silent worker stays a member (the failure
    detector; size it above the worst-case round time so a slow round is
    not a death), ``heartbeat_ms`` the renewal cadence (≤ lease/3),
    ``formation_grace_ms`` how long a forming epoch waits for stragglers
    before continuing with fewer (the shrink window), ``min_world`` the
    floor below which no epoch forms (survivors then keep waiting),
    ``join_timeout_s`` how long a joining worker parks before its
    supervisor retries.
    """

    enabled: bool = False
    lease_ms: float = 15000.0
    heartbeat_ms: float = 5000.0
    formation_grace_ms: float = 10000.0
    min_world: int = 1
    join_timeout_s: float = 180.0


@dataclass
class ShardConfig:
    """Model/catalog sharding (``fedrec_tpu.shard``) — scale state past
    per-device HBM.

    ``fsdp`` adds an ``fsdp`` mesh axis (``parallel.mesh.fed_mesh``) and
    keeps every client's AT-REST state — parameters, optimizer moments,
    grad accumulators, codec residuals — sharded across it per the
    size-aware largest-evenly-divisible-dimension policy
    (``shard.policy``, SNIPPETS [2]): scalars/1-D and sub-threshold
    leaves replicated, 2-D+ leaves sharded along the largest dim the
    axis size divides evenly, replicate fallback.  The compiled step
    gathers on entry and re-shards on exit (ZeRO-style residency), so
    the trajectory is bit-identical to the replicated layout
    (``tests/test_shard_fsdp.py``); ``fsdp=1`` builds the exact pre-PR
    1-D mesh and programs.  Not combinable with ``fed.seq_shards>1``
    (both claim the second mesh axis).

    ``table`` row-shards the frozen token-state news table across the
    client mesh axis behind ``shard.table.ShardedNewsTable``: each step
    buckets its unique news ids by owner shard, ``all_to_all``s the id
    buckets out and the gathered rows back (fixed shapes, exact —
    ``docs/DESIGN.md`` §5i), so catalog capacity scales linearly with
    devices instead of per-device HBM.  Composes with
    ``data.gather_chunk`` / the unique-cap policy; joint ("head") mode
    only, and not with ``model.fuse_hot_path``, DP-SGD, seq sharding or
    in-device cohorts (the step builders fail fast).
    """

    # fsdp axis size: shard at-rest client state across this many devices
    # per client slot. 1 = off (bit-identical degenerate layout).
    fsdp: int = 1
    # leaves smaller than this many MB (per client) stay replicated —
    # sharding tiny tensors buys nothing and costs collective latency
    fsdp_min_size_mb: float = 4.0
    # row-shard the token-state news table over the client mesh axis with
    # the in-step owner-bucketed all_to_all gather
    table: bool = False


@dataclass
class FedConfig:
    """Federation strategy (reference modes a-d, SURVEY.md section 0)."""

    # "local"     — no federation (single client)
    # "grad_avg"  — pmean of grads every step (Gradient_Averaging_main.py parity)
    # "param_avg" — pmean of params every round  (Parameter_Averaging_main.py:144-148)
    # "coordinator" — host-0 server broadcast/gather over DCN (client.py/server.py)
    strategy: str = "param_avg"
    num_clients: int = 8
    local_epochs: int = 1              # client epochs per round
    rounds: int = 10                   # global rounds (server.py global_epochs)
    participation: float = 1.0         # fraction of clients aggregated per round
    # classic FedAvg weighting by client example count in coordinator mode
    # (McMahan et al.); False = reference parity — the server's key-wise
    # UNWEIGHTED mean over whatever shard sizes clients hold
    # (reference server.py:37-55)
    weight_by_samples: bool = False
    mesh_axis: str = "clients"
    # sequence/context parallelism for long click-histories: shard the history
    # axis over `seq_shards` chips per client and attend via ring or Ulysses
    # all-to-all collectives (fedrec_tpu.parallel.ring). 1 = off.
    seq_shards: int = 1
    seq_axis: str = "seq"
    seq_impl: str = "ring"             # "ring" | "ulysses"
    # server-side optimization over round deltas (FedOpt, Reddi et al. 2021):
    # "none" adopts the client mean (plain FedAvg = reference behavior);
    # "sgd" with server_momentum>0 is FedAvgM; "adam" is FedAdam. Applies to
    # param_avg and coordinator strategies.
    server_opt: str = "none"           # "none" | "sgd" | "adam"
    server_lr: float = 1.0
    server_momentum: float = 0.9
    # client->server UPDATE compression (fedrec_tpu.comms): applied at the
    # in-graph round-end sync (each cohort client's round delta — the
    # simulated cross-device uplink) and at
    # the coordinator's cross-host DCN gather (real wire buffers). The
    # server->client fan-out stays full precision in every mode.
    #   "none"     — dense f32 (bit-identical to the pre-codec sync)
    #   "int8"     — symmetric per-tensor int8 deltas (~4x the wire)
    #   "sign1bit" — 1 bit/coord + per-tensor scale (~32x); needs EF
    #   "topk"     — keep the dcn_topk_ratio largest coords (~1/(2*ratio)x);
    #                needs EF
    #   "countsketch" — LINEAR seeded count-sketch, ceil(width * n) buckets
    #                per tensor (~1/width x); unbiased, decodes AFTER the
    #                sum (one decode at the root)
    #   "randproj" — LINEAR seeded ±1/√d random projection in 256-wide
    #                chunks (~1/width x); unbiased, decodes AFTER the sum
    #   "auto"     — adaptive per-leaf selection: a seeded warmup window
    #                measures per-tensor reconstruction, then pins a
    #                per-leaf codec map (sketch for dense towers, topk for
    #                sparse deltas, none for scalars) recorded in
    #                provenance and held fixed for replayability
    # The per-contribution codecs (int8/sign1bit/topk) decode each
    # contribution BEFORE any reduction, so robust aggregation
    # (fed.robust.method) composes with them (decode-before-reduce). The
    # linear sketches only decode after the sum — order statistics don't
    # commute with sketch collision, so robust non-mean methods fail fast
    # (the capability table in fedrec_tpu.comms marks the boundary).
    dcn_compress: str = "none"  # none|int8|sign1bit|topk|countsketch|randproj|auto
    # topk: fraction of coordinates kept per tensor (ceil(ratio * n), >= 1)
    dcn_topk_ratio: float = 0.01
    # linear sketches: sketch-to-dense size ratio in (0, 1] — wire cost is
    # ~width * dense bytes, reconstruction variance ~ ||x||^2 * width / m.
    # 0.1 → ~10x uplink reduction (the banked comm_cost contract is >= 8x).
    dcn_sketch_width: float = 0.1
    # seed for the shared sketch hash/projection: every client, process and
    # async worker must hold the SAME seed for sketches to sum.
    dcn_sketch_seed: int = 0
    # dcn_compress="auto": rounds observed (with the sync running dense)
    # before the per-leaf codec map is pinned. The map derives from the
    # warmup round's global delta, identical on every process.
    dcn_auto_warmup: int = 1
    # per-client error-feedback residuals for the biased codecs
    # (sign1bit/topk): the mass a lossy encode drops is carried in
    # ClientState.ef_residual (a fed.population sidecar field — LRU/spill,
    # checkpointed, reset on quarantine heal) and re-enters the next
    # round's update. Disable only for ablations: biased codecs without EF
    # are known not to converge (EF-signSGD, Karimireddy et al. 2019).
    # Async wire workers bank the same residual per EDGE (worker id),
    # keyed to the global version the push was based on.
    dcn_error_feedback: bool = True
    # Byzantine-robust aggregation + quarantine/rollback recovery (see
    # RobustConfig). Applies wherever params aggregate: the in-graph
    # round-end sync (param_avg) and the
    # coordinator's cross-host gather.
    robust: RobustConfig = field(default_factory=RobustConfig)
    # cross-device cohort engine: logical-client population sampled onto
    # the device slots each round (see PopulationConfig).
    population: PopulationConfig = field(default_factory=PopulationConfig)
    # elastic membership: epoch-based world formation over heartbeat
    # leases — shrink-and-continue on peer loss, rejoin at epoch
    # boundaries (see ElasticConfig).
    elastic: ElasticConfig = field(default_factory=ElasticConfig)


@dataclass
class PrivacyConfig:
    """DP-SGD (honest version of reference ``client.py:87-89,220-225,271-281``)."""

    enabled: bool = False
    epsilon: float = 10.0
    delta: float = 1e-5
    clip_norm: float = 2.0             # C (MAX_GRAD_NORM, client.py:220)
    # if sigma > 0 it overrides the accountant-calibrated value
    sigma: float = 0.0
    accountant_epochs: int = 50        # EPOCHS used for calibration (client.py:223)
    # "dpsgd"  — per-example clip + noise on all trainable grads (correct)
    # "ldp_news" — reference parity: noise only on news-embedding grads, no clipping
    mechanism: str = "dpsgd"
    # what DP rounds train (and therefore clip + noise):
    # "all"  — user tower + text head (P ~ 25.5k on the harness model)
    # "user" — user tower only, text head frozen at its current params;
    #          shrinks the noised dimension (noise norm ~ sigma*C*sqrt(P)/B,
    #          docs/DP.md section 2) and keeps the news representation
    #          stationary under noise. dpsgd mechanism only.
    dp_scope: str = "all"


@dataclass
class HealthConfig:
    """Training-health flight recorder (fedrec_tpu.obs.health/device).

    ``sentry`` turns on the in-graph numeric sentry: the jitted train step
    returns a compact per-client health vector (grad/update/param global
    norms + a non-finite flag, DP clip-rate under dpsgd) that the host
    fetches asynchronously with the round's losses.  On a non-finite
    sentinel (or the optional loss-spike predicate) the flight recorder
    dumps the offending batch, a params/opt-state checkpoint, the registry
    snapshot, and a replay manifest into ``obs.dir/flightrec/`` —
    ``fedrec-obs replay`` re-executes that exact step on CPU.
    """

    sentry: bool = True                # in-graph health vector in step metrics
    abort_on_nonfinite: bool = True    # raise TrainingHealthError after dump
    flight_recorder: bool = True       # keep the batch ring + dump (needs obs.dir)
    ring_size: int = 16                # last-N (batch, metadata) records kept
    dump_policy: str = "first"         # "first" = one dump per TRIGGER KIND | "all"
    # keep a host copy of the full client state at every round/chunk entry
    # (what replay starts from). The copy is a blocking device->host
    # transfer of params + optimizer state each round — negligible in
    # simulation, but at large model x cohort scale it is the flight
    # recorder's dominant cost; turn it off to keep batch-ring forensics
    # (dumps then have no state checkpoint and cannot replay).
    snapshot_state: bool = True
    # loss-spike divergence predicate: trigger a dump (no abort) when a
    # round's mean loss exceeds spike_factor * mean(trailing spike_window
    # round losses). 0 = off.
    spike_factor: float = 0.0
    spike_window: int = 8
    # outlier-client flag: a client whose round-mean update-norm exceeds
    # outlier_k * cohort median is counted/logged (poisoning/divergence
    # triage). 0 = off.
    outlier_k: float = 3.0
    # the replay dump includes the feature table (token states / news-vec
    # table) up to this many MB; larger tables are skipped and noted in
    # the manifest (replay then needs the table re-supplied).
    dump_table_max_mb: int = 512
    # recompile watchdog: warn (registry counter + stderr) when this many
    # XLA backend compiles land within storm_window_s seconds.
    storm_threshold: int = 5
    storm_window_s: float = 60.0


@dataclass
class QualityConfig:
    """Model-quality observability (``fedrec_tpu.obs.quality``).

    ``enabled`` turns on the sliced-evaluation telemetry layer: at eval
    cadence the full-pool eval pass additionally accumulates per-SLICE
    ranking metrics (news-category hash buckets, user history-length
    buckets, client-activity quantile buckets, per-device-client) and
    publishes ``eval.{auc,mrr,ndcg5,ndcg10}{slice=…}`` gauges plus
    per-slice impression counts — corpus-wide means hide exactly the
    per-slice skew a federated run is supposed to be judged on. The same
    jitted eval pass also emits fixed-shape score histograms and
    reliability-bin calibration sums (no extra host syncs in the step),
    from which ``eval.ece``, score-separation stats and the
    positive/negative score distributions are derived. Per-client quality
    digests flag clients whose eval AUC falls ``outlier_auc_drop`` below
    the cohort median — informational (composes with quarantine's ignore
    set, never triggers it). ``probe_users > 0`` additionally arms the
    serving store's pre-swap drift probe (``serve.drift_*``).

    Default OFF: with ``enabled=false`` the eval and serving paths run
    the exact pre-quality programs (byte-identical trajectories, pinned
    in ``tests/test_quality.py``).
    """

    enabled: bool = False
    seed: int = 0                      # seeded slice definitions (category hash)
    # news-category slices: seeded multiplicative-hash buckets of the
    # positive news id (a topic proxy when no category metadata exists)
    category_buckets: int = 8
    # user history-length bucket edges (comma ints): "10,30" = <=10,
    # 11..30, >30
    hist_len_edges: str = "10,30"
    # client-activity slices: impressions bucketed by their user's
    # validation-impression count into this many quantile buckets
    # (10 = deciles). 0 = off.
    activity_buckets: int = 10
    # per-device-client slices + quality-outlier digest (uses the
    # per-client eval breakdown when clients have diverged)
    per_client: bool = True
    # reliability bins over sigmoid(score) for ECE (fixed, equal-width)
    ece_bins: int = 10
    # fixed score-histogram shape: score_bins equal bins over
    # [-score_range, +score_range], outliers clamped to the edge bins
    score_bins: int = 20
    score_range: float = 10.0
    # flag a client as a quality outlier when its eval AUC sits this far
    # below the cohort median (absolute AUC drop). 0 = off.
    outlier_auc_drop: float = 0.05
    # serving drift probe: seeded probe-user vectors scored against the
    # outgoing AND incoming store generation BEFORE the hot-swap;
    # publishes score-shift and top-k rank-churn. 0 = off.
    probe_users: int = 32
    probe_topk: int = 10


@dataclass
class PerfConfig:
    """Performance observability (``fedrec_tpu.obs.perf``).

    ``enabled`` turns on the live efficiency telemetry layer: per-round
    ``perf.mfu`` / ``perf.samples_per_sec`` gauges priced with the SAME
    analytic FLOPs model and peak-FLOPs table ``bench.py`` certifies
    headline MFU with, a per-round roofline verdict
    (compute/HBM/input-bound — one spelling with
    ``benchmarks/step_profile.py``) derived from the existing
    ``batch_build``/``h2d``/``dispatch`` span timings, compile-cost
    telemetry (every watched XLA compilation records its
    ``cost_analysis()`` FLOPs / bytes accessed into ``xla.cost_*``
    gauges), and ``jax.live_arrays()`` HBM attribution
    (``hbm.component_bytes{component=…}``) at round cadence.

    Default OFF: with ``enabled=false`` none of this is constructed and
    the train/serve paths run the exact pre-perf programs
    (byte-identical trajectories, pinned in ``tests/test_perf.py``).
    """

    enabled: bool = False
    # record lowered.compile().cost_analysis() (FLOPs / bytes accessed /
    # arithmetic intensity) for every watched compilation; degrades
    # gracefully on backends returning None/partial dicts
    compile_cost: bool = True
    # bucket jax.live_arrays() bytes by component (params / optimizer /
    # news_table / batch / other) into hbm.component_bytes gauges at
    # round cadence
    hbm_components: bool = True
    # triggered capture window: "N" wraps round N (only) in a
    # jax.profiler trace under obs.dir/perf_capture_rNNNN; "N:K" wraps
    # rounds [N, N+K). A pointer record lands in metrics.jsonl. Empty =
    # no configured window.
    capture_rounds: str = ""
    # efficiency-drop trigger: when a round's samples/s falls this
    # fraction below the trailing-window mean, capture the NEXT round
    # (bounded at 3 triggered captures per run). 0 = off.
    capture_drop: float = 0.0
    # trailing rounds the drop trigger averages over
    capture_window: int = 8


@dataclass
class FleetConfig:
    """Fleet-wide telemetry (``fedrec_tpu.obs.fleet``).

    ``collector`` names the TCP JSON-lines telemetry collector this
    worker pushes registry snapshots + completed spans to at round
    cadence — standalone (``CollectorServer``) or riding the membership
    service's port (``python -m fedrec_tpu.parallel.membership ...
    --telemetry-dir D``).  Empty = no pushes; the per-worker
    ``obs.dir/worker_*`` artifacts remain the lossless offline source
    either way (``fedrec-obs fleet`` merges them post-hoc), so a
    no-collector run loses nothing.  Push failures are counted
    (``obs.fleet_push_failures_total``), never raised.
    """

    collector: str = ""                # HOST:PORT; "" = offline artifacts only
    push_every: int = 1                # rounds between telemetry pushes
    push_timeout_s: float = 5.0        # per-push TCP deadline


@dataclass
class WireConfig:
    """Wire-layer observability (``fedrec_tpu.obs.wire``).

    Every TCP JSON-lines exchange (fleet pushes, membership control
    plane, async agg pushes, serving requests) carries an ADDITIVE
    trace-context envelope: causal flow arrows across processes in the
    merged fleet trace, per-edge ``wire.*`` RTT/byte telemetry, and
    NTP-style clock-offset estimation that aligns barrier-less (async)
    incarnations.  ``enabled=false`` sends no envelope at all — wire
    bytes are byte-identical to the pre-envelope protocol (pinned in
    ``tests/test_wire.py``).  Spans follow the ``Tracer.enabled``
    contract: default-on costs registry counters only when no
    ``obs.dir`` will persist a trace.
    """

    enabled: bool = True               # false = byte-identical legacy wire
    window: int = 32                   # per-edge offset median window


@dataclass
class SloConfig:
    """Declarative SLOs + multi-window burn-rate alerting
    (``fedrec_tpu.obs.watch``).

    ``objectives`` is a semicolon list of objectives over metrics the
    registry already publishes::

        round_time:train.round_seconds:p95<2.5;mfu:perf.mfu>=0.3;
        serve_p99:serve.p99_ms<50;auc_all:eval.auc{slice=all}>0.6

    Each objective is ``name:metric[{label=value,...}][:pQQ]OPthreshold``
    with ``OP`` one of ``< <= > >=`` and an optional per-objective
    error-budget target suffix ``@0.999`` (otherwise ``target``
    applies).  Histogram metrics are read as the per-evaluation DELTA of
    their bucket counts (the quantile of *this round's* observations,
    not the lifetime distribution); counters as per-evaluation deltas;
    gauges and record keys at face value.  Every evaluation scores one
    good/bad event per objective, and the alert fires Google-SRE style:
    when the burn rate (bad fraction / error budget) exceeds
    ``fast_burn`` over the last ``fast_window`` evaluations AND
    ``slow_burn`` over the last ``slow_window`` — windows are counted in
    evaluations, so the thresholds scale with round cadence for the
    Trainer, heartbeat cadence for ``fedrec-serve``, and commit cadence
    for the async agg server.

    Default OFF: with ``enabled=false`` no watch layer is constructed,
    no ``alert.*`` instrument exists and the training program is
    byte-identical to a pre-watch build (pinned in
    ``tests/test_watch.py``).
    """

    enabled: bool = False
    objectives: str = ""               # "" = burn-rate SLOs off (anomaly only)
    target: float = 0.99               # default objective target (budget = 1-target)
    fast_window: int = 12              # evaluations in the fast burn window
    slow_window: int = 60              # evaluations in the slow burn window
    fast_burn: float = 14.4            # burn-rate threshold over the fast window
    slow_burn: float = 6.0             # burn-rate threshold over the slow window


@dataclass
class WatchConfig:
    """Alert lifecycle + streaming anomaly detection knobs
    (``fedrec_tpu.obs.watch``/``obs.alerts``; active only under
    ``obs.slo.enabled``).

    The anomaly detector keeps, per round-cadence series the
    MetricLogger already emits, an EWMA baseline and a MAD
    (median-absolute-deviation) scale over the trailing residual window;
    a point whose robust z-score ``|x - ewma| / (1.4826 * MAD)`` exceeds
    ``anomaly_z`` after ``anomaly_warmup`` observations raises an
    anomaly alert — the net that catches regressions no explicit SLO
    names.  The lifecycle engine drives every alert (SLO, anomaly, and
    the unified health/quality/drift/perf triggers) through
    pending→firing→resolved with dedup (a firing alert re-breaching
    emits nothing new), flap suppression (``flap_max`` fire→resolve
    cycles within ``flap_window`` evaluations mutes further transitions)
    and severity.
    """

    anomaly: bool = True               # EWMA+MAD robust z-score detector on/off
    anomaly_z: float = 6.0             # robust z-score firing threshold
    anomaly_alpha: float = 0.3         # EWMA smoothing factor
    anomaly_window: int = 32           # trailing residuals kept for the MAD scale
    anomaly_warmup: int = 8            # observations before a series may fire
    pending_for: int = 2               # consecutive breached evals before firing
    resolve_after: int = 3             # consecutive healthy evals before resolve
    flap_max: int = 3                  # fire cycles within flap_window -> suppress
    flap_window: int = 20              # evaluations the flap counter looks back
    history: int = 256                 # resolved alerts kept for surfaces
    # serving drift-probe breach: a pre-swap probe whose top-k rank churn
    # exceeds this fraction raises a serve:drift alert. 0 = off.
    drift_churn_max: float = 0.5
    # ---- fleet-level rules (collector/membership side):
    # persistent straggler: a worker whose per-push mean round seconds
    # exceeds factor x the fleet median for N consecutive pushes
    fleet_straggler_factor: float = 2.0
    fleet_straggler_evals: int = 3
    # quorum-wait growth: last agg.quorum_wait_ms > factor x trailing median
    fleet_quorum_factor: float = 3.0
    # stalled commit version: a worker whose adopted agg version stops
    # advancing for N pushes while its rounds keep completing
    fleet_stalled_pushes: int = 3


@dataclass
class ObsConfig:
    """Unified telemetry (fedrec_tpu.obs): registry snapshots + host spans.

    The registry and tracer always record in memory (cheap); ``dir``
    turns on the file artifacts — ``metrics.jsonl`` (MetricLogger
    records + per-round registry snapshots), ``trace.json``
    (Chrome-trace/Perfetto host spans), ``prometheus.txt`` (final text
    exposition).  ``fedrec-obs report <dir>`` renders them.
    """

    dir: str = ""                      # "" = no files written
    snapshot_every: int = 1            # rounds between registry snapshots
    trace_capacity: int = 200_000      # host-span ring bound (earliest kept)
    # size-based rotation for metrics.jsonl: when the event log exceeds
    # this many MB it is renamed to metrics.jsonl.1 (one level kept) and a
    # fresh file continues — a long serve/train run cannot fill the disk.
    # Readers (fedrec-obs, load_jsonl) consume rotated files in order.
    # 0 = unbounded.
    jsonl_max_mb: float = 0.0
    health: HealthConfig = field(default_factory=HealthConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    quality: QualityConfig = field(default_factory=QualityConfig)
    perf: PerfConfig = field(default_factory=PerfConfig)
    wire: WireConfig = field(default_factory=WireConfig)
    slo: SloConfig = field(default_factory=SloConfig)
    watch: WatchConfig = field(default_factory=WatchConfig)


@dataclass
class ChaosConfig:
    """Deterministic fault injection (``fedrec_tpu.fed.chaos``).

    A seeded :class:`FaultPlan` schedules per-round, per-client faults.
    Client-side faults are applied as masks at the optimizer-update
    boundary INSIDE the jitted step (the per-client fault vector rides the
    batch as ``chaos.code``/``chaos.scale`` arrays, so the round loop and
    the flight-recorder replay see identical faults), and two runs of the
    same plan are bit-identical. Host-level faults (peer kill, torn snapshot) exercise
    the coordinator deployment's recovery paths.

    ``faults`` is a comma list of ``kind@round:client[xscale]`` specs,
    ``round`` may be ``*`` (every round):

        nan@2:3          client 3's round-2 updates become NaN
        scale@*:5x100    client 5's updates x100 every round (poison)
        flip@4:2         client 2's round-4 updates sign-flipped
    """

    enabled: bool = False
    seed: int = 0
    drop_rate: float = 0.0             # per-(round, client) Bernoulli dropout
    straggle_rate: float = 0.0         # ditto; weight 0 + optional host delay
    straggle_ms: float = 0.0           # host sleep per straggler round
    faults: str = ""                   # "kind@round:client[xscale]" comma list
    # ---- population-level fault distributions (fed.population): applied
    # to LOGICAL client ids at cohort-sampling time, seeded per
    # (seed, round, attempt, client) so a whole sampled-cohort run replays
    # bit-identically. pop_drop_rate is each sampled client's per-round
    # Bernoulli dropout probability; a seeded pop_flaky_fraction subset of
    # the population drops at pop_flaky_drop_rate instead (chronically bad
    # radios). pop_straggle_ms > 0 draws each reporting client's simulated
    # report latency from lognormal(median=pop_straggle_ms,
    # sigma=pop_straggle_sigma); clients past fed.population's
    # round_deadline_ms are deadline-cut (weight 0).
    pop_drop_rate: float = 0.0
    pop_flaky_fraction: float = 0.0
    pop_flaky_drop_rate: float = 0.5
    pop_straggle_ms: float = 0.0
    pop_straggle_sigma: float = 1.0
    # host faults (coordinator deployment only):
    kill_round: int = -1               # process exits hard at this round's entry
    kill_process: int = -1             #   which coordinator process dies
    torn_snapshot_round: int = -1      # truncate the just-written local snapshot
    # elastic kill->shrink->rejoin scripting: after the chaos kill, the
    # respawned worker HOLDS OFF joining the membership service for this
    # many seconds (once, marker-guarded), so the survivors demonstrably
    # form the SHRUNK epoch first and the rejoin lands as its own later
    # epoch — without it a fast respawn can race straight back into the
    # formation window and the shrink never becomes observable. 0 = off.
    rejoin_delay_s: float = 0.0
    # ---- wire-level fault injection (fed.chaos.ChaosProxy): a seeded
    # TCP chaos proxy fronting the commit authority (or membership
    # service) applies time-windowed transport faults per connection.
    # wire_faults is a comma list of "kind@start[-end][:arg]" specs,
    # start/end in seconds since proxy start, "*" = always:
    #
    #     drop@5-10          refuse/black-hole connections in [5s, 10s)
    #     drop@*:0.3         drop 30% of connections, always
    #     delay@0-60:250     add 250ms before forwarding the request
    #     tear@5-10          forward HALF the request bytes, then RST
    #     dup@5-10           deliver the request TWICE upstream
    #     partition@20-30    full partition: nothing gets through
    #
    # Faults are drawn from a PRNG seeded per (wire_seed, connection
    # index), so a soak's fault schedule replays bit-identically; with
    # wire_faults empty the proxy forwards every byte verbatim (pinned).
    wire_faults: str = ""
    wire_seed: int = 0


@dataclass
class AggConfig:
    """Round-end aggregation topology (``fedrec_tpu.agg``).

    ``mode`` selects how the cohort's contributions become the next
    global:

      * "flat"         — the all-reporting single reduce (the default;
                         every prior PR's behavior, bit-for-bit).
      * "hierarchical" — tiered reduce: cohort contributions are grouped
                         into ``tree_fanout``-wide tiers, each tier
                         pre-aggregated with the ``fed.robust`` method,
                         and the tier outputs reduced up a tree whose
                         critical path is O(log_fanout P) instead of
                         O(P).  With ``fed.robust.method="mean"`` the
                         tree of (sum(w*x), sum(w)) partials with ONE
                         final divide is *algebraically* the flat
                         weighted mean, so the mode lowers to the
                         unchanged flat reduce and stays bit-identical
                         (pinned in tests/test_agg.py); any other robust
                         method trims/medians per tier and genuinely
                         diverges from the flat trajectory (documented
                         in docs/DESIGN.md, bounded-delta pinned).
      * "async"        — buffered quorum commit (``agg/buffer.py`` +
                         ``agg/commit.py``): the global commits once
                         ``quorum`` contributions arrive; late
                         contributions are staleness-weighted by
                         1/(1+staleness) into the NEXT commit and
                         dropped once staleness exceeds
                         ``staleness_cap`` commits.  The round barrier
                         disappears — a straggler's marginal ``gate_ms``
                         goes to ~0 (scripts/async_smoke.sh).

    ``quorum`` = 0 means all-reporting (async mode then still commits
    per round, but without early-commit savings).  The buffer state is
    checkpointed beside the model snapshot so pending late contributions
    survive a restart.
    """

    mode: str = "flat"                 # "flat" | "hierarchical" | "async"
    quorum: int = 0                    # async commit quorum K; 0 = all-reporting
    staleness_cap: int = 2             # drop buffered updates older than this (commits)
    tree_fanout: int = 2               # hierarchical tier width (>= 2)
    # ---- async worker wire policy (agg/worker.py + parallel/rpc.py):
    # the failure-handling budgets one worker<->authority edge runs
    # under. Exchanges retry transport failures with full-jitter
    # exponential backoff inside worker_rpc_attempts; a dead host fails
    # in worker_connect_timeout_s (the dial budget) while a slow fold
    # still gets worker_timeout_s on the established socket. When the
    # wire stays silent past worker_unreachable_budget_s the worker
    # stops degrading and exits rc-75 for the supervisor to respawn.
    worker_timeout_s: float = 60.0     # per-exchange read/socket deadline
    worker_connect_timeout_s: float = 5.0   # dial budget (dead host fails fast)
    worker_poll_s: float = 0.2         # sleep between commit-poll ticks
    worker_global_wait_s: float = 20.0  # bounded wait for a newer commit per round
    worker_rpc_attempts: int = 4       # per-op transport retry budget
    worker_backoff_ms: float = 50.0    # full-jitter backoff base
    worker_backoff_cap_ms: float = 2000.0   # backoff ceiling per retry
    worker_unreachable_budget_s: float = 120.0  # wire silence before rc-75 degrade


@dataclass
class TrainConfig:
    save_every: int = 1                # snapshot cadence (reference main.py argv)
    snapshot_dir: str = "snapshots"
    resume: bool = True                # auto-resume if snapshot exists (main.py:113-115)
    eval_every: int = 1
    # "sampled" — 1 pos + npratio sampled negatives per impression (the
    #             reference's per-epoch validate, client.py:149-171)
    # "full"    — deterministic full-negative-pool scoring (the protocol
    #             behind the published MIND table, evaluation_functions.py:33-47)
    # "last4"   — deterministic last-4-pool-negatives slice (client.py:159-160)
    eval_protocol: str = "full"
    # donate the batch buffers to the compiled step: donation lets XLA
    # reclaim them as scratch once consumed. Safe in the Trainer (every
    # dispatch device_puts fresh arrays); leave False when driving the step
    # builder directly with reused batches.
    donate_batch: bool = False
    # keep a separate best-validation-AUC snapshot under
    # <snapshot_dir>/best (full snapshot dir incl. config.json, so
    # `fedrec-recommend --snapshot-dir .../best` serves the best round
    # directly); the incumbent best survives resume. Off by default: the
    # round-cadence snapshots stay the only writers unless asked.
    keep_best: bool = False
    seed: int = 42
    profile: bool = False              # jax.profiler trace around the hot loop
    wandb: bool = False
    wandb_project: str = "fedrec_tpu"
    run_name: str = "run"


@dataclass
class ExperimentConfig:
    data: DataConfig = field(default_factory=DataConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    fed: FedConfig = field(default_factory=FedConfig)
    privacy: PrivacyConfig = field(default_factory=PrivacyConfig)
    shard: ShardConfig = field(default_factory=ShardConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    agg: AggConfig = field(default_factory=AggConfig)

    # ------------------------------------------------------------------ io
    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ExperimentConfig":
        cfg = cls()
        for section_name, section_val in d.items():
            section = getattr(cfg, section_name, None)
            if section is None or not dataclasses.is_dataclass(section):
                raise KeyError(f"unknown config section: {section_name!r}")
            _merge_dataclass(section, section_val, section_name)
        return cfg

    # ------------------------------------------------------- cli overrides
    def apply_overrides(self, overrides: list[str]) -> "ExperimentConfig":
        """Apply ``section.key=value`` strings (e.g. ``fed.num_clients=32``).
        Paths may descend into nested sections (``obs.health.sentry=0``)."""
        for item in overrides:
            if "=" not in item:
                raise ValueError(f"override must be section.key=value, got {item!r}")
            path, raw = item.split("=", 1)
            parts = path.split(".")
            if len(parts) < 2:
                raise ValueError(f"override path must be section.key, got {path!r}")
            section: Any = self
            for part in parts[:-1]:
                section = getattr(section, part, None)
                if section is None or not dataclasses.is_dataclass(section):
                    raise KeyError(f"unknown config section: {path!r}")
            key = parts[-1]
            if not hasattr(section, key):
                raise KeyError(f"unknown config key: {path!r}")
            current = getattr(section, key)
            if dataclasses.is_dataclass(current):
                raise KeyError(
                    f"config path {path!r} names a section, not a key; "
                    f"set one of its fields ({path}.<key>=...)"
                )
            setattr(section, key, _coerce(raw, type(current)))
        return self


# fields deleted from the schema. to_dict writes every field, so from_dict
# tolerates these: snapshot config.json files and flight-recorder manifests
# written by older runs keep loading. Everything else unknown still fails
# fast, and so does an override that names one of these.
_REMOVED_KEYS = {
    "train.total_epochs",   # the CLI positional writes fed.rounds directly
    "train.log_every",      # never consulted; the Trainer logs every round
    # the host dedups each step at a size that follows the traffic
    "data.unique_news_cap",
    "data.unique_news_cap_buckets",
    # the two scan dispatch forms: their trajectories were pinned identical
    # to the per-batch round loop's, so ignoring the value is exact
    "train.scan_steps",
    "train.rounds_per_scan",
}


def _merge_dataclass(section: Any, values: dict[str, Any], path: str) -> None:
    """Set ``values`` onto a (possibly nested) config dataclass — the
    recursion behind ``from_dict``, so nested sections like ``obs.health``
    round-trip through to_dict/from_dict like every flat one."""
    for k, v in values.items():
        if f"{path}.{k}" in _REMOVED_KEYS:
            continue
        if not hasattr(section, k):
            raise KeyError(f"unknown config key: {path}.{k}")
        current = getattr(section, k)
        if dataclasses.is_dataclass(current) and isinstance(v, dict):
            _merge_dataclass(current, v, f"{path}.{k}")
        else:
            setattr(section, k, v)


def _coerce(raw: str, ty: type) -> Any:
    if ty is bool:
        low = raw.strip().lower()
        if low in ("1", "true", "yes", "on"):
            return True
        if low in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"cannot parse bool from {raw!r}")
    if ty is int:
        return int(raw)
    if ty is float:
        return float(raw)
    return raw
