"""Jitted SPMD train/eval steps — the framework's hot loop.

One compiled XLA program replaces the reference's Python-per-sample hot loop
(reference ``model.py:41-61`` rebuilt a DataLoader and re-ran DistilBERT per
sample per batch). Design:

  * The frozen-trunk token states (or any per-news feature table) live
    HBM-resident; the step gathers only the batch's unique news
    (``jnp.unique`` with a static size bound) and runs the trainable
    ``TextHead`` on those — duplicates across candidate/history slots are
    encoded once, and their gradients sum automatically through the gather.
  * Per-nid news-embedding gradients (reference dict scatter-add
    ``main.py:20-52``, ``model.py:97-109``) become a static-shape
    ``.at[ids].add`` scatter into an ``(N_news, D)`` accumulator.
  * Federation hooks (``FedStrategy``) run inside the same program, so
    grad/param averaging compiles to XLA collectives over the mesh's
    ``clients`` axis (ICI), not a separate gloo phase.
  * Two update paths:
      - ``joint``     (TPU-first default): end-to-end autodiff through both
        towers, Adam step per batch.
      - ``decoupled`` (reference parity): user tower trains on gathered news
        vectors from a cached table; embedding grads accumulate and are
        replayed through the head via ``jax.vjp`` at epoch end — exactly the
        semantics of ``UserModel.collect``/``update_news_grad``
        (``model.py:66-109``), minus its one-Adam-step-per-epoch quirk for
        the user tower (ledger).

All functions here build *closed* jitted callables; nothing retraces across
steps because every shape is static.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.eval.metrics import ranking_metrics_batch
from fedrec_tpu.fed.strategies import FedStrategy, ParamAvg
from fedrec_tpu.models import NewsRecommender, score_loss
from fedrec_tpu.models.bert import TRUNK_COUNTERS
from fedrec_tpu.models.recommender import score_candidates
from fedrec_tpu.privacy.dpsgd import make_noise_fn, per_example_clipped_grads
from fedrec_tpu.train.state import ClientState, make_optimizers


# ----------------------------------------------------------------- helpers
@jax.custom_vjp
def _scale_grad(x: jnp.ndarray, s: float) -> jnp.ndarray:
    """Identity forward; scales the cotangent by ``s`` on the way back.

    Used under sequence parallelism: replicated computations (candidate
    encoding runs identically on every seq shard) would have their gradient
    counted ``n_seq`` times by the post-grad ``psum`` — scaling by ``1/n_seq``
    makes the psum sum to exactly one contribution.
    """
    return x


def _scale_grad_fwd(x, s):
    return x, s


def _scale_grad_bwd(s, g):
    return (g * s, None)


_scale_grad.defvjp(_scale_grad_fwd, _scale_grad_bwd)


def _tree_global_norm(*trees: Any) -> jnp.ndarray:
    """Global L2 norm over every leaf of every (non-None) tree, accumulated
    in float32 — the health sentry's one norm definition (grad, update and
    param norms all use it, so their scales are comparable)."""
    leaves = [
        leaf
        for t in trees
        if t is not None
        for leaf in jax.tree_util.tree_leaves(t)
    ]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def _unstack(tree: Any) -> Any:
    """Strip the local leading block dim (size 1) inside shard_map."""
    with jax.named_scope("client_axis"):
        return jax.tree_util.tree_map(lambda x: x[0], tree)


def _restack(tree: Any) -> Any:
    """The block dim back on. No op of its own once compiled: XLA names the
    fusion that writes a leaf of the new state (Adam's sweep, the parameter
    add) after this, its last, op."""
    with jax.named_scope("client_axis"):
        return jax.tree_util.tree_map(lambda x: x[None], tree)


def _apply_update_fault(tree: Any, code: jnp.ndarray, scale: jnp.ndarray) -> Any:
    """Chaos update-fault mask at the optimizer-update boundary.

    ``code`` is this client's scalar fault code (``fed.chaos.FAULT_CODES``:
    0 none, 1 nan, 2 scale, 3 sign-flip) and ``scale`` the multiplier for
    code 2 — both ride the batch dict so the round loop and the
    flight-recorder replay compile identical fault arithmetic. Code 0
    selects the original update untouched (exact, not ``u * 1``).
    """

    def one(u):
        factor = jnp.where(code == 3, -1.0, scale).astype(u.dtype)
        faulted = jnp.where(code == 1, jnp.full_like(u, jnp.nan), u * factor)
        return jnp.where(code == 0, u, faulted)

    return jax.tree_util.tree_map(one, tree)


# ------------------------------------------------- token-state table at rest
def is_token_state_table(table: Any) -> bool:
    """A 3-D floating ``(N, L, Dh)`` table of cached trunk token states —
    told from what it is, not from a setting: the ``(N, D)`` news-vector
    table of ``decoupled`` mode and the ``(N, 2, L)`` int32 token table of
    ``finetune`` mode are not."""
    return getattr(table, "ndim", 0) == 3 and jnp.issubdtype(
        table.dtype, jnp.floating
    )


def token_table_format(mesh: Mesh, spec: P = P()) -> Format:
    """Where a token-state table's bytes lie at rest: row-major, the layout
    the step's ``table[ids]`` gather reads. A TPU's own choice for
    ``(N, 50, 768)`` puts the 50-axis major (no padding of 50), and every
    program handed that rewrites the whole table to row-major before it can
    gather (PERF.md section 5). :func:`commit_token_table` sets this format
    once; every ``jax.jit`` that takes the table states it in its
    ``in_shardings``, so no program relays the table out."""
    return Format(Layout(major_to_minor=(0, 1, 2)), NamedSharding(mesh, spec))


def commit_token_table(
    table: Any, mesh: Mesh, spec: P = P()
) -> tuple[Any, dict | None]:
    """Commit a step's feature table to where it rests: a token-state table
    (host or device array) goes to :func:`token_table_format` with one
    ``device_put``; any other table (:func:`is_token_state_table`) comes
    back untouched with ``None``. Whoever holds a table and calls a program
    built here more than once calls this once first: a program that states
    the format refuses a committed array in another layout and relays an
    uncommitted one out at every dispatch.

    Returns the table and what was done, the args of the trainer's
    ``table_commit`` span: ``found`` (the ``major_to_minor`` it arrived in,
    ``"host"`` for a host array), ``set``, ``relaid`` and
    ``compiled_afresh``. A table that already rests there is returned as it
    is; otherwise the result is a new array and the caller's is left alone.
    On a TPU the relayout program is compiled afresh (about a second that
    ``compile_cache_misses`` does not see): one loaded from the persistent
    cache returns an array that misreports its layout
    (:func:`fedrec_tpu.utils.compile_cache.compiled_afresh`)."""
    from fedrec_tpu.utils.compile_cache import compiled_afresh

    if not is_token_state_table(table):
        return table, None
    fmt = token_table_format(mesh, spec)
    want = tuple(fmt.layout.major_to_minor)
    at = getattr(table, "format", None)
    found = None if at is None else tuple(at.layout.major_to_minor)
    did = {
        "found": "host" if found is None else str(found), "set": str(want),
        "relaid": found != want, "compiled_afresh": False,
    }
    if (
        found == want
        and getattr(table, "committed", False)
        and table.sharding.is_equivalent_to(fmt.sharding, table.ndim)
    ):
        return table, did
    # only a TPU lays (N, 50, 768) out otherwise by itself, so only there
    # does the device_put compile a program with an output layout of its own
    afresh = mesh.devices.flat[0].platform == "tpu"
    with compiled_afresh() if afresh else contextlib.nullcontext():
        committed = jax.device_put(table, fmt)
    did["compiled_afresh"] = afresh
    at = tuple(committed.format.layout.major_to_minor)
    if at != want:
        raise RuntimeError(
            f"the table was committed to {fmt.layout} but reports {at}: "
            "every program that states the format would refuse it"
        )
    return committed, did


def _resolve_mode(cfg: ExperimentConfig, mode: str | None) -> str:
    """The step mode a builder was asked for, else the configured one."""
    if mode is None:
        mode = {"table": "decoupled", "head": "joint", "finetune": "finetune"}.get(
            cfg.model.text_encoder_mode, "joint"
        )
    return mode


def _table_in_shardings(
    n_args: int, cfg: ExperimentConfig, mode: str | None, mesh: Mesh,
    spec: P = P(),
) -> tuple:
    """``in_shardings`` of a step program whose third argument is the
    feature table: a ``joint`` step reads token states and states their
    at-rest format; the other modes' tables (and every other argument) are
    left to the arrays that arrive."""
    fmt = (
        token_table_format(mesh, spec)
        if _resolve_mode(cfg, mode) == "joint"
        else None
    )
    return tuple(fmt if i == 2 else None for i in range(n_args))


# vmap axis name for the in-device client cohort (num_clients > devices):
# cross-client collectives then run over (LOCAL_AXIS, mesh_axis) jointly, so
# "average over all clients" means exactly that regardless of how clients
# map onto chips. The TPU-native analogue of oversubscribing torchrun ranks
# onto one node (reference README.md:27-34 runs N ranks on localhost).
LOCAL_AXIS = "local_clients"


def clients_per_device(cfg: ExperimentConfig, mesh: Mesh) -> int:
    """Cohort size: how many of ``fed.num_clients`` live on each mesh slot.

    1 == the classic one-client-per-chip layout. >1 requires equal cohorts
    (enforced here; ``parallel.mesh.client_mesh`` builds such meshes when
    clients outnumber devices).
    """
    m = int(mesh.shape[cfg.fed.mesh_axis])
    n = cfg.fed.num_clients
    if n % m != 0:
        raise ValueError(
            f"fed.num_clients={n} is not divisible by the mesh's "
            f"{cfg.fed.mesh_axis!r} axis size {m}; cohort sharding needs "
            "equal cohorts per device"
        )
    return n // m


def cohort_axes(cfg: ExperimentConfig, mesh: Mesh) -> tuple[int, Any]:
    """(cohort size k, the axes every cross-client collective must span).

    The ONE definition of the cohort-axes policy — all step builders use it,
    so "average over all clients" can never mean different things in
    different parts of a round.
    """
    k = clients_per_device(cfg, mesh)
    axis = cfg.fed.mesh_axis
    return k, (axis if k == 1 else (LOCAL_AXIS, axis))


def _cohort_call(local_fn: Callable, k: int, n_args_mapped: int, *args):
    """Run ``local_fn`` on a shard_map block: squeeze for k==1, vmap the
    in-device cohort (axis name LOCAL_AXIS) for k>1.

    ``n_args_mapped``: how many leading args carry the per-client block dim
    (the rest — feature tables — are replicated/unmapped).
    """
    if k == 1:
        out = local_fn(*(_unstack(a) for a in args[:n_args_mapped]),
                       *args[n_args_mapped:])
        return _restack(out)
    in_axes = (0,) * n_args_mapped + (None,) * (len(args) - n_args_mapped)
    return jax.vmap(local_fn, in_axes=in_axes, axis_name=LOCAL_AXIS)(*args)


def _encode_gathered(
    model: NewsRecommender,
    news_params: Any,
    token_states: jnp.ndarray,
    uniq: jnp.ndarray,
    chunk: int = 0,
    fused: bool = False,
    gather_fn: Callable | None = None,
    batch_of_one: bool = False,
) -> jnp.ndarray:
    """Gather unique token-state rows and run the text head over them.

    The gather result is ``stop_gradient``-ed (the trunk is frozen: no
    cotangent may ever flow into the (N, L, Dh) table, and saying so lets
    XLA drop the zero-cotangent scatter a differentiated gather would
    imply) and tagged ``checkpoint_name("token_gather")`` so remat policies
    can address it.

    ``chunk`` (``data.gather_chunk``): tile the gather+encode in
    ``lax.map`` chunks with the chunk body rematerialized in backward —
    the (unique, L, Dh) gather result then never occupies HBM beyond one
    chunk (forward residual AND backward), at the price of re-gathering
    per tile in the backward pass. Row-wise encode, so tiling is exact.

    ``fused`` (``model.fuse_hot_path``, additive head only): ONE Pallas
    kernel streams each id's token row HBM->VMEM straight into the pool +
    projection (``ops.fused_gather_encode``) — the (U, L, Dh) gather never
    exists, forward or backward, so the remat tag moves from the gathered
    states (which no longer materialize) to the kernel's (U, D) output;
    ``stop_gradient`` on the table keeps the frozen-trunk contract and the
    kernel's VJP never computes a table cotangent anyway. Composes with
    ``chunk`` unchanged (the tile body swaps implementations).

    ``batch_of_one``: run the head as a ``vmap`` over one client whose
    parameters carry the batch axis too — the form it has inside a cohort's
    ``vmap``. Same arithmetic; what changes is XLA:TPU's choice of layouts:
    un-batched it lays the gathered rows out again for the head's first
    product (a ``copy`` of ``bf16[28160,50,768]``: 7.03 ms and 2.16 GB of
    temporaries a step in ``central.b512``), batched it fuses that into
    the consumers, as it does for a cohort (PERF.md section 6, PR 27).
    Taken only by the single-worker program (one client on one device),
    whose step otherwise does not load beside two resident tables; the
    one-client-a-device programs of several devices (and ``shard.fsdp``
    against them, pinned bit for bit) keep the un-batched head until
    roadmap S3 measures them on four chips.

    ``gather_fn(table, ids) -> rows`` swaps the local ``table[ids]`` for
    the sharded-catalog exchange (``shard.table``,
    ``shard.table.owner_bucketed_gather``): collectives live inside the
    per-tile body, so ``chunk`` tiling replays the exchange per tile in
    lockstep on every device (same static trip count everywhere), and the
    ``stop_gradient`` outside it keeps any cotangent from ever touching
    the wire.
    """
    from jax.ad_checkpoint import checkpoint_name

    if gather_fn is None:
        def gather_fn(t, ids):
            return t[ids]

    if fused:
        from fedrec_tpu.ops import fused_gather_encode

        frozen = lax.stop_gradient(token_states)

        def encode(ids):
            with jax.named_scope("news_gather"):
                return checkpoint_name(
                    fused_gather_encode(
                        frozen, ids, news_params, dtype=model.cfg.dtype
                    ),
                    "token_gather",
                )
    else:
        def encode(ids):
            with jax.named_scope("news_gather"):
                states = checkpoint_name(
                    lax.stop_gradient(gather_fn(token_states, ids)),
                    "token_gather",
                )

            def head(params, rows):
                return model.apply(
                    {"params": {"text_head": params}},
                    rows,
                    method=NewsRecommender.encode_news,
                )

            if batch_of_one:
                # the batch axis' own ops (its transpose sums the head's
                # gradients over the one client) are the head's
                with jax.named_scope("text_head"):
                    return jax.vmap(head)(
                        jax.tree_util.tree_map(lambda x: x[None], news_params),
                        states[None],
                    )[0]
            return head(news_params, states)

    u = uniq.shape[0]
    if not chunk or u <= chunk:
        return encode(uniq)
    pad = (-u) % chunk
    tiles = jnp.pad(uniq, (0, pad)).reshape(-1, chunk)
    vecs = lax.map(jax.checkpoint(encode), tiles)  # (tiles, chunk, D)
    return vecs.reshape(-1, vecs.shape[-1])[:u]


# The host half of the joint step's dedup (:func:`host_news_dedup`) rides the
# batch dict under these two keys; a batch without them is deduped on the
# device (:func:`_batch_news_vecs`).
NEWS_ROWS = "news_rows"          # (K, R) int32: a client's distinct ids, 0-padded
NEWS_INVERSE = "news_inverse"    # (K, B*(C+H)) int32: slot -> row of NEWS_ROWS

# How the round loop sizes R from the distinct counts it measured
# (:func:`encode_rows_for`). The counts of one traffic are narrow (sd under 1%
# of the mean, the same from seed to seed: PERF.md section 6, PR 30), so 3% on
# the largest count seen is 3 sd or more of room above a maximum that is
# itself 2-3 sd above the mean. R is 64 past a multiple of 128, as the
# flagship's slot count 3,520 is: whole (16, 128) bf16 tiles of rows either
# way, but given a whole number of 128-row tiles XLA:TPU lays a cohort's
# gathered rows out a second time with R minor for the head's weight gradient
# (a 1.8 GB copy a step at 8 x 2,944 rows; tests/test_chip_compile.py).
ENCODE_ROOM = 0.03
ENCODE_ROW_QUANTUM = 128
ENCODE_ROW_RESIDUE = 64


def encode_rows_for(most: int, full: int) -> int:
    """The encode size R for a run whose largest distinct count so far is
    ``most``: room for the spread, rounded up to the next size the compiled
    program tiles well, never above ``full`` = ``min(B*(C+H), N)`` (which
    every count fits)."""
    want = int(most * (1.0 + ENCODE_ROOM)) - ENCODE_ROW_RESIDUE
    quanta = max(-(-want // ENCODE_ROW_QUANTUM), 0)
    return min(full, quanta * ENCODE_ROW_QUANTUM + ENCODE_ROW_RESIDUE)


def _client_news_ids(candidates: np.ndarray, history: np.ndarray) -> np.ndarray:
    """(K, B, C) and (K, B, H) ids -> (K, B*(C+H)), in the slot order
    :func:`_batch_news_vecs` scatters back into."""
    k = candidates.shape[0]
    return np.concatenate(
        [candidates.reshape(k, -1), history.reshape(k, -1)], axis=1
    )


def most_distinct_news(candidates: np.ndarray, history: np.ndarray) -> int:
    """The largest count of distinct news ids over a step's clients."""
    return max(
        np.unique(row).size for row in _client_news_ids(candidates, history)
    )


def host_news_dedup(
    candidates: np.ndarray, history: np.ndarray, rows: int, n_news: int
) -> tuple[dict[str, np.ndarray], int]:
    """Dedup each client's news ids on the host, where the ids are known
    before the step is dispatched and a compiled program's shapes are not.

    ``candidates`` (K, B, C), ``history`` (K, B, H). Returns the two batch
    entries and the largest distinct count over the clients. The distinct ids
    are padded with id 0 to ``rows``, or to the full size
    ``min(B*(C+H), n_news)`` when some client's count exceeds ``rows``: the
    step is then the same function at another shape, and no id is ever
    dropped.
    """
    ids = _client_news_ids(candidates, history)
    k, slots = ids.shape
    deduped = [np.unique(row, return_inverse=True) for row in ids]
    most = max(u.size for u, _ in deduped)
    size = rows if most <= rows else min(slots, n_news)
    news_rows = np.zeros((k, size), np.int32)
    for c, (u, _) in enumerate(deduped):
        news_rows[c, : u.size] = u
    inverse = np.stack([inv for _, inv in deduped]).astype(np.int32)
    return {NEWS_ROWS: news_rows, NEWS_INVERSE: inverse}, most


def batch_host_dedup(batch: dict) -> tuple[Any, Any] | None:
    """The (:data:`NEWS_ROWS`, :data:`NEWS_INVERSE`) entries of a step's
    batch, or None where the step dedups on the device: the batch has none,
    or a caller re-cut ``candidates`` / ``history`` under them, so that they
    no longer describe its slots (the device-side dedup is exact for any
    batch). Reads shapes only, per client or with a leading clients axis:
    the step asks at trace time and the round loop asks for its ``dispatch``
    span, of the same batch, so the span says what the step encodes."""
    # a dict's keys are static under jit
    if NEWS_ROWS not in batch:  # fedrec-lint: disable=TS105
        return None
    cand, his = batch["candidates"], batch["history"]
    slots = cand.shape[-2] * cand.shape[-1] + his.shape[-2] * his.shape[-1]
    if batch[NEWS_INVERSE].shape[-1] != slots:
        return None
    return batch[NEWS_ROWS], batch[NEWS_INVERSE]


def _batch_news_vecs(
    model: NewsRecommender,
    news_params: Any,
    token_states: jnp.ndarray,
    candidates: jnp.ndarray,
    history: jnp.ndarray,
    host_dedup: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    chunk: int = 0,
    fused: bool = False,
    gather_fn: Callable | None = None,
    n_news: int | None = None,
    batch_of_one: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Encode the batch's unique news once; gather into cand/history slots.

    ``token_states``: (N_news, L, bert_hidden) HBM-resident feature table.
    Returns cand_vecs (B, C, D) and his_vecs (B, H, D).

    One algorithm, encode the distinct rows and scatter by the inverse, whose
    size comes from the input: with ``host_dedup``, a batch's
    (:data:`NEWS_ROWS`, :data:`NEWS_INVERSE`) entries
    (:func:`host_news_dedup`), the R rows given are encoded, a size that
    follows the traffic, with no sort in the program; without, the ids are
    deduped here by ``jnp.unique`` at the worst case ``min(B*(C+H), N)``,
    whose repeats come back as padding rows of id 0 and are encoded too.
    ``chunk`` and ``batch_of_one``: see :func:`_encode_gathered`.
    ``gather_fn``/``n_news``: the sharded-catalog form (``shard.table``) —
    ``token_states`` is then this device's local row block, so the GLOBAL
    row count must come in explicitly (the local block's dim 0 would wrongly
    bound the dedup).
    """
    b, c = candidates.shape
    h = history.shape[1]
    if host_dedup is not None:
        uniq, inv = host_dedup
        if inv.shape != (b * (c + h),):
            raise ValueError(
                f"host dedup inverse of shape {inv.shape} does not describe "
                f"the batch's {b * (c + h)} news slots"
            )
    else:
        if n_news is None:
            n_news = token_states.shape[0]
        with jax.named_scope("news_dedup"):
            ids = jnp.concatenate([candidates.reshape(-1), history.reshape(-1)])
            uniq, inv = jnp.unique(
                ids, size=min(ids.shape[0], n_news), fill_value=0,
                return_inverse=True,
            )
    vecs = _encode_gathered(
        model, news_params, token_states, uniq, chunk, fused=fused,
        gather_fn=gather_fn, batch_of_one=batch_of_one,
    )
    with jax.named_scope("news_gather"):
        flat = vecs[inv]
        cand_vecs = flat[: b * c].reshape(b, c, -1)
        his_vecs = flat[b * c :].reshape(b, h, -1)
    return cand_vecs, his_vecs


def _encode_unique_tokens(
    text_encoder: Any,
    news_params: Any,
    tokens_table: jnp.ndarray,
    ids: jnp.ndarray,
    dropout_rng: jax.Array | None,
) -> tuple[jnp.ndarray, dict]:
    """Encode a flat id vector's unique news through the full TextEncoder.

    Gathers the unique token rows from the (N, 2, L) table, runs trunk +
    head once per distinct news, and scatters back to (len(ids), D). Also
    returns the counters a routed trunk sowed (``models.bert.TextEncoder``;
    empty for a dense trunk), under their ``TRUNK_COUNTERS`` metric names.
    """
    size = min(ids.shape[0], tokens_table.shape[0])
    with jax.named_scope("news_dedup"):
        uniq, inv = jnp.unique(ids, size=size, fill_value=0, return_inverse=True)
    with jax.named_scope("news_gather"):
        toks = tokens_table[uniq]  # (size, 2, L)
    train = dropout_rng is not None
    vecs, sown = text_encoder.apply(
        {"params": news_params},
        toks,
        train,
        rngs={"dropout": dropout_rng} if train else None,
        mutable=["routing"],
    )  # (size, D)
    routing = {TRUNK_COUNTERS[k]: v[0] for k, v in sown.get("routing", {}).items()}
    with jax.named_scope("news_gather"):
        return vecs[inv], routing


def _batch_news_vecs_tokens(
    text_encoder: Any,
    news_params: Any,
    tokens_table: jnp.ndarray,
    candidates: jnp.ndarray,
    history: jnp.ndarray,
    dropout_rng: jax.Array | None,
) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
    """Finetune-mode analogue of ``_batch_news_vecs``: one joint dedup over
    candidate + history ids, full trainable TextEncoder on the unique rows;
    with the trunk's routing counters."""
    b, c = candidates.shape
    h = history.shape[1]
    with jax.named_scope("news_dedup"):
        ids = jnp.concatenate([candidates.reshape(-1), history.reshape(-1)])
    flat, routing = _encode_unique_tokens(
        text_encoder, news_params, tokens_table, ids, dropout_rng
    )
    with jax.named_scope("news_gather"):
        cand_vecs = flat[: b * c].reshape(b, c, -1)
        his_vecs = flat[b * c :].reshape(b, h, -1)
    return cand_vecs, his_vecs, routing


def _encode_tokens_rows(
    text_encoder: Any,
    news_params: Any,
    tokens_table: jnp.ndarray,
    ids_2d: jnp.ndarray,
    dropout_rng: jax.Array | None,
) -> jnp.ndarray:
    """Encode one (B, K) id block's unique news through the full TextEncoder.

    Used under sequence parallelism in finetune mode, where candidates and
    history must be encoded SEPARATELY: a joint ``jnp.unique`` over
    candidates + the local history shard would place the same candidate news
    at a different row index on each seq shard, giving it a different trunk
    dropout mask despite the shared key — silently de-replicating the
    candidate encode (and making the 1/n_seq grad correction inexact).
    Encoding candidates alone keeps their row layout (and mask) identical on
    every shard; history rows live on exactly one shard each, so their masks
    are free to differ.
    """
    b, k = ids_2d.shape
    flat, _ = _encode_unique_tokens(
        text_encoder, news_params, tokens_table, ids_2d.reshape(-1), dropout_rng
    )
    return flat.reshape(b, k, -1)


def encode_corpus_tokens(
    text_encoder: Any,
    news_params: Any,
    news_tokens: jnp.ndarray,
    chunk: int = 512,
) -> jnp.ndarray:
    """(N, 2, L) token table -> (N, D) news vectors via the full TextEncoder
    (finetune-mode corpus encode for evaluation), chunked over N."""
    n = news_tokens.shape[0]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    padded = jnp.pad(news_tokens, ((0, pad), (0, 0), (0, 0)))
    chunks = padded.reshape(-1, chunk, *padded.shape[1:])

    def encode(c):
        return text_encoder.apply({"params": news_params}, c)

    vecs = lax.map(encode, chunks)
    return vecs.reshape(-1, vecs.shape[-1])[:n]


def encode_all_news(
    model: NewsRecommender,
    news_params: Any,
    token_states: jnp.ndarray,
    chunk: int = 2048,
) -> jnp.ndarray:
    """(N, L, bert_hidden) -> (N, D) news-vector table, chunked over N.

    The TPU answer to ``gen_news_vecs`` over the full corpus (reference
    ``model.py:41-61``): one jitted ``lax.map`` over fixed-size chunks keeps
    peak VMEM bounded while the matmuls stay MXU-sized.
    """
    n = token_states.shape[0]
    chunk = min(chunk, n)  # don't pad small corpora up to the chunk size
    pad = (-n) % chunk
    padded = jnp.pad(token_states, ((0, pad), (0, 0), (0, 0)))
    chunks = padded.reshape(-1, chunk, *padded.shape[1:])

    def encode(c):
        return model.apply(
            {"params": {"text_head": news_params}},
            c,
            method=NewsRecommender.encode_news,
        )

    vecs = lax.map(encode, chunks)
    return vecs.reshape(-1, vecs.shape[-1])[:n]


def encode_all_news_sharded(
    model: NewsRecommender,
    news_params: Any,
    token_states: jnp.ndarray,
    mesh: Mesh,
    chunk: int = 2048,
) -> jnp.ndarray:
    """Corpus encode sharded over EVERY mesh axis: each of the mesh's
    ``mesh.size`` devices encodes ``N / mesh.size`` rows (a (clients, seq)
    mesh shards over both axes jointly), and the result is logically the
    full (N, D) table (XLA inserts the gather only where a consumer needs
    it replicated).

    On a pod this turns the per-round corpus refresh — the eval-path
    bottleneck at MIND scale (65k news) — into ``1/mesh.size`` of the
    single-chip wall time. Exact same math as :func:`encode_all_news`
    (the per-shard body IS that function).
    """
    axes = tuple(mesh.axis_names)
    n = token_states.shape[0]
    pad = (-n) % mesh.size
    padded = (
        jnp.pad(token_states, ((0, pad), (0, 0), (0, 0))) if pad else token_states
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(axes)),
        out_specs=P(axes),
        check_vma=False,
    )
    def enc(params, rows):
        return encode_all_news(model, params, rows, chunk)

    return enc(news_params, padded)[:n]


def build_corpus_encode(model: NewsRecommender, mesh: Mesh, spec: P = P()) -> Callable:
    """``encode(news_params, token_states) -> (N, D)`` news vectors as ONE
    program whose table argument states the at-rest format
    (:func:`token_table_format`; ``spec`` is the table's at-rest
    partitioning), so the corpus encode reads the committed table where it
    lies. Sharded over every mesh device when there are several
    (:func:`encode_all_news_sharded`), else :func:`encode_all_news`."""

    def encode(news_params, token_states):
        if mesh.size > 1:
            return encode_all_news_sharded(model, news_params, token_states, mesh)
        return encode_all_news(model, news_params, token_states)

    return jax.jit(encode, in_shardings=(None, token_table_format(mesh, spec)))


def _reshard_state_out(fn: Callable, state_shardings: Any) -> Callable:
    """Wrap a compiled program so its STATE output is re-committed to the
    at-rest FSDP layout (``shard.policy``) inside the same program: the
    ``shard_map`` in-spec forces the gather on entry, this constraint is
    the slice on exit — one dispatch, no host round-trip, and donation
    still works because input and output carry identical layouts.
    ``None`` returns ``fn`` untouched (the byte-identical ``fsdp=1``
    degenerate program)."""
    if state_shardings is None:
        return fn

    def wrapped(*args):
        out = fn(*args)
        if isinstance(out, tuple):
            return (
                jax.lax.with_sharding_constraint(out[0], state_shardings),
                *out[1:],
            )
        return jax.lax.with_sharding_constraint(out, state_shardings)

    return wrapped


# ------------------------------------------------------------- train steps
# The words under which every op of ``jit_sharded_step`` and
# ``jit_sharded_sync`` that takes time is found on a device trace (an op's
# ``tf_op`` path; the innermost, the one named last, is the op's scope):
# ``jax.named_scope``s opened here, in ``models/bert.py`` and in the three
# routed trunks, and the Flax module names ``text_head``, ``user_encoder``
# and ``trunk`` (the rest of a trunk: residual adds, the final norm).
DEVICE_SCOPES = (
    # this file
    "news_dedup", "news_gather", "score_loss", "grad_sync", "dp_clip",
    "dp_noise", "optimizer", "health_sentry", "param_sync", "client_axis",
    # the towers, by module name
    "text_head", "user_encoder", "trunk",
    # the routed trunks (models/sparse_trunk.py, latent_trunk.py, window_trunk.py)
    "trunk_embed", "trunk_attention", "moe_route", "moe_experts", "moe_combine",
    "weight_cast", "chunk_stack", "residual_mix", "latent_attention",
    "dense_ffn", "shared_expert", "window_attention", "attention_core",
)


def _build_local_step(
    model: NewsRecommender,
    cfg: ExperimentConfig,
    strategy: FedStrategy,
    mesh: Mesh,
    mode: str | None = None,
    noise_fn: Callable[[Any, jax.Array], Any] | None = None,
    sharded_table: Any | None = None,
) -> tuple[Callable, int, Any, str]:
    """The ONE construction of the per-client step math.

    Returns ``(local_step, cohort_k, batch_spec, mesh_axis)`` — wrapped into
    the per-batch program by ``build_fed_train_step``.

    ``noise_fn(grads, rng) -> grads`` is the LDP hook: applied per client,
    device-side, *before* any cross-client collective (the honest version of
    reference ``client.py:87-89``). When None and ``cfg.privacy.enabled``, it
    is built from the config; with ``mechanism='dpsgd'`` the joint path
    additionally switches to per-example clipped gradients.

    ``sharded_table`` (a ``shard.table.TableSpec``, from ``shard.table``):
    the feature table arrives as this device's LOCAL row block instead of
    the replicated array, and the unique-news gather runs the
    owner-bucketed ``all_to_all`` exchange — bit-identical rows, catalog
    capacity scaling with the mesh. Joint ("head") mode only; the
    unsupported combinations fail fast here, at build time.
    """
    mode = _resolve_mode(cfg, mode)
    text_encoder = None
    if mode == "finetune":
        from fedrec_tpu.models.bert import make_text_encoder

        text_encoder = make_text_encoder(cfg.model)
    opt_user_tx, opt_news_tx = make_optimizers(cfg)
    axis = cfg.fed.mesh_axis
    # in-device client cohorts (num_clients > mesh slots): the local block
    # carries k clients, vmapped under LOCAL_AXIS; every cross-client
    # collective then spans (LOCAL_AXIS, mesh axis) so federation semantics
    # are independent of the client->chip packing
    k, sync_axes = cohort_axes(cfg, mesh)
    # sequence parallelism: history sharded over a second mesh axis, user
    # tower attends via ring/Ulysses collectives (fedrec_tpu.parallel.ring)
    n_seq = cfg.fed.seq_shards
    seq_ax = cfg.fed.seq_axis
    if n_seq > 1:
        if mode not in ("joint", "finetune"):
            raise NotImplementedError(
                "fed.seq_shards > 1 requires mode='joint'/'finetune' (the "
                "decoupled news-grad accumulator is not seq-sharded)"
            )
        if seq_ax not in mesh.axis_names:
            raise ValueError(
                f"fed.seq_shards={n_seq} but mesh {mesh.axis_names} has no "
                f"{seq_ax!r} axis — build the mesh with parallel.mesh.fed_mesh"
            )
        model = model.clone(seq_axis=seq_ax, seq_impl=cfg.fed.seq_impl)
    if noise_fn is None and cfg.privacy.enabled:
        noise_fn = make_noise_fn(cfg.privacy, cfg.data.batch_size)
    use_dpsgd = cfg.privacy.enabled and cfg.privacy.mechanism == "dpsgd"
    if use_dpsgd and n_seq > 1:
        raise NotImplementedError(
            "per-example DP-SGD with sequence parallelism is not supported; "
            "use seq_shards=1 with mechanism='dpsgd'"
        )
    if use_dpsgd and mode == "finetune":
        raise NotImplementedError(
            "per-example DP-SGD over the full trunk is not supported; use "
            "mode='joint' (frozen trunk) for DP training"
        )
    if use_dpsgd and mode != "joint":
        # decoupled mode has no per-example clipping path yet; noising
        # unclipped grads with a DP-SGD-calibrated sigma would claim an
        # (epsilon, delta) guarantee that does not hold
        raise ValueError(
            "mechanism='dpsgd' requires mode='joint'; use mechanism='ldp_news' "
            "(reference-parity noise, no rigorous epsilon) for decoupled mode"
        )
    if cfg.privacy.enabled and cfg.privacy.dp_scope not in ("all", "user"):
        raise ValueError(
            f"unknown privacy.dp_scope {cfg.privacy.dp_scope!r}; "
            "expected 'all' or 'user'"
        )
    # dp_scope='user': DP rounds train ONLY the user tower; the text head is
    # frozen at its current params, so its grads are never computed, clipped,
    # or noised — the per-example sensitivity bound C applies to the user
    # grads alone and the noised dimension shrinks accordingly (docs/DP.md)
    dp_user_only = use_dpsgd and cfg.privacy.dp_scope == "user"
    if cfg.privacy.enabled and cfg.privacy.dp_scope == "user" and not use_dpsgd:
        raise ValueError(
            "privacy.dp_scope='user' requires mechanism='dpsgd' — ldp_news "
            "noises only the news grads, which contradicts a user-only scope"
        )

    # fused hot-path kernels (model.fuse_hot_path, ops.fused_hot_path):
    # kernel (2) — attention+pool+score — rides the model modules, so it is
    # active in every mode (and composes with in-device cohorts: the
    # kernels batch under the cohort vmap); kernel (1) — gather+encode —
    # replaces the joint-mode dense gather for the additive head. The
    # unsupported combinations fail fast HERE, at build time, with the
    # lever to unset.
    fuse = getattr(cfg.model, "fuse_hot_path", False)
    fuse_gather = (
        fuse
        and getattr(cfg.model, "text_head_arch", "additive") == "additive"
    )
    if fuse:
        if use_dpsgd:
            raise NotImplementedError(
                "model.fuse_hot_path with privacy.mechanism='dpsgd' is not "
                "supported (per-example clipping would pay the kernel "
                "launch per example, exactly the overhead regime where "
                "fusion loses); unset one of the two"
            )
        if n_seq > 1:
            raise NotImplementedError(
                "model.fuse_hot_path with fed.seq_shards>1 is not supported "
                "(the fused kernel holds the whole history per row); use "
                "the ring/Ulysses path for sharded histories"
            )

    # mesh-sharded news catalog (shard.table, fedrec_tpu.shard.table): the
    # table in-spec becomes P(clients) and every unique-news gather runs
    # the owner-bucketed all_to_all exchange. The combinations the
    # exchange cannot serve fail fast HERE, with the lever to unset.
    table_gather = None
    if sharded_table is not None:
        if mode != "joint":
            raise NotImplementedError(
                "shard.table requires model.text_encoder_mode='head' (the "
                "joint frozen-trunk step): the decoupled per-epoch table "
                "refresh and the finetune token gather read a replicated "
                "table — unset shard.table for those modes"
            )
        if use_dpsgd:
            raise NotImplementedError(
                "shard.table with privacy.mechanism='dpsgd' is not "
                "supported (per-example clipping gathers each example's "
                "rows directly, bypassing the owner-bucketed exchange); "
                "unset one of the two"
            )
        if n_seq > 1:
            raise NotImplementedError(
                "shard.table with fed.seq_shards>1 is not supported (the "
                "catalog shards over the clients axis; a seq-sharded mesh "
                "would need a 2-D exchange); unset one of the two"
            )
        if fuse:
            raise NotImplementedError(
                "model.fuse_hot_path with shard.table is not supported "
                "until the fused gather+encode kernel learns remote rows "
                "(it streams LOCAL HBM rows only); unset one of the two"
            )
        if k > 1:
            raise NotImplementedError(
                "shard.table with in-device cohorts (fed.num_clients above "
                "the mesh's client slots) is not supported: the "
                "owner-bucketed all_to_all runs once per mesh slot, not "
                "per vmapped cohort client — match fed.num_clients to the "
                "device count"
            )
        from fedrec_tpu.shard.table import owner_bucketed_gather

        def table_gather(rows, ids):
            return owner_bucketed_gather(rows, ids, sharded_table)

    # in-graph numeric sentry (obs.health.sentry): the step additionally
    # returns per-client grad/update/param global norms and a non-finite
    # flag (+ DP clip-rate under dpsgd) — computed on device, fetched by
    # the host with the round's losses, so a silent NaN or a divergent
    # client is visible without a blocking readback per step
    sentry = cfg.obs.health.sentry
    # deterministic fault injection (fed.chaos): per-client update-fault
    # vectors ride the batch as chaos.code/chaos.scale and apply at the
    # update boundary below — same compiled arithmetic in every dispatch
    # mode, bit-identical across runs of the same FaultPlan
    chaos = cfg.chaos.enabled
    if chaos and n_seq > 1:
        raise NotImplementedError(
            "chaos fault injection with fed.seq_shards > 1 is not supported "
            "(the seq-parallel batch spec does not carry the per-client "
            "fault vectors); run the plan with seq_shards=1"
        )

    def local_step(state: ClientState, batch: dict, table: jnp.ndarray):
        dp_stats = None
        routing: dict = {}
        sentry_grads: tuple = ()
        sentry_updates: tuple = ()
        rng, dropout_rng, noise_rng = jax.random.split(state.rng, 3)
        # text-encoder dropout key must be IDENTICAL across seq shards so the
        # replicated candidate encode stays replicated (finetune mode)
        enc_rng = jax.random.fold_in(dropout_rng, 1)
        if n_seq > 1:
            # distinct user-encoder dropout masks per history shard
            # (state.rng is replicated over the seq axis)
            dropout_rng = jax.random.fold_in(dropout_rng, lax.axis_index(seq_ax))

        if mode in ("joint", "finetune"):
            if use_dpsgd:
                # DP-SGD: per-example grads, clipped to C, averaged; each
                # example encodes its own C+H news directly (no cross-example
                # dedup — it would couple examples and break the per-example
                # sensitivity bound; and within one example unique() saves
                # nothing, so gather + encode is the cheapest form)
                def per_example_loss(packed, cand_row, his_row, label, ex_rng):
                    user_params, news_params = packed
                    c = cand_row.shape[0]
                    with jax.named_scope("news_gather"):
                        ids = jnp.concatenate([cand_row, his_row])
                        rows = table[ids]
                    vecs = model.apply(
                        {"params": {"text_head": news_params}},
                        rows,
                        method=NewsRecommender.encode_news,
                    )
                    scores = model.apply(
                        {"params": {"user_encoder": user_params}},
                        vecs[:c][None],
                        vecs[c:][None],
                        train=True,
                        rngs={"dropout": ex_rng},
                    )
                    return score_loss(
                        scores, label[None], cfg.model.sigmoid_before_ce
                    )

                b = batch["labels"].shape[0]
                ex_rngs = jax.random.split(dropout_rng, b)
                batch_args = (
                    batch["candidates"], batch["history"], batch["labels"], ex_rngs,
                )
                # the clipping's own ops (norms, scales, the mean over
                # examples); the loss inside keeps its innermost scopes
                if dp_user_only:
                    with jax.named_scope("dp_clip"):
                        out = per_example_clipped_grads(
                            lambda up, c, h, l, r: per_example_loss(
                                (up, state.news_params), c, h, l, r
                            ),
                            state.user_params,
                            batch_args,
                            cfg.privacy.clip_norm,
                            with_stats=sentry,
                        )
                    loss, user_g = out[0], out[1]
                    news_g = None  # head frozen: no grad exists to leak
                else:
                    with jax.named_scope("dp_clip"):
                        out = per_example_clipped_grads(
                            per_example_loss,
                            (state.user_params, state.news_params),
                            batch_args,
                            cfg.privacy.clip_norm,
                            with_stats=sentry,
                        )
                    loss, (user_g, news_g) = out[0], out[1]
                dp_stats = out[2] if sentry else None
            else:

                def loss_fn(user_params, news_params):
                    routing: dict = {}
                    if mode == "finetune" and n_seq > 1:
                        # candidates and the local history shard are encoded
                        # separately so the candidate row layout — and hence
                        # its trunk dropout mask under the shared enc_rng —
                        # is identical on every seq shard (see
                        # _encode_tokens_rows)
                        cand_vecs = _encode_tokens_rows(
                            text_encoder, news_params, table,
                            batch["candidates"], enc_rng,
                        )
                        his_vecs = _encode_tokens_rows(
                            text_encoder, news_params, table,
                            batch["history"],
                            jax.random.fold_in(enc_rng, 1 + lax.axis_index(seq_ax)),
                        )
                    elif mode == "finetune":
                        # table = raw (N, 2, L) token rows; full trunk + head
                        # runs (and trains) on the batch's unique news
                        cand_vecs, his_vecs, routing = _batch_news_vecs_tokens(
                            text_encoder, news_params, table,
                            batch["candidates"], batch["history"], enc_rng,
                        )
                    else:
                        cand_vecs, his_vecs = _batch_news_vecs(
                            model, news_params, table,
                            batch["candidates"], batch["history"],
                            host_dedup=batch_host_dedup(batch),
                            chunk=cfg.data.gather_chunk,
                            fused=fuse_gather,
                            gather_fn=table_gather,
                            n_news=(
                                sharded_table.num_rows
                                if sharded_table is not None else None
                            ),
                            # the single worker (one client, one device)
                            # runs un-vmapped (_cohort_call): its head takes
                            # the cohort's form, or the step does not fit
                            # beside two resident tables (_encode_gathered)
                            batch_of_one=k == 1 and mesh.size == 1,
                        )
                    if n_seq > 1:
                        # candidate encoding is replicated across seq shards;
                        # scale so the post-grad psum counts it exactly once
                        cand_vecs = _scale_grad(cand_vecs, 1.0 / n_seq)
                    scores = model.apply(
                        {"params": {"user_encoder": user_params}},
                        cand_vecs,
                        his_vecs,
                        train=True,
                        rngs={"dropout": dropout_rng},
                    )
                    loss = score_loss(
                        scores, batch["labels"], cfg.model.sigmoid_before_ce
                    )
                    return loss, routing

                (loss, routing), (user_g, news_g) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True
                )(state.user_params, state.news_params)
                if n_seq > 1:
                    # each seq shard holds a partial param grad (its history
                    # slice); sum -> full grad, replicated over seq
                    with jax.named_scope("grad_sync"):
                        user_g = jax.tree_util.tree_map(
                            lambda g: lax.psum(g, seq_ax), user_g
                        )
                        news_g = jax.tree_util.tree_map(
                            lambda g: lax.psum(g, seq_ax), news_g
                        )
            if noise_fn is not None:
                with jax.named_scope("dp_noise"):
                    if news_g is None:
                        (user_g,) = noise_fn((user_g,), noise_rng)
                    else:
                        user_g, news_g = noise_fn((user_g, news_g), noise_rng)
            # sentry sees the PER-CLIENT grads (post-noise, pre-sync): the
            # synced mean is what steps the optimizer, but a diverging or
            # poisoned client is only visible before the collective blends
            # its gradient into the cohort's
            sentry_grads = (user_g, news_g)
            with jax.named_scope("grad_sync"):
                user_g = strategy.sync_grads(user_g, sync_axes)
            with jax.named_scope("optimizer"):
                u_updates, opt_user = opt_user_tx.update(
                    user_g, state.opt_user, state.user_params
                )
                if chaos:
                    # fault AT the update boundary: the sentry below sees
                    # the faulted update, so detection (and the quarantine
                    # path) fires exactly as it would on a real bad client
                    u_updates = _apply_update_fault(
                        u_updates, batch["chaos.code"], batch["chaos.scale"]
                    )
            n_updates = None
            if news_g is None:
                new_news_params, opt_news = state.news_params, state.opt_news
            else:
                with jax.named_scope("grad_sync"):
                    news_g = strategy.sync_grads(news_g, sync_axes)
                with jax.named_scope("optimizer"):
                    n_updates, opt_news = opt_news_tx.update(
                        news_g, state.opt_news, state.news_params
                    )
                    if chaos:
                        n_updates = _apply_update_fault(
                            n_updates, batch["chaos.code"], batch["chaos.scale"]
                        )
                    new_news_params = jax.tree_util.tree_map(
                        lambda p, u: p + u, state.news_params, n_updates
                    )
            sentry_updates = (u_updates, n_updates)
            with jax.named_scope("optimizer"):
                new_state = state.replace(
                    step=state.step + 1,
                    user_params=jax.tree_util.tree_map(
                        lambda p, u: p + u, state.user_params, u_updates
                    ),
                    news_params=new_news_params,
                    opt_user=opt_user,
                    opt_news=opt_news,
                    rng=rng,
                )

        elif mode == "decoupled":
            # table is the (N, D) news-vector table; user tower trains on
            # gathered vectors, embedding grads accumulate per-nid
            with jax.named_scope("news_gather"):
                cand_vecs0 = table[batch["candidates"]]
                his_vecs0 = table[batch["history"]]

            def loss_fn(user_params, cand_vecs, his_vecs):
                scores = model.apply(
                    {"params": {"user_encoder": user_params}},
                    cand_vecs,
                    his_vecs,
                    train=True,
                    rngs={"dropout": dropout_rng},
                )
                return score_loss(scores, batch["labels"], cfg.model.sigmoid_before_ce)

            loss, (user_g, cand_g, his_g) = jax.value_and_grad(
                loss_fn, argnums=(0, 1, 2)
            )(state.user_params, cand_vecs0, his_vecs0)

            if noise_fn is not None:
                with jax.named_scope("dp_noise"):
                    user_g, cand_g, his_g = noise_fn(
                        (user_g, cand_g, his_g), noise_rng
                    )
            sentry_grads = (user_g, cand_g, his_g)

            # per-nid scatter-add (reference process_news_grad, main.py:20-42)
            d = cand_g.shape[-1]
            with jax.named_scope("news_gather"):
                ids = jnp.concatenate(
                    [batch["candidates"].reshape(-1), batch["history"].reshape(-1)]
                )
                grads_flat = jnp.concatenate(
                    [cand_g.reshape(-1, d), his_g.reshape(-1, d)]
                )
            if state.news_grad_accum.ndim != 2:
                raise ValueError(
                    "the decoupled step accumulates per-news gradients, but "
                    "this state holds no accumulator: it was initialised for "
                    f"model.text_encoder_mode={cfg.model.text_encoder_mode!r} "
                    "(init_client_state keeps one only for 'table')"
                )
            with jax.named_scope("news_gather"):
                # the gather's transpose, kept across the epoch's steps
                accum = state.news_grad_accum.at[ids].add(grads_flat)

            with jax.named_scope("grad_sync"):
                user_g = strategy.sync_grads(user_g, sync_axes)
            with jax.named_scope("optimizer"):
                u_updates, opt_user = opt_user_tx.update(
                    user_g, state.opt_user, state.user_params
                )
                if chaos:
                    u_updates = _apply_update_fault(
                        u_updates, batch["chaos.code"], batch["chaos.scale"]
                    )
                sentry_updates = (u_updates,)
                new_state = state.replace(
                    step=state.step + 1,
                    user_params=jax.tree_util.tree_map(
                        lambda p, u: p + u, state.user_params, u_updates
                    ),
                    opt_user=opt_user,
                    rng=rng,
                    news_grad_accum=accum,
                )
        else:
            raise ValueError(f"unknown step mode {mode!r}")

        with jax.named_scope("score_loss"):
            mean_loss = lax.pmean(loss, axis_name=sync_axes)
        metrics = {"loss": loss, "mean_loss": mean_loss}
        # a routed trunk's counters, under the names ``models.bert``'s
        # ``TRUNK_COUNTERS`` gives them (``moe.*``, ``trunk.*``)
        metrics.update(routing)
        if sentry:
            with jax.named_scope("health_sentry"):
                grad_norm = _tree_global_norm(*sentry_grads)
                update_norm = _tree_global_norm(*sentry_updates)
                param_norm = _tree_global_norm(
                    new_state.user_params, new_state.news_params
                )
                finite = (
                    jnp.isfinite(loss)
                    & jnp.isfinite(grad_norm)
                    & jnp.isfinite(update_norm)
                    & jnp.isfinite(param_norm)
                )
                # int32 sentinel, not bool: the host stacks it over steps
                # and sums it — "how many step×client cells went non-finite"
                nonfinite = 1 - finite.astype(jnp.int32)
            metrics["health.grad_norm"] = grad_norm
            metrics["health.update_norm"] = update_norm
            metrics["health.param_norm"] = param_norm
            metrics["health.nonfinite"] = nonfinite
            if dp_stats is not None:
                metrics["health.clip_rate"] = dp_stats["clip_rate"]
                metrics["health.clip_max_norm"] = dp_stats["max_norm"]
        return new_state, metrics

    if n_seq > 1:
        # history's last dim lives sharded over the seq axis; the step then
        # requires exactly the canonical batch keys (shard_fed_batch's layout)
        batch_spec: Any = {
            "candidates": P(axis),
            "history": P(axis, None, seq_ax),
            "labels": P(axis),
        }
    else:
        batch_spec = P(axis)

    return local_step, k, batch_spec, axis


def build_fed_train_step(
    model: NewsRecommender,
    cfg: ExperimentConfig,
    strategy: FedStrategy,
    mesh: Mesh,
    mode: str | None = None,
    noise_fn: Callable[[Any, jax.Array], Any] | None = None,
    donate_batch: bool = False,
    sharded_table: Any | None = None,
    state_shardings: Any | None = None,
) -> Callable:
    """Compile the per-batch federated train step.

    Returns ``step(stacked_state, batch_arrays, feature_table) ->
    (new_stacked_state, metrics)`` where ``batch_arrays`` is a dict of
    ``(num_clients, B, ...)`` arrays sharded over ``clients`` and
    ``feature_table`` is replicated — token states for ``joint`` mode, the
    news-vector table for ``decoupled`` mode. Step math and the LDP/DP
    hooks are documented on ``_build_local_step``.

    ``donate_batch`` additionally donates the batch buffers (the Trainer
    device_puts fresh arrays every dispatch, so XLA may reclaim them as
    scratch once consumed); leave False when re-dispatching the same batch
    arrays.

    ``sharded_table`` (a ``shard.table.TableSpec``): the feature table is
    row-sharded over the clients axis instead of replicated, gathered
    in-step by the owner-bucketed exchange. ``state_shardings`` (from
    ``shard.policy.fsdp_state_shardings``): the returned state re-commits
    to the at-rest FSDP layout inside the same program. Both default to
    None = the byte-identical pre-shard program.
    """
    local_step, k, batch_spec, axis = _build_local_step(
        model, cfg, strategy, mesh, mode, noise_fn, sharded_table
    )
    table_spec = P(axis) if sharded_table is not None else P()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis), batch_spec, table_spec),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    def sharded_step(stacked_state, batch, table):
        return _cohort_call(local_step, k, 2, stacked_state, batch, table)

    return jax.jit(
        _reshard_state_out(sharded_step, state_shardings),
        in_shardings=_table_in_shardings(3, cfg, mode, mesh, table_spec),
        donate_argnums=(0, 1) if donate_batch else (0,),
    )


def build_news_update_step(
    model: NewsRecommender,
    cfg: ExperimentConfig,
    mesh: Mesh,
    strategy: FedStrategy | None = None,
    state_shardings: Any | None = None,
) -> Callable:
    """Epoch-end news-head update for ``decoupled`` mode.

    Replays each client's accumulated per-nid embedding gradients through the
    text head with ``jax.vjp`` — semantically the reference's
    ``update_news_grad`` (``model.py:72-90``: forward touched news, then
    ``news_vecs.backward(news_grad)``, then Adam step) — and refreshes the
    news-vector table. All news rows participate (untouched rows have zero
    accumulated grad, contributing nothing, so no dynamic-shape "touched
    only" gather is needed).

    Under ``GradAvg`` the resulting head gradient is ``pmean``-ed across
    clients before the Adam step: because the accumulator and vjp are linear,
    averaging once here is mathematically identical to averaging the per-step
    embedding grads (DDP parity, reference ``Gradient_Averaging_main.py:119``)
    at a fraction of the collective cost.
    """
    _, opt_news_tx = make_optimizers(cfg)
    axis = cfg.fed.mesh_axis
    strategy = strategy or FedStrategy()
    k, sync_axes = cohort_axes(cfg, mesh)

    def local_update(state: ClientState, token_states: jnp.ndarray):
        def encode(news_params):
            return encode_all_news(model, news_params, token_states)

        vecs, vjp = jax.vjp(encode, state.news_params)
        (head_g,) = vjp(state.news_grad_accum)
        head_g = strategy.sync_grads(head_g, sync_axes)
        n_updates, opt_news = opt_news_tx.update(
            head_g, state.opt_news, state.news_params
        )
        new_params = jax.tree_util.tree_map(
            lambda p, u: p + u, state.news_params, n_updates
        )
        new_vecs = encode(new_params)
        new_state = state.replace(
            news_params=new_params,
            opt_news=opt_news,
            news_grad_accum=jnp.zeros_like(state.news_grad_accum),
        )
        return new_state, new_vecs

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    def sharded_update(stacked_state, token_states):
        return _cohort_call(local_update, k, 1, stacked_state, token_states)

    return jax.jit(
        _reshard_state_out(sharded_update, state_shardings),
        in_shardings=(None, token_table_format(mesh)),
        donate_argnums=(0,),
    )


def compressed_sync_active(cfg: ExperimentConfig, strategy: FedStrategy) -> bool:
    """True when the round-end sync runs the update-codec body — which
    takes the round-ENTRY params as extra arguments (deltas are what the
    codec compresses). ``dcn_compress='none'`` keeps the pre-codec sync
    program byte-for-byte (the bit-identity contract)."""
    return (
        getattr(cfg.fed, "dcn_compress", "none") != "none"
        and strategy.sync_params_every_round
    )


def _make_local_sync(
    strategy: FedStrategy, sync_axes: Any, robust: Any = None,
    fed_cfg: Any = None, leaf_codecs: list | None = None,
) -> Callable:
    """THE round-end parameter-sync body of ``build_param_sync``.
    Optimizer states stay local (the reference likewise only averages
    parameters).

    ``robust`` (a ``fed.robust`` config section) swaps the weighted mean
    for a Byzantine-robust aggregator when ``method != "mean"`` — both
    towers aggregate as ONE tree so the clip method's global norm spans
    the whole client update (``fedrec_tpu.fed.robust``). Strategies that
    never sync params (local/grad_avg) stay untouched.

    ``fed_cfg`` (the ``fed`` config section) selects the update codec
    (``dcn_compress``). With a codec active the body signature grows to
    ``(state, w, entry_user, entry_news)`` — the client's round-ENTRY
    params — and the sync becomes the compressed-uplink model
    (``fedrec_tpu.comms``):

      1. ``delta_c = params_c - entry_c`` (each client's round update —
         DP clip+noise already happened per step, BEFORE any encode);
      2. ``acc_c = delta_c + residual_c`` (error feedback, biased codecs);
      3. ``decoded_c = decode(encode(acc_c))`` in-graph — the arithmetic
         twin of the wire codec; ``residual_c' = acc_c - decoded_c`` for
         participants (non-participants transmitted nothing and keep
         their residual);
      4. DECODE-BEFORE-REDUCE: the aggregator — weighted mean OR any
         ``fed.robust`` method — runs over the decoded dense deltas, so
         trimmed-mean/median judge clients, not quantization noise;
      5. every client adopts ``entry + aggregate`` (entries are the common
         post-sync global in any participating round); a round where no
         client reports keeps local params, the ``weighted_param_avg``
         contract.

    ``leaf_codecs`` (``fed.dcn_compress='auto'``): a pinned per-leaf codec
    map — one concrete codec per flattened leaf of the ``(user, news)``
    contribution tree, overriding the tree-wide codec. Error feedback then
    applies PER LEAF, only where the leaf's codec supports it (the
    capability table); sketch leaves stay unbiased and bank nothing.
    """
    method = getattr(robust, "method", "mean") if robust is not None else "mean"
    codec = getattr(fed_cfg, "dcn_compress", "none") if fed_cfg is not None else "none"
    if codec != "none" and strategy.sync_params_every_round:
        from fedrec_tpu.comms import (
            codec_caps,
            codec_uses_feedback,
            jax_encode_decode,
            validate_codec,
        )
        from fedrec_tpu.fed.strategies import weighted_param_avg

        if leaf_codecs is None and codec != "auto":
            validate_codec(codec)
        use_ef = codec_uses_feedback(codec, fed_cfg.dcn_error_feedback)
        ratio = fed_cfg.dcn_topk_ratio
        sk_width = getattr(fed_cfg, "dcn_sketch_width", 0.1)
        sk_seed = getattr(fed_cfg, "dcn_sketch_seed", 0)
        if method != "mean":
            from fedrec_tpu.fed.robust import (
                robust_aggregate,
                validate_robust_method,
            )

            validate_robust_method(method)

        def local_sync(state: ClientState, w: jnp.ndarray, entry_u, entry_n):
            entry = (entry_u, entry_n)
            theta = (state.user_params, state.news_params)
            delta = jax.tree_util.tree_map(
                lambda t, e: t.astype(jnp.float32) - e.astype(jnp.float32),
                theta, entry,
            )
            flat_d, treedef = jax.tree_util.tree_flatten(delta)
            # codec="auto" with no pinned map yet = the warmup window:
            # an all-"none" map (dense sync through the codec program
            # shape, so the later pin only swaps leaf constants)
            tree_wide = "none" if codec == "auto" else codec
            per_leaf = (
                [tree_wide] * len(flat_d)
                if leaf_codecs is None
                else [validate_codec(c) for c in leaf_codecs]
            )
            if len(per_leaf) != len(flat_d):
                raise ValueError(
                    f"per-leaf codec map has {len(per_leaf)} entries but "
                    f"the contribution tree has {len(flat_d)} leaves"
                )
            # EF applies per leaf, only where the leaf's codec is biased
            # (supports_error_feedback); unbiased leaves bank nothing
            ef_flags = [
                use_ef and codec_caps(c).supports_error_feedback
                for c in per_leaf
            ]
            flat_r = (
                jax.tree_util.tree_leaves(state.ef_residual)
                if use_ef
                else [None] * len(flat_d)
            )
            decs, new_rs = [], []
            for i, (d, c) in enumerate(zip(flat_d, per_leaf)):
                a = d + flat_r[i] if ef_flags[i] else d
                dec = jax_encode_decode(
                    a, c, ratio,
                    sketch_width=sk_width, sketch_seed=sk_seed, leaf_id=i,
                )
                decs.append(dec)
                if use_ef:
                    # a weight-0 client transmitted nothing this round:
                    # its residual carries over unchanged (its delta is
                    # discarded with its participation, not banked)
                    new_rs.append(
                        jnp.where(w > 0, a - dec, flat_r[i])
                        if ef_flags[i]
                        else flat_r[i]
                    )
            decoded = jax.tree_util.tree_unflatten(treedef, decs)
            new_residual = (
                jax.tree_util.tree_unflatten(treedef, new_rs)
                if use_ef
                else None
            )
            if method != "mean":
                agg = robust_aggregate(
                    decoded, w, sync_axes,
                    method=method, trim_k=robust.trim_k,
                    clip_norm=robust.clip_norm,
                )
            else:
                agg = weighted_param_avg(decoded, w, sync_axes)
            any_p = lax.psum(
                (w > 0).astype(jnp.float32), axis_name=sync_axes
            ) > 0
            new_user, new_news = jax.tree_util.tree_map(
                lambda e, a, t: jnp.where(
                    any_p, (e.astype(jnp.float32) + a).astype(t.dtype), t
                ),
                entry, agg, theta,
            )
            kwargs: dict = {"user_params": new_user, "news_params": new_news}
            if new_residual is not None:
                kwargs["ef_residual"] = new_residual
            return state.replace(**kwargs)

        return local_sync

    if method != "mean" and strategy.sync_params_every_round:
        from fedrec_tpu.fed.robust import robust_aggregate, validate_robust_method

        validate_robust_method(method)

        def local_sync(state: ClientState, w: jnp.ndarray):
            new_user, new_news = robust_aggregate(
                (state.user_params, state.news_params),
                w,
                sync_axes,
                method=method,
                trim_k=robust.trim_k,
                clip_norm=robust.clip_norm,
            )
            return state.replace(user_params=new_user, news_params=new_news)

        return local_sync

    def local_sync(state: ClientState, w: jnp.ndarray):
        new_user = strategy.sync_params(state.user_params, w, sync_axes)
        new_news = strategy.sync_params(state.news_params, w, sync_axes)
        return state.replace(user_params=new_user, news_params=new_news)

    return local_sync


def build_param_sync(
    cfg: ExperimentConfig,
    mesh: Mesh,
    strategy: FedStrategy | None = None,
    state_shardings: Any | None = None,
    leaf_codecs: list | None = None,
) -> Callable:
    """Round-end parameter aggregation, dispatched through the strategy.

    ``sync(stacked_state, weights) -> stacked_state`` where ``weights`` is a
    (num_clients,) mask/weight vector. With ``ParamAvg``, equal weights
    reproduce the reference's ``all_reduce(param)/world_size`` FedAvg
    (``Parameter_Averaging_main.py:144-148``); masks implement client-subset
    rounds. ``Local``/``GradAvg`` leave parameters untouched. Optimizer
    states stay local (the reference likewise only averages parameters).
    """
    axis = cfg.fed.mesh_axis
    strategy = strategy or ParamAvg()
    k, sync_axes = cohort_axes(cfg, mesh)
    local_sync = _make_local_sync(
        strategy, sync_axes, cfg.fed.robust, cfg.fed, leaf_codecs=leaf_codecs
    )

    if compressed_sync_active(cfg, strategy):
        # codec body: ``sync(state, weights, entry_user, entry_news)`` —
        # the caller supplies the round-ENTRY param trees (stacked per
        # client), captured before the round's first (buffer-donating)
        # step dispatch
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )
        def sharded_sync_c(stacked_state, weights, entry_u, entry_n):
            with jax.named_scope("param_sync"):
                return _cohort_call(
                    local_sync, k, 4, stacked_state, weights, entry_u, entry_n
                )

        return jax.jit(_reshard_state_out(sharded_sync_c, state_shardings))

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    def sharded_sync(stacked_state, weights):
        with jax.named_scope("param_sync"):
            return _cohort_call(local_sync, k, 2, stacked_state, weights)

    # NOT donated (unlike the train step): sync runs once per round, so the
    # transient double-buffer is cheap, and callers legitimately hold the
    # pre-sync state for comparisons (e.g. the local-strategy identity test)
    return jax.jit(_reshard_state_out(sharded_sync, state_shardings))


# --------------------------------------------------------------- eval step
def build_eval_step(model: NewsRecommender, cfg: ExperimentConfig) -> Callable:
    """Per-impression validation metrics on device.

    ``evaluate(user_params, news_vecs_table, batch) -> dict of (B,) arrays``
    scoring candidates by dot product (reference ``Trainer.validate``,
    ``client.py:149-171``). Returns PER-IMPRESSION vectors (incl. per-row
    loss) so the caller can trim batch padding before averaging — fixing
    both the reference's last-sample-only bug (``client.py:171``) and the
    wrap-around-pad double count of a naive batch mean.
    """

    def evaluate(user_params, news_vecs, batch):
        cand_vecs = news_vecs[batch["candidates"]]
        his_vecs = news_vecs[batch["history"]]
        user_vec = model.apply(
            {"params": {"user_encoder": user_params}},
            his_vecs,
            method=NewsRecommender.encode_user,
        )
        scores = score_candidates(cand_vecs, user_vec)
        out = dict(ranking_metrics_batch(scores))
        out["loss"] = score_loss(
            scores, batch["labels"], cfg.model.sigmoid_before_ce, reduce=False
        )
        return out

    return jax.jit(evaluate)


def _full_eval_body(
    model: NewsRecommender, quality: tuple | None = None
) -> Callable:
    """Per-impression full-pool scoring — the ONE definition both the
    unsharded and the mesh-sharded eval step wrap (a fix applied to the
    scoring math can never diverge the two paths).

    ``quality`` = ``(score_bins, score_range, ece_bins)`` additionally
    returns the fixed-shape quality partial sums
    (:func:`fedrec_tpu.eval.metrics.quality_stats_batch` — score
    histograms + reliability bins, no host syncs) from the SAME scores;
    the batch then carries a ``keep`` (B,) weight vector zeroing padded
    impressions.  ``quality=None`` builds the exact pre-quality program.
    """
    from fedrec_tpu.eval.metrics import full_pool_metrics_batch, quality_stats_batch

    def evaluate(user_params, news_vecs, batch):
        his_vecs = news_vecs[batch["history"]]
        user_vec = model.apply(
            {"params": {"user_encoder": user_params}},
            his_vecs,
            method=NewsRecommender.encode_user,
        )  # (B, D)
        pos_scores = jnp.einsum("bd,bd->b", news_vecs[batch["pos"]], user_vec)
        neg_scores = jnp.einsum("bpd,bd->bp", news_vecs[batch["neg_pools"]], user_vec)
        out = full_pool_metrics_batch(pos_scores, neg_scores, batch["neg_mask"])
        if quality is not None:
            score_bins, score_range, ece_bins = quality
            out.update(quality_stats_batch(
                pos_scores, neg_scores, batch["neg_mask"], batch["keep"],
                score_bins, score_range, ece_bins,
            ))
        return out

    return evaluate


def build_full_eval_step(
    model: NewsRecommender, cfg: ExperimentConfig, quality: tuple | None = None
) -> Callable:
    """Deterministic FULL-POOL evaluation step.

    ``evaluate(user_params, news_vecs_table, batch) -> dict of (B,) arrays``
    where ``batch`` holds per-impression ``pos`` (B,), padded negative pools
    ``neg_pools`` (B, P) with ``neg_mask`` (B, P), and ``history`` (B, H).
    Scores every real pool negative against the one positive — the protocol
    behind the reference's published MIND table (``evaluation_split``,
    reference ``evaluation_functions.py:33-47``), with no sampling noise.
    ``quality`` (see :func:`_full_eval_body`) adds the fixed-shape
    quality partial sums to the outputs.
    """
    return jax.jit(_full_eval_body(model, quality))


def build_full_eval_step_sharded(
    model: NewsRecommender, cfg: ExperimentConfig, mesh: Mesh,
    quality: tuple | None = None,
) -> Callable:
    """:func:`build_full_eval_step` sharded over EVERY mesh axis.

    Each of the mesh's devices scores ``B / mesh.size`` impressions against
    the replicated news-vector table; per-impression metrics come back
    sharded and the caller's host mean is unchanged. Same per-impression
    math as the unsharded step (the shard body IS it), so the published-
    table protocol stays exact while the full-pool pass — the eval
    bottleneck at MIND scale — takes ``1/mesh.size`` of the wall time.
    Callers must keep the batch axis divisible by ``mesh.size`` (the
    Trainer rounds its eval block size accordingly).

    With ``quality`` set, the per-shard quality partial sums are
    ``psum``-reduced across the mesh inside the shard body and come back
    replicated (out-spec ``P()``), so the host accumulates the same
    global sums it would from the unsharded step.
    """
    axes = tuple(mesh.axis_names)
    if quality is None:
        sharded = partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(), P(axes)),
            out_specs=P(axes),
            check_vma=False,
        )(_full_eval_body(model))
        return jax.jit(sharded)

    from fedrec_tpu.eval.metrics import QUALITY_SUM_KEYS

    body = _full_eval_body(model, quality)

    def body_psum(user_params, news_vecs, batch):
        out = body(user_params, news_vecs, batch)
        for k in QUALITY_SUM_KEYS:
            out[k] = jax.lax.psum(out[k], axes)
        return out

    out_specs = {
        **{k: P(axes) for k in ("auc", "mrr", "ndcg5", "ndcg10")},
        **{k: P() for k in QUALITY_SUM_KEYS},
    }
    sharded = partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(axes)),
        out_specs=out_specs,
        check_vma=False,
    )(body_psum)
    return jax.jit(sharded)
