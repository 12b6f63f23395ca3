"""Training state: parameters + dual optimizer states + PRNG, as one pytree.

The reference keeps two inner Adam optimizers on the model object (lr 5e-5,
reference ``model.py:22-23``) plus a vestigial outer SGD (``main.py:171``) —
here the state is an explicit immutable pytree: ``{user, news}`` parameter
subtrees with separate optax states (preserving the two-optimizer structure,
minus the dead outer SGD — ledger item), a per-client PRNG key, and the
news-embedding-gradient accumulator for the decoupled (reference-parity)
update path (``model.py:97-109`` ``collect``).

Federated simulation stacks one ``ClientState`` per client along a leading
axis that is sharded over the mesh's ``clients`` axis.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct

from fedrec_tpu.config import ExperimentConfig
from fedrec_tpu.models import NewsRecommender


@struct.dataclass
class ClientState:
    step: jnp.ndarray                 # int32 scalar
    user_params: Any                  # user-encoder subtree
    news_params: Any                  # text-head subtree
    opt_user: Any                     # optax state for user_params
    opt_news: Any                     # optax state for news_params
    rng: jax.Array                    # per-client PRNG key
    # (N_news, D) embedding-grad scatter target of the decoupled step; a
    # scalar zero placeholder where the configured step is joint or
    # finetune, which never read it (init_client_state)
    news_grad_accum: jnp.ndarray
    # per-client error-feedback residual for the biased update codecs
    # (fed.dcn_compress = sign1bit/topk with fed.dcn_error_feedback): a
    # (user_params, news_params)-shaped pytree holding the mass the last
    # lossy encode dropped, re-entering the next round's update. A scalar
    # zero placeholder when the active codec keeps no residual — the state
    # template (and so snapshots and the population sidecar) stay one
    # structure per config. Listed in fed.population.SIDECAR_FIELDS, so it
    # LRU/disk-spills with the optimizer moments and resets on quarantine
    # heal (a healed client must not replay a poisoned residual).
    ef_residual: Any = None

    def full_params(self) -> dict:
        """Reassemble the flax variables dict for ``model.apply``."""
        return {"params": {"user_encoder": self.user_params, "text_head": self.news_params}}


def make_optimizers(cfg: ExperimentConfig) -> tuple[optax.GradientTransformation, optax.GradientTransformation]:
    def _make(lr: float) -> optax.GradientTransformation:
        if cfg.optim.lr_schedule not in ("constant", "cosine"):
            raise ValueError(
                f"unknown lr_schedule {cfg.optim.lr_schedule!r} "
                "(constant|cosine)"
            )
        sched: float | optax.Schedule = lr
        if cfg.optim.lr_schedule == "cosine" and cfg.optim.decay_steps > 0:
            # cosine decay over the run's optimizer-step budget (the caller
            # sets decay_steps = rounds * local_epochs * steps_per_epoch;
            # decay_steps=0 means constant, per the config contract).
            # Matters most for DP-SGD: injected-noise variance scales with
            # lr^2, so a small late lr averages the noise out while the
            # large early lr does the escaping (docs/DP.md)
            sched = optax.cosine_decay_schedule(
                lr, cfg.optim.decay_steps, alpha=cfg.optim.lr_min_frac
            )
        txs = []
        if cfg.optim.grad_clip_norm > 0:
            txs.append(optax.clip_by_global_norm(cfg.optim.grad_clip_norm))
        if cfg.optim.optimizer == "adam":
            txs.append(optax.adam(sched))
        elif cfg.optim.optimizer == "sgd":
            txs.append(optax.sgd(sched))
        else:
            raise ValueError(f"unknown optimizer {cfg.optim.optimizer!r}")
        return optax.chain(*txs)

    return _make(cfg.optim.user_lr), _make(cfg.optim.news_lr)


def _as_one_program(cfg: ExperimentConfig, build: Callable) -> Callable:
    """``build`` as ONE compiled program where there is a trunk to train
    (``finetune``): run op by op, a trunk's init compiles a hundred small
    programs on the way, and every intermediate tree of gigabytes stays
    alive beside the result."""
    return jax.jit(build) if cfg.model.text_encoder_mode == "finetune" else build


def init_client_state(
    model: NewsRecommender,
    cfg: ExperimentConfig,
    rng: jax.Array,
    num_news: int,
    title_len: int | None = None,
) -> ClientState:
    """Initialize one client's state (shapes from config; no data needed)."""
    return _as_one_program(
        cfg, lambda key: _init_client_state(model, cfg, key, num_news, title_len)
    )(rng)


def _init_client_state(
    model: NewsRecommender,
    cfg: ExperimentConfig,
    rng: jax.Array,
    num_news: int,
    title_len: int | None,
) -> ClientState:
    title_len = title_len or cfg.data.max_title_len
    init_rng, state_rng = jax.random.split(rng)
    dummy_states = jnp.zeros((1, title_len, cfg.model.bert_hidden), cfg.model.dtype)
    dummy_cand = jnp.zeros((1, 1 + cfg.data.npratio, cfg.model.news_dim), cfg.model.dtype)
    dummy_his = jnp.zeros((1, cfg.data.max_his_len, cfg.model.news_dim), cfg.model.dtype)
    variables = model.init(
        init_rng, dummy_states, dummy_cand, dummy_his,
        method=NewsRecommender.init_both_towers,
    )
    user_params = variables["params"]["user_encoder"]
    if cfg.model.text_encoder_mode == "finetune":
        # news tower = full TextEncoder (trunk + head), trained in-loop
        # (BASELINE config 5); pretrained trunk weights can be grafted in
        # afterwards via models.bert.load_hf_state_dict
        from fedrec_tpu.models.bert import make_text_encoder

        te = make_text_encoder(cfg.model)
        dummy_tokens = jnp.zeros((1, 2, title_len), jnp.int32)
        news_params = te.init(init_rng, dummy_tokens)["params"]
    else:
        news_params = variables["params"]["text_head"]
    opt_user_tx, opt_news_tx = make_optimizers(cfg)
    from fedrec_tpu.comms import codec_uses_feedback

    if codec_uses_feedback(cfg.fed.dcn_compress, cfg.fed.dcn_error_feedback):
        ef_residual = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32),
            (user_params, news_params),
        )
    else:
        ef_residual = jnp.zeros((), jnp.float32)
    # the per-nid accumulator exists where something reads it: the decoupled
    # step scatters into it and news_update replays it. The joint and
    # finetune steps only carried it: 839 MB of eight clients' state at
    # MIND-small, copied again by every round-end sync (PERF.md section 6,
    # PR 27: with it fed8.b64 does not fit beside the committed table)
    if cfg.model.text_encoder_mode == "table":
        news_grad_accum = jnp.zeros((num_news, cfg.model.news_dim), jnp.float32)
    else:
        news_grad_accum = jnp.zeros((), jnp.float32)
    return ClientState(
        step=jnp.zeros((), jnp.int32),
        user_params=user_params,
        news_params=news_params,
        opt_user=opt_user_tx.init(user_params),
        opt_news=opt_news_tx.init(news_params),
        rng=state_rng,
        news_grad_accum=news_grad_accum,
        ef_residual=ef_residual,
    )


def init_stacked_state(
    model: NewsRecommender,
    cfg: ExperimentConfig,
    rng: jax.Array,
    client_rng: jax.Array,
    num_news: int,
    title_len: int | None = None,
) -> ClientState:
    """``replicate_state(init_client_state(...), cfg.fed.num_clients, ...)``
    with the stack as the only output: one client's state beside its stack
    is twice a trunk's gigabytes (8.5 GB each at 709M parameters with
    Adam's moments, on a chip of 15.75)."""
    def build(key, client_key):
        one = _init_client_state(model, cfg, key, num_news, title_len)
        return replicate_state(one, cfg.fed.num_clients, client_key)

    return _as_one_program(cfg, build)(rng, client_rng)


def stack_states(states: list[ClientState]) -> ClientState:
    """Stack per-client states along a new leading (clients) axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def replicate_state(state: ClientState, num_clients: int, rng: jax.Array) -> ClientState:
    """One init broadcast to all clients, with distinct per-client PRNG keys.

    All clients start from identical parameters — matching the reference,
    where the server broadcasts the initial model before round 1
    (``server.py:76-77``).
    """
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (num_clients,) + x.shape), state
    )
    return stacked.replace(rng=jax.random.split(rng, num_clients))
