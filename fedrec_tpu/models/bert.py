"""Flax DistilBERT trunk — the frozen text-encoder backbone.

The reference wraps HuggingFace's torch ``DistilBertModel`` (reference
``encoder.py:19``: ``DistilBertModel.from_pretrained('distilbert-base-uncased')``)
and freezes it (``model.py:25-26``), re-running it on every news title every
batch (the dominant cost, reference ``model.py:41-61``). The TPU design
instead:

  * implements DistilBERT natively in Flax (this module) so the trunk is one
    jittable XLA program — big batched matmuls on the MXU, bfloat16-capable;
  * precomputes the per-news token states ONCE (``precompute_token_states``)
    and caches them HBM-/host-resident; only the small trainable head runs in
    the hot loop (see ``fedrec_tpu.models.encoders.TextHead``);
  * supports full in-loop fine-tuning (``text_encoder_mode='finetune'``,
    BASELINE config 5) via ``TextEncoder`` with ``jax.checkpoint`` remat.

Pretrained weights are loaded by converting a HuggingFace torch ``state_dict``
(``load_hf_state_dict``) — no network access required; point it at a local
``pytorch_model.bin`` / ``model.safetensors``. Without weights the trunk
random-initializes (useful for smoke tests and from-scratch runs).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


@dataclass(frozen=True)
class DistilBertConfig:
    """Architecture knobs; defaults = ``distilbert-base-uncased``."""

    vocab_size: int = 30522
    max_position_embeddings: int = 512
    dim: int = 768
    n_layers: int = 6
    n_heads: int = 12
    hidden_dim: int = 3072          # FFN inner dim
    dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-12


class _SelfAttention(nn.Module):
    """Standard post-LN transformer self-attention WITH output projection.

    (Unlike the recommender's ``MultiHeadAttention``, which follows the
    reference user encoder's no-output-projection design,
    reference ``attention.py:81`` — DistilBERT has ``out_lin``.)
    """

    cfg: DistilBertConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, mask: jnp.ndarray, train: bool = False
    ) -> jnp.ndarray:
        c = self.cfg
        head_dim = c.dim // c.n_heads
        dense = lambda name: nn.Dense(c.dim, dtype=self.dtype, name=name)  # noqa: E731
        b, L, _ = x.shape

        def split(t):
            return t.reshape(b, L, c.n_heads, head_dim)

        q = split(dense("q_lin")(x)) / jnp.sqrt(jnp.asarray(head_dim, self.dtype))
        k = split(dense("k_lin")(x))
        v = split(dense("v_lin")(x))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        # (b, L) key mask -> additive bias; padded keys get -inf-ish
        bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9).astype(scores.dtype)
        attn = jax.nn.softmax(scores + bias, axis=-1)
        attn = nn.Dropout(c.attention_dropout, deterministic=not train)(attn)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(b, L, c.dim)
        return nn.Dense(c.dim, dtype=self.dtype, name="out_lin")(ctx)


class _TransformerBlock(nn.Module):
    cfg: DistilBertConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, mask: jnp.ndarray, train: bool = False
    ) -> jnp.ndarray:
        c = self.cfg
        attn_out = _SelfAttention(c, self.dtype, name="attention")(x, mask, train)
        x = nn.LayerNorm(epsilon=c.layer_norm_eps, dtype=self.dtype, name="sa_layer_norm")(
            x + attn_out
        )
        h = nn.Dense(c.hidden_dim, dtype=self.dtype, name="lin1")(x)
        h = nn.gelu(h, approximate=False)
        h = nn.Dense(c.dim, dtype=self.dtype, name="lin2")(h)
        h = nn.Dropout(c.dropout, deterministic=not train)(h)
        return nn.LayerNorm(
            epsilon=c.layer_norm_eps, dtype=self.dtype, name="output_layer_norm"
        )(x + h)


class DistilBert(nn.Module):
    """Token ids + attention mask -> per-token hidden states (B, L, dim)."""

    cfg: DistilBertConfig = DistilBertConfig()
    dtype: jnp.dtype = jnp.float32
    remat: bool = False               # jax.checkpoint each block (finetune mode)

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray,       # (B, L) int
        attention_mask: jnp.ndarray,  # (B, L) 0/1
        train: bool = False,
    ) -> jnp.ndarray:
        c = self.cfg
        positions = jnp.arange(input_ids.shape[1])[None, :]
        x = nn.Embed(c.vocab_size, c.dim, dtype=self.dtype, name="word_embeddings")(
            input_ids
        )
        x = x + nn.Embed(
            c.max_position_embeddings, c.dim, dtype=self.dtype,
            name="position_embeddings",
        )(positions)
        x = nn.LayerNorm(epsilon=c.layer_norm_eps, dtype=self.dtype, name="emb_layer_norm")(x)
        x = nn.Dropout(c.dropout, deterministic=not train)(x)
        block_cls = _TransformerBlock
        if self.remat:
            block_cls = nn.remat(_TransformerBlock, static_argnums=(3,))
        for i in range(c.n_layers):
            x = block_cls(c, self.dtype, name=f"layer_{i}")(x, attention_mask, train)
        return x


# --------------------------------------------------------- weight conversion
def convert_hf_state_dict(
    state_dict: Mapping[str, Any], cfg: DistilBertConfig
) -> dict:
    """HF torch ``DistilBertModel`` state_dict -> Flax ``DistilBert`` params.

    Accepts tensors or numpy arrays; keys may carry a ``distilbert.`` prefix
    (full-model checkpoints). Dense kernels are transposed (torch stores
    ``(out, in)``; Flax expects ``(in, out)``).
    """

    def arr(key: str) -> np.ndarray:
        for k in (key, f"distilbert.{key}"):
            if k in state_dict:
                v = state_dict[k]
                return np.asarray(v.detach().cpu().numpy() if hasattr(v, "detach") else v)
        raise KeyError(f"missing key {key!r} in state_dict")

    def dense(key: str) -> dict:
        return {"kernel": arr(f"{key}.weight").T, "bias": arr(f"{key}.bias")}

    def ln(key: str) -> dict:
        return {"scale": arr(f"{key}.weight"), "bias": arr(f"{key}.bias")}

    params: dict = {
        "word_embeddings": {"embedding": arr("embeddings.word_embeddings.weight")},
        "position_embeddings": {
            "embedding": arr("embeddings.position_embeddings.weight")
        },
        "emb_layer_norm": ln("embeddings.LayerNorm"),
    }
    for i in range(cfg.n_layers):
        p = f"transformer.layer.{i}"
        params[f"layer_{i}"] = {
            "attention": {
                "q_lin": dense(f"{p}.attention.q_lin"),
                "k_lin": dense(f"{p}.attention.k_lin"),
                "v_lin": dense(f"{p}.attention.v_lin"),
                "out_lin": dense(f"{p}.attention.out_lin"),
            },
            "sa_layer_norm": ln(f"{p}.sa_layer_norm"),
            "lin1": dense(f"{p}.ffn.lin1"),
            "lin2": dense(f"{p}.ffn.lin2"),
            "output_layer_norm": ln(f"{p}.output_layer_norm"),
        }
    return jax.tree_util.tree_map(jnp.asarray, params)


def load_hf_state_dict(path: str, cfg: DistilBertConfig | None = None) -> dict:
    """Load a local HF checkpoint file (.bin via torch, .safetensors) and
    convert. Works fully offline; raises with a clear message if the needed
    loader is unavailable."""
    cfg = cfg or DistilBertConfig()
    if str(path).endswith(".safetensors"):
        from safetensors.numpy import load_file  # ships with transformers deps

        sd = load_file(path)
    else:
        import torch

        sd = torch.load(path, map_location="cpu", weights_only=True)
    return convert_hf_state_dict(sd, cfg)


# --------------------------------------------------------- trunk precompute
def precompute_token_states(
    params: dict,
    news_tokens: np.ndarray,
    cfg: DistilBertConfig | None = None,
    chunk: int = 256,
    dtype: str = "float32",
) -> np.ndarray:
    """(N_news, 2, L) artifact -> (N_news, L, dim) frozen-trunk token states.

    The once-per-corpus replacement for the reference re-running DistilBERT
    per news per batch (``model.py:41-61``). Chunked, jitted; returns numpy
    (host-resident — the Trainer moves it to HBM).
    """
    cfg = cfg or DistilBertConfig()
    model = DistilBert(cfg, dtype=jnp.dtype(dtype))
    n = news_tokens.shape[0]
    chunk = min(chunk, n)

    # params as a jit ARGUMENT (not a closure constant): closing over would
    # bake ~66M weights into the jaxpr as constants for the real trunk
    @jax.jit
    def run(p, ids, mask):
        return model.apply({"params": p}, ids, mask)

    # preallocate: a chunk-list + concatenate would transiently double the
    # footprint of an already-large array (MIND-large: ~15 GB at float32)
    out = np.empty((n, news_tokens.shape[2], cfg.dim), dtype=dtype)
    for start in range(0, n, chunk):
        block = news_tokens[start : start + chunk]
        ids = jnp.asarray(block[:, 0], jnp.int32)
        mask = jnp.asarray(block[:, 1], jnp.int32)
        pad = chunk - block.shape[0]
        if pad:  # keep shapes static so the last chunk doesn't retrace
            ids = jnp.pad(ids, ((0, pad), (0, 0)))
            mask = jnp.pad(mask, ((0, pad), (0, 0)))
        states = run(params, ids, mask)
        out[start : start + block.shape[0]] = np.asarray(states[: block.shape[0]])
    return out


def init_trunk_params(
    rng: jax.Array, cfg: DistilBertConfig | None = None, title_len: int = 50
) -> dict:
    """Random-init trunk parameters (offline smoke / from-scratch runs)."""
    cfg = cfg or DistilBertConfig()
    model = DistilBert(cfg)
    dummy_ids = jnp.zeros((1, title_len), jnp.int32)
    dummy_mask = jnp.ones((1, title_len), jnp.int32)
    return model.init(rng, dummy_ids, dummy_mask)["params"]


def trunk_config_from(model_cfg) -> DistilBertConfig:
    """DistilBertConfig from a ``ModelConfig`` (finetune-mode trunk knobs)."""
    return DistilBertConfig(
        vocab_size=model_cfg.trunk_vocab,
        dim=model_cfg.bert_hidden,
        n_layers=model_cfg.trunk_layers,
        n_heads=model_cfg.trunk_heads,
        hidden_dim=model_cfg.trunk_ffn,
        dropout=model_cfg.trunk_dropout,
        attention_dropout=model_cfg.trunk_dropout,
    )


# what a routed trunk may return beside its states, and the name each
# counter takes among the step's metrics (``train/step.py`` reads the sown
# collection under these names; the trainer publishes them at a round's end)
TRUNK_COUNTERS = {
    "expert_tokens": "moe.expert_tokens",
    "absent_share": "moe.absent_share",
    "full_size_chunks": "moe.full_size_chunks",
    "residual_mix_err": "trunk.residual_mix_err",
    "attention_scores_computed_share": "trunk.attention_scores_computed_share",
}


def trunk_families() -> dict[str, tuple]:
    """``model.text_trunk`` -> (ModelConfig -> trunk config, the config's
    class, the trunk's module). A routed trunk's module maps ``(ids, mask)``
    to ``(states, counters)``, the counters by their ``TRUNK_COUNTERS``
    keys; DistilBERT's ``(ids, mask, train)`` to the states alone."""
    from fedrec_tpu.models.latent_trunk import (
        LatentMoETrunk, LatentTrunkConfig, latent_trunk_config_from,
    )
    from fedrec_tpu.models.sparse_trunk import (
        SparseExpertTrunk, SparseTrunkConfig, sparse_trunk_config_from,
    )
    from fedrec_tpu.models.window_trunk import (
        WindowMoETrunk, WindowTrunkConfig, window_trunk_config_from,
    )

    return {
        "distilbert": (trunk_config_from, DistilBertConfig, DistilBert),
        "sparse_expert": (sparse_trunk_config_from, SparseTrunkConfig, SparseExpertTrunk),
        "latent_moe": (latent_trunk_config_from, LatentTrunkConfig, LatentMoETrunk),
        "window_moe": (window_trunk_config_from, WindowTrunkConfig, WindowMoETrunk),
    }


def make_text_encoder(model_cfg) -> "TextEncoder":
    """Full trainable text tower for ``text_encoder_mode='finetune'``; the
    trunk's family is ``model.text_trunk``."""
    if getattr(model_cfg, "text_head_arch", "additive") != "additive":
        raise NotImplementedError(
            "text_encoder_mode='finetune' supports only the additive head; "
            "use text_head_arch='cnn' with mode 'head' or 'table'"
        )
    families = trunk_families()
    if model_cfg.text_trunk not in families:
        raise ValueError(
            f"unknown model.text_trunk {model_cfg.text_trunk!r} "
            f"({'|'.join(families)})"
        )
    return TextEncoder(
        trunk_cfg=families[model_cfg.text_trunk][0](model_cfg),
        news_dim=model_cfg.news_dim,
        stable_softmax=model_cfg.stable_softmax,
        dtype=jnp.dtype(model_cfg.dtype),
        remat=model_cfg.trunk_remat,
    )


class TextEncoder(nn.Module):
    """Full text tower: trunk + additive-attention head. The trunk is the
    module ``trunk_families`` names for ``trunk_cfg``'s class: DistilBERT,
    or a routed decoder (``models.sparse_trunk``, ``models.latent_trunk``,
    ``models.window_trunk``)
    that returns ``(states, counters)``; the counters are sown into the
    ``routing`` collection (``apply(..., mutable=["routing"])`` reads them).

    The in-loop fine-tuning path (``text_encoder_mode='finetune'``,
    BASELINE config 5). ``remat=True`` rematerializes each transformer block
    on backward, trading FLOPs for HBM. Mirrors reference ``encoder.py:12-30``
    (trunk -> AdditiveAttention(768->384) -> Linear(768->400)) but as one
    jitted program over batched token ids.
    """

    trunk_cfg: Any = DistilBertConfig()
    news_dim: int = 400
    stable_softmax: bool = True
    dtype: jnp.dtype = jnp.float32
    remat: bool = True

    @nn.compact
    def __call__(
        self, tokens: jnp.ndarray, train: bool = False
    ) -> jnp.ndarray:
        """(..., 2, L) stacked [ids; mask] -> (..., news_dim)."""
        from fedrec_tpu.models.encoders import TextHead

        batch_shape = tokens.shape[:-2]
        flat = tokens.reshape(-1, 2, tokens.shape[-1])
        ids, mask = flat[:, 0].astype(jnp.int32), flat[:, 1].astype(jnp.int32)
        head = TextHead(
            news_dim=self.news_dim,
            bert_hidden=self.trunk_cfg.dim,
            stable_softmax=self.stable_softmax,
            dtype=self.dtype,
            name="head",
        )  # reference passes no token mask to the pooler (encoder.py:28)
        trunk_cls = next(
            module for _, cfg_cls, module in trunk_families().values()
            if isinstance(self.trunk_cfg, cfg_cls)
        )
        trunk = trunk_cls(
            self.trunk_cfg, dtype=self.dtype, remat=self.remat, name="trunk"
        )
        if trunk_cls is DistilBert:
            vecs = head(trunk(ids, mask, train))
        else:
            states, counters = trunk(ids, mask)
            for name, value in counters.items():
                self.sow("routing", name, value)
            with jax.named_scope("text_head"):
                vecs = head(states)
        return vecs.reshape(*batch_shape, self.news_dim)
