"""Two-tower news recommender: scoring + loss (reference ``model.py:111-129``).

The reference's ``UserModel.forward`` embeds candidates and history via the
text encoder, runs the user encoder, scores with a batched dot product,
applies sigmoid, and feeds the *sigmoid outputs* to ``nn.CrossEntropyLoss``
(reference ``model.py:121-126`` — CE over probabilities, not logits; an
unusual choice we keep as the default for parity, with
``sigmoid_before_ce=False`` exposing the standard logit CE).

Here the model is a pure Flax module over *news vectors*; where those vectors
come from (precomputed table gather, cached-trunk TextHead, or full DistilBERT
fine-tune) is the caller's choice — see ``fedrec_tpu.train``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn

from fedrec_tpu.config import ModelConfig
from fedrec_tpu.models.encoders import (
    CnnTextHead,
    GRUUserEncoder,
    TextHead,
    UserEncoder,
)


def score_candidates(cand_vecs: jnp.ndarray, user_vec: jnp.ndarray) -> jnp.ndarray:
    """Dot-product scoring: (..., C, D) x (..., D) -> (..., C).

    The reference's ``torch.bmm(candidate_vecs, user_vector.unsqueeze(-1))``
    (``model.py:121``) as one einsum; XLA maps it onto the MXU.
    """
    with jax.named_scope("score_loss"):
        return jnp.einsum("...cd,...d->...c", cand_vecs, user_vec)


def score_loss(
    scores: jnp.ndarray,
    labels: jnp.ndarray,
    sigmoid_before_ce: bool = True,
    reduce: bool = True,
) -> jnp.ndarray:
    """Cross-entropy over impressions (labels are always slot 0).

    ``sigmoid_before_ce=True`` reproduces reference ``model.py:123-126``:
    ``CrossEntropyLoss()(sigmoid(scores), labels)``. ``reduce=False``
    returns the per-impression vector (used by evaluation to trim batch
    padding before averaging).
    """
    # loss math always in f32 (cast BEFORE the sigmoid — a bf16 sigmoid
    # would re-quantize): under a bfloat16 model the softmax/log lose ~3
    # decimal digits, quantizing the loss metric (visibly: a constant
    # 0.65625 across rounds) and coarsening gradients near convergence
    with jax.named_scope("score_loss"):
        scores = scores.astype(jnp.float32)
        logits = nn.sigmoid(scores) if sigmoid_before_ce else scores
        per_row = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        return jnp.mean(per_row) if reduce else per_row


class NewsRecommender(nn.Module):
    """User encoder + text head under one parameter tree.

    Methods are exposed separately so the train step can call
    ``encode_news`` on unique news only and reuse vectors across candidate
    and history slots (the TPU answer to the reference re-encoding every
    news per sample, ``model.py:41-61``).
    """

    cfg: ModelConfig
    # sequence-parallel mesh axis for the user tower (set inside shard_map
    # regions only — see fedrec_tpu.parallel.ring); None = dense single-chip.
    # Param trees are identical either way, so clones interoperate freely.
    seq_axis: str | None = None
    seq_impl: str = "ring"

    def setup(self):
        dtype = jnp.dtype(self.cfg.dtype)
        arch = getattr(self.cfg, "text_head_arch", "additive")
        if arch == "cnn":
            # attribute name (hence param-tree path "text_head") is shared
            # across head families, like user_tower; leaves differ, so
            # snapshots are per-family
            self.text_head = CnnTextHead(
                news_dim=self.cfg.news_dim,
                bert_hidden=self.cfg.bert_hidden,
                kernel=getattr(self.cfg, "cnn_kernel", 3),
                stable_softmax=self.cfg.stable_softmax,
                dtype=dtype,
                use_pallas=self.cfg.use_pallas,
            )
        elif arch == "additive":
            self.text_head = TextHead(
                news_dim=self.cfg.news_dim,
                bert_hidden=self.cfg.bert_hidden,
                stable_softmax=self.cfg.stable_softmax,
                dtype=dtype,
                use_pallas=self.cfg.use_pallas,
            )
        else:
            raise ValueError(
                f"unknown model.text_head_arch {arch!r}; have 'additive', 'cnn'"
            )
        tower = getattr(self.cfg, "user_tower", "mha")
        fuse = getattr(self.cfg, "fuse_hot_path", False)
        if fuse:
            if tower != "mha":
                raise ValueError(
                    "model.fuse_hot_path fuses the MHA user tower; "
                    f"user_tower={tower!r} has no fused kernel — unset one"
                )
            if not self.cfg.stable_softmax:
                raise ValueError(
                    "model.fuse_hot_path requires stable_softmax=True (the "
                    "fused kernels compute the max-subtracted form; the "
                    "raw-exp parity mode stays on the dense path)"
                )
            if self.seq_axis is not None:
                raise ValueError(
                    "model.fuse_hot_path cannot run under fed.seq_shards>1 "
                    "(the fused kernel holds the whole history per row); "
                    "use the ring/Ulysses path for sharded histories"
                )
        if tower == "gru":
            if self.seq_axis is not None:
                raise ValueError(
                    "model.user_tower='gru' cannot run under fed.seq_shards>1 "
                    "(sequence parallelism is attention-specific); use the "
                    "'mha' tower for seq-sharded histories"
                )
            # attribute name (hence param-tree path "user_encoder") is shared
            # across families; the leaves differ, so snapshots are per-family
            self.user_encoder = GRUUserEncoder(
                news_dim=self.cfg.news_dim,
                query_dim=self.cfg.query_dim,
                dropout_rate=self.cfg.dropout_rate,
                stable_softmax=self.cfg.stable_softmax,
                dtype=dtype,
                use_pallas=self.cfg.use_pallas,
            )
        elif tower == "mha":
            self.user_encoder = UserEncoder(
                news_dim=self.cfg.news_dim,
                num_heads=self.cfg.num_heads,
                head_dim=self.cfg.head_dim,
                query_dim=self.cfg.query_dim,
                dropout_rate=self.cfg.dropout_rate,
                stable_softmax=self.cfg.stable_softmax,
                dtype=dtype,
                use_pallas=self.cfg.use_pallas,
                fuse=fuse,
                seq_axis=self.seq_axis,
                seq_impl=self.seq_impl,
                attn_impl=self.cfg.attn_impl,
                chunk_threshold=self.cfg.attn_chunk_threshold,
            )
        else:
            raise ValueError(
                f"unknown model.user_tower {tower!r}; have 'mha', 'gru'"
            )

    def encode_news(
        self, token_states: jnp.ndarray, mask: jnp.ndarray | None = None
    ) -> jnp.ndarray:
        return self.text_head(token_states, mask)

    def encode_user(
        self,
        his_vecs: jnp.ndarray,
        mask: jnp.ndarray | None = None,
        train: bool = False,
    ) -> jnp.ndarray:
        return self.user_encoder(his_vecs, mask, train)

    def __call__(
        self,
        cand_vecs: jnp.ndarray,
        his_vecs: jnp.ndarray,
        his_mask: jnp.ndarray | None = None,
        train: bool = False,
    ) -> jnp.ndarray:
        """(..., C, D) candidates + (..., H, D) history -> (..., C) scores."""
        if getattr(self.cfg, "fuse_hot_path", False):
            # one fused kernel runs attention + pool + scoring; the dot
            # with the candidates never leaves VMEM (docs/DESIGN.md §5h)
            _, scores = self.user_encoder(
                his_vecs, his_mask, train, cand_vecs=cand_vecs
            )
            return scores
        user_vec = self.user_encoder(his_vecs, his_mask, train)
        return score_candidates(cand_vecs, user_vec)

    def init_both_towers(
        self,
        token_states: jnp.ndarray,
        cand_vecs: jnp.ndarray,
        his_vecs: jnp.ndarray,
    ) -> jnp.ndarray:
        """Init helper: touches both towers so one ``init`` creates the full
        parameter tree (Flax only materializes params for traced modules)."""
        self.text_head(token_states)
        return self(cand_vecs, his_vecs)
