"""Pallas TPU kernels for the framework's hot ops.

The reference's compute kernels are whatever cuDNN/MKL ships inside the torch
wheel (SURVEY.md section 2: zero native sources in-repo). Here the hot ops get
first-class TPU kernels:

  * ``flash_attention`` — blocked online-softmax attention (the user encoder's
    self-attention over click histories; keeps long histories O(L) in VMEM
    instead of materializing the (heads, L, L) score tensor the reference
    allocates, reference ``attention.py:38``).
  * ``additive_pool`` — fused learned-query additive pooling (tanh-MLP scores
    + softmax + weighted sum in one VMEM pass; reference ``attention.py:14-26``).
  * ``pair_sum`` — the expert layer's sum over a token's (token, choice) pairs
    on held experts: reads only those rows, writes one row a token (the
    combine's forward and the route's backward in every routed trunk).
  * ``residual_mix`` — the latent trunk's residual mixer: one pass over a
    token block's streams reads them into a sublayer (norm, maps, Sinkhorn),
    one writes its output back, one each way back.

All run in Pallas interpret mode on CPU (tests) and compiled on TPU. The
first two are routed from the Flax modules via ``ModelConfig.use_pallas``;
``pair_sum`` and ``residual_mix`` (their own modules, ``ops/pair_sum.py``,
``ops/residual_mix.py``) run wherever a routed or a latent trunk does, with
no option.
"""

from fedrec_tpu.ops.attention_kernels import additive_pool, flash_attention
from fedrec_tpu.ops.chunked_attention import chunked_attention
from fedrec_tpu.ops.fused_hot_path import (
    fused_gather_encode,
    fused_history_score,
    fused_user_vector,
)

__all__ = [
    "additive_pool",
    "chunked_attention",
    "flash_attention",
    "fused_gather_encode",
    "fused_history_score",
    "fused_user_vector",
]
