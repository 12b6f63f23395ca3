"""Online serving subsystem: micro-batched request serving over a
versioned embedding store with two-stage retrieval.

The training side of this repo ends at a jitted batch scorer
(:mod:`fedrec_tpu.serve`) and a one-shot CLI
(:mod:`fedrec_tpu.cli.recommend`).  This package turns that into a
long-lived online service:

* :mod:`fedrec_tpu.serving.store` — versioned news-embedding/user-param
  generations with atomic hot-swap, so serving tracks the federated
  trainer round-by-round without a restart;
* :mod:`fedrec_tpu.serving.batcher` — asyncio deadline-driven
  micro-batcher that coalesces single-user requests into a few fixed
  padded batch shapes (the jitted scorer never recompiles under load);
* :mod:`fedrec_tpu.serving.retrieval` — two-stage retrieval (JAX k-means
  coarse quantizer + exact rerank) for catalogs past the
  full-matmul-per-request scale, with an exact-path fallback
  parity-tested against :func:`fedrec_tpu.serve.build_recommend_fn`;
* :mod:`fedrec_tpu.serving.server` — the TCP/JSON-lines service wiring
  batcher -> store -> retrieval, with latency/occupancy/swap metrics.
"""

from fedrec_tpu.serving.batcher import Backpressure, MicroBatcher, ServedResult
from fedrec_tpu.serving.client import (
    ServingClient,
    ServingClientPool,
    ServingUnavailable,
)
from fedrec_tpu.serving.retrieval import (
    TwoStageIndex,
    build_index,
    build_two_stage_fn,
    kmeans,
    recall_at_k,
)
from fedrec_tpu.serving.server import (
    ServingService,
    serve_forever,
    start_server,
    stop_server,
)
from fedrec_tpu.serving.store import EmbeddingStore, EmptyStoreError, Generation

__all__ = [
    "Backpressure",
    "EmbeddingStore",
    "EmptyStoreError",
    "Generation",
    "MicroBatcher",
    "ServedResult",
    "ServingClient",
    "ServingClientPool",
    "ServingService",
    "ServingUnavailable",
    "TwoStageIndex",
    "build_index",
    "build_two_stage_fn",
    "kmeans",
    "recall_at_k",
    "serve_forever",
    "start_server",
    "stop_server",
]
