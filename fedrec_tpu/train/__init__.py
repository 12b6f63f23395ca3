from fedrec_tpu.train.state import ClientState, init_client_state, stack_states
from fedrec_tpu.train.step import (
    build_eval_step,
    build_fed_train_step,
    build_full_eval_step,
    build_full_eval_step_sharded,
    build_news_update_step,
    build_param_sync,
    compressed_sync_active,
    encode_all_news,
    encode_all_news_sharded,
)

__all__ = [
    "ClientState",
    "build_eval_step",
    "build_full_eval_step",
    "build_full_eval_step_sharded",
    "build_fed_train_step",
    "build_news_update_step",
    "build_param_sync",
    "compressed_sync_active",
    "encode_all_news",
    "encode_all_news_sharded",
    "init_client_state",
    "stack_states",
]
