from .llama import (
    LlamaConfig,
    forward,
    init_params,
    init_train_state,
    loss_fn,
    make_train_step,
    param_partition_specs,
    state_partition_specs,
)
from .ring_attention import ring_attention
