from repro.models.common import MLAConfig, ModelConfig, MoEConfig, SSMConfig
from repro.models.transformer import (
    commit_append_buffer,
    decode_step,
    decode_step_buffered,
    forward,
    init_append_buffer,
    init_cache,
    init_lm,
    logits_of,
    loss_fn,
    prefill,
    prefill_into_slot,
)
