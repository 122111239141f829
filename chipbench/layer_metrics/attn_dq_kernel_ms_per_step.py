"""Device time of the flash-attention backward's dq kernel per train
step: the row of the `flash_attention_dq` scope
(attn_fwd_kernel_ms_per_step.py says how the name gets there)."""

from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "flash_attention_dq"


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
