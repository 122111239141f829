"""Device time of the flash-attention backward's dkv kernel per train
step: the row of the `flash_attention_dkv` scope
(attn_fwd_kernel_ms_per_step.py says how the name gets there)."""

from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "flash_attention_dkv"


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
