"""Device time of the Mamba-1 selective-scan kernels per train step (the
forward, and the backward, which makes each chunk's states again and then
walks the chunk back), from the trace's first plane: the rows
`mosaic:...selective_scan_fwd`, `..._bwd` that the program's scopes round
each pallas_call give (ray_tpu/ops/selective_scan.py, util/profiling.py
DEVICE_SCOPES). A program without such kernels (every tree before PR 31)
has no such row and the metric is left out."""

from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "selective_scan"


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
