"""Device time of the selective-scan kernels per train step (the Mamba-2
layers' chunked scan forward and its backward, which makes the decay
tiles again), from the trace's first plane: the rows
`mosaic:...ssm_scan_fwd`, `..._bwd` that the program's scopes round each
pallas_call give (ray_tpu/ops/ssm_scan.py, util/profiling.py
DEVICE_SCOPES). A program without such kernels has no such row and the
metric is left out."""

from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "ssm_scan"


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
