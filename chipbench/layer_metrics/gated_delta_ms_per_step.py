"""Device time of the gated-delta-rule kernels per train step (the
linear-attention layers' chunked delta rule forward and its backward,
which makes each chunk's tiles, inverse, W, U and V' again), from the
trace's first plane: the rows `mosaic:...gated_delta_fwd`, `..._bwd` that
the program's scopes round each pallas_call give
(ray_tpu/ops/gated_delta.py, util/profiling.py DEVICE_SCOPES). A program
without such kernels has no such row and the metric is left out."""

from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "gated_delta"


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
