"""Device time of the gated-short-convolution kernels per train step (a
convolution layer's forward pass, its recomputation under remat, and the
backward pass that makes c again and every gradient), from the trace's
first plane: the rows `mosaic:...short_conv_fwd` and `..._bwd` that the
program's scopes round each pallas_call give (ray_tpu/ops/short_conv.py,
util/profiling.py DEVICE_SCOPES). A program without such kernels has no
such row and the metric is left out."""

from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "short_conv"


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
