"""Device time of the grouped-matmul kernels per train step (the expert
layers' gate, up and down matmuls, their recomputation under remat, and
both gradients), from the trace's first plane: the rows
`mosaic:...grouped_matmul_fwd`, `..._dlhs`, `..._drhs` that the program's
scopes round each pallas_call give (ray_tpu/ops/grouped_matmul.py,
util/profiling.py DEVICE_SCOPES). A program without such kernels has no
such row and the metric is left out."""

from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "grouped_matmul"


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
