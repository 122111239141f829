"""Device time of the lightning indexer's two kernels per train step (the
index scores over every causal pair, and their gradient rule's pass from
dI, which makes the heads' products again), from the trace's first plane:
the rows `mosaic:...sparse_index_fwd`, `..._bwd` that the program's scopes
round each pallas_call give (ray_tpu/ops/sparse_index.py, util/profiling.py
DEVICE_SCOPES; families/<family>.py SPARSE_INDEX_SCOPES names the two, and
its counts are of exactly those passes). The selection and the target pass
are XLA's and are no row of these. A program without such kernels has no
such row and the metric is left out."""

from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "sparse_index"


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
