"""Device time of the Kimi-Delta-Attention kernels per train step (the KDA
layers' chunked delta rule with a decay a key channel, forward, and its
backward, which makes each chunk's tiles, W, U and V' again from the state
entering the chunk and the kept T - I), from the trace's first plane: the
rows `mosaic:...kda_fwd`, `..._bwd` that the program's scopes round each
pallas_call give (ray_tpu/ops/kda.py, util/profiling.py DEVICE_SCOPES).
Which rows are the rule's is the family's to say
(families/<family>.py KDA_KERNEL_ROWS); a program or a family without such
kernels has no such row and the metric is left out."""

from .. import harness
from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step


def kernel_rows(record) -> tuple:
    """The family's KDA_KERNEL_ROWS, () where it names none."""
    name = (record.get("config") or {}).get("family")
    if not name:
        return ()
    try:
        family = harness.plugin("families", name)
    except ImportError:
        return ()
    return tuple(getattr(family, "KDA_KERNEL_ROWS", ()))


def read(record):
    parts = [scoped_kernel_ms_per_step(record, row)
             for row in kernel_rows(record)]
    parts = [ms for ms in parts if ms is not None]
    return sum(parts) if parts else None
