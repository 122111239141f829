"""Device time of the attention kernels' BANDED calls per train step (the
windowed layers' forward, dQ and dK/dV kernels over the band a window
leaves of the causal triangle), from the trace's first plane: the rows
`mosaic:...flash_attention_fwd_window`, `..._dq_window`, `..._dkv_window`
that the program's scopes round each pallas_call give where the call has a
window (ray_tpu/ops/attention.py, util/profiling.py DEVICE_SCOPES). The
full calls' rows carry no `_window` and are not counted; the three
`attn_*_kernel_ms_per_step` readers match the kernel's name as a substring
and read both. Which rows are the band's is the family's to say
(families/<family>.py WINDOW_KERNEL_ROWS); a program or a family without
such rows has none and the metric is left out."""

from .. import harness
from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step


def kernel_rows(record) -> tuple:
    """The family's WINDOW_KERNEL_ROWS, () where it names none."""
    name = (record.get("config") or {}).get("family")
    if not name:
        return ()
    try:
        family = harness.plugin("families", name)
    except ImportError:
        return ()
    return tuple(getattr(family, "WINDOW_KERNEL_ROWS", ()))


def read(record):
    parts = [scoped_kernel_ms_per_step(record, row)
             for row in kernel_rows(record)]
    parts = [ms for ms in parts if ms is not None]
    return sum(parts) if parts else None
