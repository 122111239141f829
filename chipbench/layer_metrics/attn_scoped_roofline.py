"""Roofline share of the three flash-attention kernels in a train step,
from their own rows of the trace (`mosaic:flash_attention_fwd`, `_dq`,
`_dkv`) and not from the time of all Mosaic kernels, so it stays right in
a step that has other kernels too (flash_attention_roofline divides by
`mosaic_s`): the least time one chip could take for them (the larger of
required operations over peak FLOP/s and least bytes over peak HBM
bytes/s, families/<family>.py attention_kernel_flops/_bytes for one
chip's share of the batch, worked out here from the configuration's file)
over their traced device time."""

from .. import harness
from .attn_fwd_kernel_ms_per_step import scoped_kernel_ms_per_step

SCOPE = "flash_attention"


def scoped_roofline(record, scope, counts):
    """100 x least time / traced time of the kernels whose trace rows
    carry `scope`; `counts(family, config, counters)` gives the required
    (operations, bytes) of one chip's share of a step."""
    ms = scoped_kernel_ms_per_step(record, scope)
    c, config = record["counters"], record.get("config") or {}
    if ms is None or not c.get("peaks"):
        return None
    family = harness.plugin("families", config["family"])
    flops, nbytes = counts(family, config, c)
    least_s = max(flops / c["peaks"]["bf16_flops"],
                  nbytes / c["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (1e-3 * ms)


def _counts(family, config, c):
    batch = c["global_batch"] // c["chips"]
    return (family.attention_kernel_flops(config, batch, c["seq"]),
            family.attention_kernel_bytes(config, batch, c["seq"]))


def read(record):
    return scoped_roofline(record, SCOPE, _counts)
