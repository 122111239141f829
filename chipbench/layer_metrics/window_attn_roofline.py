"""Roofline share of the attention kernels' banded calls in a train step:
the least time one chip could take for the band the step requires (the
larger of operations over peak FLOP/s and least bytes over peak HBM
bytes/s; families/<family>.py window_attention_flops / _bytes for one
chip's share of the batch) over their traced device time,
window_attn_ms_per_step. The operations are the band's own pairs, a query
at t against min(t + 1, window) keys (ops.attention.AttentionPlan's
`required_pairs`: 75% of the triangle at two windows), forward 2 matmuls
and backward 4, and not the tiles the kernels work the band in (which
reach past its two edges and are masked there), nor the scores the
backward kernels make again: the share cannot read over 100%. At
Trinity-Large-Preview's 48 heads of 128 and 8,192 tokens the operations
bound applies."""

from .. import harness
from .window_attn_ms_per_step import read as window_attn_ms_per_step


def read(record):
    ms = window_attn_ms_per_step(record)
    c, config = record["counters"], record.get("config") or {}
    if ms is None or not c.get("peaks"):
        return None
    family = harness.plugin("families", config["family"])
    batch = c["global_batch"] // c["chips"]
    least_s = max(
        family.window_attention_flops(config, batch, c["seq"])
        / c["peaks"]["bf16_flops"],
        family.window_attention_bytes(config, batch, c["seq"])
        / c["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (1e-3 * ms)
