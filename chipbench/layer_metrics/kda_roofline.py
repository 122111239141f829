"""Roofline share of the Kimi-Delta-Attention kernels in a train step: the
least time one chip could take for the rule the step requires (the larger
of operations over peak FLOP/s and least bytes over peak HBM bytes/s;
families/<family>.py kda_flops / kda_bytes for one chip's share of the
batch) over their traced device time, kda_ms_per_step. The counts are of
the chunked form as the model's equations write it: a head's and chunk's
two decayed tiles over their causal pairs, W, U, V', O and the state handed
on, and twice that again backward; q, k, v, g, beta, o, their gradients and
the chunk states moved once each way. No inverse, no sub-block and nothing
the backward makes again is counted, so the share cannot read over 100% and
does not go stale when a later PR rewrites the kernels. It reads low by
construction: what fills the kernels' time is the float32 products of the
inverse, the exponentials of the sub-blocks' factors and the small per-head
tiles. At Ling-3.0-flash's shapes the bytes bound applies (3.3 ms a layer
of HBM traffic against 1.1 ms of operations at 16,384 tokens)."""

from .. import harness
from .kda_ms_per_step import read as kda_ms_per_step


def read(record):
    ms = kda_ms_per_step(record)
    c, config = record["counters"], record.get("config") or {}
    if ms is None or not c.get("peaks"):
        return None
    family = harness.plugin("families", config["family"])
    batch = c["global_batch"] // c["chips"]
    least_s = max(
        family.kda_flops(config, batch, c["seq"]) / c["peaks"]["bf16_flops"],
        family.kda_bytes(config, batch, c["seq"])
        / c["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (1e-3 * ms)
