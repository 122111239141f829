"""Roofline share of the gated-delta-rule kernels in a train step: the
least time one chip could take for the delta rule the step requires (the
larger of operations over peak FLOP/s and least bytes over peak HBM
bytes/s; families/<family>.py gated_delta_flops/_bytes for one chip's
share of the batch) over their traced device time,
gated_delta_ms_per_step. The counts are the least ANY chunking needs: the
recurrence's own three K x V products a token and head forward and twice
that backward; q, k, v, g, beta, dO read and o, dq, dk, dv, dg, dbeta
written once. No inverse, no [chunk, chunk] tile, no state a chunk and
nothing the backward makes again is counted, so the share cannot read
over 100% and does not go stale when a later PR changes the chunk or how
the inverse is made. It reads low by construction: what fills the
kernels' time is the float32 products of the inverse and the small
per-head tiles, which no roofline of the recurrence counts. At
Olmo-Hybrid-7B's shapes the bytes bound applies (1.39 ms a layer of HBM
traffic against 0.83 ms of operations at 16,384 tokens)."""

from .attn_scoped_roofline import scoped_roofline
from .gated_delta_ms_per_step import SCOPE


def _counts(family, config, c):
    batch = c["global_batch"] // c["chips"]
    return (family.gated_delta_flops(config, batch, c["seq"]),
            family.gated_delta_bytes(config, batch, c["seq"]))


def read(record):
    return scoped_roofline(record, SCOPE, _counts)
