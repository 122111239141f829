"""Roofline share of the selective-scan kernels in a train step: the
least time one chip could take for the scans the step requires (the
larger of operations over peak FLOP/s and least bytes over peak HBM
bytes/s; families/<family>.py ssm_scan_flops/_bytes for one chip's share
of the batch: the chunked scan's four products forward, twice that
backward; x, dt, B, C, dy read and y, dx, d dt, dB, dC written once, one
state a chunk each way) over their traced device time,
ssm_scan_ms_per_step. It reads low by construction: what fills the
kernels' time is the exponentials, masks and products of the [chunk,
chunk] decay tiles on the vector unit, a head and chunk at a time, and
those are not operations a roofline counts; nor is the backward's making
the tiles again. At granite-4.0-h-micro's shapes the bytes bound applies
(about 1.2 ms a layer of HBM traffic against 0.8 ms of operations at
16,384 tokens). It cannot read over 100%: the counts are the least the
algorithm needs."""

from .attn_scoped_roofline import scoped_roofline
from .ssm_scan_ms_per_step import SCOPE


def _counts(family, config, c):
    batch = c["global_batch"] // c["chips"]
    return (family.ssm_scan_flops(config, batch, c["seq"]),
            family.ssm_scan_bytes(config, batch, c["seq"]))


def read(record):
    return scoped_roofline(record, SCOPE, _counts)
