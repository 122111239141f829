"""Roofline share of the Mamba-1 selective-scan kernels in a train step:
the least time one chip could take for the scans the step requires (the
larger of operations over peak FLOP/s and least bytes over peak HBM
bytes/s; families/<family>.py selective_scan_flops/_bytes for one chip's
share of the batch: six multiply-adds a (token, channel, state) forward
and twice that backward; x, dt, B, C, dm read and m, dx, d dt, dB, dC
written once) over their traced device time, selective_scan_ms_per_step.

The BYTES bound applies, by a wide margin: at Phi-4-mini-flash-reasoning's
5,120 channels x 16 states and 16,384 tokens a layer's least traffic is
1.85 GB (2.3 ms at 819 GB/s) against 24 G operations (0.12 ms at 197
TFLOP/s), because no part of this scan is a matrix product. It reads low
by construction: what fills the kernels' time is one exponential and six
vector multiply-adds for every (token, channel, state), forward, and the
same again twice in the backward, on the exponential and vector units;
exponentials and vector work are not operations a roofline counts, nor is
the MXU's peak one this kernel can approach. It cannot read over 100%:
the counts are the least the algorithm needs, and leave out the state
kept a chunk and the float32 copies at the kernels' boundary."""

from .attn_scoped_roofline import scoped_roofline
from .selective_scan_ms_per_step import SCOPE


def _counts(family, config, c):
    batch = c["global_batch"] // c["chips"]
    return (family.selective_scan_flops(config, batch, c["seq"]),
            family.selective_scan_bytes(config, batch, c["seq"]))


def read(record):
    return scoped_roofline(record, SCOPE, _counts)
