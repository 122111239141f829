"""Roofline share of the gated-short-convolution kernels in a train step:
the least time one chip could take for the convolutions the step requires
(the larger of operations over peak FLOP/s and least bytes over peak HBM
bytes/s; families/<family>.py short_conv_flops/_bytes for one chip's share
of the batch: B, C, x read and y written once forward; B, C, x, dy read
and dB, dC, dx written once backward) over their traced device time,
short_conv_ms_per_step. The bytes bound applies (1.8 ms a layer of HBM
traffic against 0.01 ms of operations at LFM2-8B-A1B's d 2048 and 32,768
tokens). The time includes the forward made again under remat and the
counts do not, as for mfu, so a step with remat cannot read above 11 / 15
= 73%."""

from .attn_scoped_roofline import scoped_roofline
from .short_conv_ms_per_step import SCOPE


def _counts(family, config, c):
    batch = c["global_batch"] // c["chips"]
    return (family.short_conv_flops(config, batch, c["seq"]),
            family.short_conv_bytes(config, batch, c["seq"]))


def read(record):
    return scoped_roofline(record, SCOPE, _counts)
