"""Roofline share of the lightning indexer's two kernels in a train step:
the least time one chip could take for the index scores the step requires
and their gradients (the larger of operations over peak FLOP/s and least
bytes over peak HBM bytes/s; families/<family>.py sparse_index_flops /
_bytes for one chip's share of the batch: a product a head and causal pair
forward and two backward; q_I, k_I, w and the [T, T] float32 scores and
their gradient moved once) over their traced device time,
sparse_index_ms_per_step. The scores the backward kernel makes again are
in the time and not in the counts, so the share cannot read over 100%. At
Keye-VL-2.0's shapes the operations bound applies (4.2 ms a layer of
products against 2.6 ms of HBM traffic at 16,384 tokens), and the products
are 64 deep, half of the matrix unit's 128, with relu and the weighted sum
on the vector unit between them: the share reads low by construction."""

from .attn_scoped_roofline import scoped_roofline
from .sparse_index_ms_per_step import SCOPE


def _counts(family, config, c):
    batch = c["global_batch"] // c["chips"]
    return (family.sparse_index_flops(config, batch, c["seq"]),
            family.sparse_index_bytes(config, batch, c["seq"]))


def read(record):
    return scoped_roofline(record, SCOPE, _counts)
