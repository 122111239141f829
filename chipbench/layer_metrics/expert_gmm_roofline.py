"""Roofline share of the grouped-matmul kernels in a train step: the
least time one chip could take for the expert matmuls the step requires
(the larger of operations over peak FLOP/s and least bytes over peak HBM
bytes/s; families/<family>.py expert_matmul_flops/_bytes for one chip's
share of the step's tokens: forward 3 grouped matmuls, backward 6) over
their traced device time, expert_gmm_ms_per_step. The time includes the
forward recomputed under remat and the counts do not, as for mfu, so a
step with remat cannot read above 75%. At OLMoE-1B-7B's shapes the
operations bound applies (50 ms of operations against 24 ms of bytes at
16,384 tokens and depth 2)."""

from .attn_scoped_roofline import scoped_roofline
from .expert_gmm_ms_per_step import SCOPE


def _counts(family, config, c):
    tokens = c["global_batch"] * c["seq"] // c["chips"]
    return (family.expert_matmul_flops(config, tokens),
            family.expert_matmul_bytes(config, tokens))


def read(record):
    return scoped_roofline(record, SCOPE, _counts)
