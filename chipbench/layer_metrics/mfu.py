"""Model FLOP/s utilisation: required forward+backward operations per
token (families/<family>.py train_flops_per_token: attention counted
causal, recomputation not counted) x tokens per second over chips x
published bf16 peak. Tokens per second are the driver's own, worked out
from the median step time (drivers/train.py median_step_s), so the
profiler's start and stop inside a traced window do not move them."""


def read(record):
    c = record["counters"]
    if not c.get("peaks") or not c.get("tokens_per_s"):
        return None
    return 100.0 * c["train_flops_per_token"] * c["tokens_per_s"] \
        / (c["chips"] * c["peaks"]["bf16_flops"])
