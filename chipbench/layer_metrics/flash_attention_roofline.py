"""Roofline share of the flash-attention kernels in a train step: the
least time one chip could take for them (the larger of required
operations over peak FLOP/s and least bytes over peak HBM bytes/s,
families/<family>.py attention_kernel_flops/_bytes for one chip's share
of the batch) over their traced device time. At B=16, S=1024, D=64 the
operations bound applies (4.71 ms against 4.42 ms of bytes)."""


def read(record):
    t, c = record.get("trace") or {}, record["counters"]
    if not c.get("peaks") or not t.get("mosaic_s"):
        return None
    least_s = max(c["attention_kernel_flops"] / c["peaks"]["bf16_flops"],
                  c["attention_kernel_bytes"] / c["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / (t["mosaic_s"] / t["steps"])
