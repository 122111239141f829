"""Forward operations of one batch (families/resnet.py
forward_flops_per_image x batch) over its median compute time and the
published bf16 peak."""


def read(record):
    c = record["counters"]
    if not c.get("peaks") or not c.get("compute_ms_p50"):
        return None
    return 100.0 * c["batch_flops"] / (c["compute_ms_p50"] * 1e-3) \
        / c["peaks"]["bf16_flops"]
