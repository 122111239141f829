"""Device time of the Mosaic kernels per train step, from the trace, mean
over chips. The train driver checks that the lowered step calls exactly
the three flash-attention kernels (_fwd_kernel, _dq_kernel, _dkv_kernel),
so every Mosaic event of the step is one of them; the trace names them by
their HLO instruction, not by kernel name."""


def read(record):
    t = record.get("trace") or {}
    if not t.get("mosaic_s"):
        return None
    return 1e3 * t["mosaic_s"] / t["steps"]
