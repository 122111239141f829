"""Device time of the forward flash-attention kernel per train step, from
the trace's first plane. The program puts a jax.named_scope round each
pallas_call (ray_tpu/util/profiling.py DEVICE_SCOPES), and a scope
reaches the HLO instruction's name, which is what the trace calls the
kernel: `mosaic:flash_attention_fwd`, `..._dq`, `..._dkv`, alone or
under shard_map. A program without the scopes (the parent of PR 23) has
no such row and the metric is left out. The three per-kernel metrics sum
to attn_kernel_ms_per_step on one chip (there the first plane is the
only one)."""

SCOPE = "flash_attention_fwd"


def scoped_kernel_ms_per_step(record, scope):
    t = record.get("trace") or {}
    rows = [s for name, s in (t.get("mosaic_by_name") or {}).items()
            if scope in name]
    if not rows or not t.get("steps"):
        return None
    return 1e3 * sum(rows) / t["steps"]


def read(record):
    return scoped_kernel_ms_per_step(record, SCOPE)
