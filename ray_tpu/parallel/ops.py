"""In-jit collective ops: the ICI hot path.

Counterpart of ray_tpu.util.collective for code already inside
jit/shard_map: thin, named wrappers over jax.lax collectives so user code
reads like the reference's `col.allreduce(...)` while compiling to ICI
collectives (SURVEY.md §2.3 TPU-native equivalent column).
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

AxisName = Union[str, Sequence[str]]


def shard_map(f, *, mesh=None, in_specs=None, out_specs=None, **kw):
    """jax.shard_map under the name every ray_tpu call site imports."""
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pvary(tree, axis_name):
    """Mark values as device-varying over `axis_name` for shard_map's
    varying-manual-axes type system. Needed on scan/fori_loop carries
    initialized from constants. Idempotent: a value that already varies
    over the axis is returned as is."""
    import jax
    from jax import lax

    def fn(x):
        try:
            return lax.pcast(x, (axis_name,), to="varying")
        except ValueError as e:
            # Only the already-varying case is benign; other ValueErrors
            # must surface here, not as confusing type mismatches deep
            # inside shard_map.
            if "varying" in str(e):
                return x
            raise
    return jax.tree.map(fn, tree)


def allreduce(x, axis_name: AxisName = "dp"):
    """Sum across an axis (lax.psum == NCCL allreduce over ICI)."""
    from jax import lax
    return lax.psum(x, axis_name)


def allreduce_mean(x, axis_name: AxisName = "dp"):
    from jax import lax
    return lax.pmean(x, axis_name)


def allgather(x, axis_name: AxisName = "sp", axis: int = 0,
              tiled: bool = True):
    from jax import lax
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reducescatter(x, axis_name: AxisName = "fsdp", scatter_axis: int = 0):
    """psum_scatter == NCCL reduce-scatter (ZeRO gradient sharding)."""
    from jax import lax
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                            tiled=True)


def all_to_all(x, axis_name: AxisName = "ep", split_axis: int = 0,
               concat_axis: int = 0, tiled: bool = True):
    """MoE dispatch / Ulysses head-sequence swap."""
    from jax import lax
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis_name: AxisName, perm):
    """Neighbour exchange (ring attention KV rotation, pipeline hops)."""
    from jax import lax
    return lax.ppermute(x, axis_name, perm=perm)


def ring_shift(x, axis_name: AxisName, shift: int = 1,
               axis_size: Optional[int] = None):
    """Rotate values around a ring axis by `shift` (helper over ppermute)."""
    from jax import lax
    n = axis_size if axis_size is not None else lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)


def axis_index(axis_name: AxisName):
    from jax import lax
    return lax.axis_index(axis_name)


def axis_size(axis_name: AxisName) -> int:
    from jax import lax
    return lax.axis_size(axis_name)


def broadcast_from(x, axis_name: AxisName, src: int = 0):
    """Select src's value on all members of the axis."""
    import jax.numpy as jnp
    from jax import lax
    full = lax.all_gather(x, axis_name, axis=0, tiled=False)
    return jnp.take(full, src, axis=0)
