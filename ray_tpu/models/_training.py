"""Shared train-step factory for the model families.

Every model family exposes the same (init_state, jitted train_step)
contract; the optimizer wiring, donation, and partition-rule placement
are identical, so they live here once. Model modules supply
(init_fn, loss_fn, axes) and keep their public make_*_train_step names.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import kernel_sharding


def place_params(params, axes, mesh, rules):
    """Put a param pytree onto `mesh` per a logical-axis tree and a
    partition rule table (scaling-book recipe: annotate shardings, let
    XLA insert the collectives)."""
    from jax.sharding import NamedSharding

    leaves, treedef = jax.tree.flatten(params)
    # Axis tuples are themselves pytrees, so flatten the axes tree only
    # down to the params tree's structure.
    axes_leaves = treedef.flatten_up_to(axes)
    placed = [
        jax.device_put(p, NamedSharding(mesh, rules.spec(ax)))
        for p, ax in zip(leaves, axes_leaves)
    ]
    return jax.tree.unflatten(treedef, placed)


def make_train_step_for(init_fn: Callable[[Any], Dict],
                        loss_fn: Callable[[Dict, Any], Any],
                        axes: Optional[Dict] = None,
                        optimizer=None,
                        donate: bool = True,
                        mesh=None, rules=None, has_aux: bool = False):
    """Build (init_state, train_step) for a model family.

    init_fn(key) -> params; loss_fn(params, batch) -> scalar loss, or with
    `has_aux` (loss, dict of counters): the step returns the counters in
    its metrics beside `loss` (models/moe.py's router counters).
    With mesh + rules (+ axes), params/opt-state carry NamedShardings and
    XLA inserts the dp gradient psum / tp collectives from the shardings —
    no explicit pmap/DDP wrapper (contrast: the reference's
    train/torch/config.py:66-153 dist.init_process_group path).
    """
    import optax

    optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.01)
    # XLA partitions everything from the shardings except the attention
    # kernels and the loss's scan over rows, which are told their split
    # (batch and heads, by the same rule table) while the step is traced.
    if mesh is not None and rules is not None:
        step_split = functools.partial(
            kernel_sharding, mesh,
            rules.spec(("batch", "heads", None, None)))
    else:
        step_split = contextlib.nullcontext

    def init_state(key):
        params = init_fn(key)
        if mesh is not None and rules is not None and axes is not None:
            params = place_params(params, axes, mesh, rules)
        opt_state = optimizer.init(params)
        return {"params": params, "opt_state": opt_state,
                "step": jnp.zeros((), dtype=jnp.int32)}

    def train_step(state, batch):
        with step_split():
            loss, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
                state["params"], batch)
        loss, counters = loss if has_aux else (loss, {})
        with jax.named_scope("optimizer_update"):
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], state["params"])
            new_params = optax.apply_updates(state["params"], updates)
        return ({"params": new_params, "opt_state": new_opt,
                 "step": state["step"] + 1},
                {**counters, "loss": loss})

    donate_argnums = (0,) if donate else ()
    return init_state, jax.jit(train_step, donate_argnums=donate_argnums)
