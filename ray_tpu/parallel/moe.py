"""Mixture-of-Experts layers.

Net-new vs the reference (SURVEY.md §2.4: EP "Absent"). Two layers:

* `moe_layer` (GShard): top-2 routing with capacity-bounded dense
  dispatch — einsum-based combine/dispatch over one-hot [T, E, C] tensors
  and `lax.all_to_all` shuffles across the `ep` mesh axis when experts are
  sharded. Right for few experts; at 64 experts and top-8 its dispatch
  tensors outgrow a chip.
* `dropless_moe_layer`: top-k routing with no capacity and no dropped
  token — the T*k assignments are sorted by expert, each expert's rows are
  multiplied by its own matrices through ops.grouped_matmul, and the
  results are summed back per token. Shapes are static (T*k rows always);
  the uneven split is data. What models/moe.py runs.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.grouped_matmul import grouped_matmul, grouped_matmul_grads


def top2_gating(logits, capacity: int):
    """Top-2 gating with capacity dropping (Switch/GShard style).

    logits: [tokens, experts]. Returns (dispatch [T, E, C] bool-ish,
    combine [T, E, C] float, aux_loss scalar).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    def one_route(p, mask_prev, offset):
        idx = jnp.argmax(jnp.where(mask_prev, -jnp.inf, p), axis=-1)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        # 1-based position of each token within its expert's queue,
        # continuing after `offset` slots already taken by earlier routes
        # (GShard: second-choice positions start after all first choices).
        pos = (jnp.cumsum(onehot, axis=0) + offset[None, :]) * onehot
        keep = (pos > 0) & (pos <= capacity)
        pos0 = jnp.clip(pos - 1, 0, capacity - 1).astype(jnp.int32)
        return idx, onehot, keep, pos0

    zero_off = jnp.zeros((e,), dtype=jnp.float32)
    idx1, oh1, keep1, pos1 = one_route(
        probs, jnp.zeros_like(probs, dtype=bool), zero_off)
    mask1 = oh1.astype(bool)
    count1 = jnp.sum(oh1, axis=0)
    idx2, oh2, keep2, pos2 = one_route(probs, mask1, count1)

    g1 = jnp.sum(probs * oh1, axis=-1)
    g2 = jnp.sum(probs * oh2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    cap_oh = functools.partial(jax.nn.one_hot, num_classes=capacity,
                               dtype=jnp.float32)
    # [T, E, C] dispatch/combine tensors
    d1 = oh1[:, :, None] * cap_oh(jnp.sum(pos1 * oh1.astype(jnp.int32),
                                          axis=-1))[:, None, :]
    d2 = oh2[:, :, None] * cap_oh(jnp.sum(pos2 * oh2.astype(jnp.int32),
                                          axis=-1))[:, None, :]
    keep1f = jnp.sum(keep1 * oh1.astype(bool), axis=-1,
                     keepdims=True)[:, :, None]
    keep2f = jnp.sum(keep2 * oh2.astype(bool), axis=-1,
                     keepdims=True)[:, :, None]
    combine = (d1 * g1[:, None, None] * keep1f
               + d2 * g2[:, None, None] * keep2f)
    dispatch = combine > 0
    # load-balancing aux loss (GShard eq. 4)
    density = jnp.mean(oh1, axis=0)
    density_probs = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_probs) * (e ** 2) / e
    return dispatch, combine, aux


def moe_layer(x, gate_w, expert_w1, expert_w2,
              capacity_factor: float = 1.25,
              axis_name: Optional[str] = None):
    """Top-2 MoE FFN. x: [tokens, d]; gate_w: [d, E];
    expert_w1: [E, d, f]; expert_w2: [E, f, d].

    With `axis_name`, call INSIDE shard_map with expert tensors sharded on
    the expert axis: tokens are all_to_all'ed to their experts' shards and
    back (the `ragged_all_to_all`-style dispatch, SURVEY.md §2.4 EP row).
    Without, experts compute locally (einsum over E).
    """
    t, d = x.shape
    e = gate_w.shape[-1]
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)

    if axis_name is None:
        capacity = max(1, int(capacity_factor * t * 2 / e))
        dispatch, combine, aux = top2_gating(logits, capacity)
        # [E, C, d] expert inputs
        xe = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                        dispatch.astype(jnp.float32))
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe,
                                   expert_w1.astype(jnp.float32)))
        ye = jnp.einsum("ecf,efd->ecd", h, expert_w2.astype(jnp.float32))
        y = jnp.einsum("ecd,tec->td", ye, combine)
        return y.astype(x.dtype), aux

    # Expert-parallel path: this shard owns e_local = E / n experts and a
    # token shard; tokens travel to their experts' shards and back.
    n = lax.axis_size(axis_name)
    e_local = expert_w1.shape[0]
    capacity = max(1, int(capacity_factor * t * 2 / e))
    dispatch, combine, aux = top2_gating(logits, capacity)
    # Per-expert input buffers built from MY tokens: [E, C, d], grouped by
    # destination shard -> [n_dest, e_local, C, d].
    xe = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                    dispatch.astype(jnp.float32))
    xe = xe.reshape(n, e_local, capacity, d)
    # all_to_all: recv[src, i] = tokens from shard `src` for my expert i.
    recv = lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=0)
    # Fold sources into the capacity axis: [e_local, n*C, d].
    xin = recv.transpose(1, 0, 2, 3).reshape(e_local, n * capacity, d)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin,
                               expert_w1.astype(jnp.float32)))
    ye = jnp.einsum("ecf,efd->ecd", h, expert_w2.astype(jnp.float32))
    # Route outputs back to their source shards.
    back = ye.reshape(e_local, n, capacity, d).transpose(1, 0, 2, 3)
    got = lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0)
    # got[j, i] = my tokens' outputs from expert (j * e_local + i):
    # reassemble the global expert axis in that order -> [E, C, d].
    ye_all = got.reshape(e, capacity, d)
    y = jnp.einsum("ecd,tec->td", ye_all, combine)
    aux = lax.pmean(aux, axis_name)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Dropless top-k layer
# ---------------------------------------------------------------------------
def _rows(x, idx):
    return x.at[idx].get(mode="promise_in_bounds")


def _spread(x, perm, k: int):
    """x [T, d] -> [T*k, d]: row i is the token of sorted assignment i,
    x[perm[i] // k]. A gather from T rows."""
    return _rows(x, perm // k)


def _sum_back(rows, inv, k: int):
    """Sorted rows [T*k, d] -> [T, d]: each token's k rows, found by
    perm's inverse `inv`, summed in float32. The transpose of `_spread`
    as a gather, never a scatter-add."""
    per_token = _rows(rows, inv).reshape(-1, k, rows.shape[-1])
    return jnp.sum(per_token.astype(jnp.float32), axis=1).astype(rows.dtype)


def _permuted(values, inv):
    """values[perm] for the permutation whose inverse is `inv`, as a sort
    of (inv, values) by `inv`: T*k scalars gathered one by one take 1.1 ms
    on the chip, the sort 0.1 (PERF.md §6, PR 30)."""
    return lax.sort((inv, values), num_keys=1)[1]


def _experts_fwd(x, weights, w_gate, w_up, w_down, perm, inv, counts):
    k = weights.shape[1]
    with jax.named_scope("moe_route"):
        xs = _spread(x, perm, k)                              # [T*k, d]
        w_sorted = _permuted(weights.reshape(-1), inv)        # [T*k]
    gate = grouped_matmul(xs, w_gate, counts)
    up = grouped_matmul(xs, w_up, counts)
    hidden = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
              * w_sorted[:, None]).astype(x.dtype)
    ys = grouped_matmul(hidden, w_down, counts)               # [T*k, d]
    with jax.named_scope("moe_combine"):
        out = _sum_back(ys, inv, k)
    # The names are for a rematerialised block (models/decoder.py
    # KEPT_UNDER_REMAT). Each names a copy that only the backward rule
    # reads, the forward above going on from the unnamed value: a kept
    # value the forward also reads gets a `reduce_precision` from
    # jax.checkpoint, a plain copy after a kernel or a gather.
    return out, (checkpoint_name(xs, "moe_xs"),
                 checkpoint_name(gate, "moe_gate"),
                 checkpoint_name(up, "moe_up"),
                 w_sorted, w_gate, w_up, w_down, perm, inv, counts)


def _experts_bwd(residuals, dout):
    xs, gate, up, w_sorted, w_gate, w_up, w_down, perm, inv, counts = residuals
    k, f32 = inv.shape[0] // dout.shape[0], jnp.float32
    dys = _spread(dout, perm, k)              # the combine is a plain sum
    gate, up, w = gate.astype(f32), up.astype(f32), w_sorted[:, None]
    sig = jax.nn.sigmoid(gate)
    act = gate * sig                                          # silu(gate)
    hidden = act * up                                         # unweighted
    dhidden, dw_down = grouped_matmul_grads(
        (hidden * w).astype(dout.dtype), w_down, counts, dys)
    dhidden = dhidden.astype(f32)
    dw_sorted = jnp.sum(dhidden * hidden, axis=-1)
    dhidden = dhidden * w
    dgate = dhidden * up * (sig * (1.0 + gate * (1.0 - sig)))
    dup = dhidden * act
    dxs_gate, dw_gate = grouped_matmul_grads(
        xs, w_gate, counts, dgate.astype(dout.dtype))
    dxs_up, dw_up = grouped_matmul_grads(
        xs, w_up, counts, dup.astype(dout.dtype))
    dx = _sum_back(dxs_gate + dxs_up, inv, k)
    dweights = _permuted(dw_sorted, perm).reshape(-1, k)
    return dx, dweights, dw_gate, dw_up, dw_down, None, None, None


@jax.custom_vjp
def _experts(x, weights, w_gate, w_up, w_down, perm, inv, counts):
    """The routed experts of x [T, d] for router weights [T, k] float32:
    dispatch, three grouped matmuls with silu(gate) * up between them, and
    the combine, as one function with one gradient rule, written so that
    the fewest rows move. `perm` sorts the T*k assignments (t*k + j) by
    expert, `inv` is its inverse, `counts` [E] the experts' rows.

        xs[i]  = x[perm[i] // k]                       a gather from T rows
        ys[i]  = (w[perm[i]] * silu(xs[i] G[e]) * (xs[i] U[e])) D[e]
        out[t] = sum_j ys[inv[t*k + j]]                in float32

    The weights go in ahead of the down matmul, which is linear in its
    rows, so the combine is an unweighted sum and its transpose is the
    dispatch itself (`dys` = `_spread(dout)`, from T rows: no [T, k, d]
    product, no gather from T*k rows on the way in); the dispatch's
    transpose is `_sum_back`; the weights' gradient is a row dot product
    on the f-wide side and T*k scalars put back by a sort. No [T*k, d]
    value but `xs` is a residual. Under remat `xs`, `gate` and `up` are
    kept by name and the rest is made again from the kept router
    probabilities; models/decoder.py KEPT_UNDER_REMAT has what the chip
    said of gathering `xs` again and of keeping the sorted `ys` instead."""
    return _experts_fwd(x, weights, w_gate, w_up, w_down, perm, inv,
                        counts)[0]


_experts.defvjp(_experts_fwd, _experts_bwd)


def dropless_moe_layer(x, router_w, w_gate, w_up, w_down, *,
                       experts_per_token: int,
                       norm_topk_prob: bool = False):
    """Top-k MoE with SwiGLU experts and no dropped token.

    x [T, d]; router_w [d, E] float32; w_gate, w_up [E, d, f];
    w_down [E, f, d]. Returns (out [T, d] in x's dtype, stats) where

        out[t] = sum_j w[t, j] * (silu(x[t] G[e_j]) * (x[t] U[e_j])) D[e_j]

    over the top-k experts e_j of softmax(x[t] router_w), weighted by
    their probabilities (renormalised over the k only with
    `norm_topk_prob`). The router runs in float32 at full precision;
    expert matmuls take the operands' dtype (bf16) and accumulate in
    float32. `stats`: `expert_tokens` [E] int32 (they sum to T*k),
    `router_prob_sum` [E] (sum over tokens of the probabilities) and
    `router_z_sq_sum` (sum over tokens of logsumexp(logits)**2): what the
    load-balancing and z losses are made of, summable over layers.
    """
    t = x.shape[0]
    e = router_w.shape[-1]
    k = experts_per_token
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # Kept under remat (models/decoder.py KEPT_UNDER_REMAT; [T, E]
        # float32, 4 MB at 16,384 tokens): rows kept in sorted order must
        # meet the order they were sorted in. A router made again can
        # break a near tie the other way (XLA may feed it an unrounded
        # copy of x, or sum in another order), and one flipped choice
        # shifts every row between the two experts; top_k of the same
        # probabilities chooses the same.
        probs = checkpoint_name(jnp.exp(logits - lse[:, None]), "moe_probs")
        weights, experts = lax.top_k(probs, k)                # [T, k]
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        # Assignment a = t*k + j goes to expert `experts[t, j]`; a stable
        # sort by expert groups them and keeps token order inside a group.
        iota = jnp.arange(t * k, dtype=jnp.int32)
        _, perm = lax.sort((experts.reshape(-1).astype(jnp.int32), iota),
                           num_keys=1, is_stable=True)
        _, inv = lax.sort((perm, iota), num_keys=1)
        counts = jnp.sum(
            experts.reshape(-1, 1) == jnp.arange(e, dtype=experts.dtype),
            axis=0, dtype=jnp.int32)
    out = _experts(x, weights, w_gate, w_up, w_down, perm, inv, counts)
    stats = {"expert_tokens": counts,
             "router_prob_sum": jnp.sum(probs, axis=0),
             "router_z_sq_sum": jnp.sum(jnp.square(lse))}
    return out, stats
