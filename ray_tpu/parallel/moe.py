"""Mixture-of-Experts layers.

Net-new vs the reference (SURVEY.md §2.4: EP "Absent"). Three layers:

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
* `held_moe_layer`: the same for a chip that holds a SHARE of the experts
  (expert parallelism without its exchange): it routes over all of them
  (sigmoid scores, a selection bias no gradient sees, the k weights
  renormalised and scaled: DeepSeek-V3's router; or with `softmax` a
  softmax over all of them and no bias at all: Qwen3-MoE's), computes the
  part of the result its own experts give (two matrices an expert with relu^2
  between, or gated: silu(gate) * up from one fused [d, 2f] matrix and a
  second) in buffers of a balanced share's rows and an eighth
  (`held_rows_plan`) that it walks in as many passes as the held rows
  take, so it drops nothing at any routing, and adds a shared expert
  every token passes where the model has one, of the experts' form.
  `balance_bias` runs the bias's own rule to its fixed point;
  `place_experts` says which experts a chip holds where a router has no
  bias to balance with (loads in, a chip for each expert out). What
  models/nemotron_h.py (relu^2, a shared expert), models/lfm2_moe.py
  (gated, none) and models/xing4.py (gated, a gated shared one) run.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.grouped_matmul import (_TILES, grouped_matmul,
                                  grouped_matmul_grads, past_groups_zeroed)


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


def _assignment_counts(experts, e: int):
    """How many of the assignments `experts` [T, k] chose each of e
    experts, [e] int32."""
    return jnp.sum(
        experts.reshape(-1, 1) == jnp.arange(e, dtype=experts.dtype),
        axis=0, dtype=jnp.int32)


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


@jax.named_scope("moe_experts_bwd")
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
        counts = _assignment_counts(experts, e)
    out = _experts(x, weights, w_gate, w_up, w_down, perm, inv, counts)
    stats = {"expert_tokens": counts,
             "router_prob_sum": jnp.sum(probs, axis=0),
             "router_z_sq_sum": jnp.sum(jnp.square(lse))}
    return out, stats


# ---------------------------------------------------------------------------
# A share of the experts: sigmoid router with a selection bias, relu^2
# experts of two matrices or gated ones of a fused first matrix and a
# second, a shared expert or none
# ---------------------------------------------------------------------------
def router_scores(x, router_w, softmax: bool = False):
    """sigmoid(x router_w) [T, E] in float32 at full precision: what the
    experts are chosen by (plus the bias) and weighted with (without).
    With `softmax`, softmax(x router_w) over all E in its place."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    return jax.nn.softmax(logits, axis=-1) if softmax \
        else jax.nn.sigmoid(logits)


def within_groups(biased, n_group: int = 1, topk_group: int = 1):
    """The one place a token's experts are narrowed before its top k is
    taken (`held_moe_layer`'s choice and `balance_bias`'s counts both pass
    through it): DeepSeek-V3's group limit. The E experts are `n_group`
    groups of E / n_group neighbours (a host's, where a group is a host);
    a group's mark is the sum of its two largest `biased` scores, the
    `topk_group` groups with the largest marks are kept, and every expert
    of another group falls out of the choice (-inf). Returns (`biased`
    [N, E] so narrowed, which groups each row kept [N, n_group] bool); with
    one group `biased` as it is and None: no operation is added."""
    if n_group == 1:
        return biased, None
    n, e = biased.shape
    grouped = biased.reshape(n, n_group, e // n_group)
    member = lax.broadcasted_iota(jnp.int32, grouped.shape, 2)
    best = jnp.argmax(grouped, axis=-1, keepdims=True)
    marks = jnp.max(grouped, axis=-1) + jnp.max(
        jnp.where(member == best, -jnp.inf, grouped), axis=-1)
    _, kept = lax.top_k(marks, topk_group)
    keep = jnp.any(
        kept[:, :, None] == jnp.arange(n_group, dtype=kept.dtype), axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(n, e), keep


# balance_bias ranks the tokens' experts anew once in this many rounds.
_ROUNDS_A_RANKING = 8


def balance_bias(scores, k: int, rounds: int = 256, start=None,
                 n_group: int = 1, topk_group: int = 1):
    """The selection bias b [E] float32 under which the top-k of `scores`
    [N, E] + b (inside the groups `within_groups` keeps, where the router
    has a group limit: the groups are chosen anew at every ranking, under
    the bias as it then stands) fall evenly on the experts: the router's
    own update, b <- b
    + r sign(mean(c) - c(b)) with c(b) the top-k counts under b, run to
    its fixed point from `start` (zeros) with the step r falling
    geometrically from the scores' spread to 1e-4 over `rounds`. A round
    that counts by a top-k is a pass of sorting, so only every eighth
    does (48 rounds of 16,384 x 128 scores 1.8 ms on the v5e, 2.1 ms for 8
    that all do): it leaves each token's bar for each expert (the
    k-th best of the others' biased scores), and the rounds between count
    who clears their bar, one comparison a score. Plain jax.numpy, no
    gradient. The initialiser's (models/nemotron_h.py) and, from the last
    step's bias and in fewer rounds, a training step's (`held_moe_layer`)."""
    assert rounds % _ROUNDS_A_RANKING == 0, rounds
    scores = lax.stop_gradient(scores.astype(jnp.float32))
    even = scores.shape[0] * k / scores.shape[1]
    spread = jnp.maximum(jnp.max(scores) - jnp.min(scores), 1e-4)
    steps = spread * (1e-4 / spread) ** jnp.linspace(0.0, 1.0, rounds)

    def refreshed(bias, steps):
        biased = within_groups(scores + bias, n_group, topk_group)[0]
        best = lax.top_k(biased, k + 1)[0]
        kth, next_best = best[:, k - 1:k], best[:, k:]
        # A chosen expert stays chosen while it beats the best one left
        # out; another gets in by beating the last one chosen.
        margin = biased - jnp.where(biased >= kth, next_best, kth)

        def one_round(moved, step):
            counts = jnp.sum(margin + moved > 0, axis=0, dtype=jnp.float32)
            return moved + step * jnp.sign(even - counts), None

        return bias + lax.scan(one_round, jnp.zeros_like(bias), steps)[0], None

    if start is None:
        start = jnp.zeros(scores.shape[-1], jnp.float32)
    bias = lax.scan(refreshed, lax.stop_gradient(start),
                    steps.reshape(-1, _ROUNDS_A_RANKING))[0]
    # One number added to every expert's bias changes no choice, and a
    # round's steps do not sum to zero (ten in bias after 170 training
    # steps on the v5e, PR 45): the mean is kept at zero, where float32
    # tells the scores apart best.
    return bias - jnp.mean(bias)


# A held share's buffers hold this much above a balanced share's rows. Under
# the bias's rounds, which run before a step routes, a layer's held rows read
# 0.9932-1.0088 of the balanced count in every one of 170 steps x 4 layers x
# 6 seeds of Nemotron-3-Nano at 16,384 tokens (PERF.md section 6, PR 45): an
# eighth is ten times that, and what lies above it takes a second pass.
_HELD_ROWS_HEADROOM = 8           # one part in this many


# A pass's rows go back to their tokens by k gathers of T rows where T * k
# is at most this many times the pass's rows, by one scatter-add of the rows
# above it: the gathers' cost is the T * k rows whatever is held, the
# scatter-add's falls with the rows, and they cross near 2.5 (`_gathered_back`
# has the chip's readings on both sides).
_GATHERED_BACK_UP_TO = 2


class HeldRowsPlan(NamedTuple):
    rows: int        # a pass's rows: what each buffer of the held share holds
    balanced: int    # the held experts' rows under an even routing
    tile: int        # grouped_matmul's row tile: `rows` is so many, or T * k
    gathered: bool   # rows come back by k gathers of T, not a scatter-add


def held_rows_plan(tokens: int, k: int, held: int,
                   experts: int) -> HeldRowsPlan:
    """The rows `held_moe_layer` gives its buffers for `tokens` tokens of k
    assignments each where a chip holds `held` of `experts` experts: the
    balanced share and an eighth, rounded up to grouped_matmul's row tile,
    and never more than the tokens * k there are (all experts held, or a
    decode step's few tokens: one pass covers any routing). And which way
    a pass's rows are added back to their tokens (`_gathered_back` or
    `_scattered_back`), from tokens * k against those rows."""
    tile = _TILES[0]
    balanced = -(-tokens * k * held // experts)
    rows = balanced + -(-balanced // _HELD_ROWS_HEADROOM)
    rows = min(-(-rows // tile) * tile, tokens * k)
    return HeldRowsPlan(rows, balanced, tile,
                        tokens * k <= _GATHERED_BACK_UP_TO * rows)


def held_backward_bytes(tokens: int, k: int, experts: int, w_up,
                        w_down) -> int:
    """What `_held_experts_bwd` holds at its peak that is not a residual,
    from shapes (`w_up` [held, d, f or 2 f] and `w_down` [held, f, d], or
    their shapes and dtypes; `tokens` those of one chip): gradients the size
    of the held experts' weights, and a pass's buffers, six values of
    `held_rows_plan`'s rows as wide as the two tensors' last axes together
    (the gathered rows and their cotangents, the up projection, the
    activation and the two gradients). From above, by XLA's
    `memory_analysis()` of the two cells' steps compiled for a v5e (PERF.md
    section 6, PR 51). What models.decoder.remat_plan sets aside for a
    layer of held experts; whoever changes the rule's buffers changes this
    beside it (tests/test_compile_v5e_lfm2moe.py and _nemotron.py hold the
    steps' totals)."""
    rows = held_rows_plan(tokens, k, w_up.shape[0], experts).rows
    sized = [(math.prod(w.shape), w.shape[-1], jnp.dtype(w.dtype).itemsize)
             for w in (w_up, w_down)]
    return sum(size * item + 6 * rows * wide * item
               for size, wide, item in sized)


def _windows(rows: int, weights, perm, inv, counts):
    """window(p) -> pass p of the held order, its places [p * rows, (p + 1)
    * rows): (the token of each place, its router weight, the held experts'
    rows inside the window: their cumulative ends less the window's start,
    clipped to it, and for each of the k assignment slots j the place in
    the window of every token's j-th assignment, `inv[t * k + j] - p *
    rows`: k index vectors of T, outside [0, rows) for another pass's).
    The T*k tokens and weights in sorted order are made once, with zeros
    after them up to a whole number of passes, so that a window cut from
    them (`lax.dynamic_slice`) is never slid back to fit."""
    k = weights.shape[1]
    pad = (0, -perm.shape[0] % rows)
    token_of = jnp.pad(perm // k, pad)
    w_sorted = jnp.pad(_permuted(weights.reshape(-1), inv), pad)
    slots = tuple(inv.reshape(-1, k).T)

    def window(p):
        ends = jnp.cumsum(counts) - p * rows
        sizes = jnp.clip(ends, 0, rows) - jnp.clip(ends - counts, 0, rows)
        return (lax.dynamic_slice_in_dim(token_of, p * rows, rows),
                lax.dynamic_slice_in_dim(w_sorted, p * rows, rows), sizes,
                tuple(slot - p * rows for slot in slots))

    return window


def _scattered_back(acc, made, tokens, sizes):
    """`acc` [T, d] float32 with every held row of `made` [R, d] added to
    its token's: `made` is what a kernel wrote for a window, `tokens` [R]
    the token of each place, the first sum(sizes) of them held; a place
    past them is sent out of bounds and dropped (what lies there was never
    written). A scatter-add of R rows into the T, added in the order of
    their places. `_gathered_back` has what the chip said of the two."""
    held = jnp.arange(made.shape[0], dtype=jnp.int32) < jnp.sum(sizes)
    return acc.at[jnp.where(held, tokens, acc.shape[0])].add(
        made.astype(acc.dtype), mode="drop")


def _gathered_back(acc, made, places, sizes):
    """`_scattered_back` as k gathers of T rows: `places` are the window's
    k index vectors [T] (`_windows`), and slot j adds to every token the
    row of `made` [R, d] at its j-th assignment's place, nothing where that
    place is not one of the window's sum(sizes) held ones (an absent
    expert's assignment, another pass's, or a row no kernel wrote). Summed
    in float32 slot by slot, never through a [T, k, d] value; XLA writes
    the k gathered [T, d] out in `made`'s dtype and adds them to `acc` in
    one fusion.

    Which of the two a layer takes is `held_rows_plan`'s to say, from T * k
    rows gathered against R scattered. A layer forward and backward alone
    on the v5e, scatter-add / gathers, ms (my chip runs, PR 49; PERF.md
    section 6): LFM2-8B-A1B's cell (T 32,768, k 4, d 2,048, 16 of 32 held,
    T * k / R = 1.78) 78.47 / 74.58; the same with 8 held (3.56) 43.37 /
    46.30 and with 6 (4.74) 34.74 / 38.79; Nemotron-3-Nano's cell (T
    16,384, k 6, d 2,688, 16 of 128 held, 7.1) 39.51 / 40.02, where PR 46
    read 44.8 / 45.4 (and 51.9 for the rows sorted by token, summed along
    their runs by shifted adds and gathered once: XLA writes each shifted
    [R, d] slice out); with 32 held (3.56) 59.16 / 63.15, with 24 (4.68)
    49.12 / 54.68. At LFM2's shape the gathers cost 4.9 ms a call whatever
    is held (131,072 rows at 33 ns and the sum) and a scattered row 92 ns,
    which cross at 2.5."""
    held = jnp.sum(sizes)
    for place in places:
        here = (place >= 0) & (place < held)
        # A place that is not here is read all the same (XLA's gather
        # skips nothing), each from a row of its own: sent to one row they
        # cost the k gathers and their sum 5.71 ms where this costs 5.39.
        picked = _rows(made, place % made.shape[0])
        acc = acc + jnp.where(here[:, None], picked, 0).astype(acc.dtype)
    return acc


def _added_back(gathered: bool, acc, made, tokens, places, sizes):
    """A window's rows `made` added to their tokens' in `acc`, by the form
    the layer's `held_rows_plan` names."""
    if gathered:
        return _gathered_back(acc, made, places, sizes)
    return _scattered_back(acc, made, tokens, sizes)


def _held_passes(counts, rows: int):
    """How many passes of `rows` the held experts' `counts` take."""
    return (jnp.sum(counts) + rows - 1) // rows


def _first_and_rest(p, first, rest, made):
    """A weight gradient over the passes as (pass 0's, as its kernel wrote
    it; the later passes' summed in float32). Where one pass is all, which
    is every step of a balanced routing, nothing is added and nothing
    converted: summed from zeros in float32 the two expert tensors cost
    5.0 ms a layer at Nemotron-3-Nano's shapes (my chip run, PR 46)."""
    return lax.cond(p == 0, lambda: (made, rest),
                    lambda: (first, rest + made.astype(rest.dtype)))


def _over_passes(passes, first, rest):
    """`_first_and_rest`'s two parts as one gradient in `first`'s dtype."""
    return lax.cond(
        passes > 1,
        lambda: (first.astype(rest.dtype) + rest).astype(first.dtype),
        lambda: first)


def _silu_and_slope(gate):
    """(silu(gate), its derivative) of a float32 pre-activation."""
    sig = jax.nn.sigmoid(gate)
    return gate * sig, sig * (1.0 + gate * (1.0 - sig))


def _held_experts_fwd(plan, gated, x, weights, w_up, w_down, perm, inv,
                      counts):
    rows, f32 = plan.rows, jnp.float32
    with jax.named_scope("moe_route"):
        window = _windows(rows, weights, perm, inv, counts)

    def one_pass(p, out):
        tokens, w, sizes, places = window(p)
        with jax.named_scope("moe_route"):
            xs = _rows(x, tokens)                             # [R, d]
        up = grouped_matmul(xs, w_up, sizes)
        if gated:                                   # [R, 2f]: gate | up
            gate, up = jnp.split(up.astype(f32), 2, axis=-1)
            act = jax.nn.silu(gate) * up
        else:
            act = jnp.square(jax.nn.relu(up.astype(f32)))
        hidden = past_groups_zeroed(act * w[:, None], sizes).astype(x.dtype)
        ys = grouped_matmul(hidden, w_down, sizes)            # [R, d]
        with jax.named_scope("moe_combine"):
            return _added_back(plan.gathered, out, ys, tokens, places,
                               sizes)

    out = lax.fori_loop(0, _held_passes(counts, rows), one_pass,
                        jnp.zeros(x.shape, f32))
    # The residuals are the inputs: how many passes ran is data, so no
    # pass's `xs` or `up` can be handed on, and the backward rule makes
    # them again, a gather of R rows and one grouped matmul over the held
    # rows. A rematerialised block's second forward is then dead code.
    return out.astype(x.dtype), (x, weights, w_up, w_down, perm, inv, counts)


@jax.named_scope("moe_experts_bwd")
def _held_experts_bwd(plan, gated, residuals, dout):
    x, weights, w_up, w_down, perm, inv, counts = residuals
    rows, k, f32 = plan.rows, weights.shape[1], jnp.float32
    passes = _held_passes(counts, rows)
    window = _windows(rows, weights, perm, inv, counts)

    def one_pass(p, carry):
        dx, dw_sorted, dw_up, dw_down = carry
        tokens, w, sizes, places = window(p)
        w = w[:, None]
        xs = _rows(x, tokens)
        dys = _rows(dout, tokens)             # the combine is a plain sum
        # Every value made from a kernel's output is zeroed past the held
        # rows in the pass that makes it: what lies there was never
        # written.
        up = grouped_matmul(xs, w_up, sizes)
        if gated:
            gate, up = jnp.split(
                past_groups_zeroed(up, sizes).astype(f32), 2, axis=-1)
            silu, slope = _silu_and_slope(gate)
            act = silu * up
        else:
            relu = jax.nn.relu(past_groups_zeroed(up, sizes).astype(f32))
            act = jnp.square(relu)
        dhidden, ddown = grouped_matmul_grads(
            (act * w).astype(dout.dtype), w_down, sizes, dys)
        dhidden = past_groups_zeroed(dhidden, sizes).astype(f32)
        dw = jnp.sum(dhidden * act, axis=-1)
        if gated:
            dup = jnp.concatenate([dhidden * w * up * slope,
                                   dhidden * w * silu], axis=-1)
        else:
            dup = dhidden * w * (2.0 * relu)
        dxs, dup_w = grouped_matmul_grads(xs, w_up, sizes,
                                          dup.astype(dout.dtype))
        with jax.named_scope("moe_dx"):       # the dispatch's transpose
            dx = _added_back(plan.gathered, dx, dxs, tokens, places, sizes)
        return (dx,
                lax.dynamic_update_slice_in_dim(dw_sorted, dw, p * rows, 0),
                _first_and_rest(p, *dw_up, dup_w),
                _first_and_rest(p, *dw_down, ddown))

    dx, dw_sorted, dw_up, dw_down = lax.fori_loop(
        0, passes, one_pass,
        (jnp.zeros(x.shape, f32),
         jnp.zeros(-(-perm.shape[0] // rows) * rows, f32),   # whole passes
         (jnp.zeros_like(w_up), jnp.zeros(w_up.shape, f32)),
         (jnp.zeros_like(w_down), jnp.zeros(w_down.shape, f32))))
    dweights = _permuted(dw_sorted[:perm.shape[0]], perm).reshape(-1, k)
    return (dx.astype(x.dtype), dweights, _over_passes(passes, *dw_up),
            _over_passes(passes, *dw_down), None, None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(plan, gated, x, weights, w_up, w_down, perm, inv, counts):
    """`_experts` for the experts a chip holds: `perm` sorts the T*k
    assignments by held expert with those of absent experts last, `counts`
    [held] are the held experts' rows, and, an expert being two matrices
    with relu^2 between or (`gated`) a fused first matrix U = G | U' [d,
    2f] and a second,

        ys[i]  = (w[perm[i]] * relu(xs[i] U[e])^2) D[e]    for i < sum(counts)
        ys[i]  = (w[perm[i]] * silu(xs[i] G[e]) * (xs[i] U'[e])) D[e]  gated
        out[t] = sum over t's held assignments of ys[inv[t*k + j]]

    The buffers hold `plan.rows` rows (`held_rows_plan`: a balanced share
    and an eighth), and the held order is walked in passes of that many, as
    many as sum(counts) takes: one at a balanced routing, T*k / rows when
    every assignment is held, none when none is. Nothing is dropped at
    any routing, and no value is made over T*k rows but the index vectors.
    A pass gathers its rows from the tokens', runs two grouped matmuls
    forward and four backward (its `xs` and `up` made again: one more
    forward; a gated expert's gate and up are one grouped matmul of twice
    the width), and adds its rows to their tokens' in float32, by k
    gathers of T rows or one scatter-add as `plan.gathered` says; the
    weights' gradients are summed over the passes in float32. One rule,
    its residuals its inputs."""
    return _held_experts_fwd(plan, gated, x, weights, w_up, w_down, perm,
                             inv, counts)[0]


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def held_moe_layer(x, router_w, router_bias, w_up, w_down, shared_up=None,
                   shared_down=None, *, experts_per_token: int, first: int,
                   routed_scale: float = 1.0, bias_rounds: int = 0,
                   gated: bool = False, weight_eps: float = 1e-20,
                   softmax: bool = False, n_group: int = 1,
                   topk_group: int = 1):
    """One chip's part of a top-k expert layer, with the model's shared
    expert where it has one, no token dropped.

    x [T, d]; router_w [d, E] float32 over ALL E experts; router_bias [E]
    float32; w_up [held, d, f], w_down [held, f, d]: experts `first` to
    `first + held - 1`, or with `gated` w_up [held, d, 2f], each expert's
    gate and up matrices side by side, the gate first; shared_up [d, fs]
    (with `gated` [d, 2 fs], gate | up as an expert's), shared_down [fs, d]
    or None for a model with no shared expert. Returns
    (out [T, d] in x's dtype, stats) where, with s = sigmoid(x router_w),
    e_1..k the top k of s + router_bias, and w_j = routed_scale * s[e_j] /
    (sum_j s[e_j] + weight_eps) (the model's own small number: 1e-20
    Nemotron-H's, 1e-6 LFM2's),

        out[t] = sum over the HELD e_j of w_j relu(x[t] U[e_j])^2 D[e_j]
                 + relu(x[t] shared_up)^2 shared_down
        out[t] = sum over the HELD e_j of
                 w_j (silu(x[t] G[e_j]) * (x[t] U'[e_j])) D[e_j]     gated
                 + (silu(x[t] G_s) * (x[t] U_s)) shared_down

    The bias picks and never weighs: no gradient reaches it (DeepSeek-V3's
    balancing without a loss). With `bias_rounds` (a training step's) it
    first follows its rule for that many rounds on these tokens' own
    scores, `balance_bias` from `router_bias`, and `stats` carry what it
    came to as `router_bias` [E], the caller's to keep for the next step;
    with none (a cache's forward) it is used as given. What the absent
    experts would add is left out; the shared expert's part is what every
    chip computes alike. `stats` besides: `expert_tokens` [E] int32 over
    all E (they sum to T*k), `expert_rows_held` the held experts' sum,
    `expert_passes` int32 (the passes that sum took of the buffers'
    `held_rows_plan` rows: 1 at a balanced routing) and `router_prob_sum`
    [E] (sum over tokens of s / sum_E s: the load-balancing loss's
    probabilities).

    With `softmax` (Qwen3-MoE's router) s = softmax(x router_w) over all E
    in float32, the experts are the top k of s itself, and there is no
    bias: `router_bias` is None, no round runs, `stats` carry none, and
    `router_prob_sum` is the sum of s (models.moe.balance_loss's).

    With `n_group` > 1 the top k are taken inside the `topk_group` groups
    of E / n_group experts a token keeps (`within_groups`, on s +
    router_bias; the bias's rounds choose under the same limit), and
    `stats` carry `expert_groups_kept` [n_group] int32, the tokens that
    kept each group (they sum to T * topk_group). The choice is made over
    all E alike on every chip, so the held shares still add up to the
    whole layer."""
    t, k, held = x.shape[0], experts_per_token, w_up.shape[0]
    plan = held_rows_plan(t, k, held, router_w.shape[-1])
    with jax.named_scope("moe_route"):
        # Kept under remat like the softmax router's probabilities, and
        # for the same reason (`dropless_moe_layer`).
        scores = checkpoint_name(router_scores(x, router_w, softmax),
                                 "moe_probs")
        if router_bias is None:
            bias, biased = None, scores
        else:
            bias = lax.stop_gradient(router_bias)
            if bias_rounds:
                bias = checkpoint_name(
                    balance_bias(scores, k, bias_rounds, bias, n_group,
                                 topk_group), "moe_probs")
            biased = scores + bias
        biased, groups_kept = within_groups(biased, n_group, topk_group)
        _, experts = lax.top_k(biased, k)
        counts = _assignment_counts(experts, scores.shape[-1])
        # An absent expert's assignments sort after every held one's.
        local = experts.reshape(-1).astype(jnp.int32) - first
        local = jnp.where((local >= 0) & (local < held), local, held)
        iota = jnp.arange(t * k, dtype=jnp.int32)
        _, perm = lax.sort((local, iota), num_keys=1, is_stable=True)
        _, inv = lax.sort((perm, iota), num_keys=1)
        held_counts = lax.slice_in_dim(counts, first, first + held)
        # What the top-k and the two sorts chose, four small integer arrays
        # ([T, k] and [T * k]), for a rematerialised block that has room
        # for them (models/decoder.py KEPT_WHERE_IT_FITS): kept, the
        # backward pass sorts nothing again.
        experts, perm, inv, held_counts = (
            checkpoint_name(choice, "moe_choice")
            for choice in (experts, perm, inv, held_counts))
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        weights = routed_scale * weights / (
            jnp.sum(weights, axis=-1, keepdims=True) + weight_eps)
    out = _held_experts(plan, gated, x, weights, w_up, w_down, perm, inv,
                        held_counts)
    if shared_up is not None:
        with jax.named_scope("moe_shared"):
            # relu^2's product is float32 (Nemotron's: a square doubles a
            # rounding); a gated one is in x's dtype, as a gated expert's
            # and ops.layers.swiglu's are, at half the bytes.
            hidden = checkpoint_name(jnp.dot(
                x, shared_up,
                preferred_element_type=None if gated else jnp.float32),
                "moe_shared_up")
            if gated:
                gate, up = jnp.split(hidden, 2, axis=-1)
                hidden = jax.nn.silu(gate) * up
            else:
                hidden = jnp.square(jax.nn.relu(hidden))
            out = out + jnp.dot(hidden.astype(x.dtype), shared_down)
    stats = {"expert_tokens": counts,
             "expert_rows_held": jnp.sum(held_counts),
             "expert_passes": _held_passes(held_counts, plan.rows),
             "router_prob_sum": jnp.sum(
                 scores if softmax
                 else scores / jnp.sum(scores, axis=-1, keepdims=True),
                 axis=0)}
    if bias is not None:
        stats["router_bias"] = bias
    if groups_kept is not None:
        stats["expert_groups_kept"] = jnp.sum(groups_kept, axis=0,
                                              dtype=jnp.int32)
    return out, stats


# ---------------------------------------------------------------------------
# Which experts a chip holds
# ---------------------------------------------------------------------------
def place_experts(loads, chips: int):
    """A chip for each expert, every chip holding as many, so that the
    chips' loads are as even as a greedy rule and swaps get them: what a
    deployment calls once, at set-up, for a router that has no bias to
    balance with (a softmax router: `held_moe_layer` with `softmax`).

    `loads` [E], or [occasions, E] where the chips are to be even on each
    occasion and not only on their sum (a ring of batches: a step sees one
    of them, not their mean): the assignments each expert was given,
    counted by routing the deployment's own tokens through its own model.
    Returns numpy int32 [E], expert e's chip in [0, chips). Longest first,
    each expert into the lightest chip (by the occasions' sum) that still
    has room for one; then, while one helps, the swap of two experts
    between the chip furthest from even on any occasion and another that
    brings the largest distance down most. Plain numpy on the host: E is
    hundreds. The caller relabels: the experts of chip c, in order, become
    c * E / chips onwards (`placement_order`), by permuting the router's
    columns, after which `held_moe_layer`'s `first` is c * E / chips."""
    import numpy as np

    loads = np.atleast_2d(np.asarray(loads, np.float64))        # [n, E]
    experts = loads.shape[1]
    if experts % chips:
        raise ValueError(f"{experts} experts do not divide over {chips} "
                         f"chips")
    room = experts // chips
    chip_of = np.full(experts, -1, np.int32)
    held, total = np.zeros(chips, np.int64), np.zeros(chips)
    summed = loads.sum(axis=0)
    for e in np.argsort(-summed, kind="stable"):
        c = min((c for c in range(chips) if held[c] < room),
                key=lambda c: total[c])
        chip_of[e], held[c], total[c] = c, held[c] + 1, total[c] + summed[e]
    even = loads.sum(axis=1, keepdims=True) / chips             # [n, 1]
    on_chip = np.stack([loads[:, chip_of == c].sum(axis=1)
                        for c in range(chips)], axis=1)         # [n, chips]
    for _ in range(16 * experts):
        off = np.abs(on_chip - even)
        worst = off.max()
        a = int(np.unravel_index(off.argmax(), off.shape)[1])
        best = None
        mine = np.flatnonzero(chip_of == a)
        for b in range(chips):
            if b == a:
                continue
            theirs = np.flatnonzero(chip_of == b)
            # moved[n, i, j]: what chip a loses on occasion n by giving
            # its expert i for b's expert j
            moved = loads[:, mine, None] - loads[:, None, theirs]
            after = np.maximum(
                np.abs(on_chip[:, a, None, None] - moved - even[:, :, None]),
                np.abs(on_chip[:, b, None, None] + moved - even[:, :, None])
            ).max(axis=0)
            i, j = np.unravel_index(after.argmin(), after.shape)
            # the swap must also leave the two chips' other occasions
            # under the worst there was
            if after[i, j] < worst and (best is None or after[i, j] < best[0]):
                best = (after[i, j], b, mine[i], theirs[j])
        if best is None:
            break
        _, b, i, j = best
        on_chip[:, a] += loads[:, j] - loads[:, i]
        on_chip[:, b] += loads[:, i] - loads[:, j]
        chip_of[i], chip_of[j] = b, a
    return chip_of


def placement_order(chip_of):
    """The relabelling a placement asks for: order[new] = old, chip 0's
    experts first in their old order, then chip 1's and so on (a stable
    sort of the experts by chip). `router[:, order]` is the router whose
    expert `new` is the old expert order[new]."""
    import numpy as np
    return np.argsort(np.asarray(chip_of), kind="stable").astype(np.int32)
