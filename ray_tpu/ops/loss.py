"""Next-token cross entropy over an output head, for every decoder family.

The float32 [rows, vocab] logits of the plain formulation are the largest
tensor of a training step (12.3 GB at 64 x 1024 rows of a 50k vocabulary),
so the loss never holds them: it scans chunks of `_LOSS_CHUNK` rows and
computes each chunk's gradient in the same pass, so that a chunk's logits
die with its iteration (autodiff through the scan would stack all of them
for the backward pass). Live memory is O(chunk * vocab) in training too.

Under a sharded train step (ops.attention.kernel_sharding, which
models._training.make_train_step_for sets while it traces) each chip scans
its own rows: a scan slices at a traced offset, which GSPMD cannot
partition along the sharded axis, so left to it every chip would gather
the whole batch and compute every chunk.

A table that is both the embedding and the head (`chip_views`) reaches the
loss as one view a chip of the batch axes, so that its two gradients, the
lookup's and the head's, are added on each chip before anything crosses
chips: the step reduces a tied table once, not once a use.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .attention import step_sharding

_LOSS_CHUNK = 4096


def _batch_split():
    """(mesh, PartitionSpec over the batch axes, their names) of the
    enclosing sharded step, or None where it has no batch axes."""
    from jax.sharding import PartitionSpec as P

    split = step_sharding()
    axes = split[1][0] if split is not None else None
    if axes is None:
        return None
    return split[0], P(axes), {axes} if isinstance(axes, str) else set(axes)


def chip_views(table):
    """`table` as [chips, *table.shape], one view for each chip of the
    enclosing step's batch axes and sharded over them, or None where
    those span one chip or there is no such step.

    Every use of a view (`lookup`, `cross_entropy`) runs on its chip and
    leaves its gradient there, a partial sum; autodiff adds a view's
    gradients where they are, and the one sum over chips is this
    function's: a table used twice crosses the chips once. The views cost
    nothing forward (each chip already holds the table)."""
    split = _batch_split()
    if split is None or math.prod(split[0].shape[a] for a in split[2]) == 1:
        return None
    mesh, rows, manual = split

    @jax.custom_vjp
    def views(table):
        from jax.sharding import PartitionSpec as P
        return jax.shard_map(lambda t: t[None], mesh=mesh, in_specs=P(),
                             out_specs=rows, axis_names=manual,
                             check_vma=False)(table)

    # The sum over the stacked partial gradients is GSPMD's all-reduce,
    # in the table's dtype as the bytes on the wire were before.
    views.defvjp(lambda table: (views(table), None),
                 lambda _, g: (jnp.sum(g, axis=0),))
    return views(table)


def lookup(views, tokens):
    """Rows of a table given as `chip_views`, tokens [batch, ...] ->
    [batch, ..., d]: each chip takes its own rows from its own view, and
    the gradient (a scatter-add) stays in that view."""
    mesh, rows, manual = _batch_split()
    return jax.shard_map(
        lambda view, ids: jnp.take(view[0], ids, axis=0), mesh=mesh,
        in_specs=(rows, rows), out_specs=rows, axis_names=manual,
        check_vma=False)(views, tokens)


def cross_entropy(x, head, targets, valid=None):
    """Mean over every row of -log softmax(x @ head)[target].

    x [batch, ..., d] hidden rows, head [d, vocab] or, from `chip_views`,
    [chips, d, vocab] (its gradient is then one partial sum a chip, which
    the views add up), targets [batch, ...] int. bf16 operands keep their
    precision (the logits are the matmul's output in the operands'
    dtype); the log-sum-exp and every accumulation are float32.

    `valid` [batch, ...] bool, where given: the rows that count (a row
    with no target: a prediction module's last position); the others add
    nothing to the sum or to any gradient, and the mean is over the rows
    that count."""
    with jax.named_scope("loss"):
        rows = targets.size if valid is None else jnp.sum(valid)
        return -_sum_ll(x, head, targets, valid) / rows


@jax.custom_vjp
def _sum_ll(x, head, targets, valid=None):
    """Sum over the rows that count (all of them with no `valid`) of the
    target's log-likelihood (float32 scalar)."""
    return _sum_ll_fwd(x, head, targets, valid)[0]


def _sum_ll_fwd(x, head, targets, valid=None):
    """The loss and, as residuals, its whole gradient: dx like x, and the
    head's as one float32 [d, vocab] partial sum per chip of the batch
    axes, stacked; _sum_ll_bwd adds them up."""
    split = _batch_split()
    if split is None:
        total, dx, dhead = _scan_chunks(x, head, targets, valid)
        return total, (dx, dhead[None], head)

    # Each chip scans its own rows; the other axes (tp on vocab, fsdp on
    # embed) stay with GSPMD. Nothing is reduced in here: the sums over
    # the stacked partial results below and in _sum_ll_bwd (or, of a
    # head given as views, in chip_views) are GSPMD's, one all-reduce
    # each, after the scan.
    from jax.sharding import PartitionSpec as P

    mesh, rows, manual = split

    def per_chip(xl, hl, tl, *vl):
        total, dx, dhead = _scan_chunks(xl, hl[0] if hl.ndim == 3 else hl, tl,
                                        *vl)
        return total[None], dx, dhead[None]

    counted = () if valid is None else (valid,)
    totals, dx, dheads = jax.shard_map(
        per_chip, mesh=mesh,
        in_specs=(rows, rows if head.ndim == 3 else P(), rows,
                  *(rows for _ in counted)),
        out_specs=(rows, rows, rows), axis_names=manual,
        check_vma=False)(x, head, targets, *counted)
    return jnp.sum(totals), (dx, dheads, head)


def _sum_ll_bwd(residuals, g):
    dx, dheads, head = residuals      # head for its dtype and rank alone
    dhead = (g * dheads).astype(head.dtype)
    if head.ndim == 2:
        dhead = jnp.sum(dhead, axis=0)
    return (g * dx).astype(dx.dtype), dhead, None, None


_sum_ll.defvjp(_sum_ll_fwd, _sum_ll_bwd)


def _chunk_rows(rows: int) -> int:
    """The rows of one chunk of `rows`: `_LOSS_CHUNK` or the largest of its
    halvings that divides them, all of them where none does."""
    chunk = _LOSS_CHUNK
    while chunk > 1 and rows % chunk:
        chunk //= 2
    return chunk if chunk > 1 else rows


def working_set_bytes(rows: int, d: int, vocab: int) -> int:
    """What the loss holds at its peak over `rows` rows of width d on one
    chip (under kernel_sharding every chip scans its own rows, so `rows`
    are a chip's and the figure is not divided again): a chunk's float32
    logits and half again for what is made from them, and the head's
    float32 gradient. From above, by XLA's `memory_analysis()` of the
    train cells' steps compiled for a v5e: 3.29 GB for the 3.16 read at
    100,352 vocabulary rows of width 2,560, 1.74 for 1.64 at 50,016
    (PERF.md section 6, PR 51). What models.decoder.remat_plan sets aside
    for it; whoever changes `_scan_chunks`'s buffers changes this beside
    it."""
    return 6 * _chunk_rows(rows) * vocab + 4 * d * vocab


def _scan_chunks(x, head, targets, valid=None):
    """(sum of log-likelihood, its gradient by x, by head in float32) over
    the rows given, `_LOSS_CHUNK` at a time: no chunk's logits outlive
    its iteration. With `valid`, over the rows it marks alone."""
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    tf = targets.reshape(-1)
    chunk = _chunk_rows(xf.shape[0])

    def one_chunk(carry, inputs):
        total, dhead = carry
        xs, ts, *counts = inputs
        lg = (xs @ head).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        hit = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1) == ts[:, None]
        tgt = jnp.sum(jnp.where(hit, lg, 0.0), axis=-1)
        # d(sum of log-likelihood) / d(logits): one-hot less softmax.
        dlg = hit - jnp.exp(lg - lse[:, None])
        if counts:
            dlg = jnp.where(counts[0][:, None], dlg, 0.0)
        dlg = dlg.astype(x.dtype)
        dxs = (dlg @ head.T).astype(x.dtype)
        dhead = dhead + jnp.matmul(xs.T, dlg,
                                   preferred_element_type=jnp.float32)
        ll = tgt - lse
        if counts:
            ll = jnp.where(counts[0], ll, 0.0)
        return (total + jnp.sum(ll), dhead), dxs

    zero = (jnp.zeros((), jnp.float32), jnp.zeros(head.shape, jnp.float32))
    counted = () if valid is None else (valid.reshape(-1, chunk),)
    (total, dhead), dx = jax.lax.scan(
        one_chunk, zero,
        (xf.reshape(-1, chunk, d), tf.reshape(-1, chunk), *counted))
    return total, dx.reshape(x.shape), dhead
