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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import step_sharding

_LOSS_CHUNK = 4096


def cross_entropy(x, head, targets):
    """Mean over every row of -log softmax(x @ head)[target].

    x [batch, ..., d] hidden rows, head [d, vocab], targets [batch, ...]
    int. bf16 operands keep their precision (the logits are the matmul's
    output in the operands' dtype); the log-sum-exp and every
    accumulation are float32."""
    with jax.named_scope("loss"):
        return -_sum_ll(x, head, targets) / targets.size


@jax.custom_vjp
def _sum_ll(x, head, targets):
    """Sum over rows of the target's log-likelihood (float32 scalar)."""
    return _sum_ll_fwd(x, head, targets)[0]


def _sum_ll_fwd(x, head, targets):
    """The loss and, as residuals, its whole gradient: dx like x, and the
    head's as one float32 [d, vocab] partial sum per chip of the batch
    axes, stacked; _sum_ll_bwd adds them up."""
    split = step_sharding()
    batch_axes = split[1][0] if split is not None else None
    if batch_axes is None:
        total, dx, dhead = _scan_chunks(x, head, targets)
        return total, (dx, dhead[None], head)

    # Each chip scans its own rows; the other axes (tp on vocab, fsdp on
    # embed) stay with GSPMD. Nothing is reduced in here: the sums over
    # the stacked partial results below and in _sum_ll_bwd are GSPMD's,
    # one all-reduce each, after the scan.
    from jax.sharding import PartitionSpec as P

    mesh = split[0]
    rows = P(batch_axes)
    manual = {batch_axes} if isinstance(batch_axes, str) else set(batch_axes)

    def per_chip(xl, hl, tl):
        total, dx, dhead = _scan_chunks(xl, hl, tl)
        return total[None], dx, dhead[None]

    totals, dx, dheads = jax.shard_map(
        per_chip, mesh=mesh, in_specs=(rows, P(), rows),
        out_specs=(rows, rows, rows), axis_names=manual,
        check_vma=False)(x, head, targets)
    return jnp.sum(totals), (dx, dheads, head)


def _sum_ll_bwd(residuals, g):
    dx, dheads, head = residuals      # head for its dtype alone
    dhead = jnp.sum((g * dheads).astype(head.dtype), axis=0)
    return (g * dx).astype(dx.dtype), dhead, None


_sum_ll.defvjp(_sum_ll_fwd, _sum_ll_bwd)


def _scan_chunks(x, head, targets):
    """(sum of log-likelihood, its gradient by x, by head in float32) over
    the rows given, `_LOSS_CHUNK` at a time: no chunk's logits outlive
    its iteration."""
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    tf = targets.reshape(-1)
    rows = xf.shape[0]
    chunk = _LOSS_CHUNK
    while chunk > 1 and rows % chunk:
        chunk //= 2
    if chunk <= 1:
        chunk = rows

    def one_chunk(carry, inputs):
        total, dhead = carry
        xs, ts = inputs
        lg = (xs @ head).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        hit = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1) == ts[:, None]
        tgt = jnp.sum(jnp.where(hit, lg, 0.0), axis=-1)
        # d(sum of log-likelihood) / d(logits): one-hot less softmax.
        dlg = (hit - jnp.exp(lg - lse[:, None])).astype(x.dtype)
        dxs = (dlg @ head.T).astype(x.dtype)
        dhead = dhead + jnp.matmul(xs.T, dlg,
                                   preferred_element_type=jnp.float32)
        return (total + jnp.sum(tgt - lse), dhead), dxs

    zero = (jnp.zeros((), jnp.float32), jnp.zeros(head.shape, jnp.float32))
    (total, dhead), dx = jax.lax.scan(
        one_chunk, zero,
        (xf.reshape(-1, chunk, d), tf.reshape(-1, chunk)))
    return total, dx.reshape(x.shape), dhead
