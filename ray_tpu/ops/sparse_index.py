"""A lightning indexer for attention over a selected subset of positions
(DeepSeek-V3.2-Exp's sparse attention): Pallas TPU kernels + jax reference.

A query names the keys it attends to by an index score of its own, cheap
beside attention's: H_I small heads of D_I columns against ONE key head,

    I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])        s <= t, float32

and sees the `topk` largest of its row, S_t = {s <= t : I[t, s] >= tau_t},
tau_t the topk-th largest (-inf while t < topk; a tie at tau_t keeps both).
The selection has no gradient. The indexer learns from the attention it
selected for: with p[t, s] the heads' mean attention probability over S_t
(stop-gradient), a layer's loss is

    L_I = 1/T sum_t sum_{s in S_t} p[t, s] (log p[t, s]
                                            - log softmax_{S_t}(I[t, .])[s])

whose gradient by the scores is dI = (softmax_{S_t}(I) - p) / T, inside S_t.

What runs, in the order a layer runs it:

    index_scores    `sparse_index_fwd`: a [tile, tile] tile of I from H_I
                    products on the matrix unit, relu and the weighted sum
                    on the vector unit; the lower triangle only, -inf above
                    the diagonal. [H_I, T, T] is never held: one [T, T]
                    float32 is (1.07 GB at 16,384 positions)
    select          tau a row, EXACTLY: a bisection over the float32 order
                    (32 counts of the row's scores at or above a bar), by
                    query chunk; then the selection [T, T] int8, which is
                    what ops.attention's kernels take (`sparse_select`)
    index_target    p from attention's own q, k and lse, L_I and dI, in
                    query chunks in plain XLA, a chunk's rows of dI over
                    the rows of I it read (`sparse_target`)
    index_grads     `sparse_index_bwd`: dw, dq_I, dk_I from dI, the H_I
                    products made again a tile

The two plain passes work the causal triangle as the kernels do, in BANDS
(`key_bands`): the queries in equal bands, a loop a band, and a chunk of
band g counts or multiplies only the first (g + 1) T / G keys, a static
prefix that covers its last query: (G + 1) / 2G of the square, 5/8 at the
four bands of 16,384 positions. The keys right of
it are above the diagonal: -inf in I, which never decides a count
(`kth_largest`), outside every selection, zero in p and in dI. A short
sequence is one band, the whole row.

`indexer_loss` is the one gradient rule. Its FORWARD pass runs the target
and the backward kernel, at a cotangent of one, and keeps the three
gradients (`sparse_index_grads`, 34 MB at 16,384 positions of 16 x 64);
its backward pass scales them. L_I's gradient goes nowhere else (attention's
q, k and lse are read, the indexer's input is detached by its caller), so
nothing of the [T, T] size outlives the forward pass, a rematerialised
block that keeps the selection and these three runs neither kernel twice,
and the target runs once.

`sparse_index_plan` gives the sizes from the shape and counts what runs.
The jax forms serve other backends, shapes the kernels do not tile and the
tests; RAY_TPU_PALLAS_INTERPRET=1 runs the kernels in interpreter mode on
the CPU (ops/attention.py `_interpret`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import attention
from .attention import DEFAULT_MASK_VALUE, VMEM_BUDGET, _NN, _NT, _dot

_TILE = 512                 # the config's q_chunk_size / kv_chunk_size
_QUERY_CHUNK = 512          # rows a selection's bisection counts at once
_TARGET_CHUNK = 256         # queries whose [heads, chunk, T] scores are held
_BAND = 2048                # the fewest queries a band of the plain passes has
_BANDS = 4                  # the most bands: 5/8 of the square (eight are 1%
#                             more tokens/s at 16,384 and 5 s more to read the
#                             step from the compile cache: PERF.md section 6)
_LANES = 128


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SparseIndexPlan:
    """The tiling of the two kernels at one sequence and what they execute
    for it: `tiles` of tile x tile on and under the diagonal (of `grid`
    squared; the others write -inf or zeros and compute nothing), a
    product a head and tile forward and four backward (the scores again,
    dq_I, dk_I and, on the vector unit, dw), `*_flops` counting the matrix
    unit's, `*_bytes` what each pass moves over HBM once; and the (query,
    key) pairs the two plain passes execute in their bands (`key_bands`):
    `select_pairs` counted 32 times, `target_pairs` multiplied a head."""
    seq_len: int
    heads: int
    head_dim: int
    tile: int
    grid: int
    tiles: int
    fwd_products: int
    bwd_products: int
    fwd_flops: int
    bwd_flops: int
    fwd_bytes: int
    bwd_bytes: int
    vmem_bytes: int
    select_pairs: int
    target_pairs: int


def _tile_of(seq_len: int) -> Optional[int]:
    return next((t for t in (_TILE, 256, 128) if seq_len % t == 0), None)


def key_bands(seq_len: int, chunk: int) -> tuple:
    """The key extents of a plain pass's bands of queries, from the shape:
    G equal bands of whole chunks, band g's queries [g, g + 1) T / G
    against the keys [0, (g + 1) T / G). G is the most, up to `_BANDS`,
    that leaves a band `_BAND` queries; a count whose edge would cut a
    chunk falls to the next one under it, a short sequence to one band of
    every key."""
    bands = next((g for g in range(min(_BANDS, seq_len // _BAND), 1, -1)
                  if seq_len % (g * chunk) == 0), 1)
    return tuple((g + 1) * (seq_len // bands) for g in range(bands))


def _select_bands(seq_len: int) -> tuple:
    """(`select`'s query chunk, its bands' key extents)."""
    chunk = next(c for c in (_QUERY_CHUNK, 256, 128, seq_len)
                 if seq_len % c == 0)
    return chunk, key_bands(seq_len, chunk)


def _target_bands(seq_len: int) -> tuple:
    """(`index_target`'s query chunk, its bands' key extents)."""
    chunk = next(c for c in (_TARGET_CHUNK, 128, seq_len)
                 if seq_len % c == 0)
    return chunk, key_bands(seq_len, chunk)


def _pairs(seq_len: int, extents: tuple) -> int:
    return sum(extents) * (seq_len // len(extents))


def sparse_index_plan(seq_len: int, heads: int, head_dim: int,
                      itemsize: int = 2) -> SparseIndexPlan:
    """The tiling `index_scores` and `index_grads` run a [.., seq_len] call
    at: the kernels take their sizes from here."""
    tile = _tile_of(seq_len)
    if tile is None:
        raise ValueError(
            f"the kernels tile sequences in multiples of 128, not {seq_len}")
    n = seq_len // tile
    tiles = n * (n + 1) // 2
    product = 2 * tile * tile * head_dim
    square = seq_len * seq_len * 4
    operands = seq_len * (heads * head_dim + head_dim) * itemsize \
        + seq_len * heads * 4
    padded = max(head_dim, _LANES)
    vmem = (2 * 2 * heads * tile * padded * itemsize      # q and dq, twice
            + heads * tile * padded * 4                   # dq's accumulator
            + 2 * 2 * tile * tile * 4                     # I or dI, twice
            + 8 * tile * tile * 4)                        # a head's tiles
    return SparseIndexPlan(
        seq_len=seq_len, heads=heads, head_dim=head_dim, tile=tile, grid=n,
        tiles=tiles, fwd_products=tiles * heads,
        bwd_products=3 * tiles * heads,
        fwd_flops=tiles * heads * product,
        bwd_flops=3 * tiles * heads * product,
        fwd_bytes=operands + square,
        bwd_bytes=2 * operands + square + n * seq_len * head_dim * 4,
        vmem_bytes=vmem,
        select_pairs=_pairs(seq_len, _select_bands(seq_len)[1]),
        target_pairs=_pairs(seq_len, _target_bands(seq_len)[1]))


def _kernel_ok(seq_len: int) -> bool:
    return attention._on_tpu() and _tile_of(seq_len) is not None


# ---------------------------------------------------------------------------
# Reference: the same mathematics in plain jax.numpy
# ---------------------------------------------------------------------------
def index_scores_reference(q, k, w):
    """q [b, H, T, D], k [b, T, D], w [b, T, H] float32 -> I [b, T, T]
    float32, -inf above the diagonal. Holds [b, H, T, T]: small T only."""
    s = jnp.einsum("bhtd,bsd->bhts", q, k,
                   preferred_element_type=jnp.float32)
    scores = jnp.einsum("bth,bhts->bts", w.astype(jnp.float32),
                        jnp.maximum(s, 0.0))
    seq = q.shape[-2]
    return jnp.where(jnp.tril(jnp.ones((seq, seq), dtype=bool)), scores,
                     -jnp.inf)


# ---------------------------------------------------------------------------
# Kernels: grid (batch, query tiles, key tiles)
# ---------------------------------------------------------------------------
def _on_or_under(ki, qi, tile: int):
    """Whether each pair of a tile is causal: all of a tile under the
    diagonal, the lower triangle of one on it."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    return (ki < qi) | (rows >= cols)


def _index_fwd_kernel(q_ref, k_ref, w_ref, o_ref, *, heads: int, tile: int):
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki > qi)
    def _above():
        o_ref[0] = jnp.full((tile, tile), -jnp.inf, jnp.float32)

    @pl.when(ki <= qi)
    def _work():
        k, w = k_ref[0], w_ref[0]
        acc = jnp.zeros((tile, tile), jnp.float32)
        for j in range(heads):
            acc = acc + w[:, j:j + 1] * jnp.maximum(
                _dot(q_ref[0, j], k, _NT), 0.0)
        o_ref[0] = jnp.where(_on_or_under(ki, qi, tile), acc, -jnp.inf)


def _index_bwd_kernel(q_ref, k_ref, w_ref, g_ref, dq_ref, dk_ref, dw_ref,
                      dq_scr, dw_scr, *, heads: int, tile: int, grid: int):
    """A tile of dI against its queries and keys: dq_I and dw ride VMEM
    scratch over a query tile's key tiles; dk_I leaves as one partial sum
    a (query tile, key tile), summed over the query tiles by the caller."""
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when(ki > qi)
    def _above():
        dk_ref[0, 0] = jnp.zeros(dk_ref.shape[2:], jnp.float32)

    @pl.when(ki <= qi)
    def _work():
        k, w, g = k_ref[0], w_ref[0], g_ref[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_scr.shape, 1)
        dk = jnp.zeros(dk_ref.shape[2:], jnp.float32)
        dw = dw_scr[...]
        for j in range(heads):
            q = q_ref[0, j]
            s = _dot(q, k, _NT)
            dw = dw + jnp.where(lane == j, jnp.sum(
                g * jnp.maximum(s, 0.0), axis=1, keepdims=True), 0.0)
            ds = jnp.where(s > 0.0, g * w[:, j:j + 1], 0.0)
            dq_scr[j] += _dot(ds.astype(k.dtype), k, _NN)
            dk = dk + _dot(ds.T.astype(q.dtype), q, _NN)
        dw_scr[...] = dw
        dk_ref[0, 0] = dk

    @pl.when(ki == grid - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_scr[...]


def _specs(plan: SparseIndexPlan):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, H, D = plan.tile, plan.heads, plan.head_dim

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)
    # Key tiles above the diagonal are never read: their index is clamped
    # to the last one used, so Mosaic skips the copy (ops/attention.py
    # `_swept_index`).
    return dict(
        q=spec((1, H, tile, D), lambda b, i, j: (b, 0, i, 0)),
        k=spec((1, tile, D), lambda b, i, j: (b, jnp.minimum(j, i), 0)),
        w=spec((1, tile, H), lambda b, i, j: (b, i, 0)),
        square=spec((1, tile, tile), lambda b, i, j: (b, i, j)),
        dk=spec((1, 1, tile, D), lambda b, i, j: (b, i, j, 0)),
        dw=spec((1, tile, _LANES), lambda b, i, j: (b, i, 0)))


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BUDGET)


@functools.partial(jax.jit, static_argnames=("plan",))
def _fwd_call(q, k, w, *, plan: SparseIndexPlan):
    from jax.experimental import pallas as pl

    b, n, specs = q.shape[0], plan.grid, _specs(plan)
    call = pl.pallas_call(
        functools.partial(_index_fwd_kernel, heads=plan.heads,
                          tile=plan.tile),
        grid=(b, n, n),
        in_specs=[specs["q"], specs["k"], specs["w"]],
        out_specs=specs["square"],
        out_shape=jax.ShapeDtypeStruct((b, plan.seq_len, plan.seq_len),
                                       jnp.float32),
        compiler_params=_compiler_params(),
        interpret=attention._interpret())
    with jax.named_scope("sparse_index_fwd"):
        return call(q, k, w)


@functools.partial(jax.jit, static_argnames=("plan",))
def _bwd_call(q, k, w, g, *, plan: SparseIndexPlan):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, specs = q.shape[0], plan.grid, _specs(plan)
    T, H, D = plan.seq_len, plan.heads, plan.head_dim
    call = pl.pallas_call(
        functools.partial(_index_bwd_kernel, heads=H, tile=plan.tile,
                          grid=n),
        grid=(b, n, n),
        in_specs=[specs["q"], specs["k"], specs["w"], specs["square"]],
        out_specs=[specs["q"], specs["dk"], specs["dw"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, n, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((b, T, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((H, plan.tile, D), jnp.float32),
                        pltpu.VMEM((plan.tile, _LANES), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=attention._interpret())
    with jax.named_scope("sparse_index_bwd"):
        return call(q, k, w, g)


# ---------------------------------------------------------------------------
# The four passes
# ---------------------------------------------------------------------------
def index_scores(q, k, w):
    """q [b, H, T, D] and k [b, T, D] in the model's dtype, w [b, T, H]
    float32 -> the index scores I [b, T, T] float32, -inf above the
    diagonal. No gradient rule of its own: `indexer_loss` is the
    indexer's."""
    if _kernel_ok(q.shape[-2]):
        plan = sparse_index_plan(q.shape[2], q.shape[1], q.shape[3],
                                 q.dtype.itemsize)
        return _fwd_call(q, k, w.astype(jnp.float32), plan=plan)
    with jax.named_scope("sparse_index_fwd"):
        return index_scores_reference(q, k, w)


def index_grads(q, k, w, d_scores):
    """The gradients of sum(I * d_scores) by q, k and w, `d_scores` [b, T,
    T] float32 zero wherever I is not read (above the diagonal, outside a
    selection): (dq like q, dk like k, dw float32 like w)."""
    if _kernel_ok(q.shape[-2]):
        plan = sparse_index_plan(q.shape[2], q.shape[1], q.shape[3],
                                 q.dtype.itemsize)
        dq, dk, dw = _bwd_call(q, k, w.astype(jnp.float32), d_scores,
                               plan=plan)
        return (dq, jnp.sum(dk, axis=1).astype(k.dtype),
                dw[..., :plan.heads])
    with jax.named_scope("sparse_index_bwd"):
        seq = q.shape[-2]
        causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
        _, vjp = jax.vjp(
            lambda *a: jnp.where(causal, index_scores_reference(*a), 0.0),
            q, k, w.astype(jnp.float32))
        return vjp(d_scores)


def _ordered(bits):
    """A float32's bits as int32 <-> an int32 in the floats' own order
    (-0.0 just under 0.0): its own inverse."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def kth_largest(scores, k: int):
    """The k-th largest of each row of `scores` [..., n] float32, exactly,
    -inf entries counted as the smallest (so a row with fewer than k others
    gives -inf, and so does n < k): a bisection over the int32 image of
    the float32 order, 32 counts of the row at or above a bar."""
    if k > scores.shape[-1]:
        return jnp.full(scores.shape[:-1], -jnp.inf, jnp.float32)
    keys = _ordered(jax.lax.bitcast_convert_type(scores, jnp.int32))
    lo = jnp.full(scores.shape[:-1], jnp.iinfo(jnp.int32).min, jnp.int32)
    hi = jnp.full(scores.shape[:-1], jnp.iinfo(jnp.int32).max, jnp.int32)

    def narrow(_, bounds):
        lo, hi = bounds
        # ceil((lo + hi) / 2) with no overflow
        mid = (lo & hi) + ((lo ^ hi) >> 1) + ((lo ^ hi) & 1)
        enough = jnp.sum(keys >= mid[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    lo, _ = jax.lax.fori_loop(0, 32, narrow, (lo, hi))
    return jax.lax.bitcast_convert_type(_ordered(lo), jnp.float32)


def _chunks(t, axis: int, chunk: int):
    """The function that reads chunk c of `chunk` along `axis` of t where
    it lies: the axis split in two and the chunks' moved first, which is a
    view (one batch) or a layout XLA reads in place."""
    shape = t.shape[:axis] + (-1, chunk) + t.shape[axis + 1:]
    t = jnp.moveaxis(t.reshape(shape), axis, 0)
    return lambda c: jax.lax.dynamic_index_in_dim(t, c, 0, False)


@jax.named_scope("sparse_select")
def select(scores, topk: int):
    """I [b, T, T] float32 (-inf above the diagonal) -> (the selection
    [b, T, T] int8, 1 where s <= t and I[t, s] >= tau_t; tau [b, T]).
    Exact: no approximate top-k, and a tie at tau_t keeps both. A chunk of
    queries counts its band's key prefix (`key_bands`): the keys right of
    it are -inf, which `kth_largest` counts only where every entry
    counts."""
    chunk, extents = _select_bands(scores.shape[1])
    return _select(scores, topk=topk, chunk=chunk, extents=extents)


# The two passes are traced once a shape, not once a layer: their loops a
# band are most of what a layer's trace would hold.
@functools.partial(jax.jit, static_argnames=("topk", "chunk", "extents"))
def _select(scores, *, topk: int, chunk: int, extents: tuple):
    b, seq, _ = scores.shape
    each = seq // chunk // len(extents)
    rows = _chunks(scores, 1, chunk)
    tau = jnp.concatenate([
        jax.lax.map(lambda c, extent=extent: kth_largest(
            rows(c)[..., :extent], topk),
            jnp.arange(g * each, (g + 1) * each))
        for g, extent in enumerate(extents)])
    tau = tau.swapaxes(0, 1).reshape(b, seq)
    causal = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    return (causal & (scores >= tau[..., None])).astype(jnp.int8), tau


@jax.named_scope("sparse_target")
def index_target(scores, selected, q, k, lse, sm_scale: float):
    """(L_I, dI): `scores` I [b, T, T] float32 and `selected` [b, T, T]
    int8 as `index_scores` and `select` made them; q [b, h, T, hd], k [b,
    kvh, T, hd] (a key head under its group of h / kvh query heads) and
    lse [b, h, 1, T] attention's own under that selection
    (ops.attention.attention_and_lse); `sm_scale` its score scale. p[t, s]
    is the heads' mean probability exp(q . k sm_scale - lse) over S_t; L_I
    the batch's mean of a sequence's 1/T sum_t KL(p[t] || softmax_{S_t}
    I[t]); dI = (softmax_{S_t}(I) - p) / (b T) inside S_t, zero outside.
    In chunks of queries: a chunk holds its [h, chunk, extent] scores
    against its band's key prefix (`key_bands`) and writes its rows of dI,
    whole, over the rows of I it read: the bands' loops carry I, which
    leaves them as dI, so where I is not read again (`indexer_loss`) no
    second [b, T, T] float32 is held and none is zeroed."""
    chunk, extents = _target_bands(q.shape[2])
    return _index_target(scores, selected, q, k, lse, sm_scale=sm_scale,
                         chunk=chunk, extents=extents)


@functools.partial(jax.jit, static_argnames=("sm_scale", "chunk", "extents"))
def _index_target(scores, selected, q, k, lse, *, sm_scale: float,
                  chunk: int, extents: tuple):
    b, h, seq, hd = q.shape
    kvh = k.shape[1]
    each = seq // chunk // len(extents)
    seen_of, q_of, lse_of = (
        _chunks(selected, 1, chunk), _chunks(q, 2, chunk),
        _chunks(lse[:, :, 0], 2, chunk))

    def one(rows, c, extent):
        """`rows` [b, chunks, chunk, T]: I where no chunk has been, dI
        where one has."""
        scores = jax.lax.dynamic_index_in_dim(rows, c, 1, False)[..., :extent]
        seen = seen_of(c)[..., :extent] != 0
        s = jnp.einsum("bjgqd,bjkd->bjgqk",
                       q_of(c).reshape(b, kvh, h // kvh, chunk, hd),
                       k[:, :, :extent],
                       preferred_element_type=jnp.float32) * sm_scale
        a = jnp.exp(s - lse_of(c).reshape(b, kvh, h // kvh, chunk, 1))
        p = jnp.where(seen, jnp.mean(a, axis=(1, 2)), 0.0)
        log_i = jax.nn.log_softmax(
            jnp.where(seen, scores, DEFAULT_MASK_VALUE), axis=-1)
        kl = jnp.sum(jnp.where(p > 0.0, p * (jnp.log(
            jnp.where(p > 0.0, p, 1.0)) - log_i), 0.0))
        d = jnp.where(seen, jnp.exp(log_i) - p, 0.0) / (b * seq)
        d = jnp.pad(d, ((0, 0), (0, 0), (0, seq - extent)))
        return jax.lax.dynamic_update_slice(
            rows, d[:, None], (0, c, 0, 0)), kl

    rows, kl = scores.reshape(b, seq // chunk, chunk, seq), []
    for g, extent in enumerate(extents):
        rows, sums = jax.lax.scan(
            functools.partial(one, extent=extent), rows,
            jnp.arange(g * each, (g + 1) * each))
        kl.append(sums)
    return (jnp.sum(jnp.concatenate(kl)) / (b * seq),
            rows.reshape(b, seq, seq))


# ---------------------------------------------------------------------------
# The gradient rule
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def indexer_loss(q_index, k_index, w, scores, selected, q, k, lse,
                 sm_scale: float):
    """L_I of a layer (`index_target`), differentiable by the indexer's
    own q_index [b, H, T, D], k_index [b, T, D] and w [b, T, H] and by
    nothing else: `scores` must be `index_scores(q_index, k_index, w)` and
    `selected` `select(scores, topk)[0]`, which are read; q, k and lse are
    attention's and are read."""
    return index_target(scores, selected, q, k, lse, sm_scale)[0]


def _indexer_loss_fwd(q_index, k_index, w, scores, selected, q, k, lse,
                      sm_scale):
    loss, d_scores = index_target(scores, selected, q, k, lse, sm_scale)
    # The rule's whole backward pass at a cotangent of one, here, while I
    # and dI are alive; what a rematerialised block keeps of an indexer
    # (models/decoder.py KEPT_UNDER_REMAT) is these three.
    grads = tuple(checkpoint_name(g, "sparse_index_grads")
                  for g in index_grads(q_index, k_index, w, d_scores))
    return loss, grads


def _indexer_loss_bwd(sm_scale, grads, g):
    return (*((g * d).astype(d.dtype) for d in grads), None, None, None,
            None, None)


indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)
