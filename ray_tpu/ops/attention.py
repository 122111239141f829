"""Causal multi-head attention: Pallas TPU flash kernels + jax reference.

Net-new vs the reference codebase (SURVEY.md §2.4: no attention kernels
in-tree — torch users bring their own): blockwise online-softmax (flash)
attention written for the TPU memory hierarchy, forward AND backward:

* Forward: a grid program holds a block of Q rows and, where VMEM allows,
  the head's whole K and V (fetched once a head: their block index does
  not depend on the Q block); fp32 accumulators persist in VMEM scratch;
  the log-sum-exp per row is saved for the backward, a lane row a head
  ([batch, heads, 1, seq] float32, four bytes a position; the backward's
  delta = rowsum(dO * O) travels in the same form).
* Backward: flash-2 style dQ (a Q block against resident K/V) and dK/dV
  (a K/V block against resident Q, dO) kernels that recompute attention
  probabilities from the saved logsumexp — no (seq, seq) matrix is ever
  materialized, so long-context *training* fits.
* Causal calls work the triangle only (`attention_plan` says what runs):
  blocks wholly under the diagonal are computed in unmasked tiles by a
  loop whose trip count is the causal limit, the block the diagonal
  crosses strip by strip up to the diagonal, and only the sub-block on
  the diagonal builds a mask. Nothing above it is computed or fetched.
  Where a whole sequence would not fit VMEM_BUDGET the swept side comes
  in blocks on the grid and the same loops run inside.
* Under a `window` the same blocks work the band only, strip by strip:
  a strip of own positions against the one tile of the other side it can
  see, masked at the band's far edge and on the diagonal (`_band_work`).

On the chip tool's v5e (PERF.md §6, PR 26; bf16, causal, a call's device
time fwd / dQ / dK+dV): (192, 1024, 64) 0.71 / 0.58 / 0.81 ms where one
masked 1024 x 1024 tile a head took 1.26 / 0.88 / 1.21; (64, 4096, 128)
2.50 / 2.38 / 3.56 ms where blocks of 1,024 on the grid took 4.18 / 3.50 /
3.86.

Layout: [batch, heads, seq, head_dim]. The jax reference implementation
serves non-TPU backends, sequences that are not a multiple of 128, and
correctness tests; set RAY_TPU_PALLAS_INTERPRET=1 to run the kernels in
interpreter mode on CPU (the SURVEY §4 CPU-mirror pattern for kernel
tests). On a TPU backend the kernels are the only path for a tileable
sequence: a kernel that fails to compile is an error.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Reference implementation (CPU tests, non-TPU backends)
# ---------------------------------------------------------------------------
def mha_reference(q, k, v, causal: bool = True,
                  sm_scale: Optional[float] = None,
                  window: Optional[int] = None, selected=None):
    """Plain XLA attention; numerically the ground truth for the kernel.
    With a `window` a query sees itself and the window - 1 positions
    before it; with `selected` [batch, seq_q, seq_k] (nonzero: seen) only
    the keys its row of it names, of those the causal mask leaves."""
    *_, seq_q, head_dim = q.shape
    seq_k = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(head_dim)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k,
        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(
            jnp.ones((seq_q, seq_k), dtype=bool), k=seq_k - seq_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((seq_q, seq_k), dtype=bool),
                              k=seq_k - seq_q - window)
        logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    if selected is not None:
        logits = jnp.where(selected[:, None] != 0, logits,
                           DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _interpret() -> bool:
    return os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"


def _on_tpu() -> bool:
    """Whether the kernels run: compiled on a TPU backend, interpreted
    where RAY_TPU_PALLAS_INTERPRET=1 asks for it on another backend. A
    backend that fails to initialise raises here, and interpret mode on
    a TPU is refused: neither may quietly pick an implementation."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if _interpret():
        if on_tpu:
            raise RuntimeError(
                "RAY_TPU_PALLAS_INTERPRET=1 on a TPU backend: the "
                "interpreter is the CPU test mode; unset it to run the "
                "compiled kernels")
        return True
    return on_tpu


def _kernel_ok(seq_len: int) -> bool:
    return _on_tpu() and seq_len >= 128 and seq_len % 128 == 0


# (mesh, PartitionSpec over [batch, heads, seq, head_dim]) while a sharded
# step is being traced; see kernel_sharding.
_SHARDING: contextvars.ContextVar = contextvars.ContextVar(
    "kernel_sharding", default=None)


@contextlib.contextmanager
def kernel_sharding(mesh, spec):
    """Trace the enclosed step with the parts XLA cannot partition run
    once per shard of `mesh`: q/k/v [batch, heads, seq, head_dim] are
    split by `spec`, which may name mesh axes for batch and heads only —
    each kernel instance sees whole sequences — and ops.loss scans each
    chip's own rows of the batch axes.

    A pallas_call is opaque to the SPMD partitioner: jax refuses to lower
    one inside a program partitioned over several devices ("Mosaic
    kernels cannot be automatically partitioned"); a scan that slices
    the batch at a traced offset is gathered whole onto every chip. So a
    sharded train step states here how its batch and heads are split
    (models._training.make_train_step_for does, from its rule table)."""
    if spec[2:] != (None,) * len(spec[2:]):
        raise ValueError(
            f"flash_attention kernels need whole sequences and head_dim; "
            f"{spec} splits them (parallel.sequence does sequence "
            f"parallelism)")
    token = _SHARDING.set((mesh, spec))
    try:
        yield
    finally:
        _SHARDING.reset(token)


def step_sharding():
    """(mesh, spec) of the enclosing kernel_sharding, or None."""
    return _SHARDING.get()


@contextlib.contextmanager
def a_chip_alone():
    """Trace the enclosed as one chip's own share of a step: no enclosing
    kernel_sharding splits it again (models.decoder.remat_plan's blocks,
    at the batch a chip holds)."""
    token = _SHARDING.set(None)
    try:
        yield
    finally:
        _SHARDING.reset(token)


# (state bytes, capacity bytes) while a train step is being traced; see
# step_memory.
_MEMORY: contextvars.ContextVar = contextvars.ContextVar(
    "step_memory", default=(None, None))


@contextlib.contextmanager
def step_memory(state_bytes: Optional[int] = None,
                capacity: Optional[int] = None):
    """Trace the enclosed step knowing what it holds beside its
    activations, `state_bytes` (models._training.make_train_step_for
    counts its state's shapes), and, where no device can say, one chip's
    `capacity` (a compile for a described chip). What is not given stays
    as an enclosing `step_memory` gave it, so a caller's
    `step_memory(capacity=0)` round a step's first call is the way back
    to the base set where the plan's estimate is short. Handed down as
    kernel_sharding hands the kernels their split; read by
    models.decoder.decoder_hidden for `remat_plan`."""
    held_state, held_capacity = _MEMORY.get()
    token = _MEMORY.set((
        held_state if state_bytes is None else state_bytes,
        held_capacity if capacity is None else capacity))
    try:
        yield
    finally:
        _MEMORY.reset(token)


def step_memory_given():
    """(state bytes, capacity bytes) of the enclosing step_memory, each
    None where none gave it."""
    return _MEMORY.get()


def _per_shard(fn):
    """`fn` over q-shaped arrays, run per shard under kernel_sharding."""
    ctx = step_sharding()
    if ctx is None:
        return fn
    mesh, spec = ctx
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


# ---------------------------------------------------------------------------
# The plan: block and sub-block sizes from the shape, and what they execute
# ---------------------------------------------------------------------------
# What one kernel instance may hold in VMEM, and the limit the kernels hand
# the compiler (a v5e has 128 MiB of it; the compiler's default scope is 16).
VMEM_BUDGET = 32 * 1024 * 1024
_MAX_BLOCK = 1024             # a kernel's own block: its score tile is
#                               block x block float32 (4 MiB at 1024;
#                               2048 does not fit a v5e's VMEM in dK/dV)


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """One kernel's tiling and, for one head, what it executes.

    A grid program owns `block` positions of its own side (Q rows in the
    forward and dQ kernels, K/V rows in dK/dV) and sees `swept` positions
    of the other side (the whole sequence where VMEM allows). It computes
    the part of the swept side wholly on the visible side of its block in
    unmasked tiles of block x block, in a loop whose trip count is the
    causal limit, and the block the diagonal crosses as a triangle of
    `sub` x `sub` sub-blocks: per strip of `sub` own positions one tile
    that ends on the diagonal, of which only the last sub-block is
    masked. Under a window the strips are all there is (`_band_work`): a
    strip's tile starts at the band's far edge and ends on the diagonal,
    and its first and last sub-blocks are the masked ones. `computed` +
    `skipped` = every sub-block of the seq_len x seq_len square; `masked`
    of the computed ones build a mask; `tiles` is the matmul tiles (calls
    of a kernel's `step`) the `computed` sub-blocks are worked in."""
    block: int
    swept: int
    sub: int
    vmem_bytes: int
    computed: int
    masked: int
    skipped: int
    tiles: int


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    seq_len: int
    head_dim: int
    causal: bool
    vmem_budget: int
    fwd: KernelPlan
    dq: KernelPlan
    dkv: KernelPlan
    window: Optional[int] = None
    # Under a selection (`flash_attention(..., selected=)`): how many keys
    # a query may name. The kernels then work the causal triangle as
    # without one, a tile of the selection applied after the causal mask.
    selected: Optional[int] = None

    @property
    def executed_pairs(self) -> int:
        """(query, key) pairs a head's forward kernel computes: every
        computed sub-block whole, masked or not."""
        return self.fwd.computed * self.fwd.sub ** 2

    @property
    def required_pairs(self) -> int:
        """Pairs a head's softmax runs over: the triangle (the band under
        a window), and under a selection a query's min(t + 1, selected)."""
        n, reach = self.seq_len, self.seq_len
        if not self.causal:
            return n * n
        if self.window is not None:
            reach = min(reach, self.window)
        if self.selected is not None:
            reach = min(reach, self.selected)
        return reach * (reach + 1) // 2 + (n - reach) * reach

    @property
    def executed_share(self) -> float:
        """Share of the score square the kernels compute (the same in all
        three; 0.5 + sub / (2 * seq_len) when causal, about
        (window + sub) / seq_len under a window)."""
        return self.fwd.computed / (self.fwd.computed + self.fwd.skipped)


def _clip(x, hi):
    if isinstance(x, int):
        return max(0, min(x, hi))
    return jnp.clip(x, 0, hi)


def _visible_blocks(rel, block: int, swept: int, mirrored: bool):
    """[lo, hi): the block-sized chunks of a swept block that a program's
    own block sees whole, `rel` being its first own position less the swept
    block's first. Forward and dQ (own = queries) see the keys before
    their block; dK/dV (`mirrored`, own = keys) are seen by the queries
    after theirs. Python ints give ints (the plan's counts), traced
    scalars the kernels' loop bounds: one rule for both."""
    if mirrored:
        return _clip(rel + block, swept) // block, swept // block
    return 0, _clip(rel, swept) // block


def _on_diagonal(rel, swept: int):
    """Whether the block the diagonal crosses lies in this swept block."""
    return (rel >= 0) & (rel < swept)


def _triangle(block: int, sub: int, mirrored: bool):
    """The diagonal block, per strip of `sub` own positions: (strip, first,
    width) of the other side's positions the strip sees, counted from the
    block's first. The sub-block on the diagonal is the tile's last; its
    first where `mirrored`."""
    for strip in range(block // sub):
        at = strip * sub
        yield (strip, at, block - at) if mirrored else (strip, 0, at + sub)


def _strip_tile(sub: int, window: int, swept: int) -> Optional[int]:
    """Width of the one tile a strip of `sub` own positions can work its
    whole band in: its own sub-block and the `reach` the band gets to
    before it (after it in dK/dV). None where that is wider than the swept
    block, or its scores larger than a causal block's (_MAX_BLOCK squared:
    a window of several thousand positions): the band then goes in pieces
    (`_band_work`)."""
    width = ((window + sub - 2) // sub + 1) * sub
    return width if width <= swept and width * sub <= _MAX_BLOCK ** 2 \
        else None


def _count(seq_len: int, block: int, swept: int, sub: int, causal: bool,
           mirrored: bool, window: Optional[int] = None):
    """(computed, masked, skipped, tiles) of one head: sub-blocks, and the
    tiles they are worked in, by the rules the kernel's loops follow."""
    n = seq_len // sub
    if not causal:
        return n * n, 0, 0, (seq_len // block) ** 2
    computed = masked = tiles = 0
    if window is not None:        # the kernels' own rule, on Python ints

        def step(rows, cols, mask):
            nonlocal computed, masked, tiles
            tiles += 1
            computed += (rows.size // sub) * (cols.size // sub)
            if mask:
                masked += min(mask[2] + mask[3], cols.size) // sub

        def each(lo, hi, body):
            for j in range(lo, hi):
                body(j)
        for own in range(0, seq_len, block):
            for other in range(0, seq_len, swept):
                _band_work(step, (own - other) // sub, block, swept, sub,
                           window, mirrored, swept == seq_len, sweep=each)
        return computed, masked, n * n - computed, tiles
    for own in range(0, seq_len, block):
        for other in range(0, seq_len, swept):
            lo, hi = _visible_blocks(own - other, block, swept, mirrored)
            computed += (hi - lo) * (block // sub) ** 2
            tiles += hi - lo
            if _on_diagonal(own - other, swept):
                for _, _, width in _triangle(block, sub, mirrored):
                    computed += width // sub
                    masked += 1
                    tiles += 1
    return computed, masked, n * n - computed, tiles


def _vmem_bytes(kernel: str, block: int, swept: int, head_dim: int,
                itemsize: int, v_dim: Optional[int] = None,
                tile: Optional[int] = None, selected: bool = False) -> int:
    """An upper estimate of one program's VMEM: every operand and result
    block twice (the pipeline's two buffers), float32 scratch, and three
    float32 tiles (scores, probabilities, their gradient) of `tile`
    elements, block x block where None. v, o and their gradients are
    `v_dim` wide (head_dim where None). Under a selection its block x swept
    tile of int8 comes in beside them, twice too."""
    v_dim = head_dim if v_dim is None else v_dim
    col = 128 * 4       # a position of an [n, 1] f32 column: lane-padded
    row = 8 * 4         # of a [1, n] f32 row: it fills eight sublanes
    own, other = block * head_dim * itemsize, swept * head_dim * itemsize
    own_v, other_v = block * v_dim * itemsize, swept * v_dim * itemsize
    tiles = 3 * (block * block if tile is None else tile) * 4
    if selected:
        tiles += 2 * block * swept
    if kernel == "fwd":     # q | k, v -> o, lse; acc, m, l
        return (2 * (own + other + other_v) + 2 * (own_v + block * row)
                + block * (v_dim * 4 + 2 * col) + tiles)
    if kernel == "dq":      # q, do, lse, delta | k, v -> dq; acc, lse, delta
        return (2 * (own + own_v + 2 * block * row + other + other_v)
                + 2 * own + block * (head_dim * 4 + 2 * col) + tiles)
    # dkv: k, v | q, do, lse, delta -> dk, dv; two accs
    return (2 * (own + own_v + other + other_v + 2 * swept * row)
            + 2 * (own + own_v) + block * (head_dim + v_dim) * 4 + tiles)


def attention_plan(seq_len: int, head_dim: int, causal: bool = True,
                   dtype=jnp.bfloat16, window: Optional[int] = None,
                   v_dim: Optional[int] = None,
                   selected: Optional[int] = None) -> AttentionPlan:
    """The tiling `flash_attention` runs a [.., seq_len, head_dim] call at,
    and the sub-blocks a head computes, masks and skips in each kernel.

    The kernels take their sizes from this function and their loop bounds
    and strips from the rules that count here (`_visible_blocks`,
    `_triangle`), so what it reports is what runs. Sizes follow from the
    shape alone. Sub-blocks are 128 where the sequence is one block, so
    that the triangle is all the work and its offsets are static, and 256
    where the sequence tiles by it and is longer (measured on a v5e at
    head_dim 64 and 128: 128 is 8-9% faster at 1,024 positions, 256 2-3%
    faster at 2,048 and 4,096; PERF.md §6, PR 26; at head_dim 256 the
    sub-block was not swept: 16k runs at 256, the rule's). A kernel's own block is
    sized first: the largest multiple of the sub-block up to 1,024 that
    tiles the sequence. Then the swept side (K and V in forward and dQ; Q,
    dO and their rows in dK/dV): resident whole where the estimate fits
    VMEM_BUDGET with that block, else in the largest blocks that do, swept
    on the grid with the same loops inside; a smaller own block only where
    no swept size fits beside the larger one. The estimate (`_vmem_bytes`)
    counts lse and delta at 32 bytes a position (a [1, n] float32 block
    fills eight sublanes) beside the swept queries of dK/dV, and a column of
    them, 512 bytes a position, in dQ's scratch for its own block. A score
    tile is block x
    block, and what a kernel pays once a tile (the forward's row maxima,
    sums and rescale most of all) it pays four times as often at 512 as at
    1,024, which costs more than a swept side in two or four grid blocks:
    at (16384, 192 | 128) the forward takes 23.4 ms a 32 heads at 1,024 x
    8,192 where 512 x 16,384 took 34.8, and dK/dV at 1,024 x 4,096 is 7-15%
    faster than at 512 x 8,192 at every 8k and 16k shape of the cells
    (PERF.md §6, PR 54). At (16384, 256 | 256), the widest the cells run,
    the swept side is in FOUR grid blocks of 4,096 in all three kernels
    beside a 1,024 own block (25.2, 26.3 and 27.8 MB of the 32 MiB; read on
    a v5e in a train step of 20 heads with dK/dV's in EIGHT of 2,048:
    0.894, 1.192 and 1.478 ms a head, 78%, 88% and 94% of the MXU's peak
    for what each computes, where 128 | 128 with K and V whole reads 69%,
    94% and 90.5%; PERF.md §6, PR 55).

    What dK/dV sweeps at 16,384 positions beside an own block of 1,024 keys
    (swept queries, `vmem_bytes`; a call's device time on a v5e beside
    what it was while lse and delta were padded to 128 lanes, 8 MiB of
    them at 4,096 queries, and the queries came in grid blocks of 4,096,
    2,048 at 256 | 256, 8,192 under the window; PERF.md §6, PR 58):
    (16384, 64) 16,384 whole, 24.6 MB, 32 heads 26.69 -> 25.89 ms;
    (16384, 128) 8,192, 25.2 MB, 32 heads 24.81 -> 24.15; (16384, 192 |
    128) 8,192, 28.0 MB, 32 heads 38.49 -> 37.85; (16384, 256 | 256)
    4,096, 27.8 MB, 20 heads 29.69 -> 29.50; (16384, 64, window 512, v 128)
    16,384 whole, 19.4 MB, 65 tiles a head where 69 were, 20 heads 3.01 ->
    2.37. Forward and dQ keep their swept sides; dQ's relayout of its
    block's two lane rows into columns, once a program, is 1.4% of a call
    at 16k x 64 and 9% at 1,024 positions (the forward's, `_lane_row`,
    costs nothing measurable).

    Under a `window` (causal, a query sees itself and the window - 1
    positions before it) the sizes follow the same rules, and a program
    works its block strip by strip and the band only (`_band_work`): per
    strip of `sub` own positions one tile from the band's far edge to the
    diagonal, (reach + 1) * sub wide with reach = ceil((window - 1) / sub),
    of which the sub-blocks the far edge crosses (one or two) and the
    diagonal's build a mask; about (window + sub) / seq_len of the square.
    A strip whose tile is cut by the sequence's start or end, or lies
    across two swept blocks, or would be larger than a causal block's
    (`_strip_tile`), goes in pieces: a sub-block for each masked edge,
    the sub-blocks between in chunks of up to 1,024 positions. `v_dim` is
    the width of v and of the output where it is not q's and k's.

    Under a selection (`selected`: the keys a query may name; causal, no
    window) the kernels compute what they compute without one, every tile
    of the triangle, and mask each by its tile of the selection [seq, seq]
    int8 after the causal mask: a program's own block x swept tile of it
    is one more operand in VMEM (16 MiB twice at 1,024 x 16,384), so the
    swept side comes in smaller grid blocks; `executed_pairs` and
    `required_pairs` say what that costs (at 16,384 positions and 2,048
    keys a query 4.3 pairs computed for one the softmax runs over)."""
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is causal and at least 1 wide")
    if selected is not None and (not causal or window is not None
                                 or selected < 1):
        raise ValueError("a selection is causal, unwindowed and at least "
                         "one key a query")
    if seq_len < 128 or seq_len % 128:
        raise ValueError(
            f"the kernels tile sequences in multiples of 128, not {seq_len}")
    itemsize = jnp.dtype(dtype).itemsize
    sub = 128 if seq_len <= _MAX_BLOCK or seq_len % 256 else 256
    sizes = [b for b in range(seq_len, 0, -sub) if seq_len % b == 0]

    def plan(kernel: str) -> KernelPlan:
        for block in sizes:
            if block > _MAX_BLOCK:
                continue
            for swept in sizes:
                if swept % block:
                    continue
                tile = None if window is None else sub * (
                    _strip_tile(sub, window, swept) or min(_MAX_BLOCK, swept))
                need = _vmem_bytes(kernel, block, swept, head_dim, itemsize,
                                   v_dim, tile, selected is not None)
                if need <= VMEM_BUDGET:
                    return KernelPlan(block, swept, sub, need, *_count(
                        seq_len, block, swept, sub, causal,
                        kernel == "dkv", window))
        raise ValueError(
            f"no {kernel} tiling of ({seq_len}, {head_dim}) fits "
            f"{VMEM_BUDGET} bytes of VMEM")

    return AttentionPlan(seq_len, head_dim, causal, VMEM_BUDGET,
                         plan("fwd"), plan("dq"), plan("dkv"), window,
                         selected)


def _scale_is_exact(sm_scale: float) -> bool:
    """A power of two scales any operand without rounding, so it can move
    off the scores onto a head_dim-wide operand (1/8 at head_dim 64; not
    1/sqrt(128))."""
    return math.frexp(sm_scale)[0] == 0.5


def _block_id(axis: int, blocks: int):
    """This program's index along a grid axis; a Python 0 where the axis
    has one block, so what depends on nothing else is static."""
    from jax.experimental import pallas as pl
    return pl.program_id(axis) if blocks > 1 else 0


def _ds(start, size: int, align: int):
    """pl.ds of `size` at `start`, a multiple of `align` (said to the
    compiler where `start` is traced)."""
    from jax.experimental import pallas as pl
    if not isinstance(start, int):
        start = pl.multiple_of(start, align)
    return pl.ds(start, size)


def _sweep(lo, hi, body):
    """body(j) for j in [lo, hi); nothing at all where both are Python
    ints and the range is empty."""
    if isinstance(lo, int) and isinstance(hi, int) and lo >= hi:
        return
    jax.lax.fori_loop(lo, hi, lambda j, _: body(j), None)


def _causal_work(step, rel, block: int, swept: int, sub: int,
                 causal: bool, mirrored: bool):
    """Everything one program computes, as calls of step(own rows, other
    rows, on_diagonal): the visible chunks whole, then the block the
    diagonal crosses strip by strip."""
    from jax.experimental import pallas as pl

    def whole(j):
        step(_ds(0, block, block), _ds(j * block, block, block), False)
    if not causal:
        _sweep(0, swept // block, whole)
        return
    _sweep(*_visible_blocks(rel, block, swept, mirrored), whole)

    @pl.when(_on_diagonal(rel, swept))
    def _diagonal():
        for strip, at, width in _triangle(block, sub, mirrored):
            step(_ds(strip * sub, sub, sub), _ds(rel + at, width, sub), True)


def _band_work(step, r, block: int, swept: int, sub: int, window: int,
               mirrored: bool, resident: bool, sweep=_sweep, tile=None,
               begin=None, done=None):
    """Everything one program computes under a window (a query sees the
    keys 0 <= q - k < window), as calls of step(own rows, other rows,
    mask), strip by strip of `sub` own positions; `r` is the own block's
    first sub-block counted from the swept block's first. A strip sees its
    own sub-block of the other side and the `reach` before it (forward and
    dQ: keys) or after it (dK/dV, `mirrored`: queries). Where they lie
    whole inside the swept block they are one tile (`_strip_tile`) in
    straight-line code, masked at both ends: mask = ("band", q - k at the
    tile's first row and column, columns masked at its start, at its
    end). Such a tile is all its strip computes, so `tile` may take it in
    `step`'s place (the forward's softmax then needs no carry). Any other
    strip — cut by the sequence's start or end, across two swept blocks, a
    tile too large — goes in pieces, in one loop over the block's strips:
    each sub-block it sees of this swept block under the band's mask, but
    those wholly inside the band in unmasked chunks of up to _MAX_BLOCK
    positions where the window holds one; `begin(own rows)` and `done(own
    rows)` are called before its first and after its last piece. With the
    swept side `resident` (one swept block: the whole sequence) the forward
    and dQ strips from the reach-th on cannot be cut. Python ints give the
    plan's counts (`sweep` then a Python loop; pl.when decides a Python
    bool at once), traced scalars the kernels' loops."""
    from jax.experimental import pallas as pl

    n = swept // sub
    whole, reach = window // sub, (window + sub - 2) // sub
    far = (reach - max(whole, 1) + 1) * sub     # what the far edge crosses
    width = _strip_tile(sub, window, swept)
    wide = min(_MAX_BLOCK, swept) // sub
    strips = block // sub

    def first_seen(t):
        return t if mirrored else t - reach

    def pieces(strip):
        own, t = _ds(strip * sub, sub, sub), r + strip
        lo, hi = (_clip(first_seen(t) + d, n) for d in (0, reach + 1))
        if begin is not None:
            begin(own)

        def single(j):
            step(own, _ds(j * sub, sub, sub),
                 ("band", (j - t if mirrored else t - j) * sub, sub, 0))
        if whole - 1 < wide:
            sweep(lo, hi, single)
        else:   # wholly inside the band: whole - 1 sub-blocks by the diagonal's
            inside, end = (
                _clip((t + 1 if mirrored else t - whole + 1) + d, n)
                for d in (0, whole - 1))
            chunks = (end - inside) // wide
            sweep(lo, inside, single)
            sweep(0, chunks, lambda j: step(
                own, _ds((inside + j * wide) * sub, wide * sub, sub), False))
            sweep(inside + chunks * wide, hi, single)
        if done is not None:
            done(own)

    def cut(strip):
        first = first_seen(r + strip)
        pl.when((first < 0) | (first + reach >= n))(
            functools.partial(pieces, strip))

    if width is None:
        sweep(0, strips, pieces)
        return
    sweep(0, strips if mirrored or not resident else min(reach, strips), cut)
    edges = ("band", 0, sub, far) if mirrored \
        else ("band", reach * sub, far, sub)
    for strip in range(strips):
        first = first_seen(r + strip)
        work = functools.partial(tile or step, _ds(strip * sub, sub, sub),
                                 _ds(first * sub, width, sub), edges)
        if resident and not mirrored and strip >= reach:
            work()
        else:
            pl.when((first >= 0) & (first + reach < n))(work)


def _mask_band(s, first, window: int, mirrored: bool):
    """Mask a tile of scores outside 0 <= q - k < window, `first` being
    q - k at its first row and column (rows queries; keys where
    `mirrored`)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ahead = first + (cols - rows if mirrored else rows - cols)
    return jnp.where((ahead >= 0) & (ahead < window), s,
                     DEFAULT_MASK_VALUE)


def _masked(s, mask, sub: int, window, mirrored: bool):
    """A tile of scores under what `step` was told: False, True (the
    diagonal's sub-block of a causal strip) or ("band", first, head,
    tail): the band's mask on the tile's first `head` and last `tail`
    columns, which hold its two edges; what lies between is whole."""
    if mask is True:
        return _mask_diagonal(s, sub, mirrored)
    if mask is False:
        return s
    _, first, head, tail = mask
    rest = s.shape[1] - tail
    if head >= rest:
        return _mask_band(s, first, window, mirrored)
    return jnp.concatenate([
        _mask_band(s[:, :head], first, window, mirrored), s[:, head:rest],
        _mask_band(s[:, rest:], first + (rest if mirrored else -rest),
                   window, mirrored)], axis=1)


def _mask_diagonal(s, sub: int, mirrored: bool):
    """Mask the sub-block of a strip's scores that the diagonal crosses:
    the last `sub` columns, the first where the scores are transposed
    (rows keys, columns queries). No other sub-block builds a mask."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    if mirrored:
        parts = [jnp.where(cols >= rows, s[:, :sub], DEFAULT_MASK_VALUE),
                 s[:, sub:]]
    else:
        parts = [s[:, :-sub],
                 jnp.where(rows >= cols, s[:, -sub:], DEFAULT_MASK_VALUE)]
    return jnp.concatenate([p for p in parts if p.shape[1]], axis=1)


def _select(s, sel_ref, rows, cols):
    """A tile of scores under its tile of the selection (int8, nonzero:
    seen), where the call has one; after the causal mask."""
    if sel_ref is None:
        return s
    seen = sel_ref[0, rows, cols].astype(jnp.int32) != 0
    return jnp.where(seen, s, DEFAULT_MASK_VALUE)


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    # Operands in their own dtype (bf16 runs the MXU at full rate),
    # float32 accumulation.
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _lane_row(col):
    """A (rows, 1) float32 column as the (1, rows) lane row of the same
    values, to the bit: 128 rows at a time, the column against the
    identity's mask, summed over sublanes (a value and 127 zeros). A
    transpose (`col.T`) gives the same row through the transpose unit and
    costs a program 0.4 us a 1,024 rows on a v5e, 7% of a forward program
    at 1,024 positions; this form costs it nothing measurable there and
    the same at 4,096 x 128 (PERF.md section 6, PR 58). The other way, a
    lane row to a column, Mosaic lowers as a transpose only (dQ's)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    return jnp.concatenate([
        jnp.sum(jnp.where(rows == cols, col[at:at + 128, :], 0.0), axis=0,
                keepdims=True)
        for at in range(0, col.shape[0], 128)], axis=1)


# ---------------------------------------------------------------------------
# Forward kernel: grid (bh, q blocks, k blocks). Float32 accumulators ride
# VMEM scratch across a program's tiles and the K blocks of the grid.
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale: float,
                causal: bool, sub: int, grid: tuple, save_lse: bool,
                window: Optional[int] = None, selected: bool = False):
    sel_ref, o_ref, *rest = rest if selected else (None, *rest)
    if save_lse:
        lse_ref, acc_scr, m_scr, l_scr = rest
    else:
        lse_ref = None
        acc_scr, m_scr, l_scr = rest
    from jax.experimental import pallas as pl

    block, swept = q_ref.shape[1], k_ref.shape[1]
    qi, ki = _block_id(1, grid[1]), _block_id(2, grid[2])
    fold = _scale_is_exact(sm_scale)

    # With K and V resident a strip under a window begins and ends in one
    # program: it starts and writes its own rows, a strip in one tile
    # without the scratch.
    by_strip = window is not None and grid[2] == 1

    def start(rows):
        acc_scr[rows, :] = jnp.zeros((rows.size, acc_scr.shape[1]),
                                     jnp.float32)
        m_scr[rows, :] = jnp.full((rows.size, 1), -jnp.inf, jnp.float32)
        l_scr[rows, :] = jnp.zeros((rows.size, 1), jnp.float32)

    if not by_strip:
        @pl.when(ki == 0)
        def _init():
            acc_scr[...] = jnp.zeros_like(acc_scr)
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)

    def scores(rows, cols, mask):
        q = q_ref[0, rows, :]
        if fold:
            q = q * sm_scale
        s = _dot(q, k_ref[0, cols, :], _NT)
        if not fold:
            s = s * sm_scale
        return _select(_masked(s, mask, sub, window, False), sel_ref, rows,
                       cols)

    def step(rows, cols, mask):
        s = scores(rows, cols, mask)
        m_prev = m_scr[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[rows, :] = alpha * l_scr[rows, :] + jnp.sum(
            p, axis=-1, keepdims=True)
        m_scr[rows, :] = m_new
        v = v_ref[0, cols, :]
        acc_scr[rows, :] = alpha * acc_scr[rows, :] + _dot(
            p.astype(v.dtype), v, _NN)

    def whole_band(rows, cols, mask):
        # All a strip of queries sees, in one tile: a softmax with no carry.
        s = scores(rows, cols, mask)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)     # the diagonal's is in it
        v = v_ref[0, cols, :]
        o_ref[0, rows, :] = (_dot(p.astype(v.dtype), v, _NN) / l).astype(
            o_ref.dtype)
        if save_lse:
            m_scr[rows, :] = m + jnp.log(l)

    def finish(rows):
        l = l_scr[rows, :]
        o_ref[0, rows, :] = (acc_scr[rows, :] / l).astype(o_ref.dtype)
        if save_lse:
            m_scr[rows, :] += jnp.log(l)

    if window is None:
        _causal_work(step, qi * block - ki * swept, block, swept, sub,
                     causal, False)
    else:
        whole = dict(tile=whole_band, begin=start, done=finish) \
            if by_strip else {}
        _band_work(step, qi * (block // sub) - ki * (swept // sub), block,
                   swept, sub, window, False, by_strip, **whole)
    if by_strip:
        if save_lse:    # every strip left its rows' lse where its maxima were
            lse_ref[0] = _lane_row(m_scr[...])
        return

    @pl.when(ki == grid[2] - 1)
    def _finalize():
        l = l_scr[...]
        # Fully-masked rows (can't happen causally, but keep it safe for
        # degenerate inputs): avoid 0/0.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        if save_lse:
            lse_ref[0] = _lane_row(m_scr[...] + jnp.log(l_safe))


def _swept_index(causal: bool, block: int, swept: int, mirrored: bool,
                 window: Optional[int] = None):
    """Index map of the swept side's blocks on a grid (bh, own, swept).
    Causal blocks wholly past the diagonal are never used: clamp their
    index to the last used one, so Mosaic sees an unchanged block and
    skips the HBM->VMEM copy (the kernel's loops run zero times there).
    Under a window the blocks wholly before the band are clamped away
    too."""
    if not causal:
        return lambda b, i, j: (b, j, 0)
    if window is not None and mirrored:     # queries k .. k + window - 1
        return lambda b, i, j: (b, jnp.clip(
            j, i * block // swept,
            ((i + 1) * block + window - 2) // swept), 0)
    if window is not None:                  # keys q - window + 1 .. q
        return lambda b, i, j: (b, jnp.clip(
            j, jnp.maximum(i * block - window + 1, 0) // swept,
            ((i + 1) * block - 1) // swept), 0)
    if mirrored:    # dK/dV: Q blocks from the one holding the K block on
        return lambda b, i, j: (b, jnp.maximum(j, i * block // swept), 0)
    return lambda b, i, j: (b, jnp.minimum(j, i * block // swept), 0)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BUDGET)


# The kernel calls are jitted so that a model's layers, which call them at
# one shape, trace and lower each kernel once a step and not once a layer
# (12 x 3 kernels of 8 unrolled strips cost gpt2-small's step 4 s of
# set-up otherwise). Only the pallas_call is inside: what XLA can fuse
# with its neighbours (reshapes, delta) stays in the caller's program.
def _selection_spec(heads: int, own_len: int, swept_len: int, swept_map):
    """The BlockSpec of a program's tile of the selection [batch, seq,
    seq] int8 (dK/dV's: of its transpose), every head of a batch row
    reading the same one: the own block's rows, the swept block's columns
    by the swept side's own (clamped) index map."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(
        (1, own_len, swept_len),
        lambda b, i, j: (b // heads, i, swept_map(b, i, j)[1]),
        memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "plan", "save_lse", "window", "heads"))
def _forward_call(qf, kf, vf, sel=None, *, causal: bool, sm_scale: float,
                  plan: KernelPlan, save_lse: bool,
                  window: Optional[int] = None, heads: int = 1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, seq_len, head_dim = qf.shape
    v_dim = vf.shape[-1]
    block, swept = plan.block, plan.swept
    grid = (bh, seq_len // block, seq_len // swept)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, sub=plan.sub,
        grid=grid, save_lse=save_lse, window=window,
        selected=sel is not None)
    own = lambda b, i, j: (b, i, 0)  # noqa: E731
    other = _swept_index(causal, block, swept, False, window)
    q_spec = pl.BlockSpec((1, block, head_dim), own, memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, block, v_dim), own, memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, swept, head_dim), other,
                          memory_space=pltpu.VMEM)
    v_spec = pl.BlockSpec((1, swept, v_dim), other, memory_space=pltpu.VMEM)
    out_specs = [o_spec]
    out_shape = [jax.ShapeDtypeStruct((bh, seq_len, v_dim), qf.dtype)]
    if save_lse:
        # One lane row a head, four bytes a position: a program turns its
        # rows' column of lse into its stretch of the row once, at its end.
        # Inference-only forwards skip it entirely: pallas outputs are
        # opaque to XLA DCE, so an unused lse would still be written.
        out_specs.append(
            pl.BlockSpec((1, 1, block), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32))
    operands, in_specs = (qf, kf, vf), [q_spec, k_spec, v_spec]
    if sel is not None:
        operands += (sel,)
        in_specs.append(_selection_spec(heads, block, swept, other))
    fwd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block, v_dim), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )
    # A scope, never pallas_call(name=...): the scope reaches the name of
    # the HLO instruction, which is what a device trace shows, and leaves
    # kernel_name (_fwd_kernel) as it is (util/profiling.py DEVICE_SCOPES).
    # Under a window the name gains `_window`, here and round the two
    # backward calls: a trace tells the banded calls' rows from the full
    # ones', and whoever matches the kernel's name as a substring reads both.
    with jax.named_scope("flash_attention_fwd") if window is None \
            else jax.named_scope("flash_attention_fwd_window"):
        return fwd(*operands)


def _flash_forward(q, k, v, causal: bool, sm_scale: float,
                   plan: KernelPlan, save_lse: bool = True,
                   window: Optional[int] = None, sel=None):
    batch, heads, seq_len, head_dim = q.shape
    flat = (batch * heads, seq_len, head_dim)
    given = {} if sel is None else {"sel": sel, "heads": heads}
    result = _forward_call(
        q.reshape(flat), k.reshape(flat),
        v.reshape(batch * heads, seq_len, v.shape[-1]), causal=causal,
        sm_scale=sm_scale, plan=plan, save_lse=save_lse, window=window,
        **given)
    out = result[0].reshape(v.shape)
    # lse is a lane row a head, [batch, heads, 1, seq]: q's leading axes
    # and rank, so it shards like q (see kernel_sharding).
    lse = result[1].reshape(batch, heads, 1, seq_len) if save_lse else None
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels (flash-2): recompute P per tile from saved lse.
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               sm_scale: float, causal: bool, sub: int, grid: tuple,
               window: Optional[int] = None, selected: bool = False):
    from jax.experimental import pallas as pl

    sel_ref, dq_ref, dq_scr, lse_scr, delta_scr = \
        rest if selected else (None, *rest)

    block, swept = q_ref.shape[1], k_ref.shape[1]
    qi, ki = _block_id(1, grid[1]), _block_id(2, grid[2])
    fold = _scale_is_exact(sm_scale)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # Its own rows' lse and delta, from lane rows to the columns a tile
        # of scores subtracts: once a program.
        lse_scr[...] = lse_ref[0].T
        delta_scr[...] = delta_ref[0].T

    def step(rows, cols, on_diagonal):
        q = q_ref[0, rows, :]
        if fold:
            q = q * sm_scale
        k = k_ref[0, cols, :]
        s = _dot(q, k, _NT)
        if not fold:
            s = s * sm_scale
        s = _select(_masked(s, on_diagonal, sub, window, False), sel_ref,
                    rows, cols)
        p = jnp.exp(s - lse_scr[rows, :])
        dp = _dot(do_ref[0, rows, :], v_ref[0, cols, :], _NT)
        ds = p * (dp - delta_scr[rows, :])
        if not fold:
            ds = ds * sm_scale
        dq_scr[rows, :] += _dot(ds.astype(k.dtype), k, _NN)

    if window is None:
        _causal_work(step, qi * block - ki * swept, block, swept, sub,
                     causal, False)
    else:
        _band_work(step, qi * (block // sub) - ki * (swept // sub), block,
                   swept, sub, window, False, grid[2] == 1)

    @pl.when(ki == grid[2] - 1)
    def _finalize():
        dq = dq_scr[...]
        if fold:
            dq = dq * sm_scale
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                sm_scale: float, causal: bool, sub: int, grid: tuple,
                window: Optional[int] = None, selected: bool = False):
    from jax.experimental import pallas as pl

    # Under a selection its tile comes transposed, rows keys, as the
    # scores here are.
    sel_ref, dk_ref, dv_ref, dk_scr, dv_scr = \
        rest if selected else (None, *rest)

    block, swept = k_ref.shape[1], q_ref.shape[1]
    ki, qi = _block_id(1, grid[1]), _block_id(2, grid[2])
    fold = _scale_is_exact(sm_scale)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(rows, cols, on_diagonal):
        k = k_ref[0, rows, :]
        if fold:
            k = k * sm_scale
        q = q_ref[0, cols, :]
        do = do_ref[0, cols, :]
        s_t = _dot(k, q, _NT)                     # rows keys, columns queries
        if not fold:
            s_t = s_t * sm_scale
        s_t = _select(_masked(s_t, on_diagonal, sub, window, True), sel_ref,
                      rows, cols)
        p_t = jnp.exp(s_t - lse_ref[0, :, cols])
        dv_scr[rows, :] += _dot(p_t.astype(do.dtype), do, _NN)
        dp_t = _dot(v_ref[0, rows, :], do, _NT)
        ds_t = p_t * (dp_t - delta_ref[0, :, cols])
        if not fold:
            ds_t = ds_t * sm_scale
        dk_scr[rows, :] += _dot(ds_t.astype(q.dtype), q, _NN)

    if window is None:
        _causal_work(step, ki * block - qi * swept, block, swept, sub,
                     causal, True)
    else:
        _band_work(step, ki * (block // sub) - qi * (swept // sub), block,
                   swept, sub, window, True, grid[2] == 1)

    @pl.when(qi == grid[2] - 1)
    def _finalize():
        dk = dk_scr[...]
        if fold:
            dk = dk * sm_scale
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _backward_pallas(kernel, mirrored: bool, q, v, *, causal: bool,
                     sm_scale: float, plan: KernelPlan,
                     window: Optional[int] = None, heads: int = 0):
    """The pallas_call of a backward kernel on a grid (bh, own blocks,
    swept blocks): Q, dO and their stretch of the lse and delta rows on one
    side, K and V on the other, `q` [bh, seq, head_dim] and `v` [bh, seq,
    v_dim] giving the widths; dQ like q, or (`mirrored`) dK like q and dV
    like v. `heads` (a selection's call): a seventh operand, the selection
    [batch, seq, seq] int8, dK/dV's transposed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, seq_len, head_dim), v_dim = q.shape, v.shape[-1]
    own = lambda b, i, j: (b, i, 0)  # noqa: E731
    other = _swept_index(causal, plan.block, plan.swept, mirrored, window)
    q_map, k_map = (other, own) if mirrored else (own, other)
    q_len, k_len = (plan.swept, plan.block) if mirrored \
        else (plan.block, plan.swept)

    def spec(length, width, index):
        return pl.BlockSpec((1, length, width), index,
                            memory_space=pltpu.VMEM)
    q_spec, do_spec = spec(q_len, head_dim, q_map), spec(q_len, v_dim, q_map)
    k_spec, v_spec = spec(k_len, head_dim, k_map), spec(k_len, v_dim, k_map)
    row_spec = pl.BlockSpec(
        (1, 1, q_len), lambda *ids: (ids[0], 0, q_map(*ids)[1]),
        memory_space=pltpu.VMEM)
    outs = [(k_spec, q), (v_spec, v)] if mirrored else [(q_spec, q)]
    scratch = [pltpu.VMEM((plan.block, like.shape[-1]), jnp.float32)
               for _, like in outs]
    if not mirrored:    # dQ's own rows' lse and delta, as columns
        scratch += [pltpu.VMEM((plan.block, 1), jnp.float32)] * 2
    grid = (bh, seq_len // plan.block, seq_len // plan.swept)
    in_specs = [q_spec, k_spec, v_spec, do_spec, row_spec, row_spec]
    if heads:
        in_specs.append(
            _selection_spec(heads, plan.block, plan.swept, other))
    return pl.pallas_call(
        functools.partial(kernel, sm_scale=sm_scale, causal=causal,
                          sub=plan.sub, grid=grid, window=window,
                          selected=bool(heads)),
        grid=grid,
        in_specs=in_specs,
        out_specs=[o for o, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(like.shape, like.dtype)
                   for _, like in outs],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "plan", "window", "heads"))
def _dq_call(*operands, **static):
    with jax.named_scope("flash_attention_dq") \
            if static.get("window") is None \
            else jax.named_scope("flash_attention_dq_window"):
        return _backward_pallas(_dq_kernel, False, operands[0], operands[2],
                                **static)(*operands)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "plan", "window", "heads"))
def _dkv_call(*operands, **static):
    with jax.named_scope("flash_attention_dkv") \
            if static.get("window") is None \
            else jax.named_scope("flash_attention_dkv_window"):
        return _backward_pallas(_dkv_kernel, True, operands[0], operands[2],
                                **static)(*operands)


def _flash_backward(q, k, v, o, lse, g, causal: bool, sm_scale: float,
                    dq_plan: KernelPlan, dkv_plan: KernelPlan,
                    window: Optional[int] = None, sel=None):
    batch, heads, seq_len, head_dim = q.shape
    bh = batch * heads
    flat = (bh, seq_len, head_dim)
    flat_v = (bh, seq_len, v.shape[-1])
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise reduce in XLA; like
    # lse a lane row a head.
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, seq_len)
    operands = (q.reshape(flat), k.reshape(flat), v.reshape(flat_v),
                g.reshape(flat_v), lse.reshape(bh, 1, seq_len), delta)
    rows, cols = ((), ()) if sel is None else (
        (sel,), (jnp.swapaxes(sel, 1, 2),))     # dK/dV's rows are keys
    given = {} if sel is None else {"heads": heads}
    dq, = _dq_call(*operands, *rows, causal=causal, sm_scale=sm_scale,
                   plan=dq_plan, window=window, **given)
    # dK/dV: K-outer, Q-inner sweep.
    dk, dv = _dkv_call(*operands, *cols, causal=causal, sm_scale=sm_scale,
                       plan=dkv_plan, window=window, **given)
    return dq.reshape(q.shape), dk.reshape(q.shape), dv.reshape(v.shape)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    window: Optional[int] = None, selected=None):
    """Flash attention: Pallas kernels on TPU, reference elsewhere.

    Differentiable end to end without materializing the (seq, seq)
    probability matrix: the backward recomputes attention blockwise from
    the saved logsumexp (flash-2), so both inference AND training scale
    to long sequences (SURVEY.md hard-part #5). With a `window` a query
    sees itself and the window - 1 positions before it, and the kernels
    compute the band and nothing else (`attention_plan`). v may be wider
    than q and k (differential attention: one score map times two heads'
    values side by side); the output is as wide as v. With `selected`
    [batch, seq, seq] int8 (nonzero: seen; one map for every head; causal,
    no window) a query sees the keys its row names of those at or before
    it: a mask in all three kernels, a tile of it beside each tile of
    scores, and no gradient (`attention_plan` says what runs).
    """
    # Primal-only call (no differentiation): skip the lse residual.
    out, _ = _flash_attention_fwd_impl(q, k, v, causal, sm_scale, window,
                                       selected, save_lse=False)
    return out


def _scale_of(q, sm_scale):
    return sm_scale if sm_scale is not None else 1.0 / math.sqrt(
        q.shape[-1])


def _plan_of(q, v, causal, window, selected=None) -> AttentionPlan:
    # (how many keys a query names is the caller's to know: the tiling
    # needs that there is a selection, not its size)
    return attention_plan(q.shape[-2], q.shape[-1], causal, q.dtype,
                          window, v.shape[-1],
                          None if selected is None else q.shape[-2])


def _with_selection(fn, selected):
    """`fn` over q-shaped arrays and, where there is one, the selection
    after them; per shard where there is none."""
    if selected is None:
        return _per_shard(fn)
    if step_sharding() is not None:
        raise NotImplementedError(
            "a selection [batch, seq, seq] under kernel_sharding: the "
            "kernels' shard_map splits q-shaped operands only")
    return lambda *operands: fn(*operands, sel=selected)


def _flash_attention_fwd_impl(q, k, v, causal, sm_scale, window=None,
                              selected=None, save_lse=True):
    scale = _scale_of(q, sm_scale)
    seq_len = q.shape[-2]
    if _kernel_ok(seq_len):
        plan = _plan_of(q, v, causal, window, selected)
        out, lse = _with_selection(functools.partial(
            _flash_forward, causal=causal, sm_scale=scale, plan=plan.fwd,
            save_lse=save_lse, window=window), selected)(q, k, v)
        return out, (out, lse)
    return mha_reference(q, k, v, causal, scale, window, selected), \
        (None, None)


def _flash_fwd(q, k, v, causal, sm_scale, window, selected=None):
    out, (o_saved, lse) = _flash_attention_fwd_impl(
        q, k, v, causal, sm_scale, window, selected)
    if o_saved is not None:
        # What the backward kernels read, by name: a rematerialised block
        # keeps these, so the forward kernel does not run again, nor the
        # norm, projection and rotary that made q, k and v
        # (models/decoder.py KEPT_UNDER_REMAT). With no jax.checkpoint
        # round the caller a name lowers to nothing.
        out = o_saved = checkpoint_name(out, "flash_attention_out")
        lse = checkpoint_name(lse, "flash_attention_lse")
        q = checkpoint_name(q, "flash_attention_q")
        k = checkpoint_name(k, "flash_attention_k")
        v = checkpoint_name(v, "flash_attention_v")
    return out, (q, k, v, o_saved, lse, selected)


@jax.named_scope("flash_attention_bwd")
def _flash_bwd(causal, sm_scale, window, residuals, g):
    q, k, v, o, lse, selected = residuals
    scale = _scale_of(q, sm_scale)
    if o is None:
        # Non-kernel path: autodiff through the reference.
        _, vjp = jax.vjp(
            lambda q_, k_, v_: mha_reference(q_, k_, v_, causal, sm_scale,
                                             window, selected),
            q, k, v)
        return (*vjp(g), None)
    plan = _plan_of(q, v, causal, window, selected)
    return (*_with_selection(functools.partial(
        _flash_backward, causal=causal, sm_scale=scale, dq_plan=plan.dq,
        dkv_plan=plan.dkv, window=window), selected)(q, k, v, o, lse, g),
            None)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _reference_lse(q, k, sm_scale, selected):
    """The log of each causal softmax's sum [batch, heads, 1, seq]
    float32 as the forward kernel saves it, in plain XLA."""
    seq = q.shape[-2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    seen = jnp.tril(jnp.ones((seq, seq), dtype=bool))
    if selected is not None:
        seen = seen & (selected[:, None] != 0)
    return jax.nn.logsumexp(jnp.where(seen, logits, DEFAULT_MASK_VALUE),
                            axis=-1)[:, :, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def attention_and_lse(q, k, v, sm_scale: Optional[float] = None,
                      selected=None):
    """Causal `flash_attention` that also hands out what its forward kernel
    saves for its backward: (out, lse [batch, heads, 1, seq] float32, the
    log of each query's softmax sum), for a caller that needs the
    probabilities again (the sparse indexer's target,
    ops/sparse_index.py). lse is read, not differentiated: no cotangent of
    it reaches q, k or v."""
    return _attention_and_lse_fwd(q, k, v, sm_scale, selected)[0]


def _attention_and_lse_fwd(q, k, v, sm_scale, selected=None):
    out, saved = _flash_fwd(q, k, v, True, sm_scale, None, selected)
    lse = saved[4]
    if lse is None:
        lse = _reference_lse(q, k, _scale_of(q, sm_scale), selected)
    return (out, lse), saved


def _attention_and_lse_bwd(sm_scale, residuals, cotangents):
    return _flash_bwd(True, sm_scale, None, residuals, cotangents[0])


attention_and_lse.defvjp(_attention_and_lse_fwd, _attention_and_lse_bwd)
