"""Streaming consumption utilities + split iterators.

Reference parity: _internal/iterator/stream_split_iterator.py
(StreamSplitDataIterator :31) and _internal/block_batching. The
pull-based operator topology itself lives in executor.py
(StreamingExecutor); this module provides the consumption side — block
resolution with prefetch, batch re-chunking, the streaming_split
coordinator, and the jax device feed.
"""

from __future__ import annotations

import collections
from typing import Callable, Iterator, List, Optional, Tuple

from .. import api
from ..util.profiling import annotate
from . import block as B
from .context import DataContext

# A streamed bundle: (ObjectRef of block, row count or -1 if not known yet)
StreamedBundle = Tuple[api.ObjectRef, int]


def iter_blocks(bundles: Iterator[StreamedBundle],
                prefetch: int = 0) -> Iterator[B.Block]:
    """Resolve bundle refs to blocks; with `prefetch` > 0, hold that many
    upcoming refs before the one being consumed. Pulling ahead from
    `bundles` advances the streaming executor's admission, so later
    bundles execute (and their results land in the store) while the
    current block is being consumed — the reference's iter_batches
    read-ahead."""
    def fetch(bundle):
        with annotate("ray_tpu.feed.fetch_block"):
            return api.get(bundle[0])

    window: collections.deque = collections.deque()
    for bundle in bundles:
        window.append(bundle)
        if len(window) > prefetch:
            yield fetch(window.popleft())
    while window:
        yield fetch(window.popleft())


def shuffled_blocks(blocks: Iterator[B.Block], buffer_size: int,
                    seed: Optional[int] = None) -> Iterator[B.Block]:
    """Consumption-side local shuffle (reference: ShufflingBatcher,
    _internal/block_batching/util — iter_batches'
    local_shuffle_buffer_size): hold a row buffer of at least
    `buffer_size` rows; each emission permutes the buffer once and
    yields the surplus prefix — a uniform draw without replacement —
    so rows mix across neighboring blocks without a distributed
    exchange. The tail is flushed permuted. Row-identity preserving:
    multiset in == multiset out."""
    import numpy as np
    rng = np.random.default_rng(seed)
    buf: Optional[B.Block] = None
    for blk in blocks:
        if not B.block_length(blk):
            continue
        buf = blk if buf is None else B.block_concat([buf, blk])
        n = B.block_length(buf)
        if n > buffer_size:
            buf = B.block_take_indices(buf, rng.permutation(n))
            yield B.block_slice(buf, 0, n - buffer_size)
            buf = B.block_slice(buf, n - buffer_size, n)
    if buf is not None and B.block_length(buf):
        n = B.block_length(buf)
        yield B.block_take_indices(buf, rng.permutation(n))


def batches_from_blocks(
    blocks: Iterator[B.Block],
    batch_size: Optional[int],
    batch_format: str = "numpy",
    drop_last: bool = False,
) -> Iterator:
    """Re-chunk a block stream into fixed-size batches (reference:
    _internal/block_batching)."""
    def batch_of(blk, start=None, stop=None):
        with annotate("ray_tpu.feed.assemble"):
            if start is not None:
                blk = B.block_slice(blk, start, stop)
            return B.to_batch_format(blk, batch_format)

    leftover: Optional[B.Block] = None
    for blk in blocks:
        if leftover is not None:
            with annotate("ray_tpu.feed.assemble"):
                blk = B.block_concat([leftover, blk])
            leftover = None
        n = B.block_length(blk)
        if batch_size is None:
            if n:
                yield batch_of(blk)
            continue
        pos = 0
        while n - pos >= batch_size:
            yield batch_of(blk, pos, pos + batch_size)
            pos += batch_size
        if pos < n:
            leftover = B.block_slice(blk, pos, n)
    if leftover is not None and B.block_length(leftover) and not drop_last:
        yield batch_of(leftover)


# ---------------------------------------------------------------------------
# streaming_split
# ---------------------------------------------------------------------------
@api.remote
class _SplitCoordinator:
    """Hands blocks out to n consumers exactly once per epoch (reference:
    the SplitCoordinator actor behind streaming_split,
    stream_split_iterator.py:31).

    Blocks are pre-assigned at construction — equal=True balances by row
    count (largest block to the least-loaded consumer, the classic LPT
    greedy) — so a consumer that starts late or pulls slowly can never be
    starved by a faster peer, and every epoch replays the same
    assignment deterministically.
    """

    def __init__(self, bundles: List[Tuple[object, int]], n: int,
                 equal: bool):
        self._n = n
        self._assignment: List[List] = [[] for _ in range(n)]
        self._rows_given = [0] * n
        if equal:
            order = sorted(bundles, key=lambda b: -b[1])
            for ref, rows in order:
                tgt = min(range(n), key=lambda i: self._rows_given[i])
                self._assignment[tgt].append(ref)
                self._rows_given[tgt] += rows
        else:
            for i, (ref, rows) in enumerate(bundles):
                self._assignment[i % n].append(ref)
                self._rows_given[i % n] += rows
        self._pos = [0] * n

    def next_block(self, consumer: int):
        """Next block ref for `consumer`, or None at epoch end (the
        position resets, so the next iteration replays the shard)."""
        pos = self._pos[consumer]
        if pos >= len(self._assignment[consumer]):
            self._pos[consumer] = 0
            return None
        self._pos[consumer] = pos + 1
        return self._assignment[consumer][pos]

    def reset(self, consumer: int):
        """Rewind `consumer` to its shard start (new epoch). Iterators
        call this when (re)starting so a partially consumed or
        prefetch-overshot previous epoch can't skip blocks."""
        self._pos[consumer] = 0

    def stats(self):
        return {"rows_given": list(self._rows_given)}


def jax_device_feed(batches: Iterator, *, device=None, sharding=None,
                    device_prefetch: int = 2) -> Iterator:
    """Shared device-upload window behind Dataset.iter_jax_batches and
    DataIterator.iter_jax_batches: yields batches already on the
    accelerator with up to `device_prefetch` async uploads in flight
    (0 = upload synchronously with consumption, no device-side
    buffering). jax.device_put(v, None) is default placement, so one
    target covers the pinned, sharded, and default cases."""
    import collections

    import jax

    if device is not None and sharding is not None:
        raise ValueError("pass device= OR sharding=, not both")
    target = sharding if sharding is not None else device
    depth = int(device_prefetch)
    if depth < 0:
        raise ValueError("device_prefetch must be >= 0")
    window: collections.deque = collections.deque()
    for batch in batches:
        with annotate("ray_tpu.feed.device_put"):
            put = {k: jax.device_put(v, target) for k, v in batch.items()}
        if depth == 0:
            yield put
            continue
        window.append(put)
        if len(window) > depth:
            yield window.popleft()
    while window:
        yield window.popleft()


def _require_drop_last_for_sharding(sharding, kwargs: dict) -> None:
    """A mesh sharding needs every batch divisible by the axis size;
    the trailing partial batch generally is not — demand an explicit
    drop_last=True instead of crashing at epoch end."""
    if sharding is not None and not kwargs.get("drop_last"):
        raise ValueError(
            "iter_jax_batches(sharding=...) requires drop_last=True: "
            "the final partial batch is generally not divisible by the "
            "mesh axis and jax.device_put would fail at epoch end")


class DataIterator:
    """Per-consumer shard stream (reference: data/iterator.py DataIterator
    returned by streaming_split). Picklable — holds only the coordinator
    actor handle — so Train can ship one into each worker actor."""

    def __init__(self, coordinator, consumer_id: int):
        self._coord = coordinator
        self._id = consumer_id

    def _iter_block_refs(self):
        api.get(self._coord.reset.remote(self._id))
        while True:
            ref = api.get(self._coord.next_block.remote(self._id))
            if ref is None:
                return
            yield ref

    def iter_batches(self, *, batch_size: Optional[int] = 256,
                     batch_format: str = "numpy",
                     drop_last: bool = False,
                     prefetch_batches: Optional[int] = None) -> Iterator:
        # Pull coordinator assignments `prefetch_batches` ahead of
        # consumption so the next block is in flight during compute.
        if prefetch_batches is None:
            prefetch_batches = DataContext.get_current().prefetch_batches
        blocks = iter_blocks(
            ((ref, -1) for ref in self._iter_block_refs()),
            prefetch=prefetch_batches)
        return batches_from_blocks(blocks, batch_size, batch_format,
                                   drop_last)

    def iter_rows(self) -> Iterator:
        for batch in self.iter_batches(batch_size=None):
            yield from B.block_to_rows(B.from_batch_format(batch))

    def iter_torch_batches(self, **kwargs):
        import torch
        for batch in self.iter_batches(
                batch_format="numpy",
                **{k: v for k, v in kwargs.items()
                   if k in ("batch_size", "drop_last")}):
            yield {k: torch.as_tensor(v) for k, v in batch.items()}

    def iter_jax_batches(self, *, device=None, device_prefetch: int = 2,
                         sharding=None, **kwargs):
        """Device-resident shard feed for train workers (same contract
        as Dataset.iter_jax_batches): upload latency hides behind the
        worker's jitted step."""
        _require_drop_last_for_sharding(sharding, kwargs)
        batches = self.iter_batches(
            batch_format="numpy",
            **{k: v for k, v in kwargs.items()
               if k in ("batch_size", "drop_last", "prefetch_batches")})
        return jax_device_feed(batches, device=device, sharding=sharding,
                               device_prefetch=device_prefetch)

    def materialize(self):
        """Collect this shard into a list of blocks (mostly for tests)."""
        return list(iter_blocks((r, -1) for r in self._iter_block_refs()))


def make_split_iterators(bundles: List[StreamedBundle], n: int,
                         equal: bool) -> List[DataIterator]:
    coord = _SplitCoordinator.remote(
        [(ref, rows) for ref, rows in bundles], n, equal)
    return [DataIterator(coord, i) for i in range(n)]
