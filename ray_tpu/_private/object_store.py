"""Shared-memory host object store (plasma equivalent).

TPU-native analogue of the reference's per-node plasma store
(src/ray/object_manager/plasma/: ObjectStore, PlasmaAllocator over mmap'd
files). Instead of a store daemon + fd-passing protocol (plasma's fling.cc),
every process maps objects directly from files under ``/dev/shm`` — the same
backing plasma uses — named by object id. Creation/seal/free bookkeeping lives
with the owner (driver) which is the single writer of the directory, so no
cross-process allocator lock is needed.

Zero-copy: readers mmap the file and deserialize with out-of-band buffers
aliasing the mapping (serialization.py), so a numpy array "read" from the
store shares pages with the writer. ``mmap.close()`` raises BufferError while
aliased views are live, which we use as the pinning mechanism (plasma's
client-side pin, object_lifecycle_manager.cc, done by the OS for free).
"""

from __future__ import annotations

import mmap
import os
import threading
import time
from typing import Any, Dict, List, Optional

from ..exceptions import ObjectStoreFullError
from ..util import tracing
from . import fault
from . import lockdep
from . import racedebug
from . import serialization
from . import telemetry
from .ids import ObjectID

from .config import ray_config

def inline_threshold() -> int:
    """Objects at or below this size are kept inline in the owner's
    memory store and shipped inside control messages, like the
    reference's in-memory store for inlined small returns
    (core_worker/store_provider/memory_store). Overridable via
    RAY_TPU_INLINE_OBJECT_MAX_BYTES or ray_config.set(
    "inline_object_max_bytes", ...) — read per call so runtime
    overrides take effect."""
    return int(ray_config.inline_object_max_bytes)


def escalated_spill(store, need: int) -> int:
    """Owner-side response to a worker's full-arena escalation (see
    create()'s request_spill): free ~2x the requested bytes — slack for
    concurrent creates — never the whole arena. One policy shared by
    the head (runtime.py) and per-node daemons (daemon.py)."""
    if fault.enabled:
        fault.fire("store.spill", need=int(need))
    used = store.stats().get("used_bytes", 0)
    return store.spill_objects(max(0, used - 2 * int(need)))


def _put_gate(size: int, prefaulted: bool = False):
    """Host-wide admission gate for big puts, shared by BOTH store
    backends: concurrent first-touch of fresh tmpfs pages from multiple
    processes collapses superlinearly on small hosts (kernel shmem
    allocation contention), so copies above the threshold go through
    netcomm's bandwidth-aware HostCopyGate — up to gate-width copies
    overlap (multi-core hosts), excess waiters admit FIFO (the old
    exclusive lock serialized EVERY multi-client put; the old ungated
    file-store path thrashed instead).

    Two bypasses keep the gate metering ONLY genuinely overlapping
    page-allocation storms: writes into `prefaulted` (pool-recycled)
    segments touch no fresh pages and run ungated whatever their size,
    and puts under ``host_copy_gate_min_bytes`` skip ticket
    acquisition entirely — a ticket round trip would dominate a small
    copy (the counter-proven small-put contract, tests/test_put_path)."""
    from .config import ray_config
    if prefaulted or size < int(ray_config.host_copy_gate_min_bytes):
        from .netcomm import _NullGate
        return _NullGate()
    thresh = float(ray_config.transfer_serialize_threshold_mb)
    if thresh > 0 and size >= thresh * (1 << 20):
        from .netcomm import _host_copy_gate
        return _host_copy_gate
    from .netcomm import _NullGate
    return _NullGate()


def _default_capacity(store_dir: str = "/dev/shm") -> int:
    """Default store capacity: a fraction of what the store's filesystem
    has free (reference defaults plasma to 30% of system memory,
    ray_config_def.h object_store_memory;
    RAY_TPU_OBJECT_STORE_MEMORY_FRACTION overrides) — bounded by what the
    machine lets one process map. A sandbox's tmpfs can report more room
    than the machine has memory (the v5e machine: 95G of /dev/shm on 45
    GiB), and every process maps the arena twice (the C side and its own
    zero-copy view), so the bounds are physical memory, an eighth of a
    finite RLIMIT_AS, and a finite RLIMIT_FSIZE."""
    import resource
    try:
        st = os.statvfs(store_dir)
        room = st.f_bsize * st.f_bavail
    except OSError:
        return 2 << 30
    try:
        room = min(room, os.sysconf("SC_PHYS_PAGES")
                   * os.sysconf("SC_PAGE_SIZE"))
    except (ValueError, OSError):
        pass
    cap = int(room * float(ray_config.object_store_memory_fraction))
    vm_limit = resource.getrlimit(resource.RLIMIT_AS)[0]
    if vm_limit != resource.RLIM_INFINITY:
        cap = min(cap, vm_limit // 8)
    file_limit = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    if file_limit != resource.RLIM_INFINITY:
        cap = min(cap, file_limit)
    return cap


# ---------------------------------------------------------------------------
# zero-copy put path (ISSUE 17): reserve -> write-in-place -> seal.
# ---------------------------------------------------------------------------

# Always-on op counter for the flag-off zero-work guard: with
# store_zero_copy_put_enabled=false this must never move (the staging
# path does not touch the in-place machinery at all).
_inplace_puts = 0


def inplace_put_ops() -> int:
    """Process-wide count of puts that took the in-place (zero-copy)
    write path."""
    return _inplace_puts


_nt_copy = None  # tri-state: None = unresolved, False = unavailable


def _nt(dst: memoryview, src) -> bool:
    """Native NT-store copy with graceful degradation (callers fall
    back to a plain slice copy on False)."""
    global _nt_copy
    if _nt_copy is None:
        try:
            from .. import _native
            _nt_copy = _native.nt_copy if _native.available() else False
        except Exception:  # lint: broad-except-ok native build absent/broken: the pure-Python copy is always correct
            _nt_copy = False
    return _nt_copy(dst, src) if _nt_copy else False


def copy_into(dst: memoryview, off: int, data) -> int:
    """Copy one payload into `dst` at `off` with non-temporal stores
    when the native primitive is available (a put destination is
    written once and read much later from another process — caching
    the lines is pure write-allocate waste below glibc's NT
    threshold). Returns the bytes copied. Shared by the put path and
    the transfer-plane chunk receiver."""
    mv = data if isinstance(data, memoryview) else memoryview(data)
    if mv.format != "B" or mv.ndim != 1:
        mv = mv.cast("B")
    n = mv.nbytes
    dst_slice = dst[off:off + n]
    try:
        if not _nt(dst_slice, mv):
            dst_slice[:] = mv
    finally:
        dst_slice.release()
    return n


class _Reservation:
    """One reserved file-store segment: the caller writes through
    ``view()`` then calls exactly one of ``seal()`` / ``abort()``
    (ref-discipline: reserve/seal helpers are registered conservation
    obligations — devtools/lint/registry.py RESERVE_SEAL_METHODS)."""

    __slots__ = ("_store", "object_id", "size", "_mm", "prefaulted")

    def __init__(self, store, object_id: ObjectID, size: int, mm,
                 prefaulted: bool):
        self._store = store
        self.object_id = object_id
        self.size = size
        self._mm = mm
        # True => every page of the segment is already faulted (pool
        # recycle): the write can skip HostCopyGate admission.
        self.prefaulted = prefaulted

    def view(self) -> memoryview:
        return memoryview(self._mm)

    def seal(self) -> None:
        self._store.seal(self.object_id)

    def abort(self) -> None:
        self._store._abort_reserve(self.object_id)


class _ArenaReservation:
    """Arena-backend reservation: wraps the two-phase create view.
    Arena slots may recycle already-faulted pages, but the shared
    header gives no way to know — so arena writes keep today's gate
    policy (prefaulted=False)."""

    __slots__ = ("_store", "object_id", "size", "_view", "prefaulted")

    def __init__(self, store, object_id: ObjectID, size: int, view):
        self._store = store
        self.object_id = object_id
        self.size = size
        self._view = view
        self.prefaulted = False

    def view(self) -> memoryview:
        return self._view

    def seal(self) -> None:
        self._store.seal(self.object_id)

    def abort(self) -> None:
        self._store._abort_reserve(self.object_id)


def put_in_place(store, object_id: ObjectID,
                 sobj: serialization.SerializedObject) -> int:
    """The zero-copy put shared by both backends: size the payload
    (already done by the pickle-5 out-of-band pass in serialize()),
    reserve the segment FIRST, write the header in place, then land
    each out-of-band buffer at its final offset with exactly one
    NT-store copy — no intermediate bytes object, no staging buffer,
    and no gate ticket unless the write actually faults fresh pages.

    The ``store:put`` span records where a slow put spent its time
    (reserve vs copy vs seal) — the phases dict is captured by
    reference, so the values recorded in the finally-block are the
    final ones."""
    size = sobj.total_size
    phases: Dict[str, float] = {}
    timed = tracing.enabled
    cm = tracing.span("store:put", nbytes=size, phases=phases) \
        if tracing.enabled else None
    with cm if cm is not None else _null_cm():
        t0 = time.perf_counter() if timed else 0.0
        res = store.reserve(object_id, size)
        t1 = time.perf_counter() if timed else 0.0
        try:
            with _put_gate(size, prefaulted=res.prefaulted):
                if fault.enabled:
                    fault.fire("store.put",
                               object_id=object_id.hex(), size=size)
                view = res.view()
                try:
                    for (off, _blen), b in zip(
                            sobj.write_header_into(view), sobj.buffers):
                        copy_into(view, off, b)
                finally:
                    view.release()
        except BaseException:
            res.abort()
            raise
        t2 = time.perf_counter() if timed else 0.0
        res.seal()
        if timed:
            t3 = time.perf_counter()
            phases["reserve_us"] = round((t1 - t0) * 1e6, 1)
            phases["copy_us"] = round((t2 - t1) * 1e6, 1)
            phases["seal_us"] = round((t3 - t2) * 1e6, 1)
    global _inplace_puts
    _inplace_puts += 1
    if telemetry.enabled:
        telemetry.record_put_bytes(size)
    return size


class _null_cm:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _PoolStripe:
    """One stripe of the segment pool. Writers hash to a stripe by
    thread id, so N concurrent put() threads claim recycled segments
    from N disjoint free lists under N independent locks — the store
    lock is never held across the claim's rename/open/mmap syscalls.
    Stripe locks are LEAF locks: a thread holds at most one stripe
    lock at a time (steal scans visit stripes sequentially), and the
    only compound order is store._lock -> stripe (free() pooling),
    never the reverse."""

    __slots__ = ("lock", "cache", "bytes")

    def __init__(self):
        self.lock = lockdep.lock("object_store.pool_stripe")
        # Entries [size, filename, mm_or_None]: mm is a kept-hot
        # mapping (pages faulted AND page-table entries live) when the
        # segment was freed with no exported views; None means the
        # claimer re-opens/mmaps (pages still faulted in tmpfs — only
        # the PTEs are rebuilt, which is minor-fault cheap).
        self.cache: List[list] = []
        self.bytes = 0


class _Segment:
    __slots__ = ("path", "mm", "size", "file_exists", "sealed",
                 "counted", "last_access", "spilling")

    def __init__(self, path: str, mm: mmap.mmap, size: int,
                 sealed: bool = False, counted: bool = True):
        self.path = path
        self.mm = mm
        self.size = size
        self.file_exists = True
        self.sealed = sealed          # writer done; safe to spill
        self.counted = counted        # participates in capacity accounting
        self.last_access = 0          # LRU clock tick for spill ordering
        self.spilling = False         # staged remote-spill write in flight


class ObjectStore:
    """Maps object ids to shm segments; every process has one client instance.

    The owner process (driver) additionally enforces capacity. Workers create
    segments for task returns and the owner adopts the accounting when the
    task reply arrives.
    """

    def __init__(self, session_dir: str, capacity: Optional[int] = None):
        self._dir = session_dir
        os.makedirs(session_dir, exist_ok=True)
        self._capacity = capacity or _default_capacity(session_dir)
        self._segments: Dict[ObjectID, _Segment] = {}
        self._used = 0
        self._graveyard = []  # mmaps with live exported buffers
        self._lock = lockdep.rlock("object_store.file_store")
        # Spilling (reference: LocalObjectManager spill/restore,
        # raylet/local_object_manager.cc): sealed objects move from shm to
        # a disk directory derived from the store dir — deterministic, so
        # any process of the session can restore without coordination.
        self._spill_dir = session_dir.rstrip("/") + "_spill"
        self._spill = _SpillTarget(self._spill_dir)
        self._spilled_bytes = 0
        self._spilled_count = 0
        self._restored_count = 0
        self._access_clock = 0
        # Objects mid-free: the spill delete runs OUTSIDE the store lock
        # (it can be a remote round trip), so a concurrent get() must not
        # resurrect the object from its still-present spill file.
        # Refcounted (not a set): two concurrent free()s of one id must
        # keep the tombstone until BOTH unlocked deletes finish.
        self._freeing: Dict[ObjectID, int] = {}
        # Segment pool: freed sealed segments are RENAMED here (size-
        # encoded names) and re-claimed by _reserve, so hot put loops
        # write into already-faulted tmpfs pages instead of paying
        # kernel shmem page allocation per put (the arena backend gets
        # the same effect from its slab recycler). The dir is shared by
        # every process of the node; claims are atomic renames.
        # Striped per-client reservation (ISSUE 17): the free list is
        # split into store_put_stripes independent stripes so parallel
        # writers never serialize on one pool lock.
        self._pool_dir = session_dir.rstrip("/") + "_pool"
        self._stripes = tuple(
            _PoolStripe()
            for _ in range(max(1, int(ray_config.store_put_stripes))))
        self._pool_seq = 0
        self._pool_hits = 0
        self._pool_misses = 0
        self._pool_reclaimed = 0
        # RAY_TPU_STORE_AUDIT=1: per-object charge ledger mirroring
        # _used, so a full-store error can name the oids whose bytes
        # were charged but whose segments are gone (accounting leaks).
        self._audit: Optional[Dict[ObjectID, list]] = \
            {} if os.environ.get("RAY_TPU_STORE_AUDIT") else None

    def _charge(self, object_id: ObjectID, delta: int, tag: str) -> None:
        if self._audit is None:
            return
        ent = self._audit.setdefault(object_id, [0, ""])
        ent[0] += delta
        ent[1] = tag

    def _audit_report_locked(self) -> str:
        if self._audit is None:
            return ""
        leaks: Dict[str, list] = {}
        for oid, (net, tag) in self._audit.items():
            if net > 0 and oid not in self._segments:
                b = leaks.setdefault(tag, [0, 0])
                b[0] += 1
                b[1] += net
        return " audit[" + " ".join(
            f"{t}:n={n} b={b}" for t, (n, b) in sorted(leaks.items())
        ) + "]" if leaks else " audit[clean]"

    # -- paths -------------------------------------------------------------
    def _path(self, object_id: ObjectID) -> str:
        return os.path.join(self._dir, object_id.hex())

    def _spill_path(self, object_id: ObjectID) -> str:
        return os.path.join(self._spill_dir, object_id.hex())

    @property
    def used_bytes(self) -> int:
        return self._used  # lint: guarded-by-ok exposition-time gauge: plain int read feeding heuristics, torn values are harmless

    @property
    def capacity(self) -> int:
        return self._capacity

    # -- segment pool ------------------------------------------------------
    def _pool_limit(self) -> int:
        return int(float(ray_config.store_segment_pool_mb) * (1 << 20))

    def _stripe(self) -> _PoolStripe:
        return self._stripes[threading.get_ident() % len(self._stripes)]

    @property
    def _pool_bytes(self) -> int:
        # Torn reads across stripes are fine: this feeds capacity
        # heuristics, and each stripe's int is GIL-consistent.
        return sum(st.bytes for st in self._stripes)  # lint: guarded-by-ok torn reads across stripes feed capacity heuristics only; each stripe int is GIL-consistent

    @property
    def pool_reclaimed_bytes(self) -> int:
        """Lifetime bytes reclaimed FROM the pool under capacity
        pressure (exported as a node-tagged gauge, telemetry.py)."""
        return self._pool_reclaimed

    def _pool_put(self, seg: _Segment, mm=None) -> bool:
        """Move a freed segment's file into the pool instead of
        unlinking it (the caller has popped the segment). `mm` is a
        still-open mapping to keep hot — reused wholesale on an
        exact-size claim so the next put of this shape pays zero
        faults. False => the caller unlinks (and closes mm) as
        before."""
        if seg.size < int(ray_config.store_segment_pool_min_bytes):
            return False
        limit = self._pool_limit()
        if limit <= 0 or self._pool_bytes + seg.size > limit:
            return False
        with self._lock:
            self._pool_seq += 1
            seq = self._pool_seq
        name = f"{seg.size}-{os.getpid()}-{seq}"
        try:
            os.makedirs(self._pool_dir, exist_ok=True)
            os.rename(seg.path, os.path.join(self._pool_dir, name))
        except OSError:
            return False
        st = self._stripe()
        with st.lock:
            st.cache.append([seg.size, name, mm])
            st.bytes += seg.size
        return True

    def _rescan_pool(self) -> bool:
        """Reconcile every stripe against the shared pool dir — a
        sibling process (the owner freeing this worker's returns) may
        have pooled files this instance never saw, or claimed files a
        stripe still lists. Locks ONE stripe at a time (no compound
        stripe-stripe hold)."""
        try:
            names = os.listdir(self._pool_dir)
        except OSError:
            names = []
        nameset = set(names)
        found = False
        n = len(self._stripes)
        for st in self._stripes:
            with st.lock:
                keep = []
                total = 0
                for ent in st.cache:
                    if ent[1] in nameset:
                        nameset.discard(ent[1])
                        keep.append(ent)
                        total += ent[0]
                    elif ent[2] is not None:
                        # Claimed out from under us by a sibling: the
                        # inode now backs THEIR object. A kept mapping
                        # has no exports (free() probed), so close
                        # cannot raise.
                        ent[2].close()
                st.cache = keep
                st.bytes = total
                found = found or bool(keep)
        for name in nameset:
            try:
                sz = int(name.split("-", 1)[0])
            except ValueError:
                continue
            st = self._stripes[hash(name) % n]
            with st.lock:
                st.cache.append([sz, name, None])
                st.bytes += sz
            found = True
        return found

    def _claim_from_stripe(self, st: _PoolStripe, size: int,
                           dst_path: str, want_mm: bool):
        """Best-fit claim from one stripe: rename the pooled file onto
        the new object's path (atomic — a lost cross-process race is
        ENOENT and the next candidate is tried). Returns ("hot", mm)
        for an exact-size kept-hot mapping (want_mm only), ("fd", fd)
        with the fd truncated to `size`, or None."""
        with st.lock:
            while True:
                best = None
                for ent in st.cache:
                    if ent[0] >= size and (best is None
                                           or ent[0] < best[0]):
                        best = ent
                if best is None:
                    return None
                st.cache.remove(best)
                st.bytes -= best[0]
                bsize, name, mm = best
                src = os.path.join(self._pool_dir, name)
                try:
                    os.rename(src, dst_path)
                except OSError:
                    if mm is not None:
                        mm.close()
                    continue  # lost the claim race; next candidate
                if mm is not None:
                    if want_mm and bsize == size:
                        return ("hot", mm)
                    mm.close()
                try:
                    fd = os.open(dst_path, os.O_RDWR)
                    os.ftruncate(fd, size)
                    return ("fd", fd)
                except OSError:
                    try:
                        os.unlink(dst_path)
                    except OSError:
                        pass
                    return None

    def _pool_claim(self, size: int, dst_path: str,
                    want_mm: bool = False):
        """Claim a pooled segment: own stripe first (the hot loop —
        a put/free cycle on one thread stays on one free list), then
        steal from the others, then rescan the shared dir once and
        retry. Never holds two stripe locks at once."""
        if self._pool_limit() <= 0 \
                or size < int(ray_config.store_segment_pool_min_bytes):
            return None
        n = len(self._stripes)
        me = threading.get_ident() % n
        for attempt in (0, 1):
            for i in range(n):
                got = self._claim_from_stripe(
                    self._stripes[(me + i) % n], size, dst_path, want_mm)
                if got is not None:
                    return got
            if attempt == 0 and not self._rescan_pool():
                return None
        return None

    def _drain_pool_locked(self, need_bytes: int) -> int:
        """Capacity pressure reclaims pooled bytes BEFORE touching live
        objects — pool files are pure cache. Caller holds _lock
        (lock order _lock -> stripe)."""
        self._rescan_pool()
        freed = 0
        for st in self._stripes:
            if freed >= need_bytes:
                break
            with st.lock:
                while st.cache and freed < need_bytes:
                    sz, name, mm = st.cache.pop()
                    st.bytes -= sz
                    if mm is not None:
                        mm.close()
                    try:
                        os.unlink(os.path.join(self._pool_dir, name))
                    except OSError:
                        continue
                    freed += sz
        if freed:
            self._pool_reclaimed += freed
        return freed

    # -- write path --------------------------------------------------------
    def _admit(self, object_id: ObjectID, size: int) -> None:
        """Capacity admission only: drain pool, evict graveyard, spill
        LRU until `size` fits, then register the unsealed segment and
        charge the accounting. This is the ONLY part of a reservation
        that needs the store lock — the file create / pool claim /
        mmap syscalls run outside it on a per-stripe lock, so N
        writers admit in N short critical sections instead of
        serializing their syscalls. Remote spills needed to make room
        are staged OUTSIDE the lock — a multi-second object-storage
        write must not freeze every concurrent store op — and their
        bookkeeping CASes back in before the capacity re-check."""
        staged = None
        orphans: list = []
        while True:
            admitted = False
            with self._lock:
                if object_id in self._segments:
                    # Duplicate reserve of an id this store already
                    # holds (a racing pull/put of the same object).
                    # Replacing the entry would orphan the original's
                    # accounting and the caller's O_EXCL open would
                    # abort-unlink the REAL object's file — refuse
                    # before touching anything instead.
                    raise FileExistsError(object_id.hex())
                if staged is not None:
                    self._commit_staged_spill_locked(staged, orphans)
                    staged = None
                if self._used + self._pool_bytes + size > self._capacity:
                    self._drain_pool_locked(
                        self._used + self._pool_bytes + size
                        - self._capacity)
                if self._used + size > self._capacity:
                    self._collect_graveyard()
                    if self._used + size > self._capacity:
                        self._spill_locked(
                            self._used + size - self._capacity)
                    if self._used + size > self._capacity:
                        staged = self._stage_remote_spill_locked(
                            self._used + size - self._capacity)
                        if staged is None:
                            raise ObjectStoreFullError(
                                f"Object of {size} bytes does not fit: "
                                f"{self._used}/{self._capacity} bytes "
                                f"used ({self._spilled_bytes} spilled; "
                                f"{self._segment_census_locked()}"
                                f"{self._audit_report_locked()})."
                            )
                if staged is None:
                    # mm attaches lazily on first read (_open handles
                    # mm=None).
                    if racedebug.enabled:
                        racedebug.access(self, "_segments", write=True)
                    self._segments[object_id] = _Segment(
                        self._path(object_id), None,  # type: ignore[arg-type]
                        size)
                    self._used += size
                    self._charge(object_id, size, "admit")
                    admitted = True
            if orphans:
                # Spill copies of objects freed mid-write: delete
                # outside the lock (remote round trips).
                for oid_hex in orphans:
                    self._spill.delete(oid_hex)
                orphans = []
            if admitted:
                return
            self._write_staged_spill(staged)

    def _reserve(self, object_id: ObjectID, size: int) -> int:
        """Legacy (staging-path) reserve: admit, then pool-claim or
        create the shm file. Returns the open fd; callers write then
        seal (or _abort_reserve on failure)."""
        self._admit(object_id, size)
        try:
            claimed = self._pool_claim(size, self._path(object_id))
            if claimed is not None:
                return claimed[1]
            return os.open(self._path(object_id),
                           os.O_CREAT | os.O_RDWR | os.O_EXCL, 0o600)
        except FileExistsError:
            # Another process created this object between our admit and
            # open: roll back the accounting, leave their file alone.
            self._abort_reserve(object_id, unlink=False)
            raise
        except BaseException:
            self._abort_reserve(object_id)
            raise

    def reserve(self, object_id: ObjectID, size: int) -> _Reservation:
        """Zero-copy put protocol, step 1 of 3 (reserve / write-in-
        place via view() / seal-or-abort): admit under the store lock,
        then claim a recycled segment from this thread's pool stripe —
        hot (exact-size kept mapping: zero faults) or warm (re-mmap a
        pooled file: minor faults only) — falling back to a fresh
        create (major faults; the only case the HostCopyGate still
        meters). Ref-discipline: the returned reservation carries a
        seal-or-abort obligation (lint check_reserve_pairing)."""
        self._admit(object_id, size)
        hit = False
        try:
            mm = None
            claimed = self._pool_claim(size, self._path(object_id),
                                       want_mm=True)
            if claimed is not None:
                hit = True
                kind, val = claimed
                if kind == "hot":
                    mm = val
                else:
                    try:
                        mm = mmap.mmap(val, size)
                    finally:
                        os.close(val)
            else:
                fd = os.open(self._path(object_id),
                             os.O_CREAT | os.O_RDWR | os.O_EXCL, 0o600)
                try:
                    os.ftruncate(fd, size)
                    mm = mmap.mmap(fd, size)
                finally:
                    os.close(fd)
        except FileExistsError:
            # O_EXCL collision with another process's live object:
            # roll back accounting only, never unlink their file.
            self._abort_reserve(object_id, unlink=False)
            raise
        except BaseException:
            self._abort_reserve(object_id)
            raise
        with self._lock:
            seg = self._segments.get(object_id)
            if seg is not None:
                seg.mm = mm
            if hit:
                self._pool_hits += 1
            else:
                self._pool_misses += 1
        if telemetry.enabled:
            telemetry.record_pool_claim(hit)
        return _Reservation(self, object_id, size, mm, prefaulted=hit)

    def _abort_reserve(self, object_id: ObjectID,
                       unlink: bool = True):
        """Roll back a failed write: no partial file may remain, or a
        reader would mmap truncated data as if sealed. Closes any
        writer-side mapping the reservation attached (the failed
        writer released its view before aborting, so exports are gone;
        graveyard otherwise). ``unlink=False`` when the failure was an
        O_EXCL collision with a file ANOTHER process created — that
        file is a live object this writer must not destroy."""
        with self._lock:
            seg = self._segments.pop(object_id, None)
            if seg is not None:
                self._used -= seg.size
                self._charge(object_id, -seg.size, "abort")
                if seg.mm is not None:
                    try:
                        seg.mm.close()
                    except BufferError:
                        self._graveyard.append(seg.mm)
            if unlink:
                try:
                    os.unlink(self._path(object_id))
                except OSError:
                    pass

    def create(self, object_id: ObjectID, size: int) -> memoryview:
        """Allocate a segment and return a writable view (then `seal`)."""
        if bool(ray_config.store_zero_copy_put_enabled):
            return self.reserve(object_id, size).view()
        fd = self._reserve(object_id, size)
        try:
            os.ftruncate(fd, size)
            mm = mmap.mmap(fd, size)
        except BaseException:
            os.close(fd)
            self._abort_reserve(object_id)
            raise
        os.close(fd)
        with self._lock:
            seg = self._segments.get(object_id)
            if seg is not None:
                seg.mm = mm
        return memoryview(mm)

    def put_serialized(self, object_id: ObjectID,
                       sobj: serialization.SerializedObject) -> int:
        """Write path. Zero-copy (default): reserve the segment first,
        write header + out-of-band buffers straight into the mapping —
        one NT-store copy per buffer, no staging bytes (put_in_place).
        Legacy (store_zero_copy_put_enabled=false): plain write(2)
        into the shm file through write_to_fd's staging header.
        Big fresh-page writes go through the host copy gate: N
        multi-client puts admitted concurrently up to the host's
        page-allocation bandwidth instead of thrashing it (this path
        used to run ungated — measured ~3x aggregate collapse at 4-way
        on a 1-core box).
        """
        if bool(ray_config.store_zero_copy_put_enabled):
            return put_in_place(self, object_id, sobj)
        size = sobj.total_size
        with _put_gate(size):
            fd = self._reserve(object_id, size)
            try:
                sobj.write_to_fd(fd)
            except BaseException:
                os.close(fd)
                self._abort_reserve(object_id)
                raise
            os.close(fd)
        self.seal(object_id)
        if telemetry.enabled:
            telemetry.record_put_bytes(size)
        return size

    def seal(self, object_id: ObjectID):
        """Writer done: the object becomes immutable and spillable
        (plasma's seal, object_store.cc)."""
        with self._lock:
            seg = self._segments.get(object_id)
            if seg is not None:
                seg.sealed = True

    def put(self, object_id: ObjectID, value: Any) -> int:
        return self.put_serialized(object_id, serialization.serialize(value))

    # -- spill path --------------------------------------------------------
    def _spill_locked(self, need_bytes: int) -> int:
        """Move LRU sealed objects from shm to disk until `need_bytes` are
        reclaimed (reference: LocalObjectManager::SpillObjects; eviction
        order per eviction_policy.cc LRU). Copy-then-rename-then-unlink so
        concurrent readers in other processes always find either the shm
        file or a complete spill file. Returns bytes reclaimed."""
        from .config import ray_config
        if not bool(ray_config.object_spilling_enabled):
            return 0
        if self._spill.remote:
            # Remote spill I/O never runs under the store lock: callers
            # stage candidates (_stage_remote_spill_locked), write
            # outside, and CAS the bookkeeping back in.
            return 0
        candidates = self._spill_candidates_locked()
        reclaimed = 0
        os.makedirs(self._spill_dir, exist_ok=True)
        for _, oid, seg in candidates:
            if reclaimed >= need_bytes:
                break
            try:
                dst = self._spill_path(oid)
                tmp = dst + ".tmp"
                try:
                    import shutil
                    shutil.copyfile(seg.path, tmp)
                    os.rename(tmp, dst)
                except FileNotFoundError:
                    # Shm file already gone: a co-resident process
                    # (typically the adopting owner's LRU) spilled or
                    # freed this object and unlinked the file. The
                    # bytes left tmpfs then — drop the stale segment
                    # and reclaim the phantom accounting, or this
                    # store believes it is full forever while holding
                    # nothing (reads resolve via the spill file or the
                    # freed-object path either way).
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    seg.file_exists = False
                    self._segments.pop(oid, None)
                    self._used -= seg.size
                    self._charge(oid, -seg.size, "phantom")
                    reclaimed += seg.size
                    if seg.mm is not None:
                        try:
                            seg.mm.close()
                        except BufferError:
                            self._graveyard.append(seg.mm)
                    continue
                except OSError:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    raise
                try:
                    os.unlink(seg.path)
                except FileNotFoundError:
                    pass  # raced with a co-resident spill of the same id
            except Exception:
                continue
            seg.file_exists = False
            self._segments.pop(oid, None)
            self._used -= seg.size
            self._charge(oid, -seg.size, "spill")
            self._spilled_bytes += seg.size
            self._spilled_count += 1
            reclaimed += seg.size
            if seg.mm is not None:
                try:
                    seg.mm.close()
                except BufferError:
                    self._graveyard.append(seg.mm)
        return reclaimed

    def _segment_census_locked(self) -> str:
        """Why is the store full? One line for ObjectStoreFullError:
        bytes by segment state, so the unspillable mass is visible."""
        buckets: Dict[str, int] = {}
        for seg in self._segments.values():
            if not seg.sealed:
                k = "unsealed"
            elif not seg.counted:
                k = "uncounted"
            elif not seg.file_exists:
                k = "fileless"
            elif seg.spilling:
                k = "spilling"
            else:
                k = "spillable"
            buckets[k] = buckets.get(k, 0) + seg.size
        return " ".join(f"{k}={v}" for k, v in sorted(buckets.items()))

    def _spill_candidates_locked(self):
        from .config import ray_config
        candidates = [
            (seg.last_access, oid, seg)
            for oid, seg in self._segments.items()
            if seg.sealed and seg.counted and seg.file_exists
            and not seg.spilling
            and seg.size >= int(ray_config.min_spilling_size)
        ]
        candidates.sort(key=lambda t: t[0])
        return candidates

    def _stage_remote_spill_locked(self, need_bytes: int):
        """Pick remote-spill candidates and mark them in flight; the
        object-storage writes run OUTSIDE the lock
        (_write_staged_spill) and the bookkeeping CASes back in
        (_commit_staged_spill_locked). None => no progress possible."""
        from .config import ray_config
        if not self._spill.remote \
                or not bool(ray_config.object_spilling_enabled):
            return None
        staged = []
        picked = 0
        for _, oid, seg in self._spill_candidates_locked():
            if picked >= need_bytes:
                break
            seg.spilling = True
            staged.append({"oid": oid, "seg": seg, "ok": False})
            picked += seg.size
        return staged or None

    def _write_staged_spill(self, staged) -> None:
        """The unlocked half of a staged remote spill: stream each
        candidate's shm file to the spill target. A concurrent free()
        is safe — it unlinks the path but our open fd keeps the inode,
        and the commit detects the popped segment and drops the orphan
        spill copy."""
        for ent in staged:
            try:
                self._spill.write_file(ent["oid"].hex(),
                                       ent["seg"].path)
                ent["ok"] = True
            except Exception:  # lint: broad-except-ok staged spill write failed (target down, file freed): the commit skips it and capacity pressure re-resolves
                pass

    def _commit_staged_spill_locked(self, staged, orphans) -> int:
        """CAS the staged writes' bookkeeping back under the lock. A
        segment freed (or already replaced) while its write was in
        flight contributes an orphan spill key for the caller to
        delete OUTSIDE the lock. Returns bytes reclaimed."""
        reclaimed = 0
        for ent in staged:
            oid, seg = ent["oid"], ent["seg"]
            seg.spilling = False
            if not ent["ok"]:
                continue
            if self._segments.get(oid) is not seg or not seg.file_exists:
                orphans.append(oid.hex())
                continue
            try:
                os.unlink(seg.path)
            except OSError:
                pass
            seg.file_exists = False
            self._segments.pop(oid, None)
            if seg.counted:
                self._used -= seg.size
                self._charge(oid, -seg.size, "rspill")
            self._spilled_bytes += seg.size
            self._spilled_count += 1
            reclaimed += seg.size
            if seg.mm is not None:
                try:
                    seg.mm.close()
                except BufferError:
                    self._graveyard.append(seg.mm)
        return reclaimed

    def spill_objects(self, target_bytes: int) -> int:
        """Spill until shm usage is at or below `target_bytes` (called by
        the memory monitor under host memory pressure — /dev/shm pages
        count as RAM). Returns bytes reclaimed."""
        staged = None
        with self._lock:
            if self._used <= target_bytes:
                return 0
            reclaimed = self._spill_locked(self._used - target_bytes)
            if self._used > target_bytes:
                staged = self._stage_remote_spill_locked(
                    self._used - target_bytes)
        if staged:
            orphans: list = []
            self._write_staged_spill(staged)
            with self._lock:
                reclaimed += self._commit_staged_spill_locked(
                    staged, orphans)
            for oid_hex in orphans:
                self._spill.delete(oid_hex)
        return reclaimed

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"used_bytes": self._used, "capacity": self._capacity,
                    "spilled_bytes": self._spilled_bytes,
                    "spilled_count": self._spilled_count,
                    "restored_count": self._restored_count,
                    "pool_bytes": self._pool_bytes,
                    "pool_hits": self._pool_hits,
                    "pool_misses": self._pool_misses,
                    "pool_reclaimed_bytes": self._pool_reclaimed,
                    "num_objects": len(self._segments)}

    # -- read path ---------------------------------------------------------
    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            if object_id in self._freeing:
                return False
            return (object_id in self._segments
                    or os.path.exists(self._path(object_id))
                    or self._spill.exists(object_id.hex()))

    def _open(self, object_id: ObjectID) -> _Segment:
        with self._lock:
            self._access_clock += 1
            seg = self._segments.get(object_id)
            if seg is not None and seg.mm is not None:
                seg.last_access = self._access_clock
                return seg
            if object_id in self._freeing:
                # Mid-free: the shm file is already gone and the spill
                # copy is being deleted unlocked — do not resurrect it.
                # OSError subclass: same failure shape a fully-freed
                # object produces (missing backing file).
                raise FileNotFoundError(f"object {object_id.hex()} freed")
            counted = seg is not None  # adopted placeholder keeps accounting
            from_spill = False
            try:
                path = self._path(object_id)
                size = os.path.getsize(path)
                fd = os.open(path, os.O_RDWR)
            except OSError:
                # Spilled (by this or another process — possibly between
                # our getsize and open): restore. Local spills mmap off
                # the page cache; URI spills stream into an anonymous
                # mapping. The object is NOT re-admitted to shm
                # accounting either way.
                from_spill = True
                if self._spill.remote:
                    # Rare under-lock fallback: the staged restore
                    # (_restore_remote_unlocked) normally lands the
                    # mapping before _open_view takes the lock.
                    mm = self._spill.read_mmap(object_id.hex())
                    size = len(mm)
                    path = self._spill_path(object_id)
                    fd = None
                else:
                    path = self._spill_path(object_id)
                    size = os.path.getsize(path)
                    fd = os.open(path, os.O_RDWR)
            if fd is not None:
                try:
                    mm = mmap.mmap(fd, size)
                finally:
                    os.close(fd)
            if seg is None:
                # Readers do not own capacity accounting; only creators do.
                seg = _Segment(path, mm, size, sealed=True, counted=False)
                self._segments[object_id] = seg
            else:  # adopted placeholder: attach the mapping
                seg.mm = mm
                seg.path = path
            if from_spill:
                if counted and seg.counted:
                    # The shm copy is gone; stop counting it.
                    self._used -= seg.size
                    self._charge(object_id, -seg.size, "restore")
                seg.counted = False
                self._restored_count += 1
            seg.last_access = self._access_clock
            return seg

    def _restore_remote_unlocked(self, object_id: ObjectID) -> None:
        """Stage a REMOTE spill restore OUTSIDE the store lock: the
        chunked object-storage read of a cold multi-GB object must not
        serialize every concurrent store op behind it (the owner-side
        LRU would otherwise freeze for the restore's duration). The
        streamed mapping CASes into the segment table; losing the race
        to a concurrent restore or free just drops it."""
        with self._lock:
            seg = self._segments.get(object_id)
            if (seg is not None and seg.mm is not None) \
                    or object_id in self._freeing \
                    or os.path.exists(self._path(object_id)):
                return
        try:
            mm = self._spill.read_mmap(object_id.hex())
        except OSError:
            return  # not spilled after all; _open re-resolves
        with self._lock:
            seg = self._segments.get(object_id)
            if object_id in self._freeing \
                    or (seg is not None and seg.mm is not None):
                mm.close()
                return
            counted = seg is not None
            if seg is None:
                seg = _Segment(self._spill_path(object_id), mm,
                               len(mm), sealed=True, counted=False)
                self._segments[object_id] = seg
            else:
                if counted and seg.counted:
                    # The shm copy is gone; stop counting it.
                    self._used -= seg.size
                    self._charge(object_id, -seg.size, "restore")
                seg.counted = False
                seg.mm = mm
                seg.path = self._spill_path(object_id)
            self._restored_count += 1

    def _open_view(self, object_id: ObjectID) -> memoryview:
        """Open + export a view atomically: the view must be created
        under the lock, so a concurrent spill's mm.close() hits
        BufferError (→ graveyard) instead of invalidating a mapping a
        reader is about to touch."""
        if self._spill.remote:
            self._restore_remote_unlocked(object_id)
        with self._lock:
            return memoryview(self._open(object_id).mm)

    def get(self, object_id: ObjectID) -> Any:
        """Deserialize an object, zero-copy for array buffers."""
        view = self._open_view(object_id)
        if telemetry.enabled:
            telemetry.record_get_bytes(view.nbytes)
        return serialization.deserialize(view)

    def get_raw(self, object_id: ObjectID) -> memoryview:
        return self._open_view(object_id)

    def adopt(self, object_id: ObjectID, size: int):
        """Owner-side accounting for a segment created by another process."""
        with self._lock:
            if object_id not in self._segments:
                self._used += size
                self._charge(object_id, size, "adopt")
                # Lazily opened on first get; record a placeholder w/ size.
                path = self._path(object_id)
                seg = _Segment(path, None, size,  # type: ignore[arg-type]
                               sealed=True)
                self._segments[object_id] = seg

    # -- free path ---------------------------------------------------------
    def free(self, object_id: ObjectID):
        with self._lock:
            # Tombstone BEFORE releasing the lock: the spill delete below
            # runs unlocked, and without this a concurrent _open() could
            # restore the object from its not-yet-deleted spill file and
            # re-insert a segment, breaking free()'s gone-after-free
            # contract.
            self._freeing[object_id] = self._freeing.get(object_id, 0) + 1
            seg = self._segments.pop(object_id, None)
            pooled = False
            if seg is not None:
                if seg.counted:
                    self._used -= seg.size
                    self._charge(object_id, -seg.size, "free")
                live_views = False
                keep_mm = None
                poolable = (seg.file_exists and seg.sealed
                            and not seg.spilling)
                if seg.mm is not None:
                    if poolable and bool(
                            ray_config.store_zero_copy_put_enabled):
                        # Keep-hot candidate: probe for live exported
                        # views WITHOUT closing. mmap.resize refuses
                        # to remap while buffer exports exist, and a
                        # same-size resize is otherwise a no-op — so
                        # BufferError here means exactly "views
                        # alive". A mapping that survives the probe
                        # goes back to the pool still open: the next
                        # exact-size put reuses it with zero faults.
                        try:
                            seg.mm.resize(seg.size)
                            keep_mm = seg.mm
                        except BufferError:
                            self._graveyard.append(seg.mm)
                            live_views = True
                        except (OSError, ValueError):
                            # resize unsupported here (e.g. the map
                            # outlived an ftruncate); fall back to the
                            # plain close-or-graveyard protocol.
                            try:
                                seg.mm.close()
                            except BufferError:
                                self._graveyard.append(seg.mm)
                                live_views = True
                    else:
                        try:
                            seg.mm.close()
                        except BufferError:
                            # Live numpy views alias this mapping; the
                            # OS keeps pages until the map closes.
                            # Retry on future allocations.
                            self._graveyard.append(seg.mm)
                            live_views = True
                # Pool the backing file instead of unlinking — UNLESS
                # views still alias the mapping (a re-claimed inode
                # would rewrite the pages under them: corruption, not
                # just a stale read) or a staged spill is mid-read.
                if poolable and not live_views:
                    pooled = self._pool_put(seg, keep_mm)
                if not pooled and keep_mm is not None:
                    keep_mm.close()  # export probe passed: cannot raise
                seg.file_exists = False
            if not pooled:
                try:
                    os.unlink(self._path(object_id))
                except OSError:
                    pass
        # Spill delete OUTSIDE the store lock: with a remote
        # object_spilling_path this is a filesystem/HTTP round trip, and
        # holding the lock across it would stall every concurrent
        # create/get/contains for its duration.
        try:
            self._spill.delete(object_id.hex())
        finally:
            with self._lock:
                n = self._freeing.get(object_id, 0) - 1
                if n <= 0:
                    self._freeing.pop(object_id, None)
                else:
                    self._freeing[object_id] = n

    def _collect_graveyard(self):
        alive = []
        for mm in self._graveyard:
            try:
                mm.close()
            except BufferError:
                alive.append(mm)
        self._graveyard = alive

    def release(self, object_id: ObjectID):
        """Close a reader-side mapping without freeing the object.

        On a segment this store CREATED (counted=True), a cluster-wide
        RELEASE_OBJECTS is this process's only teardown signal — the
        owner daemon free()s its own copy but creators only ever hear
        `release`. Popping the entry without discharging the admit
        charge leaves `_used` permanently inflated (a phantom-full
        store that can never spill its way out), so counted segments
        take the full free() path instead."""
        counted = False
        with self._lock:
            seg = self._segments.get(object_id)
            if seg is None:
                return
            if seg.counted:
                counted = True
            else:
                self._segments.pop(object_id, None)
                if seg.mm is not None:
                    try:
                        seg.mm.close()
                    except BufferError:
                        self._graveyard.append(seg.mm)
        if counted:
            self.free(object_id)

    def shutdown(self):
        import shutil
        with self._lock:
            for oid in list(self._segments):
                self.free(oid)
            self._collect_graveyard()
            # Kept-hot pool mappings hold the tmpfs inodes alive past
            # the rmtree below; drop them first.
            for st in self._stripes:
                with st.lock:
                    for ent in st.cache:
                        if ent[2] is not None:
                            ent[2].close()
                    st.cache = []
                    st.bytes = 0
            # Files written by workers that never reported back (crashes)
            # are not in _segments; sweep the whole session dir.
            shutil.rmtree(self._dir, ignore_errors=True)
            shutil.rmtree(self._pool_dir, ignore_errors=True)
            self._spill.cleanup()


class _SpillTarget:
    """Spill-location seam (reference: object spilling to URIs incl.
    S3 — src/ray/raylet/local_object_manager.* + the spill-worker IO
    protocol, configured via object_spilling_config). The default is
    the session-local directory (plain file ops + mmap restore); a
    `ray_config.object_spilling_path` URI routes writes through
    pyarrow.fs, so TPU VMs with small local disks can spill to
    file://, gs://, or s3:// targets."""

    def __init__(self, local_dir: str):
        self.local_dir = local_dir
        self._fs = None
        self._base = None
        self._base_made = False
        uri = str(getattr(ray_config, "object_spilling_path", "") or "")
        if uri:
            import pyarrow.fs as pafs
            self._fs, base = pafs.FileSystem.from_uri(uri)
            # Session-unique subdir: concurrent clusters sharing one
            # bucket must not collide.
            self._base = base.rstrip("/") + "/" + os.path.basename(
                local_dir.rstrip("/"))

    @property
    def remote(self) -> bool:
        return self._fs is not None

    def _key(self, oid_hex: str) -> str:
        return f"{self._base}/{oid_hex}"

    def write(self, oid_hex: str, view) -> None:
        if self._fs is None:
            os.makedirs(self.local_dir, exist_ok=True)
            dst = os.path.join(self.local_dir, oid_hex)
            tmp = dst + ".tmp"
            try:
                with open(tmp, "wb") as f:
                    f.write(view)
                os.rename(tmp, dst)
            except OSError:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            return
        if not self._base_made:
            self._fs.create_dir(self._base, recursive=True)
            self._base_made = True
        # tmp + move for the same atomicity the local path gets: a
        # write failing mid-stream must not leave a truncated object at
        # the final key that exists()/read_view() would then trust.
        tmp = self._key(oid_hex) + ".tmp"
        try:
            with self._fs.open_output_stream(tmp) as f:
                f.write(view)
            self._fs.move(tmp, self._key(oid_hex))
        except Exception:  # lint: broad-except-ok any backend failure (fs driver raises are untyped) must clean the temp key; re-raised below
            try:
                self._fs.delete_file(tmp)
            except Exception:  # lint: broad-except-ok best-effort temp cleanup; the original write error (re-raised) is the signal
                pass
            raise

    def write_file(self, oid_hex: str, src_path: str,
                   chunk: int = 8 << 20) -> None:
        """Stream a local file to the target in chunks (no whole-object
        heap copy — spilling happens under memory pressure)."""
        if self._fs is None:
            os.makedirs(self.local_dir, exist_ok=True)
            dst = os.path.join(self.local_dir, oid_hex)
            tmp = dst + ".tmp"
            try:
                import shutil
                # copyfile streams (sendfile where the kernel allows);
                # the old path read the whole object onto the heap.
                shutil.copyfile(src_path, tmp)
                os.rename(tmp, dst)
            except OSError:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            return
        if not self._base_made:
            self._fs.create_dir(self._base, recursive=True)
            self._base_made = True
        tmp = self._key(oid_hex) + ".tmp"
        try:
            with open(src_path, "rb") as src, \
                    self._fs.open_output_stream(tmp) as dst:
                while True:
                    buf = src.read(chunk)
                    if not buf:
                        break
                    dst.write(buf)
            self._fs.move(tmp, self._key(oid_hex))
        except Exception:  # lint: broad-except-ok any backend failure (fs driver raises are untyped) must clean the temp key; re-raised below
            try:
                self._fs.delete_file(tmp)
            except Exception:  # lint: broad-except-ok best-effort temp cleanup; the original write error (re-raised) is the signal
                pass
            raise

    def exists(self, oid_hex: str) -> bool:
        if self._fs is None:
            return os.path.exists(os.path.join(self.local_dir, oid_hex))
        import pyarrow.fs as pafs
        info = self._fs.get_file_info(self._key(oid_hex))
        return info.type != pafs.FileType.NotFound

    def read_mmap(self, oid_hex: str, chunk: int = 8 << 20):
        """Restore into a mapping: local targets mmap the spill file
        off the page cache; remote targets stream CHUNKED into an
        anonymous mapping (the pipelined-restore entry point — callers
        run this outside the store lock). Raises OSError when the key
        is missing."""
        import mmap as _mmap
        if self._fs is None:
            path = os.path.join(self.local_dir, oid_hex)
            fd = os.open(path, os.O_RDWR)
            try:
                return _mmap.mmap(fd, os.path.getsize(path))
            finally:
                os.close(fd)
        import pyarrow.fs as pafs
        info = self._fs.get_file_info(self._key(oid_hex))
        if info.type == pafs.FileType.NotFound:
            raise FileNotFoundError(oid_hex)
        size = int(info.size or 0)
        mm = _mmap.mmap(-1, max(1, size))
        off = 0
        try:
            with self._fs.open_input_stream(self._key(oid_hex)) as f:
                while off < size:
                    buf = f.read(min(chunk, size - off))
                    if not buf:
                        break
                    mm[off:off + len(buf)] = buf
                    off += len(buf)
        except Exception:
            mm.close()
            raise
        if off != size:
            mm.close()
            raise OSError(f"short restore for {oid_hex}: {off}/{size}")
        return mm

    def read_view(self, oid_hex: str):
        """Zero-copy-ish read: local spills mmap (pagecache); remote
        spills stream into one bytes buffer."""
        if self._fs is None:
            import mmap as _mmap
            path = os.path.join(self.local_dir, oid_hex)
            fd = os.open(path, os.O_RDWR)
            try:
                mm = _mmap.mmap(fd, os.path.getsize(path))
            finally:
                os.close(fd)
            return memoryview(mm)
        with self._fs.open_input_stream(self._key(oid_hex)) as f:
            return memoryview(f.read())

    def delete(self, oid_hex: str) -> None:
        try:
            if self._fs is None:
                os.unlink(os.path.join(self.local_dir, oid_hex))
            else:
                self._fs.delete_file(self._key(oid_hex))
        except Exception:  # lint: broad-except-ok spill file already gone (double-delete race) costs nothing
            pass

    def cleanup(self) -> None:
        import shutil
        shutil.rmtree(self.local_dir, ignore_errors=True)
        if self._fs is not None:
            try:
                self._fs.delete_dir(self._base)
            except Exception:  # lint: broad-except-ok best-effort removal of the remote spill dir at shutdown
                pass


class _ArenaPin:
    """Owns one reader pin on an arena object (plasma client-pin
    semantics): buffers deserialized zero-copy from the arena keep this
    object alive through the memoryview chain, and the pin releases when
    the last view is garbage-collected — only then may the slot be
    deleted/recycled (PEP 688 buffer protocol)."""

    __slots__ = ("_native", "_key", "_view", "_released")

    def __init__(self, native, key: bytes, view):
        self._native = native
        self._key = key
        self._view = view
        self._released = False

    def __buffer__(self, flags):
        return memoryview(self._view)

    def __release_buffer__(self, view):
        pass

    def __del__(self):
        if not self._released:
            self._released = True
            try:
                self._view.release()
                self._native.release(self._key)
            except Exception:  # lint: broad-except-ok destructor: interpreter teardown may have reaped the arena already
                pass


class ArenaObjectStore:
    """Native-arena backend (the DEFAULT store when the C++ lib builds).

    Backed by the C++ plasma-equivalent (_native/src/store.cpp): one
    shared mmap arena + process-shared allocator instead of a file per
    object. Puts memcpy into already-faulted pages — measured 6.0 GB/s
    vs 2.1 GB/s for fresh-tmpfs-file writes on the same host (page
    allocation, not copying, dominates the file store's put path; the
    raw single-core memcpy ceiling is 7.9 GB/s, so the reference's
    18.5 GB/s single-client figure — measured on a 64-vCPU host — is
    not reachable on this hardware class).

    Reads are ZERO-COPY with pin-until-release: deserialized arrays
    alias the arena through an _ArenaPin buffer owner, and the reader
    pin drops when the last view dies — so recycling a slot can never
    invalidate live views (the round-1 wrapper copied instead).

    Spill/restore (reference: LocalObjectManager): the OWNER process
    spills LRU sealed objects to a disk directory when the arena fills,
    and any process restores by falling back to the deterministic spill
    path — same contract as the file store, so the memory monitor and
    OOM tests work unchanged.
    """

    def __init__(self, session_dir: str, capacity: Optional[int] = None):
        from .. import _native
        os.makedirs(session_dir, exist_ok=True)
        self._path = os.path.join(session_dir, "arena.shm")
        self._capacity = capacity or _default_capacity(session_dir)
        self._spill_dir = session_dir.rstrip("/") + "_spill"
        self._spill = _SpillTarget(self._spill_dir)
        try:
            self._store = _native.NativeStore(
                self._path, self._capacity, create=True)
            self._owner = True
        except FileExistsError:
            # A peer of this session made the arena first. Any other
            # failure to create it is the machine's refusal and surfaces
            # with its errno.
            self._store = _native.NativeStore(self._path, create=False)
            self._owner = False
        self._lock = lockdep.rlock("object_store.arena_store")
        # Owner-side metadata for spill candidacy (the native header has
        # no enumeration API): oid -> size, plus an LRU clock.
        self._meta: Dict[ObjectID, int] = {}
        self._access: Dict[ObjectID, int] = {}
        self._clock = 0
        self._pending_delete: list = []
        self._spilled_bytes = 0
        self._spilled_count = 0
        self._restored_count = 0
        # Same-host zero-copy adoption (reference analogue: same-node
        # plasma clients share one store; here co-hosted NODES share
        # pages). oid -> (foreign arena path, offset, size, pinned).
        # The arena header lives in the shared mmap, so a pin taken
        # through a foreign handle is visible to the owner process and
        # blocks slot recycling until we release it.
        self._external: Dict[ObjectID, tuple] = {}
        self._foreign: Dict[str, Any] = {}  # path -> NativeStore handle

    # -- paths ------------------------------------------------------------
    def _spill_path(self, object_id: ObjectID) -> str:
        return os.path.join(self._spill_dir, object_id.hex())

    @property
    def used_bytes(self) -> int:
        return self._store.used_bytes()

    @property
    def capacity(self) -> int:
        return self._store.capacity()

    # -- write path -------------------------------------------------------
    def _track(self, object_id: ObjectID, size: int):
        with self._lock:
            self._clock += 1
            self._meta[object_id] = size
            self._access[object_id] = self._clock

    # Set by worker processes to a callable asking the OWNER to spill
    # (gcs_request "spill_store"): a worker's local spill can only move
    # its OWN objects — a full arena is usually other processes' sealed
    # blocks, which only the owner (who adopted them) may spill
    # (reference: the raylet, not the plasma client, orchestrates
    # spilling — local_object_manager.cc).
    request_spill = None

    def create(self, object_id: ObjectID, size: int):
        """Writable view for a two-phase write (seal after); used by the
        puller and put_serialized."""
        self._collect_pending()
        try:
            view = self._store.create(object_id, size)
        except MemoryError:
            with self._lock:
                self._spill_locked(size)
            try:
                view = self._store.create(object_id, size)
            except MemoryError as e:
                if self.request_spill is not None:
                    # Retry with backoff: a concurrent creator can claim
                    # the space the owner just spilled, and blocks
                    # pinned by in-flight readers only become spillable
                    # as their tasks finish.
                    import time as _time
                    view = None
                    for attempt in range(5):
                        try:
                            self.request_spill(size)
                        except Exception:
                            break
                        try:
                            view = self._store.create(object_id, size)
                            break
                        except MemoryError:
                            _time.sleep(0.05 * (attempt + 1))
                    if view is not None:
                        self._track(object_id, size)
                        return view
                raise ObjectStoreFullError(
                    f"Object of {size} bytes does not fit: "
                    f"{self.used_bytes}/{self.capacity} arena bytes used "
                    f"({self._spilled_bytes} spilled).") from e
        self._track(object_id, size)
        return view

    def seal(self, object_id: ObjectID):
        self._store.seal(object_id)

    def reserve(self, object_id: ObjectID, size: int) -> _ArenaReservation:
        """Zero-copy put protocol over the arena: wraps the two-phase
        create view so put_in_place drives both backends through one
        reserve/seal contract. Ref-discipline: seal-or-abort
        obligation, same as the file backend (lint
        check_reserve_pairing)."""
        return _ArenaReservation(
            self, object_id, size, self.create(object_id, size))

    def _abort_reserve(self, object_id: ObjectID):
        with self._lock:
            self._meta.pop(object_id, None)
            self._access.pop(object_id, None)
        try:
            self._store.release(object_id)
            self._store.delete(object_id)
        except Exception:
            pass

    def put_serialized(self, object_id: ObjectID,
                       sobj: serialization.SerializedObject) -> int:
        if bool(ray_config.store_zero_copy_put_enabled):
            # creator pin retained: owner-driven free()/spill reclaims
            return put_in_place(self, object_id, sobj)
        size = sobj.total_size
        with _put_gate(size):
            view = self.create(object_id, size)
            try:
                sobj.write_into(view)
            except BaseException:
                view.release()
                self._abort_reserve(object_id)
                raise
            view.release()
        self.seal(object_id)
        if telemetry.enabled:
            telemetry.record_put_bytes(size)
        # creator pin retained: owner-driven free()/spill is the reclaim
        return size

    def put(self, object_id: ObjectID, value: Any) -> int:
        return self.put_serialized(object_id, serialization.serialize(value))

    # -- spill path -------------------------------------------------------
    def _spill_locked(self, need_bytes: int) -> int:
        """Copy LRU sealed objects out to disk and delete them from the
        arena until `need_bytes` are reclaimable (callers hold _lock).
        Objects pinned by live reader views are skipped."""
        from .config import ray_config
        if not bool(ray_config.object_spilling_enabled):
            return 0
        candidates = sorted(
            ((self._access.get(oid, 0), oid, size)
             for oid, size in self._meta.items()
             if size >= int(ray_config.min_spilling_size)),
            key=lambda t: t[0])
        os.makedirs(self._spill_dir, exist_ok=True)
        reclaimed = 0
        for _, oid, size in candidates:
            if reclaimed >= need_bytes:
                break
            try:
                view = self._store.get(oid)  # takes a pin
            except KeyError:
                # Created-but-unsealed (a writer is mid two-phase put):
                # not spillable NOW, but must stay tracked.
                continue
            try:
                self._spill.write(oid.hex(), view)
            except Exception:
                view.release()
                self._store.release(oid)
                continue
            view.release()
            self._store.release(oid)   # our read pin
            self._store.release(oid)   # the creator pin
            try:
                self._store.delete(oid)
            except RuntimeError:
                # Reader still pinning: keep it resident, drop the copy.
                self._spill.delete(oid.hex())
                # re-take the creator pin we dropped
                try:
                    v = self._store.get(oid)
                    v.release()
                except KeyError:
                    pass
                continue
            self._meta.pop(oid, None)
            self._access.pop(oid, None)
            self._spilled_bytes += size
            self._spilled_count += 1
            reclaimed += size
        return reclaimed

    def spill_objects(self, target_bytes: int) -> int:
        with self._lock:
            used = self.used_bytes
            if used <= target_bytes:
                return 0
            return self._spill_locked(used - target_bytes)

    # -- same-host adoption ------------------------------------------------
    def _foreign_handle(self, path: str):
        from .. import _native
        with self._lock:
            h = self._foreign.get(path)
            if h is None:
                h = _native.NativeStore(path, create=False)
                self._foreign[path] = h
        return h

    def adopt_native(self, object_id: ObjectID, path: str, offset: int,
                     size: int, pin: bool = True) -> None:
        """Adopt a same-host object IN PLACE: map the source node's
        arena and reference its slot instead of copying (reference
        analogue: same-node plasma clients mmap one store; fresh-page
        allocation is also the measured wall on thin hosts). With
        ``pin=True`` (daemons) a reader pin is taken through the shared
        header so the owner can't recycle/spill the slot until free();
        ``pin=False`` (pooled workers, which may be SIGKILLed and would
        leak pins forever) relies on the daemon's pin + the head's
        task-arg refs for lifetime."""
        h = self._foreign_handle(path)
        if pin:
            off, sz = h.locate(object_id)  # pins + verifies presence
            offset, size = off, sz
        with self._lock:
            if object_id in self._external:
                if pin:
                    h.release(object_id)  # already adopted: drop dup pin
                return
            self._external[object_id] = (path, offset, size, pin)

    def _maybe_prune_foreign(self, path: str) -> None:
        """Close a cached foreign handle once its owner is GONE (arena
        file unlinked) and no adoption references it — an unlinked
        multi-GB tmpfs arena stays resident for as long as anyone maps
        it, so departed peers' handles must not live forever. Handles
        of live peers stay cached (bounded by co-hosted node count);
        closing them would no-op the release() of in-flight reader
        pins."""
        with self._lock:
            if any(e[0] == path for e in self._external.values()):
                return
            if os.path.exists(path):
                return
            h = self._foreign.pop(path, None)
        if h is not None:
            try:
                h.close(unlink=False)
            except Exception:
                pass

    def materialize_external(self, object_id: ObjectID) -> bool:
        """Copy an adopted object into the LOCAL arena (used when the
        mapping can't be shipped to another process — e.g. the owner's
        arena file was unlinked after its node died, so new mmaps of it
        fail while our established one still works). Drops the external
        entry on success."""
        try:
            src = self._external_view(object_id)
        except KeyError:
            return self._store.contains(object_id)
        try:
            size = len(src)
            view = self.create(object_id, size)
            try:
                view[0:size] = src
            except BaseException:
                view.release()
                self._abort_reserve(object_id)
                raise
            view.release()
            self.seal(object_id)
        except FileExistsError:
            pass  # another thread materialized it first
        finally:
            src.release()
        self.free_external_entry(object_id)
        return True

    def free_external_entry(self, object_id: ObjectID) -> None:
        with self._lock:
            ext = self._external.pop(object_id, None)
        if ext is not None and ext[3]:
            try:
                self._foreign_handle(ext[0]).release(object_id)
            except Exception:
                pass

    def export_adoption(self, object_id: ObjectID):
        """(path, offset, size) when this store holds `object_id` as an
        adopted external reference — what a co-hosted worker needs to
        map it directly — else None."""
        with self._lock:
            ext = self._external.get(object_id)
        return None if ext is None else (ext[0], ext[1], ext[2])

    def _external_view(self, object_id: ObjectID):
        """Pinned zero-copy view of an adopted object. Raises KeyError
        when not adopted. Takes a per-read pin (released with the view)
        on top of the adoption-lifetime pin so a concurrent free can't
        recycle the slot under a live reader."""
        with self._lock:
            ext = self._external.get(object_id)
        if ext is None:
            raise KeyError(object_id)
        path, offset, size, _pinned = ext
        h = self._foreign_handle(path)
        try:
            off, sz = h.locate(object_id)  # per-read pin
            view = h._view[off:off + sz]
        except KeyError:
            # Owner already dropped it (we were an unpinned adopter and
            # lost the race): treat as not-present.
            with self._lock:
                self._external.pop(object_id, None)
            raise
        return memoryview(_ArenaPin(h, _native_key(object_id), view))

    # -- read path --------------------------------------------------------
    def contains(self, object_id: ObjectID) -> bool:
        if self._store.contains(object_id):
            return True
        with self._lock:
            if object_id in self._external:
                return True
        return self._spill.exists(object_id.hex())

    def _pinned_view(self, object_id: ObjectID):
        try:
            view = self._store.get(object_id)  # pins
        except KeyError:
            return self._external_view(object_id)
        pin = _ArenaPin(self._store, _native_key(object_id), view)
        with self._lock:
            self._clock += 1
            if object_id in self._access:
                self._access[object_id] = self._clock
        return memoryview(pin)

    def get(self, object_id: ObjectID) -> Any:
        try:
            view = self._pinned_view(object_id)
        except KeyError:
            # Not arena-resident: spilled (or gone — surfaces as OSError)
            view = self._restore_view(object_id)
        if telemetry.enabled:
            telemetry.record_get_bytes(view.nbytes)
        return serialization.deserialize(view)

    def get_raw(self, object_id: ObjectID):
        try:
            return self._pinned_view(object_id)
        except KeyError:
            return self._restore_view(object_id)

    def _restore_view(self, object_id: ObjectID):
        """Read a spilled object back (local: page-cache mmap; URI
        targets: streamed through pyarrow.fs; not re-admitted to the
        arena)."""
        view = self._spill.read_view(object_id.hex())
        with self._lock:
            self._restored_count += 1
        return view

    def adopt(self, object_id: ObjectID, size: int):
        """Owner-side tracking for a segment a worker created (arena
        accounting is shared; this records spill candidacy)."""
        self._track(object_id, size)

    # -- free path --------------------------------------------------------
    def free(self, object_id: ObjectID):
        with self._lock:
            self._meta.pop(object_id, None)
            self._access.pop(object_id, None)
            ext = self._external.pop(object_id, None)
        if ext is not None:
            path, _off, _size, pinned = ext
            if pinned:
                try:
                    self._foreign_handle(path).release(object_id)
                except Exception:
                    pass
            self._maybe_prune_foreign(path)
            return  # adopted objects hold no local bytes
        self._spill.delete(object_id.hex())
        try:
            self._store.release(object_id)  # drop creator pin
            self._store.delete(object_id)
        except KeyError:
            pass
        except RuntimeError:
            # Live reader views pin the slot; retry on later activity.
            with self._lock:
                self._pending_delete.append(object_id)

    def _collect_pending(self):
        with self._lock:
            pending, self._pending_delete = self._pending_delete, []
        for oid in pending:
            try:
                self._store.delete(oid)
            except KeyError:
                pass
            except RuntimeError:
                with self._lock:
                    self._pending_delete.append(oid)

    def release(self, object_id: ObjectID):
        # Reader pins are view-lifetime (_ArenaPin); an external entry
        # dropped here covers cluster-wide frees relayed to workers
        # (unpinned adopters just forget the mapping).
        with self._lock:
            ext = self._external.pop(object_id, None)
        if ext is not None and ext[3]:
            try:
                self._foreign_handle(ext[0]).release(object_id)
            except Exception:
                pass

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"used_bytes": self.used_bytes,
                    "capacity": self.capacity,
                    "spilled_bytes": self._spilled_bytes,
                    "spilled_count": self._spilled_count,
                    "restored_count": self._restored_count,
                    "adopted_count": len(self._external),
                    "num_objects": self._store.num_objects()}

    def shutdown(self):
        import shutil
        with self._lock:
            external = dict(self._external)
            foreign = dict(self._foreign)
            self._foreign.clear()
            self._external.clear()
        # Release adoption pins FIRST — they live in the owner's shared
        # header and would otherwise block that (still-alive) store from
        # ever recycling the slots.
        for oid, (path, _off, _size, pinned) in external.items():
            if pinned:
                h = foreign.get(path)
                if h is not None:
                    try:
                        h.release(oid)
                    except Exception:  # lint: broad-except-ok best-effort teardown: every subsystem stops even if one is already dead
                        pass
        for h in foreign.values():
            try:
                h.close(unlink=False)
            except Exception:  # lint: broad-except-ok best-effort teardown: every subsystem stops even if one is already dead
                pass
        self._store.close(unlink=self._owner)
        if self._owner:
            self._spill.cleanup()
            shutil.rmtree(os.path.dirname(self._path),
                          ignore_errors=True)


def _native_key(object_id: ObjectID) -> bytes:
    return object_id.binary()


def create_store(session_dir: str, capacity: Optional[int] = None):
    """Pick the store backend: the native C++ arena (2x put bandwidth —
    page reuse instead of per-put tmpfs page allocation) wherever
    _ArenaPin can exist (PEP 688, Python >= 3.12). RAY_TPU_FILE_STORE=1
    chooses the file-per-object store. A native library that fails to
    build is an error, not a reason to run on the other store: head and
    workers must agree, and a slow store must not pass unnoticed."""
    import sys
    if (os.environ.get("RAY_TPU_FILE_STORE") == "1"
            or sys.version_info < (3, 12)):
        return ObjectStore(session_dir, capacity)
    from .. import _native
    if not _native.available():
        raise RuntimeError(
            f"native library unavailable ({_native.build_error()}); "
            "set RAY_TPU_FILE_STORE=1 to run on the file store")
    return ArenaObjectStore(session_dir, capacity)


def create_session_store(session_name: str, session_dir: str,
                         capacity: Optional[int] = None):
    """The store of a head or daemon session, as (store, store_dir).

    It lives in /dev/shm. A machine that refuses it there (no tmpfs at
    /dev/shm, a mount that cannot be mapped shared, a limit the bounds of
    `_default_capacity` do not know) gets the same store under the
    session's own directory — pages behind a disk instead of memory, so
    slower, and said loudly; the reference's plasma falls back to /tmp
    the same way. The first refusal is chained to a second one."""
    import shutil
    import sys
    store_dir = os.path.join("/dev/shm", f"ray_tpu_{session_name}")
    try:
        return create_store(store_dir, capacity), store_dir
    except OSError as e:
        refused = e
    shutil.rmtree(store_dir, ignore_errors=True)
    fallback_dir = os.path.join(session_dir, "store")
    sys.stderr.write(
        f"ray_tpu: WARNING: /dev/shm refused the object store "
        f"({refused}; {_shm_facts()}); using {fallback_dir} instead, "
        f"which is slower.\n")
    try:
        return create_store(fallback_dir, capacity), fallback_dir
    except OSError as e:
        raise e from refused


def _shm_facts() -> str:
    """What this process can see of /dev/shm and its own limits, for the
    message of a refused store."""
    import resource
    facts = []
    try:
        st = os.statvfs("/dev/shm")
        facts.append(f"/dev/shm free {st.f_bsize * st.f_bavail} of "
                     f"{st.f_frsize * st.f_blocks} bytes")
    except OSError as e:
        facts.append(f"statvfs(/dev/shm): {e}")
    try:
        with open("/proc/mounts") as f:
            facts += [line.strip() for line in f
                      if line.split()[1:2] == ["/dev/shm"]]
    except OSError:
        pass
    for name in ("RLIMIT_AS", "RLIMIT_FSIZE"):
        facts.append(f"{name} {resource.getrlimit(getattr(resource, name))}")
    return ", ".join(facts)
