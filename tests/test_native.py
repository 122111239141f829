"""Native C++ store/transfer tests (reference strategy: the C++ unit
suites in object_manager/plasma tests + object_manager_test.cc, run here
through the ctypes binding)."""
import os
import subprocess
import sys

import pytest

from ray_tpu import _native

pytestmark = pytest.mark.skipif(
    not _native.available(),
    reason=f"native lib unavailable: {_native.build_error()}")


def _id(i: int) -> bytes:
    return i.to_bytes(16, "little")


@pytest.fixture
def store(tmp_path):
    s = _native.NativeStore(str(tmp_path / "arena"), capacity=32 << 20)
    yield s
    s.close(unlink=True)


def test_put_get_roundtrip(store):
    payload = os.urandom(100_000)
    store.put(_id(1), payload)
    view = store.get(_id(1))
    assert bytes(view) == payload
    view.release()
    store.release(_id(1))
    assert store.contains(_id(1))
    assert store.num_objects() == 1
    assert store.used_bytes() >= 100_000


def test_two_phase_create_seal(store):
    buf = store.create(_id(2), 16)
    assert not store.contains(_id(2))  # not sealed yet
    buf[:] = b"0123456789abcdef"
    buf.release()
    store.seal(_id(2))
    v = store.get(_id(2))
    assert bytes(v) == b"0123456789abcdef"
    v.release()


def test_duplicate_and_missing(store):
    store.put(_id(3), b"x")
    with pytest.raises(FileExistsError):
        store.put(_id(3), b"y")
    with pytest.raises(KeyError):
        store.get(_id(99))


def test_delete_and_pin(store):
    store.put(_id(4), b"data")
    store.release(_id(4))           # drop creator pin
    v = store.get(_id(4))           # read pin
    with pytest.raises(RuntimeError, match="pinned"):
        store.delete(_id(4))
    v.release()
    store.release(_id(4))
    store.delete(_id(4))
    assert not store.contains(_id(4))
    assert store.num_objects() == 0


def test_lru_eviction_under_pressure(store):
    # Fill beyond capacity with unpinned objects; eviction must kick in
    # and keep puts succeeding (reference: eviction_policy.cc).
    blob = os.urandom(4 << 20)  # 4 MiB
    for i in range(20):         # 80 MiB through a 32 MiB arena
        store.put(_id(100 + i), blob)
        store.release(_id(100 + i))
    assert store.evictions() > 0
    assert store.contains(_id(119))  # newest survives
    assert not store.contains(_id(100))  # oldest evicted


def test_allocator_reuse_and_coalesce(store):
    # free + realloc bigger: coalescing must make the space reusable
    for i in range(8):
        store.put(_id(200 + i), b"a" * 100_000)
        store.release(_id(200 + i))
    for i in range(8):
        store.delete(_id(200 + i))
    used_before = store.used_bytes()
    store.put(_id(300), b"b" * 700_000)  # needs coalesced space
    assert store.used_bytes() >= used_before + 700_000


def test_cross_process_access(store, tmp_path):
    """Another process opens the same arena and reads/writes — the
    plasma property (shared mapping, process-shared lock)."""
    store.put(_id(7), b"from-parent")
    store.release(_id(7))
    code = f"""
import sys
sys.path.insert(0, {str(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))!r})
from ray_tpu import _native
s = _native.NativeStore({store.path!r}, create=False)
v = s.get((7).to_bytes(16, "little"))
assert bytes(v) == b"from-parent", bytes(v)
v.release()
s.release((7).to_bytes(16, "little"))
s.put((8).to_bytes(16, "little"), b"from-child")
s.release((8).to_bytes(16, "little"))
s.close()
print("child-ok")
"""
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert "child-ok" in out.stdout, out.stderr
    v = store.get(_id(8))
    assert bytes(v) == b"from-child"
    v.release()


def test_transfer_between_arenas(tmp_path):
    """Node-to-node pull: objects move between two arenas over TCP
    (reference: object_manager push/pull)."""
    a = _native.NativeStore(str(tmp_path / "node_a"), capacity=64 << 20)
    b = _native.NativeStore(str(tmp_path / "node_b"), capacity=64 << 20)
    try:
        server = _native.TransferServer(a)
        payload = os.urandom(5 << 20)  # 5 MiB, several chunks
        a.put(_id(42), payload)
        a.release(_id(42))
        _native.pull(b, "127.0.0.1", server.port, _id(42))
        v = b.get(_id(42))
        assert bytes(v) == payload
        v.release()
        with pytest.raises(KeyError):
            _native.pull(b, "127.0.0.1", server.port, _id(43))
        server.stop()
    finally:
        a.close(unlink=True)
        b.close(unlink=True)


def test_cluster_with_native_store(tmp_path):
    """Full runtime on the arena backend — the DEFAULT store since r2:
    tasks, large objects, actors (the e2e check that the backend honors
    the store contract). RAY_TPU_FILE_STORE=1 forces the fallback."""
    import subprocess
    code = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import ray_tpu
from ray_tpu._private import state
ray_tpu.init(num_cpus=4)
assert type(state.current().store).__name__ == "ArenaObjectStore"

@ray_tpu.remote
def big(n):
    return np.arange(n, dtype=np.float64)

refs = [big.remote(200_000) for _ in range(8)]  # ~1.6MB each, > inline
outs = ray_tpu.get(refs)
for o in outs:
    assert o.shape == (200_000,) and o[-1] == 199_999

big_ref = ray_tpu.put(np.ones((1000, 1000)))

@ray_tpu.remote
def consume(a):
    return float(a.sum())

assert ray_tpu.get(consume.remote(big_ref)) == 1_000_000.0
del big_ref, refs, outs
ray_tpu.shutdown()
print("native-cluster-ok")
"""
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=180)
    assert "native-cluster-ok" in out.stdout, out.stderr[-3000:]


def test_arena_zero_copy_pinned_reads(tmp_path):
    """Reads alias the arena (no copy) and pin the slot until the last
    view dies — recycling can't invalidate live arrays (VERDICT r1 #10:
    'make reads pin-until-release instead of copy')."""
    import numpy as np

    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.object_store import ArenaObjectStore

    store = ArenaObjectStore(str(tmp_path / "arena"), capacity=64 << 20)
    try:
        oid = ObjectID.from_random()
        src = np.arange(1_000_000, dtype=np.float64)
        store.put(oid, src)
        out = store.get(oid)
        assert out[-1] == 999_999.0
        # Zero-copy: the array's buffer lives inside the arena mapping.
        assert not out.flags["OWNDATA"]
        # Pin: free() while a view is live must not invalidate it.
        store.free(oid)
        assert float(out.sum()) == float(src.sum())
    finally:
        del out
        store.shutdown()


def test_arena_spill_and_restore(tmp_path):
    """Arena overflow spills LRU objects to disk and restores them on
    read (same contract as the file store; reference:
    LocalObjectManager spill/restore)."""
    import numpy as np

    from ray_tpu._private.ids import ObjectID
    from ray_tpu._private.object_store import ArenaObjectStore

    store = ArenaObjectStore(str(tmp_path / "arena"), capacity=2 << 20)
    try:
        oids = [ObjectID.from_random() for _ in range(4)]
        for oid in oids:
            store.put(oid, np.zeros(300 * 1024, dtype=np.uint8))
        st = store.stats()
        assert st["spilled_count"] >= 1, st
        for oid in oids:
            assert store.get(oid).nbytes == 300 * 1024
        assert store.stats()["restored_count"] >= 1
    finally:
        store.shutdown()


def test_init_shutdown_churn_no_native_crash():
    """Regression: a prestart thread's native-mux registration racing
    shutdown() used to disp_add into a destroyed Dispatcher (segfault).
    Rapid init/shutdown cycles drive exactly that window."""
    import os

    import ray_tpu
    from ray_tpu import _native
    from ray_tpu._private import state as _state
    from ray_tpu._private.scheduler import _NativeMux

    if (not _native.available()
            or os.environ.get("RAY_TPU_NATIVE_DISPATCH") == "0"):
        pytest.skip("native dispatch core unavailable")
    for i in range(6):
        ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
        if i == 0:
            # Not vacuous: the cycles must actually exercise the
            # native mux, not the pure-Python fallback.
            assert isinstance(_state.current().pool._mux, _NativeMux)
        ray_tpu.shutdown()  # immediately: prestart threads still booting


def test_refused_arena_names_step_and_errno(tmp_path):
    """A machine that refuses the arena says which step and why, and
    leaves no half-made file that a retry would take for a peer's."""
    path = str(tmp_path / "arena")
    with pytest.raises(FileNotFoundError, match="arena open: open failed"):
        _native.NativeStore(path, create=False)
    open(path, "w").close()
    with pytest.raises(FileExistsError):
        _native.NativeStore(path, capacity=32 << 20)
    with pytest.raises(OSError, match="arena open: mmap failed"):
        _native.NativeStore(path, create=False)     # empty: not an arena
    os.unlink(path)
    # The two limits a harness may put on a process, each in a child.
    code = f"""
import errno, os, resource, sys
from ray_tpu import _native
_native.available()   # g++ and dlopen need room themselves: build first
resource.setrlimit(resource.{{limit}}, ({{soft}}, resource.RLIM_INFINITY))
try:
    _native.NativeStore({path!r}, capacity=8 << 30)
except OSError as e:
    assert e.errno == errno.{{err}} and "{{step}} failed" in str(e), e
    assert not os.path.exists({path!r})
    sys.exit(7)
"""
    for limit, soft, err, step in (
            ("RLIMIT_FSIZE", 1 << 24, "EFBIG", "ftruncate"),
            ("RLIMIT_AS", 4 << 30, "ENOMEM", "mmap")):
        proc = subprocess.run(
            [sys.executable, "-c", code.format(
                limit=limit, soft=soft, err=err, step=step)],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 7, proc.stderr


def test_default_capacity_fits_process_limits():
    """The default arena is one a process under RLIMIT_AS / RLIMIT_FSIZE
    can create and map twice, and never larger than physical memory."""
    code = """
import os, resource
resource.setrlimit(resource.RLIMIT_AS, (16 << 30, resource.RLIM_INFINITY))
from ray_tpu._private import object_store
cap = object_store._default_capacity()
assert 0 < cap <= 2 << 30, cap
assert cap <= os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
resource.setrlimit(resource.RLIMIT_FSIZE, (1 << 28, resource.RLIM_INFINITY))
assert object_store._default_capacity() <= 1 << 28
import ray_tpu
ray_tpu.init(num_cpus=1)
ref = ray_tpu.put(b"x" * (1 << 20))
assert len(ray_tpu.get(ref)) == 1 << 20
ray_tpu.shutdown()
"""
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_store_falls_back_to_session_dir_loudly(tmp_path, monkeypatch,
                                                capsys):
    """/dev/shm refusing the store moves the same store under the session
    directory, with a warning that carries the errno; a second refusal
    raises with the first chained."""
    from ray_tpu._private import object_store
    real = _native.NativeStore

    def refuse_shm(path, capacity=None, create=True):
        if path.startswith("/dev/shm/"):
            raise OSError(19, "arena create: mmap failed", path)
        return real(path, capacity, create)

    monkeypatch.setattr(_native, "NativeStore", refuse_shm)
    store, store_dir = object_store.create_session_store(
        "session_test_fallback", str(tmp_path), 32 << 20)
    try:
        assert store_dir == str(tmp_path / "store")
        assert type(store).__name__ == "ArenaObjectStore"
        assert os.path.exists(os.path.join(store_dir, "arena.shm"))
        assert not os.path.exists("/dev/shm/ray_tpu_session_test_fallback")
        err = capsys.readouterr().err
        assert "WARNING: /dev/shm refused" in err and "Errno 19" in err
        assert "RLIMIT_AS" in err
    finally:
        store.shutdown()

    def refuse_all(path, capacity=None, create=True):
        raise OSError(12, "arena create: mmap failed", path)

    monkeypatch.setattr(_native, "NativeStore", refuse_all)
    with pytest.raises(OSError) as exc:
        object_store.create_session_store(
            "session_test_fallback", str(tmp_path), 32 << 20)
    assert isinstance(exc.value.__cause__, OSError)
