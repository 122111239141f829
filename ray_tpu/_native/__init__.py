"""ctypes bindings for the native (C++) runtime components.

Reference: the role of _raylet.pyx — binding Python to the C++ layer —
without Cython (not baked into this image): a plain C ABI + ctypes.

Builds lazily with g++ on first use. The library's file name carries a
hash of its sources (libray_tpu.<hash>.so), so a library on disk is
reused exactly when it was built from these sources — file times, which
a copy of the tree resets, decide nothing. A failed build is reported by
`available()` / `build_error()`; the runtime's entry points
(`create_store`, the scheduler's recv mux) raise on it rather than pick
another implementation.
"""
import contextlib
import ctypes
import glob
import hashlib
import mmap as _mmap
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = [os.path.join(_DIR, "src", f)
        for f in ("store.cpp", "transfer.cpp", "dispatch.cpp",
                  "memcopy.cpp")]
_lock = threading.Lock()
_lib = None
_build_error: Optional[str] = None


def _so_path() -> str:
    h = hashlib.sha256()
    for src in _SRC:
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_DIR, f"libray_tpu.{h.hexdigest()[:16]}.so")


def _build(so: str):
    # Per-process temp name: the head and a daemon may build at once.
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-pthread", "-std=c++17",
           "-o", tmp] + _SRC
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
        if proc.returncode != 0:
            raise RuntimeError(f"native build failed:\n{proc.stderr}")
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    for stale in glob.glob(os.path.join(_DIR, "libray_tpu*.so")):
        if stale != so:
            with contextlib.suppress(OSError):  # a racing builder's remove
                os.remove(stale)


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            so = _so_path()
            if not os.path.exists(so):
                _build(so)
            lib = ctypes.CDLL(so)
        except Exception as e:  # noqa: BLE001
            _build_error = f"{type(e).__name__}: {e}"
            return None
        # signatures
        lib.rt_store_create.restype = ctypes.c_void_p
        lib.rt_store_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.rt_store_open.restype = ctypes.c_void_p
        lib.rt_store_open.argtypes = [ctypes.c_char_p]
        lib.rt_store_create_obj.restype = ctypes.c_int64
        lib.rt_store_create_obj.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.rt_store_seal.restype = ctypes.c_int
        lib.rt_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_put.restype = ctypes.c_int64
        lib.rt_store_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_void_p, ctypes.c_uint64]
        lib.rt_store_get.restype = ctypes.c_int
        lib.rt_store_get.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
        lib.rt_store_contains.restype = ctypes.c_int
        lib.rt_store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_release.restype = ctypes.c_int
        lib.rt_store_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_delete.restype = ctypes.c_int
        lib.rt_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        for f in ("rt_store_used", "rt_store_capacity",
                  "rt_store_num_objects", "rt_store_evictions"):
            getattr(lib, f).restype = ctypes.c_uint64
            getattr(lib, f).argtypes = [ctypes.c_void_p]
        lib.rt_store_close.restype = None
        lib.rt_store_close.argtypes = [ctypes.c_void_p]
        lib.rt_store_unlink.argtypes = [ctypes.c_char_p]
        lib.rt_store_fail_step.restype = ctypes.c_char_p
        lib.rt_store_fail_step.argtypes = []
        lib.rt_store_fail_errno.restype = ctypes.c_int
        lib.rt_store_fail_errno.argtypes = []
        lib.rt_transfer_serve.restype = ctypes.c_void_p
        lib.rt_transfer_serve.argtypes = [ctypes.c_void_p, ctypes.c_uint16]
        lib.rt_transfer_port.restype = ctypes.c_uint16
        lib.rt_transfer_port.argtypes = [ctypes.c_void_p]
        lib.rt_transfer_stop.restype = None
        lib.rt_transfer_stop.argtypes = [ctypes.c_void_p]
        lib.rt_transfer_pull.restype = ctypes.c_int
        lib.rt_transfer_pull.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint16,
            ctypes.c_char_p]
        lib.disp_create.restype = ctypes.c_void_p
        lib.disp_create.argtypes = []
        lib.disp_recv_batch.restype = ctypes.c_int64
        lib.disp_recv_batch.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                        ctypes.c_uint64, ctypes.c_int]
        lib.disp_stop.restype = None
        lib.disp_stop.argtypes = [ctypes.c_void_p]
        lib.disp_destroy.restype = None
        lib.disp_destroy.argtypes = [ctypes.c_void_p]
        # Quick dispatch entry points go through PyDLL: they only
        # memcpy + enqueue + (maybe) one eventfd write, so releasing
        # the GIL around them costs more (a handoff/context-switch
        # opportunity per call) than it buys.
        qlib = ctypes.PyDLL(so)
        qlib.disp_add.restype = ctypes.c_int
        qlib.disp_add.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                  ctypes.c_uint64]
        qlib.disp_remove.restype = ctypes.c_int
        qlib.disp_remove.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        qlib.disp_send.restype = ctypes.c_int
        qlib.disp_send.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                   ctypes.c_char_p, ctypes.c_uint64]
        lib.rt_nt_copy.restype = None
        lib.rt_nt_copy.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   ctypes.c_uint64]
        lib._qlib = qlib
        _lib = lib
        return _lib


EOF_LEN = 0xFFFFFFFFFFFFFFFF


class NativeDispatcher:
    """Thin handle to the C++ dispatch core (dispatch.cpp): an epoll IO
    thread owning worker sockets. Sends enqueue without syscalls on the
    caller; receives drain in batches with one GIL entry per batch."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self._send = lib._qlib.disp_send
        self._h = lib.disp_create()
        if not self._h:
            raise RuntimeError("disp_create failed")

    def add(self, fd: int, token: int) -> bool:
        return self._lib._qlib.disp_add(self._h, fd, token) == 0

    def remove(self, token: int) -> None:
        self._lib._qlib.disp_remove(self._h, token)

    def send(self, token: int, data: bytes) -> bool:
        return self._send(self._h, token, data, len(data)) == 0

    def recv_batch(self, buf, cap: int, timeout_ms: int) -> int:
        """Fills `buf` (a ctypes char array) with framed records; see
        dispatch.cpp disp_recv_batch. Blocks GIL-free in C++."""
        return int(self._lib.disp_recv_batch(self._h, buf, cap, timeout_ms))

    def stop(self) -> None:
        if self._h:
            self._lib.disp_stop(self._h)

    def destroy(self) -> None:
        if self._h:
            self._lib.disp_destroy(self._h)
            self._h = None


def available() -> bool:
    return _load() is not None


def _buf_addr_len(view: memoryview):
    """(address, nbytes) of a contiguous 1-D byte view via numpy's
    buffer introspection (works on read-only exporters, unlike
    ``ctypes.from_buffer``). The returned address is only valid while
    `view` itself is alive — callers must keep the view referenced
    across the native call and drop the array before closing any
    backing mmap (the frombuffer array holds a buffer export)."""
    import numpy as np
    arr = np.frombuffer(view, dtype=np.uint8)
    return arr, arr.ctypes.data, arr.nbytes


def nt_copy(dst: memoryview, src) -> bool:
    """Copy `src` into `dst` with non-temporal stores (memcopy.cpp),
    bypassing the write-allocate penalty glibc memcpy pays below its
    NT threshold — the put path's single copy into a store segment.
    Returns False (caller falls back to a plain slice copy) when the
    native lib is unavailable; lengths must already match."""
    lib = _load()
    if lib is None:
        return False
    sview = src if isinstance(src, memoryview) else memoryview(src)
    if sview.format != "B" or sview.ndim != 1:
        sview = sview.cast("B")
    da, daddr, dlen = _buf_addr_len(dst)
    sa, saddr, slen = _buf_addr_len(sview)
    if dlen != slen:
        raise ValueError(f"nt_copy length mismatch: {dlen} != {slen}")
    if dlen:
        lib.rt_nt_copy(daddr, saddr, dlen)
    del da, sa  # release the buffer exports before returning
    return True


def build_error() -> Optional[str]:
    _load()
    return _build_error


class NativeStore:
    """Python handle to the C++ arena store (plasma-client equivalent).

    Reads are zero-copy: Python maps the same arena file and returns
    memoryview slices at the offsets the C side hands out.
    """

    def __init__(self, path: str, capacity: Optional[int] = None,
                 create: bool = True):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        self._lib = lib
        self.path = path
        if create:
            self._h = lib.rt_store_create(path.encode(),
                                          int(capacity or (1 << 30)))
        else:
            self._h = lib.rt_store_open(path.encode())
        if not self._h:
            # OSError with the failing step's errno: EEXIST arrives as
            # FileExistsError (a peer made the arena first), and anything
            # else names what the machine refused.
            err = lib.rt_store_fail_errno()
            raise OSError(
                err, f"arena {'create' if create else 'open'}: "
                f"{lib.rt_store_fail_step().decode()} failed"
                + (f" at capacity {int(capacity)}" if create and capacity
                   else "") + f": {os.strerror(err)}", path)
        try:
            fd = os.open(path, os.O_RDWR)
            try:
                self._map = _mmap.mmap(fd, os.path.getsize(path))
            finally:
                os.close(fd)
        except OSError:
            # The C side mapped the arena but this process's own view
            # did not fit (RLIMIT_AS): leave nothing half-made behind.
            lib.rt_store_close(self._h)
            self._h = None
            if create:
                lib.rt_store_unlink(path.encode())
            raise
        self._view = memoryview(self._map)

    # -- object API --------------------------------------------------------
    @staticmethod
    def _key(object_id) -> bytes:
        b = object_id if isinstance(object_id, bytes) else object_id.binary()
        if len(b) != 16:
            raise ValueError(f"ids must be 16 bytes, got {len(b)}")
        return b

    def put(self, object_id, data) -> int:
        if not self._h:
            raise RuntimeError("store closed")
        data = bytes(data) if not isinstance(data, (bytes, bytearray,
                                                    memoryview)) else data
        buf = (ctypes.c_char * len(data)).from_buffer_copy(data)
        off = self._lib.rt_store_put(self._h, self._key(object_id),
                                     buf, len(data))
        if off == -2:
            raise FileExistsError("object already in store")
        if off < 0:
            raise MemoryError(f"arena full (rc={off})")
        return off

    def create(self, object_id, size: int) -> memoryview:
        """Two-phase create: returns a writable view; call seal() after."""
        if not self._h:
            raise RuntimeError("store closed")
        off = self._lib.rt_store_create_obj(self._h, self._key(object_id),
                                            size)
        if off == -2:
            raise FileExistsError("object already in store")
        if off < 0:
            raise MemoryError(f"arena full (rc={off})")
        return self._view[off:off + size]

    def seal(self, object_id):
        if not self._h:
            return
        if self._lib.rt_store_seal(self._h, self._key(object_id)) != 0:
            raise KeyError("seal: object not in CREATED state")

    def locate(self, object_id):
        """(offset, size) of the object inside the arena file; PINS the
        object (call release() when done) so the slot cannot be
        recycled while a reader (zero-copy view or same-host peer
        reading the file directly) is live."""
        if not self._h:
            raise KeyError("store closed")
        off = ctypes.c_uint64()
        size = ctypes.c_uint64()
        rc = self._lib.rt_store_get(self._h, self._key(object_id),
                                    ctypes.byref(off), ctypes.byref(size))
        if rc != 0:
            raise KeyError("object not found/sealed")
        return off.value, size.value

    def get(self, object_id) -> memoryview:
        """Zero-copy read view; pins the object (call release() when
        done, plasma client semantics)."""
        off, size = self.locate(object_id)
        return self._view[off:off + size]

    def contains(self, object_id) -> bool:
        if not self._h:
            return False
        return bool(self._lib.rt_store_contains(self._h,
                                                self._key(object_id)))

    def release(self, object_id):
        # Pins can outlive an explicit close() (live zero-copy views at
        # shutdown); a released handle must be a no-op, not a segfault.
        if not self._h:
            return
        self._lib.rt_store_release(self._h, self._key(object_id))

    def delete(self, object_id):
        if not self._h:
            return
        rc = self._lib.rt_store_delete(self._h, self._key(object_id))
        if rc == -2:
            raise RuntimeError("object pinned by a reader")

    # -- stats -------------------------------------------------------------
    def used_bytes(self) -> int:
        return self._lib.rt_store_used(self._h) if self._h else 0

    def capacity(self) -> int:
        return self._lib.rt_store_capacity(self._h) if self._h else 0

    def num_objects(self) -> int:
        return self._lib.rt_store_num_objects(self._h) if self._h else 0

    def evictions(self) -> int:
        return self._lib.rt_store_evictions(self._h) if self._h else 0

    def close(self, unlink: bool = False):
        if self._h:
            try:
                self._view.release()
                self._map.close()
            except (BufferError, ValueError):
                pass
            self._lib.rt_store_close(self._h)
            if unlink:
                self._lib.rt_store_unlink(self.path.encode())
            self._h = None


class TransferServer:
    """Serves this node's arena to peers (reference: ObjectManager server
    side)."""

    def __init__(self, store: NativeStore, port: int = 0):
        self._lib = store._lib
        self._h = self._lib.rt_transfer_serve(store._h, port)
        if not self._h:
            raise RuntimeError("failed to start transfer server")
        self.port = self._lib.rt_transfer_port(self._h)

    def stop(self):
        if self._h:
            self._lib.rt_transfer_stop(self._h)
            self._h = None


def pull(local: NativeStore, host: str, port: int, object_id) -> None:
    """Pull one object from a peer into the local arena (reference:
    PullManager)."""
    rc = local._lib.rt_transfer_pull(
        local._h, host.encode(), port, NativeStore._key(object_id))
    if rc == -2:
        raise KeyError("object not on remote")
    if rc != 0:
        raise RuntimeError(f"pull failed (rc={rc})")
