"""ctypes binding for the native arena object store (native/objstore.cc).

The C++ library owns placement (first-fit free list with coalescing), pin
counts, and LRU ordering; this wrapper owns lifecycle and hands out
zero-copy memoryviews into the arena (numpy `frombuffer` reads straight
from shared memory — the plasma zero-copy-deserialize property,
/root/reference/src/ray/object_manager/plasma/store.h:55).

Build: `sh native/build.sh` (also attempted lazily on first use).
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import threading
from typing import Optional

_LIB_PATH = os.path.join(os.path.dirname(__file__), "_native", "libobjstore.so")
_BUILD_SCRIPT = os.path.join(
    os.path.dirname(__file__), "..", "..", "native", "build.sh"
)

_lib = None
_lib_lock = threading.Lock()

# Must match store_abi_version() in native/objstore.cc. A stale prebuilt
# .so (artifacts are not in VCS) would otherwise be driven with the wrong
# signatures — silently, via ctypes.
_ABI_VERSION = 3


class NativeStoreUnavailable(RuntimeError):
    """The native arena cannot be loaded; the message says why (no g++,
    the build failed, or the built library has another ABI)."""


def _build() -> None:
    """Build libobjstore.so from native/objstore.cc — the .so is not in
    git, so a fresh checkout builds it on first use."""
    if not os.path.exists(_BUILD_SCRIPT):
        raise NativeStoreUnavailable(f"build script missing: {_BUILD_SCRIPT}")
    if shutil.which("g++") is None:
        raise NativeStoreUnavailable("g++ is not installed on this machine")
    try:
        subprocess.run(
            ["sh", _BUILD_SCRIPT], capture_output=True, check=True, timeout=120
        )
    except subprocess.CalledProcessError as exc:
        raise NativeStoreUnavailable(
            f"native/build.sh failed (rc={exc.returncode}): "
            f"{exc.stderr.decode(errors='replace')[-400:]}"
        ) from exc
    except subprocess.TimeoutExpired as exc:
        raise NativeStoreUnavailable("native/build.sh timed out") from exc


def _abi_matches(path: str) -> bool:
    try:
        probe = ctypes.CDLL(path)
        fn = getattr(probe, "store_abi_version", None)
        if fn is None:
            return False
        fn.restype = ctypes.c_uint64
        fn.argtypes = [ctypes.c_void_p]
        return fn(None) == _ABI_VERSION
    except OSError:
        return False


def _load_lib() -> ctypes.CDLL:
    """The loaded library, building it first when the .so is missing or
    stale. Raises NativeStoreUnavailable with the reason otherwise."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_LIB_PATH) or not _abi_matches(_LIB_PATH):
            # missing or stale: rebuild (writes a fresh inode, so the CDLL
            # below maps the new code even if a stale handle exists)
            _build()
        if not os.path.exists(_LIB_PATH) or not _abi_matches(_LIB_PATH):
            raise NativeStoreUnavailable(
                f"{_LIB_PATH} does not report ABI {_ABI_VERSION} after a "
                "rebuild (native/objstore.cc and native_store.py disagree)"
            )
        lib = ctypes.CDLL(_LIB_PATH)
        lib.store_create_arena.restype = ctypes.c_void_p
        lib.store_create_arena.argtypes = [ctypes.c_uint64]
        lib.store_create_arena_shared.restype = ctypes.c_void_p
        lib.store_create_arena_shared.argtypes = [
            ctypes.c_uint64, ctypes.c_char_p
        ]
        lib.store_destroy_arena.argtypes = [ctypes.c_void_p]
        lib.store_create.restype = ctypes.c_int64
        lib.store_create.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]
        lib.store_seal.restype = ctypes.c_int
        lib.store_seal.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.store_get.restype = ctypes.c_int64
        lib.store_get.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.store_unpin.restype = ctypes.c_int
        lib.store_unpin.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.store_delete.restype = ctypes.c_int
        lib.store_delete.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.store_make_evictable.restype = ctypes.c_int
        lib.store_make_evictable.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.store_lru_candidate.restype = ctypes.c_int
        lib.store_lru_candidate.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        for name in ("store_used", "store_capacity", "store_num_objects",
                     "store_num_free_blocks"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_uint64
            fn.argtypes = [ctypes.c_void_p]
        lib.store_base.restype = ctypes.c_void_p
        lib.store_base.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    """True when the native arena can be used (builds it if needed). For
    callers that may go without it; code that was told to use the arena
    calls NativeArena and lets NativeStoreUnavailable say why it cannot."""
    try:
        _load_lib()
    except NativeStoreUnavailable:
        return False
    return True


class NativeArena:
    """One process-local arena. Not a singleton: the tiered ObjectStore owns
    one as its shared-memory tier; tests create scratch arenas freely."""

    def __init__(self, capacity: int, path: "Optional[str]" = None):
        """path=None: process-private malloc arena. path=str: the arena
        pages live in that file (put it under /dev/shm) mapped
        MAP_SHARED — worker processes mmap the same file and read sealed
        payloads zero-copy via (offset, size) descriptors (the plasma
        client protocol, plasma/store.h:55; descriptors ride the worker
        pipes instead of a unix socket)."""
        self._lib = lib = _load_lib()
        self.path = path
        if path is None:
            self._arena = lib.store_create_arena(capacity)
        else:
            self._arena = lib.store_create_arena_shared(
                capacity, path.encode()
            )
        if not self._arena:
            raise MemoryError(f"cannot allocate {capacity}-byte arena")
        self._base = lib.store_base(self._arena)
        self._closed = False

    def put(self, object_id: int, payload: bytes | memoryview,
            evictable: bool = True) -> bool:
        """Copy payload into the arena and seal. False if it cannot fit even
        after the caller's spill loop should run (use lru_candidate).

        evictable=False leaves the object out of the LRU (readable but
        never an eviction victim) until make_evictable() — lets a caller
        finish its own bookkeeping before eviction can race with it."""
        view = memoryview(payload)
        size = view.nbytes
        offset = self._lib.store_create(self._arena, object_id, size)
        if offset < 0:
            return False
        ctypes.memmove(self._base + offset, (ctypes.c_char * size).from_buffer_copy(view), size)
        self._lib.store_seal(self._arena, object_id)
        if evictable:
            self._lib.store_make_evictable(self._arena, object_id)
        return True

    def make_evictable(self, object_id: int) -> None:
        self._lib.store_make_evictable(self._arena, object_id)

    def get(self, object_id: int) -> Optional[memoryview]:
        """Zero-copy view, pinned until `unpin(object_id)`."""
        size = ctypes.c_uint64()
        offset = self._lib.store_get(self._arena, object_id, ctypes.byref(size))
        if offset < 0:
            return None
        buf = (ctypes.c_char * size.value).from_address(self._base + offset)
        return memoryview(buf)

    def descriptor(self, object_id: int):
        """(path, offset, size) of a sealed object, PINNED until
        release_descriptor — the cross-process handle a worker mmaps.
        None for private arenas or absent objects."""
        if self.path is None:
            return None
        size = ctypes.c_uint64()
        offset = self._lib.store_get(self._arena, object_id, ctypes.byref(size))
        if offset < 0:
            return None
        return (self.path, int(offset), int(size.value))

    def release_descriptor(self, object_id: int) -> None:
        self.unpin(object_id)

    def unpin(self, object_id: int) -> None:
        self._lib.store_unpin(self._arena, object_id)

    def delete(self, object_id: int) -> bool:
        return self._lib.store_delete(self._arena, object_id) == 0

    def lru_candidate(self) -> Optional[int]:
        out = ctypes.c_uint64()
        rc = self._lib.store_lru_candidate(self._arena, ctypes.byref(out))
        return None if rc != 0 else int(out.value)

    def put_with_eviction(
        self, object_id: int, payload, on_evict=None, on_evicted=None,
        evictable: bool = True,
    ) -> bool:
        """put(), evicting LRU objects until it fits.

        on_evict(id, view) runs before each deletion (the spill-prepare
        hook); on_evicted(id) runs only after the arena block is actually
        freed (the commit hook) — if delete fails (e.g. a concurrent get
        pinned the victim), the caller's bookkeeping is left untouched.
        """
        while True:
            if self.put(object_id, payload, evictable=evictable):
                return True
            victim = self.lru_candidate()
            if victim is None:
                return False
            if on_evict is not None:
                view = self.get(victim)
                try:
                    on_evict(victim, view)
                finally:
                    self.unpin(victim)
            if not self.delete(victim):
                return False
            if on_evicted is not None:
                on_evicted(victim)

    @property
    def used(self) -> int:
        return self._lib.store_used(self._arena)

    @property
    def capacity(self) -> int:
        return self._lib.store_capacity(self._arena)

    @property
    def num_objects(self) -> int:
        return self._lib.store_num_objects(self._arena)

    @property
    def num_free_blocks(self) -> int:
        return self._lib.store_num_free_blocks(self._arena)

    def close(self) -> None:
        if not self._closed:
            self._lib.store_destroy_arena(self._arena)
            if self.path is not None:
                try:
                    os.unlink(self.path)
                except OSError:
                    pass
            self._closed = True

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ------------------------------------------------------- cross-process views


_worker_mmaps: dict = {}
_worker_mmaps_lock = threading.Lock()


def _materialize_view(path: str, offset: int, count: int, dtype_str: str,
                      shape: tuple):
    """Worker-side half of the descriptor protocol: mmap the arena file
    once per process (read-only) and return a zero-copy numpy view of
    the sealed payload. Objects are immutable (plasma semantics): the
    returned array is read-only; mutate via .copy()."""
    import mmap as _mmap

    import numpy as np

    with _worker_mmaps_lock:
        mm = _worker_mmaps.get(path)
        if mm is None:
            fd = os.open(path, os.O_RDONLY)
            try:
                mm = _mmap.mmap(fd, 0, prot=_mmap.PROT_READ)
            finally:
                os.close(fd)
            _worker_mmaps[path] = mm
    arr = np.frombuffer(
        mm, dtype=np.dtype(dtype_str), count=count, offset=offset
    )
    return arr.reshape(shape)


class ShmView:
    """Pickles as a descriptor, unpickles as a read-only zero-copy numpy
    view over the shared arena (the plasma client handoff: bytes never
    cross the worker pipe)."""

    __slots__ = ("path", "offset", "count", "dtype_str", "shape")

    def __init__(self, path: str, offset: int, count: int, dtype_str: str,
                 shape: tuple):
        self.path = path
        self.offset = offset
        self.count = count
        self.dtype_str = dtype_str
        self.shape = tuple(shape)

    def __reduce__(self):
        return (
            _materialize_view,
            (self.path, self.offset, self.count, self.dtype_str, self.shape),
        )
