"""In-memory, tiered object store with spilling.

Plasma-equivalent for a single host. The reference keeps one shared-memory
store per node served from the raylet (/root/reference/src/ray/object_manager/
plasma/store.h:55) with LRU eviction (eviction_policy.h:159) and fallback
allocation / spilling to disk (raylet/local_object_manager.h:42). Our design
differs deliberately:

- **Device tier is first-class.** On TPU the valuable objects are jax.Arrays
  living in HBM. Plasma assumes host shared memory; we instead keep *handles*
  to device buffers and only materialize host copies on spill. HBM pressure
  is XLA's job; the store tracks but does not allocate device memory.
- **In-process by default.** Ray needs shared memory because every worker is
  a separate OS process doing fine-grained microtasks. Our hot loop is a
  compiled XLA program; Python-level tasks default to threads, so objects
  pass by reference with zero copies. A native shared-memory tier
  (ray_tpu/core/_native) backs multi-process CPU workers.

Eviction: LRU over unpinned, sealed, host-tier objects; spill to a disk
directory before dropping (reference: local_object_manager.h:112 SpillObjects).
Entries record the creating task for lineage-based recovery
(reference: object_recovery_manager.h:43).
"""

from __future__ import annotations

import enum
import os
import pickle
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional

from .exceptions import GetTimeoutError, ObjectLostError, TaskError
from .ids import ObjectID


class Tier(enum.Enum):
    INLINE = "inline"      # small host objects, kept as-is in process
    HOST = "host"          # large host objects (numpy etc.), spillable
    DEVICE = "device"      # jax.Array handles (HBM); spill via host copy
    SHM = "shm"            # native arena (ray_tpu/core/_native), numpy only
    SPILLED = "spilled"    # on disk
    REMOTE = "remote"      # value lives in another node's store (cluster)


# Tier thresholds come from the central flag registry (config.py):
# inline_max_bytes mirrors the reference task_transport inline cutoff;
# shm_min_bytes gates placement into the native arena.


def _estimate_nbytes(value: Any) -> int:
    nbytes = getattr(value, "nbytes", None)
    if isinstance(nbytes, int):
        return nbytes
    if isinstance(value, (bytes, bytearray)):
        return len(value)
    if isinstance(value, str):
        return len(value)
    # Cheap structural estimate; exact size does not matter for eviction
    # decisions, only relative magnitude.
    if isinstance(value, (list, tuple)):
        return 64 + sum(_estimate_nbytes(v) for v in value[:100]) * max(1, len(value) // max(1, min(len(value), 100)))
    if isinstance(value, dict):
        items = list(value.items())[:100]
        per = sum(_estimate_nbytes(k) + _estimate_nbytes(v) for k, v in items)
        return 64 + per * max(1, len(value) // max(1, min(len(value), 100)))
    return 64


def _is_device_array(value: Any) -> bool:
    # Duck-typed so the store never imports jax (keeps core import light).
    t = type(value)
    return t.__module__.startswith("jax") and t.__name__ in ("Array", "ArrayImpl")


class _RemoteFetchFailed(Exception):
    """Internal: a REMOTE-tier fetch-through failed (owner unreachable)."""

    def __init__(self, object_id, address):
        super().__init__(f"fetch of {object_id} from {address} failed")


class ObjectState(enum.Enum):
    PENDING = "pending"   # task not finished yet
    READY = "ready"
    ERROR = "error"       # creating task raised
    LOST = "lost"         # evicted without spill, or node died


class ObjectEntry:
    __slots__ = (
        "object_id", "state", "value", "error", "tier", "nbytes",
        "pin_count", "event", "callbacks", "spill_path", "owner_task",
        "last_access", "lock", "handle_count", "gc_on_seal", "remote_addr",
        "foreign", "owner_addr", "gc_done", "borrow_failed", "fetch_addr",
        "custodial",
    )

    def __init__(self, object_id: ObjectID):
        self.object_id = object_id
        self.state = ObjectState.PENDING
        self.value: Any = None
        self.error: Optional[BaseException] = None
        self.tier = Tier.INLINE
        self.nbytes = 0
        self.pin_count = 0
        self.event = threading.Event()
        self.callbacks: List[Callable[["ObjectEntry"], None]] = []
        self.spill_path: Optional[str] = None
        # TaskSpec of the creating task, for lineage reconstruction.
        self.owner_task = None
        self.last_access = time.monotonic()
        # RLock: _restore (under this lock, via get) may trigger _maybe_spill
        # which revisits the same entry.
        self.lock = threading.RLock()
        # Live ObjectRef handles (reference: ReferenceCounter local refs,
        # reference_count.h:72). 0 handles + sealed → value is GC-eligible.
        self.handle_count = 0
        self.gc_on_seal = False
        # Address of the executing node still holding a copy (cluster):
        # set by seal_remote, kept across fetch-through so releasing this
        # entry can free the remote copy too.
        self.remote_addr: Optional[str] = None
        # True when this entry was created for a ref that arrived from
        # ANOTHER process (nothing local will ever seal it) — the only
        # entries worth a GCS object-directory lookup on get().
        self.foreign = False
        # Borrowed reference (reference: reference_count.h:72 borrows):
        # the address of the OWNING process whose refcount pins the
        # value. get() pulls from there; releasing this entry sends an
        # unborrow (never a free — other borrowers may exist).
        self.owner_addr: Optional[str] = None
        # One-shot latch: the value was released by GC. Two racing
        # last-releasers (concurrent unborrows, unborrow vs decref) must
        # not double-run the non-idempotent accounting in _release_value.
        self.gc_done = False
        # The borrow registration for this (borrowed) ref exhausted its
        # retry budget: a later loss is plausibly the borrow protocol's
        # fault, not the object's — surfaced in ObjectLostError's note.
        self.borrow_failed = False
        # Where the VALUE physically lives, when that differs from the
        # owner (arg locality: pull peer-to-peer, borrow at the owner).
        self.fetch_addr: Optional[str] = None
        # This store holds the value ON THE OWNER'S BEHALF (a parked /
        # big task result awaiting pulls): local handle death must not
        # release it — only the owner's free_object (or node teardown)
        # may. Without this, a ref unpickled in the producing agent
        # would free the primary copy when the task's args were GC'd.
        self.custodial = False


def _reap_stale_arenas(shm_dir: str) -> None:
    """Unlink arena files whose owning process is gone: a SIGKILLed
    driver must not leak RAM-backed tmpfs files forever (the names embed
    the creator's pid exactly so this sweep can tell)."""
    try:
        names = os.listdir(shm_dir)
    except OSError:
        return
    for name in names:
        if not name.startswith("ray_tpu_arena_"):
            continue
        parts = name.split("_")
        try:
            pid = int(parts[3])
        except (IndexError, ValueError):
            continue
        try:
            os.kill(pid, 0)  # probe: raises if the pid is gone
        except ProcessLookupError:
            try:
                os.unlink(os.path.join(shm_dir, name))
            except OSError:
                pass
        except PermissionError:
            pass  # someone else's live process


class ObjectStore:
    """Thread-safe object table with futures semantics and LRU spilling."""

    def __init__(self, capacity_bytes: int = 8 << 30, spill_dir: Optional[str] = None):
        from .config import cfg

        self._entries: "OrderedDict[ObjectID, ObjectEntry]" = OrderedDict()  # guarded-by: _lock
        self._lock = threading.RLock()
        self._capacity = capacity_bytes
        self._inline_max = cfg.inline_max_bytes
        self._shm_min = cfg.shm_min_bytes
        self._host_bytes = 0
        self._device_bytes = 0
        self._spill_dir = spill_dir
        self.stats = {
            "puts": 0, "gets": 0, "spills": 0, "restores": 0, "evictions": 0,
            "shm_puts": 0, "shm_evictions": 0, "reconstructions": 0, "gc": 0,
            "spilled_bytes": 0, "restored_bytes": 0,
        }
        # Opt-in native shared-memory tier (plasma-equivalent arena) for
        # large numpy payloads. In-process workers pass objects by reference
        # already, so this buys bounded accounting + native LRU eviction and
        # is the substrate for multi-process CPU workers. Asked for and not
        # loadable (no g++, failed build, stale ABI) is an error that says
        # which: the store never drops to the Python tier on its own.
        self._arena = None
        if cfg.native_store:
            import tempfile
            import uuid as _uuid

            from .native_store import NativeArena

            # SHARED arena file (plasma-style): worker processes mmap it
            # and read sealed payloads zero-copy via descriptors
            # (resolve_process_args below)
            shm_dir = (
                "/dev/shm" if os.path.isdir("/dev/shm")
                else tempfile.gettempdir()
            )
            _reap_stale_arenas(shm_dir)
            path = os.path.join(
                shm_dir,
                f"ray_tpu_arena_{os.getpid()}_{_uuid.uuid4().hex[:8]}",
            )
            self._arena = NativeArena(capacity_bytes, path=path)
        self._shm_entries: Dict[int, ObjectID] = {}  # arena id -> object id  # guarded-by: _lock
        # Lineage resubmission hook (Runtime wires scheduler.submit here):
        # get() of a LOST entry with a recorded owner_task re-executes it
        # (reference: ObjectRecoveryManager, object_recovery_manager.h:43).
        self._resubmit: Optional[Callable[[Any], None]] = None
        self._reconstruct_lock = threading.Lock()
        self.max_reconstructions = 3
        # Cluster hooks (set by core.cluster.ClusterContext):
        # _fetch_remote(object_id, address) pulls a REMOTE-tier value over
        # the wire; _locate(object_id) asks the GCS object directory for
        # the address of an object this process has never seen (reference:
        # ownership_based_object_directory.h:39 + pull_manager.h:57).
        self._fetch_remote: Optional[Callable[[ObjectID, str], Any]] = None
        self._locate: Optional[Callable[[ObjectID], Optional[str]]] = None
        self._free_remote: Optional[Callable[[ObjectID, str], None]] = None
        self._unborrow: Optional[Callable[[ObjectID, str], None]] = None
        # owner-side borrow registry: object id -> borrower addresses
        self._borrowers: Dict[ObjectID, set] = {}  # guarded-by: _lock

    def set_resubmit(self, fn: Callable[[Any], None]) -> None:
        self._resubmit = fn

    def set_cluster_hooks(self, fetch_remote, locate, free_remote=None,
                          unborrow=None) -> None:
        self._fetch_remote = fetch_remote
        self._locate = locate
        self._free_remote = free_remote
        self._unborrow = unborrow

    # ----------------------------------------------------------- borrows
    # Cross-process borrowed references: a peer that unpickled one of our
    # refs pins the value here until it unborrows (reference: borrower
    # bookkeeping in reference_count.h:72). Pins block GC/eviction.
    # Borrows are keyed by the borrowing process's address so an
    # unborrow whose matching borrow registration was LOST in transit
    # can never release a pin that belongs to a different live borrower.

    def add_borrow(self, object_id: ObjectID, borrower: str) -> bool:
        entry = self.entry(object_id)
        if entry is None:
            return False  # already gone: the borrower's get() will fail
        with self._lock:
            holders = self._borrowers.setdefault(object_id, set())
            if borrower in holders:
                return True  # duplicate registration: one pin per borrower
            holders.add(borrower)
        self.pin(object_id)
        return True

    def remove_borrow(self, object_id: ObjectID, borrower: str) -> None:
        with self._lock:
            holders = self._borrowers.get(object_id)
            if holders is None or borrower not in holders:
                return  # no matching recorded borrow: nothing to release
            holders.discard(borrower)
            if not holders:
                del self._borrowers[object_id]
        entry = self.entry(object_id)
        if entry is None:
            return
        self.unpin(object_id)
        with entry.lock:
            gc_now = (
                entry.pin_count == 0
                and entry.handle_count == 0
                and entry.event.is_set()
            )
        if gc_now:
            # last borrower left after the owner's handles died: the
            # deferred GC the pin was blocking runs now
            self._gc_entry(entry)

    def release_borrows_from(self, borrower: str) -> int:
        """Drop every borrow a (dead) borrower registered — its unborrows
        will never arrive, and a crashed agent must not pin values here
        forever. Returns how many borrows were released."""
        with self._lock:
            doomed = [
                oid for oid, holders in self._borrowers.items()
                if borrower in holders
            ]
        for oid in doomed:
            self.remove_borrow(oid, borrower)
        return len(doomed)

    # ------------------------------------------------------------------ write

    def create(self, object_id: ObjectID, owner_task=None) -> ObjectEntry:
        """Register a pending object (a task return slot)."""
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None:
                entry = ObjectEntry(object_id)
                self._entries[object_id] = entry
            if owner_task is not None:
                # never CLEAR recorded lineage: a result push from a node
                # agent (object_transfer._push_end) calls create() without
                # an owner, and wiping the submit-time spec would break
                # reconstruction of exactly the objects that cross the wire
                entry.owner_task = owner_task
            return entry

    def put(self, object_id: ObjectID, value: Any, owner_task=None) -> ObjectEntry:
        """Seal a value into the store (create + fulfill in one step)."""
        entry = self.create(object_id, owner_task=owner_task)
        self.seal(object_id, value)
        return entry

    def _try_shm_seal(self, object_id: ObjectID, value: Any, nbytes: int):
        """Place a large numpy array into the native arena; returns the
        SHM metadata value, or None to fall through to the host tier.

        Runs OUTSIDE the store lock: put_with_eviction may spill victims
        to disk (pickle I/O in _on_arena_evict), and the arena has its own
        internal mutex. Only the _shm_entries map is touched under the
        store lock."""
        import numpy as np

        if (
            self._arena is None
            or not isinstance(value, np.ndarray)
            or value.dtype == object
            or nbytes < self._shm_min
        ):
            return None
        # Arena ids are 64-bit. Hash the FULL object id: the bit-layout puts
        # the return-index in the trailing bytes, so a prefix truncation
        # collides for every return of the same task.
        import hashlib

        aid = int.from_bytes(
            hashlib.blake2b(object_id.hex().encode(), digest_size=8).digest(), "big"
        )
        with self._lock:
            # Hash collision with a live object: fall through to the host
            # tier instead of letting store_create's duplicate-id failure
            # masquerade as out-of-space and trigger an eviction storm.
            if aid in self._shm_entries:
                return None
            # Register the aid→oid mapping BEFORE placement so a concurrent
            # seal's eviction hooks can always resolve this block.
            self._shm_entries[aid] = object_id
        contiguous = np.ascontiguousarray(value)
        # evictable=False: the block is readable but NOT an LRU candidate
        # until seal() commits the entry under the store lock and calls
        # make_evictable — a concurrent seal's eviction can never observe
        # a half-sealed object (block present, entry meta not yet written).
        ok = False
        try:
            ok = self._arena.put_with_eviction(
                aid,
                contiguous.reshape(-1).view(np.uint8).data,
                on_evict=self._on_arena_evict,
                on_evicted=self._on_arena_evicted,
                evictable=False,
            )
        finally:
            if not ok:  # failure OR a raising spill hook: unregister the aid
                with self._lock:
                    self._shm_entries.pop(aid, None)
        if not ok:
            return None
        self.stats["shm_puts"] += 1
        return ("__shm__", aid, str(value.dtype), value.shape)

    def seal(self, object_id: ObjectID, value: Any) -> None:
        nbytes = _estimate_nbytes(value)
        # Arena placement (and any victim spilling it triggers) happens
        # before taking the store lock — disk I/O must never run under it.
        shm_meta = self._try_shm_seal(object_id, value, nbytes)
        with self._lock:
            entry = self._entries[object_id]
        # entry.lock BEFORE the store lock (the established order): the
        # re-seal path below releases the old READY value, and a concurrent
        # get() holding entry.lock mid-_restore/_shm_get must never have
        # spill_path unlinked or value cleared under it.
        with entry.lock, self._lock:
            if entry.state == ObjectState.READY:
                # Re-seal: a lineage reconstruction raced the original
                # execution and both sealed. Replace, releasing the old
                # value's accounting so bytes don't double-count.
                self._release_value(entry)
            if shm_meta is not None:
                tier = Tier.SHM
                value = shm_meta
            elif _is_device_array(value):
                tier = Tier.DEVICE
                self._device_bytes += nbytes
            elif nbytes <= self._inline_max:
                tier = Tier.INLINE
                self._host_bytes += nbytes
            else:
                tier = Tier.HOST
                self._host_bytes += nbytes
            entry.value = value
            entry.nbytes = nbytes
            entry.tier = tier
            entry.state = ObjectState.READY
            entry.gc_done = False  # a re-seal makes the entry collectable again
            entry.last_access = time.monotonic()
            callbacks = list(entry.callbacks)
            entry.callbacks.clear()
        if shm_meta is not None:
            # entry committed: the arena block may now become an LRU victim
            self._arena.make_evictable(shm_meta[1])
        self.stats["puts"] += 1
        entry.event.set()
        for cb in callbacks:
            cb(entry)
        if entry.gc_on_seal:
            # every handle died while the task was still running
            entry.gc_on_seal = False
            self._gc_entry(entry)
        # Spill/evict outside the store lock: disk I/O must not block
        # unrelated puts/gets (the reference spills asynchronously too,
        # local_object_manager.h:112).
        self._maybe_spill()

    def seal_remote(self, object_id: ObjectID, address: str,
                    nbytes: int = 0) -> None:
        """Seal an object as a remote placeholder: the value stays in the
        store of the node at `address` (its ObjectTransferServer); get()
        fetches through on first access and caches locally. No-op if the
        value already arrived (e.g. a push raced the location reply).
        `nbytes` (when the producer reported it) feeds arg-locality
        scheduling before the value is ever pulled."""
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None:
                entry = self.create(object_id)
        with entry.lock, self._lock:
            if entry.state == ObjectState.READY:
                return
            entry.value = address
            entry.remote_addr = address
            if nbytes:
                entry.nbytes = nbytes
            entry.tier = Tier.REMOTE
            entry.state = ObjectState.READY
            entry.gc_done = False
            entry.error = None
            entry.last_access = time.monotonic()
            callbacks = list(entry.callbacks)
            entry.callbacks.clear()
        entry.event.set()
        for cb in callbacks:
            cb(entry)
        if entry.gc_on_seal:
            # every handle died while the task ran remotely: GC now — the
            # _gc_entry path also frees the agent-side parked copy and the
            # objdir entry via remote_addr (same contract as seal())
            entry.gc_on_seal = False
            self._gc_entry(entry)

    def _fetch_through(self, entry: ObjectEntry) -> Any:
        """Pull a REMOTE-tier value from its owner and cache it locally.
        Caller holds entry.lock (same discipline as _restore: only access
        to THIS object blocks on the wire). On failure the entry drops to
        LOST so the get() loop can lineage-reconstruct."""
        address = entry.value
        try:
            value = self._fetch_remote(entry.object_id, address)
        except Exception:
            # a peer-located pull can fall back to the owner, which can
            # always materialize its own object (the slow path we tried
            # to avoid, but correct)
            fallback = entry.owner_addr
            if not (fallback and fallback != address):
                entry.value = None
                entry.remote_addr = None  # owner unreachable: nothing to free
                entry.state = ObjectState.LOST
                entry.event.set()
                raise _RemoteFetchFailed(entry.object_id, address)
            try:
                value = self._fetch_remote(entry.object_id, fallback)
            except Exception:
                entry.value = None
                entry.remote_addr = None
                entry.state = ObjectState.LOST
                entry.event.set()
                raise _RemoteFetchFailed(entry.object_id, fallback)
        nbytes = _estimate_nbytes(value)
        with self._lock:
            entry.value = value
            entry.nbytes = nbytes
            if _is_device_array(value):
                entry.tier = Tier.DEVICE
                self._device_bytes += nbytes
            else:
                entry.tier = Tier.INLINE if nbytes <= self._inline_max else Tier.HOST
                self._host_bytes += nbytes
        return value

    def seal_error(self, object_id: ObjectID, error: BaseException) -> None:
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None:
                entry = self.create(object_id)
            entry.error = error
            entry.state = ObjectState.ERROR
            callbacks = list(entry.callbacks)
            entry.callbacks.clear()
        entry.event.set()
        for cb in callbacks:
            cb(entry)
        if entry.gc_on_seal:
            entry.gc_on_seal = False
            self._gc_entry(entry)

    # ------------------------------------------------------------------- read

    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            return object_id in self._entries

    def entry(self, object_id: ObjectID) -> Optional[ObjectEntry]:
        with self._lock:
            return self._entries.get(object_id)

    def is_ready(self, object_id: ObjectID) -> bool:
        entry = self.entry(object_id)
        return entry is not None and entry.event.is_set()

    def add_ready_callback(self, object_id: ObjectID, cb: Callable[[ObjectEntry], None]) -> None:
        """Invoke cb(entry) once the object is sealed (or errored).

        Runs immediately (in the calling thread) if already sealed. This is
        the dependency-resolution hook — the scheduler's equivalent of the
        reference LocalDependencyResolver (core_worker/transport/
        dependency_resolver.h:32).
        """
        run_now = False
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None:
                entry = self.create(object_id)
            if entry.event.is_set():
                run_now = True
            else:
                entry.callbacks.append(cb)
        if run_now:
            cb(entry)

    def remove_ready_callback(self, object_id: ObjectID, cb: Callable[[ObjectEntry], None]) -> None:
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is not None and cb in entry.callbacks:
                entry.callbacks.remove(cb)

    def get(self, object_id: ObjectID, timeout: Optional[float] = None) -> Any:
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None:
                entry = self.create(object_id)
                entry.foreign = True  # no local producer registered it
        deadline = None if timeout is None else time.monotonic() + timeout
        if entry.owner_addr is not None and not entry.event.is_set():
            # borrowed ref: pull from where the value lives (a peer node
            # when the dispatcher knew better, else the owner) — no
            # directory RPC either way
            self.seal_remote(object_id, entry.fetch_addr or entry.owner_addr)
        if (
            self._locate is not None
            and entry.foreign
            and not entry.event.is_set()
        ):
            # A ref that crossed from another process: nothing local will
            # ever seal it — a push may arrive, or the value sits in a
            # remote store registered in the GCS object directory
            # (reference: OwnershipBasedObjectDirectory lookup on pull).
            # POLL the directory while waiting: the producer may register
            # the location after this get() started (a task still
            # running, or the objdir write racing us by milliseconds).
            # Locally-owned pending entries never pay this RPC. The poll
            # is BOUNDED (foreign_locate_max_s): if no location is ever
            # registered — producing node died pre-registration, or a
            # stale ref was unpickled — the entry drops to LOST so the
            # lineage/ObjectLostError path runs instead of spinning
            # forever on an infinite timeout.
            from .config import cfg

            poll = 0.02
            give_up = time.monotonic() + cfg.foreign_locate_max_s
            while not entry.event.is_set():
                try:
                    address = self._locate(object_id)
                except Exception:
                    address = None
                if address:
                    self.seal_remote(object_id, address)
                    break
                now = time.monotonic()
                remaining = None if deadline is None else deadline - now
                if remaining is not None and remaining <= 0:
                    raise GetTimeoutError(
                        f"Get timed out after {timeout}s waiting for "
                        f"{object_id} (no location registered)"
                    )
                if now >= give_up:
                    with entry.lock:
                        if not entry.event.is_set():
                            entry.state = ObjectState.LOST
                            entry.event.set()
                    break
                wait_s = poll if remaining is None else min(poll, remaining)
                entry.event.wait(wait_s)
                poll = min(poll * 2, 1.0)
        reconstructions = 0
        restored = False
        while True:
            remaining = None if deadline is None else deadline - time.monotonic()
            if not entry.event.wait(remaining):
                raise GetTimeoutError(
                    f"Get timed out after {timeout}s waiting for {object_id}"
                )
            # Everything below re-validates under entry.lock: between the
            # wait and here, a reconstruction may have flipped the entry
            # back to PENDING (clearing the event), or eviction may have
            # flipped READY→LOST. Act only on the state actually held.
            done = False
            with entry.lock:
                state = entry.state
                if state == ObjectState.ERROR:
                    self.stats["gets"] += 1
                    raise entry.error
                if state == ObjectState.READY:
                    entry.last_access = time.monotonic()
                    if entry.tier == Tier.SPILLED:
                        value = self._restore(entry)
                        restored = True
                        done = True
                    elif entry.tier == Tier.SHM:
                        value = self._shm_get(entry)
                        done = True
                    elif entry.tier == Tier.REMOTE:
                        try:
                            value = self._fetch_through(entry)
                            # the fetched bytes count against capacity the
                            # same as a disk restore: spill-check after
                            restored = True
                            done = True
                        except _RemoteFetchFailed:
                            # owner died: entry is LOST now; fall through to
                            # the lineage-reconstruction branch below
                            state = ObjectState.LOST
                    else:
                        value = entry.value
                        done = True
            if done:
                break
            if state == ObjectState.LOST:
                # Lineage reconstruction: re-execute the recorded creating
                # task (reference object_recovery_manager.h:43) and wait
                # again. Bounded so a deterministic failure cannot loop.
                if (
                    reconstructions < self.max_reconstructions
                    and self._try_reconstruct(entry)
                ):
                    reconstructions += 1
                    continue
                raise ObjectLostError(
                    object_id,
                    "(The borrow registration to the owner failed after "
                    "retries; the owner may have GC'd the value because "
                    "this process's pin never landed.)"
                    if entry.borrow_failed else "",
                )
            # PENDING again (a reconstruction won the race): just re-wait.
        self.stats["gets"] += 1
        if restored:
            # Outside entry.lock: spilling victims takes *their* entry locks,
            # and holding one entry lock while waiting on another is an ABBA
            # deadlock between two concurrent restores.
            self._maybe_spill()
        return value

    def _try_reconstruct(self, entry: ObjectEntry) -> bool:
        """Flip a LOST entry (and its sibling returns) back to PENDING and
        resubmit the creating task. Exactly one caller wins the flip; losers
        just re-wait. False if there is no lineage to replay."""
        spec = entry.owner_task
        if spec is None or self._resubmit is None:
            return False
        # One flat lock for the flip phase: two getters reconstructing
        # different returns of the same task would otherwise take sibling
        # entry locks in opposite orders (ABBA deadlock).
        with self._reconstruct_lock:
            with entry.lock:
                if entry.state != ObjectState.LOST:
                    return True  # another getter already reconstructed
            for oid in spec.return_ids:
                sibling = self.entry(oid)
                if sibling is None:
                    continue
                with sibling.lock:
                    # a sibling still READY/SPILLED must release its value
                    # (bytes, arena block, spill file) before re-execution
                    # overwrites it — otherwise accounting drifts and SHM
                    # aids leak (their hash is deterministic per object id)
                    self._release_value(sibling)
                    sibling.state = ObjectState.PENDING
                    sibling.error = None
                    sibling.tier = Tier.INLINE
                    sibling.event.clear()
        spec.attempt = 0
        self.stats["reconstructions"] += 1
        self._resubmit(spec)
        return True

    # ---------------------------------------------------------- handle counts

    def incref(self, object_id: ObjectID) -> None:
        """A new ObjectRef handle exists for this object."""
        while True:
            with self._lock:
                entry = self._entries.get(object_id)
                if entry is None:
                    # Only a re-bound handle (unpickled after the entry was
                    # fully GC'd — or arriving from ANOTHER process) increfs
                    # a missing id. In cluster mode the object directory may
                    # know where it lives, so leave it pending+foreign for
                    # get() to locate; standalone, there is no producer, so
                    # surface the loss instead of leaving a PENDING entry
                    # nothing will ever seal (get() would hang forever).
                    entry = self.create(object_id)
                    if self._locate is not None:
                        entry.foreign = True
                    else:
                        entry.state = ObjectState.LOST
                        entry.event.set()
            with entry.lock:
                entry.handle_count += 1
                if entry.handle_count > 1:
                    return  # entry demonstrably live; no pop race possible
                # First handle back: a concurrent no-lineage GC may have
                # popped this entry between our lookup and taking entry.lock.
                # Re-check the table: if our entry still owns the slot we are
                # done; if the slot is empty, re-insert it as LOST so the
                # handle resolves to ObjectLostError instead of a later get()
                # recreating a PENDING entry nothing will ever seal; if a
                # NEWER entry took the slot, that one is authoritative —
                # undo our count on the stale entry and retry against it
                # (otherwise our eventual decref would land on the new entry
                # and release a value a live handle still guards).
                with self._lock:
                    current = self._entries.get(object_id)
                    if current is entry:
                        return
                    if current is None:
                        entry.state = ObjectState.LOST
                        entry.value = None
                        entry.event.set()
                        self._entries[object_id] = entry
                        return
                    entry.handle_count -= 1
            # loop: incref the entry that actually owns the slot now

    def decref(self, object_id: ObjectID) -> None:
        """An ObjectRef handle died. At zero handles the VALUE is released:
        the entry drops to LOST but keeps its owner_task, so a ref that
        comes back (e.g. unpickled later) can still reconstruct via lineage
        — the in-process analogue of lineage pinning (reference
        reference_count.h:72). Entries with no lineage are removed."""
        entry = self.entry(object_id)
        if entry is None:
            return
        gc_now = False
        with entry.lock:
            entry.handle_count = max(0, entry.handle_count - 1)
            if entry.handle_count == 0:
                if entry.event.is_set() or entry.owner_addr is not None:
                    # sealed, OR a borrowed foreign entry that was never
                    # get() — nothing local will ever seal it, and its
                    # unborrow must still reach the owner (releasing only
                    # on seal would pin the owner's value forever)
                    gc_now = True
                else:
                    entry.gc_on_seal = True
        if gc_now:
            self._gc_entry(entry)

    def _gc_entry(self, entry: ObjectEntry) -> None:
        with entry.lock:
            if entry.handle_count > 0 or entry.pin_count > 0:
                return  # a handle was recreated (incref) since the decref
            if entry.gc_done:
                return  # a concurrent last-releaser already ran
            if entry.custodial:
                # held for the OWNER: the local handle's death releases
                # only its borrow registration, never the value — the
                # owner's free_object is the sole release path
                if entry.owner_addr is not None and self._unborrow is not None:
                    try:
                        self._unborrow(entry.object_id, entry.owner_addr)
                    except Exception:
                        pass
                    entry.owner_addr = None
                return
            entry.gc_done = True
            self._release_value(entry)
            self.stats["gc"] += 1
            if entry.owner_task is not None:
                entry.state = ObjectState.LOST  # reconstructable via lineage
                entry.tier = Tier.INLINE
                return
            # No lineage: drop the entry while STILL holding entry.lock so
            # the liveness check and the pop are atomic with respect to a
            # concurrent incref (which increments under entry.lock and
            # re-inserts if it finds itself popped).
            with self._lock:
                self._entries.pop(entry.object_id, None)

    # ------------------------------------------------------------ ref counting

    def pin(self, object_id: ObjectID) -> None:
        entry = self.entry(object_id)
        if entry is not None:
            with entry.lock:
                entry.pin_count += 1

    def unpin(self, object_id: ObjectID) -> None:
        entry = self.entry(object_id)
        if entry is not None:
            with entry.lock:
                entry.pin_count = max(0, entry.pin_count - 1)

    def _release_value(self, entry: ObjectEntry) -> None:
        """Drop a READY entry's stored value and every resource behind it
        (byte accounting, arena block, spill file). Caller synchronizes
        (entry.lock, or the store lock on the seal/free paths — the store
        lock is re-entrant, so the internal counter updates are safe)."""
        if entry.state == ObjectState.READY:
            if entry.tier == Tier.DEVICE:
                with self._lock:
                    self._device_bytes -= entry.nbytes
            elif entry.tier in (Tier.INLINE, Tier.HOST):
                with self._lock:
                    self._host_bytes -= entry.nbytes
            elif entry.tier == Tier.SHM and self._arena is not None:
                aid = entry.value[1]
                with self._lock:
                    self._shm_entries.pop(aid, None)
                self._arena.delete(aid)
        if entry.spill_path and os.path.exists(entry.spill_path):
            os.unlink(entry.spill_path)
        if entry.owner_addr is not None:
            # borrowed value: tell the owner we are done (an unborrow,
            # NEVER a free — the owner and other borrowers may live on)
            if self._unborrow is not None:
                try:
                    self._unborrow(entry.object_id, entry.owner_addr)
                except Exception:
                    pass
            entry.owner_addr = None
            entry.remote_addr = None  # owner's copy is not ours to free
        elif entry.remote_addr is not None and self._free_remote is not None:
            # we OWN this object; the executing node still holds the
            # parked copy (whether or not we fetched it since): ask it to
            # release — best-effort, queued, never blocks under locks
            try:
                self._free_remote(entry.object_id, entry.remote_addr)
            except Exception:
                pass
            entry.remote_addr = None
        entry.spill_path = None
        entry.value = None

    def free(self, object_id: ObjectID) -> None:
        with self._lock:
            entry = self._entries.pop(object_id, None)
        if entry is not None:
            with entry.lock:
                if not entry.gc_done:  # a racing GC may have released it
                    entry.gc_done = True
                    self._release_value(entry)

    # -------------------------------------------------------------- spill/LRU

    def _maybe_spill(self) -> None:
        with self._lock:
            if self._host_bytes <= self._capacity:
                return
            # LRU over unpinned host-tier entries (victim selection only;
            # the I/O happens per-entry outside the store lock).
            candidates = sorted(
                (e for e in self._entries.values()
                 if e.state == ObjectState.READY and e.tier == Tier.HOST
                 and e.pin_count == 0),
                key=lambda e: e.last_access,
            )
        for entry in candidates:
            with self._lock:
                if self._host_bytes <= self._capacity:
                    break
            with entry.lock:
                if entry.tier != Tier.HOST or entry.pin_count > 0:
                    continue
                if self._spill_dir is not None:
                    self._spill(entry)
                else:
                    entry.value = None
                    entry.state = ObjectState.LOST
                    with self._lock:
                        self._host_bytes -= entry.nbytes
                    self.stats["evictions"] += 1

    def _shm_get(self, entry: ObjectEntry):
        """Reconstruct a numpy array from the arena. Copy-out: in-process
        consumers must not hold views into a block the allocator may
        recycle (multi-process mmap consumers will get true zero-copy)."""
        import numpy as np

        _, aid, dtype_str, shape = entry.value
        view = self._arena.get(aid)
        if view is None:  # evicted to disk between seal and get
            if entry.spill_path:
                return self._restore(entry)
            raise ObjectLostError(entry.object_id)
        try:
            return np.frombuffer(view, dtype=np.dtype(dtype_str)).reshape(shape).copy()
        finally:
            self._arena.unpin(aid)

    def _on_arena_evict(self, aid: int, view) -> None:
        """Spill-PREPARE: native LRU chose a victim — write its bytes to
        disk (if we have a spill dir) but leave all bookkeeping intact.
        The state change commits in _on_arena_evicted only after the arena
        block is actually freed, so a failed delete (victim pinned by a
        concurrent get) leaves the object fully usable in the arena."""
        import numpy as np

        with self._lock:
            object_id = self._shm_entries.get(aid)
            entry = self._entries.get(object_id) if object_id is not None else None
        if entry is None or self._spill_dir is None:
            return
        with entry.lock:
            _, _, dtype_str, shape = entry.value
            os.makedirs(self._spill_dir, exist_ok=True)
            path = os.path.join(self._spill_dir, entry.object_id.hex())
            arr = np.frombuffer(view, dtype=np.dtype(dtype_str)).reshape(shape)
            with open(path, "wb") as f:
                pickle.dump(arr.copy(), f, protocol=pickle.HIGHEST_PROTOCOL)
            entry.spill_path = path

    def _on_arena_evicted(self, aid: int) -> None:
        """Spill-COMMIT: the arena block is gone; flip the entry's tier."""
        with self._lock:
            object_id = self._shm_entries.pop(aid, None)
            entry = self._entries.get(object_id) if object_id is not None else None
        if entry is None:
            return
        with entry.lock:
            if entry.spill_path is not None:
                entry.tier = Tier.SPILLED
                self.stats["spills"] += 1
                self.stats["spilled_bytes"] += entry.nbytes
            else:
                entry.value = None
                entry.state = ObjectState.LOST
                self.stats["evictions"] += 1
        self.stats["shm_evictions"] += 1

    def _spill(self, entry: ObjectEntry) -> None:
        """Write one entry to disk. Caller holds entry.lock (NOT the store
        lock) — only access to this object blocks on the disk write."""
        os.makedirs(self._spill_dir, exist_ok=True)
        path = os.path.join(self._spill_dir, entry.object_id.hex())
        with open(path, "wb") as f:
            pickle.dump(entry.value, f, protocol=pickle.HIGHEST_PROTOCOL)
        entry.spill_path = path
        entry.value = None
        entry.tier = Tier.SPILLED
        with self._lock:
            self._host_bytes -= entry.nbytes
        self.stats["spills"] += 1
        self.stats["spilled_bytes"] += entry.nbytes

    def _restore(self, entry: ObjectEntry) -> Any:
        with open(entry.spill_path, "rb") as f:
            value = pickle.load(f)
        entry.value = value
        entry.tier = Tier.HOST
        with self._lock:
            self._host_bytes += entry.nbytes
        self.stats["restores"] += 1
        self.stats["restored_bytes"] += entry.nbytes
        return value

    # -------------------------------------------------- process-worker views

    def resolve_process_args(self, container):
        """Resolve task args for a PROCESS-executor worker: SHM-tier
        numpy values become pinned zero-copy descriptors (ShmView) the
        child mmaps instead of receiving pickled bytes over the pipe —
        the plasma client handoff (plasma/store.h:55). Everything else
        resolves by value like _resolve. Returns (resolved, release):
        call release() after the worker finishes to drop the pins."""
        from .native_store import ShmView
        from .runtime import ObjectRef

        pinned: List[int] = []
        arena = self._arena

        def one(value):
            if not isinstance(value, ObjectRef):
                return value
            entry = self.entry(value.object_id)
            if arena is not None and arena.path is not None and entry is not None:
                # under entry.lock like every reader: a concurrent arena
                # eviction flips value/state, and an unlocked unpack of
                # entry.value would race it
                with entry.lock:
                    if (
                        entry.state == ObjectState.READY
                        and entry.tier == Tier.SHM
                    ):
                        _, aid, dtype_str, shape = entry.value
                        desc = arena.descriptor(aid)  # pins; None if evicted
                    else:
                        desc = None
                if desc is not None:
                    import numpy as np

                    path, offset, size = desc
                    pinned.append(aid)
                    count = size // np.dtype(dtype_str).itemsize
                    return ShmView(path, offset, count, dtype_str, shape)
            return self.get(value.object_id)

        def release() -> None:
            for aid in pinned:
                arena.release_descriptor(aid)

        try:
            if isinstance(container, tuple):
                resolved = tuple(one(v) for v in container)
            else:
                resolved = {k: one(v) for k, v in container.items()}
        except BaseException:
            release()  # pins taken before the failing arg must not leak
            raise
        return resolved, release

    # ------------------------------------------------------------------ intro

    @property
    def large_object_tier(self) -> str:
        """Where large numpy payloads live: "native_arena" (the C++
        shared-memory arena, cfg.native_store) or "python" (host tier)."""
        return "native_arena" if self._arena is not None else "python"

    def usage(self) -> Dict[str, int]:
        with self._lock:
            return {
                "host_bytes": self._host_bytes,
                "device_bytes": self._device_bytes,
                "capacity_bytes": self._capacity,
                "num_objects": len(self._entries),
            }

    def has_primary_copy_at(self, address: str) -> bool:
        """Whether any object's primary copy lives in the remote store
        at `address`. The capacity plane refuses to retire a node whose
        store still owns primary copies — terminating it would destroy
        the only durable replica."""
        if not address:
            return False
        with self._lock:
            entries = list(self._entries.values())
        return any(
            entry.tier == Tier.REMOTE and entry.remote_addr == address
            for entry in entries
        )
