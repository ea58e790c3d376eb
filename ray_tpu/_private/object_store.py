"""Per-node object store with host and device (HBM) tiers plus disk spilling.

Parity contract (reference plasma store, ``src/ray/object_manager/plasma/``):
immutable objects, size-accounted capacity, eviction of unreferenced entries,
spill-to-disk under pressure with transparent restore, per-object pinning while
referenced.

TPU-first differences:
- A **device tier**: values that are ``jax.Array`` (or pytrees of them) stay
  resident in HBM and are handed to consumers zero-copy. They are never
  serialized through host memory on the local-host path (reference's GPU
  object store, ``python/ray/experimental/gpu_object_manager``, needs NCCL
  transfers for this; on TPU the array is already addressable by every
  consumer of the same process/mesh).
- Host-tier numpy payloads are stored as read-only views so consumers cannot
  mutate shared state (plasma gives the same guarantee via mmap PROT_READ).
"""

from __future__ import annotations

import os
import pickle
import sys
import threading
import weakref
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from ray_tpu._private.ids import NodeID, ObjectID
from ray_tpu.exceptions import OutOfMemoryError


# Values of these exact types need no deep walk: drain-path profiles
# showed _nbytes_of + _is_device_value re-walking every stored task
# result (~100us/result on sandboxed kernels for a bare None — the
# in-function imports and jax.tree_map dominate, not the data).
_TRIVIAL_TYPES = (type(None), bool, int, float)


def _nbytes_of(value: Any) -> int:
    """Best-effort deep size estimate without serializing."""
    t = type(value)
    if t in _TRIVIAL_TYPES:
        # int is arbitrary-precision — getsizeof (one cheap C call)
        # keeps a huge int honestly accounted so eviction/OOM
        # thresholds still trigger; the others are fixed-size
        return sys.getsizeof(value) if t is int else 32
    import numpy as np

    seen = set()

    def sz(v) -> int:
        vid = id(v)
        if vid in seen:
            return 0
        seen.add(vid)
        if isinstance(v, np.ndarray):
            return int(v.nbytes)
        tname = type(v).__module__
        if tname.startswith("jax"):
            nb = getattr(v, "nbytes", None)
            if nb is not None:
                return int(nb)
        if isinstance(v, (bytes, bytearray, memoryview)):
            return len(v)
        if isinstance(v, str):
            return len(v)
        if isinstance(v, (list, tuple, set, frozenset)):
            return sys.getsizeof(v) + sum(sz(x) for x in v)
        if isinstance(v, dict):
            return sys.getsizeof(v) + sum(sz(k) + sz(x) for k, x in v.items())
        return sys.getsizeof(v, 64)

    return sz(value)


def _is_device_value(value: Any) -> bool:
    """True if the value is a jax.Array or a pytree containing one."""
    import sys as _sys
    if type(value) in _TRIVIAL_TYPES or isinstance(value, (str, bytes,
                                                           bytearray)):
        return False    # never a device array; skip the tree walk
    if "jax" not in _sys.modules:
        return False    # no jax imported -> no jax.Array can exist
    try:
        import jax
    except ImportError:
        return False
    found = False

    def check(leaf):
        nonlocal found
        if isinstance(leaf, jax.Array):
            found = True
        return leaf

    try:
        jax.tree_util.tree_map(check, value)
    except Exception:
        return False
    return found


def _freeze_numpy(value: Any) -> Any:
    """Make top-level numpy arrays read-only (immutability guarantee)."""
    import numpy as np

    if isinstance(value, np.ndarray):
        v = value.view()
        v.flags.writeable = False
        return v
    return value


@dataclass
class ObjectEntry:
    value: Any
    nbytes: int
    device_tier: bool = False
    spilled_path: Optional[str] = None
    pinned: int = 0  # pin count: >0 means not evictable/spillable
    # native shm tier: (dtype, shape) of the array parked in the C++ store
    native_meta: Optional[tuple] = None
    # explicit tier (host-shm | device-hbm | spilled): drives the
    # ray_tpu_object_store_bytes{tier} occupancy accounting
    tier: str = "host-shm"


# numpy arrays at least this large go to the native shm arena when built
NATIVE_TIER_MIN_BYTES = 64 * 1024


class LocalObjectStore:
    """Size-accounted object store for one (virtual) node."""

    def __init__(self, node_id: NodeID, capacity_bytes: int,
                 spill_dir: Optional[str] = None):
        self.node_id = node_id
        self.capacity_bytes = capacity_bytes
        self._spill_dir = spill_dir
        from ray_tpu._private.lock_sanitizer import tracked_lock
        self._lock = tracked_lock("object_store")
        # insertion-ordered for LRU-ish spilling
        #: guarded by self._lock
        self._entries: "OrderedDict[ObjectID, ObjectEntry]" = OrderedDict()
        self._used = 0                  #: guarded by self._lock
        self.stats = {"puts": 0, "gets": 0, "spills": 0, "restores": 0,
                      "evictions": 0, "native_puts": 0}
        # explicit (host-shm | device-hbm | spilled) occupancy, chained
        # into the process aggregate -> ray_tpu_object_store_bytes{tier}
        from ray_tpu.objectplane.tiers import store_accounting
        self.tiers = store_accounting()
        # Outstanding zero-copy views into the native arena, per object.
        # The C++ store defers deallocation while refs are held; this
        # count decides whether close() may munmap (see close()).
        self._native_views: Dict[bytes, int] = {}
        # Native C++ shm tier (plasma equivalent): holds large numpy
        # payloads as zero-copy mmap views. Optional — absent without g++.
        self._native = None
        from ray_tpu._private.config import cfg
        if cfg().native_store:
            try:
                from ray_tpu.native_store import ShmObjectStore, available
                if available():
                    self._native = ShmObjectStore(
                        f"rtpu_{os.getpid()}_{node_id.hex()[:8]}",
                        capacity_bytes)
            except Exception:
                self._native = None

    # -- basic ops ---------------------------------------------------------
    def put(self, object_id: ObjectID, value: Any,
            nbytes: Optional[int] = None) -> int:
        with self._lock:
            if object_id in self._entries:
                return self._entries[object_id].nbytes
            size = nbytes if nbytes is not None else _nbytes_of(value)
            device = _is_device_value(value)
            if not device:
                value = _freeze_numpy(value)
            if not device and size > self.capacity_bytes:
                raise OutOfMemoryError(
                    f"object of {size} bytes exceeds store capacity "
                    f"{self.capacity_bytes}")
            entry = ObjectEntry(value=value, nbytes=size, device_tier=device)
            if device:
                entry.tier = "device-hbm"
            if not device:
                native_meta = self._try_native_put(object_id, value, size)
                if native_meta is not None:
                    entry.value = None
                    entry.native_meta = native_meta
                    self.stats["native_puts"] += 1
                else:
                    self._ensure_space(size)
                    self._used += size
            self._entries[object_id] = entry
            self.stats["puts"] += 1
            self.tiers.add(entry.tier, size)
            return size

    def _try_native_put(self, object_id: ObjectID, value: Any,
                        size: int) -> Optional[tuple]:
        """Park a large contiguous numpy array in the C++ shm arena."""
        import numpy as np

        if (self._native is None or not isinstance(value, np.ndarray)
                or size < NATIVE_TIER_MIN_BYTES
                or value.dtype == object
                or not value.flags.c_contiguous):
            return None
        from ray_tpu.native_store import ShmStoreFull
        try:
            # pin: this layer's refcounting owns lifetime; native LRU must
            # not evict behind our back (falls back to python tier + disk
            # spill when the arena is full)
            self._native.put(object_id.binary(), value, pin=True)
            return (value.dtype, value.shape)
        except (ShmStoreFull, KeyError):
            return None

    def get(self, object_id: ObjectID) -> Any:
        with self._lock:
            entry = self._entries.get(object_id)
            if entry is None:
                raise KeyError(object_id)
            self._entries.move_to_end(object_id)
            if entry.spilled_path is not None:
                self._restore(object_id, entry)
            self.stats["gets"] += 1
            if entry.native_meta is not None:
                import numpy as np
                dtype, shape = entry.native_meta
                key = object_id.binary()
                # Zero-copy view; the native ref is HELD for the lifetime
                # of the returned array (released by a finalizer), so a
                # later delete() defers deallocation instead of freeing
                # memory user code still reads (plasma client semantics).
                view = self._native.get_view(key)  # increfs
                arr = np.frombuffer(view, dtype=dtype).reshape(shape)
                arr.flags.writeable = False
                self._native_views[key] = self._native_views.get(key, 0) + 1
                weakref.finalize(arr, self._release_native_view, key)
                return arr
            return entry.value

    def get_many(self, object_ids: List[ObjectID]) -> Optional[List[Any]]:
        """``get`` of several entries under ONE acquisition of the lock,
        where every one is a value held in memory; None where one is
        missing, spilled or in the native tier (``get`` reads those)."""
        with self._lock:
            entries = [self._entries.get(oid) for oid in object_ids]
            if any(entry is None or entry.spilled_path is not None
                   or entry.native_meta is not None for entry in entries):
                return None
            for oid in object_ids:
                self._entries.move_to_end(oid)
            self.stats["gets"] += len(entries)
            return [entry.value for entry in entries]

    def _release_native_view(self, key: bytes) -> None:
        """Finalizer for zero-copy native-tier arrays."""
        with self._lock:
            n = self._native_views.get(key, 0) - 1
            if n <= 0:
                self._native_views.pop(key, None)
            else:
                self._native_views[key] = n
            if self._native is not None:
                try:
                    self._native.release(key)
                except Exception:
                    pass

    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            return object_id in self._entries

    def delete(self, object_id: ObjectID) -> None:
        with self._lock:
            entry = self._entries.pop(object_id, None)
            if entry is None:
                return
            if entry.spilled_path:
                try:
                    os.unlink(entry.spilled_path)
                except OSError:
                    pass
            elif entry.native_meta is not None:
                try:
                    self._native.delete(object_id.binary())
                except Exception:
                    pass
            elif not entry.device_tier:
                self._used -= entry.nbytes
            self.tiers.add(entry.tier, -entry.nbytes)

    def pin(self, object_id: ObjectID) -> None:
        with self._lock:
            e = self._entries.get(object_id)
            if e is not None:
                e.pinned += 1

    def unpin(self, object_id: ObjectID) -> None:
        with self._lock:
            e = self._entries.get(object_id)
            if e is not None and e.pinned > 0:
                e.pinned -= 1

    def nbytes_of(self, object_id: ObjectID) -> int:
        """Size cached on the entry at insert time (the same number the
        eviction/spill accounting uses) — never re-walks the value."""
        with self._lock:
            e = self._entries.get(object_id)
            return e.nbytes if e is not None else 0

    def used_bytes(self) -> int:
        with self._lock:
            return self._used

    def tier_bytes(self) -> Dict[str, int]:
        """Occupancy by (host-shm | device-hbm | spilled) tier."""
        return self.tiers.snapshot()

    def object_ids(self):
        with self._lock:
            return list(self._entries.keys())

    def clear(self) -> None:
        with self._lock:
            for oid in list(self._entries):
                self.delete(oid)

    def close(self) -> None:
        """Release the native shm arena (unlinks /dev/shm segment).

        If zero-copy views are still held by user code, only the segment
        NAME is removed — the mapping is left alive so those arrays stay
        valid (munmap would SIGSEGV them)."""
        self.clear()
        if self._native is not None:
            try:
                if self._native_views:
                    self._native.unlink_only()
                else:
                    self._native.close(unlink=True)
            except Exception:
                pass
            self._native = None

    # -- pressure handling -------------------------------------------------
    def _ensure_space(self, size: int) -> None:
        """Spill (pinned) or drop (unpinned) host-tier entries until
        fits. Callers hold self._lock (re-entrant) and so does this:
        the spill scan must see a stable entry table."""
        with self._lock:
            if self._used + size <= self.capacity_bytes:
                return
            # Pass 1: spill least-recently-used spillable entries to
            # disk. Native-tier entries don't count toward _used (the
            # C++ arena accounts for them) and pinned entries are in
            # active use — both are skipped.
            for oid, entry in list(self._entries.items()):
                if self._used + size <= self.capacity_bytes:
                    break
                if (entry.device_tier or entry.spilled_path is not None
                        or entry.native_meta is not None
                        or entry.pinned > 0):
                    continue
                if self._spill_dir is not None:
                    self._spill(oid, entry)
            if self._used + size > self.capacity_bytes:
                raise OutOfMemoryError(
                    f"object store on node {self.node_id.hex()[:8]} "
                    f"full: need {size}, used "
                    f"{self._used}/{self.capacity_bytes} "
                    f"and nothing left to spill")

    def _spill(self, object_id: ObjectID, entry: ObjectEntry) -> None:
        with self._lock:    # re-entrant: callers already hold it
            os.makedirs(self._spill_dir, exist_ok=True)
            path = os.path.join(self._spill_dir, object_id.hex())
            with open(path, "wb") as f:
                pickle.dump(entry.value, f, protocol=5)
            entry.spilled_path = path
            entry.value = None
            self._used -= entry.nbytes
            self.stats["spills"] += 1
            self.tiers.move(entry.tier, "spilled", entry.nbytes)
            entry.tier = "spilled"

    def _restore(self, object_id: ObjectID, entry: ObjectEntry) -> None:
        with self._lock:    # re-entrant: callers already hold it
            # Make room FIRST, while the entry is still in spilled
            # state: the scan skips spilled entries, so it can never
            # pick the one being restored (re-spilling it handed the
            # caller value=None), and a failure here leaves the store
            # untouched — spill file intact, _used consistent, a later
            # retry can succeed once pressure drops.
            self._ensure_space(entry.nbytes)
            with open(entry.spilled_path, "rb") as f:
                entry.value = pickle.load(f)
            try:
                os.unlink(entry.spilled_path)
            except OSError:
                pass
            entry.spilled_path = None
            self._used += entry.nbytes
            self.stats["restores"] += 1
            self.tiers.move("spilled", "host-shm", entry.nbytes)
            entry.tier = "host-shm"
