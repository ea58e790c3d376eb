"""Node daemon process (the raylet equivalent).

Reference capability: the per-node daemon of
``src/ray/raylet/node_manager.cc`` — worker-lease protocol
(``HandleRequestWorkerLease`` :1754), a pool of real worker processes
(``worker_pool.h``), placement-group bundle 2PC
(``node_manager.proto:443-452``), and the node's object plane (plasma
store + ``object_manager.cc:247,354`` pull/push). Spawned as its own OS
process (``python -m ray_tpu._private.daemon``); all traffic is typed
msgpack RPC (:mod:`ray_tpu._private.rpc`).

Division of labor (TPU-first): the daemon executes HOST-plane work only —
its workers are CPU-pinned processes (forkserver pool reused from
:mod:`worker_process`). Accelerator work never lands here; it stays in
the mesh-owning driver. The daemon never unpickles user payloads (raw
blobs in, raw blobs out, like the real raylet): user code exists only in
its worker processes.

Object plane: results too big to inline live in the daemon's object
table — small ones in a dict, large ones in the C++ shm arena
(``native/shm_store.cc``) — and are served by (a) raw-bytes RPC, (b)
same-host zero-copy: ``get_object`` replies (arena name, offset, size)
with a pinned ref; the client attaches the arena by name and reads the
range directly (plasma's fd-passing role), then releases; (c)
daemon⇄daemon ``pull_object`` for inter-node transfer.

Worker-initiated core ops (nested ``ray_tpu.*`` inside tasks) forward
raw to the OWNER (driver) over a dedicated connection — the
CoreWorkerService direction of the reference.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import cloudpickle

from ray_tpu._private import events as _events
from ray_tpu._private import eventloop
from ray_tpu._private import failpoints as _fp
from ray_tpu._private import rpc
from ray_tpu._private.aio import AsyncClient, AsyncConnection
from ray_tpu._private.head import HeadClient, _hb_interval
from ray_tpu._private.ids import ActorID, NodeID, TaskID
from ray_tpu._private.lock_sanitizer import tracked_lock
from ray_tpu._private.task_spec import TaskKind, TaskSpec
from ray_tpu._private.rpc import declare
from ray_tpu.util import metrics as _metrics
from ray_tpu.util import profiling as _profiling

INLINE_RESULT = 100 * 1024  # reference: max_direct_call_object_size

declare("hello_driver", "owner_addr", "job_id", "namespace", "sys_path")
declare("request_worker_lease", "task_meta")
declare("return_worker", "lease_id")
declare("push_task", "spec", "fid", "args", "lease_id", "backpressure")
declare("submit_task", "spec", "fid", "args", "backpressure")
# coalesced submit: many tasks per frame; `fns` ships each function blob
# once per (daemon, fid); completions return batched on task_batch_done
# push frames. Retried frames dedupe by task id (idempotent).
declare("push_task_batch", "tasks", "fns")
declare("create_actor", "spec", "fid", "args")
declare("call_actor_method", "spec", "args")
declare("kill_actor", "actor_id", "expected")
declare("cancel_task", "task_id", "force")
declare("gen_ack", "task_id")
declare("prepare_bundle", "pg_id", "index", "resources")
declare("commit_bundle", "pg_id", "index")
declare("cancel_bundle", "pg_id", "index")
declare("put_object", "oid", "blob")
declare("get_object", "oid", "prefer_shm")
declare("object_meta", "oid")
declare("get_object_chunk", "oid", "off", "size")
declare("release_object", "oid")
declare("free_objects", "oids")
declare("pull_object", "oid", "from_addr", "priority")
# zero-copy object plane (docs/object_plane.md): reserve+seal let a
# same-host client write the payload straight into the arena (only
# metadata rides the wire); push_object/push_chunk are the proactive
# daemon->daemon transfer direction (PushManager)
declare("create_object", "oid", "size")
declare("seal_object", "oid", "ref", "raw", "nbytes")
declare("push_object", "oid", "to_addr", "ref")
declare("push_chunk", "oid", "off", "total", "blob", "ref", "raw")
declare("daemon_ping")
# fair-share federation: the driver mirrors its per-job quota/weight
# table here (capability-gated on the "tenancy" hello bit)
declare("tenancy_sync", "jobs")
# cross-language tier (C++ clients): names resolve through the head KV,
# args/results are plain msgpack values — no Python pickles cross the
# language boundary (reference: ray cross_language function descriptors)
declare("xlang_submit", "name", "args")
declare("xlang_create_actor", "cls", "name", "args")
declare("xlang_call_actor", "name", "method", "args")
declare("daemon_stop")
declare("daemon_stats")
# on-demand profiling burst: the daemon samples its own stacks AND fans
# out to its live pool workers; blocks ~duration (handler is
# @concurrent so it cannot head-of-line-block the connection lane)
declare("profile_burst", "duration")
declare("syncer_exchange", "view")
declare("syncer_view")
declare("oom_check", "task_id", "fast_lane")
declare("set_memory_limit", "limit")
declare("core_op", "call", "payload", "task")
declare("core_release", "task")
# chaos harness only: (de)activate a seeded network-chaos spec inside
# THIS daemon process — lets a campaign partition one node's head link
# when env activation (pre-spawn, all nodes) is too blunt
declare("net_chaos", "spec")
# same per-node chaos hook for failpoints: arm a seeded spec inside
# THIS daemon process (e.g. pressure.level on one node) when env
# activation — which reaches every spawned process — is too blunt
declare("fail_points", "spec")


# ---------------------------------------------------------------------------
# preemption watcher: self-announced graceful drain
# ---------------------------------------------------------------------------

class PreemptionWatcher:
    """Funnels preemption/maintenance notices into ONE self-announced
    graceful drain to the head (reference: spot TPU-VM preemption — the
    ACPI SIGTERM plus the metadata server's maintenance-event endpoint).

    Sources, all converging on :meth:`notify`:

    - **SIGTERM** — ``install_sigterm()`` (daemon ``main()`` installs it
      before entering the heartbeat loop; the handler only sets an
      event, the announce RPC runs on the watcher thread);
    - **notice file** — ``drain_notice_file`` flag: the file appearing
      is the notice, its content the reason (the pluggable, air-gapped
      stand-in for polling the cloud metadata server);
    - **programmatic** — ``notify(reason)`` from any integration hook.

    After announcing, the daemon keeps serving: the head's DRAINING
    state fences new placements, the driver migrates work off, and the
    head escalates to the death path at the deadline — at which point
    the heartbeat's ``{"dead": True}`` reply makes this process exit.
    """

    def __init__(self, node_id_hex: str, head_addr: Tuple[str, int],
                 deadline_s: float, notice_file: str = ""):
        self.node_id_hex = node_id_hex
        self.head_addr = head_addr
        self.deadline_s = deadline_s
        self.notice_file = notice_file
        self.announced = False
        self._reason = "preemption"
        self._event = threading.Event()

    def notify(self, reason: str = "preemption") -> None:
        self._reason = reason
        self._event.set()

    def install_sigterm(self) -> None:
        import signal

        def handler(signum, frame):
            self.notify("sigterm")

        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass    # not the main thread (embedded use): file/hook only

    def start(self) -> None:
        threading.Thread(target=self._loop, daemon=True,
                         name="preemption-watch").start()

    def _loop(self) -> None:
        while not self._event.wait(0.2):
            if self.notice_file and os.path.exists(self.notice_file):
                try:
                    with open(self.notice_file) as fh:
                        reason = fh.read().strip() or "maintenance notice"
                except OSError:
                    reason = "maintenance notice"
                self.notify(reason)
        self._announce()

    def _announce(self) -> None:
        if self.announced:
            return
        self.announced = True
        if _fp.ENABLED:
            try:
                # drop/error arm = the notice never reaches the head
                # (the VM then just dies: the ordinary crash path is
                # the backstop); delay arm shrinks the drain window
                if _fp.fire("drain.announce",
                            node=self.node_id_hex) is _fp.DROP:
                    return
            except Exception:
                return
        try:
            head = HeadClient(self.head_addr)
            try:
                head.drain_node(self.node_id_hex, self.deadline_s,
                                self._reason)
            finally:
                head.close()
        except (OSError, rpc.RpcError):
            pass    # head unreachable: crash-path recovery covers us


# ---------------------------------------------------------------------------
# object table: dict for small blobs, C++ shm arena for large ones
# ---------------------------------------------------------------------------

# Ledger identity for grants whose caller could not be established
# (legacy callers, direct in-process use). Never swept by liveness —
# reclaimed only when refs observably hit zero.
UNKNOWN_CLIENT = "?"


def _pid_alive(pid: int) -> bool:
    """Liveness probe for the orphan sweep (signal 0 = existence check;
    EPERM still proves the pid exists)."""
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    except OSError:
        return False
    return True


class ObjectTable:
    def __init__(self, arena_name: str, capacity: int,
                 sweep: bool = True, spill_dir: Optional[str] = None,
                 spill_budget: int = 0):
        self._small: Dict[bytes, bytes] = {}  #: guarded by self._lock
        self._lock = tracked_lock("daemon.object_table", reentrant=False)
        self.arena_name = arena_name
        self.capacity = capacity
        # logical ObjectID binary -> daemon store key: lets same-node
        # consumers (attached workers) resolve a ray_tpu ref without
        # the owner round trip (the node-local slice of the object
        # directory); raw-tier entries carry (dtype, shape) so views
        # need no unpickle at all
        self._by_oid: Dict[bytes, bytes] = {}   #: guarded by self._lock
        self._ref_of: Dict[bytes, bytes] = {}   #: guarded by self._lock
        self._raw: Dict[bytes, Any] = {}        #: guarded by self._lock
        # per-client grant ledger: every slot ref the owner increments
        # on a client's behalf (get_ext_meta) is charged to that
        # client's identity, so liveness-driven reclamation can drop a
        # dead client's outstanding grants without a daemon restart.
        # Clients release with SILENT local atomics, so a ledger count
        # is an UPPER BOUND on what the client still holds — reclaim
        # drops min(granted, observed_refs - other clients' ledger
        # counts) and the orphan sweep trues up the residue (see
        # docs/object_plane.md "crash reclamation").
        self._ext_slots: Dict[str, Dict[int, int]] = {}  #: guarded by self._lock
        # slot -> oid of the last grant (operator attribution); the
        # native lib has no slot-enumeration API, so leak observability
        # (ray_tpu_arena_slot_refs) polls ext_refs() over this set.
        self._slot_owners: Dict[int, bytes] = {}  #: guarded by self._lock
        # unsealed direct-put reservations: key -> (client_id, ts);
        # popped at seal/abort, aborted by reclaim_client and by the
        # heartbeat sweep once past the TTL.
        self._reservations: Dict[bytes, Tuple[str, float]] = {}  #: guarded by self._lock
        # -- arena spill tier (docs/object_plane.md "Arena spill") --
        # The native store has no key-enumeration API, so spill
        # candidacy needs a Python-side index of SEALED arena entries:
        # key -> nbytes in LRU order (move_to_end on every read grant).
        # None spill_dir = spilling disarmed (every op short-circuits).
        self.spill_dir = spill_dir
        self.spill_budget = int(spill_budget or 0)
        self._entries: "OrderedDict[bytes, int]" = OrderedDict()  #: guarded by self._lock
        # key -> (path, nbytes) for entries currently parked on disk
        self._spilled: Dict[bytes, Tuple[str, int]] = {}  #: guarded by self._lock
        self._spill_stats = {"spills": 0, "restores": 0,
                             "spilled_bytes": 0, "restored_bytes": 0,
                             "spill_skipped_pinned": 0,
                             "restore_failed": 0}  #: guarded by self._lock
        self._spilled_total = 0     #: guarded by self._lock
        self._shm = None
        if sweep:
            # stale-segment hygiene: a SIGKILL'd predecessor daemon of
            # this node never unlinked its arena — reap it before
            # creating ours (same name => same node)
            try:
                from ray_tpu.objectplane.arena import sweep_stale_segments
                sweep_stale_segments(arena_name)
            except Exception:
                pass
        try:
            from ray_tpu.native_store import ShmObjectStore

            self._shm = ShmObjectStore(arena_name, capacity)
        except Exception:
            self._shm = None  # g++ missing: dict-only fallback

    def put(self, oid: bytes, blob: bytes) -> None:
        if self._shm is not None and len(blob) > INLINE_RESULT:
            if self.spill_dir is not None:
                with self._lock:
                    if oid in self._spilled:
                        return  # already stored, parked on disk
            for attempt in range(2):
                try:
                    self._shm.put(oid, blob, pin=True)
                    with self._lock:
                        self._entries[oid] = len(blob)
                        self._entries.move_to_end(oid)
                    return
                except KeyError:
                    return  # already stored (idempotent retry)
                except Exception:
                    # arena full: spill cold entries once, then retry;
                    # still full (or spilling disarmed) → dict fallback
                    if attempt or not self.spill_for(len(blob)):
                        break
        with self._lock:
            self._small[oid] = blob

    def get_blob(self, oid: bytes) -> Optional[bytes]:
        with self._lock:
            blob = self._small.get(oid)
        if blob is not None:
            return blob
        if self._shm is not None:
            if not self._maybe_restore(oid):
                # restore failed (arena still full / failpoint): serve
                # the bytes straight off the spill file — a read must
                # degrade to a disk read, never to a miss
                return self._read_spilled(oid)
            try:
                view = self._shm.get_view(oid)  # increfs
                try:
                    self._touch(oid)
                    return view.tobytes()
                finally:
                    self._shm.release(oid)
            except KeyError:
                return None
        return None

    def get_shm_ref(self, oid: bytes):
        """(arena, capacity, off, size) with a held ref, or None."""
        if self._shm is None:
            return None
        self._maybe_restore(oid)
        try:
            off, size = self._shm.get_ref(oid)
        except KeyError:
            return None
        self._touch(oid)
        return (self.arena_name, self.capacity, off, size)

    def get_ext_meta(self, oid: bytes, client_id: str = UNKNOWN_CLIENT):
        """(arena, capacity, off, size, slot) with the object's
        PROCESS-SHARED slot refcount incremented on the client's behalf
        (the client reads through its own mapping and drops the ref with
        a local atomic — no release round trip), or None. The grant is
        charged to ``client_id`` in the ledger; incref + ledger entry
        commit under one lock hold so reclaim/sweep never observe a ref
        whose holder is not yet recorded."""
        if self._shm is None:
            return None
        self._maybe_restore(oid)
        with self._lock:
            try:
                off, size, slot = self._shm.get_ext(oid)
            except Exception:
                return None
            grants = self._ext_slots.setdefault(client_id, {})
            grants[slot] = grants.get(slot, 0) + 1
            self._slot_owners[slot] = oid
            if oid in self._entries:
                self._entries.move_to_end(oid)
        return (self.arena_name, self.capacity, off, size, slot)

    def ext_release(self, slot: int, client_id: Optional[str] = None
                    ) -> None:
        """Owner-side slot release (the RPC fallback path for clients
        with no local mapping). When the caller is identified, the
        ledger charge drops with the ref so reclaim never re-drops it."""
        if self._shm is None:
            return
        with self._lock:
            try:
                self._shm.ext_release(slot)
            except Exception:
                pass
            if client_id is not None:
                grants = self._ext_slots.get(client_id)
                if grants and slot in grants:
                    if grants[slot] <= 1:
                        del grants[slot]
                    else:
                        grants[slot] -= 1
                    if not grants:
                        del self._ext_slots[client_id]

    def slot_ref_stats(self, attribution: bool = False) -> Dict[str, Any]:
        """{"held": slots with outstanding external refs, "refs": total
        outstanding external refs} over every slot ever granted via
        get_ext_meta. Fully-released slots leave tracking here (their
        ledger charges are cleared too — refs hitting zero proves every
        grant was released); what remains with refs > 0 is live readers
        or a not-yet-reclaimed grant. With ``attribution`` the reply
        adds ``clients``: per-client ledger rows so operators can see
        WHO holds a slot. Zeros on the dict-only fallback."""
        if self._shm is None:
            return {"held": 0, "refs": 0, "clients": []} if attribution \
                else {"held": 0, "refs": 0}
        held = refs = 0
        with self._lock:
            tracked = set(self._slot_owners)
            for grants in self._ext_slots.values():
                tracked.update(grants)
            released = []
            for slot in tracked:
                try:
                    n = int(self._shm.ext_refs(slot))
                except Exception:
                    n = 0
                if n > 0:
                    held += 1
                    refs += n
                else:
                    released.append(slot)
            for slot in released:
                self._slot_owners.pop(slot, None)
                for cid in list(self._ext_slots):
                    grants = self._ext_slots[cid]
                    grants.pop(slot, None)
                    if not grants:
                        del self._ext_slots[cid]
            out: Dict[str, Any] = {"held": held, "refs": refs}
            if attribution:
                out["clients"] = [
                    {"client": cid,
                     "slots": len(grants),
                     "granted": sum(grants.values())}
                    for cid, grants in sorted(self._ext_slots.items())]
        return out

    def ledger_clients(self) -> list:
        """Client ids with outstanding grants or reservations (sweep
        input: the service checks each for liveness)."""
        with self._lock:
            out = set(self._ext_slots)
            out.update(cid for cid, _ts in self._reservations.values())
            return sorted(out)

    def reclaim_client(self, client_id: str) -> Tuple[int, int]:
        """Drop a dead client's outstanding state: CAS-drop its slot
        grants (bounded so a grant the client already released locally
        — or a ref another live client holds — is never stolen), abort
        its unsealed reservations, then reap so deferred deletes free
        NOW rather than at daemon restart. Returns (refs dropped,
        reservations aborted). Idempotent: a second call finds an empty
        ledger and does nothing."""
        with self._lock:
            grants = self._ext_slots.pop(client_id, None) or {}
            res_keys = [k for k, (cid, _ts) in self._reservations.items()
                        if cid == client_id]
            for k in res_keys:
                self._reservations.pop(k, None)
            dropped = 0
            if self._shm is not None and grants:
                # ledger counts of every OTHER still-registered client
                # per slot: ledgers over-count (silent local releases),
                # so observed - others is a SAFE LOWER BOUND on what the
                # dead client still holds. Residue trues up in the
                # orphan sweep once the co-holders release or die.
                others: Dict[int, int] = {}
                for grants_o in self._ext_slots.values():
                    for slot, n in grants_o.items():
                        if slot in grants:
                            others[slot] = others.get(slot, 0) + n
                for slot, granted in grants.items():
                    try:
                        observed = int(self._shm.ext_refs(slot))
                    except Exception:
                        continue
                    n = min(granted, max(0, observed - others.get(slot, 0)))
                    if n > 0:
                        try:
                            dropped += int(self._shm.ext_release_n(slot, n))
                        except Exception:
                            pass
        for k in res_keys:
            self.abort_reserve(k)
        self.reap()
        return dropped, len(res_keys)

    def stale_reservations(self, ttl: float) -> list:
        """Reservation keys older than ``ttl`` seconds (client reserved
        arena space but never sealed or aborted — dead mid-direct-put)."""
        now = time.monotonic()
        with self._lock:
            return [k for k, (_cid, ts) in self._reservations.items()
                    if now - ts > ttl]

    def sweep_orphan_slots(self) -> int:
        """True-up pass for ledger drift. Two rules, both safe because
        grants/reclaims serialize under the table lock: (a) a slot with
        outstanding refs but NO ledger holder carries only refs of
        already-reclaimed dead clients — force them to zero; (b) a slot
        whose SINGLE holder's charge exceeds observed refs had silent
        local releases — clamp the charge down (keeps ledger >= actual,
        the invariant reclaim's bound depends on). Returns refs dropped."""
        if self._shm is None:
            return 0
        dropped = 0
        with self._lock:
            holders: Dict[int, list] = {}
            for cid, grants in self._ext_slots.items():
                for slot in grants:
                    holders.setdefault(slot, []).append(cid)
            for slot in list(self._slot_owners):
                try:
                    observed = int(self._shm.ext_refs(slot))
                except Exception:
                    continue
                held_by = holders.get(slot, [])
                if observed > 0 and not held_by:
                    try:
                        dropped += int(self._shm.ext_release_n(slot,
                                                               observed))
                    except Exception:
                        pass
                elif len(held_by) == 1:
                    grants = self._ext_slots[held_by[0]]
                    if grants.get(slot, 0) > observed:
                        if observed == 0:
                            del grants[slot]
                            if not grants:
                                del self._ext_slots[held_by[0]]
                        else:
                            grants[slot] = observed
        return dropped

    def release(self, oid: bytes) -> None:
        if self._shm is not None:
            try:
                self._shm.release(oid)
            except Exception:
                pass

    # -- oid index (node-local object directory slice) -------------------
    def register_oid(self, ref: bytes, key: bytes, raw=None) -> None:
        if not ref:
            return
        with self._lock:
            self._by_oid[ref] = key
            self._ref_of[key] = ref
            if raw is not None:
                self._raw[key] = raw

    def key_for(self, ref: bytes) -> Optional[bytes]:
        with self._lock:
            return self._by_oid.get(ref)

    def raw_for(self, key: bytes):
        with self._lock:
            return self._raw.get(key)

    # -- arena spill tier (docs/object_plane.md "Arena spill") -----------
    # Cold, sealed, UNPINNED entries move to disk files under occupancy
    # pressure and restore on demand on every read path. A live external
    # slot ref (PR 16 grant ledger) pins an entry unspillable — a held
    # zero-copy view must never lose its backing bytes. Disarmed
    # (spill_dir None) every hook below is a None-check no-op.

    def _touch(self, key: bytes) -> None:
        """LRU maintenance on read grants (spill picks oldest first)."""
        if self.spill_dir is None:
            return
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)

    def _spill_path(self, key: bytes) -> str:
        return os.path.join(self.spill_dir, key.hex() + ".spill")

    def _pinned_now(self) -> set:
        """Keys unspillable RIGHT NOW: an outstanding external slot ref
        means some process still maps the bytes as a zero-copy view.
        Caller holds self._lock (grants commit under the same lock, so
        the set cannot go stale mid-pass)."""
        pinned = set()
        for slot, oid in self._slot_owners.items():  # raylint: disable=guarded-by — caller holds self._lock
            try:
                if int(self._shm.ext_refs(slot)) > 0:
                    pinned.add(oid)
            except Exception:
                pinned.add(oid)     # unreadable slot: keep it safe
        return pinned

    def _spill_one_locked(self, key: bytes, size: int) -> bool:
        """Spill ONE sealed entry. Caller holds self._lock and has
        checked the pin set. The write goes to a temp file renamed into
        place, and arena bytes free through the native deferred-delete/
        reap path — a reader that raced past the restore check keeps a
        valid (deferred) mapping and re-reads from disk next time."""
        if key in self._spilled or key not in self._entries:  # raylint: disable=guarded-by — caller holds self._lock
            return True     # idempotent: already parked / already gone
        if _fp.ENABLED:
            # drop/error arm = this spill attempt fails; the entry
            # stays resident at tier host-shm and a later pass retries
            try:
                if _fp.fire("arena.spill", key=key.hex()[:16],
                            nbytes=size) is _fp.DROP:
                    return False
            except Exception:
                return False
        try:
            view = self._shm.get_view(key)      # increfs
            try:
                data = view.tobytes()
            finally:
                self._shm.release(key)
        except Exception:
            return False
        path = self._spill_path(key)
        try:
            os.makedirs(self.spill_dir, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "wb") as fh:
                fh.write(data)
            os.replace(tmp, path)   # readers never see a torn file
        except OSError:
            return False
        self._spilled[key] = (path, len(data))  # raylint: disable=guarded-by — caller holds self._lock
        self._spilled_total += len(data)  # raylint: disable=guarded-by — caller holds self._lock
        self._entries.pop(key, None)  # raylint: disable=guarded-by — caller holds self._lock
        try:
            self._shm.delete(key)   # frees now, or defers until refs drop
        except Exception:
            pass
        self._spill_stats["spills"] += 1
        self._spill_stats["spilled_bytes"] += len(data)
        from ray_tpu.objectplane.tiers import count_spilled_bytes
        count_spilled_bytes(len(data))
        return True

    def _spill_pass_locked(self, need_bytes: Optional[int] = None,
                           floor_bytes: Optional[int] = None,
                           max_entries: int = 64,
                           exclude: tuple = ()) -> int:
        """Shared spill loop (caller holds self._lock): LRU-first until
        ``need_bytes`` of room exists / occupancy reaches
        ``floor_bytes`` / the per-pass entry bound or the spill-dir
        budget stops it. Returns entries spilled."""
        spilled = 0
        pinned = self._pinned_now()
        for key in list(self._entries):  # raylint: disable=guarded-by — caller holds self._lock
            if spilled >= max_entries:
                break
            used = self._shm.used_bytes()
            if need_bytes is not None and \
                    self.capacity - used >= need_bytes:
                break
            if floor_bytes is not None and used <= floor_bytes:
                break
            size = self._entries[key]  # raylint: disable=guarded-by — caller holds self._lock
            if key in exclude:
                continue
            if key in pinned:
                self._spill_stats["spill_skipped_pinned"] += 1
                continue
            if self.spill_budget and (self._spilled_total + size  # raylint: disable=guarded-by — caller holds self._lock
                                      > self.spill_budget):
                break       # disk budget exhausted: pressure goes hard
            if self._spill_one_locked(key, size):
                spilled += 1
        if spilled:
            try:
                self._shm.reap()
            except Exception:
                pass
        return spilled

    def spill_for(self, nbytes: int) -> bool:
        """Make ``nbytes`` of arena room by spilling cold entries; the
        put/reserve paths call this instead of failing over to the
        blob/dict path while cold data hogs the arena. False = spilling
        disarmed or not enough unpinned cold bytes."""
        if self.spill_dir is None or self._shm is None:
            return False
        with self._lock:
            self._spill_pass_locked(need_bytes=nbytes)
            return self.capacity - self._shm.used_bytes() >= nbytes

    def spill_to_fraction(self, target: float) -> int:
        """Proactive pressure-tick pass: bring occupancy down to the
        ``target`` fraction of capacity (soft watermark), oldest first,
        bounded per call so a tick stays short."""
        if self.spill_dir is None or self._shm is None:
            return 0
        with self._lock:
            return self._spill_pass_locked(
                floor_bytes=int(self.capacity * max(0.0, target)))

    def _maybe_restore(self, key: bytes) -> bool:
        """True when ``key`` is resident (nothing to do) or was
        restored; False when it is spilled and the restore failed —
        the caller degrades to a direct disk read."""
        if self.spill_dir is None:
            return True
        with self._lock:
            if key not in self._spilled:
                return True
        return self.restore(key)

    def restore(self, key: bytes) -> bool:
        """Bring a spilled entry back into the arena (tier spilled ->
        host-shm). Idempotent: a retried/concurrent restore finds the
        entry resident and reports success. The spill file is consumed
        only AFTER the arena copy lands — a failed attempt (failpoint
        arm, arena full) leaves the file intact for the next try."""
        if self._shm is None or self.spill_dir is None:
            return False
        done_bytes = 0
        with self._lock:
            spilled = self._spilled.get(key)
            if spilled is None:
                return True     # already resident (idempotent)
            path, size = spilled
            if _fp.ENABLED:
                # drop/error arm = this restore attempt fails; the read
                # path serves the spill file directly and retries later
                try:
                    if _fp.fire("arena.restore", key=key.hex()[:16],
                                nbytes=size) is _fp.DROP:
                        self._spill_stats["restore_failed"] += 1
                        return False
                except Exception:
                    self._spill_stats["restore_failed"] += 1
                    return False
            try:
                with open(path, "rb") as fh:
                    data = fh.read()
            except OSError:
                self._spill_stats["restore_failed"] += 1
                return False
            try:
                self._shm.put(key, data, pin=True)
            except KeyError:
                pass    # resident already (deferred twin / lost race)
            except Exception:
                # arena full: make room off colder entries, retry once
                # BEFORE consuming the spill file (the PR 5 object-store
                # lesson: pressure scan precedes the file delete)
                self._spill_pass_locked(need_bytes=len(data),
                                        exclude=(key,))
                try:
                    self._shm.put(key, data, pin=True)
                except KeyError:
                    pass
                except Exception:
                    self._spill_stats["restore_failed"] += 1
                    return False
            self._spilled.pop(key, None)
            self._spilled_total -= size
            self._entries[key] = len(data)
            self._entries.move_to_end(key)
            self._spill_stats["restores"] += 1
            self._spill_stats["restored_bytes"] += len(data)
            done_bytes = len(data)
        try:
            os.unlink(path)
        except OSError:
            pass
        from ray_tpu.objectplane.tiers import count_restored_bytes
        count_restored_bytes(done_bytes)
        return True

    def _read_spilled(self, key: bytes) -> Optional[bytes]:
        """Serve a spilled entry's bytes straight off its file (restore
        failed or lost a race with a spill pass) — reads degrade to
        disk, never to a miss."""
        with self._lock:
            spilled = self._spilled.get(key)
        if spilled is None:
            return None
        try:
            with open(spilled[0], "rb") as fh:
                return fh.read()
        except OSError:
            return None

    def spilled_bytes(self) -> int:
        with self._lock:
            return self._spilled_total

    def spill_stats(self) -> Dict[str, int]:
        with self._lock:
            out = dict(self._spill_stats)
            out["spilled_now_bytes"] = self._spilled_total
            out["spilled_now_count"] = len(self._spilled)
        return out

    # -- direct-put (reserve + client write + seal) ----------------------
    def reserve(self, key: bytes, size: int,
                client_id: str = UNKNOWN_CLIENT) -> Optional[int]:
        """Reserve arena space for a client-side write; None = no arena
        or no room (caller falls back to the blob path). Idempotent for
        a retried reserve of the same (key, size). The unsealed entry is
        charged to ``client_id`` so a writer that dies between reserve
        and seal gets its bytes reclaimed (reclaim_client or the TTL
        sweep) instead of stranding them forever."""
        if self._shm is None:
            return None
        from ray_tpu.native_store import ShmStoreFull
        try:
            off = self._shm.reserve(key, size)
        except ShmStoreFull:
            # spill cold entries to make room, then retry ONCE — a
            # direct put keeps succeeding in place instead of falling
            # back to the blob path while cold data hogs the arena
            if not self.spill_for(size):
                return None
            try:
                off = self._shm.reserve(key, size)
            except (ShmStoreFull, KeyError):
                return None
        except KeyError:
            return None
        with self._lock:
            self._reservations[key] = (client_id, time.monotonic())
        return off

    def seal(self, key: bytes, ref: bytes = b"", raw=None) -> bool:
        """Seal a reserved entry (idempotent; pin matches put(pin=True)
        so this layer's refcounting owns lifetime)."""
        if self._shm is None:
            return False
        try:
            self._shm.seal(key, pin=True)
        except KeyError:
            return False
        try:
            _off, size, _sealed = self._shm.stat(key)
        except Exception:
            size = 0
        with self._lock:
            self._reservations.pop(key, None)
            self._entries[key] = size
            self._entries.move_to_end(key)
        self.register_oid(ref, key, raw=raw)
        return True

    def abort_reserve(self, key: bytes) -> None:
        """Drop a reserved-but-never-sealed entry (failed direct put)."""
        self.delete(key)

    def reap(self) -> int:
        """Free deferred-deleted entries whose external (attached-
        process) refs have dropped; external releases are silent atomic
        decrements, so the owner sweeps periodically."""
        if self._shm is None:
            return 0
        try:
            return self._shm.reap()
        except Exception:
            return 0

    def contains(self, oid: bytes) -> bool:
        with self._lock:
            if oid in self._small or oid in self._spilled:
                return True
        return self._shm is not None and self._shm.contains(oid)

    def nbytes_of(self, oid: bytes) -> Optional[int]:
        with self._lock:
            blob = self._small.get(oid)
            if blob is None:
                spilled = self._spilled.get(oid)
                if spilled is not None:
                    return spilled[1]   # size answered without restore
        if blob is not None:
            return len(blob)
        if self._shm is not None:
            try:
                off, size = self._shm.get_ref(oid)
                self._shm.release(oid)
                return size
            except KeyError:
                return None
        return None

    def read_range(self, oid: bytes, off: int, size: int
                   ) -> Optional[bytes]:
        """One chunk of the object's bytes (inter-node chunked transfer,
        reference ``object_buffer_pool.h``); pin held only per call."""
        with self._lock:
            blob = self._small.get(oid)
        if blob is not None:
            return blob[off:off + size]
        if self._shm is not None:
            if not self._maybe_restore(oid):
                # arena still full: chunk straight off the spill file
                # so outbound push/pull never depends on arena room
                blob = self._read_spilled(oid)
                return None if blob is None else blob[off:off + size]
            try:
                view = self._shm.get_view(oid)  # increfs
                try:
                    self._touch(oid)
                    return bytes(view[off:off + size])
                finally:
                    self._shm.release(oid)
            except KeyError:
                return None
        return None

    def delete(self, oid: bytes) -> None:
        spill_path = None
        with self._lock:
            self._small.pop(oid, None)
            self._raw.pop(oid, None)
            self._reservations.pop(oid, None)
            self._entries.pop(oid, None)
            spilled = self._spilled.pop(oid, None)
            if spilled is not None:
                spill_path = spilled[0]
                self._spilled_total -= spilled[1]
            ref = self._ref_of.pop(oid, None)
            if ref is not None:
                self._by_oid.pop(ref, None)
        if spill_path is not None:
            try:
                os.unlink(spill_path)
            except OSError:
                pass
        if self._shm is not None:
            try:
                # an aborted direct put leaves an UNSEALED entry whose
                # creator ref was never pinned/released — drop it first
                # or the delete defers forever
                try:
                    _off, _size, sealed = self._shm.stat(oid)
                    if not sealed:
                        self._shm.release(oid)
                except KeyError:
                    pass
                self._shm.delete(oid)
            except Exception:
                pass

    def used_bytes(self) -> int:
        with self._lock:
            small = sum(len(b) for b in self._small.values())
        return small + (self._shm.used_bytes() if self._shm else 0)

    def close(self) -> None:
        if self._shm is not None:
            self._shm.close(unlink=True)


# ---------------------------------------------------------------------------
# pull manager: chunked, deduplicated, prioritized inter-node pulls
# ---------------------------------------------------------------------------

# Priorities mirror the reference's pull policy (``pull_manager.h:38-51``):
# an explicit ray.get outranks wait(fetch_local) outranks task-arg staging.
PULL_PRIORITY_GET = 0
PULL_PRIORITY_WAIT = 1
PULL_PRIORITY_TASK_ARGS = 2

def _pull_chunk() -> int:
    from ray_tpu._private.config import cfg
    return cfg().pull_chunk


class _Pull:
    __slots__ = ("oid", "from_addr", "priority", "event", "ok", "error",
                 "missing")

    def __init__(self, oid: bytes, from_addr, priority: int):
        self.oid = oid
        self.from_addr = from_addr
        self.priority = priority
        self.event = threading.Event()
        self.ok = False
        self.missing = False
        self.error = ""


class PullManager:
    """Inter-node object transfer engine (reference:
    ``object_manager.cc:247 Pull / :354 Push``, ``pull_manager.h``,
    ``push_manager.h``, ``object_buffer_pool.h``):

    - transfers move in ``PULL_CHUNK``-sized pieces assembled into one
      preallocated buffer, so a 64 MiB object never rides one RPC frame;
    - concurrent pulls of the same object deduplicate onto one in-flight
      transfer (push-dedup role — the bytes cross the wire once);
    - queued pulls are served strictly by priority (get > wait >
      task-args), then FIFO;
    - every step feeds stats counters (surfaced by ``daemon_stats``).
    """

    def __init__(self, objects: ObjectTable, peer_fn, num_workers: int = 2,
                 chunk: Optional[int] = None):
        self.objects = objects
        self._peer = peer_fn        # addr -> AsyncClient
        self.chunk = chunk if chunk is not None else _pull_chunk()
        self._cv = threading.Condition()
        self._heap: list = []
        self._seq = 0
        self._inflight: Dict[bytes, _Pull] = {}
        self.stats = {"pulls_started": 0, "pulls_deduped": 0,
                      "pulls_failed": 0, "chunks_transferred": 0,
                      "bytes_pulled": 0}
        for i in range(num_workers):
            threading.Thread(target=self._loop, daemon=True,
                             name=f"pull-worker-{i}").start()

    def request(self, oid: bytes, from_addr, priority: int) -> _Pull:
        """Enqueue (or join) a pull; caller waits on the returned event."""
        import heapq
        with self._cv:
            existing = self._inflight.get(oid)
            if existing is not None:
                self.stats["pulls_deduped"] += 1
                return existing
            pull = _Pull(oid, from_addr, priority)
            self._inflight[oid] = pull
            self.stats["pulls_started"] += 1
            self._seq += 1
            heapq.heappush(self._heap, (priority, self._seq, pull))
            self._cv.notify()
        return pull

    def _loop(self) -> None:
        import heapq
        while True:
            with self._cv:
                while not self._heap:
                    self._cv.wait()
                _, _, pull = heapq.heappop(self._heap)
            try:
                self._transfer(pull)
                pull.ok = True
            except _PullMissing:
                pull.missing = True
                with self._cv:
                    self.stats["pulls_failed"] += 1
            except Exception as e:  # noqa: BLE001 — reported to waiter
                pull.error = repr(e)
                with self._cv:
                    self.stats["pulls_failed"] += 1
            finally:
                with self._cv:
                    self._inflight.pop(pull.oid, None)
                pull.event.set()

    def _transfer(self, pull: _Pull) -> None:
        if _fp.ENABLED:
            # error arm fails this transfer attempt (waiter sees the
            # error and may fall back to the owner directory); delay
            # arm stretches the transfer window
            _fp.fire("daemon.pull_transfer")
        if self.objects.contains(pull.oid):
            return  # a deduped predecessor already landed it
        peer = self._peer(tuple(pull.from_addr))
        meta = peer.call("object_meta", oid=pull.oid)
        if meta.get("missing"):
            raise _PullMissing()
        size = meta["size"]
        if size <= self.chunk:
            out = peer.call("get_object", oid=pull.oid, prefer_shm=False)
            if out.get("missing"):
                raise _PullMissing()
            blob = out["blob"]
            with self._cv:
                self.stats["chunks_transferred"] += 1
                self.stats["bytes_pulled"] += len(blob)
        else:
            buf = bytearray(size)  # the transfer's reassembly buffer
            for off in range(0, size, self.chunk):
                want = min(self.chunk, size - off)
                out = peer.call("get_object_chunk", oid=pull.oid,
                                off=off, size=want)
                part = out.get("blob")
                if part is None:    # evicted mid-transfer
                    raise _PullMissing()
                buf[off:off + len(part)] = part
                with self._cv:
                    self.stats["chunks_transferred"] += 1
                    self.stats["bytes_pulled"] += len(part)
            blob = bytes(buf)
        self.objects.put(pull.oid, blob)


class _PullMissing(Exception):
    pass


# ---------------------------------------------------------------------------
# batched submit plumbing (driver side: cluster._SubmitCoalescer)
# ---------------------------------------------------------------------------

class _BatchTaskConn:
    """Adapts one batched task's reply surface onto the shared
    ``_run_pushed_task`` machinery: final outcomes ride the coalescing
    reply pump instead of a per-rid reply frame; stream TERMINATIONS
    (task_stream_end / task_stream_crash) coalesce onto the same pump
    as tagged entries, while task_yield items pass straight through to
    the real connection (they pace the gen_ack flow). ``key`` is the
    (task, attempt) dedupe identity — attempt included because task
    retries reuse the task id and must re-execute, not replay the old
    outcome. ``trace`` is (name, trace_id) for sampled tasks — it rides
    outcomes as ``tr`` so both sides can record the drain-side span
    phases (result_flush / result_ingest)."""

    __slots__ = ("service", "conn", "task_hex", "key", "trace",
                 "term_pump")

    def __init__(self, service: "DaemonService", conn: AsyncConnection,
                 task_hex: str, key: tuple, trace=None,
                 term_pump: bool = False):
        self.service = service
        self.conn = conn
        self.task_hex = task_hex
        self.key = key
        self.trace = trace
        self.term_pump = term_pump

    @property
    def closed(self) -> bool:
        return self.conn.closed

    def reply(self, rid, **kw) -> None:
        out = dict(kw)
        out["task"] = self.task_hex
        # fencing stamps: the attempt this outcome belongs to and the
        # daemon's registration epoch — the driver accepts exactly the
        # live (attempt, epoch) pair and counts the rest as fenced
        out["att"] = self.key[1]
        out["ep"] = self.service.epoch
        if self.trace is not None:
            out["tr"] = list(self.trace)
        self.service._batch_task_done(self.conn, self.key, out)

    def reply_error(self, rid, err: str) -> None:
        self.reply(rid, e=err)

    def push(self, method: str, **kw) -> None:
        if (self.term_pump
                and method in ("task_stream_end", "task_stream_crash")):
            # terminations are final per task: ship them coalesced —
            # but ONLY when the submitting driver advertised it can
            # ingest terminations off the pump (entry flag term_pump);
            # an older driver on a persistent daemon gets the classic
            # per-task push and never hangs its stream consumer
            out = dict(kw)
            out["stream"] = method
            out["att"] = self.key[1]
            out["ep"] = self.service.epoch
            self.service._batch_pump.add(self.conn, out)
            return
        self.conn.push(method, **kw)


class _BatchReplyPump:
    """Coalesces completed-task outcomes into ``task_batch_done`` push
    frames — one frame carries every completion that landed within the
    linger window (the batched-reply leg of the result pipeline).
    Final outcomes, object-location updates (``stored`` results), and
    generator/stream terminations all ride the same frames; classic
    ``via_pump`` submissions and ``push_task_batch`` tasks share it.

    Knobs: ``result_batch_max`` (entries per frame) and
    ``result_linger_us`` (straggler window).

    Retry contract: a flush that fails in transit
    (``batch.result_flush`` drop/error arms — the deterministic
    stand-in for a lost frame) requeues its entries and resends them
    on the next pump pass. Resends are idempotent at the driver: final
    outcomes pop their waiter slot exactly once (a duplicate finds no
    slot), and stream terminations land on an already-drained stream
    queue at worst."""

    def __init__(self, task_events=None, node_hex: str = ""):
        from ray_tpu._private.config import cfg
        self.linger_s = max(0.0, float(cfg().result_linger_us) / 1e6)
        self.max_per_frame = max(1, int(cfg().result_batch_max))
        # result_flush span sink (daemon lane); None in bare-pump tests
        self.task_events = task_events
        self.node_hex = node_hex
        self._lock = threading.Lock()
        # conn -> [(outcome, t_add)]: t_add is perf_counter at buffering
        # for traced outcomes (0.0 untraced — no clock read)
        self._buf: Dict[AsyncConnection, list] = {}  #: guarded by self._lock
        # The pump is a call_later chain on the event loop — one
        # cross-thread wake per linger WINDOW (the arming hop), not one
        # per completion, and the flush runs where the write batcher
        # lives, so a chunk's push coalesces with other loop writes.
        self._aloop = eventloop.get_loop()
        self._armed = False     #: guarded by self._lock

    def add(self, conn: AsyncConnection, out: Dict[str, Any]) -> None:
        t_add = time.perf_counter() if "tr" in out else 0.0
        with self._lock:
            self._buf.setdefault(conn, []).append((out, t_add))
            if self._armed:
                return      # a flush is already scheduled: coalesce
            self._armed = True
        if eventloop.on_loop():
            self._arm_flush()  # raylint: disable=loop-affinity — on_loop() guard
        else:
            self._aloop.call_soon_threadsafe(self._arm_flush)

    def _arm_flush(self, backoff: float = 0.0) -> None:  #: loop-only
        delay = max(self.linger_s, backoff)
        if delay > 0:
            self._aloop.call_later(delay, self._flush_on_loop)
        else:
            self._aloop.call_soon(self._flush_on_loop)

    def _flush_on_loop(self) -> None:  #: loop-only
        with self._lock:
            buf, self._buf = self._buf, {}
            self._armed = False
        failed = False
        for conn, entries in buf.items():
            if conn.closed:
                continue
            i = 0
            while i < len(entries):
                chunk = entries[i:i + self.max_per_frame]
                if not self._send_chunk(conn, chunk):
                    # lost in transit: requeue, preserving order (the
                    # resend is idempotent at the driver); concurrent
                    # add()s may have re-armed already — checked below
                    failed = True
                    with self._lock:
                        self._buf.setdefault(conn, [])[:0] = entries[i:]
                    break
                i += self.max_per_frame
        if failed:
            with self._lock:
                re_arm = not self._armed and bool(self._buf)
                if re_arm:
                    self._armed = True
            if re_arm:
                # the linger acts as retry backoff too, floored at 1ms
                # so a linger of 0 cannot busy-spin the pump against a
                # persistently failing (but not yet closed) connection
                self._arm_flush(backoff=0.001)

    def _send_chunk(self, conn: AsyncConnection, chunk) -> bool:
        if _fp.ENABLED:
            try:
                # drop/error arm = the frame is lost in transit; the
                # caller requeues and the next pass resends
                if _fp.fire("batch.result_flush",
                            n=len(chunk)) is _fp.DROP:
                    return False
            except Exception:
                return False
        now = time.perf_counter()
        conn.push("task_batch_done", outcomes=[o for o, _ in chunk])
        if conn.closed:     # push swallows transport failure into closed
            return False
        dwell = max((now - t for _, t in chunk if t), default=0.0)
        if dwell:
            _metrics.note_queue_dwell("daemon.reply_pump", dwell)
        if self.task_events is not None:
            self._record_flush_spans(chunk, now)
        return True

    def _record_flush_spans(self, chunk, now: float) -> None:
        """result_flush phase: completion buffered on the pump -> its
        frame on the wire (daemon lane, traced outcomes only)."""
        try:
            for out, t_add in chunk:
                tr = out.get("tr")
                if not tr or not t_add:
                    continue
                _events.record_phase(
                    self.task_events, task_id=out.get("task", ""),
                    name=tr[0], phase="result_flush",
                    dur_s=max(now - t_add, 0.0), node_id=self.node_hex,
                    proc=f"daemon:{self.node_hex[:8]}", trace_id=tr[1],
                    start_wall=_events.wall_at(t_add), end_mono=now)
        except Exception:
            pass    # observability must never fail a flush


# completed batched-task outcomes kept for duplicate-frame resend; cap
# bounds the inline result blobs a slow driver can pin here
_BATCH_DONE_CAP = 512


# ---------------------------------------------------------------------------
# the daemon's runtime shim (what WorkerClient/_core paths need)
# ---------------------------------------------------------------------------

class _NodeStub:
    __slots__ = ("node_id",)

    def __init__(self, node_id: NodeID):
        self.node_id = node_id


class DaemonRuntime:
    """Forwards worker-initiated core ops to the owner (driver)."""

    def __init__(self, service: "DaemonService"):
        self.service = service
        self.job_id = None
        self.namespace = None
        self._shutdown = False
        from ray_tpu._private.worker_process import ProcessRouter

        self.process_router = ProcessRouter(self)

    @property
    def task_events(self):
        """Span sink for this daemon's workers (trace_push lands here;
        the heartbeat loop flushes it to the head)."""
        return self.service.task_events

    def shm_ops(self, call: str, kw: Dict[str, Any], client=None):
        """Daemon-LOCAL object-plane ops for this daemon's workers
        (never forwarded to the owner): meta resolution for zero-copy
        gets, reserve/seal/abort for direct puts. The worker side only
        issues these once its arena attach succeeded. ``client`` is the
        issuing WorkerClient — grants get charged to its (pid,
        generation) identity for crash reclamation."""
        return self.service.handle_worker_shm_op(call, kw, client)

    def forward_core_op(self, msg: Dict[str, Any]) -> Tuple[bool, bytes]:
        owner = self.service.owner
        if owner is None:
            raise RuntimeError("daemon has no owner connection")
        # prefer the globally-unique borrower key; the bare worker rid
        # collides across workers/daemons at the shared owner holder
        out = owner.call("core_op", call=msg["call"],
                         payload=msg["payload"],
                         task=msg.get("task_key") or msg.get("task"),
                         timeout=None)
        return out["ok"], out["value"]

    def on_actor_worker_died(self, actor_id: ActorID, cause: str) -> None:
        self.service.notify_driver("actor_worker_died",
                                   actor_id=actor_id.hex(), cause=cause)


# ---------------------------------------------------------------------------
# service
# ---------------------------------------------------------------------------

class DaemonService:
    def __init__(self, node_id_hex: str, resources: Dict[str, float],
                 object_store_bytes: int, persist: bool = False,
                 host: str = "127.0.0.1"):
        self.node_id = NodeID.from_hex(node_id_hex)
        self.resources = resources
        # persist=True (cluster started via `ray-tpu start`): survive
        # driver disconnects and serve the next driver; False (driver-
        # spawned session): die with the driver.
        self.persist = persist
        from ray_tpu._private.config import cfg as _cfg
        # Spill armed only under the memory_pressure master switch: a
        # disarmed table keeps every hook a None-check no-op
        # (zero-overhead-when-off, the netchaos discipline).
        spill_dir = None
        if _cfg().memory_pressure:
            spill_dir = (_cfg().arena_spill_dir
                         or os.path.join("/tmp", f"rtpu_spill_{node_id_hex[:12]}"))
        self.objects = ObjectTable(
            f"rtpu_{node_id_hex[:12]}", object_store_bytes,
            spill_dir=spill_dir,
            spill_budget=int(_cfg().arena_spill_budget_bytes))
        # Hand the arena to every worker this daemon spawns (the
        # worker-hello leg of the zero-copy plane): workers attach the
        # segment by name and resolve host-tier objects in place.
        if self.objects._shm is not None and _cfg().objectplane_attach:
            from ray_tpu._private import worker_process as _wp
            _wp.set_arena_info(self.objects.arena_name,
                               self.objects._shm.capacity())
        self.owner: Optional[AsyncClient] = None
        self.driver_conn: Optional[AsyncConnection] = None
        # fencing epoch minted by the head at register_node (0 =
        # standalone / never registered); stamped into heartbeats,
        # hello replies, and every result/stream frame so drivers can
        # fence a healed pre-death incarnation's late results
        self.epoch = 0
        # per-process span buffer (task_event_buffer.cc role): daemon
        # dispatch spans + this daemon's worker exec spans, flushed to
        # the head's task-event store on heartbeats (main loop)
        from ray_tpu._private.events import TaskEventBuffer
        self.task_events = TaskEventBuffer(capacity=50_000)
        self.runtime = DaemonRuntime(self)
        self.node_stub = _NodeStub(self.node_id)
        self._lock = tracked_lock("daemon.ledger", reentrant=False)
        #: guarded by self._lock
        self._leases: Dict[str, Any] = {}          # lease_id -> WorkerClient
        self._lease_seq = 0                        #: guarded by self._lock
        # task_id hex -> (client, worker rid) for cancel/gen_ack
        self._task_rids: Dict[str, Tuple[Any, str]] = {}  #: guarded by self._lock
        # task_id hex -> job hex: OOM-preemption attribution (the
        # tenant-aware policy prefers over-quota jobs' workers); pruned
        # against _task_rids in _memory_candidates
        self._task_jobs: Dict[str, str] = {}       #: guarded by self._lock
        # node memory-pressure level, advertised through heartbeats/
        # syncer gossip and pushed to the driver on transitions; stays
        # "ok" forever when cfg().memory_pressure is off
        self.pressure: Optional[Any] = None        # PressureController
        # batched-submit dedupe, keyed (task hex, attempt): a retried
        # push_task_batch frame must not double-execute — running tasks
        # are skipped, finished ones get their recorded outcome resent;
        # a task RETRY bumps the attempt and executes normally
        self._batch_running: set = set()           #: guarded by self._lock
        #: guarded by self._lock
        self._batch_done: "OrderedDict[tuple, Dict[str, Any]]" = OrderedDict()
        self._batch_pump = _BatchReplyPump(
            task_events=self.task_events, node_hex=self.node_id.hex())
        self._bundles: Dict[Tuple[str, int], Dict[str, Any]] = {}  #: guarded by self._lock
        self._peers: Dict[Tuple[str, int], AsyncClient] = {}  #: guarded by self._lock
        # cross-language actors: name -> [actor_id, seqno]
        self._xlang_actors: Dict[str, list] = {}   #: guarded by self._lock
        self.head_addr = None            # set by main() in daemon mode
        self._xlang_head_client = None
        # peer resource gossip (reference: ray_syncer.h:83): versioned
        # per-node load entries, merged peer-to-peer; loop starts in
        # main() once the head address is known
        self._syncer_view: Dict[str, Dict[str, Any]] = {}  #: guarded by self._syncer_lock
        self._syncer_lock = tracked_lock("daemon.syncer", reentrant=False)
        self._syncer_peers_cache: Dict[str, Any] = {}
        self._syncer_peers_ts = 0.0
        self._syncer_interval_s = float(
            os.environ.get("RAY_TPU_SYNCER_INTERVAL_S", "0.5"))
        # Task bodies block on worker IPC, so the pool is sized well past
        # core count; reusing threads beats per-task spawn under GIL
        # contention (reference: raylet dispatches from its event loop).
        # The cap must exceed the driver's per-node in-flight bound (256,
        # node.py max_worker_threads): a parent task blocked in get() on
        # a child routed here holds a pool thread, and a cap at or below
        # the in-flight bound could starve the child of a thread.
        from ray_tpu._private.thread_pool import DaemonThreadPool
        self._task_pool = DaemonThreadPool(1024, name="daemon-task")
        self.pulls = PullManager(self.objects, self._peer)
        # proactive node-to-node transfer (the push direction; dedupes
        # in flight, against the owner's directory, and against pulls)
        from ray_tpu.objectplane.push import PushManager, PushReceiver
        self.pushes = PushManager(self.objects, self._peer,
                                  locate_fn=self._locate_via_owner)
        self.push_rx = PushReceiver(self.objects,
                                    register_oid=self.objects.register_oid)
        # Native daemon core (native/daemon_core.cc): the C++ event loop
        # that owns the plain-task hot path — drivers submit straight to
        # it, it leases a dedicated worker, forwards the payload, routes
        # the outcome back; zero Python per task (reference: the raylet's
        # C++ lease/dispatch loop, node_manager.cc). This Python service
        # remains the policy shell (actors, PGs, runtime envs, objects).
        self.fast_core = None
        self.fast_port: Optional[int] = None
        self._fast_host = host
        self._fast_workers: list = []
        self._fast_tag_seq = 0        # targeted-lane (actor) tags
        self._fast_max = max(1, min(16, int(resources.get("CPU", 2) or 2)))
        try:
            from ray_tpu._private.fast_lane import CoreHandle
            core = CoreHandle()
            # bind exactly where the daemon's RPC server binds: a
            # loopback daemon must not open a network-reachable
            # task-submission (= code execution) port
            port = core.start(host, 0)
            if port:
                self.fast_core = core
                self.fast_port = port
                threading.Thread(target=self._fast_pool_loop,
                                 daemon=True,
                                 name="fastlane-pool").start()
        except Exception:
            self.fast_core = None
        # Worker log capture: this daemon's workers write per-pid files;
        # the monitor forwards new lines to the driver (worker_log push).
        from ray_tpu._private import log_monitor as _lm
        self._log_monitor = None
        if _lm.log_to_driver_enabled():
            self._log_monitor = _lm.LogMonitor(
                _lm.session_log_dir(), self._forward_worker_log)
        # continuous profiler (profiling_hz knob, default off): this
        # daemon's record plus worker records ingested off result
        # frames ship to the head each heartbeat (main loop)
        _profiling.maybe_start_from_config(f"daemon:{node_id_hex[:8]}")

    # -- fast lane (native core) workers --------------------------------
    def _fast_dedicate_worker(self):
        """Spawn a worker dedicated to the native core's task lane. Its
        mp channel stays open for host ops (fetch_function, nested core
        ops, metrics); it never enters the classic idle pool."""
        from ray_tpu._private import worker_process as wp

        w = wp._spawn_worker()
        # NOT _checked_out: lane workers never enter the idle pool and
        # must not skew the pool's active-checkout accounting on death
        w.fast_lane = True
        w.raw_outcomes = True
        w.runtime = self.runtime
        w.node = self.node_stub
        lane_host = ("127.0.0.1" if self._fast_host in ("0.0.0.0", "")
                     else self._fast_host)
        rid, pend = w._request({
            "op": "join_fast_lane",
            "addr": [lane_host, self.fast_port]})
        out = w._wait_outcome(rid, pend)
        if out[0] not in ("ok", "ok_raw"):
            try:
                w.kill(expected=True)
            except Exception:
                pass
            raise RuntimeError(f"fast-lane join failed: {out!r}")
        # close the hello/spawn race: a set_extra_sys_path that landed
        # between this worker's boot snapshot and now re-sends here
        wp.ensure_sys_path(w)
        return w

    def _fast_pool_loop(self) -> None:
        """Queue-depth-driven sizing of the dedicated fast-lane workers:
        at least one alive; grow one at a time while the core reports a
        backlog, up to the node's CPU capacity (reference: worker-pool
        prestart + autoscaling-by-demand)."""
        while True:
            try:
                from ray_tpu._private import worker_process as wp
                alive = [w for w in self._fast_workers if w.alive()]
                self._fast_workers = alive
                for w in alive:
                    wp.ensure_sys_path(w)   # no-op when current
                stats = (self.fast_core.stats()
                         if self.fast_core is not None else {})
                grow = (not alive
                        or (stats.get("queued", 0) > 0
                            and len(alive) < self._fast_max))
                if grow:
                    self._fast_workers.append(
                        self._fast_dedicate_worker())
                    continue   # re-check immediately while backlogged
            except Exception:
                time.sleep(1.0)
            time.sleep(0.25)

    def _forward_worker_log(self, pid: int, stream: str,
                            line: str) -> None:
        self.notify_driver("worker_log", pid=pid, stream=stream,
                           line=line, node=self.node_id.hex()[:8])

    def _peer(self, addr: Tuple[str, int]) -> AsyncClient:
        # dial OUTSIDE the lock: holding it across a TCP connect
        # stalled every other peer lookup for the dial's duration.
        # Losing a dial race just closes the extra connection.
        with self._lock:
            peer = self._peers.get(addr)
        if peer is not None and not peer.dead:
            return peer
        fresh = rpc.connect(addr)
        with self._lock:
            peer = self._peers.get(addr)
            if peer is not None and not peer.dead:
                pass        # raced: keep the established winner
            else:
                peer = self._peers[addr] = fresh
        if peer is not fresh:
            fresh.close()
        return peer

    def _locate_via_owner(self, oid: bytes):
        """Owner-keyed object directory (reference:
        ``ownership_object_directory.h``): ask the object's owner which
        nodes hold a copy."""
        if self.owner is None:
            return []
        out = self.owner.call(
            "core_op", call="locate_object",
            payload=cloudpickle.dumps({"oid": oid}), task=None,
            timeout=30.0)
        if not out.get("ok"):
            return []
        return cloudpickle.loads(out["value"])

    # -- wiring ----------------------------------------------------------
    def handle_hello_driver(self, conn, rid, msg):
        self.driver_conn = conn
        conn.link("driver")
        self.owner = rpc.connect(tuple(msg["owner_addr"]),
                                 timeout=None).link("driver")
        self.runtime.job_id = cloudpickle.loads(msg["job_id"])
        self.runtime.namespace = msg["namespace"]
        # driver import roots: future workers get them in the boot
        # frame; already-running ones (prestarted pool, fast lane) get
        # an extend op so by-reference pickles resolve immediately
        from ray_tpu._private import worker_process as _wp
        paths = list(msg.get("sys_path") or [])
        if paths:
            _wp.set_extra_sys_path(paths)
            for w in _wp.live_workers():
                try:
                    w.notify_extend_sys_path(paths)
                except Exception:
                    pass
            for w in list(self._fast_workers):
                try:
                    w.notify_extend_sys_path(paths)
                except Exception:
                    pass
        # Don't report ready until the worker pool is warm: the first
        # lease otherwise pays a cold fork while racing driver work for
        # the CPU (reference: worker prestart hides process start cost).
        from ray_tpu._private import worker_process as wp

        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with wp._POOL_LOCK:
                if wp._IDLE:
                    break
            time.sleep(0.02)
        return {"ok": True, "pid": os.getpid(),
                "fast_port": self.fast_port,
                # protocol feature flags: this daemon understands
                # push_task_batch (drivers fall back per-task
                # otherwise) and coalesced completion delivery for
                # classic submit_task calls (via_pump)
                "batch": True,
                "result_batch": True,
                # fair-share federation: this daemon accepts
                # tenancy_sync job tables (old drivers never send
                # them and keep unconditional admission)
                "tenancy": True,
                # partition fencing: result/stream frames carry epoch
                # (ep) and attempt (att) stamps; the registration
                # epoch rides along so the driver knows the live
                # incarnation (old daemons advertise neither and the
                # driver accepts frames unfenced)
                "fence": True,
                "epoch": self.epoch,
                # zero-copy object plane: same-host clients attach this
                # arena by name for direct puts / slot-ref'd gets
                "objectplane": self.objects._shm is not None,
                "arena": self.objects.arena_name,
                "arena_capacity": (self.objects._shm.capacity()
                                   if self.objects._shm else 0),
                # connection-scoped grant-ledger identity: every slot
                # grant / reservation this connection requests is
                # charged here and reclaimed when the connection dies
                "client_id": self._conn_client_id(conn)}

    def notify_driver(self, kind: str, **kw) -> None:
        conn = self.driver_conn
        if conn is not None and not conn.closed:
            conn.push(kind, **kw)

    def on_disconnect(self, conn: AsyncConnection) -> None:
        cid = None
        try:
            cid = conn.meta.get("arena_client_id")
        except Exception:
            pass
        if cid is not None:
            # connection gone (clean close and SIGKILL look the same
            # here): reclaim every grant/reservation charged to it
            self.reclaim_client(cid, "disconnect")
        if conn is self.driver_conn:
            if self.persist:
                # Shared cluster (`ray-tpu start`): drop the departed
                # driver's state and wait for the next one.
                self._reset_for_new_driver()
                return
            # Driver gone: this daemon's work is orphaned; exit like a
            # raylet whose GCS/driver session ended.
            threading.Thread(target=lambda: (time.sleep(0.2),
                                             os._exit(0)),
                             daemon=True).start()

    def _reset_for_new_driver(self) -> None:
        """Tear down the departed driver's leases/actors so the next
        driver starts clean (its objects stay until arena pressure —
        known cross-driver growth, bounded by the arena capacity)."""
        self.driver_conn = None
        old_owner, self.owner = self.owner, None
        if old_owner is not None:
            try:
                old_owner.close()
            except OSError:
                pass
        with self._lock:
            leases = list(self._leases.values())
            self._leases.clear()
            self._task_rids.clear()
            self._bundles.clear()
            self._batch_running.clear()
            self._batch_done.clear()
        for client in leases:   # leased mid-task: state unknown, kill
            try:
                client.kill(expected=True)
            except Exception:
                pass
        router = self.runtime.process_router
        with router._lock:
            actors = dict(router._actor_workers)
            router._actor_workers.clear()
        for client in actors.values():
            try:
                client.kill(expected=True)
            except Exception:
                pass

    # -- object-plane crash reclamation ----------------------------------
    def reclaim_client(self, client_id: str, reason: str
                       ) -> Tuple[int, int]:
        """One funnel for every death signal — worker pipe EOF, fast-
        lane generation death, RPC connection close — that drops the
        dead client's grants, aborts its reservations, and reaps, so
        deferred deletes free NOW instead of at daemon restart. Returns
        (refs dropped, reservations aborted); idempotent per client."""
        if _fp.ENABLED:
            try:
                # drop/error arm = the event-path reclaim is LOST (the
                # death signal raced a daemon hiccup); the heartbeat
                # orphan sweep is the backstop and must still converge
                # the leak gauge to zero
                if _fp.fire("arena.grant_reclaim", client=client_id,
                            reason=reason) is _fp.DROP:
                    return (0, 0)
            except Exception:
                return (0, 0)
        try:
            dropped, aborted = self.objects.reclaim_client(client_id)
        except Exception:
            return (0, 0)   # reclamation must never take the daemon down
        if dropped or aborted:
            from ray_tpu.objectplane import tiers as _tiers
            _tiers.count_grants_reclaimed(dropped, reason)
        return dropped, aborted

    def sweep_object_plane(self) -> None:
        """Heartbeat orphan sweep: the backstop for anything the event-
        path reclaim missed — reservations stale past the TTL (writer
        died between reserve and seal), grants charged to worker pids
        that no longer exist, and ledger drift from silent local
        releases (sweep_orphan_slots). Faults here must never take the
        beat down."""
        obj = self.objects
        if _fp.ENABLED:
            try:
                # drop/error arm = this sweep pass is skipped wholesale
                # (a later beat retries); delay stretches the pass
                if _fp.fire("arena.reservation_sweep") is _fp.DROP:
                    return
            except Exception:
                return
        try:
            ttl = float(os.environ.get("RAY_TPU_ARENA_RESERVE_TTL_S",
                                       "30"))
        except ValueError:
            ttl = 30.0
        stale = obj.stale_reservations(ttl)
        for key in stale:
            try:
                obj.abort_reserve(key)
            except Exception:
                pass
        if stale:
            from ray_tpu.objectplane import tiers as _tiers
            _tiers.count_stale_reservations(len(stale))
        # grants held by dead worker pids the pipe-EOF callback missed
        for cid in obj.ledger_clients():
            if not cid.startswith("w:"):
                continue    # conn-scoped ids reclaim via on_disconnect
            try:
                pid = int(cid.split(":")[1])
            except (IndexError, ValueError):
                continue
            if pid > 0 and not _pid_alive(pid):
                self.reclaim_client(cid, "sweep")
        dropped = obj.sweep_orphan_slots()
        if dropped:
            from ray_tpu.objectplane import tiers as _tiers
            _tiers.count_grants_reclaimed(dropped, "sweep")
        obj.reap()

    def slot_ref_attribution(self) -> Dict[str, Any]:
        """slot_ref_stats plus liveness: each ledger client row gains
        its parsed pid (worker identities only) and whether that pid is
        still alive, so operators can see WHO holds a slot and whether
        the holder is a reclamation candidate."""
        stats = self.objects.slot_ref_stats(attribution=True)
        for row in stats.get("clients", ()):
            pid = None
            cid = row.get("client", "")
            if cid.startswith("w:"):
                try:
                    pid = int(cid.split(":")[1])
                except (IndexError, ValueError):
                    pid = None
            row["pid"] = pid
            row["alive"] = _pid_alive(pid) if pid else None
        return stats

    # -- worker lease protocol ------------------------------------------
    def handle_request_worker_lease(self, conn, rid, msg):
        """Grant a pooled worker (reference: HandleRequestWorkerLease →
        WorkerPool::PopWorker)."""
        from ray_tpu._private import worker_process as wp

        if _fp.ENABLED:
            # delay arm = slow lease grant; error arm = lease denied
            # (surfaces as a RemoteError at the driver)
            _fp.fire("daemon.lease")
        client = wp.acquire_worker()
        client.raw_outcomes = True
        client.runtime = self.runtime
        client.node = self.node_stub
        with self._lock:
            self._lease_seq += 1
            lease_id = f"l{self._lease_seq}"
            self._leases[lease_id] = client
        return {"lease_id": lease_id, "worker_pid": client.proc.pid}

    def handle_return_worker(self, conn, rid, msg):
        from ray_tpu._private import worker_process as wp

        with self._lock:
            client = self._leases.pop(msg["lease_id"], None)
        if client is not None and client.actor_id is None:
            wp.release_worker(client)
        return {"ok": True}

    def _leased(self, lease_id: str):
        with self._lock:
            client = self._leases.get(lease_id)
        if client is None:
            raise KeyError(f"unknown lease {lease_id!r}")
        return client

    # -- task execution --------------------------------------------------
    def _pump_outcome(self, conn, rid, client, spec, outcome,
                      on_done=None) -> None:
        """Shared reply/stream pump for push_task and call_actor_method:
        inline or stored result, generator stream pushes, worker-crash
        reporting. ``on_done(crashed: bool)`` runs when the interaction —
        including any stream — is over."""
        from ray_tpu._private.worker_process import WorkerCrashed

        task_hex = spec.task_id.hex()
        if outcome[0] == "gen":
            conn.reply(rid, outcome="gen")
            crashed = False
            try:
                # ep: the daemon's fencing epoch rides every stream
                # push so the driver can reject a healed pre-death
                # incarnation's late stream frames
                for kind, blob in outcome[1]:
                    if kind == "yield_raw":
                        conn.push("task_yield", task=task_hex,
                                  blob=blob, ep=self.epoch)
                    else:
                        conn.push("task_stream_end", task=task_hex,
                                  ok=False, blob=blob, ep=self.epoch)
                        break
                else:
                    conn.push("task_stream_end", task=task_hex,
                              ok=True, blob=b"", ep=self.epoch)
            except WorkerCrashed as e:
                crashed = True
                client.kill(expected=False)
                conn.push("task_stream_crash", task=task_hex,
                          error=str(e), ep=self.epoch)
            finally:
                with self._lock:
                    self._task_rids.pop(task_hex, None)
                if on_done is not None:
                    on_done(crashed)
            return
        with self._lock:
            self._task_rids.pop(task_hex, None)
        try:
            ok = outcome[0] == "ok_raw"
            blob = outcome[1]
            if ok and len(blob) > INLINE_RESULT:
                oid = b"res:" + spec.task_id.binary()
                self.objects.put(oid, blob)
                n = spec.num_returns
                if spec.return_ids and (n == 1 or not isinstance(n, int)):
                    # node-local oid index: same-node attached workers
                    # resolve this result without the owner round trip.
                    # Multi-return (int n > 1) blobs hold the WHOLE
                    # tuple — the driver fetches once and splits
                    # (worker.py stored path); indexing ref0 here would
                    # hand consumers the tuple as ref0's value
                    self.objects.register_oid(
                        spec.return_ids[0].binary(), oid)
                conn.reply(rid, outcome="stored", oid=oid,
                           nbytes=len(blob))
            else:
                conn.reply(rid, outcome="ok" if ok else "err", blob=blob)
        finally:
            if on_done is not None:
                on_done(False)

    def handle_submit_task(self, conn, rid, msg):
        """Fused lease+push+release in ONE round trip — the common task
        path. The explicit lease protocol (request_worker_lease /
        push_task / return_worker) remains for callers that need to hold
        a worker across calls; the reference gets the same effect by
        caching leases per SchedulingKey
        (``transport/normal_task_submitter.cc:140``).

        ``via_pump`` submissions (driver saw ``result_batch`` in hello)
        get their COMPLETION on the coalesced task_batch_done pump
        instead of this RPC's reply: the reply is an immediate ack, so
        the classic path's completions batch exactly like the
        push_task_batch path's. Dedupe keys on (task, attempt) in the
        shared batch tables — a retried frame never double-executes."""
        if msg.get("via_pump") and msg.get("task"):
            return self._submit_task_via_pump(conn, msg)
        from ray_tpu._private import worker_process as wp

        msg["_t0"] = time.perf_counter()    # dispatch-phase span start
        client = wp.acquire_worker()
        client.raw_outcomes = True
        client.runtime = self.runtime
        client.node = self.node_stub
        try:
            return self._run_pushed_task(conn, rid, msg, client,
                                         lease_id=None)
        except BaseException:
            # e.g. an unpicklable spec: without this the checked-out
            # worker (and its _ACTIVE slot) would leak per failed submit.
            wp.release_worker(client)
            raise

    def _submit_task_via_pump(self, conn, msg):
        """Classic single-task submit whose outcome returns coalesced
        (the 'classic submitters ride the result pipeline too' leg)."""
        key = (msg["task"], msg.get("attempt", 0))
        msg["_t0"] = time.perf_counter()    # dispatch-phase span start
        resend = None
        with self._lock:
            if key in self._batch_running:
                return {"outcome": "pump"}  # duplicate of in-flight
            resend = self._batch_done.get(key)
            if resend is None:
                self._batch_running.add(key)
        if resend is not None:
            self._batch_pump.add(conn, resend)
            return {"outcome": "pump"}
        self._start_batch_task(conn, msg, key)
        return {"outcome": "pump"}

    @rpc.loop_safe
    def handle_push_task_batch(self, conn, rid, msg):
        """Coalesced submit: N tasks on one frame (driver-side
        _SubmitCoalescer). Each task runs exactly like submit_task —
        fused lease+push+release on a pooled worker — but the per-task
        RPC round trip is gone: the frame is acked once, and completions
        return batched on task_batch_done push frames.

        loop_safe: this runs inline on the event loop
        (dedupe is dict ops under a short lock hold; nothing blocks),
        so frame parse -> admission -> ack has zero thread hand-offs.
        The per-task pool submits — which may cold-SPAWN pool threads —
        are fanned out by ONE pool job below, keeping spawn cost off
        the loop.

        Idempotent by task id: a retried frame (driver saw its flush
        fail in transit) skips tasks already running and resends the
        recorded outcome of tasks already finished — never a second
        execution."""
        for fid, blob in (msg.get("fns") or {}).items():
            # content-addressed (fid == sha1(blob)): registering under
            # the same id the driver computed lets workers resolve
            # fetch_function locally with no driver round trip
            from ray_tpu._private import worker_process as wp
            wp.register_function_blob(blob)
        resend = []
        starts = []
        for entry in msg["tasks"]:
            # dedupe identity is (task, attempt): a RETRY reuses the
            # task id but must execute — only a resent frame of the
            # SAME attempt is a duplicate
            key = (entry["task"], entry.get("attempt", 0))
            entry["_t0"] = time.perf_counter()  # dispatch-phase span
            with self._lock:
                if key in self._batch_running:
                    continue        # duplicate of an in-flight task
                done = self._batch_done.get(key)
                if done is not None:
                    resend.append(done)
                    continue
                self._batch_running.add(key)
            starts.append(self._start_batch_task(conn, entry, key,
                                                 defer=True))
        if starts:
            if len(starts) == 1 or not eventloop.on_loop():
                for s in starts:
                    self._task_pool.submit(s)
            else:
                def _fan_out():
                    for s in starts:
                        self._task_pool.submit(s)
                self._task_pool.submit(_fan_out)
        for out in resend:
            self._batch_pump.add(conn, out)
        return {"ok": True, "accepted": len(msg["tasks"])}

    def _start_batch_task(self, conn, entry, key: tuple,
                          defer: bool = False):
        """Acquire a pooled worker OFF the RPC lane thread (the pool may
        cold-spawn a process) and run the shared pushed-task machinery
        with the batch reply adapter. ``defer=True`` returns the start
        closure instead of submitting it (batch fan-out)."""
        trace = ((entry.get("name", ""), entry["trace"])
                 if entry.get("trace") else None)
        bconn = _BatchTaskConn(self, conn, entry["task"], key,
                               trace=trace,
                               term_pump=bool(entry.get("term_pump")))

        def start():
            from ray_tpu._private import worker_process as wp

            try:
                client = wp.acquire_worker()
            except BaseException as e:  # noqa: BLE001 — shipped back
                bconn.reply_error(None, f"{type(e).__name__}: {e}")
                return
            client.raw_outcomes = True
            client.runtime = self.runtime
            client.node = self.node_stub
            try:
                self._run_pushed_task(bconn, None, entry, client,
                                      lease_id=None)
            except BaseException as e:  # noqa: BLE001 — e.g. an
                # undecodable spec: release the checkout and fail just
                # this task, not the whole batch
                wp.release_worker(client)
                bconn.reply_error(None, f"{type(e).__name__}: {e}")

        if defer:
            return start
        self._task_pool.submit(start)
        return None

    def _batch_task_done(self, conn, key: tuple,
                         out: Dict[str, Any]) -> None:
        with self._lock:
            self._batch_running.discard(key)
            self._batch_done[key] = out
            while len(self._batch_done) > _BATCH_DONE_CAP:
                self._batch_done.popitem(last=False)
        self._batch_pump.add(conn, out)

    def handle_push_task(self, conn, rid, msg):
        """Execute on the leased worker; replies with the outcome. Big
        results go to the object table and return as a location; streams
        flow back as task_yield/task_result pushes."""
        msg["_t0"] = time.perf_counter()    # dispatch-phase span start
        client = self._leased(msg["lease_id"])
        return self._run_pushed_task(conn, rid, msg, client,
                                     lease_id=msg["lease_id"])

    def _run_pushed_task(self, conn, rid, msg, client, lease_id):
        spec = cloudpickle.loads(msg["spec"])
        spec.backpressure_num_objects = msg["backpressure"]
        task_hex = spec.task_id.hex()

        def release_lease(crashed: bool) -> None:
            from ray_tpu._private import worker_process as wp

            if lease_id is not None:
                with self._lock:
                    self._leases.pop(lease_id, None)
            # (the driver never calls return_worker for streams; and for
            # final outcomes its return_worker becomes a no-op.)
            # Unconditional for non-actor workers: release_worker reaps
            # dead ones itself, and skipping it would leak the checkout
            # accounting for a worker that died AFTER returning its
            # result (crash paths already called kill(), which cleared
            # the checkout — release is then a no-op on accounting).
            if client.actor_id is None:
                if crashed:
                    wp._checkout_done(client)
                else:
                    wp.release_worker(client)

        def run():
            from ray_tpu._private.worker_process import WorkerCrashed

            try:
                if _fp.ENABLED:
                    # crash arm here kills the DAEMON mid-push (node
                    # death); error arm fails just this task's push
                    _fp.fire("daemon.push_task", task=task_hex)
                t0 = msg.get("_t0")
                if t0 is not None and getattr(spec, "trace_sampled",
                                              False):
                    # dispatch phase: frame arrival -> exec request to
                    # the worker (daemon queue wait + worker acquire)
                    from ray_tpu._private import events as _events
                    now = time.perf_counter()
                    _events.record_phase(
                        self.task_events, task_id=task_hex,
                        name=spec.name, phase="dispatch",
                        dur_s=now - t0, node_id=self.node_id.hex(),
                        proc=f"daemon:{self.node_id.hex()[:8]}",
                        trace_id=getattr(spec, "trace_id", ""),
                        start_wall=_events.wall_at(t0), end_mono=now)
                wrid, pend = client._request({
                    "op": "execute_task", "fn_id": msg["fid"],
                    "args_blob": msg["args"],
                    "ctx": client._ctx_fields(spec, self.node_stub,
                                              self.runtime),
                    "runtime_env": spec.runtime_env,
                    "backpressure": msg["backpressure"],
                })
                with self._lock:
                    self._task_rids[task_hex] = (client, wrid)
                    if spec.job_id is not None:
                        # job attribution for tenant-aware OOM
                        # preemption (pruned in _memory_candidates)
                        self._task_jobs[task_hex] = spec.job_id.hex()
                outcome = client._wait_outcome(wrid, pend)
            except WorkerCrashed as e:
                client.kill(expected=False)
                with self._lock:
                    self._task_rids.pop(task_hex, None)
                release_lease(True)
                conn.reply(rid, outcome="crashed", error=str(e))
                return
            except BaseException as e:  # noqa: BLE001 — must answer HOLD
                with self._lock:
                    self._task_rids.pop(task_hex, None)
                release_lease(False)
                conn.reply_error(rid, f"{type(e).__name__}: {e}")
                return
            self._pump_outcome(conn, rid, client, spec, outcome,
                               on_done=release_lease)

        self._task_pool.submit(run)
        return rpc.HOLD

    def handle_cancel_task(self, conn, rid, msg):
        with self._lock:
            entry = self._task_rids.get(msg["task_id"])
        if entry is None:
            return {"found": False}
        client, wrid = entry
        if msg["force"]:
            client.expected_death = False
            client.proc.terminate()
        else:
            client.cancel_request(wrid)
        return {"found": True}

    def handle_gen_ack(self, conn, rid, msg):
        with self._lock:
            entry = self._task_rids.get(msg["task_id"])
        if entry is not None:
            client, wrid = entry
            try:
                client._send({"op": "gen_ack", "target": wrid})
            except Exception:
                pass
        return {"ok": True}

    # -- actors ----------------------------------------------------------
    def handle_create_actor(self, conn, rid, msg):
        spec = cloudpickle.loads(msg["spec"])

        def run():
            from ray_tpu._private import worker_process as wp

            client = wp.acquire_worker()
            client.raw_outcomes = True
            client.runtime = self.runtime
            client.node = self.node_stub
            client.actor_id = spec.actor_id
            try:
                kind, blob = client.create_actor_instance(
                    spec, self.node_stub, msg["fid"], msg["args"])
            except wp.WorkerCrashed as e:
                client.kill(expected=False)
                conn.reply(rid, outcome="crashed", error=str(e))
                return
            if kind == "err_raw":
                client.actor_id = None
                wp.release_worker(client)
                conn.reply(rid, outcome="err", blob=blob)
                return
            client.actor_since = time.time()
            wp._checkout_done(client)   # actor ownership: permanent checkout
            router = self.runtime.process_router
            with router._lock:
                router._actor_workers[spec.actor_id] = client
            actor_id = spec.actor_id
            client.add_death_callback(
                lambda c, aid=actor_id: router._actor_worker_died(aid, c))
            # targeted fast lane: actors with DEFAULT (serialized)
            # execution get a per-actor tag in the native core so
            # method calls skip the daemon's Python entirely —
            # max_concurrency>1 / concurrency-group actors keep the
            # classic thread-per-call path
            fast_tag = None
            if (self.fast_core is not None
                    and getattr(spec, "max_concurrency", 1) == 1
                    and not getattr(spec, "concurrency_groups", None)):
                try:
                    with self._lock:
                        self._fast_tag_seq += 1
                        fast_tag = self._fast_tag_seq
                    lane_host = ("127.0.0.1"
                                 if self._fast_host in ("0.0.0.0", "")
                                 else self._fast_host)
                    trid, tpend = client._request({
                        "op": "join_fast_lane",
                        "addr": [lane_host, self.fast_port],
                        "tag": fast_tag})
                    tout = client._wait_outcome(trid, tpend)
                    if tout[0] not in ("ok", "ok_raw"):
                        fast_tag = None
                except Exception:
                    fast_tag = None
            conn.reply(rid, outcome="ok", worker_pid=client.proc.pid,
                       fast_tag=fast_tag)

        self._task_pool.submit(run)
        return rpc.HOLD

    def handle_call_actor_method(self, conn, rid, msg):
        spec = cloudpickle.loads(msg["spec"])
        router = self.runtime.process_router
        with router._lock:
            client = router._actor_workers.get(spec.actor_id)
        if client is None or client.dead:
            conn.reply(rid, outcome="dead")
            return rpc.HOLD
        task_hex = spec.task_id.hex()

        def run():
            from ray_tpu._private.worker_process import WorkerCrashed

            try:
                wrid, pend = client._request({
                    "op": "call_method", "method": spec.method_name,
                    "args_blob": msg["args"],
                    "ctx": client._ctx_fields(spec, self.node_stub,
                                              self.runtime),
                    "runtime_env": spec.runtime_env,
                })
                with self._lock:
                    self._task_rids[task_hex] = (client, wrid)
                    if spec.job_id is not None:
                        self._task_jobs[task_hex] = spec.job_id.hex()
                outcome = client._wait_outcome(wrid, pend)
            except WorkerCrashed as e:
                with self._lock:
                    self._task_rids.pop(task_hex, None)
                conn.reply(rid, outcome="crashed", error=str(e))
                return
            self._pump_outcome(conn, rid, client, spec, outcome)

        self._task_pool.submit(run)
        return rpc.HOLD

    def handle_kill_actor(self, conn, rid, msg):
        actor_id = ActorID.from_hex(msg["actor_id"])
        self.runtime.process_router.discard_actor(
            actor_id, expected=msg["expected"])
        return {"ok": True}

    # -- placement group bundle 2PC --------------------------------------
    def handle_prepare_bundle(self, conn, rid, msg):
        """Phase 1: reserve (advisory ledger — placement authority is the
        single controller; the 2PC matches the reference wire contract,
        node_manager.proto PrepareBundleResources)."""
        key = (msg["pg_id"], msg["index"])
        with self._lock:
            self._bundles[key] = {"resources": msg["resources"],
                                  "state": "PREPARED"}
        return {"ok": True}

    def handle_commit_bundle(self, conn, rid, msg):
        key = (msg["pg_id"], msg["index"])
        with self._lock:
            entry = self._bundles.get(key)
            if entry is None:
                return {"ok": False}
            entry["state"] = "COMMITTED"
        return {"ok": True}

    def handle_cancel_bundle(self, conn, rid, msg):
        with self._lock:
            self._bundles.pop((msg["pg_id"], msg["index"]), None)
        return {"ok": True}

    # -- object plane -----------------------------------------------------
    def _worker_client_id(self, client) -> str:
        """Ledger identity for a pool worker: ``w:<pid>:<generation>``
        (generation disambiguates a recycled pid). The FIRST grant arms
        the crash hook — the pipe-EOF death callback fans into
        reclaim_client, covering exit, crash, and SIGKILL alike."""
        if client is None:
            return UNKNOWN_CLIENT
        cid = getattr(client, "arena_client_id", None)
        if cid is None:
            pid = getattr(getattr(client, "proc", None), "pid", 0) or 0
            cid = f"w:{pid}:{getattr(client, 'gen', 0)}"
            try:
                client.arena_client_id = cid
                client.add_death_callback(
                    lambda _c, cid=cid: self.reclaim_client(cid, "death"))
            except Exception:
                pass
        return cid

    def handle_worker_shm_op(self, call: str, kw: Dict[str, Any],
                             client=None):
        """Object-plane ops from this daemon's OWN workers, served over
        the worker pipe without touching the owner (the zero-copy
        protocol's metadata leg — payloads never ride the pipe).
        Grants and reservations are charged to the issuing worker's
        ledger identity so its death reclaims them."""
        obj = self.objects
        if call == "shm_get_meta":
            cid = self._worker_client_id(client)
            out = []
            for oid in kw["oids"]:
                entry = None
                key = obj.key_for(oid)
                if key is not None:
                    meta = obj.get_ext_meta(key, cid)  # increfs ext slot
                    if meta is not None:
                        arena, cap, off, size, slot = meta
                        entry = {"arena": arena, "capacity": cap,
                                 "off": off, "size": size, "slot": slot,
                                 "raw": obj.raw_for(key)}
                out.append(entry)
            return out
        if call == "shm_release":
            cid = self._worker_client_id(client)
            for slot in kw.get("slots", ()):
                obj.ext_release(slot, cid)
            return True
        if call == "shm_put_reserve":
            if self.pressure_level() == "hard":
                # shed NEW arena writes while hard-pressured; the
                # worker falls back to its classic put path (service
                # degrades to a payload round trip, never to an error)
                return {"full": True, "backpressure": True}
            off = obj.reserve(kw["key"], int(kw["size"]),
                              self._worker_client_id(client))
            if off is None:
                return {"full": True}
            return {"off": off}
        if call == "shm_put_seal":
            return {"ok": obj.seal(kw["key"], ref=kw.get("ref") or b"",
                                   raw=kw.get("raw"))}
        if call == "shm_put_abort":
            obj.abort_reserve(kw["key"])
            return {"ok": True}
        raise ValueError(f"unknown shm op {call!r}")

    def handle_put_object(self, conn, rid, msg):
        if self.pressure_level() == "hard":
            # typed retriable backpressure: the driver raises
            # MemoryPressureError and rides RetryPolicy until relief
            return {"backpressure": True, "level": "hard"}
        self.objects.put(msg["oid"], msg["blob"])
        key = msg["oid"]
        if key.startswith(b"put:"):
            # driver puts key by logical oid: index it so same-node
            # attached workers resolve the ref without the owner
            self.objects.register_oid(key[4:], key)
        return {"ok": True}

    def _conn_client_id(self, conn) -> str:
        """Ledger identity for an RPC client (driver or external
        attacher): minted at hello, or lazily here for attachers that
        skip it — either way connection-scoped, so on_disconnect
        reclaims everything charged to it."""
        if conn is None:
            return UNKNOWN_CLIENT
        try:
            import uuid
            return conn.meta.setdefault(
                "arena_client_id", f"c:{uuid.uuid4().hex[:12]}")
        except Exception:
            return UNKNOWN_CLIENT

    def handle_create_object(self, conn, rid, msg):
        """Reserve arena space for a same-host client's direct put (the
        client writes the payload through its own mapping, then
        seal_object). Idempotent for a retried (oid, size)."""
        if self.pressure_level() == "hard":
            return {"full": True, "backpressure": True}
        off = self.objects.reserve(msg["oid"], int(msg["size"]),
                                   self._conn_client_id(conn))
        if off is None:
            return {"full": True}
        return {"ok": True, "off": off, "arena": self.objects.arena_name,
                "capacity": (self.objects._shm.capacity()
                             if self.objects._shm else 0)}

    def handle_seal_object(self, conn, rid, msg):
        """Seal a direct-put entry (idempotent retry target: a dropped
        seal reply just re-seals). ``ref``/``raw`` feed the node-local
        oid index so attached workers resolve the object zero-copy."""
        raw = msg.get("raw")
        ok = self.objects.seal(msg["oid"], ref=msg.get("ref") or b"",
                               raw=tuple(raw) if raw else None)
        return {"ok": ok}

    def handle_get_object(self, conn, rid, msg):
        if msg["prefer_shm"]:
            # ext-slot grants only to callers that ADVERTISE the slot
            # protocol (slot_ok) — an older driver would release via
            # release_object(oid), which decrements the entry's PIN
            # ref (corrupting ownership) and leaks the slot ref
            meta = (self.objects.get_ext_meta(msg["oid"],
                                              self._conn_client_id(conn))
                    if msg.get("slot_ok") else None)
            if meta is not None:
                # ext slot ref taken on the caller's behalf: the caller
                # reads through its own mapping and drops the ref with
                # a local atomic (or release_object{slot} if its attach
                # failed) — no payload round trip, no release RPC
                arena, cap, off, size, slot = meta
                return {"shm": arena, "capacity": cap, "off": off,
                        "size": size, "slot": slot}
            ref = self.objects.get_shm_ref(msg["oid"])
            if ref is not None:
                arena, cap, off, size = ref
                return {"shm": arena, "capacity": cap, "off": off,
                        "size": size}
        blob = self.objects.get_blob(msg["oid"])
        if blob is None:
            return {"missing": True}
        return {"blob": blob}

    def handle_release_object(self, conn, rid, msg):
        if msg.get("slot") is not None:
            # ext-slot release fallback (client could not attach)
            self.objects.ext_release(int(msg["slot"]),
                                     self._conn_client_id(conn))
            return {"ok": True}
        self.objects.release(msg["oid"])
        return {"ok": True}

    def handle_free_objects(self, conn, rid, msg):
        for oid in msg["oids"]:
            self.objects.delete(oid)
        return {"ok": True}

    @rpc.concurrent
    def handle_pull_object(self, conn, rid, msg):
        """Inter-node transfer: fetch from a peer daemon into the local
        table via the PullManager (chunked + deduped + prioritized;
        reference: ObjectManager::Pull/Push). ``from_addr`` is a location
        hint; when absent (or stale) the owner's object directory is
        consulted."""
        oid = msg["oid"]
        if self.objects.contains(oid):
            return {"ok": True, "already": True}
        priority = int(msg.get("priority", PULL_PRIORITY_TASK_ARGS))
        hint = [tuple(msg["from_addr"])] if msg["from_addr"] else []
        last = {}
        tried = set()
        # Try the caller's hint first; fall back to the owner's object
        # directory when there is no hint OR the hint went stale (peer
        # evicted/died) — the directory lookup is lazy so the common
        # hinted pull pays no extra owner round-trip.
        for phase in range(2):
            candidates = hint if phase == 0 else [
                tuple(a) for a in self._locate_via_owner(oid)]
            for addr in candidates:
                if addr in tried:
                    continue
                tried.add(addr)
                pull = self.pulls.request(oid, addr, priority)
                if not pull.event.wait(timeout=120.0):
                    return {"ok": False, "error": "pull timed out"}
                if pull.ok:
                    return {"ok": True}
                last = ({"ok": False, "missing": True} if pull.missing
                        else {"ok": False, "error": pull.error})
        return last or {"ok": False, "missing": True}

    @rpc.concurrent
    def handle_push_object(self, conn, rid, msg):
        """Driver-directed proactive push of a local object to a peer
        daemon (dep prefetch, drain migration). Dedupes in flight and
        against copies the destination already holds; ``ref`` carries
        the logical ObjectID so the receiver's node-local index lets
        its attached workers resolve the pushed copy zero-copy."""
        push = self.pushes.request(msg["oid"], tuple(msg["to_addr"]),
                                   ref=msg.get("ref") or b"")
        if not push.event.wait(timeout=120.0):
            return {"ok": False, "error": "push timed out"}
        if push.ok:
            return {"ok": True, "skipped": push.skipped}
        return {"ok": False, "error": push.error}

    def handle_push_chunk(self, conn, rid, msg):
        """Receiver side of a proactive push: chunks assemble into one
        buffer; ``have`` tells the sender to stop (a pull landed it)."""
        return self.push_rx.chunk(msg["oid"], int(msg["off"]),
                                  int(msg["total"]), msg["blob"],
                                  ref=msg.get("ref") or b"",
                                  raw=msg.get("raw"))

    def handle_object_meta(self, conn, rid, msg):
        size = self.objects.nbytes_of(msg["oid"])
        if size is None:
            return {"missing": True}
        return {"size": size}

    def handle_get_object_chunk(self, conn, rid, msg):
        blob = self.objects.read_range(msg["oid"], msg["off"], msg["size"])
        if blob is None:
            return {"missing": True}
        return {"blob": blob}

    # -- cross-language tier (C++ API) ------------------------------------
    # Reference capability: `cpp/include/ray/api.h` task/actor submission
    # + `python/ray/cross_language.py` descriptors. Functions/classes are
    # exported by NAME to the head KV from Python
    # (`ray_tpu.xlang.export_task/export_actor_class`); C++ submits by
    # name with msgpack args; execution happens on this daemon's pooled
    # worker processes; results return as plain msgpack values.

    def _xlang_head(self):
        with self._lock:
            if getattr(self, "_xlang_head_client", None) is None:
                if getattr(self, "head_addr", None) is None:
                    raise RuntimeError("daemon has no head address")
                self._xlang_head_client = HeadClient(self.head_addr)
            return self._xlang_head_client

    @staticmethod
    def _xlang_plain(value):
        """Results crossing the language boundary must be msgpack-plain."""
        import numpy as _np
        if isinstance(value, (_np.integer,)):
            return int(value)
        if isinstance(value, (_np.floating,)):
            return float(value)
        if isinstance(value, _np.ndarray):
            return value.tolist()
        if isinstance(value, (list, tuple)):
            return [DaemonService._xlang_plain(v) for v in value]
        if isinstance(value, dict):
            return {str(k): DaemonService._xlang_plain(v)
                    for k, v in value.items()}
        if value is None or isinstance(value, (bool, int, float, str,
                                               bytes)):
            return value
        raise TypeError(
            f"xlang result of type {type(value).__name__} cannot cross "
            f"the language boundary; return msgpack-plain values")

    def _xlang_kv_blob(self, kind: str, name: str):
        return self._xlang_head().kv_get(
            f"xlang:{kind}:{name}".encode())

    def handle_xlang_submit(self, conn, rid, msg):
        """One-shot cross-language task on a pooled worker."""
        def run():
            from ray_tpu._private import worker_process as wp
            client = None
            streaming = False
            try:
                blob = self._xlang_kv_blob("fn", msg["name"])
                if blob is None:
                    conn.reply(rid, outcome="err",
                               error=f"no exported xlang function "
                                     f"{msg['name']!r}")
                    return
                fid = wp.register_function_blob(blob)
                spec = TaskSpec(
                    task_id=TaskID.from_random(), kind=TaskKind.NORMAL,
                    name=f"xlang:{msg['name']}", func=None)
                args_blob = cloudpickle.dumps((tuple(msg["args"]), {}))
                client = wp.acquire_worker()
                # pooled workers may carry raw_outcomes=True from a
                # prior driver-relay task — this handler decodes locally
                client.raw_outcomes = False
                client.runtime = self.runtime
                client.node = self.node_stub
                outcome = client.execute_task(spec, self.node_stub, fid,
                                              args_blob)
                if outcome[0] == "ok":
                    conn.reply(rid, outcome="ok",
                               result=self._xlang_plain(outcome[1]))
                elif outcome[0] == "gen":
                    # the worker is mid-stream: it must NOT return to
                    # the idle pool while still producing
                    streaming = True
                    conn.reply(rid, outcome="err",
                               error="xlang tasks cannot stream")
                else:
                    conn.reply(rid, outcome="err",
                               error=repr(outcome[1]))
            except BaseException as e:  # noqa: BLE001 — shipped back
                conn.reply(rid, outcome="err", error=repr(e))
            finally:
                if client is not None:
                    from ray_tpu._private import worker_process as wp
                    if streaming:
                        client.kill(expected=True)
                    wp.release_worker(client)   # reaps killed workers

        self._task_pool.submit(run)
        return rpc.HOLD

    def handle_xlang_create_actor(self, conn, rid, msg):
        """Create a Python actor (class exported by name) on a pooled
        worker, addressable by ``msg['name']`` for xlang_call_actor.
        Reuses ProcessRouter.create_actor — one copy of the checkout/
        registration protocol."""
        def run():
            from ray_tpu._private import worker_process as wp
            try:
                with self._lock:
                    taken = msg["name"] in self._xlang_actors
                if taken:
                    conn.reply(rid, outcome="err",
                               error=f"xlang actor name "
                                     f"{msg['name']!r} already taken")
                    return
                blob = self._xlang_kv_blob("actor", msg["cls"])
                if blob is None:
                    conn.reply(rid, outcome="err",
                               error=f"no exported xlang actor class "
                                     f"{msg['cls']!r}")
                    return
                fid = wp.register_function_blob(blob)
                spec = TaskSpec(
                    task_id=TaskID.from_random(),
                    kind=TaskKind.ACTOR_CREATION,
                    name=f"xlang:{msg['cls']}", func=None,
                    actor_id=ActorID.from_random(),
                    actor_name=msg["name"])
                args_blob = cloudpickle.dumps((tuple(msg["args"]), {}))
                router = self.runtime.process_router
                router.create_actor(spec, self.node_stub,
                                    (fid, args_blob))
                with self._lock:
                    lost_race = msg["name"] in self._xlang_actors
                    if not lost_race:
                        self._xlang_actors[msg["name"]] = [
                            spec.actor_id, 0, threading.Lock()]
                if lost_race:
                    # lost a concurrent create race: kill ours. The
                    # worker kill (process teardown) and the reply
                    # (wire send) both happen OUTSIDE the ledger lock.
                    with router._lock:
                        dup = router._actor_workers.pop(
                            spec.actor_id, None)
                    if dup is not None:
                        dup.kill(expected=True)
                    conn.reply(rid, outcome="err",
                               error=f"xlang actor name "
                                     f"{msg['name']!r} already taken")
                    return
                conn.reply(rid, outcome="ok",
                           actor_id=spec.actor_id.hex())
            except BaseException as e:  # noqa: BLE001 — shipped back
                conn.reply(rid, outcome="err", error=repr(e))

        self._task_pool.submit(run)
        return rpc.HOLD

    def handle_xlang_call_actor(self, conn, rid, msg):
        with self._lock:
            entry = self._xlang_actors.get(msg["name"])
        if entry is None:
            return {"outcome": "err",
                    "error": f"no xlang actor named {msg['name']!r}"}
        actor_id = entry[0]
        router = self.runtime.process_router
        with router._lock:
            client = router._actor_workers.get(actor_id)
        if client is None or client.dead:
            return {"outcome": "err", "error": "actor is dead"}

        def run():
            try:
                # Per-actor submission lock: actors guarantee
                # serialized, seqno-ordered method execution. Two C++
                # clients hitting the same named actor from different
                # pool threads must not run (or be delivered)
                # concurrently — hold the actor lock across seqno
                # assignment AND the call itself.
                with entry[2]:
                    with self._lock:
                        entry[1] += 1
                        seqno = entry[1]
                    spec = TaskSpec(
                        task_id=TaskID.from_random(),
                        kind=TaskKind.ACTOR_TASK,
                        name=f"xlang:{msg['name']}.{msg['method']}",
                        func=msg["method"], actor_id=actor_id,
                        method_name=msg["method"], seqno=seqno)
                    args_blob = cloudpickle.dumps(
                        (tuple(msg["args"]), {}))
                    outcome = client.call_method(spec, self.node_stub,
                                                 args_blob)
                # router-created actor workers run non-raw by default,
                # but tolerate raw blobs (same-language daemon decodes)
                if outcome[0] in ("ok", "ok_raw"):
                    value = (cloudpickle.loads(outcome[1])
                             if outcome[0] == "ok_raw" else outcome[1])
                    conn.reply(rid, outcome="ok",
                               result=self._xlang_plain(value))
                elif outcome[0] == "err_raw":
                    e, _tb = cloudpickle.loads(outcome[1])
                    conn.reply(rid, outcome="err", error=repr(e))
                elif outcome[0] == "err":
                    conn.reply(rid, outcome="err", error=repr(outcome[1]))
                else:
                    conn.reply(rid, outcome="err",
                               error=f"unsupported outcome {outcome[0]}")
            except BaseException as e:  # noqa: BLE001 — shipped back
                conn.reply(rid, outcome="err", error=repr(e))

        self._task_pool.submit(run)
        return rpc.HOLD

    # -- peer resource gossip (reference: ray_syncer.h:83) ---------------
    def _syncer_self_entry(self) -> Dict[str, Any]:
        with self._lock:
            running = len(self._task_rids)
        fast = (self.fast_core.stats()
                if self.fast_core is not None else {})
        return {
            "running": running + fast.get("inflight", 0)
            + fast.get("queued", 0),
            "store_used": self.objects.used_bytes(),
            "fast_queued": fast.get("queued", 0),
            # pressure level rides the load view so every driver's
            # pick_node can soft-exclude hard-pressure nodes even when
            # it never heard the direct node_pressure push
            "pressure": self.pressure_level(),
        }

    def _syncer_tick(self) -> None:
        """One anti-entropy round: refresh the self entry, exchange full
        views with <=2 random peers (merge by version), occasionally
        push the merged view to the head. Peer-to-peer propagation means
        the head needs O(1) incoming reports per interval regardless of
        node count — the RaySyncer scaling property — instead of every
        node pushing every interval."""
        import random as _random

        me = self.node_id.hex()
        # Build the self entry BEFORE taking the syncer lock: it reads
        # the daemon ledger (self._lock) and the object-store accounting
        # — nesting those under _syncer_lock stalls every concurrent
        # syncer_exchange/syncer_view handler behind store bookkeeping.
        load = self._syncer_self_entry()
        with self._syncer_lock:
            mine = self._syncer_view.get(me)
            version = (mine["v"] + 1) if mine else 1
            self._syncer_view[me] = {"v": version,
                                     "load": load,
                                     "ts": time.time()}
            view = {k: dict(v) for k, v in self._syncer_view.items()}
        peers = [(hex_id, tuple(addr))
                 for hex_id, addr in self._syncer_peers().items()
                 if hex_id != me]
        for hex_id, addr in _random.sample(peers, min(2, len(peers))):
            try:
                out = self._peer(addr).call("syncer_exchange",
                                            view=view, timeout=5.0)
                self._syncer_merge(out.get("view", {}))
            except (rpc.RpcError, OSError):
                continue
        # head push: probabilistic so ~one node per interval reports
        # (every node pushes when the cluster is tiny)
        if _random.random() < 1.0 / max(1, len(peers)):
            self._syncer_push_head()

    def _syncer_peers(self) -> Dict[str, Any]:
        """node hex -> daemon addr, from the head membership (cached)."""
        now = time.monotonic()
        if now - self._syncer_peers_ts < 5.0:
            return self._syncer_peers_cache
        try:
            head = HeadClient(self.head_addr)
            try:
                nodes = head.list_nodes()
            finally:
                head.close()
            self._syncer_peers_cache = {
                n["node_id"]: tuple(n["addr"]) for n in nodes
                if n.get("alive") and n.get("addr")}
            self._syncer_peers_ts = now
        except (OSError, rpc.RpcError):
            pass
        return self._syncer_peers_cache

    def _syncer_merge(self, view: Dict[str, Any]) -> None:
        with self._syncer_lock:
            for hex_id, entry in view.items():
                cur = self._syncer_view.get(hex_id)
                if cur is None or entry["v"] > cur["v"]:
                    self._syncer_view[hex_id] = dict(entry)

    def _syncer_push_head(self) -> None:
        try:
            head = HeadClient(self.head_addr)
            try:
                with self._syncer_lock:
                    view = {k: dict(v)
                            for k, v in self._syncer_view.items()}
                head._call("report_loads_gossip", view=view)
            finally:
                head.close()
        except (OSError, rpc.RpcError):
            pass

    def _syncer_loop(self) -> None:
        while True:
            try:
                self._syncer_tick()
            except Exception:
                pass
            time.sleep(self._syncer_interval_s)

    def handle_syncer_exchange(self, conn, rid, msg):
        self._syncer_merge(msg["view"])
        with self._syncer_lock:
            return {"view": {k: dict(v)
                             for k, v in self._syncer_view.items()}}

    def handle_syncer_view(self, conn, rid, msg):
        with self._syncer_lock:
            return {"view": {k: dict(v)
                             for k, v in self._syncer_view.items()}}

    # -- node-side OOM defense (reference: the raylet memory monitor,
    # common/memory_monitor.h + worker_killing_policy) -------------------
    def _memory_candidates(self):
        """This node's killable worker processes: push-lane running
        tasks (``self._task_rids`` — the daemon's own tracking; the
        router's ``_running`` is only the xlang path here), actor
        workers, and dedicated fast-lane workers. Task ids recorded as
        hex — that is what the driver's oom_check sends."""
        from ray_tpu._private.memory_monitor import _Candidate
        out = []
        with self._lock:
            running = dict(self._task_rids)
            # prune finished tasks' job attributions here (the one
            # periodic scan) instead of chasing every pop site
            for gone in set(self._task_jobs) - set(running):
                self._task_jobs.pop(gone, None)
            jobs = dict(self._task_jobs)
        router = self.runtime.process_router
        with router._lock:
            actors = dict(router._actor_workers)
        actor_pids = {c.proc.pid for c in actors.values()}
        for task_hex, (client, _rid) in running.items():
            if client.alive() and client.proc.pid not in actor_pids:
                out.append(_Candidate(
                    client.proc.pid, "task", task_id=task_hex,
                    retriable=True, started_at=0.0,
                    owner_key=jobs.get(task_hex, "")))
        for actor_id, client in actors.items():
            if client.alive():
                out.append(_Candidate(
                    client.proc.pid, "actor", actor_id=actor_id,
                    retriable=True, started_at=0.0, owner_key=""))
        for w in list(self._fast_workers):
            if w.alive():
                out.append(_Candidate(
                    w.proc.pid, "task", retriable=True,
                    started_at=0.0, owner_key="fast-lane"))
        return out

    def start_memory_monitor(self) -> None:
        from ray_tpu._private.config import cfg
        from ray_tpu._private.memory_monitor import (MemoryMonitor,
                                                     TenantAwarePolicy)
        if cfg().memory_monitor:
            self.memory_monitor = MemoryMonitor(
                None, candidates_fn=self._memory_candidates)
            if cfg().memory_pressure:
                # degradation order under pressure: over-quota tenants'
                # workers (driver-ledger verdict, synced) die first
                self.memory_monitor.policy = TenantAwarePolicy(
                    self.memory_monitor.policy,
                    lambda: getattr(self, "_over_quota_jobs", ()))
            self.memory_monitor.start()
        if cfg().memory_pressure:
            from ray_tpu._private.pressure import PressureController
            self.pressure = PressureController(
                self.objects,
                monitor=getattr(self, "memory_monitor", None),
                on_level=self._on_pressure_level)
            self.pressure.start()

    def pressure_level(self) -> str:
        return self.pressure.level if self.pressure is not None else "ok"

    def _on_pressure_level(self, old: str, new: str) -> None:
        """Pressure transition: tell the driver immediately (placement
        reacts now, not at the next gossip round) — the same push lane
        DRAINING uses. Gossip/heartbeats carry it to everyone else."""
        self.notify_driver("node_pressure", level=new)

    def handle_set_memory_limit(self, conn, rid, msg):
        """Driver-pushed cluster-wide limit; starts this node's monitor
        if the flag left it off."""
        from ray_tpu._private.memory_monitor import MemoryMonitor
        mon = getattr(self, "memory_monitor", None)
        if mon is None:
            mon = self.memory_monitor = MemoryMonitor(
                None, candidates_fn=self._memory_candidates,
                interval_s=0.25)
            mon.start()
        mon.limit = int(msg["limit"])
        return {"ok": True}

    def handle_oom_check(self, conn, rid, msg):
        """Did this node's monitor OOM-kill the worker running
        ``task_id`` (or, for FAST-LANE crashes only, ANY worker very
        recently — lane tasks are attributed by time, their ids live in
        the C++ core)?"""
        if _fp.ENABLED:
            _fp.fire("daemon.oom_check", task=msg.get("task_id", ""))
        mon = getattr(self, "memory_monitor", None)
        if mon is None:
            return {"oom": False, "kills": 0}
        if msg.get("task_id") and any(
                (t.hex() if hasattr(t, "hex") else t) == msg["task_id"]
                for t in mon.oom_killed_tasks):
            return {"oom": True, "kills": mon.kills}
        # the un-attributed-kill fallback applies ONLY to lane crashes
        # (their task ids live in the C++ core); a classic worker's
        # segfault inside the attribution window must not steal — and
        # consume — the lane crash's OOM entry
        if not msg.get("fast_lane"):
            return {"oom": False, "kills": mon.kills}
        # CONSUMES the entry: one kill explains one crash — it must not
        # keep painting later, unrelated crashes as OOM
        return {"oom": mon.consume_unattributed_kill(),
                "kills": mon.kills}

    # -- per-node agent (reference: dashboard/agent.py) -------------------
    def start_agent(self, host: str = "127.0.0.1") -> Optional[int]:
        """Per-node observability HTTP endpoint, served from THIS daemon
        process (the dashboard agent role: the head's dashboard answers
        cluster questions; node-local stats/profiles come from the node
        itself):
          GET /api/stats        daemon_stats as JSON
          GET /api/profile/cpu  in-process stack-sample flamegraph data
          GET /metrics          Prometheus exposition (this process)
        Returns the bound port (advertised via daemon_stats)."""
        import json as _json
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        service = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _send(self, body: bytes, ctype: str,
                      code: int = 200) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                try:
                    path = self.path.split("?")[0].rstrip("/")
                    if path == "/api/stats":
                        with service._lock:
                            stats = {
                                "node_id": service.node_id.hex(),
                                "pid": os.getpid(),
                                "leases": len(service._leases),
                                "running": len(service._task_rids),
                            }
                        stats["store_used"] = (
                            service.objects.used_bytes())
                        if service.fast_core is not None:
                            stats["fast_lane"] = (
                                service.fast_core.stats())
                        self._send(_json.dumps(stats).encode(),
                                   "application/json")
                    elif path == "/api/profile/cpu":
                        from urllib.parse import parse_qsl

                        from ray_tpu.util.profiling import (
                            sample_cpu_profile)
                        q = dict(parse_qsl(
                            self.path.partition("?")[2]))
                        dur = min(float(q.get("duration", 2)), 30.0)
                        self._send(_json.dumps(
                            sample_cpu_profile(duration_s=dur)).encode(),
                            "application/json")
                    elif path == "/metrics":
                        from ray_tpu.util.metrics import prometheus_text
                        self._send(prometheus_text().encode(),
                                   "text/plain; version=0.0.4")
                    else:
                        self._send(b'{"error": "unknown path"}',
                                   "application/json", 404)
                except Exception as e:  # noqa: BLE001 — to the client
                    self._send(_json.dumps(
                        {"error": repr(e)}).encode(),
                        "application/json", 500)

        try:
            server = ThreadingHTTPServer((host, 0), Handler)
        except OSError:
            return None
        threading.Thread(target=server.serve_forever, daemon=True,
                         name="node-agent").start()
        self.agent_port = server.server_address[1]
        return self.agent_port

    # -- misc -------------------------------------------------------------
    def handle_core_release(self, conn, rid, msg):
        return {"ok": True}  # owner-side holds are driver-local

    def handle_daemon_ping(self, conn, rid, msg):
        return {"pid": os.getpid(), "node_id": self.node_id.hex()}

    def handle_net_chaos(self, conn, rid, msg):
        """Chaos-campaign hook: install (or clear, with an empty spec) a
        seeded netchaos registry in THIS daemon process. Programmatic
        per-node activation — the env form reaches every spawned
        process, so a schedule that must degrade ONE daemon's head link
        (partition-then-death-mark campaigns) arms it here instead."""
        from ray_tpu._private import netchaos as _nc
        spec = msg.get("spec") or ""
        if not spec:
            _nc.reset()
            return {"ok": True, "active": False}
        seed = msg.get("seed")
        _nc.activate(spec, seed=int(seed) if seed is not None else None)
        return {"ok": True, "active": True, "links": _nc.describe()}

    def handle_fail_points(self, conn, rid, msg):
        """Chaos-campaign hook, the failpoint twin of ``net_chaos``:
        install (or clear, with an empty spec) a seeded failpoint
        registry in THIS daemon process. Programmatic per-node arming —
        the env form reaches every spawned process, so a schedule that
        must pressure ONE node (``pressure.level=return(hard)``) arms
        it here instead."""
        spec = msg.get("spec") or ""
        if not spec:
            _fp.reset()
            return {"ok": True, "active": False}
        seed = msg.get("seed")
        _fp.activate(spec, seed=int(seed) if seed is not None else None)
        return {"ok": True, "active": True, "arms": _fp.describe()}

    def handle_tenancy_sync(self, conn, rid, msg):
        """Adopt the driver's per-job quota/weight table. The daemon is
        not the admission authority (dispatch gating runs driver-side,
        single-controller placement) — it mirrors the table so its own
        /metrics lane exports the cluster's quota configuration even
        when the driver is gone, and daemon_stats can show it."""
        jobs = msg.get("jobs") or {}
        self._tenancy_jobs = {str(j): dict(r) for j, r in jobs.items()}
        # over-quota jobs (driver ledger verdict): the memory monitor's
        # tenant-aware policy preempts these jobs' workers first
        self._over_quota_jobs = {str(j)
                                 for j in (msg.get("over_quota") or ())}
        for job, rec in self._tenancy_jobs.items():
            for res, cap in (rec.get("quota") or {}).get(
                    "hard", {}).items():
                _metrics.Gauge(
                    "ray_tpu_job_quota_bytes",
                    "configured hard quota caps per job and resource "
                    "axis", ("job_id", "resource")).set(
                    float(cap), tags={"job_id": job, "resource": res})
        return {"ok": True, "count": len(jobs)}

    def handle_daemon_stats(self, conn, rid, msg):
        with self._lock:
            leases = len(self._leases)
            running = len(self._task_rids)
        fast = (self.fast_core.stats()
                if self.fast_core is not None else {})
        # "running" covers BOTH planes: classic daemon-Python tasks and
        # fast-lane tasks in the native core (queued or executing)
        running += fast.get("inflight", 0) + fast.get("queued", 0)
        return {"leases": leases, "running": running,
                "store_used": self.objects.used_bytes(),
                "pull_stats": dict(self.pulls.stats),
                "push_stats": dict(self.pushes.stats),
                "push_rx_stats": dict(self.push_rx.stats),
                "arena": self.objects.arena_name,
                # grant-ledger leak observability with per-client
                # attribution (who holds a slot, is the holder alive)
                "slot_refs": self.slot_ref_attribution(),
                "fast_lane": fast,
                "agent_port": getattr(self, "agent_port", None),
                "pressure": self.pressure_level(),
                "spill": self.objects.spill_stats(),
                "actors": len(
                    self.runtime.process_router._actor_workers)}

    @rpc.concurrent
    def handle_profile_burst(self, conn, rid, msg):
        """On-demand stack-sampling burst: this daemon plus every live
        pool worker, one record per process. Blocks ~duration
        (@concurrent: runs off the connection lane)."""
        duration = max(0.1, min(float(msg.get("duration") or 2.0), 30.0))
        from ray_tpu._private import worker_process as _wp
        procs: Dict[str, Dict[str, Any]] = {}
        workers = list(_wp.live_workers())
        threads = []
        for w in workers:
            def burst_one(w=w):
                try:
                    rec = w.profile_burst(duration)
                    if isinstance(rec, dict) and rec.get("proc"):
                        procs[rec["proc"]] = rec
                except Exception:
                    pass    # a dying worker must not fail the burst
            t = threading.Thread(target=burst_one, daemon=True,
                                 name="profile-burst-worker")
            t.start()
            threads.append(t)
        own = _profiling.burst_record(
            f"daemon:{self.node_id.hex()[:8]}", duration_s=duration)
        for t in threads:
            t.join(timeout=duration + 10.0)
        procs[own["proc"]] = own
        # continuous-mode records (own sampler + result-frame ingests)
        # ride along so burst consumers see the low-rate history too
        node = _profiling.node_profile()
        for rec in (node or {}).get("procs", []):
            procs.setdefault(rec.get("proc", "?"), rec)
        return {"procs": list(procs.values())}

    def handle_daemon_stop(self, conn, rid, msg):
        def stop():
            time.sleep(0.1)
            self.runtime.process_router.shutdown()
            self.objects.close()
            os._exit(0)

        threading.Thread(target=stop, daemon=True).start()
        return {"ok": True}


# profile-flush cadence: cumulative snapshots, so a lower rate than
# spans costs nothing but staleness
_PROFILE_PUSH_S = 2.0


def _gate_profile_flush(last_push: float,
                        now: Optional[float] = None,
                        period: float = _PROFILE_PUSH_S):
    """The heartbeat's profile payload, or None (off-cadence, nothing
    sampled, or lost to the ``profile.flush`` seam). Records are
    CUMULATIVE and the head stores them with replace semantics, so the
    retry discipline is the trace.flush one: the caller advances its
    cadence stamp only on an acked beat — a dropped payload is re-sent
    (fresher) on the next beat."""
    now = time.monotonic() if now is None else now
    if now - last_push < period:
        return None
    try:
        payload = _profiling.node_profile()
    except Exception:
        return None
    if payload is not None and _fp.ENABLED:
        try:
            if _fp.fire("profile.flush",
                        procs=len(payload.get("procs", []))) is _fp.DROP:
                payload = None
        except Exception:
            payload = None
    return payload


# per-client attribution series published last beat: departed clients'
# series are removed (not left frozen at their last value) so the
# dashboard never shows a reclaimed client as still holding slots
_CLIENT_SERIES_SEEN: set = set()


def _publish_object_plane_metrics(service: DaemonService) -> None:
    """Leak + transfer observability gauges, refreshed each beat so
    they ride the metrics snapshot to the head: arena slot grants still
    referenced (a crashed client's not-yet-reclaimed grant shows up
    here, attributed to its ledger identity) and the push engine's
    cumulative/in-flight counters."""
    from ray_tpu.util.metrics import Gauge
    slots = service.slot_ref_attribution()
    g = Gauge("ray_tpu_arena_slot_refs",
              "external arena slot grants: slots still referenced "
              "('held') and total outstanding refs ('refs')",
              tag_keys=("state",))
    g.set(float(slots["held"]), tags={"state": "held"})
    g.set(float(slots["refs"]), tags={"state": "refs"})
    cg = Gauge("ray_tpu_arena_slot_clients",
               "outstanding ledger grants per client identity "
               "(alive=false rows are reclamation candidates)",
               tag_keys=("client", "alive"))
    live = set()
    for row in slots.get("clients", ()):
        alive = row.get("alive")
        tags = {"client": row["client"],
                "alive": "unknown" if alive is None else str(alive).lower()}
        cg.set(float(row["granted"]), tags=tags)
        live.add(tuple(sorted(tags.items())))
    for stale in _CLIENT_SERIES_SEEN - live:
        try:
            cg.remove(dict(stale))
        except Exception:
            pass
    _CLIENT_SERIES_SEEN.clear()
    _CLIENT_SERIES_SEEN.update(live)
    push = Gauge("ray_tpu_push_stats",
                 "object-plane push engine counters (cumulative), "
                 "tx = PushManager, rx = PushReceiver",
                 tag_keys=("side", "stat"))
    for stat, v in service.pushes.stats.items():
        push.set(float(v), tags={"side": "tx", "stat": stat})
    for stat, v in service.push_rx.stats.items():
        push.set(float(v), tags={"side": "rx", "stat": stat})
    Gauge("ray_tpu_push_inflight",
          "pushes queued or transferring right now").set(
        float(service.pushes.inflight_count()))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--head", required=True,
                        help="host:port of the head process")
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--resources", default="{}",
                        help="JSON resource map")
    parser.add_argument("--labels", default="{}")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--object-store-bytes", type=int,
                        default=256 * 1024 * 1024)
    parser.add_argument("--persist", action="store_true",
                        help="survive driver disconnects (shared cluster)")
    parser.add_argument("--announce-fd", type=int, default=-1)
    args = parser.parse_args()

    resources = json.loads(args.resources)
    from ray_tpu._private import netchaos as _nc
    _nc.set_local_role("daemon")
    service = DaemonService(args.node_id, resources,
                            args.object_store_bytes,
                            persist=args.persist, host=args.host)
    eventloop.set_proc_label(f"daemon:{args.node_id[:8]}")
    server = rpc.serve(service, host=args.host, port=0).start()
    if args.announce_fd >= 0:
        os.write(args.announce_fd, f"{server.addr[1]}\n".encode())
        os.close(args.announce_fd)

    head_host, head_port = args.head.rsplit(":", 1)
    head_addr = (head_host, int(head_port))
    service.head_addr = head_addr       # cross-language KV lookups
    threading.Thread(target=service._syncer_loop, daemon=True,
                     name="syncer-gossip").start()
    service.start_agent(host=args.host)
    service.start_memory_monitor()
    labels = json.loads(args.labels)
    head = HeadClient(head_addr)
    out = head.register_node(args.node_id, resources, labels, server.addr)
    if out.get("dead"):
        os._exit(0)     # fenced: this node_id was declared dead
    # Fencing epoch: minted by the head at every registration; stamped
    # into heartbeats and result frames so a healed partition can never
    # deliver results from a superseded incarnation.
    service.epoch = int(out.get("epoch") or 0)

    # Head-FT (reference: raylets resync after a GCS restart,
    # gcs_init_data.h): on transport failure keep re-dialing the head for
    # a grace window and re-register; only a head that stays down — or
    # one that explicitly declares us dead — ends the session.
    from ray_tpu._private.config import cfg
    grace = cfg().head_grace_s

    # Preemption watcher: SIGTERM / the maintenance-notice file trigger
    # a self-announced graceful drain (the head then fences placements,
    # the driver migrates, and the deadline escalates to node death).
    watcher = PreemptionWatcher(args.node_id, head_addr,
                                cfg().drain_deadline_s,
                                cfg().drain_notice_file)
    watcher.install_sigterm()
    watcher.start()
    service.preemption_watcher = watcher

    def reconnect() -> "HeadClient | None":
        from ray_tpu._private.retry import RetryPolicy

        if grace <= 0:
            # head FT disabled: the window is already expired
            # (RetryPolicy reads deadline_s=0 as "no deadline", which
            # would dial the dead head forever)
            return None

        def attempt() -> HeadClient:
            client = HeadClient(head_addr)
            try:
                rep = client.register_node(args.node_id, resources,
                                           labels, server.addr)
            except BaseException:
                client.close()
                raise
            if rep.get("dead"):
                client.close()
                os._exit(0)     # fenced out during the head outage
            service.epoch = int(rep.get("epoch") or service.epoch)
            return client

        try:
            return RetryPolicy.default(deadline_s=grace).run(
                attempt, loop="daemon.head_reconnect",
                retry_on=(OSError, rpc.RpcError))
        except (OSError, rpc.RpcError):
            return None     # head stayed down past the grace window

    # Observability piggyback state: span-flush cursor into this
    # daemon's TaskEventBuffer (advanced only after a delivered beat, so
    # a lost frame retries) and the metric-snapshot cadence (absolute
    # snapshots — a re-send replaces, never double-counts).
    trace_cursor = 0
    last_metrics_push = 0.0
    last_trace_push = 0.0
    last_profile_push = 0.0
    _METRICS_PUSH_S = 1.0
    _TRACE_PUSH_S = 0.5     # span-flush cadence: bounds head-store
    _TRACE_BATCH_MAX = 2000  # write rate under bursty task loads

    from ray_tpu.objectplane import tiers as _tiers

    while True:  # heartbeat loop; exit if the head declared us dead
        time.sleep(_hb_interval())
        try:
            # object-plane housekeeping: reap deferred deletes whose
            # attached-process refs dropped (external releases are
            # silent atomics), publish host-tier occupancy — the gauge
            # rides the metrics snapshot below to the head
            service.objects.reap()
            # orphan sweep: backstop for any death signal the event-
            # path reclaim missed (stale reservations, dead-pid grants,
            # ledger drift) — includes its own reap
            service.sweep_object_plane()
            service.push_rx.sweep()
            _tiers.publish_tier_bytes(_tiers.TIER_HOST,
                                      service.objects.used_bytes())
            _tiers.publish_tier_bytes(_tiers.TIER_SPILLED,
                                      service.objects.spilled_bytes())
            _publish_object_plane_metrics(service)
        except Exception:
            pass
        span_batch = []
        if time.monotonic() - last_trace_push >= _TRACE_PUSH_S:
            span_batch = service.task_events.events_after(trace_cursor)
            span_batch = span_batch[:_TRACE_BATCH_MAX]
        if span_batch and _fp.ENABLED:
            try:
                # drop/error arm = this flush is lost in transit; the
                # un-advanced cursor re-sends the batch next beat
                if _fp.fire("trace.flush",
                            n=len(span_batch)) is _fp.DROP:
                    span_batch = []
            except Exception:
                span_batch = []
        snapshot = None
        if time.monotonic() - last_metrics_push >= _METRICS_PUSH_S:
            try:
                from ray_tpu.util.metrics import export_snapshot
                snapshot = export_snapshot()
            except Exception:
                snapshot = None
        profile = _gate_profile_flush(last_profile_push)
        try:
            out = head.heartbeat(args.node_id, resources,
                                 wall_ts=time.time(),
                                 events=span_batch, metrics=snapshot,
                                 profile=profile,
                                 epoch=service.epoch)
            # advance the cursor ONLY on an acknowledged beat: an
            # "unknown" reply (restarted head, pre-re-register) returns
            # BEFORE ingesting the events — advancing would lose the
            # batch for good instead of re-sending after re-register
            if out.get("ok"):
                if span_batch:
                    trace_cursor = span_batch[-1]["seq"]
                    last_trace_push = time.monotonic()
                if snapshot is not None:
                    last_metrics_push = time.monotonic()
                if profile is not None:
                    last_profile_push = time.monotonic()
        except rpc.RpcError:
            head.close()
            new_head = reconnect()
            if new_head is None:
                os._exit(0)  # head stayed down: session over
            head = new_head
            continue
        if out.get("dead"):
            os._exit(0)
        if out.get("unknown"):
            # Restarted head with empty membership: re-register.
            try:
                out2 = head.register_node(args.node_id, resources,
                                          labels, server.addr)
            except rpc.RpcError:
                continue
            if out2.get("dead"):
                os._exit(0)     # fenced out: never rejoin as a zombie
            service.epoch = int(out2.get("epoch") or service.epoch)


if __name__ == "__main__":
    main()
