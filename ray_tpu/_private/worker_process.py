"""Process workers: the default execution path for tasks and actors.

Reference capability (NOT a port): the raylet worker pool + core-worker
execution plane — workers are real OS processes
(``src/ray/raylet/worker_pool.h`` StartWorkerProcess/PopWorker/prestart),
every task payload crosses a serialization boundary
(``python/ray/_private/serialization.py``), functions are shipped through
a function table (``python/ray/_private/function_manager.py:196,265``),
and workers reach back into the cluster for nested operations
(``CoreWorkerService`` RPCs, ``protobuf/core_worker.proto:457-577``).

TPU-first placement rule: work that touches the accelerator (declares TPU
resources, or consumes device-tier ``jax.Array`` arguments) runs in the
mesh-owning process — one process owns the chip/mesh and XLA releases the
GIL, so in-process threads are the right execution vehicle for SPMD work.
Everything else (the control/data plane) runs in spawned worker processes
pinned to the host CPU platform.

Architecture (single host; the pipe is the wire):

  host Runtime ── WorkerClient ──(mp.Pipe, cloudpickle frames)── worker
    - ProcessRouter: eligibility + routing + pool mgmt + crash handling
    - WorkerClient: one live worker process; demux reader thread routes
      task results/yields and services worker-initiated "core" ops
      (get/put/submit/wait/actor calls) against the host Runtime
    - worker process: reader loop + per-task threads; a
      WorkerProxyRuntime is installed as the global runtime so the full
      public API (ray_tpu.get/put/remote/actors/generators) works inside
      tasks transparently.

Process actors: the actor instance lives in a dedicated worker process;
host-side the existing ActorExecutor machinery (ordering, concurrency
groups, restarts) drives a proxy instance whose method stubs RPC into the
process. A dead worker process surfaces as actor death → the normal
restart path replays the creation spec.
"""

from __future__ import annotations

import hashlib
import inspect
import itertools
import os
import queue
import sys
import threading
import time
import traceback
import weakref
from typing import Any, Dict, List, Optional, Tuple

import cloudpickle

from ray_tpu._private.ids import ActorID, TaskID
from ray_tpu._private.task_spec import TaskKind, TaskSpec


class WorkerCrashed(Exception):
    """The worker process died while something was running on it."""


# object-plane ops served by the worker's HOST daemon itself (never
# forwarded to the owner): zero-copy meta resolution + direct-put
# reserve/seal (docs/object_plane.md)
_SHM_LOCAL_OPS = frozenset({"shm_get_meta", "shm_release",
                            "shm_put_reserve", "shm_put_seal",
                            "shm_put_abort"})


# ---------------------------------------------------------------------------
# function table (code shipping)
# ---------------------------------------------------------------------------

_FN_TABLE: "Dict[str, bytes]" = {}
_FN_REFS: Dict[str, int] = {}
_FN_TABLE_LOCK = threading.Lock()
_FN_MEMO: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
# Blobs from unweakrefable callables can't be finalizer-evicted; cap how
# many zero-ref entries may accumulate before oldest-first eviction.
_FN_TABLE_SOFT_CAP = 2048


def _release_fn_blob(fid: str) -> None:
    """weakref.finalize callback: the last live callable for this blob was
    collected — nothing can resubmit it, so the table entry is dead weight
    (retries hold the spec's live func and re-export on submission)."""
    with _FN_TABLE_LOCK:
        n = _FN_REFS.get(fid, 0) - 1
        if n <= 0:
            _FN_REFS.pop(fid, None)
            _FN_TABLE.pop(fid, None)
        else:
            _FN_REFS[fid] = n


def export_function(fn) -> Tuple[str, bytes]:
    """Serialize ``fn`` once and register it in the function table;
    returns (function_id, blob). Workers fetch the blob by id on first
    use and cache it (reference: function_manager.py export/fetch)."""
    try:
        cached = _FN_MEMO.get(fn)
    except TypeError:
        cached = None
    if cached is not None:
        return cached
    blob = cloudpickle.dumps(fn)
    fid = hashlib.sha1(blob).hexdigest()
    entry = (fid, blob)
    with _FN_TABLE_LOCK:
        _FN_TABLE[fid] = blob
        if len(_FN_TABLE) > _FN_TABLE_SOFT_CAP:
            # evict oldest zero-ref blobs (insertion-ordered dict)
            for old_fid in [f for f in _FN_TABLE
                            if _FN_REFS.get(f, 0) <= 0]:
                if len(_FN_TABLE) <= _FN_TABLE_SOFT_CAP:
                    break
                if old_fid != fid:
                    _FN_TABLE.pop(old_fid, None)
    try:
        _FN_MEMO[fn] = entry
        with _FN_TABLE_LOCK:
            _FN_REFS[fid] = _FN_REFS.get(fid, 0) + 1
        weakref.finalize(fn, _release_fn_blob, fid)
    except TypeError:
        pass  # unweakrefable callables just re-serialize
    return entry


def _local_fn_blob(msg) -> Optional[bytes]:
    """The blob for a worker fetch_function core op, if this process's
    own table has it (payload is the pickled kw dict)."""
    try:
        kw = cloudpickle.loads(msg["payload"])
        with _FN_TABLE_LOCK:
            return _FN_TABLE.get(kw.get("fid"))
    except Exception:
        return None


def register_function_blob(blob: bytes) -> str:
    """Register an ALREADY-pickled callable (e.g. fetched from the head
    KV by the cross-language tier) so pool workers can fetch it by id."""
    fid = hashlib.sha1(blob).hexdigest()
    with _FN_TABLE_LOCK:
        _FN_TABLE[fid] = blob
        _FN_REFS[fid] = _FN_REFS.get(fid, 0) + 1
    return fid


def fetch_function_blob(fid: str) -> bytes:
    with _FN_TABLE_LOCK:
        blob = _FN_TABLE.get(fid)
    if blob is None:
        raise KeyError(f"function {fid} not in function table")
    return blob


# ---------------------------------------------------------------------------
# worker-process side
# ---------------------------------------------------------------------------

_current_rid = threading.local()


def _borrower_key() -> Optional[str]:
    """Owner-side borrow key for refs created on this caller's behalf:
    actor context → held for the actor's lifetime; task context → held
    until the owner finishes that task (reference: per-task borrows,
    ``reference_count.h:73``)."""
    try:
        from ray_tpu._private import runtime_context
        ctx = runtime_context._ctx.get()
    except Exception:
        return None
    if ctx is None:
        return None
    if ctx.actor_id is not None:
        return "a:" + ctx.actor_id.hex()
    if ctx.task_id is not None:
        return "t:" + ctx.task_id.hex()
    return None


def _dump_exc(e: BaseException) -> bytes:
    tb = traceback.format_exc()
    try:
        return cloudpickle.dumps((e, tb))
    except Exception:
        return cloudpickle.dumps(
            (RuntimeError(f"{type(e).__name__}: {e}"), tb))


def _safe_dumps(value: Any) -> bytes:
    from ray_tpu._private.device_objects import wire_dumps
    return wire_dumps(value)   # sharding-preserving jax wire format


class _GeneratorStateProxy:
    """Worker-side view of a host GeneratorState (ObjectRefGenerator)."""

    def __init__(self, state: "_WorkerState", task_id: TaskID):
        self._state = state
        self._task_id = task_id

    def next_ref(self, index: int, timeout: Optional[float] = None):
        out = self._state.call_host("gen_next", task_id=self._task_id,
                                    index=index, timeout=timeout)
        if out is None:
            raise StopIteration
        return out

    @property
    def finished(self) -> bool:
        return self._state.call_host("gen_finished", task_id=self._task_id)


class _GcsProxy:
    def __init__(self, state: "_WorkerState"):
        self._state = state

    def get_actor_info(self, actor_id):
        return self._state.call_host("gcs_get_actor_info",
                                     actor_id=actor_id)

    def get_named_actor(self, name, namespace):
        return self._state.call_host("gcs_get_named_actor", name=name,
                                     namespace=namespace)

    # internal KV (debugger session registry, collectives, ...)
    def kv_put(self, key, value, overwrite=True, namespace=b""):
        return self._state.call_host("gcs_kv_put", key=key, value=value,
                                     overwrite=overwrite,
                                     namespace=namespace)

    def kv_get(self, key, namespace=b""):
        return self._state.call_host("gcs_kv_get", key=key,
                                     namespace=namespace)

    def kv_del(self, key, namespace=b""):
        return self._state.call_host("gcs_kv_del", key=key,
                                     namespace=namespace)

    def kv_keys(self, prefix=b"", namespace=b""):
        return self._state.call_host("gcs_kv_keys", prefix=prefix,
                                     namespace=namespace)


class _PgManagerProxy:
    """Worker-side pg_manager facade: returns a picklable clone of the
    host's PlacementGroup (handle semantics — id/bundles/state)."""

    def __init__(self, state: "_WorkerState"):
        self._state = state

    def get(self, pg_id):
        return self._state.call_host("pg_get", pg_id=pg_id)

    def create(self, bundles, strategy, name=""):
        return self._state.call_host("pg_create", bundles=bundles,
                                     strategy=strategy, name=name)

    def remove(self, pg):
        return self._state.call_host("pg_remove", pg_id=pg.id)

    def table(self):
        return self._state.call_host("pg_table")

    def ready_ref(self, pg_id):
        return self._state.call_host("pg_ready_ref", pg_id=pg_id)


class _NoopRefcounter:
    """Worker-held refs are kept alive host-side per task/actor (the host
    pins every ref a worker creates until the task — or the actor — ends),
    so worker-local counting is intentionally a no-op."""

    def add_local_ref(self, oid):
        pass

    def remove_local_ref(self, oid):
        pass


class WorkerProxyRuntime:
    """Installed as the global runtime inside a worker process: forwards
    the core API to the host over the pipe. Duck-types the Runtime surface
    that ObjectRef / RemoteFunction / ActorHandle / the module-level API
    touch."""

    def __init__(self, state: "_WorkerState"):
        self._state = state
        self.refcounter = _NoopRefcounter()
        self.gcs = _GcsProxy(state)
        self.pg_manager = _PgManagerProxy(state)
        self._actor_lock = threading.RLock()
        self._actor_executors: Dict[ActorID, Any] = {}

    # Pooled workers serve different runtimes over their lifetime, so
    # job/namespace are fetched from the currently-bound host.
    @property
    def namespace(self):
        return self._state.call_host("host_info")["namespace"]

    @property
    def job_id(self):
        return self._state.call_host("host_info")["job_id"]

    # -- objects ---------------------------------------------------------
    def get(self, refs, timeout: Optional[float] = None):
        refs = list(refs)
        out = self._shm_get(refs, timeout)
        if out is not None:
            return out
        return self._state.call_host("get", refs=refs,
                                     timeout=timeout)

    def _shm_get(self, refs, timeout: Optional[float]):
        """Zero-copy resolve through the attached node arena: (offset,
        nbytes) metadata from the daemon, ``np.frombuffer`` on the
        mapping — no payload crosses the pipe and raw-tier arrays skip
        serialization entirely. Per-object slot refs (taken daemon-side
        on our behalf) keep every view safe from LRU eviction until
        released. Returns None to take the classic owner path (arena
        absent/failed, or the host predates the protocol)."""
        try:
            from ray_tpu.objectplane import arena as _oparena
            ar = _oparena.get_arena()
            if ar is None or not refs or ar.store() is None:
                return None
            metas = self._state.call_host(
                "shm_get_meta", oids=[r.id.binary() for r in refs])
        except Exception:
            return None
        if not isinstance(metas, list) or len(metas) != len(refs):
            return None
        values = [None] * len(refs)
        missing: List[int] = []
        pending = {i: m for i, m in enumerate(metas)
                   if isinstance(m, dict)}
        try:
            for i, meta in enumerate(metas):
                if not isinstance(meta, dict):
                    missing.append(i)
                    continue
                # ownership handoff BEFORE resolving: from here this
                # slot's single release belongs to the code below (view
                # finalizer, or the loads finally) — the except sweep
                # must never release it a second time, or a concurrent
                # reader's ref would be consumed and eviction could
                # unmap bytes it still views
                del pending[i]
                raw = meta.get("raw")
                if raw:
                    values[i] = ar.view(meta["off"], meta["size"],
                                        meta["slot"], dtype=raw[0],
                                        shape=raw[1])
                else:
                    store = ar.store()
                    view = store.view_range(meta["off"], meta["size"])
                    try:
                        values[i] = cloudpickle.loads(memoryview(view))
                    finally:
                        ar.release_slot(meta["slot"])
        except Exception:
            # mid-resolve failure: drop every granted-but-unconsumed
            # slot ref and fall back wholesale (slots already handed
            # off released above or via their view finalizers)
            for meta in pending.values():
                try:
                    ar.release_slot(meta["slot"])
                except Exception:
                    pass
            return None
        if missing:
            fetched = self._state.call_host(
                "get", refs=[refs[i] for i in missing], timeout=timeout)
            for i, v in zip(missing, fetched):
                values[i] = v
        return values

    def put(self, value, _owner_pin: bool = False):
        if not _owner_pin:
            ref = self._shm_put(value)
            if ref is not None:
                return ref
        return self._state.call_host("put", value=value)

    def _shm_put(self, value):
        """Direct put: reserve arena space through the daemon, write
        the payload IN PLACE through our own mapping, and send only the
        seal message — the payload never rides the pipe or an RPC
        frame. Returns the owner-registered ObjectRef, or None to take
        the classic path (small value, no arena, any failure)."""
        try:
            from ray_tpu.objectplane import arena as _oparena
            ar = _oparena.get_arena()
            if ar is None or ar.store() is None:
                return None
            from ray_tpu._private.object_store import _is_device_value
            if _is_device_value(value):
                return None     # device tier stays owner-managed
            from ray_tpu._private.config import cfg
            min_direct = int(cfg().direct_put_min_bytes)
            from ray_tpu.objectplane.tiers import raw_put_eligible
            raw = raw_put_eligible(value)
            if raw is not None:
                payload = memoryview(value).cast("B")
                nbytes = payload.nbytes
            else:
                from ray_tpu._private.worker import _find_nested_refs
                if _find_nested_refs(value):
                    # nested ObjectRefs need the owner's borrowed-ref
                    # registration (classic put path) — a sealed blob
                    # would hold refs the refcounter can't see
                    return None
                blob = _safe_dumps(value)
                if len(blob) < min_direct:
                    return None
                payload = blob
                nbytes = len(blob)
            node_hex = self._node_hex()
            if node_hex is None:
                return None     # no task context: owner path
            from ray_tpu._private.ids import ObjectID
            oid = ObjectID.from_random()
            key = b"wput:" + oid.binary()
            out = self._state.call_host("shm_put_reserve", key=key,
                                        size=nbytes)
            if not isinstance(out, dict) or "off" not in out:
                return None     # arena full: classic path spills/inlines
        except Exception:
            return None
        try:
            ar.write(out["off"], payload)
        except Exception:
            # the reserve succeeded but the write didn't (mapping
            # detached mid-flight): drop the reservation or its
            # creator-ref'd bytes would leak for the arena's lifetime
            self._shm_put_abort(key)
            return None
        if not self._seal_with_retry(key, oid, raw, nbytes):
            self._shm_put_abort(key)
            return None
        try:
            return self._state.call_host(
                "put_stored", oid=oid.binary(), key=key, nbytes=nbytes,
                raw=raw, node=node_hex)
        except Exception:
            self._shm_put_abort(key)
            return None

    def _seal_with_retry(self, key: bytes, oid, raw,
                         nbytes: int) -> bool:
        from ray_tpu._private import failpoints as _fp
        for _ in range(3):
            if _fp.ENABLED:
                try:
                    # drop arm = the seal message is lost in transit;
                    # resend — sealing is idempotent at the daemon
                    if _fp.fire("shm.seal", nbytes=nbytes) is _fp.DROP:
                        continue
                except Exception:
                    continue
            try:
                out = self._state.call_host(
                    "shm_put_seal", key=key, ref=oid.binary(), raw=raw)
            except Exception:
                return False
            return bool(isinstance(out, dict) and out.get("ok"))
        return False

    def _shm_put_abort(self, key: bytes) -> None:
        try:
            self._state.call_host("shm_put_abort", key=key)
        except Exception:
            pass

    @staticmethod
    def _node_hex() -> Optional[str]:
        try:
            from ray_tpu._private import runtime_context
            ctx = runtime_context._ctx.get()
            nid = getattr(ctx, "node_id", None) if ctx else None
            return nid.hex() if nid is not None else None
        except Exception:
            return None

    def wait(self, refs, num_returns: int = 1,
             timeout: Optional[float] = None, fetch_local: bool = True):
        return self._state.call_host("wait", refs=list(refs),
                                     num_returns=num_returns,
                                     timeout=timeout,
                                     fetch_local=fetch_local)

    # -- tasks / actors --------------------------------------------------
    def submit_task(self, spec: TaskSpec, record_lineage: bool = True):
        return self._state.call_host("submit_task", spec=spec)

    def create_actor(self, spec: TaskSpec, get_if_exists: bool = False):
        return self._state.call_host("create_actor", spec=spec,
                                     get_if_exists=get_if_exists)

    def kill_actor(self, actor_id, no_restart: bool = True,
                   cause: str = "ray_tpu.kill() called"):
        return self._state.call_host("kill_actor", actor_id=actor_id,
                                     no_restart=no_restart, cause=cause)

    def cancel(self, ref, force: bool = False, recursive: bool = True):
        return self._state.call_host("cancel", ref=ref, force=force,
                                     recursive=recursive)

    def generator_state(self, task_id: TaskID) -> _GeneratorStateProxy:
        return _GeneratorStateProxy(self._state, task_id)

    # -- cluster introspection -------------------------------------------
    def cluster_resources(self):
        return self._state.call_host("cluster_resources")

    def available_resources(self):
        return self._state.call_host("available_resources")


class _WorkerState:
    def __init__(self, conn, boot: Dict[str, Any]):
        self.conn = conn
        self.boot = boot
        self.namespace = boot.get("namespace", "default")
        self.job_id = boot.get("job_id")
        arena = boot.get("arena")
        if arena:
            # the daemon's worker hello hands us its arena (name,
            # capacity): attach lazily on first object-plane use
            try:
                from ray_tpu.objectplane import arena as _oparena
                _oparena.configure(arena[0], arena[1])
            except Exception:
                pass    # plane unavailable: classic RPC path
        self._send_lock = threading.Lock()
        self._ids = itertools.count()
        self._pending: Dict[str, list] = {}  #: guarded by self._pending_lock
        self._pending_lock = threading.Lock()
        self._task_threads: Dict[str, threading.Thread] = {}
        self.actor_instance: Any = None
        # serializes actor-method execution between the classic mp
        # channel (streaming calls) and the targeted fast lane; only
        # engaged once the lane binds (_lane_bound) so non-lane actors
        # keep their configured concurrency semantics
        self.actor_lock = threading.RLock()
        self._lane_bound = False
        self._fn_cache: Dict[str, Any] = {}
        self._gen_sems: Dict[str, threading.Semaphore] = {}
        self.proxy = WorkerProxyRuntime(self)
        # compiled-DAG channel loop (dag_start/dag_stop ops)
        self._dag_stop: Any = None
        self._dag_thread: Any = None
        self._dag_channels: Dict[str, Any] = {}
        self._dag_gen: Any = None

    def send(self, msg: Dict[str, Any]) -> None:
        blob = cloudpickle.dumps(msg)
        with self._send_lock:
            self.conn.send_bytes(blob)

    def call_host(self, call: str, **kw) -> Any:
        rid = f"w{next(self._ids)}"
        ev = threading.Event()
        slot = [ev, True, None]
        with self._pending_lock:
            self._pending[rid] = slot
        from ray_tpu._private.device_objects import wire_dumps
        self.send({"op": "core", "id": rid, "call": call,
                   "task": getattr(_current_rid, "rid", None),
                   # globally-unique borrower key (reference: per-task
                   # borrow tracking, reference_count.h:73) — the worker
                   # rid above is only unique per process, so the
                   # owner's cross-daemon holder cannot key on it
                   "task_key": _borrower_key(),
                   "payload": wire_dumps(kw)})   # device args preserved
        ev.wait()
        if slot[1]:
            return slot[2]
        raise slot[2]


    # -- main loop -------------------------------------------------------
    def serve_forever(self) -> None:
        while True:
            try:
                msg = cloudpickle.loads(self.conn.recv_bytes())
            except (EOFError, OSError, ConnectionResetError):
                os._exit(0)
            op = msg.get("op")
            if op == "shutdown":
                os._exit(0)
            elif op == "reply":
                with self._pending_lock:
                    slot = self._pending.pop(msg["for"], None)
                if slot is not None:
                    slot[1] = msg["ok"]
                    slot[2] = cloudpickle.loads(msg["value"])
                    slot[0].set()
            elif op in ("execute_task", "create_actor", "call_method",
                        "reset_actor", "dag_start", "dag_stop"):
                t = threading.Thread(target=self._handle, args=(msg,),
                                     daemon=True,
                                     name=f"task-{msg['id']}")
                self._task_threads[msg["id"]] = t
                t.start()
            elif op == "gen_ack":
                sem = self._gen_sems.get(msg["target"])
                if sem is not None:
                    sem.release()
            elif op == "cancel":
                self._async_raise(msg["target"])
            elif op == "extend_sys_path":
                import sys as _sys
                for p in msg.get("paths", []):
                    if p not in _sys.path:
                        _sys.path.append(p)
            elif op == "profile_burst":
                # on-demand stack sampling; a thread so the burst never
                # blocks the op loop (results keep flowing while it runs)
                def _burst(msg=msg):
                    try:
                        from ray_tpu.util import profiling as _prof
                        rec = _prof.burst_record(
                            f"worker:{os.getpid()}",
                            duration_s=float(msg.get("duration") or 2.0))
                        self.send({"id": msg["id"], "op": "result",
                                   "ok": True,
                                   "blob": cloudpickle.dumps(rec)})
                    except BaseException as e:  # noqa: BLE001 — shipped
                        self.send({"id": msg["id"], "op": "result",
                                   "ok": False, "blob": _dump_exc(e)})
                threading.Thread(target=_burst, daemon=True,
                                 name="profile-burst").start()
            elif op == "join_fast_lane":
                # dedicate this worker to the native daemon core's task
                # lane (fast_lane.py); the mp channel stays open for
                # host ops (fetch_function, nested core ops, metrics).
                # With a tag, this is the TARGETED (actor) lane.
                try:
                    from ray_tpu._private.fast_lane import (
                        worker_fast_lane_start)
                    worker_fast_lane_start(tuple(msg["addr"]), self,
                                           tag=msg.get("tag"))
                    if msg.get("tag") is not None:
                        self._lane_bound = True
                    self.send({"id": msg["id"], "op": "result",
                               "ok": True,
                               "blob": cloudpickle.dumps(None)})
                except BaseException as e:  # noqa: BLE001 — shipped
                    self.send({"id": msg["id"], "op": "result",
                               "ok": False, "blob": _dump_exc(e)})

    def _async_raise(self, rid: str) -> None:
        """Best-effort KeyboardInterrupt into the thread running ``rid``
        (reference: non-force ray.cancel interrupts the worker)."""
        import ctypes
        t = self._task_threads.get(rid)
        if t is None or not t.is_alive():
            return
        ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(t.ident), ctypes.py_object(KeyboardInterrupt))

    def _resolve_runtime_env(self, renv):
        """pkg:// URIs -> node-local extracted dirs (fetched once from
        the owner through the host channel and cached)."""
        if not renv:
            return renv
        from ray_tpu._private import runtime_env_packaging as pkg

        def resolve(value):
            if not (isinstance(value, str)
                    and value.startswith(pkg.PKG_SCHEME)):
                return value
            local = pkg.cached_dir(value)
            if local is None:
                local = pkg.extract_blob(
                    value, self.call_host("fetch_runtime_pkg", uri=value))
            return local

        out = dict(renv)
        if out.get("working_dir"):
            out["working_dir"] = resolve(out["working_dir"])
        if out.get("py_modules"):
            out["py_modules"] = [resolve(m) for m in out["py_modules"]]
        return out

    # -- compiled-DAG channel loop ---------------------------------------
    # Reference capability: the accelerated-DAG per-actor execution loop
    # (`python/ray/dag/compiled_dag_node.py` _do_exec_tasks) — after one
    # dag_start RPC, every execute() flows ONLY through pre-allocated
    # shm channels; no task submission, no object store.
    def _dag_start(self, spec: Dict[str, Any]):
        from ray_tpu.dag.shm_channel import ShmChannel
        if self._dag_thread is not None:
            # superseded binding (an abandoned CompiledDAG that was
            # never torn down): stop the stale loop, serve the new one
            self._dag_teardown()
        channels = {name: ShmChannel(name=name)
                    for name in spec["channels"]}
        consts = spec["consts"]
        stages = spec["stages"]
        stop = threading.Event()

        def loop():
            import sys as _sys
            import traceback as _tb

            from ray_tpu.dag.shm_channel import ChannelClosed
            while not stop.is_set():
                try:
                    for st in stages:
                        self._dag_run_stage(st, channels, consts, stop)
                except ChannelClosed:
                    return
                except Exception:
                    # e.g. ChannelFull on an oversized stage output:
                    # the channel chain cannot carry this — at least
                    # leave a driver-visible diagnostic (worker logs
                    # forward to the driver) before the loop dies
                    print("[compiled-dag] worker loop died:\n"
                          + _tb.format_exc(), file=_sys.stderr,
                          flush=True)
                    return

        t = threading.Thread(target=loop, daemon=True, name="dag-loop")
        self._dag_stop = stop
        self._dag_thread = t
        self._dag_channels = channels
        self._dag_gen = spec.get("gen")
        t.start()
        return None

    def _dag_run_stage(self, st, channels, consts, stop) -> None:
        def fetch(src):
            kind, key = src
            if kind == "chan":
                # idle waiting has NO deadline: a compiled DAG parked
                # for hours must still answer the next execute(); the
                # stop event is the only exit
                return channels[key].read(stop=stop, timeout=None)
            return ("ok", consts[key])

        inputs = [fetch(s) for s in st["args"]]
        kw_in = {k: fetch(s) for k, s in st["kwargs"].items()}
        err = next((v for s, v in inputs if s != "ok"),
                   next((v for s, v in kw_in.values() if s != "ok"),
                        None))
        if err is not None:
            out = ("err", err)       # propagate upstream failure
        else:
            try:
                method = getattr(self.actor_instance, st["method"])
                out = ("ok", method(
                    *[v for _, v in inputs],
                    **{k: v for k, (_, v) in kw_in.items()}))
            except BaseException as e:  # noqa: BLE001 — via channel
                out = ("err", e)
        for name in st["out"]:
            channels[name].write(out[0], out[1], stop=stop,
                                 timeout=3600.0)

    def _dag_teardown(self):
        if self._dag_stop is not None:
            self._dag_stop.set()
        if self._dag_thread is not None:
            self._dag_thread.join(timeout=5)
        for ch in self._dag_channels.values():
            ch.close()
        self._dag_stop = None
        self._dag_thread = None
        self._dag_channels = {}
        self._dag_gen = None
        return None

    def _fn(self, msg: Dict[str, Any]):
        if "fn_blob" in msg:
            return cloudpickle.loads(msg["fn_blob"])
        fid = msg["fn_id"]
        fn = self._fn_cache.get(fid)
        if fn is None:
            fn = cloudpickle.loads(self.call_host("fetch_function",
                                                  fid=fid))
            self._fn_cache[fid] = fn
        return fn

    def _handle(self, msg: Dict[str, Any]) -> None:
        import contextlib

        from ray_tpu._private import runtime_context
        from ray_tpu.runtime_env import apply_runtime_env

        rid = msg["id"]
        _current_rid.rid = rid
        ctx = msg.get("ctx") or {}
        # exec-phase span: the user function body measured IN the worker
        # (the only process that can see it). It PIGGYBACKS on the result
        # frame — zero extra pipe writes/pickles on the hot path — and
        # the host ingests it into its span sink (daemon -> head via
        # heartbeat; driver -> its own task-event buffer).
        trace = (ctx.get("trace")
                 if msg["op"] in ("execute_task", "call_method") else None)
        t_exec0 = time.perf_counter() if trace else 0.0

        def exec_span():
            if not trace:
                return None
            from ray_tpu._private.events import wall_at
            nid = ctx.get("node_id")
            tid = ctx.get("task_id")
            end = time.perf_counter()
            return {
                "task_id": tid.hex() if tid is not None else "",
                "name": ctx.get("task_name", ""), "event": "SPAN",
                "phase": "exec",
                "node_id": nid.hex() if nid is not None else "",
                "proc": f"worker:{os.getpid()}",
                "trace_id": trace.get("id", ""),
                "wall_ts": wall_at(end), "start_wall": wall_at(t_exec0),
                "dur_s": end - t_exec0}

        try:
            token = runtime_context._set_context(**ctx)
            try:
                with apply_runtime_env(
                        self._resolve_runtime_env(msg.get("runtime_env"))), \
                        _post_mortem_on_error(), \
                        contextlib.ExitStack() as _alock:
                    if msg["op"] == "create_actor":
                        cls = self._fn(msg)
                        args, kwargs = cloudpickle.loads(msg["args_blob"])
                        self.actor_instance = cls(*args, **kwargs)
                        result = None
                    elif msg["op"] == "call_method":
                        method = getattr(self.actor_instance, msg["method"])
                        args, kwargs = cloudpickle.loads(msg["args_blob"])
                        if self._lane_bound:
                            # held through the STREAMING drain below
                            # too (the ExitStack closes after it): a
                            # lane call must not interleave with a
                            # classic streaming method's body on a
                            # serialized actor
                            _alock.enter_context(self.actor_lock)
                        result = method(*args, **kwargs)
                    elif msg["op"] == "dag_start":
                        result = self._dag_start(
                            cloudpickle.loads(msg["args_blob"]))
                    elif msg["op"] == "dag_stop":
                        gen = (cloudpickle.loads(msg["args_blob"])
                               if msg.get("args_blob") else None)
                        # generation-scoped: a STALE CompiledDAG being
                        # GC'd must not kill a newer binding's loop
                        if gen is None or gen == getattr(
                                self, "_dag_gen", None):
                            result = self._dag_teardown()
                        else:
                            result = None
                    elif msg["op"] == "reset_actor":
                        self._dag_teardown()   # recycle = no stale loop
                        # Clean actor teardown: drop the instance so the
                        # process can be recycled into the idle pool
                        # (spawns are expensive; prestart can't keep up
                        # on small hosts). If ANYTHING still references
                        # the instance after gc (a background thread the
                        # actor started, a module global, ...) the worker
                        # is dirty and must be killed, not recycled —
                        # report it so the host takes the kill path.
                        inst, self.actor_instance = self.actor_instance, None
                        wr = weakref.ref(inst) if inst is not None else None
                        del inst
                        import gc
                        gc.collect()
                        if wr is not None and wr() is not None:
                            raise RuntimeError("actor instance still "
                                               "referenced; worker dirty")
                        result = None
                    else:
                        fn = self._fn(msg)
                        args, kwargs = cloudpickle.loads(msg["args_blob"])
                        result = fn(*args, **kwargs)
                    if inspect.isgenerator(result):
                        # Producer-side flow control (reference:
                        # GeneratorBackpressureWaiter): at most
                        # `backpressure` unacked items cross the pipe;
                        # the host acks as the consumer pulls them.
                        bp = msg.get("backpressure") or -1
                        sem = None
                        if bp > 0:
                            sem = threading.Semaphore(bp)
                            self._gen_sems[rid] = sem
                        try:
                            self.send({"id": rid, "op": "gen_start"})
                            for item in result:
                                if sem is not None:
                                    sem.acquire()
                                self.send({"id": rid, "op": "yield",
                                           "blob": _safe_dumps(item)})
                            self._flush_metrics()   # before release
                            self.send({"id": rid, "op": "result",
                                       "ok": True,
                                       "span": exec_span(),  # drain incl.
                                       "blob": _safe_dumps(None)})
                        finally:
                            self._gen_sems.pop(rid, None)
                        return
            finally:
                runtime_context._reset_context(token)
            # flush BEFORE the result send: once the host sees the
            # result it may release (or kill) this worker, and a flush
            # in flight after that is lost
            self._flush_metrics()
            self.send({"id": rid, "op": "result", "ok": True,
                       "span": exec_span(),
                       "profile": _result_profile(),
                       "blob": _safe_dumps(result)})
        except BaseException as e:  # noqa: BLE001 — shipped to host
            try:
                self._flush_metrics()
                self.send({"id": rid, "op": "result", "ok": False,
                           "span": exec_span(),
                           "profile": _result_profile(),
                           "blob": _dump_exc(e)})
            except (BrokenPipeError, OSError):
                os._exit(1)
        finally:
            self._task_threads.pop(rid, None)

    def _flush_metrics(self) -> None:
        """User metrics created in THIS worker flow to the driver's
        Prometheus endpoint (reference: worker -> agent -> exporter)."""
        try:
            from ray_tpu.util import metrics as _metrics
            deltas = _metrics.drain_deltas()
            if deltas:
                self.call_host("metrics_push", entries=deltas)
        except Exception:
            pass


# Worker profile piggyback (the span discipline): the CUMULATIVE
# continuous-sampler record rides at most one result frame per second;
# the host ingests it into profiling's remote store and the daemon's
# heartbeat ships it to the head. None (the common case) costs one
# cloudpickle'd NoneType on the frame.
_PROFILE_RESULT_S = 1.0
_last_profile_sent = [0.0]


def _result_profile():
    try:
        from ray_tpu.util import profiling as _prof
        rec = _prof.process_profile()
        if rec is None:
            return None
        now = time.monotonic()
        if now - _last_profile_sent[0] < _PROFILE_RESULT_S:
            return None
        _last_profile_sent[0] = now
        return rec
    except Exception:
        return None


def _post_mortem_on_error():
    """Distributed debugger hook — single definition lives in
    ray_tpu.util.rpdb (shared with the in-process path); guarded so a
    debugger-side import failure never masks the user's exception."""
    import contextlib
    try:
        from ray_tpu.util.rpdb import post_mortem_on_error
        return post_mortem_on_error()
    except Exception:
        return contextlib.nullcontext()


def _child_main(conn) -> None:
    """Worker bootstrap, forked from the forkserver template process (NOT
    multiprocessing spawn — that re-imports the parent's __main__, which
    breaks under REPLs/stdin drivers and pulls arbitrary driver-side
    module state into every worker; and NOT a fresh ``python -c`` — that
    pays ~0.3s of interpreter+import startup per worker where a fork is
    ~10ms). The first frame on the pipe is the boot config."""
    import signal

    # Terminal Ctrl+C goes to the whole foreground process group; workers
    # must not die with it (the driver decides shutdown; force-cancel uses
    # SIGTERM). The old subprocess path got this from start_new_session.
    signal.signal(signal.SIGINT, signal.SIG_IGN)
    boot = cloudpickle.loads(conn.recv_bytes())
    os.environ.update(boot.get("env", {}))
    for _p in boot.get("extra_sys_path", []):
        if _p not in sys.path:
            sys.path.append(_p)
    if boot.get("log_dir"):
        # Per-worker log files + tail-to-driver (reference:
        # _private/log_monitor.py; VERDICT r2 #9).
        from ray_tpu._private.log_monitor import redirect_process_output
        try:
            redirect_process_output(boot["log_dir"])
        except OSError:
            pass
    if boot.get("force_cpu_platform"):
        # Env-level pinning only (no jax import): jax has NOT been
        # imported yet in this fresh process — worker startup must stay
        # cheap (importing jax costs ~1.7s) — so the env vars are
        # authoritative when user code first imports it.
        from ray_tpu._private.platform import pin_cpu_env
        pin_cpu_env(boot.get("cpu_devices"))
    from ray_tpu._private import worker as worker_mod

    # network-chaos role tag: any control-plane socket this worker opens
    # (e.g. fast-lane result delivery) matches worker>* link policies
    from ray_tpu._private import netchaos as _nc
    _nc.set_local_role("worker")

    # continuous profiler (profiling_hz via the env the host shipped in
    # boot["env"] / inherited from the forkserver template; default off)
    try:
        from ray_tpu.util import profiling as _prof
        _prof.maybe_start_from_config(f"worker:{os.getpid()}")
    except Exception:
        pass
    state = _WorkerState(conn, boot)
    worker_mod._global_runtime = state.proxy  # type: ignore[assignment]
    state.serve_forever()


# ---------------------------------------------------------------------------
# host side
# ---------------------------------------------------------------------------

class _Pending:
    __slots__ = ("q",)

    def __init__(self):
        self.q: "queue.Queue" = queue.Queue()


_DEAD = object()  # sentinel pushed into pending queues on worker death


_MP_CTX = None
_MP_CTX_LOCK = threading.Lock()


def _mp_context():
    """Forkserver context every worker forks from. The forkserver is the
    template process: it preloads this module (and the worker runtime) once,
    with the repo on ``PYTHONPATH``. Workers never own the accelerator
    (router eligibility keeps TPU work in the mesh-owning host process):
    the template never imports jax, and each worker pins
    ``JAX_PLATFORMS=cpu`` at boot, before user code can."""
    global _MP_CTX
    with _MP_CTX_LOCK:
        if _MP_CTX is not None:
            return _MP_CTX
        import multiprocessing as mp
        from multiprocessing import forkserver as _fs

        ctx = mp.get_context("forkserver")
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        # NOTE deliberately narrow: JAX_PLATFORMS is NOT touched here —
        # mutating it in the driver's global env, even briefly, races a
        # driver thread importing jax and could pin the HOST backend to
        # CPU. The template never imports jax (the preloads don't pull
        # it), and each worker pins itself at boot via the boot frame.
        saved_path = os.environ.get("PYTHONPATH")
        os.environ["PYTHONPATH"] = repo_root + (
            os.pathsep + saved_path if saved_path else "")
        try:
            # PRIVATE ForkServer instance: multiprocessing's module-level
            # singleton may already be running (started by user code) with
            # the wrong env and no preloads — and our template must never
            # serve user forks either. _start_sans_main swaps this
            # instance in around each of our Process.start() calls.
            global _OUR_FORKSERVER
            _OUR_FORKSERVER = _fs.ForkServer()
            # pyarrow MUST be imported on a template/main thread: this
            # image's libarrow ties allocator state to the importing
            # thread's TLS — first-import inside a short-lived task
            # thread, then use from another thread after it exits,
            # segfaults (verified: plain-process repro, no fork needed).
            # Preloading in the template also makes every forked worker
            # inherit warm imports for free.
            _OUR_FORKSERVER.set_forkserver_preload(
                ["ray_tpu._private.worker_process",
                 "ray_tpu._private.worker",
                 "pyarrow"])
            _OUR_FORKSERVER.ensure_running()
        finally:
            if saved_path is None:
                os.environ.pop("PYTHONPATH", None)
            else:
                os.environ["PYTHONPATH"] = saved_path
        import atexit

        atexit.register(_shutdown_worker_plane)
        _MP_CTX = ctx
        return ctx


def _shutdown_worker_plane() -> None:
    """Interpreter-exit hook: kill idle pooled workers and release the
    private forkserver. Without this, worker/template processes keep the
    multiprocessing resource-tracker pipe open and the tracker's __del__
    during final GC blocks interpreter exit (observed with grpc loaded,
    whose import makes shutdown GC collect the tracker)."""
    _POOL_CLOSED.set()
    deadline = time.monotonic() + 3.0
    while _PRESTARTING[0] > 0 and time.monotonic() < deadline:
        time.sleep(0.02)   # let racing spawns land so drain catches them
    try:
        drain_pool()
    except Exception:
        pass
    fs = _OUR_FORKSERVER
    if fs is not None:
        try:
            fd = getattr(fs, "_forkserver_alive_fd", None)
            if fd is not None:
                os.close(fd)
                fs._forkserver_alive_fd = None
        except OSError:
            pass


_START_LOCK = threading.Lock()
_OUR_FORKSERVER = None


def _start_sans_main(p) -> None:
    """Start a worker Process on OUR forkserver, WITHOUT multiprocessing's
    main-module fixup.

    spawn.get_preparation_data() tells the child to re-run the driver's
    ``__main__`` (runpy.run_path) — a worker must never do that: it would
    re-execute arbitrary user scripts in every worker (the reference
    default_worker is likewise a clean entrypoint, never the user script;
    driver-side functions reach workers through the function table
    instead). Both monkeypatches are scoped: the lock serializes our
    starts, the spawn patch checks the starting thread's identity (a
    concurrent user Process.start() on another thread sees stock
    behavior), and the forkserver global is restored before release."""
    from multiprocessing import forkserver as _fs
    from multiprocessing import spawn as _spawn

    with _START_LOCK:
        orig = _spawn.get_preparation_data
        me = threading.get_ident()

        def sans_main(name):
            d = orig(name)
            if threading.get_ident() == me:
                d.pop("init_main_from_path", None)
                d.pop("init_main_from_name", None)
            return d

        # popen_forkserver calls the module-level alias (a bound method
        # of the import-time singleton), so that alias is what we swap.
        saved_connect = _fs.connect_to_new_process
        _spawn.get_preparation_data = sans_main
        if _OUR_FORKSERVER is not None:
            _fs.connect_to_new_process = _OUR_FORKSERVER.connect_to_new_process
        try:
            p.start()
        finally:
            _fs.connect_to_new_process = saved_connect
            _spawn.get_preparation_data = orig


class _ProcHandle:
    """subprocess.Popen-shaped facade over a multiprocessing.Process."""

    __slots__ = ("p",)

    def __init__(self, p):
        self.p = p

    @property
    def pid(self):
        return self.p.pid

    def poll(self):
        return None if self.p.is_alive() else self.p.exitcode

    def wait(self, timeout=None):
        self.p.join(timeout)
        if self.p.is_alive():
            import subprocess
            raise subprocess.TimeoutExpired("worker", timeout)
        return self.p.exitcode

    def terminate(self):
        try:
            self.p.terminate()
        except Exception:
            pass

    def kill(self):
        try:
            self.p.kill()
        except Exception:
            pass




def rebind_pg(rt, spec):
    """Specs built inside a worker carry a pickled PlacementGroup CLONE
    (stale bundles, no node assignments); re-bind the strategy to the host
    manager's live object by id."""
    strat = getattr(spec, "scheduling_strategy", None)
    pg = getattr(strat, "placement_group", None)
    if pg is not None:
        live = rt.pg_manager.get(pg.id)
        if live is not None:
            strat.placement_group = live
    return spec


def dispatch_core_op(rt, holder, call: str, kw: Dict[str, Any],
                     task_rid: Optional[str]) -> Any:
    """Owner-side dispatch of a worker/daemon-initiated core operation.

    Shared by the in-process WorkerClient pipe path and the cluster-mode
    owner RPC service (reference: CoreWorkerService,
    ``protobuf/core_worker.proto:457-577``). ``holder`` pins refs created
    on behalf of the remote caller via ``_hold(task_rid, obj)``.
    """
    if call == "get":
            return rt.get(kw["refs"], timeout=kw.get("timeout"))
    if call == "put":
        ref = rt.put(kw["value"])
        holder._hold(task_rid, ref)
        return ref
    if call == "put_stored":
        # direct-put registration: the worker already wrote + sealed
        # the payload in its node's arena; the owner only records
        # ownership, location, and (for raw tier) the array dtype/shape
        ref = rt.put_stored(kw["oid"], kw["key"], kw["nbytes"],
                            kw.get("raw"), kw["node"])
        holder._hold(task_rid, ref)
        return ref
    if call == "wait":
        return rt.wait(kw["refs"], num_returns=kw["num_returns"],
                       timeout=kw["timeout"],
                       fetch_local=kw["fetch_local"])
    if call == "submit_task":
        spec = rebind_pg(rt, kw["spec"])
        refs = rt.submit_task(spec)
        holder._hold(task_rid, refs)
        return refs
    if call == "create_actor":
        return rt.create_actor(rebind_pg(rt, kw["spec"]),
                               get_if_exists=kw["get_if_exists"])
    if call == "kill_actor":
        return rt.kill_actor(kw["actor_id"],
                             no_restart=kw["no_restart"],
                             cause=kw["cause"])
    if call == "cancel":
        return rt.cancel(kw["ref"], force=kw["force"],
                         recursive=kw["recursive"])
    if call == "gen_next":
        state = rt.generator_state(kw["task_id"])
        try:
            ref = state.next_ref(kw["index"], timeout=kw.get("timeout"))
            if isinstance(ref, tuple):      # a ``RunItem``
                ref = rt.run_item_ref(ref)
            holder._hold(task_rid, ref)
            return ref
        except StopIteration:
            return None
    if call == "gen_finished":
        return rt.generator_state(kw["task_id"]).finished
    if call == "gcs_get_actor_info":
        return rt.gcs.get_actor_info(kw["actor_id"])
    if call == "gcs_get_named_actor":
        return rt.gcs.get_named_actor(kw["name"], kw["namespace"])
    if call.startswith("gcs_kv_"):
        # same store preference as ray_tpu.util.rpdb._kv: the head's KV
        # when one exists (cross-process discoverable), else local gcs
        backend = getattr(rt, "cluster_backend", None)
        store = getattr(backend, "head", None) or rt.gcs
        ns = kw.get("namespace", b"")
        if call == "gcs_kv_put":
            return store.kv_put(kw["key"], kw["value"],
                                overwrite=kw.get("overwrite", True),
                                namespace=ns)
        if call == "gcs_kv_get":
            return store.kv_get(kw["key"], namespace=ns)
        if call == "gcs_kv_del":
            return store.kv_del(kw["key"], namespace=ns)
        if call == "gcs_kv_keys":
            return store.kv_keys(kw["prefix"], namespace=ns)
    if call == "fetch_function":
        return fetch_function_blob(kw["fid"])
    if call == "metrics_push":
        from ray_tpu.util import metrics as _metrics
        _metrics.merge_deltas(kw["entries"])
        return True
    if call == "fetch_runtime_pkg":
        from ray_tpu._private.runtime_env_packaging import fetch_pkg_blob
        return fetch_pkg_blob(kw["uri"])
    if call == "locate_object":
        # Owner-keyed object directory (ownership_object_directory.h):
        # which daemons hold a copy of this object (by daemon store key),
        # answered from the owner's authoritative location metadata.
        key = kw["oid"]
        addrs = []
        with rt._nodes_lock:
            nodes = list(rt._nodes.values())
        for node in nodes:
            handle = getattr(node, "daemon", None)
            store = getattr(node, "store", None)
            has = getattr(store, "has_daemon_key", None)
            if (handle is not None and not handle.dead
                    and has is not None and has(key)):
                addrs.append(list(handle.addr))
        return addrs
    if call == "pg_get":
        return rt.pg_manager.get(kw["pg_id"])
    if call == "pg_create":
        return rt.pg_manager.create(kw["bundles"], kw["strategy"],
                                    kw["name"])
    if call == "pg_remove":
        pg = rt.pg_manager.get(kw["pg_id"])
        if pg is not None:
            rt.pg_manager.remove(pg)
        return None
    if call == "pg_table":
        return rt.pg_manager.table()
    if call == "pg_ready_ref":
        pg = rt.pg_manager.get(kw["pg_id"])
        if pg is None:
            raise ValueError("unknown placement group")
        ref = pg.ready()
        holder._hold(task_rid, ref)
        return ref
    if call == "host_info":
        return {"namespace": rt.namespace, "job_id": rt.job_id}
    if call == "cluster_resources":
        return rt.cluster_resources()
    if call == "available_resources":
        return rt.available_resources()
    raise ValueError(f"unknown core op {call!r}")


def _untrack_after(router, task_id, it):
    """Yield through a worker stream, untracking the task at stream end."""
    try:
        yield from it
    finally:
        router.untrack_task(task_id)


# Monotonic spawn counter: (pid, generation) identifies a worker to the
# object-plane grant ledger even if the OS recycles the pid within one
# daemon lifetime.
_WORKER_GEN = itertools.count(1)


class WorkerClient:
    """Host handle to one worker process."""

    def __init__(self, boot: Dict[str, Any]):
        ctx = _mp_context()
        self.conn, child = ctx.Pipe()
        p = ctx.Process(target=_child_main, args=(child,), daemon=True,
                        name="ray-tpu-worker")
        _start_sans_main(p)
        self.proc = _ProcHandle(p)
        self.gen = next(_WORKER_GEN)
        # set by the daemon at the worker's first arena grant; reclaim
        # keys the grant ledger off it when the process dies
        self.arena_client_id: Optional[str] = None
        child.close()
        # First frame: boot config (platform pinning etc.).
        self.conn.send_bytes(cloudpickle.dumps(boot))
        self._send_lock = threading.Lock()
        self._ids = itertools.count()
        self._pending: Dict[str, _Pending] = {}  #: guarded by self._pending_lock
        self._pending_lock = threading.Lock()
        # Objects created on behalf of the worker (refs from put/submit),
        # pinned until the creating task — or the whole actor — ends.
        self._holds: Dict[str, List[Any]] = {}
        self.runtime = None          # bound by the router on assignment
        self.node = None
        self.actor_id: Optional[ActorID] = None
        self.expected_death = False
        self.dead = False
        self.calls = 0
        self._on_death: List[Any] = []
        self._reader = threading.Thread(target=self._read_loop, daemon=True,
                                        name=f"wkr-read-{self.proc.pid}")
        self._reader.start()

    # -- plumbing --------------------------------------------------------
    def _send(self, msg: Dict[str, Any]) -> None:
        blob = cloudpickle.dumps(msg)
        try:
            with self._send_lock:
                self.conn.send_bytes(blob)
        except (BrokenPipeError, OSError):
            raise WorkerCrashed(
                f"worker {self.proc.pid} pipe closed "
                f"(exitcode={self.proc.poll()})")

    def _read_loop(self) -> None:
        while True:
            try:
                msg = cloudpickle.loads(self.conn.recv_bytes())
            except (EOFError, OSError, ConnectionResetError):
                self._on_dead()
                return
            except Exception:
                self._on_dead()
                return
            op = msg.get("op")
            if op in ("result", "gen_start", "yield"):
                if op == "result" and msg.get("span") is not None:
                    # exec-phase span piggybacked on the result frame:
                    # ingest into this host process's sink (daemon ->
                    # head via heartbeat; driver -> its own buffer)
                    try:
                        from ray_tpu._private import events as _events
                        _events.ingest_span_events(
                            getattr(self.runtime, "task_events", None),
                            [msg["span"]])
                    except Exception:
                        pass
                if op == "result" and msg.get("profile") is not None:
                    # worker profile piggyback (the span discipline):
                    # into this process's store; the daemon heartbeat
                    # (or a driver-side cluster_profile) federates it
                    try:
                        from ray_tpu.util import profiling as _prof
                        _prof.ingest_profile(msg["profile"])
                    except Exception:
                        pass
                with self._pending_lock:
                    pend = self._pending.get(msg["id"])
                if pend is not None:
                    pend.q.put(msg)
            elif op == "core":
                threading.Thread(target=self._serve_core, args=(msg,),
                                 daemon=True).start()

    def _on_dead(self) -> None:
        if self.dead:
            return
        self.dead = True
        with self._pending_lock:
            pending = list(self._pending.values())
        for p in pending:
            p.q.put(_DEAD)
        self._holds.clear()
        callbacks, self._on_death = self._on_death, []
        for cb in callbacks:
            try:
                cb(self)
            except Exception:
                pass

    def add_death_callback(self, cb) -> None:
        if self.dead:
            cb(self)
        else:
            self._on_death.append(cb)

    def alive(self) -> bool:
        return not self.dead and self.proc.poll() is None

    def notify_extend_sys_path(self, paths: List[str]) -> None:
        """Fire-and-forget: live workers learn new driver import roots
        (a late hello must also reach the prestarted pool)."""
        self._send({"op": "extend_sys_path", "paths": list(paths)})

    def kill(self, expected: bool = True) -> None:
        import subprocess
        _checkout_done(self)
        self.expected_death = self.expected_death or expected
        try:
            self._send({"op": "shutdown"})
        except WorkerCrashed:
            pass
        try:
            self.proc.wait(timeout=0.5)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            try:
                self.proc.wait(timeout=1.0)
            except subprocess.TimeoutExpired:
                pass
        try:
            self.conn.close()
        except OSError:
            pass

    # -- worker-initiated core ops --------------------------------------
    def _serve_core(self, msg: Dict[str, Any]) -> None:
        try:
            forward = getattr(self.runtime, "forward_core_op", None)
            shm = (getattr(self.runtime, "shm_ops", None)
                   if msg.get("call") in _SHM_LOCAL_OPS else None)
            local_fn = (_local_fn_blob(msg)
                        if (forward is not None
                            and msg.get("call") == "fetch_function")
                        else None)
            if shm is not None:
                # object-plane metadata ops are DAEMON-LOCAL: the whole
                # point is that neither metadata resolution nor payload
                # ever round-trips through the owner. The client handle
                # rides along so grants are charged to THIS worker's
                # (pid, generation) in the reclamation ledger.
                value = shm(msg["call"], cloudpickle.loads(msg["payload"]),
                            self)
                reply = {"op": "reply", "for": msg["id"], "ok": True,
                         "value": cloudpickle.dumps(value)}
            elif local_fn is not None:
                # function blobs are content-addressed (sha1 fid): serve
                # from this process's table when present — xlang fids
                # only exist here, and it skips a driver round trip
                reply = {"op": "reply", "for": msg["id"], "ok": True,
                         "value": cloudpickle.dumps(local_fn)}
            elif forward is not None:
                # Daemon mode: raw round-trip to the owner (driver); the
                # blob is already pickled at the owner's edge.
                ok, blob = forward(msg)
                reply = {"op": "reply", "for": msg["id"], "ok": ok,
                         "value": blob}
            else:
                value = self._core_dispatch(msg)
                reply = {"op": "reply", "for": msg["id"], "ok": True,
                         "value": _safe_dumps(value)}
        except BaseException as e:  # noqa: BLE001 — shipped back
            try:
                blob = cloudpickle.dumps(e)
            except Exception:
                blob = cloudpickle.dumps(RuntimeError(repr(e)))
            reply = {"op": "reply", "for": msg["id"], "ok": False,
                     "value": blob}
        try:
            self._send(reply)
        except WorkerCrashed:
            pass

    def _hold(self, task_rid: Optional[str], obj: Any) -> None:
        key = task_rid or "__actor__"
        if self.actor_id is not None:
            key = "__actor__"  # actor-held refs live as long as the actor
        self._holds.setdefault(key, []).append(obj)

    def _hold_key_for(self, msg: Dict[str, Any]) -> Optional[str]:
        """Classic workers key holds by worker rid (released in
        _finish); dedicated fast-lane workers have no per-call _finish,
        so their holds key by the global borrower id ('t:<task>') and
        release via ProcessRouter.release_borrows when the task ends."""
        if getattr(self, "fast_lane", False) and msg.get("task_key"):
            return msg["task_key"]
        return msg.get("task")

    def _core_dispatch(self, msg: Dict[str, Any]) -> Any:
        kw = cloudpickle.loads(msg["payload"])
        if msg["call"] == "metrics_push":
            # process-global registry, no runtime binding needed — the
            # post-task flush legitimately races release_worker()'s
            # runtime reset
            from ray_tpu.util import metrics as _metrics
            _metrics.merge_deltas(kw["entries"])
            return True
        rt = self.runtime
        if rt is None:
            raise RuntimeError("worker not bound to a runtime")
        return dispatch_core_op(rt, self, msg["call"], kw,
                                self._hold_key_for(msg))

    def _request(self, msg: Dict[str, Any]) -> Tuple[str, _Pending]:
        rid = f"h{next(self._ids)}"
        msg["id"] = rid
        pend = _Pending()
        with self._pending_lock:
            self._pending[rid] = pend
        if self.dead:
            pend.q.put(_DEAD)
            return rid, pend
        self._send(msg)
        return rid, pend

    def _finish(self, rid: str) -> None:
        with self._pending_lock:
            self._pending.pop(rid, None)
        self._holds.pop(rid, None)

    def profile_burst(self, duration: float = 2.0):
        """Sample this worker's stacks for ``duration`` seconds; returns
        the profile record, or None if the worker died mid-burst."""
        rid, pend = self._request({"op": "profile_burst",
                                   "duration": float(duration)})
        try:
            msg = pend.q.get(timeout=duration + 10.0)
        except queue.Empty:
            self._finish(rid)
            return None
        if msg is _DEAD:
            self._finish(rid)
            return None
        ok = msg.get("ok")
        blob = msg.get("blob")
        self._finish(rid)
        if not ok or blob is None:
            return None
        rec = cloudpickle.loads(blob)
        return rec if isinstance(rec, dict) else None

    # Daemons run no user code: with raw_outcomes they hand result blobs
    # through without unpickling (the owner deserializes at the edge).
    raw_outcomes = False

    def _wait_outcome(self, rid: str, pend: _Pending):
        """First message decides: value result, error, or generator."""
        msg = pend.q.get()
        if msg is _DEAD:
            self._finish(rid)
            raise WorkerCrashed(
                f"worker process {self.proc.pid} died "
                f"(exitcode={self.proc.poll()})")
        if msg["op"] == "gen_start":
            return ("gen", self._gen_iter(rid, pend))
        ok = msg["ok"]
        if self.raw_outcomes:
            self._finish(rid)
            return ("ok_raw" if ok else "err_raw", msg["blob"])
        payload = cloudpickle.loads(msg["blob"])
        self._finish(rid)
        if ok:
            return ("ok", payload)
        e, tb = payload
        setattr(e, "_remote_traceback", tb)
        return ("err", e)

    def _gen_iter(self, rid: str, pend: _Pending):
        try:
            while True:
                msg = pend.q.get()
                if msg is _DEAD:
                    raise WorkerCrashed(
                        f"worker process {self.proc.pid} died mid-stream")
                if msg["op"] == "yield":
                    if self.raw_outcomes:
                        # no ack here: in daemon mode the ack comes from
                        # the DRIVER's consumer via the gen_ack RPC, so
                        # flow control tracks end-consumption, not relay
                        yield ("yield_raw", msg["blob"])
                        continue
                    yield cloudpickle.loads(msg["blob"])
                    try:
                        # consumer pulled the item: grant the producer
                        # another flow-control token
                        self._send({"op": "gen_ack", "target": rid})
                    except WorkerCrashed:
                        pass
                    continue
                ok = msg["ok"]
                if self.raw_outcomes:
                    if not ok:
                        yield ("err_raw", msg["blob"])
                    return
                payload = cloudpickle.loads(msg["blob"])
                if not ok:
                    e, tb = payload
                    setattr(e, "_remote_traceback", tb)
                    raise e
                return
        finally:
            self._finish(rid)

    @staticmethod
    def _ctx_fields(spec: TaskSpec, node, runtime) -> Dict[str, Any]:
        return {
            "job_id": getattr(spec, "job_id", None) or runtime.job_id,
            "task_id": spec.task_id,
            "node_id": node.node_id if node is not None else None,
            "actor_id": spec.actor_id,
            "resources": spec.resources,
            "task_name": spec.name,
            "placement_group_id": spec.placement_group_id,
            "pg_capture": spec.pg_capture,
            "trace": ({"id": spec.trace_id}
                      if getattr(spec, "trace_sampled", False) else None),
        }

    def execute_task(self, spec: TaskSpec, node, fid: str,
                     args_blob: bytes):
        self.calls += 1
        rid, pend = self._request({
            "op": "execute_task", "fn_id": fid, "args_blob": args_blob,
            "ctx": self._ctx_fields(spec, node, self.runtime),
            "runtime_env": spec.runtime_env,
            "backpressure": spec.backpressure_num_objects,
        })
        router = self.runtime.process_router
        router.track_task(spec.task_id, self, rid)
        try:
            outcome = self._wait_outcome(rid, pend)
        except BaseException:
            router.untrack_task(spec.task_id)
            raise
        if outcome[0] == "gen":
            # Stay tracked while the worker streams — cancel()/crash
            # handling must be able to reach a producing generator task.
            return ("gen", _untrack_after(router, spec.task_id, outcome[1]))
        router.untrack_task(spec.task_id)
        return outcome

    def create_actor_instance(self, spec: TaskSpec, node, fid: str,
                              args_blob: bytes):
        self.calls += 1
        rid, pend = self._request({
            "op": "create_actor", "fn_id": fid, "args_blob": args_blob,
            "ctx": self._ctx_fields(spec, node, self.runtime),
            "runtime_env": spec.runtime_env,
        })
        return self._wait_outcome(rid, pend)

    def call_method(self, spec: TaskSpec, node, args_blob: bytes):
        self.calls += 1
        rid, pend = self._request({
            "op": "call_method", "method": spec.method_name,
            "args_blob": args_blob,
            "ctx": self._ctx_fields(spec, node, self.runtime),
            "runtime_env": spec.runtime_env,
        })
        return self._wait_outcome(rid, pend)

    def reset_actor(self):
        """Tear down the actor instance in-process (clean death path) so
        the worker can be recycled."""
        rid, pend = self._request({"op": "reset_actor", "ctx": {},
                                   "runtime_env": None})
        return self._wait_outcome(rid, pend)

    def cancel_request(self, rid: str) -> None:
        try:
            self._send({"op": "cancel", "target": rid})
        except WorkerCrashed:
            pass


# ---------------------------------------------------------------------------
# pool (module-level: idle workers survive runtime shutdown and are reused
# across test runtimes — reference: worker prestart/reuse across jobs)
# ---------------------------------------------------------------------------

_POOL_LOCK = threading.Lock()
_IDLE: List[WorkerClient] = []
# driver import roots shipped at hello (code-search-path role): new
# workers get them in the boot frame, live ones via an extend op
_EXTRA_SYS_PATH: List[str] = []
_SYS_PATH_VERSION = [0]
_ALL_WORKERS: "weakref.WeakSet" = weakref.WeakSet()


def set_extra_sys_path(paths: List[str]) -> None:
    changed = False
    for p in paths:
        if p not in _EXTRA_SYS_PATH:
            _EXTRA_SYS_PATH.append(p)
            changed = True
    if changed:
        _SYS_PATH_VERSION[0] += 1


# The hosting daemon's shm arena (name, capacity): handed to every
# worker in the boot frame so it can attach the segment and run the
# zero-copy object protocol. Unset outside daemon processes.
_ARENA_INFO: List[Optional[tuple]] = [None]


def set_arena_info(name: str, capacity: int) -> None:
    _ARENA_INFO[0] = (name, int(capacity))


def live_workers() -> List["WorkerClient"]:
    return [w for w in list(_ALL_WORKERS) if w.alive()]
_PRESTARTING = [0]
_POOL_CLOSED = threading.Event()   # interpreter exiting: no new spawns
# Demand tracking: the idle cap follows the high-water mark of concurrent
# checkouts (decayed on a window) so a burst of N parallel tasks keeps N
# workers warm instead of churning fork+join on every release (reference:
# worker_pool.h num_workers_soft_limit + idle reaping).
_ACTIVE = [0]
_PEAK = [0]
_PEAK_TS = [0.0]
_PEAK_WINDOW_S = 60.0
from ray_tpu._private.thread_pool import DaemonThreadPool

_REAPER = DaemonThreadPool(2, name="worker-reaper")


def _pool_floor() -> int:
    from ray_tpu._private.config import cfg
    n = cfg().process_pool_size
    return n if n > 0 else min(4, max(2, (os.cpu_count() or 4) // 2))


def _pool_target() -> int:
    """Idle cap: configured floor, raised to the recent peak of concurrent
    checkouts (bounded by process_pool_max)."""
    from ray_tpu._private.config import cfg
    return max(_pool_floor(), min(_PEAK[0], cfg().process_pool_max))


def _async_kill(w: WorkerClient) -> None:
    """Reap off the caller's thread: kill() blocks up to 1.5s on join."""
    _REAPER.submit(lambda: w.kill(expected=True))


def _make_boot() -> Dict[str, Any]:
    boot: Dict[str, Any] = {"env": {}}
    if _EXTRA_SYS_PATH:
        boot["extra_sys_path"] = list(_EXTRA_SYS_PATH)
    # Workers never own the accelerator: pin them to the CPU platform with
    # the same virtual device count the host uses (so jax-in-worker works
    # under the test mesh and cannot fight over the chip).
    boot["force_cpu_platform"] = True
    n = None
    try:
        import re
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                      os.environ.get("XLA_FLAGS", ""))
        if m:
            n = int(m.group(1))
    except Exception:
        pass
    boot["cpu_devices"] = n
    from ray_tpu._private.log_monitor import (log_to_driver_enabled,
                                              session_log_dir)
    boot["log_dir"] = (session_log_dir()
                       if log_to_driver_enabled() else None)
    if _ARENA_INFO[0] is not None:
        boot["arena"] = _ARENA_INFO[0]
    return boot


def _spawn_worker() -> WorkerClient:
    # version BEFORE building the boot: a set_extra_sys_path racing
    # this spawn makes the worker look stale, and ensure_sys_path
    # re-sends (idempotent) instead of silently missing the paths
    version = _SYS_PATH_VERSION[0]
    w = WorkerClient(_make_boot())
    w._sys_path_version = version
    _ALL_WORKERS.add(w)
    return w


def ensure_sys_path(w: "WorkerClient") -> None:
    """Re-send driver import roots if this worker predates the latest
    set_extra_sys_path (spawn/hello races leave stale workers)."""
    if getattr(w, "_sys_path_version", -1) != _SYS_PATH_VERSION[0]:
        try:
            w.notify_extend_sys_path(_EXTRA_SYS_PATH)
            w._sys_path_version = _SYS_PATH_VERSION[0]
        except Exception:
            pass


def _checkout_done(w: WorkerClient) -> None:
    """Decrement the active-checkout count exactly once per checkout;
    called from release_worker AND WorkerClient.kill so crash paths
    (which kill without releasing) keep the accounting straight."""
    with _POOL_LOCK:
        if getattr(w, "_checked_out", False):
            w._checked_out = False
            _ACTIVE[0] = max(0, _ACTIVE[0] - 1)


def acquire_worker() -> WorkerClient:
    got: Optional[WorkerClient] = None
    with _POOL_LOCK:
        now = time.monotonic()
        _ACTIVE[0] += 1
        if now - _PEAK_TS[0] > _PEAK_WINDOW_S:
            _PEAK[0] = _ACTIVE[0]
            _PEAK_TS[0] = now
        elif _ACTIVE[0] > _PEAK[0]:
            _PEAK[0] = _ACTIVE[0]
        while _IDLE:
            w = _IDLE.pop()
            if w.alive():
                got = w
                break
            _async_kill(w)
    if got is None:
        try:
            got = _spawn_worker()
        except BaseException:
            with _POOL_LOCK:   # keep _ACTIVE honest on spawn failure
                _ACTIVE[0] = max(0, _ACTIVE[0] - 1)
            raise
    got._checked_out = True
    ensure_sys_path(got)
    _maybe_prestart_async()
    return got


def release_worker(w: WorkerClient) -> None:
    _checkout_done(w)
    if w.actor_id is not None or not w.alive():
        _async_kill(w)
        return
    w.runtime = None
    w.node = None
    with _POOL_LOCK:
        if len(_IDLE) >= _pool_target():
            keep = False
        else:
            _IDLE.append(w)
            keep = True
    if not keep:
        _async_kill(w)


_FILL_RUNNING = [False]


def _maybe_prestart_async() -> None:
    """Keep the idle pool warm in the background (reference: PrestartWorkers).

    The deficit counts checked-out workers too: a burst's active workers
    return to the idle pool on release, so spawning replacements for them
    would overshoot and churn."""
    if _POOL_CLOSED.is_set():
        return
    with _POOL_LOCK:
        deficit = (_pool_target() - len(_IDLE) - _PRESTARTING[0]
                   - _ACTIVE[0])
        if deficit <= 0 or _FILL_RUNNING[0]:
            return
        _FILL_RUNNING[0] = True

    def fill():
        try:
            while not _POOL_CLOSED.is_set():
                with _POOL_LOCK:
                    deficit = (_pool_target() - len(_IDLE)
                               - _PRESTARTING[0] - _ACTIVE[0])
                    if deficit <= 0:
                        return
                    _PRESTARTING[0] += 1
                try:
                    w = _spawn_worker()
                finally:
                    with _POOL_LOCK:
                        _PRESTARTING[0] -= 1
                with _POOL_LOCK:
                    if (len(_IDLE) < _pool_target()
                            and not _POOL_CLOSED.is_set()):
                        _IDLE.append(w)
                    else:
                        _async_kill(w)
                        return
        except Exception:
            pass
        finally:
            with _POOL_LOCK:
                _FILL_RUNNING[0] = False
    threading.Thread(target=fill, daemon=True,
                     name="worker-prestart").start()


def drain_pool() -> None:
    """Kill every idle pooled worker (test hygiene / interpreter exit)."""
    with _POOL_LOCK:
        idle, _IDLE[:] = list(_IDLE), []
    for w in idle:
        w.kill()


# ---------------------------------------------------------------------------
# router (owned by the Runtime)
# ---------------------------------------------------------------------------

def _contains_device_value(value: Any) -> bool:
    from ray_tpu._private.object_store import _is_device_value
    return _is_device_value(value)


def _wants_accelerator(resources: Dict[str, float]) -> bool:
    return any(k == "TPU" or k.startswith("TPU") or k == "GPU"
               for k, v in (resources or {}).items() if v)


class _ProcessActorInstance:
    """Host-side proxy for an actor living in a worker process. The
    Runtime's actor-task executor detects this type and routes method
    calls through ProcessRouter.call_actor_method; all the host-side
    ActorExecutor machinery (ordering, concurrency groups, restarts)
    drives it exactly like a live instance."""

    __slots__ = ("_client", "_class_name")

    def __init__(self, client: WorkerClient, class_name: str):
        self._client = client
        self._class_name = class_name


class ProcessRouter:
    def __init__(self, runtime):
        self.runtime = runtime
        self.enabled = os.environ.get(
            "RAY_TPU_PROCESS_WORKERS", "1") != "0"
        self._actor_workers: Dict[ActorID, WorkerClient] = {}
        self._lock = threading.Lock()
        # task_id -> (client, rid) while a normal task runs in a process
        self._running: Dict[TaskID, Tuple[WorkerClient, str]] = {}
        # driver-local fast lane (the SAME native core the daemons run,
        # native/daemon_core.cc, hosted in THIS process): plain tasks
        # skip the per-task mp.Connection round trip and per-task
        # checkout entirely. Lazily started on first eligible task.
        self._fast = None                 # FastLaneClient
        self._fast_core = None            # CoreHandle
        self._fast_lock = threading.Lock()
        self._fast_workers: List[WorkerClient] = []
        # task hex -> (lane client, rid): the client pins the rid to
        # its generation (see cancel_task)
        self._fast_rids: Dict[str, Tuple[Any, int]] = {}
        self._fast_disabled = os.environ.get(
            "RAY_TPU_FAST_LANE", "1") == "0"
        self._fast_max = max(2, min(8, (os.cpu_count() or 4)))
        if self.enabled:
            # Launch the forkserver template synchronously during init()
            # (bounds the brief PYTHONPATH env window to the init
            # call), then warm the pool in the background so the
            # first task/actor doesn't pay process-spawn latency
            # (reference: worker prestart, raylet/worker_pool.h).
            try:
                _mp_context()
            except Exception:
                pass
            _maybe_prestart_async()

    # -- eligibility -----------------------------------------------------
    def _serialize_payload(self, spec: TaskSpec, args, kwargs
                           ) -> Optional[Tuple[str, bytes]]:
        if _contains_device_value((args, kwargs)):
            return None
        try:
            fid, _ = export_function(spec.func)
            args_blob = cloudpickle.dumps((args, kwargs))
        except Exception:
            return None
        return fid, args_blob

    def eligible_task(self, spec: TaskSpec, args, kwargs):
        # pg_demand is the pre-rewrite demand snapshot: once a task is
        # scheduled into a placement group its resources are renamed to
        # bundle-scoped keys (_pg_<id>_<idx>_TPU) that plain name checks
        # would miss.
        demand = getattr(spec, "pg_demand", None) or spec.resources
        if (not self.enabled or spec.kind != TaskKind.NORMAL
                or getattr(spec, "in_process", False)
                or _wants_accelerator(demand)):
            return None
        return self._serialize_payload(spec, args, kwargs)

    def eligible_actor(self, spec: TaskSpec, args, kwargs):
        demand = getattr(spec, "pg_demand", None) or spec.resources
        if (not self.enabled or spec.kind != TaskKind.ACTOR_CREATION
                or getattr(spec, "in_process", False)
                or _wants_accelerator(demand)):
            return None
        cls = spec.func
        if not inspect.isclass(cls):
            return None
        from ray_tpu._private.worker import _class_is_async
        if _class_is_async(cls):
            return None  # asyncio actors run on the host loop
        return self._serialize_payload(spec, args, kwargs)

    # -- normal tasks ----------------------------------------------------
    def track_task(self, task_id: TaskID, client: WorkerClient,
                   rid: str) -> None:
        with self._lock:
            self._running[task_id] = (client, rid)

    def untrack_task(self, task_id: TaskID) -> None:
        with self._lock:
            self._running.pop(task_id, None)

    def worker_pid_for_task(self, task_id: TaskID) -> Optional[int]:
        """Test/chaos hook: pid of the process running a task."""
        with self._lock:
            entry = self._running.get(task_id)
        return entry[0].proc.pid if entry else None

    def execute_task(self, spec: TaskSpec, node, payload):
        fid, args_blob = payload
        if self._fast_eligible(spec):
            out = self._execute_fast(spec, node, fid, args_blob)
            if out is not None:
                return out
            # lane declined (down, or the function returned a live
            # generator): classic checkout below
        client = acquire_worker()
        client.runtime = self.runtime
        client.node = node
        try:
            outcome = client.execute_task(spec, node, fid, args_blob)
        except WorkerCrashed:
            client.kill(expected=False)
            raise
        if outcome[0] == "gen":
            # Streaming generator: the worker keeps producing after this
            # returns — release it only when the stream is drained, or
            # a full pool would kill the process mid-stream.
            return ("gen", self._release_after(client, outcome[1]))
        release_worker(client)
        return outcome

    # -- driver-local fast lane ------------------------------------------
    def _fast_eligible(self, spec: TaskSpec) -> bool:
        return (not self._fast_disabled
                and spec.num_returns == 1
                and not spec.runtime_env
                and not (spec.func is not None
                         and inspect.isgeneratorfunction(spec.func)))

    def _fast_client(self):
        if self._fast is not None and not self._fast.dead:
            return self._fast
        from ray_tpu._private.fast_lane import (CoreHandle,
                                                FastLaneClient,
                                                lane_reconnect_policy)
        try:
            with self._fast_lock:
                if self._fast is not None and not self._fast.dead:
                    return self._fast
                if self._fast_core is None:
                    core = CoreHandle()
                    if core.start("127.0.0.1", 0) is None:
                        self._fast_disabled = True   # no native build
                        return None
                    self._fast_core = core
                    threading.Thread(target=self._fast_pool_loop,
                                     daemon=True,
                                     name="router-fastlane").start()
                port = self._fast_core.port
            # connect OUTSIDE the lock: the retry window's backoff
            # sleeps must not stall cancel_task/_fast_rids bookkeeping
            from ray_tpu._private import failpoints as _fp

            def connect():
                if _fp.ENABLED:
                    _fp.fire("fast_lane.reconnect")
                return FastLaneClient(("127.0.0.1", port))

            fl = lane_reconnect_policy().run(
                connect, loop="fast_lane.reconnect",
                retry_on=(OSError, _fp.FailpointError))
            with self._fast_lock:
                if self._fast is None or self._fast.dead:
                    self._fast = fl
                else:
                    fl.close()      # lost the reconnect race
                return self._fast
        except Exception:
            self._fast_disabled = True
            return None

    def _fast_dedicate(self) -> WorkerClient:
        core = self._fast_core
        if core is None:
            raise RuntimeError("fast lane stopped")
        w = _spawn_worker()
        # NOT _checked_out: lane workers never enter the idle pool, and
        # marking them checked out would make their eventual kill()
        # decrement an _ACTIVE count they never incremented (skewing
        # pool sizing)
        w.fast_lane = True
        w.runtime = self.runtime
        w.node = None
        try:
            rid, pend = w._request({
                "op": "join_fast_lane",
                "addr": ["127.0.0.1", core.port]})
            out = w._wait_outcome(rid, pend)
            if out[0] not in ("ok", "ok_raw"):
                raise RuntimeError(f"fast-lane join failed: {out!r}")
        except BaseException:
            try:
                w.kill(expected=True)
            except Exception:
                pass
            raise
        ensure_sys_path(w)
        return w

    def _fast_pool_loop(self) -> None:
        """Queue-depth-driven sizing, like the daemon's lane pool. The
        whole maintenance step holds _fast_lock so shutdown()'s swap
        can never interleave with a dedicate (which would leak the
        just-spawned worker process)."""
        while not getattr(self.runtime, "_shutdown", False):
            try:
                with self._fast_lock:
                    if self._fast_core is None:
                        return        # shut down
                    alive = [w for w in self._fast_workers
                             if w.alive()]
                    self._fast_workers = alive
                    for w in alive:
                        ensure_sys_path(w)
                    stats = self._fast_core.stats()
                    if (not alive
                            or (stats.get("queued", 0) > 0
                                and len(alive) < self._fast_max)):
                        self._fast_workers.append(
                            self._fast_dedicate())
                        continue
            except Exception:
                time.sleep(1.0)
            time.sleep(0.25)

    def _execute_fast(self, spec: TaskSpec, node, fid: str,
                      args_blob: bytes):
        from ray_tpu._private import fast_lane as _fle
        fl = self._fast_client()
        if fl is None:
            return None
        payload = _fle.build_payload(
            spec, fid, args_blob,
            getattr(spec, "job_id", None) or self.runtime.job_id,
            node.node_id if node is not None else None)
        try:
            rid, slot = fl.submit(payload)
        except _fle.FastLaneError:
            return None                  # nothing submitted: classic
        task_hex = spec.task_id.hex()
        with self._fast_lock:
            # store the CLIENT with the rid: after a lane death +
            # reconnect the new client's rid counter restarts at 1, so
            # a bare rid could cancel an unrelated task on the new lane
            self._fast_rids[task_hex] = (fl, rid)
        try:
            kind, blob = fl.wait(slot)
        except _fle.FastLaneUnsubmitted:
            # frame never reached the wire (another submitter's flush
            # failed first): nothing ran — classic path, retry-free
            return None
        except _fle.FastLaneError as e:
            # submitted but the lane died: surface as a worker crash so
            # retry accounting applies (never a silent re-run)
            crash = WorkerCrashed(f"fast lane died mid-task: {e}")
            crash.fast_lane = True
            raise crash
        finally:
            with self._fast_lock:
                self._fast_rids.pop(task_hex, None)
        if kind == _fle.KIND_OK:
            return ("ok", cloudpickle.loads(blob))
        if kind == _fle.KIND_ERR:
            e, tb = cloudpickle.loads(blob)
            setattr(e, "_remote_traceback", tb)
            return ("err", e)
        if kind == _fle.KIND_GEN_LIST:
            # the function body already ran and the worker drained its
            # returned generator: replay as a real generator so the
            # streaming machinery engages without re-running the body
            return ("gen", _fle.replay_gen_list(blob))
        if kind == _fle.KIND_GEN_FALLBACK:
            return None     # legacy worker: stream via the classic path
        if kind == _fle.KIND_CANCELLED:
            return ("err", KeyboardInterrupt())
        if kind == _fle.KIND_CRASHED:
            crash = WorkerCrashed(blob.decode(errors="replace"))
            crash.fast_lane = True
            raise crash
        raise RuntimeError(f"unknown fast-lane outcome kind {kind}")

    def release_borrows(self, key: str) -> None:
        """Drop lane workers' owner-side holds for a finished borrower
        ('t:<task>' — per-task borrow release for the driver-local
        lane, mirroring the cluster OwnerHolder)."""
        if not self._fast_workers:
            return  # no driver-local lane: per-completion fast path
        for w in list(self._fast_workers):
            dropped = w._holds.pop(key, None)
            del dropped

    @staticmethod
    def _release_after(client: WorkerClient, it):
        try:
            yield from it
        finally:
            release_worker(client)

    def cancel_task(self, task_id: TaskID, force: bool) -> bool:
        task_hex = task_id.hex()
        with self._fast_lock:
            entry = self._fast_rids.get(task_hex)
        if entry is not None:
            # cancel on the client GENERATION the task was submitted on
            # — a reconnected lane restarts its rid counter, and a
            # stale rid sent there would kill an unrelated task
            lane_client, rid = entry
            if not lane_client.dead:
                lane_client.cancel(rid, force=force)
            return True
        with self._lock:
            entry = self._running.get(task_id)
        if entry is None:
            return False
        client, rid = entry
        if force:
            client.expected_death = False
            client.proc.terminate()  # surfaces as WorkerCrashed
        else:
            client.cancel_request(rid)
        return True

    # -- actors ----------------------------------------------------------
    def create_actor(self, spec: TaskSpec, node, payload):
        """Returns a _ProcessActorInstance, or raises the user's __init__
        exception / WorkerCrashed."""
        fid, args_blob = payload
        client = acquire_worker()
        client.runtime = self.runtime
        client.node = node
        client.actor_id = spec.actor_id
        try:
            kind, value = client.create_actor_instance(
                spec, node, fid, args_blob)
        except WorkerCrashed:
            client.kill(expected=False)
            raise
        if kind == "err":
            client.actor_id = None
            release_worker(client)  # init failed cleanly; process reusable
            raise value
        client.actor_since = time.time()
        # Actor ownership is a PERMANENT checkout: stop counting it in
        # _ACTIVE, or _PEAK could never decay below the live-actor count
        # and the idle pool would stay burst-sized forever.
        _checkout_done(client)
        with self._lock:
            self._actor_workers[spec.actor_id] = client
        actor_id = spec.actor_id
        client.add_death_callback(
            lambda c, aid=actor_id: self._actor_worker_died(aid, c))
        return _ProcessActorInstance(client, getattr(spec.func, "__name__",
                                                     "Actor"))

    def call_actor_method(self, instance: _ProcessActorInstance,
                          spec: TaskSpec, node, args, kwargs):
        client: WorkerClient = instance._client
        if client.dead:
            from ray_tpu import exceptions as exc
            raise exc.ActorDiedError(spec.actor_id,
                                     "actor worker process died")
        from ray_tpu._private.device_objects import wire_dumps
        args_blob = wire_dumps((args, kwargs))   # device args over wire
        try:
            return client.call_method(spec, node, args_blob)
        except WorkerCrashed as e:
            from ray_tpu import exceptions as exc
            raise exc.ActorDiedError(spec.actor_id, str(e))

    def _actor_worker_died(self, actor_id: ActorID,
                           client: WorkerClient) -> None:
        with self._lock:
            current = self._actor_workers.get(actor_id)
            if current is client:
                self._actor_workers.pop(actor_id, None)
        if client.expected_death:
            return
        rt = self.runtime
        if rt is None or getattr(rt, "_shutdown", False):
            return
        # Unexpected process death → actor death with restart semantics
        # (reference: GcsActorManager restart path on worker failure).
        try:
            rt.on_actor_worker_died(actor_id,
                                    f"actor worker process died "
                                    f"(pid {client.proc.pid})")
        except Exception:
            pass

    def discard_actor(self, actor_id: ActorID, expected: bool = True) -> None:
        with self._lock:
            client = self._actor_workers.pop(actor_id, None)
        if client is None:
            return
        with client._pending_lock:
            busy = bool(client._pending)
        if not expected or busy or not client.alive():
            # Unexpected death, or method calls still in flight (a killed
            # actor's process dies with its running work, reference
            # semantics; recycling a busy worker would let the pool-full
            # check kill it mid-call for an unrelated reason).
            client.kill(expected=expected)
            return
        # Clean death: reset the in-process instance and recycle the
        # worker into the idle pool instead of paying a respawn later.
        try:
            kind, _ = client.reset_actor()
        except Exception:
            kind = "err"
        if kind not in ("ok", "ok_raw"):
            client.kill(expected=True)
            return
        client._on_death.clear()  # stale actor-death callbacks
        client._holds.pop("__actor__", None)
        client.actor_id = None
        release_worker(client)

    def actor_worker_pid(self, actor_id: ActorID) -> Optional[int]:
        with self._lock:
            client = self._actor_workers.get(actor_id)
        return client.proc.pid if client else None

    # -- lifecycle -------------------------------------------------------
    def shutdown(self) -> None:
        # fast lane first: the core is process-global (one rtdc per
        # process), so the next runtime in this process needs it freed
        with self._fast_lock:
            fl, self._fast = self._fast, None
            core, self._fast_core = self._fast_core, None
            lane_workers, self._fast_workers = self._fast_workers, []
        if fl is not None:
            fl.close()
        for w in lane_workers:
            try:
                w.kill(expected=True)
            except Exception:
                pass
        if core is not None:
            try:
                core.stop()
            except Exception:
                pass
        with self._lock:
            actors = dict(self._actor_workers)
            self._actor_workers.clear()
        for actor_id, client in actors.items():
            # Recycle cleanly-shut-down actor workers into the pool (the
            # pool outlives runtimes by design; respawns are expensive).
            with self._lock:
                self._actor_workers[actor_id] = client
            self.discard_actor(actor_id, expected=True)
