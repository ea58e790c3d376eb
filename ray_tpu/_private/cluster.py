"""Driver-side cluster backend: real head + node-daemon processes.

This is the deployment shape where the control plane leaves the driver
process: a head (``ray_tpu/_private/head.py``, GCS-equivalent) and N node
daemons (``ray_tpu/_private/daemon.py``, raylet-equivalent) run as
separately spawned OS processes, and every interaction is a typed msgpack
RPC. The driver remains the single controller and object owner
(reference: the driver's core worker owns objects and submits tasks;
``src/ray/core_worker/``), which is also the right shape for TPU SPMD:
gang placement is centrally decided and the accelerator plane never
leaves the mesh-owning process.

What rides the wire (reference contracts):
- worker lease + task push   (node_manager.proto RequestWorkerLease,
  core_worker.proto PushTask)
- PG bundle 2PC              (PrepareBundleResources / Commit / Cancel)
- object get/put/free/pull   (object_manager.proto), with a same-host
  zero-copy path through the C++ shm arena (plasma's fd-passing role)
- worker-initiated core ops  (CoreWorkerService direction: daemons call
  the driver's owner server)
- health                     (daemon→head heartbeats; head long-poll
  pubsub pushes node death to the driver)
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import cloudpickle

from ray_tpu._private import events as _events
from ray_tpu._private import failpoints as _fp
from ray_tpu._private import rpc
from ray_tpu._private import daemon as _daemon_schemas  # noqa: F401 — declares the daemon RPC schemas
from ray_tpu._private.head import HeadClient
from ray_tpu._private.ids import NodeID
from ray_tpu._private.lock_sanitizer import tracked_lock
from ray_tpu._private.rpc import HOLD, declare

declare("core_op", "call", "payload", "task")

INLINE_RESULT = 100 * 1024


def _spawn(module: str, args: List[str],
           output_path: Optional[str] = None
           ) -> Tuple[subprocess.Popen, int]:
    """Spawn a python -m <module> child; returns (proc, announced_port).
    ``output_path`` redirects the child's stdout/stderr to a file —
    REQUIRED when the spawning process's own stdout is a pipe a caller
    waits on (`ray-tpu start`), else the child holds the pipe open."""
    r, w = os.pipe()
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # Control-plane processes never own the accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    out = None
    if output_path is not None:
        out = open(output_path, "ab")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", module, *args, "--announce-fd", str(w)],
            pass_fds=(w,), env=env, start_new_session=True,
            stdout=out, stderr=out)
    finally:
        if out is not None:
            out.close()
    os.close(w)
    with os.fdopen(r) as f:
        line = f.readline().strip()
    if not line:
        raise RuntimeError(f"{module} failed to start")
    return proc, int(line)


class ArenaCache:
    """Same-host attach to daemon shm arenas by name (zero-copy reads,
    direct-put writes, and shared-slot ref releases). Attach-only: a
    missing segment (remote host, no native build) caches as failed and
    every object falls back to the RPC byte path."""

    def __init__(self):
        self._arenas: Dict[str, Any] = {}  #: guarded by self._lock
        self._failed: set = set()          #: guarded by self._lock
        self._lock = tracked_lock("cluster.arena_cache", reentrant=False)

    def handle(self, arena: str):
        """Attached ShmObjectStore for ``arena``, or None."""
        with self._lock:
            store = self._arenas.get(arena)
            if store is not None:
                return store
            if arena in self._failed:
                return None
            try:
                from ray_tpu.native_store import ShmObjectStore
                store = ShmObjectStore.attach(arena)
            except Exception:
                self._failed.add(arena)
                return None
            self._arenas[arena] = store
            return store

    def read(self, arena: str, capacity: int, off: int,
             size: int) -> Optional[memoryview]:
        store = self.handle(arena)
        if store is None:
            return None
        return store.read_range(off, size)

    def write(self, arena: str, off: int, payload) -> bool:
        """Fill a daemon-reserved (unsealed) range in place — the
        direct-put payload write; the bytes never ride an RPC frame."""
        store = self.handle(arena)
        if store is None:
            return False
        try:
            store.write_range(off, payload)
            return True
        except Exception:
            return False

    def ext_release(self, arena: str, slot: int) -> bool:
        """Drop a shared-slot object ref through the local mapping (the
        zero-RPC release leg of the ref/release protocol)."""
        store = self.handle(arena)
        if store is None:
            return False
        try:
            store.ext_release(slot)
            return True
        except Exception:
            return False

    def close(self) -> None:
        # Deliberate leak, not munmap: zero-copy views handed to user
        # code may outlive this cluster session, and their finalizers
        # must find a mapping (and a live handle) — see
        # ShmObjectStore.detach_leak. The daemon owns the segment name;
        # nothing here keeps /dev/shm entries alive.
        with self._lock:
            for store in self._arenas.values():
                try:
                    store.detach_leak()
                except Exception:
                    pass
            self._arenas.clear()


class DaemonCrashed(Exception):
    """The daemon PROCESS died (transport failure): node-level failure."""


class RemoteWorkerCrashed(Exception):
    """A worker process inside a (healthy) daemon died under a task."""


class _Stream:
    def __init__(self):
        import queue

        self.q: "queue.Queue" = queue.Queue()


_STREAM_DEAD = object()


class _SubmitCoalescer:
    """Per-destination driver→daemon submit batching.

    Classic-path task submissions (everything that used one
    ``submit_task`` RPC per task) enqueue here; ONE flusher thread per
    daemon drains the queue into ``push_task_batch`` wire frames —
    up to ``submit_batch_max`` tasks per frame, lingering
    ``submit_linger_us`` for stragglers (reference: the batched lease
    requests / coalesced submissions that let Ray survive high task
    rates). Completions come back coalesced on ``task_batch_done``
    push frames, demuxed by :meth:`DaemonHandle._on_push`.

    Retry contract: a flush that fails BEFORE reaching the daemon
    (``batch.submit_flush`` drop/error arms — the deterministic stand-in
    for a lost frame) resends the same batch; the daemon dedupes by task
    id, so a retried frame never double-executes a task.
    """

    _MAX_SEND_ATTEMPTS = 8

    def __init__(self, handle: "DaemonHandle"):
        from ray_tpu._private.config import cfg
        self.handle = handle
        self.batch_max = max(1, int(cfg().submit_batch_max))
        self.linger_s = max(0.0, float(cfg().submit_linger_us) / 1e6)
        self._cv = threading.Condition()
        self._q: deque = deque()           #: guarded by self._cv
        self._stopped = False              #: guarded by self._cv
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"submit-batch-{handle.node_id.hex()[:8]}")
        self._thread.start()

    def enqueue(self, entry: Dict[str, Any]) -> None:
        with self._cv:
            if self._stopped:
                raise DaemonCrashed("daemon handle closed")
            self._q.append(entry)
            # wake the flusher only out of its IDLE wait (first entry)
            # or for a full batch: waking it out of the timed linger on
            # every append would flush 2-element frames and defeat the
            # coalescing the linger exists for
            if len(self._q) == 1 or len(self._q) >= self.batch_max:
                self._cv.notify()

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._q and not self._stopped:
                    self._cv.wait()
                if self._stopped:
                    return      # waiters are failed by mark_dead
                if (self.linger_s > 0 and len(self._q) < self.batch_max):
                    # one bounded linger for the rest of a burst; a
                    # second wait would add latency, not batching
                    self._cv.wait(self.linger_s)
                n = min(len(self._q), self.batch_max)
                batch = [self._q.popleft() for _ in range(n)]
            if batch:
                self._flush(batch)

    def _flush(self, batch: List[Dict[str, Any]]) -> None:
        handle = self.handle
        # ship each function blob once per (daemon, fid): repeated
        # submissions of the same remote function send only the fid
        # (reference: Ray exports function definitions to GCS once)
        fns: Dict[str, bytes] = {}
        for entry in batch:
            fid = entry["fid"]
            if fid not in handle._fns_shipped and fid not in fns:
                try:
                    from ray_tpu._private.worker_process import \
                        fetch_function_blob
                    fns[fid] = fetch_function_blob(fid)
                except KeyError:
                    pass    # workers fall back to the fetch core op
        for attempt in range(self._MAX_SEND_ATTEMPTS):
            if handle.dead:
                return      # mark_dead already failed the waiters
            if _fp.ENABLED:
                try:
                    act = _fp.fire("batch.submit_flush", n=len(batch),
                                   attempt=attempt)
                except Exception:   # noqa: BLE001 — injected error arm:
                    # the flush attempt "failed in transit"; retry the
                    # same batch (idempotent at the daemon)
                    continue
                if act is _fp.DROP:
                    continue        # frame lost pre-send; retry
            try:
                # linger end is sampled BEFORE the RPC: the phase is
                # "enqueue -> frame leaving on the wire" — measuring
                # after the reply would fold the round trip + daemon
                # frame handling into linger AND double-count it
                # against the daemon's dispatch span
                flush_mono = time.perf_counter()
                # the reply is an enqueue ACK (results ride the pump), so
                # a deadline is safe — an unacked flush past it means a
                # wedged link, which the RpcError path below treats as
                # node death (timeout audit: no unbounded dispatch trips)
                from ray_tpu._private.config import cfg as _cfg
                handle.client.call("push_task_batch", tasks=batch,
                                   fns=fns,
                                   timeout=_cfg().control_call_timeout_s)
                self._record_linger(batch, flush_mono)
            except rpc.RemoteError as e:
                if "no such method" in str(e):
                    # old daemon without the batch handler: fall back
                    # per-task, permanently for this handle
                    handle._batch_supported = False
                    self._flush_per_task(batch)
                    return
                for entry in batch:
                    handle._complete_batch_task(
                        {"task": entry["task"], "e": str(e)})
                return
            except rpc.RpcError:
                handle.mark_dead()      # transport death: node failure
                return
            if fns:
                handle._fns_shipped.update(fns)
            return
        # retries exhausted (persistent injected failure): surface as a
        # daemon-level failure so task retry accounting engages
        handle.mark_dead()

    def _record_linger(self, batch: List[Dict[str, Any]],
                       now: float) -> None:
        """linger phase: coalescer enqueue -> the batch frame leaving on
        the wire, per sampled task (driver lane). ``now`` is the
        pre-send perf_counter reading — one clock read per batch."""
        try:
            from ray_tpu._private import events as _events
            from ray_tpu._private import worker as _worker
            rt = _worker.global_runtime()
            buf = getattr(rt, "task_events", None) if rt else None
            node_hex = self.handle.node_id.hex()
            for entry in batch:
                t_enq = entry.get("t_enq")
                if t_enq is None:
                    continue
                dur = max(now - t_enq, 0.0)
                _events.record_phase(
                    buf, task_id=entry["task"],
                    name=entry.get("name", ""), phase="linger",
                    dur_s=dur, node_id=node_hex, proc="driver",
                    trace_id=entry.get("trace", ""),
                    start_wall=_events.wall_at(t_enq), end_mono=now)
        except Exception:
            pass    # observability must never fail a flush

    def _flush_per_task(self, batch: List[Dict[str, Any]]) -> None:
        """Compatibility path: one submit_task RPC per entry."""
        for entry in batch:
            try:
                out = dict(self.handle.client.call(
                    "submit_task", spec=entry["spec"], fid=entry["fid"],
                    args=entry["args"],
                    backpressure=entry["backpressure"], timeout=None))
                out["task"] = entry["task"]
            except rpc.RemoteError as e:
                out = {"task": entry["task"], "e": str(e)}
            except rpc.RpcError:
                self.handle.mark_dead()
                return
            self.handle._complete_batch_task(out)


class _FreeCoalescer:
    """Buffers zero-ref ``free_objects`` ids per daemon and flushes them
    time/size-bounded (``free_batch_max`` / ``free_flush_ms``) — the
    on-zero callback used to fire one single-element RPC per freed
    object. Frees are idempotent at the daemon, so a flush that fails
    in transit (``batch.free_flush`` failpoint) simply requeues."""

    def __init__(self, handle: "DaemonHandle"):
        from ray_tpu._private.config import cfg
        self.handle = handle
        self.batch_max = max(1, int(cfg().free_batch_max))
        self.flush_s = max(0.0, float(cfg().free_flush_ms) / 1e3)
        self._cv = threading.Condition()
        self._oids: List[bytes] = []       #: guarded by self._cv
        self._stopped = False              #: guarded by self._cv
        self._thread: Optional[threading.Thread] = None  #: guarded by self._cv

    def queue(self, oid: bytes) -> None:
        with self._cv:
            if self._stopped:
                return
            self._oids.append(oid)
            if self._thread is None:    # lazy: most handles never free
                self._thread = threading.Thread(
                    target=self._loop, daemon=True,
                    name=f"free-batch-{self.handle.node_id.hex()[:8]}")
                self._thread.start()
            # first element wakes the idle flusher (it parks in an
            # untimed wait, so a sub-batch_max trickle still leaves
            # within flush_s); later appends ride the timed linger —
            # notifying on each would flush tiny frames; a full batch
            # wakes it early
            if len(self._oids) == 1 or len(self._oids) >= self.batch_max:
                self._cv.notify()

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._oids and not self._stopped:
                    self._cv.wait()
                if self._stopped:
                    return
                if len(self._oids) < self.batch_max:
                    # time-bounded: partial batches leave within flush_s
                    self._cv.wait(self.flush_s)
                if self._stopped:
                    return
                oids = self._oids[:self.batch_max]
                del self._oids[:len(oids)]
            if oids:    # a concurrent flush() may have drained the lot
                self._send(oids)

    def flush(self) -> None:
        """Synchronous drain (worker shutdown, node drain): no queued
        free may be lost to a process exit."""
        while True:
            with self._cv:
                oids, self._oids = self._oids, []
            if not oids:
                return
            self._send(oids)

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._oids.clear()      # daemon dead: frees are moot
            self._cv.notify_all()

    def _send(self, oids: List[bytes]) -> None:
        if _fp.ENABLED:
            try:
                act = _fp.fire("batch.free_flush", n=len(oids))
            except Exception:   # noqa: BLE001 — injected error arm
                act = _fp.DROP
            if act is _fp.DROP:
                # flush failed in transit: requeue — object deletion is
                # idempotent at the daemon, so the retry is safe
                with self._cv:
                    if not self._stopped:
                        self._oids[:0] = oids
                return
        try:
            from ray_tpu._private.config import cfg as _cfg
            self.handle.client.call("free_objects", oids=oids,
                                    timeout=_cfg().control_call_timeout_s)
        except (rpc.RpcError, rpc.RemoteError):
            pass    # daemon dead/erroring: its store dies with it


def _count_fenced(kind: str) -> None:
    """Count one result frame rejected by partition fencing.
    ``kind``: "epoch" (stale daemon incarnation), "attempt" (stale task
    attempt), "dead" (stamped frame arrived after mark_dead)."""
    try:
        from ray_tpu.util.metrics import Counter
        Counter("ray_tpu_fenced_results_total",
                "result/stream frames rejected by partition fencing "
                "(stale epoch, stale attempt, or arrival after the "
                "handle was marked dead)",
                tag_keys=("kind",)).inc(tags={"kind": kind})
    except Exception:
        pass    # metrics must never fail result ingest


class DaemonHandle:
    """Driver's connection to one node daemon (lease/push/object plane)."""

    def __init__(self, node_id: NodeID, addr: Tuple[str, int],
                 proc: Optional[subprocess.Popen], arenas: ArenaCache):
        self.node_id = node_id
        self.addr = addr
        self.proc = proc
        self.arenas = arenas
        self._streams: Dict[str, _Stream] = {}  #: guarded by self._slock
        self._slock = tracked_lock("cluster.handle.streams",
                                   reentrant=False)
        self.on_actor_worker_died = None  # set by the backend
        self.client = rpc.connect(addr, timeout=None,
                                  on_push=self._on_push).link(
                                      "daemon", node_id.hex())
        self.dead = False
        # partition fencing: the daemon's registration epoch (minted by
        # the head, learned at hello and refreshed via membership) — a
        # result frame stamped with a LOWER epoch came from a superseded
        # incarnation across a healed partition and must not resolve
        # waiters (docs/fault_tolerance.md "Partitions, epochs & fencing")
        self.epoch = 0
        self._fence_supported = False       # daemon advertises in hello
        # zero-copy object plane (set from the hello reply)
        self.objectplane = False
        self.arena_name: Optional[str] = None
        self.arena_capacity = 0
        # fast lane: direct submit to the daemon's native (C++) core
        self.fast_port: Optional[int] = None
        self._fast = None
        self._fast_lock = tracked_lock("cluster.handle.fast_rids",
                                       reentrant=False)
        # reconnects (with their backoff sleeps) serialize on their OWN
        # lock: holding _fast_lock through a retry window would stall
        # every concurrent submit's _fast_rids bookkeeping and cancels
        self._fast_dial_lock = tracked_lock("cluster.handle.fast_dial",
                                            reentrant=False)
        # task hex -> (lane client, rid): the CLIENT pins the rid to its
        # generation — a reconnected lane restarts rid numbering, so a
        # bare rid could cancel an unrelated task on the new client
        self._fast_rids: Dict[str, Tuple[Any, int]] = {}  #: guarded by self._fast_lock
        # control-plane batching (submit coalescer + free buffer)
        self._batch_supported = False       # daemon advertises in hello
        self._result_batch = False          # coalesced completions for
        #                                     classic submits (hello flag)
        self._batch: Optional[_SubmitCoalescer] = None
        self._batch_lock = tracked_lock("cluster.handle.batch_init",
                                        reentrant=False)
        self._batch_waiters: Dict[str, list] = {}  #: guarded by self._bw_lock
        self._bw_lock = tracked_lock("cluster.handle.batch_waiters",
                                     reentrant=False)
        self._fns_shipped: set = set()      # fids this daemon holds
        self._free = _FreeCoalescer(self)
        self.runtime = None                    # bound by the backend
        # node memory-pressure level (daemon node_pressure pushes /
        # gossip at join); mirrored onto the runtime Node so pick_node
        # soft-excludes hard-pressure nodes like DRAINING ones
        self.pressure_level = "ok"

    # -- push demux -------------------------------------------------------
    def _on_push(self, method: str, msg: Dict[str, Any]) -> None:
        if method == "task_batch_done":
            # batched completion replies: many task outcomes on one frame
            self._ingest_batch(msg.get("outcomes", ()))
            return
        if method in ("task_yield", "task_stream_end", "task_stream_crash"):
            if self._stale_epoch(msg):
                _count_fenced("epoch")
                return
            with self._slock:
                stream = self._streams.get(msg["task"])
            if stream is not None:
                stream.q.put(msg)
        elif method == "node_pressure":
            self._on_node_pressure(msg.get("level") or "ok")
        elif method == "actor_worker_died":
            cb = self.on_actor_worker_died
            if cb is not None:
                # OFF the reader thread: the death flow issues sync RPCs
                # on THIS client (kill_actor during _handle_actor_death);
                # running it inline would block the reader that must
                # deliver those replies — a deadlock
                threading.Thread(target=cb,
                                 args=(msg["actor_id"], msg["cause"]),
                                 daemon=True,
                                 name="actor-death-cb").start()
        elif method == "worker_log":
            # cross-process worker line surfaced on the driver
            # (reference: print_worker_logs)
            import sys

            out = sys.stderr if msg.get("stream") == "err" else sys.stdout
            print(f"(worker node={msg.get('node', '?')} "
                  f"pid={msg.get('pid')}) {msg.get('line')}", file=out)

    def _on_node_pressure(self, level: str) -> None:
        """Daemon pressure transition: mirror the level onto the
        runtime Node and invalidate the scheduler's feasibility cache
        (the DRAINING discipline — a cached pick must not keep landing
        work on a node that just went hard)."""
        self.pressure_level = level
        rt = self.runtime
        node = rt.get_node(self.node_id) if rt is not None else None
        if node is not None \
                and getattr(node, "pressure_level", "ok") != level:
            node.pressure_level = level
            from ray_tpu._private.scheduler import bump_cluster_epoch
            bump_cluster_epoch()

    def mark_dead(self) -> None:
        self.dead = True
        # fail in-flight RPCs with a typed transport error: a one-way
        # partition (daemon->driver direction lost) would otherwise wedge
        # timeout=None callers (classic submit_task) forever — the head's
        # death-mark is the deadline that lands here. The reader thread
        # stays up, so LATE result pushes still arrive and are counted
        # by the fence (kind="dead") instead of silently vanishing.
        try:
            self.client._fail_all()
        except Exception:
            pass
        with self._slock:
            streams = list(self._streams.values())
        for stream in streams:
            stream.q.put(_STREAM_DEAD)
        batch = self._batch
        if batch is not None:
            batch.stop()
        self._free.stop()
        # fail EVERY batch waiter (queued or in flight): slot[1] stays
        # None, which _submit_batched surfaces as DaemonCrashed
        with self._bw_lock:
            waiters, self._batch_waiters = self._batch_waiters, {}
        for slot in waiters.values():
            slot[0].set()
        fl = self._fast
        if fl is not None:
            fl.close()

    def _stale_epoch(self, msg: Dict[str, Any]) -> bool:
        """True when a frame's ``ep`` stamp is from a SUPERSEDED daemon
        incarnation (the head re-minted the node's epoch since). An
        unstamped frame (pre-fence daemon, or a locally-synthesized
        outcome) is never stale."""
        if not self._fence_supported:
            return False        # pre-fence daemon: nothing is stamped
        ep = msg.get("ep")
        return ep is not None and bool(self.epoch) and ep < self.epoch

    def _complete_batch_task(self, out: Dict[str, Any]) -> None:
        if self._stale_epoch(out):
            _count_fenced("epoch")
            return
        with self._bw_lock:
            task_hex = out.get("task", "")
            slot = self._batch_waiters.get(task_hex)
            if slot is not None:
                att = out.get("att")
                if att is not None and len(slot) > 2 and att != slot[2]:
                    # stale ATTEMPT: leave the slot armed for the live
                    # attempt's outcome
                    slot = None
                else:
                    self._batch_waiters.pop(task_hex, None)
            else:
                att = None
        if slot is not None:
            slot[1] = out
            slot[0].set()
        elif att is not None:
            _count_fenced("attempt")

    def _ingest_batch(self, outcomes) -> None:
        """Ingest one task_batch_done frame WITHOUT re-entering per-task
        code paths: every final outcome's waiter slot pops under ONE
        _bw_lock acquisition and every stream termination resolves its
        queue under ONE _slock acquisition; only then are the events
        set (waking the waiting task threads). Duplicate outcomes (a
        batch.result_flush retry, or out-of-order arrival of a resent
        frame) find no slot and are dropped — exactly-once per task."""
        t0 = time.perf_counter()
        if self.dead:
            # mark_dead already failed every waiter: a STAMPED frame
            # arriving now is a late delivery across a healed partition
            # (or a post-death flush) — count it so chaos campaigns can
            # assert the fence actually engaged
            for out in outcomes:
                if out.get("ep") is not None or out.get("att") is not None:
                    _count_fenced("dead")
            return
        finals = []
        streams = []
        fenced_epoch = 0
        for out in outcomes:
            if self._stale_epoch(out):
                fenced_epoch += 1
                continue
            (streams if out.get("stream") else finals).append(out)
        for _ in range(fenced_epoch):
            _count_fenced("epoch")
        woke = []
        fenced_attempt = 0
        if finals:
            with self._bw_lock:
                for out in finals:
                    task_hex = out.get("task", "")
                    slot = self._batch_waiters.get(task_hex)
                    if slot is None:
                        continue
                    att = out.get("att")
                    if att is not None and len(slot) > 2 and att != slot[2]:
                        # a retried task's slot carries the LIVE attempt
                        # number: an outcome from an earlier attempt
                        # (replayed across a heal) must not resolve it
                        fenced_attempt += 1
                        continue
                    self._batch_waiters.pop(task_hex, None)
                    slot[1] = out
                    woke.append((slot, out))
            for _ in range(fenced_attempt):
                _count_fenced("attempt")
            for slot, _out in woke:
                slot[0].set()
        if streams:
            resolved = []
            with self._slock:
                for out in streams:
                    stream = self._streams.get(out.get("task", ""))
                    if stream is not None:
                        resolved.append((stream, out))
            for stream, out in resolved:
                msg = dict(out)
                msg["m"] = msg.pop("stream")
                stream.q.put(msg)
        self._record_ingest_spans(woke, t0)

    def _record_ingest_spans(self, woke, t0: float) -> None:
        """result_ingest phase: batch frame arrival -> waiters woken
        (driver lane, traced outcomes only)."""
        try:
            traced = [(slot, out) for slot, out in woke
                      if out.get("tr")]
            if not traced:
                return
            now = time.perf_counter()
            node_hex = self.node_id.hex()
            from ray_tpu._private import worker as _worker  # lazy: circular
            rt = _worker.global_runtime()
            buf = getattr(rt, "task_events", None) if rt else None
            for _slot, out in traced:
                tr = out["tr"]
                _events.record_phase(
                    buf, task_id=out.get("task", ""), name=tr[0],
                    phase="result_ingest", dur_s=max(now - t0, 0.0),
                    node_id=node_hex, proc="driver", trace_id=tr[1],
                    start_wall=_events.wall_at(t0), end_mono=now)
        except Exception:
            pass    # observability must never fail an ingest

    def _call(self, method: str, **kw) -> Dict[str, Any]:
        if self.dead:
            raise DaemonCrashed(f"daemon {self.node_id.hex()[:8]} is dead")
        try:
            return self.client.call(method, timeout=None, **kw)
        except rpc.RpcError as e:
            self.mark_dead()
            raise DaemonCrashed(str(e))

    def profile_burst(self, duration: float = 2.0) -> List[Dict[str, Any]]:
        """Stack-sampling burst on this daemon + its pool workers; one
        record per process (the `ray-tpu profile` fan-out leg)."""
        out = self._call("profile_burst", duration=float(duration))
        return [r for r in out.get("procs", []) if isinstance(r, dict)]

    # -- wiring -----------------------------------------------------------
    def hello(self, owner_addr: Tuple[str, int], job_id, namespace: str):
        # ship the driver's import roots (the code-search-path role):
        # module-level functions pickle BY REFERENCE, so daemon workers
        # must be able to import the driver's modules (reference:
        # workers see the job's code paths via working_dir/py_modules)
        import sys as _sys
        sys_path = [p for p in _sys.path
                    if isinstance(p, str) and p
                    and os.path.isdir(p)]
        out = self._call("hello_driver", owner_addr=list(owner_addr),
                         job_id=cloudpickle.dumps(job_id),
                         namespace=namespace, sys_path=sys_path)
        self.fast_port = out.get("fast_port")
        # zero-copy object plane: the daemon's arena, attachable by
        # name when we share its host (direct puts + slot-ref'd gets)
        from ray_tpu._private.config import cfg as _cfg
        self.objectplane = (bool(out.get("objectplane"))
                            and bool(_cfg().objectplane_attach))
        self.arena_name = out.get("arena")
        self.arena_capacity = int(out.get("arena_capacity") or 0)
        # connection-scoped grant-ledger identity: the daemon charges
        # every slot grant / reservation this driver requests to it and
        # reclaims the lot if the connection dies (docs/object_plane.md
        # "crash reclamation")
        self.client_id = out.get("client_id")
        # protocol feature flag: daemons that understand push_task_batch
        # advertise it; anything older gets the per-task wire protocol
        from ray_tpu._private.config import cfg
        self._batch_supported = bool(out.get("batch")) and bool(
            cfg().submit_batch)
        # completions for classic (non-coalesced) submits may return on
        # the task_batch_done pump — independent of submit batching, so
        # a submit_batch=False driver still drains coalesced
        self._result_batch = bool(out.get("result_batch"))
        # fair-share federation: only daemons that advertised the
        # tenancy capability receive tenancy_sync job tables (old
        # daemons simply keep unconditional admission)
        self._tenancy_supported = bool(out.get("tenancy"))
        # partition fencing: epoch/attempt stamps on result frames
        self._fence_supported = bool(out.get("fence"))
        self.epoch = int(out.get("epoch") or 0)
        self._job_id = job_id
        return out

    def _submit_coalescer(self) -> Optional[_SubmitCoalescer]:
        if not self._batch_supported or self.dead:
            return None
        batch = self._batch
        if batch is not None:
            return batch
        with self._batch_lock:
            if self._batch is None and not self.dead:
                self._batch = _SubmitCoalescer(self)
            return self._batch

    def _fast_client(self):
        """Lazily-connected fast-lane client; None when unavailable."""
        if self.fast_port is None or self.dead:
            return None
        fl = self._fast
        if fl is not None and not fl.dead:
            return fl
        with self._fast_dial_lock:
            fl = self._fast
            if fl is not None and not fl.dead:
                return fl                    # a racer reconnected
            port = self.fast_port
            if port is None:
                return None
            from ray_tpu._private.fast_lane import (FastLaneClient,
                                                    lane_reconnect_policy)

            def connect():
                if _fp.ENABLED:
                    _fp.fire("cluster.lane_reconnect",
                             node=self.node_id.hex()[:8])
                return FastLaneClient(
                    (self.addr[0], port),
                    link_id=f"lane:{self.node_id.hex()}")

            try:
                fl = lane_reconnect_policy().run(
                    connect, loop="fast_lane.reconnect",
                    retry_on=(OSError, _fp.FailpointError))
            except (OSError, _fp.FailpointError):
                self.fast_port = None        # core gone: stop retrying
                return None
            self._fast = fl
            return fl

    def _lane_roundtrip(self, fl, spec, submit_fn, gen_kind_handler):
        """ONE lane submit/wait/decode cycle, shared by the plain-task
        and targeted-actor paths. Returns the (kind, value) outcome
        contract, or None when the caller should take the classic path
        (nothing ran here). ``gen_kind_handler(kind, blob)`` resolves
        the path-specific generator kind (fallback vs drained list)."""
        from ray_tpu._private import fast_lane as _fle
        try:
            rid, slot = submit_fn()
        except _fle.FastLaneError:
            # nothing was submitted: safe to fall back
            if self.dead:
                raise DaemonCrashed("daemon died (fast lane)")
            return None
        task_hex = spec.task_id.hex()
        with self._fast_lock:
            self._fast_rids[task_hex] = (fl, rid)
        try:
            kind, blob = fl.wait(slot)
        except _fle.FastLaneUnsubmitted:
            # frame never reached the wire (another submitter's flush
            # failed first): nothing ran — classic path, retry-free
            if self.dead:
                raise DaemonCrashed("daemon died (fast lane)")
            return None
        except _fle.FastLaneError as e:
            # submitted but the lane died before the outcome: the call
            # may have executed — surface as a worker crash so retry
            # accounting (max_retries) decides, never a silent re-run
            if self.dead:
                raise DaemonCrashed(str(e))
            crash = RemoteWorkerCrashed(f"fast lane died mid-call: {e}")
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
        if kind in (_fle.KIND_GEN_FALLBACK, _fle.KIND_GEN_LIST):
            return gen_kind_handler(kind, blob)
        if kind == _fle.KIND_CANCELLED:
            # same surface as a classic soft cancel: the driver maps a
            # cancelled in-flight KeyboardInterrupt to TaskCancelledError
            return ("err", KeyboardInterrupt())
        if kind == _fle.KIND_CRASHED:
            crash = RemoteWorkerCrashed(blob.decode(errors="replace"))
            # lane workers' task ids live in the C++ core: the OOM
            # check must use the lane-scoped (time-window) attribution
            crash.fast_lane = True
            raise crash
        raise RuntimeError(f"unknown fast-lane outcome kind {kind}")

    def _execute_fast(self, fl, spec, fid: str, args_blob: bytes):
        """Plain-task lane call; the daemon's Python never sees it."""
        from ray_tpu._private import fast_lane as _fle
        payload = _fle.build_payload(
            spec, fid, args_blob,
            getattr(spec, "job_id", None) or getattr(self, "_job_id", None),
            self.node_id)

        def on_gen(kind, blob):
            if kind == _fle.KIND_GEN_LIST:
                # the function body already ran and returned a live
                # generator; the worker drained it in place — replay
                # the items as a stream, never re-run the body
                return ("gen", _fle.replay_gen_list(blob))
            # legacy KIND_GEN_FALLBACK (old worker): classic re-run
            return None

        return self._lane_roundtrip(fl, spec,
                                    lambda: fl.submit(payload), on_gen)

    # -- fused task submit ------------------------------------------------
    def execute_task(self, spec, fid: str, args_blob: bytes):
        """Submit in ONE round trip: the daemon leases a pooled worker,
        pushes the task, and releases the worker itself (streams keep it
        until drained). Returns the same (kind, value) contract as
        ProcessRouter.execute_task. The explicit lease protocol
        (request_worker_lease/push_task/return_worker) stays on the wire
        for callers that pin a worker across calls.

        Plain tasks (NORMAL, single return, no runtime env, not a
        generator function) ride the fast lane — the daemon's native
        C++ core routes them to a dedicated worker with zero daemon
        Python per task."""
        import inspect as _inspect

        from ray_tpu._private.task_spec import TaskKind as _TK
        if (spec.kind == _TK.NORMAL and spec.num_returns == 1
                and not spec.runtime_env
                and not (spec.func is not None
                         and _inspect.isgeneratorfunction(spec.func))):
            fl = self._fast_client()
            if fl is not None:
                out = self._execute_fast(fl, spec, fid, args_blob)
                if out is not None:
                    return out
                # None = lane declined (submit failed, or the function
                # returned a live generator): classic path below. A
                # lane failure AFTER submit never lands here — it
                # raises RemoteWorkerCrashed so the retry accounting
                # (max_retries) applies instead of a silent re-run.
        task_hex = spec.task_id.hex()
        stream = _Stream()
        with self._slock:
            self._streams[task_hex] = stream
        out = None
        try:
            batch = self._submit_coalescer()
            if batch is not None:
                out = self._submit_batched(batch, spec, fid, args_blob)
            elif self._result_batch:
                out = self._submit_via_pump(spec, fid, args_blob)
            else:
                out = self._call(
                    "submit_task", spec=_slim_spec_blob(spec), fid=fid,
                    args=args_blob,
                    backpressure=spec.backpressure_num_objects)
            return self._decode_outcome(out, spec, stream)
        finally:
            if out_is_final(out):
                with self._slock:
                    self._streams.pop(task_hex, None)

    def _submit_batched(self, batch: _SubmitCoalescer, spec, fid: str,
                        args_blob: bytes) -> Dict[str, Any]:
        """Enqueue on the coalescer and wait for the batched completion;
        same outcome dict (and error surface) as the submit_task RPC."""
        task_hex = spec.task_id.hex()
        # slot = [wake event, outcome, live attempt number] — the third
        # element lets the ingest path fence outcomes replayed from an
        # earlier attempt across a healed partition
        slot = [threading.Event(), None, spec.attempt_number]
        with self._bw_lock:
            if self.dead:
                raise DaemonCrashed(
                    f"daemon {self.node_id.hex()[:8]} is dead")
            self._batch_waiters[task_hex] = slot
        entry = {
            "task": task_hex,
            # retries reuse the task id: the daemon's duplicate-frame
            # dedupe keys on (task, attempt) so a retry EXECUTES
            # instead of replaying the previous attempt's outcome
            "attempt": spec.attempt_number,
            "spec": _slim_spec_blob(spec),
            "fid": fid,
            "args": args_blob,
            "backpressure": spec.backpressure_num_objects,
            # opt in to coalesced stream terminations (see
            # _submit_via_pump)
            "term_pump": True,
        }
        if getattr(spec, "trace_sampled", False):
            # linger-phase span inputs — attached ONLY for sampled
            # tasks so unsampled/untraced submissions pay zero extra
            # wire bytes and no clock read (the daemon ignores them)
            entry["t_enq"] = time.perf_counter()
            entry["name"] = spec.name
            entry["trace"] = spec.trace_id
        try:
            batch.enqueue(entry)
        except DaemonCrashed:
            with self._bw_lock:
                self._batch_waiters.pop(task_hex, None)
            raise
        slot[0].wait()
        out = slot[1]
        if out is None:
            raise DaemonCrashed(
                f"daemon {self.node_id.hex()[:8]} died (batched submit)")
        if out.get("e"):
            raise rpc.RemoteError(out["e"])
        return out

    def _submit_via_pump(self, spec, fid: str,
                         args_blob: bytes) -> Dict[str, Any]:
        """Classic per-task submit_task RPC whose COMPLETION returns on
        the coalesced task_batch_done pump (daemon advertised
        ``result_batch`` at hello): the RPC reply is an immediate ack,
        so a submit_batch=False driver still gets batched completion
        delivery — same outcome dict and error surface as the coalesced
        path."""
        task_hex = spec.task_id.hex()
        slot = [threading.Event(), None, spec.attempt_number]
        with self._bw_lock:
            if self.dead:
                raise DaemonCrashed(
                    f"daemon {self.node_id.hex()[:8]} is dead")
            self._batch_waiters[task_hex] = slot
        kw: Dict[str, Any] = {
            "spec": _slim_spec_blob(spec), "fid": fid,
            "args": args_blob,
            "backpressure": spec.backpressure_num_objects,
            "task": task_hex,
            # (task, attempt) dedupe identity, like the batched path
            "attempt": spec.attempt_number,
            "via_pump": True,
            # this driver ingests stream terminations off the pump;
            # without the flag the daemon pushes them per-task (an
            # older driver on a persistent daemon would hang its
            # generator consumers waiting on coalesced terminations
            # its task_batch_done handler drops)
            "term_pump": True,
        }
        if getattr(spec, "trace_sampled", False):
            kw["name"] = spec.name
            kw["trace"] = spec.trace_id
        try:
            out = self._call("submit_task", **kw)
        except BaseException:
            with self._bw_lock:
                self._batch_waiters.pop(task_hex, None)
            raise
        if out.get("outcome") != "pump":
            # daemon ran it inline after all: the reply IS the outcome
            with self._bw_lock:
                self._batch_waiters.pop(task_hex, None)
            return out
        slot[0].wait()
        out = slot[1]
        if out is None:
            raise DaemonCrashed(
                f"daemon {self.node_id.hex()[:8]} died (pumped submit)")
        if out.get("e"):
            raise rpc.RemoteError(out["e"])
        return out

    def _decode_outcome(self, out: Dict[str, Any], spec, stream: _Stream):
        kind = out["outcome"]
        if kind == "crashed":
            # the WORKER died; the daemon itself is healthy
            raise RemoteWorkerCrashed(out["error"])
        if kind == "ok":
            return ("ok", cloudpickle.loads(out["blob"]))
        if kind == "err":
            e, tb = cloudpickle.loads(out["blob"])
            setattr(e, "_remote_traceback", tb)
            return ("err", e)
        if kind == "stored":
            return ("stored", (bytes(out["oid"]), out["nbytes"]))
        if kind == "gen":
            return ("gen", self._stream_iter(spec, stream))
        if kind == "dead":
            raise DaemonCrashed("actor worker is dead")
        raise RuntimeError(f"unknown outcome {kind!r}")

    def _stream_iter(self, spec, stream: _Stream):
        task_hex = spec.task_id.hex()
        try:
            while True:
                msg = stream.q.get()
                if msg is _STREAM_DEAD:
                    raise DaemonCrashed("daemon died mid-stream")
                op = msg["m"]
                if op == "task_yield":
                    yield cloudpickle.loads(msg["blob"])
                    try:
                        self.client.call("gen_ack", task_id=task_hex,
                                         timeout=5.0)
                    except rpc.RpcError:
                        pass
                    continue
                if op == "task_stream_crash":
                    raise RemoteWorkerCrashed(msg["error"])
                if not msg["ok"]:
                    e, tb = cloudpickle.loads(msg["blob"])
                    setattr(e, "_remote_traceback", tb)
                    raise e
                return
        finally:
            with self._slock:
                self._streams.pop(task_hex, None)

    # -- actors -----------------------------------------------------------
    def create_actor(self, spec, fid: str, args_blob: bytes):
        out = self._call("create_actor", spec=_slim_spec_blob(spec),
                         fid=fid, args=args_blob)
        kind = out["outcome"]
        if kind == "crashed":
            # the WORKER died; the daemon itself is healthy
            raise RemoteWorkerCrashed(out["error"])
        if kind == "err":
            e, tb = cloudpickle.loads(out["blob"])
            setattr(e, "_remote_traceback", tb)
            raise e
        return RemoteActorInstance(self, spec.actor_id,
                                   fast_tag=out.get("fast_tag"))

    def _call_actor_fast(self, fl, tag: int, spec, args_blob: bytes):
        """Targeted-lane actor call; returns the (kind, value) contract
        or None when the caller should take the classic path (nothing
        ran here)."""
        from ray_tpu._private import fast_lane as _fle
        payload = _fle.build_actor_payload(
            spec, args_blob,
            getattr(spec, "job_id", None) or getattr(self, "_job_id", None),
            self.node_id)

        def on_gen(kind, blob):
            # the method returned a generator: items were drained in
            # the worker (inside its context + actor lock); replay as a
            # REAL generator so the driver's streaming machinery
            # (inspect.isgenerator -> _drain_generator) engages exactly
            # like the classic path
            return ("gen", _fle.replay_gen_list(blob))

        return self._lane_roundtrip(
            fl, spec, lambda: fl.submit_targeted(tag, payload), on_gen)

    def call_actor_method(self, spec, args_blob: bytes):
        task_hex = spec.task_id.hex()
        stream = _Stream()
        with self._slock:
            self._streams[task_hex] = stream
        out = self._call("call_actor_method", spec=_slim_spec_blob(spec),
                         args=args_blob)
        return self._decode_outcome(out, spec, stream)

    def kill_actor(self, actor_id, expected: bool = True) -> None:
        try:
            self._call("kill_actor", actor_id=actor_id.hex(),
                       expected=expected)
        except DaemonCrashed:
            pass

    def cancel_task(self, task_id, force: bool) -> bool:
        task_hex = task_id.hex()
        if _fp.ENABLED:
            act = _fp.fire("cluster.cancel", task=task_hex)
            if act is _fp.DROP:
                return False        # cancel request lost in transit
        with self._fast_lock:
            entry = self._fast_rids.get(task_hex)
        if entry is not None:
            # fast-lane task: the C++ core drops it if still queued;
            # running → soft interrupt, or force → the lane worker
            # exits (surfacing as a crash, which a cancelled task maps
            # to TaskCancelledError — the classic force-kill contract).
            # The cancel goes to the CLIENT the task was submitted on:
            # after a lane death + reconnect, the new client's restarted
            # rid counter must never receive a stale rid.
            lane_client, rid = entry
            if not lane_client.dead:
                lane_client.cancel(rid, force=force)
            return True
        try:
            return self._call("cancel_task", task_id=task_hex,
                              force=force)["found"]
        except DaemonCrashed:
            return False

    # -- PG 2PC -----------------------------------------------------------
    def prepare_bundle(self, pg_id: str, index: int,
                       resources: Dict[str, float]) -> bool:
        try:
            return self._call("prepare_bundle", pg_id=pg_id, index=index,
                              resources=resources)["ok"]
        except DaemonCrashed:
            return False

    def commit_bundle(self, pg_id: str, index: int) -> bool:
        try:
            return self._call("commit_bundle", pg_id=pg_id,
                              index=index)["ok"]
        except DaemonCrashed:
            return False

    def cancel_bundle(self, pg_id: str, index: int) -> None:
        try:
            self._call("cancel_bundle", pg_id=pg_id, index=index)
        except DaemonCrashed:
            pass

    # -- object plane -----------------------------------------------------
    def _release_shm_grant(self, oid: bytes, out: Dict[str, Any]) -> None:
        """Drop the ref a get_object shm reply granted us: slot grants
        release through the local mapping (one atomic, zero RPC); the
        legacy internal-ref grant — or a slot we failed to map — falls
        back to the release_object RPC."""
        slot = out.get("slot")
        if slot is not None:
            if self.arenas.ext_release(out["shm"], slot):
                return
            try:
                self.client.call("release_object", oid=oid, slot=slot,
                                 timeout=5.0)
            except rpc.RpcError:
                pass
            return
        try:
            self.client.call("release_object", oid=oid, timeout=5.0)
        except rpc.RpcError:
            pass

    def get_object_blob(self, oid: bytes) -> Optional[bytes]:
        # slot_ok: this client understands ext-slot grants (releases
        # through the mapping, or release_object{slot} on attach
        # failure) — daemons withhold slots from clients that don't
        out = self._call("get_object", oid=oid, prefer_shm=True,
                         slot_ok=True)
        if out.get("missing"):
            return None
        if "shm" in out and out.get("shm"):
            view = self.arenas.read(out["shm"], out["capacity"],
                                    out["off"], out["size"])
            try:
                if view is not None:
                    return bytes(view)  # copy out, then release the pin
                # attach failed: re-request as bytes
                out2 = self._call("get_object", oid=oid, prefer_shm=False)
                return None if out2.get("missing") else out2["blob"]
            finally:
                self._release_shm_grant(oid, out)
        return out["blob"]

    def get_object_view(self, oid: bytes, dtype, shape):
        """Zero-copy read-only numpy view of a RAW-tier arena entry on
        the same host: the daemon grants a shared-slot ref, we map the
        range with np.frombuffer, and a finalizer drops the ref — no
        payload bytes cross any wire, no serialization at all. None →
        caller takes the blob path (remote host, attach failure, or a
        daemon without the slot protocol)."""
        import numpy as np
        out = self._call("get_object", oid=oid, prefer_shm=True,
                         slot_ok=True)
        if out.get("missing") or not out.get("shm"):
            return None
        if out.get("slot") is None:
            self._release_shm_grant(oid, out)   # legacy internal ref
            return None
        handle = self.arenas.handle(out["shm"])
        if handle is None:
            self._release_shm_grant(oid, out)
            return None
        try:
            base = handle.view_range(out["off"], out["size"])
        except Exception:
            self._release_shm_grant(oid, out)   # never pin on failure
            return None
        import weakref
        # finalizer on the BASE frombuffer array: numpy collapses base
        # chains, so a slice of the reshaped result bases on `base` —
        # releasing on the derived array's death would drop the slot
        # ref while sub-views still map the bytes
        weakref.finalize(base, _ext_release_quiet, handle, out["slot"])
        arr = base.view(np.dtype(dtype))
        if shape is not None:
            arr = arr.reshape(tuple(shape))
        return arr

    def arena_reserve(self, key: bytes, size: int
                      ) -> Optional[Dict[str, Any]]:
        """Reserve arena space for a direct put; {off, arena} or None
        (no arena / full — caller falls back to the blob RPC)."""
        try:
            out = self._call("create_object", oid=key, size=size)
        except (DaemonCrashed, rpc.RemoteError):
            return None
        if not out.get("ok"):
            return None
        return out

    def arena_seal(self, key: bytes, ref: bytes, raw,
                   nbytes: int) -> bool:
        try:
            out = self._call("seal_object", oid=key, ref=ref,
                             raw=list(raw) if raw else None,
                             nbytes=nbytes)
        except (DaemonCrashed, rpc.RemoteError):
            return False
        return bool(out.get("ok"))

    def push_object(self, oid: bytes, to_addr,
                    ref: bytes = b"") -> Dict[str, Any]:
        """Proactive push of a local object to a peer daemon (sender
        side runs the PushManager: chunked, deduped, directory-aware)."""
        return self._call("push_object", oid=oid, to_addr=list(to_addr),
                          ref=ref)

    def put_object_blob(self, oid: bytes, blob: bytes) -> None:
        out = self._call("put_object", oid=oid, blob=blob)
        if isinstance(out, dict) and out.get("backpressure"):
            from ray_tpu.exceptions import MemoryPressureError
            raise MemoryPressureError(
                f"node {self.node_id.hex()[:8]} rejected put under "
                f"{out.get('level', 'hard')} memory pressure")

    def free_objects(self, oids: List[bytes]) -> None:
        try:
            self._call("free_objects", oids=oids)
        except DaemonCrashed:
            pass

    def queue_free(self, oid: bytes) -> None:
        """Zero-ref free: coalesced (time/size-bounded) instead of one
        single-element free_objects RPC per object."""
        if not self.dead:
            self._free.queue(oid)

    def flush_frees(self) -> None:
        """Drain the free buffer NOW (worker shutdown, node drain)."""
        if not self.dead:
            self._free.flush()

    def pull_object(self, oid: bytes,
                    from_addr: Optional[Tuple[str, int]] = None,
                    priority: int = 2) -> bool:
        """priority: 0=get, 1=wait, 2=task-args (pull_manager.h:38-51).
        ``from_addr=None`` resolves via the owner's object directory."""
        out = self._call("pull_object", oid=oid,
                         from_addr=list(from_addr) if from_addr else [],
                         priority=priority)
        return out.get("ok", False)

    # -- lifecycle --------------------------------------------------------
    def stop(self) -> None:
        self.flush_frees()      # no queued free may outlive the session
        try:
            if not self.dead:
                self.client.call("daemon_stop", timeout=2.0)
        except rpc.RpcError:
            pass
        self.mark_dead()
        if self.proc is not None:
            try:
                self.proc.wait(timeout=2.0)
            except subprocess.TimeoutExpired:
                self.proc.kill()

    def sigkill(self) -> None:
        """Chaos path: hard-kill the daemon process (node failure)."""
        if self.proc is not None:
            try:
                self.proc.kill()
            except OSError:
                pass
        self.mark_dead()

    def detach(self) -> None:
        """Disconnect from a daemon we did not spawn (joined cluster):
        close the connection, leave the process running."""
        self.flush_frees()      # the daemon lives on: release its store
        self.mark_dead()
        self.client.close()


def _ext_release_quiet(handle, slot: int) -> None:
    """Finalizer for zero-copy driver-side views: drop the shared-slot
    ref through the local mapping (must never raise)."""
    try:
        handle.ext_release(slot)
    except Exception:
        pass


def out_is_final(out) -> bool:
    return out is None or out.get("outcome") != "gen"


def _slim_spec_blob(spec) -> bytes:
    """Spec metadata without the live callable/args (the daemon runs no
    user code; payloads travel as fid + args blob)."""
    import copy

    slim = copy.copy(spec)
    slim.func = None
    slim.args = ()
    slim.kwargs = {}
    slim.scheduling_strategy = "DEFAULT"
    return cloudpickle.dumps(slim)


class RemoteActorInstance:
    """Driver-side handle to an actor hosted in a daemon's worker."""

    __slots__ = ("daemon", "actor_id", "fast_tag")

    def __init__(self, daemon: DaemonHandle, actor_id,
                 fast_tag: Optional[int] = None):
        self.daemon = daemon
        self.actor_id = actor_id
        # targeted fast-lane address of the actor's dedicated worker
        # (None: classic RPC path only)
        self.fast_tag = fast_tag

    def call_actor_method(self, spec, args_blob: bytes):
        """Same (kind, value) contract as DaemonHandle's classic path;
        plain calls ride the targeted lane (per-actor FIFO in the
        native core), streaming/runtime-env calls stay classic."""
        if (self.fast_tag is not None
                and spec.num_returns not in ("streaming", "dynamic")
                and not spec.runtime_env):
            fl = self.daemon._fast_client()
            if fl is not None:
                out = self.daemon._call_actor_fast(fl, self.fast_tag,
                                                   spec, args_blob)
                if out is not None:
                    return out
        return self.daemon.call_actor_method(spec, args_blob)


class RemoteStore:
    """Store facade for a RemoteNode: values live in the daemon's object
    table; the driver keeps a metadata mirror (key, size, tier, raw
    dtype/shape) and fetches on demand. Same-host paths are zero-copy:
    large puts reserve + mmap-write + seal arena space (the payload
    never rides an RPC frame), and RAW-tier gets return read-only
    ``np.frombuffer`` views pinned by shared-slot refs."""

    def __init__(self, daemon: DaemonHandle):
        from ray_tpu.objectplane.tiers import TierAccounting
        self.daemon = daemon
        # ObjectID -> (key, nbytes, tier, raw|None)
        self._meta: Dict[Any, tuple] = {}  #: guarded by self._lock
        self._lock = tracked_lock("cluster.remote_store", reentrant=False)
        # UNCHAINED ledger: this store is a metadata MIRROR — the bytes
        # live in the daemon's arena, and the daemon already publishes
        # that occupancy to the gauge each heartbeat. Chaining here
        # would double-count every daemon-held object in federated sums.
        self.tiers = TierAccounting()
        self.stats = {"gets": 0, "puts": 0, "direct_puts": 0,
                      "zero_copy_gets": 0}

    def register_remote(self, object_id, daemon_key: bytes,
                        nbytes: int, raw=None,
                        tier: Optional[str] = None) -> None:
        from ray_tpu.objectplane.tiers import TIER_HOST
        tier = tier or TIER_HOST
        raw = tuple(raw) if raw else None
        with self._lock:
            prev = self._meta.get(object_id)
            self._meta[object_id] = (daemon_key, nbytes, tier, raw)
        if prev is None:
            self.tiers.add(tier, nbytes)

    def put(self, object_id, value, nbytes: int = 0) -> None:
        key = b"put:" + object_id.binary()
        if self._direct_put_raw(object_id, key, value):
            return
        from ray_tpu._private.device_objects import wire_dumps
        blob = wire_dumps(value)
        if self._direct_put_blob(object_id, key, blob):
            return
        from ray_tpu._private.retry import RetryPolicy
        from ray_tpu.exceptions import MemoryPressureError
        # HARD-pressure backpressure is retriable by contract: the node
        # is actively spilling/preempting its way back to capacity, so
        # ride the policy until relief instead of failing the put
        RetryPolicy.default(deadline_s=30.0).run(
            lambda: self.daemon.put_object_blob(key, blob),
            loop="put.backpressure", retry_on=(MemoryPressureError,))
        self.register_remote(object_id, key, len(blob))
        self.stats["puts"] += 1

    # -- direct put (same-host zero-RPC-payload path) --------------------
    def _direct_put_raw(self, object_id, key: bytes, value) -> bool:
        """Large contiguous numpy arrays store as RAW arena bytes: the
        payload is written through the driver's own mapping and
        consumers (driver or attached workers) frombuffer it back with
        zero serialization."""
        if not getattr(self.daemon, "objectplane", False):
            return False
        from ray_tpu.objectplane.tiers import raw_put_eligible
        raw = raw_put_eligible(value)
        if raw is None:
            return False
        return self._arena_put(object_id, key,
                               memoryview(value).cast("B"), raw)

    def _direct_put_blob(self, object_id, key: bytes,
                         blob: bytes) -> bool:
        """Large pickled payloads still skip the RPC frame: the blob is
        mmap-written in place; only reserve+seal metadata travels."""
        if not getattr(self.daemon, "objectplane", False):
            return False
        from ray_tpu._private.config import cfg
        if len(blob) < int(cfg().direct_put_min_bytes):
            return False
        return self._arena_put(object_id, key, blob, None)

    def _arena_put(self, object_id, key: bytes, payload, raw) -> bool:
        size = (payload.nbytes if isinstance(payload, memoryview)
                else len(payload))
        out = self.daemon.arena_reserve(key, size)
        if out is None:
            return False    # arena full / no native store: blob path
        if not self.daemon.arenas.write(out["arena"], out["off"],
                                        payload):
            # we cannot map the arena (different host / no native
            # build): stop attempting direct puts on this handle and
            # abort the reserve
            self.daemon.objectplane = False
            self.daemon.free_objects([key])
            return False
        if not self.daemon.arena_seal(key, object_id.binary(), raw,
                                      size):
            self.daemon.free_objects([key])
            return False
        self.register_remote(object_id, key, size, raw=raw)
        self.stats["puts"] += 1
        self.stats["direct_puts"] += 1
        return True

    def get(self, object_id):
        with self._lock:
            entry = self._meta.get(object_id)
        if entry is None:
            raise KeyError(object_id)
        key, nbytes, tier, raw = entry
        self.stats["gets"] += 1
        if raw is not None:
            # only attempt the view when the arena is actually mappable
            # (attach failures cache): a remote-host driver would
            # otherwise pay grant + release + re-request round trips
            # per get before reaching the blob path
            attachable = (self.daemon.arena_name is not None
                          and self.daemon.arenas.handle(
                              self.daemon.arena_name) is not None)
            arr = (self.daemon.get_object_view(key, raw[0], raw[1])
                   if attachable else None)
            if arr is not None:
                self.stats["zero_copy_gets"] += 1
                from ray_tpu.objectplane.tiers import count_zero_copy_get
                count_zero_copy_get()
                return arr
            # remote host / attach failure: raw bytes over RPC
            import numpy as np
            blob = self.daemon.get_object_blob(key)
            if blob is None:
                raise KeyError(object_id)
            return np.frombuffer(blob, dtype=np.dtype(raw[0])).reshape(
                tuple(raw[1]))
        blob = self.daemon.get_object_blob(key)
        if blob is None:
            raise KeyError(object_id)
        return cloudpickle.loads(blob)

    def contains(self, object_id) -> bool:
        with self._lock:
            return object_id in self._meta

    def delete(self, object_id) -> None:
        with self._lock:
            entry = self._meta.pop(object_id, None)
        if entry is None:
            return
        self.tiers.add(entry[2], -entry[1])
        if not self.daemon.dead:
            # coalesced: the zero-ref callback fires once per object,
            # but the wire sees size/time-bounded free_objects batches
            self.daemon.queue_free(entry[0])

    def object_ids(self):
        with self._lock:
            return list(self._meta)

    def nbytes_of(self, object_id) -> int:
        with self._lock:
            entry = self._meta.get(object_id)
        return entry[1] if entry else 0

    def meta_of(self, object_id) -> Tuple[bytes, int, Any]:
        """(daemon store key, nbytes, raw dtype/shape|None) — the handle
        a peer daemon needs to transfer this object directly (push
        prefetch / drain migration)."""
        with self._lock:
            key, nbytes, _tier, raw = self._meta[object_id]
        return key, nbytes, raw

    def has_daemon_key(self, daemon_key: bytes) -> bool:
        """Directory support: does this node hold the given store key?"""
        with self._lock:
            return any(e[0] == daemon_key for e in self._meta.values())

    def used_bytes(self) -> int:
        with self._lock:
            return sum(e[1] for e in self._meta.values())

    def tier_bytes(self) -> Dict[str, int]:
        """Occupancy by (host-shm | device-HBM | spilled) tier."""
        return self.tiers.snapshot()

    def close(self) -> None:
        with self._lock:
            self._meta.clear()
        self.tiers.clear()


class _OwnerHolder:
    """Pins refs created on behalf of daemon workers, keyed by borrower
    ("t:<task>" / "a:<actor>" — reference: per-task borrow tracking,
    ``reference_count.h:73``). Holds release when the borrowing task
    finishes or the actor dies, NOT only on daemon disconnect — a
    long-lived daemon must not pin dead tasks' objects."""

    def __init__(self):
        self._held: Dict[Any, List[Any]] = {}  #: guarded by self._lock
        self._lock = tracked_lock("cluster.owner_holder", reentrant=False)

    def _hold(self, task_rid, obj) -> None:
        with self._lock:
            self._held.setdefault(task_rid or "_", []).append(obj)

    def release(self, key: str) -> None:
        """Drop one borrower's holds (the dropped ObjectRefs' __del__
        cascades into refcounting — outside the lock)."""
        # GIL-atomic emptiness probe: a stale non-empty read just takes
        # the lock; a stale empty read means the hold landed after this
        # release began — the same outcome as losing the lock race.
        if not self._held:      # raylint: disable=guarded-by
            return  # empty table: the common per-task case pays no lock
        with self._lock:
            dropped = self._held.pop(key, None)
        del dropped

    def clear(self) -> None:
        with self._lock:
            held, self._held = self._held, {}
        del held

    def num_keys(self) -> int:
        with self._lock:
            return len(self._held)


class OwnerService:
    """The driver's RPC server for daemon-initiated core operations
    (CoreWorkerService direction, ``core_worker.proto:457-577``)."""

    def __init__(self, runtime):
        self.runtime = runtime
        self.holder = _OwnerHolder()

    def handle_core_op(self, conn, rid, msg):
        def run():
            from ray_tpu._private.worker_process import dispatch_core_op

            try:
                from ray_tpu._private.device_objects import wire_dumps
                kw = cloudpickle.loads(msg["payload"])
                value = dispatch_core_op(self.runtime, self.holder,
                                         msg["call"], kw, msg.get("task"))
                conn.reply(rid, ok=True, value=wire_dumps(value))
            except BaseException as e:  # noqa: BLE001 — shipped back
                try:
                    blob = cloudpickle.dumps(e)
                except Exception:
                    blob = cloudpickle.dumps(RuntimeError(repr(e)))
                conn.reply(rid, ok=False, value=blob)

        threading.Thread(target=run, daemon=True,
                         name="owner-core-op").start()
        return HOLD


class ClusterBackend:
    """Spawns + tracks the head and daemon processes for one driver.

    Head fault tolerance: the head persists KV/pubsub to sqlite in the
    session dir; a supervisor thread here respawns a crashed head on the
    SAME port with the same state file, daemons re-register themselves
    (daemon.py grace loop), and the driver's HeadClient re-dials — so a
    head SIGKILL is a blip, not a lost cluster (reference:
    ``gcs/store_client/redis_store_client.h`` + raylet resync).
    """

    HEAD_RECONNECT_S = 20.0

    def __init__(self, runtime, num_daemons: int,
                 resources_per_daemon: Dict[str, float],
                 object_store_bytes: int = 256 * 1024 * 1024):
        import tempfile
        object_store_bytes = max(object_store_bytes, 1 << 20)
        self.runtime = runtime
        self.arenas = ArenaCache()
        self._owns_cluster = True   # we spawned head+daemons; we stop them
        self.node_resources: Dict[NodeID, Dict[str, float]] = {}
        self.session_dir = tempfile.mkdtemp(prefix="ray_tpu_session_")
        self._head_state = os.path.join(self.session_dir, "head_state.db")
        self.head_proc, self._head_port = _spawn(
            "ray_tpu._private.head", ["--state-path", self._head_state])
        self.head = HeadClient(("127.0.0.1", self._head_port),
                               reconnect_window=self.HEAD_RECONNECT_S)
        self._shutting_down = False
        self._supervisor = threading.Thread(
            target=self._supervise_head, daemon=True, name="head-supervisor")
        self._supervisor.start()
        self.owner_service = OwnerService(runtime)
        self.owner_server = rpc.serve(self.owner_service).start()
        self.daemons: Dict[NodeID, DaemonHandle] = {}  #: guarded by self._lock
        self._lock = tracked_lock("cluster.backend.daemons",
                                  reentrant=False)
        import json

        head_port = self._head_port
        for _ in range(num_daemons):
            node_id = NodeID.from_random()
            proc, port = _spawn("ray_tpu._private.daemon", [
                "--head", f"127.0.0.1:{head_port}",
                "--node-id", node_id.hex(),
                "--resources", json.dumps(resources_per_daemon),
                "--object-store-bytes", str(object_store_bytes),
            ])
            handle = DaemonHandle(node_id, ("127.0.0.1", port), proc,
                                  self.arenas)
            handle.hello(self.owner_server.addr, runtime.job_id,
                         runtime.namespace)
            handle.on_actor_worker_died = self._make_actor_death_cb()
            with self._lock:
                self.daemons[node_id] = handle
        self.head.subscribe("node", self._on_node_event)
        self.start_resource_reporter()
        self.start_task_event_flusher()

    @classmethod
    def attach(cls, runtime, address: str) -> "ClusterBackend":
        """Join an EXISTING cluster (`ray-tpu start`) as a new driver:
        connect to its head, discover registered daemons, and speak the
        same wire protocol — nothing is spawned and shutdown() leaves the
        cluster running (reference: a second driver connecting to a
        `ray start` cluster, scripts.py:676)."""
        import tempfile

        self = cls.__new__(cls)
        self.runtime = runtime
        self.arenas = ArenaCache()
        self._owns_cluster = False
        self.node_resources = {}
        self.session_dir = tempfile.mkdtemp(prefix="ray_tpu_driver_")
        self._head_state = None
        host, port = address.rsplit(":", 1)
        self._head_port = int(port)
        self.head_proc = None
        self.head = HeadClient((host, self._head_port),
                               reconnect_window=cls.HEAD_RECONNECT_S)
        self._shutting_down = False
        self.owner_service = OwnerService(runtime)
        self.owner_server = rpc.serve(self.owner_service).start()
        # single-threaded construction: attach() is a constructor, the
        # reporter/subscriber threads that contend start below
        self.daemons = {}       # raylint: disable=guarded-by
        self._lock = tracked_lock("cluster.backend.daemons",
                                  reentrant=False)
        for info in self.head.list_nodes():
            if not info["alive"]:
                continue
            self._join_node(info, add_runtime_node=False)
        if not self.daemons:    # raylint: disable=guarded-by
            raise RuntimeError(
                f"cluster at {address} has no alive nodes to join")
        self.head.subscribe("node", self._on_node_event)
        self.start_resource_reporter()
        self.start_task_event_flusher()
        return self

    def describe_peers(self) -> List[str]:
        """One line per connected daemon for debug_state dumps: its
        liveness as this driver sees it."""
        out = []
        with self._lock:
            handles = list(self.daemons.values())
        for h in handles:
            out.append(f"daemon {h.node_id.hex()[:8]}: "
                       f"alive={not h.dead}")
        return out

    def start_resource_reporter(self, interval_s: float = 0.5) -> None:
        """Syncer gossip (``ray_syncer.h:83`` role): the driver is the
        scheduling authority, so it owns the true availability view —
        push it to the head periodically (and only when changed) for the
        state API / autoscaler / other drivers."""
        def loop():
            last: Dict[str, Any] = {}
            last_sent = 0.0
            while not self._shutting_down:
                time.sleep(interval_s)
                loads: Dict[str, Dict[str, float]] = {}
                with self._lock:
                    node_ids = list(self.daemons)
                for node_id in node_ids:
                    node = self.runtime.get_node(node_id)
                    if node is None or not node.alive:
                        continue
                    loads[node_id.hex()] = dict(node.ledger.available())
                # Re-send unchanged views inside the head's gossip
                # freshness window (2s): steady load must not age out
                # and let static heartbeat values take the view back.
                now = time.monotonic()
                if loads and (loads != last or now - last_sent > 1.5):
                    try:
                        self.head.report_resources(loads)
                    except rpc.RpcError:
                        continue  # lost report: retry next tick
                    last = loads  # only after a successful send
                    last_sent = now
                # fair-share federation rides the same tick: dirty
                # quota records to the head (persisted) + capable
                # daemons, and the throttled per-job usage report
                ten = getattr(self.runtime, "tenancy", None)
                if ten is not None and ten.enabled:
                    try:
                        ten.maybe_sync(self)
                    except Exception:
                        pass  # dirty records retry next tick

        threading.Thread(target=loop, daemon=True,
                         name="resource-reporter").start()

    def start_task_event_flusher(self, interval_s: float = 1.0) -> None:
        """Periodically ship NEW driver task events to the head's
        task-event store so state/timeline queries survive driver exit
        (reference: task_event_buffer.cc -> gcs_task_manager.h:94)."""
        self._task_event_cursor = 0
        flush_lock = threading.Lock()

        def flush_once() -> None:
            buf = getattr(self.runtime, "task_events", None)
            if buf is None:
                return
            # one flusher at a time: the periodic thread, shutdown's
            # final flush, and direct test calls share the cursor — a
            # concurrent read-push-advance would double-store the batch
            # (the head has no dedupe)
            with flush_lock:
                batch = buf.events_after(self._task_event_cursor)
                if not batch:
                    return
                job_hex = self.runtime.job_id.hex()
                for ev in batch:
                    ev.setdefault("job_id", job_hex)
                if _fp.ENABLED:
                    try:
                        # drop/error arm = flush lost in transit; the
                        # un-advanced cursor re-sends next interval
                        if _fp.fire("trace.flush",
                                    n=len(batch)) is _fp.DROP:
                            return
                    except Exception:
                        return
                try:
                    self.head.task_events_push(batch)
                except rpc.RpcError:
                    return   # lost flush: retry with same cursor
                self._task_event_cursor = batch[-1]["seq"]

        self._flush_task_events = flush_once

        def loop():
            while not self._shutting_down:
                time.sleep(interval_s)
                flush_once()

        threading.Thread(target=loop, daemon=True,
                         name="task-event-flusher").start()

    def _supervise_head(self) -> None:
        """Respawn a crashed head on the same port with the same state."""
        while not self._shutting_down:
            time.sleep(0.25)
            if self._shutting_down or self.head_proc.poll() is None:
                continue
            if _fp.ENABLED:
                try:
                    # delay arm extends the outage window; ANY error
                    # arm simulates a failed respawn attempt (next
                    # tick retries, like a lingering TIME_WAIT port) —
                    # an escape here would kill the supervisor thread
                    # and permanently disable head respawn
                    _fp.fire("head.respawn")
                except Exception:  # noqa: BLE001 — injected faults
                    continue
            try:
                proc, _ = _spawn(
                    "ray_tpu._private.head",
                    ["--state-path", self._head_state,
                     "--port", str(self._head_port)])
            except (RuntimeError, OSError):
                continue  # port may linger in TIME_WAIT; retry
            if self._shutting_down:
                # shutdown() won the race while we were spawning: don't
                # leak a fresh head that nothing will ever terminate
                proc.kill()
                return
            self.head_proc = proc

    def _make_actor_death_cb(self):
        def cb(actor_id_hex: str, cause: str) -> None:
            from ray_tpu._private.ids import ActorID

            try:
                self.runtime.on_actor_worker_died(
                    ActorID.from_hex(actor_id_hex), cause)
            except Exception:
                pass

        return cb

    def _join_node(self, info: Dict[str, Any],
                   add_runtime_node: bool) -> Optional[DaemonHandle]:
        """ONE node-join sequence, shared by attach() (initial sweep)
        and the mid-session 'added' event (autoscaler provisioning,
        `ray-tpu up` extension): connect, hello, wire callbacks, and
        replay driver-wide settings the daemon missed (memory limit)."""
        try:
            node_id = NodeID.from_hex(info["node_id"])
        except (KeyError, ValueError):
            return None
        with self._lock:
            if node_id in self.daemons or self._shutting_down:
                existing = self.daemons.get(node_id)
                if existing is not None:
                    # re-registered daemon (healed partition / head
                    # restart): adopt the head-minted epoch so stale
                    # frames still queued on the OLD connection are
                    # fenced, not double-observed
                    ep = int(info.get("epoch") or 0)
                    if ep > existing.epoch:
                        existing.epoch = ep
                return None
        try:
            handle = DaemonHandle(node_id, tuple(info["addr"]), None,
                                  self.arenas)
            handle.hello(self.owner_server.addr, self.runtime.job_id,
                         self.runtime.namespace)
        except (OSError, rpc.RpcError, DaemonCrashed, KeyError):
            return None    # raced its death; the death event follows
        handle.on_actor_worker_died = self._make_actor_death_cb()
        with self._lock:
            if node_id in self.daemons:         # concurrent add race
                handle.detach()
                return None
            self.daemons[node_id] = handle
        self.node_resources[node_id] = dict(info["resources"])
        # a limit set BEFORE this node joined must police it too
        mon = getattr(self.runtime, "memory_monitor", None)
        if mon is not None and getattr(mon, "_explicit_limit", None):
            try:
                handle.client.call("set_memory_limit",
                                   limit=mon._explicit_limit,
                                   timeout=5.0)
            except Exception:
                pass
        if add_runtime_node:
            node = self.runtime.add_remote_node(handle,
                                                dict(info["resources"]))
            if info.get("draining"):
                # joined mid-drain (e.g. we subscribed after the drain
                # event): start migration with the remaining window
                self.runtime.begin_node_drain(
                    node, float(info.get("drain_deadline_s") or 0.0),
                    info.get("drain_reason") or "drain")
            # joined while the node was already pressured (we missed
            # the node_pressure push): the gossip row carries the level
            level = (info.get("gossip_load") or {}).get("pressure")
            if level and level != "ok":
                handle._on_node_pressure(level)
        return handle

    def _on_node_event(self, event: Dict[str, Any]) -> None:
        kind = event.get("kind")
        if kind == "added":
            self._join_node(event.get("node") or {},
                            add_runtime_node=True)
            return
        if kind == "drain":
            # Graceful drain announced (self-announced preemption, or
            # another driver / the CLI): start proactive migration.
            # begin_node_drain is idempotent, so the initiating driver's
            # own direct call and this event coexist.
            try:
                node = self.runtime.get_node(
                    NodeID.from_hex(event["node_id"]))
            except (KeyError, ValueError):
                return
            if node is not None:
                self.runtime.begin_node_drain(
                    node, float(event.get("deadline_s") or 0.0),
                    event.get("reason") or "drain")
            return
        if kind != "death":
            return
        node_id = NodeID.from_hex(event["node_id"])
        with self._lock:
            handle = self.daemons.get(node_id)
        if handle is None:
            return
        # Do NOT skip when handle.dead is already set: an in-flight RPC
        # failure marks the handle dead without running the node-death
        # flow, and losing that race must not lose the actor restarts —
        # remove_node below is a no-op if the runtime already removed it.
        handle.mark_dead()
        # Route through the runtime's node-death flow (lost objects,
        # task retries, actor restarts).
        node = self.runtime.get_node(node_id)
        if node is not None:
            if event.get("drain_expired"):
                # the HEAD's deadline escalation beat the driver's own
                # timer (exactly-once accounting lives in the runtime)
                self.runtime.count_drain_escalation(node)
            try:
                self.runtime.remove_node(node, _from_cluster=True)
            except Exception:
                pass

    def report_daemon_dead(self, handle: DaemonHandle, reason: str) -> None:
        handle.mark_dead()
        try:
            self.head.mark_node_dead(handle.node_id.hex(), reason)
        except rpc.RpcError:
            pass

    def shutdown(self) -> None:
        # final task-event flush: post-mortem queries against a shared
        # (persistent) head see the driver's full history
        flush = getattr(self, "_flush_task_events", None)
        if flush is not None:
            try:
                flush()
            except Exception:
                pass
        self._shutting_down = True
        with self._lock:
            daemons = list(self.daemons.values())
            self.daemons.clear()
        for handle in daemons:
            if self._owns_cluster:
                handle.stop()
            else:       # joined cluster: just disconnect, don't kill
                handle.detach()
        if self._owns_cluster:
            try:
                self.head.stop_head()
            except Exception:
                pass
        self.head.close()
        if self.head_proc is not None and self._owns_cluster:
            try:
                self.head_proc.wait(timeout=2.0)
            except subprocess.TimeoutExpired:
                self.head_proc.kill()
        self.owner_server.stop()
        self.arenas.close()
        import shutil

        shutil.rmtree(self.session_dir, ignore_errors=True)
