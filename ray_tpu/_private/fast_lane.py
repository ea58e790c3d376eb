"""Fast lane: driver->C++ core->worker task path (zero daemon Python).

The native daemon core (``native/daemon_core.cc``) is the raylet-style
C++ engine for the per-task hot loop — lease a free worker, forward the
payload, pump the outcome back (reference:
``src/ray/raylet/node_manager.cc`` HandleRequestWorkerLease +
``raylet/local_task_manager.h`` dispatch). This module is everything
that speaks its wire protocol from Python:

- :class:`CoreHandle` — daemon side: start/stop the in-process C++
  event loop via ctypes.
- :class:`FastLaneClient` — driver side: submit plain tasks straight to
  the core (one frame out, one frame in; the Python daemon never sees
  them).
- :func:`worker_fast_lane_start` — worker side: a lane thread reading
  EXEC frames plus ONE persistent exec thread (no per-task thread
  spawn), replying RESULT frames.

Task payloads are msgpack maps (ids as raw bytes); results are the same
cloudpickle blobs the classic path ships. Only plain NORMAL tasks ride
the lane — actors, generators, runtime-env tasks keep the classic
daemon path, which stays the policy/compat surface.
"""

from __future__ import annotations

import ctypes
import itertools
import socket
import struct
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import msgpack

from ray_tpu._private.lock_sanitizer import tracked_lock

from ray_tpu._private import failpoints as _fp
from ray_tpu._private import netchaos as _nc

# ops (mirror daemon_core.cc)
OP_HELLO_WORKER = 0x01
OP_SUBMIT = 0x02
OP_RESULT = 0x03
OP_CANCEL = 0x04
OP_PING = 0x05
OP_EXEC = 0x06
OP_REPLY = 0x07
OP_CANCEL_EXEC = 0x08
OP_HELLO_TAGGED = 0x09
OP_SUBMIT_TARGETED = 0x0A
OP_HELLO_ACK = 0x0B

KIND_OK = 0x00
KIND_ERR = 0x01
KIND_CRASHED = 0x63
KIND_CANCELLED = 0x64
KIND_PONG = 0x65
# LEGACY: the function returned a live generator and the worker asked
# the driver to re-run classically. No longer emitted — re-running a
# plain function whose body already ran doubled its side effects; the
# worker now drains and ships KIND_GEN_LIST instead. Drivers keep
# decoding it (classic replay) for old workers mid-upgrade.
KIND_GEN_FALLBACK = 0x66
# the callable returned a generator: the body already ran (actor state
# mutated / plain-function side effects done), so no re-run — the
# worker drains it and ships the item LIST; the driver replays it as a
# stream
KIND_GEN_LIST = 0x67

_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")


# ONE recv implementation for every wire layer (recv_into, no per-chunk
# copies): rpc.recv_exact raises ConnectionError on EOF, which this
# module's except (ConnectionError, OSError) sites already handle.
from ray_tpu._private.rpc import SEND_CONCAT_MAX as _SEND_CONCAT_MAX
from ray_tpu._private.rpc import recv_exact as _recv_exact


def _read_frame(sock: socket.socket) -> bytearray:
    while True:
        (blen,) = _U32.unpack(_recv_exact(sock, 4))
        blob = _recv_exact(sock, blen)
        if (_nc.ENABLED
                and _nc.on_recv(sock, blen + 4) is _nc.DROP_FRAME):
            continue    # inbound lane frame lost on the simulated link
        return blob


def _frame_stream(sock: socket.socket):
    """Yield complete frames from a buffered reader: ONE recv may
    deliver many small frames (a drain storm's replies / a burst of
    EXEC frames), where per-frame recv_exact paid two syscalls per
    frame. Raises ConnectionError on EOF like recv_exact."""
    buf = bytearray()
    while True:
        off = 0
        n = len(buf)
        while n - off >= 4:
            (blen,) = _U32.unpack_from(buf, off)
            end = off + 4 + blen
            if end > n:
                break
            if (_nc.ENABLED
                    and _nc.on_recv(sock, blen + 4) is _nc.DROP_FRAME):
                off = end       # frame lost on the simulated link
                continue
            yield buf[off + 4:end]
            off = end
        if off:
            del buf[:off]
        chunk = sock.recv(1 << 18)
        if not chunk:
            raise ConnectionError("lane socket closed")
        buf += chunk


def _send_lane_frame(sock: socket.socket, wlock: threading.Lock, op: int,
                     head: bytes, payload: bytes = b"") -> None:
    """Lane frame write shared by client and worker sides: header and
    small payloads concatenate (one syscall); large payloads go as a
    second sendall under the same lock — no multi-MB concat copy."""
    prefix = _U32.pack(1 + len(head) + len(payload)) + bytes([op]) + head
    if _nc.ENABLED:
        verdict = _nc.on_send(sock, len(prefix) + len(payload))
        if verdict is _nc.DROP_FRAME:
            return      # whole frame suppressed; lane framing intact
        if verdict is _nc.DUP_FRAME:
            with wlock:
                if len(payload) <= _SEND_CONCAT_MAX:
                    sock.sendall(prefix + payload)
                else:
                    sock.sendall(prefix)
                    sock.sendall(payload)
    with wlock:
        if len(payload) <= _SEND_CONCAT_MAX:
            sock.sendall(prefix + payload)
        else:
            sock.sendall(prefix)
            sock.sendall(payload)


# ---------------------------------------------------------------------------
# daemon side: own the C++ core
# ---------------------------------------------------------------------------

class CoreHandle:
    """Loads the native core and runs it inside this process."""

    def __init__(self) -> None:
        from ray_tpu._private.native_build import load_native_so

        self._lib = load_native_so("daemon_core.cc",
                                   "libray_tpu_daemon_core.so")
        self.port: Optional[int] = None
        if self._lib is not None:
            self._lib.rtdc_start.restype = ctypes.c_int
            self._lib.rtdc_start.argtypes = [ctypes.c_char_p,
                                             ctypes.c_int]
            self._lib.rtdc_stats.argtypes = [
                ctypes.POINTER(ctypes.c_uint64)]

    def start(self, host: str = "0.0.0.0", port: int = 0) -> Optional[int]:
        if self._lib is None:
            return None
        got = self._lib.rtdc_start(host.encode(), port)
        self.port = got if got > 0 else None
        return self.port

    def stats(self) -> Dict[str, int]:
        if self._lib is None or self.port is None:
            return {}
        out = (ctypes.c_uint64 * 4)()
        self._lib.rtdc_stats(out)
        return {"queued": out[0], "inflight": out[1],
                "free_workers": out[2], "submitted": out[3]}

    def stop(self) -> None:
        if self._lib is not None and self.port is not None:
            self._lib.rtdc_stop()
            self.port = None


# ---------------------------------------------------------------------------
# driver side
# ---------------------------------------------------------------------------

class FastLaneError(Exception):
    """Transport failure on the fast lane (core/daemon died)."""


class FastLaneUnsubmitted(FastLaneError):
    """The frame provably never reached the wire (it was still staged
    when another thread's flush failed): nothing ran on the daemon, so
    callers fall back to the classic path without consuming a retry."""


# wait() sentinel for a slot whose frame was never written (distinct
# from None = lane died after the frame may have been delivered)
_UNSUBMITTED = object()


def replay_gen_list(blob: bytes):
    """Decode a KIND_GEN_LIST payload into a live generator replaying
    the worker-drained items — ONE decoder for every driver path
    (cluster handle + in-process router), so protocol changes can't
    drift between them. The body already ran worker-side; the driver's
    streaming machinery consumes the replay exactly like a classic
    stream without re-running anything."""
    import cloudpickle
    items = cloudpickle.loads(blob)

    def replay():
        yield from items

    return replay()


def lane_reconnect_policy():
    """The shared reconnect schedule for lane clients: a brief backoff
    window (the daemon may be mid-core-restart); persistent failure is
    the caller's cue to disable the lane."""
    from ray_tpu._private.retry import RetryPolicy
    return RetryPolicy(max_attempts=3, base_s=0.02, max_backoff_s=0.2)


class FastLaneClient:
    """One connection to a daemon's C++ core; thread-safe submit."""

    def __init__(self, addr: Tuple[str, int], link_id: str = "lane"):
        self._sock = socket.create_connection(addr, timeout=10.0)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock.settimeout(None)
        # default identity is the bare "lane"; the driver passes a
        # node-scoped id ("lane:<node_hex>") so a chaos spec can
        # partition ONE node's lane without touching its siblings
        _nc.register_link(self._sock, "daemon", link_id=link_id)
        self._wlock = tracked_lock("fast_lane.wire", reentrant=False)
        self._rids = itertools.count(1)
        # rid -> [Event, kind, payload]
        self._pending: Dict[int, list] = {}  #: guarded by self._plock
        self._plock = tracked_lock("fast_lane.pending", reentrant=False)
        # Flat-combining send stage: under concurrent submission the
        # lock holder drains everyone's frames with ONE sendall (a
        # drain storm paid a syscall + wire wakeup per task); an
        # uncontended send stays synchronous — same latency and error
        # surface as before.
        self._send_stage: list = []     #: guarded by self._stage_lock
        self._send_flushing = False     #: guarded by self._stage_lock
        self._stage_lock = tracked_lock("fast_lane.send_stage",
                                        reentrant=False)
        self.dead = False
        self._reader = threading.Thread(target=self._read_loop,
                                        daemon=True, name="fastlane-read")
        self._reader.start()

    # -- wire -------------------------------------------------------------
    def _send(self, op: int, head: bytes, payload: bytes = b"",
              rid: Optional[int] = None) -> None:
        prefix = (_U32.pack(1 + len(head) + len(payload))
                  + bytes([op]) + head)
        if len(payload) > _SEND_CONCAT_MAX:
            # large frame: rides the stage as a TWO-PART entry so the
            # flusher writes it in FIFO position without a multi-MB
            # concat copy. Bypassing the stage (the old direct write
            # under _wlock) could overtake this thread's own earlier
            # staged frame — reordering two calls to one actor.
            frame = (prefix, payload)
        else:
            frame = prefix + payload
        with self._stage_lock:
            self._send_stage.append((frame, rid))
            if self._send_flushing:
                # a flusher is active: it picks this frame up in its
                # next pass. A flush failure there resolves this slot
                # by delivery state: still-staged frames come back
                # FastLaneUnsubmitted (classic fallback, no retry),
                # written-or-partial ones as lane death (retry
                # accounting) — same contract as post-submit loss.
                return
            self._send_flushing = True
        self._drain_send_stage(frame)

    def _drain_send_stage(self, own_frame=None) -> None:
        # A send failure raises to the caller ONLY while own_frame was
        # provably never delivered: it was the sole frame of the failed
        # write (sendall raising then guarantees the daemon can't hold
        # a complete frame). Any other failure splits by delivery
        # state: frames still staged (never written) resolve their
        # slots FastLaneUnsubmitted — their submitters take the classic
        # path retry-free — while frames in the failed or an earlier
        # write may have reached the daemon, so their slots fail as
        # lane death (wait() raises "died mid-call" -> retry
        # accounting). Raising for a possibly-delivered frame would
        # make the classic fallback re-run a task the daemon may
        # already be executing.
        while True:
            with self._stage_lock:
                batch = self._send_stage
                if not batch:
                    self._send_flushing = False
                    return
                self._send_stage = []
            try:
                with self._wlock:
                    self._write_batch(batch)
            except BaseException:
                with self._stage_lock:
                    unwritten = self._send_stage
                    self._send_stage = []
                    self._send_flushing = False
                self._resolve_unsubmitted(unwritten)
                self._fail_pending()
                if len(batch) == 1 and batch[0][0] is own_frame:
                    raise
                return
            if own_frame is not None and any(
                    f is own_frame for f, _ in batch):
                own_frame = None

    def _write_batch(self, batch) -> None:
        """Write staged entries in FIFO order (caller holds _wlock):
        consecutive small frames join into one sendall; a large
        two-part entry flushes the run, then writes prefix + payload
        without ever concatenating the big payload."""
        run: list = []
        for f, _ in batch:
            if _nc.ENABLED:
                nb = (len(f[0]) + len(f[1])) if isinstance(f, tuple) \
                    else len(f)
                verdict = _nc.on_send(self._sock, nb)
                if verdict is _nc.DROP_FRAME:
                    continue    # staged frame lost on the simulated link
                if verdict is _nc.DUP_FRAME:
                    if isinstance(f, tuple):
                        run.extend(f)
                    else:
                        run.append(f)
            if isinstance(f, tuple):
                if run:
                    self._sock.sendall(
                        run[0] if len(run) == 1 else b"".join(run))
                    run = []
                self._sock.sendall(f[0])
                self._sock.sendall(f[1])
            else:
                run.append(f)
        if run:
            self._sock.sendall(run[0] if len(run) == 1 else b"".join(run))

    def _resolve_unsubmitted(self, entries) -> None:
        """Slots of never-written frames: resolve as UNSUBMITTED before
        _fail_pending sweeps the rest as died-mid-call."""
        for _, rid in entries:
            if rid is None:
                continue
            with self._plock:
                slot = self._pending.pop(rid, None)
            if slot is not None:
                slot[1] = _UNSUBMITTED
                slot[0].set()

    def _fail_pending(self) -> None:
        self.dead = True
        with self._plock:
            pending, self._pending = self._pending, {}
        for slot in pending.values():
            slot[1] = None
            slot[0].set()

    def _read_loop(self) -> None:
        try:
            for body in _frame_stream(self._sock):
                if not body or body[0] != OP_REPLY or len(body) < 10:
                    continue
                (rid,) = _U64.unpack_from(body, 1)
                kind = body[9]
                blob = body[10:]
                with self._plock:
                    slot = self._pending.pop(rid, None)
                if slot is not None:
                    slot[1] = kind
                    slot[2] = blob
                    slot[0].set()
        except (ConnectionError, OSError):
            pass
        self._fail_pending()

    # -- API --------------------------------------------------------------
    def submit(self, payload: bytes) -> Tuple[int, list]:
        """Send a task payload; returns (rid, slot) to wait on."""
        return self._submit_op(OP_SUBMIT, b"", payload)

    def submit_targeted(self, tag: int,
                        payload: bytes) -> Tuple[int, list]:
        """Send to the TAGGED worker (per-actor FIFO ordering)."""
        return self._submit_op(OP_SUBMIT_TARGETED, _U64.pack(tag),
                               payload)

    def _submit_op(self, op: int, extra: bytes,
                   payload: bytes) -> Tuple[int, list]:
        if self.dead:
            raise FastLaneError("fast lane is down")
        rid = next(self._rids)
        slot = [threading.Event(), None, None]
        with self._plock:
            self._pending[rid] = slot
        try:
            # DROP surfaces as a send failure: the lane is a stream
            # socket, so a lost frame desyncs framing — peers treat it
            # as connection loss, and the caller's classic fallback
            # stays safe (nothing was submitted)
            if _fp.ENABLED and _fp.fire("fast_lane.submit",
                                        op=op) is _fp.DROP:
                raise OSError("frame dropped by failpoint")
            self._send(op, _U64.pack(rid) + extra, payload, rid=rid)
        except Exception as e:  # noqa: BLE001 — any send-path failure
            # (socket death OR an injected error of any class) must pop
            # the slot and mark the lane dead; a narrower catch leaked
            # one pending slot per escape
            self.dead = True
            with self._plock:
                self._pending.pop(rid, None)
            raise FastLaneError(str(e))
        return rid, slot

    def wait(self, slot: list,
             timeout: Optional[float] = None) -> Tuple[int, bytes]:
        # Same loop-affinity contract as AsyncClient.call: the lane
        # reply arrives on a reader thread, but blocking the process
        # event loop here would stall every peer the loop serves —
        # fail loudly instead of deadlocking quietly.
        from ray_tpu._private import eventloop
        if eventloop.on_loop():
            raise RuntimeError(
                "FastLaneClient.wait would block the event loop; "
                "fast-lane round-trips belong on worker/caller threads")
        if not slot[0].wait(timeout):
            raise TimeoutError("fast lane reply timed out")
        if slot[1] is _UNSUBMITTED:
            raise FastLaneUnsubmitted(
                "frame never reached the wire (flush failed first)")
        if slot[1] is None:
            raise FastLaneError("fast lane died mid-call")
        return slot[1], slot[2]

    def cancel(self, rid: int, force: bool = False) -> None:
        try:
            self._send(OP_CANCEL,
                       _U64.pack(rid) + bytes([1 if force else 0]))
        except OSError:
            pass

    def ping(self, timeout: float = 5.0) -> Dict[str, int]:
        # mirrors _submit_op: a send failure must pop the pending slot
        # and mark the lane dead, not leak the slot and surface a raw
        # OSError into daemon stats paths
        if _fp.ENABLED:
            try:
                if _fp.fire("fast_lane.ping") is _fp.DROP:
                    raise OSError("ping dropped by failpoint")
            except Exception as e:  # noqa: BLE001 — any injected class
                # must mark the lane dead and surface as the typed
                # error, mirroring _submit_op's broadened catch
                self.dead = True
                raise FastLaneError(str(e))
        rid, slot = self._submit_op(OP_PING, b"", b"")
        kind, blob = self.wait(slot, timeout)
        if kind != KIND_PONG or len(blob) < 32:
            raise FastLaneError("bad pong")
        q, inf, w, done = struct.unpack("<QQQQ", blob[:32])
        return {"queued": q, "inflight": inf, "workers": w,
                "completed": done}

    def close(self) -> None:
        self.dead = True
        try:
            self._sock.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# worker side
# ---------------------------------------------------------------------------

def build_payload(spec, fid: str, args_blob: bytes, job_id,
                  node_id) -> bytes:
    """Driver-side: the msgpack task payload the worker lane decodes.
    Everything the worker's execution context needs travels here — the
    daemon's Python never synthesizes it (classic path:
    ``WorkerClient._ctx_fields``)."""
    return msgpack.packb({
        "fid": fid,
        "args": args_blob,
        "job": job_id.binary() if job_id is not None else b"",
        "task": spec.task_id.binary(),
        "node": node_id.binary() if node_id is not None else b"",
        "name": spec.name or "",
        "res": {k: float(v) for k, v in (spec.resources or {}).items()},
        "pg": (spec.placement_group_id.binary()
               if spec.placement_group_id is not None else b""),
        "pgc": bool(getattr(spec, "pg_capture", False)),
    }, use_bin_type=True)


def build_actor_payload(spec, args_blob: bytes, job_id,
                        node_id) -> bytes:
    """Driver-side payload for a TARGETED actor-method call."""
    return msgpack.packb({
        "method": spec.method_name,
        "args": args_blob,
        "job": job_id.binary() if job_id is not None else b"",
        "task": spec.task_id.binary(),
        "node": node_id.binary() if node_id is not None else b"",
        "aid": (spec.actor_id.binary()
                if spec.actor_id is not None else b""),
        "name": spec.name or "",
        "res": {k: float(v) for k, v in (spec.resources or {}).items()},
        "pg": (spec.placement_group_id.binary()
               if spec.placement_group_id is not None else b""),
        "pgc": bool(getattr(spec, "pg_capture", False)),
    }, use_bin_type=True)


# worker-side drain bound for generator-returning callables: the lane
# ships the drained items as ONE reply frame, so an unbounded (or
# infinite) generator must error out instead of wedging the lane worker
# / materializing gigabytes — true streaming belongs to the classic
# path (num_returns="streaming" or a generator function)
GEN_DRAIN_MAX_ITEMS = 100_000


def _drain_capped(gen) -> list:
    items: list = []
    for item in gen:
        items.append(item)
        if len(items) > GEN_DRAIN_MAX_ITEMS:
            gen.close()
            raise RuntimeError(
                f"fast-lane task returned a generator exceeding "
                f"{GEN_DRAIN_MAX_ITEMS} items; use "
                f"num_returns='streaming' (or a generator function) "
                f"for unbounded streams")
    return items


def worker_fast_lane_start(addr: Tuple[str, int], state,
                           tag: Optional[int] = None) -> None:
    """Connect this worker process to the core and serve EXEC frames.

    One lane thread reads frames; one persistent exec thread runs tasks
    (no per-task thread creation — at 3k tasks/s a 60us thread spawn is
    20% of the budget). CANCEL_EXEC async-raises KeyboardInterrupt into
    the exec thread, same soft-cancel contract as the classic path.

    With ``tag`` the worker registers TARGETED (per-actor lane): the
    core routes only submits addressed to this tag, strictly FIFO, and
    the exec thread runs them as ACTOR METHOD calls on
    ``state.actor_instance`` under the worker's actor lock (so classic
    streaming calls on the mp channel stay serialized with lane
    calls)."""
    import os  # noqa: F401 — force-cancel path

    sock = socket.create_connection(addr, timeout=10.0)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.settimeout(None)
    _nc.register_link(sock, "daemon", link_id="lane")
    wlock = threading.Lock()

    def send(op: int, head: bytes, payload: bytes = b"") -> None:
        _send_lane_frame(sock, wlock, op, head, payload)

    if tag is not None:
        send(OP_HELLO_TAGGED, _U64.pack(tag))
        # wait for the core's ack: only then is the tag routable, so
        # the daemon's create-actor reply (and the driver's first
        # targeted submit) cannot outrun the registration
        body = _read_frame(sock)
        if not body or body[0] != OP_HELLO_ACK:
            raise RuntimeError("targeted lane hello not acknowledged")
    else:
        send(OP_HELLO_WORKER, b"")

    import queue as _q
    tasks: "_q.Queue[Optional[Tuple[int, dict]]]" = _q.Queue()
    current = {"tid": 0}
    exec_thread_holder = {}

    # hot-path imports resolved ONCE per worker, not per task
    import inspect

    import cloudpickle

    from ray_tpu._private import runtime_context
    from ray_tpu._private.ids import (ActorID, JobID, NodeID,
                                      PlacementGroupID, TaskID)
    from ray_tpu._private.worker_process import (_current_rid, _dump_exc,
                                                 _safe_dumps)

    def run_one(tid: int, msg: dict) -> None:
        current["tid"] = tid
        _current_rid.rid = f"fl{tid}"
        try:
            ctx = {
                "job_id": (JobID(msg["job"]) if msg["job"] else None),
                "task_id": TaskID(msg["task"]),
                "node_id": (NodeID(msg["node"])
                            if msg["node"] else None),
                "actor_id": (ActorID(msg["aid"])
                             if msg.get("aid") else None),
                "resources": msg["res"],
                "task_name": msg["name"],
                "placement_group_id": (
                    PlacementGroupID(msg["pg"])
                    if msg["pg"] else None),
                "pg_capture": msg["pgc"],
            }
            gen_items = None
            token = runtime_context._set_context(**ctx)
            try:
                args, kwargs = cloudpickle.loads(msg["args"])
                if "method" in msg:
                    # targeted actor call: run on the live instance,
                    # serialized with classic-path calls by the actor
                    # lock (ordering: the core's per-tag FIFO). A
                    # generator result drains HERE — still inside the
                    # runtime context and the lock, so the body sees
                    # its actor/task context and no other method
                    # interleaves with it.
                    lock = getattr(state, "actor_lock", None)
                    method = getattr(state.actor_instance,
                                     msg["method"])
                    if lock is not None:
                        with lock:
                            result = method(*args, **kwargs)
                            if inspect.isgenerator(result):
                                gen_items = _drain_capped(result)
                    else:
                        result = method(*args, **kwargs)
                        if inspect.isgenerator(result):
                            gen_items = _drain_capped(result)
                else:
                    fn = state._fn({"fn_id": msg["fid"]})
                    result = fn(*args, **kwargs)
                    if inspect.isgenerator(result):
                        # a PLAIN function returned a live generator:
                        # its body already ran (side effects included),
                        # so the lane must NOT hand the task back for a
                        # classic re-run (KIND_GEN_FALLBACK re-executed
                        # the body). Drain here — inside the runtime
                        # context — and ship the item list; the driver
                        # replays it as a stream. Generator FUNCTIONS
                        # never ride the lane (driver eligibility), so
                        # draining only ever covers already-run bodies.
                        gen_items = _drain_capped(result)
            finally:
                runtime_context._reset_context(token)
            if gen_items is not None:
                # the body already ran (actor method or plain function
                # that returned a generator) — ship the drained items;
                # the driver replays them as a stream
                state._flush_metrics()
                current["tid"] = 0
                blob = _safe_dumps(gen_items)
                try:
                    send(OP_RESULT,
                         _U64.pack(tid) + bytes([KIND_GEN_LIST]),
                         blob)
                except BaseException:  # noqa: BLE001 — partial frame
                    raise SystemExit from None
                return
            state._flush_metrics()
            # clear BEFORE the send: once the driver sees the result a
            # late CANCEL_EXEC must become a no-op, not an async
            # interrupt landing on the next task
            current["tid"] = 0
            blob = _safe_dumps(result)
            try:
                send(OP_RESULT, _U64.pack(tid) + bytes([KIND_OK]), blob)
            except BaseException:  # noqa: BLE001 — see below
                # ANY failure mid-send (socket error, late async
                # cancel) may leave a partial frame on the wire; the
                # stream is unrecoverable — exit so the core crashes
                # the task and the daemon respawns the worker
                raise SystemExit from None
        except SystemExit:
            raise
        except BaseException as e:  # noqa: BLE001 — shipped back
            try:
                state._flush_metrics()
                current["tid"] = 0
                send(OP_RESULT, _U64.pack(tid) + bytes([KIND_ERR]),
                     _dump_exc(e))
            except BaseException:  # noqa: BLE001 — same partial-frame risk
                raise SystemExit from None
        finally:
            current["tid"] = 0
            _current_rid.rid = None

    def exec_loop() -> None:
        while True:
            try:
                item = tasks.get()
                if item is None:
                    return
                run_one(*item)
            except SystemExit:
                return
            except KeyboardInterrupt:
                # a cancel's async-raise landed outside the task body
                # (late delivery): swallow it — the lane worker must
                # survive, not die holding the core's free slot
                continue

    def lane_loop() -> None:
        try:
            for body in _frame_stream(sock):
                if not body:
                    continue
                op = body[0]
                if op == OP_EXEC and len(body) >= 9:
                    (tid,) = _U64.unpack_from(body, 1)
                    msg = msgpack.unpackb(body[9:], raw=False)
                    tasks.put((tid, msg))
                elif op == OP_CANCEL_EXEC and len(body) >= 9:
                    (tid,) = _U64.unpack_from(body, 1)
                    force = len(body) >= 10 and body[9] == 1
                    if current["tid"] == tid:
                        if force:
                            # classic force-cancel contract: kill the
                            # worker; the core reports CRASHED and the
                            # driver maps a cancelled crash to
                            # TaskCancelledError
                            os._exit(1)
                        t = exec_thread_holder.get("t")
                        if t is not None and t.is_alive():
                            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                                ctypes.c_ulong(t.ident),
                                ctypes.py_object(KeyboardInterrupt))
        except (ConnectionError, OSError):
            pass
        tasks.put(None)

    et = threading.Thread(target=exec_loop, daemon=True,
                          name="fastlane-exec")
    exec_thread_holder["t"] = et
    et.start()
    lt = threading.Thread(target=lane_loop, daemon=True,
                          name="fastlane-read")
    lt.start()
