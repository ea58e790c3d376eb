"""The control-plane wire: asyncio protocols on the per-process event loop.

The server and client that ``rpc.serve()`` / ``rpc.connect()`` build.
Reference model: the C++ runtime's asio cores (``daemon_core.cc``) —
ONE event loop per process owns every peer socket, frame parse ->
handler -> reply runs pipelined on the loop, and writes are deferred and
coalesced per peer per loop iteration (the one-sendmsg-per-peer
discipline). No per-connection reader thread, no per-frame cross-thread
wakeup; blocking handlers leave the loop (``@concurrent`` thread, FIFO
lane on the shared pool).

The wire's contract:

- Frames are ``u32 len | msgpack map``, the format every release of
  this runtime has spoken, so peers of different releases interoperate
  on the same socket.
- The ``_WIRE`` counters that back ``wire_metric_entries`` live in
  ``rpc.py`` with the schemas; this module only bumps them.
- The failpoint seams sit above the frame layer: ``rpc.client.send`` /
  ``rpc.client.recv`` around a client's frames, ``rpc.server.recv``
  before dispatch.
- netchaos sits BELOW the frame layer, and the loop must never sleep:
  the ``*_decide`` variants return ``(verdict, delay_s)`` and delays are
  served by per-connection ``call_later`` FIFO queues — a delayed frame
  holds back later frames on ITS link only, without stalling other
  peers.

Thread-affinity: everything the loop calls is ``#: loop-only``
(raylint's loop-affinity pass + ``eventloop.assert_loop`` under the
sanitizer). Handlers run on pool/dedicated threads unless marked
``@rpc.loop_safe``; their replies re-enter the loop via
``call_soon_threadsafe`` and join the peer's next write batch.
"""

from __future__ import annotations

import asyncio
import socket
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

import msgpack

from ray_tpu._private import eventloop
from ray_tpu._private import failpoints as _fp
from ray_tpu._private import netchaos as _nc
from ray_tpu._private.rpc import (
    _LEN, _WIRE, _WIRE_LOCK, _WIRE_SERVER_REQS, _WIRE_CLIENT_REQS,
    MAX_FRAME, SEND_CONCAT_MAX, RemoteError, RpcError, HOLD, _validate)


def _raw_sock(transport) -> Any:
    """The real ``socket.socket`` behind a transport. asyncio hands out
    a ``TransportSocket`` facade with ``__slots__`` (not weakref-able),
    but netchaos keys link identity in a WeakKeyDictionary — unwrap to
    the underlying socket object, which is stable for the connection's
    lifetime."""
    ts = transport.get_extra_info("socket")
    return getattr(ts, "_sock", ts)


class _WriteBatcher:
    """Per-peer deferred/coalesced outbound frames.

    ``send`` never writes: it stages the frame and arms ONE
    ``call_soon`` flush, so every frame staged by the current burst of
    loop callbacks (a drained reply batch, a pump flush, fan-out to the
    same peer) leaves in a single ``transport.write`` — the
    ``daemon_core.cc`` one-sendmsg-per-peer model. Large payloads skip
    the join copy and ride their own write; adjacency is free because
    only the loop thread writes."""

    def __init__(self, loop: asyncio.AbstractEventLoop, transport,
                 sock) -> None:
        self._loop = loop
        self._transport = transport
        self._sock = sock               # chaos-link identity
        self._stage: deque = deque()
        self._armed = False
        self._delayed: deque = deque()  # (blob, due): chaos-delayed FIFO
        self._timer: Optional[asyncio.TimerHandle] = None
        self.frames = 0                 # staged frames (test hook)
        self.writes = 0                 # flush batches (test hook)

    def send(self, blob) -> None:  #: loop-only
        n = len(blob)
        if n > MAX_FRAME:
            raise RpcError(f"frame too large: {n}")
        if _nc.ENABLED:
            # chaos below the frame layer: drop suppresses the WHOLE
            # frame, dup stages the same complete frame twice, delay
            # queues it FIFO behind earlier delayed frames on this link
            verdict, delay_s = _nc.on_send_decide(self._sock, n + 4)
            if verdict is _nc.DROP_FRAME:
                return
            copies = 2 if verdict is _nc.DUP_FRAME else 1
            if delay_s > 0 or self._delayed:
                due = self._loop.time() + delay_s
                for _ in range(copies):
                    self._delayed.append((blob, due))
                self._arm_timer()
                return
            for _ in range(copies):
                self._stage_frame(blob)
            return
        self._stage_frame(blob)

    def _stage_frame(self, blob) -> None:  #: loop-only
        _WIRE["bytes_sent"] += len(blob) + 4  # lossy-tolerant plain add
        _WIRE["frames_sent"] += 1
        self.frames += 1
        self._stage.append(blob)
        if not self._armed:
            self._armed = True
            # call_soon, not an immediate write: everything staged by
            # the rest of this loop iteration joins the same flush
            self._loop.call_soon(self._flush)

    def _arm_timer(self) -> None:  #: loop-only
        if self._timer is not None:
            return
        due = self._delayed[0][1]
        self._timer = self._loop.call_later(
            max(0.0, due - self._loop.time()), self._release_delayed)

    def _release_delayed(self) -> None:  #: loop-only
        self._timer = None
        now = self._loop.time()
        while self._delayed and self._delayed[0][1] <= now:
            self._stage_frame(self._delayed.popleft()[0])
        if self._delayed:
            self._arm_timer()

    def _flush(self) -> None:  #: loop-only
        self._armed = False
        if self._transport.is_closing():
            self._stage.clear()
            return
        small: list = []
        while self._stage:
            blob = self._stage.popleft()
            n = len(blob)
            if n > SEND_CONCAT_MAX:
                # flush the joined run first so stream order holds,
                # then hand the big payload over without a concat copy
                if small:
                    self._transport.write(b"".join(small))
                    small = []
                self._transport.write(_LEN.pack(n))
                self._transport.write(bytes(blob))
                self.writes += 1
                continue
            small.append(_LEN.pack(n))
            small.append(bytes(blob))
        if small:
            self._transport.write(b"".join(small))
            self.writes += 1

    def closing(self) -> bool:
        return self._transport.is_closing()


class _FrameProtocol(asyncio.Protocol):
    """Sans-IO framing on the loop. ``owner`` (AsyncClient or
    AsyncConnection) supplies ``_attached`` / ``_on_frame`` /
    ``_on_lost`` and a ``sock`` attribute for chaos-link identity.
    Inbound chaos delays re-schedule delivery via ``call_later`` — the
    loop never sleeps — preserving per-link FIFO."""

    def __init__(self, owner) -> None:
        self._owner = owner
        self._loop = eventloop.get_loop()
        self._buf = bytearray()
        self._in_delayed: deque = deque()  # (blob, due)
        self._in_timer: Optional[asyncio.TimerHandle] = None
        self.transport = None

    def connection_made(self, transport) -> None:  #: loop-only
        self.transport = transport
        self._owner._attached(transport)

    def data_received(self, data: bytes) -> None:  #: loop-only
        buf = self._buf
        buf += data
        off = 0
        while True:
            avail = len(buf) - off
            if avail < 4:
                break
            (n,) = _LEN.unpack_from(buf, off)
            if avail - 4 < n:
                break
            blob = bytes(buf[off + 4:off + 4 + n])
            off += 4 + n
            _WIRE["bytes_recv"] += n + 4  # lossy-tolerant plain add
            _WIRE["frames_recv"] += 1
            if _nc.ENABLED:
                verdict, delay_s = _nc.on_recv_decide(
                    self._owner.sock, n + 4)
                if verdict is _nc.DROP_FRAME:
                    continue    # inbound frame lost on the simulated link
                if delay_s > 0 or self._in_delayed:
                    self._in_delayed.append(
                        (blob, self._loop.time() + delay_s))
                    self._arm_in_timer()
                    continue
            self._deliver(blob)
        if off:
            del buf[:off]

    def _arm_in_timer(self) -> None:  #: loop-only
        if self._in_timer is not None:
            return
        due = self._in_delayed[0][1]
        self._in_timer = self._loop.call_later(
            max(0.0, due - self._loop.time()), self._release_in_delayed)

    def _release_in_delayed(self) -> None:  #: loop-only
        self._in_timer = None
        now = self._loop.time()
        while self._in_delayed and self._in_delayed[0][1] <= now:
            self._deliver(self._in_delayed.popleft()[0])
        if self._in_delayed:
            self._arm_in_timer()

    def _deliver(self, blob: bytes) -> None:  #: loop-only
        try:
            msg = msgpack.unpackb(blob, raw=False)
        except Exception:
            # protocol violation == connection death; abort tears down
            # via connection_lost
            if self.transport is not None:
                self.transport.abort()
            return
        self._owner._on_frame(msg)

    def connection_lost(self, exc) -> None:  #: loop-only
        if self._in_timer is not None:
            self._in_timer.cancel()
            self._in_timer = None
        self._owner._on_lost(exc)


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------

class AsyncClient:
    """One TCP connection to a server; thread-safe request/reply:
    blocking thread-side ``call`` / ``notify`` against a connection
    owned by the event loop. The socket is connected synchronously (a
    refused dial raises from the constructor), then handed to the
    loop."""

    def __init__(self, addr: Tuple[str, int], timeout: float = 30.0,
                 on_push: Optional[Callable[[str, Dict[str, Any]], None]]
                 = None):
        self.addr = addr
        self._sock = socket.create_connection(addr, timeout=10.0)
        self._sock.settimeout(None)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock = self._sock          # chaos-link identity
        self._id = 0                    #: guarded by self._id_lock
        self._id_lock = threading.Lock()
        self._pending: Dict[int, list] = {}  #: guarded by self._plock
        self._plock = threading.Lock()
        self._timeout = timeout
        self._on_push = on_push
        self.dead = False
        self._loop = eventloop.get_loop()
        self._proto = _FrameProtocol(self)
        self._batcher: Optional[_WriteBatcher] = None

        async def _attach():
            await self._loop.create_connection(
                lambda: self._proto, sock=self._sock)

        eventloop.run_coro(_attach(), timeout=10.0)

    def link(self, peer_role: str, link_id: str = "") -> "AsyncClient":
        _nc.register_link(self._sock, peer_role, link_id)
        return self

    # -- loop side ----------------------------------------------------
    def _attached(self, transport) -> None:  #: loop-only
        self._batcher = _WriteBatcher(self._loop, transport, self._sock)

    def _on_frame(self, msg: Dict[str, Any]) -> None:  #: loop-only
        if _fp.ENABLED and _fp.fire(
                "rpc.client.recv", method=msg.get("m", "")) is _fp.DROP:
            return      # reply/push lost in transit
        rid = msg.get("i")
        if rid is None:
            # server push (no correlation id) — inline on the loop
            if self._on_push is not None:
                try:
                    self._on_push(msg.get("m", ""), msg)
                except Exception:
                    pass
            return
        with self._plock:
            slot = self._pending.pop(rid, None)
        if slot is not None:
            slot[1] = msg
            slot[0].set()

    def _on_lost(self, exc) -> None:  #: loop-only
        self._fail_all()

    def _fail_all(self) -> None:
        self.dead = True
        with self._plock:
            pending, self._pending = self._pending, {}
        for slot in pending.values():
            slot[1] = None
            slot[0].set()

    # -- thread side --------------------------------------------------
    def _send_msg(self, msg: Dict[str, Any]) -> None:
        blob = msgpack.packb(msg, use_bin_type=True)
        if self.dead or self._batcher is None:
            raise RpcError(f"connection to {self.addr} is dead")
        if eventloop.on_loop():
            # already on the loop (push handler replying): stage direct
            self._batcher.send(blob)  # raylint: disable=loop-affinity
        else:
            self._loop.call_soon_threadsafe(self._batcher.send, blob)

    def call(self, method: str, timeout: Optional[float] = None,
             **kw) -> Dict[str, Any]:
        """Blocking request/reply — THREAD context only: waiting on the
        loop thread would deadlock the wire it is waiting on."""
        if eventloop.on_loop():
            raise RuntimeError(
                f"blocking rpc call({method!r}) on the event loop "
                f"thread — hand blocking work to an executor")
        _validate(method, kw)
        if self.dead:
            raise RpcError(f"connection to {self.addr} is dead")
        with _WIRE_LOCK:
            _WIRE_CLIENT_REQS[method] = \
                _WIRE_CLIENT_REQS.get(method, 0) + 1
            _WIRE["inflight"] += 1
        try:
            return self._call_counted(method, timeout, kw)
        finally:
            with _WIRE_LOCK:
                _WIRE["inflight"] -= 1

    def _call_counted(self, method: str, timeout: Optional[float],
                      kw: Dict[str, Any]) -> Dict[str, Any]:
        # failpoint BEFORE the pending slot exists: an error arm must
        # not leak a slot; a DROP arm skips the send so the caller times
        # out exactly like real frame loss
        dropped = (_fp.ENABLED and _fp.fire(
            "rpc.client.send", method=method) is _fp.DROP)
        if dropped and (timeout if timeout is not None
                        else self._timeout) is None:
            # a deadline-less caller (long-poll subscribers) can never
            # observe a lost frame as a timeout — surface the drop as
            # transport failure instead of wedging the waiter forever
            # (on healthy TCP, silent frame loss IS connection death)
            self._fail_all()
            raise RpcError(f"send to {self.addr} dropped by failpoint")
        with self._id_lock:
            self._id += 1
            rid = self._id
        slot = [threading.Event(), None]
        with self._plock:
            self._pending[rid] = slot
        msg = dict(kw)
        msg["m"] = method
        msg["i"] = rid
        try:
            if not dropped:
                self._send_msg(msg)
        except (OSError, RpcError):
            self._fail_all()
            raise RpcError(f"send to {self.addr} failed")
        if not slot[0].wait(timeout if timeout is not None
                            else self._timeout):
            with self._plock:
                self._pending.pop(rid, None)
            raise RpcError(f"{method} to {self.addr} timed out")
        reply = slot[1]
        if reply is None:
            raise RpcError(f"connection to {self.addr} died during "
                           f"{method}")
        if reply.get("e"):
            raise RemoteError(reply["e"])
        return reply

    def notify(self, method: str, **kw) -> None:
        """Fire-and-forget (no reply expected)."""
        _validate(method, kw)
        if (_fp.ENABLED and _fp.fire("rpc.client.send",
                                     method=method) is _fp.DROP):
            return              # notification lost in transit
        msg = dict(kw)
        msg["m"] = method
        try:
            self._send_msg(msg)
        except (OSError, RpcError):
            self._fail_all()
            raise RpcError(f"send to {self.addr} failed")

    def close(self) -> None:
        self.dead = True

        def _close() -> None:
            t = self._proto.transport
            if t is not None:
                t.abort()

        try:
            self._loop.call_soon_threadsafe(_close)
        except RuntimeError:
            pass        # loop already torn down (interpreter exit)
        self._fail_all()    # idempotent: close() means dead for callers


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

class AsyncConnection:
    """Server-side handle to one client connection, as services see
    it: ``sock`` / ``peer`` / ``meta`` / ``closed``, ``link()``,
    ``reply()``, ``reply_error()``, ``push()``. Replies may come from
    any thread (lane, @concurrent, pump); they re-enter the loop and
    join this peer's write batch."""

    def __init__(self, server: "AsyncServer"):
        self._server = server
        self._loop = server._loop
        self.sock = None
        self.peer = None
        self.meta: Dict[str, Any] = {}   # services stash identity here
        self.closed = False
        self._proto = _FrameProtocol(self)
        self._batcher: Optional[_WriteBatcher] = None
        # FIFO lane: from one peer, ordered handlers run one at a time
        # in arrival order on the shared pool, off the loop, so decoding
        # (and @concurrent handlers) pipeline ahead of a slow handler
        self._lane: deque = deque()
        self._lane_lock = threading.Lock()
        self._lane_busy = False

    def link(self, peer_role: str, link_id: str = "") -> "AsyncConnection":
        if self.sock is not None:
            _nc.register_link(self.sock, peer_role, link_id)
        return self

    # -- loop side ----------------------------------------------------
    def _attached(self, transport) -> None:  #: loop-only
        self.sock = _raw_sock(transport)
        self.peer = transport.get_extra_info("peername")
        self._batcher = _WriteBatcher(self._loop, transport, self.sock)

    def _on_frame(self, msg: Dict[str, Any]) -> None:  #: loop-only
        self._server._dispatch(self, msg)

    def _on_lost(self, exc) -> None:  #: loop-only
        self.closed = True
        self._server._conn_lost(self)

    def _abort(self) -> None:  #: loop-only
        t = self._proto.transport
        if t is not None:
            t.abort()

    # -- any-thread reply surface ------------------------------------
    def _send(self, msg: Dict[str, Any]) -> None:
        if self.closed or self._batcher is None:
            return      # the peer is gone: nobody to tell
        blob = msgpack.packb(msg, use_bin_type=True)
        if eventloop.on_loop():
            self._batcher.send(blob)  # raylint: disable=loop-affinity
        else:
            self._loop.call_soon_threadsafe(self._batcher.send, blob)

    def reply(self, rid: int, **kw) -> None:
        msg = dict(kw)
        msg["i"] = rid
        self._send(msg)

    def reply_error(self, rid: int, err: str) -> None:
        self.reply(rid, e=err)

    def push(self, method: str, **kw) -> None:
        """Server-initiated message (no correlation id)."""
        msg = dict(kw)
        msg["m"] = method
        self._send(msg)


class AsyncServer:
    """RPC server. ``service`` exposes ``handle_<method>`` callables
    with signature (conn, rid, msg) -> reply dict | HOLD. Optional
    ``on_disconnect(conn)`` on the service is called when a client
    connection drops (daemon death detection hook). The listening
    socket is bound synchronously (``addr`` valid immediately);
    ``start()`` hands it to the loop. Dispatch runs on the loop:
    ``@loop_safe`` handlers inline (parse -> handler -> reply with zero
    hand-offs), ``@concurrent`` on a dedicated thread, everything else
    through the per-connection FIFO lane on the shared pool."""

    def __init__(self, service: Any, host: str = "127.0.0.1",
                 port: int = 0):
        self.service = service
        self._sock = socket.create_server((host, port))
        self.addr = self._sock.getsockname()
        self._stop = False
        self._conns: list = []
        self._loop = eventloop.get_loop()
        self._aserver: Optional[asyncio.AbstractServer] = None
        from ray_tpu._private.thread_pool import DaemonThreadPool
        self._pool = DaemonThreadPool(128, name=f"rpc-{self.addr[1]}")

    def start(self) -> "AsyncServer":
        async def _start():
            return await self._loop.create_server(
                self._make_protocol, sock=self._sock)

        self._aserver = eventloop.run_coro(_start(), timeout=10.0)
        return self

    def _make_protocol(self):  #: loop-only
        conn = AsyncConnection(self)
        self._conns.append(conn)
        return conn._proto

    def _dispatch(self, conn: AsyncConnection,
                  msg: Dict[str, Any]) -> None:  #: loop-only
        method = msg.get("m", "")
        if _fp.ENABLED and _fp.fire(  # raylint: disable=failpoint-registry
                "rpc.server.recv", method=method) is _fp.DROP:
            return      # request lost before dispatch
        rid = msg.get("i")
        with _WIRE_LOCK:
            _WIRE_SERVER_REQS[method] = \
                _WIRE_SERVER_REQS.get(method, 0) + 1
        handler = getattr(self.service, f"handle_{method}", None)
        if handler is None:
            if rid is not None:
                conn.reply_error(rid, f"no such method {method!r}")
            return
        if getattr(handler, "_rpc_loop_safe", False):
            # declared non-blocking: run inline on the loop — the reply
            # (if immediate) joins this peer's coalesced write batch
            self._run_handler(conn, handler, rid, msg)
            return
        if getattr(handler, "_rpc_concurrent", False):
            # dedicated thread, NOT the shared pool: @concurrent
            # handlers may block for minutes (object pulls), and enough
            # of them would exhaust the pool and stall every
            # connection's lane drain
            threading.Thread(
                target=self._run_handler,
                args=(conn, handler, rid, msg), daemon=True,
                name=f"rpc-conc-{method}").start()
            return
        with conn._lane_lock:
            conn._lane.append((handler, rid, msg, time.perf_counter()))
            if conn._lane_busy:
                return
            conn._lane_busy = True
        self._pool.submit(lambda: self._drain_lane(conn))

    def _run_handler(self, conn: AsyncConnection, handler, rid,
                     msg) -> None:
        try:
            out = handler(conn, rid, msg)
            if out is HOLD or rid is None:
                return
            conn.reply(rid, **(out or {}))
        except Exception as e:  # noqa: BLE001 — shipped back; the reply
            # is inside the try because an unserializable handler return
            # raises in msgpack, not in the handler
            if rid is not None:
                conn.reply_error(rid, f"{type(e).__name__}: {e}")

    def _drain_lane(self, conn: AsyncConnection) -> None:
        while True:
            with conn._lane_lock:
                if not conn._lane:
                    conn._lane_busy = False
                    return
                handler, rid, msg, t_enq = conn._lane.popleft()
            try:    # lane dwell: time queued behind same-peer requests
                from ray_tpu.util.metrics import note_queue_dwell
                note_queue_dwell("rpc.lane",
                                 time.perf_counter() - t_enq)
            except Exception:
                pass
            try:
                self._run_handler(conn, handler, rid, msg)
            except BaseException:   # never wedge the lane
                with conn._lane_lock:
                    conn._lane_busy = False
                raise

    def _conn_lost(self, conn: AsyncConnection) -> None:  #: loop-only
        try:
            self._conns.remove(conn)
        except ValueError:
            pass
        cb = getattr(self.service, "on_disconnect", None)
        if cb is not None and not self._stop:
            # service disconnect hooks may block (reclaim, persist):
            # run them off-loop
            self._pool.submit(lambda: self._safe_disconnect(cb, conn))

    @staticmethod
    def _safe_disconnect(cb, conn) -> None:
        try:
            cb(conn)
        except Exception:
            pass

    def stop(self) -> None:
        self._stop = True

        def _close() -> None:
            if self._aserver is not None:
                self._aserver.close()
            for conn in list(self._conns):
                conn._abort()

        if self._aserver is None:
            # never started: the listening socket is still ours
            try:
                self._sock.close()
            except OSError:
                return
            return
        try:
            self._loop.call_soon_threadsafe(_close)
        except RuntimeError:
            pass        # loop already torn down (interpreter exit)
