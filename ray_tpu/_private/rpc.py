"""Typed RPC layer: length-prefixed msgpack frames over TCP.

This is the control-plane wire of the distributed runtime — the role gRPC
plays in the reference (``src/ray/rpc``, 37 protos; e.g.
``protobuf/node_manager.proto:394-494``, ``gcs_service.proto:68-860``).
Design choices, TPU-first rationale:

- The accelerator data plane NEVER rides this wire: tensors move via XLA
  collectives over ICI inside jitted programs, or via the shm object
  store between same-host processes. RPC carries control messages and
  (pickled) host-plane payloads only.
- Typed messages: every method has a declared field schema
  (``SCHEMAS``); send() validates required fields so protocol drift is
  caught at the caller, like proto field checks.
- Framing: ``u32 length | msgpack map``. msgpack handles bytes natively,
  so serialized task payloads embed without base64.

Server model (``aio.py``): ONE event loop per process owns every peer
socket; requests PIPELINE (the reference multiplexes gRPC streams the
same way). Per-connection arrival order is preserved for ordinary
handlers via a FIFO lane on a shared pool; handlers that may block mark
themselves ``@concurrent`` to run outside the lane, handlers that never
block mark themselves ``@loop_safe`` to run inline on the loop. Dispatch
is by method name to a service object (``handle_<method>``). A handler
may return ``HOLD`` to park the request (long-poll; reference
``pubsub/publisher.h:300``) and complete it later via the connection's
``reply``. This module holds the wire's shared state (schemas, errors,
markers, counters) and the two factories, ``serve`` and ``connect``.
"""

from __future__ import annotations

import socket
import struct
import threading
from typing import Any, Callable, Dict, Optional, Tuple

from ray_tpu._private import netchaos as _nc

_LEN = struct.Struct("!I")
MAX_FRAME = 1 << 31


class RpcError(Exception):
    """Transport-level failure (peer died, protocol violation)."""


class RemoteError(Exception):
    """The remote handler raised; message carries the remote repr."""


class _Hold:
    """Sentinel: handler parked the request for a deferred reply."""


HOLD = _Hold()


def concurrent(handler):
    """Mark a handler as safe to run OUTSIDE its connection's FIFO lane.

    Use for handlers that may block (e.g. a 120s object pull): they run
    directly on the dispatch pool so they cannot head-of-line-block other
    requests from the same peer. Everything unmarked keeps strict
    per-connection arrival order (actor-call ordering relies on it)."""
    handler._rpc_concurrent = True
    return handler


def loop_safe(handler):
    """Mark a handler as non-blocking: it runs INLINE
    on the event loop (parse -> handler -> reply with zero thread
    hand-offs; the reply joins the peer's coalesced write batch). The
    contract is strict — no lock that a non-loop thread holds across
    blocking work, no socket/file I/O, no pool waits; anything heavier
    must be staged to an executor by the handler itself. Ordering note:
    loop_safe frames keep arrival order among THEMSELVES (loop FIFO)
    but may run ahead of earlier lane-queued methods from the same
    peer."""
    handler._rpc_loop_safe = True
    return handler


# ---------------------------------------------------------------------------
# message schemas (the "proto file"): method -> required field names
# ---------------------------------------------------------------------------

SCHEMAS: Dict[str, Tuple[str, ...]] = {}


def declare(method: str, *fields: str) -> None:
    SCHEMAS[method] = fields


def _validate(method: str, kw: Dict[str, Any]) -> None:
    fields = SCHEMAS.get(method)
    if fields is None:
        raise RpcError(f"undeclared rpc method {method!r}")
    missing = [f for f in fields if f not in kw]
    if missing:
        raise RpcError(f"{method}: missing fields {missing}")


# ---------------------------------------------------------------------------
# wire instrumentation (reference: grpc server/client interceptors feeding
# the metrics agent). Hot-path updates are PLAIN dict/int ops — a rare lost
# increment under a race is acceptable for byte/frame counters; the
# per-method request counters and the inflight gauge take the small lock.
# Surfaced through the registry exposition via wire_metric_entries()
# (metrics.export_snapshot), so daemon wire stats federate to the head.
# ---------------------------------------------------------------------------

_WIRE_LOCK = threading.Lock()
_WIRE = {"bytes_sent": 0, "bytes_recv": 0,
         "frames_sent": 0, "frames_recv": 0, "inflight": 0}
_WIRE_CLIENT_REQS: Dict[str, int] = {}
_WIRE_SERVER_REQS: Dict[str, int] = {}


def wire_metric_entries() -> list:
    """This process's wire counters as metric-snapshot entries (the
    export_snapshot wire format: label keys as [[k, v], ...])."""
    with _WIRE_LOCK:
        client = dict(_WIRE_CLIENT_REQS)
        server = dict(_WIRE_SERVER_REQS)
        inflight = _WIRE["inflight"]
    out = [
        {"name": "ray_tpu_rpc_inflight", "kind": "gauge",
         "description": "RPC requests awaiting a reply in this process",
         "samples": [[[], inflight]]},
        {"name": "ray_tpu_wire_bytes_total", "kind": "counter",
         "description": "bytes moved on the control-plane wire",
         "samples": [[[["direction", "sent"]], _WIRE["bytes_sent"]],
                     [[["direction", "recv"]], _WIRE["bytes_recv"]]]},
        {"name": "ray_tpu_wire_frames_total", "kind": "counter",
         "description": "frames moved on the control-plane wire",
         "samples": [[[["direction", "sent"]], _WIRE["frames_sent"]],
                     [[["direction", "recv"]], _WIRE["frames_recv"]]]},
    ]
    if client:
        out.append({
            "name": "ray_tpu_rpc_client_requests_total", "kind": "counter",
            "description": "outbound RPC requests by method",
            "samples": [[[["method", m]], v]
                        for m, v in sorted(client.items())]})
    if server:
        out.append({
            "name": "ray_tpu_rpc_server_requests_total", "kind": "counter",
            "description": "inbound RPC requests by method",
            "samples": [[[["method", m]], v]
                        for m, v in sorted(server.items())]})
    out.extend(_nc.chaos_metric_entries())
    return out


# Above this size the `len + blob` concatenation copy costs more than a
# second write: header and payload go out as two writes (zero extra
# copy); below it, one small concat + one write wins.
SEND_CONCAT_MAX = 64 * 1024


def recv_exact(sock: socket.socket, n: int) -> bytearray:
    """Read exactly ``n`` bytes via recv_into on one preallocated
    buffer — no per-chunk bytes allocation + copy. ONE implementation
    for both wire layers (rpc + fast_lane). Raises ConnectionError on
    EOF (an OSError subclass, so existing transport-failure handling on
    both sides catches it unchanged)."""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:])
        if not r:
            raise ConnectionError("connection closed")
        got += r
    return buf


def serve(service: Any, host: str = "127.0.0.1", port: int = 0):
    """Build a server (NOT started — call .start())."""
    from ray_tpu._private.aio import AsyncServer
    return AsyncServer(service, host=host, port=port)


def connect(addr: Tuple[str, int], timeout: float = 30.0,
            on_push: Optional[Callable[[str, Dict[str, Any]], None]]
            = None):
    """Dial a server: one TCP connection, thread-safe request/reply."""
    from ray_tpu._private.aio import AsyncClient
    return AsyncClient(addr, timeout=timeout, on_push=on_push)


def wait_for_server(addr: Tuple[str, int], timeout: float = 15.0) -> None:
    from ray_tpu._private.retry import RetryPolicy

    if timeout <= 0:
        # an exhausted budget means fail NOW (RetryPolicy reads
        # deadline_s=0 as "no deadline" and would probe forever)
        raise RpcError(f"server at {addr} did not come up in {timeout}s")

    def probe() -> None:
        with socket.create_connection(addr, timeout=1.0):
            return

    try:
        RetryPolicy(base_s=0.05, max_backoff_s=0.5,
                    deadline_s=timeout).run(
            probe, loop="rpc.wait_for_server", retry_on=(OSError,))
    except OSError:
        raise RpcError(f"server at {addr} did not come up in {timeout}s")
