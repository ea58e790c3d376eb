"""Per-process asyncio event-loop core for the control plane.

Reference capability: the C++ runtime's single-threaded asio cores
(``common/asio/instrumented_io_context.h``, ``daemon_core.cc``) — one
event loop per process owns every peer socket, handlers run inline on
the loop, and anything blocking is handed to an executor. This module
is the Python analogue: ONE lazily-started loop thread per process
(``get_loop``), shared by the rpc wire (``aio.py``), the daemon's reply
pump, and the node dispatch pass.

Instrumentation (docs/observability.md):

- ``ray_tpu_event_loop_lag_seconds{proc}`` — a scheduled-vs-ran probe:
  a repeating ``call_later`` callback measures how late the loop ran it.
  Sustained lag means a callback is blocking the loop or the loop is
  CPU-saturated; this is the asio ``event_stats`` queue-lag analogue.
- ``ray_tpu_event_loop_slow_callbacks_total{proc}`` — the slow-callback
  watchdog. With ``cfg().async_debug`` on, the loop runs in asyncio
  debug mode with ``slow_callback_duration`` set to
  ``cfg().loop_slow_callback_s``; asyncio's own per-callback timing
  emits a warning through the ``asyncio`` logger for each offender and
  a logging filter counts them here. The always-on lag probe ALSO
  increments the counter when a probe arrives later than the threshold
  (a stalled loop is a slow callback even when debug mode is off).

Thread-affinity contract: callbacks scheduled on the loop are
``#: loop-only`` — thread-context code reaches them via
``loop.call_soon_threadsafe`` (raylint's loop-affinity pass checks
this). ``assert_loop()`` is the runtime sanitizer leg: under
``cfg().lock_sanitizer`` it raises when loop-only code runs off-loop.
"""

from __future__ import annotations

import asyncio
import logging
import os
import threading
from typing import Any, Callable, Optional

logger = logging.getLogger(__name__)

_LOCK = threading.Lock()
_LOOP: Optional[asyncio.AbstractEventLoop] = None
_LOOP_IDENT: Optional[int] = None   # loop thread's threading.get_ident()
_PROC = ""                          # {proc} label on loop metrics


def set_proc_label(proc: str) -> None:
    """Name this process's loop in metrics ("driver", "head",
    "daemon:<hex8>"). Cheap and idempotent; callable before or after
    the loop starts — the probe reads it per sample."""
    global _PROC
    _PROC = proc


def proc_label() -> str:
    return _PROC or f"pid:{os.getpid()}"


def running() -> bool:
    return _LOOP is not None and not _LOOP.is_closed()


def on_loop() -> bool:
    """True when the calling thread IS the loop thread."""
    return _LOOP_IDENT is not None and \
        threading.get_ident() == _LOOP_IDENT


def assert_loop(what: str = "loop-only code") -> None:
    """Loop-affinity sanitizer: raise when loop-only code executes on a
    non-loop thread. Armed by ``cfg().lock_sanitizer`` (the same knob
    that arms the lock-order sanitizer — both are debug-build checks);
    disarmed it costs one global read."""
    from ray_tpu._private.config import cfg
    if not cfg().lock_sanitizer:
        return
    if _LOOP_IDENT is not None and threading.get_ident() != _LOOP_IDENT:
        raise RuntimeError(
            f"{what} ran on thread "
            f"{threading.current_thread().name!r}, not the event loop "
            f"— hand it to the loop via call_soon_threadsafe")


class _SlowCallbackCounter(logging.Filter):
    """Counts asyncio debug-mode slow-callback warnings ("Executing
    <Handle ...> took 0.123 seconds") into the watchdog counter; the
    warning record itself still propagates to the log."""

    def filter(self, record: logging.LogRecord) -> bool:
        try:
            msg = record.getMessage()
            if "Executing" in msg and " took " in msg:
                _slow_callback_counter().inc(
                    1.0, tags={"proc": proc_label()})
        except Exception:
            pass    # observability must never break logging
        return True


def _lag_gauge():
    from ray_tpu.util.metrics import Gauge
    return Gauge("ray_tpu_event_loop_lag_seconds",
                 "scheduled-vs-ran lag of the control-plane event loop "
                 "(a repeating call_later probe; sustained lag = a "
                 "blocking callback or a saturated loop)",
                 ("proc",))


def _slow_callback_counter():
    from ray_tpu.util.metrics import Counter
    return Counter("ray_tpu_event_loop_slow_callbacks_total",
                   "event-loop callbacks that overran the "
                   "loop_slow_callback_s threshold (asyncio debug-mode "
                   "timing plus the lag-probe watchdog)",
                   ("proc",))


def _arm_probe(loop: asyncio.AbstractEventLoop) -> None:  #: loop-only
    from ray_tpu._private.config import cfg
    interval = float(cfg().loop_lag_probe_s)
    if interval <= 0:
        return
    threshold = float(cfg().loop_slow_callback_s)
    gauge = _lag_gauge()
    counter = _slow_callback_counter()
    expected = [loop.time() + interval]

    def probe() -> None:
        lag = max(0.0, loop.time() - expected[0])
        gauge.set(lag, tags={"proc": proc_label()})
        if threshold > 0 and lag > threshold:
            # the probe itself arrived late => some callback (or GIL
            # hold) blocked the loop past the threshold — count it even
            # outside debug mode, where asyncio's own timer is off
            counter.inc(1.0, tags={"proc": proc_label()})
        expected[0] = loop.time() + interval
        loop.call_later(interval, probe)

    loop.call_later(interval, probe)


def get_loop() -> asyncio.AbstractEventLoop:
    """The process-wide control-plane loop, started on first use.

    One loop per process by design (the ``daemon_core.cc`` model): the
    wire, the reply pump, and the dispatch pass share it, so their
    cross-thread hand-offs become plain same-thread calls."""
    global _LOOP
    with _LOCK:
        if _LOOP is not None and not _LOOP.is_closed():
            return _LOOP
        loop = asyncio.new_event_loop()
        from ray_tpu._private.config import cfg
        if cfg().async_debug:
            loop.set_debug(True)
            loop.slow_callback_duration = \
                max(1e-4, float(cfg().loop_slow_callback_s))
            aio_logger = logging.getLogger("asyncio")
            if not any(isinstance(f, _SlowCallbackCounter)
                       for f in aio_logger.filters):
                aio_logger.addFilter(_SlowCallbackCounter())

        def run() -> None:
            global _LOOP_IDENT
            _LOOP_IDENT = threading.get_ident()
            asyncio.set_event_loop(loop)
            try:
                loop.run_forever()
            finally:
                _LOOP_IDENT_reset()

        threading.Thread(target=run, daemon=True,
                         name="ray-tpu-loop").start()
        loop.call_soon_threadsafe(_arm_probe, loop)
        _LOOP = loop
        return _LOOP


def _LOOP_IDENT_reset() -> None:
    global _LOOP_IDENT
    _LOOP_IDENT = None


def call_threadsafe(fn: Callable[..., Any], *args: Any) -> None:
    """Schedule ``fn(*args)`` on the loop from any thread."""
    get_loop().call_soon_threadsafe(fn, *args)


def run_coro(coro, timeout: Optional[float] = None) -> Any:
    """Run a coroutine on the loop and block for its result (thread
    context only — calling this ON the loop would deadlock)."""
    if on_loop():
        raise RuntimeError("run_coro called on the event loop thread")
    return asyncio.run_coroutine_threadsafe(coro, get_loop()) \
        .result(timeout)


def shutdown_for_tests() -> None:
    """Stop and drop the singleton loop (test isolation only; the
    production loop is a daemon thread that dies with the process)."""
    global _LOOP
    with _LOCK:
        loop = _LOOP
        _LOOP = None
    if loop is None or loop.is_closed():
        return
    try:
        loop.call_soon_threadsafe(loop.stop)
    except RuntimeError:
        pass


if hasattr(os, "register_at_fork"):
    # a forked child inherits the loop's data structures but not its
    # thread: drop the singleton so the child lazily starts a fresh
    # loop instead of scheduling onto a loop nobody runs
    os.register_at_fork(after_in_child=lambda: (
        globals().__setitem__("_LOOP", None),
        globals().__setitem__("_LOOP_IDENT", None)))
