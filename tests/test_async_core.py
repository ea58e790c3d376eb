"""The control-plane core (``aio.py`` on ``eventloop.py``): tier-1 units.

Pins the core's contracts:

- one core: ``rpc.serve`` / ``rpc.connect`` build the ``aio`` classes
  and nothing else, no flag selects another, no dispatcher or pump
  thread exists, and no hello key names a core;
- loop-affinity sanitizer: ``eventloop.assert_loop`` is armed by
  ``lock_sanitizer`` and catches loop-only code running on a plain
  thread (the runtime leg of raylint's static loop-affinity pass);
- coalesced writes: a burst of frames staged on the loop leaves in ONE
  ``transport.write`` (the ``daemon_core.cc`` one-sendmsg-per-peer
  model), with large payloads skipping the join copy;
- failpoints and netchaos on the wire: the seams of
  ``docs/fault_tolerance.md`` fire, and a chaos delay is a
  ``call_later`` chain on its link, never a sleep on the shared loop
  (the frame-level cases live in ``tests/test_failpoints.py`` and
  ``tests/test_netchaos.py``);
- loop-lag watchdog: a blocked loop shows up in
  ``ray_tpu_event_loop_lag_seconds`` and the slow-callback counter;
- metric-registry pollution pin: a ``clear_registry()`` in one test
  must not silently eat metric writes from instances other modules
  cached (the order-dependent tenancy failures this PR fixed).
"""

import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu._private import eventloop
from ray_tpu._private import failpoints as fp
from ray_tpu._private import netchaos as nc
from ray_tpu._private import rpc
from ray_tpu._private.aio import AsyncClient, AsyncServer, _WriteBatcher


@pytest.fixture(autouse=True)
def _reset_chaos():
    yield
    nc.reset()
    fp.reset()


# ---------------------------------------------------------------------------
# loop-affinity sanitizer (runtime leg of raylint's loop-affinity pass)
# ---------------------------------------------------------------------------

def test_assert_loop_sanitizer(monkeypatch):
    """Armed by lock_sanitizer: loop-only code on a plain thread raises;
    the same check ON the loop passes; disarmed it is a no-op."""
    from ray_tpu._private import config
    monkeypatch.setenv("RAY_TPU_LOCK_SANITIZER", "1")
    config.reset()
    try:
        eventloop.get_loop()    # loop thread must exist to compare to
        with pytest.raises(RuntimeError, match="call_soon_threadsafe"):
            eventloop.assert_loop("test handler")

        async def on_loop_ok():
            eventloop.assert_loop("test handler")
            return True

        assert eventloop.run_coro(on_loop_ok(), timeout=5.0)
    finally:
        monkeypatch.delenv("RAY_TPU_LOCK_SANITIZER")
        config.reset()
    eventloop.assert_loop("disarmed")   # sanitizer off: no raise


# ---------------------------------------------------------------------------
# coalesced writes
# ---------------------------------------------------------------------------

class _FakeLoop:
    """Synchronous stand-in: callbacks run when the test drains them."""

    def __init__(self):
        self.pending = []

    def call_soon(self, fn, *args):
        self.pending.append((fn, args))

    def call_later(self, delay, fn, *args):
        self.pending.append((fn, args))

    def time(self):
        return 0.0

    def drain(self):
        while self.pending:
            fn, args = self.pending.pop(0)
            fn(*args)


class _FakeTransport:
    def __init__(self):
        self.chunks = []

    def is_closing(self):
        return False

    def write(self, data):
        self.chunks.append(bytes(data))


def test_write_batcher_coalesces_small_frames():
    """N frames staged in one loop iteration leave in ONE write."""
    loop, transport = _FakeLoop(), _FakeTransport()
    b = _WriteBatcher(loop, transport, object())
    blobs = [bytes([i]) * (i + 1) for i in range(5)]
    for blob in blobs:
        b.send(blob)
    assert transport.chunks == []       # nothing written until flush
    loop.drain()
    assert b.frames == 5
    assert b.writes == 1
    assert transport.chunks == [
        b"".join(rpc._LEN.pack(len(x)) + x for x in blobs)]


def test_write_batcher_big_payload_skips_join_copy():
    """A frame over SEND_CONCAT_MAX never rides the join: the pending
    small run flushes first (stream order holds), then header and
    payload go as their own writes — no multi-MB concat copy."""
    loop, transport = _FakeLoop(), _FakeTransport()
    b = _WriteBatcher(loop, transport, object())
    big = b"B" * (rpc.SEND_CONCAT_MAX + 1)
    b.send(b"s1")
    b.send(big)
    b.send(b"s2")
    loop.drain()
    assert b.frames == 3
    assert transport.chunks == [
        rpc._LEN.pack(2) + b"s1",       # small run before the big frame
        rpc._LEN.pack(len(big)),        # big header, own write
        big,                            # big payload, no copy-join
        rpc._LEN.pack(2) + b"s2"]       # trailing small run


# ---------------------------------------------------------------------------
# failpoints and netchaos on the wire
# ---------------------------------------------------------------------------

class _EchoSvc:
    def __init__(self):
        self.calls = 0

    def handle_ac_echo(self, conn, rid, msg):
        self.calls += 1
        return {"v": msg["v"]}


rpc.declare("ac_echo", "v")


def _async_pair(svc, timeout=0.5, chaos_roles=None):
    server = AsyncServer(svc).start()
    client = AsyncClient(server.addr, timeout=timeout)
    if chaos_roles:
        local_role, peer_role = chaos_roles
        nc.register_link(client._sock, peer_role, local_role=local_role)
    return server, client


def test_failpoint_client_send_drop():
    svc = _EchoSvc()
    server, client = _async_pair(svc, timeout=0.2)
    try:
        fp.activate("rpc.client.send=drop:max=1")
        with pytest.raises(rpc.RpcError):
            client.call("ac_echo", v=1)
        assert client.call("ac_echo", v=2)["v"] == 2
        assert fp.fire_count("rpc.client.send") == 1
    finally:
        client.close()
        server.stop()


def test_netchaos_latency_delays_without_blocking_loop():
    """lat=60 delays the round trip — but a SECOND connection's traffic
    must not stall behind it: the delay is a call_later chain on the
    chaotic link, not a sleep on the shared loop."""
    svc = _EchoSvc()
    server, client = _async_pair(svc, timeout=5.0,
                                 chaos_roles=("t", "svc"))
    clean = AsyncClient(server.addr, timeout=5.0)   # no chaos role
    try:
        nc.activate("t>svc=lat=120")
        done = {}

        def slow():
            t0 = time.monotonic()
            out = client.call("ac_echo", v=1)
            done["slow"] = (time.monotonic() - t0, out["v"])

        th = threading.Thread(target=slow)
        th.start()
        time.sleep(0.01)                # slow call is now in flight
        t0 = time.monotonic()
        assert clean.call("ac_echo", v=2)["v"] == 2
        clean_elapsed = time.monotonic() - t0
        th.join(timeout=5.0)
        assert done["slow"][0] >= 0.110 and done["slow"][1] == 1
        # the clean link did not pay the chaotic link's delay
        assert clean_elapsed < 0.110
    finally:
        client.close()
        clean.close()
        server.stop()


# ---------------------------------------------------------------------------
# one core: no second implementation, no flag, no thread, no hello key
# ---------------------------------------------------------------------------

def test_rpc_factories_build_the_one_implementation():
    svc = _EchoSvc()
    server = rpc.serve(svc).start()
    client = rpc.connect(server.addr, timeout=2.0)
    try:
        assert type(server) is AsyncServer
        assert type(client) is AsyncClient
        assert client.call("ac_echo", v=4)["v"] == 4
    finally:
        client.close()
        server.stop()
    for name in ("Server", "Client", "Connection"):
        assert not hasattr(rpc, name), f"rpc.{name} is back"


def test_no_flag_selects_a_core():
    from ray_tpu._private import config
    assert "async_core" not in {f.name for f in config.FLAG_DEFS}
    try:
        with pytest.raises(ValueError, match="async_core"):
            config.apply_system_config({"async_core": False})
    finally:
        config.reset()


def _core_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("dispatch-")
            or t.name == "batch-reply-pump"]


def test_no_dispatcher_and_no_pump_thread(monkeypatch):
    """The environment name that once asked for the thread-per-
    connection core is read by nothing: dispatch and the reply pump
    stay on the loop, and a task runs."""
    import ray_tpu
    from ray_tpu._private import config
    from ray_tpu._private.daemon import _BatchReplyPump
    monkeypatch.setenv("RAY_TPU_ASYNC_CORE", "0")
    config.reset()
    ray_tpu.init(num_nodes=2, resources={"CPU": 2})
    try:
        _BatchReplyPump()

        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get([f.remote(i) for i in range(8)],
                           timeout=60) == list(range(1, 9))
        assert _core_threads() == []
    finally:
        ray_tpu.shutdown()
        config.reset()


def test_hello_names_no_core_and_an_old_daemons_key_is_ignored(
        monkeypatch):
    """A daemon's hello reply carries no ``async_core`` key; a reply
    from an older daemon that still does is read as any unknown key
    is, and the driver connects and runs tasks."""
    import ray_tpu
    from ray_tpu._private.cluster import DaemonHandle
    sent = []
    real_call = DaemonHandle._call

    def call(self, method, **kw):
        out = real_call(self, method, **kw)
        if method == "hello_driver":
            sent.append(dict(out))
            out["async_core"] = False   # what an older daemon added
        return out

    monkeypatch.setattr(DaemonHandle, "_call", call)
    rt = ray_tpu.init(num_nodes=1, resources={"CPU": 2},
                      cluster="daemons")
    try:
        assert sent and all("async_core" not in out for out in sent)
        peers = rt.cluster_backend.describe_peers()
        assert len(peers) == 1 and "alive=True" in peers[0]
        assert "core=" not in peers[0]

        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get([f.remote(i) for i in range(8)],
                           timeout=60) == list(range(1, 9))
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# loop-lag gauge + slow-callback watchdog
# ---------------------------------------------------------------------------

def test_loop_lag_gauge_and_watchdog(monkeypatch):
    """Blocking the loop past loop_slow_callback_s must surface in the
    lag gauge and bump the slow-callback counter — even without asyncio
    debug mode (the always-on probe leg of the watchdog)."""
    from ray_tpu._private import config
    from ray_tpu.util import metrics
    monkeypatch.setenv("RAY_TPU_LOOP_LAG_PROBE_S", "0.02")
    monkeypatch.setenv("RAY_TPU_LOOP_SLOW_CALLBACK_S", "0.01")
    config.reset()
    eventloop.shutdown_for_tests()      # fresh loop with probe config
    try:
        eventloop.set_proc_label("lagtest")
        loop = eventloop.get_loop()
        loop.call_soon_threadsafe(time.sleep, 0.1)  # stall the loop
        deadline = time.monotonic() + 5.0
        hits = 0.0
        while time.monotonic() < deadline:
            counter = metrics.registry().get(
                "ray_tpu_event_loop_slow_callbacks_total")
            if counter is not None:
                hits = sum(v for k, v in counter.samples()
                           if ("proc", "lagtest") in k)
                if hits >= 1:
                    break
            time.sleep(0.02)
        assert hits >= 1, "stalled loop never hit the watchdog counter"
        gauge = metrics.registry().get("ray_tpu_event_loop_lag_seconds")
        assert gauge is not None and any(
            ("proc", "lagtest") in k for k, _ in gauge.samples())
    finally:
        eventloop.shutdown_for_tests()  # next get_loop: default config
        monkeypatch.delenv("RAY_TPU_LOOP_LAG_PROBE_S")
        monkeypatch.delenv("RAY_TPU_LOOP_SLOW_CALLBACK_S")
        config.reset()
        eventloop.set_proc_label("")


# ---------------------------------------------------------------------------
# metric-registry pollution pin (the order-dependent tenancy failures)
# ---------------------------------------------------------------------------

def test_metric_write_survives_registry_clear():
    """A module that cached a Metric instance before some test called
    clear_registry() must not write into the void: the next write
    re-attaches the instance to the live registry (Metric._reattach).
    This was the root cause of the order-dependent tenancy failures —
    tenancy's cached admission counter went dark after an
    observability test cleared the registry."""
    from ray_tpu.util import metrics
    c = metrics.Counter("pollution_pin_total", "pin")
    c.inc(1)
    metrics.clear_registry()            # orphans the cached instance
    try:
        c.inc(2)                        # must re-attach, not vanish
        assert "pollution_pin_total 3.0" in metrics.prometheus_text()
    finally:
        metrics.clear_registry()


@pytest.mark.slow
def test_polluting_pair_back_to_back():
    """The original failure order, pinned end to end: an observability
    test that clears the registry, then the tenancy test asserting
    ray_tpu_admission_total appears in the exposition — back to back
    in one fresh interpreter, no other tests in between."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly",
         "tests/test_observability.py::test_prometheus_label_escaping",
         "tests/test_tenancy.py::"
         "test_queued_is_delayed_never_lost_and_resumes"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
