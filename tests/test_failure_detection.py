"""Memory monitor + worker-killing policy, Serve long-poll push, and
controller crash recovery.

Reference capabilities: ``common/memory_monitor.h:52`` +
``raylet/worker_killing_policy*.h`` (OOM defense),
``serve/_private/long_poll.py:70,222`` (push config propagation),
``serve/tests/test_controller_crashes.py`` (controller recovery).
"""

import time

import pytest

import ray_tpu
from ray_tpu import exceptions as exc


def _rt():
    return ray_tpu._private.worker.global_runtime()


def test_memory_monitor_kills_and_retries(ray_start_regular, tmp_path):
    """A task that blows past the memory limit is SIGKILLed by the
    monitor and retried; with retries exhausted it fails with
    OutOfMemoryError."""
    rt = _rt()
    mon = rt.memory_monitor
    mon.interval_s = 0.1
    if not mon._thread.is_alive():
        mon.start()
    baseline = mon.usage_bytes()
    mon.set_limit(baseline + 150 * 1024 * 1024)  # headroom: ~150MB
    try:
        @ray_tpu.remote(max_retries=1)
        def hog():
            import numpy as np
            import time as _t
            blob = np.ones(400 * 1024 * 1024 // 8)  # ~400MB
            _t.sleep(20)
            return blob.sum()

        with pytest.raises(exc.OutOfMemoryError):
            ray_tpu.get(hog.remote(), timeout=60)
        # local topology: the driver's monitor killed; daemons
        # topology: each NODE's monitor polices its own workers (the
        # raylet role) and reports kills over the wire
        kills = mon.kills
        backend = getattr(rt, "cluster_backend", None)
        if backend is not None:
            for h in backend.daemons.values():
                kills += h.client.call("oom_check", task_id="",
                                       fast_lane=False)["kills"]
        assert kills >= 1
    finally:
        mon.set_limit(1 << 62)


def test_memory_monitor_policy_prefers_retriable():
    from ray_tpu._private.memory_monitor import (_Candidate,
                                                 GroupByOwnerPolicy,
                                                 RetriableFIFOPolicy)

    cands = [
        _Candidate(1, "task", task_id="a", retriable=False, started_at=5),
        _Candidate(2, "task", task_id="b", retriable=True, started_at=3),
        _Candidate(3, "task", task_id="c", retriable=True, started_at=4),
        _Candidate(4, "actor", actor_id="x", retriable=True,
                   started_at=9),
    ]
    # newest RETRIABLE TASK first, not the non-retriable or the actor
    assert RetriableFIFOPolicy().pick(cands).task_id == "c"
    # group-by-owner: the biggest owner group gets trimmed
    grouped = [
        _Candidate(1, "task", task_id="a", retriable=True, started_at=1,
                   owner_key="flood"),
        _Candidate(2, "task", task_id="b", retriable=True, started_at=2,
                   owner_key="flood"),
        _Candidate(3, "task", task_id="c", retriable=True, started_at=9,
                   owner_key="singleton"),
    ]
    assert GroupByOwnerPolicy().pick(grouped).owner_key == "flood"


def test_serve_long_poll_pushes_membership(ray_start_regular):
    """Scaling a deployment is pushed to handles without any request
    traffic (no poll-on-interval staleness window)."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=1)
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind())
    assert handle.remote("hi").result() == "hi"
    controller = ray_tpu.get_actor("serve_controller")
    ray_tpu.get(controller.set_target_replicas.remote("Echo", 2))
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        with handle._lock:
            if len(handle._replicas) == 2:
                break
        time.sleep(0.05)
    else:
        pytest.fail("membership change was never pushed to the handle")
    serve.shutdown()


def test_serve_controller_crash_recovery(ray_start_regular):
    """Kill the controller actor: a fresh incarnation restores the
    deployment specs from the KV checkpoint and RE-BINDS the still-live
    named replicas (stateful replica keeps its state)."""
    from ray_tpu import serve
    from ray_tpu.serve.api import _get_controller

    @serve.deployment(num_replicas=1)
    class Counter:
        def __init__(self):
            self.n = 0

        def __call__(self, _):
            self.n += 1
            return self.n

    handle = serve.run(Counter.bind())
    assert handle.remote(None).result() == 1
    controller = ray_tpu.get_actor("serve_controller")
    ray_tpu.kill(controller)
    time.sleep(0.3)

    # next controller touch recreates it; recovery re-binds the replica
    new_controller = _get_controller(create=True)
    from ray_tpu.serve.router import DeploymentHandle

    h2 = DeploymentHandle("Counter", new_controller)
    deadline = time.monotonic() + 20
    result = None
    while time.monotonic() < deadline:
        try:
            result = h2.remote(None).result(timeout=10)
            break
        except Exception:
            time.sleep(0.2)
    # state preserved => the SAME replica was adopted, not restarted
    assert result == 2
    serve.shutdown()


def test_serve_grpc_proxy(ray_start_regular):
    """gRPC ingress plane (reference: serve/_private/proxy.py gRPCProxy):
    a real grpc.Server routing to deployment handles."""
    from ray_tpu import serve
    from ray_tpu.serve.grpc_proxy import (GrpcServeClient,
                                          start_grpc_proxy,
                                          stop_grpc_proxy)

    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

        def shout(self, s):
            return s.upper()

    serve.run(Doubler.bind())
    port = start_grpc_proxy()
    client = GrpcServeClient(f"127.0.0.1:{port}")
    try:
        assert client.healthz()
        assert client.predict(21) == 42
        assert client.predict("abc", method="shout") == "ABC"
        assert "default" in client.list_applications()
        with pytest.raises(RuntimeError):
            client.predict(1, application="missing")
    finally:
        client.close()
        stop_grpc_proxy()
        serve.shutdown()


def test_serve_grpc_streaming(ray_start_regular):
    """gRPC server-streaming Predict: chunks arrive as the replica
    produces them (the second streaming ingress next to HTTP SSE)."""
    import time as _time

    from ray_tpu import serve
    from ray_tpu.serve.grpc_proxy import (GrpcServeClient,
                                          start_grpc_proxy,
                                          stop_grpc_proxy)

    @serve.deployment
    class Streamer:
        def __call__(self, n):
            for i in range(n):
                _time.sleep(0.2)
                yield {"i": i}

    serve.run(Streamer.bind())
    port = start_grpc_proxy()
    client = GrpcServeClient(f"127.0.0.1:{port}")
    try:
        t0 = _time.monotonic()
        chunks = []
        t_first = None
        for chunk in client.predict_stream(4):
            if t_first is None:
                t_first = _time.monotonic() - t0
            chunks.append(chunk)
        t_all = _time.monotonic() - t0
        assert [c["i"] for c in chunks] == [0, 1, 2, 3]
        assert t_first < t_all - 0.3, (t_first, t_all)
    finally:
        client.close()
        stop_grpc_proxy()
        serve.shutdown()


# ---------------------------------------------------------------------------
# Graceful drain: head membership state machine + HeadClient fixes
# ---------------------------------------------------------------------------

class _FakeConn:
    """Just enough Connection for direct HeadService handler calls."""

    def __init__(self):
        self.meta = {}
        self.replies = []

    def reply(self, rid, **kw):
        self.replies.append((rid, kw))

    def link(self, *a, **kw):
        pass


def _register(svc, node_id="n1", port=7001):
    return svc.handle_register_node(_FakeConn(), 1, {
        "node_id": node_id, "resources": {"CPU": 4.0}, "labels": {},
        "addr": ["127.0.0.1", port]})


def test_head_drain_state_and_deadline_escalation(tmp_path):
    """drain_node moves the node to alive+DRAINING (publishing
    node_drain, NOT node_death); the health loop escalates into the
    death path once the deadline expires."""
    from ray_tpu._private.head import HeadService

    svc = HeadService()
    try:
        assert _register(svc)["ok"]
        out = svc.handle_drain_node(_FakeConn(), 2, {
            "node_id": "n1", "deadline_s": 0.2, "reason": "preempt"})
        assert out["ok"]
        view = svc.handle_list_nodes(_FakeConn(), 3, {})["nodes"][0]
        assert view["alive"] and view["draining"]
        assert view["drain_reason"] == "preempt"
        events = svc._events.get("node", [])
        assert any(e.get("kind") == "drain" for e in events)
        assert not any(e.get("kind") == "death" for e in events)
        # deadline passes -> the monitor escalates to death
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            view = svc.handle_list_nodes(_FakeConn(), 4, {})["nodes"][0]
            if not view["alive"]:
                break
            time.sleep(0.05)
        assert not view["alive"]
        assert view["reason"] == "drain deadline expired"
        assert any(e.get("kind") == "death" and e.get("was_draining")
                   for e in svc._events["node"])
    finally:
        svc._stop.set()


def test_head_drain_survives_restart(tmp_path):
    """With --state-path, a drain outlives a head restart: when the
    draining daemon re-registers at the fresh head, the DRAINING state
    (and its remaining deadline) re-attaches and is re-announced."""
    from ray_tpu._private.head import HeadService

    state = str(tmp_path / "head_state.db")
    svc = HeadService(state_path=state)
    try:
        _register(svc)
        svc.handle_drain_node(_FakeConn(), 2, {
            "node_id": "n1", "deadline_s": 60.0, "reason": "maint"})
    finally:
        svc._stop.set()
    svc._store._db.close()

    svc2 = HeadService(state_path=state)    # the respawned head
    try:
        # membership is not persisted (daemons re-register themselves)
        assert svc2.handle_list_nodes(_FakeConn(), 1, {})["nodes"] == []
        out = _register(svc2)
        assert out["ok"] and out["draining"]
        view = svc2.handle_list_nodes(_FakeConn(), 2, {})["nodes"][0]
        assert view["draining"] and view["drain_reason"] == "maint"
        assert 0 < view["drain_deadline_s"] <= 60.0
        # the drain event is re-announced for (re)subscribed drivers
        assert any(e.get("kind") == "drain"
                   for e in svc2._events.get("node", []))
    finally:
        svc2._stop.set()


def test_head_rejects_zombie_reregistration():
    """A node_id we declared dead may not re-register with stale state —
    the register reply mirrors the heartbeat {"dead": True} contract."""
    from ray_tpu._private.head import HeadService

    svc = HeadService()
    try:
        _register(svc)
        svc._mark_dead("n1", "missed heartbeats")
        out = _register(svc)
        assert out.get("dead") and not out.get("ok")
        view = svc.handle_list_nodes(_FakeConn(), 9, {})["nodes"][0]
        assert not view["alive"]
        # a FRESH node id still registers fine
        assert _register(svc, node_id="n2", port=7002)["ok"]
    finally:
        svc._stop.set()


def test_head_client_publish_survives_head_restart():
    """HeadClient.publish rides the reconnect/retry path: with a
    reconnect window it survives the head process being replaced
    (the old direct client.call failed mid-restart)."""
    import threading

    from ray_tpu._private import rpc
    from ray_tpu._private.head import HeadClient, HeadService

    svc = HeadService()
    server = rpc.serve(svc, host="127.0.0.1", port=0).start()
    port = server.addr[1]
    client = HeadClient(("127.0.0.1", port), reconnect_window=10.0)
    try:
        client.publish("chan", {"n": 1})
        server.stop()
        svc._stop.set()

        svc2 = HeadService()
        holder = {}

        def restart():
            time.sleep(0.4)
            holder["server"] = rpc.serve(
                svc2, host="127.0.0.1", port=port).start()

        t = threading.Thread(target=restart, daemon=True)
        t.start()
        client.publish("chan", {"n": 2})     # rides the redial window
        t.join()
        assert svc2._events["chan"] == [{"n": 2}]
    finally:
        client.close()
        svc2._stop.set()
        holder["server"].stop()


def test_head_client_close_joins_subscriber_threads():
    """close() closes the per-channel subscriber connections and joins
    the threads (no leaked sockets / parked long-polls)."""
    from ray_tpu._private import rpc
    from ray_tpu._private.head import HeadClient, HeadService

    svc = HeadService()
    server = rpc.serve(svc, host="127.0.0.1", port=0).start()
    client = HeadClient(server.addr, reconnect_window=5.0)
    try:
        seen = []
        client.subscribe("events", seen.append)
        client.publish("events", {"x": 1})
        deadline = time.monotonic() + 5
        while not seen and time.monotonic() < deadline:
            time.sleep(0.02)
        assert seen == [{"x": 1}]
        client.close()
        for t in client._sub_threads:
            t.join(timeout=3.0)
            assert not t.is_alive(), "subscriber thread leaked"
        assert all(c.dead for c in client._sub_clients) \
            or not client._sub_clients
    finally:
        svc._stop.set()
        server.stop()
