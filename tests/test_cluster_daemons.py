"""Daemon-cluster mode: head + node-daemon OS processes on the wire.

Reference capabilities exercised end to end: separately spawnable GCS/
raylet processes with a typed RPC contract (``gcs_service.proto``,
``node_manager.proto`` lease protocol + PG 2PC), cross-process shm object
transfer (``plasma``), daemon⇄daemon object pull
(``object_manager.cc:247``), active health checking with pubsub death
broadcast (``gcs_health_check_manager.h``), and chaos recovery — SIGKILL
of a daemon process triggers task retry + actor restart WITHOUT any
test-side ``remove_node()`` call.

The same public test suites (test_core_tasks / test_actors /
test_placement_group) pass unmodified against this backend with
``RAY_TPU_CLUSTER=daemons`` (see ``tests/conftest.py``); here we cover
the cluster-only behaviors.
"""

import os
import signal
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions as exc


@pytest.fixture
def daemon_cluster():
    rt = ray_tpu.init(num_nodes=2, resources={"CPU": 4},
                      cluster="daemons")
    yield rt
    ray_tpu.shutdown()


def _daemon_handles(rt):
    return list(rt.cluster_backend.daemons.values())


def test_processes_exist(daemon_cluster):
    rt = daemon_cluster
    backend = rt.cluster_backend
    assert backend.head_proc.poll() is None  # head process alive
    handles = _daemon_handles(rt)
    assert len(handles) == 2
    for handle in handles:
        assert handle.proc.poll() is None
        out = handle.client.call("daemon_ping")
        assert out["pid"] == handle.proc.pid


def test_tasks_execute_in_daemon_workers(daemon_cluster):
    rt = daemon_cluster
    daemon_pids = {h.proc.pid for h in _daemon_handles(rt)}

    @ray_tpu.remote
    def tree():
        import os
        return os.getpid(), os.getppid()

    results = ray_tpu.get([tree.remote() for _ in range(8)])
    driver = os.getpid()
    for pid, ppid in results:
        assert pid != driver
        assert pid not in daemon_pids  # a worker, not the daemon itself


def test_large_result_via_shm_arena(daemon_cluster):
    """>100KiB results stay in the daemon's object table (C++ shm arena)
    and are fetched cross-process on get()."""

    @ray_tpu.remote
    def big():
        return np.arange(200_000)  # ~1.6MB

    ref = big.remote()
    out = ray_tpu.get(ref)
    assert out.shape == (200_000,) and out[-1] == 199_999
    # the blob lives remotely: some daemon's store holds bytes
    used = [h.client.call("daemon_stats")["store_used"]
            for h in _daemon_handles(daemon_cluster)]
    assert max(used) > 100_000


def test_inter_daemon_pull(daemon_cluster):
    """Object plane: daemon B pulls an object it doesn't have from daemon
    A (ObjectManager::Pull)."""
    rt = daemon_cluster
    a, b = _daemon_handles(rt)
    blob = b"x" * 300_000
    a.put_object_blob(b"oid-pull-test", blob)
    assert b.pull_object(b"oid-pull-test", a.addr)
    got = b.get_object_blob(b"oid-pull-test")
    assert got == blob


def test_nested_ops_reach_owner(daemon_cluster):
    """Worker-initiated core ops flow daemon→driver (CoreWorkerService)."""

    @ray_tpu.remote
    def inner(x):
        return x + 1

    @ray_tpu.remote
    def outer():
        refs = [inner.remote(i) for i in range(4)]
        return sum(ray_tpu.get(refs))

    assert ray_tpu.get(outer.remote()) == 10


def test_head_kv(daemon_cluster):
    head = daemon_cluster.cluster_backend.head
    assert head.kv_put(b"k1", b"v1")
    assert head.kv_get(b"k1") == b"v1"
    assert head.kv_keys(b"k") == [b"k1"]
    head.kv_del(b"k1")
    assert head.kv_get(b"k1") is None


def test_chaos_sigkill_daemon_task_retry(daemon_cluster, tmp_path):
    """SIGKILL a daemon process mid-task: the head's health check (or the
    driver's first-hand RPC failure) marks the node dead and the task is
    retried elsewhere — no remove_node() anywhere."""
    rt = daemon_cluster
    marker = str(tmp_path)

    @ray_tpu.remote(max_retries=2)
    def slow():
        import os as _os
        import time as _time
        n = len(_os.listdir(marker))
        open(os.path.join(marker, str(n)), "w").close()
        if n == 0:
            _time.sleep(30)
        return "recovered"

    ref = slow.remote()
    deadline = time.monotonic() + 10
    victim = None
    while victim is None and time.monotonic() < deadline:
        for handle in _daemon_handles(rt):
            if handle.client.call("daemon_stats")["running"] > 0:
                victim = handle
                break
        time.sleep(0.05)
    assert victim is not None, "task never started on a daemon"
    os.kill(victim.proc.pid, signal.SIGKILL)
    assert ray_tpu.get(ref, timeout=60) == "recovered"
    # the dead daemon is gone from the alive set
    assert victim.node_id not in {n.node_id for n in rt.alive_nodes()}


def test_chaos_sigkill_daemon_actor_restart(daemon_cluster):
    """SIGKILL the daemon hosting an actor: max_restarts replays the
    actor on a surviving daemon."""
    rt = daemon_cluster

    @ray_tpu.remote(max_restarts=1, max_task_retries=2)
    class Svc:
        def pid(self):
            return os.getpid()

    a = Svc.remote()
    pid1 = ray_tpu.get(a.pid.remote())
    victim = None
    for handle in _daemon_handles(rt):
        if handle.client.call("daemon_stats")["actors"] > 0:
            victim = handle
            break
    assert victim is not None
    os.kill(victim.proc.pid, signal.SIGKILL)
    # generous budget: under full-suite load on a small host the death
    # detection (~1.5s) + creation replay + cold worker pool can take a
    # while; the property under test is recovery, not latency
    deadline = time.monotonic() + 90
    pid2 = None
    while time.monotonic() < deadline:
        try:
            pid2 = ray_tpu.get(a.pid.remote(), timeout=15)
            break
        except (exc.ActorError, exc.ActorUnavailableError,
                exc.TaskError, exc.GetTimeoutError):
            time.sleep(0.3)
    assert pid2 is not None and pid2 != pid1


def test_head_health_check_marks_dead(daemon_cluster):
    """Heartbeat-miss detection: kill a daemon while IDLE (the driver has
    no in-flight RPC to observe the failure first-hand) — the head's
    health monitor must notice and broadcast the death."""
    rt = daemon_cluster
    victim = _daemon_handles(rt)[0]
    os.kill(victim.proc.pid, signal.SIGKILL)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        alive = {n.node_id for n in rt.alive_nodes()}
        if victim.node_id not in alive:
            break
        time.sleep(0.1)
    else:
        pytest.fail("head never marked the killed daemon dead")


def test_pg_2pc_bundles_on_daemons(daemon_cluster):
    from ray_tpu.util.placement_group import placement_group

    pg = placement_group([{"CPU": 2}, {"CPU": 2}], strategy="SPREAD")
    assert pg.wait(15)
    # both daemons should hold a committed bundle record
    states = []
    for handle in _daemon_handles(daemon_cluster):
        out = handle.client.call("daemon_stats")
        states.append(out)
    nodes = {b.node_id for b in pg.bundles}
    assert len(nodes) == 2


def test_chaos_sigkill_head_cluster_survives(daemon_cluster):
    """Head FT (reference: GCS restart + Redis reload, gcs_init_data.h):
    SIGKILL the head mid-run; the supervisor respawns it on the same port
    with the sqlite state, daemons re-register, KV survives, and task
    submission keeps working."""
    rt = daemon_cluster
    backend = rt.cluster_backend

    backend.head.kv_put(b"pre-crash", b"survives", namespace=b"t")
    old_pid = backend.head_proc.pid
    os.kill(old_pid, signal.SIGKILL)

    # supervisor respawns the head on the same port
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if (backend.head_proc.pid != old_pid
                and backend.head_proc.poll() is None):
            break
        time.sleep(0.1)
    assert backend.head_proc.pid != old_pid, "head was not respawned"

    # persisted KV reloaded by the restarted head
    assert backend.head.kv_get(b"pre-crash", namespace=b"t") == b"survives"
    backend.head.kv_put(b"post-crash", b"ok", namespace=b"t")
    assert backend.head.kv_get(b"post-crash", namespace=b"t") == b"ok"

    # daemons survived the outage and re-registered: membership rebuilt
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        alive = [n for n in backend.head.list_nodes() if n["alive"]]
        if len(alive) == 2:
            break
        time.sleep(0.1)
    assert len(alive) == 2, f"daemons did not re-register: {alive}"
    for handle in _daemon_handles(rt):
        assert handle.proc.poll() is None  # no daemon died with the head

    # the cluster still executes tasks end to end
    @ray_tpu.remote
    def after():
        return "recovered"

    assert ray_tpu.get(after.remote(), timeout=30) == "recovered"


# ---------------------------------------------------------------------------
# Object-manager depth (reference: object_manager.cc:247,354 chunked
# pull/push, pull_manager.h priority, push_manager.h dedup,
# ownership_object_directory.h)
# ---------------------------------------------------------------------------

def test_chunked_64mib_pull(daemon_cluster):
    """A 64 MiB object moves daemon→daemon in PULL_CHUNK pieces, not one
    monolithic RPC frame."""
    rt = daemon_cluster
    a, b = _daemon_handles(rt)
    blob = bytes(bytearray(64 * 1024 * 1024))          # 64 MiB
    a.put_object_blob(b"oid-big", blob)
    before = b.client.call("daemon_stats")["pull_stats"]
    assert b.pull_object(b"oid-big", a.addr, priority=0)
    after = b.client.call("daemon_stats")["pull_stats"]
    assert after["bytes_pulled"] - before["bytes_pulled"] == len(blob)
    assert after["chunks_transferred"] - before["chunks_transferred"] >= 16
    got = b.get_object_blob(b"oid-big")
    assert got == blob


def test_concurrent_pull_dedup(monkeypatch):
    """N concurrent pulls of one object collapse onto one transfer (the
    push-dedup role): bytes cross the wire once."""
    import threading as th
    # tiny chunks -> the transfer spans many RPCs, so all concurrent
    # pulls deterministically arrive while it is in flight
    monkeypatch.setenv("RAY_TPU_PULL_CHUNK", str(64 * 1024))
    rt = ray_tpu.init(num_nodes=2, resources={"CPU": 4},
                      cluster="daemons")
    try:
        a, b = _daemon_handles(rt)
        blob = bytes(bytearray(8 * 1024 * 1024))
        a.put_object_blob(b"oid-dedup", blob)
        results = []
        from ray_tpu._private import rpc

        def pull():
            # separate connection per puller: the server processes one
            # connection's requests sequentially, so sharing one would
            # serialize the pulls instead of racing them
            cli = rpc.connect(b.addr, timeout=120.0)
            try:
                out = cli.call("pull_object", oid=b"oid-dedup",
                               from_addr=list(a.addr), priority=2)
                results.append(out.get("ok", False))
            finally:
                cli.close()

        threads = [th.Thread(target=pull) for _ in range(6)]
        before = b.client.call("daemon_stats")["pull_stats"]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        after = b.client.call("daemon_stats")["pull_stats"]
        assert all(results)
        started = after["pulls_started"] - before["pulls_started"]
        deduped = after["pulls_deduped"] - before["pulls_deduped"]
        # one transfer, everyone else joined it; one copy of the bytes
        assert started == 1
        assert deduped == 5
        assert after["bytes_pulled"] - before["bytes_pulled"] == len(blob)
    finally:
        ray_tpu.shutdown()


def test_pull_via_owner_directory(daemon_cluster):
    """pull_object with no location hint resolves through the owner's
    object directory (ownership_object_directory.h role)."""
    rt = daemon_cluster
    a, b = _daemon_handles(rt)

    # Create an owned object on daemon A through the normal task path so
    # the owner's location metadata knows about it.
    @ray_tpu.remote
    def big():
        return np.arange(150_000)   # >100KiB: stays in the daemon table

    ref = big.remote()
    ray_tpu.get(ref)  # ensure finished + registered
    holder = None
    key = None
    for handle in (a, b):
        node = rt.get_node(handle.node_id)
        for oid in node.store.object_ids():
            holder = handle
            key = node.store._meta[oid][0]
            break
        if key:
            break
    assert key is not None
    other = b if holder is a else a
    assert not other.client.call("get_object", oid=key,
                                 prefer_shm=False).get("blob")
    assert other.pull_object(key, from_addr=None, priority=1)
    assert other.get_object_blob(key) is not None


def test_pull_priority_ordering():
    """Unit test: queued pulls are served get > wait > task-args
    (pull_manager.h:38-51)."""
    from ray_tpu._private.daemon import (PULL_PRIORITY_GET,
                                         PULL_PRIORITY_TASK_ARGS,
                                         PULL_PRIORITY_WAIT, PullManager)

    order = []
    gate = __import__("threading").Event()

    class FakeObjects:
        def contains(self, oid):
            return False

        def put(self, oid, blob):
            pass

    class FakePeer:
        def call(self, method, **kw):
            if method == "object_meta":
                gate.wait(5)             # hold transfers until all queued
                order.append(kw["oid"])
                return {"size": 1}
            return {"blob": b"x"}

    pm = PullManager(FakeObjects(), lambda addr: FakePeer(),
                     num_workers=1)
    # first pull occupies the single worker at the gate; the rest queue
    p0 = pm.request(b"warm", ("h", 1), PULL_PRIORITY_TASK_ARGS)
    time.sleep(0.2)
    p1 = pm.request(b"args", ("h", 1), PULL_PRIORITY_TASK_ARGS)
    p2 = pm.request(b"get", ("h", 1), PULL_PRIORITY_GET)
    p3 = pm.request(b"wait", ("h", 1), PULL_PRIORITY_WAIT)
    gate.set()
    for p in (p0, p1, p2, p3):
        assert p.event.wait(10)
    assert order[0] == b"warm"
    assert order[1:] == [b"get", b"wait", b"args"]


def test_resource_view_gossip(daemon_cluster):
    """Syncer role (ray_syncer.h:83): the driver gossips true per-node
    availability to the head; list_nodes and the transient 'resources'
    channel expose the live view (heartbeat static values don't clobber
    fresh gossip)."""
    rt = daemon_cluster
    backend = rt.cluster_backend
    events = []
    backend.head.subscribe("resources", events.append)

    @ray_tpu.remote(num_cpus=3)
    def hold():
        time.sleep(3.0)   # longer than the 2s gossip-freshness window:
        return 1          # steady load must not revert to static values

    ref = hold.remote()
    deadline = time.monotonic() + 1.0
    seen_during = None
    while time.monotonic() < deadline:
        per_node = {n["node_id"]: n["available"].get("CPU", 0)
                    for n in backend.head.list_nodes() if n["alive"]}
        if min(per_node.values()) <= 1:
            seen_during = per_node
            break
        time.sleep(0.05)
    assert seen_during is not None, "gossiped availability never dropped"
    # steady load past the freshness window: the view must NOT revert
    time.sleep(1.5)
    per_node = {n["node_id"]: n["available"].get("CPU", 0)
                for n in backend.head.list_nodes() if n["alive"]}
    assert min(per_node.values()) <= 1, (
        f"steady load reverted to static availability: {per_node}")
    ray_tpu.get(ref)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        per_node = {n["node_id"]: n["available"].get("CPU", 0)
                    for n in backend.head.list_nodes() if n["alive"]}
        if all(v == 4 for v in per_node.values()):
            break
        time.sleep(0.05)
    assert all(v == 4 for v in per_node.values()), per_node
    assert events and "available" in events[-1]


def test_per_task_borrow_release(daemon_cluster):
    """Refs the owner pins on a worker task's behalf (nested put) release
    when THAT task finishes — a long-lived daemon must not pin dead
    tasks' objects (reference: per-task borrows, reference_count.h:73)."""
    rt = daemon_cluster
    from ray_tpu._private.ids import ObjectID

    @ray_tpu.remote
    def put_and_drop():
        ref = ray_tpu.put(np.arange(1000))
        return ref.id.hex()      # the hex only: no live ref escapes

    @ray_tpu.remote
    def put_and_return():
        return ray_tpu.put(np.arange(1000))

    # dropped borrow: freed once the task is done (the dropped handle
    # can sit in a reply-closure cycle, so nudge the cyclic collector)
    import gc
    oid_hex = ray_tpu.get(put_and_drop.remote())
    deadline = time.monotonic() + 5.0
    oid = ObjectID.from_hex(oid_hex)
    while time.monotonic() < deadline and rt.refcounter.ref_count(oid):
        gc.collect()
        time.sleep(0.05)
    assert rt.refcounter.ref_count(oid) == 0
    svc = rt.cluster_backend.owner_service
    assert svc.holder.num_keys() == 0, "holder leaked task keys"

    # returned borrow: containment in the result keeps it alive
    inner = ray_tpu.get(put_and_return.remote())
    assert list(ray_tpu.get(inner)[:3]) == [0, 1, 2]


def test_actor_borrow_released_on_death(daemon_cluster):
    """Actor-lifetime borrows persist across its tasks, then release on
    actor death."""
    rt = daemon_cluster
    from ray_tpu._private.ids import ObjectID

    @ray_tpu.remote
    class Holder:
        def make(self):
            self.ref = ray_tpu.put(np.arange(500))
            return self.ref.id.hex()

        def read(self):
            return int(ray_tpu.get(self.ref)[1])

    h = Holder.remote()
    oid = ObjectID.from_hex(ray_tpu.get(h.make.remote()))
    assert ray_tpu.get(h.read.remote()) == 1   # alive across actor tasks
    assert rt.refcounter.ref_count(oid) > 0
    ray_tpu.kill(h)
    import gc
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and rt.refcounter.ref_count(oid):
        gc.collect()
        time.sleep(0.05)
    assert rt.refcounter.ref_count(oid) == 0


def test_head_task_event_store(daemon_cluster):
    """Task state transitions buffer at the HEAD (reference:
    gcs_task_manager.h:94) so list/timeline queries outlive the driver's
    in-process buffer."""
    rt = daemon_cluster
    backend = rt.cluster_backend

    @ray_tpu.remote
    def marked():
        return 1

    ray_tpu.get([marked.remote() for _ in range(5)])
    backend._flush_task_events()
    # wipe the driver-side buffer: reads below must come from the head
    rt.task_events.clear()
    events = backend.head.task_events_get()
    finished = [e for e in events
                if e["event"] == "FINISHED" and "marked" in e["name"]]
    assert len(finished) == 5, events
    assert all(e["job_id"] == rt.job_id.hex() for e in finished)
    # exact-name server-side filter round trip
    exact = backend.head.task_events_get(name=finished[0]["name"])
    assert len([e for e in exact if e["event"] == "FINISHED"]) == 5
    # job filter excludes other jobs
    assert backend.head.task_events_get(job_id="deadbeef") == []


def test_post_mortem_state_from_head(daemon_cluster):
    """list_tasks_from_head / timeline_from_head answer from the head
    store alone — the post-driver-exit introspection path."""
    rt = daemon_cluster
    backend = rt.cluster_backend

    @ray_tpu.remote
    def traced():
        return 1

    ray_tpu.get([traced.remote() for _ in range(3)])
    backend._flush_task_events()
    addr = f"{backend.head.addr[0]}:{backend.head.addr[1]}"
    from ray_tpu.util.state.api import (list_tasks_from_head,
                                        timeline_from_head)
    rows = list_tasks_from_head(addr)
    done = [r for r in rows
            if "traced" in r["name"] and r["state"] == "FINISHED"]
    assert len(done) == 3
    trace = timeline_from_head(addr)
    assert isinstance(trace, list)


def test_peer_resource_gossip(daemon_cluster):
    """Daemon-to-daemon anti-entropy (reference: ray_syncer.h:83 bidi
    gossip): each daemon's view converges to contain EVERY node's load
    entry via peer exchange, and the head's membership view gains
    gossip_load entries pushed by ~one node per interval."""
    rt = daemon_cluster
    handles = _daemon_handles(rt)
    all_ids = {h.node_id.hex() for h in handles}
    deadline = time.monotonic() + 15
    converged = False
    while time.monotonic() < deadline and not converged:
        views = [set(h.client.call("syncer_view")["view"])
                 for h in handles]
        converged = all(all_ids <= v for v in views)
        if not converged:
            time.sleep(0.2)
    assert converged, f"gossip never converged: {views}"
    # entries carry real load fields
    view = handles[0].client.call("syncer_view")["view"]
    entry = view[handles[1].node_id.hex()]
    assert {"running", "store_used", "fast_queued"} <= set(entry["load"])
    # the head picked up gossip entries without per-node reports
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        nodes = rt.cluster_backend.head.list_nodes()
        if any("gossip_load" in n for n in nodes):
            break
        time.sleep(0.2)
    assert any("gossip_load" in n for n in nodes), nodes


def test_per_node_agent_endpoints(daemon_cluster):
    """Each daemon serves its own observability HTTP endpoint
    (reference: dashboard/agent.py per-node agent): /api/stats,
    /api/profile/cpu (stack-sample flamegraph data), /metrics."""
    import json as _json
    import urllib.request

    rt = daemon_cluster
    for h in _daemon_handles(rt):
        port = h.client.call("daemon_stats")["agent_port"]
        assert port, "agent not started"
        base = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(f"{base}/api/stats",
                                    timeout=30) as r:
            stats = _json.loads(r.read())
        assert stats["node_id"] == h.node_id.hex()
        assert stats["pid"] == h.proc.pid
        with urllib.request.urlopen(
                f"{base}/api/profile/cpu?duration=0.3",
                timeout=30) as r:
            prof = _json.loads(r.read())
        assert "collapsed" in prof and prof["samples"] > 0
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            assert r.status == 200


def test_autoscaler_provisions_real_daemon_process(daemon_cluster):
    """ProcessHostProvider end to end: unmet demand drives the
    reconciler to SPAWN a real node-daemon OS process which registers
    at the head and becomes schedulable; idle drain terminates it
    (reference: autoscaler node providers actually creating hosts)."""
    import time as _t

    from ray_tpu.autoscaler_v2 import (InstanceStatus,
                                       ProcessHostProvider, Reconciler)
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)

    rt = daemon_cluster
    before = {n.node_id for n in rt.alive_nodes()}
    provider = ProcessHostProvider(rt)
    rec = Reconciler(rt, provider, idle_timeout_s=0.5)

    # 2 daemons x CPU:4 fully... demand a CPU:16 host (cpu-host type)
    pg = placement_group([{"CPU": 16}], strategy="PACK")
    assert not pg.wait(0.5)
    deadline = _t.monotonic() + 60
    while _t.monotonic() < deadline:
        rec.reconcile()
        if pg.wait(0.5):
            break
    assert pg.wait(5), "real daemon never provisioned"
    rec.reconcile()   # promote ALLOCATED -> RAY_RUNNING post-join
    new_nodes = {n.node_id for n in rt.alive_nodes()} - before
    assert len(new_nodes) == 1
    running = rec.instance_manager.list(InstanceStatus.RAY_RUNNING)
    assert running and running[0].node_type == "cpu-host"

    # a task actually lands on the provisioned daemon process
    @ray_tpu.remote(num_cpus=9)   # only fits the new CPU:16 host
    def where():
        return ray_tpu.get_runtime_context().get_node_id()

    assert where.remote() is not None

    remove_placement_group(pg)
    deadline = _t.monotonic() + 30
    while _t.monotonic() < deadline:
        rec.reconcile()
        if rec.instance_manager.list(InstanceStatus.TERMINATED):
            break
        _t.sleep(0.2)
    assert rec.instance_manager.list(InstanceStatus.TERMINATED)
