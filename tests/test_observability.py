"""Observability: export events (reference export_*.proto +
export_event_logger.py), the task-event/span buffer, cluster-wide task
tracing (per-phase spans flushed to the head over heartbeats), metrics
federation, and the dashboard HTTP endpoints."""

import json
import time
import urllib.error
import urllib.request

import pytest

# ---------------------------------------------------------------------------
# Export events (reference: export_*.proto + export_event_logger.py)
# ---------------------------------------------------------------------------

def test_export_events_lifecycle(tmp_path):
    import ray_tpu
    from ray_tpu._private.export_events import (get_export_logger,
                                                reset_export_logger)

    reset_export_logger()
    rt = ray_tpu.init(num_nodes=1, resources={"CPU": 4},
                      _system_config={"export_events": True})
    try:
        @ray_tpu.remote
        def f():
            return 1

        @ray_tpu.remote
        class A:
            def ping(self):
                return "pong"

        assert ray_tpu.get(f.remote()) == 1
        a = A.remote()
        assert ray_tpu.get(a.ping.remote()) == "pong"
        ray_tpu.kill(a)

        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)
        pg = placement_group([{"CPU": 1}])
        ray_tpu.get(pg.ready())
        remove_placement_group(pg)

        logger = get_export_logger()
        tasks = logger.read("TASK")
        assert any(e["state"] == "FINISHED" for e in tasks)
        assert all("task_id" in e and "timestamp" in e for e in tasks)
        actors = logger.read("ACTOR")
        states = {e["state"] for e in actors}
        assert any("ALIVE" in s for s in states)
        assert any("DEAD" in s for s in states)
        nodes = logger.read("NODE")
        assert any(e["state"] == "ALIVE" for e in nodes)
        pgs = logger.read("PLACEMENT_GROUP")
        assert {e["state"] for e in pgs} >= {"CREATED", "REMOVED"}
    finally:
        ray_tpu.shutdown()
        reset_export_logger()


def test_export_events_disabled_by_default(tmp_path):
    import ray_tpu
    from ray_tpu._private.export_events import (get_export_logger,
                                                reset_export_logger)

    reset_export_logger()
    ray_tpu.init(num_nodes=1, resources={"CPU": 2})
    try:
        @ray_tpu.remote
        def f():
            return 1

        assert ray_tpu.get(f.remote()) == 1
        logger = get_export_logger()
        assert logger.read("TASK") == []   # flag off: no writes
    finally:
        ray_tpu.shutdown()
        reset_export_logger()


# ---------------------------------------------------------------------------
# task-event buffer (reference: task_event_buffer.cc)
# ---------------------------------------------------------------------------

def test_event_buffer_extend_and_from_events():
    from ray_tpu._private.events import TaskEventBuffer

    src = TaskEventBuffer()
    src.record(task_id="t1", name="a", event="RUNNING")
    src.record(task_id="t1", name="a", event="FINISHED")
    buf = TaskEventBuffer.from_events(src.events())
    assert [e["task_id"] for e in buf.events()] == ["t1", "t1"]
    # extend re-assigns seqs locally so cursors stay monotonic
    buf.extend([{"task_id": "t2", "name": "b", "event": "RUNNING",
                 "wall_ts": time.time(), "seq": 999}])
    seqs = [e["seq"] for e in buf.events()]
    assert seqs == sorted(seqs) and seqs[-1] == 3
    assert buf.events()[-1]["task_id"] == "t2"


def test_events_after_tail_indexed():
    from ray_tpu._private.events import TaskEventBuffer

    buf = TaskEventBuffer(capacity=10)
    for i in range(25):
        buf.record(task_id=f"t{i}", name="n", event="RUNNING")
    # only the last 10 survive the ring (seqs 16..25)
    assert [e["seq"] for e in buf.events_after(20)] == [21, 22, 23, 24, 25]
    assert [e["seq"] for e in buf.events_after(0)] == list(range(16, 26))
    assert buf.events_after(25) == []
    assert buf.events_after(99) == []


def test_chrome_trace_retry_pairing():
    """A retry's second RUNNING supersedes the dead attempt's start, so
    FINISHED pairs with the retry's own start — never the stale one."""
    from ray_tpu._private.events import TaskEventBuffer

    buf = TaskEventBuffer()
    buf.record(task_id="t1", name="f", event="RUNNING", node_id="aa" * 8)
    buf.record(task_id="t1", name="f", event="RETRY")
    buf.record(task_id="t1", name="f", event="RUNNING", node_id="bb" * 8)
    buf.record(task_id="t1", name="f", event="FINISHED",
               node_id="bb" * 8)
    # second task: two RUNNINGs with NO retry marker (lost transition)
    buf.record(task_id="t2", name="g", event="RUNNING")
    buf.record(task_id="t2", name="g", event="RUNNING")
    buf.record(task_id="t2", name="g", event="FINISHED")
    events = buf.events()
    trace = buf.chrome_trace()
    t1 = [s for s in trace if s["tid"] == "t1"]
    assert len(t1) == 1
    second_running = [e for e in events if e["task_id"] == "t1"
                      and e["event"] == "RUNNING"][1]
    assert t1[0]["ts"] == second_running["ts_us"]
    t2 = [s for s in trace if s["tid"] == "t2"]
    assert len(t2) == 1


def test_merged_chrome_trace_lanes():
    from ray_tpu._private.events import merged_chrome_trace

    now = time.time()
    events = [
        {"task_id": "t1", "name": "f", "event": "SPAN", "phase": "submit",
         "proc": "driver", "wall_ts": now, "start_wall": now - 0.01,
         "dur_s": 0.01},
        {"task_id": "t1", "name": "f", "event": "SPAN",
         "phase": "dispatch", "proc": "daemon:aabbccdd",
         "wall_ts": now + 0.02, "start_wall": now + 0.01, "dur_s": 0.01},
        {"task_id": "t1", "name": "f", "event": "SPAN", "phase": "exec",
         "proc": "worker:123", "wall_ts": now + 0.05,
         "start_wall": now + 0.02, "dur_s": 0.03},
    ]
    trace = merged_chrome_trace(events)
    lanes = {s["pid"] for s in trace}
    assert lanes == {"driver", "daemon:aabbccdd", "worker:123"}
    by_phase = {s["args"]["phase"]: s["ts"] for s in trace}
    assert by_phase["submit"] <= by_phase["dispatch"] <= by_phase["exec"]


# ---------------------------------------------------------------------------
# Prometheus exposition (reference: metrics_agent.py)
# ---------------------------------------------------------------------------

def test_prometheus_label_escaping():
    """Label values with quotes/backslashes/newlines must be escaped per
    the exposition spec — a task name containing `\"` used to corrupt
    the scrape."""
    from ray_tpu.util import metrics

    metrics.clear_registry()
    try:
        c = metrics.Counter("esc_test_total", "escaping", ("name",))
        c.inc(1, tags={"name": 'he said "hi"\nback\\slash'})
        text = metrics.prometheus_text()
        assert ('esc_test_total{name="he said \\"hi\\"\\nback'
                '\\\\slash"} 1.0') in text
        # still a parseable single line
        line = [ln for ln in text.splitlines()
                if ln.startswith("esc_test_total{")]
        assert len(line) == 1
    finally:
        metrics.clear_registry()


def test_render_prometheus_federated_labels():
    """Snapshots from several processes merge into one exposition with a
    single TYPE block per metric and per-source node_id labels."""
    from ray_tpu.util import metrics

    metrics.clear_registry()
    try:
        metrics.Counter("fed_reqs_total", "reqs").inc(2)
        local = metrics.export_snapshot()
        remote = [{"name": "fed_reqs_total", "kind": "counter",
                   "description": "reqs", "samples": [[[], 5.0]]}]
        text = metrics.render_prometheus(
            [({}, local), ({"node_id": "aa" * 16}, remote)])
        assert text.count("# TYPE fed_reqs_total counter") == 1
        assert "fed_reqs_total 2.0" in text
        assert f'fed_reqs_total{{node_id="{"aa" * 16}"}} 5.0' in text
    finally:
        metrics.clear_registry()


def test_render_prometheus_keeps_to_the_expositions_alphabet():
    """A name with a dot (``train.kept_residual_bytes``) is scraped with
    an underscore; the JSON view keeps the name as it was given."""
    from ray_tpu.util import metrics

    metrics.clear_registry()
    try:
        metrics.Gauge("train.kept_residual_bytes", "", ("name",)).set(
            7, {"name": "qkv"})
        text = metrics.prometheus_text()
        assert 'train_kept_residual_bytes{name="qkv"} 7' in text
        assert "train.kept" not in text
        assert any(row["name"] == "train.kept_residual_bytes"
                   for row in metrics.cluster_metrics_json()["metrics"])
    finally:
        metrics.clear_registry()


def test_worker_metrics_flow_to_driver(ray_start_regular):
    """User metrics created inside pool workers surface on the driver's
    Prometheus endpoint (reference: worker -> agent -> exporter flow);
    counters merge across workers, histograms merge bucket counts."""
    import time

    import ray_tpu
    from ray_tpu.util.metrics import prometheus_text

    @ray_tpu.remote
    def work(i):
        from ray_tpu.util.metrics import Counter, Histogram
        Counter("xproc_events", "events").inc(5)
        Histogram("xproc_lat", "lat", boundaries=(1, 10)).observe(i)
        return 1

    assert ray_tpu.get([work.remote(i) for i in range(3)],
                       timeout=60) == [1, 1, 1]
    deadline = time.time() + 20
    while time.time() < deadline:
        text = prometheus_text()
        lines = [l for l in text.splitlines()
                 if l.startswith("xproc_events ")]
        if lines and lines[0].endswith("15.0"):
            break
        time.sleep(0.2)
    assert lines and lines[0].endswith("15.0"), lines
    assert "xproc_lat_count 3" in text
    # don't pollute later tests' prometheus_text in this process
    from ray_tpu.util.metrics import clear_registry
    clear_registry()


# ---------------------------------------------------------------------------
# cluster-wide tracing + federation (2-node daemon topology; reference:
# task_event_buffer.cc flush -> gcs_task_manager, metrics agent federation)
# ---------------------------------------------------------------------------

@pytest.fixture
def daemon_cluster():
    import ray_tpu
    rt = ray_tpu.init(num_nodes=2, resources={"CPU": 4},
                      cluster="daemons")
    yield rt
    ray_tpu.shutdown()


def _run_batched_workload(n=40):
    """num_returns=2 keeps tasks OFF the fast lane, so they ride the
    classic batched submit path (coalescer -> push_task_batch)."""
    import ray_tpu

    @ray_tpu.remote(num_returns=2)
    def duo(i):
        return i, i + 1

    refs = [duo.remote(i) for i in range(n)]
    ray_tpu.get([r for ab in refs for r in ab])


def _head_span_events(backend, phases, deadline_s=20.0):
    """Poll the head store until spans for every wanted phase landed
    (daemon flushes piggyback on ~0.2s heartbeats)."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        events = backend.head.task_events_get()
        spans = [e for e in events if e.get("event") == "SPAN"]
        got = {e.get("phase") for e in spans}
        if phases <= got:
            return events
        time.sleep(0.2)
    raise AssertionError(
        f"head store never saw phases {phases - got}; got {got}")


def test_spans_flush_to_head_and_breakdown(daemon_cluster):
    """End-to-end trace: driver submit/linger/queue/result spans,
    daemon dispatch spans, worker exec spans all reach the head;
    task_breakdown returns the six-phase vector; the merged chrome
    trace has one lane per process with monotonic phase ordering."""
    rt = daemon_cluster
    backend = rt.cluster_backend
    _run_batched_workload()
    backend._flush_task_events()
    events = _head_span_events(
        backend, {"submit", "queue", "dispatch", "exec", "result"})

    spans = [e for e in events if e.get("event") == "SPAN"]
    # daemon + worker lanes carry the daemon's node ids
    node_hexes = {h.node_id.hex() for h in backend.daemons.values()}
    dispatch = [e for e in spans if e["phase"] == "dispatch"]
    assert {e["node_id"] for e in dispatch} <= node_hexes
    assert all(e["proc"].startswith("daemon:") for e in dispatch)
    execs = [e for e in spans if e["phase"] == "exec"]
    assert any(e["proc"].startswith("worker:") for e in execs)
    # clock correction was applied on ingestion
    assert all("clock_off" in e for e in dispatch)

    # a task that has driver+daemon+worker spans -> full breakdown
    by_task = {}
    for e in spans:
        by_task.setdefault(e["task_id"], set()).add(e["phase"])
    full = [t for t, ph in by_task.items()
            if {"submit", "dispatch", "exec"} <= ph]
    assert full, f"no task with cross-process spans: {by_task}"
    from ray_tpu.util.state import task_breakdown
    bd = task_breakdown(full[0])
    assert set(bd) == {"submit", "linger", "queue", "dispatch", "exec",
                       "result_flush", "result_ingest", "result"}
    assert bd["exec"] > 0.0 and bd["dispatch"] > 0.0

    # merged chrome trace: one lane per process, monotonic ordering
    from ray_tpu.util.state import cluster_timeline
    trace = cluster_timeline()
    task_slices = [s for s in trace
                   if s.get("args", {}).get("task_id") == full[0]]
    lanes = {s["pid"] for s in task_slices}
    assert "driver" in lanes
    assert any(p.startswith("daemon:") for p in lanes)
    assert any(p.startswith("worker:") for p in lanes)
    ts = {s["args"]["phase"]: s["ts"] for s in task_slices
          if s["args"].get("phase")}
    slack = 2000.0  # µs of clock-estimate tolerance (same host: ~0)
    assert ts["submit"] <= ts["dispatch"] + slack
    assert ts["dispatch"] <= ts["exec"] + slack


def test_cluster_metrics_federation(daemon_cluster):
    """Dashboard /metrics is CLUSTER-wide: phase histograms carry
    node_id labels for both daemons, and daemon-process metrics (rpc
    server counters) federate to the driver via head heartbeats."""
    rt = daemon_cluster
    backend = rt.cluster_backend
    _run_batched_workload()
    from ray_tpu.util.metrics import cluster_prometheus_text
    node_hexes = {h.node_id.hex() for h in backend.daemons.values()}
    deadline = time.monotonic() + 25
    ok = False
    while time.monotonic() < deadline and not ok:
        text = cluster_prometheus_text()
        ok = ("ray_tpu_task_phase_seconds_bucket" in text
              and all(f'node_id="{h}"' in text for h in node_hexes)
              and "ray_tpu_rpc_server_requests_total" in text)
        if not ok:
            time.sleep(0.3)
    assert "ray_tpu_task_phase_seconds_bucket" in text
    for h in node_hexes:
        assert f'node_id="{h}"' in text
    # federated from the DAEMON processes (heartbeat snapshots): their
    # rpc server counters appear node_id-labeled
    assert "ray_tpu_rpc_server_requests_total" in text
    lines = [ln for ln in text.splitlines()
             if ln.startswith("ray_tpu_rpc_server_requests_total")
             and "node_id=" in ln]
    assert lines, "daemon rpc counters did not federate"
    # exactly one TYPE block per metric even with federated sources
    assert text.count("# TYPE ray_tpu_task_phase_seconds histogram") == 1


def test_dashboard_endpoints_live_cluster(daemon_cluster):
    """Dashboard HTTP surface against a live 2-node cluster:
    /api/timeline, /api/cluster_status, /api/metrics, /metrics, and the
    unknown-path 404."""
    from ray_tpu.dashboard.server import start_dashboard, stop_dashboard

    rt = daemon_cluster
    _run_batched_workload(10)
    rt.cluster_backend._flush_task_events()
    host, port = start_dashboard(port=0)
    base = f"http://{host}:{port}"
    try:
        with urllib.request.urlopen(f"{base}/api/timeline",
                                    timeout=30) as r:
            trace = json.loads(r.read())
        assert isinstance(trace, list) and trace
        assert any(s.get("ph") == "X" for s in trace)

        with urllib.request.urlopen(f"{base}/api/cluster_status",
                                    timeout=30) as r:
            status = json.loads(r.read())
        assert "cluster_resources" in status and "stats" in status
        assert status["task_summary"].get("FINISHED", 0) >= 1

        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            assert r.status == 200
            text = r.read().decode()
        assert "ray_tpu_task_phase_seconds" in text
        assert "ray_tpu_tasks_finished" in text

        with urllib.request.urlopen(f"{base}/api/metrics",
                                    timeout=30) as r:
            payload = json.loads(r.read())
        assert any(row["name"] == "ray_tpu_task_phase_seconds"
                   for row in payload["metrics"])

        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{base}/api/no_such_thing",
                                   timeout=30)
        assert err.value.code == 404
    finally:
        stop_dashboard()


def test_trace_flush_failpoint_retries(daemon_cluster):
    """trace.flush drop arm: a lost driver flush keeps its cursor, so
    the next interval re-sends the same batch (no span ever lost)."""
    from ray_tpu._private import failpoints as _fp

    rt = daemon_cluster
    backend = rt.cluster_backend
    _run_batched_workload(6)
    cursor_before = backend._task_event_cursor
    _fp.activate("trace.flush=drop:p=1")
    try:
        backend._flush_task_events()
        assert backend._task_event_cursor == cursor_before
    finally:
        _fp.reset()
    backend._flush_task_events()
    assert backend._task_event_cursor > cursor_before
    events = backend.head.task_events_get()
    assert any(e.get("event") == "FINISHED" for e in events)
