"""Ops shell: state API, metrics, dashboard, jobs, autoscaler, CLI,
timeline."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import metrics as rt_metrics
from ray_tpu.util import state as state_api


def test_state_api_lists(ray_start_cluster):
    @ray_tpu.remote
    def f():
        return 1

    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.options(name="state_test_actor").remote()
    ray_tpu.get(a.ping.remote())
    ray_tpu.get([f.remote() for _ in range(5)])

    nodes = state_api.list_nodes()
    assert len(nodes) == 4 and all(n["alive"] for n in nodes)
    actors = state_api.list_actors()
    assert any(x["name"] == "state_test_actor" for x in actors)
    tasks = state_api.list_tasks()
    assert len(tasks) >= 5
    summary = state_api.summarize_tasks()
    assert sum(summary.values()) == len(tasks)


def test_timeline_chrome_trace(ray_start_regular, tmp_path):
    @ray_tpu.remote
    def work():
        time.sleep(0.01)
        return 1

    ray_tpu.get([work.remote() for _ in range(3)])
    trace = state_api.timeline()
    named = [s for s in trace if "work" in s["name"]]
    assert len(named) >= 3
    assert all(s["ph"] == "X" and s["dur"] > 0 for s in named)
    path = state_api.timeline(str(tmp_path / "trace.json"))
    assert json.load(open(path))


def test_metrics_prometheus_text(ray_start_regular):
    rt_metrics.clear_registry()
    c = rt_metrics.Counter("my_requests", "test counter", ("route",))
    c.inc(3, tags={"route": "/a"})
    g = rt_metrics.Gauge("my_depth", "test gauge")
    g.set(7.5)
    h = rt_metrics.Histogram("my_lat", "test hist", boundaries=(1, 10))
    h.observe(0.5)
    h.observe(5)
    h.observe(50)
    text = rt_metrics.prometheus_text()
    assert 'my_requests{route="/a"} 3.0' in text
    assert "my_depth 7.5" in text
    assert "my_lat_count 3" in text
    assert "ray_tpu_tasks_finished" in text


def test_dashboard_endpoints(ray_start_regular):
    from ray_tpu.dashboard.server import start_dashboard, stop_dashboard

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get(f.remote())
    host, port = start_dashboard(port=0)
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://{host}:{port}{path}", timeout=10) as r:
                return r.read().decode()
        nodes = json.loads(get("/api/nodes"))
        assert len(nodes) == 1
        status = json.loads(get("/api/cluster_status"))
        assert status["stats"]["tasks_finished"] >= 1
        assert "ray_tpu_tasks_finished" in get("/metrics")
        assert json.loads(get("/api/timeline"))
        from ray_tpu._private.config import cfg
        config = json.loads(get("/api/config"))
        assert config["pull_chunk"]["value"] == cfg().pull_chunk
        assert "source" in config["memory_monitor"]
        # library observability endpoints (reference: dashboard
        # serve/train/data modules)
        from ray_tpu import data as _data
        (_data.from_items([{"x": i} for i in range(6)])
         .map(lambda r: r).take_all())   # executor path records stats
        ds_stats = json.loads(get("/api/data"))
        assert ds_stats["datasets"], "dataset stats not surfaced"
        train = json.loads(get("/api/train"))
        assert "train_runs" in train
        serve_state = json.loads(get("/api/serve"))
        assert "applications" in serve_state or serve_state == {}
    finally:
        stop_dashboard()


def test_job_submission(ray_start_regular):
    from ray_tpu.job import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint="echo hello_from_job && echo line2")
    status = client.wait_until_finished(job_id, timeout=30)
    assert status == "SUCCEEDED"
    logs = client.get_job_logs(job_id)
    assert "hello_from_job" in logs and "line2" in logs

    bad = client.submit_job(entrypoint="exit 3")
    assert client.wait_until_finished(bad, timeout=30) == "FAILED"
    assert client.get_job_info(bad).returncode == 3
    assert len(client.list_jobs()) == 2


def test_autoscaler_scales_up_and_down(ray_start_regular):
    from ray_tpu._private import worker as _worker
    from ray_tpu.autoscaler import FakeNodeProvider, StandardAutoscaler

    rt = _worker.global_runtime()
    provider = FakeNodeProvider(rt, {"CPU": 4})
    scaler = StandardAutoscaler(rt, provider, min_nodes=1, max_nodes=4,
                                idle_timeout_s=0.5)

    # more parallel work than one 8-CPU node can run
    @ray_tpu.remote(num_cpus=4)
    def slow():
        time.sleep(1.5)
        return 1

    refs = [slow.remote() for _ in range(6)]  # 24 CPUs of demand
    time.sleep(0.2)
    scaler.update()
    assert scaler.stats["launched"] >= 1
    scaler.update()
    launched = scaler.stats["launched"]
    assert launched >= 2
    assert ray_tpu.get(refs, timeout=60) == [1] * 6
    # idle: scale back down
    deadline = time.time() + 15
    while time.time() < deadline and provider.non_terminated_nodes():
        scaler.update()
        time.sleep(0.3)
    assert not provider.non_terminated_nodes()


def test_cli_status_and_summary(ray_start_regular, capsys):
    from ray_tpu.scripts.cli import main

    assert main(["status"]) == 0
    out = capsys.readouterr().out
    assert "cluster_resources" in out
    assert main(["summary"]) == 0


def test_microbenchmark_harness(ray_start_regular):
    from ray_tpu._private.perf import run_microbenchmarks

    results = run_microbenchmarks(duration_s=0.3)
    names = {r["name"] for r in results}
    assert "tasks_per_second" in names
    assert all(r["throughput_per_s"] > 0 for r in results)


def test_debug_state_and_loop_instrumentation(ray_start_regular):
    from ray_tpu._private import worker as _worker

    @ray_tpu.remote
    def f():
        return 1

    ray_tpu.get([f.remote() for _ in range(5)])
    rt = _worker.global_runtime()
    state = rt.debug_state()
    assert "loop=" in state and "tasks_launched" in state
    node = rt.nodes()[0]
    assert node.loop_stats["tasks_launched"] >= 5
    assert node.loop_stats["max_queue_lag_ms"] >= 0


def test_gcs_kv_snapshot_restore(ray_start_regular, tmp_path):
    from ray_tpu._private import worker as _worker

    rt = _worker.global_runtime()
    rt.gcs.kv_put(b"cfg", b"value1")
    path = rt.gcs.snapshot(str(tmp_path / "gcs.snap"))

    rt.gcs.kv_del(b"cfg")
    assert rt.gcs.kv_get(b"cfg") is None
    rt.gcs.restore(path)
    assert rt.gcs.kv_get(b"cfg") == b"value1"


def test_tqdm_ray(capsys):
    from ray_tpu.experimental.tqdm_ray import tqdm

    out = []
    for x in tqdm(range(5), desc="test", flush_period_s=0):
        out.append(x)
    assert out == [0, 1, 2, 3, 4]


def test_iter_torch_batches(ray_start_regular):
    from ray_tpu import data as rdata
    import torch

    ds = rdata.range(20, parallelism=2)
    batches = list(ds.iter_torch_batches(batch_size=10))
    assert len(batches) == 2
    assert isinstance(batches[0]["id"], torch.Tensor)
    vals = sorted(int(x) for b in batches for x in b["id"])
    assert vals == list(range(20))


# ---------------------------------------------------------------------------
# Autoscaler v2: GCS-state reconciler + instance lifecycle (VERDICT r1 #10)
# ---------------------------------------------------------------------------

def test_autoscaler_v2_scales_up_for_tpu_demand(ray_start_regular):
    """A pending PG demanding a TPU slice drives the reconciler to
    provision the smallest covering slice type, with the full instance
    state machine recorded."""
    import ray_tpu
    from ray_tpu.autoscaler_v2 import (InstanceStatus, Reconciler,
                                       RuntimeBackedTpuProvider)
    from ray_tpu.util.placement_group import placement_group

    rt = ray_tpu._private.worker.global_runtime()
    provider = RuntimeBackedTpuProvider(rt)
    rec = Reconciler(rt, provider, idle_timeout_s=0.2)

    pg = placement_group([{"TPU": 4}], strategy="PACK")  # unschedulable now
    assert not pg.wait(0.5)
    for _ in range(4):
        rec.reconcile()
    assert pg.wait(10), "slice node never provisioned"
    running = rec.instance_manager.list(InstanceStatus.RAY_RUNNING)
    assert len(running) == 1
    assert running[0].node_type == "v5e-4"  # smallest covering slice
    assert "QUEUED->REQUESTED" in running[0].history[0]

    # release the PG: the instance drains and terminates
    from ray_tpu.util.placement_group import remove_placement_group
    remove_placement_group(pg)
    import time as _t
    deadline = _t.monotonic() + 15
    while _t.monotonic() < deadline:
        rec.reconcile()
        if rec.instance_manager.list(InstanceStatus.TERMINATED):
            break
        _t.sleep(0.1)
    dead = rec.instance_manager.list(InstanceStatus.TERMINATED)
    assert len(dead) == 1
    assert rec.stats["terminated"] == 1


def test_autoscaler_v2_gke_provider_is_explicit_stub():
    from ray_tpu.autoscaler_v2 import GkeTpuProvider
    import pytest as _pytest

    provider = GkeTpuProvider(project="p", zone="z", cluster="c")
    with _pytest.raises(NotImplementedError, match="zero-egress|GKE|API"):
        provider.launch("v5e-4")


def test_dashboard_web_ui_and_profiling(ray_start_regular):
    """The dashboard serves an HTML UI at / and on-demand profiling
    endpoints (py-spy/memray role, stdlib sampling — SURVEY §5.1)."""
    import json
    import threading
    import time
    import urllib.request

    from ray_tpu.dashboard.server import start_dashboard, stop_dashboard

    host, port = start_dashboard(port=0)
    base = f"http://{host}:{port}"
    try:
        html = urllib.request.urlopen(f"{base}/").read().decode()
        assert "<html>" in html and "ray_tpu" in html

        # keep a thread busy so the sampler sees a stack
        stop = threading.Event()

        def burn():
            while not stop.is_set():
                sum(i * i for i in range(2000))

        t = threading.Thread(target=burn, daemon=True, name="burner")
        t.start()
        prof = json.loads(urllib.request.urlopen(
            f"{base}/api/profile/cpu?duration=0.5").read())
        stop.set()
        assert prof["samples"] > 10
        assert any("burn" in row["frame"] or "burner" in stack
                   for row in prof["top"]
                   for stack in [""]) or any(
                       "burner" in line for line in prof["collapsed"])

        mem1 = json.loads(urllib.request.urlopen(
            f"{base}/api/profile/memory").read())
        blob = [bytearray(1024 * 1024) for _ in range(4)]
        mem2 = json.loads(urllib.request.urlopen(
            f"{base}/api/profile/memory").read())
        assert mem2.get("total_traced_bytes", 0) > 0
        del blob
    finally:
        stop_dashboard()
        # the memory endpoint starts tracemalloc (16 frames an allocation)
        # and nothing stops it: every file this xdist worker ran afterwards
        # ran ~10x slower (tests/test_deepseek_v32_guards.py 22 s -> 262 s
        # behind this file, PR 59), whichever files the schedule put there
        import tracemalloc
        tracemalloc.stop()
