"""Cluster lifecycle CLI (VERDICT r2 #8; reference: scripts.py:676
`ray start` / stop): stand a cluster up from a shell, join it from two
successive drivers, tear it down."""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu


def _cli(*argv, timeout=60):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", *argv],
        capture_output=True, text=True, timeout=timeout, env=env)


def test_cli_start_join_two_drivers_stop(tmp_path):
    out = _cli("start", "--head", "--num-daemons", "2",
               "--resources", json.dumps({"CPU": 4}))
    assert out.returncode == 0, out.stderr[-2000:]
    address = None
    for line in out.stdout.splitlines():
        if "started at" in line:
            address = line.split("started at")[1].split()[0]
    assert address, out.stdout

    try:
        # cluster-status without a runtime sees both daemons
        st = _cli("cluster-status", "--address", address)
        assert st.returncode == 0, st.stderr[-2000:]
        nodes = json.loads(st.stdout)["nodes"]
        assert len([n for n in nodes if n["alive"]]) == 2

        daemon_pids = set()

        # driver 1 joins, runs work, disconnects
        rt = ray_tpu.init(address=address)
        try:
            handles = list(rt.cluster_backend.daemons.values())
            assert len(handles) == 2
            assert all(h.proc is None for h in handles)  # not ours

            @ray_tpu.remote
            def who():
                return os.getpid()

            pids = set(ray_tpu.get([who.remote() for _ in range(4)]))
            assert os.getpid() not in pids
            daemon_pids = {
                h.client.call("daemon_ping")["pid"] for h in handles}
        finally:
            ray_tpu.shutdown()

        # daemons survived driver 1's exit (persist mode)
        time.sleep(1.0)
        st = _cli("cluster-status", "--address", address)
        alive = [n for n in json.loads(st.stdout)["nodes"] if n["alive"]]
        assert len(alive) == 2, "daemons died with the first driver"

        # driver 2 joins the SAME daemons and runs work
        rt = ray_tpu.init(address=address)
        try:
            handles = list(rt.cluster_backend.daemons.values())
            pids2 = {h.client.call("daemon_ping")["pid"]
                     for h in handles}
            assert pids2 == daemon_pids  # same processes, not respawns

            @ray_tpu.remote
            class Counter:
                def __init__(self):
                    self.n = 0

                def bump(self):
                    self.n += 1
                    return self.n

            c = Counter.remote()
            assert ray_tpu.get(c.bump.remote()) == 1
            assert ray_tpu.get(c.bump.remote()) == 2
        finally:
            ray_tpu.shutdown()
    finally:
        stop = _cli("stop", "--address", address)
    assert stop.returncode == 0, stop.stderr[-2000:]
    # daemons + head actually gone
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        st = _cli("cluster-status", "--address", address)
        if st.returncode != 0:
            break
        time.sleep(0.3)
    assert st.returncode != 0 or not [
        n for n in json.loads(st.stdout)["nodes"] if n["alive"]]


def test_ray_tpu_up_down_subprocess_provider(tmp_path):
    """`ray-tpu up` with the subprocess provider creates a REAL head +
    worker-daemon cluster a driver can join; `down` terminates it
    (reference: `ray up` over autoscaler commands + NodeUpdater)."""
    import ray_tpu
    from ray_tpu import cluster_launcher as cl

    config = tmp_path / "cluster.yaml"
    config.write_text(
        "cluster_name: up-test\n"
        "provider:\n  type: subprocess\n"
        "head:\n  resources: {CPU: 2}\n"
        "worker:\n  resources: {CPU: 2}\n  count: 2\n")
    state = cl.up(str(config))
    try:
        assert cl.wait_for_nodes(state["address"], 2, timeout=60)
        rt = ray_tpu.init(address=state["address"])

        @ray_tpu.remote
        def pid():
            import os
            return os.getpid()

        pids = set(ray_tpu.get([pid.remote() for _ in range(4)],
                               timeout=120))
        assert pids and all(p != __import__("os").getpid()
                            for p in pids)
        ray_tpu.shutdown()
        # idempotent: a second `up` adds nothing
        state2 = cl.up(str(config))
        assert sum(1 for n in state2["nodes"]
                   if n["kind"] == "worker") == 2
    finally:
        n = cl.down(str(config))
    assert n == 3    # head + 2 workers


def test_ssh_provider_command_shape(tmp_path):
    """SshProvider builds correct bootstrap command lines (the
    NodeUpdater contract); run=False returns without executing."""
    from ray_tpu.cluster_launcher import SshProvider

    p = SshProvider(user="tpu", hosts=["h1", "h2"], key="/k",
                    repo="/srv/ray_tpu", run=False)
    head = p.create_head({"resources": {"CPU": 4}})
    assert head["address"] == "h1:6379"
    assert head["command"][:3] == ["ssh", "-o", "StrictHostKeyChecking=no"]
    assert "tpu@h1" in head["command"]
    w1 = p.create_worker("h1:6379", {"resources": {"CPU": 4, "TPU": 4}})
    w2 = p.create_worker("h1:6379", {"resources": {"CPU": 4}})
    assert w1["host"] == "h1" and w2["host"] == "h2"  # round robin
    remote = w1["command"][-1]
    assert "--head h1:6379" in remote
    assert "--host 0.0.0.0" in remote
    assert '"TPU": 4' in remote


def test_ray_tpu_attach_runs_command_against_cluster(tmp_path):
    """`ray-tpu attach <cmd>` exports RAY_TPU_ADDRESS so a bare
    ray_tpu.init() inside the command joins the running cluster
    (reference: `ray attach` + RAY_ADDRESS)."""
    import subprocess
    import sys as _sys

    from ray_tpu import cluster_launcher as cl

    config = tmp_path / "cluster.yaml"
    config.write_text(
        "cluster_name: attach-test\n"
        "provider:\n  type: subprocess\n"
        "head:\n  resources: {CPU: 2}\n"
        "worker:\n  resources: {CPU: 2}\n  count: 1\n")
    state = cl.up(str(config))
    try:
        assert cl.wait_for_nodes(state["address"], 1, timeout=60)
        repo = __import__("os").path.dirname(__import__("os").path.dirname(
            __import__("os").path.abspath(__file__)))
        script = ("import ray_tpu; rt = ray_tpu.init(); "
                  "print('NODES', len(rt.alive_nodes())); "
                  "ray_tpu.shutdown()")
        env = dict(__import__("os").environ)
        env["JAX_PLATFORMS"] = "cpu"  # the attached child owns no chip
        out = subprocess.run(
            [_sys.executable, "-m", "ray_tpu.scripts.cli", "attach",
             "--cluster", state["address"], "--",
             _sys.executable, "-c", script],
            capture_output=True, text=True, timeout=120,
            cwd=repo, env=env)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "NODES 1" in out.stdout, out.stdout
    finally:
        cl.down(str(config))
