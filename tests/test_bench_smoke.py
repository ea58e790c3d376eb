"""bench.py's no-fallback contract: without a chip it exits non-zero and
prints no number; the CPU is used only when asked for by name
(``--tiny-cpu`` / ``tiny_cpu=True``), and what such a run prints carries
counts and the loss but no device rate."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(*args):
    return subprocess.run(
        [sys.executable, "bench.py", *args], capture_output=True, text=True,
        timeout=300, cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_bench_without_a_chip_fails_and_prints_no_number():
    proc = _bench()
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_serving_bench_refuses_the_cpu_unless_asked():
    from ray_tpu.llm.bench import run_http_proxy_bench, run_serving_bench

    for bench in (run_serving_bench, run_http_proxy_bench):
        with pytest.raises(RuntimeError, match="needs a TPU"):
            bench()


def test_serving_bench_tiny_cpu_reports_counts_only():
    """The BENCH_SERVE row is an OPEN-LOOP loadgen run through the full
    Serve data plane; from the CPU only its counts come back."""
    from ray_tpu.llm.bench import run_serving_bench

    out = run_serving_bench(tiny_cpu=True)
    assert out["metric"] == "llm_serve_requests_per_second"
    assert out["value"] is None and out["vs_baseline"] is None
    assert out["platform"] == "cpu" and "tpu_fallback" not in out
    s = out["serving"]
    assert s["open_loop"] is True and s["replicas"] == 2
    assert s["errors"] == 0 and s["completed"] > 0
    assert not any(k.endswith(("_s", "_second", "_fraction")) for k in s)
    assert out["detail"]["spec"]["stream"] is True
    assert out["detail"]["engine_stats"]["tokens_generated"] > 0


def test_train_bench_tiny_cpu_smoke():
    """bench.py end to end in a fresh process, asked for the CPU: the
    train row's rates are null, the loss is real, and the host-side
    sections (legitimate host rates) ride along with their keys."""
    proc = _bench("--tiny-cpu")
    assert proc.returncode == 0, proc.stderr[-500:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "llama_train_tokens_per_sec_per_chip"
    assert out["value"] is None and out["vs_baseline"] is None
    assert out["detail"]["step_ms"] is None
    assert out["detail"]["config"] == "debug"
    assert out["detail"]["loss"] > 0
    assert out["platform"] == "cpu" and "tpu_fallback" not in out
    cp = out["control_plane"]
    assert cp["platform"] == "cpu"
    assert cp["tasks_per_second"] > 0 and cp["drain_tasks_per_second"] > 0
    assert set(out["objects"]) >= {"put_get_64KiB_mbps", "put_get_1MiB_mbps",
                                   "put_get_16MiB_mbps"}
    mt = out["multitenancy"]
    assert 0.0 < mt["fairness_index"] <= 1.0
    assert mt["fairshare_enabled"] is True
    assert mt["isolation_p99_ratio"] >= 1.0


def test_peaks_table_has_no_default():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)

    class Dev:
        device_kind = "TPU v5 lite"

    assert bench.peak_bf16_flops(Dev) == 197e12
    Dev.device_kind = "TPU v9"
    with pytest.raises(ValueError, match="no published peak"):
        bench.peak_bf16_flops(Dev)
