"""Flagship benchmark: Llama training-step throughput on the local chip(s).

Prints ONE JSON line: tokens/sec/chip on a Llama-family model sized to the
available memory, plus model FLOPs utilization (MFU) as ``vs_baseline``
(the reference repo publishes no tok/s numbers — BASELINE.md — so the
chip's published bf16 peak is the honest denominator).

One process, and it owns the chip. With no chip it exits non-zero and
prints no number. ``--tiny-cpu`` is the explicit request for the CPU: the
same code at debug widths, for tests; what it prints carries counts and
the loss, and every device rate is null. Stages heartbeat on stderr
("HB <stage>") so a run killed from outside leaves a diagnosable tail.

Modes:
  BENCH_SERVE=1          — serving benchmark: OPEN-LOOP load through
                           ray_tpu.loadgen against a Serve app
                           (serving.requests_per_second +
                           serving.ttft_p50_s/p99_s in the json)
                           instead of the training benchmark.
  BENCH_SERVE_HTTP=1     — proxy-level serving benchmark: the same
                           metrics measured at an HTTP client through
                           the asyncio ingress (full serving path).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

# Published per-chip peaks, keyed by jax ``device_kind``. A device that is
# not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, \"TPU v5e\"",
    },
}


def peak_bf16_flops(device) -> float:
    try:
        return PEAKS[device.device_kind]["bf16_flops_per_s"]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device.device_kind!r}; "
            f"add it to bench.PEAKS with its source") from None


def _hb(stage: str) -> None:
    """Heartbeat on stderr: survives in the captured tail if we get killed."""
    print(f"HB {time.strftime('%H:%M:%S')} {stage}", file=sys.stderr, flush=True)


def _run_train(tiny_cpu: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.train.spmd import make_train_step

    dev = jax.devices()[0]

    if not tiny_cpu:
        cfg = LlamaConfig.bench_400m()
        batch, seq = 8, 2048
        steps, warmup = 10, 2
        # A/B knobs (profiling evidence drives the committed defaults)
        batch = int(os.environ.get("BENCH_BATCH", batch))
        import dataclasses
        if os.environ.get("BENCH_REMAT") == "0":
            cfg = dataclasses.replace(cfg, remat=False)
        if os.environ.get("BENCH_REMAT_POLICY"):
            cfg = dataclasses.replace(
                cfg, remat_policy=os.environ["BENCH_REMAT_POLICY"])
        if os.environ.get("BENCH_ATTN"):
            cfg = dataclasses.replace(
                cfg, attention_impl=os.environ["BENCH_ATTN"])
    else:
        cfg = LlamaConfig.debug(vocab_size=512, max_seq_len=256)
        batch, seq = 2, 256
        steps, warmup = 3, 1

    model = LlamaModel(cfg)
    ts = make_train_step(model)
    params, opt_state = ts.init_fn(jax.random.key(0))
    _hb("params initialized")

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    bt = (tokens, targets)

    for i in range(warmup):
        params, opt_state, metrics = ts.step_fn(params, opt_state, bt)
        jax.block_until_ready(metrics["loss"])
        _hb(f"warmup step {i} done" + (" (compiled)" if i == 0 else ""))

    t0 = time.perf_counter()
    for i in range(steps):
        params, opt_state, metrics = ts.step_fn(params, opt_state, bt)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    _hb(f"timed {steps} steps in {dt:.2f}s")

    n_params = cfg.num_params()
    tokens_per_sec = mfu = step_ms = None     # device rates: chip only
    if not tiny_cpu:
        tokens_per_sec = batch * seq * steps / dt
        # MFU convention: 6*N useful FLOPs/token (fwd 2N + bwd 4N); remat
        # recompute is NOT counted as useful work.
        mfu = round(tokens_per_sec * 6 * n_params / peak_bf16_flops(dev), 4)
        tokens_per_sec = round(tokens_per_sec, 1)
        step_ms = round(dt / steps * 1000, 2)

    return {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": tokens_per_sec,
        "unit": "tokens/s/chip",
        "vs_baseline": mfu,
        "platform": dev.platform,
        "detail": {
            "model_params": n_params,
            "config": "debug" if tiny_cpu else "llama_400m",
            "attention_impl": cfg.attention_impl,
            "batch": batch, "seq": seq, "steps": steps,
            "device": dev.device_kind,
            "step_ms": step_ms,
            "loss": float(metrics["loss"]),
        },
    }


@contextlib.contextmanager
def _cluster(**init_kwargs):
    """A probe's cluster: the caller's when one is up, else its own,
    shut down on the way out. A probe that fails raises — a zero row in
    a run that exits 0 reads as a measurement."""
    import ray_tpu

    own = not ray_tpu.is_initialized()
    if own:
        ray_tpu.init(num_nodes=1, resources={"CPU": 4}, **init_kwargs)
    try:
        yield ray_tpu
    finally:
        if own:
            ray_tpu.shutdown()


def _control_plane_probe(duration_s: float, drain_n: int) -> dict:
    """Quick control-plane throughput sample (a HOST rate, whatever the
    device): round-trip tasks/s through the full
    submit→schedule→execute→get loop, plus a queued submit-then-drain
    burst whose drain rate is what the result pipeline targets."""
    with _cluster() as ray_tpu:
        @ray_tpu.remote
        def _noop():
            return None

        ray_tpu.get([_noop.remote() for _ in range(50)])    # warm
        t0 = time.perf_counter()
        count = 0
        while time.perf_counter() - t0 < duration_s:
            ray_tpu.get([_noop.remote() for _ in range(100)])
            count += 100
        tasks_per_second = round(count / (time.perf_counter() - t0), 1)
        # queued drain: submit without consuming, then time the drain
        # leg alone (timing from before the submit loop would fold the
        # submit phase into the reported drain rate). The timeout turns
        # a wedged drain path into GetTimeoutError, not a hang.
        refs = [_noop.remote() for _ in range(drain_n)]
        t1 = time.perf_counter()
        ray_tpu.get(refs, timeout=120.0)
        return {"tasks_per_second": tasks_per_second,
                "drain_tasks_per_second": round(
                    drain_n / (time.perf_counter() - t1), 1)}


def _objects_probe(seconds_per_size: float) -> dict:
    """Object-plane throughput: worker-side put+get round trips at
    64KiB / 1MiB / 16MiB, reported as MiB/s moved (put + get both move
    the payload). This is the zero-copy object plane's headline row
    (docs/object_plane.md): with the shm arena attached, the 1MiB+
    points write/read the node arena in place instead of round-tripping
    pickles through daemon RPC."""
    out = {}
    with _cluster() as ray_tpu:
        @ray_tpu.remote
        def _put_get_loop(nbytes, seconds):
            import time as _time

            import numpy as _np

            import ray_tpu as _rt
            a = _np.ones(nbytes // 4, dtype=_np.float32)
            r = _rt.put(a)
            _rt.get([r])        # warm the path
            n = 0
            t0 = _time.perf_counter()
            while _time.perf_counter() - t0 < seconds:
                r = _rt.put(a)
                b = _rt.get([r])[0]
                assert b.nbytes == nbytes
                del b, r
                n += 1
            return n, _time.perf_counter() - t0

        for size, label in ((64 << 10, "put_get_64KiB_mbps"),
                            (1 << 20, "put_get_1MiB_mbps"),
                            (16 << 20, "put_get_16MiB_mbps")):
            ref = _put_get_loop.remote(size, seconds_per_size)
            n, dt = ray_tpu.get(ref, timeout=60.0)
            out[label] = round((n * size * 2) / dt / (1 << 20), 1)
    return out


def _multitenancy_probe(duration_s: float) -> dict:
    """Fair-share sample: two equal-weight tenant jobs drive the
    control-plane loop concurrently through ``run_multi_job_load``
    (fairshare admission on), reported as Jain's fairness index over
    weight-normalized goodput plus the cross-job E2E p99 ratio
    (docs/multitenancy.md)."""
    from ray_tpu.loadgen import SLO, LoadSpec, run_multi_job_load

    with _cluster(_system_config={"fairshare": True}) as ray_tpu:
        @ray_tpu.remote
        def _unit():
            return None

        def target(payload, rec, t0):
            ray_tpu.get(_unit.remote(), timeout=30.0)
            now = time.perf_counter() - t0
            rec.first_token_at = now
            rec.finished_at = now
            rec.output_tokens = 1

        ray_tpu.get([_unit.remote() for _ in range(20)])    # warm
        spec = LoadSpec(rate=120.0, duration_s=duration_s, clients=6,
                        prompt_len=1, output_len=1, stream=False,
                        timeout_s=30.0, drain_timeout_s=60.0,
                        slo=SLO(ttft_s=10.0, e2e_s=10.0))
        rep = run_multi_job_load(target, spec, jobs=2,
                                 weights=[1.0, 1.0],
                                 job_prefix="bench-tenant")
        mt = rep["multitenancy"]
        from ray_tpu._private import worker as _worker
        ten = getattr(_worker.global_runtime(), "tenancy", None)
        return {"fairness_index": round(float(mt["fairness_index"]), 4),
                "isolation_p99_ratio": round(
                    float(mt["isolation_p99_ratio"]), 3),
                "fairshare_enabled": bool(ten is not None and ten.enabled)}


def _tracing_overhead_probe() -> float:
    """Tracing overhead on the control-plane loop: balanced-order
    spans-on/spans-off pairs in one cluster, median of per-pair ratios
    (the methodology tools/perf_smoke.sh probe 4 uses; docs/
    observability.md budgets this at <=5%)."""
    import statistics

    from ray_tpu._private import config as _config
    from ray_tpu._private.config import apply_system_config

    with _cluster() as ray_tpu:
        # apply_system_config REPLACES the whole override table: capture
        # the caller's overrides so the probe's flag flips don't clobber
        # them (and a mid-probe failure can't leave tracing disabled)
        cur = _config._config
        prev_overrides = dict(cur._system) if cur is not None else {}

        @ray_tpu.remote
        def _noop():
            return None

        def burst() -> float:
            t0 = time.perf_counter()
            ray_tpu.get([_noop.remote() for _ in range(150)])
            return 150 / (time.perf_counter() - t0)

        def flip(on: bool) -> None:
            apply_system_config({**prev_overrides, "task_trace": on})

        try:
            ray_tpu.get([_noop.remote() for _ in range(50)])    # warm
            ratios = []
            for i in range(3):
                if i % 2 == 0:
                    flip(True)
                    r_on = burst()
                    flip(False)
                    r_off = burst()
                else:
                    flip(False)
                    r_off = burst()
                    flip(True)
                    r_on = burst()
                ratios.append(r_on / r_off)
        finally:
            apply_system_config(prev_overrides or None)
        return round(
            max(0.0, (1.0 - statistics.median(ratios)) * 100.0), 1)


def main() -> int:
    tiny_cpu = "--tiny-cpu" in sys.argv[1:]
    from ray_tpu._private.platform import (enable_compile_cache,
                                           force_cpu_platform, on_chip)
    if tiny_cpu:
        force_cpu_platform()
    _hb("importing jax backend")
    import jax
    dev = jax.devices()[0]       # a backend that cannot start raises here
    _hb(f"backend acquired: platform={dev.platform} "
        f"device_kind={dev.device_kind} count={len(jax.devices())}")
    if not tiny_cpu and not on_chip(dev):
        print(f"bench.py: platform is {dev.platform!r}, not 'tpu' — no "
              f"chip, no number (pass --tiny-cpu for the CPU smoke)",
              file=sys.stderr)
        return 1
    enable_compile_cache()

    if os.environ.get("BENCH_SERVE_HTTP") == "1":
        from ray_tpu.llm.bench import run_http_proxy_bench
        result = run_http_proxy_bench(tiny_cpu=tiny_cpu)
    elif os.environ.get("BENCH_SERVE") == "1":
        from ray_tpu.llm.bench import run_serving_bench
        result = run_serving_bench(tiny_cpu=tiny_cpu)
    else:
        result = _run_train(tiny_cpu)
    if os.environ.get("BENCH_CONTROL_PLANE", "1") != "0":
        # host-side sections; each carries the platform stamp so a
        # partial json consumer knows which machine it describes
        stamp = {"platform": result["platform"]}
        # the CPU smoke wants the code path, not a steady window
        window = 0.3 if tiny_cpu else 1.5
        result["control_plane"] = {
            **_control_plane_probe(window, 400 if tiny_cpu else 2000),
            # spans-on vs spans-off delta, paired + median-of-ratios in
            # ONE cluster (sequential unpaired probes are a noise
            # lottery on shared hosts — see tools/perf_smoke.sh probe 4)
            "tracing_overhead_pct": _tracing_overhead_probe(),
            **stamp}
        result["objects"] = {**_objects_probe(window), **stamp}
        result["multitenancy"] = {**_multitenancy_probe(window), **stamp}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
