"""Serving benchmark harness: open-loop requests/s + TTFT percentiles.

Reference capability: the reference measures LLM serving with
``release/llm_tests/serve/benchmark/load_test.py:802-809`` (TTFT
percentiles + output token throughput). This is the in-tree TPU-native
equivalent, driven by ``BENCH_SERVE=1 python bench.py``: an OPEN-LOOP
load (``ray_tpu.loadgen``: seeded Poisson arrivals, concurrent client
workers, streaming TTFT at the client) against a real Serve app over
the continuous-batching engine — closed-loop bursts systematically
hide queueing collapse, so every serving row reports offered-rate
requests/s, TTFT/E2E percentiles, and goodput under an SLO
(``serving.*`` keys in the BENCH json; arXiv 2605.25645 methodology).
"""

from __future__ import annotations

import os
import time


def _require_chip(dev, tiny_cpu: bool) -> None:
    """The model is chosen by the caller's explicit ``tiny_cpu``, never
    by what the platform turns out to be: a full-size run that finds no
    chip fails instead of quietly measuring the debug model."""
    from ray_tpu._private.platform import on_chip
    if not tiny_cpu and not on_chip(dev):
        raise RuntimeError(
            f"serving bench needs a TPU; platform is {dev.platform!r} "
            f"(tiny_cpu=True is the explicit CPU smoke)")


def _percentile(vals, q: float) -> float:
    """q in [0, 100]."""
    import numpy as np
    if not vals:
        return 0.0
    return float(np.percentile(vals, q, method="nearest"))


def serving_section(report: dict, tiny_cpu: bool = False) -> dict:
    """Flatten a loadgen report into the stable ``serving.*`` keys the
    BENCH json publishes (the driver greps these across rounds). From a
    CPU smoke only the counts come back: no rate or latency of a CPU
    run goes out under a serving metric's name."""
    counts = {
        "offered_rate": report["spec"]["rate"],
        "arrival": report["spec"]["arrival"],
        "clients": report["spec"]["clients"],
        "completed": report["requests"]["completed"],
        "errors": report["requests"]["errors"],
        "open_loop": True,
    }
    if tiny_cpu:
        return counts
    good = report.get("goodput", {})
    return {
        "requests_per_second": report["requests_per_second"],
        "ttft_p50_s": report["ttft_s"]["p50"],
        "ttft_p99_s": report["ttft_s"]["p99"],
        "e2e_p50_s": report["e2e_s"]["p50"],
        "e2e_p99_s": report["e2e_s"]["p99"],
        "tpot_p50_s": report["tpot_s"]["p50"],
        "output_tokens_per_second": report["output_tokens_per_second"],
        "goodput_requests_per_second": good.get("requests_per_second",
                                                0.0),
        "goodput_fraction": good.get("fraction", 0.0),
        "slo": good.get("slo", {}),
        **counts,
    }


def run_serving_bench(tiny_cpu: bool = False) -> dict:
    """Open-loop serving bench through the full Serve data plane:
    handle -> depth-aware P2C router -> replica -> engine, measured at
    the client (streaming chunks, so TTFT is real)."""
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMConfig, build_llm_app
    from ray_tpu.loadgen import SLO, HandleTarget, LoadSpec, run_load
    from ray_tpu.models.llama import LlamaConfig

    dev = jax.devices()[0]
    _require_chip(dev, tiny_cpu)

    if not tiny_cpu:
        model_cfg = LlamaConfig.bench_400m(max_seq_len=1024)
        if os.environ.get("BENCH_DECODE"):   # "pallas" = paged kernel
            import dataclasses
            # replace() re-runs __post_init__ validation: a typo'd
            # kernel name must error, not silently bench the fallback
            cfg_err = dataclasses.replace(
                model_cfg, decode_attention=os.environ["BENCH_DECODE"])
            model_cfg = cfg_err
        replicas, max_slots, max_seq = 1, 32, 1024
        spec = LoadSpec(rate=6.0, duration_s=16.0, clients=64,
                        prompt_len="uniform:32:256", output_len=64,
                        vocab=model_cfg.vocab_size, seed=0,
                        slo=SLO(ttft_s=2.0, e2e_s=30.0))
        # EVERY engine prefill bucket (32, 64, 128, 256, 512) a
        # uniform:32:256 prompt can land in — a cold bucket pays XLA
        # compile inside the timed window
        warm_lens = (32, 64, 128, 256)
    else:  # debug model, small burst
        model_cfg = None    # LLMServer debug config
        replicas, max_slots, max_seq = 2, 4, 128
        spec = LoadSpec(rate=12.0, duration_s=2.5, clients=8,
                        prompt_len="uniform:8:24", output_len=8,
                        vocab=500, seed=0,
                        slo=SLO(ttft_s=1.0, e2e_s=5.0))
        warm_lens = (8, 24)

    own = not ray_tpu.is_initialized()
    if own:
        ray_tpu.init(num_nodes=1, resources={"CPU": 8})
    cfg = LLMConfig(model_id="bench-serving", model_config=model_cfg,
                    max_slots=max_slots, max_seq=max_seq,
                    num_replicas=replicas)
    handle = serve.run(build_llm_app(cfg))

    # Warm EVERY replica's engine at the prompt buckets the load can
    # hit (plus decode + the streaming path) — a cold replica's first
    # TTFT otherwise measures XLA compile, not serving.
    controller = ray_tpu.get_actor("serve_controller")
    reps = ray_tpu.get(
        controller.get_replicas.remote(cfg.model_id))["replicas"]
    warm = [{"prompt": [1] * n, "max_tokens": 2} for n in warm_lens]
    ray_tpu.get([r.handle_request.remote("__call__", (w,), {})
                 for r in reps for w in warm], timeout=600)

    report = run_load(HandleTarget(handle, stream=True,
                                   timeout_s=spec.timeout_s), spec)
    engine_stats = ray_tpu.get(reps[0].handle_request.remote(
        "stats", (), {}), timeout=30)
    serve.shutdown()
    if own:
        ray_tpu.shutdown()

    serving = serving_section(report, tiny_cpu)
    serving["replicas"] = replicas
    return {
        "metric": "llm_serve_requests_per_second",
        "value": serving.get("requests_per_second"),
        "unit": "req/s",
        # No published reference serving numbers (BASELINE.md) — report
        # p50 TTFT (seconds) as the comparable headline alongside req/s.
        "vs_baseline": (None if tiny_cpu
                        else round(serving["ttft_p50_s"], 4)),
        "serving": serving,
        "detail": {
            **({"spec": report["spec"]} if tiny_cpu else report),
            "max_slots": max_slots,
            "config": "debug" if tiny_cpu else "llama_400m",
            "device": dev.device_kind,
            "engine_stats": engine_stats,
        },
        "platform": dev.platform,
    }


def run_http_proxy_bench(tiny_cpu: bool = False) -> dict:
    """Proxy-level serving bench: p50 TTFT + output tok/s measured AT
    THE HTTP CLIENT through the asyncio ingress + Serve data plane +
    engine — the full serving path the reference drives
    (``release/llm_tests/serve/benchmark/load_test.py:802-809``), not
    the engine-direct numbers of :func:`run_serving_bench`."""
    import http.client
    import json
    import threading

    import jax
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMConfig, build_llm_app
    from ray_tpu.models.llama import LlamaConfig

    dev = jax.devices()[0]
    _require_chip(dev, tiny_cpu)
    if not tiny_cpu:
        model_cfg = LlamaConfig.bench_400m(max_seq_len=1024)
        n_requests, concurrency, max_tokens = 64, 16, 64
        prompt_len = 64
    else:
        model_cfg = None   # LLMServer debug config
        n_requests, concurrency, max_tokens = 8, 4, 8
        prompt_len = 12

    own = not ray_tpu.is_initialized()
    if own:
        ray_tpu.init(num_nodes=1, resources={"CPU": 8})
    cfg = LLMConfig(model_config=model_cfg, max_slots=16,
                    max_seq=(128 if tiny_cpu else 1024))
    serve.run(build_llm_app(cfg))
    port = serve.start_http_proxy(port=0, max_ongoing_requests=256)

    rng = np.random.default_rng(0)
    vocab = model_cfg.vocab_size if model_cfg else 512

    def one_request(out, idx):
        prompt = [int(x) for x in
                  rng.integers(1, vocab, prompt_len)]
        body = json.dumps({"prompt": prompt, "stream": True,
                           "max_tokens": max_tokens})
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=300)
        t0 = time.perf_counter()
        ttft = None
        tokens = 0
        try:
            conn.request("POST", "/", body=body,
                         headers={"Content-Type": "application/json",
                                  "Accept": "text/event-stream"})
            resp = conn.getresponse()
            buf = b""
            while True:
                chunk = resp.read(4096)
                if not chunk:
                    break
                buf += chunk
                while b"\n\n" in buf:
                    event, buf = buf.split(b"\n\n", 1)
                    if not event.startswith(b"data: "):
                        continue
                    data = event[6:]
                    if data == b"[DONE]":
                        break
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    try:
                        if "token_id" in json.loads(data):
                            tokens += 1
                    except json.JSONDecodeError:
                        pass
        finally:
            conn.close()
        out[idx] = (ttft, tokens)

    # warmup burst (compiles prefill/decode shapes outside the timing)
    warm: dict = {}
    warm_threads = [threading.Thread(target=one_request,
                                     args=(warm, i))
                    for i in range(min(concurrency, 4))]
    for t in warm_threads:
        t.start()
    for t in warm_threads:
        t.join()

    results: dict = {}
    t0 = time.perf_counter()
    sem = threading.Semaphore(concurrency)

    def gated(idx):
        with sem:
            one_request(results, idx)

    threads = [threading.Thread(target=gated, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    ttfts = sorted(t for t, _ in results.values() if t is not None)
    total_tokens = sum(n for _, n in results.values())
    detail = {
        "requests": n_requests,
        "concurrency": concurrency,
        "output_tokens": total_tokens,
        "plane": "asyncio-http-proxy",
        "device": dev.device_kind,
    }
    value = vs_baseline = None           # device rates: chip only
    if not tiny_cpu:
        value = round(total_tokens / wall, 1)
        vs_baseline = round(_percentile(ttfts, 50), 4)
        detail.update(ttft_p50_ms=round(_percentile(ttfts, 50) * 1e3, 2),
                      ttft_p90_ms=round(_percentile(ttfts, 90) * 1e3, 2),
                      wall_s=round(wall, 3))
    serve.shutdown()
    if own:
        ray_tpu.shutdown()
    return {
        "metric": "llm_serve_http_output_tokens_per_sec",
        "value": value,
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        "detail": detail,
        "platform": dev.platform,
    }
