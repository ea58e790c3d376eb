"""Training job: ``JaxTrainer(...).fit()`` whose loop steps
``make_train_step`` on fresh seeded batches.

One batch of prefetch runs on a host thread (generate, then place with
``shard_batch``); the loop waits for each step with
``block_until_ready`` and stamps its end, so the rate is whole steps
over the time between the first and the last stamp inside the window.
The first step's loss is checked against the float32 reference's loss
on the same batch, before the (donating) step consumes the parameters.
"""

from __future__ import annotations

import math
import os
import queue
import threading
import time


def run(run) -> dict:
    import ray_tpu
    from ray_tpu._private.platform import on_chip
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    tr, cfg = run.traffic, run.config
    if run.tiny:
        tr = {**tr, **tr.get("tiny_cpu", {})}
    batch, seq = int(tr["batch"]), int(tr["seq_len"])
    out: dict = {}

    def loop(_config):
        import jax
        import jax.profiler as prof
        import numpy as np
        import optax

        from benchmark.reference.loss import mean_cross_entropy
        from ray_tpu import train
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh

        mesh = None
        if tr.get("mesh"):
            devices = jax.devices()[:run.chips]
            mesh = build_mesh(MeshSpec.auto(len(devices), **tr["mesh"]),
                              devices)
        model = run.builder.build_model(cfg, seq, mesh=mesh,
                                        extra=tr.get("model_extra"))
        o = tr["optimizer"]
        ts = train.make_train_step(
            model, optax.adamw(o["lr"], weight_decay=o["weight_decay"]),
            mesh=mesh)
        params, opt_state = ts.init_fn(jax.random.key(run.jax_seed))
        jax.block_until_ready(params)
        run.phase("init_state")

        stop = threading.Event()
        ready: queue.Queue = queue.Queue(maxsize=max(1, int(tr["prefetch"])))

        def produce():
            step = 0
            while not stop.is_set():
                rng = np.random.default_rng([run.seed % (2**63), step])
                toks = rng.integers(0, cfg["vocab_size"], (batch, seq + 1),
                                    dtype=np.int32)
                item = train.shard_batch((toks[:, :-1], toks[:, 1:]), ts)
                while not stop.is_set():
                    try:
                        ready.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                step += 1

        producer = threading.Thread(target=produce, daemon=True,
                                    name="bench-batches")
        producer.start()

        def next_batch():
            t0 = time.perf_counter()
            with prof.TraceAnnotation("bench.batch_fetch"):
                item = ready.get(timeout=600)
            return item, time.perf_counter() - t0

        first, _ = next_batch()
        ref_forward = run.builder.reference_forward(cfg)
        want = float(jax.jit(
            lambda p, t, y: mean_cross_entropy(ref_forward, p, t, y))(
                params, *first))
        run.phase("reference_loss")

        losses, done, waits = [], [], []

        def step(item):
            nonlocal params, opt_state
            with prof.TraceAnnotation("bench.step_call"):
                params, opt_state, m = ts.step_fn(params, opt_state, item)
            with prof.TraceAnnotation("bench.wait.step_done"):
                jax.block_until_ready(m["loss"])
            done.append(time.perf_counter())
            losses.append(float(m["loss"]))

        step(first)
        for _ in range(int(tr["warmup_steps"]) - 1):
            step(next_batch()[0])
        run.phase("warmup_steps")

        n_warm = len(done)
        compiles0 = run.compiles.snapshot()["requests"]
        t_open = run.open_window(done[-1])
        t_close = t_open + run.seconds
        trace_s = float(tr.get("trace_seconds", 4))
        tracing = "no" if run.tracer is None else "armed"
        while True:
            now = time.perf_counter()
            typical = (done[-1] - done[-2])
            if now + typical > t_close:
                break
            if tracing == "armed" and now >= t_open + min(2.0, run.seconds / 4):
                run.tracer.start()
                tracing, t_trace = "on", now
            elif tracing == "on" and now >= t_trace + trace_s:
                run.tracer.stop()
                tracing = "done"
            item, waited = next_batch()
            waits.append(waited)
            step(item)
        if tracing == "on":
            run.tracer.stop()
        compiles1 = run.compiles.snapshot()["requests"]
        stop.set()
        out.update(
            want_loss=want, losses=losses, done=done, n_warm=n_warm,
            waits=waits, t_open=t_open, t_close=t_close,
            compiles_in_window=compiles1 - compiles0)
        train.report({"steps": len(done)})

    ray_tpu.init()
    run.phase("runtime_init")
    storage = os.path.join(run.root, ".bench_out", "train")
    os.makedirs(storage, exist_ok=True)
    resources = ({"CPU": 1, "TPU": run.chips} if on_chip() else {"CPU": 1})
    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker=resources),
        run_config=RunConfig(name=run.workload.replace(".", "-"),
                             storage_path=storage)).fit()
    ray_tpu.shutdown()
    if result.error or not out:
        raise RuntimeError(f"trainer failed: {result.error}")

    cc = tr["correctness"]
    losses, n_warm = out["losses"], out["n_warm"]
    window_losses = losses[n_warm:]
    last5 = sorted(losses[-5:])
    checks = {
        "first_step_loss": losses[0], "reference_loss": out["want_loss"],
        "loss_abs_diff": abs(losses[0] - out["want_loss"]),
        "loss_tolerance": cc["loss_tolerance"],
        "median_last5_loss": last5[len(last5) // 2],
        "all_finite": all(math.isfinite(x) for x in losses),
    }
    checks["ok"] = (checks["all_finite"]
                    and checks["loss_abs_diff"] <= cc["loss_tolerance"]
                    and checks["median_last5_loss"] < losses[0])
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    return {
        "kind": "train", "correct": bool(checks["ok"]) and failed == 0
        and len(window_losses) > 0,
        "attempted": len(window_losses), "failed": failed, "checks": checks,
        "t_open": out["t_open"], "t_close": out["t_close"],
        # stamps of finished steps from the last warm-up step on: the
        # first interval inside the window is a whole step
        "step_done": out["done"][n_warm - 1:],
        "tokens_per_step": batch * seq, "seq_len": seq,
        "input_wait_s": sum(out["waits"]),
        "compiles_in_window": out["compiles_in_window"],
    }
