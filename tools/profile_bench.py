"""Component-level timing of the bench_400m train step on the live chip.

Answers, in order: (1) what bf16 matmul TFLOP/s this chip actually
delivers (roofline sanity), (2) how step time splits across forward /
backward / optimizer, (3) what the flash-attention kernel costs vs the
XLA fallback, (4) whether per-dispatch latency is material (time vs
batch scaling).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main():
    dev = jax.devices()[0]
    print("device:", dev.device_kind, dev.platform)

    # 1. raw matmul roofline
    for m, k, n in ((8192, 8192, 8192), (16384, 1024, 4096)):
        a = jnp.ones((m, k), jnp.bfloat16)
        b = jnp.ones((k, n), jnp.bfloat16)
        f = jax.jit(lambda a, b: a @ b)
        dt = timeit(f, a, b, n=10)
        tflops = 2 * m * k * n / dt / 1e12
        print(f"matmul {m}x{k}x{n}: {dt*1e3:.2f} ms = {tflops:.1f} TFLOP/s")

    # 2. dispatch latency: tiny op round-trip
    tiny = jnp.ones((8, 8), jnp.float32)
    f = jax.jit(lambda x: x + 1)
    dt = timeit(f, tiny, n=20)
    print(f"tiny-op dispatch: {dt*1e3:.3f} ms")

    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.train.spmd import make_train_step

    cfg = LlamaConfig.bench_400m()
    batch, seq = 8, 2048
    model = LlamaModel(cfg)
    ts = make_train_step(model)
    params, opt_state = ts.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)

    # 3. forward-only loss
    loss_fn = jax.jit(lambda p, t, g: model.loss(p, t, g))
    dt_fwd = timeit(loss_fn, params, tokens, targets, n=5)
    print(f"forward loss: {dt_fwd*1e3:.1f} ms")

    # forward without remat
    cfg_nr = LlamaConfig.bench_400m()
    object.__setattr__(cfg_nr, "remat", False)
    model_nr = LlamaModel(cfg_nr)
    loss_nr = jax.jit(lambda p, t, g: model_nr.loss(p, t, g))
    dt_fwd_nr = timeit(loss_nr, params, tokens, targets, n=5)
    print(f"forward loss (no remat flag): {dt_fwd_nr*1e3:.1f} ms")

    # 4. grad step (no optimizer)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, tokens, targets)))
    dt_grad = timeit(grad_fn, params, n=5)
    print(f"value_and_grad: {dt_grad*1e3:.1f} ms")

    # full step
    def run_step(p, o):
        return ts.step_fn(p, o, (tokens, targets))
    p2, o2 = params, opt_state
    for _ in range(2):
        p2, o2, m = run_step(p2, o2)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(5):
        p2, o2, m = run_step(p2, o2)
    jax.block_until_ready(m["loss"])
    dt_step = (time.perf_counter() - t0) / 5
    print(f"full train step: {dt_step*1e3:.1f} ms")

    # 5. attention kernel alone: flash vs xla
    from ray_tpu.ops.attention import attention
    B, S, H, D = 8, 2048, 8, 128
    q = jnp.ones((B, S, H, D), jnp.bfloat16)
    k = jnp.ones((B, S, cfg.n_kv_heads, D), jnp.bfloat16)
    v = k
    fl = jax.jit(lambda q, k, v: attention(q, k, v, causal=True,
                                           use_flash=True))
    xl = jax.jit(lambda q, k, v: attention(q, k, v, causal=True,
                                           use_flash=False))
    print(f"flash attn fwd: {timeit(fl, q, k, v, n=10)*1e3:.2f} ms")
    print(f"xla attn fwd:   {timeit(xl, q, k, v, n=10)*1e3:.2f} ms")

    gfl = jax.jit(jax.grad(lambda q: fl(q, k, v).sum()))
    gxl = jax.jit(jax.grad(lambda q: xl(q, k, v).sum()))
    print(f"flash attn grad: {timeit(gfl, q, n=5)*1e3:.2f} ms")
    print(f"xla attn grad:   {timeit(gxl, q, n=5)*1e3:.2f} ms")


if __name__ == "__main__":
    main()
