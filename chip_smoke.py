#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
in ONE process (the one that owns the chip):

  serve   ray_tpu.init() -> serve.run(build_llm_app(LLMConfig(...))) ->
          handle (unary + streamed) and one request through
          serve.start_http_proxy -> Replica -> LLMServer ->
          ContinuousBatchingEngine, at the full widths of
          LlamaConfig.llama3_1b() (max_seq_len cut to 1024), once per
          decode-attention implementation ("xla", then "pallas"); then
          once with an expert model (OLMoE's block: MoEConfig with 16 =
          16 heads of 128, few experts, of 2048 x 896: a width XLA's
          ragged_dot tiles by 128, so on the chip the grouped matmuls
          are the Pallas kernel at the repo's tiling).
  pool    the engine's decode program compiled at a shape whose KV pool
          outweighs its weights: its temporaries must stay under one
          pool's bytes (the pool is written in place, never copied).
  train   JaxTrainer(...).fit() whose loop steps make_train_step on
          GPT2Config.gpt2_125m(); with >= 4 devices also a sharded
          llama3_1b step on MeshSpec.auto(4, fsdp=2, tp=2), and a look
          at where num_replicas=4 puts its engines.

Weights are random from a seed. What comes out is checked, not just
awaited: every emitted token's logit must sit within LOGIT_TOL of the
row maximum of one full forward over prompt+output, the block pool must
drain, losses must start near ln(vocab) and fall, kernels must match
their references. Times and memory are printed as set-up information
only; this script measures no rate.

No chip, no result: on any platform but "tpu" it exits 4 in seconds.
``--tiny-cpu`` is the explicit request for the CPU — the same phases at
debug widths on four virtual devices, for the sandbox and the tests.

The last line of stdout is one JSON object, printed only when every
check of every phase passed:
  {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
"""

from __future__ import annotations

import dataclasses
import faulthandler
import gc
import http.client
import json
import math
import os
import sys
import tempfile
import threading
import time
import traceback
import weakref

TINY = "--tiny-cpu" in sys.argv[1:]
DEADLINE_S = 1150           # the whole run; stacks are dumped on expiry
WAIT_S = 600.0              # any single wait (first calls compile)
LOGIT_TOL = 0.25            # emitted-token logit vs row max, in logits:
#   bf16 near-ties between the paged path and the full forward flip an
#   argmax by a few hundredths; a wrong token is off by several units
KERNEL_TOL = 2e-2           # Pallas kernel vs its reference, max |diff|
LOSS_BAND = 1.0             # |step-0 loss - ln(vocab)|; unit-variance
#   logits put it near ln(V) + 0.5
MEM_SPREAD = 1.10           # sharded step: max/min bytes_in_use per device
TRAIN_STEPS = 5
MAX_SLOTS, MAX_SEQ = 8, 1024
POOL_SLOTS = 32             # the pool phase: 32 slots x MAX_SEQ
EXIT_FAILED, EXIT_NO_CHIP = 1, 4


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------
class CompileStats:
    """This process's compiles, from JAX's own monitoring events: seconds
    in backend compiles (cache reads included), and the persistent
    cache's traffic — ``requests`` that consulted it, ``hits`` served
    from it, ``writes`` compiled and then stored."""

    _COUNTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.requests = self.hits = self.writes = 0
        self._lock = threading.Lock()   # the engine thread compiles too
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event: str, **_kw) -> None:
        name = self._COUNTS.get(event)
        if name is not None:
            with self._lock:
                setattr(self, name, getattr(self, name) + 1)

    def _on_secs(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration


class Report:
    def __init__(self, compiles: CompileStats):
        self.failed: list[str] = []
        self.compiles = compiles

    def info(self, msg: str) -> None:
        print(f"    {msg}", flush=True)

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)

    def phase(self, name: str, fn, *args) -> None:
        """Run one phase; an exception fails it and the run goes on, so
        one chip call shows every phase that is broken."""
        print(f"== {name}", flush=True)
        t0, c0 = time.perf_counter(), self.compiles.seconds
        try:
            fn(self, *args)
        except Exception:
            traceback.print_exc(file=sys.stdout)
            self.check(f"{name}: ran to its end", False)
        print(f"   ({name}: {time.perf_counter() - t0:.1f} s wall, of which "
              f"{self.compiles.seconds - c0:.1f} s compiling)", flush=True)


def mem_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats()
        parts.append(
            f"d{d.id} peak {st['peak_bytes_in_use'] / 2**30:.2f} now "
            f"{st['bytes_in_use'] / 2**30:.2f} GiB" if st
            else f"d{d.id} not reported by this backend")
    return "device memory: " + "; ".join(parts)


def abstract(x):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=getattr(a, "sharding", None)), x)


def i32(*shape):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def decode_inputs(eng) -> tuple:
    """What the engine's decode program takes after the parameters:
    tokens, pool, tables, offsets, temperatures, top-ks, the key and the
    expert load (None for a dense model)."""
    import jax
    import jax.numpy as jnp

    n = eng.max_slots
    return (i32(n), eng.kv, i32(n, eng.blocks_per_slot), i32(n),
            jax.ShapeDtypeStruct((n,), jnp.float32), i32(n), eng._rng_key,
            eng._ffn_counts and eng._ffn_counts[0])


def program_facts(jitted, *args, **static) -> dict:
    """What XLA built for ``jitted`` at these shapes, read from the
    compiled text so nobody assumes which attention ran: whether it holds
    a Mosaic (Pallas TPU) kernel, and which collectives."""
    compiled = jitted.lower(*abstract(args), **static).compile()
    text = compiled.as_text()
    return {"mosaic": "tpu_custom_call" in text,
            "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
            "collectives": [op for op in ("all-gather", "reduce-scatter",
                                          "all-reduce", "all-to-all",
                                          "collective-permute")
                            if f" {op}(" in text or f" {op}-start(" in text]}


def join_all(threads, what: str) -> None:
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT_S)
        if t.is_alive():
            raise TimeoutError(f"{what}: {t.name} still running after "
                               f"{WAIT_S:.0f} s")


# ---------------------------------------------------------------------------
# sizes: --tiny-cpu cuts WIDTHS; lengths, slots and the request mix stay
# ---------------------------------------------------------------------------
def sizes() -> dict:
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.models.moe import MoEConfig

    if TINY:
        return dict(
            serve_cfg=LlamaConfig.debug(vocab_size=512, max_seq_len=MAX_SEQ),
            moe_cfg=MoEConfig.debug_olmoe(vocab_size=512,
                                          max_seq_len=MAX_SEQ),
            pool_cfg=LlamaConfig.debug(vocab_size=512, max_seq_len=MAX_SEQ),
            max_tokens=6,
            gpt2=GPT2Config.debug(), gpt2_batch=(4, 128),
            sharded_cfg=LlamaConfig(
                vocab_size=512, dim=64, n_layers=2, n_heads=4,
                n_kv_heads=2, ffn_dim=128, max_seq_len=128, remat=False),
            sharded_batch=(8, 128),
            kernel_shapes=((2, 8, 4, 64),), kernel_seq=256,
            attention_cases=((256, 8, 4, 64, True), (197, 4, 4, 64, False)))
    llama = LlamaConfig.llama3_1b()
    return dict(
        serve_cfg=dataclasses.replace(llama, max_seq_len=MAX_SEQ),
        # OLMoE's block at its published attention (16 = 16 heads of
        # 128) and hidden width; few experts, layers and vocabulary rows;
        # experts of 896 (OLMoE's 1024 is tiled 512 wide by XLA and keeps
        # ragged_dot: ops.moe_dispatch.grouped_matmul_impl)
        moe_cfg=MoEConfig.debug_olmoe(
            vocab_size=4096, max_seq_len=MAX_SEQ, dim=2048, n_heads=16,
            n_kv_heads=16, ffn_dim=896, num_experts=16, expert_top_k=4),
        # the serve cells' attention (8 KV heads of 128) over narrow
        # layers: 0.5 GiB of pool at POOL_SLOTS x MAX_SEQ against 0.14
        # GiB of bf16 weights, of which the temporaries hold no copy
        pool_cfg=LlamaConfig(
            vocab_size=4096, dim=1024, n_layers=4, n_heads=8, n_kv_heads=8,
            ffn_dim=4096, max_seq_len=MAX_SEQ, remat=False),
        max_tokens=12,
        gpt2=GPT2Config.gpt2_125m(), gpt2_batch=(8, 1024),
        sharded_cfg=dataclasses.replace(llama, max_seq_len=2048),
        sharded_batch=(8, 2048),
        # (slots, H, Hkv, D): llama3_1b's and bench_400m's attention, and
        # what the benchmark's serve cells decode (mistral-7b at 32 slots)
        # and OLMoE's 16 = 16 heads of 128
        kernel_shapes=((MAX_SLOTS, 32, 8, 64), (MAX_SLOTS, 8, 4, 128),
                       (32, 32, 8, 128), (32, 16, 16, 128)),
        kernel_seq=2048,
        # (S, H, Hkv, D, causal) of the fused training attention:
        # gpt2-medium.train_1chip's, the kernel shapes' widths at 2,048 (a row block's spans split into
        # tiles there), the longest sequence that stays in VMEM, ViT-B's
        # 197 patches (not causal, ending inside a block), and one past
        # residency, which takes the scan
        attention_cases=((1024, 16, 16, 64, True), (2048, 32, 8, 64, True),
                         (2048, 8, 4, 128, True),
                         (2048, 32, 8, 128, True), (2048, 16, 16, 128, True),
                         (4096, 16, 16, 64, True), (2816, 8, 4, 128, False),
                         (197, 12, 12, 64, False), (8192, 8, 8, 128, True)))


# ---------------------------------------------------------------------------
# phase: native libraries (built on first use from what git commits)
# ---------------------------------------------------------------------------
def native_phase(rep: Report) -> None:
    from ray_tpu import cpp_client, native_store
    from ray_tpu._private.fast_lane import CoreHandle

    loaded = {"shm_store": native_store.available(),
              "cpp_client": cpp_client._load() is not None,
              "daemon_core": CoreHandle()._lib is not None}
    rep.check("every native library built and loaded", all(loaded.values()),
              ", ".join(f"{k}={'yes' if v else 'NO'}"
                        for k, v in loaded.items()))


# ---------------------------------------------------------------------------
# phase: Pallas kernels against their references
# ---------------------------------------------------------------------------
def kernels_phase(rep: Report, sz: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private.platform import on_chip, pallas_interpret
    from ray_tpu.ops.attention import (_stays_resident, flash_attention,
                                       packed_attention, reference_attention)
    from ray_tpu.ops.paged_attention import (
        default_impl, packed_row, paged_decode_attention_pallas,
        paged_decode_attention_reference)

    interpret = pallas_interpret()
    rep.check("Pallas interprets only on a CPU backend",
              interpret == (jax.default_backend() == "cpu"),
              f"interpret={interpret}, backend={jax.default_backend()}")
    picked = {(D, Hkv): default_impl(D, Hkv)
              for _, _, Hkv, D in sz["kernel_shapes"]}
    rep.check("unforced, paged decode takes the kernel exactly on the chip",
              set(picked.values()) == {"pallas" if on_chip() else "xla"},
              f"default_impl by (head_dim, kv_heads): {picked}, "
              f"on chip: {on_chip()}")

    def max_diff(a, b) -> float:
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    rng = np.random.default_rng(0)
    bs, S = 32, sz["kernel_seq"]
    for B, H, Hkv, D in sz["kernel_shapes"]:
        shape = f"H{H}/Hkv{Hkv}/D{D}"
        maxb = S // bs
        nb = B * maxb + 1
        # the pool as a model holds it: heads of 64 two to a 128-lane row
        row = packed_row(Hkv, D)
        args = (normal(B, H, D), normal(nb, bs, *row), normal(nb, bs, *row),
                jnp.asarray(rng.permutation(nb - 1)[:B * maxb]
                            .reshape(B, maxb), jnp.int32),
                jnp.asarray(rng.integers(1, S + 1, B), jnp.int32))
        err = max_diff(
            paged_decode_attention_pallas(*args, interpret=interpret),
            paged_decode_attention_reference(*args))
        rep.check(f"paged decode kernel {shape} matches its reference",
                  err <= KERNEL_TOL, f"max |diff| {err:.4f} <= {KERNEL_TOL}")
        facts = program_facts(paged_decode_attention_pallas, *args,
                              interpret=interpret)
        rep.check(f"paged decode kernel {shape} is a Mosaic kernel "
                  f"unless interpreted", facts["mosaic"] != interpret,
                  f"tpu_custom_call in compiled text: {facts['mosaic']}")

    # the fused training attention, forward AND backward: o, dq, dk, dv
    # against the reference's own, at every kernel shape's head width and
    # at the gate's edges, in the projections' layout (two 64-wide heads
    # a lane tile, a GQA group's kv head in either slot), timed; where
    # every head has its kv head and the kernels run, the packed entry
    # too, whose o and ONE gradient are the three arrays' to the bit
    for S, H, Hkv, D, causal in sz["attention_cases"]:
        shape = (f"S{S}/H{H}/Hkv{Hkv}/D{D}/"
                 f"{'causal' if causal else 'full'}")
        qkv = (normal(1, S, H, D), normal(1, S, Hkv, D),
               normal(1, S, Hkv, D))
        weigh = normal(1, S, H, D)

        def o_and_grads(attn):
            def weighed(q, k, v):
                o = attn(q, k, v)
                return (o * weigh).astype(jnp.float32).sum(), o
            (_, o), grads = jax.value_and_grad(
                weighed, argnums=(0, 1, 2), has_aux=True)(*qkv)
            return (o, *grads)

        fused = jax.jit(lambda: o_and_grads(
            lambda q, k, v: flash_attention(q, k, v, causal)))
        want = o_and_grads(
            lambda q, k, v: reference_attention(q, k, v, causal=causal))
        got = jax.block_until_ready(fused())
        t0 = time.perf_counter()
        jax.block_until_ready(fused())
        ms = (time.perf_counter() - t0) * 1e3
        errs = {name: (max_diff(a, b), float(jnp.max(jnp.abs(b))))
                for name, a, b in zip(("o", "dq", "dk", "dv"), got, want)}
        rep.check(f"fused attention {shape} matches its reference, forward "
                  f"and backward",
                  all(err <= KERNEL_TOL * max(1.0, peak)
                      for err, peak in errs.values()),
                  "max |diff| (of a peak of) " + ", ".join(
                      f"{name} {err:.4f} ({peak:.2f})"
                      for name, (err, peak) in errs.items())
                  + f" <= {KERNEL_TOL} x max(1, peak); forward + backward "
                  f"{ms:.2f} ms")
        # a head's sequence that does not stay in VMEM takes the scan
        kernels = 2 if _stays_resident(S, S, D, jnp.bfloat16, causal) else 0
        if H == Hkv and kernels:
            def packed(x):
                o = packed_attention(x, H, causal=causal, use_flash=True)
                return (o.reshape(weigh.shape) * weigh).astype(
                    jnp.float32).sum(), o
            (_, o), grad = jax.jit(jax.value_and_grad(packed, has_aux=True))(
                jnp.stack(qkv, 2).reshape(1, S, -1))
            grad = grad.reshape(1, S, 3, H, D)
            diffs = [max_diff(o.reshape(weigh.shape), got[0]),
                     *(max_diff(grad[:, :, n], got[1 + n]) for n in range(3))]
            rep.check(f"packed attention {shape} gives the three arrays' o "
                      f"and gradient", max(diffs) == 0.0,
                      f"max |diff| of o, dq, dk, dv: {diffs}")
        mosaic = fused.lower().compile().as_text().count("tpu_custom_call")
        rep.check(f"fused attention {shape} is {kernels} Mosaic kernels "
                  f"unless interpreted",
                  mosaic == (0 if interpret else kernels),
                  f"tpu_custom_call in compiled text: {mosaic} times")


# ---------------------------------------------------------------------------
# phase: the decode program keeps its pool in place
# ---------------------------------------------------------------------------
def pool_phase(rep: Report, sz: dict) -> None:
    """The engine's decode program where the pool outweighs the weights.
    Scanned over as the layer scan's ``xs``/``ys`` the pool was copied
    whole around the loop and sliced out and back a layer: 1.3 pools of
    temporaries, half of a decode step on the chip (PERF.md, PR 27).
    Carried whole it is written in place. What was left then were the
    weights' bf16 copies, cast from float32 by every step; the engine
    now holds its matmul weights in bf16 (``serving_params``), so the
    temporaries hold no copy of any weight (PERF.md, PR 31). The verdict
    is the chip's: a CPU backend widens a bf16 scatter to float32 and
    back, two float32 stacks."""
    import jax

    from ray_tpu._private.platform import on_chip
    from ray_tpu.llm.engine import ContinuousBatchingEngine
    from ray_tpu.models import model_for

    model = model_for(sz["pool_cfg"])
    eng = ContinuousBatchingEngine(
        model, jax.jit(model.init)(jax.random.key(0)),
        max_slots=POOL_SLOTS, max_seq=MAX_SEQ)

    facts = program_facts(eng._decode, eng.params, *decode_inputs(eng))
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.kv))
    smallest = min(eng.params["layers"][k].nbytes
                   for k in model.MATMUL_LAYER_LEAVES)
    detail = (f"temporaries {facts['temp_bytes'] / 2**20:.2f} MiB, pool "
              f"{pool_bytes / 2**20:.1f} MiB {tuple(eng.kv['k'].shape)} x 2, "
              f"smallest matmul weight {smallest / 2**20:.1f} MiB of "
              f"{eng.stats['param_bytes'] / 2**20:.1f} MiB held, "
              f"decode attention {eng.decode_attention_impl!r}")
    if on_chip():
        rep.check("decode program's temporaries are under one pool's bytes",
                  facts["temp_bytes"] < pool_bytes, detail)
        rep.check("decode program's temporaries hold no copy of a weight",
                  facts["temp_bytes"] < smallest, detail)
    else:
        rep.info(f"decode program, not judged off the chip: {detail}")


# ---------------------------------------------------------------------------
# phase: Serve -> engine
# ---------------------------------------------------------------------------
def local_servers(model_id: str) -> list:
    """The LLMServer instances behind a deployment. Replicas are
    in-process actors of the chip-owning process (serve/controller.py),
    which is what lets a smoke look at the engine it just drove."""
    import ray_tpu
    from ray_tpu._private import worker

    controller = ray_tpu.get_actor("serve_controller")
    reps = ray_tpu.get(controller.get_replicas.remote(model_id),
                       timeout=WAIT_S)["replicas"]
    rt = worker.global_runtime()
    return [rt._actor_executors[r._actor_id].instance._callable
            for r in reps]


def make_prompts(vocab: int) -> dict:
    import numpy as np
    rng = np.random.default_rng(0)

    def tok(n):
        return [int(t) for t in rng.integers(1, vocab, n)]

    prefix = tok(96)                      # three full 32-token blocks
    return {
        "short": tok(40),                 # bucket 64, unary handle
        "prefix_a": prefix + tok(20),     # bucket 128; seals the prefix
        "long": tok(600),                 # > largest bucket (512): chunked
        "prefix_b": prefix + tok(30),     # reuses the sealed prefix
        "http": tok(40),                  # through the HTTP proxy
    }


def ask_unary(handle, prompt, n):
    r = handle.remote({"prompt": prompt, "max_tokens": n}).result(
        timeout=WAIT_S)
    return r["token_ids"], r["finish_reason"]


def ask_streamed(handle, prompt, n):
    gen = handle.options(stream=True).remote(
        {"prompt": prompt, "max_tokens": n, "stream": True})
    toks, reason = [], None
    while True:
        try:
            chunk = gen.next(timeout=WAIT_S)
        except StopIteration:
            return toks, reason
        if "token_id" in chunk:
            toks.append(chunk["token_id"])
        elif chunk.get("done"):
            reason = chunk["finish_reason"]


def ask_http(port, prompt, n):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=WAIT_S)
    try:
        conn.request("POST", "/", json.dumps(
            {"prompt": prompt, "max_tokens": n}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"HTTP {resp.status}: {body[:300]!r}")
    r = json.loads(body)
    return r["token_ids"], r["finish_reason"]


def check_tokens(rep: Report, server, prompts: dict, got: dict) -> int:
    """One full forward over prompt+output per request: the logit of each
    emitted token against the maximum of its row. Returns tokens checked."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def gaps(params, tokens, following):
        logits = server.model.apply(params, tokens)
        picked = jnp.take_along_axis(
            logits, following[..., None], axis=-1)[..., 0]
        return (jnp.max(logits, axis=-1) - picked)[0], \
            jnp.all(jnp.isfinite(logits))

    worst, finite, total = 0.0, True, 0
    for name, (toks, _) in got.items():
        seq = prompts[name] + list(toks)
        padded = np.zeros((1, MAX_SEQ), np.int32)
        padded[0, :len(seq)] = seq
        gap, fin = gaps(server.engine.params, padded,
                        np.roll(padded, -1, axis=1))
        first = len(prompts[name]) - 1
        gap = np.asarray(gap)[first:first + len(toks)]
        worst, total = max(worst, float(gap.max())), total + len(toks)
        finite = finite and bool(fin)
    rep.check("full-forward logits finite", finite,
              f"[1, {MAX_SEQ}, {server.model.cfg.vocab_size}] per request")
    rep.check("every emitted token within tolerance of its row maximum",
              worst <= LOGIT_TOL,
              f"largest gap {worst:.4f} <= {LOGIT_TOL} over {total} tokens")
    return total


def check_engine(rep: Report, eng, impl: str) -> None:
    """What the engine counted, that its pool drained, and which of its
    programs hold a Mosaic kernel."""
    import jax

    from ray_tpu._private.platform import on_chip

    st = eng.stats
    # a hit is taken in whole runs of blocks (docs/serving.md, "Blocks
    # and runs"): all 96 shared tokens where a block is a copy
    a_run = st["kv_run_blocks"] * eng.block_size
    reused = 96 // a_run * a_run
    rep.check("prefix reuse and chunked prefill ran",
              st["prefix_prefills"] == (reused > 0)
              and st["prefix_tokens_reused"] == reused
              and st["prefills"] == 6 and st["preemptions"] == 0,
              f"stats {st} (6 prefills = 4 whole prompts + 2 chunks of "
              f"the 600-token one; {reused} of 96 shared tokens reused in "
              f"runs of {st['kv_run_blocks']} blocks)")
    deadline = time.monotonic() + 10     # done is set just before unref
    while (eng.pool.num_free != eng.num_blocks
           and time.monotonic() < deadline):
        time.sleep(0.05)
    rep.check("block pool drained", eng.pool.num_free == eng.num_blocks,
              f"{eng.pool.num_free} of {eng.num_blocks} blocks free")

    L, _, bs, Hkv, D = eng.kv["k"].shape
    prefix_kv = jax.ShapeDtypeStruct((L, 1, 4 * bs, Hkv, D),
                                     eng.kv["k"].dtype)
    programs = {
        "decode": program_facts(eng._decode, eng.params,
                                *decode_inputs(eng)),
        "prefill[1x64]": program_facts(
            eng._prefill, eng.params, i32(1, 64), i32(1)),
        "prefill_prefix[1x32 after 128]": program_facts(
            eng._prefill_prefix, eng.params, i32(1, 32), prefix_kv,
            prefix_kv, i32(1), i32(1)),
    }
    for name, facts in programs.items():
        rep.info(f"program {name}: Mosaic kernel "
                 f"{'yes' if facts['mosaic'] else 'no'}")
    rep.info("compiled specializations: " + ", ".join(
        f"{k} {getattr(eng, '_' + k)._cache_size()}" for k in
        ("decode", "prefill", "prefill_prefix", "insert", "gather",
         "sample")))
    rep.check("decode holds a Mosaic kernel exactly when it should",
              programs["decode"]["mosaic"] == (impl == "pallas"
                                               and on_chip())
              and eng.decode_attention_impl == impl,
              f"decode_attention={impl!r}, on chip: {on_chip()}, "
              f"engine built with {eng.decode_attention_impl!r}, "
              f"tpu_custom_call in compiled text: "
              f"{programs['decode']['mosaic']}")


def check_released(rep: Report, alive) -> None:
    """The next phase needs the memory back: the replica is gone, so its
    engine (weights, pool, loop thread; ``alive`` is a weak reference to
    it) must be too."""
    deadline = time.monotonic() + 60
    while alive() is not None and time.monotonic() < deadline:
        time.sleep(0.2)
        gc.collect()
    rep.check("engine released after serve.shutdown()", alive() is None)


def serve_phase(rep: Report, sz: dict, impl: str, outputs: dict) -> None:
    import jax

    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMConfig, build_llm_app

    cfg = dataclasses.replace(sz["serve_cfg"], decode_attention=impl)
    model_id = f"smoke-{impl}"
    n = sz["max_tokens"]
    prompts = make_prompts(cfg.vocab_size)
    got: dict = {}

    def run(name, ask, *args):
        def target():
            got[name] = ask(*args, prompts[name], n)
        return threading.Thread(target=target, name=name, daemon=True)

    handle = serve.run(build_llm_app(LLMConfig(
        model_id=model_id, model_config=cfg, max_slots=MAX_SLOTS,
        max_seq=MAX_SEQ)))
    try:
        port = serve.start_http_proxy(port=0, request_timeout_s=WAIT_S)
        server = local_servers(model_id)[0]
        # wave 1: three buckets at once; wave 2 once prefix_a has sealed
        # the shared blocks
        join_all([run("short", ask_unary, handle),
                  run("prefix_a", ask_unary, handle),
                  run("long", ask_streamed, handle)], "wave 1")
        join_all([run("prefix_b", ask_streamed, handle),
                  run("http", ask_http, port)], "wave 2")
        rep.check("five requests answered: unary, streamed, HTTP",
                  set(got) == set(prompts)
                  and all(1 <= len(t) <= n and r in ("length", "stop")
                          for t, r in got.values()),
                  ", ".join(f"{k}:{len(t)}tok/{r}"
                            for k, (t, r) in got.items()))
        total = check_tokens(rep, server, prompts, got)
        outputs[impl] = {k: list(t) for k, (t, _) in got.items()}
        if impl != "xla" and "xla" in outputs:
            same = sum(a == b for k in got for a, b in
                       zip(outputs[impl][k], outputs["xla"][k]))
            rep.info(f"tokens identical to the xla path: {same}/{total}")
        check_engine(rep, server.engine, impl)
        rep.info(mem_line(jax.devices()[:1]))
        alive = weakref.ref(server.engine)
        del server
    finally:
        serve.shutdown()
    check_released(rep, alive)
    rep.info(mem_line(jax.devices()[:1]))


# ---------------------------------------------------------------------------
# phase: Serve -> engine, an expert model
# ---------------------------------------------------------------------------
def serve_moe_phase(rep: Report, sz: dict) -> None:
    """The same path with a ``MoEConfig``: Serve picks the expert model,
    the engine prefills (a bucket and a chunked prompt) and decodes it
    through the platform's paged attention, and its dropless FFN
    processed every chosen expert."""
    from ray_tpu import serve
    from ray_tpu._private.platform import on_chip
    from ray_tpu.llm.serving import LLMConfig, build_llm_app
    from ray_tpu.models.moe import MoEModel

    cfg = sz["moe_cfg"]
    model_id = "smoke-moe"
    n = sz["max_tokens"]
    prompts = {k: v for k, v in make_prompts(cfg.vocab_size).items()
               if k in ("short", "long")}
    handle = serve.run(build_llm_app(LLMConfig(
        model_id=model_id, model_config=cfg, max_slots=MAX_SLOTS,
        max_seq=MAX_SEQ)))
    try:
        server = local_servers(model_id)[0]
        rep.check("Serve built the expert model from its config",
                  type(server.model) is MoEModel
                  and "e_gate" in server.engine.params["layers"],
                  type(server.model).__name__)
        got = {"short": ask_unary(handle, prompts["short"], n),
               "long": ask_streamed(handle, prompts["long"], n)}
        rep.check("two requests answered: unary, streamed (chunked prefill)",
                  all(len(t) == n for t, _ in got.values()),
                  ", ".join(f"{k}:{len(t)}tok/{r}"
                            for k, (t, r) in got.items()))
        check_tokens(rep, server, prompts, got)
        eng = server.engine
        st = eng.stats
        rep.check("the expert FFN dropped nothing in decode",
                  st["moe_assignments"] == st["moe_assignments_expected"]
                  > 0, f"{st['moe_assignments']} rows of "
                  f"{st['moe_assignments_expected']} over "
                  f"{st['decode_steps']} decode steps")
        impl = "pallas" if on_chip() else "xla"
        rep.check("decode attention is the platform's",
                  eng.decode_attention_impl == impl,
                  f"engine built with {eng.decode_attention_impl!r}")
        grouped = "pallas_gmm" if on_chip() else "ragged_dot"
        rep.check("the expert FFN's grouped matmuls are the platform's",
                  st["moe_grouped_impl"] == grouped,
                  f"{st['moe_grouped_impl']!r}, tilings gate/up/down "
                  + "/".join(repr(st[f"moe_gmm_tiling_{c}"])
                             for c in ("gate", "up", "down")))
        alive = weakref.ref(eng)
        del server, eng
    finally:
        serve.shutdown()
    check_released(rep, alive)


# ---------------------------------------------------------------------------
# phase: JaxTrainer on one device
# ---------------------------------------------------------------------------
def fit(loop, name: str, resources: dict):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        return JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=1,
                                         resources_per_worker=resources),
            run_config=RunConfig(name=name, storage_path=tmp)).fit()


def check_losses(rep: Report, result, vocab: int) -> list:
    """The checks every train phase shares; returns the reported steps."""
    steps = [h["metrics"] for h in result.metrics_history
             if "loss" in h["metrics"]]
    rep.check("trainer finished without error",
              result.error is None and len(steps) >= TRAIN_STEPS,
              f"error={result.error}, {len(steps)} steps reported")
    if not steps:
        return steps
    losses = [s["loss"] for s in steps]
    norms = [s["grad_norm"] for s in steps]
    rep.check("step-0 loss near ln(vocab)",
              abs(losses[0] - math.log(vocab)) <= LOSS_BAND,
              f"{losses[0]:.4f} vs ln({vocab}) = {math.log(vocab):.4f} "
              f"+- {LOSS_BAND}")
    rep.check("loss falls on the fixed batch, all values finite",
              losses[-1] < losses[0]
              and all(math.isfinite(x) for x in losses + norms),
              "loss " + " ".join(f"{x:.4f}" for x in losses)
              + "; grad norm " + " ".join(f"{x:.3f}" for x in norms))
    return steps


def train_phase(rep: Report, sz: dict) -> None:
    from ray_tpu._private.platform import on_chip

    cfg, batch_shape = sz["gpt2"], sz["gpt2_batch"]

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu import train
        from ray_tpu.models.gpt2 import GPT2Model

        ts = train.make_train_step(GPT2Model(cfg))
        params, opt_state = ts.init_fn(jax.random.key(0))
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, batch_shape), jnp.int32)
        batch = (tokens, jnp.roll(tokens, -1, axis=1))
        train.report({"facts": program_facts(ts.step_fn, params, opt_state,
                                             batch)})
        for step in range(TRAIN_STEPS):
            params, opt_state, m = ts.step_fn(params, opt_state, batch)
            train.report({"step": step, "loss": float(m["loss"]),
                          "grad_norm": float(m["grad_norm"])})

    result = fit(loop, "chip_smoke_gpt2",
                 {"CPU": 1, "TPU": 1} if on_chip() else {"CPU": 1})
    check_losses(rep, result, cfg.vocab_size)
    facts = result.metrics_history[0]["metrics"]["facts"]
    rep.check("the one-chip train step holds the fused attention kernels "
              "exactly on the chip", facts["mosaic"] == on_chip(),
              f"train_step[gpt2 {batch_shape[0]}x{batch_shape[1]}, head_dim "
              f"{cfg.head_dim}, no mesh]: tpu_custom_call in compiled text: "
              f"{facts['mosaic']}, on chip: {on_chip()}")


# ---------------------------------------------------------------------------
# phase (>= 4 devices): sharded Llama step on fsdp=2 x tp=2
# ---------------------------------------------------------------------------
def sharded_phase(rep: Report, sz: dict) -> None:
    from ray_tpu._private.platform import on_chip

    cfg, batch_shape = sz["sharded_cfg"], sz["sharded_batch"]

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu import train
        from ray_tpu.models.llama import LlamaModel
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh

        devices = jax.devices()[:4]
        mesh = build_mesh(MeshSpec.auto(4, fsdp=2, tp=2), devices)
        ts = train.make_train_step(LlamaModel(cfg, mesh=mesh), mesh=mesh)
        params, opt_state = ts.init_fn(jax.random.key(0))
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, batch_shape), jnp.int32)
        batch = train.shard_batch((tokens, jnp.roll(tokens, -1, axis=1)), ts)
        leaves = jax.tree.leaves(params)
        train.report({
            "facts": program_facts(ts.step_fn, params, opt_state, batch),
            "grid": [[f"d{d.id}@{getattr(d, 'coords', None)}" for d in row]
                     for row in mesh.devices.reshape(2, 2)],
            "leaves": len(leaves),
            "min_span": min(len({s.device for s in leaf.addressable_shards})
                            for leaf in leaves),
            "split": sum(leaf.addressable_shards[0].data.size < leaf.size
                         for leaf in leaves)})
        for step in range(TRAIN_STEPS):
            params, opt_state, m = ts.step_fn(params, opt_state, batch)
            train.report({"step": step, "loss": float(m["loss"]),
                          "grad_norm": float(m["grad_norm"])})
        train.report({"mem": [
            (d.memory_stats() or {}).get("bytes_in_use") for d in devices]})

    result = fit(loop, "chip_smoke_sharded",
                 {"CPU": 1, "TPU": 4} if on_chip() else {"CPU": 1})
    check_losses(rep, result, cfg.vocab_size)
    setup = result.metrics_history[0]["metrics"]
    rep.info(f"mesh fsdp x tp = {setup['grid']} (build_mesh takes "
             f"jax.devices() in enumeration order)")
    rep.check("every parameter leaf spans four distinct devices",
              setup["min_span"] == 4,
              f"{setup['leaves']} leaves, fewest devices under one leaf "
              f"{setup['min_span']}, {setup['split']} leaves split (the "
              f"rest replicated)")
    rep.info(f"program train_step[llama {batch_shape[0]}x{batch_shape[1]}]: "
             f"Mosaic kernel {'yes' if setup['facts']['mosaic'] else 'no'}, "
             f"collectives {setup['facts']['collectives']}")
    rep.check("the step communicates", bool(setup["facts"]["collectives"]))
    mem = result.metrics_history[-1]["metrics"]["mem"]
    if on_chip():
        rep.check("per-device memory in use is of one size",
                  max(mem) <= MEM_SPREAD * min(mem),
                  "bytes_in_use " + " ".join(f"{b / 2**30:.2f}" for b in mem)
                  + f" GiB, max/min <= {MEM_SPREAD}")
    else:
        rep.info("per-device memory: not reported by this backend")


# ---------------------------------------------------------------------------
# phase (>= 4 devices): where do four replicas land?
# ---------------------------------------------------------------------------
def placement_phase(rep: Report, sz: dict) -> None:
    """Observation for ROADMAP R5, not a placement feature: LLMServer
    names no device, so this prints where its engines end up."""
    import jax

    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMConfig, build_llm_app

    handle = serve.run(build_llm_app(LLMConfig(
        model_id="smoke-placement", num_replicas=4, max_slots=2,
        max_seq=128)))
    try:
        got: dict = {}

        def target(i):
            got[i] = ask_unary(handle, [1 + i] * 8, 4)

        join_all([threading.Thread(target=target, args=(i,), daemon=True,
                                   name=f"placement-{i}")
                  for i in range(8)], "placement requests")
        where = [sorted({d.id for leaf in jax.tree.leaves(s.engine.params)
                         for d in leaf.devices()})
                 for s in local_servers("smoke-placement")]
        rep.check("four debug-width replicas answered eight requests",
                  len(where) == 4 and len(got) == 8,
                  f"engine parameters on devices {where}")
    finally:
        serve.shutdown()


# ---------------------------------------------------------------------------
# phase: one process for the chip
# ---------------------------------------------------------------------------
def processes_phase(rep: Report) -> None:
    """No child may hold the accelerator runtime. A process pinned with
    JAX_PLATFORMS=cpu never maps libtpu (checked in the sandbox), so the
    map of each descendant says whether it could have touched the chip."""
    import jax

    parent: dict = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                # pid (comm) state ppid ...; comm may hold spaces
                parent[int(pid)] = int(
                    f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue           # exited while we looked

    def descends(pid: int) -> bool:
        while pid in parent:
            pid = parent[pid]
            if pid == os.getpid():
                return True
        return False

    def maps_libtpu(pid) -> bool:
        try:
            with open(f"/proc/{pid}/maps") as f:
                return "libtpu" in f.read()
        except OSError:
            return False

    children = [p for p in parent if descends(p)]
    holders = [p for p in children if maps_libtpu(p)]
    rep.check("no child process maps libtpu", not holders,
              f"{len(children)} descendants, {len(holders)} with libtpu"
              + (f": {holders}" if holders else ""))
    if jax.default_backend() == "tpu":
        rep.check("this process does (the probe sees what it looks for)",
                  maps_libtpu("self"))


# ---------------------------------------------------------------------------
# phase: compile cache
# ---------------------------------------------------------------------------
def cache_phase(rep: Report, cache_dir: str) -> None:
    import jax

    from ray_tpu._private.platform import REPO_ROOT

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    want = env or os.path.join(REPO_ROOT, ".jax_cache")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    c = rep.compiles
    rep.check("compile cache where it was asked to be",
              cache_dir == want
              and jax.config.jax_compilation_cache_dir == want
              and entries > 0,
              f"{cache_dir} (JAX_COMPILATION_CACHE_DIR "
              f"{'set' if env else 'unset'}), {entries} files")
    rep.info(f"compile cache this run: {c.requests} requests, "
             f"{c.hits} hits, {c.writes} written (a second run of the same "
             f"tree should write none)")


# ---------------------------------------------------------------------------
def main() -> int:
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    from ray_tpu._private.platform import (enable_compile_cache,
                                           force_cpu_platform, on_chip)
    if TINY:
        force_cpu_platform(4)
    import jax

    devices = jax.devices()     # a backend that cannot start raises here
    dev = devices[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"device_count={len(devices)}", flush=True)
    if not TINY and not on_chip(dev):
        print(f"chip_smoke: platform is {dev.platform!r}, not 'tpu' — no "
              f"chip, no result (--tiny-cpu is the explicit CPU run)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    cache_dir = enable_compile_cache()
    rep = Report(CompileStats())
    from ray_tpu.parallel.topology import detect_local_topology
    print(f"jax {jax.__version__}, python {sys.version.split()[0]}, "
          f"pid {os.getpid()}, topology {detect_local_topology()}, "
          f"{'TINY CPU WIDTHS' if TINY else 'full widths'}", flush=True)

    sz = sizes()
    rep.phase("native libraries", native_phase)
    rep.phase("kernels", kernels_phase, sz)
    rep.phase("decode program keeps its pool in place", pool_phase, sz)

    import ray_tpu
    ray_tpu.init()
    try:
        outputs: dict = {}
        for impl in ("xla", "pallas"):
            rep.phase(f"serve llama decode_attention={impl}", serve_phase,
                      sz, impl, outputs)
        rep.phase("serve olmoe-shaped expert model", serve_moe_phase, sz)
        rep.phase("train gpt2 on one device", train_phase, sz)
        if len(devices) >= 4:
            rep.phase("train llama sharded fsdp=2 x tp=2", sharded_phase, sz)
            rep.phase("four replicas", placement_phase, sz)
        rep.phase("processes", processes_phase)
    finally:
        ray_tpu.shutdown()
    rep.phase("compile cache", cache_phase, cache_dir)
    print(mem_line(devices), flush=True)

    if rep.failed:
        print(f"FAILED ({len(rep.failed)}): " + "; ".join(rep.failed),
              flush=True)
        return EXIT_FAILED
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
