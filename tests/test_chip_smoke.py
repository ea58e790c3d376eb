"""chip_smoke.py's contract off the chip, and the kernels' on it.

The script itself can only pass on a TPU; what the tests hold is that it
runs end to end at debug widths when the CPU is asked for by name (slow
tier), refuses in seconds when it is not, and that every Pallas kernel
lowers through Mosaic for the v5e at the smoke's shapes (libtpu compiles
for a described topology with no chip present).
"""

import dataclasses
import importlib.util
import json
import math
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra, timeout):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


# ~45 s cold. The tier-1 sweep (`-m 'not slow'`) already dies at its 870 s
# limit on the sandbox (ROADMAP D9), so this rides tools/run_ci.sh's full
# stages instead; run it by hand before spending chip time on the smoke.
@pytest.mark.slow
def test_tiny_cpu_run_passes_and_caches_where_told(tmp_path):
    cache = tmp_path / "cache"
    proc = _run(["--tiny-cpu"], {"JAX_COMPILATION_CACHE_DIR": str(cache)},
                timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("platform=cpu device_kind=cpu device_count=4")
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    assert "FAIL" not in proc.stdout
    # JAX_COMPILATION_CACHE_DIR is JAX's to honour; nothing in the tree
    # may point the cache anywhere else
    assert any(cache.iterdir())
    assert f"compile cache where it was asked to be: {cache}" in proc.stdout


def test_refuses_the_cpu_unless_asked(tmp_path):
    t0 = time.monotonic()
    proc = _run([], {"JAX_PLATFORMS": "cpu"}, timeout=120)
    assert proc.returncode not in (0, None)
    assert "platform is 'cpu'" in proc.stderr
    assert proc.stdout.startswith("platform=cpu")
    assert '"ok"' not in proc.stdout
    assert time.monotonic() - t0 < 60


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from ray_tpu._private.platform import enable_compile_cache

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        want = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # set from outside, the directory is JAX's business: no update
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == want
    finally:        # nothing compiled in between: no cache was opened
        for k, v in saved.items():
            jax.config.update(k, v)


def test_a_model_asks_once_whether_its_mesh_allows_the_kernels(monkeypatch):
    """``use_flash_on``: False under a mesh, None off one, and the import
    of Pallas starts on a thread only where the backend is a TPU (the
    fused attention's second of imports then runs under the weights'
    init). The models' constructors are its callers."""
    import threading

    from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    kernels = importlib.import_module("ray_tpu.ops.attention")
    started = []
    monkeypatch.setattr(threading.Thread, "start",
                        lambda self: started.append(self.name))
    mesh = build_mesh(MeshSpec(fsdp=1), jax.devices()[:1])
    assert GPT2Model(GPT2Config.debug(), mesh=mesh)._use_flash is False
    assert GPT2Model(GPT2Config.debug())._use_flash is None
    assert started == []
    monkeypatch.setattr(kernels, "on_chip", lambda: True)
    assert GPT2Model(GPT2Config.debug(), mesh=mesh)._use_flash is False
    assert started == []
    assert GPT2Model(GPT2Config.debug())._use_flash is None
    assert started == ["pallas-import"]


def test_the_import_thread_loads_pallas(monkeypatch):
    import threading

    kernels = importlib.import_module("ray_tpu.ops.attention")
    monkeypatch.setattr(kernels, "on_chip", lambda: True)
    assert kernels.use_flash_on(None) is None
    for t in threading.enumerate():
        if t.name == "pallas-import":
            t.join(120)
    assert "jax._src.pallas.pallas_call" in sys.modules
    assert "jax.experimental.pallas.tpu" in sys.modules


# ---------------------------------------------------------------------------
# Mosaic lowering for the v5e, no chip needed
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def v5e_topo():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo


@pytest.fixture(scope="module")
def v5e(v5e_topo):
    """An abstract-array factory placed on a described v5e chip."""
    from jax.sharding import SingleDeviceSharding

    sharding = SingleDeviceSharding(v5e_topo.devices[0])
    return lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def placed(v5e):
    """A tree of arrays or shapes as abstract arrays on that chip."""
    return lambda tree: jax.tree.map(
        lambda a: v5e(*a.shape, dtype=a.dtype), tree)


@pytest.fixture(scope="module")
def smoke_sizes():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert not mod.TINY
    return mod.sizes()


def _mosaic(lowered) -> bool:
    return "tpu_custom_call" in lowered.compile().as_text()


def _engine_params(model):
    """Shapes and dtypes of the parameters as an engine holds them."""
    return jax.eval_shape(lambda key: model.serving_params(model.init(key)),
                          jax.random.key(0))


def _engine_decode(model, num_blocks, run=1):
    """The engine's decode program (the model's step and the sampler,
    ``_decode_step_paged``) of an engine that is never built: building
    one allocates its pool, and a described chip holds no array.
    ``run``: the blocks its kernel copies as one (``kv_run``: the pool's
    windows and the table are then whole runs, as the engine sizes
    them)."""
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    eng = object.__new__(ContinuousBatchingEngine)
    eng.model, eng.num_blocks, eng.kv_run = model, num_blocks, run
    return jax.jit(eng._decode_step_paged, donate_argnums=(2,))


def _whole_runs(blocks, run):
    """A window of the pool as the engine sizes it: whole runs."""
    return -(-blocks // run) * run


def _sampling(v5e, B):
    """Temperatures, top-ks and the key, as the decode program takes
    them after the offsets."""
    return (v5e(B, dtype=jnp.float32), v5e(B, dtype=jnp.int32),
            jax.eval_shape(lambda: jax.random.key(0)))


def test_paged_decode_kernel_lowers_for_v5e(v5e, smoke_sizes):
    """(The pools laid as a model lays them: heads of 64 two to a row.)"""
    from ray_tpu.ops.paged_attention import (packed_row,
                                             paged_decode_attention_pallas)

    bs, maxb = 32, smoke_sizes["kernel_seq"] // 32
    for B, H, Hkv, D in smoke_sizes["kernel_shapes"]:
        pool = v5e(B * maxb + 1, bs, *packed_row(Hkv, D))
        assert _mosaic(paged_decode_attention_pallas.lower(
            v5e(B, H, D), pool, pool, v5e(B, maxb, dtype=jnp.int32),
            v5e(B, dtype=jnp.int32), interpret=False))


@pytest.mark.parametrize("shape", [
    (8, 32, 8, 128, 128, 16),     # a 128-token block: two pages a chunk
    (8, 32, 8, 128, 512, 4),      # one page over the row budget
    (8, 12, 12, 64, 16, 64),      # gpt2's widths, packed two heads a row
    (8, 64, 8, 128, 32, 64),      # 64 q heads (llama-70b's attention)
])
def test_paged_decode_kernel_lowers_at_other_blocks_and_widths(v5e, shape):
    """The kernel's VMEM is bounded by rows, not pages: it lowers (the
    compiler refuses a kernel over its scoped VMEM) whatever block_size
    the engine is built with."""
    from ray_tpu.ops.paged_attention import (packed_row,
                                             paged_decode_attention_pallas)

    B, H, Hkv, D, bs, maxb = shape
    pool = v5e(B * maxb + 1, bs, *packed_row(Hkv, D))
    assert _mosaic(paged_decode_attention_pallas.lower(
        v5e(B, H, D), pool, pool, v5e(B, maxb, dtype=jnp.int32),
        v5e(B, dtype=jnp.int32), interpret=False))


def test_paged_decode_kernel_refuses_widths_it_cannot_copy(v5e):
    from ray_tpu.ops.paged_attention import paged_decode_attention_pallas

    pool = v5e(65, 32, 8, 80)       # 128 lanes are no multiple of 80
    with pytest.raises(ValueError, match="128 lanes"):
        paged_decode_attention_pallas.lower(
            v5e(8, 32, 80), pool, pool, v5e(8, 8, dtype=jnp.int32),
            v5e(8, dtype=jnp.int32), interpret=False)


# the serve cells' decode shapes (BENCHMARK.json, 32 slots, block_size 32):
# table entries a slot in batch_decode (mistral-7b-v0.3-d6 at max_seq 8192),
# batch_decode_moe (olmoe-1b-7b-d3 at 4096), chat_mixed (mistral at 2048)
# and long_decode_hybrid (mellum2-12b-a2.5b-d8 at 16384), and each cell's
# query and KV heads
CELL_SLOTS, CELL_BS, CELL_TABLES = 32, 32, (256, 128, 64, 512)
CELL_HEADS = {256: (32, 8), 128: (16, 16), 64: (32, 8), 512: (32, 4)}


@pytest.mark.parametrize("maxb", CELL_TABLES)
def test_paged_decode_kernel_lowers_at_the_cells_shapes(v5e, maxb):
    from ray_tpu.ops.paged_attention import paged_decode_attention_pallas

    B, (H, Hkv), D, bs = CELL_SLOTS, CELL_HEADS[maxb], 128, CELL_BS
    pool = v5e(B * maxb + 1, bs, Hkv, D)
    assert _mosaic(paged_decode_attention_pallas.lower(
        v5e(B, H, D), pool, pool, v5e(B, maxb, dtype=jnp.int32),
        v5e(B, dtype=jnp.int32), interpret=False))


def test_windowed_kernel_lowers_at_the_hybrid_cells_shape(v5e):
    """``long_decode_hybrid``'s sliding layers: the same kernel with each
    slot's first visible position as a third prefetched scalar array;
    the sliding kind's table is as long as the full kind's (512 entries
    a slot, those behind the window dead)."""
    from ray_tpu.ops.paged_attention import paged_decode_attention_pallas

    B, (H, Hkv), D, bs, maxb = CELL_SLOTS, CELL_HEADS[512], 128, CELL_BS, 512
    pool = v5e(B * 50 + 1, bs, Hkv, D)       # a window's worth a slot
    assert _mosaic(paged_decode_attention_pallas.lower(
        v5e(B, H, D), pool, pool, v5e(B, maxb, dtype=jnp.int32),
        v5e(B, dtype=jnp.int32), v5e(B, dtype=jnp.int32), interpret=False))


def _as_on_the_chip(monkeypatch):
    """This process's backend is the CPU, where the model rightly takes
    the reference: tell it what it would see on the chip (the compile
    is the v5e's)."""
    from ray_tpu.ops import moe_dispatch, paged_attention

    # ``ray_tpu.ops.attention`` the NAME is the dispatcher, not its module
    attention = importlib.import_module("ray_tpu.ops.attention")
    for module in (attention, paged_attention, moe_dispatch):
        monkeypatch.setattr(module, "on_chip", lambda: True)
        monkeypatch.setattr(module, "pallas_interpret", lambda: False)


@pytest.mark.parametrize("maxb", CELL_TABLES)
def test_decode_program_holds_the_kernel_on_a_tpu_backend(
        v5e, placed, monkeypatch, maxb):
    """The engine's decode program (``decode_step_paged`` and the sampler
    over the 32,768-token vocabulary) at the Mistral cells' widths (depth
    1; at 128 entries too, the expert cell's table, whose own program is
    ``test_olmoe_cell_decode_program_fits_the_v5e``), on the parameters
    as an engine holds them, no ``decode_attention`` set. This process's
    backend is the CPU, where the dispatcher rightly takes the reference,
    so the test tells the dispatcher what it would see on the chip; the
    compile is the v5e's."""
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    _as_on_the_chip(monkeypatch)
    B, bs = CELL_SLOTS, CELL_BS
    model = LlamaModel(LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=1, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq_len=maxb * bs, rope_theta=1e6))
    assert model.cfg.decode_attention is None
    assert model.paged_decode_impl() == "pallas"

    args = (placed(_engine_params(model)),
            v5e(B, dtype=jnp.int32),
            placed(jax.eval_shape(
                lambda: model.init_kv_pool(B * maxb + 1, bs))),
            v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
            *_sampling(v5e, B), None)
    assert _mosaic(_engine_decode(model, B * maxb).lower(*args))
    # forced to the reference, the same program holds no kernel
    ref = LlamaModel(dataclasses.replace(model.cfg, decode_attention="xla"))
    assert not _mosaic(_engine_decode(ref, B * maxb).lower(*args))


def test_llama3_1b_decode_program_holds_the_kernel(v5e, placed, monkeypatch,
                                                   smoke_sizes):
    """The smoke's served config as published (32/8 heads of 64), no
    ``decode_attention`` set: two KV heads to a 128-lane row."""
    from ray_tpu.models.llama import LlamaModel

    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(smoke_sizes["serve_cfg"], n_layers=1)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    model = LlamaModel(cfg)
    assert cfg.decode_attention is None
    assert model.paged_decode_impl() == "pallas"
    B, bs, maxb = 8, 32, cfg.max_seq_len // 32

    assert _mosaic(jax.jit(model.decode_step_paged, donate_argnums=(2,)).lower(
        placed(_engine_params(model)), v5e(B, dtype=jnp.int32),
        placed(jax.eval_shape(lambda: model.init_kv_pool(B * maxb + 1, bs))),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32)))


def test_a_pool_of_64_lane_heads_is_read_where_it_lies(v5e, placed,
                                                       monkeypatch,
                                                       smoke_sizes):
    """llama3_1b's heads (32 / 8 of 64) at two layers, 32 slots x 2,048
    positions: the pool lies two K/V heads to a 128-lane row (``[2, NB,
    32, 4, 128]``: its bytes are its rows', nothing padded to the lanes),
    the decode program reads a layer's pages where they lie, and its
    temporaries stay under ONE layer's window of K. Laid ``[.., 8, 64]``
    the program copied a layer's window of K and of V into packed rows a
    layer a step (the kernel's own comment before PR 61; PERF.md, PR 25:
    75 us of kernel beside 489 us of copies)."""
    from ray_tpu.models.llama import LlamaModel

    _as_on_the_chip(monkeypatch)
    cfg = dataclasses.replace(smoke_sizes["serve_cfg"], n_layers=2,
                              max_seq_len=2048)
    model = LlamaModel(cfg)
    assert model.paged_decode_impl() == "pallas"
    assert model.kv_lane_pack == 2
    B, bs, maxb = 32, 32, 2048 // 32
    run = model.paged_run_blocks(bs)
    assert run == 2
    NB = _whole_runs(B * maxb + 1, run)
    pool = placed(jax.eval_shape(lambda: model.init_kv_pool(NB, bs)))
    assert pool["k"].shape == (2, NB, bs, 4, 128)
    window = NB * bs * 8 * 64 * 2                  # one layer's K, bf16
    params = placed(_engine_params(model))
    compiled = _engine_decode(model, B * maxb, run).lower(
        params, v5e(B, dtype=jnp.int32), pool,
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), None).compile()
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.temp_size_in_bytes < window, mem.temp_size_in_bytes / 2**20
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves((params, pool)))
    assert 4 * window == sum(a.size * 2 for a in jax.tree.leaves(pool))
    # the arguments are the weights and the pool's own bytes (and a few
    # vectors): a pool padded to the lanes would be twice its rows
    assert mem.argument_size_in_bytes < held + 2**20


def _holds_the_tiled_grouped_matmuls(text, model, slots):
    """The compiled decode program's three grouped matmuls are the Pallas
    kernel at ``gmm_tiling``'s choice (PR 40; that it compiled says the
    tiles fit the scoped VMEM), not XLA's ``ragged_dot`` at the tiling
    its heuristic picks (256 x 128 weight tiles at Mellum2's widths: 63
    grid steps a group)."""
    plan = model.grouped_matmul_plan(slots)
    assert plan["moe_grouped_impl"] == "pallas_gmm"
    assert all(plan[f"moe_gmm_tiling_{c}"] for c in ("gate", "up", "down"))
    # one attention kernel + three grouped matmuls in the layer scan
    assert text.count("tpu_custom_call") >= 4
    assert "ragged_dot_tiling" not in text and "ragged-dot" not in text
    assert text.count(" custom-call(") and "gmm" in text


@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304), (2048, 1024),
                                 (1024, 2048)])
def test_grouped_matmul_lowers_at_its_own_tilings(v5e, k, n):
    """The Pallas grouped matmul at ``gmm_tiling``'s choice for both
    expert cells' calls, from a two-slot decode step's 16 rows to a
    1,536-token prefill's 12,288, on a whole stack of 8 x 64 groups:
    Mosaic takes every one (what ``gmm_vmem_bytes`` reckons under its
    budget fits the v5e's scoped VMEM), forward and under ``grad``."""
    from ray_tpu.ops.moe_dispatch import (gmm_tiling, group_tiles,
                                          pallas_grouped_matmul)

    def kernel(a, b, s, t):
        return pallas_grouped_matmul(a, b, s, group_tiles(s, a.shape[0], t[0]),
                                     jnp.bfloat16, t)

    G = 512
    for m in (16, 256, 2048, 4096, 12288):
        tiling = gmm_tiling(m, k, n, 2)
        assert tiling is not None, m
        assert _mosaic(jax.jit(
            lambda a, b, s, t=tiling: kernel(a, b, s, t)).lower(
            v5e(m, k), v5e(G, k, n), v5e(G, dtype=jnp.int32))), (m, tiling)
    # training: the kernel forward, ragged_dot's transposes backward
    tiling = gmm_tiling(2048, k, n, 2)
    text = jax.jit(jax.grad(lambda a, b, s: jnp.sum(kernel(
        a, b, s, tiling).astype(jnp.float32)), (0, 1))).lower(v5e(2048, k), v5e(64, k, n),
                       v5e(64, dtype=jnp.int32)).compile().as_text()
    assert "ragged_dot_tiling" in text


def test_olmoe_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``olmoe-1b-7b-d3.batch_decode_moe``'s decode program as the engine
    jits it (the counted step: 32 slots x 4096, depth 3, all 64 experts,
    matmul weights in bf16 as the engine holds them): the v5e's compiler
    takes it (5.74 GiB of 15.75: 5.73 of arguments, 2.73 weights + 3.00
    pool; 11.56 on float32 weights, whose bf16 copies were 3.10 GiB of
    temporaries), with the paged kernel and the three grouped matmuls as
    Mosaic calls at ``gmm_tiling``'s (128, 2048, 1024) and (128, 1024,
    2048), the whole expert one grid step a group, and no ``ragged_dot``
    (PR 53: until then these widths kept XLA's own at 256 x 512 x 512).
    The temporaries that remain (0.014 GiB) hold nothing
    stack-shaped: the grouped-matmul calls read the expert stacks whole
    and in place (PR 37). Until then ONE layer's slice of a stack at a
    time was copied out as their operand: 0.25 GiB of temporaries, 5.98
    in all, and three copies a layer a step on the chip."""
    from ray_tpu.models import MoEConfig, model_for

    _as_on_the_chip(monkeypatch)
    B, bs, maxb, L, E = CELL_SLOTS, CELL_BS, 128, 3, 64
    model = model_for(MoEConfig(
        vocab_size=50304, dim=2048, n_layers=L, n_heads=16, n_kv_heads=16,
        ffn_dim=1024, max_seq_len=maxb * bs, rope_theta=1e4, num_experts=E,
        expert_top_k=8, norm_topk_prob=False, qk_norm=True))
    assert model.paged_decode_impl() == "pallas"
    assert model.ffn_load_shape() == (L, E)

    params = placed(_engine_params(model))
    compiled = _engine_decode(model, B * maxb).lower(
        params, v5e(B, dtype=jnp.int32),
        placed(jax.eval_shape(lambda: model.init_kv_pool(B * maxb + 1, bs))),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), v5e(L, E, dtype=jnp.int32)).compile()
    plan = model.grouped_matmul_plan(B)
    assert [plan[f"moe_gmm_tiling_{c}"] for c in ("gate", "up", "down")] \
        == ["128x2048x1024", "128x2048x1024", "128x1024x2048"]
    _holds_the_tiled_grouped_matmuls(compiled.as_text(), model, B)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 15.75 * 2**30
    # the expert stacks are read in place (PR 37): not one layer's slice
    # of one stack is copied out for the grouped-matmul calls
    one_stack_a_layer = params["layers"]["e_gate"].size * 2 / L
    assert mem.temp_size_in_bytes < one_stack_a_layer / 4


def test_hybrid_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``mellum2-12b-a2.5b-d8.long_decode_hybrid``'s decode program as
    the engine jits it: 32 slots x 16,384, depth 8 (S S S F twice), all
    64 experts, a K/V pool a kind as one stack (2 full layers of 16,385
    blocks, 6 sliding layers of 1,601) and a table a kind. The v5e's
    compiler takes it at 9.68 GiB of 15.75 (9.66 of arguments: 7.07
    weights + 2.59 of pools; 0.025 of temporaries, nothing of an expert
    stack's shape among them: 0.25, one layer's slice of one stack at a
    time, until PR 37); ONE pool for all eight layers would hold 8.0
    GiB of K/V where the two hold 2.59, and with the weights pass the
    chip's 15.75. Its 24 grouped matmuls are the Pallas kernel at
    ``gmm_tiling``'s (128, whole expert) (PR 40), not ``ragged_dot`` at
    the 256 x 128 weight tiles XLA's heuristic falls to at 2304 x 896."""
    from benchmark import run as harness
    from benchmark.builders import mellum
    from ray_tpu.llm.paged_cache import window_blocks_per_slot

    _as_on_the_chip(monkeypatch)
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/mellum2-12b-a2.5b-d8.json")
    B, bs, maxb, L, E = CELL_SLOTS, CELL_BS, 512, 8, 64
    model = mellum.build_model(cfg, maxb * bs)
    assert model.paged_decode_impl() == "pallas"
    assert model.layer_kinds == (1, 1, 1, 0) * 2
    full = B * maxb
    window = B * window_blocks_per_slot(cfg["sliding_window"], bs, 512)
    assert window == B * 50

    # the full kind's blocks lie in runs of 2 (32 KB a block), and every
    # layer's window of the stack is whole runs
    run = model.paged_run_blocks(bs)
    assert run == 2
    pool = jax.eval_shape(lambda: model.init_kv_pools(
        (full + 2, window + 2), bs))
    assert pool["k"].shape[0] == 2 * (full + 2) + 6 * (window + 2)
    compiled = _engine_decode(model, full, run).lower(
        placed(_engine_params(model)), v5e(B, dtype=jnp.int32), placed(pool),
        v5e(2, B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), v5e(L, E, dtype=jnp.int32)).compile()
    _holds_the_tiled_grouped_matmuls(compiled.as_text(), model, B)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 15.75 * 2**30
    # the pool is written in place and the expert stacks are read in
    # place (PR 37): nothing pool-sized among the temporaries, and not
    # one layer's slice of one expert stack
    one_stack_a_layer = E * 2304 * 896 * 2
    assert mem.temp_size_in_bytes < one_stack_a_layer / 4


# kanana-2-30b-a3b-d5.long_decode_mla: 32 slots x 24,576 at block 32 (768
# table entries a slot), a latent row of 512 + 128 lanes, 32 heads
MLA_CELL_TABLE = 768


def test_mla_decode_kernel_lowers_at_the_mla_cells_shape(v5e):
    """The absorbed latent-attention kernel (``ops/mla_attention.py``) for
    the v5e, no chip: pages ``[32, 640]`` (``c | k_pe``, ONE pool row since
    PR 45) copied as the 2-D tiles they are out of a stack of five layers'
    windows, the row's two parts lane ranges of the one buffer, the 32
    heads as the rows of its dots. The pool is what the arguments hold:
    4.69 GiB, a row's 1,280 B and nothing a sublane tile pads."""
    from ray_tpu.ops.mla_attention import (PE_LANES,
                                           mla_decode_attention_pallas)

    B, H, R, bs, maxb, L = CELL_SLOTS, 32, 512, CELL_BS, MLA_CELL_TABLE, 5
    blocks = L * (B * maxb + 1)
    compiled = mla_decode_attention_pallas.lower(
        v5e(B, H, R), v5e(B, H, PE_LANES), v5e(blocks, bs, R + PE_LANES),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        scale=192 ** -0.5, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    held = compiled.memory_analysis().argument_size_in_bytes
    assert held == pytest.approx(blocks * bs * (R + PE_LANES) * 2, rel=0.001)
    assert 4.2 < held / 2**30 < 4.7


def test_mla_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``kanana-2-30b-a3b-d5.long_decode_mla``'s decode program as the
    engine jits it: 32 slots x 24,576, one leading dense layer and four
    expert layers (two layer scans, one body), all 128 experts, latent
    pool rows. The v5e's compiler takes it at 10.57 GiB of 15.75 (10.56
    of arguments: 5.87 weights + 4.69 pool; 0.01 of temporaries), with
    the latent kernel of both scans and the three grouped matmuls as
    Mosaic calls (``grouped_matmul_impl`` takes the Pallas kernel
    wherever ``gmm_tiling`` has a tiling), and nothing of an
    expert stack's shape among the temporaries."""
    from benchmark import run as harness
    from benchmark.builders import kanana

    _as_on_the_chip(monkeypatch)
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/kanana-2-30b-a3b-d5.json")
    B, bs, maxb, E = CELL_SLOTS, CELL_BS, MLA_CELL_TABLE, 128
    model = kanana.build_model(cfg, maxb * bs)
    assert model.paged_decode_impl() == "mla_pallas"
    assert model.ffn_load_shape() == (4, E)
    pool = jax.eval_shape(lambda: model.init_kv_pool(B * maxb + 1, bs))
    assert pool["k"].shape == (5, B * maxb + 1, bs, 512 + 128)
    assert pool["v"].shape == (5, B * maxb + 1, bs, 0)
    compiled = _engine_decode(model, B * maxb).lower(
        placed(_engine_params(model)), v5e(B, dtype=jnp.int32), placed(pool),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), v5e(4, E, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    _holds_the_tiled_grouped_matmuls(text, model, B)
    # the latent kernel in each of the two scans, and the matmuls
    assert text.count("tpu_custom_call") >= 5
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 15.75 * 2**30
    assert 4.2 < sum(a.size * 2 for a in pool.values()) / 2**30 < 4.7
    one_stack_a_layer = E * 2048 * 768 * 2
    assert mem.temp_size_in_bytes < one_stack_a_layer / 4


# xing4.0-29b-a4b-d5.long_decode_mhc: 32 slots x 16,384 at block 32 (512
# table entries a slot: ``CELL_TABLES`` holds the width), four residual
# streams of 3,584 lanes, the same latent row and 32 heads as the MLA cell
MHC_CELL_TABLE = CELL_TABLES[3]


def test_mhc_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``xing4.0-29b-a4b-d5.long_decode_mhc``'s decode program as the
    engine jits it (``python3 -m benchmark.aot_fit``'s compile): 32 slots
    x 16,384, one leading dense layer and four expert layers, all 64
    experts, the whole vocabulary. The v5e's compiler takes it at 10.71
    GiB of 15.75 (7.55 of weights, 3.13 of pool, 0.03 of temporaries),
    with the latent kernel in each of its two scans; the streams' maps and
    mixing are XLA's fusions (``ops/mhc.py`` says why no kernel) and the
    grouped matmuls are the Pallas kernel at ``gmm_tiling``'s two tiles an
    expert (XLA's own 512 x 512 would make fourteen of 3,584 x 1,024)."""
    from benchmark import run as harness
    from benchmark.builders import xing

    _as_on_the_chip(monkeypatch)
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/xing4.0-29b-a4b-d5.json")
    assert harness.load_json(harness.HERE, "traffic", "long_decode_mhc.json")[
        "engine"] == {"max_slots": CELL_SLOTS, "max_seq": MHC_CELL_TABLE * 32,
                      "block_size": CELL_BS, "max_ongoing_requests": 64}
    B, bs, maxb, E = CELL_SLOTS, CELL_BS, MHC_CELL_TABLE, 64
    model = xing.build_model(cfg, maxb * bs)
    assert model.paged_decode_impl() == "mla_pallas"
    assert model.ffn_load_shape() == (4, E)
    assert model.grouped_matmul_plan(B)["moe_grouped_impl"] == "pallas_gmm"
    pool = jax.eval_shape(lambda: model.init_kv_pool(B * maxb + 1, bs))
    assert pool["k"].shape == (5, B * maxb + 1, bs, 512 + 128)
    params = _engine_params(model)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg["parameters"]
    compiled = _engine_decode(model, B * maxb).lower(
        placed(params), v5e(B, dtype=jnp.int32), placed(pool),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), v5e(4, E, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("mla_decode_attention_pallas") >= 2
    assert "ragged_dot_tiling" not in text and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 10.5 * 2**30 < total < 10.9 * 2**30
    one_stack_a_layer = E * 3584 * 1024 * 2
    assert mem.temp_size_in_bytes < one_stack_a_layer / 4


# deepseek-v3.2-d5.long_decode_dsa: 16 slots x 22,528 at block 32 (704
# table entries a slot), rows held as words, 2,048 rows selected
DSA_CELL_SLOTS, DSA_CELL_TABLE, DSA_TOPK = 16, 704, 2048


def test_dsa_kernels_lower_at_the_dsa_cells_shape(v5e):
    """The two Mosaic kernels of ``ops/dsa.py`` for the v5e, no chip: the
    index scores through the block table (of every token of a page the
    keys' sub-row, [32, 128] words: ONE strided copy a page) and the
    absorbed attention that copies 2,048 selected rows a slot by their
    numbers (the first three of a row's [4, 128] words, ONE DMA, out of a
    stack of five layers' windows). The pool is what the arguments hold:
    2,048 B a row, the layout's own spare sub-row and no padding beyond
    it, and it reaches both kernels as it lies. The selection between them is XLA's: ``top_k`` at 2,048 of
    22,528 lowers to a ``sort``, the name ``dsa.indexer_roofline.decode``
    reads."""
    from ray_tpu.ops import dsa

    B, bs, maxb, L = DSA_CELL_SLOTS, CELL_BS, DSA_CELL_TABLE, 5
    blocks = L * (B * maxb + 1)
    u32, i32 = jnp.uint32, jnp.int32
    pool = v5e(blocks, bs, 4, 128, dtype=u32)

    def moved(compiled):
        """The uint32 operands copied into another layout on the way."""
        return [line for line in compiled.as_text().splitlines()
                if " copy(" in line and "u32[" in line]

    indexer = dsa.indexer_scores_pallas.lower(
        v5e(B, 64, 128), v5e(B, 64, dtype=jnp.float32), pool,
        v5e(B, maxb, dtype=i32), v5e(B, dtype=i32),
        first_block=v5e(dtype=i32)).compile()
    assert "tpu_custom_call" in indexer.as_text() and not moved(indexer)
    assert indexer.memory_analysis().argument_size_in_bytes \
        == pytest.approx(blocks * bs * 2048, rel=0.001)
    attention = dsa.selected_attention_pallas.lower(
        v5e(B, 128, 512), v5e(B, 128, 128), pool,
        v5e(B, DSA_TOPK, dtype=i32), v5e(B, dtype=i32),
        scale=0.13523).compile()
    assert "tpu_custom_call" in attention.as_text() and not moved(attention)
    assert attention.memory_analysis().argument_size_in_bytes \
        == pytest.approx(blocks * bs * 2048, rel=0.001)
    select = jax.jit(lambda s, n: dsa.select_topk(s, n, DSA_TOPK)).lower(
        v5e(B, maxb * bs, dtype=jnp.float32), v5e(B, dtype=i32)).compile()
    assert " sort(" in select.as_text()


def test_dsa_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``deepseek-v3.2-d5.long_decode_dsa``'s decode program as the engine
    jits it: 16 slots x 22,528, one leading dense layer and four expert
    layers that hold 16 of the router's 256 experts. The v5e's compiler
    takes it at 12.09 GiB of 15.75 (8.65 of weights, 3.44 of pool, 0.005
    of temporaries: ``benchmark/aot_fit.py``), the two kernels of
    ``ops/dsa.py`` in both scans, the Pallas grouped matmul for the
    experts (7168 x 2048 is 56 of the 512 x 512 tiles XLA would make:
    ``grouped_matmul_impl``, PR 50), and nothing of an expert stack's
    shape among the temporaries."""
    from benchmark import run as harness
    from benchmark.builders import deepseek_v32

    _as_on_the_chip(monkeypatch)
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/deepseek-v3.2-d5.json")
    B, bs, maxb = DSA_CELL_SLOTS, CELL_BS, DSA_CELL_TABLE
    model = deepseek_v32.build_model(cfg, maxb * bs)
    assert model.paged_decode_impl() == "dsa_pallas"
    assert model.ffn_load_shape() == (4, 256)
    assert model.grouped_matmul_plan(B)["moe_grouped_impl"] == "pallas_gmm"
    pool = jax.eval_shape(lambda: model.init_kv_pool(B * maxb + 1, bs))
    assert pool["k"].shape == (5, B * maxb + 1, bs, 4, 128)
    assert pool["v"].shape == (5, B * maxb + 1, bs, 0)
    compiled = _engine_decode(model, B * maxb).lower(
        placed(_engine_params(model)), v5e(B, dtype=jnp.int32), placed(pool),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), v5e(4, 256, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    # both kernels in each of the two scans
    assert text.count("tpu_custom_call") >= 4
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 15.75 * 2**30
    assert sum(a.size * 4 for a in pool.values()) \
        == 5 * (B * maxb + 1) * bs * 2048
    one_layers_slice_of_a_stack = 16 * 7168 * 2048 * 2
    assert mem.temp_size_in_bytes < one_layers_slice_of_a_stack / 4


# evabyte-6.5b-d8.long_decode_eva: 16 slots x 24,576 at block 32; both
# parts' tables are 65 entries a slot (the window's 64 blocks and one more;
# the summary part needs 48), MHA 32/32 at 128
EVA_CELL_SLOTS, EVA_CELL_TABLE, EVA_CELL_HEADS = 16, 65, (32, 32)


def test_kernel_with_its_softmax_statistics_lowers_at_the_eva_cells_shape(v5e):
    """``long_decode_eva``'s attention is the paged kernel twice a layer
    (the window's pages, the summary pages), each call handing back its
    softmax's running max and sum beside a float32 output."""
    from ray_tpu.ops.paged_attention import paged_decode_attention_pallas

    B, (H, Hkv), D, bs = EVA_CELL_SLOTS, EVA_CELL_HEADS, 128, CELL_BS
    pool = v5e(B * 82 + 1, bs, Hkv, D)
    lowered = paged_decode_attention_pallas.lower(
        v5e(B, H, D), pool, pool, v5e(B, EVA_CELL_TABLE, dtype=jnp.int32),
        v5e(B, dtype=jnp.int32), interpret=False, stats=True)
    assert _mosaic(lowered)
    out, m, l = lowered.out_info
    assert out.dtype == m.dtype == l.dtype == jnp.float32
    assert out.shape == (B, H, D) and m.shape == l.shape == (B, H)


def test_eva_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``evabyte-6.5b-d8.long_decode_eva``'s decode program as the engine
    jits it: 16 slots x 24,576, depth 8, a K/V pool a PART (82 exact
    blocks and 48 summary blocks a slot-layer, + a scratch block each)
    and a table a part. The v5e's compiler takes it at 11.17 GiB of 15.75
    (3.04 of bf16 weights + 8.13 of pools) with both parts written in
    place: the temporaries stay under one layer's smallest matmul
    weight. ONE row a position would hold 48 GiB of K/V."""
    from benchmark import run as harness
    from benchmark.builders import evabyte
    from ray_tpu.llm.paged_cache import window_blocks_per_slot

    _as_on_the_chip(monkeypatch)
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/evabyte-6.5b-d8.json")
    B, bs, max_seq = EVA_CELL_SLOTS, CELL_BS, 24_576
    model = evabyte.build_model(cfg, max_seq)
    window, chunk = model.eva
    assert (window, chunk) == (2048, 16)
    assert model.paged_decode_impl() == "pallas"
    exact = B * window_blocks_per_slot(window, bs, 512)
    summary = B * (max_seq // (bs * chunk))
    assert (exact, summary) == (B * 82, B * 48)
    assert max(window // bs + 1, max_seq // (bs * chunk)) == EVA_CELL_TABLE

    pool = jax.eval_shape(lambda: model.init_kv_pools(
        (summary + 1, exact + 1), bs))
    assert pool["k"].shape[:2] == (8, exact + 1)
    assert pool["sk"].shape[:2] == (8, summary + 1)
    compiled = _engine_decode(model, summary).lower(
        placed(_engine_params(model)), v5e(B, dtype=jnp.int32), placed(pool),
        v5e(2, B, EVA_CELL_TABLE, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), None).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert 10 * 2**30 < total < 15.75 * 2**30
    assert mem.temp_size_in_bytes < 4096 * 4096 * 2


def test_smokes_decode_program_keeps_its_pool_in_place_on_the_v5e(
        v5e, placed, monkeypatch, smoke_sizes):
    """chip_smoke.py's pool phase, compiled here for the described v5e:
    the decode program's temporaries are under ONE pool's bytes, and
    under its smallest matmul weight's: no copy of a weight is among
    them, since the engine holds those in bf16. With the pool scanned
    over by the layer scan they held a whole copy of it and a layer's
    slice twice more (5.44 GiB for 2.44 at ``batch_decode``'s shape); on
    float32 weights, a bf16 copy of every one (those 2.44 GiB)."""
    from ray_tpu.models import model_for

    _as_on_the_chip(monkeypatch)
    model = model_for(smoke_sizes["pool_cfg"])
    assert model.paged_decode_impl() == "pallas"
    B, bs = 32, 32
    maxb = model.cfg.max_seq_len // bs

    pool = placed(jax.eval_shape(lambda: model.init_kv_pool(B * maxb + 1, bs)))
    def temporaries(params):
        return _engine_decode(model, B * maxb).lower(
            placed(params), v5e(B, dtype=jnp.int32), pool,
            v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
            *_sampling(v5e, B), None
        ).compile().memory_analysis().temp_size_in_bytes

    held = _engine_params(model)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    smallest = min(held["layers"][k].size * 2
                   for k in model.MATMUL_LAYER_LEAVES)
    assert temporaries(held) < min(pool_bytes, smallest)
    # the witness can see one: float32 weights are cast by the program
    assert temporaries(jax.eval_shape(model.init, jax.random.key(0))) \
        > smallest


def test_decode_under_a_mesh_keeps_the_reference(v5e_topo, monkeypatch):
    """Why ``paged_decode_impl`` answers "xla" under a mesh: XLA refuses
    to partition a Mosaic call, so the sharded decode program compiles
    for the v5e's four chips only with the reference."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    _as_on_the_chip(monkeypatch)
    mesh = Mesh(np.array(v5e_topo.devices).reshape(1, 4), ("fsdp", "tp"))
    cfg = LlamaConfig(vocab_size=4096, dim=1024, n_layers=1, n_heads=8,
                      n_kv_heads=4, ffn_dim=2048, max_seq_len=512)
    B, bs, maxb = 8, 32, 16

    def lower(model):
        def on(sharding):
            return lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=sharding)
        params = jax.tree.map(
            lambda a, s: on(s)(a),
            jax.eval_shape(model.init, jax.random.key(0)),
            model.param_shardings())
        pool = jax.tree.map(
            on(NamedSharding(mesh, P(None, None, None, "tp", None))),
            jax.eval_shape(lambda: model.init_kv_pool(B * maxb + 1, bs)))
        rep = on(NamedSharding(mesh, P()))
        ints = [rep(jax.ShapeDtypeStruct(s, jnp.int32))
                for s in ((B,), (B, maxb), (B,))]
        return jax.jit(model.decode_step_paged).lower(
            params, ints[0], pool, ints[1], ints[2])

    assert LlamaModel(cfg).paged_decode_impl() == "pallas"
    sharded = LlamaModel(cfg, mesh=mesh)
    assert sharded.paged_decode_impl() == "xla"
    assert not _mosaic(lower(sharded))
    forced = LlamaModel(dataclasses.replace(cfg, decode_attention="pallas"),
                        mesh=mesh)
    with pytest.raises(NotImplementedError, match="partitioned"):
        lower(forced).compile()


def test_flash_forward_kernel_lowers_for_v5e(v5e, smoke_sizes):
    from ray_tpu.ops.attention import _flash_forward

    S = smoke_sizes["kernel_seq"]
    for _, H, Hkv, D in smoke_sizes["kernel_shapes"]:
        kv = v5e(1, S, Hkv, D)
        assert _mosaic(_flash_forward.lower(
            v5e(1, S, H, D), kv, kv, causal=True, block=None,
            interpret=False))


# gpt2-medium.train_1chip's attention, a GQA one at head_dim 128, and
# llama3_1b's: two q heads a lane tile whose kv head is in either slot
@pytest.mark.parametrize("B,S,H,Hkv,D", [(16, 1024, 16, 16, 64),
                                         (2, 2048, 32, 8, 128),
                                         (2, 2048, 32, 8, 64)])
def test_flash_backward_kernel_lowers_for_v5e(v5e, B, S, H, Hkv, D):
    from ray_tpu.ops.attention import _flash_backward

    q, kv = v5e(B, S, H, D), v5e(B, S, Hkv, D)
    lse = v5e(B, H, 1, S, dtype=jnp.float32)
    assert _mosaic(_flash_backward.lower(
        (q, kv, kv), q, lse, q, causal=True, block=None, interpret=False))


# LlamaConfig.max_seq_len's default, at bench_400m's and at GPT-2's width
@pytest.mark.parametrize("B,S,H,Hkv,D", [(1, 8192, 8, 8, 128),
                                         (1, 8192, 16, 16, 64)])
def test_flash_past_residency_compiles_to_the_scan(v5e, monkeypatch,
                                                   B, S, H, Hkv, D):
    """A sequence the kernels do not keep in VMEM: ``attention`` on the
    chip and a forced ``flash_attention`` both compile, forward and
    backward, to ``blockwise_attention``'s scan: no Mosaic call, no array
    of the score matrix's size, temporaries of a few key blocks."""
    kernels = importlib.import_module("ray_tpu.ops.attention")
    _as_on_the_chip(monkeypatch)
    q, kv = v5e(B, S, H, D), v5e(B, S, Hkv, D)
    assert kernels.kernels_tile(q, kv)

    def loss(attn):
        return lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum()

    for attn in (lambda q, k, v: kernels.attention(q, k, v, causal=True),
                 lambda q, k, v: kernels.flash_attention(q, k, v, True)):
        compiled = jax.jit(jax.grad(loss(attn), argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" not in text
        assert f"{S},{S}" not in text
        temporaries = compiled.memory_analysis().temp_size_in_bytes
        print(f"temporaries {temporaries / 2**20:.0f} MiB")
        # one array of float32 scores would be B * H * S * S * 4 bytes
        # and the reference's backward holds three; the scan keeps a key
        # block's scores and one float32 accumulator a key block
        assert temporaries < B * H * S * S * 4 * 0.6, temporaries / 2**20


V5E_BYTES = int(15.75 * 2**30)      # what the v5e's compiler places a program in


@pytest.mark.parametrize("batch,kept", [
    (None, ("attention", "qkv", "wo")),     # the cell's 16
    (32, ("attention",)),                   # the rule fell back
])
def test_train_cell_attention_is_the_fused_kernels(v5e, placed, monkeypatch,
                                                   batch, kept):
    """``gpt2-medium.train_1chip``'s train step as ``make_train_step``
    jits it (AdamW, the state donated) at the published widths (head_dim
    64) on a device that says it holds 15.75 GiB: the v5e's compiler
    places it (it raises RESOURCE_EXHAUSTED where it cannot: with w_up's
    product kept too it does at 16 x 1,024, with the qkv product at 32),
    the layer scan keeps what ``residuals_that_fit`` and says so in
    ``train.kept_residual_bytes``, both fused attention kernels are in
    the program ONCE (the forward's outputs are kept, not remade), and no
    array of the score matrix's shape is."""
    import optax

    from benchmark import run as harness
    from benchmark.builders import gpt2
    from ray_tpu.models import gpt2 as program
    from ray_tpu.train import make_train_step
    from ray_tpu.util import metrics

    _as_on_the_chip(monkeypatch)
    monkeypatch.setattr(program, "_chip_bytes", lambda: V5E_BYTES)
    cfg = harness.load_json(harness.ROOT, "benchmark/configs/gpt2-medium.json")
    traffic = harness.load_json(harness.ROOT,
                                "benchmark/traffic/train_1chip.json")
    B, S = batch or traffic["batch"], traffic["seq_len"]
    model = gpt2.build_model(cfg, S)
    assert (model.cfg.head_dim, model.cfg.remat) == (64, True)
    o = traffic["optimizer"]
    ts = make_train_step(
        model, optax.adamw(o["lr"], weight_decay=o["weight_decay"]))
    tokens = v5e(B, S, dtype=jnp.int32)
    compiled = ts.step_fn.lower(
        *placed(jax.eval_shape(ts.init_fn, jax.random.key(0))),
        (tokens, tokens)).compile()
    stacks = {dict(tags)["name"]: size for tags, size in metrics.Gauge(
        "train.kept_residual_bytes").samples()}
    assert tuple(name for name, size in stacks.items() if size) == kept
    assert stacks["attention"] == 24 * B * (S * 1024 * 2 + 16 * S * 4)
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum("flash_attention_fwd_pallas" in c for c in calls) == 1
    assert sum("flash_attention_bwd_pallas" in c for c in calls) == 1
    assert len(calls) == 2
    H = model.cfg.n_heads
    assert f"{B},{H},{S},{S}" not in text and f"{B},{S},{H},{S}" not in text
    # the kernels read and write the projections' own layout (PR 51):
    # nothing is re-laid out under either call's scope, no operand has a
    # 64-wide minor dimension (padded to 128 lanes in HBM), and the qkv
    # product, kept or remade, reaches both calls without a copy
    relayouts = [line for line in text.splitlines()
                 if re.search(r"= \S+ (copy|transpose)\(", line)]
    assert relayouts        # the pattern still finds the program's copies
    assert not [line for line in relayouts
                if re.search(r'op_name="[^"]*jit\(_flash_(for|back)ward\)',
                             line)]
    for call in calls:
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                             call).group(1)
        shapes = re.findall(r"\[([\d,]+)\]\{(\d+)", operands)
        assert len(shapes) >= 3
        for dims, minor in shapes:
            assert int(dims.split(",")[int(minor)]) % 128 == 0, operands
    product = B * S * 3 * model.cfg.dim
    for line in relayouts:
        dims = re.search(r"= \w+\[([\d,]*)\]", line).group(1)
        assert math.prod(map(int, dims.split(",") if dims else [])) != product


def test_train_attention_under_a_mesh_keeps_the_reference(v5e_topo,
                                                          monkeypatch):
    """The sibling of ``test_decode_under_a_mesh_keeps_the_reference``:
    a model that is given a mesh asks the dispatcher for the reference
    (XLA refuses to partition a Mosaic call), whatever the shapes tile."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    _as_on_the_chip(monkeypatch)
    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), v5e_topo.devices)
    B, S = 8, 1024

    def lower(model):
        shardings = model.param_shardings()
        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            jax.eval_shape(model.init, jax.random.key(0)), shardings)
        tokens = jax.ShapeDtypeStruct(
            (B, S), jnp.int32, sharding=NamedSharding(mesh, P("fsdp")))
        return jax.jit(jax.value_and_grad(model.loss)).lower(
            params, tokens, tokens)

    gpt2 = GPT2Config(vocab_size=4096, dim=1024, n_layers=1, n_heads=16,
                      max_seq_len=S)
    llama = LlamaConfig(vocab_size=4096, dim=1024, n_layers=1, n_heads=8,
                        n_kv_heads=4, ffn_dim=2048, max_seq_len=S)
    assert (gpt2.head_dim, llama.head_dim) == (64, 128)
    for model in (GPT2Model(gpt2, mesh=mesh), LlamaModel(llama, mesh=mesh)):
        assert not _mosaic(lower(model))
    forced = LlamaModel(dataclasses.replace(llama, attention_impl="flash"),
                        mesh=mesh)
    with pytest.raises(NotImplementedError, match="partitioned"):
        lower(forced).compile()


# nemotron-3-super-d11.long_decode_ssm: 64 slots x 14,336 at block 32 (448
# entries a slot), one period MEMEMEMEM*E at the published widths
SSM_CELL_SLOTS, SSM_CELL_TABLE = 64, 448


def test_ssm_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``nemotron-3-super-d11.long_decode_ssm``'s decode program as the
    engine jits it: 64 slots x 14,336, five Mamba-2 layers whose state
    rows (``S`` float32, 4 MiB a slot a layer) the Mosaic state-update
    kernel rewrites IN PLACE, one attention layer on the paged kernel at
    GQA 32 / 2, five latent-expert layers that hold 128 of the router's
    512 experts on the Pallas grouped matmul at 1,024 x 2,688. The v5e's
    compiler takes it at 10.86 GiB of 15.75 (8.68 of weights, 1.27 of
    state, 0.875 of pool, 0.03 of temporaries), and nothing of a state
    stack's, an expert stack's or a projection's shape is among the
    temporaries: no layer's slice of a stack is copied."""
    from benchmark import run as harness
    from benchmark.builders import nemotron_h

    _as_on_the_chip(monkeypatch)
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/nemotron-3-super-d11.json")
    B, bs, maxb = SSM_CELL_SLOTS, CELL_BS, SSM_CELL_TABLE
    model = nemotron_h.build_model(cfg, maxb * bs)
    assert model.recurrent and model.paged_decode_impl() == "pallas"
    assert model.ffn_load_shape() == (5, 512)
    plan = model.grouped_matmul_plan(B)
    assert plan["moe_grouped_impl"] == "pallas_gmm"
    assert plan["moe_gmm_tiling_up"] == "128x512x2688"
    assert plan["moe_gmm_tiling_down"] == "128x2688x512"
    run = model.paged_run_blocks(bs)
    assert run == 4                     # 16 KB a block: 64 KB a copy
    NB = _whole_runs(B * maxb + 1, run)
    pool = jax.eval_shape(lambda: model.init_kv_pool(NB, bs, B))
    assert pool["k"].shape == (1, NB, bs, 2, 128)
    assert pool["ssm"].shape == (5, B, 8, 128, 1024)
    assert pool["ssm"].dtype == jnp.float32
    assert pool["conv"].shape == (5, B, 3, 10240)
    params = _engine_params(model)
    assert sum(a.size for a in jax.tree.leaves(params)) == 4_648_163_712
    compiled = _engine_decode(model, B * maxb, run).lower(
        placed(params), v5e(B, dtype=jnp.int32), placed(pool),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), v5e(5, 512, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    # the state kernel a Mamba layer, the attention kernel, two grouped
    # matmuls an expert layer
    assert text.count("ssm_state_update_pallas") >= 5
    assert text.count("tpu_custom_call") >= 16
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 11.0 * 2**30
    one_slots_state = 5 * 8 * 128 * 1024 * 4
    assert mem.temp_size_in_bytes < 4 * one_slots_state


# sdar-30b-a3b-chat-d6.block_decode: 32 slots x 8,192 at block 32 (256
# table entries a slot), a block of four rows a slot: the paged kernel's
# head axis carries 4 x 32 query rows over 4 KV heads
BLOCK_CELL_TABLE, BLOCK_CELL_ROWS, BLOCK_CELL_HEADS = 256, 4, (32, 4)


def test_paged_decode_kernel_lowers_at_the_block_cells_rows(v5e):
    """The kernel's signature stays ``[B, H, D]``: a block's rows ride
    its head axis, 128 of them over 4 KV heads at 16 pages a chunk."""
    from ray_tpu.ops.paged_attention import paged_decode_attention_pallas

    B, (H, Hkv), D, bs = CELL_SLOTS, BLOCK_CELL_HEADS, 128, CELL_BS
    maxb, rows = BLOCK_CELL_TABLE, BLOCK_CELL_ROWS
    pool = v5e(B * maxb + 1, bs, Hkv, D)
    assert _mosaic(paged_decode_attention_pallas.lower(
        v5e(B, rows * H, D), pool, pool, v5e(B, maxb, dtype=jnp.int32),
        v5e(B, dtype=jnp.int32), interpret=False))


def test_sdar_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``sdar-30b-a3b-chat-d6.block_decode``'s decode program as the
    engine jits it (``_decode_step_paged_blocks``: the model's rows, every
    slot's block of four and sixteen blocks behind, the sampler, the
    unmask rule and the block's state machine): 32 slots x 8,192, depth
    6, all 128 experts, the whole vocabulary, matmul weights in bf16. The
    v5e's compiler takes it with the paged kernel (ONE call a layer, 48
    grid rows) and the three grouped matmuls as Mosaic calls (1,536 rows
    over 128 experts, a row tile of 128) and no ``ragged_dot``, the pool
    written in place; the head and the sampler see the 128 rows being
    denoised alone."""
    from benchmark import run as harness
    from benchmark.builders import sdar
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    _as_on_the_chip(monkeypatch)
    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/sdar-30b-a3b-chat-d6.json")
    assert harness.load_json(harness.HERE, "traffic", "block_decode.json")[
        "engine"] == {"max_slots": CELL_SLOTS,
                      "max_seq": BLOCK_CELL_TABLE * CELL_BS,
                      "block_size": CELL_BS, "max_ongoing_requests": 64}
    B, bs, maxb, n, E = (CELL_SLOTS, CELL_BS, BLOCK_CELL_TABLE,
                         BLOCK_CELL_ROWS, 128)
    model = sdar.build_model(cfg, maxb * bs)
    assert model.cfg.block_length == n
    assert (model.cfg.n_heads, model.cfg.n_kv_heads) == BLOCK_CELL_HEADS
    assert model.paged_decode_impl() == "pallas"
    assert model.ffn_load_shape() == (6, E)
    room = B // 2                   # blocks behind a pass has room for
    plan = model.grouped_matmul_plan((B + room) * n)
    assert plan["moe_grouped_impl"] == "pallas_gmm"
    assert plan["moe_gmm_tiling_gate"].startswith("128x")
    params = _engine_params(model)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg["parameters"]
    eng = object.__new__(ContinuousBatchingEngine)
    eng.model, eng.num_blocks, eng.block_length = model, B * maxb, n
    eng._behind_slots = room
    eng.kv_run = model.paged_run_blocks(bs)
    assert eng.kv_run == 2              # 32 KB a block: 64 KB a copy
    pool = jax.eval_shape(lambda: model.init_kv_pool(B * maxb + 2, bs))
    compiled = jax.jit(eng._decode_step_paged_blocks,
                       donate_argnums=(2,)).lower(
        placed(params), v5e(B, 3 * n + 2, dtype=jnp.int32), placed(pool),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), v5e(6, E, dtype=jnp.int32),
        v5e(5, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("paged_decode_attention_pallas") >= 1
    # the logits are the current blocks' alone
    assert f"f32[{B},{n},{cfg['vocab_size']}]" in text
    assert f"f32[{B + room},{n},{cfg['vocab_size']}]" not in text
    assert "ragged_dot_tiling" not in text and "ragged-dot" not in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print("sdar cell fit GiB:", {
        "arguments": mem.argument_size_in_bytes / 2**30,
        "temporaries": mem.temp_size_in_bytes / 2**30,
        "total": total / 2**30})
    assert 11.0 * 2**30 < total < 11.6 * 2**30
    # the pool is written in place and no expert stack is copied out
    one_stack_a_layer = E * 2048 * 768 * 2
    assert mem.temp_size_in_bytes < one_stack_a_layer


# jamba2-3b.long_decode_mamba1: 32 slots x 24,576 at block 32 (768 table
# entries a slot), the WHOLE published model, 28 layers in five scanned runs
MAMBA1_CELL_SLOTS, MAMBA1_CELL_TABLE = 32, 768


def test_mamba1_kernels_lower_at_the_cells_shapes(v5e):
    """The Mamba-1 decode update (a traced layer index into the stack of
    26 x 32 rows of S [16, 5120] float32, aliased) and the prefill scan
    (a chunk-prefill call's 512 positions and the check's two-row bucket
    of 1,088, ``u`` in bf16) compile for the v5e as Mosaic calls."""
    from ray_tpu.ops import ssm1

    f32, i32 = jnp.float32, jnp.int32
    L, B, N, W = 26, MAMBA1_CELL_SLOTS, 16, 5120
    update = jax.jit(ssm1.state_update_pallas, donate_argnums=0).lower(
        v5e(L, B, N, W, dtype=f32), v5e(dtype=i32), v5e(N, W, dtype=f32),
        v5e(B, W, dtype=f32), v5e(B, W, dtype=f32), v5e(B, N, dtype=f32),
        v5e(B, N, dtype=f32))
    assert _mosaic(update)
    # in place: nothing of the stack's size, nor a [B, N, W] decay, beside it
    assert update.compile().memory_analysis().temp_size_in_bytes < N * W * 4
    for rows, T in ((1, 512), (2, 1088)):
        scan = jax.jit(ssm1.selective_scan_pallas).lower(
            v5e(rows, T, W), v5e(rows, T, W, dtype=f32), v5e(N, W, dtype=f32),
            v5e(rows, T, N, dtype=f32), v5e(rows, T, N, dtype=f32),
            v5e(rows, N, W, dtype=f32))
        assert _mosaic(scan)


def test_mamba1_cell_decode_program_fits_the_v5e(v5e, placed, monkeypatch):
    """``jamba2-3b.long_decode_mamba1``'s decode program as the engine jits
    it: 32 slots x 24,576, ALL 28 layers of the published model (3.03 B
    parameters, 5.64 GiB in bf16) in five scanned runs: three Mosaic
    state-update calls (one a run of Mamba layers, each rewriting its
    layer's rows of S IN PLACE) and two paged attention calls over ONE
    K/V head under 20 query heads. The v5e's compiler takes it at 6.69
    GiB of 15.75 (5.65 of weights, 1.03 of pool and state, 0.02 of
    temporaries): no layer's slice of a weight stack, no stack of state
    and no [32, 16, 5120] decay is among the temporaries. The pool's
    blocks of 8 KB lie in runs of 8 and the two attention calls read it
    as pages of 256 rows, a view that costs nothing: no pool-sized
    temporary."""
    from benchmark import run as harness
    from benchmark.builders import jamba

    _as_on_the_chip(monkeypatch)
    cfg = harness.load_json(harness.ROOT, "benchmark/configs/jamba2-3b.json")
    B, bs, maxb = MAMBA1_CELL_SLOTS, CELL_BS, MAMBA1_CELL_TABLE
    model = jamba.build_model(cfg, maxb * bs)
    assert model.recurrent and model.paged_decode_impl() == "pallas"
    assert model.ffn_load_shape() is None
    run = model.paged_run_blocks(bs)
    assert run == 8                     # 8 KB a block: 64 KB a copy
    NB = _whole_runs(B * maxb + 1, run)
    pool = jax.eval_shape(lambda: model.init_kv_pool(NB, bs, B))
    assert pool["k"].shape == (2, NB, bs, 1, 128)
    assert pool["ssm"].shape == (26, B, 1, 16, 5120)
    assert pool["ssm"].dtype == jnp.float32
    assert pool["conv"].shape == (26, B, 3, 5120)
    params = _engine_params(model)
    assert sum(a.size for a in jax.tree.leaves(params)) == 3_029_337_472
    compiled = _engine_decode(model, B * maxb, run).lower(
        placed(params), v5e(B, dtype=jnp.int32), placed(pool),
        v5e(B, maxb, dtype=jnp.int32), v5e(B, dtype=jnp.int32),
        *_sampling(v5e, B), None).compile()
    text = compiled.as_text()
    # a state kernel a RUN of Mamba layers, an attention kernel a layer
    assert text.count("ssm1_state_update_pallas") >= 3
    assert text.count("tpu_custom_call") >= 5
    # both attention calls take the stack as pages of 256 rows (a run of
    # 8 blocks of 32), none as pages of 32
    paged = [line for line in text.splitlines()
             if "tpu_custom_call" in line
             and "paged_decode_attention_pallas" in line]
    assert len(paged) == 2
    for line in paged:
        assert f"bf16[{2 * NB // run},{run * bs},128]" in line
        assert f"bf16[{2 * NB},{bs},128]" not in line
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 6.8 * 2**30
    # under one layer's SwiGLU matrix (40 MiB): no weight slice is copied
    assert mem.temp_size_in_bytes < 2560 * 8192 * 2
