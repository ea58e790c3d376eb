"""Paged KV cache: block pool, prefix reuse, preemption, paged kernel.

Reference capability: vLLM's BlockSpaceManager/prefix caching behind
`ray.llm` (`python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:126-207`); PAPERS.md paged attention.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import ContinuousBatchingEngine, SamplingParams
from ray_tpu.llm.paged_cache import (BlockPool, allocate_slot,
                                     ensure_capacity, seal_prompt_blocks)
from ray_tpu.models.llama import LlamaConfig, LlamaModel


# ---------------------------------------------------------------------------
# BlockPool host logic (no device work)
# ---------------------------------------------------------------------------

def test_pool_alloc_free_refcount():
    pool = BlockPool(4, 16)
    a = pool.alloc(3)
    assert len(a) == 3 and pool.num_free == 1
    assert pool.alloc(2) is None          # over-ask fails atomically
    assert pool.num_free == 1
    pool.ref(a[0])                        # second reference
    pool.unref(a[0])
    assert pool.num_free == 1             # still held once
    pool.unref_all(a)
    assert pool.num_free == 4
    with pytest.raises(ValueError):
        pool.unref(a[0])


def test_chain_hashes_full_blocks_only():
    h = BlockPool.chain_hashes([1, 2, 3, 4, 5], 2)
    assert len(h) == 2                    # 5 tokens -> 2 full blocks
    # chain: same prefix -> same hashes; divergence changes the tail
    h2 = BlockPool.chain_hashes([1, 2, 3, 9], 2)
    assert h2[0] == h[0] and h2[1] != h[1]


def test_prefix_match_and_resurrection():
    pool = BlockPool(4, 4)
    prompt = list(range(9))               # 2 full blocks + partial tail
    alloc, shared = allocate_slot(pool, prompt, 10)
    assert shared == 0 and len(alloc.blocks) == 3
    seal_prompt_blocks(pool, alloc, prompt)
    pool.unref_all(alloc.blocks)          # request finished
    assert pool.num_free == 4
    assert pool.cached_free_blocks() == 2
    # identical prompt: both full blocks resurrect from the free list
    alloc2, shared2 = allocate_slot(pool, prompt, 10)
    assert shared2 == 8
    assert alloc2.blocks[:2] == alloc.blocks[:2]
    assert pool.stats["prefix_hits"] >= 1


def test_block_aligned_prompt_never_shares_last_block():
    pool = BlockPool(8, 4)
    prompt = list(range(8))               # exactly 2 blocks
    alloc, _ = allocate_slot(pool, prompt, len(prompt))
    seal_prompt_blocks(pool, alloc, prompt)
    pool.unref_all(alloc.blocks)
    # full-prompt hit would skip prefill entirely; the last block must
    # re-prefill so the engine gets last-token logits
    _, shared = allocate_slot(pool, prompt, len(prompt))
    assert shared == 4


def test_eviction_drops_prefix_entry():
    pool = BlockPool(2, 4)
    alloc, _ = allocate_slot(pool, [1, 2, 3, 4], 8)
    seal_prompt_blocks(pool, alloc, [1, 2, 3, 4])
    pool.unref_all(alloc.blocks)
    assert pool.cached_free_blocks() == 1
    pool.alloc(2)                         # forces reuse of the cached block
    assert pool.cached_free_blocks() == 0
    assert pool.stats["evictions"] == 1
    assert pool.match_prefix(BlockPool.chain_hashes([1, 2, 3, 4], 4)) == []


def test_ensure_capacity_growth_and_exhaustion():
    pool = BlockPool(3, 4)
    alloc, _ = allocate_slot(pool, [1, 2, 3], 4)
    assert len(alloc.blocks) == 1
    assert ensure_capacity(pool, alloc, 9)
    assert len(alloc.blocks) == 3
    assert not ensure_capacity(pool, alloc, 13)   # pool exhausted
    assert len(alloc.blocks) == 3


# ---------------------------------------------------------------------------
# Engine end-to-end on the debug model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.debug(vocab_size=512, max_seq_len=128)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def _greedy(model, params, prompt, n):
    """Uncached greedy reference."""
    seq = list(prompt)
    out = []
    for _ in range(n):
        logits = model.apply(params, jnp.asarray([seq], jnp.int32))
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        seq.append(tok)
    return out


def test_block_reclaim_after_finish(tiny_model):
    model, params = tiny_model
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_seq=64,
                                   prefill_buckets=(8, 16), block_size=8)
    free0 = eng.pool.num_free
    reqs = eng.generate([[1, 2, 3], [4, 5, 6, 7, 8, 9]],
                        SamplingParams(max_tokens=6))
    assert all(len(r.output) == 6 for r in reqs)
    assert eng.pool.num_free == free0          # every block reclaimed
    assert all(r == 0 for r in eng.pool.refcount)


def test_prefix_reuse_cross_request_correctness(tiny_model):
    """Second request sharing a long prefix must reuse blocks AND
    produce exactly the no-sharing greedy output."""
    model, params = tiny_model
    prefix = [(7 * i + 3) % 500 for i in range(16)]   # 2 full blocks @ 8
    p1 = prefix + [100, 101]
    p2 = prefix + [200, 201, 202]
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_seq=64,
                                   prefill_buckets=(8, 16, 32),
                                   block_size=8)
    r1 = eng.generate([p1], SamplingParams(max_tokens=4))[0]
    assert eng.stats["prefix_prefills"] == 0
    r2 = eng.generate([p2], SamplingParams(max_tokens=4))[0]
    assert eng.stats["prefix_prefills"] == 1
    assert eng.stats["prefix_tokens_reused"] == 16
    assert r1.output == _greedy(model, params, p1, 4)
    assert r2.output == _greedy(model, params, p2, 4)


def test_prefix_reuse_concurrent_requests(tiny_model):
    """Same-prefix requests running TOGETHER share physical blocks
    (refcount > 1 on the prefix while all are active). Sealing happens
    at admission, so the sharers arrive one step after the first."""
    model, params = tiny_model
    prefix = [(11 * i + 5) % 500 for i in range(8)]   # 1 full block @ 8
    eng = ContinuousBatchingEngine(model, params, max_slots=4, max_seq=64,
                                   prefill_buckets=(8, 16), block_size=8)
    prompts = [prefix + [100 + i] for i in range(3)]
    r0 = eng.submit(prompts[0], SamplingParams(max_tokens=8))
    eng.step()                       # prefill + seal the prefix block
    r1 = eng.submit(prompts[1], SamplingParams(max_tokens=8))
    r2 = eng.submit(prompts[2], SamplingParams(max_tokens=8))
    eng.step()                       # admits both; r0 still active
    prefix_block = eng.allocs[0].blocks[0]
    assert eng.pool.refcount[prefix_block] == 3   # shared by all three
    while eng.has_work():
        eng.step()
    reqs = [r0, r1, r2]
    assert all(len(r.output) == 8 for r in reqs)
    assert eng.stats["prefix_prefills"] == 2
    assert eng.stats["prefix_tokens_reused"] == 16
    for p, r in zip(prompts, reqs):
        assert r.output == _greedy(model, params, p, 8)


def test_preemption_by_recompute(tiny_model):
    """A pool too small for both requests' full generations must
    preempt (recompute) yet still finish both with correct outputs."""
    model, params = tiny_model
    p1, p2 = [1, 2, 3, 4, 5], [9, 8, 7]
    # each request needs up to ceil((5+20)/8)=4 blocks; 5 blocks total
    # forces at least one preemption while both are active
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_seq=64,
                                   prefill_buckets=(8, 16), block_size=8,
                                   num_blocks=5)
    reqs = eng.generate([p1, p2], SamplingParams(max_tokens=20))
    assert all(len(r.output) == 20 for r in reqs)
    assert eng.stats["preemptions"] >= 1
    assert sum(r.preemptions for r in reqs) >= 1
    assert reqs[0].output == _greedy(model, params, p1, 20)
    assert reqs[1].output == _greedy(model, params, p2, 20)
    assert eng.pool.num_free == 5


def test_oversubscribed_pool_many_requests(tiny_model):
    """More concurrent demand than the pool can hold: FIFO admission +
    preemption must drain everything."""
    model, params = tiny_model
    eng = ContinuousBatchingEngine(model, params, max_slots=4, max_seq=64,
                                   prefill_buckets=(8, 16, 32),
                                   block_size=8, num_blocks=6)
    prompts = [[10 + i, 20 + i, 30 + i] for i in range(8)]
    reqs = eng.generate(prompts, SamplingParams(max_tokens=10))
    assert all(len(r.output) == 10 for r in reqs)
    assert all(r.finish_reason == "length" for r in reqs)
    assert eng.pool.num_free == 6


def test_chunked_prefill_long_prompt(tiny_model):
    """A prompt LONGER than the largest prefill bucket admits via
    chunked prefill (each chunk attends over the prior chunks' blocks)
    and still matches the uncached greedy reference."""
    model, params = tiny_model
    prompt = [(13 * i + 11) % 500 for i in range(21)]  # > bucket 16
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_seq=64,
                                   prefill_buckets=(8, 16), block_size=8)
    req = eng.generate([prompt], SamplingParams(max_tokens=5))[0]
    assert len(req.output) == 5
    assert req.output == _greedy(model, params, prompt, 5)


# ---------------------------------------------------------------------------
# Paged attention kernel (interpret mode)
# ---------------------------------------------------------------------------

def test_paged_kernel_matches_reference():
    from ray_tpu.ops.paged_attention import (
        paged_decode_attention_pallas, paged_decode_attention_reference)
    rng = np.random.default_rng(0)
    B, H, Hkv, D, bs, NB, maxb = 4, 8, 4, 128, 16, 32, 6
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, Hkv, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(NB)[:B * maxb].reshape(B, maxb), jnp.int32)
    lengths = jnp.asarray([1, 16, 37, 96], jnp.int32)
    ref = paged_decode_attention_reference(q, kp, vp, tables, lengths)
    out = paged_decode_attention_pallas(q, kp, vp, tables, lengths,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def _engine_like_case(name):
    """What the engine really sends the kernel: tables padded with the
    scratch block (the pool's last), lengths = offsets + 1. 72 table
    entries a slot are three of the kernel's chunks (32 pages of 64
    tokens). At D 32 four KV heads share a row of the kernel's pages
    (``_lane_pack``), as two do at llama3_1b's D 64 on the chip;
    "one_head_a_row" is the unpacked layout of the cells' D 128."""
    rng = np.random.default_rng(5)
    B, Hkv, G, D, bs, maxb = 4, 4, 4, 32, 64, 72
    dtype, tol = jnp.float32, 2e-5
    scratch = B * maxb
    lengths = {
        "idle_slot": [1, 2100, 1, 4200],
        "block_boundary": [64, 2048, 4096, 4608],
        "boundary_plus_one": [65, 2049, 4097, 4545],
        "full_table": [4608, 4608, 4608, 4608],
        "mixed_gqa4": [3, 1300, 2500, 4607],
        "bf16_pool": [5, 1025, 2048, 4500],
        "one_head_a_row": [3, 1300, 2500, 4607],
        "empty_slot": [0, 1300, 0, 4607],
    }[name]
    if name == "bf16_pool":
        dtype, tol = jnp.bfloat16, 2e-2
    if name == "one_head_a_row":
        Hkv, D = 1, 128
    tables = rng.permutation(scratch).reshape(B, maxb)
    for b, n in enumerate(lengths):
        tables[b, -(-n // bs):] = scratch          # never allocated
    if name == "idle_slot":
        tables[[0, 2]] = scratch                    # the whole row
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(scratch + 1, bs, Hkv, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(scratch + 1, bs, Hkv, D)), dtype)
    return (q, kp, vp, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32)), tol


@pytest.mark.parametrize("case", [
    "idle_slot", "block_boundary", "boundary_plus_one", "full_table",
    "mixed_gqa4", "bf16_pool", "one_head_a_row", "empty_slot"])
def test_paged_kernel_on_engine_inputs(case):
    from ray_tpu.ops.paged_attention import (
        CHUNK_ROWS, _lane_pack, paged_decode_attention_pallas,
        paged_decode_attention_reference)
    args, tol = _engine_like_case(case)
    _, bs, Hkv, D = args[1].shape
    assert _lane_pack(D, Hkv) == (1 if case == "one_head_a_row" else 4)
    page_rows = bs * Hkv // _lane_pack(D, Hkv)
    assert args[3].shape[1] * page_rows > 2 * CHUNK_ROWS   # three chunks
    ref = paged_decode_attention_reference(*args)
    out = paged_decode_attention_pallas(*args, interpret=True)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    live = np.asarray(args[4]) > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=tol, rtol=tol)
    # a slot of length 0 attends nothing: 0, not the mean of stale rows
    assert not np.asarray(out, np.float32)[~live].any()


@pytest.mark.parametrize("case", ["mixed_gqa4", "one_head_a_row"])
def test_paged_attention_reads_a_window_of_a_stack(case):
    """``first_block``/``num_blocks``: the tables count from a window of
    the pools (a layer's blocks of the stack ``decode_step_paged``
    carries). Kernel (packed rows and one head a row) and reference read
    window 1 of three as they read that window alone; the windows either
    side hold other values."""
    from ray_tpu.ops.paged_attention import paged_decode_attention
    (q, kp, vp, tables, lengths), tol = _engine_like_case(case)
    NB = kp.shape[0]
    k3, v3 = (jnp.concatenate([p + 1.0, p, p - 1.0]) for p in (kp, vp))
    for impl in ("xla", "pallas"):
        alone = paged_decode_attention(q, kp, vp, tables, lengths, impl=impl)
        windowed = jax.jit(functools.partial(
            paged_decode_attention, impl=impl, num_blocks=NB))(
                q, k3, v3, tables, lengths, first_block=jnp.int32(NB))
        np.testing.assert_allclose(np.asarray(windowed), np.asarray(alone),
                                   atol=tol, rtol=tol)
        other = paged_decode_attention(q, k3, v3, tables, lengths, impl=impl,
                                       first_block=2 * NB, num_blocks=NB)
        assert float(jnp.max(jnp.abs(other - alone))) > 0.5


@pytest.mark.parametrize("head_dim,kv_heads,pack,lowers", [
    (128, 8, 1, True),      # the serve cells (mistral-7b)
    (64, 8, 2, True),       # llama3_1b: two KV heads fill the 128 lanes
    (64, 12, 2, True),      # gpt2's widths
    (256, 4, 1, True),
    (32, 8, 4, True),
    (64, 1, 1, False),      # one KV head of 64 cannot fill a row
    (80, 8, 1, False),      # 128 is no multiple of 80
    (96, 8, 1, False),
])
def test_kernel_lane_packing_rule(head_dim, kv_heads, pack, lowers):
    from ray_tpu.ops.paged_attention import _lane_pack, kernel_lowers
    assert _lane_pack(head_dim, kv_heads) == pack
    assert kernel_lowers(head_dim, kv_heads) is lowers


def test_chunk_is_sized_by_rows_not_pages():
    """The kernel's VMEM follows CHUNK_ROWS whatever the block_size: a
    chunk holds as many whole pages as fit, at least one."""
    from ray_tpu.ops.paged_attention import (CHUNK_ROWS,
                                             paged_decode_attention_pallas)

    def chunk_rows(bs, Hkv, D, maxb):
        pool = jax.ShapeDtypeStruct((9, bs, Hkv, D), jnp.float32)
        jaxpr = jax.make_jaxpr(functools.partial(
            paged_decode_attention_pallas, interpret=True))(
            jax.ShapeDtypeStruct((2, Hkv, D), jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((2, maxb), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32))
        call = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                if e.primitive.name == "pallas_call"][0]
        return call.invars[3].aval.shape[1]      # the bias: [H, rows]

    assert chunk_rows(32, 8, 128, 96) == CHUNK_ROWS == 8 * 32 * 8
    assert chunk_rows(128, 8, 128, 24) == CHUNK_ROWS       # 2 pages
    assert chunk_rows(32, 8, 64, 64) == CHUNK_ROWS         # 16 packed
    assert chunk_rows(512, 8, 128, 6) == 512 * 8           # one page
    assert chunk_rows(32, 8, 128, 4) == 4 * 32 * 8         # the table


def _has_kernel(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_dispatcher_runs_the_side_it_is_told():
    from ray_tpu.ops.paged_attention import (default_impl,
                                             paged_decode_attention)
    args, _ = _engine_like_case("mixed_gqa4")
    # the platform's side of the choice: the reference on this backend
    assert jax.default_backend() == "cpu"
    assert default_impl(128, 8) == default_impl(64, 8) == "xla"
    forced = functools.partial(paged_decode_attention, impl="pallas")
    oracle = functools.partial(paged_decode_attention, impl="xla")
    assert _has_kernel(forced, *args)
    assert not _has_kernel(oracle, *args)
    np.testing.assert_allclose(np.asarray(forced(*args)),
                               np.asarray(oracle(*args)),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError):
        paged_decode_attention(*args, impl="mosaic")
    with pytest.raises(TypeError):       # nobody resolves in here
        paged_decode_attention(*args)


def test_decode_program_choice_by_configuration(tiny_model):
    """No ``decode_attention`` set: the platform decides for
    ``decode_step_paged`` (the reference on this CPU backend); a set one
    forces its side; ``forward_step`` holds no kernel."""
    import dataclasses
    model, params = tiny_model
    assert LlamaConfig().decode_attention is None
    assert model.cfg.decode_attention is None
    assert model.paged_decode_impl() == "xla"
    forced = LlamaModel(dataclasses.replace(model.cfg,
                                            decode_attention="pallas"))
    assert forced.paged_decode_impl() == "pallas"
    with pytest.raises(ValueError):
        dataclasses.replace(model.cfg, decode_attention="auto")

    pool = model.init_kv_pool(5, 8)
    step_args = (params, jnp.zeros((2,), jnp.int32), pool,
                 jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32))
    assert not _has_kernel(model.decode_step_paged, *step_args)
    assert _has_kernel(forced.decode_step_paged, *step_args)
    cache = model.init_kv_cache(2, 16)
    fwd_args = (params, jnp.zeros((2, 1), jnp.int32), cache,
                jnp.zeros((2,), jnp.int32))
    assert not _has_kernel(model.forward_step, *fwd_args)

    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_seq=32,
                                   prefill_buckets=(8,), block_size=8)
    assert eng.decode_attention_impl == "xla"
    eng_p = ContinuousBatchingEngine(forced, params, max_slots=2,
                                     max_seq=32, prefill_buckets=(8,),
                                     block_size=8)
    assert eng_p.decode_attention_impl == "pallas"


def test_engine_counts_the_blocks_decode_reads(tiny_model):
    model, params = tiny_model
    eng = ContinuousBatchingEngine(model, params, max_slots=4, max_seq=64,
                                   prefill_buckets=(8, 16), block_size=8)
    assert eng.stats["decode_kv_blocks_live"] == 0
    assert eng.stats["decode_kv_blocks_table"] == 0
    eng.submit([3, 1, 4, 1, 5], SamplingParams(max_tokens=30))
    eng.submit(list(range(1, 14)), SamplingParams(max_tokens=30))
    live = table = 0
    for _ in range(6):
        before = eng.stats["decode_steps"]
        eng.step()
        assert eng.stats["decode_steps"] == before + 1
        # after the step each live slot's offset counts the token the
        # step cached: the program read ceil(offset / bs) blocks of it
        offs = [int(eng.offsets[i]) for i, r in enumerate(eng.slots)
                if r is not None]
        assert len(offs) == 2
        live += sum(-(-o // 8) for o in offs)
        table += len(offs) * eng.blocks_per_slot
        assert eng.stats["decode_kv_blocks_live"] == live
        assert eng.stats["decode_kv_blocks_table"] == table
    assert eng.blocks_per_slot == 8 and table == 6 * 2 * 8
    assert 0 < live < table


def test_paged_reference_gather_equals_dense():
    """The paged XLA fallback must equal ragged attention on the dense
    equivalent of the same block layout."""
    from ray_tpu.ops.paged_attention import (
        paged_decode_attention_reference, ragged_decode_attention_reference)
    rng = np.random.default_rng(1)
    B, H, Hkv, D, bs, NB, maxb = 2, 4, 2, 16, 8, 16, 4
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, bs, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, Hkv, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:B * maxb].reshape(B, maxb),
                         jnp.int32)
    lengths = jnp.asarray([13, 27], jnp.int32)
    paged = paged_decode_attention_reference(q, kp, vp, tables, lengths)
    k_dense = kp[tables].reshape(B, maxb * bs, Hkv, D)
    v_dense = vp[tables].reshape(B, maxb * bs, Hkv, D)
    dense = ragged_decode_attention_reference(q, k_dense, v_dense, lengths)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               atol=1e-6)


def test_paged_decode_step_pallas_matches_xla(tiny_model):
    """model.decode_step_paged over the PALLAS paged kernel (interpret
    off-TPU) must match the XLA fallback's logits on identical pool
    state — logits, not greedy tokens: bf16 rounding can legally flip
    near-tied argmaxes on a 512-vocab debug model."""
    import dataclasses

    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    model, params = tiny_model
    cfg_p = dataclasses.replace(model.cfg, decode_attention="pallas")
    model_p = LlamaModel(cfg_p)

    # build LIVE pool state: submit long generations and stop mid-run so
    # slots still own real multi-block tables (a finished slot's table
    # resets to scratch and would compare degenerate inputs)
    eng = ContinuousBatchingEngine(model, params, max_slots=2, max_seq=64,
                                   prefill_buckets=(8, 16), block_size=8)
    eng.submit([3, 1, 4, 1, 5], SamplingParams(max_tokens=40))
    eng.submit([2, 7, 2, 7, 2, 7, 2, 7, 2], SamplingParams(max_tokens=40))
    for _ in range(12):                     # grow past one block each
        eng.step()
    assert all(r is not None for r in eng.slots[:2])
    tables_np = np.array(eng._tables[:2])
    offsets_np = np.array(eng.offsets[:2])
    assert (offsets_np > 8).all()           # >1 live block per slot
    assert len({int(x) for x in tables_np[:, :2].ravel()}) > 2
    pool = {"k": eng.kv["k"], "v": eng.kv["v"]}
    tokens = jnp.asarray([9, 11], jnp.int32)
    tables = jnp.asarray(tables_np, jnp.int32)
    offsets = jnp.asarray(offsets_np, jnp.int32)
    lx, _ = model.decode_step_paged(params, tokens, pool, tables, offsets)
    lp, _ = model_p.decode_step_paged(params, tokens, pool, tables,
                                      offsets)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               atol=0.15, rtol=0.05)   # bf16 K/V path


def test_paged_engine_soak_no_leaks(tiny_model):
    """Sustained mixed load (prefix sharing, varied lengths, slot churn)
    must reclaim every block and leave zero refcounts."""
    model, params = tiny_model
    rng = np.random.default_rng(0)
    eng = ContinuousBatchingEngine(model, params, max_slots=6, max_seq=64,
                                   prefill_buckets=(8, 16, 32),
                                   block_size=8, num_blocks=40)
    prefixes = [list(rng.integers(1, 500, 8)) for _ in range(3)]
    reqs = []
    for i in range(120):
        if i % 2 == 0:
            p = list(prefixes[i % 3]) + list(rng.integers(1, 500, 3))
        else:
            p = list(rng.integers(1, 500, int(rng.integers(2, 20))))
        reqs.append(eng.submit(
            p, SamplingParams(max_tokens=int(rng.integers(1, 8)))))
    while eng.has_work():
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(r.finish_reason is not None for r in reqs)
    assert eng.pool.num_free == 40            # fully reclaimed
    assert all(c == 0 for c in eng.pool.refcount)
    assert eng.stats["prefix_prefills"] > 0   # sharing actually happened


# ---------------------------------------------------------------------------
# The pool through a decode step: whole, written in place, addressed by
# layer (the layer scan carries the stack [L*NB, bs, Hkv, D]; PERF.md, PR 27)
# ---------------------------------------------------------------------------

def _deep_model(kind, impl, layers=3, dtype=jnp.float32):
    """A debug model of three layers (the first, a middle and the last
    one's blocks each have a neighbour layer to spill into), its decode
    attention forced to ``impl``."""
    import dataclasses

    from ray_tpu.models import MoEConfig, model_for
    cfg = {"dense": LlamaConfig.debug(vocab_size=512),
           "olmoe": MoEConfig.debug_olmoe(),
           # the cells' head_dim: the kernel reads one KV head to a row
           # and the stack's pages where they lie (no packed copy)
           "dense_d128": LlamaConfig(
               vocab_size=512, dim=256, n_heads=2, n_kv_heads=1,
               ffn_dim=256, max_seq_len=128, remat=False)}[kind]
    model = model_for(dataclasses.replace(
        cfg, n_layers=layers, dtype=dtype, decode_attention=impl))
    return model, jax.jit(model.init)(jax.random.key(3))


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def layer_scan_operands(model, params, slots=2, num_blocks=7, bs=8, maxb=2):
    """(xs shapes, ys shapes, carry shapes, the pool's per-layer shape,
    the stack's shape) of the layer scan of ``decode_step_paged``."""
    pool = model.init_kv_pool(num_blocks, bs)
    jaxpr = jax.make_jaxpr(model.decode_step_paged)(
        params, jnp.zeros((slots,), jnp.int32), pool,
        jnp.zeros((slots, maxb), jnp.int32), jnp.zeros((slots,), jnp.int32))
    L = model.cfg.n_layers
    scan, = [e for e in _scans(jaxpr.jaxpr) if e.params["length"] == L]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]
    per_layer = tuple(pool["k"].shape[1:])
    return (shapes(scan.invars[n_consts + n_carry:]),
            shapes(scan.outvars[n_carry:]),
            shapes(scan.invars[n_consts:n_consts + n_carry]),
            per_layer, (L * per_layer[0],) + per_layer[1:])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_layer_scan_does_not_scan_over_the_pool(impl):
    """Handed to the layer scan as ``xs`` and returned as ``ys``, the
    pool is sliced out of its stack and written back a layer and copied
    whole around the loop (22 of a 40 ms step on the chip). The scan
    carries the whole stack instead, twice (k and v), and no operand it
    scans over or stacks up has a layer's pool shape."""
    model, params = _deep_model("dense", impl)
    xs, ys, carry, per_layer, stack = layer_scan_operands(model, params)
    assert per_layer not in xs and per_layer not in ys
    assert ys == []                     # the dense block stacks nothing
    assert carry.count(stack) == 2
    # ... and the step's result is still the pool in its own layout
    pool = model.init_kv_pool(7, 8)
    _, out = jax.eval_shape(
        model.decode_step_paged, params, jnp.zeros((2,), jnp.int32), pool,
        jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), out) == jax.tree.map(
        lambda a: (a.shape, a.dtype), pool)


def paged_against_dense(model, params, prompt=13, steps=6, bs=8, seed=0):
    """Prefill two sequences into ``forward_step``'s dense cache, lay it
    into a pool through SHUFFLED block tables, then decode ``steps``
    tokens down both paths. Returns the largest |logit difference| of
    the steps, and of the pool read back through the tables against the
    dense cache, per layer. Each layer's K/V differ (they are the
    layer's own projections), so a row written to, or a page read from,
    another layer's blocks shows in both."""
    import dataclasses
    I32 = jnp.int32
    dense = type(model)(dataclasses.replace(model.cfg,
                                            decode_attention="xla"))
    rng = np.random.default_rng(seed)
    B, total = 2, prompt + steps
    maxb = -(-total // bs)
    toks = jnp.asarray(rng.integers(1, model.cfg.vocab_size, (B, total)), I32)
    cache = dense.init_kv_cache(B, maxb * bs)
    padded = jnp.zeros((B, maxb * bs), I32).at[:, :prompt].set(
        toks[:, :prompt])
    _, cache = dense.forward_step(params, padded, cache, jnp.zeros((B,), I32))
    L = model.cfg.n_layers
    NB = B * maxb + 3                  # two blocks nobody owns, + scratch
    ids = jnp.asarray(rng.permutation(NB - 1)[:B * maxb], I32)
    tables = ids.reshape(B, maxb)
    pool = model.init_kv_pool(NB, bs)
    pool = {n: pool[n].at[:, ids].set(
        cache[n].reshape(L, B * maxb, bs, *cache[n].shape[3:]))
        for n in ("k", "v")}
    worst = 0.0
    step = jax.jit(model.decode_step_paged)
    for pos in range(prompt, total):
        offsets = jnp.full((B,), pos, I32)
        got, pool = step(params, toks[:, pos], pool, tables, offsets)
        want, cache = dense.forward_step(params, toks[:, pos:pos + 1], cache,
                                         offsets)
        worst = max(worst, float(jnp.max(jnp.abs(got - want[:, 0]))))
    per_layer = [max(
        float(jnp.max(jnp.abs(
            pool[n][l][tables].reshape(B, maxb * bs, *pool[n].shape[3:])
            [:, :total] - cache[n][l][:, :total])))
        for n in ("k", "v")) for l in range(L)]
    return worst, per_layer


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("kind", ["dense", "olmoe", "dense_d128"])
def test_paged_decode_keeps_each_layers_blocks_apart(kind, impl):
    """Six steps (across a block boundary) of ``decode_step_paged``
    against ``forward_step``'s dense cache on the same tokens, in
    float32: with layer ``l`` addressed as pages ``l*NB + p`` of one
    stack, an index that is off lands in another layer's live blocks,
    not out of range, and only the values can tell."""
    from ray_tpu.ops.paged_attention import _lane_pack
    model, params = _deep_model(kind, impl)
    assert model.cfg.n_layers == 3 and model.paged_decode_impl() == impl
    assert (_lane_pack(model.cfg.head_dim, model.cfg.n_kv_heads) == 1) == (
        kind == "dense_d128")
    worst, per_layer = paged_against_dense(model, params)
    assert worst < 1e-4, worst
    assert max(per_layer) < 1e-5, per_layer


@pytest.mark.parametrize("program", ["apply", "forward_step",
                                     "decode_step_paged",
                                     "prefill_with_prefix"])
def test_one_override_of_the_layer_reaches_every_program(program):
    """The decoder layer is written ONCE (``LlamaModel._layer``); what a
    program supplies is how K/V are kept. So a subclass that changes the
    layer in one place (here: the attention output, louder) changes the
    training forward, the bucket prefill and its T == 1 steps, the paged
    decode step and the prefix prefill alike: each agrees with ``apply``
    of the same subclass on the same tokens, in float32, and none with
    the plain model's."""
    import dataclasses
    I32 = jnp.int32

    class Louder(LlamaModel):
        def _layer(self, x, layer, positions, attend, **kw):
            def louder(q, k, v):
                o, kv = attend(q, k, v)
                return 1.5 * o, kv
            return super()._layer(x, layer, positions, louder, **kw)

    cfg = dataclasses.replace(LlamaConfig.debug(vocab_size=512),
                              dtype=jnp.float32)
    model, plain = Louder(cfg), LlamaModel(cfg)
    params = model.init(jax.random.key(5))
    B, prompt, total, bs = 2, 11, 16, 8
    toks = jnp.asarray(np.random.default_rng(5).integers(
        1, cfg.vocab_size, (B, total)), I32)
    want = model.apply(params, toks)                         # [B, total, V]
    assert float(jnp.max(jnp.abs(want - plain.apply(params, toks)))) > 0.1

    def prefilled():
        """(logits, cache) of the first ``prompt`` tokens, by the bucket
        prefill into a cache of ``total`` rows."""
        padded = jnp.zeros((B, total), I32).at[:, :prompt].set(
            toks[:, :prompt])
        return model.forward_step(params, padded,
                                  model.init_kv_cache(B, total),
                                  jnp.zeros((B,), I32))

    if program == "apply":      # under remat, and at explicit positions
        got = Louder(dataclasses.replace(cfg, remat=True)).apply(
            params, toks, jnp.arange(total))
    elif program == "forward_step":
        logits, cache = prefilled()
        got = [logits[:, :prompt]]
        for pos in range(prompt, total):
            step, cache = model.forward_step(
                params, toks[:, pos:pos + 1], cache, jnp.full((B,), pos, I32))
            got.append(step)
        got = jnp.concatenate(got, axis=1)
    elif program == "decode_step_paged":
        _, cache = prefilled()
        maxb = total // bs
        tables = jnp.asarray([[3, 0], [1, 4]], I32)      # 5 = scratch
        pool = model.init_kv_pool(6, bs)
        pool = {n: pool[n].at[:, tables.reshape(-1)].set(
            cache[n].reshape(cfg.n_layers, B * maxb, bs,
                             *cache[n].shape[3:])) for n in ("k", "v")}
        got = []
        for pos in range(prompt, total):
            step, pool = model.decode_step_paged(
                params, toks[:, pos], pool, tables, jnp.full((B,), pos, I32))
            got.append(step[:, None])
        got, want = jnp.concatenate(got, axis=1), want[:, prompt:]
    else:
        # rows of the prefix past ``prefix_len`` hold the prompt's own
        # later K/V: they must be masked, not merely zero
        _, cache = prefilled()
        prefix_len = jnp.asarray([8, 5], I32)
        lengths = jnp.asarray([8, 11], I32)              # to ``total``
        Tb = 12
        cols = prefix_len[:, None] + jnp.arange(Tb)[None, :]
        suffix = jnp.take_along_axis(
            jnp.pad(toks, ((0, 0), (0, Tb))), cols, axis=1)
        got, kv = model.prefill_with_prefix(
            params, suffix, cache["k"][:, :, :prompt],
            cache["v"][:, :, :prompt], prefix_len, lengths)
        assert kv["k"].shape == (cfg.n_layers, B, Tb, cfg.n_kv_heads,
                                 cfg.head_dim)
        want = want[:, -1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_decode_step_writes_only_its_own_rows(impl):
    """One step on a pool full of noise: every element outside
    ``(layer, dest_block[b], dest_off[b])`` is bit-identical afterwards:
    the other layers' copies of the same block ids, the blocks either
    side of the scratch block in the stack (the last free block of a
    layer and block 0 of the next), a dead slot's row in scratch aside."""
    model, params = _deep_model("dense", impl, dtype=jnp.bfloat16)
    rng = np.random.default_rng(2)
    L, NB, bs, maxb = model.cfg.n_layers, 9, 8, 3
    scratch = NB - 1
    shape = model.init_kv_pool(NB, bs)["k"].shape
    pool = {n: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
            for n in ("k", "v")}
    # slots 0-2 live at blocks 1-6; slot 3 is dead (all scratch); blocks
    # 0 and 7, the scratch block's neighbours in the stack, are nobody's
    tables = np.array([[1, 4, scratch], [5, scratch, scratch],
                       [2, 6, 3], [scratch] * maxb], np.int32)
    offsets = np.array([9, 7, 16, 0], np.int32)
    dest_block = tables[np.arange(4), offsets // bs]
    assert dest_block.tolist() == [4, 5, 3, scratch]
    _, out = jax.jit(model.decode_step_paged)(
        params, jnp.asarray([5, 6, 7, 0], jnp.int32), pool,
        jnp.asarray(tables), jnp.asarray(offsets))
    written = np.zeros(shape[:3], bool)             # [L, NB, bs]
    written[:, dest_block, offsets % bs] = True
    assert written.sum() == L * 4
    for n in ("k", "v"):
        before = np.asarray(pool[n]).view(np.uint16)
        after = np.asarray(out[n]).view(np.uint16)
        assert out[n].shape == shape
        assert (before[~written] == after[~written]).all(), n
        # the live slots' rows did change, in every layer
        changed = (before != after).any(axis=(-1, -2))
        assert changed[:, dest_block[:3], (offsets % bs)[:3]].all(), n
        for untouched in (0, scratch - 1):
            assert not changed[:, untouched].any()
