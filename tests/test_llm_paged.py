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
from ray_tpu.ops.paged_attention import pack_rows
from tests.program_readers import layer_scan_operands


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
# Blocks in runs (a narrow pool's blocks lie in aligned, contiguous runs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("config,kv_heads,head_dim,run", [
    ("jamba2-3b", 1, 128, 8),
    ("nemotron-3-super-d11", 2, 128, 4),
    ("mellum2-12b-a2.5b-d8", 4, 128, 2),
    ("sdar-30b-a3b-chat-d6", 4, 128, 2),
    ("mistral-7b-v0.3-d6", 8, 128, 1),
    ("olmoe-1b-7b-d3", 16, 128, 1),
    ("evabyte-6.5b-d8", 32, 128, 1),
    ("llama3_1b", 8, 64, 2),          # two heads a row: 32 KB a block
])
def test_run_blocks_by_the_pools_row(config, kv_heads, head_dim, run):
    """The rule at the serve cells' block of 32 rows in bf16: the
    smallest power of two of blocks that reaches 64 KiB."""
    from ray_tpu.ops.paged_attention import RUN_BYTES, run_blocks
    assert RUN_BYTES == 64 * 1024
    assert run_blocks(32, kv_heads, head_dim, 2) == run


def test_run_blocks_stops_at_a_chunk_and_at_a_large_block(monkeypatch):
    from ray_tpu.ops import paged_attention
    from ray_tpu.ops.paged_attention import run_blocks
    assert run_blocks(256, 1, 128, 2) == 1          # 64 KB a block already
    assert run_blocks(16, 1, 128, 2) == 16
    # float32 debug widths: 2 KB a block, two heads a row of the lanes
    assert run_blocks(8, 2, 32, 4) == 32
    # a run never outgrows the kernel's chunk of rows
    monkeypatch.setattr(paged_attention, "CHUNK_ROWS", 128)
    assert run_blocks(32, 1, 128, 2) == 4
    assert run_blocks(32, 2, 128, 2) == 2
    assert run_blocks(32, 2, 64, 2) == 4            # two heads a row


def _drive_pool(pc, pool, seed, steps=600, slots=5):
    """A random sequence of admit / grow / preempt / release on ``pool``
    through ``pc``'s functions (``ray_tpu.llm.paged_cache``, or the
    parent commit's), as an engine makes them: prompts that share
    prefixes, growth a few tokens at a time, exhaustion preempting the
    youngest first. Yields after every operation ``(what, live)``:
    ``live`` maps a slot to its allocation."""
    rng = np.random.default_rng(seed)
    bs = pool.block_size
    stems = [rng.integers(1, 50, size=20 * bs).tolist() for _ in range(3)]
    live, order = {}, []          # slot -> [alloc, tokens held]; oldest first

    def release(slot):
        pool.unref_all(live.pop(slot)[0].blocks)
        order.remove(slot)

    for _ in range(steps):
        op = str(rng.choice(["admit", "grow", "grow", "grow", "release",
                             "preempt"]))
        if op == "admit" and len(live) < slots:
            stem = stems[rng.integers(len(stems))]
            prompt = (stem[:int(rng.integers(1, len(stem)))]
                      + rng.integers(50, 99, size=int(rng.integers(0, bs))
                                     ).tolist())
            got = pc.allocate_slot(pool, prompt, len(prompt) + 1)
            if got is None:
                yield ("full", None, None), live
                continue
            alloc, shared = got
            pc.seal_prompt_blocks(pool, alloc, prompt)
            slot = min(set(range(slots)) - set(live))
            live[slot] = [alloc, len(prompt) + 1]
            order.append(slot)
            yield ("admit", shared, tuple(alloc.blocks)), live
        elif op == "grow" and live:
            slot = int(rng.choice(sorted(live)))
            alloc = live[slot][0]
            live[slot][1] += int(rng.integers(1, 3 * bs))
            preempted = []
            while not pc.ensure_capacity(pool, alloc, live[slot][1]):
                victim = ([s for s in order if s != slot] or [slot])[-1]
                preempted.append(victim)
                release(victim)
                if victim == slot:
                    break
            yield ("grow", tuple(preempted), tuple(alloc.blocks)), live
        elif op in ("release", "preempt") and live:
            slot = order[-1] if op == "preempt" else int(
                rng.choice(sorted(live)))
            release(slot)
            yield (op, slot, None), live


@pytest.mark.parametrize("run", [2, 4, 8])
@pytest.mark.parametrize("seed", [0, 1])
def test_pool_of_runs_keeps_its_invariants(run, seed):
    """Every table aligned and contiguous a run; a block's refcount is
    the number of slots that hold it (a fresh block is in one slot); a
    run is free only when whole; ``num_free`` is what ``alloc`` can
    hand out; exhaustion preempted the youngest first."""
    from ray_tpu.llm import paged_cache as pc
    pool = pc.BlockPool(16 * run + run - 1, 4, run)   # the leftover: never out
    usable = 16 * run
    grown = shared_hits = 0
    for (what, a, b), live in _drive_pool(pc, pool, seed):
        holders = np.zeros(pool.num_blocks, int)
        for alloc, tokens in live.values():
            blocks = alloc.blocks
            assert len(blocks) % run == 0
            assert len(blocks) * 4 >= tokens
            assert len(blocks) * 4 - tokens < run * 4       # no run too many
            assert pool.whole_runs(blocks) == len(blocks)
            for r in range(0, len(blocks), run):
                assert blocks[r] % run == 0
                assert blocks[r:r + run] == list(
                    range(blocks[r], blocks[r] + run))
            holders[blocks] += 1
        assert holders.tolist() == pool.refcount
        assert not holders[usable:].any()
        held_runs = {b // run for b in np.flatnonzero(holders)}
        assert set(pool._free) == set(range(16)) - held_runs
        assert pool.num_free == (16 - len(held_runs)) * run
        if what == "admit":
            assert a % (run * 4) == 0                  # a hit in whole runs
            shared_hits += a > 0
        if what == "grow":
            grown += 1
    assert grown > 50 and shared_hits > 3
    for alloc, _ in list(live.values()):
        pool.unref_all(alloc.blocks)
    assert pool.num_free == usable
    got = pool.alloc(usable)
    assert sorted(got) == list(range(usable)) and pool.alloc(1) is None


# What ``_drive_pool`` logged on the PARENT commit's ``BlockPool(40, 4)``
# (59f2076, before a pool knew of runs): the digest of the whole log of
# each seed, its length, and its last allocation's blocks. Recorded by
# running this file's driver on ``git show 59f2076:ray_tpu/llm/
# paged_cache.py``.
_PARENT_POOL_LOGS = {
    0: ("de2d1631589e45a21b698f2b1d7de84b"
        "70f82cb7935f9f853c1fed5ff8dd553a", 243,
        (26,)),
    1: ("22d0e6c0266129b975c57e206c8ab7cd"
        "c0c97e72c1c7176a8d87401df138d990", 315,
        (21, 18, 17, 26, 35, 22, 27, 12, 8, 10, 4, 5, 33, 28)),
    2: ("596fe8e0bb0c8b08cfec6bb9430915ba"
        "d9454aaeeb73e8480391fe9da96f7e13", 307,
        (27, 37, 36, 16, 18, 14, 0)),
}


@pytest.mark.parametrize("seed", sorted(_PARENT_POOL_LOGS))
def test_pool_of_single_blocks_is_the_parents(seed):
    """``run`` 1 (the default): the same sequence hands out the SAME
    block ids, in the same order, as the allocator did before it knew of
    runs."""
    import hashlib
    from ray_tpu.llm import paged_cache as pc
    for pool in (pc.BlockPool(40, 4), pc.BlockPool(40, 4, run=1)):
        log = [entry for entry, _ in _drive_pool(pc, pool, seed)]
        last = [e[2] for e in log if e[2] is not None][-1]
        digest = hashlib.sha256(repr(log).encode()).hexdigest()
        assert (digest, len(log), last) == _PARENT_POOL_LOGS[seed]


def test_prefix_hit_ends_where_the_blocks_stop_lying_as_one_run():
    """Two slots share a stem's first run and each seals what follows:
    the index then holds blocks of two slots for one chain, and a third
    request takes the whole runs only."""
    from ray_tpu.llm.paged_cache import (BlockPool, allocate_slot,
                                         seal_prompt_blocks)
    pool = BlockPool(32, 2, run=4)
    stem = list(range(1, 13))                      # 6 blocks
    first, _ = allocate_slot(pool, stem + [90, 91, 92, 93], 16)
    seal_prompt_blocks(pool, first, stem + [90, 91, 92, 93])
    assert first.blocks == list(range(8))
    # 6 blocks match, 4 lie as a whole run: the hit is 8 tokens, not 12
    second, shared = allocate_slot(pool, stem + [80, 81, 82, 83], 16)
    assert shared == 8 and second.blocks == [0, 1, 2, 3, 8, 9, 10, 11]
    seal_prompt_blocks(pool, second, stem + [80, 81, 82, 83])
    # blocks 4, 5 of the chain are the first slot's, 6, 7 the second's
    third, shared = allocate_slot(pool, stem + [80, 81, 82, 83, 70], 18)
    assert shared == 8 and third.blocks[:4] == [0, 1, 2, 3]
    assert pool.whole_runs(third.blocks) == len(third.blocks) == 12
    # a run goes back when its LAST holder lets go of it
    for alloc in (first, second):
        pool.unref_all(alloc.blocks)
    assert pool.num_free == 32 - 12
    pool.unref_all(third.blocks)
    assert pool.num_free == 32


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


def test_engine_shares_a_prefix_in_whole_runs(tiny_model):
    """The kernel forced (interpreted) on the debug model: 2 KB blocks,
    so runs of 8 (a slot's table of 15 holds no longer one). Three
    requests on one stem of 9 blocks decode together: each takes the
    stem's one whole run (64 of its 72 tokens) from the first, its
    blocks are referenced by all three, every table lies in runs, and
    the tokens are the uncached greedy ones. A request that ends in a
    run's second block holds all of it: the rest is counted reserved."""
    import dataclasses
    model, params = tiny_model
    forced = LlamaModel(dataclasses.replace(model.cfg,
                                            decode_attention="pallas"))
    eng = ContinuousBatchingEngine(forced, params, max_slots=4, max_seq=120,
                                   prefill_buckets=(8, 16, 32),
                                   block_size=8)
    assert eng.kv_run == eng.pool.run == 8 == eng.stats["kv_run_blocks"]
    # a slot's 15 blocks are two runs, in the pool and in its table
    assert eng.num_blocks == 4 * 16 and eng.kv["k"].shape[1] == 4 * 16 + 8
    assert eng.blocks_per_slot == 15 and eng._tables.shape == (4, 16)
    stem = [(7 * i + 3) % 500 for i in range(72)]
    prompts = [stem + [100 + i, 7] for i in range(3)]
    r0 = eng.submit(prompts[0], SamplingParams(max_tokens=6))
    eng.step()                       # chunked prefill, the stem sealed
    assert eng.stats["kv_blocks_reserved_unfilled"] == 16 - 74 // 8 - 1
    rest = [eng.submit(p, SamplingParams(max_tokens=6))
            for p in prompts[1:]]
    eng.step()
    assert eng.stats["prefix_prefills"] == 2
    assert eng.stats["prefix_tokens_reused"] == 2 * 64
    for alloc in eng.allocs[:3]:
        assert alloc.blocks[:8] == eng.allocs[0].blocks[:8]
        assert eng.pool.whole_runs(alloc.blocks) == len(alloc.blocks) == 16
    assert [eng.pool.refcount[b] for b in eng.allocs[0].blocks] == (
        [3] * 8 + [1] * 8)
    while eng.has_work():
        eng.step()
    assert eng.pool.num_free == 4 * 16
    for p, r in zip(prompts, [r0] + rest):
        assert r.output == _greedy(model, params, p, 6)


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
    # (the pools laid as a model lays them: four heads of 32 a row)
    kp, vp = (pack_rows(jnp.asarray(
        rng.normal(size=(scratch + 1, bs, Hkv, D)), dtype)) for _ in "kv")
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
    (_, _, D), (_, bs, page_heads, lanes) = args[0].shape, args[1].shape
    Hkv = page_heads * lanes // D
    assert _lane_pack(D, Hkv) == (1 if case == "one_head_a_row" else 4)
    page_rows = bs * page_heads
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


@pytest.mark.parametrize("run", [1, 2])
def test_heads_of_64_read_from_a_pool_laid_in_packed_rows(run):
    """Eight K/V heads of 64 lanes (llama3_1b's, LFM2's): the pool lies
    ``[.., 4, 128]``, two heads a row, which is what ``pack_rows`` makes
    of ``[.., 8, 64]`` (the same numbers in the same order). Kernel
    (interpreted) and reference read the packed pool, a window of a stack
    of three, in single blocks and in runs of two, and give what the
    reference gives on the pool laid as heads, which the KERNEL REFUSES
    (one layout: it would have to copy the stack into rows); the kernel's
    page operand is the packed pool viewed ``[NB, bs*4, 128]``, with no
    slice or copy of a window before it."""
    from ray_tpu.ops.paged_attention import (
        pack_rows, packed_row, paged_decode_attention, unpack_rows)
    rng = np.random.default_rng(11)
    B, H, Hkv, D, bs, maxb = 3, 32, 8, 64, 8, 8
    NB = B * maxb + 2                       # + a scratch run
    assert packed_row(Hkv, D) == (4, 128)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    heads = [jnp.asarray(rng.normal(size=(3 * NB, bs, Hkv, D)), jnp.float32)
             for _ in range(2)]
    packed = [pack_rows(p) for p in heads]
    assert packed[0].shape == (3 * NB, bs, 4, 128)
    np.testing.assert_array_equal(unpack_rows(packed[0], D), heads[0])
    np.testing.assert_array_equal(packed[0][5, 3, 1, 64:], heads[0][5, 3, 3])
    runs = rng.permutation(B * maxb // 2).reshape(B, maxb // 2)
    tables = jnp.asarray((2 * runs[:, :, None] + np.arange(2)).reshape(
        B, maxb), jnp.int32)
    lengths = jnp.asarray([1, 29, 64], jnp.int32)
    window = dict(first_block=jnp.int32(NB), num_blocks=NB, run=run)
    want = paged_decode_attention(q, *heads, tables, lengths, impl="xla",
                                  **window)
    assert want.shape == (B, H, D)
    for impl in ("xla", "pallas"):
        got = paged_decode_attention(q, *packed, tables, lengths, impl=impl,
                                     **window)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match=r"rows of \(4, 128\)"):
        paged_decode_attention(q, *heads, tables, lengths, impl="pallas",
                               **window)
    other = paged_decode_attention(q, *packed, tables, lengths, impl="pallas",
                                   first_block=0, num_blocks=NB, run=run)
    assert float(jnp.max(jnp.abs(other - want))) > 0.1

    jaxpr = jax.make_jaxpr(functools.partial(
        paged_decode_attention, impl="pallas", num_blocks=NB, run=run))(
            q, *packed, tables, lengths, first_block=jnp.int32(NB))
    found = _primitives(jaxpr.jaxpr, {})
    assert "dynamic_slice" not in found and "copy" not in found
    inner = jaxpr.jaxpr.eqns[-1].params["jaxpr"]
    call, = [e for e in _flat(inner) if e.primitive.name == "pallas_call"]
    pool_ops = [v.aval.shape for v in call.invars if v.aval.ndim == 3
                and v.aval.shape[-1] == 128 and v.aval.shape[0] > B]
    assert pool_ops == [(3 * NB // run, run * bs * 4, 128)] * 2


def _flat(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _flat(inner)


def test_a_model_that_packs_one_keeps_its_pool_and_its_kernel_call():
    """Heads of 128 lanes (every serve cell of the benchmark before LFM2):
    the rows are heads, the pool ``[L, NB, bs, Hkv, D]``, and the decode
    program hands the kernel that stack viewed ``[L*NB, bs*Hkv, D]`` under
    the table it was given: what they were. Heads of 64 (llama3_1b's)
    pack two to a row in the same program; heads that fill no row (the
    debug widths' 16 x 2) and a model under a mesh stay heads."""
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    def decode_operands(**widths):
        cfg = LlamaConfig(vocab_size=64, dim=256, n_layers=2, ffn_dim=64,
                          max_seq_len=64, remat=False,
                          decode_attention="pallas", **widths)
        model = LlamaModel(cfg)
        pool = jax.eval_shape(lambda: model.init_kv_pool(9, 8))
        params = jax.eval_shape(model.init, jax.random.key(0))
        S = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(model.decode_step_paged)(
            params, S((2,), jnp.int32), pool, S((2, 4), jnp.int32),
            S((2,), jnp.int32))
        call, = [e for e in _flat(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        return model, pool, [v.aval.shape for v in call.invars]

    model, pool, operands = decode_operands(n_heads=2, n_kv_heads=2,
                                            head_dim=128)
    assert model.kv_lane_pack == 1
    assert model.kv_row_shapes() == ((2, 128), (2, 128))
    assert pool["k"].shape == (2, 9, 8, 2, 128)
    # lengths, tables, q, bias, K pages, V pages
    assert operands == [(2,), (2, 4), (2, 2, 128), (2, 64),
                        (18, 16, 128), (18, 16, 128)]

    model, pool, operands = decode_operands(n_heads=4, n_kv_heads=2,
                                            head_dim=64)
    assert model.kv_lane_pack == 2
    assert model.kv_row_shapes() == ((1, 128), (1, 128))
    assert pool["k"].shape == (2, 9, 8, 1, 128)
    assert operands[-2:] == [(18, 8, 128), (18, 8, 128)]

    assert LlamaModel(LlamaConfig.debug()).kv_lane_pack == 1
    assert LlamaModel(LlamaConfig.debug()).kv_row_shapes() == (
        (2, 16), (2, 16))


def _pool_in_runs(run, lengths, *, bs=8, Hkv=1, D=128, maxb=32, windows=1,
                  dtype=jnp.float32, seed=3):
    """Kernel inputs as an engine of ``BlockPool(run=...)`` lays them:
    every slot's table made of aligned runs of ``run`` blocks in a
    random order, entries past the slot's last run the scratch block
    (the first of a scratch run), ``windows`` windows of the pools one
    after another with other values in the others."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    NB = B * maxb + run                       # a window: whole runs
    order = rng.permutation(B * maxb // run).reshape(B, maxb // run)
    tables = (order[:, :, None] * run + np.arange(run)).reshape(B, maxb)
    for b, n in enumerate(lengths):
        tables[b, -(-n // (bs * run)) * run:] = B * maxb
    q = jnp.asarray(rng.normal(size=(B, 4 * Hkv, D)), dtype)
    kp, vp = (pack_rows(jnp.asarray(
        rng.normal(size=(windows * NB, bs, Hkv, D)), dtype))
        for _ in range(2))
    return (q, kp, vp, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32)), NB


@pytest.mark.parametrize("case,run", [
    ("one_head", 2), ("one_head", 4), ("one_head", 8), ("stats", 2),
    ("stats", 8), ("gqa_packed_rows", 4), ("window", 8), ("sliding", 2)])
def test_paged_kernel_over_runs_is_the_kernel_over_blocks(case, run,
                                                          monkeypatch):
    """``run`` > 1 reads the same rows in the same chunks: equal to the
    reference, and BIT-equal to ``run`` 1 on the same pool, at ragged
    lengths (0, 1, one that ends mid-run, a chunk's last row, the whole
    table)."""
    from ray_tpu.ops import paged_attention
    from ray_tpu.ops.paged_attention import paged_decode_attention
    monkeypatch.setattr(paged_attention, "CHUNK_ROWS", 128)   # 2 chunks
    lengths = [0, 1, 37, 128, 256]
    shape = {"gqa_packed_rows": dict(Hkv=2, D=64, bs=4, maxb=64)}.get(
        case, {})
    windows = 3 if case == "window" else 1
    (q, kp, vp, tables, lens), NB = _pool_in_runs(run, lengths,
                                                  windows=windows, **shape)
    kw = {}
    if case == "window":
        kw = dict(first_block=jnp.int32(NB), num_blocks=NB)
    if case == "stats":
        kw = dict(stats=True)
    if case == "sliding":       # a FULL layer beside sliding ones: starts 0
        kw = dict(starts=jnp.zeros_like(lens))
    call = jax.jit(functools.partial(paged_decode_attention, **kw),
                   static_argnames=("impl", "run", "num_blocks", "stats"))
    ref = call(q, kp, vp, tables, lens, impl="xla")
    one = call(q, kp, vp, tables, lens, impl="pallas")
    got = call(q, kp, vp, tables, lens, impl="pallas", run=run)
    by_runs = call(q, kp, vp, tables, lens, impl="xla", run=run)
    live = np.asarray(lens) > 0
    for o, o1, r, rr in zip(*(x if case == "stats" else (x,)
                              for x in (got, one, ref, by_runs))):
        assert np.array_equal(np.asarray(o), np.asarray(o1))
        np.testing.assert_allclose(np.asarray(o)[live], np.asarray(r)[live],
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(rr)[live],
                                   np.asarray(r)[live], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[0] if case == "stats" else got)[~live].any()


def test_runs_need_tables_and_windows_of_whole_runs():
    from ray_tpu.ops.paged_attention import paged_decode_attention
    (q, kp, vp, tables, lens), NB = _pool_in_runs(4, [5, 70])
    for bad in (dict(block_tables=tables[:, :30]), dict(k_pool=kp[:-1]),
                dict(num_blocks=NB - 2)):
        args = {**dict(q=q, k_pool=kp, v_pool=vp, block_tables=tables,
                       lengths=lens), **bad}
        with pytest.raises(ValueError, match="multiples"):
            paged_decode_attention(**args, impl="pallas", run=4)


def _primitives(jaxpr, into):
    """Count every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] = into.get(eqn.primitive.name, 0) + 1
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _primitives(inner, into)
    return into


def test_kernel_at_run_1_is_the_parents_and_at_8_starts_a_copy_a_run():
    """The claimed cell's call (20 q heads on ONE K/V head of 128, blocks
    of 32 rows, the second layer's window of the stack). ``run`` 1, said
    or not, traces what ``paged_decode_attention_pallas`` traces when
    called as the parent commit called it, equation for equation: 2,201
    of them, a chunk's 64 pages x (K, V) started in three places (the
    call's first chunk, the slot's next, the next slot's first) and
    waited for in one, as counted on the parent (59f2076). ``run`` 8:
    the same program with 8 pages of 256 rows a chunk."""
    import re
    from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                             paged_decode_attention_pallas)
    S = jax.ShapeDtypeStruct
    pool = S((2 * 264, 32, 1, 128), jnp.bfloat16)
    args = (S((4, 20, 128), jnp.bfloat16), pool, pool,
            S((4, 96), jnp.int32), S((4,), jnp.int32))
    window = dict(first_block=jnp.int32(264), num_blocks=264)

    def program(fn):
        jaxpr = jax.make_jaxpr(fn)(*args)
        return (re.sub(r"0x[0-9a-f]+", "", str(jaxpr)),
                _primitives(jaxpr.jaxpr, {}))

    parents, counts = program(lambda *a: paged_decode_attention_pallas(
        *a, None, scale=None, interpret=True, stats=False,
        first_block=window["first_block"]))
    assert sum(counts.values()) == 2201
    assert (counts["dma_start"], counts["dma_wait"]) == (3 * 64 * 2, 64 * 2)
    for said in ({}, {"run": 1}):
        text, _ = program(lambda *a: paged_decode_attention(
            *a, impl="pallas", **window, **said))
        assert text == parents
    _, counts = program(lambda *a: paged_decode_attention(
        *a, impl="pallas", run=8, **window))
    assert (counts["dma_start"], counts["dma_wait"]) == (3 * 8 * 2, 8 * 2)
    assert counts["dot_general"] == 2


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
    from ray_tpu.ops.paged_attention import (CHUNK_ROWS, packed_row,
                                             paged_decode_attention_pallas)

    def chunk_rows(bs, Hkv, D, maxb):
        pool = jax.ShapeDtypeStruct((9, bs, *packed_row(Hkv, D)),
                                    jnp.float32)
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
        # the counters are taken at the DISPATCH, and the step after this
        # one stands on the device already, a token further a slot
        assert eng._in_flight is not None
        assert eng.stats["decode_kv_blocks_live"] == live + sum(
            -(-(o + 1) // 8) for o in offs)
        assert eng.stats["decode_kv_blocks_table"] == (
            table + len(offs) * eng.blocks_per_slot)
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
               ffn_dim=256, max_seq_len=128, remat=False),
           # heads of 64 lanes: two to a row of the pool, which lies
           # [L, NB, bs, 1, 128] (llama3_1b's and LFM2's layout)
           "dense_d64": LlamaConfig(
               vocab_size=512, dim=256, n_heads=4, n_kv_heads=2,
               ffn_dim=256, max_seq_len=128, remat=False)}[kind]
    model = model_for(dataclasses.replace(
        cfg, n_layers=layers, dtype=dtype, decode_attention=impl))
    return model, jax.jit(model.init)(jax.random.key(3))


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
@pytest.mark.parametrize("kind", ["dense", "olmoe", "dense_d128",
                                  "dense_d64"])
def test_paged_decode_keeps_each_layers_blocks_apart(kind, impl):
    """Six steps (across a block boundary) of ``decode_step_paged``
    against ``forward_step``'s dense cache on the same tokens, in
    float32: with layer ``l`` addressed as pages ``l*NB + p`` of one
    stack, an index that is off lands in another layer's live blocks,
    not out of range, and only the values can tell."""
    from ray_tpu.ops.paged_attention import _lane_pack
    model, params = _deep_model(kind, impl)
    assert model.cfg.n_layers == 3 and model.paged_decode_impl() == impl
    # (the debug widths' heads of 16 fill no row: they pack 1 too)
    assert (_lane_pack(model.cfg.head_dim, model.cfg.n_kv_heads) == 2) == (
        kind == "dense_d64") == (model.kv_lane_pack == 2)
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
