"""The hybrid Mamba-1 model through ``ContinuousBatchingEngine`` at debug
widths (float32 compute): continuous batching over a fixed-size state a
slot, chunked prefill with the state carried, preemption by recompute, a
slot reused with no stale state, a prefix hit refused; and one K/V head
under 20 query heads through both decode attention implementations. (The
model's own comparisons are ``tests/test_jamba_serving.py``'s, whose
helpers these use.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
from ray_tpu.models import model_for
from ray_tpu.ops.paged_attention import paged_decode_attention
from tests.test_jamba_serving import make

KW = dict(max_slots=3, max_seq=96, prefill_buckets=(8, 16), block_size=8)


def _prompt(cfg, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n)]


def engine(model, params, **kw):
    return ContinuousBatchingEngine(model, params, **{**KW, **kw})


def alone(model, params, prompt, n_out, **kw):
    """A fresh engine's one request: what every test compares with."""
    with jax.default_matmul_precision("highest"):
        return engine(model, params, **kw).generate(
            [prompt], SamplingParams(max_tokens=n_out))[0].output


@pytest.fixture(scope="module")
def built():
    return make()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_continuous_batching_over_the_state(built, impl):
    """Five requests of different lengths through three slots: the later
    ones are admitted while others decode, into slots that others have
    left (whose state rows they must not see); one prompt is 2.6 chunks
    long (chunked prefill, a padded last chunk, the state carried from
    chunk to chunk), one a bucket with padding behind it. Streamed greedy
    tokens equal a fresh engine's, one request at a time; the kernels
    (interpreted) and their twins."""
    cfg, _, params = built
    model = model_for(dataclasses.replace(cfg, decode_attention=impl))
    lens = (5, 42, 13, 16, 9)
    outs = (9, 4, 12, 5, 7)
    prompts = [_prompt(cfg, n, i) for i, n in enumerate(lens)]
    eng = engine(model, params)
    with jax.default_matmul_precision("highest"):
        reqs = [eng.submit(p, SamplingParams(max_tokens=n))
                for p, n in zip(prompts, outs)]
        while eng.has_work():
            eng.step()
    for p, n, req in zip(prompts, outs, reqs):
        assert req.output == alone(model, params, p, n), len(p)
    stats = eng.stats
    assert stats["state_rows_written"] == 5 and stats["state_layers"] == 3
    # the 42-token prompt: chunks of 16, 16 and 10; two started from a state
    assert stats["state_chunks_carried"] == 2
    # conv [3, 64] (f32 here) + S [6, 64] float32, 3 Mamba layers
    row = 3 * (3 * 64 * 4 + 6 * 64 * 4)
    assert stats["state_row_bytes"] == row
    assert stats["state_bytes"] == 3 * row == sum(
        eng.kv[n].nbytes for n in ("conv", "ssm"))
    assert stats["kv_pool_bytes"] == eng.kv["k"].nbytes + eng.kv["v"].nbytes
    assert eng.decode_attention_impl == stats["decode_attention_impl"] \
        == f"{impl}+ssm_{impl}"
    assert stats["prefix_hits_refused_recurrent"] == 0


def test_preemption_by_recompute_rebuilds_the_state(built):
    """A pool too small for three growing requests: the youngest is
    preempted, its row dropped, and the re-prefill of prompt + output
    rebuilds it: the tokens are an unpreempted run's."""
    cfg, model, params = built
    prompts = [_prompt(cfg, n, 10 + i) for i, n in enumerate((20, 21, 22))]
    eng = engine(model, params, num_blocks=10)
    with jax.default_matmul_precision("highest"):
        reqs = eng.generate(prompts, SamplingParams(max_tokens=12))
    assert eng.stats["preemptions"] > 0
    for p, req in zip(prompts, reqs):
        assert req.output == alone(model, params, p, 12)


def test_a_reused_slot_sees_no_stale_state(built):
    """One slot, three requests one after another: each takes the slot
    the last left, whose rows hold that tenant's state until activation
    overwrites them."""
    cfg, model, params = built
    prompts = [_prompt(cfg, n, 30 + i) for i, n in enumerate((11, 4, 23))]
    eng = engine(model, params, max_slots=1)
    with jax.default_matmul_precision("highest"):
        reqs = eng.generate(prompts, SamplingParams(max_tokens=6))
    for p, req in zip(prompts, reqs):
        assert req.output == alone(model, params, p, 6, max_slots=1)
    assert eng.stats["state_rows_written"] == 3


def _tables_lie_in_runs(eng):
    """Every live slot's table is made of aligned, contiguous runs, and
    no block is in two slots' (a recurrent model shares none)."""
    run, seen = eng.kv_run, set()
    for slot, alloc in enumerate(eng.allocs):
        if alloc is None:
            continue
        blocks = list(eng._tables[slot, :len(alloc.blocks)])
        assert blocks == alloc.blocks and len(blocks) % run == 0
        for r in range(0, len(blocks), run):
            assert blocks[r] % run == 0
            assert blocks[r:r + run] == list(range(blocks[r],
                                                   blocks[r] + run))
        assert not seen & set(blocks)
        seen |= set(blocks)
    assert len(seen) + eng.pool.num_free == eng.num_blocks // run * run


def runs_decode_what_single_blocks_decode(built, monkeypatch, engine, alone,
                                          lens, outs, max_seq, num_blocks,
                                          run):
    """Requests of ``lens`` prompt and ``outs`` output tokens through an
    engine whose kernel (forced, interpreted) copies runs of ``run``
    blocks, in a pool small enough to preempt: every table lies in runs
    at every step, and the greedy tokens equal those of an engine whose
    allocator is told ``run`` 1 (single blocks, the parent's layout:
    ``paged_run_blocks`` patched, a test's argument and not a user's)
    and a fresh engine's, a request at a time. ``engine`` / ``alone``:
    the calling module's."""
    cfg, _, params = built
    model = model_for(dataclasses.replace(cfg, decode_attention="pallas"))
    prompts = [_prompt(cfg, n, 20 + i) for i, n in enumerate(lens)]

    def drive(eng):
        with jax.default_matmul_precision("highest"):
            reqs = [eng.submit(p, SamplingParams(max_tokens=n))
                    for p, n in zip(prompts, outs)]
            while eng.has_work():
                eng.step()
                _tables_lie_in_runs(eng)
                ahead = eng.stats["kv_blocks_reserved_unfilled"]
                assert ahead <= eng.kv_run * sum(
                    a is not None for a in eng.allocs)
        return [r.output for r in reqs]

    runs = engine(model, params, max_seq=max_seq, num_blocks=num_blocks)
    assert runs.kv_run == runs.pool.run == runs.stats["kv_run_blocks"] == run
    assert runs.kv["k"].shape[1] == num_blocks + run       # a scratch RUN
    got = drive(runs)
    assert runs.stats["preemptions"] > 0
    assert runs.pool.num_free == num_blocks
    assert runs.stats["kv_blocks_reserved_unfilled"] == 0

    monkeypatch.setattr(type(model), "paged_run_blocks",
                        lambda self, block_size: 1)
    single = engine(model, params, max_seq=max_seq, num_blocks=num_blocks)
    assert single.kv_run == single.pool.run == 1
    assert single.stats["kv_run_blocks"] == 1
    assert single.kv["k"].shape[1] == num_blocks + 1
    assert drive(single) == got
    for p, n, out in zip(prompts, outs, got):
        assert out == alone(model, params, p, n)


def test_blocks_in_runs_decode_what_single_blocks_decode(built, monkeypatch):
    """The claimed cell's mechanism at debug widths: ONE K/V head, a
    block of 8 rows, runs of 8 (what a table of 12 blocks holds), the
    kernel interpreted over pages of 64 rows. Four requests through
    three slots of a pool of TWO runs: a slot that grows into a second
    run finds none and the youngest is preempted, refilled later and
    re-prefilled."""
    runs_decode_what_single_blocks_decode(
        built, monkeypatch, engine, alone, lens=(42, 13, 9, 30),
        outs=(30, 12, 7, 8), max_seq=96, num_blocks=16, run=8)


def test_a_prefix_hit_is_refused_and_counted(built):
    """Two requests with a shared prefix of two blocks, one after the
    other: the second finds the first's pages in the index and does NOT
    take them (they come without the state at their end)."""
    cfg, model, params = built
    head = _prompt(cfg, 16, 50)
    prompts = [head + _prompt(cfg, n, 60 + i) for i, n in enumerate((3, 7))]
    eng = engine(model, params)
    with jax.default_matmul_precision("highest"):
        reqs = [eng.generate([p], SamplingParams(max_tokens=6))[0]
                for p in prompts]
    for p, req in zip(prompts, reqs):
        assert req.output == alone(model, params, p, 6)
    stats = eng.stats
    assert stats["prefix_hits_refused_recurrent"] == 1
    assert stats["prefix_prefills"] == stats["prefix_tokens_reused"] == 0


def test_the_handoff_is_refused(built):
    cfg, model, params = built
    eng = engine(model, params)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.prefill_only([1, 2, 3])


@pytest.mark.parametrize("block", [8, 32])
def test_one_kv_head_under_twenty_query_heads(block):
    """The published attention shape, 20 query heads over ONE K/V head of
    128 (a group that is no power of two and no multiple of 8): the paged
    kernel (interpreted) against its XLA twin, slots of unequal lengths,
    a layer's window of a stack of two."""
    B, H, D, NB, maxb = 3, 20, 128, 12, 4
    k = jax.random.split(jax.random.key(block), 3)
    q = jax.random.normal(k[0], (B, H, D), jnp.float32)
    k_pool = jax.random.normal(k[1], (2 * NB, block, 1, D), jnp.float32)
    v_pool = jax.random.normal(k[2], (2 * NB, block, 1, D), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(0).permutation(NB)[
        :B * maxb].reshape(B, maxb), jnp.int32)
    lengths = jnp.asarray([1, block + 3, maxb * block], jnp.int32)
    out = {impl: paged_decode_attention(
        q, k_pool, v_pool, tables, lengths, impl=impl, first_block=NB,
        num_blocks=NB) for impl in ("xla", "pallas")}
    np.testing.assert_allclose(out["pallas"], out["xla"], atol=2e-5,
                               rtol=2e-5)
