"""The hybrid Mamba-1 model through ``ContinuousBatchingEngine`` at debug
widths (float32 compute): continuous batching over a fixed-size state a
slot, chunked prefill with the state carried, preemption by recompute, a
slot reused with no stale state, a prefix hit refused; and one K/V head
under 20 query heads through both decode attention implementations. (The
model's own comparisons are ``tests/test_jamba_serving.py``'s; the cases
and the description both share are ``tests/serving_family.py``'s.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import SamplingParams
from ray_tpu.ops.paged_attention import paged_decode_attention
from tests import serving_family as serving
from tests.serving_family import alone, engine_of, generate, prompt_of


def state_stats(eng, stats, impl):
    # conv [3, 64] (f32 here) + S [6, 64] float32, 3 Mamba layers
    assert stats["state_row_bytes"] == 3 * (3 * 64 * 4 + 6 * 64 * 4)
    assert eng.decode_attention_impl == stats["decode_attention_impl"] \
        == f"{impl}+ssm_{impl}"


FAMILY = dataclasses.replace(
    serving.JAMBA, state_impls=("xla", "pallas"), state_stats=state_stats,
    # the claimed cell's mechanism at debug widths: ONE K/V head, a block
    # of 8 rows, runs of 8 (what a table of 12 blocks holds), the kernel
    # interpreted over pages of 64 rows. Four requests through three slots
    # of a pool of TWO runs
    runs=dict(lens=(42, 13, 9, 30), outs=(30, 12, 7, 8), max_seq=96,
              num_blocks=16, run=8))

globals().update(serving.cases_of(FAMILY))


def test_a_reused_slot_sees_no_stale_state():
    """One slot, three requests one after another: each takes the slot
    the last left, whose rows hold that tenant's state until activation
    overwrites them."""
    cfg, model, params = serving.make(FAMILY)
    prompts = [prompt_of(cfg, n, 30 + i) for i, n in enumerate((11, 4, 23))]
    eng = engine_of(FAMILY, model, params, max_slots=1)
    reqs = generate(eng, prompts, SamplingParams(max_tokens=6))
    for p, req in zip(prompts, reqs):
        assert req.output == alone(FAMILY, model, params, p, 6, max_slots=1)
    assert eng.stats["state_rows_written"] == 3


def test_a_dropped_step_ahead_leaves_no_trace_in_the_state():
    """Two slots, lengths that end one after the other, and a request
    that arrives as the first ends: it takes that slot while the step
    dispatched for the ended request is still unread. That step advanced
    the slot's state rows for nobody; the arrival's activation, enqueued
    behind it, sets them anew (PR 60: the step ahead is speculative a
    row). Tokens equal a fresh engine's, one request at a time."""
    cfg, model, params = serving.make(FAMILY)
    prompts = [prompt_of(cfg, n, 60 + i) for i, n in enumerate((11, 18, 7))]
    outs = (5, 16, 8)
    eng = engine_of(FAMILY, model, params, max_slots=2)
    reqs, ended_ahead, admitted_ahead = serving.drive_arrivals(eng, [
        (due, p, SamplingParams(max_tokens=n)) for due, p, n in zip(
            (None, None, serving.ended(0)), prompts, outs)])
    for p, n, req in zip(prompts, outs, reqs):
        assert req.output == alone(FAMILY, model, params, p, n, max_slots=2)
    stats = eng.stats
    # the first and the third end beside the second; the second ends alone
    assert stats["decode_rows_dropped"] == ended_ahead == 2
    assert admitted_ahead == 1 and stats["state_rows_written"] == 3
    assert stats["decode_steps_ahead"] > 0.7 * stats["decode_steps"]
    assert eng.pool.num_free == eng.num_blocks and eng._in_flight is None


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
