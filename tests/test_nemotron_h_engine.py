"""The hybrid state-space model through ``ContinuousBatchingEngine`` at
debug widths (float32 compute): continuous batching over a fixed-size
state a slot. Requests of different lengths admitted while others
decode, a slot released and taken by a new request, chunked prefill of a
prompt of several chunks with a padded last one, preemption by
recompute, a shared prefix whose hit is refused, the guards and the
counters. (The model's own comparisons are
``tests/test_nemotron_h_serving.py``'s, whose helpers these use.)"""

import jax
import numpy as np
import pytest

from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
from tests.test_jamba_engine import runs_decode_what_single_blocks_decode
from tests.test_nemotron_h_serving import make

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


def test_continuous_batching_over_the_state(built):
    """(d) five requests of different lengths through three slots: the
    later ones are admitted while others decode, into slots that others
    have left (whose state rows they must not see); one prompt is 2.6
    chunks long (chunked prefill, a padded last chunk, the state carried
    from chunk to chunk), one a bucket with padding behind it. Streamed
    greedy tokens equal a fresh engine's, one request at a time."""
    cfg, model, params = built
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
    row = 3 * (3 * 128 * 4 + 2 * 16 * 32 * 4)  # conv (f32 here) + S, 3 layers
    assert stats["state_row_bytes"] == row
    assert stats["state_bytes"] == 3 * row == sum(
        eng.kv[n].nbytes for n in ("conv", "ssm"))
    assert stats["kv_pool_bytes"] == eng.kv["k"].nbytes + eng.kv["v"].nbytes
    assert eng.decode_attention_impl == stats["decode_attention_impl"] \
        == "xla+ssm_xla"
    assert stats["moe_router_kind"] == "sigmoid"
    assert stats["moe_experts_held"] == 4
    assert stats["moe_assignments"] == stats["moe_assignments_expected"] > 0
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]
    assert stats["prefix_hits_refused_recurrent"] == 0


def test_preemption_by_recompute_rebuilds_the_state(built):
    """(e) a pool too small for three growing requests: the youngest is
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


def test_blocks_in_runs_decode_what_single_blocks_decode(built, monkeypatch):
    """Its cell's mechanism at debug widths: two K/V heads, a block of 8
    rows, runs of 4 (what a table of 7 blocks holds), the kernel
    interpreted over pages of 32 rows. Four requests through three slots
    of a pool of three runs, small enough to preempt
    (``tests/test_jamba_engine.py`` has the body)."""
    runs_decode_what_single_blocks_decode(
        built, monkeypatch, engine, alone, lens=(26, 13, 9, 20),
        outs=(20, 12, 22, 8), max_seq=56, num_blocks=12, run=4)


def test_a_prefix_hit_is_refused_and_counted(built):
    """(f) two requests with a shared prefix of two blocks, one after
    the other: the second finds the first's pages in the index and does
    NOT take them (they come without the state at their end); both give
    what they give with an empty cache."""
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
    """(h) ``prefill_only`` / ``submit_prefilled`` carry K/V rows only."""
    cfg, model, params = built
    eng = engine(model, params)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.prefill_only([1, 2, 3])
    with pytest.raises(NotImplementedError, match="recurrent state"):
        eng.submit_prefilled([1, 2, 3], {}, None)
