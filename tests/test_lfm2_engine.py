"""The hybrid short-convolution / attention expert model through
``ContinuousBatchingEngine`` at debug widths (float32 compute): continuous
batching over a state of two rows a slot a conv layer, chunked prefill
with the state carried, preemption by recompute, a slot reused with no
stale state, a prefix hit refused, blocks in runs, and the expert counters
over the expert layers alone. (The model's own comparisons are
``tests/test_lfm2_serving.py``'s; the cases and the description both share
are ``tests/serving_family.py``'s.)"""

import dataclasses

from ray_tpu.llm.engine import SamplingParams
from tests import serving_family as serving
from tests.serving_family import alone, engine_of, generate, prompt_of


def state_stats(eng, stats, impl):
    # four conv layers x two rows of 64 numbers (float32 here)
    assert stats["state_row_bytes"] == 4 * 2 * 64 * 4
    assert eng.decode_attention_impl == stats["decode_attention_impl"] \
        == f"{impl}+shortconv_xla"
    # heads of 16 lanes fill no row: the pool stays heads
    assert stats["kv_lane_pack"] == 1
    assert eng.kv["k"].shape[3:] == (2, 16)
    # every decode step's live rows, two experts each, FOUR expert layers
    # (the two leading layers are dense and count nothing)
    assert eng.model.ffn_load_shape() == (4, 8)
    assert eng._ffn_rows_per_slot == 2 * 4
    assert stats["moe_assignments"] == stats["moe_assignments_expected"] > 0
    assert stats["moe_assignments"] % (2 * 4) == 0
    assert stats["moe_router_kind"] == "sigmoid"


FAMILY = dataclasses.replace(
    serving.LFM2, state_impls=("xla", "pallas"), state_stats=state_stats,
    # blocks in runs at debug widths: a block of 8 rows, runs of 8 (what a
    # table of 12 blocks holds), the kernel interpreted. Four requests
    # through three slots of a pool of TWO runs
    runs=dict(lens=(42, 13, 9, 30), outs=(30, 12, 7, 8), max_seq=96,
              num_blocks=16, run=8))

globals().update(serving.cases_of(FAMILY))


def test_a_reused_slot_sees_no_stale_state():
    """One slot, three requests one after another: each takes the slot the
    last left, whose rows hold that tenant's state until activation
    overwrites them."""
    cfg, model, params = serving.make(FAMILY)
    prompts = [prompt_of(cfg, n, 30 + i) for i, n in enumerate((11, 1, 23))]
    eng = engine_of(FAMILY, model, params, max_slots=1)
    reqs = generate(eng, prompts, SamplingParams(max_tokens=6))
    for p, req in zip(prompts, reqs):
        assert req.output == alone(FAMILY, model, params, p, 6, max_slots=1)
    assert eng.stats["state_rows_written"] == 3


def test_a_dropped_step_ahead_leaves_no_trace_in_the_state():
    """Two slots, lengths that end one after the other, and a request that
    arrives as the first ends: it takes that slot while the step
    dispatched for the ended request is still unread. That step shifted
    the slot's rows for nobody; the arrival's activation, enqueued behind
    it, sets them anew. Tokens equal a fresh engine's, one request at a
    time."""
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
    assert stats["decode_rows_dropped"] == ended_ahead == 2
    assert admitted_ahead == 1 and stats["state_rows_written"] == 3
    assert eng.pool.num_free == eng.num_blocks and eng._in_flight is None
