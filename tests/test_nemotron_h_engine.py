"""The hybrid state-space model through ``ContinuousBatchingEngine`` at
debug widths (float32 compute): continuous batching over a fixed-size
state a slot. Requests of different lengths admitted while others
decode, a slot released and taken by a new request, chunked prefill of a
prompt of several chunks with a padded last one, preemption by
recompute, a shared prefix whose hit is refused, the guards and the
counters. (The model's own comparisons are
``tests/test_nemotron_h_serving.py``'s; the cases and the description both
share are ``tests/serving_family.py``'s.)"""

import dataclasses

from tests import serving_family as serving


def state_stats(eng, stats, impl):
    # conv (f32 here) + S, 3 layers
    assert stats["state_row_bytes"] == 3 * (3 * 128 * 4 + 2 * 16 * 32 * 4)
    assert eng.decode_attention_impl == stats["decode_attention_impl"] \
        == "xla+ssm_xla"
    assert stats["moe_router_kind"] == "sigmoid"
    assert stats["moe_experts_held"] == 4
    assert stats["moe_assignments"] == stats["moe_assignments_expected"] > 0
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]


FAMILY = dataclasses.replace(
    serving.NEMOTRON_H, state_stats=state_stats,
    # its cell's mechanism at debug widths: two K/V heads, a block of 8
    # rows, runs of 4 (what a table of 7 blocks holds), the kernel
    # interpreted over pages of 32 rows. Four requests through three slots
    # of a pool of three runs, small enough to preempt
    runs=dict(lens=(26, 13, 9, 20), outs=(20, 12, 22, 8), max_seq=56,
              num_blocks=12, run=4))

globals().update(serving.cases_of(FAMILY))
