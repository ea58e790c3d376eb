"""DeepSeek-V3.2's block through ``ContinuousBatchingEngine`` at debug
widths (float32 compute): admission, chunked prefill, a prefix hit that
brings index keys with it, preemption and resume, full slots decoding,
against ``benchmark/reference/deepseek_v32.py``; and the engine's
counters of the share and of the selection. (The model's own comparisons
are ``tests/test_deepseek_v32_serving.py``'s; the description both share
is ``tests/serving_family.py``'s ``DEEPSEEK_V32``.)"""

import dataclasses

import numpy as np

from tests import serving_family as serving


def engine_stats(eng, stats, cfg, model):
    """The counters of the share and of the selection add up."""
    assert eng.decode_attention_impl == stats["decode_attention_impl"] \
        == "dsa_xla"
    assert stats["decode_indexer_impl"] == "dsa_indexer_xla"
    assert stats["decode_select_impl"] == "xla_top_k"
    assert stats["index_topk"] == 12 and stats["kv_index_row_bytes"] == 64
    assert stats["kv_row_bytes"] == 4 * (32 + 16 + 16)
    assert stats["kv_pool_bytes"] == sum(a.nbytes for a in eng.kv.values())
    assert stats["moe_experts_held"] == 8 and stats["moe_router_groups"] == 4
    assert stats["moe_assignments"] == stats["moe_assignments_expected"] > 0
    load = np.asarray(stats["moe_expert_load"])
    assert load.shape == (2, 16) and load.sum() == stats["moe_assignments"]
    assert stats["moe_assignments_held"] == load[:, 4:12].sum()
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]
    # a step's slots each read min(length, 12) rows of the blocks they hold
    assert 0 < stats["decode_kv_rows_selected"] <= 12 * (
        stats["tokens_generated"])
    assert stats["decode_kv_rows_selected"] <= 8 * stats[
        "decode_kv_blocks_live"]


FAMILY = dataclasses.replace(
    serving.DEEPSEEK_V32,
    # 16 shared rows: the prefix case's second request's chunk scores
    # index keys that the first request wrote
    engine_cases=serving.engine_cases(bucket_prefill=(
        (12, 14), 6, "prefills", serving.TEN_BLOCKS, {})),
    engine_stats=engine_stats)

globals().update(serving.cases_of(FAMILY))
