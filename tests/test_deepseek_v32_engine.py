"""DeepSeek-V3.2's block through ``ContinuousBatchingEngine`` at debug
widths (float32 compute): admission, chunked prefill, a prefix hit that
brings index keys with it, preemption and resume, full slots decoding,
against ``benchmark/reference/deepseek_v32.py``; and the engine's
counters of the share and of the selection. (The model's own comparisons
are ``tests/test_deepseek_v32_serving.py``'s, whose helpers these use.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
from tests.test_deepseek_v32_serving import I32, make, ref_forward


def _prompt(cfg, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n)]


ENGINE_CASES = {
    # name: (prompt lengths, engine kwargs, the stats key that must move)
    "bucket_prefill": ((12, 14), {}, "prefills"),
    "chunked_prefill": ((40, 9), {}, "prefills"),
    "prefix_prefill": ("shared", {}, "prefix_prefills"),
    "preemption_by_recompute": ((20, 21, 22), {"num_blocks": 10},
                                "preemptions"),
}


@pytest.mark.parametrize("case", sorted(ENGINE_CASES))
def test_engine_greedy_tokens_are_the_references_argmax(case):
    """Through ``ContinuousBatchingEngine`` in float32 compute: every
    generated token is the reference's first choice given the prompt and
    the tokens before it (teacher forced) unless the reference has its
    first two within 1e-3; the counters of the share and of the
    selection add up."""
    cfg, model, params = make()
    lens, kwargs, moved = ENGINE_CASES[case]
    if lens == "shared":
        # 16 shared rows: the second request's chunk scores index keys
        # that the first request wrote
        head = _prompt(cfg, 16, 50)
        prompts = [head + _prompt(cfg, n, i) for i, n in enumerate((3, 7))]
    else:
        prompts = [_prompt(cfg, n, i) for i, n in enumerate(lens)]
    eng = ContinuousBatchingEngine(
        model, params, max_slots=4, max_seq=64, prefill_buckets=(8, 16, 32),
        block_size=8, **kwargs)
    n_out = 12 if case == "preemption_by_recompute" else 6
    with jax.default_matmul_precision("highest"):
        if lens == "shared":        # the second finds the first's blocks
            reqs = [eng.generate([p], SamplingParams(max_tokens=n_out))[0]
                    for p in prompts]
        else:
            reqs = eng.generate(prompts, SamplingParams(max_tokens=n_out))
    # ONE reference forward for the case: a row's logits depend on
    # nothing behind it, so the sequences go in padded to one length
    seqs = [prompt + req.output for prompt, req in zip(prompts, reqs)]
    width = max(map(len, seqs))
    logits = np.asarray(ref_forward(cfg, params, jnp.asarray(
        [seq + [0] * (width - len(seq)) for seq in seqs], I32)))
    for prompt, req, rows in zip(prompts, reqs, logits):
        assert len(req.output) == n_out
        want = rows[len(prompt) - 1:len(prompt) - 1 + n_out]
        for row, tok in zip(want, req.output):
            assert row.max() - row[tok] < 1e-3
    stats = eng.stats
    assert stats[moved] > 0
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
