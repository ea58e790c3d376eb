"""Mellum2's block (window and full attention layers in one model, each
kind on its own rotary table, ``head_dim`` a width of its own, a 64-expert
top-k FFN with normalised weights) on the serving path, at debug widths
with seeded weights, against ``benchmark/reference/mellum.py``.

Debug widths keep what makes the model: the pattern S S S F, a window
(16) shorter than every sequence below, ``head_dim`` 32 where hidden /
heads is 16, YaRN on the full layers. float32 compute is compared to
1e-4 (nothing swaps in the router); bf16 compute with the reference
FORCED to the system's routing, as ``tests/test_olmoe_serving.py`` does.
The ENGINE, which holds a block pool and a table a kind, is compared
token for token with a dense oracle that keeps every token in every
layer and masks (``forward_step``): the two agree only if a sliding
layer's freed blocks were never needed again.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mellum as reference
from ray_tpu.llm import paged_cache
from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
from ray_tpu.models import LlamaConfig, MoEConfig, model_for
from ray_tpu.models.llama import FULL, LAYER_KINDS, SLIDING
from ray_tpu.ops import paged_attention, rope
from tests import serving_family as serving
from tests.serving_family import (I32, bucket_prefill,
                                  prefill_then_paged_decode, rel_rms)

F32_TOL = 1e-4          # max |logit difference|, logits of RMS ~1
BF16_REL_RMS = 0.02     # the dense block's bf16 floor at debug widths
WINDOW = 16
PATTERN = ("sliding_attention",) * 3 + ("full_attention",)
ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                       "factor": 16, "original_max_position_embeddings": 64,
                       "beta_fast": 32, "beta_slow": 1,
                       "attention_factor": 1.2772588722239782}}
YARN = rope.YarnScaling(factor=16.0, original_max_position=64,
                        attention_factor=1.2772588722239782)


def config(dtype=jnp.float32, **overrides):
    return MoEConfig(**{**dict(
        vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=32, ffn_dim=32, max_seq_len=256, remat=False,
        rope_theta=500_000.0, norm_eps=1e-6, num_experts=8, expert_top_k=2,
        norm_topk_prob=True, qk_norm=False, layer_types=PATTERN,
        sliding_window=WINDOW, rope_scaling=(("full_attention", YARN),),
        dtype=dtype), **overrides})


def plain_reference(cfg, params, tokens, **kw):
    kw = {**dict(layer_types=cfg.layer_types,
                 sliding_window=cfg.sliding_window, rope_parameters=ROPE,
                 norm_topk_prob=cfg.norm_topk_prob), **kw}
    return reference.forward(
        {"embed": params["embed"], "layers": params["layers"],
         "norm_f": params["norm_f"], "lm_head": params["lm_head"]},
        tokens, rms_norm_eps=cfg.norm_eps, top_k=cfg.expert_top_k, **kw)


# -- the model's programs, each returning logits for tokens[:, from:]: the
# paged decode goes through the UNIFORM pool (every layer holds every
# token, one table) across the window's edge (24 rows are prefilled: a
# sliding layer skips the rows behind its window); the suffix prefill runs
# over a cached prefix longer than the window
def prefill_24(model, params, toks):
    return prefill_then_paged_decode(model, params, toks, prompt=24)


FAMILY = serving.Family(
    config=config, reference=plain_reference, shape=(2, 56),
    seeded=serving.drawn(("attn_norm", "mlp_norm"), 2304),
    f32_tol=F32_TOL, bf16_rel_rms=BF16_REL_RMS,
    paths={"apply": (serving.full_forward, 0),
           "forward_step": (bucket_prefill, 0),
           "prefill_then_paged_decode": (prefill_24, 0),
           "paged_decode_with_the_kernel": (
               lambda model, params, toks:
               serving.paged_decode_with_the_kernel(model, params, toks,
                                                    prompt=24), 0),
           "prefix_prefill": (
               lambda model, params, toks:
               serving.prefix_prefill(model, params, toks, prefix=24), -1)})


make = functools.partial(serving.make, FAMILY)
ref_forward = functools.partial(serving.reference, FAMILY)


seqs = functools.partial(serving.seqs, shape=FAMILY.shape)


globals().update(serving.cases_of(FAMILY))


@pytest.mark.parametrize("control,kw", [
    ("every layer full", dict(sliding_window=10 ** 6)),
    ("half the window", dict(sliding_window=WINDOW // 2)),
    ("full layers on the default table", dict(rope_parameters={
        **ROPE, "full_attention": ROPE["sliding_attention"]})),
    ("yarn without its attention factor", dict(rope_parameters={
        **ROPE, "full_attention": {**ROPE["full_attention"],
                                   "attention_factor": 1.0}})),
    ("top-k weights as they are", dict(norm_topk_prob=False)),
])
def test_the_comparison_sees_each_mechanism(control, kw):
    """What the reference does with one mechanism taken away is far from
    the system, so the agreement above is agreement on each of them."""
    cfg, model, params = make()
    toks = seqs(cfg)
    with jax.default_matmul_precision("highest"):
        got = serving.full_forward(model, params, toks)
        want = plain_reference(cfg, params, toks, **kw)  # dicts: op by op
    assert rel_rms(got, want) > 0.01, control


@pytest.mark.parametrize("path", ["apply", "prefill_then_paged_decode"])
def test_bf16_compute_matches_the_reference_forced_to_its_routing(path):
    cfg, model, params = make(jnp.bfloat16)
    toks = seqs(cfg)
    _, experts = serving.bf16_full_forward(model, params, toks)
    got = FAMILY.paths[path][0](model, model.serving_params(params), toks)
    want = ref_forward(cfg, params, toks, forced_experts=experts)
    assert rel_rms(got, want) < BF16_REL_RMS


def test_a_dense_model_with_kinds_matches_its_oracle_and_a_plain_one_has_none():
    """The kinds are the Llama block's, not the expert model's: a dense
    model with the pattern decodes through the uniform pool as its own
    dense cache says; with no sliding layer and no scaling, a model has
    no kinds at all and carries none of it."""
    plain = model_for(LlamaConfig.debug())
    assert plain.layer_kinds is None and plain._windows is None
    assert plain._angles.ndim == 2
    assert model_for(dataclasses.replace(
        LlamaConfig.debug(), layer_types=("full_attention",) * 2)
    ).layer_kinds is None
    cfg = LlamaConfig(vocab_size=256, dim=64, n_layers=4, n_heads=4,
                      n_kv_heads=2, head_dim=32, ffn_dim=128, max_seq_len=128,
                      remat=False, dtype=jnp.float32, layer_types=PATTERN,
                      sliding_window=WINDOW)
    model = model_for(cfg)
    assert model.layer_kinds == (SLIDING, SLIDING, SLIDING, FULL)
    assert cfg.head_dim == 32 and LlamaConfig.debug().head_dim == 16
    params = model.init(jax.random.key(0))
    assert params["layers"]["wq"].shape == (4, 64, 4, 32)
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    toks = seqs(cfg, shape=(2, 40))
    with jax.default_matmul_precision("highest"):
        want = bucket_prefill(model, params, toks)
        got = prefill_24(model, params, toks)
        assert float(jnp.max(jnp.abs(got - want))) < F32_TOL
        assert float(jnp.max(jnp.abs(model.apply(params, toks) - want))) \
            < F32_TOL


def test_config_refuses_a_pattern_it_cannot_run():
    with pytest.raises(ValueError, match="layer_types"):
        config(layer_types=PATTERN[:3])
    with pytest.raises(ValueError, match="layer_types"):
        config(layer_types=("linear_attention",) * 4)
    with pytest.raises(ValueError, match="sliding_window"):
        config(sliding_window=None)
    with pytest.raises(ValueError, match="rope_scaling"):
        config(rope_scaling=(("chunked_attention", YARN),))


# -- YaRN ------------------------------------------------------------------
def test_yarn_table_against_the_closed_form():
    """Published Mellum2 parameters at head_dim 128, in numpy float64:
    lanes that turn more than 32 times over the original 8,192 positions
    keep their frequency, lanes that turn less than once are slowed 16
    times, a linear ramp from lane 18 to lane 35."""
    hd, theta, original, factor = 128, 500_000.0, 8192, 16.0
    yarn = rope.YarnScaling(factor=factor, original_max_position=original,
                            beta_fast=32, beta_slow=1)
    def lane(turns):
        return hd * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low, high = math.floor(lane(32)), math.ceil(lane(1))
    assert (low, high) == (18, 35)
    i = np.arange(hd // 2)
    base = theta ** (-2.0 * i / hd)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = base * (1 - ramp) + base / factor * ramp
    got = np.asarray(rope.yarn_inv_freq(hd, theta, yarn), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(got[:low + 1], base[:low + 1], rtol=2e-6)
    np.testing.assert_allclose(got[high:], base[high:] / factor, rtol=2e-6)
    # the factor on cos and sin: the published one is the paper's default
    assert yarn.cos_sin_scale == pytest.approx(1.2772588722239782, rel=1e-12)
    assert dataclasses.replace(
        yarn, attention_factor=1.5).cos_sin_scale == 1.5
    table = rope.rope_frequencies(hd, 64, theta=theta, yarn=yarn)
    np.testing.assert_allclose(np.asarray(table[5]), 5 * got, rtol=1e-6)
    # the reference computes its own, from the published keys
    ref, scale = reference.inv_freq(hd, {
        "rope_type": "yarn", "rope_theta": theta, "factor": factor,
        "original_max_position_embeddings": original, "beta_fast": 32,
        "beta_slow": 1})
    np.testing.assert_allclose(np.asarray(ref), want, rtol=2e-6)
    assert scale == pytest.approx(yarn.cos_sin_scale)


def test_apply_rope_of_kind_is_the_kinds_table_and_factor():
    """Angles computed from the positions are the table's, bit for bit."""
    inv = jnp.stack([rope.yarn_inv_freq(8, 1e4, None),
                     rope.yarn_inv_freq(8, 1e4, YARN)])
    tables = [rope.rope_frequencies(8, 32, theta=1e4),
              rope.rope_frequencies(8, 32, theta=1e4, yarn=YARN)]
    scales = jnp.asarray([1.0, 1.5])
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 8))
    pos = jnp.asarray([[3, 4, 5, 6, 7], [0, 1, 2, 3, 4]])
    for kind in (0, 1):
        for positions in (None, pos):
            got = jax.jit(lambda k: rope.apply_rope_of_kind(
                x, inv, scales, k, positions))(kind)
            want = rope.apply_rope(x, tables[kind], positions)
            # cos and sin times the factor is the rotation times it
            np.testing.assert_allclose(got, want * scales[kind], atol=1e-6)


# -- the paged kernel with a first visible position -------------------------
@pytest.mark.parametrize("starts", [
    (0, 0, 0), (8, 16, 0), (3, 21, 12), (0, 37, 6)],
    ids=["none", "block-aligned", "inside-a-block", "mixed"])
@pytest.mark.parametrize("heads", [(4, 2, 32), (4, 2, 128)],
                         ids=["packed-rows", "128-lanes"])
def test_paged_kernel_starts_at_the_first_visible_position(starts, heads):
    """Interpreted, against the reference: the rows before ``starts``
    are not seen, whether a window begins on a block boundary or inside
    a block; the table's entries before the first page point at a block
    of NaN, which the kernel must never read."""
    H, Hkv, D = heads
    B, bs, maxb = 3, 8, 6
    lengths = jnp.asarray([20, 40, 13], I32)
    starts = jnp.asarray(starts, I32)
    key = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(key[0], (B, H, D))
    nb = B * maxb
    k_pool = jax.random.normal(key[1], (nb + 1, bs, Hkv, D))
    v_pool = jax.random.normal(key[2], (nb + 1, bs, Hkv, D))
    tables = jnp.arange(nb, dtype=I32).reshape(B, maxb)
    want = paged_attention.paged_decode_attention(
        q, k_pool, v_pool, tables, lengths, impl="xla", starts=starts)
    dense = paged_attention.ragged_decode_attention_reference(
        q, k_pool[tables].reshape(B, maxb * bs, Hkv, D),
        v_pool[tables].reshape(B, maxb * bs, Hkv, D), lengths, starts=starts)
    np.testing.assert_allclose(want, dense, atol=1e-6)
    # what the window has left behind is gone: freed blocks read as NaN
    dead = jnp.arange(maxb)[None, :] < (starts // bs)[:, None]
    poisoned = jnp.where(dead, nb, tables)
    k_nan = k_pool.at[nb].set(jnp.nan)
    v_nan = v_pool.at[nb].set(jnp.nan)
    got = paged_attention.paged_decode_attention(
        q, k_nan, v_nan, poisoned, lengths, impl="pallas", starts=starts)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if int(starts.sum()) == 0:      # and with none it is the plain kernel
        plain = paged_attention.paged_decode_attention(
            q, k_pool, v_pool, tables, lengths, impl="pallas")
        np.testing.assert_allclose(got, plain, atol=1e-6)


# -- the allocator of a model with two kinds ---------------------------------
def test_window_blocks_a_slot():
    # a 1024-token window on 32-token blocks: 32 blocks it overlaps (it
    # need not begin on a boundary), a tail block, one 512-token chunk
    assert paged_cache.window_blocks_per_slot(1024, 32, 512) == 32 + 2 + 16
    assert paged_cache.window_blocks_per_slot(16, 8, 32) == 2 + 2 + 4
    assert paged_cache.first_window_block(0, 16, 8) == 0
    assert paged_cache.first_window_block(15, 16, 8) == 0
    assert paged_cache.first_window_block(23, 16, 8) == 1     # sees 8..23
    assert paged_cache.first_window_block(24, 16, 8) == 1     # sees 9..24
    assert paged_cache.first_window_block(31, 16, 8) == 2


def test_sliding_allocation_frees_behind_the_window_and_never_a_live_block():
    bs, window, slots, max_seq = 8, 16, 3, 200
    per_slot = paged_cache.window_blocks_per_slot(window, bs, 0)
    pool = paged_cache.BlockPool(slots * per_slot, bs)
    allocs = [paged_cache.WindowAllocation(0, []) for _ in range(slots)]
    freed = 0
    for offset in range(max_seq):            # every slot decodes in step
        for w in allocs:
            freed += paged_cache.slide_window(
                pool, w, paged_cache.first_window_block(offset, window, bs),
                offset + 1)
            # every position the query at ``offset`` sees, and the one it
            # writes, lies in a held block
            held = range(w.first * bs, (w.first + len(w.blocks)) * bs)
            assert max(0, offset - window + 1) in held and offset in held
            assert len(w.blocks) <= per_slot
        assert pool.num_free + sum(len(w.blocks) for w in allocs) \
            == pool.num_blocks
        live = [b for w in allocs for b in w.blocks]
        assert len(set(live)) == len(live)          # nobody shares a block
        assert all(pool.refcount[b] == 1 for b in live)
    assert freed == slots * (max_seq // bs - (window // bs))
    assert allocs[0].ids(0, 4, -1) == [-1] * 4       # long gone
    first = allocs[0].first
    assert allocs[0].ids(first, first + 1, -1) == [allocs[0].blocks[0]]


def test_a_prefix_hit_needs_both_kinds():
    """The rule: n blocks are shared only if the full kind's index holds
    blocks 0..n-1 AND the sliding kind's still holds every block the
    window before position n*bs overlaps; else the longest n for which
    both hold."""
    bs, window = 8, 16
    full = paged_cache.BlockPool(32, bs)
    win = paged_cache.BlockPool(8, bs)
    prompt = list(range(1, 50))                       # 6 full blocks
    alloc, shared = paged_cache.allocate_slot(
        full, prompt, window_pool=win, window=window)
    assert shared == 0 and alloc.window.blocks == []
    # a prefill wrote everything; the sliding kind frees as it goes and
    # indexes each prompt block before it lets it go
    paged_cache.slide_window(win, alloc.window, 0, 49)
    paged_cache.seal_prompt_blocks(full, alloc, prompt)
    paged_cache.slide_window(
        win, alloc.window, paged_cache.first_window_block(49, window, bs), 50)
    paged_cache.seal_window_blocks(win, alloc.window)
    assert alloc.window.first == 4
    full.unref_all(alloc.blocks)
    win.unref_all(alloc.window.blocks)
    # same first 40 tokens: a hit of 5 blocks reads the window before
    # position 40, blocks 3 and 4, both still indexed (3 freed, 4 held)
    other, shared = paged_cache.allocate_slot(
        full, prompt[:40] + [900, 901, 902], window_pool=win, window=window)
    assert shared == 40 and other.window.first == 3
    assert len(other.window.blocks) == 2
    full.unref_all(other.blocks)
    win.unref_all(other.window.blocks)
    # the sliding kind's pool reallocates the freed blocks: the full
    # kind still has the prefix, the hit is gone all the same
    assert win.alloc(8) is not None
    again, shared = paged_cache.allocate_slot(
        full, prompt[:40] + [900, 901, 902], window_pool=win, window=window)
    assert shared == 0 and again.window.blocks == []
    assert again.shared_blocks == 0


# -- the engine with a pool a kind -----------------------------------------
@pytest.fixture(scope="module")
def served():
    cfg, model, params = make()
    step = jax.jit(model.forward_step)

    def oracle(prompt, n):
        """Greedy tokens through a DENSE cache that keeps every token in
        every layer and masks: what the engine has to reproduce from the
        blocks it still holds."""
        cache = model.init_kv_cache(1, len(prompt) + n)
        logits, cache = step(params, jnp.asarray([prompt], I32), cache,
                             jnp.zeros((1,), I32))
        out = [int(jnp.argmax(logits[0, -1]))]
        for i in range(n - 1):
            logits, cache = step(params, jnp.asarray([[out[-1]]], I32),
                                 cache, jnp.asarray([len(prompt) + i], I32))
            out.append(int(jnp.argmax(logits[0, 0])))
        return out

    return cfg, model, params, oracle


def engine(model, params, **kw):
    return ContinuousBatchingEngine(
        model, params, **{**dict(max_slots=4, max_seq=256,
                                 prefill_buckets=(16, 32), block_size=8),
                          **kw})


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 512, n)] for n in lengths]


def test_engine_holds_a_pool_and_a_table_a_kind(served):
    cfg, model, params, _ = served
    eng = engine(model, params)
    per_slot = paged_cache.window_blocks_per_slot(WINDOW, 8, 32)
    assert eng.window == WINDOW and eng.num_blocks == 4 * 32
    assert eng.num_window_blocks == 4 * per_slot == 32
    # one stack: a full layer's window of it is max_seq rows a slot, a
    # sliding layer's what a window, a tail and a chunk need, + scratch
    assert eng.kv["k"].shape[0] == 1 * 129 + 3 * 33
    assert eng.kv["bases"].tolist() == [0, 33, 66, 99]
    st = eng.stats
    assert (st["kv_pool_blocks_full"], st["kv_pool_blocks_window"]) \
        == (128, 32)
    # a model with one kind: the pool it always had, the counters at 0
    plain = model_for(MoEConfig.debug_olmoe())
    one = ContinuousBatchingEngine(
        plain, plain.init(jax.random.key(0)), max_slots=2, max_seq=64,
        prefill_buckets=(16,), block_size=8)
    assert one.window is None and one.window_pool is None
    assert set(one.kv) == {"k", "v"} and one.kv["k"].ndim == 5
    assert one.stats["kv_pool_blocks_window"] == 0
    assert one.stats["kv_pool_blocks_full"] == one.num_blocks


def test_engine_matches_the_dense_oracle_token_for_token(served):
    """Bucket prefill (5, 30), chunked prefill of prompts several
    windows long (100, 70: chunks of 32), decode across block frees."""
    cfg, model, params, oracle = served
    eng = engine(model, params)
    ps = prompts((5, 30, 100, 70))
    reqs = eng.generate(ps, SamplingParams(max_tokens=40))
    for p, r in zip(ps, reqs):
        assert r.output == oracle(p, 40), len(p)
    st = eng.stats
    assert st["prefills"] == 1 + 1 + 4 + 3
    assert st["kv_window_blocks_freed"] > 0
    # every sliding block came back, and every full one
    assert eng.window_pool.num_free == eng.num_window_blocks
    assert eng.pool.num_free == eng.num_blocks
    # a sliding layer reads at most the blocks a window overlaps + tail
    assert 0 < st["decode_kv_blocks_live_window"] <= 3 * (
        st["tokens_generated"] - len(ps))
    assert st["decode_kv_blocks_live_window"] < st["decode_kv_blocks_live"]
    assert st["moe_assignments"] == st["moe_assignments_expected"] > 0


def test_engine_runs_a_step_ahead_at_full_occupancy_and_frees_under_it(
        served):
    cfg, model, params, oracle = served
    eng = engine(model, params, max_slots=2)
    ps = prompts((20, 27), seed=3)
    reqs = [eng.submit(p, SamplingParams(max_tokens=45)) for p in ps]
    ahead = 0
    while eng.has_work():
        eng.step()
        ahead += eng._in_flight is not None
    assert ahead > 20
    for p, r in zip(ps, reqs):
        assert r.output == oracle(p, 45)
    assert eng.window_pool.num_free == eng.num_window_blocks


def test_a_dropped_step_ahead_leaves_both_kinds_blocks_as_they_were(served):
    """Two of four slots, lengths that end one after the other across
    window frees, and a request that arrives as the first ends, with the
    step dispatched for the ended one unread (PR 60: the step ahead is
    speculative a row): that step wrote a row into a tail block of EACH
    kind that the request had held alone; both went back to their pools
    at the read before, and what takes them is enqueued behind it."""
    cfg, model, params, oracle = served
    eng = engine(model, params)
    ps, outs = prompts((20, 27, 9), seed=5), (12, 45, 30)
    reqs, ended_ahead, admitted_ahead = serving.drive_arrivals(eng, [
        (due, p, SamplingParams(max_tokens=n)) for due, p, n in zip(
            (None, None, serving.ended(0)), ps, outs)])
    for p, n, r in zip(ps, outs, reqs):
        assert r.output == oracle(p, n), len(p)
    st = eng.stats
    # the first and the third end beside the second, which ends alone
    assert st["decode_rows_dropped"] == ended_ahead == 2
    assert admitted_ahead == 1 and st["kv_window_blocks_freed"] > 0
    assert st["decode_steps_ahead"] > 0.8 * st["decode_steps"]
    assert eng.window_pool.num_free == eng.num_window_blocks
    assert eng.pool.num_free == eng.num_blocks and eng._in_flight is None


def test_engine_preempts_and_readmits_a_two_kind_request(served):
    cfg, model, params, oracle = served
    eng = engine(model, params, num_blocks=14)
    ps = prompts((20, 24), seed=1)
    reqs = eng.generate(ps, SamplingParams(max_tokens=50))
    assert eng.stats["preemptions"] >= 1
    assert sum(r.preemptions for r in reqs) >= 1
    for p, r in zip(ps, reqs):
        assert r.output == oracle(p, 50)
    assert eng.pool.num_free == 14
    assert eng.window_pool.num_free == eng.num_window_blocks


def test_engine_shares_a_prefix_longer_than_the_window(served):
    cfg, model, params, oracle = served
    eng = engine(model, params)
    base = prompts((100,), seed=5)[0]
    eng.generate([base], SamplingParams(max_tokens=2))
    assert eng.stats["prefix_prefills"] == 0
    # 64 shared tokens: four windows. The sliding kind serves the hit
    # from two blocks it freed (and indexed) during the first prefill
    second = base[:64] + prompts((20,), seed=6)[0]
    r = eng.generate([second], SamplingParams(max_tokens=12))[0]
    assert r.output == oracle(second, 12)
    assert eng.stats["prefix_prefills"] == 1
    assert eng.stats["prefix_tokens_reused"] == 64
    # two at once with one prefix: the batched suffix prefill
    pair = [base[:40] + s for s in prompts((9, 12), seed=7)]
    out = eng.generate(pair, SamplingParams(max_tokens=10))
    for p, r in zip(pair, out):
        assert r.output == oracle(p, 10)
    assert eng.stats["prefix_prefills"] == 3
    # the sliding kind's pool is small: once other requests have gone
    # through it the blocks are reallocated and the hit is not taken,
    # though the full kind still has the prefix
    eng.generate(prompts((90, 90, 90, 90), seed=8),
                 SamplingParams(max_tokens=30))
    before = eng.stats["prefix_prefills"]
    r = eng.generate([second], SamplingParams(max_tokens=6))[0]
    assert r.output == oracle(second, 6)
    assert eng.stats["prefix_prefills"] == before


def test_disaggregated_prefill_hands_over_a_two_kind_cache(served):
    cfg, model, params, oracle = served
    prefiller, decoder = engine(model, params), engine(model, params)
    prompt = prompts((29,), seed=9)[0]
    kv, logits, n = prefiller.prefill_only(prompt)
    req = decoder.submit_prefilled(prompt, kv, logits,
                                   SamplingParams(max_tokens=30))
    while decoder.has_work():
        decoder.step()
    assert req.output == oracle(prompt, 30)
    assert decoder.window_pool.num_free == decoder.num_window_blocks


def test_serve_builds_the_model_from_its_published_keys():
    """``benchmark/builders/mellum.py`` -> ``models.model_for`` ->
    ``LLMServer``: no flag, the config's keys decide."""
    from benchmark import run as harness
    from benchmark.builders import mellum
    from ray_tpu.llm.serving import LLMConfig, LLMServer

    published = harness.load_json(
        harness.ROOT, "benchmark/configs/mellum2-12b-a2.5b-d8.json")
    cfg = mellum.program_config({**published, **published["tiny_cpu"]}, 128)
    assert cfg.layer_types == PATTERN and cfg.sliding_window == 16
    assert cfg.head_dim == 32 != cfg.dim // cfg.n_heads
    server = LLMServer(LLMConfig(model_config=cfg, max_slots=2, max_seq=128,
                                 block_size=8))
    try:
        assert server.engine.window == 16
        assert server.model.layer_kinds == (1, 1, 1, 0)
        assert LAYER_KINDS[server.model.layer_kinds[0]] == "sliding_attention"
        out = server({"prompt": list(range(1, 41)), "max_tokens": 24})
        assert len(out["token_ids"]) == 24
        assert server.stats()["kv_window_blocks_freed"] > 0
    finally:
        server._stop.set()
