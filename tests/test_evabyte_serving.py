"""An EVA model (EvaByte's layer: an exact window that RESETS, chunk
summaries of everything before it, one softmax over both) on the serving
path, at debug widths on the CPU, against the plain float32 reference
(``benchmark/reference/evabyte.py``, which shares no code with
``ray_tpu/``):

- ``apply`` (all ``num_pred_heads x vocab`` logits), bucket prefill +
  uniform-pool decode (the harness's logits check), and the ENGINE's own
  chunked prefill + two-part decode, each over sequences that cross two
  or more window ends and end mid-chunk: float32 to 1e-4, bf16 at the
  dense block's floor;
- the two decode bodies (uniform pool, two-part pools) and the two
  attention implementations give the same logits;
- the allocation: the exact part goes back WHOLE at a window end, the
  summary part grows a block per ``block_size * chunk`` positions, both
  pools are full again after the drain;
- preemption by recompute, a repeated prompt (no prefix hit: same
  tokens), and the sizes the engine refuses.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import evabyte as reference
from ray_tpu.llm.engine import SamplingParams
from ray_tpu.llm.paged_cache import (BlockPool, WindowAllocation,
                                     eva_window_block, slide_window)
from ray_tpu.models.llama import EVA_KIND, LlamaConfig
from tests import serving_family as serving
from tests.serving_family import rel_rms

W, C, BS, V = 32, 4, 8, 320
BF16_REL_RMS = 0.02     # the dense block's bf16 floor at debug widths
KW = dict(rope_theta=1e5, rms_norm_eps=1e-5, window=W, chunk=C)
REFERENCE = jax.jit(lambda params, toks: reference.forward(params, toks, **KW))


def config(dtype, impl=None, **more):
    return LlamaConfig(
        vocab_size=V, dim=64, n_layers=4, n_heads=4, n_kv_heads=4,
        ffn_dim=128, max_seq_len=256, rope_theta=1e5, norm_eps=1e-5,
        dtype=dtype, remat=False, layer_types=(EVA_KIND,) * 4, eva_window=W,
        eva_chunk=C, norm_add_unit_offset=True, fp32_residual=True,
        num_pred_heads=8, decode_attention=impl, **more)


def seeded(model, seed):
    params = model.init(jax.random.key(seed))
    # norm offsets that are not 0, so that ``1 + g`` is held to account
    for name in ("attn_norm", "mlp_norm"):
        params["layers"][name] = 0.1 * jax.random.normal(
            jax.random.key(7), params["layers"][name].shape)
    return params


FAMILY = serving.Family(
    config=config, seeded=seeded,
    reference=lambda cfg, params, toks: REFERENCE(params, toks),
    engine_kw=dict(max_slots=3, max_seq=256, prefill_buckets=(8, 16, 32),
                   block_size=BS))
make = functools.partial(serving.make, FAMILY, seed=0)
engine = functools.partial(serving.engine_of, FAMILY)


def tokens(n, rows=2, seed=0):
    return serving.seqs(make()[0], (rows, n), seed)


def want_logits(params, toks, every_head=False):
    out = REFERENCE(params, toks)
    return out if every_head else out[..., :V]


def paged_after_bucket_prefill(model, params, toks, prompt_len):
    """The harness's logits check by hand: ``forward_step`` into a slot
    cache, the cache as a uniform pool, then paged decode steps."""
    return serving.prefill_then_paged_decode(model, params, toks, prompt_len,
                                             BS)


# two window ends and a bit, ending mid-chunk
T = 2 * W + 21


def test_the_model_holds_the_kind_its_parameters_and_every_head():
    cfg, model, params = make()
    assert model.eva == (W, C) and model.layer_kinds is None
    assert params["layers"]["eva_phi"].shape == (4, 4, 16)
    assert params["lm_head"].shape == (64, 8 * V)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    served = model.serving_params(params)
    assert served["layers"]["eva_mu"].dtype == jnp.float32
    with pytest.raises(ValueError, match="not mixed"):
        dataclasses.replace(cfg, layer_types=(
            EVA_KIND, "full_attention", EVA_KIND, EVA_KIND))
    with pytest.raises(ValueError, match="eva_chunk divides"):
        dataclasses.replace(cfg, eva_chunk=5)


def test_apply_gives_every_heads_logits_as_the_reference():
    _, model, params = make()
    toks = tokens(T)
    with jax.default_matmul_precision("highest"):
        got = serving.full_forward(model, params, toks)
    assert got.shape == (2, T, 8 * V)
    np.testing.assert_allclose(got, want_logits(params, toks, True),
                               atol=1e-4)
    # the pieces matter: no summaries, no mu, a window that slides
    base = want_logits(params, toks)
    for control in (dict(phi=0.0), dict(mu=0.0)):
        changed = dict(params, layers=dict(params["layers"]))
        for name, factor in control.items():
            changed["layers"]["eva_" + name] = (
                factor * params["layers"]["eva_" + name])
        assert rel_rms(want_logits(changed, toks), base) > 0.01, control


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_bucket_prefill_and_uniform_pool_decode_match_the_reference(impl):
    _, model, params = make(impl=impl)
    toks = tokens(T, seed=1)
    with jax.default_matmul_precision("highest"):
        got = paged_after_bucket_prefill(model, params, toks, W + 7)
    np.testing.assert_allclose(got, want_logits(params, toks), atol=1e-4)


def test_bf16_compute_is_at_the_dense_blocks_floor():
    _, model, params = make(jnp.bfloat16)
    toks = tokens(T, seed=2)
    want = want_logits(params, toks)
    served = model.serving_params(params)
    got = paged_after_bucket_prefill(model, served, toks, W + 7)
    assert rel_rms(got, want) < BF16_REL_RMS
    assert rel_rms(serving.full_forward(model, params, toks)[..., :V],
                   want) < BF16_REL_RMS


def engine_logits(eng, prompt, steps):
    """The logits of the engine's own decode positions: its chunked (or
    bucket) prefill into its two-part pools, then for every step the
    model's paged step on the ENGINE's pools, tables and offsets (not
    donated), before the engine takes the same step."""
    model = eng.model
    probe = serving.jitted(model, "decode_step_paged")
    # the probe stands where the engine's next step will: no step ahead
    # may have moved the pools and the offsets on
    serving.reads_first(eng)
    req = eng.submit(list(prompt), SamplingParams(max_tokens=steps + 1))
    eng._admit()
    out = []
    for _ in range(steps):
        eng._grow_or_preempt()        # this step's blocks and tables
        logits, _ = probe(
            eng.params, jnp.asarray(eng._last_tokens), eng.kv,
            jnp.asarray(np.stack([eng._tables, eng._tables_win])),
            jnp.asarray(eng.offsets))
        out.append(logits[0])
        eng.step()
    while eng.has_work():
        eng.step()
    return req, jnp.stack(out)


@pytest.mark.parametrize("impl,prompt_len", [
    ("xla", 2 * W + 13), ("pallas", 2 * W + 13), ("xla", 21)])
def test_the_engines_prefill_and_two_part_decode_match_the_reference(
        impl, prompt_len):
    """Chunked prefill over two window ends (or one bucket), then decode
    across the next window end: every position's logits."""
    _, model, params = make(impl=impl)
    steps = W + 9
    with jax.default_matmul_precision("highest"):
        eng = engine(model, params)
        prompt = [int(t) for t in tokens(prompt_len, rows=1, seed=3)[0]]
        req, got = engine_logits(eng, prompt, steps)
        seq = jnp.asarray([prompt + req.output], jnp.int32)
        want = want_logits(params, seq)[0]
    # position p's logits predict token p + 1: the engine's own choice
    at = slice(prompt_len, prompt_len + steps)
    np.testing.assert_allclose(got, want[at], atol=1e-4)
    assert req.output[1:steps + 1] == [int(t) for t in
                                       jnp.argmax(want[at], -1)]
    assert req.output[0] == int(jnp.argmax(want[prompt_len - 1]))
    stats = eng.stats
    assert stats["kv_window_resets"] >= (prompt_len + steps) // W
    assert stats["decode_kv_blocks_live_summary"] > 0
    assert (stats["decode_kv_blocks_live"]
            < stats["decode_kv_blocks_full_equivalent"])
    assert eng.pool.num_free == eng.pool.num_blocks
    assert eng.window_pool.num_free == eng.window_pool.num_blocks


def test_both_decode_bodies_read_the_same_logits():
    """The engine's two parts against the uniform pool (one row a
    position, the earlier windows pooled as it goes), fed the engine's
    own tokens, position by position across a window end."""
    _, model, params = make()
    prompt_len, steps = W + 7, W + 9
    prompt = [int(t) for t in tokens(prompt_len, rows=1, seed=4)[0]]
    with jax.default_matmul_precision("highest"):
        req, two_part = engine_logits(engine(model, params), prompt, steps)
        fed = jnp.asarray([prompt + req.output[:steps]] * 2, jnp.int32)
        uniform = paged_after_bucket_prefill(model, params, fed, prompt_len)
    np.testing.assert_allclose(two_part, uniform[0, prompt_len:], atol=1e-4)


def test_the_exact_part_goes_whole_and_the_summary_part_grows():
    pool = BlockPool(12, BS)
    alloc = WindowAllocation(0, [])
    per_window = W // BS
    for pos in range(3 * W):
        freed = slide_window(pool, alloc, eva_window_block(pos, W, BS),
                             pos + 1)
        # nothing goes inside a window; at its end every block together
        assert freed == (per_window if pos and pos % W == 0 else 0), pos
        assert alloc.first == pos // W * per_window
        assert len(alloc.blocks) == pos % W // BS + 1
    assert eva_window_block(W - 1, W, BS) == 0
    assert eva_window_block(W, W, BS) == per_window

    _, model, params = make()
    eng = engine(model, params, max_slots=2)
    assert eng.pool.block_size == BS * C          # positions a summary block
    assert eng.blocks_per_slot == 256 // (BS * C)
    assert eng.kv["sk"].shape[:3] == (4, eng.num_blocks + 1, BS)
    assert eng.kv["k"].shape[:3] == (4, eng.num_window_blocks + 1, BS)
    req = eng.submit([int(t) for t in tokens(70, rows=1)[0]],
                     SamplingParams(max_tokens=60))
    held = []
    while eng.has_work():
        eng.step()
        if eng.allocs[0] is not None:
            alloc = eng.allocs[0]
            held.append((int(eng.offsets[0]), len(alloc.blocks),
                         len(alloc.window.blocks)))
    for offset, summary, exact in held:
        # read between two steps: the next row's block may not be there
        covered = -(-offset // (BS * C))
        assert covered <= summary <= covered + 1, (offset, summary)
        assert exact <= per_window + 1
    assert len(req.output) == 60
    stats = eng.stats
    assert stats["kv_pool_blocks_summary"] == eng.num_blocks
    assert stats["kv_pool_blocks_exact"] == eng.num_window_blocks
    assert stats["kv_pool_blocks_full"] == stats["kv_pool_blocks_window"] == 0
    # 70 // 4 whole chunks from the prefill, then one per 4 decode steps
    assert stats["kv_summary_rows_written"] == 70 // C + sum(
        1 for p in range(70, 70 + 59) if p % C == C - 1)


def test_preemption_recomputes_and_a_repeated_prompt_hits_nothing():
    _, model, params = make()
    prompts = [[int(t) for t in tokens(n, rows=1, seed=n)[0]]
               for n in (40, 50)]
    sampling = SamplingParams(max_tokens=70)
    with jax.default_matmul_precision("highest"):
        roomy = engine(model, params).generate(prompts, sampling)
        # 6 summary blocks of 32 positions: two requests of ~110-120
        # positions cannot both finish
        tight = engine(model, params, num_blocks=6)
        squeezed = tight.generate(prompts, sampling)
        again = engine(model, params)
        first = again.generate(prompts[:1], sampling)
        second = again.generate(prompts[:1], sampling)
    assert tight.stats["preemptions"] >= 1
    assert [r.output for r in squeezed] == [r.output for r in roomy]
    assert first[0].output == second[0].output == roomy[0].output
    assert again.stats["prefix_prefills"] == 0
    assert again.pool.cached_free_blocks() == 0


@pytest.mark.parametrize("kw,match", [
    (dict(block_size=6, prefill_buckets=(12, 24)), "block_size 6"),
    (dict(block_size=12, prefill_buckets=(12, 24)), "window 32"),
])
def test_the_engine_refuses_sizes_that_do_not_tile(kw, match):
    """A chunk's rows lie in one block and a window starts on a block
    (every bucket is a multiple of the block already)."""
    _, model, params = make()
    with pytest.raises(ValueError, match=match):
        engine(model, params, **kw)


def test_the_handoff_refuses_an_eva_model():
    _, model, params = make()
    with pytest.raises(NotImplementedError, match="handoff"):
        engine(model, params).prefill_only([1, 2, 3])
