"""SDAR's block and its generation by diffusion over blocks on the
serving programs, against the plain reference (``benchmark/reference/
sdar.py``), on seeded weights at debug widths: the two prefills under
the block-causal mask, the block step through the paged pool for several
blocks in a row (so that committed rows are read back), the unmask rule
on crafted confidences, and the planted faults, each of which has to
move the comparison past its tolerance. The engine end to end:
``tests/test_sdar_engine.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar as reference
from ray_tpu.models import MoEConfig, MoEModel
from ray_tpu.ops.block_diffusion import (confidence, transfer_quotas,
                                         unmask_step)
from tests import serving_family as serving
from tests.serving_family import (I32, jitted, max_abs, paged_prefill,
                                  rel_rms, seqs)
from tests.serving_family import sdar_ref_kw as ref_kw
from tests.serving_family import sdar_ref_params as ref_params

F32_TOL = 1e-4          # max |logit difference|, logits of RMS ~1
BF16_REL_RMS = 0.02     # bf16 compute, the reference forced to its routing
FAULT_REL_RMS = 1e-2    # what a planted fault has to pass (the honest
                        # float32 comparison reads ~1e-6)
N = 4                   # the debug configuration's block
FAMILY = serving.SDAR
ref_forward = functools.partial(serving.reference, FAMILY)
make = functools.partial(serving.make, FAMILY)


def states_of(cfg, tail, seed=5):
    """A run of blocks in the three states a block passes through: all
    masked, a seeded subset masked, clean."""
    hide = np.random.default_rng(seed).random(tail.shape) < 0.5
    hide = hide.reshape(tail.shape[0], -1, N)
    hide[..., 0] |= ~hide.any(-1)
    hide[..., 1] &= ~hide.all(-1)
    mask = cfg.mask_token_id
    return [jnp.full_like(tail, mask),
            jnp.where(hide.reshape(tail.shape), mask, tail), tail]


def blocks_through_the_pool(model, params, toks, prompt, states):
    """Every block of ``toks[:, prompt:]`` through the block step, in
    each of ``states`` in turn, the clean pass last (its rows stay):
    logits [states, B, total - prompt, V]."""
    _, pool, tables = paged_prefill(model, params, toks, prompt)
    B, total = toks.shape
    step = jitted(model, "block_step_paged_counted")
    out = [[] for _ in states]
    for at in range(prompt, total, N):
        for k, state in enumerate(states):
            logits, pool, _ = step(
                params, state[:, at - prompt:at - prompt + N], pool, tables,
                jnp.full((B,), at, I32))
            out[k].append(logits)
    return jnp.stack([jnp.concatenate(o, axis=1) for o in out])


# -- (a) the two prefills ---------------------------------------------------
def test_bucket_prefill_of_ragged_lengths_is_the_block_causal_forward():
    cfg, model, params = make()
    toks = seqs(cfg, (3, 32))
    lengths = (8, 20, 32)
    padded = jnp.stack([jnp.where(jnp.arange(32) < n, row, 0)
                        for row, n in zip(toks, lengths)])
    got = serving.bucket_prefill(model, params, padded)
    for row, n in enumerate(lengths):
        want = ref_forward(cfg, params, toks[row:row + 1, :n])
        assert max_abs(got[row, :n], want[0]) < F32_TOL


def test_a_block_sees_all_of_itself_and_nothing_after_it():
    """The mask itself: a change to the LAST token of a block moves the
    logits of the block's first position, a change to the next block's
    first moves none of them."""
    cfg, model, params = make()
    toks = seqs(cfg, (1, 16))
    run = functools.partial(serving.bucket_prefill, model, params)
    base = run(toks)
    inside = run(toks.at[0, 7].set(toks[0, 7] % 100 + 1))
    after = run(toks.at[0, 8].set(toks[0, 8] % 100 + 1))
    assert float(jnp.max(jnp.abs(inside[0, 4] - base[0, 4]))) > 1e-3
    assert float(jnp.max(jnp.abs(after[0, :8] - base[0, :8]))) == 0.0


@pytest.mark.parametrize("prefix,suffix", [(8, 12), (16, 16), (24, 4)])
def test_chunked_prefill_over_a_prefix_is_the_block_causal_forward(prefix,
                                                                   suffix):
    cfg, model, params = make()
    toks = seqs(cfg, (2, prefix + suffix))
    got = serving.prefix_prefill(model, params, toks, prefix)
    want = ref_forward(cfg, params, toks)[:, -1:]
    assert max_abs(got, want) < F32_TOL


# -- (b) the block step through the paged pool ------------------------------
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_block_step_through_the_pool_is_the_references_pass(impl):
    """Three blocks in a row behind a 16-token prefill, each all masked,
    at a seeded subset and clean: every pass's logits are the
    reference's ``denoise_logits`` over the clean prefix and the block
    as it stood, so the rows a commit pass wrote are read back by the
    blocks after it (kernel, interpreted, and reference alike)."""
    cfg, model, params = make(decode_attention=impl)
    toks, prompt = seqs(cfg, (2, 28)), 16
    states = states_of(cfg, toks[:, prompt:])
    got = blocks_through_the_pool(model, params, toks, prompt, states)
    for k, state in enumerate(states):
        for at in range(prompt, 28, N):
            want = reference.denoise_logits(
                ref_params(params), toks[:, :at],
                state[:, at - prompt:at - prompt + N], **ref_kw(cfg))
            err = jnp.max(jnp.abs(
                got[k][:, at - prompt:at - prompt + N] - want))
            assert float(err) < F32_TOL, (k, at)


@pytest.mark.parametrize("state,rows", [(0, (0, 1, 2, 3)), (1, (0, 1, 2, 3)),
                                        (0, (1, 0))],
                         ids=["all_masked", "subset", "compacted"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_fused_pass_is_the_commit_and_the_denoising_pass_after_it(
        impl, state, rows):
    """One call that carries the clean block BEHIND beside the block
    being denoised against the two calls it stands for (commit k, then
    denoise k + 1): block k + 1's logits and the pool's rows of BOTH
    blocks, to 1e-5. Slot 0 has both blocks in one page of 8 rows, slot 1
    a page's edge between them, slot 2 wants no commit (its rows behind
    stay as they were) and slot 3 is idle; the blocks behind a row a
    slot, or those of the two slots that want one alone, in any order;
    kernel (interpreted) and reference alike."""
    cfg, model, params = make(decode_attention=impl)
    toks, prompt = seqs(cfg, (4, 32)), 16
    _, pool, tables = paged_prefill(model, params, toks, prompt)
    scratch = pool["k"].shape[1] - 1
    step = jitted(model, "block_step_paged_counted")
    masked = jnp.full((4, N), cfg.mask_token_id, I32)

    def at(off):
        return jnp.full((4,), off, I32)

    # what a schedule leaves behind it: rows 16..19 from a denoising pass
    # everywhere; in slot 1 committed, with a denoising pass's at 20..23
    _, pool, _ = step(params, masked, pool, tables, at(16))
    only_1 = tables.at[jnp.asarray([0, 2, 3])].set(scratch)
    _, pool, _ = step(params, toks[:, 16:20], pool, only_1, at(16))
    _, pool, _ = step(params, masked, pool, only_1, at(20))

    offsets = jnp.asarray([20, 24, 20, 0], I32)
    wanted = jnp.asarray([True, True, False, False])
    live = jnp.asarray([True, True, True, False])
    tables = tables.at[3].set(scratch)
    pick = jax.vmap(lambda row, off: jax.lax.dynamic_slice(row, (off,), (N,)))
    clean_behind = pick(toks, jnp.maximum(offsets - N, 0))
    current = jnp.where(
        live[:, None],
        pick(states_of(cfg, toks[:, prompt:])[state], offsets - prompt),
        masked)

    # the two calls: the commit of the slots that want one, then the pass
    _, two, _ = step(params, clean_behind, pool,
                     jnp.where(wanted[:, None], tables, scratch),
                     offsets - N, wanted)
    want, two, want_extras = step(params, current, two, tables, offsets, live)
    rows = jnp.asarray(rows)
    got, one, extras = step(params, current, pool, tables, offsets, live,
                            (clean_behind[rows], rows, wanted[rows]))
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got[:3] - want[:3]))) < 1e-5
    for name in ("k", "v"):
        real = slice(0, scratch)          # the scratch page holds anything
        assert float(jnp.max(jnp.abs(one[name][:, real]
                                     - two[name][:, real]))) < 1e-5
        # the commit moved slot 0's rows behind; slot 2's stayed
        page, first = int(tables[0, 2]), slice(0, N)
        assert float(jnp.max(jnp.abs(one[name][:, page, first]
                                     - pool[name][:, page, first]))) > 1e-3
        page = int(tables[2, 2])
        assert float(jnp.max(jnp.abs(one[name][:, page, first]
                                     - pool[name][:, page, first]))) == 0.0
    # the expert load counts the live rows of both halves and no other
    assert int(extras["load"].sum()) == (
        (3 + 2) * N * cfg.expert_top_k * cfg.n_layers)
    assert int(want_extras["load"].sum()) == (
        3 * N * cfg.expert_top_k * cfg.n_layers)
    assert extras["experts"].shape[1] == len(rows) + 4     # behind first
    np.testing.assert_array_equal(extras["experts"][:, len(rows):][:, :3],
                                  want_extras["experts"][:, :3])


def test_teacher_forced_is_denoise_logits_block_by_block():
    """The reference's one-forward route (what the benchmark's check
    runs) against its plain one."""
    cfg, _, params = make()
    toks, start = seqs(cfg, (2, 24)), 12
    states = states_of(cfg, toks[:, start:])
    commit, *rows = reference.teacher_forced(
        ref_params(params), toks, states[:2], start, N, **ref_kw(cfg))
    for state, hidden in zip(states, (*rows, commit)):
        got = reference.logits_of(ref_params(params), hidden)
        for at in range(start, 24, N):
            want = reference.denoise_logits(
                ref_params(params), toks[:, :at],
                state[:, at - start:at - start + N], **ref_kw(cfg))
            err = jnp.max(jnp.abs(got[:, at - start:at - start + N] - want))
            assert float(err) < F32_TOL


def test_bf16_block_step_with_the_reference_forced_to_its_routing():
    """bf16 compute swaps near-tied experts; with the reference forced to
    the system's choices the rest of the arithmetic is held to the dense
    models' tolerance: three blocks from an empty cache, each all masked
    and then clean."""
    cfg, model, params = make(dtype=jnp.bfloat16)
    toks = seqs(cfg, (2, 12))
    pool = model.init_kv_pool(2 * 2 + 1, 8)
    tables = jnp.arange(4, dtype=I32).reshape(2, 2)
    step = jitted(model, "block_step_paged_counted")
    kept, errs = [], []
    for at in range(0, 12, N):
        for block in (jnp.full((2, N), cfg.mask_token_id, I32),
                      toks[:, at:at + N]):
            got, pool, extras = step(params, block, pool, tables,
                                     jnp.full((2,), at, I32))
            forced = jnp.concatenate([*kept, extras["experts"]], axis=2)
            want = reference.denoise_logits(
                ref_params(params), toks[:, :at], block,
                forced_experts=forced, **ref_kw(cfg))
            errs.append(rel_rms(got, want))
        kept.append(extras["experts"])          # the commit pass's
    assert max(errs) < BF16_REL_RMS, errs


# -- (c) the sampler ---------------------------------------------------------
MASK = 99


def _verdict(block, conf, passes=0, dynamic=True, threshold=0.9,
             quotas=(1, 1, 1, 1)):
    block = jnp.asarray([block], I32)
    x0 = jnp.asarray([[10, 11, 12, 13]], I32)
    out, placed, by_conf = unmask_step(
        block, x0, jnp.asarray([conf], jnp.float32),
        jnp.asarray([passes], I32), mask_id=MASK, quotas=quotas,
        threshold=threshold, dynamic=dynamic)
    return out[0].tolist(), placed[0].tolist(), by_conf[0].tolist()


@pytest.mark.parametrize("conf,want,surer", [
    # none passes the threshold: the quota's surest alone
    ([0.2, 0.5, 0.3, 0.1], [MASK, 11, MASK, MASK], [0, 0, 0, 0]),
    # two pass it: both go in, one of them by the threshold alone
    ([0.95, 0.5, 0.92, 0.1], [10, MASK, 12, MASK], [0, 0, 1, 0]),
    # all pass: the block is done in one pass
    ([0.95, 0.99, 0.92, 0.91], [10, 11, 12, 13], [1, 0, 1, 1]),
    # a tie goes to the earlier position
    ([0.4, 0.4, 0.4, 0.4], [10, MASK, MASK, MASK], [0, 0, 0, 0]),
])
def test_the_threshold_lets_none_some_or_all_through(conf, want, surer):
    out, placed, by_conf = _verdict([MASK] * 4, conf)
    assert out == want
    assert placed == [t != MASK for t in want]
    assert by_conf == [bool(s) for s in surer]


def test_a_given_position_is_never_rewritten_and_takes_no_quota():
    out, placed, _ = _verdict([7, MASK, 8, MASK], [0.99, 0.2, 0.99, 0.3])
    assert out == [7, MASK, 8, 13] and placed == [False, False, False, True]
    # nothing left to place: the quota is cut to what is masked
    out, placed, _ = _verdict([7, 8, 9, 6], [0.99] * 4)
    assert out == [7, 8, 9, 6] and not any(placed)


def test_the_static_rule_is_the_quota_alone_and_the_quota_follows_the_pass():
    out, _, by_conf = _verdict([MASK] * 4, [0.95, 0.99, 0.92, 0.91],
                               dynamic=False)
    assert out == [MASK, 11, MASK, MASK] and not any(by_conf)
    assert transfer_quotas(4, 4) == (1, 1, 1, 1)
    assert transfer_quotas(4, 2) == (2, 2)
    assert transfer_quotas(8, 3) == (3, 3, 2)       # the rest to the early
    out, _, _ = _verdict([MASK] * 4, [0.1, 0.4, 0.3, 0.2], passes=1,
                         quotas=(1, 2))
    assert out == [MASK, 11, 12, MASK]


def test_a_proposals_confidence_is_its_probability():
    logits = jnp.log(jnp.asarray([[[0.7, 0.2, 0.1], [0.25, 0.25, 0.5]]]))
    x0 = jnp.argmax(logits, -1)
    assert x0.tolist() == [[0, 2]]
    conf = confidence(logits, x0, jnp.zeros((1,)))
    np.testing.assert_allclose(np.asarray(conf), [[0.7, 0.5]], rtol=1e-5)
    # a sampling slot's is taken under its temperature
    conf = confidence(logits, x0, jnp.full((1,), 0.5))
    np.testing.assert_allclose(np.asarray(conf)[0, 0],
                               0.49 / (0.49 + 0.04 + 0.01), rtol=1e-5)


# -- (e) the planted faults --------------------------------------------------
@functools.lru_cache(maxsize=None)
def _honest_system():
    """The honest float32 system's logits, once for every fault."""
    cfg, model, params = make()
    toks, prompt = seqs(cfg, (2, 28)), 16
    states = states_of(cfg, toks[:, prompt:])
    return params, toks, prompt, states, blocks_through_the_pool(
        model, params, toks, prompt, states)


def _system_and_reference(fault):
    cfg = make()[0]
    params, toks, prompt, states, got = _honest_system()
    commit, *rows = reference.teacher_forced(
        ref_params(params), toks, states[:2], prompt, N, fault=fault,
        **ref_kw(cfg))
    want = jnp.stack([reference.logits_of(ref_params(params), r, fault)
                      for r in (*rows, commit)])
    return got, want


def test_the_honest_comparison_passes_where_the_faults_are_measured():
    got, want = _system_and_reference(None)
    assert float(jnp.max(jnp.abs(got - want))) < F32_TOL


@pytest.mark.parametrize("fault", [
    "causal_in_block", "skip_commit", "qk_norm_all_lanes",
    "no_renormalisation", "autoregressive_shift", "skip_last_layer",
    "int8_weights"])
def test_a_planted_fault_is_refused(fault):
    """The mask made causal INSIDE a block; the commit pass skipped (the
    cache keeps rows computed from masked inputs); QK-norm over all
    lanes; the top-k weights not renormalised; the logits read with the
    autoregressive shift; and the controls every cell has."""
    assert fault in reference.FAULTS
    got, want = _system_and_reference(fault)
    assert rel_rms(got, want) > FAULT_REL_RMS


def test_an_unknown_fault_is_refused():
    cfg, _, params = make()
    with pytest.raises(ValueError, match="no fault"):
        ref_forward(cfg, params, seqs(cfg, (1, 8)), fault="typo")


# -- the configuration --------------------------------------------------------
def test_qk_norm_a_head_has_one_weight_of_head_dim_a_layer():
    cfg, model, params = make()
    assert isinstance(model, MoEModel)
    assert params["layers"]["q_norm"].shape == (cfg.n_layers, cfg.head_dim)
    assert params["layers"]["k_norm"].shape == (cfg.n_layers, cfg.head_dim)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()


@pytest.mark.parametrize("bad", [
    dict(remasking="sequential"), dict(denoising_steps=5),
    dict(mask_token_id=None), dict(mask_token_id=512),
    dict(layer_types=("full_attention", "sliding_attention"),
         sliding_window=8)])
def test_a_block_diffusion_config_that_cannot_be_is_refused(bad):
    with pytest.raises(ValueError):
        MoEConfig.debug_sdar(**bad)


def test_training_a_block_diffusion_model_is_refused_not_run_causal():
    cfg, model, params = make()
    with pytest.raises(NotImplementedError, match="doubled sequence"):
        model.apply(params, seqs(cfg, (1, 8)))


def test_a_page_that_splits_a_block_is_refused():
    cfg, model, params = make()
    pool = model.init_kv_pool(3, 6)
    with pytest.raises(ValueError, match="whole blocks"):
        model.block_step_paged_counted(
            params, jnp.zeros((1, N), I32), pool, jnp.zeros((1, 2), I32),
            jnp.zeros((1,), I32))


def test_block_scopes_are_in_the_lowered_programs_metadata():
    cfg, model, params = make()
    pool = model.init_kv_pool(5, 8)
    text = jax.jit(model.block_step_paged_counted).lower(
        params, jnp.zeros((2, N), I32), pool, jnp.zeros((2, 2), I32),
        jnp.zeros((2,), I32)).as_text(debug_info=True)
    for scope in ("blockdiff_kv_update", "blockdiff_attention", "qk_norm",
                  "moe_router", "moe_experts"):
        assert scope in text, scope
