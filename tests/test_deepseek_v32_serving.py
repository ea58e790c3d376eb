"""DeepSeek-V3.2's block on the serving path, at debug widths with seeded
weights, against ``benchmark/reference/deepseek_v32.py``: learned sparse
attention (a lightning indexer with a cache of its own, the top-k rows as
a mask in the prefills and as row numbers in the decode step, the
absorbed latent attention over the selected rows), a compressed query,
YaRN with its scale on the softmax, the group-limited sigmoid router, and
an expert layer that holds a share of its router's experts.

Under bf16 compute TWO things swap on rounding: the router's near-tied
experts (as for kanana) and the indexer's near-tied rows at the
``index_topk``-th place. So the system is compared in float32 compute,
where nothing swaps, to 1e-4, logits AND selected sets; and in bf16 with
the reference FORCED to the system's experts and rows.
"""

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import deepseek_v32 as builder
from benchmark.reference import deepseek_v32 as reference
from ray_tpu.models import MLAConfig, model_for
from ray_tpu.ops import dsa
from ray_tpu.ops.moe_dispatch import route_topk
from tests import serving_family as serving
from tests.serving_family import I32, rel_rms, seqs

F32_TOL = 1e-4          # max |logit difference|, logits of RMS ~1
# bf16 compute against the float32 reference forced to the system's
# experts and rows, relative RMS of the logits (kanana's block reads
# 0.011-0.014 at these widths; the readings here are 0.012-0.016)
BF16_REL_RMS = 0.025
HELD = (4, 8)           # experts 4..11 of the router's 16


class Selections:
    """What the system selected, recorded as it ran: the prefills' masks
    (``ops.dsa.topk_mask``) and the decode steps' row numbers
    (``select_topk``), in the order the layers ran."""

    def __init__(self, monkeypatch):
        self.masks, self.rows = [], []
        real_mask, real_rows = dsa.topk_mask, dsa.select_topk

        def mask(scores, seen, k):
            out = real_mask(scores, seen, k)
            jax.debug.callback(
                lambda m: self.masks.append(np.asarray(m)), out,
                ordered=True)
            return out

        def rows(scores, lengths, k):
            out = real_rows(scores, lengths, k)
            jax.debug.callback(
                lambda r, n: self.rows.append((np.asarray(r),
                                               np.asarray(n))),
                *out, ordered=True)
            return out

        monkeypatch.setattr(dsa, "topk_mask", mask)
        monkeypatch.setattr(dsa, "select_topk", rows)

    def prefill(self, layers: int):
        """[L, B, T, S] of one prefill call: its blocks joined."""
        blocks = len(self.masks) // layers
        out = np.stack([np.concatenate(self.masks[l * blocks:(l + 1) * blocks],
                                       axis=1) for l in range(layers)])
        self.masks.clear()
        return out

    def decode(self, layers: int, width: int):
        """[L, B, width] bool of one decode step."""
        out = np.zeros((layers, len(self.rows[0][1]), width), bool)
        for l, (rows, count) in enumerate(self.rows[:layers]):
            for b, n in enumerate(count):
                out[l, b, rows[b, :n]] = True
        del self.rows[:layers]
        return out


def apply_and_rows(model, params, toks, sel):
    logits = serving.full_forward(model, params, toks)
    return logits, sel.prefill(model.cfg.n_layers)


def paged_decode_and_rows(model, params, toks, sel, prompt=24):
    """``check_logits``'s route: a prefill (EXPANDED, the selection as a
    mask), then paged decode steps (index scores over the pages, row
    numbers, the absorbed form over the selected rows). 24 rows are
    prefilled, so the decode steps select 12 of 25..48, and cross block
    edges at 32 and 40."""
    L, total = model.cfg.n_layers, toks.shape[1]
    chosen = []

    def seen(kind):
        chosen.append(sel.prefill(L)[:, :, :prompt, :total]
                      if kind == "prefill"
                      else sel.decode(L, total)[:, :, None])

    logits = serving.prefill_then_paged_decode(model, params, toks, prompt,
                                               seen=seen)
    return logits, np.concatenate(chosen, axis=2)


def prefix_and_rows(model, params, toks, sel, prefix=32):
    """A suffix prefill of 16 rows over a cached prefix of 32: the chunk's
    queries score the gathered prefix's index keys and their own."""
    total = toks.shape[1]
    logits = serving.prefix_prefill(model, params, toks, prefix,
                                    seen=lambda kind: sel.masks.clear())
    # the keys: 40 of the padded prefix, then the suffix's own
    masks = sel.prefill(model.cfg.n_layers)[:, :, :total - prefix]
    chosen = np.concatenate([masks[..., :prefix],
                             masks[..., 40:40 + total - prefix]], axis=-1)
    return logits, chosen                # the position total - 1's logits


@functools.lru_cache(maxsize=None)
def wanted_rows():
    cfg, _, params, toks, _ = serving.honest(FAMILY)
    _, choices = ref_forward(cfg, params, toks, with_choices=True)
    return cfg, np.asarray(choices["selection"])


def and_rows(run, first=0):
    """A path of ``FAMILY.paths``: ``run``'s logits, with the selected
    SETS held to the reference's on the way, every layer, every position
    from ``first``: 12 rows once there are so many, every row before."""
    def path(model, params, toks):
        model = serving.fresh(model)    # its traces record what they select
        assert not model.word_rows and model.paged_decode_impl() == "dsa_xla"
        with pytest.MonkeyPatch.context() as patch:
            got, chosen = run(model, params, toks, Selections(patch))
        cfg, want_rows = wanted_rows()
        want_rows = want_rows[:, :, first:]
        assert chosen.shape == want_rows.shape
        assert (chosen == want_rows).all()
        assert (want_rows.sum(-1)[0, 0] == np.minimum(
            np.arange(first, 48) + 1, cfg.index_topk)).all()
        return got
    return path


def after_serving_params(cfg, model, params, served):
    assert served["layers"]["e_gate"].shape[:2] == (2, HELD[1])
    assert served["layers"]["router"].shape == (2, cfg.dim, 16)
    assert float(jnp.std(served["layers"]["router_bias"])) > 0   # drawn


FAMILY = dataclasses.replace(
    serving.DEEPSEEK_V32, f32_tol=F32_TOL, bf16_rel_rms=BF16_REL_RMS,
    paths={"full_forward": (and_rows(apply_and_rows), 0),
           "prefill_then_paged_decode": (and_rows(paged_decode_and_rows), 0),
           "prefix_prefill": (and_rows(prefix_and_rows, 32), -1)},
    # -- controls: each must fail, against ``apply``'s honest logits
    fault_path="full_forward", faults=reference.FAULTS,
    fault_floors=lambda fault: (0.05, 100 * F32_TOL),
    scopes={
        "forward_step": (("mla_q_down", "mla_q_up", "dsa_indexer_q",
                          "dsa_indexer_k", "dsa_indexer_scores",
                          "dsa_select", "dsa_masked_attention",
                          "mla_kv_down", "mla_kv_up", "moe_group_limit",
                          "moe_router", "moe_shared_expert",
                          "dense_ffn_leading"), ("mla_q_proj",)),
        "decode_step_paged": (("mla_q_down", "mla_q_up", "dsa_indexer_q",
                               "dsa_indexer_k", "dsa_indexer_scores",
                               "dsa_select", "dsa_attention", "mla_q_absorb",
                               "mla_v_up", "moe_group_limit", "moe_router"),
                              ("mla_q_proj",))},
    f32_leaves=frozenset({"attn_norm", "mlp_norm", "kv_norm", "q_norm",
                          "idx_k_norm", "router", "router_bias"}),
    after_serving_params=after_serving_params)
ref_forward = functools.partial(serving.reference, FAMILY)


make = functools.partial(serving.make, FAMILY)
globals().update(serving.cases_of(FAMILY))


def make_word_rows(dtype=jnp.bfloat16, **kw):
    """Widths that fill lane tiles: the row is held as words and the
    Mosaic kernels (interpreted here) read it."""
    return make(dtype, kv_lora_rank=256, index_head_dim=128, **kw)


def bf16_full_forward(model, params, toks, sel):
    logits, experts = serving.bf16_full_forward(model, params, toks)
    return logits, experts, sel.prefill(model.cfg.n_layers)


def bf16_paged_decode_from_empty(model, params, toks, sel):
    """Every position by a paged decode step, with the experts and the
    rows each step chose."""
    L, total = model.cfg.n_layers, toks.shape[1]
    chosen = []
    logits, experts = serving.paged_decode_from_empty(
        model, params, toks,
        seen=lambda kind: chosen.append(sel.decode(L, total)[:, :, None]))
    return logits, experts, np.concatenate(chosen, 2)


@pytest.mark.parametrize("seed", [1])
@pytest.mark.parametrize("path,impl", [("full_forward", None),
                                       ("paged_decode", "xla"),
                                       ("paged_decode", "pallas")])
def test_bf16_compute_with_the_reference_forced_to_its_experts_and_rows(
        path, impl, seed, monkeypatch):
    """Rows held as words; the decode step's kernels interpreted."""
    cfg, model, params = make_word_rows(seed=seed)
    model = model_for(dataclasses.replace(cfg, decode_attention=impl))
    if impl is not None:
        assert model.word_rows
        assert model.paged_decode_impl() == "dsa_" + impl
    toks = seqs(cfg, (2, 40), seed=seed)
    sel = Selections(monkeypatch)
    run = bf16_full_forward if path == "full_forward" \
        else bf16_paged_decode_from_empty
    got, experts, chosen = run(model, model.serving_params(params), toks, sel)
    want = ref_forward(cfg, params, toks, forced_experts=experts,
                       forced_selection=jnp.asarray(chosen))
    assert rel_rms(got, want) < BF16_REL_RMS
    # free, the same logits stand far off: both kinds of near-tie swap
    assert (chosen.sum(-1)[0, 0] == np.minimum(np.arange(40) + 1, 12)).all()


def test_bf16_word_rows_hold_what_the_plain_rows_hold():
    """The same prefill through the word-row cache and read back: the
    packed rows unpack to the bf16 values bit for bit. The row is ONE run
    of sub-rows under "k" (``k_pe | k_I``'s, then ``c``'s) and "v" holds
    nothing."""
    cfg, model, params = make_word_rows()
    assert model.kv_row_shapes() == ((2, 128), (0,))
    assert model.kv_dtype == jnp.uint32
    x = jax.random.normal(jax.random.key(0), (3, 5, 256), jnp.bfloat16)
    assert bool(jnp.all(dsa.unpack_words(dsa.pack_words(x)) == x))
    # the three parts through the row and back, and where each lies
    c, pe, ki = (jax.random.normal(jax.random.key(i), (2, 7, n), jnp.bfloat16)
                 for i, n in enumerate((256, 128, 128)))
    k_rows, v_rows = model._rows_of(c, pe, ki)
    assert k_rows.shape == (2, 7, 2, 128) and v_rows.shape == (2, 7, 0)
    assert k_rows.dtype == v_rows.dtype == jnp.uint32
    for got, want in zip(model._row_parts(k_rows, v_rows), (c, pe, ki)):
        assert got.dtype == want.dtype and bool(jnp.all(got == want))
    assert bool(jnp.all(k_rows[..., 0, :64] == dsa.pack_words(pe)))
    assert bool(jnp.all(k_rows[..., 0, 64:] == dsa.pack_words(ki)))
    assert bool(jnp.all(k_rows[..., 1, :] == dsa.pack_words(c)))
    served = model.serving_params(params)
    toks = seqs(cfg, (1, 16))
    cache = model.init_kv_cache(1, 16)
    assert cache["k"].shape == (3, 1, 16, 2, 128)
    assert cache["v"].shape == (3, 1, 16, 0)
    _, cache = model.forward_step(served, toks, cache, jnp.zeros((1,), I32))
    assert cache["k"].dtype == cache["v"].dtype == jnp.uint32
    c, k_pe, k_idx = model._row_parts(cache["k"], cache["v"])
    assert c.shape == (3, 1, 16, 256) and k_idx.shape == (3, 1, 16, 128)
    assert k_pe.shape == (3, 1, 16, 128)
    assert float(jnp.abs(c.astype(jnp.float32)).min(-1).max()) > 0.0
    assert float(jnp.abs(k_pe[..., cfg.qk_rope_head_dim:]).max()) == 0.0
    # an index key the words' last sub-row cannot hold: the plain rows
    plain = model_for(dataclasses.replace(cfg, index_head_dim=64))
    assert not plain.word_rows
    assert plain.kv_row_shapes() == ((256,), (128 + 64,))


def test_the_published_row_is_1536_bytes_and_unpadded():
    """PR 44: the row is 2,048 bytes, of which 1,536 are held. What the
    sparse attention reads of a token (``k_pe``, the low halves of 32
    words, and ``c``, 256 words) and the index key (64 words) are ONE
    contiguous run of sub-rows of 128 words under "k": ``k_pe | k_I``,
    ``c``'s two, and ONE SPARE sub-row of zeros; "v" is a zero-width row.
    Why: the kernel then fetches a selected row with one copy where PR 43
    (``c`` [2, 128] under "k", ``k_pe | k_I`` [128] under "v") needed two,
    and a copy costs ~17 ns to start whatever its bytes. Why the spare:
    XLA lays ``[..., n, 128]`` uint32 out token by token, in tiles of (n,
    128), only where ``n`` is a power of two; three sub-rows it holds
    sub-row-major as a parameter and pads to four for the decode step's
    scatter, copying the whole pool twice a step (PERF.md section 7). The
    name is the test's since PR 43; the row it names is what stands
    here."""
    from benchmark import run as harness
    pub = harness.load_json(harness.ROOT,
                            "benchmark/configs/deepseek-v3.2-d5.json")
    model = builder.build_model(pub, 64)
    assert model.word_rows
    assert model.kv_row_shapes() == ((4, 128), (0,))
    pool = jax.eval_shape(lambda: model.init_kv_pool(4, 32))
    assert pool["k"].shape == (5, 4, 32, 4, 128)
    assert pool["v"].shape == (5, 4, 32, 0)
    assert pool["k"].dtype == pool["v"].dtype == jnp.uint32
    assert sum(a.size * 4 for a in pool.values()) == 5 * 4 * 32 * 2048
    # the spare sub-row holds zeros and nothing reads it
    k_rows, _ = model._rows_of(*(jnp.ones((2, n), jnp.bfloat16)
                                 for n in (512, 128, 128)))
    assert k_rows.shape == (2, 4, 128)
    assert bool(jnp.all(k_rows[:, :3] != 0) & jnp.all(k_rows[:, 3] == 0))
    assert [dsa.word_row_subrows(r) for r in (256, 512, 768, 1024)] \
        == [2, 4, 4, 8]
    assert model.cfg.num_params() == pub["parameters"] == 4_635_518_208
    assert model.cfg.softmax_scale == pytest.approx(0.13523, abs=1e-5)


# -- the selection ----------------------------------------------------------
@pytest.mark.parametrize("k", [1, 5, 12])
def test_topk_mask_is_exact_and_ties_go_to_the_earlier_row(k):
    rng = np.random.default_rng(k)
    # few distinct values: ties everywhere, the k-th place among them
    scores = jnp.asarray(rng.integers(-2, 3, (3, 7, 40)), jnp.float32)
    seen = jnp.asarray(rng.random((3, 7, 40)) < 0.7)
    got = np.asarray(dsa.topk_mask(scores, seen, k))
    _, rows = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(rows), True, axis=-1)
    want &= np.asarray(seen)
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(np.asarray(seen).sum(-1), k)).all()
    assert (np.asarray(reference.topk_rows(scores, seen, k)) == got).all()


def test_a_short_slot_selects_every_row_and_no_row_past_its_length():
    scores = jnp.asarray(np.random.default_rng(0).normal(size=(3, 32)),
                         jnp.float32)
    # rows past the length hold anything, even the largest scores
    scores = scores.at[:, 20:].set(100.0)
    lengths = jnp.asarray([1, 7, 20], I32)
    rows, count = dsa.select_topk(scores, lengths, 12)
    assert count.tolist() == [1, 7, 12]
    for b, n in enumerate(count.tolist()):
        assert set(np.asarray(rows[b, :n]).tolist()) <= set(
            range(int(lengths[b])))
    assert set(np.asarray(rows[1, :7]).tolist()) == set(range(7))
    # a table narrower than index_topk: every row at most
    rows, count = dsa.select_topk(scores[:, :8], lengths, 12)
    assert rows.shape == (3, 8) and count.tolist() == [1, 7, 8]


# -- the kernels, interpreted, against their twins --------------------------
def word_pools(seed, B, maxb, bs, R=256, Di=128, rope=16):
    """(keys, the pool of rows as words [NB, bs, sub-rows, 128]: ``k_pe |
    k_I``, ``c``, a spare sub-row at R = 512; block tables)."""
    ks = jax.random.split(jax.random.key(seed), 8)
    NB = B * maxb + 1
    c = jax.random.normal(ks[0], (NB, bs, R), jnp.bfloat16)
    pe = jnp.pad(jax.random.normal(ks[1], (NB, bs, rope), jnp.bfloat16),
                 ((0, 0), (0, 0), (0, 128 - rope)))
    ki = jax.random.normal(ks[2], (NB, bs, Di), jnp.bfloat16)
    sub = dsa.word_row_subrows(R)
    # the spare words hold ANYTHING: nothing may read them
    spare = jax.random.bits(ks[7], (NB, bs, 128 * (sub - 1) - R // 2),
                            jnp.uint32)
    pool = jnp.concatenate(
        [dsa.pack_words(pe), dsa.pack_words(ki), dsa.pack_words(c), spare],
        -1).reshape(NB, bs, sub, 128)
    tables = jnp.asarray(np.random.default_rng(seed).permutation(
        B * maxb).reshape(B, maxb), I32)
    return ks[3:], pool, tables


def unpacked(rows, R=256):
    """(c, k_pe, k_I) of rows of that pool."""
    words = rows.reshape(*rows.shape[:-2], -1)
    return (dsa.unpack_words(words[..., 128:128 + R // 2]),
            dsa.unpack_words(words[..., :64]),
            dsa.unpack_words(words[..., 64:128]))


# ``under_k``: every slot shorter than the 12 rows selected, so the buffer's
# tail is rows that were copied (all 12 copies always start, and the ONE
# wait counts them) and masked
KERNEL_LENGTHS = {"ragged": [1, 17, 48], "one_row": [1, 1, 1],
                  "a_block_edge": [8, 9, 16], "full": [48, 48, 47],
                  "under_k": [5, 11, 7]}


@pytest.mark.parametrize("lengths", sorted(KERNEL_LENGTHS))
@pytest.mark.parametrize("first_block", [0, 19])
def test_kernels_in_interpret_mode_are_their_xla_twins(lengths, first_block):
    B, maxb, bs, H, Hi = 3, 6, 8, 4, 4
    ks, pool, tables = word_pools(7, B, maxb, bs)
    # the pool as one layer's window of a stack that starts elsewhere
    pool = jnp.concatenate([jnp.zeros_like(pool)[:first_block], pool])
    empty = jnp.zeros((*pool.shape[:2], 0), jnp.uint32)        # its "v"
    lens = jnp.asarray(KERNEL_LENGTHS[lengths], I32)
    q_idx = jax.random.normal(ks[0], (B, Hi, 128), jnp.bfloat16)
    w = jax.random.normal(ks[1], (B, Hi), jnp.float32)
    common = dict(first_block=jnp.int32(first_block))
    twin = dsa.indexer_scores(
        q_idx, w, pool, tables, lens, impl="xla",
        key_of=lambda rows: unpacked(rows)[2], **common)
    kernel = dsa.indexer_scores(q_idx, w, pool, tables, lens,
                                impl="pallas", key_of=None, **common)
    live = np.arange(maxb * bs)[None] < np.asarray(lens)[:, None]
    assert ((np.asarray(twin) > -1e29) == live).all()
    assert ((np.asarray(kernel) > -1e29) == live).all()
    np.testing.assert_allclose(np.asarray(kernel)[live],
                               np.asarray(twin)[live], atol=1e-4)

    rows, count = dsa.select_topk(twin, lens, 12)
    assert (np.asarray(count) == np.minimum(np.asarray(lens), 12)).all()
    q_lat = jax.random.normal(ks[2], (B, H, 256), jnp.bfloat16)
    q_pe = jnp.pad(jax.random.normal(ks[3], (B, H, 16), jnp.bfloat16),
                   ((0, 0), (0, 0), (0, 112)))
    outs = [dsa.sparse_decode_attention(
        q_lat, q_pe, pool, empty, tables, rows, count, impl=impl,
        scale=0.1, parts_of=lambda k, v: unpacked(k)[:2], **common
    ).astype(jnp.float32) for impl in ("xla", "pallas")]
    assert float(jnp.abs(outs[0]).max()) > 0.5
    np.testing.assert_allclose(outs[1], outs[0], atol=0.03)


def _parents_selected_kernel(count_ref, rows_ref, q_ref, k_hbm, v_hbm, o_ref,
                             c_buf, v_buf, sems, *, scale, n_sub):
    """PR 43's ``ops/dsa.py:_selected_kernel`` (commit 2ea4753) as it was,
    the ORACLE of the test below: a row is two copies, ``c``'s sub-rows
    out of "k" and the row of "v", each waited for alone."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ray_tpu.ops.attention import NEG_INF

    b = pl.program_id(0)
    n = count_ref[b]
    K = v_buf.shape[0]

    def copies(i, row):
        return (pltpu.make_async_copy(k_hbm.at[row],
                                      c_buf.at[:, pl.ds(i, 1), :],
                                      sems.at[0]),
                pltpu.make_async_copy(v_hbm.at[pl.ds(row, 1), :],
                                      v_buf.at[pl.ds(i, 1), :], sems.at[1]))

    group = next(g for g in (8, 4, 2, 1) if K % g == 0)

    def start(i, carry):
        for j in range(group):
            for copy in copies(i * group + j, rows_ref[b, i * group + j]):
                copy.start()
        return carry

    def wait(i, carry):
        for _ in range(group):
            for copy in copies(0, 0):
                copy.wait()
        return carry

    jax.lax.fori_loop(0, K // group, start, 0)
    jax.lax.fori_loop(0, K // group, wait, 0)

    contract_lanes = (((1,), (1,)), ((), ()))
    planes = [None] * (2 * n_sub)
    for sub in range(n_sub):
        planes[sub], planes[n_sub + sub] = dsa._planes(c_buf[sub])
    s = jnp.zeros((q_ref.shape[2], K), jnp.float32)
    for p, plane in enumerate(planes + [dsa._planes(v_buf[...])[0]]):
        s = s + jax.lax.dot_general(q_ref[0, p], plane, contract_lanes,
                                    preferred_element_type=jnp.float32)
    at = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(at < n, s * scale, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    total = jnp.sum(p, axis=-1, keepdims=True)
    p = p.astype(jnp.bfloat16)
    for i, plane in enumerate(planes):
        out = jax.lax.dot_general(p, plane, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32) / total
        o_ref[0, i] = jnp.where(n > 0, out, 0.0).astype(o_ref.dtype)


def parents_selected_attention(q_lat, q_pe, k_pool, v_pool, flat, count, *,
                               scale):
    """PR 43's ``selected_attention_pallas``, interpreted: ``k_pool`` [NB,
    bs, n_sub, 128], ``v_pool`` [NB, bs, 128]."""
    import functools
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R = q_lat.shape
    K = flat.shape[1]
    n_sub = R // 256
    q_parts = jnp.concatenate([
        jnp.moveaxis(q_lat.reshape(B, H, 2 * n_sub, 128), 2, 1),
        dsa._beside(q_pe[..., :64], 0)[:, None]], axis=1)
    out = pl.pallas_call(
        functools.partial(_parents_selected_kernel, scale=scale, n_sub=n_sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec((1, 2 * n_sub + 1, H, 128),
                                   lambda b, *_: (b, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 2 * n_sub, H, 128),
                                   lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((n_sub, K, 128), jnp.uint32),
                            pltpu.VMEM((K, 128), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, 2 * n_sub, H, 128), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=True,
    )(count.astype(I32), flat.astype(I32), q_parts.astype(jnp.bfloat16),
      k_pool.reshape(-1, n_sub, 1, 128), v_pool.reshape(-1, 128))
    return jnp.moveaxis(out, 1, 2).reshape(B, H, R)


@pytest.mark.parametrize("R", [256, 512])
def test_the_kernels_output_is_bit_equal_to_the_parents(R):
    """The same rows, the same bf16 operands, the same dots in the same
    order: fetching a row with one copy and waiting once a buffer moves
    bytes and nothing else, so no tolerance enters. The parent's pools are
    RE-LAID from the new one: ``c``'s sub-rows under "k", the keys'
    sub-row under "v"; at R = 512 the new row has a spare sub-row of
    arbitrary bits that must not be read."""
    B, maxb, bs, H, K = 3, 6, 8, 4, 16
    ks, pool, _ = word_pools(11, B, maxb, bs, R=R)
    rng = np.random.default_rng(R)
    flat = jnp.asarray(np.stack([rng.choice(pool.shape[0] * bs, K,
                                            replace=False)
                                 for _ in range(B)]), I32)
    count = jnp.asarray([K, 9, 0], I32)     # full, count < K, an idle slot
    q_lat = jax.random.normal(ks[0], (B, H, R), jnp.bfloat16)
    q_pe = jnp.pad(jax.random.normal(ks[1], (B, H, 16), jnp.bfloat16),
                   ((0, 0), (0, 0), (0, 112)))
    got = dsa.selected_attention_pallas(q_lat, q_pe, pool, flat, count,
                                        scale=0.1, interpret=True)
    want = parents_selected_attention(
        q_lat, q_pe, pool[:, :, 1:1 + R // 256], pool[:, :, 0], flat, count,
        scale=0.1)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0.5
    assert bool(jnp.all(want[2] == 0))
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))


def _dma_counts(jaxpr, in_loop=False):
    """{(primitive, inside a loop?): occurrences, a loop's times its
    trips} of the DMA primitives of a jaxpr and everything under it."""
    counts = collections.Counter()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("dma_start", "dma_wait"):
            counts[name, in_loop] += 1
        loop = name in ("scan", "while")
        trips = eqn.params.get("length", 1) if loop else 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            for key, n in _dma_counts(sub, in_loop or loop).items():
                counts[key] += n * trips
    return dict(counts)


def test_the_kernel_starts_one_copy_a_row_and_waits_once_a_buffer():
    """The counter of PR 44's mechanism, read off the PROGRAM (it does not
    depend on the traffic): the start loop holds ONE ``dma_start`` a row (K
    in all a slot; PR 43: two) and no ``dma_wait`` stands in a loop over
    rows: one wait a slot, on the whole buffer."""
    B, H, R, K = 2, 4, 512, 48
    u32 = jnp.uint32
    jaxpr = jax.make_jaxpr(
        lambda *a: dsa.selected_attention_pallas(*a, scale=0.1,
                                                 interpret=False))(
        jnp.zeros((B, H, R), jnp.bfloat16), jnp.zeros((B, H, 128),
                                                      jnp.bfloat16),
        jnp.zeros((5, 8, 4, 128), u32), jnp.zeros((B, K), I32),
        jnp.zeros((B,), I32))

    def kernels(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["jaxpr"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from kernels(sub)

    (kernel,) = kernels(jaxpr.jaxpr)
    assert _dma_counts(kernel) == {("dma_start", True): K,
                                   ("dma_wait", False): 1}


def test_the_row_copy_bench_rehearses_on_the_cpu(capsys):
    """``tools/dsa_row_copy_bench.py --tiny-cpu``: every copy-only variant
    (two copies a row or one, a wait a row or a buffer, a bound on the
    copies in flight, a page of index keys as one run or as a strided
    copy) moves the pool's own rows under the interpreter; no time is
    read off a CPU."""
    import json

    from tools import dsa_row_copy_bench as bench
    assert bench.main(["--tiny-cpu"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(out["gather"]) == set(bench.GATHERS)
    assert set(out["pages"]) == set(bench.PAGES)
    readings = {**out["gather"], **out["pages"]}
    assert all(r["ms"] is None and "error" not in r
               for r in readings.values())
    assert out["gather"]["a"]["copies_a_row"] == 2
    assert out["gather"]["c3"] == {
        "bytes_a_row": 1536, "copies_a_row": 1, "wait": "buffer",
        "in_flight": out["shape"]["rows_a_slot"], "ms": None}
    assert out["pages"]["p_sub3"]["runs_a_page"] == out["shape"]["block"]


def test_the_resolver_names_what_runs(monkeypatch):
    from ray_tpu.ops import paged_attention
    cfg, model, _ = make()
    assert model.paged_decode_impl() == "dsa_xla"
    plan = model.sparse_decode_plan()
    assert plan == {"index_topk": 12, "kv_index_row_bytes": 64,
                    "decode_indexer_impl": "dsa_indexer_xla",
                    "decode_select_impl": "xla_top_k"}
    wide = make_word_rows()[1]
    monkeypatch.setattr(paged_attention, "on_chip", lambda: True)
    assert wide.paged_decode_impl() == "dsa_pallas"
    assert wide.sparse_decode_plan()["decode_indexer_impl"] \
        == "dsa_indexer_pallas"
    # a row that is not words has no kernel to read it, whatever is forced
    forced = model_for(dataclasses.replace(cfg, decode_attention="pallas"))
    assert forced.paged_decode_impl() == "dsa_xla"
    # kanana's fields: no indexer, the dense kernel's names
    plain = model_for(MLAConfig.debug_kanana())
    assert plain.paged_decode_impl() == "mla_pallas"
    assert plain.sparse_decode_plan()["index_topk"] == 0


# -- the router and the share -----------------------------------------------
def test_route_topk_is_the_references_group_limited_choice():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.3, jnp.float32)
    _, scores, weights, experts = route_topk(
        x, router, 4, True, sigmoid_bias=bias, weight_scale=2.5,
        groups=(4, 2))
    want = reference.grouped_sigmoid_topk(
        scores, bias, top_k=4, n_group=4, topk_group=2)
    assert (np.sort(np.asarray(experts)) == np.sort(np.asarray(want))).all()
    # at most 2 of the 4 groups of 4 neighbours, and the limit bites
    assert (np.asarray([len(set(row // 4)) for row in np.asarray(experts)])
            <= 2).all()
    free = route_topk(x, router, 4, True, sigmoid_bias=bias)[3]
    assert (np.sort(np.asarray(free)) != np.sort(np.asarray(experts))).any()
    # the weights are s of the chosen (not s + b), renormalised, x 2.5
    s = np.take_along_axis(np.asarray(scores), np.asarray(experts), -1)
    np.testing.assert_allclose(weights, 2.5 * s / s.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: the routed parts that the shares (0, 4),
    (4, 4), (8, 4), (12, 4) compute, plus the shared expert ONCE, are the
    uncut layer's output, by the program and by the reference."""
    cfg, whole, params = make(held=(0, 16))
    layer = {k: v[0] for k, v in params["layers"].items()}
    h = jax.random.normal(jax.random.key(9), (2, 24, cfg.dim), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, extra = whole._ffn(h, layer)
        total, ref_total = jnp.zeros_like(uncut), jnp.zeros_like(uncut)
        for first in (0, 4, 8, 12):
            part = model_for(dataclasses.replace(
                cfg, first_expert_held=first, experts_held=4))
            cut = {**layer, **{n: layer[n][first:first + 4]
                               for n in ("e_gate", "e_up", "e_down")}}
            out, ex = part._ffn(h, cut)
            assert (np.asarray(ex["experts"])
                    == np.asarray(extra["experts"])).all()
            total = total + out
            ref_out, _ = reference._expert_block(
                h, cut, top_k=4, n_group=4, topk_group=2,
                norm_topk_prob=True, routed_scale=2.5, held=(first, 4),
                forced=None, fault=None)
            ref_total = ref_total + ref_out
        # every share added the shared expert: count it once
        dense = reference._swiglu(h, layer["s_gate"], layer["s_up"],
                                  layer["s_down"])
    np.testing.assert_allclose(total - 3 * dense, uncut, atol=1e-4)
    np.testing.assert_allclose(ref_total - 3 * dense, uncut, atol=1e-4)
    # a token none of whose experts is held gets the shared expert alone
    part = model_for(dataclasses.replace(cfg, first_expert_held=0,
                                         experts_held=4))
    cut = {**layer, **{n: layer[n][:4]
                       for n in ("e_gate", "e_up", "e_down")}}
    with jax.default_matmul_precision("highest"):
        out, ex = part._ffn(h, cut)
    none_held = np.asarray((ex["experts"] >= 4).all(-1))
    assert none_held.any() and not none_held.all()
    np.testing.assert_allclose(np.asarray(out)[none_held],
                               np.asarray(dense)[none_held], atol=1e-4)
    assert int(ex["load"].sum()) == 2 * 24 * 4      # every choice counted


def test_the_config_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="indexer"):
        MLAConfig.debug_deepseek_v32(q_lora_rank=None)
    with pytest.raises(ValueError, match="group limit"):
        MLAConfig.debug_deepseek_v32(router_n_group=3)
    with pytest.raises(ValueError, match="group limit"):
        MLAConfig.debug_deepseek_v32(router_topk_group=1, expert_top_k=6)
    with pytest.raises(ValueError, match="router's"):
        MLAConfig.debug_deepseek_v32(first_expert_held=12, experts_held=8)
    from ray_tpu.ops.rope import YarnScaling
    with pytest.raises(ValueError, match="yarn"):
        MLAConfig.debug_deepseek_v32(rope_scaling=(
            ("full_attention", YarnScaling(8.0, 16)),))
