"""kanana-2-30b-a3b's block on the serving path, at debug widths with
seeded weights, against ``benchmark/reference/kanana.py``: latent
attention (a cache row of ``c`` and one rotary key part, EXPANDED in the
prefills and ABSORBED in the decode step), a sigmoid router with a drawn
selection bias, the shared experts, a leading dense layer of another
parameter tree.

As for OLMoE (``test_olmoe_serving.py``) the hazard under bf16 compute is
the router's near-ties, so the system is compared in float32 compute,
where nothing swaps, to 1e-4, and in bf16 with the reference FORCED to
the system's routing at the dense models' bf16 tolerance.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import kanana as builder
from benchmark.reference import kanana as reference
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models import MLAConfig, MLAModel, model_for
from ray_tpu.ops import mla_attention
from tests import serving_family as serving
from tests.program_readers import scans
from tests.serving_family import I32, seqs

F32_TOL = 1e-4          # max |logit difference|, logits of RMS ~1


def ref_kwargs(cfg):
    return dict(qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
                rms_norm_eps=cfg.norm_eps, top_k=cfg.expert_top_k,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob)


def plain_reference(cfg, params, tokens, **kw):
    return reference.forward(
        builder.reference_params({"tie_word_embeddings": False}, params),
        tokens, **ref_kwargs(cfg), **kw)


def kernel_path(model, params, toks):
    assert serving.forced(model, "pallas").paged_decode_impl() == "mla_pallas"
    return serving.paged_decode_with_the_kernel(model, params, toks)


def after_serving_params(cfg, model, params, served):
    assert served["lm_head"].dtype == jnp.bfloat16
    assert "router" not in served["leading_layers"]
    assert float(jnp.std(served["layers"]["router_bias"])) > 0   # drawn


def engine_stats(eng, stats, cfg, model):
    """The expert FFN processed exactly what a dropless FFN must, over the
    EXPERT layers alone; the cache holds the ONE latent row."""
    assert stats["moe_router_kind"] == "sigmoid"
    assert eng.decode_attention_impl == "mla_xla"
    assert stats["kv_row_bytes"] == 4 * (cfg.kv_lora_rank + model.pe_lanes)
    assert eng.kv["k"].shape[2:] == (8, cfg.kv_lora_rank + model.pe_lanes)
    assert eng.kv["v"].shape[2:] == (8, 0)
    assert stats["kv_pool_bytes"] == sum(a.nbytes for a in eng.kv.values())
    assert stats["moe_assignments"] == stats["moe_assignments_expected"] > 0
    load = np.asarray(stats["moe_expert_load"])
    assert load.shape == (cfg.n_layers - cfg.leading_layers, cfg.num_experts)
    assert load.sum() == stats["moe_assignments"]
    assert len(set(load.sum(1).tolist())) == 1


FAMILY = serving.Family(
    config=MLAConfig.debug_kanana, reference=plain_reference,
    seeded=serving.drawn(("kv_norm", "attn_norm", "mlp_norm"), 2048,
                         ("layers", "leading_layers")),
    f32_tol=F32_TOL,
    # bf16 compute against the float32 reference forced to the system's
    # routing, relative RMS of the logits: the dense block's floor at debug
    # widths reads 0.016 (benchmark/tests/test_references.py); the readings
    # here are 0.011-0.014 over the seeds below
    bf16_rel_rms=0.02,
    # the prefills EXPANDED, the decode steps ABSORBED, the suffix prefill
    # over a cached prefix of latent rows
    paths={"full_forward": (serving.full_forward, 0),
           "prefill_then_paged_decode": (serving.prefill_then_paged_decode,
                                         0),
           "paged_decode_with_the_kernel": (kernel_path, 0),
           "prefix_prefill": (serving.prefix_prefill, -1)},
    # as it is (``kv_lora_rank`` 32): the debug row, ``c | k_pe`` as they
    # are; 128: a latent width that fills a lane tile, so ``k_pe`` is
    # zero-padded to ``PE_LANES`` in the cache, the layout of the published
    # widths (512 + 128) that the cell times
    f32_overrides=({}, {"kv_lora_rank": 128}),
    bf16_paths={"full_forward": serving.bf16_full_forward,
                "paged_decode": serving.paged_decode_from_empty},
    bf16_cases=tuple((path, seed) for path in ("full_forward",
                                               "paged_decode")
                     for seed in (1, 2, 3)),
    faults=tuple(f for f in reference.FAULTS if f != "skip_last_layer"),
    fault_floors=lambda fault: (0.03, 100 * F32_TOL),
    scopes={
        "forward_step": (("mla_q_proj", "mla_kv_down", "mla_kv_up",
                          "mla_attention", "moe_shared_expert",
                          "dense_ffn_leading", "moe_router"), ()),
        "decode_step_paged": (("mla_q_proj", "mla_kv_down", "mla_q_absorb",
                               "mla_attention", "mla_v_up",
                               "moe_shared_expert", "dense_ffn_leading",
                               "moe_router"), ("mla_kv_up",))},
    f32_leaves=frozenset({"attn_norm", "mlp_norm", "kv_norm", "router",
                          "router_bias"}),
    after_serving_params=after_serving_params,
    # at the published widths' layout, k_pe padded to a lane tile (an
    # engine of its own): the second request's prefill gathers the first's
    # rows and decodes over them: insert, prefix gather, decode over the
    # ONE row (the other cases insert, gather and decode the 48-lane row)
    engine_cases=serving.engine_cases(prefix_prefill=(
        "shared", 6, "prefix_prefills", {}, {"kv_lora_rank": 128})),
    engine_stats=engine_stats)


make = functools.partial(serving.make, FAMILY)


globals().update(serving.cases_of(FAMILY))


def test_absorbed_and_expanded_forms_agree_on_the_same_rows():
    """One layer's attention over the same latent rows both ways: the
    decode step's absorbed product through the pool against the
    prefill's up-projection of every row."""
    cfg, model, params = make()
    layer = {k: v[0] for k, v in params["layers"].items()}
    B, S, bs = 2, 24, 8
    key = jax.random.split(jax.random.key(3), 3)
    h = jax.random.normal(key[0], (B, S, cfg.dim))
    with jax.default_matmul_precision("highest"):
        q, k_rows, v_rows = model._qkv(h, layer, None, None,
                                       lambda a, *_: a)
        expanded = model._attend_rows(q, k_rows, v_rows, layer,
                                      jnp.arange(S), jnp.arange(S))[:, -1]
        pool = (k_rows.reshape(B * S // bs, bs, k_rows.shape[-1]),
                v_rows.reshape(B * S // bs, bs, 0))
        tables = jnp.arange(B * S // bs, dtype=I32).reshape(B, -1)
        for impl in ("mla_xla", "mla_pallas"):
            absorbed = model._attend_pages(
                q[:, -1], *pool, layer, tables, jnp.full((B,), S, I32),
                impl=impl)
            np.testing.assert_allclose(absorbed, expanded, atol=1e-5)


def test_a_long_prefill_scores_a_block_of_queries_at_a_time(monkeypatch):
    """``_attend_rows`` over more queries than ``QUERY_BLOCK``: the same
    rows out, by the largest block that divides them (24 -> 8)."""
    from ray_tpu.models import mla
    cfg, model, params = make()
    model = serving.fresh(model)
    toks = seqs(cfg)
    cache = model.init_kv_cache(2, 24)
    offsets = jnp.asarray([0, 0], I32)
    with jax.default_matmul_precision("highest"):
        whole, rows = model.forward_step(params, toks, cache, offsets)
        trained = model.apply(params, toks)
        monkeypatch.setattr(mla, "QUERY_BLOCK", 9)
        blocked = jax.make_jaxpr(model.forward_step)(params, toks, cache,
                                                     offsets)
        assert "8,24]" in str(blocked) and "24,24]" not in str(blocked)
        got, got_rows = model.forward_step(params, toks, cache, offsets)
        # training hands no positions: 0..T-1
        np.testing.assert_allclose(model.apply(params, toks), trained,
                                   atol=1e-5)
    np.testing.assert_allclose(got, whole, atol=1e-5)
    np.testing.assert_allclose(got_rows["k"], rows["k"], atol=1e-6)


def test_rows_of_logits_are_the_logits_rows():
    cfg, model, params = make()
    toks = seqs(cfg)
    rows = reference.forward_rows(
        builder.reference_params({"tie_word_embeddings": False}, params),
        toks, **ref_kwargs(cfg))
    want = plain_reference(cfg, params, toks)       # op by op, as ``rows``
    np.testing.assert_allclose(rows[:, 5:9], want[:, 5:9], atol=1e-6)
    np.testing.assert_allclose(rows[0, 20:], want[0, 20:], atol=1e-6)
    # through jit, as the harness returns it
    jitted = jax.jit(lambda p, t: reference.forward_rows(
        builder.reference_params({"tie_word_embeddings": False}, p), t,
        **ref_kwargs(cfg)))(params, toks)
    np.testing.assert_allclose(jitted[:, 5:9], want[:, 5:9], atol=1e-5)


# -- the kernel against its twin -----------------------------------------------
# 2 pages a chunk: a slot of 37 rows walks three chunks (the third of ONE
# live page: the one wait must count that page's bytes alone), 64 rows four
# full ones, 17 two (the second of one page of two), 0 rows none
@pytest.mark.parametrize("lengths,first_block", [
    ((1, 8, 9, 37), 40), ((64, 63, 17, 0), 40), ((5, 5, 5, 5), 40),
    ((33, 16, 0, 41), 0), ((24, 40, 56, 48), 40)],
    ids=["one_row_and_block_edges", "full_table_and_empty", "same",
         "first_block_0_and_odd_last_chunks", "whole_pages_whole_chunks"])
def test_kernel_in_interpret_mode_is_its_xla_twin(lengths, first_block,
                                                  monkeypatch):
    """Ragged lengths, a slot with one row, slots at a block's edge and one
    past it, a slot with none, last chunks with dead pages; the table's
    entries past a slot's length point at garbage (a block of NaNs) or out
    of the layer's window."""
    monkeypatch.setattr(mla_attention, "CHUNK_ROWS", 16)    # 2 pages a chunk
    B, H, R, P, bs, maxb, NB = 4, 4, 32, 16, 8, 8, 40
    k = jax.random.split(jax.random.key(0), 3)
    pool = jax.random.normal(k[0], (2 * NB, bs, R + P))
    garbage = first_block + 39                   # the layer's garbage block
    q_lat = jax.random.normal(k[1], (B, H, R))
    q_pe = jax.random.normal(k[2], (B, H, P))
    lengths = jnp.asarray(lengths, I32)
    live = -(-lengths // bs)
    tables = np.random.default_rng(1).permutation(39)[:B * maxb].reshape(
        B, maxb)
    tables = jnp.where(jnp.arange(maxb)[None] < live[:, None], tables, 39)
    got = mla_attention.mla_decode_attention(
        q_lat, q_pe, pool.at[garbage].set(jnp.nan), tables.astype(I32),
        lengths, impl="pallas", scale=0.2, first_block=first_block)
    # the twin on the live rows alone (it gathers whole tables, garbage
    # and all, and a NaN row it masks is still 0 * NaN in its value dot)
    want = mla_attention.mla_decode_attention(
        q_lat, q_pe, pool.at[garbage].set(0.0), tables.astype(I32), lengths,
        impl="xla", scale=0.2, first_block=first_block)
    want = jnp.where(lengths[:, None, None] > 0, want, 0.0)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_kernel_starts_one_copy_a_page_and_waits_by_powers_of_two(
        monkeypatch):
    """The mechanism, read off the program: a chunk of 8 pages is 8
    ``dma_start`` (one a page: ``c | k_pe`` is one pool row) where it is
    started (the call's first chunk, the slot's next, the next slot's
    first), and its live pages are waited for by 8 + 4 + 2 + 1: four
    ``dma_wait``, of which a full chunk takes one."""
    B, H, R, P, bs, maxb = 2, 4, 128, 128, 8, 16
    monkeypatch.setattr(mla_attention, "CHUNK_ROWS", 8 * bs)
    program = str(jax.make_jaxpr(
        lambda *a: mla_attention.mla_decode_attention_pallas.__wrapped__(
            *a, scale=0.1, interpret=True))(
        jnp.zeros((B, H, R)), jnp.zeros((B, H, P)),
        jnp.zeros((40, bs, R + P)), jnp.zeros((B, maxb), I32),
        jnp.zeros((B,), I32)))
    assert program.count("dma_start") == 3 * 8
    assert program.count("dma_wait") == 4


def test_the_resolver_names_the_implementation(monkeypatch):
    cfg = MLAConfig.debug_kanana()
    assert model_for(cfg).paged_decode_impl() == "mla_xla"      # the CPU
    from ray_tpu.ops import paged_attention
    monkeypatch.setattr(paged_attention, "on_chip", lambda: True)
    assert model_for(cfg).paged_decode_impl() == "mla_pallas"
    forced = dataclasses.replace(cfg, decode_attention="xla")
    assert model_for(forced).paged_decode_impl() == "mla_xla"


# -- the cache row, the parameters and the programs ------------------------------
def test_cache_rows_have_no_head_axis_and_count_every_layer():
    cfg, model, _ = make()
    cache = model.init_kv_cache(2, 16)
    pool = model.init_kv_pool(5, 8)
    row = cfg.kv_lora_rank + cfg.qk_rope_head_dim     # ONE row: c | k_pe
    assert cache["k"].shape == (3, 2, 16, row)
    assert cache["v"].shape == (3, 2, 16, 0)
    assert pool["k"].shape == (3, 5, 8, row)
    assert pool["v"].shape == (3, 5, 8, 0)
    # at widths the kernel copies as lane tiles the rotary part is padded
    # to one, and still no head axis
    wide = model_for(dataclasses.replace(cfg, kv_lora_rank=128))
    assert wide.kv_row_shapes() == ((128 + mla_attention.PE_LANES,), (0,))


@pytest.mark.parametrize("kv_lora_rank", [32, 128])
def test_rows_of_and_row_parts_are_inverses(kv_lora_rank):
    cfg = MLAConfig.debug_kanana(kv_lora_rank=kv_lora_rank)
    model = model_for(cfg)
    assert model.kv_row_shapes() == ((
        kv_lora_rank + (mla_attention.PE_LANES if kv_lora_rank == 128
                        else cfg.qk_rope_head_dim),), (0,))
    k = jax.random.split(jax.random.key(3), 2)
    c = jax.random.normal(k[0], (2, 5, kv_lora_rank))
    k_pe = jnp.pad(jax.random.normal(k[1], (2, 5, cfg.qk_rope_head_dim)),
                   ((0, 0), (0, 0),
                    (0, model.pe_lanes - cfg.qk_rope_head_dim)))
    k_rows, v_rows = model._rows_of(c, k_pe)
    assert (k_rows.shape[2:], v_rows.shape[2:]) == model.kv_row_shapes()
    got_c, got_pe, none = model._row_parts(k_rows, v_rows)
    assert none is None
    np.testing.assert_array_equal(got_c, c)
    np.testing.assert_array_equal(got_pe, k_pe)


def test_num_params_counts_what_init_makes_and_the_published_sizes():
    cfg, _, params = make()
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    from benchmark import run as harness
    pub = harness.load_json(harness.ROOT,
                            "benchmark/configs/kanana-2-30b-a3b-d5.json")
    at = lambda n: builder.program_config(
        {**pub, "num_hidden_layers": n}, 128).num_params()
    assert at(5) == pub["parameters"] == 3_149_554_688
    assert at(48) == 30_670_815_104


@pytest.mark.parametrize("method", ["forward_step", "decode_step_paged",
                                    "prefill_with_prefix"])
def test_each_serving_program_is_two_layer_scans_and_no_weight_sized_copy(
        method):
    """The leading dense stack is scanned first, then the expert stack,
    by the one layer body; the expert stacks are closed over whole (no
    ``dynamic_slice`` reads a stack), as in ``test_moe_whole_stacks``."""
    cfg, model, params = make(jnp.bfloat16)
    params = model.serving_params(params)
    toks = jnp.ones((2, 16), I32)
    two = jnp.zeros((2,), I32)
    pool = model.init_kv_pool(9, 8)
    prefix = {n: jnp.zeros((cfg.n_layers, 2, 8) + a.shape[3:], a.dtype)
              for n, a in pool.items()}
    args = {"forward_step": (params, toks, model.init_kv_cache(2, 16), two),
            "decode_step_paged": (params, two, pool, jnp.zeros((2, 4), I32),
                                  two),
            "prefill_with_prefix": (params, toks, prefix["k"], prefix["v"],
                                    two + 8, two + 16)}[method]
    jaxpr = jax.make_jaxpr(getattr(model, method))(*args).jaxpr
    lengths = sorted(e.params["length"] for e in scans(jaxpr)
                     if e.params["length"] in (1, 2))
    assert lengths == [1, 2]
    sliced = set()

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "dynamic_slice":
                sliced.add(tuple(eqn.invars[0].aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    layers = params["layers"]
    for name in model.WHOLE_LAYER_LEAVES:
        shape = tuple(layers[name].shape)
        assert shape not in sliced and (shape[0] * shape[1],) + shape[2:] \
            not in sliced, name


def test_a_dense_models_engine_reports_its_row_and_no_router():
    from ray_tpu.models import LlamaConfig
    model = model_for(LlamaConfig.debug())
    eng = ContinuousBatchingEngine(
        model, model.init(jax.random.key(0)), max_slots=2, max_seq=32,
        prefill_buckets=(8, 16), block_size=8)
    assert eng.stats["moe_router_kind"] == ""
    assert eng.stats["kv_row_bytes"] == 2 * 2 * 16 * 2       # K and V, bf16
    assert eng.stats["kv_pool_bytes"] == 2 * eng.kv["k"].nbytes


def test_the_builder_refuses_what_the_model_has_not():
    from benchmark import run as harness
    pub = harness.load_json(harness.ROOT,
                            "benchmark/configs/kanana-2-30b-a3b-d5.json")
    assert isinstance(builder.build_model({**pub, **pub["tiny_cpu"]}, 64),
                      MLAModel)
    for key, other in (("q_lora_rank", 1536), ("n_group", 8),
                       ("rope_scaling", {"type": "yarn"}),
                       ("scoring_func", "softmax")):
        with pytest.raises(ValueError, match=key):
            builder.program_config({**pub, key: other}, 64)


@pytest.mark.parametrize("fault", [False, True])
def test_the_timed_path_check_holds_layer_one_rows_and_sees_a_fault(
        fault, capsys):
    """``tools/mla_timed_path_check.py`` at debug widths: an engine's own
    chunked prefills and decode steps leave layer 1's latent rows where
    the reference's float32 arithmetic puts them, and two planted faults
    move them far off (the cell's ``correct`` cannot say either per run:
    PERF.md section 7)."""
    from tools import mla_timed_path_check
    assert mla_timed_path_check.main(
        ["--tiny-cpu"] + ["--fault"] * fault) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["fault"] is fault and out["decode_steps"] >= 5
    assert (out["worst"] > 0.3) if fault else (out["worst"] < 1e-4)
