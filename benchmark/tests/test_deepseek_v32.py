"""The DeepSeek-V3.2 configuration's pieces: the cost model's arithmetic
against ISSUE 43's numbers, the builder's mapping of the published keys,
the configuration against the catalog, the reference against the
builder's model through the cell's own check, the new metrics' readers."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import deepseek_v32 as builder
from benchmark.costs import dsa_moe_transformer as costs
from benchmark.lib import serving
from benchmark.reference import deepseek_v32 as reference

CFG = harness.load_json(harness.ROOT,
                        "benchmark/configs/deepseek-v3.2-d5.json")
TINY = {**CFG, **CFG["tiny_cpu"]}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "deepseek-v3.2-d5.long_decode_dsa"
TRAFFIC = harness.load_json(harness.HERE, "traffic", "long_decode_dsa.json")


def test_parameter_counts_at_the_cut_and_of_the_whole_model():
    assert costs.latent_path_params(CFG) == 187_107_328
    assert costs.indexer_params(CFG) == 13_959_424
    assert costs.attention_params(CFG) == 201_066_752
    assert 16 * costs.expert_params(CFG) == 704_643_072
    assert costs.shared_params(CFG) == 44_040_192
    assert costs.router_params(CFG) == 1_835_264
    assert 2 * CFG["vocab_size"] * CFG["hidden_size"] == 231_669_760
    one = costs.total_params(dict(CFG, num_hidden_layers=1))
    assert one - 231_669_760 - 7168 == 597_442_816          # the dense layer
    assert (costs.total_params(CFG)
            - costs.total_params(dict(CFG, num_hidden_layers=4))) \
        == 951_599_616                                      # an expert layer
    assert costs.total_params(CFG) == CFG["parameters"] == 4_635_518_208
    assert builder.program_config(CFG, 64).num_params() == CFG["parameters"]
    assert 2 * costs.total_params(CFG) == pytest.approx(9.27e9, rel=0.001)
    assert 2 * costs.total_params(CFG) / 2**30 == pytest.approx(8.63, abs=.01)
    whole = dict(CFG, **CFG["published"])
    assert costs.total_params(whole) == CFG["parameters_whole_model"] \
        == 671_877_944_064
    assert builder.program_config(
        dict(whole, num_nextn_predict_layers=0), 64).num_params() \
        == 671_877_944_064


def test_decode_step_bytes_by_hand():
    assert costs.kv_bytes_per_token_layer(CFG) == 1152
    assert costs.index_bytes_per_token_layer(CFG) == 256
    assert costs.mha_kv_bytes_per_token_layer(CFG) == 81_920
    hit = 16 * (1 - (1 - 8 / 256) ** 16)
    assert hit == pytest.approx(6.37, abs=0.01)
    assert costs.expected_held_experts_hit(CFG, 16) == pytest.approx(hit)
    live = 16 * 18_500
    keys, rows = live * 5 * 256, 16 * 2048 * 5 * 1152
    assert keys == pytest.approx(0.38e9, rel=0.01)
    assert rows == pytest.approx(0.19e9, rel=0.01)
    assert live * 5 * 1152 == pytest.approx(1.70e9, rel=0.01)   # dense MLA
    assert costs.dsa_indexer_bytes(CFG, live) == keys
    assert costs.dsa_attention_bytes(CFG, costs.selected_rows(CFG, live)) \
        == rows
    bf16 = (5 * 201_066_752 + 3 * 7168 * 18_432
            + 4 * (44_040_192 + hit * 44_040_192) + 7168 * 16_160)
    weights = 2 * bf16 + 4 * 4 * 1_835_264
    assert weights == pytest.approx(5.66e9, rel=0.005)
    total = costs.decode_step_bytes(CFG, live)
    assert total == pytest.approx(weights + keys + rows)
    assert total == pytest.approx(6.2e9, rel=0.01)
    assert total / 819e9 == pytest.approx(7.6e-3, rel=0.01)
    # under 2,048 rows a slot every row is selected
    assert costs.selected_rows(CFG, 16 * 1000) == 16 * 1000
    # the kernels: 64 FLOP/B for the indexer, the ridge for the attention
    assert costs.dsa_indexer_flops(CFG, 1) / costs.dsa_indexer_bytes(
        CFG, 1) == 64
    assert costs.dsa_attention_flops(CFG, 1) / costs.dsa_attention_bytes(
        CFG, 1) == pytest.approx(241.8, abs=0.1)
    # the pool: 11,265 blocks x 5 layers x 32 rows x 1,536 B
    assert 11_265 * 5 * 32 * 1536 / 2**30 == pytest.approx(2.58, abs=0.005)
    assert 11_264 * 5 * 32 * 81_920 / 2**30 == pytest.approx(137.5, abs=0.1)


def test_configuration_keeps_every_published_key():
    import json
    import os
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3.2")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] == CFG["reduced"][key]["to"] != value
            assert CFG["reduced"][key]["from"] == value \
                == CFG["published"][key]
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == [
        "first_k_dense_replace", "max_position_embeddings",
        "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert CFG["router_experts"] == row["config"]["n_routed_experts"] == 256
    assert CFG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CFG["max_position_embeddings"] == TRAFFIC["engine"]["max_seq"]
    assert CFG["decode_slots"] == TRAFFIC["engine"]["max_slots"] == 16
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == CFG["name"]]
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])


def test_builder_maps_the_published_keys():
    from ray_tpu.models.mla import MLAModel

    cfg = builder.program_config(CFG, 22_528)
    assert (cfg.dim, cfg.n_heads, cfg.head_dim, cfg.v_head_dim) == (
        7168, 128, 192, 128)
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim) == (512, 1536, 128, 64)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        64, 128, 2048)
    assert (cfg.num_experts, cfg.held, cfg.expert_top_k, cfg.ffn_dim) == (
        256, (0, 16), 8, 2048)
    assert (cfg.router_n_group, cfg.router_topk_group) == (8, 4)
    assert (cfg.shared_ffn_dim, cfg.leading_layers, cfg.leading_ffn_dim) \
        == (2048, 1, 18_432)
    assert (cfg.router_kind, cfg.routed_scaling_factor, cfg.norm_topk_prob) \
        == ("sigmoid", 2.5, True)
    assert cfg.rope_theta == 1e4 and cfg.norm_eps == 1e-6
    assert (cfg.yarn.factor, cfg.yarn.original_max_position,
            cfg.yarn.cos_sin_scale) == (40.0, 4096, 1.0)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.36888 ** 2,
                                              rel=1e-4)
    assert cfg.n_layers == 5 and cfg.vocab_size == 16_160
    assert cfg.dtype == jnp.bfloat16 and not cfg.tie_embeddings
    model = builder.build_model(TINY, 64)
    assert type(model) is MLAModel and model.cfg.dtype == jnp.float32
    assert model.cfg.held == (4, 4) and model.ffn_load_shape() == (1, 16)
    for key, other in (("num_nextn_predict_layers", 1),
                       ("scoring_func", "softmax"), ("moe_layer_freq", 2)):
        with pytest.raises(ValueError, match=key):
            builder.program_config(dict(CFG, **{key: other}), 64)


def test_the_drawn_bias_changes_a_few_percent_of_the_chosen_sets():
    """The configuration's ``assumed`` e_score_correction_bias: N(0, 0.001)
    on a seeded router of logits sd 0.9, through the group limit."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(20_000, 256)) * 0.9
    scores = jnp.asarray(1 / (1 + np.exp(-logits)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=256) * CFG["router_bias_init_std"],
                       jnp.float32)
    pick = lambda b: np.sort(np.asarray(reference.grouped_sigmoid_topk(
        scores, b, top_k=8, n_group=8, topk_group=4)), -1)
    changed = (pick(bias) != pick(jnp.zeros(256))).any(-1).mean()
    assert 0.02 < changed < 0.12
    # and uniform routing over the held range: 16 of 256
    assert (pick(bias) < 16).mean() == pytest.approx(1 / 16, abs=0.004)


def test_reference_imports_nothing_of_the_program():
    import ast
    with open(reference.__file__) as f:
        tree = ast.parse(f.read())
    names = {n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert not [n for n in names if n.startswith(("ray_tpu", "benchmark"))]


def test_reference_matches_the_program_through_the_builder_and_the_check():
    tiny = dict(TINY, index_topk=16)
    model = builder.build_model(tiny, 128)
    params = model.init(jax.random.key(1))
    server = types.SimpleNamespace(model=model, engine=types.SimpleNamespace(
        params=params, block_size=8))
    # the cell's logits check: a prefill of 40 rows (the selection as a
    # mask), then 24 paged steps that select 16 of 41..64 rows
    with jax.default_matmul_precision("highest"):
        checks = serving.check_logits(
            server, builder.reference_forward(tiny), seed=2_147_483_999,
            prompt_len=40, decode_steps=24, tol_rel_rms=1e-4)
        wrong = serving.check_logits(
            server, builder.reference_forward(tiny, "half_topk"),
            seed=2_147_483_999, prompt_len=40, decode_steps=24,
            tol_rel_rms=1e-4)
    assert checks["ok"] and checks["positions"] == 48
    assert not wrong["ok"] and wrong["logits_rel_rms"] > 0.01


def test_new_metrics_read_the_counters_and_nothing_on_a_program_without():
    from benchmark.costs import mla_moe_transformer
    names = ("dsa.indexer_roofline.decode", "dsa.attention_roofline.decode",
             "kv.selected_read_share.decode",
             "moe.held_assignment_share.decode")
    idx, attn, share, held = map(harness.load_metric, names)
    # the parent's program: no counter; another configuration's costs
    old = {"decode_steps": 9, "decode_kv_blocks_live": 5,
           "moe_assignments": 7}
    for rec in ({}, {"engine_before": old, "engine_after": old,
                     "config": CFG, "costs": costs, "traffic": TRAFFIC},
                {"engine_trace_edges": [old, old], "config": CFG,
                 "costs": mla_moe_transformer, "traffic": TRAFFIC,
                 "trace": {"device_ops": [], "programs": {}},
                 "peaks": {"hbm_bytes_per_s": 1, "bf16_flops_per_s": 1}}):
        assert [m.read(rec) for m in (idx, attn, share, held)] == [None] * 4
    before = {"decode_steps": 100, "decode_kv_blocks_live": 1000,
              "decode_kv_rows_selected": 500, "moe_assignments": 0,
              "moe_assignments_held": 0}
    blocks = 16 * 578                            # ~18.5k rows a slot
    after = {"decode_steps": 110,
             "decode_kv_blocks_live": 1000 + 10 * blocks,
             "decode_kv_rows_selected": 500 + 10 * 16 * 2048,
             "moe_assignments": 10 * 16 * 8 * 4, "moe_assignments_held": 320}
    rec = {"engine_before": before, "engine_after": after, "config": CFG,
           "costs": costs, "traffic": TRAFFIC}
    assert share.read(rec) == pytest.approx(100 * 2048 / (578 * 32))
    assert held.read(rec) == pytest.approx(6.25)
    keys_s = blocks * 32 * 5 * 256 / 819e9
    rows_s = 16 * 2048 * 5 * 2 * 128 * 1088 / 197e12
    assert rows_s > 16 * 2048 * 5 * 1152 / 819e9     # the FLOPs bound it
    traced = {
        **rec,
        "trace": {"device_ops": [
            ["indexer_scores_pallas.3", 10 * 1.5 * keys_s],
            ["sort.61", 10 * 0.5 * keys_s],
            ["selected_attention_pallas.5", 10 * 4 * rows_s],
            ["fusion.1", 1.0]],
            "programs": {"jit__decode_step_paged": {"calls": 10,
                                                    "seconds": 1.0}}},
        "engine_trace_edges": [before, after],
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert idx.read(traced) == pytest.approx(50.0)
    assert attn.read(traced) == pytest.approx(25.0)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for name in names:
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_out_tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
