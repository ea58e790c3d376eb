"""The kanana-2 configuration's pieces: the cost model's arithmetic against
ISSUE 41's numbers, the reference against a case written out by hand, the
builder's mapping of the published keys, the reference against the
builder's model through the cell's own check, the new metrics' readers."""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import kanana as builder
from benchmark.costs import mla_moe_transformer as costs
from benchmark.lib import serving
from benchmark.reference import kanana as reference

CFG = harness.load_json(harness.ROOT,
                        "benchmark/configs/kanana-2-30b-a3b-d5.json")
TINY = {**CFG, **CFG["tiny_cpu"]}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "kanana-2-30b-a3b-d5.long_decode_mla"


def test_parameter_counts_at_the_cut_and_the_published_depth():
    assert costs.attention_params(CFG) + CFG["kv_lora_rank"] == 26_345_984
    assert costs.dense_ffn_params(CFG) == 37_748_736
    assert 128 * costs.expert_params(CFG) == 603_979_776
    assert costs.shared_params(CFG) == 9_437_184
    assert costs.router_params(CFG) == 262_272
    assert 2 * CFG["vocab_size"] * CFG["hidden_size"] == 525_336_576
    # the dense layer and an expert layer, norms and all
    assert costs.total_params(dict(CFG, num_hidden_layers=1)) \
        - 525_336_576 - 2048 == 64_098_816
    assert (costs.total_params(CFG)
            - costs.total_params(dict(CFG, num_hidden_layers=4))) \
        == 640_029_312
    assert costs.total_params(CFG) == CFG["parameters"] == 3_149_554_688
    assert builder.program_config(CFG, 64).num_params() == CFG["parameters"]
    whole = dict(CFG, num_hidden_layers=48)
    assert costs.total_params(whole) == pytest.approx(30.67e9, rel=0.001)
    assert builder.program_config(whole, 64).num_params() \
        == costs.total_params(whole)
    # 2 bytes a parameter: 6.30 GB (5.87 GiB) at depth 5
    assert 2 * costs.total_params(CFG) == pytest.approx(6.30e9, rel=0.001)
    assert 2 * costs.total_params(CFG) / 2**30 == pytest.approx(5.87, abs=.01)
    # what one token multiplies with: 3.1 B (the name's "a3b") and the
    # head's 0.26 of the whole model's 30.7
    assert costs.matmul_params(whole) == pytest.approx(3.35e9, rel=0.005)


def test_decode_step_bytes_by_hand():
    assert costs.kv_bytes_per_token_layer(CFG) == 1152
    assert costs.mha_kv_bytes_per_token_layer(CFG) == 20_480
    hit = 128 * (1 - (1 - 6 / 128) ** 32)
    assert hit == pytest.approx(100.4, abs=0.1)
    bf16 = (5 * (26_345_984 - 512) + 37_748_736
            + 4 * (9_437_184 + hit * 4_718_592) + 2048 * 128_256)
    weights = 2 * bf16 + 4 * 4 * 262_272
    assert weights == pytest.approx(4.74e9, rel=0.005)
    assert 2 * 4 * hit * 4_718_592 == pytest.approx(3.79e9, rel=0.005)
    # the window decodes ~18.5k positions of each of 32 slots: 3.4 GB of
    # latent rows, 42 % of the step's 8.1 GB, 9.9 ms at 819 GB/s
    live = 32 * 18_500
    rows = live * 5 * 1152
    assert rows == pytest.approx(3.41e9, rel=0.001)
    assert costs.mla_attention_bytes(CFG, live) == rows
    assert costs.decode_step_bytes(CFG, live) == pytest.approx(weights + rows)
    assert rows / (weights + rows) == pytest.approx(0.42, abs=0.005)
    assert (weights + rows) / 819e9 == pytest.approx(9.9e-3, rel=0.01)
    # the kernel: 2 x 32 x (576 + 512) FLOPs a row, 60 FLOP/B
    assert costs.mla_attention_flops(CFG, live) == live * 5 * 2 * 32 * 1088
    assert costs.mla_attention_flops(CFG, 1) / costs.mla_attention_bytes(
        CFG, 1) == pytest.approx(60.4, abs=0.1)
    # the pool: 24,576 blocks x 5 layers x 32 rows, 4.22 GiB at 1,152 B a
    # row, 4.69 as held (1,280); every head's K and V would take 75 GiB
    assert 24_576 * 5 * 32 * 1152 / 2**30 == pytest.approx(4.22, abs=0.005)
    assert 24_577 * 5 * 32 * 1280 / 2**30 == pytest.approx(4.69, abs=0.005)
    assert 24_576 * 5 * 32 * 20_480 / 2**30 == pytest.approx(75, abs=0.05)


def test_configuration_keeps_every_published_key():
    import json
    import os
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] == CFG["reduced"][key]["to"] != value
            assert CFG["reduced"][key]["from"] == value
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == ["max_position_embeddings",
                                      "num_hidden_layers"]
    assert CFG["max_position_embeddings"] == harness.load_json(
        harness.HERE, "traffic", "long_decode_mla.json")["engine"]["max_seq"]


def test_builder_maps_the_published_keys():
    from ray_tpu.models.mla import MLAModel

    cfg = builder.program_config(CFG, 24_576)
    assert (cfg.dim, cfg.n_heads, cfg.head_dim, cfg.v_head_dim) == (
        2048, 32, 192, 128)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim) \
        == (512, 128, 64)
    assert (cfg.num_experts, cfg.expert_top_k, cfg.ffn_dim) == (128, 6, 768)
    assert (cfg.shared_ffn_dim, cfg.leading_layers, cfg.leading_ffn_dim) \
        == (1536, 1, 6144)
    assert (cfg.router_kind, cfg.routed_scaling_factor, cfg.norm_topk_prob) \
        == ("sigmoid", 2.448, True)
    assert cfg.router_bias_init_std == 0.001
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-6
    assert cfg.n_layers == 5 and cfg.vocab_size == 128_256
    assert cfg.dtype == jnp.bfloat16 and not cfg.tie_embeddings
    tiny = builder.program_config(TINY, 64)
    assert tiny.dtype == jnp.float32 and tiny.head_dim == 24
    assert tiny.n_heads == 1
    model = builder.build_model(TINY, 64)
    assert type(model) is MLAModel
    assert model.ffn_load_shape() == (1, 8)        # the expert layers alone
    for key, other in (("q_lora_rank", 1536), ("topk_group", 4),
                       ("moe_layer_freq", 2), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            builder.program_config(dict(CFG, **{key: other}), 64)


def _silu(x):
    return x / (1.0 + math.exp(-x))


def test_reference_against_a_case_by_hand():
    """One expert layer, one head, two tokens, every number written out:
    the rotation of adjacent lanes, the shared key part, the scale by
    sqrt(nope + rope), the bias that chooses and does not weigh, the
    renormalised weights times the factor, the shared expert."""
    d, nope, rope, rank, v = 2, 1, 2, 1, 1
    theta, eps, scale = 100.0, 0.0, 2.0
    x = np.array([[1.0, 0.0], [0.0, 2.0]])
    lp = {
        "attn_norm": np.ones(d), "mlp_norm": np.ones(d),
        "q_proj": np.array([[[1.0, 1.0, 0.0]], [[0.0, 0.0, 1.0]]]),
        "kv_a_proj": np.array([[2.0, 1.0, 0.0], [1.0, 0.0, 1.0]]),
        "kv_a_layernorm": np.array([3.0]),
        "kv_b_proj": np.array([[[1.0, 2.0]]]),         # k_nope = c, v = 2 c
        "o_proj": np.array([[[1.0, -1.0]]]),
        "router": np.array([[4.0, 0.0, -4.0], [0.0, 0.0, 0.0]]),
        "router_bias": np.array([-10.0, 0.0, 0.0]),    # never expert 0
        "e_gate": np.ones((3, d, 1)), "e_up": np.ones((3, d, 1)),
        "e_down": np.array([[[1.0, 0.0]], [[0.0, 1.0]], [[1.0, 1.0]]]),
        "s_gate": np.ones((d, 1)), "s_up": np.ones((d, 1)),
        "s_down": np.array([[0.5, 0.5]]),
    }
    params = {"embed": x, "dense_layers": {},
              "moe_layers": {k: np.asarray(a)[None] for k, a in lp.items()},
              "norm_f": np.ones(d), "lm_head": np.eye(d)}
    got = np.asarray(reference.forward(
        params, jnp.asarray([[0, 1]]), qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, kv_lora_rank=rank, rope_theta=theta,
        rms_norm_eps=eps, top_k=2, routed_scaling_factor=scale))[0]

    def rms(a):
        r = math.sqrt(sum(t * t for t in a) / len(a))
        return [t / r for t in a]

    h = [rms(list(row)) for row in x]              # [sqrt2, 0], [0, sqrt2]
    outs = []
    for t, ht in enumerate(h):
        q = [ht[0], ht[0], ht[1]]                  # nope | pe (2 lanes)
        rows = []
        for s in range(t + 1):
            hs = h[s]
            down = [2 * hs[0] + hs[1], hs[0], hs[1]]
            c = 3.0 * (1.0 if down[0] > 0 else -1.0)       # RMSNorm of 1 lane
            ang = s * 1.0                                   # theta**0 = 1
            pe = [down[1] * math.cos(ang) - down[2] * math.sin(ang),
                  down[2] * math.cos(ang) + down[1] * math.sin(ang)]
            rows.append((c, pe))
        ang = t * 1.0
        q_pe = [q[1] * math.cos(ang) - q[2] * math.sin(ang),
                q[2] * math.cos(ang) + q[1] * math.sin(ang)]
        scores = [(q[0] * c + q_pe[0] * pe[0] + q_pe[1] * pe[1])
                  / math.sqrt(nope + rope) for c, pe in rows]
        top = max(scores)
        p = [math.exp(s - top) for s in scores]
        o = sum(pi * 2 * c for pi, (c, _) in zip(p, rows)) / sum(p)
        xt = [x[t][0] + o, x[t][1] - o]
        g = rms(xt)
        s_ = [1 / (1 + math.exp(-4 * g[0])), 0.5, 1 / (1 + math.exp(4 * g[0]))]
        w = [s_[1], s_[2]]                         # experts 1 and 2 always
        w = [scale * wi / (w[0] + w[1]) for wi in w]
        act = _silu(g[0] + g[1]) * (g[0] + g[1])
        routed = [w[1] * act, w[0] * act + w[1] * act]
        shared = [0.5 * act, 0.5 * act]
        outs.append(rms([xt[0] + routed[0] + shared[0],
                         xt[1] + routed[1] + shared[1]]))
    np.testing.assert_allclose(got, np.asarray(outs), atol=2e-5)


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
    model = builder.build_model(TINY, 128)
    params = model.init(jax.random.key(1))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 60)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, toks)
    want = builder.reference_forward(TINY)(params, toks)[:, :]
    np.testing.assert_allclose(got, want, atol=2e-4)
    # the cell's logits check, its five calls on the model as they are
    # (latent rows moved by the names "k" and "v"), the paged steps
    # reading the pool's second and later blocks
    server = types.SimpleNamespace(model=model, engine=types.SimpleNamespace(
        params=params, block_size=8))
    with jax.default_matmul_precision("highest"):
        checks = serving.check_logits(
            server, builder.reference_forward(TINY), seed=2_147_483_999,
            prompt_len=40, decode_steps=24, tol_rel_rms=1e-4)
        wrong = serving.check_logits(
            server, builder.reference_forward(TINY, "weights_with_bias"),
            seed=2_147_483_999, prompt_len=40, decode_steps=24,
            tol_rel_rms=1e-4)
    assert checks["ok"] and checks["positions"] == 48
    assert not wrong["ok"] and wrong["logits_rel_rms"] > 0.01


def test_new_metrics_read_the_counters_and_nothing_on_a_program_without():
    roof = harness.load_metric("mla.attention_roofline.decode")
    share = harness.load_metric("kv.latent_share_of_mha.decode")
    traffic = harness.load_json(harness.HERE, "traffic",
                                "long_decode_mla.json")
    from benchmark.costs import moe_transformer
    # the parent's program: no counter; another configuration's costs
    for rec in ({}, {"engine_after": {"decode_steps": 9}, "config": CFG,
                     "costs": costs, "traffic": traffic},
                {"engine_after": {"kv_pool_bytes": 1 << 30}, "config": CFG,
                 "costs": moe_transformer, "traffic": traffic}):
        assert share.read(rec) is None and roof.read(rec) is None
    rec = {"engine_after": {"kv_pool_bytes": 24_577 * 5 * 32 * 1280},
           "config": CFG, "costs": costs, "traffic": traffic}
    assert share.read(rec) == pytest.approx(6.25, abs=0.01)
    rec["engine_after"]["kv_pool_bytes"] = 24_576 * 5 * 32 * 1152
    assert share.read(rec) == pytest.approx(5.6, abs=0.03)
    # the kernel at its roofline: 32 slots x 18,496 rows a step, 5 layers
    blocks = 32 * 578
    least_s = blocks * 32 * 5 * 1152 / 819e9
    traced = {
        "trace": {"device_ops": [["jit(mla_decode_attention_pallas)/x",
                                  10 * 2 * least_s], ["fusion.1", 1.0]],
                  "programs": {"jit__decode_step_paged": {"calls": 10,
                                                          "seconds": 1.0}}},
        "engine_trace_edges": [
            {"decode_steps": 100, "decode_kv_blocks_live": 1000},
            {"decode_steps": 110, "decode_kv_blocks_live": 1000 + 10 * blocks}],
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        "config": CFG, "costs": costs, "traffic": traffic}
    assert roof.read(traced) == pytest.approx(50.0)
    assert roof.read({**traced, "costs": moe_transformer}) is None
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for name in ("mla.attention_roofline.decode",
                 "kv.latent_share_of_mha.decode"):
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert len(bench["workloads"]) == 8
