"""The Mellum2 configuration's pieces: the cost model's arithmetic against
ISSUE 32's numbers, the reference against a case written out by hand, the
builder's mapping of the published keys, the new metrics' readers."""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import mellum as builder
from benchmark.costs import hybrid_moe_transformer as costs
from benchmark.lib import serving
from benchmark.reference import mellum as reference

CFG = harness.load_json(harness.ROOT,
                        "benchmark/configs/mellum2-12b-a2.5b-d8.json")
TINY = {**CFG, **CFG["tiny_cpu"]}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_parameter_counts_at_the_cut_and_the_published_depth():
    assert costs.attention_params(CFG) == 21_233_664
    assert CFG["hidden_size"] * CFG["num_experts"] == 147_456
    assert CFG["num_experts"] * costs.expert_params(CFG) == 396_361_728
    assert 2 * CFG["vocab_size"] * CFG["hidden_size"] == 452_984_832
    assert costs.total_params(CFG) == CFG["parameters"] == 3_794_966_784
    assert builder.program_config(CFG, 64).num_params() == CFG["parameters"]
    whole = dict(CFG, num_hidden_layers=28)
    assert costs.total_params(whole) == pytest.approx(12.1e9, rel=0.01)
    assert builder.program_config(whole, 64).num_params() \
        == costs.total_params(whole)
    # 2 bytes a parameter: 7.59 GB at depth 8, 24.3 GB whole
    assert 2 * costs.total_params(CFG) == pytest.approx(7.59e9, rel=0.001)
    assert 2 * costs.total_params(whole) == pytest.approx(24.3e9, rel=0.005)


def test_decode_step_bytes_by_hand():
    s = costs.dims(CFG)
    assert (s["full_layers"], s["sliding_layers"], s["window"]) == (2, 6, 1024)
    assert costs.kv_bytes_per_token_layer(CFG) == 2048            # 2 KiB
    hit = 64 * (1 - 0.875 ** 32)                                  # 63.1
    layer = 21_233_664 + 147_456 + hit * 3 * 2304 * 896
    weights = 2 * (8 * layer + 2304 * 98_304)
    assert weights == pytest.approx(7.1e9, rel=0.01)
    # under the window every layer reads every token
    assert costs.decode_step_bytes(CFG, 32 * 500) == pytest.approx(
        weights + 32 * 500 * 8 * 2048)
    # past it a sliding layer reads a window and a block a slot: at 8,800
    # tokens a slot the full layers 1.15 GB, the sliding ones 0.42
    live = 32 * 8800
    assert costs.kv_read_bytes(CFG, live) == pytest.approx(
        2048 * (2 * live + 6 * 32 * (1024 + 32)))
    assert 2048 * 2 * live == pytest.approx(1.15e9, rel=0.01)
    assert 2048 * 6 * 32 * 1056 == pytest.approx(0.415e9, rel=0.01)
    assert costs.decode_step_bytes(CFG, live) == pytest.approx(
        weights + costs.kv_read_bytes(CFG, live))
    # one kind of layer would read 4.6 GB there
    assert 2048 * 8 * live == pytest.approx(4.6e9, rel=0.01)
    # and hold 8.6 GB of pool where the two kinds hold 2.15 + 0.63
    blocks = costs.uniform_pool_blocks(CFG, 32, 16_384, 32)
    assert blocks * 32 * 2048 == pytest.approx(8.59e9, rel=0.001)
    assert 2 * 16_384 * 32 * 2048 == pytest.approx(2.147e9, rel=0.001)
    assert 6 * 1600 * 32 * 2048 == pytest.approx(0.629e9, rel=0.001)


def test_configuration_keeps_every_published_key():
    import json
    import os
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] == CFG["reduced"][key]["to"] != value
            assert CFG["reduced"][key]["from"] == value
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == ["max_position_embeddings",
                                      "num_hidden_layers"]
    # two whole periods of the pattern are what is held
    assert builder.layer_types(CFG) == (
        ("sliding_attention",) * 3 + ("full_attention",)) * 2
    assert builder.layer_types(TINY) == (
        "sliding_attention",) * 3 + ("full_attention",)


def test_builder_maps_the_published_keys():
    cfg = builder.program_config(CFG, 16_384)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2304, 32, 4, 128)
    assert (cfg.num_experts, cfg.expert_top_k, cfg.ffn_dim) == (64, 8, 896)
    assert cfg.norm_topk_prob is True and cfg.qk_norm is False
    assert cfg.sliding_window == 1024 and cfg.n_layers == 8
    assert cfg.layer_types == builder.layer_types(CFG)
    (kind, yarn), = cfg.rope_scaling
    assert kind == "full_attention"
    assert (yarn.factor, yarn.original_max_position, yarn.beta_fast,
            yarn.beta_slow) == (16.0, 8192, 32.0, 1.0)
    assert yarn.attention_factor == 1.2772588722239782
    assert cfg.rope_theta == 5e5 and cfg.norm_eps == 1e-6
    assert cfg.dtype == jnp.bfloat16 and cfg.vocab_size == 98_304
    tiny = builder.program_config(TINY, 64)
    assert tiny.dtype == jnp.float32 and tiny.head_dim == 32
    with pytest.raises(ValueError, match="attention bias"):
        builder.program_config(dict(CFG, attention_bias=True), 64)
    with pytest.raises(ValueError, match="dense layer"):
        builder.program_config(dict(CFG, mlp_layer_types=["dense"] * 28), 64)
    from ray_tpu.models.moe import MoEModel
    model = builder.build_model(TINY, 64)
    assert type(model) is MoEModel and model.layer_kinds == (1, 1, 1, 0)


def _silu(x):
    return x / (1.0 + math.exp(-x))


def _stack(lp):
    return {k: np.asarray(v)[None] for k, v in lp.items()}


def test_reference_against_a_case_by_hand():
    """One sliding layer with a window of ONE, width 2, one head, two
    tokens: each position sees itself alone, so attention is the value
    itself whatever the rotation; two experts of width 1, top-1,
    renormalised (the one weight is 1). Every number follows by hand."""
    eps = 1e-6
    rows = np.array([[3.0, 4.0], [1.0, -2.0]])
    eye = np.eye(2, dtype=np.float32)
    lp = {
        "attn_norm": np.ones(2, np.float32),
        "wq": eye.reshape(2, 1, 2), "wk": eye.reshape(2, 1, 2),
        "wv": (2 * eye).reshape(2, 1, 2), "wo": eye.reshape(1, 2, 2),
        "mlp_norm": np.array([1.0, 0.5], np.float32),
        "router": np.array([[1.0, 0.0], [0.0, 2.0]], np.float32),
        "e_gate": np.array([[[1.0], [0.0]], [[0.0], [1.0]]], np.float32),
        "e_up": np.array([[[2.0], [0.0]], [[0.0], [3.0]]], np.float32),
        "e_down": np.array([[[1.0, 1.0]], [[1.0, -1.0]]], np.float32),
    }
    params = {"embed": np.concatenate([np.zeros((1, 2)), rows]).astype(
        np.float32), "layers": _stack(lp), "norm_f": np.ones(2, np.float32),
        "lm_head": eye}
    rope = {"sliding_attention": {"rope_type": "default", "rope_theta": 1e4}}
    want, chosen = [], []
    for x in rows:
        h = x / math.sqrt(np.mean(x * x) + eps)
        x1 = x + 2 * h                          # o = v = 2 * norm(x)
        g = x1 / math.sqrt(np.mean(x1 * x1) + eps) * np.array([1.0, 0.5])
        logits = np.array([g[0], 2 * g[1]])
        e = int(np.argmax(logits))
        gate, up = (g[0], 2 * g[0]) if e == 0 else (g[1], 3 * g[1])
        down = np.array([1.0, 1.0]) if e == 0 else np.array([1.0, -1.0])
        x2 = x1 + _silu(gate) * up * down       # weight p_e / p_e = 1
        want.append(x2 / math.sqrt(np.mean(x2 * x2) + eps))
        chosen.append(e)
    assert chosen == [1, 0]                     # both experts are used
    kw = dict(layer_types=("sliding_attention",), rope_parameters=rope,
              rms_norm_eps=eps, top_k=1, norm_topk_prob=True)
    toks = jnp.asarray([[1, 2]], jnp.int32)
    got, routing = reference.forward(params, toks, sliding_window=1,
                                     with_routing=True, **kw)
    np.testing.assert_allclose(np.asarray(got)[0], np.stack(want), rtol=2e-6)
    assert routing["experts"][0, 0, :, 0].tolist() == chosen
    # a window of two lets the second token see the first: only ITS
    # logits move
    wider = reference.forward(params, toks, sliding_window=2, **kw)
    np.testing.assert_allclose(np.asarray(wider)[0, 0], want[0], rtol=2e-6)
    assert np.abs(np.asarray(wider)[0, 1] - want[1]).max() > 1e-2
    # the weight as it is, not renormalised, is the softmax's
    raw = reference.forward(params, toks, sliding_window=1,
                            **{**kw, "norm_topk_prob": False})
    assert np.abs(np.asarray(raw)[0, 0] - want[0]).max() > 1e-2


def test_reference_attention_by_blocks_is_attention():
    """Query blocks (512 a block) against the plain softmax over the
    whole [S, S] matrix, window and no window, S no multiple of 512."""
    key = jax.random.split(jax.random.key(0), 3)
    B, S, H, Hkv, hd = 1, 700, 4, 2, 16
    q = jax.random.normal(key[0], (B, S, H, hd))
    k = jax.random.normal(key[1], (B, S, Hkv, hd))
    v = jax.random.normal(key[2], (B, S, Hkv, hd))
    kk, vv = (jnp.repeat(a, H // Hkv, axis=2) for a in (k, v))
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    for window in (None, 100):
        seen = (i >= j) if window is None else (i >= j) & (i - j < window)
        s = jnp.einsum("bqhk,bthk->bhqt", q, kk) / math.sqrt(hd)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        want = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), vv)
        with jax.default_matmul_precision("highest"):
            got = reference._attention(q, k, v, window)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_reference_matches_the_program_through_the_builder_and_the_check():
    model = builder.build_model(TINY, 128)
    params = model.init(jax.random.key(1))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 60)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, toks)
    want = builder.reference_forward(TINY)(params, toks)
    np.testing.assert_allclose(got, want, atol=2e-4)
    # the cell's logits check, its five calls on the model as they are,
    # across the window's edge (16) at the rehearsal's widths
    server = types.SimpleNamespace(model=model, engine=types.SimpleNamespace(
        params=params, block_size=8))
    with jax.default_matmul_precision("highest"):
        checks = serving.check_logits(
            server, builder.reference_forward(TINY), seed=2_147_483_999,
            prompt_len=40, decode_steps=24, tol_rel_rms=1e-4)
        wrong = serving.check_logits(
            server, builder.reference_forward({**TINY, "sliding_window": 8}),
            seed=2_147_483_999, prompt_len=40, decode_steps=24,
            tol_rel_rms=1e-4)
    assert checks["ok"] and checks["positions"] == 48
    assert not wrong["ok"] and wrong["logits_rel_rms"] > 0.01


def _record(before, after, **more):
    return {"engine_before": before, "engine_after": after, **more}


def test_kv_metrics_read_the_counters_and_nothing_on_a_program_without():
    share = harness.load_metric("kv.window_read_share.decode")
    pool = harness.load_metric("kv.pool_share_of_uniform.decode")
    parent = _record({"decode_steps": 5, "decode_kv_blocks_live": 10},
                     {"decode_steps": 9, "decode_kv_blocks_live": 90})
    one_kind = _record(
        {"decode_kv_blocks_live": 10, "decode_kv_blocks_live_window": 0,
         "kv_pool_blocks_full": 128, "kv_pool_blocks_window": 0},
        {"decode_kv_blocks_live": 90, "decode_kv_blocks_live_window": 0,
         "kv_pool_blocks_full": 128, "kv_pool_blocks_window": 0},
        config=CFG, costs=costs)
    for rec in (parent, one_kind, {}):
        assert share.read(rec) is None and pool.read(rec) is None
    traffic = harness.load_json(harness.HERE, "traffic",
                                "long_decode_hybrid.json")
    rec = _record(
        {"decode_kv_blocks_live": 1000, "decode_kv_blocks_live_window": 500,
         "kv_pool_blocks_full": 16_384, "kv_pool_blocks_window": 1600},
        {"decode_kv_blocks_live": 1000 + 32 * 275 * 10,
         "decode_kv_blocks_live_window": 500 + 32 * 33 * 10,
         "kv_pool_blocks_full": 16_384, "kv_pool_blocks_window": 1600},
        config=CFG, costs=costs, traffic=traffic)
    assert share.read(rec) == pytest.approx(100 * 33 / 275)        # 12 %
    assert pool.read(rec) == pytest.approx(
        100 * (2 * 16_384 + 6 * 1600) / (8 * 16_384))              # 32.3 %
    # a configuration whose costs know no kinds reads nothing
    from benchmark.costs import moe_transformer
    assert pool.read({**rec, "costs": moe_transformer}) is None
    # the roofline's bytes for this configuration, through its costs
    roof = harness.load_metric("decode_program_roofline")
    traced = {"traffic": traffic, "config": CFG, "costs": costs,
              "peaks": {"hbm_bytes_per_s": 819e9},
              "trace": {"programs": {"jit__decode_step_paged_counted": {
                  "calls": 10, "seconds": 0.6}}},
              "engine_trace_edges": [
                  {"decode_steps": 100, "decode_kv_blocks_live": 0},
                  {"decode_steps": 110, "decode_kv_blocks_live": 32 * 275 * 10}]}
    live = 32 * 275 * 32
    assert roof.live_tokens_per_step(traced) == live
    assert roof.read(traced) == pytest.approx(
        100 * costs.decode_step_bytes(CFG, live) / 819e9 / 0.06)
