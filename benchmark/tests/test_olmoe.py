"""The OLMoE configuration's pieces: the cost model's arithmetic, the
reference against a two-expert case written out by hand, the builder's
mapping of the published keys, the new metrics' readers."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import olmoe as builder
from benchmark.costs import moe_transformer as costs
from benchmark.reference import olmoe as reference

CFG = harness.load_json(harness.ROOT, "benchmark/configs/olmoe-1b-7b-d3.json")
TINY = {**CFG, **CFG["tiny_cpu"]}


@pytest.mark.parametrize("layers,params", [(3, 1_464_756_224),
                                           (16, 6_919_161_856)])
def test_parameter_count_at_the_cut_and_the_published_depth(layers, params):
    cfg = dict(CFG, num_hidden_layers=layers)
    assert costs.total_params(cfg) == params
    assert builder.program_config(cfg, 64).num_params() == params
    assert CFG["parameters"] == costs.total_params(CFG)


def test_expected_distinct_experts():
    # every expert is missed by one token with probability 1 - 8/64
    assert costs.expected_distinct_experts(64, 8, 32) == pytest.approx(
        64 * (1 - 0.875 ** 32))
    assert costs.expected_distinct_experts(64, 8, 32) == pytest.approx(
        63.108, abs=1e-3)
    assert costs.expected_distinct_experts(64, 8, 1) == pytest.approx(8)
    assert costs.expected_distinct_experts(64, 64, 5) == pytest.approx(64)
    # against a simulation of uniform routing
    rng = np.random.default_rng(0)
    hit = np.mean([len({e for _ in range(4) for e in
                        rng.permutation(16)[:3]}) for _ in range(4000)])
    assert costs.expected_distinct_experts(16, 3, 4) == pytest.approx(
        hit, rel=0.02)


def test_decode_step_bytes_by_hand():
    d, f, V, L = 2048, 1024, 50304, 3
    attn = 4 * d * d
    layer = attn + d * 64 + 64 * (1 - 0.875 ** 32) * 3 * d * f
    weights = 2 * (L * layer + d * V)
    assert costs.kv_bytes_per_token(CFG) == L * 8192       # 8 KiB a layer
    assert costs.decode_step_bytes(CFG, 0) == pytest.approx(weights)
    assert costs.decode_step_bytes(CFG, 20_000) == pytest.approx(
        weights + 20_000 * L * 8192)
    # experts are the bulk of it: 2.38 of 2.69 GB
    experts = 2 * L * 64 * (1 - 0.875 ** 32) * 3 * d * f
    assert experts / weights == pytest.approx(0.885, abs=0.005)
    # one slot reads only its own 8 experts a layer
    one = costs.decode_step_bytes(CFG, 0, batch=1)
    assert one == pytest.approx(2 * (L * (attn + d * 64 + 8 * 3 * d * f)
                                     + d * V))
    assert costs.matmul_params(CFG) == L * (attn + d * 64 + 8 * 3 * d * f) \
        + d * V


def test_builder_maps_the_published_keys():
    cfg = builder.program_config(CFG, 4096)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        2048, 16, 16, 128)
    assert (cfg.num_experts, cfg.expert_top_k, cfg.ffn_dim) == (64, 8, 1024)
    assert cfg.norm_topk_prob is False and cfg.qk_norm is True
    assert cfg.rope_theta == 10000.0 and cfg.max_seq_len == 4096
    assert cfg.dtype == jnp.bfloat16
    assert builder.program_config(TINY, 64).dtype == jnp.float32
    with pytest.raises(ValueError, match="clip_qkv"):
        builder.program_config(dict(CFG, clip_qkv=8.0), 64)
    from ray_tpu.models.moe import MoEModel
    assert type(builder.build_model(TINY, 64)) is MoEModel


def _silu(x):
    return x / (1.0 + math.exp(-x))


def test_reference_against_a_two_expert_case_by_hand():
    """One layer, width 2, one head, ONE token (so attention is the value
    itself), two experts of width 1, top-1 with the weight as it is:
    every number below follows by hand from the equations."""
    eps = 1e-5
    x = np.array([3.0, 4.0])
    eye = np.eye(2, dtype=np.float32)
    lp = {
        "attn_norm": np.ones(2, np.float32),
        "wq": eye.reshape(2, 1, 2), "wk": eye.reshape(2, 1, 2),
        "wv": (2 * eye).reshape(2, 1, 2), "wo": eye.reshape(1, 2, 2),
        "q_norm": np.ones((1, 2), np.float32),
        "k_norm": np.ones((1, 2), np.float32),
        "mlp_norm": np.array([1.0, 0.5], np.float32),
        "router": np.array([[1.0, 0.0], [0.0, 2.0]], np.float32),
        "e_gate": np.array([[[1.0], [0.0]], [[0.0], [1.0]]], np.float32),
        "e_up": np.array([[[2.0], [0.0]], [[0.0], [3.0]]], np.float32),
        "e_down": np.array([[[1.0, 1.0]], [[1.0, -1.0]]], np.float32),
    }
    params = {"embed": np.stack([np.zeros(2), x]).astype(np.float32),
              "layers": [lp], "norm_f": np.ones(2, np.float32),
              "lm_head": eye}
    # attention over one position: softmax is 1, so o = v = 2 * norm(x)
    h = x / math.sqrt(np.mean(x * x) + eps)
    x1 = x + 2 * h
    g = x1 / math.sqrt(np.mean(x1 * x1) + eps) * np.array([1.0, 0.5])
    logits = np.array([g[0], 2 * g[1]])
    probs = np.exp(logits) / np.exp(logits).sum()
    e = int(np.argmax(probs))
    assert e == 1 and probs[0] > 0.3          # a real choice, not a tie
    # expert 1 reads lane 1: silu(g1) * 3 g1, sent to [1, -1]; weight p1
    act = _silu(g[1]) * 3 * g[1]
    x2 = x1 + probs[1] * act * np.array([1.0, -1.0])
    want = x2 / math.sqrt(np.mean(x2 * x2) + eps)

    got, routing = reference.forward(
        params, jnp.asarray([[1]], jnp.int32), rope_theta=1e4,
        rms_norm_eps=eps, top_k=1, with_routing=True)
    np.testing.assert_allclose(np.asarray(got)[0, 0], want, rtol=2e-6)
    assert routing["experts"].tolist() == [[[[1]]]]
    assert float(routing["gap"][0, 0, 0]) == pytest.approx(
        probs[1] - probs[0], rel=1e-5)
    # renormalised, the one weight is 1; forced to expert 0, lane 0 is read
    renorm = reference.forward(
        params, jnp.asarray([[1]], jnp.int32), rope_theta=1e4,
        rms_norm_eps=eps, top_k=1, norm_topk_prob=True)
    x2 = x1 + act * np.array([1.0, -1.0])
    np.testing.assert_allclose(
        np.asarray(renorm)[0, 0], x2 / math.sqrt(np.mean(x2 * x2) + eps),
        rtol=2e-6)
    forced = reference.forward(
        params, jnp.asarray([[1]], jnp.int32), rope_theta=1e4,
        rms_norm_eps=eps, top_k=1,
        forced_experts=jnp.zeros((1, 1, 1, 1), jnp.int32))
    x2 = x1 + probs[0] * _silu(g[0]) * 2 * g[0] * np.array([1.0, 1.0])
    np.testing.assert_allclose(
        np.asarray(forced)[0, 0], x2 / math.sqrt(np.mean(x2 * x2) + eps),
        rtol=2e-6)


def test_reference_matches_models_moe_through_the_builder():
    model = builder.build_model(TINY, 64)
    params = model.init(jax.random.key(1))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 48)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, toks)
    want = builder.reference_forward(TINY)(params, toks)
    np.testing.assert_allclose(got, want, atol=2e-4)


def _record(before, after):
    return {"engine_before": before, "engine_after": after}


def test_moe_metrics_read_the_counters_and_nothing_on_a_program_without():
    share = harness.load_metric("moe.routed_share.decode")
    load = harness.load_metric("moe.expert_load_max_over_mean.decode")
    parent = _record({"decode_steps": 5}, {"decode_steps": 9})
    dense = _record(
        {"moe_assignments": 0, "moe_assignments_expected": 0,
         "moe_expert_load": []},
        {"moe_assignments": 0, "moe_assignments_expected": 0,
         "moe_expert_load": []})
    for rec in (parent, dense, {}):
        assert share.read(rec) is None and load.read(rec) is None
    rec = _record(
        {"moe_assignments": 100, "moe_assignments_expected": 100,
         "moe_expert_load": [[10, 10, 20, 10], [5, 5, 5, 35]]},
        {"moe_assignments": 148, "moe_assignments_expected": 148,
         "moe_expert_load": [[16, 16, 26, 16], [11, 11, 17, 35]]})
    assert share.read(rec) == 100.0
    # window rows [6, 6, 6, 6] and [6, 6, 12, 0]: 1.0 and 12 / 6
    assert load.read(rec) == pytest.approx((1.0 + 2.0) / 2)
    rec["engine_after"]["moe_assignments"] = 142          # six rows dropped
    assert share.read(rec) == pytest.approx(100 * 42 / 48)
