"""What the Xing4.0 configuration may and may not be, held where it is
cheap: the builder's refusals, the mesh, the parameter arithmetic, the
softmax scale that reaches the dense absorbed kernel (a compressed query
and YaRN WITHOUT an indexer: a combination no other configuration runs),
the grouped matmuls' tiling at 28 lane tiles, the scopes in the lowered
programs."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import xing as builder
from ray_tpu.models import LlamaConfig, MLAConfig, MLAModel, model_for

PUBLISHED = harness.load_json(harness.ROOT,
                              "benchmark/configs/xing4.0-29b-a4b-d5.json")
I32 = jnp.int32


def test_the_builder_builds_the_model_the_config_describes():
    model = builder.build_model({**PUBLISHED, **PUBLISHED["tiny_cpu"]}, 64)
    assert isinstance(model, MLAModel) and not model.indexed
    cfg = builder.program_config(PUBLISHED, 16384)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.num_experts, cfg.expert_top_k, cfg.ffn_dim,
            cfg.leading_ffn_dim, cfg.vocab_size) == (
                3584, 32, 768, 512, 64, 4, 1024, 9216, 131072)
    # 192^-0.5 x (0.1 ln 64 + 1)^2
    assert cfg.softmax_scale == pytest.approx(0.144680, rel=1e-5)


@pytest.mark.parametrize("key,other", [
    ("n_group", 8), ("topk_group", 2), ("topk_method", "greedy"),
    ("scoring_func", "softmax"), ("moe_layer_freq", 2),
    ("attention_bias", True), ("hidden_act", "gelu"), ("ep_size", 8),
    ("num_nextn_predict_layers", 1), ("tie_word_embeddings", True),
    ("rope_interleave", False), ("q_lora_rank", None), ("hc_mult", 1),
    ("rope_scaling", {**PUBLISHED["rope_scaling"], "type": "linear"}),
    ("rope_scaling", {**PUBLISHED["rope_scaling"], "mscale": 0.707})])
def test_the_builder_refuses_what_the_program_has_not(key, other):
    with pytest.raises(ValueError, match=key if key != "rope_scaling"
                       else "YaRN"):
        builder.program_config({**PUBLISHED, key: other}, 64)


def test_streams_carry_no_partitioning_rules():
    """A mesh is refused as ``MLAModel`` refuses it, and by the plain
    model too where it has streams (their axis has no logical name)."""
    from ray_tpu.models import LlamaModel, NemotronHConfig
    from ray_tpu.parallel.mesh import mesh_from_string
    mesh = mesh_from_string("dp=1", jax.devices()[:1])
    with pytest.raises(NotImplementedError, match="partitioning"):
        MLAModel(MLAConfig.debug_xing(), mesh=mesh)
    with pytest.raises(NotImplementedError, match="residual stream"):
        LlamaModel(dataclasses.replace(LlamaConfig.debug(), hc_mult=2),
                   mesh=mesh)
    LlamaModel(LlamaConfig.debug(), mesh=mesh)            # one stream: fine
    with pytest.raises(ValueError, match="one residual stream"):
        NemotronHConfig(pattern="ME", hc_mult=4)
    with pytest.raises(ValueError, match="hc_mult"):
        LlamaConfig(hc_mult=0)


def test_num_params_counts_what_init_makes_and_the_published_sizes():
    cfg = MLAConfig.debug_xing()
    params = jax.eval_shape(model_for(cfg).init, jax.random.key(0))
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    plain = dataclasses.replace(cfg, hc_mult=1)
    assert cfg.num_params() - plain.num_params() == cfg.n_layers * 2 * (
        24 * 4 * cfg.dim + 3 + 24)
    assert "attn_hc" not in jax.eval_shape(model_for(plain).init,
                                           jax.random.key(0))["layers"]
    assert builder.program_config(PUBLISHED, 128).num_params() \
        == PUBLISHED["parameters"] == 4_047_680_782
    whole = {**PUBLISHED, **PUBLISHED["published"],
             "num_nextn_predict_layers": 0}
    assert builder.program_config(whole, 128).num_params() \
        == PUBLISHED["parameters_whole_model"] == 29_505_505_264


def test_serving_params_keep_the_maps_float32():
    cfg = MLAConfig.debug_xing(dtype=jnp.bfloat16)
    model = model_for(cfg)
    served = jax.eval_shape(lambda k: model.serving_params(model.init(k)),
                            jax.random.key(0))
    for stack in ("layers", "leading_layers"):
        for sub in ("attn_hc", "mlp_hc"):
            assert {a.dtype for a in jax.tree.leaves(served[stack][sub])} \
                == {jnp.dtype(jnp.float32)}
        assert served[stack]["wq_a"].dtype == jnp.bfloat16
        assert served[stack]["q_norm"].dtype == jnp.float32
    assert served["layers"]["attn_hc"]["phi"].shape == (2, 24, 4 * cfg.dim)


@functools.lru_cache(maxsize=None)
def _one_layer():
    model = model_for(MLAConfig.debug_xing(dtype=jnp.float32))
    return jax.tree.map(lambda a: a[0], jax.jit(model.init)(
        jax.random.key(0))["layers"])


@pytest.mark.parametrize("impl", ["mla_xla", "mla_pallas"])
def test_the_dense_absorbed_kernel_receives_yarns_softmax_scale(
        impl, monkeypatch):
    """``_attend_pages`` without an indexer hands ``mla_decode_attention``
    ``MLAConfig.softmax_scale`` (YaRN's factor squared on it) and not
    ``head_dim ** -0.5``: read off the call, and off its result against
    the expanded form at both scales."""
    from ray_tpu.models import mla
    cfg = MLAConfig.debug_xing(dtype=jnp.float32)
    model = model_for(cfg)
    plain = cfg.head_dim ** -0.5
    assert cfg.softmax_scale == pytest.approx(
        plain * (0.1 * np.log(8.0) + 1.0) ** 2)
    layer = _one_layer()
    B, S, bs = 2, 24, 8
    h = jax.random.normal(jax.random.key(3), (B, S, cfg.dim))
    seen = []
    kernel = mla.mla_decode_attention
    monkeypatch.setattr(mla, "mla_decode_attention", lambda *a, **kw: (
        seen.append(kw["scale"]), kernel(*a, **kw))[1])
    unyarned = model_for(dataclasses.replace(cfg, yarn_mscale_all_dim=0.0))

    @jax.jit
    def three_ways(h, layer):
        q, k_rows, v_rows = model._qkv(h, layer, None, None, lambda a, *_: a)
        expanded = model._attend_rows(q, k_rows, v_rows, layer,
                                      jnp.arange(S), jnp.arange(S))[:, -1]
        pages = (q[:, -1], k_rows.reshape(B * S // bs, bs, -1),
                 v_rows.reshape(B * S // bs, bs, 0), layer,
                 jnp.arange(B * S // bs, dtype=I32).reshape(B, -1),
                 jnp.full((B,), S, I32))
        return (expanded, model._attend_pages(*pages, impl=impl),
                unyarned._attend_pages(*pages, impl=impl))

    with jax.default_matmul_precision("highest"):
        expanded, absorbed, unscaled = three_ways(h, layer)
    assert seen == [cfg.softmax_scale, plain]
    np.testing.assert_allclose(absorbed, expanded, atol=1e-5)
    assert float(jnp.max(jnp.abs(unscaled - expanded))) > 1e-2


@pytest.mark.parametrize("rows,k,n,want", [
    (128, 3584, 1024, (128, 3584, 512)),       # gate, up: a decode step
    (128, 1024, 3584, (128, 1024, 1792)),      # down
    (2048, 3584, 1024, (256, 3584, 512)),      # a 512-token chunk
    (2048, 1024, 3584, (256, 1024, 1792))])
def test_gmm_tiling_at_28_lane_tiles_is_legal(rows, k, n, want, monkeypatch):
    """3,584 = 28 x 128: no power of two, so a k or n tile is 3584, 1792,
    896, 512, 256 or 128; the rule finds one that divides, inside the
    VMEM budget, in two grid steps a group, and the resolver takes it on
    a TPU backend: XLA's own 512 x 512 tiles would make fourteen steps
    of such an expert (tools/moe_gmm_bench.py ``wide`` times both)."""
    from ray_tpu.ops import moe_dispatch
    from ray_tpu.ops.moe_dispatch import (GMM_VMEM_BUDGET, gmm_tiling,
                                          gmm_vmem_bytes,
                                          grouped_matmul_impl, lane_divisors)
    assert lane_divisors(3584) == [3584, 1792, 896, 512, 256, 128]
    tiling = gmm_tiling(rows, k, n, 2)
    assert tiling == want
    tm, tk, tn = tiling
    assert rows % tm == 0 and k % tk == 0 and n % tn == 0
    assert tk % 128 == 0 and tn % 128 == 0
    assert gmm_vmem_bytes(*tiling, 2) <= GMM_VMEM_BUDGET
    assert (k // tk) * (n // tn) == 2
    monkeypatch.setattr(moe_dispatch, "on_chip", lambda: True)
    assert grouped_matmul_impl(rows, k, n, 2) == ("pallas_gmm", want)


@pytest.mark.parametrize("method", ["apply", "forward_step",
                                    "decode_step_paged",
                                    "prefill_with_prefix"])
def test_scopes_are_in_every_lowered_programs_metadata(method):
    cfg = MLAConfig.debug_xing()
    model = model_for(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    two = jnp.zeros((2,), I32)
    toks = jnp.ones((2, 16), I32)
    pool = model.init_kv_pool(9, 8)
    prefix = {n: jnp.zeros((cfg.n_layers, 2, 8) + a.shape[3:], a.dtype)
              for n, a in pool.items()}
    args = {"apply": (params, toks),
            "forward_step": (params, toks, model.init_kv_cache(2, 16), two),
            "decode_step_paged": (params, two, pool, jnp.zeros((2, 4), I32),
                                  two),
            "prefill_with_prefix": (params, toks, prefix["k"], prefix["v"],
                                    two + 8, two + 16)}[method]
    text = jax.jit(getattr(model, method)).lower(*args).as_text(
        debug_info=True)
    for scope in ("mhc_maps", "mhc_mix", "mla_q_down", "mla_q_up"):
        assert scope in text, scope
