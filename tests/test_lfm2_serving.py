"""The hybrid short-convolution / attention expert model
(``ray_tpu/models/lfm2.py``) at debug widths against
``benchmark/reference/lfm2.py``, LOGITS: ``apply``; the bucket prefill of
two rows of different lengths followed by decode steps through the paged
pool and the state rows; chunked prefill (``prefill_with_prefix``); the
faults a tolerance has to refuse; heads of 64 lanes packed in the pool;
the published shape."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import lfm2 as builder
from benchmark.reference import lfm2 as ref
from ray_tpu.models import Lfm2Config, Lfm2Model
from tests import serving_family as serving
from tests.serving_family import (I32, prefill_then_decode, rel_rms,
                                  tokens_of)


def after_serving_params(cfg, model, params, served):
    assert model.runs == [("conv_dense", 0, 2), ("attn_moe", 0, 1),
                          ("conv_moe", 0, 2), ("attn_moe", 1, 1)]
    assert "lm_head" not in served
    assert served["conv_moe"]["conv_w"].dtype == jnp.bfloat16


FAMILY = dataclasses.replace(
    serving.LFM2,
    # attention in the middle, first and last; two leading dense layers
    # and one (``acc``: the first layer is attention AND dense, a fourth
    # kind); runs of one layer and of several
    patterns={
        "ccacca": {},
        "acc-1": dict(pattern="acc", num_dense_layers=1),
        "cca-2": dict(pattern="cca"),
        "cacc-1": dict(pattern="cacc", num_dense_layers=1)},
    f32_leaves=frozenset(
        (stack, n) for stack in ("conv_dense", "conv_moe", "attn_moe")
        for n in ("norm", "ffn_norm", "q_norm", "k_norm", "router",
                  "router_bias")),
    model_class=Lfm2Model, after_serving_params=after_serving_params,
    # float32 compute against the float32 reference: what is left is the
    # order of the sums (honest readings ~1e-6)
    state_f32_tol=2e-5,
    # bf16 compute, the reference fed the same bf16-rounded leaves and the
    # experts ``apply`` chose: the case's seed reads 0.021 (8 bits of
    # mantissa through 6 layers). On two other seeds 0.027 and 0.093: where
    # the bucket prefill and the decode steps break a near-tie otherwise
    # than ``apply`` did, forcing ``apply``'s choice moves the reference
    # away (unforced those read 0.042 and 0.020): a floor of routing, not
    # of arithmetic, which is why the limit stands at twice the reading
    state_bf16_tol=0.04)
ref_forward = functools.partial(serving.reference, FAMILY)
make = functools.partial(serving.make, FAMILY)


def wanted(cfg, params, toks, **kw):
    return serving.wanted(FAMILY, cfg, params, toks, **kw)


globals().update(serving.cases_of(FAMILY))


def test_a_mesh_is_refused_with_a_reason():
    with pytest.raises(NotImplementedError, match="partitioning rule"):
        Lfm2Model(Lfm2Config.debug(), mesh=object())


def test_a_wrong_pattern_is_refused():
    with pytest.raises(ValueError, match="a mixer"):
        Lfm2Config.debug(mixer_types=("conv", "mamba") + ("conv",) * 4)
    with pytest.raises(ValueError, match="dense layers"):
        Lfm2Config.debug(dense_ffn_dim=0)


def test_the_published_shape_as_built():
    """The configuration file's keys through the builder: what the
    program is built with, read off the model (shapes alone)."""
    from benchmark import run as harness
    conf = harness.load_json(harness.ROOT,
                             "benchmark/configs/lfm2-24b-a2b-d10.json")
    model = builder.build_model(conf, 16384)
    cfg = model.cfg
    assert (cfg.n_layers, cfg.vocab_size, cfg.n_kv_heads, cfg.n_heads,
            cfg.head_dim, cfg.dim) == (10, 65536, 8, 32, 64, 2048)
    assert (cfg.dense_ffn_dim, cfg.ffn_dim, cfg.num_experts,
            cfg.expert_top_k, cfg.conv_kernel) == (11776, 1536, 64, 4, 3)
    assert (cfg.router_kind, cfg.router_renorm_eps, cfg.norm_topk_prob,
            cfg.rope_theta, cfg.norm_eps) == ("sigmoid", 1e-6, True, 1e6,
                                              1e-5)
    assert model.runs == [("conv_dense", 0, 2), ("attn_moe", 0, 1),
                          ("conv_moe", 0, 3), ("attn_moe", 1, 1),
                          ("conv_moe", 3, 3)]
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 5_267_090_176
    assert cfg.num_params() == conf["parameters"] == 5_267_090_176
    assert model.state_row_shapes() == {"conv": ((2, 2048), jnp.bfloat16)}
    # heads of 64 lie two to a pool row; a block of 32 rows is copied in
    # runs of 2
    assert model.kv_lane_pack == 2
    assert model.kv_row_shapes() == ((4, 128), (4, 128))
    assert model.paged_run_blocks(32) == 2
    assert model.ffn_load_shape() == (8, 64)
    pool = jax.eval_shape(lambda: model.init_kv_pool(66, 32, 32))
    assert pool["k"].shape == (2, 66, 32, 4, 128)
    assert pool["conv"].shape == (8, 32, 2, 2048)
    assert conf["reduced"].keys() == {"num_hidden_layers", "layer_types",
                                      "max_position_embeddings"}


def test_a_prefill_that_runs_through_the_padding_differs():
    cfg, model, params, toks, _ = serving.honest_state(FAMILY)
    got = prefill_then_decode(model, params, toks, stop_at_lengths=False)
    assert rel_rms(got, wanted(cfg, params, toks)) > 0.05


def test_a_state_that_activation_left_at_zero_differs():
    """The state rows zeroed between the prefill and the first decode
    step (an activation that wrote nothing): the first two positions
    behind each prompt read a filter over zeros."""
    cfg, model, params, toks, _ = serving.honest_state(FAMILY)

    def not_written(step, pool):
        return dict(pool, conv=0 * pool["conv"]) if step == 0 else pool

    got = prefill_then_decode(model, params, toks, handed_on=not_written)
    want = wanted(cfg, params, toks)
    assert rel_rms(got[:, :2], want[:, :2]) > 0.05
    assert rel_rms(got, want) > 1e-3


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_named_fault_is_refused(fault):
    """The reference with one departure against the honest float32
    program: every one moves the logits past 1e-3, three orders over the
    float32 comparison's 2e-5 (``no_renorm`` and ``int8_weights`` the
    least, a few 1e-2)."""
    cfg, model, params, toks, got = serving.honest_state(FAMILY)
    want = wanted(cfg, params, toks, fault=fault)
    assert rel_rms(got, want) > 1e-3
    assert serving.max_abs(got, want) > 1e-3


def test_chunked_prefill_carries_the_state():
    """``prefill_with_prefix`` chunk by chunk (the state the chunk before
    left, the K/V rows gathered as a prefix) gives the last token's
    logits of one forward over the whole prompt."""
    cfg, model, params = make()
    n, chunk = 19, 8
    toks = tokens_of(cfg, (1, n))
    want = ref_forward(cfg, params, toks)[0, n - 1]
    chunked = serving.jitted(model, "prefill_with_prefix")
    pk = jnp.zeros((cfg.attn_layers, 1, 24) + model.kv_row_shapes()[0],
                   jnp.float32)
    pv, state, pos = pk, None, 0
    while pos < n:
        m = min(chunk, n - pos)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :m] = np.asarray(toks)[0, pos:pos + m]
        logits, small = chunked(params, jnp.asarray(padded), pk, pv,
                                jnp.asarray([pos], I32),
                                jnp.asarray([m], I32), state)
        assert set(small) == {"k", "v", "conv"}
        pk = pk.at[:, :, pos:pos + chunk].set(small["k"])
        pv = pv.at[:, :, pos:pos + chunk].set(small["v"])
        state = {"conv": small["conv"]}
        pos += m
    np.testing.assert_allclose(logits[0], want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_heads_of_64_lie_packed_in_the_pool(impl):
    """Two K/V heads of 64 lanes: the cache's rows are ``[1, 128]`` in the
    slot cache, the prefill's rows and the pool alike, and the programs
    give the reference's logits through both decode attentions (the
    kernel, interpreted, reads the pool's pages where they lie)."""
    cfg, model, params = make(n_heads=2, n_kv_heads=2, head_dim=64,
                              decode_attention=impl)
    assert model.kv_lane_pack == 2
    assert model.kv_row_shapes() == ((1, 128), (1, 128))
    assert model.init_kv_pool(5, 4, 2)["k"].shape == (2, 5, 4, 1, 128)
    assert model.init_kv_cache(2, 16)["v"].shape == (2, 2, 16, 1, 128)
    toks = tokens_of(cfg, (2, serving.TB + serving.STEPS))
    got = prefill_then_decode(model, params, toks)
    assert rel_rms(got, wanted(cfg, params, toks)) < 2e-5
    np.testing.assert_allclose(
        serving.full_forward(model, params, toks[:, :21]),
        ref_forward(cfg, params, toks[:, :21]), atol=1e-4, rtol=1e-4)


def test_the_router_takes_the_familys_epsilon():
    """``route_topk``'s renormalisation adds what its caller says: the
    weights of four chosen scores that sum to 1e-5 differ by a tenth
    between 1e-6 and the others' 1e-20."""
    from ray_tpu.ops.moe_dispatch import route_topk
    x = jnp.ones((1, 4), jnp.float32)
    router = jnp.full((4, 8), -3.2, jnp.float32)      # sigmoid ~2.8e-6
    bias = jnp.zeros((8,), jnp.float32)
    kw = dict(top_k=4, norm_topk_prob=True, sigmoid_bias=bias)
    _, scores, w_family, _ = route_topk(x, router, renorm_eps=1e-6, **kw)
    _, _, w_others, _ = route_topk(x, router, **kw)
    total = float(4 * scores[0, 0])
    np.testing.assert_allclose(jnp.sum(w_others), 1.0, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w_family), total / (total + 1e-6),
                               rtol=1e-5)
    assert float(jnp.sum(w_family)) < 0.95
