"""The hybrid Mamba-1 model (``ray_tpu/models/jamba.py``) at debug widths
against ``benchmark/reference/jamba.py``, LOGITS: ``apply``; the bucket
prefill of two rows of different lengths followed by decode steps through
the paged pool and the state rows; chunked prefill; the faults a tolerance
has to refuse; the published shape and the size of the programs."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import jamba as builder
from benchmark.reference import jamba as ref
from ray_tpu.models import JambaConfig, JambaModel, model_for
from tests import serving_family as serving
from tests.serving_family import (I32, prefill_then_decode, rel_rms,
                                  tokens_of)


def after_serving_params(cfg, model, params, served):
    assert model.runs == [("mamba", 0, 1), ("attn", 0, 1), ("mamba", 1, 2),
                          ("attn", 1, 1)]
    assert "lm_head" not in served


FAMILY = dataclasses.replace(
    serving.JAMBA,
    # runs of one layer and of several, attention first, last and in the
    # middle; the reference's recurrence is positional in ``[inner, N]``
    # where the program's is the scan (kernel interpreted) in ``[N, inner]``
    patterns={
        "MAMMA": dict(n_layers=5, attn_period=3, attn_offset=1),
        "M": dict(n_layers=1, attn_period=3, attn_offset=1),
        "AM": dict(n_layers=2, attn_period=2, attn_offset=0),
        "MMMAMMM": dict(n_layers=7, attn_period=4, attn_offset=3)},
    f32_leaves=frozenset(
        (stack, n) for stack in ("mamba", "attn") for n in (
            "A_log", "D", "b_dt", "norm", "ffn_norm", "dt_norm", "b_norm",
            "c_norm")),
    model_class=JambaModel, after_serving_params=after_serving_params,
    state_f32_tol=2e-5,
    # bf16 compute: 0.04 relative RMS at these widths (honest readings
    # 0.017-0.023 on three seeds)
    state_bf16_tol=0.04)
ref_forward = functools.partial(serving.reference, FAMILY)


make = functools.partial(serving.make, FAMILY)


def wanted(cfg, params, toks, **kw):
    return serving.wanted(FAMILY, cfg, params, toks, **kw)


globals().update(serving.cases_of(FAMILY))


def test_a_mesh_is_refused_with_a_reason():
    with pytest.raises(NotImplementedError, match="partitioning rule"):
        JambaModel(JambaConfig.debug(), mesh=object())


def test_the_published_shape_as_built():
    """The configuration file's keys through the builder: what the
    program is built with, read off the model (shapes alone)."""
    from benchmark import run as harness
    conf = harness.load_json(harness.ROOT, "benchmark/configs/jamba2-3b.json")
    model = builder.build_model(conf, 24576)
    cfg = model.cfg
    assert (cfg.n_layers, cfg.vocab_size, cfg.n_kv_heads, cfg.n_heads,
            cfg.head_dim) == (28, 65536, 1, 20, 128)
    assert (cfg.ssm_state, cfg.dt_rank, cfg.mamba_inner, cfg.ffn_dim) == (
        16, 160, 5120, 8192)
    assert model.runs == [("mamba", 0, 7), ("attn", 0, 1), ("mamba", 7, 13),
                          ("attn", 1, 1), ("mamba", 20, 6)]
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 3_029_337_472
    assert cfg.num_params() == conf["parameters"] == 3_029_337_472
    assert model.state_row_shapes() == {
        "conv": ((3, 5120), jnp.bfloat16),
        "ssm": ((1, 16, 5120), jnp.float32)}
    assert conf["reduced"].keys() == {"max_position_embeddings"}


def test_a_prefill_that_runs_through_the_padding_differs():
    cfg, model, params, toks, _ = serving.honest_state(FAMILY)
    got = prefill_then_decode(model, params, toks, stop_at_lengths=False)
    assert rel_rms(got, wanted(cfg, params, toks)) > 0.05


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_named_fault_is_refused(fault):
    """The reference with one departure against the honest float32
    program: every one reads well above the float32 comparison's 2e-5
    (``bf16_state`` the least: a rounding of 2^-9 a step)."""
    cfg, model, params, toks, got = serving.honest_state(FAMILY)
    floor = 1e-3 if fault in ("bf16_state", "int8_weights") else 0.02
    assert rel_rms(got, wanted(cfg, params, toks, fault=fault)) > floor


def test_a_state_handed_on_in_bf16_differs_from_the_float32_state():
    cfg, model, params, toks, _ = serving.honest_state(FAMILY)

    def in_bf16(step, pool):            # S handed on in too few bits
        return dict(pool, ssm=pool["ssm"].astype(jnp.bfloat16).astype(
            jnp.float32))

    got = prefill_then_decode(model, params, toks, handed_on=in_bf16)
    assert rel_rms(got, wanted(cfg, params, toks)) > 5e-4


def test_chunked_prefill_carries_the_state():
    """``prefill_with_prefix`` chunk by chunk (the state the chunk before
    left, the K/V rows gathered as a prefix) gives the last token's
    logits of one forward over the whole prompt."""
    cfg, model, params = make()
    n, chunk = 19, 8
    toks = tokens_of(cfg, (1, n))
    want = ref_forward(cfg, params, toks)[0, n - 1]
    chunked = serving.jitted(model, "prefill_with_prefix")
    La = cfg.attn_layers
    pk = jnp.zeros((La, 1, 24, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    pv, state, pos = pk, None, 0
    while pos < n:
        m = min(chunk, n - pos)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :m] = np.asarray(toks)[0, pos:pos + m]
        logits, small = chunked(params, jnp.asarray(padded), pk, pv,
                                jnp.asarray([pos], I32),
                                jnp.asarray([m], I32), state)
        pk = pk.at[:, :, pos:pos + chunk].set(small["k"])
        pv = pv.at[:, :, pos:pos + chunk].set(small["v"])
        state = {name: small[name] for name in ("conv", "ssm")}
        pos += m
    np.testing.assert_allclose(logits[0], want, atol=1e-4, rtol=1e-4)


def layer_bodies(jaxpr, found=None):
    """``dot_general``s whose contraction is the SwiGLU's down projection
    ([.., ffn] x [ffn, dim]): one a layer BODY in the program's jaxpr,
    however often a scan runs it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            layer_bodies(sub, found)
        if eqn.primitive.name == "dot_general":
            found.append(tuple(v.aval.shape for v in eqn.invars))
    return found


def test_the_programs_do_not_grow_with_depth():
    """28 layers of two sublayers: the decode program holds a layer body
    a RUN (five), not 28, and as many at 56 layers of the same pattern."""
    counts = []
    for layers in (28, 56):
        cfg = JambaConfig.debug(n_layers=layers, attn_period=14,
                                attn_offset=7, max_seq_len=64)
        model = model_for(cfg)
        params = jax.eval_shape(model.init, jax.random.key(0))
        pool = jax.eval_shape(lambda: model.init_kv_pool(9, 4, 2))
        jaxpr = jax.make_jaxpr(model.decode_step_paged)(
            params, jnp.zeros(2, I32), pool, jnp.zeros((2, 4), I32),
            jnp.zeros(2, I32))
        down = [s for s in layer_bodies(jaxpr.jaxpr)
                if s[1] == (cfg.ffn_dim, cfg.dim)]
        counts.append(len(down))
        assert len(model.runs) == (5 if layers == 28 else 9)
    assert counts == [5, 9]       # a body a run: 28 layers are five bodies
