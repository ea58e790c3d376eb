"""The hybrid state-space model (``ray_tpu/models/nemotron_h.py``) at
debug widths against ``benchmark/reference/nemotron_h.py``: ``apply``,
the bucket prefill of two rows of different lengths followed by decode
steps through pool and state, the faults a tolerance has to refuse, the
share of a layer that four chips share, and the published pattern."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import NemotronHConfig, NemotronHModel, model_for
from tests import serving_family as serving
from tests.serving_family import (I32, BS, prefill_then_decode, rel_rms,
                                  tokens_of)

PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# float32 compute against the float32 reference: what is left is the
# order of the sums (the chunked scan against the positional one): 1e-6.
F32_TOL = 1e-4


def after_serving_params(cfg, model, params, served):
    assert served["lm_head"].dtype == jnp.bfloat16


FAMILY = dataclasses.replace(
    serving.NEMOTRON_H,
    patterns={p: dict(pattern=p) for p in ("MEM*EM", "M", "ME", "*E*M")},
    f32_leaves=frozenset(
        {("mamba", n) for n in ("A_log", "D", "dt_bias", "norm", "gnorm")}
        | {("moe", "router"), ("moe", "router_bias"), ("moe", "norm"),
           ("attn", "norm")}),
    model_class=NemotronHModel, after_serving_params=after_serving_params,
    state_f32_tol=F32_TOL,
    # bf16 compute, S float32, the reference fed the experts the system
    # chose: 8 bits of mantissa through 6 layers read 0.01-0.03
    state_bf16_tol=0.06)
ref_forward = functools.partial(serving.reference, FAMILY)


make = functools.partial(serving.make, FAMILY)


def wanted(cfg, params, toks, **kw):
    return serving.wanted(FAMILY, cfg, params, toks, **kw)


globals().update(serving.cases_of(FAMILY))


PLANTS = ["bf16_state", "dropped_conv_window", "dropped_D", "no_gate",
          "through_padding", "no_conv_bias", "no_scaling"]


@pytest.mark.parametrize("plant", PLANTS)
def test_planted_faults_fail_the_f32_tolerance(plant):
    """What the comparison has to refuse, each planted and each far over
    ``F32_TOL``: S held in bf16 (the state's row shapes overridden here:
    the model has no such option); the convolution's window dropped between
    prefill and decode; ``D x`` dropped; the gate missing; the
    recurrence run through the padding; and two of the reference's. (A
    bf16 S over 8 steps at these widths reads 5e-4, the others 1e-2 and
    more; honest float32 reads 1e-6.)"""
    cfg, model, params, toks, honest = serving.honest_state(FAMILY)
    system, fault = params, None
    if plant == "bf16_state":
        class HoldsBf16(type(model)):
            def state_row_shapes(self):
                shapes = super().state_row_shapes()
                return dict(shapes, ssm=(shapes["ssm"][0], jnp.bfloat16))
        model = HoldsBf16(cfg)
    if plant == "dropped_D":
        system = dict(params, mamba=dict(params["mamba"],
                                         D=0 * params["mamba"]["D"]))
    def drop_the_window(step, pool):
        return pool if step else dict(pool,
                                      conv=jnp.zeros_like(pool["conv"]))

    if plant in ("no_gate", "no_conv_bias", "no_scaling"):
        fault, got = plant, honest          # the reference's
    else:
        got = prefill_then_decode(
            model, system, toks, stop_at_lengths=plant != "through_padding",
            handed_on=drop_the_window if plant == "dropped_conv_window"
            else None)
    err = rel_rms(got, wanted(cfg, params, toks, fault=fault))
    assert err > 3 * F32_TOL, (plant, err)


def test_decode_batch_is_one_row_a_slot_of_the_state():
    """The decode step strides the state's stack by the rows it holds a
    layer; a batch of another size is refused, not misplaced."""
    cfg, model, params = make()
    pool = model.init_kv_pool(5, BS, 3)
    with pytest.raises(ValueError, match="one row a slot"):
        model.decode_step_paged(params, jnp.zeros(2, I32), pool,
                                jnp.zeros((2, 2), I32), jnp.zeros(2, I32))


def test_four_shares_and_the_shared_expert_once_are_the_whole_layer():
    """(g) the guide's share test: the routed parts of all four shares
    (each through ``W_up``, which is linear) plus the shared expert
    counted ONCE equal the uncut reference's whole ``E`` layer."""
    whole_cfg, whole_model, params = make(pattern="E", experts_held=None,
                                          first_expert_held=0)
    toks = tokens_of(whole_cfg, (2, 9))
    want = ref_forward(whole_cfg, params, toks)
    x = params["embed"][toks]
    layer = {k: v[0] for k, v in params["moe"].items()}
    h = whole_model._norm(x, layer["norm"])
    total = jnp.zeros_like(x)
    shared = None
    for first in (0, 2, 4, 6):
        cfg = dataclasses.replace(whole_cfg, experts_held=2,
                                  first_expert_held=first)
        model = model_for(cfg)
        stacks = {n: params["moe"][n][0, first:first + 2]
                  for n in ("e_up", "e_down")}
        out, _ = model._experts(h, layer, stacks, 0)
        if shared is None:
            only = dict(layer, w_lat_up=0 * layer["w_lat_up"])
            shared, _ = model._experts(h, only, stacks, 0)
        total = total + (out - shared)
    x = x + total + shared
    got = whole_model._head(params, x)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_the_published_pattern_builds_and_runs():
    """(i) the published 88 letters at debug widths: ``apply`` over 6
    positions (the walk is the one the shorter patterns are held to the
    reference by)."""
    assert len(PUBLISHED) == 88 and (
        PUBLISHED.count("M"), PUBLISHED.count("E"),
        PUBLISHED.count("*")) == (40, 40, 8)
    assert "MEMEMEMEM*E" in PUBLISHED
    cfg, model, params = make(pattern=PUBLISHED, dim=16, latent_dim=8,
                              ffn_dim=8, shared_ffn_dim=8, mamba_heads=4,
                              vocab_size=64)
    toks = tokens_of(cfg, (1, 6))
    got = jax.jit(model.apply)(params, toks)
    assert got.shape == (1, 6, 64) and bool(jnp.all(jnp.isfinite(got)))
    assert model.ffn_load_shape() == (40, 8)
    assert {name: a.shape[0] for name, a in model.init_state(1).items()} \
        == {"conv": 40, "ssm": 40}


def test_any_string_over_the_three_letters_and_no_other():
    with pytest.raises(ValueError, match="pattern"):
        NemotronHConfig.debug_hybrid("MEX")
    with pytest.raises(NotImplementedError, match="one chip"):
        NemotronHModel(NemotronHConfig.debug_hybrid("ME"), mesh=object())
