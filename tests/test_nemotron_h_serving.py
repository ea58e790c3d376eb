"""The hybrid state-space model (``ray_tpu/models/nemotron_h.py``) at
debug widths against ``benchmark/reference/nemotron_h.py``: ``apply``,
the bucket prefill of two rows of different lengths followed by decode
steps through pool and state, the faults a tolerance has to refuse, the
share of a layer that four chips share, and the published pattern."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import nemotron_h as builder
from benchmark.reference import nemotron_h as ref
from ray_tpu.models import NemotronHConfig, NemotronHModel, model_for

I32 = jnp.int32
PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def make(pattern="MEM*EM", seed=1, **kw):
    """(cfg, model, float32 params) at debug widths: experts 2-5 of 8
    held, float32 compute unless ``dtype`` says otherwise."""
    kw.setdefault("experts_held", 4)
    kw.setdefault("first_expert_held", 2)
    cfg = NemotronHConfig.debug_hybrid(pattern, **kw)
    model = model_for(cfg)
    return cfg, model, model.init(jax.random.key(seed))


def ref_kwargs(cfg):
    return dict(
        pattern=cfg.pattern, mamba_heads=cfg.mamba_heads,
        mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.ssm_groups,
        ssm_state=cfg.ssm_state, num_heads=cfg.n_heads,
        num_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        top_k=cfg.expert_top_k,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, eps=cfg.norm_eps,
        experts_held=cfg.held)


def ref_forward(cfg, params, tokens, **kw):
    return ref.forward(builder.reference_params({}, params), tokens,
                       **ref_kwargs(cfg), **kw)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def tokens_of(cfg, shape, seed=2):
    return jax.random.randint(jax.random.key(seed), shape, 1, cfg.vocab_size)


@pytest.mark.parametrize("pattern", ["MEM*EM", "M", "ME", "*E*M"])
def test_apply_is_the_reference(pattern):
    """(a) float32 compute: logits to 1e-4 of the reference's, whose
    recurrence is positional where ``apply``'s is chunked (21 positions,
    chunks of 8: the last one padded)."""
    cfg, model, params = make(pattern)
    toks = tokens_of(cfg, (2, 21))
    got = jax.jit(model.apply)(params, toks)
    np.testing.assert_allclose(got, ref_forward(cfg, params, toks),
                               atol=1e-4, rtol=1e-4)


def test_model_for_and_serving_params_dtypes():
    cfg, model, params = make(dtype=jnp.bfloat16)
    assert isinstance(model, NemotronHModel) and model.recurrent
    served = model.serving_params(params)
    f32 = {("mamba", n) for n in ("A_log", "D", "dt_bias", "norm", "gnorm")}
    f32 |= {("moe", "router"), ("moe", "router_bias"), ("moe", "norm"),
            ("attn", "norm")}
    for stack in ("mamba", "attn", "moe"):
        for name, a in served[stack].items():
            want = jnp.float32 if (stack, name) in f32 else jnp.bfloat16
            assert a.dtype == want, (stack, name)
    assert served["norm_f"].dtype == jnp.float32
    assert served["embed"].dtype == served["lm_head"].dtype == jnp.bfloat16
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    assert model.serving_params(served)["mamba"]["w_in"] is (
        served["mamba"]["w_in"])


LENS, TB, STEPS, BS = (13, 7), 16, 8, 4


def prefill_then_decode(model, params, toks, *, lengths=True, plant=None,
                        impl=None):
    """(c) TWO rows of different lengths in one padded bucket, the
    prefill's pages and state placed as the engine places them, then
    decode steps over the whole cache tree: logits [2, STEPS, V] of the
    positions behind each row's own prompt."""
    if impl is not None:
        model = model_for(dataclasses.replace(model.cfg,
                                              decode_attention=impl))
    lens = np.asarray(LENS)
    padded = np.zeros((2, TB), np.int32)
    for r in range(2):
        padded[r, :lens[r]] = np.asarray(toks)[r, :lens[r]]
    cache = model.init_kv_cache(2, TB)
    _, small = jax.jit(model.forward_step)(
        params, jnp.asarray(padded), cache, jnp.zeros(2, I32),
        jnp.asarray(lens) if lengths else None)
    nb = -(-(TB + STEPS) // BS)
    pool = model.init_kv_pool(2 * nb + 1, BS, 2)
    ids = np.arange(2 * nb).reshape(2, nb)
    La = small["k"].shape[0]

    def blocks(x):
        return x.reshape(La, 2 * (TB // BS), BS, *x.shape[3:])

    at = ids[:, :TB // BS].reshape(-1)
    pool = dict(pool, k=pool["k"].at[:, at].set(blocks(small["k"])),
                v=pool["v"].at[:, at].set(blocks(small["v"])),
                conv=small["conv"], ssm=small["ssm"].astype(
                    pool["ssm"].dtype))
    if plant == "dropped_conv_window":
        pool["conv"] = jnp.zeros_like(pool["conv"])
    decode = jax.jit(model.decode_step_paged)
    got = []
    for i in range(STEPS):
        tok = jnp.asarray([np.asarray(toks)[r, lens[r] + i]
                           for r in range(2)])
        logits, pool = decode(params, tok, pool, jnp.asarray(ids),
                              jnp.asarray(lens + i))
        got.append(logits)
    return jnp.stack(got, 1)


def wanted(cfg, params, toks, **kw):
    want = ref_forward(cfg, params, toks, **kw)
    return jnp.stack([want[r, n:n + STEPS] for r, n in enumerate(LENS)])


# float32 compute against the float32 reference: what is left is the
# order of the sums (the chunked scan against the positional one): 1e-6.
F32_TOL = 1e-4


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_two_lengths_then_decode_is_the_reference_f32(impl):
    cfg, model, params = make()
    toks = tokens_of(cfg, (2, 24))
    got = prefill_then_decode(model, params, toks, impl=impl)
    assert rel_rms(got, wanted(cfg, params, toks)) < F32_TOL


def test_prefill_two_lengths_then_decode_bf16_compute():
    """bf16 compute, S float32: the reference is fed the experts the
    system chose (``apply`` in the system's arithmetic), so that what is
    measured is the arithmetic and not the router's near-ties: 8 bits of
    mantissa through 6 layers read 0.01-0.03; the limit is 0.06."""
    cfg, model, params = make(dtype=jnp.bfloat16)
    served = model.serving_params(params)
    toks = tokens_of(cfg, (2, 24))
    _, extras = jax.jit(model._apply_with_extras)(served, toks)
    got = prefill_then_decode(model, served, toks)
    want = wanted(cfg, served, toks, forced_experts=extras["experts"])
    assert rel_rms(got, want) < 0.06


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
    cfg, model, params = make()
    toks = tokens_of(cfg, (2, 24))
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
    if plant in ("no_gate", "no_conv_bias", "no_scaling"):
        fault = plant
    got = prefill_then_decode(
        model, system, toks, lengths=plant != "through_padding",
        plant=plant)
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
    whole_cfg, whole_model, params = make("E", experts_held=None,
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
    cfg, model, params = make(PUBLISHED, dim=16, latent_dim=8, ffn_dim=8,
                              shared_ffn_dim=8, mamba_heads=4,
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
