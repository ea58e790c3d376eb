"""The hybrid Mamba-1 model (``ray_tpu/models/jamba.py``) at debug widths
against ``benchmark/reference/jamba.py``, LOGITS: ``apply``; the bucket
prefill of two rows of different lengths followed by decode steps through
the paged pool and the state rows; chunked prefill; the faults a tolerance
has to refuse; the published shape and the size of the programs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.builders import jamba as builder
from benchmark.reference import jamba as ref
from ray_tpu.models import JambaConfig, JambaModel, model_for

I32 = jnp.int32


def make(seed=1, **kw):
    cfg = JambaConfig.debug(**kw)
    model = model_for(cfg)
    return cfg, model, model.init(jax.random.key(seed))


def ref_kwargs(cfg):
    return dict(n_layers=cfg.n_layers, attn_layer_period=cfg.attn_period,
                attn_layer_offset=cfg.attn_offset, num_heads=cfg.n_heads,
                num_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                d_state=cfg.ssm_state, dt_rank=cfg.dt_rank, eps=cfg.norm_eps)


def ref_forward(cfg, params, tokens, **kw):
    return ref.forward(builder.reference_params({}, params), tokens,
                       **ref_kwargs(cfg), **kw)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def tokens_of(cfg, shape, seed=2):
    return jax.random.randint(jax.random.key(seed), shape, 1, cfg.vocab_size)


@pytest.mark.parametrize("layers,period,offset", [
    (5, 3, 1), (1, 3, 1), (2, 2, 0), (7, 4, 3)],
    ids=["MAMMA", "M", "AM", "MMMAMMM"])
def test_apply_is_the_reference(layers, period, offset):
    """float32 compute: logits to 1e-4 of the reference's, whose
    recurrence is positional in ``[inner, N]`` where the program's is the
    scan (kernel interpreted) in ``[N, inner]``; runs of one layer and of
    several, attention first, last and in the middle."""
    cfg, model, params = make(n_layers=layers, attn_period=period,
                              attn_offset=offset)
    toks = tokens_of(cfg, (2, 21))
    got = jax.jit(model.apply)(params, toks)
    np.testing.assert_allclose(got, ref_forward(cfg, params, toks),
                               atol=1e-4, rtol=1e-4)


def test_model_for_and_serving_params_dtypes():
    cfg, model, params = make(dtype=jnp.bfloat16)
    assert isinstance(model, JambaModel) and model.recurrent
    assert model.runs == [("mamba", 0, 1), ("attn", 0, 1), ("mamba", 1, 2),
                          ("attn", 1, 1)]
    served = model.serving_params(params)
    f32 = {"A_log", "D", "b_dt", "norm", "ffn_norm", "dt_norm", "b_norm",
           "c_norm"}
    for stack in ("mamba", "attn"):
        for name, a in served[stack].items():
            want = jnp.float32 if name in f32 else jnp.bfloat16
            assert a.dtype == want, (stack, name)
    assert served["norm_f"].dtype == jnp.float32
    assert served["embed"].dtype == jnp.bfloat16 and "lm_head" not in served
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    assert model.serving_params(served)["mamba"]["w_in"] is (
        served["mamba"]["w_in"])


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


LENS, TB, STEPS, BS = (13, 7), 16, 8, 4


def prefill_then_decode(model, params, toks, *, lengths=True, impl=None,
                        state_dtype=None):
    """TWO rows of different lengths in one padded bucket, the prefill's
    pages and state placed as the engine places them, then decode steps
    over the whole cache tree: logits [2, STEPS, V] of the positions
    behind each row's own prompt."""
    if impl is not None:
        model = model_for(dataclasses.replace(model.cfg,
                                              decode_attention=impl))
    lens = np.asarray(LENS)
    padded = np.zeros((2, TB), np.int32)
    for r in range(2):
        padded[r, :lens[r]] = np.asarray(toks)[r, :lens[r]]
    _, small = jax.jit(model.forward_step)(
        params, jnp.asarray(padded), model.init_kv_cache(2, TB),
        jnp.zeros(2, I32), jnp.asarray(lens) if lengths else None)
    nb = -(-(TB + STEPS) // BS)
    pool = model.init_kv_pool(2 * nb + 1, BS, 2)
    ids = np.arange(2 * nb).reshape(2, nb)
    La = small["k"].shape[0]

    def blocks(x):
        return x.reshape(La, 2 * (TB // BS), BS, *x.shape[3:])

    at = ids[:, :TB // BS].reshape(-1)
    pool = dict(pool, k=pool["k"].at[:, at].set(blocks(small["k"])),
                v=pool["v"].at[:, at].set(blocks(small["v"])),
                conv=small["conv"], ssm=small["ssm"])
    decode = jax.jit(model.decode_step_paged)
    got = []
    for i in range(STEPS):
        tok = jnp.asarray([np.asarray(toks)[r, lens[r] + i]
                           for r in range(2)])
        if state_dtype is not None:       # S handed on in too few bits
            pool["ssm"] = pool["ssm"].astype(state_dtype).astype(jnp.float32)
        logits, pool = decode(params, tok, pool, jnp.asarray(ids),
                              jnp.asarray(lens + i))
        got.append(logits)
    return jnp.stack(got, 1)


def wanted(cfg, params, toks, **kw):
    want = ref_forward(cfg, params, toks, **kw)
    return jnp.stack([want[r, n:n + STEPS] for r, n in enumerate(LENS)])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_paged_decode_is_the_reference_float32(impl):
    """float32 compute against the float32 reference, tightly: the scan
    stops each row at its length, the state rows and pages land where the
    decode step reads them, both implementations of both kernels."""
    cfg, model, params = make()
    toks = tokens_of(cfg, (2, TB + STEPS))
    got = prefill_then_decode(model, params, toks, impl=impl)
    assert rel_rms(got, wanted(cfg, params, toks)) < 2e-5


def test_prefill_then_paged_decode_bf16_at_a_stated_tolerance():
    """bf16 compute (S float32) against the float32 reference fed the
    same bf16-rounded leaves: 0.04 relative RMS at these widths (honest
    readings 0.017-0.023 on three seeds)."""
    cfg, model, params = make(dtype=jnp.bfloat16)
    served = model.serving_params(params)
    toks = tokens_of(cfg, (2, TB + STEPS))
    got = prefill_then_decode(model, served, toks)
    assert rel_rms(got, wanted(cfg, served, toks)) < 0.04


def test_a_prefill_that_runs_through_the_padding_differs():
    cfg, model, params = make()
    toks = tokens_of(cfg, (2, TB + STEPS))
    got = prefill_then_decode(model, params, toks, lengths=False)
    assert rel_rms(got, wanted(cfg, params, toks)) > 0.05


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_named_fault_is_refused(fault):
    """The reference with one departure against the honest float32
    program: every one reads well above the float32 comparison's 2e-5
    (``bf16_state`` the least: a rounding of 2^-9 a step)."""
    cfg, model, params = make()
    toks = tokens_of(cfg, (2, TB + STEPS))
    got = prefill_then_decode(model, params, toks)
    floor = 1e-3 if fault in ("bf16_state", "int8_weights") else 0.02
    assert rel_rms(got, wanted(cfg, params, toks, fault=fault)) > floor


def test_a_state_handed_on_in_bf16_differs_from_the_float32_state():
    cfg, model, params = make()
    toks = tokens_of(cfg, (2, TB + STEPS))
    got = prefill_then_decode(model, params, toks, state_dtype=jnp.bfloat16)
    assert rel_rms(got, wanted(cfg, params, toks)) > 5e-4


def test_chunked_prefill_carries_the_state():
    """``prefill_with_prefix`` chunk by chunk (the state the chunk before
    left, the K/V rows gathered as a prefix) gives the last token's
    logits of one forward over the whole prompt."""
    cfg, model, params = make()
    n, chunk = 19, 8
    toks = tokens_of(cfg, (1, n))
    want = ref_forward(cfg, params, toks)[0, n - 1]
    chunked = jax.jit(model.prefill_with_prefix)
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
