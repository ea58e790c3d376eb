"""Model layer: Llama forward/loss/sharded training, MLP."""

import pytest
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import LlamaConfig, LlamaModel, MLPConfig, MLPModel
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.train.spmd import make_train_step, shard_batch


def _tiny():
    return LlamaConfig.debug(vocab_size=128, max_seq_len=64)


def test_llama_forward_shape_and_finite():
    cfg = _tiny()
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_llama_loss_decreases_under_training():
    cfg = _tiny()
    model = LlamaModel(cfg)
    ts = make_train_step(model)
    params, opt = ts.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    targets = jnp.roll(tokens, -1, 1)
    losses = []
    for _ in range(10):
        params, opt, m = ts.step_fn(params, opt, (tokens, targets))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_llama_causality():
    """Changing a future token must not change past logits."""
    cfg = _tiny()
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 10].set(5)
    l1 = model.apply(params, t1)
    l2 = model.apply(params, t2)
    np.testing.assert_allclose(np.asarray(l1[0, :10]),
                               np.asarray(l2[0, :10]), atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_llama_num_params_matches_tree():
    cfg = _tiny()
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == cfg.num_params()


def test_llama_sharded_train_step_tp_sp_fsdp():
    """Full 8-device sharded step: tp=2, sp=2, fsdp=2."""
    spec = MeshSpec.auto(8, tp=2, sp=2, fsdp=2)
    mesh = build_mesh(spec, jax.devices()[:8])
    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=64, remat=False)
    model = LlamaModel(cfg, mesh=mesh)
    ts = make_train_step(model, mesh=mesh)
    params, opt = ts.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 64)), jnp.int32)
    batch = shard_batch((tokens, jnp.roll(tokens, -1, 1)), ts)
    params, opt, m = ts.step_fn(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    # sharded == unsharded result (same seed, single step)
    model0 = LlamaModel(cfg)
    ts0 = make_train_step(model0)
    p0, o0 = ts0.init_fn(jax.random.key(0))
    _, _, m0 = ts0.step_fn(p0, o0, (tokens, jnp.roll(tokens, -1, 1)))
    np.testing.assert_allclose(float(m["loss"]), float(m0["loss"]),
                               rtol=2e-3)


def test_mlp_trains_to_fit_random_data():
    import optax
    model = MLPModel(MLPConfig(in_dim=16, hidden=(32,), num_classes=4))
    ts = make_train_step(model, optimizer=optax.adam(1e-2))
    params, opt = ts.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, (64,)), jnp.int32)
    for _ in range(150):
        params, opt, m = ts.step_fn(params, opt, (x, y))
    assert float(model.accuracy(params, x, y)) > 0.9


def test_graft_entry_contract():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert np.all(np.isfinite(np.asarray(out)))
    ge.dryrun_multichip(8)


def test_llama_remat_policy_dots_matches_full():
    """remat_policy='dots' (save matmul outputs) must be numerically
    identical to full remat — it only changes what is recomputed."""
    import dataclasses

    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    cfg_full = LlamaConfig.debug(vocab_size=128, max_seq_len=32)
    cfg_full = dataclasses.replace(cfg_full, remat=True)
    cfg_dots = dataclasses.replace(cfg_full, remat_policy="dots")
    m_full, m_dots = LlamaModel(cfg_full), LlamaModel(cfg_dots)
    params = m_full.init(jax.random.key(0))
    toks = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    gf = jax.grad(lambda p: m_full.loss(p, toks, tgts))(params)
    gd = jax.grad(lambda p: m_dots.loss(p, toks, tgts))(params)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="remat_policy"):
        dataclasses.replace(cfg_full, remat_policy="bogus")


def test_llama_attention_impl_parity():
    """ring / xla-blockwise / flash (custom tile sizes) must agree on
    the loss (f32 so near-ties cannot hide real divergence)."""
    import dataclasses

    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    base = dataclasses.replace(
        LlamaConfig.debug(vocab_size=128, max_seq_len=64),
        dtype=jnp.float32)
    toks = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8] * 8], jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    losses = {}
    for impl in ("ring", "xla", "flash"):
        cfg = dataclasses.replace(base, attention_impl=impl)
        m = LlamaModel(cfg)
        p = m.init(jax.random.key(0))
        losses[impl] = float(m.loss(p, toks, tgts))
    assert abs(losses["xla"] - losses["ring"]) < 1e-4, losses
    assert abs(losses["flash"] - losses["ring"]) < 1e-3, losses
