"""GPT-2, ViT, MoE model families."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import (GPT2Config, GPT2Model, MoEConfig, MoEModel,
                            ViTConfig, ViTModel)
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.train.spmd import make_train_step, shard_batch


def test_gpt2_forward_and_training():
    cfg = GPT2Config.debug()
    model = GPT2Model(cfg)
    ts = make_train_step(model, optimizer=optax.adam(1e-3))
    params, opt = ts.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
    losses = []
    for _ in range(8):
        params, opt, m = ts.step_fn(params, opt, (toks,
                                                  jnp.roll(toks, -1, 1)))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_gpt2_causality():
    cfg = GPT2Config.debug()
    model = GPT2Model(cfg)
    params = model.init(jax.random.key(0))
    t1 = jnp.zeros((1, 16), jnp.int32)
    t2 = t1.at[0, 12].set(9)
    l1, l2 = model.apply(params, t1), model.apply(params, t2)
    np.testing.assert_allclose(np.asarray(l1[0, :12]),
                               np.asarray(l2[0, :12]), atol=1e-4)


@functools.cache
def _gpt2_gradient(kept, dtype):
    """(loss, gradients) and the gradient's jaxpr of a debug GPT-2 whose
    layer scan keeps the first ``kept`` of its candidates (None: no
    remat), the fused kernels interpreted."""
    from ray_tpu.models import gpt2

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gpt2, "residuals_that_fit", lambda *a: kept)
        model = GPT2Model(dataclasses.replace(
            GPT2Config.debug(), dtype=dtype, remat=kept is not None))
        model._use_flash = True
        params = model.init(jax.random.key(0))
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, model.cfg.vocab_size, (2, 128)), jnp.int32)
        fn = jax.value_and_grad(model.loss)
        args = (params, tokens, jnp.roll(tokens, -1, 1))
        return jax.jit(fn)(*args), str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("kept,kernel_calls", [
    (0, 3), (1, 2), (3, 2), (4, 2), (None, 2)],
    ids=["none", "o_lse", "o_lse_qkv_wo", "all_four", "remat_off"])
def test_gpt2_gradients_do_not_depend_on_what_the_scan_keeps(
        dtype, kept, kernel_calls):
    """Loss and every gradient leaf against the scan that keeps
    ``o`` + log-sum-exp + the qkv product: a kept tensor is the remade one.
    The fused kernels run interpreted, so the forward rule's names are in
    the program: the forward kernel is in the gradient twice only where
    the scan does not keep its outputs."""
    want, _ = _gpt2_gradient(2, dtype)
    got, program = _gpt2_gradient(kept, dtype)
    assert program.count("pallas_call[") == kernel_calls
    # not exactly: the last bit of one bias's float32 gradient, where XLA
    # orders the reduction by what it was fused with
    tol = dict(rtol=1e-6, atol=1e-7)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=tol["rtol"])
    for path, a in jax.tree_util.tree_leaves_with_path(got[1]):
        b = want[1]
        for key in path:
            b = b[key.key]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol,
                                   err_msg=jax.tree_util.keystr(path))


V5E_BYTES = int(15.75 * 2**30)


# what the v5e's compiler places of GPT-2 medium's train step at 1,024
# positions (PERF.md PR 49): all four up to a batch of 14 (refused at
# 16), without w_up's product up to 24 (28), o + lse + qkv up to 28
# (32), o + lse at 32; with nothing kept 32 (48)
@pytest.mark.parametrize("batch,capacity,kept", [
    (8, V5E_BYTES, ("attention", "qkv", "wo", "w_up")),
    (12, V5E_BYTES, ("attention", "qkv", "wo", "w_up")),
    (14, V5E_BYTES, ("attention", "qkv", "wo", "w_up")),
    (16, V5E_BYTES, ("attention", "qkv", "wo")),    # train_1chip
    (20, V5E_BYTES, ("attention", "qkv")),
    (24, V5E_BYTES, ("attention",)),
    (28, V5E_BYTES, ("attention",)),
    (32, V5E_BYTES, ("attention",)),
    (48, V5E_BYTES, ()),
    (16, 2**30, ()),                    # less than the state: never raises
    (16, 2 * V5E_BYTES, ("attention", "qkv", "wo", "w_up")),
    (16, None, ()),                     # a CPU: ``remat`` as it always was
])
def test_gpt2_scan_keeps_the_prefix_that_fits(monkeypatch, batch, capacity,
                                              kept):
    """The rule at GPT-2 medium's widths: sizes from the batch's shape
    and ``cfg``, capacity from the device, never more than the compiler
    is known to place (``tests/test_chip_smoke.py`` compiles two)."""
    from ray_tpu.models import gpt2
    from ray_tpu.util import metrics

    monkeypatch.setattr(gpt2, "_chip_bytes", lambda: capacity)
    model = GPT2Model(GPT2Config(vocab_size=50_257, dim=1024, n_layers=24,
                                 n_heads=16, max_seq_len=1024))
    # the policy speaks where something under it is differentiated
    keeping = model._keeping(batch, 1024)
    jax.eval_shape(jax.grad(jax.checkpoint(jnp.sin, policy=keeping)), 1.0)
    stacks = {dict(tags)["name"]: size for tags, size in metrics.Gauge(
        "train.kept_residual_bytes").samples()}
    assert tuple(name for name, size in stacks.items() if size) == kept
    MiB = 2**20 * 24 * batch // 16      # a layer's, at the cell's batch
    want = dict(attention=33 * MiB, qkv=96 * MiB, wo=32 * MiB,
                w_up=128 * MiB)
    assert {n: stacks[n] for n in kept} == {n: want[n] for n in kept}


def test_gpt2_scan_keeps_nothing_under_a_mesh(monkeypatch):
    """A chip's share of each term is the shardings' to say: as before."""
    from ray_tpu.models import gpt2

    monkeypatch.setattr(gpt2, "_chip_bytes", lambda: V5E_BYTES)
    asked = []
    monkeypatch.setattr(gpt2, "residuals_that_fit",
                        lambda *a: asked.append(a) or 0)
    mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    cfg = dataclasses.replace(GPT2Config.debug(), remat=True)
    GPT2Model(cfg, mesh=mesh)._keeping(2, 32)
    GPT2Model(cfg)._keeping(2, 32)
    assert [a[-1] for a in asked] == [None, V5E_BYTES]


def test_vit_forward_and_training():
    cfg = ViTConfig.debug()
    model = ViTModel(cfg)
    ts = make_train_step(model, optimizer=optax.adam(1e-3))
    params, opt = ts.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.normal(size=(8, 32, 32, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32)
    logits = model.apply(params, imgs)
    assert logits.shape == (8, 10)
    losses = []
    for _ in range(10):
        params, opt, m = ts.step_fn(params, opt, (imgs, labels))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_moe_forward_loss_and_training():
    cfg = MoEConfig.debug_moe()
    model = MoEModel(cfg)
    ts = make_train_step(model, optimizer=optax.adam(1e-3))
    params, opt = ts.init_fn(jax.random.key(0))
    assert "e_gate" in params["layers"] and "w_gate" not in params["layers"]
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
    losses = []
    for _ in range(8):
        params, opt, m = ts.step_fn(params, opt, (toks,
                                                  jnp.roll(toks, -1, 1)))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


def test_moe_expert_parallel_sharded_step():
    """MoE trains over an ep=4 mesh axis; experts sharded."""
    spec = MeshSpec.auto(8, ep=4)
    mesh = build_mesh(spec, jax.devices()[:8])
    cfg = MoEConfig.debug_moe(num_experts=4)
    model = MoEModel(cfg, mesh=mesh)
    ts = make_train_step(model, mesh=mesh)
    params, opt = ts.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    batch = shard_batch((toks, jnp.roll(toks, -1, 1)), ts)
    params, opt, m = ts.step_fn(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    # expert weights actually sharded over ep
    sh = jax.tree.leaves(ts.param_shardings)
    e_gate_sharding = ts.param_shardings["layers"]["e_gate"]
    assert "ep" in str(e_gate_sharding.spec)


def test_moe_router_balance_loss_positive():
    cfg = MoEConfig.debug_moe()
    model = MoEModel(cfg)
    params = model.init(jax.random.key(0))
    toks = jnp.zeros((1, 16), jnp.int32)
    logits, aux = model.apply_with_aux(params, toks)
    assert float(aux) > 0
    assert logits.shape == (1, 16, cfg.vocab_size)
