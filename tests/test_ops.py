"""Ops layer: norms, rope, attention, ring attention (8 virtual devices)."""

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (apply_rope, attention, layer_norm, ring_attention,
                         rms_norm, rope_frequencies)
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.ring_attention import ring_attention_sharded


def test_rms_norm_matches_numpy():
    x = np.random.default_rng(0).normal(size=(2, 8, 16)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(16,)).astype(np.float32)
    out = rms_norm(jnp.asarray(x), jnp.asarray(w), eps=1e-5)
    expect = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(out, expect, rtol=1e-5)


def test_layer_norm_zero_mean_unit_var():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 32)), jnp.float32)
    out = layer_norm(x, jnp.ones((32,)), jnp.zeros((32,)))
    np.testing.assert_allclose(np.mean(np.asarray(out), -1), 0, atol=1e-5)
    np.testing.assert_allclose(np.var(np.asarray(out), -1), 1, atol=1e-3)


def test_rope_preserves_norm_and_relative_property():
    angles = rope_frequencies(8, 64, theta=10_000.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 16, 2, 8)),
                    jnp.float32)
    out = apply_rope(x, angles)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # explicit positions == default positions
    pos = jnp.arange(16)[None, :]
    out2 = apply_rope(x, angles, pos)
    np.testing.assert_allclose(out, out2, rtol=1e-6)


def _naive_attention(q, k, v, causal=True):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    k = np.repeat(k, rep, axis=2)
    v = np.repeat(v, rep, axis=2)
    out = np.zeros_like(q)
    for b in range(B):
        for h in range(H):
            s = q[b, :, h] @ k[b, :, h].T / np.sqrt(D)
            if causal:
                mask = np.tril(np.ones((S, S), bool))
                s = np.where(mask, s, -1e30)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, :, h] = p @ v[b, :, h]
    return out


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("kv_heads", [4, 2])
def test_reference_attention_vs_naive(causal, kv_heads):
    rng = np.random.default_rng(0)
    q = rng.normal(size=(2, 16, 4, 8)).astype(np.float32)
    k = rng.normal(size=(2, 16, kv_heads, 8)).astype(np.float32)
    v = rng.normal(size=(2, 16, kv_heads, 8)).astype(np.float32)
    out = attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                    causal=causal, use_flash=False)
    np.testing.assert_allclose(out, _naive_attention(q, k, v, causal),
                               rtol=2e-4, atol=2e-5)


def test_attention_grad_finite():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 4)), jnp.float32)

    def f(q):
        return attention(q, q, q, causal=True, use_flash=False).sum()

    g = jax.grad(f)(q)
    assert np.all(np.isfinite(np.asarray(g)))


def test_ring_attention_single_axis_matches_reference():
    """shard_map ring over sp=4 must equal full attention."""
    from jax.sharding import Mesh, PartitionSpec as P
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 32, 4, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)

    out = ring_attention_sharded(q, k, v, mesh, batch_axes=(), head_axis=None)
    expect = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_noncausal():
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 16, 2, 4)), jnp.float32)
    out = ring_attention_sharded(q, q, q, mesh, causal=False,
                                 batch_axes=(), head_axis=None)
    expect = reference_attention(q, q, q, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_gradients_match_reference():
    """Regression: stop_gradient on the online-softmax max broke grads."""
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 4)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 4)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(1, 32, 2, 4)), jnp.float32)

    def f_ring(q, k, v):
        return (ring_attention_sharded(q, k, v, mesh, batch_axes=(),
                                       head_axis=None) * cot).sum()

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) * cot).sum()

    g_ring = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_blockwise_attention_matches_reference_fwd_and_grad():
    from ray_tpu.ops.attention import blockwise_attention
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 40, 4, 8)), jnp.float32)  # 40 % 16 != 0
    k = jnp.asarray(rng.normal(size=(2, 40, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 40, 2, 8)), jnp.float32)
    for causal in (True, False):
        out = blockwise_attention(q, k, v, causal=causal, block_k=16)
        expect = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)

    def f_blk(q):
        return blockwise_attention(q, k, v, block_k=16).sum()

    def f_ref(q):
        return reference_attention(q, k, v).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(f_blk)(q)),
                               np.asarray(jax.grad(f_ref)(q)),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Ulysses all-to-all context parallelism (SURVEY §5.7 requires both schemes)
# ---------------------------------------------------------------------------

def test_ulysses_attention_matches_reference():
    """shard_map Ulysses over sp=4 must equal full attention."""
    from jax.sharding import Mesh
    from ray_tpu.ops.ulysses import ulysses_attention_sharded
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(3)
    B, S, H, D = 2, 32, 4, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)

    out = ulysses_attention_sharded(q, k, v, mesh, batch_axes=(),
                                    head_axis=None)
    expect = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_matches_ring():
    from jax.sharding import Mesh
    from ray_tpu.ops.ulysses import ulysses_attention_sharded
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    u = ulysses_attention_sharded(q, k, v, mesh, batch_axes=(),
                                  head_axis=None)
    r = ring_attention_sharded(q, k, v, mesh, batch_axes=(),
                               head_axis=None)
    np.testing.assert_allclose(np.asarray(u), np.asarray(r),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_gradients_match_reference():
    from jax.sharding import Mesh
    from ray_tpu.ops.ulysses import ulysses_attention_sharded
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32)

    def f_uly(q, k, v):
        return (ulysses_attention_sharded(q, k, v, mesh, batch_axes=(),
                                          head_axis=None) * cot).sum()

    def f_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) * cot).sum()

    gu = jax.grad(f_uly, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_ulysses_rejects_indivisible_heads():
    from jax.sharding import Mesh
    from ray_tpu.ops.ulysses import ulysses_attention_sharded
    devs = np.array(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    q = jnp.zeros((1, 32, 3, 8), jnp.float32)  # 3 heads, sp=4
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(q, q, q, mesh, batch_axes=(),
                                  head_axis=None)


# ---------------------------------------------------------------------------
# Explicit MoE expert all-to-all dispatch (VERDICT r1 #7)
# ---------------------------------------------------------------------------

def test_moe_alltoall_matches_einsum_dispatch():
    """The explicit all-to-all scheme must agree with the dense einsum
    scheme when capacity is ample (no drops on either side)."""
    from ray_tpu.models.moe import MoEConfig, MoEModel

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    mesh = build_mesh(MeshSpec.auto(8, sp=2, ep=2))
    cfg_a = MoEConfig.debug_moe(num_experts=4)
    cfg_a = dataclasses.replace(cfg_a, capacity_factor=4.0,
                                dtype=jnp.float32)
    cfg_b = dataclasses.replace(cfg_a, moe_dispatch="alltoall")

    model_a = MoEModel(cfg_a, mesh=mesh)
    model_b = MoEModel(cfg_b, mesh=mesh)
    params = model_a.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg_a.vocab_size, (2, 32)))

    with mesh:
        la, aux_a = model_a.apply_with_aux(params, tokens)
        lb, aux_b = model_b.apply_with_aux(params, tokens)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(float(aux_a), float(aux_b),
                               rtol=5e-2, atol=1e-3)


# ---------------------------------------------------------------------------
# Per-row positions: the serving prefills' masked attention (a slot
# cache, a gathered prefix), and one query token against a ragged cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["contiguous", "row_offsets",
                                  "one_query_token"])
def test_reference_attention_with_per_row_positions(case):
    from ray_tpu.ops.paged_attention import ragged_decode_attention_reference

    rng = np.random.default_rng(7)
    B, S, H, Hkv, D = 3, 24, 4, 2, 16
    T = 1 if case == "one_query_token" else 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    if case == "contiguous":
        # every row at 0..T-1 against its own first T keys: no positions
        k, v = k[:, :T], v[:, :T]
        pos_q = pos_k = jnp.broadcast_to(jnp.arange(T), (B, T))
        want = reference_attention(q, k, v)
    elif case == "row_offsets":
        # each row sits at its own offset into the keys, and drops its
        # own tail of them by a position past every query
        pos_q = jnp.asarray([0, 5, 16])[:, None] + jnp.arange(T)[None, :]
        pos_k = jnp.where(jnp.arange(S)[None, :]
                          < jnp.asarray([8, 13, 24])[:, None],
                          jnp.arange(S)[None, :], 2 ** 30)
        want = jnp.concatenate([
            reference_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                positions_q=pos_q[b], positions_k=pos_k[b])
            for b in range(B)])
    else:
        lengths = jnp.asarray([1, 13, 24], jnp.int32)
        pos_q, pos_k = (lengths - 1)[:, None], jnp.arange(S)
        want = ragged_decode_attention_reference(q[:, 0], k, v,
                                                 lengths)[:, None]
    out = reference_attention(q, k, v, positions_q=pos_q, positions_k=pos_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# Pallas flash attention (interpret mode: real kernel logic on CPU)
# ---------------------------------------------------------------------------

def test_flash_attention_matches_reference():
    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(0)
    B, S, H, Hkv, D = 2, 256, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    for causal in (True, False):
        out = flash_attention(q, k, v, causal, 128, 128, True)
        expect = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_ragged_seq():
    """Sequence not a multiple of the k block: tail-block masking."""
    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 192, 2, 16)), jnp.float32)
    out = flash_attention(q, q, q, True, 128, 128, True)
    expect = reference_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def _flash_grad_cases():
    """head_dim 64 and 128 x causal and not x kv heads 1, 2 and = heads,
    each at a sequence that ends inside a block; and the small square case
    this test began as (PR 48: it held the XLA-scan backward then)."""
    cases = [pytest.param(1, 128, 2, 2, 16, True, 64, id="d16-causal-mha-s128")]
    for D in (64, 128):
        for causal in (True, False):
            for Hkv in (1, 2, 4):
                cases.append(pytest.param(
                    1, 200, 4, Hkv, D, causal, 128,
                    id=f"d{D}-{'causal' if causal else 'full'}-kv{Hkv}-s200"))
    # two 64-wide heads a lane tile (PR 51): a GQA group of three, where
    # a tile's two q heads have kv heads in different slots of one kv
    # tile; a group of four over two kv tiles, where both q heads read
    # the slot that is not theirs; and odd numbers of heads and kv heads,
    # which ``flash_attention`` pads with heads of zeros to whole tiles
    # (``kernels_tile`` refuses them: test_dispatcher_reads_the_shapes)
    for H, Hkv, causal in ((6, 2, True), (16, 4, False), (3, 3, True),
                           (6, 3, False)):
        cases.append(pytest.param(
            2, 200, H, Hkv, 64, causal, 128,
            id=f"d64-{'causal' if causal else 'full'}-h{H}-kv{Hkv}-s200"))
    return cases


@pytest.mark.parametrize("B,S,H,Hkv,D,causal,block", _flash_grad_cases())
def test_flash_attention_gradients_match_reference(B, S, H, Hkv, D, causal,
                                                   block):
    """The fused kernels (interpret mode) against ``reference_attention``:
    the output AND dq, dk, dv, under a cotangent that weighs every
    element differently."""
    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

    got = _o_and_grads(lambda q, k, v: flash_attention(
        q, k, v, causal, block, block, True), q, k, v, w)
    want = _o_and_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=causal), q, k, v, w)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def _o_and_grads(attn, q, k, v, w):
    (_, o), grads = jax.value_and_grad(
        lambda q, k, v: (lambda o: ((o * w).sum(), o))(attn(q, k, v)),
        argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (o, *grads)


@pytest.mark.parametrize("S,H,D,causal,use_flash", [
    (200, 4, 64, True, True),       # two heads a tile, a ragged tail
    (256, 3, 64, False, True),      # an odd head: padded to a whole tile
    (128, 2, 128, True, True),      # a head a tile
    (128, 2, 16, True, True),       # GPT2Config.debug's: eight a tile
    (200, 4, 64, True, False),      # off the kernels: cut, the reference
], ids=["d64-h4-s200", "d64-h3-full", "d128-h2", "d16-h2", "reference"])
def test_packed_attention_matches_slices_through_the_reference(
        S, H, D, causal, use_flash):
    """``packed_attention`` on a qkv projection's ONE product
    [B, S, 3 * H * D] (the kernels interpreted): o, and the ONE packed
    gradient, against q, k and v cut out of it and taken through
    ``reference_attention``."""
    from ray_tpu.ops.attention import packed_attention

    rng = np.random.default_rng(5)
    B = 2
    qkv = jnp.asarray(rng.normal(size=(B, S, 3 * H * D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(B, S, H * D)), jnp.float32)

    def cut(qkv):
        return (qkv.reshape(B, S, 3, H, D)[:, :, n] for n in range(3))

    def through(attn):
        return jax.value_and_grad(
            lambda x: (lambda o: ((o * w).sum(), o))(attn(x)),
            has_aux=True)(qkv)

    (_, o), grad = through(lambda x: packed_attention(
        x, H, causal=causal, use_flash=use_flash))
    (_, want), want_grad = through(lambda x: reference_attention(
        *cut(x), causal=causal).reshape(B, S, H * D))
    assert o.shape == (B, S, H * D) and grad.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    for name, a, b in zip("qkv", cut(grad), cut(want_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg="d" + name)
    calls = str(jax.make_jaxpr(lambda x: packed_attention(
        x, H, causal=causal, use_flash=use_flash))(qkv)).count("pallas_call")
    assert calls == (1 if use_flash else 0)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_attention_spans_split_into_tiles(monkeypatch, causal):
    """A row block's spans wider than ``_WIDE`` split into several tiles
    (on the chip: a sequence past 1,024): here ``_WIDE`` and both blocks
    are 128, so at 400 positions a forward block walks up to three
    unmasked tiles and a masked one, a backward block as many."""
    import importlib
    kernels = importlib.import_module("ray_tpu.ops.attention")

    monkeypatch.setattr(kernels, "_WIDE", 128)
    assert len(kernels._tiles((0, 512, False), (512, 640, True))) == 5
    rng = np.random.default_rng(3)
    B, S, H, Hkv, D = 1, 400, 2, 1, 64
    q, w = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
            for _ in range(2))
    # the jitted calls' cache does not know ``_WIDE``
    for call in (kernels._flash_forward, kernels._flash_backward):
        call.clear_cache()
    try:
        got = _o_and_grads(lambda q, k, v: kernels.flash_attention(
            q, k, v, causal, 128, 128, True), q, k, v, w)
    finally:
        for call in (kernels._flash_forward, kernels._flash_backward):
            call.clear_cache()
    want = _o_and_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=causal), q, k, v, w)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_flash_attention_past_residency_takes_the_scan(monkeypatch):
    """A head's sequence the kernels do not keep in VMEM runs
    ``blockwise_attention``: same values and gradients, no Pallas call,
    at any length (here the limit is patched down to 256 causal
    positions)."""
    import importlib
    kernels = importlib.import_module("ray_tpu.ops.attention")

    monkeypatch.setattr(kernels, "_MAX_UNROLLED", 256 * 256 // 2)
    rng = np.random.default_rng(4)
    B, S, H, Hkv, D = 1, 384, 2, 1, 64
    q, w = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
            for _ in range(2))

    def flash(q, k, v):
        return kernels.flash_attention(q, k, v, True)

    assert "pallas_call" not in str(jax.make_jaxpr(flash)(q, k, v))
    assert "pallas_call" in str(jax.make_jaxpr(flash)(
        q[:, :256], k[:, :256], v[:, :256]))
    got = _o_and_grads(flash, q, k, v, w)
    want = _o_and_grads(lambda q, k, v: reference_attention(
        q, k, v, causal=True), q, k, v, w)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("S,D,dtype,causal,resident", [
    (1024, 64, jnp.bfloat16, True, True),   # gpt2-medium.train_1chip
    (4096, 128, jnp.bfloat16, True, True),  # 21 MiB resident + the tiles
    (4097, 128, jnp.bfloat16, True, False),     # more than is unrolled
    (8192, 128, jnp.bfloat16, True, False),     # LlamaConfig.max_seq_len
    (2816, 64, jnp.bfloat16, False, True),
    (4096, 128, jnp.bfloat16, False, False),    # twice the causal tiles
    (2944, 256, jnp.float32, True, True),       # 55 + 8 MiB: half of 128
    (3072, 256, jnp.float32, True, False),
])
def test_flash_residency_by_shape(S, D, dtype, causal, resident):
    """What stays in VMEM, sized for the v5e where no chip is: every
    admitted corner here compiles for the v5e chip-less (PERF.md PR 48)."""
    from ray_tpu.ops.attention import _stays_resident

    assert _stays_resident(S, S, D, dtype, causal) is resident


@pytest.mark.parametrize("S,H,Hkv,D,tiles,kernels", [
    (1024, 16, 16, 64, True, True),      # gpt2-medium.train_1chip
    (256, 8, 2, 128, True, True),
    (197, 12, 12, 64, False, False),     # ViT-B: the reference is faster
    (8192, 8, 8, 128, True, False),      # the scan inside flash_attention
    (1024, 16, 16, 80, False, False),    # a head width that does not tile
    (128, 8, 8, 128, False, False),      # short: the reference is faster
    (2048, 32, 8, 64, True, True),       # llama3_1b: two heads a lane tile
    (1024, 15, 15, 64, False, False),    # an odd head would be padded
    (1024, 8, 1, 64, False, False),      # and so would a lone kv head
])
def test_dispatcher_reads_the_shapes_on_a_tpu(monkeypatch, S, H, Hkv, D,
                                              tiles, kernels):
    """``attention`` on a TPU backend with no mesh: the kernels where the
    shapes tile and stay in VMEM, the scan where they tile and do not,
    the reference elsewhere, under a mesh (``use_flash=False``) and with
    explicit positions."""
    import importlib
    attn = importlib.import_module("ray_tpu.ops.attention")

    monkeypatch.setattr(attn, "on_chip", lambda: True)
    monkeypatch.setattr(attn, "pallas_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, S, H, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, S, Hkv, D), jnp.bfloat16)
    assert attn.kernels_tile(q, kv) is tiles

    def program(**kw):
        return str(jax.make_jaxpr(
            lambda q, k, v: attn.attention(q, k, v, **kw))(q, kv, kv))

    text = program()
    assert ("pallas_call" in text) is kernels
    # (the forward kernel itself loops over a shared lane tile's heads)
    assert ("scan" in text and not kernels) is (tiles and not kernels)
    for kw in (dict(use_flash=False),
               dict(positions_q=jnp.arange(S), positions_k=jnp.arange(S))):
        text = program(**kw)
        assert "pallas_call" not in text and "scan" not in text


def test_llama_flash_impl_matches_ring_default():
    """attention_impl='flash' (sp==1) must produce the same loss as the
    reference/blockwise path the other impls use on one device."""
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    cfg_kw = dict(vocab_size=128, dim=64, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_dim=128, max_seq_len=128, remat=False)
    m_ring = LlamaModel(LlamaConfig(attention_impl="ring", **cfg_kw))
    m_flash = LlamaModel(LlamaConfig(attention_impl="flash", **cfg_kw))
    params = m_ring.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 128, (2, 128)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    l_ring = m_ring.loss(params, tokens, targets)
    l_flash = m_flash.loss(params, tokens, targets)
    np.testing.assert_allclose(float(l_ring), float(l_flash),
                               rtol=1e-3, atol=1e-4)


def test_llama_flash_rejects_sp_mesh():
    from jax.sharding import Mesh
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("sp",))
    cfg = LlamaConfig.debug()
    cfg = dataclasses.replace(cfg, attention_impl="flash")
    with pytest.raises(ValueError, match="flash"):
        LlamaModel(cfg, mesh=mesh)
