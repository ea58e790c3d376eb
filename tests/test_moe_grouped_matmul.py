"""The expert FFN's grouped matmuls are tiled by their shapes (PR 40).

On a TPU backend ``ops.moe_dispatch.grouped_matmul`` calls jax's Pallas
grouped matmul at the tiling ``gmm_tiling(m, k, n, itemsize)`` gives;
everywhere else, and where no tiling is legal, it stays
``jax.lax.ragged_dot``, which is also the kernel's reference here. One
resolver, by platform and shape (``grouped_matmul_impl``): no flag picks
the kernel or a tile. The compiles for the v5e are in
``tests/test_chip_smoke.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, LlamaModel, MoEConfig, MoEModel
from ray_tpu.ops import moe_dispatch
from ray_tpu.ops.moe_dispatch import (GMM_VMEM_BUDGET, gmm_tiling,
                                      gmm_vmem_bytes, grouped_matmul_impl,
                                      pallas_grouped_matmul)

I32 = jnp.int32
BF16 = jnp.bfloat16
# (k, n) of the expert cells' calls: Mellum2's gate/up and down (2304 =
# 18 x 128, 896 = 7 x 128), OLMoE's gate/up and down
CELL_CALLS = [(2304, 896), (896, 2304), (2048, 1024), (1024, 2048)]


# -- the tiling rule -----------------------------------------------------------
@pytest.mark.parametrize("m", [256, 2048, 4096, 12288])
@pytest.mark.parametrize("k,n", CELL_CALLS)
def test_tiles_are_lane_multiples_that_divide_and_fit(k, n, m):
    tm, tk, tn = gmm_tiling(m, k, n, 2)
    assert m % tm == 0 and tm % 16 == 0          # megablox wants tm | m
    assert tk % 128 == 0 and k % tk == 0
    assert tn % 128 == 0 and n % tn == 0
    assert gmm_vmem_bytes(tm, tk, tn, 2) <= GMM_VMEM_BUDGET
    steps_a_group = (k // tk) * (n // tn)
    if m == 256:
        # a decode step: ~4 rows a group, so a small row tile, and each
        # expert's weights in a few large tiles (63 of 256 x 128 before)
        assert tm <= 128 and steps_a_group <= 3
    else:
        # prefill: a large row tile, a weight tile re-read as seldom as
        # possible
        assert tm >= 256 and steps_a_group <= 4


@pytest.mark.parametrize("k,n", CELL_CALLS)
def test_rows_no_tile_divides_fall_back(k, n, monkeypatch):
    assert gmm_tiling(250, k, n, 2) is None
    monkeypatch.setattr(moe_dispatch, "on_chip", lambda: True)
    assert grouped_matmul_impl(250, k, n, 2) == ("ragged_dot", None)
    jaxpr = jax.make_jaxpr(lambda a, b, s: moe_dispatch.grouped_matmul(
        a, b, s, BF16))(jax.ShapeDtypeStruct((250, k), BF16),
                        jax.ShapeDtypeStruct((4, k, n), BF16),
                        jax.ShapeDtypeStruct((4,), I32))
    assert "ragged_dot" in str(jaxpr) and "pallas_call" not in str(jaxpr)


@pytest.mark.parametrize("k,n", [(64, 32), (2304, 96), (100, 896)])
def test_widths_no_lane_multiple_divides_fall_back(k, n):
    assert gmm_tiling(256, k, n, 2) is None


def test_float32_operands_take_half_the_tile():
    """The budget is bytes: a float32 tile of the same shape is twice a
    bf16 one."""
    for k, n in CELL_CALLS:
        tm, tk, tn = gmm_tiling(256, k, n, 4)
        assert gmm_vmem_bytes(tm, tk, tn, 4) <= GMM_VMEM_BUDGET
        assert tk * tn <= np.prod(gmm_tiling(256, k, n, 2)[1:])


def test_the_cpu_keeps_ragged_dot():
    for k, n in CELL_CALLS:
        assert grouped_matmul_impl(256, k, n, 2) == ("ragged_dot", None)


@pytest.mark.parametrize("m", [256, 4096])
@pytest.mark.parametrize("k,n,impl", [
    (2304, 896, "pallas_gmm"), (896, 2304, "pallas_gmm"),   # XLA: 256 x 128
    (2048, 896, "pallas_gmm"),                  # one narrow width is enough
    (2048, 1024, "ragged_dot"), (1024, 2048, "ragged_dot"),  # XLA: 512 x 512
    (3584, 1024, "pallas_gmm"), (1024, 3584, "pallas_gmm"),  # 14 such tiles
    (7168, 2048, "pallas_gmm")])                             # 56
def test_a_tpu_backend_takes_the_kernel_where_xla_tiles_narrow(
        k, n, impl, m, monkeypatch):
    """One resolver, by the platform and the shapes: an expert of at most
    eight of the 512 x 512 tiles XLA's own heuristic reaches keeps
    ``ragged_dot`` (a third to gain a call, ~2 s of Mosaic lowering a
    process to pay: PERF.md, PR 40); a larger one takes the kernel
    (PERF.md, PR 50)."""
    monkeypatch.setattr(moe_dispatch, "on_chip", lambda: True)
    got, tiling = grouped_matmul_impl(m, k, n, 2)
    assert got == impl
    assert tiling == (gmm_tiling(m, k, n, 2) if impl == "pallas_gmm"
                      else None)


# -- the kernel, interpreted, against ragged_dot --------------------------------
def _stack_case(seed, L, E, m, k, n, layer, empty=()):
    """Rows of one layer's E groups inside a stack of L*E: zero groups
    before and after, and ``empty`` experts inside the layer."""
    rng = np.random.default_rng(seed)
    p = np.ones(E)
    p[list(empty)] = 0
    sizes = np.zeros(L * E, np.int32)
    sizes[layer * E:(layer + 1) * E] = rng.multinomial(m, p / p.sum())
    lhs = jnp.asarray(rng.normal(size=(m, k)), BF16)
    rhs = jnp.asarray(rng.normal(size=(L * E, k, n)) * k ** -0.5, BF16)
    return lhs, rhs, jnp.asarray(sizes)


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("k,n,tiling", [
    (1152, 896, (16, 384, 896)),      # k = 9 x 128 in three tiles, n = 7 x 128
    (896, 1152, (32, 896, 384)),      # n in three tiles
    (1152, 896, None),                # what gmm_tiling picks for the shape
    (896, 1152, None)])
def test_kernel_on_the_whole_stack_is_ragged_dot(k, n, tiling, layer):
    L, E, m = 3, 8, 64
    lhs, rhs, sizes = _stack_case(layer, L, E, m, k, n, layer, empty=(2, 5))
    assert int(sizes.sum()) == m
    assert int((sizes > 0).sum()) <= E - 2
    tiling = tiling or gmm_tiling(m, k, n, 2)
    assert tiling is not None
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    got = pallas_grouped_matmul(lhs, rhs, sizes, jnp.float32, tiling, True)
    # float32 sums in another order where a k tile is shorter than k
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # in the layer's dtype the two round the same sums
    got16 = pallas_grouped_matmul(lhs, rhs, sizes, BF16, tiling, True)
    np.testing.assert_allclose(
        np.asarray(got16, np.float32), np.asarray(want.astype(BF16),
                                                  np.float32),
        rtol=2 ** -7, atol=2 ** -7)


def test_kernels_gradient_is_ragged_dots():
    """Training through the kernel: its backward is ``ragged_dot``'s."""
    lhs, rhs, sizes = _stack_case(7, 1, 8, 64, 256, 128, 0, empty=(3,))
    tiling = gmm_tiling(64, 256, 128, 2)

    def loss(grouped):
        return lambda a, b: jnp.sum(
            grouped(a, b).astype(jnp.float32) ** 2)

    got = jax.grad(loss(lambda a, b: pallas_grouped_matmul(
        a, b, sizes, BF16, tiling, True)), (0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=BF16)), (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=2 ** -6, atol=2 ** -6)


@pytest.mark.parametrize("whole", [False, True], ids=["layer", "stack"])
def test_the_ffn_through_the_kernel_is_the_ffn_through_ragged_dot(
        whole, monkeypatch):
    """``dropless_expert_ffn`` as a TPU backend would build it (the
    kernel runs interpreted here) against the CPU's own build."""
    rng = np.random.default_rng(3)
    L, T, D, F, E, K = 2, 32, 256, 128, 8, 2
    x = jnp.asarray(rng.normal(size=(T, D)), BF16)
    router = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
    eg, eu = (jnp.asarray(rng.normal(size=(L * E, D, F)) * D ** -0.5, BF16)
              for _ in "gu")
    ed = jnp.asarray(rng.normal(size=(L * E, F, D)) * F ** -0.5, BF16)
    kw = dict(top_k=K, norm_topk_prob=True, dtype=BF16)
    if whole:
        args, kw["first_expert"] = (eg, eu, ed), I32(E)
    else:
        args = tuple(w[E:] for w in (eg, eu, ed))

    def ffn():
        return jax.jit(lambda *w: moe_dispatch.dropless_expert_ffn(
            x, router, *w, **kw))

    want = ffn()(*args)
    assert "pallas_call" not in str(jax.make_jaxpr(ffn())(*args))
    monkeypatch.setattr(moe_dispatch, "on_chip", lambda: True)
    assert "pallas_call" in str(jax.make_jaxpr(ffn())(*args))
    got = ffn()(*args)
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(want[0], np.float32),
                               rtol=2 ** -6, atol=2 ** -6)
    for g, w in zip(got[1:3], want[1:3]):        # load, experts
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- what an engine says it runs ---------------------------------------------
def _engine(model):
    from ray_tpu.llm import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        model, jax.jit(model.init)(jax.random.key(0)), max_slots=2,
        max_seq=64, prefill_buckets=(8, 16), block_size=8)


def test_stats_name_the_grouped_matmuls_implementation():
    stats = _engine(MoEModel(MoEConfig.debug_olmoe(max_seq_len=64))).stats
    assert stats["moe_grouped_impl"] == "ragged_dot"
    assert [stats[f"moe_gmm_tiling_{c}"] for c in ("gate", "up", "down")] \
        == ["", "", ""]
    dense = _engine(LlamaModel(LlamaConfig.debug(vocab_size=256,
                                                 max_seq_len=64))).stats
    assert dense["moe_grouped_impl"] == ""


def test_plan_on_a_tpu_backend_names_the_decode_steps_tilings(monkeypatch):
    """Mellum2's widths, 32 slots x top-8 = 256 rows."""
    monkeypatch.setattr(moe_dispatch, "on_chip", lambda: True)
    model = MoEModel(MoEConfig(
        vocab_size=256, dim=2304, n_layers=1, n_heads=2, n_kv_heads=2,
        head_dim=128, ffn_dim=896, max_seq_len=64, num_experts=64,
        expert_top_k=8))
    plan = model.grouped_matmul_plan(32)
    assert plan["moe_grouped_impl"] == "pallas_gmm"
    for call, (k, n) in (("gate", (2304, 896)), ("up", (2304, 896)),
                         ("down", (896, 2304))):
        assert plan[f"moe_gmm_tiling_{call}"] == "x".join(
            map(str, gmm_tiling(256, k, n, 2)))
    # 31 slots: 248 rows, which no row tile divides
    assert model.grouped_matmul_plan(31)["moe_grouped_impl"] == "ragged_dot"
