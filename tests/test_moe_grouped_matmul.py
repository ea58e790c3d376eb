"""The expert FFN's grouped matmuls are tiled by their shapes (PR 40).

On a TPU backend ``ops.moe_dispatch.grouped_matmul`` calls the Pallas
grouped matmul (megablox's kernel, kept in the module since PR 53 with
the walk over the groups computed once a layer) at the tiling
``gmm_tiling(m, k, n, itemsize)`` gives; everywhere else, and where no
tiling is legal, it stays ``jax.lax.ragged_dot``, which is also the
kernel's reference here, beside megablox's own call. One resolver, by
platform and shape (``grouped_matmul_impl``): no flag picks the kernel
or a tile. The compiles for the v5e are in ``tests/test_chip_smoke.py``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import LlamaConfig, LlamaModel, MoEConfig, MoEModel
from ray_tpu.ops import moe_dispatch
from ray_tpu.ops.moe_dispatch import (GMM_VMEM_BUDGET, gmm_tiling,
                                      gmm_vmem_bytes, group_tiles,
                                      grouped_matmul_impl,
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
    assert m % tm == 0 and tm % 16 == 0          # the kernel wants tm | m
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


# OLMoE's calls (the one width the deleted clause still caught): the
# whole expert one grid step a group, but for ``down`` from 2,048 rows on,
# where a 256-row tile leaves 12 MiB for two of its halves
OLMOE_TILINGS = {(256, 2048, 1024): (128, 2048, 1024),
                 (256, 1024, 2048): (128, 1024, 2048),
                 (4096, 2048, 1024): (256, 2048, 1024),
                 (4096, 1024, 2048): (256, 1024, 1024)}


@pytest.mark.parametrize("m", [256, 4096])
@pytest.mark.parametrize("k,n", [
    (2304, 896), (896, 2304), (2048, 896),       # Mellum2's, and a mix
    (2048, 1024), (1024, 2048),                  # OLMoE
    (3584, 1024), (1024, 3584),                  # Xing4.0
    (7168, 2048)])                               # DeepSeek-V3.2
def test_a_tpu_backend_takes_the_kernel_wherever_a_tiling_exists(
        k, n, m, monkeypatch):
    """One resolver, by the platform and the shapes, and ONE rule on the
    chip: the kernel at ``gmm_tiling``'s choice, whatever the compiler's
    own ``ragged_dot`` would have tiled (PERF.md, PR 53: the clause that
    kept a small expert off the kernel is gone)."""
    monkeypatch.setattr(moe_dispatch, "on_chip", lambda: True)
    impl, tiling = grouped_matmul_impl(m, k, n, 2)
    assert impl == "pallas_gmm" and tiling == gmm_tiling(m, k, n, 2)
    assert tiling == OLMOE_TILINGS.get((m, k, n), tiling)


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
    rhs = jnp.asarray(rng.standard_normal((L * E, k, n), np.float32)
                      * k ** -0.5, BF16)
    return lhs, rhs, jnp.asarray(sizes)


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("k,n,tiling,m,E", [
    (1152, 896, (16, 384, 896), 64, 8),   # k = 9 x 128 in three tiles
    (896, 1152, (32, 896, 384), 64, 8),   # n in three tiles
    (1152, 896, None, 64, 8),             # what gmm_tiling picks for the shape
    (896, 1152, None, 64, 8),
    # OLMoE's widths at the tilings its cell runs (PR 53): a two-slot
    # decode step's 16 rows and a 32-slot step's 256, gate/up and down
    (2048, 1024, (16, 2048, 1024), 16, 4),
    (1024, 2048, (16, 1024, 2048), 16, 4),
    (2048, 1024, (128, 2048, 1024), 256, 4),
    (1024, 2048, (128, 1024, 2048), 256, 4)])
def test_kernel_on_the_whole_stack_is_ragged_dot(k, n, tiling, m, E, layer):
    """The kernel reads layer ``layer``'s E groups out of the stack of
    3 x E (``first_expert``'s ``group_sizes``: zero elsewhere), with
    empty experts inside the layer and, where the rows are more than a
    row tile, a group that crosses a tile's edge."""
    L = 3
    empty = (2, 5) if E == 8 else (1,)
    lhs, rhs, sizes = _stack_case(layer, L, E, m, k, n, layer, empty=empty)
    assert int(sizes.sum()) == m
    assert int((sizes > 0).sum()) <= E - len(empty)
    if E == 4:
        assert tiling == gmm_tiling(m, k, n, 2)      # the cell's own
    tiling = tiling or gmm_tiling(m, k, n, 2)
    assert tiling is not None
    if E == 4 and m > tiling[0]:
        ends = np.cumsum(np.asarray(sizes))
        starts = ends - np.asarray(sizes)
        assert any(s // tiling[0] != (e - 1) // tiling[0]
                   for s, e in zip(starts, ends) if e > s)
    want = jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)
    tiles = group_tiles(sizes, m, tiling[0])
    got = pallas_grouped_matmul(lhs, rhs, sizes, tiles, jnp.float32, tiling,
                                True)
    # float32 sums in another order where a k tile is shorter than k
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the kernel is megablox's with the walk over the groups computed
    # apart (PR 53): the same sums in the same order, bit for bit
    from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox
    np.testing.assert_array_equal(np.asarray(got), np.asarray(megablox(
        lhs, rhs, sizes, jnp.float32, tiling, interpret=True)))
    # a k tile that is the whole of k needs no accumulator: the body
    # holds neither of the two conditionals then (set-up, PR 53)
    body = str(jax.make_jaxpr(lambda a, b: pallas_grouped_matmul(
        a, b, sizes, tiles, BF16, tiling, True))(lhs, rhs))
    assert body.count("cond[") == (0 if tiling[1] == k else 2)
    # in the layer's dtype the two round the same sums
    got16 = pallas_grouped_matmul(lhs, rhs, sizes, tiles, BF16, tiling, True)
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
        a, b, sizes, group_tiles(sizes, 64, tiling[0]), BF16, tiling, True)),
        (0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=BF16)), (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=2 ** -6, atol=2 ** -6)


@pytest.mark.parametrize("whole", [False, True, "held"],
                         ids=["layer", "stack", "held"])
def test_the_ffn_through_the_kernel_is_the_ffn_through_ragged_dot(
        whole, monkeypatch):
    """``dropless_expert_ffn`` as a TPU backend would build it (the
    kernel runs interpreted here) against the CPU's own build. ``held``:
    a layer that holds 3 of its router's 8 experts, in a stack: most
    rows belong to NO group, lie behind the held ones' and are never
    walked (``group_tiles`` stops at the last row that has a group)."""
    rng = np.random.default_rng(3)
    L, T, D, F, E, K = 2, 32, 256, 128, 8, 2
    x = jnp.asarray(rng.normal(size=(T, D)), BF16)
    router = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
    eg, eu = (jnp.asarray(rng.normal(size=(L * E, D, F)) * D ** -0.5, BF16)
              for _ in "gu")
    ed = jnp.asarray(rng.normal(size=(L * E, F, D)) * F ** -0.5, BF16)
    kw = dict(top_k=K, norm_topk_prob=True, dtype=BF16)
    if whole == "held":
        H = 3           # experts 2, 3, 4 of the router's 8, layer 1 of 2
        args = tuple(w[:2 * H] for w in (eg, eu, ed))
        kw.update(held=(2, H), first_expert=I32(H))
    elif whole:
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


# -- what the kernel costs a process's set-up -----------------------------------
def test_a_process_traces_the_kernel_once_a_shape_not_once_a_call_site(
        monkeypatch):
    """The set-up half of PR 53. A program that holds the kernel pays its
    tracing and its Mosaic lowering on the host before the compile cache
    is asked, so a cell's warm ``setup_s`` grows with the number of
    TRACES: ``gmm`` is ONE ``jax.jit`` of the module, so a process traces
    the kernel once a (rows, k, n, tiling) however many call sites and
    outer programs hold it, and the walk over the groups
    (``group_tiles``) is computed once a layer, not once a call. OLMoE's
    block at debug widths the kernel tiles (dim 128, experts of 384,
    top-2), as a TPU backend would build it: the decode step of 8 slots
    and the 1 x 8 bucket prefill both hand the experts 16 rows, a 2 x 16
    prefill 64; nine call sites, FOUR traces of the kernel (gate and up
    share a shape, down is the other), three walks. A change that traces
    the call anew at every site or in every program (a fresh closure, a
    ``jit`` made inside the layer, the walk inside the call) fails here,
    not at a cell's bound."""
    monkeypatch.setattr(moe_dispatch, "on_chip", lambda: True)
    # an expert width no other test of this process traces the kernel at:
    # jit's cache of traces is the process's
    model = MoEModel(MoEConfig.debug_olmoe(max_seq_len=64, dim=128,
                                           ffn_dim=384))
    params = jax.eval_shape(
        lambda key: model.serving_params(model.init(key)), jax.random.key(0))
    kernels, walks = [], []
    body, walk = moe_dispatch._gmm_kernel, moe_dispatch.group_tiles
    monkeypatch.setattr(
        moe_dispatch, "_gmm_kernel",
        lambda *refs, **kw: kernels.append(refs[3].shape) or body(*refs, **kw))
    monkeypatch.setattr(
        moe_dispatch, "group_tiles",
        lambda sizes, m, tm: walks.append((m, tm)) or walk(sizes, m, tm))

    def shapes(*shape):
        return jax.ShapeDtypeStruct(shape, I32)

    def prefill(B, S):
        return jax.jit(model.forward_step).trace(
            params, shapes(B, S),
            jax.eval_shape(lambda: model.init_kv_cache(B, S)), shapes(B))

    decode = jax.jit(model.decode_step_paged).trace(
        params, shapes(8), jax.eval_shape(
            lambda: model.init_kv_pool(8 * 8 + 1, 8)), shapes(8, 8),
        shapes(8))
    for program in (decode, prefill(1, 8), prefill(2, 16)):
        # gate, up and down call the kernel (the layers are scanned, so
        # depth adds no site), and gate and up call ONE jaxpr of it
        text = str(program.jaxpr)
        assert text.count("name=gmm") == 3 and text.count("pallas_call") == 2
    # the kernel's body ran once a trace: by its row tile of lhs
    assert sorted(kernels) == [(16, 128), (16, 384), (64, 128), (64, 384)]
    assert walks == [(16, 16), (16, 16), (64, 64)]


def test_the_set_up_table_reads_a_runs_events(tmp_path, capsys):
    """``tools/setup_lowerings.py --table`` on a canned run: a program is
    a row of the phase its events fell in (``setup_phases`` sums to the
    window's opening), a lowering's ``jit(f)`` and a trace's ``f`` are
    one program, an inner ``jit``'s traces a row of their own, and what
    ran after the window opened is left out."""
    from tools import setup_lowerings

    def event(at, kind, fun, seconds):
        return {"at": at, "kind": kind, "fun": fun, "seconds": seconds}

    events = tmp_path / "events.jsonl"
    events.write_text("\n".join(json.dumps(e) for e in [
        event(0.5, "trace", "init", 0.25), event(0.9, "lower", "jit(init)", 0.5),
        event(1.0, "compile", "jit(init)", 0.125),
        event(2.0, "trace", "gmm", 0.0625), event(2.1, "trace", "gmm", 0.0625),
        event(2.5, "trace", "_decode_step_paged", 1.0),
        event(2.75, "lower", "jit(_decode_step_paged)", 0.375),
        event(9.0, "trace", "late", 4.0)]) + "\n")
    line = tmp_path / "line.json"
    line.write_text(json.dumps({
        "setup_phases": [["deploy", 1.5], ["check", 2.5]],
        "metrics": {"setup_s": {"value": 4.0}}}) + "\n")
    setup_lowerings.table(str(events), str(line))
    rows = {tuple(r.split()[:2]): r.split()[2:]
            for r in capsys.readouterr().out.splitlines()}
    assert rows["deploy", "init"] == ["1", "0.250", "1", "0.500", "0.125"]
    assert rows["check", "_decode_step_paged"][:4] == [
        "1", "1.000", "1", "0.375"]
    assert rows["check", "gmm"][:3] == ["2", "0.125", "0"]
    assert not any("late" in key for key in rows)
    assert rows["programs", "lowered"][3] == "2;"


@pytest.mark.parametrize("G,m,tm", [(24, 64, 16), (12, 256, 128),
                                    (192, 256, 128), (192, 2048, 256)])
def test_the_walk_over_the_groups(G, m, tm):
    """``group_tiles`` against a loop: every (group, row tile) pair that
    holds a row, by group then tile, and no other; whole stacks with one
    layer's groups filled, empty experts, groups that cross a tile's
    edge, and rows at the end that belong to no group (``held``)."""
    rng = np.random.default_rng(G + m)
    for trial in range(12):
        sizes = np.zeros(G, np.int32)
        lo, hi = (G // 3, 2 * G // 3) if trial % 2 else (0, G)
        p = rng.random(hi - lo) * (rng.random(hi - lo) > 0.3)
        p[0] += 1e-3
        sizes[lo:hi] = rng.multinomial(m if trial % 3 else m // 2,
                                       p / p.sum())
        offsets, group_ids, m_tile_ids, num = (
            np.asarray(a) for a in moe_dispatch.group_tiles(
                jnp.asarray(sizes), m, tm))
        ends = np.cumsum(sizes)
        want = [(g, t) for g in range(G) if sizes[g]
                for t in range((ends[g] - sizes[g]) // tm,
                               (ends[g] - 1) // tm + 1)]
        assert len(want) == num <= m // tm + G - 1 == len(group_ids)
        assert list(zip(group_ids[:num], m_tile_ids[:num])) == want
        np.testing.assert_array_equal(offsets, np.concatenate([[0], ends]))
        # what is not walked still indexes inside the arrays
        assert group_ids.max() < G and m_tile_ids.max() < m // tm


# -- what an engine says it runs ---------------------------------------------
def _engine(model):
    from ray_tpu.llm import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        model, jax.jit(model.init)(jax.random.key(0)), max_slots=2,
        max_seq=64, prefill_buckets=(8, 16), block_size=8)


def test_stats_name_the_grouped_matmuls_implementation():
    """On this CPU backend: ``ragged_dot`` whatever the widths."""
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
    # 31 slots: 248 rows, which no row tile divides: no tiling, so the
    # chip too keeps ragged_dot there
    assert model.grouped_matmul_plan(31)["moe_grouped_impl"] == "ragged_dot"
