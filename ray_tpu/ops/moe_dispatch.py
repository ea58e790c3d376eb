"""The expert FFN of a mixture-of-experts block, two ways.

- ``dropless_expert_ffn``: what every model runs off a mesh, serving and
  training alike. Router in float32, top-k, the (token, choice)
  assignments sorted by expert, the three expert matmuls as grouped
  matmuls over the sorted rows (``grouped_matmul``, below), outputs
  un-sorted and summed with the router's weights. Every chosen expert is
  computed: no capacity, no drop, and nothing whose size grows faster
  than T * K.
  The expert weights are ONE layer's ``[E, ...]`` or, with
  ``first_expert``, every layer's stack ``[L*E, ...]`` of which the
  layer's E groups alone hold rows (the serving programs: a grouped
  matmul's operand is a buffer of its own, so a layer's SLICE of the
  stack is a copy of it, 268 MB a stack a layer: PERF.md, PR 37).
- ``grouped_matmul``: one algorithm, two implementations, ONE resolver
  (``grouped_matmul_impl``, by the platform and the call's shapes, the
  way ``ops.paged_attention.default_impl`` resolves decode attention: no
  flag, no environment variable, no configuration key). On a TPU backend,
  wherever ``gmm_tiling(m, k, n, itemsize)`` has a tiling, the Pallas
  grouped matmul (``gmm``: bf16 operands, float32 accumulator) at that
  tiling, whatever XLA's own heuristic for ``ragged_dot`` would have
  tiled (256 x 128 at Mellum2's 2304 x 896, 63 grid steps a group:
  PERF.md, PR 40; 512 x 512 at OLMoE's 2048 x 1024, where the kernel's
  one tile an expert still reads a third faster a call: PR 53); off the
  chip and for a shape with no legal tiling, ``jax.lax.ragged_dot`` (the
  kernel's reference in the tests). The kernel is megablox's
  (``jax.experimental.pallas.ops.tpu``), kept in this module since PR 53
  for what a call costs a process's SET-UP, which no compile cache
  saves: a program that holds it traces and lowers it on the host first,
  and the chip's host runs that Python six times slower than a desk's.
  Here the walk over the groups (``group_tiles``) is a dozen ``jax.lax``
  primitives computed ONCE a layer (megablox computes it inside every
  call, out of ``jax.numpy``'s ``repeat``, ``histogram``,
  ``searchsorted`` and ``roll``, each an inner ``jit`` traced and
  lowered apart); ``gmm`` is one ``jit`` of the module, traced once a
  (rows, k, n, tiling) a process (gate and up are one trace); and where
  a k tile is the whole of k the kernel body holds no accumulator and no
  ``cond``. Results are megablox's bit for bit. PERF.md (PR 53) counts
  the programs and their seconds; tests/test_moe_grouped_matmul.py holds
  both halves.
  TRAINING on a TPU runs the same kernel forward; its backward is
  ``ragged_dot``'s transposes (``pallas_grouped_matmul``'s
  ``custom_vjp``), which no cell measures.
- the capacity-bounded GShard pair, kept for an ``ep`` mesh axis
  (ROADMAP R2 decides their future): ``capacity_einsum_ffn`` (dense
  one-hot ``[T, E, C]`` dispatch/combine einsums, XLA's partitioner
  chooses the collectives) and ``expert_alltoall_ffn`` (the same
  buckets crossing ``ep`` as two explicit ``jax.lax.all_to_all``
  inside ``shard_map``; 2 * E * C_local * D per device per layer,
  independent of routing skew). Both DROP what exceeds an expert's
  capacity. Sharding contract of the all-to-all (enforced by the
  shard_map specs): tokens arrive sharded [batch -> (dp, fsdp), seq ->
  (sp, ep)], expert weights [E -> ep], not additionally tensor-parallel.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private.platform import on_chip, pallas_interpret

logger = logging.getLogger(__name__)


def route_topk(xf, router, top_k: int, norm_topk_prob: bool,
               precision=None, sigmoid_bias=None, weight_scale: float = 1.0,
               groups=(1, 1), renorm_eps: float = 1e-20):
    """Router in float32: ``(logits [T, E], probs [T, E], weights [T, K],
    experts [T, K])``. The top-k softmax weights are used as they are
    unless ``norm_topk_prob`` (then they sum to 1).

    With ``sigmoid_bias`` [E] (float32) the SIGMOID form (``noaux_tc``,
    one group): scores ``s = sigmoid(logits)``; the experts are the
    top-k of ``s + bias``, a selection bias that is no part of the
    weights; the weights are ``s`` of the chosen, divided by their sum
    (+ ``renorm_eps``: the family's, 1e-20 or LFM2's 1e-6) under
    ``norm_topk_prob``, times ``weight_scale``
    (``routed_scaling_factor``). ``probs`` is then ``s``.

    ``groups`` = (``n_group``, ``topk_group``), the sigmoid form's GROUP
    LIMIT: the E experts are ``n_group`` groups of neighbours; a group's
    score is the sum of its TWO largest ``s + bias``; the ``topk_group``
    best groups stay and ``s + bias`` of every other expert is set to 0
    before the top-k (the weights are still ``s`` of the chosen). (1, 1)
    is no limit."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        router.astype(jnp.float32), precision=precision)
    if sigmoid_bias is not None:
        scores = jax.nn.sigmoid(logits)
        biased = scores + sigmoid_bias
        n_group, topk_group = groups
        if n_group > 1:
            with jax.named_scope("moe_group_limit"):
                grouped = biased.reshape(biased.shape[0], n_group, -1)
                group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
                _, kept = jax.lax.top_k(group_score, topk_group)
                stays = jnp.any(jax.nn.one_hot(kept, n_group, dtype=bool),
                                axis=1)                       # [T, groups]
                biased = jnp.where(stays[:, :, None], grouped,
                                   0.0).reshape(biased.shape)
        _, gate_idx = jax.lax.top_k(biased, top_k)
        gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
        if norm_topk_prob:
            gate_vals = gate_vals / (
                jnp.sum(gate_vals, -1, keepdims=True) + renorm_eps)
        return logits, scores, gate_vals * weight_scale, gate_idx
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, gate_idx


def router_aux_loss(logits, probs, z_coef: float, lb_coef: float):
    """Router z-loss + Switch-style load-balance loss (training)."""
    num_experts = probs.shape[-1]
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, axis=-1), num_experts),
                  axis=0)
    return (jnp.mean(z ** 2) * z_coef
            + lb_coef * num_experts * jnp.sum(me * ce))


# What a grouped-matmul call may hold of the v5e's 16 MiB of scoped VMEM
# by ``gmm_vmem_bytes``' reckoning: the compiler's own temporaries (the
# dot's float32 result, the store's mask) come on top of it.
GMM_VMEM_BUDGET = 12 * 2**20


def gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM a grouped-matmul grid step holds at tiling (tm, tk, tn): the
    weight tile, the row tile and the output tile double-buffered, and
    the float32 accumulator."""
    return (2 * tk * tn * itemsize + 2 * tm * tk * itemsize
            + 2 * tm * tn * itemsize + 4 * tm * tn)


def lane_divisors(x: int):
    """Multiples of 128 that divide ``x``, largest first."""
    return [t for t in range(x - x % 128, 0, -128) if x % t == 0]


def gmm_tiling(m: int, k: int, n: int, itemsize: int = 2
               ) -> Optional[Tuple[int, int, int]]:
    """The (rows, k, n) tiling of the Pallas grouped matmul for ``lhs
    [m, k] @ rhs [G, k, n]``, from the shapes alone; ``None`` where no
    legal tiling exists (the call then stays ``jax.lax.ragged_dot``).

    The kernel's grid is (n tiles, (group, row tile) pairs that hold a
    row, k tiles) and every step fetches a (tk, tn) weight tile and
    multiplies ALL tm rows of its row tile by it. What the v5e read
    (``tools/moe_gmm_bench.py tilings``, PERF.md PR 40):

    - (tk, tn): multiples of 128 that DIVIDE k and n, as few to a group
      as ``GMM_VMEM_BUDGET`` allows, the whole expert where it fits (one
      grid step a group: 2304 x 896 in bf16 is 4.1 MB, 8.3 double-
      buffered). Where a group needs more than one, n is split before k:
      a k tile shorter than k costs a pass over the float32 accumulator
      a step. Small tiles are what made these calls slow: a fixed cost a
      grid step, 63 steps a group at 256 x 128.
    - tm: 128 rows, 256 from 2,048 rows on, and the largest power of two
      under that which divides m (the kernel requires it; 16 at least, a
      bf16 tile's sublanes). A step's multiply takes as long as its
      weights' fetch at ~240 rows (197 TFLOP/s over 819 GB/s, bf16), so
      256 is the largest row tile that is not compute-bound. The
      winners read: 128 up to 1,024 rows (32-64 a few percent behind at
      a decode step's 256), 256 at 2,048 and 4,096 (by 2 and 8 %), 512
      1.6 x slower there; at 12,288 rows, which no cell runs, 128 read
      12 % ahead again at 2304 x 896.
    """
    tm = gmm_row_tile(m)
    if tm is None:
        return None
    fits = [(tk, tn) for tk in lane_divisors(k) for tn in lane_divisors(n)
            if gmm_vmem_bytes(tm, tk, tn, itemsize) <= GMM_VMEM_BUDGET]
    if not fits:
        return None
    tk, tn = min(fits, key=lambda t: ((k // t[0]) * (n // t[1]), -t[0]))
    return tm, tk, tn


def grouped_matmul_impl(m: int, k: int, n: int, itemsize: int
                        ) -> Tuple[str, Optional[Tuple[int, int, int]]]:
    """``("pallas_gmm", tiling)`` or ``("ragged_dot", None)`` for ``lhs
    [m, k] @ rhs [G, k, n]``: the one place that decides, by the
    platform and the shapes (as ``ops.paged_attention.default_impl``
    does for decode attention). The Pallas kernel on a TPU backend
    wherever ``gmm_tiling`` has a tiling; ``jax.lax.ragged_dot`` off the
    chip and where none is legal.

    No width keeps ``ragged_dot`` for XLA's tiles' sake (PERF.md, PR 53):
    at 2048 x 1024, which XLA tiles 512 x 512, the kernel's one tile an
    expert reads a third faster a call (0.37 for 0.56 ms at 256 rows),
    4.4 times at 2304 x 896 (0.39 for 1.72). What the kernel costs is
    set-up, tracing and Mosaic lowering on the host in every program
    that holds it: the module docstring says how that is kept small.
    """
    if not on_chip():
        return "ragged_dot", None
    tiling = gmm_tiling(m, k, n, itemsize)
    if tiling is None:
        logger.warning(
            "grouped matmul [%d, %d] @ [G, %d, %d] keeps ragged_dot on the "
            "chip at XLA's small tiles: no row tile divides %d rows or no "
            "multiple of 128 divides a width", m, k, k, n, m)
        return "ragged_dot", None
    return "pallas_gmm", tiling


def gmm_row_tile(m: int) -> Optional[int]:
    """The kernel's row tile for ``m`` rows, which ``gmm_tiling`` takes
    from the rows alone: a layer's gate, up and down share it, and with
    it ONE walk over the groups (``group_tiles``)."""
    target = 256 if m >= 2048 else 128
    return next((t for t in (256, 128, 64, 32, 16)
                 if t <= target and m % t == 0), None)


def group_tiles(group_sizes, m: int, tm: int):
    """What the kernel's grid walks, from the groups' sizes alone:
    ``(offsets [G+1], group_ids [L], m_tile_ids [L], num_tiles)``. The
    visits are the (group, row tile) pairs that hold a row, by group,
    then by tile: a group of no rows has none, a group that crosses a
    tile's edge one a tile. ``L = m // tm + G - 1`` bounds their number
    (a tile is visited once, and once more for every further group that
    begins inside it); only the first ``num_tiles`` entries are walked.
    ``offsets[g]`` is group ``g``'s first row.

    A dozen integer primitives on ``[G]`` and ``[L, G]``, written in
    ``jax.lax`` (every ``jax.numpy`` call and operator is an inner
    ``jit`` a program traces, ``repeat``, ``histogram``, ``searchsorted``
    and ``roll`` ones it lowers apart as well: on the chip's host that
    was most of what a program that holds the kernel paid to start), and
    ONE call a layer: gate, up and down walk the same rows."""
    lax = jax.lax
    G = group_sizes.shape[0]
    tiles_m = m // tm
    L = tiles_m + G - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = lax.cumsum(sizes)
    first = lax.div(lax.sub(ends, sizes), np.int32(tm))   # first row tile
    tiles = lax.select(
        lax.gt(sizes, np.int32(0)),
        lax.add(lax.sub(lax.div(lax.sub(ends, np.int32(1)), np.int32(tm)),
                        first), np.int32(1)),
        lax.full_like(sizes, 0))
    tile_ends = lax.cumsum(tiles)
    # a visit's group: how many groups' visits end at or before it
    passed = lax.le(lax.broadcast_in_dim(tile_ends, (L, G), (1,)),
                    lax.broadcasted_iota(jnp.int32, (L, G), 0))
    group_ids = lax.min(
        lax.reduce_sum(passed.astype(jnp.int32), (1,)), np.int32(G - 1))
    # its row tile: the group's first, and on by the visits since then
    start = lax.sub(first, lax.sub(tile_ends, tiles))
    m_tile_ids = lax.min(
        lax.add(start.at[group_ids].get(mode="promise_in_bounds"),
                lax.iota(jnp.int32, L)), np.int32(tiles_m - 1))
    offsets = lax.pad(ends, np.int32(0), ((1, 0, 0),))
    return offsets, group_ids, m_tile_ids, tile_ends[-1]


def _gmm_kernel(offsets, group_ids, m_tile_ids, lhs, rhs, out, *acc,
                tiles_k: int, dtype):
    """One grid step (n tile, visit, k tile): the row tile times the
    visit's group's (tk, tn) weight tile, summed over k in ``acc``
    (float32); at the last k tile the rows that ARE the group's are
    stored, the tile's other rows left as the visits of their own groups
    wrote them. Where a k tile is the whole of k (``tiles_k`` 1: no
    ``acc``) the product is the sum."""
    import jax.experimental.pallas as pl

    visit, k_i = pl.program_id(1), pl.program_id(2)
    product = jax.lax.dot_general(
        lhs[...], rhs[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    def store(total):
        g = group_ids[visit]
        tm, tn = out.shape
        lax = jax.lax
        row = lax.add(lax.broadcasted_iota(jnp.int32, (tm, tn), 0),
                      lax.mul(m_tile_ids[visit], np.int32(tm)))
        mine = lax.bitwise_and(lax.ge(row, offsets[g]),
                               lax.lt(row, offsets[lax.add(g, np.int32(1))]))
        out[...] = lax.convert_element_type(lax.select(
            mine, total, lax.convert_element_type(out[...], jnp.float32)), dtype)

    if tiles_k == 1:
        return store(product)
    acc, = acc

    @pl.when(k_i == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += product

    @pl.when(k_i == tiles_k - 1)
    def _():
        store(acc[...])


@functools.partial(jax.jit, static_argnames=("dtype", "tiling", "interpret"))
def gmm(lhs, rhs, offsets, group_ids, m_tile_ids, num_tiles, *, dtype,
        tiling, interpret: bool = False):
    """The Pallas call: a grid of (n tiles, visits, k tiles), the three
    arrays of ``group_tiles`` prefetched as scalars, which the block
    index maps read: a visit's row tile of ``lhs`` and ``out``, its
    group's weight tile of ``rhs``. A ``jit`` of this module, so one
    process traces it once a (rows, k, n, tiling, dtype) however many
    call sites and programs hold it (gate and up are one trace)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n = lhs.shape, rhs.shape[2]
    tm, tk, tn = tiling
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiling {tiling} does not divide {(m, k, n)}")
    tiles_k, tiles_n = k // tk, n // tn
    itemsize = jnp.dtype(lhs.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tiles_k=tiles_k, dtype=dtype),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, off, gid, mt:
                             (mt[v], k_i)),
                pl.BlockSpec((None, tk, tn),
                             lambda n_i, v, k_i, off, gid, mt:
                             (gid[v], k_i, n_i))],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, v, k_i, off, gid, mt: (mt[v], n_i)),
            grid=(tiles_n, num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * (
                tiles_k > 1)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * itemsize * tiles_n
                            + k * n * itemsize * group_ids.size
                            + m * n * jnp.dtype(dtype).itemsize)),
        interpret=interpret,
    )(offsets, group_ids, m_tile_ids, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def pallas_grouped_matmul(lhs, rhs, group_sizes, tiles, dtype, tiling,
                          interpret: bool = False):
    """The Pallas grouped matmul at ``tiling`` (bf16 operands, float32
    accumulator, a grid of (n tiles, non-empty (group, row tile) pairs,
    k tiles): megablox's kernel, ``jax.experimental.pallas.ops.tpu``,
    kept here since PR 53 with the groups' walk computed apart, once a
    layer: ``tiles`` is ``group_tiles(group_sizes, m, tiling[0])``;
    results are megablox's bit for bit). Its gradient is ``ragged_dot``'s
    (XLA's transposes): no training cell measures a tiled backward."""
    return gmm(lhs, rhs, *tiles, dtype=dtype, tiling=tiling,
               interpret=interpret)


def _pallas_gmm_fwd(lhs, rhs, group_sizes, tiles, dtype, tiling, interpret):
    out = pallas_grouped_matmul(lhs, rhs, group_sizes, tiles, dtype, tiling,
                                interpret)
    return out, (lhs, rhs, group_sizes)


def _pallas_gmm_bwd(dtype, tiling, interpret, residuals, grad):
    lhs, rhs, group_sizes = residuals
    _, vjp = jax.vjp(lambda l, r: jax.lax.ragged_dot(
        l, r, group_sizes, preferred_element_type=dtype), lhs, rhs)
    return (*vjp(grad), None, None)


pallas_grouped_matmul.defvjp(_pallas_gmm_fwd, _pallas_gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, dtype, tiles=None):
    """``lhs [m, k]``'s rows, sorted by group, each times its group's
    ``rhs [G, k, n]``: ``[m, n]`` in ``dtype``, float32 sums. Groups of
    no rows are neither visited nor fetched. ``tiles``: the kernel's
    walk over the groups, ``group_tiles(group_sizes, m,
    gmm_row_tile(m))``, from a caller whose calls share it (a layer's
    gate, up and down); computed here for a call that stands alone."""
    m = lhs.shape[0]
    impl, tiling = grouped_matmul_impl(
        m, rhs.shape[1], rhs.shape[2], jnp.dtype(dtype).itemsize)
    if impl == "ragged_dot":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=dtype)
    if tiles is None:
        tiles = group_tiles(group_sizes, m, tiling[0])
    return pallas_grouped_matmul(lhs, rhs, group_sizes, tiles, dtype, tiling,
                                 pallas_interpret())


def dropless_expert_ffn(x, router, e_gate, e_up, e_down, *, top_k: int,
                        norm_topk_prob: bool, dtype, live=None,
                        first_expert=None,
                        z_coef: float = 0.0, lb_coef: float = 0.0,
                        sigmoid_bias=None, weight_scale: float = 1.0,
                        groups=(1, 1), held=None, expert_input=None,
                        renorm_eps: float = 1e-20):
    """x [T, D] -> ``(out [T, D] in ``dtype``, load [E] int32, experts
    [T, K] int32, aux)``.

    ``e_gate`` None: an expert WITHOUT a gate matrix, ``relu(u W1)^2 W2``
    (``e_up`` is W1, ``e_down`` W2) where the gated one is ``silu(u
    W_gate) * (u W_up)``. ``expert_input`` [T, Dl]: the experts read THESE
    rows (experts in a latent: ``e_up`` [E, Dl, F], ``e_down`` [E, F, Dl],
    ``out`` [T, Dl]) while the router reads ``x``.

    router [D, E]; e_gate/e_up [E, D, F]; e_down [E, F, D]. ``load[e]``
    is the number of rows handed to expert ``e``'s grouped matmuls, of
    the tokens ``live`` [T] bool marks (all, if ``None``): it sums to
    ``live.sum() * top_k`` because nothing is dropped. ``aux`` is the
    training loss of ``router_aux_loss``. ``sigmoid_bias``,
    ``weight_scale``, ``groups`` and ``renorm_eps`` are ``route_topk``'s:
    the sigmoid router.

    With ``held`` = (first, H) the layer HOLDS A SHARE of the router's E
    experts, ``first ... first + H``, and the weights are those H alone
    ([H, D, F]; a stack's layer is H groups): one chip's part of a layer
    that several share by experts. The router is E wide and chooses as
    ever (``load`` and ``experts`` count every choice); an assignment to
    an expert that is not held is sorted behind the held ones' rows and
    belongs to NO group, so no weight is fetched for it, and its part of
    the sum is left out: ``out`` is what the held experts add.

    With ``first_expert`` (a traced int32 scalar) the weights are G >= E
    groups, [G, D, F] and [G, F, D], a stack of several layers' experts
    read in place: this layer's are groups ``first_expert ...
    first_expert + E``, and every other group gets no row, so the
    grouped matmul neither visits it nor fetches its weights. The rows,
    their order and the E non-empty groups are the sliced call's, and
    so is every bit of the result.
    """
    T, D = x.shape
    E = router.shape[-1]
    with jax.named_scope("moe_router"):
        # 2*T*D*E operations: full float32 passes cost nothing, and a
        # bf16 pass of the logits would swap more near-tied experts
        logits, probs, weights, experts = route_topk(
            x, router, top_k, norm_topk_prob,
            precision=jax.lax.Precision.HIGHEST, sigmoid_bias=sigmoid_bias,
            weight_scale=weight_scale, groups=groups, renorm_eps=renorm_eps)
        aux = router_aux_loss(logits, probs, z_coef, lb_coef)
    with jax.named_scope("moe_dispatch"):
        flat = by = experts.reshape(T * top_k)
        if held is not None:
            # the held experts' rows first, by expert; the others' last,
            # in no group
            first_held, n_held = held
            here = (flat >= first_held) & (flat < first_held + n_held)
            by = jnp.where(here, flat - first_held, n_held)
        order = jnp.argsort(by, stable=True)          # rows by expert
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        rows = (x if expert_input is None else expert_input).astype(
            dtype)[order // top_k]                    # [T*K, D]
        load = sizes if live is None else jnp.zeros((E,), jnp.int32).at[
            flat].add(jnp.repeat(live.astype(jnp.int32), top_k))
        if held is not None:
            sizes = jnp.zeros((n_held + 1,), jnp.int32).at[by].add(
                1)[:n_held]
        if first_expert is not None:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((e_up.shape[0],), jnp.int32), sizes,
                (first_expert,))
    with jax.named_scope("moe_experts"):
        # the kernel's walk over the groups: one for the three calls
        tm = gmm_row_tile(T * top_k) if on_chip() else None
        tiles = None if tm is None else group_tiles(sizes, T * top_k, tm)

        def grouped(lhs, w):
            return grouped_matmul(lhs, w.astype(dtype), sizes, dtype, tiles)
        if e_gate is None:
            act = jnp.square(jax.nn.relu(grouped(rows, e_up)))
        else:
            act = jax.nn.silu(grouped(rows, e_gate)) * grouped(rows, e_up)
        rows = grouped(act, e_down)                   # [T*K, D]
    with jax.named_scope("moe_combine"):
        back = jnp.argsort(order)                     # un-sort
        rows = rows[back].reshape(T, top_k, rows.shape[-1])
        if held is not None:
            # a row of no group is whatever the grouped matmul left there
            rows = jnp.where(here.reshape(T, top_k, 1), rows, 0)
        # elementwise, so the weights keep their float32 (a dot would
        # round them to bf16 on the TPU)
        out = jnp.sum(rows.astype(jnp.float32) * weights[:, :, None], axis=1)
    return out.astype(dtype), load, experts, aux


def topk_dispatch(xf, router, num_experts: int, top_k: int,
                   capacity: int, z_coef: float, lb_coef: float,
                   norm_topk_prob: bool = True):
    """Capacity-bounded router math: returns (dispatch [T,E,C] bool,
    combine [T,E,C] f32, aux scalar)."""
    logits, probs, gate_vals, gate_idx = route_topk(
        xf, router, top_k, norm_topk_prob)
    aux = router_aux_loss(logits, probs, z_coef, lb_coef)
    T = xf.shape[0]
    combine = jnp.zeros((T, num_experts, capacity), jnp.float32)
    dispatch = jnp.zeros((T, num_experts, capacity), jnp.bool_)
    # Slot positions must be unique per expert ACROSS the k passes:
    # choice-k tokens start after every earlier pass's assignments to the
    # same expert (GShard top-2 priority order), or two tokens land in
    # one slot and the expert sees their SUM.
    expert_count = jnp.zeros((num_experts,), jnp.float32)
    for j in range(top_k):
        onehot = jax.nn.one_hot(gate_idx[:, j], num_experts)
        pos_in_pass = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum((pos_in_pass + expert_count[None, :]) * onehot,
                      axis=-1)
        expert_count = expert_count + jnp.sum(onehot, axis=0)
        in_cap = pos < capacity
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity)
        slot = onehot[:, :, None] * pos_oh[:, None, :]
        slot = slot * in_cap[:, None, None]
        dispatch = dispatch | (slot > 0)
        combine = combine + slot * gate_vals[:, j][:, None, None]
    return dispatch, combine, aux


def capacity_einsum_ffn(h, router, e_gate, e_up, e_down, *,
                        num_experts: int, top_k: int,
                        capacity_factor: float, z_coef: float,
                        lb_coef: float, dtype,
                        norm_topk_prob: bool = True):
    """h [B, S, D] -> (out, aux): one-hot ``[T, E, C]`` dispatch and
    combine einsums with ``C = capacity_factor * T * K / E`` rows an
    expert; assignments past an expert's capacity are dropped."""
    B, S, D = h.shape
    T = B * S
    C = max(1, int(capacity_factor * T * top_k / num_experts))
    x = h.reshape(T, D)
    dispatch, combine, aux = topk_dispatch(
        x, router, num_experts, top_k, C, z_coef, lb_coef, norm_topk_prob)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dtype),
                           x.astype(dtype))                   # [E, C, D]
    gate = jnp.einsum("ecd,edf->ecf", expert_in, e_gate.astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", expert_in, e_up.astype(dtype))
    expert_out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                            e_down.astype(dtype))             # [E, C, D]
    out = jnp.einsum("tec,ecd->td", combine.astype(dtype), expert_out)
    return out.reshape(B, S, D), aux


def expert_alltoall_ffn(h, router, e_gate, e_up, e_down, mesh, *,
                        num_experts: int, top_k: int,
                        capacity_factor: float, z_coef: float,
                        lb_coef: float, dtype,
                        norm_topk_prob: bool = True,
                        axis_name: str = "ep") -> Tuple[jax.Array,
                                                        jax.Array]:
    """MoE FFN with explicit expert all-to-all over ``axis_name``.

    h: [B, S, D] (global, inside pjit). router: [D, E].
    e_gate/e_up: [E, D, F]; e_down: [E, F, D].
    Returns (out [B, S, D], aux [n_shards] — mean it for the loss).
    """
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape.get(axis_name, 1)

    def body(x, rtr, eg, eu, ed):
        # x: [B_l, S_l, D] local; eg/eu/ed: [E_l, D|F, F|D] local experts
        B_l, S_l, D = x.shape
        T_l = B_l * S_l
        C = max(1, int(capacity_factor * T_l * top_k / num_experts))
        xf = x.reshape(T_l, D)
        dispatch, combine, aux = topk_dispatch(
            xf, rtr, num_experts, top_k, C, z_coef, lb_coef, norm_topk_prob)
        if ep > 1:
            aux = jax.lax.pmean(aux, axis_name)

        # bucket per GLOBAL expert: [E, C, D]
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dtype),
                               xf.astype(dtype))
        if ep > 1:
            # dispatch all-to-all: [E=ep*E_l, C, D] -> [E_l, ep*C, D]
            expert_in = jax.lax.all_to_all(
                expert_in, axis_name, split_axis=0, concat_axis=1,
                tiled=True)
        gate = jnp.einsum("ecd,edf->ecf", expert_in, eg.astype(dtype))
        up = jnp.einsum("ecd,edf->ecf", expert_in, eu.astype(dtype))
        act = jax.nn.silu(gate) * up
        out = jnp.einsum("ecf,efd->ecd", act, ed.astype(dtype))
        if ep > 1:
            # return all-to-all: [E_l, ep*C, D] -> [E, C, D]
            out = jax.lax.all_to_all(out, axis_name, split_axis=1,
                                     concat_axis=0, tiled=True)
        y = jnp.einsum("tec,ecd->td", combine.astype(dtype), out)
        return y.reshape(B_l, S_l, D), aux.reshape(1)

    present = set(mesh.shape.keys())
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in present)
    seq_axes = tuple(a for a in ("sp", axis_name) if a in present)
    x_spec = P(batch_axes or None, seq_axes or None, None)
    w_spec = P(axis_name if axis_name in present else None, None, None)
    aux_spec = P(batch_axes + seq_axes or None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, aux_spec), check_vma=False)
    return fn(h, router, e_gate, e_up, e_down)
