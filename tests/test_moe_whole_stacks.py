"""The expert stacks live through the serving programs' layer scans WHOLE
(PR 37): a scan that hands its body a layer's slice of ``e_gate`` /
``e_up`` / ``e_down`` copies each out a layer a step on the chip, because
a grouped matmul's operand is a buffer of its own. The serving programs
close over the stacks as ``[L*E, ...]`` and ``dropless_expert_ffn`` reads
the layer's experts as groups ``l*E`` onwards. Who keeps the slice:
training (``apply`` under ``grad``), a model with a mesh, and a dense
model, which names no whole leaf.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu.models import LlamaConfig, LlamaModel, MoEConfig, MoEModel
from ray_tpu.ops import moe_dispatch
from tests.program_readers import scans as _scans

I32 = jnp.int32
EXPERT_LEAVES = ("e_gate", "e_up", "e_down")


# -- the FFN: the whole stack and an offset against the layer's slice ------
@pytest.mark.parametrize("with_live", [False, True], ids=["all", "live"])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_whole_stack_ffn_is_the_sliced_call_bit_for_bit(layer, with_live):
    rng = np.random.default_rng(layer)
    L, T, D, F, E, K = 3, 40, 16, 8, 8, 3
    bf16 = jnp.bfloat16
    x = jnp.asarray(rng.normal(size=(T, D)), bf16)
    router = jnp.asarray(rng.normal(size=(D, E)), jnp.float32)
    eg, eu = (jnp.asarray(rng.normal(size=(L, E, D, F)), bf16) for _ in "gu")
    ed = jnp.asarray(rng.normal(size=(L, E, F, D)), bf16)
    live = jnp.asarray(rng.random(T) < 0.6) if with_live else None
    kw = dict(top_k=K, norm_topk_prob=False, dtype=bf16, live=live)

    want = jax.jit(lambda *w: moe_dispatch.dropless_expert_ffn(
        x, router, *w, **kw))(eg[layer], eu[layer], ed[layer])
    merged = [w.reshape((L * E,) + w.shape[2:]) for w in (eg, eu, ed)]
    got = jax.jit(lambda first, *w: moe_dispatch.dropless_expert_ffn(
        x, router, *w, first_expert=first, **kw))(I32(layer * E), *merged)
    for name, g, w in zip(("out", "load", "experts", "aux"), got, want):
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32), name)
    assert got[1].shape == (E,)          # a layer's experts, not the stack's
    assert int(got[1].sum()) == (int(live.sum()) if with_live else T) * K
    # ... and another layer's offset reads another layer's weights
    other = moe_dispatch.dropless_expert_ffn(
        x, router, *merged, first_expert=I32((layer + 1) % L * E), **kw)
    assert not np.array_equal(np.asarray(other[0], np.float32),
                              np.asarray(want[0], np.float32))


# -- the programs --------------------------------------------------------------
def _expert_model(mesh=None, n_layers=3):
    cfg = MoEConfig.debug_olmoe(n_layers=n_layers, max_seq_len=64)
    model = MoEModel(cfg, mesh=mesh)
    return cfg, model, model.serving_params(model.init(jax.random.key(0)))


def _dense_model():
    cfg = LlamaConfig(vocab_size=256, dim=64, n_layers=3, n_heads=4,
                      n_kv_heads=2, ffn_dim=96, max_seq_len=64, remat=False)
    model = LlamaModel(cfg)
    return cfg, model, model.serving_params(model.init(jax.random.key(0)))


def _program_args(model, params, method):
    cfg = model.cfg
    toks = jnp.ones((2, 16), I32)
    two = jnp.zeros((2,), I32)
    pool = model.init_kv_pool(9, 8)
    prefix = jnp.zeros((cfg.n_layers, 2, 8) + pool["k"].shape[3:],
                       pool["k"].dtype)
    return {
        "apply": (params, toks),
        "forward_step": (params, toks, model.init_kv_cache(2, 16), two),
        "decode_step_paged": (params, two, pool, jnp.zeros((2, 4), I32), two),
        "prefill_with_prefix": (params, toks, prefix, prefix, two + 8,
                                two + 16),
    }[method]


def _layer_scan(model, params, method):
    """(the program's jaxpr, shapes of its layer scan's consts, of its
    xs)."""
    jaxpr = jax.make_jaxpr(getattr(model, method))(
        *_program_args(model, params, method)).jaxpr
    scan, = [e for e in _scans(jaxpr)
             if e.params["length"] == model.cfg.n_layers]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = [tuple(v.aval.shape) for v in scan.invars]
    return jaxpr, shapes[:n_consts], shapes[n_consts + n_carry:]


def _sliced_operands(jaxpr, out):
    """Shapes of everything a ``dynamic_slice`` reads, anywhere."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dynamic_slice":
            out.add(tuple(eqn.invars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _sliced_operands(sub, out)
    return out


SERVING = ["forward_step", "decode_step_paged", "prefill_with_prefix"]


@pytest.mark.parametrize("method", SERVING)
def test_serving_programs_close_over_the_expert_stacks(method):
    cfg, model, params = _expert_model()
    L, E = cfg.n_layers, cfg.num_experts
    layers = params["layers"]
    jaxpr, consts, xs = _layer_scan(model, params, method)
    for name in EXPERT_LEAVES:
        per_layer_stacked = tuple(layers[name].shape)
        merged = (L * E,) + per_layer_stacked[2:]
        assert per_layer_stacked not in xs, name
        assert merged in consts, name
    # every other leaf is still sliced a layer at a time
    for name in set(layers) - set(EXPERT_LEAVES):
        assert tuple(layers[name].shape) in xs, name
    assert (L,) in xs                    # the layer's index
    sliced = _sliced_operands(jaxpr, set())
    stack_shaped = {tuple(layers[n].shape) for n in EXPERT_LEAVES} | {
        (L * E,) + tuple(layers[n].shape[2:]) for n in EXPERT_LEAVES}
    assert not sliced & stack_shaped


@pytest.mark.parametrize("method", SERVING)
def test_whole_stack_programs_compute_what_the_sliced_ones_do(method):
    """Bit for bit: the same rows through the same non-empty groups."""
    class Sliced(MoEModel):
        WHOLE_LAYER_LEAVES = ()

    cfg, model, params = _expert_model()
    args = _program_args(model, params, method)
    got = jax.jit(getattr(model, method))(*args)
    want = jax.jit(getattr(Sliced(cfg), method))(*args)
    jax.tree.map(lambda g, w: np.testing.assert_array_equal(
        np.asarray(g, np.float32), np.asarray(w, np.float32)), got, want)


def test_training_slices_and_its_gradients_are_a_per_layer_loops():
    """``apply`` keeps the stacks in its scan's ``xs`` (the transpose of
    a whole-stack operand would be a stack-sized gradient a layer), and
    the loss's gradients come per layer, ``[L, E, ...]``, equal to those
    of the same layers applied one after another with no scan."""
    cfg = MoEConfig.debug_olmoe(n_layers=3, max_seq_len=32,
                                dtype=jnp.float32)
    model = MoEModel(cfg)
    params = model.init(jax.random.key(1))
    layers = params["layers"]
    _, consts, xs = _layer_scan(model, params, "apply")
    for name in EXPERT_LEAVES:
        assert tuple(layers[name].shape) in xs, name
        assert (cfg.n_layers * cfg.num_experts,
                ) + tuple(layers[name].shape[2:]) not in consts, name

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 16)), I32)
    targets = jnp.asarray(rng.integers(1, cfg.vocab_size, (2, 16)), I32)

    def looped(params):
        def attend(q, k, v):
            return model._attention(q, k, v, None), None

        x, aux = model._embed(params, toks), 0.0
        for i in range(cfg.n_layers):
            layer = {k: v[i] for k, v in params["layers"].items()}
            x, _, extra = model._layer(x, layer, None, attend)
            aux = aux + extra["aux"]
        logits = model._head(params, x, every_head=True)
        return model._cross_entropy(logits, targets, None) + aux

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(model.loss))(params, toks, targets)
        want = jax.jit(jax.grad(looped))(params)
    for name in EXPERT_LEAVES + ("router",):
        g, w = got["layers"][name], want["layers"][name]
        assert g.shape == layers[name].shape
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("method", SERVING + ["apply"])
def test_a_dense_models_scans_hand_every_leaf_in_xs(method):
    """The hook is inert for a model that names no whole leaf: its layer
    scans slice every leaf of ``params["layers"]`` and nothing else."""
    cfg, model, params = _dense_model()
    assert model.WHOLE_LAYER_LEAVES == ()
    layers, stacks = model._whole_leaves(params["layers"])
    assert layers is params["layers"] and stacks is None
    _, consts, xs = _layer_scan(model, params, method)
    weights = sorted(tuple(v.shape) for v in params["layers"].values())
    program_xs = {"forward_step": 2, "prefill_with_prefix": 2}.get(method, 0)
    if method == "decode_step_paged":
        xs.remove((cfg.n_layers,))       # each layer's base in the pool
    assert len(xs) == len(weights) + program_xs      # K and V beside them
    for shape in weights:
        xs.remove(shape)
    assert not [s for s in consts if s and s[0] == cfg.n_layers]


def test_a_model_with_a_mesh_keeps_the_sliced_path():
    """Merging L with a dimension the mesh may shard would re-shard the
    stack, and the ``ep`` capacity paths' einsums slice for free."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    cfg, model, params = _expert_model(mesh=mesh)
    layers, stacks = model._whole_leaves(params["layers"])
    assert layers is params["layers"] and stacks is None
    _, consts, xs = _layer_scan(model, params, "decode_step_paged")
    for name in EXPERT_LEAVES:
        assert tuple(params["layers"][name].shape) in xs, name
    assert not [s for s in consts
                if s and s[0] == cfg.n_layers * cfg.num_experts]
    # the same weights off the mesh: the same step
    args = _program_args(model, params, "decode_step_paged")
    got = jax.jit(model.decode_step_paged)(*args)[0]
    want = jax.jit(MoEModel(cfg).decode_step_paged)(*args)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
