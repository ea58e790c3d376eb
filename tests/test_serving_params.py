"""The serving path stores its matmul weights in the compute dtype.

``LlamaModel.serving_params`` casts ONCE what the layer body casts at
each use; ``ContinuousBatchingEngine`` applies it to whatever it is
given. The cast is exactly the one the programs did on every call, so
the programs' outputs are EQUAL, not close; norms, QK-norm scales and an
expert model's router stay float32; training never sees it.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models import LlamaConfig, MoEConfig, model_for
from ray_tpu.train.spmd import make_train_step

I32 = jnp.int32
BF16, F32 = jnp.bfloat16, jnp.float32
CONFIGS = {"dense": LlamaConfig.debug(vocab_size=512),
           "tied": LlamaConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                               n_kv_heads=2, ffn_dim=128, max_seq_len=128,
                               remat=False, tie_embeddings=True),
           "olmoe": MoEConfig.debug_olmoe()}
DENSE_MATMULS = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
OLMOE_MATMULS = {"wq", "wk", "wv", "wo", "e_gate", "e_up", "e_down"}
B, BS, MAXB = 4, 8, 8             # slots, block size, table entries a slot


def build(name):
    model = model_for(CONFIGS[name])
    params = jax.jit(model.init)(jax.random.key(3))
    # scales other than 1, so a norm that lost its float32 would show
    layers = params["layers"]
    key = jax.random.key(7)
    for norm in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        if norm in layers:
            key, sub = jax.random.split(key)
            layers[norm] = 1.0 + 0.3 * jax.random.normal(
                sub, layers[norm].shape)
    return name, model, params


@pytest.fixture(scope="module", params=list(CONFIGS))
def built(request):
    return build(request.param)


def engine(model, params):
    return ContinuousBatchingEngine(
        model, params, max_slots=B, max_seq=BS * MAXB,
        prefill_buckets=(8, 16, 32), block_size=BS)


def dtypes(params):
    """leaf name -> dtype, the layer stacks beside the top level."""
    return {**{k: v.dtype for k, v in params.items() if k != "layers"},
            **{k: v.dtype for k, v in params["layers"].items()}}


def test_engine_holds_matmul_weights_in_bf16_and_the_rest_in_float32(built):
    name, model, params = built
    assert all(a.dtype == F32 for a in jax.tree.leaves(params))
    got = dtypes(engine(model, params).params)
    matmuls = OLMOE_MATMULS if name == "olmoe" else DENSE_MATMULS
    matmuls = matmuls | {"embed"} | (set() if name == "tied"
                                     else {"lm_head"})
    assert {k for k, d in got.items() if d == BF16} == matmuls
    stay = {k for k, d in got.items() if d == F32}
    assert {"attn_norm", "mlp_norm", "norm_f"} <= stay
    if name == "olmoe":
        assert {"router", "q_norm", "k_norm"} <= stay
    assert stay | matmuls == set(got)
    # the caller's float32 arrays are the caller's still
    assert all(a.dtype == F32 and not a.is_deleted()
               for a in jax.tree.leaves(params))


def test_already_cast_parameters_pass_through_untouched(built):
    _, model, params = built
    cast = model.serving_params(params)
    again = model.serving_params(cast)
    held = engine(model, cast).params
    for a, b, c in zip(*(jax.tree.leaves(t) for t in (cast, again, held))):
        assert a is b and a is c


def test_param_bytes_is_half_the_matmuls_plus_the_float32_leaves(built):
    name, model, params = built
    stats = engine(model, params).stats
    total = sum(a.nbytes for a in jax.tree.leaves(params))
    cast = dtypes(model.serving_params(params))
    small = sum(v.nbytes for k, v in params["layers"].items()
                if cast[k] == F32) + params["norm_f"].nbytes
    assert stats["param_bytes"] == (total - small) // 2 + small
    assert isinstance(stats["param_bytes"], int)


def test_one_program_draws_and_casts_to_the_same_bits(built):
    """``LLMServer`` draws and casts in one jitted program; the values
    are those of casting the float32 draw."""
    _, model, _ = built
    key = jax.random.key(11)
    fused = jax.jit(lambda k: model.serving_params(model.init(k)))(key)
    apart = model.serving_params(jax.jit(model.init)(key))
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(apart)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_llm_server_hands_its_engine_cast_parameters():
    from ray_tpu.llm.serving import LLMConfig, LLMServer

    server = LLMServer(LLMConfig(max_slots=2, max_seq=64))
    try:
        got = dtypes(server.engine.params)
        assert {k for k, d in got.items() if d == BF16} == (
            DENSE_MATMULS | {"embed", "lm_head"})
        want = server.model.serving_params(
            jax.jit(server.model.init)(jax.random.key(0)))
        for a, b in zip(jax.tree.leaves(server.engine.params),
                        jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    finally:
        server._stop.set()
        server._thread.join(timeout=30)
    assert not server._thread.is_alive()


# -- the programs: equal outputs on either storage ------------------------
def tokens(model, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, model.cfg.vocab_size, shape), I32)


def decode_args(model, seed=0):
    """A pool with 16 tokens cached a slot (garbage K/V, the same for
    both sides) and the next token of each."""
    rng = np.random.default_rng(seed)
    pool = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        model.init_kv_pool(B * MAXB + 1, BS))
    tables = jnp.arange(B * MAXB, dtype=I32).reshape(B, MAXB)
    return (tokens(model, (B,), seed), pool, tables, jnp.full((B,), 16, I32))


def run_decode(model, params):
    logits, pool = jax.jit(model.decode_step_paged)(
        params, *decode_args(model))
    return logits, pool["k"], pool["v"]


def run_forward_step(model, params):
    logits, cache = jax.jit(model.forward_step)(
        params, tokens(model, (2, 16)), model.init_kv_cache(2, 32),
        jnp.zeros((2,), I32))
    return logits, cache["k"], cache["v"]


def run_prefill_with_prefix(model, params):
    cfg = model.cfg
    rng = np.random.default_rng(5)
    shape = (cfg.n_layers, 2, 16, cfg.n_kv_heads, cfg.head_dim)
    prefix = [jnp.asarray(rng.standard_normal(shape), cfg.dtype)
              for _ in range(2)]
    logits, kv = jax.jit(model.prefill_with_prefix)(
        params, tokens(model, (2, 16)), *prefix, jnp.asarray([16, 8], I32),
        jnp.asarray([16, 11], I32))
    return logits, kv["k"], kv["v"]


@pytest.mark.parametrize("run", [run_decode, run_forward_step,
                                 run_prefill_with_prefix],
                         ids=lambda f: f.__name__[4:])
def test_programs_give_equal_outputs_on_cast_and_float32_parameters(
        built, run):
    _, model, params = built
    for got, want in zip(run(model, model.serving_params(params)),
                         run(model, params)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_olmoe_chooses_the_same_experts_on_cast_parameters():
    _, model, params = build("olmoe")

    def chosen(p):
        _, _, extras = jax.jit(model.decode_step_paged_counted)(
            p, *decode_args(model))
        return np.asarray(extras["experts"]), np.asarray(extras["load"])

    got, want = chosen(model.serving_params(params)), chosen(params)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert got[0].shape == (model.cfg.n_layers, B, 1, model.cfg.expert_top_k)


# -- the lowered decode program casts no weight ----------------------------
CONVERT = re.compile(
    r"stablehlo\.convert .*\(tensor<([0-9x]+)xf32>\) -> tensor<\1xbf16>")


def weight_casts(model, lowered) -> list:
    """float32 -> bf16 converts in the lowered program whose operand has
    a weight's shape (a layer's slice of a stack, a whole leaf, or an
    expert stack as the serving programs hold it, ``[L*E, ...]``)."""
    shapes = set()
    for leaf in jax.tree.leaves(jax.eval_shape(model.init,
                                               jax.random.key(0))):
        if leaf.ndim >= 2:
            shapes.add("x".join(map(str, leaf.shape)))
            shapes.add("x".join(map(str, leaf.shape[1:])))
        if leaf.ndim == 4:
            shapes.add("x".join(map(str, (leaf.shape[0] * leaf.shape[1],)
                                    + leaf.shape[2:])))
    return [s for s in CONVERT.findall(lowered.as_text()) if s in shapes]


def n_matmul_weights(name) -> int:
    """embed + seven layer stacks, and ``lm_head`` unless tied."""
    return 8 if name == "tied" else 9


def lower_decode(eng, params):
    return eng._decode.lower(
        params, jnp.zeros((B,), I32), eng.kv,
        jnp.zeros((B, eng.blocks_per_slot), I32), jnp.zeros((B,), I32),
        jnp.zeros((B,), jnp.float32), jnp.zeros((B,), I32),
        jax.random.key(0), eng._ffn_counts and eng._ffn_counts[0])


def test_lowered_decode_program_converts_no_weight(built):
    name, model, params = built
    eng = engine(model, params)
    assert weight_casts(model, lower_decode(eng, eng.params)) == []
    # the witness can see one: the same program on float32 parameters
    # converts every matmul weight
    assert len(weight_casts(model, lower_decode(eng, params))) \
        == n_matmul_weights(name)


# -- training is untouched --------------------------------------------------
def test_trainer_keeps_float32_and_casts_at_each_use(built):
    """Float32 master weights in and out of the train step, whose program
    still casts every matmul weight where it is used; an engine built on
    a trainer's live parameters leaves them as they are."""
    name, model, _ = built
    ts = make_train_step(model)
    params, opt = ts.init_fn(jax.random.key(0))
    assert all(a.dtype == F32 for a in jax.tree.leaves(params))
    toks = tokens(model, (2, 16))
    batch = (toks, jnp.roll(toks, -1, 1))
    lowered = ts.step_fn.lower(params, opt, batch)
    assert len(weight_casts(model, lowered)) >= n_matmul_weights(name)
    out_params = jax.eval_shape(ts.step_fn, params, opt, batch)[0]
    assert all(a.dtype == F32 for a in jax.tree.leaves(out_params))

    params, opt, metrics = ts.step_fn(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    eng = engine(model, params)
    assert eng.params["embed"].dtype == BF16
    assert all(a.dtype == F32 and not a.is_deleted()
               for a in jax.tree.leaves(params))
