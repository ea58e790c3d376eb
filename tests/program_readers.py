"""Readers of jaxprs and lowered programs that more than one test file
uses (pytest does not collect this module; no test module imports
another: ``tests/test_tiers.py``)."""

import importlib

import jax
import jax.numpy as jnp

from ray_tpu.llm.engine import ContinuousBatchingEngine

I32 = jnp.int32


def scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from scans(sub)


def layer_scan_operands(model, params, slots=2, num_blocks=7, bs=8, maxb=2):
    """(xs shapes, ys shapes, carry shapes, the pool's per-layer shape,
    the stack's shape) of the layer scan of ``decode_step_paged``."""
    pool = model.init_kv_pool(num_blocks, bs)
    jaxpr = jax.make_jaxpr(model.decode_step_paged)(
        params, jnp.zeros((slots,), I32), pool,
        jnp.zeros((slots, maxb), I32), jnp.zeros((slots,), I32))
    L = model.cfg.n_layers
    scan, = [e for e in scans(jaxpr.jaxpr) if e.params["length"] == L]
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]
    per_layer = tuple(pool["k"].shape[1:])
    return (shapes(scan.invars[n_consts + n_carry:]),
            shapes(scan.outvars[n_carry:]),
            shapes(scan.invars[n_consts:n_consts + n_carry]),
            per_layer, (L * per_layer[0],) + per_layer[1:])


def lowered_programs(name: str) -> dict:
    """An engine's five programs, lowered, at the debug widths of the
    benchmark configuration ``name``."""
    from benchmark import run as harness
    cfg = harness.load_json(harness.ROOT, f"benchmark/configs/{name}.json")
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    model = builder.build_model({**cfg, **cfg["tiny_cpu"]}, 128)
    params = jax.eval_shape(
        lambda key: model.serving_params(model.init(key)), jax.random.key(0))
    eng = ContinuousBatchingEngine(
        model, jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params),
        max_slots=4, max_seq=128, prefill_buckets=(16, 32), block_size=8)
    assert eng.eva is None and model.eva is None
    # a table a kind, and ids a kind, where the model has two
    kinds = () if model.layer_kinds is None else (2,)
    assert (eng.window is None) == (model.layer_kinds is None)

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, I32)

    pool = jax.eval_shape(lambda: eng.kv)
    decode = [params, S(4), pool, S(*kinds, 4, eng.blocks_per_slot), S(4),
              jax.ShapeDtypeStruct((4,), jnp.float32), S(4),
              jax.eval_shape(lambda: jax.random.key(0)),
              eng._ffn_counts and S(*eng._ffn_counts[0].shape)]
    prefix = jax.eval_shape(lambda: model.init_kv_cache(1, 32))
    return {
        "decode": eng._decode.lower(*decode),
        "prefill": eng._prefill.lower(params, S(2, 32), S(2)),
        "insert": eng._insert.lower(
            pool, jax.eval_shape(lambda: model.init_kv_cache(2, 32)),
            S(*kinds, 8)),
        "gather": eng._gather.lower(pool, S(*kinds, 1, 4)),
        "prefill_prefix": eng._prefill_prefix.lower(
            params, S(1, 16), prefix["k"], prefix["v"], S(1), S(1)),
    }
