"""OLMoE's block (64-expert dropless top-k FFN with unnormalised weights,
QK-norm over all heads) on the serving path, at debug widths with seeded
weights, against ``benchmark/reference/olmoe.py``.

The hazard of this architecture is the router's near-ties: under bf16
compute the k-th and (k+1)-th choice swap where the reference has them
nearly equal, and each swap moves that token's logits. So the system is
compared three ways: in float32 compute, where nothing swaps, to 1e-4;
in bf16 with the reference FORCED to the system's routing, at the dense
models' bf16 tolerance; and the system's own bf16 routing may differ from
the reference's only where the reference's gap between the two
probabilities is under ``NEAR_TIE``.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmoe as reference
from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
from ray_tpu.models import (LlamaConfig, LlamaModel, MoEConfig, MoEModel,
                            model_for)
from ray_tpu.ops import moe_dispatch
from ray_tpu.ops.norms import rms_norm
from tests import serving_family as serving
from tests.program_readers import layer_scan_operands
from tests.serving_family import I32, same_sets, seqs

F32_TOL = 1e-4          # max |logit difference|, logits of RMS ~1
# bf16 compute against the float32 reference, relative RMS of the logits:
# what benchmark/tests/test_references.py allows the dense block at debug
# widths (its floor reads 0.016 there)
BF16_REL_RMS = 0.02
# the k-th and (k+1)-th router probability closer than this may swap
# under bf16 compute (probabilities of ~1/8 at 8 experts). Measured over
# the seeds below: 1 % of (token, layer) pairs swapped, the widest gap
# that did read 0.00102; ONE swap in 96 pairs takes the unforced
# comparison from 0.010 to 0.030, past the tolerance
NEAR_TIE = 0.005


def plain_reference(cfg, params, tokens, **kw):
    layers = [{k: v[i] for k, v in params["layers"].items()}
              for i in range(cfg.n_layers)]
    return reference.forward(
        {"embed": params["embed"], "layers": layers,
         "norm_f": params["norm_f"], "lm_head": params["lm_head"]},
        tokens, rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        top_k=cfg.expert_top_k, norm_topk_prob=cfg.norm_topk_prob, **kw)


def near_ties_alone(cfg, model, params, served, toks, experts):
    """The system's own choices differ from the reference's only at
    near-ties of the reference."""
    _, routing = ref_forward(cfg, params, toks, with_routing=True)
    differs = ~same_sets(experts, routing["experts"])
    assert float(jnp.max(jnp.where(differs, routing["gap"], 0.0))) < NEAR_TIE
    assert float(jnp.mean(differs)) < 0.1


def test_float32_routing_is_the_references_everywhere():
    cfg, model, params = make()
    toks = seqs(cfg)
    with jax.default_matmul_precision("highest"):
        _, extras = serving.jitted(model, "_apply_with_extras")(params, toks)
    _, routing = ref_forward(cfg, params, toks, with_routing=True)
    assert extras["experts"].shape == routing["experts"].shape
    assert bool(jnp.all(same_sets(extras["experts"], routing["experts"])))


# -- what the tolerance must refuse -----------------------------------------
class _CapacityDrop(MoEModel):
    """The FFN this PR replaced: 1.25 x T x K / E rows an expert (its
    einsums read a layer's slice of the expert stacks, as under ``ep``)."""

    WHOLE_LAYER_LEAVES = ()

    def _ffn(self, h, layer, live=None, constrain=False, stacks=None):
        c = self.cfg
        out, aux = moe_dispatch.capacity_einsum_ffn(
            h, layer["router"], layer["e_gate"], layer["e_up"],
            layer["e_down"], num_experts=c.num_experts, top_k=c.expert_top_k,
            capacity_factor=1.25, z_coef=0.0, lb_coef=0.0, dtype=c.dtype,
            norm_topk_prob=c.norm_topk_prob)
        return out, {"aux": aux}


class _PerHeadQKNorm(MoEModel):
    def _qk_norm(self, q, k, layer):
        eps = self.cfg.norm_eps
        return (rms_norm(q, layer["q_norm"], eps=eps),
                rms_norm(k, layer["k_norm"], eps=eps))


FAULTS = {
    "capacity_drop": lambda cfg: _CapacityDrop(cfg),
    "renormalised_top_k": lambda cfg: MoEModel(
        dataclasses.replace(cfg, norm_topk_prob=True)),
    "no_qk_norm": lambda cfg: MoEModel(
        dataclasses.replace(cfg, qk_norm=False)),
    "per_head_qk_norm": lambda cfg: _PerHeadQKNorm(cfg),
}


def faulty(fault):
    """Each in float32 compute, so nothing but the fault differs: far
    outside the bf16 tolerance, let alone the float32 one (relative RMS
    0.11 for the capacity drop, 0.31-0.46 for the other three)."""
    cfg, _, params = make()
    toks = seqs(cfg, shape=(4, 32))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(FAULTS[fault](cfg).apply)(params, toks)
    return got, ref_forward(cfg, params, toks)


def engine_stats(eng, stats, cfg, model):
    """The expert FFN processed exactly what a dropless FFN must."""
    assert stats["moe_assignments"] == stats["moe_assignments_expected"] > 0
    load = np.asarray(stats["moe_expert_load"])
    assert load.shape == (cfg.n_layers, cfg.num_experts)
    assert load.sum() == stats["moe_assignments"]
    # K rows a live slot a layer: each layer saw the same number
    assert len(set(load.sum(1).tolist())) == 1


FAMILY = serving.Family(
    config=MoEConfig.debug_olmoe, reference=plain_reference,
    seeded=serving.drawn(("q_norm", "k_norm", "attn_norm", "mlp_norm"), 2048),
    f32_tol=F32_TOL, bf16_rel_rms=BF16_REL_RMS,
    paths={"full_forward": (serving.full_forward, 0),
           "prefill_then_paged_decode": (serving.prefill_then_paged_decode,
                                         0),
           "prefix_prefill": (serving.prefix_prefill, -1)},
    bf16_paths={"full_forward": serving.bf16_full_forward,
                "paged_decode": serving.paged_decode_from_empty},
    bf16_cases=tuple((path, seed) for seed in (1, 2, 3)
                     for path in ("full_forward", "paged_decode")),
    after_bf16=near_ties_alone,
    faults=tuple(sorted(FAULTS)), faulty=faulty,
    fault_floors=lambda fault: (2 * BF16_REL_RMS, 100 * F32_TOL),
    engine_cases=serving.engine_cases(),
    greedy_margin=0.0,      # the reference's first choice and no other
    engine_stats=engine_stats)


make = functools.partial(serving.make, FAMILY)
ref_forward = functools.partial(serving.reference, FAMILY)


globals().update(serving.cases_of(FAMILY))


# -- the dropless FFN itself -------------------------------------------------
def test_dropless_ffn_against_a_loop_over_tokens_and_its_load():
    rng = np.random.default_rng(0)
    T, D, F, E, K = 40, 16, 8, 6, 3
    x = rng.normal(size=(T, D)).astype(np.float32)
    router = rng.normal(size=(D, E)).astype(np.float32)
    eg, eu = (rng.normal(size=(E, D, F)).astype(np.float32) for _ in "gu")
    ed = rng.normal(size=(E, F, D)).astype(np.float32)
    live = rng.random(T) < 0.6
    with jax.default_matmul_precision("highest"):
        out, load, experts, _ = moe_dispatch.dropless_expert_ffn(
            jnp.asarray(x), router, eg, eu, ed, top_k=K,
            norm_topk_prob=False, dtype=jnp.float32, live=jnp.asarray(live))
    logits = x @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros((T, D), np.float32)
    counts = np.zeros(E, np.int64)
    for t in range(T):
        for e in np.argsort(-probs[t], kind="stable")[:K]:
            g, u = x[t] @ eg[e], x[t] @ eu[e]
            want[t] += probs[t, e] * ((g / (1 + np.exp(-g)) * u) @ ed[e])
            counts[e] += live[t]
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(load), counts)
    assert int(load.sum()) == int(live.sum()) * K
    assert experts.shape == (T, K)


def _shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.update(tuple(v.aval.shape) for v in eqn.outvars
                   if hasattr(v.aval, "shape"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, out)
    return out


def _has_token_expert_slot_array(shapes, T, E):
    return any(len(s) >= 3 and T in s and E in s for s in shapes)


def test_a_1536_token_prefill_builds_no_token_by_expert_by_slot_array():
    """The capacity dispatch's one-hot is [T, E, C] with C ~ T: it does
    not scale with T. The dropless FFN builds nothing with a token axis
    AND an expert axis beyond the [T, E] router probabilities."""
    T, E = 1536, 12      # 12: no other axis of the model has that length
    cfg, model, _ = make(max_seq_len=T, num_experts=E)
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: model.init_kv_cache(1, T))
    args = (params, jax.ShapeDtypeStruct((1, T), I32), cache,
            jax.ShapeDtypeStruct((1,), I32))
    shapes = _shapes(jax.make_jaxpr(model.forward_step)(*args).jaxpr, set())
    assert (T, E) in shapes                      # the check can see them
    assert not _has_token_expert_slot_array(shapes, T, E)
    # and it does see the array in the FFN that was replaced
    old = _shapes(jax.make_jaxpr(_CapacityDrop(cfg).forward_step)(
        *args).jaxpr, set())
    assert _has_token_expert_slot_array(old, T, E)


# -- the engine and Serve ------------------------------------------------------
def test_expert_counters_are_zero_and_the_decode_program_the_models_own_for_a_dense_model():
    model = LlamaModel(LlamaConfig.debug(vocab_size=256, max_seq_len=64))
    eng = ContinuousBatchingEngine(
        model, jax.jit(model.init)(jax.random.key(0)), max_slots=2,
        max_seq=64, prefill_buckets=(8, 16), block_size=8)
    eng.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
    stats = eng.stats
    assert stats is eng.stats                    # one dict, updated in place
    assert (stats["moe_assignments"], stats["moe_assignments_expected"],
            stats["moe_expert_load"]) == (0, 0, [])
    assert eng._ffn_counts is None


def test_llm_server_serves_the_expert_model_its_config_describes():
    import json

    from ray_tpu.llm.serving import LLMConfig, LLMServer

    cfg = MoEConfig.debug_olmoe(max_seq_len=64)
    server = LLMServer(LLMConfig(model_config=cfg, max_slots=2, max_seq=64))
    try:
        assert type(server.model) is MoEModel
        assert "e_gate" in server.engine.params["layers"]
        out = server({"prompt": [3, 4, 5, 6], "max_tokens": 5})
        assert len(out["token_ids"]) == 5
        # the generator itself, not a handle: chunks that waited
        # together come as one ``serve.ChunkRun`` (a list of them)
        chunks = [c for obj in server.stream({"prompt": [3, 4, 5, 6],
                                              "max_tokens": 5})
                  for c in (obj if isinstance(obj, list) else [obj])]
        assert [c["token_id"] for c in chunks[:-1]] == out["token_ids"]
        stats = server.stats()
        json.dumps(stats)                                    # JSON-plain
        assert stats["moe_assignments"] == \
            stats["moe_assignments_expected"] == \
            stats["decode_steps"] * cfg.expert_top_k * cfg.n_layers
    finally:
        server._stop.set()
        server._thread.join(10)


@pytest.mark.parametrize("cfg,cls", [
    (LlamaConfig.debug(), LlamaModel), (MoEConfig.debug_moe(), MoEModel)])
def test_the_model_class_follows_from_the_config(cfg, cls):
    assert type(model_for(cfg)) is cls


def test_model_for_refuses_a_config_it_does_not_know():
    with pytest.raises(TypeError, match="no model for"):
        model_for(object())


@pytest.mark.parametrize("layers,params", [(3, 1_464_756_224),
                                           (16, 6_919_161_856)])
def test_num_params_at_the_published_sizes(layers, params):
    cfg = MoEConfig(vocab_size=50304, dim=2048, n_layers=layers, n_heads=16,
                    n_kv_heads=16, ffn_dim=1024, num_experts=64,
                    expert_top_k=8, qk_norm=True)
    assert cfg.num_params() == params


def test_num_params_counts_what_init_makes():
    cfg, model, _ = make()
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert cfg.num_params() == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("method", ["forward_step", "decode_step_paged",
                                    "prefill_with_prefix", "apply"])
def test_expert_scopes_are_in_the_lowered_programs_metadata(method):
    cfg, model, _ = make(max_seq_len=64)
    params = jax.eval_shape(model.init, jax.random.key(0))
    toks = jax.ShapeDtypeStruct((2, 16), I32)
    two = jax.ShapeDtypeStruct((2,), I32)
    pool = jax.eval_shape(lambda: model.init_kv_pool(9, 8))
    prefix = jax.ShapeDtypeStruct(
        (cfg.n_layers, 2, 8) + pool["k"].shape[3:], pool["k"].dtype)
    args = {
        "apply": (params, toks),
        "forward_step": (params, toks, jax.eval_shape(
            lambda: model.init_kv_cache(2, 16)), two),
        "decode_step_paged": (params, two, pool,
                              jax.ShapeDtypeStruct((2, 4), I32), two),
        "prefill_with_prefix": (params, toks, prefix, prefix, two, two),
    }[method]
    text = jax.jit(getattr(model, method)).lower(*args).as_text(
        debug_info=True)
    missing = [s for s in ("moe_router", "moe_dispatch", "moe_experts",
                           "moe_combine", "qk_norm")
               if not re.search(rf'[/("]{s}[/)]', text)]
    assert not missing, f"{method}: no operation under scope(s) {missing}"


def test_the_expert_models_layer_scan_stacks_its_extras_and_never_the_pool():
    """The expert model shares the dense decode step's layer scan (it
    overrides the q/k treatment and the FFN, not the scan): the pool
    rides its carry as one stack, and all it stacks up over layers are
    its FFN's extras: router loss, per-expert load, chosen experts."""
    cfg, model, params = make(n_layers=3)
    xs, ys, carry, per_layer, stack = layer_scan_operands(model, params)
    assert per_layer not in xs and per_layer not in ys
    assert carry.count(stack) == 2
    L, E, K = cfg.n_layers, cfg.num_experts, cfg.expert_top_k
    assert sorted(ys) == sorted([(L,), (L, E), (L, 2, 1, K)])
