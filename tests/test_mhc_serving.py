"""Xing4.0's block on the serving path, at debug widths with seeded
weights, against ``benchmark/reference/xing.py``: FOUR residual streams
mixed by manifold-constrained hyper-connections (``ops/mhc.py``: maps made
from the streams a token a sublayer, 20 Sinkhorn rounds) around latent
attention with a compressed query under YaRN and the sigmoid router's
expert block. LOGITS are compared, of ``apply`` and of the three serving
programs.

As for kanana (``test_kanana_serving.py``) the hazard under bf16 compute
is the router's near-ties, so the system is compared in float32 compute,
where nothing swaps, to 1e-4, and in bf16 with the reference FORCED to the
system's routing.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.builders import xing as builder
from benchmark.reference import xing as reference
from ray_tpu.models import MLAConfig
from ray_tpu.ops import mhc
from tests import serving_family as serving
from tests.serving_family import seqs

# max |logit difference|, logits of RMS ~1: float32 sums in another order
# (readings 2e-6 to 9e-6 over the paths below)
F32_TOL = 1e-4


def drawn_maps(layers, key):
    """The maps' alpha and bias, drawn too: a program that drops one, or
    swaps two of the three alphas, differs."""
    for sub_layer in ("attn_hc", "mlp_hc"):
        hc = layers[sub_layer]
        key, a, b = jax.random.split(key, 3)
        hc["alpha"] = 1.0 + 0.3 * jax.random.normal(a, hc["alpha"].shape)
        hc["bias"] = hc["bias"] + 0.3 * jax.random.normal(b, hc["bias"].shape)
    return key


def ref_kwargs(cfg):
    y = cfg.yarn
    return dict(hc_mult=cfg.hc_mult, hc_sinkhorn_iters=cfg.hc_sinkhorn_iters,
                hc_eps=cfg.hc_eps, hc_res_clamp=cfg.hc_res_clamp,
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
                yarn=(y.factor, y.original_max_position, y.beta_fast,
                      y.beta_slow),
                mscale_all_dim=cfg.yarn_mscale_all_dim,
                rms_norm_eps=cfg.norm_eps, top_k=cfg.expert_top_k,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob)


def plain_reference(cfg, params, tokens, **kw):
    return reference.forward(builder.reference_params({}, params), tokens,
                             **ref_kwargs(cfg), **kw)


def the_maps_stay_float32(cfg, model, params, served, toks, experts):
    assert served["layers"]["attn_hc"]["phi"].dtype == jnp.float32


def engine_stats(eng, stats, cfg, model):
    """No branch of the engine knows of the streams, which it reports."""
    assert eng.decode_attention_impl == "mla_xla"
    assert stats["moe_assignments"] == stats["moe_assignments_expected"] > 0
    # the cache is the latent model's: the streams add nothing to a slot
    assert stats["kv_row_bytes"] == 4 * (cfg.kv_lora_rank + model.pe_lanes)
    assert set(eng.kv) == {"k", "v"}


FAMILY = serving.Family(
    config=MLAConfig.debug_xing, reference=plain_reference,
    seeded=serving.drawn(("kv_norm", "q_norm", "attn_norm", "mlp_norm"), 2048,
                         ("layers", "leading_layers"), drawn_maps),
    f32_tol=F32_TOL,
    # bf16 compute against the float32 reference forced to the system's
    # routing, relative RMS of the logits: kanana's block reads 0.011-0.014
    # at these widths under a limit of 0.02; four streams rounded to bf16 at
    # every sublayer boundary read 0.013-0.017 over the seeds below
    bf16_rel_rms=0.025,
    paths={"apply": (serving.full_forward, 0),
           "prefill_then_paged_decode": (serving.prefill_then_paged_decode,
                                         0),
           "chunk_prefill_over_a_gathered_prefix": (serving.prefix_prefill,
                                                    -1)},
    bf16_paths={"apply": serving.bf16_full_forward,
                "paged_decode": serving.paged_decode_from_empty},
    bf16_cases=(("apply", 1), ("paged_decode", 1), ("paged_decode", 2)),
    after_bf16=the_maps_stay_float32,
    # Each of these, done to the REFERENCE, has to show in the comparison:
    # the system computes the published block and not the faulty one. The
    # maps in bf16 (the nearest precision below the one the block states)
    # among them: a limit that passed it would pass a program that computed
    # them so. (The router's faults are ``test_kanana_serving.py``'s; the
    # whole list, the other order of a Sinkhorn round among it, runs in
    # ``benchmark/tests/test_xing.py``; the absorbed attention's Mosaic
    # kernel under this block's softmax scale is ``test_mhc_guards.py``'s.)
    faults=("h_res_identity", "one_sinkhorn_round", "maps_in_bf16",
            "h_post_without_2", "no_mscale", "no_q_norm"),
    fault_floors=lambda fault: (1e-3 if fault == "maps_in_bf16" else 0.03,
                                30 * F32_TOL),
    engine_kw=dict(serving.ENGINE_KW, prefill_buckets=(8, 32)),
    # the prefix hit is on latent blocks
    engine_cases=serving.engine_cases(),
    engine_stats=engine_stats)


make = functools.partial(serving.make, FAMILY)


globals().update(serving.cases_of(FAMILY))


def test_the_streams_stay_distinct_and_the_maps_move():
    """At the seeded weights the four streams leave the last layer as four
    different vectors, and a sublayer's ``H_res`` differs from token to
    token: the mechanism does not run on copies of one vector."""
    cfg, model, params = make()

    @jax.jit
    def carried(params, toks):
        def step(x, layer_and_kind, stacks):
            layer = layer_and_kind[0]
            x, _, _ = model._layer(x, layer, None, lambda q, k, v: (
                model._attention(q, k, v, None, layer=layer), None))
            return x, None
        return model._scan_layers(step, model._embed(params, toks), params,
                                  (params["layers"], None), (None,))[0]

    X = carried(params, seqs(cfg))
    streams = X.reshape(*X.shape[:2], cfg.hc_mult, cfg.dim)
    apart = jnp.std(streams, axis=2).mean() / jnp.std(streams)
    assert float(apart) > 0.1
    hc = jax.tree.map(lambda a: a[-1], params["layers"]["mlp_hc"])
    h_res = mhc.stream_maps(
        X, cfg.hc_mult, hc["phi"], hc["alpha"], hc["bias"],
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, norm_eps=cfg.norm_eps,
        clamp=cfg.hc_res_clamp)[2]
    assert float(jnp.std(h_res, axis=(0, 1)).mean()) > 0.01
