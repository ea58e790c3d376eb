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
import numpy as np
import pytest

from benchmark.builders import xing as builder
from benchmark.reference import xing as reference
from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
from ray_tpu.models import MLAConfig, model_for
from ray_tpu.ops import mhc

# max |logit difference|, logits of RMS ~1: float32 sums in another order
# (readings 2e-6 to 9e-6 over the paths below)
F32_TOL = 1e-4
# bf16 compute against the float32 reference forced to the system's
# routing, relative RMS of the logits: kanana's block reads 0.011-0.014 at
# these widths under a limit of 0.02; four streams rounded to bf16 at every
# sublayer boundary read 0.013-0.017 over the seeds below
BF16_REL_RMS = 0.025
I32 = jnp.int32


@functools.lru_cache(maxsize=None)
def block(dtype=jnp.float32):
    """(cfg, model), one a dtype: a model's programs compile once."""
    cfg = MLAConfig.debug_xing(dtype=dtype)
    return cfg, model_for(cfg)


@functools.lru_cache(maxsize=None)
def jitted(model, name):
    """``jax.jit`` of a model's method, one a (model, method): a second
    ``jax.jit`` of the same method would compile it again."""
    return jax.jit(getattr(model, name))


def seeded(model, seed):
    """``init``'s weights with the norms, and the maps' alpha and bias,
    drawn too: a program that drops one, or swaps two of the three
    alphas, differs."""
    params = model.init(jax.random.key(seed))
    key = jax.random.key(seed + 100)
    for stack in ("layers", "leading_layers"):
        layers = params[stack]
        for name in ("kv_norm", "q_norm", "attn_norm", "mlp_norm"):
            key, sub = jax.random.split(key)
            layers[name] = 1.0 + 0.3 * jax.random.normal(sub,
                                                         layers[name].shape)
        for sub_layer in ("attn_hc", "mlp_hc"):
            hc = layers[sub_layer]
            key, a, b = jax.random.split(key, 3)
            hc["alpha"] = 1.0 + 0.3 * jax.random.normal(a, hc["alpha"].shape)
            hc["bias"] = hc["bias"] + 0.3 * jax.random.normal(
                b, hc["bias"].shape)
    params["layers"]["router"] *= (2048 / model.cfg.dim) ** 0.5
    return params


@functools.lru_cache(maxsize=None)
def make(dtype=jnp.float32, seed=1):
    """(cfg, model, params), made once a (dtype, seed): nobody writes into
    the tree. The weights are float32 whatever the compute dtype, so ONE
    program draws them."""
    cfg, model = block(dtype)
    return cfg, model, draw(seed)


@functools.lru_cache(maxsize=None)
def draw(seed):
    return jax.jit(functools.partial(seeded, block()[1]))(seed)


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


REF_SHAPE = (4, 48)


@functools.lru_cache(maxsize=None)
def jitted_reference(block_kw, kw):
    """The plain reference as ONE program a variant (op by op it takes
    seconds a call at these sizes)."""
    return jax.jit(lambda params, tokens, forced: reference.forward(
        params, tokens, forced_experts=forced, **dict(block_kw), **dict(kw)))


def ref_forward(cfg, params, tokens, forced_experts=None, **kw):
    """The reference's logits for ``tokens`` (an array [B, S] or a list of
    token lists), always computed at ONE shape, ``REF_SHAPE`` (the rows
    right-padded, more rows added: a causal model's logits at a position
    do not see what follows it), so that its programs compile once a
    process and not once a length."""
    rows = [list(map(int, t)) for t in tokens]
    B, S = REF_SHAPE
    assert len(rows) <= B and max(map(len, rows)) <= S
    padded = np.ones(REF_SHAPE, np.int32)
    for i, row in enumerate(rows):
        padded[i, :len(row)] = row
    if forced_experts is not None:       # [L, b, s, K] -> [L, B, S, K]
        L, b, s, K = forced_experts.shape
        forced_experts = jnp.zeros((L, B, S, K), forced_experts.dtype).at[
            :, :b, :s].set(forced_experts)
    out = jitted_reference(tuple(sorted(ref_kwargs(cfg).items())),
                           tuple(sorted(kw.items())))(
        builder.reference_params({}, params), jnp.asarray(padded),
        forced_experts)
    if len({len(r) for r in rows}) == 1:
        return out[:len(rows), :len(rows[0])]
    return [out[i, :len(r)] for i, r in enumerate(rows)]


def seqs(cfg, shape=(2, 24), seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, cfg.vocab_size, shape), I32)


def rel_rms(got, want):
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))


def full_forward(model, params, toks):
    return jitted(model, "apply")(params, toks)


def prefill_then_paged_decode(model, params, toks, prompt=16, bs=8):
    """``check_logits``'s route: bucket prefill into a slot-major cache of
    latent rows, scattered into pool blocks, then paged decode steps."""
    B, total = toks.shape
    nb = -(-total // bs)
    cache = model.init_kv_cache(B, nb * bs)
    padded = jnp.zeros((B, nb * bs), I32).at[:, :prompt].set(toks[:, :prompt])
    pre, cache = jitted(model, "forward_step")(params, padded, cache,
                                               jnp.zeros((B,), I32))
    pool = model.init_kv_pool(B * nb + 1, bs)
    L = cache["k"].shape[0]
    ids = jnp.arange(B * nb)
    pool = {k: pool[k].at[:, ids].set(
        cache[k].reshape(L, B * nb, bs, *cache[k].shape[3:]))
        for k in ("k", "v")}
    tables = ids.astype(I32).reshape(B, nb)
    out = [pre[:, :prompt]]
    step = jitted(model, "decode_step_paged")
    for pos in range(prompt, total):
        logits, pool = step(
            params, toks[:, pos], pool, tables, jnp.full((B,), pos, I32))
        out.append(logits[:, None])
    return jnp.concatenate(out, axis=1)


def prefix_prefill(model, params, toks, prefix=8):
    """The last-token logits of a chunk prefill over a gathered prefix of
    latent rows (from a plain prefill), padded as the engine pads."""
    B, total = toks.shape
    cache = model.init_kv_cache(B, prefix)
    _, cache = jitted(model, "forward_step")(params, toks[:, :prefix], cache,
                                             jnp.zeros((B,), I32))
    padded = {n: jnp.pad(a, ((0, 0), (0, 0), (0, 8), (0, 0)))
              for n, a in cache.items()}
    suffix = jnp.zeros((B, 32), I32).at[:, :total - prefix].set(
        toks[:, prefix:])
    logits, _ = jitted(model, "prefill_with_prefix")(
        params, suffix, padded["k"], padded["v"], jnp.full((B,), prefix, I32),
        jnp.full((B,), total - prefix, I32))
    return logits[:, None]                       # position total - 1


PATHS = {"apply": (full_forward, 0),
         "prefill_then_paged_decode": (prefill_then_paged_decode, 0),
         "chunk_prefill_over_a_gathered_prefix": (prefix_prefill, -1)}


@pytest.fixture(scope="module")
def float32_block():
    cfg, model, params = make()
    toks = seqs(cfg)
    return cfg, model, params, toks, ref_forward(cfg, params, toks)


@pytest.fixture(scope="module")
def served_logits(float32_block):
    cfg, model, params, toks, _ = float32_block
    with jax.default_matmul_precision("highest"):
        return prefill_then_paged_decode(model, params, toks)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_float32_compute_matches_the_reference(path, float32_block):
    cfg, model, params, toks, want = float32_block
    run, first = PATHS[path]
    with jax.default_matmul_precision("highest"):
        got = run(model, params, toks)
    want = want[:, first:]
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=F32_TOL)


# Each of these, done to the REFERENCE, has to show in the comparison:
# the system computes the published block and not the faulty one. The maps
# in bf16 (the nearest precision below the one the block states) among
# them: a limit that passed it would pass a program that computed them so.
# (The router's faults are ``test_kanana_serving.py``'s; the whole list,
# the other order of a Sinkhorn round among it, runs in
# ``benchmark/tests/test_xing.py``; the absorbed attention's Mosaic kernel
# under this block's softmax scale is ``test_mhc_guards.py``'s.)
@pytest.mark.parametrize("fault", [
    "h_res_identity", "one_sinkhorn_round", "maps_in_bf16",
    "h_post_without_2", "no_mscale", "no_q_norm"])
def test_a_faulty_block_is_refused(fault, float32_block, served_logits):
    cfg, model, params, toks, _ = float32_block
    got = served_logits
    wrong = ref_forward(cfg, params, toks, fault=fault)
    assert float(jnp.max(jnp.abs(got - wrong))) > 30 * F32_TOL, fault
    assert rel_rms(got, wrong) > (
        1e-3 if fault == "maps_in_bf16" else 0.03)


def paged_decode_from_empty(model, params, toks, bs=8):
    """Every position by a counted paged decode step, with the experts
    each step chose: -> (logits [B, S, V], experts [L_moe, B, S, K])."""
    B, total = toks.shape
    nb = -(-total // bs)
    pool = model.init_kv_pool(B * nb + 1, bs)
    tables = jnp.arange(B * nb, dtype=I32).reshape(B, nb)
    step = jitted(model, "decode_step_paged_counted")
    logits, experts = [], []
    for pos in range(total):
        out, pool, extras = step(params, toks[:, pos], pool, tables,
                                 jnp.full((B,), pos, I32))
        logits.append(out[:, None])
        experts.append(extras["experts"])
    return jnp.concatenate(logits, 1), jnp.concatenate(experts, 2)


def bf16_apply(model, params, toks):
    logits, extras = jitted(model, "_apply_with_extras")(params, toks)
    return logits, extras["experts"]


@pytest.mark.parametrize("path,seed", [("apply", 1), ("paged_decode", 1),
                                       ("paged_decode", 2)])
def test_bf16_compute_with_the_reference_forced_to_its_routing(path, seed):
    cfg, model, params = make(jnp.bfloat16, seed)
    toks = seqs(cfg, seed=seed)
    run = bf16_apply if path == "apply" else paged_decode_from_empty
    served = model.serving_params(params)
    assert served["layers"]["attn_hc"]["phi"].dtype == jnp.float32
    got, experts = run(model, served, toks)
    want = ref_forward(cfg, params, toks, forced_experts=experts)
    assert rel_rms(got.astype(jnp.float32), want) < BF16_REL_RMS


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


# -- the engine ------------------------------------------------------------------
def _prompt(cfg, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n)]


ENGINE_CASES = {
    # name: (prompt lengths, tokens out, the stats key that must move)
    "bucket_prefill": ((5, 12, 20), 6, "prefills"),
    "chunked_prefill": ((40, 9), 6, "prefills"),
    "prefix_hit_on_latent_blocks": ("shared", 6, "prefix_prefills"),
    "preemption_by_recompute": ((20, 21, 22), 12, "preemptions"),
}


@pytest.fixture(scope="module")
def engine():
    """ONE engine for the four cases (its programs compile once): a pool
    of ten blocks, which three requests of 20-odd tokens that generate
    twelve overflow and everything else fits."""
    cfg, model, params = make()
    return ContinuousBatchingEngine(
        model, params, max_slots=4, max_seq=64, prefill_buckets=(8, 32),
        block_size=8, num_blocks=10)


@pytest.mark.parametrize("case", list(ENGINE_CASES))
def test_engine_greedy_tokens_are_the_references_argmax(case, engine):
    """Through ``ContinuousBatchingEngine`` in float32 compute: every
    generated token is the reference's first choice given the prompt and
    the tokens before it (teacher forced) unless the reference has its
    first two within 1e-3: a prefix hit reads another request's latent
    blocks back and gives what the cold path gives, a preempted request
    is recomputed to the same tokens; and no branch of the engine knows
    of the streams, which it reports."""
    lens, n_out, moved = ENGINE_CASES[case]
    cfg, model, params = make()
    eng = engine
    if lens == "shared":
        head = _prompt(cfg, 16, 50)
        prompts = [head + _prompt(cfg, n, i) for i, n in enumerate((3, 7))]
    else:
        prompts = [_prompt(cfg, n, i) for i, n in enumerate(lens)]
    before = eng.stats[moved]
    with jax.default_matmul_precision("highest"):
        if lens == "shared":        # the second finds the first's blocks
            reqs = [eng.generate([p], SamplingParams(max_tokens=n_out))[0]
                    for p in prompts]
        else:
            reqs = eng.generate(prompts, SamplingParams(max_tokens=n_out))
    wants = ref_forward(cfg, params, [p + r.output
                                      for p, r in zip(prompts, reqs)])
    for prompt, req, want in zip(prompts, reqs, wants):
        assert len(req.output) == n_out
        want = np.asarray(want[len(prompt) - 1:len(prompt) - 1 + n_out])
        for row, tok in zip(want, req.output):
            assert row.max() - row[tok] < 1e-3
    stats = eng.stats
    assert stats[moved] > before
    assert eng.decode_attention_impl == "mla_xla"
    assert stats["moe_assignments"] == stats["moe_assignments_expected"] > 0
    # the cache is the latent model's: the streams add nothing to a slot
    assert stats["kv_row_bytes"] == 4 * (cfg.kv_lora_rank + model.pe_lanes)
    assert set(eng.kv) == {"k", "v"}
