"""The hybrid short-convolution / attention expert configuration's pieces:
the parameter arithmetic of ISSUE 61 from the published keys, the costs'
bytes by hand, the configuration against the catalog, the reference
against equations written by hand in NumPy and against the builder's
model through the cell's own check (honest and under each fault), the new
metrics' readers, the contract's view of the new entries. (The cell's
``--tiny-cpu`` rehearsal end to end is ``test_rehearsal.py``'s, which runs
every cell of ``BENCHMARK.json``.)"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import lfm2 as builder
from benchmark.costs import shortconv_moe_transformer as costs
from benchmark.lib import scoped_ops
from benchmark.reference import lfm2 as reference

CFG = harness.load_json(harness.ROOT,
                        "benchmark/configs/lfm2-24b-a2b-d10.json")
TINY = {**CFG, **CFG["tiny_cpu"]}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "lfm2-24b-a2b-d10.long_decode_shortconv"
TRAFFIC = harness.load_json(harness.HERE, "traffic",
                            "long_decode_shortconv.json")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_parameter_count_from_the_published_keys():
    assert costs.attention_params(CFG) == 10_485_888 == (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 128)
    assert costs.conv_mixer_params(CFG) == 16_783_360 == (
        2048 * 6144 + 2048 * 2048 + 6_144)
    assert costs.dense_ffn_params(CFG) == 72_351_744
    assert 64 * costs.expert_params(CFG) == 603_979_776
    assert costs.router_params(CFG) == 131_072 + 64
    assert costs.layer_params(CFG, conv=True, dense=True) == 89_139_200
    assert costs.layer_params(CFG, conv=True, dense=False) == 620_898_368
    assert costs.layer_params(CFG, conv=False, dense=False) == 614_600_896
    assert (2 * 89_139_200 + 6 * 620_898_368 + 2 * 614_600_896
            + 134_217_728 + 2_048) == 5_267_090_176
    assert costs.total_params(CFG) == CFG["parameters"] == 5_267_090_176
    assert builder.program_config(CFG, 64).num_params() == CFG["parameters"]
    assert 2 * costs.total_params(CFG) / 2**30 == pytest.approx(9.81, abs=.01)
    # the published 40 layers: the name's 24 B
    whole = {**CFG, "num_hidden_layers": 40,
             "layer_types": CFG["published"]["layer_types"]}
    assert costs.total_params(whole) == pytest.approx(23.84e9, rel=0.001)
    d = costs.dims(CFG)
    assert (d["layers"], d["conv_layers"], d["attn_layers"],
            d["dense_layers"], d["expert_layers"]) == (10, 8, 2, 2, 8)
    assert (d["kv_heads"], d["head_dim"], d["taps"]) == (8, 64, 3)


def test_decode_step_bytes_by_hand():
    assert costs.kv_bytes_per_token_layer(CFG) == 2048
    assert costs.conv_state_bytes_per_slot_layer(CFG) == 8192
    assert costs.state_bytes_per_slot(CFG) == 8 * 8192
    assert 32 * costs.state_bytes_per_slot(CFG) == 2_097_152       # 2 MB
    assert costs.expected_distinct_experts(64, 4, 32) == pytest.approx(
        55.9, abs=0.05)
    # a mixer's weights once and every slot's two rows both ways
    assert costs.shortconv_mixer_bytes(CFG) == 8 * (
        2 * 16_783_360 + 2 * 32 * 8192)
    rows = 32 * 12_000
    assert costs.attention_bytes(CFG, rows) == 2 * rows * 2048
    parts = costs.decode_step_parts(CFG, rows)
    assert sum(parts.values()) == pytest.approx(
        costs.decode_step_bytes(CFG, rows))
    for name, gb in (("experts", 8.44), ("kv", 1.573), ("dense_ffn", 0.289),
                     ("head", 0.268), ("conv_mixers", 0.273),
                     ("attention_weights", 0.042)):
        assert parts[name] / 1e9 == pytest.approx(gb, rel=0.01), name
    step = costs.decode_step_bytes(CFG, rows)
    assert step == pytest.approx(10.89e9, rel=0.005)
    assert 819e9 / step == pytest.approx(75, abs=1)         # steps/s
    # the pool as the engine lays it out: 2 layers x K and V, rows of
    # [4, 128] in bf16, 512 blocks a slot + a scratch run
    pool = 2 * 2 * (32 * 512 + 2) * 32 * 4 * 128 * 2
    assert pool / 2**30 == pytest.approx(2.0, abs=0.001)


def test_configuration_keeps_every_published_key():
    import json
    import os
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] != value and CFG["published"][key] == value
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == [
        "layer_types", "max_position_embeddings", "num_hidden_layers"]
    assert (CFG["reduced"]["num_hidden_layers"]["from"],
            CFG["reduced"]["num_hidden_layers"]["to"]) == (40, 10)
    assert CFG["layer_types"] == row["config"]["layer_types"][:10]
    assert CFG["max_position_embeddings"] == TRAFFIC["engine"]["max_seq"]
    assert CFG["decode_slots"] == TRAFFIC["engine"]["max_slots"] == 32
    assert CFG["decode_block_size"] == TRAFFIC["engine"]["block_size"] == 32
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["intermediate_size"],
            CFG["moe_intermediate_size"], CFG["num_experts"],
            CFG["num_experts_per_tok"], CFG["vocab_size"],
            CFG["conv_L_cache"]) == (2048, 32, 8, 11776, 1536, 64, 4,
                                     65536, 3)
    assert {"tie_word_embeddings", "router_renorm_eps", "head_dim",
            "conv_tap_order", "conv_state_dtype"} <= set(CFG["assumed"])
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == CFG["name"]]
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200


def test_builder_maps_the_published_keys_and_refuses_what_it_has_not():
    cfg = builder.program_config(CFG, 16_384)
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.dense_ffn_dim, cfg.vocab_size) == (
        2048, 10, 32, 8, 64, 1536, 11776, 65_536)
    assert [i for i, t in enumerate(cfg.mixer_types)
            if t == "full_attention"] == [2, 6]
    assert cfg.tie_embeddings and cfg.norm_eps == 1e-5
    assert cfg.router_renorm_eps == 1e-6 and cfg.router_kind == "sigmoid"
    for key, value in (("conv_bias", True), ("use_expert_bias", False),
                       ("model_type", "lfm2")):
        with pytest.raises(ValueError, match=key):
            builder.program_config({**CFG, key: value}, 64)
    tiny = builder.program_config(TINY, 64)
    assert tiny.dtype == jnp.float32 and tiny.expert_layers == 3


def test_reference_imports_nothing_of_the_program():
    import ast
    with open(reference.__file__) as f:
        tree = ast.parse(f.read())
    names = {n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert not [n for n in names if n.startswith(("ray_tpu", "benchmark"))]


def _rms(x, w, eps):
    return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def test_reference_against_equations_by_hand_on_three_layers():
    """(conv, dense), (attention, experts), (conv, experts) in NumPy
    float64 loops, a position at a time: ISSUE 61's equations to the
    letter (the filter's taps, the gates, the head norms before RoPE, the
    biased selection, the renormalisation's 1e-6)."""
    rng = np.random.default_rng(3)
    V, D, F, Fe, E, K, H, Hkv, hd, S, eps = 11, 8, 10, 6, 4, 2, 4, 2, 4, 7, 1e-5
    theta = 100.0

    def n(*shape, scale=0.5):
        return rng.normal(size=shape) * scale

    norms = lambda: {"operator_norm": 1 + n(1, D, scale=0.1),
                     "ffn_norm": 1 + n(1, D, scale=0.1)}
    conv = lambda: {"in_proj": n(1, D, 3 * D), "conv_weight": n(1, D, 3),
                    "out_proj": n(1, D, D)}
    moe = lambda: {"gate": n(1, D, E), "expert_bias": n(1, E, scale=0.3),
                   "experts_w1": n(1, E, D, Fe), "experts_w3": n(1, E, D, Fe),
                   "experts_w2": n(1, E, Fe, D)}
    p = {"embed": n(V, D, scale=1.0), "embedding_norm": 1 + n(D, scale=0.1),
         "conv_dense": {**norms(), **conv(), "w1": n(1, D, F),
                        "w3": n(1, D, F), "w2": n(1, F, D)},
         "attn_moe": {**norms(), **moe(), "q_proj": n(1, D, H * hd),
                      "k_proj": n(1, D, Hkv * hd), "v_proj": n(1, D, Hkv * hd),
                      "out_proj": n(1, H * hd, D),
                      "q_layernorm": 1 + n(1, hd, scale=0.2),
                      "k_layernorm": 1 + n(1, hd, scale=0.2)},
         "conv_moe": {**norms(), **conv(), **moe()}}
    toks = rng.integers(0, V, size=(1, S))

    def short_conv(h, lp):
        bcx = h @ lp["in_proj"][0]
        b, c, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
        g = b * x
        out = np.zeros_like(g)
        for t in range(S):
            for j in range(3):
                if t - 2 + j >= 0:
                    out[t] += lp["conv_weight"][0][:, j] * g[t - 2 + j]
        return (c * out) @ lp["out_proj"][0], g

    def rope(x, t):                        # x [heads, hd]
        half = hd // 2
        ang = t * theta ** (-np.arange(half) / half)
        x1, x2 = x[:, :half], x[:, half:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], -1)

    def attention(h, lp):
        q = (h @ lp["q_proj"][0]).reshape(S, H, hd)
        k = (h @ lp["k_proj"][0]).reshape(S, Hkv, hd)
        v = (h @ lp["v_proj"][0]).reshape(S, Hkv, hd)
        q = np.stack([rope(_rms(q[t], lp["q_layernorm"][0], eps), t)
                      for t in range(S)])
        k = np.stack([rope(_rms(k[t], lp["k_layernorm"][0], eps), t)
                      for t in range(S)])
        o = np.zeros((S, H, hd))
        for t in range(S):
            for head in range(H):
                kv = head // (H // Hkv)
                s = q[t, head] @ k[:t + 1, kv].T / np.sqrt(hd)
                w = np.exp(s - s.max())
                o[t, head] = (w / w.sum()) @ v[:t + 1, kv]
        return o.reshape(S, H * hd) @ lp["out_proj"][0]

    def experts(h, lp):
        out = np.zeros_like(h)
        for t in range(S):
            s = 1 / (1 + np.exp(-(h[t] @ lp["gate"][0])))
            chosen = np.argsort(-(s + lp["expert_bias"][0]))[:K]
            w = s[chosen] / (s[chosen].sum() + 1e-6)
            for e, we in zip(chosen, w):
                out[t] += we * ((_silu(h[t] @ lp["experts_w1"][0][e])
                                 * (h[t] @ lp["experts_w3"][0][e]))
                                @ lp["experts_w2"][0][e])
        return out

    x = p["embed"][toks[0]]
    lp = p["conv_dense"]
    mixed, g0 = short_conv(_rms(x, lp["operator_norm"][0], eps), lp)
    x = x + mixed
    h = _rms(x, lp["ffn_norm"][0], eps)
    x = x + (_silu(h @ lp["w1"][0]) * (h @ lp["w3"][0])) @ lp["w2"][0]
    lp = p["attn_moe"]
    x = x + attention(_rms(x, lp["operator_norm"][0], eps), lp)
    x = x + experts(_rms(x, lp["ffn_norm"][0], eps), lp)
    lp = p["conv_moe"]
    x = x + short_conv(_rms(x, lp["operator_norm"][0], eps), lp)[0]
    x = x + experts(_rms(x, lp["ffn_norm"][0], eps), lp)
    want = _rms(x, p["embedding_norm"], eps) @ p["embed"].T

    f32 = lambda tree: jax.tree.map(lambda v: jnp.asarray(v, jnp.float32),
                                    tree)
    kw = dict(layer_types=("conv", "full_attention", "conv"),
              num_dense_layers=1, num_heads=H, num_kv_heads=Hkv, head_dim=hd,
              top_k=K, norm_topk_prob=True, routed_scaling_factor=1.0,
              rope_theta=theta, eps=eps)
    got = reference.forward(f32(p), jnp.asarray(toks), **kw)
    np.testing.assert_allclose(got[0], want, atol=3e-4, rtol=3e-4)
    g = reference.first_state(f32(p), jnp.asarray(toks), eps=eps)
    np.testing.assert_allclose(g[0], g0, atol=1e-5, rtol=1e-4)
    # every fault departs from it
    for fault in reference.FAULTS:
        other = reference.forward(f32(p), jnp.asarray(toks), fault=fault,
                                  **kw)
        assert float(jnp.max(jnp.abs(other - got))) > 1e-3, fault
    with pytest.raises(ValueError, match="no fault"):
        reference.forward(f32(p), jnp.asarray(toks), fault="nope", **kw)


def _stub(model, params):
    from ray_tpu.llm.engine import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, params, max_slots=8, max_seq=256,
                                   block_size=8)
    return types.SimpleNamespace(model=model, engine=eng)


def test_reference_matches_the_program_through_the_builder_and_the_check():
    """The builder's model at the debug widths through the cell's own
    check (the engine's prefill, placement and decode programs): float32
    against float32, the logits under the system's experts, the experts
    themselves and the first conv layer's rows; a faulty reference, a
    router without its bias and a state that was never written are each
    refused by the number that holds them."""
    from benchmark.drivers.serve_closed_conv import check_logits_state

    model = builder.build_model(TINY, 256)
    params = model.serving_params(model.init(jax.random.key(5)))
    shape = dict(seed=3_000_000_019, prompt_len=100, decode_steps=6,
                 tol_rel_rms=1e-4, state_steps=20, tol_state=1e-4,
                 min_routing_agreement=0.99)
    first = builder.reference_first_state(TINY)
    srv = _stub(model, params)
    r = check_logits_state(srv, builder.reference_forward(TINY), first,
                           **shape)
    assert r["ok"], r
    assert r["routing_agreement"] == r["routing_agreement_own_routing"] == 1
    # 3 expert layers x (106 + 29) true positions of the two sequences
    assert r["routing_choices"] == 3 * (106 + 29)
    assert r["logits_rel_rms_own_routing"] < 1e-4
    assert srv.engine.kv["conv"].shape == (4, 8, 2, 32)
    faulty = check_logits_state(
        srv, builder.reference_forward(TINY, "taps_reversed"), first, **shape)
    assert not faulty["ok"] and faulty["logits_rel_rms"] > 0.05
    # the CHOICE is the agreement's: forced to the system's experts the
    # logits of a reference that selects without the bias are the honest
    # ones, and its own choices are others (routing by itself it is far)
    unbiased = check_logits_state(
        srv, builder.reference_forward(TINY, "no_expert_bias"), first,
        **shape)
    assert not unbiased["ok"] and unbiased["logits_rel_rms"] < 1e-4
    assert unbiased["routing_agreement"] < 0.5
    assert unbiased["logits_rel_rms_own_routing"] > 0.05
    # the filter's own rows: a state check that reads the wrong tokens
    shifted = check_logits_state(
        srv, builder.reference_forward(TINY),
        lambda p, t: first(p, t[:, :-1]), **shape)
    assert not shifted["ok"] and shifted["logits_rel_rms"] < 1e-4
    assert shifted["state_conv_worst_row_rel_rms"] > 0.5
    srv.engine._write_state_impl = lambda pool, state, slots: pool
    unwritten = check_logits_state(srv, builder.reference_forward(TINY),
                                   first, **shape)
    assert not unwritten["ok"] and unwritten["logits_rel_rms"] > 0.05
    with pytest.raises(ValueError, match="first layer"):
        builder.reference_first_state(
            {**TINY, "layer_types": ["full_attention"] + ["conv"] * 4})


def test_the_driver_is_serve_closed_states_loop_with_its_own_check(
        monkeypatch):
    """``serve_closed_conv.run`` runs ``serve_closed_state.closed_loop``
    with the check swapped in for the call (the agreement's limit the
    traffic file's) and back after it, and keeps the record WHOLE:
    ``moe_expert_load`` stays for its metric to read."""
    from benchmark.drivers import serve_closed_conv, serve_closed_state

    seen = {}

    def fake_closed_loop(run):
        seen["check"] = serve_closed_state.check_logits_state
        return {"engine_before": {"moe_expert_load": [[1, 2]]},
                "engine_after": {"moe_expert_load": [[3, 8]]},
                "engine_trace_edges": []}

    kept = serve_closed_state.check_logits_state
    monkeypatch.setattr(serve_closed_state, "closed_loop", fake_closed_loop)
    record = serve_closed_conv.run(types.SimpleNamespace(traffic=TRAFFIC))
    assert seen["check"].func is serve_closed_conv.check_logits_state
    assert seen["check"].keywords == {
        "min_routing_agreement":
            TRAFFIC["correctness"]["routing"]["min_agreement"]}
    assert serve_closed_state.check_logits_state is kept
    load = harness.load_metric("moe.expert_load_max_over_mean.decode")
    assert load.read(record) == pytest.approx(6 * 2 / 8)


def _record(op_seconds, scopes=None, calls=10, program_s=1.0, steps=10,
            blocks=10 * 32 * 375):
    edge = {"decode_steps": 0, "decode_kv_blocks_live": 0}
    return {"config": CFG, "costs": costs, "traffic": TRAFFIC, "peaks": PEAKS,
            "engine_trace_edges": [edge, {"decode_steps": steps,
                                          "decode_kv_blocks_live": blocks}],
            "trace": {"device_ops": [], "programs": {
                "jit__decode_step_paged": {"calls": calls,
                                           "seconds": program_s}}},
            "_decode_op_seconds": {**op_seconds, "": calls},
            "_decode_op_scopes": scopes}


def test_new_metrics_read_the_same_scanned_unrolled_listed_or_not():
    share = harness.load_metric("shortconv.mixer_share_of_step.decode")
    mixer = harness.load_metric("shortconv.mixer_roofline.decode")
    attn = harness.load_metric("gqa64.attention_roofline.decode")
    calls = 10
    mixer_least = costs.shortconv_mixer_bytes(CFG, 32) / 819e9
    rows = 32 * 375 * 32                        # 12,000 positions a slot
    attn_least = costs.attention_bytes(CFG, rows) / 819e9
    scopes = {
        "fusion.1": "jit(f)/while/body/shortconv_in_proj/dot_general",
        "fusion.2": "jit(f)/while/body/shortconv_gate_conv/mul",
        "fusion.3": "jit(f)/shortconv_out_proj/dot_general",
        "fusion.4": "jit(f)/while/body/mlp/dot_general",
        "fusion.5": "jit(f)/attention/gqa64_attention/mul",
        "copy.9": "jit(f)/attention/gqa64_attention/reshape",
        "paged.7": "jit(f)/attention/gqa64_attention/pallas_call"}
    ops = {"fusion.1": calls * mixer_least, "fusion.2": calls * mixer_least / 2,
           "fusion.3": calls * mixer_least / 2, "fusion.4": 0.4,
           "fusion.5": calls * attn_least / 4, "paged.7": calls * attn_least}
    rec = _record(ops, scopes, calls=calls, program_s=1.0)
    assert mixer.read(rec) == pytest.approx(50.0)
    assert share.read(rec) == pytest.approx(100 * 2 * calls * mixer_least)
    assert attn.read(rec) == pytest.approx(80.0)
    # a copy of the window beside the kernel shows as a LOW share
    rec = _record({**ops, "copy.9": calls * attn_least * 5}, scopes,
                  calls=calls)
    assert attn.read(rec) == pytest.approx(100 / 6.25)
    # one scanned operation or many unrolled ones read the same
    unrolled = {f"fusion.1{i}": calls * mixer_least / 8 for i in range(8)}
    rec = _record({**unrolled, "fusion.4": 0.4},
                  {**{k: scopes["fusion.1"] for k in unrolled},
                   "fusion.4": scopes["fusion.4"]}, calls=calls)
    assert mixer.read(rec) == pytest.approx(100.0)
    # nothing to read: untraced, no scope in the program (the parent
    # commit), no compiled scopes, costs of another configuration, no step
    from benchmark.costs import ssm1_hybrid_transformer
    no_scope = _record({"fusion.4": 1.0}, {"fusion.4": scopes["fusion.4"]})
    for metric in (share, mixer, attn):
        for rec in ({}, {"config": CFG, "costs": costs, "traffic": TRAFFIC},
                    no_scope, _record(ops, None)):
            assert metric.read(rec) is None
    other = {**_record(ops, scopes), "costs": ssm1_hybrid_transformer}
    assert mixer.read(other) is None and attn.read(other) is None
    assert attn.read(_record(ops, scopes, steps=0)) is None
    # no trace on disk: the readers return nothing and do not raise
    bare = {"trace": {"programs": {}, "device_ops": []}, "config": CFG,
            "traffic": TRAFFIC, "costs": costs, "peaks": PEAKS}
    assert scoped_ops.decode_op_seconds(dict(bare)) is None
    for metric in (share, mixer, attn):
        assert metric.read(dict(bare)) is None


def test_the_contracts_view_of_the_new_entries():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-d10", "long_decode_shortconv", 1)
    assert len(cell["why"]) <= 200
    assert len(bench["workloads"]) >= 14 and len(bench["configs"]) >= 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new = ("shortconv.mixer_share_of_step.decode",
           "shortconv.mixer_roofline.decode",
           "gqa64.attention_roofline.decode")
    for name in new:
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_out_tokens_per_s"
        mod = harness.load_metric(name)
        assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"])
    for name in ("serve_out_tokens_per_s", "decode_program_roofline",
                 "decode_program_ms.decode", "device_idle_share.decode",
                 "peak_hbm_gib.decode", "compiles_in_window.decode",
                 "kv.state_share_of_cache.decode", "engine.step_ms.decode",
                 "serve.python_cpu_share.decode", "moe.routed_share.decode",
                 "moe.expert_load_max_over_mean.decode"):
        entry, = [m for g in ("end_to_end", "per_layer") for m in bench[g]
                  if m["name"] == name]
        assert CELL in entry["workloads"]
    # not reported here: another model's kernels
    for name in ("ssm1.state_update_roofline.decode",
                 "ssm.state_update_roofline.decode"):
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"]
    assert TRAFFIC["kind"] == "serve_closed_conv"
    eng = TRAFFIC["engine"]
    assert (TRAFFIC["clients"], TRAFFIC["max_tokens"],
            TRAFFIC["prompt_len"], TRAFFIC["min_streamed_before_window"],
            TRAFFIC["trace_seconds"]) == (
        32, 7900, {"dist": "constant", "value": 8192}, 8, 4)
    assert (eng["max_slots"], eng["max_seq"], eng["block_size"],
            eng["max_ongoing_requests"]) == (32, 16_384, 32, 64)
    hybrid = harness.load_json(harness.HERE, "traffic",
                               "long_decode_hybrid.json")
    for key in ("engine", "clients", "prompt_len", "max_tokens",
                "min_streamed_before_window", "trace_seconds"):
        assert TRAFFIC[key] == hybrid[key], key
