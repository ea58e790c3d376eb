"""The hybrid Mamba-1 configuration's pieces: the parameter arithmetic of
ISSUE 57 from the published keys, the costs' bytes by hand, the
configuration against the catalog, the reference against equations
written by hand in NumPy on a 2-layer case and against the builder's model
through the cell's own check, the new metrics' readers, the contract's
view of the new entries. (The cell's ``--tiny-cpu`` rehearsal end to end
is ``test_rehearsal.py``'s, which runs every cell of ``BENCHMARK.json``.)"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import jamba as builder
from benchmark.costs import ssm1_hybrid_transformer as costs
from benchmark.lib import scoped_ops
from benchmark.reference import jamba as reference

CFG = harness.load_json(harness.ROOT, "benchmark/configs/jamba2-3b.json")
TINY = {**CFG, **CFG["tiny_cpu"]}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "jamba2-3b.long_decode_mamba1"
TRAFFIC = harness.load_json(harness.HERE, "traffic",
                            "long_decode_mamba1.json")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_parameter_count_from_the_published_keys():
    assert costs.mamba_params(CFG) == 41_241_792 == (
        26_214_400 + 25_600 + 983_040 + 824_320 + 81_920 + 5_120
        + 13_107_200 + 192)
    assert costs.attention_params(CFG) == 13_762_560
    assert costs.swiglu_params(CFG) == 62_914_560
    assert 26 * 104_161_472 + 2 * 76_682_240 + 167_772_160 + 2_560 \
        == 3_029_337_472
    assert costs.total_params(CFG) == CFG["parameters"] == 3_029_337_472
    assert builder.program_config(CFG, 64).num_params() == CFG["parameters"]
    assert 2 * costs.total_params(CFG) == pytest.approx(6.06e9, rel=0.001)
    assert 2 * costs.total_params(CFG) / 2**30 == pytest.approx(5.64, abs=.01)
    d = costs.dims(CFG)
    assert (d["layers"], d["mamba_layers"], d["attn_layers"]) == (28, 26, 2)
    assert (d["inner"], d["state"], d["dt_rank"], d["kv_heads"],
            d["head_dim"]) == (5120, 16, 160, 1, 128)


def test_decode_step_bytes_by_hand():
    assert costs.kv_bytes_per_token_layer(CFG) == 512
    assert costs.ssm_state_bytes_per_slot_layer(CFG) == 327_680
    assert costs.conv_window_bytes_per_slot_layer(CFG) == 30_720
    assert 32 * costs.state_bytes_per_slot(CFG) == 32 * 26 * 358_400 \
        == pytest.approx(0.30e9, rel=0.01)
    # 8 B a number and A once a layer, NOT 12: no decay from HBM
    assert costs.ssm_state_update_bytes(CFG) == (
        2 * 32 * 26 * 327_680 + 26 * 327_680)
    assert costs.ssm_state_update_least_s(CFG, PEAKS) == pytest.approx(
        0.554e9 / 819e9, rel=0.005)
    live = 32 * 19_000
    parts = costs.decode_step_parts(CFG, live)
    assert sum(parts.values()) == costs.decode_step_bytes(CFG, live)
    for name, gb in (("swiglu", 3.52), ("mamba_projections", 2.14),
                     ("state", 0.605), ("attention_weights", 0.055),
                     ("kv", 0.62), ("head", 0.336)):
        assert parts[name] / 1e9 == pytest.approx(gb, rel=0.01), name
    step = costs.decode_step_bytes(CFG, live)
    assert step == pytest.approx(7.28e9, rel=0.005)
    assert 819e9 / step == pytest.approx(112, abs=1)        # steps/s
    # the K/V pool and the state as the engine lays them out
    pool = (32 * 768 + 1) * 32 * 2 * 512
    assert pool == pytest.approx(0.805e9, rel=0.001)


def test_scan_costs_by_hand():
    assert costs.ssm_scan_exps(CFG, 1) == 81_920
    tokens = 524_288
    exps = 26 * tokens * 81_920
    assert exps == pytest.approx(1.117e12, rel=0.001)
    # u bf16 + dt and y float32 a channel, B and C float32, S in and out
    assert costs.ssm_scan_bytes(CFG, 512) == (
        512 * (5120 * 10 + 2 * 16 * 4) + 2 * 327_680)
    least = 26 * costs.ssm_scan_least_s(CFG, PEAKS, tokens)
    assert least == pytest.approx(exps / (1024 * 940e6)) \
        == pytest.approx(1.16, abs=0.01)
    by_bytes = 26 * costs.ssm_scan_least_s(CFG, PEAKS, tokens, exp_per_s=None)
    assert by_bytes == pytest.approx(0.854, abs=0.005) and by_bytes < least


def test_configuration_keeps_every_published_key():
    import json
    import os
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] == CFG["reduced"][key]["to"] != value
            assert CFG["reduced"][key]["from"] == value \
                == CFG["published"][key]
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == ["max_position_embeddings"]
    assert CFG["max_position_embeddings"] == TRAFFIC["engine"]["max_seq"]
    assert CFG["decode_slots"] == TRAFFIC["engine"]["max_slots"] == 32
    assert CFG["decode_block_size"] == TRAFFIC["engine"]["block_size"] == 32
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == CFG["name"]]
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200


def test_builder_maps_the_published_keys_and_refuses_what_it_has_not():
    cfg = builder.program_config(CFG, 24_576)
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size) == (2560, 28, 20, 1, 128, 8192,
                                             65_536)
    assert (cfg.mamba_inner, cfg.ssm_state, cfg.conv_kernel, cfg.dt_rank,
            cfg.attn_period, cfg.attn_offset) == (5120, 16, 4, 160, 14, 7)
    assert cfg.tie_embeddings and cfg.norm_eps == 1e-6
    assert [i for i in range(28) if cfg.is_attention(i)] == [7, 21]
    for key, value in (("num_experts", 16), ("mamba_proj_bias", True),
                       ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            builder.program_config({**CFG, key: value}, 64)
    tiny = builder.program_config(TINY, 64)
    assert tiny.dtype == jnp.float32 and tiny.attn_layers == 2


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


def test_reference_against_equations_by_hand_on_two_layers():
    """A Mamba-1 layer then an attention layer (period 2, offset 1), each
    with its SwiGLU, in NumPy float64 loops, a position at a time: ISSUE
    57's equations to the letter."""
    rng = np.random.default_rng(3)
    V, D, F, inner, N, R, K, H, hd, S, eps = 11, 6, 10, 12, 3, 2, 4, 2, 4, 7, 1e-6

    def n(*shape, scale=0.5):
        return rng.normal(size=shape) * scale

    sub = lambda: {"input_layernorm": 1 + n(1, D, scale=0.1),
                   "pre_ff_layernorm": 1 + n(1, D, scale=0.1),
                   "gate_proj": n(1, D, F), "up_proj": n(1, D, F),
                   "down_proj": n(1, F, D)}
    p = {"embed": n(V, D, scale=1.0), "final_layernorm": 1 + n(D, scale=0.1),
         "mamba": {**sub(), "in_proj": n(1, D, 2 * inner),
                   "conv1d_weight": n(1, inner, K), "conv1d_bias": n(1, inner),
                   "x_proj": n(1, inner, R + 2 * N),
                   "dt_layernorm": 1 + n(1, R, scale=0.2),
                   "b_layernorm": 1 + n(1, N, scale=0.2),
                   "c_layernorm": 1 + n(1, N, scale=0.2),
                   "dt_proj_weight": n(1, R, inner),
                   "dt_proj_bias": n(1, inner) - 2.0,
                   "A_log": np.log(rng.uniform(1, 4, size=(1, inner, N))),
                   "D": 1 + n(1, inner, scale=0.1),
                   "out_proj": n(1, inner, D)},
         "attn": {**sub(), "q_proj": n(1, D, H * hd), "k_proj": n(1, D, hd),
                  "v_proj": n(1, D, hd), "o_proj": n(1, H * hd, D)}}
    toks = rng.integers(0, V, size=(1, S))

    def swiglu(h, lp):
        return (_silu(h @ lp["gate_proj"][0]) * (h @ lp["up_proj"][0])
                ) @ lp["down_proj"][0]

    x = p["embed"][toks[0]]                                   # [S, D]
    m = p["mamba"]
    h = _rms(x, m["input_layernorm"][0], eps)
    uz = h @ m["in_proj"][0]
    u_in, z = uz[:, :inner], uz[:, inner:]
    u = np.zeros_like(u_in)
    for t in range(S):
        acc = m["conv1d_bias"][0].copy()
        for j in range(K):
            if t - (K - 1) + j >= 0:
                acc += m["conv1d_weight"][0][:, j] * u_in[t - (K - 1) + j]
        u[t] = _silu(acc)
    A = -np.exp(m["A_log"][0])                                # [inner, N]
    state = np.zeros((inner, N))
    y = np.zeros((S, inner))
    for t in range(S):
        rbc = u[t] @ m["x_proj"][0]
        r = _rms(rbc[:R], m["dt_layernorm"][0], eps)
        Bt = _rms(rbc[R:R + N], m["b_layernorm"][0], eps)
        Ct = _rms(rbc[R + N:], m["c_layernorm"][0], eps)
        dt = np.log1p(np.exp(r @ m["dt_proj_weight"][0]
                             + m["dt_proj_bias"][0]))
        for d in range(inner):
            for k in range(N):
                state[d, k] = (np.exp(dt[d] * A[d, k]) * state[d, k]
                               + dt[d] * Bt[k] * u[t, d])
            y[t, d] = state[d] @ Ct + m["D"][0][d] * u[t, d]
    x = x + (y * _silu(z)) @ m["out_proj"][0]
    x = x + swiglu(_rms(x, m["pre_ff_layernorm"][0], eps), m)
    a = p["attn"]
    h = _rms(x, a["input_layernorm"][0], eps)
    q = (h @ a["q_proj"][0]).reshape(S, H, hd)
    k_, v_ = h @ a["k_proj"][0], h @ a["v_proj"][0]           # one K/V head
    o = np.zeros((S, H, hd))
    for t in range(S):
        for head in range(H):
            s = q[t, head] @ k_[:t + 1].T / np.sqrt(hd)
            w = np.exp(s - s.max())
            o[t, head] = (w / w.sum()) @ v_[:t + 1]
    x = x + o.reshape(S, H * hd) @ a["o_proj"][0]
    x = x + swiglu(_rms(x, a["pre_ff_layernorm"][0], eps), a)
    want = _rms(x, p["final_layernorm"], eps) @ p["embed"].T

    f32 = lambda tree: jax.tree.map(lambda v: jnp.asarray(v, jnp.float32),
                                    tree)
    got = reference.forward(
        f32(p), jnp.asarray(toks), n_layers=2, attn_layer_period=2,
        attn_layer_offset=1, num_heads=H, num_kv_heads=1, head_dim=hd,
        d_state=N, dt_rank=R, eps=eps)
    np.testing.assert_allclose(got[0], want, atol=2e-4, rtol=2e-4)
    kept_state, conv_in = reference.first_state(
        f32(p), jnp.asarray(toks), d_state=N, dt_rank=R, eps=eps)
    np.testing.assert_allclose(kept_state[0], state, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(conv_in[0], u_in, atol=1e-5, rtol=1e-4)


def test_reference_matches_the_program_through_the_builder_and_the_check():
    """The builder's model at the debug widths through the cell's own
    check (the engine's prefill, placement and decode programs): float32
    against float32, logits and the first layer's state, which
    ``state_heads`` and ``reference_first_state`` lay out alike."""
    from benchmark.drivers.serve_closed_state import check_logits_state
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    model = builder.build_model(TINY, 256)
    params = model.serving_params(model.init(jax.random.key(5)))
    eng = ContinuousBatchingEngine(model, params, max_slots=8, max_seq=256,
                                   block_size=8)
    r = check_logits_state(
        types.SimpleNamespace(model=model, engine=eng),
        builder.reference_forward(TINY), builder.reference_first_state(TINY),
        seed=3_000_000_019, prompt_len=100, decode_steps=6,
        tol_rel_rms=1e-4, state_steps=20, tol_state=1e-4)
    assert r["ok"], r
    assert model.state_heads(eng.kv["ssm"][0, 0]).shape == (6, 64, 1)
    faulty = check_logits_state(
        types.SimpleNamespace(model=model, engine=eng),
        builder.reference_forward(TINY, "scalar_A"),
        builder.reference_first_state(TINY), seed=3_000_000_019,
        prompt_len=100, decode_steps=6, tol_rel_rms=1e-4, state_steps=20,
        tol_state=1e-4)
    assert not faulty["ok"] and faulty["logits_rel_rms"] > 0.05


def _record(op_seconds, scopes=None, calls=10, program_s=1.0):
    return {"config": CFG, "costs": costs, "traffic": TRAFFIC, "peaks": PEAKS,
            "trace": {"device_ops": [], "programs": {
                "jit__decode_step_paged": {"calls": calls,
                                           "seconds": program_s}}},
            "_decode_op_seconds": {**op_seconds, "": calls},
            "_decode_op_scopes": scopes}


def test_new_metrics_read_the_same_scanned_unrolled_listed_or_not():
    roof = harness.load_metric("ssm1.state_update_roofline.decode")
    share = harness.load_metric("ssm1.mixer_share_of_step.decode")
    least = costs.ssm_state_update_least_s(CFG, PEAKS, 32)
    calls, kernel_s = 10, 10 * 2 * least          # twice the least a step
    scanned = {"ssm1_state_update_pallas": kernel_s, "fusion.1": 0.3}
    runs = {"ssm1_state_update_pallas": kernel_s * 7 / 26,
            "ssm1_state_update_pallas.1": kernel_s * 13 / 26,
            "ssm1_state_update_pallas.2": kernel_s * 6 / 26, "fusion.1": 0.3}
    unrolled = {f"ssm1_state_update_pallas.{i}": kernel_s / 26
                for i in range(26)}
    for ops in (scanned, runs, {**unrolled, "fusion.1": 0.3}):
        assert roof.read(_record(ops)) == pytest.approx(50.0)
    scopes = {"fusion.1": "jit(f)/while/body/ssm1_in_proj/dot_general",
              "fusion.2": "jit(f)/while/body/mlp/dot_general",
              "ssm1_state_update_pallas": "jit(f)/ssm1_state_update/pallas"}
    rec = _record({**scanned, "fusion.2": 0.5}, scopes, program_s=1.0)
    assert share.read(rec) == pytest.approx(100 * (kernel_s + 0.3) / 1.0)
    # nothing to read: untraced, a program without the kernel or scopes,
    # costs of another configuration
    from benchmark.costs import ssm_latent_moe_transformer
    for rec in ({}, {"config": CFG, "costs": costs, "traffic": TRAFFIC},
                _record({"fusion.1": 1.0}, {"fusion.1": "jit(f)/mlp/dot"}),
                {**_record(scanned), "costs": ssm_latent_moe_transformer}):
        assert roof.read(rec) is None
    for rec in ({}, _record({"fusion.1": 1.0}, {"fusion.1": "jit(f)/mlp/x"}),
                _record(scanned, None)):
        assert share.read(rec) is None
    # no trace on disk: the readers return nothing and do not raise
    bare = {"trace": {"programs": {}, "device_ops": []}, "config": CFG,
            "traffic": TRAFFIC, "costs": costs, "peaks": PEAKS}
    assert scoped_ops.decode_op_seconds(dict(bare)) is None
    assert roof.read(dict(bare)) is None and share.read(dict(bare)) is None


def test_scopes_are_read_off_the_compiled_program():
    text = '''
  %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jit(main)/while/body/ssm1_x_proj/mul" source_file="a.py" source_line=3}
  ROOT %custom-call.7 = f32[2]{0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/ssm1_state_update/pallas_call" source_file="b.py"}
  %copy.1 = f32[2]{0} copy(%y)
'''
    found = {m.group(1): m.group(2) for m in map(
        scoped_ops._INSTRUCTION.match, text.splitlines()) if m}
    assert found == {
        "fusion.3": "jit(step)/jit(main)/while/body/ssm1_x_proj/mul",
        "custom-call.7": "jit(step)/ssm1_state_update/pallas_call"}


def test_the_contracts_view_of_the_new_entries():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2-3b", "long_decode_mamba1", 1)
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    assert bench["configs"][-1]["name"] == "jamba2-3b"
    assert len(bench["workloads"]) == 13 and len(bench["configs"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new = ("ssm1.state_update_roofline.decode",
           "ssm1.mixer_share_of_step.decode")
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(new)
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
                 "serve.python_cpu_share.decode"):
        entry, = [m for g in ("end_to_end", "per_layer") for m in bench[g]
                  if m["name"] == name]
        assert entry["workloads"][-1] == CELL
    for name in ("ssm.state_update_roofline.decode",
                 "moe.routed_share.decode"):
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"]
    assert TRAFFIC["kind"] == "serve_closed_state"
    assert (TRAFFIC["clients"], TRAFFIC["max_tokens"],
            TRAFFIC["prompt_len"]["value"]) == (32, 7900, 16_384)
