"""The hybrid state-space configuration's pieces: the cost model's
arithmetic against ISSUE 47's numbers, the builder's mapping of the
published keys, the configuration against the catalog, the reference
against a NumPy recurrence and against the builder's model through the
cell's own check, the new driver held to ``serve_closed``'s body, the new
metrics' readers. (The cell's ``--tiny-cpu`` rehearsal end to end is
``test_rehearsal.py``'s, which runs every cell of ``BENCHMARK.json``.)"""

import inspect
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import nemotron_h as builder
from benchmark.costs import ssm_latent_moe_transformer as costs
from benchmark.drivers import serve_closed, serve_closed_state
from benchmark.reference import nemotron_h as reference

CFG = harness.load_json(harness.ROOT,
                        "benchmark/configs/nemotron-3-super-d11.json")
TINY = {**CFG, **CFG["tiny_cpu"]}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "nemotron-3-super-d11.long_decode_ssm"
TRAFFIC = harness.load_json(harness.HERE, "traffic", "long_decode_ssm.json")


def test_parameter_counts_at_the_cut_and_of_the_whole_model():
    assert costs.mamba_params(CFG) == 109_640_064
    assert costs.attention_params(CFG) == 35_655_680
    assert costs.expert_params(CFG) == 5_505_024
    assert costs.moe_layer_params(CFG, experts=0) == 54_530_560
    assert 2 * CFG["vocab_size"] * CFG["hidden_size"] == 268_435_456
    assert costs.total_params(CFG) == CFG["parameters"] == 4_648_163_712
    assert builder.program_config(CFG, 64).num_params() == CFG["parameters"]
    assert 2 * costs.total_params(CFG) == pytest.approx(9.30e9, rel=0.001)
    assert 2 * costs.total_params(CFG) / 2**30 == pytest.approx(8.66, abs=.01)
    whole = dict(CFG, **CFG["published"])
    assert costs.total_params(whole) == CFG["parameters_whole_model"] \
        == 120_668_707_840
    # active a token: every layer's dense part and 22 experts a layer
    active = (costs.total_params(whole)
              - 40 * (512 - 22) * costs.expert_params(CFG))
    assert active == pytest.approx(12.77e9, rel=0.002)


def test_decode_step_bytes_by_hand():
    assert costs.kv_bytes_per_token_layer(CFG) == 1024
    assert costs.ssm_state_bytes_per_slot_layer(CFG) == 4 * 2**20
    assert costs.conv_window_bytes_per_slot_layer(CFG) == 61_440
    hit = 128 * (1 - (1 - 22 / 512) ** 64)
    assert hit == pytest.approx(120.3, abs=0.1)
    assert costs.expected_held_experts_hit(CFG, 64) == pytest.approx(hit)
    state = 2 * 64 * 5 * (4 * 2**20 + 61_440)
    assert state == pytest.approx(2.72e9, rel=0.005)
    assert costs.ssm_state_update_bytes(CFG) == 2 * 64 * 5 * 4 * 2**20
    live = 64 * 11_000
    kv = live * 1024
    assert kv == pytest.approx(0.72e9, rel=0.01)
    weights = costs.decode_weight_bytes(CFG)
    assert weights == pytest.approx(8.62e9, rel=0.005)
    assert 2 * 5 * hit * 5_505_024 == pytest.approx(6.62e9, rel=0.005)
    assert 2 * 5 * costs.mamba_matmul_params(CFG) == pytest.approx(
        1.10e9, rel=0.005)
    total = costs.decode_step_bytes(CFG, live)
    assert total == pytest.approx(weights + state + kv)
    assert total == pytest.approx(12.07e9, rel=0.005)
    assert total / 819e9 == pytest.approx(14.7e-3, rel=0.01)
    # the state's term does not grow with the context; the K/V's does
    assert (costs.decode_step_bytes(CFG, 2 * live)
            - costs.decode_step_bytes(CFG, live)) == kv
    # bound by bytes by two orders of magnitude
    assert (costs.ssm_state_update_flops(CFG) / 197e12
            < 0.01 * costs.ssm_state_update_bytes(CFG) / 819e9)
    # the cell's memory: state 1.27 GiB, pool 0.875 GiB
    assert 64 * costs.state_bytes_per_slot(CFG) / 2**30 == pytest.approx(
        1.27, abs=0.005)
    assert 64 * 14_336 * 1024 / 2**30 == 0.875


def test_configuration_keeps_every_published_key():
    import json
    import os
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] == CFG["reduced"][key]["to"] != value
            assert CFG["reduced"][key]["from"] == value \
                == CFG["published"][key]
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == [
        "hybrid_override_pattern", "max_position_embeddings",
        "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    published = row["config"]["hybrid_override_pattern"]
    assert len(published) == 88
    assert [i for i in range(78) if published[i:i + 11] == "MEMEMEMEM*E"] \
        == [27, 38, 49, 60]
    assert CFG["router_experts"] == row["config"]["n_routed_experts"] == 512
    assert CFG["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert CFG["max_position_embeddings"] == TRAFFIC["engine"]["max_seq"]
    assert CFG["decode_slots"] == TRAFFIC["engine"]["max_slots"] == 64
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry, = [c for c in bench["configs"] if c["name"] == CFG["name"]]
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"])
    assert entry["source"] == CFG["source"] and len(entry["source"]) <= 200


def test_builder_maps_the_published_keys():
    from ray_tpu.models.nemotron_h import NemotronHModel

    cfg = builder.program_config(CFG, 14_336)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 32, 2, 128)
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
            cfg.ssm_state, cfg.conv_kernel, cfg.scan_chunk) == (
        128, 64, 8, 128, 4, 128)
    assert cfg.mamba_inner == 8192 and cfg.conv_channels == 10_240
    assert (cfg.num_experts, cfg.held, cfg.expert_top_k, cfg.ffn_dim,
            cfg.latent_dim, cfg.shared_ffn_dim) == (
        512, (0, 128), 22, 2688, 1024, 5376)
    assert (cfg.router_kind, cfg.routed_scaling_factor, cfg.norm_topk_prob,
            cfg.router_n_group) == ("sigmoid", 5.0, True, 1)
    assert cfg.pattern == "MEMEMEMEM*E" and cfg.n_layers == 11
    assert cfg.dtype == jnp.bfloat16
    assert cfg.vocab_size == 32_768 and cfg.norm_eps == 1e-5
    model = builder.build_model(TINY, 64)
    assert type(model) is NemotronHModel and model.cfg.dtype == jnp.float32
    assert model.cfg.held == (4, 4) and model.ffn_load_shape() == (2, 16)
    for key, other in (("num_nextn_predict_layers", 1),
                       ("mlp_hidden_act", "silu"), ("n_group", 2),
                       ("num_hidden_layers", 12)):
        with pytest.raises(ValueError, match=key):
            builder.program_config(dict(CFG, **{key: other}), 64)


def test_the_drawn_bias_changes_some_of_the_chosen_sets():
    """The configuration's ``assumed`` e_score_correction_bias: N(0, 0.001)
    on a seeded router (0.02 x sqrt(4,096): logits of sd 1.28), top-22 of
    512."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(20_000, 512)) * 1.28
    scores = jnp.asarray(1 / (1 + np.exp(-logits)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=512) * CFG["router_bias_init_std"],
                       jnp.float32)
    pick = lambda b: np.sort(np.asarray(
        reference.sigmoid_topk(scores, b, 22)), -1)
    changed = (pick(bias) != pick(jnp.zeros(512))).any(-1).mean()
    assert 0.05 < changed < 0.35
    # and uniform routing over the held range: 128 of 512
    assert (pick(bias) < 128).mean() == pytest.approx(0.25, abs=0.004)


def test_reference_imports_nothing_of_the_program():
    import ast
    with open(reference.__file__) as f:
        tree = ast.parse(f.read())
    names = {n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert not [n for n in names if n.startswith(("ray_tpu", "benchmark"))]


def test_reference_recurrence_and_convolution_against_numpy():
    """The reference's two new pieces against loops in NumPy float64."""
    rng = np.random.default_rng(1)
    B, S, H, P, G, N = 1, 9, 4, 3, 2, 5
    x = rng.normal(size=(B, S, H, P))
    dt = np.log1p(np.exp(rng.normal(size=(B, S, H)) - 1))
    a = -np.exp(rng.uniform(0, 2, size=H))
    Bm, Cm = rng.normal(size=(2, B, S, G, N))
    state = np.zeros((H, P, N))
    want = np.zeros((S, H, P))
    for t in range(S):
        for h in range(H):
            g = h // (H // G)
            state[h] = (np.exp(dt[0, t, h] * a[h]) * state[h]
                        + dt[0, t, h] * np.outer(x[0, t, h], Bm[0, t, g]))
            want[t, h] = state[h] @ Cm[0, t, g]
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    y, final = reference.recurrence(f32(x), f32(dt), f32(a), f32(Bm), f32(Cm))
    np.testing.assert_allclose(y[0], want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(final[0], state, atol=1e-5, rtol=1e-5)
    C, K = 6, 4
    seq, w, b = (rng.normal(size=s) for s in ((1, S, C), (C, K), (C,)))
    conv = np.zeros((S, C))
    for t in range(S):
        for j in range(K):
            if t - (K - 1) + j >= 0:
                conv[t] += w[:, j] * seq[0, t - (K - 1) + j]
    np.testing.assert_allclose(
        reference.causal_conv(f32(seq), f32(w), f32(b))[0], conv + b,
        atol=1e-5, rtol=1e-5)


def test_reference_matches_the_program_through_the_builder_and_the_check():
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    model = builder.build_model(TINY, 256)
    params = model.init(jax.random.key(1))
    server = types.SimpleNamespace(model=model, engine=ContinuousBatchingEngine(
        model, params, max_slots=2, max_seq=256, block_size=8,
        prefill_buckets=(8, 16)))
    # the cell's logits check: a padded prefill of 100 and 23 rows (scan
    # chunks of 16, the last padded), then 12 steps over pages and state
    # and 40 steps to the comparison of the first layer's state rows
    kw = dict(seed=2_147_483_999, prompt_len=100, decode_steps=12,
              tol_rel_rms=1e-4, state_steps=40, tol_state=1e-4)
    first = builder.reference_first_state(TINY)
    with jax.default_matmul_precision("highest"):
        checks = serve_closed_state.check_logits_state(
            server, builder.reference_forward(TINY), first, **kw)
        wrong = serve_closed_state.check_logits_state(
            server, builder.reference_forward(TINY, "no_D"), first, **kw)
        # a placement that leaves the state rows unwritten
        server.engine._write_state_impl = lambda pool, state, slots: pool
        unwritten = serve_closed_state.check_logits_state(
            server, builder.reference_forward(TINY), first, **kw)
    assert checks["ok"] and checks["positions"] == 24
    assert checks["state_steps"] == 40
    assert checks["state_worst_head_rel_rms"] < 1e-5
    assert checks["state_conv_window_rel_rms"] < 1e-5
    assert not wrong["ok"] and wrong["logits_rel_rms"] > 0.01
    assert wrong["state_worst_head_rel_rms"] < 1e-5    # D x is not in S
    assert not unwritten["ok"]
    assert unwritten["state_worst_head_rel_rms"] > 0.1


def test_scan_costs_by_hand():
    # 4 scan chunks of 128: C B^T, its product with x, the carried
    # state's part of y, the state after the chunk
    a_chunk = 2 * (128 * 128 * 8 * 128 + 128 * 128 * 8192
                   + 2 * 128 * 128 * 8192)
    assert costs.ssm_scan_flops(CFG, 512) == 4 * a_chunk == 3_355_443_200
    assert costs.ssm_scan_flops(CFG, 513) == 5 * a_chunk
    assert costs.ssm_scan_bytes(CFG, 512) == (
        512 * (8192 * 6 + 2 * 1024 * 2) + 2 * 4 * 2**20) == 35_651_584


def test_worst_head_is_one_heads_error_not_the_sum():
    want = np.ones((2, 4, 3, 5))
    want[:, 0] *= 100.0                 # one loud head
    got = want.copy()
    got[1, 3] *= 1.25                   # a quiet head a quarter off
    assert serve_closed_state.worst_head(got, want) == pytest.approx(0.25)
    overall = np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2))
    assert overall < 0.002
    got[0, 1, 0, 0] = np.nan
    assert np.isnan(serve_closed_state.worst_head(got, want))


def test_the_two_closed_drivers_run_bodies_are_one(monkeypatch):
    """``serve_closed_state.closed_loop`` is ``serve_closed.run`` line for
    line but for its name and the deploy call, so the two cannot drift;
    ``serve_closed_state.run`` is that record less the one list that
    makes the result's line too long to be read."""
    ours = inspect.getsource(serve_closed_state.closed_loop).splitlines()
    theirs = inspect.getsource(serve_closed.run).splitlines()
    differ = [(a, b) for a, b in zip(ours, theirs) if a != b]
    assert len(ours) == len(theirs)
    assert differ == [
        ("def closed_loop(run) -> dict:", "def run(run) -> dict:"),
        ("    handle, checks = deploy_and_check(run)",
         "    handle, checks = serving.deploy_and_check(run)")]
    for name in ("watch_for_stalls", "watch_window", "silent_at",
                 "resumed_by", "mis_sized", "requests_ended", "STALL_FACTOR",
                 "STALL_GRACE_S", "MIN_WINDOW_S"):
        assert getattr(serve_closed_state, name) is getattr(serve_closed,
                                                            name)
    assert TRAFFIC["kind"] == "serve_closed_state"
    load = [[7] * 512] * 5
    stats = [{"decode_steps": i, "moe_expert_load": load} for i in range(4)]
    record = {"engine_before": stats[0], "engine_after": stats[1],
              "engine_trace_edges": stats[2:]}
    monkeypatch.setattr(serve_closed_state, "closed_loop", lambda run: record)
    assert serve_closed_state.run(None) is record
    assert stats == [{"decode_steps": i} for i in range(4)]


def test_new_metrics_read_the_counters_and_nothing_on_a_program_without():
    from benchmark.costs import dsa_moe_transformer
    names = ("ssm.state_update_roofline.decode",
             "kv.state_share_of_cache.decode")
    roof, share = map(harness.load_metric, names)
    old = {"decode_steps": 9, "kv_pool_bytes": 5}
    for rec in ({}, {"engine_after": old, "config": CFG, "costs": costs,
                     "traffic": TRAFFIC},
                {"config": CFG, "costs": dsa_moe_transformer,
                 "traffic": TRAFFIC, "engine_after": old,
                 "trace": {"device_ops": [["ssm_state_update_pallas.1", 1.]],
                           "programs": {"jit__decode_step_paged": {
                               "calls": 1, "seconds": 1.0}}},
                 "peaks": {"hbm_bytes_per_s": 1, "bf16_flops_per_s": 1}},
                {"config": CFG, "costs": costs, "traffic": TRAFFIC,
                 "trace": {"device_ops": [["fusion.1", 1.0]],
                           "programs": {"jit__decode_step_paged": {
                               "calls": 1, "seconds": 1.0}}},
                 "peaks": {"hbm_bytes_per_s": 1, "bf16_flops_per_s": 1}}):
        assert [m.read(rec) for m in (roof, share)] == [None, None]
    state = 64 * 5 * (4 * 2**20 + 61_440)
    pool = (64 * 448 + 1) * 32 * 1024
    after = {"state_bytes": state, "kv_pool_bytes": pool}
    rec = {"engine_after": after, "config": CFG, "costs": costs,
           "traffic": TRAFFIC}
    assert share.read(rec) == pytest.approx(100 * state / (state + pool))
    assert share.read(rec) == pytest.approx(59.2, abs=0.1)
    least = 2 * 64 * 4 * 2**20 / 819e9         # one layer's update
    # three of the five layers' calls are among the ten listed: a call
    traced = {**rec, "trace": {
        "device_ops": [["ssm_state_update_pallas.3", 10 * 2 * least],
                       ["gmm.1", 2.0],
                       ["ssm_state_update_pallas.5", 10 * 2.5 * least],
                       ["ssm_state_update_pallas.8", 10 * 1.5 * least],
                       ["fusion.1", 1.0]],
        "programs": {"jit__decode_step_paged": {"calls": 10,
                                                "seconds": 1.0}}},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    assert roof.read(traced) == pytest.approx(50.0)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for name in names:
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "serve_out_tokens_per_s"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert CELL in [w["name"] for w in bench["workloads"]]
    for name in ("decode_program_roofline", "moe.held_assignment_share.decode",
                 "moe.routed_share.decode", "serve_out_tokens_per_s"):
        entry, = [m for g in ("end_to_end", "per_layer") for m in bench[g]
                  if m["name"] == name]
        assert CELL in entry["workloads"]
    # its reader needs the list that the record leaves out
    load, = [m for m in bench["per_layer"]
             if m["name"] == "moe.expert_load_max_over_mean.decode"]
    assert CELL not in load["workloads"]
