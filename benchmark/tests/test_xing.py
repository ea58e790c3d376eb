"""The Xing4.0 configuration's pieces: the parameter arithmetic of the
configuration's file against the program and ISSUE 50's numbers, every
``reduced`` key against the published value, the reference's stream maps
against a second, loop-free numpy writing of one sublayer, the seeded
maps' spread over tokens, every fault of the reference at debug widths,
the contract's entries, and the ``--tiny-cpu`` run of the cell end to
end."""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import xing as builder
from benchmark.costs import mhc_mla_moe_transformer as costs
from benchmark.lib import serving
from benchmark.reference import xing as reference

CFG = harness.load_json(harness.ROOT,
                        "benchmark/configs/xing4.0-29b-a4b-d5.json")
TINY = {**CFG, **CFG["tiny_cpu"]}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "xing4.0-29b-a4b-d5.long_decode_mhc"
TRAFFIC = harness.load_json(harness.HERE, "traffic", "long_decode_mhc.json")


def test_parameter_counts_at_the_cut_and_the_published_depth():
    assert costs.attention_params(CFG) == 28_411_136
    assert 2 * costs.mhc_params(CFG) == 688_182
    assert costs.dense_ffn_params(CFG) == 99_090_432
    assert costs.expert_params(CFG) == 11_010_048
    assert costs.router_params(CFG) == 229_440
    assert costs.total_params(dict(CFG, num_hidden_layers=1)) \
        - 2 * 131_072 * 3584 - 3584 == 128_196_918          # the dense layer
    assert (costs.total_params(CFG)
            - costs.total_params(dict(CFG, num_hidden_layers=4))) \
        == 744_989_046                                       # an expert layer
    assert costs.total_params(CFG) == CFG["parameters"] == 4_047_680_782
    assert builder.program_config(CFG, 128).num_params() == CFG["parameters"]
    whole = {**CFG, **CFG["published"]}
    assert costs.total_params(whole) == CFG["parameters_whole_model"] \
        == 29_505_505_264
    assert builder.program_config(
        {**whole, "num_nextn_predict_layers": 0}, 128).num_params() \
        == costs.total_params(whole)
    # 8.10 GB = 7.54 GiB in bf16; the pool 16,385 blocks x 5 x 32 x 1,280 B
    assert 2 * costs.total_params(CFG) / 2**30 == pytest.approx(7.54, abs=.01)
    assert 16_385 * 5 * 32 * 1280 / 2**30 == pytest.approx(3.125, abs=0.005)
    # about 4.4 B active a token: what a token multiplies with + its
    # embedding row's table
    assert (costs.matmul_params(whole) + 131_072 * 3584) / 1e9 \
        == pytest.approx(4.4, abs=0.05)
    assert costs.mhc_bytes(CFG, 32) / 10 == pytest.approx(
        24 * 14_336 * 4 + 32 * 10 * 3584 * 2)
    assert costs.mla_attention_bytes(CFG, 1) == 5 * 1152


def test_configuration_keeps_every_published_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] == CFG["reduced"][key]["to"] != value
            assert CFG["reduced"][key]["from"] == value
            assert CFG["published"][key] == value      # restores it
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == [
        "first_k_dense_replace", "max_position_embeddings",
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert CFG["max_position_embeddings"] == TRAFFIC["engine"]["max_seq"]
    for item in ("streams_entry_exit", "hc_eps", "sinkhorn_order",
                 "h_res_clamp", "maps_rms_statistic", "rope_interleave",
                 "hc_weights", "e_score_correction_bias", "decode_slots",
                 "decode_block_size"):
        assert item in CFG["assumed"], item


def numpy_sublayer_maps(X, phi, alpha, bias):
    """ONE sublayer's maps, loop-free in float64: 20 Sinkhorn rounds
    written as the recursion's closed alternation on logs is no shorter,
    so the rounds are a reduce over a repeated pair of normalisations."""
    from functools import reduce
    v = np.asarray(X, np.float64).reshape(X.shape[0], -1)
    m = (v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-6)) @ np.asarray(
        phi, np.float64)
    m = m * np.repeat(np.asarray(alpha, np.float64), [4, 4, 16]) + bias
    rounds = [lambda M: M / (M.sum(-1, keepdims=True) + 1e-6),
              lambda M: M / (M.sum(-2, keepdims=True) + 1e-6)] * 20
    M = reduce(lambda M, f: f(M), rounds,
               np.exp(np.clip(m[:, 8:], -30, 30)).reshape(-1, 4, 4))
    return 1 / (1 + np.exp(-m[:, :4])), 2 / (1 + np.exp(-m[:, 4:8])), M


def test_reference_maps_against_a_second_writing_and_the_seeded_spread():
    """The reference's ``stream_maps`` against the numpy one, and what
    the builder's seeded values give over 20,000 tokens: ``H_res`` near
    the identity (diagonal ~0.81) with every entry moving from token to
    token (sd ~0.077), rows short of 1 by 3e-4 in the median."""
    rng = np.random.default_rng(0)
    n, C, T = 4, 64, 20_000
    X = rng.normal(size=(T, n, C)) * rng.uniform(0.5, 3.0, (T, n, 1))
    phi = rng.normal(size=(n * C, 24)) * (n * C) ** -0.5
    alpha = np.ones(3)
    bias = np.concatenate([np.zeros(8), (3.0 * np.eye(4)).reshape(-1)])
    want = numpy_sublayer_maps(X, phi, alpha, bias)
    with jax.default_matmul_precision("highest"):
        got = reference.stream_maps(
            jnp.asarray(X[None, :500], jnp.float32), phi, alpha, bias,
            iters=20, eps=1e-6, norm_eps=1e-6, clamp=(-30.0, 30.0))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g[0], w[:500], atol=2e-5)
    H = want[2]
    assert np.mean([H[:, i, i].mean() for i in range(4)]) \
        == pytest.approx(0.81, abs=0.02)
    assert H.std(0).mean() == pytest.approx(0.077, abs=0.01)
    short = np.abs(H.sum(-1) - 1).max(-1)
    assert 1e-4 < np.median(short) < 1e-3 and short.max() < 0.05
    np.testing.assert_allclose(H.sum(-2), 1.0, atol=2e-6)


def test_reference_imports_nothing_of_the_program():
    import ast
    with open(reference.__file__) as f:
        src = f.read()
    tree = ast.parse(src)
    names = {n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    assert not [n for n in names if n.startswith(("ray_tpu", "benchmark"))]
    assert 'default_matmul_precision("highest")' in src


def test_reference_matches_the_program_through_the_builder_and_the_check():
    model = builder.build_model(TINY, 128)
    params = model.init(jax.random.key(1))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 60)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, toks)
    want = builder.reference_forward(TINY)(params, toks)[:, :]
    np.testing.assert_allclose(got, want, atol=2e-4)
    server = types.SimpleNamespace(model=model, engine=types.SimpleNamespace(
        params=params, block_size=8))
    with jax.default_matmul_precision("highest"):
        checks = serving.check_logits(
            server, builder.reference_forward(TINY), seed=2_147_483_999,
            prompt_len=40, decode_steps=24, tol_rel_rms=1e-4)
        assert checks["ok"] and checks["positions"] == 48
        for fault in reference.FAULTS:
            if fault == "no_clamp":      # |m_res| < 30 at seeded weights
                continue
            wrong = serving.check_logits(
                server, builder.reference_forward(TINY, fault),
                seed=2_147_483_999, prompt_len=40, decode_steps=24,
                tol_rel_rms=1e-4)
            assert not wrong["ok"] and wrong["logits_rel_rms"] > 3e-4, fault


def test_contract_entries():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = bench["workloads"][-1]
    assert cell == {"name": CELL, "config": "xing4.0-29b-a4b-d5",
                    "traffic": "long_decode_mhc", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    conf = bench["configs"][-1]
    assert conf["file"] == "benchmark/configs/xing4.0-29b-a4b-d5.json"
    assert conf["source"] == CFG["source"] and len(conf["why"]) <= 200
    assert sorted(conf["reduced"]) == sorted(CFG["reduced"])
    # no metric of the streams' own: XLA's fusions won (``ops/mhc.py``), a
    # fusion has no name the trace's reduction could find, and a metric
    # that finds nothing to read is none (PERF.md section 7)
    assert not [m for m in bench["per_layer"] if m["name"].startswith("mhc")]
    reports = {m["name"] for g in ("end_to_end", "per_layer")
               for m in bench[g]
               if "workloads" not in m or CELL in m["workloads"]}
    assert {"serve_out_tokens_per_s", "setup_s", "decode_program_roofline",
            "moe.routed_share.decode", "moe.expert_load_max_over_mean.decode",
            "mla.attention_roofline.decode", "kv.latent_share_of_mha.decode",
            "peak_hbm_gib.decode", "compiles_in_window.decode"} <= reports
    assert (TRAFFIC["clients"], TRAFFIC["prompt_len"]["value"],
            TRAFFIC["max_tokens"], TRAFFIC["engine"]["max_slots"],
            TRAFFIC["engine"]["max_seq"], TRAFFIC["engine"]["block_size"],
            TRAFFIC["min_streamed_before_window"]) == (
                32, 8192, 7900, 32, 16384, 32, 8)


@pytest.mark.slow
def test_the_cell_runs_end_to_end_at_debug_widths():
    """``--tiny-cpu``: deploy, both checks, 32 clients of 8,192-token
    prompts through chunked prefill, the closed window, the line."""
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
         "2147483999", "--seconds", "14", "--trace", "1", "--tiny-cpu"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] == 32
    assert line["checks"]["logits_rel_rms"] < 1e-4
    after = line["counts"]["engine_after"]
    assert after["moe_assignments"] == after["moe_assignments_expected"] > 0
    assert line["counts"]["requests_ended_in_window"] == 0
