"""The EvaByte configuration's pieces: the cost model's arithmetic against
ISSUE 35's numbers, the configuration against the catalog, the builder's
mapping of the published keys, the reference against the equations written
out position by position, the program against the reference through the
cell's own logits check, and the new metrics' readers. (The cell's
``--tiny-cpu`` run end to end is ``test_rehearsal.py``'s: it runs every
cell of ``BENCHMARK.json``.)"""

import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.builders import evabyte as builder
from benchmark.costs import eva_transformer as costs
from benchmark.lib import serving
from benchmark.reference import evabyte as reference

CFG = harness.load_json(harness.ROOT, "benchmark/configs/evabyte-6.5b-d8.json")
TINY = {**CFG, **CFG["tiny_cpu"]}
TRAFFIC = harness.load_json(harness.HERE, "traffic", "long_decode_eva.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "evabyte-6.5b-d8.long_decode_eva"


def test_parameter_counts_at_the_cut_and_the_published_depth():
    assert costs.attention_params(CFG) == 67_108_864
    assert 3 * 4096 * 11_008 == 135_266_304
    assert costs.layer_params(CFG) == 202_391_552
    assert costs.total_params(CFG) == CFG["parameters"] == 1_630_932_992
    assert builder.program_config(CFG, 64).num_params() == CFG["parameters"]
    whole = dict(CFG, num_hidden_layers=32)
    assert costs.total_params(whole) == pytest.approx(6.49e9, rel=0.001)
    assert builder.program_config(whole, 64).num_params() \
        == costs.total_params(whole)
    # 2 bytes a parameter: 3.26 GB at depth 8
    assert 2 * costs.total_params(CFG) == pytest.approx(3.26e9, rel=0.001)
    # training at 16 bytes a parameter: the floor of 4 layers is 12.95 GB
    assert 4 * costs.layer_params(CFG) * 16 == pytest.approx(12.95e9,
                                                            rel=0.001)


def test_rows_a_step_and_the_steps_bytes_by_hand():
    assert costs.kv_bytes_per_row_layer(CFG) == 16_384            # 16 KiB
    assert costs.rows_read(CFG, 16_384) == {
        "exact": 1, "summary": 1024, "full_equivalent": 16_385}
    assert costs.rows_read(CFG, 20_479) == {
        "exact": 2048, "summary": 1152, "full_equivalent": 20_480}
    # the window: positions 16.4k-20.5k a slot, ~1,040 exact and ~1,150
    # summary rows a slot-layer on average = 4.6 GB of K/V a step
    rows = 16 * (1040 + 1150)
    assert costs.attention_bytes(CFG, rows) == rows * 16_384 * 8
    assert costs.attention_bytes(CFG, rows) == pytest.approx(4.6e9, rel=0.01)
    weights = 2 * (8 * (67_108_864 + 135_266_304) + 4096 * 320)
    assert weights == pytest.approx(3.24e9, rel=0.001)
    assert costs.decode_step_bytes(CFG, rows) == weights + rows * 16_384 * 8
    share = costs.attention_bytes(CFG, rows) / costs.decode_step_bytes(
        CFG, rows)
    assert share == pytest.approx(0.586, abs=0.01)     # most of the bytes
    # one row a position would read 8x the rows there
    full = 16 * 18_432
    assert full / rows == pytest.approx(8.4, rel=0.02)
    # the pools: 82 exact + 48 summary blocks of 32 rows a slot-layer
    assert (82 + 48) * 32 * 16_384 == 65 * 2**20
    assert 16 * 8 * (82 + 48) * 32 * 16_384 / 2**30 == pytest.approx(8.125)
    assert 16 * 8 * 768 * 32 * 16_384 / 2**30 == 48.0


def test_configuration_keeps_every_published_key():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert CFG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CFG["reduced"]:
            assert CFG[key] == CFG["reduced"][key]["to"] != value
            assert CFG["reduced"][key]["from"] == value
        else:
            assert CFG[key] == value, key
    assert sorted(CFG["reduced"]) == ["max_position_embeddings",
                                      "num_hidden_layers"]
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == CFG["name"])
    assert sorted(conf["reduced"]) == sorted(CFG["reduced"])
    eng = TRAFFIC["engine"]
    assert CFG["max_position_embeddings"] == eng["max_seq"] == 24_576
    assert (CFG["decode_slots"], CFG["decode_block_size"]) == (
        eng["max_slots"], eng["block_size"])


def test_builder_maps_the_published_keys():
    from ray_tpu.models.llama import EVA_KIND, LlamaModel

    cfg = builder.program_config(CFG, 24_576)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 32, 32, 128)
    assert (cfg.ffn_dim, cfg.vocab_size, cfg.n_layers) == (11_008, 320, 8)
    assert cfg.layer_types == (EVA_KIND,) * 8
    assert (cfg.eva_window, cfg.eva_chunk, cfg.num_pred_heads) == (2048, 16, 8)
    assert cfg.norm_add_unit_offset and cfg.fp32_residual
    assert cfg.rope_theta == 1e5 and cfg.norm_eps == 1e-5
    assert cfg.dtype == jnp.bfloat16 and not cfg.tie_embeddings
    tiny = builder.program_config(TINY, 64)
    assert tiny.dtype == jnp.float32 and tiny.head_dim == 16
    assert (tiny.eva_window, tiny.eva_chunk, tiny.vocab_size) == (32, 4, 320)
    with pytest.raises(ValueError, match="attention_class"):
        builder.program_config(dict(CFG, attention_class="full"), 64)
    with pytest.raises(ValueError, match="attention bias"):
        builder.program_config(dict(CFG, attention_bias=True), 64)
    model = builder.build_model(TINY, 64)
    assert type(model) is LlamaModel and model.eva == (32, 4)


def _eva_by_loops(q, k, v, phi, mu, window, chunk):
    """The issue's equations, one query at a time, in numpy float64."""
    S, H, hd = q.shape
    scale = hd ** -0.5
    out = np.zeros_like(q)
    for h in range(H):
        ks, vs = [], []
        for j in range(S // chunk):
            rows = slice(j * chunk, (j + 1) * chunk)
            a = np.exp(scale * k[rows, h] @ phi[h])
            a /= a.sum()
            ks.append(a @ k[rows, h] + mu[h])
            vs.append(a @ v[rows, h])
        for i in range(S):
            w = i // window
            keys = [k[m, h] for m in range(w * window, i + 1)]
            vals = [v[m, h] for m in range(w * window, i + 1)]
            seen = w * window // chunk       # chunks of earlier windows
            keys, vals = keys + ks[:seen], vals + vs[:seen]
            p = np.exp([scale * q[i, h] @ key for key in keys])
            out[i, h] = (p / p.sum()) @ np.array(vals)
    return out


def test_reference_attention_against_the_equations_by_loops():
    rng = np.random.default_rng(0)
    S, H, hd, window, chunk = 27, 2, 8, 8, 4      # 3 window ends, a tail
    q, k, v = rng.normal(size=(3, S, H, hd))
    phi, mu = rng.normal(size=(2, H, hd))
    want = _eva_by_loops(q, k, v, phi, mu, window, chunk)
    pad = ((0, 0), (0, -S % window), (0, 0), (0, 0))
    args = [jnp.pad(jnp.asarray(a[None], jnp.float32), pad)
            for a in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        got = reference.eva_attention(*args, jnp.asarray(phi, jnp.float32),
                                      jnp.asarray(mu, jnp.float32),
                                      window, chunk)
    np.testing.assert_allclose(np.asarray(got)[0, :S], want, atol=2e-5)
    # the window RESETS: position 8 (a window's first) sees itself and
    # the two summaries of window 0, not its 7 predecessors
    lone = _eva_by_loops(q[8:9], k[8:9], v[8:9], phi, mu, window, 1)
    assert np.abs(want[8] - lone[0]).max() > 1e-3       # the summaries count
    assert np.abs(want[7] - want[8]).max() > 1e-3


def test_reference_matches_the_program_through_the_builder_and_the_check():
    model = builder.build_model(TINY, 256)
    params = model.init(jax.random.key(1))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 320, (2, 90)),
                       jnp.int32)
    forward = builder.reference_forward(TINY)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, toks)
    assert got.shape == (2, 90, 8 * 320)
    np.testing.assert_allclose(got, forward(params, toks, every_head=True),
                               atol=2e-4)
    # the cell's logits check, its five calls on the model as they are,
    # across two window ends (32, 64) at the rehearsal's widths
    server = types.SimpleNamespace(model=model, engine=types.SimpleNamespace(
        params=params, block_size=8))
    with jax.default_matmul_precision("highest"):
        checks = serving.check_logits(
            server, forward, seed=2_147_483_999, prompt_len=56,
            decode_steps=24, tol_rel_rms=1e-4)
        wrong = serving.check_logits(
            server, builder.reference_forward({**TINY, "chunk_size": 8}),
            seed=2_147_483_999, prompt_len=56, decode_steps=24,
            tol_rel_rms=1e-4)
    assert checks["ok"] and checks["positions"] == 48
    assert not wrong["ok"] and wrong["logits_rel_rms"] > 0.01


def _record(before, after, **more):
    return {"engine_before": before, "engine_after": after, **more}


def test_new_metrics_read_the_counters_and_nothing_on_a_program_without():
    summary = harness.load_metric("kv.summary_read_share.decode")
    of_full = harness.load_metric("kv.read_share_of_full.decode")
    roof = harness.load_metric("eva.attention_roofline.decode")
    parent = _record({"decode_steps": 5, "decode_kv_blocks_live": 10},
                     {"decode_steps": 9, "decode_kv_blocks_live": 90})
    other = _record(
        {"decode_kv_blocks_live": 10, "decode_kv_blocks_live_summary": 0,
         "decode_kv_blocks_full_equivalent": 0},
        {"decode_kv_blocks_live": 90, "decode_kv_blocks_live_summary": 0,
         "decode_kv_blocks_full_equivalent": 0}, config=CFG, costs=costs)
    for rec in (parent, other, {}):
        assert summary.read(rec) is None and of_full.read(rec) is None
        assert roof.read(rec) is None
    # ten steps of 16 slots: 33 exact + 36 summary blocks a slot where
    # one row a position reads 576
    rec = _record(
        {"decode_kv_blocks_live": 7, "decode_kv_blocks_live_summary": 3,
         "decode_kv_blocks_full_equivalent": 11},
        {"decode_kv_blocks_live": 7 + 16 * 69 * 10,
         "decode_kv_blocks_live_summary": 3 + 16 * 36 * 10,
         "decode_kv_blocks_full_equivalent": 11 + 16 * 576 * 10})
    assert summary.read(rec) == pytest.approx(100 * 36 / 69)       # 52 %
    assert of_full.read(rec) == pytest.approx(100 * 69 / 576)      # 12 %
    # both rooflines from one traced span: 10 steps of 10 ms, the two
    # kernels 6 ms of each
    traced = {
        "traffic": TRAFFIC, "config": CFG, "costs": costs,
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"programs": {"jit__decode_step_paged": {
            "calls": 10, "seconds": 0.1}},
            "device_ops": [["paged_decode_attention_pallas.16", 0.04],
                           ["fusion.1", 0.02],
                           ["paged_decode_attention_pallas.17", 0.02]]},
        "engine_trace_edges": [
            {"decode_steps": 100, "decode_kv_blocks_live": 0},
            {"decode_steps": 110, "decode_kv_blocks_live": 16 * 69 * 10}]}
    rows = 16 * 69 * 32
    assert roof.read(traced) == pytest.approx(
        100 * costs.attention_bytes(CFG, rows) / 819e9 / 0.006)
    whole = harness.load_metric("decode_program_roofline")
    assert whole.live_tokens_per_step(traced) == rows
    assert whole.read(traced) == pytest.approx(
        100 * costs.decode_step_bytes(CFG, rows) / 819e9 / 0.01)
    # the kernel not among the ten longest operations: nothing to read
    traced["trace"]["device_ops"] = [["fusion.1", 0.02]]
    assert roof.read(traced) is None
    # a configuration whose costs know no EVA reads nothing
    from benchmark.costs import dense_transformer
    assert roof.read({**traced, "costs": dense_transformer}) is None


def test_the_cell_is_declared_with_its_metrics():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte-6.5b-d8", "long_decode_eva", 1)
    names = {m["name"] for m in harness.cell_metrics(bench, CELL, "per_layer")}
    assert {"kv.summary_read_share.decode", "kv.read_share_of_full.decode",
            "eva.attention_roofline.decode", "decode_program_roofline",
            "peak_hbm_gib.decode", "device_idle_share.decode"} <= names
    assert not {n for n in names if n.startswith(("moe.", "kv.window_",
                                                  "kv.pool_share"))}
    assert {m["name"] for m in harness.cell_metrics(
        bench, CELL, "end_to_end")} == {"serve_out_tokens_per_s", "setup_s"}
    assert (TRAFFIC["clients"], TRAFFIC["prompt_len"]["value"],
            TRAFFIC["max_tokens"]) == (16, 16_384, 7900)
    cc = TRAFFIC["correctness"]
    assert (cc["prompt_len"], cc["decode_steps"]) == (2016, 64)
    assert cc["greedy"]["prompt_lens"] == [4100, 16_384]
