"""The yardstick's arithmetic: schedules, percentiles, whole-interval
rates, the costs from shapes and the peaks table."""

import json
import os

import pytest

from benchmark.lib import peaks, schedule
from benchmark.lib.records import (RequestRecord, percentile,
                                   whole_interval_rate)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def traffic(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def test_percentile_is_nearest_rank():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(vals, 50) == 3.0
    assert percentile(vals, 90) == 5.0
    assert percentile(vals, 20) == 1.0
    assert percentile([], 50) is None


def test_rate_over_whole_steps_ignores_the_window_edges():
    # a step log at exactly 10 steps/s, phase-shifted against the window:
    # count / nominal seconds would read 9.9 or 10.1, whole intervals 10.0
    for phase in (0.0, 0.031, 0.099):
        stamps = [phase + 0.1 * i for i in range(-5, 600)]
        rate, n, span = whole_interval_rate(stamps, 0.0, 48.0)
        assert rate == pytest.approx(10.0, rel=1e-9)
        assert n in (479, 480) and span == pytest.approx(0.1 * n)
    assert whole_interval_rate([1.0], 0.0, 2.0)[0] is None


def test_rate_counts_a_stall_inside_the_window():
    stamps = [0.1 * i for i in range(100)] + [20.0 + 0.1 * i for i in range(100)]
    rate, n, _ = whole_interval_rate(stamps, 0.0, 40.0)
    assert rate == pytest.approx(199 / 29.9)


def test_request_record_times_from_the_due_time():
    r = RequestRecord(index=0, due_at=10.0, sent_at=10.004,
                      first_token_at=10.2, finished_at=11.2, output_tokens=11)
    assert r.ttft_s == pytest.approx(0.2)
    assert r.lateness_s == pytest.approx(0.004)
    assert r.tpot_s == pytest.approx(0.1)


@pytest.mark.parametrize("seconds", [12.0, 48.0])
def test_every_seed_offers_the_same_work(seconds):
    tr = traffic("chat_mixed")
    runs = [schedule.cyclic_schedule(tr, "chat_mixed", seed, seconds, 10.0)
            for seed in (0, 1, 3_000_000_123)]
    n = round(tr["arrival"]["rate_per_s"] * seconds)

    def window(run):
        return [r for r in run if r["in_window"]]

    for run in runs:
        w = window(run)
        assert len(w) == n
        assert all(0 < r["due"] < n / tr["arrival"]["rate_per_s"] for r in w)
        assert all(-10.0 <= r["due"] < 0 for r in run if not r["in_window"])
        assert [r["due"] for r in run] == sorted(r["due"] for r in run)
        lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
        assert all(lo <= r["prompt_len"] <= hi for r in run)
    work = [sorted((r["prompt_len"], r["max_tokens"]) for r in window(run))
            for run in runs]
    assert work[0] == work[1] == work[2]
    assert ([r["cycle_index"] for r in window(runs[0])]
            != [r["cycle_index"] for r in window(runs[1])])
    # the same seed gives the same schedule
    assert runs[2] == schedule.cyclic_schedule(
        tr, "chat_mixed", 3_000_000_123, seconds, 10.0)


def test_gaps_sum_to_the_window_and_lengths_follow_the_distribution():
    gaps = schedule.gap_quantiles("poisson", 3.0, 144)
    assert sum(gaps) == pytest.approx(48.0)
    assert min(gaps) > 0
    lens = schedule.length_quantiles(
        {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
         "max": 1536}, 145)
    assert sorted(lens)[72] == 256
    assert min(lens) >= 32 and max(lens) <= 1536


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


@pytest.mark.parametrize("name,builder_params", [
    ("mistral-7b-v0.3-d6", 1_577_111_552), ("gpt2-medium", 354_823_168)])
def test_costs_match_the_models_own_shapes(name, builder_params):
    import importlib

    import jax

    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    assert cfg["parameters"] == builder_params
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    costs = importlib.import_module("benchmark.costs." + cfg["costs"])
    model = builder.build_model(cfg, 1024)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(x.size for x in leaves) == builder_params
    # the stacked layers' weight matrices ([L, in, out...]); biases and
    # norms are not matmuls
    matmul = sum(x.size for p, x in jax.tree_util.tree_leaves_with_path(
        shapes["layers"]) if x.ndim >= 3 and str(p[-1].key)[0] == "w")
    d = costs.dims(cfg)
    assert costs.matmul_params(cfg) == matmul + d["d"] * d["vocab"]
