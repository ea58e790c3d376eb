"""``sdar-30b-a3b-chat-d6.block_decode``: the costs' arithmetic, the
window rule in tokens on a scripted engine, the three readers on a
recorded record, the replay of a stream's masked states, and the
``--tiny-cpu`` rehearsal of the cell end to end (counts only)."""

import json

import numpy as np
import pytest

from benchmark import run as harness
from benchmark.costs import blockdiff_moe_transformer as costs
from benchmark.drivers import serve_closed_blocks as driver
from benchmark.tests.test_rehearsal import start
from benchmark.tests.test_window_close import STEPS_OPEN, FakeEngine, T_OPEN

CELL = "sdar-30b-a3b-chat-d6.block_decode"
CFG = harness.load_json(harness.ROOT,
                        "benchmark/configs/sdar-30b-a3b-chat-d6.json")
TRAFFIC = harness.load_json(harness.HERE, "traffic", "block_decode.json")


# -- the costs ---------------------------------------------------------------
def test_parameter_counts_at_the_cut_and_at_the_published_depth():
    assert costs.expert_params(CFG) * 128 == 603_979_776
    assert costs.attention_params(CFG) == 18_874_368
    assert costs.layer_params(CFG) == 623_120_640
    assert costs.total_params(CFG) == CFG["parameters"] == 4_361_055_744
    whole = costs.total_params(CFG, CFG["published"]["num_hidden_layers"])
    assert whole == CFG["parameters_whole_model"]
    assert 30.4e9 < whole < 30.6e9


def test_a_pass_reads_every_experts_weights_and_each_live_row_once():
    rows = 32 * 3000.0
    bytes_ = costs.decode_step_bytes(CFG, rows)
    hit = costs.expected_distinct_experts(128, 8, 32 * 4)
    assert 127.9 < hit < 128
    weights = 6 * (18_874_368 + 262_144 + hit * 4_718_592) + 2048 * 151_936
    kv = (rows + 32 * 4) * 6 * 2048
    assert bytes_ == pytest.approx(2 * weights + kv)
    assert 8.0e9 < 2 * weights < 8.2e9
    assert costs.attention_bytes(CFG, rows) == rows * 12_288
    # memory-bound by what the algorithm needs: ~0.2 ms of operations
    # beside ~1.4 ms of bytes at these rows
    flops_s = costs.attention_flops(CFG, rows) / 197e12
    assert flops_s < costs.attention_bytes(CFG, rows) / 819e9


def test_the_cells_sizes_hold_together():
    eng = TRAFFIC["engine"]
    assert TRAFFIC["prompt_len"]["value"] + TRAFFIC["max_tokens"] \
        <= eng["max_seq"]
    assert eng["block_size"] % CFG["generation"]["block_length"] == 0
    assert TRAFFIC["clients"] == eng["max_slots"] == CFG["decode_slots"]
    assert TRAFFIC["min_streamed_before_window"] \
        == 2 * CFG["generation"]["block_length"]
    cc = TRAFFIC["correctness"]
    assert cc["prompt_len"] % eng["block_size"] == 0
    for plen, n in zip(cc["greedy"]["prompt_lens"], cc["greedy"]["tokens"]):
        assert (plen + n) % CFG["generation"]["block_length"] == 0
    assert all(p < CFG["generation"]["mask_token_id"] for p in (1, 151_668))


# -- the window rule, in tokens ------------------------------------------
@pytest.mark.parametrize("passes_per_s, yield_a_pass", [
    (80.0, 0.8), (150.0, 0.8), (80.0, 2.0), (300.0, 2.0)])
def test_no_request_ends_inside_the_window_at_any_yield(passes_per_s,
                                                        yield_a_pass):
    """The engine's tokens a slot grow at passes x yield; the window
    closes at ``run_seconds`` or a margin of TOKENS before the first
    request's last, whichever comes first."""
    slots, block, cap = 32, 4, 5950
    eng = FakeEngine(passes_per_s * yield_a_pass)

    def tokens_a_slot():
        made = (eng.steps() - STEPS_OPEN) * slots
        return driver.tokens_a_slot({"tokens_generated": made}, slots, block)

    close = driver.watch_window(
        tokens_a_slot, t_open=T_OPEN, seconds=48,
        steps_open=driver.tokens_a_slot({"tokens_generated": 0}, slots,
                                        block),
        steps_cap=cap, clock=eng.clock, sleep=eng.sleep)
    made_a_slot = eng.done
    assert made_a_slot + block < cap        # no request has ended
    early = passes_per_s * yield_a_pass * 48 > cap - driver.MIN_MARGIN_STEPS
    assert close.closed_early == early
    if not early:
        assert close.t_closed - T_OPEN == pytest.approx(48, abs=0.1)


def test_tokens_a_slot_rounds_up_and_keeps_a_block_of_slack():
    assert driver.tokens_a_slot({"tokens_generated": 0}, 32, 4) == 4
    assert driver.tokens_a_slot({"tokens_generated": 33}, 32, 4) == 6


# -- the stream's replay --------------------------------------------------
def test_replay_rebuilds_the_states_each_token_was_chosen_in():
    prompt = list(range(1, 11))             # 10 tokens: 2 given of block 2
    toks, passes = [50, 51, 60, 61, 62, 63], [2, 1, 1, 3, 2, 4]
    clean, start, states, chosen = driver.replay_states(
        prompt, toks, passes, 4, 4, 99)
    assert start == 8 and clean.tolist() == prompt + toks
    assert states[0].tolist() == [9, 10, 99, 99, 99, 99, 99, 99]
    assert states[1].tolist() == [9, 10, 99, 51, 60, 99, 99, 99]
    assert states[2].tolist() == [9, 10, 50, 51, 60, 99, 62, 99]
    assert states[3].tolist() == [9, 10, 50, 51, 60, 61, 62, 99]
    assert chosen == [(1, 2, 50), (0, 3, 51), (0, 4, 60), (2, 5, 61),
                      (1, 6, 62), (3, 7, 63)]
    with pytest.raises(ValueError, match="inside a block"):
        driver.replay_states(prompt, toks[:-1], passes[:-1], 4, 4, 99)


# -- the readers ----------------------------------------------------------
def _stats(passes, commits, placed, live_blocks, steps):
    return {"block_slot_passes": passes, "block_commit_passes": commits,
            "block_tokens_unmasked": placed, "decode_steps": steps,
            "decode_kv_blocks_live": live_blocks}


RECORD = {
    "engine_before": _stats(1000, 200, 800, 0, 100),
    "engine_after": _stats(1000 + 3200, 200 + 640, 800 + 2560, 0, 200),
    "engine_trace_edges": [_stats(0, 0, 0, 10_000, 100),
                           _stats(0, 0, 0, 10_000 + 50 * 3000, 150)],
    "trace": {"device_ops": [["paged_decode_attention_pallas.1", 0.09],
                             ["gmm", 0.3]],
              "programs": {"jit__decode_step_paged_blocks": {
                  "calls": 50, "seconds": 0.6}}},
    "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    "costs": costs, "config": CFG, "traffic": TRAFFIC,
}


def test_the_three_readers_on_a_recorded_record():
    read = lambda name: harness.load_metric(name).read
    assert read("blockdiff.commit_pass_share.decode")(RECORD) \
        == pytest.approx(20.0)
    assert read("blockdiff.unmasked_per_denoise_pass.decode")(RECORD) \
        == pytest.approx(1.0)
    # 3000 pages of 32 rows a pass: 96,000 rows x 12,288 B over 819 GB/s
    # = 1.44 ms, over the kernel's 90 ms / 50 passes = 1.8 ms
    assert read("blockdiff.attention_roofline.decode")(RECORD) \
        == pytest.approx(100 * (96_000 * 12_288 / 819e9) / 1.8e-3)
    assert harness.load_metric("decode_program_ms.decode").read(RECORD) \
        == pytest.approx(12.0)


@pytest.mark.parametrize("name", [
    "blockdiff.commit_pass_share.decode",
    "blockdiff.unmasked_per_denoise_pass.decode",
    "blockdiff.attention_roofline.decode"])
def test_a_program_without_the_counters_reads_nothing(name):
    """The parent's engine has no ``block_*`` counters: the readers
    return nothing and do not raise."""
    old = {"decode_steps": 5, "decode_kv_blocks_live": 7}
    rec = dict(RECORD, engine_before=old, engine_after=old,
               engine_trace_edges=[old, old])
    assert harness.load_metric(name).read(rec) is None
    assert harness.load_metric(name).read({}) is None


# -- the rehearsal --------------------------------------------------------
def test_tiny_cpu_rehearsal_counts_blocks_and_no_device_number():
    p = start(["--workload", CELL, "--seed", "3000000123", "--seconds", "3",
               "--trace", "0", "--tiny-cpu"])
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 32 and line["metrics"] == {}
    c = line["counts"]
    assert c["requests_ended_in_window"] == 0 and c["compiles_in_window"] == 0
    a, b = c["engine_before"], c["engine_after"]
    assert "moe_expert_load" not in a
    assert b["prefills"] == a["prefills"]
    passes = b["block_slot_passes"] - a["block_slot_passes"]
    commits = b["block_commit_passes"] - a["block_commit_passes"]
    placed = b["block_tokens_unmasked"] - a["block_tokens_unmasked"]
    assert passes > 0 and placed == passes - commits
    assert abs(commits / passes - 0.2) < 0.05
    assert b["moe_assignments"] - a["moe_assignments"] \
        == b["moe_assignments_expected"] - a["moe_assignments_expected"]
    assert line["checks"]["greedy_tokens"] == 34
    assert line["checks"]["positions"] == 3 * 2 * 4 * 64
