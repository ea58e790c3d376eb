"""Generation by diffusion over blocks through ``ContinuousBatchingEngine``,
end to end against the reference's ``generate`` (``benchmark/reference/
sdar.py``: the published procedure as a Python loop with no cache): the
same tokens in the same order, each placed by the same pass of its block,
whatever the prompt leaves of its last block, wherever ``max_tokens`` or
a stop token cuts, with slots at different phases of their blocks, a step
run ahead or not, a preemption in mid-block or with a finished block's
rows still owed, a prefix hit. A finished block's commit rides the next
block's first denoising pass: four passes a block of four at the quota's
floor, none that places no token. Float32 compute on seeded weights at
debug widths."""

import functools

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import sdar as reference
from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
from ray_tpu.llm.serving import LLMConfig, LLMServer
from tests import serving_family as serving
from tests.serving_family import moved, prompt_of
from tests.serving_family import generate as run

N = 4
FAMILY = serving.SDAR
make = functools.partial(serving.make, FAMILY)
engine_of = functools.partial(serving.engine_of, FAMILY)


_WANTS = {}


def want_of(cfg, params, prompt, n_tokens, **kw):
    """The reference's generation, a Python loop with no cache (seconds a
    call): what several tests ask of one (model, prompt, length) is
    computed once (every test's ``params`` are ``make``'s at seed 1)."""
    key = (cfg, tuple(prompt), n_tokens, tuple(sorted(kw.items())))
    if key not in _WANTS:
        with jax.default_matmul_precision("highest"):
            _WANTS[key] = reference.generate(
                serving.sdar_ref_params(params), prompt, n_tokens,
                block_length=N, denoising_steps=cfg.denoising_steps,
                mask_id=cfg.mask_token_id, remasking=cfg.remasking,
                confidence_threshold=cfg.confidence_threshold,
                **serving.sdar_ref_kw(cfg), **kw)
    return _WANTS[key]


CASES = {
    # P mod 4 = 0, 1, 3 (the prompt's last partial block decodes with the
    # first generated one), max_tokens no multiple of 4
    "ragged_prompts": ((8, 9, 11), 10, {}),
    # more requests than slots: slots stand at different phases of their
    # blocks, and a freed slot's next tenant starts on a block of its own
    "slots_at_different_phases": ((8, 13, 6, 21, 10, 3), 7,
                                  {"max_slots": 2}),
    "chunked_prefill": ((40, 9), 6, {}),
    # the published default is the dynamic rule; the static one beside it
    "static_rule": ((8, 10), 9, {"remasking": "low_confidence_static"}),
    "two_passes_a_block": ((8, 10), 9, {"denoising_steps": 2}),
    # one slot, three of four and every slot taken (a pass then runs
    # ahead), prompts of different lengths: in one call some slots commit
    # the block behind, some do not, and they stand at different passes
    "one_slot": ((9,), 14, {"max_slots": 1}),
    "three_of_four_slots": ((8, 9, 14), 13, {}),
    "every_slot_taken": ((8, 9, 11, 14), 13, {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_generates_what_the_reference_generates(case):
    """An engine a (model, ``max_slots``), which the cases of the same
    share: every counter is read before the case and after it."""
    lens, n_out, kw = CASES[case]
    slots = {k: kw[k] for k in kw if k == "max_slots"}
    model_kw = {k: kw[k] for k in kw if k != "max_slots"}
    cfg, model, params = make(**model_kw)
    eng = serving.shared_engine(FAMILY, model_kw, **slots)
    before = dict(eng.stats)
    prompts = [prompt_of(cfg, n, i) for i, n in enumerate(lens)]
    reqs = run(eng, prompts, SamplingParams(max_tokens=n_out))
    for prompt, req in zip(prompts, reqs):
        tokens, passes = want_of(cfg, params, prompt, n_out)
        assert req.output == tokens and req.unmasked_at == passes
        assert req.finish_reason == "length"
        # in order, none retracted: the stream is the output
        streamed = []
        while True:
            tok, _ = req.stream.get_nowait()
            if tok is None:
                break
            streamed.append(tok)
        assert streamed == tokens
    assert eng.stats["block_length"] == N
    (generated, assigned, expected, fused, alone, committed, steps,
     slot_passes, unmasked) = moved(
        eng, before, "tokens_generated", "moe_assignments",
        "moe_assignments_expected", "block_commits_fused",
        "block_commit_passes", "blocks_committed", "decode_steps",
        "block_slot_passes", "block_tokens_unmasked")
    assert generated == n_out * len(prompts)
    assert assigned == expected > 0
    # every block but a request's last was committed: by the pass that
    # began the next or, where more slots owed one at once than a call
    # has room for, by a pass of its own
    assert fused > 0
    assert fused + alone == committed - len(prompts)
    assert steps <= slot_passes
    # every placed token was counted by the pass that placed it (a block
    # that ``max_tokens`` cut was placed whole)
    assert unmasked >= generated
    assert eng.pool.num_free == eng.num_blocks


@pytest.mark.parametrize("requests,blocks,alone", [
    (1, 2, 0), (1, 5, 0), (2, 3, 0), (3, 3, 1), (4, 3, 2)])
def test_a_block_of_four_costs_four_passes_at_the_quotas_floor(requests,
                                                                blocks,
                                                                alone):
    """On seeded weights no confidence passes 0.9: one token a denoising
    pass, and no pass beside them: the commit of a block rides the next
    block's first pass, and a request's last block is owed none. Four
    slots have room for TWO blocks behind a call: of three or four
    requests in step, those beyond two commit their first block alone,
    once, and stand a pass apart from then on (every slot taken: a pass
    runs ahead all the while)."""
    cfg, model, params = make()
    eng = serving.shared_engine(FAMILY)
    assert eng._behind_slots == 2
    before = dict(eng.stats)
    prompts = [prompt_of(cfg, 8, i) for i in range(requests)]
    reqs = run(eng, prompts, SamplingParams(max_tokens=N * blocks))
    for prompt, req in zip(prompts, reqs):
        assert (req.output, req.unmasked_at) == want_of(cfg, params, prompt,
                                                        N * blocks)
    assert moved(
        eng, before, "block_tokens_unmasked_by_confidence",
        "block_slot_passes", "decode_steps", "block_commit_passes",
        "block_commits_fused", "blocks_committed", "block_tokens_unmasked"
    ) == (0, N * blocks * requests + alone, N * blocks + (alone > 0), alone,
          (blocks - 1) * requests - alone, blocks * requests,
          N * blocks * requests)
    # the blocks behind ran through the experts too, and were counted
    assigned, expected = moved(eng, before, "moe_assignments",
                               "moe_assignments_expected")
    assert assigned == expected == (
        (N * blocks + blocks - 1) * requests * N * cfg.expert_top_k
        * cfg.n_layers)


def test_a_sure_model_fills_a_block_in_one_pass():
    """With the threshold under every confidence a block is done in one
    denoising pass, and the counter that tells the threshold's work from
    the quota's says so."""
    cfg, model, params = make(confidence_threshold=0.0)
    eng = engine_of(model, params)
    prompt = prompt_of(cfg, 8, 0)
    req, = run(eng, [prompt], SamplingParams(max_tokens=8))
    assert (req.output, req.unmasked_at) == want_of(cfg, params, prompt, 8)
    assert set(req.unmasked_at) == {1}
    stats = eng.stats
    assert stats["block_slot_passes"] == 2          # a pass a block
    assert stats["block_commits_fused"] == 1
    assert stats["block_tokens_unmasked_by_confidence"] == 6


def test_a_stop_token_inside_a_block_cuts_the_block_there():
    cfg, model, params = make()
    prompt = prompt_of(cfg, 9, 3)
    tokens, _ = want_of(cfg, params, prompt, 12)
    stop = tokens[5]                    # the sixth token: inside a block
    first = tokens.index(stop)
    eng = serving.shared_engine(FAMILY)
    req, = run(eng, [prompt], SamplingParams(max_tokens=12,
                                             stop_token_ids=(stop,)))
    assert req.finish_reason == "stop"
    assert req.output == tokens[:first + 1]
    assert want_of(cfg, params, prompt, 12,
                   stop_token_ids=(stop,))[0] == req.output


@pytest.mark.parametrize("full", [True, False])
def test_a_pass_runs_ahead_only_where_no_request_can_end_within_it(full):
    """Every slot taken, no stop token, room for two more blocks: the
    pass after the one in flight is dispatched before that one is read,
    and the tokens are the reference's all the same. With a slot free
    (or a request within a block of its end) it is not."""
    cfg, model, params = make()
    prompts = [prompt_of(cfg, n, i) for i, n in enumerate((8, 11))]
    eng = engine_of(model, params, max_slots=2 if full else 3)
    reqs = [eng.submit(p, SamplingParams(max_tokens=12)) for p in prompts]
    flying = []
    with jax.default_matmul_precision("highest"):
        while eng.has_work():
            eng.step()
            flying.append(eng._in_flight is not None)
            if eng._in_flight is not None:
                # the pass just read, with this one queued behind it,
                # ended no request
                assert all(len(r.output) < 12 for r in reqs)
    assert any(flying) == full
    assert eng._in_flight is None
    # a block engine keeps the rule whole (PR 60 made the step ahead
    # speculative a row for one-token-a-step engines alone): with a slot
    # free no pass is dispatched ahead, and no pass's row is ever dropped
    assert eng.stats["decode_steps_ahead"] == sum(flying)
    assert eng.stats["decode_rows_dropped"] == 0
    for prompt, req in zip(prompts, reqs):
        assert (req.output, req.unmasked_at) == want_of(cfg, params,
                                                        prompt, 12)


def test_a_preemption_in_mid_block_redoes_the_block():
    """A pool too small for three long generations: the youngest slot is
    preempted with a block in flight; its committed tokens fold into its
    context, the block is dropped and redone, and every request still
    reads the reference's tokens."""
    cfg, model, params = make()
    prompts = [prompt_of(cfg, n, i) for i, n in enumerate((20, 21, 22))]
    eng = engine_of(model, params, num_blocks=10)
    reqs = run(eng, prompts, SamplingParams(max_tokens=12))
    assert eng.stats["preemptions"] > 0
    assert any(r.preemptions for r in reqs)
    for prompt, req in zip(prompts, reqs):
        assert (req.output, req.unmasked_at) == want_of(cfg, params,
                                                        prompt, 12)
    assert eng.pool.num_free == eng.num_blocks


@pytest.mark.parametrize("n_out", [6, 14])
def test_a_prefix_hit_of_whole_pages_generates_what_a_cold_prefill_does(
        n_out):
    """A page of 8 rows holds two whole blocks, so its K/V depend on
    nothing behind it: the second request takes the first's two pages
    from the index and reads the reference's tokens all the same, over
    two blocks and over four (commits ride across a page's edge behind
    the shared pages). Only a prefill's pages are ever offered to the
    index: a page the passes filled is hashed by nobody, so a last
    block's owed rows are owed to nobody."""
    cfg, model, params = make()
    head = prompt_of(cfg, 16, 50)
    prompts = [head + prompt_of(cfg, n, i) for i, n in enumerate((3, 7))]
    eng = engine_of(model, params)
    reqs = []
    for p in prompts:
        reqs += run(eng, [p], SamplingParams(max_tokens=n_out))
        assert len(eng.pool._by_hash) == 2          # the head's two pages
    assert eng.stats["prefix_prefills"] == 1
    assert eng.stats["prefix_tokens_reused"] == 16
    for prompt, req in zip(prompts, reqs):
        assert (req.output, req.unmasked_at) == want_of(cfg, params,
                                                        prompt, n_out)


def test_a_preemption_with_a_finished_blocks_rows_owed_recomputes_them():
    """A slot is preempted between its block's last denoising pass and
    the pass that would have committed it: the block's tokens were
    handed out and fold into the context, whose prefill computes their
    rows; nothing relied on the rows that were owed."""
    cfg, model, params = make()
    prompts = [prompt_of(cfg, n, i) for i, n in enumerate((9, 12))]
    eng = engine_of(model, params, max_slots=3)     # a slot free: no pass
    reqs = [eng.submit(p, SamplingParams(max_tokens=12)) for p in prompts]
    hit = False
    with jax.default_matmul_precision("highest"):
        while eng.has_work():
            eng.step()
            owed = [i for i, r in enumerate(eng.slots) if r is not None
                    and eng._last_tokens[i, -1] and len(r.output) == 7]
            if owed and not hit:
                assert eng._in_flight is None
                hit = True
                eng._preempt(owed[0])
    assert hit and reqs[0].preemptions == 1
    for prompt, req in zip(prompts, reqs):
        assert (req.output, req.unmasked_at) == want_of(cfg, params,
                                                        prompt, 12)
    assert eng.pool.num_free == eng.num_blocks


@pytest.mark.parametrize("slots", [1, 2])
def test_a_request_that_reaches_max_seq_ends_on_its_last_whole_block(slots):
    """``max_seq`` 32 behind a prompt of 20: blocks at 20 and 24, and the
    block at 28 would touch the last row: the request ends with the
    second block's tokens (every slot taken or not: no pass runs ahead
    into rows the table does not hold)."""
    cfg, model, params = make()
    prompts = [prompt_of(cfg, 20, i) for i in range(slots)]
    eng = engine_of(model, params, max_slots=slots, max_seq=32)
    reqs = run(eng, prompts, SamplingParams(max_tokens=30))
    for prompt, req in zip(prompts, reqs):
        assert req.finish_reason == "length" and len(req.output) == 8
        assert (req.output, req.unmasked_at) == want_of(cfg, params,
                                                        prompt, 8)
    stats = eng.stats           # (two slots in step: room for one behind)
    assert stats["block_commits_fused"] + stats["block_commit_passes"] \
        == slots
    assert eng.pool.num_free == eng.num_blocks


def test_a_page_that_would_split_a_block_is_refused_by_the_engine():
    cfg, model, params = make()
    with pytest.raises(ValueError, match="block_length"):
        ContinuousBatchingEngine(model, params, max_slots=2, max_seq=36,
                                 prefill_buckets=(6, 12), block_size=6)
    # the default page of 32 rows holds eight whole blocks
    assert 32 % cfg.block_length == 0


def test_live_blocks_are_counted_up_to_the_blocks_end():
    cfg, model, params = make()
    eng = serving.shared_engine(FAMILY)
    before = dict(eng.stats)
    run(eng, [prompt_of(cfg, 8, 0)], SamplingParams(max_tokens=4))
    # four passes over rows 8..11 behind 8 cached ones: ceil(12 / 8) pages
    assert moved(eng, before, "decode_steps", "decode_kv_blocks_live",
                 "decode_kv_blocks_table") == (4, 4 * 2,
                                               4 * eng.blocks_per_slot)


def test_the_handoff_of_a_prefill_is_refused():
    cfg = make()[0]
    with pytest.raises(NotImplementedError, match="samples none"):
        serving.shared_engine(FAMILY).prefill_only(prompt_of(cfg, 8, 0))


def test_llm_server_streams_each_token_with_the_pass_that_placed_it():
    """Through ``LLMServer``: the generation kind is the model's
    configuration's, read by the engine; a streamed chunk carries the
    ``pass`` beside ``index``, a one-token model's does not."""
    cfg = make()[0]
    server = LLMServer(LLMConfig(model_config=cfg, max_slots=2, max_seq=64,
                                 block_size=8))
    try:
        assert server.engine.block_length == N
        prompt = prompt_of(cfg, 9, 1)
        chunks = list(server.stream({"prompt": prompt, "max_tokens": 7,
                                     "stream": True}))
        chunks = [c for run_ in chunks
                  for c in (run_ if isinstance(run_, list) else [run_])]
        toks = [c for c in chunks if "token_id" in c]
        assert [c["index"] for c in toks] == list(range(7))
        assert all(1 <= c["pass"] <= cfg.denoising_steps for c in toks)
        assert chunks[-1]["done"] and chunks[-1]["finish_reason"] == "length"
        out = server({"prompt": prompt, "max_tokens": 7})
        assert out["token_ids"] == [c["token_id"] for c in toks]
        assert server.stats()["blocks_committed"] >= 4
    finally:
        server._stop.set()
        server._thread.join(10)


def test_a_one_token_model_has_no_pass_and_zero_block_counters():
    from ray_tpu.models import MoEConfig, model_for
    cfg = MoEConfig.debug_olmoe(dtype=jnp.float32)
    model = model_for(cfg)
    eng = ContinuousBatchingEngine(
        model, model.init(jax.random.key(0)), max_slots=2, max_seq=64,
        prefill_buckets=(8, 16), block_size=8)
    req, = eng.generate([[1, 2, 3]], SamplingParams(max_tokens=3))
    assert req.unmasked_at is None and len(req.output) == 3
    stats = eng.stats
    assert stats["block_length"] == 1
    assert all(stats[k] == 0 for k in (
        "block_slot_passes", "block_commit_passes", "block_tokens_unmasked",
        "block_tokens_unmasked_by_confidence", "block_commits_fused",
        "blocks_committed"))
