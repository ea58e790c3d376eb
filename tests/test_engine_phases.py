"""The engine loop accounts for itself: eight sibling phases, each a
``jax.profiler.TraceAnnotation`` span and a seconds counter in
``engine.stats`` (``llm/engine.py:_Phase``), and counts taken at the
same boundaries; and for the device with no profiler: readiness probes
where the host meets the device (``_decode_step``, ``_enqueued``). CPU,
debug widths, no timing thresholds: what is checked is names, nesting,
exact counts, exact seconds on a stubbed clock and that the sums close."""

import dataclasses
import glob
import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import ContinuousBatchingEngine, SamplingParams
from ray_tpu.llm import engine as engine_module
from ray_tpu.models.llama import LlamaConfig, LlamaModel
from tests.serving_family import drive_arrivals, ended, reads_first

PHASES = {"engine.schedule": "t_schedule_s", "engine.prefill": "t_prefill_s",
          "engine.host_arrays": "t_host_arrays_s",
          "engine.decode_enqueue": "t_enqueue_s",
          "engine.sample_readback": "t_readback_s",
          "engine.emit": "t_emit_s", "engine.deliver": "t_deliver_s",
          "engine.idle": "t_idle_s"}
# what tiles ``step()``: its phases and, before the first, the lock's wait
IN_STEP = [k for name, k in PHASES.items() if name != "engine.idle"] + [
    "t_lock_wait_s"]
# the device's account (docs/serving.md, "Is my chip waiting for my host?")
DEVICE_KEYS = {"t_device_starved_s", "decode_steps_waited",
               "decode_steps_device_paced", "t_device_paced_s"}


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.debug(vocab_size=512, max_seq_len=128)
    model = LlamaModel(cfg)
    return model, model.init(jax.random.key(0))


def make_engine(tiny_model, **kw):
    model, params = tiny_model
    kw = {"max_slots": 4, "max_seq": 128, "prefill_buckets": (16, 64),
          "block_size": 8, **kw}
    return ContinuousBatchingEngine(model, params, **kw)


def distinct(n, start):
    """``n`` tokens whose first block no other prompt of this file shares."""
    return [(start + 7 * i) % 500 + 1 for i in range(n)]


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: what was opened,
    by which thread, at which depth."""

    log = []
    depth = threading.local()

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        d = getattr(Recorder.depth, "n", 0)
        Recorder.log.append((self.name, self.attrs, threading.get_ident(), d))
        Recorder.depth.n = d + 1
        return self

    def __exit__(self, *exc):
        Recorder.depth.n -= 1
        return False


def test_spans_are_the_eight_phases_siblings_on_one_thread(tiny_model,
                                                           monkeypatch):
    eng = make_engine(tiny_model)
    eng.generate([distinct(5, 0)], SamplingParams(max_tokens=2))   # compile
    Recorder.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    before = dict(eng.stats)
    # one alone, a group of three in one bucket, one longer than the
    # largest bucket (chunked), and a repeat of the first (prefix hit)
    eng.generate([distinct(9, 100)] + [distinct(40, 200 + k) for k in range(3)]
                 + [distinct(70, 300)], SamplingParams(max_tokens=6))
    eng.generate([distinct(9, 100) + [3, 4]], SamplingParams(max_tokens=3))
    stop = threading.Event()
    loop = threading.Thread(target=eng.run_forever, args=(stop, 0.001))
    loop.start()                         # nothing to do: the loop idles
    while eng.stats["t_idle_s"] == 0.0 and loop.is_alive():
        stop.wait(0.001)
    stop.set()
    loop.join(30)
    assert not loop.is_alive()

    # by thread: an engine that an earlier test file of this process left
    # idling on its own thread records too
    driven = [e for e in Recorder.log if e[2] == threading.get_ident()]
    idled = [e for e in Recorder.log if e[2] == loop.ident]
    names = [n for n, _, _, _ in driven]
    assert set(names) == set(PHASES) - {"engine.idle"}
    assert {n for n, _, _, _ in idled} == {"engine.schedule", "engine.idle"}
    assert all(depth == 0 for _, _, _, depth in driven + idled), "nested"
    steps = eng.stats["decode_steps"] - before["decode_steps"]
    groups = names.count("engine.prefill")
    assert steps > 0 and groups == 4      # 16 x1, 64 x3, chunked, prefix hit
    assert eng.stats["prefix_prefills"] - before["prefix_prefills"] == 1
    # one span around all of a step's (or a group's) tokens, never one each
    assert names.count("engine.emit") == steps + groups
    assert names.count("engine.sample_readback") == steps
    # a decode step's tokens reach their streams once, after the step:
    # at once where the next step is already on the device or the last
    # slot ended, else under the next dispatch or before an admission
    assert names.count("engine.deliver") == steps
    # (a dispatch ends with the next step's offsets, sent under the
    # program: the second ``engine.host_arrays`` span)
    assert all(names[i - 1] in ("engine.host_arrays", "engine.emit")
               and names[i - 2] in ("engine.decode_enqueue",
                                    "engine.sample_readback")
               for i, n in enumerate(names) if n == "engine.deliver")
    attrs = [a for n, a, _, _ in driven if n == "engine.prefill"]
    assert {"bucket": 64, "n": 3, "n_pad": 4} in attrs
    assert all(set(a) == {"bucket", "n", "n_pad"} for a in attrs)
    # (``engine.deliver`` names the decode step it delivers: what a
    # streamed token's ``serve.stream.produce`` span carries too,
    # tests/test_stream_phases.py)
    assert all(set(a) == {"step"} for n, a, _, _ in driven
               if n == "engine.deliver")
    # the read-back says what its readiness probe found (0 / 1) and which
    # decode step it reads: a step is read and delivered under ONE number
    reads = [a for n, a, _, _ in driven if n == "engine.sample_readback"]
    assert all(set(a) == {"waited", "step"} and a["waited"] in (0, 1)
               for a in reads)
    first = before["decode_steps"] + 1
    assert [a["step"] for a in reads] == list(range(first, first + steps))
    assert [a["step"] for n, a, _, _ in driven if n == "engine.deliver"] \
        == [a["step"] for a in reads]
    assert eng.stats["decode_steps_waited"] - before["decode_steps_waited"] \
        == sum(a["waited"] for a in reads)
    assert all(not a for n, a, _, _ in driven
               if n not in ("engine.prefill", "engine.deliver",
                            "engine.sample_readback"))


def test_streams_get_every_token_in_order_then_their_end(tiny_model):
    """Delivery after the step loses and reorders nothing: requests of
    unequal lengths that join and leave a running batch."""
    eng = make_engine(tiny_model)
    stop = threading.Event()
    loop = threading.Thread(target=eng.run_forever, args=(stop, 0.001))
    loop.start()
    try:
        reqs = [eng.submit(distinct(9 + 7 * k, 40 * k),
                           SamplingParams(max_tokens=3 + 4 * k))
                for k in range(6)]              # six requests, four slots
        streamed = [list(r.iter_tokens()) for r in reqs]
    finally:
        stop.set()
        loop.join(30)
    assert [len(t) for t in streamed] == [3 + 4 * k for k in range(6)]
    assert streamed == [r.output for r in reqs]
    assert all(r.done.is_set() and r.finish_reason == "length" for r in reqs)
    assert eng._undelivered == []


def test_a_dying_loop_delivers_what_it_generated_before_the_cause(tiny_model):
    from ray_tpu.llm.engine import EngineDeadError

    eng = make_engine(tiny_model, max_slots=1)
    # a request that waits for the slot keeps the engine from running a
    # step ahead, so a step's tokens wait for the next ``step()``
    req = eng.submit(distinct(9, 0), SamplingParams(max_tokens=50))
    queued = eng.submit(distinct(9, 40), SamplingParams(max_tokens=2))
    for _ in range(3):
        eng.step()                      # first token + three decode steps
    assert len(req.output) == 4 and req.stream.qsize() == 3   # one deferred
    assert eng.stats["decode_steps_ahead"] == 0

    def boom(*a, **k):
        raise RuntimeError("device lost")
    eng._decode = boom
    with pytest.raises(RuntimeError):
        eng.run_forever(threading.Event())
    got = []
    with pytest.raises(EngineDeadError):
        for tok in req.iter_tokens():
            got.append(tok)
    assert got == req.output and len(got) == 4
    with pytest.raises(EngineDeadError):
        list(queued.iter_tokens())


def test_decode_inputs_stay_on_the_device_until_the_hosts_copy_changes(
        tiny_model):
    """Tokens, tables and sampling parameters are sent when a slot is
    activated, freed or grows a block, and otherwise reused: the last
    step's output IS the next step's tokens. One request on four slots
    runs a step ahead all the way (``_may_run_ahead``)."""
    eng = make_engine(tiny_model)                       # block_size 8
    first = eng.submit(distinct(9, 0), SamplingParams(max_tokens=40))
    eng.step()
    sent = {"tables": 1, "sampling": 1}
    seen = [eng._dev_tables, eng._dev_sampling]
    # step 1 is read, step 2 stands on the device, and the offsets went
    # a step ahead of IT, under its program
    assert eng._in_flight is not None
    assert eng._dev_offsets.tolist() == (eng.offsets + [1, 0, 0, 0]).tolist()
    grown = 0
    for k in range(30):
        held = len(eng.allocs[0].blocks)
        if k == 8:          # a second slot: everything is sent again
            eng.submit(distinct(5, 50), SamplingParams(max_tokens=3))
        toks = eng._dev_tokens
        eng.step()
        grown += len(eng.allocs[0].blocks) != held
        for at, (name, dev) in enumerate((("tables", eng._dev_tables),
                                          ("sampling", eng._dev_sampling))):
            if dev is not None and dev is not seen[at]:
                sent[name] += 1
                seen[at] = dev
        # k 8: the prefill, with the step ahead still unread, and that
        # step's read: nothing dispatched behind it. k 10: the second
        # request's third token ends it, with a step ahead in flight;
        # k 11: that step is read, its row for the ended request dropped
        assert (eng._dev_offsets is None) == (k in (8, 10, 11))
        assert (eng._in_flight is None) == (k in (8, 11))
        # the step read the step before's own output unless a slot came in
        assert (toks is None) == (k == 9)
        assert k == 8 or list(eng._dev_tokens.shape) == [eng.max_slots]
    # 10 -> 40 tokens cached and room for the two steps on their way, in
    # blocks of 8: two blocks became six
    assert grown == 4 and len(eng.allocs[0].blocks) == 6
    # first send, four grown blocks, one activation, one freed slot;
    # the sampling parameters: first send, the activation, the freed slot
    assert sent == {"tables": 1 + grown + 2, "sampling": 3}
    assert eng.stats["decode_rows_dropped"] == 1
    assert eng.stats["decode_steps"] == 31
    assert eng.stats["decode_steps_ahead"] == 31 - 2    # k 9 and k 12
    assert int(eng._last_tokens[0]) == first.output[-1]
    assert eng._decode._cache_size() == 1   # host-sent or device: one program


def test_a_step_runs_ahead_at_any_occupancy_unless_a_request_waits(tiny_model):
    """With no request waiting the next step is dispatched before the one
    in flight is read, for the slots as they stand: free slots, a stop
    token, a length about to be reached do not hold it back (the device
    never waits for the host, tokens reach their stream at once). A
    request that ends under a step ahead has that step's row DROPPED as
    it is read; only where EVERY request reaches its length in the step
    in flight is none dispatched. The tokens are what they were."""
    never = (9999,)
    outs = {}
    eng = make_engine(tiny_model)               # four slots, one taken
    eng.submit(distinct(9, 0), SamplingParams(max_tokens=4))
    eng.step()
    assert eng._in_flight is not None
    for stop in ((), never):
        eng = make_engine(tiny_model, max_slots=1)
        req = eng.submit(distinct(9, 0), SamplingParams(
            max_tokens=12, stop_token_ids=stop))
        flying, lag = [], []
        while eng.has_work():
            eng.step()
            flying.append(eng._in_flight is not None)
            lag.append(len(req.output) - req.stream.qsize())
        outs[stop] = req.output
        assert len(req.output) == 12 and eng._in_flight is None
        assert lag[-1] == -1                    # the stream's end marker
        assert eng.stats["decode_steps"] == 11
        # the step in flight as the 11th token comes ends the request, the
        # only one: nothing is dispatched behind it, nothing dropped
        assert flying == [True] * 10 + [False] and lag[:-1] == [0] * 10
        assert eng.stats["decode_steps_ahead"] == 10
        assert eng.stats["decode_rows_dropped"] == 0
    assert outs[()] == outs[never]
    # a stop on a token's VALUE cannot be foreseen: the step ahead stands
    # on the device as it fires, and is read and dropped
    at = next(k for k in range(3, 12) if outs[()][k] not in outs[()][:k])
    eng = make_engine(tiny_model, max_slots=1)
    req = eng.submit(distinct(9, 0), SamplingParams(
        max_tokens=12, stop_token_ids=(outs[()][at],)))
    while not req.done.is_set():
        eng.step()
    assert req.output == outs[()][:at + 1] and req.finish_reason == "stop"
    assert eng._in_flight is not None and eng.has_work()
    assert eng.step() == 1 and eng._in_flight is None and not eng.has_work()
    assert eng.stats["decode_rows_dropped"] == 1
    assert eng.stats["decode_steps"] == at + 1      # the dropped one too
    assert eng.stats["tokens_generated"] == at + 1 and eng.offsets[0] == 0
    # a request that waits for a slot stops the run-ahead: it is admitted
    # with nothing in flight
    eng = make_engine(tiny_model, max_slots=1)
    first = eng.submit(distinct(9, 0), SamplingParams(max_tokens=6))
    eng.step()
    assert eng._in_flight is not None
    second = eng.submit(distinct(9, 40), SamplingParams(max_tokens=3))
    eng.step()
    assert eng._in_flight is None
    while eng.has_work():
        eng.step()
    assert len(first.output) == 6 and len(second.output) == 3
    assert eng.stats["decode_rows_dropped"] == 0


@pytest.fixture(scope="module")
def exact_model():
    """The tiny model in float32: a token is the argmax, with no bf16 tie."""
    cfg = dataclasses.replace(
        LlamaConfig.debug(vocab_size=512, max_seq_len=128), dtype=jnp.float32)
    model = LlamaModel(cfg)
    return model, model.init(jax.random.key(1))


def greedy(n, **kw):
    return SamplingParams(max_tokens=n, **kw)


def warm(n):
    return SamplingParams(max_tokens=n, temperature=0.8)


# name -> (engine kwargs, arrivals [(due, prompt, sampling)], requests that
# end under a step ahead, requests admitted under one)
STEP_AHEAD_CASES = {
    # three of four slots, lengths that end one by one: the free slot, the
    # length about to be reached, do not hold the step ahead back
    "staggered_lengths_at_partial_occupancy": (
        {}, [(None, distinct(9, 0), greedy(5)),
             (None, distinct(14, 40), greedy(9)),
             (None, distinct(20, 80), greedy(14))], 2, 0),
    # a stop on a token's VALUE (``stop_at`` fills it in: the second
    # request's fourth token) fires with the step ahead on the device
    "a_stop_token_fires_mid_batch": (
        {}, [(None, distinct(9, 0), greedy(12)),
             (None, distinct(14, 40), greedy(12, stop_token_ids="stop_at")),
             (None, distinct(20, 80), greedy(12))], 1, 0),
    # two slots, a pool of four blocks, all held: the request that arrives
    # as the first ends takes ITS slot and ITS two blocks while the step
    # dispatched for the ended one is still unread, and scatters its
    # prompt over the row that step wrote
    "an_arrival_takes_the_slot_and_blocks_an_ended_request_left": (
        {"max_slots": 2, "num_blocks": 4, "prefill_buckets": (16,)},
        [(None, distinct(9, 0), greedy(4)),
         (None, distinct(5, 40), greedy(10)),
         (ended(0), distinct(9, 80), greedy(5))], 2, 1),
    # every row draws (temperature 0.8): the key advances a step whether
    # a row is dropped or not, so the rows that stay draw what they drew
    "sampled_rows_beside_a_dropped_one": (
        {}, [(None, distinct(9, 0), warm(5)),
             (None, distinct(14, 40), warm(11)),
             (None, distinct(20, 80), warm(14))], 2, 0),
}


@pytest.mark.parametrize("case", sorted(STEP_AHEAD_CASES))
def test_a_dropped_row_changes_no_token(exact_model, case):
    """The step ahead is dispatched for the slots as they stand, and a row
    whose request ended in the step before is dropped as it is read. Every
    token is what an engine that reads each step first gives (the same
    programs, no step ahead), and a greedy one is the model's own argmax
    given the prompt and the tokens before it (``apply``, teacher forced);
    ``decode_rows_dropped`` counts the requests that ended with a step
    ahead in flight, one row each; every block comes back."""
    model, params = exact_model
    kw, arrivals, want_dropped, want_admitted = STEP_AHEAD_CASES[case]
    if any(s.stop_token_ids for _, _, s in arrivals):
        # the stop token: what its request gives fourth with no stop set,
        # and has not given before
        unstopped, _, _ = drive_arrivals(
            reads_first(make_engine(exact_model, **kw)),
            [(due, p, greedy(s.max_tokens)) for due, p, s in arrivals])
        stop_at = (unstopped[1].output[3],)
        assert stop_at[0] not in unstopped[1].output[:3]
        arrivals = [(due, p, greedy(s.max_tokens, stop_token_ids=stop_at)
                     if s.stop_token_ids else s) for due, p, s in arrivals]
    want, _, _ = drive_arrivals(
        reads_first(make_engine(exact_model, **kw)), arrivals)
    eng = make_engine(exact_model, **kw)
    held = []
    activate = eng._activate

    def recording(slot, req, alloc, now, first):
        held.append((slot, list(alloc.blocks)))
        activate(slot, req, alloc, now, first)

    eng._activate = recording
    got, ended_ahead, admitted_ahead = drive_arrivals(eng, arrivals)
    assert [r.output for r in got] == [r.output for r in want]
    assert [r.finish_reason for r in got] == [r.finish_reason for r in want]
    assert [r.finish_reason == "stop" for r in got] == [
        bool(s.stop_token_ids) for _, _, s in arrivals]
    stats = eng.stats
    assert stats["decode_rows_dropped"] == ended_ahead == want_dropped
    assert admitted_ahead == want_admitted
    assert stats["tokens_generated"] == sum(len(r.output) for r in got)
    assert stats["decode_steps_ahead"] > 0.6 * stats["decode_steps"]
    assert eng.pool.num_free == eng.num_blocks and eng._in_flight is None
    assert not eng.offsets.any() and eng._undelivered == []
    assert stats["preemptions"] == 0
    if want_admitted:       # the slot the first left, and both its blocks
        assert held[2][0] == held[0][0] and set(held[2][1]) == set(held[0][1])
    if arrivals[0][2].temperature:
        assert stats["decode_steps_sampled"] == stats["decode_steps"]
        # and the rows that stay draw what they would have drawn had the
        # ended request gone on for one token more
        longer = [(due, p, warm(s.max_tokens + (k == 0)))
                  for k, (due, p, s) in enumerate(arrivals)]
        more, _, _ = drive_arrivals(make_engine(exact_model, **kw), longer)
        assert [r.output for r in more[1:]] == [r.output for r in got[1:]]
        assert more[0].output[:-1] == got[0].output
        return
    padded = np.zeros((len(got), 128), np.int32)
    for k, ((_, prompt, _), r) in enumerate(zip(arrivals, got)):
        padded[k, :len(prompt) + len(r.output)] = prompt + r.output
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(model.apply)(params, padded))
    for (_, prompt, _), r, rows in zip(arrivals, got, logits):
        rows = rows[len(prompt) - 1:len(prompt) - 1 + len(r.output)]
        assert all(tok == row.argmax() or row.max() - row[tok] < 1e-4
                   for tok, row in zip(r.output, rows))


def test_two_long_requests_on_eight_slots_run_ahead_all_the_way(tiny_model):
    eng = make_engine(tiny_model, max_slots=8)
    reqs = eng.generate([distinct(9, 0), distinct(14, 40)],
                        SamplingParams(max_tokens=40))
    assert all(len(r.output) == 40 for r in reqs)
    stats = eng.stats
    # both reach their length in one step: nothing is dispatched behind it
    assert stats["decode_steps"] == 39 and stats["decode_steps_ahead"] == 38
    assert stats["decode_steps_ahead"] / stats["decode_steps"] > 0.8
    assert stats["decode_rows_dropped"] == 0 and eng._in_flight is None


def test_admission_and_prefill_counts_are_exact(tiny_model):
    eng = make_engine(tiny_model)
    eng.generate([distinct(40, 10 * k) for k in range(3)],
                 SamplingParams(max_tokens=2))
    assert eng.stats["admitted"] == 3
    assert eng.stats["prefill_tokens"] == 120
    assert eng.stats["prefill_padded_tokens"] == 4 * 64
    assert eng.stats["prefills"] == 1
    assert eng.stats["queue_wait_s"] > 0.0
    # longer than the largest bucket: its chunks count, 64 + 36 tokens,
    # the second padded to the smallest bucket that holds it
    eng = make_engine(tiny_model)
    eng.generate([distinct(100, 400)], SamplingParams(max_tokens=2))
    assert eng.stats["admitted"] == 1 and eng.stats["prefills"] == 2
    assert eng.stats["prefill_tokens"] == 100
    assert eng.stats["prefill_padded_tokens"] == 64 + 64


def test_a_preempted_request_is_admitted_and_waits_again(tiny_model):
    eng = make_engine(tiny_model, max_slots=2, max_seq=64,
                      prefill_buckets=(8, 16), num_blocks=5)
    reqs = eng.generate([distinct(6, 0), distinct(6, 50)],
                        SamplingParams(max_tokens=20))
    assert all(len(r.output) == 20 for r in reqs)
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["admitted"] == 2 + eng.stats["preemptions"]
    assert all(r.queued_at > r.submitted_at for r in reqs if r.preemptions)


def test_stats_keys_are_fixed_plain_monotone_and_the_phases_sum_to_the_step():
    from ray_tpu.llm.serving import LLMConfig, LLMServer

    server = LLMServer(LLMConfig(max_slots=2, max_seq=128))
    try:
        eng = server.engine
        first = server.stats()
        assert set(PHASES.values()) | DEVICE_KEYS | {
            "t_step_s", "t_lock_wait_s", "t_now_s", "cpu_host_s", "admitted",
            "queue_wait_s", "prefill_tokens", "prefill_padded_tokens"} <= set(first)
        # nothing ran yet: the device's account is empty, the clock is not
        assert all(first[k] == 0 for k in DEVICE_KEYS)
        assert first["t_now_s"] > 0.0
        # numbers, but for an expert model's per-expert rows (a list,
        # empty for this dense one: tests/test_olmoe_serving.py) and the
        # names of what implements its grouped matmuls and of its router
        # (strings, empty for this dense one:
        # tests/test_moe_grouped_matmul.py, tests/test_kanana_serving.py),
        # and since PR 43 the names of what implements the decode step's
        # attention (every engine's), index scores and selection (empty
        # but for learned sparse attention:
        # tests/test_deepseek_v32_engine.py)
        assert first["moe_expert_load"] == []
        named = {"moe_grouped_impl", "moe_gmm_tiling_gate",
                 "moe_gmm_tiling_up", "moe_gmm_tiling_down",
                 "moe_router_kind", "decode_indexer_impl",
                 "decode_select_impl"}
        assert all(first[k] == "" for k in named)
        assert first["decode_attention_impl"] == eng.decode_attention_impl
        named.add("decode_attention_impl")
        assert all(type(v) in (int, float) for k, v in first.items()
                   if k != "moe_expert_load" and k not in named)
        snaps = [first]
        for k in range(3):
            req = eng.submit(distinct(12 + k, 60 * k),
                             SamplingParams(max_tokens=5))
            assert req.done.wait(120)
            snaps.append(server.stats())
        while server.stats()["t_idle_s"] == snaps[-1]["t_idle_s"]:
            assert server._thread.is_alive()
            server._stop.wait(0.002)
        snaps.append(server.stats())
    finally:
        server._stop.set()
        server._thread.join(30)
    assert not server._thread.is_alive()
    last = server.stats()
    for a, b in zip(snaps, snaps[1:] + [last]):
        assert set(a) == set(b) == set(first)
        assert all(b[k] >= a[k] for k in a), (a, b)
    assert json.loads(json.dumps(last)) == last
    assert all(last[k] > 0.0 for k in PHASES.values())
    assert 0.0 < sum(last[k] for k in IN_STEP) <= last["t_step_s"]
    # two reads of ``stats`` carry their own clock: what an operator
    # divides by ("Is my chip waiting for my host?", docs/serving.md)
    assert snaps[-1]["t_now_s"] < last["t_now_s"]
    # one request at a time on two slots: each one's four steps but the
    # first ran ahead (the fourth stood in flight as the last token came:
    # nothing behind it, nothing dropped), so only the dispatch after a
    # prefill found the queue it had just read empty, and the starved
    # seconds lie inside the steps
    steps = last["decode_steps"]
    assert steps == 3 * 4 and last["decode_steps_ahead"] == 3 * 3
    assert last["decode_rows_dropped"] == 0
    assert last["decode_steps_device_paced"] <= last["decode_steps_ahead"]
    assert 0.0 < last["t_device_starved_s"] < last["t_step_s"]
    assert last["decode_steps_waited"] <= steps
    assert 0.0 < last["cpu_host_s"] <= last["t_step_s"]


def test_the_real_profiler_records_the_phases_on_its_host_plane(tiny_model,
                                                                tmp_path):
    from jax.profiler import ProfileData

    eng = make_engine(tiny_model)
    eng.generate([distinct(5, 0)], SamplingParams(max_tokens=2))   # compile
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.generate([distinct(9, 100)], SamplingParams(max_tokens=3))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    seen.setdefault(ev.name, []).append(ev)
    # (an engine left idling by an earlier test file adds ``engine.idle``)
    assert set(PHASES) - {"engine.idle"} <= set(seen) <= set(PHASES)
    assert all(ev.duration_ns > 0 for evs in seen.values() for ev in evs)
    prefill, = seen["engine.prefill"]
    assert dict(prefill.stats) == {"bucket": 16, "n": 1, "n_pad": 1}
    # what the read-back's readiness probe found rides on its span: the
    # steps with ``waited`` 0 are where to look for the host in a trace
    # (docs/serving.md, "Is my chip waiting for my host?")
    reads = [dict(ev.stats) for ev in seen["engine.sample_readback"]]
    assert sorted(r["step"] for r in reads) == [2, 3]
    assert all(set(r) == {"waited", "step"} and r["waited"] in (0, 1)
               for r in reads)
    assert not any(dict(ev.stats) for ev in seen["engine.decode_enqueue"])


class StubbedDevice:
    """An engine whose clock moves only where this says, and whose
    decode tokens answer ``is_ready()`` as told. Seconds: 1 to take the
    lock, 3 in ``_may_run_ahead`` (in no phase: BEFORE the probe), 5 in
    the decode program's dispatch, 2 in a delivery (AFTER the enqueue),
    7 in a decode step's blocking read. Prefills run as they are and
    take no time."""

    LOCK_S, AHEAD_S, ENQUEUE_S, DELIVER_S, READ_S = 1.0, 3.0, 5.0, 2.0, 7.0

    def __init__(self, eng, monkeypatch, ready: bool):
        import time
        import types

        self.now, self.read_ends, self.ahead_calls = 1000.0, [], 0
        stub = self
        monkeypatch.setattr(engine_module, "time", types.SimpleNamespace(
            perf_counter=lambda: stub.now, thread_time=time.thread_time,
            sleep=time.sleep))

        class Tokens:
            def __init__(self, arr):
                self.arr = arr

            def is_ready(self):
                return ready

            def __array__(self, dtype=None, copy=None):
                stub.now += stub.READ_S
                stub.read_ends.append(stub.now)
                return np.asarray(self.arr)

        class Lock:
            def __enter__(self):
                stub.now += stub.LOCK_S

            def __exit__(self, *exc):
                return False

        decode = eng._decode

        def dispatch(params, tokens, *rest):
            out = decode(params, getattr(tokens, "arr", tokens), *rest)
            stub.now += stub.ENQUEUE_S
            return (Tokens(out[0]), *out[1:])

        eng._decode = dispatch
        eng._lock = Lock()
        may_run_ahead, deliver = eng._may_run_ahead, eng._deliver

        def slow_may_run_ahead(active):
            stub.now += stub.AHEAD_S
            stub.ahead_calls += 1
            return may_run_ahead(active)

        def slow_deliver():
            stub.now += stub.DELIVER_S
            deliver()

        eng._may_run_ahead, eng._deliver = slow_may_run_ahead, slow_deliver


def run_one_slot(tiny_model, monkeypatch, ready):
    """One request on an engine of one slot, so every step after the
    first is dispatched ahead (``test_a_step_runs_ahead_at_any...``):
    the stats after the first ``step()`` and at the end."""
    eng = make_engine(tiny_model, max_slots=1)
    dev = StubbedDevice(eng, monkeypatch, ready)
    req = eng.submit(distinct(9, 0), SamplingParams(max_tokens=12))
    eng.step()                  # the prefill, step 1 read, step 2 in flight
    first = dict(eng.stats)
    while eng.has_work():
        eng.step()
    assert len(req.output) == 12 and eng.stats["decode_steps"] == 11
    return dev, first, dict(eng.stats)


def test_a_device_that_is_never_ready_sets_the_pace_and_is_never_starved(
        tiny_model, monkeypatch):
    dev, first, last = run_one_slot(tiny_model, monkeypatch, ready=False)
    # the first dispatch came after the prefill's blocking read: starved,
    # while the host delivered the first token and enqueued; its
    # read-back waited, and there was no read-back before it to measure
    # a program from
    after_prefill = dev.DELIVER_S + dev.ENQUEUE_S
    assert first["t_device_starved_s"] == after_prefill
    assert first["decode_steps_waited"] == 1
    assert first["decode_steps_device_paced"] == 0
    # from then on a step ahead stands on the device whenever the host
    # looks: never starved again, and every step waited for and paced
    assert last["t_device_starved_s"] == after_prefill
    assert last["decode_steps_waited"] == 11
    assert last["decode_steps_device_paced"] == 10
    assert len(dev.read_ends) == 11
    assert last["t_device_paced_s"] == dev.read_ends[-1] - dev.read_ends[0]
    # the seven phases inside ``step()`` and the lock's wait tile it:
    # what is left is what the stub spent outside them
    assert last["t_lock_wait_s"] == 11 * dev.LOCK_S
    assert last["t_step_s"] - sum(last[k] for k in IN_STEP) \
        == pytest.approx(dev.ahead_calls * dev.AHEAD_S)


def test_a_device_that_is_always_ready_is_starved_from_probe_to_enqueue(
        tiny_model, monkeypatch):
    dev, first, last = run_one_slot(tiny_model, monkeypatch, ready=True)
    # eleven decode dispatches, each on a device known to be done: from
    # the probe (or the prefill's read) to the enqueue's end and no
    # further. The host's seconds before the probe (``_may_run_ahead``)
    # and after the enqueue (the delivery, the read) are not the
    # device's account: it MAY have been busy then
    after_prefill = dev.DELIVER_S + dev.ENQUEUE_S
    assert first["t_device_starved_s"] == after_prefill + dev.ENQUEUE_S
    assert last["t_device_starved_s"] == after_prefill + 10 * dev.ENQUEUE_S
    assert last["decode_steps_waited"] == 0
    assert last["decode_steps_device_paced"] == 0
    assert last["t_device_paced_s"] == 0.0


def test_an_engine_without_work_books_no_starved_device(tiny_model,
                                                        monkeypatch):
    eng = make_engine(tiny_model)               # four slots
    stop = threading.Event()
    loop = threading.Thread(target=eng.run_forever, args=(stop, 0.001))
    loop.start()                         # nothing to do: the loop idles
    while eng.stats["t_idle_s"] == 0.0 and loop.is_alive():
        stop.wait(0.001)
    stop.set()
    loop.join(30)
    assert not loop.is_alive()
    assert all(eng.stats[k] == 0 for k in DEVICE_KEYS)
    # nor is the time between two requests the host's: the mark goes
    # with the last request, and the next one's prefill finds none
    dev = StubbedDevice(eng, monkeypatch, ready=False)
    for k in range(2):
        eng.generate([distinct(9, 70 * k)], SamplingParams(max_tokens=4))
        dev.now += 100.0                        # nobody asks for anything
        assert eng.step() == 0
    assert eng.stats["decode_steps"] == 6
    # a request's first decode dispatch came after the blocking read of
    # its prefill's first token: starved from that read's end to the
    # enqueue's, a delivery and an enqueue. Its two other steps stood on
    # the device a step ahead (one request on four slots), behind a step
    # that was never ready: not starved, and paced by the device
    assert eng.stats["t_device_starved_s"] == 2 * (
        dev.DELIVER_S + dev.ENQUEUE_S)
    assert eng.stats["decode_steps_waited"] == 6
    assert eng.stats["decode_steps_ahead"] == 4
    assert eng.stats["decode_steps_device_paced"] == 4


def test_program_names_the_benchmark_readers_match_are_pinned(tiny_model):
    """``benchmark/metrics/decode_program_ms.*`` match ``decode_step_paged``
    and ``prefill_program_ms_per_ktok`` matches ``prefill`` in the names
    of the trace's ``XLA Modules`` events, which are the lowered
    modules' names: a rename makes those metrics read nothing."""
    model, params = tiny_model
    eng = make_engine(tiny_model)
    i32 = jnp.int32

    def module_name(jitted, *args):
        text = jitted.lower(*args).as_text()
        return re.search(r"module @(\S+)", text).group(1)

    B, nb = eng.max_slots, eng.blocks_per_slot
    assert module_name(
        eng._decode, params, jnp.zeros(B, i32), eng.kv,
        jnp.zeros((B, nb), i32), jnp.zeros(B, i32), jnp.zeros(B),
        jnp.zeros(B, i32), jax.random.key(0), None
    ) == "jit__decode_step_paged"
    bucket = module_name(eng._prefill, params, jnp.zeros((1, 16), i32),
                         jnp.ones(1, i32))
    pk, pv = eng._gather(eng.kv, jnp.zeros((1, 1), i32))
    chunk = module_name(eng._prefill_prefix, params, jnp.zeros((1, 16), i32),
                        pk, pv, jnp.zeros(1, i32), jnp.ones(1, i32))
    for name in (bucket, chunk):
        assert name.startswith("jit_") and "prefill" in name, name
