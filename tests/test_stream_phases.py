"""The Serve stream path accounts for itself (docs/serving.md, "The
stream path"): counts at its four boundaries (the engine's put, the
replica thread's take, the runtime's report, the consumer's ref), each
end's CPU seconds as its own thread published them, and sampled spans on
both ends of the same items. A stream that has fallen behind is carried
in runs (several items, one object): the account stays in items, and
``stream_objects_reported`` beside it says how many objects carried
them. CPU, debug widths, no timing thresholds: what is checked is names,
attributes, exact counts, which thread, and that no reading ever goes
down."""

import json
import os
import sys
import threading
import time

import jax
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import worker
from ray_tpu.llm import LLMConfig, build_llm_app

COUNTS = ("stream_puts", "stream_takes", "stream_items_reported",
          "stream_items_consumed")
STREAM_KEYS = set(COUNTS) | {
    "stream_objects_reported", "streams_live", "stream_producer_cpu_s",
    "stream_consumer_cpu_s", "engine_thread_cpu_s", "stream_produce_s",
    "stream_consume_s",
    "stream_items_timed_produce", "stream_items_timed_consume"}
WAIT_S = 120


@pytest.fixture
def llm(ray_start_regular):
    """One replica of the debug model behind Serve: ``(handle, server)``,
    the server being the replica's own ``LLMServer``."""
    handle = serve.run(build_llm_app(LLMConfig(max_slots=4, max_seq=256)))
    controller = ray_tpu.get_actor("serve_controller")
    rep = ray_tpu.get(controller.get_replicas.remote(
        "llama-debug"))["replicas"][0]
    server = worker.global_runtime()._actor_executors[
        rep._actor_id].instance._callable
    yield handle, server
    serve.shutdown()


def open_stream(handle, n, k=0):
    return handle.options(stream=True).remote(
        {"prompt": [5 + k, 6, 7, 8, 9 + k], "max_tokens": n, "stream": True})


def read_all(gen, into=None):
    """A stream's chunks: its tokens' (fewer than asked for where the
    debug model samples its stop token) and the ``done`` chunk."""
    chunks = into if into is not None else []
    chunks.extend(gen)
    assert chunks[-1]["done"] and [c["index"] for c in chunks[:-1]] == list(
        range(len(chunks) - 1))
    return chunks


def timed_items(streams):
    """Items 0, 16, 32 ... of each stream."""
    return sum(len(range(0, len(chunks), 16)) for chunks in streams)


def slow_down(server, monkeypatch, seconds):
    """A pause before every engine step: a stream long enough for the
    test to catch it mid-way (on the CPU the debug model streams a
    hundred tokens between two looks of a test)."""
    step = server.engine.step

    def slow_step():
        time.sleep(seconds)
        return step()
    monkeypatch.setattr(server.engine, "step", slow_step)


def hold_reader(server, monkeypatch, tokens=None):
    """The replica thread is handed its request only once the engine
    has put ``tokens`` of it on the stream (None: all of it and the end
    marker): a reader that starts behind."""
    submit = server.engine.submit

    def submit_and_wait(ids, sampling):
        req = submit(ids, sampling)
        until(lambda: req.done.is_set() or (
            tokens is not None and req.stream.qsize() >= tokens),
            "the engine to get ahead")
        return req
    monkeypatch.setattr(server.engine, "submit", submit_and_wait)


def in_step_with_the_readers(server, monkeypatch):
    """The engine makes a step only once every item it has put was
    taken: readers that keep up, whatever the machine is busy with."""
    step = server.engine.step

    def step_when_read():
        def read():
            stats = server.engine.stats
            return stats["stream_puts"] == stats["stream_takes"]
        until(read, "the readers")
        return step()
    monkeypatch.setattr(server.engine, "step", step_when_read)


def until(cond, what):
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: what was opened,
    by which thread, at which depth among the recorded spans of its
    thread, and how many are open now."""

    log = []
    lock = threading.Lock()
    open_now = 0
    depth = threading.local()

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        d = getattr(Recorder.depth, "n", 0)
        with Recorder.lock:
            Recorder.log.append(
                (self.name, self.attrs, threading.get_ident(), d))
            Recorder.open_now += self.name.startswith("serve.stream.")
        Recorder.depth.n = d + 1
        return self

    def __exit__(self, *exc):
        Recorder.depth.n -= 1
        with Recorder.lock:
            Recorder.open_now -= self.name.startswith("serve.stream.")
        return False


def test_the_four_counts_agree_at_the_end_of_every_stream(llm):
    """(a) k greedy requests of n tokens: n tokens and the end marker,
    n token chunks and the ``done`` chunk, at every boundary."""
    handle, server = llm
    before = server.stats()
    k, n = 3, 21
    got = [[] for _ in range(k)]
    threads = [threading.Thread(target=read_all,
                                args=(open_stream(handle, n, i), got[i]))
               for i in range(k)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT_S)
    assert not any(t.is_alive() for t in threads)
    until(lambda: server.stats()["streams_live"] == 0, "the streams' end")
    after = server.stats()
    assert all(c[-1]["usage"]["completion_tokens"] == len(c) - 1 for c in got)
    assert max(len(c) for c in got) == n + 1
    for key in COUNTS:
        assert after[key] - before[key] == sum(len(c) for c in got), key
    # the same items were timed on both ends
    for key in ("stream_items_timed_produce", "stream_items_timed_consume"):
        assert after[key] - before[key] == timed_items(got), key
    assert after["stream_produce_s"] > before["stream_produce_s"]
    assert after["stream_consume_s"] > before["stream_consume_s"]


def test_a_consumer_that_does_not_read_is_a_backlog_before_the_clients(llm):
    """(b) the replica thread keeps up whatever the client does: what
    waits, waits in the handle's items, not in the request's queue."""
    handle, server = llm
    before = server.stats()
    n = 30
    gen = open_stream(handle, n)

    def moved(key):
        return server.stats()[key] - before[key]
    until(lambda: moved("stream_items_reported") > 1
          and server.stats()["streams_live"] == 0, "the producer")
    held = server.stats()
    items = held["stream_items_reported"] - before["stream_items_reported"]
    assert held["stream_puts"] - before["stream_puts"] == items
    assert held["stream_puts"] == held["stream_takes"]
    assert held["stream_items_consumed"] == before["stream_items_consumed"]
    assert len(read_all(gen)) == items
    after = server.stats()
    assert (after["stream_items_reported"] - after["stream_items_consumed"]
            == before["stream_items_reported"]
            - before["stream_items_consumed"])
    assert after["streams_live"] == 0


def test_a_reader_that_starts_behind_is_carried_in_fewer_objects_than_items(
        llm, monkeypatch):
    """A replica thread that finds n tokens and the end marker waiting
    reports them as ONE object; the caller still reads n token chunks,
    ``index`` 0 ... n-1, then ``done``, and the account is in items."""
    handle, server = llm
    read_all(open_stream(handle, 3))                    # compile
    hold_reader(server, monkeypatch)
    before = server.stats()
    chunks = read_all(open_stream(handle, 21))
    assert all(type(c) is dict and "token_id" in c for c in chunks[:-1])
    assert chunks[-1]["usage"]["completion_tokens"] == len(chunks) - 1 > 1
    until(lambda: server.stats()["streams_live"] == 0, "the stream's end")
    after = server.stats()
    for key in COUNTS:
        assert after[key] - before[key] == len(chunks), key
    assert (after["stream_objects_reported"]
            - before["stream_objects_reported"]) == 1


def test_a_stream_that_keeps_up_is_carried_an_item_an_object(
        llm, monkeypatch):
    """A token that waited alone travels as the parent's bare dict:
    ``LLMServer.stream`` yields nothing else, and through the handle the
    objects reported are the items. The one run a reader that keeps up
    meets is the stream's end: the engine puts its last token (or two:
    it runs a step ahead) and the end marker in one go, so those are
    taken together."""
    handle, server = llm
    read_all(open_stream(handle, 3))                    # compile
    in_step_with_the_readers(server, monkeypatch)
    request = {"prompt": [5, 6, 7, 8, 9], "max_tokens": 20, "stream": True}
    *yielded, end = server.stream(request)
    assert len(yielded) > 2 and all(type(c) is dict for c in yielded)
    assert type(end) is serve.ChunkRun and 2 <= len(end) <= 3
    assert end[-1]["done"] and [c["index"] for c in yielded + end[:-1]] == (
        list(range(len(yielded) + len(end) - 1)))
    before = server.stats()
    chunks = read_all(open_stream(handle, 20))
    assert [c.get("token_id") for c in chunks] == [
        c.get("token_id") for c in yielded + list(end)]
    until(lambda: server.stats()["streams_live"] == 0, "the stream's end")
    after = server.stats()
    for key in COUNTS:
        assert after[key] - before[key] == len(chunks), key
    assert len(chunks) - 2 <= (
        after["stream_objects_reported"]
        - before["stream_objects_reported"]) <= len(chunks) - 1


@pytest.mark.parametrize("ahead", [None, 20], ids=["all", "20_tokens"])
def test_a_run_is_timed_once_for_the_sampled_items_it_holds_on_both_ends(
        llm, monkeypatch, ahead):
    """Items 0, 16, 32 ... stay the sampled ones when they travel in
    runs: what an end handles at once (the replica a run, the reader
    everything it finds waiting) is ONE span, at the first sampled index
    it holds, and the timed items are counted by index, so the two ends'
    denominators agree."""
    handle, server = llm
    read_all(open_stream(handle, 3))                    # compile
    hold_reader(server, monkeypatch, ahead)
    before = server.stats()
    Recorder.log, Recorder.open_now = [], 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    chunks = read_all(open_stream(handle, 40))
    until(lambda: server.stats()["streams_live"] == 0, "the stream's end")
    after = server.stats()
    objects = (after["stream_objects_reported"]
               - before["stream_objects_reported"])
    assert objects < len(chunks) > 33
    for key in ("stream_items_timed_produce", "stream_items_timed_consume"):
        assert after[key] - before[key] == timed_items([chunks]) == 3, key
    for name in ("serve.stream.produce", "serve.stream.consume"):
        spans = [a["index"] for n, a, _, _ in Recorder.log if n == name]
        assert spans[0] == 0 and spans == sorted(set(spans)) and set(
            spans) <= {0, 16, 32}, (name, spans)
        assert len(spans) <= objects
        if ahead is None:                               # the one run
            assert spans == [0] and objects == 1
    assert Recorder.open_now == 0


def test_spans_are_on_the_sampled_items_of_both_ends_and_on_no_wait(
        llm, monkeypatch):
    """(c) items 0, 16, 32 ... by their index, the SAME items on both
    ends; siblings on their threads, none on the engine's; and while
    every stream thread waits (the engine held still), none is open."""
    handle, server = llm
    read_all(open_stream(handle, 3))                    # compile
    slow_down(server, monkeypatch, 0.005)
    before = server.stats()
    Recorder.log, Recorder.open_now = [], 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    n = 230                 # long enough to be caught and held mid-way
    gens = [open_stream(handle, n, i) for i in range(2)]
    task_of = {g._ref_gen._task_id.hex(): i for i, g in enumerate(gens)}
    got = [[] for _ in gens]

    def reader(i):
        for chunk in gens[i]:
            got[i].append(chunk)
    threads = [threading.Thread(target=reader, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    until(lambda: min(len(g) for g in got) >= 18, "item 16 of both streams")
    with server.engine._lock:           # the engine stands still: a stream
        def all_wait():                 # held back, for as long as it takes
            s = server.stats()
            return (s["stream_puts"] == s["stream_takes"]
                    and s["stream_items_reported"]
                    == s["stream_items_consumed"]
                    and sum(len(g) for g in got)
                    == s["stream_items_consumed"]
                    - before["stream_items_consumed"])
        until(all_wait, "every stream thread to wait")
        assert all(len(g) < n + 1 for g in got), "ended before the hold"
        time.sleep(0.05)
        assert Recorder.open_now == 0, "a span is open around a wait"
    for t in threads:
        t.join(WAIT_S)
    assert all(g[-1]["done"] for g in got)
    until(lambda: server.stats()["streams_live"] == 0, "the streams' end")

    engine_thread = server._thread.ident
    produce = [e for e in Recorder.log if e[0] == "serve.stream.produce"]
    consume = [e for e in Recorder.log if e[0] == "serve.stream.consume"]
    assert {e[0] for e in Recorder.log if e[0].startswith("serve.")} == {
        "serve.stream.produce", "serve.stream.consume"}
    sampled = sorted(list(range(0, len(g), 16)) for g in got)
    request_of = {}
    for name, attrs, ident, depth in produce:
        assert set(attrs) == {"request", "index", "step"}
        request_of.setdefault(attrs["request"], []).append(attrs["index"])
        assert ident != engine_thread and depth == 0
    assert sorted(request_of.values()) == sampled
    consumed = {}
    for name, attrs, ident, depth in consume:
        assert set(attrs) == {"task", "index"}
        consumed.setdefault(task_of[attrs["task"]], []).append(attrs["index"])
        assert ident in (threads[0].ident, threads[1].ident) and depth == 0
    assert sorted(consumed.values()) == sampled
    assert all(consumed[i] == list(range(0, len(got[i]), 16)) for i in (0, 1))
    # a request's chunks name it: the two ends' spans of one item join
    # on (request of the task's first chunk, index)
    assert {int(g[0]["id"].split("-")[1]) for g in got} == set(request_of)
    # a decode step's token carries the number on that step's
    # ``engine.deliver`` span (a prefill's first token has none: it is
    # handed over inside ``engine.emit``)
    delivered = {a["step"] for name, a, _, _ in Recorder.log
                 if name == "engine.deliver"}
    assert all(a["step"] in delivered for _, a, _, _ in produce
               if a["index"] > 0)


def test_stats_keys_are_there_from_construction_and_json_plain():
    """(d) with no runtime, before and after traffic: one key set."""
    from ray_tpu.llm.serving import LLMServer

    assert not ray_tpu.is_initialized()
    bare = LLMServer(LLMConfig(max_slots=2, max_seq=128))
    try:
        first = bare.stats()            # no runtime in this process
    finally:
        bare._stop.set()
        bare._thread.join(30)
    assert STREAM_KEYS <= set(first)
    assert all(first[k] == 0 for k in STREAM_KEYS - {"engine_thread_cpu_s"})

    ray_tpu.init(num_nodes=1, resources={"CPU": 8})
    try:
        handle = serve.run(build_llm_app(LLMConfig(max_slots=2, max_seq=128)))
        snaps = [handle.stats.remote().result(timeout=WAIT_S)]
        chunks = read_all(open_stream(handle, 20))
        snaps.append(handle.stats.remote().result(timeout=WAIT_S))
    finally:
        serve.shutdown()
    for snap in snaps:
        assert set(snap) == set(first)
        assert json.loads(json.dumps(snap)) == snap
        # numbers, but for an expert model's rows (a list), the names of
        # what implements its grouped matmuls and of its router (strings:
        # all empty for this dense model) and, since PR 43, the names of
        # what implements the decode step's attention, index scores and
        # selection (``decode_*_impl``)
        assert all(type(v) in (int, float) for k, v in snap.items()
                   if k != "moe_expert_load"
                   and not k.endswith("_impl")
                   and not k.startswith(("moe_grouped_", "moe_gmm_",
                                         "moe_router_")))
    assert snaps[1]["stream_puts"] - snaps[0]["stream_puts"] == len(chunks)


@ray_tpu.remote(_in_process=True)
class Burner:
    """An actor whose ONE thread serves its streams in turn."""

    def cpu(self):
        return threading.get_ident(), time.thread_time()

    def burn(self, items):
        for i in range(items):
            c0 = time.thread_time()
            while time.thread_time() - c0 < 0.002:
                pass
            yield i, threading.get_ident()


def test_cpu_seconds_are_each_threads_own_and_are_not_counted_twice(
        ray_start_regular):
    """(e) a thread that serves two streams in turn: each stream holds
    the seconds since IT was taken up, an ended stream keeps its
    seconds, and no reading goes down."""
    rt = worker.global_runtime()
    actor = Burner.remote()
    ident0, c0 = ray_tpu.get(actor.cpu.remote())
    snaps = [rt.generator_stats()]
    items = 33
    for _ in range(2):
        gen = actor.burn.options(num_returns="streaming").remote(items)
        values = [ray_tpu.get(ref) for ref in gen]
        assert [v[0] for v in values] == list(range(items))
        assert {v[1] for v in values} == {ident0}
        snaps.append(rt.generator_stats())
    ident1, c1 = ray_tpu.get(actor.cpu.remote())
    assert ident1 == ident0
    snaps.append(rt.generator_stats())          # both folded by now
    for a, b in zip(snaps, snaps[1:]):
        assert all(b[k] >= a[k] for k in a if k != "streams_live"), (a, b)
    first = snaps[1]["stream_producer_cpu_s"] - snaps[0]["stream_producer_cpu_s"]
    both = snaps[-1]["stream_producer_cpu_s"] - snaps[0]["stream_producer_cpu_s"]
    burned = items * 0.002
    # each stream's cell holds its own burn, and together no more than
    # the thread spent between the two readings around them
    assert first >= burned and both - first >= burned
    assert both <= c1 - c0
    assert snaps[-1]["stream_consumer_cpu_s"] > snaps[0]["stream_consumer_cpu_s"]
    assert snaps[-1]["streams_live"] == 0
    assert all(s.folded for s in rt._generators.values())


def test_an_engine_threads_cpu_is_published_and_monotone(llm):
    """(e) the engine thread's own reading, from the reads ``step()``
    makes anyway: all of its CPU in ``step()``, the waiting phases'
    too, so never under ``cpu_host_s``."""
    handle, server = llm
    snaps = [server.stats()]
    for i in range(2):
        read_all(open_stream(handle, 12, i))
        snaps.append(server.stats())
    for a, b in zip(snaps, snaps[1:]):
        for key in ("engine_thread_cpu_s", "stream_producer_cpu_s",
                    "stream_consumer_cpu_s"):
            assert b[key] > a[key], key
    assert all(s["engine_thread_cpu_s"] >= s["cpu_host_s"] for s in snaps)


@pytest.mark.filterwarnings(      # the loop thread dies loudly, by design
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_a_stream_that_ends_in_an_error_leaves_the_counts_consistent(llm):
    """(f) the engine dies under a stream: the failure's end marker is a
    put like any other, the replica thread takes it and reports the
    error, and nothing stays live."""
    handle, server = llm
    before = server.stats()
    gen = open_stream(handle, 200)
    first = gen.next(timeout=WAIT_S)
    assert first["index"] == 0

    def boom(*a, **k):
        raise RuntimeError("device lost")
    server.engine._decode = boom
    with pytest.raises(Exception, match="engine loop died"):
        read_all(gen)
    until(lambda: server.stats()["streams_live"] == 0, "the stream's end")
    after = server.stats()
    puts = after["stream_puts"] - before["stream_puts"]
    assert puts == after["stream_takes"] - before["stream_takes"] >= 2
    # every token was reported and read; the end marker became the error
    reported = after["stream_items_reported"] - before["stream_items_reported"]
    consumed = after["stream_items_consumed"] - before["stream_items_consumed"]
    assert reported == consumed == puts - 1


def test_a_consumer_that_gives_up_leaves_the_counts_consistent(
        llm, monkeypatch):
    """(f) a timeout at the client: the stream runs to its end behind
    it, reported stays ahead of consumed, and nothing stays live."""
    from ray_tpu.exceptions import GetTimeoutError

    handle, server = llm
    slow_down(server, monkeypatch, 0.005)
    before = server.stats()
    n = 60
    gen = open_stream(handle, n)
    assert gen.next(timeout=WAIT_S)["index"] == 0
    with server.engine._lock:                   # nothing more can come
        with pytest.raises(GetTimeoutError):
            while True:
                gen.next(timeout=0.05)
    until(lambda: server.stats()["streams_live"] == 0, "the stream's end")
    after = server.stats()
    reported = after["stream_items_reported"] - before["stream_items_reported"]
    consumed = after["stream_items_consumed"] - before["stream_items_consumed"]
    assert after["stream_puts"] - before["stream_puts"] == reported
    assert after["stream_takes"] - before["stream_takes"] == reported
    assert 1 <= consumed < reported <= n + 1


@ray_tpu.remote(num_returns="streaming")
def count_to(n):
    for i in range(n):
        yield i


def test_a_plain_streaming_task_is_counted_by_the_runtime(ray_start_regular):
    """(g) no Serve, no engine: ``Runtime.generator_stats``."""
    rt = worker.global_runtime()
    zero = rt.generator_stats()
    assert zero == worker.NO_STREAMS
    gen = count_to.remote(20)
    refs = [next(gen) for _ in range(5)]
    assert ray_tpu.get(refs) == list(range(5))
    until(lambda: rt.generator_stats()["stream_items_reported"] == 20
          and rt.generator_stats()["streams_live"] == 0, "the producer")
    mid = rt.generator_stats()
    assert mid["stream_items_consumed"] == 5 and mid["streams_live"] == 0
    assert [ray_tpu.get(r) for r in gen] == list(range(5, 20))
    end = rt.generator_stats()
    assert end["stream_items_reported"] == end["stream_items_consumed"] == 20
    # read to its end: folded, and the totals no longer need the state
    assert all(s.folded for s in rt._generators.values())
    rt._generators.clear()
    assert rt.generator_stats() == end
    # ``next_value`` is ``get(next())``, timed on the sampled items
    gen = count_to.remote(18)
    assert [gen.next_value(timeout=WAIT_S) for _ in range(18)] == list(range(18))
    assert rt.generator_stats()["stream_items_timed_consume"] == 2


def test_stats_asked_every_millisecond_never_reads_a_count_going_down(llm):
    """(h) eight streams over four slots, a second thread asking all the
    while, the interpreter switching threads far more often than it
    does by itself."""
    handle, server = llm
    snaps, errors, stop = [], [], threading.Event()

    def ask():
        try:
            while not stop.is_set():
                snaps.append(server.stats())
                time.sleep(0.001)
        except BaseException as err:            # noqa: BLE001 — reported
            errors.append(err)
            raise
    n = 48
    got = [[] for _ in range(8)]
    readers = [threading.Thread(target=read_all,
                                args=(open_stream(handle, n, i), got[i]))
               for i in range(8)]
    asker = threading.Thread(target=ask)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        asker.start()
        for t in readers:
            t.start()
        for t in readers:
            t.join(WAIT_S)
        until(lambda: server.stats()["streams_live"] == 0, "the streams' end")
        stop.set()
        asker.join(WAIT_S)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
    assert not errors and not asker.is_alive()
    assert not any(t.is_alive() for t in readers)
    assert len(snaps) > 10
    monotone = STREAM_KEYS - {"streams_live"}
    for a, b in zip(snaps, snaps[1:]):
        down = {k: (a[k], b[k]) for k in monotone if b[k] < a[k]}
        assert not down, down
        # nothing is taken before it is put, or handed out before it is
        # reported
        assert b["stream_takes"] <= b["stream_puts"]
        assert b["stream_items_consumed"] <= b["stream_items_reported"]
    last = server.stats()
    assert all(last[k] - snaps[0][k] == sum(len(c) for c in got)
               for k in COUNTS)


def test_the_engine_counts_a_stream_nobody_reads_and_one_read_twice():
    """The engine's half without Serve: puts are counted where they are
    made, takes by whoever iterates, and a request nobody reads stays a
    difference between the two."""
    from ray_tpu.llm import ContinuousBatchingEngine, SamplingParams
    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(LlamaConfig.debug(vocab_size=512, max_seq_len=128))
    eng = ContinuousBatchingEngine(
        model, model.init(jax.random.key(0)), max_slots=2, max_seq=128,
        prefill_buckets=(16,), block_size=8)
    unread, read = eng.generate([[3, 4, 5], [6, 7, 8]],
                                SamplingParams(max_tokens=5))
    assert eng.stats["stream_puts"] == 12 and eng.stats["stream_takes"] == 0
    assert list(read.iter_tokens()) == read.output
    assert eng.stats["stream_takes"] == 6 and read.takes == 0
    long = eng.submit([9] * 200, SamplingParams(max_tokens=5))   # refused
    eng.step()
    assert long.finish_reason == "prompt_too_long"
    assert eng.stats["stream_puts"] == 13
    assert list(long.iter_tokens()) == [] and eng.stats["stream_takes"] == 7
    assert unread.stream.qsize() == 6


def queued(tokens):
    """A request with ``tokens`` on its stream, as decode steps 0, 1, ...
    delivered them."""
    from ray_tpu.llm import SamplingParams
    from ray_tpu.llm.engine import Request

    req = Request([3, 4, 5], SamplingParams())
    for step, tok in enumerate(tokens):
        req.stream.put((tok, step))
    return req


@pytest.mark.parametrize("queue", ["tokens", "end", "end_alone", "fail",
                                   "fail_alone"])
def test_a_request_is_read_a_run_at_a_time(queue):
    """``Request.iter_runs``: the reader waits for ONE item and takes
    everything that waits with it, in order, up to and including the
    end marker; what came before an end or a failure is delivered."""
    from ray_tpu.llm.engine import EngineDeadError

    tokens = [] if queue.endswith("_alone") else [10, 11, 12, 13, 14]
    req = queued(tokens)
    if queue.startswith("end"):
        req.finish_reason = "length"
        req.stream.put((None, 7))
        req.done.set()
    elif queue.startswith("fail"):
        assert req.fail(RuntimeError("device lost"), 7)
    runs = req.iter_runs()
    if queue == "tokens":
        assert next(runs) == (tokens, False)
        assert (req.takes, req.step) == (5, 4)
        req.stream.put((15, 5))         # one token waiting: a run of one
        assert next(runs) == ([15], False)
        assert (req.takes, req.step) == (6, 5)
        return
    if queue.startswith("end"):
        assert next(runs) == (tokens, True)
    else:
        if tokens:
            assert next(runs) == (tokens, False)
        with pytest.raises(EngineDeadError, match="device lost"):
            next(runs)
    assert (req.takes, req.step) == (len(tokens) + 1, 7)
    assert list(runs) == [] and req.stream.qsize() == 0
    # a token at a time, the same stream reads the same
    again = queued(tokens)
    again.stream.put((None, 7))
    assert list(again.iter_tokens()) == tokens and again.takes == 6 - (
        5 - len(tokens))


ATTEMPTS = []


@ray_tpu.remote(num_returns="streaming", max_retries=1, _in_process=True)
def runs_then_a_crash():
    """Items 0 ... 7, their runs cut differently by each attempt; the
    first attempt dies after four."""
    from ray_tpu._private.worker_process import WorkerCrashed

    ATTEMPTS.append(len(ATTEMPTS))
    if len(ATTEMPTS) == 1:
        yield serve.ChunkRun([0, 1, 2])
        yield 3
        raise WorkerCrashed("the worker died under the stream")
    yield 0
    yield serve.ChunkRun([1, 2, 3, 4])
    yield serve.ChunkRun([5, 6])
    yield serve.ChunkRun([])
    yield 7


def test_a_replay_skips_the_items_reported_not_the_objects(ray_start_regular):
    """A retried stream has reported two objects and FOUR items: the
    replay drops four items, out of whatever runs it yields them in."""
    rt = worker.global_runtime()
    del ATTEMPTS[:]
    gen = runs_then_a_crash.remote()
    assert [gen.next_value(timeout=WAIT_S) for _ in range(8)] == list(range(8))
    with pytest.raises(StopIteration):
        gen.next_value(timeout=WAIT_S)
    assert ATTEMPTS == [0, 1]
    stats = rt.generator_stats()
    assert stats["stream_items_reported"] == 8
    assert stats["stream_items_consumed"] == 8
    # [0, 1, 2], 3 | 4 (what the replay left of its run), [5, 6], 7
    assert stats["stream_objects_reported"] == 5
    assert stats["streams_live"] == 0


@ray_tpu.remote(num_returns="streaming", _in_process=True)
def in_runs(runs):
    for run in runs:
        yield serve.ChunkRun(run) if isinstance(run, list) else run


@pytest.mark.parametrize("values_first", [0, 2, 4])
def test_a_reader_of_refs_gets_one_ref_an_item_of_a_run(ray_start_regular,
                                                        values_first):
    """``ObjectRefGenerator.next()`` on a stream that travelled in runs:
    one ref an item, in order (the runtime splits the run), also after
    ``next_value`` has claimed a run's items; the account is in items."""
    rt = worker.global_runtime()
    gen = in_runs.remote([0, [1, 2, 3], 4, [5, 6]])
    values = [gen.next_value(timeout=WAIT_S) for _ in range(values_first)]
    refs = list(gen)
    assert all(isinstance(r, ray_tpu.ObjectRef) for r in refs)
    assert len({r.id for r in refs}) == len(refs) == 7 - values_first
    assert values + ray_tpu.get(refs) == list(range(7))
    stats = rt.generator_stats()
    assert stats["stream_items_reported"] == 7
    assert stats["stream_items_consumed"] == 7
    assert stats["stream_objects_reported"] == 4


def test_two_readers_of_one_run_get_each_item_once(ray_start_regular):
    """Readers that share a generator and race for a run's items: every
    item reaches exactly one of them, by value or by ref."""
    n_runs, width = 60, 5
    gen = in_runs.remote([list(range(i * width, (i + 1) * width))
                          for i in range(n_runs)])
    got = [[] for _ in range(4)]

    def read(k):
        try:
            while True:
                got[k].append(gen.next_value(timeout=WAIT_S) if k % 2
                              else ray_tpu.get(gen.next(timeout=WAIT_S)))
        except StopIteration:
            pass
    threads = [threading.Thread(target=read, args=(k,)) for k in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT_S)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert sorted(sum(got, [])) == list(range(n_runs * width))
    stats = worker.global_runtime().generator_stats()
    assert stats["stream_items_consumed"] == n_runs * width
    assert stats["stream_objects_reported"] == n_runs


def test_back_pressure_counts_objects_a_run_as_one(ray_start_regular):
    """``_generator_backpressure_num_objects`` = 2: the producer waits
    with two OBJECTS (here six items) beyond what the consumer has
    touched; a reader takes what waits, and lets as many through."""
    rt = worker.global_runtime()
    gen = in_runs.options(_generator_backpressure_num_objects=2).remote(
        [[3 * i, 3 * i + 1, 3 * i + 2] for i in range(5)])

    def held_at(objects):
        def reported():
            stats = rt.generator_stats()
            return (stats["stream_objects_reported"],
                    stats["stream_items_reported"])
        until(lambda: reported() == (objects, 3 * objects),
              f"the producer to be held at {objects} objects")
        time.sleep(0.05)
        assert reported() == (objects, 3 * objects)
    held_at(2)
    assert gen.next_value(timeout=WAIT_S) == 0  # took both: two more come
    held_at(4)
    assert [gen.next_value(timeout=WAIT_S) for _ in range(5)] == list(
        range(1, 6))                            # out of the reader's hand
    held_at(4)
    assert [gen.next_value(timeout=WAIT_S) for _ in range(9)] == list(
        range(6, 15))
    held_at(5)
    assert rt.generator_stats()["stream_items_consumed"] == 15


@ray_tpu.remote
def late(gate):
    while not os.path.exists(gate):
        time.sleep(0.01)
    return "late"


@ray_tpu.remote(max_retries=0)
def broken():
    raise ValueError("no value")


def test_a_get_of_many_ready_refs_is_one_read_and_else_one_by_one(
        ray_start_regular, tmp_path):
    """What a reader's take of a backlog rides on: ``get`` of several
    refs that are all complete and in the owner's store reads them under
    one acquisition of each lock; one that is not complete yet, or an
    error, sends it back to the loop, which waits and raises as ever."""
    rt = worker.global_runtime()
    refs = [ray_tpu.put({"i": i}) for i in range(5)]
    gets = rt.memory_store.stats["gets"]
    assert rt._get_ready(refs) == [{"i": i} for i in range(5)]
    assert rt.memory_store.stats["gets"] == gets + 5
    assert ray_tpu.get(refs) == [{"i": i} for i in range(5)]
    gate = str(tmp_path / "gate")
    waiting = late.remote(gate)
    assert rt._get_ready(refs + [waiting]) is None
    with pytest.raises(ray_tpu.exceptions.GetTimeoutError):
        ray_tpu.get(refs + [waiting], timeout=0.05)
    open(gate, "w").close()
    assert ray_tpu.get(refs + [waiting], timeout=WAIT_S)[-1] == "late"
    assert rt._get_ready(refs + [waiting])[-1] == "late"
    failed = broken.remote()
    ray_tpu.wait([failed], timeout=WAIT_S)
    assert rt._get_ready(refs + [failed]) is None
    with pytest.raises(ValueError, match="no value"):
        ray_tpu.get(refs + [failed], timeout=WAIT_S)
