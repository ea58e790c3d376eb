"""LLM engine: KV-cache correctness, continuous batching, serving."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import (ByteTokenizer, ContinuousBatchingEngine, LLMConfig,
                         SamplingParams, build_llm_app)
from ray_tpu.models.llama import LlamaConfig, LlamaModel


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.debug(vocab_size=512, max_seq_len=128)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def test_kv_cache_matches_full_forward(tiny_model):
    """Greedy decode with the KV cache must equal argmax of the full
    (uncached) forward at every step."""
    model, params = tiny_model
    prompt = [1, 7, 42, 99, 3]
    engine = ContinuousBatchingEngine(model, params, max_slots=2,
                                      max_seq=64,
                                      prefill_buckets=(8, 16))
    req = engine.generate([prompt],
                          SamplingParams(max_tokens=8))[0]
    assert len(req.output) == 8

    # uncached greedy reference
    seq = list(prompt)
    expect = []
    for _ in range(8):
        logits = model.apply(params, jnp.asarray([seq], jnp.int32))
        tok = int(jnp.argmax(logits[0, -1]))
        expect.append(tok)
        seq.append(tok)
    assert req.output == expect


def test_continuous_batching_multiple_requests(tiny_model):
    model, params = tiny_model
    engine = ContinuousBatchingEngine(model, params, max_slots=4,
                                      max_seq=64, prefill_buckets=(8, 16))
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]  # > max_slots
    reqs = engine.generate(prompts, SamplingParams(max_tokens=5))
    assert all(len(r.output) == 5 for r in reqs)
    assert all(r.finish_reason == "length" for r in reqs)
    assert engine.stats["requests"] == 6
    # batched decode: fewer decode steps than 6 requests x 4 tokens
    assert engine.stats["decode_steps"] < 6 * 5


def test_batched_results_match_single_results(tiny_model):
    """Continuous batching must not change greedy outputs."""
    model, params = tiny_model
    prompts = [[5, 6, 7], [200, 201], [50, 51, 52, 53]]
    solo = []
    for p in prompts:
        eng = ContinuousBatchingEngine(model, params, max_slots=1,
                                       max_seq=64, prefill_buckets=(8,))
        solo.append(eng.generate([p], SamplingParams(max_tokens=6))[0]
                    .output)
    eng = ContinuousBatchingEngine(model, params, max_slots=4,
                                   max_seq=64, prefill_buckets=(8,))
    batched = [r.output for r in
               eng.generate(prompts, SamplingParams(max_tokens=6))]
    assert batched == solo


def test_streaming_and_ttft(tiny_model):
    model, params = tiny_model
    engine = ContinuousBatchingEngine(model, params, max_slots=2,
                                      max_seq=64, prefill_buckets=(8,))
    req = engine.submit([1, 2, 3], SamplingParams(max_tokens=4))
    got = []
    t = threading.Thread(target=lambda: got.extend(req.iter_tokens()))
    t.start()
    while engine.has_work():
        engine.step()
    t.join(timeout=10)
    assert got == req.output
    assert req.ttft_s is not None and req.ttft_s >= 0


def test_temperature_sampling_differs(tiny_model):
    model, params = tiny_model
    engine = ContinuousBatchingEngine(model, params, max_slots=2,
                                      max_seq=64, prefill_buckets=(8,))
    r1 = engine.generate([[1, 2, 3]],
                         SamplingParams(max_tokens=16,
                                        temperature=2.0))[0]
    r2 = engine.generate([[1, 2, 3]],
                         SamplingParams(max_tokens=16,
                                        temperature=2.0))[0]
    assert r1.output != r2.output  # different rng draws


def test_stop_tokens(tiny_model):
    model, params = tiny_model
    engine = ContinuousBatchingEngine(model, params, max_slots=1,
                                      max_seq=64, prefill_buckets=(8,))
    probe = engine.generate([[9, 8, 7]],
                            SamplingParams(max_tokens=6))[0]
    stop_tok = probe.output[2]
    req = engine.generate([[9, 8, 7]],
                          SamplingParams(max_tokens=6,
                                         stop_token_ids=(stop_tok,)))[0]
    assert req.finish_reason == "stop"
    assert req.output[-1] == stop_tok
    assert len(req.output) == 3


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("hello TPU")
    assert ids[0] == ByteTokenizer.BOS
    assert tok.decode(ids) == "hello TPU"


@pytest.mark.filterwarnings(      # the loop thread dies loudly, by design
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_llm_serve_app_then_a_dead_engine(ray_start_regular):
    """The app answers through the handle; then a step that raises (on
    the chip: a program that does not compile) must not leave callers
    waiting on a thread that is gone — the request it carried fails with
    the cause, later ones fail at once, and both surface through the
    Serve handle as exceptions in bounded time."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import worker
    try:
        app = build_llm_app(LLMConfig(max_slots=2, max_seq=128))
        handle = serve.run(app)
        out = handle.remote({"prompt": "hi", "max_tokens": 4}).result(
            timeout=120)
        assert out["usage"]["completion_tokens"] == 4
        assert out["finish_reason"] == "length"
        assert isinstance(out["text"], str)
        stats = handle.stats.remote().result()
        assert stats["requests"] == 1

        controller = ray_tpu.get_actor("serve_controller")
        rep = ray_tpu.get(controller.get_replicas.remote(
            "llama-debug"))["replicas"][0]
        server = worker.global_runtime()._actor_executors[
            rep._actor_id].instance._callable

        def broken(*args, **kwargs):
            raise RuntimeError("kernel does not lower")

        server.engine._prefill = broken
        t0 = time.monotonic()
        with pytest.raises(Exception, match="kernel does not lower"):
            handle.remote({"prompt": "x" * 20, "max_tokens": 4}).result(
                timeout=60)
        server._thread.join(10)
        assert not server._thread.is_alive()
        with pytest.raises(Exception, match="engine loop died"):
            list(handle.options(stream=True).remote(
                {"prompt": "abc", "max_tokens": 4, "stream": True}))
        with pytest.raises(Exception, match="engine loop died"):
            handle.remote({"prompt": "abc", "max_tokens": 4}).result(
                timeout=60)
        assert time.monotonic() - t0 < 30
        assert not server.engine.waiting
    finally:
        serve.shutdown()


def test_prefix_router_affinity_and_balance():
    from ray_tpu.llm.prefix_router import PrefixAwareRouter

    router = PrefixAwareRouter(4, block_size=4)
    prompt_a = list(range(100, 116))
    r1 = router.route(prompt_a)
    router.on_finished(r1)
    # same prefix → same replica
    assert router.route(prompt_a + [1, 2]) == r1
    router.on_finished(r1)
    # distinct prompts spread across replicas
    seen = set()
    for i in range(40):
        prompt = [i * 1000 + j for j in range(16)]
        r = router.route(prompt)
        seen.add(r)
        router.on_finished(r)
    assert len(seen) >= 3


def test_prefill_decode_disagg_matches_colocated(tiny_model, ray_start_regular):
    """1p1d disaggregated serving produces the same greedy tokens as a
    colocated engine."""
    from ray_tpu import serve
    from ray_tpu.llm.disagg import build_pd_disagg_app
    from ray_tpu.llm.serving import LLMConfig

    model, params = tiny_model
    prompt_ids = [1, 7, 42, 99, 3]
    colocated = ContinuousBatchingEngine(model, params, max_slots=1,
                                         max_seq=128,
                                         prefill_buckets=(8,))
    expect = colocated.generate([prompt_ids],
                                SamplingParams(max_tokens=6))[0].output

    try:
        app = build_pd_disagg_app(LLMConfig(max_slots=2, max_seq=128))
        handle = serve.run(app)
        out = handle.remote({"prompt": prompt_ids,
                             "max_tokens": 6}).result(timeout=300)
        assert out["token_ids"] == expect
        assert out["finish_reason"] == "length"
    finally:
        serve.shutdown()
