"""Driving the system under test through Serve: handle -> replica ->
``LLMServer`` -> ``ContinuousBatchingEngine``, from the process that owns
the chip.

Everything here is the BENCHMARK's side: clients, their clocks, warm-up
by shape, and the correctness check. From the program it takes the
deployment class, the handle, the engine's public ``stats`` and the
model's public serving methods.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from benchmark.lib.records import RequestRecord

CHUNK_TIMEOUT_S = 600.0       # a first call may compile


def start(model_config, *, model_id: str, engine: Dict, seed: int):
    """``serve.run`` one replica of the engine; returns the handle."""
    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMConfig

    from benchmark.lib.bench_server import BenchLLMServer

    config = LLMConfig(
        model_id=model_id, model_config=model_config,
        max_slots=engine["max_slots"], max_seq=engine["max_seq"],
        block_size=engine.get("block_size"), num_replicas=1,
        max_ongoing_requests=engine.get("max_ongoing_requests", 256),
        seed=seed)
    # build_llm_app's four lines, with the benchmark's subclass
    dep = serve.deployment(
        BenchLLMServer, name=model_id, num_replicas=1,
        max_ongoing_requests=config.max_ongoing_requests)
    return serve.run(dep.bind(config))


def deploy_and_check(run):
    """Runtime up, one replica deployed with weights from the seed, the
    paged path checked against the reference: ``(handle, checks)``."""
    import ray_tpu

    from benchmark.lib.bench_server import SERVERS

    tr, cfg = run.traffic, run.config
    eng = tr["engine"]
    ray_tpu.init()
    run.phase("runtime_init")
    handle = start(run.builder.program_config(cfg, eng["max_seq"]),
                   model_id=run.workload.replace(".", "-"), engine=eng,
                   seed=run.jax_seed)
    run.phase("deploy_and_init_weights")
    cc = tr["correctness"]
    reference = run.builder.reference_forward(cfg)
    checks = check_logits(
        SERVERS[-1], reference, seed=run.seed,
        prompt_len=cc["prompt_len"], decode_steps=cc["decode_steps"],
        tol_rel_rms=cc["tolerance_rel_rms"])
    greedy = check_greedy(
        handle, SERVERS[-1], reference, seed=run.seed,
        vocab=cfg["vocab_size"], prompt_lens=cc["greedy"]["prompt_lens"],
        tokens=cc["greedy"]["tokens"],
        margin_rel_rms=cc["greedy"]["margin_rel_rms"])
    checks = {**checks, **greedy, "ok": checks["ok"] and greedy["ok"]}
    run.phase("correctness_check")
    return handle, checks


def engine_stats(handle) -> Dict:
    return dict(handle.stats.remote().result(timeout=CHUNK_TIMEOUT_S))


def make_prompt(seed: int, index: int, length: int, vocab: int) -> List[int]:
    """Token ids from (seed, index): distinct per request, so no prompt
    block is shared and the prefix cache never hits."""
    rng = np.random.default_rng([seed % (2**63), index])
    return [int(t) for t in rng.integers(1, vocab, length)]


def stream_request(handle, prompt: List[int], max_tokens: int,
                   rec: RequestRecord, stamps: Optional[list] = None,
                   on_token=None) -> List[int]:
    """Send one streamed request and read it to its end, stamping ``rec``
    (and every token's arrival into ``stamps``). Errors are recorded,
    not raised: a failed request counts against the cell."""
    import jax.profiler as prof

    toks: List[int] = []
    try:
        rec.prompt_tokens, rec.max_tokens = len(prompt), max_tokens
        with prof.TraceAnnotation("bench.client_send"):
            rec.sent_at = time.perf_counter()
            gen = handle.options(stream=True).remote(
                {"prompt": prompt, "max_tokens": max_tokens,
                 "stream": True})
        while True:
            try:
                with prof.TraceAnnotation("bench.wait.stream_read"):
                    chunk = gen.next(timeout=CHUNK_TIMEOUT_S)
            except StopIteration:
                break
            now = time.perf_counter()
            if "token_id" in chunk:
                if rec.first_token_at is None:
                    rec.first_token_at = now
                rec.output_tokens += 1
                rec.finished_at = now
                toks.append(chunk["token_id"])
                if stamps is not None:
                    stamps.append(now)
                if on_token is not None:
                    on_token(len(toks))
            elif chunk.get("done"):
                rec.done_at = now
                rec.finish_reason = chunk.get("finish_reason")
                rec.engine_ttft_s = chunk.get("ttft_s")
        if rec.finish_reason is None:
            rec.error = "stream ended without its final chunk"
    except Exception as e:                      # noqa: BLE001 — recorded
        rec.error = f"{type(e).__name__}: {e}"
    return toks


# ---------------------------------------------------------------------------
# warm-up by shape
# ---------------------------------------------------------------------------
class _Pacer:
    """Keeps ONE short request decoding, so the engine is always inside
    a decode step (tens of ms) when a burst is fired: the whole burst
    then lands in ``waiting`` before the next admission and prefills as
    one group."""

    def __init__(self, handle, seed: int, vocab: int, prompt_len: int,
                 tokens: int):
        self.handle, self.seed, self.vocab = handle, seed, vocab
        self.prompt_len, self.tokens = prompt_len, tokens
        self.admissions = 0
        self.remaining = 0
        self.tick = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-pacer")
        self._thread.start()

    def _run(self):
        n = 0
        while not self._stop:
            rec = RequestRecord(index=-1, due_at=time.perf_counter())

            def on_token(k):
                with self.tick:
                    if k == 1:
                        self.admissions += 1
                    self.remaining = self.tokens - k
                    self.tick.notify_all()

            stream_request(self.handle, make_prompt(
                self.seed, 900_000 + n, self.prompt_len, self.vocab),
                self.tokens, rec, on_token=on_token)
            n += 1
            if rec.error:
                raise RuntimeError(f"pacer request failed: {rec.error}")

    def wait_for_step(self, min_remaining: int = 3) -> None:
        """Return just after a decode step delivered a pacer token, with
        at least ``min_remaining`` more steps to come."""
        with self.tick:
            while True:
                self.tick.wait(timeout=CHUNK_TIMEOUT_S)
                if self.remaining >= min_remaining:
                    return

    def stop(self):
        self._stop = True
        self._thread.join(CHUNK_TIMEOUT_S)


def warm_shapes(handle, *, seed: int, vocab: int, buckets: List[int],
                group_sizes: List[int], chunked_lens: List[int],
                log=lambda m: None) -> Dict:
    """Make the engine compile (or read back) every prefill shape the
    cell's traffic can reach: ``group_sizes`` x ``buckets`` bucket
    prefills — a burst of k same-bucket prompts fired inside one decode
    step is admitted as one group, checked by the engine's own
    ``prefills`` count and retried if it split — then one request per
    chunked length. Each warm-up request generates ONE token."""
    t0 = time.perf_counter()
    smallest = min(buckets)
    pacer = _Pacer(handle, seed, vocab, smallest - 1, 16)
    counter = [800_000]
    retries = 0

    def burst(k: int, length: int) -> bool:
        before, adm0 = engine_stats(handle)["prefills"], pacer.admissions
        # fire right after a step delivered its token: the next
        # admission is a whole decode step away
        pacer.wait_for_step()
        threads = []
        for _ in range(k):
            counter[0] += 1
            rec = RequestRecord(index=-2, due_at=time.perf_counter())
            prompt = make_prompt(seed, counter[0], length, vocab)
            threads.append((rec, threading.Thread(
                target=stream_request, daemon=True,
                args=(handle, prompt, 1, rec))))
        for _, t in threads:
            t.start()
        for rec, t in threads:
            t.join(CHUNK_TIMEOUT_S)
            if rec.error or rec.output_tokens != 1:
                raise RuntimeError(f"warm-up request failed: {rec.error}")
        delta = engine_stats(handle)["prefills"] - before
        return delta == 1 + (pacer.admissions - adm0)

    try:
        for length in sorted(buckets):
            for k in sorted(group_sizes):
                for _attempt in range(4):
                    if burst(k, length):
                        break
                    retries += 1
                else:
                    log(f"warm-up: group of {k} at bucket {length} never "
                        f"prefilled as one")
        # chunked prefill: one at a time, n prompt tokens each
        for n in chunked_lens:
            counter[0] += 1
            rec = RequestRecord(index=-3, due_at=time.perf_counter())
            stream_request(handle, make_prompt(seed, counter[0], n, vocab),
                           1, rec)
            if rec.error:
                raise RuntimeError(f"warm-up request failed: {rec.error}")
    finally:
        pacer.stop()
    return {"seconds": time.perf_counter() - t0, "split_retries": retries}


# ---------------------------------------------------------------------------
# correctness: the paged serving path against the float32 reference
# ---------------------------------------------------------------------------
def check_logits(server, reference_forward, *, seed: int, prompt_len: int,
                 decode_steps: int, tol_rel_rms: float) -> Dict:
    """Two seeded sequences: prefill ``prompt_len`` tokens into a small
    paged pool through the model's serving methods, then ``decode_steps``
    paged decode steps fed the sequence's own next tokens; the logits of
    those positions against the reference's full forward over
    ``prompt_len + decode_steps`` tokens.

    The measure is the RMS of the difference over the RMS of the
    reference logits, which is stable where a max over 2*8*V entries is
    not. Tolerance and its reason are in the traffic file."""
    import jax
    import jax.numpy as jnp

    model, params = server.model, server.engine.params
    cfg = model.cfg
    bs = server.engine.block_size
    total = prompt_len + decode_steps
    rng = np.random.default_rng([seed % (2**63), 777])
    seqs = rng.integers(1, cfg.vocab_size, (2, total)).astype(np.int32)
    nb_slot = -(-total // bs)
    n_blocks = 2 * nb_slot

    @jax.jit
    def prefill(params, tokens):
        small = model.init_kv_cache(2, nb_slot * bs)
        _, small = model.forward_step(params, tokens, small,
                                      jnp.zeros((2,), jnp.int32))
        pool = model.init_kv_pool(n_blocks + 1, bs)
        L = small["k"].shape[0]
        def to_blocks(x):
            return x.reshape(L, n_blocks, bs, *x.shape[3:])
        ids = jnp.arange(n_blocks)
        return {"k": pool["k"].at[:, ids].set(to_blocks(small["k"])),
                "v": pool["v"].at[:, ids].set(to_blocks(small["v"]))}

    decode = jax.jit(model.decode_step_paged)
    padded = np.zeros((2, nb_slot * bs), np.int32)
    padded[:, :prompt_len] = seqs[:, :prompt_len]
    pool = prefill(params, jnp.asarray(padded))
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(2, nb_slot)
    got = []
    for i in range(decode_steps):
        pos = prompt_len + i
        logits, pool = decode(params, jnp.asarray(seqs[:, pos]), pool,
                              tables, jnp.full((2,), pos, jnp.int32))
        got.append(logits)
    got = jnp.stack(got, axis=1).astype(jnp.float32)      # [2, steps, V]
    want = jax.jit(reference_forward)(params, jnp.asarray(seqs))[
        :, prompt_len:total]
    diff = got - want
    rel_rms = float(jnp.sqrt(jnp.mean(diff ** 2) / jnp.mean(want ** 2)))
    max_abs = float(jnp.max(jnp.abs(diff)))
    argmax_same = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1)))
    finite = bool(jnp.all(jnp.isfinite(got)))
    return {"ok": finite and rel_rms <= tol_rel_rms,
            "logits_rel_rms": rel_rms, "logits_max_abs_diff": max_abs,
            "argmax_agreement": argmax_same, "tolerance_rel_rms": tol_rel_rms,
            "positions": 2 * decode_steps}


def token_gaps(want: np.ndarray, toks: List[int]) -> List[float]:
    """How far each chosen token lies under the reference's first, in
    units of the RMS of the reference's logits at that position
    (``want [n, V]``); 0 where the token IS the reference's first."""
    rms = np.sqrt(np.mean(want.astype(np.float64) ** 2, axis=-1))
    chosen = want[np.arange(len(toks)), np.asarray(toks)]
    return [float(g) for g in (want.max(-1) - chosen) / rms]


def check_greedy(handle, server, reference_forward, *, seed: int, vocab: int,
                 prompt_lens: List[int], tokens: int,
                 margin_rel_rms: float) -> Dict:
    """One greedy request per prompt length THROUGH THE HANDLE: the
    engine's own prefill (bucket or chunked), insert, sample and decode
    programs and the stream back to the client, which ``check_logits``
    (it jits the model's methods itself) never runs.

    The reference is fed the prompt and the tokens the engine streamed
    (teacher forcing: one forward, and an early near-tie cannot derail
    the rest) and has to rank every streamed token first, or within
    ``margin_rel_rms`` x the RMS of its logits at that position of its
    first. bf16 compute moves a logit by ~0.015 RMS, so two candidates
    closer than that are a tie either side may win (the logits check's
    argmax agreement read 0.875 in 5 of 38 chip runs, PR 23); a token
    from a wrong program is a random one, ~4 RMS below the first."""
    import jax
    import jax.numpy as jnp

    params = server.engine.params
    worst, top1, streamed, errors = 0.0, 0, 0, []
    for k, plen in enumerate(prompt_lens):
        prompt = make_prompt(seed, 600_000 + k, int(plen), vocab)
        rec = RequestRecord(index=-4, due_at=time.perf_counter())
        toks = stream_request(handle, prompt, tokens, rec)
        if rec.error or len(toks) != tokens:
            errors.append(f"prompt {plen}: {rec.error or len(toks)}")
            continue
        # logits at position plen-1+i predict streamed token i
        want = jax.jit(lambda p, t, a=int(plen) - 1: reference_forward(
            p, t)[0, a:a + tokens])(
                params, jnp.asarray([prompt + toks], jnp.int32))
        gaps = token_gaps(np.asarray(want), toks)
        worst = max(worst, max(gaps))
        top1 += sum(g == 0.0 for g in gaps)
        streamed += tokens
    return {"ok": not errors and streamed > 0 and worst <= margin_rel_rms,
            "greedy_errors": errors, "greedy_tokens": streamed,
            "greedy_top1": top1, "greedy_worst_gap_rel_rms": worst,
            "greedy_margin_rel_rms": margin_rel_rms}
