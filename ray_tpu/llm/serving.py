"""LLM serving on ray_tpu.serve.

Reference: `python/ray/llm` — `build_openai_app` (`serve/builders/`),
`LLMConfig` (`serve/configs/server_models.py:159`), vLLM engine
deployments (`deployments/llm/vllm/vllm_models.py`). Here the engine is
the in-tree TPU continuous-batching engine; the deployment runs it on a
background thread and requests stream through per-request queues.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Dict, List, Optional

import jax

from ray_tpu import serve
from ray_tpu._private import worker
from ray_tpu.llm.engine import (ContinuousBatchingEngine, SamplingParams)
from ray_tpu.llm.tokenizer import ByteTokenizer, load_tokenizer


REQUEST_TIMEOUT_S = 300.0    # a unary request's whole generation


def _produced(req, index: int, sampled: int) -> worker.SampledItem:
    """A chunk, or a run of them, that holds ``sampled`` sampled items
    (the first at ``index``) on its replica thread: the span
    ``serve.stream.produce`` and the request's cells. ``step`` is the
    decode step that delivered the newest of its tokens: the number on
    that step's ``engine.deliver`` span. The span lies between two waits
    for the engine and around none."""
    span = jax.profiler.TraceAnnotation("serve.stream.produce", request=req.id,
                                        index=index, step=req.step)
    return worker.SampledItem(req, span, sampled)


_UNSAMPLED = contextlib.nullcontext()   # no clock, no span


@dataclasses.dataclass
class LLMConfig:
    model_id: str = "llama-debug"
    # LlamaConfig or MoEConfig (an expert model is served by the same
    # path: ``models.model_for`` picks its class); debug Llama if None
    model_config: Optional[Any] = None
    tokenizer: Optional[str] = None          # None -> ByteTokenizer
    max_slots: int = 8
    max_seq: int = 512
    num_replicas: int = 1
    max_ongoing_requests: int = 64
    seed: int = 0
    # paged KV pool (reference: vLLM cache config surface,
    # `vllm_models.py:126-207`): block granularity and total pool size;
    # num_blocks=None sizes the pool to max_slots * max_seq
    block_size: Optional[int] = None   # None -> engine default (32)
    num_blocks: Optional[int] = None


class LLMServer:
    """Serve deployment class hosting one engine per replica."""

    def __init__(self, config: LLMConfig):
        from ray_tpu.models import LlamaConfig, model_for

        self.config = config
        cfg = config.model_config or LlamaConfig.debug(
            vocab_size=512, max_seq_len=config.max_seq)
        self.model = model_for(cfg)
        # one program, not one per tensor: eager init compiles ~30 small
        # programs and holds each tensor twice (normal, then scaled).
        # Drawn AND cast to what the engine stores in that one program,
        # so the float32 set never stands whole beside the bf16 one
        params = jax.jit(lambda key: self.model.serving_params(
            self.model.init(key)))(jax.random.key(config.seed))
        self.tokenizer = (load_tokenizer(config.tokenizer)
                          if config.tokenizer else ByteTokenizer())
        self.engine = ContinuousBatchingEngine(
            self.model, params, max_slots=config.max_slots,
            max_seq=config.max_seq, block_size=config.block_size,
            num_blocks=config.num_blocks)
        self._stats_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self.engine.run_forever, args=(self._stop,), daemon=True)
        self._thread.start()

    def _parse(self, request: Dict[str, Any]):
        prompt = request.get("prompt", "")
        sampling = SamplingParams(
            max_tokens=int(request.get("max_tokens", 32)),
            temperature=float(request.get("temperature", 0.0)),
            top_k=int(request.get("top_k", 0)),
            stop_token_ids=(self.tokenizer.EOS,) if isinstance(
                self.tokenizer, ByteTokenizer) else ())
        ids = (prompt if isinstance(prompt, list)
               else self.tokenizer.encode(prompt))
        return ids, sampling

    def stream(self, request: Dict[str, Any]):
        """Streaming completions: one chunk per generated token as the
        engine produces it (reference: ray.llm streaming through Serve;
        the TTFT the serving bench measures is only real if the first
        token can leave the replica before generation completes).

        A replica thread that has fallen behind its request takes every
        token that waits in one go (``Request.iter_runs``) and yields
        their chunks, the same dicts, together as ONE ``serve.ChunkRun``:
        what the path to the client costs, it costs an object. A token
        that waited alone is the bare dict, and the handle's caller
        reads one chunk a ``next`` either way.

        Chunks 0, 16, 32 ... by their index are the stream's sampled
        items (docs/serving.md, "The stream path"): each, or the run
        that holds it, is timed from its tokens taken off the request's
        stream to the consumer of this generator asking for the next,
        which is the runtime having stored and reported this one."""
        ids, sampling = self._parse(request)
        req = self.engine.submit(ids, sampling)
        head = {"id": f"cmpl-{req.id}", "model": self.config.model_id}
        decode = self.tokenizer.decode
        index = 0
        for tokens, ended in req.iter_runs():
            count = len(tokens) + ended
            sampled = worker.sampled_items(index, count)
            with (_produced(req, worker.first_sampled(index), sampled)
                  if sampled else _UNSAMPLED):
                chunks = [{**head, "delta": decode([tok]),
                           "token_id": int(tok), "index": i}
                          for i, tok in enumerate(tokens, index)]
                if req.unmasked_at is not None:
                    # a block-diffusion model's: the denoising pass of
                    # its block that placed the token
                    for chunk in chunks:
                        chunk["pass"] = req.unmasked_at[chunk["index"]]
                if ended:
                    chunks.append({
                        **head, "finish_reason": req.finish_reason,
                        "done": True,
                        "usage": {"prompt_tokens": len(ids),
                                  "completion_tokens": len(req.output)},
                        "ttft_s": req.ttft_s})
                yield chunks[0] if count == 1 else serve.ChunkRun(chunks)
            index += count

    def __call__(self, request: Dict[str, Any]):
        """OpenAI-completions-shaped request/response; ``stream: true``
        returns a generator (chunk-per-token through Serve streaming)."""
        if isinstance(request, dict) and request.get("stream") is True:
            return self.stream(request)
        ids, sampling = self._parse(request)
        req = self.engine.submit(ids, sampling)
        if not req.done.wait(timeout=REQUEST_TIMEOUT_S):
            raise TimeoutError(
                f"request {req.id} not finished after "
                f"{REQUEST_TIMEOUT_S:.0f}s ({len(req.output)} tokens)")
        req.raise_if_failed()
        text = self.tokenizer.decode(req.output)
        return {
            "id": f"cmpl-{req.id}",
            "model": self.config.model_id,
            "text": text,
            "token_ids": list(req.output),
            "finish_reason": req.finish_reason,
            "usage": {"prompt_tokens": len(ids),
                      "completion_tokens": len(req.output)},
            "ttft_s": req.ttft_s,
        }

    def stats(self) -> Dict[str, Any]:
        """The engine's counters and, merged into them, what this
        process's runtime counts of its streaming generators
        (``Runtime.generator_stats``; zeros where the process hosts no
        stream's state): one flat dict, every key there from
        construction (docs/serving.md). Asking reads an expert model's
        load back from the device and sums the streams' cells; one
        asker at a time, so no snapshot reads a count going down."""
        rt = worker.global_runtime()
        with self._stats_lock:
            streams = (rt.generator_stats() if isinstance(rt, worker.Runtime)
                       else worker.NO_STREAMS)
            return {**self.engine.stats, **streams}

    def queue_depth(self) -> int:
        """Engine backlog beyond the decode slots: requests submitted
        but still waiting for admission. The serve replica reports this
        with its metrics push (serve/replica.py), so routers and the
        autoscaler see engine pressure, not just request counts."""
        return len(self.engine.waiting)

    def __del__(self):
        try:
            self._stop.set()
        except Exception:
            pass


def build_llm_app(config: LLMConfig) -> serve.Application:
    """`build_openai_app` equivalent: one autoscalable LLM deployment."""
    dep = serve.deployment(
        LLMServer, name=config.model_id,
        num_replicas=config.num_replicas,
        max_ongoing_requests=config.max_ongoing_requests)
    return dep.bind(config)
