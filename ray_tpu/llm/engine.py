"""TPU continuous-batching inference engine with a paged KV cache.

Reference capability: ray.llm serves via the vLLM engine (outside the
reference tree, `llm/_internal/serve/deployments/llm/vllm/`); this engine
is the in-tree TPU-native equivalent (BASELINE.md config 5):

- PAGED KV cache: one block pool ``[L, num_blocks, bs, Hkv, D]`` in HBM
  shared by all slots through per-slot block tables (PAPERS.md paged
  attention; `llm/paged_cache.py` owns the host-side pool), so HBM holds
  ragged sequences without per-slot max_seq reservations;
- PREFIX REUSE: full prompt blocks are content-hashed; identical
  prefixes across requests (and across time — freed blocks stay
  reusable until reallocated) share physical blocks AND skip their
  prefill FLOPs via a suffix-prefill that attends over the cached
  prefix (`LlamaModel.prefill_with_prefix`);
- requests admitted into free slots at any time (continuous batching —
  decode never drains to admit); pool exhaustion mid-decode PREEMPTS
  the youngest slot by recompute (blocks freed, request requeued with
  its generated tokens folded into the prompt), like vLLM's
  recompute-preemption;
- prefill at bucketed lengths (static shapes → one jit specialization
  per bucket, no recompilation churn), scattered into pool blocks;
- decode is ONE jitted step for all slots every iteration (inactive
  slots masked), block tables riding along as a tiny int32 array; the
  sampler is that program's tail and does only what the batch's
  sampling parameters ask for (a greedy batch: one argmax), so only B
  int32s return to host per step. The pool
  is DONATED to that step and lives through it whole and in place: the
  program carries it through its layer scan as one stack addressed by
  layer (block ``p`` of layer ``l`` is block ``l*NB + p``), writes each
  slot's new row a layer into it and hands it back aliased — nothing
  pool-sized is copied or sliced (docs/serving.md);
- a model whose layers are of two KINDS (full and sliding-window
  attention: ``model.layer_kinds``) gets a block pool and a table a
  kind: full layers ``max_seq`` rows a slot, sliding layers what a
  window, a tail block and one prefill chunk need, and a sliding block
  is freed as the slot's window leaves it (``llm/paged_cache.py``,
  docs/serving.md). A model with one kind is served as it always was:
  the same pool, tables, counters and programs;
- a pool whose block is NARROW (one K/V head: 8 KB) lays a slot's blocks
  in aligned runs, and the paged kernel copies a run as one page
  (``kv_run``; docs/serving.md, "Blocks and runs"): blocks, tables and
  counters stay in blocks of ``block_size`` rows;
- an EVA model (``model.eva``: an exact window that resets, chunk
  summaries of everything before it) gets a pool and a table a PART:
  the summary part grows one block per ``block_size * chunk`` positions,
  the exact part holds the slot's current window and gives ALL its
  blocks back when the slot crosses into the next (``llm/paged_cache.py``,
  docs/serving.md);
- a model with a RECURRENT STATE (``model.recurrent``: state-space
  layers among attention layers) gets, beside the block pool and the
  tables of its attention layers, a state ROW A SLOT of fixed size
  (``model.state_row_shapes``), in the same tree as the pool: a prefill
  stops each row's recurrence at that row's own length, a chunked
  prefill starts each chunk from the state the chunk before left,
  activation writes the slot's row, the decode step rewrites every
  slot's row in place, preemption drops it (the re-prefill rebuilds it)
  and a prefix hit is REFUSED: the shared pages would come without the
  state at their end (docs/serving.md, "Recurrent state");
- a model that generates BY DIFFUSION OVER BLOCKS (``model.cfg.
  block_length`` > 1) is served by the same loop: a step is a PASS over
  every slot's current block, whose yield is a count a slot (0 or a
  whole block), decided on the device by the pass's own sampler and
  unmask rule; the block a slot has just finished rides the next
  block's first pass, clean, and leaves its K/V rows there (no pass
  places no token); a prefill samples nothing and hands the loop a first
  block (docs/serving.md, "Generation by diffusion over blocks"). The
  one-token models are the case of a block of one, and their programs
  are the ones they were;
- the parameters are held in the dtype the programs compute in: the
  matmul weights cast once at construction (``model.serving_params``),
  not by every program that reads them; norms and an expert model's
  router stay float32 (docs/serving.md);
- per-request TTFT / throughput stats (the reference's
  `release/llm_tests/serve/benchmark/load_test.py` metrics);
- the loop accounts for itself: every part of ``step()`` runs inside one
  of eight sibling PHASES (``_Phase``), each a span on the profiler's
  clock and a seconds counter in ``stats``; and for the device, with no
  profiler: where it meets the device it asks ``jax.Array.is_ready()``
  who was ahead, and counts the seconds the device stood starved and
  the steps whose pace the device set (docs/serving.md).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import queue
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.llm.paged_cache import (BlockPool, SlotAllocation,
                                     WindowAllocation, allocate_slot,
                                     ensure_capacity, eva_window_block,
                                     first_window_block, seal_prompt_blocks,
                                     seal_window_blocks, slide_window,
                                     window_blocks_per_slot)
from ray_tpu.models.llama import FULL, SLIDING


class EngineDeadError(RuntimeError):
    """The engine loop died (``__cause__`` is what killed it): every
    request it held has failed and it accepts no more."""


@dataclasses.dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0                 # 0 = no top-k
    stop_token_ids: tuple = ()
    seed: int = 0


class Request:
    _ids = itertools.count()

    def __init__(self, prompt_tokens: List[int], sampling: SamplingParams,
                 readers: Optional["_StreamReaders"] = None):
        self.id = next(Request._ids)
        self.prompt = list(prompt_tokens)
        self.sampling = sampling
        self.output: List[int] = []
        # a block-diffusion engine's: for each token of ``output``, the
        # denoising pass of its block that placed it (1 the first); None
        # on every other engine. Written before the token is streamed
        self.unmasked_at: Optional[List[int]] = None
        # ``(token, decode step that delivered it)``; ``(None, step)``
        # ends the stream
        self.stream: "queue.Queue" = queue.Queue()
        # the reading thread's account of this stream (docs/serving.md,
        # "The stream path"), written by that ONE thread and summed by
        # the engine's ``stats`` while ``readers`` holds the request:
        # items taken off ``stream``, the step that delivered the newest
        # of them, and what ``LLMServer.stream`` timed of its sampled
        # items (integer ns: the engine's sums are exact in any order)
        self._readers = readers
        self.takes = 0
        self.step = 0
        self.timed_ns = 0
        self.timed_items = 0
        self.submitted_at = time.perf_counter()
        self.queued_at = self.submitted_at   # reset when a preemption requeues
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.done = threading.Event()
        self.finish_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.preemptions = 0

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    def cache_tokens(self) -> List[int]:
        """Tokens whose K/V must be cached before the next decode step —
        the prompt plus everything generated so far (non-empty output
        only after a preemption re-admission)."""
        return self.prompt + self.output

    def iter_runs(self):
        """Stream tokens as they are generated, a RUN at a time:
        ``(tokens, ended)``. The reader waits for one item and takes
        with it, under the one acquisition of the stream's lock,
        everything else that waits: a reader that keeps up gets runs of
        one, a reader that has fallen behind gets what it is behind by.
        ``ended`` says the run took the stream's end marker (``tokens``
        may then be empty); an engine that died under the request ends
        the stream by raising, after the tokens that came before. The
        request is in the engine's ``stream_takes`` sum from here until
        the stream has been READ to its end (or the reader gives up),
        which with a backlog is long after its slot was released."""
        readers = self._readers
        if readers is not None:
            readers.begin(self)
        stream = self.stream
        try:
            while True:
                with stream.not_empty:
                    while not stream.queue:
                        stream.not_empty.wait()
                    run = list(stream.queue)
                    stream.queue.clear()
                self.takes += len(run)
                self.step = run[-1][1]
                tokens = [tok for tok, _ in run]
                if tokens[-1] is not None:
                    yield tokens, False
                    continue
                tokens.pop()
                if tokens and self.error is not None:
                    yield tokens, False
                self.raise_if_failed()
                yield tokens, True
                return
        finally:
            if readers is not None:
                readers.end(self)

    def iter_tokens(self):
        """``iter_runs``, a token at a time."""
        for tokens, _ in self.iter_runs():
            yield from tokens

    def fail(self, err: BaseException, step: int = 0) -> bool:
        """Fail the request; True if that put the stream's end marker."""
        if self.done.is_set():
            return False
        self.error = err
        self.finish_reason = "error"
        self.finished_at = time.perf_counter()
        self.stream.put((None, step))
        self.done.set()
        return True

    def raise_if_failed(self) -> None:
        if self.error is not None:
            raise EngineDeadError(
                f"engine loop died: {self.error!r}") from self.error


class _StreamReaders:
    """The requests whose streams are being read, and what the readers
    of the ended ones counted. A reading thread writes its cells on the
    ``Request`` with no lock and takes this one twice a stream; ``sums``
    adds the live cells to the ended streams' totals when ``stats`` is
    asked."""

    def __init__(self):
        self._lock = threading.Lock()
        self._live: set = set()
        self._takes = self._timed_ns = self._timed_items = 0

    def begin(self, req: Request) -> None:
        with self._lock:
            self._live.add(req)

    def end(self, req: Request) -> None:
        with self._lock:
            self._live.discard(req)
            self._takes += req.takes
            self._timed_ns += req.timed_ns
            self._timed_items += req.timed_items
            # a second reading of the same request starts from nothing
            req.takes = req.timed_ns = req.timed_items = 0

    def sums(self) -> Dict[str, Any]:
        with self._lock:
            live = list(self._live)
            return {
                "stream_takes": self._takes + sum(r.takes for r in live),
                "stream_produce_s": 1e-9 * (self._timed_ns + sum(
                    r.timed_ns for r in live)),
                "stream_items_timed_produce": self._timed_items + sum(
                    r.timed_items for r in live)}


class _Phase:
    """One phase of the engine loop, timed once for two readers: a
    ``jax.profiler.TraceAnnotation`` (a span on the device trace's
    clock; a flag check while no profiler runs) and the elapsed seconds
    added to ``engine.stats[key]``. Phases are siblings that tile
    ``step()``; none encloses another (the gap attribution gives a
    device gap to the ONE span that covers most of it). ``waits`` marks
    a phase that blocks on the device or runs under its program: its
    thread CPU time is kept out of ``stats["cpu_host_s"]``."""

    __slots__ = ("engine", "name", "key", "waits", "attrs", "span", "t0",
                 "t1", "c0")

    def __init__(self, engine, name: str, key: str, waits: bool = False,
                 **attrs):
        self.engine, self.name, self.key = engine, name, key
        self.waits, self.attrs = waits, attrs

    def __enter__(self):
        # the annotation's span starts when it is constructed
        self.span = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.span.__enter__()
        if self.waits:
            self.c0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        eng = self.engine
        self.t1 = time.perf_counter()
        eng._stats[self.key] += self.t1 - self.t0
        if self.waits:
            eng._cpu_waiting += time.thread_time() - self.c0
        self.span.__exit__(*exc)
        return False


class ContinuousBatchingEngine:
    def __init__(self, model, params, *, max_slots: int = 32,
                 max_seq: int = 1024,
                 prefill_buckets: tuple = (32, 64, 128, 256, 512),
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None):
        self.model = model
        # matmul weights cast to the compute dtype once, here; float32
        # arrays the caller passed are the caller's to drop
        self.params = model.serving_params(params)
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.buckets = tuple(b for b in sorted(prefill_buckets)
                             if b <= max_seq)
        if not self.buckets:
            raise ValueError(
                f"no prefill bucket fits max_seq={max_seq}: "
                f"{prefill_buckets}")
        requested = block_size
        block_size = requested if requested is not None else 32
        if self.buckets and block_size > self.buckets[0]:
            # prefill scatters whole buckets into blocks, so every
            # bucket must be block-aligned; shrink toward the smallest
            # bucket. LOUD only for an EXPLICIT request — a caller who
            # sized num_blocks for that granularity would otherwise get
            # half the KV pool silently (the default just adapts).
            if requested is not None:
                import warnings
                warnings.warn(
                    f"block_size={requested} exceeds the smallest "
                    f"prefill bucket {self.buckets[0]}; using "
                    f"{self.buckets[0]} — resize num_blocks "
                    f"accordingly", stacklevel=2)
            block_size = self.buckets[0]
        for b in self.buckets:
            if b % block_size != 0:
                raise ValueError(
                    f"prefill bucket {b} not a multiple of "
                    f"block_size {block_size}")
        self.block_size = block_size
        # An EVA model (window, chunk): the slot's main allocation is its
        # SUMMARY part, whose block of ``block_size`` rows covers that
        # many chunks of positions; ``None`` for every other model, for
        # which nothing here differs from what it always was
        self.eva = getattr(model, "eva", None)
        if self.eva is not None:
            self._check_eva_tiles()
        # A model with a recurrent state: the names of the state's parts
        # in the cache tree (() for every other model, for which nothing
        # here differs from what it always was)
        self.recurrent = bool(getattr(model, "recurrent", False))
        # Tokens a slot's block holds (1: one token a step, every model
        # but a block-diffusion one), read as ``eva`` and the window are
        self.block_length = int(getattr(model.cfg, "block_length", 1))
        if self.block_length > 1:
            self._check_block_diffusion()
        self._state_names = (tuple(model.state_row_shapes())
                             if self.recurrent else ())
        covers = block_size * (self.eva[1] if self.eva else 1)
        self.blocks_per_slot = (max_seq + covers - 1) // covers
        # "pallas" (the Mosaic kernel that reads only the live blocks)
        # or "xla" (the gather over every table): chosen by the model
        # from its configuration and the platform when the engine is built
        self.decode_attention_impl = model.paged_decode_impl()
        # Blocks the kernel copies as one page (docs/serving.md, "Blocks
        # and runs"): more than 1 where the model's block is narrow, and
        # the full kind's blocks then lie in runs. A block stays what
        # the caller sized, what a prefill scatters, what the prefix
        # index hashes and what every counter counts. The gather has no
        # page to widen, and no run outgrows a slot's table
        self.kv_run = (model.paged_run_blocks(block_size)
                       if self.decode_attention_impl == "pallas" else 1)
        while self.kv_run > self.blocks_per_slot:
            self.kv_run //= 2

        def whole_runs(n):
            # a layer's window of the device stack and a slot's table
            # hold whole runs: every layer's first block starts a run
            return -(-n // self.kv_run) * self.kv_run

        if num_blocks is None:
            num_blocks = max_slots * whole_runs(self.blocks_per_slot)
        self.num_blocks = num_blocks
        self.pool = BlockPool(num_blocks, covers, self.kv_run)

        # +1: physical block ``num_blocks`` is the SCRATCH block — every
        # padded table/scatter entry points there, so inactive slots and
        # bucket padding write garbage into scratch instead of a live
        # block, and every device index stays in-bounds (no OOB DMA for
        # the Pallas path to trip on). (Where blocks lie in runs it is
        # the first of a scratch RUN.)
        # A model with sliding-window layers: a second pool, of the
        # blocks a layer of THAT kind holds, a second table, and on the
        # device one stack with each layer's window of its kind's size
        # (``model.init_kv_pools``). ``window`` None: one kind, and
        # nothing below this line differs from what it always was.
        kinds = model.layer_kinds
        if self.recurrent and (kinds or self.eva is not None):
            raise NotImplementedError(
                "a recurrent state beside a pool a kind or a part")
        self.window: Optional[int] = (
            model.cfg.sliding_window if kinds and SLIDING in kinds else None)
        table_width = whole_runs(self.blocks_per_slot)
        if self.eva is not None:
            # the exact part: the blocks of one window a slot (and, as
            # for a sliding layer, a tail block and one prefill chunk's),
            # their table counted from the window's first block
            self.window = self.eva[0]
            self.num_window_blocks = max_slots * window_blocks_per_slot(
                self.window, block_size, self.buckets[-1])
            self.window_pool = BlockPool(self.num_window_blocks, block_size)
            self.kv = model.init_kv_pools(
                (num_blocks + 1, self.num_window_blocks + 1), block_size)
            # one array for both tables: as wide as the wider
            table_width = max(table_width, self.window // block_size + 1)
            self._tables_win = np.full((max_slots, table_width),
                                       self.num_window_blocks, np.int32)
        elif self.window is None:
            self.window_pool = self._tables_win = None
            # (a recurrent model: the state rows, one a slot, in the tree)
            self.kv = model.init_kv_pool(
                whole_runs(num_blocks + 1), block_size,
                *((max_slots,) if self.recurrent else ()))
        else:
            self._layer_kinds = np.asarray(kinds, np.int32)
            self.num_window_blocks = max_slots * window_blocks_per_slot(
                self.window, block_size, self.buckets[-1])
            self.window_pool = BlockPool(self.num_window_blocks, block_size)
            # each pool's block past its last is its kind's scratch block
            self.kv = model.init_kv_pools(
                (whole_runs(num_blocks + 1),
                 whole_runs(self.num_window_blocks + 1)), block_size)
            self._tables_win = np.full((max_slots, table_width),
                                       self.num_window_blocks, np.int32)

        self.slots: List[Optional[Request]] = [None] * max_slots
        self.allocs: List[Optional[SlotAllocation]] = [None] * max_slots
        self.offsets = np.zeros(max_slots, np.int32)   # tokens cached/slot
        self._tables = np.full((max_slots, table_width), num_blocks,
                               np.int32)
        self._admit_order: List[int] = []   # oldest-first slot ids
        # The decode step's inputs live on the device from step to step
        # and are sent again only when the host's copy changed (``None``
        # = stale): what stands between a step's read-back and the next
        # dispatch is host time the device idles in. ``_dev_tokens`` is
        # the last step's own output until a slot is activated;
        # ``_last_tokens`` is the host's copy, kept by ``_emit``. The
        # sampler's key never has a host copy: every program that
        # samples returns the next one (``_rng_key``).
        self._last_tokens = np.zeros(max_slots, np.int32)
        if self.block_length > 1:
            # a block-diffusion model's step input is each slot's BLOCK
            # STATE [3n + 2]: the block's tokens (the mask id where
            # nothing stands yet), the pass that placed each, the passes
            # the block has had; then the block BEHIND it and whether its
            # rows are still owed (``_decode_step_paged_blocks``); the
            # program hands the next one on, the host's copy follows
            # with every read-back. ``_given``: how many of a slot's
            # first block the prompt gave
            n = self.block_length
            self._last_tokens = np.zeros((max_slots, 3 * n + 2), np.int32)
            self._last_tokens[:, :n] = model.cfg.mask_token_id
            self._given = np.zeros(max_slots, np.int32)
            # [slot-passes, of them commits that stood alone, tokens
            # placed, of them by the threshold, commits that rode a
            # denoising pass], summed by the program, read by ``stats``
            self._block_counts = jnp.zeros(5, jnp.int32)
            # blocks behind a pass has room for: HALF the slots'. At
            # ``denoising_steps`` passes a block one slot in so many
            # owes a commit in a pass, at two passes a block every
            # other; every row of room is a block's rows through every
            # layer whether a slot takes it or not (PERF.md, PR 56)
            self._behind_slots = -(-max_slots // 2)
        self._dev_tokens = None
        self._dev_tables = None
        self._dev_offsets = None            # sent a step ahead, see there
        self._dev_sampling = None           # (temperatures, top-ks)
        self._sampling_asked = (False, False)   # any of each above 0
        self.waiting: "deque[Request]" = deque()
        # popped from ``waiting`` but not yet in a slot: where a failed
        # prefill finds the requests it was carrying
        self._admitting: List[Request] = []
        # (request, token) in order, ``None`` for a stream's end: what
        # ``_emit`` decided and the streams have not been handed yet
        self._undelivered: List[tuple] = []
        # (tokens on the device, active slots, the requests those rows
        # were dispatched for, dispatched a step ahead?) of the decode
        # step that is dispatched and not read yet, between two ``step()``s
        self._in_flight: Optional[tuple] = None
        # the host time at which this thread last KNEW the device's queue
        # empty and has enqueued nothing since (None: not known to be);
        # and the end of the last decode read-back if it waited for the
        # device (``_enqueued``, ``_decode_step``)
        self._device_dry_at: Optional[float] = None
        self._read_waited_at: Optional[float] = None
        self._lock = threading.Lock()
        self._readers = _StreamReaders()
        self._rng_key = jax.random.key(0)
        self.error: Optional[BaseException] = None   # set once, by run_forever

        # jitted programs ------------------------------------------------
        if self.recurrent:      # what advances the state, by the model
            self.decode_attention_impl += "+" + model.state_update_impl()
        # An expert model's FFN reports, each decode step, the rows it
        # handed to each expert: summed ON THE DEVICE by the decode
        # program itself and read only when ``stats`` is asked for, so
        # no step gains a transfer. ``_ffn_counts`` pairs that array
        # [layers, experts] with the host's count of what a dropless FFN
        # must process; the loop replaces the pair after each dispatch.
        # (A block-diffusion model's holds, third, the same program's
        # ``_block_counts``: the blocks that rode behind are rows too,
        # and only the program knows how many they were.)
        load_shape = model.ffn_load_shape()
        if load_shape is None:
            self._ffn_counts = None
        else:
            self._ffn_counts = (jnp.zeros(load_shape, jnp.int32), 0)
            # the EXPERT layers' (a model may lead with dense ones)
            self._ffn_rows_per_slot = (model.cfg.expert_top_k
                                       * load_shape[0] * self.block_length)
        # ONE program a decode step: the model's step and the sampler
        self._decode = jax.jit(
            self._decode_step_paged if self.block_length == 1
            else self._decode_step_paged_blocks, donate_argnums=(2,))
        self._prefill = jax.jit(self._prefill_impl)
        self._prefill_prefix = jax.jit(model.prefill_with_prefix)
        self._insert = jax.jit(
            self._insert_eva_impl if self.eva is not None
            else self._insert_impl if self.window is None
            else self._insert_kinds_impl, donate_argnums=(0,))
        self._gather = jax.jit(
            self._gather_eva_impl if self.eva is not None
            else self._gather_impl if self.window is None
            else self._gather_kinds_impl)
        # the prefill's first token: the decode program's sampler, alone
        self._sample = jax.jit(self._sample_impl)
        if self.recurrent:
            self._write_state = jax.jit(self._write_state_impl,
                                        donate_argnums=(0,))
            # a prompt's first chunk starts from this; the later ones
            # from what the chunk before left (``_prefill_chunk``)
            self._zero_state = model.init_state(1)
            self._chunk_state = self._zero_state

        # Every key exists from here on (another thread copies the dict
        # while the loop writes it), flat and JSON-plain; units and
        # meanings in docs/serving.md. ``t_*_s`` are the phases' wall
        # seconds (``_Phase``); ``t_step_s`` is all of ``step()`` from
        # before it takes the lock; ``cpu_host_s`` is this thread's CPU
        # time in ``step()`` outside the phases that wait for the device;
        # ``t_lock_wait_s`` is ``step()``'s wait for the lock (a float, no
        # span); ``t_now_s`` is the clock as ``stats`` was last asked.
        sparse = model.sparse_decode_plan()
        self._index_topk = sparse["index_topk"]
        state_bytes = sum(math.prod(self.kv[name].shape)
                          * self.kv[name].dtype.itemsize
                          for name in self._state_names)
        self._stats = {"requests": 0, "tokens_generated": 0,
                      "decode_steps": 0,
                      # decode steps whose batch held a row with
                      # temperature > 0 / top_k > 0: the steps whose
                      # program took the sampler's draw / its sort
                      "decode_steps_sampled": 0, "decode_steps_topk": 0,
                      # decode steps dispatched while the step before
                      # them was unread, and rows of such steps read and
                      # NOT booked: their request had ended in the step
                      # before (``_may_run_ahead``)
                      "decode_steps_ahead": 0, "decode_rows_dropped": 0,
                      # generation by diffusion over blocks (1 and zeros
                      # for every other model): a block's positions; live
                      # slots x passes; of those the passes that ran a
                      # finished block clean to keep its rows (its
                      # COMMIT) ALONE and so placed nothing (more slots
                      # owed one than the pass had room for); tokens the
                      # denoising passes placed, and of those the ones
                      # the confidence threshold let through where the
                      # pass's quota alone would not have; slot-passes
                      # that committed the block behind WHILE denoising
                      # the next (all five summed by the program, read
                      # when ``stats`` is asked); blocks whose tokens
                      # the host has handed out. ``decode_steps`` counts
                      # passes
                      "block_length": self.block_length,
                      "block_slot_passes": 0, "block_commit_passes": 0,
                      "block_tokens_unmasked": 0,
                      "block_tokens_unmasked_by_confidence": 0,
                      "block_commits_fused": 0,
                      "blocks_committed": 0,
                      "prefills": 0,
                      "prefix_prefills": 0, "prefix_tokens_reused": 0,
                      "preemptions": 0,
                      "admitted": 0, "queue_wait_s": 0.0,
                      "prefill_tokens": 0, "prefill_padded_tokens": 0,
                      "decode_kv_blocks_live": 0,
                      "decode_kv_blocks_table": 0,
                      # blocks the paged kernel copies as one page (1:
                      # a block a copy), and a gauge, read when ``stats``
                      # is asked: blocks the slots hold AHEAD of the one
                      # their next token goes to (what lying in runs
                      # costs the pool: up to ``run - 1`` a slot, and a
                      # run more while a step ahead has reserved it)
                      "kv_run_blocks": self.kv_run,
                      "kv_blocks_reserved_unfilled": 0,
                      # a model with sliding layers (0 with one kind):
                      # blocks of the slots' SLIDING-kind tables a
                      # step's attention reads, blocks that kind freed
                      # behind its windows, and a layer's capacity of
                      # each kind
                      "decode_kv_blocks_live_window": 0,
                      "kv_window_blocks_freed": 0,
                      "kv_pool_blocks_full": (
                          0 if self.eva is not None else num_blocks),
                      "kv_pool_blocks_window": (
                          0 if self.window is None or self.eva is not None
                          else self.num_window_blocks),
                      # an EVA model (0 for every other): blocks of the
                      # slots' SUMMARY tables a step's attention reads
                      # (``decode_kv_blocks_live`` then counts both
                      # parts'), blocks one row a position would read at
                      # the same offsets, a layer's capacity of each
                      # part, the times a slot crossed into its next
                      # window and gave its exact blocks back, and the
                      # summary rows written to stay (a prefill's whole
                      # chunks, a decode step's at a chunk's last row)
                      "decode_kv_blocks_live_summary": 0,
                      "decode_kv_blocks_full_equivalent": 0,
                      "kv_pool_blocks_exact": (
                          0 if self.eva is None else self.num_window_blocks),
                      "kv_pool_blocks_summary": (
                          0 if self.eva is None else num_blocks),
                      "kv_window_resets": 0,
                      "kv_summary_rows_written": 0,
                      "t_step_s": 0.0, "t_schedule_s": 0.0,
                      "t_prefill_s": 0.0, "t_host_arrays_s": 0.0,
                      "t_enqueue_s": 0.0, "t_readback_s": 0.0,
                      "t_emit_s": 0.0, "t_deliver_s": 0.0, "t_idle_s": 0.0,
                      "t_lock_wait_s": 0.0, "cpu_host_s": 0.0, "t_now_s": 0.0,
                      # the device's account, with no profiler
                      # (``_enqueued``, ``_decode_step``): the seconds the
                      # device's queue stood KNOWN to be empty before a
                      # program was handed to it (a lower bound of the
                      # device time lost to the host); decode read-backs
                      # that found their tokens not ready, and of those
                      # the steps that ran back to back with the step
                      # before them on the device, with the seconds
                      # between their read-backs' ends: the decode
                      # program's time, seen from the host
                      "t_device_starved_s": 0.0,
                      "decode_steps_waited": 0,
                      "decode_steps_device_paced": 0,
                      "t_device_paced_s": 0.0,
                      # the stream path (docs/serving.md, "The stream
                      # path"): items put on the requests' streams
                      # (tokens and end markers; this thread, or under
                      # the lock), items their readers took off them,
                      # what ``LLMServer.stream`` timed of its sampled
                      # items (the readers' cells, summed when ``stats``
                      # is asked), and the CPU seconds of the thread(s)
                      # in ``step()``, waiting phases included
                      "stream_puts": 0, "stream_takes": 0,
                      "stream_produce_s": 0.0,
                      "stream_items_timed_produce": 0,
                      "engine_thread_cpu_s": 0.0,
                      # expert models (0 / empty for a dense one): rows
                      # the expert FFN processed for live slots in decode
                      # steps, what a dropless FFN must have processed
                      # (live slots x top-k x layers, counted on the
                      # host), and the rows per [layer][expert]
                      "moe_assignments": 0, "moe_assignments_expected": 0,
                      "moe_expert_load": [],
                      # a layer that holds A SHARE of its router's experts
                      # (``MoEConfig.experts_held``): how many (0 for a
                      # dense model; all of them otherwise), the rows of
                      # ``moe_assignments`` that went to them, which are
                      # the rows computed here, and the router's groups
                      "moe_experts_held": (
                          model.cfg.held[1] if load_shape else 0),
                      "moe_assignments_held": 0,
                      "moe_router_groups": getattr(
                          model.cfg, "router_n_group", 0),
                      # learned sparse attention (0 / "" for every other
                      # model): the rows a query's attention keeps, the
                      # rows the decode steps' attention read (each live
                      # slot's ``min(length, index_topk)``, a layer;
                      # counted on the host like the live blocks), a
                      # row's index key in bytes, and what implements
                      # the decode step's attention, index scores and
                      # selection
                      "decode_kv_rows_selected": 0,
                      **sparse,
                      "decode_attention_impl": self.decode_attention_impl,
                      # what implements the decode step's three grouped
                      # matmuls ("pallas_gmm" / "ragged_dot") and their
                      # (rows, k, n) tilings, as the model resolves them
                      # from the platform and the step's shapes (a
                      # block-diffusion model's pass: the slots' blocks
                      # and the blocks behind it has room for)
                      **model.grouped_matmul_plan(
                          max_slots * self.block_length
                          + (self._behind_slots * self.block_length
                             if self.block_length > 1 else 0)),
                      # the router of an expert model's FFN ("softmax" /
                      # "sigmoid"; "" for a dense model)
                      "moe_router_kind": getattr(model.cfg, "router_kind",
                                                 ""),
                      # the K/V pool as it is held: one position's bytes
                      # a layer (both parts of a row, padding and all:
                      # ``model.kv_row_shapes``) and all its arrays' bytes
                      "kv_row_bytes": sum(
                          math.prod(row) for row in model.kv_row_shapes()
                      ) * jnp.dtype(model.kv_dtype).itemsize,
                      # K/V heads a row of the pool holds (2 where heads
                      # of 64 lanes lie packed; 1 in every other pool)
                      "kv_lane_pack": getattr(model, "kv_lane_pack", 1),
                      "kv_pool_bytes": sum(
                          math.prod(a.shape) * a.dtype.itemsize
                          for name, a in self.kv.items()
                          if name != "bases"
                          and name not in self._state_names),
                      # a model with a recurrent state (0 for every
                      # other): the layers that carry one, a slot's row
                      # over all of them and all slots' rows in bytes,
                      # rows written at activation, chunks of a chunked
                      # prefill that started from the state the chunk
                      # before left, and prefix hits not taken because
                      # the shared pages come without a state
                      "state_layers": len(
                          self.kv[self._state_names[0]])
                      if self.recurrent else 0,
                      "state_row_bytes": state_bytes // max_slots,
                      "state_bytes": state_bytes,
                      "state_rows_written": 0,
                      "state_chunks_carried": 0,
                      "prefix_hits_refused_recurrent": 0,
                      # bytes of the parameters as the engine holds them
                      "param_bytes": sum(
                          a.nbytes for a in jax.tree.leaves(self.params))}
        self._cpu_waiting = 0.0     # CPU seconds of this step's waiting phases

    @property
    def stats(self) -> Dict[str, Any]:
        """The counters (one dict, updated in place). Asking for them is
        what reads an expert model's load back from the device and sums
        the stream readers' cells."""
        self._stats.update(self._readers.sums())
        covers = self.pool.block_size
        self._stats["kv_blocks_reserved_unfilled"] = sum(
            max(0, len(alloc.blocks) - int(at) // covers - 1)
            for alloc, at in zip(self.allocs, self.offsets)
            if alloc is not None)
        if self._ffn_counts is not None:
            # one tuple, one step
            load, expected, *block_counts = self._ffn_counts
            load = np.asarray(load)
            if block_counts:
                # a block that rode behind ran its rows through the FFN
                # beside the slot's own
                expected += (int(np.asarray(block_counts[0])[4])
                             * self._ffn_rows_per_slot)
            first, n_held = self.model.cfg.held
            self._stats.update(
                moe_assignments=int(load.sum()),
                moe_assignments_expected=expected,
                moe_assignments_held=int(
                    load[:, first:first + n_held].sum()),
                moe_expert_load=load.tolist())
        if self.block_length > 1:
            self._stats.update(zip(
                ("block_slot_passes", "block_commit_passes",
                 "block_tokens_unmasked",
                 "block_tokens_unmasked_by_confidence",
                 "block_commits_fused"),
                map(int, np.asarray(self._block_counts))))
        # last: the read above may have waited for a program in flight
        self._stats["t_now_s"] = time.perf_counter()
        return self._stats

    def _check_eva_tiles(self) -> None:
        """An EVA model's window and chunk against the engine's sizes: a
        chunk's rows lie in one block and a window starts on a block.
        Every prefill (a bucket, or a chunk of the largest bucket) then
        starts on a chunk: the buckets are multiples of the block."""
        window, chunk = self.eva
        bs = self.block_size
        if bs % chunk:
            raise ValueError(
                f"block_size {bs} is no multiple of the EVA chunk {chunk}")
        if window % bs:
            raise ValueError(
                f"the EVA window {window} is no multiple of block_size {bs}")

    def _check_block_diffusion(self) -> None:
        """A block-diffusion model against the engine's sizes: a block
        never straddles a page (so a pass writes ONE page a slot, and a
        hashed page holds whole blocks: its K/V depend on nothing behind
        it), and the model's cache is plain rows of tokens."""
        n = self.block_length
        if self.block_size % n:
            raise ValueError(
                f"block_size {self.block_size} is no multiple of the "
                f"model's block_length {n}")
        if (self.eva is not None or self.recurrent
                or self.model.layer_kinds):
            raise NotImplementedError(
                "generation by diffusion over blocks beside a pool a "
                "kind, a part or a recurrent state")

    # -- jitted internals --------------------------------------------------
    def _decode_step_paged_blocks(self, params, state, pool, block_tables,
                                  offsets, temps, top_ks, key, ffn_load,
                                  counts):
        """One PASS over every slot's block as ONE program (a model with
        ``block_length`` n > 1): the model's rows, the sampler, the
        unmask rule and the block's state machine, none of which leaves
        the device. ``state`` [B, 3n + 2]: the block's tokens, the pass
        that placed each (0: the prompt's), the denoising passes it has
        had; then the block BEHIND it (at ``offset - n``), clean, and
        whether its K/V rows are still OWED. A block with a mask left is
        DENOISED: the pass's proposals that the rule keeps go in, and
        its K/V rows, computed from masked inputs, are overwritten by
        the next pass. The pass that leaves it with none FINISHES it:
        its tokens are the host's to hand out, the slot's offset moves
        on by n, the next block starts all masked and the finished one
        stands behind it, owed. The pass after that carries both (the
        model's ``behind``): the block behind runs clean once more,
        beside the next block's first denoising pass, and its rows stay
        (its COMMIT): a pass places tokens in every slot it runs.

        The call has room for ``_behind_slots`` blocks behind (the owed
        slots' first so many, compacted). A slot that owes beyond them
        commits ALONE: its own rows of this pass are the block behind's,
        nothing is denoised and nothing placed, and its next pass starts
        the next block with nothing owed, as a slot that found room
        does. A block that ENTERS clean (none does, as the host starts
        them) is finished by the pass that finds it so. Slots stand at
        different phases of their blocks in one call; an idle slot (its
        table points at the scratch block) keeps what it has and is
        owed nothing.

        Returns the next state, the pool, the next offsets, the next
        key, the expert load, ``counts`` with this pass's added, and
        what the host reads, [B, 4n + 3]: the pass that placed each of
        the block's tokens as the pass LEFT it, a flag (the block was
        finished: its tokens are the next state's block behind), then
        the next state (the host's copy of it)."""
        from ray_tpu.ops.block_diffusion import (confidence,
                                                 transfer_quotas,
                                                 unmask_step)
        cfg = self.model.cfg
        n, mask_id = self.block_length, cfg.mask_token_id
        B, room = state.shape[0], self._behind_slots
        block, placed_at, passes = (state[:, :n], state[:, n:2 * n],
                                    state[:, 2 * n])
        live = block_tables[:, 0] != self.num_blocks
        behind = state[:, 2 * n + 1:3 * n + 1]
        owed = live & (state[:, 3 * n + 1] != 0)
        with jax.named_scope("blockdiff_commit"):
            # the owed slots that find room, in the rows they find it in
            rank = jnp.cumsum(owed) - 1
            fused = owed & (rank < room)
            alone = owed & ~fused
            rows = jnp.zeros(room, jnp.int32).at[
                jnp.where(fused, rank, room)].set(jnp.arange(B), mode="drop")
            taken = jnp.arange(room) < jnp.sum(fused)
        logits, pool, extras = self.model.block_step_paged_counted(
            params, jnp.where(alone[:, None], behind, block), pool,
            block_tables, offsets - n * alone, live,
            (behind[rows], rows, taken), run=self.kv_run)
        if ffn_load is not None:
            ffn_load = ffn_load + extras["load"]
        with jax.named_scope("blockdiff_unmask"):
            # the decode program's sampler over the slots' n rows each
            x0, key = self._sample_impl(
                logits.reshape(-1, logits.shape[-1]), jnp.repeat(temps, n),
                jnp.repeat(top_ks, n), key)
            x0 = x0.reshape(block.shape)
            denoised, placed, by_confidence = unmask_step(
                block, x0, confidence(logits, x0, temps), passes,
                mask_id=mask_id,
                quotas=transfer_quotas(n, cfg.denoising_steps),
                threshold=cfg.confidence_threshold,
                dynamic=cfg.remasking == "low_confidence_dynamic")
        with jax.named_scope("blockdiff_commit"):
            runs = live & ~alone
            denoise = runs & jnp.any(block == mask_id, axis=-1)
            placed &= denoise[:, None]
            block = jnp.where(denoise[:, None], denoised, block)
            placed_at = jnp.where(placed, passes[:, None] + 1, placed_at)
            done = runs & ~jnp.any(block == mask_id, axis=-1)
            state = jnp.concatenate([
                jnp.where(done[:, None], mask_id, block),
                jnp.where(done[:, None], 0, placed_at),
                jnp.where(done, 0, passes + denoise)[:, None],
                jnp.where(done[:, None], block, behind),
                done[:, None].astype(state.dtype)], axis=-1)
            counts = counts + jnp.stack([
                jnp.sum(live), jnp.sum(alone), jnp.sum(placed),
                jnp.sum(placed & by_confidence),
                jnp.sum(fused)]).astype(counts.dtype)
            report = jnp.concatenate(
                [placed_at, done[:, None].astype(state.dtype), state],
                axis=-1)
        return (state, pool, offsets + n * done, key, ffn_load, counts,
                report)

    def _decode_step_paged(self, params, tokens, pool, block_tables, offsets,
                           temps, top_ks, key, ffn_load):
        """One decode step as ONE program: the model's step, then the
        sampler on its logits, which never leave the program. Returns
        the next tokens [B], the pool, the next key and, for an expert
        model (``ffn_load`` not None), that array with the FFN's
        per-expert rows of the LIVE slots added (an idle slot's table
        points at the scratch block)."""
        if ffn_load is None:
            logits, pool = self.model.decode_step_paged(
                params, tokens, pool, block_tables, offsets,
                run=self.kv_run)
        else:
            full = (block_tables if block_tables.ndim == 2
                    else block_tables[FULL])
            live = full[:, 0] != self.num_blocks
            logits, pool, extras = self.model.decode_step_paged_counted(
                params, tokens, pool, block_tables, offsets, live,
                run=self.kv_run)
            ffn_load = ffn_load + extras["load"]
        tokens, key = self._sample_impl(logits, temps, top_ks, key)
        return tokens, pool, key, ffn_load

    def _prefill_impl(self, params, tokens, lengths):
        """BATCHED prefill: tokens [N, Tb], lengths [N]; returns each
        request's last-valid-token logits [N, V] + a BUCKET-SIZED cache
        [L, N, Tb, Hkv, D] that admission scatters into pool blocks."""
        N, Tb = tokens.shape
        small = self.model.init_kv_cache(N, Tb)
        # a recurrence must stop at each row's own length: the padding
        # behind it would advance the state (attention does not mind:
        # the rows behind the prompt are overwritten)
        logits, small = self.model.forward_step(
            params, tokens, small, jnp.zeros((N,), jnp.int32),
            *((lengths,) if self.recurrent else ()))
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        return last, small

    def _insert_impl(self, pool, small, block_ids):
        """Scatter bucket prefill K/V [L, N, Tb, Hkv, D] into pool
        blocks. ``block_ids`` [N*nb] flat physical ids in logical order
        (pad with num_blocks = the scratch block)."""
        L, N, Tb = small["k"].shape[:3]
        bs = self.block_size
        nb = Tb // bs

        def to_blocks(x):
            # [L, N, Tb, H, D] -> [L, N*nb, bs, H, D]
            return x.reshape(L, N * nb, bs, *x.shape[3:])

        k = pool["k"].at[:, block_ids].set(to_blocks(small["k"]))
        v = pool["v"].at[:, block_ids].set(to_blocks(small["v"]))
        return dict(pool, k=k, v=v)

    def _write_state_impl(self, pool, state, slots):
        """A prefill's recurrent state ``state[name]`` [Lm, N, ...] into
        the slots' rows: row ``r`` into slot ``slots[r]`` of every
        layer, a padding row (``slots[r]`` = ``max_slots``, out of
        bounds) nowhere."""
        return dict(pool, **{
            name: pool[name].at[:, slots].set(
                state[name].astype(pool[name].dtype), mode="drop")
            for name in self._state_names})

    def _gather_impl(self, pool, block_ids):
        """Gather prefix blocks [N, Pb] -> dense [L, N, Pb*bs, Hkv, D]."""
        k = pool["k"][:, block_ids]          # [L, N, Pb, bs, Hkv, D]
        v = pool["v"][:, block_ids]
        L, N, Pb, bs = k.shape[:4]
        return (k.reshape(L, N, Pb * bs, *k.shape[4:]),
                v.reshape(L, N, Pb * bs, *v.shape[4:]))

    def _insert_kinds_impl(self, pool, small, block_ids):
        """``_insert_impl`` for a pool a kind: ``block_ids`` [kinds,
        N*nb], each kind's own physical ids (pad with that kind's
        scratch block); layer ``l`` writes at ``bases[l]`` plus its
        kind's."""
        L, N, Tb = small["k"].shape[:3]
        bs = self.block_size
        rows = (pool["bases"][:, None]
                + block_ids[self._layer_kinds]).reshape(-1)   # [L*N*nb]

        def to_blocks(x):
            return x.reshape(L * N * (Tb // bs), bs, *x.shape[3:])

        return dict(pool, k=pool["k"].at[rows].set(to_blocks(small["k"])),
                    v=pool["v"].at[rows].set(to_blocks(small["v"])))

    def _gather_kinds_impl(self, pool, block_ids):
        """``_gather_impl`` for a pool a kind: ``block_ids`` [kinds, N,
        Pb]. A sliding layer's rows behind its window are whatever its
        scratch block holds: ``prefill_with_prefix`` masks them."""
        rows = (pool["bases"][:, None, None]
                + block_ids[self._layer_kinds])               # [L, N, Pb]
        k, v = pool["k"][rows], pool["v"][rows]
        L, N, Pb, bs = k.shape[:4]
        return (k.reshape(L, N, Pb * bs, *k.shape[4:]),
                v.reshape(L, N, Pb * bs, *v.shape[4:]))

    def _insert_eva_impl(self, pool, small, block_ids, sum_blocks, sum_rows):
        """``_insert_impl`` for an EVA model's two parts: the exact rows
        by blocks (``block_ids`` [N*nb], the exact part's ids), the
        chunk summaries ``small["sk"/"sv"]`` [L, N, Tb/chunk, Hkv, D]
        row by row at ``(sum_blocks, sum_rows)`` [N*Tb/chunk] of the
        summary part (a bucket need not fill a summary block)."""
        L, N, Tb = small["k"].shape[:3]
        bs = self.block_size

        def to_blocks(x):
            return x.reshape(L, N * (Tb // bs), bs, *x.shape[3:])

        def to_rows(x):
            return x.reshape(L, -1, *x.shape[3:])

        return {
            "k": pool["k"].at[:, block_ids].set(to_blocks(small["k"])),
            "v": pool["v"].at[:, block_ids].set(to_blocks(small["v"])),
            "sk": pool["sk"].at[:, sum_blocks, sum_rows].set(
                to_rows(small["sk"])),
            "sv": pool["sv"].at[:, sum_blocks, sum_rows].set(
                to_rows(small["sv"]))}

    def _gather_eva_impl(self, pool, block_ids, sum_ids):
        """An EVA model's prefix: the window's exact blocks [N, We] and
        the slot's summary blocks [N, Ws], each dense [L, N, rows, Hkv,
        D]. Both are as long as they ever get, whatever the prompt."""
        def dense(x, ids):
            x = x[:, ids]                       # [L, N, n, bs, Hkv, D]
            return x.reshape(*x.shape[:2], -1, *x.shape[4:])

        return (dense(pool["k"], block_ids), dense(pool["v"], block_ids),
                dense(pool["sk"], sum_ids), dense(pool["sv"], sum_ids))

    def _sample_impl(self, logits, temps, top_ks, key):
        """logits [B, V] → (tokens [B], the next key), on the device.
        Only the work the batch asks for: the predicates are scalars of
        the whole batch, so each ``cond`` is a real conditional and an
        all-greedy batch costs one ``argmax``. A batch with a row of
        temperature > 0 splits the key and draws; one that also has a
        row of top-k > 0 sorts. Row by row the arithmetic does not
        depend on what the other rows asked for."""
        B, V = logits.shape
        greedy = jnp.argmax(logits, axis=-1)

        def mask_below_kth(scaled):
            # top-k with static k = full V: a row with top-k keeps what
            # reaches its k-th largest (ties too), another row all of it
            def row(s, tk):
                kth = jnp.sort(s)[V - jnp.clip(tk, 1, V)]
                return jnp.where((tk <= 0) | (s >= kth), s, -1e30)
            return jax.vmap(row)(scaled, top_ks)

        def draw(key):
            key, sub = jax.random.split(key)
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            scaled = jax.lax.cond(jnp.any(top_ks > 0), mask_below_kth,
                                  lambda s: s, scaled)
            sampled = jax.vmap(jax.random.categorical)(
                jax.random.split(sub, B), scaled)
            return jnp.where(temps <= 0.0, greedy, sampled), key

        return jax.lax.cond(jnp.any(temps > 0.0), draw,
                            lambda key: (greedy, key), key)

    # -- public API --------------------------------------------------------
    def submit(self, prompt_tokens: List[int],
               sampling: Optional[SamplingParams] = None) -> Request:
        req = Request(prompt_tokens, sampling or SamplingParams(),
                      self._readers)
        if self.block_length > 1:
            req.unmasked_at = []
        self._stats["requests"] += 1
        # deque.append is atomic — submitters never contend on the
        # engine-step lock (a step can span a whole prefill+decode)
        self.waiting.append(req)
        if self.error is not None:
            # the loop died: its sweep may have run before this append
            self._fail_all(self.error)
        return req

    def has_work(self) -> bool:
        # (a step ahead whose every request ended is still to be read)
        return (bool(self.waiting) or self._in_flight is not None
                or any(s is not None for s in self.slots))

    def step(self) -> int:
        """One engine iteration: admit+prefill, then one decode step for
        all active slots. Returns number of active slots."""
        t0 = time.perf_counter()
        with self._lock:
            # ``submit_prefilled`` and a dead loop's sweep hold the lock:
            # what ``t_step_s`` holds before the first phase
            self._stats["t_lock_wait_s"] += time.perf_counter() - t0
            c0 = time.thread_time()
            self._cpu_waiting = 0.0
            self._admit()
            active = self._decode_step()
            if not self._admit_order and not self.waiting:
                # nothing left to run: from here the device idles for
                # want of requests (``t_idle_s``'s), not for the host
                self._device_dry_at = None
            cpu = time.thread_time() - c0
            self._stats["cpu_host_s"] += cpu - self._cpu_waiting
            self._stats["engine_thread_cpu_s"] += cpu
        self._stats["t_step_s"] += time.perf_counter() - t0
        return active

    def _prefilled(self, n: int) -> int:
        """Of a context of ``n`` tokens, those a prefill caches: all of
        them, or a block-diffusion model's whole blocks (what is left
        of the prompt stands in the first block the passes fill)."""
        return n - n % self.block_length

    def _bucket_for(self, n: int) -> Optional[int]:
        for b in self.buckets:
            if n <= b:
                return b
        return None

    # -- admission ---------------------------------------------------------
    def _admit(self) -> None:
        """Admit as many waiting requests as slots AND pool blocks
        allow. Prefix-hit requests prefill one-by-one through the
        suffix path; the rest batch per bucket (one forward per
        bucket). Pool exhaustion stops admission (FIFO order held)."""
        if not self.waiting:
            return
        # a prefill would hold the last decode step's tokens up
        self._deliver_deferred()
        with _Phase(self, "engine.schedule", "t_schedule_s"):
            by_shape, singles, by_bucket = self._plan_admission()
        for (pb_pad, s_bucket), group in by_shape.items():
            self._admit_prefix_batch(pb_pad, s_bucket, group)
        for slot, req, alloc, shared_tok in singles:
            self._admit_chunked(slot, req, alloc, shared_tok)
        for bucket, group in by_bucket.items():
            self._admit_bucket(bucket, group)
        self._admitting.clear()

    def _plan_admission(self):
        """Pop what fits, give each a slot and its blocks, and group the
        admitted by prefill shape: ``(by_shape, singles, by_bucket)``."""
        now = time.perf_counter()
        free = [i for i, r in enumerate(self.slots) if r is None]
        by_bucket: Dict[int, List] = {}
        chunked_group: List = []
        while free and self.waiting:
            req = self.waiting.popleft()
            self._admitting.append(req)
            toks = req.cache_tokens()
            n = len(toks)
            covers = self.pool.block_size
            never_fits = not self.pool.holds((n + 1 + covers - 1) // covers)
            if n >= self.max_seq or never_fits:
                req.finish_reason = ("length" if req.output
                                     else "prompt_too_long")
                req.finished_at = time.perf_counter()
                req.done.set()
                self._put_end(req)
                continue
            if self.recurrent:
                # shared pages would come without the state at their
                # end: the hit is not taken, and counted
                first = self.pool.chain_hashes(toks[:min(covers, n - 1)],
                                               covers)
                self._stats["prefix_hits_refused_recurrent"] += bool(
                    first and first[0] in self.pool._by_hash)
            # +1 (a whole first block) so the first decode write never
            # needs a growth step
            alloc = allocate_slot(self.pool, toks,
                                  self._prefilled(n) + self.block_length,
                                  window_pool=self.window_pool,
                                  window=self.window,
                                  share=not self.recurrent)
            if alloc is None:
                # pool can't host it right now — put it back, stop
                self.waiting.appendleft(req)
                break
            alloc, shared_tok = alloc
            slot = free.pop(0)
            self._stats["admitted"] += 1
            self._stats["queue_wait_s"] += now - req.queued_at
            bucket = self._bucket_for(n)
            if shared_tok > 0 or bucket is None:
                # prefix hit, or context longer than the largest
                # bucket (e.g. a preempted request's regrown context):
                # CHUNKED prefill over the cached/growing prefix
                chunked_group.append((slot, req, alloc, shared_tok))
            else:
                by_bucket.setdefault(bucket, []).append(
                    (slot, req, alloc))
        # single-chunk prefix hits with identical padded shapes BATCH
        # through prefill_with_prefix's N dimension (the common wave of
        # same-prefix requests); multi-chunk contexts go one-by-one
        big = self.buckets[-1]
        by_shape: Dict[tuple, List] = {}
        singles: List = []
        for item in chunked_group:
            _, req, alloc, shared_tok = item
            suffix_len = len(req.cache_tokens()) - shared_tok
            if 0 < shared_tok and suffix_len <= big:
                pb_pad = self._pad_pow2(
                    max(shared_tok // self.block_size, 1),
                    self.blocks_per_slot)
                key = (pb_pad, self._bucket_for(suffix_len))
                by_shape.setdefault(key, []).append(item)
            else:
                singles.append(item)
        return by_shape, singles, by_bucket

    # -- the blocks of a model with two kinds of layer ----------------------
    def _slide(self, alloc: SlotAllocation, n_cached: int,
               needed_tokens: int) -> bool:
        """A model with sliding layers: the slot's blocks of THAT kind
        follow its window (``paged_cache.slide_window``): those a query
        at position ``n_cached`` no longer sees go back to the pool, and
        blocks for ``needed_tokens`` are held. True if the set of blocks
        changed. With one kind there is nothing to do."""
        if self.window_pool is None:
            return False
        w = alloc.window
        before = (w.first, len(w.blocks))
        if self.eva is None:
            self._stats["kv_window_blocks_freed"] += slide_window(
                self.window_pool, w,
                first_window_block(n_cached, self.window, self.block_size),
                needed_tokens)
        else:
            # an EVA model's exact part: nothing goes inside a window,
            # the whole window's blocks at its end
            self._stats["kv_window_resets"] += bool(slide_window(
                self.window_pool, w,
                eva_window_block(n_cached, self.window, self.block_size),
                needed_tokens))
        return before != (w.first, len(w.blocks))

    def _set_window_table(self, slot: int, alloc: SlotAllocation) -> None:
        w = alloc.window
        self._tables_win[slot] = self.num_window_blocks
        if self.eva is None:
            self._tables_win[slot, w.first:w.first + len(w.blocks)] = w.blocks
        else:       # counted from the window's first block
            held = w.blocks[:self._tables_win.shape[1]]
            self._tables_win[slot, :len(held)] = held
        self._dev_tables = None

    def _follow_window(self, slot: int, alloc: SlotAllocation,
                       at: int) -> None:
        """Before a decode step that writes a slot's token at offset
        ``at``: its sliding-kind blocks and their table follow."""
        if self._slide(alloc, at, at + 1):
            self._set_window_table(slot, alloc)

    def _block_ids(self, rows: List[tuple], n: int, n_rows: int,
                   gather: bool = False):
        """Physical ids of the logical blocks ``[lo, hi)`` of each of
        ``rows`` = [(allocation, lo, hi)], ``n`` entries a row and
        ``n_rows`` rows: [n_rows, n], or with two kinds [kinds, n_rows,
        n], each kind's own ids. An entry with no block behind it is the
        kind's scratch block (a scatter's padding lands there); for a
        ``gather`` the full kind's is block 0 as ever (the prefix's
        padding is masked by position). A sliding layer's blocks behind
        its window are such entries."""
        ids = np.full((n_rows, n), 0 if gather else self.num_blocks,
                      np.int32)
        for r, (alloc, lo, hi) in enumerate(rows):
            held = alloc.blocks[lo:hi]
            ids[r, :len(held)] = held
        if self.window_pool is None:
            return ids
        win = np.full((n_rows, n), self.num_window_blocks, np.int32)
        for r, (alloc, lo, hi) in enumerate(rows):
            win[r, :hi - lo] = alloc.window.ids(lo, hi,
                                                self.num_window_blocks)
        return np.stack([ids, win])

    def _scatter(self, small, rows: List[tuple], nb: int,
                 n_rows: int) -> None:
        """A prefill's K/V ``small`` [L, n_rows, nb*bs, ..] into the pool:
        row ``r`` into the logical blocks ``[lo, lo + nb)`` of ``rows[r]``
        = (allocation, lo, hi), what lies past ``hi`` (and the padding
        rows) into the scratch block."""
        if self.eva is None:
            self.kv = self._insert(
                self.kv, small,
                self._flat(self._block_ids(rows, nb, n_rows)))
            return
        # an EVA model: the exact part by blocks, and each chunk's
        # summary at its own row of the summary part (row j of the slot:
        # row j % bs of its summary block j // bs)
        bs = self.block_size
        per_block = bs // self.eva[1]            # chunks in an exact block
        exact = np.full((n_rows, nb), self.num_window_blocks, np.int32)
        blocks = np.full((n_rows, nb * per_block), self.num_blocks, np.int32)
        at = np.zeros((n_rows, nb * per_block), np.int32)
        for r, (alloc, lo, hi) in enumerate(rows):
            exact[r, :hi - lo] = alloc.window.ids(lo, hi,
                                                  self.num_window_blocks)
            j = lo * per_block + np.arange((hi - lo) * per_block)
            held = j // bs < len(alloc.blocks)
            blocks[r, :len(j)][held] = np.asarray(
                alloc.blocks, np.int32)[j[held] // bs]
            at[r, :len(j)] = j % bs
        self.kv = self._insert(self.kv, small, *map(self._flat,
                                                    (exact, blocks, at)))

    @staticmethod
    def _flat(ids: np.ndarray):
        """[.., rows, n] -> [.., rows*n] on the device: what the scatter
        of a group's blocks takes."""
        return jnp.asarray(ids.reshape(*ids.shape[:-2], -1))

    def _pad_pow2(self, n: int, cap: int) -> int:
        p = 1
        while p < n:
            p *= 2
        return min(p, cap)

    def _admit_bucket(self, bucket: int, group: List) -> None:
        """Batched no-prefix prefill: one forward + one pool scatter +
        one sample for the whole group."""
        bs = self.block_size
        nb = bucket // bs
        # pad the group to the next power of two so each bucket has
        # O(log max_slots) jit specializations, not one per N
        n_pad = self._pad_pow2(len(group), self.max_slots)
        with self._prefill_phase(bucket, len(group), n_pad):
            lengths = np.ones(n_pad, np.int32)
            toks = np.zeros((n_pad, bucket), np.int32)
            for row, (slot, req, alloc) in enumerate(group):
                seq = req.cache_tokens()
                seq = seq[:self._prefilled(len(seq))]
                lengths[row] = max(len(seq), 1)
                toks[row, :len(seq)] = seq
                self._slide(alloc, len(seq), len(seq) + 1)
                self._stats["prefill_tokens"] += len(seq)
            last_logits, small = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lengths))
            self._enqueued()
            self._scatter(small, [(alloc, 0, nb) for _, _, alloc in group],
                          nb, n_pad)
            self._set_state_rows(small, [slot for slot, _, _ in group], n_pad)
            self._stats["prefills"] += 1
            self._stats["prefill_padded_tokens"] += n_pad * bucket
            toks_out = self._sample_batch(
                last_logits, [req for _, req, _ in group], n_pad)
        with _Phase(self, "engine.emit", "t_emit_s"):
            now = time.perf_counter()
            for row, (slot, req, alloc) in enumerate(group):
                self._activate(slot, req, alloc, now, toks_out[row])
            self._deliver()

    def _set_state_rows(self, state, slots: List[int], n_pad: int) -> None:
        """Activation of a model with a recurrent state: what the
        prefill left, row ``r`` of ``state``, becomes slot ``slots[r]``'s
        row, so a slot never sees its last tenant's state. Nothing for
        every other model."""
        if not self.recurrent:
            return
        at = np.full(n_pad, self.max_slots, np.int32)
        at[:len(slots)] = slots
        self.kv = self._write_state(
            self.kv, {name: state[name] for name in self._state_names},
            jnp.asarray(at))
        self._stats["state_rows_written"] += len(slots)

    def _prefill_phase(self, bucket: int, n: int, n_pad: int) -> _Phase:
        """One admitted group's prefill, host work and device wait alike:
        decode is held up for all of it."""
        return _Phase(self, "engine.prefill", "t_prefill_s", waits=True,
                      bucket=bucket, n=n, n_pad=n_pad)

    def _admit_prefix_batch(self, pb_pad: int, s_bucket: int,
                            group: List) -> None:
        """Batched suffix prefill for same-shape prefix hits: one
        gather + one forward + one scatter + one sample for the wave."""
        bs = self.block_size
        nb = s_bucket // bs
        n_pad = self._pad_pow2(len(group), self.max_slots)
        with self._prefill_phase(s_bucket, len(group), n_pad):
            toks = np.zeros((n_pad, s_bucket), np.int32)
            plens = np.zeros(n_pad, np.int32)
            slens = np.ones(n_pad, np.int32)
            prefix, fresh = [], []
            for row, (slot, req, alloc, shared) in enumerate(group):
                seq = req.cache_tokens()
                pb = shared // bs
                suffix = seq[shared:self._prefilled(len(seq))]
                toks[row, :len(suffix)] = suffix
                plens[row] = shared
                slens[row] = max(len(suffix), 1)
                self._slide(alloc, shared, len(seq) + 1)
                prefix.append((alloc, 0, pb))
                fresh.append((alloc, pb, pb + nb))
                self._stats["prefix_prefills"] += 1
                self._stats["prefix_tokens_reused"] += shared
                self._stats["prefill_tokens"] += len(suffix)
            ids = self._block_ids(prefix, pb_pad, n_pad, gather=True)
            pk, pv = self._gather(self.kv, jnp.asarray(ids))
            self._enqueued()
            last_logits, small = self._prefill_prefix(
                self.params, jnp.asarray(toks), pk, pv,
                jnp.asarray(plens), jnp.asarray(slens))
            self._scatter(small, fresh, nb, n_pad)
            self._stats["prefills"] += 1
            self._stats["prefill_padded_tokens"] += n_pad * s_bucket
            toks_out = self._sample_batch(
                last_logits, [req for _, req, _, _ in group], n_pad)
        with _Phase(self, "engine.emit", "t_emit_s"):
            now = time.perf_counter()
            for row, (slot, req, alloc, shared) in enumerate(group):
                self._activate(slot, req, alloc, now, toks_out[row])
            self._deliver()

    def _prefill_chunk(self, alloc: SlotAllocation, seq: List[int],
                       pos: int, chunk_len: int):
        """Prefill ``seq[pos:pos+chunk_len]`` attending over the
        already-cached ``pos`` tokens (gathered dense from the pool),
        scattering the chunk's K/V into the slot's blocks. ``pos`` is
        block-aligned. Returns the chunk's last-token logits."""
        bs = self.block_size
        pb = pos // bs
        chunk = seq[pos:pos + chunk_len]
        s_bucket = self._bucket_for(len(chunk))
        # pad the gathered prefix to a power-of-two block count to bound
        # jit specializations; padded rows are position-masked
        pb_pad = self._pad_pow2(max(pb, 1), self.blocks_per_slot)
        # a sliding layer holds, and gathers, only the blocks the chunk's
        # first token still sees, and the chunk's own
        self._slide(alloc, pos, pos + len(chunk) + 1)
        toks = np.zeros((1, s_bucket), np.int32)
        toks[0, :len(chunk)] = chunk
        plen = jnp.asarray([pos], np.int32)
        slen = jnp.asarray([len(chunk)], np.int32)
        if self.eva is None:
            ids = self._block_ids([(alloc, 0, pb)], pb_pad, 1, gather=True)
            pk, pv = self._gather(self.kv, jnp.asarray(ids))
            self._enqueued()
            if self.recurrent:
                # the chunk starts from the state the chunk before left
                last_logits, small = self._prefill_prefix(
                    self.params, jnp.asarray(toks), pk, pv, plen, slen,
                    self._chunk_state)
                self._chunk_state = {name: small[name]
                                     for name in self._state_names}
                self._stats["state_chunks_carried"] += pos > 0
            else:
                last_logits, small = self._prefill_prefix(
                    self.params, jnp.asarray(toks), pk, pv, plen, slen)
        else:
            # an EVA model gathers its window's exact rows before the
            # chunk and all its summary rows: two shapes that do not
            # grow with the prompt, so one program for every chunk
            first = eva_window_block(pos, self.window, bs)
            exact = alloc.window.ids(first, first + self.window // bs,
                                     self.num_window_blocks)
            summ = np.full(self.blocks_per_slot, self.num_blocks, np.int32)
            summ[:len(alloc.blocks)] = alloc.blocks[:len(summ)]
            pk, pv, sk, sv = self._gather(
                self.kv, jnp.asarray([exact], np.int32),
                jnp.asarray(summ[None]))
            self._enqueued()
            last_logits, small = self._prefill_prefix(
                self.params, jnp.asarray(toks), pk, pv, plen, slen,
                jnp.asarray([first * bs], np.int32), (sk, sv))
        nb = s_bucket // bs
        # chunk cache is [L, 1, Tb, ...]: reuse the batched scatter
        self._scatter(small, [(alloc, pb, pb + nb)], nb, 1)
        self._stats["prefills"] += 1
        self._stats["prefill_tokens"] += len(chunk)
        self._stats["prefill_padded_tokens"] += s_bucket
        return last_logits

    def _admit_chunked(self, slot: int, req: Request,
                      alloc: SlotAllocation, shared_tok: int) -> None:
        """Single-request chunked prefill: the cached prefix (shared
        blocks and/or earlier chunks) is attended as context, so any
        context length admits — shared-prefix FLOPs are skipped, and a
        context longer than the largest bucket prefills in bucket-sized
        chunks (vLLM's chunked prefill)."""
        seq = req.cache_tokens()
        n = self._prefilled(len(seq))
        if shared_tok > 0:
            self._stats["prefix_prefills"] += 1
            self._stats["prefix_tokens_reused"] += shared_tok
        pos = shared_tok
        big = self.buckets[-1]
        last_logits = None
        # one phase for all its chunks; ``bucket`` is the first chunk's
        with self._prefill_phase(self._bucket_for(
                min(big, max(n - pos, 1))), 1, 1):
            if self.recurrent:
                self._chunk_state = self._zero_state
            while pos < n:
                chunk_len = min(big, n - pos)
                last_logits = self._prefill_chunk(alloc, seq, pos,
                                                  chunk_len)
                pos += chunk_len
            if self.recurrent:
                self._set_state_rows(self._chunk_state, [slot], 1)
            toks_out = self._sample_batch(last_logits, [req], 1)
        with _Phase(self, "engine.emit", "t_emit_s"):
            self._activate(slot, req, alloc, time.perf_counter(),
                           toks_out[0])
            self._deliver()

    def _activate(self, slot: int, req: Request, alloc: SlotAllocation,
                  now: float, first: Optional[int]) -> None:
        """The prefill is in: the request takes its slot and decodes
        from here. ``first`` is the token its prefill sampled, the
        request's first; a block-diffusion model's prefill samples none
        (``None``): the slot starts on a first BLOCK instead, what the
        prefill left of the context and masks behind it."""
        context = req.cache_tokens()
        n_cached = self._prefilled(len(context))
        if self.eva is None:
            seal_prompt_blocks(self.pool, alloc, context)
        else:       # an EVA model's blocks are not hashed (paged_cache.py)
            self._stats["kv_summary_rows_written"] += n_cached // self.eva[1]
        if self.window_pool is not None:
            # the prefill is in: what is left of it behind the window
            # goes, the prompt's blocks still held are indexed
            self._slide(alloc, n_cached, n_cached + 1)
            seal_window_blocks(self.window_pool, alloc.window)
            self._set_window_table(slot, alloc)
        self.slots[slot] = req
        self.allocs[slot] = alloc
        self.offsets[slot] = n_cached
        self._tables[slot] = self.num_blocks
        self._tables[slot, :len(alloc.blocks)] = alloc.blocks
        self._dev_tokens = self._dev_tables = self._dev_sampling = None
        self._dev_offsets = None
        self._admit_order.append(slot)
        if first is not None:
            if req.first_token_at is None:
                req.first_token_at = now
            self._emit(slot, int(first))
            return
        n = self.block_length
        given = context[n_cached:]
        self._given[slot] = len(given)
        self._last_tokens[slot] = 0     # (and nothing behind it is owed)
        self._last_tokens[slot, :n] = self.model.cfg.mask_token_id
        self._last_tokens[slot, :len(given)] = given

    def _sample_batch(self, logits, reqs: List[Request], n_pad: int):
        """The requests' first tokens [n_pad], off their prefill's last
        logits; ``None`` a row where the model's prefill samples none."""
        if self.block_length > 1:
            return [None] * n_pad
        temps = np.zeros(n_pad, np.float32)
        top_ks = np.zeros(n_pad, np.int32)
        for row, req in enumerate(reqs):
            temps[row] = req.sampling.temperature
            top_ks[row] = req.sampling.top_k
        toks, self._rng_key = self._sample(
            logits, jnp.asarray(temps), jnp.asarray(top_ks), self._rng_key)
        toks = np.asarray(toks)
        # the newest program is read: the device's queue is empty
        self._device_dry_at = time.perf_counter()
        return toks

    # -- decode ------------------------------------------------------------
    def _preempt(self, slot: int) -> None:
        """Free a slot's blocks and requeue its request (recompute
        preemption): generated tokens fold into the prompt so the
        re-admission prefill rebuilds the full context."""
        req = self.slots[slot]
        self._release(slot)
        req.preemptions += 1
        req.queued_at = time.perf_counter()
        self._stats["preemptions"] += 1
        self.waiting.appendleft(req)

    def _release(self, slot: int) -> None:
        """Give a slot's blocks back (cached-free: their content stays
        prefix-reusable until the pool reallocates them) and empty it."""
        alloc = self.allocs[slot]
        self.pool.unref_all(alloc.blocks)
        self._tables[slot] = self.num_blocks   # idle writes go to scratch
        if self.window_pool is not None:
            self.window_pool.unref_all(alloc.window.blocks)
            self._tables_win[slot] = self.num_window_blocks
        self.slots[slot] = None
        self.allocs[slot] = None
        self.offsets[slot] = 0
        # (the sampling parameters too: a batch whose last sampled row
        # left decodes greedily again, without the draw)
        self._dev_tables = self._dev_offsets = self._dev_sampling = None
        self._admit_order.remove(slot)

    def _grow_or_preempt(self) -> None:
        """Every active slot must have capacity for its next token's (its
        block's) K/V before the batched decode runs. Exhaustion preempts the
        YOUNGEST slot (recompute is cheapest for it) until the older
        ones fit — the victim may be the grower itself."""
        for slot in list(self._admit_order):      # oldest first
            if self.slots[slot] is None:
                continue
            alloc = self.allocs[slot]
            held = len(alloc.blocks)
            while not ensure_capacity(
                    self.pool, alloc,
                    int(self.offsets[slot]) + self.block_length):
                # chunked prefill re-admits ANY context length, so plain
                # youngest-first is always safe (and discards the least
                # computed work)
                victims = [s for s in self._admit_order
                           if s != slot] or [slot]
                victim = victims[-1]
                self._preempt(victim)
                if victim == slot:
                    break
            if self.slots[slot] is None:
                continue
            if len(alloc.blocks) != held:
                self._tables[slot, :len(alloc.blocks)] = alloc.blocks
                self._dev_tables = None
            self._follow_window(slot, alloc, int(self.offsets[slot]))

    def _decode_step(self) -> int:
        """Read one decode step's tokens; before that, put the next step
        on the device for the slots as they stand (``_may_run_ahead``):
        the device then goes from one step to the next with no host in
        the way, and the read-back, the emit and the streams' work all
        run under a program. A row is booked only if its slot still
        holds the request it was dispatched for: one whose request ended
        in the step before is dropped as it is read."""
        if self._in_flight is None:
            self._in_flight = self._dispatch_decode(ahead=False)
            if self._in_flight is None:
                self._deliver_deferred()
                return 0
        toks, active, reqs, ahead = self._in_flight
        if self._may_run_ahead(active):
            if toks.is_ready():
                # the step in flight is done and the one after it is
                # not there yet: the device waits from here, at least
                self._device_dry_at = time.perf_counter()
            self._in_flight = self._dispatch_decode(ahead=True)
            self._stats["decode_steps_ahead"] += 1
        else:
            self._in_flight = None
        # the step before's tokens reach their streams now, under the
        # program just dispatched: each put wakes a stream thread, and
        # their part of a token (~11 ms of Python for 32 streams) then
        # runs while this thread waits for the device, not while the
        # device waits for this thread to have the GIL back
        self._deliver_deferred()
        # not ready: the host came first, the wait is the device's
        waited = not toks.is_ready()
        with _Phase(self, "engine.sample_readback", "t_readback_s",
                    waits=True, waited=int(waited),
                    step=self._stats["decode_steps"] + 1) as read:
            toks = np.asarray(toks)
        if waited:
            self._stats["decode_steps_waited"] += 1
            if ahead and self._read_waited_at is not None:
                # this step was queued behind the last one while that
                # still ran, and the host stood waiting as each ended:
                # between the two ends lies one program's device time
                self._stats["decode_steps_device_paced"] += 1
                self._stats["t_device_paced_s"] += (
                    read.t1 - self._read_waited_at)
        self._read_waited_at = read.t1 if waited else None
        if self._in_flight is None:
            # the newest program is read: the device's queue is empty
            self._device_dry_at = read.t1
        with _Phase(self, "engine.emit", "t_emit_s"):
            self._stats["decode_steps"] += 1
            if self.block_length > 1:
                self._emit_blocks(active, toks)
            else:
                for i, req in zip(active, reqs):
                    if self.slots[i] is req:
                        self.offsets[i] += 1
                        self._emit(i, int(toks[i]))
                    else:   # ended at the read before: nothing is its
                        self._stats["decode_rows_dropped"] += 1
        if self._in_flight is not None or not self._admit_order:
            # the device is busy with the next step, or the last slot
            # ended and there is no next dispatch to deliver under
            self._deliver_deferred()
        return len(active)

    def _may_run_ahead(self, active: List[int]) -> bool:
        """May the step after the one in flight be dispatched before the
        one in flight is read? It is dispatched FOR THE SLOTS AS THEY
        STAND, at any occupancy, and is speculative a ROW: whether the
        step in flight ends a request (a stop on a token's value, its
        length) is found when it is read, and the row the step ahead
        computed for that slot is then dropped (``_decode_step``). What
        has to hold: no request waits (it is admitted next, and its
        prefill goes before any further step), nothing came in or left
        since the dispatch (the device's tokens and offsets still
        stand), no slot stands at the table's end, every slot has room
        for one more token without a preemption, and not EVERY request
        reaches its length in the step in flight (the host knows: that
        step ahead would be device time nobody reads, in front of the
        next arrival).

        Why a dropped row harms nobody. It wrote one K/V row at ``offset
        + 1`` of the ended request, in the tail block that request held
        alone: room for it was reserved before the dispatch, and the
        sealed prompt blocks others may share are full and lie before
        it. The blocks were released when the step before was read, and
        whatever is allocated or scattered into them afterwards is
        enqueued AFTER the step ahead on the one device stream, so it
        lands on top; a recurrent model's state rows of the slot are
        set anew at its next admission (``_set_state_rows``). A slot
        freed at one read may hold a new request by the next
        (``step()`` admits before it decodes), so a row is known by its
        REQUEST, not its slot, and touches neither ``offsets`` nor
        ``_last_tokens`` nor a stream. The sampler's key advances a
        step whether a row is dropped or not, so every other slot draws
        what it would have. The price is a request's first token: one
        that arrives under a step ahead finds its prefill queued behind
        it, once a request, where every token used to pay the host's
        round trip (docs/serving.md, "A step ahead").

        A block-diffusion engine (``block_length`` > 1) keeps the rule
        it had, all or nothing a pass: every slot taken, no stop token,
        no request that the block in flight can end. Its pass ahead also
        commits the block behind and its offsets are the device's to
        know, so a dropped pass is another proof (ROADMAP S13). There
        "one more token" reads "one more block" throughout: the pass in
        flight may finish one (at ``offset``), and the pass ahead then
        writes that block's rows to stay and, at ``offset + n``, the
        next block's: ``offset + 2n`` rows of room."""
        n = self.block_length
        if (self.waiting or self._dev_tokens is None
                or self._dev_offsets is None):
            return False
        reqs = [self.slots[i] for i in active]
        if n > 1:
            if len(active) < self.max_slots or any(
                    req.sampling.stop_token_ids
                    or len(req.output) + n >= req.sampling.max_tokens
                    for req in reqs):
                return False
        elif all(len(req.output) + 1 >= req.sampling.max_tokens
                 for req in reqs):
            return False
        if any(self.offsets[i] + 2 * n >= self.max_seq for i in active):
            return False
        for i in active:
            alloc = self.allocs[i]
            held = len(alloc.blocks)
            if not ensure_capacity(self.pool, alloc,
                                   int(self.offsets[i]) + 2 * n):
                return False
            if len(alloc.blocks) != held:
                self._tables[i, :len(alloc.blocks)] = alloc.blocks
                self._dev_tables = None
            # the step ahead writes at offset + 1; the step in flight,
            # queued before it, has read the block this may free
            self._follow_window(i, alloc, int(self.offsets[i]) + 1)
        return True

    def _dispatch_decode(self, ahead: bool):
        """Enqueue one decode step, sampling included: ``(tokens on the
        device, active slots, their requests, ahead)``, or None with no
        active slot. ``ahead``: the step before is still in flight, so
        every active slot stands one token further than the host's
        ``offsets`` say. The REQUESTS say whose the rows are when they
        are read: a slot may have changed hands by then."""
        with _Phase(self, "engine.schedule", "t_schedule_s"):
            if not ahead:
                self._grow_or_preempt()
            active = [i for i, r in enumerate(self.slots) if r is not None]
            reqs = [self.slots[i] for i in active]
        if not active:
            return None
        with _Phase(self, "engine.host_arrays", "t_host_arrays_s"):
            # ``jnp.array`` copies: these outlive the step, and the
            # host's arrays change in place under them
            if self._dev_tokens is None:
                self._dev_tokens = jnp.array(self._last_tokens)
            if self._dev_tables is None:
                self._dev_tables = jnp.array(
                    self._tables if self.window_pool is None
                    else np.stack([self._tables, self._tables_win]))
            if self._dev_offsets is None:
                self._dev_offsets = jnp.array(self.offsets)
            if self._dev_sampling is None:
                temps = np.zeros(self.max_slots, np.float32)
                top_ks = np.zeros(self.max_slots, np.int32)
                for i in active:
                    sampling = self.slots[i].sampling
                    temps[i] = sampling.temperature
                    top_ks[i] = sampling.top_k
                self._dev_sampling = (jnp.asarray(temps), jnp.asarray(top_ks))
                # what the program's conditionals will find
                self._sampling_asked = (bool((temps > 0).any()),
                                        bool((top_ks > 0).any()))
        # dispatch only: the call returns before the device is done
        with _Phase(self, "engine.decode_enqueue", "t_enqueue_s"):
            load, expected, *block_counts = self._ffn_counts or (None, 0)
            if self.block_length > 1:
                # the pass hands on its own next state and offsets (a
                # slot's offset moves when the DEVICE finishes its
                # block) and, apart, what the host reads of it
                (self._dev_tokens, self.kv, self._dev_offsets, self._rng_key,
                 load, self._block_counts, report) = self._decode(
                    self.params, self._dev_tokens, self.kv, self._dev_tables,
                    self._dev_offsets, *self._dev_sampling, self._rng_key,
                    load, self._block_counts)
                block_counts = [self._block_counts]
            else:
                self._dev_tokens, self.kv, self._rng_key, load = self._decode(
                    self.params, self._dev_tokens, self.kv, self._dev_tables,
                    self._dev_offsets, *self._dev_sampling, self._rng_key,
                    load)
            if load is not None:
                self._ffn_counts = (
                    load, expected + len(active) * self._ffn_rows_per_slot,
                    *block_counts)
            self._enqueued()
        self._stats["decode_steps_sampled"] += self._sampling_asked[0]
        self._stats["decode_steps_topk"] += self._sampling_asked[1]
        if self.block_length > 1:
            # (what this pass's attention reads is counted when it is
            # read back, ``_emit_blocks``: where a step ahead stands is
            # the device's to know until then)
            return report, active, reqs, ahead
        with _Phase(self, "engine.host_arrays", "t_host_arrays_s"):
            # the NEXT step's offsets go now, under the running program:
            # every active slot will have advanced by one, unless a slot
            # ends, is preempted or comes in, which drops them. The next
            # dispatch then waits for no transfer at all
            at = self.offsets.copy()          # where this step writes
            at[active] += ahead
            following = at.copy()
            following[active] += 1
            self._dev_offsets = jnp.asarray(following)
        # what the dispatched program's attention reads (the kernel:
        # ceil((offset + 1) / bs) blocks a slot) of what its tables hold;
        # counted under the running program, in no phase's span
        bs = self.block_size
        self._stats["decode_kv_blocks_table"] += (
            len(active) * self.blocks_per_slot)
        pos = at[active]
        if self.eva is not None:
            # an EVA layer reads its window's exact blocks so far and the
            # summary blocks of the windows before it
            window, chunk = self.eva
            summary = -(-(pos // window * (window // chunk)) // bs)
            self._stats["decode_kv_blocks_live"] += int(
                (pos % window // bs + 1 + summary).sum())
            self._stats["decode_kv_blocks_live_summary"] += int(summary.sum())
            self._stats["decode_kv_blocks_full_equivalent"] += int(
                ((pos + bs) // bs).sum())
            self._stats["kv_summary_rows_written"] += int(
                (pos % chunk == chunk - 1).sum())
        else:
            self._stats["decode_kv_blocks_live"] += int(
                ((pos + bs) // bs).sum())
            if self._index_topk:
                self._stats["decode_kv_rows_selected"] += int(
                    np.minimum(pos + 1, self._index_topk).sum())
            if self.window is not None:
                # a sliding layer's kernel starts at its window's first
                # block
                first = np.maximum(pos - self.window + 1, 0) // bs
                self._stats["decode_kv_blocks_live_window"] += int(
                    (pos // bs - first + 1).sum())
        return self._dev_tokens, active, reqs, ahead

    def _enqueued(self) -> None:
        """A program has just been handed to the device (a decode step,
        or the first program of a prefill group or chunk). If this
        thread knew the device's queue empty, the device has stood
        starved since at least then: booked, and the mark cleared."""
        if self._device_dry_at is not None:
            self._stats["t_device_starved_s"] += (
                time.perf_counter() - self._device_dry_at)
            self._device_dry_at = None

    def _emit(self, slot: int, tok: int) -> None:
        """Book one sampled token: the request's output, the stop test,
        a finished slot's blocks. The stream gets it from ``_deliver``."""
        req = self.slots[slot]
        req.output.append(tok)
        self._last_tokens[slot] = tok
        self._undelivered.append((req, tok))
        self._stats["tokens_generated"] += 1
        stop = (tok in req.sampling.stop_token_ids
                or len(req.output) >= req.sampling.max_tokens
                or self.offsets[slot] + 1 >= self.max_seq)
        if stop:
            self._finish(slot, "stop" if tok in req.sampling.stop_token_ids
                         else "length")

    def _finish(self, slot: int, reason: str) -> None:
        req = self.slots[slot]
        req.finish_reason = reason
        req.finished_at = time.perf_counter()
        self._undelivered.append((req, None))
        self._release(slot)

    def _emit_blocks(self, active: List[int], report: np.ndarray) -> None:
        """Book one pass of a block-diffusion model, ``report`` [B, 4n +
        3] as ``_decode_step_paged_blocks`` hands it back: the host's
        copy of the slots' state follows the device's; a slot whose
        block the pass FINISHED moves on by a block and its tokens are
        handed out, in sequence order, never to be taken back (all but
        those the prompt gave of it), each with the pass that placed it.
        (Its K/V rows come with the slot's next pass: a request that
        ends here leaves them owed, and nobody reads them.)
        ``max_tokens`` and a stop token cut the block where they fall."""
        n, bs = self.block_length, self.block_size
        # what this pass's attention had to read: each slot's rows up to
        # its block's end, of what its table holds (the rows a block
        # behind read over again are the implementation's, not counted)
        self._stats["decode_kv_blocks_table"] += (
            len(active) * self.blocks_per_slot)
        self._stats["decode_kv_blocks_live"] += int(
            ((self.offsets[active] + n + bs - 1) // bs).sum())
        self._last_tokens[:] = report[:, n + 1:]
        now = None
        for i in active:
            if not report[i, n]:
                continue
            self.offsets[i] += n
            self._stats["blocks_committed"] += 1
            req, sampling = self.slots[i], self.slots[i].sampling
            given, self._given[i] = int(self._given[i]), 0
            if req.first_token_at is None:
                req.first_token_at = now = now or time.perf_counter()
            finished = self._last_tokens[i, 2 * n + 1:3 * n + 1]
            for tok, at in zip(finished[given:].tolist(),
                               report[i, given:n].tolist()):
                req.output.append(tok)
                req.unmasked_at.append(at)
                self._undelivered.append((req, tok))
                self._stats["tokens_generated"] += 1
                if (tok in sampling.stop_token_ids
                        or len(req.output) >= sampling.max_tokens):
                    self._finish(i, "stop" if tok in sampling.stop_token_ids
                                 else "length")
                    break
            else:
                if self.offsets[i] + n >= self.max_seq:
                    self._finish(i, "length")

    def _put_end(self, req: Request) -> None:
        """End a stream that never ran (a prompt refused): the marker,
        numbered and counted as ``_deliver`` does. By the engine thread,
        or under the lock."""
        req.stream.put((None, self._stats["decode_steps"]))
        self._stats["stream_puts"] += 1

    def _deliver(self) -> None:
        """Hand the streams what ``_emit`` booked, in its order."""
        step = self._stats["decode_steps"]
        # counted first: no snapshot reads an item taken that was not put
        self._stats["stream_puts"] += len(self._undelivered)
        for req, tok in self._undelivered:
            req.stream.put((tok, step))
            if tok is None:
                req.done.set()
        self._undelivered.clear()

    def _deliver_deferred(self) -> None:
        """A decode step's tokens, delivered after the step: as a rule
        under the next step's program (``_decode_step``)."""
        if self._undelivered:
            with _Phase(self, "engine.deliver", "t_deliver_s", waits=True,
                        step=self._stats["decode_steps"]):
                self._deliver()

    # -- prefill/decode disaggregation handoff -----------------------------
    def prefill_only(self, prompt_tokens: List[int]):
        """Prefill WITHOUT occupying a decode slot: returns
        (kv_small_numpy, last_logits_numpy, prompt_len) for transfer to a
        decode engine (reference: ray.llm prefill/decode disaggregation,
        `deployments/prefill_decode_disagg/`)."""
        n = len(prompt_tokens)
        bucket = self._bucket_for(n)
        if bucket is None:
            raise ValueError(f"prompt of {n} tokens exceeds buckets")
        self._no_handoff_for_eva()
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = prompt_tokens
        last_logits, small = self._prefill(
            self.params, jnp.asarray(toks), jnp.asarray([n], np.int32))
        kv = {"k": np.asarray(small["k"]), "v": np.asarray(small["v"])}
        self._stats["prefills"] += 1
        return kv, np.asarray(last_logits[0]), n

    def _no_handoff_for_eva(self) -> None:
        if self.block_length > 1:
            raise NotImplementedError(
                "the prefill/decode handoff ends in a sampled token; a "
                "block-diffusion model's prefill samples none")
        if self.recurrent:
            raise NotImplementedError(
                "the prefill/decode handoff carries K/V rows only; a "
                "model's recurrent state is not part of it yet")
        if self.eva is not None:
            raise NotImplementedError(
                "the prefill/decode handoff carries K/V rows only; an EVA "
                "model's chunk summaries are not part of it yet")

    def submit_prefilled(self, prompt_tokens: List[int], kv: Dict,
                         last_logits, sampling: Optional[SamplingParams]
                         = None) -> Optional[Request]:
        """Admit a request whose prefill happened elsewhere. Returns None
        if no slot (or pool room) is free (caller retries)."""
        self._no_handoff_for_eva()
        req = Request(prompt_tokens, sampling or SamplingParams(),
                      self._readers)
        n = len(prompt_tokens)
        if n >= self.max_seq:
            req.finish_reason = "prompt_too_long"
            req.finished_at = time.perf_counter()
            req.done.set()
            with self._lock:
                self._put_end(req)
            return req
        with self._lock:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                return None
            bs = self.block_size
            Tb = kv["k"].shape[2]
            nb = Tb // bs
            need = max((n + 1 + bs - 1) // bs, 1)
            blocks = self.pool.alloc(max(need, 0))
            if blocks is None:
                return None
            alloc = SlotAllocation(
                blocks, 0, None if self.window_pool is None
                else WindowAllocation(0, []))
            self._slide(alloc, n, n + 1)
            small = {"k": jnp.asarray(kv["k"]), "v": jnp.asarray(kv["v"])}
            self._scatter(small, [(alloc, 0, nb)], nb, 1)
            slot = free[0]
            toks_out = self._sample_batch(jnp.asarray(last_logits)[None],
                                          [req], 1)
            self._stats["requests"] += 1
            self._activate(slot, req, alloc, time.perf_counter(),
                           toks_out[0])
            self._deliver()
        return req

    # -- convenience -------------------------------------------------------
    def generate(self, prompts: List[List[int]],
                 sampling: Optional[SamplingParams] = None
                 ) -> List[Request]:
        reqs = [self.submit(p, sampling) for p in prompts]
        while self.has_work():
            self.step()
        return reqs

    def run_forever(self, stop_event: threading.Event,
                    idle_sleep_s: float = 0.002) -> None:
        """Background engine loop (used by the serving integration).
        A step that raises (a program that does not compile, device
        OOM) ends the loop — the donated pool is gone — but never
        silently: every held and waiting request fails with the cause,
        later submits fail at once, and the thread dies with the
        traceback."""
        try:
            while not stop_event.is_set():
                if self.step() == 0 and not self.waiting:
                    with _Phase(self, "engine.idle", "t_idle_s"):
                        time.sleep(idle_sleep_s)
        except Exception as err:
            self.error = err
            self._fail_all(err)
            raise

    def _fail_all(self, err: BaseException) -> None:
        # under the lock: the loop is dead, and submitters that find it
        # so sweep from their own threads
        with self._lock:
            self._deliver()         # what was generated before the failure
            step = self._stats["decode_steps"]
            for req in self._admitting:
                self._stats["stream_puts"] += req.fail(err, step)
            for slot, req in enumerate(self.slots):
                if req is not None:
                    self.slots[slot] = None
                    self._stats["stream_puts"] += req.fail(err, step)
            while self.waiting:
                try:
                    req = self.waiting.popleft()
                except IndexError:  # a concurrent sweep drained it
                    break
                self._stats["stream_puts"] += req.fail(err, step)
