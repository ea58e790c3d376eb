"""Host-side block pool for the paged KV cache.

Reference capability: vLLM's BlockSpaceManager / prefix caching (the
engine behind `ray.llm`, outside the reference tree; its TPU/HBM
config surface at `python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:126-207`). PAPERS.md: PagedAttention (Kwon et al.).

Design (vLLM-v1-shaped, TPU-adapted):

- The DEVICE side is one pool ``[L, num_blocks, block_size, Hkv, D]``
  per k/v (allocated once, scanned over L); THIS module is the host
  side: free-list, per-block refcounts, and the content-hash prefix
  index. No jax imports — pure Python, unit-testable anywhere.
- Blocks are IMMUTABLE once full. A prompt's full blocks are hashed by
  chain ``h_i = hash(h_{i-1}, tokens_i)``; identical prefixes across
  live requests resolve to the SAME physical blocks (refcount++), so
  admission skips both HBM and prefill FLOPs for the shared prefix.
  Writes only ever target a request's own tail blocks (a prefix hit is
  full-block-granular, so the write offset always lands in a private
  block) — classic copy-on-write never triggers without beam search,
  which keeps the device side scatter-free.
- Freed blocks go to an LRU free-list but KEEP their prefix-index entry
  (content stays valid in HBM) until the block is reallocated — a later
  request with the same prefix can resurrect a "free" block. This is
  the cross-request prefix cache; eviction is allocation itself.
- A model whose layers are of two KINDS (full and sliding-window
  attention, ``models/llama.py``) gets a ``BlockPool`` a kind. The full
  kind's is the one above. The SLIDING kind's holds, a slot, only the
  blocks its window still overlaps (``WindowAllocation``): as the slot's
  offset passes a block boundary the block that left the window goes
  back to that pool's free list (``slide_window``; freed, not reused as
  a ring: a freed block keeps its prefix-index entry like any other).
  A prefix hit needs BOTH kinds: ``allocate_slot`` takes the longest
  prefix whose blocks the full kind's index holds AND whose last
  window's worth of blocks the sliding kind's index still holds.
- An EVA model (``models/llama.py``: an exact window that RESETS and
  one summary row a chunk of everything before it) keeps a slot's K/V in
  two PARTS, a ``BlockPool`` each. The SUMMARY part is the slot's main
  allocation (``SlotAllocation.blocks``): a block of ``block_size``
  summary rows covers ``block_size * chunk`` positions, that pool's
  ``block_size`` is in POSITIONS, and ``allocate_slot`` /
  ``ensure_capacity`` grow it as they grow any slot: one block every 512
  positions at 32 rows of 16. The EXACT part is a ``WindowAllocation``
  moved by ``slide_window`` to ``eva_window_block``: nothing goes while
  the slot stays in its window, and at a window's end ALL its blocks go
  back together. Neither part's blocks are hashed, so an EVA model's
  prompts are never prefix hits: a hit would need the summary blocks of
  every earlier window AND the exact blocks of the window it ends in,
  and the exact ones are gone one window later.
- A pool whose block is NARROW (few K/V heads: a block of a few KB) is
  built with ``run`` > 1 (``ops.paged_attention.run_blocks``): the paged
  kernel then copies RUNS of that many blocks as one page, so a slot's
  blocks must LIE in runs. The pool hands out, takes back and evicts
  whole aligned runs (blocks ``[r*run, (r+1)*run)``): every table is
  made of them (``table[r*run + k] == table[r*run] + k``), a slot that
  needs the first block of a run holds all of it (the rest RESERVED
  until its length reaches them), and a prefix hit is taken in whole
  runs. A block stays what it was to every caller: ``block_size``
  tokens, one hash, one entry of a table, one count. With ``run`` 1
  every method hands out what it always did, in the same order.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

_NO_HASH = None


class BlockPool:
    """Refcounted physical blocks + content-hash prefix index."""

    def __init__(self, num_blocks: int, block_size: int, run: int = 1):
        if num_blocks < 1 or block_size < 1 or run < 1:
            raise ValueError("num_blocks, block_size and run must be >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # blocks handed out together: run ``r`` is blocks [r*run,
        # (r+1)*run); what ``num_blocks`` leaves over is never handed out
        self.run = run
        self.refcount = [0] * num_blocks
        # the free RUNS (with ``run`` 1: the free blocks), LRU order:
        # oldest-freed first == evicted first. A run is free while none
        # of its blocks is referenced
        self._free: "OrderedDict[int, None]" = OrderedDict(
            (i, None) for i in range(num_blocks // run))
        # content hash -> physical block (live or cached-free)
        self._by_hash: Dict[int, int] = {}
        self._hash_of: List[Optional[int]] = [_NO_HASH] * num_blocks
        self.stats = {"prefix_hits": 0, "prefix_queries": 0,
                      "evictions": 0}

    # -- introspection ----------------------------------------------------
    @property
    def num_free(self) -> int:
        """Blocks an ``alloc`` can still hand out."""
        return len(self._free) * self.run

    def _blocks_of(self, run_id: int) -> range:
        return range(run_id * self.run, (run_id + 1) * self.run)

    def holds(self, n: int) -> bool:
        """Could ``n`` blocks EVER be allocated together (in whole runs,
        from the runs the pool has)?"""
        return -(-n // self.run) <= self.num_blocks // self.run

    def cached_free_blocks(self) -> int:
        """Free blocks still carrying reusable prefix content."""
        return sum(1 for r in self._free for b in self._blocks_of(r)
                   if self._hash_of[b] is not None)

    # -- hashing ----------------------------------------------------------
    @staticmethod
    def chain_hashes(tokens: Sequence[int], block_size: int,
                     extra_key: Optional[Tuple] = None) -> List[int]:
        """Hash chain over the FULL blocks of ``tokens``. ``extra_key``
        (e.g. a model/adapter id) salts the chain so different models
        never share blocks.

        Content addressing uses sha256, not Python ``hash()``: a 64-bit
        hash collision (or a crafted token sequence in a multi-tenant
        server) would silently map different block contents onto the
        same physical block and serve wrong KV. The digest cost is
        negligible next to prefill FLOPs (vLLM made the same move)."""
        import hashlib

        hashes: List[int] = []
        prev = hashlib.sha256(repr(extra_key).encode()).digest()
        for start in range(0, len(tokens) - block_size + 1, block_size):
            h = hashlib.sha256(prev)
            h.update(repr(tuple(tokens[start:start + block_size]))
                     .encode())
            prev = h.digest()
            hashes.append(int.from_bytes(prev[:16], "little"))
        return hashes

    # -- allocation -------------------------------------------------------
    def match_prefix(self, hashes: Sequence[int]) -> List[int]:
        """Longest prefix of ``hashes`` resolvable to live-or-cached
        blocks. Returns the physical ids (NOT yet referenced)."""
        out: List[int] = []
        self.stats["prefix_queries"] += 1
        for h in hashes:
            b = self._by_hash.get(h)
            if b is None:
                break
            out.append(b)
        if out:
            self.stats["prefix_hits"] += 1
        return out

    def ref(self, block: int) -> None:
        """Take a reference; resurrects a cached-free block (and takes
        its run off the free list: a hit takes the run's other blocks
        too, ``allocate_slot``)."""
        if self.refcount[block] == 0:
            self._free.pop(block // self.run, None)
        self.refcount[block] += 1

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` fresh (private, writable) blocks, or None if
        the pool can't cover it: in whole runs, so ``n`` rounded up to
        one (the blocks past ``n`` are the caller's all the same,
        reserved). Eviction = reusing the LRU free run, dropping
        whatever prefix content it still cached."""
        runs = -(-n // self.run)
        if runs > len(self._free):
            return None
        out = []
        for _ in range(runs):
            r, _ = self._free.popitem(last=False)
            for b in self._blocks_of(r):
                old = self._hash_of[b]
                if old is not None:
                    self._by_hash.pop(old, None)
                    self._hash_of[b] = _NO_HASH
                    self.stats["evictions"] += 1
                self.refcount[b] = 1
                out.append(b)
        return out

    def whole_runs(self, blocks: Sequence[int]) -> int:
        """How many of ``blocks``, from the first on, lie as whole runs:
        aligned, contiguous, ``run`` at a time (all of them at ``run``
        1). What a table of runs can share of a matched prefix."""
        run, n = self.run, 0
        if run == 1:
            return len(blocks)
        while (n + run <= len(blocks) and blocks[n] % run == 0
               and all(blocks[n + k] == blocks[n] + k
                       for k in range(1, run))):
            n += run
        return n

    def seal(self, block: int, content_hash: int) -> None:
        """Mark a full block's content, making it prefix-shareable. If
        an identical block is already indexed, the index keeps the OLD
        one (dedup happens at the next admission, not retroactively)."""
        if self._hash_of[block] is not None:
            return
        if content_hash in self._by_hash:
            return
        self._by_hash[content_hash] = block
        self._hash_of[block] = content_hash

    def unref(self, block: int) -> None:
        """Drop a reference; at zero the block joins the free list (its
        run does, once none of its blocks is referenced) but keeps its
        prefix-index entry (cached-free) until reallocated."""
        if self.refcount[block] <= 0:
            raise ValueError(f"unref of unreferenced block {block}")
        self.refcount[block] -= 1
        r = block // self.run
        if self.refcount[block] == 0 and not any(
                self.refcount[b] for b in self._blocks_of(r)):
            self._free[r] = None       # append = most-recently-freed

    def unref_all(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self.unref(b)


class SlotAllocation:
    """A slot's logical->physical block mapping plus which of its
    blocks were prefix hits (already containing K/V)."""

    __slots__ = ("blocks", "shared_blocks", "sealed_upto", "window")

    def __init__(self, blocks: List[int], shared_blocks: int,
                 window: Optional["WindowAllocation"] = None):
        self.blocks = blocks              # physical ids, logical order
        self.shared_blocks = shared_blocks
        self.sealed_upto = shared_blocks  # blocks already hash-indexed
        self.window = window              # the sliding kind's blocks, if any

    @property
    def capacity(self) -> int:
        return len(self.blocks)


class WindowAllocation:
    """A slot's blocks of the SLIDING kind: the logical blocks
    ``[first, first + len(blocks))`` of its sequence, the ones its
    window still overlaps (and, in a prefill, the chunk being written).
    Blocks before ``first`` were freed, or never held. ``hashes`` is the
    chain of the request's full prompt blocks (by logical index): a
    prompt block is indexed before it is freed, so a later request can
    still find it."""

    __slots__ = ("first", "blocks", "hashes")

    def __init__(self, first: int, blocks: List[int],
                 hashes: Sequence[int] = ()):
        self.first = first
        self.blocks = blocks
        self.hashes = hashes

    def ids(self, lo: int, hi: int, fill: int) -> List[int]:
        """Physical ids of the logical blocks ``[lo, hi)``, ``fill``
        where none is held."""
        end = self.first + len(self.blocks)
        return [self.blocks[j - self.first] if self.first <= j < end
                else fill for j in range(lo, hi)]


def first_window_block(n_cached: int, window: int, block_size: int) -> int:
    """The logical block of the first position a sliding layer's query
    at position ``n_cached`` sees (key j is visible iff i - j < window)."""
    return max(0, n_cached - window + 1) // block_size


def eva_window_block(n_cached: int, window: int, block_size: int) -> int:
    """The logical block of the first position an EVA layer's query at
    position ``n_cached`` sees exactly: its window's first (the window
    resets at every multiple of ``window``, a multiple of the block)."""
    return n_cached // window * (window // block_size)


def window_blocks_per_slot(window: int, block_size: int, chunk: int) -> int:
    """Most blocks of the sliding kind one slot ever holds: those a
    window overlaps (it need not start on a block boundary), the partly
    filled tail block the next token goes to, and the blocks of one
    prefill chunk of ``chunk`` tokens being written beside them."""
    return -(-(window - 1) // block_size) + 2 + -(-chunk // block_size)


def slide_window(pool: BlockPool, alloc: WindowAllocation,
                 first_block: int, needed_tokens: int) -> int:
    """Move a slot's sliding-kind blocks on: free those before logical
    block ``first_block`` (the window has left them), then hold blocks
    up to the one ``needed_tokens`` ends in. Returns how many were
    freed. A full PROMPT block is indexed by its hash before it goes, so
    it stays a prefix hit until the pool reallocates it. The pool is
    sized for every slot's most (``window_blocks_per_slot``), so running
    out is a fault of the caller's accounting, not load."""
    bs = pool.block_size
    freed = 0
    while alloc.blocks and alloc.first < first_block:
        block = alloc.blocks.pop(0)
        if alloc.first < len(alloc.hashes):
            pool.seal(block, alloc.hashes[alloc.first])
        pool.unref(block)
        alloc.first += 1
        freed += 1
    if not alloc.blocks:
        alloc.first = max(alloc.first, first_block)
    need = (needed_tokens + bs - 1) // bs - (alloc.first + len(alloc.blocks))
    if need > 0:
        fresh = pool.alloc(need)
        if fresh is None:
            raise RuntimeError(
                f"the sliding-window block pool ({pool.num_blocks} blocks) "
                f"cannot give a slot {need} more: it is sized so that "
                f"this cannot happen")
        alloc.blocks.extend(fresh)
    return freed


def seal_window_blocks(pool: BlockPool, alloc: WindowAllocation) -> None:
    """After prefill lands: index the full prompt blocks still held."""
    for k, block in enumerate(alloc.blocks):
        if alloc.first + k < len(alloc.hashes):
            pool.seal(block, alloc.hashes[alloc.first + k])


def allocate_slot(pool: BlockPool, prompt: Sequence[int],
                  reserve_tokens: Optional[int] = None,
                  extra_key: Optional[Tuple] = None,
                  window_pool: Optional[BlockPool] = None,
                  window: Optional[int] = None, share: bool = True
                  ) -> Optional[Tuple[SlotAllocation, int]]:
    """Allocate blocks for a request: longest shared prefix from the
    pool's index + fresh blocks covering the rest of ``reserve_tokens``
    (default: the prompt). Decode-time growth goes through
    ``ensure_capacity``; exhaustion there triggers engine preemption.

    With a ``window_pool`` (a model with sliding layers of that
    ``window``) a prefix of n blocks is shared only if the sliding
    kind can serve it too: the suffix's first token sees the ``window -
    1`` positions before it, so every block from the one that holds
    position ``n*bs - window + 1`` up to block n - 1 has to be in the
    sliding kind's index still. The longest n for which both hold is
    taken (it may be 0), and the allocation's ``window`` then holds
    those blocks; the rest of the sliding kind's come with
    ``slide_window`` as the prefill and the decode go.

    ``share`` false: no prefix is taken from the index, whatever it
    holds (a model with a recurrent state: the shared pages would come
    without the state at their end); every block is fresh.

    A pool of RUNS shares a prefix in whole runs (the hit rounds down to
    ``run * block_size`` tokens, and ends where the matched blocks stop
    lying as one) and reserves the fresh blocks in whole runs too: the
    allocation's ``blocks`` then hold those past ``reserve_tokens`` that
    fill its last run.

    Returns (allocation, shared_token_count) or None if the pool cannot
    cover the non-shared remainder right now.
    """
    bs = pool.block_size
    reserve_tokens = max(reserve_tokens or 0, len(prompt))
    hashes = pool.chain_hashes(prompt, bs, extra_key)
    shared = pool.match_prefix(hashes) if share else []
    # never share the block holding the LAST prompt token: a FULL-prompt
    # hit would skip prefill entirely and the engine still needs the
    # last-token logits — keep >=1 token of real prefill.
    if len(shared) * bs >= len(prompt):
        shared = shared[:max(0, (len(prompt) - 1) // bs)]
    shared = shared[:pool.whole_runs(shared)]
    window_alloc = None
    if window_pool is not None:
        def in_window(n):      # the sliding kind's blocks a hit of n needs
            return range(first_window_block(n * bs, window, bs), n)

        n = len(shared)
        while n and any(hashes[j] not in window_pool._by_hash
                        for j in in_window(n)):
            n -= pool.run
        shared = shared[:n]
        held = [window_pool._by_hash[hashes[j]] for j in in_window(n)]
        for b in held:
            window_pool.ref(b)
        window_alloc = WindowAllocation(n - len(held), held, hashes)
    n_shared_tok = len(shared) * bs
    total_blocks = (reserve_tokens + bs - 1) // bs
    n_fresh = total_blocks - len(shared)
    # ref shared blocks FIRST: alloc() below may otherwise evict a
    # cached-free block that match_prefix just handed us
    for b in shared:
        pool.ref(b)
    fresh = pool.alloc(n_fresh)
    if fresh is None:
        pool.unref_all(shared)
        if window_alloc is not None:
            window_pool.unref_all(window_alloc.blocks)
        return None
    alloc = SlotAllocation(list(shared) + fresh, len(shared), window_alloc)
    return alloc, n_shared_tok


def ensure_capacity(pool: BlockPool, alloc: SlotAllocation,
                    needed_tokens: int) -> bool:
    """Grow ``alloc`` until it covers ``needed_tokens`` (by whole runs,
    where the pool has them). False = pool exhausted (caller preempts
    someone)."""
    bs = pool.block_size
    need = (needed_tokens + bs - 1) // bs - len(alloc.blocks)
    if need <= 0:
        return True
    fresh = pool.alloc(need)
    if fresh is None:
        return False
    alloc.blocks.extend(fresh)
    return True


def seal_prompt_blocks(pool: BlockPool, alloc: SlotAllocation,
                       prompt: Sequence[int],
                       extra_key: Optional[Tuple] = None) -> None:
    """After prefill lands, index the prompt's full blocks so later
    requests can share them."""
    bs = pool.block_size
    hashes = pool.chain_hashes(prompt, bs, extra_key)
    for i in range(alloc.sealed_upto, len(hashes)):
        pool.seal(alloc.blocks[i], hashes[i])
    alloc.sealed_upto = max(alloc.sealed_upto, len(hashes))
