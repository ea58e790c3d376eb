"""Per-node runtime: resource accounting, task dispatch, actor hosting.

Parity contract (reference ``src/ray/raylet/``): each node owns a resource
ledger (``LocalResourceManager``), a queue of leased tasks gated on resource
availability (``LocalTaskManager``), a worker pool that executes them, and the
actor executors living on the node. Worker leases are implicit: the scheduler
(:mod:`ray_tpu._private.scheduler`) assigns a task to a node, the node's
dispatch loop admits it when resources free up, and a pooled worker thread
runs it.

TPU-first note: heavy compute on this framework happens inside XLA executables
which release the GIL, so a thread-based worker pool gives real parallelism
for accelerator work; CPU-bound Python tasks still interleave. The dispatch /
resource model is process-agnostic so a subprocess worker pool can slot in.
"""

from __future__ import annotations

import heapq
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional

from ray_tpu._private.gcs import NodeInfo
from ray_tpu._private.ids import ActorID, NodeID
from ray_tpu._private.lock_sanitizer import tracked_lock
from ray_tpu._private.object_store import LocalObjectStore
from ray_tpu._private.task_spec import TaskKind, TaskSpec
from ray_tpu.util import metrics as _metrics


def _bump_cluster_epoch() -> None:
    # lazy import: scheduler.py imports this module at top level
    from ray_tpu._private.scheduler import bump_cluster_epoch
    bump_cluster_epoch()


class ResourceLedger:
    """Tracks total/available resources; acquires never block."""

    def __init__(self, total: Dict[str, float]):
        self.total = dict(total)
        self._available = dict(total)
        self._lock = threading.Lock()
        # availability-grew hook: fired OUTSIDE the lock after
        # release/release_many/add_total so the node's dispatch pass
        # wakes immediately instead of waiting for its retry timer.
        self.on_change: Optional[Callable[[], None]] = None

    def _fire_on_change(self) -> None:
        cb = self.on_change
        if cb is not None:
            try:
                cb()
            except Exception:
                pass    # a wake hook must never fail a release

    def can_fit_total(self, demand: Dict[str, float]) -> bool:
        return all(self.total.get(k, 0.0) >= v for k, v in demand.items())

    def try_acquire(self, demand: Dict[str, float]) -> bool:
        with self._lock:
            if all(self._available.get(k, 0.0) >= v - 1e-9
                   for k, v in demand.items()):
                for k, v in demand.items():
                    self._available[k] = self._available.get(k, 0.0) - v
                return True
            return False

    def release(self, demand: Dict[str, float]) -> None:
        with self._lock:
            for k, v in demand.items():
                self._available[k] = min(
                    self._available.get(k, 0.0) + v, self.total.get(k, 0.0))
        self._fire_on_change()

    def available(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._available)

    def add_total(self, extra: Dict[str, float]) -> None:
        """Grow capacity in place (placement-group bundle resources)."""
        with self._lock:
            for k, v in extra.items():
                self.total[k] = self.total.get(k, 0.0) + v
                self._available[k] = self._available.get(k, 0.0) + v
        self._fire_on_change()
        _bump_cluster_epoch()   # can_fit_total answers changed

    def remove_total(self, extra: Dict[str, float]) -> None:
        with self._lock:
            for k, v in extra.items():
                self.total[k] = max(self.total.get(k, 0.0) - v, 0.0)
                self._available[k] = max(self._available.get(k, 0.0) - v, 0.0)
        _bump_cluster_epoch()

    def try_acquire_many(self, demand: Dict[str, float],
                         max_n: int) -> int:
        """Admit as many identically-shaped demands as fit — computed
        and deducted under ONE lock acquisition (the dispatch loop's
        batch admission; per-task try_acquire paid a lock round-trip
        per queued task)."""
        if max_n <= 0:
            return 0
        with self._lock:
            n = max_n
            for k, v in demand.items():
                if v <= 0:
                    continue
                have = self._available.get(k, 0.0)
                n = min(n, int((have + 1e-9) // v))
                if n <= 0:
                    return 0
            for k, v in demand.items():
                self._available[k] = self._available.get(k, 0.0) - v * n
            return n

    def release_many(self, groups) -> None:
        """Release a batch of completions' demands under ONE lock
        acquisition and ONE ``on_change`` wake — the drain-side sibling
        of :meth:`try_acquire_many`. ``groups`` is an iterable of
        ``(demand, count)`` pairs (same-shape completions pre-grouped
        by the caller)."""
        with self._lock:
            for demand, count in groups:
                for k, v in demand.items():
                    self._available[k] = min(
                        self._available.get(k, 0.0) + v * count,
                        self.total.get(k, 0.0))
        self._fire_on_change()


class _DirectOp:
    """Closure queued on an ActorExecutor by a compiled DAG.

    ``on_dead(cause)`` is invoked when the actor dies with the op still
    queued, so the DAG's channel fails promptly instead of timing out.
    """

    __slots__ = ("fn", "on_dead")

    def __init__(self, fn: Callable[[Any], None],
                 on_dead: Optional[Callable[[str], None]] = None):
        self.fn = fn
        self.on_dead = on_dead


class ActorExecutor:
    """Executes one actor's tasks: FIFO by seqno, optional concurrency/async.

    Reference: ``core_worker/transport/actor_scheduling_queue.h`` (ordered),
    ``out_of_order_actor_scheduling_queue.h`` (threaded/async actors), and
    the fiber-based async path (``core_worker/fiber.h``).
    """

    def __init__(self, actor_id: ActorID, max_concurrency: int,
                 run_task: Callable[[TaskSpec, Any], None],
                 run_task_async: Optional[Callable] = None,
                 concurrency_groups: Optional[Dict[str, int]] = None):
        self.actor_id = actor_id
        self.max_concurrency = max(1, max_concurrency)
        self._run_task = run_task
        self._run_task_async = run_task_async
        self.instance: Any = None
        self.is_async = False
        # Concurrency groups (reference: concurrency_group_manager.h:37):
        # each named group gets its own queue + thread pool; methods route
        # by spec.concurrency_group, "" = the default group.
        self._groups: Dict[str, Dict[str, Any]] = {}
        for name, limit in {"": self.max_concurrency,
                            **(concurrency_groups or {})}.items():
            self._groups[name] = {"heap": [], "limit": max(1, int(limit))}
        self._cond = threading.Condition()
        self._push_seq = 0
        self._dead = False
        self.death_cause: Optional[str] = None
        self._threads: List[threading.Thread] = []
        self._loop = None  # asyncio loop for async actors
        self.num_pending = 0

    def start(self, instance: Any, is_async: bool) -> None:
        self.instance = instance
        self.is_async = is_async
        if is_async:
            t = threading.Thread(target=self._async_main, daemon=True,
                                 name=f"actor-{self.actor_id.hex()[:8]}-loop")
            t.start()
            self._threads.append(t)
        else:
            for gname, group in self._groups.items():
                for i in range(group["limit"]):
                    t = threading.Thread(
                        target=self._sync_main, args=(gname,), daemon=True,
                        name=(f"actor-{self.actor_id.hex()[:8]}"
                              f"-{gname or 'default'}-{i}"))
                    t.start()
                    self._threads.append(t)

    def _group_of(self, spec: TaskSpec) -> str:
        name = getattr(spec, "concurrency_group", "") or ""
        return name if name in self._groups else ""

    def submit_direct(self, fn: Callable[[Any], None],
                      on_dead: Optional[Callable[[str], None]] = None
                      ) -> bool:
        """Compiled-graph channel op (reference: the per-actor exec loop
        of ``compiled_dag_node.py:809``): run ``fn(instance)`` on this
        actor's executor thread, FIFO-ordered with normal method calls,
        WITHOUT the task-submission machinery (no TaskSpec, scheduler,
        futures, or refcounting on the per-call path)."""
        from ray_tpu._private.ids import next_seqno
        with self._cond:
            if self._dead or self.is_async:
                return False
            self._push_seq += 1
            heapq.heappush(self._groups[""]["heap"],
                           (next_seqno(), self._push_seq,
                            _DirectOp(fn, on_dead)))
            self.num_pending += 1
            self._cond.notify_all()
        return True

    def submit(self, spec: TaskSpec) -> bool:
        with self._cond:
            if self._dead:
                return False
            # tiebreaker: seqnos from DIFFERENT submitter processes can
            # collide, and TaskSpec is not orderable
            self._push_seq += 1
            heapq.heappush(self._groups[self._group_of(spec)]["heap"],
                           (spec.seqno, self._push_seq, spec))
            self.num_pending += 1
            self._cond.notify_all()
        return True

    def kill(self, cause: str) -> List[TaskSpec]:
        """Mark dead; return tasks that were still pending."""
        with self._cond:
            if self._dead:
                return []
            self._dead = True
            self.death_cause = cause
            dropped = [spec for g in self._groups.values()
                       for _, _, spec in g["heap"]]
            pending = [s for s in dropped if not isinstance(s, _DirectOp)]
            direct_ops = [s for s in dropped if isinstance(s, _DirectOp)]
            for g in self._groups.values():
                g["heap"].clear()
            self.num_pending = 0
            self._cond.notify_all()
        for op in direct_ops:   # fail compiled-DAG channels promptly
            if op.on_dead is not None:
                try:
                    op.on_dead(cause)
                except Exception:
                    pass
        if self._loop is not None:
            try:
                self._loop.call_soon_threadsafe(self._loop.stop)
            except RuntimeError:
                pass
        return pending

    def _next(self, group: str = "") -> Optional[TaskSpec]:
        heap = self._groups[group]["heap"]
        with self._cond:
            while not heap and not self._dead:
                self._cond.wait()
            if self._dead:
                return None
            _, _, spec = heapq.heappop(heap)
            self.num_pending -= 1
            return spec

    def _next_any(self) -> Optional[TaskSpec]:
        """Async actors: one pump across all groups (semaphores bound
        per-group concurrency there)."""
        with self._cond:
            while not self._dead:
                for g in self._groups.values():
                    if g["heap"]:
                        _, _, spec = heapq.heappop(g["heap"])
                        self.num_pending -= 1
                        return spec
                self._cond.wait()
            return None

    def _sync_main(self, group: str = "") -> None:
        while True:
            spec = self._next(group)
            if spec is None:
                return
            if isinstance(spec, _DirectOp):
                try:
                    spec.fn(self.instance)
                except Exception:   # op delivers errors via its channel
                    pass
                continue
            self._run_task(spec, self.instance)

    def _async_main(self) -> None:
        import asyncio

        loop = asyncio.new_event_loop()
        self._loop = loop
        asyncio.set_event_loop(loop)
        sems = {name: asyncio.Semaphore(g["limit"])
                for name, g in self._groups.items()}

        # asyncio holds only weak references to tasks: an unretained
        # handle() task can be garbage-collected mid-await, silently
        # dropping the actor call — keep strong refs until done
        inflight: set = set()

        def track(task):  #: loop-only
            inflight.add(task)
            task.add_done_callback(inflight.discard)

        async def handle(spec):
            async with sems[self._group_of(spec)]:
                await self._run_task_async(spec, self.instance)

        async def pump():
            while True:
                spec = await loop.run_in_executor(None, self._next_any)
                if spec is None:
                    loop.stop()
                    return
                track(loop.create_task(handle(spec)))

        # the local binding retains the pump task for the whole
        # run_forever below (track() is loop-only; this thread isn't)
        pump_task = loop.create_task(pump())
        try:
            loop.run_forever()
        finally:
            for task in asyncio.all_tasks(loop):
                task.cancel()
            # Let cancellations unwind before closing the loop.
            loop.run_until_complete(
                asyncio.gather(*asyncio.all_tasks(loop),
                               return_exceptions=True))
            loop.close()


class _ExecPool:
    """Sized task-execution pool fed by the dispatch loop.

    Replaces the per-task ``_launch`` closure + semaphore feeding the
    shared ``DaemonThreadPool``: the dispatch loop hands whole admitted
    batches over in ONE lock acquisition + wakeup (``_launch`` paid a
    semaphore acquire, a pool submit, and a closure allocation per
    task), it never blocks on a full pool (the semaphore stalled it at
    capacity), and admitted-but-unstarted specs stay visible as
    TaskSpecs (``steal_pending``) so a graceful drain hands them back
    to the scheduler instead of burning them down locally (the closure
    queue made admitted work opaque and unreclaimable). Kept separate
    from ``DaemonThreadPool`` on purpose: that pool's contract is
    fire-and-forget opaque closures for its other consumers; this one
    needs a drainable, stoppable typed-spec queue."""

    def __init__(self, size: int, run_spec: Callable[[TaskSpec], None],
                 name: str):
        self._run_spec = run_spec
        self._size = max(1, size)
        self._name = name
        self._cv = threading.Condition()
        self._q: deque = deque()    #: guarded by self._cv
        self._spawned = 0           #: guarded by self._cv
        self._idle = 0              #: guarded by self._cv
        self._stopped = False       #: guarded by self._cv

    def submit_batch(self, specs) -> None:
        with self._cv:
            self._q.extend(specs)
            # spawn only to cover queued work not already matched by an
            # idle worker; stale counters over-spawn (bounded by _size),
            # never under-spawn
            spawn = min(len(self._q) - self._idle,
                        self._size - self._spawned)
            spawn = max(0, spawn)
            self._spawned += spawn
            base = self._spawned
            self._cv.notify(len(specs))
        for i in range(spawn):
            threading.Thread(target=self._work, daemon=True,
                             name=f"{self._name}-{base - i}").start()

    def steal_pending(self) -> List[TaskSpec]:
        """Atomically take every admitted-but-unstarted spec (drain
        handback / node shutdown). In-flight specs are untouched — they
        finish on their worker threads."""
        with self._cv:
            out = list(self._q)
            self._q.clear()
        return out

    def has_handback_pending(self) -> bool:
        """Any queued spec the drain pass could still hand back?
        Bounced-back specs (scheduler found nowhere else) stay here and
        run locally — without this filter the drain pass would steal
        and requeue them every dispatch tick until a thread freed up."""
        with self._cv:
            return any(not getattr(s, "_drain_bounced", False)
                       for s in self._q)

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()

    def _work(self) -> None:
        try:
            while True:
                with self._cv:
                    self._idle += 1
                    while not self._q and not self._stopped:
                        self._cv.wait()
                    self._idle -= 1
                    if not self._q:
                        return      # stopped and drained
                    spec = self._q.popleft()
                try:
                    self._run_spec(spec)
                except BaseException:   # noqa: BLE001 — task errors are
                    # delivered through the runtime's finish paths; a
                    # stray escape must not kill a pool worker
                    pass
        finally:
            with self._cv:
                self._spawned -= 1


def _bucket_job(key: tuple) -> str:
    """Job hex of a backlog bucket key. Tenancy-keyed buckets are
    ``(job_hex, shape_tuple)``; plain ones are the shape tuple itself
    (possible transiently around an enablement toggle) and attribute
    to the anonymous driver job."""
    return key[0] if (len(key) == 2 and isinstance(key[0], str)) else ""


class Node:
    """One (virtual) node: resources + store + dispatch loop + actors."""

    def __init__(self, node_id: NodeID, resources: Dict[str, float],
                 labels: Dict[str, str], store: LocalObjectStore,
                 execute_task: Callable[[TaskSpec, "Node"], None],
                 max_worker_threads: int = 256):
        self.node_id = node_id
        self.ledger = ResourceLedger(resources)
        self.labels = dict(labels)
        self.store = store
        self._execute_task = execute_task
        self.alive = True
        # Optional dep-staging hook (daemon-backed nodes): called at
        # enqueue so a proactive object push overlaps the task's queue
        # wait (reference: ObjectManager::Push ahead of task-arg pulls).
        self.prefetch: Optional[Callable[[TaskSpec], None]] = None
        # Multi-tenant fair share (set by the runtime when the
        # ``fairshare`` flag is on): backlog buckets become
        # (job, shape)-keyed, admission runs in deficit order under
        # per-job quota gates. None keeps this dispatch path identical
        # to the single-tenant one.
        self.tenancy = None
        # last per-job backlog counts pushed to the tenancy ledger —
        # dispatch-loop only; lets unchanged rounds skip the call
        self._tenancy_qcounts: Dict[str, int] = {}
        # Graceful drain: alive + draining = finish running work, take
        # no new placements; the dispatch loop hands queued-but-
        # unstarted tasks back to the runtime for resubmission elsewhere.
        self.draining = False
        # Node memory-pressure level ("ok"/"soft"/"hard"), mirrored
        # from daemon node_pressure pushes; pick_node soft-excludes
        # "hard" nodes the way it soft-excludes DRAINING ones.
        self.pressure_level = "ok"
        self.actors: Dict[ActorID, ActorExecutor] = {}  #: guarded by self._actors_lock
        self._actors_lock = tracked_lock("node.actors", reentrant=False)
        # Backlog bucketed by exact resource shape: one dispatch pass
        # is O(#shapes), not O(#queued tasks) — with a deep uniform
        # backlog (the reference's 1M+ queued-task envelope) a flat
        # list degrades quadratically (every completion rescans every
        # queued task). FIFO order holds within a shape; across shapes
        # there is no ordering contract (the flat scan also launched
        # whichever task fit first).
        self._backlog: "OrderedDict[tuple, deque]" = OrderedDict()
        self._backlog_n = 0
        # Demand of enqueued-but-not-yet-admitted tasks; lets the cluster
        # scheduler see load before the dispatch loop acquires resources
        # (reference: ReportWorkerBacklog, node_manager.proto:421).
        self._pending_demand: Dict[str, float] = {}  #: guarded by self._pending_lock
        self._pending_lock = tracked_lock("node.pending_demand",
                                          reentrant=False)
        self._running: set = set()      #: guarded by self._running_lock
        self._running_lock = tracked_lock("node.running", reentrant=False)
        # Coalesced ledger-release staging: completing tasks append
        # here and the dispatch pass drains the whole batch with ONE
        # release_many call, so under a drain storm hundreds of
        # releases share one ledger lock acquisition.
        self._release_stage: List[Dict[str, float]] = []  #: guarded by self._stage_lock
        self._stage_lock = tracked_lock("node.release_stage",
                                        reentrant=False)
        from ray_tpu._private.config import cfg
        pool_size = int(cfg().exec_pool_size) or max_worker_threads
        self._exec_pool = _ExecPool(pool_size, self._run_spec,
                                    name=f"task-{node_id.hex()[:8]}")
        # Event-loop instrumentation (reference: asio
        # instrumented_io_context / event_stats.h — per-handler counts and
        # queue lag surfaced in debug_state dumps).
        self.loop_stats = {"dispatch_iterations": 0, "tasks_launched": 0,
                           "max_queue_lag_ms": 0.0, "launch_ms_total": 0.0}
        # The dispatch pass is a callback on the process event loop —
        # submit, release and dispatch share one thread, so there is no
        # cross-thread convoy (a futex wake per enqueue, a condition
        # notify per completion, a dispatcher wakeup per release).
        # Producers stage on plain deques and arm ONE
        # call_soon_threadsafe per burst behind a dirty flag.
        from ray_tpu._private import eventloop
        self._aloop = eventloop.get_loop()
        self._inbox: deque = deque()     # GIL-atomic append/popleft
        self._wake_armed = False         # dirty flag (benign races)
        self._stopped = False            #: loop-only
        self._retry_timer = None         #: loop-only
        self.ledger.on_change = self._wake_loop

    def info(self) -> NodeInfo:
        return NodeInfo(node_id=self.node_id, alive=self.alive,
                        resources=dict(self.ledger.total),
                        labels=dict(self.labels))

    # -- normal task path --------------------------------------------------
    def enqueue(self, spec: TaskSpec) -> None:
        spec.enqueued_at = time.perf_counter()
        if self.prefetch is not None and spec.dependencies():
            # stage remote deps toward this node while the task waits
            # for admission (cheap no-op when every dep is local)
            try:
                self.prefetch(spec)
            except Exception:
                pass    # staging is best-effort; pulls cover misses
        with self._pending_lock:
            for k, v in spec.resources.items():
                self._pending_demand[k] = self._pending_demand.get(k, 0.0) + v
        self._post(spec)

    def _post(self, item) -> None:
        """Dispatch-input hand-off: stage on a plain deque and coalesce
        wakes behind the dirty flag — one call_soon_threadsafe per
        BURST of submissions, not one per task. ``None`` stops the
        pass for good."""
        self._inbox.append(item)
        self._wake_loop()

    def _wake_loop(self) -> None:
        # benign race on the flag: two producers may both arm — the
        # second pass finds empty stages and returns; a producer that
        # loses the other way (flag already True) is covered by the
        # armed pass, which drains AFTER clearing the flag
        if self._wake_armed:
            return
        self._wake_armed = True
        try:
            self._aloop.call_soon_threadsafe(self._loop_pass)
        except RuntimeError:
            pass    # loop torn down (interpreter exit)

    def _drop_pending(self, spec: TaskSpec) -> None:
        self._drop_pending_many((spec,))

    def _drop_pending_many(self, specs) -> None:
        """One pending-lock round-trip for a whole admitted batch."""
        with self._pending_lock:
            for spec in specs:
                for k, v in spec.resources.items():
                    left = max(self._pending_demand.get(k, 0.0) - v, 0.0)
                    if left <= 1e-12:
                        # Drop zeroed keys: PG-scoped names are unique per
                        # group and would otherwise accumulate forever.
                        self._pending_demand.pop(k, None)
                    else:
                        self._pending_demand[k] = left

    def effective_available(self) -> Dict[str, float]:
        """Available capacity minus demand already queued here."""
        avail = self.ledger.available()
        with self._pending_lock:
            for k, v in self._pending_demand.items():
                avail[k] = avail.get(k, 0.0) - v
        return avail

    def _ingest(self, spec: TaskSpec) -> None:  #: loop-only
        """Bucket one submitted spec into the backlog."""
        # re-read per spec: the runtime attaches the tenancy manager
        # right after construction
        ten = self.tenancy
        key = tuple(sorted(spec.resources.items()))
        if ten is not None:
            key = (spec.job_id.hex()
                   if spec.job_id is not None else "", key)
        bucket = self._backlog.get(key)
        if bucket is None:
            bucket = self._backlog[key] = deque()
        bucket.append(spec)
        self._backlog_n += 1

    def _loop_pass(self) -> None:  #: loop-only
        """One dispatch round on the process event loop.

        Producers (submit handlers, completing workers, ledger
        releases) stage work on plain deques and arm at most one of
        these per burst via ``_wake_armed``. The flag is cleared FIRST:
        a wake staged after the clear schedules a fresh pass, one
        staged before it is drained below — the occasional extra no-op
        pass (an on-loop ledger release re-arms mid-pass) is cheaper
        than a lost wake.
        """
        self._wake_armed = False
        if self._retry_timer is not None:
            self._retry_timer.cancel()
            self._retry_timer = None
        # coalesced ledger releases: one release_many for the burst
        with self._stage_lock:
            batch, self._release_stage = self._release_stage, []
        if batch:
            self._release_batch(batch)
        inbox = self._inbox
        while inbox:
            item = inbox.popleft()
            if item is None:
                self._stopped = True
                return
            self._ingest(item)
        if self._stopped:
            return
        progressed = self._dispatch_pass()
        if self._backlog_n and not progressed and not self._stopped:
            # blocked on resources/quota with no release in flight —
            # poll-retry every 50 ms; a real release cancels this timer
            # via the ledger's on_change wake
            self._retry_timer = self._aloop.call_later(
                0.05, self._retry_pass)

    def _retry_pass(self) -> None:  #: loop-only
        self._retry_timer = None
        self._loop_pass()

    def _dispatch_pass(self) -> bool:
        """One admission pass over the backlog buckets. Returns whether
        any bucket made progress; ``_loop_pass`` arms a retry timer
        when none did."""
        ten = self.tenancy
        if not self.alive:
            self._fail_backlog()
            return True     # backlog emptied: nothing to wait on
        if self.draining and (self._backlog_n
                              or self._exec_pool
                              .has_handback_pending()):
            # Hand queued-but-unstarted work back to the cluster
            # scheduler (no retry consumed) — both backlog entries
            # AND specs already admitted into the exec-pool queue
            # (the backlog can be empty while the pool still holds
            # unstarted work). Whatever bounces back (nowhere else
            # fits) falls through and dispatches here.
            self._resubmit_backlog()
        progressed = False
        self.loop_stats["dispatch_iterations"] += 1
        if ten is not None and self._backlog:
            # Deficit-ordered batch admission: a job's same-shape
            # ready group is considered whole, highest fair-share
            # deficit first (batch-DAG dispatch per 2002.07062) —
            # a light job's small groups cut ahead of a saturating
            # job's backlog instead of interleaving arbitrarily.
            keys = ten.order_buckets(
                [((_bucket_job(k), k), len(b))
                 for k, b in self._backlog.items()])
            keys = [k for _job, k in keys]
        else:
            keys = list(self._backlog)
        for key in keys:
            bucket = self._backlog.get(key)
            if bucket is None:
                continue
            while bucket:
                demand = bucket[0].resources
                want = len(bucket)
                if ten is not None:
                    # per-job hard-cap gate: a clamped group stays
                    # QUEUED in the backlog (never lost) until the
                    # job's own completions free quota headroom
                    want = ten.admit_cap(_bucket_job(key), demand,
                                         want)
                    if want <= 0:
                        break
                # Batch admission: every task in a bucket shares one
                # resource shape, so ONE ledger lock round-trip
                # admits as many as currently fit (per-task
                # try_acquire paid a lock + dict scan per task).
                n = self.ledger.try_acquire_many(demand, want)
                if n <= 0:
                    break
                admitted = [bucket.popleft() for _ in range(n)]
                self._backlog_n -= n
                self._drop_pending_many(admitted)
                t0 = time.perf_counter()
                for spec in admitted:
                    # Pairs this admission's ledger acquire with
                    # exactly one release: the worker may release
                    # early (see worker._release_task_resources) or
                    # _run_spec's `finally` does.
                    spec._resources_released = False
                    if spec.enqueued_at:
                        lag_ms = (t0 - spec.enqueued_at) * 1000
                        if lag_ms > self.loop_stats["max_queue_lag_ms"]:
                            self.loop_stats["max_queue_lag_ms"] = lag_ms
                        _metrics.note_queue_dwell(
                            "node.dispatch", lag_ms / 1000.0)
                        if getattr(spec, "trace_sampled", False):
                            # queue phase: backlog enqueue ->
                            # dispatch-loop admission. t0 is reused
                            # as the span end: zero extra clock
                            # reads in the dispatch pass.
                            from ray_tpu._private import events as _ev
                            _ev.record_phase_rt(
                                spec, "queue", lag_ms / 1000.0,
                                self.node_id.hex(),
                                start_wall=_ev.wall_at(
                                    spec.enqueued_at),
                                end_mono=t0)
                # count BEFORE the pool takes them: a task may
                # finish (and a get() observe it) before control
                # returns here
                self.loop_stats["tasks_launched"] += n
                if ten is not None:
                    ten.note_admitted(_bucket_job(key), demand, n)
                with self._running_lock:
                    self._running.update(s.task_id for s in admitted)
                # ONE handoff for the whole admitted batch; the
                # sized pool reuses threads instead of paying a
                # spawn + closure per task
                self._exec_pool.submit_batch(admitted)
                self.loop_stats["launch_ms_total"] += (
                    time.perf_counter() - t0) * 1000
                progressed = True
            if not bucket:
                self._backlog.pop(key, None)
        if ten is not None:
            counts: Dict[str, int] = {}
            for k, b in self._backlog.items():
                job = _bucket_job(k)
                counts[job] = counts.get(job, 0) + len(b)
            # unchanged since last round ⇒ the ledger already saw
            # this state (idle deficit reset included) — skip the
            # per-round lock round-trip
            if counts != self._tenancy_qcounts:
                self._tenancy_qcounts = counts
                ten.observe_queued(self.node_id.hex(), counts)
        return progressed

    def _run_spec(self, spec: TaskSpec) -> None:
        """One task's execution on an exec-pool worker thread."""
        try:
            self._execute_task(spec, self)
        finally:
            with self._running_lock:
                self._running.discard(spec.task_id)
            if (spec.kind != TaskKind.ACTOR_CREATION
                    and not getattr(spec, "_resources_released", True)):
                # Actors hold their resources for their whole lifetime;
                # the runtime releases them on actor death.
                spec._resources_released = True
                self.stage_release(spec.resources)
            ten = self.tenancy
            if ten is not None and spec.kind != TaskKind.ACTOR_CREATION:
                # per-job usage attribution (lock-free append); actor
                # creations are settled when the runtime releases the
                # actor's lifetime hold
                ten.note_done(spec.job_id.hex()
                              if spec.job_id is not None else "",
                              spec.resources)

    # -- coalesced ledger release (flat combining) -----------------------
    def stage_release(self, resources: Dict[str, float]) -> None:
        """Release ledger resources, coalescing concurrent completions:
        every release stages and the LOOP drains the whole batch at the
        top of its next pass — the completing worker thread never
        touches the ledger lock, and a drain storm collapses to one
        release_many + zero cross-thread dispatch wakeups (the pass it
        woke is already the one dispatching)."""
        with self._stage_lock:
            self._release_stage.append(resources)
        self._wake_loop()

    def _release_batch(self, batch) -> None:
        if len(batch) == 1:
            self.ledger.release(batch[0])
            return
        # group same-shape demands: one release_many call covers
        # the whole batch under one ledger lock acquisition
        groups: "OrderedDict[tuple, list]" = OrderedDict()
        for res in batch:
            key = tuple(sorted(res.items()))
            entry = groups.get(key)
            if entry is None:
                groups[key] = [res, 1]
            else:
                entry[1] += 1
        self.ledger.release_many(groups.values())

    def _notify_off_loop(self, fn: Callable[[], None]) -> None:
        """Run runtime notifications off the event loop. The lost/
        drained callbacks resubmit through the scheduler and may do
        blocking RPC (AsyncClient.call raises on the loop by design),
        so the dispatch pass ships them to a helper thread; a caller
        off the loop (the shutdown path) runs inline."""
        from ray_tpu._private import eventloop
        if eventloop.on_loop():
            threading.Thread(target=fn, daemon=True,
                             name="node-notify").start()
        else:
            fn()

    def _fail_backlog(self) -> None:
        from ray_tpu._private import worker
        rt = worker.global_runtime()
        buckets, self._backlog = self._backlog, OrderedDict()
        self._backlog_n = 0
        backlog = [spec for bucket in buckets.values() for spec in bucket]
        for spec in backlog:
            self._drop_pending(spec)
        if rt is not None and backlog:
            def _notify() -> None:
                for spec in backlog:
                    rt.on_node_task_lost(spec, self)
            self._notify_off_loop(_notify)

    def start_drain(self) -> None:
        """Enter the DRAINING state: running tasks finish, the dispatch
        loop returns queued work to the runtime, the scheduler stops
        placing here. Runs on any thread; the backlog itself is only
        touched by the dispatch pass, woken here."""
        self.draining = True
        # DRAINING must leave cached pick_node candidate sets NOW, not
        # at the next natural invalidation
        _bump_cluster_epoch()
        self._wake_loop()

    def _resubmit_backlog(self) -> None:
        """Graceful-drain pass (dispatch pass only): queued tasks that
        have not been bounced before go back to the cluster scheduler;
        a task the scheduler sent BACK here (nothing else fits) keeps
        its spot and dispatches locally — no resubmit ping-pong."""
        from ray_tpu._private import worker
        rt = worker.global_runtime()
        if rt is None:
            return
        keep: "OrderedDict[tuple, deque]" = OrderedDict()
        moved: List[TaskSpec] = []
        for key, bucket in self._backlog.items():
            stay: deque = deque()
            for spec in bucket:
                if getattr(spec, "_drain_bounced", False):
                    stay.append(spec)
                else:
                    moved.append(spec)
            if stay:
                keep[key] = stay
        self._backlog = keep
        self._backlog_n = sum(len(b) for b in keep.values())
        for spec in moved:
            self._drop_pending(spec)
        handback = self._steal_drain_handback()
        drained = moved + handback
        if drained:
            def _notify() -> None:
                for spec in drained:
                    rt.on_node_task_drained(spec, self)
            self._notify_off_loop(_notify)

    def _steal_drain_handback(self) -> List[TaskSpec]:
        """Exec-pool drain interaction: in-flight tasks finish on their
        worker threads, but admitted-but-unstarted specs still sitting
        in the pool queue are stolen back and their ledger admission
        undone; the returned specs are handed to the scheduler like
        backlog entries (no retry consumed). Bounced-back specs
        (nothing else fits) re-feed the pool and run here."""
        stolen = self._exec_pool.steal_pending()
        if not stolen:
            return []
        requeue: List[TaskSpec] = []
        handback: List[TaskSpec] = []
        for spec in stolen:
            if getattr(spec, "_drain_bounced", False):
                requeue.append(spec)
            else:
                handback.append(spec)
        if requeue:
            self._exec_pool.submit_batch(requeue)
        if not handback:
            return []
        with self._running_lock:
            for spec in handback:
                self._running.discard(spec.task_id)
        for spec in handback:
            # undo the admission's ledger acquire before rescheduling
            if not getattr(spec, "_resources_released", True):
                spec._resources_released = True
                self.stage_release(spec.resources)
                if self.tenancy is not None:
                    self.tenancy.note_done(
                        spec.job_id.hex()
                        if spec.job_id is not None else "",
                        spec.resources)
        return handback

    # -- actor hosting -----------------------------------------------------
    def host_actor(self, executor: ActorExecutor) -> None:
        with self._actors_lock:
            self.actors[executor.actor_id] = executor

    def evict_actor(self, actor_id: ActorID) -> None:
        with self._actors_lock:
            self.actors.pop(actor_id, None)

    # -- lifecycle ---------------------------------------------------------
    def shutdown(self, fail_tasks: bool = True) -> Dict[ActorID, List[TaskSpec]]:
        """Stop the node; returns per-actor pending tasks for FT handling."""
        self.alive = False
        _bump_cluster_epoch()
        self._post(None)
        pending_by_actor: Dict[ActorID, List[TaskSpec]] = {}
        with self._actors_lock:
            actors = dict(self.actors)
            self.actors.clear()
        for aid, ex in actors.items():
            pending_by_actor[aid] = ex.kill("node died")
        if fail_tasks:
            self._fail_backlog()
            self._fail_pool_pending()
        # let in-flight pool work unwind, then retire the idle threads
        self._exec_pool.stop()
        return pending_by_actor

    def _fail_pool_pending(self) -> None:
        """Node death with specs admitted but not yet started: route
        them through the same lost-task flow as the backlog."""
        stolen = self._exec_pool.steal_pending()
        if not stolen:
            return
        from ray_tpu._private import worker
        rt = worker.global_runtime()
        with self._running_lock:
            for spec in stolen:
                self._running.discard(spec.task_id)
        if rt is not None:
            for spec in stolen:
                rt.on_node_task_lost(spec, self)
