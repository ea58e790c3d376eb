"""The Runtime: task submission, execution, objects, actors, recovery.

This is the core-worker equivalent (reference ``src/ray/core_worker/``): it
owns task submission (``NormalTaskSubmitter`` / ``ActorTaskSubmitter``), the
dependency resolver, result storage (inline memory store for small values,
node object store for large ones), distributed refcounting hooks, task retries
and lineage-based object reconstruction (``task_manager.h``,
``object_recovery_manager.h``), and the actor lifecycle driven through GCS
state (``gcs_actor_manager.cc``).

Topology: one Runtime per driver process hosts N virtual nodes (the test
cluster fixture of the reference, ``python/ray/cluster_utils.py``, is the
*primary* deployment shape here for a single host; multi-host attaches via
the coordination service in later rounds).
"""

from __future__ import annotations

import bisect
import inspect
import os
import threading
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Set, Tuple)

from ray_tpu import exceptions as exc
from ray_tpu._private import events as trace_events
from ray_tpu._private import runtime_context
from ray_tpu._private.gcs import GCS, ActorInfo, ActorState, NodeInfo
from ray_tpu._private.lock_sanitizer import tracked_lock
from ray_tpu._private.ids import (ActorID, JobID, NodeID, ObjectID, TaskID,
                                  WorkerID, next_seqno)
from ray_tpu._private.node import ActorExecutor, Node
from ray_tpu._private.object_ref import FutureTable, ObjectRef
from ray_tpu._private.object_store import LocalObjectStore, _nbytes_of
from ray_tpu._private.refcount import LineageTable, ReferenceCounter
from ray_tpu._private.scheduler import ClusterScheduler, SchedulingError
from ray_tpu._private.serialization import SerializationContext
from ray_tpu._private.task_spec import TaskKind, TaskSpec

# Values at or below this go to the owner's in-process memory store and
# survive node failures (reference: max_direct_call_object_size = 100 KiB,
# ray_config_def.h:195).
INLINE_OBJECT_SIZE = 100 * 1024

_global_runtime: Optional["Runtime"] = None
# tracked when the sanitizer env is set BEFORE import (module scope)
_global_lock = tracked_lock("worker.global_init", reentrant=False)


def global_runtime() -> Optional["Runtime"]:
    return _global_runtime


def global_worker() -> "Runtime":
    if _global_runtime is None:
        raise RuntimeError(
            "ray_tpu has not been initialized; call ray_tpu.init() first")
    return _global_runtime


class TaskState:
    PENDING_DEPS = "PENDING_ARGS_AVAIL"
    QUEUED = "PENDING_NODE_ASSIGNMENT"
    RUNNING = "RUNNING"
    FINISHED = "FINISHED"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"


class _InFlightTask:
    __slots__ = ("spec", "state", "node_id", "cancelled", "deps_remaining",
                 "lock")

    def __init__(self, spec: TaskSpec):
        self.spec = spec
        self.state = TaskState.PENDING_DEPS
        self.node_id: Optional[NodeID] = None
        self.cancelled = False
        self.deps_remaining = 0
        self.lock = threading.Lock()


# A stream's items 0, 16, 32 ... BY THEIR INDEX are its sampled items:
# the ones whose handling both ends time and at which each end's thread
# publishes its CPU seconds (docs/serving.md, "The stream path")
STREAM_SAMPLE_MASK = 15


def sampled_items(first: int, count: int) -> int:
    """How many of the items ``first`` ... ``first + count - 1`` are
    sampled ones."""
    step = STREAM_SAMPLE_MASK + 1
    return (first + count + STREAM_SAMPLE_MASK) // step - (
        first + STREAM_SAMPLE_MASK) // step


def first_sampled(index: int) -> int:
    """The first sampled item at or after ``index``."""
    return index + (-index & STREAM_SAMPLE_MASK)


class ChunkRun(list):
    """Several consecutive items of a stream, carried as ONE object
    (``serve.ChunkRun``): a streaming generator that has fallen behind
    its source yields the items that wait together, the runtime stores
    and reports the run once and counts its items, and the consumer
    still reads one item a ``next`` (docs/serving.md, "The stream
    path"). An empty run carries nothing and is not reported."""

    __slots__ = ()


class RunItem(NamedTuple):
    """What ``GeneratorState.next_ref`` hands out for an item that
    travelled in a run: the run's ref and the item's place in it."""

    ref: ObjectRef
    offset: int


class _ThreadCpu:
    """CPU time a stream's producing or consuming thread has spent on
    it, as that thread published it: it reads its OWN
    ``time.thread_time_ns()`` when it takes the stream up (the consumer:
    at item 0), at every sampled item and when the stream ends, and
    ``ns`` is the sum of the differences between its consecutive
    readings (a reading by another thread, a retry's or a second
    consumer's, starts anew). No other thread's clock is ever read: a
    thread that has exited leaves a dangling handle."""

    __slots__ = ("ns", "ident", "_last")

    def __init__(self):
        self.ns = 0
        self.ident = None               # of the thread that read last

    def publish(self) -> None:
        now, me = time.thread_time_ns(), threading.get_ident()
        if self.ident == me:
            self.ns += now - self._last
        self._last, self.ident = now, me


class SampledItem:
    """A sampled item on the thread that handles it: a replica's chunk,
    from its token taken off the request's stream to the runtime having
    reported it; a consumer's, from ``next_ref`` having returned to the
    value being in hand. ONE pair of clock reads feeds ``span`` (the
    caller's annotation for jax's profiler, on the device trace's clock
    and a flag check while no profiler runs; None where the process has
    not loaded jax) and the cells ``timed_ns`` / ``timed_items`` of
    ``account``, which this thread alone writes (the engine's ``_Phase``
    idiom). A run of items is handled, and so timed, ONCE: ``items`` is
    how many sampled ones it holds. Never around a wait for an item: a
    device gap belongs to the span that covers most of it
    (``benchmark/lib/trace.py``)."""

    __slots__ = ("account", "span", "items", "t0")

    def __init__(self, account, span, items: int = 1):
        self.account = account
        self.span = span
        self.items = items

    def __enter__(self):
        if self.span is not None:
            self.span.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        account = self.account
        account.timed_ns += time.perf_counter_ns() - self.t0
        account.timed_items += self.items
        if self.span is not None:
            self.span.__exit__(*exc)
        return False


class GeneratorState:
    """Producer/consumer state for a streaming-generator task.

    Reference: ``ReportGeneratorItemReturns`` proactive item reporting +
    ``GeneratorBackpressureWaiter`` (core_worker/generator_waiter.h).

    ``items`` holds one ref an OBJECT the producer reported: an item, or
    a ``ChunkRun`` of them, ``starts`` the index of each object's first
    item. A consumer asks by ITEM (``next_ref``), and while no run was
    reported an item's index is its object's. Back-pressure
    (``backpressure_num_objects``) counts OBJECTS, a run as one: the
    producer waits while that many were reported beyond the newest one
    a consumer has been handed an item of (``consumed``).

    It is also the stream's account, which ``Runtime.generator_stats``
    sums over all streams, in ITEMS but for the objects reported:
    ``produced`` (items reported) and ``handed_out`` (items ``next_ref``
    and ``take_waiting`` handed to a consumer), each end's CPU time, and
    what the consumer timed of its sampled items. Every cell has ONE
    writing thread or is written under ``cond``, and is an integer
    (times in ns): sums of them are exact in any order, so a reading
    never goes down when a stream moves into the totals.
    """

    def __init__(self, backpressure_num_objects: int = -1):
        self.cond = threading.Condition()
        self.items: List[ObjectRef] = []
        self.starts: List[int] = []
        self.produced = 0
        self.consumed = 0
        self.handed_out = 0
        self.finished = False
        self.error: Optional[BaseException] = None
        self.backpressure = backpressure_num_objects
        self.producer_cpu = _ThreadCpu()
        self.consumer_cpu = _ThreadCpu()
        self.timed_ns = 0               # the consumer's ``SampledItem``s
        self.timed_items = 0
        # the consumer was told the stream's end; ``generator_stats``
        # has folded the account into the runtime's totals
        self.read_to_end = False
        self.folded = False

    def account(self) -> Dict[str, Any]:
        """The cells ``Runtime.generator_stats`` sums."""
        return {"stream_items_reported": self.produced,
                "stream_objects_reported": len(self.items),
                "stream_items_consumed": self.handed_out,
                "stream_producer_cpu_ns": self.producer_cpu.ns,
                "stream_consumer_cpu_ns": self.consumer_cpu.ns,
                "stream_consume_ns": self.timed_ns,
                "stream_items_timed_consume": self.timed_items}

    def report_item(self, ref: ObjectRef, count: int = 1) -> None:
        """One object: an item, or a run of ``count`` of them."""
        with self.cond:
            self.items.append(ref)
            self.starts.append(self.produced)
            self.produced += count
            self.cond.notify_all()
            if self.backpressure > 0:
                while (not self.finished and len(self.items) - self.consumed
                       >= self.backpressure):
                    self.cond.wait(1.0)

    def finish(self, error: Optional[BaseException] = None) -> None:
        with self.cond:
            self.finished = True
            self.error = error
            self.cond.notify_all()

    def _object_of(self, index: int) -> int:
        """Which of ``items`` holds the reported item ``index``; under
        ``cond``. While no run was reported an item is an object."""
        if self.produced == len(self.items):
            return index
        return bisect.bisect_right(self.starts, index) - 1

    def next_ref(self, index: int, timeout: Optional[float] = None):
        """Wait for item ``index`` and hand it out: its ``ObjectRef``,
        or, where it travelled in a run, a ``RunItem`` (a reader of
        values takes what waits beyond the item with it,
        ``take_waiting``; one that wants a ref an item splits the run,
        ``Runtime.run_item_ref``)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self.cond:
            while True:
                if index < self.produced:
                    at = self._object_of(index)
                    out, starts = self.items[at], self.starts
                    if (starts[at + 1] if at + 1 < len(starts)
                            else self.produced) - starts[at] > 1:
                        out = RunItem(out, index - starts[at])
                    self.consumed = max(self.consumed, at + 1)
                    self.handed_out += 1
                    self.cond.notify_all()
                    break
                if self.finished:
                    self.consumer_cpu.publish()
                    self.read_to_end = True
                    if self.error is not None:
                        raise self.error
                    raise StopIteration
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise exc.GetTimeoutError("generator item timeout")
                self.cond.wait(remaining)
        if not index & STREAM_SAMPLE_MASK:
            # outside the stream's lock; the consumer's first reading is
            # item 0's (the wait for it cost no CPU)
            self.consumer_cpu.publish()
        return out

    def take_waiting(self, index: int) -> Tuple[List[ObjectRef], int]:
        """The reader that was handed item ``index`` takes with it
        everything reported beyond it, the rest of its run and the
        objects after: their refs, the first the object that holds
        ``index``, and how many further items they carry."""
        with self.cond:
            count = self.produced - index - 1
            refs = self.items[self._object_of(index):]
            self.handed_out += count
            self.consumed = len(self.items)
            self.cond.notify_all()
        if sampled_items(index + 1, count):
            self.consumer_cpu.publish()
        return refs, count


def _ns_as_seconds(sums: Dict[str, int]) -> Dict[str, Any]:
    """``stats``' form of summed cells: a ``*_ns`` key as ``*_s``."""
    return {(k[:-2] + "s" if k.endswith("_ns") else k):
            (v * 1e-9 if k.endswith("_ns") else v) for k, v in sums.items()}


# ``Runtime.generator_stats`` of a runtime that has seen no stream
NO_STREAMS = _ns_as_seconds(dict(GeneratorState().account(), streams_live=0))


class Runtime:
    def __init__(self, num_nodes: int = 1,
                 resources_per_node: Optional[Dict[str, float]] = None,
                 object_store_memory: int = 2 * 1024 ** 3,
                 namespace: Optional[str] = None,
                 session_dir: Optional[str] = None,
                 cluster: Optional[str] = None,
                 address: Optional[str] = None,
                 job_config=None):
        self.job_id = JobID.from_random()
        self.worker_id = WorkerID.from_random()
        self.namespace = namespace or self.job_id.hex()
        # per-job config (reference: JobConfig serialized at connect —
        # worker.py:2347): job-default runtime env consumed by
        # prepare_runtime_env. code_search_path rides that env as
        # py_modules — PRE-EXISTING pool workers (forked before this
        # init) never see driver sys.path edits, but py_modules
        # materialize per task in any worker; it also joins the
        # driver's own sys.path for local imports.
        self.job_config = job_config
        self._job_default_env = None
        if job_config is not None:
            import sys as _sys
            env = dict(job_config.runtime_env or {})
            if job_config.code_search_path:
                paths = [os.path.abspath(p)
                         for p in job_config.code_search_path]
                env["py_modules"] = (list(env.get("py_modules") or [])
                                     + paths)
                for p in paths:
                    if p not in _sys.path:
                        _sys.path.insert(0, p)
            self._job_default_env = env or None
        self.session_dir = session_dir or os.path.join(
            "/tmp", "ray_tpu", f"session_{self.job_id.hex()}")
        os.makedirs(self.session_dir, exist_ok=True)

        # Worker log capture + tail-to-driver (reference:
        # _private/log_monitor.py + worker.py:2164 print_worker_logs).
        # The log dir is process-stable (NOT per-session): pooled workers
        # outlive init/shutdown cycles and must keep a valid log target;
        # start_at_end skips any previous session's lines.
        self._log_monitor = None
        from ray_tpu._private import log_monitor as _lm
        if _lm.log_to_driver_enabled():
            self._log_monitor = _lm.LogMonitor(
                _lm.session_log_dir(), _lm.make_driver_printer(),
                start_at_end=True)

        self.gcs = GCS()
        self.scheduler = ClusterScheduler()
        # Multi-tenant fair share (ray_tpu/tenancy): submit-time
        # admission verdicts + deficit-ordered dispatch. The manager is
        # always constructed (job records and /api/jobs work either
        # way); enforcement only hooks into scheduler/node dispatch
        # when the `fairshare` flag is on, so the single-tenant hot
        # path stays untouched.
        from ray_tpu.tenancy import TenancyManager
        self.tenancy = TenancyManager(runtime=self)
        if self.tenancy.enabled:
            self.scheduler.tenancy = self.tenancy
        self.futures = FutureTable()
        self.lineage = LineageTable()
        self.refcounter = ReferenceCounter(on_zero=self._free_object)
        self.serialization = SerializationContext()

        # Owner memory store: inline values + error objects; survives node
        # death (reference: CoreWorkerMemoryStore).
        self.memory_store = LocalObjectStore(
            NodeID.nil(), capacity_bytes=1 << 62)

        self._nodes: Dict[NodeID, Node] = {}  #: guarded by self._nodes_lock
        self._nodes_lock = tracked_lock("worker.nodes")    # reentrant
        #: guarded by self._loc_lock
        self._locations: Dict[ObjectID, Set[NodeID]] = {}
        self._loc_lock = tracked_lock("worker.locations", reentrant=False)
        # Objects whose every copy died with a node; reconstruction is
        # triggered lazily on the next get/wait/dependency touch.
        self._lost: Set[ObjectID] = set()
        # Proactive dep-push staging (objectplane): a small shared pool
        # (never thread-per-enqueue) + an in-flight (dep, dest) table so
        # one fan-out stages each dep once.
        from ray_tpu._private.thread_pool import DaemonThreadPool
        self._prefetch_pool = DaemonThreadPool(2, name="push-prefetch")
        #: guarded by self._prefetch_lock
        self._prefetch_inflight: Set[tuple] = set()
        self._prefetch_lock = tracked_lock("worker.push_prefetch",
                                           reentrant=False)

        self._tasks: Dict[TaskID, _InFlightTask] = {}  #: guarded by self._tasks_lock
        self._tasks_lock = tracked_lock("worker.tasks", reentrant=False)

        #: guarded by self._actor_lock
        self._actor_pending_tasks: Dict[ActorID, List[TaskSpec]] = {}
        self._actor_lock = tracked_lock("worker.actors")   # reentrant
        self._actor_executors: Dict[ActorID, ActorExecutor] = {}
        # actor_id -> DaemonHandle for actors hosted on node daemons
        self._remote_actors: Dict[ActorID, Any] = {}

        self._generators: Dict[TaskID, GeneratorState] = {}
        # what the streams that ended and were read to their end
        # counted (``generator_stats`` folds them in)
        self._generator_totals = GeneratorState().account()
        self._generator_stats_lock = threading.Lock()

        # ICI-topology-aware gang scheduling: when a slice topology is
        # declared, TPU placement-group bundles claim contiguous
        # sub-slices instead of landing by resource count
        # (bundle_scheduling_policy.h role; SURVEY §2.3 gang row).
        from ray_tpu._private.config import cfg as _cfg
        # deterministic fault injection: the `failpoints` flag activates
        # the registry for this process (spawned daemons/heads/workers
        # activate from the inherited RAY_TPU_FAILPOINTS env var)
        from ray_tpu._private import failpoints as _failpoints
        _failpoints.maybe_activate_from_config(_cfg())
        # network chaos rides the same activation discipline: the
        # `net_chaos` flag arms this process's link policies and
        # exports RAY_TPU_NET_CHAOS for spawned processes
        from ray_tpu._private import netchaos as _netchaos
        _netchaos.maybe_activate_from_config(_cfg())
        _netchaos.set_local_role("driver")
        from ray_tpu._private import eventloop as _eventloop
        _eventloop.set_proc_label("driver")
        self.tpu_topology = None
        _topo_spec = _cfg().tpu_topology
        if _topo_spec:
            from ray_tpu.parallel.topology import TpuTopologyManager
            self.tpu_topology = TpuTopologyManager.from_spec(_topo_spec)

        from ray_tpu.util.placement_group import PlacementGroupManager
        self.pg_manager = PlacementGroupManager(self)
        self._shutdown = False
        self.stats = {"tasks_submitted": 0, "tasks_finished": 0,
                      "tasks_retried": 0, "objects_reconstructed": 0,
                      "actor_restarts": 0,
                      # graceful-drain counters (surfaced on /metrics as
                      # ray_tpu_drains_total etc. by prometheus_text)
                      "drains_total": 0, "drain_objects_migrated": 0,
                      "drain_actors_migrated": 0,
                      "drain_escalations_total": 0}
        from ray_tpu._private.events import TaskEventBuffer
        self.task_events = TaskEventBuffer()
        # continuous profiler (profiling_hz knob, default off): the
        # driver lane of `ray-tpu profile` / util.state.cluster_profile
        from ray_tpu.util import profiling as _profiling
        _profiling.maybe_start_from_config("driver")

        # Process workers: the default execution path for host-plane
        # tasks/actors (VERDICT r1 #2). Accelerator-plane work (TPU
        # resources / device-tier args) stays in this process — it owns
        # the mesh.
        from ray_tpu._private.worker_process import ProcessRouter
        self.process_router = ProcessRouter(self)

        # OOM defense: sample driver+worker RSS, kill a worker per policy
        # on threshold breach (reference: common/memory_monitor.h:52 +
        # raylet/worker_killing_policy*.h). The driver (mesh owner) is
        # never a victim.
        from ray_tpu._private.memory_monitor import MemoryMonitor
        self.memory_monitor = MemoryMonitor(self)
        from ray_tpu._private.config import cfg
        if cfg().memory_monitor:
            self.memory_monitor.start()

        if resources_per_node is None:
            resources_per_node = self._detect_resources()
        self.cluster_backend = None
        if cluster is None:
            cluster = cfg().cluster or None
        if address:
            # Join an EXISTING `ray-tpu start` cluster as a new driver
            # (reference: ray.init(address=...) against a running GCS).
            from ray_tpu._private.cluster import ClusterBackend
            backend = ClusterBackend.attach(self, address)
            self.cluster_backend = backend
            for node_id, handle in backend.daemons.items():
                self.add_remote_node(
                    handle, dict(backend.node_resources[node_id]))
        elif cluster == "daemons":
            # Real head + node-daemon OS processes behind the wire
            # protocol; every schedulable node is a daemon. In-process /
            # accelerator work still executes driver-side, on the
            # assigned node's dispatch thread (see _execute_inline).
            from ray_tpu._private.cluster import ClusterBackend
            backend = ClusterBackend(self, num_nodes,
                                     dict(resources_per_node),
                                     object_store_bytes=object_store_memory)
            self.cluster_backend = backend
            for node_id, handle in backend.daemons.items():
                self.add_remote_node(handle, dict(resources_per_node))
        else:
            for _ in range(num_nodes):
                self.add_node(dict(resources_per_node),
                              object_store_memory=object_store_memory)
        if self.cluster_backend is not None and self.tenancy.enabled:
            # adopt quota records persisted at the head (other drivers
            # or a previous incarnation of this one may have set them)
            self.tenancy.load_from_head(self.cluster_backend.head)

    # ------------------------------------------------------------------
    # cluster topology
    # ------------------------------------------------------------------
    @staticmethod
    def _detect_resources() -> Dict[str, float]:
        # the driver owns the chip: a backend that cannot initialize
        # raises here instead of registering a node with no TPU
        from ray_tpu._private.platform import chip_devices
        res: Dict[str, float] = {"CPU": float(os.cpu_count() or 1)}
        chips = chip_devices()
        if chips:
            res["TPU"] = float(len(chips))
        return res

    def add_node(self, resources: Dict[str, float],
                 labels: Optional[Dict[str, str]] = None,
                 object_store_memory: int = 2 * 1024 ** 3) -> Node:
        node_id = NodeID.from_random()
        store = LocalObjectStore(
            node_id, object_store_memory,
            spill_dir=os.path.join(self.session_dir, "spill",
                                   node_id.hex()[:8]))
        node = Node(node_id, resources, labels or {}, store,
                    execute_task=self._execute_on_node)
        if self.tenancy.enabled:
            node.tenancy = self.tenancy
        with self._nodes_lock:
            self._nodes[node_id] = node
        self.gcs.register_node(node.info())
        from ray_tpu._private.scheduler import bump_cluster_epoch
        bump_cluster_epoch()
        return node

    def add_remote_node(self, handle, resources: Dict[str, float]) -> Node:
        """Register a node daemon process as a schedulable node. The Node
        machinery (ledger, dispatch queue, backlog) runs driver-side —
        single-controller placement — while execution, workers, and the
        object payloads live in the daemon."""
        from ray_tpu._private.cluster import RemoteStore
        store = RemoteStore(handle)
        node = Node(handle.node_id, resources, {}, store,
                    execute_task=self._execute_on_remote_node)
        if self.tenancy.enabled:
            node.tenancy = self.tenancy
        node.daemon = handle
        handle.runtime = self   # node_pressure pushes resolve the Node
        # proactive dep staging: enqueue-time pushes overlap the
        # transfer with the task's queue wait (PushManager dedupes)
        node.prefetch = (lambda spec, _node=node:
                         self._push_prefetch_deps(spec, _node))
        with self._nodes_lock:
            self._nodes[handle.node_id] = node
        self.gcs.register_node(node.info())
        from ray_tpu._private.scheduler import bump_cluster_epoch
        bump_cluster_epoch()
        return node

    def _push_prefetch_deps(self, spec: TaskSpec, node: Node) -> None:
        """Proactively push task deps that live only on OTHER daemon
        nodes to ``node`` (reference: ``object_manager.cc:354 Push``) —
        by the time the task (or a same-node consumer) needs them, a
        local copy exists. The PushManager dedupes in-flight pushes,
        copies the destination already holds, and chunks a concurrent
        pull already transferred; failures are harmless (the classic
        pull/owner path still serves the object on demand)."""
        deps = spec.dependencies()
        if not deps or getattr(node, "daemon", None) is None:
            return
        from ray_tpu._private.config import cfg
        if not cfg().push_prefetch:
            return
        if getattr(node, "pressure_level", "ok") != "ok":
            # soft/hard memory pressure: stop staging optional copies
            # onto the node — the demand pull path still serves the
            # task's args when it actually runs (pressure.py)
            return
        with self._loc_lock:
            locs = {dep: list(self._locations.get(dep, ()))
                    for dep in deps}
        work = []
        for dep, node_ids in locs.items():
            if not node_ids or node.node_id in node_ids:
                continue
            src = self.get_node(node_ids[0])
            src_daemon = getattr(src, "daemon", None)
            meta_of = getattr(getattr(src, "store", None), "meta_of",
                              None)
            if (src is None or not src.alive or src_daemon is None
                    or meta_of is None or node.store.contains(dep)):
                continue
            # driver-side (dep, dest) in-flight dedupe: a fan-out of
            # tasks sharing one dep must stage it ONCE, not once per
            # enqueue (the daemon's PushManager dedupes too, but this
            # avoids the redundant RPCs entirely)
            fly = (dep, node.node_id)
            with self._prefetch_lock:
                if fly in self._prefetch_inflight:
                    continue
                self._prefetch_inflight.add(fly)
            try:
                key, nbytes, raw = meta_of(dep)
            except KeyError:
                with self._prefetch_lock:
                    self._prefetch_inflight.discard(fly)
                continue
            work.append((dep, fly, src_daemon, key, nbytes, raw))
        if not work:
            return

        def run_one(dep, fly, src_daemon, key, nbytes, raw) -> None:
            try:
                out = src_daemon.push_object(
                    key, node.daemon.addr, ref=dep.binary())
                if out.get("ok"):
                    node.store.register_remote(dep, key, nbytes,
                                               raw=raw)
                    with self._loc_lock:
                        self._locations.setdefault(dep, set()).add(
                            node.node_id)
                    self.stats["objects_push_prefetched"] = (
                        self.stats.get("objects_push_prefetched", 0)
                        + 1)
            except Exception:
                pass            # on-demand pull/owner path covers it
            finally:
                with self._prefetch_lock:
                    self._prefetch_inflight.discard(fly)

        # small shared pool, never thread-per-task: a 10k-task fan-out
        # with remote deps must not spawn 10k threads each parked in a
        # (bounded) push RPC
        for item in work:
            self._prefetch_pool.submit(lambda it=item: run_one(*it))

    def _execute_on_remote_node(self, spec: TaskSpec, node: Node) -> None:
        """Task execution on a node-daemon process (wire protocol:
        RequestWorkerLease + PushTask; reference call stack SURVEY §3.1).
        """
        from ray_tpu._private.cluster import DaemonCrashed
        if spec.kind == TaskKind.ACTOR_CREATION:
            self._execute_actor_creation(spec, node)
            return
        if spec.kind == TaskKind.ACTOR_TASK:
            self._run_actor_task_from_node(spec, node)
            return
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
        if inflight is not None:
            with inflight.lock:
                if inflight.cancelled:
                    return
                inflight.state = TaskState.RUNNING
        # same RUNNING transition the in-process path records: the
        # timeline/chrome-trace pairs RUNNING with FINISHED/FAILED
        self.task_events.record(
            task_id=spec.task_id.hex(), name=spec.name, event="RUNNING",
            node_id=node.node_id.hex())
        try:
            args, kwargs = self._resolve_args(spec)
        except exc.TaskError as te:
            self._finish_task(spec, node, error=te)
            return
        from ray_tpu._private.cluster import RemoteWorkerCrashed
        from ray_tpu._private.worker_process import _wants_accelerator
        demand = getattr(spec, "pg_demand", None) or spec.resources
        payload = None
        if not getattr(spec, "in_process", False) and \
                not _wants_accelerator(demand):
            payload = self.process_router._serialize_payload(spec, args,
                                                             kwargs)
        if payload is None:
            # Accelerator-plane / in_process / unserializable work stays
            # in the mesh-owning driver process: run it right here on the
            # node's dispatch thread (resources stay accounted on this
            # node; the compute itself is driver-side XLA).
            self._execute_inline(spec, node, args, kwargs)
            return
        fid, args_blob = payload
        try:
            kind, value = node.daemon.execute_task(spec, fid, args_blob)
        except RemoteWorkerCrashed as crash:
            # one worker died; the daemon (node) is fine — plain retry
            self._on_process_task_crash(spec, node, crash)
            return
        except DaemonCrashed as crash:
            self._on_daemon_crash(node)
            self._on_process_task_crash(spec, node, crash)
            return
        self._finish_remote_outcome(spec, node, kind, value)

    def _finish_remote_outcome(self, spec: TaskSpec, node: Node,
                               kind: str, value) -> None:
        if kind == "err":
            with self._tasks_lock:
                inflight = self._tasks.get(spec.task_id)
            if (inflight is not None and inflight.cancelled
                    and isinstance(value, KeyboardInterrupt)):
                self._release_task_resources(spec, node)
                self._fail_task(spec, exc.TaskError(
                    exc.TaskCancelledError(spec.task_id), spec.name))
                return
            self._finish_task(spec, node, error=exc.TaskError(
                value, spec.name))
            return
        if kind == "gen" or spec.num_returns in ("streaming", "dynamic"):
            self._drain_generator(spec, node, value)
            return
        if kind == "stored":
            daemon_key, nbytes = value
            n = spec.num_returns
            if n == 1 or not isinstance(n, int):
                t_result = (time.perf_counter()
                            if getattr(spec, "trace_sampled", False)
                            else 0.0)
                oid = spec.return_ids[0]
                node.store.register_remote(oid, daemon_key, nbytes)
                with self._loc_lock:
                    self._locations.setdefault(oid, set()).add(
                        node.node_id)
                self.task_events.record(task_id=spec.task_id.hex(),
                                        name=spec.name, event="FINISHED")
                self._release_task_resources(spec, node)
                self.futures.complete(oid)
                if t_result:
                    now = time.perf_counter()
                    trace_events.record_phase_rt(
                        spec, "result", now - t_result,
                        node.node_id.hex(),
                        start_wall=trace_events.wall_at(t_result),
                        end_mono=now)
                self._on_task_done(spec, TaskState.FINISHED)
                return
            # multi-return tuple stored remotely: fetch once and split
            value = node.store.daemon.get_object_blob(daemon_key)
            import cloudpickle as _cp
            value = _cp.loads(value)
            kind = "ok"
        self._finish_task(spec, node, result=value)

    def _on_daemon_crash(self, node: Node) -> None:
        """Daemon RPC failure observed first-hand: report to the head and
        run the node-death flow (objects lost, actors restart)."""
        backend = self.cluster_backend
        handle = getattr(node, "daemon", None)
        if backend is None or handle is None:
            return
        backend.report_daemon_dead(handle, "rpc failure")
        if self.get_node(node.node_id) is not None:
            try:
                self.remove_node(node, _from_cluster=True)
            except Exception:
                pass

    def _run_actor_task_from_node(self, spec: TaskSpec, node: Node) -> None:
        # Actor tasks are driven by the ActorExecutor, not the dispatch
        # queue; reaching here means a retry raced — resubmit properly.
        inflight = None
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
        self._submit_actor_task(spec, inflight, spec.dependencies())

    def remove_node(self, node: Node, _from_cluster: bool = False) -> None:
        """Simulate node failure: lose its objects, tasks, and actors.
        For daemon-backed nodes this hard-kills the daemon process."""
        from ray_tpu._private.scheduler import bump_cluster_epoch
        bump_cluster_epoch()    # before the pop: no stale cache window
        with self._nodes_lock:
            present = self._nodes.pop(node.node_id, None) is not None
        if not present:
            # already removed — a clean drain completion and the head's
            # death event (or deadline escalation) race here; the death
            # flow must run exactly once
            return
        handle = getattr(node, "daemon", None)
        if handle is not None and not _from_cluster:
            handle.sigkill()
            if self.cluster_backend is not None:
                try:
                    self.cluster_backend.head.mark_node_dead(
                        node.node_id.hex(), "removed")
                except Exception:
                    pass
        pending_by_actor = node.shutdown()
        self.gcs.mark_node_dead(node.node_id)
        # Objects on this node are lost.
        lost = node.store.object_ids()
        with self._loc_lock:
            for oid in lost:
                locs = self._locations.get(oid)
                if locs is not None:
                    locs.discard(node.node_id)
                    if not locs:
                        del self._locations[oid]
                        self.futures.reset(oid)
                        self._lost.add(oid)
        node.store.close()
        self.pg_manager.on_node_death(node.node_id)
        # Actors on this node die (and may restart).
        for actor_id, pending in pending_by_actor.items():
            self._handle_actor_death(actor_id, "node died",
                                     pending_tasks=pending,
                                     may_restart=True)

    def nodes(self) -> List[Node]:
        with self._nodes_lock:
            return list(self._nodes.values())

    def alive_nodes(self) -> List[Node]:
        return [n for n in self.nodes() if n.alive]

    def schedulable_nodes(self) -> List[Node]:
        """Alive nodes accepting NEW placements (draining excluded);
        falls back to every alive node when all are draining."""
        alive = self.alive_nodes()
        return [n for n in alive
                if not getattr(n, "draining", False)] or alive

    def get_node(self, node_id: NodeID) -> Optional[Node]:
        with self._nodes_lock:
            return self._nodes.get(node_id)

    def head_node(self) -> Node:
        nodes = self.alive_nodes()
        if not nodes:
            raise RuntimeError("cluster has no alive nodes")
        return nodes[0]

    def cluster_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n in self.alive_nodes():
            for k, v in n.ledger.total.items():
                out[k] = out.get(k, 0.0) + v
        return out

    def available_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for n in self.alive_nodes():
            for k, v in n.ledger.available().items():
                out[k] = out.get(k, 0.0) + v
        return out

    # ------------------------------------------------------------------
    # graceful node drain (preemption / downscale / maintenance)
    # ------------------------------------------------------------------
    def drain_node(self, node, deadline_s: Optional[float] = None,
                   reason: str = "preemption") -> bool:
        """Gracefully drain a node: no new placements land on it, its
        queued tasks resubmit elsewhere, primary object replicas and
        actors migrate off it proactively, and when its in-flight work
        completes it leaves the cluster cleanly. If the deadline expires
        first, the drain escalates into the ordinary node-death path
        (lineage reconstruction covers anything unmigrated).

        ``node`` is a Node, NodeID, or node-id hex string. Returns True
        if a drain was started (False: unknown/dead/already draining).
        """
        if not isinstance(node, Node):
            node_id = (NodeID.from_hex(node) if isinstance(node, str)
                       else node)
            node = self.get_node(node_id)
            if node is None:
                return False
        if deadline_s is None:
            from ray_tpu._private.config import cfg
            deadline_s = cfg().drain_deadline_s
        backend = self.cluster_backend
        if backend is not None and getattr(node, "daemon", None) is not None:
            # Publish through the head so the DRAINING membership state
            # (and its deadline escalation) outlives this driver — and
            # survives a head restart via the persisted drain record.
            try:
                backend.head.drain_node(node.node_id.hex(), deadline_s,
                                        reason)
            except Exception:
                pass        # head unreachable: drain locally anyway
        started = self.begin_node_drain(node, deadline_s, reason)
        # the head's own node_drain event may have won the race to start
        # the local migration — that still counts as "draining now"
        return started or bool(getattr(node, "draining", False))

    def begin_node_drain(self, node: Node, deadline_s: float,
                         reason: str) -> bool:
        """Idempotent driver-side entry (also fed by the head's
        ``node_drain`` pubsub event): flips the node to DRAINING and
        starts the migration worker."""
        with self._nodes_lock:
            if (not node.alive or getattr(node, "draining", False)
                    or self._nodes.get(node.node_id) is not node):
                return False
            node.start_drain()
        self.stats["drains_total"] += 1
        threading.Thread(
            target=self._drain_node_worker,
            args=(node, deadline_s, reason), daemon=True,
            name=f"drain-{node.node_id.hex()[:8]}").start()
        return True

    def _drain_node_worker(self, node: Node, deadline_s: float,
                           reason: str) -> None:
        deadline = time.monotonic() + max(0.0, deadline_s)
        # flush coalesced frees first: the draining daemon's store should
        # not migrate (or hold) objects the driver already released
        for handle in ([getattr(node, "daemon", None)]
                       + [getattr(n, "daemon", None)
                          for n in self.alive_nodes()]):
            if handle is not None:
                try:
                    handle.flush_frees()
                except Exception:
                    pass
        try:
            self._migrate_node_objects(node)
            self._migrate_node_actors(node, reason, deadline=deadline)
        except Exception:
            pass    # escalation still bounds the drain; lineage recovers
        while time.monotonic() < deadline:
            with node._running_lock:
                busy = bool(node._running)
            if not busy and node._backlog_n == 0 and not node._inbox:
                # Clean drain: sweep again — results stored (and actors
                # created) WHILE draining live on this node too — then
                # leave the cluster with zero reconstruction debt.
                try:
                    self._migrate_node_actors(node, reason,
                                              deadline=deadline)
                    self._migrate_node_objects(node)
                except Exception:
                    pass
                self._finish_drain(node, reason)
                return
            time.sleep(0.05)
        self._escalate_drain(node, reason)

    def _migrate_node_objects(self, node: Node) -> int:
        """Copy primary (sole-replica) objects off the draining node so
        the eventual departure loses nothing (``objects_reconstructed``
        stays 0 when migration wins the race against the deadline)."""
        targets = [n for n in self.alive_nodes()
                   if n.node_id != node.node_id
                   and not getattr(n, "draining", False)]
        if not targets:
            return 0
        from ray_tpu._private import failpoints as _fp
        moved = 0
        i = 0
        for oid in node.store.object_ids():
            with self._loc_lock:
                locs = self._locations.get(oid, set())
                if locs - {node.node_id}:
                    continue        # a replica already lives elsewhere
            target = targets[i % len(targets)]
            i += 1
            if _fp.ENABLED:
                try:
                    _fp.fire("drain.migrate_object", oid=oid.hex())
                except Exception:
                    continue    # this object stays; lineage covers it
            try:
                src_daemon = getattr(node, "daemon", None)
                dst_daemon = getattr(target, "daemon", None)
                if src_daemon is not None and dst_daemon is not None:
                    # daemon→daemon transfer: bytes move directly over
                    # the object plane, never through the driver —
                    # proactive push first (chunked/deduped
                    # PushManager), pull as the fallback direction
                    key, nbytes, raw = node.store.meta_of(oid)
                    moved_ok = False
                    try:
                        moved_ok = src_daemon.push_object(
                            key, dst_daemon.addr,
                            ref=oid.binary()).get("ok", False)
                    except Exception:
                        moved_ok = False
                    if not moved_ok and not dst_daemon.pull_object(
                            key, from_addr=src_daemon.addr, priority=1):
                        continue
                    target.store.register_remote(oid, key, nbytes,
                                                 raw=raw)
                else:
                    value = node.store.get(oid)
                    # reuse the size cached at insert time — migrating
                    # a large pytree must not pay a fresh deep walk
                    target.store.put(oid, value,
                                     nbytes=node.store.nbytes_of(oid)
                                     or _nbytes_of(value))
            except Exception:
                continue
            with self._loc_lock:
                self._locations.setdefault(oid, set()).add(
                    target.node_id)
            moved += 1
        if moved:
            self.stats["drain_objects_migrated"] += moved
        return moved

    def _migrate_node_actors(self, node: Node, reason: str,
                             deadline: Optional[float] = None) -> int:
        """Restart the draining node's actors on surviving nodes via the
        existing restart machinery — graceful, so pending tasks replay
        on the new incarnation instead of failing, and the planned move
        does not consume the actors' max_restarts budget."""
        from ray_tpu._private.task_spec import (
            NodeAffinitySchedulingStrategy)
        with node._actors_lock:
            actors = dict(node.actors)
        migrate: Dict[ActorID, ActorExecutor] = {}
        for actor_id, executor in actors.items():
            info = self.gcs.get_actor_info(actor_id)
            strat = getattr(getattr(info, "creation_spec", None),
                            "scheduling_strategy", None)
            if (isinstance(strat, NodeAffinitySchedulingStrategy)
                    and not strat.soft
                    and strat.node_id == node.node_id.hex()):
                # hard-pinned HERE: it cannot live anywhere else —
                # leave it to finish work until the node departs
                continue
            migrate[actor_id] = executor
        with node._actors_lock:
            for actor_id in migrate:
                node.actors.pop(actor_id, None)
        moved = 0
        cause = f"node draining ({reason})"
        for actor_id, executor in migrate.items():
            pending = executor.kill(cause)
            # Let an IN-FLIGHT method finish before the actor's worker
            # process is recycled: kill() stops dispatch, so the
            # executor threads exit right after the current call — a
            # planned move should not crash a running call. Bounded by
            # the drain deadline (a stuck call escalates instead).
            for t in executor._threads:
                budget = 1.0
                if deadline is not None:
                    budget = min(budget, max(
                        0.0, deadline - time.monotonic()))
                t.join(timeout=budget)
            try:
                self._handle_actor_death(actor_id, cause,
                                         pending_tasks=pending,
                                         may_restart=True, graceful=True)
                moved += 1
            except Exception:
                continue
        if moved:
            self.stats["drain_actors_migrated"] += moved
        return moved

    def _finish_drain(self, node: Node, reason: str) -> None:
        """Clean completion: the node leaves via the normal removal flow,
        but with its objects replicated and actors already elsewhere."""
        if self.get_node(node.node_id) is None:
            return      # a death event won the race
        backend = self.cluster_backend
        handle = getattr(node, "daemon", None)
        if backend is not None and handle is not None:
            try:
                backend.head.mark_node_dead(node.node_id.hex(),
                                            f"drained ({reason})")
            except Exception:
                pass
            try:
                handle.stop()
            except Exception:
                pass
        try:
            self.remove_node(node, _from_cluster=True)
        except Exception:
            pass

    def count_drain_escalation(self, node: Node) -> None:
        """Exactly-once escalation accounting: the driver's own deadline
        timer and the head's death event race to escalate the same
        drain — whichever wins counts, the loser is a no-op."""
        with self._nodes_lock:
            if getattr(node, "_drain_escalated", False):
                return
            node._drain_escalated = True
        self.stats["drain_escalations_total"] += 1

    def _escalate_drain(self, node: Node, reason: str) -> None:
        """Deadline expired with work still on the node: fall back to
        the ordinary node-death path (hard kill; retries + lineage
        reconstruction recover whatever did not migrate in time)."""
        if self.get_node(node.node_id) is None:
            return      # drained cleanly / head escalated first
        self.count_drain_escalation(node)
        from ray_tpu._private import failpoints as _fp
        if _fp.ENABLED:
            try:
                # delay arm stretches the escalation window; an error
                # arm must NOT suppress the escalation (the node would
                # linger draining forever)
                _fp.fire("drain.deadline", node=node.node_id.hex())
            except Exception:
                pass
        try:
            self.remove_node(node)
        except Exception:
            pass

    def on_node_task_drained(self, spec: TaskSpec, node: Node) -> None:
        """A queued-but-unstarted task handed back by a draining node:
        reschedule it elsewhere WITHOUT consuming a retry (planned
        departure, not a failure)."""
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
        if inflight is None:
            return
        with inflight.lock:
            if inflight.cancelled:
                return
        # one bounce only: if the scheduler sends it back (nothing else
        # fits), the draining node's dispatch loop runs it locally
        spec._drain_bounced = True
        self._schedule(spec, inflight)

    # ------------------------------------------------------------------
    # objects
    # ------------------------------------------------------------------
    def put(self, value: Any, _owner_pin: bool = False) -> ObjectRef:
        if isinstance(value, ObjectRef):
            raise TypeError("put() of an ObjectRef is not allowed "
                            "(pass the ref itself instead)")
        oid = ObjectID.from_random()
        ref = ObjectRef(oid, owner_hex=self.worker_id.hex(), task_name="put")
        self._store_value(oid, value)
        self.futures.complete(oid)
        if _owner_pin:
            self.refcounter.pin(oid)
        return ref

    def put_stored(self, oid_bin: bytes, key: bytes, nbytes: int,
                   raw, node_hex: str) -> ObjectRef:
        """Owner-side registration of a worker DIRECT put: the payload
        is already written + sealed in ``node``'s arena under ``key``
        (zero-copy object plane) — record ownership, location, and the
        raw-tier dtype/shape; no value ever reaches the driver."""
        oid = ObjectID(bytes(oid_bin))
        node = self.get_node(NodeID.from_hex(node_hex))
        store = getattr(node, "store", None) if node is not None else None
        register = getattr(store, "register_remote", None)
        if node is None or not node.alive or register is None:
            # unknown/dead/non-daemon node: the worker falls back to
            # the classic value put (its arena entry is aborted)
            raise RuntimeError(f"no daemon store on node {node_hex!r}")
        register(oid, bytes(key), int(nbytes),
                 raw=tuple(raw) if raw else None)
        if self.tenancy.enabled:
            from ray_tpu.tenancy import current_job_id
            jid = current_job_id(self)
            self.tenancy.note_put(
                oid.hex(), jid.hex() if jid is not None else "",
                int(nbytes))
        with self._loc_lock:
            self._locations.setdefault(oid, set()).add(node.node_id)
        ref = ObjectRef(oid, owner_hex=self.worker_id.hex(),
                        task_name="put")
        self.futures.complete(oid)
        return ref

    def _store_value(self, oid: ObjectID, value: Any,
                     prefer_node: Optional[Node] = None) -> None:
        nested = _find_nested_refs(value)
        if nested:
            self.refcounter.add_nested_refs(oid, [r.id for r in nested])
        size = _nbytes_of(value)
        if self.tenancy.enabled:
            from ray_tpu.tenancy import current_job_id
            jid = current_job_id(self)
            self.tenancy.note_put(
                oid.hex(), jid.hex() if jid is not None else "", size)
        if size <= INLINE_OBJECT_SIZE or prefer_node is None:
            self.memory_store.put(oid, value, nbytes=size)
            return
        prefer_node.store.put(oid, value, nbytes=size)
        with self._loc_lock:
            self._locations.setdefault(oid, set()).add(prefer_node.node_id)

    def _free_object(self, oid: ObjectID) -> None:
        """Refcount hit zero: drop the value everywhere + its lineage."""
        if self.tenancy.enabled:
            self.tenancy.note_free(oid.hex())
        self.memory_store.delete(oid)
        with self._loc_lock:
            locs = self._locations.pop(oid, set())
        for node_id in locs:
            node = self.get_node(node_id)
            if node is not None:
                node.store.delete(oid)
        self.lineage.release(oid)

    def _fetch_value(self, oid: ObjectID) -> Tuple[bool, Any]:
        """Return (found, value) looking across memory store + node stores."""
        if self.memory_store.contains(oid):
            return True, self.memory_store.get(oid)
        with self._loc_lock:
            locs = list(self._locations.get(oid, ()))
        for node_id in locs:
            node = self.get_node(node_id)
            if node is not None and node.alive and node.store.contains(oid):
                return True, node.store.get(oid)
        return False, None

    def _ensure_available(self, oid: ObjectID) -> None:
        """Kick off lineage reconstruction if every copy of oid was lost."""
        with self._loc_lock:
            was_lost = oid in self._lost
            self._lost.discard(oid)
        if was_lost:
            self._recover_object(
                ObjectRef(oid, _register=False))

    def get(self, refs: Sequence[ObjectRef],
            timeout: Optional[float] = None) -> List[Any]:
        if len(refs) > 1:
            ready = self._get_ready(refs)
            if ready is not None:
                return ready
        deadline = None if timeout is None else time.monotonic() + timeout
        out: List[Any] = []
        for ref in refs:
            self._ensure_available(ref.id)
            remaining = None
            if deadline is not None:
                remaining = max(deadline - time.monotonic(), 0.0)
            if not self.futures.wait_for(ref.id, remaining):
                raise exc.GetTimeoutError(
                    f"get() timed out waiting for {ref}")
            value = self._get_one(ref, deadline)
            if isinstance(value, exc.TaskError):
                raise value.as_instanceof_cause()
            if isinstance(value, exc.RayTpuError):
                raise value
            out.append(value)
        return out

    def _get_ready(self, refs: Sequence[ObjectRef]) -> Optional[List[Any]]:
        """``get`` of refs whose objects are all complete, none lost,
        none an error, all in the owner's memory store: each table's
        lock is taken ONCE for all of them, where the loop in ``get``
        takes five a ref (among ~65 stream threads every one of them is
        a chance to stand in line for the interpreter lock again: a
        reader that took a backlog of a stream's objects paid a turn of
        it an OBJECT, PERF.md section 6, PR 42). None where any of that
        does not hold: ``get`` then takes them one by one."""
        if self._lost:
            return None
        ids = [ref.id for ref in refs]
        if not self.futures.all_done(ids):
            return None
        values = self.memory_store.get_many(ids)
        if values is None or any(isinstance(value, exc.RayTpuError)
                                 for value in values):
            return None
        return values

    def _get_one(self, ref: ObjectRef, deadline: Optional[float],
                 _depth: int = 0) -> Any:
        self._ensure_available(ref.id)
        found, value = self._fetch_value(ref.id)
        if found:
            return value
        # Object lost (node death). Attempt lineage reconstruction.
        if _depth > 100:
            raise exc.ObjectReconstructionFailedError(
                ref.id, "reconstruction recursion limit hit")
        self._recover_object(ref)
        remaining = None
        if deadline is not None:
            remaining = max(deadline - time.monotonic(), 0.0)
        if not self.futures.wait_for(ref.id, remaining):
            raise exc.GetTimeoutError(
                f"get() timed out waiting for reconstruction of {ref}")
        return self._get_one(ref, deadline, _depth + 1)

    def _recover_object(self, ref: ObjectRef) -> None:
        """Resubmit the producing task of a lost object (lineage recovery)."""
        spec = self.lineage.producer_of(ref.id)
        if spec is None:
            err = exc.ObjectLostError(
                ref.id, f"object {ref.id.hex()[:12]} was lost and has no "
                        f"lineage to reconstruct it (e.g. created by put())")
            self._store_value(ref.id, err)
            self.futures.complete(ref.id)
            return
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
            if inflight is not None and inflight.state in (
                    TaskState.PENDING_DEPS, TaskState.QUEUED,
                    TaskState.RUNNING):
                return  # already being recomputed
        self.stats["objects_reconstructed"] += 1
        respec = _clone_spec_for_retry(spec)
        for oid in respec.return_ids:
            self.futures.reset(oid)
        self.submit_task(respec, record_lineage=False)

    def wait(self, refs: Sequence[ObjectRef], num_returns: int = 1,
             timeout: Optional[float] = None,
             fetch_local: bool = True) -> Tuple[List[ObjectRef], List[ObjectRef]]:
        if num_returns > len(refs):
            raise ValueError("num_returns exceeds the number of refs")
        for r in refs:
            self._ensure_available(r.id)
        ids = [r.id for r in refs]
        # Cap at num_returns even if more completed (API contract parity).
        done_list = self.futures.wait_any(ids, num_returns, timeout)
        done_ids = set(done_list[:num_returns])
        ready = [r for r in refs if r.id in done_ids]
        not_ready = [r for r in refs if r.id not in done_ids]
        return ready, not_ready

    # ------------------------------------------------------------------
    # task submission
    # ------------------------------------------------------------------
    def submit_task(self, spec: TaskSpec,
                    record_lineage: bool = True) -> List[ObjectRef]:
        if self.tenancy.enabled:
            # fair-share admission verdict; REJECTED raises
            # AdmissionRejectedError here, before any future/lineage
            # state exists (the backpressure contract)
            self.tenancy.admit(spec)
        self.stats["tasks_submitted"] += 1
        trace_events.stamp_trace(spec)
        refs = [ObjectRef(oid, owner_hex=self.worker_id.hex(),
                          task_name=spec.name) for oid in spec.return_ids]
        for oid in spec.return_ids:
            self.futures.register(oid)
        deps = spec.dependencies()
        if deps:
            self.refcounter.add_submitted_task_refs(deps)
        if record_lineage and spec.max_retries != 0:
            self.lineage.record(spec.return_ids, spec)
        if spec.num_returns in ("streaming", "dynamic"):
            # Pre-create the generator state so the configured backpressure
            # applies even if the consumer races the producer to it.
            self._generators.setdefault(
                spec.task_id, GeneratorState(spec.backpressure_num_objects))

        inflight = _InFlightTask(spec)
        with self._tasks_lock:
            self._tasks[spec.task_id] = inflight

        if spec.kind == TaskKind.ACTOR_TASK:
            self._submit_actor_task(spec, inflight, deps)
        else:
            self._submit_with_deps(spec, inflight, deps)
        return refs

    def _submit_with_deps(self, spec: TaskSpec, inflight: _InFlightTask,
                          deps: List[ObjectID]) -> None:
        for d in deps:
            self._ensure_available(d)
        pending = [d for d in deps if not self.futures.is_done(d)]
        inflight.deps_remaining = len(pending)
        if not pending:
            self._schedule(spec, inflight)
            return
        counter_lock = threading.Lock()

        def on_dep_done(_oid):
            with counter_lock:
                inflight.deps_remaining -= 1
                ready = inflight.deps_remaining == 0
            if ready:
                self._schedule(spec, inflight)

        for d in pending:
            self.futures.add_done_callback(d, on_dep_done)

    def _schedule(self, spec: TaskSpec, inflight: _InFlightTask) -> None:
        with inflight.lock:
            if inflight.cancelled:
                return
            inflight.state = TaskState.QUEUED
        from ray_tpu._private.task_spec import PlacementGroupSchedulingStrategy
        if isinstance(spec.scheduling_strategy,
                      PlacementGroupSchedulingStrategy):
            self._schedule_into_pg(spec, inflight)
            return
        try:
            node = self.scheduler.pick_node(spec, self.nodes(),
                                            preferred=self._locality_node(spec))
        except SchedulingError as e:
            self._fail_unschedulable(spec, exc.TaskError(e, spec.name))
            return
        inflight.node_id = node.node_id
        node.enqueue(spec)
        self._record_submit_phase(spec, node)

    def _record_submit_phase(self, spec: TaskSpec, node: Node) -> None:
        """submit phase: submit_task entry -> node backlog enqueue
        (dependency waits + scheduler placement)."""
        if getattr(spec, "trace_sampled", False) and spec.submit_mono:
            now = time.perf_counter()
            trace_events.record_phase_rt(
                spec, "submit", now - spec.submit_mono,
                node.node_id.hex(), start_wall=spec.submit_wall,
                end_mono=now)

    def _fail_unschedulable(self, spec: TaskSpec,
                            error: exc.TaskError) -> None:
        """An infeasible placement must fail the ACTOR too, not just the
        creation task: plain _fail_task left the actor RESTARTING
        forever with its method calls buffering (reachable whenever a
        restart's target — e.g. a hard-affinity node — left the
        cluster)."""
        if spec.kind == TaskKind.ACTOR_CREATION:
            self._actor_creation_failed(spec, error)
        else:
            self._fail_task(spec, error)

    def _schedule_into_pg(self, spec: TaskSpec,
                          inflight: _InFlightTask) -> None:
        """Rewrite the demand onto bundle-scoped resources and enqueue."""
        strat = spec.scheduling_strategy
        pg = strat.placement_group
        # The strategy may carry a pickled CLONE of the pg (handle that
        # crossed a worker/object-store boundary): its event is never set
        # by the manager and its bundles are stale — re-bind to the live
        # object by id whenever one exists.
        live = self.pg_manager.get(pg.id)
        if live is not None and live is not pg:
            strat.placement_group = pg = live
        if not pg.is_ready():
            # Queue behind placement; the PG manager sets the event when
            # placed (or removed/unschedulable).
            def wait_then_schedule():
                pg._ready_event.wait()
                self._schedule_into_pg(spec, inflight)
            threading.Thread(target=wait_then_schedule, daemon=True).start()
            return
        if pg.state != "CREATED":
            self._fail_unschedulable(spec, exc.TaskError(
                exc.PlacementGroupUnschedulableError(
                    f"placement group is {pg.state}"), spec.name))
            return
        idx = strat.placement_group_bundle_index
        if idx != -1 and not (0 <= idx < len(pg.bundles)):
            self._fail_unschedulable(spec, exc.TaskError(
                ValueError(
                    f"placement_group_bundle_index={idx} out of range for "
                    f"{len(pg.bundles)} bundles"), spec.name))
            return
        # On a retry the spec's resources are already bundle-scoped; match
        # bundles against the original demand snapshot.
        if spec.pg_demand is None:
            spec.pg_demand = dict(spec.resources)
        demand = spec.pg_demand
        candidates = (pg.bundles if idx == -1 else [pg.bundles[idx]])
        # Prefer bundles on non-draining hosts: a bundle pinned to a
        # draining node is a last resort (the PG re-places when the
        # node finally leaves).
        if idx == -1 and len(candidates) > 1:
            def _bundle_draining(b) -> int:
                n = self.get_node(b.node_id) if b.node_id else None
                return 1 if (n is not None
                             and getattr(n, "draining", False)) else 0
            candidates = sorted(candidates, key=_bundle_draining)
        chosen = None
        for bundle in candidates:
            if all(bundle.resources.get(k, 0.0) >= v - 1e-9
                   for k, v in demand.items()):
                node = self.get_node(bundle.node_id)
                if node is not None and node.alive:
                    avail = node.ledger.available()
                    scoped = {f"_pg_{pg.id.hex()[:16]}_{bundle.index}_{k}": v
                              for k, v in demand.items()}
                    if chosen is None or all(
                            avail.get(k, 0.0) >= v - 1e-9
                            for k, v in scoped.items()):
                        chosen = (bundle, node, scoped)
                        if all(avail.get(k, 0.0) >= v - 1e-9
                               for k, v in scoped.items()):
                            break
        if chosen is None:
            self._fail_unschedulable(spec, exc.TaskError(
                SchedulingError(
                    f"demand {demand} does not fit any bundle of "
                    f"the placement group"), spec.name))
            return
        bundle, node, scoped = chosen
        spec.resources = scoped
        spec.placement_group_id = pg.id
        spec.bundle_index = bundle.index
        spec.pg_capture = bool(
            getattr(strat, "placement_group_capture_child_tasks", False))
        inflight.node_id = node.node_id
        node.enqueue(spec)
        self._record_submit_phase(spec, node)

    def _locality_node(self, spec: TaskSpec) -> Optional[Node]:
        """Prefer the node holding the largest dependency (locality-aware)."""
        # snapshot both tables under their own locks, then do the store
        # size accounting lock-free: _nodes was read here without
        # _nodes_lock (raylint guarded-by), and the per-dep nbytes
        # lookups have no business running under _loc_lock
        with self._nodes_lock:
            nodes = dict(self._nodes)
        with self._loc_lock:
            dep_locs = [(dep, list(self._locations.get(dep, ())))
                        for dep in spec.dependencies()]
        best, best_size = None, 0
        for dep, node_ids in dep_locs:
            for node_id in node_ids:
                node = nodes.get(node_id)
                if node is None or not node.alive:
                    continue
                try:
                    store = node.store
                    if hasattr(store, "nbytes_of"):
                        size = store.nbytes_of(dep)
                    else:
                        size = store._entries[dep].nbytes  # noqa: SLF001
                except KeyError:
                    continue
                if size > best_size:
                    best, best_size = node, size
        return best

    # ------------------------------------------------------------------
    # task execution (runs on node worker threads)
    # ------------------------------------------------------------------
    def _execute_on_node(self, spec: TaskSpec, node: Node) -> None:
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
        if inflight is not None:
            with inflight.lock:
                if inflight.cancelled:
                    return
                inflight.state = TaskState.RUNNING
        if spec.kind == TaskKind.ACTOR_CREATION:
            self._execute_actor_creation(spec, node)
            return
        self.task_events.record(
            task_id=spec.task_id.hex(), name=spec.name, event="RUNNING",
            node_id=node.node_id.hex())
        try:
            args, kwargs = self._resolve_args(spec)
        except exc.TaskError as te:
            self._finish_task(spec, node, error=te)
            return
        if self._try_process_execute(spec, node, args, kwargs):
            return
        self._execute_inline(spec, node, args, kwargs)

    def _execute_inline(self, spec: TaskSpec, node: Node, args: tuple,
                        kwargs: dict) -> None:
        """In-driver execution: accelerator-plane / in_process work runs
        on the node's (driver-side) dispatch thread — the mesh-owning
        process, with XLA releasing the GIL."""
        token = runtime_context._set_context(
            job_id=spec.job_id or self.job_id, task_id=spec.task_id,
            node_id=node.node_id,
            actor_id=None, resources=spec.resources, task_name=spec.name,
            placement_group_id=spec.placement_group_id,
            pg_capture=spec.pg_capture)
        from ray_tpu.runtime_env import apply_runtime_env
        from ray_tpu.util.rpdb import post_mortem_on_error
        sampled = getattr(spec, "trace_sampled", False)
        t_exec0 = time.perf_counter() if sampled else 0.0
        try:
            with apply_runtime_env(spec.runtime_env), \
                    post_mortem_on_error():
                result = spec.func(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001
            self._finish_task(spec, node,
                              error=exc.TaskError(e, spec.name))
            return
        finally:
            runtime_context._reset_context(token)
            if sampled:
                # exec phase, driver lane (in-process/accelerator work
                # runs in the mesh-owning process, not a worker)
                now = time.perf_counter()
                trace_events.record_phase_rt(
                    spec, "exec", now - t_exec0, node.node_id.hex(),
                    start_wall=trace_events.wall_at(t_exec0),
                    end_mono=now)
        if spec.num_returns in ("streaming", "dynamic") or inspect.isgenerator(
                result):
            self._drain_generator(spec, node, result)
            return
        self._finish_task(spec, node, result=result)

    def _try_process_execute(self, spec: TaskSpec, node: Node,
                             args: tuple, kwargs: dict) -> bool:
        """Route an eligible normal task to a worker process. Returns
        False if the task must run in-process (accelerator-plane work or
        unserializable payload)."""
        from ray_tpu._private.worker_process import WorkerCrashed
        router = self.process_router
        payload = router.eligible_task(spec, args, kwargs)
        if payload is None:
            return False
        try:
            kind, value = router.execute_task(spec, node, payload)
        except WorkerCrashed as crash:
            self._on_process_task_crash(spec, node, crash)
            return True
        if kind == "err":
            with self._tasks_lock:
                inflight = self._tasks.get(spec.task_id)
            if (inflight is not None and inflight.cancelled
                    and isinstance(value, KeyboardInterrupt)):
                # Non-force cancel: the injected KeyboardInterrupt is the
                # cancellation surfacing, not an app error — it must not
                # hit the retry logic nor leak as TaskError(KeyboardInterrupt).
                self._release_task_resources(spec, node)
                self._fail_task(spec, exc.TaskError(
                    exc.TaskCancelledError(spec.task_id), spec.name))
                return True
            self._finish_task(spec, node,
                              error=exc.TaskError(value, spec.name))
        elif (spec.num_returns in ("streaming", "dynamic")
              or kind == "gen"):
            self._drain_generator(spec, node, value)
        else:
            self._finish_task(spec, node, result=value)
        return True

    def _on_process_task_crash(self, spec: TaskSpec, node: Node,
                               crash: Exception) -> None:
        """A worker process died under a task: cancelled → cancelled
        error; otherwise system-failure retry up to max_retries
        (reference: task_manager.h RetryTaskIfPossible on worker death)."""
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
        cancelled = inflight is not None and inflight.cancelled
        self._release_task_resources(spec, node)
        if cancelled:
            self._fail_task(spec, exc.TaskError(
                exc.TaskCancelledError(spec.task_id), spec.name))
            return
        oom = self.memory_monitor.was_oom_killed(spec.task_id)
        fast_lane = bool(getattr(crash, "fast_lane", False))
        if not oom and fast_lane:
            # lane workers' task ids live in the native core: attribute
            # by claiming ONE recent un-attributed monitor kill, scoped
            # to lane crashes only so a classic worker's segfault near
            # a lane OOM kill is never mislabeled
            oom = self.memory_monitor.consume_unattributed_kill()
        if not oom and node is not None:
            # remote workers are policed by THEIR node's monitor (the
            # raylet role): ask the daemon whether this crash was its
            # OOM kill. The fast_lane flag rides along so the daemon
            # only takes its un-attributed-kill fallback for lane
            # crashes — a classic segfault must not consume a lane
            # crash's OOM entry.
            daemon = getattr(node, "daemon", None)
            if daemon is not None and not daemon.dead:
                try:
                    oom = daemon.client.call(
                        "oom_check", task_id=spec.task_id.hex(),
                        fast_lane=fast_lane, timeout=5.0)["oom"]
                except Exception:
                    pass
        if _retries_left(spec):
            self.task_events.record(task_id=spec.task_id.hex(),
                                    name=spec.name,
                                    event="RETRY_OOM" if oom else "RETRY")
            self._retry(spec)
            return
        if oom:
            self._fail_task(spec, exc.TaskError(
                exc.OutOfMemoryError(
                    f"task {spec.name} was killed by the memory monitor "
                    f"({self.memory_monitor.kills} kills; limit "
                    f"{self.memory_monitor.limit >> 20} MiB) and "
                    f"exhausted its retries"), spec.name))
            return
        self._fail_task(spec, exc.TaskError(
            exc.WorkerCrashedError(str(crash)), spec.name))

    def _resolve_args(self, spec: TaskSpec) -> Tuple[tuple, dict]:
        def resolve(a):
            if isinstance(a, ObjectRef):
                value = self._get_one(a, deadline=None)
                if isinstance(value, exc.TaskError):
                    raise value
                if isinstance(value, exc.RayTpuError):
                    raise exc.TaskError(value, spec.name)
                return value
            return a

        try:
            args = tuple(resolve(a) for a in spec.args)
            kwargs = {k: resolve(v) for k, v in spec.kwargs.items()}
        except exc.TaskError:
            raise
        except exc.RayTpuError as e:
            raise exc.TaskError(e, spec.name)
        return args, kwargs

    def _finish_task(self, spec: TaskSpec, node: Optional[Node],
                     result: Any = None,
                     error: Optional[exc.TaskError] = None) -> None:
        if node is not None and not node.alive:
            # Node "died" while the thread was still running: results are
            # lost with the node; retry is handled by on_node_task_lost.
            self.on_node_task_lost(spec, node)
            return
        if error is not None:
            self.task_events.record(task_id=spec.task_id.hex(),
                                    name=spec.name, event="FAILED")
            if self._maybe_retry_app_error(spec, error):
                return
            self._fail_task(spec, error)
            return
        t_result = (time.perf_counter()
                    if getattr(spec, "trace_sampled", False) else 0.0)
        self.task_events.record(task_id=spec.task_id.hex(),
                                name=spec.name, event="FINISHED")
        # Release the task's resources BEFORE completing the futures: a
        # driver unblocked by get() must observe the node's ledger already
        # freed, or back-to-back submit-after-get races see the node as
        # busy and locality-biased scheduling scatters (the node's
        # dispatch `finally` skips the release via the spec flag).
        self._release_task_resources(spec, node)
        values: List[Any]
        n = spec.num_returns
        if n == 1 or not isinstance(n, int):
            values = [result]
        elif n == 0:
            values = []
        else:
            if not isinstance(result, (tuple, list)) or len(result) != n:
                self._fail_task(spec, exc.TaskError(
                    ValueError(f"task declared num_returns={n} but returned "
                               f"{type(result).__name__}"), spec.name))
                return
            values = list(result)
        for oid, value in zip(spec.return_ids, values):
            self._store_value(oid, value, prefer_node=node)
            self.futures.complete(oid)
        if t_result:
            # result phase: outcome in hand -> return futures completed
            now = time.perf_counter()
            trace_events.record_phase_rt(
                spec, "result", now - t_result,
                node.node_id.hex() if node is not None else "",
                start_wall=trace_events.wall_at(t_result), end_mono=now)
        self._on_task_done(spec, TaskState.FINISHED)

    def _fail_task(self, spec: TaskSpec, error: exc.TaskError) -> None:
        for oid in spec.return_ids:
            self._store_value(oid, error)
            self.futures.complete(oid)
        gen = self._generators.get(spec.task_id)
        if gen is not None:
            gen.finish(error.as_instanceof_cause())
        self._on_task_done(spec, TaskState.FAILED)

    def _on_task_done(self, spec: TaskSpec, state: str) -> None:
        self.stats["tasks_finished"] += 1
        task_hex = spec.task_id.hex()
        # Per-task borrow release (reference: reference_count.h:73): refs
        # the owner created on this task's behalf (nested put/submit from
        # its worker) un-pin NOW — results are already stored, so
        # containment keeps anything the task returned alive. Without
        # this a long-lived daemon pins dead tasks' objects forever.
        backend = getattr(self, "cluster_backend", None)
        svc = getattr(backend, "owner_service", None)
        if svc is not None:
            svc.holder.release("t:" + task_hex)
        # same release for the driver-local fast lane's workers
        self.process_router.release_borrows("t:" + task_hex)
        from ray_tpu._private.export_events import emit_export
        emit_export("TASK", task_id=task_hex, name=spec.name,
                    state=state, kind=str(spec.kind),
                    job_id=self.job_id.hex())
        deps = spec.dependencies()
        if deps:
            self.refcounter.remove_submitted_task_refs(deps)
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
            if inflight is not None:
                inflight.state = state
                # Drop terminal entries (FINISHED and FAILED both) so the
                # in-flight table doesn't leak specs and their arg pins.
                del self._tasks[spec.task_id]

    def _maybe_retry_app_error(self, spec: TaskSpec,
                               error: exc.TaskError) -> bool:
        retry_on = spec.retry_exceptions
        if retry_on is False or not _retries_left(spec):
            return False
        if retry_on is not True:
            try:
                if not isinstance(error.cause, tuple(retry_on)):
                    return False
            except TypeError:
                return False
        self._retry(spec)
        return True

    def on_node_task_lost(self, spec: TaskSpec, node: Node) -> None:
        """A node died holding this queued/running task (system failure)."""
        if _retries_left(spec):
            self._retry(spec)
        else:
            self._fail_task(spec, exc.TaskError(
                exc.NodeDiedError(
                    f"task {spec.name} lost to death of node "
                    f"{node.node_id.hex()[:8]} and retries exhausted"),
                spec.name))

    def _retry(self, spec: TaskSpec) -> None:
        self.stats["tasks_retried"] += 1
        from ray_tpu._private import failpoints as _fp
        from ray_tpu._private.retry import TASK_RETRY, record_retry
        if _fp.ENABLED:
            # ANY injected error turns the would-be retry into a
            # terminal failure (an escape here would leave the task
            # neither retried nor failed, futures hanging); delay arm
            # stretches the retry storm
            try:
                _fp.fire("worker.retry", task=spec.task_id.hex(),
                         attempt=spec.attempt_number)
            except Exception as e:  # noqa: BLE001 — routed to the task
                self._fail_task(spec, exc.TaskError(e, spec.name))
                return
        # unified backoff before the resubmit (exponential, full
        # jitter, short caps): a crash-looping task must not hammer the
        # scheduler, and the attempt shows up in the retry counters.
        # The wait is DEFERRED, never a blocking sleep: node-death
        # fans out retries for a whole backlog on one thread, and
        # serialized sleeps there would stall every task behind the
        # ones before it.
        backoff = TASK_RETRY.backoff_s(spec.attempt_number)
        record_retry("worker.task_retry", backoff)
        if backoff >= 0.01:
            # ONE shared timer thread services every deferred retry: a
            # node-death fan-out over a 10k-task backlog must not spawn
            # 10k Timer threads (thread exhaustion raises out of the
            # crash-handling path). A resubmit that raises must fail
            # the task — the wheel's own backstop would silently drop
            # it and leave its futures hanging forever.
            from ray_tpu._private.retry import defer

            def fire_retry(spec=spec):
                try:
                    self._resubmit_retry(spec)
                except Exception as e:  # noqa: BLE001 — routed to task
                    try:
                        self._fail_task(spec, exc.TaskError(e, spec.name))
                    except Exception:
                        pass

            defer(backoff, fire_retry)
            return
        self._resubmit_retry(spec)

    def _resubmit_retry(self, spec: TaskSpec) -> None:
        if self._shutdown:
            return
        respec = _clone_spec_for_retry(spec)
        # ONE critical section for check + replace: a gap between the
        # pop and the reinsert would hide the task from a concurrent
        # cancel() scan, silently losing the cancel
        with self._tasks_lock:
            old = self._tasks.get(spec.task_id)
            if old is None:
                # terminal state reached during the deferred window
                # (e.g. a force cancel already ran _fail_task and
                # removed the entry): resurrecting it would re-run a
                # body the user was told is cancelled/failed
                return
            if not old.cancelled:
                inflight = _InFlightTask(respec)
                self._tasks[respec.task_id] = inflight
        if old.cancelled:
            # a cancel() landed during the deferred-backoff window: the
            # lane/daemon cancel paths found nothing running, so honor
            # the flag here instead of resurrecting the task
            # (_fail_task's _on_task_done drops the stale entry)
            self._fail_task(spec, exc.TaskError(
                exc.TaskCancelledError(spec.task_id), spec.name))
            return
        deps = respec.dependencies()
        if respec.kind == TaskKind.ACTOR_TASK:
            # Replay on the (possibly restarting) actor, not the task path.
            self._submit_actor_task(respec, inflight, deps)
        else:
            self._submit_with_deps(respec, inflight, deps)

    # -- streaming generators ----------------------------------------------
    def _release_task_resources(self, spec: TaskSpec,
                                node: Optional[Node]) -> None:
        """Idempotent early release (runs on the worker thread, strictly
        before the exec pool's own `finally` release). Staged: a batch
        of same-shape completions lands on the ledger under ONE lock
        acquisition (node.stage_release flat-combining)."""
        from ray_tpu._private.task_spec import TaskKind
        if (node is not None and spec.kind != TaskKind.ACTOR_CREATION
                and not getattr(spec, "_resources_released", False)):
            spec._resources_released = True
            node.stage_release(spec.resources)

    def _drain_generator(self, spec: TaskSpec, node: Node, gen) -> None:
        state = self._generators.setdefault(
            spec.task_id, GeneratorState(spec.backpressure_num_objects))
        # On a retry, skip the ITEMS the previous attempt reported
        # (streams are assumed deterministic, as in lineage
        # reconstruction; where its runs begin and end is not).
        skip = state.produced
        from ray_tpu._private import failpoints as _fp
        cpu = state.producer_cpu
        cpu.publish()                   # this thread takes the stream up
        try:
            for item in gen:
                if _fp.ENABLED:
                    # per-object seam: error arm kills the stream mid-way
                    # (consumer sees a typed error); delay arm throttles
                    _fp.fire("worker.generator_stream",
                             task=spec.task_id.hex())
                count = 1
                if isinstance(item, ChunkRun):
                    # what the transport costs, it costs an OBJECT: a
                    # run is stored and reported once, counted in items
                    if skip:
                        replayed = min(skip, len(item))
                        skip -= replayed
                        item = ChunkRun(item[replayed:])
                    count = len(item)
                    if count == 1:
                        item = item[0]
                    elif not count:
                        continue
                elif skip:
                    skip -= 1
                    continue
                oid = ObjectID.from_random()
                self._store_value(oid, item, prefer_node=node)
                self.futures.complete(oid)
                ref = ObjectRef(oid, owner_hex=self.worker_id.hex(),
                                task_name=spec.name)
                sampled = sampled_items(state.produced, count)
                state.report_item(ref, count)
                if sampled:
                    cpu.publish()
        except BaseException as e:  # noqa: BLE001
            cpu.publish()
            from ray_tpu._private.worker_process import WorkerCrashed
            if isinstance(e, WorkerCrashed):
                # System failure mid-stream (worker process died): retry
                # like any other worker crash — already-reported items are
                # skipped on the replay (deterministic streams), matching
                # lineage-reconstruction semantics.
                state.finished = False
                self._on_process_task_crash(spec, node, e)
                return
            te = exc.TaskError(e, spec.name)
            state.finish(te.as_instanceof_cause())
            self._fail_task(spec, te)
            return
        cpu.publish()
        state.finish()
        # The task's own return value is the generator handle sentinel.
        for oid in spec.return_ids:
            self._store_value(oid, _StreamingGeneratorSentinel(spec.task_id))
            self.futures.complete(oid)
        self._on_task_done(spec, TaskState.FINISHED)

    def generator_state(self, task_id: TaskID) -> GeneratorState:
        # every ``next`` of a consumer comes through here: no throwaway
        # state (a lock, a deque, the account's cells) for ``setdefault``
        state = self._generators.get(task_id)
        if state is None:
            state = self._generators.setdefault(task_id, GeneratorState())
        return state

    def run_item_ref(self, item: RunItem) -> ObjectRef:
        """A ref of its own for ONE item of a run, for a reader that
        wants refs (``ObjectRefGenerator.next``, a worker process's
        ``gen_next``): the run is read and the item stored again. The
        slow path; Serve reads values (``next_value``)."""
        return self.put(self.get([item.ref])[0][item.offset])

    def generator_stats(self) -> Dict[str, Any]:
        """What this process's runtime has seen of its streaming
        generators, Serve's or not (docs/serving.md, "The stream path"):
        the sums of every stream's account, and ``streams_live``, the
        streams begun and not ended. A stream that ended and was read
        to its end is folded into running totals here, so the sums no
        longer depend on its state (the states themselves stay in
        ``_generators`` for the life of the runtime)."""
        with self._generator_stats_lock:
            totals = self._generator_totals
            out = dict(totals, streams_live=0)
            for state in list(self._generators.values()):
                if state.folded:
                    continue
                # before the cells: an ended stream's are final
                ended = state.finished and state.read_to_end
                out["streams_live"] += not state.finished
                for key, value in state.account().items():
                    out[key] += value
                    if ended:
                        totals[key] += value
                state.folded = ended
            return _ns_as_seconds(out)

    # ------------------------------------------------------------------
    # actors
    # ------------------------------------------------------------------
    def create_actor(self, spec: TaskSpec,
                     get_if_exists: bool = False) -> ActorID:
        actor_id = spec.actor_id
        info = ActorInfo(
            actor_id=actor_id, name=spec.actor_name,
            namespace=spec.namespace or self.namespace,
            max_restarts=spec.max_restarts,
            max_task_retries=spec.max_task_retries,
            detached=(spec.lifetime == "detached"),
            creation_spec=spec,
            class_name=getattr(spec.func, "__name__", "Actor"),
            method_options=dict(spec.method_options))
        if get_if_exists and spec.actor_name:
            actor_id, created = self.gcs.register_actor_or_get_existing(info)
            if not created:
                return actor_id
        else:
            self.gcs.register_actor(info)
        with self._actor_lock:
            self._actor_pending_tasks[actor_id] = []
        self.submit_task(spec, record_lineage=False)
        return actor_id

    def _execute_actor_creation(self, spec: TaskSpec, node: Node) -> None:
        actor_id = spec.actor_id
        try:
            args, kwargs = self._resolve_args(spec)
        except exc.TaskError as te:
            self._actor_creation_failed(spec, te, node)
            return
        from ray_tpu._private.worker_process import WorkerCrashed
        from ray_tpu._private.cluster import DaemonCrashed
        instance = None
        if getattr(node, "daemon", None) is not None:
            payload = None
            if (inspect.isclass(spec.func)
                    and not _class_is_async(spec.func)
                    and not getattr(spec, "in_process", False)):
                payload = self.process_router._serialize_payload(
                    spec, args, kwargs)
            if payload is not None:
                fid, args_blob = payload
                try:
                    instance = node.daemon.create_actor(spec, fid,
                                                        args_blob)
                    self._remote_actors[spec.actor_id] = node.daemon
                except RemoteWorkerCrashed as e:
                    self._retry_or_fail_creation(spec, node, e)
                    return
                except DaemonCrashed as e:
                    self._on_daemon_crash(node)
                    self._retry_or_fail_creation(spec, node, e)
                    return
                except BaseException as e:  # noqa: BLE001
                    self._actor_creation_failed(
                        spec, exc.TaskError(e, spec.name), node)
                    return
            # unserializable / in_process: fall through and create the
            # instance in the driver (mesh-owning process)
        actor_payload = None
        if instance is None and getattr(node, "daemon", None) is None:
            actor_payload = self.process_router.eligible_actor(spec, args,
                                                               kwargs)
        if actor_payload is not None:
            try:
                instance = self.process_router.create_actor(
                    spec, node, actor_payload)
            except WorkerCrashed as e:
                self._retry_or_fail_creation(spec, node, e)
                return
            except BaseException as e:  # noqa: BLE001
                self._actor_creation_failed(
                    spec, exc.TaskError(e, spec.name), node)
                return
        if instance is None:
            token = runtime_context._set_context(
                job_id=spec.job_id or self.job_id, task_id=spec.task_id,
                node_id=node.node_id, actor_id=actor_id,
                resources=spec.resources, task_name=spec.name,
                placement_group_id=spec.placement_group_id,
                pg_capture=spec.pg_capture)
            from ray_tpu.runtime_env import apply_runtime_env
            try:
                with apply_runtime_env(spec.runtime_env):
                    instance = spec.func(*args, **kwargs)
            except BaseException as e:  # noqa: BLE001
                self._actor_creation_failed(spec,
                                            exc.TaskError(e, spec.name),
                                            node)
                return
            finally:
                runtime_context._reset_context(token)

        # The actor may have been killed while __init__ was running; do not
        # resurrect it (install nothing, free the lifetime resources).
        info = self.gcs.get_actor_info(actor_id)
        if info is not None and info.state == ActorState.DEAD:
            self.process_router.discard_actor(actor_id)
            if node.alive:
                node.ledger.release(spec.resources)
            for oid in spec.return_ids:
                self._store_value(oid, exc.ActorDiedError(
                    actor_id, info.death_cause or "actor killed"))
                self.futures.complete(oid)
            self._on_task_done(spec, TaskState.FAILED)
            return

        is_async = _class_is_async(type(instance))
        executor = ActorExecutor(
            actor_id, spec.max_concurrency,
            run_task=lambda s, inst: self._execute_actor_task(s, inst, node),
            run_task_async=lambda s, inst: self._execute_actor_task_async(
                s, inst, node),
            concurrency_groups=spec.concurrency_groups)
        executor.start(instance, is_async)
        node.host_actor(executor)
        with self._actor_lock:
            self._actor_executors[actor_id] = executor
            pending = self._actor_pending_tasks.pop(actor_id, [])
        self.gcs.update_actor_state(actor_id, ActorState.ALIVE,
                                    node_id=node.node_id)
        # Creation-task return: the actor handle's readiness object.
        for oid in spec.return_ids:
            self._store_value(oid, actor_id)
            self.futures.complete(oid)
        self._on_task_done(spec, TaskState.FINISHED)
        for pspec in pending:
            executor.submit(pspec)

    def _retry_or_fail_creation(self, spec: TaskSpec, node: Node,
                                e: BaseException) -> None:
        """System failure (worker process / daemon died during __init__):
        restart semantics, not permanent death — a transient OOM/SIGKILL
        must behave like the post-creation worker-failure path
        (reference: GcsActorManager worker-failure restart)."""
        actor_id = spec.actor_id
        if node.alive:
            node.ledger.release(spec.resources)
        info = self.gcs.get_actor_info(actor_id)
        if (info is not None
                and (info.max_restarts == -1
                     or info.num_restarts < info.max_restarts)):
            self.stats["actor_restarts"] += 1
            info.num_restarts += 1
            self.gcs.update_actor_state(actor_id, ActorState.RESTARTING)
            respec = _clone_spec_for_retry(spec)
            respec.actor_id = actor_id
            with self._tasks_lock:
                inflight = _InFlightTask(respec)
                self._tasks[respec.task_id] = inflight
            self._submit_with_deps(respec, inflight, respec.dependencies())
            return
        self._actor_creation_failed(spec, exc.TaskError(e, spec.name),
                                    node)

    def _actor_creation_failed(self, spec: TaskSpec, error: exc.TaskError,
                               node: Optional[Node] = None) -> None:
        actor_id = spec.actor_id
        if node is not None and node.alive:
            node.ledger.release(spec.resources)
        self.gcs.update_actor_state(actor_id, ActorState.DEAD,
                                    death_cause=str(error.cause))
        with self._actor_lock:
            pending = self._actor_pending_tasks.pop(actor_id, [])
        self._fail_task(spec, error)
        died = exc.ActorError(
            exc.ActorDiedError(actor_id,
                               f"actor __init__ failed: {error.cause!r}"),
            spec.name, actor_id)
        for pspec in pending:
            self._fail_task(pspec, died)

    def _submit_actor_task(self, spec: TaskSpec, inflight: _InFlightTask,
                           deps: List[ObjectID]) -> None:
        actor_id = spec.actor_id
        info = self.gcs.get_actor_info(actor_id)
        if info is None:
            self._fail_task(spec, exc.TaskError(
                ValueError(f"unknown actor {actor_id}"), spec.name))
            return
        if info.state == ActorState.DEAD:
            self._fail_task(spec, exc.ActorError(
                exc.ActorDiedError(actor_id, info.death_cause or "actor died"),
                spec.name, actor_id))
            return

        for d in deps:
            self._ensure_available(d)
        pending = [d for d in deps if not self.futures.is_done(d)]
        if not pending:
            self._enqueue_actor_task_when_ready(spec)
            return
        inflight.deps_remaining = len(pending)
        counter_lock = threading.Lock()

        def on_dep_done(_oid):
            with counter_lock:
                inflight.deps_remaining -= 1
                ready = inflight.deps_remaining == 0
            if ready:
                self._enqueue_actor_task_when_ready(spec)

        for d in pending:
            self.futures.add_done_callback(d, on_dep_done)

    def _enqueue_actor_task_when_ready(self, spec: TaskSpec) -> None:
        actor_id = spec.actor_id
        with self._actor_lock:
            executor = self._actor_executors.get(actor_id)
            if executor is None:
                info = self.gcs.get_actor_info(actor_id)
                if info is None or info.state == ActorState.DEAD:
                    self._fail_task(spec, exc.ActorError(
                        exc.ActorDiedError(
                            actor_id,
                            (info.death_cause if info else None)
                            or "actor is dead"),
                        spec.name, actor_id))
                    return
                # PENDING or RESTARTING: buffer until alive.
                self._actor_pending_tasks.setdefault(actor_id, []).append(spec)
                return
        if not executor.submit(spec):
            # The executor died but _handle_actor_death hasn't unregistered
            # it yet (node-death and task-retry race): drop the stale
            # executor and re-evaluate against GCS state — the task is
            # buffered if the actor is pending/restarting, failed only on
            # confirmed death (reference: actor_task_submitter resubmits
            # queued tasks across restarts, not failing them on the race).
            with self._actor_lock:
                if self._actor_executors.get(actor_id) is executor:
                    self._actor_executors.pop(actor_id, None)
            self._enqueue_actor_task_when_ready(spec)

    def _execute_actor_task(self, spec: TaskSpec, instance: Any,
                            node: Node) -> None:
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
        if inflight is not None:
            with inflight.lock:
                if inflight.cancelled:
                    return
                inflight.state = TaskState.RUNNING
        try:
            args, kwargs = self._resolve_args(spec)
        except exc.TaskError as te:
            self._finish_task(spec, node, error=te)
            return
        token = runtime_context._set_context(
            job_id=spec.job_id or self.job_id, task_id=spec.task_id,
            node_id=node.node_id,
            actor_id=spec.actor_id, resources=spec.resources,
            task_name=spec.name,
            placement_group_id=spec.placement_group_id,
            pg_capture=spec.pg_capture)
        from ray_tpu._private.worker_process import _ProcessActorInstance
        from ray_tpu._private.cluster import (DaemonCrashed,
                                              RemoteActorInstance,
                                              RemoteWorkerCrashed)
        try:
            if isinstance(instance, RemoteActorInstance):
                import cloudpickle as _cp
                try:
                    kind, result = instance.call_actor_method(
                        spec, _cp.dumps((args, kwargs)))
                except (DaemonCrashed, RemoteWorkerCrashed) as e:
                    raise exc.ActorDiedError(spec.actor_id, str(e))
                if kind == "err":
                    raise result
                if kind == "stored":
                    # the finally below resets the runtime context
                    daemon_key, nbytes = result
                    node.store.register_remote(spec.return_ids[0],
                                               daemon_key, nbytes)
                    with self._loc_lock:
                        self._locations.setdefault(
                            spec.return_ids[0], set()).add(node.node_id)
                    self.task_events.record(task_id=spec.task_id.hex(),
                                            name=spec.name,
                                            event="FINISHED")
                    self._release_task_resources(spec, node)
                    self.futures.complete(spec.return_ids[0])
                    self._on_task_done(spec, TaskState.FINISHED)
                    return
            elif isinstance(instance, _ProcessActorInstance):
                kind, result = self.process_router.call_actor_method(
                    instance, spec, node, args, kwargs)
                if kind == "err":
                    raise result
            else:
                method = getattr(instance, spec.method_name)
                result = method(*args, **kwargs)
        except _ExitActor:
            self._finish_task(spec, node, result=None)
            self.kill_actor(spec.actor_id, no_restart=True,
                            cause="exit_actor() called")
            return
        except BaseException as e:  # noqa: BLE001
            if (isinstance(e, exc.ActorDiedError)
                    and getattr(node, "draining", False)
                    and self._resubmit_drained_actor_task(spec)):
                # the drain's worker recycle caught this call mid-flight:
                # a planned migration replays it on the new incarnation
                # instead of failing it
                return
            self._finish_task(spec, node, error=exc.ActorError(
                e, spec.name, spec.actor_id))
            return
        finally:
            runtime_context._reset_context(token)
        if inspect.isgenerator(result) or spec.num_returns in (
                "streaming", "dynamic"):
            self._drain_generator(spec, node, result)
            return
        self._finish_task(spec, node, result=result)

    def _resubmit_drained_actor_task(self, spec: TaskSpec) -> bool:
        """Replay an actor task whose worker was recycled by a graceful
        drain. Only while the actor is still restartable — a genuinely
        DEAD actor keeps the normal failure surface."""
        info = self.gcs.get_actor_info(spec.actor_id)
        if info is None or info.state == ActorState.DEAD:
            return False
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
        if inflight is None:
            return False
        with inflight.lock:
            if inflight.cancelled:
                return False
        self._submit_actor_task(spec, inflight, spec.dependencies())
        return True

    async def _execute_actor_task_async(self, spec: TaskSpec, instance: Any,
                                        node: Node) -> None:
        with self._tasks_lock:
            inflight = self._tasks.get(spec.task_id)
        if inflight is not None:
            with inflight.lock:
                if inflight.cancelled:
                    return
                inflight.state = TaskState.RUNNING
        try:
            args, kwargs = self._resolve_args(spec)
        except exc.TaskError as te:
            self._finish_task(spec, node, error=te)
            return
        token = runtime_context._set_context(
            job_id=spec.job_id or self.job_id, task_id=spec.task_id,
            node_id=node.node_id,
            actor_id=spec.actor_id, resources=spec.resources,
            task_name=spec.name,
            placement_group_id=spec.placement_group_id,
            pg_capture=spec.pg_capture)
        try:
            method = getattr(instance, spec.method_name)
            result = method(*args, **kwargs)
            if inspect.iscoroutine(result):
                result = await result
        except _ExitActor:
            runtime_context._reset_context(token)
            self._finish_task(spec, node, result=None)
            self.kill_actor(spec.actor_id, no_restart=True,
                            cause="exit_actor() called")
            return
        except BaseException as e:  # noqa: BLE001
            runtime_context._reset_context(token)
            self._finish_task(spec, node, error=exc.ActorError(
                e, spec.name, spec.actor_id))
            return
        runtime_context._reset_context(token)
        self._finish_task(spec, node, result=result)

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True,
                   cause: str = "ray_tpu.kill() called") -> None:
        # Order matters: stop the executor FIRST so no queued spec can be
        # dispatched to the worker while/after it is reset and recycled.
        with self._actor_lock:
            executor = self._actor_executors.pop(actor_id, None)
        pending = executor.kill(cause) if executor is not None else []
        self.process_router.discard_actor(actor_id)
        info = self.gcs.get_actor_info(actor_id)
        if info is not None and info.node_id is not None:
            node = self.get_node(info.node_id)
            if node is not None:
                node.evict_actor(actor_id)
        self._handle_actor_death(actor_id, cause, pending_tasks=pending,
                                 may_restart=not no_restart)

    def on_actor_worker_died(self, actor_id: ActorID, cause: str) -> None:
        """An actor's worker PROCESS died unexpectedly (crash/kill -9):
        actor-death semantics with restart (reference: GcsActorManager
        worker-failure restart path)."""
        with self._actor_lock:
            executor = self._actor_executors.pop(actor_id, None)
        pending = executor.kill(cause) if executor is not None else []
        info = self.gcs.get_actor_info(actor_id)
        if info is not None and info.node_id is not None:
            node = self.get_node(info.node_id)
            if node is not None:
                node.evict_actor(actor_id)
        self._handle_actor_death(actor_id, cause, pending_tasks=pending,
                                 may_restart=True)

    def _handle_actor_death(self, actor_id: ActorID, cause: str,
                            pending_tasks: List[TaskSpec],
                            may_restart: bool,
                            graceful: bool = False) -> None:
        """``graceful=True`` is the planned-migration variant (node
        drain): the restart neither consumes the actor's max_restarts
        budget nor fails its pending tasks — they replay on the new
        incarnation regardless of max_task_retries."""
        self.process_router.discard_actor(actor_id)
        # Actor-lifetime borrows die with the incarnation (a restart
        # rebuilds state from creation args; the old in-worker refs are
        # gone either way).
        svc = getattr(getattr(self, "cluster_backend", None),
                      "owner_service", None)
        if svc is not None:
            svc.holder.release("a:" + actor_id.hex())
        remote = self._remote_actors.pop(actor_id, None)
        if remote is not None and not remote.dead:
            remote.kill_actor(actor_id, expected=True)
        info = self.gcs.get_actor_info(actor_id)
        if info is None:
            return
        with self._actor_lock:
            self._actor_executors.pop(actor_id, None)
        # Release the actor's lifetime resource hold on its (alive) node.
        if info.node_id is not None and info.creation_spec is not None:
            host = self.get_node(info.node_id)
            if host is not None and host.alive:
                host.ledger.release(info.creation_spec.resources)
                if host.tenancy is not None:
                    # settle the creation's per-job usage (held for the
                    # actor's whole lifetime, see node._run_spec)
                    host.tenancy.note_done(
                        info.creation_spec.job_id.hex()
                        if info.creation_spec.job_id is not None else "",
                        info.creation_spec.resources)
            info.node_id = None
        can_restart = (may_restart and info.creation_spec is not None
                       and (graceful or info.max_restarts == -1
                            or info.num_restarts < info.max_restarts))
        if can_restart:
            self.stats["actor_restarts"] += 1
            if not graceful:    # planned moves don't burn the budget
                info.num_restarts += 1
            self.gcs.update_actor_state(actor_id, ActorState.RESTARTING)
            if graceful or info.max_task_retries != 0:
                # Pending tasks survive the restart and replay on the new
                # incarnation (reference: actor_task_submitter.cc resubmit
                # queue on ConnectActor).
                with self._actor_lock:
                    self._actor_pending_tasks.setdefault(
                        actor_id, []).extend(pending_tasks)
            else:
                for spec in pending_tasks:
                    self._fail_task(spec, exc.ActorError(
                        exc.ActorUnavailableError(
                            f"actor restarting: {cause}"),
                        spec.name, actor_id))
            respec = _clone_spec_for_retry(info.creation_spec)
            respec.actor_id = actor_id
            with self._tasks_lock:
                inflight = _InFlightTask(respec)
                self._tasks[respec.task_id] = inflight
            self._submit_with_deps(respec, inflight, respec.dependencies())
        else:
            self.gcs.update_actor_state(actor_id, ActorState.DEAD,
                                        death_cause=cause)
            err_base = exc.ActorDiedError(actor_id, cause)
            with self._actor_lock:
                buffered = self._actor_pending_tasks.pop(actor_id, [])
            for spec in list(pending_tasks) + buffered:
                self._fail_task(spec, exc.ActorError(err_base, spec.name,
                                                     actor_id))

    # ------------------------------------------------------------------
    # cancellation
    # ------------------------------------------------------------------
    def cancel(self, ref: ObjectRef, force: bool = False,
               recursive: bool = True) -> None:
        while True:
            with self._tasks_lock:
                target = None
                for inflight in self._tasks.values():
                    if ref.id in inflight.spec.return_ids:
                        target = inflight
                        break
            if target is None:
                return
            with target.lock:
                if target.state in (TaskState.FINISHED,
                                    TaskState.FAILED):
                    return
                target.cancelled = True
                was_running = target.state == TaskState.RUNNING
            # a retry resubmit replaces the _tasks entry (same task_id,
            # fresh _InFlightTask): if that happened between our lookup
            # and the flag set, the flag landed on a stale object —
            # re-loop and cancel the live incarnation (converges: a
            # flagged live entry stops the retry chain)
            with self._tasks_lock:
                if self._tasks.get(target.spec.task_id) is target:
                    break
        if was_running:
            # Running in a worker process: force → SIGTERM the process
            # (the crash handler reports TaskCancelledError); non-force →
            # async KeyboardInterrupt into the executing thread.
            if self.process_router.cancel_task(target.spec.task_id, force):
                return
            # Daemon-executed task: forward over the wire (CancelTask,
            # core_worker.proto:525).
            node = self.get_node(target.node_id) if target.node_id else None
            daemon = getattr(node, "daemon", None) if node else None
            if daemon is not None and daemon.cancel_task(
                    target.spec.task_id, force):
                return
        if not was_running or force:
            self._fail_task(target.spec, exc.TaskError(
                exc.TaskCancelledError(target.spec.task_id),
                target.spec.name))

    # ------------------------------------------------------------------
    # debug state (reference: raylet debug_state_*.txt dumps with asio
    # handler stats — common/asio/instrumented_io_context.h)
    # ------------------------------------------------------------------
    def debug_state(self) -> str:
        lines = [f"session: {self.session_dir}",
                 f"stats: {self.stats}",
                 f"tracked refs: {self.refcounter.num_tracked()}",
                 f"lineage entries: {self.lineage.num_entries()}"]
        for node in self.nodes():
            with node._running_lock:
                running = len(node._running)
            lines.append(
                f"node {node.node_id.hex()[:8]}: alive={node.alive} "
                f"running={running} backlog={node._backlog_n} "
                f"actors={len(node.actors)} "
                f"store_used={node.store.used_bytes()} "
                f"loop={node.loop_stats}")
        if self.cluster_backend is not None:
            # which control-plane core each daemon advertised in hello
            lines.extend(self.cluster_backend.describe_peers())
        return "\n".join(lines)

    # ------------------------------------------------------------------
    # shutdown
    # ------------------------------------------------------------------
    def shutdown(self) -> None:
        self._shutdown = True
        from ray_tpu.util import profiling as _profiling
        _profiling.stop_process_sampler()
        self.memory_monitor.stop()
        if self._log_monitor is not None:
            self._log_monitor.stop()  # joins; loop does the final drain
        self.process_router.shutdown()
        if self.cluster_backend is not None:
            try:
                self.cluster_backend.shutdown()
            except Exception:
                pass
        for node in self.nodes():
            node.shutdown(fail_tasks=False)
            node.store.close()
        with self._nodes_lock:
            self._nodes.clear()
        self.memory_store.clear()


class _StreamingGeneratorSentinel:
    def __init__(self, task_id: TaskID):
        self.task_id = task_id


class _ExitActor(BaseException):
    pass


def _class_is_async(cls) -> bool:
    return any(inspect.iscoroutinefunction(m)
               for _, m in inspect.getmembers(cls,
                                              predicate=inspect.isfunction))


def _clone_spec_for_retry(spec: TaskSpec) -> TaskSpec:
    # The task_id is kept stable across attempts (parity: the reference
    # retries under the same TaskID with attempt_number++), so streaming
    # generator consumers and in-flight bookkeeping stay bound to it.
    import copy
    respec = copy.copy(spec)
    respec.attempt_number = spec.attempt_number + 1
    return respec


def _retries_left(spec: TaskSpec) -> bool:
    """max_retries < 0 means unlimited retries (option contract parity)."""
    return spec.max_retries < 0 or spec.attempt_number < spec.max_retries


def _find_nested_refs(value: Any, _depth: int = 0) -> List[ObjectRef]:
    """Shallow recursive scan for ObjectRefs inside standard containers."""
    if _depth > 6:
        return []
    if isinstance(value, ObjectRef):
        return [value]
    out: List[ObjectRef] = []
    if isinstance(value, (list, tuple, set, frozenset)):
        for v in value:
            out.extend(_find_nested_refs(v, _depth + 1))
    elif isinstance(value, dict):
        for k, v in value.items():
            out.extend(_find_nested_refs(k, _depth + 1))
            out.extend(_find_nested_refs(v, _depth + 1))
    return out


def capture_parent_pg_strategy(strategy):
    """Inherit the caller's PG when it asked to capture child tasks."""
    if strategy != "DEFAULT":
        return strategy
    ctx = runtime_context._ctx.get()
    if (ctx is None or not getattr(ctx, "pg_capture", False)
            or ctx.placement_group_id is None):
        return strategy
    rt = global_runtime()
    if rt is None:
        return strategy
    pg = rt.pg_manager.get(ctx.placement_group_id)
    if pg is None:
        return strategy
    from ray_tpu._private.task_spec import PlacementGroupSchedulingStrategy
    return PlacementGroupSchedulingStrategy(
        pg, placement_group_bundle_index=-1,
        placement_group_capture_child_tasks=True)


def init_runtime(**kwargs) -> Runtime:
    global _global_runtime
    with _global_lock:
        if _global_runtime is not None:
            raise RuntimeError("ray_tpu is already initialized")
        _global_runtime = Runtime(**kwargs)
        return _global_runtime


def shutdown_runtime() -> None:
    from ray_tpu._private.config import reset as _cfg_reset
    from ray_tpu._private.export_events import reset_export_logger
    _cfg_reset()
    reset_export_logger()  # next session binds its own dir
    global _global_runtime
    with _global_lock:
        if _global_runtime is not None:
            _global_runtime.shutdown()
            _global_runtime = None
