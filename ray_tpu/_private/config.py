"""Central config/flag system.

Reference capability: the ``RAY_CONFIG(type, name, default)`` X-macro
table (``src/ray/common/ray_config_def.h`` — 219 flags), overridable
per-process via ``RAY_<name>`` env vars and via the ``_system_config``
dict passed at ``ray.init`` (``includes/ray_config.pxi``).

Here every tunable lives in ONE declared table. Resolution order per
flag (highest wins):

1. ``_system_config={...}`` passed to ``ray_tpu.init``
2. ``RAY_TPU_<NAME>`` environment variable
3. the declared default

Usage::

    from ray_tpu._private.config import cfg
    cfg().heartbeat_s            # typed value
    cfg().describe()             # full table with provenance

Subsystems that must read a flag before ``init`` (module import time)
use ``cfg()`` lazily so a later ``_system_config`` is still honored by
anything reading through the accessor.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

_PREFIX = "RAY_TPU_"


def _parse_bool(raw: str) -> bool:
    return raw.strip().lower() not in ("0", "false", "no", "off", "")


@dataclass(frozen=True)
class Flag:
    name: str               # lower_snake; env var is RAY_TPU_<UPPER>
    type: Callable
    default: Any
    doc: str

    @property
    def env_var(self) -> str:
        return _PREFIX + self.name.upper()


# ---------------------------------------------------------------------------
# THE flag table (ray_config_def.h role). Add new tunables here, not as
# ad-hoc os.environ reads.
# ---------------------------------------------------------------------------

FLAG_DEFS = [
    # -- cluster topology / processes --
    Flag("cluster", str, "", "execution topology: '' = in-process virtual "
         "nodes, 'daemons' = head + node-daemon OS processes"),
    Flag("process_pool_size", int, 0, "idle worker-process pool target "
         "(0 = auto: min(4, max(2, cpus//2)))"),
    Flag("process_pool_max", int, 32, "hard cap on the adaptive idle pool "
         "(demand high-water raises the target up to this)"),
    Flag("head_grace_s", float, 20.0, "how long daemons/drivers re-dial a "
         "crashed head before giving up (head FT window)"),
    # -- health / heartbeats --
    Flag("heartbeat_interval_s", float, 0.2, "daemon->head heartbeat period"),
    Flag("node_dead_after_s", float, 1.5, "missed-heartbeat window before "
         "the head declares a node dead"),
    # -- graceful drain / preemption --
    Flag("drain_deadline_s", float, 30.0, "default graceful-drain window: "
         "planned departures (preemption notice, downscale, maintenance) "
         "migrate objects/actors and finish running work for up to this "
         "long before escalating to the hard node-death path"),
    Flag("drain_notice_file", str, "", "path the daemon's preemption "
         "watcher polls; the file appearing (content = reason) triggers "
         "a self-announced graceful drain — the air-gapped stand-in for "
         "the cloud metadata server's maintenance/preemption notice"),
    # -- object plane --
    Flag("native_store", bool, True, "use the C++ shm arena for large "
         "objects (False = pure-dict store)"),
    Flag("pull_chunk", int, 4 << 20, "inter-daemon object transfer chunk "
         "size in bytes (object_buffer_pool role; push and pull share it)"),
    Flag("objectplane_attach", bool, True, "workers (and the same-host "
         "driver) map the node daemon's shm arena and resolve host-tier "
         "objects zero-copy with shared-slot ref/release; False = the "
         "classic per-RPC object path (docs/object_plane.md)"),
    Flag("direct_put_min_bytes", int, 256 * 1024, "puts at/above this "
         "size reserve+write+seal arena space directly — the payload "
         "never rides an RPC/pipe frame; smaller puts stay classic"),
    Flag("raw_tier_min_bytes", int, 64 * 1024, "contiguous numpy arrays "
         "at/above this size store as RAW arena bytes; same-node "
         "consumers get read-only np.frombuffer views with zero "
         "serialization. COUPLED to direct_put_min_bytes: the raw path "
         "rides direct puts, so the effective gate is "
         "max(raw_tier_min_bytes, direct_put_min_bytes) — lower both "
         "to widen zero-copy coverage"),
    Flag("push_prefetch", bool, True, "proactively push task deps to "
         "the consumer's node at dispatch (PushManager: in-flight + "
         "directory + pull dedupe); False = pull-only transfer"),
    Flag("inline_object_size", int, 100 * 1024, "values <= this inline in "
         "the owner memory store (max_direct_call_object_size role)"),
    # -- memory monitor / OOM defense --
    Flag("memory_monitor", bool, True, "enable the host-memory monitor + "
         "worker-killing policies"),
    Flag("memory_monitor_interval", float, 1.0,
         "memory monitor check period (seconds)"),
    Flag("memory_usage_threshold", float, 0.95,
         "fraction of the limit at which the killer engages"),
    Flag("memory_limit_bytes", int, 0, "explicit memory limit "
         "(0 = detect from cgroup/system)"),
    Flag("worker_killing_policy", str, "retriable_fifo",
         "'retriable_fifo' or 'group_by_owner'"),
    # -- memory pressure / graceful degradation (_private/pressure.py,
    # docs/fault_tolerance.md "Memory pressure & graceful degradation") --
    Flag("memory_pressure", bool, False, "arm the per-node "
         "PressureController: fuses host RSS, arena occupancy, and the "
         "spill-dir budget into an ok/soft/hard level — soft spills "
         "cold arena entries proactively and throttles push-prefetch, "
         "hard rejects new reservations/puts with a retriable "
         "MemoryPressureError and feeds pressure-aware placement; off "
         "keeps every put/get hot path byte-identical (zero-overhead-"
         "when-off, same discipline as net_chaos)"),
    Flag("arena_spill_dir", str, "", "directory for spilled host-shm "
         "arena entries; empty = <tmpdir>/rtpu_spill_<arena> created "
         "on first spill"),
    Flag("arena_spill_watermarks", str, "0.70,0.85", "soft,hard arena "
         "occupancy fractions: past soft the controller spills cold "
         "sealed unpinned entries back down to the soft line; past "
         "hard (or when the host monitor is at its own threshold) new "
         "reservations/puts are rejected with MemoryPressureError"),
    Flag("arena_spill_budget_bytes", int, 0, "cap on total bytes parked "
         "in the spill dir (0 = unbounded); at budget the spiller "
         "stops and sustained arena pressure escalates to hard"),
    Flag("pressure_tick_s", float, 0.5, "PressureController evaluation "
         "period (seconds); 0 disarms the controller even when "
         "memory_pressure is on"),
    # -- logs --
    Flag("log_to_driver", bool, True, "capture worker stdout/stderr to "
         "per-pid files and tail them to the driver"),
    Flag("log_dir", str, "", "worker log directory override"),
    # -- observability --
    Flag("export_events", bool, False, "write structured task/actor/node/"
         "job/train/PG lifecycle events as JSONL under the session dir "
         "(export_*.proto role)"),
    Flag("task_trace", bool, True, "stamp a trace context into every "
         "task and record per-phase latency spans (submit/linger/queue/"
         "dispatch/exec/result) on every process; spans feed `ray_tpu "
         "timeline`, util.state.task_breakdown, and the "
         "ray_tpu_task_phase_seconds histogram (docs/observability.md)"),
    Flag("trace_sample", float, 1.0, "fraction of tasks traced when "
         "task_trace is on; sampling is deterministic in the task id so "
         "driver, daemon, and worker agree per task (1.0 = every task)"),
    Flag("profiling_hz", float, 0.0, "continuous stack-sampler rate "
         "(samples/second) in every process — driver, head, daemon, "
         "workers; 0 = off (the default; on-demand bursts via `ray_tpu "
         "profile` / util.state.cluster_profile work either way). "
         "Profiles federate to the head on heartbeats "
         "(docs/observability.md)"),
    Flag("lock_metrics", bool, False, "meter tracked runtime locks: "
         "wait/hold-time histograms (ray_tpu_lock_wait_seconds / "
         "ray_tpu_lock_hold_seconds{lock}) plus a contended counter on "
         "every named lock; mutually exclusive with lock_sanitizer "
         "(sanitizer wins when both are set)"),
    # -- accelerator topology --
    Flag("tpu_topology", str, "", "TPU slice topology for ICI-aware gang "
         "scheduling, '<gen>:<AxBxC>' (e.g. 'v5p:4x4x4'); '' = no "
         "topology (resource-count placement only)"),
    # -- control-plane batching (docs/performance.md) --
    Flag("submit_batch", bool, True, "coalesce driver->daemon task "
         "submissions into push_task_batch wire frames (False = one "
         "submit_task RPC per task, the pre-batching behavior)"),
    Flag("submit_batch_max", int, 64, "max tasks per push_task_batch "
         "frame; the coalescer flushes when this many are queued"),
    Flag("submit_linger_us", int, 200, "how long (microseconds) the "
         "submit coalescer waits for more tasks before flushing a "
         "non-full batch; 0 = flush immediately (batching only under "
         "concurrent submission pressure)"),
    Flag("free_batch_max", int, 256, "max object ids per free_objects "
         "RPC; the zero-ref free buffer flushes when this many are "
         "queued"),
    Flag("free_flush_ms", float, 5.0, "max milliseconds a queued "
         "zero-ref free waits before its buffer is flushed to the "
         "daemon"),
    # -- drain-side result pipeline (docs/performance.md "Result path") --
    Flag("result_batch_max", int, 256, "max task completions per "
         "task_batch_done push frame; the daemon's reply pump flushes "
         "when this many are buffered for one driver connection"),
    Flag("result_linger_us", int, 500, "how long (microseconds) the "
         "daemon's reply pump lingers for more completions before "
         "flushing a non-full task_batch_done frame; 0 = flush "
         "immediately"),
    Flag("exec_pool_size", int, 0, "worker threads in each node's task "
         "execution pool (the dispatch loop feeds admitted tasks to "
         "this sized pool instead of spawning per task); 0 = the "
         "node's max_worker_threads (256)"),
    # -- sanitizers (SURVEY §5.2: the reference's TSAN-in-CI role) --
    Flag("lock_sanitizer", bool, False, "track runtime lock acquisition "
         "order and warn on inversion cycles (potential deadlocks); "
         "see _private/lock_sanitizer.py"),
    # -- fault injection / retry discipline (_private/failpoints.py,
    # _private/retry.py) --
    Flag("failpoints", str, "", "failpoint spec activating deterministic "
         "fault injection, e.g. 'rpc.client.send=drop:every=3'; also "
         "honored as the RAY_TPU_FAILPOINTS env var by spawned "
         "daemon/head/worker processes"),
    Flag("failpoints_seed", int, 0, "RNG seed for probabilistic "
         "failpoint arms (0 = unseeded); same seed => same schedule"),
    Flag("net_chaos", str, "", "network-chaos link-policy spec "
         "degrading control-plane links deterministically, e.g. "
         "'driver>daemon=drop=0.3;daemon>head=partition:start=500"
         ":dur=2000'; also honored as the RAY_TPU_NET_CHAOS env var "
         "by spawned daemon/head/worker processes "
         "(_private/netchaos.py)"),
    Flag("net_chaos_seed", int, 0, "RNG seed for probabilistic "
         "link-policy draws (0 = unseeded); same seed => same "
         "drop/dup/jitter schedule"),
    Flag("control_call_timeout_s", float, 60.0, "deadline for bounded "
         "control-plane round trips whose reply is an ack, not a task "
         "outcome (batch-submit flush, free flush): a silent one-way "
         "partition surfaces as a typed RpcError instead of a wedged "
         "thread"),
    Flag("retry_base_backoff_s", float, 0.05, "RetryPolicy.default "
         "first-backoff cap (exponential, full jitter)"),
    Flag("retry_max_backoff_s", float, 2.0, "RetryPolicy.default "
         "backoff cap ceiling"),
    Flag("fairshare", bool, False, "multi-tenant fair share: DRF "
         "admission verdicts at submit, per-job quota gates and "
         "deficit-ordered batch admission in node dispatch; off keeps "
         "the dispatch hot path byte-identical (Node.tenancy is None)"),
    Flag("job_default_weight", float, 1.0, "fair-share weight assigned "
         "to jobs that never declared one; deficit quanta are split "
         "proportionally to weight among jobs with pending work"),
    Flag("admission_queue_max", int, 4096, "bounded per-job pending "
         "queue: tasks over quota beyond this many outstanding get a "
         "REJECTED verdict (AdmissionRejectedError) instead of QUEUED"),
    Flag("loop_lag_probe_s", float, 0.25, "interval of the event-loop "
         "lag probe behind ray_tpu_event_loop_lag_seconds (a repeating "
         "call_later measuring scheduled-vs-ran skew); 0 disarms"),
    Flag("loop_slow_callback_s", float, 0.05, "slow-callback watchdog "
         "threshold: loop callbacks (asyncio debug timing) or probe "
         "lag past this many seconds count into "
         "ray_tpu_event_loop_slow_callbacks_total"),
    Flag("async_debug", bool, False, "run the control-plane loop in "
         "asyncio debug mode: per-callback timing feeds the "
         "slow-callback watchdog and logs each offender (dev/test "
         "only; debug mode taxes every callback)"),
]

FLAGS: Dict[str, Flag] = {f.name: f for f in FLAG_DEFS}


class Config:
    """Resolved flag values; refreshed when _system_config changes."""

    def __init__(self, system_config: Optional[Dict[str, Any]] = None):
        self._system = dict(system_config or {})
        unknown = set(self._system) - set(FLAGS)
        if unknown:
            raise ValueError(
                f"unknown _system_config keys: {sorted(unknown)}; "
                f"known flags: {sorted(FLAGS)}")
        self._values: Dict[str, Any] = {}
        self._provenance: Dict[str, str] = {}
        for flag in FLAG_DEFS:
            if flag.name in self._system:
                raw: Any = self._system[flag.name]
                source = "_system_config"
            elif flag.env_var in os.environ:
                raw = os.environ[flag.env_var]
                source = f"env:{flag.env_var}"
            else:
                raw = flag.default
                source = "default"
            if flag.type is bool and isinstance(raw, str):
                value: Any = _parse_bool(raw)
            else:
                value = flag.type(raw)
            self._values[flag.name] = value
            self._provenance[flag.name] = source

    def __getattr__(self, name: str) -> Any:
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(f"no flag named {name!r}") from None

    def describe(self) -> Dict[str, Dict[str, Any]]:
        return {name: {"value": self._values[name],
                       "source": self._provenance[name],
                       "doc": FLAGS[name].doc}
                for name in self._values}


_lock = threading.Lock()
_config: Optional[Config] = None


def cfg() -> Config:
    global _config
    with _lock:
        if _config is None:
            _config = Config()
        return _config


def apply_system_config(system_config: Optional[Dict[str, Any]]) -> Config:
    """Install the per-init overrides (called from ray_tpu.init)."""
    global _config
    with _lock:
        _config = Config(system_config)
        return _config


def reset() -> None:
    """Drop cached values (shutdown path; env changes re-resolve)."""
    global _config
    with _lock:
        _config = None
