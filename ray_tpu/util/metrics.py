"""User + system metrics (reference: `python/ray/util/metrics.py`
Counter/Gauge/Histogram over the C++ OpenCensus registry,
`_private/metrics_agent.py` Prometheus exposition)."""

from __future__ import annotations

import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

_REGISTRY: Dict[str, "Metric"] = {}
_REG_LOCK = threading.Lock()


def _labels_key(labels: Optional[Dict[str, str]]) -> Tuple:
    return tuple(sorted((labels or {}).items()))


class Metric:
    kind = "untyped"

    def __new__(cls, name: str, *args, **kwargs):
        # get-or-create by name: re-declaring a metric (the natural
        # pattern inside tasks — Counter("x").inc() per call) must
        # return the LIVE instance, not a fresh zeroed one. A replace
        # here silently reset values, so a worker reusing a process
        # reported only its first flush's deltas.
        with _REG_LOCK:
            existing = _REGISTRY.get(name)
            if existing is not None and type(existing) is cls:
                return existing
        return super().__new__(cls)

    def __init__(self, name: str, description: str = "",
                 tag_keys: Sequence[str] = ()):
        if getattr(self, "_initialized", False):
            return                      # live instance from __new__
        self.name = name
        self.description = description
        self.tag_keys = tuple(tag_keys)
        self._values: Dict[Tuple, float] = {}
        self._lock = threading.Lock()
        self._initialized = True
        with _REG_LOCK:
            _REGISTRY[name] = self

    def _reattach(self) -> None:
        # clear_registry() (test isolation, process reuse) empties the
        # name->metric table, but module-level metric HOLDERS (tenancy
        # gauges, wire counters) keep writing to the orphaned instance —
        # which then never appears in prometheus_text() again. Re-attach
        # on write so a live metric always reaches the exposition; a
        # cleared metric nobody writes again stays gone. The unlocked
        # membership probe is safe: dict get is atomic, and a lost race
        # just means one extra locked setdefault.
        if _REGISTRY.get(self.name) is not self:
            with _REG_LOCK:
                _REGISTRY.setdefault(self.name, self)

    def _set(self, key: Tuple, value: float) -> None:
        self._reattach()
        with self._lock:
            self._values[key] = value

    def _add(self, key: Tuple, delta: float) -> None:
        self._reattach()
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + delta

    def samples(self) -> List[Tuple[Tuple, float]]:
        with self._lock:
            return list(self._values.items())


class Counter(Metric):
    kind = "counter"

    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None) -> None:
        self._add(_labels_key(tags), value)


class Gauge(Metric):
    kind = "gauge"

    def set(self, value: float,
            tags: Optional[Dict[str, str]] = None) -> None:
        self._set(_labels_key(tags), value)

    def remove(self, tags: Optional[Dict[str, str]] = None) -> None:
        """Drop one label series (e.g. a downscaled replica slot) so
        the exposition stops reporting its last value forever."""
        with self._lock:
            self._values.pop(_labels_key(tags), None)


class Histogram(Metric):
    kind = "histogram"

    def __init__(self, name: str, description: str = "",
                 boundaries: Sequence[float] = (0.01, 0.1, 1, 10, 100),
                 tag_keys: Sequence[str] = ()):
        if getattr(self, "_initialized", False):
            return                      # live instance from __new__
        super().__init__(name, description, tag_keys)
        self.boundaries = tuple(boundaries)
        self._counts: Dict[Tuple, List[int]] = {}
        self._sums: Dict[Tuple, float] = {}
        self._totals: Dict[Tuple, int] = {}

    def observe(self, value: float,
                tags: Optional[Dict[str, str]] = None) -> None:
        self._reattach()
        key = _labels_key(tags)
        with self._lock:
            counts = self._counts.setdefault(
                key, [0] * (len(self.boundaries) + 1))
            i = 0
            while i < len(self.boundaries) and value > self.boundaries[i]:
                i += 1
            counts[i] += 1
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._totals[key] = self._totals.get(key, 0) + 1


# ---------------------------------------------------------------------------
# cross-process flow: pool workers drain deltas after each task; the
# driver merges them so user metrics from ANY process surface on the
# one Prometheus endpoint (reference: workers -> agent -> exporter)
# ---------------------------------------------------------------------------

_FLUSH_STATE: Dict[str, Dict] = {}
# one drainer at a time per process (concurrent task threads would read
# the same snapshot and double-count), and one merger at a time on the
# receiving side (check-then-create on first sight of a metric)
_FLUSH_LOCK = threading.Lock()


def drain_deltas() -> List[Dict]:
    """Changes since the last drain, as plain picklable entries.
    Counters/histograms ship DELTAS (mergeable across workers); gauges
    ship absolute values (last writer wins)."""
    with _FLUSH_LOCK:
        return _drain_deltas_locked()


def _drain_deltas_locked() -> List[Dict]:
    out: List[Dict] = []
    for name, m in registry().items():
        if m.kind == "histogram":
            prev = _FLUSH_STATE.get(name, {})
            hist = {}
            with m._lock:
                for key, counts in m._counts.items():
                    p = prev.get(key, ([0] * len(counts), 0.0, 0))
                    dc = [c - pc for c, pc in zip(counts, p[0])]
                    ds = m._sums.get(key, 0.0) - p[1]
                    dt = m._totals.get(key, 0) - p[2]
                    if dt:
                        hist[key] = (dc, ds, dt)
                _FLUSH_STATE[name] = {
                    key: (list(c), m._sums.get(key, 0.0),
                          m._totals.get(key, 0))
                    for key, c in m._counts.items()}
            if hist:
                out.append({"name": name, "kind": "histogram",
                            "description": m.description,
                            "tag_keys": m.tag_keys,
                            "boundaries": m.boundaries,
                            "hist": hist})
            continue
        prev = _FLUSH_STATE.get(name, {})
        cur = dict(m.samples())
        if m.kind == "counter":
            samples = [(k, v - prev.get(k, 0.0)) for k, v in cur.items()
                       if v != prev.get(k, 0.0)]
        else:
            samples = [(k, v) for k, v in cur.items()
                       if v != prev.get(k)]
        _FLUSH_STATE[name] = cur
        if samples:
            out.append({"name": name, "kind": m.kind,
                        "description": m.description,
                        "tag_keys": m.tag_keys, "samples": samples})
    return out


def merge_deltas(entries: List[Dict]) -> None:
    """Apply another process's drained deltas to this registry."""
    with _FLUSH_LOCK:                 # serialize check-then-create
        _merge_deltas_locked(entries)


def _merge_deltas_locked(entries: List[Dict]) -> None:
    for e in entries:
        with _REG_LOCK:
            m = _REGISTRY.get(e["name"])
        if m is None:
            if e["kind"] == "counter":
                m = Counter(e["name"], e["description"],
                            tag_keys=e.get("tag_keys", ()))
            elif e["kind"] == "gauge":
                m = Gauge(e["name"], e["description"],
                          tag_keys=e.get("tag_keys", ()))
            elif e["kind"] == "histogram":
                m = Histogram(e["name"], e["description"],
                              boundaries=e.get("boundaries",
                                               (0.01, 0.1, 1, 10, 100)),
                              tag_keys=e.get("tag_keys", ()))
            else:
                continue
        if e["kind"] == "histogram":
            if tuple(e.get("boundaries", ())) != tuple(m.boundaries):
                import warnings
                warnings.warn(
                    f"histogram {e['name']!r}: incoming boundaries "
                    f"{e.get('boundaries')} != registered "
                    f"{m.boundaries}; dropping this batch (a truncated "
                    f"merge would corrupt the exposition)",
                    stacklevel=2)
                continue
            with m._lock:
                for key, (dc, ds, dt) in e["hist"].items():
                    counts = m._counts.setdefault(
                        key, [0] * (len(m.boundaries) + 1))
                    for i, d in enumerate(dc[:len(counts)]):
                        counts[i] += d
                    m._sums[key] = m._sums.get(key, 0.0) + ds
                    m._totals[key] = m._totals.get(key, 0) + dt
        elif e["kind"] == "counter":
            for key, v in e["samples"]:
                m._add(key, v)
        else:
            for key, v in e["samples"]:
                m._set(key, v)


def registry() -> Dict[str, Metric]:
    with _REG_LOCK:
        return dict(_REGISTRY)


def clear_registry() -> None:
    with _FLUSH_LOCK:
        with _REG_LOCK:
            _REGISTRY.clear()
        # a metric re-created with the same name must not drain against
        # stale baselines (negative counter deltas break monotonicity)
        _FLUSH_STATE.clear()


def _esc_label(value: Any) -> str:
    """Escape a label VALUE per the Prometheus exposition spec: backslash,
    double-quote, and newline must be escaped or the scrape corrupts
    (e.g. a task name containing ``"`` used to break parsing)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _esc_help(text: str) -> str:
    """HELP text escaping per the spec: backslash and newline only."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt_labels(key: Tuple) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_esc_label(v)}"' for k, v in key)
    return "{" + inner + "}"


# ---------------------------------------------------------------------------
# queue-dwell gauges (observability for the control-plane hot loops:
# node dispatch, daemon reply pump, rpc server lane). Like the rpc wire
# counters, updates are PLAIN dict stores — single writer per queue
# name, last-value-wins gauge semantics, so a rare lost store under a
# race is acceptable and the hot path pays no lock.
# ---------------------------------------------------------------------------

_QUEUE_DWELL: Dict[str, float] = {}


def note_queue_dwell(queue: str, seconds: float) -> None:
    """Record how long the most recent item sat queued before service
    (``ray_tpu_queue_dwell_seconds{queue}``)."""
    _QUEUE_DWELL[queue] = seconds


def queue_dwell_entries() -> List[Dict]:
    """Dwell gauges in the export_snapshot wire-entry format."""
    if not _QUEUE_DWELL:
        return []
    return [{
        "name": "ray_tpu_queue_dwell_seconds", "kind": "gauge",
        "description": "seconds the most recently serviced item waited "
                       "in a control-plane queue",
        "samples": [[[["queue", q]], v]
                    for q, v in sorted(_QUEUE_DWELL.items())],
    }]


# ---------------------------------------------------------------------------
# cluster federation (reference: per-process OpenCensus registries merged
# into ONE Prometheus view by the metrics agent). Each process exports a
# wire-plain snapshot of its registry; daemons ship theirs to the head on
# heartbeats; the driver's dashboard renders local + federated snapshots
# with a node_id label per source.
# ---------------------------------------------------------------------------

def export_snapshot() -> List[Dict]:
    """Absolute (idempotent) snapshot of every registered metric as
    msgpack-plain entries — keys serialized as [[k, v], ...] pair lists.
    Re-sending a snapshot replaces the previous one at the receiver, so
    nothing double-counts (unlike deltas)."""
    out: List[Dict] = []
    for name, m in registry().items():
        if m.kind == "histogram":
            with m._lock:
                hist = [[[list(p) for p in key], list(counts),
                         m._sums.get(key, 0.0), m._totals.get(key, 0)]
                        for key, counts in m._counts.items()]
            if hist:
                out.append({"name": name, "kind": "histogram",
                            "description": m.description,
                            "boundaries": list(m.boundaries),
                            "hist": hist})
            continue
        samples = [[[list(p) for p in key], v] for key, v in m.samples()]
        if samples:
            out.append({"name": name, "kind": m.kind,
                        "description": m.description,
                        "samples": samples})
    try:    # wire/RPC counters live outside the registry (hot path)
        from ray_tpu._private import rpc as _rpc
        out.extend(_rpc.wire_metric_entries())
    except Exception:
        pass
    try:    # lock wait/hold meters (lock_sanitizer's metering mode)
        from ray_tpu._private import lock_sanitizer as _ls
        out.extend(_ls.lock_metric_entries())
    except Exception:
        pass
    out.extend(queue_dwell_entries())
    return out


def _inject(key, extra: Dict[str, str]) -> Tuple:
    """Label key (pair list or tuple) + per-source labels (a source's own
    label of the same name wins)."""
    pairs = {str(k): v for k, v in key}
    for k, v in (extra or {}).items():
        pairs.setdefault(k, v)
    return tuple(sorted(pairs.items()))


def render_prometheus(parts: List[Tuple[Dict[str, str], List[Dict]]]
                      ) -> str:
    """Render one exposition from many process snapshots: one HELP/TYPE
    block per metric name, every sample labeled with its source's extra
    labels (``node_id`` for federated daemons)."""
    merged: Dict[str, Dict[str, Any]] = {}
    for extra, entries in parts:
        for e in entries or []:
            slot = merged.setdefault(e["name"], {
                "kind": e["kind"], "description": e.get("description", ""),
                "boundaries": tuple(e.get("boundaries", ())),
                "scalars": [], "hists": []})
            if e["kind"] != slot["kind"]:
                continue        # conflicting registration: first wins
            if e["kind"] == "histogram":
                if tuple(e.get("boundaries", ())) != slot["boundaries"]:
                    continue    # a truncated merge would corrupt buckets
                for key, counts, hsum, total in e.get("hist", []):
                    slot["hists"].append(
                        (_inject(key, extra), counts, hsum, total))
            else:
                for key, value in e.get("samples", []):
                    slot["scalars"].append((_inject(key, extra), value))
    lines: List[str] = []
    for name in sorted(merged):
        slot = merged[name]
        # the exposition's alphabet: ``train.kept_residual_bytes`` is
        # scraped as ``train_kept_residual_bytes``
        name = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
        lines.append(f"# HELP {name} {_esc_help(slot['description'])}")
        lines.append(f"# TYPE {name} {slot['kind']}")
        if slot["kind"] == "histogram":
            for key, counts, hsum, total in slot["hists"]:
                cum = 0
                for bound, c in zip(slot["boundaries"], counts):
                    cum += c
                    lk = _inject(key, {"le": str(bound)})
                    lines.append(f"{name}_bucket{_fmt_labels(lk)} {cum}")
                lk = _inject(key, {"le": "+Inf"})
                lines.append(f"{name}_bucket{_fmt_labels(lk)} {total}")
                lines.append(f"{name}_sum{_fmt_labels(key)} {hsum}")
                lines.append(f"{name}_count{_fmt_labels(key)} {total}")
        else:
            for key, value in slot["scalars"]:
                lines.append(f"{name}{_fmt_labels(key)} {value}")
    return "\n".join(lines)


def _system_stats_lines() -> List[str]:
    lines: List[str] = []
    try:
        from ray_tpu._private import worker as _worker
        rt = _worker.global_runtime()
        if rt is not None:
            for k, v in rt.stats.items():
                lines.append(f"# TYPE ray_tpu_{k} counter")
                lines.append(f"ray_tpu_{k} {v}")
            lines.append("# TYPE ray_tpu_nodes_alive gauge")
            lines.append(
                f"ray_tpu_nodes_alive "
                f"{sum(1 for n in rt.nodes() if n.alive)}")
    except Exception:
        pass
    return lines


def _federated_parts() -> List[Tuple[Dict[str, str], List[Dict]]]:
    """Per-node metric snapshots the daemons shipped to the head with
    their heartbeats (empty outside the daemon topology)."""
    parts: List[Tuple[Dict[str, str], List[Dict]]] = []
    try:
        from ray_tpu._private import worker as _worker
        rt = _worker.global_runtime()
        backend = getattr(rt, "cluster_backend", None)
        head = getattr(backend, "head", None)
        if head is not None:
            for node_id, snap in head.metrics_get().items():
                parts.append(({"node_id": node_id}, snap))
    except Exception:
        pass
    return parts


def prometheus_text() -> str:
    """Prometheus exposition for THIS process's registry, plus the
    runtime's system stats as gauges."""
    lines = [render_prometheus([({}, export_snapshot())])]
    lines.extend(_system_stats_lines())
    return "\n".join(line for line in lines if line) + "\n"


def cluster_prometheus_text() -> str:
    """CLUSTER-WIDE exposition: this process's registry merged with every
    daemon's federated snapshot (``node_id``-labeled). Served by the
    dashboard's ``/metrics``; identical to :func:`prometheus_text` in the
    in-process topology."""
    parts = [({}, export_snapshot())] + _federated_parts()
    lines = [render_prometheus(parts)]
    lines.extend(_system_stats_lines())
    return "\n".join(line for line in lines if line) + "\n"


def cluster_metrics_json() -> Dict[str, Any]:
    """Structured (JSON) view of the cluster-wide metric samples — the
    dashboard's ``/api/metrics``."""
    rows: List[Dict[str, Any]] = []
    for extra, entries in [({}, export_snapshot())] + _federated_parts():
        for e in entries or []:
            if e["kind"] == "histogram":
                for key, counts, hsum, total in e.get("hist", []):
                    rows.append({
                        "name": e["name"], "kind": "histogram",
                        "labels": dict(_inject(key, extra)),
                        "sum": hsum, "count": total,
                        # one label per count INCLUDING the overflow
                        # bucket (counts has len(boundaries)+1 cells)
                        "buckets": dict(zip(
                            [str(b) for b in e.get("boundaries", ())]
                            + ["+Inf"],
                            counts))})
            else:
                for key, value in e.get("samples", []):
                    rows.append({"name": e["name"], "kind": e["kind"],
                                 "labels": dict(_inject(key, extra)),
                                 "value": value})
    return {"metrics": rows}
