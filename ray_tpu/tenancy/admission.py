"""Admission control: submit-time verdicts and cluster federation.

Every driver submit gets a verdict against the fair-share ledger:

- ``ADMITTED`` — within caps, flows straight to dispatch;
- ``QUEUED``   — over a hard cap (or decision path degraded): the task
  still enters the node backlog but the dispatch-side quota gate holds
  it until the job's own completions free headroom — over-cap work is
  delayed, never lost;
- ``REJECTED`` — the job's bounded pending queue
  (``admission_queue_max``) is full: surfaces as
  :class:`ray_tpu.exceptions.AdmissionRejectedError` in the submitting
  driver — the backpressure signal.

Failpoint seams: ``admission.verdict`` (drop ⇒ decision lost, fail
OPEN to admitted; error ⇒ decision path failed, degrade to QUEUED) and
``tenancy.quota_sync`` (drop/error ⇒ this federation tick is skipped,
records stay dirty and retry next tick).

The manager owns the driver-side view; when a head is attached the
quota records persist there (``--state-path``) and per-job accounting
federates via the resource-reporter tick.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from ray_tpu._private import failpoints as _fp
from ray_tpu._private.config import cfg
from ray_tpu._private.lock_sanitizer import tracked_lock
from ray_tpu.exceptions import AdmissionRejectedError
from ray_tpu.tenancy.policy import FairShareLedger
from ray_tpu.tenancy.quota import QUOTA_RESOURCES, JobQuota
from ray_tpu.util.metrics import Counter, Gauge

ADMITTED = "admitted"
QUEUED = "queued"
REJECTED = "rejected"

#: pre-built counter tags — admit() runs per submit; building a dict
#: per call shows up in drain-rate profiles
_VERDICT_TAGS = {v: {"verdict": v} for v in (ADMITTED, QUEUED, REJECTED)}

#: gauge refresh + usage federation are throttled to this period.
_REFRESH_S = 0.2
_REPORT_S = 1.0

_admission_total = Counter(
    "ray_tpu_admission_total",
    "admission verdicts by outcome", ("verdict",))
_job_running = Gauge(
    "ray_tpu_job_running_tasks",
    "tasks currently executing per job", ("job_id",))
_job_queued = Gauge(
    "ray_tpu_job_queued_tasks",
    "tasks held in node backlogs per job", ("job_id",))
_job_quota = Gauge(
    "ray_tpu_job_quota_bytes",
    "configured hard quota caps per job and resource axis",
    ("job_id", "resource"))


class TenancyManager:
    """Driver-side tenancy authority: ledger + verdicts + federation."""

    def __init__(self, runtime: Any = None,
                 enabled: Optional[bool] = None,
                 capacity_fn=None,
                 default_weight: Optional[float] = None,
                 queue_max: Optional[int] = None) -> None:
        conf = cfg()
        self.enabled = (bool(conf.fairshare)
                        if enabled is None else bool(enabled))
        self.queue_max = int(conf.admission_queue_max
                             if queue_max is None else queue_max)
        self._runtime = runtime
        if capacity_fn is None and runtime is not None:
            capacity_fn = runtime.cluster_resources
        self.ledger = FairShareLedger(
            capacity_fn or (lambda: {}),
            default_weight=float(conf.job_default_weight
                                 if default_weight is None
                                 else default_weight))
        self._lock = tracked_lock("tenancy.manager", reentrant=False)
        #: guarded by self._lock — per-job OVER-CAP submits awaiting
        #: dispatch (the REJECTED bound; admitted flow never counts)
        self._pending: Dict[str, int] = {}
        #: guarded by self._lock — per-job demand submitted but not yet
        #: dispatched. The submit-time verdict folds this in so a BURST
        #: of submits sees its own outstanding demand: usage alone made
        #: the QUEUED verdict a race against the dispatch pass (the
        #: node coalesces dispatch wakes, so a tight submit loop
        #: can finish before the first task is ever marked running).
        self._inflight: Dict[str, Dict[str, float]] = {}
        #: guarded by self._lock — quota/weight records awaiting head sync
        self._dirty: Dict[str, Dict[str, Any]] = {}
        #: guarded by self._lock
        self._records: Dict[str, Dict[str, Any]] = {}
        #: guarded by self._lock — live object attribution (oid hex ->
        #: (job, nbytes)) so frees debit the job that put the object
        self._objects: Dict[str, Any] = {}
        #: guarded by self._lock
        self._gauges_at = 0.0
        #: guarded by self._lock
        self._reported_at = 0.0

    # ------------------------------------------------------------------
    # job records / quotas
    # ------------------------------------------------------------------
    def ensure_job(self, job: str, weight: Optional[float] = None,
                   name: Optional[str] = None) -> None:
        from ray_tpu.tenancy.context import canonical_job
        job, derived = canonical_job(job)
        name = name if name is not None else derived
        self.ledger.ensure(job, weight=weight)
        if weight is not None or name is not None:
            with self._lock:
                rec = self._records.setdefault(job, {})
                if weight is not None:
                    rec["weight"] = float(weight)
                if name is not None:
                    rec["name"] = name
                self._dirty[job] = dict(rec)

    def set_quota(self, job: str,
                  hard: Optional[Dict[str, float]] = None,
                  soft: Optional[Dict[str, float]] = None,
                  weight: Optional[float] = None) -> None:
        from ray_tpu.tenancy.context import canonical_job
        job, name = canonical_job(job)
        quota = JobQuota(hard=hard or {}, soft=soft or {})
        self.ledger.set_quota(job, quota)
        if weight is not None:
            self.ledger.set_weight(job, weight)
        for res in QUOTA_RESOURCES:
            cap = quota.hard_cap(res)
            if cap is not None:
                _job_quota.set(cap, tags={"job_id": job, "resource": res})
            else:
                _job_quota.remove(tags={"job_id": job, "resource": res})
        with self._lock:
            rec = self._records.setdefault(job, {})
            rec["quota"] = quota.to_wire()
            if weight is not None:
                rec["weight"] = float(weight)
            if name is not None:
                rec["name"] = name
            self._dirty[job] = dict(rec)

    def adopt_record(self, job: str, rec: Dict[str, Any]) -> None:
        """Apply a record pulled from the head (no re-dirty)."""
        quota = JobQuota.from_wire(rec.get("quota"))
        self.ledger.set_quota(job, quota)
        if rec.get("weight") is not None:
            self.ledger.set_weight(job, float(rec["weight"]))
        with self._lock:
            self._records[job] = dict(rec)

    # ------------------------------------------------------------------
    # submit-time verdict
    # ------------------------------------------------------------------
    def admit(self, spec: Any) -> str:
        """Verdict for one submit. Raises AdmissionRejectedError on
        REJECTED; otherwise the spec proceeds into scheduling (the
        dispatch-side gate enforces QUEUED)."""
        job = spec.job_id.hex() if spec.job_id is not None else ""
        verdict = ADMITTED
        demand = spec.resources
        flight = self._inflight.get(job)  # raylint: disable=guarded-by
        if flight:
            # this task ON TOP OF the job's own not-yet-dispatched
            # submits — deterministic under a burst, dispatcher-timing
            # independent (lock-free peek; a stale read only shades
            # the advisory verdict, never correctness)
            demand = dict(demand)
            for res, v in flight.items():
                demand[res] = demand.get(res, 0.0) + v
        if self.ledger.over_hard_cap(job, demand):
            verdict = QUEUED
        if _fp.ENABLED:
            try:
                act = _fp.fire("admission.verdict", job=job,
                               verdict=verdict)
                if act is _fp.DROP:
                    verdict = ADMITTED   # decision lost: fail open
            except Exception:
                verdict = QUEUED         # decision path failed: degrade
        if verdict != ADMITTED:
            # only over-cap work counts against the bounded pending
            # queue — the ADMITTED fast path stays lock-free
            with self._lock:
                pending = self._pending.get(job, 0)
                if pending >= self.queue_max:
                    verdict = REJECTED
                else:
                    self._pending[job] = pending + 1
        if verdict != REJECTED and self.ledger.any_caps():
            # the submit's demand counts as in flight until dispatch
            # marks it running (note_admitted). Only paid once a quota
            # exists somewhere — quota-free clusters keep the lock-free
            # submit path.
            with self._lock:
                flight = self._inflight.setdefault(job, {})
                for res, v in spec.resources.items():
                    flight[res] = flight.get(res, 0.0) + float(v)
        _admission_total.inc(tags=_VERDICT_TAGS[verdict])
        if verdict == REJECTED:
            raise AdmissionRejectedError(
                f"job {job or '<driver>'}: admission queue full "
                f"({self.queue_max} pending tasks over quota); "
                f"retry after completions free capacity")
        return verdict

    # ------------------------------------------------------------------
    # dispatch hooks (called by Node)
    # ------------------------------------------------------------------
    def prefers_spread(self, job: str) -> bool:
        """Placement consult for ``ClusterScheduler.pick_node``: a job
        at a hard cap or over a soft cap spreads its queued work across
        nodes instead of packing, so per-node quota gates free
        uniformly and one node's backlog never pins the job."""
        return (self.ledger.at_hard_cap(job)
                or self.ledger.over_soft_cap(job))

    def order_buckets(self, items: List[Any]) -> List[Any]:
        # single-tenant fast path: with one job present the deficit
        # ordering is the identity — skip the ledger round-trip the
        # dispatch loop would otherwise pay every round
        first = None
        for (job, _key), _n in items:
            if first is None:
                first = job
            elif job != first:
                return self.ledger.order(items)
        return [k for k, _n in items]

    def admit_cap(self, job: str, demand: Dict[str, float],
                  want: int) -> int:
        return self.ledger.admit_cap(job, demand, want)

    def note_admitted(self, job: str, demand: Dict[str, float],
                      n: int) -> None:
        self.ledger.note_admitted(job, demand, n)
        # only over-cap (QUEUED) submits increment _pending, but a
        # dispatched group can mix previously-queued and admitted
        # tasks, so the decrement floors at 0 — the bound errs toward
        # fewer rejections, never spurious ones. Lock-free peek keeps
        # the common no-backlog drain path out of the lock.
        if self._pending.get(job, 0) > 0:  # raylint: disable=guarded-by
            with self._lock:
                left = self._pending.get(job, 0) - n
                self._pending[job] = left if left > 0 else 0
        if self._inflight.get(job):  # raylint: disable=guarded-by
            # retire the dispatched demand from the inflight view. A
            # dispatched group can mix resource shapes, so per-resource
            # floors at zero — over-subtraction CORRECTS leaks (tasks
            # cancelled before dispatch) rather than compounding them.
            with self._lock:
                flight = self._inflight.get(job)
                if flight:
                    for res, v in demand.items():
                        left = flight.get(res, 0.0) - float(v) * n
                        if left > 1e-9:
                            flight[res] = left
                        else:
                            flight.pop(res, None)
                    if not flight:
                        self._inflight.pop(job, None)
        self._refresh_gauges()

    def note_done(self, job: str, resources: Dict[str, float]) -> None:
        self.ledger.note_done(job, resources)

    def note_object_bytes(self, job: str, delta: float) -> None:
        self.ledger.note_object_bytes(job, delta)

    def note_put(self, oid_hex: str, job: str, nbytes: int) -> None:
        with self._lock:
            self._objects[oid_hex] = (job, int(nbytes))
        self.ledger.note_object_bytes(job, nbytes)

    def note_free(self, oid_hex: str) -> None:
        with self._lock:
            entry = self._objects.pop(oid_hex, None)
        if entry is not None:
            self.ledger.note_object_bytes(entry[0], -entry[1])

    def observe_queued(self, node: str, counts: Dict[str, int]) -> None:
        self.ledger.observe_queued(node, counts)
        self._refresh_gauges()

    def _refresh_gauges(self) -> None:
        now = time.monotonic()
        # lock-free throttle peek: this runs per dispatch round; the
        # stale-read race only delays one refresh by a round
        if now - self._gauges_at < _REFRESH_S:  # raylint: disable=guarded-by
            return
        with self._lock:
            if now - self._gauges_at < _REFRESH_S:
                return
            self._gauges_at = now
        snap = self.ledger.snapshot()
        for job, row in snap.items():
            tags = {"job_id": job or "<driver>"}
            _job_running.set(float(row["running"]), tags=tags)
            _job_queued.set(float(row["queued"]), tags=tags)
        with self._lock:
            # reconcile the pending bound: specs that died before
            # dispatch (cancel, unschedulable) never hit note_admitted
            # and would otherwise leak counts. Only a FULLY idle job is
            # reset — reconciling against observed backlog depth while
            # work is in flight would race submits mid-bucketing and
            # deflate the rejection bound.
            for job, row in snap.items():
                if int(row["queued"]) == 0 and int(row["running"]) == 0:
                    if self._pending.get(job, 0) > 0:
                        self._pending[job] = 0
                    self._inflight.pop(job, None)

    # ------------------------------------------------------------------
    # views / federation
    # ------------------------------------------------------------------
    def jobs_view(self) -> Dict[str, Dict[str, Any]]:
        snap = self.ledger.snapshot()
        with self._lock:
            for job, row in snap.items():
                row["pending"] = self._pending.get(job, 0)
                rec = self._records.get(job)
                if rec and rec.get("name"):
                    row["name"] = rec["name"]
        return snap

    def maybe_sync(self, backend: Any) -> None:
        """Federation tick (piggybacks the resource reporter): push
        dirty quota records to the head (persisted) and to daemons that
        advertised the ``tenancy`` hello capability, then report usage.
        All RPCs run outside the manager lock."""
        head = getattr(backend, "head", None)
        if head is None:
            return
        now = time.monotonic()
        with self._lock:
            dirty = dict(self._dirty)
            report_due = now - self._reported_at >= _REPORT_S
            if report_due:
                self._reported_at = now
        if not dirty and not report_due:
            return
        if _fp.ENABLED:
            try:
                if _fp.fire("tenancy.quota_sync",
                            dirty=len(dirty)) is _fp.DROP:
                    return   # records stay dirty; retried next tick
            except Exception:
                return
        try:
            for job, rec in dirty.items():
                head.tenancy_set(job, rec)
            if report_due:
                head.tenancy_report(self.jobs_view())
            if dirty or (report_due and self.ledger.any_caps()):
                table = {}
                with self._lock:
                    table = {j: dict(r)
                             for j, r in self._records.items()}
                # over-quota jobs ride along so node memory monitors
                # can point OOM preemption at them first (pressure.py
                # TenantAwarePolicy — only meaningful once caps exist)
                over = [j for j in table if self.ledger.at_hard_cap(j)]
                for handle in getattr(backend, "daemons", {}).values():
                    if getattr(handle, "_tenancy_supported", False):
                        handle.client.call("tenancy_sync", jobs=table,
                                           over_quota=over)
        except Exception:
            return   # still dirty; retried next tick
        with self._lock:
            for job in dirty:
                if self._dirty.get(job) == dirty[job]:
                    del self._dirty[job]

    def load_from_head(self, head: Any) -> None:
        """Adopt quota records persisted at the head (other drivers or
        a previous incarnation may have set them)."""
        try:
            records = head.tenancy_get() or {}
        except Exception:
            return
        for job, rec in records.items():
            if isinstance(rec, dict) and (rec.get("quota")
                                          or rec.get("weight")):
                self.adopt_record(job, rec)
