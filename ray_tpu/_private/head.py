"""Head control-plane process (the GCS-server equivalent).

Reference capability: ``src/ray/gcs/gcs_server/gcs_server.h:91`` — node
membership, active health checking (``gcs_health_check_manager.h``),
internal KV (``gcs_kv_manager.h``), and long-poll pubsub
(``src/ray/pubsub/publisher.h:300``). Spawned as its own OS process
(``python -m ray_tpu._private.head``); every interaction is a typed
msgpack RPC (:mod:`ray_tpu._private.rpc`).

TPU-first division of labor: the head holds *cluster* state only — node
directory, health, KV (function table / rendezvous), pubsub. Object
ownership, scheduling authority, and lineage stay with the single
controller (the driver), which matches the SPMD model: gang placement is
decided centrally, and the accelerator data plane never crosses this
process.

Services:
- NodeInfo: register_node / heartbeat / list_nodes / drain_node;
  a monitor thread marks nodes dead after ``DEAD_AFTER_S`` without a
  heartbeat and publishes ``node_death`` (active health checking).
  ``drain_node(node_id, deadline_s, reason)`` moves the node to a
  DRAINING membership state and publishes a ``node_drain`` event so the
  scheduling authority can migrate work off it; when the deadline
  expires the monitor escalates into the ordinary death path
  (reference: the GCS DrainNode RPC + autoscaler drain protocol,
  ``gcs_node_manager.cc HandleDrainNode``). A node that was declared
  dead may NOT re-register under the same id (zombie fencing,
  mirroring the heartbeat ``{"dead": True}`` contract).
- InternalKV: kv_put / kv_get / kv_del / kv_keys (bytes in, bytes out).
- Pubsub: subscribe(channel) parks the request (long-poll HOLD); publish
  completes every parked subscriber with the event batch.

Fault tolerance (reference: GCS restart reload —
``gcs/store_client/redis_store_client.h``, ``gcs_init_data.h``): with
``--state-path`` the KV table and pubsub event logs are write-through
persisted to sqlite, so a restarted head resumes with identical KV
contents and valid pubsub cursors. Node membership is NOT persisted —
live daemons re-register themselves on their next heartbeat (the
raylet-resync model), which is the ground truth for liveness anyway.
"""

from __future__ import annotations

import argparse
import sqlite3
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import msgpack

from ray_tpu._private import failpoints as _fp
from ray_tpu._private import rpc
from ray_tpu._private.aio import AsyncClient, AsyncConnection
from ray_tpu._private.lock_sanitizer import tracked_lock
from ray_tpu._private.rpc import HOLD, declare

def _hb_interval() -> float:
    from ray_tpu._private.config import cfg
    return cfg().heartbeat_interval_s


def _dead_after() -> float:
    from ray_tpu._private.config import cfg
    return cfg().node_dead_after_s


# back-compat names (resolved through the central flag table)
HEARTBEAT_S = 0.2

declare("register_node", "node_id", "resources", "labels", "addr")
# heartbeat piggybacks observability: ``wall_ts`` (sender clock, for the
# head's per-node clock-offset estimate), ``events`` (daemon/worker span
# batch for the task-event store), ``metrics`` (absolute metric snapshot
# federated into the cluster /metrics view) — all optional/empty.
declare("heartbeat", "node_id", "available", "wall_ts", "events",
        "metrics", "profile", "epoch")
declare("metrics_get")
declare("profile_get")
declare("list_nodes")
declare("drain_node", "node_id", "deadline_s", "reason")
declare("mark_node_dead", "node_id", "reason")
declare("kv_put", "key", "value", "overwrite", "ns")
declare("kv_get", "key", "ns")
declare("kv_del", "key", "ns")
declare("kv_keys", "prefix", "ns")
declare("subscribe", "channel", "cursor")
declare("publish", "channel", "event")
declare("report_resources", "loads")
declare("report_loads_gossip", "view")
declare("task_events_push", "events")
declare("task_events_get", "job_id", "name", "limit")
# tenancy: persisted per-job quota/weight records (the admission
# authority's durable store) + per-job accounting federation
declare("tenancy_set", "job_id", "record")
declare("tenancy_get")
declare("tenancy_report", "jobs")
declare("head_stop")

# High-frequency gossip channels: never persisted, log trimmed to a
# window (the RaySyncer stream carries LATEST views, not history).
TRANSIENT_CHANNELS = {"resources"}
TRANSIENT_WINDOW = 200


class _NodeEntry:
    __slots__ = ("node_id", "resources", "labels", "addr", "alive",
                 "last_beat", "available", "reason", "avail_gossip_ts",
                 "draining", "drain_deadline", "drain_reason", "epoch")

    def __init__(self, node_id: str, resources: Dict[str, float],
                 labels: Dict[str, str], addr: Tuple[str, int]):
        self.node_id = node_id
        self.resources = resources
        self.labels = labels
        self.addr = addr
        self.alive = True
        self.last_beat = time.monotonic()
        self.available = dict(resources)
        self.reason = ""
        self.avail_gossip_ts = 0.0   # last syncer report for this node
        # graceful-drain state: alive + draining = no NEW placements,
        # running work may finish; past drain_deadline the monitor
        # escalates to the death path
        self.draining = False
        self.drain_deadline = 0.0    # monotonic
        self.drain_reason = ""
        # fencing epoch minted by the head at register_node; a frame
        # stamped with a LOWER epoch comes from a pre-death incarnation
        self.epoch = 0

    def view(self) -> Dict[str, Any]:
        return {"node_id": self.node_id, "resources": self.resources,
                "labels": self.labels, "addr": list(self.addr),
                "alive": self.alive, "available": self.available,
                "epoch": self.epoch,
                "reason": self.reason, "draining": self.draining,
                "drain_reason": self.drain_reason,
                "drain_deadline_s": (
                    max(0.0, self.drain_deadline - time.monotonic())
                    if self.draining else 0.0)}


class _HeadStore:
    """Write-through sqlite persistence for head state (GCS-FT role)."""

    def __init__(self, path: str):
        self._db = sqlite3.connect(path, check_same_thread=False)
        self._db.execute("PRAGMA journal_mode=WAL")
        # Writes happen under the HeadService lock: per-op fsync there
        # would stall every head RPC (incl. heartbeats) behind disk.
        # WAL + synchronous=NORMAL keeps commits memory-speed; the WAL
        # still survives a head-process crash (the FT case we replay).
        self._db.execute("PRAGMA synchronous=NORMAL")
        self._db.execute(
            "CREATE TABLE IF NOT EXISTS kv (key BLOB PRIMARY KEY, "
            "value BLOB)")
        self._db.execute(
            "CREATE TABLE IF NOT EXISTS events (channel TEXT, idx INTEGER, "
            "event BLOB, PRIMARY KEY(channel, idx))")
        # Head-side task-event store (reference: gcs_task_manager.h:94):
        # task state transitions buffered by drivers land here so the
        # state API / timeline survive driver exit. Bounded by row count.
        self._db.execute(
            "CREATE TABLE IF NOT EXISTS task_events ("
            "seq INTEGER PRIMARY KEY AUTOINCREMENT, "
            "task_id TEXT, name TEXT, event TEXT, job_id TEXT, "
            "wall_ts REAL, payload BLOB)")
        self._db.commit()

    def append_task_events(self, events: List[Dict[str, Any]],
                           max_rows: int) -> None:
        self._db.executemany(
            "INSERT INTO task_events "
            "(task_id, name, event, job_id, wall_ts, payload) "
            "VALUES (?, ?, ?, ?, ?, ?)",
            [(ev.get("task_id", ""), ev.get("name", ""),
              ev.get("event", ""), ev.get("job_id", ""),
              ev.get("wall_ts", 0.0),
              msgpack.packb(ev, use_bin_type=True))
             for ev in events])
        # bounded: drop the oldest rows past the cap (one statement,
        # amortized — gcs_task_manager evicts the same way)
        self._db.execute(
            "DELETE FROM task_events WHERE seq <= ("
            "SELECT MAX(seq) FROM task_events) - ?", (max_rows,))
        self._db.commit()

    def get_task_events(self, job_id: str = "", name: str = "",
                        limit: int = 10_000) -> List[Dict[str, Any]]:
        q = "SELECT payload FROM task_events"
        cond, args = [], []
        if job_id:
            cond.append("job_id = ?")
            args.append(job_id)
        if name:
            cond.append("name = ?")
            args.append(name)
        if cond:
            q += " WHERE " + " AND ".join(cond)
        q += " ORDER BY seq DESC LIMIT ?"
        args.append(int(limit))
        rows = self._db.execute(q, args).fetchall()
        out = [msgpack.unpackb(r[0], raw=False) for r in rows]
        out.reverse()
        return out

    def load(self) -> Tuple[Dict[bytes, bytes], Dict[str, List[Any]]]:
        kv = {bytes(k): bytes(v) for k, v in
              self._db.execute("SELECT key, value FROM kv")}
        events: Dict[str, List[Any]] = {}
        for chan, idx, blob in self._db.execute(
                "SELECT channel, idx, event FROM events "
                "ORDER BY channel, idx"):
            events.setdefault(chan, []).append(
                msgpack.unpackb(blob, raw=False))
        return kv, events

    def put(self, key: bytes, value: bytes) -> None:
        self._db.execute("INSERT OR REPLACE INTO kv VALUES (?, ?)",
                         (key, value))
        self._db.commit()

    def delete(self, key: bytes) -> None:
        self._db.execute("DELETE FROM kv WHERE key = ?", (key,))
        self._db.commit()

    def append_event(self, channel: str, idx: int, event: Any) -> None:
        self._db.execute(
            "INSERT OR REPLACE INTO events VALUES (?, ?, ?)",
            (channel, idx, msgpack.packb(event, use_bin_type=True)))
        self._db.commit()


# Persisted drain records live in the head store's kv table under this
# raw prefix. Client-visible keys are stored as ``ns + b":" + key`` —
# they ALWAYS contain a colon — so a colon-free prefix can never collide
# with (or leak into) any namespace's kv_get/kv_keys view.
_DRAIN_KEY = b"\x00drain\x00"
# Per-job tenancy records (quota/weight) persist under the same
# colon-free raw-prefix scheme: ``--state-path`` survives head respawn,
# so quotas outlive both the head process and the submitting driver.
_TENANCY_KEY = b"\x00tenancy\x00"
# Per-node fencing epochs persist under the same colon-free raw-prefix
# scheme: epochs must be monotonic ACROSS head restarts, or a healed
# pre-restart zombie could stamp frames the fence accepts.
_EPOCH_KEY = b"\x00epoch\x00"


class HeadService:
    def __init__(self, state_path: Optional[str] = None):
        self._lock = tracked_lock("head.state", reentrant=False)
        self._nodes: Dict[str, _NodeEntry] = {}  #: guarded by self._lock
        self._kv: Dict[bytes, bytes] = {}        #: guarded by self._lock
        # pubsub: channel -> (event log, parked subscriber conns)
        self._events: Dict[str, List[Any]] = {}  #: guarded by self._lock
        #: guarded by self._lock
        self._bases: Dict[str, int] = {}   # trimmed-channel log offsets
        #: guarded by self._lock
        self._parked: Dict[str, List[Tuple[AsyncConnection, int, int]]] = {}
        self._store: Optional[_HeadStore] = None
        # task-event store: sqlite when persistent, bounded ring in
        # memory otherwise (reference: gcs_task_manager.h:94)
        self._task_events_cap = 100_000
        # per-node load entries converged via daemon peer gossip
        # (report_loads_gossip); versioned like the daemons' own views
        self._gossip_loads: Dict[str, Dict[str, Any]] = {}  #: guarded by self._lock
        from collections import deque as _deque
        self._task_events: Any = _deque(maxlen=self._task_events_cap)
        # metrics federation: node_id -> latest absolute metric snapshot
        # shipped on that daemon's heartbeat (snapshot REPLACE, so a
        # re-sent frame never double-counts); per-node clock offset
        # (head wall - daemon wall) estimated from the same heartbeats.
        #: guarded by self._lock
        self._node_metrics: Dict[str, List[Dict[str, Any]]] = {}
        self._node_clock_off: Dict[str, float] = {}  #: guarded by self._lock
        # profile federation: node_id -> latest CUMULATIVE profile
        # payload off that daemon's heartbeat (replace semantics — the
        # counters only grow, so the newest payload supersedes all)
        #: guarded by self._lock
        self._node_profiles: Dict[str, Dict[str, Any]] = {}
        # node_id -> (wall-clock deadline, reason): drains survive a
        # head restart (membership does not, so the record re-attaches
        # when the draining daemon re-registers after the respawn).
        self._drains: Dict[str, Tuple[float, str]] = {}  #: guarded by self._lock
        # tenancy: job -> {"weight": .., "quota": {"hard": .., "soft": ..}}
        # (persisted) and job -> latest reported usage row (replace
        # semantics — each driver report supersedes its previous one).
        self._tenancy: Dict[str, Dict[str, Any]] = {}  #: guarded by self._lock
        self._tenancy_usage: Dict[str, Dict[str, Any]] = {}  #: guarded by self._lock
        # node_id -> last minted fencing epoch (persisted: epochs stay
        # monotonic across a head restart even though membership resets)
        self._node_epochs: Dict[str, int] = {}  #: guarded by self._lock
        if state_path:
            self._store = _HeadStore(state_path)
            self._kv, self._events = self._store.load()
            for key in [k for k in self._kv if k.startswith(_DRAIN_KEY)]:
                blob = self._kv.pop(key)
                try:
                    rec = msgpack.unpackb(blob, raw=False)
                    self._drains[key[len(_DRAIN_KEY):].decode()] = (
                        float(rec["deadline_wall"]), str(rec["reason"]))
                except Exception:
                    # a malformed record must not keep the head down
                    self._store.delete(key)
            for key in [k for k in self._kv
                        if k.startswith(_TENANCY_KEY)]:
                blob = self._kv.pop(key)
                try:
                    self._tenancy[key[len(_TENANCY_KEY):].decode()] = (
                        msgpack.unpackb(blob, raw=False))
                except Exception:
                    self._store.delete(key)
            for key in [k for k in self._kv
                        if k.startswith(_EPOCH_KEY)]:
                blob = self._kv.pop(key)
                try:
                    self._node_epochs[key[len(_EPOCH_KEY):].decode()] = (
                        int(blob))
                except Exception:
                    self._store.delete(key)
        self._stop = threading.Event()
        self._monitor = threading.Thread(target=self._health_loop,
                                         daemon=True, name="head-health")
        self._monitor.start()

    # -- node membership / health ---------------------------------------
    def handle_register_node(self, conn, rid, msg):
        node_id = msg["node_id"]
        entry = _NodeEntry(node_id, msg["resources"],
                           msg["labels"], tuple(msg["addr"]))
        with self._lock:
            cur = self._nodes.get(node_id)
            if cur is not None and not cur.alive:
                # Zombie fencing: this node was declared dead (death
                # published, owners already recovered its work); a
                # re-registration would resurrect it with stale state.
                # Same contract as the heartbeat {"dead": True} reply —
                # the daemon must exit.
                return {"ok": False, "dead": True, "reason": cur.reason}
            drain = self._drains.get(node_id)
            if drain is not None:
                # A drain survived a head restart: re-attach it with the
                # remaining wall-clock window.
                entry.draining = True
                entry.drain_deadline = time.monotonic() + max(
                    0.0, drain[0] - time.time())
                entry.drain_reason = drain[1]
            # Mint a monotonic fencing epoch for this incarnation:
            # bumped on EVERY register (a re-registration after a head
            # restart or death-mark gets a strictly higher epoch), and
            # persisted so epochs survive head respawn. Drivers fence
            # result frames stamped with an older epoch.
            epoch = self._node_epochs.get(node_id, 0) + 1
            self._node_epochs[node_id] = epoch
            entry.epoch = epoch
            if self._store is not None:
                self._store.put(_EPOCH_KEY + node_id.encode(),
                                str(epoch).encode())
            self._nodes[node_id] = entry
        conn.meta["node_id"] = node_id
        conn.link("daemon", node_id)
        self._publish("node", {"kind": "added", "node": entry.view()})
        if entry.draining:
            # re-announce so a (re)subscribed driver resumes migration
            self._publish("node", {
                "kind": "drain", "node_id": node_id,
                "deadline_s": max(0.0, entry.drain_deadline
                                  - time.monotonic()),
                "reason": entry.drain_reason})
        return {"ok": True, "draining": entry.draining,
                "epoch": entry.epoch}

    def handle_heartbeat(self, conn, rid, msg):
        node_id = msg["node_id"]
        # clock-offset estimate (head wall - daemon wall at receipt; the
        # half-RTT error is negligible next to cross-host clock skew):
        # applied to every span the daemon flushes so the merged timeline
        # shares ONE timebase.
        off = 0.0
        wall = float(msg.get("wall_ts") or 0.0)
        if wall:
            off = time.time() - wall
        with self._lock:
            entry = self._nodes.get(node_id)
            if entry is None:
                return {"ok": False, "unknown": True}
            ep = msg.get("epoch")
            if ep is not None and ep and entry.epoch and ep < entry.epoch:
                # Stale-epoch beat: a NEWER incarnation of this node_id
                # has registered since this sender's epoch was minted.
                # The zombie must exit — and its beat must not refresh
                # the live incarnation's liveness.
                return {"ok": False, "dead": True, "stale_epoch": True}
            entry.last_beat = time.monotonic()
            # The daemon's heartbeat carries its STATIC resources; the
            # driver's syncer gossip carries the true availability.
            # Gossip wins while fresh; heartbeat repopulates once the
            # reporting driver goes quiet (left / crashed).
            if time.monotonic() - entry.avail_gossip_ts > 2.0:
                entry.available = msg["available"]
            was_dead = not entry.alive
            draining = entry.draining
            if wall:
                self._node_clock_off[node_id] = off
            snapshot = msg.get("metrics")
            if snapshot is not None:
                self._node_metrics[node_id] = snapshot
            profile = msg.get("profile")
            if profile is not None:
                self._node_profiles[node_id] = profile
        if was_dead:
            # A heartbeat from a node we declared dead: tell it to exit
            # (reference: raylets that lost GCS contact must not rejoin
            # with stale state).
            return {"ok": False, "dead": True}
        events = msg.get("events") or []
        if events:
            for ev in events:
                if off:
                    ev["wall_ts"] = ev.get("wall_ts", 0.0) + off
                    if "start_wall" in ev:
                        ev["start_wall"] = ev["start_wall"] + off
                ev["clock_off"] = off
                ev.setdefault("node_id", node_id)
            self._ingest_task_events(events)
        return {"ok": True, "draining": draining,
                "head_wall": time.time()}

    def handle_metrics_get(self, conn, rid, msg):
        """Federated per-node metric snapshots (daemon heartbeats)."""
        with self._lock:
            return {"nodes": {nid: snap for nid, snap
                              in self._node_metrics.items()}}

    def handle_profile_get(self, conn, rid, msg):
        """Federated per-node profile payloads (daemon heartbeats) plus
        the head's own continuous-sampler record."""
        with self._lock:
            nodes = dict(self._node_profiles)
        try:
            from ray_tpu.util import profiling as _profiling
            own = _profiling.process_profile()
        except Exception:
            own = None
        return {"nodes": nodes, "head": own}

    def handle_list_nodes(self, conn, rid, msg):
        with self._lock:
            nodes = [e.view() for e in self._nodes.values()]
            for n in nodes:
                g = self._gossip_loads.get(n["node_id"])
                if g is not None:
                    n["gossip_load"] = g["load"]
                    n["gossip_version"] = g["v"]
            return {"nodes": nodes}

    def handle_drain_node(self, conn, rid, msg):
        """Graceful drain: publish ``node_drain`` and keep the node
        alive-but-DRAINING so running work can finish and the driver can
        migrate objects/actors off it; the health monitor escalates to
        the death path when ``deadline_s`` expires. (The former behavior
        — an immediate ``_mark_dead`` — made every planned departure as
        expensive as a crash.)"""
        node_id = msg["node_id"]
        deadline_s = max(0.0, float(msg.get("deadline_s") or 0.0))
        reason = msg.get("reason") or "drain"
        with self._lock:
            entry = self._nodes.get(node_id)
            if entry is None or not entry.alive:
                return {"ok": False, "unknown": True}
            if entry.draining:
                # idempotent: the first drain's deadline stands
                return {"ok": True, "already": True}
            entry.draining = True
            entry.drain_deadline = time.monotonic() + deadline_s
            entry.drain_reason = reason
            self._drains[node_id] = (time.time() + deadline_s, reason)
            if self._store is not None:
                self._store.put(
                    _DRAIN_KEY + node_id.encode(),
                    msgpack.packb({"deadline_wall": time.time()
                                   + deadline_s,
                                   "reason": reason},
                                  use_bin_type=True))
        self._publish("node", {"kind": "drain", "node_id": node_id,
                               "deadline_s": deadline_s,
                               "reason": reason})
        return {"ok": True}

    def handle_mark_node_dead(self, conn, rid, msg):
        # The driver observed a daemon failure directly (RPC error) and
        # reports it before the health window elapses.
        self._mark_dead(msg["node_id"], msg["reason"])
        return {"ok": True}

    def _mark_dead(self, node_id: str, reason: str,
                   drain_expired: bool = False) -> None:
        with self._lock:
            entry = self._nodes.get(node_id)
            if entry is None or not entry.alive:
                return
            entry.alive = False
            entry.reason = reason
            was_draining = entry.draining
            entry.draining = False
            self._drains.pop(node_id, None)
            # a dead node's last metric snapshot must not keep being
            # served as live by the cluster /metrics federation (and
            # the dicts must not grow forever under node churn)
            self._node_metrics.pop(node_id, None)
            self._node_clock_off.pop(node_id, None)
            self._node_profiles.pop(node_id, None)
            if self._store is not None:
                self._store.delete(_DRAIN_KEY + node_id.encode())
        self._publish("node", {"kind": "death", "node_id": node_id,
                               "reason": reason,
                               "was_draining": was_draining,
                               "drain_expired": drain_expired})

    def _health_loop(self) -> None:
        while not self._stop.wait(_hb_interval()):
            now = time.monotonic()
            dead: List[str] = []
            expired: List[str] = []
            window = _dead_after()
            with self._lock:
                for entry in self._nodes.values():
                    if not entry.alive:
                        continue
                    if now - entry.last_beat > window:
                        dead.append(entry.node_id)
                    elif entry.draining and now > entry.drain_deadline:
                        expired.append(entry.node_id)
            for node_id in dead:
                self._mark_dead(node_id, "missed heartbeats")
            for node_id in expired:
                # escalation: the drain window closed with the node
                # still up — fall back to the ordinary death path
                # (lineage reconstruction covers whatever did not
                # migrate in time)
                self._mark_dead(node_id, "drain deadline expired",
                                drain_expired=True)

    def on_disconnect(self, conn: AsyncConnection) -> None:
        node_id = conn.meta.get("node_id")
        if node_id:
            self._mark_dead(node_id, "connection lost")
        # drop parked long-polls from this conn
        with self._lock:
            for parked in self._parked.values():
                parked[:] = [p for p in parked if p[0] is not conn]

    # -- internal KV -----------------------------------------------------
    # -- tenancy: quota store + per-job accounting federation -----------
    def handle_tenancy_set(self, conn, rid, msg):
        """Upsert one job's quota/weight record (persisted)."""
        job = str(msg["job_id"])
        record = msg.get("record") or {}
        with self._lock:
            self._tenancy[job] = record
            if self._store is not None:
                self._store.put(_TENANCY_KEY + job.encode(),
                                msgpack.packb(record, use_bin_type=True))
        return {"ok": True}

    def handle_tenancy_get(self, conn, rid, msg):
        """All job records, with the latest federated usage merged in."""
        with self._lock:
            jobs = {j: dict(r) for j, r in self._tenancy.items()}
            for j, usage in self._tenancy_usage.items():
                jobs.setdefault(j, {})["usage"] = usage
        return {"jobs": jobs}

    def handle_tenancy_report(self, conn, rid, msg):
        """Per-job accounting federation (replace semantics per job)."""
        jobs = msg.get("jobs") or {}
        with self._lock:
            for j, row in jobs.items():
                self._tenancy_usage[str(j)] = row
        return {"ok": True, "count": len(jobs)}

    def handle_kv_put(self, conn, rid, msg):
        if _fp.ENABLED:
            # crash arm = head dies mid-put (the respawn/redial drill);
            # error arm surfaces as a RemoteError at the caller
            _fp.fire("head.kv_put")
        key = msg["ns"] + b":" + msg["key"]
        with self._lock:
            if not msg["overwrite"] and key in self._kv:
                return {"added": False}
            self._kv[key] = msg["value"]
            if self._store is not None:
                self._store.put(key, msg["value"])
        return {"added": True}

    def handle_kv_get(self, conn, rid, msg):
        with self._lock:
            value = self._kv.get(msg["ns"] + b":" + msg["key"])
        return {"value": value}

    def handle_kv_del(self, conn, rid, msg):
        key = msg["ns"] + b":" + msg["key"]
        with self._lock:
            self._kv.pop(key, None)
            if self._store is not None:
                self._store.delete(key)
        return {"ok": True}

    def handle_kv_keys(self, conn, rid, msg):
        pre = msg["ns"] + b":" + msg["prefix"]
        nslen = len(msg["ns"]) + 1
        with self._lock:
            keys = [k[nslen:] for k in self._kv if k.startswith(pre)]
        return {"keys": keys}

    # -- long-poll pubsub -------------------------------------------------
    def handle_subscribe(self, conn, rid, msg):
        """Long-poll: reply immediately if the cursor is behind, else park
        until the next publish (reference: long_poll.py:70,222 — clients
        hold a request open and the host completes it on change)."""
        channel, cursor = msg["channel"], msg["cursor"]
        with self._lock:
            log = self._events.setdefault(channel, [])
            base = self._bases.get(channel, 0)
            total = base + len(log)
            if cursor < total:
                start = max(0, cursor - base)  # trimmed past: skip ahead
                return {"events": log[start:], "cursor": total}
            self._parked.setdefault(channel, []).append(
                (conn, rid, cursor))
        return HOLD

    def _publish(self, channel: str, event: Any) -> None:
        if _fp.ENABLED and _fp.fire("head.pubsub_publish",
                                    channel=channel) is _fp.DROP:
            return      # event lost before the log (subscribers starve)
        with self._lock:
            log = self._events.setdefault(channel, [])
            log.append(event)
            if channel in TRANSIENT_CHANNELS:
                if len(log) > TRANSIENT_WINDOW:  # keep only the window
                    drop = len(log) - TRANSIENT_WINDOW
                    del log[:drop]
                    self._bases[channel] = \
                        self._bases.get(channel, 0) + drop
            elif self._store is not None:
                self._store.append_event(channel, len(log) - 1, event)
            parked = self._parked.pop(channel, [])
            base = self._bases.get(channel, 0)
            cursor = base + len(log)
        for conn, rid, start in parked:
            conn.reply(rid, events=log[max(0, start - base):],
                       cursor=cursor)

    def handle_publish(self, conn, rid, msg):
        self._publish(msg["channel"], msg["event"])
        return {"ok": True}

    # -- task events (reference: gcs_task_manager.h:94) ------------------
    def _ingest_task_events(self, events: List[Dict[str, Any]]) -> None:
        with self._lock:
            if self._store is not None:
                self._store.append_task_events(events,
                                               self._task_events_cap)
            else:
                self._task_events.extend(events)

    def handle_task_events_push(self, conn, rid, msg):
        events = msg["events"]
        self._ingest_task_events(events)
        return {"ok": True, "count": len(events)}

    def handle_task_events_get(self, conn, rid, msg):
        job_id = msg.get("job_id") or ""
        name = msg.get("name") or ""
        limit = int(msg.get("limit") or 10_000)
        with self._lock:
            if self._store is not None:
                out = self._store.get_task_events(job_id, name, limit)
            else:
                out = [ev for ev in self._task_events
                       if (not job_id or ev.get("job_id") == job_id)
                       and (not name or ev.get("name") == name)]
                out = out[-limit:]
        return {"events": out}

    def handle_report_resources(self, conn, rid, msg):
        """Resource-view gossip (the RaySyncer role,
        ``common/ray_syncer/ray_syncer.h:83``): the scheduling authority
        pushes per-node availability; the head updates its membership
        view and re-broadcasts on the transient 'resources' channel so
        any subscriber (state API, autoscaler, other drivers) converges
        on the same cluster view without polling."""
        updated = {}
        with self._lock:
            for node_hex, avail in msg["loads"].items():
                entry = self._nodes.get(node_hex)
                if entry is not None and entry.alive:
                    entry.available = dict(avail)
                    entry.avail_gossip_ts = time.monotonic()
                    updated[node_hex] = dict(avail)
        if updated:
            self._publish("resources", {"available": updated})
        return {"ok": True}

    def handle_report_loads_gossip(self, conn, rid, msg):
        """Peer-gossip ingestion (reference: ray_syncer.h:83): ONE node
        per gossip interval pushes the cluster-wide merged view it
        converged on — the head never needs per-node load reports, so
        its inbound load-report rate is O(1) in cluster size."""
        with self._lock:
            for node_hex, entry in msg["view"].items():
                cur = self._gossip_loads.get(node_hex)
                if cur is None or entry["v"] > cur["v"]:
                    self._gossip_loads[node_hex] = dict(entry)
        return {"ok": True}

    def handle_head_stop(self, conn, rid, msg):
        self._stop.set()
        threading.Thread(target=lambda: (time.sleep(0.1),
                                         __import__("os")._exit(0)),
                         daemon=True).start()
        return {"ok": True}


class HeadClient:
    """Typed client for head services, with a background subscriber.

    ``reconnect_window`` > 0 makes every call transparently re-dial the
    head for up to that many seconds on transport failure — the driver's
    survival path across a head restart (reference: GCS client retries,
    ``gcs/gcs_client``).
    """

    def __init__(self, addr: Tuple[str, int], reconnect_window: float = 0.0):
        self._client = rpc.connect(addr).link("head")
        self.addr = addr
        self._reconnect_window = reconnect_window
        self._dial_lock = tracked_lock("head_client.dial",
                                       reentrant=False)
        self._sub_stop = threading.Event()
        self._sub_threads: List[threading.Thread] = []
        # live per-channel subscriber connections, tracked so close()
        # can actually close them (a parked long-poll otherwise holds
        # its socket open forever)
        self._sub_clients: List[AsyncClient] = []  #: guarded by self._sub_lock
        self._sub_lock = tracked_lock("head_client.subs",
                                      reentrant=False)
        self._retry_policy = None   # built lazily; immutable once made

    def _redial(self) -> None:
        with self._dial_lock:
            if not self._client.dead:
                return
            # raises OSError while head is down. _dial_lock exists to
            # single-flight this dial — concurrent callers must wait
            # for ONE reconnect, not race N of them — so holding it
            # across the connect is the lock's entire purpose.
            client = rpc.connect(self.addr).link("head")  # raylint: disable=blocking-under-lock
            old, self._client = self._client, client
            old.close()

    def _call(self, method: str, timeout: Optional[float] = None, **kw):
        if self._reconnect_window <= 0:
            return self._client.call(method, timeout=timeout, **kw)
        if self._retry_policy is None:
            # built once: cfg() reads + dataclass construction must not
            # ride every head RPC on the control-plane hot path
            from ray_tpu._private.retry import RetryPolicy
            self._retry_policy = RetryPolicy.default(
                deadline_s=self._reconnect_window)

        def attempt():
            try:
                # the client may have been swapped by a redial; read it
                # fresh each attempt
                return self._client.call(method, timeout=timeout, **kw)
            except rpc.RpcError:
                try:
                    self._redial()
                except OSError:
                    pass        # head still down: next attempt retries
                raise

        return self._retry_policy.run(
            attempt, loop="head.redial", retry_on=(rpc.RpcError,))

    # node info
    def register_node(self, node_id: str, resources: Dict[str, float],
                      labels: Dict[str, str], addr: Tuple[str, int]):
        return self._call("register_node", node_id=node_id,
                          resources=resources, labels=labels,
                          addr=list(addr))

    def heartbeat(self, node_id: str, available: Dict[str, float],
                  wall_ts: float = 0.0,
                  events: Optional[List[Dict[str, Any]]] = None,
                  metrics: Optional[List[Dict[str, Any]]] = None,
                  profile: Optional[Dict[str, Any]] = None,
                  epoch: int = 0):
        return self._call("heartbeat", node_id=node_id,
                          available=available, wall_ts=wall_ts,
                          events=events or [], metrics=metrics,
                          profile=profile, epoch=epoch, timeout=5.0)

    def metrics_get(self) -> Dict[str, List[Dict[str, Any]]]:
        """node_id -> latest federated metric snapshot. Bounded: a
        wedged head must not hang a dashboard scrape thread forever."""
        return self._call("metrics_get", timeout=5.0)["nodes"]

    def profile_get(self) -> Dict[str, Any]:
        """{"nodes": node_id -> federated profile payload, "head": the
        head's own record or None}."""
        return self._call("profile_get", timeout=5.0)

    def list_nodes(self) -> List[Dict[str, Any]]:
        return self._call("list_nodes")["nodes"]

    def task_events_push(self, events: List[Dict[str, Any]]) -> int:
        return self._call("task_events_push",
                          events=events)["count"]

    def task_events_get(self, job_id: str = "", name: str = "",
                        limit: int = 10_000) -> List[Dict[str, Any]]:
        return self._call("task_events_get", job_id=job_id, name=name,
                          limit=limit)["events"]

    def mark_node_dead(self, node_id: str, reason: str) -> None:
        self._call("mark_node_dead", node_id=node_id, reason=reason)

    def drain_node(self, node_id: str, deadline_s: float,
                   reason: str = "drain") -> Dict[str, Any]:
        """Ask the head to move a node into the DRAINING state (graceful
        departure); escalates to the death path after ``deadline_s``."""
        return self._call("drain_node", node_id=node_id,
                          deadline_s=deadline_s, reason=reason)

    def report_resources(self, loads: Dict[str, Dict[str, float]]) -> None:
        """Push per-node availability views (syncer gossip)."""
        self._call("report_resources", loads=loads, timeout=5.0)

    # tenancy (fair-share quota store + accounting federation)
    def tenancy_set(self, job_id: str, record: Dict[str, Any]) -> None:
        self._call("tenancy_set", job_id=job_id, record=record,
                   timeout=5.0)

    def tenancy_get(self) -> Dict[str, Dict[str, Any]]:
        return self._call("tenancy_get", timeout=5.0)["jobs"]

    def tenancy_report(self, jobs: Dict[str, Any]) -> None:
        self._call("tenancy_report", jobs=jobs, timeout=5.0)

    # kv
    def kv_put(self, key: bytes, value: bytes, overwrite: bool = True,
               namespace: bytes = b"") -> bool:
        return self._call("kv_put", key=key, value=value,
                          overwrite=overwrite, ns=namespace)["added"]

    def kv_get(self, key: bytes, namespace: bytes = b"") -> Optional[bytes]:
        return self._call("kv_get", key=key, ns=namespace)["value"]

    def kv_del(self, key: bytes, namespace: bytes = b"") -> None:
        self._call("kv_del", key=key, ns=namespace)

    def kv_keys(self, prefix: bytes = b"",
                namespace: bytes = b"") -> List[bytes]:
        return self._call("kv_keys", prefix=prefix, ns=namespace)["keys"]

    # pubsub
    def _sub_swap(self, old: Optional[AsyncClient],
                  new: Optional[AsyncClient]) -> None:
        """Track the live subscriber connection for close(). If close()
        already ran, the fresh client is closed on the spot (the dial
        won the race with stop)."""
        with self._sub_lock:
            if old is not None:
                try:
                    self._sub_clients.remove(old)
                except ValueError:
                    pass
            if new is not None:
                self._sub_clients.append(new)
                if self._sub_stop.is_set():
                    new.close()

    def subscribe(self, channel: str, callback) -> None:
        """Long-poll subscription: dedicated connection per channel (a
        parked poll must not block other requests' replies)."""
        def loop():
            cursor = 0
            try:
                sub = rpc.connect(self.addr, timeout=None)
            except OSError:
                return
            self._sub_swap(None, sub)
            try:
                while not self._sub_stop.is_set():
                    try:
                        out = sub.call("subscribe", channel=channel,
                                       cursor=cursor, timeout=None)
                    except rpc.RpcError:
                        if (self._sub_stop.is_set()
                                or self._reconnect_window <= 0):
                            return
                        # Head restart: re-dial and resume from our
                        # cursor (the persisted event log keeps it
                        # valid).
                        from ray_tpu._private.retry import RetryPolicy
                        try:
                            # stop-interruptible backoff: close() must
                            # not wait out a multi-second redial sleep
                            new = RetryPolicy.default(
                                deadline_s=self._reconnect_window).run(
                                lambda: rpc.connect(self.addr,
                                                    timeout=None),
                                loop="head.subscribe_redial",
                                retry_on=(OSError,),
                                abort=self._sub_stop.is_set,
                                sleep=self._sub_stop.wait)
                        except OSError:
                            return
                        self._sub_swap(sub, new)
                        sub = new
                        continue
                    cursor = out["cursor"]
                    for event in out["events"]:
                        try:
                            callback(event)
                        except Exception:
                            pass
            finally:
                self._sub_swap(sub, None)
                sub.close()

        t = threading.Thread(target=loop, daemon=True,
                             name=f"head-sub-{channel}")
        t.start()
        self._sub_threads.append(t)

    def publish(self, channel: str, event: Any) -> None:
        # rides _call: with reconnect_window > 0 a publish survives a
        # head restart like every other head RPC (a direct client.call
        # here bypassed the redial path and failed mid-restart)
        self._call("publish", channel=channel, event=event)

    def stop_head(self) -> None:
        try:
            self._client.call("head_stop", timeout=2.0)
        except rpc.RpcError:
            pass

    def close(self) -> None:
        self._sub_stop.set()
        # closing the per-channel sub clients unblocks their parked
        # long-polls, so the threads exit instead of leaking sockets
        with self._sub_lock:
            subs = list(self._sub_clients)
        for sub in subs:
            sub.close()
        cur = threading.current_thread()
        for t in self._sub_threads:
            if t is not cur:        # close() from a callback thread
                t.join(timeout=2.0)
        self._client.close()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--state-path", default="",
                        help="sqlite file for KV/pubsub persistence (FT)")
    parser.add_argument("--announce-fd", type=int, default=-1,
                        help="write the bound port here once listening")
    args = parser.parse_args()
    from ray_tpu._private import netchaos as _nc
    _nc.set_local_role("head")
    from ray_tpu._private import eventloop
    eventloop.set_proc_label("head")
    server = rpc.serve(HeadService(state_path=args.state_path or None),
                       host=args.host, port=args.port).start()
    try:    # continuous profiler (profiling_hz knob; default off)
        from ray_tpu.util import profiling as _profiling
        _profiling.maybe_start_from_config("head")
    except Exception:
        pass
    if args.announce_fd >= 0:
        import os

        os.write(args.announce_fd, f"{server.addr[1]}\n".encode())
        os.close(args.announce_fd)
    threading.Event().wait()  # serve forever


if __name__ == "__main__":
    main()
