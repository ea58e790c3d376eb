"""Profiler trace (``.xplane.pb``) -> the numbers the per-layer metrics
read. One reduction, kept with the benchmark, checked against a small
recorded trace (``benchmark/tests/data``), so every PR computes device
busy/idle, program time, top operations and idle gaps the same way.

What a TPU trace holds (jax 0.9, ``jax.profiler.ProfileData``): one plane
per chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per
executed program, named ``jit_<fn>(<fingerprint>)``) and a line
``XLA Ops`` (one event per executed HLO operation; loops repeat theirs,
and a ``while`` holds its body's events inside its own) and a line
``Async XLA Ops`` (copies and collectives in flight beside them);
and one host plane, ``/host:CPU``, with a line per thread that carries
``TraceAnnotation`` spans and the runtime's own TraceMe events. All
events carry ``start_ns`` and ``duration_ns`` on one clock.

The reduction works on plain tuples so it can be tested without a
profiler: ``planes = {plane_name: {line_name: [(name, start_ns,
dur_ns), ...]}}``.
"""

from __future__ import annotations

import glob
import os
import re
import time
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
# host spans that only WAIT for the system (a client blocked on its
# stream) explain nothing about a device gap
WAIT_PREFIX = "bench.wait."
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv)")


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_planes(trace_dir: str) -> Dict[str, Dict[str, list]]:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(trace_dir))
    planes: Dict[str, Dict[str, list]] = {}
    for plane in data.planes:
        keep_all = bool(DEVICE_PLANE.match(plane.name))
        if not (keep_all or plane.name.startswith(HOST_PLANE_PREFIX)):
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if keep_all and line.name not in (OPS_LINE, ASYNC_LINE,
                                              MODULES_LINE):
                continue
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
    return planes


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _total(intervals: List[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def _clip(events: list, lo: int, hi: int) -> list:
    """Events cut to ``[lo, hi]``; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def _subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]):
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by
    the (disjoint, sorted) intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _self_times(ops: list) -> List[Tuple[str, int, int, int]]:
    """``(name, start, duration, self time)`` of each operation, the
    self time being its own less that of the operations nested inside
    it: the ``XLA Ops`` line holds a ``while`` (a scan over layers) AND
    everything its body runs, so plain sums would count the loop twice."""
    out: List[list] = []
    stack: List[Tuple[int, int]] = []          # (end, index into out)
    for name, s, d in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and s + d <= stack[-1][0]:        # wholly inside: a child
            out[stack[-1][1]][3] -= d
        out.append([name, s, d, d])
        stack.append((s + d, len(out) - 1))
    return [(n, s, d, max(0, own)) for n, s, d, own in out]


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...]`` / ``fusion.12`` -> ``fusion.12``; what
    the trace prints, made safe for one JSON string."""
    name = name.strip().lstrip("%")
    name = name.split(" = ")[0]
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:120]


def program_name(module_event: str) -> str:
    """``jit_decode_step_paged(1234567)`` -> ``decode_step_paged``."""
    name = module_event.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def reduce_planes(planes: Dict[str, Dict[str, list]],
                  window_ns: Optional[Tuple[int, int]] = None,
                  top: int = 10) -> Optional[Dict]:
    """The whole reduction. ``window_ns`` cuts every plane to one
    interval of the trace's clock; by default the window runs from the
    first to the last device event. Returns ``None`` when the trace
    holds no device operation (a CPU trace)."""
    devices = {n: l for n, l in planes.items() if DEVICE_PLANE.match(n)}
    ops_all = [ev for l in devices.values() for ev in l.get(OPS_LINE, [])]
    if not ops_all:
        return None
    if window_ns is None:
        window_ns = (min(s for _, s, _ in ops_all),
                     max(s + d for _, s, d in ops_all))
    lo, hi = window_ns
    window_s = (hi - lo) / 1e9

    busy_each, op_time, programs = [], {}, {}
    coll_total, coll_exposed = [], []
    gaps_first: List[Tuple[int, int]] = []
    for n in sorted(devices):
        ops = _clip(devices[n].get(OPS_LINE, []), lo, hi)
        busy = _union([(s, s + d) for _, s, d in ops])
        busy_each.append(_total(busy) / 1e9)
        if not gaps_first:
            gaps_first = _subtract([(lo, hi)], busy)
        timed = _self_times(ops)
        for name, _, _, own in timed:
            key = short_name(name)
            op_time[key] = op_time.get(key, 0.0) + own / 1e9
        # a collective is in flight from its start on the async line (or,
        # where it runs synchronously, for its own event); compute is
        # every LEAF operation that is not a collective — a loop's own
        # event covers its whole body and says nothing
        in_flight = _clip(devices[n].get(ASYNC_LINE, []), lo, hi) + ops
        coll = _union([(s, s + d) for name, s, d in in_flight
                       if COLLECTIVE.match(short_name(name))])
        compute = _union([(s, s + d) for name, s, d, own in timed
                          if own == d
                          and not COLLECTIVE.match(short_name(name))])
        coll_total.append(_total(coll) / 1e9)
        coll_exposed.append(_total(_subtract(coll, compute)) / 1e9)
        for name, s, d in _clip(devices[n].get(MODULES_LINE, []), lo, hi):
            # only whole executions count toward a program's time per call
            p = programs.setdefault(program_name(name),
                                    {"calls": 0, "seconds": 0.0})
            p["calls"] += 1
            p["seconds"] += d / 1e9
    n_dev = len(devices)
    for p in programs.values():           # average over the chips
        p["calls"] = p["calls"] / n_dev
        p["seconds"] = p["seconds"] / n_dev
    # one op name runs on every chip of a sharded program: average
    device_ops = sorted(((k, v / n_dev) for k, v in op_time.items()),
                        key=lambda kv: -kv[1])[:top]

    return {
        "window_s": window_s,
        "busy_s": sum(busy_each) / n_dev,
        "busy_s_each": busy_each,
        "n_devices": n_dev,
        "device_ops": [[k, v] for k, v in device_ops],
        "programs": programs,
        "collective_s": sum(coll_total) / n_dev,
        "collective_exposed_s": sum(coll_exposed) / n_dev,
        "idle_gaps": attribute_gaps(gaps_first, planes, top),
    }


def attribute_gaps(gaps: List[Tuple[int, int]], planes: Dict, top: int):
    """Give every idle gap of the first chip to the host span that
    covers most of it — the innermost one where several do — leaving out
    spans that only wait for the system. What no span covers is
    ``unattributed``: today the engine loop has no spans of its own."""
    host = []
    for pname, lines in planes.items():
        if not pname.startswith(HOST_PLANE_PREFIX):
            continue
        for events in lines.values():
            host.extend((s, s + d, name) for name, s, d in events
                        if d > 0 and not name.startswith(WAIT_PREFIX))
    host.sort()
    starts = [h[0] for h in host]
    import bisect
    longest = max((e - s for s, e, _ in host), default=0)
    totals: Dict[str, float] = {}
    for gs, ge in gaps:
        best, best_key = "unattributed", (0, 0)
        i = bisect.bisect_left(starts, gs - longest)
        while i < len(host) and host[i][0] < ge:
            s, e, name = host[i]
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                key = (overlap, -(e - s))       # most cover, then innermost
                if key > best_key:
                    best, best_key = name, key
            i += 1
        covered = best_key[0] if best != "unattributed" else 0
        if covered:
            totals[short_name(best)] = totals.get(short_name(best), 0.0) \
                + covered / 1e9
        if (ge - gs) - covered > 0:
            totals["unattributed"] = totals.get("unattributed", 0.0) \
                + ((ge - gs) - covered) / 1e9
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:top]]


class Tracer:
    """Start/stop the JAX profiler around part of the measured window and
    reduce what it wrote. Directory fixed inside the checkout, emptied
    first so a run reads only its own trace."""

    def __init__(self, directory: str):
        self.directory = directory
        self.reduced: Optional[Dict] = None
        self._on = False

    def start(self) -> None:
        import shutil

        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        # no Python tracer: it hooks every call of every thread, and the
        # engine's host work between steps read a fifth slower under it
        # (PR 23, batch_decode: 133 ms a step traced against 115 ms).
        # Host spans come from TraceAnnotation / the runtime's TraceMe.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.t_start = time.perf_counter()
        self._on = True

    def stop(self) -> None:
        import jax
        if not self._on:
            return
        self._on = False
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.reduced = reduce_planes(load_planes(self.directory))
        if self.reduced is not None:
            # the traced interval on the host's clock, for readers that
            # match requests to it
            self.reduced["t_start"] = self.t_start
            self.reduced["t_stop"] = self.t_stop


def describe(trace_dir: str, samples: int = 6) -> None:
    """Print what a trace holds — look at one by hand before trusting
    the reduction: ``python3 -m benchmark.lib.trace <dir>``."""
    from jax.profiler import ProfileData

    path = newest_xplane(trace_dir)
    data = ProfileData.from_file(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            seen = {}
            for ev in events:
                seen.setdefault(ev.name, [0, 0.0])
                seen[ev.name][0] += 1
                seen[ev.name][1] += ev.duration_ns / 1e6
            for name, (n, ms) in sorted(seen.items(),
                                        key=lambda kv: -kv[1][1])[:samples]:
                print(f"      {n:6d} x {ms:10.3f} ms  {name[:110]}")


if __name__ == "__main__":
    import sys
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6)
