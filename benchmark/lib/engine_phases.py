"""Readers of the engine's phase counters (``ray_tpu/llm/engine.py``,
``stats``; docs/serving.md): the seconds each phase of the loop took and
the counts taken at the same boundaries, as deltas between the engine's
``stats`` at both edges of the window (``rec["engine_before"]`` /
``rec["engine_after"]``). A program that has no such counter, or a
window in which no decode step ran, reads ``None``: the harness then
leaves the metric out."""

from __future__ import annotations

from typing import Optional

# the loop's own Python between two decode programs; the other phases
# (``t_readback_s``, ``t_prefill_s``, ``t_idle_s``) wait for the device
HOST_PHASES = ("t_schedule_s", "t_host_arrays_s", "t_enqueue_s", "t_emit_s")


def delta(rec, key: str) -> Optional[float]:
    a, b = rec.get("engine_before"), rec.get("engine_after")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]


def total(rec, keys) -> Optional[float]:
    parts = [delta(rec, k) for k in keys]
    return None if None in parts else sum(parts)


def ms_per_step(rec, *keys: str) -> Optional[float]:
    """Seconds of the phases ``keys`` per decode step of the window, ms."""
    steps, secs = delta(rec, "decode_steps"), total(rec, keys)
    if not steps or steps <= 0 or secs is None:
        return None
    return 1e3 * secs / steps


def host_cpu_share(rec) -> Optional[float]:
    """The engine thread's CPU time in the host phases over their wall
    time, %: near 100 the loop's own Python fills the gap between decode
    programs; well under, the thread stood descheduled or without the
    GIL while the device idled."""
    cpu, wall = delta(rec, "cpu_host_s"), total(rec, HOST_PHASES)
    if cpu is None or not wall or wall <= 0 or not delta(rec, "decode_steps"):
        return None
    return 100.0 * cpu / wall


def prefill_padding_share(rec) -> Optional[float]:
    """Share of the token positions prefill computed that held no token
    (bucket and group padding), %."""
    real = delta(rec, "prefill_tokens")
    padded = delta(rec, "prefill_padded_tokens")
    if (real is None or not padded or padded <= 0
            or not delta(rec, "decode_steps")):
        return None
    return 100.0 * (1.0 - real / padded)
