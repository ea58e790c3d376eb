"""Readers of the Serve stream path's own account (``LLMServer.stats()``;
docs/serving.md, "The stream path: where a token waits"): counts at the
path's four boundaries, each end's CPU seconds as its thread published
them, and the wall seconds of the sampled items, as deltas between the
``stats`` snapshots at both edges of the window (``rec["engine_before"]``
/ ``rec["engine_after"]``), beside ``engine_phases``, whose ``delta``
they share. A program without such a counter, or a window in which no
item moved, reads ``None``: the harness then leaves the metric out.

The boundaries, in a token's order: ``stream_puts`` (the engine puts it
on the request's queue), ``stream_takes`` (the request's replica thread
takes it off), ``stream_items_reported`` (the runtime has stored the
chunk and reported it to the handle), ``stream_items_consumed`` (the
client's ``next`` was handed its ref). What lies between the first two
waits for the replica threads, between the last two for the clients."""

from __future__ import annotations

from typing import Optional

from benchmark.lib.engine_phases import delta, total

# the Python threads of a serve cell that the program accounts for: the
# engine's, the replicas' (producers), the clients' (consumers)
THREAD_CPU = ("engine_thread_cpu_s", "stream_producer_cpu_s",
              "stream_consumer_cpu_s")


def _window_s(rec) -> Optional[float]:
    t_open, t_close = rec.get("t_open"), rec.get("t_close")
    if t_open is None or t_close is None or t_close <= t_open:
        return None
    return t_close - t_open


def consumed_share(rec) -> Optional[float]:
    """Items handed to the clients over items the engine put on its
    streams, %: under 100 the stream path, not the engine, sets the
    clients' rate, and the difference is a backlog that grows."""
    consumed, puts = delta(rec, "stream_items_consumed"), delta(rec, "stream_puts")
    if consumed is None or not puts or puts <= 0:
        return None
    return 100.0 * consumed / puts


def backlog_per_s(rec, ahead: str, behind: str) -> Optional[float]:
    """How fast the count ``ahead`` outran ``behind`` over the window,
    items/s: the growth of what waits between the two boundaries."""
    a, b, window = delta(rec, ahead), delta(rec, behind), _window_s(rec)
    if a is None or b is None or window is None or not delta(rec, "stream_puts"):
        return None
    return (a - b) / window


def us_per_item(rec, seconds: str, items: str) -> Optional[float]:
    """Seconds of ``seconds`` per item of ``items`` over the window, us."""
    secs, n = delta(rec, seconds), delta(rec, items)
    if secs is None or not n or n <= 0:
        return None
    return 1e6 * secs / n


def python_cpu_share(rec) -> Optional[float]:
    """CPU seconds of the engine's, the replicas' and the clients'
    threads over the window's wall seconds, %: well under 100 the
    threads waited (for the device, for tokens); near or over 100 the
    interpreter lock is contended (a thread's CPU clock also runs in
    native code that let the lock go and in the kernel)."""
    cpu, window = total(rec, THREAD_CPU), _window_s(rec)
    if cpu is None or window is None or not delta(rec, "stream_puts"):
        return None
    return 100.0 * cpu / window
