"""Readers shared by several metric files (``benchmark/metrics``): each
takes the run's record and returns a number, or ``None`` when there is
nothing to read (the harness then leaves the metric out of the line)."""

from __future__ import annotations

from typing import Optional

from benchmark.lib.records import percentile, whole_interval_rate


def device_idle_share(rec) -> Optional[float]:
    """1 - union of device-op intervals over the traced window, %,
    averaged over the chips used."""
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def peak_hbm_gib(rec) -> Optional[float]:
    """``memory_stats()["peak_bytes_in_use"]`` on the fullest chip. What
    the backend counts is an open question (PERF.md): after PR 21's
    sharded step it equalled ``bytes_in_use``, i.e. no temporaries."""
    peak = rec.get("memory_peak_bytes")
    return peak / 2**30 if peak else None


def compiles_in_window(rec) -> Optional[float]:
    n = rec.get("compiles_in_window")
    return None if n is None else float(n)


def batch_occupancy(rec) -> Optional[float]:
    """Tokens the decode steps of the window produced over decode steps
    x slots, %. ``tokens_generated`` also counts the token each prefill
    samples, so first tokens seen in the window are taken off."""
    a, b = rec.get("engine_before"), rec.get("engine_after")
    if not a or not b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    toks = (b["tokens_generated"] - a["tokens_generated"]
            - rec.get("first_tokens_in_window", 0))
    return 100.0 * toks / (steps * rec["slots"])


def program_ms_per_call(rec, needle: str) -> Optional[float]:
    """Device milliseconds per execution of the programs whose name
    holds ``needle``, from the trace's ``XLA Modules`` line."""
    tr = rec.get("trace")
    if not tr:
        return None
    calls = sum(p["calls"] for n, p in tr["programs"].items() if needle in n)
    secs = sum(p["seconds"] for n, p in tr["programs"].items() if needle in n)
    return 1e3 * secs / calls if calls else None


def streamed_tokens_per_s(rec) -> Optional[float]:
    """Tokens the clients received per second: each client's rate over
    the WHOLE inter-token intervals it saw between the window's open and
    its close as it came, summed over the clients."""
    total = 0.0
    for stamps in rec.get("stamps", []):
        rate, _, _ = whole_interval_rate(stamps, rec["t_open"], rec["t_close"])
        total += rate or 0.0
    return total or None


def live_tokens_per_step(rec) -> Optional[float]:
    """Tokens whose K/V one decode step of the traced span read, summed
    over the slots (``d decode_kv_blocks_live x block_size / d
    decode_steps`` between the engine's two snapshots inside the span);
    ``None`` where the span has no two snapshots, no counter or no step."""
    edges = rec.get("engine_trace_edges") or []
    if len(edges) != 2 or "decode_kv_blocks_live" not in edges[0]:
        return None
    steps = edges[1]["decode_steps"] - edges[0]["decode_steps"]
    blocks = (edges[1]["decode_kv_blocks_live"]
              - edges[0]["decode_kv_blocks_live"])
    if steps <= 0:
        return None
    return blocks * rec["traffic"]["engine"]["block_size"] / steps


def decode_program_roofline(rec, needle: str) -> Optional[float]:
    """Least time one decode step could take (the configuration's
    ``costs.decode_step_bytes`` at the traced span's live K/V over the
    chip's memory bandwidth) over the time the program took, %."""
    ms = program_ms_per_call(rec, needle)
    live = live_tokens_per_step(rec)
    if ms is None or live is None or not rec.get("peaks"):
        return None
    least_s = rec["costs"].decode_step_bytes(
        rec["config"], live) / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)


def ok_requests(rec):
    return [r for r in rec.get("requests", []) if r.ok]


def ttft_percentile_ms(rec, q: float) -> Optional[float]:
    """Client TTFT from the DUE time over ALL window requests; one that
    failed or never started counts as an hour: it misses any limit."""
    reqs = rec.get("requests")
    if not reqs:
        return None
    vals = [r.ttft_s if (r.ok and r.ttft_s is not None) else 3600.0
            for r in reqs]
    return 1e3 * percentile(vals, q)


def ttft_overhead_ms(rec) -> Optional[float]:
    """What the Serve layers add to time to first token: the client's
    TTFT from SEND minus the engine's own ``first_token_at - submit``
    (the final chunk's ``ttft_s``), median over the window's requests."""
    vals = [(r.first_token_at - r.sent_at) - r.engine_ttft_s
            for r in ok_requests(rec)
            if r.engine_ttft_s is not None and r.first_token_at is not None]
    p = percentile(vals, 50)
    return None if p is None else 1e3 * p


def gen_lateness_ms(rec) -> Optional[float]:
    """How late the load generator sent against its schedule, 90th
    percentile: a starved generator must not read as a fast server."""
    vals = [r.lateness_s for r in rec.get("requests", [])
            if r.lateness_s is not None]
    p = percentile(vals, 90)
    return None if p is None else 1e3 * p


def tpot_percentile_ms(rec, q: float) -> Optional[float]:
    vals = [r.tpot_s for r in ok_requests(rec) if r.tpot_s is not None]
    p = percentile(vals, q)
    return None if p is None else 1e3 * p


def step_intervals(rec):
    done = [t for t in rec.get("step_done", [])
            if rec["t_open"] <= t <= rec["t_close"]]
    return [b - a for a, b in zip(done, done[1:])]


def median_step_s(rec) -> Optional[float]:
    return percentile(step_intervals(rec), 50)


def train_rate_tokens_per_s_chip(rec) -> Optional[float]:
    rate, _, _ = whole_interval_rate(rec.get("step_done", []),
                                     rec["t_open"], rec["t_close"])
    if rate is None:
        return None
    return rate * rec["tokens_per_step"] / rec["chips"]
