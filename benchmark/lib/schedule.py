"""Arrival schedules and length samples for serving traffic.

The arithmetic of ``ray_tpu/loadgen/arrival.py`` (seeded Poisson or
constant arrivals, log-normal / uniform / constant lengths), copied so
the yardstick cannot move, with one change that the benchmark's contract
asks for: EVERY SEED OFFERS THE SAME WORK. A run holds only ~150
requests, so lengths and gaps drawn afresh per seed would make the seed
change the work by several percent and drown any regression. Instead

- the multiset of gaps is the ``n`` stratified quantiles of the arrival
  process's inter-arrival distribution (exponential for Poisson), scaled
  so they sum to ``n / rate`` exactly: the same ``n`` requests in every
  run, at the cell's fixed rate;
- the multiset of lengths is the ``n`` stratified quantiles of the
  length distribution, clipped to its bounds;
- each multiset is put into ONE canonical order by a shuffle keyed by
  the traffic's name (so gaps, prompt lengths and output lengths are
  independent of each other), and that order is a cycle;
- the seed chooses where in the cycle the window starts (a rotation):
  "the same set of sizes and arrivals, in another order". Token values,
  and the model's weights, come from the seed as well.

Warm-up traffic is the part of the same cycle that precedes the start,
so the window opens on a system already at its steady occupancy.

What this gives up: the quantiles hold each gap and length once, in one
order, so NO seed ever offers a burst, a lull or a run of long prompts
that this one cycle does not contain. The cell measures one fixed
sample of its distribution steadily, not the distribution's own
variance; burstier traffic is a traffic file of its own.
"""

from __future__ import annotations

import math
import random
import statistics
from typing import Dict, List


def _quantile_points(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def length_quantiles(spec: Dict, n: int) -> List[int]:
    """``n`` stratified samples of a length distribution.

    ``spec``: ``{"dist": "constant", "value": v}`` |
    ``{"dist": "uniform", "min": a, "max": b}`` |
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    (all in tokens; min/max clip).
    """
    dist = spec["dist"]
    if dist == "constant":
        return [int(spec["value"])] * n
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for u in _quantile_points(n):
        if dist == "uniform":
            v = lo + u * (hi - lo)
        elif dist == "lognormal":
            z = statistics.NormalDist().inv_cdf(u)
            v = math.exp(math.log(spec["median"]) + spec["sigma"] * z)
        else:
            raise ValueError(f"unknown length dist {dist!r}")
        out.append(max(lo, min(hi, int(round(v)))))
    return out


def gap_quantiles(process: str, rate: float, n: int) -> List[float]:
    """``n`` inter-arrival gaps whose sum is exactly ``n / rate``."""
    if rate <= 0:
        raise ValueError(f"rate must be > 0, got {rate}")
    if process == "constant":
        return [1.0 / rate] * n
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    gaps = [-math.log(1.0 - u) / rate for u in _quantile_points(n)]
    scale = (n / rate) / sum(gaps)
    return [g * scale for g in gaps]


def canonical(values: List, key: str) -> List:
    """The one canonical order of a multiset: a shuffle keyed by name,
    never by the run's seed."""
    out = list(values)
    random.Random(key).shuffle(out)
    return out


def cyclic_schedule(traffic: Dict, name: str, seed: int, seconds: float,
                    warmup_s: float) -> List[Dict]:
    """The requests of one run: ``{"due": seconds from window open
    (negative in warm-up), "prompt_len", "max_tokens", "in_window"}``,
    ascending by due time.

    The cycle has ``n = round(rate * seconds)`` requests and lasts
    ``n / rate`` — the window's length to within half a request; the
    window plays it once from the seed's offset (a request due a moment
    after the window closes still belongs to it and is read to its end),
    and warm-up plays the ``warmup_s`` that come before it.
    """
    arr = traffic["arrival"]
    n = max(1, int(round(arr["rate_per_s"] * seconds)))
    gaps = canonical(gap_quantiles(arr["process"], arr["rate_per_s"], n),
                     f"{name}:gaps")
    plens = canonical(length_quantiles(traffic["prompt_len"], n),
                      f"{name}:prompt_len")
    olens = canonical(length_quantiles(traffic["output_len"], n),
                      f"{name}:output_len")
    start = random.Random(f"{seed}:offset").randrange(n)

    def item(i: int, due: float, in_window: bool) -> Dict:
        j = i % n
        return {"cycle_index": j, "due": due, "prompt_len": plens[j],
                "max_tokens": olens[j], "in_window": in_window}

    out: List[Dict] = []
    # the gap BEFORE request j is gaps[j]; the first window request is
    # due half its gap after the window opens so no request is due at 0
    t = gaps[start % n] / 2.0
    for k in range(n):
        out.append(item(start + k, t, True))
        t += gaps[(start + k + 1) % n]
    t = gaps[start % n] / 2.0
    k = 1
    while True:
        t -= gaps[(start - k + 1) % n]
        if t < -warmup_s or k > n:
            break
        out.append(item(start - k, t, False))
        k += 1
    out.sort(key=lambda r: r["due"])
    return out
