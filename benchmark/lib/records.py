"""Request records and percentile arithmetic.

Copied from ``ray_tpu/loadgen/recorder.py`` (sound; only ever run on a
CPU) so that no later PR can move the yardstick. Timestamps are seconds
on one ``time.perf_counter`` clock. Percentiles use the nearest-rank
method — exact, no interpolation, checkable by hand:
``p(q) = sorted[ceil(q/100 * n) - 1]``.

Differences from the original, both deliberate: TTFT is timed from the
moment the request was DUE (open loop: a stalled generator or a queue
charges the wait to the request), and the engine's own TTFT is kept
beside it so the Serve layers' share can be read.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional


@dataclasses.dataclass
class RequestRecord:
    index: int
    due_at: float                      # when the schedule wanted it sent
    prompt_tokens: int = 0
    max_tokens: int = 0
    sent_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None     # the last token's arrival
    done_at: Optional[float] = None         # the finish chunk's arrival
    output_tokens: int = 0
    engine_ttft_s: Optional[float] = None   # engine: first token - submit
    finish_reason: Optional[str] = None
    error: Optional[str] = None
    in_window: bool = False            # due inside the measured window

    @property
    def ok(self) -> bool:
        return self.error is None and self.finished_at is not None

    @property
    def ttft_s(self) -> Optional[float]:
        """Client time to first token, from the DUE time."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.due_at

    @property
    def lateness_s(self) -> Optional[float]:
        """How late the generator sent it against its schedule."""
        if self.sent_at is None:
            return None
        return max(0.0, self.sent_at - self.due_at)

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean gap between this request's output tokens, at the client
        (needs >= 2 tokens)."""
        if (self.finished_at is None or self.first_token_at is None
                or self.output_tokens < 2):
            return None
        return ((self.finished_at - self.first_token_at)
                / (self.output_tokens - 1))


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile; ``None`` for an empty list (a metric
    with nothing to read is left out, never reported as 0)."""
    if not values:
        return None
    vals = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return float(vals[min(rank, len(vals)) - 1])


def whole_interval_rate(stamps: List[float], lo: float, hi: float):
    """Events per second over WHOLE intervals inside ``[lo, hi]``.

    ``stamps`` are the ascending times of one stream of events (one
    client's tokens, one trainer's finished steps). The rate is taken
    from the first to the last event that lies inside the window:
    ``(n - 1) / (t_last - t_first)``. Dividing the count by the nominal
    window length instead would add up to one event of quantisation at
    each edge — at 10 steps/s over 48 s that alone is 0.4 %.

    Returns ``(rate, n_intervals, span_s)``; rate is ``None`` with fewer
    than two events inside.
    """
    inside = [t for t in stamps if lo <= t <= hi]
    if len(inside) < 2 or inside[-1] <= inside[0]:
        return None, 0, 0.0
    span = inside[-1] - inside[0]
    return (len(inside) - 1) / span, len(inside) - 1, span
