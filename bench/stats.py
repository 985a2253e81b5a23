"""What a metric reader is given: the run's stamps, tick records, reduced
trace and the tables it needs, with the window arithmetic they share."""
from __future__ import annotations

import dataclasses

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (linear interpolation), None for no values."""
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


@dataclasses.dataclass
class Ctx:
    """One run, as the metric readers see it.  Times are host seconds
    (``time.perf_counter``); ``window`` is the measured window and
    ``trace_window`` the traced one (the same window in a ``--trace 1``
    run, None otherwise)."""

    records: list  # serve.Record, every request sent
    ticks: list  # serve.Tick, every engine step
    window: tuple[float, float]
    setup_s: float
    spec: object  # model.Spec
    work: object  # work/<family>.py
    peak: dict | None  # peaks.json entry of this device
    trace: dict | None = None  # trace.reduce_xspace output
    trace_window: tuple[float, float] | None = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def sent_in_window(self) -> list:
        t0, t1 = self.window
        return [r for r in self.records if t0 <= r.send_t < t1]

    def ttfts_s(self) -> list[float]:
        """First token minus send time of every request sent in the window
        that has its first token (the harness follows them past the close)."""
        return [r.first_t - r.send_t for r in self.sent_in_window() if r.first_t is not None]

    def tokens_in_window(self) -> int:
        t0, t1 = self.window
        return sum(1 for r in self.records for t in r.times if t0 <= t <= t1)

    def gaps_s(self) -> list[float]:
        """Every gap between consecutive output tokens of one request, both
        emitted in the window."""
        t0, t1 = self.window
        out = []
        for r in self.records:
            ts = [t for t in r.times if t0 <= t <= t1]
            out.extend(b - a for a, b in zip(ts, ts[1:]))
        return out

    def traced_ticks(self) -> list:
        """Engine steps that ran wholly inside the traced window."""
        if self.trace_window is None:
            return []
        lo, hi = self.trace_window
        return [t for t in self.ticks if t.t0 >= lo and t.t1 <= hi]

    def traced_sent(self) -> list:
        if self.trace_window is None:
            return []
        lo, hi = self.trace_window
        return [r for r in self.records if lo <= r.send_t < hi]
