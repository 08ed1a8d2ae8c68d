"""Percentile, window and block arithmetic. Plain Python on lists of floats, so
the numbers a PR is judged by do not depend on a library's default.

A throughput is judged as the MEDIAN over the blocks of its window, not as the
window's total over its length: a one-chip machine shares its host's cores, and
a host that stands still for a second takes 2% off a 50-second total while it
touches one block of twenty (PERF.md, Findings: the driver's first check). A
run's ``marks`` are ``[(t, count), ...]``, host time against work counted so
far (tokens), one mark at each block's end; a block is the span between two
marks."""

import math
from typing import Iterable, List, Optional, Sequence, Tuple

Marks = Sequence[Tuple[float, float]]


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) with linear interpolation between the
    two nearest order statistics; None for an empty sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Iterable[float]) -> Optional[float]:
    return percentile(values, 50.0)


def spread(values: Iterable[float]) -> Optional[float]:
    """The driver's measure of run-to-run noise: the distance between the
    quartiles over the median."""
    xs = list(values)
    mid = median(xs)
    if not xs or not mid:
        return None
    return (percentile(xs, 75.0) - percentile(xs, 25.0)) / abs(mid)


def in_window(t: Optional[float], t0: float, t1: float) -> bool:
    return t is not None and t0 <= t < t1


def mean(values: Iterable[float]) -> Optional[float]:
    xs: List[float] = [float(v) for v in values]
    return sum(xs) / len(xs) if xs else None


def block_rates(marks: Marks) -> List[float]:
    """Work per second in each block."""
    return [(c1 - c0) / (t1 - t0) for (t0, c0), (t1, c1) in zip(marks, marks[1:])]


def median_rate(marks: Marks) -> Optional[float]:
    """The median block's work per second; None without a block."""
    return median(block_rates(marks))


def slowest_block_pct(marks: Marks) -> Optional[float]:
    """How much longer, per unit of work, the slowest block took than the
    median one, in percent: what the median leaves out of the judged number."""
    rates = block_rates(marks)
    return 100.0 * (median(rates) / min(rates) - 1.0) if rates else None


class BlockMarks:
    """Takes a run's marks from a poll loop that sees a growing count arrive in
    bursts (a step of the engine delivers a token to each of its rows at once).
    A mark is taken when the count has stood still for one poll, so that no
    burst is cut in two, and has grown by ``every`` since the last mark; its
    time is that of the poll that saw the burst's end."""

    def __init__(self, every: int):
        self.every = int(every)
        self.marks: List[Tuple[float, int]] = []
        self._last: Optional[int] = None
        self._changed: Optional[float] = None

    def see(self, now: float, count: int) -> None:
        if self._last is None:
            self._last = count
        elif count != self._last:
            self._last, self._changed = count, now
        elif self._changed is not None and (
                not self.marks or count - self.marks[-1][1] >= self.every):
            self.marks.append((self._changed, count))
