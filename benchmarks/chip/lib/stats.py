"""The arithmetic of the end-to-end numbers and of a set's spread."""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest ranks, as numpy's default does."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    low = int(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def rate(count: int, seconds: float) -> float:
    """All the work over all the time of the window."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return count / seconds


def iqr(values) -> float:
    """Distance between the first and third quartile as
    statistics.quantiles(values, n=4) gives them."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return q3 - q1


def spread(values) -> float:
    """The quartile distance as a share of the median."""
    return iqr(values) / abs(statistics.median(values))


def trimmed_spread(values) -> float:
    """The driver's rule for tightness: a set's spread with the run farthest
    from its median left out, where that narrows it."""
    median = statistics.median(values)
    farthest = max(range(len(values)), key=lambda i: abs(values[i] - median))
    rest = [v for i, v in enumerate(values) if i != farthest]
    if len(rest) < 2:
        return spread(values)
    return min(spread(values), iqr(rest) / abs(median))


def verdict(sets: list[list[float]], bound: float) -> dict:
    """What the driver's check would say of `bound` for these sets of runs of
    one metric in one cell: too tight where the mean of the sets' trimmed
    spreads is over half of it; too loose where it is over eight times the
    widest spread of all the runs (a bound of 1% is never too loose)."""
    trimmed = [trimmed_spread(s) for s in sets]
    mean_trimmed = sum(trimmed) / len(trimmed)
    widest = max([spread(s) for s in sets] + [spread([v for s in sets for v in s])])
    return {
        "trimmed": trimmed,
        "mean_trimmed": mean_trimmed,
        "widest": widest,
        "too_tight": mean_trimmed > bound / 2,
        "too_loose": bound > 0.01 and bound > 8 * widest,
        "medians": [statistics.median(s) for s in sets],
    }
