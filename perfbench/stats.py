"""Percentile arithmetic for the benchmark's timings.

Nearest-rank percentiles (the arithmetic of bench.py's `pctl`, copied so
that no later PR to the program can change the yardstick). A percentile is
printed only when at least `MIN_BEYOND` samples lie beyond it
(choosing-metrics guide, section 1): a p95 of 100 samples has 5 beyond it
and is refused. The rule judges the window as designed: where the window's
length is given, the count held to it is what the window holds at the median
sample, because a stall of the host cuts the realised count by seconds and is
itself one sample.
"""

from __future__ import annotations

import math

MIN_BEYOND = 10


class TooFewSamples(ValueError):
    """The percentile asked for has fewer than MIN_BEYOND samples beyond it."""


def pctl(samples, p: float) -> float:
    """Nearest-rank percentile, p in (0, 1]; raises on an empty list."""
    if not samples:
        raise TooFewSamples("no samples")
    ordered = sorted(samples)
    k = max(math.ceil(p * len(ordered)), 1)
    return float(ordered[min(k, len(ordered)) - 1])


def samples_beyond(n: int, p: float) -> int:
    """How many of n samples lie strictly beyond the nearest-rank p."""
    return n - max(math.ceil(p * n), 1)


def tail(samples, p: float, window=None) -> float:
    """pctl, refused unless MIN_BEYOND samples lie beyond it: of the samples
    given or, with `window` (their sum as designed, in their unit), of the
    count that such a window holds at the median sample."""
    n = len(samples) if window is None else int(window // median(samples))
    beyond = samples_beyond(n, p)
    if beyond < MIN_BEYOND:
        raise TooFewSamples(
            f"p{round(p * 100)} of {n} samples has {beyond} beyond "
            f"it, fewer than {MIN_BEYOND}")
    return pctl(samples, p)


def median(samples) -> float:
    """Plain median (mean of the middle two for an even count)."""
    if not samples:
        raise TooFewSamples("no samples")
    ordered = sorted(samples)
    mid = len(ordered) // 2
    if len(ordered) % 2:
        return float(ordered[mid])
    return (ordered[mid - 1] + ordered[mid]) / 2.0
