"""Latency arithmetic for the benchmark's end-to-end metrics.

``percentile`` is the serving stack's nearest-rank percentile
(``repro.serve.metrics.percentile``), copied so that a change to the
program cannot move the yardstick, with one difference: an empty sample
raises instead of reading as 0.0, which would look like a perfect tail.
"""

from __future__ import annotations


class EmptySampleError(ValueError):
    """A latency metric was asked of a sample with no values."""


def percentile(values, pct: float) -> float:
    """Nearest-rank percentile: no interpolation, exact on small samples."""
    xs = sorted(values)
    if not xs:
        raise EmptySampleError(f"p{pct:g} of an empty sample")
    k = max(0, min(len(xs) - 1, int(round(pct / 100.0 * len(xs) + 0.5)) - 1))
    return float(xs[k])
