"""Shared arithmetic of the readers: percentiles as the benchmark defines
them (nearest rank on the sorted sample, no interpolation)."""

from __future__ import annotations

import math


def percentile(values, p):
    ordered = sorted(values)
    if not ordered:
        return None
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def stat(values, name):
    """name: "mean", "max" or "p<number>"."""
    values = list(values)
    if not values:
        return None
    if name == "mean":
        return sum(values) / len(values)
    if name == "max":
        return max(values)
    if name.startswith("p"):
        return percentile(values, float(name[1:]))
    raise ValueError(f"unknown statistic {name!r}")
