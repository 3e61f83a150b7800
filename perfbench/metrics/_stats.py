"""Order statistics the metric readers share."""

import math


def p95(values: list):
    """The 95th percentile by nearest rank; None for no values."""
    if not values:
        return None
    v = sorted(values)
    return v[math.ceil(0.95 * len(v)) - 1]


def median(values: list):
    if not values:
        return None
    v = sorted(values)
    n = len(v)
    return v[n // 2] if n % 2 else (v[n // 2 - 1] + v[n // 2]) / 2
