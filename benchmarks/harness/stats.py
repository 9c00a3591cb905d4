"""Tail and median arithmetic, in one place."""

from __future__ import annotations

import numpy as np


def quantile(values, q: float) -> float:
    """Nearest-rank quantile: the smallest sample with at least a share
    q of the samples at or below it.  No interpolation, so a tail is a
    value that was observed."""
    a = np.sort(np.asarray(values))
    if a.size == 0:
        raise ValueError("quantile of no samples")
    k = int(np.ceil(q * a.size)) - 1
    return float(a[min(max(k, 0), a.size - 1)])


def median(values) -> float:
    return quantile(values, 0.5)


def tail_ms(ns_samples, q: float = 0.95) -> dict:
    """A tail beside its median and sample count, in milliseconds."""
    a = np.asarray(ns_samples, dtype=np.float64) / 1e6
    if a.size == 0:
        return {"n": 0}
    return {"n": int(a.size), "p50_ms": quantile(a, 0.5),
            f"p{round(q * 100)}_ms": quantile(a, q), "max_ms": float(a.max())}
