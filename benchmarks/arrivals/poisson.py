"""Poisson arrivals: exponential gaps at the traffic file's
`rate_per_s`, from the seed."""

from __future__ import annotations

import numpy as np


def due_ns(traffic: dict, n: int, seed: int) -> np.ndarray:
    """Offsets in ns, from the start of traffic, at which offer k of an
    open loop is due."""
    rng = np.random.default_rng([seed, 0xA881])
    gaps = rng.exponential(1e9 / traffic["rate_per_s"], size=n)
    return np.cumsum(gaps).astype(np.int64)
