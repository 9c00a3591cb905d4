"""On/off arrivals: Poisson while on, nothing while off, `rate_per_s`
on average (the traffic file's `on_ms` / `off_ms`)."""

from __future__ import annotations

import numpy as np


def due_ns(traffic: dict, n: int, seed: int) -> np.ndarray:
    on, off = traffic["on_ms"] * 1e6, traffic["off_ms"] * 1e6
    rng = np.random.default_rng([seed, 0x0FF])
    gaps = rng.exponential(1e9 / traffic["rate_per_s"] * on / (on + off),
                           size=n)
    lit = np.cumsum(gaps)            # time spent on
    return (lit + np.floor(lit / on) * off).astype(np.int64)
