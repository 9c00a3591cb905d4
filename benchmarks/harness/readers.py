"""Arithmetic the per-layer readers share.  Each reader file under
layer_metrics/ is one metric; two cells' variants of one quantity
(`.tile`, `.leader`) are two files over one function here.  A reader
that finds nothing to read returns None and the metric is left out."""

from __future__ import annotations

from .stats import quantile


def fill_pct(run):
    """Share of the fixed-shape batch's lanes that carried a signature."""
    v = run["counters"]["verify0"]
    if not v.get("batches"):
        return None
    return 100.0 * v["batch_elems"] / (v["batches"] * run["batch"])


def kernel_ms_per_batch(run):
    """Device time of the sigverify program per execution, from the
    trace's module events that lie wholly inside the traced window."""
    tr = run["trace"]
    if not tr or not tr["program_runs"]:
        return None
    return 1e3 * tr["program_s"] / tr["program_runs"]


def idle_pct(run):
    tr = run["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def host_us_per_txn(run):
    """Host time in the stages behind verify (harness timers around
    their run_once, measured window) per transaction served."""
    if not run["timers_s"] or not run["served"]:
        return None
    t = sum(run["timers_s"][n] for n in run["host_stages"])
    return 1e6 * t / run["served"]


def late_ms_p95(run):
    if len(run["late_ns"]) == 0:
        return None
    return quantile(run["late_ns"], 0.95) / 1e6


def lat_ms_p95(run):
    """The 95th percentile, in ms, of the window's latency samples: each
    counted from the transaction's `tsorig` (its due time in a paced
    cell) to when the harness saw it leave the served path."""
    if len(run["lat_ns"]) == 0:
        return None
    return quantile(run["lat_ns"], 0.95) / 1e6


def verify_stage_ms_per_batch(run):
    """Host time inside the verify stage's run_once (harness timer,
    measured window) per device batch it dispatched: intake, seal,
    host->device copies, dispatch, reap, publish."""
    v = run["counters"]["verify0"]
    if not run["timers_s"] or not v.get("batches"):
        return None
    return 1e3 * run["timers_s"]["verify0"] / v["batches"]


def served_per_s(run):
    """Work that left the served path in the window, per second of it."""
    return run["served"] / run["window_s"]


def setup_s(run):
    """Process entry to the start of the warm-up window."""
    return run["setup_s"]
