"""Arithmetic of the readers that take the program's own stamps: the
verify batch's life (cumulative-ns counters of the verify stage, one per
phase), every hop's wait (`frag_wait_ns` / `frag_wait_n` of each stage's
intake) and the time inside the native crossings (`sweep_busy_ns` /
`sweep_crossings` of the natively swept stages).  All of them are
counters in `run["counters"]`, deltas over the measured window.  A
program without the counter (an older commit) gives None, and the
metric is left out."""

from __future__ import annotations

VERIFY = "verify0"


def _phase_ms_per_batch(phase: str):
    """Mean ms a batch of the window spent in `phase` of its life.  A
    window's two edges cut at most four batches (two in flight, one
    sealed, one open) of ~2,500."""
    counter = f"batch_{phase}_ns"

    def read(run):
        v = run["counters"].get(VERIFY, {})
        if counter not in v or not v.get("batches"):
            return None
        return v[counter] / v["batches"] / 1e6

    read.__name__ = f"{phase}_ms_per_batch"
    return read


open_ms_per_batch = _phase_ms_per_batch("open")
sealed_wait_ms_per_batch = _phase_ms_per_batch("sealed_wait")
h2d_ms_per_batch = _phase_ms_per_batch("h2d")
launch_ms_per_batch = _phase_ms_per_batch("launch")
inflight_ms_per_batch = _phase_ms_per_batch("inflight")
reap_ms_per_batch = _phase_ms_per_batch("reap")
publish_ms_per_batch = _phase_ms_per_batch("publish")


def wait_ms(run, stage: str, other_frags: int = 0):
    """Mean ms from `tsorig` (the due time in a paced cell) to the
    stage's intake, over the frags it consumed in the window.
    `other_frags` of them are not transactions (below)."""
    c = run["counters"].get(stage, {})
    if "frag_wait_ns" not in c:
        return None
    n = c.get("frag_wait_n", 0) - other_frags
    if n <= 0:
        return None
    return c["frag_wait_ns"] / n / 1e6


def to_verify_ms(run):
    """Due time -> the verify stage's intake: generator lateness plus
    the ring in front of verify."""
    return wait_ms(run, VERIFY)


def _in_verify_ms(run, after_ms):
    before = to_verify_ms(run)
    if before is None or after_ms is None:
        return None
    return after_ms - before


def in_verify_ms_tile(run):
    """The same mean at the sink's intake, less the one at verify's:
    what a frag spent in the verify stage and on the ring behind it."""
    return _in_verify_ms(run, wait_ms(run, "sink"))


def in_verify_ms_leader(run):
    """The same at pack's intake.  Pack also consumes the banks' done
    frames (one per microblock, stamped when published, consumed within
    a sweep): they are counted out by number (`microblock_done`); the
    sweep or less that each waited stays in the sum, which overstates
    the mean by under a sweep's time per microblock's worth of
    transactions."""
    done = run["counters"].get("pack", {}).get("microblock_done", 0)
    return _in_verify_ms(run, wait_ms(run, "pack", other_frags=done))


def _swept(run) -> list[dict]:
    """The host stages' counters, of those that sweep natively."""
    return [run["counters"][n] for n in run.get("host_stages") or []
            if "sweep_busy_ns" in run["counters"].get(n, {})]


def in_crossing_us_per_txn(run):
    """Time inside the non-empty native crossings of the host stages
    that have them (banks, shred), per transaction served."""
    swept = _swept(run)
    if not swept or not run.get("served"):
        return None
    return sum(c["sweep_busy_ns"] for c in swept) / run["served"] / 1e3


def empty_sweep_pct(run):
    """Share of those stages' sweeps that found nothing to drain."""
    swept = _swept(run)
    if not swept or not run.get("sweeps"):
        return None
    crossings = sum(c.get("sweep_crossings", 0) for c in swept)
    return 100.0 * (1.0 - crossings / (run["sweeps"] * len(swept)))
