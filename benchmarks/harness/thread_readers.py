"""Arithmetic of the readers over the thread's ledger, which the program
keeps itself: every stage's `run_once` charges each call whole to one of
three regimes (`loop_work_ns` / `loop_work_n`: it consumed or published
a frag, or a hook moved a device batch, ticked, closed a slot;
`loop_poll_ns` / `loop_poll_n`: it found nothing to do; `loop_hk_ns`:
housekeeping, taken out of the call it ran in), and the verify stage
stamps when the chip had nothing of its to run (`chip_empty_ns` /
`chip_empty_n`: from the loop's first sight of a finished batch with no
other in flight to the end of the next dispatch's launch) and whose
time that was (`chip_empty_call_ns`: its own blocking phases;
`chip_empty_away_ns`: the thread was in other stages; the rest: the
stage in its own loop, taking frags, polling).  All of them are counters
in `run["counters"]`, deltas over the measured window, in traced and
untraced runs alike.  A program without the counter (an older commit)
gives None, and the metric is left out."""

from __future__ import annotations

VERIFY, DEDUP = "verify0", "dedup"
_REGIMES = ("loop_work_ns", "loop_poll_ns", "loop_hk_ns")
# the four blocking calls of a batch's life that the stage stamps
_CALLS = ("batch_h2d_ns", "batch_launch_ns", "batch_reap_ns",
          "batch_publish_ns")


def _verify_batches(run):
    """-> (the verify stage's counters, batches dispatched) where the
    stage keeps the ledger and dispatched in the window, else None."""
    v = run["counters"].get(VERIFY, {})
    if "loop_work_ns" not in v or not v.get("batches"):
        return None
    return v, v["batches"]


def verify_work_ms_per_batch(run):
    """Time inside the verify stage's working calls per device batch
    it dispatched: `verify.stage_ms_per_batch` without the empty polls
    and the housekeeping."""
    got = _verify_batches(run)
    if got is None:
        return None
    v, batches = got
    return v["loop_work_ns"] / batches / 1e6


def verify_offcall_ms_per_batch(run):
    """The same less the four stamped blocking calls (h2d, launch,
    reap, publish): the part of a batch's cost to the thread that no
    phase stamps — intake, the pump's passes, the seal, the books."""
    got = _verify_batches(run)
    if got is None or any(k not in got[0] for k in _CALLS):
        return None
    v, batches = got
    return (v["loop_work_ns"] - sum(v[k] for k in _CALLS)) / batches / 1e6


def host_work_us_per_txn(run):
    """Time inside the working calls of the stages behind verify per
    transaction served: `host.us_per_txn` without the empty polls."""
    stages = [run["counters"].get(n, {}) for n in run.get("host_stages")
              or []]
    if not stages or not run.get("served") \
            or any("loop_work_ns" not in c for c in stages):
        return None
    return sum(c["loop_work_ns"] for c in stages) / run["served"] / 1e3


def dedup_work_us_per_txn(run):
    """Time inside the dedup stage's working calls per frag it
    consumed."""
    c = run["counters"].get(DEDUP, {})
    if "loop_work_ns" not in c or not c.get("frags_in"):
        return None
    return c["loop_work_ns"] / c["frags_in"] / 1e3


def stage_loop_ns(run) -> dict[str, int] | None:
    """Per stage: work + poll + housekeeping ns of the window, over
    the stages that keep the ledger; None where none does."""
    out = {n: sum(c[k] for k in _REGIMES)
           for n, c in run["counters"].items()
           if all(k in c for k in _REGIMES)}
    return out or None


def thread_accounted_pct(run):
    """What of the one thread's window the stages' ledgers cover; the
    rest is the harness's loop, its taps, and the stamps themselves."""
    per_stage = stage_loop_ns(run)
    if per_stage is None or not run.get("window_s"):
        return None
    return 100.0 * sum(per_stage.values()) / (run["window_s"] * 1e9)


def _chip_empty(run):
    v = run["counters"].get(VERIFY, {})
    return v if "chip_empty_ns" in v else None


def chip_empty_pct(run):
    """Share of the measured window in which the chip had nothing of
    the verify stage's to run, as the stage saw it (how late the ready
    flag turns is not in it): `device.idle_pct` over 20 s in place of
    0.12 s of trace."""
    v = _chip_empty(run)
    if v is None or not run.get("window_s"):
        return None
    return 100.0 * v["chip_empty_ns"] / (run["window_s"] * 1e9)


def _share_of_empty(counter: str):
    def read(run):
        v = _chip_empty(run)
        if v is None or counter not in v:
            return None
        # a window in which the chip never ran dry: none of no time
        return 100.0 * v[counter] / v["chip_empty_ns"] \
            if v["chip_empty_ns"] else 0.0

    return read


# of the time the chip was empty: the thread was in other stages
chip_empty_away_pct = _share_of_empty("chip_empty_away_ns")
# ... and inside the verify stage's own blocking phases
chip_empty_call_pct = _share_of_empty("chip_empty_call_ns")
