"""Batch-geometry autotuner for the verify stage (ISSUE 13).

The metrics plane already records, per verify stage, the batch-fill
histogram (elements per closed device batch), the msg-length histogram,
and the generic/cached element counters.  This module turns those
observations into a (batch, max_msg_len, comb split) recommendation —
the wiredancer path sizes its FPGA burst the same way, except here the
"burst" is a compiled XLA shape, so retuning costs a recompile and the
choice must be made from evidence, not per batch.

Pure and deterministic by contract: the same histogram state always
yields the same recommendation (tested), so a tuned stage is exactly as
reproducible as an untuned one and a recommendation computed offline
from a scraped snapshot matches what the live stage would pick.

The stage applies a recommendation only at a quiet point (no open
accumulator, no in-flight batches) and only when the autotune knob is
on.
"""

from __future__ import annotations

from dataclasses import dataclass

from firedancer_tpu.utils import metrics as fm

# the discrete ladders a recommendation picks from: compiled shapes are
# expensive (one XLA compile each), so the tuner quantizes to a small
# menu rather than chasing the histogram exactly
BATCH_LADDER = (64, 128, 256, 512, 1024, 2048, 4096)
MSG_LEN_LADDER = (128, 256, 512, 1232)

# hysteresis: a recommendation must beat the current geometry by this
# factor of headroom before it is worth a recompile
FILL_TARGET_Q = 0.95  # size the batch so the p95 fill fits
MSG_LEN_Q = 0.99  # and the msg rows so the p99 length fits
COMB_SPLIT_MIN = 0.25  # cached lane earns its own batch above this share


@dataclass(frozen=True)
class Geometry:
    """One verify-stage shape choice (what a compile is keyed on)."""

    batch: int
    max_msg_len: int
    comb_split: bool  # keep a separate cached-signer batch lane

    def as_dict(self) -> dict:
        return {
            "batch": self.batch,
            "max_msg_len": self.max_msg_len,
            "comb_split": self.comb_split,
        }


def _ladder_at_least(ladder: tuple, v: float) -> int:
    """Smallest ladder rung >= v (the top rung when v overflows)."""
    for rung in ladder:
        if rung >= v:
            return rung
    return ladder[-1]


def recommend(
    fill_hist: dict,
    msg_len_hist: dict | None = None,
    *,
    batch_elems: int = 0,
    comb_elems: int = 0,
    current: Geometry | None = None,
) -> Geometry:
    """The deterministic recommendation from one metrics snapshot.

    fill_hist / msg_len_hist: histogram dicts as Metrics.hist() returns
    them ({"buckets", "counts", "sum", "count"}).  batch_elems /
    comb_elems: the stage's element counters (comb share decides the
    cached-lane split).  `current` supplies fallbacks for axes with no
    evidence yet (empty histograms keep the current choice).
    """
    cur = current or Geometry(256, 1232, True)

    # batch: size the fixed shape so the p95 observed fill fits — a
    # batch that always closes full wants headroom (the deadline never
    # fires), a batch that closes at 5% fill is paying pad-lane compute
    # for nothing.  hist_quantile interpolates within the bucket, which
    # is fine: the ladder quantizes the answer anyway.
    if fill_hist and fill_hist.get("count"):
        q = fm.hist_quantile(fill_hist, FILL_TARGET_Q)
        if q == float("inf"):  # fills above the top edge: take the top rung
            batch = BATCH_LADDER[-1]
        else:
            batch = _ladder_at_least(BATCH_LADDER, q)
    else:
        batch = cur.batch

    # max_msg_len: the compiled row height — every byte row is hashed,
    # so rows sized for 1232 when the traffic is 200-byte votes wastes
    # ~6x the sha work.  Oversize txns are dropped by the stage guard,
    # so the p99 ladder rung keeps the drop rate inside the tail.
    if msg_len_hist and msg_len_hist.get("count"):
        q = fm.hist_quantile(msg_len_hist, MSG_LEN_Q)
        if q == float("inf"):
            mml = MSG_LEN_LADDER[-1]
        else:
            mml = _ladder_at_least(MSG_LEN_LADDER, q)
    else:
        mml = cur.max_msg_len

    # cached-lane split: a separate comb batch only pays (two shapes,
    # two partial fills) when enough traffic actually rides it
    total = batch_elems or 0
    comb = comb_elems or 0
    if total > 0:
        split = (comb / total) >= COMB_SPLIT_MIN
    else:
        split = cur.comb_split

    return Geometry(batch=batch, max_msg_len=mml, comb_split=split)


def recommend_for_stage(stage, current: Geometry | None = None) -> Geometry:
    """The live-stage entry point: read the stage's OWN schema metrics
    (batch_fill + msg_len histograms, batch/comb element counters) and
    recommend.  Never touches device state."""
    m = stage.metrics
    try:
        fill = m.hist("batch_fill")
    except KeyError:  # pragma: no cover - schema-less test stages
        fill = {}
    try:
        mlh = m.hist("msg_len")
    except KeyError:  # pragma: no cover
        mlh = None
    return recommend(
        fill,
        mlh,
        batch_elems=m.get("batch_elems"),
        comb_elems=m.get("comb_elems"),
        current=current or Geometry(stage.batch, stage.max_msg_len,
                                    stage.comb_slots > 0),
    )
