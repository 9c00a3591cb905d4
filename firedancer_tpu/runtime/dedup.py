"""Dedup stage: the global signature dedup after verify.

Reference: src/app/fdctl/run/tiles/fd_dedup.c — one stage with a big tcache
keyed on the first signature; drops duplicates, forwards everything else
unchanged.  The verify stages' tiny tcaches only guard racing duplicates
across round-robin peers; this is the authoritative filter.

Over native rings with the native tag cache the stage takes a drained
sweep at a time (`_sweep_frags`: one tag-cache crossing and one publish
burst a sweep); otherwise a frag at a time (`after_frag`).  One rule on
both: a tag goes into the cache only when its frag can be forwarded.
`dedup_dup_sigs` counts the signatures of what was dropped: lanes the
verify stage in front spent on repeats past its own small cache.
"""

from __future__ import annotations

from firedancer_tpu.tango.rings import TCache
from firedancer_tpu.utils import metrics as fm
from .stage import Stage

DEDUP_TCACHE_DEPTH = 1 << 16


def trailer_sig_cnt(frag: bytes) -> int:
    """How many signatures a verified frag's transaction carries, read
    off its trailer (payload || packed descriptor || u16 payload_sz,
    verify.encode_verified_packed; the descriptor's second byte is the
    count); 0 where the frag is too short to carry one."""
    at = int.from_bytes(frag[-2:], "little") + 1
    return frag[at] if at < len(frag) - 2 else 0


class DedupStage(Stage):
    @classmethod
    def extra_schema(cls) -> fm.MetricsSchema:
        # hit rate for dashboards = dedup_dup / frags_in
        return (
            fm.MetricsSchema()
            .counter("dedup_dup", "duplicate txns dropped by the global tcache")
            .counter("dedup_dup_sigs",
                     "signatures of the txns counted in dedup_dup: what"
                     " the verify stage checked for nothing")
        )

    def __init__(self, *args, tcache_depth: int = DEDUP_TCACHE_DEPTH, **kwargs):
        super().__init__(*args, **kwargs)
        # fdrace FD403 true positive: after_frag inserts into the tcache
        # BEFORE publishing, so a backpressured publish dropped the txn
        # while the tcache already marked it seen — an upstream
        # retransmit then dies here as a "duplicate" forever.  Never
        # consume a frag that can't be forwarded (bank/poh/sign's
        # contract).
        self.require_credit = True
        # the native C++ tcache is the hot path (fd_dedup.c's position is
        # all per-frag overhead); the Python ring is the portable fallback
        try:
            from firedancer_tpu.tango.tcache_native import NativeTCache
            from firedancer_tpu.utils.nativebuild import NativeUnavailable

            try:
                self.tcache = NativeTCache(tcache_depth)
                # a drained sweep at a time (Stage._native_burst): the
                # native tag cache takes a sweep's tags in one crossing,
                # and verify hands over a reaped batch at a time, so a
                # sweep takes what a batch brings (the fixed cost of a
                # sweep is five frags' worth of the work per frag)
                self.sweep_frags = self._sweep_frags
                self.burst = 256
            except NativeUnavailable:
                self.tcache = TCache(tcache_depth)
        except ImportError:
            self.tcache = TCache(tcache_depth)

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        from firedancer_tpu.tango.rings import MCache

        tag = int(meta[MCache.COL_SIG])
        if self.tcache.insert(tag):
            self.metrics.inc("dedup_dup")
            self.metrics.inc("dedup_dup_sigs", trailer_sig_cnt(payload))
            return
        if self.outs:
            self.publish(
                0, payload, sig=tag, tsorig=int(meta[MCache.COL_TSORIG])
            )

    def _sweep_frags(self, rows, buf: bytes):
        """Drain-table intake: one tag-cache crossing and one publish
        burst a sweep, for two crossings a frag.  The same rule as
        after_frag in the same order: _native_burst drains no more
        frags than the out ring has credits for (require_credit), so a
        tag goes into the cache only with the credit that forwards its
        frag."""
        dup = self.tcache.insert_bulk([r[1] for r in rows]).tolist()
        items = []
        dup_sigs = 0
        for r, d in zip(rows, dup):
            frag = buf[r[2] : r[2] + r[3]]
            if d:
                dup_sigs += trailer_sig_cnt(frag)
            else:
                items.append((frag, r[1], r[5]))
        if len(items) < len(rows):
            self.metrics.inc("dedup_dup", len(rows) - len(items))
            self.metrics.inc("dedup_dup_sigs", dup_sigs)
        if self.outs:
            self.publish_burst_out(0, items)
        return len(rows), [r[5] for r in rows]
