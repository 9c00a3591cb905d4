"""The verify tile with the dedup tile behind it: generator -> shm ring
-> one VerifyStage -> shm ring -> DedupStage -> shm ring -> the
harness's sink.  BASELINE.json configs[2] (the reference's fd_verify in
front of fd_dedup).

The tile topology's `System` (verify_tile.py) with one more stage and
one more ring, and what follows from them: what is due passes verify's
rule and then dedup's tag cache; the signatures whose verdict left the
verify stage are counted where they leave it, by a tap on verify's out
ring (a transaction that dedup then drops used its lanes); latency and
landings are read at the sink, through dedup.  A program whose stages
lack the counters this deployment is read by (a commit before
`batch_fit_pad_lanes` and `dedup_dup_sigs`) cannot run it: loading this
file refuses it by name, with exit code 2, before anything is built,
compiled or signed.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from firedancer_tpu.runtime.dedup import DedupStage
from firedancer_tpu.runtime.verify import VERIFY_TCACHE_DEPTH, VerifyStage
from firedancer_tpu.tango import shm

from harness import check
from harness.manifest import Manifest
from harness.rowmap import RowMap
from harness.stages import Sink, TrafficGen

_tile = Manifest().topology("verify_tile")

_need = {VerifyStage: ("batch_fit_pad_lanes", "verify_fail_elems"),
         DedupStage: ("dedup_dup_sigs",)}
for _stage, _names in _need.items():
    _have = _stage.metrics_schema().names()
    if not set(_names) <= _have:
        print(f"benchmark: this program's {_stage.__name__} does not count "
              f"{', '.join(n for n in _names if n not in _have)}: it cannot "
              f"run a verify_dedup configuration", file=sys.stderr)
        raise SystemExit(2)


class VerifyTap:
    """Reads verify's out ring beside the dedup stage, without an fseq
    of its own (it never gates the producer): the tag of every frag
    verify published, a sweep's worth in one indexed read of the ring's
    metadata table.  Lapped (the producer more than a ring's depth
    ahead of where the tap last looked) it counts the frags it missed
    in `overrun`, which the check holds to 0."""

    name = "tap"   # a member of the sweep: looks once a sweep

    def __init__(self, link, producer):
        self.mc = link.mcache
        self.producer = producer
        self.seq = 0
        self.overrun = 0
        self.tags: list[np.ndarray] = []

    def run_once(self) -> bool:
        end = self.producer.seq
        if end == self.seq:
            return False
        mc = self.mc
        depth = mc.depth
        if end - self.seq > depth:
            self.overrun += end - self.seq - depth
            self.seq = end - depth
        seqs = np.arange(self.seq, end, dtype=np.uint64)
        rows = mc.table[seqs & np.uint64(depth - 1)]
        ok = rows[:, 0] == seqs     # not busy, not overwritten since
        self.overrun += int((~ok).sum())
        self.tags.append(rows[ok, 1])
        self.seq = end
        return True

    def all_tags(self) -> np.ndarray:
        if len(self.tags) > 1:
            self.tags = [np.concatenate(self.tags)]
        return self.tags[0] if self.tags else np.zeros((0,), np.uint64)


class System(_tile.System):
    def __init__(self, config: dict, gen_kw: dict, control: str | None,
                 genesis: dict):
        v, d = config["verify"], config["dedup"]
        if v["tcache_depth"] != VERIFY_TCACHE_DEPTH:
            raise ValueError(
                f"verify.tcache_depth {v['tcache_depth']} is stated, not "
                f"set: the program's is {VERIFY_TCACHE_DEPTH}")
        self.batch = v["batch"]
        self.dedup_depth = d["tcache_depth"]
        uid = shm.fresh_uid()
        self.links = [
            shm.ShmLink.create(f"fdtpu_bgv_{uid}",
                               depth=v["receive_buffer_depth"], mtu=1232),
            shm.ShmLink.create(f"fdtpu_bvd_{uid}",
                               depth=v["out_depth"], mtu=v["out_mtu"]),
            shm.ShmLink.create(f"fdtpu_bds_{uid}",
                               depth=d["out_depth"], mtu=d["out_mtu"]),
        ]
        gv, vd, ds = self.links
        self.gen = TrafficGen("gen", outs=[shm.make_producer(gv)],
                              max_burst=v["receive_buffer_depth"], **gen_kw)
        self.verify = VerifyStage(
            "verify0", ins=[shm.make_consumer(gv, lazy=32)],
            outs=[shm.make_producer(vd)], batch=v["batch"],
            max_msg_len=v["max_msg_len"],
            batch_deadline_s=v["batch_deadline_ms"] / 1e3,
            precomputed_ok=(control == "allpass"),
        )
        self.tap = VerifyTap(vd, self.verify.outs[0])
        self.dedup = DedupStage(
            "dedup", ins=[shm.make_consumer(vd, lazy=32)],
            outs=[shm.make_producer(ds)], tcache_depth=d["tcache_depth"])
        self.sink = Sink("sink", ins=[shm.make_consumer(ds, lazy=64)],
                         keep=_tile.KEEP_FRAMES)
        self.stages = [self.gen, self.verify, self.tap, self.dedup,
                       self.sink]
        self.host_stages: list[str] = []
        self.rowmap = RowMap(self.gen.pool)
        self._kept_memo: tuple | None = None

    def armed(self) -> dict:
        return dict(super().armed(),
                    dedup_tcache=type(self.dedup.tcache).__name__
                    == "NativeTCache")

    def counters(self) -> dict:
        self.verify.during_housekeeping()  # C-side intake counters
        return {s.name: dict(s.metrics.counters) for s in self.stages
                if s is not self.tap}

    # -- what verify's tag cache lets through --------------------------------

    def _kept(self, n_offers: int) -> np.ndarray:
        """The pool rows of the first `n_offers` offers that verify's
        tag cache lets through, in order (what reaches verification:
        no row here is longer than the stage takes)."""
        if self._kept_memo is None or self._kept_memo[0] != n_offers:
            offered = check.offered_rows(self.gen.order, 0, n_offers)
            self._kept_memo = (n_offers, offered[
                check.tcache_keeps(offered, VERIFY_TCACHE_DEPTH)])
        return self._kept_memo[1]

    def served(self) -> int:
        """Signatures whose verdict left the verify stage: those of the
        frags the tap saw on verify's out ring, each the row its tag
        names, plus those of the `verify_fail` transactions, which fail
        in the order corrupted rows reach verification."""
        pool = self.gen.pool
        rows = self.rowmap.of_tags(self.tap.all_tags())
        passed = int(pool.sigs[rows[rows >= 0]].sum())
        fail = self.verify.metrics.get("verify_fail")
        if not fail:
            return passed
        kept = self._kept(self.gen.i)
        return passed + int(pool.sigs[kept[~pool.valid[kept]]][:fail].sum())

    def _quiet(self) -> bool:
        return (self.gen.outs[0].seq == self.verify.ins[0].seq
                and self.verify.outs[0].seq == self.dedup.ins[0].seq)

    def drain(self, limit_s: float) -> bool:
        """Stop offering, then run the pair until both rings in front
        of the sink's are empty, nothing is in flight and the sink sees
        nothing more."""
        self.gen.limit = 0
        behind = self.stages[1:]
        t_end = time.monotonic() + limit_s
        while time.monotonic() < t_end:
            for _ in range(32):
                for s in behind:
                    s.run_once()
            if not self._quiet():
                continue
            self.verify.flush()
            moved = [bool(s.run_once()) for _ in range(8)
                     for s in behind[1:]]
            if not any(moved) and self._quiet() and self._verify_idle():
                return True
        return False

    def due(self, offered: np.ndarray, valid: np.ndarray) -> dict:
        """What the guarantees say of the offered rows: an offer that
        passes the verify stage leaves the pair unless dedup's tag
        cache has seen its row among the last `dedup.tcache_depth` it
        let through."""
        passed, fail, dups = check.through_verify(
            offered, valid, VERIFY_TCACHE_DEPTH)
        keep = check.tcache_keeps(passed, self.dedup_depth)
        return {"landings": np.bincount(passed[keep], minlength=len(valid)),
                "verify_fail": fail,
                "duplicates": dups + int((~keep).sum())}

    def dedup_counted(self, c: dict) -> int:
        return c["verify0"].get("dedup_dup", 0) \
            + c["dedup"].get("dedup_dup", 0)

    def extra_checks(self) -> dict:
        pool = self.gen.pool
        v = self.counters()["verify0"]
        lanes_due = int(pool.sigs[self._kept(self.gen.i)].sum())
        landed = np.bincount(pool.sigs[self.landed()[0] > 0],
                             minlength=9)[1:9]
        return {
            # every signature of every transaction verify's tag cache
            # let through took a lane, and nothing else did
            "lanes_minus_signatures_due":
                (abs(v.get("elems_in", 0) - lanes_due), 0),
            # of 1..8: a count none of whose transactions came out
            "sig_counts_never_landed": (int((landed == 0).sum()), 0),
            # a batch sealed for want of room is short by at most 7
            "fit_pad_lanes_over_7_a_full_batch":
                (max(0, v.get("batch_fit_pad_lanes", 0)
                     - 7 * v.get("batch_close_full", 0)), 0),
            "tap_overrun": (self.tap.overrun, 0),
            "msg_too_long": (v.get("msg_too_long", 0), 0),
        }

    def notes(self) -> dict:
        c = self.counters()
        v, d = c["verify0"], c["dedup"]
        return {
            "verify": {k: int(v.get(k, 0)) for k in (
                "batches", "batch_elems", "txn_in", "elems_in",
                "batch_close_full", "batch_close_deadline",
                "batch_close_window", "batch_queued_behind",
                "batch_fit_pad_lanes", "verify_fail", "verify_fail_elems",
                "dedup_dup")},
            "dedup": {k: int(d.get(k, 0)) for k in (
                "frags_in", "frags_out", "dedup_dup", "dedup_dup_sigs",
                "backpressure_stall")}}

    def close(self) -> None:
        self.stages.remove(self.tap)    # holds no ring of its own
        self.tap.mc = self.tap.producer = None
        super().close()


prewarm = _tile.prewarm
