"""The verify tile alone: generator -> shm ring -> one VerifyStage ->
shm ring -> the harness's sink.  BASELINE.json configs[1].  A
configuration with a `mesh` puts that many chips behind the stage."""

from __future__ import annotations

import time

import numpy as np

from firedancer_tpu.runtime.verify import VERIFY_TCACHE_DEPTH, VerifyStage
from firedancer_tpu.tango import shm

from harness import check
from harness.rowmap import RowMap
from harness.stages import Sink, TrafficGen

KEEP_FRAMES = 4096  # whole frames kept for the byte-for-byte comparison


class System:
    def __init__(self, config: dict, gen_kw: dict, control: str | None,
                 genesis: dict):
        v = config["verify"]
        self.batch = v["batch"]
        uid = shm.fresh_uid()
        self.links = [
            shm.ShmLink.create(f"fdtpu_bgv_{uid}",
                               depth=v["receive_buffer_depth"], mtu=1232),
            shm.ShmLink.create(f"fdtpu_bvo_{uid}",
                               depth=v["out_depth"], mtu=v["out_mtu"]),
        ]
        gv, vo = self.links
        self.gen = TrafficGen("gen", outs=[shm.make_producer(gv)],
                              max_burst=v["receive_buffer_depth"], **gen_kw)
        self.verify = VerifyStage(
            "verify0", ins=[shm.make_consumer(gv, lazy=32)],
            outs=[shm.make_producer(vo)], batch=v["batch"],
            max_msg_len=v["max_msg_len"],
            batch_deadline_s=v["batch_deadline_ms"] / 1e3,
            devices=config.get("mesh", {}).get("devices"),
            precomputed_ok=(control == "allpass"),
        )
        self.sink = Sink("sink", ins=[shm.make_consumer(vo, lazy=64)],
                         keep=KEEP_FRAMES)
        self.stages = [self.gen, self.verify, self.sink]
        self.host_stages: list[str] = []
        self.rowmap = RowMap(self.gen.pool)

    def warmup(self) -> float:
        return self.verify.warmup()

    def armed(self) -> dict:
        return {
            "verify": self.verify._sweep_client is not None,
            "rings": type(self.gen.outs[0]).__name__ == "NativeProducer",
        }

    def counters(self) -> dict:
        self.verify.during_housekeeping()  # C-side intake counters
        return {s.name: dict(s.metrics.counters) for s in self.stages}

    def served(self) -> int:
        """Signatures in verified-or-rejected transactions that left the
        stage: a batch counts only once its mask was reaped.  What
        passed is the sink's frags, each the row its tag names; what
        failed is `verify_fail` transactions, which the stage (in order)
        rejects in the order the corrupted rows were offered."""
        gen, fail = self.gen, self.verify.metrics.get("verify_fail")
        sigs = gen.pool.sigs
        if sigs.min() == sigs.max():
            return int(sigs[0]) * (self.sink.n + fail)
        rows = self.rowmap.of_tags(self.sink.arrays()[1])
        bad = sigs[gen.order][~gen.pool.valid[gen.order]]  # a lap's, in order
        laps, rest = divmod(fail, max(len(bad), 1))
        return int(sigs[rows[rows >= 0]].sum()
                   + laps * bad.sum() + bad[:rest].sum())

    def latencies_ns(self, t0: int, t1: int) -> np.ndarray:
        arr, _, ts = self.sink.arrays()
        m = (arr >= t0) & (arr < t1) & (ts > 0)
        return arr[m] - ts[m]

    def _verify_idle(self) -> bool:
        v = self.verify
        c = v._sweep_client
        if c is not None:
            return not (v._nv_inflight or v._nv_emit or c.stash_pending
                        or c.open_elems())
        return not (v._inflight or v._submit_queue or v._emit_queue
                    or v._gen.elems)

    def drain(self, limit_s: float) -> bool:
        """Stop offering, then run the tile until the input ring is
        empty, nothing is in flight and the sink sees nothing more."""
        self.gen.limit = 0
        t_end = time.monotonic() + limit_s
        while time.monotonic() < t_end:
            for _ in range(32):
                self.verify.run_once()
                self.sink.run_once()
            if self.gen.outs[0].seq != self.verify.ins[0].seq:
                continue
            self.verify.flush()
            moved = [bool(self.sink.run_once()) for _ in range(8)]
            if not any(moved) and self._verify_idle():
                return True
        return False

    def due(self, offered: np.ndarray, valid: np.ndarray) -> dict:
        """What the guarantees say of the offered rows: every offer
        that passes the verify stage leaves the tile (nothing
        downstream dedups)."""
        passed, fail, dups = check.through_verify(
            offered, valid, VERIFY_TCACHE_DEPTH)
        return {"landings": np.bincount(passed, minlength=len(valid)),
                "verify_fail": fail, "duplicates": dups}

    def dedup_counted(self, c: dict) -> int:
        return c["verify0"].get("dedup_dup", 0)

    def landed(self):
        """-> (times each pool row landed, landed things that match no
        offered transaction).  Every frag by its tag; the kept frames
        byte for byte (payload || descriptor || u16 payload size)."""
        rows = self.rowmap.of_tags(self.sink.arrays()[1])
        count = np.bincount(rows[rows >= 0], minlength=self.gen.pool.n)
        kept = [f[:int.from_bytes(f[-2:], "little")] for f in self.sink.kept]
        unknown = int((rows < 0).sum()) \
            + int((self.rowmap.of_payloads(kept) < 0).sum())
        return count, unknown

    def extra_checks(self) -> dict:
        return {}

    def dropped(self, c: dict) -> int:
        v = c["verify0"]
        return v.get("emit_dropped", 0) + v.get("intake_dropped", 0)

    def notes(self) -> dict:
        return {}

    def close(self) -> None:
        for s in self.stages:
            s.ins = []
            s.outs = []
            s.drop_native_views()
        import gc

        gc.collect()
        for link in self.links:
            link.close()
            link.unlink()


def prewarm(config: dict, control: str | None) -> float:
    from harness.stages import prewarm_verify

    v = config["verify"]
    return prewarm_verify(v["batch"], v["max_msg_len"], control)
