"""The leader pipeline in its cooperative form, built by the program's
own `build_leader_pipeline_from_config` under the 400 ms slot clock:
generator -> verify (device) -> pack(+dedup) -> banks -> poh -> shred ->
store.  The program's generator stage is replaced by the benchmark's."""

from __future__ import annotations

import time

import numpy as np

from firedancer_tpu.models.leader import build_leader_pipeline_from_config
from firedancer_tpu.runtime.bank import default_bank_ctx
from firedancer_tpu.runtime.dedup import DEDUP_TCACHE_DEPTH
from firedancer_tpu.runtime.slot_clock import SlotClockCfg
from firedancer_tpu.runtime.verify import VERIFY_TCACHE_DEPTH
from firedancer_tpu.utils.config import load_config

from harness import check
from harness.rowmap import RowMap
from harness.stages import CommitTap, TrafficGen

# protocol/shred.MAX_PER_SLOT: a block of more data shreds does not parse,
# and `landed` says so as a number of the check.  Under the slot clock
# the store holds a block a 400 ms slot (a run stores ~56), each far
# under the limit.
MAX_DATA_SHREDS_PER_SLOT = 1 << 15


class System:
    def __init__(self, config: dict, gen_kw: dict, control: str | None,
                 genesis: dict):
        cfg = load_config(None, overrides=config["program_config"])
        self.batch = cfg.verify.batch
        n_payers = genesis["n_payers"]
        clk = config["slot_clock"]
        self.pipe = pipe = build_leader_pipeline_from_config(
            cfg, pool_size=n_payers, gen_limit=0,
            verify_precomputed=(control == "allpass"),
            bank_ctx=default_bank_ctx(**genesis),   # the shape's payers
            keep_sets=False, n_payers=n_payers,
            slot_clock=SlotClockCfg(slot_ms=clk["slot_ms"], n_slots=None),
        )
        old = pipe.benchg
        self.gen = TrafficGen("benchg", outs=old.outs,
                              max_burst=cfg.verify.receive_buffer_depth,
                              **gen_kw)
        old.outs = []
        pipe.benchg = self.gen
        pipe.stages[0] = self.gen
        self.verify = pipe.verifies[0]
        self.tap = CommitTap([c.link for c in pipe.poh.ins])
        self.stages = list(pipe.stages) + [self.tap]
        self.host_stages = [s.name for s in pipe.stages
                            if s is not self.gen and s not in pipe.verifies]

    def warmup(self) -> float:
        return sum(v.warmup() for v in self.pipe.verifies)

    def armed(self) -> dict:
        pipe = self.pipe
        return {
            "verify": self.verify._sweep_client is not None,
            "pack": type(pipe.pack).__name__ == "NativePackStage",
            "bank": all(b._sweep_client is not None for b in pipe.banks),
            "shred": pipe.shred._sweep_client is not None,
            "funk": hasattr(pipe.bank_ctx.funk, "txn_diff"),
            "rings": type(self.gen.outs[0]).__name__ == "NativeProducer",
        }

    def counters(self) -> dict:
        for s in self.pipe.stages:
            s.during_housekeeping()  # C-side counters into the metrics
        return {s.name: dict(s.metrics.counters) for s in self.pipe.stages}

    def served(self) -> int:
        """Transactions in committed microblocks, as the tap saw them."""
        return self.tap.n_txn

    def latencies_ns(self, t0: int, t1: int) -> np.ndarray:
        return self.tap.window(t0, t1)[0]

    def drain(self, limit_s: float) -> bool:
        """Stop offering and run the program's own drain, then sweep
        until the banks stop committing or the limit passes."""
        pipe = self.pipe
        t_end = time.monotonic() + limit_s
        self.gen.limit = 0
        while time.monotonic() < t_end:
            before = self.tap.n_txn
            pipe.finish(max_sweeps=2_000)
            self.tap.run_once()
            if self.tap.n_txn == before \
                    and not pipe.pack.pack.pending_cnt() \
                    and self.gen.outs[0].seq == self.verify.ins[0].seq:
                return True
        return False

    def due(self, offered: np.ndarray, valid: np.ndarray) -> dict:
        """What the guarantees say of the offered rows: of those that
        pass the verify stage, pack's tag cache drops a row it has seen
        within its own depth, and what passes both lands once (the
        bank's status cache rejects a replay that outlived pack's
        memory)."""
        passed, fail, dups = check.through_verify(
            offered, valid, VERIFY_TCACHE_DEPTH)
        packed = passed[check.tcache_keeps(passed, DEDUP_TCACHE_DEPTH)]
        return {"landings": np.minimum(
                    np.bincount(packed, minlength=len(valid)), 1),
                "verify_fail": fail,
                "duplicates": dups + len(passed) - len(packed)}

    def dedup_counted(self, c: dict) -> int:
        return c["verify0"].get("dedup_dup", 0) + c["pack"].get("dedup_dup", 0)

    def landed(self):
        """-> (times each pool row landed, landed transactions that match
        no offered one), read from the stored block: every slot the
        store holds, reassembled from its FEC sets.  Entry batch:
        (u32 len | entry)*; entry: u32 num_hashes | 32B hash | u16 cnt |
        (u16 len | payload)*.  A payload is the pool row whose first
        signature it carries, and its bytes must equal that row's."""
        store = self.pipe.store
        found = []
        self.shreds_over_limit = 0
        for slot in sorted(store.sets_by_slot):
            n_data = sum(len(st.data_shreds) for st in store.sets_by_slot[slot])
            if n_data > MAX_DATA_SHREDS_PER_SLOT:
                # the program cannot read such a block back: say so as a
                # number of the check, the slot's transactions go missing
                self.shreds_over_limit += n_data - MAX_DATA_SHREDS_PER_SLOT
                continue
            batch = store.entry_batch_bytes(slot)
            o = 0
            while o < len(batch):
                end = o + 4 + int.from_bytes(batch[o:o + 4], "little")
                cnt = int.from_bytes(batch[o + 40:o + 42], "little")
                o += 42
                for _ in range(cnt):
                    ln = int.from_bytes(batch[o:o + 2], "little")
                    found.append(batch[o + 2:o + 2 + ln])
                    o += 2 + ln
                if o != end:
                    raise RuntimeError("stored entry batch does not parse")
        pool = self.gen.pool
        rows = RowMap(pool).of_payloads(found)
        return (np.bincount(rows[rows >= 0], minlength=pool.n),
                int((rows < 0).sum()))

    def extra_checks(self) -> dict:
        """name -> (value, limit); a value over its limit is a miss."""
        pipe = self.pipe
        fec = pipe.shred.metrics.get("fec_sets")
        stored = pipe.store.metrics.get("sets_stored")
        exec_ = sum(b.metrics.get("txn_exec") for b in pipe.banks)
        return {
            "data_shreds_over_the_slot_limit": (self.shreds_over_limit, 0),
            "fec_sets_not_stored": (abs(fec - stored) + (fec == 0), 0),
            "tap_overrun": (self.tap.overrun, 0),
            "tap_txn_minus_bank_txn_exec": (abs(self.tap.n_txn - exec_), 0),
        }

    def dropped(self, c: dict) -> int:
        return (c["pack"].get("txn_dropped", 0) + c["pack"].get("txn_shed", 0)
                + c["verify0"].get("emit_dropped", 0)
                + c["verify0"].get("intake_dropped", 0))

    def notes(self) -> dict:
        poh, pack = self.pipe.poh.metrics, self.pipe.pack.metrics
        return {"slots_sealed": poh.get("slots_sealed"),
                "slot_missed": poh.get("slot_missed"),
                "blocks_closed": pack.get("blocks_closed")}

    def close(self) -> None:
        self.tap.links = []
        self.pipe.close()


def prewarm(config: dict, control: str | None) -> float:
    from harness.stages import prewarm_verify

    v = config["program_config"]["verify"]
    return prewarm_verify(v["batch"], v["max_msg_len"], control)
