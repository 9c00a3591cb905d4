"""The leader pipeline with a process per tile, as `fddev bench` and
every operator's `fdctl run` have it: this process holds the generator,
the verify tile (the chip: only the process that holds it can trace it)
and the commit tap on one thread; pack(+dedup), the bank, poh, shred and
store are each an OS process of their own over the same shm rings, built
by the program's `build_leader_topology_from_config` and launched with
`launch(topo, held=...)` under the 400 ms slot clock.

What the cooperative cell reads from its own memory is read here across
processes: every tile's counters from its shm metrics segment
(`TopologyHandle.counters`, under the stage names topologies/leader.py
has), the stored block from the files the store tile writes, the
account store through `NativeFunk.attach_readonly`.  A program without
that launch cannot run this configuration: loading this file refuses it
by name, with exit code 2, before anything is built, compiled or signed.
"""

from __future__ import annotations

import signal
import sys
import time
from types import SimpleNamespace

from firedancer_tpu.models import leader_topo
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.utils import metrics as fm

from harness import ledger_reference as ledger
from harness.manifest import Manifest
from harness.rowmap import RowMap
from harness.stages import CommitTap, TrafficGen

if not hasattr(leader_topo, "build_leader_topology_from_config") \
        or not hasattr(ft.TopologyHandle, "counters"):
    print("benchmark: this program cannot hold stages of a process "
          "topology in the launching process (runtime/topo.launch(held=), "
          "models/leader_topo.build_leader_topology_from_config): it cannot "
          "run a leader_tiles configuration", file=sys.stderr)
    raise SystemExit(2)

_leader = Manifest().topology("leader")

HELD = ("benchg", "verify0")        # with the tap: this process's thread
BOOT_LIMIT_S = 180.0                # the children's imports and builders


def _terminated(signum, frame):
    # a run cut by SIGTERM (a time limit's) unwinds like any other, so
    # that close() takes the children and the segments away
    raise SystemExit(128 + signum)


class System(_leader.System):
    """The surface of topologies/leader.py's System; what is the same
    in both forms (what is due, the dedup and drop counts, the stored
    block's parser, the tap's numbers) is that class's."""

    def __init__(self, config: dict, gen_kw: dict, control: str | None,
                 genesis: dict):
        import jax

        from firedancer_tpu.runtime.slot_clock import SlotClockCfg
        from firedancer_tpu.tango import shm
        from firedancer_tpu.utils.config import load_config

        cfg = load_config(None, overrides=config["program_config"])
        self.batch = cfg.verify.batch
        self.genesis = genesis
        self.handle = None
        self.deaths: list[str] = []
        self.left: list[str] | None = None      # set by the shutdown
        self._snaps: list[dict] = []            # the runner's reads
        self._final: dict = {}
        topo = leader_topo.build_leader_topology_from_config(
            cfg, genesis=genesis,
            slot_clock=SlotClockCfg(slot_ms=config["slot_clock"]["slot_ms"],
                                    n_slots=None),
            verify_precomputed=(control == "allpass"),
            verify_cpu=jax.default_backend() != "tpu")
        signal.signal(signal.SIGTERM, _terminated)
        self.handle = h = ft.launch(topo, held=HELD)
        try:
            self.gen = TrafficGen(
                "benchg", outs=[shm.make_producer(h.links["gv"])],
                cnc=h.cncs["benchg"],
                max_burst=cfg.verify.receive_buffer_depth, **gen_kw)
            h.hold(self.gen)
            # the builder a child would run: select_device finds what
            # run.py selected, and the program is warm (prewarm)
            self.verify = h.build_held("verify0")
            self.tap = CommitTap([h.links["bp0"]])
            self.stages = [self.gen, self.verify, self.tap]
            self.host_stages = [s.name for s in topo.stages
                                if s.name not in HELD]
            h.wait_running(BOOT_LIMIT_S)
        except BaseException:
            self.close()
            raise

    def warmup(self) -> float:
        return self.verify.warmup()

    # -- the tiles' counters, across processes ------------------------------

    def _read(self) -> dict:
        """Every tile's counters, from the shm segments; a tile that
        died since the last look is noted by name."""
        for name in self.handle.dead():
            if name not in self.deaths:
                self.deaths.append(name)
                print(f"benchmark: tile '{name}' died (flight dump: "
                      f"{self.handle.dump_flight(f'tile {name} died')})",
                      file=sys.stderr)
        return self.handle.counters()

    def counters(self) -> dict:
        c = self._read()
        self._snaps.append(c)
        return c

    def armed(self) -> dict:
        """Per tile: every native lane it has is armed, in its own
        process (Stage.native_lanes, put out as two gauges)."""
        return {name: c.get("native_lanes", 0) > 0
                and c.get("native_lanes_off", 1) == 0
                for name, c in self._read().items()}

    # -- the end of a run -----------------------------------------------------

    def drain(self, limit_s: float) -> bool:
        """Stop offering, flush verify's open batch, and keep this
        thread's stages going until no transaction has moved anywhere
        for two slot boundaries (the shred tile flushes a slot's tail
        when poh closes the slot; an empty slot still makes a set of
        its ticks) with every set stored."""
        t_end = time.monotonic() + limit_s
        self.gen.limit = 0
        self.verify.flush()
        seen, slots_at = None, 0
        while time.monotonic() < t_end and not self.deaths:
            t_look = time.monotonic() + 0.02
            while time.monotonic() < t_look:
                self.verify.run_once()
                self.tap.run_once()
            c = self._read()
            slots = c["poh"]["slots_sealed"] + c["poh"]["slot_missed"]
            now = (self.tap.n_txn, self.verify.metrics.get("frags_out"),
                   c["pack"]["txn_in"], c["pack"]["txn_scheduled"],
                   c["bank0"]["txn_exec"], c["shred"]["entries_in"]
                   - c["poh"]["ticks"])
            if now != seen:
                seen, slots_at = now, slots
            elif slots - slots_at >= 2 \
                    and self.gen.outs[0].seq == self.verify.ins[0].seq \
                    and c["shred"]["fec_sets"] == c["store"]["sets_stored"] \
                    and self.tap.n_txn == c["bank0"]["txn_exec"]:
                return True
        return False

    def landed(self):
        from firedancer_tpu.runtime.store import StoredSlots

        self._final = self._read()      # the store tile flushes its files
        self.pipe = SimpleNamespace(
            store=StoredSlots(leader_topo.store_dir(self.handle)))
        return super().landed()

    def _account_store_off(self) -> int:
        """Accounts whose lamports in the bank tile's funk, read from
        this process, differ from the plain replay of the stored
        block over the shape's genesis."""
        from firedancer_tpu.flamenco.runtime import acct_decode
        from firedancer_tpu.funk.funk_native import NativeFunk
        from firedancer_tpu.runtime.benchg import pool_payers
        from firedancer_tpu.runtime.poh_stage import parse_entry
        from firedancer_tpu.runtime.shred_stage import deshred_entry_batch

        store = self.pipe.store
        block = [txn for slot in sorted(store.sets_by_slot)
                 for entry in deshred_entry_batch(
                     store.entry_batch_bytes(slot))
                 for txn in parse_entry(entry)[2]]
        # only rows of the pool are replayed: anything else in the block
        # is already a miss of the harness's own check
        known = RowMap(self.gen.pool).of_payloads(block) >= 0
        g = self.genesis
        funded = 10**12                 # genesis_bank_ctx's payer_lamports
        ref = ledger.replay(
            {pub: funded for _s, pub in pool_payers(g["seed"], g["n_payers"])},
            {}, {}, 1, [t for t, ok in zip(block, known) if ok])
        funk = NativeFunk.attach_readonly(
            leader_topo.bank_funk_shm(self.handle))
        try:
            off = 0
            for key, want in ref["lamports"].items():
                val = funk.rec_query(leader_topo.BANK_FORK_XID, key)
                off += (acct_decode(val)[0] if val else 0) != want
            self.accounts_replayed = len(ref["lamports"])
            return off
        finally:
            funk.close()

    def _shutdown(self) -> list[str]:
        """Halt the tiles and take the run's segments and files away
        -> what is left all the same (processes, /dev/shm names,
        directories), which has to be nothing."""
        if self.left is None:
            for s in (self.gen, self.verify):
                s.ins, s.outs = [], []
                s.drop_native_views()
            self.tap.links = []
            self.pipe = None
            import gc

            gc.collect()
            self.handle.halt()
            self.handle.close()
            self.left = self.handle.left_behind()
        return self.left

    def extra_checks(self) -> dict:
        """topologies/leader.py's four, from the tiles' last counters,
        and this form's three.  The last thing of a run that needs the
        tiles: they are halted here, and what they leave is counted."""
        c = self._final
        fec, stored = c["shred"]["fec_sets"], c["store"]["sets_stored"]
        try:
            off = self._account_store_off()
        except Exception as e:      # a dead bank tile: no store to read
            print(f"benchmark: the account store could not be read back: "
                  f"{e!r}", file=sys.stderr)
            off = -1
        self._read()                # a tile that died since
        left = self._shutdown()
        if left:
            print(f"benchmark: left behind: {left}", file=sys.stderr)
        return {
            "data_shreds_over_the_slot_limit": (self.shreds_over_limit, 0),
            "fec_sets_not_stored": (abs(fec - stored) + (fec == 0), 0),
            "tap_overrun": (self.tap.overrun, 0),
            "tap_txn_minus_bank_txn_exec":
                (abs(self.tap.n_txn - c["bank0"]["txn_exec"]), 0),
            # accounts off the replay; every account where none was read
            "account_store_off_ledger_replay":
                (off if off >= 0 else self.genesis["n_payers"], 0),
            "tile_deaths": (len(self.deaths), 0),
            "children_or_segments_left": (len(left), 0),
        }

    def notes(self) -> dict:
        """Per tile, over the measured window (the runner's first two
        reads of the counters): what of its loop time went to work, to
        backpressure and to empty polls; and which tile was busiest."""
        c = self._final
        out = {"slots_sealed": c["poh"]["slots_sealed"],
               "slot_missed": c["poh"]["slot_missed"],
               "blocks_closed": c["pack"]["blocks_closed"],
               "dead_tiles": self.deaths,
               "accounts_replayed": getattr(self, "accounts_replayed", None)}
        if len(self._snaps) >= 2:
            c0, c1 = self._snaps[:2]
            tiles = {}
            for name in c1:
                shares = fm.loop_shares(fm.loop_row([c1[name]]),
                                        fm.loop_row([c0.get(name, c1[name])]))
                if shares:
                    tiles[name] = {k: round(v, 2) for k, v in shares.items()}
            out["tiles"] = tiles
            if tiles:
                out["busiest_tile"] = max(
                    tiles, key=lambda n: tiles[n]["busy_pct"])
        return out

    def close(self) -> None:
        if self.handle is None:
            return
        left = self._shutdown() if hasattr(self, "tap") \
            else (self.handle.close() or self.handle.left_behind())
        if left:
            raise RuntimeError(f"children_or_segments_left: {left}")


prewarm = _leader.prewarm
