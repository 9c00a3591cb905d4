"""The verify tile behind its front door, a process per tile: the
generator, verify0 (the chip: only the process that holds it can trace
it) and the sink on this process's one thread; each sender tile
(benchs) and the quic tile an OS process of its own, the senders and
the quic tile over a kernel UDP socket on loopback, built by the
program's `build_quic_topology_from_config` and launched with
`launch(topo, held=...)`.

    TrafficGen -> gb -> benchs x S -> (UDP, QUIC) -> quic -> gv
               -> verify0 -> vd -> Sink

What the bare-tile cell reads from its own memory is read here across
processes (each tile's counters from its shm metrics segment).  What
the quic tile published is held to the plain reference
(harness/quic_reference.py): each sender tile writes its 1-RTT key log
and the first 4,096 datagrams it sent under the run's directory, the
reference opens and reassembles them on its own, and every transaction
it makes of them has to be among what a tap on the quic tile's out ring
saw.  A program without that builder cannot run this configuration:
loading this file refuses it by name, with exit code 2, before
anything is built, compiled or signed.
"""

from __future__ import annotations

import glob
import signal
import sys
import time
from collections import Counter

from firedancer_tpu.models import leader_topo
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.tango import shm
from firedancer_tpu.utils import metrics as fm

from harness import quic_reference
from harness.manifest import Manifest
from harness.rowmap import RowMap
from harness.stages import Sink, TrafficGen

if not hasattr(leader_topo, "build_quic_topology_from_config") \
        or not hasattr(ft.TopologyHandle, "counters"):
    print("benchmark: this program has no front-door topology "
          "(models/leader_topo.build_quic_topology_from_config: sender "
          "tiles, a quic tile and a verify tile as processes): it cannot "
          "run a verify_quic configuration", file=sys.stderr)
    raise SystemExit(2)

_tile = Manifest().topology("verify_tile")

HELD = ("benchg", "verify0", "out")     # this process's thread
BOOT_LIMIT_S = 180.0                    # the children's imports, handshakes
TAP_SLACK = 2048    # frags tapped beyond what the captures can hold


def _terminated(signum, frame):
    # a run cut by SIGTERM (a time limit's) unwinds like any other, so
    # that close() takes the children and the segments away
    raise SystemExit(128 + signum)


class RingTap:
    """Reads the quic tile's out ring beside verify, without an fseq of
    its own (it never gates the producer), and keeps the first `keep`
    payloads whole.  It looks before verify does in every sweep, and
    the producer never runs further ahead of verify than the ring is
    deep, so it cannot be lapped while it still keeps."""

    name = "tap"

    def __init__(self, link, keep: int):
        self.link = link
        self.keep = keep
        self.seq = 0
        self.overrun = 0
        self.kept: list[bytes] = []

    def run_once(self) -> bool:
        if len(self.kept) >= self.keep or self.link is None:
            return False
        mc, dc = self.link.mcache, self.link.dcache
        while len(self.kept) < self.keep:
            status, meta = mc.query(self.seq)
            if status < 0:
                break
            if status > 0:
                self.overrun += 1
                self.seq = int(mc.table[mc.line(self.seq), 0]) & ~mc.BUSY
                continue
            self.kept.append(bytes(dc.read(int(meta[2]), int(meta[3]))))
            self.seq += 1
        return False


class System(_tile.System):
    """The surface of topologies/verify_tile.py's System; what is the
    same in both forms (what is served and due, the landings, the
    latencies, verify's idleness) is that class's."""

    def __init__(self, config: dict, gen_kw: dict, control: str | None,
                 genesis: dict):
        import jax

        from firedancer_tpu.runtime.benchs import CAPTURE_DATAGRAMS
        from firedancer_tpu.utils.config import load_config

        cfg = load_config(None, overrides=config["program_config"])
        self.cfg = cfg
        self.batch = cfg.verify.batch
        self.n_benchs = cfg.layout.benchs_stage_count
        self.senders = [f"benchs{i}" for i in range(self.n_benchs)]
        self.handle = None
        self.deaths: list[str] = []
        self.left: list[str] | None = None      # set by the shutdown
        self._snaps: list[dict] = []            # the runner's reads
        self._final: dict = {}
        topo = leader_topo.build_quic_topology_from_config(
            cfg, verify_precomputed=(control == "allpass"),
            verify_cpu=jax.default_backend() != "tpu", capture=True)
        signal.signal(signal.SIGTERM, _terminated)
        self.handle = h = ft.launch(topo, held=HELD)
        try:
            depth = cfg.verify.receive_buffer_depth
            self.gen = TrafficGen(
                "benchg", outs=[shm.make_producer(h.links["gb"])],
                cnc=h.cncs["benchg"], max_burst=depth, **gen_kw)
            h.hold(self.gen)
            # the builder a child would run: select_device finds what
            # run.py selected, and the program is warm (prewarm)
            self.verify = h.build_held("verify0")
            self.sink = Sink("out", ins=[shm.make_consumer(h.links["vd"],
                                                           lazy=64)],
                             cnc=h.cncs["out"], keep=_tile.KEEP_FRAMES)
            h.hold(self.sink)
            self.tap = RingTap(
                h.links["gv"],
                self.n_benchs * CAPTURE_DATAGRAMS + TAP_SLACK)
            self.stages = [self.gen, self.tap, self.verify, self.sink]
            self.host_stages = self.senders + ["quic"]
            self.rowmap = RowMap(self.gen.pool)
            h.wait_running(BOOT_LIMIT_S)
        except BaseException:
            self.close()
            raise

    # -- the tiles' counters, across processes ------------------------------

    def _read(self) -> dict:
        """Every tile's counters, from the shm segments (the sink's
        also under the bare-tile cell's name for it); a tile that died
        since the last look is noted by name."""
        for name in self.handle.dead():
            if name not in self.deaths:
                self.deaths.append(name)
                print(f"benchmark: tile '{name}' died (flight dump: "
                      f"{self.handle.dump_flight(f'tile {name} died')})",
                      file=sys.stderr)
        c = self.handle.counters()
        c["sink"] = c["out"]
        return c

    def counters(self) -> dict:
        c = self._read()
        self._snaps.append(c)
        return c

    def armed(self) -> dict:
        """Per tile: every native lane it has is armed, in its own
        process (Stage.native_lanes, put out as two gauges): the rings
        everywhere, verify's sweep client, the quic tile's net lane."""
        return {name: c.get("native_lanes", 0) > 0
                and c.get("native_lanes_off", 1) == 0
                for name, c in self._read().items() if name != "sink"}

    # -- the end of a run -----------------------------------------------------

    @staticmethod
    def _gone(q: dict) -> int:
        """Streams the quic tile ended under a named drop counter."""
        return q["reasm_evicted"] + q["reasm_oversz"] + q["reasm_cancelled"]

    def _front_settled(self, c: dict) -> bool:
        """Everything the generator offered went out on a stream, every
        stream was acknowledged, and everything the quic tile made of
        them is on verify's ring."""
        sent = sum(c[s]["txn_tx"] for s in self.senders)
        acked = sum(c[s]["streams_acked"] for s in self.senders)
        q = c["quic"]
        return (sent == self.gen.i and acked == sent
                and q["txn_rx"] + self._gone(q) == sent
                and q["txn_held"] == 0)

    def drain(self, limit_s: float) -> bool:
        """Stop offering, then run this thread's stages until the
        front has settled, verify's rings are empty, nothing is in
        flight and the sink sees nothing more."""
        self.gen.limit = 0
        t_end = time.monotonic() + limit_s
        while time.monotonic() < t_end and not self.deaths:
            t_look = time.monotonic() + 0.02
            while time.monotonic() < t_look:
                self.tap.run_once()
                self.verify.run_once()
                self.sink.run_once()
            if not self._front_settled(self._read()):
                continue
            if self.verify.ins[0].has_pending():
                continue
            self.verify.flush()
            moved = [bool(self.sink.run_once()) for _ in range(8)]
            if not any(moved) and self._verify_idle():
                return True
        return False

    def dropped(self, c: dict) -> int:
        return super().dropped(c) + self._gone(c["quic"])

    def _published_off_reference(self) -> tuple[int, dict]:
        """Transactions the plain reference reassembles from the
        senders' captured datagrams that the tap on the quic tile's
        out ring did not see (each as often as the reference made it),
        plus tapped payloads that are no row of the pool."""
        run_dir = leader_topo.quic_dir(self.handle)
        seen = Counter(self.tap.kept)
        want: Counter = Counter()
        facts = {"captures": 0, "datagrams": 0, "reassembled": 0,
                 "multi_chunk": 0, "tapped": len(self.tap.kept)}
        for path in sorted(glob.glob(run_dir + "/benchs*.keys")):
            keys, dgs = quic_reference.read_capture(path[:-len(".keys")])
            rx = quic_reference.reassemble(keys, dgs)
            want.update(rx.out)
            facts["captures"] += 1
            facts["datagrams"] += len(dgs)
            facts["reassembled"] += len(rx.out)
            facts["multi_chunk"] += rx.multi_chunk
        off = sum(max(n - seen[t], 0) for t, n in want.items())
        foreign = int((self.rowmap.of_payloads(list(seen)) < 0).sum()) \
            if seen else 0
        # no capture, or captures that made nothing: nothing was held
        # to the reference, which is a miss of its own
        if facts["captures"] != self.n_benchs or not facts["reassembled"]:
            off += 1
        return off + foreign + self.tap.overrun, facts

    def _shutdown(self) -> list[str]:
        """Halt the tiles and take the run's segments and files away
        -> what is left all the same (processes, /dev/shm names,
        directories), which has to be nothing."""
        if self.left is None:
            for s in (self.gen, self.verify, self.sink):
                s.ins, s.outs = [], []
                s.drop_native_views()
            self.tap.link = None
            import gc

            gc.collect()
            self.handle.halt()
            self.handle.close()
            self.left = self.handle.left_behind()
        return self.left

    def extra_checks(self) -> dict:
        """The front's four.  The last thing of a run that needs the
        tiles: they are halted here, and what they leave is counted."""
        self._final = c = self._read()
        try:
            off, self.ref_facts = self._published_off_reference()
        except Exception as e:      # a dead sender: no capture to read
            print(f"benchmark: the senders' captures could not be read: "
                  f"{e!r}", file=sys.stderr)
            off, self.ref_facts = -1, {}
        acked = sum(c[s]["streams_acked"] for s in self.senders)
        q = c["quic"]
        gone = self._gone(q)
        left = self._shutdown()
        if left:
            print(f"benchmark: left behind: {left}", file=sys.stderr)
        return {
            "quic_published_off_plain_reference":
                (off if off >= 0 else len(self.tap.kept) + 1, 0),
            "acked_minus_published_minus_drops":
                (abs(acked - q["txn_rx"] - gone), 0),
            "quic_drop_counters": (gone + q["conn_drop"], 0),
            "tile_deaths": (len(self.deaths), 0),
            "children_or_segments_left": (len(left), 0),
        }

    def notes(self) -> dict:
        """Per tile, over the measured window (the runner's first two
        reads of the counters): what of its loop time went to work, to
        backpressure and to empty polls, and which tile was busiest;
        the front's counters over the same window."""
        out = {"dead_tiles": self.deaths,
               "reference": getattr(self, "ref_facts", None)}
        if len(self._snaps) < 2:
            return out
        c0, c1 = self._snaps[:2]
        tiles = {}
        for name in c1:
            if name == "sink":
                continue
            shares = fm.loop_shares(fm.loop_row([c1[name]]),
                                    fm.loop_row([c0.get(name, c1[name])]))
            if shares:
                tiles[name] = {k: round(v, 2) for k, v in shares.items()}
        out["tiles"] = tiles
        if tiles:
            out["busiest_tile"] = max(
                tiles, key=lambda n: tiles[n]["busy_pct"])
        def over_window(tile: str, keys) -> dict:
            return {k: c1[tile].get(k, 0) - c0[tile].get(k, 0)
                    for k in keys if k in c1[tile]}

        q = over_window("quic", fm.FRONT_COUNTERS + (
            "bad_packet", "sweep_busy_ns", "sweep_crossings"))
        for gauge in ("conn_active", "rcvbuf_bytes"):
            q[gauge] = c1["quic"].get(gauge, 0)
        rx, pub = q.get("dgram_rx", 0), q.get("reasm_published", 0)
        q["net_punts_over_dgram_rx"] = q.get("net_punts", 0) / rx \
            if rx else None
        q["reasm_multi_chunk_over_published"] = \
            q.get("reasm_multi_chunk", 0) / pub if pub else None
        out["quic"] = q
        out["benchs"] = {
            s: over_window(s, ("txn_tx", "dgram_tx", "dgram_rx", "dgram_rtx",
                               "streams_acked", "send_blocked_credit"))
            for s in self.senders}
        return out

    def close(self) -> None:
        if self.handle is None:
            return
        left = self._shutdown() if hasattr(self, "tap") \
            else (self.handle.close() or self.handle.left_behind())
        if left:
            raise RuntimeError(f"children_or_segments_left: {left}")


def prewarm(config: dict, control: str | None) -> float:
    from harness.stages import prewarm_verify

    v = config["program_config"]["verify"]
    return prewarm_verify(v["batch"], v["max_msg_len"], control)
