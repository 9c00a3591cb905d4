"""The verify phase of a follower's replay tile: the generator -> shm
ring -> a harness-side blocker -> shm ring -> the replay verify stage
(named verify0) -> shm ring -> the harness's sink, all on this
process's one thread, the stage and its two rings built by the
program's `build_replay_topology_from_config` and held with
`launch(topo, held=...)`.

    TrafficGen -> gb -> Blocker -> rv -> verify0 -> vo -> BlockSink

The blocker is traffic, not the reference: it cuts the offered
transactions into what a leader here would have sent — entries of
`txns_per_entry` (one mixin hash each), `ticks_per_slot` ticks of
`hashes_per_tick` hashes, entry batches of `entries_per_batch` entries,
slots of `slot_txns` transactions: offer k is in slot k // slot_txns —
with the chain's hashes under `hashlib`, a slot's seed the hash of its
number.  What a slot's frames are is a pure function of the pool and
the slot's number (`slot_frames`), so the check makes them again.

What is served is fixed by the blocks and not by timing: of a slot dead
at entry batch j, the batches before j left, batch j is rejected,
everything after j is skipped.  What came out is held to that for every
slot, and for a seeded sample of slots (every dead one first) to the
plain reference (harness/replay_reference.py: the frames parsed on its
own, the chain under hashlib, every signature under OpenSSL) frame by
frame, by size and crc32, and byte for byte for the first frames kept
whole.  A program without the replay topology cannot run this
configuration: loading this file refuses it by name, with exit code 2,
before anything is built, compiled or signed.
"""

from __future__ import annotations

import hashlib
import struct
import sys
import time
import zlib
from collections import deque

import numpy as np

from firedancer_tpu.models import leader_topo
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.runtime.stage import Stage
from firedancer_tpu.tango import shm

from harness import replay_reference
from harness.stages import Sink, TrafficGen, prewarm_verify

if not hasattr(leader_topo, "build_replay_topology_from_config"):
    print("benchmark: this program has no replay topology "
          "(models/leader_topo.build_replay_topology_from_config: a verify "
          "stage that takes entry batches): it cannot run a replay_verify "
          "configuration", file=sys.stderr)
    raise SystemExit(2)

HELD = ("replaysrc", "verify0", "replayout")    # this process's thread
KEEP_FRAMES = 2048      # whole frames kept for the byte-for-byte comparison
GEN_DEPTH = 4096        # the generator's ring to the blocker, in txns
OUT_QUEUE = 64          # frames the blocker makes ahead of the ring's room
SAMPLE_SLOTS = 3        # slots held to the plain reference, dead ones first
HDR = struct.Struct("<QII")
F_LAST, F_SEED, F_VERDICT = 1, 2, 4


class Layout:
    """Where the transactions of a slot lie: entry e holds
    `entry_txns[e]` of them (a tick none), batch j entries [j * per,
    (j + 1) * per), so transactions [batch_txn0[j], batch_txn0[j] +
    batch_txns[j])."""

    def __init__(self, r: dict, poh: dict):
        self.slot_txns = n = r["slot_txns"]
        self.tpe, self.per = r["txns_per_entry"], r["entries_per_batch"]
        self.ticks, self.hpt = poh["ticks_per_slot"], poh["hashes_per_tick"]
        groups = -(-n // self.tpe)
        per_tick = -(-groups // self.ticks)
        cnt = []                    # transactions an entry; 0 = a tick
        ticks = 0
        for k in range(groups):
            cnt.append(min(self.tpe, n - k * self.tpe))
            if (k + 1) % per_tick == 0 and ticks < self.ticks - 1:
                cnt.append(0)
                ticks += 1
        cnt += [0] * (self.ticks - ticks)
        self.entry_txns = np.asarray(cnt, dtype=np.int64)
        self.n_batches = -(-len(cnt) // self.per)
        pad = np.zeros(self.n_batches * self.per, dtype=np.int64)
        pad[:len(cnt)] = cnt
        self.batch_txns = pad.reshape(self.n_batches, self.per).sum(axis=1)
        self.batch_txn0 = np.cumsum(self.batch_txns) - self.batch_txns

    def batch_of(self, k: int) -> int:
        """The entry batch transaction k of a slot lies in."""
        return int(np.searchsorted(self.batch_txn0, k, side="right") - 1)


def slot_seed(gseed: bytes, slot: int) -> bytes:
    return hashlib.sha256(gseed + b"slot%d" % slot).digest()


def entry_bytes(num_hashes: int, h: bytes, txns: list[bytes]) -> bytes:
    return (num_hashes.to_bytes(4, "little") + h
            + len(txns).to_bytes(2, "little")
            + b"".join(len(p).to_bytes(2, "little") + p for p in txns))


def slot_frames(lay: Layout, gseed: bytes, slot: int,
                txns: list[bytes]) -> list[bytes]:
    """The frames of slot `slot` holding `txns` (all of its transactions,
    or the first of them where the run ended inside it: then the last
    frame is short and carries no LAST flag).  The blocker makes the
    same bytes as it goes."""
    seed = h = slot_seed(gseed, slot)
    frames, cur = [], []
    at = 0
    whole = len(txns) == lay.slot_txns
    for cnt in lay.entry_txns.tolist():
        if cnt:
            if at >= len(txns):
                break
            grp = txns[at:at + cnt]
            at += len(grp)
            h = hashlib.sha256(h + hashlib.sha256(
                b"".join(p[1:65] for p in grp)).digest()).digest()
            cur.append(entry_bytes(1, h, grp))
            if len(grp) < cnt:
                break           # the run ended inside this entry
        else:                   # a tick follows a whole entry at once
            for _ in range(lay.hpt):
                h = hashlib.sha256(h).digest()
            cur.append(entry_bytes(lay.hpt, h, []))
        if len(cur) == lay.per:
            frames.append(cur)
            cur = []
    if cur:
        frames.append(cur)
    out = []
    for j, ents in enumerate(frames):
        last = whole and j == len(frames) - 1
        flags = (F_LAST if last else 0) | (F_SEED if j == 0 else 0)
        out.append(HDR.pack(slot, j, flags) + (seed if j == 0 else b"")
                   + b"".join(len(e).to_bytes(4, "little") + e for e in ents))
    return out


class Blocker(Stage):
    """Cuts the offered transactions into entries, ticks, entry batches
    and slots as it takes them (module docstring), one frag an entry
    batch, `tsorig` its first transaction's.  Frames wait here for the
    ring's room (`OUT_QUEUE` of them at most: then the intake stops)."""

    def __init__(self, *args, layout: Layout, gseed: bytes, **kwargs):
        super().__init__(*args, **kwargs)
        if type(self.ins[0]).__name__ != "NativeConsumer":
            raise RuntimeError("the blocker needs the native ring lane")
        self.lay = layout
        self.gseed = gseed
        self.burst = GEN_DEPTH
        self.n_txn = 0              # transactions taken
        self.frames_made = 0
        self._q: deque = deque()    # (frame, sig, tsorig)
        self._pend: list[bytes] = []    # transactions of the open entry
        self._pend_ts = 0
        self._ents: list[bytes] = []    # entries of the open batch
        self._ents_ts = 0
        self._slot = 0
        self._entry = 0             # the next entry of the slot
        self._batch = 0             # the next batch of the slot
        self._h = self._seed = slot_seed(gseed, 0)
        self._carry: np.ndarray | None = None   # rows short of an entry
        self._carry_ts: list = []

    def before_credit(self) -> None:
        self.intake_room = None if len(self._q) < OUT_QUEUE else 0

    def after_credit(self) -> None:
        q = self._q
        p = self.outs[0]
        while q:
            f, sig, ts = q[0]
            if not p.try_publish(f, sig=sig, tsorig=ts):
                self.metrics.inc("backpressure")
                return
            self.metrics.inc("frags_out")
            q.popleft()

    # -- cutting --------------------------------------------------------------

    def _close_batch(self, last: bool) -> None:
        if not self._ents:
            return
        j = self._batch
        flags = (F_LAST if last else 0) | (F_SEED if j == 0 else 0)
        f = (HDR.pack(self._slot, j, flags) + (self._seed if j == 0 else b"")
             + b"".join(len(e).to_bytes(4, "little") + e
                        for e in self._ents))
        self._q.append((f, (self._slot & 0x7FFFFFFF) << 32 | j,
                        self._ents_ts))
        self.frames_made += 1
        self._ents = []
        self._batch += 1

    def _push_entry(self, e: bytes, ts: int) -> None:
        if not self._ents:
            self._ents_ts = ts
        self._ents.append(e)
        self._entry += 1
        end = self._entry == len(self.lay.entry_txns)
        if len(self._ents) == self.lay.per or end:
            self._close_batch(last=end)
        if end:
            self._slot += 1
            self._entry = self._batch = 0
            self._h = self._seed = slot_seed(self.gseed, self._slot)

    def _ticks_due(self, ts: int) -> None:
        lay = self.lay
        while lay.entry_txns[self._entry] == 0:
            h = self._h
            for _ in range(lay.hpt):
                h = hashlib.sha256(h).digest()
            self._h = h
            self._push_entry(lay.hpt.to_bytes(4, "little") + h + b"\0\0", ts)

    def _entry_of(self, sigs: bytes, body: bytes, cnt: int, ts: int) -> None:
        """One transaction entry: `sigs` its first signatures joined,
        `body` its (u16 len | txn) frames joined."""
        self._h = h = hashlib.sha256(
            self._h + hashlib.sha256(sigs).digest()).digest()
        self._push_entry(b"\x01\0\0\0" + h + cnt.to_bytes(2, "little") + body,
                         ts)
        self._ticks_due(ts)

    def sweep_frags(self, rows, buf: bytes):
        """A sweep's transactions.  Rows of one size lying back to back
        (the transfer shape's) are cut with numpy, an entry a Python
        step, what is left of a sweep carried to the next; anything
        else a transaction a step."""
        n = len(rows)
        ts_all = [r[5] for r in rows]
        self.n_txn += n
        sz = rows[0][3]
        lay = self.lay
        carry = self._carry
        if not self._pend and (carry is None or carry.shape[1] == sz) \
                and rows[-1][2] - rows[0][2] == (n - 1) * sz \
                and all(r[3] == sz for r in rows):
            arr = np.frombuffer(buf, dtype=np.uint8, count=n * sz,
                                offset=rows[0][2]).reshape(n, sz)
            ts = ts_all
            if carry is not None:
                arr = np.concatenate([carry, arr])
                ts = self._carry_ts + ts
            m = len(arr)
            framed = np.empty((m, sz + 2), dtype=np.uint8)
            framed[:, 0], framed[:, 1] = sz & 0xFF, sz >> 8
            framed[:, 2:] = arr
            sigs = np.ascontiguousarray(arr[:, 1:65])
            at = 0
            while True:
                cnt = int(lay.entry_txns[self._entry])
                if m - at < cnt:
                    break
                self._entry_of(sigs[at:at + cnt].tobytes(),
                               framed[at:at + cnt].tobytes(), cnt, ts[at])
                at += cnt
            self._carry = arr[at:].copy() if at < m else None
            self._carry_ts = ts[at:]
            return n, ts_all
        self._uncarry()
        for r, t in zip(rows, ts_all):
            self._take(buf[r[2]:r[2] + r[3]], t)
        return n, ts_all

    def _uncarry(self) -> None:
        """What the numpy lane carried, as transactions of the open
        entry."""
        carry, self._carry = self._carry, None
        if carry is not None:
            for row, t in zip(carry, self._carry_ts):
                self._take(row.tobytes(), t)

    def _take(self, p: bytes, ts: int) -> None:
        if not self._pend:
            self._pend_ts = ts
        self._pend.append(p)
        if len(self._pend) == self.lay.entry_txns[self._entry]:
            self._flush_entry()

    def _flush_entry(self) -> None:
        grp, self._pend = self._pend, []
        self._entry_of(
            b"".join(p[1:65] for p in grp),
            b"".join(len(p).to_bytes(2, "little") + p for p in grp),
            len(grp), self._pend_ts)

    def flush_tail(self) -> None:
        """The run ends inside a slot: what is open goes out short (an
        entry of fewer transactions than the layout's, then the batch,
        without the LAST flag)."""
        self._uncarry()
        if self._pend:
            grp, self._pend = self._pend, []
            self._h = h = hashlib.sha256(self._h + hashlib.sha256(
                b"".join(p[1:65] for p in grp)).digest()).digest()
            if not self._ents:
                self._ents_ts = self._pend_ts
            self._ents.append(entry_bytes(1, h, grp))
        self._close_batch(last=False)


class BlockSink(Sink):
    """The harness's sink, and per frag its size and crc32 (what came
    out is compared with the reference frame by frame without keeping
    20 s of blocks)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.sizes: list[int] = []
        self.crcs: list[int] = []
        self.verdicts: list[bytes] = []     # the verdict frames, whole

    def sweep_frags(self, rows, buf: bytes):
        mv = memoryview(buf)
        crc = zlib.crc32
        for r in rows:
            self.sizes.append(r[3])
            self.crcs.append(crc(mv[r[2]:r[2] + r[3]]))
            if r[1] >> 63:
                self.verdicts.append(buf[r[2]:r[2] + r[3]])
        return super().sweep_frags(rows, buf)


class System:
    def __init__(self, config: dict, gen_kw: dict, control: str | None,
                 genesis: dict):
        import jax

        from firedancer_tpu.utils.config import load_config

        cfg = load_config(None, overrides=config["program_config"])
        self.cfg = cfg
        self.batch = cfg.verify.batch
        pc = config["program_config"]
        self.lay = Layout(pc["replay"], pc["poh"])
        self.gseed = genesis["seed"]
        self.handle = None
        topo = leader_topo.build_replay_topology_from_config(
            cfg, verify_precomputed=(control == "allpass"),
            verify_cpu=jax.default_backend() != "tpu")
        topo.link("gb", depth=GEN_DEPTH, mtu=1232)
        self.handle = h = ft.launch(topo, held=HELD)
        try:
            self.gen = TrafficGen("gen", outs=[shm.make_producer(h.links["gb"])],
                                  max_burst=GEN_DEPTH, **gen_kw)
            self.blocker = Blocker(
                "replaysrc", ins=[shm.make_consumer(h.links["gb"], lazy=64)],
                outs=[shm.make_producer(h.links["rv"])],
                cnc=h.cncs["replaysrc"], layout=self.lay, gseed=self.gseed)
            h.hold(self.blocker)
            # the builder a child would run: select_device finds what
            # run.py selected, and the program is warm (prewarm)
            self.verify = h.build_held("verify0")
            self.sink = BlockSink(
                "replayout", ins=[shm.make_consumer(h.links["vo"], lazy=64)],
                cnc=h.cncs["replayout"], keep=KEEP_FRAMES)
            h.hold(self.sink)
            self.sink.name = "sink"     # the readers' name for it
        except BaseException:
            self.close()
            raise
        self.stages = [self.gen, self.blocker, self.verify, self.sink]
        self.host_stages: list[str] = []
        self._memo: tuple | None = None
        if not np.array_equal(self.gen.order, np.arange(self.gen.pool.n)):
            self.close()
            raise ValueError("the blocks' layout needs a shape that offers "
                             "its pool in order (offer k is row k % pool)")

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

    # -- what came out ---------------------------------------------------------

    def _out(self):
        """The sink's frags so far as arrays: (slot, idx, is a verdict,
        the verdict's reason, size, crc32, arrival, tsorig)."""
        n = self.sink.n
        if self._memo is not None and self._memo[0] == n:
            return self._memo[1]
        arr, tag, ts = self.sink.arrays()
        verdict = (tag >> np.uint64(63)).astype(bool)
        slot = ((tag >> np.uint64(32)) & np.uint64(0x7FFFFFFF)).astype(np.int64)
        idx = (tag & np.uint64(0xFFFFFFFF)).astype(np.int64)
        out = dict(slot=slot, idx=idx, verdict=verdict,
                   size=np.asarray(self.sink.sizes[:len(tag)], dtype=np.int64),
                   crc=np.asarray(self.sink.crcs[:len(tag)], dtype=np.int64),
                   arr=arr, ts=ts)
        self._memo = (n, out)
        return out

    def _first_bad(self, slot: int, n_pool: int, bad: np.ndarray):
        """The first offer of slot `slot` (counted from its start) that
        carries a corrupted row, or None: by the blocks alone (offer k
        carries pool row k % n_pool: System.__init__ holds the order to
        that)."""
        n = self.lay.slot_txns
        ks = (bad - slot * n) % n_pool      # each row's first offer in it
        ks = ks[ks < n]
        return int(ks.min()) if len(ks) else None

    def _batch_txns(self, slot, idx) -> np.ndarray:
        """Transactions in entry batch `idx` of `slot` (arrays): the
        layout's, or fewer in the short last batch the drain makes."""
        lay = self.lay
        idx = np.minimum(idx, lay.n_batches - 1)
        k0 = slot * lay.slot_txns + lay.batch_txn0[idx]
        return k0, np.minimum(lay.batch_txns[idx],
                              np.maximum(self.gen.i - k0, 0))

    def served(self) -> int:
        """Signatures of what left or was rejected, once the frame that
        says so is out: the transactions of the entry batches that
        left, and of the batch each dead verdict names (1-signature
        transfers: a transaction a signature)."""
        o = self._out()
        left = ~o["verdict"]
        n = int(self._batch_txns(o["slot"][left], o["idx"][left])[1].sum())
        rej = [(s, j) for s, j, why in self._verdicts() if why > 0]
        if rej:
            s, j = np.asarray(rej, dtype=np.int64).T
            n += int(self._batch_txns(s, j)[1].sum())
        return n

    def _verdicts(self) -> list[tuple[int, int, int]]:
        """(slot, idx, reason) of each verdict frame, in order; a frame
        of another size reads as reason -1."""
        out = []
        for f in self.sink.verdicts:
            if len(f) != HDR.size:
                out.append((-1, -1, -1))
                continue
            slot, idx, flags = HDR.unpack(f)
            out.append((slot, idx, (flags >> 8) & 0xFF
                        if flags & F_VERDICT else -1))
        return out

    def latencies_ns(self, t0: int, t1: int) -> np.ndarray:
        o = self._out()
        m = (o["arr"] >= t0) & (o["arr"] < t1) & (o["ts"] > 0) & ~o["verdict"]
        return o["arr"][m] - o["ts"][m]

    def _idle(self) -> bool:
        v = self.verify
        c = v._sweep_client
        if c is not None:
            return not (v._nv_inflight or v._nv_emit or c.stash_pending
                        or c.open_elems() or v.held())
        return not (v._inflight or v._submit_queue or v._emit_queue
                    or v._gen.elems or v.held())

    def drain(self, limit_s: float) -> bool:
        """Stop offering, let the blocker send what it has open (a short
        last batch), then run the stage until both rings in front are
        empty, nothing is held or in flight and the sink sees nothing
        more."""
        self.gen.limit = 0
        behind = self.stages[1:]
        t_end = time.monotonic() + limit_s
        tail = False
        while time.monotonic() < t_end:
            for _ in range(32):
                for s in behind:
                    s.run_once()
            if self.gen.outs[0].seq != self.blocker.ins[0].seq:
                continue
            if not tail:
                self.blocker.flush_tail()
                tail = True
            if self.blocker._q \
                    or self.blocker.outs[0].seq != self.verify.ins[0].seq:
                continue
            self.verify.flush()
            moved = [bool(self.sink.run_once()) for _ in range(8)]
            if not any(moved) and self._idle():
                return True
        return False

    # -- what the guarantees say -----------------------------------------------

    def _slots(self, n_offers: int, n_pool: int, bad: np.ndarray) -> list:
        """Per slot begun: (slot, offers of it made, the batch it dies
        at or None)."""
        n = self.lay.slot_txns
        out = []
        for s in range(-(-n_offers // n)):
            have = min(n, n_offers - s * n)
            k = self._first_bad(s, n_pool, bad)
            # a corrupted offer that was never made kills nothing
            out.append((s, have, self.lay.batch_of(k)
                        if k is not None and k < have else None))
        return out

    def due(self, offered: np.ndarray, valid: np.ndarray) -> dict:
        """Every offer of a live slot lands once; of a slot dead at
        entry batch j, the offers of the batches before j."""
        n_pool = len(valid)
        bad = np.flatnonzero(~valid)
        lay = self.lay
        land = np.zeros(n_pool, dtype=np.int64)
        fail = 0
        for s, have, at in self._slots(len(offered), n_pool, bad):
            upto = have if at is None else int(lay.batch_txn0[at])
            k0 = s * lay.slot_txns
            np.add.at(land, offered[k0:k0 + upto], 1)
            fail += at is not None
        self._due_fail = fail
        return {"landings": land, "verify_fail": fail, "duplicates": 0}

    def dedup_counted(self, c: dict) -> int:
        return c["verify0"].get("dedup_dup", 0)

    def landed(self):
        """-> (times each pool row landed: the offers of every entry
        batch that came out, by its slot and index; frags that match
        nothing: kept whole frames that are not the blocker's bytes)."""
        o = self._out()
        pool = self.gen.pool
        order = self.gen.order
        left = ~o["verdict"]
        k0, cnt = self._batch_txns(o["slot"][left], o["idx"][left])
        ks = np.repeat(k0, cnt) + (np.arange(int(cnt.sum()))
                                   - np.repeat(np.cumsum(cnt) - cnt, cnt))
        count = np.bincount(order[ks % len(order)], minlength=pool.n)
        return count, self._kept_off_blocks()

    def _frames_of(self, slot: int) -> list[bytes]:
        n = self.lay.slot_txns
        pool, order = self.gen.pool, self.gen.order
        have = min(n, self.gen.i - slot * n)
        ks = np.arange(slot * n, slot * n + have)
        return slot_frames(self.lay, self.gseed, slot,
                           [pool.row(int(r)) for r in order[ks % len(order)]])

    def _kept_off_blocks(self) -> int:
        """Kept whole frames that are not, byte for byte, the frame the
        blocker made for that slot and index (or a verdict frame)."""
        o = self._out()
        off = 0
        frames: dict[int, list[bytes]] = {}
        for i, f in enumerate(self.sink.kept):
            if o["verdict"][i]:
                off += len(f) != HDR.size
                continue
            s = int(o["slot"][i])
            if s not in frames:
                frames = {s: self._frames_of(s)}    # one slot at a time
            j = int(o["idx"][i])
            off += j >= len(frames[s]) or frames[s][j] != f
        return off

    def extra_checks(self) -> dict:
        o = self._out()
        v = self.counters()["verify0"]
        pool = self.gen.pool
        lay = self.lay
        slots = self._slots(self.gen.i, pool.n, pool.bad)
        whole = {s: at for s, have, at in slots if have == lay.slot_txns
                 or at is not None}
        # -- verdicts: one a finished slot, dead where the blocks say ----
        vi = np.flatnonzero(o["verdict"])
        got = {s: (why, j) for s, j, why in self._verdicts()}
        due_dead = {s: at for s, at in whole.items() if at is not None}
        dead_got = {s: ri for s, ri in got.items() if ri[0] != 0}
        off_batch = sum(1 for s, (r, j) in dead_got.items()
                        if due_dead.get(s) != j or r != 1)
        off_batch += sum(1 for s, (r, j) in got.items()
                         if r == 0 and (s in due_dead or j != lay.n_batches))
        off_batch += sum(1 for s in whole if s not in got) \
            + (len(vi) - len(got))
        # -- order: within a slot idx 0, 1, ...; nothing after its dead
        # verdict; slots ascending -------------------------------------------
        disorder = 0
        cur, nxt, closed = -1, 0, True
        for s, j, isv in zip(o["slot"].tolist(), o["idx"].tolist(),
                             o["verdict"].tolist()):
            if isv:
                if s != cur:    # dead at its first entry batch
                    disorder += s < cur or not closed and cur >= 0
                    cur = s
                closed = True
                continue
            if s != cur:
                disorder += s < cur or j != 0
                cur, nxt, closed = s, 0, False
            disorder += closed or j != nxt
            nxt = j + 1
        # -- the sample held to the plain reference --------------------------
        rng = np.random.default_rng([self._seed_of(), 0x51])
        done = sorted(whole)
        dead_first = [s for s in done if s in due_dead][:SAMPLE_SLOTS - 1]
        live = [s for s in done if s not in due_dead]
        take = SAMPLE_SLOTS - len(dead_first)
        sample = dead_first + ([int(x) for x in rng.choice(
            live, size=min(take, len(live)), replace=False)] if live else [])
        off_ref = 0
        by_slot: dict[int, list[int]] = {}
        for i, s in enumerate(o["slot"].tolist()):
            by_slot.setdefault(s, []).append(i)
        for s in sample:
            ref = replay_reference.replay(
                self._frames_of(s), max_msg_len=self.cfg.verify.max_msg_len)
            mine = by_slot.get(s, [])
            want = [(len(f), zlib.crc32(f)) for f in ref.out]
            have = [(int(o["size"][i]), int(o["crc"][i])) for i in mine]
            off_ref += want != have
            rs = ref.slots[0]
            if rs.verdict == "dead":
                off_batch += due_dead.get(s) != rs.at or rs.reason != "sig"
            else:
                off_batch += s in due_dead
        self._sample = sample
        left = int(v.get("entry_txn_out", 0))
        rej = int(v.get("entry_txn_rejected", 0))
        skip = int(v.get("dead_slot_txn_skipped", 0))
        served = self.served()
        return {
            "slots_dead_minus_due":
                (abs(len(dead_got) - len(due_dead)), 0),
            "dead_at_batch_off_reference": (int(off_batch), 0),
            "entry_batches_out_of_order": (int(disorder), 0),
            "entry_batches_off_plain_reference": (int(off_ref), 0),
            "offered_minus_left_rejected_skipped":
                (abs(self.gen.i - left - rej - skip), 0),
            "lanes_minus_served_minus_dead_slot_lanes_spent":
                (abs(int(v.get("elems_in", 0)) - served
                     - int(v.get("dead_slot_lanes_spent", 0))), 0),
            "blocker_txns_minus_offered":
                (abs(self.blocker.n_txn - self.gen.i), 0),
        }

    def _seed_of(self) -> int:
        return int.from_bytes(hashlib.sha256(self.gseed).digest()[:4],
                              "little")

    def dropped(self, c: dict) -> int:
        v = c["verify0"]
        return v.get("emit_dropped", 0) + v.get("intake_dropped", 0)

    def notes(self) -> dict:
        v = self.counters()["verify0"]
        keys = ("entry_batches_in", "entries_in", "txn_in", "elems_in",
                "slots_live", "slots_dead_sig", "slots_dead_poh",
                "slots_dead_parse", "dead_slot_txn_skipped",
                "dead_slot_lanes_spent", "poh_hashes", "poh_check_ns",
                "entry_unpack_ns", "entry_batches_out", "entry_txn_out",
                "entry_txn_rejected", "batches", "batch_elems",
                "batch_close_full", "batch_close_deadline",
                "batch_close_window", "batch_queued_behind",
                "batch_held_backlogged", "batch_fit_pad_lanes",
                "sweep_busy_ns", "sweep_crossings")
        n = max(int(v.get("batches", 0)), 1)
        return {
            "replay": {k: int(v.get(k, 0)) for k in keys},
            "verify_inflight_ms_per_batch":
                v.get("batch_inflight_ns", 0) / n / 1e6,
            "blocker": {"txns": self.blocker.n_txn,
                        "frames": self.blocker.frames_made,
                        "batches_a_slot": self.lay.n_batches},
            "sampled_slots": getattr(self, "_sample", []),
        }

    def close(self) -> None:
        h, self.handle = self.handle, None
        for s in getattr(self, "stages", []):
            s.ins = []
            s.outs = []
            s.drop_native_views()
        import gc

        gc.collect()
        if h is not None:
            h.close()


def prewarm(config: dict, control: str | None) -> float:
    v = config["program_config"]["verify"]
    return prewarm_verify(v["batch"], v["max_msg_len"], control)
