"""The benchmark's ends of the pipeline: the traffic generator, the sink
behind the verify tile, and the tap on the banks' commit rings.

The generator is a `Stage` (the program's loop drives it like any
other), but what it offers and when is decided here, from the cell's
traffic file: `flood` publishes whenever the ring has room, `paced`
makes offer k once its due time has come and stamps `tsorig` with the
due time, so latency downstream counts from when it was due.  Which row
an offer carries is the shape's (`order`).
"""

from __future__ import annotations

import numpy as np

from firedancer_tpu.runtime.stage import Stage
from firedancer_tpu.tango.shm import now_ns


class TrafficGen(Stage):
    """Offers what the shape says: offer k is pool row
    `order[k % len(order)]`, with that row's own offset and length.
    `wrap` lets the order come round again (a fixed pool, replayed);
    otherwise reaching its end sets `exhausted` and stops, which makes
    the run incorrect: an order never wraps silently."""

    def __init__(self, *args, pool, order: np.ndarray,
                 due_ns: np.ndarray | None, wrap: bool,
                 max_burst: int, **kwargs):
        super().__init__(*args, **kwargs)
        self.pool = pool
        self.order = order
        self.due_rel = due_ns            # None = flood
        self.due = None                  # absolute, set by start()
        self.limit = None                # LeaderPipeline.finish sets 0
        self.max_burst = max_burst
        self.i = 0                       # offers made: the next is offer i
        # how many offers this generator can ever make
        self.cap = 1 << 62 if wrap else len(order)
        if due_ns is not None:
            self.cap = min(self.cap, len(due_ns))
        self.exhausted = False
        self.late: list = []             # (first index, now - due) chunks
        # one lap of offers as the ring's burst table: offset, size, and
        # two columns the burst fills in (the offer's index, its tsorig)
        lap = np.zeros((len(order), 4), dtype=np.uint64)
        lap[:, 0] = pool.off[order]
        lap[:, 1] = pool.len[order]
        self._lap = lap
        self._pool_ptr = pool.buf.ctypes.data
        # one ring crossing per burst, straight from the pool's memory;
        # the python ring lane has no such call and is not measured
        self._raw = getattr(self.outs[0], "publish_burst_raw", None)
        if self._raw is None:
            raise RuntimeError("the generator needs the native ring lane "
                               "(native/fd_ring.so did not build or load)")

    def start(self, t0_ns: int) -> None:
        if self.due_rel is not None:
            self.due = self.due_rel + t0_ns

    def after_credit(self) -> None:
        if self.limit == 0 or self.exhausted:
            return
        i = self.i
        cap = self.cap
        now = now_ns()
        if self.due is not None:
            j = int(np.searchsorted(self.due, now, side="right"))
            n = min(j, cap) - i
        else:
            n = cap - i
        n = min(n, self.max_burst)
        if n <= 0:
            if i >= cap:
                self.exhausted = True
            return
        p = self.outs[0]
        if p.cr_avail < n:
            p.refresh_credits()
            n = min(n, p.cr_avail)
            if n <= 0:
                self.metrics.inc("backpressure")
                return
        idx = np.arange(i, i + n, dtype=np.int64)
        rows = self._lap[idx % len(self._lap)]
        rows[:, 2] = idx.astype(np.uint64)
        if self.due is not None:
            due = self.due[i:i + n]
            rows[:, 3] = due.astype(np.uint64)
        else:
            rows[:, 3] = now
        done = self._raw(self._pool_ptr, rows, n)
        if done:
            self.metrics.inc("txn_gen", done)
            self.metrics.inc("frags_out", done)
            if self.due is not None:
                self.late.append((i, now - due[:done]))
            self.i = i + done

    def late_ns(self, lo: int, hi: int) -> np.ndarray:
        """publish time - due time for transactions [lo, hi)."""
        out = [a[max(lo - i0, 0):max(hi - i0, 0)] for i0, a in self.late
               if i0 < hi and i0 + len(a) > lo]
        return np.concatenate(out) if out else np.zeros((0,), np.int64)


    def longest_pause(self, lo: int, hi: int) -> tuple[int, int]:
        """-> (ns, publish time) of the longest pause between two bursts
        of transactions [lo, hi): where the sweep loop stalled, if it did."""
        t = [int(self.due[i0]) + int(a[0]) for i0, a in self.late
             if lo <= i0 < hi and len(a)]
        if len(t) < 2:
            return 0, 0
        k = int(np.argmax(np.diff(t)))
        return t[k + 1] - t[k], t[k]


class Sink(Stage):
    """Consumes the verify tile's output ring.  Per sweep it keeps the
    arrival time, and per frag the sig tag and `tsorig`; the first
    `keep` frags are kept whole for the byte-for-byte comparison."""

    def __init__(self, *args, keep: int = 0, **kwargs):
        super().__init__(*args, **kwargs)
        if type(self.ins[0]).__name__ != "NativeConsumer":
            raise RuntimeError("the sink needs the native ring lane")
        self.burst = 1024
        self.n = 0
        self.chunks: list = []     # (arrival ns, [tags], [tsorigs])
        self.keep = keep
        self.kept: list[bytes] = []
        self._arrays = (0, None)   # (chunks covered, arrays) memo

    def sweep_frags(self, rows, buf: bytes):
        now = now_ns()
        ts = [r[5] for r in rows]
        self.chunks.append((now, [r[1] for r in rows], ts))
        self.n += len(rows)
        if len(self.kept) < self.keep:
            for r in rows:
                self.kept.append(buf[r[2]:r[2] + r[3]])
        return len(rows), ts

    def arrays(self):
        """-> (arrival ns, tag, tsorig) per frag, as three arrays."""
        if self._arrays[0] == len(self.chunks) and self._arrays[1]:
            return self._arrays[1]
        if not self.chunks:
            z = np.zeros((0,), dtype=np.int64)
            return z, z.astype(np.uint64), z
        arr = np.concatenate([np.full(len(t), now, dtype=np.int64)
                              for now, t, _ in self.chunks])
        tag = np.concatenate([np.asarray(t, dtype=np.uint64)
                              for _, t, _ in self.chunks])
        ts = np.concatenate([np.asarray(s, dtype=np.uint64).astype(np.int64)
                             for _, _, s in self.chunks])
        self._arrays = (len(self.chunks), (arr, tag, ts))
        return arr, tag, ts


class CommitTap:
    """Reads the bank -> poh rings beside their consumer, without an
    fseq of its own (it never gates the producer): one sample per
    committed microblock, taken on the benchmark's clock when the
    harness next looks (once a sweep).  A microblock's `tsorig` is its
    oldest transaction's, so each of its `cnt` transactions is charged
    the whole wait.  Bank frame: 32B mixin | u16 txn_cnt | ..."""

    name = "tap"   # a member of the sweep: looked at once per sweep

    def __init__(self, links):
        self.links = list(links)
        self.seq = [0] * len(self.links)
        self.overrun = 0
        self.n_txn = 0
        self.t: list[int] = []
        self.lat: list[int] = []
        self.cnt: list[int] = []

    def run_once(self) -> bool:
        now = 0
        for k, link in enumerate(self.links):
            mc = link.mcache
            while True:
                status, meta = mc.query(self.seq[k])
                if status < 0:
                    break
                if status > 0:      # lapped: resync at the frontier
                    self.overrun += 1
                    self.seq[k] = int(mc.table[mc.line(self.seq[k]), 0]) \
                        & ~mc.BUSY
                    continue
                if not now:
                    now = now_ns()
                hdr = link.dcache.read(int(meta[2]), 34)
                cnt = int.from_bytes(hdr[32:34], "little")
                ts = int(meta[5])
                self.seq[k] += 1
                self.n_txn += cnt
                self.t.append(now)
                self.lat.append(now - ts if ts else -1)
                self.cnt.append(cnt)
        return False

    def window(self, t0: int, t1: int):
        """-> (latency ns repeated per transaction, transactions) of the
        microblocks seen in [t0, t1)."""
        t = np.asarray(self.t, dtype=np.int64)
        m = (t >= t0) & (t < t1)
        lat = np.asarray(self.lat, dtype=np.int64)[m]
        cnt = np.asarray(self.cnt, dtype=np.int64)[m]
        ok = lat >= 0
        return np.repeat(lat[ok], cnt[ok]), int(cnt.sum())


def prewarm_verify(batch: int, max_msg_len: int, control: str | None) -> float:
    """Compile (or load from the persistent cache) the verify stage's
    program at its dispatch shape BEFORE the system is built: the slot
    clock anchors when the pipeline is built, and a 40 s trace-and-lower
    after that would open the run a hundred slots late.  A ringless
    stage makes the same call the served stage makes; the served stage's
    own warmup() afterwards is a jit-cache hit.  -> seconds."""
    from firedancer_tpu.runtime.verify import VerifyStage

    if control == "allpass":
        return 0.0
    return VerifyStage("prewarm", batch=batch, max_msg_len=max_msg_len,
                       native_client=False).warmup()
