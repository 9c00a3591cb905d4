"""Single-producer shared-memory message rings (the tango layer).

Clean-room re-implementation of the reference's inter-stage messaging
concepts (/root/reference/src/tango/fd_tango_base.h:4-90):

  - 64-bit global fragment sequence numbers with *signed wraparound*
    comparison (fd_seq_diff), so rings run forever;
  - MCache: power-of-2 depth ring of fragment metadata, single producer,
    many consumers; consumers are never waited on — a slow consumer detects
    the sequence gap (overrun) and resynchronizes (fd_mcache.h:15-38);
  - DCache: payload bytes addressed by chunk, written compactly ahead of the
    matching mcache publish (fd_dcache_compact_next);
  - Fseq: a consumer's published progress sequence, read lazily by the
    producer for credit-based flow control toward *reliable* consumers
    (fd_fseq.h, fd_fctl.h);
  - TCache: ring+set cache of recently seen 64-bit tags for dedup
    (fd_tcache.h: oldest tag evicted on insert);
  - Cnc: out-of-band command-and-control cell with heartbeat (fd_cnc.h).

All state lives in plain numpy arrays over an optional buffer, so the same
code runs in-process (tests) or over `multiprocessing.shared_memory` blocks
(the multi-process topology runner).  The publish protocol orders writes
(payload, then meta fields, then the seq word last) so that a reader
re-checking the seq word after copying observes torn frags as overruns —
the reference's speculative-read discipline (fd_mux.c during_frag).
"""

from __future__ import annotations

import numpy as np

U64 = np.uint64
_MASK64 = (1 << 64) - 1


def seq_diff(a: int, b: int) -> int:
    """Signed distance a-b in 64-bit sequence space (fd_seq_diff)."""
    d = (int(a) - int(b)) & _MASK64
    return d - (1 << 64) if d >= (1 << 63) else d


# Control bits in frag meta (fd_tango_base.h SOM/EOM/ERR).
CTL_SOM = 1 << 0
CTL_EOM = 1 << 1
CTL_ERR = 1 << 2


class MCache:
    """Metadata ring: depth rows of (seq, sig, chunk, sz, ctl, tsorig, tspub).

    Single producer.  Row layout is a (depth, 7) uint64 array for simple,
    atomic-enough numpy stores; the seq word (column 0) is written last on
    publish and checked first/last on read.
    """

    NCOL = 7
    COL_SEQ, COL_SIG, COL_CHUNK, COL_SZ, COL_CTL, COL_TSORIG, COL_TSPUB = range(7)

    # Reserved "row being overwritten" bit in the stored seq word.  No
    # consumer ever polls a seq with this bit set (seqs are < 2^63 for the
    # lifetime of any real deployment), so a busy row can never satisfy a
    # reader's d==0 match — closing the ABA window where the previous lap's
    # frag at this line (seq - depth) could be consumed torn.
    BUSY = 1 << 63

    def __init__(self, depth: int, buf: np.ndarray | None = None):
        if depth & (depth - 1) or depth <= 0:
            raise ValueError("depth must be a power of 2")
        self.depth = depth
        if buf is None:
            buf = np.zeros(depth * self.NCOL, dtype=U64)
        self.table = buf.reshape(depth, self.NCOL)
        if not self.table.flags.writeable:
            raise ValueError("mcache buffer must be writable")
        # Initialize each line as busy-at-its-own-first-seq: a consumer
        # polling seq k (any lap) sees "not yet published".
        for line in range(depth):
            self.table[line, self.COL_SEQ] = self.BUSY | line

    @classmethod
    def footprint(cls, depth: int) -> int:
        return depth * cls.NCOL * 8

    def line(self, seq: int) -> int:
        return int(seq) & (self.depth - 1)

    def publish(
        self,
        seq: int,
        sig: int = 0,
        chunk: int = 0,
        sz: int = 0,
        ctl: int = CTL_SOM | CTL_EOM,
        tsorig: int = 0,
        tspub: int = 0,
    ) -> None:
        row = self.table[self.line(seq)]
        # Mark line in-progress with the BUSY bit set: a value no consumer
        # can match (they poll seqs < 2^63), unlike the previous lap's seq
        # (seq - depth) which a lagging consumer could legitimately poll.
        row[self.COL_SEQ] = self.BUSY | (int(seq) & _MASK64)
        row[self.COL_SIG] = int(sig) & _MASK64
        row[self.COL_CHUNK] = int(chunk) & _MASK64
        row[self.COL_SZ] = int(sz) & _MASK64
        row[self.COL_CTL] = int(ctl) & _MASK64
        row[self.COL_TSORIG] = int(tsorig) & _MASK64
        row[self.COL_TSPUB] = int(tspub) & _MASK64
        row[self.COL_SEQ] = int(seq) & _MASK64  # publish: seq word last

    def query(self, seq: int):
        """Poll for frag `seq`.

        Returns (status, meta): status 0 = available (meta = row copy),
        -1 = not yet published (caught up), +1 = overrun (consumer too slow).
        """
        row = self.table[self.line(seq)]
        mseq = int(row[self.COL_SEQ])
        if mseq & self.BUSY:
            # Row is mid-overwrite with frag `mseq & ~BUSY`: if that frag is
            # newer than what we want, ours is gone (overrun); otherwise
            # (it IS ours, still being written) not yet published.
            d = seq_diff(mseq & ~self.BUSY, seq)
            return (1, None) if d > 0 else (-1, None)
        d = seq_diff(mseq, seq)
        if d == 0:
            meta = row.copy()
            # Re-check: the producer may have started overwriting mid-copy.
            if int(row[self.COL_SEQ]) != int(seq) & _MASK64:
                return 1, None
            return 0, meta
        return (-1, None) if d < 0 else (1, None)

    def recover(self) -> tuple[int, int, set[int]]:
        """Reconstruct the producer's cursor state from the ring alone —
        the in-place-restart path (a respawned stage reattaching to its
        EXISTING shm ring must resume at its pre-crash frontier, not at
        seq 0).

        Returns (frontier_seq, next_chunk, published_sigs):
          - frontier_seq: the next seq to publish.  The producer writes
            sequentially and flips each row's seq word last, so the
            newest row WITHOUT the BUSY bit is the last completed
            publish; a row caught mid-overwrite (BUSY set with a real
            seq) was never visible to any consumer and is simply
            re-published.  All-BUSY-initial (never published) -> 0.
          - next_chunk: the dcache cursor after the frontier frag, so a
            resumed producer cannot overwrite payloads of in-flight
            frags (DCache.alloc arithmetic, CHUNK_SZ granules).
          - published_sigs: the sig of every completed row — the replay
            window's dedup set (exactly-once resume requires sigs unique
            within a ring depth, which every pipeline link provides).
        """
        best = None  # (seq, chunk, sz)
        sigs: set[int] = set()
        for line in range(self.depth):
            row = self.table[line]
            mseq = int(row[self.COL_SEQ])
            if mseq & self.BUSY:
                continue  # initial, or mid-overwrite (never published)
            sigs.add(int(row[self.COL_SIG]))
            if best is None or seq_diff(mseq, best[0]) > 0:
                best = (mseq, int(row[self.COL_CHUNK]),
                        int(row[self.COL_SZ]))
        if best is None:
            return 0, 0, sigs
        frontier = (best[0] + 1) & _MASK64
        next_chunk = best[1] + (-(-max(best[2], 1) // DCache.CHUNK_SZ))
        return frontier, next_chunk, sigs


class DCache:
    """Compact payload ring paired with an mcache (fd_dcache).

    Chunk addressing: offsets in CHUNK_SZ (64-byte) granules, like the
    reference's chunk/wmark scheme.  `alloc` returns the chunk index for the
    next payload of size <= mtu and advances compactly, wrapping to 0 when
    the write would pass the watermark.
    """

    CHUNK_SZ = 64

    def __init__(self, mtu: int, depth: int, buf: np.ndarray | None = None):
        self.mtu = mtu
        chunk_mtu = -(-mtu // self.CHUNK_SZ)
        data_sz = (depth + 2) * chunk_mtu * self.CHUNK_SZ * 2
        if buf is None:
            buf = np.zeros(data_sz, dtype=np.uint8)
        self.data = buf
        self.wmark = (len(self.data) - chunk_mtu * self.CHUNK_SZ) // self.CHUNK_SZ
        self._chunk = 0

    @classmethod
    def footprint(cls, mtu: int, depth: int) -> int:
        chunk_mtu = -(-mtu // cls.CHUNK_SZ)
        return (depth + 2) * chunk_mtu * cls.CHUNK_SZ * 2

    def alloc(self, sz: int) -> int:
        """Chunk index to write the next sz-byte payload at."""
        if sz > self.mtu:
            raise ValueError("payload exceeds mtu")
        chunk = self._chunk
        if chunk > self.wmark:
            chunk = 0
        self._chunk = chunk + (-(-max(sz, 1) // self.CHUNK_SZ))
        return chunk

    def write(self, chunk: int, payload: bytes) -> None:
        o = chunk * self.CHUNK_SZ
        self.data[o : o + len(payload)] = np.frombuffer(payload, dtype=np.uint8)

    def read(self, chunk: int, sz: int) -> bytes:
        o = chunk * self.CHUNK_SZ
        return self.data[o : o + sz].tobytes()


class Fseq:
    """A consumer's published progress sequence (single u64 cell)."""

    def __init__(self, buf: np.ndarray | None = None):
        self.cell = buf if buf is not None else np.zeros(1, dtype=U64)

    @classmethod
    def footprint(cls) -> int:
        return 8

    def publish(self, seq: int) -> None:
        self.cell[0] = int(seq) & _MASK64

    def query(self) -> int:
        return int(self.cell[0])


class FlowControl:
    """Producer-side credit accounting over reliable consumers' fseqs.

    cr_avail = cr_max - max(seq - fseq_i): how many frags the producer can
    publish before the slowest *reliable* consumer would be overrun
    (fd_fctl.h).  Unreliable consumers are not consulted — they take
    overruns instead of exerting backpressure.
    """

    def __init__(self, depth: int, fseqs: list[Fseq], cr_max: int | None = None):
        self.cr_max = cr_max if cr_max is not None else depth
        self.fseqs = fseqs

    def credits(self, seq: int) -> int:
        if not self.fseqs:
            return self.cr_max
        lag = max(seq_diff(seq, f.query()) for f in self.fseqs)
        return max(self.cr_max - max(lag, 0), 0)


class TCache:
    """Dedup cache of recently seen 64-bit tags (fd_tcache.h).

    Ring of the last `depth` tags + a set for O(1) membership; inserting a
    fresh tag evicts the oldest.  The reference reserves tag 0 as null —
    same here (tag 0 never dedups).
    """

    def __init__(self, depth: int):
        self.depth = depth
        self.ring = np.zeros(depth, dtype=U64)
        self.oldest = 0
        self.map: set[int] = set()

    def query(self, tag: int) -> bool:
        """True if tag was seen recently (a duplicate)."""
        return tag != 0 and (tag & _MASK64) in self.map

    def insert(self, tag: int) -> bool:
        """Insert tag; returns True if it was already present (duplicate)."""
        tag &= _MASK64
        if tag == 0:
            return False
        if tag in self.map:
            return True
        old = int(self.ring[self.oldest])
        if old:
            self.map.discard(old)
        self.ring[self.oldest] = tag
        self.oldest = (self.oldest + 1) % self.depth
        self.map.add(tag)
        return False


# Cnc signal values (fd_cnc.h state machine).
CNC_SIG_BOOT = 0
CNC_SIG_RUN = 1
CNC_SIG_HALT = 2
CNC_SIG_FAIL = 3
# the supervisor asks a running stage to put its counters out to shm
# now (Stage.run does, and answers RUN): TopologyHandle.counters
CNC_SIG_SYNC = 4


class Cnc:
    """Command-and-control cell: (signal, heartbeat) + diagnostics words."""

    NDIAG = 6

    def __init__(self, buf: np.ndarray | None = None):
        self.cells = buf if buf is not None else np.zeros(2 + self.NDIAG, dtype=U64)

    @classmethod
    def footprint(cls) -> int:
        return (2 + cls.NDIAG) * 8

    @property
    def signal(self) -> int:
        return int(self.cells[0])

    @signal.setter
    def signal(self, v: int) -> None:
        self.cells[0] = v

    def heartbeat(self, now: int) -> None:
        self.cells[1] = int(now) & _MASK64

    @property
    def last_heartbeat(self) -> int:
        return int(self.cells[1])

    def diag(self, idx: int) -> int:
        return int(self.cells[2 + idx])

    def diag_set(self, idx: int, v: int) -> None:
        self.cells[2 + idx] = int(v) & _MASK64
