"""What came out of the timed path, found in the pool by what it is: a
landed transaction is the pool row whose first signature it carries,
and then every byte of it has to be that row's."""

from __future__ import annotations

import numpy as np


class RowMap:
    def __init__(self, pool):
        self.pool = pool
        tag = pool.first_sig_tags()
        self._by_tag = np.argsort(tag, kind="stable")
        self._tags = tag[self._by_tag]

    def of_tags(self, tags: np.ndarray) -> np.ndarray:
        """uint64 tags (a frag's sig field downstream of verify) -> the
        pool row each belongs to, -1 where none does."""
        tags = np.asarray(tags, dtype=np.uint64)
        pos = np.minimum(np.searchsorted(self._tags, tags), len(self._tags) - 1)
        return np.where(self._tags[pos] == tags, self._by_tag[pos], -1)

    def of_payloads(self, payloads: list[bytes]) -> np.ndarray:
        """Landed payloads -> the pool row each equals byte for byte, -1
        where it equals none."""
        pool = self.pool
        ln = np.fromiter(map(len, payloads), dtype=np.int64,
                         count=len(payloads))
        rows = np.full(len(payloads), -1, dtype=np.int64)
        for sz in np.unique(ln[ln >= 9]):    # rows of one size together
            sel = np.flatnonzero(ln == sz)
            got = np.frombuffer(b"".join([payloads[k] for k in sel]),
                                dtype=np.uint8).reshape(len(sel), sz)
            tag = np.ascontiguousarray(got[:, 1:9]).view("<u8").ravel()
            cand = self.of_tags(np.where(tag == 0, np.uint64(1), tag))
            ok = cand >= 0
            ok[ok] = pool.len[cand[ok]] == sz
            at = np.lib.stride_tricks.sliding_window_view(pool.buf, sz)
            ok[ok] = (at[pool.off[cand[ok]]] == got[ok]).all(axis=1)
            rows[sel[ok]] = cand[ok]
        return rows
