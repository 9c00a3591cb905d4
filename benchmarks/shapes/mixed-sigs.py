"""The `mixed-sigs` shape: what a verify tile with a dedup tile behind
it is sent when transactions carry one to eight signatures (BASELINE.json
configs[2]: "mixed 1-8 sigs/txn with fd_dedup downstream").  A row is a
legacy system transfer signed by k accounts, the payer and k - 1
read-only co-signers, all distinct; k is drawn with P(k) proportional to
1/k, so most transactions have one or two signers, the mean is 2.94, and
each count carries an equal eighth of the signatures; a tenth of all
offers repeat a row offered before, half near (inside the verify tile's
16-deep tag cache), half far (past it, inside the dedup tile's 65,536).
Pure functions of the seed; `benchmarks/configs/verify-dedup-v5e.json`
lists what is assumed.

Imports neither JAX nor the program: the signing workers load this file
alone.
"""

from __future__ import annotations

import hashlib

import numpy as np

from harness import traffic as T

CLASSES = ("transfer",)
MAX_K = T.MAX_SIGS                 # 8
# P(k) proportional to 1/k over 1..8
P_K = (1.0 / np.arange(1, MAX_K + 1)) / (1.0 / np.arange(1, MAX_K + 1)).sum()
MSG_BASE, MSG_PER_SIG = 118, 32    # message bytes: 118 + 32 k
ROW_BASE, ROW_PER_SIG = 119, 96    # row bytes: 119 + 96 k

REPEAT_SHARE = 0.10       # of offers (leader-mainnet-v5e's, so the two agree)
NEAR = (1, 8)             # offers behind: inside verify's 16-deep tag cache
FAR = (1024, 32768)       # past it, inside dedup's 65,536 tags

SYSTEM_PROGRAM = bytes(32)
BLOCK = 4096              # rows that share one generator (a worker's range)


def destinations(gseed: bytes, n: int) -> list[bytes]:
    return [hashlib.sha256(gseed + b"to%d" % k).digest() for k in range(n)]


def plan(seed: int, accounts: dict, lo: int, hi: int) -> dict:
    """Per row of [lo, hi): its signature count k, its signers (k
    distinct keys of `n_payers`, the payer first; columns past k are
    not used) and its destination.  Rows come in blocks of BLOCK that
    each hang off the seed and the block's number alone, so a range is
    the same rows whatever pool it is cut from."""
    n_keys, n_dests = accounts["n_payers"], accounts["n_dests"]
    cdf = np.cumsum(P_K)
    ks, signers, dests = [], [], []
    for b in range(lo // BLOCK, (max(hi, lo + 1) - 1) // BLOCK + 1):
        rng = np.random.default_rng([seed, 0x51C5, b])
        k = np.minimum(np.searchsorted(cdf, rng.random(BLOCK)), MAX_K - 1) + 1
        who = rng.integers(0, n_keys, size=(BLOCK, MAX_K))
        dest = rng.integers(0, n_dests, size=BLOCK)
        used = np.arange(MAX_K)[None, :] < k[:, None]
        while True:     # draw again the rows whose signers are not distinct
            srt = np.sort(np.where(used, who, -1 - np.arange(MAX_K)), axis=1)
            again = np.flatnonzero((srt[:, 1:] == srt[:, :-1]).any(axis=1))
            if not again.size:
                break
            who[again] = rng.integers(0, n_keys, size=(again.size, MAX_K))
        a, z = max(lo - b * BLOCK, 0), min(hi - b * BLOCK, BLOCK)
        ks.append(k[a:z])
        signers.append(who[a:z])
        dests.append(dest[a:z])
    return {"k": np.concatenate(ks), "signers": np.concatenate(signers),
            "dest": np.concatenate(dests)}


def message(signers: list[bytes], dest: bytes, blockhash: bytes,
            lamports: int) -> bytes:
    """A system transfer from `signers[0]` to `dest`, co-signed
    read-only by the rest: 118 + 32 k bytes."""
    k = len(signers)
    return (bytes([k, k - 1, 1, k + 2]) + b"".join(signers) + dest
            + SYSTEM_PROGRAM + blockhash
            + bytes([1, k + 1, 2, 0, k, 12]) + (2).to_bytes(4, "little")
            + lamports.to_bytes(8, "little"))


def build(seed: int, n_rows: int, accounts: dict, traffic: dict,
          lo: int = 0, hi: int | None = None) -> T.Pool:
    """Rows [lo, hi) of the pool.  Transfer i moves 1 + i lamports, so
    every row of a pool is distinct."""
    hi = n_rows if hi is None else hi
    gseed = T.genesis_seed(seed)
    pl = plan(seed, accounts, lo, hi)
    bh = T.blockhash(gseed)
    keys = T.signers(gseed, accounts["n_payers"])
    dests = destinations(gseed, accounts["n_dests"])
    rows = []
    for j, i in enumerate(range(lo, hi)):
        who = [keys[s] for s in pl["signers"][j, :pl["k"][j]].tolist()]
        msg = message([pub for _, pub in who], dests[int(pl["dest"][j])],
                      bh, 1 + i)
        rows.append(bytes([len(who)])
                    + b"".join(key.sign(msg) for key, _ in who) + msg)
    return T.join(rows, pl["k"], np.zeros(hi - lo, np.uint8), CLASSES)


def corrupt(pool: T.Pool, every: int, seed: int) -> np.ndarray:
    """Flip one seeded bit in one of the k signatures, chosen uniformly,
    of one seeded row in each run of `every`: the row fails whole.  In
    place; -> sorted bad rows."""
    if not every:
        return np.zeros((0,), dtype=np.int64)
    rng = np.random.default_rng([seed, 0xBAD])
    starts = np.arange(0, pool.n - every + 1, every, dtype=np.int64)
    bad = starts + rng.integers(0, every, size=starts.size)
    T.flip(pool, bad, rng.integers(0, pool.sigs[bad]),
           rng.integers(0, 64, size=bad.size),
           rng.integers(0, 8, size=bad.size))
    return bad


def order(pool: T.Pool, seed: int, traffic: dict) -> np.ndarray:
    """The pool in order, with REPEAT_SHARE of the offers repeats of a
    row offered before: half NEAR offers behind it, half FAR; corrupted
    rows repeat like any other.  (Where the offer that far behind is
    itself a repeat, the row is the one first offered just before that,
    a few offers further.)"""
    n = pool.n
    rng = np.random.default_rng([seed, 0x0DD])
    n_rep = int(round(n * REPEAT_SHARE / (1.0 - REPEAT_SHARE)))
    total = n + n_rep
    at = np.sort(rng.choice(np.arange(1, total), size=n_rep, replace=False))
    fresh = np.ones(total, dtype=bool)
    fresh[at] = False
    row = np.cumsum(fresh) - 1                  # a fresh offer's pool row
    # the last fresh offer at or before each offer
    last = np.maximum.accumulate(np.where(fresh, np.arange(total), 0))
    near = rng.random(n_rep) < 0.5
    gap = np.where(near, rng.integers(NEAR[0], NEAR[1] + 1, size=n_rep),
                   rng.integers(FAR[0], FAR[1] + 1, size=n_rep))
    row[at] = row[last[np.maximum(at - gap, 0)]]
    return row.astype(np.int64)


def genesis(accounts: dict, seed: int) -> dict:
    """What has to exist before traffic: the funded signers (the
    arguments the program's `default_bank_ctx` takes; a verify tile
    reads none of it)."""
    return {"seed": T.genesis_seed(seed), "n_payers": accounts["n_payers"]}
