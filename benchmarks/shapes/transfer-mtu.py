"""The `transfer-mtu` shape: a legacy 1-signature transaction of exactly
1,232 wire bytes, the most a transaction may be (FD_TPU_MTU) — the
system transfer of shapes/transfer.py plus one Memo-program instruction
whose seeded data pads it to the bound.  Every row distinct, offered in
pool order.  Pure functions of the seed.

    1        compact signature count
    64       signature
    3        header: 1 signer, 0 read-only signed, 2 read-only unsigned
    1 + 128  four account keys: payer, destination, the System
             program, the Memo program
    32       recent blockhash
    1        two instructions
    17       System transfer: program 2, accounts [0, 1], 12 data bytes
    4 + 981  Memo: program 3, no accounts, compact-u16 length, data

Payers, destinations, blockhash and lamports are `transfer`'s; the memo
is 981 seeded lowercase hex characters (the Memo program takes UTF-8).
`corrupt`, `order` and `genesis` are `transfer`'s own.  Imports neither
JAX nor the program: the signing workers load this file alone.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

from harness import traffic as T
from harness.manifest import load_module

_transfer = load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "transfer.py"), "shape_transfer_for_mtu")

CLASSES = ("transfer-mtu",)
TXN_SZ = T.TXN_MTU    # 1,232
SYSTEM_PROGRAM = bytes(32)
# MemoSq4gqABAXKb96qnH8TysNcWxMyWCqXgDLGmfcHr (SPL Memo v2), base58-decoded
MEMO_PROGRAM = bytes.fromhex(
    "054a535a992921064d24e87160da387c7c35b5ddbc92bb81e41fa8404105448d")
MEMO_SZ = TXN_SZ - 251


def _compact_u16(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def memo(gseed: bytes, i: int) -> bytes:
    """Row i's memo: MEMO_SZ hex characters off a seeded hash chain."""
    out = b""
    h = hashlib.sha256(gseed + b"memo%d" % i).digest()
    while len(out) < MEMO_SZ:
        out += h.hex().encode()
        h = hashlib.sha256(h).digest()
    return out[:MEMO_SZ]


def build(seed: int, n_rows: int, accounts: dict, traffic: dict,
          lo: int = 0, hi: int | None = None) -> T.Pool:
    """Rows [lo, hi) of the pool.  Row i: payer i mod n_payers,
    destination and lamports by index (as `transfer`), memo by index."""
    hi = n_rows if hi is None else hi
    gseed = T.genesis_seed(seed)
    n_payers, n_dests = accounts["n_payers"], accounts["n_dests"]
    signers = T.signers(gseed, n_payers)
    bh = T.blockhash(gseed)
    dests = [hashlib.sha256(gseed + b"to%d" % k).digest()
             for k in range(n_dests)]
    memo_len = _compact_u16(MEMO_SZ)
    rows = []
    for i in range(lo, hi):
        key, pub = signers[i % n_payers]
        msg = (b"\x01\x00\x02\x04" + pub + dests[i % n_dests]
               + SYSTEM_PROGRAM + MEMO_PROGRAM + bh + b"\x02"
               + b"\x02\x02\x00\x01\x0c"
               + (2).to_bytes(4, "little") + (1 + i).to_bytes(8, "little")
               + b"\x03\x00" + memo_len + memo(gseed, i))
        rows.append(b"\x01" + key.sign(msg) + msg)
        if len(rows[-1]) != TXN_SZ:
            raise RuntimeError(f"row {i} is {len(rows[-1])} bytes")
    n = hi - lo
    return T.join(rows, np.ones(n, np.int64), np.zeros(n, np.uint8), CLASSES)


corrupt = _transfer.corrupt
order = _transfer.order
genesis = _transfer.genesis
