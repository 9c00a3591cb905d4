"""The `mainnet-mix` shape: what a staked mainnet leader is sent in its
slots.  Seven rows in ten are votes, one a validator a round from a
bounded validator set; the rest are system transfers of one or two
signatures from many payers onto Zipf-hot destinations, half of them
naming a compute-unit price; a tenth of all offers repeat a row offered
before.  Pure functions of the seed (BASELINE.json configs[3]: "mainnet
pcap replay"; there is no pcap here, so a seeded generator stands in and
`benchmarks/configs/leader-mainnet-v5e.json` lists what is assumed).

The wire format is Solana's legacy transaction; a vote is
`VoteInstruction::Vote` in Agave's account layout (vote account,
SlotHashes, Clock, authority).  Imports neither JAX nor the program: the
signing workers load this file alone.
"""

from __future__ import annotations

import hashlib

import numpy as np

from harness import traffic as T

CLASSES = ("vote", "transfer")
VOTE, TRANSFER = 0, 1

VOTE_SHARE = 0.70
COSIGNED_ONE_IN = 5       # transfers with a second, read-only signer
PRICED_ONE_IN = 2         # transfers that name a compute-unit price
CU_LIMIT = 20_000
PRICE_MAX_EXP = 6.0       # price log-uniform over 1 .. 10**6 micro-lamports
ZIPF_THETA = 0.99         # YCSB's default constant
REPEAT_SHARE = 0.10       # of offers
NEAR = (1, 8)             # offers behind: inside verify's 16-deep tag cache
FAR = (1024, 32768)       # past it, inside pack's 65,536 tags
MAX_VOTES_PER_VOTER = 480  # SlotHashes holds 512 slots
BASE_SLOT = 1             # the first slot SlotHashes holds, and voted
TIMESTAMP0 = 1_700_000_000

VOTE_TXN_SZ = 330         # 1 + 64 + a 265-byte message

SYSTEM_PROGRAM = bytes(32)
VOTE_PROGRAM = bytes.fromhex(
    "0761481d357474bb7c4d7624ebd3bdb3d8355e73d11043fc0da3538000000000")
COMPUTE_BUDGET_PROGRAM = bytes.fromhex(
    "0306466fe5211732ffecadba72c39be7bc8ce5bbc5f7126b2c439b3a40000000")
SYSVAR_CLOCK = bytes.fromhex(
    "06a7d51718c774c928566398691d5eb68b5eb8a39b4b6d5c73555b2100000000")
SYSVAR_SLOT_HASHES = bytes.fromhex(
    "06a7d517192f0aafc6f265e3fb77cc7ada82c529d0be3b136e2d005520000000")


# -- the accounts, from the seed ---------------------------------------------

def voter_secrets(gseed: bytes, n: int) -> list[bytes]:
    return [hashlib.sha256(gseed + b"voter%d" % k).digest() for k in range(n)]


def vote_accounts(gseed: bytes, n: int) -> list[bytes]:
    return [hashlib.sha256(gseed + b"voteacct%d" % k).digest()
            for k in range(n)]


def destinations(gseed: bytes, n: int) -> list[bytes]:
    return [hashlib.sha256(gseed + b"to%d" % k).digest() for k in range(n)]


def slot_hash(gseed: bytes, slot: int) -> bytes:
    return hashlib.sha256(gseed + b"slothash%d" % slot).digest()


def _keys(secrets: list[bytes]):
    """-> [(OpenSSL private key, 32-byte public key)]."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )
    from cryptography.hazmat.primitives.serialization import (
        Encoding, PublicFormat,
    )

    out = []
    for s in secrets:
        key = Ed25519PrivateKey.from_private_bytes(s)
        out.append((key, key.public_key().public_bytes(Encoding.Raw,
                                                       PublicFormat.Raw)))
    return out


# -- what each row is, from the seed (all rows at once: numpy, cheap) --------

_PLANS: dict = {}


def plan(seed: int, n_rows: int, accounts: dict) -> dict:
    """Per row, for the whole pool: its class; a vote's voter and which
    of the voter's votes it is; a transfer's payer, Zipf-picked
    destination, co-signer (or -1) and price (or 0).  A worker builds a
    range of rows from the same plan (memoised a process)."""
    key = (seed, n_rows, tuple(sorted(accounts.items())))
    if key in _PLANS:
        return _PLANS[key]
    n_voters, n_payers = accounts["n_voters"], accounts["n_payers"]
    n_dests = accounts["n_dests"]
    rng = np.random.default_rng([seed, 0xC1A55])
    cls = np.where(rng.random(n_rows) < VOTE_SHARE, VOTE, TRANSFER) \
        .astype(np.uint8)
    # votes: the k-th vote row is the (k % n_voters)-th of round
    # k // n_voters, a seeded permutation of the validators a round
    k = np.cumsum(cls == VOTE) - 1
    rounds = int(k[-1]) // n_voters + 1 if n_rows else 0
    if rounds > MAX_VOTES_PER_VOTER:
        raise ValueError(
            f"a pool of {n_rows} rows asks each of {n_voters} voters for "
            f"{rounds} votes; SlotHashes carries {MAX_VOTES_PER_VOTER}")
    perms = np.stack([np.random.default_rng([seed, 0x707E, r])
                      .permutation(n_voters) for r in range(rounds)]) \
        if rounds else np.zeros((0, n_voters), np.int64)
    rnd = k // n_voters
    voter = perms[np.maximum(rnd, 0), np.maximum(k, 0) % n_voters]
    # transfers
    payer = rng.integers(0, n_payers, size=n_rows)
    w = 1.0 / np.arange(1, n_dests + 1) ** ZIPF_THETA
    dest = np.searchsorted(np.cumsum(w / w.sum()), rng.random(n_rows))
    dest = np.minimum(dest, n_dests - 1)
    hop = rng.integers(1, n_payers, size=n_rows)      # co-signer != payer
    cosigner = np.where(rng.random(n_rows) < 1.0 / COSIGNED_ONE_IN,
                        (payer + hop) % n_payers, -1)
    price = np.where(rng.random(n_rows) < 1.0 / PRICED_ONE_IN,
                     np.floor(10.0 ** (PRICE_MAX_EXP * rng.random(n_rows))),
                     0).astype(np.int64)
    if len(_PLANS) > 2:
        _PLANS.clear()
    _PLANS[key] = out = {
        "cls": cls, "voter": voter, "vote_no": rnd, "payer": payer,
        "dest": dest, "cosigner": cosigner, "price": price}
    return out


# -- rows ---------------------------------------------------------------------

def _instr(prog: int, accts: bytes, data: bytes) -> bytes:
    return bytes([prog, len(accts)]) + accts + bytes([len(data)]) + data


def vote_message(identity: bytes, vote_account: bytes, blockhash: bytes,
                 slot: int, slot_hash_: bytes, timestamp: int) -> bytes:
    """`Vote` (tag 2): one slot, its SlotHashes entry, a timestamp; the
    accounts Agave's instruction names — five keys, 265 bytes."""
    data = ((2).to_bytes(4, "little") + (1).to_bytes(8, "little")
            + slot.to_bytes(8, "little") + slot_hash_
            + b"\x01" + timestamp.to_bytes(8, "little", signed=True))
    return (b"\x01\x00\x03\x05" + identity + vote_account
            + SYSVAR_SLOT_HASHES + SYSVAR_CLOCK + VOTE_PROGRAM + blockhash
            + b"\x01" + _instr(4, bytes([1, 2, 3, 0]), data))


def transfer_message(payer: bytes, cosigner: bytes | None, dest: bytes,
                     blockhash: bytes, lamports: int, price: int) -> bytes:
    """A system transfer; `cosigner` signs second, read-only; `price`
    > 0 puts SetComputeUnitLimit and SetComputeUnitPrice in front."""
    k = 2 if cosigner is not None else 1
    keys = payer + (cosigner or b"") + dest + SYSTEM_PROGRAM
    instrs = []
    if price:
        keys += COMPUTE_BUDGET_PROGRAM
        instrs = [_instr(k + 2, b"", b"\x02" + CU_LIMIT.to_bytes(4, "little")),
                  _instr(k + 2, b"", b"\x03" + price.to_bytes(8, "little"))]
    instrs.append(_instr(k + 1, bytes([0, k]),
                         (2).to_bytes(4, "little")
                         + lamports.to_bytes(8, "little")))
    n_keys = k + 2 + bool(price)
    return (bytes([k, k - 1, n_keys - k - 1, n_keys]) + keys + blockhash
            + bytes([len(instrs)]) + b"".join(instrs))


def build(seed: int, n_rows: int, accounts: dict, traffic: dict,
          lo: int = 0, hi: int | None = None) -> T.Pool:
    """Rows [lo, hi) of the pool.  Transfer i moves 1 + i lamports, and
    a voter's j-th vote names slot BASE_SLOT + j, so every row of a
    pool is distinct."""
    hi = n_rows if hi is None else hi
    gseed = T.genesis_seed(seed)
    pl = plan(seed, n_rows, accounts)
    bh = T.blockhash(gseed)
    payers = T.signers(gseed, accounts["n_payers"])
    voters = _keys(voter_secrets(gseed, accounts["n_voters"]))
    vaccts = vote_accounts(gseed, accounts["n_voters"])
    dests = destinations(gseed, accounts["n_dests"])
    rows, sigs = [], []
    for i in range(lo, hi):
        if pl["cls"][i] == VOTE:
            v, j = int(pl["voter"][i]), int(pl["vote_no"][i])
            key, pub = voters[v]
            slot = BASE_SLOT + j
            msg = vote_message(pub, vaccts[v], bh, slot,
                               slot_hash(gseed, slot), TIMESTAMP0 + j)
            who = [key]
        else:
            key, pub = payers[int(pl["payer"][i])]
            c = int(pl["cosigner"][i])
            msg = transfer_message(
                pub, payers[c][1] if c >= 0 else None,
                dests[int(pl["dest"][i])], bh, 1 + i, int(pl["price"][i]))
            who = [key] + ([payers[c][0]] if c >= 0 else [])
        rows.append(bytes([len(who)]) + b"".join(k.sign(msg) for k in who)
                    + msg)
        sigs.append(len(who))
    return T.join(rows, sigs, pl["cls"][lo:hi], CLASSES)


def corrupt(pool: T.Pool, every: int, seed: int) -> np.ndarray:
    """Flip one seeded bit in a seeded one of the signatures of one
    seeded row in each run of `every`.  In place; -> sorted bad rows."""
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
    row offered before: half NEAR offers behind it, half FAR.  (Where
    the offer that far behind is itself a repeat, the row is the one
    first offered just before that, a few offers further.)"""
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
    """What has to exist before traffic, as the program's
    `genesis_bank_ctx` takes it: the funded payers (listed: `n_payers`
    0 seed-derived ones), the validators' identities and vote accounts,
    SlotHashes' entries newest first, the bank's slot after them, and
    everything traffic touches as the set to preload."""
    gseed = T.genesis_seed(seed)
    payers = [pub for _, pub in T.signers(gseed, accounts["n_payers"])]
    ids = [pub for _, pub in _keys(voter_secrets(gseed,
                                                 accounts["n_voters"]))]
    vaccts = vote_accounts(gseed, accounts["n_voters"])
    n_sh = accounts["slot_hashes"]
    slots = range(BASE_SLOT + n_sh - 1, BASE_SLOT - 1, -1)
    return {"seed": gseed, "n_payers": 0, "payers": payers,
            "voters": list(zip(ids, vaccts)),
            "slot_hashes": [(s, slot_hash(gseed, s)) for s in slots],
            "slot": BASE_SLOT + n_sh,
            "preload": payers + ids + vaccts
            + destinations(gseed, accounts["n_dests"])
            + [SYSVAR_SLOT_HASHES, SYSVAR_CLOCK]}
