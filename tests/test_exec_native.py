"""Differential tests: the native executor fast lane vs the Python lane.

The contract (ISSUE 4): a randomized stream of system/vote txns — valid,
malformed, boundary lamports, missing signers, duplicate accounts,
duplicate signatures, stale blockhashes, punt-inducing shapes — executed
through both lanes must produce identical per-txn status codes and fees,
an identical bank hash, and byte-identical final account state.  Since
ISSUE 16 the native surface also covers stake-program ops and the
durable-nonce family (the session's in-line durable gate owns the
stale-blockhash decision), and since ISSUE 31 the compute-budget
instructions with the priority fee they name; CPI/BPF/lookup-table txns
still route to the Python lane (classifier test).

The whole module SKIPS (never fails) when the native lane is unavailable
(no toolchain, .so deleted, or FDTPU_NATIVE_EXEC=0).
"""

from __future__ import annotations

import hashlib
import os
import random

import pytest

from firedancer_tpu.flamenco import exec_native

if not exec_native.available():  # pragma: no cover - toolchain-less host
    pytest.skip("native executor lane unavailable", allow_module_level=True)

from firedancer_tpu.flamenco import nonce as fnonce
from firedancer_tpu.flamenco import vote_program as vp
from firedancer_tpu.flamenco.stake import STAKE_PROGRAM
from firedancer_tpu.flamenco.agave_state import (
    Lockout,
    PriorVoters,
    VoteState,
    vote_state_encode,
)
from firedancer_tpu.flamenco.blockstore import StatusCache
from firedancer_tpu.flamenco.runtime import SlotExecution, acct_build
from firedancer_tpu.flamenco import types as T
from firedancer_tpu.funk import Funk
from firedancer_tpu.protocol import txn as ft
from firedancer_tpu.protocol.txn import SYSTEM_PROGRAM, VOTE_PROGRAM

SLOT = 41
BH = hashlib.sha256(b"exec-native-bh").digest()
STALE_BH = hashlib.sha256(b"stale").digest()
# a durable-nonce era hash: unknown to the status cache, stored as the
# nonce value of the pre-seeded "noncedur*" accounts in _world()
NONCE_BH = hashlib.sha256(b"nonce-era").digest()
SLOT_HASHES = [
    (s, hashlib.sha256(b"sh%d" % s).digest()) for s in range(1, 40)
]
SH = dict(SLOT_HASHES)

BPF_PROG = hashlib.sha256(b"some-bpf-program").digest()
CB_PROG_B58 = "ComputeBudget111111111111111111111111111111"


def _pk(tag: str) -> bytes:
    return hashlib.sha256(b"pk:" + tag.encode()).digest()


def _sig(rng: random.Random) -> bytes:
    return rng.randbytes(64)


def _txn(rng, payers, others, instrs, *, ro_signed=0, ro_unsigned=0,
         blockhash=BH, version=ft.VLEGACY, luts=None, sig=None):
    """Assemble a txn over payers (signers) + others; executor-path only
    (no sigverify here), so signatures are random bytes."""
    msg = ft.message_build(
        version=version,
        signature_cnt=len(payers),
        readonly_signed_cnt=ro_signed,
        readonly_unsigned_cnt=ro_unsigned,
        acct_addrs=payers + others,
        recent_blockhash=blockhash,
        instrs=instrs,
        luts=luts,
    )
    sigs = [sig or _sig(rng) for _ in payers]
    return ft.txn_assemble(sigs, msg)


def _transfer_data(lamports: int) -> bytes:
    return (2).to_bytes(4, "little") + lamports.to_bytes(8, "little")


def _create_data(lamports: int, space: int, owner: bytes) -> bytes:
    return ((0).to_bytes(4, "little") + lamports.to_bytes(8, "little")
            + space.to_bytes(8, "little") + owner)


def _vote_state_v1_blob() -> bytes:
    """A V1_14_11-encoded vote state (native lane must punt on it)."""
    from firedancer_tpu.flamenco.agave_state import (
        _VOTE_STATE_BODY_1_14_11,
    )

    vs = VoteState(
        node_pubkey=_pk("node"),
        authorized_withdrawer=_pk("voterA"),
        votes=[Lockout(3, 1)],
        authorized_voters={0: _pk("voterA")},
        prior_voters=PriorVoters(),
        epoch_credits=[(0, 5, 0)],
    )
    blob = T.U32.encode(1) + _VOTE_STATE_BODY_1_14_11.encode(vs)
    return blob.ljust(vp.VOTE_STATE_SIZE, b"\x00")


def _world() -> tuple[Funk, StatusCache]:
    funk = Funk()
    sc = StatusCache()
    sc.register_blockhash(BH, SLOT - 1)
    for name in ("payerA", "payerB", "payerC", "payerD", "voterA"):
        funk.rec_insert(None, _pk(name), acct_build(10**10))
    funk.rec_insert(None, _pk("poor"), acct_build(4_999))
    funk.rec_insert(None, _pk("exact"), acct_build(5_000))
    funk.rec_insert(None, _pk("richdst"), acct_build((1 << 64) - 10_000))
    funk.rec_insert(None, _pk("datasrc"),
                    acct_build(10**9, data=b"\x01\x02"))
    funk.rec_insert(None, _pk("foreign"),
                    acct_build(10**9, owner=_pk("owner")))
    # legacy short record (u64||data layout, no owner header)
    funk.rec_insert(None, _pk("legacy"),
                    (10**9).to_bytes(8, "little") + b"old-format")
    # initialized vote accounts: one current-version, one V1 (punt)
    vs = VoteState(
        node_pubkey=_pk("node"),
        authorized_withdrawer=_pk("voterA"),
        authorized_voters={0: _pk("voterA")},
    )
    funk.rec_insert(
        None, _pk("voteacct"),
        acct_build(10**9, owner=VOTE_PROGRAM,
                   data=vote_state_encode(vs).ljust(vp.VOTE_STATE_SIZE,
                                                    b"\x00")))
    funk.rec_insert(
        None, _pk("voteacct_v1"),
        acct_build(10**9, owner=VOTE_PROGRAM, data=_vote_state_v1_blob()))
    funk.rec_insert(
        None, _pk("voteacct_zero"),
        acct_build(10**9, owner=VOTE_PROGRAM,
                   data=bytes(vp.VOTE_STATE_SIZE)))
    funk.rec_insert(None, _pk("notvote"),
                    acct_build(10**9, data=bytes(vp.VOTE_STATE_SIZE)))
    # durable-nonce era accounts: stored nonce == NONCE_BH (which the
    # status cache does NOT know), authority payerB; "noncepay" is its
    # own authority so it can serve as the fee payer of a durable txn
    for name in ("noncedur0", "noncedur1", "noncedur2"):
        funk.rec_insert(None, _pk(name),
                        acct_build(10**8, data=fnonce.encode_state(
                            fnonce.STATE_INIT, _pk("payerB"), NONCE_BH)))
    funk.rec_insert(None, _pk("noncepay"),
                    acct_build(10**8, data=fnonce.encode_state(
                        fnonce.STATE_INIT, _pk("noncepay"), NONCE_BH)))
    funk.rec_insert(None, _pk("nonceU"), acct_build(10**8, data=bytes(68)))
    return funk, sc


def _stream(rng: random.Random) -> list[bytes]:
    """The randomized system/vote stream, conflict-heavy by design."""
    payers = [_pk("payerA"), _pk("payerB"), _pk("payerC"), _pk("payerD")]
    txns: list[bytes] = []

    def sys_instr(prog_idx, accounts, data):
        return ft.InstrSpec(program_id=prog_idx, accounts=accounts, data=data)

    fresh = 0
    for i in range(220):
        p = payers[rng.randrange(len(payers))]
        kind = rng.randrange(17)
        if kind == 0:  # plain transfer (intra-batch conflicts via few payers)
            dst = payers[rng.randrange(len(payers))]
            others = [SYSTEM_PROGRAM] if dst == p else [dst, SYSTEM_PROGRAM]
            acc = bytes([0, 0]) if dst == p else bytes([0, 1])
            txns.append(_txn(rng, [p], others,
                             [sys_instr(len(others), acc,
                                        _transfer_data(rng.randrange(1, 9999)))],
                             ro_unsigned=1))
        elif kind == 1:  # insufficient funds / boundary lamports
            lam = rng.choice([0, 1, 10**10, 10**12, (1 << 64) - 1])
            txns.append(_txn(rng, [p], [_pk("dst%d" % i), SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]),
                                        _transfer_data(lam))],
                             ro_unsigned=1))
        elif kind == 2:  # missing signer: source is an unsigned account
            txns.append(_txn(rng, [p],
                             [_pk("payerB"), _pk("dst%d" % i), SYSTEM_PROGRAM],
                             [sys_instr(3, bytes([1, 2]),
                                        _transfer_data(5))],
                             ro_unsigned=1))
        elif kind == 3:  # readonly destination (writability violation)
            txns.append(_txn(rng, [p], [_pk("rodst%d" % i), SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]),
                                        _transfer_data(5))],
                             ro_unsigned=2))
        elif kind == 4:  # source carries data / foreign owner / legacy record
            src = rng.choice([_pk("datasrc"), _pk("foreign"), _pk("legacy")])
            txns.append(_txn(rng, [p, src], [_pk("dst%d" % i), SYSTEM_PROGRAM],
                             [sys_instr(3, bytes([1, 2]),
                                        _transfer_data(7))],
                             ro_unsigned=1))
        elif kind == 5:  # create account (fresh -> ok; repeat -> in use)
            fresh += rng.randrange(2)
            new = _pk("new%d" % fresh)
            txns.append(_txn(rng, [p, new], [SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]),
                                        _create_data(
                                            rng.randrange(1, 10**6),
                                            rng.choice([0, 1, 64, 1024]),
                                            rng.choice([SYSTEM_PROGRAM,
                                                        _pk("owner")])))]))
        elif kind == 6:  # create too big / short data (malformed)
            data = rng.choice([
                _create_data(5, 10 * 1024 * 1024 + 1, SYSTEM_PROGRAM),
                (0).to_bytes(4, "little") + b"short",
            ])
            txns.append(_txn(rng, [p, _pk("newX%d" % i)], [SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]), data)]))
        elif kind == 7:  # assign / allocate on a fresh account
            tag = rng.choice([1, 8])
            data = ((1).to_bytes(4, "little") + _pk("owner") if tag == 1
                    else (8).to_bytes(4, "little")
                    + rng.choice([16, 0, 2048]).to_bytes(8, "little"))
            txns.append(_txn(rng, [p, _pk("aa%d" % i)], [SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([1]), data)]))
        elif kind == 8:  # garbage system data: no-op tags / short / unknown
            data = rng.choice([b"", b"\x01", (3).to_bytes(4, "little"),
                               (99).to_bytes(4, "little") + b"xx",
                               (2).to_bytes(4, "little") + b"\x05"])
            txns.append(_txn(rng, [p], [_pk("dst%d" % i), SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]), data)],
                             ro_unsigned=1))
        elif kind == 9:  # fee payer short / exactly at the fee
            who = rng.choice([_pk("poor"), _pk("exact")])
            txns.append(_txn(rng, [who], [_pk("dst%d" % i), SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]),
                                        _transfer_data(1))],
                             ro_unsigned=1))
        elif kind == 10:  # duplicate account address (AccountLoadedTwice)
            txns.append(_txn(rng, [p], [p, SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]),
                                        _transfer_data(1))],
                             ro_unsigned=1))
        elif kind == 11:  # near-u64-max destination balance (no overflow:
            # past it BOTH lanes die the same way — python's acct_encode
            # raises uncaught, the native lane punts into that raise)
            txns.append(_txn(rng, [p], [_pk("richdst"), SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]),
                                        _transfer_data(1))],
                             ro_unsigned=1))
        elif kind == 12:  # vote: valid vote / tower sync on live account
            va = _pk("voteacct")
            slot = rng.randrange(1, 39)
            if rng.randrange(2):
                data = vp.encode_vote_ix([slot], SH[slot])
            else:
                data = vp.encode_tower_sync_ix(
                    [(slot, 2), (slot + 1, 1)] if slot + 1 in SH
                    else [(slot, 1)],
                    None, SH.get(slot + 1, SH[slot]))
            txns.append(_txn(rng, [_pk("voterA")], [va, VOTE_PROGRAM],
                             [sys_instr(2, bytes([1, 0]), data)],
                             ro_unsigned=1))
        elif kind == 13:  # vote failures: bad hash, old slot, empty, garbage
            va = rng.choice([_pk("voteacct"), _pk("voteacct_zero"),
                             _pk("notvote")])
            data = rng.choice([
                vp.encode_vote_ix([5], b"\xee" * 32),
                vp.encode_vote_ix([], b"\x00" * 32),
                vp.encode_vote_ix([500], b"\x00" * 32),
                T.U32.encode(2) + b"\x01",       # truncated bincode
                b"\x02\x00",                      # truncated tag
                T.U32.encode(12),                 # unsupported instruction
            ])
            txns.append(_txn(rng, [_pk("voterA")], [va, VOTE_PROGRAM],
                             [sys_instr(2, bytes([1, 0]), data)],
                             ro_unsigned=1))
        elif kind == 14:  # vote punts: V1 state, init, authorize, withdraw
            va = rng.choice([_pk("voteacct_v1"), _pk("voteacct")])
            data = rng.choice([
                vp.encode_vote_ix([7], SH[7]),
                vp.encode_initialize_ix(_pk("node"), _pk("voterA"),
                                        _pk("voterA")),
                T.U32.encode(3) + T.U64.encode(1),  # Withdraw
            ])
            txns.append(_txn(rng, [_pk("voterA")], [va, VOTE_PROGRAM],
                             [sys_instr(2, bytes([1, 0]), data)],
                             ro_unsigned=1))
        elif kind == 15:  # BPF stays Python-lane; nonce init is native now
            if rng.randrange(2):
                txns.append(_txn(rng, [p], [_pk("dst%d" % i), BPF_PROG],
                                 [sys_instr(2, bytes([0, 1]), b"\x01\x02")],
                                 ro_unsigned=1))
            else:
                txns.append(_txn(rng, [p],
                                 [_pk("nonce%d" % i), SYSTEM_PROGRAM],
                                 [sys_instr(2, bytes([1, 0]),
                                            (6).to_bytes(4, "little")
                                            + _pk("auth"))],
                                 ro_unsigned=1))
        else:  # multi-instruction txns (mixed success/failure ordering)
            dst = _pk("dst%d" % i)
            txns.append(_txn(rng, [p], [dst, SYSTEM_PROGRAM],
                             [sys_instr(2, bytes([0, 1]),
                                        _transfer_data(10)),
                              sys_instr(2, bytes([0, 1]),
                                        _transfer_data(
                                            rng.choice([5, 10**12])))],
                             ro_unsigned=1))

    # duplicate signatures: resend a few txns verbatim (gate must reject
    # the second copy), including adjacent duplicates inside one batch
    for idx in (3, 10, 10, 50):
        if idx < len(txns):
            txns.append(txns[idx])
    # stale blockhash -> TXN_ERR_BLOCKHASH through either lane
    txns.append(_txn(rng, [payers[0]], [_pk("dstS"), SYSTEM_PROGRAM],
                     [sys_instr(2, bytes([0, 1]), _transfer_data(5))],
                     ro_unsigned=1, blockhash=STALE_BH))
    return txns


def _run(txns: list[bytes], *, native: bool, batch: int = 16):
    """Execute the stream in microblock-sized batches; returns statuses,
    fees, bank hash, and the full visible account state."""
    os.environ[exec_native.ENV_SWITCH] = "1" if native else "0"
    try:
        funk, sc = _world()
        sx = SlotExecution(funk, slot=SLOT, status_cache=sc,
                           slot_hashes=SLOT_HASHES)
        results = []
        for o in range(0, len(txns), batch):
            items = []
            for p in txns[o : o + batch]:
                t = ft.txn_parse(p)
                assert t is not None
                items.append((p, t, None))
            results.extend(sx.execute_batch(items))
        sealed = sx.seal(b"\x33" * 32)
        state = {
            k: funk.rec_query(sx.xid, k) for k in funk.rec_keys(sx.xid)
        }
        return ([(r.status, r.fee) for r in results], sealed.bank_hash,
                sealed.fees, sealed.signature_cnt, state,
                (sx.native_done_cnt, sx.native_punt_cnt))
    finally:
        os.environ.pop(exec_native.ENV_SWITCH, None)


def test_differential_random_stream():
    rng = random.Random(0xD1FF)
    txns = _stream(rng)
    py = _run(txns, native=False)
    nat = _run(txns, native=True)
    assert py[0] == nat[0], [
        (i, a, b) for i, (a, b) in enumerate(zip(py[0], nat[0])) if a != b
    ][:10]
    assert py[1] == nat[1], "bank hash diverged"
    assert py[2] == nat[2] and py[3] == nat[3]
    assert py[4].keys() == nat[4].keys()
    diff = [k for k in py[4] if py[4][k] != nat[4][k]]
    assert not diff, f"{len(diff)} account(s) diverged, e.g. {diff[0].hex()}"


def test_differential_more_seeds():
    for seed in (1, 2026):
        rng = random.Random(seed)
        txns = _stream(rng)
        py = _run(txns, native=False, batch=31)
        nat = _run(txns, native=True, batch=31)
        assert py[0] == nat[0]
        assert py[1] == nat[1]
        assert py[4] == nat[4]


def test_vote_state_bytes_identical():
    """After a native vote, the stored VoteState bytes match the Python
    lane exactly (latency credits, lockout doubling, timestamp)."""
    rng = random.Random(7)
    va = _pk("voteacct")
    txns = []
    for slot in (1, 2, 3, 5, 8, 13, 21, 34):
        data = T.U32.encode(2) + vp.VOTE_IX.encode(
            vp.VoteIx([slot], SH[slot], 1000 + slot))
        txns.append(_txn(rng, [_pk("voterA")], [va, VOTE_PROGRAM],
                         [ft.InstrSpec(program_id=2, accounts=bytes([1, 0]),
                                       data=data)],
                         ro_unsigned=1))
    py = _run(txns, native=False)
    nat = _run(txns, native=True)
    assert py[0] == nat[0] and all(s == 0 for s, _ in py[0])
    assert py[4][va] == nat[4][va]


def test_fallback_routing_classifier():
    """CPI/BPF and lookup-table txns never route native; system
    transfers, votes, stake ops, the nonce family and compute-budget
    instructions do (ISSUE 16 widened the surface to stake + durable
    nonce, ISSUE 31 to the compute budget)."""
    from firedancer_tpu.protocol.base58 import b58_decode32

    rng = random.Random(3)
    p = _pk("payerA")

    def eligible(payload):
        t = ft.txn_parse(payload)
        return exec_native.eligible_packed(payload, ft.txn_pack(t))

    transfer = _txn(rng, [p], [_pk("d"), SYSTEM_PROGRAM],
                    [ft.InstrSpec(2, bytes([0, 1]), _transfer_data(5))],
                    ro_unsigned=1)
    assert eligible(transfer)
    vote = _txn(rng, [_pk("voterA")], [_pk("voteacct"), VOTE_PROGRAM],
                [ft.InstrSpec(2, bytes([1, 0]),
                              vp.encode_vote_ix([5], SH[5]))],
                ro_unsigned=1)
    assert eligible(vote)
    bpf = _txn(rng, [p], [_pk("d"), BPF_PROG],
               [ft.InstrSpec(2, bytes([0, 1]), b"\x00")], ro_unsigned=1)
    assert not eligible(bpf)
    nonce = _txn(rng, [p], [_pk("n"), SYSTEM_PROGRAM],
                 [ft.InstrSpec(2, bytes([1, 0]),
                               (4).to_bytes(4, "little"))], ro_unsigned=1)
    assert eligible(nonce)  # durable-nonce family runs native now
    stake = _txn(rng, [p], [_pk("stk"), STAKE_PROGRAM],
                 [ft.InstrSpec(2, bytes([1, 0]),
                               (2).to_bytes(4, "little"))], ro_unsigned=1)
    assert eligible(stake)  # stake-program ops run native now
    cb = _txn(rng, [p], [_pk("d"), b58_decode32(CB_PROG_B58)],
              [ft.InstrSpec(2, bytes([0]), b"\x02\x40\x42\x0f\x00")],
              ro_unsigned=1)
    assert eligible(cb)  # the compute budget runs native now
    vote_auth = _txn(rng, [_pk("voterA")], [_pk("voteacct"), VOTE_PROGRAM],
                     [ft.InstrSpec(2, bytes([1, 0]),
                                   T.U32.encode(1) + _pk("x")
                                   + T.U32.encode(0))],
                     ro_unsigned=1)
    assert not eligible(vote_auth)
    lut = _txn(rng, [p], [_pk("d"), SYSTEM_PROGRAM],
               [ft.InstrSpec(2, bytes([0, 1]), _transfer_data(5))],
               ro_unsigned=1, version=ft.V0,
               luts=[ft.LutSpec(_pk("table"), bytes([0]), b"")])
    assert not eligible(lut)


def _cb_stream(rng):
    """Transfers and votes under compute-budget instructions: prices
    from 0 to u64 scale, limits under and over what the builtins cost,
    the deprecated units+fee form, a heap frame, malformed ones (the
    native lane punts, Python fails them typed), a payer that covers
    the signature fee but not the priority fee."""
    from firedancer_tpu.protocol.base58 import b58_decode32

    cbp = b58_decode32(CB_PROG_B58)

    def cu(n):
        return b"\x02" + n.to_bytes(4, "little")

    def price(n):
        return b"\x03" + n.to_bytes(8, "little")

    budgets = [
        [cu(20_000), price(1)], [cu(20_000), price(1_000_000)],
        [price(999_999)], [cu(1_400_000), price(2**40)],
        [cu(449), price(5)],          # three builtins cost 450: exceeded
        [cu(450), price(5)],          # ...and exactly enough
        [cu(0), price(7)], [cu(300)],
        [b"\x00" + (90_000).to_bytes(4, "little")
         + (1234).to_bytes(4, "little")],          # units + total fee
        [b"\x01" + (64 * 1024).to_bytes(4, "little"), price(3)],
        [cu(5), cu(6)],               # duplicate: malformed
        [b"\x02\x01"], [b"\x09" * 5],             # truncated, unknown tag
        [cu(20_000), price(2**63)],   # a fee no payer holds
        [cu(1_400_000), price(2**64 - 1)],
    ]
    txns = []
    for i in range(120):
        cb = rng.choice(budgets)
        pre = [ft.InstrSpec(3, b"", x) for x in cb]
        if i % 3 == 0:  # a vote, priced
            slot = 1 + (i // 3) % 39
            data = T.U32.encode(2) + vp.VOTE_IX.encode(
                vp.VoteIx([slot], SH[slot], 1000 + slot))
            txns.append(_txn(rng, [_pk("voterA")],
                             [_pk("voteacct"), VOTE_PROGRAM, cbp],
                             pre + [ft.InstrSpec(2, bytes([1, 0]), data)],
                             ro_unsigned=2))
        else:
            p = _pk(rng.choice(["payerA", "payerB", "exact"]))
            txns.append(_txn(rng, [p],
                             [_pk("dst%d" % (i % 7)), SYSTEM_PROGRAM, cbp],
                             pre + [ft.InstrSpec(2, bytes([0, 1]),
                                                 _transfer_data(1 + i))],
                             ro_unsigned=2))
    return txns


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_differential_compute_budget_stream(seed):
    """ComputeBudget native/Python parity: the same statuses, the same
    fees — signature fee plus ceil(limit x price / 10^6), charged
    whatever the outcome — the same bank hash and account bytes."""
    txns = _cb_stream(random.Random(seed))
    py = _run(txns, native=False)
    nat = _run(txns, native=True)
    assert py[0] == nat[0], [
        (i, a, b) for i, (a, b) in enumerate(zip(py[0], nat[0])) if a != b
    ][:10]
    assert py[1] == nat[1] and py[2] == nat[2] and py[4] == nat[4]
    fees = {f for _s, f in py[0]}
    assert 5000 + 20_000 in fees and 5000 + 1 in fees    # priced landings
    assert any(s == -4 and f > 5000 for s, f in py[0])   # budget exceeded
    assert nat[5][0] > 0                                 # and ran natively


def test_priority_fee_is_charged_to_the_payer():
    """limit 20,000 x 1,000,000 micro-lamports = 20,000 lamports over
    the 5,000 of the signature, on both lanes, off the fee payer."""
    from firedancer_tpu.protocol.base58 import b58_decode32

    rng = random.Random(5)
    p, d = _pk("payerA"), _pk("dstfee")
    txn = _txn(rng, [p], [d, SYSTEM_PROGRAM, b58_decode32(CB_PROG_B58)],
               [ft.InstrSpec(3, b"", b"\x02" + (20_000).to_bytes(4, "little")),
                ft.InstrSpec(3, b"", b"\x03" + (10**6).to_bytes(8, "little")),
                ft.InstrSpec(2, bytes([0, 1]), _transfer_data(77))],
               ro_unsigned=2)
    from firedancer_tpu.flamenco.runtime import acct_lamports

    funk0, _sc = _world()
    before = acct_lamports(funk0.rec_query(None, p))
    for native in (False, True):
        res = _run([txn], native=native)
        assert res[0] == [(0, 25_000)]
        assert acct_lamports(res[4][p]) == before - 25_000 - 77
        assert acct_lamports(res[4][d]) == 77


def test_env_switch_disables():
    os.environ[exec_native.ENV_SWITCH] = "0"
    try:
        assert not exec_native.available()
    finally:
        os.environ.pop(exec_native.ENV_SWITCH, None)


def test_session_gate_duplicates_stay_native():
    """ISSUE 9 bank-lane residual: with the session armed, a duplicate
    signature in a LATER microblock is gated by the C++ side in-line
    (TXN_ERR_ALREADY_PROCESSED) — it still counts as native work, never
    re-enters the Python lane, and matches the Python lane's verdict."""
    from firedancer_tpu.flamenco.runtime import TXN_ERR_ALREADY_PROCESSED

    rng = random.Random(55)
    p = _pk("payerA")
    t1 = _txn(rng, [p], [_pk("sgd1"), SYSTEM_PROGRAM],
              [ft.InstrSpec(2, bytes([0, 1]), _transfer_data(7))],
              ro_unsigned=1)
    t2 = _txn(rng, [p], [_pk("sgd2"), SYSTEM_PROGRAM],
              [ft.InstrSpec(2, bytes([0, 1]), _transfer_data(8))],
              ro_unsigned=1)
    funk, sc = _world()
    sx = SlotExecution(funk, slot=SLOT, status_cache=sc,
                       slot_hashes=SLOT_HASHES)
    r1 = sx.execute_batch([(t1, ft.txn_parse(t1), None)])
    r2 = sx.execute_batch([(t2, ft.txn_parse(t2), None),
                           (t1, ft.txn_parse(t1), None)])
    assert [r.status for r in r1] == [0]
    assert [r.status for r in r2] == [0, TXN_ERR_ALREADY_PROCESSED]
    assert r2[1].fee == 0
    # all four records were native-lane work: the duplicate was gated by
    # the session, not flushed back to Python
    assert sx.native_done_cnt == 3
    assert sx.native_punt_cnt == 0
    assert sx._native_session is not None


def test_session_values_survive_python_lane_interleave():
    """The session's account-value overlay must resync after Python-lane
    writes dirty it: native transfer -> BPF-ish fallback touching the
    same payer -> native transfer again.  Balances must equal the pure
    Python lane's (a stale overlay would double-spend or under-debit)."""
    rng = random.Random(66)
    p = _pk("payerA")

    def t_native(i, lam):
        return _txn(rng, [p], [_pk("svi%d" % i), SYSTEM_PROGRAM],
                    [ft.InstrSpec(2, bytes([0, 1]), _transfer_data(lam))],
                    ro_unsigned=1)

    # a BPF txn is Python-lane by classifier and touches the payer (fee
    # debit), so it dirties the session overlay between native crossings
    py_lane = _txn(rng, [p], [_pk("svin"), BPF_PROG],
                   [ft.InstrSpec(2, bytes([0, 1]), b"\x01\x02")],
                   ro_unsigned=1)
    txns = [t_native(0, 100), py_lane, t_native(1, 200), py_lane,
            t_native(2, 400)]
    py = _run(txns, native=False, batch=2)  # crosses microblock bounds
    nat = _run(txns, native=True, batch=2)
    assert py[0] == nat[0]
    assert py[1] == nat[1], "bank hash diverged (stale session overlay?)"
    assert py[4] == nat[4]


def test_session_stale_blockhash_punts_to_python_gate():
    """An unknown/stale blockhash mid-batch: the session gate PUNTS (it
    cannot rule out a durable nonce), and the Python gate settles it
    with the same TXN_ERR_BLOCKHASH the pure lane produces."""
    from firedancer_tpu.flamenco.runtime import TXN_ERR_BLOCKHASH

    rng = random.Random(77)
    p = _pk("payerA")
    good = _txn(rng, [p], [_pk("sbp1"), SYSTEM_PROGRAM],
                [ft.InstrSpec(2, bytes([0, 1]), _transfer_data(5))],
                ro_unsigned=1)
    stale = _txn(rng, [p], [_pk("sbp2"), SYSTEM_PROGRAM],
                 [ft.InstrSpec(2, bytes([0, 1]), _transfer_data(5))],
                 ro_unsigned=1, blockhash=STALE_BH)
    tail = _txn(rng, [p], [_pk("sbp3"), SYSTEM_PROGRAM],
                [ft.InstrSpec(2, bytes([0, 1]), _transfer_data(5))],
                ro_unsigned=1)
    py = _run([good, stale, tail], native=False, batch=3)
    nat = _run([good, stale, tail], native=True, batch=3)
    assert py[0] == nat[0]
    assert nat[0][1] == (TXN_ERR_BLOCKHASH, 0)
    assert py[4] == nat[4]


def test_punt_mid_batch_resumes_in_order():
    """A punt (vote init) between native txns: order, statuses and state
    all match the pure-Python lane."""
    rng = random.Random(11)
    p = _pk("payerA")
    mk_t = lambda lam: _txn(rng, [p], [_pk("pd"), SYSTEM_PROGRAM],
                            [ft.InstrSpec(2, bytes([0, 1]),
                                          _transfer_data(lam))],
                            ro_unsigned=1)
    init = _txn(rng, [_pk("voterA")], [_pk("voteacct_zero"), VOTE_PROGRAM],
                [ft.InstrSpec(2, bytes([1, 0]),
                              vp.encode_initialize_ix(
                                  _pk("voterA"), _pk("voterA"),
                                  _pk("voterA")))],
                ro_unsigned=1)
    vote = _txn(rng, [_pk("voterA")], [_pk("voteacct_zero"), VOTE_PROGRAM],
                [ft.InstrSpec(2, bytes([1, 0]),
                              vp.encode_vote_ix([9], SH[9]))],
                ro_unsigned=1)
    txns = [mk_t(10), init, mk_t(20), vote, mk_t(30)]
    py = _run(txns, native=False, batch=len(txns))
    nat = _run(txns, native=True, batch=len(txns))
    assert py[0] == nat[0] == [(0, 5000)] * 5
    assert py[4] == nat[4]


# -- ISSUE 16: widened eligibility (stake program + durable nonce) -------------


def _stake_stream(rng: random.Random) -> list[bytes]:
    """Randomized stake-program ops — create/init/delegate/deactivate/
    withdraw/split plus malformed, wrong-signer and foreign-owner shapes.
    All of it is native-eligible now, so the native lane must match the
    Python lane tag for tag (incl. warmup-locked withdraw arithmetic)."""
    payers = [_pk("payerA"), _pk("payerB")]
    ii = ft.InstrSpec
    txns: list[bytes] = []
    n_stake = 5
    for j in range(n_stake):
        p = payers[j % 2]
        sk = _pk("stk%d" % j)
        txns.append(_txn(rng, [p, sk], [SYSTEM_PROGRAM],
                         [ii(2, bytes([0, 1]),
                             _create_data(10**7, 124, STAKE_PROGRAM))]))
        txns.append(_txn(rng, [p], [sk, STAKE_PROGRAM],
                         [ii(2, bytes([1]),
                             (0).to_bytes(4, "little") + p + p)],
                         ro_unsigned=1))
    for i in range(90):
        p = payers[rng.randrange(2)]
        sk = _pk("stk%d" % rng.randrange(n_stake))
        kind = rng.randrange(8)
        if kind == 0:  # delegate to the live vote account
            txns.append(_txn(rng, [p],
                             [sk, _pk("voteacct"), STAKE_PROGRAM],
                             [ii(3, bytes([1, 2, 0]),
                                 (1).to_bytes(4, "little"))],
                             ro_unsigned=2))
        elif kind == 1:  # deactivate
            txns.append(_txn(rng, [p], [sk, STAKE_PROGRAM],
                             [ii(2, bytes([1, 0]),
                                 (2).to_bytes(4, "little"))],
                             ro_unsigned=1))
        elif kind == 2:  # withdraw: in-range, overdrawn, or warmup-locked
            lam = rng.choice([1, 5_000, 10**7, 10**12])
            txns.append(_txn(rng, [p],
                             [sk, _pk("sdst%d" % i), STAKE_PROGRAM],
                             [ii(3, bytes([1, 2, 0]),
                                 (3).to_bytes(4, "little")
                                 + lam.to_bytes(8, "little"))],
                             ro_unsigned=1))
        elif kind == 3:  # split into a prepared (or missing) sibling
            dst = _pk("stk%dsib" % rng.randrange(n_stake))
            if rng.randrange(2):
                txns.append(_txn(rng, [p, dst], [SYSTEM_PROGRAM],
                                 [ii(2, bytes([0, 1]),
                                     _create_data(10**6, 124,
                                                  STAKE_PROGRAM))]))
            txns.append(_txn(rng, [p],
                             [sk, dst, STAKE_PROGRAM],
                             [ii(3, bytes([1, 2, 0]),
                                 (4).to_bytes(4, "little")
                                 + rng.choice([1_000, 10**9])
                                 .to_bytes(8, "little"))],
                             ro_unsigned=1))
        elif kind == 4:  # wrong signer for delegate (staker absent)
            q = payers[1 - payers.index(p)]
            txns.append(_txn(rng, [q],
                             [sk, _pk("voteacct"), STAKE_PROGRAM],
                             [ii(3, bytes([1, 2, 0]),
                                 (1).to_bytes(4, "little"))],
                             ro_unsigned=2))
        elif kind == 5:  # malformed: short data / unknown tag / not owned
            data = rng.choice([b"\x01", (9).to_bytes(4, "little"),
                               (0).to_bytes(4, "little") + b"short"])
            tgt = rng.choice([sk, _pk("datasrc")])
            txns.append(_txn(rng, [p], [tgt, STAKE_PROGRAM],
                             [ii(2, bytes([1, 0]), data)],
                             ro_unsigned=1))
        elif kind == 6:  # re-init / init of a foreign-owner account
            tgt = rng.choice([sk, _pk("foreign")])
            txns.append(_txn(rng, [p], [tgt, STAKE_PROGRAM],
                             [ii(2, bytes([1]),
                                 (0).to_bytes(4, "little") + p + p)],
                             ro_unsigned=1))
        else:  # plain transfers keep intra-batch payer conflicts hot
            txns.append(_txn(rng, [p], [_pk("sd%d" % i), SYSTEM_PROGRAM],
                             [ii(2, bytes([0, 1]),
                                 _transfer_data(rng.randrange(1, 999)))],
                             ro_unsigned=1))
    return txns


def _nonce_stream(rng: random.Random) -> list[bytes]:
    """Randomized durable-nonce traffic: the full instruction family via
    the normal (valid-blockhash) path, plus genuine durable txns whose
    recent_blockhash is the STORED nonce — those must clear the
    session's in-line durable gate, rotate the nonce on typed failure,
    and handle the nonce-is-payer shape (writes[0] replacement)."""
    pA, pB = _pk("payerA"), _pk("payerB")
    ii = ft.InstrSpec
    adv = (4).to_bytes(4, "little")
    txns: list[bytes] = []
    for j in range(3):  # fresh nonce accounts through the normal path
        nk = _pk("nnk%d" % j)
        txns.append(_txn(rng, [pA, nk], [SYSTEM_PROGRAM],
                         [ii(2, bytes([0, 1]),
                             _create_data(10**7, 68, SYSTEM_PROGRAM))]))
        txns.append(_txn(rng, [pA], [nk, SYSTEM_PROGRAM],
                         [ii(2, bytes([1]),
                             (6).to_bytes(4, "little") + pB)],
                         ro_unsigned=1))
    for i in range(70):
        kind = rng.randrange(10)
        nk = _pk("nnk%d" % rng.randrange(3))
        if kind == 0:
            # durable advance on a pre-seeded era account: the first use
            # lands (fee + rotation); any reuse of the SAME account then
            # fails the gate (nonce moved) with TXN_ERR_BLOCKHASH
            dk = _pk("noncedur%d" % rng.randrange(3))
            txns.append(_txn(rng, [pB], [dk, SYSTEM_PROGRAM],
                             [ii(2, bytes([1, 0]), adv)],
                             ro_unsigned=1, blockhash=NONCE_BH))
        elif kind == 1:
            # durable txn whose SECOND instruction fails typed: the fee
            # sticks and the nonce still rotates (failure-rotation path)
            dk = _pk("noncedur%d" % rng.randrange(3))
            txns.append(_txn(rng, [pB], [dk, SYSTEM_PROGRAM],
                             [ii(2, bytes([1, 0]), adv),
                              ii(2, bytes([0, 1]),
                                 _transfer_data(10**13))],
                             ro_unsigned=1, blockhash=NONCE_BH))
        elif kind == 2:
            # the nonce account IS the fee payer (writes[0] replacement)
            txns.append(_txn(rng, [_pk("noncepay")], [SYSTEM_PROGRAM],
                             [ii(1, bytes([0]), adv)],
                             ro_unsigned=1, blockhash=NONCE_BH))
        elif kind == 3:
            # gate rejections: wrong authority / uninit / unknown hash
            shape = rng.randrange(3)
            if shape == 0:  # pA signs but the authority is pB
                txns.append(_txn(rng, [pA],
                                 [_pk("noncedur0"), SYSTEM_PROGRAM],
                                 [ii(2, bytes([1, 0]), adv)],
                                 ro_unsigned=1, blockhash=NONCE_BH))
            elif shape == 1:
                txns.append(_txn(rng, [pB],
                                 [_pk("nonceU"), SYSTEM_PROGRAM],
                                 [ii(2, bytes([1, 0]), adv)],
                                 ro_unsigned=1, blockhash=STALE_BH))
            else:
                txns.append(_txn(rng, [pB],
                                 [_pk("noncedur1"), SYSTEM_PROGRAM],
                                 [ii(2, bytes([1, 0]), adv)],
                                 ro_unsigned=1,
                                 blockhash=_pk("junkbh%d" % i)))
        elif kind == 4:  # same-slot advance via valid BH: hash unmoved
            txns.append(_txn(rng, [pB], [nk, SYSTEM_PROGRAM],
                             [ii(2, bytes([1, 0]), adv)],
                             ro_unsigned=1))
        elif kind == 5:  # withdraw: partial above/below the rent floor,
            # exact-balance drain (blockhash-not-expired), overdrawn
            lam = rng.choice([100, 10**7 - 100, 10**7, 10**12])
            txns.append(_txn(rng, [pB],
                             [nk, _pk("ndst%d" % i), SYSTEM_PROGRAM],
                             [ii(3, bytes([1, 2, 0]),
                                 (5).to_bytes(4, "little")
                                 + lam.to_bytes(8, "little"))],
                             ro_unsigned=1))
        elif kind == 6:  # authorize: may flip authority away from pB
            txns.append(_txn(rng, [pB], [nk, SYSTEM_PROGRAM],
                             [ii(2, bytes([1, 0]),
                                 (7).to_bytes(4, "little")
                                 + rng.choice([pB, pA]))],
                             ro_unsigned=1))
        elif kind == 7:  # malformed: short init/authorize, re-init
            data = rng.choice([(6).to_bytes(4, "little") + b"short",
                               (7).to_bytes(4, "little"),
                               (6).to_bytes(4, "little") + pB])
            txns.append(_txn(rng, [pA], [nk, SYSTEM_PROGRAM],
                             [ii(2, bytes([1, 0]), data)],
                             ro_unsigned=1))
        elif kind == 8:  # withdraw from an uninitialized system account
            txns.append(_txn(rng, [pA],
                             [_pk("nonceU"), _pk("ndst%d" % i),
                              SYSTEM_PROGRAM],
                             [ii(3, bytes([1, 2, 0]),
                                 (5).to_bytes(4, "little")
                                 + (500).to_bytes(8, "little"))],
                             ro_unsigned=1))
        else:  # interleaved plain transfers
            txns.append(_txn(rng, [pA], [_pk("nd%d" % i), SYSTEM_PROGRAM],
                             [ii(2, bytes([0, 1]),
                                 _transfer_data(rng.randrange(1, 999)))],
                             ro_unsigned=1))
    return txns


def test_differential_stake_stream():
    rng = random.Random(0x57A4E)
    txns = _stake_stream(rng)
    py = _run(txns, native=False)
    nat = _run(txns, native=True)
    assert py[0] == nat[0], [
        (i, a, b) for i, (a, b) in enumerate(zip(py[0], nat[0])) if a != b
    ][:10]
    assert py[1] == nat[1], "bank hash diverged"
    assert py[2] == nat[2] and py[3] == nat[3]
    assert py[4] == nat[4]
    # the stake surface must actually have run native, not punted away
    assert nat[5][0] > len(txns) // 2


def test_differential_nonce_stream():
    rng = random.Random(0xD0CE)
    txns = _nonce_stream(rng)
    py = _run(txns, native=False, batch=13)
    nat = _run(txns, native=True, batch=13)
    assert py[0] == nat[0], [
        (i, a, b) for i, (a, b) in enumerate(zip(py[0], nat[0])) if a != b
    ][:10]
    assert py[1] == nat[1], "bank hash diverged"
    assert py[4] == nat[4]
    assert nat[5][0] > len(txns) // 2
    # the durable path itself must have been exercised: at least one
    # fee-charged SUCCESS against a blockhash the status cache rejects
    durable_ok = [
        s for t, (s, fee) in zip(txns, py[0])
        if ft.txn_parse(t).recent_blockhash(t) == NONCE_BH
        and s == 0 and fee > 0
    ]
    assert durable_ok, "no durable-nonce txn landed — stream too weak"


@pytest.mark.slow
def test_differential_widened_more_seeds():
    for seed in (3, 1137, 20260):
        rng = random.Random(seed)
        txns = _stake_stream(rng) + _nonce_stream(rng)
        py = _run(txns, native=False, batch=17)
        nat = _run(txns, native=True, batch=17)
        assert py[0] == nat[0], seed
        assert py[1] == nat[1], seed
        assert py[4] == nat[4], seed
