"""Agave-conformance fixture harness (the sol_compat shape).

The reference's heavyweight correctness strategy replays the public
test-vectors corpus through instruction-level harnesses
(/root/reference/src/flamenco/runtime/tests/fd_exec_sol_compat.c:36-42,
fd_exec_instr_test.c fd_exec_instr_fixture_run); fixtures are protobuf
`InstrFixture` messages (schema: org.solana.sealevel.v1, field tags
mirrored from the nanopb descriptors in
/root/reference/src/flamenco/runtime/tests/generated/{invoke,context}.pb.h).

This module is the TPU build's adapter: a self-contained protobuf wire
codec (no protoc dependency), the fixture schema, and a runner that
replays an InstrContext through flamenco.executor and diffs the observed
effects against InstrEffects.  Pointing it at the real corpus (the
`dump/test-vectors` tree the reference's CI fetches) is zero further
work; the committed mini-corpus under tests/fixtures/instr/ was authored
with encode_fixture() in the same wire format and pins the rule edges
this build has implemented.

Comparison semantics follow fd_exec_instr_test.c:_diff_effects:
  - result compares as zero/nonzero ("error codes are not relevant to
    consensus" — invoke.pb.h:46-48); custom_err compares exactly when
    the fixture expects one;
  - modified_accounts: every listed account must match the post-state
    (lamports, owner, executable, data) exactly; accounts not listed
    must be unchanged;
  - cu_avail compares exactly when the fixture sets it (>0).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from firedancer_tpu.protocol.base58 import b58_decode32

# -- protobuf wire codec ------------------------------------------------------

WT_VARINT = 0
WT_I64 = 1
WT_LEN = 2
WT_I32 = 5


def _uvarint(buf: bytes, off: int) -> tuple[int, int]:
    x = 0
    sh = 0
    while True:
        b = buf[off]
        off += 1
        x |= (b & 0x7F) << sh
        if not b & 0x80:
            return x, off
        sh += 7
        if sh > 70:
            raise ValueError("varint overflow")


def _enc_uvarint(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def wire_decode(buf: bytes) -> list[tuple[int, int, object]]:
    """-> [(field_no, wire_type, value)]; LEN values are bytes."""
    out = []
    off = 0
    while off < len(buf):
        key, off = _uvarint(buf, off)
        fno, wt = key >> 3, key & 7
        if wt == WT_VARINT:
            v, off = _uvarint(buf, off)
        elif wt == WT_I64:
            v = int.from_bytes(buf[off : off + 8], "little")
            off += 8
        elif wt == WT_I32:
            v = int.from_bytes(buf[off : off + 4], "little")
            off += 4
        elif wt == WT_LEN:
            ln, off = _uvarint(buf, off)
            v = buf[off : off + ln]
            if len(v) != ln:
                raise ValueError("truncated LEN field")
            off += ln
        else:
            raise ValueError(f"unsupported wire type {wt}")
        out.append((fno, wt, v))
    return out


def enc_field(fno: int, wt: int, v) -> bytes:
    key = _enc_uvarint((fno << 3) | wt)
    if wt == WT_VARINT:
        return key + _enc_uvarint(v)
    if wt == WT_I64:
        return key + int(v).to_bytes(8, "little")
    if wt == WT_LEN:
        return key + _enc_uvarint(len(v)) + bytes(v)
    raise ValueError(f"unsupported wire type {wt}")


# -- fixture schema -----------------------------------------------------------


@dataclass
class AcctState:
    address: bytes = b"\x00" * 32
    lamports: int = 0
    data: bytes = b""
    executable: bool = False
    rent_epoch: int = 0
    owner: bytes = b"\x00" * 32

    @classmethod
    def decode(cls, buf: bytes) -> "AcctState":
        a = cls()
        for fno, _wt, v in wire_decode(buf):
            if fno == 1:
                a.address = bytes(v)
            elif fno == 2:
                a.lamports = v
            elif fno == 3:
                a.data = bytes(v)
            elif fno == 4:
                a.executable = bool(v)
            elif fno == 5:
                a.rent_epoch = v
            elif fno == 6:
                a.owner = bytes(v)
        return a

    def encode(self) -> bytes:
        out = enc_field(1, WT_LEN, self.address)
        if self.lamports:
            out += enc_field(2, WT_VARINT, self.lamports)
        if self.data:
            out += enc_field(3, WT_LEN, self.data)
        if self.executable:
            out += enc_field(4, WT_VARINT, 1)
        if self.rent_epoch:
            out += enc_field(5, WT_VARINT, self.rent_epoch)
        out += enc_field(6, WT_LEN, self.owner)
        return out


@dataclass
class InstrAcctRef:
    index: int = 0
    is_writable: bool = False
    is_signer: bool = False

    @classmethod
    def decode(cls, buf: bytes) -> "InstrAcctRef":
        a = cls()
        for fno, _wt, v in wire_decode(buf):
            if fno == 1:
                a.index = v
            elif fno == 2:
                a.is_writable = bool(v)
            elif fno == 3:
                a.is_signer = bool(v)
        return a

    def encode(self) -> bytes:
        out = enc_field(1, WT_VARINT, self.index)
        if self.is_writable:
            out += enc_field(2, WT_VARINT, 1)
        if self.is_signer:
            out += enc_field(3, WT_VARINT, 1)
        return out


@dataclass
class InstrContext:
    program_id: bytes = b"\x00" * 32
    accounts: list[AcctState] = field(default_factory=list)
    instr_accounts: list[InstrAcctRef] = field(default_factory=list)
    data: bytes = b""
    cu_avail: int = 0
    slot: int = 10  # SlotContext.slot
    features: list[int] = field(default_factory=list)  # EpochContext ids

    @classmethod
    def decode(cls, buf: bytes) -> "InstrContext":
        c = cls(slot=0)
        for fno, _wt, v in wire_decode(buf):
            if fno == 1:
                c.program_id = bytes(v)
            elif fno == 3:
                c.accounts.append(AcctState.decode(v))
            elif fno == 4:
                c.instr_accounts.append(InstrAcctRef.decode(v))
            elif fno == 5:
                c.data = bytes(v)
            elif fno == 6:
                c.cu_avail = v
            elif fno == 8:  # SlotContext
                for f2, _w2, v2 in wire_decode(v):
                    if f2 == 1:
                        c.slot = v2
            elif fno == 9:  # EpochContext { FeatureSet features = 1 }
                for f2, _w2, v2 in wire_decode(v):
                    if f2 == 1:
                        for f3, w3, v3 in wire_decode(v2):
                            if f3 != 1:
                                continue
                            if w3 == WT_I64:
                                c.features.append(v3)
                            elif w3 == WT_LEN:
                                # proto3 packs repeated fixed64 (protoc/
                                # nanopb corpora); 8-byte LE chunks
                                for i in range(0, len(v3) - 7, 8):
                                    c.features.append(
                                        int.from_bytes(v3[i : i + 8],
                                                       "little")
                                    )
        return c

    def encode(self) -> bytes:
        out = enc_field(1, WT_LEN, self.program_id)
        for a in self.accounts:
            out += enc_field(3, WT_LEN, a.encode())
        for ia in self.instr_accounts:
            out += enc_field(4, WT_LEN, ia.encode())
        if self.data:
            out += enc_field(5, WT_LEN, self.data)
        if self.cu_avail:
            out += enc_field(6, WT_VARINT, self.cu_avail)
        out += enc_field(8, WT_LEN, enc_field(1, WT_VARINT, self.slot))
        if self.features:
            feats = b"".join(enc_field(1, WT_I64, f) for f in self.features)
            out += enc_field(9, WT_LEN, enc_field(1, WT_LEN, feats))
        return out


@dataclass
class InstrEffects:
    result: int = 0
    custom_err: int = 0
    modified_accounts: list[AcctState] = field(default_factory=list)
    cu_avail: int = 0
    return_data: bytes = b""

    @classmethod
    def decode(cls, buf: bytes) -> "InstrEffects":
        e = cls()
        for fno, _wt, v in wire_decode(buf):
            if fno == 1:
                # int32 result rides as a varint (possibly sign-extended)
                e.result = v - (1 << 64) if v >= 1 << 63 else v
            elif fno == 2:
                e.custom_err = v
            elif fno == 3:
                e.modified_accounts.append(AcctState.decode(v))
            elif fno == 4:
                e.cu_avail = v
            elif fno == 5:
                e.return_data = bytes(v)
        return e

    def encode(self) -> bytes:
        out = b""
        if self.result:
            out += enc_field(1, WT_VARINT, self.result & ((1 << 64) - 1))
        if self.custom_err:
            out += enc_field(2, WT_VARINT, self.custom_err)
        for a in self.modified_accounts:
            out += enc_field(3, WT_LEN, a.encode())
        if self.cu_avail:
            out += enc_field(4, WT_VARINT, self.cu_avail)
        if self.return_data:
            out += enc_field(5, WT_LEN, self.return_data)
        return out


@dataclass
class InstrFixture:
    input: InstrContext
    output: InstrEffects

    @classmethod
    def decode(cls, buf: bytes) -> "InstrFixture":
        inp, outp = InstrContext(), InstrEffects()
        for fno, _wt, v in wire_decode(buf):
            if fno == 1:
                inp = InstrContext.decode(v)
            elif fno == 2:
                outp = InstrEffects.decode(v)
        return cls(inp, outp)

    def encode(self) -> bytes:
        return enc_field(1, WT_LEN, self.input.encode()) + enc_field(
            2, WT_LEN, self.output.encode()
        )


def load_fixture(path: str) -> InstrFixture:
    with open(path, "rb") as f:
        return InstrFixture.decode(f.read())


# -- runner -------------------------------------------------------------------

# the owner of every sysvar account
SYSVAR_OWNER = b58_decode32("Sysvar1111111111111111111111111111111111111")
# canonical sysvar account addresses -> the names flamenco's TxnCtx uses
SYSVAR_NAMES = {
    b58_decode32("SysvarC1ock11111111111111111111111111111111"): "clock",
    b58_decode32("SysvarRent111111111111111111111111111111111"): "rent",
    b58_decode32("SysvarEpochSchedu1e111111111111111111111111"):
        "epoch_schedule",
    b58_decode32("SysvarS1otHashes111111111111111111111111111"): "slot_hashes",
}


@dataclass
class FixtureDiff:
    ok: bool
    mismatches: list[str]


def run_instr_fixture(fix: InstrFixture) -> FixtureDiff:
    """Replay fix.input through the executor; diff against fix.output."""
    from firedancer_tpu.flamenco.executor import (
        Account, Executor, InstrAccount, InstrError, TxnCtx,
    )
    from firedancer_tpu.flamenco.runtime import default_sysvars

    ctx_accounts = []
    signer = []
    writable = []
    for a in fix.input.accounts:
        ctx_accounts.append(
            Account(
                key=a.address,
                lamports=a.lamports,
                owner=a.owner,
                executable=a.executable,
                data=bytearray(a.data),
            )
        )
        signer.append(False)
        writable.append(False)
    iaccts = []
    for ia in fix.input.instr_accounts:
        if ia.index >= len(ctx_accounts):
            return FixtureDiff(False, ["instr account index out of range"])
        iaccts.append(
            InstrAccount(
                txn_idx=ia.index,
                is_signer=ia.is_signer,
                is_writable=ia.is_writable,
            )
        )
        signer[ia.index] = signer[ia.index] or ia.is_signer
        writable[ia.index] = writable[ia.index] or ia.is_writable

    sysvars = dict(default_sysvars(fix.input.slot))
    for a in fix.input.accounts:
        name = SYSVAR_NAMES.get(a.address)
        if name is not None and a.data:
            sysvars[name] = bytes(a.data)

    cu = fix.input.cu_avail or 200_000
    ctx = TxnCtx(
        accounts=ctx_accounts,
        signer=signer,
        writable=writable,
        budget=cu,
        sysvars=sysvars,
    )
    ex = Executor()
    err: InstrError | None = None
    try:
        ex.execute_instr(ctx, fix.input.program_id, iaccts, fix.input.data)
    except InstrError as e:
        err = e
    except Exception as e:  # untyped escape = harness-visible bug
        return FixtureDiff(
            False, [f"untyped {type(e).__name__}: {e}"]
        )

    mism: list[str] = []
    want = fix.output
    # result: zero/nonzero parity; exact custom code when expected
    if bool(want.result) != bool(err):
        mism.append(
            f"result: expected {'error' if want.result else 'success'}, "
            f"got {'error: ' + str(err) if err else 'success'}"
        )
    if want.custom_err and (err is None or err.custom != want.custom_err):
        mism.append(
            f"custom_err: expected {want.custom_err}, "
            f"got {getattr(err, 'custom', None)}"
        )
    # modified accounts listed must match exactly
    by_addr = {a.key: a for a in ctx_accounts}
    for m in want.modified_accounts:
        got = by_addr.get(m.address)
        if got is None:
            mism.append(f"modified acct {m.address[:4].hex()} not in ctx")
            continue
        if got.lamports != m.lamports:
            mism.append(
                f"acct {m.address[:4].hex()} lamports "
                f"{got.lamports} != {m.lamports}"
            )
        if bytes(got.data) != m.data:
            mism.append(f"acct {m.address[:4].hex()} data differs")
        if got.owner != m.owner:
            mism.append(f"acct {m.address[:4].hex()} owner differs")
        if bool(got.executable) != bool(m.executable):
            mism.append(f"acct {m.address[:4].hex()} executable differs")
    # accounts NOT listed must be unchanged (success paths only: Agave
    # rolls back all writes on error, and so does the txn-level caller
    # here — instruction-level state is only committed on success)
    if not want.result and not err:
        listed = {m.address for m in want.modified_accounts}
        for orig in fix.input.accounts:
            if orig.address in listed:
                continue
            got = by_addr[orig.address]
            if (
                got.lamports != orig.lamports
                or bytes(got.data) != orig.data
                or got.owner != orig.owner
            ):
                mism.append(
                    f"acct {orig.address[:4].hex()} changed but not in "
                    "modified_accounts"
                )
    if want.cu_avail:
        got_avail = cu - ctx.cu_used
        if got_avail != want.cu_avail:
            mism.append(f"cu_avail {got_avail} != {want.cu_avail}")
    if want.return_data:
        if ctx.return_data[1] != want.return_data:
            mism.append("return_data differs")
    return FixtureDiff(not mism, mism)


def run_corpus(root: str) -> dict:
    """Run every .fix under `root`; -> {path: FixtureDiff} (sorted)."""
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for f in sorted(files):
            if not f.endswith(".fix"):
                continue
            p = os.path.join(dirpath, f)
            try:
                out[p] = run_instr_fixture(load_fixture(p))
            except Exception as e:
                out[p] = FixtureDiff(False, [f"load/run: {e}"])
    return out
