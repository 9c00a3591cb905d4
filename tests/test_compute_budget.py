"""Compute-budget ENFORCEMENT (the r3 gap: limits were parsed for pack
costing but the VM always ran with 200k).

Covers: SetComputeUnitLimit drives TxnCtx/VM budget through the full
runtime; a CU-limited txn aborts at its requested budget; RequestHeapFrame
sizes the VM heap; builtins charge their fixed cost."""

import hashlib

import pytest

from firedancer_tpu.flamenco.executor import (
    Account,
    BPF_LOADER_PROGRAM,
    Executor,
    InstrAccount,
    InstrError,
    TxnCtx,
)
from firedancer_tpu.flamenco.runtime import (
    TXN_ERR_PROGRAM,
    TXN_SUCCESS,
    acct_build,
    execute_block,
)
from firedancer_tpu.funk import Funk
from firedancer_tpu.ops.ref import ed25519_ref as ref
from firedancer_tpu.pack.cost import (
    COMPUTE_BUDGET_PROGRAM,
    DEFAULT_HEAP_SIZE,
    txn_budget_fee,
)
from firedancer_tpu.protocol import txn as ft
from tests.test_sbpf import build_elf, ins


def keypair(tag: bytes):
    secret = hashlib.sha256(tag).digest()
    return secret, ref.public_key(secret)


def _bh(tag: bytes) -> bytes:
    return hashlib.sha256(tag).digest()


def _set_cu_limit(units: int) -> bytes:
    return bytes([2]) + units.to_bytes(4, "little")


def _req_heap(size: int) -> bytes:
    return bytes([1]) + size.to_bytes(4, "little")


def test_txn_budget_fee_resolution():
    secret, payer = keypair(b"cb")
    prog_key = hashlib.sha256(b"cb-prog").digest()

    def build(cb_datas, n_other=1):
        instrs = [ft.InstrSpec(program_id=1, accounts=bytes([0]), data=d)
                  for d in cb_datas]
        instrs += [ft.InstrSpec(program_id=2, accounts=bytes([0]), data=b"x")
                   for _ in range(n_other)]
        msg = ft.message_build(
            version=ft.VLEGACY, signature_cnt=1, readonly_signed_cnt=0,
            readonly_unsigned_cnt=2,
            acct_addrs=[payer, COMPUTE_BUDGET_PROGRAM, prog_key],
            recent_blockhash=_bh(b"bh"), instrs=instrs,
        )
        p = ft.txn_assemble([ref.sign(secret, msg)], msg)
        return p, ft.txn_parse(p)

    # explicit limit wins
    p, t = build([_set_cu_limit(77_000)])
    assert txn_budget_fee(p, t) == (77_000, DEFAULT_HEAP_SIZE, 0)
    # default: 200k per instruction (including the CB instr itself, capped)
    p, t = build([], n_other=2)
    assert txn_budget_fee(p, t) == (400_000, DEFAULT_HEAP_SIZE, 0)
    # heap frame
    p, t = build([_req_heap(64 * 1024)])
    assert txn_budget_fee(p, t) == (200_000, 64 * 1024, 0)
    # the priority fee: ceil(limit x price / 10^6) lamports
    p, t = build([_set_cu_limit(20_000),
                  b"\x03" + (1_000_001).to_bytes(8, "little")])
    assert txn_budget_fee(p, t) == (20_000, DEFAULT_HEAP_SIZE, 20_001)
    # duplicate SetComputeUnitLimit = malformed
    p, t = build([_set_cu_limit(1), _set_cu_limit(2)])
    assert txn_budget_fee(p, t) is None


def _loop_elf(iters: int) -> bytes:
    """r1 = iters; loop { r1 -= 1; if r1 != 0 goto loop }; exit.
    Costs ~2*iters CU (one per insn)."""
    text = (
        ins(0xB7, dst=1, imm=iters)          # mov r1, iters
        + ins(0x17, dst=1, imm=1)            # sub r1, 1
        + ins(0x55, dst=1, off=-2, imm=0)    # jne r1, 0, -2
        + ins(0xB7, dst=0, imm=0)            # mov r0, 0
        + ins(0x95)                          # exit
    )
    return build_elf(text)


def test_cu_limited_txn_aborts_at_requested_budget():
    """e2e: same program, generous limit passes, tight limit aborts."""
    funk = Funk()
    secret, payer = keypair(b"cu-payer")
    funk.rec_insert(None, payer, acct_build(10_000_000))
    prog_key = hashlib.sha256(b"cu-prog").digest()
    funk.rec_insert(
        None, prog_key,
        acct_build(1, data=_loop_elf(5_000), owner=BPF_LOADER_PROGRAM,
                   executable=True),
    )

    def run(cu_limit, nonce):
        msg = ft.message_build(
            version=ft.VLEGACY, signature_cnt=1, readonly_signed_cnt=0,
            readonly_unsigned_cnt=2,
            acct_addrs=[payer, COMPUTE_BUDGET_PROGRAM, prog_key],
            recent_blockhash=_bh(b"bh%d" % nonce),
            instrs=[
                ft.InstrSpec(program_id=1, accounts=bytes([0]),
                             data=_set_cu_limit(cu_limit)),
                ft.InstrSpec(program_id=2, accounts=bytes([0]), data=b""),
            ],
        )
        txn = ft.txn_assemble([ref.sign(secret, msg)], msg)
        return execute_block(funk, slot=5 + nonce, txns=[txn]).results[0]

    ok = run(50_000, 0)  # ~10k CU needed
    assert ok.status == TXN_SUCCESS, ok
    tight = run(2_000, 1)  # loop needs ~10k: must abort, fee still paid
    assert tight.status == TXN_ERR_PROGRAM
    assert tight.fee > 0


def test_builtins_charge_fixed_cost():
    ex = Executor()
    a = Account(b"k" * 32, 1000, ft.SYSTEM_PROGRAM, False, bytearray())
    b = Account(b"j" * 32, 0, ft.SYSTEM_PROGRAM, False, bytearray())
    ctx = TxnCtx(accounts=[a, b], signer=[True, False],
                 writable=[True, True], budget=100)  # system costs 150
    ia = [InstrAccount(0, True, True), InstrAccount(1, False, True)]
    data = (2).to_bytes(4, "little") + (5).to_bytes(8, "little")
    with pytest.raises(InstrError, match="compute budget"):
        ex.execute_instr(ctx, ft.SYSTEM_PROGRAM, ia, data)
    ctx2 = TxnCtx(accounts=[a, b], signer=[True, False],
                  writable=[True, True], budget=1000)
    ex.execute_instr(ctx2, ft.SYSTEM_PROGRAM, ia, data)
    assert ctx2.cu_used == 150


def test_heap_frame_sizes_vm_heap():
    """sol_alloc_free_ can reach the requested heap, not one byte more."""
    from firedancer_tpu.flamenco import vm as fvm
    from firedancer_tpu.protocol import sbpf

    # call sol_alloc_free_(40*1024, 0) -> NULL under default heap,
    # non-NULL under a 64K RequestHeapFrame
    text = (
        ins(0xB7, dst=1, imm=40 * 1024)  # r1 = size
        + ins(0xB7, dst=2, imm=0)        # r2 = free_addr (0 = alloc)
        + ins(0x85, imm=fvm.SYSCALL_SOL_ALLOC_FREE)
        + ins(0x95)
    )
    prog = sbpf.load(build_elf(text))
    v = fvm.Vm(program=prog, budget=10_000)
    fvm.register_default_syscalls(v)
    assert v.run() == 0  # default 32K heap: allocation fails -> NULL
    v2 = fvm.Vm(program=prog, budget=10_000, heap_size=64 * 1024)
    fvm.register_default_syscalls(v2)
    assert v2.run() != 0
