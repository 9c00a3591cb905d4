"""The pool, the corrupted set and the schedule are pure functions of
the seed; the transfer bytes are the program's own."""

import numpy as np

from harness import reference
from harness import traffic as T


def _pool(seed, n=256):
    return T.PoolJob(seed, n, 64, 1024, workers=1).result()


def test_pool_is_the_programs_transfer_byte_for_byte():
    from firedancer_tpu.runtime.benchg import gen_transfer_pool

    buf = _pool(7, 70)
    want = gen_transfer_pool(70, seed=T.genesis_seed(7), n_payers=64,
                             n_dests=1024)
    assert [T.txn_bytes(buf, i) for i in range(70)] == want


def test_pool_corruption_schedule_pure_functions_of_seed():
    # a large seed: the driver's are a little over 2**31
    for seed in (3, 2**31 + 12345):
        a, b = _pool(seed), _pool(seed)
        assert (a == b).all()
        bad_a = T.corrupt(a, 256, 128, seed)
        bad_b = T.corrupt(b, 256, 128, seed)
        assert (bad_a == bad_b).all() and (a == b).all()
        assert len(bad_a) == 2 and bad_a[0] < 128 <= bad_a[1] < 256
        d1 = T.poisson_due_ns(4000.0, 10_000, seed)
        d2 = T.poisson_due_ns(4000.0, 10_000, seed)
        assert (d1 == d2).all() and (np.diff(d1) >= 0).all()
        # the rate is the cell's whatever the seed
        assert abs(d1[-1] / 1e9 - 2.5) < 0.15
    assert (T.poisson_due_ns(4000.0, 100, 1)
            != T.poisson_due_ns(4000.0, 100, 2)).any()
    assert (_pool(1, 8) != _pool(2, 8)).any()


def test_spawned_signers_agree_with_inline():
    a = T.PoolJob(11, 3 * T.CHUNK + 5, 64, 1024, workers=2).result()
    b = T.PoolJob(11, 3 * T.CHUNK + 5, 64, 1024, workers=1).result()
    assert (a == b).all()


def test_reference_rejects_exactly_the_corrupted():
    buf = _pool(5)
    bad = set(T.corrupt(buf, 256, 64, 5).tolist())
    got = reference.verdicts(buf, range(256))
    assert {i for i, ok in got.items() if not ok} == bad and len(bad) == 4
    # and the program's plain reference says the same
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    for i in sorted(bad) + [0, 1]:
        p = T.txn_bytes(buf, i)
        assert ref.verify(p[T.MSG_OFF:], p[1:65],
                          p[T.PAYER_OFF:T.PAYER_OFF + 32]) == got[i]
