"""The pool, the corrupted set, the order and the schedule are pure
functions of the seed; the `transfer` shape's bytes are the program's
own, and what the parent's harness/traffic.py made (digests taken from
commit 236f618 with its own code: PoolJob(seed, 300, 64, 1024),
corrupt(.., 300, 128, seed), poisson_due_ns(4000, 10000, seed))."""

import hashlib

import numpy as np
import pytest

from harness import reference
from harness import traffic as T
from harness.manifest import Manifest

MAN = Manifest()
TRAFFIC = {"rate_per_s": 4000.0}
ACCOUNTS = {"n_payers": 64, "n_dests": 1024}
SHAPE = MAN.shape(TRAFFIC)
PARENT = {   # seed: (pool, bad rows, pool after corruption, schedule)
    3: ("9df8bd4601d26094ffbf2ecbee84bc71fed2326053e909bdcfb4c204526db22a",
        [2, 169],
        "0e18f6df4ae5b443de90bfbf79fca96d1c3f0d633a77ee1ba18686ff0e19db3d",
        "74ea7a951ee4441db90e46e45b68ebb3a4a7405e50db13b5d30614f6ffba93e3"),
    2**31 + 12345: (
        "3bea4adbc574b252f6de439ba8f860c125435edf06d51b3707e7843c7a485829",
        [5, 159],
        "c93c18d1622d9ab382599c28b9d0026e40ec4a82d7ac3ba9d5bc3d38e35f8a39",
        "ee614b921cae47d3afc5f71cd9c0bd72e9b8dce3c0e805a06ec6edf21913a4f9"),
}


def _pool(seed, n=256, workers=1):
    return T.PoolJob(MAN.shape_path(TRAFFIC), seed, n, ACCOUNTS, TRAFFIC,
                     workers=workers).result()


def _sha(a: np.ndarray) -> str:
    return hashlib.sha256(a.tobytes()).hexdigest()


def test_pool_is_the_programs_transfer_byte_for_byte():
    from firedancer_tpu.runtime.benchg import gen_transfer_pool

    pool = _pool(7, 70)
    want = gen_transfer_pool(70, seed=T.genesis_seed(7), n_payers=64,
                             n_dests=1024)
    assert [pool.row(i) for i in range(70)] == want
    assert (pool.len == SHAPE.TXN_SZ).all() and (pool.sigs == 1).all()
    assert pool.classes == ("transfer",) and not pool.cls.any()


@pytest.mark.parametrize("seed", sorted(PARENT))
def test_transfer_shape_and_poisson_make_what_the_parent_made(seed):
    # a large seed: the driver's are a little over 2**31
    before, bad, after, schedule = PARENT[seed]
    pool = _pool(seed, 300)
    assert _sha(pool.buf) == before
    assert SHAPE.corrupt(pool, 128, seed).tolist() == bad
    assert _sha(pool.buf) == after
    assert (SHAPE.order(pool, seed, TRAFFIC) == np.arange(300)).all()
    due = MAN.arrivals(TRAFFIC).due_ns(TRAFFIC, 10_000, seed)
    assert _sha(due) == schedule


def test_pool_corruption_schedule_pure_functions_of_seed():
    due_ns = MAN.arrivals(TRAFFIC).due_ns
    for seed in (3, 2**31 + 12345):
        a, b = _pool(seed), _pool(seed)
        assert (a.buf == b.buf).all()
        bad_a = SHAPE.corrupt(a, 128, seed)
        bad_b = SHAPE.corrupt(b, 128, seed)
        assert (bad_a == bad_b).all() and (a.buf == b.buf).all()
        assert len(bad_a) == 2 and bad_a[0] < 128 <= bad_a[1] < 256
        d1 = due_ns(TRAFFIC, 10_000, seed)
        assert (d1 == due_ns(TRAFFIC, 10_000, seed)).all()
        assert (np.diff(d1) >= 0).all()
        # the rate is the cell's whatever the seed
        assert abs(d1[-1] / 1e9 - 2.5) < 0.15
    assert (due_ns(TRAFFIC, 100, 1) != due_ns(TRAFFIC, 100, 2)).any()
    assert (_pool(1, 8).buf != _pool(2, 8).buf).any()


def test_spawned_signers_agree_with_inline():
    a = _pool(11, 3 * T.CHUNK + 5, workers=2)
    b = _pool(11, 3 * T.CHUNK + 5, workers=1)
    assert (a.buf == b.buf).all() and (a.off == b.off).all()
    assert a.n == 3 * T.CHUNK + 5 and (a.off == np.arange(a.n) * 215).all()


def test_genesis_is_what_the_leader_funds():
    assert SHAPE.genesis(ACCOUNTS, 9) == {"seed": b"bench9", "n_payers": 64}


def test_a_pool_over_the_mtu_or_eight_signatures_is_refused():
    job = T.PoolJob(MAN.shape_path(TRAFFIC), 1, 4, ACCOUNTS, TRAFFIC,
                    workers=1)
    job._parts[0].sigs[2] = 9
    with pytest.raises(RuntimeError):
        job.result()


def test_reference_rejects_exactly_the_corrupted():
    pool = _pool(5)
    bad = set(SHAPE.corrupt(pool, 64, 5).tolist())
    got = reference.verdicts(pool, range(256))
    assert {i for i, ok in got.items() if not ok} == bad and len(bad) == 4
    # and the program's plain reference says the same
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    for i in sorted(bad) + [0, 1]:
        sigs, pks, msg = reference.split(pool.row(i))
        assert ref.verify(msg, sigs[0], pks[0]) == got[i]
