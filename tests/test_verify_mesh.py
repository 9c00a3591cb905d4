"""More than one chip behind one verify intake (ISSUE 26):
VerifyStage(devices=...) over four of conftest's virtual CPU devices.

The stage's own path is under test — the native intake sealing slots of
the whole fixed shape, `_place` dealing the packed rows round-robin
straight onto the chips (one array, one callback a chip), the real
jitted program over the mesh with no collective in it, the reap of a
sharded mask dealt back — with arithmetic that costs nothing to compile
(conftest's `toy_verify_ok`: the real program around a lane-wise toy
_verify_ok).  The real kernel compiles for minutes on a CPU: those cases
carry `slow`, and hold the mesh lane to ops/ref's Ed25519 verdicts.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from firedancer_tpu.runtime import verify as rv
from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.runtime.benchg import gen_transfer_pool
from firedancer_tpu.runtime.verify import VerifyStage
from firedancer_tpu.tango import shm
from firedancer_tpu.utils import metrics as fm

N_DEV = 4
BATCH = 16          # 4 lanes a device
MAX_MSG = 256
PER = BATCH // N_DEV
FOLD_BATCH = N_DEV * 128    # the program folds it: (1, 128) a chip


def _devices():
    import jax

    return jax.devices()[:N_DEV]


def _ringless(devices, batch=BATCH):
    return VerifyStage("m", batch=batch, max_msg_len=MAX_MSG,
                       native_client=False, devices=devices)


# -- the constructor ------------------------------------------------------------


def test_devices_builds_the_row_sharding_over_the_serving_planes_axis():
    from jax.sharding import PartitionSpec as P

    from firedancer_tpu.parallel.mesh import AXIS

    st = _ringless(N_DEV)
    rows = st._row_sharding
    assert st.mesh_devices == N_DEV
    assert rows.spec == P(AXIS, None)      # the packed rows, by row
    # a count: the first n local devices
    assert list(rows.mesh.devices.ravel()) == _devices()
    assert st.metrics.get("mesh_devices") == N_DEV
    names = st.metrics.schema.names()
    assert {f"shard_elems_s{i}" for i in range(N_DEV)} <= names
    assert "mesh_devices" in names


@pytest.mark.parametrize("devices", [None, 1])
def test_one_device_is_the_default_device_and_has_no_mesh(devices):
    st = _ringless(devices)
    assert st._row_sharding is None and st.mesh_devices == 1
    assert st.metrics.get("mesh_devices") == 1
    assert "mesh_devices" in VerifyStage.metrics_schema().names()
    assert "shard_elems_s0" not in st.metrics.schema.names()


def test_devices_must_divide_the_batch_and_own_the_dispatch():
    with pytest.raises(ValueError, match="does not divide"):
        VerifyStage("m", batch=BATCH, devices=3, native_client=False)
    with pytest.raises(ValueError, match="comb bank"):
        VerifyStage("m", batch=BATCH, devices=N_DEV, comb_slots=4,
                    native_client=False)
    # a batch the program folds to (batch // 128, 128): the rows have
    # to divide over the devices too (3 rows over 2: no; 4 over 2: yes)
    with pytest.raises(ValueError, match="3 rows of 128 lanes"):
        _ringless(2, 3 * 128)
    assert _ringless(2, 4 * 128).metrics.get(fm.KERNEL_FOLD_LANES) == 128
    assert _ringless(2, 3 * 64).metrics.get(fm.KERNEL_FOLD_LANES) == 0


def test_config_asks_for_the_mesh():
    from firedancer_tpu.utils import config as fc

    assert fc.load_config().verify.devices == 1
    cfg = fc.load_config(overrides={"verify": {"devices": 4, "batch": 16}})
    assert cfg.verify.devices == 4
    with pytest.raises(fc.ConfigError):
        fc.load_config(overrides={"verify": {"devices": 3, "batch": 16}})
    with pytest.raises(fc.ConfigError):
        fc.load_config(overrides={"verify": {"devices": 0}})


def test_the_pipeline_builder_passes_the_mesh_to_its_verify_stages():
    from firedancer_tpu.models.leader import build_leader_pipeline_from_config
    from firedancer_tpu.utils import config as fc

    cfg = fc.load_config(overrides={
        "verify": {"devices": N_DEV, "batch": BATCH, "max_msg_len": MAX_MSG}})
    pipe = build_leader_pipeline_from_config(
        cfg, verify_precomputed=True, pool_size=8, gen_limit=8)
    try:
        assert [v.mesh_devices for v in pipe.verifies] == [N_DEV]
    finally:
        pipe.close()


@pytest.mark.parametrize("intake", ["native", "python"])
def test_the_pipeline_over_a_mesh_stores_exactly_the_txns_that_verify(
        intake, toy_verify_ok, monkeypatch):
    """build_leader_pipeline(verify_devices=4) end to end, on both
    intakes, with the program (toy arithmetic) in the path and not the
    all-pass mask: of 64 transfers 7 have a signature bit flipped, and
    exactly those are missing from the stored block; every chip was
    dealt lanes."""
    from firedancer_tpu.models.leader import build_leader_pipeline
    from firedancer_tpu.runtime.poh_stage import parse_entry
    from firedancer_tpu.runtime.shred_stage import deshred_entry_batch

    if intake == "native" and not vn.available():
        pytest.skip("native verify client unavailable")
    monkeypatch.setenv(vn.ENV_SWITCH, "1" if intake == "native" else "0")
    n, bad = 64, (3, 11, 12, 30, 41, 55, 63)
    good = [t for t in gen_transfer_pool(192, n_payers=8, n_dests=64)
            if _toy_txn_ok(t, toy_verify_ok)][:n]
    assert len(good) == n
    txns = list(good)
    for i in bad:           # byte 0 is the signature count, 1.. the sig
        txns[i] = bytes([txns[i][0], txns[i][1] ^ 1]) + txns[i][2:]
        assert not _toy_txn_ok(txns[i], toy_verify_ok)
    pipe = build_leader_pipeline(
        verify_devices=N_DEV, batch=BATCH, max_msg_len=MAX_MSG,
        pool_size=8, gen_limit=n, n_payers=8)
    try:
        pipe.benchg.pool = txns
        v = pipe.verifies[0]
        assert (v._sweep_client is not None) == (intake == "native")
        assert v.mesh_devices == N_DEV and not v.precomputed_ok
        pipe.run(until_txns=n - len(bad), max_iters=200_000)
        v.during_housekeeping()
        c = v.metrics.get
        assert c("verify_fail") == len(bad)
        assert c("txn_verified") == n - len(bad)
        assert sum(b.metrics.get("txn_exec") for b in pipe.banks) \
            == n - len(bad)
        shards = [c(f"shard_elems_s{i}") for i in range(N_DEV)]
        assert all(shards) and sum(shards) == c("batch_elems") == n
        stored = {p for e in deshred_entry_batch(
                      pipe.store.entry_batch_bytes(1))
                  for p in parse_entry(e)[2]}
        assert stored == set(good) - {good[i] for i in bad}
    finally:
        pipe.close()


# -- placement and verdicts, the dispatch alone ------------------------------------


def _toy_batch(n: int, seed: int, toy_lane_ok, batch: int = BATCH):
    """Random packed rows, `n` of them real and the pad rows random too
    (a reused slot's are an earlier batch's); -> (rows, the toy's
    verdicts on every row)."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 256, (batch, vn.row_width(MAX_MSG)),
                        dtype=np.uint8)
    ln = vn.row_lens(rows, MAX_MSG)
    ln[:] = rng.integers(1, MAX_MSG, (batch,))
    tail = rows[:, MAX_MSG:].astype(np.int64)
    want = toy_lane_ok(ln, rows[:, 0], tail[:, 0], tail[:, 63],
                       tail[:, 64], tail[:, 95])
    assert want[:n].any() or n < 3
    return rows, want


def _signed_batch(n: int, seed: int, batch: int = BATCH):
    """`n` rows of honestly signed messages (seeded keys, messages of
    seeded lengths), every third with one seeded corrupted signature
    bit, the pad rows zero; -> (rows, ops/ref's verdicts)."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    rng = np.random.default_rng(seed)
    msg = np.zeros((n, MAX_MSG), dtype=np.uint8)
    ln = np.zeros((n,), dtype=np.int32)
    sig = np.zeros((n, 64), dtype=np.uint8)
    pk = np.zeros((n, 32), dtype=np.uint8)
    want = np.zeros((batch,), dtype=bool)
    for i in range(n):
        secret = hashlib.sha256(b"mesh%d-%d" % (seed, i)).digest()
        pub = ref.public_key(secret)
        m = rng.bytes(int(rng.integers(1, MAX_MSG + 1)))
        s = bytearray(ref.sign(secret, m))
        if i % 3 == 1:
            bit = int(rng.integers(0, 512))
            s[bit // 8] ^= 1 << (bit % 8)
        msg[i, :len(m)] = np.frombuffer(m, dtype=np.uint8)
        ln[i] = len(m)
        sig[i] = np.frombuffer(bytes(s), dtype=np.uint8)
        pk[i] = np.frombuffer(pub, dtype=np.uint8)
        want[i] = ref.verify(m, bytes(s), pub)
    assert want[:n].any() and not want[:n].all() or n < 2
    return vn.pack_rows(msg, ln, sig, pk, batch=batch), want


# fills (chip i is dealt elements i, i + 4, ...): full; three chips one
# short; every chip one short; 2, 1, 1, 1; the last chip wholly empty;
# one lane; nothing
FILLS = [BATCH, BATCH - 3, BATCH - N_DEV, PER + 1, N_DEV - 1, 1, 0]


def _dispatch_both(fill: int, make, *args):
    rows, want = make(fill, 1000 + fill, *args)
    got = {}
    for name, devices in (("one", None), ("mesh", N_DEV)):
        st = _ringless(devices, len(rows))
        mask = st._device_verify(rv._Life(rv._now_ns()), rows)
        got[name] = (mask, st._mask_of(mask))
    return got, want


def _check_verdicts(got, want, fill: int):
    """The dealt-back mesh mask is the one-device mask, lane for lane,
    pad lanes and all; on the real lanes both are the wanted verdicts."""
    for name in ("one", "mesh"):
        _fut, mask = got[name]
        assert mask.dtype == np.bool_ and mask.shape == want.shape
        assert (mask[:fill] == want[:fill]).all(), name   # booleans: exact
    assert (got["one"][1] == got["mesh"][1]).all()


@pytest.mark.parametrize("fill", FILLS)
def test_mesh_dispatch_places_shards_and_agrees_with_one_device(
        fill, exchange, toy_verify_ok):
    got, want = _dispatch_both(fill, _toy_batch, toy_verify_ok)
    _check_verdicts(got, want, fill)
    assert (got["mesh"][1] == want).all()      # every row, pad rows too
    # one program each, given one array each: one device_put of the
    # whole batch on one device; over the mesh one array made of one
    # callback a chip, chip i given rows i, i + 4, ... and nothing else
    assert exchange.programs == 2
    (one,), (mesh,) = exchange.puts, exchange.made
    rows, _want = _toy_batch(fill, 1000 + fill, toy_verify_ok)
    assert len(one.sharding.device_set) == 1
    assert one.shape == mesh.shape == rows.shape
    assert one.dtype == mesh.dtype == np.uint8
    assert (np.asarray(one) == rows).all()
    devs = _devices()
    assert mesh.sharding.device_set == set(devs)
    assert len(exchange.callbacks) == N_DEV
    assert sorted(idx[0].start for idx in exchange.callbacks) \
        == [i * PER for i in range(N_DEV)]
    by_dev = {s.device: s for s in mesh.addressable_shards}
    for i, d in enumerate(devs):
        sh = by_dev[d]
        assert sh.index[0] == slice(i * PER, (i + 1) * PER)
        assert (np.asarray(sh.data) == rows[i::N_DEV]).all()
    # the mask comes back on the lanes' shards, and is all that does
    fut = got["mesh"][0].fut
    assert fut.sharding.device_set == set(devs)
    assert {s.data.shape for s in fut.addressable_shards} == {(PER,)}
    assert [f.shape for f in exchange.fetches] == [(BATCH,)] * 2


def _collectives(hlo_text: str) -> dict:
    import re

    return {op: len(re.findall(rf"= [^\n]*\b{op}(-start)?\(", hlo_text))
            for op in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute", "reduce-scatter")}


@pytest.mark.parametrize("batch, lanes", [(BATCH, (BATCH,)),
                                          (FOLD_BATCH, (N_DEV, 128))])
def test_the_mesh_module_holds_no_collective(batch, lanes, toy_verify_ok,
                                             monkeypatch):
    """The program as the mesh dispatch compiles it — the packed rows
    sharded by row, partitioned by that sharding alone — over four
    devices: the unpack's transpose moves the sharded axis and nothing
    between chips; the mask stays on the lanes' shards.  At 4 x 128
    lanes the program folds its batch (ops/sigverify.fold_batch): the
    sharded axis becomes the rows of the fold, one (1, 128) a chip,
    still with nothing between chips, and the mask comes back (B,)
    for _mask_of to deal the lanes back in order."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.parallel.mesh import AXIS

    seen = []
    toy = sv._verify_ok

    def spy(msg, msg_len, sig, pubkey, *, max_msg_len):
        seen.append(msg_len.shape)
        return toy(msg, msg_len, sig, pubkey, max_msg_len=max_msg_len)

    monkeypatch.setattr(sv, "_verify_ok", spy)
    st = _ringless(N_DEV, batch)
    assert st.metrics.get(fm.KERNEL_FOLD_LANES) == (128 if len(lanes) == 2
                                                    else 0)
    assert st._row_sharding.spec == P(AXIS, None)
    rows = jax.ShapeDtypeStruct((batch, vn.row_width(MAX_MSG)), jnp.uint8,
                                sharding=st._row_sharding)
    compiled = sv.ed25519_verify_batch_fused.lower(
        rows, max_msg_len=MAX_MSG).compile()
    assert seen == [lanes]
    assert not any(_collectives(compiled.as_text()).values())
    assert compiled.output_shardings.spec == P(AXIS)
    # dispatched: element e dealt to chip e % 4 and its verdict dealt
    # back to index e, pad rows and all
    real, want = _toy_batch(batch - 3, 7, toy_verify_ok, batch)
    fut = st._device_verify(rv._Life(rv._now_ns()), real)
    assert {s.data.shape for s in fut.addressable_shards} \
        == {(batch // N_DEV,)}
    mask = st._mask_of(fut)
    assert mask.shape == (batch,) and (mask == want).all()
    # the count of ops that would cross chips, on a program that has one
    assert _collectives("x = f32[] all-reduce(y)\n"
                        "z = all-gather-start(w)")["all-reduce"] == 1


@pytest.mark.slow
@pytest.mark.parametrize("batch, fill", [(BATCH, BATCH - 3),
                                         (BATCH, N_DEV - 1),
                                         (FOLD_BATCH, FOLD_BATCH - 131)])
def test_mesh_lane_equals_the_reference_and_the_single_device_lane(
        batch, fill):
    """The real program (ed25519_verify_batch_fused) over the mesh:
    the mask equals ops/ref's verdicts and the one-device lane's, with
    corrupted signatures, the last shard partly and wholly empty; at
    4 x 128 lanes the folded program, a chip's shard one (1, 128) row."""
    got, want = _dispatch_both(fill, _signed_batch, batch)
    _check_verdicts(got, want, fill)
    assert not got["mesh"][1][fill:].any()    # a zero row never verifies


# -- frags through the native-armed stage ------------------------------------------


def _drain(cons, got: list) -> None:
    while True:
        res = cons.poll()
        if res in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
            return
        payload = bytes(res[1])
        got.append(payload[:int.from_bytes(payload[-2:], "little")])


def _toy_txn_ok(t: bytes, toy_lane_ok) -> bool:
    """The toy's verdict on a one-signature transaction's element."""
    from firedancer_tpu.protocol import txn as ft

    d = ft.txn_parse(t)
    msg, sig, pk = d.message(t), d.signatures(t)[0], d.signers(t)[0]
    return bool(toy_lane_ok(len(msg), msg[0], sig[0], sig[63], pk[0],
                            pk[31]))


@pytest.mark.parametrize("mask", ["allpass", "toy"])
def test_every_txn_leaves_a_native_armed_mesh_stage_exactly_once(
        mask, request):
    if not vn.available():
        pytest.skip("native verify client unavailable")
    if mask == "toy":
        exchange = request.getfixturevalue("exchange")
        lane_ok = request.getfixturevalue("toy_verify_ok")
    pool = gen_transfer_pool(120, n_payers=12, n_dests=64)
    uid = shm.fresh_uid()
    lin = shm.ShmLink.create(f"tvm_i_{uid}", depth=256, mtu=1232, n_fseq=1)
    lout = shm.ShmLink.create(f"tvm_o_{uid}", depth=256, mtu=4096, n_fseq=1)
    st = None
    try:
        prod = shm.make_producer(lin)
        st = VerifyStage(
            "verify0", ins=[shm.make_consumer(lin, lazy=8)],
            outs=[shm.make_producer(lout)], batch=BATCH, max_msg_len=MAX_MSG,
            batch_deadline_s=0.001, max_inflight=3, devices=N_DEV,
            precomputed_ok=(mask == "allpass"), native_client=True)
        cons = shm.make_consumer(lout, lazy=4)
        assert st._sweep_client is not None          # armed, over a mesh
        assert st._sweep_client.batch == BATCH       # the whole fixed shape
        got: list = []
        fed = 0
        # bursts of uneven size, so batches close full and on the deadline
        bursts = [1, 7, 16, 3, 29, 16, 2, 11, 35]
        assert sum(bursts) == len(pool)
        for n in bursts:
            for _ in range(n):
                assert prod.try_publish(pool[fed], sig=fed, tsorig=0)
                fed += 1
            for _ in range(400):
                st.run_once()
                _drain(cons, got)
        st.flush()
        _drain(cons, got)
        st.during_housekeeping()
        c = st.metrics.get
        want = [t for t in pool
                if mask == "allpass" or _toy_txn_ok(t, lane_ok)]
        assert 0 < len(want) and (mask == "allpass" or len(want) < len(pool))
        assert sorted(got) == sorted(want) and len(set(got)) == len(got)
        assert c("txn_verified") == len(want)
        assert c("verify_fail") == len(pool) - len(want)
        assert c("batch_elems") == len(pool)
        shards = [c(f"shard_elems_s{i}") for i in range(N_DEV)]
        assert sum(shards) == c("batch_elems")
        # dealt round-robin: chip i never got more than chip i - 1, and
        # the first at most one a batch more than the last
        assert shards == sorted(shards, reverse=True)
        assert 0 <= shards[0] - shards[-1] <= c("batches")
        assert shards[-1] >= len(pool) // N_DEV - c("batches")
        assert sum(c(k) for k in rv._CLOSE_COUNTERS) == c("batches") >= 9
        assert c("batch_close_full") >= 2 and c("batch_close_deadline") >= 2
        # behind a running step goes a full one, the step sealed right
        # behind a full one, and what flush() sends
        assert c("batch_queued_behind") <= 2 * c("batch_close_full") + 1
        assert c("mesh_devices") == N_DEV
        phases = [c(f"batch_{p}_ns") for p in fm.BATCH_PHASES]
        if mask == "toy":
            assert all(v > 0 for v in phases)        # all seven read
            # a step: one module, given one array made of one callback
            # a chip (the warm-up none: the stage was never warmed),
            # handing back one mask, fetched once
            n = c("batches")
            assert exchange.programs == n
            assert len(exchange.made) == n and not exchange.puts
            assert len(exchange.callbacks) == N_DEV * n
            assert len(exchange.fetches) == n
        else:
            assert all(v >= 0 for v in phases) and phases[0] > 0
    finally:
        if st is not None:
            st.ins, st.outs = [], []
            st.drop_native_views()
        lin.close()
        lout.close()


# -- the real program, compiled for the real chips ---------------------------------


@pytest.fixture(scope="module")
def v5e_2x2():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.slow
def test_the_mesh_module_has_no_collective_on_a_v5e_host(v5e_2x2):
    """The real program at the deployment's shape (4 x 1,024 rows of
    356 bytes, sharded by row as the mesh dispatch places them),
    partitioned by its argument's sharding alone, compiled for four
    described v5e chips: no collective at all, and nothing gathers the
    batch."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.parallel import mesh as pm

    mesh = Mesh(np.array(v5e_2x2.devices), (pm.AXIS,))
    b, mm = 4096, 256
    rows = jax.ShapeDtypeStruct(
        (b, vn.row_width(mm)), jnp.uint8,
        sharding=NamedSharding(mesh, P(pm.AXIS, None)))
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = sv.ed25519_verify_batch_fused.lower(
            rows, max_msg_len=mm).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    assert not any(_collectives(compiled.as_text()).values())
    assert compiled.output_shardings.is_equivalent_to(
        NamedSharding(mesh, P(pm.AXIS)), 1)
