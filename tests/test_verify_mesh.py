"""More than one chip behind one verify intake (ISSUE 26):
VerifyStage(devices=...) over four of conftest's virtual CPU devices.

The stage's own path is under test — the native intake sealing slots of
the whole fixed shape, `_place` dealing each array's columns round-robin
straight onto its shards, one jitted program over the mesh, the reap of
a sharded mask dealt back — with a program that costs nothing to
compile (`toy`: a lane passes iff its signature's first byte is even;
the real program's pad mask and ok-count).  The real kernel compiles for minutes on a CPU: those cases
carry `slow`, and hold the mesh lane to ops/ref's Ed25519 verdicts.
"""

from __future__ import annotations

import functools
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
SIG_OFF = 1         # a 1-signature transaction: count byte, then the signature


def _devices():
    import jax

    return jax.devices()[:N_DEV]


@pytest.fixture
def toy_program(monkeypatch):
    """ops/sigverify.verify_dispatch replaced by a lane-wise program of
    the same signature that compiles in no time.  -> the argument
    tuples it was called with."""
    import jax
    import jax.numpy as jnp

    from firedancer_tpu.ops import sigverify as sv

    @functools.partial(jax.jit, static_argnames=("max_msg_len",))
    def toy_fused(msg, msg_len, sig, pk, n_real, *, max_msg_len):
        ok = ((sig[0].astype(jnp.int32) & 1) == 0) & (msg_len >= 0) \
            & (msg[0] == msg[0]) & (pk[0] == pk[0])
        lane = jnp.arange(ok.shape[0], dtype=jnp.int32)
        ok = ok & (lane < n_real)
        return ok, jnp.sum(ok.astype(jnp.int32))

    calls = []

    def dispatch(kernel, msg, msg_len, sig, pk, n_real, *, max_msg_len):
        calls.append((msg, msg_len, sig, pk, n_real))
        if getattr(n_real, "ndim", 0) == 0:
            n_real = jnp.int32(n_real)
        return toy_fused(msg, msg_len, sig, pk, n_real,
                         max_msg_len=max_msg_len)

    monkeypatch.setattr(sv, "verify_dispatch", dispatch)
    return calls


def _ringless(devices):
    return VerifyStage("m", batch=BATCH, max_msg_len=MAX_MSG,
                       native_client=False, devices=devices)


# -- the constructor ------------------------------------------------------------


def test_devices_builds_the_serving_planes_lane_shardings():
    from jax.sharding import PartitionSpec as P

    from firedancer_tpu.parallel.mesh import AXIS

    st = _ringless(N_DEV)
    rows, vec = st._lane_shardings
    assert st.mesh_devices == N_DEV
    assert rows.spec == P(None, AXIS) and vec.spec == P(AXIS)
    # a count: the first n local devices
    assert list(rows.mesh.devices.ravel()) == _devices()
    assert st.metrics.get("mesh_devices") == N_DEV
    names = st.metrics.schema.names()
    assert {f"shard_elems_s{i}" for i in range(N_DEV)} <= names
    assert "mesh_devices" in names


@pytest.mark.parametrize("devices", [None, 1])
def test_one_device_is_the_default_device_and_has_no_mesh(devices):
    st = _ringless(devices)
    assert st._lane_shardings is None and st.mesh_devices == 1
    assert st.metrics.get("mesh_devices") == 1
    assert "mesh_devices" in VerifyStage.metrics_schema().names()
    assert "shard_elems_s0" not in st.metrics.schema.names()


def test_devices_must_divide_the_batch_and_own_the_dispatch():
    with pytest.raises(ValueError, match="does not divide"):
        VerifyStage("m", batch=BATCH, devices=3, native_client=False)
    with pytest.raises(ValueError, match="comb bank"):
        VerifyStage("m", batch=BATCH, devices=N_DEV, comb_slots=4,
                    native_client=False)


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


# -- placement and verdicts, the dispatch alone ------------------------------------


def _toy_batch(n: int, seed: int):
    """Random byte rows with `n` real lanes; -> (arrays, expected mask)."""
    rng = np.random.default_rng(seed)
    msg = rng.integers(0, 256, (MAX_MSG, BATCH), dtype=np.uint8)
    ln = rng.integers(1, MAX_MSG, (BATCH,)).astype(np.int32)
    sig = rng.integers(0, 256, (64, BATCH), dtype=np.uint8)
    pk = rng.integers(0, 256, (32, BATCH), dtype=np.uint8)
    want = (sig[0] & 1) == 0
    want[n:] = False
    return (msg, ln, sig, pk), want


def _signed_batch(n: int, seed: int):
    """`n` lanes of honestly signed messages (seeded keys, messages of
    seeded lengths), every third with one seeded corrupted signature
    bit; -> (arrays, ops/ref's verdicts)."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    rng = np.random.default_rng(seed)
    msg = np.zeros((MAX_MSG, BATCH), dtype=np.uint8)
    ln = np.zeros((BATCH,), dtype=np.int32)
    sig = np.zeros((64, BATCH), dtype=np.uint8)
    pk = np.zeros((32, BATCH), dtype=np.uint8)
    want = np.zeros((BATCH,), dtype=bool)
    for i in range(n):
        secret = hashlib.sha256(b"mesh%d-%d" % (seed, i)).digest()
        pub = ref.public_key(secret)
        m = rng.bytes(int(rng.integers(1, MAX_MSG + 1)))
        s = bytearray(ref.sign(secret, m))
        if i % 3 == 1:
            bit = int(rng.integers(0, 512))
            s[bit // 8] ^= 1 << (bit % 8)
        msg[:len(m), i] = np.frombuffer(m, dtype=np.uint8)
        ln[i] = len(m)
        sig[:, i] = np.frombuffer(bytes(s), dtype=np.uint8)
        pk[:, i] = np.frombuffer(pub, dtype=np.uint8)
        want[i] = ref.verify(m, bytes(s), pub)
    assert want[:n].any() and not want[:n].all() or n < 2
    return (msg, ln, sig, pk), want


# fills (chip i is dealt elements i, i + 4, ...): full; three chips one
# short; every chip one short; 2, 1, 1, 1; the last chip wholly empty;
# one lane; nothing
FILLS = [BATCH, BATCH - 3, BATCH - N_DEV, PER + 1, N_DEV - 1, 1, 0]


def _dispatch_both(fill: int, make):
    arrays, want = make(fill, seed=1000 + fill)
    got = {}
    for name, devices in (("one", None), ("mesh", N_DEV)):
        st = _ringless(devices)
        mask, n_ok = st._device_verify(None, *arrays, fill)
        got[name] = (mask, st._mask_of(mask), int(n_ok))
    return got, want


def _check_verdicts(got, want):
    for name in ("one", "mesh"):
        _fut, mask, n_ok = got[name]
        assert mask.dtype == np.bool_ and mask.shape == (BATCH,)
        assert (mask == want).all(), name       # booleans: exact
        assert n_ok == int(want.sum()), name


@pytest.mark.parametrize("fill", FILLS)
def test_mesh_dispatch_places_shards_and_agrees_with_one_device(
        fill, toy_program):
    got, want = _dispatch_both(fill, _toy_batch)
    _check_verdicts(got, want)
    # what the mesh call was given: each array on its shards, device i
    # holding lanes [i * PER, (i + 1) * PER) = elements i, i + 4, ... and
    # nothing else; the real lanes as a vector placed with them
    one, mesh = toy_program
    assert all(len(a.sharding.device_set) == 1 for a in one[:4])
    assert one[4] == fill
    devs = _devices()
    arrays, _want = _toy_batch(fill, seed=1000 + fill)
    real = np.arange(BATCH) < fill
    for a, host in zip(mesh, arrays + (real,)):
        assert a.sharding.device_set == set(devs)
        by_dev = {s.device: s for s in a.addressable_shards}
        for i, d in enumerate(devs):
            sh = by_dev[d]
            assert sh.index[-1] == slice(i * PER, (i + 1) * PER)
            dealt = host[..., i::N_DEV]
            if host is real:    # a limit above the lane's index where real
                lanes = np.arange(i * PER, (i + 1) * PER)
                assert ((np.asarray(sh.data) > lanes) == dealt).all()
            else:
                assert (np.asarray(sh.data) == dealt).all()
    # the mask comes back on the lanes' shards, the count on every device
    fut = got["mesh"][0]
    assert fut.sharding.device_set == set(devs)
    assert {s.data.shape for s in fut.addressable_shards} == {(PER,)}


@pytest.mark.slow
@pytest.mark.parametrize("fill", [BATCH - 3, N_DEV - 1])
def test_mesh_lane_equals_the_reference_and_the_single_device_lane(fill):
    """The real program (ed25519_verify_batch_fused) over the mesh:
    mask and ok-count equal ops/ref's verdicts and the one-device
    lane's, with corrupted signatures, the last shard partly and wholly
    empty."""
    got, want = _dispatch_both(fill, _signed_batch)
    _check_verdicts(got, want)


# -- frags through the native-armed stage ------------------------------------------


def _drain(cons, got: list) -> None:
    while True:
        res = cons.poll()
        if res in (shm.POLL_EMPTY, shm.POLL_OVERRUN):
            return
        payload = bytes(res[1])
        got.append(payload[:int.from_bytes(payload[-2:], "little")])


@pytest.mark.parametrize("mask", ["allpass", "toy"])
def test_every_txn_leaves_a_native_armed_mesh_stage_exactly_once(
        mask, request):
    if not vn.available():
        pytest.skip("native verify client unavailable")
    if mask == "toy":
        calls = request.getfixturevalue("toy_program")
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
                if mask == "allpass" or t[SIG_OFF] & 1 == 0]
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
        assert c("mesh_devices") == N_DEV
        phases = [c(f"batch_{p}_ns") for p in fm.BATCH_PHASES]
        if mask == "toy":
            assert all(v > 0 for v in phases)        # all seven read
            assert len(calls) == c("batches")        # one module a step
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
def test_the_mesh_module_has_one_collective_on_a_v5e_host(v5e_2x2):
    """The fused program at the deployment's shape (4 x 1,024 lanes x
    256 bytes, the real lanes as a lane vector as the mesh dispatch
    gives them), partitioned by its arguments' shardings alone, compiled
    for four described v5e chips: the ok-count's all-reduce is its only
    collective, and nothing gathers the batch."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from jax.sharding import Mesh

    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.parallel import mesh as pm

    rows, vec = pm.batch_sharding(
        Mesh(np.array(v5e_2x2.devices), (pm.AXIS,)))
    b, mm = 4096, 256
    args = (jax.ShapeDtypeStruct((mm, b), jnp.uint8, sharding=rows),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=vec),
            jax.ShapeDtypeStruct((64, b), jnp.uint8, sharding=rows),
            jax.ShapeDtypeStruct((32, b), jnp.uint8, sharding=rows),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=vec))
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = sv.ed25519_verify_batch_fused.lower(
            *args, max_msg_len=mm).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    text = compiled.as_text()

    def n(op):
        return len(re.findall(rf"= [^\n]*\b{op}(-start)?\(", text))

    assert n("all-reduce") == 1
    for op in ("all-gather", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert n(op) == 0, op
    mask_s, count_s = compiled.output_shardings
    assert mask_s.is_equivalent_to(vec, 1)
    assert count_s.is_fully_replicated
