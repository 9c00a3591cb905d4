"""Sharded serving plane tests (8 virtual CPU devices, conftest).

Tier split: router/stage host logic, the process topology's per-shard
metrics labels, and the CHEAP sharded programs (pad-lane mask, reedsol,
PoH — seconds of XLA) run in tier 1; anything compiling the ed25519
verify kernel (the full single-program serving step) is slow-tier, the
same line test_sigverify/test_parallel draw.
"""

import os
import time

import numpy as np
import pytest

from firedancer_tpu.parallel.router import ShardRouterStage, shard_of
from firedancer_tpu.parallel.serve import ServeConfig, ServePlane
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.runtime.stage import Stage

# one tiny plane shared by the tier-1 device tests: every sharded
# program it compiles (mask probe, RS, PoH) is canary-sized
TINY = ServeConfig(
    n_devices=8,
    batch_per_shard=4,
    max_msg_len=128,
    fec_sets_per_shard=1,
    fec_data_shreds=4,
    fec_parity_shreds=2,
    fec_shred_sz=64,
    poh_chains_per_shard=1,
    poh_iters=4,
)


@pytest.fixture(scope="module")
def tiny_plane():
    return ServePlane(TINY)


# -- router: deterministic assignment + conservation (host only) --------------


def test_shard_of_deterministic():
    assert [shard_of(s, 4) for s in range(8)] == [0, 1, 2, 3, 0, 1, 2, 3]


def test_router_conserves_frags_cooperative():
    """In-process router over real shm rings: every ingress frag lands on
    exactly one shard ring, round-robin by sequence."""
    from firedancer_tpu.tango import shm

    n_shards = 4
    uid = f"tsrv_{time.monotonic_ns() % 1_000_000}"
    ingress = shm.ShmLink.create(f"fdtpu_ri_{uid}", depth=64, mtu=64)
    rings = [
        shm.ShmLink.create(f"fdtpu_rs{i}_{uid}", depth=64, mtu=64)
        for i in range(n_shards)
    ]
    try:
        router = ShardRouterStage(
            "router",
            ins=[shm.Consumer(ingress, lazy=8)],
            outs=[shm.Producer(r) for r in rings],
            n_shards=n_shards,
        )
        src = shm.Producer(ingress)
        sinks = [shm.Consumer(r) for r in rings]
        got = [[] for _ in range(n_shards)]
        for k in range(37):
            src.try_publish(b"frag%03d" % k, sig=k)
        for _ in range(500):
            router.run_once()
            for i, c in enumerate(sinks):
                res = c.poll()
                if isinstance(res, tuple):
                    got[i].append(res[1])
        m = router.metrics
        assert m.get("routed_total") == 37
        per = [m.get(f"routed_s{i}") for i in range(n_shards)]
        assert sum(per) == 37
        assert per == [10, 9, 9, 9]  # seq % 4, 37 frags
        for i in range(n_shards):
            assert len(got[i]) == per[i]
            # shard i received exactly the frags whose seq % n == i
            assert got[i] == [b"frag%03d" % k for k in range(37)
                              if k % n_shards == i]
        # drop the ring views before close (the BufferError discipline)
        router.ins = []
        router.outs = []
        src = sinks = None
    finally:
        import gc

        gc.collect()
        for link in [ingress, *rings]:
            link.close()
            link.unlink()


# -- the sharded pipeline, host machinery only (precomputed verify) -----------


def test_sharded_pipeline_precomputed_end_to_end():
    from firedancer_tpu.models.leader import build_sharded_leader_pipeline

    n = 64
    pipe = build_sharded_leader_pipeline(
        n_shards=4, batch_per_shard=8, max_msg_len=256,
        pool_size=n, gen_limit=n, verify_precomputed=True,
    )
    try:
        pipe.run(until_txns=n, max_iters=200_000)
        executed = sum(b.metrics.get("txn_exec") for b in pipe.banks)
        assert executed == n
        r = pipe.router.metrics
        v = pipe.verifies[0].metrics
        assert r.get("routed_total") == n
        # conservation INTO the sharded stage, per shard
        for i in range(4):
            assert v.get(f"shard_elems_s{i}") == r.get(f"routed_s{i}")
        assert v.get("txn_verified") == n
        assert pipe.store.metrics.get("frags_in") > 0
    finally:
        pipe.close()


# -- per-shard metrics labels through the PROCESS topology --------------------


@pytest.mark.slow  # ~17 s (spawns the full sharded process topology);
# tier-1 keeps the sharded e2e via test_sharded_pipeline_precomputed_
# end_to_end and the metrics plane via test_monitor
def test_sharded_topology_shm_metrics_and_labels():
    """(a) of the serving-plane test triad: router frag conservation per
    shard read from the shm registries of a REAL process topology, plus
    the shard labels riding descriptor -> scrape -> monitor aggregation."""
    from firedancer_tpu.models.leader_topo import build_sharded_leader_topology
    from firedancer_tpu.runtime import monitor as mon

    n_shards, n_txns = 2, 48
    topo = build_sharded_leader_topology(
        n_shards=n_shards, n_txns=n_txns, pool_size=n_txns, batch=8,
        verify_precomputed=True,
    )
    h = ft.launch(topo)
    try:
        ok = h.supervise(
            until=lambda h: h.cncs["store"].diag(Stage.DIAG_FRAGS_IN) > 0,
            timeout_s=300,
            heartbeat_timeout_s=120,
        )
        assert ok, f"supervisor failed (failed stage: {h.failed})"
        # frag conservation per shard, via the shm metric registries: what
        # the router routed to shard i is what verify_s{i} consumed (poll:
        # registries flush on the lazy housekeeping cadence)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            router_reg = h.met_views["router"][0]
            routed = [router_reg.get(f"routed_s{i}") for i in range(n_shards)]
            seen = [h.met_views[f"verify_s{i}"][0].get("frags_in")
                    for i in range(n_shards)]
            if sum(routed) == n_txns and routed == seen:
                break
            time.sleep(0.05)
        assert sum(routed) == n_txns
        assert routed == seen, (routed, seen)
        assert router_reg.get("routed_total") == n_txns
        # labels: descriptor -> MonitorSession scrape carries
        # {stage="verify",shard="i"} series instead of colliding names
        ses = mon.MonitorSession.attach(mon.descriptor_path(h.uid))
        try:
            text = ses.scrape()
            for i in range(n_shards):
                assert f'frags_in{{stage="verify",shard="{i}"}}' in text
            assert 'stage="verify_s0"' not in text
            # the TUI sample folds shards into one logical row
            rows = {r["stage"]: r for r in ses.sample(aggregate_shards=True)}
            row = rows[f"verify x{n_shards}"]
            assert row["shards"] == n_shards
            assert row["in"] == sum(seen)
            assert "verify_s0" not in rows
            # unaggregated view still exposes the physical stages
            flat = {r["stage"]: r for r in ses.sample()}
            assert flat["verify_s0"]["shard"] == 0
        finally:
            ses.close()
        h.halt()
    finally:
        h.close()


# -- pad-lane masking on device (the cheap probe) -----------------------------


def test_pad_lane_mask_uneven_final_shard(tiny_plane):
    """(c): uneven fills mask exactly — shard s keeps its first n_real[s]
    lanes, every pad lane reads False, computed by the same lane_real_mask
    the compiled serving step applies to the verify output."""
    per = TINY.batch_per_shard
    fills = [4, 4, 4, 4, 4, 4, 3, 0]  # uneven final shards
    mask = tiny_plane.real_mask(fills)
    assert mask.shape == (TINY.batch,)
    expect = np.zeros(TINY.batch, dtype=bool)
    for s, f in enumerate(fills):
        expect[s * per : s * per + f] = True
    assert (mask == expect).all()


# -- sharded RS + PoH programs byte-identical to single device ----------------


def test_sharded_reedsol_identical_and_padded(tiny_plane):
    """(b), reedsol hop: the plane's mesh-sharded parity equals the
    unsharded encoder byte for byte, including set-count padding up to
    the mesh divisor and sz zero-padding up to the compiled width."""
    from firedancer_tpu.ops import reedsol as rs

    rng = np.random.default_rng(7)
    d, p = TINY.fec_data_shreds, TINY.fec_parity_shreds
    # 5 sets of 48-byte shreds: pads to 8 sets on the mesh, sz to 64
    data = rng.integers(0, 256, (5, d, 48), dtype=np.uint8)
    par = tiny_plane.encode_parity(data, p)
    expect = np.asarray(rs.encode(data, p))
    assert par.shape == expect.shape == (5, p, 48)
    assert (par == expect).all()


def test_sharded_reedsol_offshape_falls_back(tiny_plane):
    from firedancer_tpu.ops import reedsol as rs

    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, (2, 3, 16), dtype=np.uint8)  # d != compiled
    par = tiny_plane.encode_parity(data, 2)
    assert (par == np.asarray(rs.encode(data, 2))).all()


def test_sharded_poh_segments_identical(tiny_plane):
    """(b), PoH hop: mesh-sharded segment verification agrees with the
    host chain, pads masked, a corrupted segment rejected."""
    import hashlib

    n = 5  # pads to 8 chains on the mesh
    starts = np.zeros((32, n), dtype=np.int32)
    ends = np.zeros((32, n), dtype=np.int32)
    for i in range(n):
        h0 = hashlib.sha256(b"serve%d" % i).digest()
        h = h0
        for _ in range(TINY.poh_iters):
            h = hashlib.sha256(h).digest()
        starts[:, i] = np.frombuffer(h0, dtype=np.uint8)
        ends[:, i] = np.frombuffer(h, dtype=np.uint8)
    ends[0, 2] ^= 1  # corrupt chain 2
    ok = tiny_plane.verify_poh_segments(starts, ends, TINY.poh_iters)
    assert ok.shape == (n,)
    assert list(ok) == [True, True, False, True, True]


# -- the full single-program serving step (verify kernel: slow tier) ----------


@pytest.mark.slow
@pytest.mark.timeout(1800)
def test_serving_step_byte_identical_to_single_device():
    """(b), the whole step: sharded verify output == the single-device
    kernel on the same batch, with an uneven final shard padded+masked
    and a corrupted signature rejected across the shard boundary."""
    import jax.numpy as jnp

    import __graft_entry__ as ge
    from firedancer_tpu.ops import sigverify as sv

    plane = ServePlane(TINY)
    b = TINY.batch
    msg, msg_len, sig, pk = ge._example_batch(b, seed=23)
    sig[0, 5] ^= 0xFF  # corrupt one element mid-shard
    # single-device truth at the same shapes
    expect = np.asarray(sv.ed25519_verify_batch(
        jnp.asarray(msg), jnp.asarray(msg_len), jnp.asarray(sig),
        jnp.asarray(pk), max_msg_len=TINY.max_msg_len,
    ))
    fills = np.full((TINY.n_devices,), TINY.batch_per_shard, dtype=np.int32)
    fills[-1] = 2  # uneven final shard: lanes beyond 2 are pads
    pend = plane.submit(msg, msg_len, sig, pk, fills)
    got = np.asarray(pend.ok)
    real = plane.real_mask(fills)
    assert (got[real] == expect[real]).all()
    assert not got[~real].any()
    assert int(np.asarray(pend.n_ok)) == int(expect[real].sum())


# -- warm-boot lane selection (ISSUE 13) --------------------------------------
#
# The serialize_executable path is accelerator-only: on CPU the
# executable round trip fails ("Symbols not found"), so CPU must keep
# the jax.export lane while a real chip picks the serialized-executable
# lane and the 10 s warm_cold_start budget.  The selection (not the TPU
# serialization itself, which cannot run here) is what these pin.


def test_warmboot_lane_selection_cpu_vs_accel(monkeypatch):
    plane = ServePlane(TINY)
    assert not plane._use_serialized_executable()  # the CPU test mesh
    monkeypatch.setattr(type(plane), "_mesh_platform", lambda self: "tpu")
    assert plane._use_serialized_executable()


def test_plane_selects_export_lane_on_cpu(tiny_plane):
    assert tiny_plane._mesh_platform() == "cpu"
    assert not tiny_plane._use_serialized_executable()


@pytest.fixture
def swap_cache_dir(tmp_path):
    """Point jax's compilation-cache config at a temp dir for one test
    (jax.config attrs are read-only properties: update() + restore)."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    cache = str(tmp_path)
    jax.config.update("jax_compilation_cache_dir", cache)
    yield cache
    jax.config.update("jax_compilation_cache_dir", prev)


def test_plane_warm_boot_loads_serialized_executable(swap_cache_dir,
                                                      monkeypatch):
    """On a (simulated) accelerator mesh, a warm boot is pure
    deserialization: no export, no compile.  The blob machinery and
    the lane wiring are real; only the backend serializer is stubbed —
    it cannot run on CPU by design."""
    import pickle

    import jax

    plane = ServePlane(TINY)
    monkeypatch.setattr(plane, "_use_serialized_executable", lambda: True)
    monkeypatch.setattr(type(plane), "_mesh_platform",
                        lambda self: "faketpu")
    cache = swap_cache_dir
    blob = plane._exec_blob_path(cache)
    assert "faketpu" in os.path.basename(blob)
    sentinel = object()
    calls = {}

    def fake_load(payload, in_tree, out_tree):
        calls["args"] = (payload, in_tree, out_tree)
        return sentinel

    from jax.experimental import serialize_executable as se

    monkeypatch.setattr(se, "deserialize_and_load", fake_load)
    with open(blob, "wb") as f:
        pickle.dump((b"exec-bytes", "in-tree", "out-tree"), f)

    def boom(cache_dir):  # a warm boot must never reach the compiler
        raise AssertionError("export/compile lane entered on warm boot")

    monkeypatch.setattr(plane, "_warmup_export", boom)
    compile_s = plane.warmup()
    assert plane._aot is sentinel
    assert calls["args"] == (b"exec-bytes", "in-tree", "out-tree")
    assert compile_s < 5.0  # deserialization, not compilation


def test_plane_cold_boot_serializes_executable(swap_cache_dir, monkeypatch):
    """Cold boot on an accelerator: compile through the export lane
    once, then persist the serialized executable for the next boot."""
    import pickle

    import jax

    plane = ServePlane(TINY)
    monkeypatch.setattr(plane, "_use_serialized_executable", lambda: True)
    monkeypatch.setattr(type(plane), "_mesh_platform",
                        lambda self: "faketpu")
    cache = swap_cache_dir
    compiled = object()

    def fake_export(cache_dir):
        plane._aot = compiled

    from jax.experimental import serialize_executable as se

    monkeypatch.setattr(plane, "_warmup_export", fake_export)
    monkeypatch.setattr(
        se, "serialize", lambda aot: (b"xc", "it", "ot"))
    plane.warmup()
    blob = plane._exec_blob_path(cache)
    assert os.path.exists(blob)
    with open(blob, "rb") as f:
        assert pickle.load(f) == (b"xc", "it", "ot")
