import pytest

pytestmark = pytest.mark.slow  # multichip shard compiles (see conftest)

"""Multi-host topology helpers (single-host degenerate mode) + shm ring
race stress (threads hammering the BUSY-bit publish/poll protocol)."""

import os
import threading
import time

import numpy as np
import pytest


# -- multihost (single-host degenerate checks + mesh shapes) -------------------


def test_topology_defaults_single_host():
    from firedancer_tpu.parallel import multihost as mh

    topo = mh.initialize()
    assert topo.num_hosts == 1 and topo.host_id == 0
    assert topo.local_devices >= 1
    assert topo.global_devices == topo.local_devices


def test_global_and_host_tiled_mesh():
    import jax

    from firedancer_tpu.parallel import multihost as mh

    m = mh.global_mesh()
    assert m.axis_names == ("verify",)
    assert m.devices.size == jax.device_count()
    ht = mh.host_tiled_mesh()
    assert ht.axis_names == ("host", "verify")
    assert ht.devices.size == jax.device_count()


def test_shard_counts_deterministic():
    from firedancer_tpu.parallel.multihost import HostTopology, shard_counts

    topo = HostTopology(num_hosts=3, host_id=1, local_devices=4,
                        global_devices=12)
    assert shard_counts(topo, 10) == [4, 3, 3]
    assert sum(shard_counts(topo, 1001)) == 1001


def _sharded_verify_child() -> None:
    # a spawned child runs no conftest: pin the CPU backend (8 virtual
    # devices) before any device use
    from firedancer_tpu.utils.platform import force_cpu_backend

    force_cpu_backend(device_count=8)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    import __graft_entry__ as ge
    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.parallel import multihost as mh

    mesh = mh.global_mesh()
    n = jax.device_count()
    msg, ml, sig, pk = ge._example_batch(2 * n)
    sh = NamedSharding(mesh, PS(None, "verify"))
    sh1 = NamedSharding(mesh, PS("verify"))
    args = (
        jax.device_put(jnp.asarray(msg), sh),
        jax.device_put(jnp.asarray(ml), sh1),
        jax.device_put(jnp.asarray(sig), sh),
        jax.device_put(jnp.asarray(pk), sh),
    )

    @jax.jit
    def step(m, l, s, p):
        return sv.ed25519_verify_batch(m, l, s, p, max_msg_len=m.shape[0])

    ok = np.asarray(step(*args))
    assert ok.all()
    os._exit(0)


def test_sharded_verify_on_global_mesh():
    """The verify kernel jitted over the multihost-shaped mesh (the
    single-host 8-device CPU mesh here) — the path that must survive a
    real multi-host deployment unchanged.

    Runs in a SPAWNED subprocess: XLA:CPU intermittently segfaults when
    this large sharded program compiles late in a long session that has
    already built hundreds of executables (observed at three different
    points of the compile/serialize path); a fresh interpreter is the
    reliable environment, and it also matches how the driver's
    dryrun_multichip invokes the same path."""
    import multiprocessing as mp

    import jax

    if jax.device_count() < 2:
        pytest.skip("needs the virtual multi-device mesh")
    ctx = mp.get_context("spawn")
    proc = ctx.Process(target=_sharded_verify_child)
    proc.start()
    proc.join(600)
    alive = proc.is_alive()
    if alive:
        proc.terminate()
    assert not alive, "sharded verify child timed out"
    assert proc.exitcode == 0, f"child exited {proc.exitcode}"


# -- shm ring race stress ------------------------------------------------------


def test_ring_stress_producer_consumer_threads():
    """One producer thread blasting, one consumer polling, zero frame
    corruption: every received payload must round-trip exactly (the
    BUSY-bit + seq-recheck discipline under real thread interleaving).
    An unreliable consumer MAY be overrun (that is the design) but must
    never see torn data."""
    from firedancer_tpu.tango import shm

    uid = f"stress_{os.getpid()}_{int(time.monotonic_ns() % 1_000_000)}"
    link = shm.ShmLink.create(f"fdtpu_st_{uid}", depth=64, mtu=256)
    n_msgs = 20_000
    errors: list[str] = []
    got = [0]

    def producer():
        p = shm.Producer(link, reliable_fseq_idx=[])
        for i in range(n_msgs):
            payload = (i % 251).to_bytes(1, "little") * (1 + i % 200)
            while not p.try_publish(payload, sig=i):
                time.sleep(0)

    def consumer():
        c = shm.Consumer(link, lazy=64)
        seen = 0
        deadline = time.monotonic() + 60
        while seen < n_msgs and time.monotonic() < deadline:
            res = c.poll()
            if res in (shm.POLL_EMPTY,):
                time.sleep(0)
                continue
            if res == shm.POLL_OVERRUN:
                # overrun skips ahead; count what the gap swallowed
                seen = int(c.seq)
                continue
            meta, payload = res
            sig = int(meta[1])
            want = (sig % 251).to_bytes(1, "little") * (1 + sig % 200)
            if payload != want:
                errors.append(f"torn frame at sig {sig}")
                break
            seen = sig + 1
            got[0] += 1
        if seen < n_msgs:
            errors.append(f"consumer stalled at {seen}/{n_msgs}")

    tp = threading.Thread(target=producer)
    tc = threading.Thread(target=consumer)
    tc.start()
    tp.start()
    tp.join(120)
    tc.join(120)
    link.close()
    link.unlink()
    assert not errors, errors
    assert got[0] > 0
