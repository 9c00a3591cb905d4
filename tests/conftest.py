"""Test configuration: force an 8-device virtual CPU mesh.

Tests never assume real TPU hardware; multi-chip sharding is validated on a
virtual CPU mesh exactly like the driver's dryrun (see __graft_entry__.py).
force_cpu_backend must run before any jax device use; enable_compile_cache
makes the 10-60s curve/sigverify compiles persistent across test runs.
"""

from firedancer_tpu.utils import platform as fd_platform

fd_platform.force_cpu_backend(device_count=8)
fd_platform.enable_compile_cache()

import os

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0x5F3759DF)


# -- two-tier suite -----------------------------------------------------------
# Tier 1 (default): every host-logic test — target < 20 min on one core.
# Tier 2 (opt-in):  XLA-compile-heavy tests (fresh sigverify/curve
# compiles, process-topology children cold-compiling, multichip shards).
# Run them with `pytest --slow` or FDTPU_SLOW=1.  The reference's CI has
# the same split (quick unit tier vs the long fuzz/conformance tier).


def pytest_addoption(parser):
    parser.addoption(
        "--slow", action="store_true", default=False,
        help="run the XLA-compile-heavy tier too",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: XLA-compile-heavy; opt in with --slow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--slow") or os.environ.get("FDTPU_SLOW"):
        return
    skip = pytest.mark.skip(
        reason="slow tier (run with --slow or FDTPU_SLOW=1)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


# -- the verify program with toy arithmetic -------------------------------------


def toy_lane_ok(msg_len, msg0, sig0, sig63, pk0, pk31):
    """The toy verdict of one lane (or an array of lanes), from the
    bytes the real program reads at both ends of every field: a lane
    passes iff their sum with the message length is even.  An all-zero
    pad row passes, so a reap that read pad lanes would show."""
    return ((np.asarray(msg_len).astype(np.int64) + msg0 + sig0 + sig63
             + pk0 + pk31) & 1) == 0


def toy_verify_core(msg, msg_len, sig, pubkey, *, max_msg_len):
    """toy_lane_ok as the program traces it: what stands in for
    ops/sigverify._verify_ok (the fixture below; a child process of a
    test patches it in by hand)."""
    import jax.numpy as jnp

    assert msg.shape[0] == max_msg_len
    assert sig.shape[0] == 64 and pubkey.shape[0] == 32
    i32 = jnp.int32
    total = (msg_len + msg[0].astype(i32) + sig[0].astype(i32)
             + sig[63].astype(i32) + pubkey[0].astype(i32)
             + pubkey[31].astype(i32))
    return (total & 1) == 0


@pytest.fixture
def toy_verify_ok(monkeypatch):
    """ops/sigverify._verify_ok — the program's arithmetic, minutes of
    compile on a CPU — replaced by a lane-wise toy that compiles in no
    time.  Everything around it is the real thing: the packed rows, the
    on-device unpack, the jitted program under its own name, its
    dispatch.  -> toy_lane_ok, the toy's verdicts on the host."""
    from firedancer_tpu.ops import sigverify as sv

    clear = sv.ed25519_verify_batch_fused.clear_cache
    clear()   # nothing traced before may answer for the toy, nor after
    monkeypatch.setattr(sv, "_verify_ok", toy_verify_core)
    yield toy_lane_ok
    clear()


class Exchange:
    """Every crossing of the host-device boundary the stage makes,
    counted by wrapping what makes one: `jax.device_put`,
    `jax.make_array_from_callback` (and each call of its callback), and
    the fetch of a dispatched program's output (`np.asarray` of it)."""

    def __init__(self, monkeypatch):
        import jax

        from firedancer_tpu.ops import sigverify as sv

        self.puts: list = []         # arrays handed to device_put
        self.made: list = []         # arrays built from callbacks
        self.callbacks: list = []    # the index each callback was asked
        self.fetches: list = []      # programs' outputs fetched
        self.programs = 0
        ex = self
        put, make, dispatch = (jax.device_put, jax.make_array_from_callback,
                               sv.verify_dispatch)

        def device_put(x, *a, **kw):
            out = put(x, *a, **kw)
            ex.puts.append(out)
            return out

        def make_array_from_callback(shape, sharding, cb, *a, **kw):
            def counted(idx):
                ex.callbacks.append(idx)
                return cb(idx)

            out = make(shape, sharding, counted, *a, **kw)
            ex.made.append(out)
            return out

        class Output:
            """The mask future: ready when the device says (or waited
            for, unfetched), fetched through __array__ and by no other
            way."""

            def __init__(self, fut):
                self.fut = fut

            def is_ready(self):
                return self.fut.is_ready()

            def block_until_ready(self):      # the warm-up's wait
                self.fut.block_until_ready()
                return self

            def __array__(self, dtype=None, copy=None):
                ex.fetches.append(self.fut)
                return np.asarray(self.fut)

        def verify_dispatch(rows, *, max_msg_len):
            ex.programs += 1
            return Output(dispatch(rows, max_msg_len=max_msg_len))

        monkeypatch.setattr(jax, "device_put", device_put)
        monkeypatch.setattr(jax, "make_array_from_callback",
                            make_array_from_callback)
        monkeypatch.setattr(sv, "verify_dispatch", verify_dispatch)

    @property
    def h2d(self) -> list:
        return self.puts + self.made


@pytest.fixture
def exchange(monkeypatch, toy_verify_ok):
    return Exchange(monkeypatch)
