"""The flagship pipeline as REAL OS processes: 9 stages forked over shm
links, supervised by cnc heartbeats, monitored, cleanly halted — the
fdctl-run operational model end to end."""

import pytest

pytestmark = pytest.mark.slow  # XLA-compile-heavy tier (see conftest)

from firedancer_tpu.models.leader_topo import build_leader_topology
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.runtime.stage import Stage

N_TXNS = 32


@pytest.mark.timeout(1800)
def test_leader_pipeline_as_processes():
    # verify_cpu: the verify child owns the chip by default; here it is
    # asked to run its kernel on the CPU.  It compiles (or loads from the
    # shared cache) in its builder; the supervision windows allow that.
    topo = build_leader_topology(n_txns=N_TXNS, pool_size=N_TXNS, batch=16,
                                 verify_cpu=True)
    h = ft.launch(topo)
    try:
        ok = h.supervise(
            until=lambda h: h.cncs["store"].diag(Stage.DIAG_FRAGS_IN) > 0
            and h.cncs["bank0"].diag(Stage.DIAG_FRAGS_IN) > 0,
            timeout_s=1200,
            heartbeat_timeout_s=900,
        )
        mon = h.format_monitor()
        assert ok, f"process pipeline stalled:\n{mon}"
        snap = {r["stage"]: r for r in h.snapshot()}
        assert snap["verify0"]["frags_in"] >= N_TXNS
        assert snap["store"]["frags_in"] > 0  # wire shreds arrived
        assert all(r["alive"] for r in snap.values()), mon
        h.halt()
        assert all(not p.is_alive() for p in h.procs.values())
    finally:
        h.close()
