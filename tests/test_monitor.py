"""monitor TUI + ready gate: cross-process attach via the run
descriptor, readiness blocking, rate rendering (fdctl monitor/ready
parity, runtime/monitor.py)."""

import io
import json
import os
import time

from firedancer_tpu.runtime import monitor as mon
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.runtime.stage import Stage
from firedancer_tpu.tango import shm


class _TickStage(Stage):
    """Minimal producer: counts iterations, publishes nothing."""

    def after_credit(self) -> None:
        self.metrics.inc("ticks")


def _tick_builder(links, cnc):
    return _TickStage("ticker", cnc=cnc)


def _mini_topology():
    topo = ft.Topology()
    topo.link("noop", mtu=64, depth=64)
    topo.stage("ticker", _tick_builder)
    return topo


def test_descriptor_attach_ready_and_monitor():
    topo = _mini_topology()
    h = ft.launch(topo)
    try:
        path = mon.descriptor_path(h.uid)
        assert os.path.exists(path)
        d = json.load(open(path))
        assert d["stages"].keys() == {"ticker"}

        ses = mon.MonitorSession.attach(path)
        try:
            assert ses.wait_ready(timeout_s=30), ses.sample()
            s1 = ses.sample()
            time.sleep(0.3)
            s2 = ses.sample()
            assert s2[0]["iters"] > s1[0]["iters"], "stage not iterating"
            text = mon.MonitorSession.render(s2, s1, 0.3)
            assert "ticker" in text and "RUN" in text
            # the TUI loop runs bounded iterations without a terminal
            buf = io.StringIO()
            ses.run(interval_s=0.05, iterations=3, out=buf)
            assert buf.getvalue().count("ticker") == 3
        finally:
            ses.close()
        h.halt()
    finally:
        h.close()
    # descriptor removed on close; newest-run discovery no longer sees it
    assert not os.path.exists(mon.descriptor_path(h.uid))


def test_attach_newest_run_discovery():
    topo = _mini_topology()
    h = ft.launch(topo)
    try:
        runs = mon.list_runs()
        assert mon.descriptor_path(h.uid) in runs
        ses = mon.MonitorSession.attach()  # newest live run
        try:
            assert ses.wait_ready(timeout_s=30)
        finally:
            ses.close()
        h.halt()
    finally:
        h.close()


def test_ready_cli_exit_codes(monkeypatch, tmp_path):
    from firedancer_tpu.__main__ import main

    topo = _mini_topology()
    h = ft.launch(topo)
    try:
        rc = main(["ready", "--descriptor", mon.descriptor_path(h.uid),
                   "--timeout", "30"])
        assert rc == 0
        h.halt()
    finally:
        h.close()
    # no live runs -> attach fails -> exit 1 (asked of a run directory of
    # its own: under several test workers /tmp holds the live topologies
    # of the other workers' tests, and the newest of those is a live run)
    monkeypatch.setattr(mon, "RUN_DIR", str(tmp_path))
    assert main(["ready", "--timeout", "1"]) == 1


def test_monitor_cli_bounded():
    from firedancer_tpu.__main__ import main

    topo = _mini_topology()
    h = ft.launch(topo)
    try:
        rc = main(["monitor", "--descriptor", mon.descriptor_path(h.uid),
                   "--interval", "0.05", "--iterations", "2"])
        assert rc == 0
        h.halt()
    finally:
        h.close()
