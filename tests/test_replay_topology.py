"""The follower's verify phase on the normal path: the `[replay]`
section, `build_replay_topology_from_config`, `run --config` end to
end, the `replay` row on the monitor, Prometheus and slotreport, and
the plain reference's copy under benchmarks/."""

from __future__ import annotations

import json
import os

import pytest

from firedancer_tpu.models import leader_topo
from firedancer_tpu.runtime import monitor as mon
from firedancer_tpu.runtime import replay_verify as rr
from firedancer_tpu.runtime import slot_report
from firedancer_tpu.runtime import topo as ft
from firedancer_tpu.runtime import verify_native as vn
from firedancer_tpu.utils import config as fc
from firedancer_tpu.utils import metrics as fm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOML = os.path.join(ROOT, "config", "replay-verify-v5e.toml")

SMALL = {
    "layout": {"replay_stage_count": 1},
    "verify": {"batch": 16, "max_msg_len": 256},
    "poh": {"hashes_per_tick": 8, "ticks_per_slot": 4},
    "replay": {"txns_per_entry": 5, "entries_per_batch": 2, "slot_txns": 40,
               "dead_one_in_slots": 2},
}


def test_the_plain_reference_and_the_benchmarks_copy_are_one_file():
    a = os.path.join(ROOT, "firedancer_tpu/ops/ref/replay_verify_plain.py")
    b = os.path.join(ROOT, "benchmarks/harness/replay_reference.py")
    assert open(a, "rb").read() == open(b, "rb").read()


def test_the_plain_reference_imports_nothing_of_the_program():
    src = open(os.path.join(
        ROOT, "firedancer_tpu/ops/ref/replay_verify_plain.py")).read()
    assert "firedancer_tpu" not in src.split('"""', 2)[2]
    assert "import jax" not in src and "runtime" not in src.split('"""', 2)[2]


def test_the_committed_toml_is_the_benchmarks_configuration():
    """config/replay-verify-v5e.toml and the cell's program_config are
    one deployment, at the published sizes."""
    cfg = fc.load_config(TOML)
    bench = json.load(open(os.path.join(
        ROOT, "benchmarks/configs/replay-verify-v5e.json")))
    assert fc.load_config(None, overrides=bench["program_config"]) == cfg
    assert cfg.layout.replay_stage_count == 1
    assert (cfg.verify.batch, cfg.verify.max_msg_len) == (16384, 256)
    r = cfg.replay
    assert (r.txns_per_entry, r.entries_per_batch, r.slot_txns,
            r.dead_one_in_slots, r.frag_mtu) == (31, 2, 39990, 16, 65536)
    assert bench["widths"]["slot"]["entry_batches"] == 649


@pytest.mark.parametrize("overrides,says", [
    ({"layout": {"replay_stage_count": 2}}, "replay_stage_count"),
    ({"layout": {"replay_stage_count": 1, "benchs_stage_count": 1}},
     "two topologies"),
    ({"replay": {"frag_mtu": 70000}}, "frag_mtu"),
    ({"replay": {"out_depth": 1000}}, "out_depth"),
    ({"replay": {"slot_txns": 0}}, "slot_txns"),
    ({"replay": {"no_such_key": 1}}, "unknown config key"),
])
def test_the_replay_section_is_validated(overrides, says):
    with pytest.raises(fc.ConfigError, match=says):
        fc.load_config(None, overrides=overrides)


@pytest.mark.parametrize("overrides,says", [
    ({}, "replay_stage_count = 1"),
    ({"layout": {"replay_stage_count": 1},
      "verify": {"batch": 16, "devices": 2}}, "one chip"),
])
def test_what_the_replay_topology_cannot_build_it_refuses(overrides, says):
    with pytest.raises(ValueError, match=says):
        leader_topo.build_replay_topology_from_config(
            fc.load_config(None, overrides=overrides))


def test_the_topology_is_source_stage_out_over_two_rings():
    topo = leader_topo.build_replay_topology_from_config(
        fc.load_config(None, overrides=SMALL), n_slots=4)
    assert [s.name for s in topo.stages] == ["replaysrc", "verify0",
                                             "replayout"]
    links = {ln.name: ln for ln in topo.links}
    assert set(links) == {"rv", "vo"}
    assert links["rv"].mtu == links["vo"].mtu == 65536
    spec = {s.name: s for s in topo.stages}
    assert spec["verify0"].ins == ("rv",) and spec["verify0"].outs == ("vo",)
    assert spec["replaysrc"].kwargs["corrupt_slots"] == (1, 3)
    names = rr.ReplayVerifyStage.metrics_schema().names()
    assert set(fm.REPLAY_COUNTERS) <= names
    # every batch_* phase, close reason, chip_empty_* and loop_* counter
    # VerifyStage has
    from firedancer_tpu.runtime.verify import VerifyStage

    assert VerifyStage.metrics_schema().names() <= names


def _small_toml(tmp_path) -> str:
    """The committed deployment file cut to a toy: device batch 16,
    slots of 40 transfers in entries of 5, every second slot dead."""
    text = open(TOML).read()
    for a, b in (("batch = 16384", "batch = 16"),
                 ("slot_txns = 39990", "slot_txns = 40"),
                 ("txns_per_entry = 31", "txns_per_entry = 5"),
                 ("dead_one_in_slots = 16", "dead_one_in_slots = 2"),
                 ("hashes_per_tick = 64", "hashes_per_tick = 8")):
        assert a in text
        text = text.replace(a, b)
    path = tmp_path / "replay-small.toml"
    path.write_text(text)
    return str(path)


def test_run_config_cpu_end_to_end(tmp_path, capsys, toy_verify_ok):
    """`run --config <the deployment's file, cut to a toy> --cpu`: typed
    config -> build_replay_topology_from_config -> the stages over shm
    rings on one thread -> every slot's verdict, the corrupted slots
    dead (the toy fails a lane whose bytes sum odd: the source's flipped
    bit makes one so, as it makes the signature invalid)."""
    if not vn.available():
        pytest.skip("native verify client unavailable")
    from firedancer_tpu import __main__ as cli

    # the toy passes a lane iff a sum of its bytes is even: choose the
    # pool so that every valid transfer's lane is, as test_replay_verify
    # does for its blocks
    from firedancer_tpu.runtime import benchg
    from test_replay_verify import _toy_even

    real = benchg.gen_transfer_pool

    def even_pool(n, **kw):
        return [t for t in real(4 * n + 64, **kw) if _toy_even(t)][:n]

    benchg.gen_transfer_pool = even_pool
    try:
        rc = cli.main(["run", "--config", _small_toml(tmp_path), "--cpu",
                       "--txns", "160"])
    finally:
        benchg.gen_transfer_pool = real
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "2 slots live, 2 dead by a signature" in out
    assert "verify0" in out and "replayout" in out


def test_the_replay_row_on_monitor_prometheus_and_slotreport():
    """The stage's counters and its two spans where an operator reads
    them: the monitor's `replay` line, the scrape, slotreport's block."""
    cfg = fc.load_config(None, overrides=dict(
        SMALL, replay=dict(SMALL["replay"], dead_one_in_slots=0)))
    topo = leader_topo.build_replay_topology_from_config(
        cfg, n_slots=3, verify_precomputed=True)
    names = [s.name for s in topo.stages]
    h = ft.launch(topo, held=tuple(names))
    stages = []
    try:
        stages = [h.build_held(n) for n in names]
        v = h.met_views["verify0"][0]
        for _ in range(400):
            for s in stages:
                s.run_once()
            if stages[1].metrics.get("slots_live") == 3:
                break
        for s in stages:
            s.sync_counters()
        assert v.get("slots_live") == 3 and v.get("entry_txn_out") == 120
        assert v.get("poh_hashes") == 3 * (8 + 4 * 8)
        assert h.met_views["replayout"][0].get("verdicts_live") == 3
        ses = mon.MonitorSession.attach(mon.descriptor_path(h.uid))
        try:
            rows = {r["stage"]: r for r in ses.sample()}
            row = rows["verify0"]["replay"]
            assert row["entry_batches_in"] == row["entry_batches_out"] > 0
            assert row["slots_live"] == 3
            assert rows["replaysrc"]["replay"] is None
            table = mon.MonitorSession.render(list(rows.values()), None, 0.0)
            assert "verify0: replay entry_batches_in=" in table
            assert "slots_dead_sig=0" in table and "poh_check_ns=" in table
            text = ses.scrape()
            for name in fm.REPLAY_COUNTERS:
                assert f'{name}{{stage="verify0"}}' in text, name
            block = slot_report.report_from_session(ses)["stages"]["verify0"]
            assert block["replay"]["slots_live"] == 3
            assert block["replay"]["entry_unpack_ns"] >= 0
        finally:
            ses.close()
    finally:
        for s in stages:
            s.ins, s.outs = [], []
            s.drop_native_views()
        h.close()


def test_replay_entries_takes_first_signatures_from_a_parse_already_made():
    """runtime/poh.replay_entries with and without `first_sigs` gives
    the same verdict and segments; check_entry is its per-entry form."""
    import hashlib

    from firedancer_tpu.protocol import txn as ft_txn
    from firedancer_tpu.runtime import poh as fpoh
    from firedancer_tpu.runtime.benchg import gen_transfer_pool

    pool = gen_transfer_pool(9, n_payers=3)
    seed = hashlib.sha256(b"s").digest()
    frames = rr.build_slot_frames(0, seed, pool, txns_per_entry=4,
                                  entries_per_batch=3, ticks_per_slot=2,
                                  hashes_per_tick=5)
    from firedancer_tpu.runtime.poh_stage import parse_entry
    from firedancer_tpu.runtime.shred_stage import deshred_entry_batch

    entries = [parse_entry(e) for f in frames
               for e in deshred_entry_batch(rr.unframe(f)[4])]
    sigs = [[ft_txn.txn_parse(p).signatures(p)[0] for p in txs]
            for _, _, txs in entries]
    assert fpoh.replay_entries(seed, entries) \
        == fpoh.replay_entries(seed, entries, first_sigs=sigs)
    assert fpoh.replay_entries(seed, entries, first_sigs=sigs)[0]
    h = seed
    for (n, expect, _), s in zip(entries, sigs):
        ok, h = fpoh.check_entry(h, n, expect, s)
        assert ok
    bad = [(entries[0][0], bytes(32), entries[0][2])] + entries[1:]
    assert not fpoh.replay_entries(seed, bad, first_sigs=sigs)[0]
    assert fpoh.check_entry(seed, 0, entries[0][1], sigs[0]) == (False, seed)
