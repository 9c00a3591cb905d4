"""Per-slot structured reports over the native observability plane
(ISSUE 20 tentpole c).

A slot report folds one run's flight-recorder timeline + shm metric
registries into JSON an operator (or CI) can diff:

  * per-slot rows (sealed/missed, microblocks, committed txns, shed)
    reconstructed from EV_SLOT_* flight events,
  * per-stage sweep-phase quantiles (drain/callback/apply/publish) from
    the nsweep_* histograms C code populated from INSIDE the crossing —
    the bank 13.8 us/txn decomposition ROADMAP item 1 asks for,
  * native-vs-punt counts, funk write totals and restart events.

Three sources feed the same report shape:

  build_report(dump)          -- a flight-dump object (live session via
                                 MonitorSession.flight_dump(), or a
                                 /tmp/fdtpu_flight_<uid>.json post-mortem)
  aggregate_reports(reports)  -- several dumps (one per validator)
  cluster_report(harness,...) -- a chaos/cluster.py in-process cluster,
                                 folded from deterministic model state so
                                 two same-seed runs byte-diff in CI.

The funk storage plane has no standalone sweep stage (funk apply rides
inside the bank crossing — PR "fdfunk"), so the report derives a `funk`
pseudo-stage from the bank shards' apply-phase histograms and
bank_funk_writes/bank_funk_falls counters; its drain/callback/publish
phases are present-but-empty blocks so every consumer sees the same
four keys on all of bank/verify/net/funk.
"""
from __future__ import annotations

import json

from ..utils import metrics as fm

REPORT_KIND = "slotreport"
CLUSTER_KIND = "slotreport-cluster"
AGGREGATE_KIND = "slotreport-aggregate"

# Counters surfaced under the per-stage "native" block when present.
_NATIVE_EXTRA = (
    "nbank_txn_native", "nbank_punts", "nverify_batches", "nverify_punts",
    "net_native_frames", "net_punts", "nshred_batches", "nshred_punts",
    "npack_takes", "npack_punts", "bank_funk_writes", "bank_funk_falls",
)


def _pq(h: dict | None) -> dict:
    """{count,p50_ns,p99_ns} from a hist() dict; overflowed quantiles
    surface as null + an explicit overflow flag (strict-JSON safe)."""
    if not h or not h.get("count"):
        return {"count": 0, "p50_ns": None, "p99_ns": None}
    out = {"count": h["count"]}
    overflow = False
    for key, q in (("p50_ns", 0.5), ("p99_ns", 0.99)):
        v = fm.hist_quantile(h, q)
        if v == float("inf"):
            out[key] = None
            overflow = True
        else:
            out[key] = v
    if overflow:
        out["overflow"] = True
    return out


def _hmerge(a: dict | None, b: dict | None) -> dict | None:
    """Merge two hist() dicts of the same schema (bucket counts sum)."""
    if a is None:
        return b
    if b is None:
        return a
    return {
        "buckets": a["buckets"],
        "counts": [x + y for x, y in zip(a["counts"], b["counts"])],
        "sum": a["sum"] + b["sum"],
        "count": a["count"] + b["count"],
    }


def _is_hist(v) -> bool:
    return isinstance(v, dict) and "counts" in v


def _stage_block(mets: dict, records: list) -> dict:
    """One stage's report block from its registry_obj snapshot + flight
    records."""
    phases = {}
    for ph in fm.NSWEEP_PHASES:
        phases[ph] = _pq(mets.get(f"nsweep_{ph}_ns"))
    block: dict = {
        "sweep_phases": phases,
        "e2e": _pq(mets.get("frag_latency_ns")),
        "nsweep_lat": _pq(mets.get("nsweep_lat_ns")),
    }
    if _is_hist(mets.get("nbank_txn_lat_ns")):
        block["txn_lat"] = _pq(mets.get("nbank_txn_lat_ns"))
    native = {
        "frags": int(mets.get("nsweep_frags", 0) or 0),
        "crossings": int(mets.get("nsweep_crossings", 0) or 0),
    }
    for name in _NATIVE_EXTRA:
        v = mets.get(name)
        if v is not None and not _is_hist(v):
            native[name] = int(v)
    block["native"] = native
    block["counters"] = {k: int(v) for k, v in sorted(mets.items())
                        if not _is_hist(v)}
    # In-crossing C-side evidence: the chaos crash assertions check that
    # a SIGKILLed sweep stage's LAST drain/publish made it to the shm
    # flight ring (fdm_flight release-stores survive any kill).
    flight = {"nsweep_drain": 0, "nsweep_publish": 0,
              "last_drain_ts": None, "last_publish_ts": None}
    # which phase of which device batch held the stage's thread for
    # 100 ms or more (verify): the answer to "where did the pause go"
    stalls = []
    for ts, ev, arg in records:
        if ev == fm.EV_NSWEEP_DRAIN:
            flight["nsweep_drain"] += 1
            flight["last_drain_ts"] = ts
        elif ev == fm.EV_NSWEEP_PUBLISH:
            flight["nsweep_publish"] += 1
            flight["last_publish_ts"] = ts
        elif ev == fm.EV_BATCH_STALL:
            stalls.append({"ts": ts, **fm.batch_stall_fields(arg)})
    block["flight"] = flight
    if stalls:
        block["batch_stalls"] = stalls
    # beside them, what closed the verify stage's batches (full /
    # deadline / window: the three add up to the `batches` counter)
    if fm.BATCH_CLOSE_COUNTERS[0] in mets:
        block["batch_closes"] = {
            c: int(mets.get(name, 0) or 0)
            for c, name in zip(fm.BATCH_CLOSES, fm.BATCH_CLOSE_COUNTERS)}
        # how many of them were dispatched behind a running one, how
        # many a backlog in front kept open past their deadline, the
        # lanes they left empty because the next transaction did not
        # fit, the lanes of the transactions that failed whole, and
        # how the program lays its batch (the gauge)
        for name in (fm.BATCH_QUEUED_BEHIND, fm.BATCH_HELD_BACKLOGGED,
                     fm.BATCH_FIT_PAD_LANES, fm.VERIFY_FAIL_ELEMS,
                     fm.KERNEL_FOLD_LANES):
            block[name] = int(mets.get(name, 0) or 0)
        # and how many frags one intake crossing took, since boot
        intake = fm.intake_row(mets)
        if intake and intake["crossings"]:
            block["frags_per_crossing"] = (intake["frags"]
                                           / intake["crossings"])
    # the thread's ledger: what of the stage's loop time went to work,
    # to backpressure (the tile behind it held it up) and to empty
    # polls, since boot.  With a process a tile: the busiest limits
    loop = fm.loop_row([mets])
    shares = loop and fm.loop_shares(loop)
    if shares:
        block["loop"] = dict(loop, **shares)
    # when the chip had nothing of the verify stage's to run (ns and
    # intervals, cumulative), and of that the shares the thread spent
    # in other stages and in the stage's own blocking calls
    empty = fm.chip_empty_row(mets)
    if empty:
        ns = empty["ns"]
        block["chip_empty"] = dict(
            empty,
            away_pct=100.0 * empty["away_ns"] / ns if ns else None,
            call_pct=100.0 * empty["call_ns"] / ns if ns else None)
    # a verify stage over a mesh: how many chips, and the useful lanes
    # each was dealt
    mesh = fm.mesh_row(mets)
    if mesh:
        block["mesh"] = mesh
    # pack and the banks: votes scheduled / dropped / landed / landed
    # failed, and the scan steps pack made over a locked account
    votes = fm.vote_row(mets)
    if votes:
        block["votes"] = votes
    # a bank tile's use of the account store it shares with the others
    # (its lock, what its session took from the segment after another
    # tile's write, and each wait over 100 us with the holder's writer
    # id: bank tile i is writer i + 1); pack: microblocks a bank
    funk = fm.funk_row(mets)
    if funk:
        waits = [{"ts": ts, **fm.funk_lock_wait_fields(arg)}
                 for ts, ev, arg in records if ev == fm.EV_FUNK_LOCK_WAIT]
        block["funk"] = dict(funk, long_waits=waits) if waits else funk
    # the dedup stage: transactions its tag cache dropped and the
    # signatures they carried
    dedup = fm.dedup_row(mets)
    if dedup:
        block["dedup"] = dedup
    # the front door: a quic tile's datagrams, punts, reassembly
    # outcomes and held transactions; a sender tile's sends, resends
    # and calls held by the peer's credit
    front = fm.front_row(mets)
    if front:
        block["front"] = front
    # a replay verify stage: entry batches, verdicts, what dead slots
    # cost, the PoH check's and the unpack's ns
    replay = fm.replay_row(mets)
    if replay:
        block["replay"] = replay
    return block


def _funk_pseudo_stage(dump_stages: dict) -> dict | None:
    """Derive the `funk` stage block: funk apply runs inside the bank
    crossing (native shm storage plane), so its profile is the bank
    shards' merged apply-phase histogram + funk counters — with more
    than one bank tile over the one store, every tile's: the lock they
    meet at and what each took from the segment after another's write
    (fm.FUNK_COUNTERS, summed; `bank_tiles` says over how many)."""
    apply_h = None
    writes = falls = n_banks = 0
    shared = dict.fromkeys(fm.FUNK_COUNTERS, 0)
    found = False
    for name, st in dump_stages.items():
        mets = st.get("metrics") or {}
        if "bank_funk_writes" not in mets:
            continue
        found = True
        n_banks += 1
        writes += int(mets.get("bank_funk_writes", 0) or 0)
        falls += int(mets.get("bank_funk_falls", 0) or 0)
        for k in shared:
            shared[k] += int(mets.get(k, 0) or 0)
        h = mets.get("nsweep_apply_ns")
        if _is_hist(h):
            apply_h = _hmerge(apply_h, h)
    if not found:
        return None
    empty = {"count": 0, "p50_ns": None, "p99_ns": None}
    return {
        "derived_from": "bank apply phase (funk rides the bank crossing)",
        "sweep_phases": {
            "drain": dict(empty),
            "callback": dict(empty),
            "apply": _pq(apply_h),
            "publish": dict(empty),
        },
        "e2e": dict(empty),
        "nsweep_lat": dict(empty),
        "native": {"frags": 0, "crossings": 0},
        "counters": {"bank_funk_writes": writes, "bank_funk_falls": falls,
                     "bank_tiles": n_banks, **shared},
        "flight": {"nsweep_drain": 0, "nsweep_publish": 0,
                   "last_drain_ts": None, "last_publish_ts": None},
    }


def _fold_slots(dump_stages: dict) -> tuple[list, int]:
    """Reconstruct the per-slot table from EV_SLOT_* flight events across
    every stage, and count EV_RESTART respawn events.

    Boundaries are EV_SLOT_SEAL/EV_SLOT_MISSED records (arg = slot);
    duplicates (several shards stamping the same seal) dedup to the
    earliest timestamp.  EV_MICROBLOCK (arg = txns) and EV_SLOT_SHED
    (arg = txns) attribute to the first boundary at-or-after their
    timestamp; events after the last boundary land in a trailing
    open-slot row (slot null) so nothing is silently dropped."""
    boundaries: dict[tuple[int, bool], int] = {}  # (slot, sealed) -> ts
    work: list[tuple[int, int, int]] = []         # (ts, ev, arg)
    restarts = 0
    for st in dump_stages.values():
        for ts, ev, arg in st.get("records", ()):
            if ev in (fm.EV_SLOT_SEAL, fm.EV_SLOT_MISSED):
                key = (arg, ev == fm.EV_SLOT_SEAL)
                if key not in boundaries or ts < boundaries[key]:
                    boundaries[key] = ts
            elif ev in (fm.EV_MICROBLOCK, fm.EV_SLOT_SHED):
                work.append((ts, ev, arg))
            elif ev == fm.EV_RESTART:
                restarts += 1
    rows = [{"slot": slot, "sealed": sealed, "ts_ns": ts,
             "microblocks": 0, "txns": 0, "shed_txns": 0}
            for (slot, sealed), ts in boundaries.items()]
    rows.sort(key=lambda r: (r["ts_ns"], r["slot"]))
    open_row = {"slot": None, "sealed": None, "ts_ns": None,
                "microblocks": 0, "txns": 0, "shed_txns": 0}
    for ts, ev, arg in sorted(work):
        dst = open_row
        for r in rows:
            if ts <= r["ts_ns"]:
                dst = r
                break
        if ev == fm.EV_MICROBLOCK:
            dst["microblocks"] += 1
            dst["txns"] += arg
        else:
            dst["shed_txns"] += arg
    if open_row["microblocks"] or open_row["shed_txns"]:
        rows.append(open_row)
    return rows, restarts


def build_report(dump: dict) -> dict:
    """The per-run slot report from one flight-dump object."""
    dump_stages = dump.get("stages", {}) or {}
    stages = {}
    for name in sorted(dump_stages):
        st = dump_stages[name]
        stages[name] = _stage_block(st.get("metrics") or {},
                                    st.get("records", ()))
    if "funk" not in stages:
        funk = _funk_pseudo_stage(dump_stages)
        if funk is not None:
            stages["funk"] = funk
    slots, restarts = _fold_slots(dump_stages)
    return {
        "kind": REPORT_KIND,
        "uid": dump.get("uid"),
        "failed": dump.get("failed"),
        "reason": dump.get("reason", ""),
        "slots": slots,
        "sealed": sum(1 for r in slots if r["sealed"] is True),
        "missed": sum(1 for r in slots if r["sealed"] is False),
        "restarts": restarts,
        "stages": stages,
    }


def report_from_session(ses) -> dict:
    """Live slot report from an attached MonitorSession."""
    return build_report(ses.flight_dump("slotreport"))


def aggregate_reports(reports: list[dict]) -> dict:
    """Fold several per-run reports (one per validator / dump file) into
    one cluster-wide object: roll-up totals plus the per-node reports."""
    return {
        "kind": AGGREGATE_KIND,
        "nodes": len(reports),
        "sealed": sum(r.get("sealed", 0) for r in reports),
        "missed": sum(r.get("missed", 0) for r in reports),
        "restarts": sum(r.get("restarts", 0) for r in reports),
        "reports": reports,
    }


# -- cluster mode (chaos/cluster.py harness) ---------------------------------


def cluster_report(harness, first_slot: int, n_slots: int) -> dict:
    """Aggregate a ClusterHarness run into a per-slot cluster report.

    Folded entirely from deterministic model state (the harness clock is
    rounds-based, not wall time), so two same-seed runs produce
    byte-identical JSON — CI diffs them for determinism."""
    obs = harness.observer
    chain = set(obs.best_chain())
    slots = []
    for slot in range(first_slot, first_slot + n_slots):
        leader = harness.leader_of(slot)
        sealed_by = sorted(v.index for v in harness.validators
                           if slot in v.blocks)
        slots.append({
            "slot": slot,
            "leader": leader.index if leader is not None else None,
            "sealed_by": sealed_by,
            "on_best_chain": slot in chain,
            "observer_landed": len(obs.landed.get(slot, ())),
        })
    validators = []
    for v in harness.validators:
        validators.append({
            "index": v.index,
            "alive": bool(v.alive),
            "frozen": bool(v.frozen),
            "cold_boots": v.cold_boots,
            "blocks": len(v.blocks),
            "chain_len": len(v.best_chain()),
            "landed_txns": sum(len(s) for s in v.landed.values()),
            "shred_receipts": len(v.receipts),
        })
    return {
        "kind": CLUSTER_KIND,
        "n_validators": len(harness.validators),
        "first_slot": first_slot,
        "n_slots": n_slots,
        "slots": slots,
        "validators": validators,
        "sealed": sum(1 for r in slots if r["sealed_by"]),
        "missed": sum(1 for r in slots if not r["sealed_by"]),
        "faults_fired": list(harness.fired),
        "landed_digest": harness.landed_digest(),
        "net": {"cut_dropped": harness.net.cut_dropped,
                "lossy_dropped": harness.net.lossy_dropped},
    }


def run_cluster_report(n: int, *, slots: int, seed: int) -> dict:
    """Boot a small in-process cluster, run it fault-free, and report —
    the `slotreport --cluster N` CLI/CI entry point."""
    from ..chaos.cluster import ClusterHarness
    h = ClusterHarness(n, seed=seed, steps_per_slot=24, n_txns=28)
    try:
        h.boot()
        h.make_client(per_slot=2)
        h.run_slots(1, slots)
        h.settle(40)
        rep = cluster_report(h, 1, slots)
        rep["seed"] = seed
        return rep
    finally:
        h.close()


# -- determinism normalisation ----------------------------------------------


def normalize(report: dict) -> dict:
    """Strip timing-dependent fields so two same-seed runs of the SAME
    scenario compare equal: pipeline reports keep only seed-deterministic
    structure (stage names, phase keys, metric-name sets); cluster
    reports are already deterministic and pass through whole."""
    kind = report.get("kind")
    if kind == CLUSTER_KIND:
        return report
    if kind == AGGREGATE_KIND:
        return {
            "kind": kind,
            "nodes": report.get("nodes"),
            "reports": [normalize(r) for r in report.get("reports", ())],
        }
    out = {"kind": kind, "stages": {}}
    for name in sorted(report.get("stages", {})):
        st = report["stages"][name]
        out["stages"][name] = {
            "sweep_phases": sorted(st.get("sweep_phases", {})),
            "counters": sorted(st.get("counters", {})),
            "has_txn_lat": "txn_lat" in st,
        }
    return out


def dumps(report: dict) -> str:
    return json.dumps(report, indent=2, sort_keys=True) + "\n"
