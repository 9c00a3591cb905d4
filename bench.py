"""Headline benchmark: batched ed25519 sigverify throughput on one chip.

Mirrors the reference's verify-tile measurement configs (BASELINE.md):
1-signature transfer-sized messages, fixed batch, steady-state pipelined
dispatch.  Baseline for the vs_baseline ratio is the reference's own
accelerator backend: the wiredancer FPGA at 1.0 M verify/s
(/root/reference/src/wiredancer/README.md:100-103,118-122).

`python bench.py` requires the TPU (utils/platform.require_chip), runs
in this one process, prints exactly one JSON line
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "backend": ...}
and exits non-zero if any phase raised: there is no fallback device and
no partial result.  `--cpu` is the explicit CPU run and labels its
output "backend": "cpu".
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

BASELINE_VERIFY_PER_S = 1.0e6  # wiredancer FPGA, the reference's offload path
BATCH = int(os.environ.get("FDTPU_BENCH_BATCH", "16384"))
MAX_MSG_LEN = 128
STEADY_ROUNDS = int(os.environ.get("FDTPU_BENCH_ROUNDS", "8"))
INFLIGHT = int(os.environ.get("FDTPU_BENCH_INFLIGHT", "4"))


def run_bench(cpu: bool = False, *, rounds: int = STEADY_ROUNDS) -> None:
    from firedancer_tpu.utils.platform import select_device

    select_device(cpu)
    import jax
    import jax.numpy as jnp

    from firedancer_tpu.ops import sigverify as sv
    import __graft_entry__ as ge

    dev = jax.devices()[0]
    print(f"# bench: device={dev.platform}:{dev.device_kind} "
          f"x{jax.device_count()} kernel=fused", file=sys.stderr)

    batch = BATCH
    msg, msg_len, sig, pk = ge._example_batch(batch)
    args = tuple(
        jax.device_put(jnp.asarray(a), dev) for a in (msg, msg_len, sig, pk)
    )
    n_real = jnp.int32(batch)

    def step(a):
        # the served program (the verify stage's fused single dispatch);
        # its on-device ok-count is the scalar every timing barrier below
        # fetches, so a completed fetch means a completed batch
        return sv.ed25519_verify_batch_fused(
            *a, n_real, max_msg_len=MAX_MSG_LEN)[1]

    def fetch(o) -> int:
        return int(np.asarray(o))

    # Warmup / compile.
    t0 = time.time()
    n_ok = fetch(step(args))
    print(
        f"# compile+first batch {time.time()-t0:.1f}s, {n_ok}/{batch} ok",
        file=sys.stderr,
    )
    assert n_ok == batch, "honest signatures must all verify"

    # Steady state: keep INFLIGHT batches in flight, fetch to cap the
    # queue — the async-offload shape the wiredancer path uses (requests
    # pushed, the results ring drained later).  Per-batch completion
    # latency is measured in a second, serialized pass.
    outs = []
    t0 = time.time()
    for r in range(rounds):
        outs.append(step(args))
        if len(outs) >= INFLIGHT:
            fetch(outs.pop(0))
    for o in outs:
        fetch(o)
    elapsed = time.time() - t0
    total = batch * rounds
    rate = total / elapsed

    lat = []
    for _ in range(rounds):
        t1 = time.time()
        fetch(step(args))
        lat.append(time.time() - t1)
    lat_ms = np.array(sorted(lat)) * 1e3
    p50 = lat_ms[len(lat_ms) // 2]
    p99 = lat_ms[min(int(len(lat_ms) * 0.99), len(lat_ms) - 1)]
    print(
        f"# steady: {total} sigs in {elapsed:.3f}s; batch latency "
        f"p50={p50:.2f}ms p99={p99:.2f}ms (batch={batch})",
        file=sys.stderr,
    )
    out = {
        "metric": "ed25519_sigverify_per_s_per_chip",
        "value": round(rate, 1),
        "unit": "verify/s",
        "vs_baseline": round(rate / BASELINE_VERIFY_PER_S, 4),
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "kernel": "fused",
        "batch": batch,
        "batch_latency_p99_ms": round(float(p99), 3),
    }
    # Repeated-signer fast path (vote-shaped traffic): pre-fill the comb
    # bank for the batch's unique signers, then steady-state the cached
    # kernel.  Real ingress is mostly votes from a bounded signer set, so
    # this is the steady-state rate a validator actually sees; the generic
    # number above is the cold/unique-signer floor.
    out.update(run_comb_bench(args, batch, rounds, fetch))
    # Secondary headline: whole-pipeline txn/s (the bencho analog; the
    # reference's pure-leader figure is 270K txn/s, book/guide/tuning.md:
    # 238-254), then the host machinery alone.  A failure in any phase
    # fails the run: no number is printed for a run that raised.
    out.update(run_pipeline_bench(dev.platform))
    out.update(run_host_pipeline_bench())
    print(json.dumps(out))


PIPELINE_BASELINE_TXN_PER_S = 270_000.0  # reference pure-leader bench


def _scrape_stage_latencies(pipe) -> dict:
    """Per-stage + end-to-end latency percentiles from the stages' schema
    metrics (utils/metrics.py): every stage's frag_latency_ns histogram
    observes now - tsorig per consumed frag, and tsorig is stamped ONCE
    at benchg and carried through every ring — so the store stage's
    histogram IS the whole ingress->verify->...->shred->store path."""
    stages = {}
    for s in pipe.stages:
        try:
            h = s.metrics.hist("frag_latency_ns")
        except KeyError:
            continue
        if not h["count"]:
            continue

        def q(p):
            # the +Inf overflow estimate must stay strict-JSON: clamp to
            # the top edge and flag it (json.dumps would emit the
            # non-standard `Infinity` token and break artifact parsers)
            v = s.metrics.quantile("frag_latency_ns", p)
            return (round(h["buckets"][-1], 1), True) if v == float("inf") \
                else (round(v, 1), False)

        p50, o50 = q(0.5)
        p99, o99 = q(0.99)
        stages[s.name] = {"p50_ns": p50, "p99_ns": p99, "count": h["count"]}
        if o50 or o99:
            stages[s.name]["overflow"] = True  # true value above top edge
        # sweep-phase decomposition (ISSUE 20 tentpole b): the nsweep_*
        # words are C-owned, written from inside the fdr_sweep crossing —
        # read them off the registry, never the Python facade
        from firedancer_tpu.utils import metrics as fm

        reg = s.metrics.registry
        if reg is not None:
            phases = {}
            for ph in fm.NSWEEP_PHASES:
                try:
                    ph_h = reg.hist(f"nsweep_{ph}_ns")
                except KeyError:
                    continue
                if not ph_h["count"]:
                    continue
                p50v = fm.hist_quantile(ph_h, 0.5)
                p99v = fm.hist_quantile(ph_h, 0.99)
                top = ph_h["buckets"][-1]
                phases[ph] = {
                    "count": ph_h["count"],
                    "p50_ns": round(min(p50v, top), 1),
                    "p99_ns": round(min(p99v, top), 1),
                }
            if phases:
                stages[s.name]["sweep_phases"] = phases
    out = {"stage_latency_ns": stages}
    e2e = stages.get(pipe.store.name)
    if e2e:
        out["e2e_latency_p50_ns"] = e2e["p50_ns"]
        out["e2e_latency_p99_ns"] = e2e["p99_ns"]
    return out


def run_comb_bench(args, batch: int, rounds: int, fetch) -> dict:
    """Steady-state the cached (comb-bank) kernel on the same batch."""
    import jax.numpy as jnp

    from firedancer_tpu.ops import sigverify as sv
    import __graft_entry__ as ge

    msg, msg_len, sig, pk = args
    uniq = np.unique(np.asarray(pk), axis=1)
    n_signers = uniq.shape[1]
    fill = np.zeros((32, n_signers), dtype=np.uint8)
    fill[:, :] = uniq
    t0 = time.time()
    tables, ok = sv.comb_fill(jnp.asarray(fill))
    assert int(np.asarray(jnp.sum(ok.astype(jnp.int32)))) == n_signers
    bank = sv.bank_alloc(n_signers)
    bank = sv.bank_install(
        bank, tables, jnp.asarray(np.arange(n_signers, dtype=np.int32))
    )
    # slot per element = index of its pubkey among the unique signers
    pk_np = np.asarray(pk)
    keys = {uniq[:, i].tobytes(): i for i in range(n_signers)}
    slots = np.asarray(
        [keys[pk_np[:, i].tobytes()] for i in range(batch)], dtype=np.int32
    )
    slots = jnp.asarray(slots)

    def step():
        return jnp.sum(
            sv.ed25519_verify_batch_cached(
                msg, msg_len, sig, pk, bank, slots,
                max_msg_len=ge.MAX_MSG_LEN,
            ).astype(jnp.int32)
        )

    n_ok = fetch(step())  # compile + first batch
    print(
        f"# comb: bank fill + compile + first batch {time.time()-t0:.1f}s, "
        f"{n_ok}/{batch} ok ({n_signers} signers)",
        file=sys.stderr,
    )
    assert n_ok == batch, "cached kernel must verify all honest signatures"
    outs = []
    t0 = time.time()
    for r in range(rounds):
        outs.append(step())
        if len(outs) >= INFLIGHT:
            fetch(outs.pop(0))
    for o in outs:
        fetch(o)
    elapsed = time.time() - t0
    rate = batch * rounds / elapsed
    print(
        f"# comb steady: {batch * rounds} sigs in {elapsed:.3f}s "
        f"({rate:.0f}/s cached)",
        file=sys.stderr,
    )
    return {
        "comb_verify_per_s": round(rate, 1),
        "comb_vs_baseline": round(rate / BASELINE_VERIFY_PER_S, 4),
        "comb_signers": n_signers,
    }


AB_MIN_PAIRS = 2


def _require_ab_pairs(pairs: int, label: str) -> int:
    """Variance hygiene (ISSUE 11): single-window A/B readings on the
    1-core box swing +-15% run to run and have produced absurd per-stage
    figures (see docs/PERF.md round 8's postmortem) — interleaved ON/OFF
    pairs are MANDATORY for every A/B metric.  Fails loudly rather than
    producing a number that looks like evidence."""
    if pairs < AB_MIN_PAIRS:
        raise ValueError(
            f"single-window A/B requested for '{label}' (pairs={pairs}): "
            f"readings on this box swing +-15% between windows, so a "
            f"lone ON/OFF comparison is noise dressed as a delta — pass "
            f"pairs >= {AB_MIN_PAIRS} (interleaved ON/OFF measurement)."
        )
    return pairs


from statistics import median as _median


def ab_summary(ons: list[dict], offs: list[dict], key: str) -> dict:
    """Per-pair deltas + median-of-pairs for one metric across the
    interleaved readings (every A/B metric in an artifact reports this
    shape, never a single window)."""
    on_v = [o.get(key) for o in ons]
    off_v = [o.get(key) for o in offs]
    deltas = [None if (a is None or b is None) else round(a - b, 2)
              for a, b in zip(on_v, off_v)]
    ok_d = [d for d in deltas if d is not None]
    return {
        "on": on_v,
        "off": off_v,
        "pair_delta": deltas,
        "on_median": round(_median([v for v in on_v if v is not None]), 2)
        if any(v is not None for v in on_v) else None,
        "off_median": round(_median([v for v in off_v if v is not None]), 2)
        if any(v is not None for v in off_v) else None,
        "delta_median": round(_median(ok_d), 2) if ok_d else None,
    }


def run_host_pipeline_bench(pairs: int | None = None) -> dict:
    """Pipeline machinery throughput NET of accelerator round trips: the
    verify stage runs with a precomputed all-pass mask (no device
    dispatch), so rings/parse/dedup/pack/bank/poh/shred are what's timed.
    The target to beat is the reference's stock single-host bench, 63K
    txn/s (book/guide/tuning.md:131).

    Measures the all-native configuration against each lane's Python
    fallback (`*_native_pack_off`, `*_native_ring_off`,
    `*_native_shred_off`) in INTERLEAVED ON/OFF pairs — single-window
    A/B readings swing +-15% on the 1-core box, so every pair cycle
    measures ON then each OFF lane back to back and the artifact
    carries per-pair deltas + median-of-pairs (`ab` key).  Every
    measure also splits ring overhead (poll+publish) from stage compute
    in the per-stage us/txn breakdown."""
    from firedancer_tpu.pack import scheduler_native as sn
    from firedancer_tpu.runtime import shred_native as shn
    from firedancer_tpu.runtime import verify_native as vfn
    from firedancer_tpu.tango import shm as tango_shm

    pairs = _require_ab_pairs(
        pairs if pairs is not None
        else int(os.environ.get("FDTPU_BENCH_AB_PAIRS", "2")),
        "host pipeline lanes",
    )
    ring_avail = tango_shm._native_ring_available()
    pack_avail = sn.available()
    shred_avail = shn.available()
    verify_avail = vfn.available()
    if not (ring_avail or pack_avail or shred_avail or verify_avail):
        # toolchain-less host: no fallback lane to compare against, so
        # repeated identical windows buy nothing — one measurement
        pairs = 1
    ons: list[dict] = []
    lanes: dict[str, list[dict]] = {}
    windows: list[tuple] = [("on", dict(native_pack=pack_avail))]
    if pack_avail:
        windows.append(("pack", dict(native_pack=False)))
    if ring_avail:
        windows.append(("ring", dict(native_pack=pack_avail,
                                     native_ring=False)))
    if shred_avail:
        windows.append(("shred", dict(native_pack=pack_avail,
                                      native_shred=False)))
    if verify_avail:
        windows.append(("verify", dict(native_pack=pack_avail,
                                       native_verify=False)))
    if len(windows) > 1:
        # the process's first measure pays one-time costs (imports, comb
        # tables, numpy warmup) — discard one window so pair 0's first
        # lane isn't systematically biased low
        _host_pipeline_warm_window()
    for i in range(pairs):
        # alternate within-pair order so a slow box phase (and any
        # residual process aging) penalizes lanes evenly across the run
        order = windows if i % 2 == 0 else list(reversed(windows))
        for lane, kw in order:
            m = _host_pipeline_measure(**kw)
            (ons if lane == "on" else lanes.setdefault(lane, [])).append(m)
    out = dict(ons[-1])  # headline keys: the last all-native window
    out["pipeline_host_txn_per_s"] = round(
        _median([o["pipeline_host_txn_per_s"] for o in ons]), 1
    )
    out["pipeline_host_native_pack"] = pack_avail
    out["pipeline_host_ab_pairs"] = pairs
    ab: dict = {}
    for lane, offs in lanes.items():
        ab[lane] = {
            "txn_per_s": ab_summary(ons, offs, "pipeline_host_txn_per_s"),
        }
        # legacy single-value keys stay as the medians so existing
        # consumers keep working
        out[f"pipeline_host_txn_per_s_native_{lane}_off"] = \
            ab[lane]["txn_per_s"]["off_median"]
    if "ring" in lanes:
        roffs = lanes["ring"]
        ab["ring"]["ring_us_per_txn"] = ab_summary(
            ons, roffs, "pipeline_host_ring_us_per_txn")
        out["pipeline_host_ring_us_per_txn_native_ring_off"] = \
            ab["ring"]["ring_us_per_txn"]["off_median"]
        out["pipeline_host_ring_us_per_stage_native_ring_off"] = \
            roffs[-1]["pipeline_host_ring_us_per_stage"]
    if "verify" in lanes:
        voffs = lanes["verify"]
        ab["verify"]["verify_stage_us_per_txn"] = ab_summary(
            [{"v": o["pipeline_host_stage_us_per_txn"].get("verify0")}
             for o in ons],
            [{"v": o["pipeline_host_stage_us_per_txn"].get("verify0")}
             for o in voffs],
            "v",
        )
        out["pipeline_host_verify_us_per_txn_native_verify_off"] = \
            ab["verify"]["verify_stage_us_per_txn"]["off_median"]
    if "shred" in lanes:
        soffs = lanes["shred"]
        ab["shred"]["shred_stage_us_per_txn"] = ab_summary(
            [{"v": o["pipeline_host_stage_us_per_txn"].get("shred")}
             for o in ons],
            [{"v": o["pipeline_host_stage_us_per_txn"].get("shred")}
             for o in soffs],
            "v",
        )
        out["pipeline_host_shred_us_per_txn_native_shred_off"] = \
            ab["shred"]["shred_stage_us_per_txn"]["off_median"]
        out["pipeline_host_stage_us_per_txn_native_shred_off"] = \
            soffs[-1]["pipeline_host_stage_us_per_txn"]
    out["ab"] = ab
    out["verify_stage_host_txn_per_s"] = round(_verify_stage_loop_rate(), 1)
    return out


def _host_pipeline_warm_window() -> None:
    """One small, DISCARDED pipeline window: the process's first measure
    pays one-time costs (imports, comb tables, numpy warmup) that the
    in-measure 512-txn warmup does not cover — without this the first
    real window reads ~1K txn/s low and 'pair 0' measures process age."""
    prev = os.environ.get("FDTPU_BENCH_PIPELINE_TXNS")
    os.environ["FDTPU_BENCH_PIPELINE_TXNS"] = "2048"
    try:
        print("# A/B warmup window (discarded)", file=sys.stderr)
        _host_pipeline_measure(native_pack=False)
    finally:
        if prev is None:
            os.environ.pop("FDTPU_BENCH_PIPELINE_TXNS", None)
        else:
            os.environ["FDTPU_BENCH_PIPELINE_TXNS"] = prev


def run_shred_ab(pairs: int = 3, out_path: str | None = None) -> dict:
    """The ISSUE 11 acceptance artifact: interleaved same-box A/B of the
    native shredder lane — per pair, one all-native window and one
    window with ONLY the shred lane off, per-stage us/txn tables for
    both, per-pair deltas and median-of-pairs.  Writes
    BENCH_r10_shred_ab.json (or FDTPU_BENCH_SHRED_AB_PATH)."""
    from firedancer_tpu.runtime import shred_native as shn

    from firedancer_tpu.pack import scheduler_native as sn_pack

    _require_ab_pairs(pairs, "shred lane A/B")
    if not shn.available():
        print("# native shredder unavailable: no A/B to run",
              file=sys.stderr)
        return {"shred_ab_unavailable": True}
    pack_avail = sn_pack.available()
    ons, offs = [], []
    _host_pipeline_warm_window()
    for i in range(pairs):
        print(f"# shred A/B pair {i + 1}/{pairs}", file=sys.stderr)
        # alternate within-pair order so a slow box phase penalizes both
        # lanes evenly across the run, not always the same one
        order = (True, False) if i % 2 == 0 else (False, True)
        for on in order:
            (ons if on else offs).append(_host_pipeline_measure(
                native_pack=pack_avail, native_shred=on))
    out = {
        "pairs": pairs,
        "txn_per_s": ab_summary(ons, offs, "pipeline_host_txn_per_s"),
        # one A/B-metric shape everywhere: the same {"v": ...} wrap the
        # host-pipeline artifact uses for per-stage keys
        "shred_us_per_txn": ab_summary(
            [{"v": o["pipeline_host_stage_us_per_txn"].get("shred")}
             for o in ons],
            [{"v": o["pipeline_host_stage_us_per_txn"].get("shred")}
             for o in offs],
            "v",
        ),
        "pipeline_host_txn_per_s": round(_median(
            [o["pipeline_host_txn_per_s"] for o in ons]), 1),
        "stage_us_per_txn_on": [o["pipeline_host_stage_us_per_txn"]
                                for o in ons],
        "stage_us_per_txn_off": [o["pipeline_host_stage_us_per_txn"]
                                 for o in offs],
        "shred_mode_on": ons[-1].get("pipeline_host_native_shred"),
        "shred_mode_off": offs[-1].get("pipeline_host_native_shred"),
        "native_exec": ons[-1].get("pipeline_host_native_exec"),
        "native_ring": ons[-1].get("pipeline_host_native_ring"),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    path = out_path or os.environ.get("FDTPU_BENCH_SHRED_AB_PATH",
                                      "BENCH_r10_shred_ab.json")
    try:
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"# shred A/B artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# shred A/B artifact write failed: {e}", file=sys.stderr)
    return out


class _NetSink:
    """Unlimited-credit null producer: counts published frames so the
    ingress windows measure intake, not downstream compute."""

    def __init__(self):
        self.n = 0

    def try_publish(self, payload, sig=0, tsorig=0):
        self.n += 1
        return True


def _net_env(native: bool):
    prev = os.environ.get("FDTPU_NATIVE_NET")
    os.environ["FDTPU_NATIVE_NET"] = "1" if native else "0"
    return prev


def _net_env_restore(prev):
    if prev is None:
        os.environ.pop("FDTPU_NATIVE_NET", None)
    else:
        os.environ["FDTPU_NATIVE_NET"] = prev


def _net_quic_window(native: bool, clients: int = 4,
                     dgrams: int = 240) -> dict:
    """One QUIC-flavor ingress window: establish in-process client
    connections against a ChaosSock'd stage, pre-seal the steady-state
    short-header datagrams OUTSIDE the timed region, then time ONLY the
    ingress path (stage._on_datagram + after_credit) — µs/datagram with
    client-side seal and downstream compute split out.  The OFF window
    pins the net lane off at stage build (FDTPU_NATIVE_NET=0) and
    ops/aes.py to pure Python for the timed region only, so setup stays
    fast and the measured lane is honest."""
    import hashlib

    from firedancer_tpu.chaos.population import ChaosSock
    from firedancer_tpu.ops import aes
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.runtime.net import QuicIngressStage
    from firedancer_tpu.waltz import quic

    identity = hashlib.sha256(b"net-ab").digest()
    prev = _net_env(native)
    try:
        sink = _NetSink()
        st = QuicIngressStage("quic", outs=[sink], sock=ChaosSock(),
                              rx_burst=64, identity_secret=identity)
        assert (st._net_client is not None) == native
        conns = []
        for ci in range(clients):
            c = quic.Connection.client_new(
                expected_peer=ref.public_key(identity))
            addr = ("ab", ci)
            for _ in range(40):
                moved = False
                for dg in c.flush():
                    moved = True
                    st._on_datagram(dg, addr)
                q = st.sock.tx.get(addr)
                while q:
                    moved = True
                    c.receive(q.popleft())
                if not moved:
                    break
            assert c.established
            conns.append((c, addr))
        # mixed steady-state txn sizes, one short-header datagram each
        sizes = (96, 512, 1200)
        h = hashlib.sha256(b"net-ab-payload")
        batch = []
        sids = [2] * clients
        for i in range(dgrams):
            ci = i % clients
            c, addr = conns[ci]
            n = sizes[i % len(sizes)]
            buf = b""
            while len(buf) < n:
                h = hashlib.sha256(h.digest() + bytes([ci]))
                buf += h.digest()
            c.send_stream(sids[ci], buf[:n], fin=True)
            sids[ci] += 4
            for dg in c.flush():
                batch.append((dg, addr))
        sent_txns = dgrams
        base_txns = sink.n
        if not native:
            aes._NATIVE = False  # pure-Python lane for the timed region
        try:
            t0 = time.perf_counter()
            for dg, addr in batch:
                st._on_datagram(dg, addr)
            st.after_credit()
            elapsed = time.perf_counter() - t0
        finally:
            aes._NATIVE = None  # back to env-resolved on next call
        delivered = sink.n - base_txns
        st.close()
        if delivered != sent_txns:
            print(f"# net A/B quic window delivered {delivered}/"
                  f"{sent_txns} txns", file=sys.stderr)
        return {"v": round(elapsed * 1e6 / max(len(batch), 1), 3),
                "datagrams": len(batch), "txns": delivered,
                "native": native}
    finally:
        _net_env_restore(prev)


def _net_udp_window(native: bool, pkts: int = 512,
                    payload: int = 900) -> dict:
    """One UDP-flavor ingress window over a real localhost socket: send
    rx_burst-sized chunks, time only the after_credit drains (native
    recvmmsg-style sweep vs one recvfrom per datagram)."""
    import socket as _socket

    from firedancer_tpu.runtime.net import UdpIngressStage

    prev = _net_env(native)
    tx = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
    try:
        sink = _NetSink()
        st = UdpIngressStage("udp", outs=[sink], rx_burst=64)
        assert (st._net_client is not None) == native
        addr = st.addr
        data = b"\xA5" * payload
        elapsed = 0.0
        got0 = st.metrics.get("pkt_rx") or 0
        sent = 0
        while sent < pkts:
            chunk = min(st.rx_burst, pkts - sent)
            for _ in range(chunk):
                tx.sendto(data, addr)
            sent += chunk
            deadline = time.monotonic() + 1.0
            while ((st.metrics.get("pkt_rx") or 0) - got0 < sent
                   and time.monotonic() < deadline):
                t0 = time.perf_counter()
                st.after_credit()
                elapsed += time.perf_counter() - t0
        got = (st.metrics.get("pkt_rx") or 0) - got0
        st.close()
        if got != pkts:
            print(f"# net A/B udp window drained {got}/{pkts} pkts",
                  file=sys.stderr)
        return {"v": round(elapsed * 1e6 / max(got, 1), 3),
                "datagrams": got, "native": native}
    finally:
        tx.close()
        _net_env_restore(prev)


def run_net_ab(pairs: int = 3, out_path: str | None = None) -> dict:
    """The ISSUE 18 acceptance artifact: interleaved same-box A/B of the
    native net sweep client, both ingress flavors — QUIC short-header
    steady state (DCID lookup + HP unmask + GCM open + frame walk +
    reasm in one FFI crossing, vs the per-datagram pure-Python lane) and
    plain UDP (batched sweep vs recvfrom loop).  Per-pair deltas +
    median-of-pairs in ingress µs/datagram, split from client seal and
    downstream compute.  Writes BENCH_r13_net_ab.json (or
    FDTPU_BENCH_NET_AB_PATH)."""
    from firedancer_tpu.runtime import net_native

    _require_ab_pairs(pairs, "net ingress-lane A/B")
    if not net_native.available():
        print("# native net client unavailable: no A/B to run",
              file=sys.stderr)
        return {"net_ab_unavailable": True}
    q_ons, q_offs, u_ons, u_offs = [], [], [], []
    _net_quic_window(True, clients=1, dgrams=24)  # warm both .so paths
    for i in range(pairs):
        print(f"# net A/B pair {i + 1}/{pairs}", file=sys.stderr)
        order = (True, False) if i % 2 == 0 else (False, True)
        for on in order:
            (q_ons if on else q_offs).append(_net_quic_window(on))
            (u_ons if on else u_offs).append(_net_udp_window(on))
    quic_ab = ab_summary(q_ons, q_offs, "v")
    udp_ab = ab_summary(u_ons, u_offs, "v")
    out = {
        "pairs": pairs,
        "quic_ingress_us_per_datagram": quic_ab,
        "udp_ingress_us_per_datagram": udp_ab,
        "quic_speedup_median": round(
            quic_ab["off_median"] / max(quic_ab["on_median"], 1e-9), 2),
        "udp_speedup_median": round(
            udp_ab["off_median"] / max(udp_ab["on_median"], 1e-9), 2),
        "quic_windows_on": q_ons,
        "quic_windows_off": q_offs,
        "udp_windows_on": u_ons,
        "udp_windows_off": u_offs,
        "native_simd": net_native.simd_features(),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    path = out_path or os.environ.get("FDTPU_BENCH_NET_AB_PATH",
                                      "BENCH_r13_net_ab.json")
    try:
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"# net A/B artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# net A/B artifact write failed: {e}", file=sys.stderr)
    return out


def _e2e_ingress_window(net_on: bool, n_txn: int | None = None) -> dict:
    """One e2e window over REAL network bytes: the flagship pipeline
    with a localhost UDP socket at the front (udp_ingress=True) and
    every other native lane at its availability default — ingress ->
    verify -> pack -> bank -> poh+shred -> store, txn/s to execution
    completion.  Only the net sweep lane toggles between windows, so
    the pair delta isolates ingress intake inside the full pipe."""
    import socket as _socket

    from firedancer_tpu.models.leader import build_leader_pipeline
    from firedancer_tpu.runtime.bank import default_bank_ctx
    from firedancer_tpu.runtime.benchg import gen_transfer_pool

    n_txn = n_txn or int(os.environ.get("FDTPU_BENCH_E2E_TXNS", "4096"))
    n_bank = int(os.environ.get("FDTPU_BENCH_PIPELINE_BANKS", "2"))
    warm = 512
    prev = _net_env(net_on)
    tx = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
    pipe = None
    try:
        ctx = default_bank_ctx(n_payers=64)
        pipe = build_leader_pipeline(
            n_verify=1, n_bank=n_bank, pool_size=64, batch=512,
            max_msg_len=256, batch_deadline_s=0.005,
            verify_precomputed=True, bank_ctx=ctx, keep_sets=False,
            fuse_poh_shred=True, udp_ingress=True)
        ing = pipe.benchg
        assert (ing._net_client is not None) == net_on
        # default rmem (~208K of skb truesize) sits right at the burst
        # size and drops silently; ask for headroom (clamped to rmem_max)
        ing.sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, 1 << 22)
        addr = ing.addr
        pool = gen_transfer_pool(n_txn, n_payers=64, n_dests=1024)
        funk_on = (pipe.banks[0]._sweep_client is not None
                   and hasattr(ctx.sx.funk, "txn_diff"))

        def executed() -> int:
            return sum(b.metrics.get("txn_exec") for b in pipe.banks)

        sent = 0
        resends = 0

        def pump(target_exec: int, t_limit: float) -> None:
            nonlocal sent, resends
            deadline = time.monotonic() + t_limit
            prog_t = time.monotonic()
            prog_n = executed()
            while executed() < target_exec and time.monotonic() < deadline:
                # keep <=128 datagrams in the socket buffer: loopback
                # UDP drops silently past the rcvbuf, and a lost txn
                # would pin the window below target until the deadline
                rx = ing.metrics.get("pkt_rx") or 0
                end = min(n_txn, rx + 128)
                while sent < end:
                    tx.sendto(pool[sent], addr)
                    sent += 1
                for s in pipe.stages:
                    s.run_once()
                pipe.pack.after_credit()
                cur = executed()
                if cur != prog_n:
                    prog_n, prog_t = cur, time.monotonic()
                elif (sent >= n_txn
                      and time.monotonic() - prog_t > 0.2):
                    # everything sent but execution stalled: a rare
                    # residual rcvbuf loss ate txns.  Resend the pool —
                    # dedup/tcache absorbs the duplicates, so this is
                    # the UDP client's natural retry, not double-spend
                    sent = 0
                    resends += 1
                    prog_t = time.monotonic()

        pump(warm, 60.0)
        warm_exec = executed()
        for b in pipe.banks:
            b.commit_latencies_ns.clear()
        target = n_txn - 16
        t0 = time.time()
        pump(target, 120.0)
        elapsed = max(time.time() - t0, 1e-9)
        done = executed() - warm_exec
        if executed() < target:
            print(f"# e2e ingress window INCOMPLETE: {executed()}/{target}",
                  file=sys.stderr)
        lats = sorted(
            lat for b in pipe.banks for lat in b.commit_latencies_ns)
        p99_ms = (lats[min(int(len(lats) * 0.99), len(lats) - 1)] / 1e6
                  if lats else -1.0)
        rate = done / elapsed
        print(f"# e2e ingress window: {done} txns in {elapsed:.2f}s "
              f"({rate:.0f} txn/s, net={'on' if net_on else 'off'})",
              file=sys.stderr)
        return {
            "v": round(rate, 1),
            "txns": done,
            "commit_p99_ms": round(p99_ms, 2),
            "resends": resends,
            # the python lane DROPS on ring backpressure (real loss, the
            # resend backstop re-feeds it); the native lane retains the
            # tail in C and re-publishes — zero loss by construction
            "backpressure_drops": (
                0 if ing._net_client is not None
                else ing.metrics.get("pkt_drop_backpressure") or 0),
            "tail_retained": (
                int(ing._net_client.counters()["tail_retained"])
                if ing._net_client is not None else 0),
            "native_net": net_on,
            "lanes": {
                "net": "sweep" if ing._net_client is not None else "python",
                "verify": ("sweep"
                           if pipe.verifies[0]._sweep_client is not None
                           else "python"),
                "bank": ("sweep" if pipe.banks[0]._sweep_client is not None
                         else "python"),
                "shred": ("sweep" if pipe.shred._sweep_client is not None
                          else "python"),
                "funk": "native" if funk_on else "python",
            },
            "incomplete": executed() < target,
        }
    finally:
        tx.close()
        if pipe is not None:
            pipe.close()
        _net_env_restore(prev)


def run_e2e_ingress_ab(pairs: int = 3, out_path: str | None = None) -> dict:
    """The five-lane e2e artifact: the flagship pipeline fed over a real
    localhost socket, interleaved A/B on the net sweep lane only (shred,
    verify, bank, funk stay native in BOTH windows) — the ingress->store
    txn/s delta the net lane buys inside the full pipe.  Writes
    BENCH_r14_e2e_ingress.json (or FDTPU_BENCH_E2E_PATH)."""
    from firedancer_tpu.runtime import net_native

    _require_ab_pairs(pairs, "e2e ingress A/B")
    if not net_native.available():
        print("# native net client unavailable: no e2e A/B to run",
              file=sys.stderr)
        return {"e2e_ingress_unavailable": True}
    _host_pipeline_warm_window()  # reedsol/bmtree compiles out of pair 0
    ons, offs = [], []
    for i in range(pairs):
        print(f"# e2e ingress A/B pair {i + 1}/{pairs}", file=sys.stderr)
        order = (True, False) if i % 2 == 0 else (False, True)
        for on in order:
            (ons if on else offs).append(_e2e_ingress_window(on))
    ab = ab_summary(ons, offs, "v")
    out = {
        "pairs": pairs,
        "e2e_ingress_txn_per_s": ab,
        "e2e_speedup_median": round(
            ab["on_median"] / max(ab["off_median"], 1e-9), 3),
        "commit_p99_ms_on": [o["commit_p99_ms"] for o in ons],
        "commit_p99_ms_off": [o["commit_p99_ms"] for o in offs],
        "lanes_on": ons[-1]["lanes"],
        "windows_on": ons,
        "windows_off": offs,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    path = out_path or os.environ.get("FDTPU_BENCH_E2E_PATH",
                                      "BENCH_r14_e2e_ingress.json")
    try:
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"# e2e ingress artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# e2e ingress artifact write failed: {e}", file=sys.stderr)
    return out


def run_verify_ab(pairs: int = 3, out_path: str | None = None) -> dict:
    """The ISSUE 13 host acceptance artifact: interleaved same-box A/B
    of the native verify sweep lane — per pair, one all-native window
    and one window with ONLY the verify sweep client off (per-frag
    python intake on the same rings), per-stage us/txn tables for both,
    per-pair deltas and median-of-pairs.  Writes
    BENCH_r11_verify_ab.json (or FDTPU_BENCH_VERIFY_AB_PATH)."""
    from firedancer_tpu.pack import scheduler_native as sn_pack
    from firedancer_tpu.runtime import verify_native as vfn

    _require_ab_pairs(pairs, "verify sweep-lane A/B")
    if not vfn.available():
        print("# native verify client unavailable: no A/B to run",
              file=sys.stderr)
        return {"verify_ab_unavailable": True}
    pack_avail = sn_pack.available()
    ons, offs = [], []
    _host_pipeline_warm_window()
    for i in range(pairs):
        print(f"# verify A/B pair {i + 1}/{pairs}", file=sys.stderr)
        order = (True, False) if i % 2 == 0 else (False, True)
        for on in order:
            (ons if on else offs).append(_host_pipeline_measure(
                native_pack=pack_avail, native_verify=on))

    def _stage_key(rows, key):
        return [{"v": o["pipeline_host_stage_us_per_txn"].get(key)}
                for o in rows]

    out = {
        "pairs": pairs,
        "txn_per_s": ab_summary(ons, offs, "pipeline_host_txn_per_s"),
        "verify_us_per_txn": ab_summary(
            _stage_key(ons, "verify0"), _stage_key(offs, "verify0"), "v"),
        "pipeline_host_txn_per_s": round(_median(
            [o["pipeline_host_txn_per_s"] for o in ons]), 1),
        "stage_us_per_txn_on": [o["pipeline_host_stage_us_per_txn"]
                                for o in ons],
        "stage_us_per_txn_off": [o["pipeline_host_stage_us_per_txn"]
                                 for o in offs],
        "verify_mode_on": ons[-1].get("pipeline_host_native_verify"),
        "verify_mode_off": offs[-1].get("pipeline_host_native_verify"),
        "native_exec": ons[-1].get("pipeline_host_native_exec"),
        "native_ring": ons[-1].get("pipeline_host_native_ring"),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    path = out_path or os.environ.get("FDTPU_BENCH_VERIFY_AB_PATH",
                                      "BENCH_r11_verify_ab.json")
    try:
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"# verify A/B artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# verify A/B artifact write failed: {e}", file=sys.stderr)
    return out


def run_bank_ab(pairs: int = 3, out_path: str | None = None) -> dict:
    """The ISSUE 16 acceptance artifact: interleaved same-box A/B of the
    native bank sweep lane — per pair, one all-native window and one
    window with ONLY the bank sweep client off (per-frag Python commits
    on the same rings and the same exec session), per-stage us/txn
    tables for both, per-pair deltas and median-of-pairs, plus the
    commit-p99 A/B and the per-run autotune snapshot.  Writes
    BENCH_r12_bank_ab.json (or FDTPU_BENCH_BANK_AB_PATH)."""
    from firedancer_tpu.pack import scheduler_native as sn_pack
    from firedancer_tpu.runtime import bank_native as bkn

    _require_ab_pairs(pairs, "bank sweep-lane A/B")
    if not bkn.available():
        print("# native bank client unavailable: no A/B to run",
              file=sys.stderr)
        return {"bank_ab_unavailable": True}
    pack_avail = sn_pack.available()
    ons, offs = [], []
    # the endgame topology, applied to BOTH windows: 2 banks (the
    # cooperative scheduler runs one thread, so extra banks only add
    # idle sweep crossings) and warmup past the 1024-dest account set
    # (first touches stash on the sweep lane and fault funk loads on
    # the python lane — warmup either way, steady state is the claim)
    env_prev = {k: os.environ.get(k)
                for k in ("FDTPU_BENCH_PIPELINE_BANKS",
                          "FDTPU_BENCH_PIPELINE_WARM")}
    os.environ.setdefault("FDTPU_BENCH_PIPELINE_BANKS", "2")
    os.environ.setdefault("FDTPU_BENCH_PIPELINE_WARM", "1536")
    try:
        _host_pipeline_warm_window()
        for i in range(pairs):
            print(f"# bank A/B pair {i + 1}/{pairs}", file=sys.stderr)
            order = (True, False) if i % 2 == 0 else (False, True)
            for on in order:
                # BOTH windows run the ISSUE 16 endgame topology (fused
                # poh+shred crash domain) so the pair isolates the bank
                # lane alone; the fused-vs-unfused delta is the
                # byte-equal test's concern, not this artifact's
                (ons if on else offs).append(_host_pipeline_measure(
                    native_pack=pack_avail, native_bank=on, fused=True))
        n_bank_cfg = int(os.environ["FDTPU_BENCH_PIPELINE_BANKS"])
        warm_cfg = int(os.environ["FDTPU_BENCH_PIPELINE_WARM"])
    finally:
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def _stage_key(rows, key):
        return [{"v": o["pipeline_host_stage_us_per_txn"].get(key)}
                for o in rows]

    out = {
        "pairs": pairs,
        "fused_poh_shred": True,
        "n_bank": n_bank_cfg,
        "warm_txns": warm_cfg,
        "txn_per_s": ab_summary(ons, offs, "pipeline_host_txn_per_s"),
        "bank_us_per_txn": ab_summary(
            _stage_key(ons, "bank"), _stage_key(offs, "bank"), "v"),
        "commit_p99_ms": ab_summary(
            ons, offs, "pipeline_host_commit_p99_ms"),
        "pipeline_host_txn_per_s": round(_median(
            [o["pipeline_host_txn_per_s"] for o in ons]), 1),
        "stage_us_per_txn_on": [o["pipeline_host_stage_us_per_txn"]
                                for o in ons],
        "stage_us_per_txn_off": [o["pipeline_host_stage_us_per_txn"]
                                 for o in offs],
        "bank_mode_on": ons[-1].get("pipeline_host_native_bank"),
        "bank_mode_off": offs[-1].get("pipeline_host_native_bank"),
        "native_exec": ons[-1].get("pipeline_host_native_exec"),
        "native_ring": ons[-1].get("pipeline_host_native_ring"),
        "native_verify": ons[-1].get("pipeline_host_native_verify"),
        "native_shred": ons[-1].get("pipeline_host_native_shred"),
        "autotune": ons[-1].get("autotune"),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    # the acceptance gates, evaluated in-artifact so the CI smoke (and
    # the next round's reader) need no out-of-band thresholds
    bank_on = out["bank_us_per_txn"]["on_median"]
    rate_on = out["txn_per_s"]["on_median"]
    out["accept_bank_us_per_txn_le_8"] = (
        bank_on is not None and bank_on <= 8.0)
    out["accept_pipeline_txn_per_s_ge_24k"] = (
        rate_on is not None and rate_on >= 24_000.0)
    path = out_path or os.environ.get("FDTPU_BENCH_BANK_AB_PATH",
                                      "BENCH_r12_bank_ab.json")
    try:
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"# bank A/B artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# bank A/B artifact write failed: {e}", file=sys.stderr)
    return out


def run_funk_ab(pairs: int = 3, out_path: str | None = None) -> dict:
    """The ISSUE 19 acceptance artifact: interleaved same-box A/B of the
    native shm storage plane — per pair, one window with the whole stack
    native (committed records land in the shm map INSIDE the bank sweep
    crossing; the drain is result-log accounting only) and one window
    with ONLY the funk store swapped to the dict-backed lane (the sweep
    still commits in C, but `BankStage._drain_native` re-applies every
    committed record host-side, per record).  Per-stage us/txn tables
    for both, the commit-p99 A/B, per-pair deltas and median-of-pairs.
    Writes BENCH_r14_funk_ab.json (or FDTPU_BENCH_FUNK_AB_PATH)."""
    from firedancer_tpu.funk import funk_native as fkn
    from firedancer_tpu.pack import scheduler_native as sn_pack
    from firedancer_tpu.runtime import bank_native as bkn

    _require_ab_pairs(pairs, "funk storage-plane A/B")
    if not (fkn.available() and bkn.available()):
        print("# native funk/bank unavailable: no A/B to run",
              file=sys.stderr)
        return {"funk_ab_unavailable": True}
    pack_avail = sn_pack.available()
    ons, offs = [], []
    # the round-12 endgame topology in BOTH windows (2 banks, fused
    # poh+shred, warmup past the dest-account set) so the pair isolates
    # the storage plane alone
    env_prev = {k: os.environ.get(k)
                for k in ("FDTPU_BENCH_PIPELINE_BANKS",
                          "FDTPU_BENCH_PIPELINE_WARM")}
    os.environ.setdefault("FDTPU_BENCH_PIPELINE_BANKS", "2")
    os.environ.setdefault("FDTPU_BENCH_PIPELINE_WARM", "1536")
    try:
        _host_pipeline_warm_window()
        for i in range(pairs):
            print(f"# funk A/B pair {i + 1}/{pairs}", file=sys.stderr)
            order = (True, False) if i % 2 == 0 else (False, True)
            for on in order:
                (ons if on else offs).append(_host_pipeline_measure(
                    native_pack=pack_avail, native_bank=True,
                    native_funk=on, fused=True))
        n_bank_cfg = int(os.environ["FDTPU_BENCH_PIPELINE_BANKS"])
        warm_cfg = int(os.environ["FDTPU_BENCH_PIPELINE_WARM"])
    finally:
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def _stage_key(rows, key):
        return [{"v": o["pipeline_host_stage_us_per_txn"].get(key)}
                for o in rows]

    out = {
        "pairs": pairs,
        "fused_poh_shred": True,
        "n_bank": n_bank_cfg,
        "warm_txns": warm_cfg,
        "txn_per_s": ab_summary(ons, offs, "pipeline_host_txn_per_s"),
        "bank_us_per_txn": ab_summary(
            _stage_key(ons, "bank"), _stage_key(offs, "bank"), "v"),
        "commit_p99_ms": ab_summary(
            ons, offs, "pipeline_host_commit_p99_ms"),
        "pipeline_host_txn_per_s": round(_median(
            [o["pipeline_host_txn_per_s"] for o in ons]), 1),
        "stage_us_per_txn_on": [o["pipeline_host_stage_us_per_txn"]
                                for o in ons],
        "stage_us_per_txn_off": [o["pipeline_host_stage_us_per_txn"]
                                 for o in offs],
        "funk_mode_on": ons[-1].get("pipeline_host_native_funk"),
        "funk_mode_off": offs[-1].get("pipeline_host_native_funk"),
        "bank_mode": ons[-1].get("pipeline_host_native_bank"),
        "native_exec": ons[-1].get("pipeline_host_native_exec"),
        "native_ring": ons[-1].get("pipeline_host_native_ring"),
        "native_verify": ons[-1].get("pipeline_host_native_verify"),
        "native_shred": ons[-1].get("pipeline_host_native_shred"),
        "autotune": ons[-1].get("autotune"),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    # the ISSUE 19 gates, evaluated in-artifact: bank stage <= 8 us/txn
    # with the store native, the pipeline at/over 30K txn/s, and commit
    # p99 no worse than round 12's 17.3 ms median
    bank_on = out["bank_us_per_txn"]["on_median"]
    rate_on = out["txn_per_s"]["on_median"]
    p99_on = out["commit_p99_ms"]["on_median"]
    out["accept_bank_us_per_txn_le_8"] = (
        bank_on is not None and bank_on <= 8.0)
    out["accept_pipeline_txn_per_s_ge_30k"] = (
        rate_on is not None and rate_on >= 30_000.0)
    out["accept_commit_p99_ms_le_17_3"] = (
        p99_on is not None and 0 <= p99_on <= 17.3)
    path = out_path or os.environ.get("FDTPU_BENCH_FUNK_AB_PATH",
                                      "BENCH_r14_funk_ab.json")
    try:
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"# funk A/B artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# funk A/B artifact write failed: {e}", file=sys.stderr)
    return out


def run_metrics_ab(pairs: int = 3, out_path: str | None = None) -> dict:
    """The ISSUE 20 acceptance artifact: interleaved same-box A/B of the
    in-crossing metrics plane — per pair, one window with the native
    observability plane armed (every sweep client stamping phase
    histograms, latency observes and decimated flight events from
    INSIDE the crossing) and one with FDTPU_NATIVE_METRICS=0 (the exact
    same native pipeline, zero instrumentation).  The claim under test:
    in-crossing instrumentation costs <2% pipeline txn/s.  Writes
    BENCH_r15_metrics_ab.json (or FDTPU_BENCH_METRICS_AB_PATH)."""
    from firedancer_tpu.pack import scheduler_native as sn_pack
    from firedancer_tpu.runtime import bank_native as bkn

    _require_ab_pairs(pairs, "metrics-plane A/B")
    if not bkn.available():
        print("# native bank client unavailable: no A/B to run",
              file=sys.stderr)
        return {"metrics_ab_unavailable": True}
    pack_avail = sn_pack.available()
    ons, offs = [], []
    # the round-14 endgame topology in BOTH windows; the metrics switch
    # must be held across the WHOLE measure window (not just the build):
    # plane arming is lazy, at each stage's first sweep
    env_prev = {k: os.environ.get(k)
                for k in ("FDTPU_BENCH_PIPELINE_BANKS",
                          "FDTPU_BENCH_PIPELINE_WARM",
                          "FDTPU_NATIVE_METRICS")}
    os.environ.setdefault("FDTPU_BENCH_PIPELINE_BANKS", "2")
    os.environ.setdefault("FDTPU_BENCH_PIPELINE_WARM", "1536")
    try:
        _host_pipeline_warm_window()
        for i in range(pairs):
            print(f"# metrics A/B pair {i + 1}/{pairs}", file=sys.stderr)
            order = (True, False) if i % 2 == 0 else (False, True)
            for on in order:
                os.environ["FDTPU_NATIVE_METRICS"] = "1" if on else "0"
                (ons if on else offs).append(_host_pipeline_measure(
                    native_pack=pack_avail, native_bank=True, fused=True))
        n_bank_cfg = int(os.environ["FDTPU_BENCH_PIPELINE_BANKS"])
        warm_cfg = int(os.environ["FDTPU_BENCH_PIPELINE_WARM"])
    finally:
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def _stage_key(rows, key):
        return [{"v": o["pipeline_host_stage_us_per_txn"].get(key)}
                for o in rows]

    out = {
        "pairs": pairs,
        "fused_poh_shred": True,
        "n_bank": n_bank_cfg,
        "warm_txns": warm_cfg,
        "txn_per_s": ab_summary(ons, offs, "pipeline_host_txn_per_s"),
        "bank_us_per_txn": ab_summary(
            _stage_key(ons, "bank"), _stage_key(offs, "bank"), "v"),
        "commit_p99_ms": ab_summary(
            ons, offs, "pipeline_host_commit_p99_ms"),
        "pipeline_host_txn_per_s": round(_median(
            [o["pipeline_host_txn_per_s"] for o in ons]), 1),
        "stage_us_per_txn_on": [o["pipeline_host_stage_us_per_txn"]
                                for o in ons],
        "stage_us_per_txn_off": [o["pipeline_host_stage_us_per_txn"]
                                 for o in offs],
        # the sweep-phase decomposition from the instrumented windows —
        # the bank 13.8 us/txn breakdown ROADMAP item 1 asks for
        "sweep_phases_on": [o.get("stage_latency_ns", {}) for o in ons],
        "bank_mode": ons[-1].get("pipeline_host_native_bank"),
        "native_exec": ons[-1].get("pipeline_host_native_exec"),
        "native_ring": ons[-1].get("pipeline_host_native_ring"),
        "native_verify": ons[-1].get("pipeline_host_native_verify"),
        "native_shred": ons[-1].get("pipeline_host_native_shred"),
        "autotune": ons[-1].get("autotune"),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    # the ISSUE 20 gate, evaluated in-artifact: the instrumented window
    # keeps >=98% of the uninstrumented window's txn/s (median of pairs)
    rate_on = out["txn_per_s"]["on_median"]
    rate_off = out["txn_per_s"]["off_median"]
    overhead_pct = None
    if rate_on is not None and rate_off:
        overhead_pct = round(100.0 * (rate_off - rate_on) / rate_off, 2)
    out["overhead_pct"] = overhead_pct
    out["accept_overhead_lt_2pct"] = (
        overhead_pct is not None and overhead_pct < 2.0)
    path = out_path or os.environ.get("FDTPU_BENCH_METRICS_AB_PATH",
                                      "BENCH_r15_metrics_ab.json")
    try:
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)
        print(f"# metrics A/B artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# metrics A/B artifact write failed: {e}", file=sys.stderr)
    return out


def _host_pipeline_measure(*, native_pack: bool,
                           native_ring: bool | None = None,
                           native_shred: bool | None = None,
                           native_verify: bool | None = None,
                           native_bank: bool | None = None,
                           native_funk: bool | None = None,
                           fused: bool = False) -> dict:
    from firedancer_tpu.models.leader import build_leader_pipeline
    from firedancer_tpu.runtime.bank import default_bank_ctx
    from firedancer_tpu.runtime.benchg import gen_transfer_pool

    n_txn = int(os.environ.get("FDTPU_BENCH_PIPELINE_TXNS", "8192"))
    # bank fan-out is a topology knob, not a fixed fact of the bench:
    # the sweep lane amortizes one FFI dispatch per bank per iteration,
    # so fewer/busier banks beat many mostly-idle ones on one box
    n_bank = int(os.environ.get("FDTPU_BENCH_PIPELINE_BANKS", "4"))
    n_payers = 64  # schedulable parallelism (fd_benchg rotates a
    #                bounded funded account set the same way)
    t0 = time.time()
    # the ring, shred, bank AND funk lanes are chosen at endpoint/stage/
    # store CONSTRUCTION (shm.make_*, ShredStage.__init__,
    # BankStage._arm_native, make_funk inside default_bank_ctx): the env
    # switches only need to hold while the ctx + pipeline build
    env_prev = {k: os.environ.get(k)
                for k in ("FDTPU_NATIVE_RING", "FDTPU_NATIVE_SHRED",
                          "FDTPU_NATIVE_VERIFY", "FDTPU_NATIVE_BANK",
                          "FDTPU_NATIVE_FUNK")}
    if native_ring is not None:
        os.environ["FDTPU_NATIVE_RING"] = "1" if native_ring else "0"
    if native_shred is not None:
        os.environ["FDTPU_NATIVE_SHRED"] = "1" if native_shred else "0"
    if native_verify is not None:
        os.environ["FDTPU_NATIVE_VERIFY"] = "1" if native_verify else "0"
    if native_bank is not None:
        os.environ["FDTPU_NATIVE_BANK"] = "1" if native_bank else "0"
    if native_funk is not None:
        os.environ["FDTPU_NATIVE_FUNK"] = "1" if native_funk else "0"
    try:
        ctx = default_bank_ctx(n_payers=n_payers)
        pipe = build_leader_pipeline(
            n_verify=1,
            n_bank=n_bank,
            pool_size=64,  # placeholder; the real pool replaces it below
            gen_limit=n_txn,
            batch=512,
            max_msg_len=256,
            batch_deadline_s=0.005,
            verify_precomputed=True,
            bank_ctx=ctx,
            native_pack=native_pack,
            keep_sets=False,  # frees the shred stage for the sweep lane
            fuse_poh_shred=fused,
        )
    finally:
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    ring_on = type(pipe.pack.ins[0]).__name__ == "NativeConsumer"
    shred_mode = ("sweep" if pipe.shred._sweep_client is not None
                  else ("batch" if pipe.shred.native_shred else "python"))
    verify_mode = ("sweep" if pipe.verifies[0]._sweep_client is not None
                   else "python")
    bank_mode = ("sweep" if pipe.banks[0]._sweep_client is not None
                 else "python")
    funk_mode = "native" if hasattr(ctx.funk, "txn_diff") else "python"
    pipe.benchg.pool = gen_transfer_pool(n_txn, n_payers=n_payers,
                                         n_dests=1024)
    # genesis-style destination preload: the pool rotates 1024 FIXED
    # destinations (benchg derives them from the seed), so fund them
    # and push them into the native session overlay — a validator
    # enters a slot with its accounts DB resident, and without this
    # every first touch stashes a microblock to the resume lane, so the
    # "steady state" window would partly measure cold-start punts.
    # Applied identically in every window, so A/B deltas are unaffected.
    import hashlib as _hl
    dests = [_hl.sha256(b"benchg" + b"to%d" % d).digest()
             for d in range(1024)]
    for a in dests:
        ctx.fund(a, 1)
    ctx.preload(dests)
    print(f"# host pipeline: pool of {n_txn} signed in {time.time()-t0:.1f}s"
          f" (native_pack={native_pack}, native_ring={ring_on},"
          f" shred={shred_mode}, verify={verify_mode}, bank={bank_mode},"
          f" funk={funk_mode}, fused={fused})",
          file=sys.stderr)

    def executed_cnt() -> int:
        return sum(b.metrics.get("txn_exec") for b in pipe.banks)

    try:
        # warmup: the first FEC sets trigger the reedsol/bmtree compiles;
        # steady-state throughput is the meaningful figure, so compile
        # cost stays out of the timed window (a real validator compiles
        # once per boot)
        # default 512 covers the compiles; the bank A/B raises it past
        # the dest-account set so the timed window is steady-state for
        # BOTH lanes (first touches stash on the sweep lane and fault
        # funk loads on the python lane — warmup cost either way)
        warm = int(os.environ.get("FDTPU_BENCH_PIPELINE_WARM", "512"))
        pipe.run(until_txns=warm, max_iters=500_000, finish=False)
        warm_exec = executed_cnt()
        for b in pipe.banks:
            b.commit_latencies_ns.clear()
        # measure to EXECUTION completion (pack intake runs ahead of the
        # banks under burst draining; stopping at intake would time only
        # the front half of the pipe)
        t0 = time.time()
        it = 0
        target = n_txn - warm - 16
        last_progress_t = t0
        last_cnt = warm_exec
        # per-stage breakdown, SAMPLED (every 8th sweep is clocked per
        # stage, scaled back up) so the instrument costs ~1% of the run
        # instead of two clock reads per stage per sweep
        stage_s = {s.name: 0.0 for s in pipe.stages}
        stage_s["pack.after_credit"] = 0.0
        # ring time spent inside the explicit after_credit call (native
        # pack publishes its microblocks there): tracked apart so the
        # ring split stays a SUBSET of the same lane it is printed under
        ring_ac_s = 0.0
        progress_snap = None
        sample_every = 8
        pc = time.perf_counter
        while executed_cnt() - warm_exec < target and it < 2_000_000:
            if it % sample_every == 0:
                # sampled sweeps also run the ring-cost instrument
                # (stage.ring_clock): poll/drain + publish time accumulate
                # per stage, scaled alongside the stage times below
                for s in pipe.stages:
                    s.ring_clock = True
                    t1 = pc()
                    s.run_once()
                    stage_s[s.name] += pc() - t1
                    s.ring_clock = False
                pipe.pack.ring_clock = True
                r0 = pipe.pack.ring_poll_s + pipe.pack.ring_publish_s
                t1 = pc()
                pipe.pack.after_credit()
                stage_s["pack.after_credit"] += pc() - t1
                ring_ac_s += (pipe.pack.ring_poll_s
                              + pipe.pack.ring_publish_s) - r0
                pipe.pack.ring_clock = False
            else:
                for s in pipe.stages:
                    s.run_once()
                pipe.pack.after_credit()
            it += 1
            if it % 512 == 0:
                cur = executed_cnt()
                if cur > last_cnt:
                    last_cnt = cur
                    last_progress_t = time.time()
                    # snapshot the sampled instruments at every progress
                    # mark: if the run later stalls, the dead-spin tail
                    # (sampled idle sweeps) must not pollute the
                    # per-stage table — the stall made round-9 artifacts
                    # read 1300 us/txn for a stage while throughput was
                    # fine
                    progress_snap = (
                        dict(stage_s),
                        {s.name: (s.ring_poll_s, s.ring_publish_s)
                         for s in pipe.stages},
                        ring_ac_s,
                    )
                elif time.time() - last_progress_t > 5:
                    break  # stalled: stop rather than time a dead spin
        executed = executed_cnt() - warm_exec
        if executed < target:
            # a partial run must be VISIBLE, and the dead tail must not
            # deflate the rate OR inflate the sampled per-stage times:
            # time (and count) only to the last observed progress
            print(f"# host pipeline INCOMPLETE: {executed}/{target} "
                  f"executed (drops/stall)", file=sys.stderr)
            elapsed = max(last_progress_t - t0, 1e-9)
            if progress_snap is not None:
                stage_s, ring_snap, ring_ac_s = progress_snap
                for s in pipe.stages:
                    s.ring_poll_s, s.ring_publish_s = ring_snap[s.name]
        else:
            elapsed = time.time() - t0
        lats = sorted(
            lat for b in pipe.banks for lat in b.commit_latencies_ns
        )
        p99_ms = (
            lats[min(int(len(lats) * 0.99), len(lats) - 1)] / 1e6
            if lats else -1.0
        )
        rate = executed / elapsed if elapsed > 0 else 0.0
        print(
            f"# host pipeline: {executed} txns in {elapsed:.2f}s "
            f"({rate:.0f} txn/s, no device), commit p99 {p99_ms:.1f}ms",
            file=sys.stderr,
        )
        # scale the sampled stage times back to the whole run; merge the
        # bank stages into one lane (they share the executor)
        breakdown_us = {}
        ring_us = {}
        ring_total_us = 0.0
        if executed > 0:
            scale = sample_every * 1e6 / executed
            for name, sec in stage_s.items():
                lane = "bank" if name.startswith("bank") else name
                breakdown_us[lane] = round(
                    breakdown_us.get(lane, 0.0) + sec * scale, 1
                )
            # the ring split: poll/drain + publish time per stage, a
            # SUBSET of the stage lane above — (stage - ring) is compute
            for s in pipe.stages:
                sec = s.ring_poll_s + s.ring_publish_s
                if s is pipe.pack:
                    # publishes from the explicit after_credit call were
                    # clocked into the same counters; re-home them so
                    # each ring figure subsets its own printed lane
                    sec -= ring_ac_s
                lane = "bank" if s.name.startswith("bank") else s.name
                ring_us[lane] = round(ring_us.get(lane, 0.0) + sec * scale, 1)
            ring_us["pack.after_credit"] = round(ring_ac_s * scale, 1)
            ring_total_us = round(sum(ring_us.values()), 1)
            for lane, us in sorted(breakdown_us.items(), key=lambda kv: -kv[1]):
                print(f"#   stage {lane:20s} {us:8.1f} us/txn"
                      f"   (ring {ring_us.get(lane, 0.0):6.1f})",
                      file=sys.stderr)
            print(f"#   ring poll+publish total {ring_total_us:8.1f} us/txn",
                  file=sys.stderr)
        from firedancer_tpu.flamenco import exec_native

        # the ISSUE 9 criterion watches pack + dedup COMBINED us/txn
        # (the fused lane has no dedup stage at all)
        pack_dedup_us = round(
            breakdown_us.get("pack", 0.0)
            + breakdown_us.get("pack.after_credit", 0.0)
            + breakdown_us.get("dedup", 0.0), 1)
        out = {
            "pipeline_host_txn_per_s": round(rate, 1),
            "pipeline_host_commit_p99_ms": round(p99_ms, 2),
            "pipeline_host_txn_executed": executed,
            "pipeline_host_stage_us_per_txn": breakdown_us,
            "pipeline_host_pack_dedup_us_per_txn": pack_dedup_us,
            "pipeline_host_ring_us_per_txn": ring_total_us,
            "pipeline_host_ring_us_per_stage": ring_us,
            "pipeline_host_native_ring": ring_on,
            "pipeline_host_native_exec": exec_native.available(),
            "pipeline_host_native_shred": shred_mode,
            "pipeline_host_native_verify": verify_mode,
            "pipeline_host_native_bank": bank_mode,
            "pipeline_host_native_funk": funk_mode,
            "pipeline_host_fused_poh_shred": fused,
        }
        out.update(_scrape_stage_latencies(pipe))
        # the occupancy-driven link tuner's snapshot for this run:
        # pure function of the stages' own out_occupancy samples, so
        # the NEXT topology build can consume it straight from the
        # artifact (runtime/autotune.py — nothing resizes live rings)
        from firedancer_tpu.runtime.autotune import recommend_topology

        tuned = recommend_topology(pipe.stages)
        out["autotune"] = {k: {str(i): t for i, t in v.items()}
                           for k, v in tuned.items() if v}
        if executed < target:
            out["pipeline_host_incomplete"] = True
        return out
    finally:
        pipe.close()


def _verify_stage_loop_rate(n: int = 20_000, batch: int = 512) -> float:
    """The verify STAGE machinery alone (frag in -> parse -> dedup ->
    batch assembly -> emit, precomputed mask): the per-stage host number
    scripts/perf_verify_host.py measures, recorded in the artifact so
    the machinery claim is checkable."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perf_verify_host",
        os.path.join(os.path.dirname(__file__), "scripts",
                     "perf_verify_host.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.bench_stage_loop(n, batch)


# -- the kernel ladder (ISSUE 13) ---------------------------------------------

KERNEL_ARTIFACT = os.environ.get(
    "FDTPU_KERNEL_LADDER_PATH",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "KERNEL_r01.json"),
)


def _kernel_ladder_stage_probe() -> dict:
    """Fill-rate / occupancy / autotuner evidence from the verify STAGE
    machinery (precomputed mask, no device): feed a real signed-txn
    stream through intake + batching and read the stage's own schema
    histograms — the same numbers the live metrics plane records."""
    import numpy as _np

    from firedancer_tpu.runtime import verify_tune as vt
    from firedancer_tpu.runtime.benchg import gen_transfer_pool
    from firedancer_tpu.runtime.verify import VerifyStage

    st = VerifyStage("kprobe", ins=[], outs=[], batch=64, max_msg_len=256,
                     batch_deadline_s=0.0005, precomputed_ok=True,
                     native_client=False)
    pool = gen_transfer_pool(512, n_payers=32, n_dests=64)
    meta = _np.zeros(7, dtype=_np.uint64)
    for i, p in enumerate(pool):
        meta[5] = 1 + i
        st.after_frag(0, meta, p)
        st.before_credit()
        st.after_credit()
    st.flush()
    m = st.metrics
    batches = m.get("batches")
    fill_rate = (m.get("batch_elems") / (batches * st.batch)
                 if batches else 0.0)
    rec = vt.recommend_for_stage(st)
    return {
        "batches": batches,
        "batch": st.batch,
        "fill_rate": round(fill_rate, 3),
        "occupancy_p50": round(m.quantile("inflight_occupancy", 0.5), 2),
        "occupancy_p99": round(m.quantile("inflight_occupancy", 0.99), 2),
        "msg_len_p99": round(m.quantile("msg_len", 0.99), 1),
        "autotune_recommendation": rec.as_dict(),
    }


def run_kernel_ladder(out_path: str | None = None, *,
                      cpu: bool = False) -> dict:
    """bench.py --kernel-ladder [--cpu]: the verify-kernel capture, on
    the chip unless --cpu asks for the CPU (KERNEL_r01.json).  Per
    ladder lane (fused/split[/baseline]): compile_s, dispatches per
    batch PROVEN by counting live compiled entries, and steady-state
    elems/s at each async in-flight window; plus the stage-machinery
    section (batch fill rate, window occupancy, the autotuner's
    recommendation from the same histograms the metrics plane records).
    Knobs: FDTPU_KERNEL_BATCH / _ROUNDS / _LANES / _WINDOWS."""
    from firedancer_tpu.utils.platform import select_device

    select_device(cpu)
    import jax
    import jax.numpy as jnp

    from firedancer_tpu.ops import sigverify as sv
    import __graft_entry__ as ge

    dev = jax.devices()[0]
    batch = int(os.environ.get("FDTPU_KERNEL_BATCH",
                               "256" if cpu else str(BATCH)))
    rounds = int(os.environ.get("FDTPU_KERNEL_ROUNDS",
                                "4" if cpu else str(STEADY_ROUNDS)))
    lanes = [k.strip() for k in os.environ.get(
        "FDTPU_KERNEL_LANES", "fused,split").split(",") if k.strip()]
    wins = tuple(int(x) for x in os.environ.get(
        "FDTPU_KERNEL_WINDOWS", "3,8").split(","))
    print(f"# kernel ladder: {dev.platform}:{dev.device_kind} batch={batch}"
          f" rounds={rounds} lanes={lanes} windows={wins}", file=sys.stderr)
    msg, msg_len, sig, pk = ge._example_batch(batch)
    args = tuple(jax.device_put(jnp.asarray(a), dev)
                 for a in (msg, msg_len, sig, pk))
    art = {
        "metric": "verify_kernel_ladder",
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "batch": batch,
        "max_msg_len": MAX_MSG_LEN,
        "rounds": rounds,
        "rungs": [],
    }

    for kernel in lanes:
        sv.kernel_clear_caches(kernel)

        def step():
            mask, n_ok = sv.verify_dispatch(kernel, *args, batch,
                                            max_msg_len=MAX_MSG_LEN)
            return (n_ok if n_ok is not None
                    else jnp.sum(mask.astype(jnp.int32)))

        t0 = time.time()
        n = int(np.asarray(step()))
        compile_s = time.time() - t0
        assert n == batch, f"{kernel}: honest signatures must all verify"
        entries = sv.kernel_compiled_entries(kernel)
        want = sv.kernel_dispatch_count(kernel)
        rung = {
            "kernel": kernel,
            "compile_s": round(compile_s, 2),
            "dispatches_per_batch": want,
            "compiled_entries": entries,
            # the acceptance check: one batch shape ran, so live entries
            # == modules entered per dispatch (1 for fused, 4 for split)
            "single_dispatch_ok": entries == want,
            "windows": {},
        }
        for w in wins:
            outs = []
            occ = occ_n = 0
            t0 = time.time()
            for _ in range(rounds):
                outs.append(step())
                occ += len(outs)
                occ_n += 1
                if len(outs) >= w:
                    int(np.asarray(outs.pop(0)))
            for o in outs:
                int(np.asarray(o))
            el = time.time() - t0
            rung["windows"][str(w)] = {
                "elems_per_s": round(batch * rounds / el, 1),
                "inflight_mean": round(occ / occ_n, 2),
            }
        art["rungs"].append(rung)
        print(f"# ladder {kernel}: compile {compile_s:.1f}s, "
              f"{want} dispatch(es)/batch (entries={entries}), "
              f"{rung['windows']}", file=sys.stderr)

    try:
        art["stage"] = _kernel_ladder_stage_probe()
    except Exception as e:  # the device rungs must survive a probe bug
        print(f"# stage probe failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        art["stage_error"] = f"{type(e).__name__}"
    art["captured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    path = out_path or KERNEL_ARTIFACT
    try:
        with open(path, "w") as fh:
            json.dump(art, fh, indent=1)
        print(f"# kernel ladder artifact -> {path}", file=sys.stderr)
    except OSError as e:
        print(f"# kernel ladder artifact write failed: {e}", file=sys.stderr)
    return art


def run_pipeline_bench(platform: str) -> dict:
    """End-to-end leader-pipeline throughput: gen -> verify(TPU) -> dedup ->
    pack -> bank -> poh -> shred -> store, measured at the bank commit
    point (tsorig-stamped at benchg, fd_tango_base.h:48-60)."""
    from firedancer_tpu.models.leader import build_leader_pipeline

    small = platform == "cpu"
    n_txn = 256 if small else 2048
    batch = 64 if small else 1024  # BASELINE config 2's batch on the chip
    t0 = time.time()
    pipe = build_leader_pipeline(
        n_verify=1,
        n_bank=2,
        pool_size=n_txn,
        gen_limit=n_txn,
        batch=batch,
        max_msg_len=256,
        batch_deadline_s=0.005,
    )
    print(f"# pipeline: pool of {n_txn} signed in {time.time()-t0:.1f}s",
          file=sys.stderr)
    try:
        # warm the verify kernel shape outside the timed window (compile
        # time is reported by the kernel bench, not the pipeline number)
        import jax.numpy as jnp

        from firedancer_tpu.ops import sigverify as sv
        import __graft_entry__ as ge

        wm, wl, ws, wp = ge._example_batch(batch)
        wm2 = np.zeros((256, batch), dtype=np.uint8)  # match VerifyStage's wire dtype
        wm2[: wm.shape[0]] = wm
        t0 = time.time()
        # warm the STAGE's default program (the fused single-dispatch
        # lane) at its exact shape, so compile cost stays out of the
        # timed pipeline window
        sv.ed25519_verify_batch_fused(
            jnp.asarray(wm2), jnp.asarray(wl), jnp.asarray(ws),
            jnp.asarray(wp), jnp.int32(batch), max_msg_len=256,
        )[0].block_until_ready()
        print(f"# pipeline: verify kernel warm in {time.time()-t0:.1f}s",
              file=sys.stderr)
        t0 = time.time()
        pipe.run(until_txns=n_txn, max_iters=2_000_000)
        elapsed = time.time() - t0
        executed = sum(
            b.metrics.get("txn_exec") for b in pipe.banks
        )
        lats = sorted(
            lat for b in pipe.banks for lat in b.commit_latencies_ns
        )
        p99_ms = (
            lats[min(int(len(lats) * 0.99), len(lats) - 1)] / 1e6 if lats else -1.0
        )
        rate = executed / elapsed if elapsed > 0 else 0.0
        print(
            f"# pipeline: {executed} txns committed in {elapsed:.2f}s "
            f"({rate:.0f} txn/s), commit p99 {p99_ms:.1f}ms, "
            f"{pipe.shred.metrics.get('fec_sets')} FEC sets emitted",
            file=sys.stderr,
        )
        out = {
            "pipeline_txn_per_s": round(rate, 1),
            "pipeline_vs_baseline": round(rate / PIPELINE_BASELINE_TXN_PER_S, 5),
            "pipeline_commit_p99_ms": round(p99_ms, 2),
            "pipeline_txn_executed": executed,
        }
        out.update(_scrape_stage_latencies(pipe))
        return out
    finally:
        pipe.close()


def _run_child(extra_args: list[str], timeout_s: int) -> str | None:
    """Re-exec this script with `extra_args` (one fresh process per
    serving-plane rung); returns the JSON line printed by the child, or
    None on any failure.  Child stderr is streamed through so the
    artifact keeps the diagnostic trail.  The child runs in its own
    session and the whole process GROUP is killed on timeout, so no
    grandchild can keep the pipes — or the chip — after the rung."""
    import signal

    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), *extra_args],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        start_new_session=True,
    )
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            stdout, stderr = proc.communicate(timeout=30)
        except subprocess.TimeoutExpired:
            stdout, stderr = "", ""
        for line in (stderr or "").splitlines()[-20:]:
            print(line, file=sys.stderr)
        print(f"# child {extra_args} timed out after {timeout_s}s", file=sys.stderr)
        return None
    for line in stderr.splitlines():
        print(line, file=sys.stderr)
    if proc.returncode != 0:
        print(f"# child {extra_args} rc={proc.returncode}", file=sys.stderr)
        return None
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                json.loads(line)
                return line
            except json.JSONDecodeError:
                continue
    return None


# -- multichip serve: the sharded serving plane at 1/2/4/8 devices ------------

MULTICHIP_ARTIFACT = os.environ.get(
    "FDTPU_MULTICHIP_PATH",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "MULTICHIP_r06.json"),
)
SERVE_DEVICE_LADDER = (1, 2, 4, 8)
SERVE_CHILD_TIMEOUT_S = int(os.environ.get("FDTPU_SERVE_CHILD_TIMEOUT", "1800"))
SERVE_BATCH_PER_SHARD = int(os.environ.get("FDTPU_SERVE_BATCH", "32"))
SERVE_TXNS = int(os.environ.get("FDTPU_SERVE_TXNS", "192"))
SERVE_STEP_ROUNDS = int(os.environ.get("FDTPU_SERVE_ROUNDS", "6"))
WARM_COLD_START_BUDGET_S = 10.0


def serve_child(n_devices: int, *, measure_boot: bool = False,
                cpu: bool = False) -> None:
    """One mesh size, one fresh process: compile (through the persistent
    compile cache), steady-state the sharded step, then push real
    pipeline traffic through the serving plane.  Prints one JSON line.

    measure_boot: the warm-boot probe — time from process entry to the
    first completed serving step (the leader's cold-start figure; with
    the cache hot this must be seconds, not the 2m15s MULTICHIP_r05
    compile)."""
    t_boot = time.time()
    from firedancer_tpu.utils import platform as fp

    # --cpu: always 8 virtual devices so every rung shares one target
    # configuration; the mesh takes the first n
    fp.select_device(cpu, device_count=8)
    cache_dir = fp.compile_cache_dir()

    import jax

    from firedancer_tpu.models.leader import build_sharded_leader_pipeline
    from firedancer_tpu.parallel.serve import ServeConfig, ServePlane

    cfg = ServeConfig(
        n_devices=n_devices,
        batch_per_shard=SERVE_BATCH_PER_SHARD,
        max_msg_len=256,
        fec_shred_sz=1024,
        poh_iters=64,
    )
    plane = ServePlane(cfg)
    was_warm = os.path.exists(os.path.join(
        cache_dir, f"serve_step_{cfg.cache_key()}.hlo"))
    compile_s = plane.warmup()
    print(f"# serve[{n_devices}d]: step compile/load {compile_s:.1f}s "
          f"({'warm' if was_warm else 'cold'} cache {cache_dir})",
          file=sys.stderr)

    # -- sharded-step portion: steady-state the ONE program ----------------
    import __graft_entry__ as ge

    b = cfg.batch
    msg, msg_len, sig, pk = ge._example_batch(b, seed=13)
    # _example_batch emits MAX_MSG_LEN(=128) rows; widen to the plane's
    mm = np.zeros((cfg.max_msg_len, b), dtype=np.uint8)
    mm[: msg.shape[0]] = msg
    full = np.full((n_devices,), cfg.batch_per_shard, dtype=np.int32)
    pend = plane.submit(mm, msg_len, sig, pk, full)
    n_ok = int(np.asarray(pend.n_ok))
    t_first = time.time() - t_boot
    assert n_ok == b, f"honest signatures must all verify ({n_ok}/{b})"
    if measure_boot:
        print(json.dumps({
            "mode": "boot_probe", "devices": n_devices,
            "boot_to_first_step_s": round(t_first, 2),
            "compile_s": round(compile_s, 2),
            "compile_cache": "warm" if was_warm else "cold",
            "backend": jax.devices()[0].platform,
        }))
        return
    outs = []
    t0 = time.time()
    for _ in range(SERVE_STEP_ROUNDS):
        outs.append(plane.submit(mm, msg_len, sig, pk, full))
        if len(outs) >= 3:
            int(np.asarray(outs.pop(0).n_ok))
    for o in outs:
        int(np.asarray(o.n_ok))
    step_elapsed = time.time() - t0
    step_rate = b * SERVE_STEP_ROUNDS / step_elapsed
    print(f"# serve[{n_devices}d]: step steady "
          f"{b * SERVE_STEP_ROUNDS} elems in {step_elapsed:.2f}s "
          f"({step_rate:.0f}/s)", file=sys.stderr)

    # -- real pipeline traffic through the plane ---------------------------
    pipe = build_sharded_leader_pipeline(
        plane=plane,
        n_shards=n_devices,
        batch_per_shard=cfg.batch_per_shard,
        max_msg_len=cfg.max_msg_len,
        pool_size=SERVE_TXNS,
        gen_limit=SERVE_TXNS,
        batch_deadline_s=0.01,
    )
    try:
        t0 = time.time()
        pipe.run(until_txns=SERVE_TXNS, max_iters=2_000_000)
        elapsed = time.time() - t0
        executed = sum(bk.metrics.get("txn_exec") for bk in pipe.banks)
        rate = executed / elapsed if elapsed > 0 else 0.0
        vm = pipe.verifies[0].metrics
        shard_elems = [
            vm.get(f"shard_elems_s{i}") for i in range(n_devices)
        ]
        out = {
            "mode": "serve", "devices": n_devices,
            "compile_s": round(compile_s, 2),
            "compile_cache": "warm" if was_warm else "cold",
            "step_elems_per_s": round(step_rate, 1),
            "step_batch": b,
            "pipeline_txn_per_s": round(rate, 1),
            "pipeline_txn_executed": executed,
            "shard_elems": shard_elems,
            "router_routed": pipe.router.metrics.get("routed_total"),
            "poh_spans_ok": vm.get("poh_spans_ok"),
            "fec_sets": pipe.shred.metrics.get("fec_sets"),
            "backend": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
        }
        print(f"# serve[{n_devices}d]: pipeline {executed} txns in "
              f"{elapsed:.2f}s ({rate:.0f} txn/s), shards {shard_elems}",
              file=sys.stderr)
        print(json.dumps(out))
    finally:
        pipe.close()


def _persist_multichip(obj: dict) -> None:
    obj["captured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    with open(MULTICHIP_ARTIFACT, "w") as f:
        json.dump(obj, f, indent=1)
        f.write("\n")
    print(f"# multichip artifact persisted: {MULTICHIP_ARTIFACT}",
          file=sys.stderr)


def run_multichip_serve(cpu: bool = False) -> None:
    """The serving-plane ladder: 1/2/4/8 devices as far as the machine
    has them, each rung in a fresh child (one process holds the chips at
    a time; this parent never imports JAX), then the warm-boot probe.  The artifact separates compile time from
    steady state and reports scaling efficiency on the sharded-step
    portion (weak scaling: per-shard batch fixed, so N devices carry N x
    the elements; efficiency = rate_N / (N * rate_1))."""
    art: dict = {
        "metric": "multichip_serve",
        "device_ladder": list(SERVE_DEVICE_LADDER),
        "batch_per_shard": SERVE_BATCH_PER_SHARD,
        "host_cores": os.cpu_count(),
        "runs": [],
    }
    rates = {}
    mode = ["--cpu"] if cpu else []
    device_count = 1  # the first rung's child reports what there is
    for n in SERVE_DEVICE_LADDER:
        if n > device_count:
            art["runs"].append(
                {"devices": n, "skipped": f"{device_count} device(s)"})
            continue
        line = _run_child(["--serve-child", str(n), *mode],
                          SERVE_CHILD_TIMEOUT_S)
        if line is None:
            art["runs"].append({"devices": n, "error": "child failed"})
            _persist_multichip(dict(art))
            continue
        rec = json.loads(line)
        art["runs"].append(rec)
        rates[n] = rec.get("step_elems_per_s", 0.0)
        device_count = rec["device_count"]
        # per-rung persistence: a later rung wedging must not erase the
        # earlier evidence (the BENCH mid-artifact discipline)
        _persist_multichip(dict(art))
    if 1 in rates and rates[1] > 0:
        # raw rate ratio: the number to read when the N virtual devices
        # actually run concurrently (multi-core host or real chips)
        art["scaling_efficiency_step"] = {
            str(n): round(rates[n] / (n * rates[1]), 3)
            for n in rates if n != 1 and rates.get(n)
        }
        # serialized-host normalization: on a 1-core host XLA's virtual
        # devices TIME-SLICE, so rate_N/(N*rate_1) is bounded by ~1/N by
        # construction and measures the scheduler, not the program.  The
        # meaningful 1-core signal is work conservation, N*t_1/t_N; with
        # rate = N*per/t_N that reduces to rate_N/rate_1 — 1.0 means
        # sharding added zero overhead over running the N per-shard
        # programs back to back (no resharding collectives / partition
        # blowup), which IS the wall-clock efficiency once the
        # partitions run on N real devices.
        art["scaling_efficiency_step_serialized_host"] = {
            str(n): round(rates[n] / rates[1], 3)
            for n in rates if n != 1 and rates.get(n)
        }
        one_core = (os.cpu_count() or 1) <= 1
        art["efficiency_basis"] = (
            "serialized_host" if one_core else "concurrent"
        )
        key = ("scaling_efficiency_step_serialized_host" if one_core
               else "scaling_efficiency_step")
        eff4 = art[key].get("4")
        if eff4 is not None:
            art["scaling_efficiency_4dev_ok"] = eff4 >= 0.70
    # warm-boot probe: the cache is hot now — a fresh process must reach
    # its first served step inside the slot-start budget
    line = _run_child(["--serve-boot-probe", str(min(4, device_count)),
                       *mode], SERVE_CHILD_TIMEOUT_S)
    if line is not None:
        rec = json.loads(line)
        art["warm_cold_start_s"] = rec.get("boot_to_first_step_s")
        art["warm_cold_start_budget_s"] = WARM_COLD_START_BUDGET_S
        art["warm_cold_start_ok"] = (
            rec.get("boot_to_first_step_s", 1e9) < WARM_COLD_START_BUDGET_S
        )
    _persist_multichip(art)
    basis = art.get("efficiency_basis")
    eff_key = ("scaling_efficiency_step_serialized_host"
               if basis == "serialized_host" else "scaling_efficiency_step")
    print(json.dumps({
        "metric": "multichip_serve",
        "value": max(
            (r.get("pipeline_txn_per_s", 0.0) for r in art["runs"]
             if isinstance(r, dict)), default=0.0,
        ),
        "unit": "txn/s",
        "artifact": MULTICHIP_ARTIFACT,
        # the headline efficiency is the artifact's basis-selected one;
        # printing the raw time-sliced ratio on a 1-core host would read
        # as broken scaling when the basis says otherwise
        "efficiency_basis": basis,
        "scaling_efficiency_step": art.get(eff_key),
        "warm_cold_start_s": art.get("warm_cold_start_s"),
    }))


def main() -> int:
    cpu = "--cpu" in sys.argv
    if "--kernel-ladder" in sys.argv:
        print(json.dumps(run_kernel_ladder(cpu=cpu), indent=1))
        return 0
    if "--net-ab" in sys.argv:
        i = sys.argv.index("--net-ab")
        n = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 \
            and sys.argv[i + 1].isdigit() else 3
        print(json.dumps(run_net_ab(pairs=n), indent=1))
        return 0
    if "--e2e-ingress" in sys.argv:
        i = sys.argv.index("--e2e-ingress")
        n = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 \
            and sys.argv[i + 1].isdigit() else 3
        print(json.dumps(run_e2e_ingress_ab(pairs=n), indent=1))
        return 0
    if "--verify-ab" in sys.argv:
        i = sys.argv.index("--verify-ab")
        n = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 \
            and sys.argv[i + 1].isdigit() else 3
        print(json.dumps(run_verify_ab(pairs=n), indent=1))
        return 0
    if "--bank-ab" in sys.argv:
        i = sys.argv.index("--bank-ab")
        n = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 \
            and sys.argv[i + 1].isdigit() else 3
        print(json.dumps(run_bank_ab(pairs=n), indent=1))
        return 0
    if "--funk-ab" in sys.argv:
        i = sys.argv.index("--funk-ab")
        n = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 \
            and sys.argv[i + 1].isdigit() else 3
        print(json.dumps(run_funk_ab(pairs=n), indent=1))
        return 0
    if "--metrics-ab" in sys.argv:
        i = sys.argv.index("--metrics-ab")
        n = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 \
            and sys.argv[i + 1].isdigit() else 3
        print(json.dumps(run_metrics_ab(pairs=n), indent=1))
        return 0
    if "--shred-ab" in sys.argv:
        i = sys.argv.index("--shred-ab")
        n = int(sys.argv[i + 1]) if len(sys.argv) > i + 1 \
            and sys.argv[i + 1].isdigit() else 3
        print(json.dumps(run_shred_ab(pairs=n), indent=1))
        return 0
    if "--host-pipeline" in sys.argv:
        print(json.dumps(run_host_pipeline_bench(), indent=1))
        return 0
    if "--serve-child" in sys.argv:
        n = int(sys.argv[sys.argv.index("--serve-child") + 1])
        serve_child(n, cpu=cpu)
        return 0
    if "--serve-boot-probe" in sys.argv:
        n = int(sys.argv[sys.argv.index("--serve-boot-probe") + 1])
        serve_child(n, measure_boot=True, cpu=cpu)
        return 0
    if "--multichip-serve" in sys.argv:
        run_multichip_serve(cpu=cpu)
        return 0
    run_bench(cpu=cpu)
    return 0


if __name__ == "__main__":
    from firedancer_tpu.utils.platform import NoChipError

    try:
        sys.exit(main())
    except NoChipError as e:
        print(f"bench.py: {e}", file=sys.stderr)
        sys.exit(1)
