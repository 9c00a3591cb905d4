"""Builder's tool: where a verify batch waits, on the device trace's clock.

    python3 benchmarks/tools/batch_timeline.py --workload NAME --seed N \
        [--trace-s 0.12] [--out chiprun_out/<file>.json]

A short traced session on the cell's own topology and traffic (the
set-up, warm-up and profiler options of a `--trace 1` run).  The verify
stage wraps each batch's dispatch, reap and publish in a
`jax.profiler.TraceAnnotation` that carries the batch's sequence number;
under the profiler they land on the host plane of the same `.xplane.pb`
as the device's module events, so the two share a clock with no offset
arithmetic.  Batches run on the device in dispatch order, so one anchor
numbers every module by counting (a hole of a program's length between
two modules is a module event the tracer lost, and counts as one); `join`
says how the anchor is found.  A batch is dropped, and counted, when its
dispatch does not begin before its module starts or its module does not
end before its reap begins: the join is then wrong for it, not the
program (nor when the reap begins a program's length or more after the
module ended: the anchor rests on that bound).  start_trace holds the thread ~50 ms, so a session opens on a
drained window: the first batches dispatched in it find the device idle,
and the summary gives the medians again without them.

Prints per batch the device queue (module start - dispatch end), the
reap lag (reap begin - module end) and the spans' own lengths, their
medians, and the ten longest gaps between modules by the annotation that
covers them.  A metric it is not: the benchmark's reducer does not read
annotations (PERF.md, Open questions).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SPANS = ("verify.dispatch", "verify.reap", "verify.publish")
_SEQ = re.compile(r"#batch=(\d+)#")
KEYS = ("dispatch_ms", "queue_ms", "exec_ms", "reap_lag_ms", "reap_ms")


def read_trace(xplane_path: str, program: str) -> dict:
    """-> {"spans": {span name: {seq: [start, end]}}, "modules": [[start,
    end]...] of `program`, sorted}, ns from the session start."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    spans: dict = {name: {} for name in SPANS}
    modules = []
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        if not host and not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if not host and line.name != "XLA Modules":
                continue
            for e in line.events:
                if not host:
                    if program in e.name:
                        modules.append([e.start_ns,
                                        e.start_ns + e.duration_ns])
                    continue
                base = e.name.split("#", 1)[0]
                if base not in spans:
                    continue
                m = _SEQ.search(e.name)
                seq = int(m.group(1)) if m else None
                if seq is None:
                    for key, val in e.stats:
                        if key == "batch":
                            seq = int(val)
                if seq is not None:
                    spans[base][seq] = [e.start_ns,
                                        e.start_ns + e.duration_ns]
    modules.sort()
    return {"spans": spans, "modules": modules}


def join(spans: dict, modules: list) -> dict:
    """Module k <-> batch, in FIFO order.  -> {"rows": [{seq,
    dispatch_ms, queue_ms, exec_ms, reap_lag_ms, reap_ms}...], "dropped":
    n, "unmatched": modules with no annotated batch}.

    A batch is in order when its dispatch begins before its module
    starts, its module ends before its reap begins, and the reap begins
    within one program's length of that end (the loop looks every few
    ms; without this bound the eight-deep window leaves an anchor that
    is too high unpunished, as the reap order alone punishes one that is
    too low).  The anchor is the numbering under which most batches are
    in order; the others are dropped and counted.  (The module that ends
    just before the first reap is NOT that reap's batch in a traced
    session: start_trace holds the thread ~50 ms, the window drains
    meanwhile, and the first reaps are a backlog.)"""
    disp, reap = spans["verify.dispatch"], spans["verify.reap"]
    both = sorted(set(disp) & set(reap))
    if not both or not modules:
        return {"rows": [], "dropped": 0, "unmatched": len(modules)}
    # the tracer can lose a module event: a hole of about one program's
    # length between two modules counts as the batches it would hold
    period = statistics.median(b - a for a, b in modules)
    order = [0]
    for k in range(1, len(modules)):
        hole = max(modules[k][0] - modules[k - 1][1], 0)
        order.append(order[-1] + 1 + round(hole / period))

    def in_order(seq: int, m0: float, m1: float) -> bool:
        return disp[seq][0] <= m0 and m1 <= reap[seq][0] < m1 + period

    def joined(first: int) -> int:
        return sum(1 for k, (m0, m1) in enumerate(modules)
                   if first + order[k] in disp and first + order[k] in reap
                   and in_order(first + order[k], m0, m1))

    first = max(range(both[0] - order[-1], both[-1] + 1),
                key=lambda f: (joined(f), -f))
    rows, dropped, unmatched = [], 0, 0
    for k, (m0, m1) in enumerate(modules):
        seq = first + order[k]
        if seq not in disp or seq not in reap:
            unmatched += 1      # dispatched before, or reaped after, the trace
            continue
        d0, d1 = disp[seq]
        r0, r1 = reap[seq]
        if not in_order(seq, m0, m1):
            dropped += 1
            continue
        rows.append({"seq": seq, "dispatch_ms": (d1 - d0) / 1e6,
                     "queue_ms": (m0 - d1) / 1e6, "exec_ms": (m1 - m0) / 1e6,
                     "reap_lag_ms": (r0 - m1) / 1e6,
                     "reap_ms": (r1 - r0) / 1e6})
    return {"rows": rows, "dropped": dropped, "unmatched": unmatched}


def gaps(spans: dict, modules: list, top: int = 10) -> list:
    """The longest gaps between consecutive modules, each with the
    annotation that covers most of it.  -> [[label, us]...]."""
    flat = [(f"{name}#{seq}", a, b) for name, by in spans.items()
            for seq, (a, b) in by.items()]
    out = []
    for (_, end), (start, _) in zip(modules, modules[1:]):
        if start <= end:
            continue
        best, cover = "no annotation", 0.0
        for label, a, b in flat:
            o = min(b, start) - max(a, end)
            if o > cover:
                best, cover = label, o
        out.append([best, (start - end) / 1e3])
    return sorted(out, key=lambda g: -g[1])[:top]


def _median(rows: list, key: str):
    return statistics.median(r[key] for r in rows) if rows else None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace-s", type=float, default=0.12)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    from firedancer_tpu.utils.platform import NoChipError, select_device

    try:
        dev = select_device(cpu=False)
    except NoChipError as e:
        print(f"batch_timeline: {e}", file=sys.stderr)
        return 3
    import jax

    from harness import runner
    from harness.manifest import Manifest

    man = Manifest()
    cell = man.cell(a.workload)
    config, traffic = man.config(cell), man.traffic(cell)
    system = runner.build_system(
        man, config, traffic, a.seed,
        traffic["warmup_s"] + a.trace_s + 4.0, None)[0]
    trace_dir = os.path.join(ROOT, ".bench_trace")
    try:
        system.warmup()
        stages = system.stages
        t0 = time.monotonic_ns()
        system.gen.start(t0)
        runner.sweep_until(stages, t0 + int(traffic["warmup_s"] * 1e9))
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # annotations only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            runner.sweep_until(stages, time.monotonic_ns() + int(
                (runner.TRACE_SETTLE_S + a.trace_s) * 1e9))
        finally:
            system.gen.limit = 0
            jax.profiler.stop_trace()
        from harness import trace_reduce

        tr = read_trace(trace_reduce.find_xplane(trace_dir), runner.PROGRAM)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        system.close()
    got = join(tr["spans"], tr["modules"])
    rows = got["rows"]
    print(f"# {a.workload} seed {a.seed} on {dev[1]}: {len(tr['modules'])} "
          f"modules, " + ", ".join(f"{len(v)} {k}" for k, v
                                   in tr["spans"].items()))
    print("seq dispatch_ms queue_ms exec_ms reap_lag_ms reap_ms")
    for r in rows:
        print(f"{r['seq']} {r['dispatch_ms']:.3f} {r['queue_ms']:.3f} "
              f"{r['exec_ms']:.3f} {r['reap_lag_ms']:.3f} {r['reap_ms']:.3f}")
    summary = {"workload": a.workload, "seed": a.seed, "device": dev[1],
               "joined": len(rows), "dropped": got["dropped"],
               "unmatched": got["unmatched"],
               "median_ms": {k: _median(rows, k) for k in KEYS},
               "median_ms_after_refill": {
                   k: _median(rows[system.verify.max_inflight:], k)
                   for k in KEYS},
               "longest_gaps_us": gaps(tr["spans"], tr["modules"])}
    print(json.dumps(summary))
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({**summary, "rows": rows, "modules": tr["modules"],
                       "spans": {k: {str(q): v for q, v in by.items()}
                                 for k, by in tr["spans"].items()}}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
