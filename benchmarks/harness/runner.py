"""One run of one cell: set up, warm up, measure for --seconds, (trace),
drain, check, and hand back the result line's content.

Everything runs in ONE process, the cooperative form: this process holds
the chip and is the only one that can trace it.  The harness owns the
sweep loop (`for s in stages: s.run_once()`), because the program's own
`LeaderPipeline.run` stops on a count, not on a clock.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

from . import check, stats, trace_reduce
from . import traffic as T
from .manifest import Manifest

# The traced window comes after the measured one and is short: the
# sigverify program is ~110,000 device events a batch, 7 million a second
# on a saturated chip, and stop_trace costs ~27 s per million of them
# (my chip runs, PR 23: 0.15 s of capture 27.5 s, 0.4 s 171 s).  The
# settle lets the in-flight window refill after start_trace's ~50 ms stall.
TRACE_SETTLE_S = 0.05
TRACE_S = 0.12
DRAIN_LIMIT_S = 20.0
PROGRAM = "ed25519_verify_batch_fused"  # the sigverify program's module


class Compiles:
    """Counts XLA backend compiles (cache loads included) through
    jax.monitoring (chip_smoke.py's counter, copied)."""

    def __init__(self):
        import jax.monitoring as jm

        self.n = 0
        jm.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.n += 1


def say(**fields) -> None:
    """An earlier line of stdout: one JSON object, never the last."""
    print(json.dumps(fields, default=float), flush=True)


def sweep_until(stages, t_end_ns: int) -> int:
    """The timed loop.  -> sweeps made."""
    now = time.monotonic_ns
    n = 0
    while now() < t_end_ns:
        for s in stages:
            s.run_once()
        n += 1
    return n


def sweep_timed(stages, t_end_ns: int, acc: list[int], spans=None) -> int:
    """The same loop with a host timer around each stage's run_once
    (traced runs): `acc[k]` sums ns in stage k; `spans`, when given,
    collects (k, start, end) on the monotonic clock."""
    now = time.monotonic_ns
    n = 0
    t = now()
    while t < t_end_ns:
        for k, s in enumerate(stages):
            s.run_once()
            t2 = now()
            acc[k] += t2 - t
            if spans is not None:
                spans.append((k, t, t2))
            t = t2
        n += 1
    return n


def delta(c1: dict, c0: dict) -> dict:
    return {s: {k: v - c0.get(s, {}).get(k, 0) for k, v in d.items()}
            for s, d in c1.items()}


def run_cell(args, t_entry: float, dev: tuple) -> dict:
    man = Manifest()
    if dev[0] == "tpu":
        man.peaks(dev[1])               # an unknown device is an error
    cell = man.cell(args.workload)
    config = man.config(cell)
    traffic = man.traffic(cell)
    for kv in args.set or []:           # --cpu rehearsal only
        path, val = kv.split("=", 1)
        node = config
        *head, leaf = path.split(".")
        for h in head:
            node = node[h]
        node[leaf] = json.loads(val)
    extra_s = (TRACE_SETTLE_S + TRACE_S + 1.0) if args.trace else 0.0
    span_s = traffic["warmup_s"] + args.seconds + extra_s + 2.0
    system, compiles, prewarm_s = build_system(
        man, config, traffic, args.seed, span_s, args.control)
    try:
        return _drive(args, man, cell, traffic, system, compiles, t_entry,
                      prewarm_s, dev)
    finally:
        system.close()


def build_system(man, config: dict, traffic: dict, seed: int, span_s: float,
                 control: str | None):
    """A cell's set-up: the pool its shape makes (spawned signers) beside
    the device warm-up, the corrupted rows, the order and the arrivals
    for `span_s` of traffic, then the system under test.
    -> (system, the compile counter, prewarm seconds); the pool and the
    order are the system's generator's (`system.gen`)."""
    topo = man.topology(config["topology"])
    shape = man.shape(traffic)
    # a fixed pool is replayed (its order wraps); one sized by a rate has
    # to last the run, and running out of it makes the run incorrect
    wrap = "pool_txns" in traffic
    n_pool = traffic["pool_txns"] if wrap \
        else int(np.ceil(traffic["pool_txn_per_s"] * span_s))
    acct = config["traffic_accounts"]
    job = T.PoolJob(man.shape_path(traffic), seed, n_pool, acct, traffic)
    try:
        from firedancer_tpu.utils import nativebuild

        nativebuild.build_all()         # stale or missing libraries only
        compiles = Compiles()
        prewarm_s = topo.prewarm(config, control)
        pool = job.result()
    except BaseException:
        job.abort()
        raise
    pool.bad = shape.corrupt(pool, traffic["corrupt_one_in"], seed)
    order = shape.order(pool, seed, traffic)
    due = None
    if traffic["kind"] == "paced":
        n_due = int(np.ceil(traffic["rate_per_s"] * span_s))
        due = man.arrivals(traffic).due_ns(traffic, n_due, seed)
    elif traffic["kind"] != "flood":
        raise ValueError(f"traffic kind {traffic['kind']!r}")
    system = topo.System(
        config, dict(pool=pool, order=order, due_ns=due, wrap=wrap),
        control, shape.genesis(acct, seed))
    return system, compiles, prewarm_s


def _drive(args, man, cell, traffic, system, compiles, t_entry, prewarm_s,
           dev) -> dict:
    stages = system.stages
    gen = system.gen
    pool = gen.pool
    warm2_s = system.warmup()   # the stage's own call: a jit-cache hit
    armed = system.armed()
    n_compiles_setup = compiles.n

    # -- warm-up window: traffic through every host lane -------------------
    setup_s = time.monotonic() - t_entry
    t_start = time.monotonic_ns()
    gen.start(t_start)
    sweep_until(stages, t_start + int(traffic["warmup_s"] * 1e9))
    say(setup={"setup_s": setup_s, "prewarm_s": prewarm_s,
               "stage_warmup_s": warm2_s, "pool_txns": pool.n,
               "compiles_in_setup": n_compiles_setup, "armed": armed})

    # -- the measured window ------------------------------------------------
    c0 = system.counters()
    n_comp0 = compiles.n
    i0, served0 = gen.i, system.served()
    acc = [0] * len(stages)
    t0 = time.monotonic_ns()
    if args.trace:
        sweeps = sweep_timed(stages, t0 + int(args.seconds * 1e9), acc)
    else:
        sweeps = sweep_until(stages, t0 + int(args.seconds * 1e9))
    t1 = time.monotonic_ns()
    i1, served1 = gen.i, system.served()
    compiles_in_window = compiles.n - n_comp0
    c1 = system.counters()

    # -- the traced window (its own, after the measured one) ---------------
    trace = None
    if args.trace:
        trace = _trace(args, system, dev)
    n_comp_end = compiles.n

    # -- drain and check ----------------------------------------------------
    drained = system.drain(DRAIN_LIMIT_S)
    c_end = system.counters()
    landed, unknown = system.landed()
    offered = check.offered_rows(gen.order, 0, gen.i)
    res = check.compare(
        pool=pool, offered=offered, due=system.due(offered, pool.valid),
        landed=landed, unknown=unknown,
        verify_fail=c_end["verify0"].get("verify_fail", 0),
        dedup=system.dedup_counted(c_end), dropped=system.dropped(c_end),
        drained=drained, window=(i0, i1), seed=args.seed)
    numbers = dict(res.pop("numbers"))
    numbers["compiles_in_window"] = (compiles_in_window, 0)
    numbers["native_lanes_not_armed"] = (
        sum(not v for v in armed.values()), 0)
    numbers["pool_exhausted"] = (int(gen.exhausted), 0)
    numbers.update(system.extra_checks())
    correct = all(v <= lim for v, lim in numbers.values())
    say(check={k: {"value": v, "limit": lim} for k, (v, lim)
               in numbers.items()}, drained=drained, control=args.control,
        compiles_after_window=n_comp_end - n_comp0 - compiles_in_window,
        **res, **system.notes())

    window_s = (t1 - t0) / 1e9
    lat = system.latencies_ns(t0, t1)
    late = gen.late_ns(i0, i1)
    run = {
        "window_s": window_s, "served": served1 - served0,
        "offered": i1 - i0, "lat_ns": lat, "late_ns": late,
        "counters": delta(c1, c0), "batch": system.batch,
        "timers_s": ({s.name: acc[k] / 1e9 for k, s in enumerate(stages)}
                     if args.trace else None),
        "host_stages": system.host_stages, "trace": trace,
        "setup_s": setup_s, "sweeps": sweeps,
    }
    say(window={"window_s": window_s, "sweeps": sweeps,
                "offered": run["offered"], "served": run["served"],
                "latency": stats.tail_ms(lat),
                "generator_late": stats.tail_ms(late),
                "longest_generator_pause": _pause(gen, i0, i1, t0),
                "stage_s": run["timers_s"],
                "verify": _verify_counts(run["counters"]["verify0"]),
                "dedup_dup": {s: c["dedup_dup"] for s, c
                              in run["counters"].items() if "dedup_dup" in c}})
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in man.metrics(group, cell["name"]):
        v = man.reader(group, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(run["offered"]),
           "failed": int(res["failed"]), "metrics": metrics}
    if trace is not None:
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
        out["_trace"] = {"busy_s": trace["busy_s"],
                         "window_s": trace["window_s"]}
    return out


def _verify_counts(v: dict) -> dict:
    """The verify stage's counters a builder reads beside the metrics
    (printed, not metrics): work, why its batches closed, stalls, and
    over a mesh what each chip was dealt."""
    keys = ["batches", "batch_elems", "txn_verified", "verify_fail",
            "submit_deferred", "batch_close_full", "batch_close_deadline",
            "batch_close_window", "batch_stalls"]
    keys += sorted((k for k in v if k.startswith("shard_elems_s")),
                   key=lambda k: int(k[len("shard_elems_s"):]))
    return {k: v.get(k, 0) for k in keys}


def _pause(gen, i0: int, i1: int, t0: int) -> dict | None:
    if gen.due is None:
        return None
    ns, at = gen.longest_pause(i0, i1)
    return {"ms": ns / 1e6, "at_s": (at - t0) / 1e9}


def _trace(args, system, dev) -> dict | None:
    """A moment more of the same traffic under the profiler, in a window
    of its own so that starting and stopping the profiler (which stall
    this thread) fall outside every counter the measured window read."""
    import jax

    from .manifest import ROOT

    stages = system.stages
    trace_dir = os.path.join(ROOT, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # the sweep loop is millions of calls
    opts.host_tracer_level = 1      # annotations only
    opts.enable_hlo_proto = False   # the program's HLO is not read here
    acc = [0] * len(stages)
    spans: list = []
    t_a = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_b = time.monotonic()
    try:
        sweep_until(stages,
                    time.monotonic_ns() + int(TRACE_SETTLE_S * 1e9))
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            w0 = time.monotonic_ns()
            sweep_timed(stages, w0 + int(TRACE_S * 1e9), acc, spans)
    finally:
        # nothing more is offered: stop_trace stalls this thread for tens
        # of seconds, and a paced generator would owe all of them at once
        system.gen.limit = 0
        t_c = time.monotonic()
        jax.profiler.stop_trace()
    t_d = time.monotonic()
    if dev[0] != "tpu":
        return None                 # a rehearsal has no device plane
    raw = trace_reduce.extract(trace_reduce.find_xplane(trace_dir))
    if args.keep_trace:
        _dump_trace(raw, args.keep_trace)
    shutil.rmtree(trace_dir, ignore_errors=True)
    off = raw["window"][0] - w0 if raw["window"] else 0
    names = [s.name for s in stages]
    reduced = trace_reduce.reduce(
        raw, PROGRAM, [(names[k], a + off, b + off) for k, a, b in spans])
    say(trace={k: reduced[k] for k in (
        "window_s", "busy_s", "program_s", "program_runs", "ops_cover",
        "n_gaps", "idle_gap_s")},
        stage_s_in_traced_window={n: a / 1e9 for n, a in zip(names, acc)},
        profiler_cost_s={"start": t_b - t_a, "stop": t_d - t_c,
                         "read_and_reduce": time.monotonic() - t_d})
    return reduced


def _dump_trace(raw: dict, path: str) -> None:
    """Builder's aid (--keep-trace <file>): the first 40 ms of the
    extracted window with 1 ms of its operations, as the gzipped JSON
    the trace tests read."""
    import gzip

    w0 = raw["window"][0]
    w1, o1 = w0 + 40e6, w0 + 1e6
    small = {"window": [w0, w1], "devices": {
        name: {"modules": [e for e in dev["modules"]
                           if e[1] + e[2] > w0 - 10e6 and e[1] < w1 + 10e6],
               "ops": [e for e in dev["ops"] if e[1] < o1],
               "ops_window": [w0, o1],
               "last_ns": min(dev["last_ns"], w1 + 10e6)}
        for name, dev in raw["devices"].items()}}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(small, f)
