"""Schema-driven metrics: declared layout -> flat u64 array -> Prometheus.

The reference compiles metrics.xml into per-tile accessor headers over a
plain ulong array in shared memory, then a metric tile serves Prometheus
(/root/reference/src/disco/metrics/fd_metrics.h:22-47,
run/tiles/fd_metric.c).  Same shape here: a MetricsSchema declares
counters/gauges/histograms per stage kind, MetricsRegistry lays them out
in one flat uint64 numpy array (shared-memory-backable, so a monitor
process reads producers' metrics without cooperation), and
render_prometheus emits the text exposition format.

Histograms are fixed-bucket log-spaced (the fd_histf shape): `buckets`
edges; value counts land in the first bucket whose edge >= value, plus a
+Inf overflow bucket and a running sum for averages.  The sum word is a
SCALED integer (value * SUM_SCALE, rounded) so sub-unit observations —
e.g. ms-denominated latencies — accumulate without truncating to zero;
readers divide back out, so `hist()["sum"]` is a float in the metric's
own unit.  Negative observations clamp to zero (counted in the first
bucket, zero added to the sum) — histograms here measure non-negative
quantities (latencies, sizes).

This module also carries the FLIGHT RECORDER: a tiny fixed ring of
(ts, event, arg) records living in the same shm segment as a stage's
metric words, written in-line (not flushed lazily) so the record
survives the writing process crashing — the supervisor dumps every
stage's ring on abnormal exit and `flight_to_chrome_trace` converts a
dump into Chrome trace-event JSON that Perfetto/chrome://tracing opens.

Segment layout (metrics_segment_*): 4 header words (magic, metric word
count, recorder capacity, reserved) | metric words | recorder words.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

# histogram sum words store round(value * SUM_SCALE): 1/1024 resolution,
# so a 0.5 ms observation into an ms-denominated histogram adds 512, not 0
SUM_SCALE = 1024

_MASK64 = (1 << 64) - 1


@dataclass(frozen=True)
class MetricDef:
    name: str
    kind: str
    help: str = ""
    buckets: tuple = ()  # histogram edges, ascending
    # native=True marks a metric OWNED by a C sweep client: it is written
    # in-line from inside the fdr_sweep crossing, so the Python Metrics
    # facade must neither flush nor resume-copy these words (either would
    # clobber the relaxed-atomic C increments).  fdlint FD219 enforces
    # the ownership split statically.
    native: bool = False

    def words(self) -> int:
        if self.kind == HISTOGRAM:
            return len(self.buckets) + 2  # buckets + overflow + sum
        return 1


@dataclass
class MetricsSchema:
    defs: list[MetricDef] = field(default_factory=list)

    def counter(self, name: str, help: str = "", *,
                native: bool = False) -> "MetricsSchema":
        self.defs.append(MetricDef(name, COUNTER, help, native=native))
        return self

    def gauge(self, name: str, help: str = "", *,
              native: bool = False) -> "MetricsSchema":
        self.defs.append(MetricDef(name, GAUGE, help, native=native))
        return self

    def histogram(self, name: str, buckets, help: str = "", *,
                  native: bool = False) -> "MetricsSchema":
        edges = tuple(buckets)
        if list(edges) != sorted(edges) or not edges:
            raise ValueError("histogram buckets must be ascending, non-empty")
        self.defs.append(MetricDef(name, HISTOGRAM, help, edges,
                                   native=native))
        return self

    def footprint(self) -> int:
        return sum(d.words() for d in self.defs)

    def names(self) -> set[str]:
        return {d.name for d in self.defs}


def schema_to_obj(schema: MetricsSchema) -> list[dict]:
    """JSON-serializable schema (run-descriptor form): a monitor process
    reconstructs the registry layout without importing stage classes."""
    out = []
    for d in schema.defs:
        o = {"name": d.name, "kind": d.kind, "help": d.help,
             "buckets": list(d.buckets)}
        if d.native:  # omit-when-false keeps old descriptors byte-stable
            o["native"] = True
        out.append(o)
    return out


def schema_from_obj(obj: list[dict]) -> MetricsSchema:
    s = MetricsSchema()
    for d in obj:
        s.defs.append(MetricDef(d["name"], d["kind"], d.get("help", ""),
                                tuple(d.get("buckets", ())),
                                native=bool(d.get("native", False))))
    return s


def exp_buckets(lo: float, hi: float, n: int) -> tuple:
    """Log-spaced bucket edges (the fd_histf approximate-exponential shape)."""
    return tuple(float(x) for x in np.geomspace(lo, hi, n))


class MetricsRegistry:
    """One stage's metric words over a (shareable) uint64 array."""

    def __init__(self, schema: MetricsSchema, buf: np.ndarray | None = None):
        self.schema = schema
        n = schema.footprint()
        self.words = buf if buf is not None else np.zeros(n, dtype=np.uint64)
        if len(self.words) < n:
            raise ValueError("buffer too small for schema")
        self._off: dict[str, tuple[MetricDef, int]] = {}
        # bucket edges precomputed per histogram: observe() must not
        # allocate per call (fdlint FD208's rationale)
        self._edges: dict[str, np.ndarray] = {}
        off = 0
        for d in schema.defs:
            if d.name in self._off:
                # a colliding name would silently orphan the first def's
                # words and emit duplicate series — fail at layout time
                raise ValueError(f"duplicate metric name '{d.name}'")
            self._off[d.name] = (d, off)
            if d.kind == HISTOGRAM:
                self._edges[d.name] = np.asarray(d.buckets, dtype=np.float64)
            off += d.words()

    # -- producers ----------------------------------------------------------

    def inc(self, name: str, v: int = 1) -> None:
        d, off = self._off[name]
        if d.kind not in (COUNTER, GAUGE):
            raise TypeError(f"{name} is a {d.kind}")
        self.words[off] += np.uint64(v)

    def set(self, name: str, v: int) -> None:
        d, off = self._off[name]
        if d.kind != GAUGE:
            raise TypeError(f"{name} is a {d.kind}")
        self.words[off] = np.uint64(v)

    def observe(self, name: str, value: float) -> None:
        d, off = self._off[name]
        if d.kind != HISTOGRAM:
            raise TypeError(f"{name} is a {d.kind}")
        idx = int(np.searchsorted(self._edges[name], value, side="left"))
        self.words[off + idx] += np.uint64(1)  # overflow lands at len(buckets)
        # scaled integer sum: fractional observations accumulate exactly
        # to 1/SUM_SCALE resolution instead of truncating to 0
        self.words[off + len(d.buckets) + 1] += np.uint64(
            max(int(value * SUM_SCALE + 0.5), 0)
        )

    def store(self, name: str, value: int) -> None:
        """Overwrite a counter/gauge word (the housekeeping-flush path:
        the stage's local count is the source of truth)."""
        d, off = self._off[name]
        self.words[off] = np.uint64(int(value) & _MASK64)

    def store_hist(self, name: str, counts, sum_value: float) -> None:
        """Overwrite a histogram's words from local (counts, sum)."""
        d, off = self._off[name]
        n = len(d.buckets) + 1
        self.words[off : off + n] = counts
        self.words[off + n] = np.uint64(
            max(int(sum_value * SUM_SCALE + 0.5), 0) & _MASK64
        )

    # -- readers ------------------------------------------------------------

    def get(self, name: str) -> int:
        d, off = self._off[name]
        if d.kind == HISTOGRAM:
            raise TypeError("use hist() for histograms")
        return int(self.words[off])

    def hist_sum(self, name: str) -> float:
        """A histogram's sum alone (one word: no walk over its buckets)."""
        d, off = self._off[name]
        return int(self.words[off + len(d.buckets) + 1]) / SUM_SCALE

    def hist(self, name: str) -> dict:
        d, off = self._off[name]
        counts = [int(self.words[off + i]) for i in range(len(d.buckets) + 1)]
        return {
            "buckets": list(d.buckets),
            "counts": counts,
            "sum": self.hist_sum(name),
            "count": sum(counts),
        }

    def quantile(self, name: str, q: float) -> float:
        """Upper-edge estimate of the q-quantile from bucket counts."""
        return hist_quantile(self.hist(name), q)


def latency_row(reg: "MetricsRegistry | None") -> dict:
    """The monitor/snapshot latency fields from a stage registry: p50/p99
    of frag_latency_ns in ms, or Nones when the plane is not joined."""
    out = {"lat_p50_ms": None, "lat_p99_ms": None}
    if reg is not None and "frag_latency_ns" in reg._off:
        h = reg.hist("frag_latency_ns")
        if h["count"]:
            out["lat_p50_ms"] = hist_quantile(h, 0.5) / 1e6
            out["lat_p99_ms"] = hist_quantile(h, 0.99) / 1e6
    return out


def latency_row_merged(regs: list) -> dict:
    """latency_row over SEVERAL shard registries of one logical stage:
    bucket counts merge (histograms of the same schema sum exactly), so
    the quantiles are the logical stage's true cross-shard estimates,
    not any single shard's."""
    merged = None
    for reg in regs:
        if reg is None or "frag_latency_ns" not in reg._off:
            continue
        h = reg.hist("frag_latency_ns")
        if merged is None:
            merged = h
        else:
            merged["counts"] = [a + b for a, b in
                                zip(merged["counts"], h["counts"])]
            merged["count"] += h["count"]
            merged["sum"] += h["sum"]
    out = {"lat_p50_ms": None, "lat_p99_ms": None}
    if merged and merged["count"]:
        out["lat_p50_ms"] = hist_quantile(merged, 0.5) / 1e6
        out["lat_p99_ms"] = hist_quantile(merged, 0.99) / 1e6
    return out


def nsweep_phase_row(regs: list) -> dict:
    """Per-phase p50 sweep durations in us, merged across the shard
    registries of one logical stage — the monitor's sweep-phase column
    (ISSUE 20 tentpole b).  Phases with no crossings map to None."""
    out = {}
    for ph in NSWEEP_PHASES:
        name = f"nsweep_{ph}_ns"
        merged = None
        for reg in regs:
            if reg is None or name not in reg._off:
                continue
            h = reg.hist(name)
            if merged is None:
                merged = h
            else:
                merged["counts"] = [a + b for a, b in
                                    zip(merged["counts"], h["counts"])]
                merged["count"] += h["count"]
        v = None
        if merged and merged["count"]:
            q = hist_quantile(merged, 0.5)
            v = None if q == float("inf") else q / 1e3
        out[ph] = v
    return out


def format_phase_cell(row: dict) -> str:
    """Compact sweep-phase cell: 'd12/c48/a3/p7' (p50 us per phase,
    phases without crossings omitted), '-' when the stage has no native
    sweep client."""
    parts = [f"{ph[0]}{row[ph]:.0f}" for ph in NSWEEP_PHASES
             if row.get(ph) is not None]
    return "/".join(parts) if parts else "-"


def format_latency_ms(v: float | None) -> str:
    """One cell of the monitor's latency columns: '-' when the metrics
    plane is not joined, '>max' when the quantile overflowed the last
    bucket (the +Inf estimate carries no magnitude)."""
    if v is None:
        return "-"
    if v == float("inf"):
        return ">max"
    return f"{v:,.1f}ms"


def hist_quantile(h: dict, q: float) -> float:
    """Upper-edge q-quantile estimate over a hist() dict."""
    total = h["count"]
    if total == 0:
        return 0.0
    target = q * total
    run = 0
    for edge, c in zip(h["buckets"] + [float("inf")], h["counts"]):
        run += c
        if run >= target:
            return edge
    return float("inf")


# -- Prometheus text exposition ----------------------------------------------


def _escape_label(v: str) -> str:
    """Label-value escaping per the Prometheus text format: backslash,
    double-quote and line-feed must be escaped or a hostile stage name
    injects fake series into the scrape."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _escape_help(v: str) -> str:
    """HELP-text escaping: backslash and line-feed only (spec)."""
    return v.replace("\\", "\\\\").replace("\n", "\\n")


def render_prometheus(stages: dict[str, MetricsRegistry],
                      labels: dict[str, dict] | None = None) -> str:
    """Text exposition over {stage_name: registry} (fd_metric.c's endpoint).

    labels: optional per-stage extra label sets (the sharded-serving
    plane's {"stage": <logical>, "shard": <i>} relabeling) — when a stage
    has an entry, its series carry THOSE labels (the "stage" key replaces
    the physical name), so N shards of one logical stage surface as one
    metric family distinguished by the shard label and aggregate with a
    plain `sum by (stage)` instead of colliding on (or fragmenting over)
    physical stage names."""
    seen_help: set[str] = set()
    lines: list[str] = []
    for stage, reg in stages.items():
        lset = {"stage": stage}
        if labels and stage in labels:
            lset.update({k: v for k, v in labels[stage].items()
                         if v is not None})
        base = ",".join(
            f'{k}="{_escape_label(str(v))}"' for k, v in lset.items()
        )
        label = "{" + base + "}"
        for d in reg.schema.defs:
            if d.name not in seen_help:
                seen_help.add(d.name)
                if d.help:
                    lines.append(f"# HELP {d.name} {_escape_help(d.help)}")
                lines.append(f"# TYPE {d.name} {d.kind}")
            if d.kind == HISTOGRAM:
                h = reg.hist(d.name)
                run = 0
                for edge, c in zip(h["buckets"], h["counts"]):
                    run += c
                    lines.append(
                        f'{d.name}_bucket{{{base},le="{edge}"}} {run}'
                    )
                lines.append(
                    f'{d.name}_bucket{{{base},le="+Inf"}} {h["count"]}'
                )
                lines.append(f"{d.name}_sum{label} {h['sum']}")
                lines.append(f"{d.name}_count{label} {h['count']}")
            else:
                lines.append(f"{d.name}{label} {reg.get(d.name)}")
    return "\n".join(lines) + "\n"


class MetricsServer:
    """The metric-tile endpoint: serves the Prometheus text exposition
    over HTTP (run/tiles/fd_metric.c:1-3).  `stages` may be swapped or
    mutated live; every scrape renders the current registries."""

    def __init__(self, stages: dict[str, MetricsRegistry], *,
                 host="127.0.0.1", port=0, labels: dict | None = None,
                 resolver=None):
        from firedancer_tpu.protocol import http as H

        self.stages = stages
        self.labels = labels
        # resolver: optional () -> (stages, labels), consulted per scrape
        # so a scraper over an externally-attached session re-resolves
        # the registry set instead of serving a boot-time snapshot that
        # goes stale across an in-place restart (ISSUE 20 satellite 2)
        self.resolver = resolver

        def handler(req, _body):
            if req.method != "GET":
                return H.build_response(405, b"GET only\n")
            if req.path not in ("/metrics", "/"):
                return H.build_response(404, b"not found\n")
            if self.resolver is not None:
                try:
                    self.stages, self.labels = self.resolver()
                except (RuntimeError, OSError):
                    pass  # keep serving the last good registry set
            # snapshot the dict: a registrar may add stages while a
            # scrape renders (this runs on a per-connection thread)
            body = render_prometheus(dict(self.stages),
                                     labels=self.labels).encode()
            return H.build_response(
                200, body,
                content_type="text/plain; version=0.0.4; charset=utf-8",
            )

        self._srv = H.MiniServer(handler, host=host, port=port)

    @property
    def addr(self):
        return self._srv.addr

    def close(self):
        self._srv.close()


# -- flight recorder ----------------------------------------------------------

# event ids (stable wire values: dumps outlive the writing process)
EV_BOOT = 1            # stage constructed
EV_RUN = 2             # run loop entered
EV_HALT = 3            # clean halt observed
EV_FAIL = 4            # stage raised / signaled FAIL
EV_HOUSEKEEPING = 5    # housekeeping pass (arg = iteration)
EV_BACKPRESSURE_ON = 6   # an output ran out of credits (arg = iteration)
EV_BACKPRESSURE_OFF = 7  # credits recovered (arg = iterations spent stalled)
EV_BATCH_SUBMIT = 8    # device/work batch submitted (arg = elements)
EV_BATCH_COMPLETE = 9  # device/work batch drained (arg = elements)
EV_NATIVE_PUNT = 10    # native fast lane punted to the fallback (arg = count)
EV_OVERRUN = 11        # input overrun detected (arg = input index)
EV_MICROBLOCK = 12     # microblock committed/emitted (arg = txn count)
EV_SLOT_SEAL = 13      # slot sealed at its deadline (arg = slot)
EV_SLOT_MISSED = 14    # slot boundary passed unsealed — MISSED (arg = slot)
EV_SLOT_ROLL = 15      # slot boundary observed by a non-poh stage (arg = slot)
EV_SLOT_SHED = 16      # pack shed pending work at the deadline (arg = txns)
EV_RESTART = 17        # stage resumed in place after a supervisor respawn
EV_NSWEEP_DRAIN = 18   # native sweep crossing drained (arg = frags; C-side,
                       # decimated — every FDM_FLIGHT_DECIMATE crossings)
EV_NSWEEP_PUBLISH = 19  # native sweep crossing published (arg = frags; C-side)
EV_BATCH_STALL = 20    # one thread-blocking phase of one device batch took
                       # BATCH_STALL_NS or more (arg = batch_stall_arg)
EV_FUNK_LOCK_WAIT = 21  # a bank tile waited over 100 us for the account
                        # store's lock (arg = funk_lock_wait_arg: the
                        # holder's writer id and the microseconds)

EVENT_NAMES = {
    EV_BOOT: "boot",
    EV_RUN: "run",
    EV_HALT: "halt",
    EV_FAIL: "fail",
    EV_HOUSEKEEPING: "housekeeping",
    EV_BACKPRESSURE_ON: "backpressure_on",
    EV_BACKPRESSURE_OFF: "backpressure_off",
    EV_BATCH_SUBMIT: "batch_submit",
    EV_BATCH_COMPLETE: "batch_complete",
    EV_NATIVE_PUNT: "native_punt",
    EV_OVERRUN: "overrun",
    EV_MICROBLOCK: "microblock",
    EV_SLOT_SEAL: "slot_seal",
    EV_SLOT_MISSED: "slot_missed",
    EV_SLOT_ROLL: "slot_roll",
    EV_SLOT_SHED: "slot_shed",
    EV_RESTART: "restart",
    EV_NSWEEP_DRAIN: "nsweep_drain",
    EV_NSWEEP_PUBLISH: "nsweep_publish",
    EV_BATCH_STALL: "batch_stall",
    EV_FUNK_LOCK_WAIT: "funk_lock_wait",
}


def funk_lock_wait_arg(holder: int, wait_ns: int) -> int:
    """EV_FUNK_LOCK_WAIT's arg: the holder's writer id (a bank tile's
    index + 1 in a process topology) in the top 16 bits of 48, the wait
    in microseconds under it."""
    return ((holder & 0xFFFF) << 32) | min(wait_ns // 1000, 0xFFFFFFFF)


def funk_lock_wait_fields(arg: int) -> dict:
    return {"holder": arg >> 32, "us": arg & 0xFFFFFFFF}

# The life of a device batch, in order.  Phase k ends where phase k+1
# begins; the verify stage adds each phase's nanoseconds to the counter
# `batch_<phase>_ns` when the phase ENDS (runtime/verify.py).
BATCH_PHASES = ("open", "sealed_wait", "h2d", "launch", "inflight", "reap",
                "publish")
# the phases in which the stage's one thread is inside a call
BATCH_BLOCKING_PHASES = frozenset(
    BATCH_PHASES.index(p) for p in ("h2d", "launch", "reap", "publish"))
BATCH_STALL_NS = 100_000_000
# What closed a batch, by id: it filled; its deadline passed with the
# in-flight window open to it (nothing in flight and no backlog in
# front, or room in the window after a full batch had to wait for its
# place: the thread leads the chip); the window held it open past its
# deadline and a reap sealed it.  The verify stage counts each dispatched batch in
# `batch_close_<why>`, so the three add up to `batches`.
BATCH_CLOSES = ("full", "deadline", "window")
BATCH_CLOSE_COUNTERS = tuple(f"batch_close_{c}" for c in BATCH_CLOSES)
# dispatches made while another batch was in flight: how often the
# window's second place (full batches, the batch sealed past its
# deadline after a full one had to wait for its place, and flush()) is
# used
BATCH_QUEUED_BEHIND = "batch_queued_behind"
# batches kept open past their deadline, with nothing in flight, because
# the intake was backlogged (its last sweep took the whole burst): they
# fill instead of going out part empty.  Once a batch, so over `batches`
# it is how often that rule engages
BATCH_HELD_BACKLOGGED = "batch_held_backlogged"
# lanes whose verdict nobody used: those a batch sealed for want of room
# left empty (the next transaction's signatures did not fit, and a
# transaction's elements land in one batch), and those of the
# transactions that failed whole
BATCH_FIT_PAD_LANES = "batch_fit_pad_lanes"
VERIFY_FAIL_ELEMS = "verify_fail_elems"
# gauge: 128 where the program the stage dispatches folds its batch to
# (batch // 128, 128) (ops/sigverify.fold_batch), 0 where it does not
KERNEL_FOLD_LANES = "kernel_fold_lanes"
# The thread's ledger (runtime/stage.py run_once): every call of a
# stage's loop is charged whole to one regime — it did work, it found
# nothing to do, it could do nothing because a ring behind it was full
# while a ring in front held a frag (backpressure), or (taken out of the
# call it ran in) housekeeping.  ns and, for the first three, calls
LOOP_COUNTERS = ("loop_work_ns", "loop_work_n", "loop_poll_ns",
                 "loop_poll_n", "loop_backp_ns", "loop_backp_n",
                 "loop_hk_ns")
LOOP_REGIMES = ("work_ns", "poll_ns", "backp_ns", "hk_ns")
# When the chip had nothing of a verify stage's to run (runtime/verify.py
# _phase_end): from the loop's first sight of a finished batch with no
# other in flight to the end of the next dispatch's launch, and of that
# the stage's own blocking calls and the time the thread was in other
# stages.  Added when an interval ends; the rest of an interval is the
# stage waiting inside its own loop (intake, polls, the close rule)
CHIP_EMPTY_COUNTERS = ("chip_empty_ns", "chip_empty_n",
                       "chip_empty_call_ns", "chip_empty_away_ns")


def loop_shares(loop: dict, since: dict | None = None) -> dict | None:
    """{"busy_pct", "backp_pct", "poll_pct"}: what of a stage's loop
    time went to calls that did work, to calls held by backpressure and
    to empty polls, from a `loop_row` (less an earlier one: the
    monitor's two samples); None where the ledger saw no time."""
    d = {k: loop[k] - (since[k] if since else 0) for k in LOOP_REGIMES}
    total = sum(d.values())
    if total <= 0:
        return None
    return {"busy_pct": 100.0 * d["work_ns"] / total,
            "backp_pct": 100.0 * d["backp_ns"] / total,
            "poll_pct": 100.0 * d["poll_ns"] / total}


def loop_row(srcs: list) -> dict | None:
    """LOOP_REGIMES summed over the shards of one logical stage, each
    its registry (the monitor) or a dict of its metrics (slotreport):
    the monitor's busy% and backp% are shares of a sample's delta of
    the four.  None where no source has the ledger; a source from
    before the backpressure regime reads 0 there."""
    rows = []
    for src in srcs:
        if isinstance(src, MetricsRegistry):
            src = {n: src.get(n) for n in LOOP_COUNTERS if n in src._off}
        if src and "loop_work_ns" in src:
            rows.append(src)
    if not rows:
        return None
    return {k: sum(int(r.get(f"loop_{k}") or 0) for r in rows)
            for k in LOOP_REGIMES}


def chip_empty_row(src) -> dict | None:
    """{"ns", "n", "call_ns", "away_ns"} of a verify stage's
    CHIP_EMPTY_COUNTERS, from its registry (the monitor) or a dict of
    its metrics (slotreport); None where the stage has none."""
    if src is None:
        return None
    if isinstance(src, MetricsRegistry):
        src = {n: src.get(n) for n in CHIP_EMPTY_COUNTERS if n in src._off}
    if CHIP_EMPTY_COUNTERS[0] not in src:
        return None
    return {n[len("chip_empty_"):]: int(src.get(n) or 0)
            for n in CHIP_EMPTY_COUNTERS}


def format_chip_empty(row: dict, prev: dict | None, dt_s: float) -> str:
    """'chip_empty=71.2% (away=33% call=29%)': the share of the `dt_s`
    between two samples' chip_empty_row in which the chip had nothing
    of the stage's to run, and of that the shares the thread spent in
    other stages and in the stage's own blocking calls; '-' where there
    is no earlier sample, or no interval ended since."""
    if not prev or dt_s <= 0:
        return "chip_empty=- (away=- call=-)"
    ns, away, call = (row[k] - prev[k] for k in ("ns", "away_ns", "call_ns"))
    if ns <= 0:
        return "chip_empty=0.0% (away=- call=-)"
    return (f"chip_empty={100.0 * ns / (dt_s * 1e9):.1f}%"
            f" (away={100.0 * away / ns:.0f}% call={100.0 * call / ns:.0f}%)")


def batch_close_row(regs: list) -> dict | None:
    """{why: batches closed that way, "queued_behind":
    batch_queued_behind, "held_backlogged": batch_held_backlogged,
    "fit_pad_lanes": batch_fit_pad_lanes,
    "fail_elems": verify_fail_elems, "stalls": batch_stalls} summed
    over the shard registries of one logical stage, and "fold_lanes":
    the gauge kernel_fold_lanes (the same on every shard), for the
    monitor and slotreport; None where the stage is not a verify
    stage."""
    regs = [r for r in regs
            if r is not None and BATCH_CLOSE_COUNTERS[0] in r._off]
    if not regs:
        return None
    names = zip(BATCH_CLOSES + ("queued_behind", "held_backlogged",
                                "fit_pad_lanes", "fail_elems", "stalls"),
                BATCH_CLOSE_COUNTERS + (BATCH_QUEUED_BEHIND,
                                        BATCH_HELD_BACKLOGGED,
                                        BATCH_FIT_PAD_LANES,
                                        VERIFY_FAIL_ELEMS, "batch_stalls"))
    row = {k: sum(r.get(n) for r in regs) for k, n in names}
    row["fold_lanes"] = max(r.get(KERNEL_FOLD_LANES) for r in regs)
    return row


def intake_row(src) -> dict | None:
    """{"frags": frags_in, "crossings": nsweep_crossings} of a stage
    whose intake is a native sweep, from its registry (the monitor) or
    a dict of its metrics (slotreport); None where it has no such
    sweep.  Over two samples, frags a crossing: how much one sweep
    takes (a verify stage's whole burst under a backlog, 0-2 where it
    is paced)."""
    if src is None:
        return None
    if isinstance(src, MetricsRegistry):
        src = {n: src.get(n) for n in ("frags_in", "nsweep_crossings")
               if n in src._off}
    if "nsweep_crossings" not in src:
        return None
    return {"frags": int(src.get("frags_in") or 0),
            "crossings": int(src.get("nsweep_crossings") or 0)}


def format_frags_per_crossing(row: dict, prev: dict | None) -> str:
    """'frags/crossing=255.8' between two samples' intake_row (since
    boot where there is no earlier one); '-' where no crossing took a
    frag in between."""
    prev = prev or {"frags": 0, "crossings": 0}
    n = row["crossings"] - prev["crossings"]
    if n <= 0:
        return "frags/crossing=-"
    return f"frags/crossing={(row['frags'] - prev['frags']) / n:.1f}"


# What a dedup stage's tag cache dropped (counter -> the key the monitor
# and slotreport show it by): transactions, and the signatures they
# carried, which the verify stage in front of it spent lanes on
DEDUP_COUNTERS = {"dedup_dup": "dup", "dedup_dup_sigs": "dup_sigs"}


def dedup_row(src) -> dict | None:
    """{key: count} of DEDUP_COUNTERS, from a stage's registry (the
    monitor) or a dict of its metrics (slotreport); None where the
    stage is not a dedup stage (verify and pack count `dedup_dup` too,
    and have no `dedup_dup_sigs`)."""
    if src is None:
        return None
    if isinstance(src, MetricsRegistry):
        src = {n: src.get(n) for n in DEDUP_COUNTERS if n in src._off}
    if "dedup_dup_sigs" not in src:
        return None
    return {k: int(src.get(n) or 0) for n, k in DEDUP_COUNTERS.items()}


# What the replay verify stage counts beside a verify stage's own (the
# monitor's `replay` line and slotreport's block show them by these
# names; runtime/replay_verify.py has what each means): entry batches
# and what they held, the slots' verdicts, what was skipped of dead
# slots and the lanes spent on it, and the two spans (cumulative ns)
REPLAY_COUNTERS = (
    "entry_batches_in", "entries_in", "txn_in", "elems_in",
    "slots_live", "slots_dead_sig", "slots_dead_poh", "slots_dead_parse",
    "dead_slot_txn_skipped", "dead_slot_lanes_spent", "poh_hashes",
    "poh_check_ns", "entry_unpack_ns", "entry_batches_out",
    "entry_txn_out", "entry_txn_rejected",
)


def replay_row(src) -> dict | None:
    """{name: count} of REPLAY_COUNTERS, from a stage's registry (the
    monitor) or a dict of its metrics (slotreport); None where the
    stage is no replay verify stage (a verify stage counts `txn_in`
    too, and has no `entry_batches_in`)."""
    if src is None:
        return None
    if isinstance(src, MetricsRegistry):
        src = {n: src.get(n) for n in REPLAY_COUNTERS if n in src._off}
    if "entry_batches_in" not in src:
        return None
    return {n: int(src.get(n) or 0) for n in REPLAY_COUNTERS}


# What the front door counts (the monitor and slotreport show them by
# these names): the quic tile's datagrams, punts from the C
# lane to Python, connections, the reassembler's outcomes, whole
# transactions that waited for verify's ring and the stream credit
# returned, the datagrams it sent that carry nothing but an ACK; a
# sender tile's transactions, datagrams, chunks sent again, streams
# acknowledged and calls held by the peer's credit
FRONT_COUNTERS = (
    # the quic tile
    "dgram_rx", "dgram_rx_bytes", "net_punts", "handshakes_done",
    "conn_active", "reasm_published", "reasm_multi_chunk", "reasm_evicted",
    "reasm_oversz", "reasm_cancelled", "reasm_dup_stream",
    "txn_held_for_credit", "streams_granted", "ack_tx",
    # a sender tile (dgram_rx is both's)
    "txn_tx", "dgram_tx", "dgram_rtx", "streams_acked",
    "send_blocked_credit",
)


def front_row(src) -> dict | None:
    """{name: count} of FRONT_COUNTERS' counters that the stage has,
    from its registry (the monitor) or a dict of its metrics
    (slotreport); None where the stage is neither a quic tile nor a
    sender tile.  A quic tile's row ends with the two ratios that say
    whether it amortises its Python (docs/OPERATIONS.md): `dgram/
    crossing` = dgram_rx / nsweep_crossings, datagrams a crossing into
    C, and `ack/dgram` = ack_tx / dgram_rx."""
    if src is None:
        return None
    names = FRONT_COUNTERS + ("nsweep_crossings",)
    if isinstance(src, MetricsRegistry):
        have = {n: src.get(n) for n in names if n in src._off}
    else:
        have = {n: src[n] for n in names if n in src}
    if "reasm_published" not in have and "txn_tx" not in have:
        return None
    row = {n: int(v or 0) for n, v in have.items()}
    crossings = row.pop("nsweep_crossings", 0)
    rx = row.get("dgram_rx", 0)
    if "ack_tx" in row and rx:
        if crossings:
            row["dgram/crossing"] = round(rx / crossings, 2)
        row["ack/dgram"] = round(row["ack_tx"] / rx, 3)
    return row


def mesh_row(src) -> dict | None:
    """{"devices": n, "shard_elems": [useful lanes dispatched to chip
    i, ...]} of a verify stage over a mesh of n > 1 devices, from its
    registry (the monitor) or a dict of its metrics (slotreport); None
    where the stage has one device or is not a verify stage."""
    if src is None:
        return None
    if isinstance(src, MetricsRegistry):
        reg = src
        src = {n: reg.get(n) for n in reg._off
               if n == "mesh_devices" or n.startswith("shard_elems_s")}
    n = int(src.get("mesh_devices") or 0)
    if n <= 1:
        return None
    return {"devices": n,
            "shard_elems": [int(src.get(f"shard_elems_s{i}") or 0)
                            for i in range(n)]}


# What the pack stage and the banks count of votes and of account
# conflicts (counter -> the key the monitor and slotreport show it by)
VOTE_COUNTERS = {
    "txn_scheduled_votes": "scheduled",          # pack
    "txn_dropped_votes": "dropped",
    "votes_dropped_while_regular_pending": "dropped_while_regular_pending",
    "conflict_skips": "conflict_skips",
    "txn_exec_votes": "exec",                    # bank
    "txn_exec_failed_votes": "exec_failed",
}


def vote_row(src) -> dict | None:
    """{key: count} of VOTE_COUNTERS' counters that the stage has, from
    its registry (the monitor) or a dict of its metrics (slotreport);
    None where the stage is neither the pack stage nor a bank."""
    if src is None:
        return None
    if isinstance(src, MetricsRegistry):
        have = {n: src.get(n) for n in VOTE_COUNTERS if n in src._off}
    else:
        have = {n: src[n] for n in VOTE_COUNTERS if n in src}
    return {VOTE_COUNTERS[n]: int(v or 0) for n, v in have.items()} or None


# A bank tile's use of the account store it shares with the other bank
# tiles (native/fd_funk.cpp's lock, native/fd_bank.cpp's read-through),
# and what pack gave each bank (counter -> the key shown)
FUNK_COUNTERS = {
    "funk_lock_acquires": "lock_holds",
    "funk_lock_contended": "contended",
    "funk_lock_wait_ns": "wait_ns",
    "session_refreshed": "session_refreshed",
}


def funk_row(src) -> dict | None:
    """{key: count} of FUNK_COUNTERS for a bank stage, and for the pack
    stage its microblocks a bank (`mb_b<i>`) and `bank_idle_polls`, from
    the stage's registry (the monitor) or a dict of its metrics
    (slotreport); None for any other stage."""
    if src is None:
        return None
    names = src._off if isinstance(src, MetricsRegistry) else src
    get = src.get
    out = {key: int(get(n) or 0) for n, key in FUNK_COUNTERS.items()
           if n in names}
    banks = sorted((n for n in names if n.startswith("mb_scheduled_b")),
                   key=lambda n: int(n[len("mb_scheduled_b"):]))
    for n in banks:
        out["mb_" + n[len("mb_scheduled_"):]] = int(get(n) or 0)
    if banks:
        out["bank_idle_polls"] = int(get("bank_idle_polls") or 0)
    return out or None


def format_funk(rows: dict[str, dict]) -> str | None:
    """The monitor's `funk` line: the bank tiles' one account store.
    `rows`: stage -> its funk_row.  A contended share that is not
    small says the tiles wait for each other at the store's lock, and
    `session_refreshed` how many account values a tile's session took
    from the segment (every account a microblock names, where other
    tiles write the store too)."""
    banks = {n: r for n, r in rows.items() if r and "lock_holds" in r}
    if not banks:
        return None
    holds = sum(r["lock_holds"] for r in banks.values())
    cont = sum(r["contended"] for r in banks.values())
    line = (f"funk: {len(banks)} bank tile(s) over one store  lock "
            f"holds={holds:,} contended={cont:,} "
            f"({100.0 * cont / holds if holds else 0.0:.2f}%) wait_ms="
            f"{sum(r['wait_ns'] for r in banks.values()) / 1e6:,.1f}  "
            "session_refreshed " + " ".join(
                f"{n}={r['session_refreshed']:,}" for n, r in banks.items()))
    for r in rows.values():
        if r and "bank_idle_polls" in r:
            line += "  pack: microblocks " + " ".join(
                f"{k[3:]}={v:,}" for k, v in r.items()
                if k.startswith("mb_")) \
                + f" bank_idle_polls={r['bank_idle_polls']:,}"
    return line


def batch_stall_arg(phase: int, ns: int) -> int:
    """EV_BATCH_STALL's arg: phase id in the high half, whole ms below."""
    return (phase << 32) | min(ns // 1_000_000, 0xFFFFFFFF)


def batch_stall_fields(arg: int) -> dict:
    phase = arg >> 32
    name = BATCH_PHASES[phase] if phase < len(BATCH_PHASES) else str(phase)
    return {"phase": name, "ms": arg & 0xFFFFFFFF}

FLIGHT_DEPTH = 512  # records per stage ring (fixed, small: ~12 KiB)


class FlightRecorder:
    """Fixed ring of (ts_ns, event, arg) u64 triples + a write-count word.

    Records are written STRAIGHT to the backing words (no lazy flush):
    the whole point is surviving the writer's crash, so the last records
    before an abort must already be in shared memory.  Events are rare
    (lifecycle, backpressure transitions, batch boundaries), so the ~µs
    numpy store cost never rides the per-frag path.
    """

    REC_WORDS = 3

    def __init__(self, capacity: int = FLIGHT_DEPTH,
                 words: np.ndarray | None = None):
        if words is None:
            words = np.zeros(1 + capacity * self.REC_WORDS, dtype=np.uint64)
        else:
            capacity = (len(words) - 1) // self.REC_WORDS
        if capacity <= 0:
            raise ValueError("flight recorder needs capacity >= 1")
        self.capacity = capacity
        self.words = words

    @classmethod
    def words_needed(cls, capacity: int) -> int:
        return 1 + capacity * cls.REC_WORDS

    def record(self, event: int, arg: int = 0, ts: int | None = None) -> None:
        if ts is None:
            import time

            ts = time.monotonic_ns()
        w = self.words
        n = int(w[0])
        i = 1 + (n % self.capacity) * self.REC_WORDS
        w[i] = np.uint64(ts & _MASK64)
        w[i + 1] = np.uint64(event & _MASK64)
        w[i + 2] = np.uint64(int(arg) & _MASK64)
        w[0] = np.uint64(n + 1)

    def records(self) -> list[tuple[int, int, int]]:
        """Oldest-first [(ts_ns, event, arg)]; at most `capacity` entries."""
        w = self.words
        n = int(w[0])
        take = min(n, self.capacity)
        out = []
        for k in range(n - take, n):
            i = 1 + (k % self.capacity) * self.REC_WORDS
            out.append((int(w[i]), int(w[i + 1]), int(w[i + 2])))
        return out

    def replay_into(self, other: "FlightRecorder") -> None:
        """Copy this ring's records (preserving timestamps) into `other` —
        the attach path moves pre-shm boot events into the shared ring."""
        for ts, ev, arg in self.records():
            other.record(ev, arg, ts=ts)


# -- the per-stage shm segment ------------------------------------------------

SEG_MAGIC = 0xFD7B0F17  # arbitrary, stable
_SEG_HDR_WORDS = 4  # magic, metric word count, recorder capacity, reserved


def metrics_segment_words(schema: MetricsSchema,
                          recorder_depth: int = FLIGHT_DEPTH) -> int:
    return (_SEG_HDR_WORDS + schema.footprint()
            + FlightRecorder.words_needed(recorder_depth))


def metrics_segment_footprint(schema: MetricsSchema,
                              recorder_depth: int = FLIGHT_DEPTH) -> int:
    return metrics_segment_words(schema, recorder_depth) * 8


def metrics_segment_init(buf, schema: MetricsSchema,
                         recorder_depth: int = FLIGHT_DEPTH):
    """Lay out a fresh segment over `buf` (shm or bytes-like); returns
    (registry, recorder).  Called once by the CREATOR (topo.launch)."""
    nw = metrics_segment_words(schema, recorder_depth)
    arr = np.frombuffer(buf, dtype=np.uint64, count=nw)
    arr[0] = np.uint64(SEG_MAGIC)
    arr[1] = np.uint64(schema.footprint())
    arr[2] = np.uint64(recorder_depth)
    arr[3] = np.uint64(0)
    return _segment_views(arr, schema)


def metrics_segment_attach(buf, schema: MetricsSchema):
    """Join an existing segment (child stage or read-only monitor)."""
    hdr = np.frombuffer(buf, dtype=np.uint64, count=_SEG_HDR_WORDS)
    if int(hdr[0]) != SEG_MAGIC:
        raise ValueError("not a metrics segment (bad magic)")
    n_met = int(hdr[1])
    if n_met != schema.footprint():
        raise ValueError(
            f"segment metric words ({n_met}) != schema footprint "
            f"({schema.footprint()}): schema drift between writer and reader"
        )
    depth = int(hdr[2])
    nw = _SEG_HDR_WORDS + n_met + FlightRecorder.words_needed(depth)
    arr = np.frombuffer(buf, dtype=np.uint64, count=nw)
    return _segment_views(arr, schema)


def _segment_views(arr: np.ndarray, schema: MetricsSchema):
    n_met = int(arr[1])
    a = _SEG_HDR_WORDS
    b = a + n_met
    reg = MetricsRegistry(schema, buf=arr[a:b])
    rec = FlightRecorder(words=arr[b:])
    # retain the whole-segment view: the native metrics plane
    # (runtime/native_metrics.py) derives the segment base address from
    # it so fdm_plane_attach can re-validate the header magic in C
    reg._seg = arr
    return reg, rec


# -- flight dumps + Chrome trace export ---------------------------------------


def registry_obj(reg: MetricsRegistry) -> dict:
    """Structured (JSON-ready) snapshot of one registry: counters/gauges
    as ints, histograms as hist() dicts.  The slotreport --dump path
    reads THIS (not the Prometheus text) out of flight dumps."""
    out: dict = {}
    for d in reg.schema.defs:
        out[d.name] = reg.hist(d.name) if d.kind == HISTOGRAM \
            else reg.get(d.name)
    return out


def flight_dump_obj(uid: str, stages: dict, *, failed: str | None = None,
                    reason: str = "") -> dict:
    """Build the crash-dump object: per-stage flight records + a final
    Prometheus snapshot.  `stages`: name -> (registry|None, recorder)."""
    obj = {
        "uid": uid,
        "failed": failed,
        "reason": reason,
        "stages": {},
    }
    regs = {}
    for name, (reg, rec) in stages.items():
        obj["stages"][name] = {
            "records": [list(r) for r in rec.records()] if rec else [],
        }
        if reg is not None:
            regs[name] = reg
            # structured snapshot per stage so post-mortem tooling
            # (slotreport --dump) never has to re-parse Prometheus text
            obj["stages"][name]["metrics"] = registry_obj(reg)
    if regs:
        obj["metrics"] = render_prometheus(regs)
    return obj


def flight_to_chrome_trace(dump: dict) -> dict:
    """Chrome trace-event JSON from a flight dump: one thread per stage,
    instant events per record, ASYNC b/e span pairs for batch
    submit/complete.  Async (not B/E duration) events because batches
    pipeline: verify keeps max_inflight batches going and completes them
    FIFO, while Chrome pairs B/E as a LIFO stack — duration events would
    swap overlapping spans.  Async ids pair submit k with the k-th
    completion (the stage's own FIFO drain order)."""
    events = []
    stages = sorted(dump.get("stages", {}))
    for tid, name in enumerate(stages):
        events.append({
            "name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
            "args": {"name": name},
        })
        open_ids: list[int] = []  # FIFO of submitted-batch ids
        batch_seq = 0
        for ts, ev, arg in dump["stages"][name].get("records", []):
            us = ts / 1e3
            ev_name = EVENT_NAMES.get(ev, f"ev{ev}")
            if ev == EV_BATCH_SUBMIT:
                batch_seq += 1
                bid = f"{name}:{batch_seq}"
                open_ids.append(bid)
                events.append({"name": "batch", "cat": "batch", "ph": "b",
                               "id": bid, "pid": 1, "tid": tid, "ts": us,
                               "args": {"elems": arg}})
            elif ev == EV_BATCH_COMPLETE and open_ids:
                bid = open_ids.pop(0)  # completions drain FIFO
                events.append({"name": "batch", "cat": "batch", "ph": "e",
                               "id": bid, "pid": 1, "tid": tid, "ts": us,
                               "args": {"elems": arg}})
            else:
                args = batch_stall_fields(arg) if ev == EV_BATCH_STALL \
                    else funk_lock_wait_fields(arg) \
                    if ev == EV_FUNK_LOCK_WAIT else {"arg": arg}
                events.append({"name": ev_name, "ph": "i", "pid": 1,
                               "tid": tid, "ts": us, "s": "t",
                               "args": args})
        # close dangling batch spans (crash mid-flight) at the last ts
        # so the JSON stays well-formed for strict importers
        for bid in open_ids:
            events.append({"name": "batch", "cat": "batch", "ph": "e",
                           "id": bid, "pid": 1, "tid": tid,
                           "ts": events[-1]["ts"], "args": {}})
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"uid": dump.get("uid"), "failed": dump.get("failed"),
                      "reason": dump.get("reason", "")},
    }


# The stage-loop schema every pipeline stage shares (the "all tiles" block
# of metrics.xml): frag counters + latency histograms, plus the
# native-sweep block below so any stage a C sweep client drives can be
# instrumented from INSIDE the fdr_sweep crossing without a relaunch.
def stage_schema() -> MetricsSchema:
    s = (
        MetricsSchema()
        .counter("frags_in", "fragments consumed")
        .counter("frags_out", "fragments published")
        .counter("overrun", "input overruns detected")
        .counter("backpressure", "publishes dropped for credits")
        .counter("backpressure_stall", "consume stalls while credit-gated")
        .counter("filtered", "frags dropped by before_frag")
        .counter("restart_dedup",
                 "replayed frags suppressed by the in-place-restart"
                 " publish guard (exactly-once resume)")
        .counter("frag_wait_ns",
                 "sum of tsorig->consume ns over the frags observed into"
                 " frag_latency_ns (the histogram's sum, as a counter)")
        .counter("frag_wait_n", "frags in frag_wait_ns")
        # the thread's ledger: where this stage's share of its thread
        # went (run_once stamps each call whole into one regime)
        .counter("loop_work_ns",
                 "ns inside run_once calls that did work: consumed or"
                 " published a frag, or a hook dispatched, reaped or"
                 " published a device batch, ticked, closed a slot")
        .counter("loop_work_n", "run_once calls that did work")
        .counter("loop_poll_ns",
                 "ns inside run_once calls that found nothing to do")
        .counter("loop_poll_n", "run_once calls that found nothing to do")
        .counter("loop_backp_ns",
                 "ns inside run_once calls that did nothing because a"
                 " ring behind the stage had no credits (or the stage no"
                 " room) while a ring in front held a frag")
        .counter("loop_backp_n", "run_once calls held by backpressure")
        .counter("loop_hk_ns",
                 "ns inside housekeeping passes, taken out of the call"
                 " they ran in")
        .gauge("native_lanes",
               "native lanes of this stage that are armed (rings, a C"
               " sweep client, the native pack / funk), as of the last"
               " time a supervisor asked (Stage.sync_counters)")
        .gauge("native_lanes_off",
               "native lanes of this stage that are NOT armed: it runs"
               " them in Python")
        .histogram(
            "frag_latency_ns",
            exp_buckets(1e3, 1e10, 24),
            "tsorig->processing latency per frag",
        )
        .histogram(
            "out_occupancy",
            (0.0625, 0.125, 0.25, 0.5, 0.75, 0.875, 0.9375, 1.0),
            "out-ring occupancy fraction (1 - credits/depth) sampled at"
            " housekeeping cadence — the autotuner's sizing evidence",
        )
    )
    return add_native_sweep_schema(s)


# Sweep-phase profiler buckets: one crossing drains <= burst frags, so
# phase durations span ~100 ns (idle publish) to ~100 ms (a stalled
# funk apply under chaos).
NSWEEP_PHASE_BUCKETS = exp_buckets(1e2, 1e9, 22)

# The sweep-phase histogram per phase, in crossing order.  The names
# double as the slotreport "sweep_phases" keys.
NSWEEP_PHASES = ("drain", "callback", "apply", "publish")


def sweep_counters(reg: "MetricsRegistry") -> dict:
    """Time inside a stage's non-empty native crossings, from the words
    C writes into its registry: {"sweep_busy_ns": the sums of the four
    nsweep_*_ns phase histograms, "sweep_crossings": nsweep_crossings};
    {} where the schema lacks the native-sweep block."""
    if "nsweep_crossings" not in reg._off:
        return {}
    return {"sweep_busy_ns": int(sum(reg.hist_sum(f"nsweep_{ph}_ns")
                                     for ph in NSWEEP_PHASES)),
            "sweep_crossings": reg.get("nsweep_crossings")}


def registry_counters(reg: "MetricsRegistry") -> dict:
    """A stage's counters and gauges as its registry holds them — what
    `Metrics.counters` is to a reader in the stage's own process, for
    one in another (the registry lives in shm) — with `sweep_counters`
    beside them."""
    out = {d.name: reg.get(d.name) for d in reg.schema.defs
           if d.kind != HISTOGRAM}
    out.update(sweep_counters(reg))
    return out


def add_native_sweep_schema(s: MetricsSchema) -> MetricsSchema:
    """The native-sweep observability block (ISSUE 20 tentpole a+b):
    counters + per-phase histograms written ONLY by C code inside the
    fdr_sweep crossing (native=True: the Python facade neither flushes
    nor resume-copies these words)."""
    s.counter("nsweep_frags",
              "frags consumed inside native sweep crossings", native=True)
    s.counter("nsweep_crossings",
              "non-empty native sweep crossings", native=True)
    for ph in NSWEEP_PHASES:
        s.histogram(
            f"nsweep_{ph}_ns", NSWEEP_PHASE_BUCKETS,
            f"native sweep {ph}-phase duration per crossing (ns)",
            native=True,
        )
    s.histogram(
        "nsweep_lat_ns", exp_buckets(1e3, 1e10, 24),
        "tsorig->consume latency per frag, stamped in-crossing by C"
        " (the native twin of frag_latency_ns)",
        native=True,
    )
    return s


def native_owned_names() -> frozenset:
    """Every metric name a registered native sweep client may write —
    the FD219 double-count set (analysis/ast_rules.py mirrors it)."""
    names = {d.name for d in stage_schema().defs if d.native}
    names.add("nbank_txn_lat_ns")  # bank's per-txn extra (runtime/bank.py)
    return frozenset(names)
