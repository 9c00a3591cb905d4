"""From a profiler trace (.xplane.pb) to numbers: device busy time, the
sigverify program's time per execution, the device operations by self
time, and the longest idle gaps labelled by what the host was doing.

Two steps, so the second can be checked on a small recorded trace kept
as JSON beside the tests: `extract` reads the xplane into plain lists,
`reduce` is arithmetic on those lists.

Time base: every plane of an xplane counts ns from the session start.
The harness wraps the traced window in one TraceAnnotation (`WINDOW`),
which appears on the host plane; its start ties the harness's own
monotonic clock to the trace's.
"""

from __future__ import annotations

import glob
import os

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


OPS_SPAN_NS = 30e6   # ops are kept for this much of the window only


def _short(name: str) -> str:
    """'%multiply_fusion.951 = s32[20,1024]{...} fusion(...)' ->
    'multiply_fusion': the instruction's name without its number, so the
    thousands of instructions of one program add up by kind."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def extract(xplane_path: str, ops_span_ns: float = OPS_SPAN_NS) -> dict:
    """-> {"window": [start, end] | None, "devices": {plane name:
    {"modules": [[name, start, dur]...], "ops": [[kind, start, dur]...],
    "ops_window": [start, end], "last_ns": t}}}, times in ns from the
    session start.

    The sigverify program is ~55,000 device operations of ~100 ns per
    batch, 7 million events a second: the device tracer's buffer holds
    about half a second of that and then stops, and reading every
    operation's (kilobyte-long) name would take minutes.  So every
    program execution is kept (the "XLA Modules" line: two events a
    batch), the operations only for the first `ops_span_ns` of the
    window, and `last_ns` says when the device's capture ended."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = {"window": None, "devices": {}}
    planes = list(pd.planes)
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        out["window"] = [e.start_ns, e.start_ns + e.duration_ns]
    o0 = out["window"][0] if out["window"] else 0.0
    o1 = o0 + ops_span_ns
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = {"modules": [], "ops": [], "ops_window": [o0, o1],
               "last_ns": 0.0}
        for line in plane.lines:
            if line.name == MODULES_LINE:
                dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                  for e in line.events]
                for _, start, dur in dev["modules"]:
                    dev["last_ns"] = max(dev["last_ns"], start + dur)
            elif line.name == OPS_LINE:
                last = 0.0
                for e in line.events:
                    start = e.start_ns
                    if start > last:
                        last = start
                    if o0 <= start < o1:
                        dev["ops"].append(
                            [_short(e.name), start, e.duration_ns])
                dev["last_ns"] = max(dev["last_ns"], last)
        if dev["modules"] or dev["ops"]:
            out["devices"][plane.name] = dev
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(events, w0: float, w1: float):
    for name, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            yield name, a, b


def _leaf_time(events: list[tuple[str, float, float]]) -> dict[str, float]:
    """Self time per op name: an op that contains others (a while loop
    around its body) is charged only what its children do not cover, so
    the names add up to the busy time and nothing counts twice."""
    evs = sorted(events, key=lambda e: (e[1], -(e[2] - e[1])))
    total: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self time so far]
    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_t = stack.pop()
            total[name] = total.get(name, 0.0) + self_t
    for name, a, b in evs:
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return total


def reduce(trace: dict, program: str, spans=None, top: int = 10) -> dict:
    """`program`: a substring of the sigverify program's module name.
    `spans`: [(label, start, end)] host spans in TRACE time (the
    harness's timers around each stage's run_once).  Seconds out.

    The window is the annotation's, cut where the device's capture ended
    (a full trace buffer stops recording; what follows is unknown, not
    idle).  Busy is the union of the intervals in which a program ran
    on the device (module events); `ops_cover` says how much of that the
    operations inside them cover, over the span where operations were
    kept."""
    if trace["window"] is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    if not trace["devices"]:
        raise ValueError("trace has no device plane")
    w0, w1 = trace["window"]
    w1 = min([w1] + [d["last_ns"] for d in trace["devices"].values()])
    if w1 <= w0:
        raise ValueError("the device capture ended before the window began")
    busy_ns = []
    prog_ns = prog_n = 0
    op_time: dict[str, float] = {}
    ops_busy = mods_in_ops_span = 0.0
    gaps: list[tuple[float, float]] = []
    for dev in trace["devices"].values():
        merged = _union([(a, b) for _, a, b in _clip(dev["modules"], w0, w1)])
        busy_ns.append(sum(b - a for a, b in merged))
        for name, start, dur in dev["modules"]:
            # whole executions only: one cut by the window's edge would
            # count as an execution with part of its time
            if program in name and start >= w0 and start + dur <= w1:
                prog_ns += dur
                prog_n += 1
        o0, o1 = dev["ops_window"]
        o0, o1 = max(o0, w0), min(o1, w1)
        ops = list(_clip(dev["ops"], o0, o1))
        for name, t in _leaf_time(ops).items():
            op_time[name] = op_time.get(name, 0.0) + t
        ops_busy += sum(b - a for a, b in _union([(a, b) for _, a, b in ops]))
        mods_in_ops_span += sum(
            b - a for a, b in _union([(a, b) for _, a, b in _clip(
                dev["modules"], o0, o1)]))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                 if edges[k + 1] > edges[k]]
    n_dev = len(trace["devices"])
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        labelled.append([_label(spans, a, b), (b - a) / 1e9])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "program_s": prog_ns / 1e9,
        "program_runs": prog_n,
        "ops_cover": ops_busy / mods_in_ops_span if mods_in_ops_span else None,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": labelled,
        "n_gaps": len(gaps),
        "idle_gap_s": sum(b - a for a, b in gaps) / n_dev / 1e9,
    }


def _label(spans, a: float, b: float) -> str:
    """The host span that covers most of the gap [a, b)."""
    if not spans:
        return "unlabelled"
    cover: dict[str, float] = {}
    for label, s, e in spans:
        o = min(e, b) - max(s, a)
        if o > 0:
            cover[label] = cover.get(label, 0.0) + o
    if not cover:
        return "between_sweeps"
    return max(cover.items(), key=lambda kv: kv[1])[0]
