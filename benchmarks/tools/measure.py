"""Builder's tool: run one cell several times in one call and summarise.

    python3 benchmarks/tools/measure.py --label L --cell NAME --seconds S \
        --seeds 11,12,13 [--sets 2] [--trace 1] [--control allpass] \
        [--rate R] [-- extra args for run.py]

Each run is a fresh process of the benchmark's own command (this parent
never imports JAX, so the child owns the chip).  Every stdout line of
every run goes to chiprun_out/<label>.log; the summary (per metric: the
values, the median, and the spread = (Q3 - Q1) / median by
statistics.quantiles(n=4), per set) goes to stdout and to
chiprun_out/<label>.summary.json.  `--sets 2` repeats the same seeds.
`--rate R` writes R over the cell's traffic file's rate_per_s for this
call only (the rate sweep of the paced cells), and restores the file.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def spread(vals: list[float]) -> float | None:
    if len(vals) < 2:
        return None
    q1, _, q3 = statistics.quantiles(vals, n=4)
    med = statistics.median(vals)
    return (q3 - q1) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", required=True)
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--control", default=None)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("extra", nargs="*")
    a = ap.parse_args()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, a.label + ".log"), "a")
    restore = None
    if a.rate is not None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cell = next(w for w in json.load(f)["workloads"]
                        if w["name"] == a.cell)
        path = os.path.join(BENCH, "traffic", cell["traffic"] + ".json")
        with open(path) as f:
            restore = (path, f.read())
        tr = json.loads(restore[1])
        if "pool_txn_per_s" in tr:
            tr["pool_txn_per_s"] = a.rate * 1.1
        tr["rate_per_s"] = a.rate
        with open(path, "w") as f:
            json.dump(tr, f)
    sets: list[list[dict]] = []
    try:
        for k in range(a.sets):
            rows = []
            for seed in a.seeds.split(","):
                cmd = [sys.executable, os.path.join(BENCH, "run.py"),
                       "--workload", a.cell, "--seed", seed, "--seconds",
                       a.seconds, "--trace", a.trace, *a.extra]
                if a.control:
                    cmd += ["--control", a.control]
                t0 = time.monotonic()
                p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                   text=True)
                wall = time.monotonic() - t0
                log.write(f"# set {k} seed {seed} rc {p.returncode} "
                          f"wall {wall:.1f}s: {' '.join(cmd[1:])}\n")
                log.write(p.stdout)
                if p.returncode != 0:
                    log.write(p.stderr[-4000:] + "\n")
                    sys.stderr.write(p.stderr[-3000:] + "\n")
                log.flush()
                last = p.stdout.strip().splitlines()[-1:] or ["{}"]
                row = json.loads(last[0]) if p.returncode == 0 else {}
                row.update(seed=int(seed), rc=p.returncode, wall_s=wall)
                for ln in p.stdout.splitlines()[:-1]:
                    d = json.loads(ln)
                    for key in ("window", "check", "setup", "trace"):
                        if key in d:
                            row["_" + key] = d
                rows.append(row)
                m = {n: v["value"] for n, v in row.get("metrics", {}).items()}
                print(json.dumps({"set": k, "seed": int(seed),
                                  "rc": p.returncode, "wall_s": round(wall, 1),
                                  "correct": row.get("correct"),
                                  "failed": row.get("failed"),
                                  "attempted": row.get("attempted"),
                                  "metrics": m}), flush=True)
            sets.append(rows)
    finally:
        if restore:
            with open(restore[0], "w") as f:
                f.write(restore[1])
    names = sorted({n for rows in sets for r in rows
                    for n in r.get("metrics", {})})
    summary = {"label": a.label, "cell": a.cell, "seconds": a.seconds,
               "trace": a.trace, "control": a.control, "rate": a.rate,
               "metrics": {}}
    for n in names:
        per_set = []
        for rows in sets:
            vals = [r["metrics"][n]["value"] for r in rows
                    if n in r.get("metrics", {})]
            # the first run of a checkout compiles: its set-up is apart
            per_set.append({"values": vals,
                            "median": statistics.median(vals) if vals else None,
                            "spread": spread(vals)})
        summary["metrics"][n] = per_set
    summary["all_correct"] = all(r.get("correct") for rows in sets
                                 for r in rows)
    summary["runs"] = [[{k: v for k, v in r.items() if k != "metrics"}
                        for r in rows] for rows in sets]
    with open(os.path.join(out_dir, a.label + ".summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=float)
    print(json.dumps({"summary": {n: [{"median": s["median"],
                                       "spread": s["spread"]} for s in v]
                                  for n, v in summary["metrics"].items()},
                      "all_correct": summary["all_correct"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
