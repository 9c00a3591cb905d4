"""One run of one benchmark cell:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The chip first: without a TPU (or with fewer chips than the cell asks
for) nothing is printed on stdout and the exit code is not 0.  The last
line of stdout is the result, one JSON object.  See benchmarks/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("allpass",), default=None,
                    help="the run the check has to fail: the verify stage's "
                         "all-pass mask in place of the device's verdicts")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearsal on the CPU: drives the whole run and "
                         "its check, prints no metric")
    ap.add_argument("--set", action="append", metavar="KEY.PATH=JSON",
                    help="override a key of the configuration file "
                         "(rehearsal only: refused without --cpu)")
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="also write the first 200 ms of the extracted "
                         "trace as JSON (the trace tests' fixture format)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    t_entry = time.monotonic()
    args = parse(argv)
    if args.set and not args.cpu:
        print("benchmark: --set is for --cpu rehearsals only",
              file=sys.stderr)
        return 2
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)    # the program under test
    try:
        from harness.manifest import Manifest

        chips = Manifest().cell(args.workload)["chips"]
        from firedancer_tpu.utils.platform import NoChipError, select_device
    except (ImportError, OSError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        dev = select_device(cpu=args.cpu)   # and the one compile cache
    except NoChipError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    if not args.cpu and dev[2] < chips:
        print(f"benchmark: the cell needs {chips} chips, JAX found {dev[2]}",
              file=sys.stderr)
        return 3

    from harness import runner

    out = runner.run_cell(args, t_entry, dev)
    import jax

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    device = {"platform": dev[0], "kind": dev[1], "count": dev[2],
              "memory_peak_bytes": int(peak)}
    tr = out.pop("_trace", None)
    if tr is not None:
        device.update(tr)
    out["device"] = device
    if args.cpu:
        # a rehearsal: no number read on a CPU goes out under a metric's name
        out["metrics"] = {}
        out.pop("breakdown", None)
        out["rehearsal"] = True
    if args.control:
        out["control"] = args.control
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
