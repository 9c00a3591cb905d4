"""Probe what a field multiply and a point doubling cost on the chip, as
chains in a fori_loop, on either batch layout.

Steps (one per iteration, the state fed back):

  mulchain   — one fe_mul
  mul4       — 4 independent fe_mul (a state of 4 fe's)
  sqr4       — 4 fe_sqr
  sqrchain   — one fe_sqr fed to itself: the fe_pow2523 pattern (2 x 252 of
               them a signature, each waiting for the last)
  addchain   — one fe_add (carry2): the carry-pass cost
  dblnoc     — point_dbl with NO carry passes on add/sub (raw +/-, bounds
               be damned — timing only)
  dblprod    — the 4 sqr + 4 mul of point_dbl with the adds replaced by
               constants (isolates the mul DAG shape)
  dbl        — production point_dbl

--fold lays the chain state as (20, B // 128, 128) — the batch on both
tiled axes, what the sigverify programs run (ops/sigverify.fold_batch) —
instead of (20, B).  ms/iter is (t(k2) - t(k1)) / (k2 - k1) over the best of
three calls each: at --batch 1024 an iteration is microseconds, so ask for
--k1 512 --k2 4096 there.  /PERF.md section 6 (PR 38) has both layouts'
columns at 1,024 and 16,384 as read on a v5e.

What the rows read at 1,024 folded lanes (--fold --batch 1024 --k1 512
--k2 4096; a v5e, /PERF.md section 6, PR 43), us an iteration, before ->
after the lazier carries and the one-shape product of PR 43:

  mulchain   1.2 -> 1.0      sqrchain   2.7 -> 1.0      sqr4   2.7 -> 1.0
  addchain   0.5 -> 0.4      dbl        9.5 -> 6.0

sqr4 equal to sqrchain is the finding, not a fault: four independent
squarings cost what one does, because a step is a chain of small fusions
and each costs a launch whatever it holds.

--ops adds, under each row, where an iteration's time goes: one call of
the chain under a jax.profiler session, the device ops inside its loop
added up by kind (a fusion's name without its number) — how many an
iteration, their mean time, their time an iteration.  The verify program
is bound by the fusions it launches, so this and not an op count is what
prices a change to ops/limbs.py (/PERF.md section 6, PR 43).

Usage: python scripts/perf_probe.py [--batch 16384] [--k1 64] [--k2 256]
                                    [--fold] [--only mulchain,dbl] [--ops]
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from firedancer_tpu.ops import limbs as fl
from firedancer_tpu.ops import curve as fc
from firedancer_tpu.ops import sigverify as sv


OPS_ITERS = 32


def _kind(op_name):
    head = op_name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def device_ops(run, state, iters=OPS_ITERS, top=12):
    """Print the device ops inside `run`'s loop by kind, from a profiler
    trace of one (already compiled) call of `iters` iterations."""
    from jax.profiler import ProfileData

    trace_dir = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(trace_dir)
        float(run(state, jnp.int32(iters)))
        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        events = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:TPU:0"):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        events = [(_kind(e.name), e.start_ns, e.duration_ns)
                                  for e in line.events]
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    loops = [e for e in events if e[0].startswith("while")]
    if not loops:
        print("   --ops: no device loop in the trace (not a TPU?)")
        return
    loop = max(loops, key=lambda e: e[2])
    kinds = collections.defaultdict(lambda: [0, 0.0])
    for kind, start, dur in events:
        if loop[1] <= start and start + dur <= loop[1] + loop[2] \
                and (kind, start, dur) != loop:
            kinds[kind][0] += 1
            kinds[kind][1] += dur
    n = sum(c for c, _ in kinds.values())
    print(f"   loop {loop[2] / iters:.0f} ns/iter, {n / iters:.1f} device ops "
          f"an iteration taking {sum(t for _, t in kinds.values()) / iters:.0f}")
    for kind, (c, t) in sorted(kinds.items(), key=lambda kv: -kv[1][1])[:top]:
        print(f"   {kind[:46]:46s} {c / iters:6.1f}/iter  mean {t / c:7.1f} ns"
              f"  {t / iters:8.0f} ns/iter")


def bench_step(name, step, state, k1, k2, ops=False):
    @jax.jit
    def run(state, n):
        out = jax.lax.fori_loop(0, n, lambda i, s: step(s), state)
        leaf = jax.tree_util.tree_leaves(out)[0]
        return jnp.sum(leaf[0].astype(jnp.float32))

    float(run(state, jnp.int32(2)))
    t = {}
    for k in (k1, k2):
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            float(run(state, jnp.int32(k)))
            best = min(best, time.perf_counter() - t0)
        t[k] = best
    per_iter = (t[k2] - t[k1]) / (k2 - k1)
    print(
        f"{name:10s}  {per_iter*1e3:8.4f} ms/iter"
        f"   [t{k1}={t[k1]*1e3:.0f}ms t{k2}={t[k2]*1e3:.0f}ms]"
    )
    if ops:
        device_ops(run, state)
    return per_iter


def step_mulchain(s):
    x, y = s
    return fl.fe_mul(x, y), x


def step_mul4(s):
    a, b, c, d = s
    return fl.fe_mul(a, b), fl.fe_mul(b, c), fl.fe_mul(c, d), fl.fe_mul(d, a)


def step_sqr4(s):
    a, b, c, d = s
    return fl.fe_sqr(a), fl.fe_sqr(b), fl.fe_sqr(c), fl.fe_sqr(d)


def step_sqrchain(s):
    return (fl.fe_sqr(s[0]),)


def step_addchain(s):
    x, y = s
    return fl.fe_add(x, y), x


def _rawadd(a, b):
    return a + b


def _rawsub(a, b):
    return a - b


def step_dblnoc(s):
    x1, y1, z1, t1 = s[0]
    a = fl.fe_sqr(x1)
    b = fl.fe_sqr(y1)
    zz = fl.fe_sqr(z1)
    c = _rawadd(zz, zz)
    e = _rawsub(_rawsub(fl.fe_sqr(_rawadd(x1, y1)), a), b)
    g = _rawsub(b, a)
    f = _rawsub(g, c)
    h = -(_rawadd(a, b))
    return ((fl.fe_mul(e, f), fl.fe_mul(g, h), fl.fe_mul(f, g), fl.fe_mul(e, h)),)


def step_dblprod(s):
    x1, y1, z1, t1 = s[0]
    a = fl.fe_sqr(x1)
    b = fl.fe_sqr(y1)
    zz = fl.fe_sqr(z1)
    e = fl.fe_sqr(t1)
    return ((fl.fe_mul(e, a), fl.fe_mul(b, zz), fl.fe_mul(a, b), fl.fe_mul(e, zz)),)


def step_dbl(s):
    return (fc.point_dbl(s[0]),)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16384)
    ap.add_argument("--k1", type=int, default=64)
    ap.add_argument("--k2", type=int, default=256)
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--ops", action="store_true",
                    help="under each row, the device ops of an iteration by"
                         " kind, from a profiler trace of one call")
    ap.add_argument("--fold", action="store_true",
                    help="chain state (20, B // 128, 128): the batch on both"
                         " tiled axes, as ops/sigverify.fold_batch lays it")
    args = ap.parse_args()
    B = args.batch
    only = set(args.only.split(",")) if args.only else None
    rng = np.random.default_rng(11)

    def mk():
        x = jnp.asarray(rng.integers(0, 1 << 13, (fl.NLIMB, B)), jnp.int32)
        return sv.fold_batch(x)[0] if args.fold else x


    x, y = mk(), mk()
    print("backend:", jax.default_backend(), jax.devices(), "batch", B,
          "state", x.shape)
    p4 = (mk(), mk(), mk(), mk())

    todo = [
        ("mulchain", step_mulchain, (x, y)),
        ("mul4", step_mul4, p4),
        ("sqr4", step_sqr4, p4),
        ("sqrchain", step_sqrchain, (x,)),
        ("addchain", step_addchain, (x, y)),
        ("dblprod", step_dblprod, (p4,)),
        ("dblnoc", step_dblnoc, (p4,)),
        ("dbl", step_dbl, (p4,)),
    ]
    for name, step, state in todo:
        if only is None or name in only:
            bench_step(name, step, state, args.k1, args.k2, args.ops)


if __name__ == "__main__":
    main()
