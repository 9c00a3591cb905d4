"""chip_smoke.py — the quickest proof that the leader pipeline still starts
on the chip.

Drives the flagship path — benchg -> verify (device sigverify) ->
pack(+dedup) -> bank x2 -> poh -> shred -> store — once through the
normal entry points on a TPU, at BASELINE config 2's verify width
(config/leader-v5e.toml: batch 1024, max_msg_len 256), and checks what
comes out against the plain reference ops/ref/ed25519_ref.

The parent never imports JAX (a chip belongs to one process at a time):
it runs the phases as sequential child processes that share one compile
cache directory, relays their JSON lines, and exits 0 only if every
phase passed.  Each child's first act is require_chip(): without a TPU
nothing compiles, nothing is printed on stdout, and the exit is non-zero.

  A      one process, cooperative pipeline through cmd_run's code path:
         native rebuild from the committed sources, the programs the
         deployment dispatches compiled at its widths (compile seconds
         are set-up), 30,720 seeded transfers end to end, then 4,096
         with 37 corrupted signatures that must be exactly the ones
         missing from the stored block.
  B      the process topology (`run --processes`): the verify child owns
         the chip, every other child is pinned to the CPU or never
         imports JAX, and the cache written by A is hit, not extended.
  C      only with >= 4 devices: the same pipeline with `[verify]
         devices = 4`, one program over a mesh of four chips (the path
         the cell verify-fanout-4chip runs).

Times and rates printed here are observations for whoever reads the
log, not metrics.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "config", "leader-v5e.toml")
BUDGET_S = 1150.0  # the driver allows 1200 s, compilation included
SEED = 21

# 30 full batches: more than one 400 ms slot at the reference's stock
# ~63K txn/s, ~4x the in-flight window, and 94% of ONE block — pack's
# 48M-CU block limit admits 32,586 of these transfers (cu_consumed
# 47,999,178 with 182 left pending, PR 21), and the cooperative pipeline
# runs one slot, so 32 batches cannot all land
N_STREAM = 30_720
N_BAD_STREAM = 4_096
N_BAD = 37
N_SAMPLE = 256
N_TOPO = 8_192
MESH_DEVICES = 4  # phase C's `[verify] devices`
N_PAYERS = 64  # pack admits one txn per payer per microblock: over the
#                generator's default 8 it sheds a stream this long
COMB_SLOTS = 1_024


def emit(dev, **fields) -> None:
    """One JSON line on stdout; every line names the device it ran on."""
    print(json.dumps({
        **fields, "platform": dev[0], "device_kind": dev[1],
        "device_count": dev[2],
    }), flush=True)


class Compiles:
    """Counts XLA backend compiles (cache loads included) through
    jax.monitoring — 'zero compilations after warm-up' is read here."""

    def __init__(self):
        import jax.monitoring as jm

        self.n = 0
        jm.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.n += 1

    def close(self) -> None:
        import jax.monitoring as jm

        jm.unregister_event_duration_listener(self._on)


# -- seeded inputs ------------------------------------------------------------


def signed_lanes(batch: int, max_msg_len: int, n_bad: int, seed: int):
    """(msg, ln, sig, pk, expect): kernel-shaped byte rows from the
    repo's example signer, `n_bad` seeded lanes corrupted, and the mask
    the plain reference gives for every lane."""
    import numpy as np

    import __graft_entry__ as ge
    from firedancer_tpu.ops.ref import ed25519_ref as ref

    m, ln, sig, pk = ge._example_batch(batch, seed=seed)
    msg = np.zeros((max_msg_len, batch), dtype=np.uint8)
    msg[: m.shape[0]] = m
    sig = sig.copy()
    rng = np.random.default_rng(seed)
    for lane in rng.choice(batch, size=n_bad, replace=False):
        sig[int(rng.integers(64)), lane] ^= 1 << int(rng.integers(8))
    memo: dict = {}
    expect = np.zeros((batch,), dtype=bool)
    for i in range(batch):
        key = (bytes(msg[: ln[i], i]), bytes(sig[:, i]), bytes(pk[:, i]))
        if key not in memo:
            memo[key] = ref.verify(*key)
        expect[i] = memo[key]
    assert int((~expect).sum()) == n_bad
    return msg, ln, sig, pk, expect


def corrupt_pool(pool: list[bytes], n_bad: int, seed: int):
    """Flip one seeded bit in the signature of `n_bad` seeded txns.
    -> (pool with the corrupted txns in place, their indices)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    bad = sorted(int(i) for i in rng.choice(len(pool), n_bad, replace=False))
    out = list(pool)
    for i in bad:
        p = bytearray(out[i])
        # byte 0 is the compact-u16 signature count; 1..64 the signature
        p[1 + int(rng.integers(64))] ^= 1 << int(rng.integers(8))
        out[i] = bytes(p)
    return out, bad


def ref_verdicts(pool: list[bytes], idxs) -> dict[int, bool]:
    """ed25519_ref's verdict on each sampled txn's one signature."""
    from firedancer_tpu.ops.ref import ed25519_ref as ref
    from firedancer_tpu.protocol import txn as ft

    out = {}
    for i in idxs:
        p = pool[i]
        t = ft.txn_parse(p)
        out[i] = ref.verify(t.message(p), t.signatures(p)[0], t.signers(p)[0])
    return out


# -- phase A ------------------------------------------------------------------


def compile_programs(batch: int, max_msg_len: int, shapes, comb_slots: int,
                     seed: int) -> dict:
    """Compile, at the deployment's widths, every program the verify
    stage can dispatch, and hold each to the plain reference: the fused
    program at `shapes`, then comb_fill / bank_install /
    ed25519_verify_batch_cached at (batch, max_msg_len) over a
    `comb_slots` bank, whose mask must equal the generic lane's on the
    same inputs.  -> per-program first-call seconds (compile included)."""
    import jax.numpy as jnp
    import numpy as np

    from firedancer_tpu.ops import sigverify as sv
    from firedancer_tpu.runtime.verify_native import pack_rows

    out: dict = {"fused": {}}
    for b, mm in shapes:
        msg, ln, sig, pk, expect = signed_lanes(b, mm, 5, seed)
        t0 = time.monotonic()
        ok = np.asarray(sv.ed25519_verify_batch_fused(
            jnp.asarray(pack_rows(msg.T, ln, sig.T, pk.T)), max_msg_len=mm))
        out["fused"][f"{b}x{mm}"] = round(time.monotonic() - t0, 2)
        if not (ok == expect).all():
            raise AssertionError(
                f"fused ({b},{mm}) disagrees with ed25519_ref on "
                f"{int((ok != expect).sum())} lanes")
    out["fused_cache_entries"] = int(
        sv.ed25519_verify_batch_fused._cache_size())

    msg, ln, sig, pk, expect = signed_lanes(batch, max_msg_len, 5, seed + 1)
    args = tuple(jnp.asarray(a) for a in (msg, ln, sig, pk))
    uniq = np.unique(pk, axis=1)
    n_keys = uniq.shape[1]
    fill = np.zeros((32, 32), dtype=np.uint8)  # COMB_FILL_BATCH columns
    fill[:, :n_keys] = uniq
    t0 = time.monotonic()
    tables, fill_ok = sv.comb_fill(jnp.asarray(fill))
    fill_ok = np.asarray(fill_ok)
    out["comb_fill"] = round(time.monotonic() - t0, 2)
    assert fill_ok[:n_keys].all(), "comb_fill rejected an honest pubkey"
    # the stage's layout: slot `comb_slots` is the scratch lane pad
    # columns land in, so every install is one fixed-shape dispatch
    rng = np.random.default_rng(seed)
    slot_col = np.full((32,), comb_slots, dtype=np.int32)
    slot_col[:n_keys] = rng.choice(comb_slots, n_keys, replace=False)
    t0 = time.monotonic()
    bank = sv.bank_install(sv.bank_alloc(comb_slots + 1), tables,
                           jnp.asarray(slot_col))
    bank.block_until_ready()
    out["bank_install"] = round(time.monotonic() - t0, 2)
    out["bank_mib"] = round(bank.nbytes / 2**20, 1)
    slot_of = {uniq[:, i].tobytes(): int(slot_col[i]) for i in range(n_keys)}
    slots = np.asarray([slot_of[pk[:, i].tobytes()] for i in range(batch)],
                       dtype=np.int32)
    t0 = time.monotonic()
    cached = np.asarray(sv.ed25519_verify_batch_cached(
        *args, bank, jnp.asarray(slots), max_msg_len=max_msg_len))
    out["verify_cached"] = round(time.monotonic() - t0, 2)
    generic = np.asarray(sv.ed25519_verify_batch_fused(
        jnp.asarray(pack_rows(msg.T, ln, sig.T, pk.T)),
        max_msg_len=max_msg_len))
    if not (cached == generic).all() or not (cached == expect).all():
        raise AssertionError("comb lane mask differs from the generic "
                             "lane / ed25519_ref on the same inputs")
    out["comb_equals_generic"] = True
    return out


def armed_lanes(pipe) -> dict:
    """Which native sweep clients this pipeline armed."""
    return {
        "verify": pipe.verifies[0]._sweep_client is not None,
        "pack": type(pipe.pack).__name__ == "NativePackStage",
        "bank": all(b._sweep_client is not None for b in pipe.banks),
        "shred": pipe.shred._sweep_client is not None,
        "funk": hasattr(pipe.bank_ctx.funk, "txn_diff"),
    }


def run_stream(cfg, pool: list[bytes], bad: list[int], compiles: Compiles,
               seed: int, warm_txns: int) -> dict:
    """One seeded stream through build_leader_pipeline_from_config (the
    code path of `python -m firedancer_tpu run`), device in the path.
    Raises on any miss; -> the observations."""
    import numpy as np

    from firedancer_tpu.models.leader import build_leader_pipeline_from_config
    from firedancer_tpu.runtime.bank import default_bank_ctx
    from firedancer_tpu.runtime.poh_stage import parse_entry
    from firedancer_tpu.runtime.shred_stage import deshred_entry_batch

    n = len(pool)
    pipe = build_leader_pipeline_from_config(
        cfg, pool_size=N_PAYERS, gen_limit=n, verify_precomputed=False,
        bank_ctx=default_bank_ctx(n_payers=N_PAYERS), keep_sets=False,
    )
    try:
        pipe.benchg.pool = pool
        lanes = armed_lanes(pipe)
        warm_s = sum(v.warmup() for v in pipe.verifies)
        t0 = time.monotonic()
        if warm_txns:
            # warm-up window: the first sweeps touch every host lane and
            # any small device program the stages dispatch besides
            # sigverify
            pipe.run(until_txns=warm_txns, max_iters=2_000_000,
                     finish=False)
        n_compiles = compiles.n
        pipe.run(until_txns=n - len(bad), max_iters=2_000_000)
        run_s = time.monotonic() - t0
        late_compiles = compiles.n - n_compiles
        rep = pipe.report()
        executed = sum(b.metrics.get("txn_exec") for b in pipe.banks)
        v = rep["verify0"]
        fec_sets = pipe.shred.metrics.get("fec_sets")
        sets_stored = pipe.store.metrics.get("sets_stored")
        entries = [parse_entry(e) for e in deshred_entry_batch(
            pipe.store.entry_batch_bytes(1))]
        wire = {p for _, _, txns in entries for p in txns}
    finally:
        pipe.close()
    obs = {
        "txns": n, "txn_exec": executed, "batches": v.get("batches", 0),
        "verify_fail": v.get("verify_fail", 0), "fec_sets": fec_sets,
        "sets_stored": sets_stored, "compiles_after_warmup": late_compiles,
        "armed": lanes, "verify_warm_s": round(warm_s, 2),
        "run_s": round(run_s, 2),
    }
    good = set(pool) - {pool[i] for i in bad}
    rng = np.random.default_rng(seed)
    bad_set = set(bad)
    rest = [i for i in range(n) if i not in bad_set]
    sample = bad + [rest[int(j)] for j in rng.choice(
        len(rest), min(N_SAMPLE, n) - len(bad), replace=False)]
    verdict = ref_verdicts(pool, sample)
    checks = {
        "txn_exec == sent - corrupted": executed == n - len(bad),
        "verify_fail == corrupted": obs["verify_fail"] == len(bad),
        "a full device batch per 'batch' txns":
            obs["batches"] >= n // cfg.verify.batch,
        "no compilation after warm-up": late_compiles == 0,
        "FEC sets emitted": fec_sets > 0,
        "every FEC set reassembled by the store": sets_stored == fec_sets,
        "stored block == exactly the uncorrupted txns": wire == good,
        "sample agrees with ed25519_ref":
            all(verdict[i] == (pool[i] in wire) for i in sample),
        "every native lane armed": all(lanes.values()),
    }
    obs["ref_sample"] = len(sample)
    failed = [k for k, ok in checks.items() if not ok]
    if failed:
        raise AssertionError(f"stream of {n} failed: {failed}; {obs}")
    return obs


def phase_a(dev, *, config: str = CONFIG, n_stream: int = N_STREAM,
            n_bad_stream: int = N_BAD_STREAM, n_bad: int = N_BAD,
            extra_shapes=((1024, 1232), (16384, 128)),
            comb_slots: int = COMB_SLOTS, seed: int = SEED) -> None:
    """The cooperative pipeline on the device `dev` names (the caller
    chose it: __main__ requires the chip; the slow test passes the CPU
    and tiny sizes).  Prints one JSON line per step, raises on a miss."""
    from firedancer_tpu.runtime.benchg import gen_transfer_pool
    from firedancer_tpu.utils.config import load_config

    cfg = load_config(config)
    b, mm = cfg.verify.batch, cfg.verify.max_msg_len
    t0 = time.monotonic()
    progs = compile_programs(b, mm, ((b, mm), *extra_shapes), comb_slots, seed)
    emit(dev, phase="A", step="compile", ok=True,
         setup_s=round(time.monotonic() - t0, 1), first_call_s=progs)

    t0 = time.monotonic()
    pool = gen_transfer_pool(n_stream, n_payers=N_PAYERS, n_dests=1024)
    bad_pool, bad = corrupt_pool(
        gen_transfer_pool(n_bad_stream, n_payers=N_PAYERS, n_dests=1024),
        n_bad, seed)
    sign_s = round(time.monotonic() - t0, 1)
    compiles = Compiles()
    try:
        emit(dev, phase="A", step="stream", ok=True, sign_s=sign_s,
             **run_stream(cfg, pool, [], compiles, seed, warm_txns=2 * b))
        emit(dev, phase="A", step="corrupted_stream", ok=True,
             corrupted=n_bad,
             **run_stream(cfg, bad_pool, bad, compiles, seed, warm_txns=0))
    finally:
        compiles.close()


# -- the other phases ---------------------------------------------------------


def phase_c(dev, *, config: str = CONFIG, n_topo: int = N_TOPO) -> None:
    """Four chips, one process: the cooperative pipeline with `[verify]
    devices = 4` at the config's widths — one native intake, one program
    a step over a mesh of the first four devices — over `n_topo`
    transactions.  Skips below four devices."""
    import numpy as np

    from firedancer_tpu.models.leader import build_leader_pipeline_from_config
    from firedancer_tpu.runtime import verify as fv
    from firedancer_tpu.runtime.bank import default_bank_ctx
    from firedancer_tpu.runtime.benchg import gen_transfer_pool
    from firedancer_tpu.runtime.verify_native import row_width
    from firedancer_tpu.utils.config import load_config

    if dev[2] < MESH_DEVICES:
        emit(dev, phase="C", ok=True, skipped=f"{dev[2]} device")
        return
    cfg = load_config(config, overrides={"verify": {"devices": MESH_DEVICES}})
    pipe = build_leader_pipeline_from_config(
        cfg, pool_size=N_PAYERS, gen_limit=n_topo, verify_precomputed=False,
        bank_ctx=default_bank_ctx(n_payers=N_PAYERS), keep_sets=False,
    )
    try:
        pipe.benchg.pool = gen_transfer_pool(n_topo, n_payers=N_PAYERS,
                                             n_dests=1024)
        v = pipe.verifies[0]
        placed = fv.place_rows(
            np.zeros((cfg.verify.batch, row_width(cfg.verify.max_msg_len)),
                     dtype=np.uint8), v._row_sharding)
        obs = {
            "warmup_s": round(sum(s.warmup() for s in pipe.verifies), 2),
            "input_devices": len({s.device
                                  for s in placed.addressable_shards}),
        }
        t0 = time.monotonic()
        pipe.run(until_txns=n_topo, max_iters=2_000_000)
        obs["run_s"] = round(time.monotonic() - t0, 2)
        obs["txn_exec"] = sum(b.metrics.get("txn_exec") for b in pipe.banks)
        obs["pack_dropped"] = pipe.pack.metrics.get("txn_dropped")
        obs["shard_elems"] = [v.metrics.get(f"shard_elems_s{i}")
                              for i in range(MESH_DEVICES)]
        obs["verify_fail"] = v.metrics.get("verify_fail")
    finally:
        pipe.close()
    ok = (obs["txn_exec"] == n_topo and all(obs["shard_elems"])
          and obs["input_devices"] == MESH_DEVICES
          and not obs["verify_fail"])
    emit(dev, phase="C", ok=ok, **obs)
    if not ok:
        raise AssertionError(f"phase C failed: {obs}")


def child_main(phase: str) -> int:
    """One phase in this process.  The chip first: nothing below runs,
    builds or compiles without one."""
    from firedancer_tpu.utils import nativebuild
    from firedancer_tpu.utils.platform import NoChipError, select_device

    try:
        dev = select_device()  # the chip, and the shared compile cache
    except NoChipError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 3
    if phase == "A":
        # from the committed sources, before anything loads a library: a
        # stale .so copied along with the tree must not be what runs
        built = nativebuild.build_all(force=True)
        emit(dev, phase="A", step="native_build", ok=True,
             built=[os.path.basename(p) for p in built])
    {"A": phase_a, "C": phase_c}[phase](dev)
    return 0


# -- the JAX-free parent ------------------------------------------------------


def run_child(argv: list[str], timeout_s: float):
    """Run one child in its own session; on timeout kill the whole group
    (phase B has grandchildren).  -> (rc, stdout, stderr)."""
    import signal

    proc = subprocess.Popen(
        [sys.executable, *argv], cwd=HERE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True,
    )
    try:
        out, err = proc.communicate(timeout=max(timeout_s, 1.0))
        return proc.returncode, out, err
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        out, err = proc.communicate()
        return 124, out, err + f"\nchip_smoke: timed out after {timeout_s:.0f}s"
    finally:
        # a child that exited may still have left grandchildren behind
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def fused_cache_entries() -> set[str]:
    from firedancer_tpu.utils.platform import compile_cache_dir

    try:
        return {f for f in os.listdir(compile_cache_dir())
                if f.startswith("jit_ed25519_verify_batch_fused")}
    except FileNotFoundError:
        return set()


def phase_b(dev, cold_fused_s, timeout_s: float) -> bool:
    """`python -m firedancer_tpu run --processes` over N_TOPO
    transactions, judged from outside: its exit code (bank txn_exec ==
    --txns), each child's `stage <name> jax=` line, the verify child's
    device line, and the cache directory before and after."""
    import re

    before = fused_cache_entries()
    t0 = time.monotonic()
    rc, out, err = run_child(
        ["-m", "firedancer_tpu", "run", "--processes", "--config", CONFIG,
         "--txns", str(N_TOPO)], timeout_s)
    wall_s = round(time.monotonic() - t0, 1)
    jax_state = dict(re.findall(r"stage (\w+) jax=(\w+)", err))
    m = re.search(r"stage verify0 device=(\w+) kind='([^']*)' count=(\d+) "
                  r"warmup_s=([\d.]+)", err)
    verify_dev = m.group(1) if m else None
    new_entries = sorted(fused_cache_entries() - before)
    obs = {
        "rc": rc, "wall_s": wall_s, "txns": N_TOPO, "jax": jax_state,
        "verify_platform": verify_dev,
        # the same program at the same shape: compiled cold in phase A,
        # loaded from the shared cache by the verify child here
        "fused_cold_s": cold_fused_s,
        "fused_warm_s": float(m.group(4)) if m else None,
        "new_fused_cache_entries": new_entries,
    }
    ok = (
        rc == 0
        and verify_dev == "tpu"
        and jax_state.get("verify0") == "default"
        and all(s in ("cpu", "none") for n, s in jax_state.items()
                if n != "verify0")
        and len(jax_state) >= 7
        and not new_entries
    )
    emit(dev, phase="B", ok=ok, **obs)
    if not ok:
        sys.stderr.write(err[-6000:] + "\n" + out[-2000:] + "\n")
    return ok


def main() -> int:
    if len(sys.argv) == 3 and sys.argv[1] == "--phase":
        return child_main(sys.argv[2])
    t_end = time.monotonic() + BUDGET_S
    dev = None
    cold_fused_s = None
    for phase in ("A", "B", "C"):
        left = t_end - time.monotonic()
        if phase == "B":
            if not phase_b(dev, cold_fused_s, left):
                break
            continue
        rc, out, err = run_child([os.path.abspath(__file__), "--phase", phase],
                                 left)
        sys.stderr.write(err)
        if rc == 3 and dev is None:
            return 3  # no chip: no result of any kind on stdout
        sys.stdout.write(out)
        sys.stdout.flush()
        lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
        if rc != 0 or not lines or not all(ln.get("ok") for ln in lines):
            print(f"chip_smoke: phase {phase} failed (rc={rc})",
                  file=sys.stderr)
            break
        dev = (lines[0]["platform"], lines[0]["device_kind"],
               lines[0]["device_count"])
        if phase == "A":
            fused = next(ln for ln in lines
                         if ln.get("step") == "compile")["first_call_s"]["fused"]
            cold_fused_s = next(iter(fused.values()))  # the config's shape
    else:
        print(json.dumps({"ok": True, "device": {
            "platform": dev[0], "kind": dev[1], "count": dev[2]}}))
        return 0
    if dev is not None:
        print(json.dumps({"ok": False, "device": {
            "platform": dev[0], "kind": dev[1], "count": dev[2]}}))
    return 1


if __name__ == "__main__":
    sys.exit(main())
