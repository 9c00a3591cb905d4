"""Sharded serving plane: real leader-pipeline traffic over the device mesh.

`parallel/mesh.py` proved the sharded leader step compiles and reduces
correctly (the MULTICHIP dryruns); this module graduates it to SERVING:
a plane object that owns the mesh, the partition specs, and ONE compiled
pjit leader step, plus the stage that pushes live pipeline frags through
it.  The shape follows the pjit discipline of the SNIPPETS exemplars —
in_shardings and out_shardings pinned per hop and MATCHED across hops so
XLA never inserts a resharding collective between the verify, reedsol,
and PoH sections of the step:

  - verify inputs/outputs: batch axis sharded over the mesh, byte-row
    leading dims replicated (`P(None, axis)` rows / `P(axis)` lanes);
  - reedsol: FEC sets sharded over their leading axis
    (`P(axis, None, None)`), the bit-generator matrix replicated;
  - PoH: hash chains sharded over the lane axis (`P(None, axis)`);
  - scalar summaries (`n_ok`) come back replicated — the psum is the
    only cross-shard collective in the program, by construction.

Lane geometry is FIXED per compile (the verify-stage padding discipline):
each shard owns a contiguous `batch_per_shard` lane range, uneven final
fills are padded and masked ON DEVICE from the replicated per-shard real
counts, and the frag->shard assignment is deterministic (the router's
`seq % n_shards`, carried by which per-shard ring a frag arrived on).

Cold-start is a production concern (a leader that compiles for 2 minutes
misses its slot): the plane supports
AOT warmup (`warmup()` lowers+compiles before traffic arrives) and the
persistent compilation cache (utils/platform.enable_compile_cache) so a
warmed host's next process boots the step from cache in seconds.
"""

from __future__ import annotations

import os
import sys
import time
from dataclasses import dataclass

import numpy as np

from .mesh import AXIS, make_mesh, pad_to_multiple


@dataclass(frozen=True)
class ServeConfig:
    """Static geometry of the serving step (one compile per config).

    The verify lanes carry the txn batch; the reedsol and PoH lanes are
    sized small by default — they carry the shredder's parity work and
    the PoH self-audit spans when those stages ride the plane, and cost
    placeholder compute when idle, so default shapes are the smallest
    useful ones.
    """

    n_devices: int
    batch_per_shard: int = 128  # verify elements per shard
    max_msg_len: int = 256
    fec_sets_per_shard: int = 1  # RS sets per shard per step
    fec_data_shreds: int = 32  # d (the normal-FEC-set shape)
    fec_parity_shreds: int = 32  # p = parity_cnt_for(32)
    fec_shred_sz: int = 1024  # per-shred byte capacity (sz-padded)
    poh_chains_per_shard: int = 1
    poh_iters: int = 64  # pure-append span length (hashes_per_tick)
    axis: str = AXIS

    @property
    def batch(self) -> int:
        return self.batch_per_shard * self.n_devices

    @property
    def fec_sets(self) -> int:
        return self.fec_sets_per_shard * self.n_devices

    @property
    def poh_chains(self) -> int:
        return self.poh_chains_per_shard * self.n_devices

    def cache_key(self) -> str:
        return (
            f"d{self.n_devices}_b{self.batch_per_shard}_m{self.max_msg_len}"
            f"_f{self.fec_sets_per_shard}x{self.fec_data_shreds}"
            f"p{self.fec_parity_shreds}s{self.fec_shred_sz}"
            f"_h{self.poh_chains_per_shard}i{self.poh_iters}"
        )


def lane_real_mask(lane_count: int, per_shard: int, n_real):
    """THE pad-lane mask, one place: lane j belongs to shard j//per and is
    real iff its intra-shard index is below that shard's fill.  Jittable
    (n_real a traced (n_devices,) int vector) — the serving step and the
    test-facing mask probe both call exactly this."""
    import jax.numpy as jnp

    lane = jnp.arange(lane_count, dtype=jnp.int32)
    return (lane % per_shard) < n_real[lane // per_shard]


@dataclass
class Pending:
    """One serving step in flight: device futures + the real-lane counts."""

    ok: object  # (batch,) bool, pad lanes already masked false on device
    n_ok: object  # scalar int32 (the psum)
    parity: object  # (fec_sets, p, sz) uint8
    poh_ok: object  # (poh_chains,) bool
    n_real: np.ndarray  # (n_devices,) verify fill per shard
    fec_real: int
    poh_real: int

    def ready(self) -> bool:
        return self.ok.is_ready()


class ServePlane:
    """The mesh + the one compiled serving step + its sharded arg plumbing."""

    def __init__(self, cfg: ServeConfig):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.cfg = cfg
        self.mesh = make_mesh(cfg.n_devices, cfg.axis)
        ax = cfg.axis
        ns = lambda *spec: NamedSharding(self.mesh, P(*spec))  # noqa: E731
        # one spec per hop, matched on the batch axis so the program has
        # no resharding between its verify/reedsol/PoH sections
        self.s_rows = ns(None, ax)  # (rows, batch) byte rows
        self.s_vec = ns(ax)  # (batch,) lanes
        self.s_sets = ns(ax, None, None)  # (fec_sets, d, sz)
        self.s_repl = ns()  # replicated (rs bits, counts)
        self._step = None  # compiled/jitted step
        self._aot = None  # AOT-compiled executable (warmup path)
        self._placeholder = None  # device-resident zero fec/poh args
        self.compile_s: float | None = None  # measured by warmup()
        # warmup() booted from a serialized executable (no trace/compile)
        self.loaded_blob = False
        # rider queue: PoH spans other stages park for the next step call
        self._poh_spans: list[tuple[bytes, bytes]] = []
        self._jax = jax

    # -- the single program -------------------------------------------------

    def _build_step(self):
        import functools

        import jax
        import jax.numpy as jnp

        from firedancer_tpu.ops import reedsol as rs
        from firedancer_tpu.ops import sha256 as fsha
        from firedancer_tpu.ops import sigverify as sv

        cfg = self.cfg
        per = cfg.batch_per_shard
        per_poh = cfg.poh_chains_per_shard

        @functools.partial(
            jax.jit,
            in_shardings=(
                self.s_rows, self.s_vec, self.s_rows, self.s_rows,  # verify
                self.s_repl,  # n_real (n_dev,)
                self.s_repl, self.s_sets, self.s_repl,  # rs bits, fec, fec_real
                self.s_rows, self.s_rows, self.s_repl,  # poh start/end, poh_real
            ),
            out_shardings=(self.s_vec, self.s_repl, self.s_sets, self.s_vec),
        )
        def step(msg, msg_len, sig, pk, n_real,
                 rs_bits, fec, fec_real, poh_start, poh_end, poh_real):
            ok = sv.ed25519_verify_batch(
                msg, msg_len, sig, pk, max_msg_len=cfg.max_msg_len
            )
            # pad-lane masking from the replicated per-shard fills —
            # computed on device so the psum'd count never sees a pad lane
            ok = ok & lane_real_mask(ok.shape[0], per, n_real)
            n_ok = jnp.sum(ok.astype(jnp.int32))
            par = rs.encode_core(rs_bits, fec)
            got = fsha.sha256_iter32(poh_start, cfg.poh_iters)
            poh_ok = jnp.all(got == poh_end, axis=0) & lane_real_mask(
                got.shape[1], per_poh, poh_real
            )
            del fec_real  # parity of zero-padded sets is zero: no mask needed
            return ok, n_ok, par, poh_ok

        return step

    def _get_step(self):
        if self._step is None:
            self._step = self._build_step()
        return self._step

    def _abstract_args(self):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
        return (
            S((cfg.max_msg_len, cfg.batch), jnp.uint8),
            S((cfg.batch,), jnp.int32),
            S((64, cfg.batch), jnp.uint8),
            S((32, cfg.batch), jnp.uint8),
            S((cfg.n_devices,), jnp.int32),
            # the bit-block generator matrix is int8 (gf_matrix_to_bits)
            S((8 * cfg.fec_parity_shreds, 8 * cfg.fec_data_shreds), jnp.int8),
            S((cfg.fec_sets, cfg.fec_data_shreds, cfg.fec_shred_sz), jnp.uint8),
            S((cfg.n_devices,), jnp.int32),
            S((32, cfg.poh_chains), jnp.int32),
            S((32, cfg.poh_chains), jnp.int32),
            S((cfg.n_devices,), jnp.int32),
        )

    def _sharding_tuples(self):
        in_sh = (
            self.s_rows, self.s_vec, self.s_rows, self.s_rows, self.s_repl,
            self.s_repl, self.s_sets, self.s_repl,
            self.s_rows, self.s_rows, self.s_repl,
        )
        out_sh = (self.s_vec, self.s_repl, self.s_sets, self.s_vec)
        return in_sh, out_sh

    def _mesh_platform(self) -> str:
        """The platform the step actually runs on (the plane's OWN mesh,
        not the process default — a CPU dryrun next to a TPU mesh must
        not pick the TPU lane)."""
        return self.mesh.devices.flat[0].platform

    def _use_serialized_executable(self) -> bool:
        """Warm-boot lane choice, from the platform the step runs on: on
        an accelerator the serialized executable IS machine code, so a
        warm boot deserializes in seconds (the 10 s warm_cold_start
        budget's path); on XLA:CPU the round trip fails ("Symbols not
        found" at load — the executable references process-local
        symbols), so CPU keeps the jax.export StableHLO lane."""
        return self._mesh_platform() != "cpu"

    def _exec_blob_path(self, cache_dir: str | None) -> str | None:
        if not cache_dir:
            return None
        return os.path.join(
            cache_dir,
            f"serve_step_{self.cfg.cache_key()}_{self._mesh_platform()}.xc",
        )

    def warmup(self) -> float:
        """AOT-compile the serving step before any traffic exists (the
        leader's boot-time obligation).  Returns seconds.

        Two warm-boot lanes, selected by backend
        (_use_serialized_executable):

          - accelerators: the COMPILED executable serializes
            (jax.experimental.serialize_executable) next to the cache as
            `serve_step_<key>_<platform>.xc`; a warm boot is pure
            deserialization — no trace, no XLA, no codegen — which is
            what fits the 10 s warm_cold_start budget;
          - CPU (the executable round trip fails there: "Symbols not
            found"): the jax.export lane below — the Python trace/lower
            (~20s on one core) is skipped by reloading the serialized
            StableHLO export (`serve_step_<key>.hlo`), and the XLA
            optimization pipeline by the persistent compilation cache.
            What remains is LLVM rehydration (~26s on one core).

        Measured ladder on this host class: ~175s cold / ~27s warm via
        the export lane."""
        import jax

        t0 = time.monotonic()
        cache_dir = jax.config.jax_compilation_cache_dir
        if self._use_serialized_executable():
            if self._warmup_serialized(cache_dir):
                self.compile_s = time.monotonic() - t0
                return self.compile_s
        self._warmup_export(cache_dir)
        self.compile_s = time.monotonic() - t0
        return self.compile_s

    def _warmup_serialized(self, cache_dir: str | None) -> bool:
        """The accelerator lane: load the serialized executable if one
        exists, else compile through the export lane and serialize the
        result for the next boot.  Returns False only when the blob
        machinery is unusable (no cache dir and nothing to gain)."""
        import pickle

        from jax.experimental import serialize_executable as se

        blob = self._exec_blob_path(cache_dir)
        if blob is None:
            return False
        if os.path.exists(blob):
            try:
                with open(blob, "rb") as f:
                    payload, in_tree, out_tree = pickle.load(f)
                self._aot = se.deserialize_and_load(payload, in_tree,
                                                    out_tree)
                self.loaded_blob = True
                return True
            except Exception as e:
                # an unreadable or incompatible blob must cost ONE slow
                # recompile, not the boot: drop it and fall through to
                # the export lane, which rewrites a fresh blob below
                print(f"# warm-boot blob unusable ({type(e).__name__}: "
                      f"{e}); recompiling", file=sys.stderr)
                try:
                    os.remove(blob)
                except OSError:
                    pass
        self._warmup_export(cache_dir)
        payload, in_tree, out_tree = se.serialize(self._aot)
        tmp = f"{blob}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump((payload, in_tree, out_tree), f)
        os.replace(tmp, blob)
        return True

    def _warmup_export(self, cache_dir: str | None) -> None:
        """The CPU-safe lane: serialized StableHLO export (skips
        re-trace) + persistent compilation cache (skips
        re-optimization)."""
        import jax
        import jax.export

        blob = None
        if cache_dir:
            blob = os.path.join(
                cache_dir, f"serve_step_{self.cfg.cache_key()}.hlo"
            )
        exp = None
        if blob is not None and os.path.exists(blob):
            with open(blob, "rb") as f:
                exp = jax.export.deserialize(f.read())
        if exp is None:
            exp = jax.export.export(self._get_step())(*self._abstract_args())
            if blob is not None:
                os.makedirs(cache_dir, exist_ok=True)
                tmp = f"{blob}.tmp.{os.getpid()}"
                with open(tmp, "wb") as f:
                    f.write(exp.serialize())
                os.replace(tmp, blob)
        in_sh, out_sh = self._sharding_tuples()
        self._aot = jax.jit(
            exp.call, in_shardings=in_sh, out_shardings=out_sh
        ).lower(*self._abstract_args()).compile()

    # -- sharded argument plumbing -------------------------------------------

    def _placeholders(self):
        """Device-resident zero fec/poh args, built once: a verify-only
        step call must not pay a host->device transfer for lanes that
        carry no work."""
        if self._placeholder is None:
            import jax
            import jax.numpy as jnp

            from firedancer_tpu.ops import reedsol as rs

            cfg = self.cfg
            dp = jax.device_put
            self._rs_bits = dp(
                rs._encode_bits(cfg.fec_data_shreds, cfg.fec_parity_shreds),
                self.s_repl,
            )
            self._placeholder = (
                dp(jnp.zeros((cfg.fec_sets, cfg.fec_data_shreds,
                              cfg.fec_shred_sz), jnp.uint8), self.s_sets),
                dp(jnp.zeros((32, cfg.poh_chains), jnp.int32), self.s_rows),
                dp(jnp.zeros((32, cfg.poh_chains), jnp.int32), self.s_rows),
            )
            self._zero_real = dp(
                jnp.zeros((cfg.n_devices,), jnp.int32), self.s_repl
            )
        return self._placeholder

    def place_verify(self, msg, msg_len, sig, pk):
        """Commit pre-padded (rows, batch) verify arrays to the mesh with
        the step's OWN input shardings (pre-partitioned, per the pjit
        exemplar note: matching placement skips the implicit reshard)."""
        import jax

        # host arrays go straight to their shards: wrapping them in
        # jnp.asarray first would commit the whole batch to device 0 and
        # then reshard it across the mesh
        dp = jax.device_put
        return (
            dp(msg, self.s_rows),
            dp(msg_len, self.s_vec),
            dp(sig, self.s_rows),
            dp(pk, self.s_rows),
        )

    # -- rider queues (shredder / poh park work for the next step) ----------

    def queue_poh_span(self, start: bytes, end: bytes) -> bool:
        """Park one pure-append PoH span (exactly cfg.poh_iters hashes)
        for device re-verification on the next serving step.  Bounded:
        drops (returns False) when a slot's worth is already pending."""
        if len(self._poh_spans) >= 4 * self.cfg.poh_chains:
            return False
        self._poh_spans.append((start, end))
        return True

    def _take_poh(self):
        import jax

        cfg = self.cfg
        if not self._poh_spans:
            ph = self._placeholders()
            return ph[1], ph[2], self._zero_real, 0
        take = self._poh_spans[: cfg.poh_chains]
        del self._poh_spans[: len(take)]
        starts = np.zeros((32, cfg.poh_chains), dtype=np.int32)
        ends = np.zeros((32, cfg.poh_chains), dtype=np.int32)
        for i, (s, e) in enumerate(take):
            starts[:, i] = np.frombuffer(s, dtype=np.uint8)
            ends[:, i] = np.frombuffer(e, dtype=np.uint8)
        per = cfg.poh_chains_per_shard
        real = np.asarray(
            [min(max(len(take) - d * per, 0), per)
             for d in range(cfg.n_devices)], dtype=np.int32
        )
        dp = jax.device_put
        return (
            dp(starts, self.s_rows),
            dp(ends, self.s_rows),
            dp(real, self.s_repl),
            len(take),
        )

    # -- dispatch ------------------------------------------------------------

    def submit(self, msg, msg_len, sig, pk, n_real_per_shard,
               riders: bool = True) -> Pending:
        """One serving step over pre-padded verify arrays (+ any parked
        PoH spans when riders=True).  Returns futures; pad lanes are
        already masked.  riders=False leaves the span queue alone — for
        callers that return only the verify mask and would otherwise
        consume the self-audit results without reporting them."""
        import jax

        self._placeholders()
        fec, _, _ = self._placeholder
        if riders:
            p_start, p_end, p_real, n_poh = self._take_poh()
        else:
            ph = self._placeholder
            p_start, p_end, p_real, n_poh = ph[1], ph[2], self._zero_real, 0
        args = self.place_verify(msg, msg_len, sig, pk)
        n_real = np.asarray(n_real_per_shard, dtype=np.int32)
        fn = self._aot if self._aot is not None else self._get_step()
        ok, n_ok, par, poh_ok = fn(
            *args, jax.device_put(n_real, self.s_repl),
            self._rs_bits, fec, self._zero_real,
            p_start, p_end, p_real,
        )
        return Pending(ok, n_ok, par, poh_ok, n_real, 0, n_poh)

    def verify_batch(self, msg, msg_len, sig, pk):
        """Synchronous whole-batch verify through the serving step —
        drop-in for ops.sigverify.ed25519_verify_batch at the plane's
        exact batch shape (the VerifyStage plane hook).  Returns the
        (batch,) ok mask as a device array."""
        b = self.cfg.batch
        if msg.shape[1] != b:
            raise ValueError(
                f"plane step is compiled for batch {b}, got {msg.shape[1]}"
            )
        per = self.cfg.batch_per_shard
        full = np.full((self.cfg.n_devices,), per, dtype=np.int32)
        # riders=False: this caller returns only the mask, so consuming
        # parked PoH spans here would silently drop their audit results
        return self.submit(msg, msg_len, sig, pk, full, riders=False).ok

    def encode_parity(self, data: np.ndarray, parity_cnt: int) -> np.ndarray:
        """Sharded Reed-Solomon parity for (nsets, d, sz) FEC sets: sets
        padded up to the mesh divisor, sz zero-padded up to the compiled
        width (parity of a zero-padded column is zero — the GF(2^8) code
        is linear per byte column), dispatched with the step's matched
        set shardings.  Shapes outside the plane's compiled (d, p) fall
        back to the unsharded encoder."""
        import jax

        from firedancer_tpu.ops import reedsol as rs

        cfg = self.cfg
        nsets, d, sz = data.shape
        if (d != cfg.fec_data_shreds or parity_cnt != cfg.fec_parity_shreds
                or sz > cfg.fec_shred_sz):
            # off-shape tails keep the shredder's HOST lane (parity-
            # identical, no device dispatch mid-slot for a fresh shape)
            return np.asarray(rs.encode_host(np.asarray(data), parity_cnt))
        pad_sets = pad_to_multiple(nsets, cfg.n_devices)
        buf = np.zeros((pad_sets, d, cfg.fec_shred_sz), dtype=np.uint8)
        buf[:nsets, :, :sz] = data
        fec = jax.device_put(buf, self.s_sets)
        # the sharded path only fires at the compiled (d, p), whose bit
        # matrix _placeholders() already committed once — reuse it
        self._placeholders()
        par = self._sharded_rs()(self._rs_bits, fec)
        return np.asarray(par)[:nsets, :, :sz]

    def _sharded_rs(self):
        """RS-only sharded program (the shredder's synchronous path): the
        same encode_core + set shardings as the serving step, compiled
        once per plane."""
        if getattr(self, "_rs_step", None) is None:
            import jax

            from firedancer_tpu.ops import reedsol as rs

            self._rs_step = jax.jit(
                rs.encode_core,
                in_shardings=(self.s_repl, self.s_sets),
                out_shardings=self.s_sets,
            )
        return self._rs_step

    def verify_poh_segments(self, starts, ends, iters: int) -> np.ndarray:
        """Sharded equal-length PoH segment verification: (32, n) int32
        start/end byte rows, n padded to the mesh divisor and pad chains
        masked.  Off-shape iter counts fall back to the host verifier's
        device path (runtime/poh.verify_segments_tpu)."""
        import jax

        cfg = self.cfg
        if iters != cfg.poh_iters:
            from firedancer_tpu.runtime import poh as rpoh

            s = [bytes(np.asarray(starts[:, i], dtype=np.uint8))
                 for i in range(starts.shape[1])]
            e = [bytes(np.asarray(ends[:, i], dtype=np.uint8))
                 for i in range(ends.shape[1])]
            return np.asarray(rpoh.verify_segments_tpu(s, iters, e))
        n = starts.shape[1]
        pad = pad_to_multiple(n, cfg.n_devices)
        sb = np.zeros((32, pad), dtype=np.int32)
        eb = np.zeros((32, pad), dtype=np.int32)
        sb[:, :n] = starts
        eb[:, :n] = ends
        got = self._sharded_poh()(jax.device_put(sb, self.s_rows))
        return np.asarray((np.asarray(got) == eb).all(axis=0))[:n]

    def real_mask(self, n_real_per_shard) -> np.ndarray:
        """The step's pad-lane mask, ON DEVICE with the step's own lane
        sharding — the cheap probe tier-1 uses to pin the masking logic
        without paying the verify kernel's compile."""
        import functools

        import jax

        if getattr(self, "_mask_step", None) is None:
            self._mask_step = jax.jit(
                functools.partial(
                    lane_real_mask, self.cfg.batch, self.cfg.batch_per_shard
                ),
                in_shardings=(self.s_repl,),
                out_shardings=self.s_vec,
            )
        n_real = np.asarray(n_real_per_shard, dtype=np.int32)
        return np.asarray(
            self._mask_step(jax.device_put(n_real, self.s_repl))
        )

    def _sharded_poh(self):
        if getattr(self, "_poh_step", None) is None:
            import functools

            import jax

            from firedancer_tpu.ops import sha256 as fsha

            self._poh_step = jax.jit(
                functools.partial(fsha.sha256_iter32, n=self.cfg.poh_iters),
                in_shardings=(self.s_rows,),
                out_shardings=self.s_rows,
            )
        return self._poh_step


# -- the serving stage ---------------------------------------------------------


from firedancer_tpu.runtime.verify import (  # noqa: E402
    CLOSE_DEADLINE,
    CLOSE_FULL,
    MCACHE_COL_TSORIG,
    VerifyStage,
    _Acc,
    _Pending as _VPending,
    sig_tag,
)
from firedancer_tpu.utils import metrics as fmet  # noqa: E402


class ShardedVerifyStage(VerifyStage):
    """The serving plane's pipeline position: ONE stage consuming the
    router's per-shard rings and dispatching ONE sharded step per batch.

    Each input ring IS a shard: frags that arrived on ring i fill shard
    i's contiguous lane range of the fixed-shape batch, so the router's
    deterministic `seq % n_shards` assignment carries through to device
    placement (ring i -> mesh device i) with no host-side reshuffle.

    The batch closes when any shard's lane range fills, or when the
    deadline has passed and the window is open to it (the VerifyStage
    close rule, `_deadline_close`: this stage only names its accumulators);
    uneven fills pad and the step masks pad lanes on device from the
    per-shard counts.
    """

    def __init__(self, *args, plane: ServePlane, **kwargs):
        cfg = plane.cfg
        kwargs.setdefault("batch", cfg.batch_per_shard)
        kwargs["max_msg_len"] = cfg.max_msg_len
        kwargs["comb_slots"] = 0  # the plane step IS the kernel choice
        super().__init__(*args, **kwargs)
        self.plane = plane
        if self.batch != cfg.batch_per_shard:
            raise ValueError("stage batch must equal plane batch_per_shard")
        self.n_shards = cfg.n_devices
        # one accumulator per shard (per input ring); VerifyStage's _gen
        # acc is unused on this subclass
        self._shards = [_Acc() for _ in range(self.n_shards)]
        self._use_shard_schema(self.n_shards)
        self.metrics.counters["mesh_devices"] = self.n_shards
        # the plane's step sees all the shards' lanes as one batch
        from firedancer_tpu.ops.sigverify import fold_lanes

        self.metrics.counters[fmet.KERNEL_FOLD_LANES] = fold_lanes(
            self.batch * self.n_shards)

    # -- observability ------------------------------------------------------

    @classmethod
    def extra_schema(cls) -> fmet.MetricsSchema:
        s = VerifyStage.extra_schema()
        s.counter("poh_spans_ok", "PoH self-audit spans verified on-mesh")
        s.counter("poh_spans_fail", "PoH self-audit spans that FAILED")
        return s

    # -- mux callbacks -------------------------------------------------------

    # this subclass accumulates per SHARD in after_frag below; the base
    # class's drain-table batch intake would route through the wrong
    # accumulator — keep the per-frag path
    sweep_frags = None

    def before_frag(self, in_idx: int, seq: int, sig: int) -> bool:
        return True  # the router already sharded; never re-filter

    def after_frag(self, in_idx: int, meta, payload: bytes) -> None:
        # the intake rules (parse incl. the packed-offset fast path,
        # dedup tag, length + fit guards) are VerifyStage._intake — one
        # implementation across both verify lanes
        got = self._intake(payload)
        if got is None:
            return
        sigs, msg, signers, t, packed = got
        acc = self._shards[in_idx]
        if acc.elems and len(acc.elems) + len(sigs) > self.batch:
            # this shard's lane range is full: close the WHOLE step (the
            # fixed shape ships every shard's partial fill, masked)
            self._close_batch()
            acc = self._shards[in_idx]
        start = len(acc.elems)
        for s, pk in zip(sigs, signers):
            acc.elems.append((msg, s, pk))
        acc.ranges.append((start, len(acc.elems)))
        acc.payloads.append(payload)
        acc.descs.append((t, packed))
        acc.tsorigs.append(int(meta[MCACHE_COL_TSORIG]))
        if len(acc.elems) >= self.batch:
            self._close_batch()

    def _open_accs(self):
        return self._shards

    def during_housekeeping(self) -> None:
        self._drain(block=False)

    # -- the sharded dispatch ------------------------------------------------

    def _close_batch(self, acc=None, why: int = CLOSE_FULL) -> None:
        """Close the WHOLE step (every shard's partial fill) and dispatch
        it.  A shard that filled closes it whatever is in flight — the
        step takes the window's second place, or waits for the head
        where that is taken; the reap it waits for may itself close the
        step (a shard held past its deadline and the window open to it
        after the reap), so the fills are read after it.  The deadline comes
        through VerifyStage._deadline_close, which asks first."""
        accs = self._shards
        if not self._window_has_room() and any(a.elems for a in accs):
            self._waits_for_place(why)
            self._drain(block=True)
        n_elems = sum(len(a.elems) for a in accs)
        if n_elems == 0:
            return
        cfg = self.plane.cfg
        per = cfg.batch_per_shard
        b = cfg.batch
        mm = cfg.max_msg_len
        msg = np.zeros((mm, b), dtype=np.uint8)
        ln = np.zeros((b,), dtype=np.int32)
        sg = np.zeros((64, b), dtype=np.uint8)
        pk = np.zeros((32, b), dtype=np.uint8)
        n_real = np.zeros((self.n_shards,), dtype=np.int32)
        payloads, descs, ranges, tsorigs = [], [], [], []
        for s, acc in enumerate(accs):
            base = s * per
            n_real[s] = len(acc.elems)
            for j, (m, sig_b, pk_b) in enumerate(acc.elems):
                col = base + j
                mrow = np.frombuffer(m, dtype=np.uint8)
                msg[: len(mrow), col] = mrow
                ln[col] = len(mrow)
                sg[:, col] = np.frombuffer(sig_b, dtype=np.uint8)
                pk[:, col] = np.frombuffer(pk_b, dtype=np.uint8)
            payloads.extend(acc.payloads)
            descs.extend(acc.descs)
            ranges.extend((a + base, bb + base) for a, bb in acc.ranges)
            tsorigs.extend(acc.tsorigs)
            self.metrics.inc(f"shard_elems_s{s}", len(acc.elems))
            acc.clear()
        if self.precomputed_ok:
            result = _PrecomputedPending(b)
        else:
            result = self.plane.submit(msg, ln, sg, pk, n_real)
        self._inflight.append(
            _VPending(
                payloads=payloads,
                descs=descs,
                elem_ranges=ranges,
                tsorigs=tsorigs,
                n_elems=n_elems,
                result=result,
            )
        )
        self._count_dispatch(n_elems, why, len(self._inflight))

    # the drain loop itself is VerifyStage._drain (ONE implementation of
    # the txn-level pass-iff-all-pass rule); these hooks adapt it to the
    # Pending the serving step returns

    def _result_ready(self, head) -> bool:
        return head.result.ready()

    def _result_mask(self, head):
        pend: Pending = head.result
        if pend.poh_real:
            # the PoH self-audit spans that rode this step: account for
            # them exactly once, when the step's results are consumed
            n_ok = int(np.asarray(pend.poh_ok).sum())
            self.metrics.inc("poh_spans_ok", n_ok)
            self.metrics.inc("poh_spans_fail", pend.poh_real - n_ok)
            pend.poh_real = 0
        return np.asarray(pend.ok)

    def flush(self) -> None:
        self._close_batch(why=CLOSE_DEADLINE)
        while self._inflight:
            self._drain(block=True)


class _PrecomputedPending(Pending):
    """Bench instrument: the all-pass mask with no device dispatch (the
    VerifyStage precomputed_ok analog for the sharded stage)."""

    def __init__(self, batch: int):
        super().__init__(
            ok=np.ones((batch,), dtype=bool), n_ok=batch,
            parity=None, poh_ok=None,
            n_real=np.zeros(0, dtype=np.int32), fec_real=0, poh_real=0,
        )

    def ready(self) -> bool:
        return True
