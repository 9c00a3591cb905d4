"""Rule registry + Finding type shared by both fdlint halves.

A Rule is identity + documentation: the checkers (topo_check, ast_rules)
emit Findings tagged with a registered rule ID, and the CLI / baseline /
suppression machinery works purely on those IDs, so rule logic and rule
policy never entangle (the shape of the reference's per-check error
paths in fd_topob.c, which FD_LOG_ERR a stable message per invariant).
"""

from __future__ import annotations

from dataclasses import dataclass, field

SEV_ERROR = "error"
SEV_WARNING = "warning"


@dataclass(frozen=True)
class Rule:
    id: str  # stable: FD1xx topology, FD2xx AST
    name: str  # short kebab-case handle
    severity: str  # SEV_ERROR | SEV_WARNING
    summary: str  # one line, shown by --list-rules


@dataclass
class Finding:
    rule: str
    path: str  # source file, or "topo:<label>" for topology findings
    line: int  # 1-based; 0 for topology findings
    msg: str
    suppressed: str | None = None  # None, "inline", or "baseline"

    def format(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        sev = get_rule(self.rule).severity
        sup = f" [suppressed: {self.suppressed}]" if self.suppressed else ""
        return f"{loc}: {self.rule} [{sev}] {self.msg}{sup}"


_RULES: dict[str, Rule] = {}


def _rule(id: str, name: str, severity: str, summary: str) -> Rule:
    r = Rule(id, name, severity, summary)
    assert id not in _RULES, f"duplicate rule id {id}"
    _RULES[id] = r
    return r


def get_rule(id: str) -> Rule:
    return _RULES[id]


def all_rules() -> list[Rule]:
    return [_RULES[k] for k in sorted(_RULES)]


# -- topology rules (FD1xx): the fd_topob pre-boot invariants ---------------

FD101 = _rule(
    "FD101", "topo-multi-producer", SEV_ERROR,
    "link has more than one producing stage (mcache is single-producer)",
)
FD102 = _rule(
    "FD102", "topo-no-producer", SEV_ERROR,
    "stage consumes a link no stage produces (orphan consumer)",
)
FD103 = _rule(
    "FD103", "topo-no-consumer", SEV_ERROR,
    "link is produced but no stage consumes it (producer stalls at depth)",
)
FD104 = _rule(
    "FD104", "topo-depth-pow2", SEV_ERROR,
    "link depth is not a power of two (mcache line index is seq & (depth-1))",
)
FD105 = _rule(
    "FD105", "topo-dcache-small", SEV_ERROR,
    "dcache_sz override below DCache.footprint(mtu, depth): frags in flight"
    " would be overwritten before consumers read them",
)
FD106 = _rule(
    "FD106", "topo-fseq-underprovision", SEV_ERROR,
    "link declares fewer fseq slots (n_consumers) than consuming stages:"
    " credit flow cannot see the extra consumers and will overrun them",
)
FD107 = _rule(
    "FD107", "topo-credit-deadlock", SEV_ERROR,
    "cycle of credit-gated stages: every stage on the loop stops consuming"
    " when backpressured, so the loop can wedge permanently",
)
FD108 = _rule(
    "FD108", "topo-dup-name", SEV_ERROR,
    "duplicate stage or link name (shm segment names would collide)",
)
FD109 = _rule(
    "FD109", "topo-unknown-link", SEV_ERROR,
    "stage wiring references a link the topology never declared",
)
FD110 = _rule(
    "FD110", "topo-unpicklable-builder", SEV_ERROR,
    "stage builder is not a module-level callable: it cannot pickle into"
    " the spawned child (fork is unusable with XLA, see runtime/topo.py)",
)
FD111 = _rule(
    "FD111", "topo-isolated-stage", SEV_WARNING,
    "stage declares wiring but neither produces nor consumes any link",
)

# -- AST rules (FD2xx): hot-loop + spawn discipline -------------------------

FD200 = _rule(
    "FD200", "parse-error", SEV_ERROR,
    "file does not parse as Python (the rest of the rules never ran on it)",
)
FD201 = _rule(
    "FD201", "host-sync-in-frag", SEV_ERROR,
    "host-sync call (.item()/np.asarray/jax.device_get/block_until_ready/"
    "float(device_val)) inside a before_frag/during_frag/after_frag body:"
    " blocks the stage on the device per frag, serializing the pipeline",
)
FD202 = _rule(
    "FD202", "wallclock-in-frag", SEV_ERROR,
    "wall-clock read (time.time/monotonic/perf_counter) inside a frag"
    " callback: per-frag syscall cost — stamp deadlines in before_credit"
    " (run unconditionally every iteration) or during_housekeeping",
)
FD203 = _rule(
    "FD203", "global-random", SEV_ERROR,
    "module-level random.* call (process-global, unseeded): use the seeded"
    " utils/rng.Rng (or a random.Random instance) for reproducible runs",
)
FD204 = _rule(
    "FD204", "salted-hash-seed", SEV_ERROR,
    "builtin hash() call: str/bytes hashing is salted per process"
    " (PYTHONHASHSEED), so derived seeds/keys differ across spawned"
    " children and runs — use zlib.crc32 or hashlib",
)
FD205 = _rule(
    "FD205", "nonmodule-builder", SEV_ERROR,
    "lambda / nested function / partial passed as a stage builder: will not"
    " pickle under the spawn start method",
)
FD206 = _rule(
    "FD206", "bare-except", SEV_WARNING,
    "bare except (or except BaseException) without re-raise: swallows"
    " KeyboardInterrupt/SystemExit and can eat a stage's HALT/teardown path",
)
FD207 = _rule(
    "FD207", "ffi-in-frag", SEV_ERROR,
    "native/FFI crossing (ctypes, a *native* module or a _lib handle)"
    " inside a frag callback: ~1-3us of marshalling per frag — batch native"
    " calls at burst granularity (the fd_exec_batch shape)",
)
FD208 = _rule(
    "FD208", "alloc-in-metric-hot-path", SEV_ERROR,
    "allocation/formatting (f-string, dict/list/set literal or"
    " comprehension, str.format) passed to observe()/trace() inside a frag"
    " callback: the metric/trace hot path must stay allocation-free —"
    " precompute labels and pass scalars",
)
FD209 = _rule(
    "FD209", "unseeded-randomness-in-chaos", SEV_ERROR,
    "non-seeded entropy source (os.urandom, secrets.*, uuid4, unseeded"
    " random.Random()/np.random.default_rng()) inside the chaos package:"
    " every scenario must thread the run seed through utils/rng —"
    " reproducible replay is the harness's core contract",
)
FD210 = _rule(
    "FD210", "transfer-in-frag", SEV_ERROR,
    "host<->device transfer (jax.device_put / .copy_to_host_async) inside a"
    " frag callback in runtime/ or parallel/: a per-frag transfer"
    " serializes the chips behind the host — commit arrays at batch-close"
    " granularity (runtime/verify.place_rows), never per frag"
    " (device->host syncs are FD201's half of the same rule)",
)
FD211 = _rule(
    "FD211", "alloc-sort-in-pack-frag", SEV_ERROR,
    "sort (sorted()/.sort()/bisect.insort*) or per-frag comprehension inside"
    " a frag callback in a pack module: pack's intake runs per verified frag"
    " and a sort or container build there is O(pool) work multiplied by"
    " ingress rate — pool maintenance belongs in the ordered structure"
    " (scheduler's insort at insert is the POOL's cost, paid once per"
    " accepted txn; the native lane pays it in C++), and burst handoff must"
    " be append-only (NativePackStage.after_frag's shape)",
)
FD212 = _rule(
    "FD212", "ctypes-alloc-in-frag", SEV_ERROR,
    "per-frag ctypes allocation/marshalling churn (create_string_buffer,"
    " byref/cast/addressof temporaries, `(c_type * n)()` array construction)"
    " inside a frag callback: each builds a fresh ctypes object per frag on"
    " top of the crossing FD207 already bans — native endpoints cache their"
    " byref/out-buffer objects at construction (tango/native.py) and cross"
    " the FFI once per drained burst (fdr_drain / fdr_publish_burst)",
)
FD214 = _rule(
    "FD214", "sync-outside-reap-point", SEV_ERROR,
    "device->host sync (np.asarray/np.array on device values, .item(),"
    " .block_until_ready(), jax.device_get) inside a verify-stage method"
    " that is NOT the designated reap point (_drain/_nv_drain, the fetch"
    " they share _mask_of, flush): the verify stage keeps a"
    " >= 8 deep async in-flight window and exactly one place may block on"
    " device results — a sync anywhere else (intake, batching, submit,"
    " housekeeping) quietly serializes the window back to depth 1",
)
FD215 = _rule(
    "FD215", "blocking-wait-in-hot-hook", SEV_ERROR,
    "blocking sleep/wait (time.sleep, zero-arg .wait()/.join()/.acquire())"
    " inside a frag callback or a stage-loop hook (before_credit,"
    " after_credit, during_housekeeping): the slot-clock plane"
    " (runtime/slot_clock) is the only sanctioned deadline authority — a"
    " stage that sleeps in its loop stalls every link it serves and"
    " cannot be paced, sealed, or missed on the schedule; wait by"
    " RETURNING from the hook and re-checking the clock next sweep",
)
FD213 = _rule(
    "FD213", "hash-alloc-in-shred-frag", SEV_ERROR,
    "per-frag hashing or bytes assembly (hashlib/merkle-helper call,"
    " bytes()/b''.join()/bytes-literal concat) inside a frag callback of a"
    " shred-path module: merkle node churn and per-shred concat belong at"
    " FEC-set granularity — accumulate entries append-only (bytearray"
    " extend) and hash/frame once per closed batch (the shredder's"
    " entry_batch_to_fec_sets shape; the native lane does it all in one"
    " crossing)",
)
FD216 = _rule(
    "FD216", "txn-reparse-in-bank-frag", SEV_ERROR,
    "txn re-parse (txn_parse/txn_unpack/message-level parse) inside a frag"
    " callback of a bank-path module: every frag a bank consumes already"
    " carries `payload || packed descriptor || u16 trailer` — verify parsed"
    " it once and pack preserved the trailer precisely so the commit path"
    " reads offsets out of the descriptor (sig/blockhash/account slices by"
    " u16 index) instead of re-paying the parse per txn; a parse here is"
    " pure duplicate work on the hottest path (the native sweep reads the"
    " same descriptor bytes in C)",
)
FD217 = _rule(
    "FD217", "python-crypto-in-ingress-frag", SEV_ERROR,
    "per-datagram Python crypto (AES-GCM seal/open, GHASH, AES block"
    " encrypt, header-protection mask, packet seal/open) or a per-datagram"
    " recvfrom inside an ingress frag callback / loop hook / _on_datagram"
    " of a net module that registers a native sweep client: the short-"
    " header steady state belongs to the one-crossing native lane"
    " (fd_net's DCID lookup + HP unmask + GCM open + frame walk), and the"
    " socket drains through the batched sweep — per-datagram Python"
    " crypto or recvfrom there silently re-serializes ingress to the"
    " pure-Python rate; keep it in the _py_* punt lane the native client"
    " falls back to",
)
FD218 = _rule(
    "FD218", "python-funk-mutation-in-bank-frag", SEV_ERROR,
    "per-record Python funk mutation (rec_insert/rec_remove, _root_merge,"
    " txn_recs_for_write) inside a frag callback / loop hook of a"
    " bank-path module that arms the native funk lane (set_funk): with"
    " the lane armed, session commits write records straight into the"
    " shm map inside the fdr_sweep crossing — a per-record Python write"
    " there re-pays a map probe + allocation per record on the commit"
    " hot path; batch host-side writes through rec_insert_batch at burst"
    " granularity",
)
FD219 = _rule(
    "FD219", "python-write-on-native-owned-metric", SEV_ERROR,
    "a Python-side metrics write (observe/observe_batch/inc/record/"
    "store/store_hist) on a NATIVE-OWNED metric name (the nsweep_*"
    " block + nbank_txn_lat_ns) in a module that registers a native"
    " sweep client: those shm words are written in-line by C from inside"
    " the fdr_sweep crossing, and the Python facade deliberately never"
    " tracks them — a facade write either double-counts the metric or"
    " zero-clobbers the C increments at the next housekeeping flush;"
    " declare a separate (non-native) metric for host-side observations",
)

# -- race/crash-domain rules (FD4xx): ring discipline + restart safety ------
#
# Registered here, implemented in race_check.py (the fdrace half of the
# gate).  The crash-domain map is reconstructed statically from the same
# topology factories the FD1xx pass checks: one StageSpec = one OS
# process = one crash domain (a fused stage like FusedPohShredStage is
# ONE spec and therefore ONE domain).

FD401 = _rule(
    "FD401", "crossdomain-mutable-state", SEV_ERROR,
    "module-level mutable state mutated at runtime in a module reachable"
    " from two or more crash domains: under the spawn start method every"
    " domain holds a divergent private copy, so any shared-state"
    " assumption silently breaks — coordinate through a ring or shm"
    " segment instead",
)
FD402 = _rule(
    "FD402", "restart-unsafe-frag-state", SEV_ERROR,
    "stage used by a restartable crash domain accumulates cross-sweep"
    " in-memory state in a frag callback (or is a source stage without a"
    " resume_from_rings override): a SIGKILL + in-place respawn loses"
    " that state and the replay-dedup ledger only covers the ring wire,"
    " breaking the exactly-once contract — restartable stages must be"
    " relay-shaped (frag effects = publishes + metrics only)",
)
FD403 = _rule(
    "FD403", "uncredited-publish", SEV_ERROR,
    "frag callback publishes with the result discarded in a stage class"
    " that neither arms require_credit nor checks credits (cr_avail):"
    " under backpressure try_publish returns False and the consumed frag"
    " silently vanishes from the pipeline — arm self.require_credit ="
    " True (the bank/poh/sign contract) or handle the False return",
)
FD404 = _rule(
    "FD404", "seq-read-after-publish", SEV_ERROR,
    "mcache read-back (query()/table[] load) after publishing to the same"
    " mcache in one function: the published line may already be BUSY or"
    " overwritten by the next lap, so the read races the ring's own"
    " overrun window — producers must trust their seq cursor, never"
    " re-read the ring (the BUSY-bit protocol exists to make consumer"
    " reads detect exactly this)",
)
FD405 = _rule(
    "FD405", "speculative-read-no-recheck", SEV_ERROR,
    "dcache payload read after an mcache query without the second query"
    " re-check: a producer lapping the ring mid-copy hands the consumer"
    " torn payload bytes undetected — the speculative-read protocol is"
    " query, copy, query again and retry on seq change"
    " (tango/shm.py Consumer.poll is the compliant shape)",
)
FD406 = _rule(
    "FD406", "native-fence-discipline", SEV_ERROR,
    "native ring code (native/*.cpp) breaks fence discipline: a shared"
    " seq/fseq cell reached through a non-atomic pointer, a seq or credit"
    " store weaker than memory_order_release, or a speculative dcache"
    " copy with no acquire-ordered seq re-check after the memcpy —"
    " exactly the orderings the Python/NumPy lane gets for free from the"
    " GIL and the C++ lane must spell out",
)
