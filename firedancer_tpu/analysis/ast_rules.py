"""AST lint pass: the hot-loop + spawn discipline rules (FD2xx).

The frag callbacks (`before_frag` / `during_frag` / `after_frag`) are the
per-frag hot path of every stage (runtime/stage.py run_once): anything
per-frag that blocks on the device or enters the kernel is multiplied by
ingress rate.  The reference gets this discipline for free — its tiles
are C loops with no allocator and no syscalls in the frag path — so the
linter is where this codebase encodes the same rule.

Scope notes (deliberate):
  - FD201/FD202 look at the DIRECT bodies of functions named like frag
    callbacks (any class; nested calls are not traced — keep helpers
    called from frag paths clean by keeping the callbacks thin);
  - `float(...)` only counts as a host sync when its argument is not a
    literal/constant expression (e.g. `float(mask[i])` on a device array
    blocks; `float("inf")` does not);
  - suppression is per-line: `# fdlint: disable=FD204 -- reason`, with
    multiple IDs comma-separated.
"""

from __future__ import annotations

import ast
import os
import re

from .framework import Finding

FRAG_CALLBACKS = frozenset({"before_frag", "during_frag", "after_frag"})

# FD201: attribute calls that force a device->host sync on jax arrays
_SYNC_ATTRS = frozenset({"item", "block_until_ready"})
# FD201: module-level calls that materialize a device array on host
# (canonical module names; import aliasing is resolved before matching)
_SYNC_CALLS = frozenset({
    ("jax", "device_get"),
    ("np", "asarray"),
    ("np", "array"),
    ("jnp", "asarray"),  # per-frag host->device transfer: same cost class
})
# FD202: wall-clock reads
_CLOCK_CALLS = frozenset({
    "time", "monotonic", "monotonic_ns", "perf_counter", "perf_counter_ns",
    "time_ns", "clock_gettime",
})
# FD203: process-global random module entry points (instances are fine)
_RANDOM_GLOBALS = frozenset({
    "random", "randrange", "randint", "uniform", "choice", "choices",
    "shuffle", "sample", "getrandbits", "randbytes", "gauss", "betavariate",
    "expovariate", "normalvariate", "seed",
})

_DISABLE_RE = re.compile(r"#\s*fdlint:\s*disable=([A-Z0-9, ]+)")

# FD208: metric/trace entry points whose per-frag arguments must stay
# allocation-free (a label f-string or a dict literal per observation is
# a hidden allocator in the hottest path the stage has)
_METRIC_HOT_ATTRS = frozenset({"observe", "trace", "record"})

# FD209: non-seeded entropy entry points forbidden inside the chaos
# package (firedancer_tpu/chaos/): reproducible replay from the run seed
# is the harness's contract, so every random choice must come from
# utils/rng.Rng (or something seeded from it).  Bare names only match
# from-imports (a method on a SEEDED instance, e.g. r.getrandbits(), is
# compliant and must not trip the rule); module-qualified matching
# covers the whole secrets surface.
_FD209_BARE = frozenset({
    "urandom", "token_bytes", "token_hex", "token_urlsafe",
    "randbelow", "getrandbits", "uuid4", "SystemRandom",
})
# builder calls that allocate a fresh container per invocation
_ALLOC_BUILTINS = frozenset({"dict", "list", "set", "tuple"})

# FD212: ctypes entry points that allocate/marshal a fresh object per
# call — per-frag churn on top of the crossing cost FD207 already flags.
# Native endpoints cache these at construction (tango/native.py).
_CTYPES_CHURN = frozenset({
    "create_string_buffer", "create_unicode_buffer", "byref", "cast",
    "addressof", "string_at",
})

# FD213: hashing entry points whose per-frag use is merkle node churn in
# the shred path — bare-name matches cover from-imports of the hashlib
# constructors and the bmtree helpers the shredder/resolver build trees
# with; `hashlib.*` is matched module-qualified (any attr).  Scoped to
# shred-path modules so a hash in an unrelated stage stays FD-clean.
_FD213_HASH_NAMES = frozenset({
    "sha256", "sha512", "sha3_256", "blake2b", "blake2s",
    "hash_leaf_full", "hash_leaf", "hash_node", "tree_layers",
    "root32_from_layers", "verify_proof",
})
_SHRED_PATH_FILES = frozenset({
    "shredder.py", "shred_stage.py", "shred_native.py", "store.py",
    "fec_resolver.py",
})

# FD216: txn re-parse entry points whose per-frag use in a bank-path
# module re-pays verify's parse — the verified frag already carries
# `payload || packed descriptor || u16 trailer`, so the commit path
# reads descriptor offsets, never reconstructs the Txn.  Bare names
# cover from-imports; `ft.txn_parse`-style is matched by last component
# (struct.unpack stays FD-clean: "unpack" alone is not in the set).
_FD216_PARSE_NAMES = frozenset({
    "txn_parse", "txn_unpack", "parse_txn", "message_parse",
})
_BANK_PATH_FILES = frozenset({"bank.py", "bank_native.py"})

# FD214: the async-window discipline (ISSUE 13).  A verify stage keeps
# >= 8 device batches in flight; ONE designated reap point consumes
# device results, and a device->host sync anywhere else in the stage
# (np.asarray on a future, .item(), block_until_ready) silently
# serializes the window back to depth 1.  Scoped to the verify-stage
# classes in the verify-path modules; the reap-point methods are the
# allowlist.  Frag callbacks are excluded here — FD201 already owns
# them.
_FD214_FILES = frozenset({"verify.py", "verify_native.py"})
# _mask_of is the fetch both lanes' reaps (_nv_drain, _drain's _reap)
# share; the boot-time warm-up syncs outside the class
# (runtime/verify.warm_program), before any batch is in flight
_FD214_REAP_METHODS = frozenset({
    "_drain", "_nv_drain", "_mask_of", "flush",
})
_FD214_SYNC_CALLS = frozenset({
    ("np", "asarray"), ("np", "array"), ("jax", "device_get"),
})

# FD215: blocking waits in the stage loop's hot hooks.  The slot-clock
# plane (runtime/slot_clock) is the only sanctioned deadline authority;
# a time.sleep (or an unbounded zero-arg .wait()/.join()/.acquire()) in
# a frag callback OR a loop hook (before_credit / after_credit /
# during_housekeeping) stalls every link the stage serves and makes its
# slots unpaceable.  The loop hooks are included because they run every
# run_once sweep — a sleep there is a sleep in the hot loop even though
# no frag is in hand.
_HOT_HOOKS = frozenset({
    "during_housekeeping", "before_credit", "after_credit",
})
_FD215_BLOCKING_ATTRS = frozenset({"wait", "join", "acquire"})

# FD217: per-datagram Python crypto / recvfrom in the ingress hot path
# of a net module that REGISTERS a native sweep client — the
# `self._net_client` / `self._sweep_client` assignment is the gate, so
# a module that never arms the lane keeps its Python receive loop
# un-flagged.  Scope is LEXICAL: the flagged calls may live only in the
# _py_* punt helpers the hot path falls back to, never in a frag
# callback, a loop hook, or _on_datagram itself.
_NET_PATH_FILES = frozenset({"net.py", "net_native.py"})
_FD217_INGRESS_CBS = frozenset({"_on_datagram"})
_FD217_CRYPTO_NAMES = frozenset({
    "_ghash", "ghash", "_ghash_mul", "ghash_mul", "encrypt_block",
    "seal_packet", "open_packet", "_hp_mask", "hp_mask",
})
_FD217_SWEEP_ATTRS = frozenset({"_net_client", "_sweep_client"})

# FD218: per-record Python funk mutation in the bank commit hot path of
# a module that ARMS the native funk lane — the `.set_funk(...)` call is
# the gate, so a pure-Python bank keeps its funk writes un-flagged.
# Once the lane is armed, the session commit writes records straight
# into the shm map inside the fdr_sweep crossing and the sanctioned
# host-side write is rec_insert_batch at burst granularity; a
# per-record rec_insert/rec_remove (or a _root_merge / a
# txn_recs_for_write dict materialization) in a frag callback or loop
# hook re-pays a map probe + allocation per record on the hottest path.
# rec_insert_batch itself is exempt by exact-name match.
_FD218_FUNK_MUTATORS = frozenset({
    "rec_insert", "rec_remove", "_root_merge", "txn_recs_for_write",
})

# FD219: Python-side write on a NATIVE-OWNED metric name in a module
# that registers a native sweep client (same `self._net_client` /
# `self._sweep_client` gate as FD217).  These shm words are written
# in-line by C from inside the fdr_sweep crossing and the Metrics
# facade deliberately never tracks them — a Python observe()/inc()
# either double-counts or zero-clobbers the C increments at the next
# housekeeping flush.  The name set mirrors
# utils/metrics.native_owned_names() (a test asserts they stay equal).
_FD219_NATIVE_OWNED = frozenset({
    "nsweep_frags", "nsweep_crossings",
    "nsweep_drain_ns", "nsweep_callback_ns", "nsweep_apply_ns",
    "nsweep_publish_ns", "nsweep_lat_ns", "nbank_txn_lat_ns",
})
_FD219_WRITERS = frozenset({
    "observe", "observe_batch", "inc", "record", "store", "store_hist",
})


def _fd208_offender(arg: ast.AST) -> str | None:
    """Why `arg` allocates/formats, or None if it looks scalar-cheap."""
    for node in ast.walk(arg):
        if isinstance(node, ast.JoinedStr):
            return "f-string"
        if isinstance(node, (ast.Dict, ast.List, ast.Set)):
            return "container literal"
        if isinstance(node, (ast.DictComp, ast.ListComp, ast.SetComp,
                             ast.GeneratorExp)):
            return "comprehension"
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name) \
                    and node.func.id in _ALLOC_BUILTINS:
                return f"{node.func.id}() construction"
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "format":
                return "str.format()"
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod) \
                and isinstance(node.left, (ast.Constant, ast.JoinedStr)) \
                and isinstance(getattr(node.left, "value", None), str):
            return "%-formatting"
    return None


def _disabled_lines(source: str) -> dict[int, set[str]]:
    """line -> rule IDs inline-suppressed on that line."""
    out: dict[int, set[str]] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _DISABLE_RE.search(text)
        if m:
            out[i] = {t.strip() for t in m.group(1).split(",") if t.strip()}
    return out


def _dotted(node: ast.AST) -> tuple[str, ...] | None:
    """`a.b.c` -> ("a","b","c"); None for non-name chains."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


# canonical short names the rule tables are written against
_MOD_CANON = {
    "numpy": "np", "np": "np",
    "jax.numpy": "jnp", "jnp": "jnp",
    "jax": "jax", "time": "time", "random": "random",
}


def _native_imports(tree: ast.Module):
    """Names bound to native-FFI surfaces for FD207/FD212: modules whose
    last dotted segment mentions `native` (tango.native,
    protocol.txn_native, flamenco.exec_native, tango.tcache_native,
    utils.nativebuild) plus ctypes itself.  Returns (module aliases,
    from-imported names, ctypes module aliases, ctypes from-imports) —
    the ctypes sets are tracked separately so FD212's churn check never
    fires on a *native*-module helper that happens to share a name."""
    mods: set[str] = set()
    funcs: set[str] = set()
    cmods: set[str] = set()
    cfuncs: dict[str, str] = {}  # bound name -> original ctypes name
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                last = a.name.split(".")[-1]
                if "native" in last or a.name == "ctypes":
                    mods.add(a.asname or a.name.split(".")[0])
                if a.name == "ctypes":
                    cmods.add(a.asname or "ctypes")
        elif isinstance(node, ast.ImportFrom) and node.module:
            last = node.module.split(".")[-1]
            if "native" in last or node.module == "ctypes":
                for a in node.names:
                    funcs.add(a.asname or a.name)
            if node.module == "ctypes":
                for a in node.names:
                    cfuncs[a.asname or a.name] = a.name
            if "native" not in last and node.module != "ctypes":
                for a in node.names:
                    # `from pkg import txn_native as tn`: a native MODULE
                    # imported by name — calls go through its alias
                    if "native" in a.name:
                        mods.add(a.asname or a.name)
    return mods, funcs, cmods, cfuncs


def _import_aliases(tree: ast.Module):
    """Resolve import aliasing so `import numpy as xp` / `from time
    import monotonic as mono` cannot evade the module-call rules.

    Returns (mod_alias -> canonical short name,
             bare name -> (canonical module, original func name))."""
    mods: dict[str, str] = {}
    funcs: dict[str, tuple[str, str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                canon = _MOD_CANON.get(a.name)
                if canon:
                    mods[a.asname or a.name.split(".")[0]] = canon
        elif isinstance(node, ast.ImportFrom) and node.module:
            canon = _MOD_CANON.get(node.module)
            if canon:
                for a in node.names:
                    funcs[a.asname or a.name] = (canon, a.name)
    return mods, funcs


def _registers_sweep_client(tree: ast.Module) -> bool:
    """FD217's gate: does this module assign a native sweep client
    (`self._net_client = ...` / `self._sweep_client = ...`) anywhere in
    a class body's subtree?"""
    for node in ast.walk(tree):
        targets: tuple | list = ()
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            targets = (node.target,)
        for t in targets:
            d = _dotted(t)
            if d is not None and len(d) == 2 and d[0] == "self" \
                    and d[1] in _FD217_SWEEP_ATTRS:
                return True
    return False


def _registers_funk_client(tree: ast.Module) -> bool:
    """FD218's gate: does this module arm the native funk lane — a
    `<anything>.set_funk(...)` call anywhere in its subtree?  (The bank
    stage's _arm_native does `self._sweep_client.set_funk(funk, xid)`;
    a module that never arms the lane keeps its Python funk writes
    un-flagged.)"""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "set_funk":
            return True
    return False


def _local_defs(fn: ast.AST) -> set[str]:
    """Function names bound in fn's OWN scope: descend into compound
    statements (if/for/try/with) but not into nested class or function
    bodies, whose defs are not visible as fn-locals."""
    out: set[str] = set()
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.add(node.name)  # the binding is local; its body is not
        elif isinstance(node, (ast.ClassDef, ast.Lambda)):
            pass  # opaque inner scope
        else:
            stack.extend(ast.iter_child_nodes(node))
    return out


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, mods=None, funcs=None, nmods=None,
                 nfuncs=None, cmods=None, cfuncs=None, net_gate=False,
                 funk_gate=False):
        self.path = path
        self.findings: list[Finding] = []
        self._frag_depth = 0  # >0 while inside a frag-callback body
        self._hook_depth = 0  # >0 inside a loop hook (FD215 scope)
        self._ncb_depth = 0  # >0 inside _on_datagram (FD217 scope)
        self._func_stack: list[ast.FunctionDef] = []
        self._mods = mods or {}  # import alias -> canonical module
        self._funcs = funcs or {}  # from-imported name -> (module, func)
        self._nmods = nmods or set()  # FD207: native-module aliases
        self._nfuncs = nfuncs or set()  # FD207: native from-imports
        self._cmods = cmods or set()  # FD212: ctypes module aliases
        self._cfuncs = cfuncs or {}  # FD212: ctypes from-import -> orig
        # FD209 scope: files under a chaos/ package directory
        parts = re.split(r"[/\\]", path)
        self._chaos = "chaos" in parts
        # FD210 scope: the packages whose frag callbacks feed the device
        self._transfer_scope = "runtime" in parts or "parallel" in parts
        # FD211 scope: pack modules (the pack package + the runtime pack
        # stage) — their frag callbacks are the pool intake hot path.
        # Exact matches only: a future packet.py/unpack_utils.py must
        # not inherit the comprehension ban by substring accident.
        self._pack_scope = bool(parts) and (
            "pack" in parts or parts[-1] == "pack_stage.py"
        )
        # FD213 scope: the shred-path modules — their frag callbacks run
        # once per entry/shred and must stay append-only; hashing and
        # shred framing happen at FEC-set granularity
        self._shred_scope = bool(parts) and parts[-1] in _SHRED_PATH_FILES
        # FD216 scope: the bank-path modules — their frag callbacks are
        # the commit hot path and consume pre-parsed verified frags
        self._bank_scope = bool(parts) and parts[-1] in _BANK_PATH_FILES
        # FD217 scope: net ingress modules, gated on the module actually
        # registering a native sweep client (net_gate from the prescan)
        self._net_scope = net_gate and bool(parts) \
            and parts[-1] in _NET_PATH_FILES
        # FD218 scope: bank-path modules, gated on the module actually
        # arming the native funk lane (funk_gate from the prescan)
        self._funk_scope = funk_gate and bool(parts) \
            and parts[-1] in _BANK_PATH_FILES
        # FD219 scope: ANY module that registers a native sweep client —
        # once armed, the nsweep_* words are C-owned everywhere in the
        # file (cold paths double-count just as surely as hot ones)
        self._fd219_scope = net_gate
        # FD214 scope: verify-path modules; the class/method context is
        # tracked below (verify-stage classes only, reap methods exempt)
        self._verify_scope = bool(parts) and parts[-1] in _FD214_FILES
        self._vclass_stack: list[bool] = []  # is-a-verify-stage class?
        self._fd214_method: list[str] = []  # enclosing method per depth

    def _resolve(self, node: ast.Call) -> tuple[str, str] | None:
        """Canonical (module, func) for a call, seeing through `import
        numpy as xp` and `from time import monotonic as mono`."""
        dq = _dotted(node.func)
        if dq is None:
            return None
        if len(dq) == 1:
            return self._funcs.get(dq[0])
        if len(dq) == 3 and dq[:2] == ("jax", "numpy"):
            return ("jnp", dq[2])
        if len(dq) == 2:
            canon = self._mods.get(dq[0]) or _MOD_CANON.get(dq[0])
            if canon:
                return (canon, dq[1])
        return None

    def hit(self, rule: str, node: ast.AST, msg: str) -> None:
        self.findings.append(Finding(
            rule=rule, path=self.path,
            line=getattr(node, "lineno", 0), msg=msg,
        ))

    def _ctypesish(self, node: ast.AST) -> bool:
        """An expression that references a ctypes type: rooted at a
        ctypes module alias or from-import, or a `c_*`-named type (the
        ctypes naming convention).  FD212's array-shape check requires
        this of an operand — AND the file to bind ctypes at all (the
        call-site gate), so neither `(scale * gain)(x)` next to a ctypes
        import nor `(c_scale * gain)(x)` in a ctypes-free file is
        mistaken for `(c_u64 * n)()`."""
        for sub in ast.walk(node):
            d = _dotted(sub)
            if d is None:
                continue
            if d[0] in self._cmods or d[0] in self._cfuncs:
                return True
            if d[-1].startswith("c_"):
                return True
        return False

    # -- scope tracking -----------------------------------------------------

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        is_frag = node.name in FRAG_CALLBACKS and self._in_class()
        is_hook = node.name in _HOT_HOOKS and self._in_class()
        is_ncb = node.name in _FD217_INGRESS_CBS and self._in_class()
        # FD214 method attribution: a def directly inside a verify-stage
        # class opens a method scope; nested defs inherit it
        opens_method = (
            not self._func_stack
            and self._vclass_stack and self._vclass_stack[-1]
        )
        if opens_method:
            self._fd214_method.append(node.name)
        self._func_stack.append(node)
        if is_frag:
            self._frag_depth += 1
        if is_hook:
            self._hook_depth += 1
        if is_ncb:
            self._ncb_depth += 1
        self.generic_visit(node)
        if is_frag:
            self._frag_depth -= 1
        if is_hook:
            self._hook_depth -= 1
        if is_ncb:
            self._ncb_depth -= 1
        self._func_stack.pop()
        if opens_method:
            self._fd214_method.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _in_class(self) -> bool:
        # frag callbacks are methods; a free function named after_frag is
        # someone's helper, not the hot path
        return bool(self._class_depth)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_depth += 1
        # FD214: a verify-stage class by name or by base (a subclass
        # inherits the async-window discipline)
        def _base_name(b: ast.AST) -> str:
            d = _dotted(b)
            return d[-1] if d else ""

        is_vs = self._verify_scope and (
            "VerifyStage" in node.name
            or any("VerifyStage" in _base_name(b) for b in node.bases)
        )
        self._vclass_stack.append(is_vs)
        self.generic_visit(node)
        self._vclass_stack.pop()
        self._class_depth -= 1

    _class_depth = 0

    # -- rules --------------------------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        mf = self._resolve(node)
        if self._frag_depth:
            self._check_frag_call(node, mf)
        if self._frag_depth or self._hook_depth:
            self._check_fd215(node, mf)
        if self._net_scope and (self._frag_depth or self._hook_depth
                                or self._ncb_depth):
            self._check_fd217(node)
        if self._funk_scope and (self._frag_depth or self._hook_depth):
            self._check_fd218(node)
        if self._fd219_scope:
            self._check_fd219(node)
        self._check_fd214(node, mf)
        if mf and mf[0] == "random" and mf[1] in _RANDOM_GLOBALS:
            self.hit("FD203", node,
                     f"process-global random.{mf[1]}() — use a seeded"
                     " utils/rng.Rng or random.Random instance")
        if isinstance(node.func, ast.Name) and node.func.id == "hash" \
                and len(node.args) == 1:
            self.hit("FD204", node,
                     "builtin hash() is salted per process"
                     " (PYTHONHASHSEED); use zlib.crc32/hashlib for"
                     " stable values")
        if self._chaos:
            self._check_chaos_entropy(node)
        self._check_builder_arg(node)
        self.generic_visit(node)

    def _check_fd215(self, node: ast.Call,
                     mf: tuple[str, str] | None) -> None:
        """FD215: blocking sleep/wait inside a frag callback or loop
        hook.  time.sleep anywhere in them is a hard hit; a zero-arg
        .wait()/.join()/.acquire() is the unbounded-blocking shape
        (str.join(iterable) and bounded waits carry arguments, so they
        never match).  The slot-clock plane is the only deadline
        authority — waiting means returning and re-checking the clock
        next sweep."""
        if mf == ("time", "sleep"):
            where = ("frag callback" if self._frag_depth
                     else "stage-loop hook")
            self.hit("FD215", node,
                     f"time.sleep in a {where}: the stage loop must"
                     " never block — pace against runtime/slot_clock and"
                     " return until due")
            return
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _FD215_BLOCKING_ATTRS
                and not node.args and not node.keywords):
            where = ("frag callback" if self._frag_depth
                     else "stage-loop hook")
            self.hit("FD215", node,
                     f"unbounded .{node.func.attr}() in a {where}:"
                     " zero-arg wait/join/acquire blocks the stage loop"
                     " indefinitely — bound it and move it off the hot"
                     " loop (the slot clock is the deadline authority)")

    def _check_fd217(self, node: ast.Call) -> None:
        """FD217: per-datagram Python crypto / recvfrom in the ingress
        hot path (frag callback, loop hook, or _on_datagram) of a net
        module that registers a native sweep client.  The native lane
        owns the short-header steady state in one FFI crossing; these
        calls belong only in the _py_* punt helpers it falls back to.

        Shapes: `.recvfrom(...)` (the per-datagram syscall the batched
        sweep replaces), `.seal(iv, ...)` / `.open(iv, ct, tag, ...)`
        (the AesGcm surface — the arg-count floors keep builtin
        file-open and zero-arg seals out), and the bare/dotted crypto
        primitives (GHASH, AES block, HP mask, packet seal/open)."""
        if isinstance(node.func, ast.Attribute):
            a = node.func.attr
            if a == "recvfrom":
                self.hit("FD217", node,
                         "per-datagram recvfrom in an ingress hot path"
                         " with a native sweep client registered: the"
                         " batched native sweep owns the socket drain —"
                         " keep the recvfrom loop in the _py_* fallback"
                         " lane")
                return
            if (a == "seal" and len(node.args) >= 1) \
                    or (a == "open" and len(node.args) >= 3):
                self.hit("FD217", node,
                         f"per-datagram Python AES-GCM .{a}() in an"
                         " ingress hot path with a native sweep client"
                         " registered: short-header crypto belongs to"
                         " the one-crossing native lane (fd_net); keep"
                         " Python crypto in the _py_* punt lane")
                return
        fq = _dotted(node.func)
        if fq is not None and fq[-1] in _FD217_CRYPTO_NAMES:
            self.hit("FD217", node,
                     f"per-datagram Python crypto '{'.'.join(fq)}' in an"
                     " ingress hot path with a native sweep client"
                     " registered: GHASH/AES-block/HP-mask per datagram"
                     " re-serializes ingress to the pure-Python rate —"
                     " the native lane does this in one crossing")

    def _check_fd218(self, node: ast.Call) -> None:
        """FD218: per-record Python funk mutation in the bank commit hot
        path (frag callback or loop hook) of a module that arms the
        native funk lane.  With the lane armed, session commits write
        records straight into the shm map inside the fdr_sweep crossing
        and the only sanctioned host-side write is rec_insert_batch at
        burst granularity — a per-record rec_insert/rec_remove, a
        _root_merge, or a txn_recs_for_write dict materialization in a
        frag re-pays a map probe + allocation per record right where the
        native lane just removed it.  Matched by exact last component,
        so rec_insert_batch never trips the rule."""
        fq = _dotted(node.func)
        if fq is not None and len(fq) >= 2 \
                and fq[-1] in _FD218_FUNK_MUTATORS:
            self.hit("FD218", node,
                     f"per-record funk mutation '{'.'.join(fq)}' in a"
                     " bank-path frag callback / loop hook with the"
                     " native funk lane armed: committed records land in"
                     " the shm map inside the fdr_sweep crossing — batch"
                     " any host-side write through rec_insert_batch at"
                     " burst granularity, never per record in a frag")

    def _check_fd219(self, node: ast.Call) -> None:
        """FD219: Python-side write on a native-owned metric name in a
        module that registers a native sweep client.  Matched on an
        attribute call named observe/observe_batch/inc/record/store/
        store_hist whose FIRST argument is a string literal in the
        native-owned set — recorder.record(EV_..., arg) and dynamic
        names never trip it."""
        if not isinstance(node.func, ast.Attribute) \
                or node.func.attr not in _FD219_WRITERS or not node.args:
            return
        a0 = node.args[0]
        if isinstance(a0, ast.Constant) and isinstance(a0.value, str) \
                and a0.value in _FD219_NATIVE_OWNED:
            self.hit("FD219", node,
                     f"Python {node.func.attr}() on native-owned metric"
                     f" '{a0.value}' with a native sweep client"
                     " registered: C writes this shm word from inside"
                     " the fdr_sweep crossing and the facade never"
                     " tracks it — this write double-counts (or"
                     " zero-clobbers the C increments at flush);"
                     " declare a separate non-native metric instead")

    def _check_fd214(self, node: ast.Call,
                     mf: tuple[str, str] | None) -> None:
        """FD214: device sync outside the designated reap point in a
        verify-stage class.  The verify stage's whole point is a >= 8
        deep async in-flight window; ONE method family (_drain /
        _nv_drain and its _result_* hooks, plus flush) is WHERE device
        results become host values.  An np.asarray/.item()/
        block_until_ready anywhere else in the stage stalls the loop on
        the device mid-stream and quietly serializes the window.  Frag
        callbacks are FD201's jurisdiction and are not re-flagged."""
        if not self._fd214_method or self._frag_depth:
            return
        method = self._fd214_method[-1]
        if method in _FD214_REAP_METHODS or method in FRAG_CALLBACKS:
            return
        what = None
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SYNC_ATTRS:
            what = f".{node.func.attr}()"
        elif mf and mf in _FD214_SYNC_CALLS:
            what = f"{'.'.join(mf)}()"
        if what:
            self.hit("FD214", node,
                     f"device sync {what} in verify-stage method "
                     f"'{method}' outside the designated reap point"
                     " (_drain/_mask_of/flush): syncing mid-stream"
                     " serializes the async in-flight window")

    def _check_chaos_entropy(self, node: ast.Call) -> None:
        """FD209: the chaos package must derive ALL randomness from the
        run seed (utils/rng) — an os.urandom/secrets/unseeded-generator
        call anywhere in a scenario silently breaks seed-replay.  The
        process-global random module (random.choice/randint/...) is NOT
        re-checked here: FD203 already flags it repo-wide, chaos
        included."""
        dq = _dotted(node.func)
        if dq is None:
            return
        entropy = (
            dq[0] == "secrets"               # the whole secrets surface
            or dq == ("os", "urandom")
            or dq[-1] in ("uuid4", "SystemRandom")
            or (len(dq) == 1 and dq[0] in _FD209_BARE)  # from-imports
        )
        if entropy:
            self.hit("FD209", node,
                     f"non-seeded entropy '{'.'.join(dq)}' in chaos/:"
                     " thread the run seed through utils/rng.Rng"
                     " (reproducible replay is the harness contract)")
            return
        unseeded = not node.args and not node.keywords
        if dq[-1] == "Random" and unseeded:
            self.hit("FD209", node,
                     "unseeded random.Random() in chaos/: construct from"
                     " the run seed (or use utils/rng.Rng)")
        elif dq[-1] == "default_rng" and len(dq) >= 2 \
                and dq[-2] == "random" and unseeded:
            self.hit("FD209", node,
                     "unseeded np.random.default_rng() in chaos/: pass"
                     " the run seed")

    def _check_frag_call(self, node: ast.Call,
                         mf: tuple[str, str] | None) -> None:
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SYNC_ATTRS:
            self.hit("FD201", node,
                     f".{node.func.attr}() in a frag callback blocks the"
                     " stage on the device per frag")
        if mf and mf in _SYNC_CALLS:
            self.hit("FD201", node,
                     f"{'.'.join(mf)}() in a frag callback forces a"
                     " device->host transfer per frag")
        if isinstance(node.func, ast.Name) and node.func.id == "float" \
                and node.args \
                and not isinstance(node.args[0], ast.Constant):
            self.hit("FD201", node,
                     "float(x) on a non-constant in a frag callback: if x"
                     " is a device scalar this is a blocking sync")
        # FD210: host->device transfers per frag (runtime/ + parallel/).
        # The device->host direction (np.asarray, device_get, .item,
        # block_until_ready) is FD201 above; this closes the other half:
        # a device_put per frag re-commits (and on a mesh re-shards) one
        # element at a time, serializing the chips behind the host.
        if self._transfer_scope:
            if (mf == ("jax", "device_put")) or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "copy_to_host_async"
            ):
                what = (
                    "jax.device_put" if mf == ("jax", "device_put")
                    else ".copy_to_host_async()"
                )
                self.hit("FD210", node,
                         f"{what} in a frag callback: commit device arrays"
                         " at batch-close granularity (runtime/verify"
                         " place_rows), never per frag")
        if mf and mf[0] == "time" and mf[1] in _CLOCK_CALLS:
            self.hit("FD202", node,
                     f"time.{mf[1]}() in a frag callback; stamp deadlines"
                     " in before_credit/during_housekeeping instead"
                     " (after_credit is skipped under backpressure)")
        # FD208: the metric/trace hot path must not allocate or format
        # per frag — a label f-string or a dict-literal tag set built per
        # observation multiplies a hidden allocator by ingress rate
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _METRIC_HOT_ATTRS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                why = _fd208_offender(arg)
                if why:
                    self.hit("FD208", node,
                             f"{why} passed to .{node.func.attr}() in a"
                             " frag callback: metric/trace hot paths must"
                             " be allocation-free — precompute the label/"
                             "edges and pass scalars")
                    break
        # FD211: sorting in a pack frag callback — pool maintenance is
        # O(log n) in the ordered pool (or native); a sorted()/insort in
        # the intake path re-pays O(pool) per frag
        if self._pack_scope:
            is_sort = (
                isinstance(node.func, ast.Name) and node.func.id == "sorted"
            ) or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in ("sort", "insort", "insort_left",
                                       "insort_right")
            )
            if is_sort:
                what = (node.func.id if isinstance(node.func, ast.Name)
                        else node.func.attr)
                self.hit("FD211", node,
                         f"'{what}' in a pack frag callback: per-frag"
                         " sorting is O(pool) x ingress rate — keep the"
                         " pool ordered incrementally (scheduler insort"
                         " at insert / the native treap) and keep the"
                         " frag path append-only")
        # FD212: per-frag ctypes allocation/marshalling churn — a fresh
        # create_string_buffer/byref/cast temporary per frag is an
        # allocator in the hot path even before the crossing itself
        # (FD207) is counted; native endpoints cache these objects at
        # construction (tango/native.py) and cross at burst granularity
        cdq = _dotted(node.func)
        if cdq is not None and (
            (cdq[0] in self._cmods and cdq[-1] in _CTYPES_CHURN)
            or (len(cdq) == 1
                and self._cfuncs.get(cdq[0]) in _CTYPES_CHURN)
        ):
            self.hit("FD212", node,
                     f"per-frag ctypes churn '{'.'.join(cdq)}' in a frag"
                     " callback: cache the buffer/byref at construction"
                     " and batch crossings (fdr_drain/fdr_publish_burst)")
        if (self._cmods or self._cfuncs) \
                and isinstance(node.func, ast.BinOp) \
                and isinstance(node.func.op, ast.Mult) \
                and (self._ctypesish(node.func.left)
                     or self._ctypesish(node.func.right)):
            # `(c_uint64 * n)()` — a fresh ctypes ARRAY TYPE + instance
            # per frag (the costliest churn shape: type creation)
            self.hit("FD212", node,
                     "ctypes array construction `(c_type * n)()` in a"
                     " frag callback: allocate once at construction and"
                     " reuse (tango/native.py's _meta/_out discipline)")
        # FD213: per-frag hashing / bytes assembly in the shred path —
        # merkle node churn (a hashlib/bmtree call per frag) and
        # per-shred concat (bytes()/b"".join) multiply an allocator +
        # compression function by ingress rate; both belong at FEC-set
        # granularity (entry_batch_to_fec_sets / one native crossing)
        if self._shred_scope:
            hq = _dotted(node.func)
            if hq is not None and (
                hq[0] == "hashlib" or hq[-1] in _FD213_HASH_NAMES
            ):
                self.hit("FD213", node,
                         f"per-frag hash '{'.'.join(hq)}' in a shred-path"
                         " frag callback: merkle/hash work belongs at"
                         " FEC-set granularity (close the batch, then"
                         " hash once per set)")
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in ("bytes", "bytearray") \
                    and node.args:
                self.hit("FD213", node,
                         f"{node.func.id}() construction in a shred-path"
                         " frag callback: accumulate entries append-only"
                         " (bytearray extend) and frame shreds once per"
                         " closed FEC set")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "join" \
                    and isinstance(node.func.value, ast.Constant) \
                    and isinstance(node.func.value.value, (bytes, str)):
                self.hit("FD213", node,
                         "per-frag join-concat in a shred-path frag"
                         " callback: shred framing belongs at FEC-set"
                         " granularity, not per entry")
        # FD216: txn re-parse in a bank-path frag callback — the frag is
        # `payload || packed descriptor || u16 trailer` by the verify
        # contract; the commit path reads sig/blockhash/account slices
        # straight out of the descriptor's u16 offsets
        if self._bank_scope:
            pq = _dotted(node.func)
            if pq is not None and pq[-1] in _FD216_PARSE_NAMES:
                self.hit("FD216", node,
                         f"txn re-parse '{'.'.join(pq)}' in a bank-path"
                         " frag callback: the verified frag already"
                         " carries the packed descriptor trailer — read"
                         " offsets from it (bank.py's zero-copy items"
                         " shape) instead of re-paying verify's parse"
                         " per txn")
        # FD207: a native (ctypes) crossing per frag — the crossing
        # itself costs ~1-3us, so it belongs at burst granularity (one
        # call per drained burst / microblock, the fd_exec_batch shape)
        dq = _dotted(node.func)
        if dq is not None and (
            "_lib" in dq
            or dq[0] in self._nmods
            or (len(dq) == 1 and dq[0] in self._nfuncs)
        ):
            self.hit("FD207", node,
                     f"per-frag FFI crossing '{'.'.join(dq)}' in a frag"
                     " callback; batch native calls at burst granularity"
                     " (one crossing per drained burst, as"
                     " flamenco/exec_native.fd_exec_batch)")

    def _check_builder_arg(self, node: ast.Call) -> None:
        """FD205: `<topo>.stage(name, builder, ...)` / `StageSpec(name,
        builder, ...)` with a builder that cannot pickle under spawn."""
        is_stage_call = (
            isinstance(node.func, ast.Attribute) and node.func.attr == "stage"
        ) or (isinstance(node.func, ast.Name) and node.func.id == "StageSpec")
        if not is_stage_call:
            return
        builder = None
        if len(node.args) >= 2:
            builder = node.args[1]
        else:
            for kw in node.keywords:
                if kw.arg == "builder":
                    builder = kw.value
        if builder is None:
            return
        if isinstance(builder, ast.Lambda):
            self.hit("FD205", builder,
                     "lambda stage builder will not pickle under spawn;"
                     " use a module-level function + StageSpec.kwargs")
            return
        bq = _dotted(builder)
        if bq and bq[-1] == "partial" or (
            isinstance(builder, ast.Call)
            and (_dotted(builder.func) or ("",))[-1] == "partial"
        ):
            self.hit("FD205", builder,
                     "functools.partial builder may not pickle under"
                     " spawn; use a module-level function + kwargs")
            return
        if isinstance(builder, ast.Name):
            # a name bound to a def in an enclosing function's LOCAL
            # scope is a closure: flag it.  Only local bindings count —
            # defs inside nested classes/functions don't shadow the
            # module-level builder the Name actually resolves to.
            for fn in self._func_stack:
                if builder.id in _local_defs(fn):
                    self.hit("FD205", builder,
                             f"builder '{builder.id}' is defined inside"
                             f" '{fn.name}' and will not pickle under"
                             " spawn")
                    return

    def visit_BinOp(self, node: ast.BinOp) -> None:
        # FD213 (concat half): `hdr + payload`-style bytes assembly per
        # frag in the shred path.  Only literal-anchored concats are
        # decidable from the AST (an operand that IS a bytes constant);
        # the bytes()/join() construction shapes are caught in
        # _check_frag_call.
        def _bytesish(o: ast.AST) -> bool:
            # a bytes literal, or the `b"\\x00" * n` padding idiom
            if isinstance(o, ast.Constant) and isinstance(o.value, bytes):
                return True
            return isinstance(o, ast.BinOp) \
                and isinstance(o.op, ast.Mult) \
                and any(isinstance(x, ast.Constant)
                        and isinstance(x.value, bytes)
                        for x in (o.left, o.right))

        if self._frag_depth and self._shred_scope \
                and isinstance(node.op, ast.Add) \
                and (_bytesish(node.left) or _bytesish(node.right)):
            self.hit("FD213", node,
                     "bytes-literal concat in a shred-path frag callback:"
                     " per-shred framing belongs at FEC-set granularity —"
                     " accumulate append-only and frame once per set")
        self.generic_visit(node)

    def _visit_comp(self, node: ast.AST) -> None:
        # FD211 (other half): a comprehension per frag in pack intake is
        # a hidden allocator + O(n) pass in the hottest path pack has
        if self._frag_depth and self._pack_scope:
            self.hit("FD211", node,
                     "comprehension in a pack frag callback: per-frag"
                     " container builds multiply an allocator by ingress"
                     " rate — keep the frag path append-only and batch"
                     " the work at burst granularity")
        self.generic_visit(node)

    visit_ListComp = _visit_comp
    visit_SetComp = _visit_comp
    visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        bare = node.type is None or (
            isinstance(node.type, ast.Name)
            and node.type.id == "BaseException"
        )
        if bare:
            reraises = any(
                isinstance(n, ast.Raise) and n.exc is None
                for n in ast.walk(node)
            )
            if not reraises:
                self.hit("FD206", node,
                         "bare except without re-raise swallows"
                         " KeyboardInterrupt/SystemExit (the topology"
                         " teardown path)")
        self.generic_visit(node)


def lint_source(source: str, path: str) -> list[Finding]:
    """All findings for one file; inline suppressions are MARKED (not
    dropped) so reports can show what a disable comment ate."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(rule="FD200", path=path, line=e.lineno or 0,
                        msg=f"file does not parse: {e.msg}")]
    mods, funcs = _import_aliases(tree)
    nmods, nfuncs, cmods, cfuncs = _native_imports(tree)
    linter = _Linter(path, mods, funcs, nmods, nfuncs, cmods, cfuncs,
                     net_gate=_registers_sweep_client(tree),
                     funk_gate=_registers_funk_client(tree))
    linter.visit(tree)
    disabled = _disabled_lines(source)
    for f in linter.findings:
        ids = disabled.get(f.line)
        if ids and f.rule in ids:
            f.suppressed = "inline"
    return linter.findings


def lint_file(path: str) -> list[Finding]:
    with open(path, encoding="utf-8") as fh:
        return lint_source(fh.read(), path)


def lint_path(root: str) -> list[Finding]:
    """Lint a file or a package tree (every .py under root)."""
    if os.path.isfile(root):
        return lint_file(root)
    findings: list[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames if d not in {"__pycache__", ".git"}
        )
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                findings.extend(lint_file(os.path.join(dirpath, fn)))
    return findings
