"""fdlint (firedancer_tpu/analysis) tests: the topology checker's
negative cases per rule ID, the AST rules on synthetic sources, inline +
baseline suppression mechanics, launch()'s fail-fast integration, and —
the tier-1 gate itself — the analyzer running clean over the whole
shipped package via scripts/fdlint.sh.

Also regression-locks the violations fdlint found and this codebase
FIXED rather than baselined:
  - runtime/stage.py seeded its housekeeping RNG with builtin hash(name)
    (process-salted: every spawned child and every run drew a different
    phase) — FD204, now zlib.crc32;
  - runtime/verify.py and runtime/pack_stage.py stamped batch deadlines
    with time.monotonic() INSIDE after_frag (a per-frag syscall on the
    hot path) — FD202, stamping moved to before_credit (the hook
    run_once calls unconditionally; after_credit is skipped under
    backpressure).
"""

import os
import subprocess
import sys

import pytest

from firedancer_tpu.analysis import ast_rules, check_topology
from firedancer_tpu.analysis import baseline as bl
from firedancer_tpu.analysis import cli as fdcli
from firedancer_tpu.analysis.framework import all_rules, get_rule
from firedancer_tpu.analysis.topo_check import TopologyError
from firedancer_tpu.runtime import topo as ft

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "firedancer_tpu")


def _builder(links, cnc):  # a picklable module-level builder for specs
    raise AssertionError("never called: topologies here are checked, not run")


def _ids(findings):
    return sorted({f.rule for f in findings})


# -- rule registry -----------------------------------------------------------


def test_rule_registry_has_both_halves():
    rules = all_rules()
    ids = [r.id for r in rules]
    assert len(ids) == len(set(ids))
    assert len(ids) >= 8  # the acceptance floor, comfortably beaten
    assert any(i.startswith("FD1") for i in ids)  # topology half
    assert any(i.startswith("FD2") for i in ids)  # AST half
    for r in rules:
        assert r.severity in ("error", "warning") and r.summary


def test_cli_list_rules_prints_every_id(capsys):
    assert fdcli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for r in all_rules():
        assert r.id in out


# -- topology checker: negative cases per rule ID ---------------------------


def _wired_pair(depth=64, **link_kw):
    """gen -> l0 -> sink, fully declared and clean."""
    topo = ft.Topology()
    topo.link("l0", depth=depth, mtu=256, **link_kw)
    topo.stage("gen", _builder, outs=["l0"])
    topo.stage("sink", _builder, ins=["l0"])
    return topo


def test_clean_wired_topology_has_no_findings():
    assert check_topology(_wired_pair()) == []


def test_fd101_duplicate_producer():
    topo = _wired_pair()
    topo.stage("gen2", _builder, outs=["l0"])
    assert "FD101" in _ids(check_topology(topo))


def test_fd102_orphan_consumer():
    topo = ft.Topology()
    topo.link("l0", depth=64, mtu=256)
    topo.stage("sink", _builder, ins=["l0"])  # nobody produces l0
    assert "FD102" in _ids(check_topology(topo))


def test_fd103_unconsumed_link():
    topo = ft.Topology()
    topo.link("l0", depth=64, mtu=256)
    topo.stage("gen", _builder, outs=["l0"])  # nobody consumes l0
    assert "FD103" in _ids(check_topology(topo))


def test_fd104_non_pow2_depth():
    topo = _wired_pair(depth=1000)
    f = [x for x in check_topology(topo) if x.rule == "FD104"]
    assert f and "1000" in f[0].msg


def test_fd105_dcache_too_small():
    topo = _wired_pair(dcache_sz=64)  # far below footprint(256, 64)
    f = [x for x in check_topology(topo) if x.rule == "FD105"]
    assert f and "footprint" in f[0].msg
    # and the shm layer independently refuses to build it
    from firedancer_tpu.tango import shm

    with pytest.raises(ValueError):
        shm.ShmLink.create("fdtpu_test_fd105", depth=64, mtu=256,
                           dcache_sz=64)


def test_fd105_oversized_dcache_is_fine_and_real():
    """Oversizing is legal config, survives the header round-trip, and
    the checker stays quiet."""
    from firedancer_tpu.tango import shm
    from firedancer_tpu.tango.rings import DCache

    big = 2 * DCache.footprint(256, 64)
    assert check_topology(_wired_pair(dcache_sz=big)) == []
    link = shm.ShmLink.create("fdtpu_test_fd105b", depth=64, mtu=256,
                              dcache_sz=big)
    try:
        joined = shm.ShmLink.join("fdtpu_test_fd105b")
        assert joined.dcache_sz == big
        assert len(joined.dcache.data) == big
        joined.close()
    finally:
        link.close()
        link.unlink()


def test_fd106_fseq_underprovision():
    topo = ft.Topology()
    topo.link("l0", depth=64, mtu=256, n_consumers=1)
    topo.stage("gen", _builder, outs=["l0"])
    topo.stage("sink_a", _builder, ins=["l0"])
    topo.stage("sink_b", _builder, ins=["l0"])
    assert "FD106" in _ids(check_topology(topo))


def test_fd107_credit_gated_cycle():
    topo = ft.Topology()
    topo.link("ab", depth=64, mtu=256)
    topo.link("ba", depth=64, mtu=256)
    topo.stage("a", _builder, ins=["ba"], outs=["ab"], credit_gated=True)
    topo.stage("b", _builder, ins=["ab"], outs=["ba"], credit_gated=True)
    f = [x for x in check_topology(topo) if x.rule == "FD107"]
    assert f
    assert "a -> b" in f[0].msg or "b -> a" in f[0].msg


def test_fd107_silent_when_one_stage_drains():
    """The leader pipeline's pack<->bank loop shape: one non-gated stage
    on the cycle keeps draining and no deadlock is possible."""
    topo = ft.Topology()
    topo.link("ab", depth=64, mtu=256)
    topo.link("ba", depth=64, mtu=256)
    topo.stage("a", _builder, ins=["ba"], outs=["ab"])  # not gated
    topo.stage("b", _builder, ins=["ab"], outs=["ba"], credit_gated=True)
    assert "FD107" not in _ids(check_topology(topo))


def test_fd108_duplicate_names():
    topo = _wired_pair()
    topo.link("l0", depth=64, mtu=256)
    topo.stage("gen", _builder, outs=["l0"])
    ids = _ids(check_topology(topo))
    assert "FD108" in ids


def test_fd109_unknown_link():
    topo = ft.Topology()
    topo.stage("gen", _builder, outs=["ghost"])
    assert "FD109" in _ids(check_topology(topo))


def test_fd110_unpicklable_builder():
    topo = ft.Topology()
    topo.link("l0", depth=64, mtu=256)
    topo.stage("gen", lambda links, cnc: None, outs=["l0"])
    topo.stage("sink", _builder, ins=["l0"])
    assert "FD110" in _ids(check_topology(topo))


def test_fd111_isolated_stage_warns_only():
    topo = _wired_pair()
    topo.stage("loner", _builder, ins=[], outs=[])
    findings = check_topology(topo)
    assert "FD111" in _ids(findings)
    topo.validate()  # warnings never raise


def test_hand_wired_topologies_skip_graph_rules():
    """Stages with no declared wiring (pre-existing tests) stay valid."""
    topo = ft.Topology()
    topo.link("l0", depth=64, mtu=256)
    topo.stage("gen", _builder)
    topo.stage("sink", _builder)
    assert check_topology(topo) == []


def test_launch_fails_fast_in_parent_before_any_shm():
    """Satellite: a mis-wired topology raises a readable TopologyError
    from launch() itself — no child process, no shm segment."""
    topo = _wired_pair(depth=1000)  # FD104
    topo.stage("ghost_rider", _builder, ins=["ghost"])  # FD109 + FD102
    with pytest.raises(TopologyError) as ei:
        ft.launch(topo)
    msg = str(ei.value)
    assert "FD104" in msg and "FD109" in msg
    assert "pre-boot validation" in msg


def test_flagship_leader_topology_is_clean():
    from firedancer_tpu.models.leader_topo import build_leader_topology

    assert check_topology(build_leader_topology()) == []


# -- AST rules ---------------------------------------------------------------


_FRAG_SRC = '''
import time, random

class MyStage:
    def after_frag(self, in_idx, meta, payload):
        v = self.result.item()             # FD201
        a = np.asarray(self.mask)          # FD201
        jax.device_get(a)                  # FD201
        self.mask.block_until_ready()      # FD201
        x = float(payload[0])              # FD201 (non-constant arg)
        y = float("inf")                   # ok: constant
        t = time.monotonic()               # FD202
        r = random.randrange(8)            # FD203
        h = hash(payload)                  # FD204

    def during_housekeeping(self):
        import numpy as np
        return np.asarray(self.mask)       # ok: housekeeping is blessed
'''


def test_frag_rules_fire_and_scope_to_frag_bodies():
    findings = ast_rules.lint_source(_FRAG_SRC, "synth.py")
    ids = [f.rule for f in findings]
    assert ids.count("FD201") == 5
    assert "FD202" in ids and "FD203" in ids and "FD204" in ids
    # the housekeeping np.asarray produced nothing
    hk_line = _FRAG_SRC[:_FRAG_SRC.index("during_housekeeping")].count("\n") + 1
    assert all(f.line < hk_line for f in findings if f.rule == "FD201")


def test_frag_rules_see_through_import_aliases():
    """`from time import monotonic` / `import numpy as xp` must not
    evade the module-call rules the PR's own fixes rely on."""
    src = '''
from time import monotonic as mono
from random import randrange
import numpy as xp

class S:
    def after_frag(self, i, m, p):
        t = mono()
        a = xp.asarray(p)
        r = randrange(4)
'''
    ids = sorted(f.rule for f in ast_rules.lint_source(src, "synth.py"))
    assert ids == ["FD201", "FD202", "FD203"]


def test_fd205_ignores_defs_in_nested_class_scopes():
    """A method of a nested class does not shadow the module-level
    builder the Name resolves to — no false positive."""
    src = '''
def wire(topo):
    class Helper:
        def build_x(self):
            return None
    topo.stage("a", build_x)
'''
    assert ast_rules.lint_source(src, "synth.py") == []


def test_fd105_unaligned_dcache_sz():
    from firedancer_tpu.tango import shm
    from firedancer_tpu.tango.rings import DCache

    odd = DCache.footprint(256, 64) + 8  # big enough, but not 64-aligned
    topo = _wired_pair(dcache_sz=odd)
    f = [x for x in check_topology(topo) if x.rule == "FD105"]
    assert f and "granule" in f[0].msg
    with pytest.raises(ValueError):
        shm.ShmLink.create("fdtpu_test_fd105c", depth=64, mtu=256,
                           dcache_sz=odd)


def test_fd205_lambda_and_nested_builders():
    src = '''
def wire(topo):
    def local_builder(links, cnc):
        return None
    topo.stage("a", lambda links, cnc: None)
    topo.stage("b", local_builder)
    topo.stage("c", module_builder)
'''
    findings = ast_rules.lint_source(src, "synth.py")
    assert [f.rule for f in findings] == ["FD205", "FD205"]


def test_fd206_bare_except_unless_reraised():
    src = '''
try:
    x = 1
except:
    pass
try:
    y = 2
except:
    raise
'''
    findings = ast_rules.lint_source(src, "synth.py")
    assert [f.rule for f in findings] == ["FD206"]
    assert findings[0].line == 4


def test_fd200_unparseable_file():
    findings = ast_rules.lint_source("def broken(:\n", "synth.py")
    assert [f.rule for f in findings] == ["FD200"]


def test_fd209_unseeded_randomness_scoped_to_chaos():
    """ISSUE 7 satellite: every entropy source inside chaos/ must thread
    the run seed through utils/rng — os.urandom, secrets.*, uuid4, and
    unseeded generator constructions are flagged there, and ONLY there
    (net.py et al legitimately use os.urandom for protocol CIDs)."""
    src = '''
import os
import secrets
import random
import uuid
import numpy as np

cid = os.urandom(8)
tok = secrets.token_bytes(16)
pick = secrets.choice(options)
uid = uuid.uuid4()
r1 = random.Random()
r2 = np.random.default_rng()
'''
    findings = ast_rules.lint_source(
        src, "firedancer_tpu/chaos/population.py")
    assert [f.rule for f in findings] == ["FD209"] * 6
    # seeded constructions pass — including METHODS on seeded instances
    # (the rule's own prescribed fix must not trip the rule)
    ok = '''
import random
import numpy as np
from firedancer_tpu.utils.rng import Rng

rng = Rng(seed, 7)
r1 = random.Random(seed)
bits = r1.getrandbits(64)
pick = r1.choice(options)
r2 = np.random.default_rng(seed)
'''
    assert ast_rules.lint_source(
        ok, "firedancer_tpu/chaos/scenario.py") == []
    # identical entropy OUTSIDE chaos/ is not FD209's business
    assert ast_rules.lint_source(src, "firedancer_tpu/runtime/net.py") == []
    # the process-global random module in chaos/ is FD203's catch (the
    # division of labor _check_chaos_entropy documents): still an error
    glob = "import random\npick = random.choice([1, 2])\n"
    assert [f.rule for f in ast_rules.lint_source(
        glob, "firedancer_tpu/chaos/scenario.py")] == ["FD203"]


def test_fd209_listed_and_chaos_package_clean():
    from firedancer_tpu.analysis.framework import all_rules

    assert "FD209" in {r.id for r in all_rules()}
    findings = ast_rules.lint_path(os.path.join(PKG, "chaos"))
    assert [f for f in findings if f.rule == "FD209"] == []


def test_inline_disable_suppresses_named_rule_only():
    src = ("class S:\n"
           "    def after_frag(self, i, m, p):\n"
           "        t = time.time()  "
           "# fdlint: disable=FD202 -- latency probe\n"
           "        h = hash(p)\n")
    findings = ast_rules.lint_source(src, "synth.py")
    by_rule = {f.rule: f for f in findings}
    assert by_rule["FD202"].suppressed == "inline"
    assert by_rule["FD204"].suppressed is None


def test_baseline_grandfathers_exact_counts(tmp_path):
    base = tmp_path / "baseline.toml"
    base.write_text(
        '[[suppress]]\npath = "synth.py"\nrule = "FD204"\ncount = 1\n'
        'reason = "test"\n'
    )
    src = "a = hash(b)\nc = hash(d)\n"
    findings = ast_rules.lint_source(src, "synth.py")
    bl.apply_baseline(findings, bl.load_baseline(str(base)))
    assert [f.suppressed for f in findings] == ["baseline", None]


def test_write_baseline_roundtrip(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text("a = hash(b)\n")
    base = tmp_path / "generated.toml"
    rc = fdcli.main(["--write-baseline", "--no-topo",
                     "--baseline", str(base), str(src)])
    assert rc == 0
    # with the generated baseline the same tree is clean
    assert fdcli.main(["--no-topo", "--baseline", str(base),
                       str(src)]) == 0
    # without it, the finding fails the run
    assert fdcli.main(["--no-topo", "--no-baseline", str(src)]) == 1


def test_prune_baseline_drops_and_shrinks_stale_entries(tmp_path):
    """Satellite (ISSUE 15): baseline hygiene.  Entries whose
    file/rule no longer produces a finding are dropped; overcounted
    entries shrink to the live count; live entries keep their reason
    verbatim; entries OUTSIDE the run's analyzed scope pass through
    untouched (a scoped run must not eat suppressions it never
    looked at)."""
    src = tmp_path / "mod.py"
    src.write_text("a = hash(b)\n")  # exactly ONE live FD204
    base = tmp_path / "baseline.toml"
    base.write_text(
        # stale: rule fixed long ago, no current finding
        '[[suppress]]\npath = "%s"\nrule = "FD203"\ncount = 2\n'
        'reason = "fixed since"\n'
        # overcounted: 3 grandfathered, 1 live
        '[[suppress]]\npath = "%s"\nrule = "FD204"\ncount = 3\n'
        'reason = "keep me"\n'
        # stale: the file itself was deleted (still inside the scope)
        '[[suppress]]\npath = "%s"\nrule = "FD204"\ncount = 1\n'
        'reason = "file deleted"\n'
        # outside the scanned tree entirely: must survive verbatim
        '[[suppress]]\npath = "elsewhere/keep.py"\nrule = "FD202"\n'
        'count = 5\nreason = "not my scope"\n'
        % (src, src, tmp_path / "gone.py")
    )
    rc = fdcli.main(["--prune-baseline", "--no-topo", "--no-abi",
                     "--baseline", str(base), str(tmp_path)])
    assert rc == 0
    entries = bl.load_entries(str(base))
    assert [(e["rule"], int(e["count"])) for e in entries] == \
        [("FD204", 1), ("FD202", 5)]
    assert entries[0]["reason"] == "keep me"  # shrunk from 3, reason kept
    assert entries[1]["reason"] == "not my scope"  # out of scope: verbatim
    # the pruned file still suppresses exactly the live finding
    assert fdcli.main(["--no-topo", "--no-abi", "--baseline", str(base),
                       str(tmp_path)]) == 0


def test_prune_baseline_scoped_abi_run_keeps_lint_entries(tmp_path):
    """Regression: `--abi --prune-baseline` analyzes zero lint paths —
    it must NOT drop the shipped verify.py FD214 suppressions as
    'stale' just because this run never linted them."""
    import shutil

    base = tmp_path / "baseline.toml"
    shutil.copy(bl.DEFAULT_BASELINE, base)
    rc = fdcli.main(["--abi", "--prune-baseline", "--baseline",
                     str(base)])
    assert rc == 0
    assert bl.load_baseline(str(base)) == {
        ("firedancer_tpu/runtime/verify.py", "FD214"): 2,
    }


def test_prune_baseline_keeps_shipped_file_intact(tmp_path):
    """Pruning the SHIPPED baseline against the shipped tree is a
    no-op: its only entry (verify.py FD214 x2) is live, so nothing is
    stale — the hygiene pass never eats a justified suppression."""
    import shutil

    base = tmp_path / "baseline.toml"
    shutil.copy(bl.DEFAULT_BASELINE, base)
    rc = fdcli.main(["--prune-baseline", "--no-topo", "--no-abi",
                     "--baseline", str(base),
                     os.path.join(PKG, "runtime", "verify.py")])
    assert rc == 0
    assert bl.load_baseline(str(base)) == {
        ("firedancer_tpu/runtime/verify.py", "FD214"): 2,
    }


def test_abi_pass_is_clean_and_wired_into_the_cli():
    """Satellite (ISSUE 15): `--abi` alone exits 0 over the shipped
    repo (zero cross-language drift after the binding fixes), and the
    FD3xx family is registered alongside FD1xx/FD2xx."""
    assert fdcli.main(["--abi"]) == 0
    ids = {r.id for r in all_rules()}
    assert {"FD301", "FD302", "FD303", "FD304", "FD305", "FD306",
            "FD307", "FD308"} <= ids


# -- the tier-1 gate + fixed-violation regressions ---------------------------


def test_fdlint_script_runs_clean_over_shipped_tree():
    """Satellite: scripts/fdlint.sh = compileall + analyzer, exit 0.
    This is the CI hook — any new violation in the package fails here."""
    r = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "fdlint.sh")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, f"fdlint gate failed:\n{r.stdout}\n{r.stderr}"
    assert "clean" in r.stdout


def test_fixed_violations_stay_fixed():
    """The three true positives fdlint found at introduction were FIXED,
    not baselined: their files carry no unsuppressed error finding, and
    the baseline holds ONLY the documented FD214 comb-install exception
    (ISSUE 13 — see baseline.toml for the reasoning)."""
    for mod in ("runtime/stage.py", "runtime/verify.py",
                "runtime/pack_stage.py"):
        findings = [f for f in ast_rules.lint_file(os.path.join(PKG, mod))
                    if get_rule(f.rule).severity == "error"]
        bl.apply_baseline(findings, bl.load_baseline())
        live = [f for f in findings if not f.suppressed]
        assert live == [], f"{mod}: {[f.format() for f in live]}"
    assert set(bl.load_baseline()) == {
        ("firedancer_tpu/runtime/verify.py", "FD214"),
    }


def test_stage_housekeeping_phase_survives_hash_salt():
    """Regression for the FD204 fix: the housekeeping schedule derived
    from (name, seed) must be identical across interpreters with
    different hash salts — exactly what builtin hash(name) broke for
    every spawned child."""
    prog = (
        "from firedancer_tpu.runtime.stage import Stage\n"
        "s = Stage('verify0', seed=7)\n"
        "s._housekeeping()\n"
        "print(s._next_housekeeping)\n"
    )
    outs = set()
    for salt in ("0", "1"):
        env = {**os.environ, "PYTHONHASHSEED": salt, "JAX_PLATFORMS": "cpu"}
        r = subprocess.run([sys.executable, "-c", prog], env=env,
                           capture_output=True, text=True, timeout=120,
                           cwd=REPO)
        assert r.returncode == 0, r.stderr
        outs.add(r.stdout.strip())
    assert len(outs) == 1, f"schedule depends on hash salt: {outs}"


def test_verify_deadline_close_still_works():
    """The FD202 fix moved deadline stamping to before_credit (the hook
    run_once calls unconditionally every iteration, unlike after_credit
    which is skipped under backpressure); a partial batch must still
    close once the deadline passes."""
    import time as _time

    from firedancer_tpu.runtime.verify import VerifyStage

    st = VerifyStage("v", batch=8, batch_deadline_s=0.01,
                     precomputed_ok=True)
    from firedancer_tpu.runtime.benchg import gen_transfer_pool

    payload = gen_transfer_pool(1)[0]
    meta = [0] * 8
    st.after_frag(0, meta, payload)
    assert st._gen.elems and st._gen.opened_at == 0.0
    st.before_credit()  # stamps the clock (even under backpressure)
    assert st._gen.opened_at > 0.0
    _time.sleep(0.02)
    st.after_credit()  # deadline passed -> closes + dispatches
    assert not st._gen.elems
    st.flush()
    assert st.metrics.get("txn_verified") == 1


def test_partial_declaration_never_fires_absence_rules():
    """A hand-wired (undeclared) stage may be the missing producer or
    consumer: FD102/FD103 need the FULL graph declared, while
    evidence-based rules (here FD101) still fire on the subset."""
    topo = ft.Topology()
    topo.link("l0", depth=64, mtu=256)
    topo.stage("mystery", _builder)  # actually produces l0, undeclared
    topo.stage("sink", _builder, ins=["l0"])
    assert check_topology(topo) == []
    topo.validate()  # launch() accepts the mixed topology
    # ...but a duplicate producer among the declared subset still fails
    topo.stage("gen_a", _builder, outs=["l0"])
    topo.stage("gen_b", _builder, outs=["l0"])
    assert "FD101" in _ids(check_topology(topo))


# -- FD207: per-frag FFI crossings --------------------------------------------


_FFI_FRAG_SRC = '''
import ctypes
from firedancer_tpu.protocol.txn_native import txn_parse_packed
from firedancer_tpu.tango import tcache_native as tn

class MyStage:
    def after_frag(self, in_idx, meta, payload):
        d = txn_parse_packed(payload)        # FD207: from-import of *native*
        self._lib.fd_exec_batch(payload)     # FD207: _lib handle
        tn.insert(payload)                   # FD207: native-module alias
        f = ctypes.CDLL("x.so")              # FD207: raw ctypes
        self.batch.append(payload)           # ok: plain python

    def after_credit(self):
        # burst granularity: one crossing per drained batch is the
        # design (fd_exec_batch shape) — not a frag callback, no finding
        return self._lib.fd_exec_batch(b"".join(self.batch))
'''


def test_fd207_flags_per_frag_ffi_only_in_frag_bodies():
    findings = ast_rules.lint_source(_FFI_FRAG_SRC, "synth.py")
    hits = [f for f in findings if f.rule == "FD207"]
    assert len(hits) == 4
    credit_line = _FFI_FRAG_SRC[: _FFI_FRAG_SRC.index("after_credit")].count(
        "\n") + 1
    assert all(f.line < credit_line for f in hits)


# -- FD208: allocation/formatting in metric/trace hot paths -------------------


_METRIC_HOT_SRC = '''
class MyStage:
    def after_frag(self, in_idx, meta, payload):
        self.metrics.observe(f"lat_{in_idx}", 5)       # FD208: f-string label
        self.metrics.observe("lat", len({1: 2}))       # FD208: dict literal
        self.trace(EV_X, dict(n=len(payload)))         # FD208: dict() call
        self.recorder.record(EV_X, "n={}".format(3))   # FD208: str.format
        self.metrics.observe("lat", [x for x in payload][0])  # FD208: comp
        self.metrics.observe("lat", 5)                 # ok: scalar
        self.trace(EV_X, len(payload))                 # ok: scalar
        self.metrics.inc("seen")                       # ok: not observe/trace

    def during_housekeeping(self):
        # not a frag callback: formatting here is fine (cold path)
        self.trace(EV_X, sum(len(p) for p in self.batch))
'''


def test_fd208_flags_alloc_in_observe_trace_frag_paths():
    findings = ast_rules.lint_source(_METRIC_HOT_SRC, "synth.py")
    hits = [f for f in findings if f.rule == "FD208"]
    assert len(hits) == 5
    hk_line = _METRIC_HOT_SRC[: _METRIC_HOT_SRC.index(
        "during_housekeeping")].count("\n") + 1
    assert all(f.line < hk_line for f in hits)


def test_fd208_clean_on_repo_hot_paths():
    """The shipped stages' frag callbacks observe/trace with scalars
    only — the rule that gates new code must hold on the code that
    motivated it."""
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "firedancer_tpu",
                        "runtime")
    findings = ast_rules.lint_path(root)
    assert [f for f in findings if f.rule == "FD208"] == []


# -- FD210: host<->device transfers in serving frag paths ---------------------


_TRANSFER_SRC = '''
import jax
from jax import device_put

class ServeishStage:
    def after_frag(self, in_idx, meta, payload):
        a = jax.device_put(payload, self.sharding)   # FD210: per-frag commit
        b = device_put(payload)                      # FD210: from-import
        self.pending.copy_to_host_async()            # FD210: transfer kick
        self.acc.append(payload)                     # ok: host accumulation

    def after_credit(self):
        # batch-close granularity: the sanctioned place for device_put
        return jax.device_put(self.batch, self.sharding)
'''


def test_fd210_flags_per_frag_transfers_in_serve_scope():
    findings = ast_rules.lint_source(
        _TRANSFER_SRC, "firedancer_tpu/runtime/somestage.py")
    hits = [f for f in findings if f.rule == "FD210"]
    assert len(hits) == 3
    ac_line = _TRANSFER_SRC[: _TRANSFER_SRC.index("after_credit")].count(
        "\n") + 1
    assert all(f.line < ac_line for f in hits)


def test_fd210_scoped_to_runtime_and_parallel():
    # the same source outside runtime//parallel/ is not FD210's business
    findings = ast_rules.lint_source(_TRANSFER_SRC, "firedancer_tpu/waltz/x.py")
    assert [f for f in findings if f.rule == "FD210"] == []
    findings = ast_rules.lint_source(
        _TRANSFER_SRC, "firedancer_tpu/parallel/mesh.py")
    assert len([f for f in findings if f.rule == "FD210"]) == 3


def test_fd210_registered_and_clean_on_repo():
    assert "FD210" in {r.id for r in all_rules()}
    import os

    for pkg in ("runtime", "parallel"):
        root = os.path.join(os.path.dirname(__file__), "..",
                            "firedancer_tpu", pkg)
        findings = ast_rules.lint_path(root)
        assert [f for f in findings if f.rule == "FD210"] == []


# -- FD211: per-frag allocation/sort in pack hot paths ------------------------


_PACK_SORT_SRC = '''
import bisect

class PackishStage:
    def after_frag(self, in_idx, meta, payload):
        self.pool.sort()                          # FD211: per-frag sort
        k = sorted(self.pool)                     # FD211: per-frag sort
        bisect.insort(self.pool, payload)         # FD211: per-frag insort
        w = {a for a in self.addrs}               # FD211: comprehension
        self.burst.append((payload, 1))           # ok: append-only handoff

    def after_credit(self):
        # burst granularity: the sanctioned place for pool work
        return sorted(self.pool)
'''


def test_fd211_flags_sort_and_comprehension_in_pack_frag():
    findings = ast_rules.lint_source(
        _PACK_SORT_SRC, "firedancer_tpu/runtime/pack_stage.py")
    hits = [f for f in findings if f.rule == "FD211"]
    assert len(hits) == 4
    ac_line = _PACK_SORT_SRC[: _PACK_SORT_SRC.index("after_credit")].count(
        "\n") + 1
    assert all(f.line < ac_line for f in hits)


def test_fd211_scoped_to_pack_modules():
    # identical source outside a pack module is not FD211's business
    findings = ast_rules.lint_source(
        _PACK_SORT_SRC, "firedancer_tpu/runtime/verify.py")
    assert [f for f in findings if f.rule == "FD211"] == []
    # the pack package itself is in scope
    findings = ast_rules.lint_source(
        _PACK_SORT_SRC, "firedancer_tpu/pack/scheduler.py")
    assert len([f for f in findings if f.rule == "FD211"]) == 4


def test_fd211_registered_and_clean_on_repo():
    assert "FD211" in {r.id for r in all_rules()}
    import os

    for rel in (("pack",), ("runtime", "pack_stage.py")):
        root = os.path.join(os.path.dirname(__file__), "..",
                            "firedancer_tpu", *rel)
        findings = ast_rules.lint_path(root)
        assert [f for f in findings if f.rule == "FD211"] == []


# -- FD212: per-frag ctypes allocation churn ----------------------------------


_CTYPES_CHURN_SRC = '''
import ctypes
from ctypes import byref as br

class RingishStage:
    def after_frag(self, in_idx, meta, payload):
        out = ctypes.create_string_buffer(1232)   # FD212: buffer per frag
        self._lib.fdr_poll(br(self._ls), out)     # FD212: byref temporary
        m = (ctypes.c_uint64 * 7)()               # FD212: array per frag
        p = ctypes.cast(out, ctypes.c_void_p)     # FD212: cast temporary
        self._burst.append(payload)               # ok: append-only handoff

    def before_credit(self):
        # burst granularity: the sanctioned place for the crossing
        return self._lib.fdr_drain(self._lsp)
'''


def test_fd212_flags_ctypes_churn_in_frag():
    findings = ast_rules.lint_source(
        _CTYPES_CHURN_SRC, "firedancer_tpu/tango/somering.py")
    hits = [f for f in findings if f.rule == "FD212"]
    assert len(hits) == 4
    bc_line = _CTYPES_CHURN_SRC[: _CTYPES_CHURN_SRC.index(
        "before_credit")].count("\n") + 1
    assert all(f.line < bc_line for f in hits)


def test_fd212_needs_ctypes_import():
    # the same shapes without a ctypes import (e.g. a math `(a*b)(x)`,
    # even with a c_-prefixed name) are not FD212's business
    src = '''
class S:
    def after_frag(self, in_idx, meta, payload):
        f = (scale * gain)(payload)
        g = (c_scale * gain)(payload)
        out = create_string_buffer(64)
'''
    findings = ast_rules.lint_source(src, "firedancer_tpu/tango/x.py")
    assert [f for f in findings if f.rule == "FD212"] == []


def test_fd212_non_ctypes_mult_callee_ok():
    # `(a * b)(x)` where neither operand references ctypes must not trip
    # the array-shape check just because the FILE imports ctypes
    src = '''
import ctypes

class S:
    def after_frag(self, in_idx, meta, payload):
        f = (scale * gain)(payload)
        m = (ctypes.c_uint64 * 7)()   # this one IS the churn shape
'''
    findings = ast_rules.lint_source(src, "firedancer_tpu/tango/x.py")
    hits = [f for f in findings if f.rule == "FD212"]
    assert len(hits) == 1
    assert "array construction" in hits[0].msg


def test_fd212_cached_byref_outside_frag_ok():
    # the tango/native.py discipline: byref/buffers cached in __init__,
    # frag-adjacent code only *uses* them
    src = '''
import ctypes

class Endpoint:
    def __init__(self):
        self._out = ctypes.create_string_buffer(1232)
        self._lsp = ctypes.byref(self._ls)

    def after_frag(self, in_idx, meta, payload):
        self._burst.append((payload, int(meta[1])))
'''
    findings = ast_rules.lint_source(src, "firedancer_tpu/tango/x.py")
    assert [f for f in findings if f.rule == "FD212"] == []


def test_fd212_registered_and_clean_on_repo():
    assert "FD212" in {r.id for r in all_rules()}
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "firedancer_tpu")
    findings = ast_rules.lint_path(root)
    assert [f for f in findings if f.rule == "FD212"] == []


# -- FD213: per-frag hashing/bytes assembly in the shred path -----------------


_SHRED_CHURN_SRC = '''
import hashlib
from firedancer_tpu.ops.ref.bmtree import hash_leaf_full

class ShredishStage:
    def after_frag(self, in_idx, meta, payload):
        leaf = hash_leaf_full(payload)            # FD213: merkle churn
        node = hashlib.sha256(payload).digest()   # FD213: hash per frag
        frame = b"\\x00" * 4 + payload            # FD213: literal concat
        buf = bytes(payload)                      # FD213: bytes() per frag
        joined = b"".join(self._parts)            # FD213: join concat
        self._buf += payload                      # ok: append-only extend

    def _shred_batch(self):
        # FEC-set granularity: the sanctioned place for all of it
        root = hashlib.sha256(bytes(self._buf)).digest()
        return b"".join(self._shreds)
'''


def test_fd213_flags_hash_and_concat_in_shred_frag():
    findings = ast_rules.lint_source(
        _SHRED_CHURN_SRC, "firedancer_tpu/runtime/shredder.py")
    hits = [f for f in findings if f.rule == "FD213"]
    assert len(hits) == 5
    batch_line = _SHRED_CHURN_SRC[: _SHRED_CHURN_SRC.index(
        "_shred_batch")].count("\n") + 1
    assert all(f.line < batch_line for f in hits)


def test_fd213_scoped_to_shred_path_modules():
    # the identical body in a non-shred module is not FD213's business
    findings = ast_rules.lint_source(
        _SHRED_CHURN_SRC, "firedancer_tpu/runtime/dedup.py")
    assert [f for f in findings if f.rule == "FD213"] == []


def test_fd213_batch_granularity_ok():
    # the ShredStage discipline: frag callbacks append; hashing/framing
    # happen when the batch closes (helper methods, not frag callbacks)
    src = '''
import hashlib

class ShredStage:
    def after_frag(self, in_idx, meta, payload):
        self._buf += len(payload).to_bytes(4, "little")
        self._buf += payload

    def flush(self):
        return hashlib.sha256(bytes(self._buf)).digest()
'''
    findings = ast_rules.lint_source(
        src, "firedancer_tpu/runtime/shred_stage.py")
    assert [f for f in findings if f.rule == "FD213"] == []


def test_fd213_registered_and_clean_on_repo():
    assert "FD213" in {r.id for r in all_rules()}
    import os

    for rel in ("shredder.py", "shred_stage.py", "shred_native.py",
                "store.py", "fec_resolver.py"):
        root = os.path.join(os.path.dirname(__file__), "..",
                            "firedancer_tpu", "runtime", rel)
        findings = ast_rules.lint_path(root)
        assert [f for f in findings if f.rule == "FD213"] == []


# -- FD214: device sync outside the designated reap point ---------------------


_VERIFY_SYNC_SRC = '''
import numpy as np

class VerifyStage:
    def _accumulate(self, got, payload, tsorig):
        n = int(np.asarray(self._count))          # FD214: sync in intake
        self._elems.append(got)

    def _submit(self, acc, cached):
        res = self._dispatch(acc, cached)
        res.block_until_ready()                   # FD214: sync at submit
        self._inflight.append(res)

    def during_housekeeping(self):
        v = self._probe.item()                    # FD214: sync in hk
        self._log(v)

    def _drain(self, block):
        mask = np.asarray(self._inflight[0].result)   # ok: THE reap point
        return mask

    def _mask_of(self, result):
        return np.asarray(result)                 # ok: the reaps' fetch

    def flush(self):
        return np.asarray(self._tail)             # ok: shutdown drain

    def after_frag(self, in_idx, meta, payload):
        x = np.asarray(meta)                      # FD201 territory, not 214
        return x


class MeshVerifyStage(VerifyStage):
    def _close_batch(self, acc):
        n_ok = int(np.asarray(self._pend.n_ok))   # FD214: subclass inherits
        return n_ok


class UnrelatedHelper:
    def _submit(self):
        return np.asarray(self._x)                # not a verify-stage class
'''


def test_fd214_flags_sync_outside_reap_point():
    findings = ast_rules.lint_source(
        _VERIFY_SYNC_SRC, "firedancer_tpu/runtime/verify.py")
    hits = [f for f in findings if f.rule == "FD214"]
    msgs = [f.msg for f in hits]
    assert len(hits) == 4, msgs
    assert any("_accumulate" in m for m in msgs)
    assert any("_submit" in m for m in msgs)
    assert any("during_housekeeping" in m for m in msgs)
    assert any("_close_batch" in m for m in msgs)  # subclass inherits
    # the frag callback is FD201's jurisdiction, not re-flagged as FD214
    assert not any("after_frag" in m for m in msgs)
    assert any(f.rule == "FD201" for f in findings)


def test_fd214_scoped_to_verify_path_modules():
    # the identical body elsewhere is not FD214's business
    findings = ast_rules.lint_source(
        _VERIFY_SYNC_SRC, "firedancer_tpu/runtime/bank.py")
    assert [f for f in findings if f.rule == "FD214"] == []


def test_fd214_registered_and_baselined_on_repo():
    assert "FD214" in {r.id for r in all_rules()}
    # the repo's verify path carries exactly the two baselined
    # _fill_bank hits (deliberate comb-install sync, documented in
    # baseline.toml) and nothing else
    for rel, allowed in (("runtime/verify.py", 2),
                         ("runtime/verify_native.py", 0)):
        root = os.path.join(os.path.dirname(__file__), "..",
                            "firedancer_tpu", rel)
        findings = [f for f in ast_rules.lint_path(root)
                    if f.rule == "FD214"]
        assert len(findings) == allowed, (rel, findings)
        assert all("_fill_bank" in f.msg for f in findings)


# -- FD215: blocking waits in hot hooks (slot-clock discipline) ---------------


_BLOCKING_SRC = '''
import time
import threading
from time import sleep as zzz

class SomeStage:
    def after_frag(self, in_idx, meta, payload):
        time.sleep(0.01)                          # FD215: sleep in frag

    def before_credit(self):
        zzz(0.5)                                  # FD215: aliased sleep

    def after_credit(self):
        self._done_event.wait()                   # FD215: unbounded wait

    def during_housekeeping(self):
        self._worker.join()                       # FD215: unbounded join
        self._lock.acquire()                      # FD215: unbounded acquire

    def flush(self):
        time.sleep(0.1)                           # not a hot hook: clean

    def before_frag(self, in_idx, seq, sig):
        ok = self._done_event.wait(0.0)           # bounded: clean
        joined = ",".join(self._parts)            # str.join(arg): clean
        got = self._lock.acquire(False)           # non-blocking: clean
        return ok and got and bool(joined)


def after_credit():
    time.sleep(1.0)                               # free function: clean
'''


def test_fd215_flags_blocking_waits_in_hot_hooks():
    findings = ast_rules.lint_source(
        _BLOCKING_SRC, "firedancer_tpu/runtime/somestage.py")
    hits = [f for f in findings if f.rule == "FD215"]
    msgs = [f.msg for f in hits]
    assert len(hits) == 5, msgs
    assert sum("time.sleep" in m for m in msgs) == 2
    assert any(".wait()" in m for m in msgs)
    assert any(".join()" in m for m in msgs)
    assert any(".acquire()" in m for m in msgs)
    # hook hits name the surface so the fix is obvious
    assert any("stage-loop hook" in m for m in msgs)
    assert any("frag callback" in m for m in msgs)


def test_fd215_suppressible_inline():
    src = ("import time\n"
           "class S:\n"
           "    def after_credit(self):\n"
           "        time.sleep(0.1)  "
           "# fdlint: disable=FD215 -- test fixture pacing\n")
    findings = [f for f in ast_rules.lint_source(src, "firedancer_tpu/x.py")
                if f.rule == "FD215"]
    # suppressions are MARKED, not dropped (reports show what a disable
    # comment ate), and the repo-clean test below counts only live hits
    assert len(findings) == 1 and findings[0].suppressed == "inline"


def test_fd215_registered_and_repo_clean():
    assert "FD215" in {r.id for r in all_rules()}
    # the slot-clock plane is the only deadline authority: the repo's
    # own stage code carries ZERO blocking waits in hot hooks
    root = os.path.join(os.path.dirname(__file__), "..", "firedancer_tpu")
    findings = [f for f in ast_rules.lint_path(root)
                if f.rule == "FD215"]
    assert findings == [], findings


# -- FD216: txn re-parse in bank-path frag callbacks (zero-copy commit) -------


_REPARSE_SRC = '''
from firedancer_tpu.protocol import txn as ft
from firedancer_tpu.protocol.txn import txn_parse
import struct

class BankishStage:
    def after_frag(self, in_idx, meta, payload):
        t = ft.txn_parse(payload)                 # FD216: qualified re-parse
        desc, end = ft.txn_unpack(payload, 0)     # FD216: descriptor re-parse
        t2 = txn_parse(payload)                   # FD216: from-import alias
        psz = struct.unpack("<H", payload[-2:])   # struct.unpack: clean
        n = int.from_bytes(payload[-2:], "little")  # offset read: clean
        return t or t2 or desc or psz or n

    def _arm_native(self):
        return ft.txn_parse(b"")                  # not a frag callback: clean


def txn_parse_free(payload):
    return txn_parse(payload)                     # free function: clean
'''


def test_fd216_flags_reparse_in_bank_frag():
    findings = ast_rules.lint_source(
        _REPARSE_SRC, "firedancer_tpu/runtime/bank.py")
    hits = [f for f in findings if f.rule == "FD216"]
    msgs = [f.msg for f in hits]
    assert len(hits) == 3, msgs
    assert sum("txn_parse" in m for m in msgs) == 2
    assert sum("txn_unpack" in m for m in msgs) == 1
    # the same source OUTSIDE the bank path is not FD216's business
    clean = [f for f in ast_rules.lint_source(
        _REPARSE_SRC, "firedancer_tpu/runtime/poh_stage.py")
        if f.rule == "FD216"]
    assert clean == [], clean


def test_fd216_suppressible_inline():
    src = ("from firedancer_tpu.protocol.txn import txn_parse\n"
           "class B:\n"
           "    def after_frag(self, in_idx, meta, payload):\n"
           "        return txn_parse(payload)  "
           "# fdlint: disable=FD216 -- replay-side decode\n")
    findings = [f for f in ast_rules.lint_source(
        src, "firedancer_tpu/runtime/bank_native.py")
        if f.rule == "FD216"]
    assert len(findings) == 1 and findings[0].suppressed == "inline"


def test_fd216_registered_and_repo_clean():
    assert "FD216" in {r.id for r in all_rules()}
    # the commit path honors the verify contract: the repo's own bank
    # modules read the packed descriptor, they never re-parse the txn
    root = os.path.join(os.path.dirname(__file__), "..", "firedancer_tpu")
    findings = [f for f in ast_rules.lint_path(root)
                if f.rule == "FD216"]
    assert findings == [], findings


# -- FD217: per-datagram Python crypto in ingress with a sweep client ---------


_NET_CRYPTO_SRC = '''
from firedancer_tpu.ops.aes import AesGcm
from firedancer_tpu.waltz.quic import _hp_mask
from . import net_native


class IngressStage:
    def __init__(self):
        self._net_client = net_native.NetClient(max_conns=1, reasm_depth=1)
        self._gcm = AesGcm(b"k" * 16)

    def _on_datagram(self, data, src):
        pt = self._gcm.open(data[:12], data[12:-16], data[-16:])  # FD217
        mask = _hp_mask(b"h" * 16, data[:16])                     # FD217
        return pt or mask

    def after_credit(self):
        data, src = self.sock.recvfrom(2048)                      # FD217
        ct, tag = self._gcm.seal(b"\\x00" * 12, data)              # FD217
        return ct, tag

    def _py_datagram(self, data, src):
        # the punt lane: the same calls are FD217-clean here
        pt = self._gcm.open(data[:12], data[12:-16], data[-16:])
        mask = _hp_mask(b"h" * 16, data[:16])
        for _ in range(2):
            data, src = self.sock.recvfrom(2048)
        return pt or mask

    def report(self, path):
        with open(path) as fh:                    # builtin open: clean
            return fh.read()
'''


def test_fd217_flags_ingress_crypto_with_sweep_client():
    findings = ast_rules.lint_source(
        _NET_CRYPTO_SRC, "firedancer_tpu/runtime/net.py")
    hits = [f for f in findings if f.rule == "FD217"]
    msgs = [f.msg for f in hits]
    assert len(hits) == 4, msgs
    assert sum(".open()" in m for m in msgs) == 1
    assert sum(".seal()" in m for m in msgs) == 1
    assert sum("recvfrom" in m for m in msgs) == 1
    assert sum("_hp_mask" in m for m in msgs) == 1
    # without the sweep-client registration the SAME hot-path calls are
    # the module's legitimate Python lane — the gate must not fire
    ungated = _NET_CRYPTO_SRC.replace(
        "self._net_client = net_native.NetClient"
        "(max_conns=1, reasm_depth=1)",
        "self._net_client_off = None")
    clean = [f for f in ast_rules.lint_source(
        ungated, "firedancer_tpu/runtime/net.py") if f.rule == "FD217"]
    assert clean == [], clean
    # and outside the net modules the rule has no opinion at all
    other = [f for f in ast_rules.lint_source(
        _NET_CRYPTO_SRC, "firedancer_tpu/runtime/verify.py")
        if f.rule == "FD217"]
    assert other == [], other


def test_fd217_suppressible_inline():
    src = ("class S:\n"
           "    def __init__(self):\n"
           "        self._sweep_client = object()\n"
           "    def _on_datagram(self, data, src):\n"
           "        return self.gcm.open(data[:12], data[12:], b'')  "
           "# fdlint: disable=FD217 -- bring-up shim\n")
    findings = [f for f in ast_rules.lint_source(
        src, "firedancer_tpu/runtime/net.py") if f.rule == "FD217"]
    assert len(findings) == 1 and findings[0].suppressed == "inline"


def test_fd217_registered_and_repo_clean():
    assert "FD217" in {r.id for r in all_rules()}
    # the ingress hot path honors the lane split: the repo's own net
    # modules keep per-datagram Python crypto in the _py_* punt lane
    root = os.path.join(os.path.dirname(__file__), "..", "firedancer_tpu")
    findings = [f for f in ast_rules.lint_path(root)
                if f.rule == "FD217"]
    assert findings == [], findings


# -- FD218: per-record Python funk mutation with the native funk lane armed ---


_BANK_FUNK_SRC = '''
from firedancer_tpu.runtime import bank_native


class BankStage:
    def __init__(self, funk, xid):
        self._sweep_client = bank_native.StageClient(n_lanes=1)
        self._sweep_client.set_funk(funk, xid)
        self.funk = funk
        self.xid = xid

    def after_frag(self, sig, frag):
        recs = self.funk.txn_recs_for_write(self.xid)        # FD218
        for key, val in frag.items():
            self.funk.rec_insert(self.xid, key, val)         # FD218
        self.funk.rec_insert_batch(self.xid, frag.items())   # clean
        return recs

    def after_credit(self):
        self.funk._root_merge([(b"k", b"v")])                # FD218
        self.funk.rec_remove(self.xid, b"dead")              # FD218

    def _drain_native(self, rows):
        # cold path, not a frag callback: per-record writes are fine
        for key, val in rows:
            self.funk.rec_insert(self.xid, key, val)
        self.funk._root_merge(rows)
'''


def test_fd218_flags_per_record_funk_mutation_with_lane_armed():
    findings = ast_rules.lint_source(
        _BANK_FUNK_SRC, "firedancer_tpu/runtime/bank.py")
    hits = [f for f in findings if f.rule == "FD218"]
    msgs = [f.msg for f in hits]
    assert len(hits) == 4, msgs
    assert sum("txn_recs_for_write" in m for m in msgs) == 1
    assert sum("rec_insert'" in m for m in msgs) == 1  # not rec_insert_batch
    assert sum("_root_merge" in m for m in msgs) == 1
    assert sum("rec_remove" in m for m in msgs) == 1
    # without the set_funk arming the SAME writes are the module's
    # legitimate Python funk lane — the gate must not fire
    ungated = _BANK_FUNK_SRC.replace(
        "self._sweep_client.set_funk(funk, xid)", "self._armed = False")
    clean = [f for f in ast_rules.lint_source(
        ungated, "firedancer_tpu/runtime/bank.py") if f.rule == "FD218"]
    assert clean == [], clean
    # and outside the bank-path modules the rule has no opinion at all
    other = [f for f in ast_rules.lint_source(
        _BANK_FUNK_SRC, "firedancer_tpu/runtime/net.py")
        if f.rule == "FD218"]
    assert other == [], other


def test_fd218_suppressible_inline():
    src = ("class S:\n"
           "    def __init__(self, c):\n"
           "        c.set_funk(None, b'')\n"
           "    def after_frag(self, sig, frag):\n"
           "        return self.funk.rec_insert(None, b'k', b'v')  "
           "# fdlint: disable=FD218 -- bring-up shim\n")
    findings = [f for f in ast_rules.lint_source(
        src, "firedancer_tpu/runtime/bank.py") if f.rule == "FD218"]
    assert len(findings) == 1 and findings[0].suppressed == "inline"


def test_fd218_registered_and_repo_clean():
    assert "FD218" in {r.id for r in all_rules()}
    # the commit hot path honors the one-crossing contract: the repo's
    # own bank modules never mutate funk per record inside a frag
    root = os.path.join(os.path.dirname(__file__), "..", "firedancer_tpu")
    findings = [f for f in ast_rules.lint_path(root)
                if f.rule == "FD218"]
    assert findings == [], findings


# -- FD219: Python write on a native-owned metric with a sweep client armed ---


_NATIVE_METRIC_SRC = '''
class BankStage:
    def __init__(self, client):
        self._sweep_client = client

    def after_frag(self, sig, frag):
        self.metrics.observe("nsweep_apply_ns", 120.0)       # FD219
        self.metrics.inc("nsweep_frags", 4)                  # FD219
        self.metrics.observe("nbank_txn_lat_ns", 9.0)        # FD219
        self.metrics.observe("frag_latency_ns", 9.0)         # non-native: ok
        self.metrics.inc("frags_in")                         # non-native: ok

    def during_housekeeping(self):
        # cold paths double-count just as surely as hot ones
        self.metrics.registry.store("nsweep_crossings", 1)   # FD219
        self.recorder.record(17, 0)          # event id, not a name: ok

    def report(self, name):
        self.metrics.observe(name, 1.0)      # dynamic name: ok
'''


def test_fd219_flags_python_writes_on_native_owned_metrics():
    findings = ast_rules.lint_source(
        _NATIVE_METRIC_SRC, "firedancer_tpu/runtime/bank.py")
    hits = [f for f in findings if f.rule == "FD219"]
    msgs = [f.msg for f in hits]
    assert len(hits) == 4, msgs
    assert sum("nsweep_apply_ns" in m for m in msgs) == 1
    assert sum("nsweep_frags" in m for m in msgs) == 1
    assert sum("nbank_txn_lat_ns" in m for m in msgs) == 1
    assert sum("nsweep_crossings" in m for m in msgs) == 1
    # without the sweep-client registration the module owns its facade:
    # the SAME writes are the legitimate Python metrics lane
    ungated = _NATIVE_METRIC_SRC.replace(
        "self._sweep_client = client", "self._client_off = client")
    clean = [f for f in ast_rules.lint_source(
        ungated, "firedancer_tpu/runtime/bank.py") if f.rule == "FD219"]
    assert clean == [], clean


def test_fd219_name_set_mirrors_metrics_schema():
    # the lint mirror must track utils/metrics.native_owned_names():
    # a native metric added to the schema without extending the mirror
    # silently escapes the double-count gate (and vice versa)
    from firedancer_tpu.utils import metrics as fm

    assert ast_rules._FD219_NATIVE_OWNED == fm.native_owned_names()


def test_fd219_suppressible_inline():
    src = ("class S:\n"
           "    def __init__(self, c):\n"
           "        self._sweep_client = c\n"
           "    def after_frag(self, sig, frag):\n"
           "        self.metrics.inc('nsweep_frags')  "
           "# fdlint: disable=FD219 -- bring-up shim\n")
    findings = [f for f in ast_rules.lint_source(
        src, "firedancer_tpu/runtime/bank.py") if f.rule == "FD219"]
    assert len(findings) == 1 and findings[0].suppressed == "inline"


def test_fd219_registered_and_repo_clean():
    assert "FD219" in {r.id for r in all_rules()}
    # the repo's own sweep-client modules never write native-owned words
    # from Python (the facade skip + this rule are the same contract)
    root = os.path.join(os.path.dirname(__file__), "..", "firedancer_tpu")
    findings = [f for f in ast_rules.lint_path(root)
                if f.rule == "FD219"]
    assert findings == [], findings
