"""The device rule and the compile-cache rule (utils/platform.py): the
chip is required unless the caller asks for the CPU, and the cache can
be placed from outside."""

import os
import subprocess
import sys
import time

import pytest

from firedancer_tpu.utils import platform as fp

REPO = fp.REPO_ROOT


@pytest.fixture
def restore_cache_dir():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_env_is_left_to_jax(monkeypatch, restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set the code performs no cache-dir
    update; a fresh interpreter shows JAX picked the env value itself."""
    import jax

    calls = []
    real_update = jax.config.update
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append(k) or real_update(k, v))
    monkeypatch.setenv(fp.CACHE_DIR_ENV, "/some/dir")
    assert fp.enable_compile_cache() == "/some/dir"
    assert "jax_compilation_cache_dir" not in calls
    out = subprocess.run(
        [sys.executable, "-c",
         "from firedancer_tpu.utils.platform import enable_compile_cache;"
         "enable_compile_cache(); import jax;"
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, fp.CACHE_DIR_ENV: "/some/dir",
             "JAX_PLATFORMS": "cpu"},
    )
    assert out.stdout.strip() == "/some/dir", out.stderr[-2000:]


def test_cache_dir_default_is_one_fixed_path(monkeypatch, restore_cache_dir):
    import jax

    monkeypatch.delenv(fp.CACHE_DIR_ENV, raising=False)
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    a = fp.enable_compile_cache()
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    b = fp.enable_compile_cache()
    assert a == b == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == a


def test_require_chip_raises_on_cpu():
    with pytest.raises(fp.NoChipError, match="no TPU"):
        fp.require_chip()


def test_process_jax_state_reports_the_pin():
    assert fp.process_jax_state() == "cpu"  # conftest pinned this process
    out = subprocess.run(
        [sys.executable, "-c",
         "from firedancer_tpu.utils.platform import process_jax_state as p;"
         "print(p()); import jax; print(p())"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
    )
    assert out.stdout.split() == ["none", "default"], out.stderr[-2000:]


@pytest.mark.parametrize("cmd", [
    ["chip_smoke.py"],
    ["benchmarks/run.py", "--workload", "verify-spam-flood", "--seed", "1",
     "--seconds", "1"],
    ["-m", "firedancer_tpu", "run", "--txns", "8"],
])
def test_entry_points_refuse_to_run_without_a_chip(cmd):
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, *cmd], cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()  # no result of any kind
    assert time.monotonic() - t0 < 60  # before any compile


@pytest.mark.slow
def test_chip_smoke_phase_a_on_cpu_at_tiny_size(tmp_path, capsys):
    """Phase A's own function — programs compiled and held to the
    reference, a clean stream and a corrupted one end to end — at batch
    16 / 64 transactions / 3 corrupted on the CPU.  Called as a function:
    the script itself has no way to pass without a chip."""
    import json

    import jax

    sys.path.insert(0, REPO)
    import chip_smoke

    cfg = tmp_path / "tiny.toml"
    cfg.write_text("[layout]\nverify_stage_count = 1\nbank_stage_count = 2\n"
                   "[verify]\nbatch = 16\nmax_msg_len = 256\n")
    d = jax.devices()
    chip_smoke.phase_a(
        (d[0].platform, d[0].device_kind, len(d)), config=str(cfg),
        n_stream=64, n_bad_stream=64, n_bad=3, extra_shapes=(),
        comb_slots=16,
    )
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [ln["step"] for ln in lines] == [
        "compile", "stream", "corrupted_stream"]
    assert all(ln["ok"] and ln["platform"] == "cpu" for ln in lines)
    assert lines[1]["txn_exec"] == 64 and lines[1]["verify_fail"] == 0
    assert lines[2]["txn_exec"] == 61 and lines[2]["verify_fail"] == 3


def test_chip_smoke_phase_c_on_cpu_at_tiny_size(tmp_path, capsys,
                                                toy_verify_ok, monkeypatch):
    """Phase C's own function — the pipeline with `[verify] devices =
    4`, every chip dealt lanes, nothing failed, everything executed —
    at batch 16 / 64 transactions over four of conftest's virtual
    devices.  The toy arithmetic stands in for the program's, so the
    generator is held to transactions the toy passes (phase C sends no
    corrupted ones)."""
    import json

    import jax

    from firedancer_tpu.protocol import txn as ft
    from firedancer_tpu.runtime import benchg

    sys.path.insert(0, REPO)
    import chip_smoke

    gen = benchg.gen_transfer_pool

    def passing(n, **kw):
        def ok(t):
            d = ft.txn_parse(t)
            msg, sig, pk = d.message(t), d.signatures(t)[0], d.signers(t)[0]
            return toy_verify_ok(len(msg), msg[0], sig[0], sig[63], pk[0],
                                 pk[31])

        pool = [t for t in gen(3 * n, **kw) if ok(t)]
        assert len(pool) >= n
        return pool[:n]

    monkeypatch.setattr(benchg, "gen_transfer_pool", passing)
    cfg = tmp_path / "tiny.toml"
    cfg.write_text("[layout]\nverify_stage_count = 1\nbank_stage_count = 2\n"
                   "[verify]\nbatch = 16\nmax_msg_len = 256\n")
    d = jax.devices()
    assert len(d) >= chip_smoke.MESH_DEVICES
    chip_smoke.phase_c((d[0].platform, d[0].device_kind, len(d)),
                       config=str(cfg), n_topo=64)
    chip_smoke.phase_c((d[0].platform, d[0].device_kind, 1))
    ran, skipped = [json.loads(ln)
                    for ln in capsys.readouterr().out.splitlines()
                    if ln.startswith("{")]
    assert ran["phase"] == "C" and ran["ok"] and "skipped" not in ran
    assert ran["txn_exec"] == 64 and ran["verify_fail"] == 0
    assert ran["input_devices"] == 4 and all(ran["shard_elems"])
    assert sum(ran["shard_elems"]) == 64
    assert skipped == {"phase": "C", "ok": True, "skipped": "1 device",
                       "platform": "cpu", "device_kind": d[0].device_kind,
                       "device_count": 1}


# which packages a package may import, by AST walk (ISSUE 44): the
# device library under everything, the mesh beside it, the stages above
_LAYERS = {
    "parallel": {"forbidden": ("runtime", "models"), "only": {}},
    "ops": {"forbidden": ("runtime", "parallel", "models"), "only": {}},
    "runtime": {"forbidden": (), "only": {"parallel": {"mesh"}}},
}
# the one upward import the tree has (ROADMAP Queue 3): the host AES
# delegates to the net tile's native library through its binding
_LAYER_DEBTS = {
    ("firedancer_tpu/ops/aes.py", "firedancer_tpu.runtime"),
    ("firedancer_tpu/ops/aes.py", "firedancer_tpu.runtime.net_native"),
}


def _imports(path: str, pkg_parts: list[str]):
    """Every firedancer_tpu module a file imports, absolute:
    ("firedancer_tpu", "parallel", "mesh", ...) tuples, `from x import
    y` giving both x and x.y (y may be a module)."""
    import ast

    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield tuple(a.name.split("."))
        elif isinstance(node, ast.ImportFrom):
            base = (pkg_parts[:len(pkg_parts) - node.level + 1]
                    if node.level else [])
            base = tuple(base) + tuple((node.module or "").split(".")
                                       if node.module else ())
            yield base
            for a in node.names:
                yield base + (a.name,)


@pytest.mark.parametrize("layer", sorted(_LAYERS))
def test_the_layers_import_downwards_only(layer):
    rule = _LAYERS[layer]
    root = os.path.join(REPO, "firedancer_tpu", layer)
    seen = 0
    debts = set()
    for d, _, fs in os.walk(root):
        for f in fs:
            if not f.endswith(".py"):
                continue
            path = os.path.join(d, f)
            pkg = os.path.relpath(d, REPO).split(os.sep)
            for mod in _imports(path, pkg):
                if mod[:1] != ("firedancer_tpu",) or len(mod) < 2:
                    continue
                seen += 1
                rel = os.path.relpath(path, REPO).replace(os.sep, "/")
                if (rel, ".".join(mod)) in _LAYER_DEBTS:
                    debts.add((rel, ".".join(mod)))
                    continue
                where = f"{rel} imports {'.'.join(mod)}"
                assert mod[1] not in rule["forbidden"], where
                only = rule["only"].get(mod[1])
                if only is not None and len(mod) > 2:
                    assert mod[2] in only, where
    assert seen > 0
    # a debt that was paid leaves the list
    assert debts == {d for d in _LAYER_DEBTS
                     if d[0].startswith(f"firedancer_tpu/{layer}/")}


def test_every_environment_switch_read_is_documented():
    """The FDTPU_* names the program, its native code and its scripts
    read are the names docs/OPERATIONS.md documents: a switch nobody can
    find is a debt, and a documented one that nothing reads is a lie."""
    import re

    name = re.compile(r"FDTPU_[A-Z0-9_]+")
    read = set()
    files = [os.path.join(REPO, "chip_smoke.py"),
             os.path.join(REPO, "__graft_entry__.py")]
    for root in ("firedancer_tpu", "native", "scripts"):
        for d, _, fs in os.walk(os.path.join(REPO, root)):
            files += [os.path.join(d, f) for f in fs
                      if f.endswith((".py", ".cpp", ".h", ".sh"))]
    for f in files:
        with open(f, encoding="utf-8") as fh:
            read |= set(name.findall(fh.read()))
    with open(os.path.join(REPO, "docs", "OPERATIONS.md"),
              encoding="utf-8") as fh:
        documented = set(name.findall(fh.read()))
    assert read == documented, (sorted(read - documented),
                                sorted(documented - read))
    assert len(read) == 20  # a new switch is a decision, not a drift
