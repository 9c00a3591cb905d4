"""Shared on-demand builder for the native (C++) components.

One place owns the build-to-temp + atomic-rename discipline (concurrent
stage processes must never clobber each other's half-written .so) and the
temp cleanup on failure; every binding module loads through it.

Sanitizer lane (ISSUE 15): `FDTPU_NATIVE_SAN=asan|ubsan|tsan` redirects
every build into `native/san/<san>/` with the matching instrumentation
flags,
so the SAME differential suites exercise the SAME bindings over
ASan/UBSan-instrumented .so's — no second build system, no test forks.
`build_so` RETURNS the path actually built (the san twin when the lane
is armed); callers must CDLL that return value, never their own `so`
argument.  ASan additionally needs its runtime loaded before python's
first allocation: run the process under `san_env()` (LD_PRELOAD of the
toolchain's libasan + leak detection off — CPython deliberately leaks
arenas at exit and would drown real reports).
"""

from __future__ import annotations

import os
import subprocess


class NativeUnavailable(RuntimeError):
    pass


SAN_ENV = "FDTPU_NATIVE_SAN"

_BASE_FLAGS = ["-O2", "-shared", "-fPIC"]
_SAN_FLAGS = {
    # -O1 keeps frames honest for reports while staying fast enough for
    # the differential suites; -g makes the report lines resolvable
    "asan": ["-O1", "-shared", "-fPIC", "-g", "-fno-omit-frame-pointer",
             "-fsanitize=address"],
    "ubsan": ["-O1", "-shared", "-fPIC", "-g",
              "-fsanitize=undefined", "-fno-sanitize-recover=undefined"],
    # TSan sees in-PROCESS threads only: the cross-process shm rings are
    # invisible to it (docs/OPERATIONS.md "TSan vs the shm rings"), so
    # this lane guards the threaded native paths + validates the fence
    # annotations race_check's FD406 checks statically
    "tsan": ["-O1", "-shared", "-fPIC", "-g", "-fno-omit-frame-pointer",
             "-fsanitize=thread"],
}


def san_mode() -> str | None:
    """The armed sanitizer lane, or None.  An unknown value is a hard
    error — a typo'd FDTPU_NATIVE_SAN silently running uninstrumented
    would defeat the lane's whole point."""
    v = os.environ.get(SAN_ENV, "").strip().lower()
    if not v:
        return None
    if v not in _SAN_FLAGS:
        raise NativeUnavailable(
            f"{SAN_ENV}={v!r}: expected 'asan', 'ubsan' or 'tsan'")
    return v


def san_so_path(so: str, san: str) -> str:
    """native/foo.so -> native/san/<san>/foo.so (instrumented twin)."""
    d = os.path.dirname(so)
    return os.path.join(d, "san", san, os.path.basename(so))


def _toolchain_lib(lib: str) -> str:
    try:
        path = subprocess.run(
            ["g++", f"-print-file-name={lib}"],
            check=True, capture_output=True, text=True,
        ).stdout.strip()
    except (OSError, subprocess.CalledProcessError) as e:
        raise NativeUnavailable(f"cannot locate {lib}: {e}") from e
    if not os.path.isabs(path) or not os.path.exists(path):
        raise NativeUnavailable(f"toolchain has no {lib} (got {path!r})")
    return path


def san_env(san: str) -> dict[str, str]:
    """Environment additions for a process that will dlopen
    instrumented .so's: the sanitizer runtime preloaded (ASan must be
    the FIRST loaded DSO or dlopen refuses the instrumented library)
    and leak detection off (CPython's arena teardown is all noise).
    libstdc++ rides the preload list too: ASan resolves the REAL
    __cxa_throw at startup via RTLD_NEXT, and a python process has no
    libstdc++ in its link map yet (jaxlib bundles its own statically)
    — without it the first C++ exception anywhere dies in
    "AsanCheckFailed real___cxa_throw != 0" instead of propagating.
    Raises NativeUnavailable when the toolchain lacks the runtime."""
    lib = {"asan": "libasan.so", "ubsan": "libubsan.so",
           "tsan": "libtsan.so"}[san]
    preload = f"{_toolchain_lib(lib)} {_toolchain_lib('libstdc++.so')}"
    env = {SAN_ENV: san, "LD_PRELOAD": preload}
    if san == "asan":
        env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
    elif san == "tsan":
        # The suppressions file mutes jaxlib's UNinstrumented
        # xla_extension.so (TSan cannot see its internal sync, so XLA
        # threadpool alloc/free handoffs report as races — third-party
        # noise, while our instrumented twins stay fully checked).
        # detect_deadlocks=0: native/*.cpp holds ZERO mutexes (pure
        # std::atomic; FD406 + grep enforce it), so the experimental
        # lock-order detector can only ever report libgcc/libstdc++/XLA
        # internals — race detection, the lane's point, stays fully on.
        # The shm rings are cross-process and thus OUTSIDE TSan's
        # model — a report against an mmap'd ring cell is an artifact,
        # see docs/OPERATIONS.md before trusting one.
        supp = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tsan.supp")
        env["TSAN_OPTIONS"] = (
            f"halt_on_error=1:detect_deadlocks=0:suppressions={supp}")
    else:
        env["UBSAN_OPTIONS"] = "print_stacktrace=1:halt_on_error=1"
    return env


def _source_key(src: str, flags: list[str]) -> str:
    """Content hash of everything the .so is built from: the flags, the
    source, and every header beside it (fd_bank/fd_net/fd_ring/fd_shred
    include fd_metrics.h).  Keyed on content, not mtime: a checkout or a
    copied tree carries no trustworthy timestamps, and what loads must
    be built from the files git would commit."""
    import glob
    import hashlib

    h = hashlib.sha256(" ".join(flags).encode())
    d = os.path.dirname(os.path.abspath(src))
    for path in [src, *sorted(glob.glob(os.path.join(d, "*.h")))]:
        with open(path, "rb") as f:
            h.update(b"\0" + os.path.basename(path).encode() + b"\0")
            h.update(f.read())
    return h.hexdigest()


def build_so(src: str, so: str, *, force: bool = False) -> str:
    """Compile `src` -> `so` if missing/stale and return the path to
    load.  Stale means the `<so>.key` sidecar does not match the content
    hash of the sources (_source_key); force=True rebuilds regardless.
    Under FDTPU_NATIVE_SAN the build lands in the san/<san>/ twin with
    instrumentation flags — the RETURN VALUE is the loadable path, which
    differs from `so` on that lane.  Raises NativeUnavailable when no
    toolchain exists or the compile fails."""
    san = san_mode()
    flags = _BASE_FLAGS
    if san:
        so = san_so_path(so, san)
        flags = _SAN_FLAGS[san]
        os.makedirs(os.path.dirname(so), exist_ok=True)
    key = _source_key(src, flags)
    key_path = f"{so}.key"
    if not force and os.path.exists(so):
        try:
            with open(key_path) as f:
                if f.read() == key:
                    return so
        except OSError:
            pass
    tmp = f"{so}.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", *flags, "-o", tmp, src],
            check=True,
            capture_output=True,
            text=True,
        )
        os.replace(tmp, so)
        # the key lands after the library: a crash between the two
        # leaves a mismatch, i.e. one more rebuild, never a stale load
        with open(f"{key_path}.{os.getpid()}", "w") as f:
            f.write(key)
        os.replace(f"{key_path}.{os.getpid()}", key_path)
    except subprocess.CalledProcessError as e:
        raise NativeUnavailable(
            f"cannot build {os.path.basename(so)}: {e}\n{e.stderr}") from e
    except OSError as e:
        raise NativeUnavailable(f"cannot build {os.path.basename(so)}: {e}") from e
    finally:
        if os.path.exists(tmp):  # failed/interrupted compile leftovers
            try:
                os.remove(tmp)
            except OSError:
                pass
    return so


NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)


def build_all(*, force: bool = False) -> list[str]:
    """Build every native/*.cpp (the explicit form of what each loader
    does on demand: CI, baked containers, chip_smoke.py's from-source
    rebuild).  Returns the built paths; raises NativeUnavailable on the
    first failure."""
    import glob

    return [
        build_so(src, src[: -len(".cpp")] + ".so", force=force)
        for src in sorted(glob.glob(os.path.join(NATIVE_DIR, "*.cpp")))
    ]


if __name__ == "__main__":
    import sys

    for _so in build_all(force="--force" in sys.argv[1:]):
        print(f"nativebuild: {os.path.relpath(_so, NATIVE_DIR)} ok")
