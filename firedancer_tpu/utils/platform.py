"""Device selection and the persistent compile cache.

One rule for choosing the device: the chip is the default and is
required; the CPU is what a caller asks for.  Entry points that serve or
measure (`run`, `warmup`, benchmarks/run.py, chip_smoke.py) call
`require_chip()` and fail when JAX resolves to anything but a TPU;
tests, the chaos/cluster harnesses, the multi-chip dryrun and every
non-verify child of the process topology call `force_cpu_backend()`
before their first device use.

A chip belongs to one process at a time, so a process that only launches
others must never initialise a backend: nothing in this module touches a
device except `require_chip()`.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChipError(RuntimeError):
    """The process needs a TPU and JAX did not resolve to one."""


def require_chip() -> tuple[str, str, int]:
    """Initialise the default backend and insist it is a TPU.

    Returns (platform, device_kind, device_count) as JAX reports them,
    for the caller to print next to whatever it measures.  Raises
    NoChipError — entry points turn it into a plain message and a
    non-zero exit — when JAX finds no accelerator or resolves to the
    CPU; there is no fallback."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChipError(
            f"no TPU: JAX could not initialise a backend ({e})") from e
    d = devs[0]
    if d.platform != "tpu":
        raise NoChipError(
            f"no TPU: JAX resolved to {d.platform}:{d.device_kind} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}) and "
            f"the chip is required here; where a command takes --cpu, "
            f"that is the explicit CPU run")
    return d.platform, d.device_kind, len(devs)


def select_device(cpu: bool = False,
                  device_count: int | None = None) -> tuple[str, str, int]:
    """The device rule in one call, for every entry point: the chip, or
    the CPU when the caller asked for it (`device_count` virtual host
    devices for a mesh), then join the compile cache.
    -> (platform, device_kind, device_count) as JAX reports them."""
    if cpu:
        force_cpu_backend(device_count)
        import jax

        devs = jax.devices()
        dev = (devs[0].platform, devs[0].device_kind, len(devs))
    else:
        dev = require_chip()
    enable_compile_cache()
    return dev


def process_jax_state() -> str:
    """What this process has done about JAX so far, without initialising
    anything: "none" (never imported), "cpu" (pinned by
    force_cpu_backend) or "default" (imported and unpinned: its first
    device use takes the default backend, i.e. the chip).  The process
    topology logs it per child so a run shows that exactly one process
    can reach the chip."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return "none"
    return "cpu" if jax.config.jax_platforms == "cpu" else "default"


def force_cpu_backend(device_count: int | None = None) -> None:
    """Pin this process to the CPU backend.

    Must be called before the first device use; safe to call more than
    once and before or after `import jax`.  ``device_count`` additionally
    requests N virtual host devices (the multi-chip test mesh)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={device_count}"
            ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: JAX_COMPILATION_CACHE_DIR
    when set, else the one fixed path <checkout>/.jax_cache — the path is
    part of how a later process finds the entries, so nothing about it
    depends on XLA_FLAGS, JAX_PLATFORMS, versions, pids or time.
    Imports no JAX (a launching parent may ask)."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory
    (compile_cache_dir).  The directory can be placed from outside: when
    JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and this
    function sets nothing.

    The CPU backend shares the cache.  The older jaxlib's XLA:CPU AOT
    (de)serialisation segfaults, which the CPU no-op and the
    per-configuration sub-directories worked around, did not reproduce
    on jaxlib 0.9.0 (re-tested for PR 21: the sigverify programs written
    by one CPU process and loaded by the next, with the variable set and
    unset, and tier-1 run twice over one directory); XLA:CPU still logs
    "machine feature +prefer-no-scatter is not supported" on every load
    — its own pseudo-features — and proceeds.  So there is no CPU
    exemption."""
    cache_dir = compile_cache_dir()
    if not os.environ.get(CACHE_DIR_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
