#!/usr/bin/env bash
# Build every native (C++) hot-path component.
#
# The runtime builds these on demand (utils/nativebuild.build_so: one set
# of flags, staleness keyed on a content hash of the .cpp AND the headers
# beside it, atomic rename so concurrent stage processes never see a
# half-written .so); this script is the explicit form of the same builder
# for CI, containers baked ahead of time, and clean rebuilds.  Hosts
# without a toolchain are fine: every loader raises NativeUnavailable and
# its caller falls back to the Python lane, and the tests SKIP (never
# fail).
#
# Sanitizer lane (ISSUE 15): `--san asan|ubsan|tsan` builds instrumented
# twins into native/san/<san>/ (FDTPU_NATIVE_SAN selects the same lane at
# run time).  Run the suites against them with
#   FDTPU_NATIVE_SAN=asan LD_PRELOAD="$(g++ -print-file-name=libasan.so)" \
#     ASAN_OPTIONS=detect_leaks=0 python -m pytest tests/test_native_san.py
# (docs/OPERATIONS.md has the full runbook).
#
# Usage: scripts/build_native.sh [--force] [--san asan|ubsan|tsan]

set -euo pipefail
cd "$(dirname "$0")/.."

args=()
while [ $# -gt 0 ]; do
    case "$1" in
        --force) args+=(--force) ;;
        --san)
            shift
            case "${1:-}" in
                asan|ubsan|tsan) export FDTPU_NATIVE_SAN="$1" ;;
                *) echo "build_native: --san expects asan|ubsan|tsan (got '${1:-}')" >&2; exit 2 ;;
            esac
            ;;
        *) echo "build_native: unknown arg '$1'" >&2; exit 2 ;;
    esac
    shift
done

if ! command -v g++ >/dev/null 2>&1; then
    echo "build_native: no g++ on this host; runtime falls back to python lanes" >&2
    exit 0
fi

exec "${PYTHON:-python3}" -m firedancer_tpu.utils.nativebuild "${args[@]}"
