"""Lazy build of the native library (g++ → libtpusnap.so).

Built on first use and cached next to the source; rebuilt when the source is
newer than the .so (the rebuild-staleness guard: a source edit must never be
silently served by yesterday's binary).  When the rebuild cannot run — no
compiler on the host image — a STALE library is still returned with a
warning: the old entry points keep working and ``native_io`` probes each
newer symbol individually, degrading feature-by-feature instead of losing
the whole data plane.  No pybind11 — the library exposes a C ABI consumed
via ctypes.

zlib support (the native codec-encode offload) is probed at build time:
the first compile attempt links ``-lz`` with ``-DTPUSNAP_WITH_ZLIB``; if
that fails (no zlib dev files), the library builds without it and
``tpusnap_has_zlib()`` reports 0.

zstd is probed the same way per attempt (``-DTPUSNAP_WITH_ZSTD -lzstd``
when the dev headers exist), but unlike zlib a header-less build is NOT a
dead end: the source carries a dlopen shim over the stable ``ZSTD_*`` C
API, so any build linked with ``-ldl`` resolves the runtime
``libzstd.so.1`` most images ship without the -dev package —
``tpusnap_has_zstd()`` reports what the RUNNING process actually found.

Sanitizer builds (``TPUSNAP_NATIVE_SANITIZE={tsan,asan,ubsan}``): the same
source compiles with ``-fsanitize=...`` into a separately-named
``libtpusnap-<mode>.so`` so the production library is never replaced by an
instrumented one.  The race-regression suite (tests/test_native_sanitize.py)
loads that library in a subprocess with the sanitizer runtime preloaded to
catch data races in the worker pool; a time taken while the knob is set
measures the sanitizer.  A sanitizer build that fails (toolchain without the
runtime) returns None — the data plane then degrades to pure Python rather
than silently running uninstrumented.
"""

from __future__ import annotations

import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "tpustore.cc")
_LIB = os.path.join(_HERE, "libtpusnap.so")
_LOCK = threading.Lock()

_BASE_CMD = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]

# Per-sanitizer compile flags.  -O1 -fno-omit-frame-pointer is the
# documented sweet spot for all three: reports keep usable stacks while the
# instrumented code stays fast enough for the race suite's timeout.
_SANITIZE_FLAGS = {
    "tsan": ["-fsanitize=thread", "-O1", "-g", "-fno-omit-frame-pointer"],
    "asan": ["-fsanitize=address", "-O1", "-g", "-fno-omit-frame-pointer"],
    "ubsan": ["-fsanitize=undefined", "-O1", "-g", "-fno-omit-frame-pointer"],
}


def _sanitize_mode() -> str:
    from .. import knobs

    return knobs.get_native_sanitize()


def sanitized_lib_path(mode: str) -> str:
    """Where the ``mode``-instrumented library lives (never ``_LIB``)."""
    return os.path.join(_HERE, f"libtpusnap-{mode}.so")


def _compile(cmd, tmp: str, out: str) -> None:
    subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    # fsync before publishing: a host crash mid-build must leave either the
    # old library or the new one, never a truncated .so that every later
    # process would dlopen (the same tmp+fsync+rename commit discipline the
    # storage layer uses — see docs/static_analysis.md, durability rule).
    fd = os.open(tmp, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    os.replace(tmp, out)


def _build(extra_flags=None, out: Optional[str] = None) -> None:
    """Compile _SRC → ``out`` (default _LIB) atomically; raises on failure."""
    out = out or _LIB
    extra = list(extra_flags or [])
    tmp = out + ".tmp"
    # Ordered best-to-degraded: each attempt drops one optional dependency.
    # -ldl is unconditional (glibc always provides it; the zstd dlopen shim
    # needs it when the dev headers are absent).
    attempts = (
        _BASE_CMD
        + extra
        + ["-DTPUSNAP_WITH_ZLIB", "-DTPUSNAP_WITH_ZSTD", _SRC, "-o", tmp,
           "-lz", "-lzstd", "-ldl"],
        _BASE_CMD + extra + ["-DTPUSNAP_WITH_ZLIB", _SRC, "-o", tmp, "-lz",
                             "-ldl"],
        _BASE_CMD + extra + [_SRC, "-o", tmp, "-ldl"],
        _BASE_CMD + extra + [_SRC, "-o", tmp],
    )
    last_error: Optional[Exception] = None
    for cmd in attempts:
        try:
            _compile(cmd, tmp, out)
            return
        except Exception as e:  # noqa: BLE001
            last_error = e
    raise RuntimeError(f"native build failed: {last_error}")


def rebuild_native_lib() -> str:
    """Compile ``tpustore.cc`` now and replace whatever ``libtpusnap.so``
    is there; raises when the build cannot run.  For callers that must not
    run against a library of unknown origin: the mtime test below trusts a
    copied tree's timestamps, and ``get_native_lib_path`` degrades where
    this fails (chip_smoke.py)."""
    with _LOCK:
        _build()
    return _LIB


def lib_is_stale() -> bool:
    """Whether ``tpustore.cc`` is newer than the built ``libtpusnap.so``
    (or the library is missing entirely)."""
    try:
        return os.path.getmtime(_LIB) < os.path.getmtime(_SRC)
    except OSError:
        return True


def _get_sanitized_lib_path(mode: str) -> Optional[str]:
    """Build-or-reuse the ``mode``-instrumented library.  Unlike the normal
    path there is NO stale-serve fallback: a stale instrumented library is
    rebuilt or the build fails to None — the race suite must never report
    "clean" from yesterday's binary."""
    out = sanitized_lib_path(mode)
    try:
        fresh = os.path.getmtime(out) >= os.path.getmtime(_SRC)
    except OSError:
        fresh = False
    if fresh:
        return out
    try:
        _build(_SANITIZE_FLAGS[mode], out=out)
        return out
    except Exception as e:  # noqa: BLE001
        logger.warning(
            "sanitizer build (%s) unavailable (%s); native data plane "
            "disabled for this process",
            mode,
            e,
        )
        return None


def get_native_lib_path() -> Optional[str]:
    """Path to the built library, rebuilding when the source is newer;
    None only when nothing loadable exists.  A stale library that cannot
    be rebuilt is returned with a warning — callers (native_io) probe the
    symbols they need and degrade per-feature.  With
    ``TPUSNAP_NATIVE_SANITIZE`` set, the instrumented variant is built and
    returned instead (or None when the toolchain can't build it)."""
    with _LOCK:
        mode = _sanitize_mode()
        if mode:
            return _get_sanitized_lib_path(mode)
        have_lib = os.path.exists(_LIB)
        if have_lib and not lib_is_stale():
            return _LIB
        try:
            _build()
            return _LIB
        except Exception as e:  # noqa: BLE001
            if have_lib:
                logger.warning(
                    "tpustore.cc is newer than libtpusnap.so and the rebuild "
                    "failed (%s); using the stale library — newer native "
                    "fast paths may be unavailable",
                    e,
                )
                return _LIB
            logger.warning("Native library unavailable (%s); using fallbacks", e)
            return None


def sanitizer_runtime(mode: str) -> Optional[str]:
    """Path to the sanitizer runtime shared library (libtsan.so/…) for
    LD_PRELOAD, or None when the toolchain doesn't ship one.  Loading an
    instrumented .so into an uninstrumented python needs the runtime mapped
    first — the race suite preloads it in its subprocess."""
    runtime = {"tsan": "libtsan.so", "asan": "libasan.so", "ubsan": "libubsan.so"}[
        mode
    ]
    for compiler in ("g++", "gcc", "clang"):
        try:
            out = subprocess.run(
                [compiler, f"-print-file-name={runtime}"],
                check=True,
                capture_output=True,
                timeout=30,
                text=True,
            ).stdout.strip()
        except Exception:  # noqa: BLE001
            continue
        # An unknown runtime echoes the bare name back; a real one is a path.
        if out and os.path.sep in out and os.path.exists(out):
            return os.path.realpath(out)
    return None
