"""Native file I/O data plane (ctypes over libtpusnap).

Replaces aiofiles' thread-pooled Python I/O in the hot path (reference
/root/reference/torchsnapshot/storage_plugins/fs.py): whole-buffer writes and
(ranged) reads happen in one C call each, with the GIL released by ctypes for
the entire syscall loop — no Python-level chunking overhead.

Beyond per-call GIL release, the library runs an internal C++ worker pool
(``TPUSNAP_NATIVE_THREADS``) executing the off-GIL data plane:

- ``write_parts_hash`` — ONE call per payload/slab that writes all member
  buffers AND returns each member's digest, hash and write fused over the
  same cache-resident bytes;
- ``write_parts_hash_batch`` — N payloads in ONE call and ONE pool
  submission (the fs plugin's micro-batcher feeds it), so thousand-leaf
  drains stop being FFI-dispatch-bound;
- ``xxhash64_striped`` — the parallel "xxh64s" digest for large buffers
  (independent per-stripe xxh64s combined over the digest stream);
- ``read_ranges_hash`` — multi-range pread fan-out with optional fused
  per-range hashing for restore and audit;
- ``touch_pages`` — the first touch of a restore's host arena, every page
  written once in parallel before a read lands in it;
- native codec encode/decode straight into/out of compression frames
  (zlib byte-identical to Python's; zstd as standard frames the
  ``zstandard`` wheel cross-decodes);
- an opt-in direct-I/O write plane (``TPUSNAP_DIRECT_IO``): io_uring →
  aligned pwrite+O_DIRECT → buffered capability ladder with a one-time
  ``native.degraded`` event when a filesystem forces the last rung.

``TPUSNAP_NATIVE=0`` disables the whole native plane (``maybe_create``
returns None); every consumer then takes a byte-identical pure-Python path.
A stale library missing the newer symbols degrades per-feature: the
``has_*`` capability flags gate each fast path and a one-time
``native.degraded`` event records what was lost.
"""

from __future__ import annotations

import ctypes
import logging
import os
from typing import Any, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

# Digest striping policy — these constants DEFINE the "xxh64s" digest value
# (recorded in manifests, naming CAS chunks) and are mirrored by the native
# library call arguments and integrity.py's pure-Python fallback.  Changing
# them changes every striped digest: never bump without a new algo tag.
STRIPE_BYTES = 8 << 20
STRIPED_MIN_BYTES = 32 << 20

# The native data plane's ABI generation.  native_io reads the library's
# tpusnap_abi_version() at load and treats a mismatch exactly like missing
# symbols (full per-feature degrade): a STALE .so that still EXPORTS every
# entry point but with changed semantics (a hash fix, a different stripe
# combination) must never silently fill manifests with divergent digests.
# Bump in lockstep with TPUSNAP_ABI_VERSION in tpustore.cc whenever any
# existing entry point's observable behavior changes.
NATIVE_ABI_VERSION = 1


class NativeZlibError(RuntimeError):
    """Native deflate could not run (unavailable, bad level, Z_MEM_ERROR) —
    distinct from the None 'did not fit' result; callers fall back to the
    Python codec, whose output is byte-identical."""


class NativeZstdError(RuntimeError):
    """Native zstd could not run (backend unavailable or a real codec
    error) — distinct from the None 'did not fit' result.  Callers fall
    back to the ``zstandard`` wheel; frames are standard zstd frames, so
    the two backends decode each other's output."""


def _contiguous_views(parts: Sequence[Any]) -> "List[memoryview]":
    """Each part as a C-contiguous uint8 memoryview (non-contiguous parts
    are copied once) — the ONE normalization every native call shares."""
    views = []
    for part in parts:
        view = memoryview(part)
        if not view.c_contiguous:
            view = memoryview(bytes(view))
        views.append(view.cast("B"))
    return views


def _views_ctypes(views: Sequence[Any]):
    """(arrs, bufs, sizes) ctypes marshalling for a view list.  ``arrs``
    alias the views' memory zero-copy (np.frombuffer works on read-only
    buffers — the jax staging case) and MUST stay referenced for the
    duration of the native call.  Empty views marshal as NULL/0."""
    import numpy as np

    n = max(len(views), 1)
    arrs = [np.frombuffer(v, np.uint8) if v.nbytes else None for v in views]
    bufs = (ctypes.c_void_p * n)(
        *(a.ctypes.data if a is not None else None for a in arrs)
    )
    sizes = (ctypes.c_int64 * n)(*(v.nbytes for v in views))
    return arrs, bufs, sizes


def striped_hash64(view: memoryview, hash64) -> int:
    """The ONE Python-side implementation of the "xxh64s" combination:
    per-STRIPE_BYTES digests via ``hash64`` (any xxh64-compatible callable
    returning an int), combined by hashing their little-endian u64 stream.
    Both fallbacks — the xxhash wheel (integrity.py) and a stale native
    library without the striped symbol — go through here, so they cannot
    drift from each other (the native C implementation mirrors it and is
    pinned by the parity tests)."""
    import struct

    if view.nbytes <= STRIPE_BYTES:
        return hash64(view)
    packed = b"".join(
        struct.pack("<Q", hash64(view[o : o + STRIPE_BYTES]))
        for o in range(0, view.nbytes, STRIPE_BYTES)
    )
    return hash64(packed)


class NativeFileIO:
    _instance: Optional["NativeFileIO"] = None
    _failed = False
    _degraded_reported = False

    def __init__(self) -> None:
        from ._native.build import get_native_lib_path

        path = get_native_lib_path()
        if path is None:
            raise RuntimeError("native IO library unavailable")
        lib = ctypes.CDLL(path)
        lib.tpusnap_write_file.restype = ctypes.c_int
        lib.tpusnap_write_file.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_int64,
        ]
        lib.tpusnap_write_file_parts.restype = ctypes.c_int
        lib.tpusnap_write_file_parts.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
        ]
        lib.tpusnap_read_range.restype = ctypes.c_int
        lib.tpusnap_read_range.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.tpusnap_file_size.restype = ctypes.c_int64
        lib.tpusnap_file_size.argtypes = [ctypes.c_char_p]
        lib.tpusnap_xxhash64.restype = ctypes.c_uint64
        lib.tpusnap_xxhash64.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_uint64,
        ]
        lib.tpusnap_read_range_hash.restype = ctypes.c_int
        lib.tpusnap_read_range_hash.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        self._lib = lib
        self._probe_data_plane(lib)

    def _probe_data_plane(self, lib: ctypes.CDLL) -> None:
        """Bind the off-GIL data-plane symbols, degrading per-feature when
        a stale library predates them (build.py returns a stale .so rather
        than nothing when the rebuild can't run)."""
        missing: List[str] = []

        # ABI generation gate: a stale library that still exports every
        # symbol but with changed semantics must degrade like one missing
        # them all — per-symbol probing alone can't see a behavior change.
        abi_ok = False
        try:
            fn = lib.tpusnap_abi_version
            fn.restype = ctypes.c_int
            fn.argtypes = []
            abi_ok = int(fn()) == NATIVE_ABI_VERSION
        except AttributeError:
            pass
        if not abi_ok:
            missing.append(f"abi_version=={NATIVE_ABI_VERSION}")

        def _bind(name: str, restype, argtypes, optional: bool = False) -> bool:
            if not abi_ok:
                return False
            try:
                fn = getattr(lib, name)
            except AttributeError:
                if not optional:  # nothing falls back to Python: not reported
                    missing.append(name)
                return False
            fn.restype = restype
            fn.argtypes = argtypes
            return True

        self.has_pool = _bind(
            "tpusnap_pool_configure", None, [ctypes.c_int]
        ) and _bind("tpusnap_pool_size", ctypes.c_int, [])
        self.has_striped_hash = _bind(
            "tpusnap_xxhash64_striped",
            ctypes.c_uint64,
            [ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int64],
        )
        self.has_fused_write = _bind(
            "tpusnap_write_parts_hash",
            ctypes.c_int,
            [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int,
                ctypes.c_uint64,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint64),
            ],
        )
        self.has_ranged_read = _bind(
            "tpusnap_read_ranges_hash",
            ctypes.c_int,
            [
                ctypes.c_char_p,
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int,
                ctypes.c_uint64,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint64),
            ],
        )
        # Optional: without it a restore's host arena is populated by its
        # reads, page by page, as it was before the symbol existed.
        self.has_touch_pages = _bind(
            "tpusnap_touch_pages",
            None,
            [ctypes.c_void_p, ctypes.c_int64],
            optional=True,
        )
        self.has_batch_write = _bind(
            "tpusnap_write_parts_hash_batch",
            ctypes.c_int,
            [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int,
                ctypes.c_uint64,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_int),
            ],
        )
        self.has_direct_io = _bind(
            "tpusnap_direct_io_configure", ctypes.c_int, [ctypes.c_int]
        ) and _bind("tpusnap_direct_io_mode", ctypes.c_int, [])
        self.has_cdc = _bind(
            "tpusnap_cdc_boundaries",
            ctypes.c_int64,
            [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
            ],
        )
        # Advanced-parameter zstd (window log / long-distance matching).
        # Probed independently of the basic codec pair: a stale library can
        # have zstd without it, and the codec tier then falls back to the
        # plain encode with a one-time warning.
        self.has_zstd_params = _bind(
            "tpusnap_zstd_encode2",
            ctypes.c_int64,
            [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
            ],
        )
        self.has_zlib = False
        if _bind("tpusnap_has_zlib", ctypes.c_int, []):
            _bind(
                "tpusnap_zlib_encode",
                ctypes.c_int64,
                [
                    ctypes.c_void_p,
                    ctypes.c_int64,
                    ctypes.c_void_p,
                    ctypes.c_int64,
                    ctypes.c_int,
                ],
            )
            self.has_zlib = bool(lib.tpusnap_has_zlib())
        self.has_zstd = False
        if (
            _bind("tpusnap_has_zstd", ctypes.c_int, [])
            and _bind(
                "tpusnap_zstd_encode",
                ctypes.c_int64,
                [
                    ctypes.c_void_p,
                    ctypes.c_int64,
                    ctypes.c_void_p,
                    ctypes.c_int64,
                    ctypes.c_int,
                ],
            )
            and _bind(
                "tpusnap_zstd_decode",
                ctypes.c_int64,
                [
                    ctypes.c_void_p,
                    ctypes.c_int64,
                    ctypes.c_void_p,
                    ctypes.c_int64,
                ],
            )
        ):
            # Runtime-probed: 1 only when the library actually resolved a
            # zstd backend (compile-time link or the dlopen shim).
            self.has_zstd = bool(lib.tpusnap_has_zstd())
        if self.has_pool:
            from . import knobs

            lib.tpusnap_pool_configure(knobs.get_native_threads())
        if missing:
            self._report_degraded(missing)

    @classmethod
    def _report_degraded(cls, missing: List[str]) -> None:
        if cls._degraded_reported:
            return
        cls._degraded_reported = True
        logger.warning(
            "libtpusnap.so is missing data-plane symbols %s (stale build?); "
            "the corresponding fast paths fall back to Python",
            missing,
        )
        try:
            from .event import Event
            from .event_handlers import log_event
            from .telemetry import metrics as tmetrics

            tmetrics.record_native_degraded("stale_library")
            log_event(
                Event(
                    name="native.degraded",
                    metadata={"missing": sorted(missing)},
                )
            )
        except Exception:
            pass  # telemetry must never break the data plane

    def pool_size(self) -> int:
        """Current size of the native worker pool (0 before lazy creation);
        requires ``has_pool``."""
        return int(self._lib.tpusnap_pool_size())

    def xxhash64(self, buf) -> int:
        view = memoryview(buf)
        if not view.c_contiguous:
            view = memoryview(bytes(view))
        view = view.cast("B")
        nbytes = view.nbytes
        if nbytes == 0:
            return int(self._lib.tpusnap_xxhash64(b"", 0, 0))
        if isinstance(buf, bytes):
            c_buf: Any = ctypes.c_char_p(buf)
        else:
            # Zero-copy even for read-only views (np.asarray of a jax.Array
            # is read-only — the common TPU save path): np.frombuffer aliases
            # the buffer without copying and exposes its address.
            import numpy as np

            arr = np.frombuffer(view, np.uint8)
            c_buf = ctypes.c_void_p(arr.ctypes.data)
        return int(self._lib.tpusnap_xxhash64(c_buf, nbytes, 0))

    def xxhash64_striped(self, buf) -> int:
        """The striped ("xxh64s") digest of ``buf``: per-STRIPE_BYTES xxh64
        digests combined via xxh64 over their little-endian stream, computed
        in parallel on the native worker pool.  Falls back to a sequential
        per-stripe loop over the plain hasher when the library predates the
        symbol — same value either way."""
        view = memoryview(buf)
        if not view.c_contiguous:
            view = memoryview(bytes(view))
        view = view.cast("B")
        if self.has_striped_hash:
            import numpy as np

            if view.nbytes == 0:
                return int(self._lib.tpusnap_xxhash64_striped(b"", 0, 0, STRIPE_BYTES))
            arr = np.frombuffer(view, np.uint8)
            return int(
                self._lib.tpusnap_xxhash64_striped(
                    ctypes.c_void_p(arr.ctypes.data),
                    view.nbytes,
                    0,
                    STRIPE_BYTES,
                )
            )
        return striped_hash64(view, self.xxhash64)

    def write_parts_hash(self, path: str, parts: Sequence[Any]) -> List[int]:
        """Fused write+hash: ``parts`` land sequentially in one file while
        each part's digest is computed from the same cache-resident bytes on
        the native worker pool.  Returns one hash per part, in order (parts
        of >= STRIPED_MIN_BYTES are "xxh64s" digests, smaller ones plain
        "xxh64" — ``integrity.format_digest`` applies the same policy).
        Zero-length parts are kept (their digest is the empty hash)."""
        views = _contiguous_views(parts)
        n = len(views)
        if n == 0:
            with open(path, "wb"):
                return []
        arrs, bufs, sizes = _views_ctypes(views)
        out = (ctypes.c_uint64 * n)()
        rc = self._lib.tpusnap_write_parts_hash(
            path.encode(),
            bufs,
            sizes,
            n,
            0,
            STRIPE_BYTES,
            STRIPED_MIN_BYTES,
            out,
        )
        if rc != 0:
            raise OSError(-rc, os.strerror(-rc), path)
        return list(out)

    def write_parts_hash_batch(
        self, jobs: Sequence[Tuple[str, Sequence[Any]]]
    ) -> List[Any]:
        """Batched fused write+hash: every ``(path, parts)`` job crosses
        the FFI boundary in ONE call and enters the native pool as one
        task set — the per-payload dispatch cost a drain of small requests
        (thousand-leaf optimizer trees) otherwise pays per file.  Returns
        one result per job, in order: the job's per-part digest list
        (identical to what ``write_parts_hash`` would return), or an
        ``OSError`` instance when that job's write failed — error
        isolation per member, so one full disk never discards siblings'
        completed writes.  Requires ``has_batch_write``."""
        njobs = len(jobs)
        if njobs == 0:
            return []
        paths: List[bytes] = []
        parts_per: List[int] = []
        views: List[Any] = []
        for path, parts in jobs:
            paths.append(path.encode())
            job_views = _contiguous_views(parts)
            views.extend(job_views)
            parts_per.append(len(job_views))
        total = len(views)
        arrs, bufs, sizes = _views_ctypes(views)
        out = (ctypes.c_uint64 * max(total, 1))()
        errs = (ctypes.c_int * njobs)()
        c_paths = (ctypes.c_char_p * njobs)(*paths)
        c_parts = (ctypes.c_int * njobs)(*parts_per)
        rc = self._lib.tpusnap_write_parts_hash_batch(
            c_paths,
            njobs,
            c_parts,
            bufs,
            sizes,
            total,
            0,
            STRIPE_BYTES,
            STRIPED_MIN_BYTES,
            out,
            errs,
        )
        del rc  # per-job outcomes live in errs; rc is just the first of them
        results: List[Any] = []
        index = 0
        for job_i, count in enumerate(parts_per):
            err = int(errs[job_i])
            if err != 0:
                results.append(OSError(-err, os.strerror(-err), paths[job_i].decode()))
            else:
                results.append([int(out[index + k]) for k in range(count)])
            index += count
        return results

    def read_ranges_into(
        self,
        path: str,
        ranges: Sequence[Tuple[int, int]],
        views: Sequence[Any],
        want_hash: bool = False,
    ) -> Optional[List[int]]:
        """Parallel multi-range pread into caller-owned buffers, optionally
        fused with per-range hashing (striped for ranges >=
        STRIPED_MIN_BYTES, plain below).  ``ranges`` are absolute
        ``(offset, end)`` file extents; ``views[i]`` must be writable and
        exactly ``end - offset`` bytes.  Returns per-range hashes when
        ``want_hash`` else None."""
        import numpy as np

        n = len(ranges)
        if n == 0:
            return [] if want_hash else None
        arrs = []
        for (off, end), view in zip(ranges, views):
            mv = memoryview(view)
            if mv.nbytes != end - off:
                raise ValueError(
                    f"range [{off}, {end}) needs {end - off} bytes, "
                    f"destination has {mv.nbytes}"
                )
            arrs.append(np.frombuffer(mv, np.uint8) if mv.nbytes else None)
        bufs = (ctypes.c_void_p * n)(
            *(a.ctypes.data if a is not None else None for a in arrs)
        )
        offs = (ctypes.c_int64 * n)(*(off for off, _ in ranges))
        lens = (ctypes.c_int64 * n)(*(end - off for off, end in ranges))
        out = (ctypes.c_uint64 * n)()
        rc = self._lib.tpusnap_read_ranges_hash(
            path.encode(),
            n,
            offs,
            lens,
            bufs,
            1 if want_hash else 0,
            0,
            STRIPE_BYTES,
            STRIPED_MIN_BYTES,
            out,
        )
        if rc != 0:
            raise OSError(-rc, os.strerror(-rc), path)
        return list(out) if want_hash else None

    def touch_pages(self, buf) -> None:
        """Write one byte (0, the first of each page) to every page of the
        writable numpy buffer ``buf``, on the native worker pool and this
        thread together, the GIL released: the first touch of fresh
        anonymous memory, taken in parallel.  The kernel writes the byte (a
        ``readv`` from ``/dev/zero``), so that the first ``pread`` into the
        page finds it as a later one would.  Requires ``has_touch_pages``."""
        self._lib.tpusnap_touch_pages(ctypes.c_void_p(buf.ctypes.data), buf.nbytes)

    def zlib_encode_into(self, src, dst, level: int) -> Optional[int]:
        """Deflate ``src`` directly into ``dst`` (a writable view sized to
        the incompressible cap), byte-identical to ``zlib.compress(src,
        level)``.  Returns the encoded length, or None when the output
        would not fit ``dst`` — the genuinely-incompressible signal the
        caller turns into a raw frame.  A real zlib failure (bad level,
        Z_MEM_ERROR) raises :class:`NativeZlibError` instead: conflating it
        with "didn't fit" would silently store a compressible payload raw;
        the caller catches it and retries through Python zlib."""
        if not self.has_zlib:
            raise NativeZlibError("native zlib unavailable")
        import numpy as np

        src_view = memoryview(src)
        if not src_view.c_contiguous:
            src_view = memoryview(bytes(src_view))
        src_view = src_view.cast("B")
        if src_view.nbytes == 0:
            raise NativeZlibError("empty input")
        dst_view = memoryview(dst)
        src_arr = np.frombuffer(src_view, np.uint8)
        dst_arr = np.frombuffer(dst_view, np.uint8)
        n = self._lib.tpusnap_zlib_encode(
            ctypes.c_void_p(src_arr.ctypes.data),
            src_view.nbytes,
            ctypes.c_void_p(dst_arr.ctypes.data),
            dst_view.nbytes,
            int(level),
        )
        if n > 0:
            return int(n)
        if n == -1:
            return None  # would not shrink below the cap
        raise NativeZlibError(f"compress2 failed (rc {int(n)})")

    def zstd_encode_into(self, src, dst, level: int) -> Optional[int]:
        """Native zstd straight into ``dst`` (a writable view sized to the
        incompressible cap).  Returns the encoded length, or None when the
        output would not fit ``dst`` — the genuinely-incompressible signal
        the caller turns into a raw frame.  A real codec failure raises
        :class:`NativeZstdError`; the caller retries through the
        ``zstandard`` wheel (standard zstd frames either way)."""
        if not self.has_zstd:
            raise NativeZstdError("native zstd unavailable")
        import numpy as np

        src_view = memoryview(src)
        if not src_view.c_contiguous:
            src_view = memoryview(bytes(src_view))
        src_view = src_view.cast("B")
        if src_view.nbytes == 0:
            raise NativeZstdError("empty input")
        dst_view = memoryview(dst)
        src_arr = np.frombuffer(src_view, np.uint8)
        dst_arr = np.frombuffer(dst_view, np.uint8)
        n = self._lib.tpusnap_zstd_encode(
            ctypes.c_void_p(src_arr.ctypes.data),
            src_view.nbytes,
            ctypes.c_void_p(dst_arr.ctypes.data),
            dst_view.nbytes,
            int(level),
        )
        if n > 0:
            return int(n)
        if n == -1:
            return None  # would not shrink below the cap
        raise NativeZstdError(f"ZSTD_compress failed (rc {int(n)})")

    def cdc_boundaries(
        self, buf, min_size: int, avg_size: int, max_size: int
    ) -> List[int]:
        """Content-defined chunk END offsets of ``buf`` (ascending, last ==
        nbytes) — the gear-hash candidate scan striped across the native
        worker pool.  Byte-identical to ``chunker.boundaries_py`` (the
        boundaries name CAS chunks; parity is pinned by tests).  Requires
        ``has_cdc``."""
        import numpy as np

        view = memoryview(buf)
        if not view.c_contiguous:
            view = memoryview(bytes(view))
        view = view.cast("B")
        n = view.nbytes
        if n == 0:
            return []
        arr = np.frombuffer(view, np.uint8)
        cap = n // min_size + 2
        out = (ctypes.c_int64 * cap)()
        rc = self._lib.tpusnap_cdc_boundaries(
            ctypes.c_void_p(arr.ctypes.data),
            n,
            min_size,
            avg_size,
            max_size,
            out,
            cap,
        )
        if rc < 0:
            raise ValueError(
                f"tpusnap_cdc_boundaries failed (rc {int(rc)}) for "
                f"min={min_size} avg={avg_size} max={max_size}"
            )
        return list(out[: int(rc)])

    def zstd_encode2_into(
        self, src, dst, level: int, window_log: int, enable_ldm: bool
    ) -> Optional[int]:
        """Native zstd encode with advanced parameters (window log /
        long-distance matching) straight into ``dst``.  Same didn't-fit
        contract as :meth:`zstd_encode_into` (None = store raw); raises
        :class:`NativeZstdError` on real failures, including an ancient
        libzstd without the cctx API — callers fall back to the plain
        encode (standard frames either way)."""
        if not self.has_zstd or not self.has_zstd_params:
            raise NativeZstdError("native zstd advanced API unavailable")
        import numpy as np

        src_view = memoryview(src)
        if not src_view.c_contiguous:
            src_view = memoryview(bytes(src_view))
        src_view = src_view.cast("B")
        if src_view.nbytes == 0:
            raise NativeZstdError("empty input")
        dst_view = memoryview(dst)
        src_arr = np.frombuffer(src_view, np.uint8)
        dst_arr = np.frombuffer(dst_view, np.uint8)
        n = self._lib.tpusnap_zstd_encode2(
            ctypes.c_void_p(src_arr.ctypes.data),
            src_view.nbytes,
            ctypes.c_void_p(dst_arr.ctypes.data),
            dst_view.nbytes,
            int(level),
            int(window_log),
            1 if enable_ldm else 0,
        )
        if n > 0:
            return int(n)
        if n == -1:
            return None  # would not shrink below the cap
        raise NativeZstdError(f"ZSTD_compress2 failed (rc {int(n)})")

    def zstd_decode_into(self, src, dst) -> int:
        """Native zstd decode of one frame's payload into ``dst`` (a
        writable view of the recorded uncompressed size).  Returns the
        decoded length; raises :class:`NativeZstdError` on any decode
        failure (corrupt frame, backend missing) — the caller maps it to
        the codec tier's FrameError."""
        if not self.has_zstd:
            raise NativeZstdError("native zstd unavailable")
        import numpy as np

        src_view = memoryview(src)
        if not src_view.c_contiguous:
            src_view = memoryview(bytes(src_view))
        src_view = src_view.cast("B")
        dst_view = memoryview(dst)
        src_arr = np.frombuffer(src_view, np.uint8)
        dst_arr = np.frombuffer(dst_view, np.uint8)
        n = self._lib.tpusnap_zstd_decode(
            ctypes.c_void_p(src_arr.ctypes.data),
            src_view.nbytes,
            ctypes.c_void_p(dst_arr.ctypes.data),
            dst_view.nbytes,
        )
        if n < 0:
            raise NativeZstdError(f"ZSTD_decompress failed (rc {int(n)})")
        return int(n)

    # ------------------------------------------------------- direct I/O

    _direct_io_reported = False

    def configure_direct_io(self, enabled: bool) -> int:
        """Resolve the direct-I/O capability ladder for this process
        (``TPUSNAP_DIRECT_IO``): io_uring → aligned pwrite+O_DIRECT →
        buffered.  Returns the resolved mode (0 off, 1 uring, 2 O_DIRECT,
        3 buffered fallback); 0 when the library predates the symbols."""
        if not self.has_direct_io:
            return 0
        return int(self._lib.tpusnap_direct_io_configure(1 if enabled else 0))

    def direct_io_mode(self) -> int:
        """Current resolved direct-I/O mode (see configure_direct_io);
        may degrade from 1/2 to 3 at the first write to a filesystem that
        rejects O_DIRECT."""
        if not self.has_direct_io:
            return 0
        return int(self._lib.tpusnap_direct_io_mode())

    def check_direct_io_degrade(self) -> None:
        """One-time ``native.degraded`` event when direct I/O was
        requested but the process degraded to buffered writes (mode 3 —
        the filesystem rejected O_DIRECT).  Called by the fs plugin after
        native writes while the knob is on; writes themselves already
        succeeded through the fallback, this only makes the loss
        observable."""
        if NativeFileIO._direct_io_reported or not self.has_direct_io:
            return
        if self.direct_io_mode() != 3:
            return
        NativeFileIO._direct_io_reported = True
        logger.warning(
            "TPUSNAP_DIRECT_IO requested but the filesystem rejected "
            "O_DIRECT; payload writes fall back to buffered I/O"
        )
        try:
            from .event import Event
            from .event_handlers import log_event
            from .telemetry import metrics as tmetrics

            tmetrics.record_native_degraded("direct_io")
            log_event(
                Event(
                    name="native.degraded",
                    metadata={"missing": ["direct_io"], "mode": "buffered"},
                )
            )
        except Exception:
            pass  # telemetry must never break the data plane

    @classmethod
    def maybe_create(cls) -> Optional["NativeFileIO"]:
        from . import knobs

        if not knobs.native_enabled():
            # TPUSNAP_NATIVE=0: force the byte-identical pure-Python path.
            # Checked per call so tests can toggle the knob; the built
            # instance stays cached for when it flips back on.
            return None
        # Validate the sanitize knob OUTSIDE the swallowed constructor
        # path: a typo'd TPUSNAP_NATIVE_SANITIZE must fail loudly (the
        # knob's contract), not silently run every save pure-Python via
        # the sticky _failed flag.
        knobs.get_native_sanitize()
        if cls._failed:
            return None
        if cls._instance is None:
            try:
                cls._instance = cls()
            except Exception:
                cls._failed = True
                logger.warning(
                    "native data plane unavailable; every save and restore "
                    "of this process takes the pure-Python path",
                    exc_info=True,
                )
                return None
        return cls._instance

    def write_file(self, path: str, buf) -> None:
        view = memoryview(buf)
        if not view.c_contiguous:
            view = memoryview(bytes(view))
        nbytes = view.nbytes
        if nbytes == 0:
            with open(path, "wb"):
                return
        # Zero-copy regardless of writability: np.frombuffer aliases any
        # buffer (incl. the read-only host views jax staging produces) and
        # exposes its address for the GIL-released native write.
        import numpy as np

        arr = np.frombuffer(view, np.uint8)
        c_buf = ctypes.c_void_p(arr.ctypes.data)
        rc = self._lib.tpusnap_write_file(path.encode(), c_buf, nbytes)
        if rc != 0:
            raise OSError(-rc, os.strerror(-rc), path)

    def write_file_parts(self, path: str, parts: List[Any]) -> None:
        """Scatter-gather write: parts land sequentially in one file with no
        pack memcpy.  The GIL is released for the whole C write loop."""
        views = [v for v in _contiguous_views(parts) if v.nbytes]
        n = len(views)
        if n == 0:
            with open(path, "wb"):
                return
        arrs, bufs, sizes = _views_ctypes(views)
        rc = self._lib.tpusnap_write_file_parts(path.encode(), bufs, sizes, n)
        if rc != 0:
            raise OSError(-rc, os.strerror(-rc), path)

    def read_file(
        self,
        path: str,
        byte_range: Optional[List[int]],
        want_hash: bool = False,
    ) -> "tuple[bytearray, Optional[int]]":
        """Ranged read into a fresh buffer; with ``want_hash`` the xxh64 of
        the read bytes is computed fused in C (see read_file_into)."""
        if byte_range is None:
            size = self._lib.tpusnap_file_size(path.encode())
            if size < 0:
                raise OSError(-size, os.strerror(-size), path)
            offset, nbytes = 0, size
        else:
            offset = byte_range[0]
            nbytes = byte_range[1] - byte_range[0]
        out = bytearray(nbytes)
        hash64: Optional[int] = None
        if nbytes:
            c_buf = (ctypes.c_char * nbytes).from_buffer(out)
            if want_hash:
                h = ctypes.c_uint64()
                rc = self._lib.tpusnap_read_range_hash(
                    path.encode(), c_buf, offset, nbytes, 0, ctypes.byref(h)
                )
                hash64 = int(h.value) if rc == 0 else None
            else:
                rc = self._lib.tpusnap_read_range(
                    path.encode(), c_buf, offset, nbytes
                )
            if rc != 0:
                raise OSError(-rc, os.strerror(-rc), path)
        return out, hash64

    def read_file_into(
        self,
        path: str,
        byte_range: Optional[List[int]],
        view: Any,
        want_hash: bool = False,
    ) -> Optional[int]:
        """Ranged pread straight into a caller-owned writable buffer — the
        zero-copy restore path (no bytearray allocation, no consume memcpy).

        With ``want_hash`` the read and its xxh64 are fused in C (each block
        hashed cache-hot right after its pread), and the digest of exactly
        the read bytes is returned — the consumer's integrity check then
        skips its own full pass over the payload."""
        import numpy as np

        mv = memoryview(view)
        if byte_range is None:
            offset, nbytes = 0, mv.nbytes
        else:
            offset = byte_range[0]
            nbytes = byte_range[1] - byte_range[0]
        if nbytes == 0:
            return None
        if mv.nbytes != nbytes:
            raise ValueError(f"into-view is {mv.nbytes} bytes, range is {nbytes}")
        arr = np.frombuffer(mv, np.uint8)
        if want_hash:
            out = ctypes.c_uint64()
            rc = self._lib.tpusnap_read_range_hash(
                path.encode(),
                ctypes.c_void_p(arr.ctypes.data),
                offset,
                nbytes,
                0,
                ctypes.byref(out),
            )
            if rc != 0:
                raise OSError(-rc, os.strerror(-rc), path)
            return int(out.value)
        rc = self._lib.tpusnap_read_range(
            path.encode(), ctypes.c_void_p(arr.ctypes.data), offset, nbytes
        )
        if rc != 0:
            raise OSError(-rc, os.strerror(-rc), path)
        return None
