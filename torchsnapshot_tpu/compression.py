"""Pluggable chunk-compression codecs + the self-describing frame format.

Every byte the pipeline persists is raw by default; on object-store-backed
TPU hosts bytes-on-the-wire is the dominant save/restore cost (cloud
plugins are bottlenecked on payload size; not measured on the chip).
This module is the codec tier the production stacks ship (Orbax/TensorStore
compress chunks by default): a registry of codecs (``raw``, ``zstd``,
``lz4``, plus always-available stdlib ``zlib``) and a 16-byte per-chunk
frame header so every compressed payload is self-describing on disk.

Frame layout (little-endian, 16 bytes)::

    offset  size  field
    0       4     magic  b"TSNC"
    4       1     codec id (0=raw 1=zstd 2=lz4 3=zlib)
    5       1     flags  (reserved, 0)
    6       2     reserved (0)
    8       8     uncompressed payload length (u64)

followed by the codec's compressed bytes.  The header — not the manifest —
is authoritative for decoding: a stager that planned ``zstd`` but found the
payload incompressible stores the bytes raw *inside* a frame (codec id 0),
and the reader never needs to know.  The manifest's ``codec`` field answers
only "is this payload framed at all" (``None`` = legacy bare bytes, the
pre-compression format, which must keep restoring unchanged) plus operator
display.

Codec availability is probed lazily with graceful degradation: a configured
codec with no usable backend resolves to ``raw`` with a one-time warning —
a checkpoint must never fail because a host image lacks ``zstandard``.
Backends resolve native-first: zstd and zlib run through libtpusnap when it
is loaded (zstd via the library's own runtime probe — no dev headers or
wheel required), with the optional wheels as ordered fallbacks; frames are
interchangeable across backends (zlib byte-identical, zstd standard
frames).  Decoding a frame with no backend at all raises
:class:`FrameError` (the bytes genuinely cannot be recovered there).

Integrity contract: manifest checksums cover the FRAME (exactly the bytes
on disk), so ``verify``/``audit`` and the read-fused xxh64 path work on
compressed payloads without decompressing.
"""

from __future__ import annotations

import logging
import struct
from typing import Any, Callable, Dict, Optional, Tuple

logger = logging.getLogger(__name__)

MAGIC = b"TSNC"
_HEADER = struct.Struct("<4sBBHQ")
HEADER_BYTES = _HEADER.size  # 16


class FrameError(RuntimeError):
    """A frame that cannot be decoded: truncated, corrupted, unknown codec,
    or a codec whose library is unavailable on this host."""


class _Codec:
    __slots__ = (
        "name",
        "codec_id",
        "_compress",
        "_decompress",
        "default_level",
        "_available",
    )

    def __init__(
        self,
        name: str,
        codec_id: int,
        compress: Callable[[bytes, Optional[int]], bytes],
        decompress: Callable[[bytes, int], bytes],
        default_level: Optional[int] = None,
        available: Optional[Callable[[], bool]] = None,
    ) -> None:
        self.name = name
        self.codec_id = codec_id
        self._compress = compress
        self._decompress = decompress
        self.default_level = default_level
        self._available = available

    def compress(self, data, level: Optional[int] = None) -> bytes:
        return self._compress(data, level if level is not None else self.default_level)

    def decompress(self, data, uncompressed_len: int) -> bytes:
        return self._decompress(data, uncompressed_len)

    def is_available(self) -> bool:
        """Whether a backend can run RIGHT NOW.  Per-call for codecs whose
        backends come and go (zstd loses its native backend under
        ``TPUSNAP_NATIVE=0``); import-probed codecs are static."""
        return True if self._available is None else bool(self._available())


def _raw_compress(data, level):
    return bytes(data)


def _raw_decompress(data, uncompressed_len):
    return bytes(data)


# The real codecs all accept buffer-protocol objects directly — no bytes()
# copy of multi-hundred-MB chunks on the hot path.


# The zstandard wheel, probed exactly once (False = probed-and-absent): a
# failed import is NOT cached by sys.modules, and re-walking sys.path per
# chunk on wheel-less hosts — precisely the hosts the native backend
# serves — would tax every encode/decode/resolve call.
_ZSTD_WHEEL: Any = None


def _zstd_backends():
    """(native, wheel) zstd backends usable RIGHT NOW, native-first order.
    Both produce/consume standard zstd frames, so they decode each other's
    output (the cross-decode matrix in the parity suite pins this); the
    native half is re-resolved per call because ``TPUSNAP_NATIVE=0`` can
    retire it mid-process (a cheap cached-instance check), the wheel half
    is import-probed once."""
    from .native_io import NativeFileIO

    native = NativeFileIO.maybe_create()
    if native is not None and not native.has_zstd:
        native = None
    global _ZSTD_WHEEL
    if _ZSTD_WHEEL is None:
        try:
            import zstandard  # type: ignore[import-not-found]

            _ZSTD_WHEEL = zstandard
        except ImportError:
            _ZSTD_WHEEL = False
    return native, (_ZSTD_WHEEL or None)


def _zstd_params() -> Tuple[int, bool]:
    """(window_log, enable_ldm) from the ``TPUSNAP_ZSTD_*`` knobs —
    (0, False) means plain level-only encoding (today's path)."""
    from . import knobs

    return knobs.get_zstd_window_log(), knobs.zstd_ldm_enabled()


def _zstd_encode_into(native, mv, out, level) -> Optional[int]:
    """Native zstd encode of ``mv`` into ``out``, honoring the advanced
    knobs (window log / long-distance matching) when set.  Ancient
    backends without the cctx API degrade to the plain encode with a
    one-time warning — frames are standard either way, only the match
    window shrinks."""
    from .native_io import NativeZstdError

    window_log, ldm = _zstd_params()
    if window_log or ldm:
        if native.has_zstd_params:
            try:
                return native.zstd_encode2_into(
                    mv, out, level, window_log, ldm
                )
            except NativeZstdError:
                # An ancient libzstd without the cctx API reports itself
                # here (rc -3); degrade to the plain encode below.
                pass
        if "zstd-params" not in _WARNED:
            _WARNED.add("zstd-params")
            logger.warning(
                "TPUSNAP_ZSTD_WINDOW_LOG/TPUSNAP_ZSTD_LDM requested but the "
                "zstd backend lacks the advanced API; encoding with the "
                "plain level-only path"
            )
    return native.zstd_encode_into(mv, out, level)


def _wheel_zstd_compressor(wheel, level):
    """A wheel compressor honoring the advanced knobs when set (and
    constructible); plain level compressor otherwise."""
    window_log, ldm = _zstd_params()
    if window_log or ldm:
        try:
            params = wheel.ZstdCompressionParameters.from_level(
                level,
                window_log=window_log or 0,
                enable_ldm=bool(ldm),
            )
            return wheel.ZstdCompressor(compression_params=params)
        except Exception:
            if "zstd-params-wheel" not in _WARNED:
                _WARNED.add("zstd-params-wheel")
                logger.warning(
                    "zstandard wheel rejected the advanced parameters "
                    "(window_log=%s ldm=%s); encoding level-only",
                    window_log,
                    ldm,
                )
    return wheel.ZstdCompressor(level=level)


def _make_zstd() -> Optional[_Codec]:
    native, wheel = _zstd_backends()
    if native is None and wheel is None:
        return None

    def _compress(data, level):
        native, wheel = _zstd_backends()
        mv = memoryview(data)
        if native is not None and mv.nbytes:
            from .native_io import NativeZstdError

            # One-shot encode into a bound-sized buffer (srcSize + srcSize/256
            # + 1 KiB always covers ZSTD_compressBound); the frame hot path
            # for large payloads encodes straight into the frame instead
            # (_native_codec_frame) and never reaches here.
            out = bytearray(mv.nbytes + (mv.nbytes >> 8) + 1024)
            try:
                n = _zstd_encode_into(native, mv, memoryview(out), level)
            except NativeZstdError:
                n = None
                native = None  # real failure: fall through to the wheel
            if native is not None and n is not None:
                del out[n:]
                return out
        if wheel is not None:
            return _wheel_zstd_compressor(wheel, level).compress(data)
        raise RuntimeError("no zstd backend available (native or wheel)")

    def _decompress(data, uncompressed_len):
        native, wheel = _zstd_backends()
        if native is not None:
            import numpy as np

            from .native_io import NativeZstdError

            # np.empty, not bytearray: same GIL-held-memset avoidance as
            # the encode path (_native_codec_frame) — the decoder
            # overwrites every byte it reports.
            out = np.empty(uncompressed_len, dtype=np.uint8)
            try:
                n = native.zstd_decode_into(data, memoryview(out))
            except NativeZstdError:
                if wheel is None:
                    raise  # decode() wraps this into FrameError
            else:
                return memoryview(out)[:n]
        if wheel is not None:
            return wheel.ZstdDecompressor().decompress(
                data, max_output_size=uncompressed_len
            )
        raise FrameError(
            "zstd frame cannot be decoded: no backend available "
            "(native library disabled/missing and no zstandard wheel)"
        )

    # Level 1, same rationale as zlib below: the checkpoint hot path wants
    # throughput.  Measured on bf16 random-normal checkpoint bytes (the
    # 2-byte-period data the match finder chokes on at higher levels):
    # level 1 compresses at 0.66 GB/s/thread vs level 3's 0.13 for a ratio
    # of 1.44 vs 1.59 — 5x the speed for 10% of the ratio.  Ratio-hungry
    # operators pass zstd:3 (or higher) explicitly.
    return _Codec(
        "zstd",
        1,
        _compress,
        _decompress,
        default_level=1,
        available=lambda: any(b is not None for b in _zstd_backends()),
    )


def _make_lz4() -> Optional[_Codec]:
    try:
        import lz4.frame  # type: ignore[import-not-found]
    except ImportError:
        return None

    def _compress(data, level):
        return lz4.frame.compress(data, compression_level=level)

    def _decompress(data, uncompressed_len):
        return lz4.frame.decompress(data)

    return _Codec("lz4", 2, _compress, _decompress, default_level=0)


def _make_zlib() -> _Codec:
    import zlib

    def _compress(data, level):
        return zlib.compress(data, level)

    def _decompress(data, uncompressed_len):
        return zlib.decompress(data)

    # Level 1: the checkpoint hot path wants throughput; ratio-hungry
    # operators pass zlib:6 explicitly.
    return _Codec("zlib", 3, _compress, _decompress, default_level=1)


RAW = _Codec("raw", 0, _raw_compress, _raw_decompress)

_FACTORIES: Dict[str, Callable[[], Optional[_Codec]]] = {
    "zstd": _make_zstd,
    "lz4": _make_lz4,
    "zlib": lambda: _make_zlib(),
}

_CODECS: Dict[str, Optional[_Codec]] = {"raw": RAW}
_BY_ID: Dict[int, _Codec] = {0: RAW}
_WARNED: set = set()


# Codecs whose availability can CHANGE within a process and must be
# re-probed when a prior probe found nothing: zstd's native backend
# appears the moment libtpusnap loads and retires under TPUSNAP_NATIVE=0
# (its factory is cheap — both backend probes are cached).  Import-only
# codecs keep the probed-and-absent result cached: a failed import is not
# cached by sys.modules, and re-walking sys.path per payload on a host
# without the wheel would tax every plan-time resolve().
_REPROBE = frozenset({"zstd"})


def get_codec(name: str) -> Optional[_Codec]:
    """The codec named ``name``, or None when no backend is currently
    available (unknown names raise — a typo must not silently disable
    compression)."""
    if name == "raw":
        return RAW
    factory = _FACTORIES.get(name)
    if factory is None:
        raise ValueError(
            f"Unknown compression codec {name!r} "
            f"(known: raw, {', '.join(sorted(_FACTORIES))})"
        )
    if name in _CODECS:
        codec = _CODECS[name]
        if codec is not None or name not in _REPROBE:
            return codec
    codec = factory()
    _CODECS[name] = codec
    if codec is not None:
        _BY_ID[codec.codec_id] = codec
    return codec


def resolve(name: str) -> str:
    """Resolve a configured codec name to what this host can run: the name
    itself, or ``raw`` (with a one-time warning) when the optional import
    is missing."""
    if name == "raw":
        return "raw"
    codec = get_codec(name)
    if codec is not None and codec.is_available():
        return name
    if name not in _WARNED:
        _WARNED.add(name)
        logger.warning(
            "Compression codec %r requested but its library is not "
            "installed; storing chunks raw",
            name,
        )
    return "raw"


def available_codecs() -> Tuple[str, ...]:
    """Codec names usable on this host RIGHT NOW, preference order (best
    first)."""
    out = []
    for name in ("zstd", "lz4", "zlib"):
        codec = get_codec(name)
        if codec is not None and codec.is_available():
            out.append(name)
    return tuple(out)


# Below this the native encode-into-frame saves less than its setup costs.
_NATIVE_ENCODE_MIN_BYTES = 1 << 20


def _native_codec_frame(mv, usize: int, codec: _Codec, level: Optional[int]):
    """Native encode straight into the frame's payload region (the codec
    encode offload): one allocation, zero copies of the compressed bytes.
    Returns the finished frame, ``None`` when the payload is incompressible
    (caller stores raw — same decision Python's ``len(candidate) < usize``
    makes, via the codec's didn't-fit signal at cap usize-1), or ``False``
    when the native backend is unavailable/failed (caller runs the Python
    codec; zlib output is byte-identical, zstd output is a standard frame
    either backend decodes, so the fallback is invisible to readers)."""
    from . import phase_stats
    from .native_io import NativeFileIO, NativeZlibError, NativeZstdError

    native = NativeFileIO.maybe_create()
    if native is None:
        return False
    if codec.name == "zlib":
        if not native.has_zlib:
            return False
        encode_into = native.zlib_encode_into
    elif codec.name == "zstd":
        if not native.has_zstd:
            return False

        # Routed through the advanced-parameter shim so the window-log /
        # LDM knobs apply to the large-payload frame path too.
        def encode_into(src, dst, level):
            return _zstd_encode_into(native, src, dst, level)

    else:
        return False
    import numpy as np

    # np.empty, not bytearray: a bytearray zero-fills its buffer under the
    # GIL — ~22 ms per 32 MB chunk on a busy host, which measured as the
    # difference between 0.43 and 0.72 GB/s per encode thread.  The
    # returned memoryview keeps the array alive and is buffer-compatible
    # with every downstream consumer (stager, hashers, writers).
    arr = np.empty(HEADER_BYTES + usize - 1, dtype=np.uint8)
    frame = memoryview(arr)
    eff_level = level if level is not None else codec.default_level
    try:
        with phase_stats.timed("compress", usize):
            elen = encode_into(mv, frame[HEADER_BYTES:], eff_level)
    except (NativeZlibError, NativeZstdError):
        return False  # real failure: the Python codec runs instead
    if elen is None:
        return None  # would not shrink: store raw-in-frame
    _HEADER.pack_into(arr, 0, MAGIC, codec.codec_id, 0, 0, usize)
    flen = HEADER_BYTES + elen
    if flen < usize // 2:
        # A memoryview slice pins the WHOLE uncompressed-bound allocation
        # until the write completes, while the scheduler re-credits its
        # memory budget down to the slice's nbytes (on_staged) — at high
        # ratios that silently overcommits the per-rank budget.  Copy out
        # when the allocation is more than 2x the frame (zero-heavy
        # optimizer states, sparse tensors: exactly where pinning hurts
        # most and the copy costs least); at typical checkpoint ratios
        # (~1.4x) the view stays zero-copy and the overcommit is bounded
        # by 2x the credited bytes.  The GIL-held copy of the WHOLE frame
        # at modest ratios measured ~2x on the compressed-save wall, which
        # is why this is ratio-gated rather than unconditional.
        return bytearray(frame[:flen])
    return frame[:flen]


def encode(buf, codec_name: str, level: Optional[int] = None) -> Tuple[Any, str]:
    """Frame ``buf``'s bytes with ``codec_name``; returns ``(frame,
    inner_codec_name)`` — the frame is a writable buffer (bytearray, or a
    memoryview from the native encode path), consumed through the buffer
    protocol by stagers/hashers/writers.

    Falls back to raw-inside-frame when compression does not pay (output
    would not be smaller than the input) or the codec fails — the frame
    header records what actually happened, so readers never consult the
    plan.  Runs one pass over the payload; callers put it on the
    scheduler's worker pool (the underlying C codecs release the GIL).
    Large zlib/zstd payloads encode natively straight into the frame
    (libtpusnap) — zlib byte-identical to Python's, zstd a standard frame
    either backend decodes — with one fewer full copy of the compressed
    bytes.
    """
    from . import phase_stats

    from . import preemption

    mv = memoryview(buf).cast("B")
    usize = mv.nbytes
    # Emergency-flush deadline mode (preemption.py): frame raw regardless
    # of the configured codec — the grace window buys durability, not
    # ratio, and the self-describing frame header means readers never
    # consult the plan-time codec choice.
    codec = None if preemption.deadline_active() else get_codec(codec_name)
    payload = mv  # raw fallback: the input itself, copied once into the frame
    inner = RAW
    if codec is not None and codec.codec_id != 0:
        tried_native = False
        if codec.name in ("zlib", "zstd") and usize >= _NATIVE_ENCODE_MIN_BYTES:
            native_frame = _native_codec_frame(mv, usize, codec, level)
            if native_frame is not False:
                tried_native = True
                if native_frame is not None:
                    return native_frame, codec.name
                # incompressible: fall through to the raw frame below
        if not tried_native:
            try:
                with phase_stats.timed("compress", usize):
                    candidate = codec.compress(mv, level)
                if len(candidate) < usize:
                    payload = candidate
                    inner = codec
            except Exception:
                logger.warning(
                    "Compression with %r failed; storing chunk raw", codec_name,
                    exc_info=True,
                )
    # One pre-sized allocation, one copy of the payload — no intermediate
    # bytes(mv) and no header+payload concat copy.
    frame = bytearray(HEADER_BYTES + len(payload))
    _HEADER.pack_into(frame, 0, MAGIC, inner.codec_id, 0, 0, usize)
    frame[HEADER_BYTES:] = payload
    return frame, inner.name


def decode(buf, expected_nbytes: Optional[int] = None, location: str = "") -> memoryview:
    """Decode one frame back to its uncompressed payload bytes.

    Raises :class:`FrameError` on a truncated or corrupted frame, an
    unknown codec id, a codec whose library is missing, or (when
    ``expected_nbytes`` is given) a payload whose recorded uncompressed
    length disagrees with what the manifest implies — every failure mode a
    torn write or bit rot can produce surfaces as one clean error type.
    """
    from . import phase_stats

    mv = memoryview(buf).cast("B")
    where = f" for {location}" if location else ""
    if mv.nbytes < HEADER_BYTES:
        raise FrameError(
            f"Truncated compression frame{where}: {mv.nbytes} bytes < "
            f"{HEADER_BYTES}-byte header"
        )
    magic, codec_id, flags, _reserved, usize = _HEADER.unpack(mv[:HEADER_BYTES])
    if magic != MAGIC:
        raise FrameError(
            f"Bad compression frame magic{where}: {bytes(magic)!r} != {MAGIC!r}"
        )
    if expected_nbytes is not None and usize != expected_nbytes:
        raise FrameError(
            f"Compression frame{where} records {usize} uncompressed bytes; "
            f"manifest implies {expected_nbytes}"
        )
    codec = _BY_ID.get(codec_id)
    if codec is None:
        # Lazily probe optional codecs: a snapshot written by a host WITH
        # zstd must decode here if this host has it too, even if nothing
        # registered it yet.
        for name in _FACTORIES:
            get_codec(name)
        codec = _BY_ID.get(codec_id)
    if codec is None:
        raise FrameError(
            f"Compression frame{where} uses codec id {codec_id}, which is "
            "unknown or whose library is not installed on this host"
        )
    body = mv[HEADER_BYTES:]
    if codec.codec_id == 0:
        if body.nbytes != usize:
            raise FrameError(
                f"Truncated raw frame{where}: {body.nbytes} payload bytes, "
                f"header records {usize}"
            )
        return body
    try:
        with phase_stats.timed("decompress", usize):
            out = codec.decompress(body, usize)
    except FrameError:
        raise
    except Exception as e:
        raise FrameError(
            f"Corrupt {codec.name} frame{where}: {type(e).__name__}: {e}"
        ) from e
    if len(out) != usize:
        raise FrameError(
            f"Corrupt {codec.name} frame{where}: decompressed to {len(out)} "
            f"bytes, header records {usize}"
        )
    return memoryview(out)


def is_framed(entry) -> bool:
    """Whether a manifest entry's payload is frame-encoded (its ``codec``
    field is set — including ``"raw"``, the incompressible fallback).
    ``None``/absent means legacy bare bytes: the pre-compression on-disk
    format, restored byte-for-byte without this module."""
    return getattr(entry, "codec", None) is not None
