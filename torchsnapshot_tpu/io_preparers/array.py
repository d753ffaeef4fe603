"""Per-array write/read planning: the core preparer.

TPU-native analogue of the reference's ``torchsnapshot/io_preparers/tensor.py``
(/root/reference/torchsnapshot/io_preparers/tensor.py:49-409).  Differences by
design:

- Staging is the pjrt transfer engine (``copy_to_host_async`` + ``asarray``),
  enqueued at scheduler admission so the memory budget holds (see staging.py),
  instead of CUDA-stream copies on a thread pool (reference tensor.py:249-264).
- Restore targets are immutable ``jax.Array``s, so "in-place" restore is
  host-side: bytes land in a host assembly buffer (the restore working set the
  budget controls), then one ``device_put`` with the target's sharding per
  array.  Plain numpy targets are written truly in place (zero extra copy),
  matching the reference's in-place goal (tensor.py:191-205).
- Tiled reads (byte-ranged pieces under a buffer budget) port unchanged —
  they are storage-side math (reference tensor.py:129-181).
"""

from __future__ import annotations

import asyncio
import bisect
import functools
import logging
import math
import mmap
import threading
import time
import weakref
from collections import deque
from concurrent.futures import Executor
from queue import SimpleQueue
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import knobs, serialization, staging
from ..compression import is_framed
from ..io_types import BufferConsumer, BufferStager, BufferType, Future, ReadReq, WriteReq
from ..manifest import TensorEntry
from ..serialization import Serializer

logger = logging.getLogger(__name__)


_INTO_PLACE_MIN_BYTES = 1 << 20


def _plan_codec(nbytes: int) -> Optional[str]:
    """The codec this payload will be framed with, decided at PLAN time
    (``TPUSNAP_COMPRESSION``), or None for legacy bare bytes.

    Plan time matters: the batcher needs to know a payload's stored size
    to pre-assign slab offsets, so codec-tagged entries are excluded from
    slab batching — the decision must exist before batch_write_requests
    runs.  Payloads under the size floor stay raw (and batchable); a
    configured codec whose library is missing resolves to raw here, so
    the whole save degrades to the legacy format, not to framed-raw
    overhead."""
    codec, _ = knobs.get_compression()
    if codec == "raw" or nbytes < knobs.get_compression_min_bytes():
        return None
    from .. import compression

    resolved = compression.resolve(codec)
    return None if resolved == "raw" else resolved


class ArrayIOPreparer:
    @staticmethod
    def _choose_serializer(dtype: Any) -> Serializer:
        if serialization.supports_buffer_protocol(dtype):
            return Serializer.BUFFER_PROTOCOL
        return Serializer.PICKLE

    @classmethod
    def prepare_write(
        cls,
        storage_path: str,
        obj: Any,
        is_async_snapshot: bool = False,
    ) -> Tuple[TensorEntry, List[WriteReq]]:
        # Prefer the dtype attribute: np.asarray would materialize lazy
        # handles (chunked _LazyHostSlice) with a full transfer at PLAN time.
        if staging.is_jax_array(obj) or hasattr(obj, "dtype"):
            arr_dtype = np.dtype(obj.dtype)
        else:
            arr_dtype = np.asarray(obj).dtype
        serializer = cls._choose_serializer(arr_dtype)
        shape = list(np.shape(obj))
        entry = TensorEntry(
            location=storage_path,
            serializer=serializer.value,
            dtype=serialization.dtype_to_string(arr_dtype)
            if serializer is Serializer.BUFFER_PROTOCOL
            else str(arr_dtype),
            shape=shape,
            replicated=False,
        )
        if serializer is Serializer.BUFFER_PROTOCOL:
            # Compression applies only to raw-bytes payloads whose size is
            # knowable here (dtype×shape); the stager frames at stage time
            # and may downgrade entry.codec to "raw" (framed, uncompressed)
            # if the payload turns out incompressible.
            entry.codec = _plan_codec(
                serialization.array_nbytes(shape, entry.dtype)
            )
        write_reqs = [
            WriteReq(
                path=storage_path,
                buffer_stager=ArrayBufferStager(
                    obj=obj,
                    entry=entry,
                    is_async_snapshot=is_async_snapshot,
                ),
            )
        ]
        return entry, write_reqs

    @staticmethod
    def can_load_inplace(entry: TensorEntry, obj: Any) -> bool:
        """In-place restore requires a mutable host array of identical
        dtype/shape (reference tensor.py:191-205)."""
        if not isinstance(obj, np.ndarray) or not obj.flags.writeable:
            return False
        if not obj.flags.c_contiguous:
            return False
        if list(obj.shape) != list(entry.shape):
            return False
        try:
            return obj.dtype == serialization.string_to_dtype(entry.dtype)
        except ValueError:
            return False

    @staticmethod
    def empty_array_from_entry(entry: TensorEntry) -> np.ndarray:
        return np.empty(entry.shape, dtype=serialization.string_to_dtype(entry.dtype))

    @classmethod
    def prepare_read(
        cls,
        entry: TensorEntry,
        obj_out: Optional[Any] = None,
        buffer_size_limit_bytes: Optional[int] = None,
        h2d_batch: Optional["H2DBatcher"] = None,
    ) -> Tuple[List[ReadReq], Future]:
        """Plan reads for one array entry.

        ``obj_out`` semantics: numpy array → in-place when possible;
        jax.Array → restored to the device(s) with the same sharding;
        None → a fresh host array.  ``h2d_batch``: collect this array's
        device upload into a cross-array batch (owner must flush).
        """
        if entry.serializer == Serializer.PICKLE.value:
            fut: Future = Future()
            return (
                [
                    ReadReq(
                        path=entry.location,
                        byte_range=entry.byte_range,
                        buffer_consumer=_PickleArrayConsumer(entry=entry, fut=fut, obj_out=obj_out),
                    )
                ],
                fut,
            )

        assembly = ArrayAssembly(entry=entry, obj_out=obj_out, h2d_batch=h2d_batch)
        total_bytes = serialization.array_nbytes(entry.shape, entry.dtype)

        # Read-into-place: hand storage the assembly's own memory so fs
        # preads land the bytes directly (no allocation, no consume memcpy).
        # The plan only learns that it will: the memory is taken when the
        # read is dispatched (IntoPlace).
        _into_view = assembly.into_view

        if is_framed(entry):
            # Framed payloads: byte offsets inside the compressed stream
            # are meaningless, so neither tiled reads nor read-into-place
            # apply — one whole-frame read, decompressed by the consumer.
            read_reqs = [
                ReadReq(
                    path=entry.location,
                    byte_range=entry.byte_range,
                    buffer_consumer=ArrayBufferConsumer(
                        assembly=assembly,
                        flat_offset=0,
                        nbytes=total_bytes,
                        checksum=entry.checksum,
                        location=entry.location,
                        codec=entry.codec,
                        frame_nbytes=entry.compressed_nbytes,
                    ),
                )
            ]
            assembly.expect(1)
            return read_reqs, assembly.fut

        if (
            buffer_size_limit_bytes is None
            or buffer_size_limit_bytes <= 0
            or total_bytes <= buffer_size_limit_bytes
        ):
            into = _into_view(0, total_bytes)
            read_reqs = [
                ReadReq(
                    path=entry.location,
                    byte_range=entry.byte_range,
                    buffer_consumer=ArrayBufferConsumer(
                        assembly=assembly,
                        flat_offset=0,
                        nbytes=total_bytes,
                        checksum=entry.checksum,
                        location=entry.location,
                        into=into,
                    ),
                    into=into,
                )
            ]
            assembly.expect(1)
            return read_reqs, assembly.fut

        # Tiled read: split into byte-ranged pieces each under the limit
        # (reference prepare_read_tiled, tensor.py:129-181).
        base = entry.byte_range[0] if entry.byte_range else 0
        n_tiles = math.ceil(total_bytes / buffer_size_limit_bytes)
        tile = math.ceil(total_bytes / n_tiles)
        read_reqs = []
        offset = 0
        while offset < total_bytes:
            length = min(tile, total_bytes - offset)
            tile_into = _into_view(offset, length)
            read_reqs.append(
                ReadReq(
                    path=entry.location,
                    byte_range=[base + offset, base + offset + length],
                    buffer_consumer=ArrayBufferConsumer(
                        assembly=assembly,
                        flat_offset=offset,
                        nbytes=length,
                        into=tile_into,
                    ),
                    # Merging the tiles back together would defeat the
                    # caller's buffer budget (they all target one location).
                    no_merge=True,
                    into=tile_into,
                )
            )
            offset += length
        assembly.expect(len(read_reqs))
        return read_reqs, assembly.fut


class ArrayBufferStager(BufferStager):
    def __init__(self, obj: Any, entry: TensorEntry, is_async_snapshot: bool) -> None:
        self._obj = obj
        self._entry = entry
        self._is_async_snapshot = is_async_snapshot
        # Deferred-digest contract with the scheduler: instead of hashing
        # the staged bytes here (a separate memory pass), stage_buffer
        # registers one sink per buffer part; the scheduler resolves them
        # at write time — fused into the native write+hash call where the
        # storage supports it, or via one pre-write hash pass otherwise.
        # The digest policy is size-only, so both routes produce identical
        # manifests.
        self.hash_sinks: Optional[list] = None

    def _defer_checksum(self) -> None:
        from .. import integrity

        if integrity.save_checksums_enabled():
            entry = self._entry

            def _set(digest_str) -> None:
                entry.checksum = digest_str

            self.hash_sinks = [_set]

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        from .. import phase_stats

        obj = self._obj
        if self._entry.serializer == Serializer.PICKLE.value:
            host = staging.to_host(obj)
            with phase_stats.timed("serialize", getattr(host, "nbytes", 0)):
                data = serialization.pickle_save_as_bytes(host)
            self._obj = None
            self._defer_checksum()
            return data
        from .chunked_array import _LazyDeviceSlice

        to_host = None
        if isinstance(obj, _LazyDeviceSlice):
            # A chunk of a device array: sliced, transferred and let go on
            # the worker, so no slice waits in HBM for its turn.
            to_host = obj.to_host
        elif staging.is_jax_array(obj):
            # Enqueue the async DMA now (we are being admitted by the
            # scheduler), materialize in the executor so concurrent stagers'
            # transfers overlap.
            staging.enqueue_d2h(obj)
            to_host = functools.partial(staging.to_host, obj)
        if to_host is not None:
            if executor is not None:
                host = await asyncio.get_running_loop().run_in_executor(
                    executor, to_host
                )
            else:
                host = to_host()
        else:
            host = np.asarray(obj)
            if self._is_async_snapshot:
                # Defensive copy: the caller may mutate host arrays after
                # async_take returns (reference tensor.py:283-293).
                host = host.copy()
        self._obj = None  # drop the device reference promptly
        mv = serialization.array_as_memoryview(host)
        if is_framed(self._entry):
            # Frame (compress) on the scheduler's worker pool so the codec
            # pass overlaps other stagers' D2H and in-flight storage I/O.
            # The checksum covers the FRAME — exactly the bytes on disk —
            # so verify/audit and read-fused hashing need no decompression.
            uncompressed_nbytes = mv.nbytes
            frame, inner = await serialization.compress_staged(
                mv, self._entry.codec, self._level(), executor
            )
            del mv, host  # the uncompressed copy is no longer needed
            self._entry.codec = inner
            self._entry.compressed_nbytes = len(frame)
            from ..telemetry import metrics as tmetrics

            tmetrics.record_codec(inner, uncompressed_nbytes, len(frame))
            # The deferred digest covers the FRAME — exactly the bytes the
            # scheduler hands storage.
            self._defer_checksum()
            return frame
        self._defer_checksum()
        return mv

    @staticmethod
    def _level():
        return knobs.get_compression()[1]

    def get_staging_cost_bytes(self) -> int:
        nbytes = serialization.array_nbytes(
            self._entry.shape, self._entry.dtype
        ) if self._entry.serializer == Serializer.BUFFER_PROTOCOL.value else _approx_nbytes(self._obj)
        from .chunked_array import _LazySlice

        if (
            staging.is_jax_array(self._obj)
            or self._is_async_snapshot
            # Lazy slice handles materialize a host buffer at staging
            # time — real memory the budget must see.
            or isinstance(self._obj, _LazySlice)
        ):
            return nbytes
        if is_framed(self._entry):
            # Framing allocates the compressed copy; budget against
            # max(compressed, uncompressed) = the uncompressed bound (the
            # incompressible fallback stores raw-in-frame, so the stored
            # size never exceeds nbytes + the 16-byte header; the scheduler
            # re-credits down to the actual frame size once staged).  The
            # compress pass itself transiently holds input + output — up
            # to ~2x nbytes for an incompressible payload — which the
            # budget deliberately does not double-charge: the window is
            # one codec pass per in-flight stager, bounded by the worker
            # pool width, and double-charging would halve admission for
            # the common well-compressing case.
            return nbytes
        return 0  # zero-copy view of an existing host array


def _approx_nbytes(obj: Any) -> int:
    try:
        return int(np.asarray(obj).nbytes)
    except Exception:
        return 4096


# What an H2DBatcher is handed: the host array, the array to place it like,
# the future of the result, and the pool's buffer under the host array (None
# where it is nobody's to give back).
_Item = Tuple[np.ndarray, Any, Future, Optional[np.ndarray]]
# A landing array (None: the transfer was never made) and the pool's buffer
# it was made from.
_Lease = Tuple[Any, np.ndarray]

_PAGE = mmap.PAGESIZE

# The clock of every stamp of a range's turn through the arena: the one the
# phases use, so a turn's stamps and the phases' intervals are on one clock.
# A name of this module so that a test can put its own in.
_now = time.monotonic

# A range's turn through the arena, from ``HostBufferPool._fit`` to
# ``HostBufferPool.give``, is cut into these stages by the stamps below, in
# this order: stamp k ends stage k and begins stage k+1, the grant begins the
# first and the give ends the last, so the stages of a turn add up to it.
_STAGES = (
    "grant",  # fitted -> adopted by its assembly (a waiter: the loop's _grant)
    "slot",  # -> its first read handed to storage (io_slot_wait, range in hand)
    "read",  # -> its last read taken off by the loop thread (storage, hash, queue)
    "parked",  # -> its consume begins (0 unless parked behind the loader)
    "consume",  # -> submitted to the batcher (digest, chunk_assemble, loop turns)
    "gather",  # -> its batch taken by the dispatcher, window room had
    "dispatch",  # -> the batch's device_put has returned
    "land",  # -> given back (the batch's block_until_ready, the settle loop)
)
# The stamp that ends each stage but the last, which the give ends.
_STAMPS = (
    "adopted", "read_began", "read_back", "consume_began", "submitted", "sent", "put",
)  # fmt: skip


class _Turn:
    """One range's stamps.  Made under the pool's lock (``_fit``), written
    without it by whoever holds the range (an attribute write each: the
    assembly on the pipeline's thread, the batcher on the dispatcher), folded
    into the pool's totals under the lock again (``give``).  A stamp never
    made reads None and its stage 0."""

    __slots__ = ("size", "nbytes", "granted", *_STAMPS)

    def __init__(self, size: int, nbytes: int) -> None:
        self.size, self.nbytes, self.granted = size, nbytes, _now()
        for stamp in _STAMPS:
            setattr(self, stamp, None)


def _pages(nbytes: int) -> int:
    """``nbytes`` rounded up to whole pages."""
    return -(-nbytes // _PAGE) * _PAGE


def _fresh_host_buffer(nbytes: int) -> np.ndarray:
    # A function of its own so that a test can count the plain buffers made.
    return np.empty(nbytes, dtype=np.uint8)


def _arena_memory(nbytes: int) -> np.ndarray:
    """``nbytes`` of anonymous memory that begins at a page boundary, not yet
    touched (``_make_arena`` populates it): one allocation, one
    ``munmap`` when the last view of it dies.  A function of its own so that
    a test can choose where it begins (the CPU backend aliases a 64-byte-
    aligned range into the landed array)."""
    raw = np.empty(nbytes + _PAGE, dtype=np.uint8)
    begin = -raw.ctypes.data % _PAGE
    return raw[begin : begin + nbytes]


def _page_toucher() -> Optional[Any]:
    """What populates an arena: the native pool's ``touch_pages`` (every page
    of a buffer written once by the kernel, a ``readv`` of one byte from
    ``/dev/zero``, in parallel, on the threads the restore's reads run on and
    on the caller's, the GIL released; no thread is started), or
    None without the native library or with one that predates the symbol:
    the reads then fault the pages in as they land, one at a time.  A
    function of its own so that a test can see what is touched, and when."""
    from ..native_io import NativeFileIO

    native = NativeFileIO.maybe_create()
    if native is None or not native.has_touch_pages:
        return None
    return native.touch_pages


def _keeps_host_memory(target: Any) -> bool:
    """Whether a ``device_put`` like ``target`` may keep the host memory it
    is given as the array itself, as the CPU backend does with a 64-byte-
    aligned buffer: such a target never lands from the arena, or a restored
    array would pin it and the next tenant of its range would change it."""
    try:
        return any(device.platform == "cpu" for device in target.devices())
    except Exception:  # noqa: BLE001 -- not seen to be apart: no arena
        return True


class HostBufferPool:
    """The host read buffers of ONE ``Snapshot.restore`` call: one bounded
    arena, handed out as page-aligned ranges of any size.

    A leaf that is read into place and uploaded through an ``H2DBatcher``
    reserves its size at plan time (``reserve``; every stateful is planned
    before the first read), takes a range when the first of its reads is
    dispatched (``take``), and the batcher's lander gives the range back once
    the transfer has landed (``give``).  The arena is one allocation, made at
    the first ``take`` and **populated then and there**: every page of it is
    written once, in parallel, on the native pool that the restore's reads
    run on (idle at that moment: no read has been dispatched into it yet;
    ``_page_toucher``, the phase ``arena_populate``, no thread started),
    before a range of it is handed to a read.  So no read of the restore,
    the first stateful's included, faults a fresh page, and nothing is
    unmapped beside the reads (a ``munmap`` holds the GIL and takes the
    address space's lock for writing while the readers fault pages in under
    it).  What a first touch costs one reader against many threads at once,
    and against a read into a touched page, on the hosts measured, is in
    PERF.md sections 5 and 7.  A native library without the symbol, or none,
    populates nothing: a page then costs its first touch when the first leaf
    is read into it, and nothing after.

    **Its size** is a rule over what the plan reserved and the batchers'
    window, and no knob: ``max(the largest reserved leaf, the batchers'
    in-flight cap)``, in whole pages, and no more than the largest stateful's
    bytes.  The largest leaf, so that every leaf fits; the in-flight cap, so
    that the landings the batcher allows can all be under way while reads go
    on, and the arena, not the batcher's window, is what holds the pipeline
    back.  No more: reads into touched pages outrun the landings, so a
    second buffer of the largest leaf's size buys no overlap and costs its
    first touches (PERF.md section 5 holds what each rule tried on the chip
    read).

    **A take** is first-fit from the lowest address over a short list of free
    ranges, so that what was ever handed out is always a prefix of the arena
    (``high_water``; ``fresh`` counts what is handed out for the first time,
    populated or not); a give
    frees the range and coalesces it with its neighbours.  **A read that
    finds no room waits** (``take`` with the pipeline's loop: a future of
    that loop; the assembly's phase ``host_buffer_wait``), in the order of
    dispatch, for a landing of ANY stateful, its own included, and the wait
    makes every attached batcher send what it holds to the device (a flush
    on pressure: ``attach``, ``waiting``), or a stateful would wait on its
    own small leaves gathering under the batcher's threshold.  Whoever holds
    a range gets on without the waiter: a read under way holds its io slot
    (the scheduler takes the range first), a landing needs the lander alone,
    and a read of the next stateful parked behind the load holds a range only
    when every read of the stateful in front has one.  ``give`` hands the
    freed room to the waiters at the head of the queue itself, so no range
    is in two hands and none is taken out of turn.

    **No arena** (``take`` is a plain ``np.empty``, ``give`` drops it) where
    a target's backend may keep the host memory as the landed array
    (``_keeps_host_memory``: decided at ``reserve``, from the target's
    devices), where nothing could be used twice (one pooled leaf; everything
    reserved fits the arena), and from the moment a range is given back unfit
    (``recycle=False``: its transfer failed, or the landed array may be the
    range itself).  A take that cannot wait (a piece copied in on an executor
    thread) and finds no room is a plain buffer too.  A plain buffer is
    never populated.  Nothing outlives the restore: ``close()`` drops the
    arena.

    The pool also owns the restore's upload threads (``h2d_threads``:
    it outlives the statefuls, each of which has a batcher of its own):
    ``start_threads`` before the first read, ``close`` joins them.

    **A range's turn is stamped and accounted** (``turn_stats``; the counter
    ``arena_turn``).  The arena's size is fixed by the rule above, so a
    restore streams at ``bytes lent / turn``, and what can still shorten it
    is the turn.  Each range out has one record (``_Turn``, kept where the
    range's size was: ``_lent``): ``_fit`` makes it, whoever holds the range
    stamps it at each hand-over without the lock (the assembly finds it once,
    ``turn_of``, and the read pipeline stamps through ``IntoPlace``; the
    batcher finds it by the lease it is handed), and ``give``, which holds
    the lock already, folds it into totals by stage (``_STAGES``) in seconds
    and in byte-seconds (the leaf's bytes times the stage's length).  The
    stages of a turn add up to it, and ``turn_bs`` can be no more than the
    arena's size times ``lent_s`` (no byte is lent twice at once): their
    ratio is the arena's occupancy.  A range given back unfit, or never
    adopted by a leaf, is ``dropped`` and in no stage; a plain buffer has no
    turn.

    Thread-safe: ``reserve`` and ``attach`` run on the planning thread,
    ``take`` on the read pipeline's thread or its executor, ``give`` on the
    lander (on the dispatcher for a leaf that was never sent)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._group_bytes: List[int] = []  # reserved by each stateful, in pages
        self._largest = 0  # the largest reserved leaf, in pages
        self._batchers: List["weakref.ref[H2DBatcher]"] = []
        self._plain = False  # no arena (any more)
        self._arena: Optional[np.ndarray] = None
        self._base = self._size = 0
        self._free: List[List[int]] = []  # [offset, size], by offset
        self._lent: Dict[int, _Turn] = {}  # offset -> each range out, its turn
        # (loop, future, nbytes) of each read held for room, in dispatch order
        self._waiters: "deque[Tuple[Any, Any, int]]" = deque()
        self._plain_alive = 0  # bytes of plain buffers taken and not given back
        # The restore's dispatcher and lander: every attached batcher's.
        self.h2d_threads = H2DThreads()
        self._stats = dict.fromkeys(
            ("bytes", "fresh", "hits", "misses", "high_water", "populated"), 0
        )
        self._touched = 0  # the arena's prefix ever handed out
        # The turns that ended in a give of a landed range, by stage.
        self._turns: Dict[str, float] = dict.fromkeys(
            ("bytes", "ranges", "dropped", "lent_s"), 0
        )
        for stage in _STAGES:
            self._turns[stage + "_s"] = self._turns[stage + "_bs"] = 0.0
        self._first_grant: Optional[float] = None

    def begin_group(self) -> None:
        """The reservations that follow are one stateful's."""
        with self._lock:
            self._group_bytes.append(0)

    def reserve(self, nbytes: int, target: Any) -> None:
        """One ``take`` of ``nbytes`` is to come, for a leaf that will be
        placed like ``target``."""
        keeps = _keeps_host_memory(target)
        with self._lock:
            self._group_bytes[-1] += _pages(nbytes)
            self._largest = max(self._largest, _pages(nbytes))
            self._plain = self._plain or keeps

    def attach(self, batcher: "H2DBatcher") -> None:
        """``batcher`` uploads leaves of this pool: its in-flight cap goes
        into the arena's size, and a read that waits for room flushes it."""
        with self._lock:
            self._batchers.append(weakref.ref(batcher))

    def _live_batchers(self) -> List["H2DBatcher"]:
        return [b for b in (ref() for ref in self._batchers) if b is not None]

    def start_threads(self) -> None:
        """Every stateful is planned and no read has been issued: the
        dispatcher and the lander start now, where a batcher has a leaf to
        upload, and not under read load from the thread that issues reads (a
        thread's start costs its starter 0.2-0.5 s there: PERF.md section
        5)."""
        with self._lock:
            wanted = any(b.expects_uploads for b in self._live_batchers())
        if wanted:
            self.h2d_threads.start()

    def _make_arena(self) -> None:
        # Under the lock, at the first take: every stateful is planned.
        window = max(
            (_pages(b.inflight_cap_bytes) for b in self._live_batchers()), default=0
        )
        size = min(max(self._largest, window), max(self._group_bytes, default=0))
        if size >= sum(self._group_bytes):
            self._plain = True  # nothing would be used twice (one leaf, say)
            return
        self._arena = _arena_memory(size)
        self._base, self._size = self._arena.ctypes.data, size
        self._free = [[0, size]]
        # All of it, before a range of it is handed to a read: the native
        # pool is idle now.  The lock is held: a take that comes meanwhile
        # has nothing to take yet.
        touch = _page_toucher()
        if touch is not None:
            from .. import phase_stats

            with phase_stats.timed("arena_populate", size):
                touch(self._arena)
            self._stats["populated"] = size

    def _fit(self, nbytes: int) -> Optional[np.ndarray]:
        """Under the lock: the lowest free range that holds ``nbytes``, taken
        out of the free list and counted, or None."""
        size = _pages(nbytes)
        for i, (offset, room) in enumerate(self._free):
            if room >= size:
                break
        else:
            return None
        if room == size:
            del self._free[i]
        else:
            self._free[i] = [offset + size, room - size]
        self._lent[offset] = turn = _Turn(size, nbytes)
        if self._first_grant is None:
            self._first_grant = turn.granted
        fresh = min(nbytes, max(0, offset + size - self._touched))
        self._touched = max(self._touched, offset + size)
        self._count(nbytes, fresh)
        return self._arena[offset : offset + nbytes]

    def _count(self, nbytes: int, fresh: int) -> None:
        stats = self._stats
        stats["misses" if fresh else "hits"] += 1
        stats["fresh"] += fresh
        stats["bytes"] += nbytes - fresh
        stats["high_water"] = max(
            stats["high_water"], self._touched + self._plain_alive
        )

    def _plain_buffer(self, nbytes: int) -> np.ndarray:
        # Under the lock (an np.empty of this size touches nothing).
        self._plain_alive += nbytes
        self._count(nbytes, nbytes)
        return _fresh_host_buffer(nbytes)

    def take(self, nbytes: int, loop: Optional[Any] = None) -> Any:
        """A flat uint8 buffer of exactly ``nbytes``: a range of the arena,
        or a plain buffer where there is no arena (or, with no ``loop``, no
        room in it).  With a ``loop`` and no room, or with reads already
        waiting: a future of ``loop`` that a ``give`` resolves to the buffer,
        this read's turn having come; the batchers are flushed first."""
        with self._lock:
            if self._arena is None and not self._plain:
                self._make_arena()
            if self._plain or _pages(nbytes) > self._size:
                return self._plain_buffer(nbytes)
            if loop is None or not self._waiters:
                buf = self._fit(nbytes)
                if buf is not None:
                    return buf
                if loop is None:
                    return self._plain_buffer(nbytes)
            coming = loop.create_future()
            self._waiters.append((loop, coming, nbytes))
            batchers = self._live_batchers()
        for batcher in batchers:
            batcher.flush()
        return coming

    def waiting(self) -> bool:
        """Whether a read is held for room: what a batcher is handed then
        goes to the device at once."""
        with self._lock:
            return bool(self._waiters)

    def give(self, buf: np.ndarray, recycle: bool) -> None:
        """``buf``, taken here, is done with.  ``recycle`` only where its
        transfer has landed and the landed array is not the buffer itself:
        its range is free again, and goes to the reads at the head of the
        queue as far as it reaches.  Otherwise the range is never handed out
        again, and nor is any other: there is no arena from here on, and
        whoever waits gets a plain buffer."""
        granted: List[Tuple[Any, Any, np.ndarray]] = []
        with self._lock:
            offset = buf.ctypes.data - self._base
            turn = self._lent.pop(offset, None)
            if turn is None:
                self._plain_alive -= buf.nbytes  # a plain buffer: dropped
                return
            self._fold(turn, landed=recycle)
            if recycle:
                self._release(offset, turn.size)
            else:
                self._plain = True
            while self._waiters:
                loop, coming, nbytes = self._waiters[0]
                lease = (
                    self._plain_buffer(nbytes) if self._plain else self._fit(nbytes)
                )
                if lease is None:
                    break
                self._waiters.popleft()
                granted.append((loop, coming, lease))
        for loop, coming, lease in granted:
            try:
                loop.call_soon_threadsafe(self._grant, coming, lease)
            except RuntimeError:  # the pipeline was aborted, its loop closed
                self.give(lease, recycle=True)

    def turn_of(self, buf: np.ndarray) -> Optional[_Turn]:
        """The record of the range under ``buf`` while it is out, for its
        holder to stamp (None for a plain buffer).  Takes no lock: a lookup
        in a dict that only ``_fit`` and ``give`` change, and whoever holds
        a range is between the two."""
        return self._lent.get(buf.ctypes.data - self._base)

    def _fold(self, turn: _Turn, landed: bool) -> None:
        """Under the lock, in ``give``: a turn is over.  One that a leaf
        went through (adopted, landed) adds each stage's seconds and
        byte-seconds to the totals; any other is ``dropped``."""
        totals = self._turns
        if not landed or turn.adopted is None:
            totals["dropped"] += 1
            return
        given = _now()
        totals["bytes"] += turn.nbytes
        totals["ranges"] += 1
        totals["lent_s"] = given - self._first_grant  # (gives come in order)
        at = turn.granted
        stamps = [getattr(turn, stamp) for stamp in _STAMPS] + [given]
        for stage, stamp in zip(_STAGES, stamps):
            if stamp is not None and stamp > at:
                totals[stage + "_s"] += stamp - at
                totals[stage + "_bs"] += turn.nbytes * (stamp - at)
                at = stamp

    def _grant(self, coming: "asyncio.Future[np.ndarray]", lease: np.ndarray) -> None:
        if coming.done():  # cancelled with its read
            self.give(lease, recycle=True)
        else:
            coming.set_result(lease)

    def _release(self, offset: int, size: int) -> None:
        # Under the lock: the range is free, one with its free neighbours.
        free = self._free
        i = bisect.bisect_left(free, [offset, 0])
        if i < len(free) and offset + size == free[i][0]:
            size += free.pop(i)[1]
        if i and free[i - 1][0] + free[i - 1][1] == offset:
            free[i - 1][1] += size
        else:
            free.insert(i, [offset, size])

    def close(self) -> None:
        """The restore is over, nothing reads any more: the dispatcher and
        the lander run what they were handed and are joined, then the arena
        goes."""
        self.h2d_threads.close()
        with self._lock:
            arena, self._arena = self._arena, None
            self._free, self._plain = [], True
        del arena  # unmapped outside the lock

    def stats(self) -> Dict[str, int]:
        """``fresh`` bytes handed out from pages of the arena never handed
        out before, and every byte of a plain buffer (``misses``: the takes
        that touched any); ``bytes`` handed out from pages that were
        (``hits``: the takes that touched nothing new); the ``high_water``
        of the arena's bytes ever handed out, at most its size, plus the
        plain buffers alive; and the bytes of the arena ``populated`` before
        any were handed out (its size, or 0: no arena, or nothing to
        populate it with), which changes none of the others."""
        with self._lock:
            return dict(self._stats)

    def turn_stats(self) -> Dict[str, float]:
        """The turns through the arena that ended with a landed range given
        back: their ``bytes`` and count (``ranges``); ``<stage>_s`` the
        seconds each stage of ``_STAGES`` lasted, summed over the ranges, and
        ``<stage>_bs`` the same weighted by each range's bytes;
        ``turn_bs`` the sum of the eight, so the byte-seconds lent: at most
        ``arena`` (its size) times ``lent_s`` (the last such give less the
        first grant); ``dropped`` the ranges given back unfit or unused.
        Zeros where no arena was made."""
        with self._lock:
            out = dict(self._turns)
            out["arena"] = self._size
        out["turn_bs"] = sum(out[stage + "_bs"] for stage in _STAGES)
        return out


def _may_alias(out: Any, buf: np.ndarray) -> bool:
    """Whether the landed array ``out`` may be the host buffer ``buf``
    itself.  The CPU backend's ``device_put`` of a 64-byte-aligned host array
    copies nothing: the device array is the host memory, and writing the
    next leaf into it would change a restored array.  An accelerator's
    arrays live in memory of its own."""
    try:
        if all(device.platform != "cpu" for device in out.devices()):
            return False
        begin = buf.ctypes.data
        return any(
            begin <= shard.data.unsafe_buffer_pointer() < begin + buf.nbytes
            for shard in out.addressable_shards
        )
    except Exception:  # noqa: BLE001 -- not seen to be apart: not recycled
        return True


class _Worker:
    """A daemon thread that runs, in the order given, the jobs it is handed.
    Started by ``start`` or by the first ``hand``.  Once closed it starts no
    more: a job handed then runs on the thread that hands it, so that nothing
    handed is ever left undone."""

    def __init__(self, name: str) -> None:
        self._name = name
        self._lock = threading.Lock()
        self._jobs: "SimpleQueue[Optional[Callable[[], None]]]" = SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self._closed = False

    def _start(self) -> None:
        # Under the lock.
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name=self._name, daemon=True
            )
            self._thread.start()

    def start(self) -> None:
        with self._lock:
            if not self._closed:
                self._start()

    def hand(self, job: Callable[[], None]) -> None:
        with self._lock:
            if not self._closed:
                self._start()
                self._jobs.put(job)
                return
        job()

    def is_current(self) -> bool:
        return threading.current_thread() is self._thread

    def _run(self) -> None:
        for job in iter(self._jobs.get, None):
            try:
                job()
            except BaseException:  # noqa: BLE001 -- a job keeps its own errors
                logger.exception("a job of %s raised", self._name)

    def close(self) -> None:
        """Run what was handed, then end and join the thread (idempotent)."""
        with self._lock:
            self._closed = True
            thread, self._thread = self._thread, None
        if thread is not None:
            self._jobs.put(None)
            thread.join()


class H2DThreads:
    """The two threads that send one restore's leaves to the device: the
    **dispatcher** (``tpusnap-h2d-dispatcher``) runs each batch's
    ``device_put`` and the **lander** (``tpusnap-h2d-lander``) its
    ``block_until_ready``, so batch N+1 is copied while batch N lands and
    neither runs on the thread that issues the reads.  One pair serves every
    batcher of a restore (its ``HostBufferPool`` owns it, starts it before
    the first read is issued and joins it when the restore ends); a batcher
    without a pool has a pair of its own.

    One dispatcher, not several: beside sixteen threads that read and hash
    as a restore's io slots do, two threads' ``device_put`` calls land three
    to four times what one thread's do (``tools/h2d_ceiling_probe.py``,
    PERF.md section 7), but in a restore a batch split two ways shortened
    the dispatch's wall and not the call (PERF.md section 6, PR 34): the
    dispatcher is not what a restore waits for.  What does pay is that a
    busy dispatcher's backlog goes as one call (``H2DBatcher``): a call a
    leaf from this thread was slower than the calls it replaced.

    ``route()`` is the account of where the ``device_put`` calls ran (the
    counter ``h2d_dispatch_route``): ``bytes`` dispatched, of them
    ``off_caller`` on the dispatcher and ``on_caller`` on the thread that
    called ``flush`` or ``drain`` (only once the threads are closed: the
    fall-back that leaves nothing handed over undone), in ``batches``
    calls."""

    def __init__(self) -> None:
        self.dispatcher = _Worker("tpusnap-h2d-dispatcher")
        self.lander = _Worker("tpusnap-h2d-lander")
        self._lock = threading.Lock()
        self._route = dict.fromkeys(("bytes", "off_caller", "on_caller", "batches"), 0)

    def start(self) -> None:
        self.dispatcher.start()
        self.lander.start()

    def close(self) -> None:
        # The dispatcher first: what it still sends, the lander still lands.
        self.dispatcher.close()
        self.lander.close()

    def count_put(self, nbytes: int) -> None:
        """One ``device_put`` call of ``nbytes`` has returned on this thread
        (a call that raised sent nothing and is not counted)."""
        side = "off_caller" if self.dispatcher.is_current() else "on_caller"
        with self._lock:
            self._route["bytes"] += nbytes
            self._route[side] += nbytes
            self._route["batches"] += 1

    def route(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._route)


# A landing that stalls says so (the counter ``h2d_land_slow``): half a second
# and more at under 0.5 GB/s.  The slowest ceiling a healthy landing was
# measured at is 5.4 GB/s (one large bf16 leaf, tools/h2d_ceiling_probe.py,
# PERF.md section 7), and the largest single landing of any cell, 713 MB,
# takes 0.13 s at it: a tenth of that rate over half a second is no healthy
# landing of any size, and every stall seen (3-7 s, PERF.md section 5) is.
_SLOW_LANDING_S = 0.5
_SLOW_LANDING_BYTES_PER_S = 0.5e9


def _note_slow_landing(seconds: float, nbytes: int, leaves: int) -> None:
    from .. import phase_stats

    if seconds >= _SLOW_LANDING_S and nbytes < seconds * _SLOW_LANDING_BYTES_PER_S:
        phase_stats.add_counter("h2d_land_slow", seconds, nbytes)
        logger.info(
            "a slow H2D landing: %d bytes in %d leaves took %.3f s "
            "(%.3f GB/s) to be on the device",
            nbytes,
            leaves,
            seconds,
            nbytes / seconds / 1e9,
        )


class H2DBatcher:
    """Cross-array H2D upload batching + landing pacing for the restore path.

    Per-array ``device_put`` dispatches serialize each upload behind its
    array's read; collecting completed host buffers and uploading
    them in ONE batched pjrt transfer lets the backend overlap the streams
    and overlaps the batch with the remaining storage reads.  Buffers
    accumulate up to ``flush_bytes`` (bounding the extra host-memory
    residency beyond the scheduler's budget), then flush incrementally.

    **A flush is a hand-off.**  ``flush`` queues what has gathered for the
    dispatcher thread (``H2DThreads``) and returns: no ``device_put`` runs on
    the thread that finalised the leaf, which is the read pipeline's loop
    thread, the only one that issues reads and hands out io slots.  The
    dispatcher takes what is queued as one batch (no more than the in-flight
    cap admits, but for a single leaf that is larger), waits for window room
    (``h2d_window_wait``), reserves the batch's bytes, makes the ONE batched
    ``device_put`` (``h2d_dispatch``), sets each future and hands the batch
    to the lander; what is flushed while it is busy gathers into the batch it
    takes when it comes free.  A queued batch holds host memory (ranges of
    the pool's arena), never HBM.

    Dispatched batches land EAGERLY on the lander thread; a bounded
    unlanded-bytes window (default 2× ``flush_bytes``) backpressures new
    dispatches so batch N's landing overlaps the reads feeding batch N+1
    instead of every transfer piling up behind the caller's final
    ``block_until_ready`` (r04 bench: 159 s of unattributed restore wall —
    the reference's read scheduler overlaps read and consume end-to-end,
    /root/reference/torchsnapshot/scheduler.py:386-447).  Landings are
    attributed to the byte-carrying ``h2d_land`` phase; the dispatch to
    ``h2d_dispatch``.  The owner calls :meth:`drain` after the read pipeline
    finishes: on return every submitted array is ON DEVICE, not in flight,
    nothing of this batcher is queued or running on either thread, and a
    batcher's own threads have exited.

    With a ``host_pool`` (``Snapshot.restore``'s), a buffer submitted with
    its ``lease`` (the pool's range under ``host``) goes back to the pool
    when its transfer has landed (the lander's ``block_until_ready`` before
    the ``give`` is what keeps a range from its next tenant until its last
    one is on the device), unless the landed array may be the buffer itself
    (``_may_alias``); one whose transfer failed, or was never made, is given
    back unfit.  While a read waits for room in the pool
    (``HostBufferPool.waiting``) nothing gathers here: the pool flushes every
    batcher when the wait begins, and a leased buffer submitted during it is
    flushed at once, whatever ``flush_bytes`` says; a flush always leaves a
    dispatcher run scheduled, so no waiter waits on a batch that nobody will
    send.  Nothing here keeps a host buffer past its landing.

    **What it tells the record.**  A leased buffer's range has a record of
    its turn through the arena (``HostBufferPool.turn_of``): the dispatcher
    stamps it when it takes the batch with window room had (``sent``: until
    then the leaf gathered under ``flush_bytes``, queued behind a busy
    dispatcher, or waited for the window) and when the batch's
    ``device_put`` has returned (``put``); the lander's ``give`` ends the
    turn.  A leaf's own landing inside its batch is one number with its
    batch-mates': every range of a batch comes back when the whole batch has
    landed.  A landing that stalls (``_SLOW_LANDING_S`` and more, at under
    ``_SLOW_LANDING_BYTES_PER_S``) is counted, ``h2d_land_slow``, and logged
    at INFO with its bytes, leaves and seconds.

    **Errors.**  A dispatch or landing failure must not wedge the batcher:
    the first one is kept, the byte accounting stays exact, the batches
    behind it are settled (a bad item fails alone and its batch-mates still
    restore; after a failure nothing more is sent and every lease goes back
    unfit, so a read waiting for room gets on and meets the error), and the
    error is raised, sticky, by the next ``flush`` and by ``drain``.

    Thread-safety: ``submit``/``flush`` may run on the read pipeline's loop
    or executor threads and on the pool's behalf (a read's wait for room),
    ``drain`` on the caller thread; ``_send`` runs on the dispatcher and
    ``_land`` on the lander (on the flushing thread only once the threads
    are closed).  A back-pressure wait lasts only until the lander frees
    window room, and holds the dispatcher, never a thread that reads — and
    the window bounds unlanded host-buffer residency, which the scheduler's
    read budget stops tracking the moment a consume completes.
    """

    _DEFAULT_FLUSH_BYTES = 256 << 20

    def __init__(
        self,
        flush_bytes: int = _DEFAULT_FLUSH_BYTES,
        inflight_cap_bytes: Optional[int] = None,
        host_pool: Optional[HostBufferPool] = None,
    ) -> None:
        self.host_pool = host_pool
        self._items: List[_Item] = []
        self._bytes = 0
        self._flush_bytes = flush_bytes
        self.inflight_cap_bytes = (
            inflight_cap_bytes if inflight_cap_bytes is not None else 2 * flush_bytes
        )
        # The restore's two threads, or a pair of this batcher's own.
        self.threads = host_pool.h2d_threads if host_pool is not None else H2DThreads()
        # Whether a planned leaf will be submitted here: the pool starts its
        # threads before the first read only for a batcher that has work.
        self.expects_uploads = False
        if host_pool is not None:
            host_pool.attach(self)
        self._cond = threading.Condition()
        self._queued: List[_Item] = []  # flushed, not yet taken by the dispatcher
        self._dispatching = False  # a dispatcher run of this batcher is scheduled
        self._landing = 0  # batches handed to the lander and not yet settled
        self._unlanded_bytes = 0  # dispatched, not yet landed
        self._error: Optional[BaseException] = None

    def submit(
        self,
        host: np.ndarray,
        like: Any,
        fut: Future,
        lease: Optional[np.ndarray] = None,
    ) -> None:
        with self._cond:
            self._items.append((host, like, fut, lease))
            self._bytes += host.nbytes
            should_flush = self._bytes >= self._flush_bytes
        if should_flush or (lease is not None and self.host_pool.waiting()):
            self.flush()

    def flush(self) -> None:
        """Hand what has gathered to the dispatcher and return at once;
        raises the sticky error of an earlier dispatch or landing."""
        with self._cond:
            self._raise_error()
            if not self._items:
                return
            self._queued += self._items
            self._items, self._bytes = [], 0
            if self._dispatching:
                return  # its run takes them when it comes free
            self._dispatching = True
        self.threads.dispatcher.hand(self._dispatch_queued)

    def _dispatch_queued(self) -> None:
        """One dispatcher run: what is queued goes as one batch, as far as
        the in-flight cap reaches (a leaf larger than the cap goes alone),
        then what was queued meanwhile, until nothing is."""
        while True:
            with self._cond:
                n = nbytes = 0
                for host, *_ in self._queued:
                    if n and nbytes + host.nbytes > self.inflight_cap_bytes:
                        break
                    n, nbytes = n + 1, nbytes + host.nbytes
                items, self._queued = self._queued[:n], self._queued[n:]
                if not items:
                    self._dispatching = False
                    self._cond.notify_all()
                    return
            try:
                self._send(items)
            except BaseException as e:  # noqa: BLE001 -- raised by flush/drain
                with self._cond:
                    if self._error is None:
                        self._error = e

    def _send(self, items: List[_Item]) -> None:
        from .. import phase_stats

        batch_bytes = sum(host.nbytes for host, *_ in items)
        # Backpressure: wait for the lander to free window room, and RESERVE
        # this batch's bytes in the same critical section.  The wait lasts
        # only for the EXCESS over the window (landing of older batches
        # started the moment they were dispatched), and a full window
        # stalling the dispatcher is the point: what is read gathers behind
        # it in the arena, and the arena's bound then holds the reads, so
        # they cannot run unboundedly ahead of a slow H2D link.  The wait is
        # h2d_window_wait when it lasted.
        window_wait = None
        with self._cond:
            while (
                self._error is None
                and self._unlanded_bytes > 0
                and self._unlanded_bytes + batch_bytes > self.inflight_cap_bytes
            ):
                if window_wait is None:
                    window_wait = phase_stats.open_interval("h2d_window_wait")
                self._cond.wait(timeout=1.0)
            failed_before = self._error is not None
            if not failed_before:
                self._unlanded_bytes += batch_bytes  # reserved
        if window_wait is not None:
            window_wait.close(min_s=0.001)
        # The ranges of this batch, for their turn's stamps: gathered and
        # queued until here, in the device_put call until it returns.
        turns = self._turns_of(items)
        self._stamp(turns, "sent")
        if failed_before:
            # Nothing more goes to the device; the ranges go back unfit, so a
            # read that waits for one gets on and meets the error.
            self._settle_unsent(items)
            return
        try:
            outs, failed = self._dispatch(items)
        except BaseException:
            with self._cond:
                self._unlanded_bytes -= batch_bytes
                self._cond.notify_all()
            self._settle_unsent(items)
            raise
        self._stamp(turns, "put")
        landed_bytes = sum(
            host.nbytes for (host, *_), out in zip(items, outs) if out is not None
        )
        good = [out for out in outs if out is not None]
        leases: List[_Lease] = []
        for out, (_, _, fut, lease) in zip(outs, items):
            if out is not None:
                fut.obj = out
                if lease is not None:
                    leases.append((out, lease))
        with self._cond:
            # Release the reservation for items that did not dispatch (they
            # land synchronously in the per-item retry below, outside the
            # window).
            self._unlanded_bytes -= batch_bytes - landed_bytes
            if good:
                self._landing += 1
            self._cond.notify_all()
        if good:
            self.threads.lander.hand(
                functools.partial(self._land, good, landed_bytes, leases)
            )
        if failed:
            # A failed batch retries per item so one bad array (dtype/
            # sharding mismatch) fails alone with correct blame and its
            # batch-mates still restore.
            self._dispatch_per_item(failed)

    def _turns_of(self, items: List[_Item]) -> List[_Turn]:
        """The arena's records of the leased buffers among ``items``."""
        if self.host_pool is None:
            return []
        found = (
            self.host_pool.turn_of(lease) for *_, lease in items if lease is not None
        )
        return [turn for turn in found if turn is not None]

    @staticmethod
    def _stamp(turns: List[_Turn], stamp: str) -> None:
        at = _now()
        for turn in turns:
            setattr(turn, stamp, at)

    def drain(self) -> None:
        """Flush the tail and block until every transfer handed over has been
        dispatched and LANDS (attributed to ``h2d_land``).  After this,
        restored arrays are device-resident — the caller's own
        block_until_ready sees ~0 s.

        On a dispatch or landing failure the error still surfaces here, but
        only after the remaining batches have been settled: drain exits
        quiescent (nothing queued, byte accounting settled, no job of this
        batcher on either thread, its own threads joined) whether it raises
        or not, so callers never observe mid-landing counters or a running
        thread of the batcher's own after an error."""
        try:
            self.flush()
        finally:
            # The lander decrements unlanded bytes even for failed
            # landings, so this loop terminates regardless of errors.
            with self._cond:
                while self._dispatching or self._landing:
                    self._cond.wait(timeout=1.0)
            self.shutdown()
        self._raise_error()

    def shutdown(self) -> None:
        """End and join this batcher's own threads, once they have run what
        they were handed (idempotent; never raises the sticky error —
        callers check via drain).  A restore's threads are its pool's, and
        end with it (``HostBufferPool.close``)."""
        if self.host_pool is None:
            self.threads.close()

    def _raise_error(self) -> None:
        # Sticky: a batcher with a failed dispatch or landing keeps raising
        # (it is per-restore and discarded after; clearing would let a drain
        # following a flush-consumed error report clean).
        if self._error is not None:
            raise self._error

    def _land(self, outs: List[Any], nbytes: int, leases: List[_Lease]) -> None:
        """On the lander: one dispatched batch lands, its buffers go back to
        the pool, the window opens.  A landing failure keeps the accounting
        exact and is kept for the next flush/drain; the batches behind it
        still settle, so backpressure waiters and drain() always make
        progress."""
        import jax

        from .. import phase_stats

        err: Optional[BaseException] = None
        began = _now()
        try:
            with phase_stats.timed("h2d_land", nbytes):
                jax.block_until_ready(outs)
            _note_slow_landing(_now() - began, nbytes, len(outs))
        except BaseException as e:  # noqa: BLE001
            err = e
        # Before the window opens: whoever it lets through finds the
        # buffers this batch landed from.
        try:
            self._settle(leases, landed=err is None)
        except BaseException as e:  # noqa: BLE001
            err = err or e
        with self._cond:
            self._unlanded_bytes -= nbytes
            self._landing -= 1
            if err is not None and self._error is None:
                self._error = err
            self._cond.notify_all()

    def _settle(self, leases: List[_Lease], landed: bool) -> None:
        """Give the pool's buffers back: to be used again where the transfer
        has ``landed`` and the array it made is not the buffer itself."""
        for out, lease in leases:
            self.host_pool.give(
                lease, recycle=landed and out is not None and not _may_alias(out, lease)
            )

    def _settle_unsent(self, items: List[_Item]) -> None:
        self._settle(
            [(None, lease) for *_, lease in items if lease is not None], landed=False
        )

    def _dispatch(self, items: List[_Item]) -> Tuple[List[Any], List[_Item]]:
        """Dispatch the batch in ONE batched ``device_put``, each buffer onto
        its target's own sharding (device, layout and memory kind preserved
        exactly, as _device_put_like does per item); returns (outs, failed)
        where ``outs[i]`` is None for items that did not dispatch and
        ``failed`` lists exactly those items for the caller's per-item
        retry."""
        import jax

        from .. import phase_stats

        idx: List[int] = []
        bufs: List[np.ndarray] = []
        shardings: List[Any] = []
        failed: List[_Item] = []
        for i, (host, like, _, _) in enumerate(items):
            # Classification must never sink the batch: an item whose dtype
            # cast raises goes straight to the per-item retry (correct
            # blame), the rest dispatch normally.
            try:
                if host.dtype != np.dtype(like.dtype):
                    host = host.astype(np.dtype(like.dtype))
            except Exception:
                failed.append(items[i])
                continue
            idx.append(i)
            bufs.append(host)
            shardings.append(getattr(like, "sharding", None))
        outs: List[Any] = [None] * len(items)
        if not bufs:
            return outs, failed
        nbytes = sum(b.nbytes for b in bufs)
        # Manual phase accounting, recorded only for DISPATCHED bytes:
        # timed() commits in its finally, so a failed batch would charge its
        # bytes to h2d_dispatch and the per-item retry would charge again.
        dispatch = phase_stats.open_interval("h2d_dispatch")
        try:
            for i, out in zip(idx, jax.device_put(bufs, shardings)):
                outs[i] = out
            self.threads.count_put(nbytes)
        except Exception:
            dispatch.drop()
            # An HBM OOM looks like this: the per-item retry may well
            # succeed, so the first failure must not vanish with it.
            logger.warning(
                "batched device_put of %d arrays (%d bytes) failed; "
                "retrying them one by one",
                len(bufs),
                nbytes,
                exc_info=True,
            )
            outs = [None] * len(items)
            failed.extend(items[i] for i in idx)
        else:
            dispatch.close(nbytes)
        return outs, failed

    def _dispatch_per_item(self, items: List[_Item]) -> None:
        import jax

        from .. import phase_stats

        first_exc: Optional[BaseException] = None
        outs: List[Any] = []
        leases: List[_Lease] = []
        nbytes = 0
        for host, like, fut, lease in items:
            out = None
            try:
                fut.obj = out = _device_put_like(host, like)
                self.threads.count_put(host.nbytes)
                outs.append(out)
                nbytes += host.nbytes
            except Exception as e:
                if first_exc is None:
                    first_exc = e
            if lease is not None:
                leases.append((out, lease))
        # These transfers bypass the in-flight window (error path): land them
        # here so drain()'s "on device on return" contract still holds and
        # the landing wall stays attributed.
        landed = False
        try:
            if outs:
                began = _now()
                with phase_stats.timed("h2d_land", nbytes):
                    jax.block_until_ready(outs)
                _note_slow_landing(_now() - began, nbytes, len(outs))
            landed = True
        finally:
            self._settle(leases, landed)
        if first_exc is not None:
            raise first_exc


class ArrayAssembly:
    """Shared restore target for one logical array: a host buffer that one or
    more consumers fill, finalized into the caller's target exactly once.

    The buffer lives from the dispatch of the first read that lands in it
    (``host``: nothing is allocated at plan time) to ``finalize``, which
    hands it on and lets go.  A jax-array target of a megabyte and more that
    uploads through an ``H2DBatcher`` with a ``HostBufferPool`` takes a range
    of the pool's arena (``buffer_ready``, which waits for room; every read
    into the leaf shares the one range), and the batcher gives it back once
    landed."""

    def __init__(
        self,
        entry: TensorEntry,
        obj_out: Optional[Any],
        h2d_batch: Optional[H2DBatcher] = None,
    ) -> None:
        self.entry = entry
        self.obj_out = obj_out
        self.fut: Future = Future()
        self._pending = 0
        self._h2d_batch = h2d_batch
        self._inplace = ArrayIOPreparer.can_load_inplace(entry, obj_out)
        self._host: Optional[np.ndarray] = obj_out if self._inplace else None
        # Pieces are copied in on executor threads while the loop's thread
        # dispatches reads: one of them makes the buffer.
        self._host_lock = threading.Lock()
        self._lease: Optional[np.ndarray] = None  # the pool's buffer under _host
        self._turn: Optional[_Turn] = None  # the arena's record of that range
        self._pool: Optional[HostBufferPool] = None
        # the pool's promise of a range, while the first read waits for room
        self._coming: Optional["asyncio.Future[np.ndarray]"] = None
        self._nbytes = serialization.array_nbytes(entry.shape, entry.dtype)
        if (
            h2d_batch is not None
            and not self._inplace
            and staging.is_jax_array(obj_out)
        ):
            h2d_batch.expects_uploads = True  # finalize() will submit there
            if (
                h2d_batch.host_pool is not None
                and self._nbytes >= _INTO_PLACE_MIN_BYTES
            ):
                self._pool = h2d_batch.host_pool
                self._pool.reserve(self._nbytes, obj_out)

    @property
    def host(self) -> np.ndarray:
        with self._host_lock:
            if self._host is None:
                if self._pool is not None:
                    self._adopt(self._pool.take(self._nbytes))
                else:
                    self._host = ArrayIOPreparer.empty_array_from_entry(self.entry)
            return self._host

    def _adopt(self, lease: np.ndarray) -> None:
        # Under _host_lock.
        self._lease = lease
        self._turn = self._pool.turn_of(lease)
        self.stamp("adopted")
        self._host = lease.view(
            serialization.string_to_dtype(self.entry.dtype)
        ).reshape(self.entry.shape)

    async def buffer_ready(self) -> None:
        """Before a read into this assembly is dispatched, and before it
        takes its io slot: the leaf's range of the pool's arena is taken, by
        the first of its reads to come, and where there is no room that read
        waits for a landing to free some (``HostBufferPool.take``; the phase
        ``host_buffer_wait``), the leaf's other reads with it."""
        from .. import phase_stats

        if self._pool is None or self._host is not None:
            return
        waited = None
        if self._coming is None:
            taken = self._pool.take(self._nbytes, asyncio.get_running_loop())
            if isinstance(taken, np.ndarray):
                self._settle_on(taken)
                return
            self._coming = taken
            waited = phase_stats.open_interval("host_buffer_wait")
        try:
            lease = await self._coming
        finally:
            if waited is not None:
                waited.close()
        self._settle_on(lease)

    def _settle_on(self, lease: np.ndarray) -> None:
        """``lease`` is this leaf's, unless a piece copied in on an executor
        thread has made the buffer meanwhile: then it goes back unused."""
        with self._host_lock:
            if self._host is None:
                self._adopt(lease)
            elif self._lease is not lease:
                self._pool.give(lease, recycle=True)

    def stamp(self, stamp: str) -> None:
        """A hand-over of this leaf's range of the arena, for the record of
        its turn (``_STAGES``; nothing for a leaf with no range): of several
        reads into the leaf the first ``read_began`` stands, and the last of
        every other stamp."""
        turn = self._turn
        if turn is not None and (stamp != "read_began" or turn.read_began is None):
            setattr(turn, stamp, _now())

    def expect(self, n: int) -> None:
        self._pending = n
        if n == 0:  # degenerate zero-size array
            self.finalize()

    def flat_u8(self) -> np.ndarray:
        host = self.host
        arr = host if host.ndim > 0 else host.reshape(1)
        return arr.view(np.uint8).reshape(-1)

    def into_view(self, offset: int, nbytes: int) -> Optional["IntoPlace"]:
        """Read-into-place of ``[offset, offset+nbytes)`` of this assembly,
        or None when not worth it (below the size threshold — small reads
        should keep merging in the batcher).  The single policy point for
        the dense and chunked read paths."""
        if nbytes < _INTO_PLACE_MIN_BYTES:
            return None
        return IntoPlace(self, offset, nbytes)

    def piece_done(self) -> None:
        self._pending -= 1
        if self._pending == 0:
            self.finalize()

    def finalize(self) -> None:
        out = self.host
        self.stamp("submitted")
        lease, self._host, self._lease, self._turn = self._lease, None, None, None
        target = self.obj_out
        if self._inplace:
            self.fut.obj = target
            return
        if target is None:
            self.fut.obj = out
            return
        if staging.is_jax_array(target):
            if self._h2d_batch is not None:
                self._h2d_batch.submit(out, target, self.fut, lease)
            else:
                self.fut.obj = _device_put_like(out, target)
            return
        if isinstance(target, np.ndarray) and target.flags.writeable and list(
            target.shape
        ) == list(out.shape):
            # dtype-converting in-place copy (reference tensor_copy
            # dequant-on-mismatch, tensor.py:385-409)
            np.copyto(target, out.astype(target.dtype, copy=False))
            self.fut.obj = target
            return
        self.fut.obj = out


class IntoPlace:
    """Where one read lands in its assembly's buffer (``ReadReq.into``).  The
    plan knows that much; the memory is there from ``acquire()``, which the
    read pipeline awaits when it dispatches the read, before the read's io
    slot, and the consumer lets go of it (``release``) once it has seen the
    read arrive.  It is also the pipeline's handle on the leaf's range of
    the restore's arena, for the stamps of the range's turn (``stamp``)."""

    def __init__(self, assembly: ArrayAssembly, offset: int, nbytes: int) -> None:
        self._assembly = assembly
        self._offset = offset
        self._nbytes = nbytes
        self._view: Optional[memoryview] = None

    async def acquire(self) -> memoryview:
        """The same view at every call: a plug-in that read in place hands
        it back as the read's buffer, and a read tried again lands where
        the first try did."""
        if self._view is None:
            await self._assembly.buffer_ready()
            flat = memoryview(self._assembly.flat_u8())
            self._view = flat[self._offset : self._offset + self._nbytes]
        return self._view

    def stamp(self, stamp: str) -> None:
        """The read pipeline's hand-overs of this read (``read_began``,
        ``read_back``, ``consume_began``), told to the leaf's range."""
        self._assembly.stamp(stamp)

    def holds(self, buf: Any) -> bool:
        """Whether ``buf`` is the view this read was given to land in."""
        return self._view is not None and buf is self._view

    def release(self) -> None:
        self._view = None


def _device_put_like(host: np.ndarray, like: Any) -> Any:
    """Place a host array like an existing jax.Array (device + sharding +
    memory kind + dtype).  The H2D analogue of the reference's
    consume-into-GPU-target copy (tensor.py:331-340)."""
    import jax

    from .. import phase_stats

    if host.dtype != np.dtype(like.dtype):
        host = host.astype(np.dtype(like.dtype))
    # Dispatch time with bytes — the transfer itself is async and lands
    # either under the batcher's h2d_land phase or the caller's sync point.
    with phase_stats.timed("h2d_dispatch", host.nbytes):
        return jax.device_put(host, like.sharding)


class ArrayBufferConsumer(BufferConsumer):
    # Leaf consumer (1 read : 1 payload): a read-fused digest of the request's
    # bytes is valid for this verify (set by the scheduler, io_types.ReadIO).
    accepts_hash64 = True

    def __init__(
        self,
        assembly: ArrayAssembly,
        flat_offset: int,
        nbytes: int,
        checksum: Optional[str] = None,
        location: str = "",
        into: Optional[IntoPlace] = None,
        codec: Optional[str] = None,
        frame_nbytes: Optional[int] = None,
    ) -> None:
        self._assembly = assembly
        self._flat_offset = flat_offset
        self._nbytes = nbytes
        self._checksum = checksum
        self._location = location
        self._into = into
        self._codec = codec
        self._frame_nbytes = frame_nbytes
        self.precomputed_hash64: Optional[int] = None
        # Tiled reads carry checksum=None (partial payloads are never
        # verified) — don't ask the plugin to hash them.
        self.wants_read_hash = checksum is not None
        # Which digest the fused read must compute ("xxh64s" large payloads
        # verify with parallel per-stripe reads on the native pool).
        from .. import integrity

        self.hash_algo = integrity.hash_algo_of(checksum)

    def consume_landed(self, buf: BufferType) -> bool:
        """The short way, for the read pipeline's loop thread when it takes
        a finished read off: if nothing is left to compute, finish here and
        say True.  Nothing is left when ``buf`` is the view the read was
        given to land in (so no codec: a framed payload has no place), and
        the digest came with the read (``precomputed_hash64``: what is left
        of ``integrity.verify`` is a string compare, and a mismatch raises
        ``ChecksumError`` with the location as ever) or there is none to
        check (the entry has no known checksum, or checksums are off).
        Else False, and nothing was done: ``consume_buffer`` hashes, decodes
        and copies on the executor."""
        from .. import integrity

        if self._into is None or not self._into.holds(buf) or self._codec is not None:
            return False
        if (
            self.precomputed_hash64 is None
            and self.hash_algo is not None
            and integrity.checksums_enabled()
        ):
            return False  # a whole pass over the bytes: the executor's
        integrity.verify(
            buf, self._checksum, self._location, precomputed=self.precomputed_hash64
        )
        self._into.release()
        self._assembly.piece_done()
        return True

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        in_place = self._into is not None and self._into.holds(buf)

        def _copy() -> None:
            from .. import integrity, phase_stats

            # The checksum covers the stored bytes — for framed payloads,
            # the compressed frame — so verification precedes decoding and
            # a corrupt frame fails as ChecksumError before FrameError.
            integrity.verify(
                buf,
                self._checksum,
                self._location,
                precomputed=self.precomputed_hash64,
            )
            if in_place:
                return  # storage already read the bytes into the assembly
            src_buf = buf
            if self._codec is not None:
                src_buf = serialization.decompress_staged(
                    buf, self._nbytes, self._location
                )
            with phase_stats.timed("consume_copy", self._nbytes):
                view = self._assembly.flat_u8()
                src = np.frombuffer(src_buf, dtype=np.uint8, count=self._nbytes)
                view[self._flat_offset : self._flat_offset + self._nbytes] = src

        if executor is not None and self._nbytes > 1 << 20:
            await asyncio.get_running_loop().run_in_executor(executor, _copy)
        else:
            _copy()
        if self._into is not None:
            self._into.release()
        self._assembly.piece_done()

    def get_consuming_cost_bytes(self) -> int:
        if self._codec is not None:
            # While decoding, the read frame and the decompressed payload
            # coexist — charge both (the frame size is recorded in the
            # manifest; fall back to the uncompressed bound without it).
            return self._nbytes + (self._frame_nbytes or self._nbytes)
        return self._nbytes


class _PickleArrayConsumer(BufferConsumer):
    def __init__(self, entry: TensorEntry, fut: Future, obj_out: Optional[Any]) -> None:
        self._entry = entry
        self._fut = fut
        self._obj_out = obj_out

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        from .. import integrity

        integrity.verify(buf, self._entry.checksum, self._entry.location)
        value = serialization.pickle_load_from_bytes(bytes(buf))
        target = self._obj_out
        if isinstance(target, np.ndarray) and target.flags.writeable and list(
            target.shape
        ) == list(np.shape(value)):
            np.copyto(target, value)
            self._fut.obj = target
        else:
            self._fut.obj = value

    def get_consuming_cost_bytes(self) -> int:
        return serialization.array_nbytes(self._entry.shape, "uint8") * 2
