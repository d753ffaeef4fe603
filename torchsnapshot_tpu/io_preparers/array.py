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
import functools
import math
import threading
from concurrent.futures import Executor
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from .. import knobs, serialization, staging
from ..compression import is_framed
from ..h2d import H2DBatcher, HostBufferPool, _device_put_like, _Turn
from ..io_types import BufferConsumer, BufferStager, BufferType, Future, ReadReq, WriteReq
from ..manifest import TensorEntry
from ..serialization import Serializer

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


class ArrayAssembly:
    """Shared restore target for one logical array: a host buffer that one or
    more consumers fill, finalized into the caller's target exactly once.

    The buffer lives from the dispatch of the first read that lands in it
    (``host``: nothing is allocated at plan time) to ``finalize``, which
    hands it on and lets go.  A jax-array target of a megabyte and more that
    uploads through the restore's ``H2DBatcher`` takes a range of its pool's
    arena (``buffer_ready``, or ``buffer_ready_soon``, which wait for room;
    every read into the leaf shares the one range), and the batcher gives it
    back once landed."""

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
        # the pool's promise of a range, while the first read waits for room;
        # or, for reads handed on by callbacks, what each waiting read does next
        self._coming: Optional["asyncio.Future[np.ndarray]"] = None
        self._then: Optional[List[Callable[[], None]]] = None
        self._waited: Optional[Any] = None  # their host_buffer_wait interval
        self._nbytes = serialization.array_nbytes(entry.shape, entry.dtype)
        if (
            h2d_batch is not None
            and not self._inplace
            and staging.is_jax_array(obj_out)
        ):
            h2d_batch.expects_uploads = True  # finalize() will submit there
            if self._nbytes >= _INTO_PLACE_MIN_BYTES:
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

    def buffer_ready_soon(self, loop: Any, then: Callable[[], None]) -> bool:
        """``buffer_ready`` for a read pipeline that hands reads on by
        callbacks: True where the leaf's range is in hand now; otherwise
        False, and ``then()`` is called on ``loop``'s thread once the pool has
        granted the range (one callback, ``_granted``) and the leaf has
        adopted it.  The leaf's other reads wait with the first."""
        from .. import phase_stats

        if self._pool is None or self._host is not None:
            return True
        if self._then is not None:
            self._then.append(then)
            return False
        taken = self._pool.take(self._nbytes, loop, granted=self._granted)
        if taken is not None:
            self._settle_on(taken)
            return True
        self._then = [then]
        self._waited = phase_stats.open_interval("host_buffer_wait")
        return False

    def _granted(self, lease: np.ndarray) -> None:
        # On the loop's thread: the pool's grant to the first waiting read.
        waiting, self._then = self._then, None
        self._waited.close()
        self._settle_on(lease)
        for then in waiting:
            then()

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
        its turn (``h2d._Turn.stamp``; nothing for a leaf with no range)."""
        if self._turn is not None:
            self._turn.stamp(stamp)

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
        return self._viewed()

    def acquire_soon(
        self, loop: Any, then: Callable[[memoryview], None]
    ) -> Optional[memoryview]:
        """``acquire()`` for a read pipeline that hands reads on by
        callbacks: the view where the leaf's range is in hand now; otherwise
        None, and ``then(view)`` is called on ``loop``'s thread once it is."""
        if self._view is None and not self._assembly.buffer_ready_soon(
            loop, lambda: then(self._viewed())
        ):
            return None
        return self._viewed()

    def _viewed(self) -> memoryview:
        if self._view is None:
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
