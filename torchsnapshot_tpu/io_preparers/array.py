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
import logging
import math
import threading
from collections import defaultdict, deque
from concurrent.futures import Executor
from typing import Any, Dict, List, Optional, Tuple

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
        if staging.is_jax_array(obj):
            # Enqueue the async DMA now (we are being admitted by the
            # scheduler), materialize in the executor so concurrent stagers'
            # transfers overlap.
            staging.enqueue_d2h(obj)
            loop = asyncio.get_running_loop()
            if executor is not None:
                host = await loop.run_in_executor(
                    executor, staging.to_host, obj
                )
            else:
                host = staging.to_host(obj)
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
        from .chunked_array import _LazyHostSlice

        if (
            staging.is_jax_array(self._obj)
            or self._is_async_snapshot
            # Lazy host-slice handles materialize a host buffer at staging
            # time — real memory the budget must see.
            or isinstance(self._obj, _LazyHostSlice)
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


def _fresh_host_buffer(nbytes: int) -> np.ndarray:
    # A function of its own so that a test can choose where a buffer begins
    # (the CPU backend aliases a 64-byte-aligned one into the landed array).
    return np.empty(nbytes, dtype=np.uint8)


class HostBufferPool:
    """The host read buffers of ONE ``Snapshot.restore`` call, carried from a
    leaf whose H2D has landed to the next leaf of the same byte size.

    A leaf that is read into place and uploaded through an ``H2DBatcher``
    reserves its size at plan time (``reserve``), takes its buffer when the
    first of its reads is dispatched (``take``), and the batcher gives it
    back once the transfer has landed (``give``).  The large statefuls of a
    train state (parameters, first and second moments) are one tree three
    times over and each is read smallest leaf first, so from the second on
    a ``take`` finds the buffer its twin landed from long ago: nothing is
    unmapped beside the reads (a ``munmap`` holds the GIL and takes the
    address space's lock for writing while the readers fault pages in under
    it) and the reads land in pages already faulted in.

    A read finds its twin's buffer only once that has landed, and the reads
    of a stateful of a dozen leaves are all dispatched in the instant the
    one before it has been read, its largest leaves last: so a read whose
    size has none free, while one lent to an EARLIER stateful is still to
    come back, is held until it has (``coming``, which the assembly awaits
    before it takes; a ``give`` wakes it).  That one is read already and
    lands without anything this read could hold up.  What the wait buys
    depends on the host: it trades the storage's time under that landing
    for pages that need no first touch, and is worth it where a first touch
    costs more than the read, as on the hosts measured (PERF.md section 5).
    A buffer lent to the same stateful is never waited for: it may land
    only at that stateful's drain, which waits for this very read.

    Free lists keyed by exact byte size, nothing more: a size not seen
    before is a plain ``np.empty``, as without a pool.  A buffer that
    nothing will take again is kept all the same, so that it is not freed
    beside reads: it goes with the rest when the restore ends (``close``),
    or earlier only to make room, when a miss would take the bytes alive
    through the pool (taken and not given back, plus free) over the two
    largest groups' reservations: the bound the read pipeline keeps without
    a pool.  Nothing outlives the restore.

    Thread-safe: ``reserve`` runs on the planning thread, ``coming`` and
    ``take`` on the read pipeline's thread or its executor, ``give`` on the
    lander."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._free: Dict[int, List[np.ndarray]] = defaultdict(list)
        self._group_bytes: List[int] = []  # reserved by each stateful
        self._lent: Dict[int, Tuple[int, int]] = {}  # id(buffer) -> (size, group)
        # size -> (loop, future) of each read held until one comes back
        self._waiters: Dict[int, List[Tuple[Any, Any]]] = defaultdict(list)
        self._alive = 0  # bytes taken and not given back, plus free
        self._stats = {"bytes": 0, "fresh": 0, "hits": 0, "misses": 0, "high_water": 0}

    def begin_group(self) -> None:
        """The reservations that follow are one stateful's."""
        with self._lock:
            self._group_bytes.append(0)

    def reserve(self, nbytes: int) -> int:
        """One ``take`` of ``nbytes`` is to come; the group (stateful) it
        will come from."""
        with self._lock:
            self._group_bytes[-1] += nbytes
            return len(self._group_bytes) - 1

    def coming(self, nbytes: int, group: int) -> Optional["asyncio.Future[None]"]:
        """None where a ``take`` need not wait; else a future of the running
        loop that the next ``give`` of a buffer of ``nbytes`` resolves: no
        such buffer is free, and one lent to a group before ``group`` is yet
        to be given back."""
        with self._lock:
            if self._free[nbytes] or not any(
                size == nbytes and lent_to < group
                for size, lent_to in self._lent.values()
            ):
                return None
            loop = asyncio.get_running_loop()
            woken = loop.create_future()
            self._waiters[nbytes].append((loop, woken))
            return woken

    def take(self, nbytes: int, group: int) -> np.ndarray:
        """A flat uint8 buffer of exactly ``nbytes`` for a leaf of ``group``:
        one given back, else a new one."""
        evicted: List[np.ndarray] = []
        stats = self._stats
        with self._lock:
            if self._free[nbytes]:
                stats["hits"] += 1
                stats["bytes"] += nbytes
                buf = self._free[nbytes].pop()
            else:
                stats["misses"] += 1
                stats["fresh"] += nbytes
                over = self._alive + nbytes - sum(sorted(self._group_bytes)[-2:])
                for bufs in self._free.values():
                    while over > 0 and bufs:
                        evicted.append(bufs.pop())
                        over -= evicted[-1].nbytes
                self._alive += nbytes - sum(buf.nbytes for buf in evicted)
                stats["high_water"] = max(stats["high_water"], self._alive)
                buf = _fresh_host_buffer(nbytes)
            self._lent[id(buf)] = (nbytes, group)
        del evicted  # unmapped outside the lock
        return buf

    def give(self, buf: np.ndarray, recycle: bool) -> None:
        """``buf``, taken here, is done with.  ``recycle`` only where its
        transfer has landed and the landed array is not the buffer itself;
        otherwise it is dropped, and only counted out.  Either way whoever
        waits for a buffer of its size (``coming``) is woken to look again."""
        with self._lock:
            del self._lent[id(buf)]
            if recycle:
                self._free[buf.nbytes].append(buf)
            else:
                self._alive -= buf.nbytes
            waiters = self._waiters.pop(buf.nbytes, ())
        for loop, woken in waiters:
            try:
                loop.call_soon_threadsafe(_wake, woken)
            except RuntimeError:  # the pipeline was aborted, its loop closed
                pass

    def close(self) -> None:
        """The restore is over, nothing reads any more: what is free goes."""
        with self._lock:
            free = [buf for bufs in self._free.values() for buf in bufs]
            self._free.clear()
            self._alive -= sum(buf.nbytes for buf in free)
        del free  # unmapped outside the lock

    def stats(self) -> Dict[str, int]:
        """``bytes`` read into recycled buffers (``hits`` of them), ``fresh``
        bytes read into new ones (``misses``), and the ``high_water`` of the
        bytes alive through the pool."""
        with self._lock:
            return dict(self._stats)


def _wake(woken: "asyncio.Future[None]") -> None:
    if not woken.done():  # cancelled with its read
        woken.set_result(None)


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


class H2DBatcher:
    """Cross-array H2D upload batching + landing pacing for the restore path.

    Per-array ``device_put`` dispatches serialize each upload behind its
    array's read; collecting completed host buffers and uploading
    them in ONE batched pjrt transfer lets the backend overlap the streams
    and overlaps the batch with the remaining storage reads.  Buffers
    accumulate up to ``flush_bytes`` (bounding the extra host-memory
    residency beyond the scheduler's budget), then flush incrementally.

    Dispatched batches land EAGERLY on a dedicated lander thread; a bounded
    unlanded-bytes window (default 2× ``flush_bytes``) backpressures new
    dispatches so batch N's landing overlaps the reads feeding batch N+1
    instead of every transfer piling up behind the caller's final
    ``block_until_ready`` (r04 bench: 159 s of unattributed restore wall —
    the reference's read scheduler overlaps read and consume end-to-end,
    /root/reference/torchsnapshot/scheduler.py:386-447).  Landings are
    attributed to the byte-carrying ``h2d_land`` phase; dispatch CPU time to
    ``h2d_dispatch``.  The owner calls :meth:`drain` after the read pipeline
    finishes: on return every submitted array is ON DEVICE, not in flight,
    and the lander thread has exited.

    With a ``host_pool`` (``Snapshot.restore``'s), a buffer submitted with
    its ``lease`` (the pool's buffer under ``host``) goes back to the pool
    when its transfer has landed, unless the landed array may be the buffer
    itself (``_may_alias``); one whose transfer failed, or was never made,
    is dropped.  Nothing here keeps a host buffer past its landing.

    Thread-safety: ``submit``/``flush`` may run on the read pipeline's loop
    or executor threads, ``drain`` on the caller thread.  Because landings
    run on the lander (never on the flushing thread), a backpressure wait
    in ``flush`` lasts only until the lander frees window room — and the
    window bounds unlanded host-buffer residency, which the scheduler's
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
        self._inflight_cap = (
            inflight_cap_bytes if inflight_cap_bytes is not None else 2 * flush_bytes
        )
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        # (landing arrays, their bytes, (array, lease) of the pool's buffers)
        self._inflight: "deque[Tuple[List[Any], int, List[_Lease]]]" = deque()
        self._unlanded_bytes = 0  # dispatched, not yet landed
        self._lander: Optional[Any] = None
        self._lander_stop = False
        self._lander_error: Optional[BaseException] = None

    def submit(
        self,
        host: np.ndarray,
        like: Any,
        fut: Future,
        lease: Optional[np.ndarray] = None,
    ) -> None:
        with self._lock:
            self._items.append((host, like, fut, lease))
            self._bytes += host.nbytes
            should_flush = self._bytes >= self._flush_bytes
        if should_flush:
            self.flush()

    def flush(self) -> None:
        from .. import phase_stats

        with self._lock:
            items, self._items, self._bytes = self._items, [], 0
        if not items:
            return
        batch_bytes = sum(host.nbytes for host, *_ in items)
        # Backpressure: wait for the lander to free window room, and RESERVE
        # this batch's bytes in the same critical section — otherwise N
        # concurrent flushers all pass the check against the
        # still-unincremented counter and overshoot the window by N batches.
        # The wait lasts only for the EXCESS over the window (landing of
        # older batches started the moment they were dispatched), and a full
        # window stalling the producer is the point — reads must not run
        # unboundedly ahead of a slow H2D link.
        # The wait is h2d_window_wait when it lasted: it can hold a consumer
        # on the read pipeline's loop thread, and with it the pipeline.
        window_wait = None
        with self._cond:
            self._raise_lander_error()
            while (
                self._unlanded_bytes > 0
                and self._unlanded_bytes + batch_bytes > self._inflight_cap
            ):
                if window_wait is None:
                    window_wait = phase_stats.open_interval("h2d_window_wait")
                self._cond.wait(timeout=1.0)
                self._raise_lander_error()
            self._unlanded_bytes += batch_bytes  # reserved
        if window_wait is not None:
            window_wait.close(min_s=0.001)
        try:
            outs, failed = self._dispatch(items, batch_bytes)
        except BaseException:
            with self._cond:
                self._unlanded_bytes -= batch_bytes
                self._cond.notify_all()
            raise
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
                self._inflight.append((good, landed_bytes, leases))
                self._ensure_lander()
            self._cond.notify_all()
        if failed:
            # A failed batch retries per item so one bad array (dtype/
            # sharding mismatch) fails alone with correct blame and its
            # batch-mates still restore.
            self._dispatch_per_item(failed)

    def drain(self) -> None:
        """Flush the tail and block until every dispatched transfer LANDS
        (attributed to ``h2d_land``).  After this, restored arrays are
        device-resident — the caller's own block_until_ready sees ~0 s.

        On a landing failure the error still surfaces here, but only after
        the remaining dispatched batches finish their landing attempts:
        drain exits quiescent (byte accounting settled, lander joined)
        whether it raises or not, so callers never observe mid-landing
        counters or a still-running lander thread after an error."""
        try:
            self.flush()
        finally:
            # The lander decrements unlanded bytes even for failed
            # landings, so this loop terminates regardless of errors.
            with self._cond:
                while self._unlanded_bytes > 0 or self._inflight:
                    self._cond.wait(timeout=1.0)
            self.shutdown()
        self._raise_lander_error()

    def shutdown(self) -> None:
        """Stop and join the lander thread (idempotent; never raises the
        landing error — callers check via drain).  Owners call this from a
        ``finally`` so an aborted read pipeline doesn't leak a parked
        thread per restore in a long-lived trainer."""
        with self._cond:
            self._lander_stop = True
            self._cond.notify_all()
            lander = self._lander
            self._lander = None
        if lander is not None:
            lander.join()
        self._lander_stop = False  # reusable after drain/shutdown

    def _raise_lander_error(self) -> None:
        # Sticky: a batcher with a failed landing keeps raising (it is
        # per-restore and discarded after; clearing would let a drain
        # following a flush-consumed error report clean).
        if self._lander_error is not None:
            raise self._lander_error

    def _ensure_lander(self) -> None:
        # Called under the lock.
        if self._lander is None:
            self._lander = threading.Thread(
                target=self._land_loop, name="tpusnap-h2d-lander", daemon=True
            )
            self._lander.start()

    def _land_loop(self) -> None:
        import jax

        from .. import phase_stats

        while True:
            with self._cond:
                while not self._inflight and not self._lander_stop:
                    self._cond.wait()
                if not self._inflight:  # stop requested and queue empty
                    return
                outs, nbytes, leases = self._inflight.popleft()
            # A landing failure must not wedge the batcher: record the first
            # error, keep the byte accounting exact, and KEEP LANDING the
            # remaining batches so backpressure waiters and drain() always
            # make progress (the error surfaces at the next flush/drain).
            err: Optional[BaseException] = None
            try:
                with phase_stats.timed("h2d_land", nbytes):
                    jax.block_until_ready(outs)
            except BaseException as e:  # noqa: BLE001
                err = e
            # Before the window opens: whoever it lets through finds the
            # buffers this batch landed from.
            self._settle(leases, landed=err is None)
            outs = leases = None
            with self._cond:
                self._unlanded_bytes -= nbytes
                if err is not None and self._lander_error is None:
                    self._lander_error = err
                self._cond.notify_all()

    def _settle(self, leases: List[_Lease], landed: bool) -> None:
        """Give the pool's buffers back: to be used again where the transfer
        has ``landed`` and the array it made is not the buffer itself."""
        for out, lease in leases:
            self.host_pool.give(
                lease, recycle=landed and out is not None and not _may_alias(out, lease)
            )

    def _dispatch(
        self, items: List[_Item], batch_bytes: int
    ) -> Tuple[List[Any], List[_Item]]:
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
        # Manual phase accounting, recorded only for DISPATCHED bytes:
        # timed() commits in its finally, so a failed batch would charge its
        # bytes to h2d_dispatch and the per-item retry would charge again.
        dispatch = phase_stats.open_interval("h2d_dispatch")
        try:
            for i, out in zip(idx, jax.device_put(bufs, shardings)):
                outs[i] = out
        except Exception:
            dispatch.drop()
            # An HBM OOM looks like this: the per-item retry may well
            # succeed, so the first failure must not vanish with it.
            logger.warning(
                "batched device_put of %d arrays (%d bytes) failed; "
                "retrying them one by one",
                len(bufs),
                batch_bytes,
                exc_info=True,
            )
            outs = [None] * len(items)
            failed.extend(items[i] for i in idx)
        else:
            dispatch.close(sum(b.nbytes for b in bufs))
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
                with phase_stats.timed("h2d_land", nbytes):
                    jax.block_until_ready(outs)
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
    uploads through an ``H2DBatcher`` with a ``HostBufferPool`` takes its
    buffer from the pool, and the batcher gives it back once landed."""

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
        self._pool: Optional[HostBufferPool] = None
        self._nbytes = serialization.array_nbytes(entry.shape, entry.dtype)
        if (
            h2d_batch is not None
            and h2d_batch.host_pool is not None
            and not self._inplace
            and staging.is_jax_array(obj_out)
            and self._nbytes >= _INTO_PLACE_MIN_BYTES
        ):
            self._pool = h2d_batch.host_pool
            self._group = self._pool.reserve(self._nbytes)

    @property
    def host(self) -> np.ndarray:
        with self._host_lock:
            if self._host is None:
                if self._pool is not None:
                    self._lease = self._pool.take(self._nbytes, self._group)
                    self._host = self._lease.view(
                        serialization.string_to_dtype(self.entry.dtype)
                    ).reshape(self.entry.shape)
                else:
                    self._host = ArrayIOPreparer.empty_array_from_entry(self.entry)
            return self._host

    async def buffer_ready(self) -> None:
        """Before a read into this assembly is dispatched: while the pool
        has no buffer of its size free and its twin's, lent to a stateful
        before this one, is still landing, wait for that one
        (``HostBufferPool.coming``; the phase ``host_buffer_wait``)."""
        from .. import phase_stats

        waited = None
        try:
            while self._host is None and self._pool is not None:
                woken = self._pool.coming(self._nbytes, self._group)
                if woken is None:
                    break
                if waited is None:
                    waited = phase_stats.open_interval("host_buffer_wait")
                await woken
        finally:
            if waited is not None:
                waited.close()

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
        lease, self._host, self._lease = self._lease, None, None
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
    read pipeline awaits when it dispatches the read, and the consumer lets
    go of it (``release``) once it has seen the read arrive."""

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
