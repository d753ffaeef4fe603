"""Core I/O contracts: write/read requests, stagers, consumers, storage ABC.

TPU-native analogue of the reference's ``torchsnapshot/io_types.py``
(/root/reference/torchsnapshot/io_types.py:24-120).  The shapes are the same
because they are device-agnostic: a ``WriteReq`` pairs a storage path with a
``BufferStager`` that produces host bytes (for us: async HBM→host DMA via
pjrt, then a zero-copy view); a ``ReadReq`` pairs a path + byte range with a
``BufferConsumer`` that scatters bytes into the restore target.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass
from typing import Any, Generic, List, Optional, TypeVar

from .utils.loops import run_coro

BufferType = Any  # bytes | bytearray | memoryview | ScatterBuffer

T = TypeVar("T")


class ScatterBuffer:
    """Ordered host buffers forming one logical payload (a slab).

    Lets batched writes skip the pack memcpy: storage backends with
    scatter-gather support (the native fs data plane) write the parts
    directly from their own memory; others call :meth:`join` — one memcpy,
    the contiguous-slab behavior.  On a host whose memory bandwidth is the
    bottleneck (every TPU host mid-D2H), the skipped pack is a full extra
    pass over the checkpoint bytes.
    """

    __slots__ = ("parts", "nbytes")

    def __init__(self, parts) -> None:
        self.parts = [memoryview(p).cast("B") for p in parts]
        self.nbytes = sum(p.nbytes for p in self.parts)

    def join(self) -> memoryview:
        from . import phase_stats

        if len(self.parts) == 1:
            return self.parts[0]
        out = bytearray(self.nbytes)
        offset = 0
        with phase_stats.timed("slab_pack", self.nbytes):
            for part in self.parts:
                out[offset : offset + part.nbytes] = part
                offset += part.nbytes
        return memoryview(out)


def contiguous(buf: BufferType) -> BufferType:
    """The payload as one contiguous buffer (joins a ScatterBuffer)."""
    return buf.join() if isinstance(buf, ScatterBuffer) else buf


class Future(Generic[T]):
    """Holds a value produced during read consumption (reference
    io_types.py:24-30)."""

    def __init__(self, obj: Optional[T] = None) -> None:
        self.obj = obj


@dataclass
class WriteIO:
    path: str
    buf: BufferType
    # Crash-durability request: the write must survive a host crash the
    # moment it returns (fs fsyncs the file AND its parent dir before/after
    # the atomic rename).  Set by commit-critical writes only — the
    # ``.snapshot_metadata`` marker whose existence IS the committed signal;
    # payload writes stay fast (they are re-creatable until the commit).
    # Backends whose writes are already durable-on-ack (object stores)
    # ignore it.
    durable: bool = False
    # Fused write+hash request (scheduler → plugins advertising
    # ``supports_write_hash``): compute each part's digest fused with the
    # write — one memory pass on native threads instead of a separate
    # Python-level checksum pass — and fill ``part_hash64``.  Parts are the
    # ScatterBuffer members in order, or the single whole buffer.  A plugin
    # that leaves ``part_hash64`` None is fine: the scheduler hashes the
    # still-held buffer itself.
    want_part_hashes: bool = False
    # Per-part 64-bit digests under the size policy integrity.format_digest
    # applies (plain xxh64 below STRIPED_MIN_BYTES, striped xxh64s at or
    # above), set by the plugin when it fused hashing into the write.
    part_hash64: Optional[List[int]] = None
    # Scheduler hint that sibling write requests are in flight or queued:
    # plugins that micro-batch small fused writes into one native call
    # (fs + TPUSNAP_NATIVE_BATCH) route this write through their
    # group-commit gate.  False for a lone write, which skips the gate
    # machinery entirely.
    batch_hint: bool = False


@dataclass
class ReadIO:
    path: str
    byte_range: Optional[List[int]] = None
    buf: Optional[bytearray] = None
    # Optional preallocated destination: plugins that can read directly into
    # it (fs readinto/native pread) do so and set buf = into — the consumer
    # then skips its copy.  Plugins that can't simply ignore it.
    into: Optional[memoryview] = None
    # Set by the issuer (scheduler/CLI) when the consumer of this read will
    # verify the WHOLE payload against a recorded digest: plugins that can
    # fuse hashing into the read loop (native fs) then do so.  Off by
    # default so merged spanning reads, tiled reads, and checksum-less
    # entries never pay for a digest nobody will use.
    want_hash: bool = False
    # The recorded digest's algorithm ("xxh64" | "xxh64s"), so a fusing
    # plugin computes the digest the consumer will actually compare
    # against.  "xxh64s" (striped) additionally unlocks the parallel
    # read path for checksummed payloads: stripes read+hash concurrently
    # on the native pool, which a sequential xxh64 stream forbids.
    hash_algo: Optional[str] = None
    # The 64-bit digest (under ``hash_algo``) of exactly the bytes placed
    # in ``buf``, when the plugin computed it fused with the read (native
    # fs data plane).  Consumers whose integrity check covers the whole
    # read use it to skip their own hash pass; None means "not computed"
    # and is always safe.
    hash64: Optional[int] = None


class BufferStager(abc.ABC):
    """Produces the host buffer for one write (reference io_types.py:36-50)."""

    @abc.abstractmethod
    async def stage_buffer(self, executor: Any = None) -> BufferType:
        ...

    @abc.abstractmethod
    def get_staging_cost_bytes(self) -> int:
        """Peak transient host memory needed to stage (admission control)."""
        ...


@dataclass
class WriteReq:
    path: str
    buffer_stager: BufferStager


class BufferConsumer(abc.ABC):
    """Consumes the bytes read for one request (reference io_types.py:60-74)."""

    @abc.abstractmethod
    async def consume_buffer(self, buf: BufferType, executor: Any = None) -> None:
        ...

    @abc.abstractmethod
    def get_consuming_cost_bytes(self) -> int:
        ...


@dataclass
class ReadReq:
    path: str
    buffer_consumer: BufferConsumer
    byte_range: Optional[List[int]] = None
    # Tiled reads (one tensor split under a buffer budget) must never be
    # re-merged by the batcher — that would silently defeat the caller's
    # buffer_size_limit_bytes and buffer the whole payload at once.
    no_merge: bool = False
    # Read-into-place: the consumer's destination, forwarded to the storage
    # plugin via ReadIO.into once the read is dispatched and no sooner: the
    # read pipeline awaits its ``acquire()`` for the memoryview, or calls its
    # ``acquire_soon()`` (IntoView: memory that is there already;
    # io_preparers/array.IntoPlace: memory taken then, from the restore's
    # pool of host buffers).  Requests
    # carrying one are never merged (their destinations are not contiguous
    # in host memory).
    into: Optional[Any] = None


class IntoView:
    """A ``ReadReq.into`` whose memory is there at plan time."""

    def __init__(self, view: memoryview) -> None:
        self._view = view

    async def acquire(self) -> memoryview:
        return self._view

    def acquire_soon(self, loop: Any, then: Any) -> memoryview:
        """``acquire()`` without a coroutine (``io_preparers/array.IntoPlace
        .acquire_soon``): the memory is there, so ``then`` is never called."""
        return self._view

    def stamp(self, stamp: str) -> None:
        """Nothing to tell: the memory is no range of a restore's arena
        (``io_preparers/array.IntoPlace.stamp``)."""


class StoragePlugin(abc.ABC):
    """Async storage backend contract (reference io_types.py:80-120)."""

    # True when write() consumes a ScatterBuffer part-by-part with no join
    # memcpy/allocation (the native fs data plane).  Backends that join at
    # write time leave this False so the batcher keeps the slab-sized side
    # allocation in the staging cost the scheduler budgets for.
    supports_scatter: bool = False

    # True when write() honors WriteIO.want_part_hashes — digests computed
    # fused with the write on native threads (the fs native data plane).
    # The scheduler defers manifest checksums to write time for such
    # backends; for everything else it hashes the staged buffer itself
    # right before the write, so manifests are identical either way.
    supports_write_hash: bool = False

    @abc.abstractmethod
    async def write(self, write_io: WriteIO) -> None:
        ...

    @abc.abstractmethod
    async def read(self, read_io: ReadIO) -> None:
        ...

    @abc.abstractmethod
    async def delete(self, path: str) -> None:
        ...

    @abc.abstractmethod
    async def delete_dir(self, path: str) -> None:
        ...

    @abc.abstractmethod
    async def close(self) -> None:
        ...

    async def list_dir(self, path: str) -> List[str]:
        """Immediate child names under ``path`` (files and directory-like
        prefixes, no trailing slash).  Lets SnapshotManager enumerate
        committed steps on any backend; raises NotImplementedError where the
        backend genuinely cannot list."""
        raise NotImplementedError(f"{type(self).__name__} cannot list")

    async def copy_from_sibling(self, src_root: str, path: str) -> bool:
        """Server-side duplication of ``src_root``'s ``path`` (a sibling
        location on the same backend, e.g. the previous snapshot directory)
        into this plugin's ``path``, without moving the bytes through this
        host.  Returns False when the backend can't (caller falls back to a
        normal write) — incremental snapshots use this to skip re-uploading
        unchanged payloads."""
        return False

    async def exists(self, path: str) -> bool:
        """Whether ``path`` holds a readable object.  Default probes with a
        read (commit-marker files are small); backends override with a
        cheaper stat/HEAD where available."""
        read_io = ReadIO(path=path)
        try:
            await self.read(read_io)
            return True
        except (FileNotFoundError, KeyError):
            # Only typed not-found signals classify as absent.  Transport or
            # proxy errors must propagate: retention treats "missing commit
            # marker" as a torn snapshot and prunes it, so misclassifying a
            # flaky 5xx (or an error page whose text happens to contain
            # "404") would delete a valid restore point.  Backends whose
            # not-found surfaces differently must override exists().
            return False

    # Sync conveniences (reference io_types.py:101-120); run a private loop,
    # delegating to a helper thread when the caller is already inside a
    # running loop (Jupyter / async trainers — utils/loops.py).
    def sync_write(self, write_io: WriteIO) -> None:
        run_coro(lambda: self.write(write_io))

    def sync_read(self, read_io: ReadIO) -> None:
        run_coro(lambda: self.read(read_io))

    def sync_list_dir(self, path: str) -> List[str]:
        return run_coro(lambda: self.list_dir(path))

    def sync_exists(self, path: str) -> bool:
        return run_coro(lambda: self.exists(path))

    def sync_delete(self, path: str) -> None:
        run_coro(lambda: self.delete(path))

    def sync_delete_dir(self, path: str) -> None:
        run_coro(lambda: self.delete_dir(path))

    def sync_close(self) -> None:
        run_coro(lambda: self.close())
