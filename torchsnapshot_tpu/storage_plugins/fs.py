"""Local/posix filesystem storage plugin.

TPU-native analogue of the reference's ``torchsnapshot/storage_plugins/fs.py``
(/root/reference/torchsnapshot/storage_plugins/fs.py:21-63).  Writes/reads run
through a thread pool (posix I/O releases the GIL); when the native helper
library (tpusnap_io, C++ pread/pwrite pool) is built, it takes over the data
plane for large buffers.  Parent-directory creation is cached like the
reference (fs.py:31-34); byte-ranged reads seek (fs.py:42-51).
"""

from __future__ import annotations

import asyncio
import itertools
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Set

from ..io_types import ReadIO, StoragePlugin, WriteIO

# Per-process sequence for tmp-file names (see _blocking_write): thread-safe
# (itertools.count's __next__ is atomic under the GIL).
_TMP_SEQ = itertools.count()

from ._ranged import PARALLEL_READ_CHUNK_BYTES as _PARALLEL_READ_CHUNK
from ._ranged import PARALLEL_READ_MAX_WAYS as _PARALLEL_READ_MAX_WAYS

_DEFAULT_IO_THREADS = 16
_PARALLEL_READ_MIN_BYTES = 64 * 1024 * 1024
_ADAPTIVE_REPROBE_EVERY = 16

# Micro-batching (TPUSNAP_NATIVE_BATCH): only payloads at or below this
# join a batch — the gains are per-call dispatch overhead, which only
# matters for small files; a large slab behind the gather gate would
# serialize siblings behind its write instead.
_BATCH_MAX_MEMBER_BYTES = 8 * 1024 * 1024


class _FusedWriteBatcher:
    """Group-commit gate in front of ``write_parts_hash_batch``: small
    fused writes arriving on concurrent fs_io threads coalesce into ONE
    native call and ONE pool submission per batch, so a drain of
    thousand-leaf small payloads stops paying per-payload FFI dispatch.

    No gather window: the first free member leads whatever is pending
    RIGHT NOW (possibly just itself — a batch of one costs what the single
    call costs), and members arriving while that native call runs pile up
    for the next leader.  Batch size therefore self-tunes to arrival rate
    × call duration — the classic group-commit shape — and a lone write
    never waits on a gate nobody else will join.  A member's failure is
    isolated (its OSError re-raises on its own thread); a whole-call
    failure falls back to per-member single calls so batching can never
    lose a write the single path would have made."""

    def __init__(self, native, max_batch: int) -> None:
        self._native = native
        self._max = max_batch
        self._cond = threading.Condition()
        self._pending: list = []
        self._leader_active = False

    def write(self, path: str, parts) -> list:
        """Write ``parts`` to ``path`` through the current batch; blocks
        until this member's digests are back.  Raises the member's own
        OSError on failure, exactly like ``write_parts_hash``."""
        member = {"path": path, "parts": parts, "done": False,
                  "result": None, "error": None}
        with self._cond:
            self._pending.append(member)
            while not member["done"]:
                if self._leader_active or not self._pending:
                    # A batch is executing (ours may be in it), or ours was
                    # taken and is in flight: wait for results / the next
                    # leadership vacancy.
                    self._cond.wait()
                    continue
                # Leadership: take up to max_batch pending members —
                # including this one unless a full batch formed ahead of it
                # — and execute outside the lock.
                self._leader_active = True
                batch = self._pending[: self._max]
                del self._pending[: self._max]
                self._cond.release()
                try:
                    self._execute(batch)
                finally:
                    self._cond.acquire()
                    self._leader_active = False
                    self._cond.notify_all()
        if member["error"] is not None:
            raise member["error"]
        return member["result"]

    def _execute(self, batch: list) -> None:
        # Every member MUST come out of here done (result or error): a
        # member left pending would park its fs_io thread forever, so the
        # done-marking lives in a finally and the fallback catches
        # everything, not just OSError.
        try:
            try:
                results = self._native.write_parts_hash_batch(
                    [(m["path"], m["parts"]) for m in batch]
                )
            except Exception:  # noqa: BLE001 — whole-call failure only
                results = None
            if results is None:
                # The batch path itself broke (never expected): every
                # member falls back to its own single call, preserving
                # single-path semantics exactly.
                for m in batch:
                    try:
                        m["result"] = self._native.write_parts_hash(
                            m["path"], m["parts"]
                        )
                    except Exception as e:  # noqa: BLE001
                        m["error"] = e
            else:
                for m, res in zip(batch, results):
                    if isinstance(res, OSError):
                        m["error"] = res
                    else:
                        m["result"] = res
        finally:
            with self._cond:
                for m in batch:
                    if m["result"] is None and m["error"] is None:
                        m["error"] = RuntimeError(
                            f"batched write of {m['path']} aborted"
                        )
                    m["done"] = True
                self._cond.notify_all()


class FSStoragePlugin(StoragePlugin):
    supports_scatter = True  # writes ScatterBuffer parts with no join

    def __init__(self, root: str, storage_options=None) -> None:
        if storage_options:
            # No fs tunables today; unknown keys must fail loudly rather
            # than silently change nothing (reference storage_plugin.py:20).
            raise ValueError(
                f"fs accepts no storage_options, got {sorted(storage_options)}"
            )
        self.root = root
        self._dir_cache: Set[str] = set()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._executor_lock = threading.Lock()
        # Built eagerly: the getter runs concurrently on fs_io worker
        # threads, where lazy init would race and leak a pool.  Construction
        # is cheap — ThreadPoolExecutor spawns threads on first submit.
        self._chunk_executor: ThreadPoolExecutor = ThreadPoolExecutor(
            max_workers=_PARALLEL_READ_MAX_WAYS, thread_name_prefix="fs_chunk"
        )
        try:
            from ..native_io import NativeFileIO

            self._native: Optional[NativeFileIO] = NativeFileIO.maybe_create()
        except Exception:
            self._native = None
        self._write_batcher: Optional[_FusedWriteBatcher] = None
        self._direct_io = False
        if self._native is not None:
            from .. import knobs

            if self._native.has_direct_io:
                # The direct-I/O mode is PROCESS-global (one atomic in the
                # native library) with the env knob as its source of
                # truth.  Reconfigure only when the knob disagrees with
                # the current mode: an unconditional re-store from every
                # plugin constructor would flip the mode under sibling
                # instances mid-save and reset the sticky
                # buffered-degrade state a rejected O_DIRECT left behind.
                self._direct_io = knobs.direct_io_enabled()
                if self._direct_io != (self._native.direct_io_mode() != 0):
                    self._native.configure_direct_io(self._direct_io)
            batch_max = knobs.get_native_batch()
            if (
                batch_max > 1
                and self._native.has_fused_write
                and self._native.has_batch_write
            ):
                self._write_batcher = _FusedWriteBatcher(
                    self._native, batch_max
                )
        # Adaptive strategy for large UNchecksummed into-reads (checksummed
        # ones always take the sequential fused read+hash path): the first
        # two qualifying reads measure sequential vs parallel once, then the
        # winner sticks for this plugin's lifetime.  No static default is
        # right everywhere — sequential rode readahead 2.6x faster on a
        # virtual disk, parallel wins on NVMe queue depth.
        self._adaptive_lock = threading.Lock()
        self._seq_gbps: Optional[float] = None
        self._par_gbps: Optional[float] = None
        self._reads_since_probe = 0

    def _get_executor(self) -> ThreadPoolExecutor:
        # Double-checked under a lock: the sync_* surface is driven from
        # multiple caller threads (replication workers), where an unlocked
        # check-then-set would build two pools and leak one.
        if self._executor is None:
            with self._executor_lock:
                if self._executor is None:
                    self._executor = ThreadPoolExecutor(
                        max_workers=_DEFAULT_IO_THREADS,
                        thread_name_prefix="fs_io",
                    )
        return self._executor

    def _get_chunk_executor(self) -> ThreadPoolExecutor:
        # Separate pool for intra-file chunk reads: the parent read occupies
        # an fs_io thread and blocks on its chunks, so submitting chunks to
        # the same pool deadlocks once every fs_io thread holds a parent
        # read (16 concurrent reads is exactly the scheduler's default cap).
        return self._chunk_executor

    def _prepare_parent(self, path: str) -> None:
        parent = os.path.dirname(path)
        if parent not in self._dir_cache:
            os.makedirs(parent, exist_ok=True)
            self._dir_cache.add(parent)

    @property
    def supports_write_hash(self) -> bool:
        """Fused write+hash available: the scheduler defers manifest digests
        to write time and gets them back from one native call per payload."""
        native = self._native
        return native is not None and native.has_fused_write

    def _blocking_write(
        self, path: str, buf, durable: bool = False, write_io=None
    ) -> None:
        # Write to a temp file and rename: atomic (readers never see partial
        # payloads) and breaks hard links instead of truncating a shared
        # inode (incremental snapshots hard-link unchanged payloads into new
        # snapshot dirs — an in-place rewrite would corrupt the base).
        # ``durable`` additionally fsyncs the bytes BEFORE the rename and
        # the parent directory AFTER it: a crash mid-commit can then never
        # leave a name pointing at torn content, nor a rename the journal
        # forgot — the contract the ``.snapshot_metadata`` marker needs,
        # since its existence alone means "committed".
        from .. import phase_stats

        from ..io_types import ScatterBuffer

        self._prepare_parent(path)
        # Unique per call, not just per process: two concurrent writers of
        # the SAME path in one process are legal (CAS chunk writers racing
        # identical content-defined chunks from different payloads), and a
        # shared tmp name would let one writer's rename/cleanup steal the
        # other's in-progress file (observed as FileNotFoundError at
        # os.replace).  Each writer renames its own tmp; last-rename-wins
        # is safe because same-path writes carry identical bytes.
        tmp = f"{path}.tmp.{os.getpid()}.{next(_TMP_SEQ)}"
        scatter = isinstance(buf, ScatterBuffer)
        nbytes = buf.nbytes if scatter else memoryview(buf).nbytes
        fused = (
            write_io is not None
            and getattr(write_io, "want_part_hashes", False)
            and self._native is not None
            and self._native.has_fused_write
        )
        phase = "native_write_hash" if fused else "fs_write"
        try:
            with phase_stats.timed(phase, nbytes):
                if fused:
                    # ONE native call: every part lands while its digest is
                    # computed from the same cache-resident bytes on the
                    # native worker pool — the off-GIL data plane that
                    # replaces the separate Python-level checksum + write
                    # passes.  Small payloads with in-flight siblings
                    # (batch_hint) coalesce further: the micro-batcher
                    # groups them into one write_parts_hash_batch call.
                    parts = buf.parts if scatter else [buf]
                    if (
                        self._write_batcher is not None
                        and getattr(write_io, "batch_hint", False)
                        and nbytes <= _BATCH_MAX_MEMBER_BYTES
                    ):
                        write_io.part_hash64 = self._write_batcher.write(
                            tmp, parts
                        )
                    else:
                        write_io.part_hash64 = self._native.write_parts_hash(
                            tmp, parts
                        )
                elif scatter:
                    # Slab members land sequentially with no pack memcpy.
                    if self._native is not None:
                        self._native.write_file_parts(tmp, buf.parts)
                    else:
                        with open(tmp, "wb") as f:
                            for part in buf.parts:
                                f.write(part)
                elif self._native is not None:
                    self._native.write_file(tmp, buf)
                else:
                    with open(tmp, "wb") as f:
                        f.write(buf)
                if durable:
                    fd = os.open(tmp, os.O_RDONLY)
                    try:
                        os.fsync(fd)
                    finally:
                        os.close(fd)
                os.replace(tmp, path)
                if durable:
                    dfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
                    try:
                        os.fsync(dfd)
                    finally:
                        os.close(dfd)
            if self._direct_io and self._native is not None:
                # One-time native.degraded event if this write (or an
                # earlier one) forced the buffered fallback rung.
                self._native.check_direct_io_degrade()
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def _blocking_read(
        self, path: str, byte_range, into=None, want_hash=False, hash_algo=None
    ):
        import time

        from .. import phase_stats

        begin = time.monotonic()
        result, hash64, phase = self._read_impl(
            path, byte_range, into, want_hash, hash_algo
        )
        phase_stats.add(
            phase, time.monotonic() - begin, memoryview(result).nbytes
        )
        return result, hash64

    def _native_ranges(self, path: str, byte_range, view, want_hash: bool):
        """The native multi-range read path (``native_read`` phase): the
        range lands via parallel pread tasks on the C++ worker pool — one
        call replaces the per-chunk Python loop.  With ``want_hash`` the
        per-stripe digests are fused with the reads (the "xxh64s"
        verify-while-reading path)."""
        from .. import phase_stats

        offset = byte_range[0] if byte_range is not None else 0
        # _blocking_read records the phase once _read_impl has said which
        # it was; here the name is known from the start.
        with phase_stats.annotation("native_read"):
            hashes = self._native.read_ranges_into(
                path,
                [(offset, offset + view.nbytes)],
                [view],
                want_hash=want_hash,
            )
        return hashes[0] if hashes else None

    def _read_impl(self, path: str, byte_range, into, want_hash, hash_algo):
        """Returns (buffer, digest-or-None, phase_stats phase name).

        The digest comes from the fused C read (each block hashed cache-hot
        right after its pread) — one memory pass for read+verify instead of
        two.  Only reads whose issuer asked (ReadIO.want_hash: the consumer
        will verify the whole payload) pay for it, and the issuer's
        ``hash_algo`` decides the shape: "xxh64s" (striped) payloads read
        AND verify in parallel on the native pool; plain "xxh64" streams
        are order-dependent and stay sequential.  The sequential native
        reads (``fs_read``) are annotated here as ``_native_ranges``
        annotates ``native_read``: ``_blocking_read`` records the interval
        once this has said which phase it was."""
        from .. import integrity, phase_stats

        want_hash = want_hash and integrity.checksums_enabled()
        striped = want_hash and hash_algo == "xxh64s"
        if into is not None:
            # Read-into-place: bytes land in the restore target's own
            # memory — no allocation, and the consumer skips its copy.
            if self._native is not None:
                view = memoryview(into).cast("B")
                if striped and self._native.has_ranged_read:
                    # Parallel fused read+verify: stripes pread and hash
                    # concurrently, digest combined natively — the large
                    # checksummed restore no longer chooses between
                    # parallelism and verification.
                    hash64 = self._native_ranges(
                        path, byte_range, view, want_hash=True
                    )
                    return into, hash64, "native_read"
                if view.nbytes >= _PARALLEL_READ_MIN_BYTES and self._use_parallel(
                    want_hash
                ):
                    parallel_ways = self._parallel_ways(view.nbytes)
                    if parallel_ways > 1:
                        phase = self._timed_parallel(
                            path, byte_range, view, parallel_ways
                        )
                        return into, None, phase
                if want_hash and not striped:
                    # One memory pass for read+verify — preferred for plain-
                    # digest payloads (a parallel read would need a second
                    # full hash pass; the xxh64 stream is order-dependent).
                    # A striped request that reaches here (ranged-read
                    # symbol missing) must NOT return a plain digest the
                    # consumer would compare against an xxh64s value —
                    # read unhashed and let verify() do its own pass.
                    with phase_stats.annotation("fs_read"):
                        hash64 = self._native.read_file_into(
                            path, byte_range, into, want_hash=True
                        )
                    return into, hash64, "fs_read"
                with phase_stats.annotation("fs_read"):
                    self._timed_sequential(
                        path,
                        byte_range,
                        into,
                        record=view.nbytes >= _PARALLEL_READ_MIN_BYTES,
                    )
                return into, None, "fs_read"
            with open(path, "rb") as f:
                if byte_range is not None:
                    f.seek(byte_range[0])
                view = memoryview(into).cast("B")
                filled = 0
                while filled < view.nbytes:
                    n = f.readinto(view[filled:])
                    if not n:
                        # A silent short read would leave stale bytes in
                        # the restore target (and the checksum verify may
                        # be degraded on a native-less build).
                        raise OSError(
                            f"short read from {path}: got {filled} of "
                            f"{view.nbytes} bytes"
                        )
                    filled += n
            return into, None, "fs_read"
        if self._native is not None:
            if striped and self._native.has_ranged_read:
                if byte_range is None:
                    size = os.path.getsize(path)
                    byte_range = [0, size]
                out = bytearray(byte_range[1] - byte_range[0])
                hash64 = None
                if len(out):
                    hash64 = self._native_ranges(
                        path, byte_range, memoryview(out), want_hash=True
                    )
                return out, hash64, "native_read"
            with phase_stats.annotation("fs_read"):
                buf, hash64 = self._native.read_file(
                    # Same algo guard as the into-path: never hand back a
                    # plain digest for an xxh64s consumer.
                    path, byte_range, want_hash=want_hash and not striped
                )
            return buf, hash64, "fs_read"
        with open(path, "rb") as f:
            if byte_range is None:
                return bytearray(f.read()), None, "fs_read"
            offset, end = byte_range
            f.seek(offset)
            return bytearray(f.read(end - offset)), None, "fs_read"

    def _use_parallel(self, want_hash: bool) -> bool:
        """Strategy for a large into-read: pinned env var wins outright;
        plain-checksummed reads stay sequential (the fused read+hash is one
        memory pass — parallel would need a second full hash pass; striped
        "xxh64s" reads never reach here, they have their own parallel fused
        path); otherwise the first two qualifying reads A/B-measure and the
        winner sticks."""
        from .. import knobs

        pinned = knobs.get_parallel_read_ways()
        if pinned is not None:
            return pinned > 1
        if want_hash:
            return False
        with self._adaptive_lock:
            if self._seq_gbps is None:
                return False  # first qualifying read measures sequential
            if self._par_gbps is None:
                return True  # second measures parallel
            # Periodically re-measure the losing strategy: a single early
            # sample can be distorted (cold vs warm cache, pool contention)
            # and must not lock in the wrong pick for the plugin's lifetime.
            self._reads_since_probe += 1
            if self._reads_since_probe >= _ADAPTIVE_REPROBE_EVERY:
                self._reads_since_probe = 0
                if self._par_gbps > self._seq_gbps:
                    self._seq_gbps = None  # next qualifying read re-measures
                    return False
                self._par_gbps = None
                return True
            return self._par_gbps > self._seq_gbps

    def _parallel_ways(self, total: int) -> int:
        from .. import knobs

        pinned = knobs.get_parallel_read_ways()
        return min(
            pinned if pinned is not None else _PARALLEL_READ_MAX_WAYS,
            _PARALLEL_READ_MAX_WAYS,
            max(2, total // _PARALLEL_READ_CHUNK),
        )

    def _timed_sequential(self, path: str, byte_range, into, record: bool) -> None:
        import time

        begin = time.monotonic()
        self._native.read_file_into(path, byte_range, into, want_hash=False)
        if record:
            elapsed = max(time.monotonic() - begin, 1e-6)
            with self._adaptive_lock:
                if self._seq_gbps is None:
                    self._seq_gbps = memoryview(into).nbytes / 1e9 / elapsed

    def _timed_parallel(self, path: str, byte_range, view, ways: int) -> str:
        import time

        begin = time.monotonic()
        phase = self._parallel_read_into(path, byte_range, view, ways)
        elapsed = max(time.monotonic() - begin, 1e-6)
        with self._adaptive_lock:
            if self._par_gbps is None:
                self._par_gbps = view.nbytes / 1e9 / elapsed
        return phase

    def _parallel_read_into(self, path: str, byte_range, view, n_chunks: int) -> str:
        """Parallel unhashed into-read; returns the phase it ran under.
        Prefers ONE native multi-range call (pread tasks on the C++ pool —
        no per-chunk Python dispatch); the thread-pool chunk loop remains
        as the degraded-library fallback."""
        if byte_range is not None:
            expected = byte_range[1] - byte_range[0]
            if view.nbytes != expected:
                # Same contract the sequential native path enforces: never
                # silently read past the requested range into the target.
                raise ValueError(
                    f"into-view is {view.nbytes} bytes, range is {expected}"
                )
        if self._native.has_ranged_read:
            self._native_ranges(path, byte_range, view, want_hash=False)
            return "native_read"
        base = byte_range[0] if byte_range is not None else 0
        total = view.nbytes
        chunk = -(-total // n_chunks)
        futures = []
        offset = 0
        while offset < total:
            length = min(chunk, total - offset)
            futures.append(
                self._get_chunk_executor().submit(
                    self._native.read_file_into,
                    path,
                    [base + offset, base + offset + length],
                    view[offset : offset + length],
                )
            )
            offset += length
        for fut in futures:
            fut.result()
        return "fs_read"

    async def write(self, write_io: WriteIO) -> None:
        path = os.path.join(self.root, write_io.path)
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(
            self._get_executor(),
            self._blocking_write,
            path,
            write_io.buf,
            getattr(write_io, "durable", False),
            write_io,
        )

    async def read(self, read_io: ReadIO) -> None:
        await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), self.read_blocking, read_io
        )

    def read_executor(self) -> ThreadPoolExecutor:
        """The plugin's own workers, on which ``read`` runs ``read_blocking``.
        A read pipeline that has the range of a read in hand runs
        ``read_blocking`` there itself (``scheduler._DirectReads``): its
        workers hand the io slots on, and no read is a task."""
        return self._get_executor()

    def read_blocking(self, read_io: ReadIO) -> None:
        """``read`` on the calling thread, one of ``read_executor``'s."""
        read_io.buf, read_io.hash64 = self._blocking_read(
            os.path.join(self.root, read_io.path),
            read_io.byte_range,
            read_io.into,
            read_io.want_hash,
            getattr(read_io, "hash_algo", None),
        )

    async def copy_from_sibling(self, src_root: str, path: str) -> bool:
        # Hard link: zero-copy dedup; the new snapshot dir stays
        # self-contained (links are real directory entries) and pruning the
        # base is safe (the payload survives via its remaining link).
        def _link() -> bool:
            src = os.path.join(src_root, path)
            dst = os.path.join(self.root, path)
            try:
                os.makedirs(os.path.dirname(dst), exist_ok=True)
                if os.path.exists(dst):
                    os.unlink(dst)
                os.link(src, dst)
                return True
            except OSError:
                return False

        # Off the event loop: on NFS/Lustre each link is network round-trips,
        # and an incremental save may issue thousands.
        return await asyncio.get_running_loop().run_in_executor(
            self._get_executor(), _link
        )

    async def list_dir(self, path: str) -> list:
        try:
            return sorted(os.listdir(os.path.join(self.root, path)))
        except FileNotFoundError:
            return []

    async def exists(self, path: str) -> bool:
        # os.stat, not os.path.exists: permission/transport errors must
        # propagate — classifying an unreadable committed snapshot as torn
        # would let retention prune valid restore points.
        try:
            os.stat(os.path.join(self.root, path))
            return True
        except (FileNotFoundError, NotADirectoryError):
            return False

    async def delete(self, path: str) -> None:
        os.unlink(os.path.join(self.root, path))

    async def delete_dir(self, path: str) -> None:
        import shutil

        shutil.rmtree(os.path.join(self.root, path), ignore_errors=True)

    async def close(self) -> None:
        if self._executor is not None:
            self._executor.shutdown()
            self._executor = None
        self._chunk_executor.shutdown()
