"""Memory-budgeted execution pipelines for write/read requests.

TPU-native analogue of the reference's ``torchsnapshot/scheduler.py``
(/root/reference/torchsnapshot/scheduler.py:222-463) — the performance core.

Write path: each request moves ready_for_staging → staging → ready_for_io →
io.  Staging (HBM→host DMA + serialization + optional chunk compression,
compression.py) is admitted while its declared cost fits the remaining
memory budget, with an always-admit-one starvation guard (reference
scheduler.py:266-277).  The budget is debited by staging cost — for
compressed payloads max(compressed, uncompressed), i.e. the uncompressed
bound, since the frame never exceeds it beyond the 16-byte header —
re-credited down to the actual buffer size once staged (which is where a
good compression ratio hands budget back to waiting stagers), and fully
re-credited after the write lands (reference scheduler.py:303-320).
Compression runs inside ``stage_buffer`` on this pipeline's worker pool
(the executor below): the C codecs release the GIL, so one payload's
compress pass overlaps other payloads' D2H DMAs and in-flight storage
writes.  Storage
I/O concurrency is capped (16 by default, knobs).  ``execute_write_reqs``
returns a :class:`PendingIOWork` as soon as **staging** is complete — the
async-snapshot early-return point (reference scheduler.py:332-339): training
may resume (and donate/overwrite device buffers) because all bytes are in
host memory.

Read path mirrors it: io → consuming, with budget-gated read admission
(reference scheduler.py:386-447), over ordered groups of requests: a restore
hands in one group a stateful and loads them on its own thread while the
pipeline, on a thread of its own (``ReadAhead``), reads the next group ahead
and consumes nothing ahead (``execute_read_reqs``).

Unlike the reference we never monkey-patch a nested event loop
(asyncio_utils.py:13-153): pipelines run on a dedicated loop owned by the
caller thread (a restore's read pipeline: by the thread ``ReadAhead``
starts), and ``PendingIOWork.sync_complete`` may be driven from a
background thread (no collectives there — store-based barriers only).
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import logging
import socket
import threading
import time
from collections import deque
from concurrent.futures import Executor, ThreadPoolExecutor
from typing import Awaitable, Callable, Dict, List, Optional, Sequence, Tuple

import psutil

from . import knobs, phase_stats, preemption, retry as retry_policy
from .event import Event
from .event_handlers import log_event
from .telemetry import metrics as tmetrics
from .telemetry import monitor as tmonitor
from .telemetry import trace as ttrace
from .io_types import (
    BufferConsumer,
    ReadIO,
    ReadReq,
    ScatterBuffer,
    StoragePlugin,
    WriteIO,
    WriteReq,
)
from .pg_wrapper import PGWrapper

logger = logging.getLogger(__name__)

_MAX_PER_RANK_MEMORY_BUDGET_BYTES = 32 * 1024 * 1024 * 1024
_AVAILABLE_MEMORY_MULTIPLIER = 0.6
_NUM_EXECUTOR_THREADS = 4
# Ceiling of the compression-aware automatic executor sizing below.
_MAX_EXECUTOR_THREADS = 16

# Requests handed to the write/read pipelines this process, by verb — the
# observable the streaming-delta acceptance rests on: an unchanged leaf
# must cost ZERO pipeline requests (it was resolved to a manifest
# reference before dispatch), which this counter proves without scraping
# metrics.  Monotonic; tests snapshot-and-diff around an operation.
# Lock-guarded: pipelines run on per-op background threads, and a bare
# `+=` read-modify-write could lose an increment under concurrent ops —
# a counter that exists to PROVE an invariant must not under-count.
_DISPATCHED_REQUESTS = {"write": 0, "read": 0}
_DISPATCH_LOCK = threading.Lock()


def _count_dispatched(verb: str, n: int) -> None:
    with _DISPATCH_LOCK:
        _DISPATCHED_REQUESTS[verb] += n


def dispatched_requests(verb: str) -> int:
    """Total requests the ``verb`` pipeline has been asked to execute in
    this process (monotonic)."""
    with _DISPATCH_LOCK:
        return _DISPATCHED_REQUESTS[verb]


def _staging_executor_workers() -> int:
    """Size of the WRITE pipeline's staging executor.

    ``TPUSNAP_STAGING_THREADS`` pins it; the automatic default is 4 —
    except when the resolved compression codec is real, where it widens to
    min(16, cores): compressed saves are staging-executor-bound (ROADMAP
    4b — the codecs release the GIL, so every extra thread is extra encode
    bandwidth), while raw saves are storage-bound and extra threads only
    add wakeup contention."""
    override = knobs.get_staging_threads()
    if override > 0:
        return override
    codec, _ = knobs.get_compression()
    if codec != "raw":
        from . import compression

        if compression.resolve(codec) != "raw":
            return _wide_executor_workers()
    return _NUM_EXECUTOR_THREADS


def _wide_executor_workers() -> int:
    import os

    return max(
        _NUM_EXECUTOR_THREADS,
        min(_MAX_EXECUTOR_THREADS, os.cpu_count() or _NUM_EXECUTOR_THREADS),
    )


def _read_executor_workers(read_reqs: List[ReadReq]) -> int:
    """The read pipeline's executor keys off the WORKLOAD, not the
    save-side compression knob: a restore-only process (knob unset)
    pulling a compressed snapshot is exactly the decode-bound case that
    needs the wide pool, and a knob-carrying process restoring a raw
    snapshot is not.  Framed payloads are visible on their consumers (the
    codec rides the read request); ``TPUSNAP_STAGING_THREADS`` still
    pins."""
    override = knobs.get_staging_threads()
    if override > 0:
        return override
    if any(
        getattr(rr.buffer_consumer, "_codec", None) is not None
        for rr in read_reqs
    ):
        return _wide_executor_workers()
    return _NUM_EXECUTOR_THREADS


class _PhaseInheritingExecutor(ThreadPoolExecutor):
    """ThreadPoolExecutor whose workers inherit the submitter's phase tag.

    Pool callbacks that run phase work WITHOUT their own phase_stats
    timer (codec encode closures, consume callbacks, plugin helpers)
    would sample as ``<untagged>`` in the continuous profiler even
    though the submitting coroutine knows exactly which phase they
    belong to.  ``submit`` captures the submitter's innermost phase (or
    its op-driver tag) and wraps the callable in a ``tagged`` scope —
    pure attribution, no time recorded, so phase_stats walls are
    unchanged."""

    def submit(self, fn, /, *args, **kwargs):
        tag = phase_stats.current_phase()
        if tag is None:
            tag = phase_stats.thread_phases().get(threading.get_ident())
        if tag is None:
            return super().submit(fn, *args, **kwargs)

        def _run_tagged():
            with phase_stats.tagged(tag):
                return fn(*args, **kwargs)

        return super().submit(_run_tagged)


def get_local_world_size(pg: PGWrapper) -> int:
    """Number of ranks on this host (reference scheduler.py:35-44) — reduced
    at rank 0 to a {hostname: count} dict and broadcast, O(world) store ops
    where the reference's hostname all-gather is O(world²) GETs."""
    from collections import Counter

    hostname = socket.gethostname()
    counts = pg.all_reduce_object(hostname, Counter)
    return counts[hostname]


def get_process_memory_budget_bytes(pg: PGWrapper) -> int:
    """min(60% of available RAM / local ranks, 32 GB), env-overridable
    (reference scheduler.py:47-67)."""
    override = knobs.get_per_rank_memory_budget_bytes_override()
    if override is not None:
        logger.info("Manually set process memory budget to %d bytes", override)
        return override
    available = psutil.virtual_memory().available
    local_world_size = get_local_world_size(pg)
    budget = int(available * _AVAILABLE_MEMORY_MULTIPLIER) // local_world_size
    budget = min(budget, _MAX_PER_RANK_MEMORY_BUDGET_BYTES)
    logger.debug("Process memory budget: %d bytes", budget)
    return budget


class _WritePipeline:
    """One write request's state through the pipeline (reference
    scheduler.py:70-97)."""

    def __init__(self, write_req: WriteReq, storage: StoragePlugin) -> None:
        self.write_req = write_req
        self.storage = storage
        self.staging_cost = write_req.buffer_stager.get_staging_cost_bytes()
        self.buf: Optional[object] = None
        self.buf_sz_bytes = 0
        self._io_credited = False
        self._digests_done = False
        # Set at io-dispatch time (on_staged): whether other write work is
        # in flight or queued — the signal plugins use to micro-batch
        # small fused writes (WriteIO.batch_hint).
        self.batch_hint = False

    def release_after_io(self, budget: "_BudgetTracker") -> None:
        """Release the staged buffer and credit its bytes, exactly once.

        Idempotent because it runs from two places that can both fire: the
        io coroutine's ``finally``, and pipeline teardown — where an io task
        cancelled before its first event-loop step never executes its
        coroutine body (so the ``finally`` is skipped entirely)."""
        if not self._io_credited:
            self._io_credited = True
            self.buf = None
            budget.remaining += self.buf_sz_bytes

    async def stage_buffer(self, executor: Optional[Executor]) -> "_WritePipeline":
        self.buf = await self.write_req.buffer_stager.stage_buffer(executor)
        self.buf_sz_bytes = _buf_nbytes(self.buf)
        return self

    def _hash_sinks(self) -> Optional[list]:
        """Per-part digest callbacks the stager deferred to write time
        (io_preparers set these instead of hashing during staging), or
        None when digests were already resolved / recording is off."""
        return getattr(self.write_req.buffer_stager, "hash_sinks", None)

    def _parts(self) -> list:
        buf = self.buf
        return buf.parts if isinstance(buf, ScatterBuffer) else [buf]

    def _aligned_parts(self, sinks: list) -> list:
        parts = self._parts()
        if len(parts) != len(sinks):
            raise RuntimeError(
                f"{self.write_req.path}: {len(sinks)} digest sinks for "
                f"{len(parts)} buffer parts — stager/batcher mismatch"
            )
        return parts

    async def ensure_digests(self, executor: Optional[Executor]) -> None:
        """Resolve deferred manifest digests for storages WITHOUT fused
        write+hash: one hash pass over the staged parts, off the event loop
        (the hashers release the GIL), before the write is issued.  Parts
        hash concurrently across the executor — the per-member overlap the
        stage-time compute_on path had.  The fused path skips this — the
        plugin returns the digests from the write itself (write_buffer).
        Manifests are identical either way: the digest policy is
        size-only."""
        sinks = self._hash_sinks()
        if not sinks or self._digests_done:
            return
        if getattr(self.storage, "supports_write_hash", False):
            return  # fused at write time
        from . import integrity

        parts = self._aligned_parts(sinks)
        if executor is not None and self.buf_sz_bytes >= 1 << 20:
            loop = asyncio.get_running_loop()
            digests = await asyncio.gather(
                *(loop.run_in_executor(executor, integrity.digest, p) for p in parts)
            )
        else:
            digests = [integrity.digest(p) for p in parts]
        for sink, d in zip(sinks, digests):
            sink(d)
        self._digests_done = True

    async def write_buffer(self) -> "_WritePipeline":
        assert self.buf is not None
        sinks = self._hash_sinks()
        write_io = WriteIO(
            path=self.write_req.path, buf=self.buf, batch_hint=self.batch_hint
        )
        fused = (
            bool(sinks)
            and not self._digests_done
            and getattr(self.storage, "supports_write_hash", False)
        )
        if fused:
            parts = self._aligned_parts(sinks)
            sizes = [memoryview(p).nbytes for p in parts]
            write_io.want_part_hashes = True
        await self.storage.write(write_io)
        if fused:
            from . import integrity

            hashes = write_io.part_hash64
            if hashes is not None and len(hashes) == len(sinks):
                for sink, h, n in zip(sinks, hashes, sizes):
                    sink(integrity.format_digest(h, n))
            else:
                # The plugin declined (e.g. degraded mid-run): hash the
                # still-held parts — digests must exist before the commit
                # gathers the manifest.
                for sink, part in zip(sinks, parts):
                    sink(integrity.digest(part))
            self._digests_done = True
        self.buf = None  # release host memory promptly
        return self


def _buf_nbytes(buf: object) -> int:
    if isinstance(buf, ScatterBuffer):
        return buf.nbytes
    if isinstance(buf, memoryview):
        return buf.nbytes
    if isinstance(buf, (bytes, bytearray)):
        return len(buf)
    mv = memoryview(buf)  # type: ignore[arg-type]
    return mv.nbytes


class PendingIOWork:
    """Handle over in-flight storage I/O after staging completed (reference
    scheduler.py:180-219)."""

    def __init__(
        self,
        loop: asyncio.AbstractEventLoop,
        executor: Optional[ThreadPoolExecutor],
        io_tasks: List["asyncio.Task"],
        budget_tracker: "_BudgetTracker",
        bytes_total: int,
        reporter: Optional["_ProgressReporter"] = None,
    ) -> None:
        self._loop = loop
        self._executor = executor
        self._io_tasks = io_tasks
        self._budget_tracker = budget_tracker
        self.bytes_total = bytes_total
        self._reporter = reporter

    def sync_complete(self) -> None:
        from .utils.loops import call_outside_loop

        call_outside_loop(self._sync_complete_impl)

    async def _drain(self) -> None:
        """Await all I/O tasks, surfacing the progress table on its interval
        while writes crawl — this drain runs in the background thread of an
        async snapshot, which is exactly where an operator needs to see a
        stuck rank's pipeline state."""
        reporter = self._reporter
        interval = reporter._interval_s if reporter is not None else 0
        pending = set(self._io_tasks)
        while pending:
            # FIRST_COMPLETED always: the first I/O failure must surface
            # immediately (triggering cancel-and-drain upstream), never
            # after every other in-flight write finishes.
            done, pending = await asyncio.wait(
                pending,
                timeout=interval or None,
                return_when=asyncio.FIRST_COMPLETED,
            )
            for task in done:
                if task.exception() is not None:
                    raise task.exception()
            if reporter is not None:
                reporter.maybe_report(
                    self._budget_tracker, inflight_io=len(pending)
                )

    def _sync_complete_impl(self) -> None:
        begin = time.monotonic()
        try:
            if self._io_tasks:
                # tagged(): profiler attribution only — the drain thread
                # driving async I/O between phases must not sample as
                # <untagged>.  The existing io_drain span records the wall.
                with ttrace.span(
                    "io_drain", cat="scheduler", n_tasks=len(self._io_tasks)
                ), phase_stats.tagged("io_drain_drive"):
                    self._loop.run_until_complete(self._drain())
        except BaseException:
            # First failure propagates; cancel and drain the rest so the loop
            # closes clean and staged host buffers release promptly.
            pending = [t for t in self._io_tasks if not t.done()]
            for t in pending:
                t.cancel()
            if pending:
                self._loop.run_until_complete(
                    asyncio.gather(*pending, return_exceptions=True)
                )
            raise
        finally:
            if self._executor is not None:
                self._executor.shutdown()
            self._loop.close()
            # All I/O drained (or torn down): zero the pipeline gauges so
            # scrapes after the op see an idle scheduler, not the last
            # in-flight values frozen forever.
            tmetrics.record_scheduler_idle("write")
        elapsed = time.monotonic() - begin
        if elapsed > 0 and self.bytes_total:
            logger.debug(
                "Completed pending I/O: %.1f MB in %.2fs (%.1f MB/s)",
                self.bytes_total / 1e6,
                elapsed,
                self.bytes_total / 1e6 / elapsed,
            )


class _BudgetTracker:
    def __init__(self, budget_bytes: int) -> None:
        self.total = budget_bytes
        self.remaining = budget_bytes
        self.inflight = 0

    @property
    def in_use(self) -> int:
        return self.total - self.remaining


class DeferredIOWork:
    """PendingIOWork variant for device-staged async snapshots: the ENTIRE
    write pipeline — D2H staging included — runs at ``sync_complete`` time
    on the async background thread.  Safe because the app state was already
    copied on-device (device_staging.py): the donation-safety contract is
    met by the copies, not by host staging, so nothing here needs to finish
    before ``async_take`` returns."""

    def __init__(
        self,
        write_reqs: List[WriteReq],
        storage: StoragePlugin,
        memory_budget_bytes: int,
        rank: int,
    ) -> None:
        self._write_reqs = write_reqs
        self._storage = storage
        self._memory_budget_bytes = memory_budget_bytes
        self._rank = rank
        self.bytes_total = 0

    def sync_complete(self) -> None:
        pending = sync_execute_write_reqs(
            write_reqs=self._write_reqs,
            storage=self._storage,
            memory_budget_bytes=self._memory_budget_bytes,
            rank=self._rank,
        )
        self._write_reqs = []
        self.bytes_total = pending.bytes_total
        pending.sync_complete()


async def execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    executor: Optional[ThreadPoolExecutor] = None,
) -> PendingIOWork:
    """Stage all buffers under the memory budget, overlapping staging with
    storage I/O; return once staging has fully drained (reference
    scheduler.py:222-339)."""
    loop = asyncio.get_running_loop()
    own_executor = executor is None
    if executor is None:
        executor = _PhaseInheritingExecutor(
            max_workers=_staging_executor_workers()
        )
    _count_dispatched("write", len(write_reqs))

    budget = _BudgetTracker(memory_budget_bytes)
    phases_before = phase_stats.snapshot()
    ready_for_staging: deque[_WritePipeline] = deque(
        sorted(
            (_WritePipeline(wr, storage) for wr in write_reqs),
            key=lambda p: p.staging_cost,
        )
    )
    staging_tasks: set = set()
    staging_pipelines: dict = {}
    io_tasks: set = set()
    io_pipelines: dict = {}
    all_io_tasks: List[asyncio.Task] = []
    # Deadline mode (preemption.py) starts new pipelines at the boosted io
    # width; otherwise the semaphore is registered so an activation landing
    # MID-drain widens it in place — extra permits are released onto this
    # pipeline's own loop, no loop-turn polling needed.
    base_io_cap = knobs.get_max_per_rank_io_concurrency()
    io_cap = preemption.effective_io_cap(base_io_cap)
    io_semaphore = asyncio.Semaphore(io_cap)
    if io_cap == base_io_cap:
        preemption.register_write_semaphore(loop, io_semaphore, base_io_cap)
    staged_bytes = 0
    max_write_retries = knobs.get_io_retries()
    reporter = _ProgressReporter(
        rank=rank, total=len(write_reqs), verb="write", budget=budget
    )
    reporter.debug_refs = {
        # Best-effort snapshots for stall bundles; racing mutation from
        # this loop only costs the bundle section (monitor wraps in
        # try/except).
        "ready_for_staging": lambda: [
            p.write_req.path for p in list(ready_for_staging)
        ],
        "staging": lambda: [
            p.write_req.path for p in list(staging_pipelines.values())
        ],
        "inflight_io": lambda: [
            p.write_req.path
            for t, p in list(io_pipelines.items())
            if not t.done()
        ],
    }

    async def _io(pipeline: _WritePipeline) -> None:
        try:
            # Deferred manifest digests for non-fusing storages resolve
            # HERE — outside the io semaphore, so a hash pass never
            # occupies an I/O slot (fusing storages return digests from
            # the write call itself).
            await pipeline.ensure_digests(executor)
            # Bounded retry of TRANSIENT write failures (shared taxonomy,
            # retry.py): the staged buffer is still held (write_buffer only
            # releases it on success), so a requeue is a pure re-send — a
            # flaky fs/NFS blip or an injected fault no longer aborts the
            # whole pipeline.  Terminal errors and an exhausted budget
            # propagate exactly as before.  The backoff sleeps OUTSIDE the
            # io semaphore so a waiting request isn't blocked by a slot
            # parked in backoff.
            attempt = 0
            while True:
                try:
                    slot_wait_begin = time.monotonic()
                    async with io_semaphore:
                        # Time spent queued for an I/O slot: when this
                        # dominates a save, the limiting resource is the
                        # io_concurrency cap, not the storage itself —
                        # the distinction `analyze` draws.
                        slot_wait_s = time.monotonic() - slot_wait_begin
                        if slot_wait_s > 0.001:
                            phase_stats.add("io_slot_wait", slot_wait_s)
                        await pipeline.write_buffer()
                    break
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001
                    if attempt >= max_write_retries or not (
                        retry_policy.is_transient(e)
                    ):
                        raise
                    attempt += 1
                    tmetrics.record_pipeline_retry("write")
                    log_event(
                        Event(
                            name="scheduler.write_retry",
                            metadata={
                                "path": pipeline.write_req.path,
                                "attempt": attempt,
                                "error": repr(e),
                            },
                        )
                    )
                    logger.warning(
                        "[rank %d] transient write failure for %s "
                        "(attempt %d/%d): %r; retrying",
                        rank,
                        pipeline.write_req.path,
                        attempt,
                        max_write_retries,
                        e,
                    )
                    await asyncio.sleep(retry_policy.backoff_s(attempt))
            reporter.io_done += 1
            reporter.bytes_done += pipeline.buf_sz_bytes
            tmetrics.record_io_bytes("written", pipeline.buf_sz_bytes)
        finally:
            # Credit (and release the buffer) on every outcome — success,
            # storage failure, or cancellation during a pipeline teardown —
            # so the budget is always fully re-credited.
            pipeline.release_after_io(budget)

    def dispatch_staging() -> None:
        # Admit while cost fits; always admit one if nothing is in flight at
        # ANY stage (starvation guard for requests larger than the whole
        # budget, reference scheduler.py:266-277 — which requires staging,
        # ready-for-io and io all empty; admitting whenever staging alone is
        # empty would let N over-budget buffers pile up awaiting slow I/O).
        while ready_for_staging:
            pipeline = ready_for_staging[0]
            if pipeline.staging_cost <= budget.remaining or (
                budget.inflight == 0 and not staging_tasks and not io_tasks
            ):
                ready_for_staging.popleft()
                budget.remaining -= pipeline.staging_cost
                budget.inflight += 1
                task = asyncio.ensure_future(pipeline.stage_buffer(executor))
                staging_tasks.add(task)
                staging_pipelines[task] = pipeline
            else:
                break

    def on_staged(pipeline: _WritePipeline) -> None:
        # Re-credit the delta between declared cost and actual buffer size
        # (reference scheduler.py:303-312); the buffer itself stays debited
        # until its write completes.  Compressed payloads declare their
        # uncompressed bound and stage down to the frame size, so the
        # ratio is returned to the budget here (an incompressible frame's
        # 16-byte header makes the delta fractionally negative — harmless).
        nonlocal staged_bytes
        budget.remaining += pipeline.staging_cost - pipeline.buf_sz_bytes
        budget.inflight -= 1
        staged_bytes += pipeline.buf_sz_bytes
        reporter.staged += 1
        reporter.bytes_staged += pipeline.buf_sz_bytes
        # Anything else in flight or still queued means more writes will
        # reach the plugin around the same time — worth a micro-batch
        # gather window there.  A lone write keeps batch_hint False and
        # never waits on the gate.
        pipeline.batch_hint = bool(
            io_tasks or staging_tasks or ready_for_staging
        )
        io_task = asyncio.ensure_future(_io(pipeline))
        io_tasks.add(io_task)
        all_io_tasks.append(io_task)
        io_pipelines[io_task] = pipeline
        io_task.add_done_callback(io_tasks.discard)

    staging_span = ttrace.span(
        "write_staging", cat="scheduler", n_reqs=len(write_reqs)
    )
    staging_span.__enter__()
    try:
        dispatch_staging()
        # Loop until staging fully drains.  With the io-aware starvation
        # guard, staging_tasks can be empty while over-budget requests wait
        # for in-flight writes to free budget — keep waiting on io_tasks.
        while staging_tasks or ready_for_staging:
            # `budget_wait` phase: the memory budget is the BINDING
            # constraint this turn — the queue head is inadmissible
            # (dispatch_staging already admitted everything that fits)
            # while nothing is staging AND io slots sit idle, i.e. a
            # bigger budget would demonstrably add parallelism.  A head
            # merely queued behind saturated storage/staging is NOT
            # budget-bound (that wall belongs to the storage/stage
            # phases, and counting it would make `analyze` blame the
            # budget for every storage-bound save).  Deliberately NOT
            # counted as watchdog progress (monitor excludes it) — a rank
            # parked here behind hung storage is exactly a stall.
            budget_bound = (
                bool(ready_for_staging)
                and not staging_tasks
                and len(io_tasks) < io_cap
            )
            blocked_begin = time.monotonic() if budget_bound else None
            # The timeout lets the progress table fire while a rank is
            # budget-blocked on hung storage — the flagship stuck-rank case
            # would otherwise log nothing (no task ever completes).
            done, _ = await asyncio.wait(
                staging_tasks | io_tasks,
                timeout=reporter._interval_s or None,
                return_when=asyncio.FIRST_COMPLETED,
            )
            if blocked_begin is not None:
                phase_stats.add(
                    "budget_wait", time.monotonic() - blocked_begin
                )
            for task in done:
                if task in staging_pipelines:
                    staging_tasks.discard(task)
                    pipeline = task.result()  # raises on staging failure
                    staging_pipelines.pop(task)
                    on_staged(pipeline)
                elif task.exception() is not None:
                    raise task.exception()  # I/O failure surfaces immediately
            dispatch_staging()
            reporter.maybe_report(
                budget,
                pending=len(ready_for_staging),
                staging=len(staging_tasks),
                inflight_io=len(io_tasks),
            )
    except BaseException:
        import sys

        staging_span.__exit__(*sys.exc_info())
        # Cancel-and-drain every outstanding task before re-raising
        # (reference scheduler.py:299-331 fails clean): no
        # destroyed-pending-task warnings, host buffers released, budget
        # fully re-credited.  I/O tasks self-credit in _io's finally;
        # staging tasks that never reached on_staged are credited here.
        for t in staging_tasks | io_tasks:
            if not t.done():
                t.cancel()
        # Gather ALL io tasks ever created, not just the live set: a sibling
        # failure in the same done-batch was already auto-discarded from
        # io_tasks by its done-callback, and skipping it would leave its
        # exception never-retrieved (asyncio GC noise).
        if staging_tasks or all_io_tasks:
            await asyncio.gather(
                *staging_tasks, *all_io_tasks, return_exceptions=True
            )
        for pipeline in staging_pipelines.values():
            pipeline.buf = None
            budget.remaining += pipeline.staging_cost
            budget.inflight -= 1
        for pipeline in io_pipelines.values():
            # No-op for tasks whose _io finally already ran; credits the ones
            # cancelled before their coroutine body ever started.
            pipeline.release_after_io(budget)
        # On success the returned PendingIOWork owns the executor; on this
        # path it is never constructed, so shut our own executor down too.
        if own_executor:
            executor.shutdown(wait=False)
        # The op is over: zero the pipeline gauges so they don't freeze at
        # their last in-flight values (PendingIOWork handles the success
        # path's zeroing after the drain).
        tmetrics.record_scheduler_idle("write")
        raise

    staging_span.__exit__(None, None, None)
    elapsed = time.monotonic() - reporter._begin
    if staged_bytes and elapsed > 0:
        # End-of-phase throughput line (reference _WriteReporter,
        # scheduler.py:166-173) + per-phase attribution so a slow save
        # points at its dominant phase (d2h / checksum / slab_pack /
        # fs_write) instead of a bare total.
        logger.info(
            "[rank %d] staged %.1f MB in %.2fs (%.1f MB/s), %d/%d writes "
            "landed; phases: %s",
            rank,
            staged_bytes / 1e6,
            elapsed,
            staged_bytes / 1e6 / elapsed,
            reporter.io_done,
            len(write_reqs),
            phase_stats.format_line(phase_stats.delta(phases_before)),
        )
    return PendingIOWork(
        loop=loop,
        executor=executor if own_executor else None,
        io_tasks=all_io_tasks,
        budget_tracker=budget,
        bytes_total=staged_bytes,
        reporter=reporter,
    )


def sync_execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
) -> PendingIOWork:
    """Run the write pipeline on a fresh private event loop; the returned
    PendingIOWork owns the loop and may be completed from another thread
    (reference scheduler.py:342-383).  Safe to call from inside a running
    loop (delegates to a helper thread — utils/loops.py)."""
    from .utils.loops import call_outside_loop

    return call_outside_loop(
        _sync_execute_write_reqs_impl, write_reqs, storage, memory_budget_bytes, rank
    )


def _sync_execute_write_reqs_impl(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
) -> PendingIOWork:
    loop = asyncio.new_event_loop()
    try:
        pending = loop.run_until_complete(
            _run_with_loop(
                loop,
                execute_write_reqs,
                write_reqs,
                storage,
                memory_budget_bytes,
                rank,
            )
        )
    except BaseException:
        loop.close()
        raise
    return pending


async def _run_with_loop(
    loop: asyncio.AbstractEventLoop, fn: Callable[..., Awaitable], *args: object
) -> object:
    return await fn(*args)


class _ReadPipeline:
    """(reference scheduler.py:359-384)"""

    def __init__(
        self, read_req: ReadReq, storage: StoragePlugin, group: int = 0
    ) -> None:
        self.read_req = read_req
        self.storage = storage
        self.group = group
        self.consuming_cost = read_req.buffer_consumer.get_consuming_cost_bytes()
        self.buf: Optional[bytearray] = None
        self.hash64: Optional[int] = None
        self.into: Optional[memoryview] = None  # where the read lands in place
        self.read_began = 0.0  # when storage was asked, its memory acquired
        self.in_hand = 0.0  # when its memory was in hand (the slot hand-over's)
        self.attempt = 0  # transient failures retried (the slot hand-over's)

    async def take_memory(self) -> None:
        """The read is being dispatched: only now is the memory it lands in
        taken (a restored leaf's range of the restore's host arena lives
        from here to its landing; ``acquire`` holds the read while the arena
        has no room, until a landing has freed some).  Before the io slot,
        in dispatch order: ``h2d.py``'s liveness condition 1.  The same
        memory on a retry."""
        if self.read_req.into is not None:
            self.into = await self.read_req.into.acquire()

    def take_memory_soon(
        self, loop: asyncio.AbstractEventLoop, then: Callable[[], None]
    ) -> bool:
        """``take_memory`` for the slot hand-over: True where the memory is in
        hand now; otherwise False, and ``then()`` is called on ``loop``'s
        thread once it is (the pool's grant, one callback)."""
        if self.read_req.into is None:
            return True

        def granted(view: memoryview) -> None:
            self.into = view
            then()

        self.into = self.read_req.into.acquire_soon(loop, granted)
        return self.into is not None

    def stamp(self, stamp: str) -> None:
        """A hand-over of this read (``read_began``, ``read_back``,
        ``consume_began``), for the record of its leaf's turn through the
        restore's host arena (``h2d.HostBufferPool``): told
        through the handle the read lands by, nothing for a read with none."""
        if self.read_req.into is not None:
            self.read_req.into.stamp(stamp)

    async def read_buffer(self) -> "_ReadPipeline":
        read_io = self._read_io()
        await self.storage.read(read_io)
        self._landed(read_io)
        return self

    def read_blocking(self, read: Callable[[ReadIO], None]) -> None:
        """``read_buffer`` on the calling thread, through the plugin's
        ``read_blocking``: a worker of the slot hand-over."""
        read_io = self._read_io()
        read(read_io)
        self._landed(read_io)

    def _read_io(self) -> ReadIO:
        consumer = self.read_req.buffer_consumer
        self.read_began = time.monotonic()
        self.stamp("read_began")
        return ReadIO(
            path=self.read_req.path,
            byte_range=(
                list(self.read_req.byte_range)
                if self.read_req.byte_range is not None
                else None
            ),
            into=self.into,
            # Ask for a read-fused digest only when this consumer will
            # actually verify the whole payload against one — merged
            # spanning reads (composite consumers) and digest-less entries
            # must not pay for hashing nobody uses.
            want_hash=getattr(consumer, "accepts_hash64", False)
            and getattr(consumer, "wants_read_hash", True),
            # The recorded digest's algo: a fusing plugin must compute the
            # digest the consumer will compare against, and "xxh64s" lets
            # it read+hash stripes in parallel.
            hash_algo=getattr(consumer, "hash_algo", None),
        )

    def _landed(self, read_io: ReadIO) -> None:
        self.buf = read_io.buf
        self.hash64 = read_io.hash64
        self.into = None

    def _hand_digest(self) -> BufferConsumer:
        consumer = self.read_req.buffer_consumer
        if self.hash64 is not None and getattr(consumer, "accepts_hash64", False):
            # The plugin hashed exactly the bytes of this request fused with
            # the read; a leaf consumer (1 request : 1 payload) verifies
            # against it without a second pass.  Composite consumers (merged
            # spanning reads) never opt in — their sub-payloads are slices.
            consumer.precomputed_hash64 = self.hash64
        return consumer

    def consume_landed(self) -> bool:
        """Consume the read here and now, on the calling (the loop's) thread,
        if its consumer has a ``consume_landed`` and that says nothing is
        left to compute (the bytes landed in the view the read was given and
        the digest came with the read, or there is none to check): True, and
        the consumer is done, its leaf submitted if this was its last piece.
        False, and nothing was done: ``consume_buffer`` is the way.  A digest
        that does not match raises ``ChecksumError`` from here."""
        assert self.buf is not None
        landed = getattr(self._hand_digest(), "consume_landed", None)
        if landed is None or not landed(self.buf):
            return False
        self.buf = None
        return True

    async def consume_buffer(self, executor: Optional[Executor]) -> "_ReadPipeline":
        assert self.buf is not None
        await self._hand_digest().consume_buffer(self.buf, executor)
        self.buf = None
        return self


# What a read on the slot hand-over comes back as: the read, its error or
# None, and when it began and ended.
_Completion = Tuple[_ReadPipeline, Optional[BaseException], float, float]


class _DirectReads:
    """The io slots of a read pipeline whose storage plugin reads blocking
    (``read_blocking`` and ``read_executor``, as ``storage_plugins/fs``
    offers): a read with its memory in hand runs on the plugin's own workers
    and is never a task.

    ``start``, on the loop's thread, takes a free slot and submits one job to
    the plugin's executor, or queues the read (first in, first out).  A job
    runs its read and posts the completion, then, under the same lock, takes
    the next queued read and runs it on the same thread, or frees its slot:
    the loop thread never hands a slot on, and at most ``io_cap`` reads run
    at once.  A completion goes on a list, and the loop is woken (``wake``,
    through ``call_soon_threadsafe``) only where no wake-up is pending
    already: one flag under the list's lock, which ``take`` clears as it
    takes every completion off.  ``close`` drops the queue and returns
    once no read runs, so no range is given back while a worker still writes
    into it.

    A read's ``io_slot_wait`` is the time from its memory in hand to its
    start, recorded by the worker that starts it when it is 1 ms or more;
    ``wakes`` counts the wake-ups posted; ``cpu_s`` is the CPU seconds the
    jobs spent, one ``time.thread_time`` delta a job (the reads it ran),
    added under the lock with the job's last completion, so that every job
    whose completions have all been taken off is in it."""

    def __init__(
        self,
        storage: StoragePlugin,
        io_cap: int,
        loop: asyncio.AbstractEventLoop,
        wake: Callable[[], None],
    ) -> None:
        self._read: Callable[[ReadIO], None] = storage.read_blocking  # type: ignore[attr-defined]
        self._executor: Executor = storage.read_executor()  # type: ignore[attr-defined]
        self._loop, self._wake = loop, wake
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._cap = self._free = io_cap
        self._queue: "deque[_ReadPipeline]" = deque()
        self._done: List[_Completion] = []
        self._woken = False  # a wake-up is posted and its completions not taken
        self._closed = False
        self.wakes = 0
        self.cpu_s = 0.0

    def start(self, pipeline: _ReadPipeline) -> None:
        """On the loop's thread: ``pipeline``'s memory is in hand, and its
        read runs once a slot is free."""
        pipeline.in_hand = time.monotonic()
        with self._lock:
            if self._closed:
                return
            if not self._free:
                self._queue.append(pipeline)
                return
            self._free -= 1
        try:
            self._executor.submit(self._run, pipeline)
        except BaseException as e:  # noqa: BLE001 -- the plugin is closed
            self._finished(pipeline, e, pipeline.in_hand, time.monotonic())

    def _run(self, pipeline: Optional[_ReadPipeline]) -> None:
        cpu = time.thread_time()
        while pipeline is not None:
            began = time.monotonic()
            error: Optional[BaseException] = None
            if not self._closed:
                try:
                    waited = began - pipeline.in_hand
                    if waited >= 0.001:
                        phase_stats.add("io_slot_wait", waited, end=began)
                    pipeline.read_blocking(self._read)
                except BaseException as e:  # noqa: BLE001 -- the loop's to raise
                    error = e
            pipeline = self._finished(pipeline, error, began, time.monotonic(), cpu)

    def _finished(
        self,
        pipeline: _ReadPipeline,
        error: Optional[BaseException],
        began: float,
        ended: float,
        cpu: Optional[float] = None,
    ) -> Optional[_ReadPipeline]:
        """Post the completion; return the next queued read, the slot handed
        on with it, or None and the slot freed (and, given ``cpu``, the
        worker's ``thread_time`` at the job's start, the job's CPU seconds
        counted)."""
        with self._lock:
            self._done.append((pipeline, error, began, ended))
            wake = not (self._woken or self._closed)
            if wake:
                self._woken = True
                self.wakes += 1
            following = self._queue.popleft() if self._queue else None
            if following is None:
                self._free += 1
                self._idle.notify_all()
                if cpu is not None:
                    self.cpu_s += time.thread_time() - cpu
        if wake:
            try:
                self._loop.call_soon_threadsafe(self._wake)
            except RuntimeError:  # the loop has closed: nobody waits
                pass
        return following

    def take(self) -> List[_Completion]:
        """On the loop's thread: every completion posted, in the order they
        came; the next one posted wakes the loop again."""
        with self._lock:
            done, self._done = self._done, []
            self._woken = False
        return done

    def close(self) -> None:
        """No read starts from here on; return once none runs."""
        with self._lock:
            self._closed = True
            self._queue.clear()
            while self._free < self._cap:
                self._idle.wait()


class ReadAhead:
    """One read pipeline for a whole restore, on a thread of its own, and
    what it and the thread that loads tell each other.

    The groups are the statefuls' read requests in the order they are
    loaded.  The thread that makes this object (the one that called
    ``restore``) is the loader: for each group k it calls
    ``wait_consumed(k)``, does what only it may do (the H2D drain, after
    which none of k's host buffers is in use, and user code) and then
    ``mark_loaded(k)``.  The pipeline's thread runs ``execute_read_reqs``
    over every group on a loop of its own and is meanwhile reading group
    k+1.  ``close()`` (always, from a ``finally``) cancels what is still in
    flight and joins the thread.

    After ``close()``, ``read_ahead_s`` and ``read_ahead_bytes`` say how
    much was read ahead (see ``_read_ahead``), ``read_loop`` what the
    pipeline's loop did (the counter of that name, ``execute_read_reqs``),
    ``loop_cpu_s`` the CPU seconds of the pipeline's thread from its start
    to its end, and ``fs_cpu_s`` those of the jobs the plug-in's workers ran
    for it (``_DirectReads``; 0 on the task path)."""

    def __init__(
        self,
        read_groups: Sequence[List[ReadReq]],
        storage: StoragePlugin,
        memory_budget_bytes: int,
        rank: int,
    ) -> None:
        self._cond = threading.Condition()
        self._consumed = 0  # groups whose last consume has finished
        self._loaded_at: List[float] = []  # when each group's load returned
        self._error: Optional[BaseException] = None
        self._finished = False
        self._cancelled = False
        self._loop = asyncio.new_event_loop()
        # Of the pipeline, while it runs: its task, for close(), and the
        # event mark_loaded() sets.  Both touched under _cond only, so
        # neither is used once the loop has closed.
        self._task: Optional["asyncio.Task"] = None
        self._wake: Optional[asyncio.Event] = None
        self.read_ahead_s = 0.0
        self.read_ahead_bytes = 0
        self.read_loop: Dict[str, int] = {}
        self.loop_cpu_s = self.fs_cpu_s = 0.0
        # The coroutine, not the groups, goes to the thread: it drops its
        # own reference to the requests once they are queued, so nothing
        # here keeps a loaded group's host buffers alive.
        coro = execute_read_reqs(
            read_groups, storage, memory_budget_bytes, rank, loader=self
        )
        # The loader's phase tag (the op's driver tag), so that executor
        # workers started from the pipeline's thread sample as the op's.
        tag = phase_stats.current_phase() or phase_stats.thread_phases().get(
            threading.get_ident()
        )
        self._thread = threading.Thread(
            # The loader's context: spans opened on the pipeline's thread
            # hang off the span that is open here.
            target=contextvars.copy_context().run,
            args=(self._run, coro, tag),
            name="tpusnap-read-pipeline",
            daemon=True,
        )
        self._thread.start()

    # ------------------------------------------------- the loader's thread

    def wait_consumed(self, group: int) -> None:
        """Block until the last consume of ``group`` has finished; raise
        the pipeline's error if it ended before that."""
        with self._cond:
            while self._consumed <= group and not self._finished:
                self._cond.wait()
            if self._consumed <= group:
                raise self._error or RuntimeError(
                    f"read pipeline ended before group {group} was consumed"
                )

    def mark_loaded(self, group: int) -> None:
        """``group`` is loaded and none of its host buffers is in use: its
        successor may be consumed, and the group after that read."""
        with self._cond:
            assert group == len(self._loaded_at), (group, len(self._loaded_at))
            self._loaded_at.append(time.monotonic())
            if self._wake is not None:
                self._loop.call_soon_threadsafe(self._wake.set)

    def close(self) -> None:
        """Cancel the pipeline if it is still running, and join its thread
        (no-op after a pipeline that ran to its end)."""
        with self._cond:
            self._cancelled = True
            if self._task is not None:
                self._loop.call_soon_threadsafe(self._task.cancel)
        self._thread.join()

    # ----------------------------------------------- the pipeline's thread

    def _run(self, coro: Awaitable[None], tag: Optional[str]) -> None:
        began = time.thread_time()
        try:
            with phase_stats.tagged(tag) if tag else contextlib.nullcontext():
                self._loop.run_until_complete(coro)
        except BaseException as e:  # noqa: BLE001
            # Raised again on the loader's thread (wait_consumed); a
            # cancellation is close()'s own.
            with self._cond:
                self._error = e
        finally:
            with self._cond:
                self._task = self._wake = None
                self._finished = True
                self._cond.notify_all()
            self._loop.close()
            # Read by the loader once close() has joined this thread.
            self.loop_cpu_s = time.thread_time() - began

    def _attach(self) -> asyncio.Event:
        with self._cond:
            if self._cancelled:
                raise asyncio.CancelledError()
            self._task = asyncio.current_task()
            self._wake = asyncio.Event()
            return self._wake

    def _group_consumed(self, consumed: int) -> None:
        with self._cond:
            self._consumed = consumed
            self._cond.notify_all()

    def _loaded(self) -> List[float]:
        with self._cond:
            return list(self._loaded_at)


def _read_ahead(
    reads: List[List[Tuple[float, float, int]]], loaded_at: List[float]
) -> Tuple[float, int]:
    """What was read ahead: of each group's reads ``(begin, end, bytes)``,
    the part that ran before the load of the group before it returned, as
    seconds (the union of the reads' intervals) and bytes (a read still
    running then counts the share of its bytes that its time gives), summed
    over the groups."""
    seconds, nbytes = 0.0, 0.0
    for group, until in zip(reads[1:], loaded_at):
        ahead = [(b, e, n) for b, e, n in group if b < until]
        seconds += phase_stats.union_s([(b, min(e, until)) for b, e, _ in ahead])
        nbytes += sum(
            n if e <= until else n * (until - b) / (e - b) for b, e, n in ahead
        )
    return seconds, int(nbytes)


async def execute_read_reqs(
    read_groups: Sequence[List[ReadReq]],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    loader: Optional[ReadAhead] = None,
) -> None:
    """Budget-gated read → consume pipeline (reference scheduler.py:386-447)
    over ordered groups of requests: one loop, one executor, one memory
    budget and one set of io slots for all of them.

    A group is what one stateful of a restore reads; ``read_object`` and
    the like pass one.  Reads are dispatched in group order.  With a
    ``loader`` (``ReadAhead``: the thread that called ``restore``) the
    pipeline reads ahead of it by one group and consumes ahead of it by
    none:

    - the reads of group k+1 start when the last read of group k has
      finished, so storage is driven through k's tail (its last consumes,
      the loader's H2D drain and ``load_state_dict``).  Reading ahead of
      the loader is safe: a committed snapshot is immutable.  They start
      no sooner, because reads in flight share the storage's rate: read
      beside k, group k+1 only delays k's last read and so its own H2D.
    - no consume of group k+1 (checksum, H2D submit, sharded
      ``device_put``) starts before the loader has loaded group k.  Until
      then k's restore target is alive on the device, and k+1's arrays
      landing beside it would raise the restore's HBM peak.  A read that
      finishes early is parked with its bytes debited from the budget.  The
      restore's one H2D batcher counts on this (``h2d.py``).
    - in a restore a leaf's host memory is a range of the restore's arena
      (``h2d.HostBufferPool``), taken when its first read is dispatched,
      BEFORE the read's io slot and in dispatch order (within a group:
      smallest first).  A read that finds no room is HELD there, its bytes
      debited and no slot taken, until a landing has freed some
      (``host_buffer_wait``).  Why the restore stays live meanwhile, and
      what this loop keeps for it: the head of ``h2d.py``.
    - no read of group k+2 starts before group k is loaded, all of its
      host ranges landed from.
    - a range's turn through that arena is stamped where this loop hands it
      on (``_ReadPipeline.stamp``): ``read_began`` when storage is asked (on
      the slot hand-over, by the worker that starts the read),
      ``read_back`` when this thread takes the finished read off (so a
      finished read that waits for this thread counts with its read), and
      ``consume_began`` when a read parked behind the loader is let go; the
      pool accounts the stages between (the counter ``arena_turn``).

    **What a turn of the loop costs.**  Each read and each consume that is a
    task registers its completion once, when it is made (a done-callback that
    queues the task and sets the one event, the same that the loader's
    ``mark_loaded`` and the reporter's interval set); a turn awaits that
    event and takes the queued completions off in the order they came, so
    what the loop does for one read does not grow with the reads pending
    (``io_tasks``, ``consume_tasks`` and ``pipelines`` are the record of what
    is live, for the failure path).  **A read with nothing left to compute
    is consumed in the turn it is taken off**, or in the turn that lets it go
    if it was parked: where the consumer's ``consume_landed`` says the bytes
    landed in the view the read was given and the digest came with the read
    or none is to be checked (``io_preparers/array.ArrayBufferConsumer``),
    the digests are compared, the leaf's piece counted and, if it was the
    last, the leaf submitted, all on this thread: no task, no executor.
    Everything else (a checksum to hash, a frame to decode, a merged slab
    read, objects, sharded pieces) is consumed as a task as before.

    **Where the plugin reads blocking** (``read_blocking`` and
    ``read_executor``: ``storage_plugins/fs``) **no read is a task**: its
    memory is taken in ``dispatch_io`` (``_ReadPipeline.take_memory_soon``;
    a read that waits for room is handed on by the pool's grant, one
    callback on this loop, that adopts the range), and then it goes to the
    slot hand-over (``_DirectReads``), whose workers, the plugin's own, hand
    the io slots on themselves and post each finished read with one
    coalesced wake-up of this loop.  A transient failure comes back as a
    completion and is started again after its backoff, outside any slot.
    Other plugins (the wrappers, the object stores, ``memory``) have their
    reads run as tasks behind the ``io_semaphore``.

    The counter ``read_loop`` (and ``ReadAhead.read_loop``, the
    ``restore.end`` entry) says what the loop did: ``turns``, completions
    ``taken`` off, reads consumed ``inline``, consumes ``handed`` on as a
    task (``inline + handed`` is the requests), the most reads and consumes
    pending at once (``max_pending``), reads run by the slot hand-over
    (``direct``: the requests, or 0 on the task path), the wake-ups its
    workers posted (``wakes``), ``n`` pipelines.

    With no loader a group counts as loaded once it is consumed.  An error
    in any read or consume cancels everything in flight and is raised."""
    all_reqs = [rr for group in read_groups for rr in group]
    executor = _PhaseInheritingExecutor(
        max_workers=_read_executor_workers(all_reqs)
    )
    _count_dispatched("read", len(all_reqs))
    budget = _BudgetTracker(memory_budget_bytes)
    ready_for_io: List[deque[_ReadPipeline]] = [
        deque(
            sorted(
                (_ReadPipeline(rr, storage, k) for rr in group),
                key=lambda p: p.consuming_cost,
            )
        )
        for k, group in enumerate(read_groups)
    ]
    n_groups = len(ready_for_io)
    n_reqs = len(all_reqs)
    # From here the queues own the requests, and drop each once consumed.
    del read_groups, all_reqs
    unread = [len(queue) for queue in ready_for_io]
    unconsumed = list(unread)
    # (begin, end, bytes) of each finished read, by group: the read-ahead
    # counter's source.
    reads: List[List[Tuple[float, float, int]]] = [[] for _ in range(n_groups)]
    # Read, and waiting for the group before theirs to be loaded.
    parked: List[_ReadPipeline] = []
    consumed = 0  # leading groups whose last consume has finished
    loaded = 0  # leading groups the loader has loaded and released
    io_cap = knobs.get_max_per_rank_io_concurrency()
    io_semaphore = asyncio.Semaphore(io_cap)
    io_tasks: set = set()
    consume_tasks: set = set()
    # task -> pipeline, for re-crediting un-consumed pipelines on failure
    pipelines: dict = {}
    # Dispatched to the slot hand-over and not yet taken off: the same record.
    direct_reads: set = set()
    # The counter ``read_loop``: what this loop did, for every request.
    loop_stats = dict.fromkeys(
        ("turns", "taken", "inline", "handed", "max_pending", "direct", "wakes"), 0
    )
    reporter = _ProgressReporter(
        rank=rank, total=n_reqs, verb="read", budget=budget
    )
    reporter.debug_refs = {
        "ready_for_io": lambda: [
            p.read_req.path for queue in list(ready_for_io) for p in list(queue)
        ],
        "inflight": lambda: [
            p.read_req.path
            for t, p in list(pipelines.items())
            if not t.done()
        ]
        + [p.read_req.path for p in list(direct_reads)],
    }

    max_read_retries = knobs.get_io_retries()

    def retrying(pipeline: _ReadPipeline, e: Exception, attempt: int) -> None:
        tmetrics.record_pipeline_retry("read")
        log_event(
            Event(
                name="scheduler.read_retry",
                metadata={
                    "path": pipeline.read_req.path,
                    "attempt": attempt,
                    "error": repr(e),
                },
            )
        )
        logger.warning(
            "[rank %d] transient read failure for %s "
            "(attempt %d/%d): %r; retrying",
            rank,
            pipeline.read_req.path,
            attempt,
            max_read_retries,
            e,
        )

    async def _read(pipeline: _ReadPipeline) -> _ReadPipeline:
        # Bounded retry of TRANSIENT read failures — the write path's
        # mirror (same TPUSNAP_IO_RETRIES budget, same retry.py
        # classifier/backoff): a 503 burst or flaky-NFS blip mid-restore
        # no longer aborts the whole read pipeline.  read_buffer builds a
        # fresh ReadIO per attempt, so a requeue is a pure re-send; the
        # backoff sleeps OUTSIDE the io semaphore so a parked retry never
        # blocks a healthy read's slot.
        attempt = 0
        while True:
            try:
                await pipeline.take_memory()
                slot_wait = phase_stats.open_interval("io_slot_wait")
                async with io_semaphore:
                    slot_wait.close(min_s=0.001)
                    await pipeline.read_buffer()
                    reads[pipeline.group].append(
                        (
                            pipeline.read_began,
                            time.monotonic(),
                            _buf_nbytes(pipeline.buf),
                        )
                    )
                    return pipeline
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001
                if attempt >= max_read_retries or not (
                    retry_policy.is_transient(e)
                ):
                    raise
                attempt += 1
                retrying(pipeline, e, attempt)
                await asyncio.sleep(retry_policy.backoff_s(attempt))

    def next_for_io() -> Optional["deque[_ReadPipeline]"]:
        # Group order, and a look-ahead of one group: the group being
        # loaded next, and the one after it once the last read of the first
        # has finished.  Not sooner: reads in flight share the storage's
        # rate, so a group read beside the one before it delays that one's
        # last read, with it its load, and with that (nothing is consumed
        # ahead) its own H2D: the two then finish together and leave one
        # tail twice as long where there were two.
        for k in range(loaded, min(loaded + 2, n_groups)):
            if ready_for_io[k]:
                return ready_for_io[k]
            if unread[k]:
                return None
        return None

    # Completions, in the order they came: each task registers ``finished``
    # once, when it is made, and the main loop waits for the one event (the
    # loader's ``mark_loaded`` sets the same one).
    finished_tasks: "deque[asyncio.Task]" = deque()
    wake = loader._attach() if loader is not None else asyncio.Event()
    loop = asyncio.get_running_loop()
    # The plugin that reads blocking has its reads run by the slot hand-over,
    # on its own workers, which wake the loop through the same event.
    direct = (
        _DirectReads(storage, io_cap, loop, wake.set)
        if callable(getattr(storage, "read_blocking", None))
        and callable(getattr(storage, "read_executor", None))
        else None
    )

    def finished(task: "asyncio.Task") -> None:
        finished_tasks.append(task)
        wake.set()

    def start(coro: Awaitable[_ReadPipeline], tasks: set, pipeline: _ReadPipeline) -> None:
        task = asyncio.ensure_future(coro)
        task.add_done_callback(finished)
        tasks.add(task)
        pipelines[task] = pipeline

    def hand(pipeline: _ReadPipeline) -> None:
        # To the slot hand-over, with the read's memory now or once granted.
        loop_stats["direct"] += 1
        direct_reads.add(pipeline)
        if pipeline.take_memory_soon(loop, lambda: direct.start(pipeline)):
            direct.start(pipeline)

    def reading() -> int:
        """Reads dispatched and not yet taken off."""
        return len(io_tasks) + len(direct_reads)

    def dispatch_io() -> None:
        while (queue := next_for_io()) is not None:
            pipeline = queue[0]
            if pipeline.consuming_cost <= budget.remaining or (
                budget.inflight == 0 and not reading() and not consume_tasks
            ):
                queue.popleft()
                budget.remaining -= pipeline.consuming_cost
                budget.inflight += 1
                if direct is None:
                    start(_read(pipeline), io_tasks, pipeline)
                else:
                    hand(pipeline)
            else:
                break
        loop_stats["max_pending"] = max(
            loop_stats["max_pending"], reading() + len(consume_tasks)
        )

    def took_off(pipeline: _ReadPipeline) -> None:
        unread[pipeline.group] -= 1
        # Taken off: until here a finished read waited for this thread, which
        # its range's turn counts with the read.
        pipeline.stamp("read_back")
        if pipeline.group <= loaded:
            consume(pipeline)
        else:
            parked.append(pipeline)

    def take_direct() -> None:
        for pipeline, error, began, ended in direct.take():
            loop_stats["taken"] += 1
            if error is not None:
                if pipeline.attempt >= max_read_retries or not (
                    isinstance(error, Exception) and retry_policy.is_transient(error)
                ):
                    raise error
                # Started again after its backoff, its memory still in hand
                # and no slot held meanwhile.
                pipeline.attempt += 1
                retrying(pipeline, error, pipeline.attempt)
                call_later(
                    retry_policy.backoff_s(pipeline.attempt), direct.start, pipeline
                )
                continue
            direct_reads.discard(pipeline)
            reads[pipeline.group].append((began, ended, _buf_nbytes(pipeline.buf)))
            took_off(pipeline)

    def consumed_one(pipeline: _ReadPipeline) -> None:
        budget.remaining += pipeline.consuming_cost
        budget.inflight -= 1
        unconsumed[pipeline.group] -= 1
        reporter.io_done += 1
        reporter.bytes_done += pipeline.consuming_cost
        tmetrics.record_io_bytes("read", pipeline.consuming_cost)

    def consume(pipeline: _ReadPipeline) -> None:
        """Here and now if the read left nothing to compute (landed in
        place, its digest in hand or none wanted: no task, no executor), as
        a task otherwise."""
        try:
            landed = pipeline.consume_landed()
        except BaseException:
            # In no container the failure path knows: re-credited here.
            pipeline.buf = None
            budget.remaining += pipeline.consuming_cost
            budget.inflight -= 1
            raise
        if landed:
            loop_stats["inline"] += 1
            consumed_one(pipeline)
        else:
            loop_stats["handed"] += 1
            start(pipeline.consume_buffer(executor), consume_tasks, pipeline)

    def note_progress() -> None:
        """Groups consumed are told to the loader; groups it has loaded
        let the parked reads of the next one be consumed."""
        nonlocal consumed, loaded
        while True:
            told, let_go = consumed, loaded
            while consumed < n_groups and unconsumed[consumed] == 0:
                consumed += 1
            if loader is None:
                loaded = consumed
            else:
                if consumed != told:
                    loader._group_consumed(consumed)
                loaded = len(loader._loaded())
            if loaded == let_go or not parked:
                return
            # Only a group newly loaded lets a parked read go, and a read
            # consumed here and now may have been its group's last: again.
            for pipeline in [p for p in parked if p.group <= loaded]:
                parked.remove(pipeline)
                pipeline.stamp("consume_began")  # was parked from read_back to here
                consume(pipeline)

    # read_starved: one interval per stretch in which the pipeline is alive
    # (a consume is pending: running, or parked behind the loader) and no
    # read is in flight, so storage is not being driven.  With groups read
    # ahead it opens only where the next group has nothing left to read;
    # the tail after the last read is always one.
    starved: Optional[phase_stats.open_interval] = None

    def track_starved() -> None:
        nonlocal starved
        if (consume_tasks or parked) and not reading():
            if starved is None:
                starved = phase_stats.open_interval("read_starved")
        elif starved is not None:
            starved.close()
            starved = None

    read_span = ttrace.span(
        "read_pipeline", cat="scheduler", n_reqs=n_reqs, n_groups=n_groups
    )
    read_span.__enter__()
    # The reporter's interval: the event is set at least that often.
    ticker: Optional[asyncio.TimerHandle] = None
    call_later = loop.call_later

    def tick() -> None:
        nonlocal ticker
        wake.set()
        ticker = call_later(reporter._interval_s, tick)

    try:
        if reporter._interval_s:
            ticker = call_later(reporter._interval_s, tick)
        note_progress()  # leading groups with nothing to read
        dispatch_io()
        while consumed < n_groups:
            # Mirror of the write path's budget_wait attribution: the
            # consuming budget is binding only when the queue head is
            # inadmissible WHILE read slots sit idle — a head queued
            # behind saturated storage is storage-bound, not budget-bound.
            budget_bound = next_for_io() is not None and reading() < io_cap
            blocked = (
                phase_stats.open_interval("budget_wait") if budget_bound else None
            )
            await wake.wait()
            wake.clear()
            if blocked is not None:
                blocked.close()
            loop_stats["turns"] += 1
            if direct is not None:
                take_direct()
            while finished_tasks:
                task = finished_tasks.popleft()
                loop_stats["taken"] += 1
                if task in io_tasks:
                    io_tasks.discard(task)
                    pipeline = task.result()  # raises on storage failure
                    pipelines.pop(task)
                    took_off(pipeline)
                else:
                    consume_tasks.discard(task)
                    pipeline = task.result()  # raises on consume failure
                    pipelines.pop(task)
                    consumed_one(pipeline)
            # No local of this frame keeps a consumed request (and, of a
            # leaf not uploaded through a pool, its host buffer) past the turn.
            task = pipeline = None
            note_progress()
            dispatch_io()
            track_starved()
            reporter.maybe_report(
                budget,
                pending=sum(len(queue) for queue in ready_for_io),
                staging=reading(),
                inflight_io=len(consume_tasks) + len(parked),
            )
        if direct is not None:
            loop_stats["wakes"] = direct.wakes
        phase_stats.add_counter("read_loop", 0.0, 0, **loop_stats)
        if loader is not None:
            loader.read_loop = loop_stats
            # Every read is taken off, so every job's CPU seconds are in.
            loader.fs_cpu_s = direct.cpu_s if direct is not None else 0.0
            loader.read_ahead_s, loader.read_ahead_bytes = _read_ahead(
                reads, loader._loaded()
            )
        read_span.__exit__(None, None, None)
    except BaseException:
        import sys

        if starved is not None:
            starved.close()
        read_span.__exit__(*sys.exc_info())
        # Mirror the write path: cancel-and-drain outstanding reads/consumes
        # before re-raising, releasing buffers and re-crediting the budget.
        # The slot hand-over starts no read from here on, and every read that
        # runs returns before anything is given back.
        if direct is not None:
            direct.close()
        for t in io_tasks | consume_tasks:
            if not t.done():
                t.cancel()
        if io_tasks or consume_tasks:
            await asyncio.gather(
                *io_tasks, *consume_tasks, return_exceptions=True
            )
        for pipeline in [*pipelines.values(), *direct_reads, *parked]:
            pipeline.buf = None
            budget.remaining += pipeline.consuming_cost
            budget.inflight -= 1
        raise
    finally:
        if ticker is not None:
            ticker.cancel()
        executor.shutdown()
        # Success or error, the read pipeline is over: zero its gauges.
        tmetrics.record_scheduler_idle("read")


def sync_execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
) -> None:
    """The read pipeline over one group, on the calling thread (reference
    scheduler.py:449-463)."""
    from .utils.loops import call_outside_loop

    call_outside_loop(
        _sync_execute_read_reqs_impl, read_reqs, storage, memory_budget_bytes, rank
    )


def _sync_execute_read_reqs_impl(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
) -> None:
    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(
            execute_read_reqs([read_reqs], storage, memory_budget_bytes, rank)
        )
    finally:
        loop.close()


class _ProgressReporter:
    """Periodic per-rank progress table (reference scheduler.py:98-177): at
    pod scale this line is how an operator sees a stuck rank — which
    pipeline state its requests are parked in, whether its budget is
    exhausted, and whether RSS is drifting past the budget.  Interval via
    the ``TPUSNAP_PROGRESS_INTERVAL_S`` knob (0 disables)."""

    def __init__(
        self,
        rank: int,
        total: int,
        verb: str,
        budget: Optional[_BudgetTracker] = None,
    ) -> None:
        self.rank = rank
        self.total = total
        self.verb = verb
        self.staged = 0
        self.io_done = 0
        self.bytes_staged = 0
        self.bytes_done = 0
        # Last-reported pipeline-state counts, refreshed every loop turn:
        # the health monitor (telemetry/monitor.py) reads these — plus the
        # counters above and `budget` — for its progress snapshots and
        # stall fingerprints.
        self.pending = 0
        self.staging = 0
        self.inflight_io = 0
        self.budget = budget
        # Optional {label: () -> [paths]} closures over the scheduler's
        # request containers, snapshotted (best-effort) into stall bundles.
        self.debug_refs: Optional[dict] = None
        try:
            self.loop: Optional[asyncio.AbstractEventLoop] = (
                asyncio.get_running_loop()
            )
        except RuntimeError:
            self.loop = None
        self._interval_s = knobs.get_progress_interval_s()
        self._last = time.monotonic()
        self._begin = self._last
        # One handle for the pipeline's life: a psutil.Process() reads the
        # process's stat file anew each time it is made, and with one read
        # pipeline a restore a reporter now lives long enough to report
        # from the pipeline's own thread.
        try:
            self._process: Optional[psutil.Process] = psutil.Process()
            self._rss_base = self._process.memory_info().rss
        except Exception:
            self._process = None
            self._rss_base = None
        tmonitor.attach_reporter(self)

    def maybe_report(
        self,
        budget: _BudgetTracker,
        pending: int = 0,
        staging: int = 0,
        inflight_io: int = 0,
    ) -> None:
        self.pending = pending
        self.staging = staging
        self.inflight_io = inflight_io
        # Gauges refresh on every scheduler loop turn, not just on the log
        # interval — short operations would otherwise never register.  One
        # env lookup when metrics are off.
        tmetrics.record_scheduler_state(
            verb=self.verb,
            pending=pending,
            staging=staging,
            inflight_io=inflight_io,
            budget_in_use=budget.in_use,
        )
        tmetrics.record_progress(
            verb=self.verb,
            requests_total=self.total,
            requests_staged=self.staged,
            requests_done=self.io_done,
            bytes_staged=self.bytes_staged,
            bytes_done=self.bytes_done,
        )
        if not self._interval_s:
            return
        now = time.monotonic()
        if now - self._last < self._interval_s:
            return
        self._last = now
        if not logger.isEnabledFor(logging.INFO):
            return
        if self._process is not None:
            try:
                rss_delta = self._process.memory_info().rss - self._rss_base
                rss_str = f"{rss_delta / 1e6:+.0f}MB"
            except Exception:
                rss_str = "?"
        else:
            rss_str = "?"
        stage_verb, io_verb = (
            ("stageable/staging", "writing")
            if self.verb == "write"
            else ("unread/reading", "consuming")
        )
        logger.info(
            "[rank %d] %s pipeline: %s=%d/%d %s=%d done=%d/%d "
            "staged=%.1fMB completed=%.1fMB rss%s budget=%.1fMB "
            "elapsed=%.0fs",
            self.rank,
            self.verb,
            stage_verb,
            pending,
            staging,
            io_verb,
            inflight_io,
            self.io_done,
            self.total,
            self.bytes_staged / 1e6,
            self.bytes_done / 1e6,
            rss_str,
            budget.remaining / 1e6,
            now - self._begin,
        )
