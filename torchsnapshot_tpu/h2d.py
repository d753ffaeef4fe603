"""The restore's H2D stage: the host arena its leaves are read into
(``HostBufferPool``), the one batcher that sends them to the device
(``H2DBatcher``, with the two threads that do it).  One pool a
``Snapshot.restore`` call, which owns the batcher; its ``close`` joins the
batcher's threads and drops the arena.

A leaf read into place takes a range of the arena when its first read is
dispatched (``HostBufferPool.take``), is submitted to the batcher when its
last read has been consumed (``H2DBatcher.submit``), and the lander gives the
range back once its transfer has landed (``HostBufferPool.give``), to
whichever read comes next, of any size and any stateful.

**One batcher serves every stateful of a restore.**  The read pipeline
consumes no read of group k+1 before the loader has loaded group k
(``scheduler.execute_read_reqs``: a read of k+1 that finishes early is
parked), and a leaf is submitted only by the consume of its last read.  So
the one assertion that holds it: *at the ``drain`` of group k, nothing of
another group has been submitted* (every leaf submitted since the drain of
group k-1 is group k's; ``tests/test_restore_read_ahead.py`` holds it).  A
batcher of each stateful's own would hold what this one holds, drained at
the same point (``Snapshot._load_stateful``, once a stateful), with the same
in-flight cap (the arena's window term).

**Liveness.**  A read that finds no room in the arena waits for a landing
(the phase ``host_buffer_wait``), so a restore stays live only while
*whoever holds a range gets on without the waiter*.  Five places keep that,
each by one condition:

1. ``scheduler._ReadPipeline.take_memory`` / ``take_memory_soon``: *ranges
   are taken in dispatch order, before the io slot*.  A read that waits for
   room holds no slot, so a read under way always has one; and a read of
   group k+1 (dispatched once group k's last read has finished) takes a
   range only behind every read of k, so it starves none of them.
2. ``io_preparers/array.IntoPlace.acquire`` / ``acquire_soon``: a leaf's
   first read takes its range and its other reads wait with it, handed on
   by the one grant callback (``ArrayAssembly._granted``).
3. ``HostBufferPool.take`` and ``H2DBatcher.submit``: *a wait flushes the
   batcher*.  The take that begins a wait flushes it, and a leased leaf
   submitted while a read waits is flushed at once, whatever
   ``flush_bytes`` says: no waiter waits on leaves gathering under the
   threshold.
4. ``H2DBatcher.flush``: *a flush always leaves a dispatcher run
   scheduled* (``_dispatching``, cleared only by a run that finds nothing
   queued, under the lock), so no waiter waits on a batch that nobody will
   send; a landing needs the lander alone.
5. ``HostBufferPool.give``: *``give`` grants the head of the queue*, as far
   as the freed room reaches, so no range is in two hands and none is taken
   out of turn.

A change to any of the five keeps its condition, or restates this argument.
"""

from __future__ import annotations

import asyncio
import bisect
import functools
import logging
import mmap
import threading
import time
from collections import deque
from queue import SimpleQueue
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import phase_stats
from .io_types import Future

logger = logging.getLogger(__name__)

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

    def stamp(self, stamp: str) -> None:
        """A hand-over of the range, now: of several reads into one leaf the
        first ``read_began`` stands, and the last of every other stamp."""
        if stamp != "read_began" or self.read_began is None:
            setattr(self, stamp, _now())


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
    from .native_io import NativeFileIO

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
    arena, handed out as page-aligned ranges of any size, and the stage that
    uploads from it (``batcher``).  Why the restore stays
    live while reads wait for room here: the head of this module.

    A leaf that is read into place and uploaded through the batcher
    reserves its size at plan time (``reserve``; every stateful is planned
    before the first read, each after a ``begin_group``), takes a range when
    the first of its reads is dispatched (``take``), and the lander gives
    the range back once the transfer has landed (``give``).  The arena is
    one allocation, made at the first ``take`` and **populated then and
    there**: every page of it is written once, in parallel, on the native
    pool that the restore's reads run on (idle at that moment; the phase
    ``arena_populate``, no thread started), before a range of it is handed
    to a read.  So no read of the restore faults a fresh page, and nothing
    is unmapped beside the reads (a ``munmap`` holds the GIL and takes the
    address space's lock for writing while the readers fault pages in under
    it: PERF.md sections 5 and 7).  A native library without the symbol, or
    none, populates nothing: a page then costs its first touch when the
    first leaf is read into it.

    **Its size** is a rule, and no knob: ``max(the largest reserved leaf,
    the batcher's in-flight cap)``, in whole pages, and no more than the
    largest stateful's bytes.  The largest leaf, so that every leaf fits;
    the in-flight cap, so that the landings the batcher allows can all be
    under way while reads go on.  No more: reads into touched pages outrun
    the landings (PERF.md section 5 holds what each rule tried on the chip).

    **A take** is first-fit from the lowest address over a short list of
    free ranges, so that what was ever handed out is always a prefix of the
    arena (``high_water``; ``fresh`` counts what is handed out for the first
    time); a give frees the range and coalesces it with its neighbours.  A
    read that finds no room waits in the order of dispatch (a future of the
    pipeline's loop, or a callback its ``give`` hands that loop), for a
    landing of any stateful, its own included.

    **No arena** (``take`` is a plain ``np.empty``, ``give`` drops it) where
    a target's backend may keep the host memory as the landed array
    (``_keeps_host_memory``: decided at ``reserve``), where nothing could be
    used twice (everything reserved fits the arena), and from the moment a
    range is given back unfit (``recycle=False``: its transfer failed, or
    the landed array may be the range itself).  A take that cannot wait (a
    piece copied in on an executor thread) and finds no room is a plain
    buffer too.  A plain buffer is never populated.  Nothing outlives the
    restore: ``close()`` joins the threads and drops the arena.

    **A range's turn is stamped and accounted** (``turn_stats``; the counter
    ``arena_turn``).  Each range out has one record (``_Turn``, kept where
    the range's size was: ``_lent``): ``_fit`` makes it, whoever holds the
    range stamps it at each hand-over without the lock (the assembly finds
    it once, ``turn_of``; the batcher by the lease it is handed), and
    ``give``, which holds the lock already, folds it into totals by stage
    (``_STAGES``) in seconds and in byte-seconds.  The stages of a turn add
    up to it, and ``turn_bs`` can be no more than the arena's size times
    ``lent_s``: their ratio is the arena's occupancy.  A range given back
    unfit, or never adopted by a leaf, is ``dropped``; a plain buffer has no
    turn.  **While a read waits for room** the pool counts the seconds
    (``starved_s``) and the free bytes of the arena over them
    (``stranded_bs``), where the free ranges and the queue change.

    Thread-safe: ``reserve`` runs on the planning thread, ``take`` on the
    read pipeline's thread or its executor, ``give`` on the lander (on the
    dispatcher for a leaf that was never sent)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._group_bytes: List[int] = []  # reserved by each stateful, in pages
        self._largest = 0  # the largest reserved leaf, in pages
        self._plain = False  # no arena (any more)
        self._arena: Optional[np.ndarray] = None
        self._base = self._size = 0
        self._free: List[List[int]] = []  # [offset, size], by offset
        self._lent: Dict[int, _Turn] = {}  # offset -> each range out, its turn
        # (loop, what grants the range, nbytes) of each read held for room, in
        # dispatch order
        self._waiters: "deque[Tuple[Any, Callable[[np.ndarray], None], int]]" = deque()
        self._plain_alive = 0  # bytes of plain buffers taken and not given back
        self.batcher = H2DBatcher(self)  # the restore's one, with its two threads
        self._stats = dict.fromkeys(
            ("bytes", "fresh", "hits", "misses", "high_water", "populated"), 0
        )
        self._touched = 0  # the arena's prefix ever handed out
        # The turns that ended in a give of a landed range, by stage.
        self._turns: Dict[str, float] = dict.fromkeys(
            ("bytes", "ranges", "dropped", "lent_s", "starved_s", "stranded_bs"), 0
        )
        for stage in _STAGES:
            self._turns[stage + "_s"] = self._turns[stage + "_bs"] = 0.0
        self._first_grant: Optional[float] = None
        self._changed = 0.0  # when the free ranges or the queue last changed

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

    def start_threads(self) -> None:
        """Every stateful is planned and no read has been issued: the
        dispatcher and the lander start now, where a leaf is to be uploaded,
        and not under read load from the thread that issues reads (a
        thread's start costs its starter 0.2-0.5 s there: PERF.md section
        5)."""
        if self.batcher.expects_uploads:
            self.batcher.start()

    def _make_arena(self) -> None:
        # Under the lock, at the first take: every stateful is planned.
        window = _pages(self.batcher.inflight_cap_bytes)
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

    def take(
        self,
        nbytes: int,
        loop: Optional[Any] = None,
        granted: Optional[Callable[[np.ndarray], None]] = None,
    ) -> Any:
        """A flat uint8 buffer of exactly ``nbytes``: a range of the arena,
        or a plain buffer where there is no arena (or, with no ``loop``, no
        room in it).  With a ``loop`` and no room, or with reads already
        waiting: a future of ``loop`` that a ``give`` resolves to the buffer,
        this read's turn having come; the batcher is flushed first.  Given
        ``granted``, None in place of the future, and the ``give`` calls
        ``granted(buffer)`` on ``loop``'s thread: one callback, no future."""
        with self._lock:
            if self._arena is None and not self._plain:
                self._make_arena()
            self._note_starved()
            if self._plain or _pages(nbytes) > self._size:
                return self._plain_buffer(nbytes)
            if loop is None or not self._waiters:
                buf = self._fit(nbytes)
                if buf is not None:
                    return buf
                if loop is None:
                    return self._plain_buffer(nbytes)
            coming = None
            if granted is None:
                coming = loop.create_future()
                granted = functools.partial(self._grant, coming)
            self._waiters.append((loop, granted, nbytes))
        self.batcher.flush()
        return coming

    def waiting(self) -> bool:
        """Whether a read is held for room: what the batcher is handed then
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
        granted: List[Tuple[Any, Callable[[np.ndarray], None], np.ndarray]] = []
        with self._lock:
            offset = buf.ctypes.data - self._base
            turn = self._lent.pop(offset, None)
            if turn is None:
                self._plain_alive -= buf.nbytes  # a plain buffer: dropped
                return
            self._note_starved()
            self._fold(turn, landed=recycle)
            if recycle:
                self._release(offset, turn.size)
            else:
                self._plain = True
            while self._waiters:
                loop, grant, nbytes = self._waiters[0]
                lease = (
                    self._plain_buffer(nbytes) if self._plain else self._fit(nbytes)
                )
                if lease is None:
                    break
                self._waiters.popleft()
                granted.append((loop, grant, lease))
        for loop, grant, lease in granted:
            try:
                loop.call_soon_threadsafe(grant, lease)
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

    def _note_starved(self) -> None:
        """Under the lock, before the free ranges or the queue change: the
        stretch since they last changed counts as starved if a read waited
        all through it, and the arena's free bytes over it as stranded."""
        now = _now()
        if self._waiters:
            span = now - self._changed
            self._turns["starved_s"] += span
            self._turns["stranded_bs"] += span * sum(room for _, room in self._free)
        self._changed = now

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
        self.batcher.close()
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
        first grant); ``dropped`` the ranges given back unfit or unused;
        ``starved_s`` the seconds in which a read waited for room and
        ``stranded_bs`` the arena's free bytes integrated over them, at most
        ``arena`` times ``starved_s``.  Zeros where no arena was made."""
        with self._lock:
            out = dict(self._turns)
            out["arena"] = self._size
        out["turn_bs"] = sum(out[stage + "_bs"] for stage in _STAGES)
        return out

    def account(self) -> Dict[str, Any]:
        """The stage's account of the restore, once ``close`` has joined the
        threads: the counters ``host_pool`` (``stats``),
        ``h2d_dispatch_route`` (``H2DBatcher.route``) and ``arena_turn``
        (``turn_stats``), and the CPU seconds of the dispatcher and the
        lander (``dispatcher_s``, ``lander_s``; 0 where they never started)."""
        batcher = self.batcher
        return {
            "host_pool": self.stats(),
            "h2d_dispatch_route": batcher.route(),
            "arena_turn": self.turn_stats(),
            "dispatcher_s": batcher.dispatcher.cpu_s,
            "lander_s": batcher.lander.cpu_s,
        }


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
    handed is ever left undone.  Once joined, ``cpu_s`` is the CPU seconds
    the thread spent from its start to its end (its own ``time.thread_time``
    read twice), 0 where it never started."""

    def __init__(self, name: str) -> None:
        self._name = name
        self._lock = threading.Lock()
        self._jobs: "SimpleQueue[Optional[Callable[[], None]]]" = SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self.cpu_s = 0.0

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

    def _run(self) -> None:
        began = time.thread_time()
        try:
            for job in iter(self._jobs.get, None):
                try:
                    job()
                except BaseException:  # noqa: BLE001 -- a job keeps its own errors
                    logger.exception("a job of %s raised", self._name)
        finally:
            self.cpu_s = time.thread_time() - began

    def close(self) -> None:
        """Run what was handed, then end and join the thread (idempotent)."""
        with self._lock:
            self._closed = True
            thread, self._thread = self._thread, None
        if thread is not None:
            self._jobs.put(None)
            thread.join()


# A landing that stalls says so (the counter ``h2d_land_slow``): half a second
# and more at under 0.5 GB/s.  The slowest ceiling a healthy landing was
# measured at is 5.4 GB/s (one large bf16 leaf, tools/h2d_ceiling_probe.py,
# PERF.md section 7), and the largest single landing of any cell, 713 MB,
# takes 0.13 s at it: a tenth of that rate over half a second is no healthy
# landing of any size, and every stall seen (3-7 s, PERF.md section 5) is.
_SLOW_LANDING_S = 0.5
_SLOW_LANDING_BYTES_PER_S = 0.5e9


def _note_slow_landing(seconds: float, nbytes: int, leaves: int) -> None:
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


def _device_put_like(host: np.ndarray, like: Any) -> Any:
    """Place a host array like an existing jax.Array (device + sharding +
    memory kind + dtype).  The H2D analogue of the reference's
    consume-into-GPU-target copy (tensor.py:331-340)."""
    import jax

    if host.dtype != np.dtype(like.dtype):
        host = host.astype(np.dtype(like.dtype))
    # Dispatch time with bytes — the transfer itself is async and lands
    # either under the batcher's h2d_land phase or the caller's sync point.
    with phase_stats.timed("h2d_dispatch", host.nbytes):
        return jax.device_put(host, like.sharding)


class H2DBatcher:
    """Cross-array H2D upload batching + landing pacing: the restore's one
    batcher, its ``HostBufferPool``'s (``host_pool``), which starts it before
    the first read is issued and closes it when the restore ends.  What it
    must keep for the restore to stay live (items 3 and 4) is at the head of
    this module.

    **Its two threads**: the ``dispatcher`` (``tpusnap-h2d-dispatcher``) runs
    each batch's ``device_put`` and the ``lander`` (``tpusnap-h2d-lander``)
    its ``block_until_ready``, so batch N+1 is copied while batch N lands and
    neither runs on the thread that issues the reads.  One dispatcher, not
    several: beside sixteen threads that read and hash as a restore's io
    slots do, two threads' ``device_put`` calls land three to four times what
    one thread's do (``tools/h2d_ceiling_probe.py``, PERF.md section 7), but
    in a restore a batch split two ways shortened the dispatch's wall and not
    the call (PERF.md section 6): the dispatcher is not what a restore waits
    for.  What does pay is that a busy dispatcher's backlog goes as one call:
    a call a leaf from this thread was slower than the calls it replaced.

    Per-array ``device_put`` dispatches serialize each upload behind its
    array's read; collecting completed host buffers and uploading
    them in ONE batched pjrt transfer lets the backend overlap the streams
    and overlaps the batch with the remaining storage reads.  Buffers
    accumulate up to ``flush_bytes`` (bounding the extra host-memory
    residency beyond the scheduler's budget), then flush incrementally.

    **A flush is a hand-off.**  ``flush`` queues what has gathered for the
    dispatcher thread and returns: no ``device_put`` runs on
    the thread that finalised the leaf, which is the read pipeline's loop
    thread, the only one that issues reads and hands out io slots.  The
    dispatcher takes what is queued as one batch (no more than the in-flight
    cap admits, but for a single leaf that is larger), waits for window room
    (``h2d_window_wait``), reserves the batch's bytes, makes the ONE batched
    ``device_put`` (``h2d_dispatch``), sets each future and hands the batch
    to the lander; what is flushed while it is busy gathers into the batch it
    takes when it comes free.  A queued batch holds host memory (ranges of
    the pool's arena), never HBM.

    Dispatched batches land EAGERLY on the lander thread (``h2d_land``); a
    bounded unlanded-bytes window (default 2× ``flush_bytes``)
    backpressures new dispatches so batch N's landing overlaps the reads
    feeding batch N+1 instead of every transfer piling up behind the
    caller's final ``block_until_ready``.  The owner calls :meth:`drain`
    once a stateful's last consume is done: on return every submitted array
    is ON DEVICE, not in flight, and nothing of the batcher is queued or
    running on either thread.

    A buffer submitted with its ``lease`` (the pool's range under ``host``)
    goes back to the pool when its transfer has landed (the lander's
    ``block_until_ready`` before the ``give`` is what keeps a range from its
    next tenant until its last one is on the device), unless the landed
    array may be the buffer itself (``_may_alias``); one whose transfer
    failed, or was never made, is given back unfit.  Nothing here keeps a
    host buffer past its landing.

    **What it tells the record.**  The dispatcher stamps a leased buffer's
    turn (``HostBufferPool.turn_of``) when it takes the batch with window
    room had (``sent``) and when the batch's ``device_put`` has returned
    (``put``); the lander's ``give`` ends the turn.  A landing that stalls
    (``_SLOW_LANDING_S`` and more, at under ``_SLOW_LANDING_BYTES_PER_S``) is
    counted, ``h2d_land_slow``, and logged at INFO.

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
    are closed).  A back-pressure wait holds the dispatcher, never a thread
    that reads.

    ``route()`` is the account of the ``device_put`` calls (the counter
    ``h2d_dispatch_route``): the ``bytes`` and ``leaves`` they sent, and
    ``cpu_s`` the CPU seconds of the calling thread inside them (its
    ``time.thread_time`` before and after each), whose ``h2d_dispatch``
    wall less this is the calls' time off the CPU.  Each thread's own CPU
    seconds, once joined, are its ``_Worker.cpu_s``.
    """

    _DEFAULT_FLUSH_BYTES = 256 << 20

    def __init__(
        self,
        host_pool: HostBufferPool,
        flush_bytes: int = _DEFAULT_FLUSH_BYTES,
        inflight_cap_bytes: Optional[int] = None,
    ) -> None:
        self.host_pool = host_pool
        self._items: List[_Item] = []
        self._bytes = 0
        self._flush_bytes = flush_bytes
        self.inflight_cap_bytes = (
            inflight_cap_bytes if inflight_cap_bytes is not None else 2 * flush_bytes
        )
        # Whether a planned leaf will be submitted here (``ArrayAssembly``
        # says so at plan time): the threads start before the first read only
        # then (``HostBufferPool.start_threads``).
        self.expects_uploads = False
        self.dispatcher = _Worker("tpusnap-h2d-dispatcher")
        self.lander = _Worker("tpusnap-h2d-lander")
        self._route_lock = threading.Lock()
        self._route: Dict[str, float] = {"bytes": 0, "leaves": 0, "cpu_s": 0.0}
        self._cond = threading.Condition()
        self._queued: List[_Item] = []  # flushed, not yet taken by the dispatcher
        self._dispatching = False  # a dispatcher run of this batcher is scheduled
        self._landing = 0  # batches handed to the lander and not yet settled
        self._unlanded_bytes = 0  # dispatched, not yet landed
        self._error: Optional[BaseException] = None

    def start(self) -> None:
        self.dispatcher.start()
        self.lander.start()

    def close(self) -> None:
        """Join the two threads once they have run what they were handed
        (idempotent); a job handed after this runs on the handing thread."""
        # The dispatcher first: what it still sends, the lander still lands.
        self.dispatcher.close()
        self.lander.close()

    def _count_put(self, nbytes: int, leaves: int, cpu_s: float) -> None:
        """One ``device_put`` call of ``nbytes`` in ``leaves`` arrays has
        returned, having spent ``cpu_s`` on the CPU inside it (a call that
        raised sent nothing and is not counted)."""
        with self._route_lock:
            self._route["bytes"] += nbytes
            self._route["leaves"] += leaves
            self._route["cpu_s"] += cpu_s

    def route(self) -> Dict[str, float]:
        with self._route_lock:
            return dict(self._route)

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
        self.dispatcher.hand(self._dispatch_queued)

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
            self.lander.hand(
                functools.partial(self._land, good, landed_bytes, leases)
            )
        if failed:
            # A failed batch retries per item so one bad array (dtype/
            # sharding mismatch) fails alone with correct blame and its
            # batch-mates still restore.
            self._dispatch_per_item(failed)

    def _turns_of(self, items: List[_Item]) -> List[_Turn]:
        """The arena's records of the leased buffers among ``items``."""
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
        batcher on either thread) whether it raises or not, so callers never
        observe mid-landing counters after an error."""
        try:
            self.flush()
        finally:
            # The lander decrements unlanded bytes even for failed
            # landings, so this loop terminates regardless of errors.
            with self._cond:
                while self._dispatching or self._landing:
                    self._cond.wait(timeout=1.0)
        self._raise_error()

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
            cpu = time.thread_time()
            for i, out in zip(idx, jax.device_put(bufs, shardings)):
                outs[i] = out
            self._count_put(nbytes, len(bufs), time.thread_time() - cpu)
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

        first_exc: Optional[BaseException] = None
        outs: List[Any] = []
        leases: List[_Lease] = []
        nbytes = 0
        for host, like, fut, lease in items:
            out = None
            try:
                cpu = time.thread_time()
                fut.obj = out = _device_put_like(host, like)
                self._count_put(host.nbytes, 1, time.thread_time() - cpu)
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
