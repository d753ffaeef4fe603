"""Per-phase time/byte attribution for the checkpoint pipeline.

Answers "where do the seconds go" for a save/restore: per pipeline phase
(device→host transfer, serialization memcpys, checksum, storage write/read)
it accumulates both **thread-seconds** (``s``: sum over concurrent workers —
the attribution signal: the dominant phase is the one to attack) and
**wall-seconds** (``wall``: the union of that phase's active intervals — the
honest share of elapsed time; concurrent stagers over one link can burn 120
thread-seconds of d2h inside a 40 s save, and reporting only the former
misled round 3's bench record).  Overhead is one clock pair + dict update
per payload; payload counts are small.

One clock with the device: every ``timed()`` block (and every
``open_interval``, the same thing for a site whose end is not a block's
end) also opens a ``jax.profiler.TraceAnnotation`` named after the phase,
so under any ``jax.profiler`` session the phases are host events of the
``.xplane.pb``, on the trace's clock, from their beginning and on their own
thread.  With no session the annotation is a flag test.

Consumers: ``chipbench`` (``snapshot``/``delta`` around a cell's window,
``set_trace_hook`` in a traced run; its per-layer readers divide these
walls by the window's restores), ``Snapshot.restore``'s ``restore.end``
event (``walls_between`` and ``attributed_wall_s`` over the one call: the
per-phase wall and what no phase covers), the span tracer's leaf spans, and
the scheduler's end-of-pipeline log line.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, Generator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

_lock = threading.Lock()
_stats: Dict[str, Dict[str, float]] = {}
_intervals: Dict[str, List[Tuple[float, float]]] = {}
# Wall-union seconds of intervals retired from _intervals by compaction
# (see add).  Only intervals ending BEFORE the low-water mark of in-flight
# timed() begins are retired, so no timed() block still running can later
# append an interval overlapping the retired region — wall = base +
# union(live list) stays exact for timed() blocks.  Raw add() callers
# construct their interval retroactively (begin = end - seconds) without
# registering a begin; their intervals are clamped at the phase's retired
# high-water mark (_retired_hwm) so they can never overlap the retired
# base and overstate wall.  (The clamp can slightly UNDERstate when a raw
# interval falls into a gap between retired intervals — acceptable: the
# overstatement was the bug, and the known raw-add sites (h2d dispatch
# accounting) are short.)
_wall_base: Dict[str, float] = {}
# Per-phase end stamp of the newest retired interval: the clamp floor for
# retroactive raw-add intervals.
_retired_hwm: Dict[str, float] = {}
# begin timestamps of in-flight timed() blocks, keyed per phase
# (phase -> {token -> begin}): each phase's compaction low-water mark.
# Per-phase so one long-running block (a multi-minute fs_write on a huge
# payload) only stalls retirement for ITS phase — unrelated phases keep
# compacting and their lists stay bounded.
_active_begins: Dict[str, Dict[object, float]] = {}
# begin timestamps of calls that will ask for their own account when they
# end (``hold``; ``Snapshot.restore``): no interval ending after the earliest
# of them is retired, in any phase, so ``attributed_wall_s`` and
# ``walls_between`` over such a call see every interval it left, however
# many.  A phase's list then outgrows the threshold for the length of the
# call; ``_compact_at`` keeps the merge from running at every add meanwhile.
_holds: Dict[object, float] = {}
_compact_at: Dict[str, int] = {}


# Compact a phase's interval list (exact union-merge) when it grows past
# this: long-lived training jobs add one interval per payload per phase
# forever, and without compaction the lists — and every snapshot()'s sort —
# grow without bound.  Overlapping intervals (the common case: concurrent
# stagers) collapse to a handful; the list only stays large when the phase
# genuinely has that many disjoint active periods.
_COMPACT_THRESHOLD = 512

# Telemetry tracer hook (telemetry/trace.py): while a traced operation is
# collecting, every recorded interval is forwarded as
# hook(phase, begin_monotonic, end_monotonic, nbytes) and becomes a leaf
# span.  None (the default) keeps this module telemetry-free: one local
# read per add().  Installed/removed under the tracer's own lock.
_trace_hook: Optional[object] = None

# Flight-recorder observer hook (telemetry/blackbox.py): a second, always-on
# observer slot with the same contract as the trace hook — forwarded
# (phase, begin, end, nbytes) after the lock, exceptions swallowed.  Kept
# separate from _trace_hook because tracing is per-operation (installed and
# removed around each traced op) while the recorder observes for the whole
# process lifetime.
_observer_hook: Optional[object] = None

# Name of the most recently recorded phase: the "where was the pipeline"
# answer a heartbeat or a crash record wants, without holding any state in
# the caller.  Written under _lock, read without it (a str swap is atomic).
_last_phase: Optional[str] = None

# Per-thread stack of phases CURRENTLY active on that thread (innermost
# last), keyed by thread ident.  Maintained by timed() (exact: the block
# is running right now) and tagged() (scope tag only, no time recorded —
# the mechanism executor workers use to inherit the submitting thread's
# phase).  Read by the sampling profiler (telemetry/profiler.py) to
# attribute a thread's stack sample to a phase; all mutations are single
# list/dict operations (GIL-atomic), and readers tolerate a stack
# emptying between lookup and index.
_thread_phases: Dict[int, List[str]] = {}

# Fallback tag per op-DRIVING thread (ident -> stack of tags): the thread
# running an operation's event loop / commit path spends real CPU in
# dispatch work that no timed() block covers.  monitor.op_started
# registers the driver ident with a "<kind>_drive" tag; thread_phases()
# falls back to it so those samples classify as driver work instead of
# landing in the profiler's <untagged> bucket.
_driver_tags: Dict[int, List[str]] = {}


def set_trace_hook(hook) -> None:
    global _trace_hook
    _trace_hook = hook


def set_observer_hook(hook) -> None:
    global _observer_hook
    _observer_hook = hook


def last_phase() -> Optional[str]:
    """Name of the most recently recorded phase (None before any)."""
    return _last_phase


def _push_thread_phase(phase: str) -> None:
    _thread_phases.setdefault(threading.get_ident(), []).append(phase)


def _pop_thread_phase() -> None:
    ident = threading.get_ident()
    stack = _thread_phases.get(ident)
    if stack:
        stack.pop()
        if not stack:
            _thread_phases.pop(ident, None)


def current_phase() -> Optional[str]:
    """Innermost phase active on the CALLING thread (timed() block or
    tagged() scope), or None.  The tag an executor wrapper captures at
    submit time so pool workers inherit the submitting phase."""
    stack = _thread_phases.get(threading.get_ident())
    try:
        return stack[-1] if stack else None
    except IndexError:
        return None


@contextmanager
def tagged(phase: str) -> Generator[None, None, None]:
    """Tag the calling thread as working on ``phase`` WITHOUT recording
    any time: pure attribution scope for the sampling profiler (pool
    callbacks inheriting the submitting phase, op-drive loops).  Unlike
    timed(), nothing lands in the stats tables."""
    _push_thread_phase(phase)
    try:
        yield
    finally:
        _pop_thread_phase()


def register_driver(ident: int, tag: str) -> None:
    """Register ``tag`` as the fallback phase for op-driving thread
    ``ident`` (see _driver_tags)."""
    _driver_tags.setdefault(ident, []).append(tag)


def unregister_driver(ident: int, tag: str) -> None:
    """Remove one occurrence of ``tag`` from ``ident``'s driver stack —
    callable from any thread (an async op's finish may run on the commit
    thread, not the thread that registered)."""
    stack = _driver_tags.get(ident)
    if not stack:
        return
    try:
        stack.reverse()
        stack.remove(tag)
    except ValueError:
        pass
    finally:
        stack.reverse()
    if not stack:
        _driver_tags.pop(ident, None)


def thread_phases() -> Dict[int, str]:
    """Snapshot of every thread's current phase attribution: the
    innermost timed()/tagged() phase, else the thread's op-driver tag.
    Read by the sampling profiler once per tick; tolerates concurrent
    mutation (worst case a sample attributes to the phase that just
    ended — one sample of noise, never an error)."""
    out: Dict[int, str] = {}
    for ident, stack in list(_driver_tags.items()):
        try:
            out[ident] = stack[-1]
        except IndexError:
            pass
    for ident, stack in list(_thread_phases.items()):
        try:
            out[ident] = stack[-1]
        except IndexError:
            pass
    return out


def add(
    phase: str,
    seconds: float,
    nbytes: int = 0,
    end: Optional[float] = None,
    _release_token: Optional[object] = None,
) -> None:
    """Record one occurrence of ``phase``.  ``end`` (a ``time.monotonic``
    stamp; defaults to now) anchors the occurrence's interval for the
    wall-union computation.  ``_release_token`` (timed() internal) retires
    the block's active-begin registration in the same critical section as
    the append, so compaction can never observe the gap between them."""
    global _last_phase
    if end is None:
        end = time.monotonic()
    begin = end - seconds
    _last_phase = phase
    with _lock:
        if _release_token is not None:
            actives = _active_begins.get(phase)
            if actives is not None:
                actives.pop(_release_token, None)
                if not actives:
                    del _active_begins[phase]
        else:
            # Raw add: the retroactive interval may reach back past a
            # compaction's retired region (whose wall already landed in
            # _wall_base) — clamp at the retired high-water mark so the
            # union can't double-count.  timed() blocks are exempt: their
            # registered begin IS the compaction low-water mark, so their
            # intervals provably never overlap the retired base.
            hwm = _retired_hwm.get(phase)
            if hwm is not None and begin < hwm:
                begin = min(hwm, end)
        slot = _stats.setdefault(phase, {"s": 0.0, "bytes": 0, "n": 0})
        slot["s"] += seconds
        slot["bytes"] += nbytes
        slot["n"] += 1
        ivs = _intervals.setdefault(phase, [])
        # A fully-clamped interval (begin == end) union-sums to zero and
        # is appended anyway to keep "n" and interval counts aligned.
        ivs.append((begin, end))
        if len(ivs) >= _compact_at.get(phase, _COMPACT_THRESHOLD):
            merged = _merge(ivs)
            if len(merged) >= _COMPACT_THRESHOLD // 2:
                # Exact merge couldn't shrink (disjoint intervals — e.g.
                # periodic snapshots in a week-long trainer): retire the
                # oldest intervals into the phase's wall base, but only
                # those ending before the earliest still-running timed()
                # begin — a long concurrent block that started before the
                # retired region will eventually append an interval
                # reaching back there, and retiring past its begin would
                # double-count that wall.  (Closing gaps instead would
                # overstate the wall by the closed gaps: ~the whole run
                # for evenly spaced checkpoints.)
                keep = _COMPACT_THRESHOLD // 4
                low_water = min(
                    (*_active_begins.get(phase, {}).values(), *_holds.values()),
                    default=float("inf"),
                )
                retire_n = min(
                    len(merged) - keep,
                    sum(1 for _, e in merged if e <= low_water),
                )
                if retire_n > 0:
                    retired, merged = merged[:retire_n], merged[retire_n:]
                    _wall_base[phase] = _wall_base.get(phase, 0.0) + sum(
                        e - b for b, e in retired
                    )
                    _retired_hwm[phase] = retired[-1][1]
            _intervals[phase] = merged
            # Merge again only once the list has doubled: one that cannot
            # shrink (disjoint intervals under a hold or a running block)
            # would otherwise be sorted at every add.
            _compact_at[phase] = max(_COMPACT_THRESHOLD, 2 * len(merged))
    hook = _trace_hook
    if hook is not None:
        try:
            hook(phase, begin, end, nbytes)
        except Exception:
            pass  # telemetry must never break the pipeline
    observer = _observer_hook
    if observer is not None:
        try:
            observer(phase, begin, end, nbytes)
        except Exception:
            pass  # telemetry must never break the pipeline


def add_counter(name: str, seconds: float, nbytes: int = 0, **more: int) -> None:
    """Record seconds (and bytes) that belong to no interval
    (``restore_unattributed``: what is left of a call once every phase's
    interval is taken out; ``read_ahead``: what a restore's pipeline read
    before the loader was ready for it, a sum over stretches that the reads'
    own phases already draw; ``slab_write``: one occurrence a slab file
    staged, its bytes, and ``members=`` how many leaves it packs;
    ``slab_read``: one occurrence a plan, the bytes it takes out of slab
    files, and ``members=``, ``reads=``, ``merged=``, batcher.py;
    ``host_pool``: one occurrence a restore, the bytes read into pages of
    the restore's host arena that had been handed out before, and ``fresh=``,
    ``hits=``, ``misses=``, ``high_water=``, ``populated=``,
    h2d.HostBufferPool; ``chunked_read``: one occurrence a
    stateful's read plan, the bytes of its chunked leaves, and ``leaves=``,
    ``chunks=``; ``chunked_write``: the same, one occurrence a take's write
    plan, io_preparers/chunked_array.count_chunked; ``read_route``: one
    occurrence a stateful's read plan, its bytes, and ``sequential=``,
    ``striped=``, ``merged=`` by the route each read will take,
    batcher.count_read_routes; ``h2d_dispatch_route``: one occurrence a
    restore, the bytes its H2D batcher's ``device_put`` calls sent to the
    device, ``leaves=`` the arrays they sent and ``cpu_s=`` the calling
    thread's CPU seconds inside them, h2d.H2DBatcher; ``restore_cpu``: one
    occurrence a restore, the payload bytes its plans read, ``s`` the
    process's CPU seconds over the call, and ``driver_s=``, ``loop_s=``, ``fs_s=``,
    ``dispatcher_s=``, ``lander_s=`` those of the calling thread, the read
    pipeline's thread, the storage plug-in's read jobs and the two H2D
    threads, ``call_s=`` the call's wall, snapshot.Snapshot.restore;
    ``arena_turn``:
    one occurrence a restore, the bytes of the ranges of its host arena
    that completed a turn, and ``ranges=``, ``dropped=``, ``turn_bs=``,
    ``<stage>_s=`` and ``<stage>_bs=`` for each of the eight stages of a
    turn, ``arena=``, ``lent_s=``, h2d.HostBufferPool;
    ``restore_overlap``: one occurrence a restore, the seconds in which a
    storage read and an H2D dispatch or landing were both under way, and
    ``reads_s=``, ``h2d_s=``, ``neither_s=``, snapshot._restore_overlap;
    ``h2d_land_slow``: one occurrence a landing that stalled, its seconds
    and bytes, h2d._note_slow_landing; ``read_loop``: one
    occurrence a read pipeline, ``turns=`` of its loop, completions
    ``taken=`` off, reads consumed ``inline=`` on the loop thread, consumes
    ``handed=`` on as a task, ``max_pending=`` the most tasks alive at once
    (summed like the rest: over ``n`` it is the mean of the maxima),
    scheduler.execute_read_reqs; ``rng_restore``: one occurrence a restore
    that restored an ``RNGState``, the payload bytes its plan reads,
    ``entries=`` its manifest entries, ``plan_s=`` its ``plan_read`` wall,
    ``tail_s=`` from the previous stateful's load to its own, and ``s``
    their sum, snapshot.Snapshot.restore).  The
    entry has ``s``, ``bytes``, ``n`` and whatever ``more`` names, and no
    ``wall``, and reaches neither hook, so it can name no gap of a trace;
    ``delta()`` differences it like any other."""
    with _lock:
        slot = _stats.setdefault(name, {"s": 0.0, "bytes": 0, "n": 0})
        slot["s"] += seconds
        slot["bytes"] += nbytes
        slot["n"] += 1
        for key, value in more.items():
            slot[key] = slot.get(key, 0) + value


def hold(begin: float) -> object:
    """Keep every interval that ends after ``begin`` (a ``time.monotonic``
    stamp) out of compaction until ``release`` is given the token returned:
    a call that will read ``attributed_wall_s`` or ``walls_between`` over
    itself holds from its own beginning."""
    token = object()
    with _lock:
        _holds[token] = begin
    return token


def release(token: object) -> None:
    with _lock:
        _holds.pop(token, None)


@contextmanager
def timed(phase: str, nbytes: int = 0) -> Generator[None, None, None]:
    begin = time.monotonic()
    annotation = TraceAnnotation(phase)
    annotation.__enter__()
    token = object()
    with _lock:
        _active_begins.setdefault(phase, {})[token] = begin
    _push_thread_phase(phase)
    try:
        yield
    finally:
        _pop_thread_phase()
        annotation.__exit__(None, None, None)
        end = time.monotonic()
        add(phase, end - begin, nbytes, end=end, _release_token=token)


def annotation(phase: str) -> TraceAnnotation:
    """The annotation alone, for a block whose interval another site
    records with ``add()`` once it knows the phase's name
    (``fs._blocking_read``)."""
    return TraceAnnotation(phase)


class open_interval:
    """``timed()`` for a site whose interval does not end where a block
    ends: a wait that is recorded only when it lasted (``io_slot_wait``), a
    stretch that runs across turns of an event loop (``read_starved``), a
    dispatch recorded only when it succeeded.  Reads the clock and opens the
    phase's ``TraceAnnotation`` now; ``close()`` ends the annotation and
    records through ``add()``; one dropped unclosed (an error on the way)
    records nothing, and its annotation ends with it.  It sets no thread
    tag: on a loop thread such intervals interleave with other coroutines'
    blocks."""

    __slots__ = ("phase", "begin", "_annotation")

    def __init__(self, phase: str) -> None:
        self.phase = phase
        self.begin = time.monotonic()
        self._annotation: Optional[TraceAnnotation] = TraceAnnotation(phase)
        self._annotation.__enter__()

    def close(self, nbytes: int = 0, min_s: float = 0.0) -> None:
        """Idempotent.  An interval shorter than ``min_s`` is not recorded
        (its annotation still is: a trace shows the site was passed)."""
        annotation, self._annotation = self._annotation, None
        if annotation is None:
            return
        annotation.__exit__(None, None, None)
        end = time.monotonic()
        if end - self.begin >= min_s:
            add(self.phase, end - self.begin, nbytes, end=end)

    def drop(self) -> None:
        """End the annotation and record nothing: the work failed and is
        done again under another interval."""
        self.close(min_s=float("inf"))


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Exact union of intervals as a sorted disjoint list."""
    merged: List[Tuple[float, float]] = []
    for begin, end in sorted(intervals):
        if merged and begin <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((begin, end))
    return merged


def union_s(intervals: List[Tuple[float, float]]) -> float:
    """Seconds covered by at least one of ``intervals``."""
    return sum(end - begin for begin, end in _merge(intervals))


def overlap_s(
    a: List[Tuple[float, float]], b: List[Tuple[float, float]]
) -> float:
    """Seconds covered by at least one interval of ``a`` AND at least one of
    ``b``: the intersection of the two unions, by one walk over both merged
    lists (``Snapshot.restore``'s ``restore_overlap``: how long reads and H2D
    were under way at once)."""
    a, b = _merge(a), _merge(b)
    i = j = 0
    both = 0.0
    while i < len(a) and j < len(b):
        begin, end = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if end > begin:
            both += end - begin
        # The one that ends first can meet nothing further of the other.
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return both


def snapshot() -> Dict[str, Dict[str, float]]:
    with _lock:
        out = {k: dict(v) for k, v in _stats.items()}
        for phase, ivs in _intervals.items():
            out[phase]["wall"] = _wall_base.get(phase, 0.0) + union_s(ivs)
    return out


def _clipped(
    intervals: List[Tuple[float, float]], begin: float, end: float
) -> List[Tuple[float, float]]:
    return [
        (max(b, begin), min(e, end))
        for b, e in intervals
        if e > begin and b < end
    ]


def attributed_wall_s(
    begin: float = float("-inf"), end: float = float("inf")
) -> float:
    """Union of EVERY phase's active intervals, clipped to ``[begin,
    end]``: the share of that time that at least one phase accounts for.
    A call's coverage is this over its wall time — the r4 verdict's blind
    spot was 159 s of restore wall no phase could see (coverage 0.23).
    Retired wall bases are excluded (they cannot be unioned across
    phases): exact for a window under a ``hold`` taken at its beginning,
    whatever the number of intervals; without one, exact while no one
    phase has left more than the compaction threshold's worth of DISJOINT
    intervals inside the window, an under-count after that."""
    with _lock:
        ivs = [iv for lst in _intervals.values() for iv in lst]
    return union_s(_clipped(ivs, begin, end))


def intervals_between(
    begin: float, end: float
) -> Dict[str, List[Tuple[float, float]]]:
    """Each phase's intervals clipped to ``[begin, end]``, for the phases
    that were active in it: what a call that holds its intervals
    (``hold``) reduces once it has ended, by phase (``walls_between``) or
    across phases (``overlap_s`` over two groups of them).  Same exactness
    as ``attributed_wall_s``."""
    with _lock:
        live = {phase: list(ivs) for phase, ivs in _intervals.items()}
    out: Dict[str, List[Tuple[float, float]]] = {}
    for phase, ivs in live.items():
        clipped = _clipped(ivs, begin, end)
        if clipped:
            out[phase] = clipped
    return out


def walls_between(begin: float, end: float) -> Dict[str, float]:
    """Each phase's wall-union clipped to ``[begin, end]``, for the phases
    that were active in it: one call's own account, where ``delta()``
    differences process-wide totals.  Same exactness as
    ``attributed_wall_s``."""
    return {
        phase: union_s(ivs) for phase, ivs in intervals_between(begin, end).items()
    }


def reset() -> None:
    with _lock:
        _stats.clear()
        _intervals.clear()
        _wall_base.clear()
        _retired_hwm.clear()
        _compact_at.clear()


def delta(before: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """Difference between now and an earlier :func:`snapshot`.  ``wall`` is
    differenced too — only meaningful when the phases in between don't
    interleave with the before-window (bench attempts reset instead)."""
    out: Dict[str, Dict[str, float]] = {}
    for phase, now in snapshot().items():
        prev = before.get(phase, {})
        d = {k: now[k] - prev.get(k, 0) for k in now}
        if d["n"]:
            out[phase] = d
    return out


def format_line(stats: Dict[str, Dict[str, float]]) -> str:
    """Compact one-line rendering: phase=1.2s_wall/3.4s_cpu/4.5GB(3.7GB/s).
    Rate is bytes over *wall* (the deliverable throughput of that phase);
    thread-seconds shown when they differ (concurrency > 1)."""
    parts = []
    for phase in sorted(stats, key=lambda p: -stats[p]["s"]):
        s = stats[phase]["s"]
        wall = stats[phase].get("wall", s)
        b = stats[phase]["bytes"]
        head = f"{phase}={wall:.2f}s"
        if s - wall > 0.05 * max(wall, 0.01):
            head += f"({s:.2f}s-cpu)"
        if b and wall > 0:
            head += f"/{b / 1e9:.2f}GB({b / 1e9 / wall:.1f}GB/s)"
        parts.append(head)
    return " ".join(parts) if parts else "no phases recorded"
