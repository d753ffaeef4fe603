"""From a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
benchmark reports: the seconds an operation ran on the device, the device
operations that took most time, and the longest idle gaps named by what the
host was doing in them.

The arithmetic (``reduce_planes``) works on anything shaped like
``ProfileData.planes`` (``.name``, ``.lines`` -> ``.name``, ``.events`` ->
``.name``, ``.start_ns``, ``.duration_ns``), so the tests drive it with
planes written by hand as well as with a recorded trace.

Clocks: events start at nanoseconds since the trace began.  Host spans that
the benchmark keeps on ``time.monotonic`` (the job's own, and the library's
``phase_stats`` intervals that reach it through ``set_trace_hook``) are
moved onto the trace's clock by the ``chipbench_sync`` annotation, which is
emitted right after a ``time.monotonic_ns`` reading.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

SYNC_NAME = "chipbench_sync"
WINDOW_NAME = "chipbench_window"
# Lines of a device plane that hold one event per executed operation.  A
# plane that has none of them is read whole, but for its step markers.
_OP_LINES = ("XLA Ops",)
_NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope", "Framework Ops",
            "Source code")
# A gap shorter than this is the space between two operations, not idleness
# anyone could name.
_MIN_GAP_S = 1e-3

Interval = Tuple[float, float]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for b, e in sorted(intervals):
        if out and b <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((b, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(b, lo), min(e, hi)) for b, e in intervals if e > lo and b < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - b for b, e in intervals)


def _overlap(merged: Sequence[Interval], begins: Sequence[float], lo: float, hi: float) -> float:
    i = max(bisect.bisect_right(begins, lo) - 1, 0)
    s = 0.0
    while i < len(merged) and merged[i][0] < hi:
        s += max(0.0, min(merged[i][1], hi) - max(merged[i][0], lo))
        i += 1
    return s


def name_gaps(
    gaps: Sequence[Interval],
    phases: Dict[str, List[Interval]],
    spans: Dict[str, List[Interval]],
) -> Dict[str, float]:
    """Seconds of idle gap by what the host was doing: the library phase
    that covers most of the gap if one covers half of it, else the job's own
    span that does, else ``untagged``."""
    named: Dict[str, float] = {}
    tables = []
    for group in (phases, spans):
        t = {}
        for name, ivs in group.items():
            m = merge(ivs)
            t[name] = (m, [b for b, _ in m])
        tables.append(t)
    for lo, hi in gaps:
        length = hi - lo
        label = "untagged"
        for t in tables:
            best, best_s = None, 0.0
            for name, (m, begins) in t.items():
                s = _overlap(m, begins, lo, hi)
                if s > best_s:
                    best, best_s = name, s
            if best is not None and best_s >= 0.5 * length:
                label = best
                break
        named[label] = named.get(label, 0.0) + length
    return named


def reduce_planes(
    planes: Iterable[Any],
    host_spans: Optional[Sequence[Tuple[str, float, float]]] = None,
    host_phases: Optional[Sequence[Tuple[str, float, float]]] = None,
    sync_mono_ns: Optional[int] = None,
    n_devices: int = 1,
) -> Dict[str, Any]:
    """``host_spans`` and ``host_phases`` are ``(name, begin, end)`` in
    seconds of ``time.monotonic``; ``sync_mono_ns`` is the reading taken just
    before the sync annotation.  Returns None-free fields only for what the
    trace holds: a trace with no device plane gives ``busy_s`` None."""
    device_ops: Dict[str, List[Interval]] = {}  # plane -> intervals (s)
    op_time: Dict[str, float] = {}
    sync_trace_ns = None
    window: Optional[Interval] = None
    for plane in planes:
        is_device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name.upper()
        lines = list(plane.lines)
        if is_device:
            op_lines = [ln for ln in lines if ln.name in _OP_LINES] or [
                ln for ln in lines if ln.name not in _NOT_OPS
            ]
            ivs = device_ops.setdefault(plane.name, [])
            for ln in op_lines:
                for ev in ln.events:
                    b = ev.start_ns * 1e-9
                    d = ev.duration_ns * 1e-9
                    ivs.append((b, b + d))
                    op_time[ev.name] = op_time.get(ev.name, 0.0) + d
        else:
            for ln in lines:
                for ev in ln.events:
                    if ev.name == SYNC_NAME and sync_trace_ns is None:
                        sync_trace_ns = ev.start_ns
                    elif ev.name == WINDOW_NAME:
                        b = ev.start_ns * 1e-9
                        window = (b, b + ev.duration_ns * 1e-9)
    out: Dict[str, Any] = {
        "devices_traced": len(device_ops),
        "busy_s": None,
        "window_s": None if window is None else window[1] - window[0],
        "device_ops": [],
        "idle_gaps": [],
        "sync_found": sync_trace_ns is not None,
    }
    if not device_ops:
        return out
    if window is None:
        lo = min(b for ivs in device_ops.values() for b, _ in ivs)
        hi = max(e for ivs in device_ops.values() for _, e in ivs)
        window = (lo, hi)
        out["window_s"] = hi - lo
    busy = {name: clip(merge(ivs), *window) for name, ivs in device_ops.items()}
    # Averaged over the chips the cell uses; a chip with no event was idle.
    out["busy_s"] = sum(total(b) for b in busy.values()) / max(n_devices, len(busy))
    out["device_ops"] = [
        [short_op(name), seconds]
        for name, seconds in sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    ]
    first = busy[sorted(busy)[0]]
    gaps, at = [], window[0]
    for b, e in first:
        if b - at >= _MIN_GAP_S:
            gaps.append((at, b))
        at = max(at, e)
    if window[1] - at >= _MIN_GAP_S:
        gaps.append((at, window[1]))
    out["idle_gap_s"] = total(gaps)
    if sync_trace_ns is not None and sync_mono_ns is not None:
        shift = (sync_trace_ns - sync_mono_ns) * 1e-9

        def moved(items):
            table: Dict[str, List[Interval]] = {}
            for name, b, e in items or ():
                table.setdefault(name, []).append((b + shift, e + shift))
            return table

        named = name_gaps(gaps, moved(host_phases), moved(host_spans))
    else:
        named = {"untagged": total(gaps)} if gaps else {}
    out["idle_gaps"] = [
        [name, seconds] for name, seconds in sorted(named.items(), key=lambda kv: -kv[1])[:10]
    ]
    return out


def short_op(name: str) -> str:
    """``%fusion.32 = (f32[4096,32768]{1,0:T(8,128)}, ...) fusion(...)`` as
    ``fusion.32 (f32[4096,32768]{1,0:T(8,128)}, ...``: the instruction and the
    start of what it produces, which is what tells two fusions apart."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    return f"{head} {rest[:56]}" if sep else head[:80]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_file(path: str, **kwargs: Any) -> Dict[str, Any]:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes, **kwargs)
