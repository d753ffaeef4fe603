"""RSS memory profiling (reference torchsnapshot/rss_profiler.py:35-60).

Two consumers:

- :func:`measure_rss_deltas` — the reference's benchmark context manager:
  samples RSS deltas on a thread at a fixed interval (kept for parity
  with the reference; no caller in this tree but its test).
- :class:`RSSWatermark` — the health monitor's incremental variant
  (telemetry/monitor.py): no thread of its own; the monitor samples it on
  each progress tick, and the high-water mark lands in the operation's
  telemetry sidecar as ``rss_high_water_bytes`` — the number an OOM
  post-mortem needs ("did the save blow past its memory budget, and by
  how much") that a point-in-time RSS delta can't answer.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Generator, List

import psutil


class RSSWatermark:
    """Incremental RSS high-water tracking for one operation.

    ``sample()`` is cheap (one /proc read) and safe to call from any
    thread; the watermark is monotone, and a tracker that never ticks
    still reports an honest watermark from its construction-time sample.
    """

    __slots__ = ("_proc", "baseline", "high_water")

    def __init__(self) -> None:
        self._proc = psutil.Process()
        try:
            rss = self._proc.memory_info().rss
        except Exception:  # psutil races process teardown on some platforms
            rss = 0
        self.baseline = rss
        self.high_water = rss

    def sample(self) -> int:
        """Take one RSS sample; returns the current RSS and raises the
        watermark if exceeded.  Never raises (telemetry must not break the
        pipeline)."""
        try:
            rss = self._proc.memory_info().rss
        except Exception:
            return self.high_water
        if rss > self.high_water:
            self.high_water = rss
        return rss

    @property
    def delta(self) -> int:
        """High-water minus baseline: the operation's peak RSS growth."""
        return self.high_water - self.baseline


@contextmanager
def measure_rss_deltas(
    rss_deltas: List[int], interval_ms: float = 100.0
) -> Generator[None, None, None]:
    proc = psutil.Process()
    baseline = proc.memory_info().rss
    stop = threading.Event()

    def sample() -> None:
        while not stop.is_set():
            rss_deltas.append(proc.memory_info().rss - baseline)
            stop.wait(interval_ms / 1000.0)

    thread = threading.Thread(target=sample, daemon=True)
    thread.start()
    try:
        yield
    finally:
        stop.set()
        thread.join()
        rss_deltas.append(proc.memory_info().rss - baseline)
