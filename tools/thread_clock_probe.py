"""One-off probe, outside every cell and imported by nothing: whether the
thread CPU clock that the ``restore_cpu`` counter and ``h2d_dispatch_route``'s
``cpu_s`` read (``time.thread_time``) ticks finely on a host, and how it stands
against the wall clock the phases read (``time.monotonic``).

    python3 tools/thread_clock_probe.py   # on the host to be measured

What it prints, one JSON line: ``clocks``, ``time.get_clock_info`` of the two
CPU clocks and the wall clocks; ``spin``, the steps ``thread_time`` takes
under a thread that reads it over and over for 0.2 s (how many, the smallest,
the median and the largest: a clock charged by the scheduler's tick steps by
the tick); ``short_calls``, 2,000 calls of about 30 us of pure Python each,
timed by both clocks (``zero_share``: the calls whose CPU read 0, which a
tick-charged clock gives most of; ``cpu_over_wall``: their CPU summed over
their wall summed; ``worst_excess_s``: the most a call's CPU read over its
wall, the two clocks' disagreement); ``sleep``, the CPU a thread reads over a
50 ms sleep (about 0 for a thread clock, 0.05 for a wall clock); ``read_s``,
what one read of each CPU clock costs (the mean of 100,000)."""

from __future__ import annotations

import json
import statistics
import time


def _spin(seconds: float = 0.2) -> dict:
    steps = []
    last = time.thread_time()
    until = time.perf_counter() + seconds
    while time.perf_counter() < until:
        now = time.thread_time()
        if now != last:
            steps.append(now - last)
            last = now
    return {
        "steps": len(steps),
        "min_s": min(steps, default=0.0),
        "median_s": statistics.median(steps) if steps else 0.0,
        "max_s": max(steps, default=0.0),
    }


def _short_calls(n: int = 2000) -> dict:
    cpus, walls = [], []
    for _ in range(n):
        c, w = time.thread_time(), time.monotonic()
        sum(range(1500))
        cpus.append(time.thread_time() - c)
        walls.append(time.monotonic() - w)
    return {
        "calls": n,
        "mean_wall_s": statistics.mean(walls),
        "zero_share": sum(1 for c in cpus if c == 0) / n,
        "cpu_over_wall": sum(cpus) / sum(walls),
        "worst_excess_s": max(c - w for c, w in zip(cpus, walls)),
    }


def _read_s(clock, n: int = 100_000) -> float:
    began = time.perf_counter()
    for _ in range(n):
        clock()
    return (time.perf_counter() - began) / n


def main() -> None:
    clocks = {
        name: vars(time.get_clock_info(name))
        for name in ("thread_time", "process_time", "monotonic", "perf_counter")
    }
    began = time.thread_time()
    time.sleep(0.05)
    slept = time.thread_time() - began
    print(
        json.dumps(
            {
                "clocks": clocks,
                "spin": _spin(),
                "short_calls": _short_calls(),
                "sleep": {"slept_s": 0.05, "cpu_s": slept},
                "read_s": {
                    "thread_time": _read_s(time.thread_time),
                    "process_time": _read_s(time.process_time),
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
