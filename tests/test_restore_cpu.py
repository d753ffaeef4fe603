"""What a restore's own threads spend on the CPU, beside their wall: the
``restore_cpu`` counter (``Snapshot.restore``: the process over the call, the
calling thread, the read pipeline's thread, the fs plug-in's read jobs, the H2D
dispatcher and lander), and the ``leaves`` and ``cpu_s`` of
``h2d_dispatch_route`` (``h2d.H2DBatcher._count_put``: the dispatcher's calls).
Each thread reads its own ``time.thread_time`` when it starts and when it ends; no
reading is per read.

One restore through an arena (the CPU backend taken for an accelerator, as
``tests/test_host_buffer_pool.py`` takes it) holds every reading to a wall that
contains it; the batcher alone holds ``leaves`` to what it was handed; a
restore with nothing to upload holds the H2D fields at 0; the benchmark's four
readers read these fields and nothing where they are absent."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import job as chipbench_job
from torchsnapshot_tpu import Snapshot, StateDict, knobs, phase_stats
from torchsnapshot_tpu.event_handlers import (
    register_event_handler,
    unregister_event_handler,
)
from torchsnapshot_tpu import h2d as h2d_mod
from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
from torchsnapshot_tpu.io_types import Future

KEYS = ("a_params", "b_mu", "c_nu")  # loaded in the order of their names
# float32 leaves of 1, 2 and 3 MiB: from the size a read lands in place
SHAPES = ((256, 1024), (512, 1024), (768, 1024))
STATE_BYTES = len(KEYS) * sum(4 * rows * cols for rows, cols in SHAPES)
THREADS = ("driver_s", "loop_s", "fs_s", "dispatcher_s", "lander_s")
FIELDS = {"s", "bytes", "n", "call_s", *THREADS}
READERS = (
    "h2d_dispatch_leaf_ms",
    "h2d_dispatch_wait_pct.resume",
    "loop_cpu_pct.resume",
    "restore_cpu_s_per_gb",
)


def _steps():
    """The steps the thread clock takes under a thread that spins on it for
    20 ms of its own CPU: ``(median, largest)``, the largest no less than the
    stated resolution (a clock charged by the scheduler's tick steps by the
    tick, tools/thread_clock_probe.py)."""
    steps, last = [], time.thread_time()
    spin_until = last + 0.02
    while (now := time.thread_time()) < spin_until:
        if now != last:
            steps.append(now - last)
            last = now
    steps.sort()
    resolution = time.get_clock_info("thread_time").resolution
    return steps[len(steps) // 2], max(resolution, steps[-1])


_MEDIAN_STEP, _LARGEST_STEP = _steps()
# A thread's CPU against a wall read on another clock: the largest step twice
# (one at each end) and a margin for the two clocks' reads not being one.
TICK = 2 * _LARGEST_STEP + 1e-4
# Whether a thread that ran at all reads more than 0: not where the clock is
# charged by a tick of milliseconds and the thread ran for less.
FINE = _MEDIAN_STEP < 1e-4


def reader(name):
    return chipbench_job.load_module("metrics", name, "metric").read


def make_app(seed, zero=False, numpy_only=False):
    rng = np.random.RandomState(seed)

    def leaf(shape):
        host = np.zeros(shape, np.float32) if zero else rng.rand(*shape).astype(np.float32)
        return host if numpy_only else jnp.asarray(host)

    return {key: StateDict({f"w{i}": leaf(s) for i, s in enumerate(SHAPES)}) for key in KEYS}


def restore(path, target):
    """``(delta of phase_stats, the restore.end event)`` of one restore."""
    ends = []

    def on_event(event):
        if event.name == "restore.end":
            ends.append(dict(event.metadata))

    before = phase_stats.snapshot()
    register_event_handler(on_event)
    try:
        Snapshot(path).restore(target)
    finally:
        unregister_event_handler(on_event)
    (end,) = ends
    return phase_stats.delta(before), end


def memory_at(nbytes, offset_from_64):
    # As tests/test_host_buffer_pool.py: 16 bytes past a 64-byte boundary,
    # the CPU backend's device_put copies a range, as an accelerator does.
    raw = np.empty(nbytes + 128, dtype=np.uint8)
    offset = (offset_from_64 - raw.ctypes.data) % 64
    return raw[offset : offset + nbytes]


@pytest.fixture(scope="module")
def through_the_arena(tmp_path_factory):
    """One restore of three statefuls through the restore's arena, from the
    fs plug-in (reads on its workers' jobs): ``(delta, end)``."""
    path = str(tmp_path_factory.mktemp("restore_cpu") / "snap")
    saved = make_app(seed=7)
    Snapshot.take(path, saved)
    target = make_app(seed=7, zero=True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(h2d_mod, "_keeps_host_memory", lambda target: False)
        mp.setattr(h2d_mod, "_arena_memory", lambda nbytes: memory_at(nbytes, 16))
        with knobs.override_max_per_rank_io_concurrency(2):
            delta, end = restore(path, target)
    for key in KEYS:
        for name, want in saved[key].state_dict().items():
            np.testing.assert_array_equal(
                np.asarray(target[key].state_dict()[name]), np.asarray(want)
            )
    return delta, end


def test_a_restore_through_the_arena_records_its_cpu_with_every_field(through_the_arena):
    delta, end = through_the_arena
    assert end["arena_turn"]["arena"] > 0 and end["arena_turn"]["ranges"] > 0
    counter = delta["restore_cpu"]
    assert set(counter) == FIELDS and counter["n"] == 1 and "wall" not in counter
    cpu = end["restore_cpu"]
    assert set(cpu) == FIELDS - {"n"}
    assert {k: counter[k] for k in cpu} == pytest.approx(cpu, rel=1e-9)
    assert cpu["bytes"] == STATE_BYTES
    assert cpu["call_s"] == end["duration_s"]
    # every thread ran, and the dispatcher sent every leaf in its calls
    assert all(cpu[field] > 0 or not FINE for field in ("s", *THREADS)), cpu
    route = end["h2d_dispatch_route"]
    assert route["leaves"] == len(KEYS) * len(SHAPES) and route["cpu_s"] >= 0


CEILINGS = {
    # (what is held, what holds it): a thread's CPU by the call its life lies in
    "driver_s": lambda cpu, end: cpu["call_s"],
    "loop_s": lambda cpu, end: cpu["call_s"],
    "dispatcher_s": lambda cpu, end: cpu["call_s"],
    "lander_s": lambda cpu, end: cpu["call_s"],
    # the read jobs run on at most as many workers at once as there are io slots
    "fs_s": lambda cpu, end: 2 * cpu["call_s"],
    # the five threads are the process's, over the same call
    "threads": lambda cpu, end: cpu["s"],
    # the dispatcher's calls: its CPU inside them by their wall
    "dispatch_calls": lambda cpu, end: end["phases"]["h2d_dispatch"],
}


@pytest.mark.parametrize("held", sorted(CEILINGS))
def test_no_reading_is_more_cpu_than_the_wall_it_lies_in(through_the_arena, held):
    _, end = through_the_arena
    cpu = end["restore_cpu"]
    got = {
        "threads": sum(cpu[field] for field in THREADS),
        "dispatch_calls": end["h2d_dispatch_route"]["cpu_s"],
    }.get(held, cpu.get(held))
    assert (got > 0 or not FINE) and got <= CEILINGS[held](cpu, end) + TICK


def test_a_threads_cpu_is_its_time_on_the_cpu_and_not_its_wall():
    """A worker that spins 30 ms of its own CPU and sleeps 60 ms reads the
    spin, not the sleep: the clock is the thread's."""
    worker = h2d_mod._Worker("tpusnap-test-worker")

    def spin():
        until = time.thread_time() + 0.03
        while time.thread_time() < until:
            pass

    began = time.monotonic()
    worker.hand(spin)
    worker.hand(lambda: time.sleep(0.06))
    worker.close()
    wall = time.monotonic() - began
    assert 0.03 <= worker.cpu_s <= wall - 0.06 + TICK
    never = h2d_mod._Worker("tpusnap-test-idle")
    never.close()
    assert never.cpu_s == 0.0


def batcher_handed(n, retried, monkeypatch):
    """A batcher handed ``n`` leaves that go as one batch; with ``retried``
    the batched call raises and each leaf goes again alone."""
    if retried:
        real = jax.device_put

        def batched_fails(x, *args, **kwargs):
            if isinstance(x, list):
                raise RuntimeError("the batched call fails")
            return real(x, *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", batched_fails)
    pool = HostBufferPool()
    pool.batcher = b = H2DBatcher(pool, flush_bytes=1 << 30)
    futs = [Future() for _ in range(n)]
    before = phase_stats.snapshot()
    for i, fut in enumerate(futs):
        b.submit(np.full(256, float(i), np.float32), jnp.zeros(256, jnp.float32), fut)
    b.drain()
    pool.close()
    for i, fut in enumerate(futs):
        np.testing.assert_array_equal(np.asarray(fut.obj), np.full(256, float(i)))
    return b.route(), phase_stats.delta(before)["h2d_dispatch"]["n"]


@pytest.mark.parametrize("retried", [False, True], ids=["one_call", "per_item_retry"])
def test_leaves_are_what_the_batcher_was_handed_counted_once(retried, monkeypatch):
    route, calls = batcher_handed(5, retried, monkeypatch)
    assert route["leaves"] == 5 and route["bytes"] == 5 * 256 * 4
    assert calls == (5 if retried else 1)
    assert sorted(route) == ["bytes", "cpu_s", "leaves"]
    assert 0 <= route["cpu_s"]


def test_with_nothing_to_upload_the_h2d_threads_never_start_and_read_nothing(tmp_path):
    """numpy targets are read in place and never uploaded: the dispatcher and
    the lander never start, their fields read 0, and the two H2D readers
    read nothing; the call's own readers still read it."""
    path = str(tmp_path / "snap")
    Snapshot.take(path, make_app(seed=3, numpy_only=True))
    delta, end = restore(path, make_app(seed=3, zero=True, numpy_only=True))
    cpu = end["restore_cpu"]
    assert cpu["dispatcher_s"] == 0 and cpu["lander_s"] == 0
    assert end["h2d_dispatch_route"]["leaves"] == 0 and end["h2d_dispatch_route"]["cpu_s"] == 0
    assert cpu["loop_s"] >= 0 and cpu["bytes"] == STATE_BYTES
    run = {"phases": delta, "counters": {}}
    assert reader("h2d_dispatch_leaf_ms")(run) is None
    assert reader("h2d_dispatch_wait_pct.resume")(run) is None
    assert reader("loop_cpu_pct.resume")(run) >= 0
    assert reader("restore_cpu_s_per_gb")(run) >= 0


def test_the_readers_read_the_counters_fields(through_the_arena):
    delta, end = through_the_arena
    run = {"phases": delta, "counters": {}}
    cpu, route, wall = delta["restore_cpu"], delta["h2d_dispatch_route"], delta["h2d_dispatch"]["wall"]
    want = {
        "h2d_dispatch_leaf_ms": 1000.0 * wall / route["leaves"],
        "h2d_dispatch_wait_pct.resume": 100.0 * (wall - route["cpu_s"]) / wall,
        "loop_cpu_pct.resume": 100.0 * cpu["loop_s"] / cpu["call_s"],
        "restore_cpu_s_per_gb": cpu["s"] / (cpu["bytes"] / 1e9),
    }
    for name in READERS:
        assert reader(name)(run) == pytest.approx(want[name], rel=1e-12), name
    assert 0 <= want["loop_cpu_pct.resume"] <= 100 * (1 + TICK / cpu["call_s"])


@pytest.mark.parametrize("name", READERS)
def test_a_library_without_the_fields_reads_nothing(name):
    """As the parent's library records them: ``h2d_dispatch_route`` with no
    ``leaves`` or ``cpu_s``, and no ``restore_cpu`` at all."""
    old_route = {"s": 0.0, "bytes": 1 << 20, "n": 1, "off_caller": 1 << 20,
                 "on_caller": 0, "batches": 2}
    phases = {"h2d_dispatch": {"s": 0.01, "wall": 0.01, "bytes": 1 << 20, "n": 2},
              "h2d_dispatch_route": old_route}
    assert reader(name)({"phases": phases, "counters": {}}) is None
    assert reader(name)({"phases": {}, "counters": {}}) is None

