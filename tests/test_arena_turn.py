"""A range's turn through the restore's host arena, measured where it happens
(``h2d.HostBufferPool``: the counter ``arena_turn``), the call
split by which of reads and H2D were under way (``Snapshot.restore``'s
``restore_overlap``), and a landing that stalls counted (``h2d_land_slow``).

The pool is driven by hand under a clock of the test's own, so each stage reads
exactly the seconds put between its stamps; whole restores through an arena are
in ``tests/test_host_buffer_pool.py``, whose fixture takes the CPU backend for
an accelerator.  Here a restore on the CPU backend as it is: no arena, the
counters recorded with zeros, the benchmark's readers silent."""

import asyncio
import logging
import threading
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import job as chipbench_job
from torchsnapshot_tpu import Snapshot, StateDict, phase_stats
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.event_handlers import (
    register_event_handler,
    unregister_event_handler,
)
from torchsnapshot_tpu import h2d as h2d_mod
from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
from torchsnapshot_tpu.io_preparers import array as array_mod
from torchsnapshot_tpu.io_types import Future

PAGE = h2d_mod._PAGE
STAGES = h2d_mod._STAGES
STAMPS = h2d_mod._STAMPS  # the stamp that ends each stage but the last: the give's
ON_A_CHIP = types.SimpleNamespace(devices=lambda: [types.SimpleNamespace(platform="tpu")])
ARENA_READERS = ("arena_turn_s", "arena_wait_pct.resume", "arena_h2d_side_pct.resume",
                 "arena_stranded_pct.resume")
READERS = ARENA_READERS + ("read_h2d_overlap_pct.resume", "h2d_land_slow_s")


class Clock:
    """What the pool and its ranges' holders read in place of
    ``time.monotonic``: it moves when the test says so."""

    def __init__(self):
        self.t = 5000.0

    def __call__(self):
        return self.t

    def tick(self, seconds):
        self.t += seconds


@pytest.fixture
def clock(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(h2d_mod, "_now", clock)
    return clock


def pool_of(*groups, window):
    """A pool with ``groups`` of leaves reserved, in pages, whose one batcher's
    in-flight cap, ``window`` pages, is the arena."""
    pool = HostBufferPool()
    pool.batcher.inflight_cap_bytes = window * PAGE
    for group in groups:
        pool.begin_group()
        for pages in group:
            pool.reserve(pages * PAGE, ON_A_CHIP)
    return pool, pool.batcher


def memory_at(nbytes, offset_from_64):
    """A flat uint8 buffer that begins ``offset_from_64`` bytes past a 64-byte
    boundary (its allocation lives as long as the view)."""
    raw = np.empty(nbytes + 128, dtype=np.uint8)
    offset = (offset_from_64 - raw.ctypes.data) % 64
    return raw[offset : offset + nbytes]


def turn(pool, clock, buf, seconds):
    """``buf``'s whole turn from its grant on: ``seconds[k]`` pass before the
    stamp that ends stage k, the last before the give."""
    record = pool.turn_of(buf)
    for stamp, s in zip(STAMPS, seconds):
        clock.tick(s)
        setattr(record, stamp, clock())
    clock.tick(seconds[-1])
    pool.give(buf, recycle=True)


def reader(name):
    return chipbench_job.load_module("metrics", name, "metric").read


def run_of(phases, restores=1):
    done = [{"op": "kill_resume", "ok": True}] * restores
    return {
        "account": types.SimpleNamespace(window_operations=lambda op: done),
        "phases": phases,
        "counters": {},
    }


# ------------------------------------------------------ the pool, by hand


@pytest.mark.parametrize("stage", STAGES)
def test_a_stage_is_the_seconds_between_its_two_stamps(clock, stage):
    """Every stage a power of two of its own, so that each sum is exact: the
    stage named holds what was put between its stamps and nothing else."""
    pool, _batcher = pool_of([2, 2], [2, 2], window=2)
    seconds = [2.0 ** -(k + 1) for k in range(len(STAGES))]
    buf = pool.take(2 * PAGE - 96)
    turn(pool, clock, buf, seconds)
    stats = pool.turn_stats()
    k = STAGES.index(stage)
    assert stats[stage + "_s"] == seconds[k]
    assert stats[stage + "_bs"] == (2 * PAGE - 96) * seconds[k]
    assert stats["bytes"] == 2 * PAGE - 96 and stats["ranges"] == 1 and stats["dropped"] == 0


def test_the_stages_add_up_to_the_turn_and_no_byte_is_lent_twice(clock):
    """Three ranges, two of them out at once: the eight ``_bs`` are
    ``turn_bs``, which is each range's bytes times its time out, and no more
    than the arena's size times ``lent_s`` (the last give less the first
    grant): their ratio is the arena's occupancy."""
    pool, _batcher = pool_of([2, 1, 2], [2, 1, 2], window=3)
    t0 = clock()
    a = pool.take(2 * PAGE)
    clock.tick(0.25)
    b = pool.take(PAGE)
    turn(pool, clock, a, [0.0, 0.5, 1.0, 0.0, 0.125, 0.25, 0.125, 2.0])  # out 4.25 s
    a_out = clock() - t0
    turn(pool, clock, b, [0.0] * 7 + [0.5])
    b_out = clock() - (t0 + 0.25)
    c = pool.take(2 * PAGE)
    turn(pool, clock, c, [0.0, 0.0, 0.5, 0.25, 0.0, 0.0, 0.0, 0.25])
    stats = pool.turn_stats()
    assert stats["turn_bs"] == sum(stats[stage + "_bs"] for stage in STAGES)
    assert stats["turn_bs"] == 2 * PAGE * a_out + PAGE * b_out + 2 * PAGE * 1.0
    assert stats["arena"] == 3 * PAGE and stats["lent_s"] == clock() - t0
    assert stats["turn_bs"] <= stats["arena"] * stats["lent_s"]
    assert 0.5 < stats["turn_bs"] / (stats["arena"] * stats["lent_s"]) < 1.0
    assert stats["ranges"] == 3 and stats["bytes"] == 5 * PAGE
    # a stage whose stamp was never made, or made at once, is 0: parked here
    # but for the third range, which was let go a quarter of a second late
    assert stats["parked_s"] == 0.25 and stats["parked_bs"] == 2 * PAGE * 0.25
    # the readers, from the counter as Snapshot.restore records it
    before = phase_stats.snapshot()
    phase_stats.add_counter(
        "arena_turn", 0.0, stats["bytes"], **{k: v for k, v in stats.items() if k != "bytes"}
    )
    run = run_of(phase_stats.delta(before))
    assert reader("arena_turn_s")(run) == stats["turn_bs"] / (5 * PAGE)
    waits = sum(stats[s + "_bs"] for s in ("grant", "slot", "parked", "gather"))
    assert reader("arena_wait_pct.resume")(run) == 100.0 * waits / stats["turn_bs"]
    h2d = sum(stats[s + "_bs"] for s in ("gather", "dispatch", "land"))
    assert reader("arena_h2d_side_pct.resume")(run) == 100.0 * h2d / stats["turn_bs"]


@pytest.mark.parametrize("waiter", ["future", "callback"])
def test_a_waiters_grant_is_over_zero_and_a_direct_takes_is_zero(clock, waiter):
    """A take that finds room is adopted in the same breath; one that waits
    is fitted by the lander's give and adopted when the pipeline's loop gets
    to it: ``grant`` is what lies between.  The waiter is a future of the
    loop, or (the read pipeline's slot hand-over) a callback the give hands
    the loop, one handle, with the range."""
    pool, _batcher = pool_of([2, 2], [2, 2], window=2)

    async def pipeline():
        loop = asyncio.get_running_loop()
        direct = pool.take(2 * PAGE, loop)
        pool.turn_of(direct).adopted = clock()
        granted = []
        if waiter == "future":
            coming = pool.take(2 * PAGE, loop)
            assert isinstance(coming, asyncio.Future)
        else:
            coming = loop.create_future()

            def grant(lease):
                granted.append(threading.current_thread())
                coming.set_result(lease)

            assert pool.take(2 * PAGE, loop, granted=grant) is None
        clock.tick(1.0)  # (the waiter's record is not made yet: nothing is lent)
        lander = threading.Thread(target=pool.give, args=(direct, True))
        lander.start()
        lander.join(5)
        clock.tick(0.5)  # fitted by the give, and the loop busy elsewhere
        lease = await asyncio.wait_for(coming, 5)
        assert granted == ([] if waiter == "future" else [threading.current_thread()])
        pool.turn_of(lease).adopted = clock()
        clock.tick(0.25)
        pool.give(lease, recycle=True)

    asyncio.run(pipeline())
    stats = pool.turn_stats()
    # the direct take: grant 0, all of its second a landing; the waiter: half a
    # second granted and not adopted, a quarter landing
    assert stats["grant_s"] == 0.5 and stats["grant_bs"] == 2 * PAGE * 0.5
    assert stats["land_s"] == 1.0 + 0.25 and stats["ranges"] == 2
    assert stats["lent_s"] == 1.75


def stranded_share(stats):
    before = phase_stats.snapshot()
    phase_stats.add_counter(
        "arena_turn", 0.0, stats["bytes"], **{k: v for k, v in stats.items() if k != "bytes"}
    )
    return reader("arena_stranded_pct.resume")(run_of(phase_stats.delta(before)))


def test_a_read_that_waits_counts_the_seconds_and_the_free_bytes_it_could_not_use(clock):
    """Three ranges out of an arena of ten pages (3 + 3 + 2), a fourth take of
    three pages waits two seconds beside two free ones; the 2-page range comes
    back and the waiter gets its three, leaving one free; a fifth of three
    waits a second beside that one.  ``starved_s`` is the three seconds,
    ``stranded_bs`` the free bytes over them, and the reader their share of
    the arena."""
    pool, _batcher = pool_of([3, 3, 2, 3, 3], [3, 3, 2, 3, 3], window=10)

    async def pipeline():
        loop = asyncio.get_running_loop()
        a, b, c = pool.take(3 * PAGE, loop), pool.take(3 * PAGE, loop), pool.take(2 * PAGE, loop)
        for buf in (a, b, c):
            pool.turn_of(buf).adopted = clock()
        fourth = pool.take(3 * PAGE, loop)
        assert isinstance(fourth, asyncio.Future)
        clock.tick(2.0)
        pool.give(c, recycle=True)  # four pages free: the waiter's three fit
        d = await asyncio.wait_for(fourth, 5)
        pool.turn_of(d).adopted = clock()
        clock.tick(0.25)  # nobody waits: not counted
        fifth = pool.take(3 * PAGE, loop)
        assert isinstance(fifth, asyncio.Future)
        clock.tick(1.0)
        pool.give(a, recycle=True)
        e = await asyncio.wait_for(fifth, 5)
        for buf in (b, d, e):
            pool.turn_of(buf).adopted = clock()
            pool.give(buf, recycle=True)

    asyncio.run(pipeline())
    stats = pool.turn_stats()
    assert stats["arena"] == 10 * PAGE and stats["ranges"] == 5
    assert stats["starved_s"] == 3.0
    assert stats["stranded_bs"] == 2.0 * 2 * PAGE + 1.0 * PAGE
    assert stats["stranded_bs"] <= stats["arena"] * stats["starved_s"]
    assert stranded_share(stats) == pytest.approx(100.0 * 5 / 30, rel=1e-12)


def test_with_no_read_waiting_nothing_is_starved_and_the_reader_says_nothing(clock):
    pool, _batcher = pool_of([3, 3], [3, 3], window=6)
    for _ in range(3):
        buf = pool.take(3 * PAGE)
        pool.turn_of(buf).adopted = clock()
        clock.tick(1.0)
        pool.give(buf, recycle=True)
    stats = pool.turn_stats()
    assert stats["ranges"] == 3 and stats["starved_s"] == 0 == stats["stranded_bs"]
    assert stranded_share(stats) is None


@pytest.mark.parametrize("how", ["unused", "unfit", "never_given"])
def test_a_range_that_no_leaf_went_through_is_dropped_and_in_no_stage(clock, how):
    pool, _batcher = pool_of([2, 2], [2, 2], window=2)
    buf = pool.take(2 * PAGE)
    clock.tick(1.0)
    if how == "unused":  # a piece copied in had made the buffer meanwhile
        pool.give(buf, recycle=True)
    elif how == "unfit":  # its transfer failed, or the landed array may be it
        pool.turn_of(buf).adopted = clock()
        pool.give(buf, recycle=False)
    stats = pool.turn_stats()
    assert stats["dropped"] == (0 if how == "never_given" else 1)
    assert stats["bytes"] == stats["ranges"] == stats["turn_bs"] == stats["lent_s"] == 0
    assert all(stats[stage + "_s"] == stats[stage + "_bs"] == 0 for stage in STAGES)
    if how != "unused":  # no room, or no arena any more: a plain buffer has no turn
        plain = pool.take(2 * PAGE)
        assert pool.turn_of(plain) is None
        pool.give(plain, recycle=True)
        assert pool.turn_stats() == stats


def test_the_first_read_began_stands_and_the_last_of_every_other_stamp(clock, monkeypatch):
    """A leaf of several reads (a chunked one): ``slot`` ends where the first
    of them is handed to storage, ``read`` where the last is taken off.  Through
    the handles the pipeline holds: the assembly and its ``IntoPlace``."""
    from torchsnapshot_tpu.io_preparers.array import ArrayAssembly
    from torchsnapshot_tpu.manifest import TensorEntry

    pool, _batcher = pool_of([2, 2], [2, 2], window=2)
    batcher = types.SimpleNamespace(host_pool=pool, submitted=[])
    batcher.submit = lambda *item: batcher.submitted.append(item)
    monkeypatch.setattr(array_mod.staging, "is_jax_array", lambda obj: obj is ON_A_CHIP)
    monkeypatch.setattr(array_mod, "_INTO_PLACE_MIN_BYTES", PAGE)
    entry = TensorEntry(
        location="x", serializer="buffer_protocol", dtype="uint8", shape=[2 * PAGE],
        replicated=False,
    )
    assembly = ArrayAssembly(entry, ON_A_CHIP, h2d_batch=batcher)
    halves = [assembly.into_view(0, PAGE), assembly.into_view(PAGE, PAGE)]
    assembly.expect(2)

    async def pipeline():
        await halves[0].acquire()  # the range is taken, and adopted
        clock.tick(0.5)
        halves[0].stamp("read_began")
        clock.tick(0.25)
        await halves[1].acquire()
        halves[1].stamp("read_began")  # the second read: not the leaf's first
        clock.tick(1.0)
        halves[0].stamp("read_back")
        clock.tick(2.0)
        halves[1].stamp("read_back")  # the last stands

    asyncio.run(pipeline())
    assembly.piece_done()
    clock.tick(0.125)
    assembly.piece_done()  # finalize: submitted, and the assembly lets go
    ((_host, _like, _fut, lease),) = batcher.submitted
    assembly.stamp("submitted")  # nothing any more: the range is the batcher's
    clock.tick(4.0)
    pool.give(lease, recycle=True)
    stats = pool.turn_stats()
    assert (stats["grant_s"], stats["slot_s"], stats["read_s"]) == (0.0, 0.5, 3.25)
    assert (stats["parked_s"], stats["consume_s"]) == (0.0, 0.125)
    assert stats["gather_s"] == stats["dispatch_s"] == 0.0 and stats["land_s"] == 4.0


def test_the_batcher_stamps_sent_and_put_on_the_dispatcher(clock, monkeypatch):
    """Through a real batcher with the pool's threads: ``gather`` ends once the
    dispatcher has the batch and window room, ``dispatch`` when the
    ``device_put`` has returned, ``land`` at the lander's give."""
    monkeypatch.setattr(h2d_mod, "_keeps_host_memory", lambda target: False)
    # (the CPU backend copies a range that begins 16 bytes past a 64-byte
    # boundary, as an accelerator copies any: the landed array is its own)
    monkeypatch.setattr(h2d_mod, "_arena_memory", lambda nbytes: memory_at(nbytes, 16))
    import jax

    pool = HostBufferPool()
    pool.batcher = batcher = H2DBatcher(
        pool, flush_bytes=1 << 30, inflight_cap_bytes=2 * PAGE
    )
    like = jnp.zeros(2 * PAGE // 4, jnp.float32)
    for _ in range(2):
        pool.begin_group()
        pool.reserve(2 * PAGE, like)
    real_put = jax.device_put

    def slow_put(bufs, shardings):
        clock.tick(0.5)  # the call itself
        return real_put(bufs, shardings)

    real_ready = jax.block_until_ready

    def slow_ready(outs):
        clock.tick(2.0)  # the landing
        return real_ready(outs)

    monkeypatch.setattr(jax, "device_put", slow_put)
    monkeypatch.setattr(jax, "block_until_ready", slow_ready)
    lease = pool.take(2 * PAGE)
    pool.turn_of(lease).adopted = clock()
    pool.turn_of(lease).submitted = clock()
    fut = Future()
    batcher.submit(lease.view(np.float32), like, fut, lease)
    clock.tick(0.25)  # gathering under flush_bytes: nobody asked for it yet
    batcher.drain()
    pool.close()
    stats = pool.turn_stats()
    assert (stats["gather_s"], stats["dispatch_s"], stats["land_s"]) == (0.25, 0.5, 2.0)
    assert stats["ranges"] == 1 and stats["bytes"] == 2 * PAGE and stats["dropped"] == 0


# ---------------------------------------------- the call, split by stage


@pytest.mark.parametrize(
    "reads, h2d, want",
    [
        ([(0.0, 2.0)], [(3.0, 4.0)], 0.0),  # disjoint
        ([(0.0, 8.0)], [(1.0, 2.0), (3.0, 5.0)], 3.0),  # nested
        ([(1.0, 3.0)], [(1.0, 3.0)], 2.0),  # equal
        ([], [(1.0, 3.0)], 0.0),  # empty
        ([(0.0, 2.0), (1.0, 4.0), (6.0, 7.0)], [(3.0, 6.5), (3.5, 4.5)], 1.5),  # overlapping lists
        ([(0.0, 1.0), (1.0, 2.0)], [(0.5, 1.5)], 1.0),  # touching neighbours
    ],
    ids=["disjoint", "nested", "equal", "empty", "overlapping", "touching"],
)
def test_overlap_s_is_the_intersection_of_two_unions(reads, h2d, want):
    assert phase_stats.overlap_s(reads, h2d) == want
    assert phase_stats.overlap_s(h2d, reads) == want


@pytest.mark.parametrize(
    "held, want",
    [
        (  # disjoint: a read, then a landing, a gap between and around
            {"native_read": [(1.0, 3.0)], "h2d_land": [(4.0, 7.0)]},
            {"s": 0.0, "reads_s": 2.0, "h2d_s": 3.0, "neither_s": 5.0},
        ),
        (  # nested: landings inside one long read, both kinds of each group
            {"fs_read": [(0.0, 6.0)], "native_read": [(5.0, 8.0)], "fs_write": [(9.5, 10.0)],
             "h2d_dispatch": [(1.0, 2.0)], "h2d_land": [(2.0, 4.0), (7.0, 9.0)]},
            {"s": 4.0, "reads_s": 8.0, "h2d_s": 5.0, "neither_s": 1.0},
        ),
        (  # equal
            {"native_read": [(2.0, 5.0)], "h2d_land": [(2.0, 5.0)]},
            {"s": 3.0, "reads_s": 3.0, "h2d_s": 3.0, "neither_s": 7.0},
        ),
        (  # empty: nothing was read and nothing uploaded
            {"plan_read": [(0.0, 1.0)], "load_state": [(9.0, 10.0)]},
            {"s": 0.0, "reads_s": 0.0, "h2d_s": 0.0, "neither_s": 10.0},
        ),
    ],
    ids=["disjoint", "nested", "equal", "empty"],
)
def test_restore_overlap_splits_the_call_four_ways(held, want):
    """Reads are what ``analyze.classify_phase`` calls ``storage_io``
    (``plan_read`` is the driver's there, the sidecar's ``fs_write`` at the
    call's end is no read, and the waits are nobody's work), H2D its group
    ``h2d``; both, each alone and neither add up to the call."""
    held = dict(held, host_buffer_wait=[(0.0, 10.0)], plan_read=held.get("plan_read", [(0.0, 0.5)]))
    got = snapshot_mod._restore_overlap(held, 10.0)
    assert got == want
    reads_only, h2d_only = got["reads_s"] - got["s"], got["h2d_s"] - got["s"]
    assert got["s"] + reads_only + h2d_only + got["neither_s"] == pytest.approx(10.0, rel=0.01)


# ----------------------------------- a restore on the CPU backend as it is


def restore_with_its_account(tmp_path):
    """Two statefuls of two leaves of a megabyte and more, saved and restored
    on the CPU backend: ``(delta of phase_stats, the restore.end event)``."""
    def app(zero):
        rng = np.random.RandomState(3)
        return {
            key: StateDict({
                f"w{i}": jnp.zeros((512, 1024), jnp.float32) if zero
                else jnp.asarray(rng.rand(512, 1024), jnp.float32)
                for i in range(2)
            })
            for key in ("a_params", "b_mu")
        }

    path = str(tmp_path / "snap")
    saved = app(zero=False)
    Snapshot.take(path, saved)
    ends = []

    def on_event(event):
        if event.name == "restore.end":
            ends.append(dict(event.metadata))

    target = app(zero=True)
    before = phase_stats.snapshot()
    register_event_handler(on_event)
    try:
        Snapshot(path).restore(target)
    finally:
        unregister_event_handler(on_event)
    for key in saved:
        for name, want in saved[key].state_dict().items():
            np.testing.assert_array_equal(np.asarray(target[key].state_dict()[name]), np.asarray(want))
    (end,) = ends
    return phase_stats.delta(before), end


def test_a_restore_with_no_arena_records_zeros_and_the_arena_readers_say_nothing(tmp_path):
    delta, end = restore_with_its_account(tmp_path)
    counter = delta["arena_turn"]
    assert counter["n"] == 1 and counter["s"] == 0 and "wall" not in counter
    assert end["host_pool"]["fresh"] == 4 * 2 * (1 << 20)  # pooled leaves, plain buffers
    want_keys = {"bytes", "ranges", "dropped", "lent_s", "arena", "turn_bs", "starved_s", "stranded_bs"}
    want_keys |= {stage + suffix for stage in STAGES for suffix in ("_s", "_bs")}
    assert set(end["arena_turn"]) == want_keys
    assert all(value == 0 for value in end["arena_turn"].values())
    assert all(counter[key] == 0 for key in want_keys)
    run = run_of(delta)
    for name in ARENA_READERS:
        assert reader(name)(run) is None, name


def test_an_fs_restore_runs_every_read_on_the_slot_hand_over_and_says_so(tmp_path):
    """The fs plug-in reads blocking: every request of the restore is read
    by the slot hand-over (``read_loop``'s ``direct``), on the plug-in's
    workers, each of its wake-ups posted for one finished read or more."""
    delta, end = restore_with_its_account(tmp_path)
    loop = end["read_loop"]
    requests = loop["inline"] + loop["handed"]
    assert requests == 4 and loop["direct"] == requests
    assert 1 <= loop["wakes"] <= requests and loop["taken"] == requests + loop["handed"]
    counter = delta["read_loop"]
    assert counter["n"] == 1 and {k: counter[k] for k in loop} == loop


def test_every_restore_records_its_overlap_and_the_event_agrees_with_its_phases(tmp_path):
    """The three entries of ``restore.end`` beside ``host_pool``, and
    ``restore_overlap``'s two walls against the event's own ``phases``."""
    delta, end = restore_with_its_account(tmp_path)
    overlap = end["restore_overlap"]
    assert sorted(overlap) == ["h2d_s", "neither_s", "reads_s", "s"]
    counter = delta["restore_overlap"]
    assert counter["n"] == 1 and "wall" not in counter
    assert {k: counter[k] for k in overlap} == overlap
    phases = end["phases"]
    reads = [v for k, v in phases.items() if k in ("native_read", "fs_read")]
    assert reads and 0 < max(reads) <= overlap["reads_s"] <= sum(reads) + 1e-9
    h2d = [v for k, v in phases.items() if k in ("h2d_dispatch", "h2d_land")]
    assert h2d and 0 < max(h2d) <= overlap["h2d_s"] <= sum(h2d) + 1e-9
    assert 0 <= overlap["s"] <= min(overlap["reads_s"], overlap["h2d_s"])
    both_or_either = overlap["reads_s"] + overlap["h2d_s"] - overlap["s"]
    assert overlap["neither_s"] == pytest.approx(end["duration_s"] - both_or_either, abs=1e-9)
    assert end["h2d_land_slow"] == {"s": 0.0, "n": 0}
    run = run_of(delta)
    got = reader("read_h2d_overlap_pct.resume")(run)
    assert got == 100.0 * overlap["s"] / min(overlap["reads_s"], overlap["h2d_s"])
    assert 0.0 <= got <= 100.0
    assert reader("h2d_land_slow_s")(run) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_says_nothing_of_a_library_without_its_counter(name):
    """The parent's library under this benchmark's files: landings and reads
    there are, the three counters there are not."""
    phases = {
        "h2d_land": {"s": 1.0, "wall": 1.0, "bytes": 100, "n": 3},
        "native_read": {"s": 1.0, "wall": 1.0, "bytes": 100, "n": 3},
        "host_pool": {"s": 0.0, "bytes": 100, "fresh": 50, "n": 1},
    }
    assert reader(name)(run_of(phases)) is None


# ------------------------------------------------- a landing that stalls


@pytest.mark.parametrize("path", ["batch", "per_item"])
def test_a_landing_that_stalls_is_counted_once_and_a_fast_one_never(monkeypatch, caplog, path):
    import jax

    real_ready = jax.block_until_ready
    stall = {"s": 0.0}

    def ready(outs):
        time.sleep(stall["s"])
        return real_ready(outs)

    monkeypatch.setattr(jax, "block_until_ready", ready)
    if path == "per_item":  # the batched call fails: each leaf is sent alone
        real_put = jax.device_put

        def put(bufs, shardings):
            if isinstance(bufs, list):
                raise RuntimeError("injected failure of the batched call")
            return real_put(bufs, shardings)

        monkeypatch.setattr(jax, "device_put", put)
    like = jnp.zeros(16, jnp.float32)
    before = phase_stats.snapshot()
    with caplog.at_level(logging.INFO, logger=h2d_mod.logger.name):
        for stall["s"] in (0.0, h2d_mod._SLOW_LANDING_S + 0.05, 0.0):
            pool = HostBufferPool()
            pool.batcher = batcher = H2DBatcher(pool, flush_bytes=64)
            futs = [Future(), Future()]
            for i, fut in enumerate(futs):
                batcher.submit(np.full(16, float(i), dtype=np.float32), like, fut)
            batcher.drain()
            pool.close()
            assert [float(np.asarray(f.obj)[0]) for f in futs] == [0.0, 1.0]
    delta = phase_stats.delta(before)
    slow = delta["h2d_land_slow"]
    assert slow["n"] == 1 and slow["s"] >= h2d_mod._SLOW_LANDING_S and "wall" not in slow
    assert 0 < slow["bytes"] <= 128 and delta["h2d_land"]["n"] >= 3
    said = [r for r in caplog.records if "slow H2D landing" in r.getMessage()]
    assert len(said) == 1 and said[0].levelno == logging.INFO
    assert f"{int(slow['bytes'])} bytes" in said[0].getMessage()
    # the reader sums the window's stalls, where the library records overlaps
    delta["restore_overlap"] = {"s": 0.0, "n": 3, "bytes": 0}
    assert reader("h2d_land_slow_s")(run_of(delta, restores=3)) == slow["s"]


def test_a_large_landing_that_takes_long_at_a_healthy_rate_is_not_slow():
    """Half a second and more is slow only under half a gigabyte a second."""
    before = phase_stats.snapshot()
    h2d_mod._note_slow_landing(0.6, int(0.6 * h2d_mod._SLOW_LANDING_BYTES_PER_S), 1)
    h2d_mod._note_slow_landing(0.49, 8, 1)
    assert "h2d_land_slow" not in phase_stats.delta(before)
    h2d_mod._note_slow_landing(0.6, int(0.6 * h2d_mod._SLOW_LANDING_BYTES_PER_S) - 1, 1)
    assert phase_stats.delta(before)["h2d_land_slow"]["n"] == 1
