"""Direct unit tests for the small utility modules that otherwise get only
indirect coverage (manifest predicates, memoryview stream, phase stats, RSS
profiler, loop helpers)."""

import time

import numpy as np
import pytest


def test_manifest_predicates():
    from torchsnapshot_tpu.manifest import (
        DictEntry,
        ListEntry,
        PrimitiveEntry,
        Shard,
        ShardedArrayEntry,
        TensorEntry,
    )
    from torchsnapshot_tpu.manifest_utils import (
        is_container_entry,
        is_fully_replicated_entry,
        is_sharded_entry,
    )

    assert is_container_entry(DictEntry(keys=[]))
    assert is_container_entry(ListEntry())
    tensor = TensorEntry(
        location="x", serializer="buffer_protocol", dtype="float32",
        shape=[2], replicated=False,
    )
    assert not is_container_entry(tensor)
    sharded = ShardedArrayEntry(
        dtype="float32", shape=[4],
        shards=[Shard(offsets=[0], sizes=[4], tensor=tensor)],
        mesh_shape=[2], axis_names=["x"], partition_spec=[["x"]],
    )
    assert is_sharded_entry(sharded)
    assert not is_sharded_entry(tensor)
    # sharded entries are by definition not fully replicated; a replicated
    # dense entry is
    assert not is_fully_replicated_entry(sharded)
    replicated = TensorEntry(
        location="r", serializer="buffer_protocol", dtype="float32",
        shape=[2], replicated=True,
    )
    assert is_fully_replicated_entry(replicated)
    from torchsnapshot_tpu.manifest_utils import is_partially_replicated_entry

    hsdp = ShardedArrayEntry(
        dtype="float32", shape=[8],
        shards=[Shard(offsets=[0], sizes=[8], tensor=tensor)],
        mesh_shape=[2, 2], axis_names=["replica", "shard"],
        partition_spec=[["shard"]],
    )
    assert is_partially_replicated_entry(hsdp)
    assert not is_partially_replicated_entry(sharded)
    prim = PrimitiveEntry.from_object(3)
    assert not is_sharded_entry(prim)


def test_memoryview_stream_read_seek():
    from torchsnapshot_tpu.memoryview_stream import MemoryviewStream

    data = bytes(range(100))
    stream = MemoryviewStream(memoryview(data))
    assert stream.read(10) == data[:10]
    stream.seek(50)
    assert stream.read(10) == data[50:60]
    stream.seek(-5, 2)  # from end
    assert stream.read() == data[-5:]
    assert stream.readable() and stream.seekable()
    assert stream.tell() == 100


def test_phase_stats_compaction_keeps_wall_exact():
    """Evenly spaced disjoint intervals (a periodic-snapshot trainer) must
    stay bounded in memory WITHOUT inflating the wall union: retired
    intervals move into a per-phase base, never into closed gaps."""
    from torchsnapshot_tpu import phase_stats

    phase_stats.reset()
    # 1s of work every 601s, 600 occurrences — far past the compaction
    # threshold, zero overlaps for the exact merge to collapse.
    for i in range(600):
        phase_stats.add("periodic", 1.0, 10, end=i * 601.0 + 1.0)
    with phase_stats._lock:
        live = len(phase_stats._intervals["periodic"])
    assert live < 600  # compaction actually ran
    wall = phase_stats.snapshot()["periodic"]["wall"]
    assert wall == pytest.approx(600.0)  # exact: no gap ever closed
    phase_stats.reset()


def test_phase_stats_accumulate_delta_format():
    from torchsnapshot_tpu import phase_stats

    phase_stats.reset()
    with phase_stats.timed("unit_x", 1000):
        time.sleep(0.01)
    before = phase_stats.snapshot()
    assert before["unit_x"]["n"] == 1 and before["unit_x"]["bytes"] == 1000
    phase_stats.add("unit_x", 0.5, 500)
    delta = phase_stats.delta(before)
    assert delta["unit_x"]["n"] == 1 and delta["unit_x"]["bytes"] == 500
    line = phase_stats.format_line(phase_stats.snapshot())
    assert "unit_x" in line and "GB" in line
    phase_stats.reset()
    assert phase_stats.snapshot() == {}
    assert phase_stats.format_line({}) == "no phases recorded"


def test_rss_profiler_records_deltas():
    from torchsnapshot_tpu.rss_profiler import measure_rss_deltas

    deltas: list = []
    with measure_rss_deltas(deltas, interval_ms=10.0):
        # Over glibc's largest mmap threshold (32 MiB), so that the pages are
        # fresh whatever this process has freed before: a smaller blob can be
        # cut from heap memory that is already resident, and RSS then stays.
        blob = np.ones(80_000_000, np.uint8)  # ~80 MB
        time.sleep(0.08)
        del blob
    assert deltas, "sampler recorded nothing"
    assert max(deltas) > 10_000_000, max(deltas)  # saw the ~80 MB allocation


def test_call_outside_loop_propagates_exceptions():
    import asyncio

    from torchsnapshot_tpu.utils.loops import call_outside_loop, run_coro

    class Boom(RuntimeError):
        pass

    def _raises():
        raise Boom("inner")

    # plain-thread path
    try:
        call_outside_loop(_raises)
        raise AssertionError("should have raised")
    except Boom:
        pass

    # inside-a-loop path (delegates to helper thread)
    async def scenario():
        try:
            call_outside_loop(_raises)
            raise AssertionError("should have raised")
        except Boom:
            pass
        assert run_coro(lambda: _coro()) == 42

    async def _coro():
        return 42

    asyncio.run(scenario())
