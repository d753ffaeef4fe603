"""Scheduler admission/budget/pipeline tests (reference scheduler semantics,
scheduler.py:222-447)."""

import asyncio
import threading
import time

import pytest

from torchsnapshot_tpu import phase_stats
from torchsnapshot_tpu.io_types import (
    BufferConsumer,
    BufferStager,
    ReadReq,
    WriteReq,
)
from torchsnapshot_tpu.pg_wrapper import PGWrapper
from torchsnapshot_tpu.scheduler import (
    get_process_memory_budget_bytes,
    sync_execute_read_reqs,
    sync_execute_write_reqs,
)
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin


class _TrackingStager(BufferStager):
    concurrent = 0
    peak_concurrent = 0
    peak_outstanding_bytes = 0
    outstanding_bytes = 0

    def __init__(self, payload: bytes, cost: int):
        self.payload = payload
        self.cost = cost

    async def stage_buffer(self, executor=None):
        cls = _TrackingStager
        cls.concurrent += 1
        cls.outstanding_bytes += self.cost
        cls.peak_concurrent = max(cls.peak_concurrent, cls.concurrent)
        cls.peak_outstanding_bytes = max(
            cls.peak_outstanding_bytes, cls.outstanding_bytes
        )
        await asyncio.sleep(0.001)
        cls.concurrent -= 1
        cls.outstanding_bytes -= self.cost
        return self.payload

    def get_staging_cost_bytes(self) -> int:
        return self.cost

    @classmethod
    def reset(cls):
        cls.concurrent = cls.peak_concurrent = 0
        cls.outstanding_bytes = cls.peak_outstanding_bytes = 0


class _CollectConsumer(BufferConsumer):
    def __init__(self, sink: dict, key: str, cost: int):
        self.sink = sink
        self.key = key
        self.cost = cost

    async def consume_buffer(self, buf, executor=None) -> None:
        self.sink[self.key] = bytes(buf)

    def get_consuming_cost_bytes(self) -> int:
        return self.cost


def test_write_then_read_roundtrip():
    MemoryStoragePlugin.reset()
    storage = MemoryStoragePlugin(root="test_sched")
    _TrackingStager.reset()
    payloads = {f"p{i}": bytes([i]) * (100 + i) for i in range(20)}
    write_reqs = [
        WriteReq(path=k, buffer_stager=_TrackingStager(v, cost=len(v)))
        for k, v in payloads.items()
    ]
    pending = sync_execute_write_reqs(
        write_reqs, storage, memory_budget_bytes=1 << 20, rank=0
    )
    pending.sync_complete()
    assert pending.bytes_total == sum(len(v) for v in payloads.values())

    sink: dict = {}
    read_reqs = [
        ReadReq(path=k, buffer_consumer=_CollectConsumer(sink, k, cost=len(v)))
        for k, v in payloads.items()
    ]
    sync_execute_read_reqs(read_reqs, storage, memory_budget_bytes=1 << 20, rank=0)
    assert sink == payloads


def test_memory_budget_respected():
    MemoryStoragePlugin.reset()
    storage = MemoryStoragePlugin(root="test_budget")
    _TrackingStager.reset()
    # 10 requests of cost 100 with budget 250: at most 2 concurrently staged
    write_reqs = [
        WriteReq(path=f"p{i}", buffer_stager=_TrackingStager(b"x" * 100, cost=100))
        for i in range(10)
    ]
    pending = sync_execute_write_reqs(
        write_reqs, storage, memory_budget_bytes=250, rank=0
    )
    pending.sync_complete()
    assert _TrackingStager.peak_outstanding_bytes <= 250


def test_starvation_guard_admits_oversized_request():
    MemoryStoragePlugin.reset()
    storage = MemoryStoragePlugin(root="test_starve")
    _TrackingStager.reset()
    # Single request far above budget must still be admitted
    write_reqs = [
        WriteReq(path="big", buffer_stager=_TrackingStager(b"y" * 1000, cost=10**9))
    ]
    pending = sync_execute_write_reqs(
        write_reqs, storage, memory_budget_bytes=10, rank=0
    )
    pending.sync_complete()
    assert storage._files["big"] == b"y" * 1000


def test_staging_failure_raises():
    class _FailingStager(BufferStager):
        async def stage_buffer(self, executor=None):
            raise RuntimeError("boom")

        def get_staging_cost_bytes(self) -> int:
            return 10

    MemoryStoragePlugin.reset()
    storage = MemoryStoragePlugin(root="test_fail")
    with pytest.raises(RuntimeError, match="boom"):
        sync_execute_write_reqs(
            [WriteReq(path="x", buffer_stager=_FailingStager())],
            storage,
            memory_budget_bytes=1 << 20,
            rank=0,
        )


def test_read_budget_respected():
    MemoryStoragePlugin.reset()
    storage = MemoryStoragePlugin(root="test_read_budget")
    payloads = {f"p{i}": bytes([i]) * 100 for i in range(10)}
    write_reqs = [
        WriteReq(path=k, buffer_stager=_TrackingStager(v, cost=100))
        for k, v in payloads.items()
    ]
    sync_execute_write_reqs(write_reqs, storage, 1 << 20, 0).sync_complete()

    outstanding = {"now": 0, "peak": 0}

    class _CostedConsumer(_CollectConsumer):
        async def consume_buffer(self, buf, executor=None):
            outstanding["now"] += self.cost
            outstanding["peak"] = max(outstanding["peak"], outstanding["now"])
            await asyncio.sleep(0.001)
            await super().consume_buffer(buf, executor)
            outstanding["now"] -= self.cost

    sink: dict = {}
    read_reqs = [
        ReadReq(path=k, buffer_consumer=_CostedConsumer(sink, k, cost=100))
        for k in payloads
    ]
    # budget 250 with cost-100 items: at most 2 concurrently consuming
    sync_execute_read_reqs(read_reqs, storage, memory_budget_bytes=250, rank=0)
    assert sink == payloads
    assert outstanding["peak"] <= 250


def test_overbudget_requests_do_not_pile_up_awaiting_io():
    """With N over-budget requests and slow storage, the always-admit-one
    guard must not admit the next request while a staged buffer still awaits
    its write — otherwise all N buffers accumulate in host memory, the exact
    condition the budget exists to prevent (reference scheduler.py:266-277
    requires staging, ready-for-io and io all empty)."""
    live = {"now": 0, "peak": 0}

    class _LiveStager(BufferStager):
        def __init__(self, payload: bytes):
            self.payload = payload

        async def stage_buffer(self, executor=None):
            live["now"] += 1
            live["peak"] = max(live["peak"], live["now"])
            await asyncio.sleep(0.001)
            return self.payload

        def get_staging_cost_bytes(self) -> int:
            return 10**9  # far above budget: every admission is via the guard

    class _SlowMemoryStorage(MemoryStoragePlugin):
        async def write(self, write_io):
            await asyncio.sleep(0.02)
            await super().write(write_io)
            live["now"] -= 1  # buffer lifetime ends when the write lands

    MemoryStoragePlugin.reset()
    storage = _SlowMemoryStorage(root="test_pileup")
    write_reqs = [
        WriteReq(path=f"p{i}", buffer_stager=_LiveStager(b"z" * 64))
        for i in range(4)
    ]
    pending = sync_execute_write_reqs(
        write_reqs, storage, memory_budget_bytes=10, rank=0
    )
    pending.sync_complete()
    assert live["peak"] == 1, f"{live['peak']} over-budget buffers were live at once"
    assert len(storage._files) == 4


def _install_budget_probe(monkeypatch):
    """Record every _BudgetTracker the scheduler creates."""
    from torchsnapshot_tpu import scheduler as sched_mod

    created = []
    real = sched_mod._BudgetTracker

    class _Probe(real):
        def __init__(self, budget_bytes):
            super().__init__(budget_bytes)
            self.initial = budget_bytes
            created.append(self)

    monkeypatch.setattr(sched_mod, "_BudgetTracker", _Probe)
    return created


def test_write_failure_drains_and_recredits(monkeypatch, caplog):
    """A mid-pipeline storage failure must cancel-and-drain outstanding
    staging/io tasks (no destroyed-pending-task warnings) and fully re-credit
    the budget (round-1 review item; reference scheduler fails clean)."""
    import gc
    import logging

    class _FailingStorage(MemoryStoragePlugin):
        async def write(self, write_io):
            # Two concurrent failures: the non-raised sibling's exception
            # must still be retrieved during teardown (no asyncio GC noise).
            if write_io.path in ("p3", "p4"):
                raise RuntimeError("injected io failure")
            await asyncio.sleep(0.05)  # keep peers in flight at failure time
            await super().write(write_io)

    MemoryStoragePlugin.reset()
    _TrackingStager.reset()
    storage = _FailingStorage(root="test_drain")
    budgets = _install_budget_probe(monkeypatch)
    write_reqs = [
        WriteReq(path=f"p{i}", buffer_stager=_TrackingStager(b"w" * 100, cost=100))
        for i in range(8)
    ]
    with caplog.at_level(logging.ERROR, logger="asyncio"):
        with pytest.raises(RuntimeError, match="injected io failure"):
            sync_execute_write_reqs(
                write_reqs, storage, memory_budget_bytes=250, rank=0
            )
        gc.collect()  # surface any never-retrieved task exceptions now
    assert not any("Task was destroyed" in r.message for r in caplog.records)
    assert not any("never retrieved" in r.message for r in caplog.records)
    (budget,) = budgets
    assert budget.remaining == budget.initial, "budget not fully re-credited"
    assert budget.inflight == 0


def test_read_failure_drains_and_recredits(monkeypatch, caplog):
    """Same clean-failure contract on the read pipeline."""
    import logging

    MemoryStoragePlugin.reset()
    _TrackingStager.reset()
    storage = MemoryStoragePlugin(root="test_read_drain")
    payloads = {f"p{i}": bytes([i]) * 100 for i in range(8)}
    write_reqs = [
        WriteReq(path=k, buffer_stager=_TrackingStager(v, cost=100))
        for k, v in payloads.items()
    ]
    sync_execute_write_reqs(write_reqs, storage, 1 << 20, 0).sync_complete()

    class _FailingConsumer(_CollectConsumer):
        async def consume_buffer(self, buf, executor=None):
            if self.key == "p3":
                raise RuntimeError("injected consume failure")
            await asyncio.sleep(0.05)
            await super().consume_buffer(buf, executor)

    budgets = _install_budget_probe(monkeypatch)
    sink: dict = {}
    read_reqs = [
        ReadReq(path=k, buffer_consumer=_FailingConsumer(sink, k, cost=100))
        for k in payloads
    ]
    with caplog.at_level(logging.ERROR, logger="asyncio"):
        with pytest.raises(RuntimeError, match="injected consume failure"):
            sync_execute_read_reqs(
                read_reqs, storage, memory_budget_bytes=250, rank=0
            )
    assert not any("Task was destroyed" in r.message for r in caplog.records)
    (budget,) = budgets
    assert budget.remaining == budget.initial, "budget not fully re-credited"
    assert budget.inflight == 0


def test_sync_take_failure_no_metadata(tmp_path):
    """Sync-save failure must not commit .snapshot_metadata (commit
    protocol, sync side — async side covered in test_distributed)."""
    import os
    from unittest import mock

    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.storage_plugins import fs as fs_mod

    class FaultyFS(fs_mod.FSStoragePlugin):
        async def write(self, write_io):
            raise RuntimeError("injected write failure")

    with mock.patch.object(fs_mod, "FSStoragePlugin", FaultyFS):
        with pytest.raises(RuntimeError, match="injected"):
            Snapshot.take(
                str(tmp_path / "snap"),
                {"m": StateDict({"w": np.ones(8, np.float32)})},
            )
    assert not os.path.exists(tmp_path / "snap" / ".snapshot_metadata")


def test_memory_budget_env_override():
    from torchsnapshot_tpu import knobs

    with knobs.override_per_rank_memory_budget_bytes(12345):
        assert get_process_memory_budget_bytes(PGWrapper()) == 12345


def test_memory_budget_default_positive():
    assert get_process_memory_budget_bytes(PGWrapper()) > 0


def test_progress_table_visible_on_slow_storage(caplog):
    """The per-rank progress table (pipeline-state counts + RSS delta +
    budget, reference scheduler.py:98-177) must surface on an interval while
    writes crawl — at pod scale this line is how an operator spots a stuck
    rank."""
    import logging

    from torchsnapshot_tpu import knobs

    class _CrawlingStorage(MemoryStoragePlugin):
        async def write(self, write_io):
            await asyncio.sleep(0.03)
            await super().write(write_io)

    class _SmallStager(BufferStager):
        async def stage_buffer(self, executor=None):
            await asyncio.sleep(0.005)
            return b"x" * 1024

        def get_staging_cost_bytes(self) -> int:
            return 1024

    MemoryStoragePlugin.reset()
    storage = _CrawlingStorage(root="progress")
    write_reqs = [
        WriteReq(path=f"p{i}", buffer_stager=_SmallStager()) for i in range(12)
    ]
    with knobs.override_progress_interval_s(0.01), caplog.at_level(
        logging.INFO, logger="torchsnapshot_tpu.scheduler"
    ):
        pending = sync_execute_write_reqs(
            write_reqs, storage, memory_budget_bytes=1 << 20, rank=3
        )
        pending.sync_complete()
    tables = [r for r in caplog.messages if "write pipeline:" in r]
    assert tables, "no progress table logged on slow storage"
    line = tables[0]
    for field in (
        "[rank 3]",
        "stageable/staging=",
        "writing=",
        "done=",
        "rss",
        "budget=",
    ):
        assert field in line, f"{field!r} missing from: {line}"

    # knob at 0 disables the table entirely
    MemoryStoragePlugin.reset()
    caplog.clear()
    with knobs.override_progress_interval_s(0), caplog.at_level(
        logging.INFO, logger="torchsnapshot_tpu.scheduler"
    ):
        pending = sync_execute_write_reqs(
            [WriteReq(path="q", buffer_stager=_SmallStager())],
            _CrawlingStorage(root="progress2"),
            memory_budget_bytes=1 << 20,
            rank=0,
        )
        pending.sync_complete()
    assert not any("write pipeline:" in m for m in caplog.messages)


def test_pending_io_drain_fails_fast():
    """The PendingIOWork drain must surface the FIRST I/O failure
    immediately — not after every other in-flight write finishes (the
    drain's progress-reporting rewrite must keep gather()'s fail-fast)."""
    import time

    class _FailFastStorage(MemoryStoragePlugin):
        async def write(self, write_io):
            if write_io.path == "poison":
                await asyncio.sleep(0.05)
                raise RuntimeError("poison write failed")
            await asyncio.sleep(1.0)  # healthy writes crawl
            await super().write(write_io)

    class _InstantStager(BufferStager):
        async def stage_buffer(self, executor=None):
            return b"x" * 64

        def get_staging_cost_bytes(self) -> int:
            return 64

    MemoryStoragePlugin.reset()
    storage = _FailFastStorage(root="failfast")
    write_reqs = [
        WriteReq(path=("poison" if i == 0 else f"slow{i}"), buffer_stager=_InstantStager())
        for i in range(6)
    ]
    pending = sync_execute_write_reqs(
        write_reqs, storage, memory_budget_bytes=1 << 20, rank=0
    )
    begin = time.monotonic()
    with pytest.raises(RuntimeError, match="poison"):
        pending.sync_complete()
    elapsed = time.monotonic() - begin
    assert elapsed < 0.9, f"failure surfaced after {elapsed:.2f}s (not fail-fast)"


def test_progress_table_fires_while_budget_blocked_on_hung_storage():
    """The flagship stuck-rank case: storage hangs, the budget is exhausted,
    NO task completes — the table must still fire on its interval (the
    scheduler waits carry the interval as a timeout)."""
    import logging
    import threading
    import time

    from torchsnapshot_tpu import knobs

    release = threading.Event()

    class _HangingStorage(MemoryStoragePlugin):
        async def write(self, write_io):
            while not release.is_set():
                await asyncio.sleep(0.01)
            await super().write(write_io)

    class _BigStager(BufferStager):
        async def stage_buffer(self, executor=None):
            return b"x" * 4096

        def get_staging_cost_bytes(self) -> int:
            return 4096

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = _Capture()
    sched_logger = logging.getLogger("torchsnapshot_tpu.scheduler")
    prior_level = sched_logger.level
    sched_logger.addHandler(handler)
    sched_logger.setLevel(logging.INFO)
    MemoryStoragePlugin.reset()
    try:
        # budget fits ONE request; the second stays budget-blocked while the
        # first's write hangs -> the main loop has nothing completing.
        def _run():
            pending = sync_execute_write_reqs(
                [
                    WriteReq(path="a", buffer_stager=_BigStager()),
                    WriteReq(path="b", buffer_stager=_BigStager()),
                ],
                _HangingStorage(root="hung"),
                memory_budget_bytes=5000,
                rank=7,
            )
            pending.sync_complete()

        with knobs.override_progress_interval_s(0.05):
            t = threading.Thread(target=_run)
            t.start()
            time.sleep(0.6)  # several intervals with storage hung
            blocked_lines = [m for m in records if "write pipeline:" in m]
            release.set()
            t.join(timeout=30)
        assert blocked_lines, "no table line while budget-blocked on hung storage"
        assert "[rank 7]" in blocked_lines[0]
    finally:
        sched_logger.removeHandler(handler)
        sched_logger.setLevel(prior_level)


# ------------------------------------- the read pipeline's loop, leaf by leaf
#
# What the loop thread does for one read does not depend on how many reads are
# pending (one done-callback a task, one event), and a read that landed in
# place with its digest in hand is consumed in the turn it is taken off: no
# task, no executor (the counter ``read_loop``).


class _LandingStorage(MemoryStoragePlugin):
    """The memory plug-in doing what the native fs plug-in does: a read that
    brings a view lands in it and hands that view back, and (``fuse``) the
    digest the request asks for comes with it.  Reads of ``held`` paths wait
    for ``gate``; ``reads`` counts the reads asked for."""

    def __init__(self, root, fuse=True, held=()):
        super().__init__(root)
        self.fuse, self.held, self.reads = fuse, set(held), 0
        self.gate = None

    async def read(self, read_io):
        from torchsnapshot_tpu import integrity

        self.reads += 1
        if read_io.path in self.held:
            if self.gate is None:
                self.gate = asyncio.Event()
            await self.gate.wait()
        await super().read(read_io)
        if read_io.into is not None:
            read_io.into[:] = read_io.buf
            read_io.buf = read_io.into
        if self.fuse and read_io.want_hash:
            read_io.hash64 = integrity._hash64(read_io.buf, read_io.hash_algo)


class _SpyExecutor:
    """In place of the pipeline's executor: counts what is sent to it."""

    submits = 0

    def __init__(self, max_workers=None):
        from concurrent.futures import ThreadPoolExecutor

        self._inner = ThreadPoolExecutor(max_workers=max_workers)

    def submit(self, fn, /, *args, **kwargs):
        type(self).submits += 1
        return self._inner.submit(fn, *args, **kwargs)

    def shutdown(self, *args, **kwargs):
        self._inner.shutdown(*args, **kwargs)


@pytest.fixture
def spy_executor(monkeypatch):
    from torchsnapshot_tpu import scheduler as sched_mod

    _SpyExecutor.submits = 0
    monkeypatch.setattr(sched_mod, "_PhaseInheritingExecutor", _SpyExecutor)
    return _SpyExecutor


_LEAF = 1 << 20  # from this size a read lands in place (array._INTO_PLACE_MIN_BYTES)


def _leaf_reads(storage, names, nbytes=_LEAF, corrupt=(), checksum=True):
    """One leaf a name in ``storage``, and the plan that restores each into a
    fresh host array: ``(read_reqs, {name: (future, bytes)})``."""
    import numpy as np

    from torchsnapshot_tpu import integrity
    from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer
    from torchsnapshot_tpu.manifest import TensorEntry

    read_reqs, want = [], {}
    for k, name in enumerate(names):
        data = np.random.RandomState(k).bytes(nbytes)
        stored = bytearray(data)
        if name in corrupt:
            stored[nbytes // 2] ^= 0x01
        storage._files[name] = bytes(stored)
        entry = TensorEntry(
            location=name, serializer="buffer_protocol", dtype="uint8", shape=[nbytes],
            replicated=False, checksum=integrity.digest(data) if checksum else None,
        )  # fmt: skip
        reqs, fut = ArrayIOPreparer.prepare_read(entry)
        read_reqs += reqs
        want[name] = (fut, data)
    return read_reqs, want


def _read_loop_of(run):
    from torchsnapshot_tpu import phase_stats

    before = phase_stats.snapshot()
    run()
    return phase_stats.delta(before).get("read_loop")


def test_a_pending_read_is_registered_once_however_many_turns_pass():
    """1,000 reads held behind a gate while 24 others come back one by one
    (each only once the one before it is consumed, so each in a turn of its
    own): the pipeline registers one done-callback a task, when it makes it,
    and a turn registers nothing on a task that cannot finish (before, every
    turn added and removed one on every pending task)."""
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.scheduler import execute_read_reqs

    registrations = []

    class _CountingTask(asyncio.Task):
        def add_done_callback(self, fn, *, context=None):
            if self.get_coro().__name__ != "execute_read_reqs":  # (the loop's own)
                registrations.append(self)
            super().add_done_callback(fn, context=context)

    MemoryStoragePlugin.reset()
    held = [f"held{i}" for i in range(1000)]
    free = [f"free{i}" for i in range(24)]
    sink: dict = {}

    class _OneByOne(_LandingStorage):
        async def read(self, read_io):
            if read_io.path in free:
                while len(sink) < free.index(read_io.path):
                    await asyncio.sleep(0.001)
            await super().read(read_io)

    storage = _OneByOne("registered_once", held=held)
    for name in held + free:
        storage._files[name] = name.encode()

    class _LastOpensTheGate(_CollectConsumer):
        async def consume_buffer(self, buf, executor=None):
            await super().consume_buffer(buf, executor)
            if len(sink) == len(free):
                storage.gate.set()  # every free read consumed: let the rest go

    # smallest first: the held reads are dispatched first and wait, pending,
    # through every turn the free ones take
    read_reqs = [
        ReadReq(path=n, buffer_consumer=_LastOpensTheGate(sink, n, cost=1)) for n in held
    ] + [ReadReq(path=n, buffer_consumer=_LastOpensTheGate(sink, n, cost=2)) for n in free]
    loop = asyncio.new_event_loop()
    loop.set_task_factory(lambda loop, coro, **kw: _CountingTask(coro, loop=loop, **kw))
    stats = {}
    try:
        with knobs.override_max_per_rank_io_concurrency(2000):
            stats = _read_loop_of(
                lambda: loop.run_until_complete(
                    execute_read_reqs([read_reqs], storage, 1 << 30, 0)
                )
            )
    finally:
        loop.close()
    assert len(sink) == 1024
    tasks = 2 * 1024  # a read and a consume each
    assert len(registrations) == tasks == len(set(registrations))
    assert stats["taken"] == tasks and stats["handed"] == 1024 and stats["inline"] == 0
    assert stats["max_pending"] >= 1000
    # each free read was taken off and consumed in turns of its own while
    # the thousand waited
    assert stats["turns"] >= 2 * len(free)


def test_a_read_landed_in_place_with_its_digest_never_reaches_the_executor(spy_executor):
    MemoryStoragePlugin.reset()
    storage = _LandingStorage("inline")
    read_reqs, want = _leaf_reads(storage, [f"leaf{i}" for i in range(6)])
    stats = _read_loop_of(lambda: sync_execute_read_reqs(read_reqs, storage, 1 << 30, 0))
    assert spy_executor.submits == 0
    assert stats["inline"] == 6 and stats["handed"] == 0
    assert stats["taken"] == 6 and stats["n"] == 1 and 1 <= stats["turns"] <= 6
    for fut, data in want.values():
        assert fut.obj.tobytes() == data
    # no checksum on the entry, or checksums off: nothing to check, inline too
    read_reqs, want = _leaf_reads(storage, ["bare0", "bare1"], checksum=False)
    stats = _read_loop_of(lambda: sync_execute_read_reqs(read_reqs, storage, 1 << 30, 0))
    assert (stats["inline"], stats["handed"], spy_executor.submits) == (2, 0, 0)
    from torchsnapshot_tpu import knobs

    unfused = _LandingStorage("inline", fuse=False)
    read_reqs, want = _leaf_reads(unfused, ["off0"], corrupt=["off0"])
    with knobs.override_env(knobs.CHECKSUM_ENV_VAR, "0"):
        stats = _read_loop_of(lambda: sync_execute_read_reqs(read_reqs, unfused, 1 << 30, 0))
    assert (stats["inline"], stats["handed"], spy_executor.submits) == (1, 0, 0)


def test_a_wrong_fused_digest_fails_inline_and_leaves_the_budget_whole(
    monkeypatch, spy_executor, caplog
):
    import logging

    from torchsnapshot_tpu.integrity import ChecksumError

    MemoryStoragePlugin.reset()
    names = [f"leaf{i}" for i in range(8)]
    # the corrupt leaf comes back while four others are still being read
    storage = _LandingStorage("inline_corrupt", held=names[4:])
    read_reqs, want = _leaf_reads(storage, names, corrupt=["leaf2"])
    budgets = _install_budget_probe(monkeypatch)
    with caplog.at_level(logging.ERROR, logger="asyncio"):
        with pytest.raises(ChecksumError, match="Checksum mismatch for leaf2"):
            sync_execute_read_reqs(read_reqs, storage, 1 << 30, 0)
    assert spy_executor.submits == 0  # found on the loop thread, by a compare
    assert storage.reads == 8  # all dispatched: the held four were cancelled
    assert not any("Task was destroyed" in r.message for r in caplog.records)
    assert not any("never retrieved" in r.message for r in caplog.records)
    (budget,) = budgets
    assert budget.remaining == budget.initial, "budget not fully re-credited"
    assert budget.inflight == 0
    assert want["leaf2"][0].obj is None  # never handed on


@pytest.mark.parametrize("case", ["no_fused_digest", "framed", "merged_slab"])
def test_a_read_with_work_left_is_handed_on_and_verified_as_before(case, spy_executor):
    """The short path is taken read by read from what the read shows: a
    checksum with no digest fused into the read is hashed on the executor, a
    framed payload decoded there, a merged slab read gathers its members."""
    import numpy as np

    from torchsnapshot_tpu import batcher, compression, integrity
    from torchsnapshot_tpu.integrity import ChecksumError
    from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer
    from torchsnapshot_tpu.manifest import TensorEntry

    def plan(corrupt):
        MemoryStoragePlugin.reset()
        storage = _LandingStorage(f"handed_{case}", fuse=case != "no_fused_digest")
        if case == "no_fused_digest":
            return (storage, *_leaf_reads(storage, ["leaf"], 2 * _LEAF, corrupt=["leaf"] * corrupt))
        if case == "framed":
            data = bytes(range(256)) * (8 * _LEAF // 256)
            frame, codec = compression.encode(data, "zlib")
            frame = bytearray(frame)
            entry = TensorEntry(
                location="leaf", serializer="buffer_protocol", dtype="uint8", shape=[len(data)],
                replicated=False, checksum=integrity.digest(bytes(frame)), codec=codec,
                compressed_nbytes=len(frame),
            )  # fmt: skip
            frame[len(frame) // 2] ^= corrupt
            storage._files["leaf"] = bytes(frame)
            reqs, fut = ArrayIOPreparer.prepare_read(entry)
            return storage, reqs, {"leaf": (fut, data)}
        members = [np.random.RandomState(k).bytes(1000) for k in range(3)]
        slab = bytearray(b"".join(members))
        reqs, want = [], {}
        for k, data in enumerate(members):
            entry = TensorEntry(
                location="batched/slab", serializer="buffer_protocol", dtype="uint8",
                shape=[1000], replicated=False, byte_range=[1000 * k, 1000 * (k + 1)],
                checksum=integrity.digest(data),
            )  # fmt: skip
            member_reqs, fut = ArrayIOPreparer.prepare_read(entry)
            reqs += member_reqs
            want[k] = (fut, data)
        slab[1500] ^= corrupt
        storage._files["batched/slab"] = bytes(slab)
        return storage, batcher.batch_read_requests(reqs), want

    storage, read_reqs, want = plan(corrupt=0)
    assert len(read_reqs) == 1
    stats = _read_loop_of(lambda: sync_execute_read_reqs(read_reqs, storage, 1 << 30, 0))
    assert (stats["inline"], stats["handed"], stats["taken"]) == (0, 1, 2)
    for fut, data in want.values():
        assert fut.obj.tobytes() == data
    if case != "merged_slab":  # (its members are under the executor's megabyte)
        assert spy_executor.submits == 1
    storage, read_reqs, _ = plan(corrupt=1)
    with pytest.raises(ChecksumError, match="Checksum mismatch for"):
        sync_execute_read_reqs(read_reqs, storage, 1 << 30, 0)


# ------------------------------------------------------ the slot hand-over
#
# A plug-in that reads blocking (``read_blocking`` on ``read_executor``'s
# workers, as the fs plug-in offers) has its reads run by the slot hand-over
# (``scheduler._DirectReads``): no read is a task, the workers hand the io
# slots on themselves, and a finished read is posted with one coalesced
# wake-up of the loop.


class _BlockingStorage(MemoryStoragePlugin):
    """The memory plug-in with the blocking entry the fs plug-in offers: a
    read lands in the view it brings, with its digest, as fs does.  ``hook``
    is called with the path as each read starts, on the worker (it may sleep
    or raise); what ran when, and on which thread, is recorded."""

    def __init__(self, root, hook=None, workers=16):
        from concurrent.futures import ThreadPoolExecutor

        super().__init__(root)
        self.hook = hook
        self.pool = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="blocking_io")
        self.lock = threading.Lock()
        self.running = self.peak = 0
        self.started, self.ended = [], []  # (path, thread name, monotonic)

    def read_executor(self):
        return self.pool

    def read_blocking(self, read_io):
        from torchsnapshot_tpu import integrity

        me = threading.current_thread().name
        with self.lock:
            self.running += 1
            self.peak = max(self.peak, self.running)
            self.started.append((read_io.path, me, time.monotonic()))
        try:
            if self.hook is not None:
                self.hook(read_io.path)
            data = self._files[read_io.path]
            if read_io.byte_range is not None:
                data = data[read_io.byte_range[0] : read_io.byte_range[1]]
            if read_io.into is not None:
                read_io.into[:] = data
                read_io.buf = read_io.into
            else:
                read_io.buf = bytearray(data)
            if read_io.want_hash:
                read_io.hash64 = integrity._hash64(read_io.buf, read_io.hash_algo)
        finally:
            with self.lock:
                self.running -= 1
                self.ended.append((read_io.path, me, time.monotonic()))


def _groups_of(storage, sink, groups=3, per_group=8, nbytes=64):
    """``groups`` groups of small payloads (consumed as tasks into ``sink``),
    each group in the order it will be dispatched (its costs ascending)."""
    out = []
    for g in range(groups):
        group = []
        for i in range(per_group):
            path = f"g{g}/p{i:02d}"
            storage._files[path] = bytes([g * 16 + i]) * nbytes
            group.append(ReadReq(path=path, buffer_consumer=_CollectConsumer(sink, path, cost=1 + i)))
        out.append(group)
    return out


def _load_in_turn(pipeline, n_groups, load_s=0.03):
    """The loader's side of a ``ReadAhead``: each group waited for, held a
    while, and marked loaded; when each was."""
    loaded_at = []
    try:
        for group in range(n_groups):
            pipeline.wait_consumed(group)
            time.sleep(load_s)
            loaded_at.append(time.monotonic())
            pipeline.mark_loaded(group)
    finally:
        pipeline.close()
    return loaded_at


def test_the_slot_hand_over_keeps_io_cap_the_order_and_the_look_ahead():
    """Three groups of eight reads behind three io slots: never more than
    three run at once; within a group the reads start in dispatch order (a
    read can pass only those fewer than three places in front of it, which
    started beside it); a group is read once the last read of the one before
    it has finished, and not before the one before that is loaded."""
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.scheduler import ReadAhead

    MemoryStoragePlugin.reset()
    storage = _BlockingStorage("hand_over_order", hook=lambda path: time.sleep(0.005))
    sink: dict = {}
    groups = _groups_of(storage, sink)
    cap = 3
    before = phase_stats.snapshot()
    with knobs.override_max_per_rank_io_concurrency(cap):
        pipeline = ReadAhead(groups, storage, 1 << 20, rank=0)
        loaded_at = _load_in_turn(pipeline, len(groups))
    assert sink == {path: data for path, data in storage._files.items()}
    assert 1 < storage.peak <= cap
    start = {path: t for path, _, t in storage.started}
    end = {path: t for path, _, t in storage.ended}
    for g, group in enumerate(groups):
        order = [rr.path for rr in group]
        for i, early in enumerate(order):
            for late in order[i + cap :]:
                assert start[early] <= start[late], (early, late)
        if g:
            assert min(start[p] for p in order) >= max(end[rr.path] for rr in groups[g - 1])
        if g >= 2:
            assert min(start[p] for p in order) >= loaded_at[g - 2]
    # every read ran on the plug-in's workers, and none was a task
    assert {name for _, name, _ in storage.started} <= {
        f"blocking_io_{k}" for k in range(16)
    }
    loop = pipeline.read_loop
    reqs = sum(len(group) for group in groups)
    assert loop["direct"] == reqs and loop["handed"] == reqs
    assert loop["taken"] == 2 * reqs and 1 <= loop["wakes"] <= reqs
    counter = phase_stats.delta(before)["read_loop"]
    assert counter["direct"] == reqs and counter["wakes"] == loop["wakes"]
    storage.pool.shutdown()


def test_queued_reads_start_on_the_worker_while_the_loop_thread_is_held():
    """One io slot and thirty reads of 10 ms: while the pipeline's loop
    thread sleeps inside a callback for 0.3 s, the worker that finished a read
    starts the next queued one itself, read after read, on the same thread."""
    from torchsnapshot_tpu import knobs
    from torchsnapshot_tpu.scheduler import ReadAhead

    held, made = [], []

    def hold():
        held.append(time.monotonic())
        time.sleep(0.3)
        held.append(time.monotonic())

    def hook(path):
        if path == "g0/p00":  # the first read: the loop thread is held now
            while not made:
                time.sleep(0.001)
            made[0]._loop.call_soon_threadsafe(hold)
        time.sleep(0.01)

    MemoryStoragePlugin.reset()
    storage = _BlockingStorage("hand_over_held", hook=hook)
    sink: dict = {}
    groups = _groups_of(storage, sink, groups=1, per_group=30)
    with knobs.override_max_per_rank_io_concurrency(1):
        made.append(ReadAhead(groups, storage, 1 << 20, rank=0))
        _load_in_turn(made[0], 1, load_s=0)
    assert len(sink) == 30 and len(held) == 2
    assert storage.peak == 1
    assert len({name for _, name, _ in storage.started}) == 1  # one job: the slot handed on
    during = [t for _, _, t in storage.started if held[0] < t < held[1]]
    assert len(during) >= 10, (len(during), held)
    # the reads that finished while the loop was held posted one wake-up
    loop = made[0].read_loop
    assert loop["direct"] == 30 and loop["wakes"] <= 30 - len(during) + 2
    storage.pool.shutdown()


def test_a_transient_read_failure_on_the_hand_over_is_retried_after_its_backoff(
    monkeypatch, caplog
):
    import logging

    from torchsnapshot_tpu import retry
    from torchsnapshot_tpu.retry import StorageTransientError

    failed = []

    def hook(path):
        if path == "g0/p03" and not failed:
            failed.append(time.monotonic())
            raise StorageTransientError("injected 503")

    monkeypatch.setattr(retry, "backoff_s", lambda attempt, **kwargs: 0.1)
    MemoryStoragePlugin.reset()
    storage = _BlockingStorage("hand_over_retry", hook=hook)
    sink: dict = {}
    (group,) = _groups_of(storage, sink, groups=1)
    events = []
    from torchsnapshot_tpu.event_handlers import (
        register_event_handler,
        unregister_event_handler,
    )

    register_event_handler(events.append)
    try:
        with caplog.at_level(logging.WARNING, logger="torchsnapshot_tpu.scheduler"):
            stats = _read_loop_of(lambda: sync_execute_read_reqs(group, storage, 1 << 20, 0))
    finally:
        unregister_event_handler(events.append)
    assert sink == dict(storage._files)
    tries = [t for path, _, t in storage.started if path == "g0/p03"]
    assert len(tries) == 2 and tries[1] - failed[0] >= 0.1  # its backoff, outside any slot
    retries = [e for e in events if e.name == "scheduler.read_retry"]
    assert len(retries) == 1 and retries[0].metadata["attempt"] == 1
    assert any("transient read failure for g0/p03" in r.getMessage() for r in caplog.records)
    assert stats["direct"] == 8 and stats["taken"] == 8 + 1 + 8  # the failed try is a completion
    storage.pool.shutdown()


def test_a_fatal_read_failure_on_the_hand_over_waits_for_the_running_reads(monkeypatch):
    """The first read fails at once while three others run for 0.3 s: the
    error is raised only once those three have returned (no range may be
    given back while a worker writes into it), the queued reads never start,
    and the budget is whole again."""
    from torchsnapshot_tpu import knobs

    def hook(path):
        if path == "g0/p00":
            raise ValueError("injected fatal read failure")
        time.sleep(0.3)

    MemoryStoragePlugin.reset()
    storage = _BlockingStorage("hand_over_fatal", hook=hook)
    sink: dict = {}
    (group,) = _groups_of(storage, sink, groups=1, per_group=10)
    budgets = _install_budget_probe(monkeypatch)
    with knobs.override_max_per_rank_io_concurrency(4):
        with pytest.raises(ValueError, match="injected fatal read failure"):
            sync_execute_read_reqs(group, storage, 1 << 20, 0)
    raised = time.monotonic()
    assert len(storage.ended) == len(storage.started) < 10
    assert all(t <= raised for _, _, t in storage.ended)
    assert max(t for _, _, t in storage.ended) - storage.started[0][2] >= 0.3
    (budget,) = budgets
    assert budget.remaining == budget.initial and budget.inflight == 0
    storage.pool.shutdown()


TOY_LEAVES = 100  # a stateful, two statefuls


@pytest.mark.parametrize("plugin", ["fs", "faults", "memory"])
def test_a_read_on_the_hand_over_costs_the_loop_three_handles_or_fewer(
    tmp_path, monkeypatch, plugin
):
    """A toy restore of two hundred leaves of four pages through an arena of
    eight leaves (the CPU backend taken for an accelerator, whose copy a
    range at 16 bytes past a 64-byte boundary is): the callbacks the
    pipeline's loop is handed (``call_soon`` and ``call_soon_threadsafe``),
    over the requests.  The fs plug-in reads blocking: its reads run on the
    slot hand-over (``direct`` is the requests), each costs the pool's grant
    where it waited, a share of one coalesced wake-up and of the main task's
    step: three or fewer.  Under the fault injector's wrapper, which has no
    blocking read, every read is a task of about seven (its start, the
    grant's future and its wake, the executor's future and its wake, the
    done-callback, the main task's step), and so on the memory plug-in:
    ``direct`` 0.  The same bytes come back every way."""
    import collections

    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict, knobs
    from torchsnapshot_tpu import h2d as h2d_mod
    from torchsnapshot_tpu import scheduler as sched_mod
    from torchsnapshot_tpu.event_handlers import (
        register_event_handler,
        unregister_event_handler,
    )
    from torchsnapshot_tpu.io_preparers import array as array_mod

    page = h2d_mod._PAGE
    leaf = 4 * page

    def unaligned(nbytes):
        raw = np.empty(nbytes + 128, dtype=np.uint8)
        offset = (16 - raw.ctypes.data) % 64
        return raw[offset : offset + nbytes]

    monkeypatch.setattr(array_mod, "_INTO_PLACE_MIN_BYTES", page)
    monkeypatch.setattr(h2d_mod, "_keeps_host_memory", lambda target: False)
    monkeypatch.setattr(h2d_mod, "_arena_memory", unaligned)
    real_pool_init = h2d_mod.HostBufferPool.__init__

    def pool_with_a_window_of_eight_leaves(self):
        real_pool_init(self)
        self.batcher = h2d_mod.H2DBatcher(
            self, flush_bytes=2 * leaf, inflight_cap_bytes=8 * leaf
        )

    monkeypatch.setattr(
        h2d_mod.HostBufferPool, "__init__", pool_with_a_window_of_eight_leaves
    )

    def app(zero):
        rng = np.random.RandomState(5)
        return {
            key: StateDict(
                {
                    f"w{i:03d}": jnp.zeros((leaf // 4,), jnp.float32)
                    if zero
                    else jnp.asarray(rng.rand(leaf // 4), jnp.float32)
                    for i in range(TOY_LEAVES)
                }
            )
            for key in ("a_params", "b_mu")
        }

    MemoryStoragePlugin.reset()
    path = f"memory://toy_{time.monotonic_ns()}" if plugin == "memory" else str(tmp_path / "snap")
    saved = app(zero=False)
    Snapshot.take(path, saved)

    handles = collections.Counter()
    real_soon = asyncio.BaseEventLoop.call_soon
    real_threadsafe = asyncio.BaseEventLoop.call_soon_threadsafe

    def call_soon(self, *args, **kwargs):
        handles[id(self)] += 1
        return real_soon(self, *args, **kwargs)

    def call_soon_threadsafe(self, *args, **kwargs):
        handles[id(self)] += 1
        return real_threadsafe(self, *args, **kwargs)

    pipelines = []
    real_init = sched_mod.ReadAhead.__init__

    def init(self, *args, **kwargs):
        pipelines.append(self)
        real_init(self, *args, **kwargs)

    monkeypatch.setattr(sched_mod.ReadAhead, "__init__", init)
    ends = []

    def on_event(event):
        if event.name == "restore.end":
            ends.append(dict(event.metadata))

    target = app(zero=True)
    register_event_handler(on_event)
    monkeypatch.setattr(asyncio.BaseEventLoop, "call_soon", call_soon)
    monkeypatch.setattr(asyncio.BaseEventLoop, "call_soon_threadsafe", call_soon_threadsafe)
    try:
        with knobs.override_faults("none" if plugin == "faults" else None):
            Snapshot(path).restore(target)
    finally:
        monkeypatch.undo()
        unregister_event_handler(on_event)
    for key in saved:
        for name, want in saved[key].state_dict().items():
            np.testing.assert_array_equal(np.asarray(target[key][name]), np.asarray(want))
    (end,) = ends
    (pipeline,) = pipelines
    loop = end["read_loop"]
    reqs = loop["inline"] + loop["handed"]
    assert reqs >= 2 * TOY_LEAVES
    per_read = handles[id(pipeline._loop)] / reqs
    arena = end["arena_turn"]
    assert arena["ranges"] == 2 * TOY_LEAVES and arena["turn_bs"] == pytest.approx(
        sum(arena[stage + "_bs"] for stage in h2d_mod._STAGES)
    )
    assert arena["grant_s"] > 0  # reads waited for room, and were granted
    if plugin == "fs":
        assert loop["direct"] == reqs and loop["inline"] == 2 * TOY_LEAVES
        assert 1 <= loop["wakes"] <= reqs
        assert per_read <= 3, per_read
    else:
        assert loop["direct"] == loop["wakes"] == 0
        if plugin == "faults":
            assert per_read >= 5, per_read
