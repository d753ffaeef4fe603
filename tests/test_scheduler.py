"""Scheduler admission/budget/pipeline tests (reference scheduler semantics,
scheduler.py:222-447)."""

import asyncio

import pytest

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
