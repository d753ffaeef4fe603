"""Read-ahead across statefuls: one read pipeline serves the whole restore
(``scheduler.ReadAhead``).  On a slow fake storage plug-in: the next
stateful is read while the one before it loads (a read that finds no room in
the restore's host arena is dispatched then and asks storage once a landing
has freed some, which its own wait sets off); nothing of it is consumed or
sent to the device before that load has returned; the look-ahead is one
stateful, and a read of it parked with its range held starves no read of the
stateful in front; user code stays on the calling thread, in key order; a
failure ahead leaves what was loaded loaded and no thread behind."""

import asyncio
import collections
import gc
import threading
import time
import types
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import RNGState, Snapshot, StateDict, integrity, knobs, phase_stats
from torchsnapshot_tpu import scheduler as scheduler_mod
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.event_handlers import (
    register_event_handler,
    unregister_event_handler,
)
from torchsnapshot_tpu import h2d as h2d_mod
from torchsnapshot_tpu.h2d import H2DBatcher
from torchsnapshot_tpu.io_preparers import array as array_mod
from torchsnapshot_tpu.io_types import BufferConsumer, ReadReq, StoragePlugin
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin

KEYS = ("s0", "s1", "s2", "s3")
LEAVES = 3
SHAPE = (512, 1024)  # 2 MiB of float32: over the size from which a read lands in place
READ_S = 0.03  # one storage read
LOAD_S = 0.15  # one load_state_dict: several reads long, so the gate binds
PIPELINE_THREADS = (
    "tpusnap-read-pipeline",
    "tpusnap-h2d-dispatcher",
    "tpusnap-h2d-lander",
)


class Log:
    """``(what, key, monotonic, thread name)`` from every thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rows = []

    def add(self, what, key):
        with self._lock:
            self.rows.append(
                (what, key, time.monotonic(), threading.current_thread().name)
            )

    def times(self, what, key=None):
        return [t for w, k, t, _ in self.rows if w == what and key in (None, k)]

    def first(self, what, key):
        return min(self.times(what, key))

    def last(self, what, key):
        return max(self.times(what, key))


class Recorder(StateDict):
    """A stateful whose load lasts, and says when and on which thread."""

    def __init__(self, key, data, log, load_s=LOAD_S):
        super().__init__(data)
        self._key, self._log, self._load_s = key, log, load_s

    def state_dict(self):
        self._log.add("state_dict", self._key)
        return super().state_dict()

    def load_state_dict(self, state_dict):
        self._log.add("load_begin", self._key)
        time.sleep(self._load_s)
        super().load_state_dict(state_dict)
        self._log.add("load_end", self._key)


def stateful_of(path):
    """``0/<key>/<leaf>``: with batching off a payload's path names its stateful."""
    parts = path.split("/")
    return parts[1] if len(parts) >= 3 and parts[1] in KEYS else None


def land_in_place(read_io):
    """What the native fs plug-in does with a read that brings a view: the
    bytes land there, the view comes back as the read's buffer, and the
    digest the request asks for comes with it."""
    if read_io.into is not None:
        read_io.into[:] = read_io.buf
        read_io.buf = read_io.into
    if read_io.want_hash:
        read_io.hash64 = integrity._hash64(read_io.buf, read_io.hash_algo)


class SlowStorage(StoragePlugin):
    """The memory plug-in with reads that take ``READ_S`` and can fail, and
    (``in_place``) land in the view they bring with their digest fused."""

    def __init__(
        self, inner, log, fail_key=None, fail_after=None, on_read=None, in_place=False
    ):
        self._inner, self._log, self._in_place = inner, log, in_place
        self._fail_key, self._fail_after, self._on_read = fail_key, fail_after, on_read

    async def read(self, read_io):
        key = stateful_of(read_io.path)
        if key is None:
            return await self._inner.read(read_io)
        self._log.add("read_begin", key)
        if self._on_read is not None:
            self._on_read(key)
        await asyncio.sleep(READ_S)
        if key == self._fail_key:
            # terminal, not transient: no retry; and only once the stateful
            # before it is inside its load
            while not self._log.times(*self._fail_after):
                await asyncio.sleep(0.005)
            raise ValueError(f"injected read failure in {key}")
        await self._inner.read(read_io)
        if self._in_place:
            land_in_place(read_io)
        self._log.add("read_end", key)

    async def write(self, write_io):
        await self._inner.write(write_io)

    async def delete(self, path):
        await self._inner.delete(path)

    async def delete_dir(self, path):
        await self._inner.delete_dir(path)

    async def close(self):
        await self._inner.close()


def unaligned_memory(nbytes):
    """``(raw, buf)``: memory that begins 16 bytes off a 64-byte boundary, as
    a large ``np.empty`` does, and the allocation under it.  The CPU backend
    copies such a buffer on ``device_put``, and every range of it a whole
    number of pages on (one at a 64-byte boundary it takes as the array
    itself), so what happens to it is what happens on an accelerator."""
    raw = np.empty(nbytes + 80, dtype=np.uint8)
    offset = (16 - raw.ctypes.data) % 64
    return raw, raw[offset : offset + nbytes]


# One tree in every stateful, as a train state's parameters and moments are.
# Or a tree of its own in each, no two leaves of a size: a range of the arena
# is any size, so the one is read ahead as the other is.
TREES = {
    "twins": {key: SHAPE for key in KEYS},
    "no_twins": {key: (SHAPE[0] + 64 * k, SHAPE[1]) for k, key in enumerate(KEYS)},
}


def make_app(
    log, keys=KEYS, zero=False, dtype=jnp.float32, seed=0, load_s=LOAD_S, tree="twins"
):
    rng = np.random.RandomState(seed)
    app = {}
    for key in keys:
        shape = TREES[tree][key]
        data = {
            f"w{i}": jnp.zeros(shape, dtype)
            if zero
            else jnp.asarray(rng.rand(*shape), dtype)
            for i in range(LEAVES)
        }
        app[key] = Recorder(key, data, log, load_s)
    return app


@pytest.fixture
def world(monkeypatch, request):
    """A snapshot of ``KEYS`` in memory (the ``twins`` tree, or the one a
    test names), and every seam recorded: reads (when the pipeline
    dispatches one, and when storage is asked, slowly), plans, host buffers
    (the CPU backend taken for an accelerator; a weak reference to each arena
    made and its size, when a read has its range, and when one is given
    back), consumes, H2D submits and dispatches, each with its stateful.  A
    test may give the restore's batcher other arguments (``batcher_args``)."""
    MemoryStoragePlugin.reset()
    log = Log()
    url = f"memory://read_ahead_{time.monotonic_ns()}"
    tree = getattr(request, "param", "twins")
    saved = make_app(Log(), tree=tree)
    with knobs.override_batching_disabled(True):
        Snapshot.take(url, saved)

    storage_args = {}
    real_plugin = snapshot_mod.url_to_storage_plugin
    monkeypatch.setattr(
        snapshot_mod,
        "url_to_storage_plugin",
        lambda path, options=None: SlowStorage(
            real_plugin(path, options), log, **storage_args
        ),
    )

    leaves = {}  # id of a leaf's future -> its stateful
    pools = []  # the pool of each restore
    real_plan = Snapshot._plan_stateful_reads

    def recording_plan(key, stateful, metadata, rank, host_pool):
        plan = real_plan(key, stateful, metadata, rank, host_pool)
        into_place = [rr for rr in plan.read_reqs if rr.into is not None]
        assert len(into_place) == (LEAVES if key in KEYS else 0)
        leaves.update((id(fut), key) for fut in plan.futures.values())
        if host_pool not in pools:
            pools.append(host_pool)
        return plan

    monkeypatch.setattr(Snapshot, "_plan_stateful_reads", staticmethod(recording_plan))

    arenas, arena_sizes = [], []  # a weak reference to every arena made

    def recording_arena(nbytes):
        raw, buf = unaligned_memory(nbytes)
        arenas.append(weakref.ref(raw))
        arena_sizes.append(nbytes)
        return buf

    monkeypatch.setattr(h2d_mod, "_arena_memory", recording_arena)
    monkeypatch.setattr(h2d_mod, "_keeps_host_memory", lambda target: False)
    plain = []  # the size of every plain buffer made beside an arena
    monkeypatch.setattr(
        h2d_mod,
        "_fresh_host_buffer",
        lambda nbytes: plain.append(nbytes) or np.empty(nbytes, dtype=np.uint8),
    )
    batcher_args = {}
    real_pool_init = h2d_mod.HostBufferPool.__init__

    def pool_with_batcher_args(self):
        real_pool_init(self)
        self.batcher = H2DBatcher(self, **batcher_args)

    monkeypatch.setattr(h2d_mod.HostBufferPool, "__init__", pool_with_batcher_args)

    def key_of_items(items):
        """The stateful of the leaves ``items``, or all of theirs where they
        are not one stateful's."""
        keys = sorted({leaves[id(fut)] for _, _, fut, _ in items})
        return keys[0] if len(keys) == 1 else tuple(keys)

    real_give = h2d_mod.HostBufferPool.give
    lent_to = {}  # where a range begins -> the stateful whose leaf is in it

    def recording_give(self, buf, recycle):
        log.add("buffer_back", lent_to.pop(buf.ctypes.data))
        return real_give(self, buf, recycle)

    monkeypatch.setattr(h2d_mod.HostBufferPool, "give", recording_give)
    real_take = scheduler_mod._ReadPipeline.take_memory
    real_consume = scheduler_mod._ReadPipeline.consume_buffer

    async def recording_take(self):
        key = stateful_of(self.read_req.path)
        log.add("read_dispatch", key)
        await real_take(self)
        log.add("range_taken", key)


    async def recording_consume(self, executor):
        log.add("consume_begin", stateful_of(self.read_req.path))
        return await real_consume(self, executor)

    real_landed = scheduler_mod._ReadPipeline.consume_landed

    def recording_landed(self):
        key = stateful_of(self.read_req.path)
        log.add("consume_begin", key)  # either way: consume_buffer comes next
        landed = real_landed(self)
        if landed:
            log.add("consume_inline", key)
        return landed

    monkeypatch.setattr(scheduler_mod._ReadPipeline, "consume_landed", recording_landed)
    monkeypatch.setattr(scheduler_mod._ReadPipeline, "take_memory", recording_take)
    monkeypatch.setattr(scheduler_mod._ReadPipeline, "consume_buffer", recording_consume)
    real_submit, real_dispatch = H2DBatcher.submit, H2DBatcher._dispatch

    def recording_submit(self, host, like, fut, lease=None):
        log.add("h2d_submit", leaves[id(fut)])
        if lease is not None:
            lent_to[lease.ctypes.data] = leaves[id(fut)]
        return real_submit(self, host, like, fut, lease)

    def recording_dispatch(self, items):
        log.add("h2d_dispatch", key_of_items(items))
        return real_dispatch(self, items)

    monkeypatch.setattr(H2DBatcher, "submit", recording_submit)
    monkeypatch.setattr(H2DBatcher, "_dispatch", recording_dispatch)

    return types.SimpleNamespace(
        log=log,
        url=url,
        tree=tree,
        saved=saved,
        pools=pools,
        arenas=arenas,
        arena_sizes=arena_sizes,
        plain=plain,
        storage_args=storage_args,
        batcher_args=batcher_args,
    )


def restore(world, target):
    ends = []

    def on_event(event):
        if event.name == "restore.end":
            ends.append(dict(event.metadata))

    before = phase_stats.snapshot()
    register_event_handler(on_event)
    try:
        Snapshot(world.url).restore(target)
    finally:
        unregister_event_handler(on_event)
    return phase_stats.delta(before), ends


def assert_equal_bits(target, saved, keys=KEYS):
    for key in keys:
        for name, want in saved[key].state_dict().items():
            got = target[key].state_dict()[name]
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(
                np.asarray(got).view(np.uint8), np.asarray(want).view(np.uint8)
            )


def no_pipeline_thread_alive():
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        alive = [t.name for t in threading.enumerate() if t.name in PIPELINE_THREADS]
        if not alive:
            return True
        time.sleep(0.01)
    return False


@pytest.mark.parametrize("world", ["twins", "no_twins"], indirect=True)
def test_the_next_stateful_is_read_while_this_one_loads(world):
    target = make_app(world.log, zero=True, tree=world.tree)
    delta, (end,) = restore(world, target)
    log = world.log
    for this, ahead in zip(KEYS, KEYS[1:]):
        assert log.first("read_begin", ahead) < log.last("load_end", this), (this, ahead)
        # ... and not beside this one's reads, whose rate it would share: the
        # read-ahead begins where the tail begins
        assert log.first("read_dispatch", ahead) >= log.last("read_end", this), (this, ahead)
        assert log.first("read_dispatch", ahead) < log.first("load_begin", this), (this, ahead)
    # whether its leaves are of the sizes of the one before or of no size seen
    # before: it lands in the ranges that one landed from
    assert end["host_pool"]["hits"] > 0 and "host_buffer_wait" in delta
    # ... on a thread that is not the one that loads
    readers = {name for what, _, _, name in log.rows if what == "read_begin"}
    loaders = {name for what, _, _, name in log.rows if what == "load_begin"}
    assert readers == {"tpusnap-read-pipeline"} and readers.isdisjoint(loaders)
    counter = delta["read_ahead"]
    assert counter["n"] == 1 and counter["s"] > 0 and counter["bytes"] > 0
    assert "wall" not in counter
    assert end["read_ahead_s"] == pytest.approx(counter["s"])
    assert end["read_ahead_bytes"] == counter["bytes"]
    # three statefuls are read ahead, and none of them twice
    ahead_bytes = sum(LEAVES * int(np.prod(TREES[world.tree][key])) * 4 for key in KEYS[1:])
    assert 0 < counter["bytes"] <= ahead_bytes
    assert_equal_bits(target, world.saved)
    assert no_pipeline_thread_alive()


def test_a_read_with_no_room_asks_storage_when_a_landing_has_freed_some(world):
    """What the arena changed of the rule above: a read of k+1 is dispatched
    where k's tail begins, as ever, but the arena (here one stateful's bytes)
    is full of k, so it asks storage only once a range of k has come back, to
    land in pages already faulted in.  Every leaf is smaller than the
    batcher's flush size, so of itself nothing of k would land before k's
    drain: the read's wait makes k's batcher flush, and storage is driven from
    that landing on."""
    target = make_app(world.log, zero=True)
    delta, (end,) = restore(world, target)
    log = world.log
    leaf = int(np.prod(SHAPE)) * 4
    assert world.arena_sizes == [LEAVES * leaf] and not world.plain
    for this, ahead in zip(KEYS, KEYS[1:]):
        dispatched = log.times("read_dispatch", ahead)
        begun = log.times("read_begin", ahead)
        assert len(dispatched) == len(begun) == LEAVES
        # held from the dispatch, where this one's tail begins ...
        assert max(dispatched) < log.first("load_begin", this), (this, ahead)
        # ... each until a range of this one has come back
        back = sorted(log.times("buffer_back", this))
        assert len(back) == LEAVES
        for n, began in enumerate(sorted(begun)):
            assert began >= back[n], (this, ahead, n)
        # ... and no longer: none waits for this one's load to end
        assert max(begun) < log.first("load_begin", this) + LOAD_S / 2, (this, ahead)
    # the wait is a phase of its own, and what it bought is in the account:
    # every read after the first stateful's landed in pages used before
    waited = delta["host_buffer_wait"]
    assert len(KEYS) - 1 <= waited["n"] <= (len(KEYS) - 1) * LEAVES and waited["wall"] > 0
    assert waited["wall"] < end["duration_s"]
    assert end["phases"]["host_buffer_wait"] == pytest.approx(waited["wall"], rel=0.05)
    assert end["host_pool"]["hits"] == (len(KEYS) - 1) * LEAVES
    assert end["host_pool"]["misses"] == LEAVES
    assert_equal_bits(target, world.saved)


def test_a_parked_read_of_the_next_stateful_starves_no_read_of_this_one(world):
    """An arena of two leaves behind statefuls of three: within a stateful a
    read waits for a landing of the stateful's own.  A read of k+1 that has
    finished is parked until k is loaded, with its range held; were it to hold
    room that a read of k still waits for, k could never be loaded.  It
    cannot: k+1's first range is taken when k's last read has FINISHED, and
    by then every read of k has had its range."""
    world.batcher_args.update(flush_bytes=256 << 20, inflight_cap_bytes=4 << 20)
    target = make_app(world.log, zero=True)
    done = []
    runner = threading.Thread(
        target=lambda: done.append(restore(world, target)), daemon=True
    )
    runner.start()
    runner.join(timeout=60)
    assert done, "the restore waits on itself"
    ((_, (end,)),) = done
    log = world.log
    leaf = int(np.prod(SHAPE)) * 4
    assert world.arena_sizes == [2 * leaf] and not world.plain
    for this, ahead in zip(KEYS, KEYS[1:]):
        assert len(log.times("range_taken", this)) == LEAVES
        assert log.first("range_taken", ahead) >= log.last("read_end", this)
        assert log.last("read_end", this) > log.last("range_taken", this)
        # and reads of the next one were parked, their ranges held, while
        # this one was loading
        assert log.first("read_end", ahead) < log.last("load_end", this)
        assert log.first("consume_begin", ahead) >= log.last("load_end", this)
    pool = end["host_pool"]
    assert pool["fresh"] == pool["high_water"] == 2 * leaf
    assert pool["misses"] == 2 and pool["hits"] == len(KEYS) * LEAVES - 2
    assert_equal_bits(target, world.saved)
    assert no_pipeline_thread_alive()


def test_nothing_is_consumed_or_sent_to_the_device_ahead_of_the_load(world):
    target = make_app(world.log, zero=True)
    restore(world, target)
    log = world.log
    for this, ahead in zip(KEYS, KEYS[1:]):
        loaded = log.last("load_end", this)
        # the gate binds: the reads of the next one were over before then
        assert log.last("read_end", ahead) < loaded, (this, ahead)
        for what in ("consume_begin", "h2d_submit", "h2d_dispatch"):
            assert log.times(what, ahead), (what, ahead)
            assert log.first(what, ahead) >= loaded, (what, this, ahead)
    # "h2d_dispatch" is still where the device_put is made: on the dispatcher,
    # which sends only what the pipeline has submitted, so the gate above
    # holds it too
    assert {name for what, _, _, name in log.rows if what == "h2d_dispatch"} == {
        "tpusnap-h2d-dispatcher"
    }


def test_at_the_drain_of_a_stateful_nothing_of_another_has_been_submitted(world, monkeypatch):
    """The restore's one H2D batcher serves every stateful (``h2d.py``): it
    holds at stateful k's drain what a batcher of k's own would hold, because
    every leaf submitted between the drain of k-1 and that of k is k's, and
    none of another.  Each drain is held for longer than a stateful takes to
    read, so that k+1's reads are back while k drains: a pipeline that
    consumed them then would submit them into k's drain."""
    real_drain = H2DBatcher.drain

    def held_drain(self):
        time.sleep(3 * LEAVES * READ_S)
        real_drain(self)
        world.log.add("drain_end", None)

    monkeypatch.setattr(H2DBatcher, "drain", held_drain)
    target = make_app(world.log, zero=True)
    restore(world, target)
    drained, submitted = 0, {key: 0 for key in KEYS}
    for what, key, _, _ in world.log.rows:
        if what == "drain_end":
            assert submitted[KEYS[drained]] == LEAVES, (drained, submitted)
            drained += 1
        elif what == "h2d_submit":
            assert key == KEYS[drained], (key, KEYS[drained])
            submitted[key] += 1
    assert drained == len(KEYS)
    # k+1 was read, and its reads were back, while k drained
    ends = world.log.times("drain_end")
    for k, ahead in enumerate(KEYS[1:]):
        assert world.log.first("read_end", ahead) < ends[k], ahead
    assert_equal_bits(target, world.saved)


def test_a_read_landed_in_place_ahead_of_the_load_is_parked_then_consumed_inline(world):
    """The short path changes nothing of the gate: a read of k+1 that landed
    in place with its digest and is back before k is loaded is parked, its
    range held, and consumed (on the pipeline's thread, in the turn that
    lets it go: no task, no executor) only once k is loaded."""
    world.storage_args.update(in_place=True)
    target = make_app(world.log, zero=True)
    delta, (end,) = restore(world, target)
    log = world.log
    for this, ahead in zip(KEYS, KEYS[1:]):
        loaded = log.last("load_end", this)
        assert log.last("read_end", ahead) < loaded, (this, ahead)  # back, and parked
        for what in ("consume_begin", "consume_inline", "h2d_submit"):
            assert len(log.times(what, ahead)) == LEAVES, (what, ahead)
            assert log.first(what, ahead) >= loaded, (what, this, ahead)
    inline = [name for what, _, _, name in log.rows if what == "consume_inline"]
    assert len(inline) == len(KEYS) * LEAVES and set(inline) == {"tpusnap-read-pipeline"}
    loop = end["read_loop"]
    assert sorted(loop) == [
        "direct", "handed", "inline", "max_pending", "taken", "turns", "wakes"
    ]  # fmt: skip
    assert (loop["inline"], loop["handed"], loop["taken"]) == (len(KEYS) * LEAVES, 0, 12)
    # a plug-in with no blocking read: every read a task, as before
    assert loop["direct"] == loop["wakes"] == 0
    assert 1 <= loop["turns"] and 1 <= loop["max_pending"] <= LEAVES
    counter = delta["read_loop"]
    assert counter["n"] == 1 and {k: counter[k] for k in loop} == loop
    # parked from read_back to consume_began: the stage is there, and the
    # stages still add up
    turned = end["arena_turn"]
    assert turned["parked_s"] > 0 and turned["ranges"] == len(KEYS) * LEAVES
    assert turned["turn_bs"] == pytest.approx(
        sum(turned[stage + "_bs"] for stage in h2d_mod._STAGES)
    )
    assert_equal_bits(target, world.saved)
    assert no_pipeline_thread_alive()


def test_the_device_put_runs_on_the_dispatcher_and_the_account_says_so(world):
    """A flush on the pipeline's thread is a hand-off: every batch's
    ``device_put`` runs on the restore's dispatcher, and the
    ``h2d_dispatch_route`` counter, once a restore and in ``restore.end``
    beside ``host_pool``, says how much went that way."""
    target = make_app(world.log, zero=True)
    delta, (end,) = restore(world, target)
    rows = world.log.rows
    assert {name for what, _, _, name in rows if what == "h2d_submit"} == {
        "tpusnap-read-pipeline"
    }
    assert {name for what, _, _, name in rows if what == "h2d_dispatch"} == {
        "tpusnap-h2d-dispatcher"
    }
    state_bytes = len(KEYS) * LEAVES * int(np.prod(SHAPE)) * 4
    route = end["h2d_dispatch_route"]
    assert sorted(route) == ["bytes", "cpu_s", "leaves"]
    assert route["leaves"] == len(KEYS) * LEAVES
    assert route["bytes"] == state_bytes
    calls = delta["h2d_dispatch"]["n"]
    assert calls >= 1 and calls == len(world.log.times("h2d_dispatch"))
    counter = delta["h2d_dispatch_route"]
    assert counter["n"] == 1 and "wall" not in counter
    # (cpu_s, a float, comes back from the counter's delta rounded)
    assert {k: counter[k] for k in route} == pytest.approx(route, rel=1e-9)
    assert_equal_bits(target, world.saved)


def test_a_restore_starts_its_h2d_threads_once_before_its_first_read(world, monkeypatch):
    """A thread's start under read load costs its starter dearly on some
    hosts (PERF.md section 5), so the dispatcher and the lander are started
    once a restore (the parent started a lander a stateful, from the
    pipeline's thread), by the thread that called ``restore``, before the
    pipeline exists; none is started later and none is left behind.  A
    restore with nothing to upload starts neither."""
    started = []
    real_start = threading.Thread.start

    def recording_start(self):
        if self.name.startswith("tpusnap-"):
            started.append(
                (self.name, threading.current_thread().name, len(world.log.times("read_dispatch")))
            )
        return real_start(self)

    monkeypatch.setattr(threading.Thread, "start", recording_start)
    target = make_app(world.log, zero=True)
    restore(world, target)
    me = threading.current_thread().name
    h2d = [row for row in started if row[0].startswith("tpusnap-h2d-")]
    assert sorted(h2d) == [
        ("tpusnap-h2d-dispatcher", me, 0),
        ("tpusnap-h2d-lander", me, 0),
    ]
    assert len(h2d) <= len(KEYS)  # the parent's count: a lander a stateful
    names = [name for name, _, _ in started]
    assert names.index("tpusnap-h2d-lander") < names.index("tpusnap-read-pipeline")
    assert no_pipeline_thread_alive()
    assert_equal_bits(target, world.saved)
    # numpy targets are filled in place: nothing is uploaded, no thread is started
    del started[:]
    host = {
        key: StateDict({k: np.zeros(v.shape, v.dtype) for k, v in sd.state_dict().items()})
        for key, sd in world.saved.items()
    }
    Snapshot(world.url).restore(host)
    assert not [name for name, _, _ in started if name.startswith("tpusnap-h2d-")]
    for key in KEYS:
        for name, want in world.saved[key].state_dict().items():
            np.testing.assert_array_equal(host[key].state_dict()[name], np.asarray(want))


def test_the_look_ahead_is_one_stateful(world):
    violations = []

    def on_read(key):
        k = KEYS.index(key)
        if k >= 2 and not world.log.times("load_end", KEYS[k - 2]):
            violations.append((key, KEYS[k - 2], "not loaded"))

    world.storage_args["on_read"] = on_read
    target = make_app(world.log, zero=True)
    _, (end,) = restore(world, target)
    assert not violations, violations
    assert len(world.log.times("read_begin")) == len(KEYS) * LEAVES
    # and it is a look-ahead: k+1 was being read before k was loaded
    for this, ahead in zip(KEYS, KEYS[1:]):
        assert world.log.first("read_begin", ahead) < world.log.last("load_end", this)
    # host memory is bounded by the one arena, whatever is read ahead: of four
    # statefuls' bytes, one stateful's were ever touched
    leaf = int(np.prod(SHAPE)) * 4
    pool = end["host_pool"]
    assert world.arena_sizes == [LEAVES * leaf]
    assert pool["fresh"] == pool["high_water"] == LEAVES * leaf
    assert pool["misses"] == LEAVES
    assert pool["hits"] + pool["misses"] == len(KEYS) * LEAVES
    assert pool["bytes"] == pool["hits"] * leaf
    # nothing of it outlives the restore
    made = weakref.ref(world.pools.pop())
    assert not world.pools
    gc.collect()
    assert made() is None and all(ref() is None for ref in world.arenas)


def test_user_code_runs_on_the_calling_thread_in_key_order_rng_last(world):
    target = make_app(world.log, zero=True)
    target["rng"] = RNGState()
    order = []
    real_load = RNGState.load_state_dict

    def rng_load(self, state_dict):
        order.append(("rng", threading.current_thread().name))
        return real_load(self, state_dict)

    saved = dict(world.saved)
    saved["rng"] = RNGState()
    MemoryStoragePlugin.reset()
    with knobs.override_batching_disabled(True):
        Snapshot.take(world.url, saved)
    RNGState.load_state_dict = rng_load
    try:
        restore(world, target)
    finally:
        RNGState.load_state_dict = real_load
    me = threading.current_thread().name
    rows = [r for r in world.log.rows if r[0] in ("state_dict", "load_begin", "load_end")]
    assert {name for _, _, _, name in rows} == {me}
    assert [k for what, k, _, _ in rows if what == "state_dict"] == list(KEYS)
    loads = [(what, k) for what, k, _, _ in rows if what != "state_dict"]
    assert loads == [(what, k) for k in KEYS for what in ("load_begin", "load_end")]
    # every plan is made before the first load, RNG state's too, and it loads last
    assert max(world.log.times("state_dict")) < min(world.log.times("load_begin"))
    assert order == [("rng", me)]


def test_a_read_failure_ahead_leaves_the_loaded_loaded_and_no_thread(world):
    # s2's reads fail, and not before s1 is inside its load_state_dict
    world.storage_args.update(fail_key="s2", fail_after=("load_begin", "s1"))
    target = make_app(world.log, zero=True)
    before = {k: dict(target[k].state_dict()) for k in KEYS}
    with pytest.raises(ValueError, match="injected read failure in s2"):
        restore(world, target)
    assert no_pipeline_thread_alive()
    assert_equal_bits(target, world.saved, keys=("s0", "s1"))
    loaded = [k for what, k, _, _ in world.log.rows if what == "load_end"]
    assert loaded == ["s0", "s1"]
    for key in ("s2", "s3"):
        assert not world.log.times("load_begin", key)
        for name, was in before[key].items():
            assert target[key].state_dict()[name] is was
    # nothing of the failed stateful or the one after it reached the device
    assert not world.log.times("h2d_dispatch", "s2")
    assert not world.log.times("read_begin", "s3")


def test_a_load_that_raises_stops_the_pipeline(world):
    target = make_app(world.log, zero=True)

    def boom(state_dict):
        raise RuntimeError("user code failed")

    target["s1"].load_state_dict = boom
    with pytest.raises(RuntimeError, match="user code failed"):
        restore(world, target)
    assert no_pipeline_thread_alive()
    assert_equal_bits(target, world.saved, keys=("s0",))
    assert not world.log.times("load_begin", "s2")


def test_one_stateful_counts_no_read_ahead(world):
    target = make_app(world.log, keys=("s1",), zero=True)
    delta, (end,) = restore(world, target)
    assert delta["read_ahead"] == {"s": 0.0, "bytes": 0, "n": 1}
    assert end["read_ahead_s"] == 0.0 and end["read_ahead_bytes"] == 0
    assert delta["read_starved"]["n"] >= 1
    assert_equal_bits(target, world.saved, keys=("s1",))


@pytest.mark.parametrize("case", ["float32", "bfloat16", "chunked"])
def test_a_restore_that_reads_ahead_is_bit_equal(tmp_path, case):
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    chunk = 64 << 10 if case == "chunked" else 512 << 20
    rng = np.random.RandomState(7)

    def state(zero):
        return {
            key: StateDict(
                {
                    f"w{i}": jnp.zeros((256, 256 + 64 * i), dtype)
                    if zero
                    else jnp.asarray(rng.randn(256, 256 + 64 * i), dtype)
                    for i in range(4)
                }
            )
            for key in ("adam_mu", "adam_nu", "params", "progress")
        }

    saved, target = state(zero=False), state(zero=True)
    # the fourth stateful has nothing to read, as chipbench's has
    saved["progress"], target["progress"] = StateDict({"step": 7}), StateDict({"step": 0})
    before = phase_stats.snapshot()
    with knobs.override_max_chunk_size_bytes(chunk):
        snapshot = Snapshot.take(str(tmp_path / "snap"), saved)
        if case == "chunked":
            kinds = {type(e).__name__ for e in snapshot.get_manifest().values()}
            assert "ChunkedTensorEntry" in kinds
        snapshot.restore(target)
    assert phase_stats.delta(before)["read_ahead"]["n"] == 1
    assert_equal_bits(target, saved, keys=("adam_mu", "adam_nu", "params"))
    assert target["progress"]["step"] == 7


# ------------------------------------------------ the scheduler, on its own


class _Consumer(BufferConsumer):
    def __init__(self, sink, key, cost):
        self.sink, self.key, self.cost = sink, key, cost

    async def consume_buffer(self, buf, executor=None):
        self.sink.append((self.key, time.monotonic(), bytes(buf)))

    def get_consuming_cost_bytes(self):
        return self.cost


@pytest.mark.parametrize("budget", [1, 250, 1 << 20])
def test_groups_complete_in_order_under_any_budget(budget):
    """A budget under one request, one that parked reads exhaust, and one
    that never binds: no order of events leaves the pipeline waiting on
    itself, and a group is consumed only once the one before is loaded."""
    MemoryStoragePlugin.reset()
    storage = MemoryStoragePlugin(root=f"groups_{budget}")
    payloads = {f"g{g}/p{i}": bytes([g * 16 + i]) * 100 for g in range(4) for i in range(5)}
    for path, data in payloads.items():
        storage._files[path] = data
    sink = []
    groups = [
        [ReadReq(path=p, buffer_consumer=_Consumer(sink, p, 100)) for p in payloads if p.startswith(f"g{g}/")]
        for g in range(4)
    ]
    groups.insert(2, [])  # a stateful with nothing to read
    began = time.monotonic()
    pipeline = scheduler_mod.ReadAhead(groups, storage, budget, rank=0)
    loaded_at = []
    try:
        for group in range(len(groups)):
            waiter = threading.Thread(target=pipeline.wait_consumed, args=(group,))
            waiter.start()
            waiter.join(timeout=10)
            assert not waiter.is_alive(), f"group {group} never consumed"
            time.sleep(0.02)
            loaded_at.append(time.monotonic())
            pipeline.mark_loaded(group)
    finally:
        pipeline.close()
    assert {k: v for k, _, v in sink} == payloads
    order = [int(k[1]) for k, _, _ in sink]
    assert order == sorted(order)
    for key, at, _ in sink:
        g = int(key[1])
        if g:
            before = g - 1 if g < 2 else g  # g2 and g3 sit behind the empty group
            assert at >= loaded_at[before], (key, g)
    assert not pipeline._thread.is_alive()
    # no wake-up was lost: the pipeline's own timeout is 5 s
    assert time.monotonic() - began < 3.0
    assert pipeline.read_ahead_s >= 0 and pipeline.read_ahead_bytes <= 1500


class BlockingLanding(MemoryStoragePlugin):
    """The memory plug-in with the blocking entry the fs plug-in offers
    (``read_blocking`` on ``read_executor``'s workers): the read pipeline
    runs its reads on the slot hand-over, and each lands in place with its
    digest.  ``read_s`` is how long a read takes."""

    def __init__(self, root, read_s=0.0):
        from concurrent.futures import ThreadPoolExecutor

        super().__init__(root)
        self.read_s = read_s
        self.pool = ThreadPoolExecutor(max_workers=16, thread_name_prefix="blocking_io")

    def read_executor(self):
        return self.pool

    def read_blocking(self, read_io):
        time.sleep(self.read_s)
        data = self._files[read_io.path]
        if read_io.byte_range is not None:
            data = data[read_io.byte_range[0] : read_io.byte_range[1]]
        read_io.buf = bytearray(data)
        land_in_place(read_io)


@pytest.mark.parametrize("path", ["task", "hand_over"])
def test_the_stamps_of_a_turn_come_in_order_on_the_short_path_and_add_up(monkeypatch, path):
    """A hand-driven pool behind the real pipeline, under a clock that moves
    one second a reading: two leaves of group 0 consumed inline in the turn
    they are taken off, one of group 1 parked until group 0 is loaded.  Every
    stamp is made, in the order of ``_STAMPS`` (``consume_began`` only for the
    parked one), and the eight ``_bs`` are ``turn_bs``.  On the slot
    hand-over (a plug-in that reads blocking) the range is adopted on the
    pipeline's thread, at the dispatch or in the pool's grant, and
    ``read_began`` is stamped by the worker that starts the read: ``slot``
    runs from the one to the other."""
    from torchsnapshot_tpu.h2d import HostBufferPool
    from torchsnapshot_tpu.io_preparers.array import ArrayIOPreparer
    from torchsnapshot_tpu.manifest import TensorEntry

    page = h2d_mod._PAGE
    ticks = iter(range(5000, 10**6))
    monkeypatch.setattr(h2d_mod, "_now", lambda: float(next(ticks)))
    stamped = []  # (stamp, thread)
    real_stamp = array_mod.ArrayAssembly.stamp

    def recording_stamp(self, stamp):
        stamped.append((stamp, threading.current_thread().name))
        real_stamp(self, stamp)

    monkeypatch.setattr(array_mod.ArrayAssembly, "stamp", recording_stamp)
    on_a_chip = types.SimpleNamespace(
        devices=lambda: [types.SimpleNamespace(platform="tpu")]
    )
    monkeypatch.setattr(array_mod.staging, "is_jax_array", lambda obj: obj is on_a_chip)
    monkeypatch.setattr(array_mod, "_INTO_PLACE_MIN_BYTES", page)
    pool = HostBufferPool()
    pool.batcher.inflight_cap_bytes = 8 * page  # the arena's window term
    batcher = types.SimpleNamespace(host_pool=pool, submitted=[])
    batcher.submit = lambda *item: batcher.submitted.append(item)
    MemoryStoragePlugin.reset()

    class Landing(MemoryStoragePlugin):
        async def read(self, read_io):
            await super().read(read_io)
            land_in_place(read_io)

    storage = (Landing if path == "task" else BlockingLanding)(root="turn_in_order")
    groups, wanted = [], {}
    for names in (["a", "b"], ["c"]):
        pool.begin_group()
        group = []
        for name in names:
            data = np.random.RandomState(ord(name)).bytes(2 * page)
            storage._files[name] = data
            entry = TensorEntry(
                location=name, serializer="buffer_protocol", dtype="uint8", shape=[2 * page],
                replicated=False, checksum=integrity.digest(data),
            )  # fmt: skip
            reqs, _fut = ArrayIOPreparer.prepare_read(entry, on_a_chip, h2d_batch=batcher)
            group += reqs
            wanted[name] = data
        groups.append(group)
    parked = []

    def land(item):
        """The H2D side, by hand: the stamps so far are all made and in
        order, then ``sent``, ``put`` and the give."""
        host, _like, _fut, lease = item
        assert host.tobytes() in wanted.values()
        turn = pool.turn_of(lease)
        stamps = [getattr(turn, stamp) for stamp in h2d_mod._STAMPS[:5]]
        if stamps[3] is None:  # consume_began: only a parked read's
            del stamps[3]
        else:
            parked.append(lease)
        assert None not in stamps
        assert [turn.granted, *stamps] == sorted([turn.granted, *stamps])
        turn.sent = turn.put = h2d_mod._now()
        pool.give(lease, recycle=True)

    before = phase_stats.snapshot()
    pipeline = scheduler_mod.ReadAhead(groups, storage, 1 << 30, rank=0)
    try:
        pipeline.wait_consumed(0)
        # the arena is group 0's two leaves: c, dispatched once they are back,
        # waits for room until one has landed
        a, b = batcher.submitted
        land(a)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            turns = list(pool._lent.values())
            if len(turns) == 2 and all(turn.read_back is not None for turn in turns):
                break
            time.sleep(0.005)
        time.sleep(0.05)
        assert len(batcher.submitted) == 2  # c is back and parked, not consumed
        pipeline.mark_loaded(0)
        pipeline.wait_consumed(1)
        pipeline.mark_loaded(1)
    finally:
        pipeline.close()
    loop = phase_stats.delta(before)["read_loop"]
    assert (loop["inline"], loop["handed"], loop["taken"]) == (3, 0, 3)
    assert loop["direct"] == (3 if path == "hand_over" else 0)
    threads = {}
    for stamp, thread in stamped:
        threads.setdefault(stamp, set()).add(thread)
    assert threads["adopted"] == threads["read_back"] == {"tpusnap-read-pipeline"}
    if path == "hand_over":
        assert threads["read_began"] and all(
            name.startswith("blocking_io") for name in threads["read_began"]
        )
        storage.pool.shutdown()
    else:
        assert threads["read_began"] == {"tpusnap-read-pipeline"}
    land(b)
    land(batcher.submitted[2])
    assert len(parked) == 1
    stats = pool.turn_stats()
    assert stats["ranges"] == 3 and stats["dropped"] == 0 and stats["bytes"] == 6 * page
    assert stats["turn_bs"] == sum(stats[stage + "_bs"] for stage in h2d_mod._STAGES)
    assert stats["parked_s"] > 0 and stats["consume_s"] > 0 and stats["read_s"] > 0
    assert stats["grant_s"] > 0  # c's range was fitted by a's give, adopted by the loop
    pool.close()


def test_on_the_slot_hand_over_a_read_that_waits_a_millisecond_records_io_slot_wait(
    monkeypatch,
):
    """One io slot and six reads of 20 ms each on the slot hand-over: every
    read after the first is queued for the slot (the hand-over's own queue
    records it) and waits, from its memory in hand to its start on the
    worker that finished the one before, and each such wait is an
    ``io_slot_wait`` of the worker's; with sixteen slots no read is queued.
    A wait under a millisecond is never recorded.  What a read that is not
    queued may still record is its hop onto a worker, which a loaded host
    can stretch past a millisecond: the test decides from the queue, not
    from how fast the host hops, so the first read of the one slot may add
    one record and no other may."""
    queued = []  # of each hand-over, the reads it queued for a slot
    waits = []  # the seconds of each io_slot_wait recorded

    def recording_add(phase, seconds, *args, **kwargs):
        if phase == "io_slot_wait":
            waits.append(seconds)
        return real_add(phase, seconds, *args, **kwargs)

    real_add = phase_stats.add
    monkeypatch.setattr(phase_stats, "add", recording_add)

    class CountedQueue(collections.deque):
        def append(self, pipeline):
            queued[-1] += 1
            super().append(pipeline)

    class RecordedHandOver(scheduler_mod._DirectReads):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            queued.append(0)
            self._queue = CountedQueue()

    monkeypatch.setattr(scheduler_mod, "_DirectReads", RecordedHandOver)
    MemoryStoragePlugin.reset()
    sink = []
    payloads = {f"p{i}": bytes([i]) * 100 for i in range(6)}
    for cap, read_s in ((1, 0.02), (16, 0.0)):
        storage = BlockingLanding(root=f"slot_wait_{cap}", read_s=read_s)
        storage._files.update(payloads)
        reqs = [ReadReq(path=p, buffer_consumer=_Consumer(sink, p, 100)) for p in payloads]
        before = phase_stats.snapshot()
        del waits[:]
        with knobs.override_max_per_rank_io_concurrency(cap):
            scheduler_mod.sync_execute_read_reqs(reqs, storage, 1 << 20, rank=0)
        delta = phase_stats.delta(before)
        assert delta["read_loop"]["direct"] == len(payloads)
        waited = delta.get("io_slot_wait")
        assert all(w >= 0.001 for w in waits)
        if cap == 1:
            assert queued[-1] == len(payloads) - 1
            assert waited["n"] - queued[-1] in (0, 1)
            assert waited["s"] >= 0.02 * (len(payloads) - 1)
            assert waited["wall"] > 0.02 * 4
        else:
            assert queued[-1] == 0
        storage.pool.shutdown()
    assert len(queued) == 2 and len(sink) == 2 * len(payloads)
