"""Host read buffers that are reused (``io_preparers.array.HostBufferPool``,
one a ``Snapshot.restore``): a leaf's buffer is taken when its read is
dispatched and given back when its H2D has landed, to the next leaf of the
same byte size, which waits for it where it is still landing.  Restores of
train-state-shaped trees (three statefuls, one tree) through the fs plug-in,
the reads one at a time so that each has its number; then the pool alone."""

import asyncio
import gc
import os
import sys
import threading
import time
import types
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, knobs, phase_stats
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.event_handlers import (
    register_event_handler,
    unregister_event_handler,
)
from torchsnapshot_tpu.integrity import ChecksumError
from torchsnapshot_tpu.io_preparers import array as array_mod
from torchsnapshot_tpu.io_preparers.array import HostBufferPool
from torchsnapshot_tpu.io_types import StoragePlugin

KEYS = ("a_params", "b_mu", "c_nu")  # loaded in the order of their names
# float32 leaves of 1, 2 and 3 MiB: from the size a read lands in place, and
# no two of a stateful alike, so a leaf's buffer can only come from its twin
SHAPES = ((256, 1024), (512, 1024), (768, 1024))
LEAVES = len(SHAPES)
STATEFUL_BYTES = sum(4 * rows * cols for rows, cols in SHAPES)
PIPELINE_THREADS = ("tpusnap-read-pipeline", "tpusnap-h2d-lander")


def make_app(seed, shapes=SHAPES, keys=KEYS, zero=False):
    rng = np.random.RandomState(seed)
    return {
        key: StateDict(
            {
                f"w{i}": jnp.zeros(shape, jnp.float32)
                if zero
                else jnp.asarray(rng.rand(*shape), jnp.float32)
                for i, shape in enumerate(shapes[k] if isinstance(shapes, dict) else shapes)
            }
        )
        for k, key in enumerate(keys)
    }


def assert_equal_bits(target, saved):
    assert list(target) == list(saved)
    for key in saved:
        for name, want in saved[key].state_dict().items():
            got = target[key].state_dict()[name]
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(
                np.asarray(got).view(np.uint8), np.asarray(want).view(np.uint8)
            )


def buffer_at(nbytes, offset_from_64):
    """A flat uint8 buffer that begins ``offset_from_64`` bytes past a 64-byte
    boundary.  At 16, where a large ``np.empty`` begins, the CPU backend's
    ``device_put`` copies it, as an accelerator does; at 0 it takes the memory
    as the array itself."""
    raw = np.empty(nbytes + 128, dtype=np.uint8)
    offset = (offset_from_64 - raw.ctypes.data) % 64
    return raw[offset : offset + nbytes]


class Faulty(StoragePlugin):
    """Reads into place come one at a time (the io knob) and are numbered.
    The read that ``fails`` names raises; the one that ``tails`` names comes
    from a plug-in that hashes nothing itself, leaves the second half of its
    destination as it found it, and says nothing."""

    def __init__(self, inner, world):
        self._inner, self._world = inner, world

    async def read(self, read_io):
        world = self._world
        if read_io.into is None:
            return await self._inner.read(read_io)
        m = len(world.reads)
        world.reads.append(read_io.path)
        if world.fails is not None and m == world.fails:
            raise ValueError(f"injected failure of read {m}")
        kept = None
        if world.tails is not None and m == world.tails:
            half = read_io.into.nbytes // 2
            kept = bytes(read_io.into[half:])
        await self._inner.read(read_io)
        if kept is not None:
            assert read_io.buf is read_io.into
            read_io.into[half:] = kept
            read_io.hash64 = None

    async def write(self, write_io):
        await self._inner.write(write_io)

    async def delete(self, path):
        await self._inner.delete(path)

    async def delete_dir(self, path):
        await self._inner.delete_dir(path)

    async def close(self):
        await self._inner.close()


@pytest.fixture
def world(monkeypatch, tmp_path):
    """Every seam of the pool recorded: the pools made (weakly), the buffers
    made (weakly, and where they begin), the reads into place."""
    w = types.SimpleNamespace(
        root=str(tmp_path),
        pools=[],
        buffers=[],
        addresses=[],
        offset_from_64=16,
        made_before=0,
        reads=[],
        fails=None,
        tails=None,
    )

    class RecordedPool(HostBufferPool):
        def __init__(self):
            super().__init__()
            w.pools.append(weakref.ref(self))

    monkeypatch.setattr(snapshot_mod, "HostBufferPool", RecordedPool)

    def recorded_buffer(nbytes):
        buf = buffer_at(nbytes, w.offset_from_64)
        w.buffers.append(weakref.ref(buf))
        w.addresses.append((buf.ctypes.data, nbytes))
        return buf

    monkeypatch.setattr(array_mod, "_fresh_host_buffer", recorded_buffer)

    real_plan = Snapshot._plan_stateful_reads

    def plan_that_allocates_nothing(*args):
        plan = real_plan(*args)
        assert len(w.buffers) == w.made_before, "a host buffer was made at plan time"
        return plan

    monkeypatch.setattr(
        Snapshot, "_plan_stateful_reads", staticmethod(plan_that_allocates_nothing)
    )

    real_plugin = snapshot_mod.url_to_storage_plugin
    monkeypatch.setattr(
        snapshot_mod,
        "url_to_storage_plugin",
        lambda path, options=None: Faulty(real_plugin(path, options), w),
    )
    # (no retry of a read: the warning's exception, kept by the log capture,
    # would hold the pool through its traceback)
    with knobs.override_max_per_rank_io_concurrency(1), knobs.override_io_retries(0):
        yield w


def take(world, name, app):
    path = os.path.join(world.root, name)
    Snapshot.take(path, app)
    return path


def restore(world, path, target):
    """The restore's ``host_pool`` counter and its ``restore.end`` event."""
    ends = []

    def on_event(event):
        if event.name == "restore.end":
            ends.append(dict(event.metadata))

    world.reads.clear()
    world.made_before = len(world.buffers)
    before = phase_stats.snapshot()
    register_event_handler(on_event)
    try:
        Snapshot(path).restore(target)
    finally:
        unregister_event_handler(on_event)
    (end,) = ends
    return phase_stats.delta(before).get("host_pool"), end


def nothing_left(world):
    """No pool, no buffer the restored arrays do not own, no thread."""
    deadline = time.monotonic() + 5.0
    while [t for t in threading.enumerate() if t.name in PIPELINE_THREADS]:
        if time.monotonic() > deadline:
            return False
        time.sleep(0.01)
    gc.collect()
    return all(ref() is None for ref in world.pools)


# ------------------------------------------------------------ the restore


def test_three_same_shaped_statefuls_read_into_the_first_ones_buffers(world):
    saved = make_app(1)
    path = take(world, "snap", saved)
    target = make_app(0, zero=True)
    counter, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    # the second and the third stateful made no buffer of their own
    assert end["host_pool"] == {
        "bytes": 2 * STATEFUL_BYTES,
        "fresh": STATEFUL_BYTES,
        "hits": 2 * LEAVES,
        "misses": LEAVES,
        "high_water": STATEFUL_BYTES,
    }
    assert len(world.buffers) == LEAVES
    # because a read whose twin was still landing waited for it
    assert end["phases"]["host_buffer_wait"] > 0
    # and what the pool held went once, with a name, when the last had loaded
    assert end["phases"]["host_pool_free"] > 0
    # the counter holds the same numbers, once a restore
    assert counter["n"] == 1 and counter["s"] == 0
    assert {k: counter[k] for k in end["host_pool"]} == end["host_pool"]
    assert len(world.pools) == 1 and nothing_left(world)
    assert all(ref() is None for ref in world.buffers)


def test_sizes_that_never_repeat_restore_as_without_a_pool(world):
    shapes = {
        0: ((256, 1024), (320, 1024)),
        1: ((384, 1024), (448, 1024)),
        2: ((512, 1024), (576, 1024)),
    }
    saved = make_app(2, shapes=shapes)
    path = take(world, "snap", saved)
    target = make_app(0, shapes=shapes, zero=True)
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    pool = end["host_pool"]
    assert pool["hits"] == 0 and pool["bytes"] == 0 and pool["misses"] == 6
    group_bytes = [sum(4 * r * c for r, c in shapes[k]) for k in shapes]
    assert pool["fresh"] == sum(group_bytes)
    assert pool["high_water"] <= sum(sorted(group_bytes)[-2:])
    assert nothing_left(world) and all(ref() is None for ref in world.buffers)


def test_a_buffer_the_landed_array_aliases_is_never_read_into_again(world):
    # 64-byte aligned: the CPU backend's device_put copies nothing, the
    # restored array IS the host buffer
    world.offset_from_64 = 0
    saved, other = make_app(3), make_app(4)
    path, other_path = take(world, "snap", saved), take(world, "other", other)
    target = make_app(0, zero=True)
    _, end = restore(world, path, target)
    restored = [leaf for sd in target.values() for leaf in sd.state_dict().values()]
    aliased = [
        leaf
        for leaf in restored
        if any(
            begin <= leaf.unsafe_buffer_pointer() < begin + nbytes
            for begin, nbytes in world.addresses
        )
    ]
    assert len(aliased) == len(restored) == len(KEYS) * LEAVES
    # not one buffer was used twice, though each read waited for its twin
    assert end["host_pool"]["hits"] == 0
    assert end["host_pool"]["misses"] == len(KEYS) * LEAVES
    assert_equal_bits(target, saved)
    # and another snapshot restored through the same code changes nothing
    second = make_app(0, zero=True)
    restore(world, other_path, second)
    assert_equal_bits(second, other)
    assert_equal_bits(target, saved)
    del restored, aliased
    assert nothing_left(world)


@pytest.mark.parametrize("fault", ["corrupt", "truncated", "silently_short"])
def test_a_bad_read_into_a_recycled_buffer_raises(world, fault):
    """``b_mu/w1`` lands in the buffer ``a_params/w1`` landed from, which still
    holds those bytes: a read that does not fill it must not pass for one
    that did."""
    saved = make_app(5)
    with knobs.override_batching_disabled(True):
        path = take(world, "snap", saved)
    entry = Snapshot(path).get_manifest()["0/b_mu/w1"]
    payload = os.path.join(path, entry.location)
    if fault == "corrupt":
        with open(payload, "r+b") as f:
            f.seek(4096)
            byte = f.read(1)
            f.seek(4096)
            f.write(bytes([byte[0] ^ 0x01]))
        raises = pytest.raises(ChecksumError)
    elif fault == "truncated":
        os.truncate(payload, os.path.getsize(payload) // 2)
        raises = pytest.raises(OSError)
    else:
        world.tails = LEAVES + 1  # the read of b_mu/w1: the fifth
        raises = pytest.raises(ChecksumError)
    target = make_app(0, zero=True)
    with raises:
        restore(world, path, target)
    del raises  # and with it the traceback, whose frames hold the pool
    assert world.reads[LEAVES + 1].endswith("b_mu/w1")
    # the buffer was a recycled one (where the read itself raised, the read
    # behind it may have gone ahead of its twin's landing and made its own)
    assert len(world.buffers) == LEAVES or fault == "truncated"
    assert len(world.buffers) <= LEAVES + 1
    # nothing stale was handed back: the stateful before is as saved (or not
    # yet loaded, where the read itself raised), the others untouched
    if fault != "truncated" or np.asarray(target["a_params"].state_dict()["w0"]).any():
        assert_equal_bits({"a_params": target["a_params"]}, {"a_params": saved["a_params"]})
    for key in ("b_mu", "c_nu"):
        for leaf in target[key].state_dict().values():
            assert not np.asarray(leaf).any()
    assert nothing_left(world) and all(ref() is None for ref in world.buffers)


@pytest.mark.parametrize("failure", ["read_fails", "load_raises", "device_put_fails"])
def test_a_failure_leaves_no_buffer_in_flight_and_no_pool_behind(
    world, monkeypatch, failure
):
    saved = make_app(6)
    path = take(world, "snap", saved)
    target = make_app(0, zero=True)
    if failure == "read_fails":
        world.fails = LEAVES + 1
        with pytest.raises(ValueError, match="injected failure of read 4"):
            restore(world, path, target)
    elif failure == "load_raises":

        def boom(state_dict):
            raise RuntimeError("user code failed")

        target["b_mu"].load_state_dict = boom
        with pytest.raises(RuntimeError, match="user code failed"):
            restore(world, path, target)
        assert_equal_bits({"a_params": target["a_params"]}, {"a_params": saved["a_params"]})
    else:
        # every batched device_put fails: each leaf goes the per-item way,
        # lands there, and its buffer is given back all the same
        real_put = jax.device_put
        # (the warning's traceback, kept by the log capture, would hold the pool)
        monkeypatch.setattr(array_mod.logger, "warning", lambda *args, **kwargs: None)

        def no_batches(x, *args, **kwargs):
            if isinstance(x, list):
                raise RuntimeError("injected batched device_put failure")
            return real_put(x, *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", no_batches)
        _, end = restore(world, path, target)
        monkeypatch.setattr(jax, "device_put", real_put)
        assert_equal_bits(target, saved)
        assert end["host_pool"]["hits"] == 2 * LEAVES
        assert end["host_pool"]["high_water"] == STATEFUL_BYTES
    assert nothing_left(world)
    assert all(ref() is None for ref in world.buffers)


def test_chunked_leaves_go_through_the_pool(world):
    # the path of a leaf at the chunk knob, at toy size: four reads a leaf
    # into one buffer, taken when the first of them is dispatched
    shapes = ((1024, 1024),) * LEAVES
    leaf = 4 << 20
    saved = make_app(7, shapes=shapes)
    with knobs.override_max_chunk_size_bytes(1 << 20):
        path = take(world, "snap", saved)
    manifest = Snapshot(path).get_manifest()
    assert all(len(manifest[f"0/{key}/w0"].chunks) == 4 for key in KEYS)
    target = make_app(0, shapes=shapes, zero=True)
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    assert len(world.reads) == len(KEYS) * LEAVES * 4
    # one take a leaf, not a read; a leaf of the first stateful may already
    # read into the buffer of one before it (they are all of one size)
    pool = end["host_pool"]
    assert pool["hits"] + pool["misses"] == len(KEYS) * LEAVES
    assert pool["hits"] >= 2 * LEAVES and len(world.buffers) == pool["misses"]
    assert pool["bytes"] == pool["hits"] * leaf
    assert pool["fresh"] == pool["high_water"] == pool["misses"] * leaf
    assert nothing_left(world) and all(ref() is None for ref in world.buffers)


def test_what_is_not_uploaded_through_the_batcher_never_touches_the_pool(world):
    """Numpy targets (filled in place), leaves under a megabyte and
    ``read_object`` (no target: the buffer is the result)."""
    rng = np.random.RandomState(8)
    saved = {
        "host": StateDict({"w": rng.rand(512, 1024).astype(np.float32)}),
        "small": StateDict({"w": jnp.asarray(rng.rand(64, 1024), jnp.float32)}),
    }
    path = take(world, "snap", saved)
    target = {
        "host": StateDict({"w": np.zeros((512, 1024), np.float32)}),
        "small": StateDict({"w": jnp.zeros((64, 1024), jnp.float32)}),
    }
    in_place = target["host"].state_dict()["w"]
    _, end = restore(world, path, target)
    assert target["host"].state_dict()["w"] is in_place
    assert_equal_bits(target, saved)
    assert end["host_pool"] == dict.fromkeys(
        ("bytes", "fresh", "hits", "misses", "high_water"), 0
    )
    assert not world.buffers
    got = Snapshot(path).read_object("0/host/w")
    np.testing.assert_array_equal(got, saved["host"].state_dict()["w"])
    assert len(world.pools) == 1 and nothing_left(world)


# ----------------------------------------------------------- the pool alone


def reserved(pool, *groups):
    for group in groups:
        pool.begin_group()
        for nbytes in group:
            pool.reserve(nbytes)


def test_the_pool_hands_a_buffer_to_the_next_taker_of_its_size_only():
    pool = HostBufferPool()
    reserved(pool, [100, 200], [100, 200], [100, 300])
    a, b = pool.take(100, 0), pool.take(200, 0)
    assert a.dtype == np.uint8 and a.shape == (100,) and b.shape == (200,)
    pool.give(a, recycle=True)
    pool.give(b, recycle=True)
    assert pool.take(100, 0) is a and pool.take(200, 0) is b
    pool.give(a, recycle=True)
    assert pool.take(100, 0) is a
    assert pool.take(300, 0) is not b  # 300 is no size seen before
    assert pool.stats() == {
        "bytes": 400,
        "fresh": 600,
        "hits": 3,
        "misses": 3,
        "high_water": 600,
    }


def test_a_take_waits_for_a_buffer_lent_to_an_earlier_stateful_only():
    pool = HostBufferPool()
    reserved(pool, [100, 100], [100, 200])
    a = pool.take(100, 0)

    async def read_pipeline():
        assert pool.coming(100, 0) is None  # a sibling's may land only once this is read
        assert pool.coming(200, 1) is None  # nothing of that size is out
        woken = pool.coming(100, 1)  # the stateful before is landing from it
        assert woken is not None and not woken.done()
        lander = threading.Thread(target=pool.give, args=(a, True))
        lander.start()
        await asyncio.wait_for(woken, 5)  # woken by the give, from its thread
        lander.join()
        assert pool.coming(100, 1) is None and pool.take(100, 1) is a  # it is there
        first, second = pool.coming(100, 2), pool.coming(100, 2)
        assert first is not None and second is not None
        pool.give(a, recycle=False)  # dropped: every waiter looks again ...
        await asyncio.wait_for(asyncio.gather(first, second), 5)
        assert pool.coming(100, 2) is None  # ... and nothing is coming any more

    asyncio.run(read_pipeline())


def test_a_give_after_the_waiting_pipeline_is_gone_is_only_a_give():
    pool = HostBufferPool()
    reserved(pool, [100], [100], [100])
    a = pool.take(100, 0)

    async def aborted():
        cancelled = pool.coming(100, 1)
        cancelled.cancel()  # with its read
        return pool.coming(100, 2)

    loop = asyncio.new_event_loop()
    left = loop.run_until_complete(aborted())
    loop.close()
    pool.give(a, recycle=True)  # the lander, after the pipeline's loop has closed
    assert not left.done() and pool.take(100, 1) is a


def test_a_buffer_given_back_unfit_is_counted_out_and_not_handed_on():
    pool = HostBufferPool()
    reserved(pool, [100], [100])
    a = pool.take(100, 0)
    pool.give(a, recycle=False)
    assert pool.take(100, 0) is not a
    stats = pool.stats()
    assert stats["hits"] == 0 and stats["misses"] == 2 and stats["high_water"] == 100


def test_nothing_is_freed_beside_reads_and_all_of_it_when_the_restore_ends():
    pool = HostBufferPool()
    reserved(pool, [100, 200])
    a, b = pool.take(100, 0), pool.take(200, 0)
    alive = [weakref.ref(a), weakref.ref(b)]
    pool.give(a, recycle=True)  # nothing will take it again, and it is kept
    pool.give(b, recycle=True)
    del a, b
    assert alive[0]() is not None and alive[1]() is not None
    pool.close()  # the restore has ended: what is free goes
    assert alive[0]() is None and alive[1]() is None
    assert pool.stats()["high_water"] == 300
    pool.close()  # said twice is said once


def test_the_pool_stays_under_two_groups_where_sizes_never_repeat():
    pool = HostBufferPool()
    groups = [[100, 110], [200, 210], [300, 310], [50, 60]]
    reserved(pool, *groups)
    for group in groups:  # each lands before the next is read
        for buf in [pool.take(nbytes, 0) for nbytes in group]:
            pool.give(buf, recycle=True)
    stats = pool.stats()
    assert stats["hits"] == 0
    assert stats["high_water"] <= 610 + 410  # the two largest groups
    # room is made from what is free, and no more of it than the miss needs
    pool = HostBufferPool()
    reserved(pool, [100], [200], [300])
    a, b = pool.take(100, 0), pool.take(200, 0)
    pool.give(a, recycle=True)
    pool.give(b, recycle=True)
    gone, kept = weakref.ref(a), weakref.ref(b)
    del a, b
    pool.take(300, 0)  # 600 alive against the 500 of the two largest groups
    assert gone() is None and kept() is not None


def test_no_buffer_is_in_two_hands_at_once():
    """More takers than cores, a short switch interval: a buffer taken is
    nobody else's until given back, and the account adds up."""
    pool = HostBufferPool()
    workers, rounds, sizes = 16, 200, (64, 128, 192)
    reserved(pool, [size for size in sizes for _ in range(workers * rounds)])
    clashes = []

    def work(ident):
        for n in range(rounds):
            for size in sizes:
                buf = pool.take(size, 0)
                buf[:] = ident
                time.sleep(0)
                if (buf != ident).any():
                    clashes.append((ident, n, size))
                pool.give(buf, recycle=n % 7 != 0)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i + 1,)) for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not clashes
    stats = pool.stats()
    assert stats["hits"] + stats["misses"] == workers * rounds * len(sizes)
    assert stats["high_water"] <= workers * sum(sizes)
