"""The host arena of a restore (``h2d.HostBufferPool``, one a
``Snapshot.restore``): a leaf takes a page-aligned range of it when its read is
dispatched and the lander gives the range back when the H2D has landed, to
whichever read comes next, of any size and any stateful, which waits for room
where there is none and makes the batcher flush; the arena is populated in
bulk, once, before its first range is handed out.  Restores of train-state-
shaped trees (three statefuls, one tree) through the fs plug-in, the reads one
at a time so that each has its number, as tasks and on the slot hand-over (the
plug-in's blocking entry forwarded); then the pool alone."""

import asyncio
import functools
import gc
import os
import random
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
from torchsnapshot_tpu import h2d as h2d_mod
from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
from torchsnapshot_tpu.io_types import Future, StoragePlugin

KEYS = ("a_params", "b_mu", "c_nu")  # loaded in the order of their names
MIB = 1 << 20
PAGE = h2d_mod._PAGE
# float32 leaves of 1, 2 and 3 MiB: from the size a read lands in place
SHAPES = ((256, 1024), (512, 1024), (768, 1024))
LEAVES = len(SHAPES)
STATEFUL_BYTES = sum(4 * rows * cols for rows, cols in SHAPES)
PIPELINE_THREADS = (
    "tpusnap-read-pipeline",
    "tpusnap-h2d-dispatcher",
    "tpusnap-h2d-lander",
)


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


def memory_at(nbytes, offset_from_64):
    """``(raw, buf)``: a flat uint8 buffer that begins ``offset_from_64`` bytes
    past a 64-byte boundary, and the allocation under it.  At 16, where a large
    ``np.empty`` begins, the CPU backend's ``device_put`` copies it, as an
    accelerator does, and so it does every range a whole number of pages on;
    at 0 it takes the memory as the array itself."""
    raw = np.empty(nbytes + 128, dtype=np.uint8)
    offset = (offset_from_64 - raw.ctypes.data) % 64
    return raw, raw[offset : offset + nbytes]


class Faulty(StoragePlugin):
    """Reads into place come one at a time (the io knob) and are numbered.
    The read that ``fails`` names raises; the one that ``tails`` names comes
    from a plug-in that hashes nothing itself, leaves the second half of its
    destination as it found it, and says nothing."""

    def __init__(self, inner, world):
        self._inner, self._world = inner, world

    async def read(self, read_io):
        if read_io.into is None:
            return await self._inner.read(read_io)
        kept = self._numbered(read_io)
        await self._inner.read(read_io)
        self._tail(read_io, kept)

    def _numbered(self, read_io):
        """The read into place counted; it raises if it is the one that
        fails, and what the one that ``tails`` keeps of its destination."""
        world = self._world
        m = len(world.reads)
        world.reads.append(read_io.path)
        if world.fails is not None and m == world.fails:
            raise ValueError(f"injected failure of read {m}")
        if world.tails is not None and m == world.tails:
            return bytes(read_io.into[read_io.into.nbytes // 2 :])
        return None

    def _tail(self, read_io, kept):
        if kept is not None:
            assert read_io.buf is read_io.into
            read_io.into[read_io.into.nbytes - len(kept) :] = kept
            read_io.hash64 = None

    async def write(self, write_io):
        await self._inner.write(write_io)

    async def delete(self, path):
        await self._inner.delete(path)

    async def delete_dir(self, path):
        await self._inner.delete_dir(path)

    async def close(self):
        await self._inner.close()


class BlockingFaulty(Faulty):
    """``Faulty`` over the fs plug-in's blocking entry, which it forwards:
    the read pipeline runs its reads on the slot hand-over, on the fs
    plug-in's own workers."""

    def read_executor(self):
        return self._inner.read_executor()

    def read_blocking(self, read_io):
        if read_io.into is None:
            return self._inner.read_blocking(read_io)
        kept = self._numbered(read_io)
        self._inner.read_blocking(read_io)
        self._tail(read_io, kept)


@pytest.fixture(params=["task", "hand_over"])
def world(monkeypatch, tmp_path, request):
    """The CPU backend taken for an accelerator (``accelerator``: it keeps no
    host memory, and the arena begins where it copies), and every seam of the
    pool recorded: the pools made (weakly), the arenas and the plain buffers
    made (weakly, their sizes, and where they begin), the reads into place,
    and what was populated (``touches``: where, how much, on which thread,
    and after how many reads into place), by the native pool's own
    ``touch_pages`` where the library has it (``populates`` False: a library
    that predates the symbol)."""
    w = types.SimpleNamespace(
        root=str(tmp_path),
        pools=[],
        arenas=[],
        arena_sizes=[],
        plain=[],
        addresses=[],
        accelerator=True,
        offset_from_64=16,
        batcher_args={},
        made_before=0,
        reads=[],
        fails=None,
        tails=None,
        touches=[],
        populates=True,
        hand_over=request.param == "hand_over",
    )

    class RecordedPool(HostBufferPool):
        def __init__(self):
            super().__init__()
            self.batcher = H2DBatcher(self, **w.batcher_args)
            w.pools.append(weakref.ref(self))

    monkeypatch.setattr(snapshot_mod, "HostBufferPool", RecordedPool)
    really_keeps = h2d_mod._keeps_host_memory
    monkeypatch.setattr(
        h2d_mod,
        "_keeps_host_memory",
        lambda target: not w.accelerator and really_keeps(target),
    )

    def recorded_arena(nbytes):
        raw, buf = memory_at(nbytes, w.offset_from_64)
        w.arenas.append(weakref.ref(raw))
        w.arena_sizes.append(nbytes)
        w.addresses.append((buf.ctypes.data, nbytes))
        return buf

    monkeypatch.setattr(h2d_mod, "_arena_memory", recorded_arena)

    really_touches = h2d_mod._page_toucher()

    def recorded_touch(buf):
        w.touches.append(
            (buf.ctypes.data, buf.nbytes, threading.current_thread().name, len(w.reads))
        )
        if really_touches is not None:
            really_touches(buf)

    monkeypatch.setattr(
        h2d_mod, "_page_toucher", lambda: recorded_touch if w.populates else None
    )

    def recorded_buffer(nbytes):
        buf = np.empty(nbytes, dtype=np.uint8)
        w.plain.append(weakref.ref(buf))
        w.addresses.append((buf.ctypes.data, nbytes))
        return buf

    monkeypatch.setattr(h2d_mod, "_fresh_host_buffer", recorded_buffer)

    real_plan = Snapshot._plan_stateful_reads

    def plan_that_allocates_nothing(*args):
        plan = real_plan(*args)
        made = len(w.arenas) + len(w.plain)
        assert made == w.made_before, "host memory was taken at plan time"
        return plan

    monkeypatch.setattr(
        Snapshot, "_plan_stateful_reads", staticmethod(plan_that_allocates_nothing)
    )

    real_plugin = snapshot_mod.url_to_storage_plugin
    monkeypatch.setattr(
        snapshot_mod,
        "url_to_storage_plugin",
        lambda path, options=None: (Faulty if request.param == "task" else BlockingFaulty)(
            real_plugin(path, options), w
        ),
    )
    # (no retry of a read: the warning's exception, kept by the log capture,
    # would hold the pool through its traceback)
    with knobs.override_max_per_rank_io_concurrency(1), knobs.override_io_retries(0):
        yield w


def take(world, name, app):
    path = os.path.join(world.root, name)
    Snapshot.take(path, app)
    return path


def restore(world, path, target, within_s=60.0):
    """The restore's ``host_pool`` counter and its ``restore.end`` event.  On a
    thread of its own, so that a restore that waits on itself fails its test
    and does not hang the run."""
    ends, raised = [], []

    def on_event(event):
        if event.name == "restore.end":
            ends.append(dict(event.metadata))

    def run():
        try:
            Snapshot(path).restore(target)
        except BaseException as e:  # noqa: BLE001 -- raised again below
            raised.append(e)

    world.reads.clear()
    world.made_before = len(world.arenas) + len(world.plain)
    before = phase_stats.snapshot()
    register_event_handler(on_event)
    try:
        runner = threading.Thread(target=run, name="restore-under-test", daemon=True)
        runner.start()
        runner.join(timeout=within_s)
        assert not runner.is_alive(), f"the restore did not end within {within_s} s"
    finally:
        unregister_event_handler(on_event)
    if raised:
        error = raised.pop()  # (a kept traceback would hold the pool)
        raise error.with_traceback(None)
    (end,) = ends
    # every read on the slot hand-over where the plug-in reads blocking, none elsewhere
    loop = end["read_loop"]
    assert loop["direct"] == (loop["inline"] + loop["handed"] if world.hand_over else 0)
    return phase_stats.delta(before).get("host_pool"), end


def nothing_left(world):
    """No pool, no arena, no plain buffer the restored arrays do not own, no
    thread."""
    deadline = time.monotonic() + 5.0
    while [t for t in threading.enumerate() if t.name in PIPELINE_THREADS]:
        if time.monotonic() > deadline:
            return False
        time.sleep(0.01)
    gc.collect()
    return all(ref() is None for ref in world.pools + world.arenas + world.plain)


def restored_leaves(target):
    return [leaf for sd in target.values() for leaf in sd.state_dict().values()]


# ------------------------------------------------------------ the restore


def test_three_same_shaped_statefuls_are_read_into_one_arena(world):
    saved = make_app(1)
    path = take(world, "snap", saved)
    target = make_app(0, zero=True)
    counter, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    # one arena of one stateful's bytes (the window is larger, and a restore
    # needs no more than its largest stateful), and no other host buffer: the
    # second and the third stateful touched no page the first had not
    assert world.arena_sizes == [STATEFUL_BYTES] and not world.plain
    assert end["host_pool"] == {
        "bytes": 2 * STATEFUL_BYTES,
        "fresh": STATEFUL_BYTES,
        "hits": 2 * LEAVES,
        "misses": LEAVES,
        "high_water": STATEFUL_BYTES,
        "populated": STATEFUL_BYTES,
    }
    # because a read that found no room waited for a landing
    assert end["phases"]["host_buffer_wait"] > 0
    # and the arena went once, with a name, when the last had loaded
    assert end["phases"]["host_pool_free"] > 0
    # the counter holds the same numbers, once a restore
    assert counter["n"] == 1 and counter["s"] == 0
    assert {k: counter[k] for k in end["host_pool"]} == end["host_pool"]
    assert len(world.pools) == 1 and nothing_left(world)


@pytest.mark.parametrize(
    "batcher",
    ["every_leaf_over_the_flush_threshold", "every_leaf_under_the_flush_threshold"],
)
def test_the_first_stateful_reuses_its_own_pages(world, batcher):
    """One stateful of six 2 MiB leaves behind a window of 4 MiB: the arena is
    two leaves, a third of the stateful, and four of the six reads land in
    pages this very stateful has landed from.  With every leaf far under the
    batcher's flush threshold nothing would ever land of itself before the
    drain, which waits for these very reads: the read that waits for room
    makes the batcher flush."""
    if batcher == "every_leaf_over_the_flush_threshold":
        world.batcher_args.update(flush_bytes=2 * MIB)
    else:
        world.batcher_args.update(flush_bytes=256 * MIB, inflight_cap_bytes=4 * MIB)
    shapes, keys = ((512, 1024),) * 6, ("only",)
    saved = make_app(9, shapes=shapes, keys=keys)
    path = take(world, "snap", saved)
    target = make_app(0, shapes=shapes, keys=keys, zero=True)
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    arena = 4 * MIB
    assert world.arena_sizes == [arena] and not world.plain
    pool = end["host_pool"]
    assert pool["fresh"] == pool["high_water"] == arena
    assert pool["bytes"] == 12 * MIB - arena
    assert pool["misses"] == 2 and pool["hits"] == 4
    assert end["phases"]["host_buffer_wait"] > 0
    assert nothing_left(world)


def test_sizes_that_never_repeat_share_the_arena_all_the_same(world):
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
    group_bytes = [sum(4 * r * c for r, c in shapes[k]) for k in shapes]
    assert world.arena_sizes == [max(group_bytes)] and not world.plain
    pool = end["host_pool"]
    # a range is any size: a leaf lands where leaves of other sizes landed
    assert pool["hits"] >= 2 and pool["hits"] + pool["misses"] == 6
    assert pool["bytes"] + pool["fresh"] == sum(group_bytes)
    assert pool["fresh"] <= pool["high_water"] <= max(group_bytes)
    assert nothing_left(world)


@pytest.mark.parametrize("seen", ["at_plan_time", "only_at_the_landing"])
def test_a_backend_that_keeps_host_memory_gets_no_arena(world, seen):
    """The CPU backend's ``device_put`` of a 64-byte-aligned host array copies
    nothing: the restored array IS the host memory.  Seen from the targets'
    devices when the leaves are reserved, there is no arena at all.  Were it
    not seen there (the backend taken for an accelerator, the arena at a
    64-byte boundary), the lander sees it of each landed array: the range is
    never handed out again, and nor is any other."""
    if seen == "at_plan_time":
        world.accelerator = False
    else:
        world.offset_from_64 = 0
    saved, other = make_app(3), make_app(4)
    path, other_path = take(world, "snap", saved), take(world, "other", other)
    target = make_app(0, zero=True)
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    # not one page was used twice
    assert end["host_pool"]["hits"] == 0 and end["host_pool"]["bytes"] == 0
    assert end["host_pool"]["misses"] == len(KEYS) * LEAVES
    assert end["host_pool"]["fresh"] == len(KEYS) * STATEFUL_BYTES
    if seen == "at_plan_time":
        assert not world.arenas and len(world.plain) == len(KEYS) * LEAVES
    else:
        aliased = [
            leaf
            for leaf in restored_leaves(target)
            if any(
                begin <= leaf.unsafe_buffer_pointer() < begin + nbytes
                for begin, nbytes in world.addresses[:1]
            )
        ]
        assert len(world.arenas) == 1 and len(aliased) == LEAVES
        assert len(world.plain) == (len(KEYS) - 1) * LEAVES
        del aliased
    # and another snapshot restored through the same code changes nothing
    second = make_app(0, zero=True)
    restore(world, other_path, second)
    assert_equal_bits(second, other)
    assert_equal_bits(target, saved)
    del target, second
    assert nothing_left(world)


@pytest.mark.parametrize("fault", ["corrupt", "truncated", "silently_short"])
def test_a_bad_read_into_a_range_used_before_raises(world, fault):
    """``b_mu/w1`` lands in the range ``a_params/w1`` landed from, which still
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
    # the memory was the one arena's
    assert len(world.arenas) == 1 and not world.plain
    # nothing stale was handed back: the stateful before is as saved (or not
    # yet loaded, where the read itself raised), the others untouched
    if fault != "truncated" or np.asarray(target["a_params"].state_dict()["w0"]).any():
        assert_equal_bits({"a_params": target["a_params"]}, {"a_params": saved["a_params"]})
    for key in ("b_mu", "c_nu"):
        for leaf in target[key].state_dict().values():
            assert not np.asarray(leaf).any()
    assert nothing_left(world)


@pytest.mark.parametrize(
    "failure", ["read_fails", "load_raises", "device_put_fails", "landing_fails"]
)
def test_a_failure_returns_its_range_and_leaves_no_arena_behind(
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
    elif failure == "landing_fails":
        # no transfer is seen to land: every range comes back unfit, so none
        # is handed out again, and the restore raises at its first drain
        real_ready = jax.block_until_ready

        def never_lands(x):
            if threading.current_thread().name == "tpusnap-h2d-lander":
                raise RuntimeError("injected landing failure")
            return real_ready(x)

        monkeypatch.setattr(jax, "block_until_ready", never_lands)
        with pytest.raises(RuntimeError, match="injected landing failure"):
            restore(world, path, target)
        monkeypatch.setattr(jax, "block_until_ready", real_ready)
    else:
        # every batched device_put fails: each leaf goes the per-item way,
        # lands there, and its range is given back all the same
        real_put = jax.device_put
        # (the warning's traceback, kept by the log capture, would hold the pool)
        monkeypatch.setattr(h2d_mod.logger, "warning", lambda *args, **kwargs: None)

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
    assert len(world.arenas) == 1 and (not world.plain or failure == "landing_fails")
    assert nothing_left(world)


def test_chunked_leaves_go_through_the_pool(world):
    # the path of a leaf at the chunk knob, at toy size: four reads a leaf
    # into one range, taken when the first of them is dispatched
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
    # one take a leaf, not a read
    pool = end["host_pool"]
    assert world.arena_sizes == [LEAVES * leaf] and not world.plain
    assert pool["misses"] == LEAVES and pool["hits"] == 2 * LEAVES
    assert pool["bytes"] == pool["hits"] * leaf
    assert pool["fresh"] == pool["high_water"] == pool["misses"] * leaf
    assert nothing_left(world)


STAGES = h2d_mod._STAGES


def assert_turns_add_up(end):
    """What every restore's ``arena_turn`` holds to, whatever went through it:
    the eight stages are the turn, no byte of the arena is lent twice at once,
    and the ranges that completed a turn are what the pool handed out less
    those it dropped."""
    turn, pool = end["arena_turn"], end["host_pool"]
    assert turn["turn_bs"] == pytest.approx(
        sum(turn[stage + "_bs"] for stage in STAGES), rel=1e-12
    )
    assert all(turn[stage + "_s"] >= 0 and turn[stage + "_bs"] >= 0 for stage in STAGES)
    assert turn["turn_bs"] <= turn["arena"] * turn["lent_s"]
    assert turn["ranges"] + turn["dropped"] <= pool["hits"] + pool["misses"]
    assert turn["bytes"] <= pool["bytes"] + pool["fresh"]
    return turn


@pytest.mark.parametrize("leaves", ["dense", "chunked"])
def test_every_pooled_leaf_completes_a_turn_through_the_arena(world, leaves):
    """Three statefuls through one arena of one stateful's bytes: each pooled
    leaf's range is stamped from its grant to its give, by the pipeline's loop
    (adopted, read began, read back), the assembly (submitted), the dispatcher
    (sent, put) and the lander's give, and the counter, once a restore and in
    ``restore.end``, holds the stages' seconds and byte-seconds."""
    shapes = SHAPES if leaves == "dense" else ((1024, 1024),) * LEAVES
    saved = make_app(11, shapes=shapes)
    with knobs.override_max_chunk_size_bytes((512 << 20) if leaves == "dense" else MIB):
        path = take(world, "snap", saved)
    target = make_app(0, shapes=shapes, zero=True)
    before = phase_stats.snapshot()
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    state_bytes = sum(leaf.nbytes for leaf in restored_leaves(saved))
    turn = assert_turns_add_up(end)
    assert not world.plain and turn["dropped"] == 0
    assert turn["ranges"] == len(KEYS) * LEAVES
    assert turn["bytes"] == state_bytes == end["host_pool"]["bytes"] + end["host_pool"]["fresh"]
    assert turn["arena"] == world.arena_sizes[0] == state_bytes // len(KEYS)
    # every range was read into, uploaded and landed from: time in each
    assert turn["read_s"] > 0 and turn["land_s"] > 0 and turn["lent_s"] > 0
    assert turn["gather_s"] + turn["dispatch_s"] > 0
    # and nothing lasted longer than the arena was lent, range by range
    assert sum(turn[stage + "_s"] for stage in STAGES) <= turn["ranges"] * turn["lent_s"]
    counter = phase_stats.delta(before)["arena_turn"]
    assert counter["n"] == 1 and counter["s"] == 0 and "wall" not in counter
    assert {k: counter[k] for k in turn} == turn
    assert nothing_left(world)


def test_a_read_parked_behind_the_loader_shows_under_parked(world, monkeypatch):
    """The first stateful's ``load_state_dict`` takes a fifth of a second:
    the second's reads have their ranges (the first's have landed), are back,
    and wait for the loader with the ranges in hand."""
    saved = make_app(12)
    path = take(world, "snap", saved)
    target = make_app(0, zero=True)
    slow = target[KEYS[0]]
    really_loads = slow.load_state_dict

    def load_slowly(state_dict):
        time.sleep(0.2)
        really_loads(state_dict)

    monkeypatch.setattr(slow, "load_state_dict", load_slowly)
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    turn = assert_turns_add_up(end)
    assert turn["ranges"] == len(KEYS) * LEAVES and turn["dropped"] == 0
    # three leaves of the second stateful, most of the fifth of a second each
    assert turn["parked_s"] > 0.3
    assert turn["parked_bs"] > 0.1 * STATEFUL_BYTES
    wait_pct = 100.0 * sum(
        turn[stage + "_bs"] for stage in ("grant", "slot", "parked", "gather")
    ) / turn["turn_bs"]
    assert 0 < wait_pct <= 100


def test_a_range_whose_landing_failed_is_dropped_and_the_rest_still_add_up(
    world, monkeypatch
):
    """``recycle=False``: the arena ends there, the range is in no stage."""
    saved = make_app(13)
    path = take(world, "snap", saved)
    target = make_app(0, zero=True)
    monkeypatch.setattr(h2d_mod, "_may_alias", lambda out, buf: True)
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    turn = assert_turns_add_up(end)
    # the first range given back "may be the landed array itself": dropped, and
    # from then on plain buffers, which have no turn
    assert turn["dropped"] >= 1 and turn["ranges"] == 0 and turn["bytes"] == 0
    assert world.plain


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
        ("bytes", "fresh", "hits", "misses", "high_water", "populated"), 0
    )
    assert not world.arenas and not world.plain
    got = Snapshot(path).read_object("0/host/w")
    np.testing.assert_array_equal(got, saved["host"].state_dict()["w"])
    assert len(world.pools) == 1 and nothing_left(world)


POOL_ACCOUNT = ("bytes", "fresh", "hits", "misses", "high_water")


def test_the_arena_is_populated_once_whole_before_any_read_lands_in_it(world):
    saved = make_app(10)
    path = take(world, "snap", saved)
    target = make_app(0, zero=True)
    before = phase_stats.snapshot()
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    # one touch a restore: the whole arena, on the thread of the first take,
    # before a single read into place had been dispatched
    ((begin, nbytes),) = world.addresses
    assert world.touches == [(begin, nbytes, "tpusnap-read-pipeline", 0)]
    assert nbytes == STATEFUL_BYTES and len(world.reads) == len(KEYS) * LEAVES
    assert end["host_pool"]["populated"] == STATEFUL_BYTES
    # with a phase of its own: in the call's account, so not unattributed
    populate = phase_stats.delta(before)["arena_populate"]
    assert populate["n"] == 1 and populate["bytes"] == STATEFUL_BYTES
    assert end["phases"]["arena_populate"] == pytest.approx(populate["wall"])
    assert nothing_left(world)


def test_a_library_without_the_symbol_restores_as_before_and_says_nothing(world, caplog):
    """What is handed out for the first time is ``fresh`` whether or not its
    pages were populated: the account of a restore is the same numbers with
    the population and without, and without it nothing is logged."""
    saved = make_app(11)
    path = take(world, "snap", saved)
    target = make_app(0, zero=True)
    _, populated = restore(world, path, target)
    assert_equal_bits(target, saved)
    world.populates = False
    del world.touches[:]
    target = make_app(0, zero=True)
    before = phase_stats.snapshot()
    with caplog.at_level("WARNING"):
        _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    assert not world.touches and end["host_pool"]["populated"] == 0
    assert "arena_populate" not in end["phases"]
    assert "arena_populate" not in phase_stats.delta(before)
    assert not [r for r in caplog.records if r.name.startswith("torchsnapshot_tpu")]
    assert populated["host_pool"]["populated"] == STATEFUL_BYTES
    for key in POOL_ACCOUNT:
        assert end["host_pool"][key] == populated["host_pool"][key], key
    assert world.arena_sizes == [STATEFUL_BYTES] * 2 and not world.plain
    assert nothing_left(world)


def test_a_restore_through_a_populated_arena_is_bit_exact(world, monkeypatch):
    """The arena arrives full of ones, so the byte that the population
    writes to each page is there to be seen; every leaf is read over it."""
    if h2d_mod._page_toucher() is None:
        pytest.skip("the native library has no tpusnap_touch_pages")
    arenas = []
    recorded = h2d_mod._arena_memory

    def full_of_ones(nbytes):
        buf = recorded(nbytes)
        buf[:] = 0xFF
        arenas.append(buf)
        return buf

    monkeypatch.setattr(h2d_mod, "_arena_memory", full_of_ones)
    seen = []
    touch = h2d_mod._page_toucher()

    def touched_and_looked_at(buf):
        touch(buf)
        seen.append(int((buf == 0).sum()))

    monkeypatch.setattr(h2d_mod, "_page_toucher", lambda: touched_and_looked_at)
    shapes = ((256, 1024), (513, 1024), (770, 1024))  # leaves that end inside a page
    saved = make_app(12, shapes=shapes)
    path = take(world, "snap", saved)
    target = make_app(0, shapes=shapes, zero=True)
    _, end = restore(world, path, target)
    assert_equal_bits(target, saved)
    ((begin, nbytes),) = world.addresses
    # the first byte of the arena (which begins inside a page here) and of
    # each page that begins in it, and not one byte more
    assert seen == [1 + (begin + nbytes - 1) // PAGE - begin // PAGE]
    assert end["host_pool"]["populated"] == nbytes
    del arenas[:]
    assert nothing_left(world)


# ----------------------------------------------------------- the pool alone

ON_A_CHIP = types.SimpleNamespace(devices=lambda: [types.SimpleNamespace(platform="tpu")])
ON_THE_HOST = types.SimpleNamespace(devices=lambda: [types.SimpleNamespace(platform="cpu")])


def pool_of(*groups, window, target=ON_A_CHIP):
    """A pool with ``groups`` of leaves reserved, in pages, whose one batcher
    has an in-flight cap of ``window`` pages (the arena's size, where no leaf
    is larger and a stateful is) and counts its flushes (``flushes``)."""
    pool = HostBufferPool()
    batcher = pool.batcher
    batcher.inflight_cap_bytes = window * PAGE
    real_flush, batcher.flushes = batcher.flush, 0

    def flush():
        batcher.flushes += 1
        real_flush()

    batcher.flush = flush
    for group in groups:
        pool.begin_group()
        for pages in group:
            pool.reserve(pages * PAGE, target)
    return pool, batcher


def in_arena(buf, base, pages):
    return base <= buf.ctypes.data and buf.ctypes.data + buf.nbytes <= base + pages * PAGE


def test_a_take_is_a_range_a_give_frees_it_and_neighbours_coalesce():
    pool, _batcher = pool_of([4, 2, 2], [4, 2, 2], [4, 2, 2], window=8)
    a = pool.take(4 * PAGE - 100)  # any size: the range is whole pages
    b, c = pool.take(2 * PAGE), pool.take(2 * PAGE)
    assert a.dtype == np.uint8 and a.shape == (4 * PAGE - 100,) and b.shape == (2 * PAGE,)
    base = a.ctypes.data
    assert base % PAGE == 0
    assert (b.ctypes.data, c.ctypes.data) == (base + 4 * PAGE, base + 6 * PAGE)
    # full: a take that cannot wait is a plain buffer, and counted as one
    plain = pool.take(PAGE)
    assert not in_arena(plain, base, 8)
    pool.give(plain, recycle=True)
    pool.give(a, recycle=True)
    pool.give(c, recycle=True)
    # [0, 4) and [6, 8) are free, and five pages fit in neither
    assert not in_arena(pool.take(5 * PAGE), base, 8)
    # first fit from the lowest address, where the pages have been touched
    d = pool.take(PAGE)
    assert d.ctypes.data == base
    pool.give(d, recycle=True)
    pool.give(b, recycle=True)  # one with both its neighbours
    whole = pool.take(8 * PAGE)
    assert whole.ctypes.data == base
    assert pool.stats() == {
        "bytes": PAGE + 8 * PAGE,
        "fresh": (4 * PAGE - 100) + 2 * PAGE + 2 * PAGE + PAGE + 5 * PAGE,
        "hits": 2,
        "misses": 5,
        "high_water": 8 * PAGE + 5 * PAGE,
        "populated": 8 * PAGE if h2d_mod._page_toucher() else 0,
    }


def test_a_take_with_no_room_flushes_the_batchers_and_waits_its_turn():
    pool, batcher = pool_of([4, 4, 6, 1, 1], [4, 4, 6, 1, 1], window=12)
    from_thread = []

    def landed(buf):
        lander = threading.Thread(target=pool.give, args=(buf, True))
        lander.start()
        lander.join(5)
        from_thread.append(lander.is_alive())

    async def read_pipeline():
        loop = asyncio.get_running_loop()
        a, b, c = (pool.take(4 * PAGE, loop) for _ in range(3))
        base = a.ctypes.data
        assert not pool.waiting() and batcher.flushes == 0
        first = pool.take(6 * PAGE, loop)  # no room: what the batcher holds goes
        assert isinstance(first, asyncio.Future) and not first.done()
        assert pool.waiting() and batcher.flushes == 1
        landed(b)  # four pages free in the middle: not six
        second = pool.take(PAGE, loop)  # fits, and is behind the first all the same
        assert isinstance(second, asyncio.Future) and batcher.flushes == 2
        await asyncio.sleep(0.05)
        assert not first.done() and not second.done()
        landed(a)  # eight pages from the bottom: woken by the give, from its thread
        got = await asyncio.wait_for(first, 5)
        assert got.ctypes.data == base and got.shape == (6 * PAGE,)
        got = await asyncio.wait_for(second, 5)
        assert got.ctypes.data == base + 6 * PAGE
        assert not pool.waiting()
        assert isinstance(pool.take(PAGE, loop), np.ndarray)  # room, nobody in front
        assert not any(from_thread) and c.ctypes.data == base + 8 * PAGE

    asyncio.run(read_pipeline())


@pytest.mark.parametrize("dispatcher", ["idle", "busy"])
def test_a_read_with_no_room_is_served_by_a_batch_that_was_only_queued(
    monkeypatch, dispatcher
):
    """The liveness argument with the flush a hand-off: a read that finds no
    room flushes the batcher, which now only queues, and still the leaf
    gathered there is sent, lands and frees its range, whether the dispatcher
    was idle (nothing had reached ``flush_bytes``) or busy with the batch
    before (then the queued one goes when it comes free: a waiter never waits
    on a batch that nobody will dispatch).  While a batch is only queued it
    holds its range of the arena and nothing on the device."""
    monkeypatch.setattr(
        h2d_mod, "_arena_memory", lambda nbytes: memory_at(nbytes, 16)[1]
    )
    pool = HostBufferPool()
    pool.batcher = batcher = H2DBatcher(
        pool, flush_bytes=1 << 30, inflight_cap_bytes=8 * PAGE
    )
    pool.begin_group()
    for _ in range(3):
        pool.reserve(4 * PAGE, ON_A_CHIP)
    like = jnp.zeros(PAGE, jnp.float32)  # four pages of float32
    puts, gate = [], threading.Event()
    real_dispatch = H2DBatcher._dispatch

    def held_dispatch(self, items):
        puts.append(threading.current_thread().name)
        assert gate.wait(10)
        return real_dispatch(self, items)

    monkeypatch.setattr(H2DBatcher, "_dispatch", held_dispatch)
    if dispatcher == "idle":
        gate.set()

    async def read_pipeline():
        loop = asyncio.get_running_loop()
        a, b = pool.take(4 * PAGE, loop), pool.take(4 * PAGE, loop)
        assert a.ctypes.data + 4 * PAGE == b.ctypes.data  # the arena is both
        futures = [Future(), Future()]
        for lease, fut, fill in ((a, futures[0], 1.0), (b, futures[1], 2.0)):
            lease.view(np.float32)[:] = fill
            if dispatcher == "busy" and lease is b:
                # the first leaf is with the dispatcher, held; this one is
                # flushed behind it by the wait below
                batcher.flush()
                await asyncio.sleep(0.05)
                assert puts == ["tpusnap-h2d-dispatcher"]
            batcher.submit(lease.view(np.float32), like, fut, lease)
        assert not batcher._dispatching or dispatcher == "busy"
        coming = pool.take(4 * PAGE, loop)  # no room: the wait flushes the batcher
        assert isinstance(coming, asyncio.Future) and pool.waiting()
        if dispatcher == "busy":
            await asyncio.sleep(0.05)
            # queued, not sent: its range is held, nothing of it is on the device
            assert not coming.done() and len(batcher._queued) == 1
            # (the window holds the first leaf's reservation and no more)
            assert futures[1].obj is None and batcher._unlanded_bytes == 4 * PAGE
            gate.set()
        got = await asyncio.wait_for(coming, 10)
        assert got.ctypes.data in (a.ctypes.data, b.ctypes.data)
        return futures

    try:
        futures = asyncio.run(read_pipeline())
        batcher.drain()
        for fut, fill in zip(futures, (1.0, 2.0)):
            np.testing.assert_array_equal(np.asarray(fut.obj), np.full(PAGE, fill))
        assert set(puts) == {"tpusnap-h2d-dispatcher"}
        assert pool.batcher.route()["bytes"] == 8 * PAGE
    finally:
        gate.set()
        pool.close()
    assert not [t for t in threading.enumerate() if t.name in PIPELINE_THREADS]


def test_a_give_after_the_waiting_pipeline_is_gone_is_only_a_give():
    pool, _batcher = pool_of([4, 4], [4, 4], window=8)
    a, b = pool.take(4 * PAGE), pool.take(4 * PAGE)
    base = a.ctypes.data

    async def aborted():
        loop = asyncio.get_running_loop()
        cancelled = pool.take(4 * PAGE, loop)
        cancelled.cancel()  # with its read
        pool.give(a, recycle=True)  # granted to a read that is gone ...
        await asyncio.sleep(0.05)
        assert not pool.waiting()
        again = pool.take(4 * PAGE, loop)  # ... and given back by the grant
        assert isinstance(again, np.ndarray) and again.ctypes.data == base
        return pool.take(4 * PAGE, loop)

    loop = asyncio.new_event_loop()
    left = loop.run_until_complete(aborted())
    loop.close()
    pool.give(b, recycle=True)  # the lander, after the pipeline's loop has closed
    assert not left.done() and not pool.waiting()
    assert pool.take(4 * PAGE).ctypes.data == base + 4 * PAGE


def test_a_range_given_back_unfit_ends_the_arena():
    pool, _batcher = pool_of([4, 4], [4, 4], window=8)
    a, b = pool.take(4 * PAGE), pool.take(4 * PAGE)
    base = a.ctypes.data

    async def read_pipeline():
        coming = pool.take(4 * PAGE, asyncio.get_running_loop())
        # its transfer failed, or the landed array may be the range itself
        pool.give(a, recycle=False)
        return await asyncio.wait_for(coming, 5)

    got = asyncio.run(read_pipeline())
    assert got.shape == (4 * PAGE,) and not in_arena(got, base, 8)
    pool.give(b, recycle=True)  # free, and handed to nobody
    assert not in_arena(pool.take(4 * PAGE), base, 8)
    stats = pool.stats()
    assert stats["hits"] == 0 and stats["misses"] == 4 and stats["bytes"] == 0


@pytest.mark.parametrize(
    "groups, window, target, pages",
    [
        # every leaf fits
        pytest.param([[1, 2, 4, 8, 8, 16, 16]] * 3, 10, ON_A_CHIP, 16, id="the_largest_leaf"),
        # many small leaves: what the batcher may have in flight
        pytest.param([[1] * 30 + [6] * 5] * 3, 20, ON_A_CHIP, 20, id="the_window"),
        pytest.param([[2, 3], [2, 3], [1]], 1 << 17, ON_A_CHIP, 5, id="the_largest_stateful"),
        pytest.param([[8]], 4, ON_A_CHIP, None, id="one_leaf"),
        pytest.param([[2, 3], []], 20, ON_A_CHIP, None, id="nothing_to_use_twice"),
        pytest.param([[1, 2, 4, 8]] * 3, 4, ON_THE_HOST, None, id="host_memory_kept"),
    ],
)
def test_the_arena_is_sized_from_what_the_plan_reserved(
    monkeypatch, groups, window, target, pages
):
    made = []
    real = h2d_mod._arena_memory
    monkeypatch.setattr(
        h2d_mod, "_arena_memory", lambda nbytes: made.append(nbytes) or real(nbytes)
    )
    pool, _batcher = pool_of(*groups, window=window, target=target)
    assert not made  # nothing before the first take
    first = pool.take(PAGE)
    assert made == ([pages * PAGE] if pages else [])
    assert first.shape == (PAGE,) and pool.stats()["fresh"] == PAGE


@pytest.fixture
def touches(monkeypatch):
    """The populations of pools made alone, ``(where, how much)`` each, with
    nothing really written."""
    seen = []
    monkeypatch.setattr(
        h2d_mod, "_page_toucher", lambda: lambda buf: seen.append((buf.ctypes.data, buf.nbytes))
    )
    return seen


def test_the_first_take_populates_the_whole_arena_and_no_later_one_does(touches):
    pool, _batcher = pool_of([4, 2, 2], [4, 2, 2], window=6)
    assert not touches  # nothing before the first take
    a = pool.take(2 * PAGE - 7)
    base = a.ctypes.data
    # all six pages, inside the take that handed out the first range, which
    # wanted two of them
    assert touches == [(base, 6 * PAGE)]
    b = pool.take(4 * PAGE)  # pages never handed out: populated all the same
    pool.give(a, recycle=True)
    c = pool.take(PAGE)
    plain = pool.take(4 * PAGE)  # no room and no loop: a plain buffer
    assert len(touches) == 1 and (b.ctypes.data, c.ctypes.data) == (base + 2 * PAGE, base)
    stats = pool.stats()
    assert stats["populated"] == 6 * PAGE and stats["high_water"] == 10 * PAGE
    # handed out for the first time is fresh, populated or not
    assert stats["fresh"] == (2 * PAGE - 7) + 4 * PAGE + 4 * PAGE and stats["bytes"] == PAGE
    assert not in_arena(plain, base, 6)


@pytest.mark.parametrize(
    "path",
    ["host_memory_kept", "one_pooled_leaf", "everything_fits", "after_a_range_came_back_unfit"],
)
def test_no_plain_path_populates(touches, path):
    if path == "host_memory_kept":
        pool, _batcher = pool_of(*[[1, 2, 4, 8]] * 3, window=4, target=ON_THE_HOST)
    elif path == "one_pooled_leaf":
        pool, _batcher = pool_of([8], window=4)
    elif path == "everything_fits":
        pool, _batcher = pool_of([2, 3], [], window=20)
    else:
        pool, _batcher = pool_of([4, 4], [4, 4], window=8)
        a = pool.take(4 * PAGE)
        assert len(touches) == 1
        del touches[:]
        pool.give(a, recycle=False)  # no arena from here on
    before = phase_stats.snapshot()
    bufs = [pool.take(2 * PAGE), pool.take(PAGE)]
    assert not touches and "arena_populate" not in phase_stats.delta(before)
    stats = pool.stats()
    assert stats["populated"] == (8 * PAGE if path == "after_a_range_came_back_unfit" else 0)
    assert stats["hits"] == 0 and stats["bytes"] == 0
    assert all(buf.dtype == np.uint8 for buf in bufs)


def test_the_arena_goes_when_the_restore_ends_and_its_last_range_is_back(monkeypatch):
    arenas = []

    def recorded(nbytes):
        raw, buf = memory_at(nbytes, 0)
        arenas.append(weakref.ref(raw))
        return buf

    monkeypatch.setattr(h2d_mod, "_arena_memory", recorded)
    pool, _batcher = pool_of([4, 4], [4, 4], window=8)
    a, b = pool.take(4 * PAGE), pool.take(PAGE)
    assert len(arenas) == 1 and pool.stats()["high_water"] == 5 * PAGE
    pool.give(a, recycle=True)
    del a
    pool.close()  # the restore has ended ...
    assert arenas[0]() is not None  # ... and a range still out is still memory
    pool.give(b, recycle=True)  # given back late, to nobody
    del b
    assert arenas[0]() is None
    pool.close()  # said twice is said once
    assert pool.stats()["high_water"] == 5 * PAGE


def test_no_range_is_in_two_hands_at_once_however_the_landings_come():
    """Takes in dispatch order from one loop, more of them than the arena
    holds; four landers that give back in whatever order their naps end; a
    short switch interval.  A range taken is nobody else's until given back,
    every read gets its turn, and the account adds up."""
    rng = random.Random(7)
    takes = [rng.randint(1, 6) for _ in range(600)]
    pool, _batcher = pool_of(*[takes[i::4] for i in range(4)], window=12)
    out, clashes, held = [], [], {}
    check, stop = threading.Lock(), threading.Event()

    def lander(seed):
        naps = random.Random(seed)
        while not stop.is_set() or out:
            try:
                n, lease = out.pop(naps.randrange(len(out)))
            except (IndexError, ValueError):
                time.sleep(0.0005)
                continue
            time.sleep(naps.random() * 0.002)
            if (lease != n % 251).any():
                clashes.append(("written over", n))
            with check:
                del held[n]
            pool.give(lease, recycle=True)

    async def read(n, pages):
        taken = pool.take(pages * PAGE - n % 64, asyncio.get_running_loop())
        lease = taken if isinstance(taken, np.ndarray) else await asyncio.wait_for(taken, 30)
        begin = lease.ctypes.data
        with check:
            for other, (b, e) in held.items():
                if begin < e and b < begin + pages * PAGE:
                    clashes.append(("overlaps", n, other))
            held[n] = (begin, begin + pages * PAGE)
        lease[:] = n % 251
        out.append((n, lease))
        return begin

    async def read_pipeline():
        return await asyncio.gather(*(read(n, pages) for n, pages in enumerate(takes)))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    landers = [threading.Thread(target=lander, args=(seed,)) for seed in range(4)]
    try:
        for t in landers:
            t.start()
        begins = asyncio.run(read_pipeline())
    finally:
        stop.set()
        for t in landers:
            t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in landers)
    assert not clashes and not held and not pool.waiting()
    base = min(begins)
    assert max(begins) < base + 12 * PAGE  # never a plain buffer
    stats = pool.stats()
    assert stats["hits"] + stats["misses"] == len(takes)
    assert stats["fresh"] <= stats["high_water"] <= 12 * PAGE
    assert pool.take(12 * PAGE).ctypes.data == base  # all of it is free, and one range
