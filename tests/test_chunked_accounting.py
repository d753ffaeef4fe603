"""The chunked path accounts for itself: the ``chunked_write`` /
``chunked_read`` counters (one occurrence a plan; the bytes and leaves of the
plan's chunked entries and how many chunks they are in), the phase
``chunk_assemble`` (one interval a chunked leaf, from the arrival of its
first chunk to the arrival of its last; none for a dense leaf), and
``chunked_read_bytes`` in the ``restore.end`` event."""

import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import SnapshotManager, StateDict, knobs, phase_stats
from torchsnapshot_tpu.event_handlers import (
    register_event_handler,
    unregister_event_handler,
)
from torchsnapshot_tpu.telemetry import analyze

BIG = (4, 256, 512)  # 2 MiB of float32 in 4 rows: 3 + 1 under a chunk size of 1.75 MiB
DENSE = (256, 256)  # 256 KiB: dense under it
CHUNK = 1792 << 10
BIG_BYTES = int(np.prod(BIG)) * 4


def make_app(zero=False, big=True, as_numpy=False):
    rng = np.random.RandomState(5)

    def leaf(shape):
        a = np.zeros(shape, np.float32) if zero else rng.rand(*shape).astype(np.float32)
        return a if as_numpy else jnp.asarray(a)

    def stateful(name):
        state = {"dense": leaf(DENSE)}
        if big and name == "params":
            state["big"] = leaf(BIG)
        return StateDict(state)

    return {"params": stateful("params"), "moments": stateful("moments")}


def run(tmp_path, **shape):
    """One save and one restore of two statefuls under a chunk size of
    ``CHUNK``: each side's phase delta, the hook's intervals of the restore
    and the restore.end event."""
    ends, intervals = [], []

    def on_event(event):
        if event.name == "restore.end":
            ends.append(dict(event.metadata))

    saved = make_app(**shape)
    with knobs.override_max_chunk_size_bytes(CHUNK):
        manager = SnapshotManager(str(tmp_path / "root"))
        before = phase_stats.snapshot()
        manager.save(1, saved)
        save_delta = phase_stats.delta(before)
        target = make_app(zero=True, **shape)
        before = phase_stats.snapshot()
        register_event_handler(on_event)
        phase_stats.set_trace_hook(lambda p, b, e, n: intervals.append((p, b, e, n)))
        try:
            assert manager.restore_latest(target) == 1
        finally:
            phase_stats.set_trace_hook(None)
            unregister_event_handler(on_event)
        restore_delta = phase_stats.delta(before)
    for key, stateful in saved.items():
        for name, want in stateful.state_dict().items():
            np.testing.assert_array_equal(np.asarray(target[key][name]), np.asarray(want))
    return save_delta, restore_delta, intervals, ends[-1]


@pytest.mark.parametrize("as_numpy", [False, True], ids=["jax_target", "numpy_target"])
def test_counters_count_the_chunked_leaf_and_not_the_dense_ones(tmp_path, as_numpy):
    save, restore, intervals, end = run(tmp_path, as_numpy=as_numpy)
    w, r = save["chunked_write"], restore["chunked_read"]
    # one write plan a take; one read plan a stateful, the one without a
    # chunked leaf counted too
    assert (w["n"], r["n"]) == (1, 2)
    for c in (w, r):
        assert (c["bytes"], c["leaves"], c["chunks"]) == (BIG_BYTES, 1, 2)
        assert "wall" not in c and c["s"] == 0  # counters: no interval
    assert end["chunked_read_bytes"] == BIG_BYTES
    assert end["leaves"] == 3


def test_chunk_assemble_opens_and_closes_once_a_chunked_leaf(tmp_path):
    _, restore, intervals, end = run(tmp_path)
    a = restore["chunk_assemble"]
    assert a["n"] == 1 and a["bytes"] == BIG_BYTES and a["wall"] > 0
    mine = [iv for iv in intervals if iv[0] == "chunk_assemble"]
    assert len(mine) == 1
    _, begin, finish, nbytes = mine[0]
    assert nbytes == BIG_BYTES and finish > begin
    # it closes before the leaf is handed on: its upload begins no earlier
    uploads = [iv for iv in intervals if iv[0] == "h2d_dispatch" and iv[3] >= BIG_BYTES]
    assert uploads and all(b >= finish - 1e-6 for _, b, _, _ in uploads)
    assert end["phases"]["chunk_assemble"] == pytest.approx(a["wall"], rel=0.05)
    assert analyze.classify_phase("chunk_assemble") == "serialize"


def test_a_restore_with_no_chunked_leaf_counts_its_plans_and_opens_no_interval(tmp_path):
    save, restore, intervals, end = run(tmp_path, big=False)
    w, r = save["chunked_write"], restore["chunked_read"]
    assert (w["n"], r["n"]) == (1, 2)
    for c in (w, r):
        assert (c["bytes"], c["leaves"], c["chunks"]) == (0, 0, 0)
    assert "chunk_assemble" not in restore
    assert not [iv for iv in intervals if iv[0] == "chunk_assemble"]
    assert end["chunked_read_bytes"] == 0 and "chunk_assemble" not in end["phases"]


def test_the_chunks_are_three_rows_and_one(tmp_path):
    run(tmp_path)
    manifest = SnapshotManager(str(tmp_path / "root")).snapshot(1).get_manifest()
    entry = next(e for path, e in manifest.items() if path.endswith("params/big"))
    assert [c.sizes[0] for c in entry.chunks] == [3, 1]
    assert [c.offsets[0] for c in entry.chunks] == [0, 3]


# ------------------------------------------- no second copy of a chunked leaf


def test_planning_a_chunked_device_leaf_makes_no_device_slice(tmp_path):
    """``arr[start:stop]`` of a jax array is a buffer of its own: sliced at
    plan time, every chunk of every chunked leaf sat on the device beside the
    leaf for the whole save (PR 31: 6.42 GB beside a 7.77 GB state).  The
    plan holds handles; a stager slices, transfers and lets go."""
    import asyncio

    import jax

    from torchsnapshot_tpu.io_preparers.chunked_array import ChunkedArrayIOPreparer

    leaf = jnp.asarray(np.random.RandomState(7).rand(*BIG).astype(np.float32))
    instruction = ChunkedArrayIOPreparer.chunk_instructions(list(BIG), np.float32, CHUNK)
    alive = {id(a) for a in jax.live_arrays()}
    entry, reqs = ChunkedArrayIOPreparer.prepare_write("0/big", leaf, instruction)
    assert len(reqs) == 2
    assert not [a for a in jax.live_arrays() if id(a) not in alive]
    assert [c.sizes[0] for c in entry.chunks] == [3, 1]
    # planning reads shape and dtype off the handle, and the budget sees its bytes
    assert [r.buffer_stager.get_staging_cost_bytes() for r in reqs] == [3 * BIG_BYTES // 4, BIG_BYTES // 4]
    staged = [np.frombuffer(asyncio.run(r.buffer_stager.stage_buffer()), np.float32) for r in reqs]
    want = np.asarray(leaf)
    np.testing.assert_array_equal(staged[0], want[:3].ravel())
    np.testing.assert_array_equal(staged[1], want[3:].ravel())
    # staged, a stager holds nothing of the leaf
    assert all(r.buffer_stager._obj is None for r in reqs)
