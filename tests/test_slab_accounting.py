"""The slab path accounts for itself, and a call's account stays exact for a
tree of many leaves: ``slab_write`` / ``slab_read`` counters and the
``slab_scatter`` phase of ``batcher.py``, ``leaves`` and ``slab_read_bytes``
in the ``restore.end`` event, and ``phase_stats.hold`` (a call that reads its
own account keeps every interval it leaves, however many)."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import SnapshotManager, StateDict, knobs, phase_stats
from torchsnapshot_tpu.event_handlers import (
    register_event_handler,
    unregister_event_handler,
)
from torchsnapshot_tpu.telemetry import analyze

SMALL = 40  # leaves of 4 KiB
BIG = (256, 1024)  # one leaf of 1 MiB


def make_app(zero=False, small=(32, 32)):
    rng = np.random.RandomState(3)

    def leaf(shape):
        return jnp.zeros(shape, jnp.float32) if zero else jnp.asarray(rng.rand(*shape).astype(np.float32))

    state = {f"w{i:02d}": leaf(small) for i in range(SMALL)}
    state["big"] = leaf(BIG)
    return {"params": StateDict(state)}


def run(tmp_path, threshold, **shape):
    """One save and one restore with the slab threshold at ``threshold``;
    returns each side's phase delta and the restore.end event."""
    ends = []

    def on_event(event):
        if event.name == "restore.end":
            ends.append(dict(event.metadata))

    saved = make_app(**shape)
    with knobs.override_slab_size_threshold_bytes(threshold):
        manager = SnapshotManager(str(tmp_path / "root"))
        before = phase_stats.snapshot()
        manager.save(1, saved)
        save_delta = phase_stats.delta(before)
        target = make_app(zero=True, **shape)
        before = phase_stats.snapshot()
        register_event_handler(on_event)
        try:
            assert manager.restore_latest(target) == 1
        finally:
            unregister_event_handler(on_event)
        restore_delta = phase_stats.delta(before)
    for name, want in saved["params"].state_dict().items():
        np.testing.assert_array_equal(np.asarray(target["params"][name]), np.asarray(want))
    return save_delta, restore_delta, ends[-1]


def test_slab_counters_count_what_the_slabs_carried(tmp_path):
    # every small leaf is under the threshold and the big one is not
    save, restore, end = run(tmp_path, 64 << 10)
    small_bytes = SMALL * 32 * 32 * 4
    w, r = save["slab_write"], restore["slab_read"]
    assert w["bytes"] == small_bytes and w["members"] == SMALL
    assert w["n"] == 3  # 40 x 4 KiB packed greedily under 64 KiB: 15 + 15 + 10
    assert r["bytes"] == small_bytes and r["members"] == SMALL
    # one plan (one stateful); a leaf of 4 KiB has no place of its own to land
    # in, so each slab file is one merged read
    assert r["n"] == 1 and r["reads"] == w["n"] and r["merged"] == small_bytes
    assert "wall" not in w and "wall" not in r  # counters: no interval
    scatter = restore["slab_scatter"]
    assert scatter["n"] == r["reads"] and scatter["bytes"] == small_bytes and scatter["wall"] > 0
    assert end["slab_read_bytes"] == small_bytes
    assert end["leaves"] == SMALL + 1
    assert end["phases"]["slab_scatter"] > 0
    assert analyze.classify_phase("slab_scatter") == "serialize"


def test_a_member_of_a_megabyte_keeps_a_read_of_its_own(tmp_path):
    # 41 leaves of 1 MiB under a threshold of 8 MiB: five slab files of 8 members
    # (the last leaf is alone: a plain file), each member read into its restore
    # target by a ranged read of its own
    save, restore, end = run(tmp_path, 8 << 20, small=(512, 512))
    w, r = save["slab_write"], restore["slab_read"]
    assert w["n"] == 5 and w["members"] == 40 and w["bytes"] == 40 << 20
    assert r["bytes"] == w["bytes"] and r["members"] == w["members"]
    assert r["reads"] == r["members"] and r["merged"] == 0
    assert "slab_scatter" not in restore
    assert end["slab_read_bytes"] == r["bytes"]


def test_a_leaf_alone_in_its_slab_is_no_slab(tmp_path):
    # threshold under two leaves' bytes: every group is one leaf, a plain file
    save, restore, end = run(tmp_path, 6 << 10)
    assert "slab_write" not in save and "slab_read" not in restore
    assert "slab_scatter" not in restore
    assert end["slab_read_bytes"] == 0 and end["leaves"] == SMALL + 1


def test_add_counter_sums_what_it_is_given_more_of():
    before = phase_stats.snapshot()
    phase_stats.add_counter("slab_test_counter", 0.0, 10, members=3)
    phase_stats.add_counter("slab_test_counter", 0.0, 5, members=2)
    d = phase_stats.delta(before)["slab_test_counter"]
    assert (d["bytes"], d["n"], d["members"]) == (15, 2, 5)


# ------------------------------------------------- a call's account, exact


def record_disjoint(phase, n, begin):
    """``n`` intervals of 1 ms, 2 ms apart, from ``begin`` on."""
    for i in range(n):
        phase_stats.add(phase, 0.001, end=begin + 0.002 * i + 0.001)
    return begin + 0.002 * n


@pytest.mark.parametrize("n", [1000, 3000])
def test_the_remainder_is_exact_with_a_thousand_intervals_a_phase(n):
    phase = f"slab_test_many_{n}"
    begin = time.monotonic() + 20.0 * n  # stamps no other phase of this process has
    token = phase_stats.hold(begin)
    try:
        end = record_disjoint(phase, n, begin)
        assert phase_stats.attributed_wall_s(begin, end) == pytest.approx(0.001 * n, rel=1e-6)
        assert phase_stats.walls_between(begin, end)[phase] == pytest.approx(0.001 * n, rel=1e-6)
    finally:
        phase_stats.release(token)
    # the process-wide wall was exact all along, and stays so
    assert phase_stats.snapshot()[phase]["wall"] == pytest.approx(0.001 * n, rel=1e-6)


def test_without_a_hold_the_old_intervals_retire_and_the_list_stays_bounded():
    phase = "slab_test_unheld"
    begin = time.monotonic() + 5000.0
    end = record_disjoint(phase, 3000, begin)
    assert len(phase_stats._intervals[phase]) <= 2 * phase_stats._COMPACT_THRESHOLD
    assert phase_stats.walls_between(begin, end)[phase] < 0.001 * 3000  # the under-count a hold cures
    assert phase_stats.snapshot()[phase]["wall"] == pytest.approx(3.0, rel=1e-6)


def test_a_released_hold_lets_the_list_shrink_again():
    phase = "slab_test_released"
    begin = time.monotonic() + 9000.0
    token = phase_stats.hold(begin)
    end = record_disjoint(phase, 2000, begin)
    assert len(phase_stats._intervals[phase]) >= 2000
    phase_stats.release(token)
    record_disjoint(phase, 3000, end)
    assert len(phase_stats._intervals[phase]) <= 2 * phase_stats._COMPACT_THRESHOLD
    assert phase_stats.snapshot()[phase]["wall"] == pytest.approx(5.0, rel=1e-6)


def test_restore_holds_its_own_account_and_lets_go(tmp_path):
    run(tmp_path, 64 << 10)
    assert not phase_stats._holds

    class Refuses(StateDict):
        def load_state_dict(self, state_dict):
            raise ValueError("refused")

    manager = SnapshotManager(str(tmp_path / "root"))
    target = {"params": Refuses(make_app(zero=True)["params"])}
    with pytest.raises(RuntimeError, match="failed to restore"):
        manager.restore_latest(target)
    assert not phase_stats._holds


def test_a_long_block_beside_many_short_ones_is_counted_once():
    """ADVICE.md's double count: compaction retiring intervals that a
    concurrent, longer ``timed()`` block will later reach back over.  The
    block's begin is the phase's low-water mark, so the wall is the union."""
    phase = "slab_test_concurrent"
    started, stop = threading.Event(), threading.Event()

    def long_block():
        with phase_stats.timed(phase):
            started.set()
            stop.wait(30)

    t0 = time.monotonic()
    thread = threading.Thread(target=long_block)
    thread.start()
    started.wait(30)
    # 700 short disjoint blocks inside the long one: past the threshold
    for _ in range(700):
        with phase_stats.timed(phase):
            pass
        time.sleep(0.0002)
    stop.set()
    thread.join()
    t1 = time.monotonic()
    wall = phase_stats.snapshot()[phase]["wall"]
    assert wall <= t1 - t0 + 1e-6
    assert wall == pytest.approx(phase_stats.walls_between(t0, t1)[phase], rel=1e-6)
