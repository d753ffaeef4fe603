"""The window's account: all the work over all the time, so a stall inside
the window moves the end-to-end metric it touches; and the faults that the
comparison has to catch."""

import os

import jax
import pytest

import faults
from chipbench import harness, reference
from conftest import ROOT

TINY = os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny.json")


def run(workload, make_manager=None, seconds=1.0, seed=2147483777, trace=False):
    cell = harness.Cell(TINY, workload)
    return harness.run_cell(cell, jax.devices()[:1], seed, seconds, trace,
                            make_manager=make_manager, setup_clock=lambda: 1.0)


def value(result, name):
    return result["metrics"][name]["value"]


def test_a_stall_moves_the_end_to_end_metric():
    base = run("tiny.toy-save-resume", seconds=2.0)
    slow = run("tiny.toy-save-resume", faults.stalling(0.25), seconds=2.0)
    assert base["correct"] and slow["correct"]
    assert value(slow, "resume_s") > value(base, "resume_s") + 0.2
    # the window closes when the last cycle begun has finished, never before
    assert base["window_s"] >= 2.0 and slow["window_s"] >= 2.0
    # every operation begun is in the account: a save and a restore a cycle
    assert slow["attempted"] >= 2 and slow["attempted"] % 2 == 0 and slow["failed"] == 0
    ops = [o for o in slow["operations"] if o["in_window"]]
    assert len(ops) == slow["attempted"] and {o["op"] for o in ops} == {"save", "kill_resume"}
    assert all(o["save_s"] >= 0.25 for o in ops if o["op"] == "save")


def test_the_rate_is_over_the_whole_window():
    r = run("codestral22b.kill-resume", seconds=1.0)
    assert value(r, "resume_s") == pytest.approx(r["window_s"] / r["attempted"])
    # the set-up's save is in the account and outside the window
    assert [o["op"] for o in r["operations"] if not o["in_window"]] == ["save"]
    assert sum(o["resume_s"] for o in r["operations"] if o["in_window"]) <= r["window_s"]


@pytest.mark.parametrize(
    "workload,fault,number",
    [
        ("codestral22b.kill-resume", faults.FlipOneBit, "leaves_differ"),
        ("mistral7b.kill-resume", faults.FlipOneBit, "leaves_differ"),
        ("codestral22b.kill-resume", faults.RestoreNothing, "leaves_differ"),
        ("mistral7b.kill-resume", faults.RestoreNothing, "step_gap"),
        ("tiny.toy-save-resume", faults.StaleRestore, "leaves_differ"),
        ("mistral7b.kill-resume", faults.LosesAStateful, "leaves_differ"),
        ("codestral22b.kill-resume", faults.LosesAStateful, "leaves_differ"),
    ],
)
def test_a_broken_path_is_not_correct(workload, fault, number):
    r = run(workload, fault)
    assert r["correct"] is False
    got, limit = r["checks"][number]
    assert got > limit, r["checks"]


@pytest.mark.parametrize("workload", ["codestral22b.kill-resume", "mistral7b.kill-resume"])
def test_the_control_is_not_correct(workload):
    """The reference in the program's place, one precision down (bfloat16 for
    the float32 state, float8 for the bfloat16 state), fails the comparison;
    the program itself passes it on the same seed."""
    for seed in (2147483801, 2147483802, 2147483803):
        control = run(workload, reference.LowerPrecisionStore, seed=seed)
        assert control["correct"] is False
        assert control["checks"]["leaves_differ"][0] >= 6
        assert control["checks"]["loss_gap"][0] > 0
    assert run(workload, seed=2147483801)["correct"] is True


def test_a_library_warning_fails_the_operation():
    import logging

    from torchsnapshot_tpu import SnapshotManager

    class Warns(SnapshotManager):
        def save(self, *a, **k):
            logging.getLogger("torchsnapshot_tpu.staging").warning("fell back")
            return super().save(*a, **k)

    r = run("tiny.toy-save-resume", Warns)
    saves = [o for o in r["operations"] if o["op"] == "save" and o["in_window"]]
    assert r["failed"] == len(saves) > 0 and r["correct"] is False
    assert not any(o["ok"] for o in saves)

    class Notices(SnapshotManager):
        def save(self, *a, **k):
            logging.getLogger("torchsnapshot_tpu.telemetry.history").warning("slower than median")
            return super().save(*a, **k)

    r = run("tiny.toy-save-resume", Notices)
    assert r["failed"] == 0 and r["correct"] is True
    assert any("slower than median" in n for n in r["notes"])


def test_a_save_that_raises_is_failed_and_not_correct():
    from torchsnapshot_tpu import SnapshotManager

    class Raises(SnapshotManager):
        calls = 0

        def save(self, *a, **k):
            Raises.calls += 1
            if Raises.calls == 2:
                raise OSError("disk full")
            return super().save(*a, **k)

    r = run("tiny.toy-save-resume", Raises)
    assert r["failed"] == 1 and r["correct"] is False
