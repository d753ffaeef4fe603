"""The rehearsal twin of ``sdar30b.kill-resume``: the cell's command end to
end on the CPU against ``data/BENCHMARK.tiny-sdar.json`` (the toy
configuration ``configs/tiny-sdar.json`` through the ``sdar_moe`` builder,
the real mix, every metric the real benchmark lists for the cell), traced and
untraced; and the comparison that decides ``correct`` turning false where the
restore leaves the ``RNGState`` out or hands its key back zeroed, under
``faults.py``'s breaks, and under the control, one precision down."""

import json
import os

import jax
import numpy as np
import pytest

import faults
from chipbench import control_rng, harness
from conftest import ROOT
from test_rehearsal import BENCH, run
from torchsnapshot_tpu import RNGState, SnapshotManager

TWIN = os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny-sdar.json")
TWIN_BENCH = json.load(open(TWIN))
CELL = "sdar30b.kill-resume"
TOY = json.load(open(os.path.join(ROOT, "chipbench", "configs", "tiny-sdar.json")))


class LeavesTheRNGStateOut(SnapshotManager):
    """A restore of every stateful but the ``RNGState``: the key stays the
    target's zeros."""

    def restore_latest(self, app_state):
        return super().restore_latest(
            {k: v for k, v in app_state.items() if not isinstance(v, RNGState)})


class ZeroesTheKey(SnapshotManager):
    """A restore that hands the ``RNGState`` back with its key zeroed."""

    def restore_latest(self, app_state):
        step = super().restore_latest(app_state)
        for key, stateful in app_state.items():
            if isinstance(stateful, RNGState):
                app_state[key] = RNGState(jax_key=jax.random.wrap_key_data(np.zeros(2, np.uint32)))
        return step


def test_the_twin_has_the_cell_and_every_metric_the_real_benchmark_lists_for_it():
    real = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    twin = next(w for w in TWIN_BENCH["workloads"] if w["name"] == CELL)
    assert twin["traffic"] == real["traffic"] and twin["chips"] == real["chips"] == 1
    real_cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", real["config"] + ".json")))
    assert TOY["builder"] == real_cfg["builder"] and TOY["state_dtypes"] == real_cfg["state_dtypes"]
    assert set(TOY) == set(real_cfg)
    for group in ("end_to_end", "per_layer"):
        twins = {m["name"]: m for m in TWIN_BENCH[group]}
        listed = [m for m in BENCH[group] if CELL in m.get("workloads", [CELL])]
        assert list(twins) == [m["name"] for m in listed]
        for m in listed:
            assert {k: v for k, v in twins[m["name"]].items() if k != "workloads"} == {
                k: v for k, v in m.items() if k != "workloads"}
    assert len(TWIN_BENCH["per_layer"]) == 25 and TWIN_BENCH["per_layer"][-1]["name"] == "rng_restore_s"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(trace):
    p = run(CELL, trace, "--benchmark", TWIN, "--rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and list(line)[-1] == "checks"
    assert line["correct"] is True, (line["checks"], line["notes"])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"] == {"leaves_differ": [0, 0], "loss_gap": [0.0, 0.0], "step_gap": [0, 0],
                              "failed_operations": [0, 0]}
    if not trace:
        assert set(line["metrics"]) == {"resume_s", "setup_s"}
        return
    for name in ("restore_call_s", "h2d_s", "plan_read_s", "restore_unattributed_s", "rng_restore_s"):
        assert line["metrics"][name]["value"] > 0, name
    assert line["metrics"]["rng_restore_s"]["value"] < line["metrics"]["restore_call_s"]["value"]
    assert not [n for n in line["metrics"] if "idle" in n or "hbm" in n]  # no device number on the CPU


def in_process(make_manager=None, seed=4100000777):
    cell = harness.Cell(TWIN, CELL)
    return harness.run_cell(cell, jax.devices()[:1], seed, 1.0, make_manager=make_manager,
                            setup_clock=lambda: 1.0)


@pytest.mark.parametrize(
    "fault,number",
    [(LeavesTheRNGStateOut, "leaves_differ"), (ZeroesTheKey, "leaves_differ"),
     (faults.FlipOneBit, "leaves_differ"), (faults.RestoreNothing, "step_gap")],
)
def test_a_broken_path_is_not_correct(fault, number):
    r = in_process(fault)
    assert r["correct"] is False
    got, limit = r["checks"][number]
    assert got > limit, r["checks"]
    if fault in (LeavesTheRNGStateOut, ZeroesTheKey):
        # the one leaf is the key, and the step drew another mask
        assert got == 1 and r["checks"]["loss_gap"][0] > 0, r["checks"]


def test_the_control_is_not_correct_and_the_program_is():
    """One precision down (float8 for the bfloat16 leaves), the RNGState
    passed through as it is, fails by the leaves and by the loss, on every
    seed; the key and the two counters survive."""
    for seed in (4100000801, 4100000802):
        control = in_process(control_rng.RNGPassingStore, seed=seed)
        assert control["correct"] is False
        assert control["checks"]["leaves_differ"][0] >= 100
        assert control["checks"]["loss_gap"][0] > 0
    assert in_process(seed=4100000801)["correct"] is True
