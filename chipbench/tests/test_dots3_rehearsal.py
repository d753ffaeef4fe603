"""The rehearsal twin of ``dots3.kill-resume``: the cell's command end to end
on the CPU against ``data/BENCHMARK.tiny-moe.json`` (the toy configuration
``configs/tiny-moe.json`` through the ``dots3_note`` builder, the real mix,
every metric of ``BENCHMARK.json``), and the comparison that decides
``correct`` turning false under ``faults.py``'s breaks and under the control."""

import json
import os

import jax
import pytest

import faults
from chipbench import harness, reference
from conftest import ROOT
from test_rehearsal import BENCH, run

TWIN = os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny-moe.json")
TWIN_BENCH = json.load(open(TWIN))
CELL = "dots3.kill-resume"
NEW = ("slab_bytes_pct.resume", "slab_scatter_s", "consume_s")


def test_the_twin_has_the_cell_and_every_metric_of_the_real_benchmark():
    real = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    twin = next(w for w in TWIN_BENCH["workloads"] if w["name"] == CELL)
    assert twin["traffic"] == real["traffic"] and twin["chips"] == real["chips"] == 1
    real_cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", real["config"] + ".json")))
    twin_cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", twin["config"] + ".json")))
    assert twin_cfg["builder"] == real_cfg["builder"]
    assert twin_cfg["state_dtypes"] == real_cfg["state_dtypes"]
    for group in ("end_to_end", "per_layer"):
        twins = {m["name"]: m for m in TWIN_BENCH[group]}
        for m in BENCH[group]:
            assert {k: v for k, v in twins[m["name"]].items() if k != "workloads"} == {
                k: v for k, v in m.items() if k != "workloads"}
            if CELL in m.get("workloads", [CELL]):
                assert CELL in twins[m["name"]].get("workloads", [CELL])
    for name in NEW:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["moves"] == "resume_s" and set(m["workloads"]) == {w["name"] for w in BENCH["workloads"]}


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
    for name in NEW + ("restore_call_s", "h2d_s", "read_gbps", "plan_read_s", "restore_unattributed_s"):
        assert line["metrics"][name]["value"] > 0, name
    # every toy leaf is under the slab threshold: the slabs carry the whole state
    assert line["metrics"]["slab_bytes_pct.resume"]["value"] == pytest.approx(100.0, abs=0.01)
    assert line["metrics"]["slab_bytes_pct.resume"]["unit"] == "%"
    assert not [n for n in line["metrics"] if "idle" in n or "hbm" in n]  # no device number on the CPU


def in_process(make_manager=None, seed=2147483777):
    cell = harness.Cell(TWIN, CELL)
    return harness.run_cell(cell, jax.devices()[:1], seed, 1.0, make_manager=make_manager,
                            setup_clock=lambda: 1.0)


@pytest.mark.parametrize(
    "fault,number",
    [
        (faults.FlipOneBit, "leaves_differ"),
        (faults.RestoreNothing, "leaves_differ"),
        (faults.RestoreNothing, "step_gap"),
        (faults.LosesAStateful, "leaves_differ"),
    ],
)
def test_a_broken_path_is_not_correct(fault, number):
    r = in_process(fault)
    assert r["correct"] is False
    got, limit = r["checks"][number]
    assert got > limit, r["checks"]


def test_the_control_is_not_correct_and_the_program_is():
    """One precision down (float8 for the bfloat16 leaves, bfloat16 for the
    float32 biases) fails by the leaves and by the loss, on every seed."""
    for seed in (2147483801, 2147483802, 2147483803):
        control = in_process(reference.LowerPrecisionStore, seed=seed)
        assert control["correct"] is False
        assert control["checks"]["leaves_differ"][0] >= 100
        assert control["checks"]["loss_gap"][0] > 0
    assert in_process(seed=2147483801)["correct"] is True
