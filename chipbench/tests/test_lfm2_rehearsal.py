"""The rehearsal twin of ``lfm2-8b-a1b.kill-resume``: the cell's command end
to end on the CPU against ``data/BENCHMARK.tiny-lfm2.json`` (the toy
configuration ``configs/tiny-lfm2.json`` through the ``lfm2_moe`` builder, the
real mix, every metric the real benchmark lists for the cell); and the
comparison that decides ``correct`` turning false under ``faults.py``'s
breaks and under the control, kept on the device as ``control.py`` keeps it
and on the host as ``control_host.py`` does at the cell's size."""

import json
import os
import sys

import jax
import pytest

import faults
from chipbench import control_host, harness, reference
from conftest import ROOT
from test_rehearsal import BENCH, run

TWIN = os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny-lfm2.json")
TWIN_BENCH = json.load(open(TWIN))
CELL = "lfm2-8b-a1b.kill-resume"
NEW = ("sequential_read_pct.resume", "fs_read_gbps")
TOY = json.load(open(os.path.join(ROOT, "chipbench", "configs", "tiny-lfm2.json")))


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
    assert len(TWIN_BENCH["per_layer"]) == 18


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
    for name in ("restore_call_s", "h2d_s", "plan_read_s", "restore_unattributed_s", "slab_bytes_pct.resume"):
        assert line["metrics"][name]["value"] > 0, name
    # no toy leaf reaches a megabyte: every one is a merged slab member, none goes the sequential route
    assert line["metrics"]["slab_bytes_pct.resume"]["value"] == pytest.approx(100.0, abs=0.01)
    assert line["metrics"]["sequential_read_pct.resume"] == {"value": 0.0, "unit": "%"}
    if "fs_read_gbps" in line["metrics"]:
        assert line["metrics"]["fs_read_gbps"]["unit"] == "GB/s" and line["metrics"]["fs_read_gbps"]["value"] > 0
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


@pytest.mark.parametrize("store", [reference.LowerPrecisionStore, control_host.HostKeptLowerPrecisionStore],
                         ids=["kept_on_the_device", "kept_on_the_host"])
def test_the_control_is_not_correct_and_the_program_is(store):
    """One precision down (float8 for the bfloat16 leaves, bfloat16 for the
    float32 biases) fails by the leaves and by the loss, on every seed."""
    for seed in (2147483801, 2147483802, 2147483803):
        control = in_process(store, seed=seed)
        assert control["correct"] is False
        assert control["checks"]["leaves_differ"][0] >= 100
        assert control["checks"]["loss_gap"][0] > 0
    assert in_process(seed=2147483801)["correct"] is True


def test_the_two_controls_hand_back_the_same_bits():
    """Where the copy is kept changes nothing of what comes back."""
    a = in_process(reference.LowerPrecisionStore, seed=2147483811)
    b = in_process(control_host.HostKeptLowerPrecisionStore, seed=2147483811)
    assert a["checks"] == b["checks"]


def test_control_host_runs_controls_loop_with_the_copy_on_the_host(monkeypatch, capsys):
    """The command itself, as it is run on the chip: every control line not
    correct, by the leaves and the loss and by no failed operation, and the
    program's line correct; the other store is back in place afterwards."""
    monkeypatch.setattr(sys, "argv", ["control_host.py", "--workload", CELL, "--seeds", "2147483821",
                                      "--seconds", "1", "--program", "1", "--benchmark", TWIN])
    assert control_host.main() == 0
    assert reference.LowerPrecisionStore is control_host.OnTheDevice
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert [line["side"] for line in lines] == ["control", "program"]
    control, program = lines
    assert control["correct"] is False and control["checks"]["failed_operations"] == [0, 0]
    assert control["checks"]["leaves_differ"][0] >= 100 and control["checks"]["loss_gap"][0] > 0
    assert program["correct"] is True
