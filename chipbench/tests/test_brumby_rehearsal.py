"""The rehearsal twin of ``brumby14b.kill-resume``: the cell's command end to
end on the CPU against ``data/BENCHMARK.tiny-brumby.json`` (the toy
configuration ``configs/tiny-brumby.json`` through the ``brumby`` builder, the
real mix, every metric of ``BENCHMARK.json``) with the library's chunk size
forced to the toy's, so that the toy's nine stacked feed-forward leaves are
chunked 3 + 1 rows as the real ones are; and the comparison that decides
``correct`` turning false under ``faults.py``'s breaks and under the control."""

import json
import os

import jax
import pytest

import faults
from chipbench import harness, reference
from conftest import ROOT
from test_rehearsal import BENCH, run
from torchsnapshot_tpu import knobs

TWIN = os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny-brumby.json")
TWIN_BENCH = json.load(open(TWIN))
CELL = "brumby14b.kill-resume"
NEW = ("chunked_bytes_pct.resume", "chunk_assemble_s")
TOY = json.load(open(os.path.join(ROOT, "chipbench", "configs", "tiny-brumby.json")))
CHUNK = TOY["assumed"]["rehearsal_chunk_size_bytes"]


def test_the_twin_has_the_cell_and_every_metric_of_the_real_benchmark():
    real = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    twin = next(w for w in TWIN_BENCH["workloads"] if w["name"] == CELL)
    assert twin["traffic"] == real["traffic"] and twin["chips"] == real["chips"] == 1
    real_cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", real["config"] + ".json")))
    assert TOY["builder"] == real_cfg["builder"]
    assert TOY["state_dtypes"] == real_cfg["state_dtypes"]
    for key in ("num_hidden_layers", "num_attention_heads", "num_key_value_heads"):
        assert TOY[key] == real_cfg[key] and TOY["published"][key] == real_cfg["published"][key]
    for group in ("end_to_end", "per_layer"):
        twins = {m["name"]: m for m in TWIN_BENCH[group]}
        for m in BENCH[group]:
            assert {k: v for k, v in twins[m["name"]].items() if k != "workloads"} == {
                k: v for k, v in m.items() if k != "workloads"}
            if CELL in m.get("workloads", [CELL]):
                assert CELL in twins[m["name"]].get("workloads", [CELL])
    for name in NEW:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["moves"] == "resume_s" and m["workloads"] == [CELL]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_end_to_end(trace):
    p = run(CELL, trace, "--benchmark", TWIN, "--rehearsal", env={knobs.MAX_CHUNK_SIZE_ENV_VAR: str(CHUNK)})
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
    # nine leaves of 131,072 B of a state of 3 x 524,160 B of parameters and moments + 8
    state = 3 * 2 * (2 * 128 * 64 + 4 * (2 * 64 * 80 + 2 * 64 * 16 + 64 + 32 + 3 * 64 * 256 + 128) + 64) + 8
    assert line["metrics"]["chunked_bytes_pct.resume"]["value"] == pytest.approx(
        100.0 * 9 * 131072 / state, abs=0.01)
    assert line["metrics"]["chunked_bytes_pct.resume"]["unit"] == "%"
    assert line["metrics"]["chunk_assemble_s"]["unit"] == "s"
    assert not [n for n in line["metrics"] if "idle" in n or "hbm" in n]  # no device number on the CPU


def test_without_the_forced_chunk_size_the_toy_has_nothing_chunked():
    """The readers on a restore with no chunked leaf: the share reads 0 (the
    counter counts plans) and the phase's reader finds nothing."""
    p = run(CELL, 1, "--benchmark", TWIN, "--rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["chunked_bytes_pct.resume"]["value"] == 0.0
    assert "chunk_assemble_s" not in line["metrics"]


def in_process(make_manager=None, seed=2147483777):
    cell = harness.Cell(TWIN, CELL)
    with knobs.override_max_chunk_size_bytes(CHUNK):
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
    """One precision down (float8 for the bfloat16 leaves) fails by the
    leaves and by the loss, on every seed."""
    for seed in (2147483801, 2147483802, 2147483803):
        control = in_process(reference.LowerPrecisionStore, seed=seed)
        assert control["correct"] is False
        assert control["checks"]["leaves_differ"][0] >= 20
        assert control["checks"]["loss_gap"][0] > 0
    assert in_process(seed=2147483801)["correct"] is True
