"""The five per-layer metrics that read a range's turn through the restore's
host arena (the ``arena_turn`` counter), the split of the call by which of
reads and H2D were under way (``restore_overlap``) and the landings that
stalled (``h2d_land_slow``): a traced rehearsal against
``data/BENCHMARK.tiny-turn.json``, the tiny benchmark with every per-layer
entry the real one lists for its first cell, these five last.  No toy leaf
reaches the pool's megabyte and the CPU backend gets no arena, so the three
``arena_*`` readers find a counter of zeros and are left out (their arithmetic
is held in tier-1, ``tests/test_arena_turn.py``, on a pool driven by hand);
the other two are reported by every ``kill-resume`` twin."""

import json
import os

import pytest

from conftest import ROOT
from test_rehearsal import BENCH, run

TURN = os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny-turn.json")
TURN_BENCH = json.load(open(TURN))
NEW = ("arena_turn_s", "arena_wait_pct.resume", "arena_h2d_side_pct.resume",
       "read_h2d_overlap_pct.resume", "h2d_land_slow_s")
NEED_AN_ARENA = NEW[:3]
RESUME_TWINS = [w["name"] for w in TURN_BENCH["workloads"] if w["traffic"] == "kill-resume"]


def test_the_tiny_benchmark_has_the_entries_of_the_real_ones_first_cell():
    twins = {m["name"]: m for m in TURN_BENCH["per_layer"]}
    real = {m["name"]: m for m in BENCH["per_layer"] if RESUME_TWINS[0] in m["workloads"]}
    assert set(NEW) <= set(real) and list(twins)[-5:] == list(NEW)
    assert list(twins) == list(real)
    for name in real:
        assert {k: v for k, v in twins[name].items() if k != "workloads"} == {
            k: v for k, v in real[name].items() if k != "workloads"}
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", name + ".py"))


@pytest.mark.parametrize("workload", RESUME_TWINS)
def test_a_traced_rehearsal_reports_the_overlap_and_no_stall(workload):
    p = run(workload, 1, "--benchmark", TURN, "--rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, (line["checks"], line["notes"])
    metrics = line["metrics"]
    assert 0.0 <= metrics["read_h2d_overlap_pct.resume"]["value"] <= 100.0
    assert metrics["read_h2d_overlap_pct.resume"]["unit"] == "%"
    assert metrics["h2d_land_slow_s"] == {"value": 0.0, "unit": "s"}
    for name in NEED_AN_ARENA:
        assert name not in metrics, (name, metrics[name])
    # what was there before still is
    for name in ("restore_call_s", "h2d_s", "read_gbps", "sched_wait_s.resume",
                 "restore_unattributed_s"):
        assert metrics[name]["value"] > 0
