"""The five per-layer metrics that read the restore path's driver phases
(``restore_open``, ``plan_read``, ``read_starved``, ``h2d_drain``,
``load_state``) and the ``restore_unattributed`` counter: a traced rehearsal
against ``data/BENCHMARK.tiny-spans.json``, the tiny benchmark with the five
entries ``BENCHMARK.json`` has, reports each of them, over 0."""

import json
import os

import pytest

from conftest import ROOT
from test_rehearsal import BENCH, run

SPANS = os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny-spans.json")
SPANS_BENCH = json.load(open(SPANS))
NEW = ("orchestration_s", "plan_read_s", "sched_wait_s.resume", "h2d_tail_s",
       "restore_unattributed_s")


def test_the_tiny_benchmark_has_the_entries_of_the_real_one():
    twins = {m["name"]: m for m in SPANS_BENCH["per_layer"]}
    real = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(NEW) <= set(real)
    for name in real:
        assert {k: v for k, v in twins[name].items() if k != "workloads"} == {
            k: v for k, v in real[name].items() if k != "workloads"}
        assert set(real[name]["workloads"]) <= set(twins[name]["workloads"])


@pytest.mark.parametrize("workload", [w["name"] for w in SPANS_BENCH["workloads"]])
def test_a_traced_rehearsal_reports_all_five(workload):
    p = run(workload, 1, "--benchmark", SPANS, "--rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, (line["checks"], line["notes"])
    for name in NEW:
        assert name in line["metrics"], (name, sorted(line["metrics"]))
        assert line["metrics"][name]["unit"] == "s" and line["metrics"][name]["value"] > 0
    # what was there before still is
    for name in ("restore_call_s", "h2d_s", "read_gbps"):
        assert line["metrics"][name]["value"] > 0
    # the driver thread's parts of a call do not overlap, so they are within it
    # (the scheduler's waits run beside reads and beside each other)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert sum(m[name] for name in NEW if name != "sched_wait_s.resume") < m["restore_call_s"]
