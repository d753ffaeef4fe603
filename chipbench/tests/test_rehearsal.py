"""Every cell's command, end to end, on the CPU with the toy configurations:
the contract line's keys, the refusal without a chip, and that a new
configuration, a new mix and a new per-layer metric are found as files."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

TINY = os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny.json")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TINY_BENCH = json.load(open(TINY))
CELLS = [w["name"] for w in TINY_BENCH["workloads"]]


def run(workload, trace, *extra, env=None):
    cmd = [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", workload,
           "--seed", "3000000019", "--seconds", "1.5", "--trace", str(trace), *extra]
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    full_env.update(env or {})
    return subprocess.run(cmd, capture_output=True, text=True, timeout=300, cwd=ROOT, env=full_env)


def test_every_real_cell_has_a_rehearsal_twin():
    assert {w["name"] for w in BENCH["workloads"]} <= set(CELLS)
    for real in BENCH["workloads"]:
        twin = next(w for w in TINY_BENCH["workloads"] if w["name"] == real["name"])
        assert twin["traffic"] == real["traffic"]
    for group in ("end_to_end", "per_layer"):
        twins = {m["name"]: m for m in TINY_BENCH[group]}
        for m in BENCH[group]:
            twin = twins[m["name"]]
            assert {k: v for k, v in twin.items() if k != "workloads"} == {
                k: v for k, v in m.items() if k != "workloads"}
            assert set(m.get("workloads", [])) <= set(twin.get("workloads", []))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_end_to_end(workload, trace):
    p = run(workload, trace, "--benchmark", TINY, "--rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, (line["checks"], line["notes"])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for name, (value, limit) in line["checks"].items():
        assert f"check {name} = {value} limit {limit}" in p.stderr
    group = "per_layer" if trace else "end_to_end"
    listed = {
        m["name"]: m for m in TINY_BENCH[group] if "workloads" not in m or workload in m["workloads"]
    }
    assert set(line["metrics"]) <= set(listed)
    for name, m in line["metrics"].items():
        assert m["unit"] == listed[name]["unit"]
        assert m["value"] > 0
    if not trace:
        # every end-to-end metric the cell is listed under is reported
        assert set(line["metrics"]) == set(listed)
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    else:
        assert line["metrics"], "a traced run reports at least one per-layer metric"
        # nothing read from a device on the CPU
        assert not [n for n in line["metrics"] if "idle" in n or "mfu" in n or "hbm" in n]


def test_no_chip_no_result():
    p = run(CELLS[0], 0, "--benchmark", TINY)  # JAX_PLATFORMS=cpu but no --rehearsal
    assert p.returncode != 0 and p.stdout.strip() == ""
    p = run(CELLS[0], 0, "--benchmark", TINY, "--rehearsal", env={"JAX_PLATFORMS": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_cell_is_refused():
    p = run("no.such-cell", 0, "--benchmark", TINY, "--rehearsal")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_found_by_name_not_by_code():
    """The harness names no cell, configuration, mix, operation or metric
    (but ``setup_s``, its own reading); each is a file of its own."""
    bench_dir = os.path.join(ROOT, "chipbench")
    mixes = {w["traffic"] for w in BENCH["workloads"] + TINY_BENCH["workloads"]}
    ops = set()
    for mix in mixes:
        data = json.load(open(os.path.join(bench_dir, "traffic", mix + ".json")))
        ops |= {op["op"] for op in data.get("setup", []) + data["cycle"]}
    metrics = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]} - {"setup_s"}
    for name in ("run.py", "harness.py", "job.py"):
        text = open(os.path.join(bench_dir, name)).read()
        for entry in BENCH["workloads"] + BENCH["configs"]:
            assert entry["name"] not in text, (name, entry["name"])
        for word in mixes | ops | metrics:
            assert '"' + word + '"' not in text, (name, word)
    for m in metrics:
        assert os.path.exists(os.path.join(bench_dir, "metrics", m + ".py")), m
    for op in ops:
        assert os.path.exists(os.path.join(bench_dir, "ops", op + ".py")), op
    # and nothing is there that no cell, mix or test reads
    have = lambda d, ext: {n[: -len(ext)] for n in os.listdir(os.path.join(bench_dir, d))
                           if n.endswith(ext) and not n.startswith("_")}
    assert have("metrics", ".py") == metrics
    assert have("ops", ".py") == ops
    assert have("traffic", ".json") == mixes


def test_an_operation_the_generator_has_not_is_refused(tmp_path):
    bench = dict(TINY_BENCH, workloads=[dict(TINY_BENCH["workloads"][0], traffic="../tests/data/x")])
    os.makedirs(os.path.join(ROOT, "chipbench", "tests", "data"), exist_ok=True)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    mix = os.path.join(ROOT, "chipbench", "tests", "data", "x.json")
    with open(mix, "w") as f:
        json.dump({"cycle": [{"op": "no_such_op"}]}, f)
    try:
        p = run(CELLS[0], 0, "--benchmark", str(path), "--rehearsal")
    finally:
        os.remove(mix)
    assert p.returncode != 0 and p.stdout.strip() == "" and "no_such_op" in p.stderr


def test_imports_nothing_of_the_old_benchmarks():
    import re

    for dirpath, _, names in os.walk(os.path.join(ROOT, "chipbench")):
        if "tests" in dirpath:
            continue
        for n in names:
            if n.endswith(".py"):
                text = open(os.path.join(dirpath, n)).read()
                assert not re.search(r"^\s*(import|from)\s+(chip_smoke|bench|benchmarks|tools)\b",
                                     text, re.M), n
