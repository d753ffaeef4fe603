"""The surface the driver runs must work in one shot: ``chipbench/run.py``
and the readers under ``chipbench/metrics/``, which find the library's work
by ``phase_stats`` names.  A library change that renames or stops firing a
phase, or a benchmark change that lists a metric whose reader finds nothing,
would otherwise meet the driver as a ``null`` under ``per_layer`` on the chip.

One rehearsal of a toy cell on the CPU, untraced and traced, under a
benchmark file made of the toy ``configs`` and ``workloads`` of
``chipbench/tests/data/BENCHMARK.tiny.json`` and the ``per_layer`` list of the
real ``BENCHMARK.json``; and the traced one once more with the library's chunk
size forced under the toy's leaves, for the metrics that read the chunked
path.  Nothing a rehearsal prints is a device number."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from torchsnapshot_tpu import knobs, phase_stats
from torchsnapshot_tpu.io_preparers import array as array_mod
from torchsnapshot_tpu.io_preparers.array import HostBufferPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    REAL = json.load(f)
CELL = "codestral22b.kill-resume"
# No toy leaf reaches the pool's megabyte, so a rehearsal's ``host_pool``
# counter is empty and this one reader reads nothing from it.
NEEDS_A_MEGABYTE_LEAF = "host_reuse_pct.resume"
# Nor does a toy restore make an arena, so nothing is populated: this reader is
# held to the phase that a pool which does make one fires.
NEEDS_AN_ARENA = "arena_populate_s"
# No toy leaf reaches the chunk size of 512 MiB: these two are read from the
# third rehearsal, where it is 100,000 B and the toy's 131,072 B leaves chunk.
NEED_A_CHUNKED_LEAF = ("chunked_bytes_pct.resume", "chunk_assemble_s")
TOY_CHUNK_BYTES = 100_000


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    """The result lines of the three rehearsals, ``[untraced, traced, traced
    with small chunks]``."""
    tmp = tmp_path_factory.mktemp("benchmark_surface")
    with open(os.path.join(ROOT, "chipbench", "tests", "data", "BENCHMARK.tiny.json")) as f:
        tiny = json.load(f)
    toy_cells = [w["name"] for w in tiny["workloads"] if w["traffic"] == "kill-resume"]
    assert CELL in toy_cells
    bench = dict(
        REAL,
        configs=tiny["configs"],
        workloads=tiny["workloads"],
        per_layer=[dict(m, workloads=toy_cells) for m in REAL["per_layer"]],
    )
    bench_file = tmp / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    # The child's environment as chipbench/tests/test_rehearsal.py makes it,
    # less the eight virtual devices of tests/conftest.py: a cell asks for one.
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored", TMPDIR=str(tmp))
    env["XLA_FLAGS"] = " ".join(
        flag
        for flag in env.get("XLA_FLAGS", "").split()
        if not flag.startswith("--xla_force_host_platform_device_count")
    )
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(ROOT, REAL["command"][1]),
             "--benchmark", str(bench_file), "--workload", CELL, "--seed", "3000000019",
             "--seconds", "1.5", "--rehearsal", "--trace", str(trace)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=ROOT,
            env=dict(env, **more),
        )
        for trace, more in (
            (0, {}), (1, {}), (1, {knobs.MAX_CHUNK_SIZE_ENV_VAR: str(TOY_CHUNK_BYTES)})
        )
    ]
    out = []
    try:
        for proc in procs:
            stdout, stderr = proc.communicate(timeout=600)
            assert proc.returncode == 0, stderr[-3000:]
            out.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return out


def test_the_untraced_line_is_the_contracts(lines):
    line = lines[0]
    assert line["rehearsal"] is True
    assert line["correct"] is True, (line["checks"], line["notes"])
    assert line["attempted"] > 0 and line["failed"] == 0
    for m in REAL["end_to_end"]:
        got = line["metrics"][m["name"]]
        assert got["value"] > 0 and got["unit"] == m["unit"]


@pytest.mark.parametrize("metric", REAL["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_finds_what_it_reads(metric, lines):
    if metric["layer"] == "Device":
        pytest.skip("read from the device: a CPU rehearsal has nothing to show it")
    if metric["name"] == NEEDS_A_MEGABYTE_LEAF:
        # what its reader takes from the counter (chipbench/metrics/<name>.py)
        assert {"bytes", "fresh"} <= set(HostBufferPool().stats())
        return
    if metric["name"] == NEEDS_AN_ARENA:
        assert metric["name"] not in lines[1]["metrics"]  # no arena, nothing read
        arena_populate_s_reads_the_pools_phase(metric)
        return
    line = lines[2 if metric["name"] in NEED_A_CHUNKED_LEAF else 1]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"].get(metric["name"])
    assert got is not None, (
        f"{metric['name']}: its reader found nothing in a traced restore "
        f"(a phase renamed or no longer fired?); reported: {sorted(line['metrics'])}"
    )
    assert isinstance(got["value"], (int, float)) and got["unit"] == metric["unit"]
    if metric["name"] in NEED_A_CHUNKED_LEAF:
        assert got["value"] > 0
        assert metric["name"] not in lines[1]["metrics"] or lines[1]["metrics"][metric["name"]]["value"] == 0


def arena_populate_s_reads_the_pools_phase(metric):
    """A pool that makes an arena (two statefuls of two leaves behind a window
    of two) fires the phase once, at its first take, and the reader divides
    that phase's wall by the window's restores; where it never fired, as in a
    library from before the population, the reader reads nothing."""
    read = importlib.import_module("chipbench.metrics." + metric["name"]).read
    restores = [{"op": "kill_resume", "ok": True}] * 2
    account = types.SimpleNamespace(window_operations=lambda op: restores)
    page = array_mod._PAGE
    class Batcher:  # what the pool knows of an H2DBatcher
        inflight_cap_bytes = 2 * page

    pool, batcher = HostBufferPool(), Batcher()
    pool.attach(batcher)
    on_a_chip = types.SimpleNamespace(devices=lambda: [types.SimpleNamespace(platform="tpu")])
    for _ in range(2):
        pool.begin_group()
        pool.reserve(page, on_a_chip)
        pool.reserve(page, on_a_chip)
    before = phase_stats.snapshot()
    assert read({"account": account, "phases": phase_stats.delta(before)}) is None
    pool.take(page)
    pool.take(page)
    phases = phase_stats.delta(before)
    if array_mod._page_toucher() is None:  # no native library here: nothing populates
        assert "arena_populate" not in phases and pool.stats()["populated"] == 0
        return
    assert phases["arena_populate"]["n"] == 1 and pool.stats()["populated"] == 2 * page
    assert phases["arena_populate"]["bytes"] == 2 * page
    got = read({"account": account, "phases": phases})
    assert got == phases["arena_populate"]["wall"] / 2 and got > 0


def test_the_population_brought_the_benchmark_one_metric_and_one_reader():
    (entry,) = [m for m in REAL["per_layer"] if m["name"] == NEEDS_AN_ARENA]
    resume_cells = next(m for m in REAL["end_to_end"] if m["name"] == "resume_s")["workloads"]
    assert entry == {
        "name": "arena_populate_s", "unit": "s", "better": "lower", "source": "program_span",
        "layer": "H2D", "moves": "resume_s", "workloads": resume_cells,
    }
    # appended, behind the newest metric the benchmark had
    names = [m["name"] for m in REAL["per_layer"]]
    assert names.index(NEEDS_AN_ARENA) == names.index("chunk_assemble_s") + 1 == 17
    assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", NEEDS_AN_ARENA + ".py"))
