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
import time
import types

import pytest

from torchsnapshot_tpu import knobs, phase_stats
from torchsnapshot_tpu import h2d as h2d_mod
from torchsnapshot_tpu.h2d import HostBufferPool

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
# Nor is there a range of an arena to stamp: these three read the pool's
# ``arena_turn`` counter, which a toy restore records with zeros, and are held
# to the counter of a pool driven by hand, through the real readers.
NEED_A_TURN = ("arena_turn_s", "arena_wait_pct.resume", "arena_h2d_side_pct.resume")
# Nor does a toy read wait for room in an arena: this one reads the same
# counter's ``starved_s`` and ``stranded_bs``, held to a pool driven by hand
# in which a take waits.
NEEDS_A_WAIT = "arena_stranded_pct.resume"
# Nor does the toy Codestral state hold an RNGState: this one reads the
# ``rng_restore`` counter, held to a restore made by hand with one.
NEEDS_AN_RNG_STATE = "rng_restore_s"
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
    if metric["name"] in NEED_A_TURN:
        assert metric["name"] not in lines[1]["metrics"]  # no arena, no turn
        an_arena_reader_reads_the_pools_turns(metric)
        return
    if metric["name"] == NEEDS_A_WAIT:
        assert metric["name"] not in lines[1]["metrics"]  # no arena, no wait
        the_stranded_reader_reads_a_pool_in_which_a_take_waited(metric)
        return
    if metric["name"] == NEEDS_AN_RNG_STATE:
        assert metric["name"] not in lines[1]["metrics"]  # no RNGState, no counter
        the_rng_reader_reads_a_restore_that_held_an_rng_state(metric)
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


def a_pool_that_makes_an_arena():
    """Two statefuls of two leaves of a page behind a window of two pages
    (the arena), the in-flight cap of the pool's one batcher."""
    page = h2d_mod._PAGE
    pool = HostBufferPool()
    pool.batcher.inflight_cap_bytes = 2 * page
    on_a_chip = types.SimpleNamespace(devices=lambda: [types.SimpleNamespace(platform="tpu")])
    for _ in range(2):
        pool.begin_group()
        pool.reserve(page, on_a_chip)
        pool.reserve(page, on_a_chip)
    return pool, pool.batcher


def arena_populate_s_reads_the_pools_phase(metric):
    """A pool that makes an arena (two statefuls of two leaves behind a window
    of two) fires the phase once, at its first take, and the reader divides
    that phase's wall by the window's restores; where it never fired, as in a
    library from before the population, the reader reads nothing."""
    read = importlib.import_module("chipbench.metrics." + metric["name"]).read
    restores = [{"op": "kill_resume", "ok": True}] * 2
    account = types.SimpleNamespace(window_operations=lambda op: restores)
    page = h2d_mod._PAGE
    pool, _batcher = a_pool_that_makes_an_arena()
    before = phase_stats.snapshot()
    assert read({"account": account, "phases": phase_stats.delta(before)}) is None
    pool.take(page)
    pool.take(page)
    phases = phase_stats.delta(before)
    if h2d_mod._page_toucher() is None:  # no native library here: nothing populates
        assert "arena_populate" not in phases and pool.stats()["populated"] == 0
        return
    assert phases["arena_populate"]["n"] == 1 and pool.stats()["populated"] == 2 * page
    assert phases["arena_populate"]["bytes"] == 2 * page
    got = read({"account": account, "phases": phases})
    assert got == phases["arena_populate"]["wall"] / 2 and got > 0


def an_arena_reader_reads_the_pools_turns(metric):
    """A pool that makes an arena and lends two ranges of it, each stamped by
    hand as its holders stamp it: the counter as ``Snapshot.restore`` records
    it (``turn_stats``, once a restore) through the reader ``run.py`` loads;
    with the counter all zeros, as a restore with no arena leaves it, or not
    there, as in a library from before it, the reader reads nothing."""
    from chipbench import job as chipbench_job

    read = chipbench_job.load_module("metrics", metric["name"], "metric").read
    page = h2d_mod._PAGE
    pool, _batcher = a_pool_that_makes_an_arena()

    def recorded(stats):
        before = phase_stats.snapshot()
        phase_stats.add_counter(
            "arena_turn", 0.0, stats["bytes"], **{k: v for k, v in stats.items() if k != "bytes"}
        )
        return {"phases": phase_stats.delta(before), "counters": {}}

    assert read({"phases": {}, "counters": {}}) is None
    assert read(recorded(pool.turn_stats())) is None
    for buf in (pool.take(page), pool.take(page)):
        for stamp in h2d_mod._STAMPS:
            time.sleep(0.001)
            setattr(pool.turn_of(buf), stamp, h2d_mod._now())
        time.sleep(0.001)
        pool.give(buf, recycle=True)
    stats = pool.turn_stats()
    assert stats["ranges"] == 2 and stats["bytes"] == 2 * page and stats["turn_bs"] > 0
    assert stats["turn_bs"] <= stats["arena"] * stats["lent_s"]
    got = read(recorded(stats))
    share = lambda stages: 100.0 * sum(stats[s + "_bs"] for s in stages) / stats["turn_bs"]
    want = {
        "arena_turn_s": stats["turn_bs"] / stats["bytes"],
        "arena_wait_pct.resume": share(("grant", "slot", "parked", "gather")),
        "arena_h2d_side_pct.resume": share(("gather", "dispatch", "land")),
    }[metric["name"]]
    assert got == pytest.approx(want, rel=1e-12) and got > 0
    if metric["unit"] == "%":
        assert got < 100


def the_stranded_reader_reads_a_pool_in_which_a_take_waited(metric):
    """Two ranges of a page out of an arena of three pages, a take of two
    pages waits beside the one free page until a range comes back: the
    counter as ``Snapshot.restore`` records it, through the reader ``run.py``
    loads, gives the free page's share of the arena over the wait; with no
    wait, or no such fields (a library from before them), it reads nothing."""
    import asyncio

    from chipbench import job as chipbench_job

    read = chipbench_job.load_module("metrics", metric["name"], "metric").read
    page = h2d_mod._PAGE
    pool = HostBufferPool()
    pool.batcher.inflight_cap_bytes = 3 * page
    on_a_chip = types.SimpleNamespace(devices=lambda: [types.SimpleNamespace(platform="tpu")])
    for _ in range(2):
        pool.begin_group()
        for pages in (1, 1, 2):
            pool.reserve(pages * page, on_a_chip)

    def recorded(stats):
        before = phase_stats.snapshot()
        phase_stats.add_counter(
            "arena_turn", 0.0, stats["bytes"], **{k: v for k, v in stats.items() if k != "bytes"}
        )
        return {"phases": phase_stats.delta(before), "counters": {}}

    async def pipeline():
        loop = asyncio.get_running_loop()
        a, b = pool.take(page, loop), pool.take(page, loop)
        assert read(recorded(pool.turn_stats())) is None  # nobody has waited yet
        coming = pool.take(2 * page, loop)
        assert isinstance(coming, asyncio.Future)
        time.sleep(0.01)
        pool.give(b, recycle=True)  # b's page and the free one after it: two pages
        c = await asyncio.wait_for(coming, 5)
        for buf in (a, c):
            pool.give(buf, recycle=True)

    asyncio.run(pipeline())
    stats = pool.turn_stats()
    assert stats["starved_s"] > 0 and stats["stranded_bs"] == pytest.approx(page * stats["starved_s"])
    got = read(recorded(stats))
    assert got == pytest.approx(100.0 / 3, rel=1e-9)
    old = {k: v for k, v in stats.items() if k not in ("starved_s", "stranded_bs")}
    assert read(recorded(old)) is None


def the_rng_reader_reads_a_restore_that_held_an_rng_state(metric):
    """A restore on the memory plug-in of a state saved with an RNGState
    records the counter once, through the reader ``run.py`` loads, as its
    ``plan_s`` and ``tail_s``; a restore of a state without one records
    nothing and the reader reads nothing."""
    import jax.numpy as jnp

    from chipbench import job as chipbench_job
    from torchsnapshot_tpu import RNGState, SnapshotManager, StateDict

    read = chipbench_job.load_module("metrics", metric["name"], "metric").read
    restores = [{"op": "kill_resume", "ok": True}]
    manager = SnapshotManager(f"memory://rng_reader_{time.monotonic_ns()}")
    manager.save(1, {"params": StateDict(w=jnp.ones((8,))), "rng": RNGState()})
    manager.save(2, {"params": StateDict(w=jnp.ones((8,)))})

    def restored(step, app_state):
        before = phase_stats.snapshot()
        manager.restore_at(step, app_state)
        return {"account": types.SimpleNamespace(window_operations=lambda op: restores),
                "phases": phase_stats.delta(before), "counters": {}}

    run = restored(1, {"params": StateDict(w=jnp.zeros((8,))), "rng": RNGState()})
    p = run["phases"]["rng_restore"]
    assert p["n"] == 1 and read(run) == p["plan_s"] + p["tail_s"] > 0
    run = restored(2, {"params": StateDict(w=jnp.zeros((8,)))})
    assert "rng_restore" not in run["phases"] and read(run) is None


def test_the_turn_brought_the_benchmark_five_metrics_and_five_readers():
    resume_cells = next(m for m in REAL["end_to_end"] if m["name"] == "resume_s")["workloads"]
    names = [m["name"] for m in REAL["per_layer"]]
    at = names.index("fs_read_gbps") + 1  # appended, behind the newest there was
    assert at == 20
    assert REAL["per_layer"][at:at + 5] == [
        {"name": name, "unit": unit, "better": better, "source": "program_span",
         "layer": layer, "moves": "resume_s", "workloads": resume_cells}
        for name, unit, better, layer in (
            ("arena_turn_s", "s", "lower", "H2D"),
            ("arena_wait_pct.resume", "%", "lower", "Scheduler"),
            ("arena_h2d_side_pct.resume", "%", "lower", "H2D"),
            ("read_h2d_overlap_pct.resume", "%", "higher", "Scheduler"),
            ("h2d_land_slow_s", "s", "lower", "H2D"),
        )
    ]
    for name in names[at:]:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", name + ".py"))


def test_the_trinity_cell_brought_the_benchmark_one_metric_last():
    """The free share of the arena while reads waited, appended behind the
    arena's five and reported by every resume cell, the trinity cell's too,
    which came with it: each stays where it was appended."""
    resume_cells = next(m for m in REAL["end_to_end"] if m["name"] == "resume_s")["workloads"]
    assert resume_cells.index("trinity-large.kill-resume") == 5
    assert REAL["per_layer"][25] == {
        "name": NEEDS_A_WAIT, "unit": "%", "better": "lower", "source": "program_span",
        "layer": "Scheduler", "moves": "resume_s", "workloads": resume_cells,
    }
    assert [m["name"] for m in REAL["per_layer"]].index(NEEDS_A_WAIT) == 25
    assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", NEEDS_A_WAIT + ".py"))


def test_the_sdar_cell_brought_the_benchmark_one_metric_last():
    """The seventh resume cell, reported by every metric that named all six
    but the chunked path's two, and the seconds its RNGState adds to a
    restore, appended last then (the 27th) and read in that cell alone;
    the metrics appended since name it too."""
    cell = "sdar30b.kill-resume"
    resume_cells = next(m for m in REAL["end_to_end"] if m["name"] == "resume_s")["workloads"]
    assert resume_cells.index(cell) == 6 == len(resume_cells) - 1
    assert [w["name"] for w in REAL["workloads"]][-1] == cell
    assert REAL["workloads"][-1]["chips"] == 1 and REAL["configs"][-1]["name"] == "sdar-30b-a3b-chat"
    assert REAL["per_layer"][26] == {
        "name": NEEDS_AN_RNG_STATE, "unit": "s", "better": "lower", "source": "program_span",
        "layer": "Orchestration", "moves": "resume_s", "workloads": [cell],
    }
    listed = [m["name"] for m in REAL["per_layer"][:27] if cell in m.get("workloads", [cell])]
    assert len(listed) == 25 and not {"chunked_bytes_pct.resume", "chunk_assemble_s"} & set(listed)
    assert all(cell in m["workloads"] for m in REAL["per_layer"][27:])
    assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", NEEDS_AN_RNG_STATE + ".py"))


# What the restore's threads spent on the CPU, beside their wall: appended
# behind rng_restore_s, read in every resume cell and on the toy's line.
CPU_ACCOUNT = (
    ("h2d_dispatch_leaf_ms", "ms", "program_span", "H2D"),
    ("h2d_dispatch_wait_pct.resume", "%", "program_span", "H2D"),
    ("loop_cpu_pct.resume", "%", "program_counter", "Scheduler"),
    ("restore_cpu_s_per_gb", "CPU-s/GB", "program_counter", "Orchestration"),
)


def test_the_cpu_account_brought_the_benchmark_four_metrics_last():
    resume_cells = next(m for m in REAL["end_to_end"] if m["name"] == "resume_s")["workloads"]
    assert [m["name"] for m in REAL["per_layer"]].index(NEEDS_AN_RNG_STATE) == 26
    assert REAL["per_layer"][27:] == [
        {"name": name, "unit": unit, "better": "lower", "source": source,
         "layer": layer, "moves": "resume_s", "workloads": resume_cells}
        for name, unit, source, layer in CPU_ACCOUNT
    ]
    for name, *_ in CPU_ACCOUNT:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", name + ".py"))


def test_a_toy_restore_reads_its_cpu_account_in_range(lines):
    """On the toy's traced line each of the four reads a number, and the
    shares lie where a thread's CPU against its wall can: no thread is on
    the CPU longer than it runs (the clock's tick aside: a toy restore's
    calls last milliseconds, so the shares are held only to the range a
    tick-charged clock can read)."""
    metrics = lines[1]["metrics"]
    assert metrics["h2d_dispatch_leaf_ms"]["value"] > 0
    assert metrics["restore_cpu_s_per_gb"]["value"] > 0
    assert metrics["loop_cpu_pct.resume"]["value"] >= 0
    assert metrics["h2d_dispatch_wait_pct.resume"]["value"] <= 100


def test_a_toy_restore_holds_no_stall_and_says_so(lines):
    """``h2d_land_slow_s`` is 0.0, not absent, where landings were made and
    none stalled; the overlap is a share of the shorter stage."""
    metrics = lines[1]["metrics"]
    assert metrics["h2d_land_slow_s"] == {"value": 0.0, "unit": "s"}
    assert 0.0 <= metrics["read_h2d_overlap_pct.resume"]["value"] <= 100.0


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
