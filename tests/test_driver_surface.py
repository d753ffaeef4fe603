"""Smoke tests for the driver-facing surface: bench.py and the benchmark
drivers must run end-to-end in one shot — a syntax or API drift there means
no recorded number for the whole round, so the suite guards them."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, env_extra, timeout=240):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run(
        cmd,
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_bench_py_produces_json_line():
    proc = _run(
        [sys.executable, "bench.py"],
        {
            "BENCH_TARGET_BYTES": str(16 << 20),
            "BENCH_SAVE_ATTEMPTS": "1",
            "BENCH_MAX_S": "200",
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    assert result["metric"] == "checkpoint_save_throughput_per_chip"
    assert result["value"] > 0
    assert result["unit"] == "GB/s"
    assert "vs_baseline" in result
    # Every result names the device it ran on; here that is the CPU, because
    # _run asked for it.
    assert result["platform"] == "cpu"
    assert result["device_kind"] and result["device_count"] >= 1
    aux = result["aux"]
    for key in (
        "save_phases",
        "restore_phases",
        "async_stall_s",
        "raw_d2h_link_gbps",
        "save_phase_cpu_sum_s",
    ):
        assert key in aux, key


def test_bench_py_refuses_cpu_it_was_not_asked_for():
    """No accelerator is an error, not a quiet CPU run: with JAX_PLATFORMS
    naming anything but exactly ``cpu`` and only the CPU to be found, bench.py
    must exit non-zero and print no result."""
    proc = _run(
        [sys.executable, "bench.py"],
        {"JAX_PLATFORMS": "", "BENCH_TARGET_BYTES": str(16 << 20)},
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert proc.stdout.strip() == ""


def test_huge_bench_tiny_run():
    proc = _run(
        [
            sys.executable,
            "benchmarks/huge/main.py",
            "--gib",
            "0.02",
            "--budget-gib",
            "0.01",
        ],
        {},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["bench"] == "huge"
    assert result.get("skipped") or result["rss_within_budget"] is True


def test_coordination_small_collective_tiny_run():
    proc = _run(
        [
            sys.executable,
            "benchmarks/coordination/main.py",
            "--worlds",
            "",
            "--small-worlds",
            "16",
        ],
        {},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout.strip().splitlines()[-1]
    assert "reduce_bcast_s" in out and "op_ratio" in out
