"""One run of one cell, from a device list to the result line's fields.

``run.py`` is the command: it looks for the chip and calls ``run_cell``.
The tests and ``control.py`` call ``run_cell`` themselves, with the CPU's
devices or with a manager of their own in the library's place, so that
everything after the look for a chip is the same code.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process was started, interpreter start-up and
    imports included."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def named(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r}")


def resolve(dotted: str) -> Any:
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


def reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def ensure_native_library() -> str:
    """The data plane compiled on this machine from the committed source,
    once per checkout: the stamp holds the source's hash (the library's own
    staleness test trusts a copied tree's timestamps)."""
    from torchsnapshot_tpu._native import build

    native_dir = os.path.dirname(os.path.abspath(build.__file__))
    with open(os.path.join(native_dir, "tpustore.cc"), "rb") as f:
        want = hashlib.sha256(f.read()).hexdigest()
    stamp = os.path.join(HERE, ".cache", "native.sha256")
    lib = os.path.join(native_dir, "libtpusnap.so")
    try:
        with open(stamp) as f:
            have = f.read().strip()
    except OSError:
        have = None
    if have != want or not os.path.exists(lib):
        lib = build.rebuild_native_lib()
        os.makedirs(os.path.dirname(stamp), exist_ok=True)
        with open(stamp, "w") as f:
            f.write(want + "\n")
    return lib


def open_chips(cell: "Cell", rehearsal: bool = False):
    """The devices the cell runs on, with the compile cache placed and the
    native library in place; ``None`` (and a line on standard error) where
    JAX finds no accelerator, fewer chips than the cell asks for, or no
    native library.  The rehearsal takes the CPU and keeps no programs."""
    if not rehearsal:
        from torchsnapshot_tpu.utils.compile_cache import place_compile_cache

        place_compile_cache()
    import jax

    from torchsnapshot_tpu.native_io import NativeFileIO

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if (platform == "cpu" and not rehearsal) or len(devices) < cell.chips:
        problem = (f"{cell.name} needs {cell.chips} accelerator chip(s); JAX found "
                   f"{len(devices)} device(s) of platform {platform!r}")
    else:
        lib = ensure_native_library()
        problem = None if NativeFileIO.maybe_create() else "the native library did not load"
    if problem:
        print(f"chipbench: {problem}", file=sys.stderr)
        return None
    log(f"device {platform}/{kind} x{cell.chips}; native {lib}")
    return devices[: cell.chips]


class Cell:
    """A cell of a benchmark file with its configuration and its mix."""

    def __init__(self, benchmark_path: str, workload: str) -> None:
        self.bench = load_json(benchmark_path)
        self.cell = named(self.bench["workloads"], workload, "cell")
        entry = named(self.bench["configs"], self.cell["config"], "configuration")
        self.cfg = load_json(os.path.join(ROOT, entry["file"]))
        self.mix = load_json(os.path.join(HERE, "traffic", self.cell["traffic"] + ".json"))
        self.name = workload
        self.chips = int(self.cell["chips"])

    def build_load(self, devices) -> Any:
        return resolve(self.cfg["builder"])(self.cfg, devices)


def run_cell(
    cell: Cell,
    devices,
    seed: int,
    seconds: float,
    trace: bool = False,
    *,
    load: Any = None,
    make_manager: Optional[Callable[..., Any]] = None,
    keep_trace: Optional[str] = None,
    setup_clock: Callable[[], float] = process_age_s,
) -> Dict[str, Any]:
    """Warm up, drive the window, compare, reduce.  Returns the fields of
    the contract's line (``checks`` last) and leaves nothing on disk."""
    import jax

    from chipbench import job as jobs, reference, state as st, trace as tr
    from torchsnapshot_tpu import phase_stats

    bench, cfg, mix = cell.bench, cell.cfg, cell.mix
    workdir = tempfile.mkdtemp(prefix="chipbench_")
    trace_dir = os.path.join(workdir, "trace")
    root = os.path.join(workdir, "snapshots")
    os.makedirs(root)
    hook_phases: List[tuple] = []
    tracing = False
    try:
        if load is None:
            load = cell.build_load(devices)
        job = jobs.Job(load, mix, seed, root, make_manager=make_manager)
        job.warm_up()
        sync_mono_ns = None
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing = True
            phase_stats.set_trace_hook(
                lambda phase, begin, end, nbytes: hook_phases.append((phase, begin, end))
            )
            sync_mono_ns = time.monotonic_ns()
            with jax.profiler.TraceAnnotation(tr.SYNC_NAME):
                pass
        phases_before = phase_stats.snapshot()
        setup_s = setup_clock()
        with jax.profiler.TraceAnnotation(tr.WINDOW_NAME):
            account = job.run_window(seconds)
        phases = phase_stats.delta(phases_before)
        if tracing:
            phase_stats.set_trace_hook(None)
            jax.profiler.stop_trace()
            tracing = False
        hbm = st.hbm_stats(devices)
        fullest = st.assert_even_share(job.state, load.devices, "after the window")
        log(
            f"{cell.name} seed {seed}: window {account.window_s:.3f} s, "
            f"{account.attempted()} operations; hbm {hbm}"
        )
        log("phases " + phase_stats.format_line(phases))

        # The comparison: after the window, after the peak was read.
        t_check = time.monotonic()
        compared = job.compared()
        job.free_state()
        correct, numbers, notes = reference.compare(compared)
        failed = account.failed()
        if failed or account.failures:
            correct = False
        check_s = time.monotonic() - t_check

        reduced = None
        if trace:
            xplane = tr.find_xplane(trace_dir)
            if xplane is None:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            if keep_trace:
                os.makedirs(os.path.dirname(os.path.abspath(keep_trace)), exist_ok=True)
                shutil.copy(xplane, keep_trace)
            reduced = tr.reduce_file(
                xplane,
                host_spans=account.spans,
                host_phases=hook_phases,
                sync_mono_ns=sync_mono_ns,
                n_devices=len(load.devices),
            )
            log(
                f"trace {os.path.getsize(xplane)} B: "
                + json.dumps({k: reduced.get(k) for k in
                              ("devices_traced", "busy_s", "window_s", "idle_gap_s", "sync_found")})
            )

        run = {
            "account": account,
            "phases": phases,
            "counters": {
                "state_bytes": job.state_bytes,
                "state_bytes_fullest_device": fullest,
                "hbm_peak_bytes": hbm["peak_bytes_in_use"],
                "hbm_peak_reserved_bytes": hbm["peak_bytes_reserved"],
            },
            "trace": reduced,
            "config": cfg,
            "device_kind": devices[0].device_kind,
        }
        metrics: Dict[str, Dict[str, Any]] = {}
        for m in bench["per_layer" if trace else "end_to_end"]:
            if not reports(m, cell.name):
                continue
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = jobs.load_module("metrics", m["name"], "metric").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device: Dict[str, Any] = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": hbm["peak_bytes_in_use"],
            "memory_peak_reserved_bytes": hbm["peak_bytes_reserved"],
        }
        result: Dict[str, Any] = {
            "correct": correct,
            "attempted": account.attempted(),
            "failed": failed,
            "metrics": metrics,
            "device": device,
        }
        if reduced is not None and reduced["busy_s"]:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
        result["window_s"] = account.window_s
        result["check_s"] = check_s
        result["setup_s"] = setup_s
        result["operations"] = account.operations
        result["notes"] = account.failures + notes + job.watch.notices
        result["checks"] = {k: [v["value"], v["limit"]] for k, v in numbers.items()}
        result["checks"]["failed_operations"] = [failed, 0]
        return result
    finally:
        if tracing:
            phase_stats.set_trace_hook(None)
            jax.profiler.stop_trace()
        shutil.rmtree(workdir, ignore_errors=True)


def print_result(result: Dict[str, Any]) -> None:
    """Notes and each number beside its limit as the last lines on standard
    error; the result's one line last on standard output."""
    for line in result["notes"]:
        log(line)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} = {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
