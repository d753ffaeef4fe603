"""Checkpoint benchmark: save throughput of a Llama-style model from TPU HBM.

Mirrors the reference's headline DDP benchmark
(/root/reference/benchmarks/ddp/main.py + benchmarks/ddp/README.md): wall-time
to persist a model resident on the accelerator to local storage.  Reference
baseline (BASELINE.md): 20 GB on 1 GPU to local FS in ~13.91 s = 1.438 GB/s
per chip; torch.save managed 0.625 GB/s.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N}
plus auxiliary metrics (async stall time, restore throughput) on stderr.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

# Reference: torchsnapshot 1 node x 1 GPU, 20 GB to local FS (~13.91 s)
BASELINE_GBPS = 20.0 / 13.91


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# Filled by _init_devices: every result names the device it ran on.
_DEVICE = {"platform": None, "device_kind": None, "device_count": None}


def _watchdog_remaining_s() -> float:
    budget_s = int(os.environ.get("BENCH_MAX_S", 540))
    armed_at = _PARTIAL.get("alarm_armed_at")
    if armed_at is None:
        return float(budget_s)
    return budget_s - (time.monotonic() - armed_at)


def _init_devices():
    """``jax.devices()``, in this process only: a parent that has touched
    JAX holds the chip, so there is no probing child and no re-exec.  Finding
    no accelerator is an error, not a CPU run, unless the caller asked for
    the CPU with ``JAX_PLATFORMS=cpu`` (tier-1 does)."""
    import jax

    from torchsnapshot_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    devices = jax.devices()
    _DEVICE.update(
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
        device_count=len(devices),
    )
    if (
        _DEVICE["platform"] == "cpu"
        and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu"
    ):
        raise SystemExit(
            f"bench.py found no accelerator (platform 'cpu', "
            f"{len(devices)} device(s)); set JAX_PLATFORMS=cpu to run on the "
            f"CPU on purpose"
        )
    return devices


_PARTIAL = {"save_gbps": 0.0, "phase": "init"}


def _drift_dominant_phase(attempt_phases: list, attempts_s: list):
    """Name the phase whose wall grew most between the best and worst
    attempt — the drift explanation the record needs when the ratio
    exceeds 1.2 (r4 verdict: a 3.6x restore variance went unexplained)."""
    if len(attempts_s) < 2 or not attempt_phases:
        return None
    best = attempt_phases[attempts_s.index(min(attempts_s))]
    worst = attempt_phases[attempts_s.index(max(attempts_s))]
    deltas = {
        ph: worst.get(ph, {}).get("s", 0.0) - best.get(ph, {}).get("s", 0.0)
        for ph in set(worst) | set(best)
    }
    if not deltas:
        return None
    drift_s = max(attempts_s) - min(attempts_s)
    ph = max(deltas, key=deltas.get)
    if deltas[ph] <= max(0.1, 0.25 * drift_s):
        # No phase explains the drift — naming one would be actively
        # misleading; the gap lives in unattributed wall (see coverage).
        return {"phase": "unattributed", "delta_s": round(drift_s, 2)}
    return {"phase": ph, "delta_s": round(deltas[ph], 2)}


def _dir_bytes(path: str) -> int:
    """Bytes actually on disk under ``path`` — with compression on this is
    smaller than the logical state size, and the delta is the codec's win."""
    total = 0
    for root, _, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:
                pass
    return total


def _phases_brief(stats: dict) -> dict:
    """Per-phase {wall_s, cpu_s, gb, gbps} with throughput over WALL time
    (thread-seconds would understate concurrent phases' rates)."""
    out = {}
    for phase, v in sorted(stats.items(), key=lambda kv: -kv[1]["s"]):
        wall = v.get("wall", v["s"])
        out[phase] = {
            "s": round(wall, 3),
            "cpu_s": round(v["s"], 3),
            "gb": round(v["bytes"] / 1e9, 3),
            "gbps": round(v["bytes"] / 1e9 / wall, 2) if wall > 0 else None,
        }
    return out


def _install_watchdog() -> None:
    """If a transfer hangs mid-run (flaky transport), emit an honest partial
    JSON line instead of dying silently at the driver's timeout."""
    import signal

    budget_s = int(os.environ.get("BENCH_MAX_S", 540))
    _PARTIAL["alarm_armed_at"] = time.monotonic()

    def _on_alarm(signum, frame):
        result = {
            "metric": "checkpoint_save_throughput_per_chip",
            "value": round(_PARTIAL["save_gbps"], 3),
            "unit": "GB/s",
            "vs_baseline": round(_PARTIAL["save_gbps"] / BASELINE_GBPS, 3),
            "backend": _DEVICE["platform"],
            **_DEVICE,
            "aux": {
                "incomplete": True,
                "hung_in_phase": _PARTIAL["phase"],
                # Evidence from every section that DID complete (a partial
                # must not discard the banked sync/async/restore numbers).
                **_PARTIAL.get("banked", {}),
            },
        }
        print(json.dumps(result), flush=True)
        os._exit(2)

    try:
        signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(budget_s)
    except (ValueError, OSError):
        pass  # non-main thread / unsupported platform


def _serve_state_nbytes(value) -> int:
    """Total array bytes in a restored (possibly nested) state dict."""
    nbytes = getattr(value, "nbytes", None)
    if isinstance(nbytes, int):
        return nbytes
    if isinstance(value, dict):
        return sum(_serve_state_nbytes(v) for v in value.values())
    if isinstance(value, (list, tuple)):
        return sum(_serve_state_nbytes(v) for v in value)
    return 0


def _serve_worker(path: str) -> int:
    """One serve-benchmark restore worker: materialize every app-state key
    of the snapshot at ``path`` through the normal read path (ranged reads,
    CAS resolve, chunk cache when TPUSNAP_CACHE_DIR is set) and print one
    JSON line: restore wall, bytes, and this process's cache hit/miss
    split.  Spawned by ``bench.py --serve N`` — and usable standalone as a
    minimal serving client.

    The whole pull is one monitored ``serve`` op: with
    TPUSNAP_FLEET_TELEMETRY set it publishes live fleet entries (`tpusnap
    top` shows this worker mid-pull), and it records a per-worker `serve`
    telemetry sidecar next to the snapshot's — the record fleet-view
    totals are cross-checked against."""
    import uuid

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu import cache as tcache
    from torchsnapshot_tpu import peer as tpeer
    from torchsnapshot_tpu import phase_stats
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin
    from torchsnapshot_tpu.telemetry import fleet as tfleet
    from torchsnapshot_tpu.telemetry import monitor as tmonitor
    from torchsnapshot_tpu.telemetry import sidecar as tsidecar
    from torchsnapshot_tpu.telemetry import trace as ttrace

    snap = Snapshot(path)
    md = snap.metadata
    if os.environ.get("BENCH_SERVE_SEED_WARM"):
        # Seed posture: pre-fault the full chunk set into the host cache
        # through the peer-aware read stack (run with TPUSNAP_PEER_FETCH=1)
        # so every part lands under its servable cas/<algo>/<hex> key — a
        # restore alone populates ranged sub-keys the exporting daemon
        # cannot serve.  This process's miss_bytes then meter the fleet's
        # ONE origin pull; the restore below hits the warmed cache.
        from torchsnapshot_tpu import cas as tcas

        warm_storage = tcache.maybe_wrap_cache_reads(
            tcas.maybe_wrap_cas_reads(url_to_storage_plugin(path), path, md),
            md,
        )
        try:
            tcache.warm_snapshot(warm_storage, md)
        finally:
            warm_storage.sync_close()
    keys = sorted(
        {p.split("/", 2)[1] for p in md.manifest if "/" in p}
    )
    op_id = uuid.uuid4().hex
    phases_before = phase_stats.snapshot()
    mon = tmonitor.op_started("serve", op_id, 0, watchdog=False)
    # With TPUSNAP_TRACE_DIR set this op (and the peer_fetch spans inside
    # it) lands in a per-worker trace file — the serving-plane tracing the
    # overhead proof below bills for.
    trace_op = ttrace.begin_op("serve", op_id, 0)
    start = time.time()
    t0 = time.monotonic()
    nbytes = 0
    try:
        for key in keys:
            state = snap.get_state_dict_for_key(key)
            nbytes += _serve_state_nbytes(state)
    except BaseException:
        ttrace.end_op(trace_op, success=False)
        tmonitor.op_finished(mon, success=False)
        raise
    wall = time.monotonic() - t0
    ttrace.end_op(trace_op, success=True)
    tmonitor.op_finished(mon, success=True)
    cache_stats = tcache.process_stats()
    if tsidecar.enabled():
        storage = url_to_storage_plugin(path)
        try:
            tsidecar.write(
                storage,
                tsidecar.build(
                    action="serve",
                    unique_id=op_id,
                    rank=0,
                    duration_s=wall,
                    phases=phase_stats.delta(phases_before),
                    nbytes=nbytes,
                    extra={
                        "cache": {
                            k: cache_stats.get(k, 0)
                            for k in (
                                "hits",
                                "misses",
                                "hit_bytes",
                                "miss_bytes",
                            )
                        }
                    },
                ),
            )
        finally:
            storage.sync_close()
    # Overhead accounting: the calibrated estimate (isolated per-publish
    # cost x publishes performed) is the honest marginal bill — the raw
    # wall total includes time the publisher thread spent descheduled
    # behind this very restore and is reported alongside for reference.
    cal = tfleet.calibrated_overhead_s()
    span_cal = ttrace.calibrated_span_cost_s()
    board_cal = tpeer.calibrated_scoreboard_cost_s()
    out = {
        "start": start,
        "end": time.time(),
        "wall_s": round(wall, 4),
        "bytes": nbytes,
        "op_id": op_id,
        "telemetry_overhead_s": cal["estimated_s"],
        "telemetry_overhead_raw_s": round(tfleet.process_overhead_s(), 6),
        "telemetry_publishes": cal["publishes"],
        # Serving-plane tracing bill, measured the same way: isolated
        # per-unit cost x units this process actually performed.
        "trace_overhead_s": span_cal["estimated_s"],
        "trace_spans": span_cal["spans"],
        "scoreboard_overhead_s": board_cal["estimated_s"],
        "scoreboard_updates": board_cal["updates"],
        **cache_stats,
        # Peer-tier split (all zero unless TPUSNAP_PEER_FETCH was on):
        # peer_hit_bytes came from sibling daemons instead of origin.
        **{f"peer_{k}": v for k, v in tpeer.process_stats().items()},
    }
    print(json.dumps(out), flush=True)
    return 0


def main() -> None:
    # Serve-benchmark worker mode: no device probes, no watchdog — just a
    # restore client (spawned N-up by the --serve probe below).
    if "--serve-worker" in sys.argv[1:]:
        idx = sys.argv.index("--serve-worker")
        if idx + 1 >= len(sys.argv):
            raise SystemExit("--serve-worker requires a snapshot path")
        raise SystemExit(_serve_worker(sys.argv[idx + 1]))

    import jax

    # Refuse to bank numbers from an instrumented native library: TSAN/ASAN
    # slow the data plane 2-20x, so any wall/phase measurement under
    # TPUSNAP_NATIVE_SANITIZE would poison the BENCH_r* trajectory.
    from torchsnapshot_tpu import knobs as _sanitize_knobs

    if _sanitize_knobs.get_native_sanitize():
        raise SystemExit(
            "bench.py refuses to run with TPUSNAP_NATIVE_SANITIZE set: "
            "sanitizer-built native libraries produce meaningless perf "
            "numbers. Unset it (or TPUSNAP_NATIVE=0 for the pure-Python "
            "baseline) and re-run."
        )

    # --telemetry: assert the save produced a telemetry sidecar
    # (telemetry/sidecar.py) and embed its summary in the result aux — the
    # CI hook that keeps the observability path exercised end to end.
    telemetry_enabled = "--telemetry" in sys.argv[1:]

    # --faults <spec>: run the whole bench with the fault-injection wrapper
    # installed (faults.py grammar).  `--faults none` installs the wrapper
    # with zero rules — the pure-overhead probe, so the wrapper's cost (off
    # and on) shows up in the perf trajectory; a real spec measures the
    # pipeline's retry/backoff cost under that schedule.
    faults_spec = None
    argv = sys.argv[1:]
    if "--faults" in argv:
        idx = argv.index("--faults")
        if idx + 1 >= len(argv):
            raise SystemExit("--faults requires a spec argument (or 'none')")
        faults_spec = argv[idx + 1]
        from torchsnapshot_tpu.faults import parse_fault_spec

        parse_fault_spec(faults_spec)  # fail fast on a typo'd spec
        # Whole-process install, read back by the plugin resolver: an env
        # export, not a config read — knobs.override_faults would unwind
        # before the bench body.
        os.environ["TPUSNAP_FAULTS"] = faults_spec  # tpusnap-lint: disable=knob-discipline
        log(f"fault injection enabled: {faults_spec!r}")

    _install_watchdog()
    devices = _init_devices()

    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict

    log(f"devices: {devices}")

    # Raw device->host link bandwidth first (the hardware ceiling for
    # staging): one 64 MiB transfer via the same fast path the stagers use.
    # Measured early so the state can be sized to the link: a slow link must
    # not get a 2 GiB state that blows the watchdog mid-save.
    from torchsnapshot_tpu import staging as _staging

    _PARTIAL["phase"] = "link_probe"
    # Untimed warm transfer first: the probe must not charge one-time costs
    # (transfer-engine and native-lib init) to the link.
    warm = jax.block_until_ready(jnp.ones((256, 256), jnp.bfloat16))
    _staging.to_host(warm)
    probe = jax.block_until_ready(
        jax.jit(lambda k: jax.random.normal(k, (8192, 4096), jnp.bfloat16))(
            jax.random.key(99)
        )
    )
    t0 = time.monotonic()
    _staging.to_host(probe)
    link_gbps = probe.size * 2 / 1e9 / (time.monotonic() - t0)
    log(f"raw D2H link: {link_gbps:.3f} GB/s")

    # Aggregate ceiling: the same bytes as 8 concurrent transfers, enqueued
    # together so the DMAs overlap — what the scheduler's admission actually
    # drives.  Where one stream is latency-bound the single-stream probe
    # understates the hardware ceiling and efficiency would read >1.  The
    # ceiling used for efficiency is max(single, agg).
    _PARTIAL["phase"] = "link_probe_agg"
    _mk_part = jax.jit(lambda k: jax.random.normal(k, (1024, 4096), jnp.bfloat16))
    agg_parts = [
        jax.block_until_ready(_mk_part(k))
        for k in jax.random.split(jax.random.key(98), 8)
    ]
    t0 = time.monotonic()
    for a in agg_parts:
        _staging.enqueue_d2h(a)
    for a in agg_parts:
        _staging.to_host(a)
    agg_bytes = sum(a.size * 2 for a in agg_parts)
    link_agg_gbps = agg_bytes / 1e9 / (time.monotonic() - t0)
    del agg_parts
    link_ceiling_gbps = max(link_gbps, link_agg_gbps)
    log(
        f"raw D2H aggregate (8 streams): {link_agg_gbps:.3f} GB/s "
        f"(ceiling {link_ceiling_gbps:.3f})"
    )

    # Raw storage write rate (the OTHER hardware ceiling): one 256 MiB
    # native write + fsync to the bench dir, so pipeline efficiency can be
    # judged against the disk's line rate, not just the D2H link
    # (SURVEY §2.2: "async file I/O >= line rate").
    _PARTIAL["phase"] = "disk_probe"
    workdir_probe = os.environ.get("BENCH_DIR") or tempfile.gettempdir()
    disk_gbps = None
    try:
        from torchsnapshot_tpu.native_io import NativeFileIO

        native = NativeFileIO.maybe_create()
        probe_path = os.path.join(workdir_probe, f".disk_probe_{os.getpid()}")
        probe_buf = memoryview(bytearray(256 << 20))
        try:
            t0 = time.monotonic()
            if native is not None:
                native.write_file(probe_path, probe_buf)
            else:
                with open(probe_path, "wb") as f:
                    f.write(probe_buf)
            fd = os.open(probe_path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
            disk_gbps = probe_buf.nbytes / 1e9 / (time.monotonic() - t0)
        finally:
            try:
                os.unlink(probe_path)
            except OSError:
                pass
        del probe_buf
        log(f"raw disk write (fsynced): {disk_gbps:.3f} GB/s")
    except OSError as e:
        log(f"disk probe failed: {e}")

    # ~2 GiB of bf16 params (1B params) on one chip, as stacked layer arrays
    # (mirrors the flagship model's layout: few large arrays, the MXU- and
    # DMA-friendly shape).  2 GiB so a >1 GB/s pipeline measures
    # multi-second phases, not noise.  The SCHEDULE is budgeted against the
    # measured link (round-3 verdict: sizing only the state while keeping 9
    # fixed passes blew the watchdog): state size sheds first (to a 256 MB
    # floor — still link-dominated on a slow transport), attempts shed
    # last and only below 2 as a last resort (round-4 verdict: best-of-1
    # numbers made drift ratios vacuous).  Override with
    # BENCH_TARGET_BYTES / BENCH_SAVE_ATTEMPTS either way.
    def _shed_schedule(cost_s, nbytes, n_attempts, first_floor, remaining_s):
        """One shed policy for every backend (r4 verdict: shedding attempts
        first made drift ratios vacuous): state size sheds to its first
        floor, then attempts to 2, then size to 64 MB, and attempts drop to
        1 only as a last resort."""
        while nbytes > first_floor and cost_s(nbytes, n_attempts) > remaining_s:
            nbytes //= 2
        while n_attempts > 2 and cost_s(nbytes, n_attempts) > remaining_s:
            n_attempts -= 1
        while nbytes > (64 << 20) and cost_s(nbytes, n_attempts) > remaining_s:
            nbytes //= 2
        if cost_s(nbytes, n_attempts) > remaining_s:
            n_attempts = 1
        return max(64 << 20, nbytes), n_attempts

    # Each attempt of each of the 3 phases moves the full state across the
    # link once (sync D2H / async background D2H / restore H2D) and the disk
    # twice (write + the inter-phase writeback drains); 1.35x slack absorbs
    # run-to-run drift.  The 256 MB first floor stays link-dominated on a
    # slow link.
    link_rate = max(link_ceiling_gbps, 1e-3) * 1e9
    disk_rate = max(disk_gbps or 1.0, 1e-3) * 1e9
    default_bytes, default_attempts = _shed_schedule(
        lambda nbytes, n: n
        * 3
        * (nbytes / link_rate + 2 * nbytes / disk_rate)
        * 1.35,
        2048 << 20,
        3,
        first_floor=256 << 20,
        remaining_s=max(_watchdog_remaining_s() - 75.0, 30.0),
    )
    target_bytes = int(os.environ.get("BENCH_TARGET_BYTES", default_bytes))
    n_arrays = 8
    per_array = target_bytes // n_arrays // 2  # bf16 = 2 bytes
    dim = 4096
    rows = per_array // dim

    @jax.jit
    def make(key):
        return [
            jax.random.normal(k, (rows, dim), dtype=jnp.bfloat16)
            for k in jax.random.split(key, n_arrays)
        ]

    arrays = jax.block_until_ready(make(jax.random.key(0)))
    actual_bytes = sum(a.size * 2 for a in arrays)
    gib = actual_bytes / (1 << 30)
    log(f"state: {n_arrays} arrays, {gib:.2f} GiB bf16 on {arrays[0].device}")

    workdir = os.environ.get("BENCH_DIR") or tempfile.mkdtemp(prefix="tpusnap_bench_")
    app_state = {"model": StateDict({f"w{i}": a for i, a in enumerate(arrays)})}

    # Warm-up (tiny) to exclude one-time costs: native lib build, imports.
    warm_state = {"model": StateDict({"w": jnp.ones((128, 128), jnp.bfloat16)})}
    Snapshot.take(os.path.join(workdir, "warmup"), warm_state)
    shutil.rmtree(os.path.join(workdir, "warmup"), ignore_errors=True)

    from torchsnapshot_tpu import phase_stats

    def _drain_writeback() -> None:
        # Start every timed phase with page-cache headroom: without this,
        # the previous phase's dirty pages push the kernel past its dirty
        # ratio mid-measurement and write() blocks on disk writeback —
        # run-to-run swings of 10x on this box.  The reference's runs on
        # fresh dirs amortize the same way.
        try:
            os.sync()
        except OSError:
            pass

    # --- sync save: best of N ---
    # Page-cache writeback throttling swings this box's write path by 10x
    # run to run; best-of-N measures the pipeline, not the disk's mood.
    # Every attempt — time AND per-attempt phase breakdown — is reported in
    # aux, with worst-of-N alongside (r03 drifted +66% by attempt 3 and
    # best-of-N alone hid it; an operator's steady state is nearer worst).
    attempts = int(os.environ.get("BENCH_SAVE_ATTEMPTS", default_attempts))
    save_attempts_s = []
    save_attempt_phases = []
    save_attempt_coverage = []
    snapshot = None
    save_phases = {}
    best_save_s = float("inf")
    for attempt in range(attempts):
        _PARTIAL["phase"] = f"sync_save[{attempt + 1}/{attempts}]"
        snap_path = os.path.join(workdir, "snap")
        shutil.rmtree(snap_path, ignore_errors=True)
        _drain_writeback()
        phase_stats.reset()
        begin = time.monotonic()
        snapshot = Snapshot.take(snap_path, app_state)
        elapsed = time.monotonic() - begin
        save_attempts_s.append(round(elapsed, 2))
        save_attempt_phases.append(_phases_brief(phase_stats.snapshot()))
        save_attempt_coverage.append(
            round(phase_stats.attributed_wall_s() / elapsed, 3)
        )
        if elapsed < best_save_s:
            best_save_s = elapsed
            save_phases = phase_stats.snapshot()
        _PARTIAL["save_gbps"] = actual_bytes / 1e9 / best_save_s
    save_s = min(save_attempts_s)
    save_gbps = actual_bytes / 1e9 / save_s
    bytes_written = _dir_bytes(os.path.join(workdir, "snap"))

    telemetry_sidecar = None
    if telemetry_enabled:
        from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin
        from torchsnapshot_tpu.telemetry import sidecar as _sidecar

        _storage = url_to_storage_plugin(os.path.join(workdir, "snap"))
        try:
            _docs = [
                d
                for d in _sidecar.read_all(_storage)
                if d.get("action") == "take"
            ]
        finally:
            _storage.sync_close()
        if not _docs:
            raise RuntimeError(
                "--telemetry: the save produced no telemetry sidecar "
                "(is TPUSNAP_SIDECAR=0 set?)"
            )
        doc = _docs[0]  # newest (last attempt's) take
        telemetry_sidecar = {
            "path": _sidecar.sidecar_path(
                doc["action"], doc["op_id"], doc["rank"]
            ),
            "duration_s": doc.get("duration_s"),
            "bytes": doc.get("bytes"),
            "throughput_gbps": doc.get("throughput_gbps"),
            "phases": doc.get("phases"),
            "knobs": doc.get("knobs"),
            "rss_high_water_bytes": doc.get("rss_high_water_bytes"),
        }
        log(f"telemetry sidecar: {telemetry_sidecar['path']}")
    log(f"sync save: {save_s:.2f}s -> {save_gbps:.2f} GB/s (runs: {save_attempts_s})")
    log(f"  save phases (best attempt): {phase_stats.format_line(save_phases)}")
    log(f"  bytes written: {bytes_written / 1e9:.3f} GB for {actual_bytes / 1e9:.3f} GB of state")
    _PARTIAL.setdefault("banked", {})["sync"] = {
        "state_gib": round(gib, 2),
        "save_attempts_s": save_attempts_s,
        "save_phases": _phases_brief(save_phases),
        "bytes_written": bytes_written,
    }

    # --- compression probe: one save with the best available codec ---
    # The default save path ships bytes raw; this measures what the codec
    # tier (TPUSNAP_COMPRESSION, compression.py) buys on the same state:
    # bytes written, wall time, and effective GB/s (logical bytes over
    # wall — the number that beats the raw save when storage, not the
    # codec, is the bottleneck).  Skipped when the operator already set
    # TPUSNAP_COMPRESSION (the main save measured it), when no codec
    # library is available, or when the watchdog budget can't cover an
    # extra save pass.  BENCH_COMPRESSION=<codec> forces, =0 disables.
    compression_probe = None
    from torchsnapshot_tpu import compression as _compression

    from torchsnapshot_tpu import knobs as _knobs

    requested = os.environ.get("BENCH_COMPRESSION", "zstd")
    # Resolve the configured codec through availability: an env spelling of
    # zstd on a host without the wheel stored RAW bytes, and must take the
    # fallback probe below, not claim the main save measured compression.
    if _compression.resolve(_knobs.get_compression()[0]) != "raw":
        _codec, _level = _knobs.get_compression()
        compression_probe = {
            "codec": _codec if _level is None else f"{_codec}:{_level}",
            # The operator's configured codec IS what ran (resolve() just
            # confirmed it); surfaced explicitly so every probe shape has
            # the downgrade answer at top level.
            "codec_downgraded": False,
            "note": "main save ran compressed (TPUSNAP_COMPRESSION set)",
            "bytes_written": bytes_written,
            "logical_bytes": actual_bytes,
            "ratio": round(actual_bytes / bytes_written, 3) if bytes_written else None,
        }
    elif requested.lower() not in ("0", "off", "none", "raw", "false"):
        # Same codec[:level] syntax as TPUSNAP_COMPRESSION (zstd:6, zlib:1);
        # only the codec name goes through availability resolution.
        req_name, _, req_level = requested.strip().lower().partition(":")
        try:
            if req_level and not req_level.lstrip("-").isdigit():
                raise ValueError(
                    f"BENCH_COMPRESSION={requested!r}: level {req_level!r} "
                    "is not an integer"
                )
            codec = (
                req_name
                if _compression.resolve(req_name) != "raw"
                else next(iter(_compression.available_codecs()), None)
            )
        except ValueError as e:
            # A typo'd BENCH_COMPRESSION must not abort the whole bench
            # after the sync-save section already ran.
            codec = None
            skip_reason = str(e)
        else:
            skip_reason = f"no codec library available (requested {requested})"
        # Extra pass ≈ one save + one codec pass.  30 MB/s floor: measured
        # zlib on a 1-vCPU box runs ~40 MB/s (docs/performance.md), and an
        # undershot estimate runs the watchdog out mid-probe, losing the
        # async/restore sections the bench exists to collect.
        est_s = save_s + actual_bytes / 30e6
        if codec is not None and _watchdog_remaining_s() > est_s + 60:
            _PARTIAL["phase"] = "compression_probe"
            comp_path = os.path.join(workdir, "snap_comp")
            shutil.rmtree(comp_path, ignore_errors=True)
            _drain_writeback()
            # Carry the requested level through only when the requested
            # codec itself is the one running (a fallback codec has its
            # own level scale).
            setting = (
                f"{codec}:{req_level}"
                if codec == req_name and req_level
                else codec
            )
            with _knobs.override_compression(setting):
                phase_stats.reset()
                t0 = time.monotonic()
                Snapshot.take(comp_path, app_state)
                comp_save_s = time.monotonic() - t0
            comp_bytes = _dir_bytes(comp_path)
            shutil.rmtree(comp_path, ignore_errors=True)
            compression_probe = {
                "codec": codec,
                "requested": requested,
                # Top-level downgrade flag: BENCH_r07's reader had to diff
                # codec vs requested to notice zlib stood in for zstd —
                # surface it where nobody can miss it.
                "codec_downgraded": codec != req_name,
                "save_s": round(comp_save_s, 2),
                "bytes_written": comp_bytes,
                "raw_bytes_written": bytes_written,
                "ratio": round(bytes_written / comp_bytes, 3) if comp_bytes else None,
                "effective_gbps": round(actual_bytes / 1e9 / comp_save_s, 3),
                "phases": _phases_brief(phase_stats.snapshot()),
            }
            log(
                f"compression probe ({codec}): {comp_save_s:.2f}s, "
                f"{comp_bytes / 1e9:.3f} GB written vs {bytes_written / 1e9:.3f} raw "
                f"(ratio {compression_probe['ratio']}x)"
            )
        elif codec is None:
            log(f"compression probe skipped: {skip_reason}")
        else:
            log("compression probe skipped: insufficient watchdog budget")
    _PARTIAL["banked"]["sync"]["compression_probe"] = compression_probe

    # --- compressed-save scaling probe (--compress-scale): does encode
    # bandwidth scale with the staging executor?  ROADMAP 4b: compressed
    # saves saturate the fixed 4-thread staging executor; the scheduler
    # now sizes it from codec resolution (min(16, cores) when a real codec
    # resolved, TPUSNAP_STAGING_THREADS pins).  The probe saves the same
    # compressible host-side state at executor sizes 1 / 4 / auto and
    # reports GB/s per size — acceptance is auto ≥ 4-thread ≥ 1-thread on
    # a multi-core host (scaling, not saturation).
    compress_scale_probe = None
    if "--compress-scale" in argv:
        _PARTIAL["phase"] = "compress_scale_probe"
        codec = next(iter(_compression.available_codecs()), None)
        if codec is None:
            log("compress-scale probe skipped: no codec library available")
        else:
            scale_mb = int(os.environ.get("BENCH_COMPRESS_SCALE_MB", "256"))
            rs = np.random.RandomState(23)
            # Half-compressible state: structured low bytes + noise, split
            # into per-chunk leaves so concurrent stagers exist to spread
            # across the executor.
            n_scale_leaves = 16
            leaf_nbytes = (scale_mb << 20) // n_scale_leaves
            base = np.arange(leaf_nbytes, dtype=np.uint8)
            scale_state = {
                f"c{i:02d}": (
                    base + rs.randint(0, 3, leaf_nbytes).astype(np.uint8)
                )
                for i in range(n_scale_leaves)
            }
            scale_app = {"scale": StateDict(scale_state)}
            logical = n_scale_leaves * leaf_nbytes
            runs = {}
            for label, threads in (("1", 1), ("4", 4), ("auto", 0)):
                scale_path = os.path.join(workdir, f"snap_scale_{label}")
                shutil.rmtree(scale_path, ignore_errors=True)
                _drain_writeback()
                with _knobs.override_compression(codec), (
                    _knobs.override_staging_threads(threads)
                ):
                    t0 = time.monotonic()
                    Snapshot.take(scale_path, scale_app)
                    wall = time.monotonic() - t0
                written = _dir_bytes(scale_path)
                shutil.rmtree(scale_path, ignore_errors=True)
                runs[label] = {
                    "staging_threads": threads,
                    "save_s": round(wall, 3),
                    "bytes_written": written,
                    "effective_gbps": round(logical / 1e9 / wall, 3),
                }
            import os as _os

            compress_scale_probe = {
                "codec": codec,
                "logical_bytes": logical,
                "cores": _os.cpu_count(),
                "runs": runs,
                "speedup_auto_vs_1": round(
                    runs["auto"]["effective_gbps"]
                    / max(runs["1"]["effective_gbps"], 1e-9),
                    3,
                ),
                # THE acceptance bar: the executor is no longer the
                # compressed-save ceiling — auto sizing beats one thread
                # materially on a multi-core host.
                "scales_with_threads": (
                    (_os.cpu_count() or 1) < 2
                    or runs["auto"]["effective_gbps"]
                    > 1.2 * runs["1"]["effective_gbps"]
                ),
            }
            log(
                f"compress-scale probe ({codec}): "
                f"1-thread {runs['1']['effective_gbps']} GB/s, "
                f"4-thread {runs['4']['effective_gbps']} GB/s, "
                f"auto {runs['auto']['effective_gbps']} GB/s "
                f"(auto/1 = {compress_scale_probe['speedup_auto_vs_1']}x on "
                f"{compress_scale_probe['cores']} cores)"
            )
        _PARTIAL["banked"]["sync"]["compress_scale_probe"] = compress_scale_probe

    # --- blackbox flight-recorder probe (--blackbox): calibrated cost ---
    # One extra save with TPUSNAP_BLACKBOX pointed at a scratch ring, then
    # the recorder's own estimate-by-parts calibration (per-record pwrite
    # cost on a scratch ring x records the save actually spilled) against
    # that save's wall.  The acceptance bar is overhead_below_1pct — the
    # always-on forensics budget from docs/observability.md — and
    # records_per_s is banked as its own gated trajectory series so a
    # change that makes the spill path slow (sync, fsync, lock contention)
    # fails tools/bench_trajectory.py like any throughput loss.
    blackbox_probe = None
    if "--blackbox" in argv:
        _PARTIAL["phase"] = "blackbox_probe"
        if _watchdog_remaining_s() > save_s + 60:
            from torchsnapshot_tpu.telemetry import blackbox as _blackbox

            bb_dir = os.path.join(workdir, "blackbox")
            bb_path = os.path.join(workdir, "snap_blackbox")
            shutil.rmtree(bb_path, ignore_errors=True)
            _drain_writeback()
            with _knobs.override_blackbox_dir(bb_dir):
                t0 = time.monotonic()
                Snapshot.take(bb_path, app_state)
                bb_wall_s = time.monotonic() - t0
                cal = _blackbox.calibrated_overhead_s(samples=500)
            shutil.rmtree(bb_path, ignore_errors=True)
            bb_records = int(cal["records"])
            bb_overhead_s = cal["estimated_s"]
            blackbox_probe = {
                "records": bb_records,
                "per_record_s": round(cal["per_record_s"], 9),
                "records_per_s": round(1.0 / cal["per_record_s"], 1)
                if cal["per_record_s"] > 0
                else None,
                "overhead_s": round(bb_overhead_s, 6),
                "op_wall_s": round(bb_wall_s, 3),
                "overhead_frac_of_wall": round(bb_overhead_s / bb_wall_s, 6)
                if bb_wall_s > 0
                else 0.0,
                # THE acceptance bar: always-on forensics must cost less
                # than 1% of the op it is recording.
                "overhead_below_1pct": bb_overhead_s < 0.01 * bb_wall_s,
            }
            log(
                f"blackbox probe: {bb_records} records @ "
                f"{cal['per_record_s'] * 1e6:.1f} us -> "
                f"{bb_overhead_s * 1e3:.2f} ms of {bb_wall_s:.2f}s save "
                f"({blackbox_probe['overhead_frac_of_wall'] * 100:.3f}%, "
                f"below_1pct={blackbox_probe['overhead_below_1pct']})"
            )
        else:
            log("blackbox probe skipped: insufficient watchdog budget")
        _PARTIAL["banked"]["sync"]["blackbox_probe"] = blackbox_probe

    # --- CAS dedup probe (--cas): content-addressed store economics ---
    # A 3-step simulated fine-tune — frozen backbone + churning optimizer —
    # saved under TPUSNAP_CAS=1: physical chunk bytes written per step and
    # the logical/physical dedup ratio, the storage-cost story the CAS
    # subsystem (cas.py) exists for.  Host-side state on purpose: dedup is
    # a storage-layer property, and burning watchdog budget on D2H here
    # would steal it from the async/restore sections.
    cas_probe = None
    if "--cas" in argv:
        _PARTIAL["phase"] = "cas_probe"
        from torchsnapshot_tpu.manager import SnapshotManager as _Manager

        cas_root = os.path.join(workdir, "cas_root")
        shutil.rmtree(cas_root, ignore_errors=True)
        backbone_mb = int(os.environ.get("BENCH_CAS_BACKBONE_MB", "64"))
        backbone = np.random.RandomState(7).bytes(backbone_mb << 20)
        backbone = np.frombuffer(backbone, np.uint8).reshape(-1)
        opt_nbytes = max(backbone.nbytes // 8, 1 << 20)
        logical_per_step = backbone.nbytes + opt_nbytes
        step_s = []
        # Dedup granularity is the CHUNK: payloads under the slab threshold
        # share slab chunks, and a slab mixing the frozen backbone with the
        # churning optimizer can never dedup (one changed member renames
        # the whole slab's digest).  Real frozen backbones are far above
        # the 128 MB threshold; the probe's scaled-down one must be too,
        # so drop the threshold instead of inflating the probe state.
        with _knobs.override_cas(True), _knobs.override_slab_size_threshold_bytes(
            4 << 20
        ):
            mgr = _Manager(cas_root)
            for step in (1, 2, 3):
                opt = np.random.RandomState(step).bytes(opt_nbytes)
                opt = np.frombuffer(opt, np.uint8).reshape(-1)
                _drain_writeback()
                t0 = time.monotonic()
                mgr.save(
                    step,
                    {
                        "ft": StateDict(
                            {"backbone": backbone, "optimizer": opt}
                        )
                    },
                )
                step_s.append(round(time.monotonic() - t0, 2))
        physical_bytes = _dir_bytes(os.path.join(cas_root, "cas"))
        logical_bytes = 3 * logical_per_step
        # Restore the oldest step to prove dedup'd references resolve.
        dst = {
            "ft": StateDict(
                {
                    "backbone": np.zeros_like(backbone),
                    "optimizer": np.zeros(opt_nbytes, np.uint8),
                }
            )
        }
        mgr.snapshot(1).restore(dst)
        np.testing.assert_array_equal(
            np.asarray(dst["ft"]["backbone"][:64]), backbone[:64]
        )
        shutil.rmtree(cas_root, ignore_errors=True)
        cas_probe = {
            "steps": 3,
            "backbone_bytes": backbone.nbytes,
            "optimizer_bytes": opt_nbytes,
            "logical_bytes": logical_bytes,
            "physical_bytes_written": physical_bytes,
            "dedup_ratio": round(logical_bytes / physical_bytes, 3)
            if physical_bytes
            else None,
            "step_save_s": step_s,
            # The frozen backbone must be stored exactly once: physical ≈
            # backbone + 3 optimizers (+ manifest/sidecar noise outside
            # cas/, not counted here).
            "backbone_stored_once": physical_bytes
            < backbone.nbytes + 3 * opt_nbytes + (1 << 20),
        }
        log(
            f"cas probe: {physical_bytes / 1e9:.3f} GB physical for "
            f"{logical_bytes / 1e9:.3f} GB logical "
            f"(dedup {cas_probe['dedup_ratio']}x, steps {step_s})"
        )
        _PARTIAL["banked"]["sync"]["cas_probe"] = cas_probe

    # --- shared-store probe (--store): multi-tenant CAS economics ---
    # Two tenants (two manager roots) fine-tuning from the SAME frozen
    # backbone into one shared store (store.py): the backbone should land
    # physically ONCE store-wide while each tenant's churning head lands
    # per-tenant — physical ≈ 1× backbone + per-tenant deltas.  The
    # cross-tenant dedup ratio is the number the multi-tenant store
    # exists for; banked as a gated trajectory series.  Same slab-
    # threshold note as the cas probe: dedup granularity is the chunk,
    # so the scaled-down backbone must exceed the slab threshold.
    store_probe = None
    if "--store" in argv:
        _PARTIAL["phase"] = "store_probe"
        from torchsnapshot_tpu import store as _store_mod
        from torchsnapshot_tpu.manager import SnapshotManager as _Manager

        store_dir = os.path.join(workdir, "store_shared")
        shutil.rmtree(store_dir, ignore_errors=True)
        tenant_roots = [
            os.path.join(workdir, f"store_tenant_{i}") for i in (0, 1)
        ]
        for r in tenant_roots:
            shutil.rmtree(r, ignore_errors=True)
        backbone_mb = int(os.environ.get("BENCH_STORE_BACKBONE_MB", "64"))
        backbone = np.random.RandomState(11).bytes(backbone_mb << 20)
        backbone = np.frombuffer(backbone, np.uint8).reshape(-1)
        head_nbytes = max(backbone.nbytes // 8, 1 << 20)
        step_s = []
        with _knobs.override_slab_size_threshold_bytes(4 << 20):
            mgrs = [
                _Manager(r, store=store_dir) for r in tenant_roots
            ]
            for step in (1, 2):
                for ti, mgr in enumerate(mgrs):
                    head = np.random.RandomState(100 * ti + step).bytes(
                        head_nbytes
                    )
                    head = np.frombuffer(head, np.uint8).reshape(-1)
                    _drain_writeback()
                    t0 = time.monotonic()
                    mgr.save(
                        step,
                        {
                            "ft": StateDict(
                                {"backbone": backbone, "head": head}
                            )
                        },
                    )
                    step_s.append(round(time.monotonic() - t0, 2))
        physical_bytes = _dir_bytes(os.path.join(store_dir, "cas"))
        usage = _store_mod.tenant_usage(store_dir)
        logical_bytes = usage["logical_bytes"]
        # Prove both tenants restore through the shared store.
        for ti, mgr in enumerate(mgrs):
            dst = {
                "ft": StateDict(
                    {
                        "backbone": np.zeros_like(backbone),
                        "head": np.zeros(head_nbytes, np.uint8),
                    }
                )
            }
            mgr.restore_latest(dst)
            np.testing.assert_array_equal(
                np.asarray(dst["ft"]["backbone"][:64]), backbone[:64]
            )
        shutil.rmtree(store_dir, ignore_errors=True)
        for r in tenant_roots:
            shutil.rmtree(r, ignore_errors=True)
        store_probe = {
            "tenants": 2,
            "steps_per_tenant": 2,
            "backbone_bytes": backbone.nbytes,
            "head_bytes": head_nbytes,
            "logical_bytes": logical_bytes,
            "physical_bytes": physical_bytes,
            "dedup_ratio": round(logical_bytes / physical_bytes, 3)
            if physical_bytes
            else None,
            "step_save_s": step_s,
            # The shared backbone must be stored exactly once STORE-WIDE:
            # physical ≈ 1× backbone + 4 tenant heads (2 tenants × 2
            # steps), not 2× backbone.
            "backbone_stored_once": physical_bytes
            < backbone.nbytes + 4 * head_nbytes + (1 << 20),
        }
        log(
            f"store probe: {physical_bytes / 1e9:.3f} GB physical for "
            f"{logical_bytes / 1e9:.3f} GB logical across 2 tenants "
            f"(dedup {store_probe['dedup_ratio']}x, "
            f"backbone_stored_once={store_probe['backbone_stored_once']})"
        )
        _PARTIAL["banked"]["sync"]["store_probe"] = store_probe

    # --- journal probe (--journal): high-frequency delta-save economics ---
    # N steps of a 10%-churn workload (20 equal leaves, 2 mutated per
    # step) saved twice: full async_take baseline vs journal mode
    # (journal.py).  Reports per-step wall and bytes APPENDED to the root
    # per step — the acceptance bar is append ∝ changed fraction and step
    # wall below the full baseline.  Host-side state like the CAS probe:
    # the journal's economics are a storage-layer property.
    journal_probe = None
    if "--journal" in argv:
        _PARTIAL["phase"] = "journal_probe"
        from torchsnapshot_tpu.manager import SnapshotManager as _Manager

        n_leaves, churn_per_step = 20, 2
        leaf_mb = int(os.environ.get("BENCH_JOURNAL_LEAF_MB", "4"))
        n_journal_steps = int(os.environ.get("BENCH_JOURNAL_STEPS", "8"))
        leaf_nbytes = leaf_mb << 20
        logical_bytes = n_leaves * leaf_nbytes

        def _leaves(rs):
            return {
                f"leaf_{i:02d}": np.frombuffer(
                    rs.bytes(leaf_nbytes), np.uint8
                ).reshape(-1)
                for i in range(n_leaves)
            }

        def _mutate(leaves, step):
            rs = np.random.RandomState(1000 + step)
            for j in range(churn_per_step):
                i = (step * churn_per_step + j) % n_leaves
                leaves[f"leaf_{i:02d}"] = np.frombuffer(
                    rs.bytes(leaf_nbytes), np.uint8
                ).reshape(-1)

        def _run_mode(root, journal_mode):
            shutil.rmtree(root, ignore_errors=True)
            leaves = _leaves(np.random.RandomState(3))
            walls, appended = [], []
            # Leaves must stay distinct chunks for per-leaf dedup (same
            # slab-granularity reasoning as the CAS probe).
            with _knobs.override_slab_size_threshold_bytes(
                1 << 20
            ), _knobs.override_journal_max_segments(4):
                mgr = _Manager(root, journal=journal_mode)
                for step in range(1, n_journal_steps + 1):
                    _mutate(leaves, step)
                    before = _dir_bytes(root)
                    _drain_writeback()
                    t0 = time.monotonic()
                    mgr.save(
                        step,
                        {"m": StateDict(dict(leaves))},
                        async_=True,
                    ).wait()
                    walls.append(round(time.monotonic() - t0, 3))
                    appended.append(_dir_bytes(root) - before)
                dst = {
                    "m": StateDict(
                        {
                            k: np.zeros(leaf_nbytes, np.uint8)
                            for k in leaves
                        }
                    )
                }
                restored = mgr.restore_latest(dst)
                assert restored == n_journal_steps, restored
                np.testing.assert_array_equal(
                    np.asarray(dst["m"]["leaf_00"][:64]),
                    leaves["leaf_00"][:64],
                )
            return walls, appended

        journal_root = os.path.join(workdir, "journal_root")
        full_root = os.path.join(workdir, "journal_full_root")
        full_walls, full_appended = _run_mode(full_root, journal_mode=False)
        j_walls, j_appended = _run_mode(journal_root, journal_mode=True)
        shutil.rmtree(journal_root, ignore_errors=True)
        shutil.rmtree(full_root, ignore_errors=True)
        churn_bytes = churn_per_step * leaf_nbytes
        # Steady-state = delta steps after the base save (step 1 writes the
        # full base) and excluding compaction steps' fold bookkeeping.
        steady_appended = j_appended[1:]
        journal_probe = {
            "steps": n_journal_steps,
            "leaves": n_leaves,
            "leaf_bytes": leaf_nbytes,
            "logical_bytes_per_step": logical_bytes,
            "churn_fraction": round(churn_per_step / n_leaves, 3),
            "churn_bytes_per_step": churn_bytes,
            "full_step_wall_s": full_walls,
            "journal_step_wall_s": j_walls,
            "full_appended_bytes": full_appended,
            "journal_appended_bytes": j_appended,
            "journal_mean_appended_bytes": int(
                sum(steady_appended) / max(len(steady_appended), 1)
            ),
            "append_vs_churn_ratio": round(
                sum(steady_appended)
                / max(len(steady_appended), 1)
                / churn_bytes,
                3,
            ),
            "mean_full_wall_s": round(sum(full_walls) / len(full_walls), 3),
            "mean_journal_wall_s": round(
                sum(j_walls[1:]) / max(len(j_walls) - 1, 1), 3
            ),
            # THE acceptance pair: appended bytes track the churn (not the
            # total), and delta steps beat the full-save baseline.
            "append_proportional_to_churn": (
                sum(steady_appended) / max(len(steady_appended), 1)
                < 0.5 * logical_bytes
            ),
            "journal_faster_than_full": (
                sum(j_walls[1:]) / max(len(j_walls) - 1, 1)
                < sum(full_walls) / len(full_walls)
            ),
        }
        log(
            f"journal probe: {journal_probe['mean_journal_wall_s']} s/step "
            f"(full baseline {journal_probe['mean_full_wall_s']} s), "
            f"appended {journal_probe['journal_mean_appended_bytes'] / 1e6:.1f} MB/step "
            f"for {churn_bytes / 1e6:.1f} MB churned of "
            f"{logical_bytes / 1e6:.1f} MB total "
            f"(append/churn {journal_probe['append_vs_churn_ratio']}x)"
        )
        _PARTIAL["banked"]["sync"]["journal_probe"] = journal_probe

        # --- churn-WITHIN-slab mode: the slab-granularity amplification
        # probe.  Many small leaves pack into ONE slab (threshold left at
        # a value that swallows them all); 10% of the leaves churn per
        # step.  Pre-CDC, any churned member re-wrote the whole slab
        # (append ≈ slab size); with content-defined sub-chunking
        # (TPUSNAP_CDC) only the edit-overlapping chunks append, so the
        # acceptance is append ∝ churn.  Banked as its own gated
        # trajectory series (journal_slab churn efficiency).
        _PARTIAL["phase"] = "journal_slab_probe"
        slab_leaves, slab_churn = 40, 4
        slab_leaf_nbytes = 64 * 1024
        slab_logical = slab_leaves * slab_leaf_nbytes
        slab_steps = int(os.environ.get("BENCH_JOURNAL_SLAB_STEPS", "6"))

        def _slab_leaves_of(rs):
            return {
                f"s{i:02d}": np.frombuffer(
                    rs.bytes(slab_leaf_nbytes), np.uint8
                ).reshape(-1)
                for i in range(slab_leaves)
            }

        def _run_slab_mode(root):
            shutil.rmtree(root, ignore_errors=True)
            leaves = _slab_leaves_of(np.random.RandomState(17))
            appended = []
            # All 40 leaves fit one 128 MB-threshold slab; CDC chunks it
            # on content-defined edges (small params so a 64 KB edit maps
            # to ~a chunk, not the whole slab).
            with _knobs.override_cdc(True), _knobs.override_cdc_params(
                4096, 16384, 65536
            ), _knobs.override_journal_max_segments(slab_steps + 1):
                mgr = _Manager(root, journal=True)
                for step in range(1, slab_steps + 1):
                    if step > 1:
                        rs = np.random.RandomState(2000 + step)
                        for j in range(slab_churn):
                            i = (step * slab_churn + j) % slab_leaves
                            leaves[f"s{i:02d}"] = np.frombuffer(
                                rs.bytes(slab_leaf_nbytes), np.uint8
                            ).reshape(-1)
                    before = _dir_bytes(root)
                    _drain_writeback()
                    mgr.save(
                        step, {"m": StateDict(dict(leaves))}, async_=True
                    ).wait()
                    appended.append(_dir_bytes(root) - before)
                dst = {
                    "m": StateDict(
                        {
                            k: np.zeros(len(v), np.uint8)
                            for k, v in leaves.items()
                        }
                    )
                }
                restored = mgr.restore_latest(dst)
                assert restored == slab_steps, restored
                np.testing.assert_array_equal(
                    np.asarray(dst["m"]["s00"]), leaves["s00"]
                )
            return appended

        slab_root = os.path.join(workdir, "journal_slab_root")
        slab_appended = _run_slab_mode(slab_root)
        shutil.rmtree(slab_root, ignore_errors=True)
        slab_churn_bytes = slab_churn * slab_leaf_nbytes
        slab_steady = slab_appended[1:]
        slab_mean_appended = sum(slab_steady) / max(len(slab_steady), 1)
        journal_probe["slab_mode"] = {
            "leaves": slab_leaves,
            "leaf_bytes": slab_leaf_nbytes,
            "logical_bytes": slab_logical,
            "churn_fraction": round(slab_churn / slab_leaves, 3),
            "churn_bytes_per_step": slab_churn_bytes,
            "appended_bytes": slab_appended,
            "mean_appended_bytes": int(slab_mean_appended),
            "append_vs_churn_ratio": round(
                slab_mean_appended / slab_churn_bytes, 3
            ),
            # churn/append — higher is better (1.0 = perfect); the gated
            # trajectory series value.  Pre-CDC this sat near
            # churn/slab ≈ 0.1 (whole-slab re-write).
            "churn_efficiency": round(
                slab_churn_bytes / max(slab_mean_appended, 1), 3
            ),
            # THE acceptance bar: appended bytes track the churned
            # members, not the slab (amplification < half the slab).
            "append_proportional_to_churn": (
                slab_mean_appended < 0.5 * slab_logical
            ),
        }
        log(
            f"journal slab-churn probe: {slab_mean_appended / 1e6:.2f} MB/step "
            f"appended for {slab_churn_bytes / 1e6:.2f} MB churned inside a "
            f"{slab_logical / 1e6:.1f} MB slab "
            f"(append/churn {journal_probe['slab_mode']['append_vs_churn_ratio']}x, "
            f"proportional: {journal_probe['slab_mode']['append_proportional_to_churn']})"
        )

    # --- native A/B probe (--native-ab): off-GIL data plane economics ---
    # The same host-side state saved+restored twice: native data plane on
    # (fused write+hash, striped xxh64s, parallel ranged reads) vs
    # TPUSNAP_NATIVE=0 (the byte-identical pure-Python fallback).  Reports
    # per-leg wall, per-phase thread-seconds ("cpu_s") and wall, and THE
    # acceptance metric: the save-path cpu_s/wall ratio over the
    # write+checksum phases (fs_write + checksum + native_write_hash +
    # slab_pack).  BENCH_r05 measured ~3 thread-seconds per wall-second
    # there — GIL/thread-pool bound; the fused native call should collapse
    # it toward 1.  Host-side state on purpose: this is a CPU data-plane
    # probe, and D2H would burn watchdog budget the async/restore sections
    # need.  Byte identity between the two legs is asserted, not assumed.
    native_ab_probe = None
    profiler_probe = None
    if "--native-ab" in argv:
        _PARTIAL["phase"] = "native_ab_probe"
        import hashlib

        from torchsnapshot_tpu import knobs as _kn

        ab_mb = int(os.environ.get("BENCH_NATIVE_AB_MB", "512"))
        n_ab = 8
        per_ab = (ab_mb << 20) // n_ab
        ab_arrays = {
            f"w{i}": np.frombuffer(
                np.random.RandomState(100 + i).bytes(per_ab), np.uint8
            ).copy()
            for i in range(n_ab)
        }
        ab_logical = sum(a.nbytes for a in ab_arrays.values())
        _WRITE_PHASES = ("fs_write", "checksum", "native_write_hash", "slab_pack")

        def _ab_write_ratio(phases_snapshot):
            cpu = sum(
                phases_snapshot[p]["s"]
                for p in _WRITE_PHASES
                if p in phases_snapshot
            )
            wall = sum(
                phases_snapshot[p].get("wall", phases_snapshot[p]["s"])
                for p in _WRITE_PHASES
                if p in phases_snapshot
            )
            return cpu, wall, (cpu / wall if wall > 0 else None)

        def _ab_dir_digest(root):
            out = {}
            for dirpath, _, files in os.walk(root):
                for fname in sorted(files):
                    p = os.path.join(dirpath, fname)
                    rel = os.path.relpath(p, root)
                    if rel.startswith("telemetry/"):
                        continue
                    with open(p, "rb") as f:
                        out[rel] = hashlib.sha1(f.read()).hexdigest()
            return out

        def _proc_cpu_s() -> float:
            import resource

            r = resource.getrusage(resource.RUSAGE_SELF)
            return r.ru_utime + r.ru_stime

        def _ab_leg(root, native_on, profile_dir=None):
            from torchsnapshot_tpu import knobs as _kn

            shutil.rmtree(root, ignore_errors=True)
            # profile_dir set -> the leg's take+restore run under the
            # continuous profiler (telemetry/profiler.py), one profile
            # file per op; None unsets the knob (warm legs unprofiled).
            with _kn.override_profile_dir(profile_dir), _kn.override_native(
                native_on
            ):
                _drain_writeback()
                phase_stats.reset()
                c0, t0 = _proc_cpu_s(), time.monotonic()
                ab_snap = Snapshot.take(
                    root, {"m": StateDict(dict(ab_arrays))}
                )
                save_s = time.monotonic() - t0
                save_cpu_s = _proc_cpu_s() - c0
                save_ph = phase_stats.snapshot()
                dst = {
                    "m": StateDict(
                        {k: np.zeros_like(v) for k, v in ab_arrays.items()}
                    )
                }
                _drain_writeback()
                phase_stats.reset()
                c0, t0 = _proc_cpu_s(), time.monotonic()
                ab_snap.restore(dst)
                restore_s = time.monotonic() - t0
                restore_cpu_s = _proc_cpu_s() - c0
                restore_ph = phase_stats.snapshot()
            np.testing.assert_array_equal(
                np.asarray(dst["m"]["w0"][:64]), ab_arrays["w0"][:64]
            )
            cpu, wall, ratio = _ab_write_ratio(save_ph)
            return {
                "save_s": round(save_s, 3),
                "restore_s": round(restore_s, 3),
                "save_gbps": round(ab_logical / 1e9 / save_s, 3),
                "restore_gbps": round(ab_logical / 1e9 / restore_s, 3),
                # Real process CPU (getrusage, all threads incl. the native
                # pool) — phase "cpu_s" counts concurrent CALL durations,
                # which overstates modes that drive more concurrency.
                "save_proc_cpu_s": round(save_cpu_s, 3),
                "restore_proc_cpu_s": round(restore_cpu_s, 3),
                "save_phases": _phases_brief(save_ph),
                "restore_phases": _phases_brief(restore_ph),
                "write_checksum_cpu_s": round(cpu, 3),
                "write_checksum_wall_s": round(wall, 3),
                "write_checksum_cpu_per_wall": round(ratio, 3)
                if ratio is not None
                else None,
            }

        ab_native_root = os.path.join(workdir, "ab_native")
        ab_py_root = os.path.join(workdir, "ab_fallback")
        # Untimed warm pass per mode (page-cache state, pool spin-up, lazy
        # imports), then the measured legs.
        _ab_leg(os.path.join(workdir, "ab_warm"), True)
        _ab_leg(os.path.join(workdir, "ab_warm"), False)
        shutil.rmtree(os.path.join(workdir, "ab_warm"), ignore_errors=True)
        # Measured legs run profiled: the differential profile between
        # them names the checksum/decode frames the native plane moves.
        ab_prof_native = os.path.join(workdir, "ab_prof_native")
        ab_prof_py = os.path.join(workdir, "ab_prof_fallback")
        leg_native = _ab_leg(ab_native_root, True, profile_dir=ab_prof_native)
        leg_py = _ab_leg(ab_py_root, False, profile_dir=ab_prof_py)
        identical = _ab_dir_digest(ab_native_root) == _ab_dir_digest(ab_py_root)

        from torchsnapshot_tpu.telemetry import profiler as _profiler

        def _leg_profile_meta(prof_dir, kind=None):
            """Merged profile meta of one leg's dir (optionally one op
            kind only), or None if that leg produced no profiles."""
            try:
                docs = _profiler.load_profile_dir(prof_dir)
            except ValueError:
                return None
            metas = [
                d["tpusnap"]
                for d in docs
                if kind is None or d["tpusnap"].get("kind") == kind
            ]
            return _profiler.merge_metas(metas) if metas else None

        def _diff_summary(meta_a, meta_b, top=5):
            """Compact top-regressed/improved frame rows for aux."""
            if meta_a is None or meta_b is None:
                return None
            diff = _profiler.diff_profiles(meta_a, meta_b, top=top)
            return {
                "delta_oncpu_s": diff["delta_oncpu_s"],
                "top_regressed": diff["top_regressed"],
                "top_improved": diff["top_improved"],
            }

        # --- --direct-io A/B: the same native save through the direct-I/O
        # ladder (io_uring / O_DIRECT pwrite / buffered fallback) vs the
        # buffered leg just measured.  Byte identity asserted against the
        # buffered native leg — direct I/O changes the submission path,
        # never the bytes.
        direct_io_probe = None
        if "--direct-io" in argv:
            from torchsnapshot_tpu.native_io import NativeFileIO as _NIO

            ab_direct_root = os.path.join(workdir, "ab_direct")
            ab_prof_direct = os.path.join(workdir, "ab_prof_direct")
            with _kn.override_direct_io(True):
                leg_direct = _ab_leg(
                    ab_direct_root, True, profile_dir=ab_prof_direct
                )
                _nio = _NIO.maybe_create()
                dio_mode = _nio.direct_io_mode() if _nio is not None else 0
            if _nio is not None:
                _nio.configure_direct_io(False)
            direct_identical = _ab_dir_digest(ab_native_root) == _ab_dir_digest(
                ab_direct_root
            )
            shutil.rmtree(ab_direct_root, ignore_errors=True)
            direct_io_probe = {
                "mode": {0: "off", 1: "io_uring", 2: "odirect", 3: "buffered"}.get(
                    dio_mode, str(dio_mode)
                ),
                "direct": leg_direct,
                "buffered_save_s": leg_native["save_s"],
                "buffered_restore_s": leg_native["restore_s"],
                "bytes_identical": direct_identical,
                "save_wall_ratio_buffered_over_direct": round(
                    leg_native["save_s"] / leg_direct["save_s"], 2
                )
                if leg_direct["save_s"]
                else None,
                # Differential profile buffered (A) -> direct (B): which
                # frames the submission-path change moves.
                "profile_diff": _diff_summary(
                    _leg_profile_meta(ab_prof_native),
                    _leg_profile_meta(ab_prof_direct),
                ),
            }
            log(
                f"direct-io A/B: mode={direct_io_probe['mode']}, save "
                f"{leg_direct['save_s']}s direct vs {leg_native['save_s']}s "
                f"buffered; bytes identical: {direct_identical}"
            )
        shutil.rmtree(ab_native_root, ignore_errors=True)
        shutil.rmtree(ab_py_root, ignore_errors=True)
        native_ab_probe = {
            "state_bytes": ab_logical,
            "native": leg_native,
            "fallback": leg_py,
            "bytes_identical": identical,
            # The acceptance story: byte-identical output, wall speedups,
            # and the write+checksum phase thread-seconds the fused call
            # eliminates (per byte — the ratio-form cpu_s/wall is reported
            # per leg above but conflates concurrency with cost: a mode
            # driving MORE parallel calls per wall second reads "worse" on
            # it while finishing sooner).
            "save_wall_speedup": round(
                leg_py["save_s"] / leg_native["save_s"], 2
            ),
            "restore_wall_speedup": round(
                leg_py["restore_s"] / leg_native["restore_s"], 2
            ),
            "write_checksum_cpu_s_per_gb": {
                "native": round(
                    leg_native["write_checksum_cpu_s"] / (ab_logical / 1e9), 3
                ),
                "fallback": round(
                    leg_py["write_checksum_cpu_s"] / (ab_logical / 1e9), 3
                ),
            },
        }
        log(
            f"native A/B probe ({ab_logical / 1e9:.2f} GB): save "
            f"{leg_native['save_s']}s native vs {leg_py['save_s']}s fallback "
            f"({native_ab_probe['save_wall_speedup']}x), restore "
            f"{leg_native['restore_s']}s vs {leg_py['restore_s']}s "
            f"({native_ab_probe['restore_wall_speedup']}x); write+checksum "
            f"thread-s/GB {native_ab_probe['write_checksum_cpu_s_per_gb']}; "
            f"proc cpu save {leg_native['save_proc_cpu_s']}s vs "
            f"{leg_py['save_proc_cpu_s']}s; bytes identical: {identical}"
        )
        if direct_io_probe is not None:
            native_ab_probe["direct_io_probe"] = direct_io_probe

        # --- continuous-profiler probe: the A/B differential profile
        # (native A -> fallback B names the checksum/decode frames the
        # native plane eliminates) plus the sampler's own calibrated
        # overhead and attribution health, banked as profiler_probe and
        # gated by tools/bench_trajectory.py (profiler_overhead_pct).
        meta_native = _leg_profile_meta(ab_prof_native)
        meta_py = _leg_profile_meta(ab_prof_py)
        native_ab_probe["profile_diff"] = _diff_summary(meta_native, meta_py)
        if native_ab_probe["profile_diff"] is not None:
            log(
                "A/B differential profile (native -> fallback): "
                f"delta on-CPU "
                f"{native_ab_probe['profile_diff']['delta_oncpu_s']}s; "
                "top regressed "
                + ", ".join(
                    f"{r['frame']} {r['delta_s']:+.2f}s"
                    for r in native_ab_probe["profile_diff"]["top_regressed"][:3]
                )
            )
        prof_cal = _profiler.calibrated_overhead_s(samples=200)
        prof_hz = _kn.get_profile_hz() or 99.0
        # Overhead as % of op wall is wall-independent at a fixed rate:
        # per-tick cost x ticks/second.  Floored so the trajectory series
        # never banks a hard 0 (which would read as a missing value).
        prof_overhead_pct = max(prof_cal["per_tick_s"] * prof_hz * 100, 1e-4)
        meta_restore = _leg_profile_meta(ab_prof_native, kind="restore")
        restore_attr = None
        if meta_restore is not None and leg_native["restore_proc_cpu_s"]:
            tagged_oncpu_s = (
                meta_restore["oncpu_samples"] - meta_restore["untagged_oncpu"]
            ) * (meta_restore.get("weight_s") or 0.0)
            restore_attr = round(
                tagged_oncpu_s / leg_native["restore_proc_cpu_s"], 4
            )
        profiler_probe = {
            "hz": prof_hz,
            "per_tick_s": round(prof_cal["per_tick_s"], 9),
            "overhead_pct": round(prof_overhead_pct, 4),
            # THE acceptance bar: sampling at the default rate must cost
            # less than 1% of any op it profiles.
            "overhead_below_1pct": prof_overhead_pct < 1.0,
            "samples_total": meta_native["samples_total"]
            if meta_native
            else 0,
            "untagged_oncpu_share": round(
                meta_native["untagged_oncpu"] / meta_native["oncpu_samples"],
                4,
            )
            if meta_native and meta_native["oncpu_samples"]
            else None,
            # Share of the restore leg's getrusage process CPU landing in
            # named (phase, frame) buckets (acceptance: >= 0.8).
            "restore_cpu_attribution": restore_attr,
        }
        _PARTIAL["banked"]["sync"]["profiler_probe"] = profiler_probe
        log(
            f"profiler probe: {prof_cal['per_tick_s'] * 1e6:.1f} us/tick @ "
            f"{prof_hz:g} Hz -> {prof_overhead_pct:.3f}% of wall "
            f"(below_1pct={profiler_probe['overhead_below_1pct']}); "
            f"untagged on-CPU share "
            f"{profiler_probe['untagged_oncpu_share']}; restore CPU "
            f"attribution {restore_attr}"
        )
        shutil.rmtree(ab_prof_native, ignore_errors=True)
        shutil.rmtree(ab_prof_py, ignore_errors=True)
        shutil.rmtree(os.path.join(workdir, "ab_prof_direct"), ignore_errors=True)

        # --- compressed leg: the requested codec (zstd) through the native
        # encode-into-frame path vs TPUSNAP_NATIVE=0 resolution.  Per-leg
        # codec resolution is reported — the fallback leg may resolve to
        # the wheel or degrade to raw, which is exactly the story this leg
        # exists to tell — and byte identity is NOT asserted across legs
        # (raw-vs-compressed frames differ); decode equality is.
        _PARTIAL["phase"] = "native_ab_compressed"
        from torchsnapshot_tpu import compression as _ab_compression

        comp_requested = "zstd"
        comp_arrays = {
            # float32 in [0,1): compressible exponent structure, the same
            # character as real model weights (random uint8 would measure
            # the incompressible-store path instead).
            f"c{i}": np.random.RandomState(200 + i)
            .rand(per_ab // 4)
            .astype(np.float32)
            for i in range(n_ab)
        }
        comp_logical = sum(a.nbytes for a in comp_arrays.values())

        def _comp_leg(root, native_on):
            shutil.rmtree(root, ignore_errors=True)
            with _kn.override_native(native_on):
                resolved = _ab_compression.resolve(comp_requested)
                with _kn.override_compression(comp_requested):
                    _drain_writeback()
                    phase_stats.reset()
                    t0 = time.monotonic()
                    snap = Snapshot.take(
                        root, {"m": StateDict(dict(comp_arrays))}
                    )
                    comp_save_s = time.monotonic() - t0
                    ph = phase_stats.snapshot()
            nbytes = _dir_bytes(root)
            return snap, {
                "codec_resolved": resolved,
                "codec_downgraded": resolved != comp_requested,
                "save_s": round(comp_save_s, 3),
                "bytes_written": nbytes,
                "ratio": round(comp_logical / nbytes, 3) if nbytes else None,
                "effective_gbps": round(comp_logical / 1e9 / comp_save_s, 3),
                "phases": _phases_brief(ph),
            }

        ab_comp_native_root = os.path.join(workdir, "ab_comp_native")
        ab_comp_py_root = os.path.join(workdir, "ab_comp_fallback")
        _comp_leg(os.path.join(workdir, "ab_comp_warm"), True)  # warm pass
        shutil.rmtree(os.path.join(workdir, "ab_comp_warm"), ignore_errors=True)
        snap_comp_native, comp_native = _comp_leg(ab_comp_native_root, True)
        snap_comp_py, comp_py = _comp_leg(ab_comp_py_root, False)
        decode_equal = True
        for snap in (snap_comp_native, snap_comp_py):
            dstc = {
                "m": StateDict(
                    {k: np.zeros_like(v) for k, v in comp_arrays.items()}
                )
            }
            snap.restore(dstc)
            for k, v in comp_arrays.items():
                if not np.array_equal(np.asarray(dstc["m"][k]), v):
                    decode_equal = False
        shutil.rmtree(ab_comp_native_root, ignore_errors=True)
        shutil.rmtree(ab_comp_py_root, ignore_errors=True)
        native_ab_probe["compressed"] = {
            "requested": comp_requested,
            "state_bytes": comp_logical,
            "native": comp_native,
            "fallback": comp_py,
            "decode_equal": decode_equal,
            "effective_gbps_speedup": round(
                comp_native["effective_gbps"] / comp_py["effective_gbps"], 2
            )
            if comp_py["effective_gbps"]
            else None,
        }
        log(
            f"compressed A/B ({comp_logical / 1e9:.2f} GB, requested "
            f"{comp_requested}): native resolved "
            f"{comp_native['codec_resolved']} at "
            f"{comp_native['effective_gbps']} GB/s effective (ratio "
            f"{comp_native['ratio']}x), fallback resolved "
            f"{comp_py['codec_resolved']} at {comp_py['effective_gbps']} "
            f"GB/s; decode equal: {decode_equal}"
        )

        # --- batched-dispatch leg: a thousand-leaf state, one file per
        # leaf (slab batching off), TPUSNAP_NATIVE_BATCH on vs off — the
        # per-payload dispatch overhead story.
        _PARTIAL["phase"] = "native_ab_batch"
        n_small = int(os.environ.get("BENCH_AB_BATCH_LEAVES", "1000"))
        small_leaf_bytes = 64 << 10
        small_arrays = {
            f"s{i}": np.frombuffer(
                np.random.RandomState(i).bytes(small_leaf_bytes), np.uint8
            ).copy()
            for i in range(n_small)
        }

        def _batch_leg(root, batch):
            shutil.rmtree(root, ignore_errors=True)
            with _kn.override_env(_kn.DISABLE_BATCHING_ENV_VAR, "1"):
                with _kn.override_native_batch(batch):
                    _drain_writeback()
                    phase_stats.reset()
                    c0, t0 = _proc_cpu_s(), time.monotonic()
                    Snapshot.take(root, {"m": StateDict(dict(small_arrays))})
                    return (
                        round(time.monotonic() - t0, 3),
                        round(_proc_cpu_s() - c0, 3),
                    )

        _batch_leg(os.path.join(workdir, "ab_batch_warm"), 16)  # warm pass
        shutil.rmtree(os.path.join(workdir, "ab_batch_warm"), ignore_errors=True)
        batch_root = os.path.join(workdir, "ab_batch_on")
        single_root = os.path.join(workdir, "ab_batch_off")
        # Median of 3 alternating trials per leg: per-file syscall latency
        # on shared hosts is noisy enough that a single sample can invert
        # the verdict (observed: 1.09x and 0.76x CPU from consecutive
        # runs) — the same best-of-N discipline the round-2 verdict forced
        # on the sync/async sections.
        import statistics as _stats

        batch_trials, single_trials = [], []
        for _trial in range(3):
            batch_trials.append(_batch_leg(batch_root, 16))
            single_trials.append(_batch_leg(single_root, 0))
        batched_save_s = _stats.median(t[0] for t in batch_trials)
        batched_cpu_s = _stats.median(t[1] for t in batch_trials)
        single_save_s = _stats.median(t[0] for t in single_trials)
        single_cpu_s = _stats.median(t[1] for t in single_trials)
        batch_identical = _ab_dir_digest(batch_root) == _ab_dir_digest(
            single_root
        )
        shutil.rmtree(batch_root, ignore_errors=True)
        shutil.rmtree(single_root, ignore_errors=True)
        native_ab_probe["batch_probe"] = {
            "leaves": n_small,
            "leaf_bytes": small_leaf_bytes,
            "batched_save_s": batched_save_s,
            "single_save_s": single_save_s,
            # THE dispatch-overhead metric: real process CPU (getrusage,
            # all threads) per payload.  Wall can tie on hosts where the
            # filesystem round-trip is the bottleneck (this sandbox's v9fs)
            # while the per-payload FFI/pool-handshake CPU still drops —
            # CPU that a storage-bound host returns to training threads
            # and a fast-NVMe host converts to wall.
            "per_payload_cpu_us": {
                "batched": round(batched_cpu_s / n_small * 1e6, 1),
                "single": round(single_cpu_s / n_small * 1e6, 1),
            },
            "per_payload_wall_us": {
                "batched": round(batched_save_s / n_small * 1e6, 1),
                "single": round(single_save_s / n_small * 1e6, 1),
            },
            "bytes_identical": batch_identical,
            "cpu_speedup": round(single_cpu_s / batched_cpu_s, 2)
            if batched_cpu_s
            else None,
            "wall_speedup": round(single_save_s / batched_save_s, 2)
            if batched_save_s
            else None,
            "trials": {
                "batched": batch_trials,
                "single": single_trials,
            },
        }
        log(
            f"batched dispatch ({n_small} x {small_leaf_bytes >> 10} KiB "
            f"leaves): per-payload CPU "
            f"{native_ab_probe['batch_probe']['per_payload_cpu_us']} us "
            f"({native_ab_probe['batch_probe']['cpu_speedup']}x), wall "
            f"{batched_save_s}s batched vs {single_save_s}s single-call; "
            f"bytes identical: {batch_identical}"
        )
        _PARTIAL["banked"]["sync"]["native_ab_probe"] = native_ab_probe

    # --- async save: training-blocked time, best of N ---
    # Round-2 verdict: a single async run recorded 11.87 s total vs 0.23 s
    # best-of-3 sync — cold-start apples vs warm oranges.  Async gets the
    # same best-of-N treatment (fresh arrays per attempt: jax caches host
    # copies, which would fake the staging cost), with per-attempt
    # (stall, total) pairs and phase attribution.  With device-side staging
    # (device_staging.py, round-4 feature) the stall is the on-device copy
    # only; the one-time jit of that copy is warmed untimed below so the
    # stall number measures the steady-state training interruption.
    _PARTIAL["phase"] = "async_warm"
    from torchsnapshot_tpu import device_staging

    probe_flat = {f"model/w{i}": a for i, a in enumerate(arrays)}
    bench_staging_mode = device_staging.resolve_mode(probe_flat)
    if bench_staging_mode != "host":
        copied, warm_stats = device_staging.stage_app_state(
            probe_flat, bench_staging_mode
        )
        del copied
        bench_staging_mode = warm_stats["mode"]
        log(
            f"async staging mode: {bench_staging_mode} "
            f"(warm copy {warm_stats['copy_s'] * 1e3:.0f}ms for "
            f"{warm_stats['copy_bytes'] / 1e9:.2f}GB)"
        )

    async_attempts = []
    async_phases = {}
    best_async_total_s = float("inf")
    stall_s = 0.0
    arrays2 = app_state2 = pending = None
    for attempt in range(attempts):
        _PARTIAL["phase"] = f"async_save[{attempt + 1}/{attempts}]"
        # Drop the previous attempt's arrays BEFORE allocating fresh ones:
        # holding both alongside the original state would peak at ~3x the
        # state size in device memory and OOM small-HBM chips.
        arrays2 = app_state2 = pending = None
        arrays2 = jax.block_until_ready(make(jax.random.key(100 + attempt)))
        app_state2 = {
            "model": StateDict({f"w{i}": a for i, a in enumerate(arrays2)})
        }
        async_path = os.path.join(workdir, "snap_async")
        shutil.rmtree(async_path, ignore_errors=True)
        _drain_writeback()
        phase_stats.reset()
        begin = time.monotonic()
        pending = Snapshot.async_take(async_path, app_state2)
        attempt_stall_s = time.monotonic() - begin
        bench_staging_mode = pending.staging_mode
        pending.wait()
        attempt_total_s = time.monotonic() - begin
        async_attempts.append(
            {"stall_s": round(attempt_stall_s, 3), "total_s": round(attempt_total_s, 2)}
        )
        if attempt_total_s < best_async_total_s:
            best_async_total_s = attempt_total_s
            stall_s = attempt_stall_s
            async_phases = phase_stats.snapshot()
    async_total_s = best_async_total_s
    async_d2h_s = async_phases.get("d2h", {}).get("wall", 0.0)
    log(
        f"async save: blocked {stall_s:.3f}s of {async_total_s:.2f}s total "
        f"(staging_mode={bench_staging_mode}; background d2h {async_d2h_s:.2f}s"
        f" wall; attempts: {async_attempts})"
    )
    _PARTIAL.setdefault("banked", {})["async"] = {
        "async_attempts": async_attempts,
        "async_staging_mode": bench_staging_mode,
        "async_stall_s": round(stall_s, 3),
    }

    # --- restore ---
    dst = {
        "model": StateDict(
            {f"w{i}": jnp.zeros((rows, dim), jnp.bfloat16) for i in range(n_arrays)}
        )
    }
    restore_attempts_s = []
    restore_attempt_phases = []
    restore_attempt_coverage = []
    restore_phases = {}
    best_restore_s = float("inf")
    for attempt in range(attempts):
        _PARTIAL["phase"] = f"restore[{attempt + 1}/{attempts}]"
        _drain_writeback()
        phase_stats.reset()
        begin = time.monotonic()
        snapshot.restore(dst)
        # restore() now drains H2D landings itself (H2DBatcher.drain, timed
        # as h2d_land); this residual sync should read ~0 and is timed so
        # any regression shows up as a phase, not as unattributed wall.
        with phase_stats.timed("post_restore_sync"):
            jax.block_until_ready(list(dst["model"].values()))
        elapsed = time.monotonic() - begin
        restore_attempts_s.append(round(elapsed, 2))
        restore_attempt_phases.append(_phases_brief(phase_stats.snapshot()))
        restore_attempt_coverage.append(
            round(phase_stats.attributed_wall_s() / elapsed, 3)
        )
        if elapsed < best_restore_s:
            best_restore_s = elapsed
            restore_phases = phase_stats.snapshot()
    restore_s = min(restore_attempts_s)
    log(
        f"restore: {restore_s:.2f}s -> {actual_bytes / 1e9 / restore_s:.2f} "
        f"GB/s (runs: {restore_attempts_s})"
    )
    log(f"  restore phases (best attempt): {phase_stats.format_line(restore_phases)}")
    _PARTIAL.setdefault("banked", {})["restore"] = {
        "restore_attempts_s": restore_attempts_s,
        "restore_phases": _phases_brief(restore_phases),
        "restore_attempt_coverage": restore_attempt_coverage,
    }
    # --- serve probe (--serve N): fleet-scale concurrent-restore economics ---
    # N worker PROCESSES restore the same fs snapshot concurrently through
    # the shared host chunk cache (cache.py, TPUSNAP_CACHE_DIR): aggregate
    # GB/s, per-worker p50/p99 restore wall, cache hit ratio, and
    # bytes-from-origin vs bytes-from-cache — the ROADMAP item 2 scenario
    # no earlier benchmark covered.  Host-side state on purpose (serving
    # is a storage-layer story); a 1-worker uncached leg first gives the
    # single-restore baseline the aggregate is judged against.
    serve_probe = None
    if "--serve" in argv:
        import subprocess

        idx = argv.index("--serve")
        if idx + 1 >= len(argv):
            raise SystemExit("--serve requires a worker count")
        n_serve = max(1, int(argv[idx + 1]))
        _PARTIAL["phase"] = "serve_probe"
        serve_root = os.path.join(workdir, "serve")
        shutil.rmtree(serve_root, ignore_errors=True)
        serve_mb = int(os.environ.get("BENCH_SERVE_MB", "512"))
        # 4 leaves so each clears the slab threshold (128 MB at the default
        # 512 MB state): standalone entries take the read-into-place path,
        # which is what a serving fleet would tune for anyway.
        n_serve_leaves = 4
        serve_leaf_bytes = max(1 << 20, (serve_mb << 20) // n_serve_leaves)
        serve_state = {
            "m": StateDict(
                {
                    f"w{i}": np.frombuffer(
                        np.random.RandomState(200 + i).bytes(
                            serve_leaf_bytes
                        ),
                        np.uint8,
                    ).copy()
                    for i in range(n_serve_leaves)
                }
            )
        }
        serve_snap = os.path.join(serve_root, "snap")
        Snapshot.take(serve_snap, serve_state)
        serve_logical = n_serve_leaves * serve_leaf_bytes
        # Fleet telemetry spool at the conventional <root>/telemetry/live:
        # every worker publishes live entries the probe aggregates after
        # each round — the acceptance check that `tpusnap top` sees all N
        # workers, totals match, and telemetry costs <1% of op wall.
        fleet_spool = os.path.join(serve_snap, "telemetry", "live")

        def _run_serve_workers(n, cache_dir):
            env = dict(os.environ)
            # This process holds the chip, and a chip belongs to one process:
            # the workers restore to host memory and must stay on the CPU.
            env["JAX_PLATFORMS"] = "cpu"
            # Launcher-side child-env exports: the workers read them back
            # through knobs accessors.
            if cache_dir:
                env["TPUSNAP_CACHE_DIR"] = cache_dir
            else:
                env.pop("TPUSNAP_CACHE_DIR", None)
            env["TPUSNAP_FLEET_TELEMETRY"] = fleet_spool
            env["TPUSNAP_FLEET_TELEMETRY_INTERVAL_S"] = "0.2"
            env["TPUSNAP_FLEET_TELEMETRY_STALE_S"] = "600"
            procs = [
                subprocess.Popen(
                    [
                        sys.executable,
                        os.path.abspath(__file__),
                        "--serve-worker",
                        serve_snap,
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                    env=env,
                )
                for _ in range(n)
            ]
            docs = []
            for proc in procs:
                out, err = proc.communicate(
                    timeout=max(_watchdog_remaining_s() - 10, 60)
                )
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"serve worker failed (rc {proc.returncode}): "
                        f"{err.strip().splitlines()[-1:] or out}"
                    )
                docs.append(json.loads(out.strip().splitlines()[-1]))
            return docs

        def _round_stats(docs):
            span_s = max(
                max(d["end"] for d in docs) - min(d["start"] for d in docs),
                1e-6,
            )
            walls = sorted(d["wall_s"] for d in docs)
            total = sum(d["bytes"] for d in docs)
            origin = sum(d["miss_bytes"] for d in docs)
            hit = sum(d["hit_bytes"] for d in docs)
            return {
                "aggregate_gbps": round(total / 1e9 / span_s, 3),
                "worker_wall_p50_s": walls[len(walls) // 2],
                "worker_wall_p99_s": walls[
                    min(len(walls) - 1, round(0.99 * (len(walls) - 1)))
                ],
                "worker_walls_s": walls,
                "bytes_from_origin": origin,
                "bytes_from_cache": hit,
                "cache_hit_ratio": round(
                    hit / max(hit + origin, 1), 4
                ),
            }

        _drain_writeback()
        baseline = _run_serve_workers(1, None)[0]
        single_gbps = baseline["bytes"] / 1e9 / baseline["wall_s"]
        # The reference restore this scenario is judged against: the
        # BENCH_r07-style device restore measured by THIS run's restore
        # section (banked r07: 0.70 GB/s).
        r07_style_gbps = actual_bytes / 1e9 / restore_s
        serve_cache_dir = os.path.join(serve_root, "cache")
        # Round 1 — COLD host: N workers race one empty cache.  Origin
        # traffic must stay ~one snapshot (per-key single-flight).
        _drain_writeback()
        cold_docs = _run_serve_workers(n_serve, serve_cache_dir)
        cold = _round_stats(cold_docs)
        # Round 2 — WARM host: the steady serving state every worker after
        # the first cohort sees (the fleet scenario is thousands of pulls).
        warm_docs = _run_serve_workers(n_serve, serve_cache_dir)
        warm = _round_stats(warm_docs)

        # Round 3 — MULTI-HOST peer distribution: H simulated hosts with
        # SEPARATE cache dirs and one shared origin.  One seed host pulls
        # from origin and runs `tpusnap serve --daemon`; every later host
        # pulls peer-first (TPUSNAP_PEER_FETCH).  The acceptance pair:
        # total origin traffic stays ~one snapshot regardless of host
        # count, while AGGREGATE restore bandwidth scales with hosts —
        # the fan-out a shared-cache single host cannot give.
        from torchsnapshot_tpu import knobs as _peer_knobs

        n_hosts = max(3, min(n_serve, 6))
        peer_root = os.path.join(serve_root, "peer")
        peer_snap = os.path.join(peer_root, "snap")
        # CAS layout is what makes chunks digest-addressed (the peer
        # protocol's unit); the serving snapshot above is layout-default.
        with _peer_knobs.override_cas(True):
            Snapshot.take(peer_snap, serve_state)
        peer_kv = os.path.join(peer_root, "kv")
        peer_trace_dir = os.path.join(peer_root, "trace")

        def _peer_env(host_idx, peer_fetch, seed_warm=False):
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["TPUSNAP_CACHE_DIR"] = os.path.join(
                peer_root, f"host{host_idx}"
            )
            env["TPUSNAP_STORE_PATH"] = peer_kv
            env["TPUSNAP_FAULTS"] = "none"  # pure per-host origin meter
            # Serving-plane tracing ON for the whole peer round (client
            # peer_fetch spans, daemon peerd_handle spans + access logs):
            # the overhead proof below runs against real traced traffic.
            env["TPUSNAP_TRACE_DIR"] = peer_trace_dir
            env["TPUSNAP_PEER_FETCH"] = "1" if peer_fetch else "0"
            # Large whole-slab chunks over GIL-shared loopback can stall a
            # socket read past the 5 s default on a starved box; a timed-out
            # fetch silently falls back to origin and the probe reads as
            # "peer tier off".  The probe measures distribution economics,
            # not timeout tuning — give transfers a generous ceiling.
            env.setdefault("TPUSNAP_PEER_TIMEOUT_S", "60")
            if seed_warm:
                env["BENCH_SERVE_SEED_WARM"] = "1"
            else:
                env.pop("BENCH_SERVE_SEED_WARM", None)
            env.pop("TPUSNAP_FLEET_TELEMETRY", None)
            return env

        def _run_peer_hosts(host_indices, peer_fetch, seed_warm=False):
            procs = [
                subprocess.Popen(
                    [
                        sys.executable,
                        os.path.abspath(__file__),
                        "--serve-worker",
                        peer_snap,
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                    env=_peer_env(i, peer_fetch, seed_warm),
                )
                for i in host_indices
            ]
            docs = []
            for proc in procs:
                out, err = proc.communicate(
                    timeout=max(_watchdog_remaining_s() - 10, 60)
                )
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"peer host worker failed (rc {proc.returncode}): "
                        f"{err.strip().splitlines()[-1:] or out}"
                    )
                docs.append(json.loads(out.strip().splitlines()[-1]))
            return docs

        def _start_daemon(host_idx):
            proc = subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "torchsnapshot_tpu",
                    "serve",
                    peer_snap,
                    "--daemon",
                    "--advertise",
                    "127.0.0.1",
                    "--cache-dir",
                    os.path.join(peer_root, f"host{host_idx}"),
                ],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=_peer_env(host_idx, peer_fetch=False),
            )
            line = proc.stdout.readline()
            if "listening on" not in line:
                proc.terminate()
                raise RuntimeError(f"peer daemon failed to start: {line!r}")
            return proc

        daemons = []
        try:
            # Seed host 0: the ONE origin pull — a part-wise warm through
            # the peer-aware stack (servable cas/ keys), then a restore
            # that hits the warmed cache.
            seed_doc = _run_peer_hosts([0], peer_fetch=True, seed_warm=True)[0]
            daemons.append(_start_daemon(0))
            # Single puller (host 1): the per-host peer-path baseline.
            single_doc = _run_peer_hosts([1], peer_fetch=True)[0]
            daemons.append(_start_daemon(1))
            # H hosts pull concurrently from the two seeded daemons.
            multi_docs = _run_peer_hosts(
                range(2, 2 + n_hosts), peer_fetch=True
            )
        finally:
            for proc in daemons:
                proc.terminate()
            for proc in daemons:
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()

        all_pull_docs = [single_doc] + multi_docs
        multi_span = max(
            max(d["end"] for d in multi_docs)
            - min(d["start"] for d in multi_docs),
            1e-6,
        )
        origin_total = seed_doc["miss_bytes"] + sum(
            d["miss_bytes"] for d in all_pull_docs
        )
        peer_bytes = sum(d["peer_hit_bytes"] for d in all_pull_docs)
        single_agg = single_doc["bytes"] / 1e9 / max(single_doc["wall_s"], 1e-6)
        multi_agg = sum(d["bytes"] for d in multi_docs) / 1e9 / multi_span
        multihost = {
            "hosts": 2 + n_hosts,
            "concurrent_pullers": n_hosts,
            "snapshot_bytes": serve_logical,
            "seed_origin_bytes": seed_doc["miss_bytes"],
            "origin_bytes_total": origin_total,
            "origin_amplification": round(origin_total / serve_logical, 3),
            "peer_bytes": peer_bytes,
            "peer_rejects": sum(d["peer_rejects"] for d in all_pull_docs),
            "single_puller_gbps": round(single_agg, 3),
            "aggregate_gbps": round(multi_agg, 3),
            "puller_walls_s": sorted(d["wall_s"] for d in multi_docs),
            # Acceptance: origin ~one snapshot at >=3 hosts, and the
            # concurrent fleet's aggregate beats one peer-path puller.
            "origin_bytes_near_snapshot_size": origin_total
            <= 1.25 * serve_logical,
            "aggregate_scales_with_hosts": multi_agg >= 1.3 * single_agg,
        }
        # Serving-plane tracing + peer-scoreboard overhead, measured the
        # same way as the fleet-telemetry budget: isolated per-unit cost x
        # units each traced worker performed, summed over the peer round
        # (the only round that ran with TPUSNAP_TRACE_DIR set) and held
        # against those workers' own op wall.
        traced_docs = [seed_doc] + all_pull_docs
        traced_wall = sum(d["wall_s"] for d in traced_docs)
        trace_overhead_s = sum(
            d.get("trace_overhead_s", 0.0) for d in traced_docs
        )
        scoreboard_overhead_s = sum(
            d.get("scoreboard_overhead_s", 0.0) for d in traced_docs
        )
        tracing_total_s = trace_overhead_s + scoreboard_overhead_s
        tracing_probe = {
            "trace_overhead_s": round(trace_overhead_s, 6),
            "trace_spans": sum(d.get("trace_spans", 0) for d in traced_docs),
            "scoreboard_overhead_s": round(scoreboard_overhead_s, 6),
            "scoreboard_updates": sum(
                d.get("scoreboard_updates", 0) for d in traced_docs
            ),
            "overhead_s": round(tracing_total_s, 6),
            "worker_wall_s": round(traced_wall, 4),
            "overhead_frac_of_wall": round(
                tracing_total_s / traced_wall, 6
            )
            if traced_wall
            else 0.0,
            "overhead_below_1pct": tracing_total_s < 0.01 * traced_wall,
        }
        log(
            f"multi-host peer probe ({multihost['hosts']} hosts, "
            f"{n_hosts} concurrent pullers): origin "
            f"{multihost['origin_amplification']}x snapshot, "
            f"{peer_bytes / 1e9:.2f} GB served peer-to-peer, aggregate "
            f"{multihost['aggregate_gbps']} GB/s vs single puller "
            f"{multihost['single_puller_gbps']} GB/s"
        )
        # Fleet-telemetry acceptance: the spool must carry one terminal
        # entry per worker process (baseline + cold + warm rounds), the
        # aggregated cache totals must equal the workers' own accounting,
        # and the metered publish overhead must stay <1% of op wall.
        from torchsnapshot_tpu.telemetry import fleet as tfleet

        fleet_entries = tfleet.collect(fleet_spool, stale_s=600.0, sweep=False)
        fleet_view = tfleet.aggregate(fleet_entries)
        all_docs = [baseline] + cold_docs + warm_docs
        worker_hit = sum(d["hit_bytes"] for d in all_docs)
        worker_miss = sum(d["miss_bytes"] for d in all_docs)
        worker_wall = sum(d["wall_s"] for d in all_docs)
        overhead_s = sum(d.get("telemetry_overhead_s", 0.0) for d in all_docs)
        overhead_raw_s = sum(
            d.get("telemetry_overhead_raw_s", 0.0) for d in all_docs
        )
        fleet_probe = {
            "spool_entries": fleet_view["n_entries"],
            "processes": fleet_view["n_processes"],
            "expected_processes": 1 + 2 * n_serve,
            "all_workers_seen": fleet_view["n_processes"] == 1 + 2 * n_serve,
            "cache_totals_match": (
                fleet_view["cache"]["hit_bytes"] == worker_hit
                and fleet_view["cache"]["miss_bytes"] == worker_miss
            ),
            "telemetry_overhead_s": round(overhead_s, 6),
            "telemetry_overhead_raw_s": round(overhead_raw_s, 6),
            "telemetry_publishes": sum(
                d.get("telemetry_publishes", 0) for d in all_docs
            ),
            "overhead_frac_of_wall": round(overhead_s / worker_wall, 6)
            if worker_wall
            else 0.0,
            "overhead_below_1pct": overhead_s < 0.01 * worker_wall,
        }
        serve_probe = {
            "fleet": fleet_probe,
            "tracing": tracing_probe,
            "multihost": multihost,
            "workers": n_serve,
            "snapshot_bytes": serve_logical,
            "single_restore_s": baseline["wall_s"],
            "single_restore_gbps": round(single_gbps, 3),
            "r07_style_restore_gbps": round(r07_style_gbps, 3),
            "cold": cold,
            "warm": warm,
            "origin_amplification": round(
                cold["bytes_from_origin"] / serve_logical, 3
            ),
            # THE acceptance pair: a cold fleet pulls the snapshot from
            # origin ~once (cache hit ratio >= (N-1)/N of logical bytes),
            # and the warm serving tier's aggregate beats 3x a single
            # BENCH_r07-style restore.
            "origin_bytes_near_snapshot_size": cold["bytes_from_origin"]
            <= 1.25 * serve_logical,
            "aggregate_at_least_3x_r07_restore": warm["aggregate_gbps"]
            >= 3 * r07_style_gbps,
        }
        log(
            f"serve probe ({n_serve} workers, "
            f"{serve_logical / 1e9:.2f} GB snapshot): cold aggregate "
            f"{cold['aggregate_gbps']} GB/s (origin "
            f"{serve_probe['origin_amplification']}x snapshot, hit ratio "
            f"{cold['cache_hit_ratio']}), warm aggregate "
            f"{warm['aggregate_gbps']} GB/s vs 3x r07-style restore "
            f"{3 * r07_style_gbps:.2f} GB/s (single uncached "
            f"{single_gbps:.2f}); warm walls p50 "
            f"{warm['worker_wall_p50_s']}s p99 {warm['worker_wall_p99_s']}s"
        )
        log(
            f"fleet telemetry: {fleet_probe['processes']} worker "
            f"process(es) in spool (expected "
            f"{fleet_probe['expected_processes']}), cache totals match: "
            f"{fleet_probe['cache_totals_match']}, overhead "
            f"{fleet_probe['telemetry_overhead_s']}s = "
            f"{100 * fleet_probe['overhead_frac_of_wall']:.3f}% of op wall "
            f"(<1%: {fleet_probe['overhead_below_1pct']})"
        )
        log(
            f"serving-plane tracing: {tracing_probe['trace_spans']} spans + "
            f"{tracing_probe['scoreboard_updates']} scoreboard updates cost "
            f"{tracing_probe['overhead_s']}s = "
            f"{100 * tracing_probe['overhead_frac_of_wall']:.3f}% of op "
            f"wall (<1%: {tracing_probe['overhead_below_1pct']})"
        )
        shutil.rmtree(serve_root, ignore_errors=True)
        _PARTIAL.setdefault("banked", {})["serve"] = serve_probe

    _PARTIAL["phase"] = "verify_and_report"

    # verify a sample
    np.testing.assert_array_equal(
        np.asarray(dst["model"]["w0"][:4]), np.asarray(arrays[0][:4])
    )

    if not os.environ.get("BENCH_DIR"):
        shutil.rmtree(workdir, ignore_errors=True)

    result = {
        "metric": "checkpoint_save_throughput_per_chip",
        "value": round(save_gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(save_gbps / BASELINE_GBPS, 3),
        "backend": _DEVICE["platform"],
        **_DEVICE,
        "aux": {
            "state_gib": round(gib, 2),
            "attempts": attempts,
            "bytes_written": bytes_written,
            "faults_spec": faults_spec,
            "telemetry_sidecar": telemetry_sidecar,
            "compression_probe": compression_probe,
            "compress_scale_probe": compress_scale_probe,
            "blackbox_probe": blackbox_probe,
            "cas_probe": cas_probe,
            "store_probe": store_probe,
            "journal_probe": journal_probe,
            "native_ab_probe": native_ab_probe,
            "profiler_probe": profiler_probe,
            "serve_probe": serve_probe,
            "sync_save_s": round(save_s, 2),
            "sync_save_worst_s": round(max(save_attempts_s), 2),
            "save_attempts_s": save_attempts_s,
            "save_drift_ratio": round(max(save_attempts_s) / min(save_attempts_s), 2),
            "save_drift_dominant_phase": _drift_dominant_phase(
                save_attempt_phases, save_attempts_s
            ),
            "save_attempt_coverage": save_attempt_coverage,
            "restore_attempts_s": restore_attempts_s,
            "async_stall_s": round(stall_s, 3),
            "async_stall_worst_s": round(
                max(a["stall_s"] for a in async_attempts), 3
            ),
            "async_total_s": round(async_total_s, 2),
            "async_attempts": async_attempts,
            "async_staging_mode": bench_staging_mode,
            # The north-star check (BASELINE.md: <2 s training stall):
            # stall ≤ max(2 s, 10% of sync save).
            "async_stall_target_met": stall_s <= max(2.0, 0.1 * save_s),
            "async_d2h_wall_s": round(async_d2h_s, 2),
            "async_phases": _phases_brief(async_phases),
            # The r4 open question: storage writes sharing the process with
            # the D2H drain ran 48% slower than sync writes (wall AND
            # thread-seconds up — CPU/memory-bandwidth contention between
            # the drain's host materialization and write syscalls on a
            # small host, not queueing).  Tracked here; it is only a
            # problem if async_total also exceeds the d2h wall materially,
            # since the pipeline is D2H-bound and the write stretch hides
            # under the drain.
            "async_fs_write_stretch": round(
                async_phases["fs_write"].get(
                    "wall", async_phases["fs_write"]["s"]
                )
                / save_phases["fs_write"].get(
                    "wall", save_phases["fs_write"]["s"]
                ),
                2,
            )
            if "fs_write" in async_phases and "fs_write" in save_phases
            else None,
            "restore_s": round(restore_s, 2),
            "restore_worst_s": round(max(restore_attempts_s), 2),
            "restore_drift_ratio": round(
                max(restore_attempts_s) / min(restore_attempts_s), 2
            ),
            "restore_drift_dominant_phase": _drift_dominant_phase(
                restore_attempt_phases, restore_attempts_s
            ),
            "restore_attempt_coverage": restore_attempt_coverage,
            "restore_gbps": round(actual_bytes / 1e9 / restore_s, 3),
            "raw_d2h_link_gbps": round(link_gbps, 3),
            "raw_d2h_aggregate_gbps": round(link_agg_gbps, 3),
            "raw_disk_write_gbps": round(disk_gbps, 3) if disk_gbps else None,
            "pipeline_efficiency_vs_link": round(save_gbps / link_ceiling_gbps, 3)
            if link_ceiling_gbps > 0
            else None,
            # The BASELINE north star: >= 90% of storage write bandwidth.
            "pipeline_efficiency_vs_disk": round(save_gbps / disk_gbps, 3)
            if disk_gbps
            else None,
            # Which hardware ceiling the save is actually limited by: where
            # the D2H link is the slower of the two, efficiency_vs_disk is
            # noise; where the disk is, THAT number is the north star.
            "binding_constraint": (
                None
                if not disk_gbps
                else "d2h_link"
                if link_ceiling_gbps < disk_gbps
                else "disk"
            ),
            "device": str(devices[0]),
            "save_phases": _phases_brief(save_phases),
            "save_attempt_phases": save_attempt_phases,
            "restore_phases": _phases_brief(restore_phases),
            "restore_attempt_phases": restore_attempt_phases,
            # Overlap evidence: per-phase thread-seconds summing past the
            # save wall means d2h/checksum/fs_write ran concurrently; the
            # per-phase wall numbers are the honest elapsed shares.
            "save_phase_cpu_sum_s": round(
                sum(v["s"] for v in save_phases.values()), 3
            ),
            "save_phase_overlap_s": round(
                max(0.0, sum(v["s"] for v in save_phases.values()) - save_s), 3
            ),
        },
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
