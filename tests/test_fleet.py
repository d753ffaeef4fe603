"""Fleet telemetry plane + barrier timing.

Covers telemetry/fleet.py (atomic spool publish, stale aging, collector
aggregation, merged Prometheus), the `tpusnap top` CLI, the
LinearBarrier barrier_wait phase + store-exchanged arrival stamps, the
cache single-flight wait metering (cache_wait phase / cache.wait event /
counter).  The multi-process aggregation test spawns
``tests/serve_worker.py``, so the spool sees real worker processes and
`top --json` totals are cross-checked against the per-worker `serve`
telemetry sidecars.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, knobs, phase_stats
from torchsnapshot_tpu.__main__ import main as cli_main
from torchsnapshot_tpu.dist_store import FileStore, LinearBarrier
from torchsnapshot_tpu.telemetry import fleet, metrics
from torchsnapshot_tpu.telemetry import monitor as tmonitor
from torchsnapshot_tpu.telemetry import sidecar as tsidecar

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE_WORKER = os.path.join(REPO_ROOT, "tests", "serve_worker.py")

OP = "feedc0dedeadbeef" * 2


# ---------------------------------------------------------------- publisher


def test_publish_collect_aggregate_roundtrip(tmp_path):
    """A monitored op publishes periodic + terminal entries; the collector
    sees one entry with terminal state and the aggregation folds it."""
    spool = str(tmp_path / "live")
    fleet.reset_process_totals()
    with knobs.override_fleet_telemetry(spool), \
            knobs.override_fleet_telemetry_interval_s(0.05):
        mon = tmonitor.op_started("take", OP, 0)
        time.sleep(0.25)
        tmonitor.op_finished(mon, success=True)
        entries = fleet.collect(spool)
    assert len(entries) == 1
    doc = entries[0]
    assert doc["kind"] == "take"
    assert doc["op_id"] == OP
    assert doc["op"]["done"] is True
    assert doc["op"]["success"] is True
    assert doc["proc"]["ops_done"] == 1
    assert doc["proc"]["overhead_s"] > 0  # self-metered
    view = fleet.aggregate(entries)
    assert view["n_entries"] == 1
    assert view["n_live"] == 0
    assert view["workers"][0]["state"] == "done"
    assert view["proc_totals"]["ops_done"] == 1


def test_terminal_fold_is_idempotent(tmp_path):
    """Double op_finished must not double-count process totals."""
    spool = str(tmp_path / "live")
    fleet.reset_process_totals()
    with knobs.override_fleet_telemetry(spool):
        mon = tmonitor.op_started("restore", OP, 0)
        tmonitor.op_finished(mon, success=True)
        fleet.publish(mon, final=True)  # a second terminal publish
    assert fleet.process_totals()["ops_done"] == 1


def test_stale_entries_age_out(tmp_path):
    """Stale-entry triage: a FINISHED op's stale entry is completion
    debris (skipped + swept); an IN-FLIGHT op's stale entry is the last
    sign of a worker that likely died mid-op — surfaced as a
    ``suspected-dead`` row with its last-seen age, excluded from the live
    set, and swept only past the longer horizon."""
    spool = tmp_path / "live"
    spool.mkdir()
    fresh = {
        "schema": 1,
        "host": "h",
        "pid": 1,
        "rank": 0,
        "kind": "take",
        "op_id": OP,
        "publish_time": time.time(),
        "op": {"done": False, "requests": {}, "bytes": {}},
        "proc": {},
        "metrics": [],
        "cache": {},
    }
    # Dead mid-op: stale but within the suspect window (60s > 30s bound).
    suspect = dict(fresh, pid=2, publish_time=time.time() - 60)
    # Finished then aged: completion debris, swept.
    done_stale = dict(
        fresh,
        pid=3,
        publish_time=time.time() - 60,
        op={"done": True, "requests": {}, "bytes": {}},
    )
    # Dead long ago: past the sweep horizon (9999 > 30 * 10), reclaimed.
    ancient = dict(fresh, pid=4, publish_time=time.time() - 9999)
    (spool / "h-1-take-rank0.fleet.json").write_text(json.dumps(fresh))
    (spool / "h-2-take-rank0.fleet.json").write_text(json.dumps(suspect))
    done_path = spool / "h-3-take-rank0.fleet.json"
    done_path.write_text(json.dumps(done_stale))
    ancient_path = spool / "h-4-take-rank0.fleet.json"
    ancient_path.write_text(json.dumps(ancient))
    (spool / "garbage.fleet.json").write_text("{torn")
    entries = fleet.collect(str(spool), stale_s=30.0)
    assert sorted(e["pid"] for e in entries) == [1, 2]
    assert not done_path.exists()  # completion debris swept
    assert not ancient_path.exists()  # past the suspect horizon: swept
    # Unreadable entries are skipped, never fatal, and never swept.
    assert (spool / "garbage.fleet.json").exists()

    view = fleet.aggregate(entries)
    assert view["n_suspected_dead"] == 1
    assert view["suspected_dead"][0]["worker"] == "h:2"
    assert view["suspected_dead"][0]["last_seen_s"] >= 59
    rows = {w["worker"]: w for w in view["workers"]}
    assert rows["h:2"]["state"] == "suspected-dead"
    # Suspected-dead workers never pollute the live set / stragglers.
    assert view["n_live"] == 1
    assert all(s["worker"] != "h:2" for s in view["stragglers"])
    # The rendered table carries the death callout.
    rendered = fleet.render(view, str(spool))
    assert "SUSPECTED DEAD: h:2" in rendered
    assert "suspected-dead" in rendered


def test_peer_stale_event_emitted_once(tmp_path):
    """One fleet.peer_stale event per death, not one per collect pass;
    the tpusnap_fleet_stale_peers gauge tracks the current count."""
    from torchsnapshot_tpu.event_handlers import (
        register_event_handler,
        unregister_event_handler,
    )
    from torchsnapshot_tpu.telemetry import metrics as tmetrics

    spool = tmp_path / "live"
    spool.mkdir()
    suspect = {
        "schema": 1,
        "host": "h",
        "pid": 9,
        "rank": 1,
        "kind": "async_take",
        "op_id": OP,
        "publish_time": time.time() - 60,
        "op": {"done": False, "requests": {}, "bytes": {}},
        "proc": {},
        "metrics": [],
        "cache": {},
    }
    (spool / "h-9-async_take-rank1.fleet.json").write_text(
        json.dumps(suspect)
    )
    events = []

    def capture(e):
        if e.name == "fleet.peer_stale":
            events.append(e)

    register_event_handler(capture)
    tmetrics.reset()
    try:
        with knobs.override_metrics(True):
            fleet.collect(str(spool), stale_s=30.0)
            fleet.collect(str(spool), stale_s=30.0)  # second pass: no dup
    finally:
        unregister_event_handler(capture)
    assert len(events) == 1, [e.metadata for e in events]
    assert events[0].metadata["worker"] == "h:9"
    assert events[0].metadata["kind"] == "async_take"
    assert events[0].metadata["last_seen_s"] >= 59
    assert (
        tmetrics.gauge("tpusnap_fleet_stale_peers").get() == 1.0
    )


def test_aggregate_counts_process_totals_once(tmp_path):
    """A process publishing several op kinds contributes its cumulative
    cache/proc counters once, while op-level bytes sum across entries."""
    now = time.time()

    def entry(kind, pid, bytes_written):
        return {
            "host": "h",
            "pid": pid,
            "rank": 0,
            "kind": kind,
            "op_id": OP,
            "publish_time": now,
            "op": {
                "done": False,
                "elapsed_s": 1.0,
                "requests": {"total": 4, "staged": 4, "written": 2},
                "bytes": {"staged": bytes_written, "written": bytes_written},
                "eta_s": 1.0,
            },
            "proc": {"ops_done": 3, "bytes_written": 100},
            "cache": {"hits": 1, "misses": 1, "hit_bytes": 10, "miss_bytes": 5},
            "metrics": [],
        }

    view = fleet.aggregate(
        [entry("restore", 1, 7), entry("read_object", 1, 9), entry("take", 2, 1)]
    )
    assert view["n_processes"] == 2
    assert view["cache"]["hit_bytes"] == 20  # pid1 once + pid2 once
    assert view["cache"]["origin_bytes"] == 10
    assert view["proc_totals"]["ops_done"] == 6
    assert view["op_totals"]["bytes_written"] == 17
    assert view["straggler"] is not None


def test_resolve_spool_prefers_conventional_subdir(tmp_path):
    root = tmp_path / "root"
    nested = root / "telemetry" / "live"
    nested.mkdir(parents=True)
    assert fleet.resolve_spool(str(root)) == str(nested)
    assert fleet.resolve_spool(str(nested)) == str(nested)
    with knobs.override_fleet_telemetry(str(nested)):
        assert fleet.resolve_spool(None) == str(nested)
    assert fleet.resolve_spool(str(tmp_path / "absent")) is None


# ------------------------------------------------------------------ top CLI


def _publish_one(spool, kind="restore"):
    fleet.reset_process_totals()
    with knobs.override_fleet_telemetry(spool):
        mon = tmonitor.op_started(kind, OP, 0, watchdog=False)
        tmonitor.op_finished(mon, success=True)


def test_top_json_one_shot(tmp_path, capsys):
    spool = str(tmp_path / "live")
    _publish_one(spool)
    assert cli_main(["top", spool, "--json"]) == 0
    view = json.loads(capsys.readouterr().out)
    assert view["n_entries"] == 1
    assert view["workers"][0]["kind"] == "restore"


def test_top_table_once_and_missing_spool(tmp_path, capsys):
    spool = str(tmp_path / "live")
    _publish_one(spool, kind="take")
    assert cli_main(["top", spool, "--once"]) == 0
    out = capsys.readouterr().out
    assert "tpusnap top" in out and "take" in out
    assert cli_main(["top", str(tmp_path / "nope")]) == 2


def test_top_prometheus_merges_worker_registries(tmp_path, capsys):
    """Entries embedding metrics dumps render as one exposition with
    per-worker labels plus the synthesized fleet gauges."""
    spool = str(tmp_path / "live")
    with knobs.override_metrics(True):
        metrics.reset()
        metrics.counter("tpusnap_test_total", "t").inc(3, backend="fs")
        _publish_one(spool)
        metrics.reset()
    assert cli_main(["top", spool, "--prometheus"]) == 0
    out = capsys.readouterr().out
    assert "tpusnap_fleet_workers 1" in out
    assert "tpusnap_test_total" in out
    assert 'worker="' in out
    assert "tpusnap_fleet_origin_bytes" in out


# ------------------------------------------- multi-process fleet aggregation


def _state(nbytes_per_leaf=1 << 19, leaves=4, seed=3):
    return {
        "m": StateDict(
            {
                f"w{i}": np.frombuffer(
                    np.random.RandomState(seed * 100 + i).bytes(
                        nbytes_per_leaf
                    ),
                    np.uint8,
                ).copy()
                for i in range(leaves)
            }
        )
    }


def test_multiprocess_fleet_aggregation(tmp_path, capsys):
    """The acceptance scenario: N serve workers publish into one
    spool; `top --json` reports all N worker processes and its aggregated
    cache totals equal the sums from the per-worker `serve` telemetry
    sidecars; stale aging then empties the view."""
    n = 2
    state = _state()
    snap_path = str(tmp_path / "root" / "step_1")
    Snapshot.take(snap_path, state)
    spool = os.path.join(snap_path, "telemetry", "live")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Launcher-side child-env exports (read back through knobs accessors).
    env["TPUSNAP_CACHE_DIR"] = str(tmp_path / "cache")
    env["TPUSNAP_FLEET_TELEMETRY"] = spool
    env["TPUSNAP_FLEET_TELEMETRY_INTERVAL_S"] = "0.1"
    env.pop("TPUSNAP_FAULTS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, SERVE_WORKER, snap_path],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
        )
        for _ in range(n)
    ]
    docs = []
    for proc in procs:
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err[-2000:]
        docs.append(json.loads(out.strip().splitlines()[-1]))

    assert cli_main(["top", snap_path, "--json", "--stale", "600"]) == 0
    view = json.loads(capsys.readouterr().out)
    assert view["n_processes"] == n, view
    assert all(w["kind"] == "serve" for w in view["workers"])
    assert all(w["done"] for w in view["workers"])

    # Cross-check: top's aggregated cache totals == per-worker sidecar sums
    # (both derive from each worker's process-cumulative cache counters).
    sidecar_dir = os.path.join(snap_path, "telemetry")
    serve_sidecars = [
        json.load(open(os.path.join(sidecar_dir, name)))
        for name in os.listdir(sidecar_dir)
        if name.startswith("serve-") and name.endswith(".json")
    ]
    assert len(serve_sidecars) == n
    assert view["cache"]["hit_bytes"] == sum(
        d["cache"]["hit_bytes"] for d in serve_sidecars
    )
    assert view["cache"]["miss_bytes"] == sum(
        d["cache"]["miss_bytes"] for d in serve_sidecars
    )
    # One shared cache: origin traffic ≈ one snapshot, and the fleet view's
    # origin-bytes headline says so.
    logical = sum(v.nbytes for v in state["m"].values())
    assert view["cache"]["origin_bytes"] <= 1.25 * logical
    # Telemetry self-metering made it into the worker records.
    assert all(d["telemetry_overhead_s"] >= 0 for d in docs)
    # The sidecars render (incl. the cache hit/miss split).
    assert cli_main(["stats", snap_path]) == 0
    out = capsys.readouterr().out
    assert "serve" in out and "cache=" in out

    # Stale aging: with an aggressive bound every entry ages out of the view.
    time.sleep(0.05)
    assert cli_main(["top", snap_path, "--json", "--stale", "0.001"]) == 0
    view = json.loads(capsys.readouterr().out)
    assert view["n_entries"] == 0


# ----------------------------------------------- barrier timestamps + phase


def test_linear_barrier_records_arrival_table_and_wait_phase(tmp_path):
    """Two 'ranks' over one FileStore: the straggler's late arrival shows
    in the exchanged arrival table, and the leader's blocking wait is
    metered as the barrier_wait phase."""
    store = FileStore(str(tmp_path))
    b0 = LinearBarrier(prefix="t", store=store, rank=0, world_size=2)
    b1 = LinearBarrier(prefix="t", store=store, rank=1, world_size=2)
    before = phase_stats.snapshot()

    def rank1():
        time.sleep(0.3)
        b1.arrive(timeout_s=30)
        b1.depart(timeout_s=30)

    t = threading.Thread(target=rank1)
    t.start()
    b0.arrive(timeout_s=30)  # leader blocks here ~0.3s for rank 1
    b0.depart(timeout_s=30)
    t.join()

    table = b0.arrival_table()
    assert set(table) == {0, 1}
    assert "arrive" in table[0] and "arrive" in table[1]
    assert table[1]["arrive"] - table[0]["arrive"] >= 0.2
    delta = phase_stats.delta(before)
    assert "barrier_wait" in delta
    assert delta["barrier_wait"]["s"] >= 0.2


def test_cache_wait_is_metered(tmp_path):
    """A reader parked on a held populate lock records the cache_wait
    phase, the cache.wait event, and tpusnap_cache_wait_seconds_total."""
    from torchsnapshot_tpu import cache as cache_mod
    from torchsnapshot_tpu import event_handlers

    state = _state(nbytes_per_leaf=1 << 16, leaves=1, seed=5)
    snap_path = str(tmp_path / "step_1")
    # Batching off: the leaf is a standalone payload, so the reader's
    # cache key (full object, no byte range) is exactly the one we hold
    # the populate lock for.
    with knobs.override_batching_disabled(True):
        snap = Snapshot.take(snap_path, state)
    md = snap.metadata
    location = cache_mod.payload_locations(md)[0][0]
    ns = cache_mod.snapshot_fingerprint(md)
    exact_key, _, _ = cache_mod.keys_for(ns, location, None)

    events = []
    handler = events.append
    event_handlers.register_event_handler(handler)
    try:
        with knobs.override_cache_dir(str(tmp_path / "cache")), \
                knobs.override_metrics(True):
            metrics.reset()
            store = cache_mod.CacheStore(str(tmp_path / "cache"))
            fd = store.try_acquire_populate_lock(exact_key)
            assert fd is not None
            before = phase_stats.snapshot()
            result = {}

            def read():
                result["value"] = snap.read_object("0/m/w0")

            t = threading.Thread(target=read)
            t.start()
            time.sleep(0.3)
            store.release_populate_lock(fd)
            t.join(timeout=60)
            assert "value" in result
            np.testing.assert_array_equal(
                np.asarray(result["value"]), state["m"]["w0"]
            )
            delta = phase_stats.delta(before)
            assert "cache_wait" in delta, delta
            assert delta["cache_wait"]["s"] >= 0.1
            assert (
                metrics.counter("tpusnap_cache_wait_seconds_total").get() > 0
            )
    finally:
        event_handlers.unregister_event_handler(handler)
        metrics.reset()
    assert any(e.name == "cache.wait" for e in events)


# ----------------------------------------------------- warm/serve sidecars


def test_warm_and_serve_cli_write_sidecars(tmp_path, capsys):
    state = _state(nbytes_per_leaf=1 << 16, leaves=2, seed=7)
    snap_path = str(tmp_path / "step_1")
    Snapshot.take(snap_path, state)
    with knobs.override_cache_dir(str(tmp_path / "cache")):
        assert cli_main(["warm", snap_path]) == 0
        assert cli_main(["serve", snap_path]) == 0
    capsys.readouterr()
    storage = None
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin

    storage = url_to_storage_plugin(snap_path)
    try:
        docs = tsidecar.read_all(storage)
    finally:
        storage.sync_close()
    actions = {d["action"] for d in docs}
    assert {"warm", "serve"} <= actions
    warm_doc = next(d for d in docs if d["action"] == "warm")
    assert warm_doc["bytes"] == sum(v.nbytes for v in state["m"].values())
    assert "cache" in warm_doc
    serve_doc = next(d for d in docs if d["action"] == "serve")
    res = serve_doc["residency"]
    assert res["resident"] == res["locations"] > 0
    # stats renders them (the satellite's render half).
    assert cli_main(["stats", snap_path]) == 0
    out = capsys.readouterr().out
    assert "warm" in out and "serve" in out
