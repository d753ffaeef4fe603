"""The serving worker three tests spawn as a fixture: one restore client.

Moved as it was from the ``bench.py`` of earlier rounds (its ``--serve-worker``
mode; the rest of that file is gone, git keeps it).  ``python
tests/serve_worker.py <snapshot path>`` materializes every app-state key of
the snapshot and prints one JSON line.  ``BENCH_SERVE_SEED_WARM`` keeps its
meaning: pre-fault the chunk set into the host cache before the restore.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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
    split.  Spawned by ``tests/test_serve.py`` and ``tests/test_fleet.py``
    — and usable standalone as a minimal serving client.

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


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit("usage: serve_worker.py <snapshot path>")
    raise SystemExit(_serve_worker(sys.argv[1]))
