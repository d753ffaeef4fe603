"""Checkpoint-serving read tier: shared host chunk cache + partial reads.

Covers cache.py (hit/miss/populate/verify/evict semantics, cross-process
single-flight), the plan-driven partial sharded reads (origin bytes track
the shard plan, not the entry size), the warm/serve CLI, and the
concurrent-restore serving scenario (2-worker fast smoke tier-1; the
8-worker soak is slow-marked).  Origin traffic is asserted through the
fault wrapper's read counters (``TPUSNAP_FAULTS=none`` = pure meter).
"""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, knobs
from torchsnapshot_tpu import cache as cache_mod
from torchsnapshot_tpu import faults
from torchsnapshot_tpu.__main__ import main
from torchsnapshot_tpu.io_types import ReadIO, StoragePlugin, WriteIO
from torchsnapshot_tpu.manager import SnapshotManager

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE_WORKER = os.path.join(REPO_ROOT, "tests", "serve_worker.py")


def _payload_read_bytes() -> int:
    """Origin bytes requested for payloads (metadata/sidecar reads excluded)."""
    return sum(
        nbytes
        for path, nbytes in faults.read_counters().items()
        if not path.rsplit("/", 1)[-1].startswith(".")
        and not path.startswith("telemetry/")
    )


def _state(nbytes_per_leaf=1 << 20, leaves=4, seed=0):
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


def _zeros_like(state):
    return {
        "m": StateDict(
            {k: np.zeros_like(v) for k, v in state["m"].items()}
        )
    }


def _cache_data_files(cache_dir):
    return [
        p
        for p in glob.glob(
            os.path.join(cache_dir, "objects", "**", "*"), recursive=True
        )
        if os.path.isfile(p)
        and not p.endswith((".meta", ".lock"))
        and ".tmp." not in p
    ]


# ------------------------------------------------------------- cache basics


def test_second_restore_served_from_cache(tmp_path):
    state = _state()
    snap = Snapshot.take(str(tmp_path / "root" / "step_1"), state)
    with knobs.override_cache_dir(str(tmp_path / "cache")), knobs.override_faults(
        "none"
    ):
        faults.reset_read_counters()
        dst = _zeros_like(state)
        snap.restore(dst)
        first_origin = _payload_read_bytes()
        assert first_origin > 0
        faults.reset_read_counters()
        dst2 = _zeros_like(state)
        snap.restore(dst2)
        second_origin = _payload_read_bytes()
    np.testing.assert_array_equal(
        np.asarray(dst2["m"]["w0"]), state["m"]["w0"]
    )
    # The whole payload set came from local cache the second time.
    assert second_origin == 0, (first_origin, second_origin)


def test_cache_metrics_and_sidecar(tmp_path):
    from torchsnapshot_tpu.telemetry import metrics, sidecar

    state = _state()
    path = str(tmp_path / "root" / "step_1")
    snap = Snapshot.take(path, state)
    metrics.reset()
    with knobs.override_cache_dir(str(tmp_path / "cache")), knobs.override_metrics(
        True
    ):
        snap.restore(_zeros_like(state))
        snap.restore(_zeros_like(state))
        assert metrics.counter("tpusnap_cache_misses_total").get() > 0
        assert metrics.counter("tpusnap_cache_hits_total").get() > 0
        # The restore sidecar records the hit/miss byte split.
        from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin

        storage = url_to_storage_plugin(path)
        try:
            docs = [
                d
                for d in sidecar.read_all(storage)
                if d.get("action") == "restore"
            ]
        finally:
            storage.sync_close()
        assert docs and "cache" in docs[0]
        assert docs[0]["cache"]["hits"] > 0
    metrics.reset()


class _CountingPlugin(StoragePlugin):
    """Origin meter for in-process single-flight tests."""

    def __init__(self, inner):
        self._inner = inner
        self.reads = 0
        self._lock = threading.Lock()

    async def read(self, read_io):
        with self._lock:
            self.reads += 1
        await self._inner.read(read_io)

    async def write(self, write_io):
        await self._inner.write(write_io)

    async def exists(self, path):
        return await self._inner.exists(path)

    async def list_dir(self, path):
        return await self._inner.list_dir(path)

    async def delete(self, path):
        await self._inner.delete(path)

    async def delete_dir(self, path):
        await self._inner.delete_dir(path)

    async def close(self):
        await self._inner.close()


def test_concurrent_populate_single_flight_and_untorn(tmp_path):
    """8 threads cold-read one key concurrently: the per-key populate lock
    single-flights the origin fetch (1 read, not 8) and every caller gets
    identical, untorn bytes."""
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    payload = np.random.RandomState(3).bytes(1 << 20)
    origin_dir = tmp_path / "origin"
    os.makedirs(origin_dir)
    with open(origin_dir / "chunk", "wb") as f:
        f.write(payload)
    counting = _CountingPlugin(FSStoragePlugin(root=str(origin_dir)))
    store = cache_mod.CacheStore(str(tmp_path / "cache"))
    plugin = cache_mod.CacheReaderPlugin(
        inner=counting, store=store, namespace="t"
    )
    results = [None] * 8
    errors = []

    def _reader(i):
        try:
            read_io = ReadIO(path="chunk")
            plugin.sync_read(read_io)
            results[i] = bytes(read_io.buf)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=_reader, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    plugin.sync_close()
    assert not errors
    assert all(r == payload for r in results)
    assert counting.reads == 1, counting.reads


def test_corrupt_cache_entry_detected_and_refetched(tmp_path):
    state = _state(leaves=1)
    snap = Snapshot.take(str(tmp_path / "root" / "step_1"), state)
    cache_dir = str(tmp_path / "cache")
    with knobs.override_cache_dir(cache_dir), knobs.override_faults("none"):
        snap.restore(_zeros_like(state))
        files = _cache_data_files(cache_dir)
        assert files
        # Corrupt every cached data file (keep sizes — a short file would
        # be caught by the cheaper length check).
        for path in files:
            with open(path, "r+b") as f:
                f.seek(8)
                f.write(b"\xde\xad\xbe\xef")
        faults.reset_read_counters()
        dst = _zeros_like(state)
        snap.restore(dst)
        refetched = _payload_read_bytes()
    # The corruption was detected, origin re-fetched, and the restore is
    # byte-correct regardless.
    np.testing.assert_array_equal(
        np.asarray(dst["m"]["w0"]), state["m"]["w0"]
    )
    assert refetched > 0


def test_eviction_lru_bound_and_open_fd_semantics(tmp_path):
    store = cache_mod.CacheStore(str(tmp_path / "cache"), max_bytes=3 << 20)
    payloads = {
        f"k{i}": np.random.RandomState(i).bytes(1 << 20) for i in range(3)
    }
    now = time.time()
    for i, (key, data) in enumerate(payloads.items()):
        assert store.put(key, data)
        # Deterministic LRU order regardless of fs timestamp granularity.
        data_path, _ = store._paths(key)
        os.utime(data_path, (now - 100 + i, now - 100 + i))
    # Touch k0 so k1 becomes the eviction victim.
    assert store.get("k0") is not None
    # Hold an fd on k1's data file: eviction must not tear the in-flight
    # read (POSIX unlink keeps the inode alive for the holder).
    victim_path, _ = store._paths("k1")
    fd = os.open(victim_path, os.O_RDONLY)
    try:
        assert store.put("k3", np.random.RandomState(9).bytes(1 << 20))
        store.maybe_evict()
        stats = store.stats()
        assert stats["bytes"] <= 3 << 20
        assert store.resident_nbytes("k1") is None  # LRU victim
        assert store.resident_nbytes("k0") is not None  # recently used
        held = b""
        while True:
            chunk = os.read(fd, 1 << 16)
            if not chunk:
                break
            held += chunk
        assert held == payloads["k1"]  # evicted mid-read, still whole
    finally:
        os.close(fd)


def test_ranged_slice_verifies_whole_entry_once(tmp_path):
    """The first ranged slice of a cached entry verifies the WHOLE entry
    against its digest (a crash-torn populate corrupts bytes the slice
    itself may not cover), then fast-paths; corruption outside the
    requested range is still detected."""
    store = cache_mod.CacheStore(str(tmp_path / "cache"))
    data = np.random.RandomState(1).bytes(1 << 20)
    assert store.put("k", data)
    sliced = store.get("k", byte_range=[0, 4096])
    assert bytes(sliced) == data[:4096]
    # Corrupt OUTSIDE the slice's range, size preserved (a torn populate).
    data_path, _ = store._paths("k")
    with open(data_path, "r+b") as f:
        f.seek(1 << 19)
        f.write(b"\x00\x11\x22\x33")
    fresh = cache_mod.CacheStore(str(tmp_path / "cache"))  # new process view
    assert fresh.get("k", byte_range=[0, 4096]) is None  # detected, dropped
    assert fresh.resident_nbytes("k") is None


def test_stale_tmp_debris_swept(tmp_path):
    """A crashed populate's tmp file (invisible to eviction accounting by
    design) is age-swept by the maintenance pass."""
    store = cache_mod.CacheStore(str(tmp_path / "cache"), max_bytes=0)
    assert store.put("k", b"x" * 1024)
    data_path, _ = store._paths("k")
    stale = f"{data_path}.tmp.999.1"
    with open(stale, "wb") as f:
        f.write(b"y" * (1 << 16))
    old = time.time() - 7200
    os.utime(stale, (old, old))
    fresh = f"{data_path}.tmp.999.2"
    with open(fresh, "wb") as f:
        f.write(b"z")
    store.maybe_evict()
    assert not os.path.exists(stale)  # crashed populate reclaimed
    assert os.path.exists(fresh)  # a live populate's tmp is untouched
    assert store.get("k") is not None


def test_ranged_read_served_from_warmed_full_entry(tmp_path):
    """warm populates whole objects; a later ranged read slices the
    resident entry instead of touching origin."""
    state = _state(nbytes_per_leaf=1 << 18, leaves=4)
    path = str(tmp_path / "root" / "step_1")
    snap = Snapshot.take(path, state)
    cache_dir = str(tmp_path / "cache")
    with knobs.override_cache_dir(cache_dir), knobs.override_faults("none"):
        assert main(["warm", path]) == 0
        faults.reset_read_counters()
        dst = _zeros_like(state)
        snap.restore(dst)  # slab members read by byte range
        assert _payload_read_bytes() == 0
    np.testing.assert_array_equal(
        np.asarray(dst["m"]["w3"]), state["m"]["w3"]
    )


def test_cache_namespace_prevents_stale_bytes(tmp_path):
    """A step pruned and re-saved at the same path with different content
    must not be served the old step's cached bytes (the manifest
    fingerprint namespaces non-CAS keys)."""
    import shutil

    path = str(tmp_path / "root" / "step_1")
    cache_dir = str(tmp_path / "cache")
    old = _state(leaves=1, seed=1)
    with knobs.override_cache_dir(cache_dir):
        Snapshot.take(path, old).restore(_zeros_like(old))
        shutil.rmtree(path)
        new = _state(leaves=1, seed=2)
        snap = Snapshot.take(path, new)
        dst = _zeros_like(new)
        snap.restore(dst)
    np.testing.assert_array_equal(np.asarray(dst["m"]["w0"]), new["m"]["w0"])


# ----------------------------------------------------------- partial reads


def _sharded_entry(arr, checksum=True):
    from torchsnapshot_tpu import integrity
    from torchsnapshot_tpu.manifest import (
        Shard,
        ShardedArrayEntry,
        TensorEntry,
    )

    return ShardedArrayEntry(
        dtype=str(arr.dtype),
        shape=list(arr.shape),
        shards=[
            Shard(
                offsets=[0] * arr.ndim,
                sizes=list(arr.shape),
                tensor=TensorEntry(
                    location="piece",
                    serializer="buffer_protocol",
                    dtype=str(arr.dtype),
                    shape=list(arr.shape),
                    replicated=False,
                    checksum=(
                        integrity.digest(arr.tobytes()) if checksum else None
                    ),
                ),
            )
        ],
        mesh_shape=None,
        axis_names=None,
        partition_spec=None,
    )


def test_half_shard_plan_reads_under_60_percent(tmp_path):
    """THE partial-read acceptance criterion: a plan covering a strict
    subset of an entry fetches only the intersecting byte ranges — origin
    bytes < 60% of entry bytes for a half-shard plan, counted by the
    fault wrapper."""
    from torchsnapshot_tpu.io_preparers.sharded_array import (
        ShardedArrayIOPreparer,
        _ShardedRestore,
    )
    from torchsnapshot_tpu.scheduler import sync_execute_read_reqs
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin

    arr = np.random.RandomState(0).rand(1024, 256).astype(np.float32)
    origin = url_to_storage_plugin(str(tmp_path))
    origin.sync_write(WriteIO(path="piece", buf=arr.tobytes()))
    origin.sync_close()
    entry = _sharded_entry(arr)
    with knobs.override_partial_read_min_saved_bytes(1024):
        restore = _ShardedRestore(entry=entry, obj_out=None)
        restore.add_target((0, 0), [512, 256])
        reqs, fut = ShardedArrayIOPreparer._plan_reads(entry, restore)
        assert len(reqs) == 1
        assert reqs[0].byte_range == [0, 512 * 256 * 4]
        faults.reset_read_counters()
        counted = url_to_storage_plugin(str(tmp_path), {"faults": "none"})
        try:
            sync_execute_read_reqs(reqs, counted, 1 << 30, 0)
        finally:
            counted.sync_close()
        origin_bytes = _payload_read_bytes()
    assert origin_bytes < 0.6 * arr.nbytes, (origin_bytes, arr.nbytes)
    np.testing.assert_array_equal(fut.obj, arr[:512])


def test_partial_read_interior_span_and_knob_off(tmp_path):
    from torchsnapshot_tpu.io_preparers.sharded_array import (
        ShardedArrayIOPreparer,
        _ShardedRestore,
    )

    arr = np.arange(1024 * 16, dtype=np.float32).reshape(1024, 16)
    entry = _sharded_entry(arr)
    with knobs.override_partial_read_min_saved_bytes(64):
        restore = _ShardedRestore(entry=entry, obj_out=None)
        restore.add_target((256, 0), [128, 16])
        reqs, _ = ShardedArrayIOPreparer._plan_reads(entry, restore)
        # Interior span: rows [256, 384) at 64 bytes per row.
        assert reqs[0].byte_range == [256 * 64, 384 * 64]
        # The shrunken piece must drop its whole-payload digest.
        assert reqs[0].buffer_consumer._piece_entry.checksum is None
    with knobs.override_partial_reads(False):
        restore = _ShardedRestore(entry=entry, obj_out=None)
        restore.add_target((256, 0), [128, 16])
        reqs, _ = ShardedArrayIOPreparer._plan_reads(entry, restore)
        assert reqs[0].byte_range is None  # knob off: whole-piece read
    with knobs.override_partial_read_min_saved_bytes(1 << 30):
        restore = _ShardedRestore(entry=entry, obj_out=None)
        restore.add_target((256, 0), [128, 16])
        reqs, _ = ShardedArrayIOPreparer._plan_reads(entry, restore)
        assert reqs[0].byte_range is None  # saving below the floor


def test_partial_read_full_plan_keeps_checksum():
    """A plan needing every row keeps the whole-piece read AND its digest."""
    from torchsnapshot_tpu.io_preparers.sharded_array import (
        ShardedArrayIOPreparer,
        _ShardedRestore,
    )

    arr = np.ones((64, 8), np.float32)
    entry = _sharded_entry(arr)
    restore = _ShardedRestore(entry=entry, obj_out=None)
    restore.add_target((0, 0), [64, 8])
    reqs, _ = ShardedArrayIOPreparer._plan_reads(entry, restore)
    assert reqs[0].byte_range is None
    assert reqs[0].buffer_consumer._piece_entry.checksum is not None


# ------------------------------------------------------- cache under faults


def test_chaos_restore_through_faults_over_cache(tmp_path):
    """Cache correctness under adversity: restores running through the
    fault wrapper (latency + terminal origin faults) stay byte-correct, a
    mid-restore failure never leaves a poisoned cache, and the retry lands
    from a coherent mix of partially-populated cache and origin."""
    state = _state(leaves=4, seed=5)
    path = str(tmp_path / "root" / "step_1")
    # Unbatched payloads so fault globs can target individual files.
    with knobs.override_batching_disabled(True):
        snap = Snapshot.take(path, state)
    # Cold cache + latency faults: slow origin, correct bytes.
    with knobs.override_cache_dir(str(tmp_path / "cache_a")):
        with knobs.override_faults("read:1:latency:0.01;read:3:latency:0.01"):
            dst = _zeros_like(state)
            snap.restore(dst)
        for key in state["m"]:
            np.testing.assert_array_equal(
                np.asarray(dst["m"][key]), state["m"][key]
            )
    # Fresh cold cache; a terminal origin fault mid-restore fails the
    # restore loudly after SOME payloads already populated...
    with knobs.override_cache_dir(str(tmp_path / "cache_b")):
        with knobs.override_faults("read:2:terminal@0/m/*"):
            with pytest.raises(Exception):
                Snapshot(path).restore(_zeros_like(state))
        # ...and what was cached is valid: the retry restores byte-correct
        # from the partially-populated cache plus origin.
        with knobs.override_faults("read:1:latency:0.005"):
            dst2 = _zeros_like(state)
            Snapshot(path).restore(dst2)
        for key in state["m"]:
            np.testing.assert_array_equal(
                np.asarray(dst2["m"][key]), state["m"][key]
            )


# ------------------------------------------------------------ CLI warm/serve


def test_cli_warm_and_serve_on_manager_root(tmp_path, capsys):
    mgr = SnapshotManager(str(tmp_path / "run"))
    state = _state(nbytes_per_leaf=1 << 16, leaves=2)
    mgr.save(1, state)
    mgr.save(2, state)
    cache_dir = str(tmp_path / "cache")
    assert (
        main(["warm", str(tmp_path / "run"), "--cache-dir", cache_dir]) == 0
    )
    out = capsys.readouterr().out
    assert "warmed" in out and "step_2" in out
    assert (
        main(
            [
                "serve",
                str(tmp_path / "run"),
                "--cache-dir",
                cache_dir,
                "--json",
            ]
        )
        == 0
    )
    doc = json.loads(capsys.readouterr().out)
    assert doc["residency"]["resident"] == doc["residency"]["locations"]
    assert doc["residency"]["bytes_resident"] > 0
    # --step targets a specific point; serve without a cache dir errors.
    assert (
        main(
            [
                "warm",
                str(tmp_path / "run"),
                "--step",
                "1",
                "--cache-dir",
                cache_dir,
            ]
        )
        == 0
    )
    with knobs.override_cache_dir(None):
        assert main(["serve", str(tmp_path / "run")]) == 2


def test_warm_direct_segment_path_covers_chain(tmp_path):
    """warm of a journal segment PATH (not root + --step) pre-faults the
    whole replay chain — base chunks included — so a following restore
    touches origin zero times."""
    from torchsnapshot_tpu import integrity

    if not integrity.hashing_available():
        pytest.skip("journal mode needs a hash backend")
    root = str(tmp_path / "run")
    with knobs.override_journal(True), knobs.override_batching_disabled(True):
        mgr = SnapshotManager(root)
        state1 = _state(nbytes_per_leaf=1 << 17, leaves=3, seed=21)
        mgr.save(1, state1)
        state2 = {"m": StateDict(dict(state1["m"]))}
        state2["m"]["w0"] = np.frombuffer(
            np.random.RandomState(99).bytes(1 << 17), np.uint8
        ).copy()
        mgr.save(2, state2)
    cache_dir = str(tmp_path / "cache")
    with knobs.override_cache_dir(cache_dir), knobs.override_faults("none"):
        assert main(["warm", f"{root}/seg_2"]) == 0
        faults.reset_read_counters()
        dst = _zeros_like(state2)
        mgr2 = SnapshotManager(root)
        assert mgr2.restore_latest(dst) == 2
        assert _payload_read_bytes() == 0  # base + delta all resident
    np.testing.assert_array_equal(
        np.asarray(dst["m"]["w0"]), state2["m"]["w0"]
    )
    np.testing.assert_array_equal(
        np.asarray(dst["m"]["w1"]), state2["m"]["w1"]
    )


def test_manager_restore_as_of(tmp_path):
    mgr = SnapshotManager(str(tmp_path / "run"))
    marks = {}
    for step in (1, 2, 3):
        mgr.save(
            step, {"m": StateDict({"w": np.full(16, step, np.float32)})}
        )
        marks[step] = time.time()
        time.sleep(0.02)
    assert mgr.step_as_of(marks[2]) == 2
    dst = {"m": StateDict({"w": np.zeros(16, np.float32)})}
    assert mgr.restore_as_of(marks[1], dst) == 1
    assert dst["m"]["w"][0] == 1.0
    with pytest.raises(ValueError, match="no restore point"):
        mgr.step_as_of(marks[1] - 1e6)
    # --time flows through the CLI target resolution too.
    assert (
        main(
            [
                "warm",
                str(tmp_path / "run"),
                "--time",
                str(marks[2]),
                "--cache-dir",
                str(tmp_path / "cache"),
            ]
        )
        == 0
    )


# -------------------------------------------------- concurrent restore procs


def _spawn_serve_workers(snap_path, n, cache_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["TPUSNAP_CACHE_DIR"] = cache_dir  # launcher-side child-env export
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
    return docs


def _assert_serve_outcome(docs, logical_bytes, n):
    total = sum(d["bytes"] for d in docs)
    assert total == n * logical_bytes
    origin = sum(d["miss_bytes"] for d in docs)
    hit = sum(d["hit_bytes"] for d in docs)
    # One host-shared cache: the fleet pulls the snapshot from origin
    # about once (the per-key populate lock single-flights cold fetches).
    assert origin <= 1.25 * logical_bytes, (origin, logical_bytes)
    assert hit + origin == total
    return origin, hit


def test_two_worker_concurrent_restore_fast(tmp_path):
    """The tier-1 serve smoke: 2 restore processes, one shared cache —
    origin traffic ≈ one snapshot, both restores byte-complete."""
    state = _state(nbytes_per_leaf=1 << 20, leaves=4, seed=8)
    snap_path = str(tmp_path / "root" / "step_1")
    Snapshot.take(snap_path, state)
    logical = sum(v.nbytes for v in state["m"].values())
    docs = _spawn_serve_workers(snap_path, 2, str(tmp_path / "cache"))
    _assert_serve_outcome(docs, logical, 2)


@pytest.mark.slow
def test_eight_worker_serve_soak(tmp_path):
    """The N≥8 soak: aggregate hit ratio ≥ 7/8 of logical bytes and
    origin traffic ≈ one snapshot."""
    state = _state(nbytes_per_leaf=1 << 21, leaves=8, seed=9)
    snap_path = str(tmp_path / "root" / "step_1")
    Snapshot.take(snap_path, state)
    logical = sum(v.nbytes for v in state["m"].values())
    docs = _spawn_serve_workers(snap_path, 8, str(tmp_path / "cache"))
    origin, hit = _assert_serve_outcome(docs, logical, 8)
    assert hit / (hit + origin) >= 7 / 8, (hit, origin)


# ------------------------------------------------------------ fake-gcs serve


@pytest.fixture()
def gcs_env(monkeypatch):
    from fake_gcs import FakeGCSServer

    server = FakeGCSServer()
    monkeypatch.setenv("TPUSNAP_GCS_ENDPOINT", server.endpoint)
    yield server
    server.stop()


def test_serve_from_gcs_origin_downloads_once(tmp_path, gcs_env):
    """The cloud half of the serving story: after one cache-mediated
    restore (or a warm), later restores of a GCS snapshot issue ZERO
    download requests to the bucket."""
    state = _state(nbytes_per_leaf=1 << 18, leaves=2, seed=11)
    snap = Snapshot.take("gs://ckpt/run/step_1", state)
    with knobs.override_cache_dir(str(tmp_path / "cache")):
        snap.restore(_zeros_like(state))
        downloads_after_first = gcs_env.downloads
        assert downloads_after_first > 0
        dst = _zeros_like(state)
        snap2 = Snapshot("gs://ckpt/run/step_1")
        _ = snap2.metadata  # the commit-marker read is origin by design
        baseline = gcs_env.downloads
        snap2.restore(dst)
        assert gcs_env.downloads == baseline
    np.testing.assert_array_equal(
        np.asarray(dst["m"]["w1"]), state["m"]["w1"]
    )
