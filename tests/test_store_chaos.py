"""Multi-tenant shared-store chaos: concurrent tenants, faults, kills.

Two SnapshotManagers in different roots drive one shared store through
{take, prune, gc} concurrently, under injected delete/ledger faults and
kill -9 mid-take / mid-sweep.  The invariant checked after every
scenario: store-wide ``chunk_classification`` accounts for every present
chunk, no root's committed manifest references a chunk missing from both
``cas/`` and the quarantine, and ``restore_latest`` lands a good
snapshot on every root.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import StateDict, knobs
from torchsnapshot_tpu import cas as cas_mod
from torchsnapshot_tpu import store as store_mod
from torchsnapshot_tpu.io_types import ReadIO
from torchsnapshot_tpu.manager import SnapshotManager
from torchsnapshot_tpu.manifest import SnapshotMetadata
from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin


@pytest.fixture(autouse=True)
def _knob_env_as_found():
    """The tenants run as threads, and each enters ``knobs.override_*``
    scopes of its own: two threads that interleave save and restore each
    other's values, and ``TPUSNAP_STORE`` and ``TPUSNAP_CAS`` outlive the
    test.  Whatever file the worker ran next then wrote into this test's
    store.  Put the environment back as it was found."""
    saved = {k: v for k, v in os.environ.items() if k.startswith("TPUSNAP_")}
    yield
    for key in [k for k in os.environ if k.startswith("TPUSNAP_")]:
        if key not in saved:
            del os.environ[key]
    os.environ.update(saved)


def _state(v):
    return {
        "m": StateDict(
            {"w": np.full((512,), float(v), np.float32), "step": v}
        )
    }


def _zeros():
    return {
        "m": StateDict({"w": np.zeros((512,), np.float32), "step": 0})
    }


def _assert_store_invariants(store, roots):
    """The acceptance invariant, checked fault-free."""
    storage = url_to_storage_plugin(str(store))
    try:
        present = cas_mod.list_chunk_relpaths(storage)
        quarantined = store_mod.quarantined_chunk_relpaths(storage)
    finally:
        storage.sync_close()
    cls = store_mod.chunk_classification(str(store))
    # Every present chunk is referenced or orphan; every quarantined one
    # is condemned — nothing unclassifiable.
    assert sorted(cls["referenced"] + cls["orphan"]) == sorted(present)
    assert cls["condemned"] == sorted(quarantined)
    # No committed manifest references a chunk that is gone from BOTH
    # cas/ and the quarantine (the resolver covers quarantined ones).
    available = set(present) | set(quarantined)
    for root in roots:
        rp = url_to_storage_plugin(str(root))
        try:
            for marker in cas_mod.committed_marker_relpaths(rp):
                read_io = ReadIO(path=marker)
                rp.sync_read(read_io)
                metadata = SnapshotMetadata.from_json(
                    bytes(read_io.buf).decode("utf-8")
                )
                refs = cas_mod.referenced_chunk_relpaths(metadata.manifest)
                missing = refs - available
                assert not missing, (
                    f"{root}: manifest {marker} references missing "
                    f"chunks {sorted(missing)}"
                )
        finally:
            rp.sync_close()


def _restore_ok(root, store):
    mgr = SnapshotManager(str(root), max_to_keep=10, store=str(store))
    points = mgr.restore_points()
    if not points:
        return None
    dst = _zeros()
    mgr.restore_latest(dst)
    v = float(dst["m"]["w"][0])
    assert v == float(dst["m"]["step"]) == float(points[-1][0])
    return v


# ------------------------------------------------------- concurrent faults

# (spec, both_tenants_must_commit): transient faults are retried through,
# terminal ones may abort individual takes — the invariant must hold
# either way.
_MENU = [
    ("", True),
    ("delete:1:transient@cas/*", True),
    ("write:2:transient@cas/*; read:3:transient", True),
    ("ledger:1:transient", True),  # first hit is a swallowed control read
    # A fault on the journal APPEND aborts that take pre-commit — the
    # debris must still classify and sweep.
    ("ledger:1:transient@ledger/*", False),
    ("ledger:1:terminal@ledger/*", False),
    ("delete:2:terminal@cas/*", True),  # deletes are GC-side: takes commit
]


@pytest.mark.parametrize("spec,must_commit", _MENU)
def test_two_tenants_concurrent_under_faults(tmp_path, spec, must_commit):
    store = tmp_path / "store"
    roots = [tmp_path / "ra", tmp_path / "rb"]
    errors = []

    def tenant(root, base):
        try:
            mgr = SnapshotManager(
                str(root), max_to_keep=2, store=str(store)
            )
            for v in (base + 1, base + 2, base + 3):
                try:
                    mgr.save(v, _state(v))
                except Exception:
                    if must_commit:
                        raise
                try:
                    mgr.gc_detail(apply=True, force=True)
                except Exception:
                    pass
        except Exception as e:  # pragma: no cover - surfaced via errors
            errors.append(e)

    with knobs.override_retry_base_s(0.001), knobs.override_sidecar(
        False
    ), knobs.override_lease_interval_s(0.05), knobs.override_store_quarantine_s(
        0.0
    ), knobs.override_faults(spec or None):
        threads = [
            threading.Thread(target=tenant, args=(root, 10 * i))
            for i, root in enumerate(roots)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert not errors, errors
    # Fault-free epilogue: a final sweep converges the store, then the
    # invariant and restores must hold on both roots.
    with knobs.override_store_quarantine_s(0.0):
        try:
            store_mod.sweep(str(store), force=True)
        except store_mod.StoreSweepBusyError:
            pass
    _assert_store_invariants(store, roots)
    for i, root in enumerate(roots):
        v = _restore_ok(root, store)
        if must_commit:
            assert v == 10 * i + 3


# ------------------------------------------------------------ process kills

_CHILD_TAKE = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from torchsnapshot_tpu import StateDict
from torchsnapshot_tpu.manager import SnapshotManager

root, store = sys.argv[1], sys.argv[2]
mgr = SnapshotManager(root, max_to_keep=10, store=store)
mgr.save(1, {"m": StateDict({"w": np.full((512,), 1.0, np.float32), "step": 1})})
os._exit(7)  # never reached: the crash fault fires mid-take
"""

_CHILD_SWEEP = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from torchsnapshot_tpu import store as store_mod

store_mod.sweep(sys.argv[1])
os._exit(7)  # never reached: the crash fault fires mid-sweep
"""


def _run_child(code, args, faults, blackbox_dir=None):
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "TPUSNAP_FAULTS": faults,
            "TPUSNAP_SIDECAR": "0",
            # Keep lease refreshers quiet so the fault counters are
            # deterministic (control-plane writes come from the op
            # sequence, not a timer).
            "TPUSNAP_LEASE_INTERVAL_S": "9999",
        }
    )
    if blackbox_dir is not None:
        env["TPUSNAP_BLACKBOX"] = str(blackbox_dir)
    proc = subprocess.run(
        [sys.executable, "-c", code, *[str(a) for a in args]],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1, (
        f"child should die on the crash fault, got {proc.returncode}: "
        f"{proc.stderr[-2000:]}"
    )


def test_kill_mid_take_debris_swept_by_survivor(tmp_path):
    """kill -9 (crash fault) during a take's chunk writes: the dead
    writer's lease goes stale, its debris chunks classify as orphans, and
    the SURVIVING tenant's sweep condemns and deletes them."""
    store = tmp_path / "store"
    ra, rb = tmp_path / "ra", tmp_path / "rb"
    bb = tmp_path / "bb"
    # Crash at the reference-journal append: every chunk is written (real
    # debris in the store) but neither the journal nor the commit marker
    # landed — the canonical crashed-writer window.
    _run_child(
        _CHILD_TAKE, [ra, store], "ledger:1:crash@ledger/*", blackbox_dir=bb
    )
    # Postmortem names the dead writer from its flight-recorder ring: the
    # kill lands mid-take (journal append), debited to the right tenant.
    from torchsnapshot_tpu.telemetry import blackbox, postmortem

    report = postmortem.analyze_root(
        str(ra), store_url=str(store), blackbox_dir=str(bb)
    )
    assert report["classification"] == "killed_mid_take", report
    fd = report["first_dead"]
    (ring_path,) = blackbox.read_all(str(bb)).keys()
    ring_pid = int(
        os.path.basename(ring_path).rsplit("-", 1)[1][: -len(".ring")]
    )
    assert fd["pid"] == ring_pid != os.getpid(), fd
    assert fd["verdict"] == "crash_fault", fd
    assert fd["fault"]["path"].startswith("ledger/"), fd
    # The store plane pins the blast radius: the dead pid's writer lease
    # (stale once the grace passes) and its orphan chunks, and the
    # prescription is a store sweep.
    assert report["store"]["chunks"]["orphan"] > 0, report["store"]
    assert any(
        a["action"] == "store_sweep"
        for a in report["remediation"]["actions"]
    ), report["remediation"]
    # Survivor saves normally against the same store.
    mb = SnapshotManager(str(rb), max_to_keep=10, store=str(store))
    mb.save(2, _state(2))
    with knobs.override_lease_interval_s(0.05), knobs.override_lease_grace_s(
        0.3
    ), knobs.override_store_quarantine_s(0.0):
        time.sleep(0.6)  # let the dead writer's lease/journal age out
        report = store_mod.sweep(str(store))
        # Anything the dead take wrote and nothing references is gone.
        assert not report["deferred_epochs"]
        _assert_store_invariants(store, [ra, rb])
    assert _restore_ok(rb, store) == 2.0
    # The crashed root has no committed step; its uncommitted debris and
    # stale in-flight marker are GC-able without force (dead pid).
    ma = SnapshotManager(str(ra), max_to_keep=10, store=str(store))
    removed, _, _ = ma.gc_detail(apply=True)
    assert removed in ([], [1])  # [] if the crash preceded the step dir
    assert ma.restore_points() == []
    # The prescribed remediation converged: the store holds no orphan or
    # quarantined chunks anymore, so postmortem stops reporting debris.
    after = postmortem.analyze_root(
        str(ra), store_url=str(store), blackbox_dir=str(bb)
    )
    assert after["store"]["chunks"]["orphan"] == 0, after["store"]
    assert after["store"]["quarantined"] == [], after["store"]
    assert after["debris"]["orphan_steps"] == [], after["debris"]


def test_kill_mid_sweep_lease_adopted(tmp_path):
    """kill -9 during a sweep (crash fault on the epoch bump): the sweep
    lease is left behind; a concurrent sweep refuses while it looks live
    and ADOPTS it once stale — no operator cleanup."""
    store = tmp_path / "store"
    ra = tmp_path / "ra"
    ma = SnapshotManager(str(ra), max_to_keep=10, store=str(store))
    ma.save(1, _state(1))
    # Touches of sweep/epoch.json in a sweep: report read, bump read,
    # bump WRITE — crashing on the third dies right after the lease
    # acquire, with the lease durably on storage.
    bb = tmp_path / "bb"
    _run_child(
        _CHILD_SWEEP,
        [store],
        "ledger:3:crash@sweep/epoch.json",
        blackbox_dir=bb,
    )
    # Postmortem places the kill INSIDE the two-phase GC (fault on a
    # sweep/ control path; store_sweep lease acquired, never released)
    # and prescribes the adopting sweep the rest of this test performs.
    from torchsnapshot_tpu.telemetry import postmortem

    report = postmortem.analyze_root(
        str(ra), store_url=str(store), blackbox_dir=str(bb)
    )
    assert report["classification"] == "killed_mid_sweep", report
    assert report["first_dead"]["verdict"] == "crash_fault", report
    assert report["store"]["sweep_lease"] is not None, report["store"]
    sweep_actions = [
        a
        for a in report["remediation"]["actions"]
        if a["action"] == "store_sweep"
    ]
    assert sweep_actions and sweep_actions[0]["force"], report["remediation"]
    # The dead sweeper's lease is fresh for a grace: busy.
    with pytest.raises(store_mod.StoreSweepBusyError):
        store_mod.sweep(str(store))
    with knobs.override_lease_interval_s(0.05), knobs.override_lease_grace_s(
        0.3
    ), knobs.override_store_quarantine_s(0.0):
        time.sleep(0.6)
        report = store_mod.sweep(str(store))
        assert report["adopted_lease"]
    _assert_store_invariants(store, [ra])
    assert _restore_ok(ra, store) == 1.0
    # Adoption converged: the dead sweeper's lease is gone, so postmortem
    # stops prescribing a sweep.
    after = postmortem.analyze_root(
        str(ra), store_url=str(store), blackbox_dir=str(bb)
    )
    assert after["store"]["sweep_lease"] is None, after["store"]
    assert not any(
        a["action"] == "store_sweep"
        for a in after["remediation"]["actions"]
    ), after["remediation"]


def test_kill_mid_condemn_quarantine_converges(tmp_path):
    """kill -9 between the condemn stamp and the chunk moves: the stamped
    epoch's age is known, so a later sweep processes (or removes) it and
    the classification still accounts for everything."""
    store = tmp_path / "store"
    ra = tmp_path / "ra"
    ma = SnapshotManager(str(ra), max_to_keep=10, store=str(store))
    ma.save(1, _state(1))
    # An orphan gives the condemn phase something to move.
    storage = url_to_storage_plugin(str(store))
    try:
        from torchsnapshot_tpu.io_types import WriteIO

        storage.sync_write(
            WriteIO(path="cas/xxh64/de/deadbeef", buf=b"junk", durable=True)
        )
    finally:
        storage.sync_close()
    # First quarantine write is the .condemned stamp; crashing on the
    # SECOND quarantine write dies between stamp and chunk move.
    bb = tmp_path / "bb"
    _run_child(
        _CHILD_SWEEP, [store], "ledger:2:crash@quarantine/*", blackbox_dir=bb
    )
    # Postmortem distinguishes this kill window from mid-sweep: the fault
    # landed on a quarantine/ path — between the condemn stamp and the
    # chunk moves.
    from torchsnapshot_tpu.telemetry import postmortem

    report = postmortem.analyze_root(
        str(ra), store_url=str(store), blackbox_dir=str(bb)
    )
    assert report["classification"] == "killed_mid_condemn", report
    assert report["first_dead"]["fault"]["path"].startswith(
        "quarantine/"
    ), report["first_dead"]
    assert any(
        a["action"] == "store_sweep" and a["force"]
        for a in report["remediation"]["actions"]
    ), report["remediation"]
    with knobs.override_lease_interval_s(0.05), knobs.override_lease_grace_s(
        0.3
    ), knobs.override_store_quarantine_s(0.0):
        time.sleep(0.6)
        report = store_mod.sweep(str(store))
        assert report["adopted_lease"] or report["epoch"] >= 1
        # The orphan is condemned (and with grace 0, deleted) by the
        # adopting sweep; nothing referenced was harmed.
        _assert_store_invariants(store, [ra])
    assert _restore_ok(ra, store) == 1.0
    # Convergence: the quarantine drained and the lease is gone.
    after = postmortem.analyze_root(
        str(ra), store_url=str(store), blackbox_dir=str(bb)
    )
    assert after["store"]["quarantined"] == [], after["store"]
    assert after["store"]["sweep_lease"] is None, after["store"]


# -------------------------------------------------------------------- soak


@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_store_chaos_soak(tmp_path, seed):
    """Randomized matrix soak: 2 tenants × {take, prune, gc} × random
    fault specs, several rounds, invariant after each round."""
    import random

    rng = random.Random(seed)
    store = tmp_path / "store"
    roots = [tmp_path / "ra", tmp_path / "rb"]
    specs = [s for s, _ in _MENU]
    step = {0: 0, 1: 100}
    for _ in range(4):
        spec = rng.choice(specs)
        errors = []

        def tenant(i, root):
            try:
                mgr = SnapshotManager(
                    str(root), max_to_keep=2, store=str(store)
                )
                for _ in range(rng.randint(1, 3)):
                    step[i] += 1
                    try:
                        mgr.save(step[i], _state(step[i]))
                    except Exception:
                        pass
                try:
                    mgr.gc_detail(apply=True, force=True)
                except Exception:
                    pass
            except Exception as e:  # pragma: no cover
                errors.append(e)

        with knobs.override_retry_base_s(0.001), knobs.override_sidecar(
            False
        ), knobs.override_lease_interval_s(
            0.05
        ), knobs.override_store_quarantine_s(
            0.0
        ), knobs.override_faults(spec or None):
            threads = [
                threading.Thread(target=tenant, args=(i, root))
                for i, root in enumerate(roots)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        assert not errors, errors
        with knobs.override_store_quarantine_s(0.0):
            try:
                store_mod.sweep(str(store), force=True)
            except store_mod.StoreSweepBusyError:
                pass
        _assert_store_invariants(store, roots)
        for root in roots:
            _restore_ok(root, store)
        # Classifier per round: no process died (faults here are raised
        # errors, not kills), so postmortem must never invent a death.
        from torchsnapshot_tpu.telemetry import postmortem

        for root in roots:
            verdict = postmortem.analyze_root(
                str(root),
                store_url=str(store),
                blackbox_dir=str(tmp_path / "bb"),
            )
            assert verdict["classification"] == "no_failure", (
                seed,
                spec,
                verdict["classification"],
            )
