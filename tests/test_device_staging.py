"""Device-side async staging: async_take must be donation-safe the moment it
returns, in every staging mode (device_staging.py).

The reference can only offer host staging (stage-to-RAM-then-return,
/root/reference/torchsnapshot/snapshot.py:962-1068); the device modes are the
TPU-native capability this suite pins: state copied inside the accelerator
(spare HBM or pinned_host memory space), background D2H, bit-exact restore.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict, knobs
from torchsnapshot_tpu import device_staging
from torchsnapshot_tpu.serialization import PrePickled


def _mesh8():
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("x",))


# ------------------------------------------------------------ mode resolution


def test_resolve_host_when_forced():
    with knobs.override_async_staging("host"):
        assert device_staging.resolve_mode({"m/w": jnp.ones(4)}) == "host"


def test_resolve_host_when_no_device_arrays():
    # Nothing needs a D2H DMA -> host staging is already instant.
    flattened = {"m/w": np.ones(4), "m/step": 3, "m/obj": ["a"]}
    with knobs.override_async_staging("auto"):
        assert device_staging.resolve_mode(flattened) == "host"


def test_resolve_device_when_forced():
    with knobs.override_async_staging("device"):
        assert device_staging.resolve_mode({"m/w": jnp.ones(4)}) == "device"


def test_resolve_auto_prefers_pinned_host():
    # The CPU test backend exposes a pinned_host memory space.
    device_staging.reset_pinned_host_health()
    with knobs.override_async_staging("auto"):
        assert device_staging.resolve_mode({"m/w": jnp.ones(4)}) in (
            "pinned_host",
            "device",
        )


def test_resolve_rejects_bad_mode():
    with knobs.override_async_staging("gpu"):
        with pytest.raises(ValueError):
            device_staging.configured_mode()


def test_resolve_mode_collective_agreement():
    """Device/pinned_host staging launches collective executions; ranks with
    diverging local signals must agree on the most conservative mode or the
    job hangs at checkpoint time (advisor r4 medium finding)."""

    class FakePG:
        def get_world_size(self):
            return 2

        def all_gather_object(self, obj):
            # Peer rank resolved host (no headroom anywhere).
            return [obj, {"mode": "host", "device_fits": False}]

    device_staging.reset_pinned_host_health()
    with knobs.override_async_staging("auto"):
        mode = device_staging.resolve_mode({"m/w": jnp.ones(4)}, pg=FakePG())
    assert mode == "host"


def test_resolve_mode_empty_rank_is_wildcard():
    """A rank holding no device arrays (eval/coordinator) joins no
    collective staging program; its vote must not drag device-holding peers
    into blocking host staging."""
    device_staging.reset_pinned_host_health()

    class FakePG:
        def get_world_size(self):
            return 2

        def all_gather_object(self, obj):
            return [
                obj,
                {"mode": "host", "device_fits": True, "any_ok": True},
            ]

    with knobs.override_async_staging("auto"):
        mode = device_staging.resolve_mode({"m/w": jnp.ones(4)}, pg=FakePG())
    assert mode in ("pinned_host", "device")


def test_resolve_mode_agreement_respects_device_capability(monkeypatch):
    """A rank that prefers pinned_host (and so never needed HBM headroom)
    must not be agreement-downgraded into a device copy it cannot hold:
    the gather carries capability, not just preference."""
    device_staging.reset_pinned_host_health()
    monkeypatch.setattr(
        device_staging, "_hbm_headroom_fits", lambda arrays: False
    )

    class FakePG:
        def get_world_size(self):
            return 2

        def all_gather_object(self, signals):
            # Peer lacks pinned_host and prefers device (its headroom fits).
            return [signals, {"mode": "device", "device_fits": True}]

    with knobs.override_async_staging("auto"):
        mode = device_staging.resolve_mode({"m/w": jnp.ones(4)}, pg=FakePG())
    assert mode == "host"


def test_agreement_downgrade_emits_event():
    """A cross-rank agreement forcing a rank off its preferred mode is a
    stall regression; it must land in the event stream like every other
    downgrade — but ONLY when the resolution feeds an actual staging
    (emit_events=True, what async_take passes).  Pure probes/diagnostics
    resolve silently, so a 300 s backoff window doesn't spray one event
    per query (r5 advisor finding)."""
    from torchsnapshot_tpu import event_handlers

    events = []
    handler = events.append
    event_handlers.register_event_handler(handler)
    try:
        device_staging.reset_pinned_host_health()

        class FakePG:
            def get_world_size(self):
                return 2

            def all_gather_object(self, obj):
                return [obj, {"mode": "host", "device_fits": True}]

        # Pure probe: no event.
        with knobs.override_async_staging("auto"):
            mode = device_staging.resolve_mode({"m/w": jnp.ones(4)}, pg=FakePG())
        assert mode == "host"
        assert not [
            e for e in events if e.name == "async_take.staging_downgrade"
        ]

        # Staging-bound resolution: the event fires.
        with knobs.override_async_staging("auto"):
            mode = device_staging.resolve_mode(
                {"m/w": jnp.ones(4)}, pg=FakePG(), emit_events=True
            )
        assert mode == "host"
        downgrades = [
            e for e in events if e.name == "async_take.staging_downgrade"
        ]
        assert downgrades and "agreement" in downgrades[-1].metadata["reason"]
    finally:
        event_handlers.unregister_event_handler(handler)


def test_resolve_mode_mixed_platform_probe(monkeypatch):
    """A mixed-platform state must consult pinned_host support/health for
    EVERY platform present, not whichever array iterates first."""
    a, b = jnp.ones(4), jnp.ones(8)
    plat = {id(a): "cpu", id(b): "exotic"}
    monkeypatch.setattr(
        device_staging, "_platform_of", lambda arr: plat.get(id(arr), "cpu")
    )
    device_staging.reset_pinned_host_health()
    device_staging.record_pinned_host_failure("exotic")
    with knobs.override_async_staging("auto"):
        mode = device_staging.resolve_mode({"m/a": a, "m/b": b})
    assert mode != "pinned_host"  # the unhealthy second platform vetoes
    device_staging.reset_pinned_host_health()
    with knobs.override_async_staging("auto"):
        mode = device_staging.resolve_mode({"m/a": a, "m/b": b})
    assert mode in ("pinned_host", "device")  # healthy again after reset


def test_pinned_host_health_retry_cycle(monkeypatch):
    """A pinned_host failure skips the mode for a backoff window then
    retries — never a permanent downgrade (r4 verdict: old flag was sticky
    forever).  The predicate is pure: probes don't burn the retry clock."""
    import time

    monkeypatch.setenv(knobs.PINNED_HOST_RETRY_S_ENV_VAR, "0.2")
    device_staging.reset_pinned_host_health()
    device_staging.record_pinned_host_failure("cpu")
    assert not device_staging._pinned_host_usable("cpu")
    assert not device_staging._pinned_host_usable("cpu")  # pure: no decay
    time.sleep(0.25)
    assert device_staging._pinned_host_usable("cpu")  # backoff passed: retry
    device_staging.record_pinned_host_failure("cpu")
    assert not device_staging._pinned_host_usable("cpu")
    device_staging.reset_pinned_host_health()
    assert device_staging._pinned_host_usable("cpu")


def test_staging_fallback_chain_end_to_end(tmp_path, monkeypatch):
    """pinned_host -> device -> host, forced: the snapshot still commits
    bit-exact, the resolved mode is honest, and every downgrade emits an
    operator-visible event (r4 verdict item 5)."""
    from torchsnapshot_tpu import event_handlers

    events = []
    handler = events.append
    event_handlers.register_event_handler(handler)
    try:
        device_staging.reset_pinned_host_health()

        def boom_pinned(arrays):
            raise RuntimeError("forced pinned_host failure")

        def boom_device(arrays):
            raise RuntimeError("forced device-copy failure")

        monkeypatch.setattr(
            device_staging, "_pinned_host_copy_batch", boom_pinned
        )
        monkeypatch.setattr(device_staging, "_device_copy_batch", boom_device)
        x = jnp.arange(64, dtype=jnp.float32)
        expected = np.asarray(x).copy()
        with knobs.override_async_staging("pinned_host"):
            pending = Snapshot.async_take(
                str(tmp_path / "snap"), {"m": StateDict({"w": x})}
            )
            snapshot = pending.wait()
        assert pending.staging_mode == "host"
        dst = {"m": StateDict({})}
        snapshot.restore(dst)
        np.testing.assert_array_equal(np.asarray(dst["m"]["w"]), expected)
        downgrades = [
            (e.metadata["from_mode"], e.metadata["to_mode"])
            for e in events
            if e.name == "async_take.staging_downgrade"
        ]
        assert ("pinned_host", "device") in downgrades
        assert any(to == "host" for _, to in downgrades)
        # The failure was recorded: the next auto-resolve skips pinned_host.
        assert not device_staging._pinned_host_usable("cpu")
    finally:
        event_handlers.unregister_event_handler(handler)
        device_staging.reset_pinned_host_health()


def test_async_take_end_event_telemetry(tmp_path):
    """async_take.end carries staging_mode/stall_s/copy_bytes/copy_s so a
    fleet can alert on stall regressions from events alone (r4 item 8)."""
    from torchsnapshot_tpu import event_handlers

    events = []
    handler = events.append
    event_handlers.register_event_handler(handler)
    try:
        device_staging.reset_pinned_host_health()
        x = jnp.ones((64, 64), jnp.float32)
        with knobs.override_async_staging("device"):
            pending = Snapshot.async_take(
                str(tmp_path / "snap"), {"m": StateDict({"w": x})}
            )
            pending.wait()
        end = [e for e in events if e.name == "async_take.end"][-1]
        md = end.metadata
        assert md["is_success"] is True
        assert md["staging_mode"] == "device"
        assert md["copy_bytes"] == 64 * 64 * 4
        assert md["stall_s"] >= 0.0
        assert "copy_s" in md and "downgraded_from" not in md
    finally:
        event_handlers.unregister_event_handler(handler)


# ------------------------------------------------------- donation-safety core


@pytest.mark.parametrize("mode", ["device", "pinned_host", "host"])
def test_async_roundtrip_with_donation(tmp_path, mode):
    x = jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)
    expected = np.asarray(x).copy()
    app_state = {"m": StateDict({"w": x})}
    with knobs.override_async_staging(mode):
        pending = Snapshot.async_take(str(tmp_path / f"snap_{mode}"), app_state)
        # Donate the original buffer immediately after return — the
        # adversarial step for device-side staging.
        step = jax.jit(lambda a: a * 0 - 1.0, donate_argnums=(0,))
        jax.block_until_ready(step(x))
        snapshot = pending.wait()
    dst = {"m": StateDict({})}
    snapshot.restore(dst)
    np.testing.assert_array_equal(np.asarray(dst["m"]["w"]), expected)


@pytest.mark.parametrize("mode", ["device", "pinned_host"])
def test_staging_mode_exposed(tmp_path, mode):
    app_state = {"m": StateDict({"w": jnp.ones((32, 32), jnp.float32)})}
    with knobs.override_async_staging(mode):
        pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
        resolved = pending.staging_mode
        pending.wait()
    # pinned_host may legitimately degrade to device on backends that cannot
    # reshard into host memory; host means the copy path failed outright.
    assert resolved in ("device", "pinned_host")


def test_np_array_mutation_after_return(tmp_path):
    arr = np.arange(512, dtype=np.float32)
    dev = jnp.ones(8, jnp.float32)  # forces a device staging mode
    app_state = {"m": StateDict({"host": arr, "dev": dev})}
    with knobs.override_async_staging("device"):
        pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
        arr[:] = -5.0  # training mutates the host array before I/O completes
        snapshot = pending.wait()
    dst = {"m": StateDict({})}
    snapshot.restore(dst)
    np.testing.assert_array_equal(dst["m"]["host"], np.arange(512, dtype=np.float32))


def test_object_mutation_after_return(tmp_path):
    log = ["step_100"]
    dev = jnp.ones(8, jnp.float32)
    app_state = {"m": StateDict({"log": log, "dev": dev})}
    with knobs.override_async_staging("device"):
        pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
        log.append("step_101")  # mutated before background pickling would run
        snapshot = pending.wait()
    dst = {"m": StateDict({})}
    snapshot.restore(dst)
    assert dst["m"]["log"] == ["step_100"]


def test_sharded_state_device_staging(tmp_path):
    mesh = _mesh8()
    sharding = NamedSharding(mesh, P("x", None))
    x = jax.device_put(
        jnp.arange(64 * 16, dtype=jnp.float32).reshape(64, 16), sharding
    )
    expected = np.asarray(x).copy()
    app_state = {"m": StateDict({"w": x})}
    with knobs.override_async_staging("device"):
        pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
        step = jax.jit(lambda a: a - a, donate_argnums=(0,))
        jax.block_until_ready(step(x))
        snapshot = pending.wait()
    dst = {
        "m": StateDict({"w": jax.device_put(jnp.zeros((64, 16), jnp.float32), sharding)})
    }
    snapshot.restore(dst)
    restored = dst["m"]["w"]
    assert restored.sharding.is_equivalent_to(sharding, restored.ndim)
    np.testing.assert_array_equal(np.asarray(restored), expected)


def test_rng_and_primitives_survive_device_staging(tmp_path):
    key = jax.random.key(7)
    dev = jnp.full(8, 2.0, jnp.float32)
    app_state = {
        "m": StateDict({"key": key, "step": 42, "lr": 1e-3, "dev": dev})
    }
    with knobs.override_async_staging("device"):
        pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
        snapshot = pending.wait()
    dst = {"m": StateDict({})}
    snapshot.restore(dst)
    assert dst["m"]["step"] == 42
    assert dst["m"]["lr"] == pytest.approx(1e-3)
    np.testing.assert_array_equal(
        jax.random.key_data(dst["m"]["key"]), jax.random.key_data(key)
    )


def test_checksums_present_in_committed_manifest(tmp_path):
    """Device staging moves checksum computation to the background thread;
    the committed manifest must still carry them (the round-3 sync-path
    guarantee, snapshot.py manifest-gathered-post-staging)."""
    dev = jnp.ones((64, 64), jnp.float32)
    app_state = {"m": StateDict({"w": dev})}
    with knobs.override_async_staging("device"):
        pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
        snapshot = pending.wait()
    manifest = snapshot.get_manifest()
    payload_entries = [
        e for e in manifest.values() if getattr(e, "checksum", None) is not None
    ]
    assert payload_entries, "no checksummed payload entries in manifest"


def test_no_sidecars_left_behind(tmp_path):
    dev = jnp.ones(64, jnp.float32)
    app_state = {"m": StateDict({"w": dev})}
    with knobs.override_async_staging("device"):
        Snapshot.async_take(str(tmp_path / "snap"), app_state).wait()
    leftovers = [p.name for p in (tmp_path / "snap").iterdir() if "manifest_rank" in p.name]
    assert leftovers == []


def test_prepickled_holds_bytes():
    p = PrePickled({"a": 1})
    assert isinstance(p.data, bytes) and p.obj_type == "dict"


def test_device_staging_with_slow_storage_returns_fast(tmp_path):
    """The headline: stall decoupled from BOTH storage and D2H. With device
    staging the return happens before any serialization at all."""
    import time
    from unittest import mock

    from torchsnapshot_tpu.storage_plugins import fs as fs_mod

    class SlowFS(fs_mod.FSStoragePlugin):
        async def write(self, write_io):
            import asyncio

            await asyncio.sleep(0.3)
            await super().write(write_io)

    dev = jnp.ones((128, 128), jnp.float32)
    app_state = {"m": StateDict({"w": dev})}
    with knobs.override_async_staging("device"):
        with mock.patch.object(fs_mod, "FSStoragePlugin", SlowFS):
            begin = time.monotonic()
            pending = Snapshot.async_take(str(tmp_path / "snap"), app_state)
            stall = time.monotonic() - begin
            snapshot = pending.wait()
            total = time.monotonic() - begin
    assert stall < total and total >= 0.3
    assert stall < 0.25, f"device-staged async_take blocked {stall:.2f}s"
    dst = {"m": StateDict({})}
    snapshot.restore(dst)
    np.testing.assert_array_equal(np.asarray(dst["m"]["w"]), np.ones((128, 128)))


# ----------------------------------------------------- restore H2D batching

H2D_THREADS = ("tpusnap-h2d-dispatcher", "tpusnap-h2d-lander")


def _no_h2d_thread_alive():
    return not [t for t in threading.enumerate() if t.name in H2D_THREADS]


def test_h2d_batcher_incremental_flush():
    from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    pool = HostBufferPool()
    pool.batcher = b = H2DBatcher(pool, flush_bytes=64)  # tiny: every submit flushes
    like = jnp.zeros(16, jnp.float32)
    f1, f2 = Future(), Future()
    b.submit(np.arange(16, dtype=np.float32), like, f1)
    b.submit(np.arange(16, dtype=np.float32) * 2, like, f2)
    b.drain()  # a flush is a hand-off: the drain is what waits
    np.testing.assert_array_equal(np.asarray(f1.obj), np.arange(16))
    np.testing.assert_array_equal(np.asarray(f2.obj), np.arange(16) * 2)
    pool.close()
    assert _no_h2d_thread_alive()


def test_h2d_batcher_dtype_cast():
    from torchsnapshot_tpu.h2d import HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    pool = HostBufferPool()
    b = pool.batcher
    like = jnp.zeros(8, jnp.bfloat16)
    f = Future()
    b.submit(np.arange(8, dtype=np.float32), like, f)
    b.drain()
    pool.close()
    assert f.obj.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(f.obj, dtype=np.float32), np.arange(8))


def test_h2d_batcher_drain_lands_and_attributes():
    """drain() leaves nothing in flight and the landing time is attributed
    to the byte-carrying h2d_land phase (r04 verdict: 159 s of restore wall
    was invisible to every phase)."""
    from torchsnapshot_tpu import phase_stats
    from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    phase_stats.reset()
    pool = HostBufferPool()
    pool.batcher = b = H2DBatcher(pool, flush_bytes=64, inflight_cap_bytes=128)
    like = jnp.zeros(16, jnp.float32)
    futs = [Future() for _ in range(4)]
    for i, f in enumerate(futs):
        b.submit(np.full(16, float(i), dtype=np.float32), like, f)
    b.drain()
    pool.close()
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(np.asarray(f.obj), np.full(16, float(i)))
    assert b._unlanded_bytes == 0 and not b._queued
    assert not b._dispatching and not b._landing
    stats = phase_stats.snapshot()
    assert stats.get("h2d_land", {}).get("bytes", 0) > 0
    assert stats.get("h2d_dispatch", {}).get("bytes", 0) > 0


class _HeldDispatch:
    """``H2DBatcher._dispatch`` recorded (the thread it ran on, its items'
    first elements, the unlanded bytes it found reserved) and, while ``hold``
    is clear, held at its entry."""

    def __init__(self, monkeypatch, held=True):
        from torchsnapshot_tpu.h2d import H2DBatcher

        self.calls = []
        self.entered = threading.Event()
        self.hold = threading.Event()
        if not held:
            self.hold.set()
        real = H2DBatcher._dispatch

        def recording(batcher, items):
            self.calls.append(
                (
                    threading.current_thread().name,
                    [float(host.reshape(-1)[0]) for host, *_ in items],
                    batcher._unlanded_bytes,
                )
            )
            self.entered.set()
            assert self.hold.wait(10)
            return real(batcher, items)

        monkeypatch.setattr(H2DBatcher, "_dispatch", recording)


def test_h2d_flush_returns_without_a_dispatch_on_the_calling_thread(monkeypatch):
    """``flush`` only queues: the batch's ``device_put`` runs on the
    batcher's dispatcher, never on the thread that finalised the leaf (in a
    restore, the read pipeline's loop thread)."""
    from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    held = _HeldDispatch(monkeypatch)
    pool = HostBufferPool()
    pool.batcher = b = H2DBatcher(pool, flush_bytes=64)
    f = Future()
    begin = time.monotonic()
    b.submit(np.full(16, 7.0, dtype=np.float32), jnp.zeros(16, jnp.float32), f)
    # the submit flushed, and came back while the dispatch is still held
    assert held.entered.wait(5)
    assert time.monotonic() - begin < 5 and f.obj is None
    held.hold.set()
    b.drain()
    pool.close()
    assert [name for name, _, _ in held.calls] == ["tpusnap-h2d-dispatcher"]
    assert threading.current_thread().name != "tpusnap-h2d-dispatcher"
    np.testing.assert_array_equal(np.asarray(f.obj), np.full(16, 7.0))
    route = b.route()
    assert 0.0 <= route.pop("cpu_s")
    assert route == {"bytes": 64, "leaves": 1}
    assert _no_h2d_thread_alive()


def test_h2d_what_is_flushed_while_the_dispatcher_is_busy_goes_when_it_comes_free(
    monkeypatch,
):
    """Submits go on gathering behind a busy dispatcher, whether or not they
    reach ``flush_bytes``; what was flushed meanwhile is ONE batch when it
    comes free, and what was not waits for its own flush or the drain."""
    from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    held = _HeldDispatch(monkeypatch)
    pool = HostBufferPool()
    # two leaves of 64 bytes a flush
    pool.batcher = b = H2DBatcher(pool, flush_bytes=128)
    like = jnp.zeros(16, jnp.float32)
    futs = [Future() for _ in range(7)]
    for i in (0, 1):
        b.submit(np.full(16, float(i), dtype=np.float32), like, futs[i])
    assert held.entered.wait(5)  # the first batch is with the dispatcher
    for i in (2, 3, 4, 5, 6):  # two more flushes, and one leaf under the threshold
        b.submit(np.full(16, float(i), dtype=np.float32), like, futs[i])
    assert len(b._queued) == 4 and len(b._items) == 1 and len(held.calls) == 1
    held.hold.set()
    deadline = time.monotonic() + 5
    while len(held.calls) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    # the dispatcher, free again, took both flushes as one batch; nobody has
    # flushed the seventh leaf
    assert [firsts for _, firsts, _ in held.calls] == [[0.0, 1.0], [2.0, 3.0, 4.0, 5.0]]
    assert len(b._items) == 1 and futs[6].obj is None
    b.drain()
    pool.close()
    assert [firsts for _, firsts, _ in held.calls][2:] == [[6.0]]
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(np.asarray(f.obj), np.full(16, float(i)))
    assert {name for name, _, _ in held.calls} == {"tpusnap-h2d-dispatcher"}


def test_h2d_batcher_paces_inflight_window(monkeypatch):
    """Dispatches past the in-flight-bytes window land earlier batches first
    — the window is what lets landings overlap the remaining reads instead
    of piling up behind the caller's final sync.  With the dispatcher in
    between the reservation still holds (no dispatch ever finds more than
    the cap reserved), the wait is the dispatcher's, and the thread that
    flushes is never held."""
    import jax as jax_mod

    from torchsnapshot_tpu import phase_stats
    from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    held = _HeldDispatch(monkeypatch)
    landing = threading.Event()  # the lander is held: the window stays shut
    real_ready = jax_mod.block_until_ready

    def slow_landing(x):
        if threading.current_thread().name == "tpusnap-h2d-lander":
            assert landing.wait(10)
        return real_ready(x)

    monkeypatch.setattr(jax_mod, "block_until_ready", slow_landing)
    before = phase_stats.snapshot()
    pool = HostBufferPool()
    pool.batcher = b = H2DBatcher(pool, flush_bytes=64, inflight_cap_bytes=64)
    like = jnp.zeros(16, jnp.float32)  # 64 bytes: every submit flushes
    futs = [Future() for _ in range(4)]
    begin = time.monotonic()
    for i, f in enumerate(futs):
        b.submit(np.full(16, float(i), dtype=np.float32), like, f)
    assert time.monotonic() - begin < 2  # no submit waited for the window
    assert held.entered.wait(5)
    held.hold.set()
    time.sleep(0.1)
    # one batch of no more than the cap is out, unlanded; the rest wait behind
    # the window, on the host, however much has queued up meanwhile
    assert len(held.calls) == 1 and b._unlanded_bytes == 64 and len(b._queued) == 2
    landing.set()
    b.drain()
    pool.close()
    assert b._unlanded_bytes == 0
    assert [reserved for _, _, reserved in held.calls] == [64] * 4
    waited = phase_stats.delta(before).get("h2d_window_wait", {})
    assert waited.get("n", 0) >= 1 and waited["wall"] >= 0.04
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(np.asarray(f.obj), np.full(16, float(i)))


def test_h2d_drain_waits_for_queued_batches_and_leaves_no_thread(monkeypatch):
    from torchsnapshot_tpu import phase_stats
    from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    held = _HeldDispatch(monkeypatch)
    before = phase_stats.snapshot()
    pool = HostBufferPool()
    pool.batcher = b = H2DBatcher(pool, flush_bytes=64)
    like = jnp.zeros(16, jnp.float32)
    futs = [Future() for _ in range(4)]
    b.submit(np.full(16, 0.0, dtype=np.float32), like, futs[0])
    assert held.entered.wait(5)
    for i in (1, 2, 3):
        b.submit(np.full(16, float(i), dtype=np.float32), like, futs[i])
    assert len(b._queued) == 3
    drained = threading.Event()
    drainer = threading.Thread(target=lambda: (b.drain(), drained.set()), daemon=True)
    drainer.start()
    assert not drained.wait(0.1)  # a batch dispatching, three queued: not yet
    held.hold.set()
    assert drained.wait(10)
    drainer.join(5)
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(np.asarray(f.obj), np.full(16, float(i)))
    assert b._unlanded_bytes == 0 and not b._queued and not b._landing
    pool.close()
    assert _no_h2d_thread_alive()
    # its threads are gone: what is flushed now is sent by the thread that
    # flushes (nothing handed over is ever left undone), and counted as such
    late = Future()
    b.submit(np.full(16, 9.0, dtype=np.float32), like, late)
    np.testing.assert_array_equal(np.asarray(late.obj), np.full(16, 9.0))
    assert held.calls[-1][0] == threading.current_thread().name
    route = b.route()
    assert route["bytes"] == 5 * 64 and route["leaves"] == 5
    # four calls: the first leaf, the queued three as far as the cap reaches
    # (two, then one), and the late leaf
    assert phase_stats.delta(before)["h2d_dispatch"]["n"] == 4 == len(held.calls)


def test_h2d_batcher_bad_item_fails_alone(caplog):
    """One bad item must not sink the batch: good arrays restore, the bad
    one's error surfaces with correct attribution (advisor r4 finding), at
    the next flush and at the drain."""
    from torchsnapshot_tpu.h2d import HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    mesh = _mesh8()
    good_sharded = jax.device_put(
        jnp.zeros((8, 4), jnp.float32), NamedSharding(mesh, P("x", None))
    )

    class _Bad:
        # A sharded target the host buffer cannot satisfy: length 7 is not
        # divisible over the 8-way mesh axis — device_put raises.
        dtype = np.float32
        sharding = NamedSharding(_mesh8(), P("x"))

    pool = HostBufferPool()
    b = pool.batcher
    f_plain, f_sharded, f_bad = Future(), Future(), Future()
    b.submit(np.ones(8, dtype=np.float32), jnp.zeros(8, jnp.float32), f_plain)
    b.submit(np.ones((8, 4), dtype=np.float32), good_sharded, f_sharded)
    b.submit(np.ones(7, dtype=np.float32), _Bad(), f_bad)
    with caplog.at_level("WARNING", logger="torchsnapshot_tpu"):
        b.flush()  # the hand-off itself raises nothing
        with pytest.raises(Exception) as first:
            b.drain()
    # The failed batch's first exception is in the log before the per-item
    # retry runs (an HBM OOM would otherwise vanish with a retry that
    # succeeds); chip_smoke.py fails on any library warning.
    failed = [r for r in caplog.records if "batched device_put" in r.getMessage()]
    assert len(failed) == 1 and failed[0].exc_info is not None
    # The retried good items both restored.
    np.testing.assert_array_equal(np.asarray(f_plain.obj), np.ones(8))
    np.testing.assert_array_equal(np.asarray(f_sharded.obj), np.ones((8, 4)))
    assert f_bad.obj is None
    # sticky: the same error at every later flush and drain
    for call in (b.flush, b.drain):
        with pytest.raises(Exception) as again:
            call()
        assert again.value is first.value
    pool.close()
    assert _no_h2d_thread_alive()


def test_h2d_dispatch_error_surfaces_at_the_next_flush_and_sends_nothing_more():
    from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    class _Bad:
        dtype = np.float32
        sharding = NamedSharding(_mesh8(), P("x"))

    pool = HostBufferPool()
    pool.batcher = b = H2DBatcher(pool, flush_bytes=16)
    like = jnp.zeros(8, jnp.float32)
    f_bad, f_next, f_last = Future(), Future(), Future()
    b.submit(np.ones(7, dtype=np.float32), _Bad(), f_bad)  # flushed: fails on the dispatcher
    with b._cond:
        assert b._cond.wait_for(lambda: b._error is not None, 10)
    # the submit that flushes next meets it; its leaf stays on the host
    with pytest.raises(Exception) as raised:
        b.submit(np.ones(8, dtype=np.float32), like, f_next)
    assert raised.value is b._error
    with pytest.raises(Exception):
        b.drain()
    assert f_bad.obj is None and f_next.obj is None
    assert b._unlanded_bytes == 0 and not b._dispatching and not b._landing
    assert b.route()["bytes"] == 0
    pool.close()
    assert _no_h2d_thread_alive()


def test_h2d_batcher_lander_error_surfaces(monkeypatch):
    """A landing failure must not wedge the batcher: the error surfaces at
    the next flush and at drain, byte accounting stays exact, and the pool's
    close still joins its threads cleanly."""
    import jax as jax_mod

    from torchsnapshot_tpu.h2d import H2DBatcher, HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    calls = {"n": 0}
    orig = jax_mod.block_until_ready

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("forced landing failure")
        return orig(x)

    monkeypatch.setattr(jax_mod, "block_until_ready", flaky)
    pool = HostBufferPool()
    pool.batcher = b = H2DBatcher(pool, flush_bytes=64, inflight_cap_bytes=1 << 30)
    like = jnp.zeros(16, jnp.float32)
    f1, f2 = Future(), Future()
    b.submit(np.ones(16, dtype=np.float32), like, f1)  # landing fails
    with b._cond:
        assert b._cond.wait_for(lambda: b._error is not None, 10)
    with pytest.raises(RuntimeError, match="forced landing failure"):
        b.submit(np.ones(16, dtype=np.float32), like, f2)  # the next flush
    with pytest.raises(RuntimeError, match="forced landing failure"):
        b.drain()
    assert b._unlanded_bytes == 0 and not b._landing
    pool.close()
    pool.close()  # idempotent, returns without hanging
    assert _no_h2d_thread_alive()


def test_h2d_batcher_mixed_targets():
    """Plain-device and sharded targets in one batch both restore."""
    from torchsnapshot_tpu.h2d import HostBufferPool
    from torchsnapshot_tpu.io_types import Future

    pool = HostBufferPool()
    b = pool.batcher
    mesh = _mesh8()
    sharded_like = jax.device_put(
        jnp.zeros((8, 4), jnp.float32), NamedSharding(mesh, P("x", None))
    )
    plain_like = jnp.zeros(8, jnp.float32)
    f1, f2 = Future(), Future()
    b.submit(np.ones((8, 4), dtype=np.float32), sharded_like, f1)
    b.submit(np.full(8, 3.0, dtype=np.float32), plain_like, f2)
    b.drain()
    pool.close()
    np.testing.assert_array_equal(np.asarray(f1.obj), np.ones((8, 4)))
    assert f1.obj.sharding.is_equivalent_to(sharded_like.sharding, 2)
    np.testing.assert_array_equal(np.asarray(f2.obj), np.full(8, 3.0))
