"""A restore that accounts for itself: the driver phases of the restore path
(``restore_open``, ``plan_read``, ``read_starved``, ``h2d_window_wait``,
``h2d_drain``, ``load_state``) are ``phase_stats`` leaves, what no phase
covers is the ``restore_unattributed`` counter, the ``restore.end`` event
carries the one call's account, and under a ``jax.profiler`` session every
phase is a host event of the trace, on the trace's clock.  One read pipeline
serves the whole restore (``tests/test_restore_read_ahead.py``), so what
belongs to the pipeline comes once a restore and what belongs to the loader
once a stateful."""

import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import SnapshotManager, StateDict, knobs, phase_stats
from torchsnapshot_tpu.event_handlers import (
    register_event_handler,
    unregister_event_handler,
)
from torchsnapshot_tpu.telemetry import analyze, trace as ttrace

STATEFULS = ("params", "mu", "nu", "meta")
DRIVER = ("restore_open", "plan_read", "read_starved", "h2d_drain", "load_state")
READS = ("fs_read", "native_read", "mem_read")


def make_state(seed=0, zero=False, leaves=5, shape=(256, 512)):
    rng = np.random.RandomState(seed)
    return {
        key: StateDict(
            {
                f"w{i}": jnp.zeros(shape, jnp.float32)
                if zero
                else jnp.asarray(rng.rand(*shape).astype(np.float32))
                for i in range(leaves)
            }
        )
        for key in STATEFULS
    }


def assert_restored(target, saved):
    for key in STATEFULS:
        for name, want in saved[key].state_dict().items():
            np.testing.assert_array_equal(
                np.asarray(target[key].state_dict()[name]), np.asarray(want)
            )


class Observed:
    """One restore_latest with the hook and an event handler installed."""

    def __init__(self, root, saved=None):
        self.saved = saved if saved is not None else make_state()
        self.manager = SnapshotManager(root)
        if saved is None:
            self.manager.save(1, self.saved)
        self.hooked = []
        self.ends = []  # the metadata of each restore.end

    def restore(self):
        def on_event(event):
            if event.name == "restore.end":
                self.ends.append(dict(event.metadata))

        target = make_state(zero=True)
        before = phase_stats.snapshot()
        register_event_handler(on_event)
        phase_stats.set_trace_hook(
            lambda phase, begin, end, nbytes: self.hooked.append((phase, begin, end))
        )
        try:
            assert self.manager.restore_latest(target) == 1
        finally:
            phase_stats.set_trace_hook(None)
            unregister_event_handler(on_event)
        self.delta = phase_stats.delta(before)
        assert_restored(target, self.saved)
        return self


@pytest.fixture(params=["fs", "memory"])
def observed(request, tmp_path):
    if request.param == "fs":
        root = str(tmp_path / "root")
    else:
        root = f"memory://phases_{os.getpid()}_{time.monotonic_ns()}"
    return Observed(root).restore()


def test_each_driver_phase_is_in_the_delta_with_its_count(observed):
    d = observed.delta
    for phase in DRIVER:
        assert phase in d, (phase, sorted(d))
        assert d[phase]["wall"] > 0
    # once a stateful
    for phase in ("plan_read", "h2d_drain", "load_state"):
        assert d[phase]["n"] == len(STATEFULS), (phase, d[phase])
    # restore_latest's listing, then Snapshot.restore before the manifest's
    # read and after it
    assert d["restore_open"]["n"] == 3
    # the tail after the pipeline's last read is a stretch, whatever else is:
    # once a restore, no longer once a stateful
    assert d["read_starved"]["n"] >= 1
    assert any(r in d for r in READS)


def test_no_driver_phase_encloses_a_storage_read_of_its_own_stateful(observed):
    """The next stateful's reads run under ``h2d_drain`` and ``load_state``
    by design; a stateful's own are over before its drain begins, and no
    payload is read before the last ``plan_read`` ends."""
    spans = sorted(
        (b, e, phase) for phase, b, e in observed.hooked if phase in ("h2d_drain", "load_state")
    )
    assert [phase for _, _, phase in spans] == ["h2d_drain", "load_state"] * len(STATEFULS)
    planned = max(e for phase, _, e in observed.hooked if phase == "plan_read")
    reads = [(b, e) for phase, b, e in observed.hooked if phase in READS]
    # the manifest's read is before the plans, every payload's after them
    straddling = [(b, e) for b, e in reads if b < planned < e]
    assert not straddling, straddling
    reads = [(b, e) for b, e in reads if b >= planned]
    assert len(reads) >= len(STATEFULS)
    # the statefuls' payloads differ in nothing but their key, so the k-th
    # quarter of the reads (by end) is the least that stateful k has read
    # before its drain begins
    ends = sorted(e for _, e in reads)
    per = len(ends) // len(STATEFULS)
    for k in range(len(STATEFULS)):
        drain_begin = spans[2 * k][0]
        assert sum(1 for e in ends if e <= drain_begin) >= per * (k + 1), k
    for phase, begin, end in observed.hooked:
        if phase in ("restore_open", "plan_read"):
            assert not [(b, e) for b, e in reads if b >= begin and e <= end], phase


def test_unattributed_is_a_counter_and_names_no_gap(observed):
    slot = observed.delta["restore_unattributed"]
    assert slot["n"] == 1 and slot["s"] >= 0
    assert "wall" not in slot
    assert "restore_unattributed" not in {phase for phase, _, _ in observed.hooked}
    assert phase_stats.format_line(observed.delta)  # renders without a wall


def test_the_phases_and_the_remainder_make_the_call(observed):
    (meta,) = observed.ends
    duration = meta["duration_s"]
    # the call begins where its own first restore_open does (the one before
    # it is restore_latest's listing)
    begin = [b for phase, b, _ in observed.hooked if phase == "restore_open"][1]
    end = begin + duration
    clipped = [
        (max(b, begin), min(e, end))
        for _, b, e in observed.hooked
        if e > begin and b < end
    ]
    union = sum(e - b for b, e in phase_stats._merge(clipped))
    assert union + meta["unattributed_s"] == pytest.approx(duration, rel=0.01)
    assert 0 <= meta["unattributed_s"] < duration


def test_the_end_event_carries_the_calls_own_account(observed):
    (meta,) = observed.ends
    assert meta["is_success"] is True
    assert meta["unattributed_s"] == pytest.approx(
        observed.delta["restore_unattributed"]["s"]
    )
    phases = meta["phases"]
    for phase in ("plan_read", "read_starved", "h2d_drain", "load_state"):
        assert phases[phase] == pytest.approx(observed.delta[phase]["wall"], rel=1e-6, abs=1e-9)
    # the listing in restore_latest is before the call, so not in its account
    assert 0 < phases["restore_open"] < observed.delta["restore_open"]["wall"]
    assert all(0 <= wall <= meta["duration_s"] + 1e-9 for wall in phases.values())
    json.dumps(meta)  # an event's metadata is JSON


def test_a_second_restore_accounts_for_itself_alone(tmp_path):
    first = Observed(str(tmp_path / "root")).restore()
    again = Observed(str(tmp_path / "root"), saved=first.saved).restore()
    (meta,) = again.ends
    assert again.delta["plan_read"]["n"] == len(STATEFULS)
    assert meta["phases"]["plan_read"] == pytest.approx(
        again.delta["plan_read"]["wall"], rel=1e-6, abs=1e-9
    )
    assert meta["phases"]["plan_read"] < meta["duration_s"]


def test_a_small_h2d_window_records_h2d_window_wait(tmp_path, monkeypatch):
    from torchsnapshot_tpu.io_preparers import array as array_mod

    real_init = array_mod.H2DBatcher.__init__
    monkeypatch.setattr(
        array_mod.H2DBatcher,
        "__init__",
        lambda self, *a, **k: real_init(self, flush_bytes=1, inflight_cap_bytes=1),
    )
    real_block = jax.block_until_ready

    def slow_landing(x):
        time.sleep(0.02)
        return real_block(x)

    saved = Observed(str(tmp_path / "root"))
    monkeypatch.setattr(jax, "block_until_ready", slow_landing)
    observed = saved.restore()
    monkeypatch.setattr(jax, "block_until_ready", real_block)
    wait = observed.delta["h2d_window_wait"]
    # every flush after a stateful's first waits for the one before it to land
    assert wait["n"] >= len(STATEFULS) and wait["wall"] >= 0.01 * wait["n"]
    assert "h2d_window_wait" in observed.ends[0]["phases"]
    assert analyze.classify_phase("h2d_window_wait") in analyze.WAIT_GROUPS


def test_a_wait_shorter_than_a_millisecond_is_not_recorded(observed):
    # the default window (512 MiB) is never reached by 10 MiB of state
    assert "h2d_window_wait" not in observed.delta


def test_the_chrome_trace_draws_each_phase_once_a_site(tmp_path):
    saved = Observed(str(tmp_path / "root"))
    trace_dir = tmp_path / "traces"
    with knobs.override_trace_dir(str(trace_dir)):
        saved.restore()
    (path,) = glob.glob(str(trace_dir / f"restore-*{ttrace.TRACE_FILE_SUFFIX}"))
    doc = json.load(open(path))
    assert ttrace.validate_trace(doc) == []
    spans = [ev for ev in doc["traceEvents"] if ev.get("ph") == "X"]
    count = lambda name: sum(1 for ev in spans if ev["name"] == name)
    for name in ("plan_read", "h2d_drain", "load_state", "load_stateful"):
        assert count(name) == len(STATEFULS), (name, count(name))
    # one pipeline a restore, over as many groups as statefuls
    (pipeline,) = [ev for ev in spans if ev["name"] == "read_pipeline"]
    assert pipeline["args"]["n_groups"] == len(STATEFULS)
    # restore_latest's listing is before the operation: the call's own two
    assert count("restore_open") == 2
    assert count("read_starved") >= 1
    # leaves, not structure
    for ev in spans:
        if ev["name"] in DRIVER:
            assert ev["cat"] == "phase", ev
    for name in ("load_stateful", "read_pipeline"):
        assert all(ev["cat"] != "phase" for ev in spans if ev["name"] == name)


@pytest.mark.parametrize(
    "phase,group",
    [
        ("restore_open", "driver"),
        ("plan_read", "driver"),  # not storage_io, for all its suffix
        ("load_state", "driver"),
        ("read_starved", "read_starved"),
        ("h2d_window_wait", "h2d_wait"),
        ("h2d_drain", "h2d_wait"),
        ("host_buffer_wait", "h2d_wait"),
        ("host_pool_free", "driver"),
        ("arena_populate", "driver"),
    ],
)
def test_every_new_phase_has_a_resource_group(phase, group):
    assert analyze.classify_phase(phase) == group
    assert (group in analyze.WAIT_GROUPS) == (group != "driver")


# ------------------------------------------------------- phase_stats itself


def test_open_interval_records_like_timed_and_closes_once():
    before = phase_stats.snapshot()
    seen = []
    phase_stats.set_trace_hook(lambda p, b, e, n: seen.append((p, b, e, n)))
    try:
        iv = phase_stats.open_interval("restore_open")
        time.sleep(0.002)
        iv.close(nbytes=7)
        iv.close(nbytes=7)
        short = phase_stats.open_interval("io_slot_wait")
        short.close(min_s=10.0)
        dropped = phase_stats.open_interval("h2d_dispatch")
        dropped.drop()
        dropped.close()
    finally:
        phase_stats.set_trace_hook(None)
    d = phase_stats.delta(before)
    assert d["restore_open"]["n"] == 1 and d["restore_open"]["bytes"] == 7
    assert d["restore_open"]["wall"] >= 0.002
    assert "io_slot_wait" not in d and "h2d_dispatch" not in d
    assert [(p, n) for p, _, _, n in seen] == [("restore_open", 7)]
    assert seen[0][1] == iv.begin


def test_walls_between_clips_to_the_window():
    phase_stats.add("clip_a", 2.0, end=1000012.0)  # 1000010-1000012
    phase_stats.add("clip_a", 2.0, end=1000013.0)  # 1000011-1000013: union 3
    phase_stats.add("clip_b", 1.0, end=1000020.0)  # 1000019-1000020
    walls = phase_stats.walls_between(1000011.5, 1000019.5)
    assert walls["clip_a"] == pytest.approx(1.5) and walls["clip_b"] == pytest.approx(0.5)
    assert phase_stats.attributed_wall_s(1000011.5, 1000019.5) == pytest.approx(2.0)
    assert "clip_a" not in phase_stats.walls_between(1000013.0, 1000019.0)
    assert phase_stats.attributed_wall_s(1000013.0, 1000019.0) == 0.0
    # unclipped, the old reading: everything
    assert phase_stats.attributed_wall_s() >= 4.0


def test_add_counter_has_no_interval():
    before = phase_stats.snapshot()
    wall_before = phase_stats.attributed_wall_s()
    seen = []
    phase_stats.set_trace_hook(lambda *a: seen.append(a))
    try:
        phase_stats.add_counter("restore_unattributed", 0.25)
        phase_stats.add_counter("restore_unattributed", 0.5)
    finally:
        phase_stats.set_trace_hook(None)
    slot = phase_stats.delta(before)["restore_unattributed"]
    assert slot == {"s": pytest.approx(0.75), "bytes": 0, "n": 2}
    assert seen == [] and phase_stats.attributed_wall_s() == wall_before
    assert "restore_unattributed" not in phase_stats.walls_between(float("-inf"), float("inf"))


# ------------------------------------------------------------- one clock


def host_events(xplane):
    """``{name: [(begin_ns, end_ns)]}`` of every host plane's events."""
    out = {}
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                )
    return out


def test_every_timed_phase_is_a_host_event_on_the_traces_clock(tmp_path):
    saved = Observed(str(tmp_path / "root"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
    try:
        # as chipbench/trace.py does it: a clock reading, then the annotation
        sync_mono_ns = time.monotonic_ns()
        with jax.profiler.TraceAnnotation("phases_sync"):
            pass
        saved.restore()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = host_events(xplane)
    shift_ns = events["phases_sync"][0][0] - sync_mono_ns
    annotated = set(DRIVER) | {"h2d_dispatch", "h2d_land"}
    checked = {}
    for phase, begin, end in saved.hooked:
        if phase not in annotated:
            continue
        want = (begin * 1e9 + shift_ns, end * 1e9 + shift_ns)
        assert phase in events, (phase, sorted(events))
        nearest = min(events[phase], key=lambda ev: abs(ev[0] - want[0]))
        assert abs(nearest[0] - want[0]) < 1e6, (phase, nearest, want)
        assert abs(nearest[1] - want[1]) < 1e6, (phase, nearest, want)
        checked[phase] = checked.get(phase, 0) + 1
    assert set(checked) == annotated, checked
    assert checked["plan_read"] == len(STATEFULS)
    # the read pipeline's own waits are passed on every read, recorded or not
    assert len(events.get("io_slot_wait", ())) >= len(STATEFULS)
