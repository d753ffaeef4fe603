"""The reduction from a profiler trace to busy seconds, top operations and
named idle gaps: on planes written by hand, where every number is known, and
on a trace recorded from the profiler (``data/*.xplane.pb``)."""

import os
from types import SimpleNamespace as NS

import pytest

from chipbench import trace as tr
from conftest import ROOT

S = 1_000_000_000  # ns


def ev(name, start_s, dur_s):
    return NS(name=name, start_ns=start_s * S, duration_ns=dur_s * S)


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev(tr.SYNC_NAME, 1.0, 0.0), ev(tr.WINDOW_NAME, 2.0, 10.0)])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step", 2.0, 4.0)]),
        NS(name="Steps", events=[ev("0", 0.0, 20.0)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 1.0, 0.5),              # before the window: clipped away
            ev("fusion.1", 2.0, 1.0), ev("convolution.2", 2.5, 1.5),   # overlap: union 2.0-4.0
            ev("fusion.1", 7.0, 1.0),              # gap 4.0-7.0
            ev("copy.3", 11.5, 1.0),               # gap 8.0-11.5, then clipped at 12.0
        ])])
    return [host, device]


def test_busy_is_the_union_clipped_to_the_window():
    r = tr.reduce_planes(planes())
    assert r["devices_traced"] == 1 and r["sync_found"]
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(2.0 + 1.0 + 0.5)
    assert r["idle_gap_s"] == pytest.approx(3.0 + 3.5)
    ops = dict(map(tuple, r["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(2.5) and ops["convolution.2"] == pytest.approx(1.5)
    assert r["device_ops"][0][0] == "fusion.1"
    assert len(r["device_ops"]) <= 10


def test_gaps_are_named_by_what_the_host_was_doing():
    # monotonic clock: the sync reading was taken at 101.0 s, so shift is -100 s
    spans = [("save_call", 104.0, 107.0), ("train_step", 107.0, 108.0), ("restore_call", 108.0, 111.4)]
    phases = [("device_stage", 104.2, 106.9), ("native_read", 108.1, 109.0)]
    r = tr.reduce_planes(planes(), host_spans=spans, host_phases=phases, sync_mono_ns=101 * S)
    gaps = dict(map(tuple, r["idle_gaps"]))
    # 4.0-7.0 is covered by device_stage (2.7 of 3.0 s): the library's phase wins
    assert gaps["device_stage"] == pytest.approx(3.0)
    # 8.0-11.5: native_read covers 0.9 of 3.5 s, under half; the job's restore_call covers it
    assert gaps["restore_call"] == pytest.approx(3.5)
    assert sum(gaps.values()) == pytest.approx(r["idle_gap_s"])


def test_without_a_sync_reading_gaps_stay_untagged():
    r = tr.reduce_planes(planes(), host_spans=[("save_call", 104.0, 107.0)])
    assert dict(map(tuple, r["idle_gaps"])) == {"untagged": pytest.approx(6.5)}


def test_two_chips_average_and_a_silent_chip_counts_as_idle():
    two = planes() + [NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[ev("f", 2.0, 1.5)])])]
    assert tr.reduce_planes(two, n_devices=2)["busy_s"] == pytest.approx((3.5 + 1.5) / 2)
    assert tr.reduce_planes(planes(), n_devices=4)["busy_s"] == pytest.approx(3.5 / 4)


def test_a_trace_with_no_device_plane_reads_nothing():
    r = tr.reduce_planes(planes()[:1])
    assert r["busy_s"] is None and r["device_ops"] == [] and r["window_s"] == pytest.approx(10.0)


def test_merge_and_clip():
    assert tr.merge([(3, 4), (1, 2), (1.5, 3.5)]) == [(1, 4)]
    assert tr.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


RECORDED = {
    # the traced run of mistral7b.kill-resume on the chip, 51 s, seed 2147497101 (my chip run, PR 24)
    "tpu_mistral7b_kill-resume.xplane.pb.gz": dict(devices=1, busy_s=1.3866112259980388,
                                                  window_s=56.33167370300001),
    # the rehearsal on the CPU: host planes only
    "cpu_tiny_kill-resume.xplane.pb.gz": dict(devices=0, busy_s=None, window_s=None),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_a_recorded_trace_reduces(name, tmp_path):
    import gzip
    import shutil

    want = RECORDED[name]
    path = str(tmp_path / name[:-3])
    with gzip.open(os.path.join(ROOT, "chipbench", "tests", "data", name)) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    r = tr.reduce_file(path)
    assert r["sync_found"] and r["window_s"] > 0
    assert r["devices_traced"] == want["devices"]
    if want["devices"]:
        assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
        assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
        assert r["busy_s"] + r["idle_gap_s"] <= r["window_s"] + 1e-6
        assert r["busy_s"] + r["idle_gap_s"] > 0.99 * r["window_s"]
        assert len(r["device_ops"]) == 10 and all(s > 0 for _, s in r["device_ops"])
        assert r["device_ops"][0][0].startswith("fusion.32 (f32[4096,32768]")
        assert dict(map(tuple, r["idle_gaps"])) == {"untagged": pytest.approx(r["idle_gap_s"])}
    else:
        assert r["busy_s"] is None and r["device_ops"] == []
