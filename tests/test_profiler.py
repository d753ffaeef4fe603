"""Continuous profiling plane (telemetry/profiler.py): sampler core
(on/off-CPU split, phase tags), Hz=0 disable, per-rank merge, the
``profile diff`` CLI, schema validation, and the phase-attribution
health bar (<5% untagged on-CPU samples on a profiled fs take).
"""

import json
import os
import resource
import threading
import time

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, knobs, phase_stats
from torchsnapshot_tpu.__main__ import main as cli_main
from torchsnapshot_tpu.telemetry import analyze, monitor, profiler


@pytest.fixture(autouse=True)
def _no_leaks():
    yield
    assert monitor._ACTIVE == [], "leaked op monitors"
    assert profiler._OPS == [], "leaked profiler ops"
    assert profiler._SAMPLER is None, "leaked shared sampler"
    assert not any(
        t.name == "tpusnap-profiler" for t in threading.enumerate()
    ), "leaked sampler thread"


# How long a test may go on sampling before it takes what it has: the
# sample counts below are floors for a sampler that ran, not for a box of
# some speed.
_SAMPLING_DEADLINE_S = 30.0


def _wait_until(enough, at_least_s=0.0):
    """Sleep ``at_least_s``, then on until ``enough()`` or the deadline."""
    begin = time.monotonic()
    time.sleep(at_least_s)
    while not enough() and time.monotonic() - begin < _SAMPLING_DEADLINE_S:
        time.sleep(0.05)


def _profile_files(dirpath):
    return sorted(
        str(p)
        for p in os.listdir(dirpath)
        if p.endswith(profiler.PROFILE_FILE_SUFFIX)
    )


# ------------------------------------------------------------ sampler core


def test_busy_vs_sleep_split_and_phase_tags(tmp_path):
    """A busy-loop thread inside timed("checksum") must sample under the
    checksum phase, on-CPU as far as the box let it run; a sleeping thread
    inside timed("fs_write") must sample off-CPU under fs_write."""
    with knobs.override_profile_dir(str(tmp_path)), knobs.override_profile_hz(
        "99"
    ):
        op = profiler.begin_op("take", "cafe" * 8, rank=0)
        assert op is not None
        stop = threading.Event()
        busy_ran = {}

        def busy():
            # The thread's own clock: the process's rusage would credit it
            # with the sampler's CPU time as well, which grows with the
            # threads the process has and is taken under the GIL, off this
            # thread's share.
            ru0 = resource.getrusage(resource.RUSAGE_THREAD)
            began = time.monotonic()
            with phase_stats.timed("checksum"):
                while not stop.is_set():
                    x = 0
                    for i in range(20000):
                        x += i * i
            ru1 = resource.getrusage(resource.RUSAGE_THREAD)
            busy_ran["cpu_s"] = (ru1.ru_utime + ru1.ru_stime) - (
                ru0.ru_utime + ru0.ru_stime
            )
            busy_ran["elapsed_s"] = time.monotonic() - began

        def sleeper():
            with phase_stats.timed("fs_write"):
                stop.wait(_SAMPLING_DEADLINE_S + 5.0)

        threads = [
            threading.Thread(target=busy),
            threading.Thread(target=sleeper),
        ]
        for t in threads:
            t.start()
        # The work lasts a time and not a size, and that time is the
        # sampler's: 0.7 s on a free box, longer where the box gives the
        # sampler's thread fewer turns.
        _wait_until(lambda: profiler._SAMPLER.ticks >= 40, at_least_s=0.7)
        stop.set()
        for t in threads:
            t.join()
        path = profiler.end_op(op)
    assert path is not None and os.path.exists(path)
    doc = json.load(open(path, encoding="utf-8"))
    assert profiler.validate_profile(doc) == []
    meta = doc["tpusnap"]
    assert meta["kind"] == "take" and meta["rank"] == 0
    assert meta["samples_total"] > 30
    checksum = meta["stacks"].get("checksum", {})
    fs_write = meta["stacks"].get("fs_write", {})
    n_checksum_on = sum(checksum.get("on", {}).values())
    n_checksum_off = sum(checksum.get("off", {}).values())
    n_fs_on = sum(fs_write.get("on", {}).values())
    n_fs_off = sum(fs_write.get("off", {}).values())
    # Every sample of the busy thread carries its phase (but a tick that
    # caught it on its way into or out of the block).
    assert n_checksum_on + n_checksum_off > 10
    strays = sum(
        count
        for phase, states in meta["stacks"].items()
        if phase != "checksum"
        for stacks in states.values()
        for stack, count in stacks.items()
        if stack.rsplit(";", 1)[-1].endswith(".busy")
    )
    assert strays <= 2, meta["stacks"].keys()
    # On-CPU as far as the box scheduled it.  A tick calls a thread on-CPU
    # when it ran for half the tick or more, so a thread that got the share
    # S of its time is on-CPU in at least 2S - 1 of it: everything on a free
    # box, nothing that can be promised under 0.5, where the thread IS
    # off-CPU most of the time and the profiler is right to say so.  A
    # quarter is left for jiffy-grained CPU clocks and ticks of uneven length.
    cpu_share = min(1.0, busy_ran["cpu_s"] / busy_ran["elapsed_s"])
    on_share = n_checksum_on / (n_checksum_on + n_checksum_off)
    assert on_share > 2 * cpu_share - 1 - 0.25, (on_share, cpu_share)
    # The sleeper never (beyond jiffy-granularity noise) samples on-CPU.
    assert n_fs_off > 10
    assert n_fs_on <= max(2, n_fs_off // 10)
    # The busy thread's hot frame is attributed by name.
    hot = max(
        checksum.get("on") or checksum.get("off"),
        key=(checksum.get("on") or checksum.get("off")).get,
    )
    assert "busy" in hot.rsplit(";", 1)[-1]
    # Collapsed-text twin rides along, phase-and-state rooted.
    collapsed = path[: -len(profiler.PROFILE_FILE_SUFFIX)] + (
        profiler.COLLAPSED_FILE_SUFFIX
    )
    lines = open(collapsed, encoding="utf-8").read().splitlines()
    assert lines and any(
        l.startswith(("checksum;oncpu;", "checksum;offcpu;")) for l in lines
    )
    assert all(l.rsplit(" ", 1)[1].isdigit() for l in lines)


def test_hz_zero_disables_cleanly(tmp_path):
    """TPUSNAP_PROFILE_HZ=0 with a profile dir set: no sampler thread,
    no profile files, begin_op returns None and end_op(None) is a
    no-op."""
    with knobs.override_profile_dir(str(tmp_path)), knobs.override_profile_hz(
        "0"
    ):
        assert not profiler.enabled()
        assert knobs.get_profile_hz() == 0.0
        op = profiler.begin_op("take", "dead" * 8, rank=0)
        assert op is None
        assert profiler.end_op(op) is None
        Snapshot.take(
            str(tmp_path / "snap"),
            {"m": StateDict({"w": np.ones((32, 32), np.float32)})},
        )
    assert not any(
        t.name == "tpusnap-profiler" for t in threading.enumerate()
    )
    assert _profile_files(tmp_path) == []


def test_profiling_off_by_default(tmp_path):
    assert knobs.get_profile_dir() is None or True  # env-independent guard
    with knobs.override_profile_dir(None):
        assert not profiler.enabled()
        assert profiler.begin_op("take", "beef" * 8, rank=0) is None


def test_sample_burst_returns_valid_meta():
    stop = threading.Event()

    def busy():
        with phase_stats.timed("serialize"):
            while not stop.is_set():
                sum(i * i for i in range(5000))

    t = threading.Thread(target=busy)
    t.start()
    metas = []
    begin = time.monotonic()
    try:
        # Bursts of a fixed length until they hold enough samples: one on a
        # free box; a burst's thread gets fewer turns on a crowded one.
        while True:
            metas.append(profiler.sample_burst(0.3, hz=99))
            if (
                sum(m["samples_total"] for m in metas) > 10
                or time.monotonic() - begin > _SAMPLING_DEADLINE_S
            ):
                break
    finally:
        stop.set()
        t.join()
    assert sum(m["samples_total"] for m in metas) > 10
    assert any("serialize" in m["stacks"] for m in metas)
    for meta in metas:
        assert profiler.validate_profile(profiler.build_document(meta)) == []


# ------------------------------------------------------- merge + validation


def _synthetic_meta(rank, stacks, hz=100.0, kind="restore", op="feed" * 8):
    samples = sum(
        n for states in stacks.values() for b in states.values()
        for n in b.values()
    )
    oncpu = sum(
        n for states in stacks.values() for b in (states.get("on") or {},)
        for n in b.values()
    )
    return {
        "schema": profiler.PROFILE_SCHEMA,
        "op": op,
        "kind": kind,
        "rank": rank,
        "hz": hz,
        "weight_s": 1.0 / hz,
        "duration_s": 2.0 + rank,
        "ticks": samples,
        "samples_total": samples,
        "oncpu_samples": oncpu,
        "untagged_oncpu": 0,
        "success": True,
        "host": f"host{rank}",
        "stacks": stacks,
        "calibration": {
            "per_tick_s": 1e-5,
            "ticks": samples,
            "estimated_s": 1e-5 * samples,
        },
    }


def test_per_rank_merge(tmp_path):
    meta0 = _synthetic_meta(
        0, {"checksum": {"on": {"a;b;digest": 100}, "off": {"a;b;wait": 10}}}
    )
    meta1 = _synthetic_meta(
        1, {"checksum": {"on": {"a;b;digest": 50}}, "fs_write": {"off": {"a;io": 7}}}
    )
    paths = []
    for meta in (meta0, meta1):
        p = tmp_path / (
            f"{meta['kind']}-{meta['op'][:8]}-rank{meta['rank']}"
            f"{profiler.PROFILE_FILE_SUFFIX}"
        )
        p.write_text(json.dumps(profiler.build_document(meta)))
        paths.append(str(p))
    merged_doc = profiler.merge_profile_files(paths)
    assert profiler.validate_profile(merged_doc) == []
    merged = merged_doc["tpusnap"]
    assert merged["samples_total"] == meta0["samples_total"] + meta1["samples_total"]
    assert merged["stacks"]["checksum"]["on"]["a;b;digest"] == 150
    assert merged["stacks"]["fs_write"]["off"]["a;io"] == 7
    assert merged["duration_s"] == 3.0  # max across ranks, not sum
    assert len(merged["merged_from"]) == 2


def test_validate_profile_rejects_garbage():
    assert profiler.validate_profile([]) != []
    assert profiler.validate_profile({}) != []
    doc = profiler.build_document(
        _synthetic_meta(0, {"d2h": {"on": {"x;y": 3}}})
    )
    assert profiler.validate_profile(doc) == []
    bad = json.loads(json.dumps(doc))
    bad["tpusnap"]["schema"] = "wrong"
    assert any("schema" in p for p in profiler.validate_profile(bad))
    bad = json.loads(json.dumps(doc))
    bad["profiles"][0]["samples"] = [[999]]
    assert any(
        "out of range" in p for p in profiler.validate_profile(bad)
    )


# ---------------------------------------------------------------- CLI: diff


def test_cli_profile_diff_golden(tmp_path, capsys):
    """Two synthetic profiles where the digest frame triples and a decode
    frame appears: diff must name digest as top regressed."""
    a = tmp_path / "a.profile.json"
    b = tmp_path / "b.profile.json"
    meta_a = _synthetic_meta(
        0, {"checksum": {"on": {"a;b;digest": 100}}}
    )
    meta_b = _synthetic_meta(
        0,
        {
            "checksum": {"on": {"a;b;digest": 300}},
            "serialize": {"on": {"a;b;decode": 80}},
        },
    )
    a.write_text(json.dumps(profiler.build_document(meta_a)))
    b.write_text(json.dumps(profiler.build_document(meta_b)))
    rc = cli_main(["profile", "diff", str(a), str(b)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "top regressed" in out
    assert "digest" in out and "decode" in out
    # digest moved +2.0s (200 samples @ 10ms): the biggest regression.
    rc = cli_main(["profile", "diff", str(a), str(b), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["top_regressed"][0]["frame"] == "digest"
    assert doc["top_regressed"][0]["delta_s"] == pytest.approx(2.0)
    assert doc["delta_oncpu_s"] == pytest.approx(2.8)
    assert not doc["top_improved"]


def test_cli_profile_diff_garbage_exits_nonzero(tmp_path, capsys):
    good = tmp_path / "good.profile.json"
    good.write_text(
        json.dumps(
            profiler.build_document(
                _synthetic_meta(0, {"d2h": {"on": {"x": 1}}})
            )
        )
    )
    garbage = tmp_path / "bad.profile.json"
    garbage.write_text("{not json")
    assert cli_main(["profile", "diff", str(good), str(garbage)]) == 1
    assert "invalid profile" in capsys.readouterr().out
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli_main(["profile", "diff", str(empty), str(good)]) == 2


def test_cli_analyze_profile_garbage_exits_nonzero(tmp_path, capsys):
    (tmp_path / "x.profile.json").write_text("]]]")
    assert cli_main(["analyze", str(tmp_path), "--profile"]) == 1
    assert "invalid profile" in capsys.readouterr().out


# --------------------------------------------- profiled ops, end to end


def _take_profiled(root, profile_dir, mb=96, hz="499", enough=None):
    """One profiled fs take of ~mb MB of random float32 (checksummed,
    chunked): returns the written profile docs.  With ``enough`` (a
    predicate over the docs so far), takes follow one another for at least
    a second and until it holds: how many samples one take of a fixed size
    leaves depends on how fast the box writes it."""
    state = {
        "m": StateDict(
            {
                f"w{i}": np.random.RandomState(i)
                .rand((mb << 20) // 2 // 4)
                .astype(np.float32)
                for i in range(2)
            }
        )
    }
    begin = time.monotonic()
    takes = 0
    with knobs.override_profile_dir(str(profile_dir)), knobs.override_profile_hz(
        hz
    ):
        while True:
            Snapshot.take(f"{root}_{takes}" if takes else str(root), state)
            takes += 1
            elapsed_s = time.monotonic() - begin
            if enough is None or elapsed_s > _SAMPLING_DEADLINE_S:
                break
            if elapsed_s >= 1.0 and enough(
                profiler.load_profile_dir(str(profile_dir))
            ):
                break
    return profiler.load_profile_dir(str(profile_dir))


def test_untagged_share_under_5pct_on_profiled_fs_take(tmp_path):
    """THE attribution-health bar (tier-1): on a healthy profiled take,
    fewer than 5% of on-CPU samples may land in <untagged> — executor
    workers inherit the submitting phase, the op driver thread carries
    take_drive, and the drain thread carries io_drain_drive."""
    take_metas = lambda docs: [
        d["tpusnap"] for d in docs if d["tpusnap"]["kind"] == "take"
    ]
    docs = _take_profiled(
        tmp_path / "snap",
        tmp_path / "prof",
        enough=lambda docs: sum(
            m["oncpu_samples"] for m in take_metas(docs)
        ) >= 40,
    )
    metas = take_metas(docs)
    assert metas
    merged = profiler.merge_metas(metas)
    # Checksummed 96 MB takes burn real CPU: demand a sample floor so the
    # assertion below divides something meaningful.
    assert merged["oncpu_samples"] >= 20, merged
    share = merged["untagged_oncpu"] / merged["oncpu_samples"]
    assert share < 0.05, (
        f"untagged on-CPU share {share:.1%} "
        f"({merged['untagged_oncpu']}/{merged['oncpu_samples']}); "
        f"phases: {sorted(merged['stacks'])}"
    )
    # The driver pseudo-phases classify into their own group.
    assert analyze.classify_phase("take_drive") == "driver"
    assert analyze.classify_phase("io_drain_drive") == "driver"


def test_profile_smoke_gate(tmp_path, capsys):
    """The tools/check.sh gate: a profiled take writes schema-valid
    profile files and `analyze --profile` folds them into the report and
    exits 0 — including on a dir holding only profiles (no traces)."""
    prof_dir = tmp_path / "prof"
    docs = _take_profiled(tmp_path / "snap", prof_dir, mb=32)
    assert docs, "profiled take wrote no profile files"
    for doc in docs:
        assert profiler.validate_profile(doc) == []
    rc = cli_main(["analyze", str(prof_dir), "--profile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "dominant CPU sink" in out or "CPU:" in out
    rc = cli_main(["analyze", str(prof_dir), "--profile", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    profiles = report["profiles"]
    assert profiles and profiles[0]["kind"] == "take"
    assert profiles[0]["samples_total"] > 0
    # Per-phase rows carry the PHASE_GROUPS cross-check.
    for info in profiles[0]["phases"].values():
        assert "group" in info and "cpu_s" in info
    # Calibrated self-overhead rides every profile, blackbox-style.
    assert profiles[0]["overhead"]["per_tick_s"] is not None


def test_profiles_and_traces_fold_into_one_report(tmp_path, capsys):
    """TPUSNAP_PROFILE and TPUSNAP_TRACE_DIR pointed at the same dir:
    one analyze --profile invocation renders both planes."""
    shared = tmp_path / "telemetry"
    state = {"m": StateDict({"w": np.ones((256, 256), np.float32)})}
    with knobs.override_trace_dir(str(shared)), knobs.override_profile_dir(
        str(shared)
    ), knobs.override_profile_hz("499"):
        Snapshot.take(str(tmp_path / "snap"), state)
    rc = cli_main(["analyze", str(shared), "--profile", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["ops"] and report["ops"][0]["kind"] == "take"
    assert report["profiles"] and report["profiles"][0]["kind"] == "take"


def test_monitor_releases_driver_tag(tmp_path):
    """OpMonitor registers <kind>_drive for its driver thread and MUST
    unregister on finish — a leak would tag unrelated later samples."""
    ident = threading.get_ident()
    mon = monitor.op_started("take", "abba" * 8, rank=0)
    assert phase_stats.thread_phases().get(ident) == "take_drive"
    monitor.op_finished(mon)
    assert phase_stats.thread_phases().get(ident) is None
