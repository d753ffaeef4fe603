"""Native data plane ⇄ pure-Python fallback parity + degrade behavior.

The contract: ``TPUSNAP_NATIVE=0`` (or a missing/stale libtpusnap.so) must
produce byte-identical snapshots — same manifests, same digests, same
on-disk payload bytes — and every take/restore/verify/audit path must work
in both modes.  The digest policy (plain xxh64 below STRIPED_MIN_BYTES,
striped "xxh64s" above) is size-only, so native, fused-write, and
pure-Python computation routes can never disagree.
"""

import hashlib
import os

import numpy as np
import pytest

from torchsnapshot_tpu import Snapshot, StateDict, integrity
from torchsnapshot_tpu.native_io import (
    STRIPE_BYTES,
    STRIPED_MIN_BYTES,
    NativeFileIO,
)

# A buffer just over the striping threshold (33 MiB): big enough for real
# stripe parallelism, small enough for tier-1.
_BIG_N = (STRIPED_MIN_BYTES // 4) + 300_000


def _state():
    return {
        "m": StateDict(
            {
                "big": np.arange(_BIG_N, dtype=np.float32),  # striped digest
                "mid": np.random.RandomState(3).rand(512, 512).astype(np.float32),
                **{
                    f"tiny{i}": np.full((64,), i, np.float32) for i in range(12)
                },  # slab members
                "obj": {"nested": [1, "two", 3.0]},
            }
        )
    }


def _dir_digest(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for fname in sorted(files):
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root)
            if rel.startswith("telemetry/"):
                continue  # op-id-named observability sidecars, not payload
            with open(path, "rb") as f:
                out[rel] = hashlib.sha1(f.read()).hexdigest()
    return out


def _restore_and_check(snapshot, state):
    dst = {"m": StateDict({})}
    snapshot.restore(dst)
    np.testing.assert_array_equal(dst["m"]["big"], state["m"]["big"])
    np.testing.assert_array_equal(dst["m"]["mid"], state["m"]["mid"])
    assert dst["m"]["obj"] == state["m"]["obj"]


def test_take_byte_identity_native_vs_fallback(tmp_path, monkeypatch):
    """Identical manifests, digests, and payload bytes in both modes, and
    each mode restores + audits the OTHER mode's snapshot."""
    state = _state()
    monkeypatch.setenv("TPUSNAP_SIDECAR", "0")
    snap_native = Snapshot.take(str(tmp_path / "native"), state)
    monkeypatch.setenv("TPUSNAP_NATIVE", "0")
    snap_py = Snapshot.take(str(tmp_path / "fallback"), state)
    monkeypatch.delenv("TPUSNAP_NATIVE")

    da = _dir_digest(str(tmp_path / "native"))
    db = _dir_digest(str(tmp_path / "fallback"))
    assert da == db and da, "on-disk bytes must be identical"

    # The manifest must carry BOTH digest algos (the big payload striped,
    # the rest plain) and be byte-identical across modes (covered by the
    # dir compare, re-asserted here for a readable failure).
    manifest_text = (tmp_path / "native" / ".snapshot_metadata").read_text()
    assert manifest_text == (tmp_path / "fallback" / ".snapshot_metadata").read_text()
    assert "xxh64s:" in manifest_text and '"xxh64:' in manifest_text

    for knob in ("1", "0"):
        monkeypatch.setenv("TPUSNAP_NATIVE", knob)
        _restore_and_check(snap_native, state)
        _restore_and_check(snap_py, state)


@pytest.mark.parametrize("knob", ["1", "0"], ids=["native", "pyfallback"])
def test_audit_works_in_both_modes(tmp_path, monkeypatch, knob):
    state = _state()
    monkeypatch.setenv("TPUSNAP_SIDECAR", "0")
    snapshot = Snapshot.take(str(tmp_path / "snap"), state)
    monkeypatch.setenv("TPUSNAP_NATIVE", knob)
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin

    storage = url_to_storage_plugin(str(tmp_path / "snap"))
    try:
        ok, corrupt, unreadable, problems = integrity.audit(
            storage, snapshot.metadata
        )
    finally:
        storage.sync_close()
    assert (corrupt, unreadable, problems) == (0, 0, []) and ok > 0


@pytest.mark.parametrize("knob", ["1", "0"], ids=["native", "pyfallback"])
def test_audit_catches_corruption_in_both_modes(tmp_path, monkeypatch, knob):
    """Flipping one byte of the striped payload must fail the audit in
    BOTH modes — the pure-Python path really verifies, it doesn't skip."""
    state = _state()
    monkeypatch.setenv("TPUSNAP_SIDECAR", "0")
    snapshot = Snapshot.take(str(tmp_path / "snap"), state)
    # Find the largest payload file (the slab holding the striped member).
    paths = []
    for dirpath, _, files in os.walk(tmp_path / "snap"):
        for fname in files:
            if not fname.startswith("."):
                paths.append(os.path.join(dirpath, fname))
    victim = max(paths, key=os.path.getsize)
    with open(victim, "r+b") as f:
        f.seek(os.path.getsize(victim) // 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))

    monkeypatch.setenv("TPUSNAP_NATIVE", knob)
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin

    storage = url_to_storage_plugin(str(tmp_path / "snap"))
    try:
        ok, corrupt, unreadable, problems = integrity.audit(
            storage, snapshot.metadata
        )
    finally:
        storage.sync_close()
    assert corrupt >= 1 and problems


def test_digest_policy_is_size_only(monkeypatch):
    """Every compute route — native one-shot, native striped, pure Python —
    produces the same digest string for the same bytes."""
    rng = np.random.default_rng(11)
    small = rng.integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    big = rng.integers(0, 256, STRIPED_MIN_BYTES + 12_345, dtype=np.uint8).tobytes()

    native_digests = (integrity.digest(small), integrity.digest(big))
    assert native_digests[0].startswith("xxh64:")
    assert native_digests[1].startswith("xxh64s:")

    monkeypatch.setenv("TPUSNAP_NATIVE", "0")
    py_digests = (integrity.digest(small), integrity.digest(big))
    assert native_digests == py_digests


def test_striped_digest_matches_python_reference():
    """Pin the xxh64s combination: per-STRIPE_BYTES xxh64 digests, combined
    via xxh64 over their little-endian u64 stream (seed 0 throughout)."""
    xxhash = pytest.importorskip("xxhash")
    import struct

    data = np.random.default_rng(5).integers(
        0, 256, 3 * STRIPE_BYTES + 777, dtype=np.uint8
    ).tobytes()
    packed = b"".join(
        struct.pack(
            "<Q", xxhash.xxh64(data[o : o + STRIPE_BYTES]).intdigest()
        )
        for o in range(0, len(data), STRIPE_BYTES)
    )
    expected = xxhash.xxh64(packed).intdigest()

    native = NativeFileIO.maybe_create()
    if native is not None:
        assert native.xxhash64_striped(data) == expected
    h = integrity._hash64(data, "xxh64s")
    assert h == expected


def test_fused_write_hash_matches_separate_passes(tmp_path):
    """The digests the fused native write returns must equal what separate
    hashing of each part produces — manifests cannot depend on the route."""
    native = NativeFileIO.maybe_create()
    if native is None:
        pytest.skip("native library unavailable")
    if not native.has_fused_write:
        pytest.skip("fused write symbol unavailable (stale library)")
    rng = np.random.default_rng(7)
    parts = [
        rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        for n in (0, 5, 1_000_000, STRIPED_MIN_BYTES + 3)
    ]
    path = str(tmp_path / "fused.bin")
    hashes = native.write_parts_hash(path, parts)
    with open(path, "rb") as f:
        assert f.read() == b"".join(parts)
    for h, part in zip(hashes, parts):
        assert integrity.format_digest(h, len(part)) == integrity.digest(part)


def test_read_ranges_into_parity(tmp_path):
    native = NativeFileIO.maybe_create()
    if native is None or not native.has_ranged_read:
        pytest.skip("native ranged read unavailable")
    data = np.random.default_rng(9).integers(
        0, 256, STRIPED_MIN_BYTES + 50_000, dtype=np.uint8
    ).tobytes()
    path = str(tmp_path / "r.bin")
    with open(path, "wb") as f:
        f.write(data)
    ranges = [(0, 10_000), (10_000, len(data))]
    views = [bytearray(end - off) for off, end in ranges]
    hashes = native.read_ranges_into(path, ranges, views, want_hash=True)
    for (off, end), view, h in zip(ranges, views, hashes):
        assert bytes(view) == data[off:end]
        assert integrity.format_digest(h, end - off) == integrity.digest(
            data[off:end]
        )
    # unhashed parallel read
    views2 = [bytearray(end - off) for off, end in ranges]
    assert native.read_ranges_into(path, ranges, views2) is None
    assert all(
        bytes(v) == data[off:end] for (off, end), v in zip(ranges, views2)
    )


@pytest.mark.parametrize(
    "begins_at, nbytes",
    [
        # several slices (8 MiB each), neither end on a page or a slice boundary
        pytest.param(100, (20 << 20) + 1234, id="several_slices_ends_inside_pages"),
        pytest.param(0, 3 * 4096, id="whole_pages"),
        pytest.param(4000, 50, id="inside_one_page"),
        pytest.param(4090, 10, id="over_one_page_boundary"),
        pytest.param(0, (8 << 20) + 4096, id="one_page_past_a_slice"),
    ],
)
def test_touch_pages_writes_every_page_of_the_range_and_nothing_else(begins_at, nbytes):
    """One byte a page: the first of the range and the first of every page
    that begins inside it.  Every other byte of those pages, and the pages
    either side, stay as they were."""
    import mmap

    native = NativeFileIO.maybe_create()
    if native is None or not native.has_touch_pages:
        pytest.skip("native touch_pages unavailable")
    page = mmap.PAGESIZE
    raw = np.empty(nbytes + 4 * page, dtype=np.uint8)
    aligned = -raw.ctypes.data % page
    whole = raw[aligned:]  # begins at a page boundary
    whole[:] = 0xAB
    begin = page + begins_at  # a canary page in front, at least one behind
    buf = whole[begin : begin + nbytes]
    native.touch_pages(buf)
    assert (whole[:begin] == 0xAB).all() and (whole[begin + nbytes :] == 0xAB).all()
    written = np.flatnonzero(buf != 0xAB)
    expected = [0] + [
        at - begin for at in range((begin // page + 1) * page, begin + nbytes, page)
    ]
    assert written.tolist() == expected and (buf[written] == 0).all()
    # said twice changes nothing more; an empty range is nobody's page
    native.touch_pages(buf)
    native.touch_pages(whole[:0])
    assert np.flatnonzero(whole != 0xAB).tolist() == [begin + at for at in expected]


# ------------------------------------------------- staleness / degrade


def test_stale_library_rebuilds(tmp_path, monkeypatch):
    """Touching the source newer than the .so triggers a rebuild attempt."""
    from torchsnapshot_tpu._native import build

    calls = []

    def fake_build():
        calls.append(True)

    monkeypatch.setattr(build, "_build", fake_build)
    monkeypatch.setattr(build, "lib_is_stale", lambda: True)
    assert build.get_native_lib_path() == build._LIB
    assert calls, "a stale library must trigger a rebuild"


def test_stale_library_degrades_without_compiler(monkeypatch, caplog):
    """Rebuild impossible (no compiler): the stale library is still served
    with a warning instead of losing the whole native plane."""
    import logging

    from torchsnapshot_tpu._native import build

    def broken_build():
        raise RuntimeError("g++ not found")

    monkeypatch.setattr(build, "_build", broken_build)
    monkeypatch.setattr(build, "lib_is_stale", lambda: True)
    with caplog.at_level(logging.WARNING):
        path = build.get_native_lib_path()
    assert path == build._LIB  # the stale lib, not None
    assert any("stale" in r.message for r in caplog.records)


def test_missing_symbols_degrade_not_crash(tmp_path, monkeypatch):
    """A library missing the newer data-plane symbols loads with the old
    entry points working and the capability flags off — and a take still
    succeeds (loads-or-degrades, never crashes)."""
    io = NativeFileIO.maybe_create()
    if io is None:
        pytest.skip("native library unavailable")
    monkeypatch.setattr(io, "has_fused_write", False)
    monkeypatch.setattr(io, "has_ranged_read", False)
    monkeypatch.setattr(io, "has_striped_hash", False)
    monkeypatch.setenv("TPUSNAP_SIDECAR", "0")
    state = _state()
    snapshot = Snapshot.take(str(tmp_path / "snap"), state)
    _restore_and_check(snapshot, state)
    # Striped digests still computed (sequential per-stripe fallback) and
    # identical to the full-featured value.
    manifest_text = (tmp_path / "snap" / ".snapshot_metadata").read_text()
    assert "xxh64s:" in manifest_text


def test_a_library_without_touch_pages_is_not_reported_degraded(monkeypatch, caplog):
    """The one optional data-plane symbol: nothing falls back to Python
    without it (a restore's host arena is populated by its reads, as before
    the symbol existed), so a library that lacks only it logs nothing and
    emits no ``native.degraded`` event."""
    import ctypes
    import logging

    from torchsnapshot_tpu.event_handlers import (
        register_event_handler,
        unregister_event_handler,
    )

    if NativeFileIO.maybe_create() is None:
        pytest.skip("native library unavailable")

    class WithoutTouchPages:
        def __init__(self, lib):
            self._lib = lib

        def __getattr__(self, name):
            if name == "tpusnap_touch_pages":
                raise AttributeError(name)
            return getattr(self._lib, name)

    real_cdll = ctypes.CDLL
    monkeypatch.setattr(ctypes, "CDLL", lambda path: WithoutTouchPages(real_cdll(path)))
    monkeypatch.setattr(NativeFileIO, "_instance", None)
    monkeypatch.setattr(NativeFileIO, "_failed", False)
    monkeypatch.setattr(NativeFileIO, "_degraded_reported", False)
    events = []
    register_event_handler(events.append)
    try:
        with caplog.at_level(logging.WARNING):
            io = NativeFileIO.maybe_create()
    finally:
        unregister_event_handler(events.append)
    assert io is not None and not io.has_touch_pages
    assert io.has_ranged_read and io.has_fused_write and io.has_pool
    assert not caplog.records and not NativeFileIO._degraded_reported
    assert not [e for e in events if e.name == "native.degraded"]


def test_native_knob_disables_plugin_capabilities(monkeypatch):
    monkeypatch.setenv("TPUSNAP_NATIVE", "0")
    assert NativeFileIO.maybe_create() is None
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    plugin = FSStoragePlugin("/tmp")
    try:
        assert plugin._native is None
        assert plugin.supports_write_hash is False
    finally:
        plugin.sync_close()


def test_abi_mismatch_degrades_like_missing_symbols(monkeypatch):
    """A stale library exporting every symbol but an older ABI generation
    must lose the data-plane fast paths (semantics may have changed), not
    silently keep them."""
    import torchsnapshot_tpu.native_io as native_io_mod

    monkeypatch.setattr(NativeFileIO, "_instance", None)
    monkeypatch.setattr(NativeFileIO, "_failed", False)
    monkeypatch.setattr(NativeFileIO, "_degraded_reported", True)
    monkeypatch.setattr(native_io_mod, "NATIVE_ABI_VERSION", 999)
    io = NativeFileIO.maybe_create()
    assert io is not None  # the old entry points still load...
    assert not io.has_fused_write and not io.has_ranged_read
    assert not io.has_striped_hash and not io.has_zlib
    # ...and the striped digest still computes (sequential fallback),
    # identical to the full-featured value.
    data = np.random.default_rng(3).integers(
        0, 256, STRIPED_MIN_BYTES + 5, dtype=np.uint8
    ).tobytes()
    degraded_digest = integrity.digest(data)
    monkeypatch.setattr(native_io_mod, "NATIVE_ABI_VERSION", 1)
    monkeypatch.setattr(NativeFileIO, "_instance", None)
    assert integrity.digest(data) == degraded_digest


# ------------------------------------------------- zstd cross-decode matrix


def _native_with_zstd():
    native = NativeFileIO.maybe_create()
    if native is None or not native.has_zstd:
        pytest.skip("native zstd unavailable")
    return native


def test_zstd_cross_decode_matrix():
    """Native-encoded frames and wheel-encoded frames decode through EACH
    backend to the same bytes: both emit standard zstd frames, so a
    snapshot written on a native host restores on a wheel-only host and
    vice versa.  Wheel legs skip where the wheel is absent; the
    native→native leg always runs."""
    from torchsnapshot_tpu import compression

    _native_with_zstd()
    payload = np.arange(500_000, dtype=np.float32).tobytes()

    frame, inner = compression.encode(payload, "zstd")
    assert inner == "zstd", "compressible payload must actually compress"
    # native encode → native decode (the always-on leg)
    assert bytes(compression.decode(frame, len(payload))) == payload

    try:
        import zstandard
    except ImportError:
        pytest.skip("zstandard wheel absent: wheel legs of the matrix skip")
    # native encode → wheel decode (raw zstd payload inside the frame)
    body = bytes(frame[compression.HEADER_BYTES :])
    assert (
        zstandard.ZstdDecompressor().decompress(
            body, max_output_size=len(payload)
        )
        == payload
    )
    # wheel encode → native decode
    wheel_bytes = zstandard.ZstdCompressor(level=3).compress(payload)
    out = bytearray(len(payload))
    n = _native_with_zstd().zstd_decode_into(wheel_bytes, memoryview(out))
    assert n == len(payload) and bytes(out) == payload


def test_zstd_ldm_window_log_cross_decode():
    """The long-distance-matching / window-log knobs (ROADMAP 4c) produce
    STANDARD zstd frames: an LDM-encoded frame decodes through the plain
    native decoder (and the wheel where present) to the same bytes, and on
    repeat-heavy payloads LDM+window never loses to the plain encode."""
    from torchsnapshot_tpu import compression, knobs

    native = _native_with_zstd()
    if not native.has_zstd_params:
        pytest.skip("native zstd advanced API unavailable")
    # A repeat at 2 MB distance: inside a 27-bit window, far outside a
    # level-1 small window — exactly what LDM exists to find.
    block = np.random.RandomState(5).bytes(2 << 20)
    payload = block + b"\x00" * 4096 + block

    with knobs.override_zstd_ldm(True), knobs.override_zstd_window_log(24):
        ldm_frame, inner = compression.encode(payload, "zstd")
    assert inner == "zstd"
    plain_frame, _ = compression.encode(payload, "zstd")
    # Both decode identically through the plain decoder.
    assert bytes(compression.decode(ldm_frame, len(payload))) == payload
    assert bytes(compression.decode(plain_frame, len(payload))) == payload
    # The repeat is invisible to the small window, found by LDM.
    assert len(ldm_frame) < len(plain_frame)
    try:
        import zstandard
    except ImportError:
        return  # wheel leg of the matrix skips
    body = bytes(memoryview(ldm_frame)[compression.HEADER_BYTES :])
    assert (
        zstandard.ZstdDecompressor().decompress(
            body, max_output_size=len(payload)
        )
        == payload
    )


def test_zstd_resolves_native_first_and_degrades(monkeypatch):
    """The codec registry resolves zstd through the native backend (no
    wheel or dev headers required); with the native plane knobbed off and
    no wheel, the request degrades to raw exactly like any unavailable
    codec."""
    from torchsnapshot_tpu import compression

    _native_with_zstd()
    assert compression.resolve("zstd") == "zstd"
    assert compression.available_codecs()[0] == "zstd"
    monkeypatch.setenv("TPUSNAP_NATIVE", "0")
    try:
        import zstandard  # noqa: F401

        assert compression.resolve("zstd") == "zstd"  # wheel backend
    except ImportError:
        assert compression.resolve("zstd") == "raw"


def test_zstd_truncated_frame_raises_frame_error():
    """A torn write (truncated compressed payload) must surface as
    FrameError, not a short or garbage buffer.  (A mid-stream BIT flip can
    decode silently — zstd's simple frame carries no content checksum;
    catching that is the manifest digest's job, which covers the frame
    bytes as stored.)"""
    from torchsnapshot_tpu import compression

    _native_with_zstd()
    payload = np.arange(300_000, dtype=np.float32).tobytes()
    frame, inner = compression.encode(payload, "zstd")
    assert inner == "zstd"
    with pytest.raises(compression.FrameError):
        compression.decode(frame[: len(frame) // 2], len(payload))


# ------------------------------------------------- batched dispatch


def test_batched_write_hash_matches_single(tmp_path):
    """The batch call's per-part digests and on-disk bytes must equal what
    N single fused calls produce — manifests cannot depend on the
    dispatch route."""
    native = NativeFileIO.maybe_create()
    if native is None or not native.has_batch_write:
        pytest.skip("native batched write unavailable")
    rng = np.random.default_rng(21)
    jobs = []
    for f in range(6):
        parts = [
            rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for n in (0, 17, 64 << 10, (1 << 20) + 3)[: f % 4 + 1]
        ]
        jobs.append((str(tmp_path / f"batch_{f}"), parts))
    results = native.write_parts_hash_batch(jobs)
    assert len(results) == len(jobs)
    for (path, parts), hashes in zip(jobs, results):
        assert not isinstance(hashes, OSError)
        single = native.write_parts_hash(path + ".single", parts)
        assert hashes == single
        with open(path, "rb") as f:
            assert f.read() == b"".join(parts)
        for h, part in zip(hashes, parts):
            assert integrity.format_digest(h, len(part)) == integrity.digest(
                part
            )


def test_batched_write_error_isolation(tmp_path):
    """One member's failing write (missing parent dir) surfaces as ITS
    OSError while siblings' writes and digests complete normally."""
    native = NativeFileIO.maybe_create()
    if native is None or not native.has_batch_write:
        pytest.skip("native batched write unavailable")
    good = str(tmp_path / "good")
    bad = str(tmp_path / "no_such_dir" / "bad")
    payload = b"x" * 10_000
    results = native.write_parts_hash_batch(
        [(bad, [payload]), (good, [payload])]
    )
    assert isinstance(results[0], OSError)
    assert not isinstance(results[1], OSError)
    with open(good, "rb") as f:
        assert f.read() == payload


def test_take_with_micro_batching_byte_identical(tmp_path, monkeypatch):
    """A take whose small payloads flow through the fs micro-batcher
    (slab batching off so each leaf is its own file) produces the same
    bytes as one with micro-batching disabled."""
    monkeypatch.setenv("TPUSNAP_SIDECAR", "0")
    monkeypatch.setenv("TPUSNAP_DISABLE_BATCHER", "1")
    state = {
        "m": StateDict(
            {
                f"leaf{i}": np.random.RandomState(i).rand(32, 32).astype(
                    np.float32
                )
                for i in range(64)
            }
        )
    }
    monkeypatch.setenv("TPUSNAP_NATIVE_BATCH", "8")
    Snapshot.take(str(tmp_path / "batched"), state)
    monkeypatch.setenv("TPUSNAP_NATIVE_BATCH", "0")
    snap_single = Snapshot.take(str(tmp_path / "single"), state)
    da = _dir_digest(str(tmp_path / "batched"))
    db = _dir_digest(str(tmp_path / "single"))
    assert da == db and da
    dst = {"m": StateDict({})}
    snap_single.restore(dst)
    np.testing.assert_array_equal(dst["m"]["leaf3"], state["m"]["leaf3"])


# ------------------------------------------------- direct I/O


def test_direct_io_take_parity(tmp_path, monkeypatch):
    """TPUSNAP_DIRECT_IO=1 must produce byte-identical snapshots through
    whatever rung of the capability ladder this host resolves (io_uring,
    O_DIRECT pwrite, or the buffered fallback)."""
    native = NativeFileIO.maybe_create()
    if native is None or not native.has_direct_io:
        pytest.skip("native direct-io symbols unavailable")
    monkeypatch.setenv("TPUSNAP_SIDECAR", "0")
    state = _state()
    snap_buffered = Snapshot.take(str(tmp_path / "buffered"), state)
    monkeypatch.setenv("TPUSNAP_DIRECT_IO", "1")
    try:
        Snapshot.take(str(tmp_path / "direct"), state)
        mode = native.direct_io_mode()
    finally:
        monkeypatch.delenv("TPUSNAP_DIRECT_IO")
        native.configure_direct_io(False)
    assert mode in (1, 2, 3), mode
    da = _dir_digest(str(tmp_path / "buffered"))
    db = _dir_digest(str(tmp_path / "direct"))
    assert da == db and da
    _restore_and_check(snap_buffered, state)


def test_direct_io_degrade_emits_event_once(tmp_path, monkeypatch):
    """A filesystem that rejects O_DIRECT degrades writes to buffered with
    ONE native.degraded event — not one per write, and never a failed
    save.  The buffered mode (3) is simulated (this host's filesystems
    accept O_DIRECT); the write itself still runs with the knob on, so
    the degrade-check call path is the production one."""
    from torchsnapshot_tpu import event_handlers

    native = NativeFileIO.maybe_create()
    if native is None or not native.has_direct_io:
        pytest.skip("native direct-io symbols unavailable")
    monkeypatch.setattr(NativeFileIO, "_direct_io_reported", False)
    monkeypatch.setattr(NativeFileIO, "direct_io_mode", lambda self: 3)
    events = []
    event_handlers.register_event_handler(events.append)
    monkeypatch.setenv("TPUSNAP_SIDECAR", "0")
    monkeypatch.setenv("TPUSNAP_DIRECT_IO", "1")
    try:
        snapshot = Snapshot.take(str(tmp_path / "snap"), _state())
    finally:
        monkeypatch.delenv("TPUSNAP_DIRECT_IO")
        event_handlers.unregister_event_handler(events.append)
        native.configure_direct_io(False)
    degraded = [
        e
        for e in events
        if e.name == "native.degraded"
        and "direct_io" in (e.metadata or {}).get("missing", [])
    ]
    assert len(degraded) == 1, [e.name for e in events]
    _restore_and_check(snapshot, _state())


def test_incremental_dedup_hashes_under_recorded_algo():
    """digest_as must hash the way the BASE recorded, so pre-striped-era
    bases (plain xxh64 on large payloads) keep deduplicating."""
    data = np.random.default_rng(4).integers(
        0, 256, STRIPED_MIN_BYTES + 9, dtype=np.uint8
    ).tobytes()
    native = NativeFileIO.maybe_create()
    if native is None:
        pytest.skip("native library unavailable")
    # A pre-upgrade base would have recorded the PLAIN digest of this
    # large payload.
    old_style = f"xxh64:{native.xxhash64(data):016x}"
    assert integrity.digest_as(data, old_style) == old_style
    # And a post-upgrade base's striped digest round-trips too.
    new_style = integrity.digest(data)
    assert new_style.startswith("xxh64s:")
    assert integrity.digest_as(data, new_style) == new_style
