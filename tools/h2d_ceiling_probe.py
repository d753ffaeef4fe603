"""One-off probe, outside every cell and imported by nothing: what a plain
``jax.device_put`` costs from an arena-like host buffer whose pages a read has
written (ROADMAP Speed 1a's open question, ISSUE 34's satellite 2).

    chiprun -- python3 tools/h2d_ceiling_probe.py > chiprun_out/h2d_probe.jsonl

Cases, each a state's own shape: (a) one 403 MB bf16 leaf (Codestral's
embedding), (b) one 512 MiB float32 leaf (Mistral's), (b16) the same bytes as
bf16, which separates a cost per element from a cost per byte, (c) 36 bf16
leaves of 7 MiB in one call (an ``lfm2`` flush), and (c1) the same 36 leaves a
call each.  For each: the seconds inside the call or calls (``call_s``) and
from the first call's start to ``block_until_ready`` (``ready_s``), with 1, 2
and 4 threads each putting its own share at once (a single leaf is split by
rows).  ``gil_gap_ms`` is the longest stretch for which a Python thread that
sleeps 0.1 ms and reads the clock, over and over, was kept from running
meanwhile: whether a call holds the GIL, so whether a read pipeline's loop
thread runs beside it.  Cases c and c1 are measured a second time
(``"loaded": true``) beside what an ``lfm2`` restore's sixteen io slots do
meanwhile: sixteen threads, each reading and hashing 7 MiB of a file into a
buffer of its own, over and over (``NativeFileIO.read_file_into`` with its
fused digest, the sequential ``fs_read``); ``load_gbps`` is what they read
during the measurement.  (A clock that spins instead holds the GIL itself and
every call then waits the interpreter's 5 ms switch interval for it: the first
version of this probe read 0.25 s for case c that way.)  One JSON line a
measurement on stdout."""

from __future__ import annotations

import json
import mmap
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPS = 3
PAGE = mmap.PAGESIZE


def arena(nbytes: int, path: str, native) -> np.ndarray:
    """Page-aligned, populated as the restore's arena is, then written by a read."""
    raw = np.empty(nbytes + PAGE, np.uint8)
    begin = -raw.ctypes.data % PAGE
    buf = raw[begin : begin + nbytes]
    if native is not None and native.has_touch_pages:
        native.touch_pages(buf)
    with open(path, "rb", buffering=0) as f:
        got = f.readinto(memoryview(buf))
    assert got == nbytes, (got, nbytes)
    return buf


class Clock(threading.Thread):
    """Sleeps 0.1 ms and reads the clock, in a loop; ``gap`` is the longest
    it was kept from doing so."""

    def __init__(self) -> None:
        super().__init__(daemon=True)
        self.gap, self.stop = 0.0, False

    def run(self) -> None:
        last = time.monotonic()
        while not self.stop:
            time.sleep(1e-4)
            now = time.monotonic()
            self.gap = max(self.gap, now - last)
            last = now


LEAF = 1792 * 2048 * 2  # one lfm2 expert matrix, bf16
READERS = 16  # the restore's io slots


class ReadLoad:
    """``READERS`` threads that read and hash ``LEAF`` bytes of ``path`` into
    a touched buffer of their own, again and again, until ``stop()``."""

    def __init__(self, native, path: str) -> None:
        self.done, self._stop = [0] * READERS, False
        span = os.path.getsize(path) // LEAF
        bufs = [arena(LEAF, path, native) for _ in range(READERS)]

        def read(i: int) -> None:
            n = i
            while not self._stop:
                at = (n % span) * LEAF
                native.read_file_into(path, [at, at + LEAF], bufs[i], want_hash=True)
                self.done[i] += LEAF
                n += READERS

        self._threads = [threading.Thread(target=read, args=(i,), daemon=True) for i in range(READERS)]
        for t in self._threads:
            t.start()

    def read_bytes(self) -> int:
        return sum(self.done)

    def stop(self) -> None:
        self._stop = True
        for t in self._threads:
            t.join()


def measure(jax, shares, sharding, per_leaf: bool = False) -> dict:
    """``shares``: one list of host arrays a thread; all threads call at once,
    once for the whole share or (``per_leaf``) once a leaf."""
    n = len(shares)
    gate = threading.Barrier(n + 1)
    rows = [None] * n

    def put(i: int) -> None:
        gate.wait()
        begin = time.monotonic()
        if per_leaf:
            outs = [jax.device_put(leaf, sharding) for leaf in shares[i]]
        else:
            outs = jax.device_put(shares[i], [sharding] * len(shares[i]))
        called = time.monotonic()
        jax.block_until_ready(outs)
        rows[i] = (begin, called, time.monotonic())
        del outs

    threads = [threading.Thread(target=put, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    clock = Clock()
    clock.start()
    time.sleep(0.05)  # every thread parked at the gate, the clock running
    clock.gap = 0.0
    gate.wait()
    for t in threads:
        t.join()
    clock.stop = True
    clock.join()
    begin = min(r[0] for r in rows)
    return {
        "call_s": max(r[1] for r in rows) - begin,
        "ready_s": max(r[2] for r in rows) - begin,
        "call_s_each": [round(r[1] - r[0], 5) for r in rows],
        "gil_gap_ms": round(clock.gap * 1e3, 3),
    }


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from torchsnapshot_tpu.native_io import NativeFileIO

    device = jax.devices()[0]
    sharding = SingleDeviceSharding(device)
    native = NativeFileIO.maybe_create()
    bf16, f32 = np.dtype(jnp.bfloat16), np.dtype(np.float32)
    cases = [
        ("a_codestral_403MB_bf16", bf16, [(32768, 6144)]),
        ("b_mistral_512MiB_f32", f32, [(32768, 4096)]),
        ("b16_512MiB_bf16", bf16, [(32768, 8192)]),
        ("c_lfm2_36x7MiB_bf16", bf16, [(1792, 2048)] * 36),
        ("c1_lfm2_36x7MiB_bf16_a_call_a_leaf", bf16, [(1792, 2048)] * 36),
    ]
    print(json.dumps({"device": device.device_kind, "platform": device.platform,
                      "cache_dir_env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
                      "cpus": os.cpu_count(), "touch_pages": bool(native and native.has_touch_pages)}), flush=True)
    tmp = tempfile.mkdtemp(prefix="h2d_probe_")
    path = os.path.join(tmp, "payload.bin")
    block = np.random.default_rng(34).integers(0, 256, 64 << 20, dtype=np.uint8).tobytes()
    with open(path, "wb") as f:
        for _ in range(8):
            f.write(block)
    jax.block_until_ready(jax.device_put(np.zeros(8, np.float32), sharding))  # the client is up
    try:
        for name, dtype, shapes in cases:
            sizes = [int(np.prod(s)) * dtype.itemsize for s in shapes]
            offsets = np.cumsum([0] + [-(-b // PAGE) * PAGE for b in sizes])
            buf = arena(int(offsets[-1]), path, native)
            leaves = [
                buf[o : o + b].view(dtype).reshape(s) for o, b, s in zip(offsets, sizes, shapes)
            ]
            for loaded in (False, True) if name.startswith("c") and native is not None else (False,):
                load = ReadLoad(native, path) if loaded else None
                if load is not None:
                    time.sleep(0.3)  # every reader under way
                for ways in (1, 2, 4):
                    if len(leaves) == 1:  # one leaf: each thread puts its rows
                        shares = [[part] for part in np.array_split(leaves[0], ways)]
                    else:
                        shares = [leaves[i::ways] for i in range(ways)]
                    for rep in range(REPS + 2 * loaded):
                        read, began = (load.read_bytes(), time.monotonic()) if load else (0, 0.0)
                        row = measure(jax, shares, sharding, per_leaf=name.startswith("c1_"))
                        if load is not None:
                            row["load_gbps"] = round(
                                (load.read_bytes() - read) / (time.monotonic() - began) / 1e9, 3
                            )
                        nbytes = sum(sizes)
                        print(json.dumps({"case": name, "loaded": loaded, "ways": ways, "rep": rep, "bytes": nbytes,
                                          "call_gbps": round(nbytes / row["call_s"] / 1e9, 3),
                                          "ready_gbps": round(nbytes / row["ready_s"] / 1e9, 3),
                                          **{k: (round(v, 5) if isinstance(v, float) else v) for k, v in row.items()}}),
                              flush=True)
                if load is not None:
                    load.stop()
            del leaves, buf
    finally:
        os.remove(path)
        os.rmdir(tmp)


if __name__ == "__main__":
    main()
