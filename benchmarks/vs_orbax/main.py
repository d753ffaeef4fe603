"""Head-to-head vs orbax.checkpoint — the JAX-ecosystem incumbent.

Saves/restores the same sharded train-state pytree with torchsnapshot_tpu
and with orbax's PyTreeCheckpointer, reporting wall times.  Apples-to-apples
on local fs, same process, same mesh.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/vs_orbax/main.py --size-mb 512
"""

import argparse
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--size-mb", type=int, default=256)
    parser.add_argument("--n-arrays", type=int, default=16)
    parser.add_argument("--work-dir", default="/tmp/tpusnap_bench_vs_orbax")
    args = parser.parse_args()

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("x",))
    sharding = NamedSharding(mesh, P("x", None))

    per = args.size_mb * (1 << 20) // args.n_arrays // 4
    rows = per // 1024
    rows -= rows % len(devices) or len(devices)
    rows = max(rows, len(devices))

    @jax.jit
    def make(key):
        return {
            f"w{i}": jax.lax.with_sharding_constraint(
                jax.random.normal(k, (rows, 1024), jnp.float32), sharding
            )
            for i, k in enumerate(jax.random.split(key, args.n_arrays))
        }

    with mesh:
        tree = jax.block_until_ready(make(jax.random.key(0)))
    gb = sum(x.size * 4 for x in tree.values()) / 1e9
    print(f"pytree: {args.n_arrays} sharded arrays, {gb:.2f} GB")
    shutil.rmtree(args.work_dir, ignore_errors=True)

    def _settle():
        # Page-cache writeback swings this box's I/O 10x run to run; start
        # every timed measurement with the dirty set drained (same
        # discipline as bench.py).
        try:
            os.sync()
        except OSError:
            pass

    def _best_of(fn, n=2):
        times = []
        for _ in range(n):
            _settle()
            t0 = time.monotonic()
            fn()
            times.append(time.monotonic() - t0)
        return min(times)

    # --- torchsnapshot_tpu ---
    snaps = {}

    def _save(attempt=[0]):
        attempt[0] += 1
        path = os.path.join(args.work_dir, f"tpusnap{attempt[0]}")
        prev = os.path.join(args.work_dir, f"tpusnap{attempt[0] - 1}")
        shutil.rmtree(prev, ignore_errors=True)  # keep peak disk ~1 state
        shutil.rmtree(path, ignore_errors=True)
        snaps["snap"] = Snapshot.take(path, {"m": StateDict(tree)})

    ours_save = _best_of(_save)
    snap = snaps["snap"]
    dst = {"m": StateDict({k: jnp.zeros_like(v) for k, v in tree.items()})}

    def _load():
        snap.restore(dst)
        jax.block_until_ready(dst["m"].data)

    ours_load = _best_of(_load)
    ok = np.array_equal(np.asarray(dst["m"]["w0"]), np.asarray(tree["w0"]))
    # The "verifying" label must be true: the save above ran under the
    # caller's environment, so confirm digests were actually recorded.
    # Sharded/chunked entries carry their checksums on per-piece tensor
    # records, not the top-level entry.
    def _has_digest(e):
        if getattr(e, "checksum", None):
            return True
        for piece in list(getattr(e, "shards", None) or []) + list(
            getattr(e, "chunks", None) or []
        ):
            if getattr(getattr(piece, "tensor", None), "checksum", None):
                return True
        return False

    n_digests = sum(1 for e in snap.get_manifest().values() if _has_digest(e))
    verifying = n_digests > 0
    # Apples-to-apples load: our default restore VERIFIES every payload's
    # xxh64 against the manifest; orbax's does not verify payload bytes.
    # The context manager restores any pre-existing user setting even when
    # the no-verify load raises — a failed run must not leak mutated env.
    from torchsnapshot_tpu.knobs import override_env

    with override_env("TPUSNAP_CHECKSUM", "0"):
        ours_load_noverify = _best_of(_load)
    print(
        f"torchsnapshot_tpu: save {ours_save:.2f}s ({gb / ours_save:.2f} GB/s), "
        f"load {ours_load:.2f}s ({gb / ours_load:.2f} GB/s) "
        f"[{'verifies ' + str(n_digests) + ' payload checksums' if verifying else 'NO digests recorded (TPUSNAP_CHECKSUM off?)'}; "
        f"values_equal={ok}], "
        f"load w/o verify {ours_load_noverify:.2f}s "
        f"({gb / ours_load_noverify:.2f} GB/s) [best of 2 each, saves too]"
    )

    # --- orbax ---
    try:
        import orbax.checkpoint as ocp

        ckptr = ocp.PyTreeCheckpointer()
        orbax_dirs = {}

        def _orbax_save(attempt=[0]):
            attempt[0] += 1
            path = os.path.join(args.work_dir, f"orbax{attempt[0]}")
            prev = os.path.join(args.work_dir, f"orbax{attempt[0] - 1}")
            shutil.rmtree(prev, ignore_errors=True)
            shutil.rmtree(path, ignore_errors=True)
            ckptr.save(path, tree)
            orbax_dirs["dir"] = path

        orbax_save = _best_of(_orbax_save)
        orbax_dir = orbax_dirs["dir"]
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            tree,
        )

        def _orbax_load():
            restored = ckptr.restore(orbax_dir, args=ocp.args.PyTreeRestore(
                restore_args=ocp.checkpoint_utils.construct_restore_args(abstract)
            ))
            jax.block_until_ready(restored)

        orbax_load = _best_of(_orbax_load)
        print(
            f"orbax:             save {orbax_save:.2f}s ({gb / orbax_save:.2f} GB/s), "
            f"load {orbax_load:.2f}s ({gb / orbax_load:.2f} GB/s)"
        )
        verify_note = (
            "with payload verification orbax does not do"
            if verifying
            else "NO verification either side"
        )
        print(
            f"speedup: save {orbax_save / ours_save:.2f}x, "
            f"load {orbax_load / ours_load:.2f}x ({verify_note}), "
            f"{orbax_load / ours_load_noverify:.2f}x (equal work)"
        )
    except Exception as e:  # noqa: BLE001
        print(f"orbax comparison unavailable: {e}")
    shutil.rmtree(args.work_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
