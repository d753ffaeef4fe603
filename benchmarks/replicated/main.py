"""Replicated-model save benchmark (reference benchmarks/ddp/main.py).

N local processes hold an identical model; torchsnapshot_tpu dedups and
load-balances the writes across ranks (partitioner), vs the naive baseline of
every rank pickling its own full copy.

    python benchmarks/replicated/main.py --nproc 4 --size-mb 512
"""

import argparse
import os
import pickle
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def worker(rank: int, nproc: int, store_path: str, size_mb: int, work_dir: str) -> None:
    import numpy as np

    from torchsnapshot_tpu import Snapshot, StateDict
    from torchsnapshot_tpu.dist_store import FileStore
    from torchsnapshot_tpu.pg_wrapper import PGWrapper

    pg = PGWrapper(
        store=FileStore(store_path), rank=rank, world_size=nproc
    )
    n = size_mb * (1 << 20) // 4 // 16
    model = {f"layer{i}": np.random.rand(n).astype(np.float32) for i in range(16)}
    app_state = {"model": StateDict(model)}

    # baseline: every rank writes its full copy
    pg.barrier()
    begin = time.monotonic()
    with open(os.path.join(work_dir, f"naive_{rank}.pkl"), "wb") as f:
        pickle.dump(model, f, protocol=pickle.HIGHEST_PROTOCOL)
    pg.barrier()
    naive_s = time.monotonic() - begin

    # torchsnapshot_tpu: deduped + partitioned
    pg.barrier()
    begin = time.monotonic()
    Snapshot.take(
        os.path.join(work_dir, "snap"), app_state, pg=pg, replicated=["model/**"]
    )
    pg.barrier()
    snap_s = time.monotonic() - begin

    if rank == 0:
        total_gb = size_mb / 1024
        print(
            f"replicated {total_gb:.2f} GB x {nproc} ranks | "
            f"naive per-rank pickle: {naive_s:.2f}s ({nproc * total_gb / naive_s:.2f} GB/s written) | "
            f"tpusnap deduped: {snap_s:.2f}s ({total_gb / snap_s:.2f} GB/s unique)"
        )


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--nproc", type=int, default=4)
    parser.add_argument("--size-mb", type=int, default=256)
    parser.add_argument("--work-dir", default="/tmp/tpusnap_bench_replicated")
    args = parser.parse_args()

    import multiprocessing as mp
    import tempfile

    shutil.rmtree(args.work_dir, ignore_errors=True)
    os.makedirs(args.work_dir, exist_ok=True)
    with tempfile.TemporaryDirectory() as store_path:
        # CPU-only by contract: the workers are forked after ``import jax``
        # and stay numpy-only.  A chip belongs to one process; this bench
        # never runs from one that holds it.
        ctx = mp.get_context("fork")
        procs = [
            ctx.Process(
                target=worker,
                args=(r, args.nproc, store_path, args.size_mb, args.work_dir),
            )
            for r in range(args.nproc)
        ]
        for p in procs:
            p.start()
        for p in procs:
            p.join()
            assert p.exitcode == 0
    shutil.rmtree(args.work_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
