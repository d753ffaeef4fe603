"""Sharded embedding-table benchmark (reference benchmarks/torchrec/main.py:
119-235): host-offloaded embedding shards (the UVM analogue), sync save vs
async save (training-blocked time vs total), peak RSS.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/embeddings/main.py --table-mb 256
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
from torchsnapshot_tpu.rss_profiler import measure_rss_deltas
from torchsnapshot_tpu.utils.host_offload import (
    supports_host_memory,
    to_host_memory,
)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--table-mb", type=int, default=128)
    parser.add_argument("--n-tables", type=int, default=4)
    parser.add_argument("--work-dir", default="/tmp/tpusnap_bench_emb")
    args = parser.parse_args()

    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("x",))
    sharding = NamedSharding(mesh, P("x", None))  # row-wise sharded tables

    rows = args.table_mb * (1 << 20) // 4 // 64
    rows -= rows % len(devices)
    tables = {}
    for i in range(args.n_tables):
        t = jax.device_put(
            jax.random.normal(jax.random.key(i), (rows, 64), jnp.float32), sharding
        )
        if supports_host_memory():
            t = to_host_memory(t)  # host-offloaded, as UVM tables would be
        tables[f"table{i}"] = t
    jax.block_until_ready(list(tables.values()))
    gb = args.n_tables * args.table_mb / 1024
    print(
        f"{args.n_tables} row-wise sharded tables, {gb:.2f} GB total, "
        f"host_offloaded={supports_host_memory()}"
    )

    shutil.rmtree(args.work_dir, ignore_errors=True)
    app_state = {"emb": StateDict(tables)}

    rss_deltas = []
    begin = time.monotonic()
    with measure_rss_deltas(rss_deltas=rss_deltas):
        Snapshot.take(os.path.join(args.work_dir, "sync"), app_state)
    sync_s = time.monotonic() - begin
    print(
        f"sync save:  {sync_s:.2f}s ({gb / sync_s:.2f} GB/s), "
        f"peak RSS delta {max(rss_deltas) / (1 << 20):.0f} MB"
    )

    rss_deltas = []
    begin = time.monotonic()
    with measure_rss_deltas(rss_deltas=rss_deltas):
        pending = Snapshot.async_take(os.path.join(args.work_dir, "async"), app_state)
        blocked_s = time.monotonic() - begin
        pending.wait()
    total_s = time.monotonic() - begin
    print(
        f"async save: blocked {blocked_s:.2f}s / total {total_s:.2f}s, "
        f"peak RSS delta {max(rss_deltas) / (1 << 20):.0f} MB"
    )
    shutil.rmtree(args.work_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
