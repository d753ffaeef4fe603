"""FSDP-sharded transformer save/load benchmark (reference
benchmarks/fsdp/main.py:35-104): wall time to checkpoint and restore a
GSPMD-sharded Llama-style train state.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python benchmarks/fsdp/main.py --d-model 1024 --n-layers 8
"""

import argparse
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import jax
import optax

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.models import LlamaConfig, init_train_state
from torchsnapshot_tpu.parallel import factor_mesh, make_mesh


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--d-model", type=int, default=1024)
    parser.add_argument("--n-layers", type=int, default=8)
    parser.add_argument("--vocab", type=int, default=8192)
    parser.add_argument("--work-dir", default="/tmp/tpusnap_bench_fsdp")
    args = parser.parse_args()

    cfg = LlamaConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.n_layers,
        n_heads=args.d_model // 128,
        n_kv_heads=max(1, args.d_model // 256),
        d_ff=args.d_model * 7 // 2,
    )
    n = len(jax.devices())
    data, fsdp, model = factor_mesh(n)
    mesh = make_mesh(data=data, fsdp=fsdp, model=model)
    opt = optax.adamw(1e-3)
    train_state = init_train_state(jax.random.key(0), cfg, opt, mesh)
    jax.block_until_ready(train_state["params"])
    nbytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(train_state)
    )
    gb = nbytes / 1e9
    print(f"train state: {gb:.2f} GB over mesh {data}x{fsdp}x{model}")

    shutil.rmtree(args.work_dir, ignore_errors=True)
    path = os.path.join(args.work_dir, "snap")

    begin = time.monotonic()
    snapshot = Snapshot.take(path, {"train": StateDict(train_state)})
    save_s = time.monotonic() - begin
    print(f"save: {save_s:.2f}s = {gb / save_s:.2f} GB/s")

    target = init_train_state(None, cfg, opt, mesh)
    begin = time.monotonic()
    dst = {"train": StateDict(target)}
    snapshot.restore(dst)
    jax.block_until_ready(dst["train"]["params"])
    load_s = time.monotonic() - begin
    print(f"load: {load_s:.2f}s = {gb / load_s:.2f} GB/s")
    shutil.rmtree(args.work_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
