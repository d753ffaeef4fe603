"""Flagship integration: multi-process GSPMD Llama training + checkpoint.

Two spawned jax.distributed processes × 2 CPU devices = a 4-device
(fsdp=2, model=2) mesh spanning processes.  Each process runs the SAME jitted
train step (SPMD), then checkpoints the sharded train state — each process
writing only its addressable shards — and restores it into a freshly
initialized sharded target.  This is the BASELINE.md north-star shape
(FSDP-sharded transformer on a multi-host slice) at toy scale.
"""

import multiprocessing as mp
import os
import shutil
import socket
import sys
import tempfile
import traceback

SNAP_PATH = "/tmp/tpusnap_multihost_llama/snap"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker(rank: int, world: int, coord_port: int, store_path: str, conn) -> None:
    try:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.distributed.initialize(
            coordinator_address=f"127.0.0.1:{coord_port}",
            num_processes=world,
            process_id=rank,
        )
        import jax.numpy as jnp
        import numpy as np
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        from torchsnapshot_tpu import Snapshot, StateDict
        from torchsnapshot_tpu.dist_store import FileStore
        from torchsnapshot_tpu.models import (
            LlamaConfig,
            init_train_state,
            make_train_step,
        )
        from torchsnapshot_tpu.pg_wrapper import PGWrapper

        devices = jax.devices()
        assert len(devices) == 4
        grid = np.array(devices).reshape(1, 2, 2)  # (data=1, fsdp=2(procs), model=2)
        mesh = Mesh(grid, ("data", "fsdp", "model"))

        cfg = LlamaConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128
        )
        opt = optax.adamw(1e-3)
        train_state = init_train_state(jax.random.key(0), cfg, opt, mesh)

        with mesh:
            step_fn = jax.jit(make_train_step(cfg, opt))
            tokens = jax.device_put(
                jnp.ones((2, 16), jnp.int32), NamedSharding(mesh, P("data", None))
            )
            train_state, loss = step_fn(train_state, tokens)
            jax.block_until_ready(loss)
        assert np.isfinite(float(loss))

        pg = PGWrapper(store=FileStore(store_path), rank=rank, world_size=world)
        if rank == 0:
            shutil.rmtree(os.path.dirname(SNAP_PATH), ignore_errors=True)
        pg.barrier()

        snapshot = Snapshot.take(SNAP_PATH, {"train": StateDict(train_state)}, pg=pg)

        # fresh differently-seeded target, same shardings
        target = init_train_state(jax.random.key(9), cfg, opt, mesh)
        dst = {"train": StateDict(target)}
        snapshot.restore(dst)
        restored = dst["train"]

        assert int(jax.device_get(restored["step"])) == 1
        # compare local shards of a sharded param and an optimizer moment
        for path in (
            ("params", "layers", "attn", "wq"),
            ("params", "embed", "tokens"),
        ):
            a = train_state
            b = restored
            for k in path:
                a, b = a[k], b[k]
            for sa, sb in zip(a.addressable_shards, b.addressable_shards):
                np.testing.assert_array_equal(
                    np.asarray(sa.data), np.asarray(sb.data)
                )
        mu_a = train_state["opt_state"][0].mu["layers"]["mlp"]["w_gate"]
        mu_b = restored["opt_state"][0].mu["layers"]["mlp"]["w_gate"]
        np.testing.assert_array_equal(
            np.asarray(mu_a.addressable_shards[0].data),
            np.asarray(mu_b.addressable_shards[0].data),
        )
        conn.send(None)
    except BaseException:  # noqa: BLE001
        conn.send(traceback.format_exc())


def test_multihost_llama_train_checkpoint_restore():
    world = 2
    coord_port = _free_port()
    ctx = mp.get_context("spawn")
    with tempfile.TemporaryDirectory() as store_path:
        procs, conns = [], []
        for rank in range(world):
            parent, child = ctx.Pipe()
            p = ctx.Process(
                target=_worker, args=(rank, world, coord_port, store_path, child)
            )
            p.start()
            procs.append(p)
            conns.append(parent)
        errors = []
        for rank, (p, conn) in enumerate(zip(procs, conns)):
            p.join(timeout=240)
            if p.is_alive():
                p.terminate()
                errors.append(f"rank {rank}: timed out")
            elif conn.poll():
                err = conn.recv()
                if err is not None:
                    errors.append(f"rank {rank}:\n{err}")
            elif p.exitcode != 0:
                errors.append(f"rank {rank}: exit {p.exitcode}")
        assert not errors, "\n".join(errors)
