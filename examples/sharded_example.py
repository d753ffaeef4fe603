"""GSPMD example: snapshot an FSDP+TP-sharded model from a device mesh and
restore it under a different sharding (elastic resharding on load).

Run on CPU with a virtual mesh:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/sharded_example.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.models import (
    LlamaConfig,
    init_train_state,
    make_train_step,
)
from torchsnapshot_tpu.parallel import make_mesh


def main() -> None:
    mesh = make_mesh(data=2, fsdp=2, model=2)
    cfg = LlamaConfig.tiny()
    opt = optax.adamw(1e-3)
    train_state = init_train_state(jax.random.key(0), cfg, opt, mesh)

    with mesh:
        step_fn = jax.jit(make_train_step(cfg, opt))
        tokens = jax.device_put(
            jnp.ones((4, 32), jnp.int32), NamedSharding(mesh, P("data", None))
        )
        train_state, loss = step_fn(train_state, tokens)
    print("one sharded train step, loss:", float(loss))

    snapshot = Snapshot.take(
        "/tmp/tpusnap_example/sharded_snap", {"train": StateDict(train_state)}
    )
    print("snapshot taken; manifest entries:", len(snapshot.get_manifest()))

    # Restore into a different mesh layout: pure-FSDP (no tensor parallelism)
    mesh2 = make_mesh(data=1, fsdp=8, model=1)
    target = init_train_state(jax.random.key(9), cfg, opt, mesh2)
    dst = {"train": StateDict(target)}
    snapshot.restore(dst)
    restored = dst["train"]

    np.testing.assert_array_equal(
        np.asarray(restored["params"]["embed"]["tokens"]),
        np.asarray(train_state["params"]["embed"]["tokens"]),
    )
    print(
        "resharded restore verified:",
        restored["params"]["embed"]["tokens"].sharding.spec,
    )


if __name__ == "__main__":
    main()
