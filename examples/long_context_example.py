"""Long-context training layout end-to-end: ring attention + checkpointing.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context_example.py

A (data=2, sp=4) mesh shards the sequence across devices; attention runs as
ring attention (KV blocks rotate over the `sp` axis — O(S/n) memory per
device), one train step executes, and the sequence-sharded train state
checkpoints and restores with its layout preserved.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.models import (
    LlamaConfig,
    init_params,
    make_train_step,
)


def main() -> None:
    n = len(jax.devices())
    sp = 4 if n >= 8 else max(1, n // 2)
    data = max(1, n // sp)
    devices = np.array(jax.devices()[: data * sp]).reshape(data, sp)
    mesh = Mesh(devices, ("data", "sp"))
    print(f"mesh: data={data} x sp={sp} (sequence sharded over 'sp')")

    cfg = LlamaConfig(
        vocab_size=512,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
    )
    params = init_params(jax.random.key(0), cfg)
    opt = optax.adamw(1e-3)
    train_state = {
        "params": params,
        "opt_state": opt.init(params),
        "step": jnp.zeros((), jnp.int32),
    }

    step_fn = jax.jit(
        make_train_step(
            cfg, opt, activation_spec=P("data", "sp"), ring=(mesh, "sp", "data")
        )
    )
    seq_len = 16 * sp  # long context: divisible across the ring
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (2 * data, seq_len), 0, 512),
        NamedSharding(mesh, P("data", None)),
    )
    with mesh:
        train_state, loss = step_fn(train_state, tokens)
        jax.block_until_ready(loss)
    print(f"ring-attention train step done; loss={float(loss):.4f}")

    with tempfile.TemporaryDirectory() as tmp:
        snapshot = Snapshot.take(f"{tmp}/snap", {"train": StateDict(train_state)})
        target = {
            "train": StateDict(jax.tree.map(jnp.zeros_like, train_state))
        }
        snapshot.restore(target)
        restored = int(jax.device_get(target["train"]["step"]))
        assert restored == 1, restored
        print("checkpoint round trip verified (step", restored, ")")


if __name__ == "__main__":
    main()
