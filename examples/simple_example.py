"""Minimal end-to-end example (reference examples/simple_example.py): train a
tiny model, snapshot it, restore into a fresh one, verify equality."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchsnapshot_tpu import RNGState, Snapshot, StateDict
from torchsnapshot_tpu.tricks.flax import PytreeAdapter


def main() -> None:
    key = jax.random.key(0)
    params = {
        "w": jax.random.normal(key, (8, 4), dtype=jnp.float32),
        "b": jnp.zeros((4,), jnp.float32),
    }
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss_fn(p):
            pred = x @ p["w"] + p["b"]
            return jnp.mean((pred - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    x = jax.random.normal(jax.random.key(1), (16, 8))
    y = jax.random.normal(jax.random.key(2), (16, 4))
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, x, y)
    print("trained 5 steps, loss:", float(loss))

    app_state = {
        "params": PytreeAdapter(params),
        "opt": PytreeAdapter(opt_state),
        "extra": StateDict({"steps_done": 5}),
        "rng": RNGState(),
    }
    snapshot = Snapshot.take("/tmp/tpusnap_example/snap", app_state)
    print("snapshot taken at", snapshot.path)

    fresh_params = PytreeAdapter(jax.tree.map(jnp.zeros_like, params))
    fresh_opt = PytreeAdapter(tx.init(jax.tree.map(jnp.zeros_like, params)))
    extra = StateDict({"steps_done": 0})
    snapshot.restore(
        {"params": fresh_params, "opt": fresh_opt, "extra": extra, "rng": RNGState()}
    )

    np.testing.assert_array_equal(
        np.asarray(fresh_params.tree["w"]), np.asarray(params["w"])
    )
    assert extra["steps_done"] == 5
    print("restore verified; a single weight:", snapshot.read_object("0/params/b"))


if __name__ == "__main__":
    main()
