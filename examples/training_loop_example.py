"""Training-loop checkpointing: periodic async saves, interruption, resume.

The TPU-native analogue of the reference's DDP training example
(/root/reference/examples/ddp_example.py): a data-parallel model on a device
mesh, checkpointed every few steps with ``async_take`` through a
:class:`SnapshotManager` (step-numbered directories, retention, resume-
latest), "crashed" mid-run, and resumed exactly where it left off — the
restored step counter, parameters, optimizer state, and RNG line up.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/training_loop_example.py
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

from torchsnapshot_tpu import RNGState, SnapshotManager, StateDict

LAYER_SIZES = [(128, 64), (64, 32), (32, 1)]
TOTAL_STEPS = 12
SAVE_EVERY = 4


def init_params(key):
    params = {}
    for i, (fan_in, fan_out) in enumerate(LAYER_SIZES):
        key, sub = jax.random.split(key)
        params[f"w{i}"] = jax.random.normal(sub, (fan_in, fan_out)) * 0.05
        params[f"b{i}"] = jnp.zeros((fan_out,))
    return params


def forward(params, x):
    for i in range(len(LAYER_SIZES)):
        x = x @ params[f"w{i}"] + params[f"b{i}"]
        if i < len(LAYER_SIZES) - 1:
            x = jax.nn.relu(x)
    return x


@jax.jit
def train_step(params, opt_state, x, y):
    def loss_fn(p):
        pred = forward(p, x)
        return jnp.mean((pred - y) ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = optimizer.update(grads, opt_state)
    params = optax.apply_updates(params, updates)
    return params, opt_state, loss


optimizer = optax.adam(1e-3)


def make_batch(step):
    rng = np.random.RandomState(step)
    x = rng.rand(32, 128).astype(np.float32)
    return x, (x @ np.ones((128, 1), np.float32) * 0.01)


def train(ckpt_dir: str, stop_after: int) -> tuple:
    """Train until ``stop_after`` steps have run IN THIS PROCESS INVOCATION,
    checkpointing every SAVE_EVERY steps; resumes from the latest committed
    snapshot if one exists.  Returns (last_step, params, resumed_from_step
    or None)."""
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
    replicated = NamedSharding(mesh, P())

    params = jax.device_put(init_params(jax.random.key(42)), replicated)
    opt_state = jax.device_put(optimizer.init(params), replicated)
    progress = StateDict({"step": 0})
    manager = SnapshotManager(ckpt_dir, max_to_keep=2)

    app_state = {
        "model": StateDict(params),
        "optim": StateDict({"opt": opt_state}),
        "progress": progress,
        "rng": RNGState(),
    }
    latest = manager.restore_latest(app_state)  # the resume-if-possible idiom
    if latest is not None:
        params = dict(app_state["model"])
        opt_state = app_state["optim"]["opt"]
        print(f"resumed from step {progress['step']} (snapshot {latest})")

    resumed_from = latest
    pending = None
    ran_here = 0
    while progress["step"] < TOTAL_STEPS and ran_here < stop_after:
        step = progress["step"]
        x, y = make_batch(step)
        params, opt_state, loss = train_step(params, opt_state, x, y)
        progress["step"] = step + 1
        ran_here += 1
        if progress["step"] % SAVE_EVERY == 0:
            if pending is not None:
                pending.wait()  # at most one checkpoint in flight
            app_state["model"] = StateDict(params)
            app_state["optim"] = StateDict({"opt": opt_state})
            pending = manager.save(progress["step"], app_state, async_=True, incremental=True)
            print(
                f"step {progress['step']}: loss {float(loss):.5f} "
                f"(async snapshot {progress['step']} launched)"
            )
    if pending is not None:
        pending.wait()
    return progress["step"], params, resumed_from


def main() -> None:
    ckpt_dir = os.path.join(
        tempfile.mkdtemp(prefix="tpusnap_train_"), "ckpts"
    )

    # Phase 1: run 7 steps, then "crash" (process would die here).
    step, _, resumed_from = train(ckpt_dir, stop_after=7)
    assert step == 7 and resumed_from is None
    print(f"-- simulated crash after step {step}; latest committed "
          f"snapshot is step {SAVE_EVERY * (step // SAVE_EVERY)} --")

    # Phase 2: a fresh invocation resumes from the latest committed
    # snapshot (step 4) and finishes the run.
    final_step, resumed_params, resumed_from = train(
        ckpt_dir, stop_after=TOTAL_STEPS
    )
    assert final_step == TOTAL_STEPS, final_step
    # The resume genuinely happened (a silently-fresh run would make the
    # equality check below pass vacuously).
    assert resumed_from == 4, resumed_from

    # The resumed run retraced steps 4..12 from the checkpoint; a
    # straight-through run must land on identical parameters (exact
    # determinism of restore: params, optimizer state, step counter).
    straight_dir = os.path.join(
        tempfile.mkdtemp(prefix="tpusnap_train_straight_"), "ckpts"
    )
    _, straight_params, _ = train(straight_dir, stop_after=TOTAL_STEPS)
    for k in resumed_params:
        # Bit-exact: restore is deterministic (params, optimizer state,
        # step counter, RNG all round-trip exactly).
        np.testing.assert_array_equal(
            np.asarray(resumed_params[k]),
            np.asarray(straight_params[k]),
            err_msg=k,
        )
    print("resumed run matches straight-through run exactly — OK")


if __name__ == "__main__":
    main()
