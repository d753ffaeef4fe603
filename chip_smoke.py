"""The quickest proof that the system still starts on the chip.

Drives the home workload once, through the entry points a trainer calls
(``SnapshotManager.save(async_=True)`` / ``wait`` / ``restore_latest``,
``StateDict``, ``models.make_train_step`` with ``optax.adamw``), at the full
widths of Llama-3-8B cut by depth, in ONE process that uses every device
``jax.devices()`` shows:

  born-sharded train state -> jitted donating train steps -> async save while
  the loop keeps stepping -> wait -> drop the state -> zeroed target under
  another (fsdp, model) factorisation -> restore -> every leaf bit-equal to a
  host copy taken at save time -> resumed losses against the uninterrupted
  run's.

Run as a script it requires an accelerator and exits non-zero without one,
printing no result.  Any hidden fallback on the way (staging downgrade, a
repack kernel that did not run, a pure-Python data plane, a skipped restore
point) fails the run.  The last line of stdout is the driver's contract and
nothing more: ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
line before it, ``[chip_smoke] result {...}``, carries the rest: staging mode,
native library, the cut, the meshes, peak HBM.  Wall times in it are
information, not a measurement.

``run_smoke`` takes the config and the devices, so tier-1 calls it at
``LlamaConfig.tiny()`` on the CPU mesh (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, List, Sequence

# Events that mean a fast path on the smoke's route quietly became a slower
# one, or a restore point was skipped.  Any of them fails the run.
FALLBACK_EVENTS = frozenset(
    {
        "async_take.staging_downgrade",
        "native.degraded",
        "restore_latest.fallback",
        "journal.fallback",
    }
)

# Share of a device's HBM the cut may plan to use; the rest is room for what
# the estimate below does not see (XLA temporaries, fragmentation).
_HBM_PLAN_FRACTION = 0.85
# Depth is cut for time as well as for room: the state crosses the host link
# and the disk twice.
_MAX_DEPTH = 4
# How far resumed losses may sit from the uninterrupted run's when the restore
# layout sums in another order than the save layout.
_CROSS_LAYOUT_LOSS_RTOL = 1e-3


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def choose_cut(n_devices: int, hbm_bytes_per_device: int):
    """Llama-3-8B at full width, cut by depth and parameter dtype to what
    ``n_devices`` of ``hbm_bytes_per_device`` hold: float32 parameters where
    they fit, else bfloat16; then the deepest stack up to ``_MAX_DEPTH``.

    What must fit, summed over the devices (AdamW keeps two moments in the
    parameter dtype, so the state is 3x the parameters):
    - a train step: the state, the gradients (1x parameters) and the float32
      scatter XLA may use for the embedding gradient;
    - a restore: the whole target plus the landed copy of the largest
      stateful (restore hands arrays to ``load_state_dict`` only when the
      stateful is complete), which is 1x parameters with the state split into
      params / mu / nu.
    """
    import jax.numpy as jnp

    from torchsnapshot_tpu.models import LlamaConfig

    full = LlamaConfig.llama3_8b()
    embed_f32 = full.vocab_size * full.d_model * 4
    room = _HBM_PLAN_FRACTION * hbm_bytes_per_device * n_devices
    for dtype in (jnp.float32, jnp.bfloat16):
        for depth in range(min(_MAX_DEPTH, full.n_layers), 0, -1):
            cfg = dataclasses.replace(full, n_layers=depth, param_dtype=dtype)
            params = cfg.param_count() * jnp.dtype(dtype).itemsize
            peak = 3 * params + params + embed_f32
            if peak <= room:
                return cfg
    raise RuntimeError(
        f"no depth of Llama-3-8B fits {n_devices} device(s) of "
        f"{hbm_bytes_per_device / 1e9:.1f} GB"
    )


def _hbm_stats(devices: Sequence[Any]) -> Dict[str, Any]:
    """Largest ``memory_stats()`` figures over the devices (None where the
    backend has none, as on CPU)."""
    keys = ("bytes_limit", "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved")
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return dict.fromkeys(keys)
    return {key: max(s[key] for s in stats) for key in keys}


def _assert_even_share(tree: Any, devices: Sequence[Any], what: str) -> int:
    """No device may hold more than its share of ``tree``: the sum of its
    addressable shards' bytes against total/n.  The slack covers the leaves
    the partition rules replicate over an axis (norms, counters).  Returns
    the largest per-device byte count."""
    import jax

    per_device = {d: 0 for d in devices}
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
    worst = max(per_device.values())
    limit = total / len(devices) * 1.02 + (64 << 10)
    if worst > limit:
        raise AssertionError(
            f"{what}: a device holds {worst} bytes of a {total}-byte state "
            f"over {len(devices)} devices (share {total // len(devices)}): "
            f"{ {str(d): b for d, b in per_device.items()} }"
        )
    return worst


def _split_statefuls(state: Dict[str, Any]) -> Dict[str, Any]:
    """params / mu / nu / progress as four statefuls, so that restore's
    target-plus-landed-copy peak is state x 4/3 and not state x 2."""
    from torchsnapshot_tpu import StateDict

    adam = state["opt_state"][0]
    return {
        "params": StateDict(params=state["params"]),
        "adam_mu": StateDict(mu=adam.mu),
        "adam_nu": StateDict(nu=adam.nu),
        "progress": StateDict(step=state["step"], adam_count=adam.count),
    }


def _join_statefuls(app_state: Dict[str, Any], opt_state_like: tuple) -> Dict[str, Any]:
    """The train state back from the four statefuls; ``opt_state_like`` is an
    optimizer state of the same structure, for its leafless remainder."""
    adam = opt_state_like[0]._replace(
        count=app_state["progress"]["adam_count"],
        mu=app_state["adam_mu"]["mu"],
        nu=app_state["adam_nu"]["nu"],
    )
    return {
        "params": app_state["params"]["params"],
        "opt_state": (adam,) + tuple(opt_state_like[1:]),
        "step": app_state["progress"]["step"],
    }


def _bits(a) -> Any:
    import numpy as np

    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


def run_smoke(
    cfg,
    devices: Sequence[Any],
    *,
    seq_len: int = 128,
    steps_before_save: int = 2,
    steps_after_save: int = 3,
    seed: int = 0,
) -> Dict[str, Any]:
    """The whole path at ``cfg`` on ``devices``; raises on the first phase
    that fails.  Returns the fields of the ``result`` line."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import SnapshotManager
    from torchsnapshot_tpu.event_handlers import (
        register_event_handler,
        unregister_event_handler,
    )
    from torchsnapshot_tpu.models import init_train_state, make_train_step
    from torchsnapshot_tpu.native_io import NativeFileIO
    from torchsnapshot_tpu.parallel import make_mesh

    n = len(devices)
    platform = devices[0].platform
    if n > 1 and n % 2:
        raise ValueError(f"{n} devices: need 1 or an even number for (fsdp, model)")
    save_axes = (n, 1)
    restore_axes = (n // 2, 2) if n > 1 else (1, 1)
    mesh_a = make_mesh(data=1, fsdp=save_axes[0], model=save_axes[1], devices=devices)
    mesh_b = make_mesh(
        data=1, fsdp=restore_axes[0], model=restore_axes[1], devices=devices
    )
    for name, mesh in (("save", mesh_a), ("restore", mesh_b)):
        ids = np.vectorize(lambda d: d.id)(mesh.devices)
        _log(f"{name} mesh (data, fsdp, model), device ids:\n{ids}")

    native = NativeFileIO.maybe_create()
    if native is None:
        raise RuntimeError(
            "native library not loaded: the data plane would be pure Python"
        )

    events: List[Any] = []
    warnings: List[str] = []

    class _Warnings(logging.Handler):
        def emit(self, record: logging.LogRecord) -> None:
            warnings.append(f"{record.name}: {record.getMessage()}")

    log_handler = _Warnings(level=logging.WARNING)
    pkg_logger = logging.getLogger("torchsnapshot_tpu")
    register_event_handler(events.append)
    pkg_logger.addHandler(log_handler)
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    wall: Dict[str, float] = {}
    t0 = time.monotonic()
    try:
        opt = optax.adamw(1e-3)
        batch = 2 * n
        rng = np.random.RandomState(seed)
        n_steps = steps_before_save + 2 * steps_after_save
        all_tokens = rng.randint(
            0, cfg.vocab_size, size=(n_steps, batch, seq_len), dtype=np.int32
        )

        def tokens_for(step: int, mesh) -> Any:
            return jax.device_put(
                all_tokens[step], NamedSharding(mesh, P("fsdp", None))
            )

        def compile_step(state):
            return jax.jit(
                make_train_step(cfg, opt),
                donate_argnums=(0,),
                out_shardings=(jax.tree.map(lambda x: x.sharding, state), None),
            )

        # --- init, born sharded
        state = jax.block_until_ready(
            init_train_state(jax.random.key(seed), cfg, opt, mesh_a)
        )
        state_bytes = sum(x.nbytes for x in jax.tree.leaves(state))
        n_leaves = len(jax.tree.leaves(state))
        share_init = _assert_even_share(state, devices, "after init")
        hbm_init = _hbm_stats(devices)
        wall["init"] = time.monotonic() - t0
        _log(
            f"state: {n_leaves} leaves, {state_bytes / 1e9:.3f} GB, "
            f"{share_init / 1e9:.3f} GB on the fullest device; hbm {hbm_init}"
        )

        # --- train, save asynchronously, keep training
        step_fn = compile_step(state)
        t = time.monotonic()
        losses: List[float] = []
        for i in range(steps_before_save):
            state, loss = step_fn(state, tokens_for(i, mesh_a))
            losses.append(float(loss))
        wall["steps_before_save_incl_compile"] = time.monotonic() - t
        save_step = int(state["step"])
        if save_step != steps_before_save:
            raise AssertionError(f"step {save_step} != {steps_before_save}")
        # The oracle: a host copy of every leaf, taken at save time (a copy:
        # on CPU asarray may alias the buffer the next step is donated).
        oracle = jax.tree.map(np.array, state)

        mgr = SnapshotManager(workdir)
        t = time.monotonic()
        pending = mgr.save(save_step, _split_statefuls(state), async_=True)
        wall["async_take_returned"] = time.monotonic() - t
        staging_mode = pending.staging_mode
        # The uninterrupted run: the loop goes on, donating the buffers the
        # save was taken from, while the drain runs in the background.
        for i in range(steps_before_save, steps_before_save + steps_after_save):
            state, loss = step_fn(state, tokens_for(i, mesh_a))
            losses.append(float(loss))
        wall["steps_during_drain"] = time.monotonic() - t
        pending.wait()
        wall["save_committed"] = time.monotonic() - t
        hbm_save = _hbm_stats(devices)
        if not all(np.isfinite(losses)):
            raise AssertionError(f"non-finite loss: {losses}")
        _log(f"saved step {save_step}: staging_mode={staging_mode}; hbm {hbm_save}")
        if platform != "cpu" and staging_mode == "host":
            raise AssertionError(
                "async_take staged through host memory on an accelerator"
            )

        # --- the kill: drop everything the run held on the devices
        del state, pending, step_fn
        target = init_train_state(None, cfg, opt, mesh_b)
        _assert_even_share(target, devices, "restore target")
        shardings_b = jax.tree.map(lambda x: x.sharding, target)
        app_state = _split_statefuls(target)
        # Structure only: what is left of the optimizer state has no leaves.
        opt_state_like = jax.tree.map(lambda _: None, target["opt_state"])
        del target

        # --- resume under the other layout
        t = time.monotonic()
        restored_step = SnapshotManager(workdir).restore_latest(app_state)
        restored = jax.block_until_ready(
            _join_statefuls(app_state, opt_state_like)
        )
        del app_state
        wall["restore_landed"] = time.monotonic() - t
        hbm_restore = _hbm_stats(devices)
        if restored_step != save_step or int(restored["step"]) != save_step:
            raise AssertionError(
                f"restored step {restored_step} / {int(restored['step'])}, "
                f"saved {save_step}"
            )
        share_restore = _assert_even_share(restored, devices, "after restore")
        for got, want in zip(
            jax.tree.leaves(restored), jax.tree.leaves(shardings_b)
        ):
            if not got.sharding.is_equivalent_to(want, got.ndim):
                raise AssertionError(
                    f"restore changed a target's layout: {got.sharding} != {want}"
                )
        # Bit equality, leaf by leaf, releasing the oracle as it goes.
        flat_restored = jax.tree_util.tree_flatten_with_path(restored)[0]
        flat_oracle = jax.tree.leaves(oracle)
        del oracle
        if len(flat_restored) != n_leaves or len(flat_oracle) != n_leaves:
            raise AssertionError("restored tree has another number of leaves")
        for i, (path, got) in enumerate(flat_restored):
            want = flat_oracle[i]
            flat_oracle[i] = None
            got = np.asarray(got)
            if got.dtype != want.dtype or got.shape != want.shape:
                raise AssertionError(
                    f"{jax.tree_util.keystr(path)}: {got.dtype}{got.shape} != "
                    f"{want.dtype}{want.shape}"
                )
            if not np.array_equal(_bits(got), _bits(want)):
                raise AssertionError(
                    f"{jax.tree_util.keystr(path)} is not bit-equal after restore"
                )
        wall["verified"] = time.monotonic() - t
        _log(f"restored step {restored_step}: {n_leaves} leaves bit-equal; hbm {hbm_restore}")

        # --- the resumed run must continue the uninterrupted one
        same_layout = restore_axes == save_axes
        step_fn = compile_step(restored)
        t = time.monotonic()
        resumed: List[float] = []
        for i in range(steps_before_save, steps_before_save + steps_after_save):
            restored, loss = step_fn(restored, tokens_for(i, mesh_b))
            resumed.append(float(loss))
        wall["resumed_steps_incl_compile"] = time.monotonic() - t
        reference = losses[steps_before_save:]
        loss_rel_diff = float(
            np.max(np.abs(np.subtract(resumed, reference)) / np.abs(reference))
        )
        # The same layout runs the same program on the same bits: equal.
        # Another factorisation sums in another order, so the bound is a
        # tolerance: 8e-5 was seen on four chips (PR 21), and consecutive
        # steps differ by 5e-3, so a resume that is one step off, or that
        # carries the wrong moments, is outside it.
        if loss_rel_diff > (0.0 if same_layout else _CROSS_LAYOUT_LOSS_RTOL):
            raise AssertionError(
                f"resumed losses {resumed} != uninterrupted {reference} "
                f"(same layout: {same_layout}, relative difference "
                f"{loss_rel_diff:.2e})"
            )
        if int(restored["step"]) != save_step + steps_after_save:
            raise AssertionError("resumed run lost count of its steps")
        del restored

        fallbacks = [e for e in events if e.name in FALLBACK_EVENTS]
        if fallbacks:
            raise AssertionError(
                "fallback on the smoke's path: "
                + "; ".join(f"{e.name} {e.metadata}" for e in fallbacks)
            )
        # The fallbacks with no event of their own (a failed batched upload,
        # a backend that cannot answer addressable_memories() or
        # memory_stats(), a native library that would not load) warn.
        if warnings:
            raise AssertionError(
                "library warning on the smoke's path: " + "; ".join(warnings)
            )
    finally:
        pkg_logger.removeHandler(log_handler)
        unregister_event_handler(events.append)
        shutil.rmtree(workdir, ignore_errors=True)
        for w in warnings:
            _log(f"library warning: {w}")

    return {
        "staging_mode": staging_mode,
        "native_library": native._lib._name,
        "cut": {
            "d_model": cfg.d_model,
            "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size,
            "n_layers": cfg.n_layers,
            "param_dtype": np.dtype(cfg.param_dtype).name,
            "batch": batch,
            "seq_len": seq_len,
        },
        "mesh": {
            "save_fsdp_model": list(save_axes),
            "restore_fsdp_model": list(restore_axes),
        },
        "leaves": n_leaves,
        "state_bytes": state_bytes,
        "fullest_device_state_bytes": {
            "after_init": share_init,
            "after_restore": share_restore,
        },
        "hbm": {
            "bytes_limit": hbm_init["bytes_limit"],
            "peak_after_init": hbm_init["peak_bytes_in_use"],
            "peak_after_save": hbm_save["peak_bytes_in_use"],
            "peak_after_restore": hbm_restore["peak_bytes_in_use"],
            # Restore holds the target plus the landed copy of one stateful.
            "restore_peak_over_fullest_share": (
                round(hbm_restore["peak_bytes_in_use"] / share_restore, 3)
                if hbm_restore["peak_bytes_in_use"]
                else None
            ),
        },
        "losses": {"uninterrupted": losses, "resumed": resumed},
        "loss_check": (
            "equal" if same_layout else f"rtol={_CROSS_LAYOUT_LOSS_RTOL:g}"
        ),
        "loss_rel_diff": loss_rel_diff,
        # What the loss check must resolve: the smallest relative change
        # between consecutive steps of the uninterrupted run.
        "loss_step_rel_change": float(
            np.min(np.abs(np.diff(losses)) / np.abs(losses[:-1]))
        ),
        "wall_s_not_a_measurement": {k: round(v, 2) for k, v in wall.items()},
    }


def report(device: Dict[str, Any], body: Callable[[], Dict[str, Any]]) -> int:
    """Runs ``body`` on an accelerator already found, prints what it returns
    as the ``result`` line, and then, last, the line the driver parses: exactly
    ``ok`` and ``device`` (``platform``, ``kind``, ``count``).  A body that
    raises is reported as ``"ok": false`` with a non-zero exit code."""
    try:
        detail = body()
    except Exception:
        traceback.print_exc()
        ok = False
    else:
        _log("result " + json.dumps(detail))
        ok = True
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


def main() -> int:
    import jax

    from torchsnapshot_tpu._native.build import rebuild_native_lib
    from torchsnapshot_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] == "cpu":
        print(
            f"chip_smoke: JAX found no accelerator (platform "
            f"{device['platform']!r}, {device['count']} device(s) of kind "
            f"{device['kind']!r}); this check only runs on the chip",
            file=sys.stderr,
        )
        return 2

    def on_chip() -> Dict[str, Any]:
        _log(f"device {device}; compile cache at {cache_dir}")
        _log(f"memories: {[m.kind for m in devices[0].addressable_memories()]}")
        # The data plane must be the committed source, compiled on this
        # machine: a library copied in with the tree is replaced, a missing
        # compiler raises.
        built = rebuild_native_lib()
        cfg = choose_cut(len(devices), devices[0].memory_stats()["bytes_limit"])
        _log(
            f"cut: Llama-3-8B widths, n_layers={cfg.n_layers} of 32, "
            f"param_dtype={jax.numpy.dtype(cfg.param_dtype).name}"
        )
        result = run_smoke(cfg, devices)
        if result["native_library"] != built:
            raise AssertionError(f"loaded {result['native_library']}, built {built}")
        return {"device": device, **result, "native_built_here": True, "claim": None}

    return report(device, on_chip)


if __name__ == "__main__":
    sys.exit(main())
