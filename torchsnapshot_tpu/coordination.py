"""Bridge to the JAX distributed coordination service.

When the training job already called ``jax.distributed.initialize()``, every
host has a connection to the coordination service (gRPC over DCN).  We expose
its KV interface as a :class:`~torchsnapshot_tpu.dist_store.KVStore` so the
snapshot layer can run object collectives and barriers over it without any
extra infrastructure — the TPU-native replacement for the reference's
c10d TCPStore bootstrap (/root/reference/torchsnapshot/dist_store.py:24-88).

The service has no atomic counter, so ``add`` is emulated with per-contributor
keys + a directory count.  That covers the snapshot layer's only usage
pattern: each rank contributes +1 at most once per unique key, and pollers
call ``add(key, 0)`` to read the count.
"""

from __future__ import annotations

import uuid
from typing import Optional

from .dist_store import KVStore


def _get_jax_client():
    # jax keeps the coordination client in a private module; an import or
    # attribute error here means the installed jax moved it, and is repaired
    # here rather than read as "not distributed".
    from jax._src import distributed

    return distributed.global_state.client


def jax_process_info() -> Optional[tuple]:
    """(rank, world_size) if jax.distributed is initialized, else None."""
    from jax._src import distributed

    state = distributed.global_state
    if state.client is None:
        return None
    return state.process_id, state.num_processes


class JaxCoordinationStore(KVStore):
    def __init__(self, client) -> None:
        self._client = client
        self._uid = uuid.uuid4().hex

    def set(self, key: str, value: bytes) -> None:
        self._client.key_value_set_bytes(key, value)

    def get(self, key: str, timeout_s=None) -> bytes:
        from .dist_store import resolve_wait_timeout_s

        try:
            return self._client.blocking_key_value_get_bytes(
                key, int(resolve_wait_timeout_s(timeout_s) * 1000)
            )
        except Exception as e:
            # Normalize the service's DEADLINE_EXCEEDED XlaRuntimeError to the
            # KVStore.get contract so barrier/LinearBarrier timeout handling
            # (and their error-key re-check) works uniformly across backends.
            msg = str(e).lower()
            if "deadline" in msg or "timed out" in msg or "timeout" in msg:
                raise TimeoutError(
                    f"Timed out waiting for store key: {key}"
                ) from e
            raise

    def try_get(self, key: str) -> Optional[bytes]:
        try:
            return self._client.key_value_try_get_bytes(key)
        except Exception:
            return None

    def add(self, key: str, amount: int) -> int:
        if amount > 0:
            for i in range(amount):
                self._client.key_value_set_bytes(
                    f"{key}/contrib/{self._uid}/{uuid.uuid4().hex}", b"1"
                )
        try:
            entries = self._client.key_value_dir_get_bytes(f"{key}/contrib")
        except Exception:
            return 0
        return len(entries)

    def delete_prefix(self, prefix: str) -> int:
        # The coordination service's delete has directory semantics: removing
        # a key recursively removes everything under it.  Count is not
        # reported; return 1 as "attempted" so callers can tell it ran.
        try:
            self._client.key_value_delete(prefix.rstrip("/"))
            return 1
        except Exception:
            return 0


def maybe_jax_coordination_store() -> Optional[KVStore]:
    client = _get_jax_client()
    if client is None:
        return None
    return JaxCoordinationStore(client)
