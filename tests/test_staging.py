"""Staging helper unit tests: D2H paths, sharding predicates, spec capture."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import staging


def _mesh8():
    return Mesh(np.array(jax.devices()), ("x",))


def test_predicates():
    host = np.zeros(4)
    single = jnp.zeros(4)
    sharded = jax.device_put(
        jnp.zeros((8, 4)), NamedSharding(_mesh8(), P("x", None))
    )
    replicated = jax.device_put(jnp.zeros(4), NamedSharding(_mesh8(), P()))

    assert not staging.is_jax_array(host)
    assert staging.is_jax_array(single)
    assert staging.is_array_like(host) and staging.is_array_like(single)
    assert staging.is_sharded(sharded)
    assert not staging.is_sharded(single)
    assert not staging.is_sharded(replicated)
    assert staging.is_fully_replicated(replicated)
    assert not staging.is_fully_replicated(single)  # single device: trivial


@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float16, jnp.int8, jnp.float32, jnp.bool_, jnp.int4]
)
def test_enqueue_then_to_host_roundtrip(dtype):
    """Arrays cross the link in their own dtype and shape, bit for bit."""
    x = jnp.arange(48).reshape(6, 8).astype(dtype)
    staging.enqueue_d2h(x)
    host = staging.to_host(x)
    assert host.dtype == np.dtype(x.dtype) and host.shape == (6, 8)
    np.testing.assert_array_equal(host, np.asarray(x))


def test_failed_async_copy_is_reported(monkeypatch, caplog):
    """A backend that cannot enqueue the async copy still lands the bytes
    (blocking), but no longer silently: the log and the staging_downgrade
    event stream carry it (chip_smoke.py fails on either)."""
    from torchsnapshot_tpu.event_handlers import (
        register_event_handler,
        unregister_event_handler,
    )

    class _NoAsync:
        def copy_to_host_async(self):
            raise RuntimeError("UNIMPLEMENTED: pretend")

        def __array__(self, dtype=None, copy=None):
            return np.arange(4, dtype=np.float32)

    monkeypatch.setattr(staging, "is_jax_array", lambda obj: True)
    events = []
    register_event_handler(events.append)
    try:
        with caplog.at_level("WARNING", logger="torchsnapshot_tpu"):
            arr = _NoAsync()
            staging.enqueue_d2h(arr)
            host = staging.to_host(arr)
    finally:
        unregister_event_handler(events.append)
    np.testing.assert_array_equal(host, np.arange(4, dtype=np.float32))
    assert [
        (e.metadata["from_mode"], e.metadata["to_mode"])
        for e in events
        if e.name == "async_take.staging_downgrade"
    ] == [("async_d2h", "blocking_d2h")]
    assert "pretend" in events[0].metadata["reason"]
    assert any("copy_to_host_async failed" in r.getMessage() for r in caplog.records)


def test_local_shards_dedup():
    # replicated over x: 8 devices hold the same box -> one distinct shard
    arr = jax.device_put(jnp.arange(16), NamedSharding(_mesh8(), P()))
    shards = staging.local_shards(arr)
    assert len(shards) == 1
    offsets, data = shards[0]
    assert offsets == (0,)
    np.testing.assert_array_equal(np.asarray(data), np.arange(16))


def test_partition_spec_capture():
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("a", "b"))
    arr = jax.device_put(jnp.zeros((8, 4)), NamedSharding(mesh, P(("a", "b"), None)))
    mesh_shape, axis_names, per_dim = staging.partition_spec_of(arr)
    assert mesh_shape == [4, 2]
    assert axis_names == ["a", "b"]
    assert per_dim == [["a", "b"], []]


def test_prng_key_envelope_roundtrip():
    key = jax.random.key(7)
    env = staging.prng_key_envelope(key)
    out = staging.maybe_unwrap_prng_key(env)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(out)), np.asarray(jax.random.key_data(key))
    )
    # non-envelope values pass through untouched
    assert staging.maybe_unwrap_prng_key({"a": 1}) == {"a": 1}
