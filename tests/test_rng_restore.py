"""The ``rng_restore`` counter: what restoring an ``RNGState``, planned and
loaded last, adds to ``Snapshot.restore``.  One occurrence a restore that
restored one (its manifest entries, the bytes its reads took, its plan's wall
and the tail from the stateful before it to its own), the same numbers in the
``restore.end`` event; nothing where no ``RNGState`` was restored."""

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import RNGState, Snapshot, StateDict, phase_stats
from torchsnapshot_tpu.event_handlers import register_event_handler, unregister_event_handler


@pytest.fixture
def ends():
    got = []

    def on_event(event):
        if event.name == "restore.end":
            got.append(event.metadata)

    register_event_handler(on_event)
    yield got
    unregister_event_handler(on_event)


def restored(path, app_state):
    before = phase_stats.snapshot()
    Snapshot(path).restore(app_state)
    return phase_stats.delta(before)


def test_a_restore_with_an_rng_state_records_what_it_added(ends):
    path = f"memory://rng_restore_{time.monotonic_ns()}"
    key = jax.random.key(7)
    Snapshot.take(path, {"params": StateDict(w=jnp.arange(64.0)), "rng": RNGState(jax_key=key)})
    saved = random.getstate()
    random.random()
    target = {"params": StateDict(w=jnp.zeros(64)), "rng": RNGState(jax_key=jax.random.key(0))}
    delta = restored(path, target)
    c = delta["rng_restore"]
    # Python's generator (625 words and its framing) as primitives, numpy's
    # as an array beside four primitives, the key's data: about 630 entries
    assert c["n"] == 1 and 620 <= c["entries"] <= 660
    assert c["plan_s"] >= 0 and c["tail_s"] >= 0 and c["s"] == pytest.approx(c["plan_s"] + c["tail_s"])
    # numpy's 624 words and the key's two, at least, come by reads
    assert c["bytes"] >= 624 * 4 + 2 * 4
    assert ends[-1]["rng_restore"] == {k: c[k] for k in ("bytes", "entries", "plan_s", "tail_s", "s")}
    # and the state itself is back: the key, the process's generator
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(target["rng"].jax_key)), np.asarray(jax.random.key_data(key)))
    assert random.getstate() == saved
    np.testing.assert_array_equal(np.asarray(target["params"]["w"]), np.arange(64.0))


def test_a_restore_without_an_rng_state_records_none(ends):
    path = f"memory://rng_restore_{time.monotonic_ns()}"
    Snapshot.take(path, {"params": StateDict(w=jnp.arange(64.0)), "rng": RNGState()})
    # saved with one and restored without it: nothing of it is restored
    delta = restored(path, {"params": StateDict(w=jnp.zeros(64))})
    assert "rng_restore" not in delta and "rng_restore" not in ends[-1]
    other = f"memory://rng_restore_{time.monotonic_ns()}"
    Snapshot.take(other, {"params": StateDict(w=jnp.arange(64.0))})
    delta = restored(other, {"params": StateDict(w=jnp.zeros(64))})
    assert "rng_restore" not in delta and "rng_restore" not in ends[-1]
