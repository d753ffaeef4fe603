"""Managers with the timed path broken underneath, for the tests that must
see ``correct`` come out false, and one that only stalls."""

import time

import jax
import numpy as np

from torchsnapshot_tpu import SnapshotManager


def _flip_one_bit(app_state) -> None:
    nu = app_state["adam_nu"]["nu"]
    leaves, treedef = jax.tree.flatten(nu)
    a = np.array(leaves[-1])
    a.reshape(-1).view(np.uint8)[0] ^= 1
    leaves[-1] = jax.device_put(a, leaves[-1].sharding)
    app_state["adam_nu"]["nu"] = jax.tree.unflatten(treedef, leaves)


class FlipOneBit(SnapshotManager):
    """An answer altered where it is produced: one bit of one restored leaf."""

    def restore_latest(self, app_state):
        step = super().restore_latest(app_state)
        _flip_one_bit(app_state)
        return step



class RestoreNothing(SnapshotManager):
    """A restore that returns its target unchanged (all zeros) and says it
    restored."""

    def restore_latest(self, app_state):
        steps = self.all_steps()
        return steps[-1] if steps else None


class StaleRestore(SnapshotManager):
    """A restore that hands back the snapshot before the latest."""

    def restore_latest(self, app_state):
        steps = self.all_steps()
        if len(steps) < 2:
            return super().restore_latest(app_state)
        super().restore_at(steps[-2], app_state)
        return steps[-1]


class LosesAStateful(SnapshotManager):
    """A save that leaves one stateful out: its restore leaves mu at zero."""

    def save(self, step, app_state, **kwargs):
        return super().save(step, {k: v for k, v in app_state.items() if k != "adam_mu"}, **kwargs)

    def restore_latest(self, app_state):
        return super().restore_latest({k: v for k, v in app_state.items() if k != "adam_mu"})


def stalling(seconds: float):
    """The library with ``seconds`` of sleep inside ``save`` and inside
    ``restore_latest``: a stall inside the window."""

    class Stalling(SnapshotManager):
        def save(self, *args, **kwargs):
            time.sleep(seconds)
            return super().save(*args, **kwargs)

        def restore_latest(self, app_state):
            time.sleep(seconds)
            return super().restore_latest(app_state)

    return Stalling
