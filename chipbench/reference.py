"""The plain reference of what the library promises, and its control.

The promise (each configuration's ``guarantees``): what ``restore`` hands
back is, bit for bit and in the stated dtypes, what ``save`` was given, and
the job trains on from it as if it had never stopped.  The reference is
therefore the live state itself, read where it lives by plain ``jax.numpy``
code of the benchmark's own, never by the library: a 64-bit fingerprint of
every leaf, taken on the device just before ``save`` is called, and the loss
of the step the live job took from that state.  A restore is right when the
restored leaves give the same fingerprints and the same step gives the same
loss.  Limits are 0: the comparison is exact.

The control (``LowerPrecisionStore``) is the reference put in the program's
place in the nearest precision below the one the configuration states: a
store that keeps bfloat16 for a float32 leaf and float8_e4m3fn for a
bfloat16 leaf, and hands back the stated dtype.  ``compare`` must fail it.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# The number a run prints beside each limit.
LIMITS = {"leaves_differ": 0, "loss_gap": 0.0, "step_gap": 0}

_NEXT_LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn", "float16": "float8_e4m3fn"}


def _words(x):
    """The leaf's bits as uint32 words of the same shape (a same-width
    bitcast, widened: a narrowing bitcast adds a minor dimension that the
    TPU's tiling pads 64-fold, PR 21 finding 1)."""
    import jax
    import jax.numpy as jnp

    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint32)
    size = x.dtype.itemsize
    if size == 8:
        raise ValueError("64-bit leaves are not in any configuration")
    unsigned = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[size]
    return jax.lax.bitcast_convert_type(x, unsigned).astype(jnp.uint32)


def _fingerprint_leaf(x):
    """Two uint32 sums over the leaf's words, one plain and one weighted by
    the word's position, both modulo 2**32: a flipped bit, a rounded value,
    two rows swapped or a leaf left at zero each change at least one."""
    import jax
    import jax.numpy as jnp

    w = _words(x)
    if w.ndim == 0:
        return jnp.stack([w, w * jnp.uint32(2654435761)])
    index = jnp.zeros(w.shape, jnp.uint32)
    stride = 1
    for axis in range(w.ndim - 1, -1, -1):
        index = index + jax.lax.broadcasted_iota(jnp.uint32, w.shape, axis) * jnp.uint32(
            stride & 0xFFFFFFFF
        )
        stride *= w.shape[axis]
    weight = index * jnp.uint32(2654435761) + jnp.uint32(1)
    return jnp.stack([jnp.sum(w, dtype=jnp.uint32), jnp.sum(w * weight, dtype=jnp.uint32)])


class Fingerprinter:
    """One jitted program that fingerprints every leaf of a train state."""

    def __init__(self) -> None:
        self._fn = None

    def __call__(self, state) -> Any:
        """Dispatches the program and returns the device array ``[leaves,
        2]``; read it with ``np.asarray`` once the device is idle."""
        import jax
        import jax.numpy as jnp

        if self._fn is None:
            self._fn = jax.jit(
                lambda s: jnp.stack([_fingerprint_leaf(x) for x in jax.tree.leaves(s)])
            )
        return self._fn(state)


def leaf_names(state) -> List[str]:
    import jax

    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(state)[0]]


def compare(
    checked: List[Dict[str, Any]],
) -> Tuple[bool, Dict[str, Dict[str, float]], List[str]]:
    """``checked``: one entry per restore that was compared, with
    ``fingerprint``/``want_fingerprint`` (``[leaves, 2]`` uint32 arrays),
    ``loss``/``want_loss`` (floats; the reference's may be None where the
    live job never took that step), ``step``/``want_step`` and ``names``.
    Returns ``correct``, the numbers beside their limits, and notes."""
    notes: List[str] = []
    leaves_differ = 0
    loss_gap = 0.0
    step_gap = 0
    if not checked:
        notes.append("nothing was compared")
    for c in checked:
        got = np.asarray(c["fingerprint"])
        want = np.asarray(c["want_fingerprint"])
        if got.shape != want.shape:
            bad = np.ones(max(len(got), len(want)), bool)
        else:
            bad = np.any(got != want, axis=1)
        if bad.any():
            names = c.get("names") or [str(i) for i in range(len(bad))]
            notes.append(
                f"{c['what']}: {int(bad.sum())} leaves differ, first "
                f"{[n for n, b in zip(names, bad) if b][:3]}"
            )
        leaves_differ = max(leaves_differ, int(bad.sum()))
        step_gap = max(step_gap, abs(int(c["step"]) - int(c["want_step"])))
        if c.get("want_loss") is None:
            notes.append(f"{c['what']}: the live job took no step from there; loss not compared")
        else:
            want_loss = float(c["want_loss"])
            gap = abs(float(c["loss"]) - want_loss) / max(abs(want_loss), 1e-30)
            if not np.isfinite(gap):
                gap = float("inf")
            loss_gap = max(loss_gap, gap)
    numbers = {
        "leaves_differ": {"value": leaves_differ, "limit": LIMITS["leaves_differ"]},
        "loss_gap": {"value": loss_gap, "limit": LIMITS["loss_gap"]},
        "step_gap": {"value": step_gap, "limit": LIMITS["step_gap"]},
    }
    correct = bool(checked) and all(v["value"] <= v["limit"] for v in numbers.values())
    return correct, numbers, notes


# ----------------------------------------------------------------- control


class LowerPrecisionStore:
    """The control: keeps each floating leaf one precision below its own, on
    the device, and gives it back in its own dtype.  Has the part of
    ``SnapshotManager`` that a job calls."""

    def __init__(self, root: str, max_to_keep: Optional[int] = None) -> None:
        self.root = root
        self.max_to_keep = max_to_keep
        self._kept: Dict[int, Dict[str, Dict[str, Any]]] = {}

    @staticmethod
    def _lower(x):
        import jax.numpy as jnp

        lower = _NEXT_LOWER.get(jnp.dtype(x.dtype).name)
        return x.astype(lower) if lower else jnp.copy(x)

    def save(self, step: int, app_state: Dict[str, Any]) -> None:
        """Returns committed."""
        import jax

        self._kept[step] = {
            key: jax.tree.map(self._lower, stateful.state_dict())
            for key, stateful in app_state.items()
        }
        while self.max_to_keep and len(self._kept) > self.max_to_keep:
            del self._kept[min(self._kept)]

    def restore_latest(self, app_state: Dict[str, Any]) -> Optional[int]:
        import jax
        import jax.numpy as jnp

        if not self._kept:
            return None
        step = max(self._kept)
        for key, stateful in app_state.items():
            target = stateful.state_dict()
            # A fresh buffer each time: the job donates what it is handed.
            stateful.load_state_dict(
                jax.tree.map(
                    lambda kept, t: jnp.copy(kept.astype(t.dtype)), self._kept[step][key], target
                )
            )
        return step
