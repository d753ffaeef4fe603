"""The one traffic generator: it reads a mix (``chipbench/traffic/<mix>.json``)
and drives the job the mix describes against a snapshot manager.

A mix is data: a ``setup`` list and a ``cycle`` list of operations, and the
retention policy.  The job is closed-loop, one trainer, as a trainer is:
set-up runs once before the window, then whole cycles run back to back; a
cycle begins only while ``--seconds`` has not elapsed, and the window closes
when the last cycle begun has finished, so no operation is cut out of the
account.

An operation is a JSON object ``{"op": "<name>", ...parameters}``.  The
generator knows none by name: ``<name>`` is the file
``chipbench/ops/<name>.py`` with ``run(job, **parameters)`` and, where the
operation has a program of its own to warm, ``warm(job)``.  An operation
that is work of the library's (a save, a restore) runs inside
``job.operation(name)``, which puts it into the account: it is counted in
``attempted``, and in ``failed`` where it raises or the library fell back or
warned while it ran.  The end-to-end metrics are reduced from the account by
readers of their own (``chipbench/metrics/<metric>.py``).

The seed changes the weights and the tokens, never the work.
"""

from __future__ import annotations

import contextlib
import importlib.util
import os
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

from chipbench import reference, state as st

HERE = os.path.dirname(os.path.abspath(__file__))
TOKEN_POOL = 16


def load_module(directory: str, name: str, what: str) -> Any:
    """``chipbench/<directory>/<name>.py``, found by the name a data file
    gives it."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"chipbench: no {what} named {name!r}: {path} is not there")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{directory}_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Account:
    """Every counted operation and every interval of a run.  The metrics'
    readers read it."""

    def __init__(self) -> None:
        self.open_t: Optional[float] = None
        self.close_t: Optional[float] = None
        self.operations: List[Dict[str, Any]] = []
        self.spans: List[tuple] = []  # (name, begin, end) on time.monotonic
        self.failures: List[str] = []

    def in_window(self) -> bool:
        return self.open_t is not None and self.close_t is None

    def span(self, name: str, begin: float, end: float) -> None:
        if self.in_window():
            self.spans.append((name, begin, end))

    @property
    def window_s(self) -> float:
        return (self.close_t or time.monotonic()) - (self.open_t or time.monotonic())

    def window_operations(self, op: Optional[str] = None) -> List[Dict[str, Any]]:
        return [
            r for r in self.operations if r["in_window"] and (op is None or r["op"] == op)
        ]

    def attempted(self) -> int:
        return len(self.window_operations())

    def failed(self) -> int:
        return sum(1 for r in self.window_operations() if not r["ok"])


class Job:
    """One trainer with its snapshot manager, driven by a mix."""

    def __init__(
        self,
        load: Any,
        mix: Dict[str, Any],
        seed: int,
        root: str,
        make_manager: Optional[Callable[..., Any]] = None,
    ) -> None:
        self.load = load
        self.mix = mix
        self.seed = seed
        self.root = root
        if make_manager is None:
            from torchsnapshot_tpu import SnapshotManager

            make_manager = SnapshotManager
        self.manager = make_manager(root, max_to_keep=mix.get("max_to_keep"))
        self.account = Account()
        self.watch = st.FallbackWatch()
        self.fingerprint = reference.Fingerprinter()
        self.step_fn = load.step_fn()
        self.state = None
        self.tokens = None
        self.state_bytes = load.state_bytes()
        # What the live job did: the loss of the step taken FROM each step
        # index, and the fingerprint of the state each save was given.
        self.live_loss: Dict[int, Any] = {}
        self.saved_fp: Dict[int, Any] = {}
        self.checked: List[Dict[str, Any]] = []
        self.names = reference.leaf_names(load.abstract_state())
        self.step_index = 0
        self._ops = {
            op["op"]: load_module("ops", op["op"], "operation")
            for op in mix.get("setup", []) + mix["cycle"]
        }

    # -------------------------------------------------------------- set-up

    def warm_up(self) -> None:
        """The operations' own programs, then state and tokens from the seed
        and the step, compiled or loaded from the cache; then the mix's
        set-up."""
        import jax

        # Before the state exists: a program that builds a second state (a
        # restore's zeroed target) would otherwise set the process's peak at
        # twice the state, which no operation does.
        for module in self._ops.values():
            if hasattr(module, "warm"):
                module.warm(self)
        self.state = self.load.init_state(self.seed)
        pool = self.load.token_pool(self.seed, TOKEN_POOL)
        self.tokens = [pool[i] for i in range(TOKEN_POOL)]
        jax.block_until_ready((self.state, self.tokens))
        jax.block_until_ready(self.fingerprint(self.state))
        self.train(int(self.mix.get("warm_steps", 2)))
        with self.watch:
            for op in self.mix.get("setup", []):
                self.run_op(op)

    # ------------------------------------------------------------- the window

    def run_window(self, seconds: float) -> Account:
        acc = self.account
        with self.watch:
            acc.open_t = time.monotonic()
            while True:
                for op in self.mix["cycle"]:
                    self.run_op(op)
                if time.monotonic() - acc.open_t >= seconds:
                    break
            acc.close_t = time.monotonic()
        return acc

    # ------------------------------------------------- what operations call

    def run_op(self, op: Dict[str, Any]) -> None:
        self._ops[op["op"]].run(self, **{k: v for k, v in op.items() if k != "op"})

    @contextlib.contextmanager
    def operation(self, name: str, **fields: Any) -> Iterator[Dict[str, Any]]:
        """One counted operation.  The record (``op``, ``in_window``, ``ok``
        and the operation's own fields) is in the account from the start; it
        is ``ok`` only if the body ends without raising and the library
        neither fell back nor warned meanwhile.  The run goes on and says so."""
        acc = self.account
        rec = {"op": name, "in_window": acc.in_window(), "ok": False, **fields}
        acc.operations.append(rec)
        self.watch.drain()
        try:
            yield rec
            rec["ok"] = True
        except Exception as e:
            acc.failures.append(f"{name}: {type(e).__name__}: {e}")
        found = self.watch.drain()
        if found:
            rec["ok"] = False
            acc.failures.extend(f"{name}: {f}" for f in found)

    def batch(self, index: int) -> Any:
        """The token batch of the step taken from step ``index``."""
        return self.tokens[index % TOKEN_POOL]

    def train(self, steps: int) -> None:
        """``steps`` train steps, each closed by ``block_until_ready``; the
        loss of each is kept as the live job's."""
        for _ in range(steps):
            begin = time.monotonic()
            index = self.step_index
            self.state, loss = self.step_fn(self.state, self.batch(index))
            loss.block_until_ready()
            self.live_loss[index] = loss
            self.step_index = index + 1
            self.account.span("train_step", begin, time.monotonic())

    # ------------------------------------------------------ after the window

    def free_state(self) -> None:
        self.state = None

    def compared(self) -> List[Dict[str, Any]]:
        """The comparison's inputs as host values."""
        out = []
        for c in self.checked:
            want_fp = c["want_fingerprint"]
            out.append(
                {
                    **c,
                    "fingerprint": np.asarray(c["fingerprint"]),
                    "want_fingerprint": (
                        np.zeros((0, 2), np.uint32) if want_fp is None else np.asarray(want_fp)
                    ),
                    "loss": float(c["loss"]),
                    "want_loss": None if c["want_loss"] is None else float(c["want_loss"]),
                    "step": int(c["step"]),
                }
            )
        return out
