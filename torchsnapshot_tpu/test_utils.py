"""Shipped test utilities: array-aware equality + multi-process launch.

TPU-native analogue of the reference's ``torchsnapshot/test_utils.py``
(/root/reference/torchsnapshot/test_utils.py:52-276).  ``tensor_eq`` compares
numpy and jax arrays (sharded jax arrays are compared by materialized global
value — the analogue of the reference's redistribute-to-Replicate for
DTensor, :52-77); ``run_with_procs`` re-executes a test function in N local
processes coordinated through a FileStore (the torchelastic pet-launch
analogue, :210-243).
"""

from __future__ import annotations

import functools
import multiprocessing as mp
import os
import tempfile
import traceback
from typing import Any, Callable, Dict

import numpy as np

from . import knobs


def tensor_eq(a: Any, b: Any) -> bool:
    from . import staging

    a_is_arr = staging.is_array_like(a)
    b_is_arr = staging.is_array_like(b)
    if a_is_arr != b_is_arr:
        return False
    if not a_is_arr:
        return bool(a == b)
    a_np = np.asarray(a)
    b_np = np.asarray(b)
    if a_np.shape != b_np.shape or a_np.dtype != b_np.dtype:
        return False
    return bool(np.array_equal(a_np, b_np))


def _state_dict_eq(a: Any, b: Any, path: str = "") -> tuple:
    from . import staging

    if isinstance(a, dict) and isinstance(b, dict):
        if set(a.keys()) != set(b.keys()):
            return False, f"{path}: keys differ {set(a)} vs {set(b)}"
        for k in a:
            ok, why = _state_dict_eq(a[k], b[k], f"{path}/{k}")
            if not ok:
                return ok, why
        return True, ""
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        if type(a) is not type(b) or len(a) != len(b):
            return False, f"{path}: sequence type/length differs"
        for i, (x, y) in enumerate(zip(a, b)):
            ok, why = _state_dict_eq(x, y, f"{path}[{i}]")
            if not ok:
                return ok, why
        return True, ""
    if staging.is_array_like(a) or staging.is_array_like(b):
        if not tensor_eq(a, b):
            return False, f"{path}: arrays differ"
        return True, ""
    if a != b:
        return False, f"{path}: {a!r} != {b!r}"
    return True, ""


def assert_state_dict_eq(a: Dict[str, Any], b: Dict[str, Any]) -> None:
    """(reference assert_state_dict_eq, test_utils.py:97-111)"""
    ok, why = _state_dict_eq(a, b)
    assert ok, why


def check_state_dict_eq(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    """(reference check_state_dict_eq, test_utils.py:114-126)"""
    ok, _ = _state_dict_eq(a, b)
    return ok


def rand_state_dict(seed: int, shapes: Dict[str, tuple]) -> Dict[str, np.ndarray]:
    rng = np.random.RandomState(seed)
    return {k: rng.rand(*shape).astype(np.float32) for k, shape in shapes.items()}


def _proc_entry(
    fn: Callable, rank: int, world_size: int, store_path: str, conn: Any
) -> None:
    # An ambient production TPUSNAP_STORE_ADDR (exported on a dev box or CI
    # host for a real job) must not silently reroute every test's
    # coordination to an external — possibly dead — server; tests that WANT
    # the TCP store opt in with TPUSNAP_TEST_KEEP_STORE_ADDR.
    # The writes below are launcher-side EXPORTS for this forked child (the
    # bootstrap contract dist_store/make_test_pg read back through knobs),
    # not configuration reads — the one pattern knob discipline permits
    # outside knobs.py, under an explicit suppression.
    if not os.environ.get("TPUSNAP_TEST_KEEP_STORE_ADDR"):
        os.environ.pop(knobs.STORE_ADDR_ENV_VAR, None)  # tpusnap-lint: disable=knob-discipline
    os.environ[knobs.STORE_PATH_ENV_VAR] = store_path  # tpusnap-lint: disable=knob-discipline
    os.environ[knobs.RANK_ENV_VAR] = str(rank)  # tpusnap-lint: disable=knob-discipline
    os.environ[knobs.WORLD_SIZE_ENV_VAR] = str(world_size)  # tpusnap-lint: disable=knob-discipline
    # Subprocesses run on the CPU backend (tests): single device per proc.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        fn()
        conn.send(None)
    except BaseException:  # noqa: BLE001
        conn.send(traceback.format_exc())


def make_test_pg():
    """PGWrapper for the current test subprocess, from env set by
    run_with_procs — through the PRODUCTION store resolution
    (get_or_create_store), so a test that pre-sets ``TPUSNAP_STORE_ADDR``
    runs the whole snapshot protocol over the C++ TCP store instead of the
    FileStore run_with_procs provides by default."""
    from .dist_store import get_or_create_store
    from .pg_wrapper import PGWrapper

    rank = knobs.get_env_rank()
    world_size = knobs.get_env_world_size()
    assert rank is not None and world_size is not None, (
        "make_test_pg() requires the run_with_procs bootstrap env"
    )
    store = get_or_create_store(rank, world_size)
    return PGWrapper(store=store, rank=rank, world_size=world_size)


def run_with_procs(nproc: int) -> Callable:
    """Decorator: re-execute the test body in ``nproc`` local processes
    (reference run_with_pet, test_utils.py:232-255).  The body calls
    ``make_test_pg()`` for its process group.  Uses fork start method (fast,
    and jax CPU backend tolerates it before first backend use in children).

    CPU-only by contract: the children are forked after ``import jax`` and
    must stay numpy-only.  A chip belongs to one process, so a parent that
    holds one cannot hand it to a forked child; never call this from a
    process that has touched an accelerator backend."""

    def decorator(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any) -> None:
            ctx = mp.get_context("fork")
            with tempfile.TemporaryDirectory() as store_path:
                procs = []
                conns = []
                for rank in range(nproc):
                    parent_conn, child_conn = ctx.Pipe()
                    p = ctx.Process(
                        target=_proc_entry,
                        args=(fn, rank, nproc, store_path, child_conn),
                    )
                    p.start()
                    procs.append(p)
                    conns.append(parent_conn)
                errors = []
                for rank, (p, conn) in enumerate(zip(procs, conns)):
                    p.join(timeout=120)
                    if p.is_alive():
                        p.terminate()
                        errors.append(f"rank {rank}: timed out")
                    elif conn.poll():
                        err = conn.recv()
                        if err is not None:
                            errors.append(f"rank {rank}:\n{err}")
                    elif p.exitcode != 0:
                        errors.append(f"rank {rank}: exit code {p.exitcode}")
                if errors:
                    raise AssertionError("\n".join(errors))

        return wrapper

    return decorator
