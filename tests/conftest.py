"""Test harness config: force an 8-device virtual CPU mesh before jax import.

Mirrors the reference's multi-device-without-a-cluster strategy
(/root/reference/torchsnapshot/test_utils.py:210-243 uses torchelastic local
procs); for single-process mesh tests the JAX trick is
``--xla_force_host_platform_device_count`` (SURVEY.md §4).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the 8-device CPU mesh

import pytest  # noqa: E402

from torchsnapshot_tpu import knobs  # noqa: E402


@pytest.fixture(params=[True, False], ids=["batching_on", "batching_off"])
def toggle_batching(request):
    """Run snapshot round-trips with batching on and off (reference
    tests/conftest.py:17-20)."""
    with knobs.override_batching_disabled(not request.param):
        yield request.param


@pytest.fixture(params=[True, False], ids=["chunking_on", "chunking_off"])
def toggle_chunking(request):
    """Force tiny chunks so chunked paths are exercised (reference
    tests/test_ddp.py:37-46)."""
    if request.param:
        with knobs.override_max_chunk_size_bytes(1024):
            yield True
    else:
        yield False
