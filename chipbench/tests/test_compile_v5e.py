"""Both train steps compiled for a described TPU v5e chip at the real sizes:
what the chip's compiler would refuse, it refuses here, at no chip time.
The topology is described inside a fixture (only one process may load the
TPU's library), and the persistent compile cache is off around it."""

import json
import os

import jax
import pytest

from chipbench.models.dense_decoder import build
from conftest import ROOT

BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", ["mistral-7b-v0.3", "codestral-22b-v0.1"])
def test_train_step_fits_one_chip(one_chip, name):
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", name + ".json")))
    load = build(cfg, jax.devices())
    m = load.lower_step(one_chip).compile().memory_analysis()
    live = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    # the state is donated: it is counted once
    assert m.alias_size_in_bytes >= 0.99 * load.state_bytes()
    assert load.state_bytes() < live < 0.85 * BYTES_LIMIT, live
    # a restore holds the target and the landed copy of the largest stateful
    assert load.state_bytes() * 4 / 3 < 0.85 * BYTES_LIMIT
