"""``brumby-14b-base``: each published size against the catalog row's
``config``, the cut against ISSUE 31's table, the leaf count, the parameter
count and the state's bytes and share as the builder gives them, the nine
leaves the library chunks and their rows, and the train step compiled for a
described TPU v5e chip at the real sizes."""

import json
import os

import jax
import numpy as np
import pytest

from chipbench.models.brumby import build
from conftest import ROOT
from torchsnapshot_tpu import knobs
from torchsnapshot_tpu.io_preparers.chunked_array import ChunkedArrayIOPreparer

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
BYTES_LIMIT = 16_909_336_064  # one TPU v5 lite, as JAX reported it (PR 21)
NAME = "brumby-14b-base"
CELL = "brumby14b.kill-resume"

# the row's ``config`` (architectures.jsonl beside the model-configs guide)
PUBLISHED = dict(
    attention_bias=False, head_dim=128, hidden_act="silu", hidden_size=5120, intermediate_size=17408,
    max_position_embeddings=32768, max_window_layers=40, model_type="brumby", num_attention_heads=40,
    num_hidden_layers=40, num_key_value_heads=8, rms_norm_eps=1e-06, rope_scaling=None,
    rope_theta=1000000, sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=151936,
)
CUT = dict(num_hidden_layers=4, num_attention_heads=5, num_key_value_heads=1, vocab_size=18992)
LEAF_BYTES = 4 * 5120 * 17408 * 2  # one stacked feed-forward leaf


@pytest.fixture(scope="module")
def entry_cfg():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    return entry, json.load(open(os.path.join(ROOT, entry["file"])))


@pytest.fixture(scope="module")
def load(entry_cfg):
    return build(entry_cfg[1], jax.devices())


def test_every_key_is_as_published_but_the_cut(entry_cfg):
    entry, cfg = entry_cfg
    for key, want in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, want), key
        if key in CUT:
            assert cfg["published"][key] == want, key
    assert set(cfg["published"]) == set(CUT)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == sorted(CUT)
    assert entry["reduced"] == ["num_hidden_layers", "num_attention_heads", "num_key_value_heads", "vocab_size"]
    # no width among the cuts
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size", key
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json")
    # the floors of the model-configs guide: four layers (a period is one), an
    # eighth of the vocabulary; heads 8-way in whole key-value groups
    assert cfg["num_hidden_layers"] >= 4 and cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_attention_heads"] * 8 == cfg["published"]["num_attention_heads"]
    assert cfg["num_key_value_heads"] * 8 == cfg["published"]["num_key_value_heads"]


def test_the_file_states_what_it_assumes(entry_cfg):
    _, cfg = entry_cfg
    assumed = cfg["assumed"]
    assert assumed["batch_sequences"] == 8 and assumed["sequence_length"] == 1024
    assert assumed["retention_power"] == 2 and assumed["retention_eps"] == 1e-6
    for key in ("deployment", "state_dtypes", "retention_power_why", "retention_gate",
                "retention_normalisation", "retention_scale", "qk_norm_and_rope", "norm_placement",
                "init", "logits"):
        assert len(assumed[key]) > 40, key
    assert "8 chips" in assumed["deployment"] and "8-way" in assumed["deployment"]
    assert "feed-forward whole" in assumed["deployment"]
    assert cfg["state_dtypes"] == {"params": "bfloat16", "adam_mu": "bfloat16", "adam_nu": "bfloat16",
                                   "step": "int32", "adam_count": "int32"}
    dots3 = json.load(open(os.path.join(ROOT, "chipbench", "configs", "dots3-note-prev.json")))
    assert cfg["guarantees"] == dots3["guarantees"]
    assert cfg["builder"] == "chipbench.models.brumby:build"


def test_the_cell_is_the_issues(entry_cfg):
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["config"] == NAME and cell["traffic"] == "kill-resume" and cell["chips"] == 1
    assert len(cell["why"]) <= 200
    assert CELL in next(m for m in BENCH["end_to_end"] if m["name"] == "resume_s")["workloads"]
    listed = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert len(listed) == 17 and listed[-2:] == ["chunked_bytes_pct.resume", "chunk_assemble_s"]
    for name in listed[-2:]:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "resume_s"


def test_leaves_parameters_and_bytes(load):
    abstract = load.abstract_state()
    params = jax.tree.leaves(abstract["params"])
    assert len(params) == 15 and len(jax.tree.leaves(abstract)) == 3 * 15 + 2 == 47
    a = abstract["params"]["layers"]["attn"]
    assert a["wq"].shape == (4, 5120, 640) and a["wo"].shape == (4, 640, 5120)
    assert a["wk"].shape == a["wv"].shape == (4, 5120, 128)
    assert a["wg"].shape == (4, 5120, 1) and a["q_norm"].shape == a["k_norm"].shape == (4, 128)
    layer = 2 * 3_276_800 + 2 * 655_360 + 5_120 + 256 + 267_386_880 + 10_240
    assert layer == 275_266_816
    assert sum(int(p.size) for p in jax.tree.leaves(abstract["params"]["layers"])) == 4 * layer == 1_101_067_264
    assert sum(int(p.size) for p in params) == 4 * layer + 2 * 97_239_040 + 5_120 == 1_295_550_464
    assert load.state_bytes() == 1_295_550_464 * 6 + 8 == 7_773_302_792
    assert load.state_bytes() / BYTES_LIMIT == pytest.approx(0.4597, abs=1e-4)
    assert load.state_bytes() / BYTES_LIMIT > 0.25
    sizes = sorted(int(p.size) * p.dtype.itemsize for p in params)
    assert sizes[0] == 1024 and sizes[-1] == LEAF_BYTES == 713_031_680
    assert {str(p.dtype) for p in params} == {"bfloat16"}
    # what lies under the slab threshold: the attention block's and the norms' leaves
    threshold = knobs.get_slab_size_threshold_bytes()
    assert threshold == 128 << 20
    under = sum(s for s in sizes if s < threshold)
    assert under / (load.state_bytes() / 3) == pytest.approx(0.0243, abs=5e-4)
    assert sizes.count(4 * 5120 * 640 * 2) == 2 and sizes.count(4 * 5120 * 128 * 2) == 2  # 26.2 MB, 5.2 MB


def test_nine_leaves_are_chunked_into_three_rows_and_one(load):
    knob = knobs.get_max_chunk_size_bytes()
    assert knob == 512 << 20
    leaves = jax.tree.leaves(load.abstract_state())
    chunked = [p for p in leaves if int(p.size) * p.dtype.itemsize > knob]
    assert len(chunked) == 9 and {p.shape for p in chunked} == {(4, 5120, 17408), (4, 17408, 5120)}
    assert all(int(p.size) * p.dtype.itemsize == LEAF_BYTES for p in chunked)
    assert 9 * LEAF_BYTES / load.state_bytes() == pytest.approx(0.8255, abs=1e-4)
    for p in chunked:
        chunks = ChunkedArrayIOPreparer.chunk_instructions(list(p.shape), np.dtype(p.dtype), knob)
        assert [c.sizes[0] for c in chunks] == [3, 1] and [c.offsets[0] for c in chunks] == [0, 3]
        assert [int(np.prod(c.sizes)) * 2 for c in chunks] == [534_773_760, 178_257_920]
    # larger than the H2D batcher's window too: the restore's host arena is this one leaf
    from torchsnapshot_tpu.io_preparers.array import H2DBatcher
    assert LEAF_BYTES > H2DBatcher().inflight_cap_bytes == 512 << 20


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


def test_train_step_fits_one_chip(one_chip, load):
    m = load.lower_step(one_chip).compile().memory_analysis()
    live = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes >= 0.99 * load.state_bytes()  # the state is donated
    assert load.state_bytes() < live < 0.85 * BYTES_LIMIT, live
    assert load.state_bytes() * 4 / 3 < 0.85 * BYTES_LIMIT
