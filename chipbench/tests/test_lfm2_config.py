"""``lfm2-8b-a1b``: each published size against the catalog row's ``config``,
the cut against ISSUE 33's table, the leaf count, the parameter count and the
state's bytes and share as the builder gives them, the 1,152 leaves that are
single expert matrices of 7 MiB and the route the library's read plan gives
them, and the train step compiled for a described TPU v5e chip at the real
sizes."""

import json
import os

import jax
import pytest

from chipbench.models.lfm2_moe import build
from conftest import ROOT
from torchsnapshot_tpu import knobs, native_io
from torchsnapshot_tpu.io_preparers.array import _INTO_PLACE_MIN_BYTES

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
BYTES_LIMIT = 16_909_336_064  # one TPU v5 lite, as JAX reported it (PR 21)
NAME = "lfm2-8b-a1b"
CELL = "lfm2-8b-a1b.kill-resume"

PATTERN = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
           "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
           "conv", "conv", "full_attention", "conv", "conv"]
# the row's ``config`` (architectures.jsonl beside the model-configs guide)
PUBLISHED = dict(
    conv_L_cache=3, conv_bias=False, hidden_size=2048, intermediate_size=7168, layer_types=PATTERN,
    max_position_embeddings=128000, model_type="lfm2_moe", moe_intermediate_size=1792, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2, num_experts=32, num_experts_per_tok=4,
    num_hidden_layers=24, num_key_value_heads=8, rope_theta=1000000, routed_scaling_factor=1,
    use_expert_bias=True, vocab_size=65536,
)
CUT = dict(num_hidden_layers=5, num_dense_layers=1,
           layer_types=["conv", "full_attention", "conv", "conv", "conv"], vocab_size=16384)
EXPERT_BYTES = 2048 * 1792 * 2


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
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "num_dense_layers", "layer_types", "vocab_size"]
    # no width among the cuts: the experts, the router's 4 a token and every head are whole
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size", key
    assert "num_experts" not in cfg["reduced"] and "num_experts_per_tok" not in cfg["reduced"]
    assert cfg["source"] == entry["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    # the kept layers are published layers 0, 2, 3, 4, 5: one leading dense layer and one whole period, 1 : 3
    assert cfg["layer_types"] == [PATTERN[i] for i in (0, 2, 3, 4, 5)]
    assert PATTERN.count("full_attention") * 3 == PATTERN.count("conv") == 18
    # the floors of the model-configs guide: four layers after the dense ones, 8 experts and more, an eighth of the rows
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]


def test_the_file_states_what_it_assumes(entry_cfg):
    _, cfg = entry_cfg
    assumed = cfg["assumed"]
    assert assumed["batch_sequences"] == 8 and assumed["sequence_length"] == 1024
    assert assumed["head_dim"] == 64 == cfg["hidden_size"] // cfg["num_attention_heads"]
    assert assumed["router_weight_sum_eps"] == 1e-6
    for key in ("deployment", "head_dim_why", "tied_head", "expert_leaves", "state_dtypes", "optimizer_and_bias",
                "conv_operator", "attention_operator", "router", "expert_compute", "norm_placement", "init", "logits"):
        assert len(assumed[key]) > 40, key
    assert "whole on this one chip" in assumed["deployment"] and "pipeline" in assumed["deployment"]
    assert "ONE LEAF AN EXPERT MATRIX" in assumed["expert_leaves"] and "NOT three stacked banks" in assumed["expert_leaves"]
    assert "pipeline stages" in cfg["reduced_why"]
    assert cfg["state_dtypes"] == {"params": "bfloat16", "adam_mu": "bfloat16", "adam_nu": "bfloat16",
                                   "expert_bias": "float32", "step": "int32", "adam_count": "int32"}
    dots3 = json.load(open(os.path.join(ROOT, "chipbench", "configs", "dots3-note-prev.json")))
    assert cfg["guarantees"] == dots3["guarantees"]
    assert cfg["builder"] == "chipbench.models.lfm2_moe:build"


def test_the_cell_is_the_issues(entry_cfg):
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["config"] == NAME and cell["traffic"] == "kill-resume" and cell["chips"] == 1
    assert len(cell["why"]) <= 200 and len(entry_cfg[0]["why"]) <= 200
    assert BENCH["workloads"][-1] == cell and BENCH["configs"][-1] == entry_cfg[0]
    assert CELL in next(m for m in BENCH["end_to_end"] if m["name"] == "resume_s")["workloads"]
    listed = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert not {"chunked_bytes_pct.resume", "chunk_assemble_s"} & set(listed)
    new = ["sequential_read_pct.resume", "fs_read_gbps"]
    assert new[0] in listed and new[1] in listed and [m["name"] for m in BENCH["per_layer"]][-2:] == new
    for name, layer, source in zip(new, ("Planning", "Storage"), ("program_counter", "program_span")):
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [w["name"] for w in BENCH["workloads"]] and m["moves"] == "resume_s"
        assert m["layer"] == layer and m["source"] == source and m["better"] == "higher"
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", name + ".py"))


def test_leaves_parameters_and_bytes(load):
    abstract = load.abstract_state()
    params = jax.tree.leaves(abstract["params"])
    assert len(params) == 425 and len(jax.tree.leaves(abstract)) == 3 * 425 + 2 == 1277
    layers = abstract["params"]["layers"]
    count = lambda tree: sum(int(p.size) for p in jax.tree.leaves(tree))  # noqa: E731
    assert count(layers[0]) == 60_827_648
    assert count(layers[1]) == 362_877_088
    assert [count(layer) for layer in layers[2:]] == [369_174_560] * 3
    assert count(abstract["params"]["embed_tokens"]) + count(abstract["params"]["embedding_norm"]) == 33_556_480
    assert count(abstract["params"]) == 1_564_784_896
    # 6 B a parameter, but the four expert biases of 32 and their moments, which are float32
    assert load.state_bytes() == (1_564_784_896 - 128) * 6 + 128 * 12 + 8 == 9_388_710_152
    assert load.state_bytes() / BYTES_LIMIT == pytest.approx(0.5552, abs=1e-4)
    assert load.state_bytes() / BYTES_LIMIT > 0.25
    a = layers[1]["self_attn"]
    assert a["q_proj"].shape == a["out_proj"].shape == (2048, 2048)
    assert a["k_proj"].shape == a["v_proj"].shape == (2048, 512)
    assert a["q_layernorm"].shape == a["k_layernorm"].shape == (64,)
    assert layers[0]["conv"]["in_proj"].shape == (2048, 6144) and layers[0]["conv"]["conv"].shape == (2048, 3)
    assert layers[0]["feed_forward"]["w2"].shape == (7168, 2048)
    assert {str(p.dtype) for p in params} == {"bfloat16", "float32"}
    assert layers[1]["feed_forward"]["expert_bias"].dtype == "float32"
    assert "output" not in abstract["params"]  # the head is the embedding


def test_1152_leaves_are_single_expert_matrices_of_7_mib(load):
    abstract = load.abstract_state()
    experts = [layer["feed_forward"]["experts"] for layer in abstract["params"]["layers"][1:]]
    assert [len(e) for e in experts] == [32] * 4
    per_stateful = jax.tree.leaves(experts)
    assert len(per_stateful) == 4 * 32 * 3 == 384
    assert {p.shape for p in per_stateful} == {(2048, 1792), (1792, 2048)}
    sizes = [int(p.size) * p.dtype.itemsize for p in jax.tree.leaves(abstract)]
    assert sizes.count(EXPERT_BYTES) == 3 * 384 == 1152 and EXPERT_BYTES == 7_340_032 == 7 << 20
    assert 1152 * EXPERT_BYTES == 8_455_716_864
    assert 1152 * EXPERT_BYTES / load.state_bytes() == pytest.approx(0.9006, abs=1e-4)
    # the route the library's plan gives each leaf by its size: every leaf but the
    # embedding (64 MiB: striped) and the norms, biases and routers (under a megabyte:
    # merged) lands in place under the striped minimum, so by the sequential fs_read
    assert _INTO_PLACE_MIN_BYTES == 1 << 20 and native_io.STRIPED_MIN_BYTES == 32 << 20
    sequential = sum(s for s in sizes if _INTO_PLACE_MIN_BYTES <= s < native_io.STRIPED_MIN_BYTES)
    striped = sum(s for s in sizes if s >= native_io.STRIPED_MIN_BYTES)
    assert striped == 3 * 16384 * 2048 * 2  # the three embeddings, 64 MiB each
    assert sequential / load.state_bytes() == pytest.approx(0.9785, abs=1e-3)
    assert (load.state_bytes() - sequential - striped) / load.state_bytes() < 3e-4  # 1.86 MB merged
    threshold = knobs.get_slab_size_threshold_bytes()
    assert threshold == 128 << 20 and max(sizes) < threshold  # every leaf is a slab member
    assert threshold // EXPERT_BYTES == 18  # experts a slab file, at the most


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
    # the program itself lives in HBM too, and this one is large (1,277 leaves, 128 unrolled expert modules)
    assert load.state_bytes() < live + m.generated_code_size_in_bytes < 0.85 * BYTES_LIMIT, (
        live, m.generated_code_size_in_bytes)
    assert load.state_bytes() * 1.4 + m.generated_code_size_in_bytes < 0.9 * BYTES_LIMIT  # a restore beside it
