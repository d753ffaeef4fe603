"""``dots3-note-prev``: each published size against the catalog row's
``config``, the cut against ISSUE 27's words, the leaf count, the parameter
count and the state's bytes and share as the builder gives them, and the
train step compiled for a described TPU v5e chip at the real sizes."""

import json
import os

import jax
import pytest

from chipbench.models.dots3_note import build, layer_kinds
from conftest import ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
BYTES_LIMIT = 16_909_336_064  # one TPU v5 lite, as JAX reported it (PR 21)
NAME = "dots3-note-prev"

# the row's ``config`` (architectures.jsonl beside the model-configs guide), but layer_types
PUBLISHED = dict(
    apply_mla_qkv_lora_rescale=True, attention_bias=False, attention_gate_type="headwise",
    first_k_dense_replace=1, hidden_act="silu", hidden_size=5120, index_head_dim=128,
    index_n_heads=64, index_topk=2048, intermediate_size=13824, kv_lora_rank=512,
    max_position_embeddings=524288, model_type="dots3_note", moe_intermediate_size=1536,
    moe_layer_freq=1, n_routed_experts=256, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=128, num_experts_per_tok=8, num_hidden_layers=46, num_key_value_heads=128,
    q_lora_rank=1024, qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-05,
    rope_scaling=None, rope_theta=80000000, routed_scaling_factor=1, scoring_func="sigmoid",
    sliding_window_size=513, swa_attention_gate_type="headwise", swa_kv_lora_rank=1024,
    swa_num_attention_heads=64, swa_num_key_value_heads=64, swa_q_lora_rank=1024,
    swa_qk_nope_head_dim=192, swa_qk_rope_head_dim=64, swa_rope_theta=50000, swa_v_head_dim=128,
    tie_word_embeddings=False, topk_method="noaux_tc", v_head_dim=128, vocab_size=152064,
)
CUT = dict(num_hidden_layers=5, n_routed_experts=8, num_attention_heads=16, num_key_value_heads=16,
           swa_num_attention_heads=8, swa_num_key_value_heads=8, vocab_size=19008)


@pytest.fixture(scope="module")
def entry_cfg():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    return entry, json.load(open(os.path.join(ROOT, entry["file"])))


def test_every_key_is_as_published_but_the_cut(entry_cfg):
    entry, cfg = entry_cfg
    for key, want in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, want), key
        if key in CUT:
            assert cfg["published"][key] == want, key
    assert set(cfg["published"]) == set(CUT)
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    # no width among the cuts
    for key in cfg["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size", key
    assert cfg["source"] == entry["source"] == (
        "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json")
    types = cfg["layer_types"]
    assert len(types) == 46 and types.count("full_attention") == 13
    assert types[:6] == ["full_attention", "full_attention", "sliding_attention",
                         "sliding_attention", "sliding_attention", "full_attention"]
    # the leading dense layer and one whole period
    assert layer_kinds(cfg) == [("full", "dense"), ("full", "moe"), ("sliding", "moe"),
                                ("sliding", "moe"), ("sliding", "moe")]
    # the floors of the model-configs guide
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4


def test_the_file_states_what_it_assumes(entry_cfg):
    _, cfg = entry_cfg
    assumed = cfg["assumed"]
    assert assumed["batch_sequences"] == 8 and assumed["sequence_length"] == 1024
    for key in ("deployment", "state_dtypes", "optimizer_and_bias", "mla_qkv_lora_rescale", "indexer",
                "indexer_training_term", "router", "expert_compute"):
        assert len(assumed[key]) > 40, key
    assert "32 chips" in assumed["deployment"] and "8-way" in assumed["deployment"]
    assert cfg["state_dtypes"] == {"params": "bfloat16", "adam_mu": "bfloat16", "adam_nu": "bfloat16",
                                   "router_bias": "float32", "step": "int32", "adam_count": "int32"}
    others = [json.load(open(os.path.join(ROOT, c["file"]))) for c in BENCH["configs"] if c["name"] != NAME]
    assert all(cfg["guarantees"] == other["guarantees"] for other in others)
    assert cfg["builder"] == "chipbench.models.dots3_note:build"


def test_leaves_parameters_and_bytes(entry_cfg):
    _, cfg = entry_cfg
    load = build(cfg, jax.devices())
    abstract = load.abstract_state()
    params = jax.tree.leaves(abstract["params"])
    # 95 leaves in the five layers (18 + 23 + 3 x 18), and embedding, final norm and head
    assert len(jax.tree.leaves(abstract["params"]["layers"])) == 95
    assert len(params) == 98 and len(jax.tree.leaves(abstract)) == 3 * 98 + 2
    assert sum(int(p.size) for p in params) == 1_390_831_104
    assert load.state_bytes() == 8_344_992_776
    assert 8.3e9 < load.state_bytes() < 8.4e9
    assert load.state_bytes() / BYTES_LIMIT == pytest.approx(0.4935, abs=1e-4)
    assert load.state_bytes() / BYTES_LIMIT > 0.25
    sizes = sorted(int(p.size) * p.dtype.itemsize for p in params)
    assert sizes[0] == 256 and sizes[-1] == 19008 * 5120 * 2  # the indexer's LayerNorm; embedding and head
    banks = [s for s in sizes if s == 8 * 5120 * 1536 * 2]
    assert len(banks) == 12 and banks[0] < 128 << 20  # 120 MiB: just under the slab threshold
    under = sum(s for s in sizes if s < 128 << 20) / sum(sizes)
    assert under == pytest.approx(0.707, abs=2e-3) and 12 * banks[0] / sum(sizes) == pytest.approx(0.543, abs=2e-3)
    float32 = [p for p in params if p.dtype == "float32"]
    assert len(float32) == 4 and all(p.shape == (256,) for p in float32)
    assert {str(p.dtype) for p in params} == {"bfloat16", "float32"}


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


def test_train_step_fits_one_chip(one_chip, entry_cfg):
    _, cfg = entry_cfg
    load = build(cfg, jax.devices())
    m = load.lower_step(one_chip).compile().memory_analysis()
    live = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes >= 0.99 * load.state_bytes()  # the state is donated
    assert load.state_bytes() < live < 0.85 * BYTES_LIMIT, live
    assert load.state_bytes() * 4 / 3 < 0.85 * BYTES_LIMIT
