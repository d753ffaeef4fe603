"""``sdar-30b-a3b-chat``: each published number against the catalog row's
``config``, the cut as the file states it, the leaf count, the parameter count
and the state's bytes and share as the builder gives them, the 1,728 leaves
that are single expert matrices of 3 MiB, and the train step compiled for a
described TPU v5e chip at the real sizes."""

import json
import os

import jax
import pytest

from chipbench.models.sdar_moe import build
from conftest import ROOT
from torchsnapshot_tpu import knobs, native_io
from torchsnapshot_tpu.io_preparers.array import _INTO_PLACE_MIN_BYTES

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
BYTES_LIMIT = 16_909_336_064  # one TPU v5 lite, as JAX reported it on the chip
NAME = "sdar-30b-a3b-chat"
CELL = "sdar30b.kill-resume"

# the row's ``config`` (architectures.jsonl beside the model-configs guide)
PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128, hidden_act="silu", hidden_size=2048,
    intermediate_size=6144, max_position_embeddings=32768, max_window_layers=48, mlp_only_layers=[],
    model_type="sdar_moe", moe_intermediate_size=768, norm_topk_prob=True, num_attention_heads=32,
    num_experts=128, num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4, rms_norm_eps=1e-06,
    rope_scaling=None, rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
    use_sliding_window=False, vocab_size=151936,
)
CUT = dict(num_hidden_layers=12, num_experts=16, vocab_size=18992)
EXPERT_BYTES = 2048 * 768 * 2


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
    assert entry["reduced"] == cfg["reduced"] == list(CUT)
    assert cfg["source"] == entry["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    # the floors: four layers and more (a period is one layer), 8 experts and more, an eighth of the rows
    assert cfg["num_hidden_layers"] * 4 == 48 and cfg["num_experts"] * 8 == 128
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]


def test_the_file_states_what_it_assumes(entry_cfg):
    _, cfg = entry_cfg
    assumed = cfg["assumed"]
    assert assumed["batch_sequences"] == 1 and assumed["sequence_length"] == 4096
    assert assumed["block_length"] == 4 and assumed["noise_eps"] == 1e-3
    assert assumed["router_aux_loss_coef"] == 1e-3 and assumed["first_expert"] == 0
    for key in ("deployment", "block_length_why", "noise_schedule", "objective", "no_shift", "mask_id",
                "noise_key", "qk_norm", "router", "router_aux_loss", "expert_leaves", "expert_compute",
                "state_dtypes", "step_blocks", "init", "logits"):
        assert len(assumed[key]) > 40, key
    assert "8 chips" in assumed["deployment"] and "pipeline stages" in assumed["deployment"]
    assert "arXiv:2503.09573" in assumed["objective"] and "RNGState" in assumed["noise_key"]
    assert cfg["state_dtypes"] == {"params": "bfloat16", "adam_mu": "bfloat16", "adam_nu": "bfloat16",
                                   "step": "int32", "adam_count": "int32", "noise_key": "uint32"}
    dots3 = json.load(open(os.path.join(ROOT, "chipbench", "configs", "dots3-note-prev.json")))
    assert cfg["guarantees"] == dots3["guarantees"]
    assert cfg["builder"] == "chipbench.models.sdar_moe:build"


def test_the_cell_is_the_issues(entry_cfg):
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["config"] == NAME and cell["traffic"] == "kill-resume" and cell["chips"] == 1
    assert len(cell["why"]) <= 200 and len(entry_cfg[0]["why"]) <= 200
    resume = next(m for m in BENCH["end_to_end"] if m["name"] == "resume_s")["workloads"]
    assert resume.index(CELL) == 6
    listed = [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert not {"chunked_bytes_pct.resume", "chunk_assemble_s"} & set(listed) and len(listed) == 25
    assert listed[-1] == "rng_restore_s"


def test_leaves_parameters_and_bytes(load):
    abstract = load.abstract_state()
    params = jax.tree.leaves(abstract["params"])
    assert len(params) == 687 and len(jax.tree.leaves(abstract)) == 3 * 687 + 3 == 2064
    layers = abstract["params"]["layers"]
    count = lambda tree: sum(int(p.size) for p in jax.tree.leaves(tree))  # noqa: E731
    assert count(layers[0]["self_attn"]) == 18_874_624
    assert [count(layer) for layer in layers] == [94_638_336] * 12
    assert count(abstract["params"]) == 1_213_453_312
    assert {str(p.dtype) for p in params} == {"bfloat16"}
    assert load.state_bytes() == 1_213_453_312 * 6 + 8 + 8 == 7_280_719_888
    assert load.state_bytes() / BYTES_LIMIT == pytest.approx(0.4306, abs=1e-4)
    assert abstract["noise_key"].shape == (2,) and abstract["noise_key"].dtype == "uint32"
    a = layers[0]["self_attn"]
    assert a["q_proj"].shape == (2048, 4096) and a["o_proj"].shape == (4096, 2048)
    assert a["k_proj"].shape == a["v_proj"].shape == (2048, 512) and a["q_norm"].shape == (128,)
    assert layers[0]["mlp"]["gate"].shape == (2048, 128)
    assert abstract["params"]["lm_head"].shape == abstract["params"]["embed_tokens"].shape == (18992, 2048)


def test_1728_leaves_are_single_expert_matrices_of_3_mib(load):
    abstract = load.abstract_state()
    experts = [layer["mlp"]["experts"] for layer in abstract["params"]["layers"]]
    assert [len(e) for e in experts] == [16] * 12
    sizes = [int(p.size) * p.dtype.itemsize for p in jax.tree.leaves(abstract)]
    assert sizes.count(EXPERT_BYTES) == 3 * 12 * 16 * 3 == 1728 and EXPERT_BYTES == 3_145_728 == 3 << 20
    assert 1728 * EXPERT_BYTES / load.state_bytes() == pytest.approx(0.7466, abs=1e-4)
    # the route the library's plan gives each leaf by its size
    assert _INTO_PLACE_MIN_BYTES == 1 << 20 and native_io.STRIPED_MIN_BYTES == 32 << 20
    sequential = sum(s for s in sizes if _INTO_PLACE_MIN_BYTES <= s < native_io.STRIPED_MIN_BYTES)
    striped = sum(s for s in sizes if s >= native_io.STRIPED_MIN_BYTES)
    assert striped == 6 * 18992 * 2048 * 2  # the embedding and head slices, 74 MiB each, in three statefuls
    assert sequential / load.state_bytes() == pytest.approx(0.9333, abs=1e-4)
    threshold = knobs.get_slab_size_threshold_bytes()
    assert threshold == 128 << 20 and max(sizes) < threshold  # every leaf is a slab member


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
    """12.44 GB live (7.28 of state, 5.16 of gradients and activations) and
    0.11 GB of program when it was written."""
    m = load.lower_step(one_chip).compile().memory_analysis()
    live = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.alias_size_in_bytes >= 0.99 * load.state_bytes()  # the state is donated
    assert load.state_bytes() < live + m.generated_code_size_in_bytes < 0.8 * BYTES_LIMIT, (
        live, m.generated_code_size_in_bytes)
    assert load.state_bytes() * 1.4 + m.generated_code_size_in_bytes < 0.9 * BYTES_LIMIT  # a restore beside it
