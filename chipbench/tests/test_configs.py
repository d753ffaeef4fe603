"""Each published size in the two configuration files against the numbers
ISSUE 24 gives, and BENCHMARK.json against the rules of its contract that a
file can break."""

import json
import os
import re

import jax
import pytest

from chipbench.models.dense_decoder import build
from conftest import ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
BYTES_LIMIT = 16_909_336_064  # one TPU v5 lite, as JAX reported it (PR 21)

PUBLISHED = {
    "mistral-7b-v0.3": dict(hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
                            num_key_value_heads=8, vocab_size=32768, rope_theta=1e6,
                            rms_norm_eps=1e-5, head_dim=128, layers=32, dtype="float32",
                            params=486_551_552, state=5_838_618_632, share=0.345),
    "codestral-22b-v0.1": dict(hidden_size=6144, intermediate_size=16384, num_attention_heads=48,
                               num_key_value_heads=8, vocab_size=32768, rope_theta=1e6,
                               rms_norm_eps=1e-5, head_dim=128, layers=56, dtype="bfloat16",
                               params=792_741_888, state=4_756_451_336, share=0.281),
}


def config(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    return entry, json.load(open(os.path.join(ROOT, entry["file"])))


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_sizes_and_state_bytes(name):
    want = PUBLISHED[name]
    entry, cfg = config(name)
    for key in ("hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
                "vocab_size", "rope_theta", "rms_norm_eps", "head_dim"):
        assert cfg[key] == want[key], key
    assert cfg["published"]["num_hidden_layers"] == want["layers"]
    # the depth is the one cut; the published dtype stays, the state's is stated apart
    assert cfg["num_hidden_layers"] == 1 and cfg["torch_dtype"] == "bfloat16"
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["state_dtypes"]["params"] == want["dtype"] and "state_dtypes" in cfg["assumed"]
    assert cfg["source"] == entry["source"] and entry["source"].startswith("https://huggingface.co/")
    assert cfg["assumed"]["batch_sequences"] == 8 and cfg["assumed"]["sequence_length"] == 1024
    load = build(cfg, jax.devices())
    leaves = jax.tree.leaves(load.abstract_state())
    assert len(leaves) == 38
    params = sum(int(l.size) for l in jax.tree.leaves(load.abstract_state()["params"]))
    assert params == want["params"]
    assert load.state_bytes() == want["state"]
    assert load.state_bytes() / BYTES_LIMIT == pytest.approx(want["share"], abs=1e-3)
    assert load.state_bytes() / BYTES_LIMIT > 0.25
    for leaf in leaves:
        if leaf.ndim:
            assert leaf.dtype == want["dtype"]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
                          "per_layer"}
    assert BENCH["paths"] == ["chipbench"] and 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert len(cells) == len(BENCH["workloads"])
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(cells)
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == configs
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size|head)", key)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher") and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for name, cell in cells.items():
        got = [m for m in BENCH["end_to_end"] if "workloads" not in m or name in m["workloads"]]
        assert len(got) >= 2
        assert [m for m in BENCH["per_layer"] if name in m.get("workloads", [name])]
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in moved.get("workloads", cells), (m["name"], cell)
    layers = {m["layer"] for m in BENCH["per_layer"]}
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, layer
