"""The ``lfm2_moe`` load (``chipbench/models/lfm2_moe.py``) against its plain
reference (``lfm2_moe_reference.py``) at the toy widths of
``chipbench/configs/tiny-lfm2.json``, on the CPU, from seeds: loss and
gradients, the short convolution against a loop over positions, the sliced
vocabulary, the state (one leaf an expert matrix) through ``SnapshotManager``
against a per-leaf ``np.save`` oracle, and the library's ``read_route``
counter and ``fs_read`` annotation, which this state's cell reads.

Tolerance of the float32 comparisons: 1e-4 of the largest magnitude in the
leaf (or output).  Both sides then compute in float32 and differ in the
order of operations alone (grouped heads against a loop over heads, shifted
products against a loop over taps, the head's loss a sequence at a time,
rematerialised against not), which reads 1e-6 here; the same load computing
in bfloat16 reads 1e-2 and more and has to fail it."""

import copy
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models import lfm2_moe, lfm2_moe_reference as reference
from torchsnapshot_tpu import SnapshotManager, StateDict, phase_stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOLERANCE = 1e-4


def tiny(dtype="float32", **changes):
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", "tiny-lfm2.json")))
    cfg = copy.deepcopy(cfg)
    cfg["state_dtypes"].update(params=dtype, adam_mu=dtype, adam_nu=dtype)
    cfg["activation_dtype"] = dtype
    cfg.update(changes)
    return cfg


def worst_gap(got, want):
    """Per leaf: the largest difference over the largest magnitude wanted."""
    gaps = jax.tree.map(
        lambda a, b: float(
            jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / (jnp.max(jnp.abs(b)) + 1e-30)
        ),
        got, want,
    )
    return max(jax.tree.leaves(gaps))


def is_bias(path):
    return jax.tree_util.keystr(path).endswith("['expert_bias']")


@pytest.fixture(scope="module")
def float32_pair():
    """One seeded state and batch, the load's loss and gradients in float32
    and the reference's."""
    cfg = tiny()
    load = lfm2_moe.build(cfg, jax.devices())
    params = load.init_state(11)["params"]
    # biases off zero, as a few steps leave them, so that they matter to the choice
    for layer in params["layers"]:
        if "experts" in layer["feed_forward"]:
            bias = layer["feed_forward"]["expert_bias"]
            layer["feed_forward"]["expert_bias"] = bias + 0.01 * jnp.sin(jnp.arange(bias.size, dtype=bias.dtype))
    tokens = load.token_pool(11, 1)[0]
    loss, grads, loads = jax.jit(load.loss_and_grads)(params, tokens)
    (want_loss, want_loads), want_grads = jax.jit(
        jax.value_and_grad(lambda p: reference.loss(cfg, p, tokens), has_aux=True)
    )(params)
    return dict(cfg=cfg, load=load, params=params, tokens=tokens, loss=loss, grads=grads, loads=loads,
                want_loss=want_loss, want_grads=want_grads, want_loads=want_loads)


def test_the_toy_has_the_real_configurations_keys_and_both_kinds_of_layer():
    toy = tiny()
    real = json.load(open(os.path.join(ROOT, "chipbench", "configs", "lfm2-8b-a1b.json")))
    assert set(toy) == set(real) and set(toy["assumed"]) == set(real["assumed"])
    assert toy["builder"] == real["builder"] == "chipbench.models.lfm2_moe:build"
    assert lfm2_moe.layer_kinds(toy) == [("conv", "dense"), ("full_attention", "moe"), ("conv", "moe")]
    assert toy["num_experts"] == 8 and toy["num_experts_per_tok"] == 2


def test_loss_matches_the_reference(float32_pair):
    p = float32_pair
    assert abs(float(p["loss"]) - float(p["want_loss"])) <= TOLERANCE * abs(float(p["want_loss"]))
    assert 1.0 < float(p["want_loss"]) < 10.0  # the tied head's logits are of order one


def test_gradients_of_every_kind_of_leaf_match_the_reference(float32_pair):
    p = float32_pair
    assert worst_gap(p["grads"], p["want_grads"]) <= TOLERANCE
    kinds = {jax.tree_util.keystr(path[-1:]) for path, _ in jax.tree_util.tree_flatten_with_path(p["grads"])[0]}
    assert kinds >= {"['embed_tokens']", "['in_proj']", "['conv']", "['q_proj']", "['k_layernorm']",
                     "['gate']", "['w1']", "['w2']", "['w3']", "['operator_norm']", "['embedding_norm']"}


def test_every_leaf_but_an_expert_bias_gets_a_gradient(float32_pair):
    named = jax.tree_util.tree_flatten_with_path(float32_pair["grads"])[0]
    still = [jax.tree_util.keystr(path) for path, g in named if float(jnp.max(jnp.abs(g))) == 0.0]
    assert len(still) == 2 and all(name.endswith("['expert_bias']") for name in still), still


def test_expert_loads_equal_the_references(float32_pair):
    p = float32_pair
    routed = p["tokens"].shape[0] * (p["tokens"].shape[1] - 1) * p["cfg"]["num_experts_per_tok"]
    for got, want, (_, ffn) in zip(p["loads"], p["want_loads"], p["load"].kinds):
        if ffn == "moe":
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            assert float(jnp.sum(got)) == routed
        else:
            assert want is None and not np.any(np.asarray(got))


def test_bfloat16_in_place_of_float32_fails_the_tolerance(float32_pair):
    p = float32_pair
    load = lfm2_moe.build(tiny("bfloat16"), jax.devices())
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if is_bias(path) else a.astype(jnp.bfloat16), p["params"])
    loss, grads, _ = jax.jit(load.loss_and_grads)(params, p["tokens"])
    assert worst_gap(grads, p["want_grads"]) > 10 * TOLERANCE
    assert abs(float(loss) - float(p["want_loss"])) > TOLERANCE * abs(float(p["want_loss"]))


# ------------------------------------------------------ the short convolution


def conv_by_positions(v, w):
    """``c[t] = sum_j w[:, j] v[t - (L - 1) + j]``, one position and one tap
    at a time, in numpy."""
    v, w = np.asarray(v, np.float64), np.asarray(w, np.float64)
    out = np.zeros_like(v)
    taps = w.shape[1]
    for t in range(v.shape[1]):
        for j in range(taps):
            s = t - (taps - 1) + j
            if s >= 0:
                out[:, t] += w[:, j] * v[:, s]
    return out


def test_the_conv_as_written_equals_a_plain_loop_over_positions(float32_pair):
    load, p = float32_pair["load"], float32_pair["params"]["layers"][0]["conv"]
    u = jax.random.normal(jax.random.key(3), (2, 12, load.d), jnp.float32)
    bcz = np.asarray(u, np.float64) @ np.asarray(p["in_proj"], np.float64)
    b_, c_, z = np.split(bcz, 3, axis=-1)
    want = (c_ * conv_by_positions(b_ * z, p["conv"])) @ np.asarray(p["out_proj"], np.float64)
    for got in (load.conv_operator(p, u), reference.conv_operator(p, u)):
        assert float(np.max(np.abs(np.asarray(got) - want))) <= TOLERANCE * float(np.max(np.abs(want)))
    v = jax.random.normal(jax.random.key(4), (1, 7, 5), jnp.float32)
    w = jax.random.normal(jax.random.key(5), (5, 3), jnp.float32)
    np.testing.assert_allclose(np.asarray(reference.short_conv(v, w)), conv_by_positions(v, w), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("index", [0, 1], ids=["conv", "full_attention"])
def test_a_later_token_changes_no_earlier_output(float32_pair, index):
    """Both operators are causal: the stream up to a position is the same
    whatever comes after it."""
    load, layer = float32_pair["load"], float32_pair["params"]["layers"][index]
    x = jax.random.normal(jax.random.key(6), (2, 16, load.d), jnp.float32)
    other = x.at[:, 9:].set(jax.random.normal(jax.random.key(7), (2, 7, load.d), jnp.float32))
    got, _ = load._layer(layer, x, load.kinds[index])
    moved, _ = load._layer(layer, other, load.kinds[index])
    np.testing.assert_array_equal(np.asarray(got[:, :9]), np.asarray(moved[:, :9]))
    assert float(jnp.max(jnp.abs(got[:, 9:] - moved[:, 9:]))) > 1e-3


def test_the_sliced_vocabularys_loss_is_the_loss_over_the_slice(float32_pair):
    """The file holds 64 of 256 rows: with the embedding's other rows there
    too, the loss over the slice (ids from it, the softmax over its rows
    alone) is what the sliced model gives."""
    p = float32_pair
    cfg, load, tokens = p["cfg"], p["load"], p["tokens"]
    held, full = cfg["vocab_size"], cfg["published"]["vocab_size"]
    assert (held, full) == (64, 256) and int(jnp.max(tokens)) < held
    extra = jax.random.normal(jax.random.key(8), (full - held, load.d), jnp.float32) / np.sqrt(load.d)
    whole = dict(p["params"], embed_tokens=jnp.concatenate([p["params"]["embed_tokens"], extra]))
    with jax.default_matmul_precision("highest"):
        x, _ = reference.hidden(cfg, whole, tokens[:, :-1])
        logits = x @ whole["embed_tokens"].T
        over_slice = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits[..., :held], axis=-1), tokens[:, 1:, None], axis=-1))
        over_all = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), tokens[:, 1:, None], axis=-1))
    assert abs(float(over_slice) - float(p["want_loss"])) <= 1e-6 * float(p["want_loss"])
    assert abs(float(over_slice) - float(p["loss"])) <= TOLERANCE * float(p["want_loss"])
    assert float(over_all) > float(over_slice) + 0.1  # the other rows would take their share


# ---------------------------------------------- the state through the library


def bits(leaf):
    a = np.asarray(leaf)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.fixture(scope="module")
def resumed(tmp_path_factory):
    """The toy state in the configuration's dtypes (bfloat16, the biases
    float32): trained, saved beside an ``np.save`` of every leaf's bits,
    trained on for three steps; then restored into a zeroed target and
    trained for the same three."""
    root = tmp_path_factory.mktemp("lfm2")
    load = lfm2_moe.build(tiny("bfloat16"), jax.devices())
    step = load.step_fn()
    tokens = load.token_pool(3, 8)
    state = load.init_state(3)
    first = jax.tree.map(np.asarray, state)
    for i in range(2):
        state, _ = step(state, tokens[i])
    names = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(state)[0]]
    for i, leaf in enumerate(jax.tree.leaves(state)):
        np.save(root / f"oracle_{i}.npy", bits(leaf))
    trained = jax.tree.map(np.asarray, state)
    manager = SnapshotManager(str(root / "snapshots"))
    manager.save(2, load.split(state))
    live = []
    for i in range(2, 5):
        state, loss = step(state, tokens[i])
        live.append(float(loss))
    zero = load.zero_state
    assert load._zero is None
    target = load.split(zero())
    program = load._zero
    before = phase_stats.snapshot()
    assert manager.restore_latest(target) == 2
    routes = phase_stats.delta(before)["read_route"]
    restored = load.join(target)
    restored_bits = [bits(leaf) for leaf in jax.tree.leaves(restored)]
    again = []
    for i in range(2, 5):
        restored, loss = step(restored, tokens[i])
        again.append(float(loss))
    zero()
    return dict(root=root, names=names, first=first, trained=trained, restored_bits=restored_bits,
                live=live, again=again, load=load, routes=routes, one_zero_program=load._zero is program)


def test_the_state_has_one_leaf_an_expert_matrix(resumed):
    load = resumed["load"]
    experts = [n for n in resumed["names"] if "['experts']" in n and n.startswith("['params']")]
    # two expert layers of 8 modules of three matrices, none stacked
    assert len(experts) == 2 * 8 * 3
    assert "['params']['layers'][1]['feed_forward']['experts'][7]['w2']" in experts
    shapes = {leaf.shape for leaf in jax.tree.leaves(
        [layer["feed_forward"]["experts"] for layer in load.abstract_state()["params"]["layers"][1:]])}
    assert shapes == {(64, 32), (32, 64)}
    # 48 expert leaves and 27 others a stateful, and the two counters
    assert len(resumed["names"]) == 3 * (48 + 27) + 2 == 227
    assert resumed["one_zero_program"]  # the zeroed target is one jitted program, made once


def test_the_restored_state_is_the_oracles_bit_for_bit(resumed):
    assert len(resumed["restored_bits"]) == len(resumed["names"])
    for i, (name, got) in enumerate(zip(resumed["names"], resumed["restored_bits"])):
        want = np.load(resumed["root"] / f"oracle_{i}.npy")
        assert got.dtype == want.dtype and got.shape == want.shape, name
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_the_three_steps_after_the_resume_give_the_uninterrupted_losses(resumed):
    assert resumed["again"] == resumed["live"]
    assert len(set(resumed["live"])) == 3 and all(np.isfinite(resumed["live"]))


def test_a_float32_bias_beside_bfloat16_moved_by_its_rule(resumed):
    trained, adam = resumed["trained"], resumed["trained"]["opt_state"][0]
    dtypes = {str(leaf.dtype) for leaf in jax.tree.leaves(trained["params"])}
    assert dtypes == {"bfloat16", "float32"}
    speed = resumed["load"].bias_speed
    seen = 0
    for layer, mu, nu in zip(trained["params"]["layers"], adam.mu["layers"], adam.nu["layers"]):
        if "experts" in layer["feed_forward"]:
            bias = layer["feed_forward"]["expert_bias"]
            assert bias.dtype == np.float32
            # two steps of +-speed (or 0 at the mean): a multiple of it, and not all zero
            np.testing.assert_allclose(bias / speed, np.round(bias / speed), atol=1e-3)
            assert np.any(bias != 0)
            assert not np.any(mu["feed_forward"]["expert_bias"]) and not np.any(nu["feed_forward"]["expert_bias"])
            seen += 1
    assert seen == 2
    # every expert matrix moved in two steps, and both of its moments
    moved = jax.tree.map(lambda a, b: bool(np.any(bits(a) != bits(b))), resumed["first"], trained)
    for tree in (moved["params"], moved["opt_state"][0].mu, moved["opt_state"][0].nu):
        assert all(jax.tree.leaves([layer["feed_forward"]["experts"] for layer in tree["layers"][1:]]))
    assert int(trained["step"]) == 2 and int(adam.count) == 2


# ------------------------------------------------- what the cell's metrics read


def test_the_read_routes_add_up_to_the_states_bytes(resumed):
    """No toy leaf reaches a megabyte, so every one of them is a merged
    slab member; ``entries`` counts the leaves."""
    r = resumed["routes"]
    assert r["n"] == 4  # one a stateful
    assert r["sequential"] + r["striped"] + r["merged"] == r["bytes"] == resumed["load"].state_bytes()
    assert r["merged"] == r["bytes"] and r["merged_leaves"] == r["entries"] == 227
    assert r["sequential_leaves"] == r["striped_leaves"] == 0


def host_events(xplane):
    names = set()
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/device:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def test_leaves_of_megabytes_go_the_sequential_route_and_fs_read_is_a_named_host_event(tmp_path):
    """A tree as the real state's: leaves of 1 MiB and more and under 32 MiB
    are read into place by the sequential ``fs_read``, which a traced
    restore names; the small ones merge; one of 32 MiB is striped."""
    rng = np.random.default_rng(0)
    leaves = {f"expert_{i}": jnp.asarray(rng.standard_normal((512, 1024)), jnp.bfloat16) for i in range(6)}
    leaves["norm"] = jnp.ones((1024,), jnp.bfloat16)
    leaves["bias"] = jnp.zeros((32,), jnp.float32)
    leaves["table"] = jnp.asarray(rng.standard_normal((4096, 4096)), jnp.bfloat16)
    manager = SnapshotManager(str(tmp_path / "root"))
    manager.save(1, {"params": StateDict(**leaves)})
    target = {"params": StateDict(**jax.tree.map(jnp.zeros_like, leaves))}
    events = []

    def on_event(event):
        if event.name == "restore.end":
            events.append(event.metadata)

    from torchsnapshot_tpu.event_handlers import register_event_handler, unregister_event_handler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    before = phase_stats.snapshot()
    register_event_handler(on_event)
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=options)
    try:
        assert manager.restore_latest(target) == 1
    finally:
        jax.profiler.stop_trace()
        unregister_event_handler(on_event)
    for name, leaf in leaves.items():
        np.testing.assert_array_equal(bits(target["params"][name]), bits(leaf), err_msg=name)
    delta = phase_stats.delta(before)
    r = delta["read_route"]
    total = sum(leaf.nbytes for leaf in leaves.values())
    assert r["sequential"] + r["striped"] + r["merged"] == r["bytes"] == total
    assert r["sequential"] == 6 << 20 and r["sequential_leaves"] == 6
    assert r["striped"] == 32 << 20 and r["striped_leaves"] == 1
    assert r["merged"] == 2048 + 128 and r["merged_leaves"] == 2 and r["entries"] == 9
    # under the striped minimum the sequential route is all but all of the bytes
    assert r["sequential"] / (total - r["striped"]) > 0.999
    assert events[-1]["read_route"] == {"sequential": 6 << 20, "striped": 32 << 20, "merged": 2176, "entries": 9}
    # the route the plan names is the phase the read ran under
    assert delta["fs_read"]["bytes"] >= 6 << 20 and delta["fs_read"]["n"] >= 6
    (xplane,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = host_events(xplane)
    assert "fs_read" in names and "native_read" in names
