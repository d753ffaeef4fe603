"""The ``dots3_note`` load (``chipbench/models/dots3_note.py``) against its
plain reference (``dots3_note_reference.py``) at the toy widths of
``chipbench/configs/tiny-moe.json``, on the CPU, from seeds: loss and
gradients, the shares of a deployment adding up to the uncut layer, and the
state through ``SnapshotManager`` against a per-leaf ``np.save`` oracle.

Tolerance of the float32 comparisons: 1e-4 of the largest magnitude in the
leaf (or output).  Both sides then compute in float32 and differ in the
order of operations alone (banks against a loop over experts, fused against
split logits, rematerialised against not), which reads 4e-6 here; the same
load computing in bfloat16 reads 1e-2 and more and has to fail it."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models import dots3_note, dots3_note_reference as reference
from torchsnapshot_tpu import SnapshotManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOLERANCE = 1e-4


def tiny(dtype="float32", **changes):
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", "tiny-moe.json")))
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


@pytest.fixture(scope="module")
def float32_pair():
    """One seeded state and batch, the load's loss and gradients in float32
    and the reference's."""
    cfg = tiny()
    load = dots3_note.build(cfg, jax.devices())
    params = load.init_state(11)["params"]
    # biases off zero, as a few steps leave them, so that they matter to the choice
    for layer in params["layers"]:
        if "moe" in layer:
            bias = layer["moe"]["router"]["bias"]
            layer["moe"]["router"]["bias"] = bias + 0.01 * jnp.sin(jnp.arange(bias.size, dtype=bias.dtype))
    tokens = load.token_pool(11, 1)[0]
    loss, grads, loads = jax.jit(load.loss_and_grads)(params, tokens)
    (want_loss, want_loads), want_grads = jax.jit(
        jax.value_and_grad(lambda p: reference.loss(cfg, p, tokens), has_aux=True)
    )(params)
    return dict(cfg=cfg, params=params, tokens=tokens, loss=loss, grads=grads, loads=loads,
                want_loss=want_loss, want_grads=want_grads, want_loads=want_loads)


def test_loss_matches_the_reference(float32_pair):
    p = float32_pair
    assert abs(float(p["loss"]) - float(p["want_loss"])) <= TOLERANCE * abs(float(p["want_loss"]))
    assert p["tokens"].shape[1] > p["cfg"]["index_topk"]  # the indexer's selection ran


def test_gradients_match_the_reference(float32_pair):
    p = float32_pair
    assert worst_gap(p["grads"], p["want_grads"]) <= TOLERANCE


def test_every_leaf_but_a_routers_bias_gets_a_gradient(float32_pair):
    named = jax.tree_util.tree_flatten_with_path(float32_pair["grads"])[0]
    still = [jax.tree_util.keystr(path) for path, g in named if float(jnp.max(jnp.abs(g))) == 0.0]
    assert len(still) == 4 and all(name.endswith("['router']['bias']") for name in still), still


def test_expert_loads_match_the_reference(float32_pair):
    p = float32_pair
    for got, want, (_, ffn) in zip(p["loads"], p["want_loads"], dots3_note.layer_kinds(p["cfg"])):
        if ffn == "moe":
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            assert float(jnp.sum(got)) == p["tokens"].shape[0] * (p["tokens"].shape[1] - 1) * p["cfg"]["num_experts_per_tok"]


def test_bfloat16_in_place_of_float32_fails_the_tolerance(float32_pair):
    p = float32_pair
    load = dots3_note.build(tiny("bfloat16"), jax.devices())
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if "router" in jax.tree_util.keystr(path) and "bias" in jax.tree_util.keystr(path)
        else a.astype(jnp.bfloat16),
        p["params"],
    )
    loss, grads, _ = jax.jit(load.loss_and_grads)(params, p["tokens"])
    assert worst_gap(grads, p["want_grads"]) > 10 * TOLERANCE
    assert abs(float(loss) - float(p["want_loss"])) > TOLERANCE * abs(float(p["want_loss"]))


# ------------------------------------------------------------- the shares


def slice_heads(attn, share, shares, dims):
    heads = attn["w_g"].shape[1] // shares
    cols = lambda w, per: w[:, share * heads * per:(share + 1) * heads * per]  # noqa: E731
    return dict(
        attn,
        w_qb=cols(attn["w_qb"], dims["nope"] + dims["rope"]),
        w_kvb=cols(attn["w_kvb"], dims["nope"] + dims["v"]),
        w_g=cols(attn["w_g"], 1),
        w_o=attn["w_o"][share * heads * dims["v"]:(share + 1) * heads * dims["v"], :],
    )


@pytest.mark.parametrize("index,kind", [(1, "full"), (2, "sliding")])
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(index, kind):
    """All 8 head shares and all 32 expert shares of one layer, as the load
    computes each, with the shared expert counted once, against the
    reference's uncut layer (16 or 8 heads, 64 experts)."""
    share_cfg = tiny()
    published = share_cfg["published"]
    uncut_cfg = tiny(**{k: published[k] for k in ("num_attention_heads", "swa_num_attention_heads",
                                                  "n_routed_experts")})
    uncut = dots3_note.build(uncut_cfg, jax.devices())
    layer = uncut.init_state(5)["params"]["layers"][index]
    layer["moe"]["router"]["bias"] = 0.01 * jnp.cos(jnp.arange(64, dtype=jnp.float32))
    x = jax.random.normal(jax.random.key(9), (2, 24, uncut.d), jnp.float32)
    want, _, _ = reference.layer_forward(uncut_cfg, layer, x, kind == "sliding")

    load = dots3_note.build(share_cfg, jax.devices())
    dims = dots3_note.attention_dims(share_cfg, kind)
    h = load._rms_norm(x, layer["attn_norm"])
    head_shares = uncut_cfg[("swa_" if kind == "sliding" else "") + "num_attention_heads"] // dims["heads"]
    assert head_shares == 8
    attn = sum(
        load.attention(dict(layer, attn=slice_heads(layer["attn"], r, head_shares, dims)), h, kind)[0]
        for r in range(head_shares)
    )
    x1 = x + attn  # what the all-reduce over the head shares leaves on every chip
    g = load._rms_norm(x1, layer["ffn_norm"])
    expert_shares = uncut.experts_here // load.experts_here
    assert expert_shares == 32
    out = x1 + load._swiglu(layer["moe"]["shared"], g)
    loads = 0.0
    for r in range(expert_shares):
        load.first_expert = r * load.experts_here
        banks = {k: w[load.first_expert:load.first_expert + load.experts_here]
                 for k, w in layer["moe"]["experts"].items()}
        part, n = load.routed(dict(layer["moe"], experts=banks), g)
        out = out + part
        loads = loads + n
    assert float(jnp.max(jnp.abs(out - want))) <= TOLERANCE * float(jnp.max(jnp.abs(want)))
    # every share routes over all 64 and counts the same loads
    assert float(jnp.sum(loads)) == expert_shares * 2 * 24 * share_cfg["num_experts_per_tok"]


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
    root = tmp_path_factory.mktemp("dots3")
    load = dots3_note.build(tiny("bfloat16"), jax.devices())
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
    target = load.split(load.zero_state())
    assert manager.restore_latest(target) == 2
    restored = load.join(target)
    restored_bits = [bits(leaf) for leaf in jax.tree.leaves(restored)]
    again = []
    for i in range(2, 5):
        restored, loss = step(restored, tokens[i])
        again.append(float(loss))
    return dict(root=root, names=names, first=first, trained=trained, restored_bits=restored_bits,
                live=live, again=again, load=load)


def test_the_restored_state_is_the_oracles_bit_for_bit(resumed):
    assert len(resumed["restored_bits"]) == len(resumed["names"]) == 296
    for i, (name, got) in enumerate(zip(resumed["names"], resumed["restored_bits"])):
        want = np.load(resumed["root"] / f"oracle_{i}.npy")
        assert got.dtype == want.dtype and got.shape == want.shape, name
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_the_three_steps_after_the_resume_give_the_uninterrupted_losses(resumed):
    assert resumed["again"] == resumed["live"]
    assert len(set(resumed["live"])) == 3 and all(np.isfinite(resumed["live"]))


def test_two_steps_change_every_stateful_and_the_bias_by_its_rule(resumed):
    first, trained = resumed["first"], resumed["trained"]
    moved = jax.tree.map(lambda a, b: bool(np.any(bits(a) != bits(b))), first, trained)
    adam_first, adam = first["opt_state"][0], trained["opt_state"][0]
    named = jax.tree_util.tree_flatten_with_path(moved["params"])[0]
    # a norm's scale at 1.0 in bfloat16 does not take a step of 1e-3 (half a unit
    # in the last place there is 2e-3); every other parameter moved
    still = [jax.tree_util.keystr(p) for p, m in named if not m]
    assert all("norm" in name and "bias" not in name for name in still), still
    assert len(named) - len(still) >= 98 - 24
    speed = resumed["load"].bias_speed
    for layer, mu, nu in zip(trained["params"]["layers"], adam.mu["layers"], adam.nu["layers"]):
        if "moe" in layer:
            bias = layer["moe"]["router"]["bias"]
            assert bias.dtype == np.float32
            # two steps of +-speed (or 0 at the mean): a multiple of it, and not all zero
            np.testing.assert_allclose(bias / speed, np.round(bias / speed), atol=1e-3)
            assert np.any(bias != 0)
            assert not np.any(mu["moe"]["router"]["bias"]) and not np.any(nu["moe"]["router"]["bias"])
    # the moments of everything else moved, and the counters
    for tree_first, tree in ((adam_first.mu, adam.mu), (adam_first.nu, adam.nu)):
        named = jax.tree_util.tree_flatten_with_path(
            jax.tree.map(lambda a, b: bool(np.any(bits(a) != bits(b))), tree_first, tree))[0]
        still = [jax.tree_util.keystr(p) for p, m in named if not m]
        assert all(name.endswith("['router']['bias']") for name in still) and len(still) == 4, still
    assert int(trained["step"]) == 2 and int(adam.count) == 2


def test_statefuls_hold_bfloat16_and_float32_side_by_side(resumed):
    dtypes = {str(leaf.dtype) for leaf in jax.tree.leaves(resumed["trained"]["params"])}
    assert dtypes == {"bfloat16", "float32"}
