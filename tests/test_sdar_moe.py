"""The ``sdar_moe`` load (``chipbench/models/sdar_moe.py``) against its plain
reference (``sdar_moe_reference.py``) at the toy widths of
``chipbench/configs/tiny-sdar.json``, on the CPU, from seeds: loss and
gradients, the block-diffusion mask against a loop over positions and what it
lets a token change, the noise drawn from the state's key, the softmax router
against a hand computation, the expert shares against the uncut layer, the
sliced vocabulary, and the state (one leaf an expert matrix, the noise key an
``RNGState`` of the app state) through ``SnapshotManager`` against a per-leaf
``np.save`` oracle.

Tolerance of the float32 comparisons: 1e-4 of the largest magnitude in the
leaf (or output).  Both sides then compute in float32 and differ in the order
of operations alone (grouped heads and clipped key blocks against a loop over
heads under a whole mask, the experts' weight applied before their last
product against after it, the head's loss in blocks), which reads 1e-6 here;
the same load computing in bfloat16 reads 1e-2 and more and has to fail it."""

import copy
import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models import sdar_moe, sdar_moe_reference as reference
from torchsnapshot_tpu import RNGState, SnapshotManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOLERANCE = 1e-4


def tiny(dtype="float32", **assumed):
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", "tiny-sdar.json")))
    cfg = copy.deepcopy(cfg)
    cfg["state_dtypes"].update(params=dtype, adam_mu=dtype, adam_nu=dtype)
    cfg["activation_dtype"] = dtype
    cfg["assumed"].update(assumed)
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
    """One seeded state, batch and noise key; the load's loss and gradients
    in float32 and the reference's."""
    cfg = tiny()
    load = sdar_moe.build(cfg, jax.devices())
    params = load.init_state(11)["params"]
    tokens = load.token_pool(11, 1)[0]
    key = jax.random.key(41)
    loss, grads, loads = jax.jit(load.loss_and_grads)(params, tokens, key)
    (want_loss, want_loads), want_grads = jax.jit(
        jax.value_and_grad(lambda p: reference.loss(cfg, p, tokens, key), has_aux=True)
    )(params)
    return dict(cfg=cfg, load=load, params=params, tokens=tokens, key=key, loss=loss, grads=grads,
                loads=loads, want_loss=want_loss, want_grads=want_grads, want_loads=want_loads)


def test_the_toy_has_the_real_configurations_keys():
    toy = tiny()
    real = json.load(open(os.path.join(ROOT, "chipbench", "configs", "sdar-30b-a3b-chat.json")))
    assert set(toy) == set(real) and set(toy["assumed"]) == set(real["assumed"])
    assert toy["builder"] == real["builder"] == "chipbench.models.sdar_moe:build"
    assert toy["published"]["num_experts"] == 16 and toy["num_experts"] == 4
    assert toy["assumed"]["sequence_length"] % toy["assumed"]["query_block"] == 0


def test_loss_matches_the_reference(float32_pair):
    p = float32_pair
    assert abs(float(p["loss"]) - float(p["want_loss"])) <= TOLERANCE * abs(float(p["want_loss"]))
    assert 1.0 < float(p["want_loss"]) < 30.0


def test_gradients_of_every_kind_of_leaf_match_the_reference(float32_pair):
    p = float32_pair
    assert worst_gap(p["grads"], p["want_grads"]) <= TOLERANCE
    kinds = {jax.tree_util.keystr(path[-1:]) for path, _ in jax.tree_util.tree_flatten_with_path(p["grads"])[0]}
    assert kinds == {"['embed_tokens']", "['input_layernorm']", "['q_proj']", "['k_proj']", "['v_proj']",
                     "['o_proj']", "['q_norm']", "['k_norm']", "['post_attention_layernorm']", "['gate']",
                     "['gate_proj']", "['up_proj']", "['down_proj']", "['norm']", "['lm_head']"}


def test_expert_loads_equal_the_references(float32_pair):
    p = float32_pair
    positions = 2 * p["tokens"].size
    for got, want in zip(p["loads"], p["want_loads"]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert float(jnp.sum(got)) == positions * p["cfg"]["num_experts_per_tok"]
        assert got.shape == (16,)  # over every published expert


def test_bfloat16_in_place_of_float32_fails_the_tolerance(float32_pair):
    p = float32_pair
    load = sdar_moe.build(tiny("bfloat16"), jax.devices())
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p["params"])
    loss, grads, _ = jax.jit(load.loss_and_grads)(params, p["tokens"], p["key"])
    assert worst_gap(grads, p["want_grads"]) > 10 * TOLERANCE
    assert abs(float(loss) - float(p["want_loss"])) > TOLERANCE * abs(float(p["want_loss"]))


# ----------------------------------------------------------------- the mask


def mask_by_positions(length, block):
    """Query ``i`` of ``[x_t ; x_0]`` reads key ``j``, one pair at a time."""
    keep = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            qb, kb = (i % length) // block, (j % length) // block
            if i >= length:  # a clean query: clean keys of its block and before
                keep[i, j] = j >= length and kb <= qb
            else:  # a noised query: its own block's noised keys, earlier blocks' clean ones
                keep[i, j] = (j < length and kb == qb) or (j >= length and kb < qb)
    return keep


def test_the_mask_as_written_equals_a_plain_loop_over_positions(float32_pair):
    """The load's blocks of queries with their clipped keys, laid out as one
    matrix (a key no block reads is masked), and the reference's whole mask,
    both against the loop; the blocks read about a quarter of the pairs."""
    load, block = float32_pair["load"], float32_pair["cfg"]["assumed"]["block_length"]
    length = load.seq
    want = mask_by_positions(length, block)
    np.testing.assert_array_equal(np.asarray(reference.mask(length, block)), want)
    got = np.zeros_like(want)
    read = 0
    for first, size, key_ranges in load.query_blocks(length):
        q_at = first + np.arange(size)
        k_at = np.concatenate([lo + np.arange(n) for lo, n in key_ranges])
        got[np.ix_(q_at, k_at)] = np.asarray(sdar_moe.visible(q_at, k_at, length, block))
        read += size * len(k_at)
    np.testing.assert_array_equal(got, want)
    assert read < 0.45 * (2 * length) ** 2
    # every query reads at least its own position
    assert np.all(np.diag(want))


def outputs(load, params, z):
    at = jnp.arange(z.shape[1] // 2)
    positions = jnp.broadcast_to(jnp.concatenate([at, at]), z.shape)
    x, _, _ = jax.jit(load.stream)(params, z, positions)
    return np.asarray(x)


def changed_rows(load, params, z, at):
    """Positions of ``[x_t ; x_0]`` whose output moves when the id at ``at``
    is changed."""
    other = z.at[:, at].set((z[:, at] + 1) % (load.v - 1))
    return np.where(np.any(outputs(load, params, z) != outputs(load, params, other), axis=(0, 2)))[0]


def test_a_clean_token_of_block_b_changes_no_output_of_an_earlier_block(float32_pair):
    """In either half: the clean token at block 3 moves no output of blocks 0-2,
    noised or clean; the clean positions of its block read it."""
    load, p = float32_pair["load"], float32_pair
    length, block = load.seq, load.block
    z = jnp.concatenate([p["tokens"], p["tokens"]], axis=1)
    at = length + 3 * block + 1
    moved = changed_rows(load, p["params"], z, at)
    earlier = set(range(3 * block)) | set(range(length, length + 3 * block))
    assert not earlier & set(moved.tolist())
    assert set(range(length + 3 * block, length + 4 * block)) <= set(moved.tolist())


def test_a_noised_token_changes_no_output_outside_its_own_blocks_noised_queries(float32_pair):
    load, p = float32_pair["load"], float32_pair
    block = load.block
    z = jnp.concatenate([p["tokens"], p["tokens"]], axis=1)
    moved = changed_rows(load, p["params"], z, 5 * block + 2)
    assert moved.tolist() == list(range(5 * block, 6 * block))


# ---------------------------------------------------------------- the noise


def test_the_same_key_gives_the_same_noise_and_another_key_another(float32_pair):
    load, tokens = float32_pair["load"], float32_pair["tokens"]
    a = load.noise(jax.random.key(1), tokens)
    b = load.noise(jax.random.key(1), tokens)
    c = load.noise(jax.random.key(2), tokens)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert np.any(np.asarray(a[1]) != np.asarray(c[1]))
    # the reference draws the same noise from the same key
    for x, y in zip(a, reference.noise(float32_pair["cfg"], jax.random.key(1), tokens)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_the_masked_rate_is_near_the_mean_level_and_a_level_is_a_blocks():
    """Over 16,384 blocks the masked share is E[t] = (1 + 1e-3) / 2 (the
    standard error is about 0.0025: a limit of 0.015 is six of them); a
    block's positions share one level in [1e-3, 1); the mask is never a clean
    token and every masked position holds it."""
    load = sdar_moe.build(tiny(sequence_length=4096, query_block=4096), jax.devices())
    tokens = load.token_pool(3, 1)[0][:, :4096]
    tokens = jnp.concatenate([tokens] * 8)  # 16 sequences of 4,096: 16,384 blocks
    x_t, masked, t = (np.asarray(a) for a in load.noise(jax.random.key(9), tokens))
    assert abs(masked.mean() - (1 + 1e-3) / 2) < 0.015
    levels = t.reshape(t.shape[0], -1, load.block)
    assert np.all(levels == levels[..., :1]) and levels.min() >= 1e-3 and levels.max() < 1
    assert load.mask_id == load.v - 1 and int(np.max(np.asarray(tokens))) < load.mask_id
    np.testing.assert_array_equal(x_t == load.mask_id, masked)
    np.testing.assert_array_equal(x_t[~masked], np.asarray(tokens)[~masked])


def test_unmasked_positions_add_nothing_to_the_loss(float32_pair):
    """On the same stream, the head's loss is the same whatever the targets
    of the unmasked positions are, moves when a masked position's target
    moves, and is 0 with nothing masked."""
    load, p = float32_pair["load"], float32_pair
    tokens, head = p["tokens"], p["params"]["lm_head"]
    length = tokens.shape[1]
    x_t, masked, t = load.noise(p["key"], tokens)
    z = jnp.concatenate([x_t, tokens], axis=1)
    at = jnp.arange(length)
    x, _, _ = load.stream(p["params"], z, jnp.broadcast_to(jnp.concatenate([at, at]), z.shape))
    x = x[:, :length]
    weight = jnp.where(masked, 1.0 / t, 0.0)
    assert bool(jnp.any(masked)) and bool(jnp.any(~masked))
    full = float(load._head_loss(head, x, tokens, weight))
    moved_unmasked = jnp.where(masked, tokens, (tokens + 1) % load.mask_id)
    assert float(load._head_loss(head, x, moved_unmasked, weight)) == full
    moved_masked = jnp.where(masked, (tokens + 1) % load.mask_id, tokens)
    assert float(load._head_loss(head, x, moved_masked, weight)) != full
    assert float(load._head_loss(head, x, tokens, jnp.zeros_like(weight))) == 0.0


# --------------------------------------------------------------- the router


def test_the_softmax_router_against_a_hand_computation(float32_pair):
    load, mlp = float32_pair["load"], float32_pair["params"]["layers"][0]["mlp"]
    h = jax.random.normal(jax.random.key(12), (1, 10, load.d), jnp.float32)
    combine, (load_count, aux) = load.route(mlp, h)
    x = np.asarray(h, np.float64).reshape(-1, load.d)
    scores = x @ np.asarray(mlp["gate"], np.float64)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    k = load.top_k
    want = np.zeros((10, load.experts_here))
    count = np.zeros(load.experts)
    for t in range(10):
        chosen = np.argsort(-p[t])[:k]
        count[chosen] += 1
        for e in chosen:
            if e < load.experts_here:
                want[t, e] = p[t, e] / p[t, chosen].sum()
    np.testing.assert_allclose(np.asarray(combine), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(load_count), count)
    coef = float(float32_pair["cfg"]["assumed"]["router_aux_loss_coef"])
    want_aux = coef * load.experts * np.sum(count / (10 * k) * p.mean(0))
    assert float(aux) == pytest.approx(want_aux, rel=1e-5)


def test_the_shares_add_up_to_the_uncut_layer(float32_pair):
    """Four expert shares (experts 0-3, 4-7, 8-11, 12-15 of 16, the router
    whole in each): their routed parts sum to what the uncut layer's sixteen
    give, and each share's balance term is the uncut one."""
    p = float32_pair
    uncut_cfg = tiny(first_expert=0)
    uncut_cfg["num_experts"] = 16
    uncut = sdar_moe.build(uncut_cfg, jax.devices())
    gate = p["params"]["layers"][0]["mlp"]["gate"]
    experts = [
        {name: jax.random.normal(jax.random.fold_in(jax.random.key(13), 3 * e + i), shape, jnp.float32) / 8
         for i, (name, shape) in enumerate((("gate_proj", (64, 32)), ("up_proj", (64, 32)), ("down_proj", (32, 64))))}
        for e in range(16)
    ]
    h = jax.random.normal(jax.random.key(14), (2, 12, 64), jnp.float32)
    whole, (_, whole_aux) = uncut.experts_forward({"gate": gate, "experts": experts}, h)
    parts = []
    for share in range(4):
        load = sdar_moe.build(tiny(first_expert=4 * share), jax.devices())
        part, (_, aux) = load.experts_forward({"gate": gate, "experts": experts[4 * share:4 * share + 4]}, h)
        assert float(aux) == pytest.approx(float(whole_aux), rel=1e-6)
        parts.append(part)
    total = sum(parts)
    assert float(jnp.max(jnp.abs(total - whole))) <= TOLERANCE * float(jnp.max(jnp.abs(whole)))
    assert all(float(jnp.max(jnp.abs(part))) > 0 for part in parts)


def test_the_sliced_vocabularys_loss_is_the_loss_over_the_slice(float32_pair):
    """The file holds 64 of 256 rows: with the head's other rows there too,
    the loss over the slice (the softmax over its rows alone) is what the
    sliced model gives."""
    p = float32_pair
    cfg, load, tokens = p["cfg"], p["load"], p["tokens"]
    held, full = cfg["vocab_size"], cfg["published"]["vocab_size"]
    assert (held, full) == (64, 256) and int(jnp.max(tokens)) < held - 1
    extra = jax.random.normal(jax.random.key(8), (full - held, load.d), jnp.float32) / np.sqrt(load.d)
    head = jnp.concatenate([p["params"]["lm_head"], extra])
    length = tokens.shape[1]
    with jax.default_matmul_precision("highest"):
        x_t, m, t = reference.noise(cfg, p["key"], tokens)
        x, _, aux = reference.hidden(cfg, p["params"], jnp.concatenate([x_t, tokens], axis=1))
        logits = x[:, :length] @ head.T

        def scored(logp):
            nll = -jnp.take_along_axis(logp, tokens[..., None], axis=-1)[..., 0]
            return jnp.sum(jnp.where(m, nll / t, 0.0)) / tokens.size + aux

        over_slice = scored(jax.nn.log_softmax(logits[..., :held], axis=-1))
        over_all = scored(jax.nn.log_softmax(logits, axis=-1))
    assert abs(float(over_slice) - float(p["want_loss"])) <= 1e-6 * float(p["want_loss"])
    assert abs(float(over_slice) - float(p["loss"])) <= TOLERANCE * float(p["want_loss"])
    assert float(over_all) > float(over_slice) + 0.1  # the other rows would take their share


# ---------------------------------------------- the state through the library


def bits(leaf):
    a = np.asarray(leaf)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.fixture(scope="module")
def resumed(tmp_path_factory):
    """The toy state in the configuration's dtypes: trained, saved (the noise
    key as an ``RNGState``) beside an ``np.save`` of every leaf's bits,
    trained on for three steps; then restored into a zeroed target and
    trained for the same three; and once more with the ``RNGState`` left out
    of the restore's app state."""
    root = tmp_path_factory.mktemp("sdar")
    load = sdar_moe.build(tiny("bfloat16"), jax.devices())
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
    app_state = load.split(state)
    assert isinstance(app_state["rng"], RNGState)
    python_rng = random.getstate()
    manager.save(2, app_state)
    live = []
    for i in range(2, 5):
        state, loss = step(state, tokens[i])
        live.append(float(loss))
    random.random()  # the process's generator moves on after the save
    target = load.split(load.zero_state())
    assert manager.restore_latest(target) == 2
    python_rng_restored = random.getstate() == python_rng
    restored = load.join(target)
    restored_bits = [bits(leaf) for leaf in jax.tree.leaves(restored)]
    again = []
    for i in range(2, 5):
        restored, loss = step(restored, tokens[i])
        again.append(float(loss))
    partial = load.split(load.zero_state())
    assert manager.restore_latest({k: v for k, v in partial.items() if k != "rng"}) == 2
    without, without_loss = step(load.join(partial), tokens[2])
    return dict(root=root, names=names, first=first, trained=trained, restored_bits=restored_bits,
                live=live, again=again, load=load, without_loss=float(without_loss),
                without_key=np.asarray(without["noise_key"]), python_rng_restored=python_rng_restored)


def test_the_state_has_one_leaf_an_expert_matrix(resumed):
    experts = [n for n in resumed["names"] if "['experts']" in n and n.startswith("['params']")]
    # two layers of 4 modules of three matrices, none stacked
    assert len(experts) == 2 * 4 * 3
    assert "['params']['layers'][1]['mlp']['experts'][3]['down_proj']" in experts
    # 24 expert leaves and 21 others a stateful, the two counters and the noise key
    assert len(resumed["names"]) == 3 * (24 + 21) + 3 == 138
    assert "['noise_key']" in resumed["names"]


def test_the_restored_state_is_the_oracles_bit_for_bit_the_noise_key_among_it(resumed):
    assert len(resumed["restored_bits"]) == len(resumed["names"])
    for i, (name, got) in enumerate(zip(resumed["names"], resumed["restored_bits"])):
        want = np.load(resumed["root"] / f"oracle_{i}.npy")
        assert got.dtype == want.dtype and got.shape == want.shape, name
        np.testing.assert_array_equal(got, want, err_msg=name)
    key = resumed["restored_bits"][resumed["names"].index("['noise_key']")]
    assert key.dtype == np.uint32 and key.shape == (2,) and np.any(key != 0)
    # the RNGState's own part: the process's generator as it was at the save
    assert resumed["python_rng_restored"]


def test_the_three_steps_after_the_resume_give_the_uninterrupted_losses(resumed):
    assert resumed["again"] == resumed["live"]
    assert len(set(resumed["live"])) == 3 and all(np.isfinite(resumed["live"]))
    # the key moved every step, so each step drew its own noise
    trained, first = resumed["trained"], resumed["first"]
    assert np.any(trained["noise_key"] != first["noise_key"]) and int(trained["step"]) == 2


def test_gc_freeze_freezes_the_heap_once_after_the_first_step():
    """With ``gc_freeze`` the first step collects the heap it leaves and
    freezes it out of the collector's later walks, once; the steps give the
    plain step's losses.  Without it the step is the jitted step itself and
    freezes nothing."""
    import gc

    before = gc.get_freeze_count()
    plain = sdar_moe.build(tiny("bfloat16"), jax.devices())
    assert plain.step_fn() is plain.step_fn() and hasattr(plain.step_fn(), "lower")
    tokens = plain.token_pool(5, 2)
    state = plain.init_state(5)
    want = []
    for i in range(2):
        state, loss = plain.step_fn()(state, tokens[i])
        want.append(float(loss))
    assert gc.get_freeze_count() == before
    load = sdar_moe.build(tiny("bfloat16", gc_freeze=True), jax.devices())
    step = load.step_fn()
    assert load.step_fn() is step
    state, loss = step(load.init_state(5), tokens[0])
    frozen = gc.get_freeze_count()
    assert frozen > before
    state, loss2 = step(state, tokens[1])
    assert gc.get_freeze_count() <= frozen  # not again: frozen objects freed by count drop out
    assert [float(loss), float(loss2)] == want


def test_a_resume_without_the_rng_state_gives_another_loss(resumed):
    """Left out of the restore's app state, the key stays at the target's
    zeros: every other leaf is restored, the step draws another mask, and the
    loss is not the live job's."""
    assert not np.any(resumed["without_key"] != np.asarray(
        jax.random.key_data(jax.random.split(jax.random.wrap_key_data(np.zeros(2, np.uint32)))[0])))
    assert resumed["without_loss"] != resumed["live"][0]
    assert np.isfinite(resumed["without_loss"])
