"""The ``brumby`` load (``chipbench/models/brumby.py``) against its plain
reference (``brumby_reference.py``) at the toy widths of
``chipbench/configs/tiny-brumby.json``, on the CPU, from seeds: loss and every
leaf's gradient, the two forms of the retention, the head shares of a
deployment adding up to the uncut layer, the vocabulary's slice, and the state
through ``SnapshotManager`` at a chunk size forced small, so that the three
stacked feed-forward leaves of each stateful are chunked as the real ones are.

Tolerances of the float32 comparisons.  Both sides compute in float32 and
differ in the order of operations alone (all heads in one einsum against a
loop over heads, a ``scan`` of rematerialised layers against a loop, the
batch at once against one sequence after another).  The loss, a mean of a few
hundred terms, is held to 1e-5 relative (reads 2e-7 and less over four
seeds).  A leaf's gradient is held to 1e-4 of its L2 norm: it passes through
four layers, a square and a division by a sum of weights that can be small,
and reads up to 1.1e-5 over four seeds, so ISSUE 31's 1e-5 does not hold for
it; the same load computing in bfloat16 reads 8e-2 and more."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.models import brumby, brumby_reference as reference
from torchsnapshot_tpu import SnapshotManager, knobs
from torchsnapshot_tpu.manifest import ChunkedTensorEntry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOSS_TOLERANCE = 1e-5
GRADIENT_TOLERANCE = 1e-4
LEAVES = [
    "embed/tokens", "layers/attn/wq", "layers/attn/wk", "layers/attn/wv", "layers/attn/wo",
    "layers/attn/wg", "layers/attn/q_norm", "layers/attn/k_norm", "layers/mlp/w_gate",
    "layers/mlp/w_up", "layers/mlp/w_down", "layers/attn_norm", "layers/mlp_norm", "final_norm",
    "output/kernel",
]


def tiny(dtype="float32", sequence_length=None, **changes):
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs", "tiny-brumby.json")))
    cfg = copy.deepcopy(cfg)
    cfg["state_dtypes"].update(params=dtype, adam_mu=dtype, adam_nu=dtype)
    cfg["activation_dtype"] = dtype
    if sequence_length:
        cfg["assumed"]["sequence_length"] = sequence_length
    cfg.update(changes)
    return cfg


def get(tree, name):
    for key in name.split("/"):
        tree = tree[key]
    return tree


def rel_l2(got, want):
    a, b = np.asarray(got, np.float64).ravel(), np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def cosine(got, want):
    a, b = np.asarray(got, np.float64).ravel(), np.asarray(want, np.float64).ravel()
    return float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))


def reference_loss_and_grads(cfg, params, tokens):
    return jax.jit(jax.value_and_grad(lambda p: reference.loss(cfg, p, tokens)))(params)


@pytest.fixture(scope="module")
def float32_pair():
    """One seeded state and batch, the load's loss and gradients in float32
    and the reference's."""
    cfg = tiny()
    load = brumby.build(cfg, jax.devices())
    params = load.init_state(11)["params"]
    tokens = load.token_pool(11, 1)[0]
    loss, grads = jax.jit(load.loss_and_grads)(params, tokens)
    want_loss, want_grads = reference_loss_and_grads(cfg, params, tokens)
    return dict(cfg=cfg, params=params, tokens=tokens, loss=loss, grads=grads,
                want_loss=want_loss, want_grads=want_grads)


def test_the_tree_is_the_dense_decoders_plus_the_gate_and_the_two_norms(float32_pair):
    named = jax.tree_util.tree_flatten_with_path(float32_pair["params"])[0]
    assert len(named) == len(LEAVES) == 15
    attn = float32_pair["params"]["layers"]["attn"]
    assert attn["wg"].shape == (4, 64, 1) and attn["q_norm"].shape == attn["k_norm"].shape == (4, 16)
    assert attn["wq"].shape == (4, 64, 5 * 16) and attn["wk"].shape == (4, 64, 16)


def test_loss_matches_the_reference(float32_pair):
    p = float32_pair
    assert abs(float(p["loss"]) - float(p["want_loss"])) <= LOSS_TOLERANCE * abs(float(p["want_loss"]))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_matches_the_reference(float32_pair, leaf):
    got, want = get(float32_pair["grads"], leaf), get(float32_pair["want_grads"], leaf)
    assert float(jnp.max(jnp.abs(want))) > 0  # every leaf gets a gradient
    assert rel_l2(got, want) <= GRADIENT_TOLERANCE


def test_bfloat16_in_place_of_float32_fails_the_tolerance(float32_pair):
    p = float32_pair
    load = brumby.build(tiny("bfloat16"), jax.devices())
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p["params"])
    loss, grads = jax.jit(load.loss_and_grads)(params, p["tokens"])
    worst = max(rel_l2(get(grads, leaf), get(p["want_grads"], leaf)) for leaf in LEAVES)
    assert worst > 100 * GRADIENT_TOLERANCE
    assert abs(float(loss) - float(p["want_loss"])) > LOSS_TOLERANCE * abs(float(p["want_loss"]))


class LowerPrecisionGate(brumby.Load):
    """The load with the gate one precision down: the log-sigmoid and its
    running sum in the activation dtype, not float32."""

    def gate(self, attn, u):
        gamma = jax.nn.log_sigmoid(u @ attn["wg"].astype(self.act_dtype))
        return jnp.cumsum(gamma, axis=1)


def test_the_configured_load_is_near_and_a_lower_precision_gate_is_not():
    """The load as configured (bfloat16 activations, the gate, the weights and
    their sum in float32) against the reference on the same bfloat16
    parameters, at 192 positions: the loss within 1e-3 and the gate
    projection's gradient at a cosine of 0.97 and more (read 1.7e-4 to 3.0e-4
    and 0.992 to 0.997 over three seeds).  With the gate in bfloat16 a
    running sum near 130 is kept to the nearest 1, so a decay is off by up
    to e: the same gradient reads a cosine of 0.57 to 0.85, and fails."""
    cfg = tiny("bfloat16", sequence_length=192)
    load = brumby.build(cfg, jax.devices())
    params = load.init_state(13)["params"]
    tokens = load.token_pool(13, 1)[0]
    want_loss, want_grads = reference_loss_and_grads(cfg, params, tokens)
    want_gate = want_grads["layers"]["attn"]["wg"]
    loss, grads = jax.jit(load.loss_and_grads)(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= 1e-3 * abs(float(want_loss))
    assert cosine(grads["layers"]["attn"]["wg"], want_gate) >= 0.97
    _, low = jax.jit(LowerPrecisionGate(cfg, jax.devices()).loss_and_grads)(params, tokens)
    assert cosine(low["layers"]["attn"]["wg"], want_gate) < 0.9


# ------------------------------------------------- the two forms of the layer


@pytest.mark.parametrize("seed", [0, 1])
def test_the_quadratic_form_is_the_recurrence(seed):
    """One head, 24 positions, head size 8: the weights over all earlier
    positions against the state of fixed size carried from one position to
    the next, within 1e-5 of the largest output."""
    kq, kk, kv, kg = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(key, (24, 8), jnp.float32) for key in (kq, kk, kv))
    gamma = jax.nn.log_sigmoid(jax.random.normal(kg, (24,), jnp.float32))
    with jax.default_matmul_precision("highest"):
        quadratic = reference.retention_quadratic(q, k, v, gamma, 2, 1e-6)
        recurrent = reference.retention_recurrent(q, k, v, gamma, 1e-6)
    assert float(jnp.max(jnp.abs(quadratic - recurrent))) <= 1e-5 * float(jnp.max(jnp.abs(quadratic)))
    # causal, and no softmax: the first position's output is its own value
    np.testing.assert_allclose(np.asarray(quadratic[0]), np.asarray(v[0]), rtol=1e-4)


def test_the_loads_retention_is_the_references(float32_pair):
    """The block alone, all heads in one einsum against the loop over heads."""
    cfg, params = float32_pair["cfg"], float32_pair["params"]
    load = brumby.build(cfg, jax.devices())
    attn = jax.tree.map(lambda leaf: leaf[2], params["layers"]["attn"])
    u = jax.random.normal(jax.random.key(3), (2, 24, load.d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
    got = load.retention(attn, u, positions)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.retention_block(cfg, attn, row) for row in u])
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


# ------------------------------------------------------------- the shares


def test_the_head_shares_of_a_layer_add_up_to_the_uncut_layer():
    """All 8 head shares of one layer, as the load computes each (5 query
    heads, their key-value head and the gate's column for it), summed, with
    the feed-forward, which every chip computes alike, counted once, against
    the reference's uncut layer (40 query heads in 8 groups)."""
    share_cfg = tiny()
    published = share_cfg["published"]
    uncut_cfg = tiny(num_attention_heads=published["num_attention_heads"],
                     num_key_value_heads=published["num_key_value_heads"])
    uncut = brumby.build(uncut_cfg, jax.devices())
    layer = jax.tree.map(lambda leaf: leaf[1], uncut.init_state(5)["params"]["layers"])
    x = jax.random.normal(jax.random.key(9), (2, 24, uncut.d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.layer_forward(uncut_cfg, layer, row) for row in x])

    load = brumby.build(share_cfg, jax.devices())
    shares = uncut.kv_heads // load.kv_heads
    assert shares == 8 and uncut.heads // load.heads == 8
    q_cols, kv_cols = load.heads * load.head_dim, load.kv_heads * load.head_dim

    def share(attn, r):
        return dict(
            attn,
            wq=attn["wq"][:, r * q_cols:(r + 1) * q_cols],
            wk=attn["wk"][:, r * kv_cols:(r + 1) * kv_cols],
            wv=attn["wv"][:, r * kv_cols:(r + 1) * kv_cols],
            wg=attn["wg"][:, r * load.kv_heads:(r + 1) * load.kv_heads],
            wo=attn["wo"][r * q_cols:(r + 1) * q_cols, :],
        )

    positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
    u = load._rms_norm(x, layer["attn_norm"])
    x1 = x + sum(load.retention(share(layer["attn"], r), u, positions) for r in range(shares))
    # what the all-reduce over the head shares leaves on every chip; then the
    # feed-forward, once
    out = x1 + load.mlp(layer["mlp"], load._rms_norm(x1, layer["mlp_norm"]))
    assert float(jnp.max(jnp.abs(out - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))
    # and one share alone is not the layer
    alone = x + load.retention(share(layer["attn"], 0), u, positions)
    assert float(jnp.max(jnp.abs(alone - x1))) > 1e-2 * float(jnp.max(jnp.abs(x1)))


def test_a_sliced_vocabularys_loss_is_the_loss_over_the_slice():
    """The load holds rows 0-127 of 1,024 and draws its ids from them: its
    loss is the uncut model's negative log-likelihood with the softmax taken
    over the slice's logits alone."""
    cfg = tiny()
    uncut_cfg = tiny(vocab_size=cfg["published"]["vocab_size"])
    uncut = brumby.build(uncut_cfg, jax.devices()).init_state(7)["params"]
    load = brumby.build(cfg, jax.devices())
    assert load.v * 8 == uncut["embed"]["tokens"].shape[0]
    sliced = dict(uncut, embed={"tokens": uncut["embed"]["tokens"][: load.v]},
                  output={"kernel": uncut["output"]["kernel"][:, : load.v]})
    tokens = load.token_pool(7, 1)[0]
    assert int(jnp.max(tokens)) < load.v
    got, _ = jax.jit(load.loss_and_grads)(sliced, tokens)
    with jax.default_matmul_precision("highest"):
        nll = []
        for row in tokens:
            logits = reference.hidden(uncut_cfg, uncut, row[:-1]) @ uncut["output"]["kernel"]
            logp = jax.nn.log_softmax(logits[:, : load.v], axis=-1)
            nll.append(jnp.mean(-jnp.take_along_axis(logp, row[1:, None], axis=-1)))
        want = float(jnp.mean(jnp.stack(nll)))
        whole = float(reference.loss(uncut_cfg, uncut, tokens))
    assert abs(float(got) - want) <= LOSS_TOLERANCE * abs(want)
    assert whole - want > 1.0  # over all 1,024 rows it is another number


# ---------------------------------------------- the state through the library


def bits(leaf):
    a = np.asarray(leaf)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.fixture(scope="module")
def resumed(tmp_path_factory):
    """The toy state in the configuration's dtypes: trained, saved at the
    rehearsal's chunk size, trained on for three steps; then restored into a
    zeroed target and trained for the same three."""
    root = tmp_path_factory.mktemp("brumby")
    cfg = tiny("bfloat16")
    load = brumby.build(cfg, jax.devices())
    step = load.step_fn()
    tokens = load.token_pool(3, 8)
    state = load.init_state(3)
    for i in range(2):
        state, _ = step(state, tokens[i])
    saved_bits = [bits(leaf) for leaf in jax.tree.leaves(state)]
    with knobs.override_max_chunk_size_bytes(cfg["assumed"]["rehearsal_chunk_size_bytes"]):
        manager = SnapshotManager(str(root / "snapshots"))
        manager.save(2, load.split(state))
        live = []
        for i in range(2, 5):
            state, loss = step(state, tokens[i])
            live.append(float(loss))
        target = load.split(load.zero_state())
        assert manager.restore_latest(target) == 2
        manifest = manager.snapshot(2).get_manifest()
    restored = load.join(target)
    restored_bits = [bits(leaf) for leaf in jax.tree.leaves(restored)]
    again = []
    for i in range(2, 5):
        restored, loss = step(restored, tokens[i])
        again.append(float(loss))
    return dict(saved_bits=saved_bits, restored_bits=restored_bits, live=live, again=again,
                manifest=manifest)


def test_the_feed_forward_leaves_were_chunked_three_rows_and_one(resumed):
    chunked = {path: e for path, e in resumed["manifest"].items() if isinstance(e, ChunkedTensorEntry)}
    assert len(chunked) == 9
    assert all(path.rsplit("/", 1)[1] in ("w_gate", "w_up", "w_down") for path in chunked)
    for entry in chunked.values():
        assert [c.sizes[0] for c in entry.chunks] == [3, 1]


def test_the_restored_state_is_the_saved_one_bit_for_bit(resumed):
    assert len(resumed["restored_bits"]) == len(resumed["saved_bits"]) == 47
    for got, want in zip(resumed["restored_bits"], resumed["saved_bits"]):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_the_three_steps_after_the_resume_give_the_uninterrupted_losses(resumed):
    assert resumed["again"] == resumed["live"]
    assert len(set(resumed["live"])) == 3 and all(np.isfinite(resumed["live"]))
