"""One step's loss and a sample of its gradients, the ``dots3_note`` load
against its plain reference, at a configuration's own widths:

    python3 chipbench/models/dots3_note_check.py --config chipbench/configs/dots3-note-prev.json --seed 7

Run by hand, on the chip, outside any window (the benchmark's runs never run
it).  The reference computes in float32 at the highest matmul precision, in
blocks so that it fits beside the parameters: one sequence at a time (the
loss is a mean over sequences of one length) and gradients only of the
sampled leaves.  Both sides read the same parameters, drawn from the seed in
the configuration's dtypes.  The load computes the whole batch with its
layers rematerialised, twice:

``float32``: the load's own code with float32 activations at the highest
matmul precision.  What is left between the two sides is the order of
operations: the loss within 1e-5 and each sampled gradient within 1e-2 of
the reference's in relative L2 norm (read: 1e-7 and 1e-4 to 3e-3; PERF.md,
PR 27).  The load as configured fails both by a wide margin, as it should.

``configured``: as the timed step computes (bfloat16 activations).  A
rounding of 4e-3 moves a token's choice of experts where the 8th and the 9th
of 256 scores lie closer than that, and a token routed elsewhere gives a
different gradient, not a slightly different one: so the loss is held within
1e-3 and each sampled gradient to a cosine of 0.9 with the reference's, and
the share of routing choices that moved is reported beside them
(``routing_moved``).  A term, a scale or a mask left out reads a cosine
under 0.9 in some sampled leaf or moves the loss by more.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# One leaf of each kind: (path into params).  A path that a configuration's
# tree lacks is skipped.
SAMPLE = [
    ("embed", "tokens"),
    ("layers", 0, "attn", "w_qa"),
    ("layers", 0, "attn", "w_o"),
    ("layers", 0, "indexer", "w_q"),
    ("layers", 0, "indexer", "k_norm_bias"),
    ("layers", 0, "mlp", "w_down"),
    ("layers", 1, "attn", "w_kvb"),
    ("layers", 1, "moe", "router", "kernel"),
    ("layers", 1, "moe", "experts", "w_gate"),
    ("layers", 1, "moe", "shared", "w_up"),
    ("layers", 2, "attn", "q_norm"),
    ("layers", 3, "attn", "w_g"),
    ("layers", 3, "attn", "w_qb"),
    ("layers", 4, "moe", "experts", "w_down"),
    ("layers", 4, "ffn_norm"),
    ("output", "kernel"),
]


def get(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def put(tree, path, value):
    """A copy of ``tree`` (dicts and lists) with ``value`` at ``path``."""
    if not path:
        return value
    key = path[0]
    if isinstance(tree, list):
        return [put(v, path[1:], value) if i == key else v for i, v in enumerate(tree)]
    return {k: (put(v, path[1:], value) if k == key else v) for k, v in tree.items()}


def has(tree, path):
    try:
        get(tree, path)
        return True
    except (KeyError, IndexError, TypeError):
        return False


LIMITS = {  # mode -> (loss gap, gradient relative L2, gradient cosine)
    "float32": (1e-5, 1e-2, 0.9999),
    "configured": (1e-3, None, 0.9),
}


def check(cfg, devices, seed, modes=("float32", "configured")):
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.models import dots3_note, dots3_note_reference as reference

    load = dots3_note.build(cfg, devices)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    params = jax.jit(load._init_params)(key)
    tokens = load.token_pool(seed, 1)[0]
    paths = [p for p in SAMPLE if has(params, p)]

    sides = {}
    for mode in modes:
        side = load
        precision = contextlib.nullcontext()
        if mode == "float32":
            side = dots3_note.build(dict(cfg, activation_dtype="float32"), devices)
            precision = jax.default_matmul_precision("highest")
        with precision:
            loss, grads, loads = jax.jit(side.loss_and_grads)(params, tokens)
        sides[mode] = (
            float(loss),
            [np.asarray(get(grads, p).astype(jnp.float32)) for p in paths],
            [np.asarray(x) for x in loads],
        )
        del grads, loss, loads
    got = sides[modes[0]][1]

    def block_loss(sampled, rest, block):
        tree = rest
        for p, leaf in zip(paths, sampled):
            tree = put(tree, p, leaf)
        loss, loads = reference.loss(cfg, tree, block)
        return loss, [x for x in loads if x is not None]

    sampled = [get(params, p).astype(jnp.float32) for p in paths]
    block_grad = jax.jit(jax.value_and_grad(block_loss, has_aux=True))
    want_loss = 0.0
    want = [np.zeros(g.shape, np.float32) for g in got]
    want_loads = None
    n = tokens.shape[0]
    for i in range(n):
        (l, loads), g = block_grad(sampled, params, tokens[i:i + 1])
        want_loss += float(l) / n
        for acc, leaf in zip(want, g):
            acc += np.asarray(leaf) / n
        loads = [np.asarray(x) for x in loads]
        want_loads = loads if want_loads is None else [a + b for a, b in zip(want_loads, loads)]

    result = {
        "ok": True,
        "seed": seed,
        "reference_loss": want_loss,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind},
        "configured_activation_dtype": cfg.get("activation_dtype", "bfloat16"),
        "sides": {},
    }
    moe = [i for i, (_, ffn) in enumerate(load.kinds) if ffn == "moe"]
    for mode, (got_loss, got, got_loads) in sides.items():
        loss_tol, l2_tol, cos_tol = LIMITS[mode]
        rows = []
        for p, a, b in zip(paths, got, want):
            a, b = a.astype(np.float64).ravel(), b.astype(np.float64).ravel()
            gap = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
            cos = float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))
            rows.append({"leaf": "/".join(map(str, p)), "rel_l2": gap, "cosine": cos})
        # net: a choice that left one expert arrived at another, so half the L1 gap
        moved = [
            float(np.abs(got_loads[i] - ref).sum() / 2 / ref.sum()) for i, ref in zip(moe, want_loads)
        ]
        loss_gap = abs(got_loss - want_loss) / abs(want_loss)
        ok = (
            loss_gap <= loss_tol
            and all(r["cosine"] >= cos_tol for r in rows)
            and (l2_tol is None or all(r["rel_l2"] <= l2_tol for r in rows))
        )
        result["sides"][mode] = {
            "ok": bool(ok),
            "loss": got_loss,
            "loss_gap": loss_gap,
            "limits": {"loss_gap": loss_tol, "rel_l2": l2_tol, "cosine": cos_tol},
            "worst_rel_l2": max(r["rel_l2"] for r in rows),
            "worst_cosine": min(r["cosine"] for r in rows),
            "routing_moved": moved,
            "gradients": rows,
        }
        result["ok"] = result["ok"] and bool(ok)
    if set(modes) == {"float32", "configured"}:
        # the tight limits have to refuse the lower precision
        c = result["sides"]["configured"]
        tight = LIMITS["float32"]
        result["configured_fails_the_float32_limits"] = bool(
            c["loss_gap"] > tight[0] or c["worst_rel_l2"] > tight[1]
        )
        result["ok"] = result["ok"] and result["configured_fails_the_float32_limits"]
    return result


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default=os.path.join(ROOT, "chipbench", "configs", "dots3-note-prev.json"))
    parser.add_argument("--seed", type=int, default=7)
    args = parser.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    with open(args.config) as f:
        cfg = json.load(f)
    result = check(cfg, jax.devices(), args.seed)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
