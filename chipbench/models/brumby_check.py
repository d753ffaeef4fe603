"""One step's loss and a sample of its gradients, the ``brumby`` load against
its plain reference, at a configuration's own widths:

    python3 chipbench/models/brumby_check.py --config chipbench/configs/brumby-14b-base.json --seed 7

Run by hand, on the chip, outside any window (the benchmark's runs never run
it).  Both sides read the same parameters, drawn from the seed in the
configuration's dtypes, and both take gradients of the sampled leaves alone,
given in float32 (the rest stay as they are, constants of the step), so that
a gradient is not rounded to the parameters' dtype before it is compared.
The reference computes in float32 at the highest matmul precision, one
sequence at a time (the loss is a mean over sequences of one length).  The
load computes the whole batch, its layers under ``scan`` and rematerialised,
twice:

``float32``: the load's own code with float32 activations at the highest
matmul precision.  What is left between the two sides is the order of
operations: the loss within 1e-5 and each sampled gradient within 3e-2 of the
reference's in relative L2 norm, at a cosine of 0.999 and more.  The gradient
limit is loose for a reason: with no softmax a position's weights are squares
of scores, and where the few scores within the gate's reach are all near zero
the normalised weights move by as much as they are under a rounding of a
score, so single rows dominate a leaf's error and its size depends on the
seed (on the chip at the real widths: 3.1e-4 on one seed and 2.9e-3 on
another below the head, 6e-6 to 1e-5 at it, where a softmax layer reads 1e-5
throughout; PERF.md, PR 31).  The limit lies a factor of ten over the larger
reading and sixteen under what the load as configured reads (0.50-0.58),
which has to fail it.

``configured``: as the timed step computes (bfloat16 activations; the gate,
the retention's weights and their sum, and the head's logits in float32).
The loss is held within 1e-3 (read: 1e-5) and each sampled gradient to a
cosine of 0.7 with the reference's (read: 0.82 to 0.995 below the head,
0.9999 at it, for the reason above); the readings are reported.  A term, a
scale, the gate or the causal mask left out still reads under 0.7 in some
sampled leaf, or moves the loss by more.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.models.dots3_note_check import get, put  # noqa: E402  (a leaf of a tree by its path, and a copy with one replaced)

# One leaf of each kind (the layers' leaves are stacked: all four layers at once).
SAMPLE = [
    ("embed", "tokens"),
    ("layers", "attn", "wq"),
    ("layers", "attn", "wk"),
    ("layers", "attn", "wv"),
    ("layers", "attn", "wo"),
    ("layers", "attn", "wg"),
    ("layers", "attn", "q_norm"),
    ("layers", "attn", "k_norm"),
    ("layers", "mlp", "w_up"),
    ("layers", "attn_norm"),
    ("layers", "mlp_norm"),
    ("final_norm",),
    ("output", "kernel"),
]

LIMITS = {  # mode -> (loss gap, gradient relative L2, gradient cosine)
    "float32": (1e-5, 3e-2, 0.999),
    "configured": (1e-3, None, 0.7),
}


def with_sampled(params, leaves):
    tree = params
    for p, leaf in zip(SAMPLE, leaves):
        tree = put(tree, p, leaf)
    return tree


def load_side(cfg, devices, mode):
    """``(leaves, params, tokens) -> (loss, gradients of leaves)`` of the load
    in ``mode``, jitted, and the precision to call it under."""
    import contextlib

    import jax

    from chipbench.models import brumby

    if mode == "float32":
        side = brumby.build(dict(cfg, activation_dtype="float32"), devices)
        precision = jax.default_matmul_precision("highest")
    else:
        side = brumby.build(cfg, devices)
        precision = contextlib.nullcontext()
    fn = jax.jit(
        jax.value_and_grad(lambda leaves, params, tokens: side._loss(with_sampled(params, leaves), tokens))
    )
    return fn, precision


def reference_side(cfg):
    """The same of the reference, for a block of whole sequences."""
    import jax

    from chipbench.models import brumby_reference as reference

    return jax.jit(
        jax.value_and_grad(lambda leaves, params, block: reference.loss(cfg, with_sampled(params, leaves), block))
    )


def check(cfg, devices, seed, modes=("float32", "configured")):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.models import brumby

    load = brumby.build(cfg, devices)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    params = jax.jit(load._init_params)(key)
    tokens = load.token_pool(seed, 1)[0]
    sampled = [get(params, p).astype(jnp.float32) for p in SAMPLE]

    sides = {}
    for mode in modes:
        fn, precision = load_side(cfg, devices, mode)
        with precision:
            loss, grads = fn(sampled, params, tokens)
        sides[mode] = (float(loss), [np.asarray(g) for g in grads])
        del grads, loss, fn

    block_grad = reference_side(cfg)
    want_loss = 0.0
    want = [np.zeros(leaf.shape, np.float32) for leaf in sampled]
    n = tokens.shape[0]
    for i in range(n):
        l, g = block_grad(sampled, params, tokens[i:i + 1])
        want_loss += float(l) / n
        for acc, leaf in zip(want, g):
            acc += np.asarray(leaf) / n
        del l, g

    result = {
        "ok": True,
        "seed": seed,
        "reference_loss": want_loss,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind},
        "configured_activation_dtype": cfg.get("activation_dtype", "bfloat16"),
        "sides": {},
    }
    for mode, (got_loss, got) in sides.items():
        loss_tol, l2_tol, cos_tol = LIMITS[mode]
        rows = []
        for p, a, b in zip(SAMPLE, got, want):
            a, b = a.astype(np.float64).ravel(), b.astype(np.float64).ravel()
            gap = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
            cos = float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))
            rows.append({"leaf": "/".join(p), "rel_l2": gap, "cosine": cos})
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
    parser.add_argument("--config", default=os.path.join(ROOT, "chipbench", "configs", "brumby-14b-base.json"))
    parser.add_argument("--seed", type=int, default=7)
    args = parser.parse_args()
    import jax

    with open(args.config) as f:
        cfg = json.load(f)
    result = check(cfg, jax.devices(), args.seed)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
