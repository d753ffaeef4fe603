"""One step's loss and a sample of its gradients, the ``lfm2_moe`` load
against its plain reference, at a configuration's own widths and batch:

    python3 chipbench/models/lfm2_moe_check.py --config chipbench/configs/lfm2-8b-a1b.json --seeds 7,8

Run by hand, on the chip, outside any window (the benchmark's runs never run
it).  Both sides read the same parameters, drawn from the seed in the
configuration's dtypes, and both take gradients of the sampled leaves alone
(one of each kind), given in float32 (the rest stay as they are, constants
of the step), so that a gradient is not rounded to the parameters' dtype
before it is compared.  The reference computes in float32 at the highest
matmul precision, in blocks so that it fits beside the parameters: one
sequence at a time (the loss is a mean over sequences of one length), each
layer recomputed in its backward pass.  The load computes the whole batch,
twice:

``float32``: the load's own code with float32 activations at the highest
matmul precision.  What is left between the two sides is the order of
operations (grouped heads against a loop over heads, shifted products
against a loop over taps, 32 expert modules under a mask against the same
under a weight, the head's loss summed a sequence at a time): the loss
within 1e-5 and each sampled gradient within 1e-2 of the reference's in
relative L2 norm, at a cosine of 0.9999 and more.  Both sides score the
router in float32 at the highest precision, so a token's choice of experts
moves only where the 4th and the 5th of 32 biased scores lie within a
float32 rounding of each other; ``routing_moved`` reports the share of
choices that did.  The load as configured has to fail these limits.

``configured``: as the timed step computes (bfloat16 activations; the
router's scores, the attention's and the head's logits in float32).  The
stream that the router scores is then rounded to bfloat16 (4e-3), which
moves a token's choice where the 4th and 5th scores lie closer than that,
and a token routed elsewhere gives a different gradient, not a slightly
different one: so the loss is held within 1e-3 and each sampled gradient to
a cosine of 0.9 with the reference's, and ``routing_moved`` is reported
beside them.  A term, a scale, a gate or the causal mask left out reads a
cosine under 0.9 in some sampled leaf or moves the loss by more.

The readings on the chip are in PERF.md (PR 33).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.models.dots3_note_check import get, has, put  # noqa: E402  (a leaf of a tree by its path)

# One leaf of each kind; a path that a configuration's tree lacks is skipped
# (the toy has three layers of eight experts).
SAMPLE = [
    ("embed_tokens",),
    ("layers", 0, "conv", "in_proj"),
    ("layers", 0, "feed_forward", "w2"),
    ("layers", 0, "operator_norm"),
    ("layers", 1, "self_attn", "q_proj"),
    ("layers", 1, "self_attn", "k_proj"),
    ("layers", 1, "self_attn", "q_layernorm"),
    ("layers", 1, "feed_forward", "gate"),
    ("layers", 1, "feed_forward", "experts", 3, "w1"),
    ("layers", 2, "conv", "conv"),
    ("layers", 2, "feed_forward", "experts", 5, "w2"),
    ("layers", 3, "ffn_norm"),
    ("layers", 4, "conv", "out_proj"),
    ("layers", 4, "feed_forward", "experts", 17, "w2"),
    ("layers", 4, "feed_forward", "experts", 30, "w3"),
    ("embedding_norm",),
]

LIMITS = {  # mode -> (loss gap, gradient relative L2, gradient cosine)
    "float32": (1e-5, 1e-2, 0.9999),
    "configured": (1e-3, None, 0.9),
}


class Sides:
    """The three programs, jitted once for however many seeds: the load as
    ``float32`` and as ``configured`` over the whole batch, the reference
    over a block of whole sequences; each ``(sampled leaves, params, tokens)
    -> ((loss, expert loads), gradients of the sampled leaves)``."""

    def __init__(self, cfg, devices, modes=("float32", "configured")):
        import jax

        from chipbench.models import lfm2_moe, lfm2_moe_reference as reference

        self.cfg = cfg
        self.load = lfm2_moe.build(cfg, devices)
        self.paths = [p for p in SAMPLE if has(self.load.abstract_state()["params"], p)]
        self.modes = {}
        for mode in modes:
            side, precision = self.load, contextlib.nullcontext
            if mode == "float32":
                side = lfm2_moe.build(dict(cfg, activation_dtype="float32"), devices)
                precision = lambda: jax.default_matmul_precision("highest")  # noqa: E731
            self.modes[mode] = (self._grad(side._loss), precision)
        self.reference = self._grad(lambda params, block: reference.loss(cfg, params, block, remat=True))

    def with_sampled(self, params, leaves):
        for p, leaf in zip(self.paths, leaves):
            params = put(params, p, leaf)
        return params

    def _grad(self, loss):
        import jax

        return jax.jit(jax.value_and_grad(
            lambda leaves, params, tokens: loss(self.with_sampled(params, leaves), tokens), has_aux=True))


def check(sides: Sides, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    load, paths = sides.load, sides.paths
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    params = jax.jit(load._init_params)(key)
    tokens = load.token_pool(seed, 1)[0]
    sampled = [get(params, p).astype(jnp.float32) for p in paths]
    moe = [i for i, (_, ffn) in enumerate(load.kinds) if ffn == "moe"]

    got_sides = {}
    for mode, (fn, precision) in sides.modes.items():
        with precision():
            (loss, loads), grads = fn(sampled, params, tokens)
        got_sides[mode] = (float(loss), [np.asarray(g) for g in grads], [np.asarray(loads[i]) for i in moe])
        del grads, loss, loads

    want_loss = 0.0
    want = [np.zeros(leaf.shape, np.float32) for leaf in sampled]
    want_loads = [0.0 for _ in moe]
    n = tokens.shape[0]
    for i in range(n):
        (l, loads), g = sides.reference(sampled, params, tokens[i:i + 1])
        want_loss += float(l) / n
        for acc, leaf in zip(want, g):
            acc += np.asarray(leaf) / n
        want_loads = [acc + np.asarray(loads[j]) for acc, j in zip(want_loads, moe)]
        del l, g, loads

    devices = load.devices
    result = {
        "ok": True,
        "seed": seed,
        "reference_loss": want_loss,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind},
        "configured_activation_dtype": sides.cfg.get("activation_dtype", "bfloat16"),
        "batch": [int(x) for x in tokens.shape],
        "sides": {},
    }
    for mode, (got_loss, got, got_loads) in got_sides.items():
        loss_tol, l2_tol, cos_tol = LIMITS[mode]
        rows = []
        for p, a, b in zip(paths, got, want):
            a, b = a.astype(np.float64).ravel(), b.astype(np.float64).ravel()
            gap = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
            cos = float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))
            rows.append({"leaf": "/".join(map(str, p)), "rel_l2": gap, "cosine": cos})
        # net: a choice that left one expert arrived at another, so half the L1 gap
        moved = [float(np.abs(a - b).sum() / 2 / b.sum()) for a, b in zip(got_loads, want_loads)]
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
    if set(got_sides) == {"float32", "configured"}:
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
    parser.add_argument("--config", default=os.path.join(ROOT, "chipbench", "configs", "lfm2-8b-a1b.json"))
    parser.add_argument("--seeds", default="7,8")
    args = parser.parse_args()
    import jax

    with open(args.config) as f:
        cfg = json.load(f)
    sides = Sides(cfg, jax.devices())
    ok = True
    for seed in [int(s) for s in args.seeds.split(",")]:
        result = check(sides, seed)
        print(json.dumps(result), flush=True)
        ok = ok and result["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
