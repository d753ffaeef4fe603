"""One block-diffusion step's loss and a sample of its gradients, the
``sdar_moe`` load against its plain reference, at a configuration's own
widths, batch and noise:

    python3 chipbench/models/sdar_moe_check.py --config chipbench/configs/sdar-30b-a3b-chat.json --seeds 7,8

Run by hand, on the chip, outside any window (the benchmark's runs never run
it).  Both sides read the same parameters, drawn from the seed in the
configuration's dtypes, the same clean tokens and the same noise key (the
state's ``noise_key`` from that seed, split as the step splits it), and both
take gradients of the sampled leaves alone (one of each kind), given in
float32 (the rest stay as they are, constants of the step), so that a
gradient is not rounded to the parameters' dtype before it is compared.  The
reference computes in float32 at the highest matmul precision over the
whole batch (the noise is drawn for the batch's shape; the cell's batch is
one sequence of 4,096 clean tokens, 8,192 positions), in blocks so that it
fits beside the parameters: each layer, and each head inside it, recomputed
in its backward pass, under the whole mask.  The load computes the batch
twice, with ``lfm2_moe_check``'s limits and for its reasons:

``float32``: the load's own code with float32 activations at the highest
matmul precision.  What is left between the two sides is the order of
operations (blocks of queries over clipped keys against whole masks, grouped
heads against a loop over heads, an expert's weight applied before its last
product against after it, the head's loss in blocks): the loss within 1e-5
and each sampled gradient within 1e-2 of the reference's in relative L2 norm,
at a cosine of 0.9999 and more.  Both sides score the router in float32 at
the highest precision, so a position's choice of experts moves only where
the 8th and the 9th of 128 probabilities lie within a float32 rounding of
each other; ``routing_moved`` reports the share of choices that did.  The
load as configured has to fail these limits.

``configured``: as the timed step computes (bfloat16 activations; the
router's probabilities, the attention's and the head's logits in float32).
The stream the router scores is then rounded to bfloat16, which moves a
position's choice where the 8th and 9th probabilities lie closer than that,
and a position routed elsewhere gives a different gradient, not a slightly
different one: so the loss is held within 1e-3 and each sampled gradient to
a cosine of 0.9 with the reference's, ``routing_moved`` beside them.  A term,
a norm, the 1/t weight, a block of the mask or the balance term left out
reads a cosine under 0.9 in some sampled leaf or moves the loss by more.

The readings on the chip are in PERF.md (section 6).
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
from chipbench.models.lfm2_moe_check import LIMITS  # noqa: E402  (the same limits, for the same reasons)

# One leaf of each kind, first and last layers; a path a configuration's tree
# lacks is skipped (the toy has two layers of four experts).
SAMPLE = [
    ("embed_tokens",),
    ("layers", 0, "input_layernorm"),
    ("layers", 0, "self_attn", "q_proj"),
    ("layers", 0, "self_attn", "k_norm"),
    ("layers", 0, "mlp", "gate"),
    ("layers", 0, "mlp", "experts", 3, "gate_proj"),
    ("layers", 1, "self_attn", "v_proj"),
    ("layers", 1, "post_attention_layernorm"),
    ("layers", 1, "mlp", "experts", 0, "down_proj"),
    ("layers", 5, "self_attn", "k_proj"),
    ("layers", 5, "self_attn", "q_norm"),
    ("layers", 5, "mlp", "experts", 11, "up_proj"),
    ("layers", 11, "self_attn", "o_proj"),
    ("layers", 11, "mlp", "gate"),
    ("layers", 11, "mlp", "experts", 15, "down_proj"),
    ("norm",),
    ("lm_head",),
]


class Sides:
    """The three programs, jitted once for however many seeds: the load as
    ``float32`` and as ``configured``, and the reference, each over the
    whole batch; each ``(sampled leaves, params, tokens, key) ->
    ((loss, expert loads), gradients of the sampled leaves)``."""

    def __init__(self, cfg, devices, modes=("float32", "configured")):
        import jax

        from chipbench.models import sdar_moe, sdar_moe_reference as reference

        self.cfg = cfg
        self.load = sdar_moe.build(cfg, devices)
        self.paths = [p for p in SAMPLE if has(self.load.abstract_state()["params"], p)]
        self.modes = {}
        for mode in modes:
            side, precision = self.load, contextlib.nullcontext
            if mode == "float32":
                side = sdar_moe.build(dict(cfg, activation_dtype="float32"), devices)
                precision = lambda: jax.default_matmul_precision("highest")  # noqa: E731
            self.modes[mode] = (self._grad(side._loss), precision)
        self.reference = self._grad(
            lambda params, tokens, key: reference.loss(cfg, params, tokens, key, remat=True))

    def with_sampled(self, params, leaves):
        for p, leaf in zip(self.paths, leaves):
            params = put(params, p, leaf)
        return params

    def _grad(self, loss):
        import jax

        return jax.jit(jax.value_and_grad(
            lambda leaves, params, tokens, key: loss(self.with_sampled(params, leaves), tokens, key),
            has_aux=True))


def check(sides: Sides, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    load, paths = sides.load, sides.paths
    # the parameters and the noise key of the seed's state, as ``Load._build``
    # draws them, and the noise of the step taken from it, as ``_train_step``
    k_params, k_noise = jax.random.split(jax.random.key(np.uint32(seed & 0xFFFFFFFF)))
    params = jax.jit(load._init_params)(k_params)
    key = jax.random.split(k_noise)[1]
    tokens = load.token_pool(seed, 1)[0]
    sampled = [get(params, p).astype(jnp.float32) for p in paths]

    got_sides = {}
    for mode, (fn, precision) in sides.modes.items():
        with precision():
            (loss, loads), grads = fn(sampled, params, tokens, key)
        got_sides[mode] = (float(loss), [np.asarray(g) for g in grads], [np.asarray(x) for x in loads])
        del grads, loss, loads

    # the whole batch at once: the noise is drawn for the batch's shape
    (want_loss, want_loads), want = sides.reference(sampled, params, tokens, key)
    want_loss = float(want_loss)
    want = [np.asarray(g) for g in want]
    want_loads = [np.asarray(x) for x in want_loads]

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
            if not a.any() and not b.any():
                # a last layer's expert that no masked position chose: no gradient on either side
                rows.append({"leaf": "/".join(map(str, p)), "rel_l2": 0.0, "cosine": 1.0, "zero": True})
                continue
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
    parser.add_argument("--config", default=os.path.join(ROOT, "chipbench", "configs", "sdar-30b-a3b-chat.json"))
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
