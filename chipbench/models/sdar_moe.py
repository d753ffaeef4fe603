"""The benchmark's load for ``sdar_moe`` configurations: one chip's share of a
mixture-of-experts decoder trained by block diffusion, with AdamW.

The layer is Qwen3-MoE's (SDAR is converted from it): grouped-query attention
with per-head query and key norms and the rotation, then 8 of 128 experts a
token, chosen by softmax scores and renormalised over the chosen, no shared
expert, every layer an expert layer.  Block diffusion changes the input, the
mask and the loss, not the layer: the clean sequence ``x_0`` is cut into
blocks of ``block_length``; each block gets a noise level ``t`` and each of
its positions is masked with probability ``t``, giving ``x_t``; the model
reads ``[x_t ; x_0]`` (2L positions) under the block-diffusion mask and is
scored on the noised half against ``x_0``, each masked position weighted by
``1/t``.  The noise is drawn from a key that is part of the train state
(``noise_key``, uint32 key data), and the app state hands that key to the
library as an ``RNGState``: a resumed step draws the live job's noise only if
the library restored it.

What the chip holds is what one chip of an expert-parallel deployment holds:
``num_experts`` of the published experts (the router keeps its published
width and its experts a token), ``vocab_size`` rows of the vocabulary for the
embedding and the untied head, attention and the router whole.  The chip
computes its own experts' part of each expert layer; what absent experts
would add is left out, here and in ``sdar_moe_reference.py`` alike.

Like ``dense_decoder.py`` this is traffic generation: the leaves, their sizes
and dtypes and how they are split into statefuls are the work of a cell.  The
tree mirrors the public module tree: layers a list, ``self_attn``,
``mlp.gate`` (the router) and ``mlp.experts[e].{gate_proj, up_proj,
down_proj}``, one leaf an expert matrix.  Shared, and not edited here:
``dense_decoder``'s surface, norm and rotation, ``dots3_note``'s ``Spec`` and
seeded draw, ``lfm2_moe``'s single zeroing program and its expert loop
(``experts_forward``: each held expert over every position under its combine
weight, rematerialised one at a time).  The equations are in the reference's
docstring; every departure or inference is listed under ``assumed`` in the
configuration's file.
"""

from __future__ import annotations

import functools
import gc
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from chipbench.models import dense_decoder, lfm2_moe
from chipbench.models.dots3_note import Spec


def visible(q_at, k_at, length: int, block: int):
    """The block-diffusion mask over positions of ``[x_t ; x_0]`` (``0 ..
    length - 1`` noised, ``length .. 2 length - 1`` clean): ``[queries,
    keys]``, true where the query reads the key.  A noised query of block
    ``b`` reads the noised keys of block ``b`` and the clean keys of blocks
    before ``b``; a clean query of block ``b`` reads the clean keys of blocks
    up to ``b``.  ``q_at`` and ``k_at`` are integer arrays (numpy or jax)."""
    q_clean, k_clean = (q_at >= length)[:, None], (k_at >= length)[None, :]
    qb, kb = ((q_at % length) // block)[:, None], ((k_at % length) // block)[None, :]
    noised = (~k_clean & (kb == qb)) | (k_clean & (kb < qb))
    clean = k_clean & (kb <= qb)
    return (q_clean & clean) | (~q_clean & noised)


class Load(lfm2_moe.Load):
    """The surface of ``dense_decoder.Load`` and the code of it, and of
    ``lfm2_moe.Load``, that does not know the architecture; the parameters,
    the noise, the model and the train step are this file's."""

    def __init__(self, cfg: Dict[str, Any], devices: Sequence[Any]) -> None:
        # Not the parents': they read their own architectures' keys.
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.cfg = cfg
        self.d = int(cfg["hidden_size"])
        self.v = int(cfg["vocab_size"])
        self.layers = int(cfg["num_hidden_layers"])
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.head_dim = int(cfg["head_dim"])
        # The router's width is the published count; the expert modules are
        # those that live here, the first of them being ``first_expert``.
        self.experts_here = int(cfg["num_experts"])
        self.experts = int(cfg.get("published", {}).get("num_experts", self.experts_here))
        self.top_k = int(cfg["num_experts_per_tok"])
        assumed = cfg["assumed"]
        self.first_expert = int(assumed["first_expert"])
        self.aux_coef = float(assumed["router_aux_loss_coef"])
        self.block = int(assumed["block_length"])
        self.noise_eps = float(assumed["noise_eps"])
        self.query_block = int(assumed["query_block"])
        self.loss_block = int(assumed["loss_block"])
        self.gc_freeze = bool(assumed.get("gc_freeze", False))
        self.mask_id = self.v - 1  # the slice's last row; clean ids are the others
        self.param_dtype = jnp.dtype(cfg["state_dtypes"]["params"])
        self.act_dtype = jnp.dtype(cfg.get("activation_dtype", "bfloat16"))
        self.batch = int(assumed["batch_sequences"])
        self.seq = int(assumed["sequence_length"])
        if self.seq % self.block or min(self.query_block, self.seq) % self.block:
            raise ValueError("sequence_length and query_block must be multiples of block_length")
        if self.seq % min(self.query_block, self.seq):
            raise ValueError("query_block must divide sequence_length")
        self.opt = optax.adamw(
            float(assumed["learning_rate"]),
            b1=float(assumed["adam_b1"]),
            b2=float(assumed["adam_b2"]),
            eps=float(assumed["adam_eps"]),
            weight_decay=float(assumed["weight_decay"]),
        )
        self.devices = list(devices[:1])
        self.sharding = NamedSharding(Mesh(np.array(self.devices), ("d",)), P())
        self._abstract = jax.eval_shape(self._build, jax.random.key(0))
        self._step = None
        self._stepped = None
        self._zero = None

    # ------------------------------------------------------------- the state

    def param_specs(self) -> Dict[str, Any]:
        d = self.d
        s = 1.0 / np.sqrt(d)
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        width = int(self.cfg["moe_intermediate_size"])

        def expert():
            return {
                "gate_proj": Spec((d, width), s),
                "up_proj": Spec((d, width), s),
                "down_proj": Spec((width, d), 1.0 / np.sqrt(width)),
            }

        layers = [
            {
                "input_layernorm": Spec((d,), "ones"),
                "self_attn": {
                    "q_proj": Spec((d, q), s),
                    "k_proj": Spec((d, kv), s),
                    "v_proj": Spec((d, kv), s),
                    "o_proj": Spec((q, d), 1.0 / np.sqrt(q)),
                    "q_norm": Spec((self.head_dim,), "ones"),
                    "k_norm": Spec((self.head_dim,), "ones"),
                },
                "post_attention_layernorm": Spec((d,), "ones"),
                # One module an expert, one leaf a matrix: never a bank.
                "mlp": {
                    "gate": Spec((d, self.experts), s),
                    "experts": [expert() for _ in range(self.experts_here)],
                },
            }
            for _ in range(self.layers)
        ]
        return {
            "embed_tokens": Spec((self.v, d), 1.0),
            "layers": layers,
            "norm": Spec((d,), "ones"),
            "lm_head": Spec((self.v, d), s),
        }

    def _build(self, key):
        import jax
        import jax.numpy as jnp

        k_params, k_noise = jax.random.split(key)
        params = self._init_params(k_params)
        return {
            "params": params,
            "opt_state": self.opt.init(params),
            "step": jnp.zeros((), jnp.int32),
            "noise_key": jax.random.key_data(k_noise),
        }

    def split(self, state):
        """``dense_decoder``'s four statefuls and the noise key as the
        library's ``RNGState``, which ``restore`` loads last."""
        import jax

        from torchsnapshot_tpu import RNGState

        app_state = dense_decoder.Load.split(self, state)
        app_state["rng"] = RNGState(jax_key=jax.random.wrap_key_data(state["noise_key"]))
        return app_state

    def join(self, app_state):
        import jax

        state = dense_decoder.Load.join(self, app_state)
        key_data = jax.random.key_data(app_state["rng"].jax_key)
        state["noise_key"] = jax.device_put(key_data, self.sharding)
        return state

    def token_pool(self, seed: int, n: int):
        """``n`` batches of clean token ids on the device, from the seed,
        drawn from every row of the slice but the mask's."""
        import jax
        import jax.numpy as jnp

        key = jax.random.fold_in(jax.random.key(np.uint32(seed & 0xFFFFFFFF)), 7)
        make = jax.jit(
            lambda k: jax.random.randint(
                k, (n, self.batch, self.seq), 0, self.mask_id, dtype=jnp.int32
            ),
            out_shardings=self.sharding,
        )
        return make(key)

    def step_fn(self):
        """The compiled, donating train step.  Where the configuration's
        ``gc_freeze`` says so, the trainer's heap as the first step leaves it
        (the step's traced program and all that warm-up built) is collected
        once and frozen out of the collector's later walks."""
        if self._stepped is None:
            step = super().step_fn()
            if not self.gc_freeze:
                self._stepped = step
            else:
                frozen = []

                def stepped(state, tokens):
                    out = step(state, tokens)
                    if not frozen:
                        frozen.append(True)
                        gc.collect()
                        gc.freeze()
                    return out

                self._stepped = stepped
        return self._stepped

    # ------------------------------------------------------------- the noise

    def noise(self, key, tokens):
        """``(x_t, masked, t)`` of clean ``tokens`` ``[b, L]``: the key split
        in two, ``t ~ U(noise_eps, 1)`` a block from the first, each position
        masked where a ``U(0, 1)`` draw from the second is under its block's
        ``t``; ``t`` is given a position."""
        import jax
        import jax.numpy as jnp

        b, length = tokens.shape
        k_t, k_m = jax.random.split(key)
        t = jax.random.uniform(k_t, (b, length // self.block), jnp.float32, self.noise_eps, 1.0)
        t = jnp.repeat(t, self.block, axis=1)
        masked = jax.random.uniform(k_m, (b, length), jnp.float32) < t
        return jnp.where(masked, self.mask_id, tokens), masked, t

    # ------------------------------------------------------------- the model

    def query_blocks(self, length: int) -> List[Tuple[int, int, List[Tuple[int, int]]]]:
        """The blocks of queries of ``[x_t ; x_0]`` and the key ranges each
        reads, clipped statically to what the mask lets it see: ``(first
        query, queries, [(first key, keys), ...])``.  A noised block reads its
        own span of noised keys and the clean keys before its end; a clean
        block the clean keys up to its end."""
        size = min(self.query_block, length)
        noised = [(lo, size, [(lo, size), (length, lo + size)]) for lo in range(0, length, size)]
        clean = [(length + lo, size, [(length, lo + size)]) for lo in range(0, length, size)]
        return noised + clean

    def _attend_block(self, qb, kb, vb, q_first, key_ranges, length):
        """One block of queries over its keys, the mask built from the
        positions; logits and softmax in float32."""
        import jax
        import jax.numpy as jnp

        q_at = q_first + jnp.arange(qb.shape[1])
        k_at = jnp.concatenate([first + jnp.arange(n) for first, n in key_ranges])
        keep = visible(q_at, k_at, length, self.block)
        logits = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qb, kb, preferred_element_type=jnp.float32
        ) / np.sqrt(self.head_dim)
        logits = jnp.where(keep[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(self.act_dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, vb)

    def attention(self, p, a, positions):
        """Grouped-query attention of the normed stream ``a`` ``[b, 2L,
        hidden]`` under the block-diffusion mask, through ``o_proj``: queries
        and keys normed per head, then rotated at ``positions``; each block
        of queries rematerialised, so the float32 scores never exist whole."""
        import jax
        import jax.numpy as jnp

        dt = self.act_dtype
        b, n = a.shape[:2]
        length, hd = n // 2, self.head_dim
        rep = self.heads // self.kv_heads
        q = (a @ p["q_proj"].astype(dt)).reshape(b, n, self.heads, hd)
        k = (a @ p["k_proj"].astype(dt)).reshape(b, n, self.kv_heads, hd)
        v = (a @ p["v_proj"].astype(dt)).reshape(b, n, self.kv_heads, hd)
        q = self._rope(self._rms_norm(q, p["q_norm"]), positions)
        k = self._rope(self._rms_norm(k, p["k_norm"]), positions)
        # query head g * rep + r reads key-value head g
        q = q.reshape(b, n, self.kv_heads, rep, hd)
        outs = []
        for first, size, key_ranges in self.query_blocks(length):
            kb = jnp.concatenate([k[:, lo:lo + m] for lo, m in key_ranges], axis=1)
            vb = jnp.concatenate([v[:, lo:lo + m] for lo, m in key_ranges], axis=1)
            one = functools.partial(
                self._attend_block, q_first=first, key_ranges=key_ranges, length=length
            )
            outs.append(jax.checkpoint(one)(q[:, first:first + size], kb, vb))
        out = jnp.concatenate(outs, axis=1).reshape(b, n, self.heads * hd)
        return out @ p["o_proj"].astype(dt)

    def _swiglu(self, p, h, weight=None):
        import jax

        dt = self.act_dtype
        hidden = jax.nn.silu(h @ p["gate_proj"].astype(dt)) * (h @ p["up_proj"].astype(dt))
        if weight is not None:
            hidden = hidden * weight[:, None]
        return hidden @ p["down_proj"].astype(dt)

    def route(self, mlp, h):
        """The softmax router over all published experts, in float32 at the
        highest precision: each position's combine weight for each expert
        held here ``[positions, experts_here]`` (0 where it did not choose
        it), and beside it ``(load, aux)``: how many positions chose each
        published expert, and the layer's balance term ``coef * experts *
        sum_e f_e P_e`` (``f_e`` the share of the (position, choice) pairs
        routed to ``e``, ``P_e`` the mean probability of ``e``)."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("router"):
            x = h.reshape(-1, self.d).astype(jnp.float32)
            p = jax.nn.softmax(
                jnp.dot(x, mlp["gate"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST),
                axis=-1,
            )
            w, chosen = jax.lax.top_k(p, self.top_k)
            if self.cfg.get("norm_topk_prob"):
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            here = self.first_expert + jnp.arange(self.experts_here)
            combine = jnp.sum(
                jnp.where(chosen[:, :, None] == here[None, None, :], w[:, :, None], 0.0), axis=1
            )
            load = jnp.sum(jax.nn.one_hot(chosen, self.experts, dtype=jnp.float32), axis=(0, 1))
            share = load / chosen.size
            aux = self.aux_coef * self.experts * jnp.sum(share * jnp.mean(p, axis=0))
            return combine, (load, aux)

    def _layer(self, layer, x, positions):
        import jax

        with jax.named_scope("block_diffusion_attention"):
            a = self._rms_norm(x, layer["input_layernorm"])
            x = x + self.attention(layer["self_attn"], a, positions)
        h = self._rms_norm(x, layer["post_attention_layernorm"])
        with jax.named_scope("routed_experts"):
            routed, (load, aux) = self.experts_forward(layer["mlp"], h)
        return x + routed, load, aux

    def stream(self, params, z, positions):
        """The stream of ``z`` ``[b, 2L]`` after the final norm, each layer's
        expert loads, and the summed balance term."""
        import jax
        import jax.numpy as jnp

        x = params["embed_tokens"].astype(self.act_dtype)[z]
        loads, aux = [], jnp.zeros((), jnp.float32)
        for layer in params["layers"]:
            x, load, layer_aux = jax.checkpoint(self._layer)(layer, x, positions)
            loads.append(load)
            aux = aux + layer_aux
        return self._rms_norm(x, params["norm"]), loads, aux

    def _head_loss(self, head, x, targets, weight):
        """``sum_i weight_i * -log p(targets_i)`` over the head's rows, in
        blocks of positions, each rematerialised: the float32 logits live for
        one block."""
        import jax
        import jax.numpy as jnp

        table = head.astype(self.act_dtype)
        x, targets, weight = x.reshape(-1, self.d), targets.reshape(-1), weight.reshape(-1)
        size = min(self.loss_block, x.shape[0])
        n = -(-x.shape[0] // size)
        pad = n * size - x.shape[0]
        x = jnp.pad(x, ((0, pad), (0, 0))).reshape(n, size, self.d)
        targets = jnp.pad(targets, (0, pad)).reshape(n, size)
        weight = jnp.pad(weight, (0, pad)).reshape(n, size)

        def one(args):
            rows, want, w = args
            logits = jnp.einsum("td,vd->tv", rows, table, preferred_element_type=jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.sum(w * jnp.take_along_axis(logp, want[:, None], axis=-1)[:, 0])

        return jnp.sum(jax.lax.map(jax.checkpoint(one), (x, targets, weight)))

    def _loss(self, params, tokens, key):
        """The block-diffusion loss of clean ``tokens`` ``[b, L]`` under the
        noise ``key`` draws: ``(1 / bL) sum_i m_i / t_i * -log p(x_0[i])``
        over the noised half (position ``i`` predicts ``x_0[i]``, no shift),
        plus the layers' balance terms; beside it, each layer's loads."""
        import jax
        import jax.numpy as jnp

        b, length = tokens.shape
        with jax.named_scope("noise"):
            x_t, masked, t = self.noise(key, tokens)
            weight = jnp.where(masked, 1.0 / t, 0.0)
        z = jnp.concatenate([x_t, tokens], axis=1)
        at = jnp.arange(length)
        positions = jnp.broadcast_to(jnp.concatenate([at, at]), z.shape)
        x, loads, aux = self.stream(params, z, positions)
        with jax.named_scope("head"):
            nll = self._head_loss(params["lm_head"], x[:, :length], tokens, weight)
        return nll / (b * length) + aux, loads

    def loss_and_grads(self, params, tokens, key):
        import jax

        (loss, loads), grads = jax.value_and_grad(self._loss, has_aux=True)(params, tokens, key)
        return loss, grads, loads

    def _train_step(self, state, tokens):
        import jax

        key, draw = jax.random.split(jax.random.wrap_key_data(state["noise_key"]))
        loss, grads, _ = self.loss_and_grads(state["params"], tokens, draw)
        updates, opt_state = self.opt.update(grads, state["opt_state"], state["params"])
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), state["params"], updates)
        return {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
            "noise_key": jax.random.key_data(key),
        }, loss


def build(cfg: Dict[str, Any], devices: Sequence[Any]) -> Load:
    return Load(cfg, devices)
