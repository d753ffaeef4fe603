"""The benchmark's load for ``dots3_note`` configurations: one chip's share of
a mixture-of-experts decoder with latent attention, trained with AdamW.

What the chip holds is what one of the chips that share a layer holds in an
expert-parallel, head-parallel deployment: ``n_routed_experts`` of the
published experts (the router keeps its published width and its experts a
token), ``num_attention_heads`` / ``swa_num_attention_heads`` of the
published heads (the low-rank down projections, their norms and the indexer
whole), ``vocab_size`` rows of the vocabulary.  The chip computes its own
experts' part of the expert layer for the tokens routed to them and adds the
shared expert; what absent experts and heads would add is left out, here and
in ``dots3_note_reference.py`` alike, and no code stands in for absent chips.

Like ``dense_decoder.py`` this is traffic generation: the leaves, their
sizes and dtypes and how they are split into statefuls are the work of a
cell.  Unlike it, layers are a list (their kinds differ, so nothing is
stacked across layers) and expert weights are three banks a layer,
``[experts_here, ...]``.  The layer equations are in the reference's
docstring; every departure or inference is listed under ``assumed`` in the
configuration's file.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from chipbench.models import dense_decoder


class Spec(NamedTuple):
    """One parameter leaf: its shape, how it starts (a float is the standard
    deviation of a normal draw; ``"ones"``; ``"zeros"``) and its dtype
    (``None``: the configuration's parameter dtype)."""

    shape: Tuple[int, ...]
    init: Any
    dtype: Any = None


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    """``(attention kind, ffn kind)`` of each layer held: the first
    ``num_hidden_layers`` of the published pattern."""
    n = int(cfg["num_hidden_layers"])
    dense = int(cfg["first_k_dense_replace"])
    return [
        ("sliding" if cfg["layer_types"][i] == "sliding_attention" else "full",
         "dense" if i < dense else "moe")
        for i in range(n)
    ]


def attention_dims(cfg: Dict[str, Any], kind: str) -> Dict[str, Any]:
    """The widths of one kind of attention block, under one set of names."""
    p = "swa_" if kind == "sliding" else ""
    return {
        "heads": int(cfg[p + "num_attention_heads"]),
        "q_rank": int(cfg[p + "q_lora_rank"]),
        "kv_rank": int(cfg[p + "kv_lora_rank"]),
        "nope": int(cfg[p + "qk_nope_head_dim"]),
        "rope": int(cfg[p + "qk_rope_head_dim"]),
        "v": int(cfg[p + "v_head_dim"]),
        "theta": float(cfg[p + "rope_theta"]),
        "window": int(cfg["sliding_window_size"]) if kind == "sliding" else None,
    }


class Load(dense_decoder.Load):
    """The surface of ``dense_decoder.Load``, and its code for everything
    that does not know the architecture (the state from a seed and zeroed,
    the split into statefuls and back, token batches, the jitted donating
    step); the parameters, the model and the train step are this file's."""

    def __init__(self, cfg: Dict[str, Any], devices: Sequence[Any]) -> None:
        # Not the dense decoder's: that one reads its own architecture's keys.
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.cfg = cfg
        self.d = int(cfg["hidden_size"])
        self.v = int(cfg["vocab_size"])
        self.kinds = layer_kinds(cfg)
        self.eps = float(cfg["rms_norm_eps"])
        # The router's width is the published count; the banks hold the
        # experts that live here, the first of them being ``first_expert``.
        self.experts_here = int(cfg["n_routed_experts"])
        self.experts = int(cfg.get("published", {}).get("n_routed_experts", self.experts_here))
        self.top_k = int(cfg["num_experts_per_tok"])
        assumed = cfg["assumed"]
        self.first_expert = int(assumed.get("first_expert", 0))
        self.bias_speed = float(assumed["router_bias_update_speed"])
        self.param_dtype = jnp.dtype(cfg["state_dtypes"]["params"])
        self.bias_dtype = jnp.dtype(cfg["state_dtypes"]["router_bias"])
        self.act_dtype = jnp.dtype(cfg.get("activation_dtype", "bfloat16"))
        self.batch = int(assumed["batch_sequences"])
        self.seq = int(assumed["sequence_length"])
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

    # ------------------------------------------------------------- the state

    def param_specs(self) -> Dict[str, Any]:
        cfg, d = self.cfg, self.d
        s = 1.0 / np.sqrt(d)

        def swiglu(width, lead=()):
            return {
                "w_gate": Spec(lead + (d, width), s),
                "w_up": Spec(lead + (d, width), s),
                "w_down": Spec(lead + (width, d), 1.0 / np.sqrt(width)),
            }

        layers = []
        for attn_kind, ffn_kind in self.kinds:
            a = attention_dims(cfg, attn_kind)
            h = a["heads"]
            layer = {
                "attn_norm": Spec((d,), "ones"),
                "attn": {
                    "w_qa": Spec((d, a["q_rank"]), s),
                    "q_norm": Spec((a["q_rank"],), "ones"),
                    "w_qb": Spec((a["q_rank"], h * (a["nope"] + a["rope"])), 1.0 / np.sqrt(a["q_rank"])),
                    "w_kva": Spec((d, a["kv_rank"] + a["rope"]), s),
                    "kv_norm": Spec((a["kv_rank"],), "ones"),
                    "w_kvb": Spec((a["kv_rank"], h * (a["nope"] + a["v"])), 1.0 / np.sqrt(a["kv_rank"])),
                    "w_g": Spec((d, h), s),
                    "w_o": Spec((h * a["v"], d), 1.0 / np.sqrt(h * a["v"])),
                },
                "ffn_norm": Spec((d,), "ones"),
            }
            if attn_kind == "full":
                ih, idim = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
                layer["indexer"] = {
                    "w_q": Spec((a["q_rank"], ih * idim), 1.0 / np.sqrt(a["q_rank"])),
                    "w_k": Spec((d, idim), s),
                    "k_norm_scale": Spec((idim,), "ones"),
                    "k_norm_bias": Spec((idim,), "zeros"),
                    "w_w": Spec((d, ih), s),
                }
            if ffn_kind == "dense":
                layer["mlp"] = swiglu(int(cfg["intermediate_size"]))
            else:
                width = int(cfg["moe_intermediate_size"])
                layer["moe"] = {
                    "router": {
                        "kernel": Spec((d, self.experts), s),
                        "bias": Spec((self.experts,), "zeros", self.bias_dtype),
                    },
                    "shared": swiglu(width * int(cfg["n_shared_experts"])),
                    "experts": swiglu(width, (self.experts_here,)),
                }
            layers.append(layer)
        return {
            "embed": {"tokens": Spec((self.v, d), 1.0)},
            "layers": layers,
            "final_norm": Spec((d,), "ones"),
            "output": {"kernel": Spec((d, self.v), s)},
        }

    def _init_params(self, key):
        import jax
        import jax.numpy as jnp

        specs, treedef = jax.tree.flatten(
            self.param_specs(), is_leaf=lambda x: isinstance(x, Spec)
        )
        leaves = []
        for i, spec in enumerate(specs):
            dtype = spec.dtype or self.param_dtype
            if spec.init == "ones":
                leaf = jnp.ones(spec.shape, dtype)
            elif spec.init == "zeros":
                leaf = jnp.zeros(spec.shape, dtype)
            else:
                draw = jax.random.normal(jax.random.fold_in(key, i), spec.shape, jnp.float32)
                leaf = (draw * spec.init).astype(dtype)
            leaves.append(leaf)
        return jax.tree.unflatten(treedef, leaves)

    # ------------------------------------------------------------- the model

    def _rms_norm(self, x, w, scale=1.0):
        import jax
        import jax.numpy as jnp

        dtype = x.dtype
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        return (x * w.astype(jnp.float32) * scale).astype(dtype)

    def _layer_norm(self, x, scale, bias):
        import jax
        import jax.numpy as jnp

        dtype = x.dtype
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)

    @staticmethod
    def _rotate(x, theta):
        """Half-split rotation of ``x`` ``[b, s, heads, rope]`` by position."""
        import jax.numpy as jnp

        half = x.shape[-1] // 2
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
        angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
        x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.astype(x.dtype)

    def _index_scores(self, p, x, c_q):
        """``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``, float32
        ``[b, s, s]``, the dot product scaled by ``index_head_dim^-1/2`` and
        the weights by ``index_n_heads^-1/2``.  Its inputs are cut from the
        graph: the auxiliary term trains the indexer's own leaves and nothing
        else."""
        import jax
        import jax.numpy as jnp

        dt = self.act_dtype
        ih, idim = int(self.cfg["index_n_heads"]), int(self.cfg["index_head_dim"])
        x = jax.lax.stop_gradient(x)
        c_q = jax.lax.stop_gradient(c_q)
        b, s = x.shape[:2]
        q = (c_q @ p["w_q"].astype(dt)).reshape(b, s, ih, idim)
        k = self._layer_norm(x @ p["w_k"].astype(dt), p["k_norm_scale"], p["k_norm_bias"])
        w = (x @ p["w_w"].astype(dt)) * ih ** -0.5
        hit = jax.nn.relu(jnp.einsum("btjd,bsd->bjts", q, k) * idim ** -0.5)
        return jnp.einsum("bjts,btj->bts", hit, w, preferred_element_type=jnp.float32)

    def attention(self, layer, x, kind):
        """The attention block's part of the residual stream from the heads
        held here (the residual itself is not in it), and the indexer's
        auxiliary term (0 where the layer has no indexer)."""
        import jax
        import jax.numpy as jnp

        a = attention_dims(self.cfg, kind)
        p = layer["attn"]
        dt = self.act_dtype
        b, s = x.shape[:2]
        heads, nope, rope, vd = a["heads"], a["nope"], a["rope"], a["v"]
        rescale = bool(self.cfg.get("apply_mla_qkv_lora_rescale"))
        c_q = self._rms_norm(
            x @ p["w_qa"].astype(dt), p["q_norm"],
            np.sqrt(self.d / a["q_rank"]) if rescale else 1.0,
        )
        q = (c_q @ p["w_qb"].astype(dt)).reshape(b, s, heads, nope + rope)
        kva = x @ p["w_kva"].astype(dt)
        c_kv = self._rms_norm(
            kva[..., : a["kv_rank"]], p["kv_norm"],
            np.sqrt(self.d / a["kv_rank"]) if rescale else 1.0,
        )
        k_rope = self._rotate(kva[..., a["kv_rank"]:][:, :, None, :], a["theta"])
        kv = (c_kv @ p["w_kvb"].astype(dt)).reshape(b, s, heads, nope + vd)
        q = jnp.concatenate([q[..., :nope], self._rotate(q[..., nope:], a["theta"])], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))], axis=-1
        )
        v = kv[..., nope:]
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) / np.sqrt(nope + rope)
        t = jnp.arange(s)
        mask = t[None, :] <= t[:, None]
        if a["window"] is not None:
            mask = mask & (t[:, None] - t[None, :] < a["window"])
        aux = jnp.zeros((), jnp.float32)
        scores = None
        if kind == "full":
            scores = self._index_scores(layer["indexer"], x, c_q)
            topk = int(self.cfg["index_topk"])
            if s > topk:
                # Only the index_topk highest-scored earlier positions stay.
                ranked = jnp.where(mask[None], jax.lax.stop_gradient(scores), -jnp.inf)
                kth = jax.lax.top_k(ranked, topk)[0][..., -1:]
                keep = mask[None] & (ranked >= kth)
            else:
                keep = jnp.broadcast_to(mask[None], (b, s, s))
            logits = jnp.where(keep[:, None], logits, -1e30)
        else:
            keep = None
            logits = jnp.where(mask[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        if scores is not None:
            # The indexer learns the heads' mean distribution over the
            # positions kept (cross-entropy against it, cut from the graph).
            target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
            logq = jax.nn.log_softmax(jnp.where(keep, scores, -1e30), axis=-1)
            aux = -jnp.mean(jnp.sum(jnp.where(keep, target * logq, 0.0), axis=-1))
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(dt), v)
        gate = jax.nn.sigmoid((x @ p["w_g"].astype(dt)).astype(jnp.float32)).astype(dt)
        out = (out * gate[..., None]).reshape(b, s, heads * vd)
        return out @ p["w_o"].astype(dt), aux

    def _swiglu(self, p, h):
        import jax

        dt = self.act_dtype
        gate = jax.nn.silu(h @ p["w_gate"].astype(dt))
        return (gate * (h @ p["w_up"].astype(dt))) @ p["w_down"].astype(dt)

    def route(self, router, h):
        """The router over all published experts: the combine weight of each
        expert held here for each token ``[tokens, experts_here]`` and how
        many tokens chose each published expert ``[experts]``."""
        import jax
        import jax.numpy as jnp

        x = h.reshape(-1, self.d).astype(jnp.float32)
        s = jax.nn.sigmoid(
            jnp.dot(x, router["kernel"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        )
        biased = jax.lax.stop_gradient(s + router["bias"].astype(jnp.float32))
        chosen = jax.lax.top_k(biased, self.top_k)[1]
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if self.cfg.get("norm_topk_prob"):
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        w = w * float(self.cfg.get("routed_scaling_factor", 1))
        here = self.first_expert + jnp.arange(self.experts_here)
        combine = jnp.sum(
            jnp.where(chosen[:, :, None] == here[None, None, :], w[:, :, None], 0.0), axis=1
        )
        load = jnp.sum(jax.nn.one_hot(chosen, self.experts, dtype=jnp.float32), axis=(0, 1))
        return combine, load

    def routed(self, moe, h):
        """The part of the expert layer that the experts held here give:
        each bank over every token, weighted by the token's combine weight
        for that expert (0 where it was not routed there), so no token is
        dropped whatever the imbalance."""
        import jax
        import jax.numpy as jnp

        dt = self.act_dtype
        combine, load = self.route(moe["router"], h)
        x = h.reshape(-1, self.d)
        banks = moe["experts"]
        gate = jax.nn.silu(jnp.einsum("td,edf->etf", x, banks["w_gate"].astype(dt)))
        up = jnp.einsum("td,edf->etf", x, banks["w_up"].astype(dt))
        hidden = gate * up * combine.T.astype(dt)[:, :, None]
        out = jnp.einsum("etf,efd->td", hidden, banks["w_down"].astype(dt))
        return out.reshape(h.shape), load

    def _attend_each(self, layer, h, kind):
        """``attention`` one sequence at a time, each rematerialised: what
        grows with the square of the length (logits, probabilities, the
        indexer's scores) then lives for one sequence, not for the batch."""
        import jax
        import jax.numpy as jnp

        def one(row):
            out, aux = self.attention(layer, row[None], kind)
            return out[0], aux

        out, aux = jax.lax.map(jax.checkpoint(one), h)
        return out, jnp.mean(aux)

    def _layer(self, layer, x, kinds):
        import jax.numpy as jnp

        attn_kind, ffn_kind = kinds
        attn, aux = self._attend_each(layer, self._rms_norm(x, layer["attn_norm"]), attn_kind)
        x = x + attn
        h = self._rms_norm(x, layer["ffn_norm"])
        if ffn_kind == "dense":
            return x + self._swiglu(layer["mlp"], h), aux, jnp.zeros((self.experts,), jnp.float32)
        routed, load = self.routed(layer["moe"], h)
        return x + self._swiglu(layer["moe"]["shared"], h) + routed, aux, load

    def _loss(self, params, tokens):
        """The next-token loss over the vocabulary's slice plus the indexers'
        auxiliary terms; beside it, each layer's expert loads."""
        import functools

        import jax
        import jax.numpy as jnp

        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = params["embed"]["tokens"].astype(self.act_dtype)[inputs]
        aux_total = jnp.zeros((), jnp.float32)
        loads = []
        for layer, kinds in zip(params["layers"], self.kinds):
            body = jax.checkpoint(functools.partial(self._layer, kinds=kinds))
            x, aux, load = body(layer, x)
            aux_total = aux_total + aux
            loads.append(load)
        x = self._rms_norm(x, params["final_norm"])
        logits = jnp.dot(
            x, params["output"]["kernel"].astype(self.act_dtype),
            preferred_element_type=jnp.float32,
        )
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll) + aux_total, loads

    def loss_and_grads(self, params, tokens):
        import jax

        (loss, loads), grads = jax.value_and_grad(self._loss, has_aux=True)(params, tokens)
        return loss, grads, loads

    def _train_step(self, state, tokens):
        import jax
        import jax.numpy as jnp

        loss, grads, loads = self.loss_and_grads(state["params"], tokens)
        updates, opt_state = self.opt.update(grads, state["opt_state"], state["params"])
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), state["params"], updates)
        # The balance rule, not a gradient, moves a router's bias: up for an
        # expert that got fewer tokens than the mean, down for one that got
        # more.  (Its gradient is zero, so AdamW's moments of it stay zero
        # and only the weight decay is undone here.)
        for new, old, load, (_, ffn_kind) in zip(
            params["layers"], state["params"]["layers"], loads, self.kinds
        ):
            if ffn_kind == "moe":
                bias = old["moe"]["router"]["bias"]
                new["moe"]["router"]["bias"] = bias + (
                    self.bias_speed * jnp.sign(jnp.mean(load) - load)
                ).astype(bias.dtype)
        return {"params": params, "opt_state": opt_state, "step": state["step"] + 1}, loss


def build(cfg: Dict[str, Any], devices: Sequence[Any]) -> Load:
    return Load(cfg, devices)
