"""The benchmark's load for ``lfm2_moe`` configurations: a decoder whose
layers are gated short convolutions and grouped-query attention in a fixed
pattern, a leading dense feed-forward and then mixture-of-experts layers,
trained with AdamW.

Like ``dense_decoder.py`` and ``dots3_note.py`` this is traffic generation:
the leaves, their sizes and dtypes and how they are split into statefuls are
the work of a cell.  What this load brings is the tree: layers are a list
and **an expert is a module of its own**, ``feed_forward.experts[e]`` with
its three matrices ``w1``, ``w3`` ``[hidden, width]`` and ``w2`` ``[width,
hidden]``, as the public implementation's module tree and its checkpoint
have them; nothing is stacked, in the state or in the step.  Each layer is
whole here (every expert, every head); the vocabulary may be a slice.

The layer equations are in ``lfm2_moe_reference.py``'s docstring; every
departure or inference is listed under ``assumed`` in the configuration's
file.  ``dots3_note``'s ``Spec`` and seeded draw, its router's shape and its
bias-by-balance rule are shared; the operators are this file's.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from chipbench.models import dense_decoder, dots3_note
from chipbench.models.dots3_note import Spec


def layer_kinds(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    """``(operator kind, ffn kind)`` of each layer held."""
    n, dense = int(cfg["num_hidden_layers"]), int(cfg["num_dense_layers"])
    if len(cfg["layer_types"]) != n:
        raise ValueError("layer_types names another number of layers than num_hidden_layers")
    return [(cfg["layer_types"][i], "dense" if i < dense else "moe") for i in range(n)]


class Load(dense_decoder.Load):
    """The surface of ``dense_decoder.Load`` and its code for everything that
    does not know the architecture (the state from a seed, the split into
    statefuls and back, token batches, the jitted donating step); the
    parameters, the model, the train step and the zeroed target are this
    file's."""

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
        self.eps = float(cfg["norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.taps = int(cfg["conv_L_cache"])
        self.experts = int(cfg["num_experts"])
        self.top_k = int(cfg["num_experts_per_tok"])
        assumed = cfg["assumed"]
        self.head_dim = int(assumed["head_dim"])
        self.sum_eps = float(assumed["router_weight_sum_eps"])
        self.bias_speed = float(assumed["expert_bias_update_speed"])
        self.param_dtype = jnp.dtype(cfg["state_dtypes"]["params"])
        self.bias_dtype = jnp.dtype(cfg["state_dtypes"]["expert_bias"])
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
        self._zero = None

    # ------------------------------------------------------------- the state

    def param_specs(self) -> Dict[str, Any]:
        cfg, d = self.cfg, self.d
        s = 1.0 / np.sqrt(d)
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim

        def swiglu(width):
            return {
                "w1": Spec((d, width), s),
                "w3": Spec((d, width), s),
                "w2": Spec((width, d), 1.0 / np.sqrt(width)),
            }

        layers = []
        for operator, ffn in self.kinds:
            layer = {"operator_norm": Spec((d,), "ones"), "ffn_norm": Spec((d,), "ones")}
            if operator == "conv":
                layer["conv"] = {
                    "in_proj": Spec((d, 3 * d), s),
                    "conv": Spec((d, self.taps), 1.0 / np.sqrt(self.taps)),
                    "out_proj": Spec((d, d), s),
                }
            else:
                layer["self_attn"] = {
                    "q_proj": Spec((d, q), s),
                    "k_proj": Spec((d, kv), s),
                    "v_proj": Spec((d, kv), s),
                    "out_proj": Spec((q, d), 1.0 / np.sqrt(q)),
                    "q_layernorm": Spec((self.head_dim,), "ones"),
                    "k_layernorm": Spec((self.head_dim,), "ones"),
                }
            if ffn == "dense":
                layer["feed_forward"] = swiglu(int(cfg["intermediate_size"]))
            else:
                # One module an expert, one leaf a matrix: never a bank.
                layer["feed_forward"] = {
                    "gate": Spec((d, self.experts), s),
                    "expert_bias": Spec((self.experts,), "zeros", self.bias_dtype),
                    "experts": [
                        swiglu(int(cfg["moe_intermediate_size"])) for _ in range(self.experts)
                    ],
                }
            layers.append(layer)
        return {
            # tied to the head: hidden^-1/2 keeps the logits of order one
            "embed_tokens": Spec((self.v, d), s),
            "layers": layers,
            "embedding_norm": Spec((d,), "ones"),
        }

    # The seeded draw over ``param_specs`` knows no architecture.
    _init_params = dots3_note.Load._init_params

    def zero_state(self):
        """The all-zeros state of the same structure: a restore target.  One
        jitted program for the process: at 1,277 arrays a fresh one a cycle,
        as ``dense_decoder.Load.zero_state`` makes it, is a retrace a cycle."""
        import jax
        import jax.numpy as jnp

        if self._zero is None:
            abstract = self._abstract
            self._zero = jax.jit(
                lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract),
                out_shardings=self._shardings(),
            )
        return self._zero()

    # ------------------------------------------------------------- the model

    _rotate = staticmethod(dots3_note.Load._rotate)

    def conv_operator(self, p, u):
        """``(C * conv(B * z)) W_out`` with ``[B, C, z] = split3(u W_in)``:
        the depthwise causal convolution as shifted products, tap ``j``
        reading position ``t - (taps - 1) + j``."""
        import jax.numpy as jnp

        dt = self.act_dtype
        b_, c_, z = jnp.split(u @ p["in_proj"].astype(dt), 3, axis=-1)
        v = b_ * z
        s = v.shape[1]
        padded = jnp.pad(v, ((0, 0), (self.taps - 1, 0), (0, 0)))
        w = p["conv"].astype(dt)
        conv = sum(padded[:, j:j + s, :] * w[:, j] for j in range(self.taps))
        return (c_ * conv) @ p["out_proj"].astype(dt)

    def attention_operator(self, p, u):
        """Grouped-query causal softmax attention, queries and keys normed
        per head and then rotated; logits and softmax in float32."""
        import jax
        import jax.numpy as jnp

        dt = self.act_dtype
        b, s = u.shape[:2]
        kv, rep, hd = self.kv_heads, self.heads // self.kv_heads, self.head_dim
        q = (u @ p["q_proj"].astype(dt)).reshape(b, s, self.heads, hd)
        k = (u @ p["k_proj"].astype(dt)).reshape(b, s, kv, hd)
        v = (u @ p["v_proj"].astype(dt)).reshape(b, s, kv, hd)
        q = self._rotate(self._rms_norm(q, p["q_layernorm"]), self.theta)
        k = self._rotate(self._rms_norm(k, p["k_layernorm"]), self.theta)
        q = q.reshape(b, s, kv, rep, hd)  # query head g * rep + r reads key-value head g
        logits = jnp.einsum(
            "bqgrd,bkgd->bgrqk", q, k, preferred_element_type=jnp.float32
        ) / np.sqrt(hd)
        t = jnp.arange(s)
        logits = jnp.where((t[None, :] <= t[:, None])[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(dt)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(b, s, self.heads * hd)
        return out @ p["out_proj"].astype(dt)

    def _attend_each(self, p, u):
        """``attention_operator`` one sequence at a time, each rematerialised:
        the logits then live for one sequence, not for the batch."""
        import jax

        return jax.lax.map(jax.checkpoint(lambda row: self.attention_operator(p, row[None])[0]), u)

    def _swiglu(self, p, h, weight=None):
        import jax

        dt = self.act_dtype
        hidden = jax.nn.silu(h @ p["w1"].astype(dt)) * (h @ p["w3"].astype(dt))
        if weight is not None:
            hidden = hidden * weight[:, None]
        return hidden @ p["w2"].astype(dt)

    def route(self, ff, h):
        """Each token's combine weight for each expert ``[tokens, experts]``
        (0 where it was not chosen) and how many tokens chose each expert."""
        import jax
        import jax.numpy as jnp

        x = h.reshape(-1, self.d).astype(jnp.float32)
        s = jax.nn.sigmoid(
            jnp.dot(x, ff["gate"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        )
        biased = jax.lax.stop_gradient(s) + ff["expert_bias"].astype(jnp.float32)
        chosen = jax.lax.top_k(biased, self.top_k)[1]
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if self.cfg.get("norm_topk_prob"):
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + self.sum_eps)
        w = w * float(self.cfg.get("routed_scaling_factor", 1))
        picked = jax.nn.one_hot(chosen, self.experts, dtype=jnp.float32)
        return jnp.sum(picked * w[:, :, None], axis=1), jnp.sum(picked, axis=(0, 1))

    def experts_forward(self, ff, h):
        """``sum_e weight_e SwiGLU_e(h)``: the expert modules one after the
        other over every token, each under its column of the combine
        weights and each rematerialised, so that one expert's activations
        are alive at a time; no token is dropped."""
        import jax
        import jax.numpy as jnp

        combine, load = self.route(ff, h)
        combine = combine.astype(self.act_dtype)
        x = h.reshape(-1, self.d)
        one = jax.checkpoint(self._swiglu)
        out = jnp.zeros_like(x)
        for e, expert in enumerate(ff["experts"]):
            out = out + one(expert, x, combine[:, e])
        return out.reshape(h.shape), load

    def _layer(self, layer, x, kinds):
        import jax.numpy as jnp

        operator, ffn = kinds
        u = self._rms_norm(x, layer["operator_norm"])
        if operator == "conv":
            x = x + self.conv_operator(layer["conv"], u)
        else:
            x = x + self._attend_each(layer["self_attn"], u)
        h = self._rms_norm(x, layer["ffn_norm"])
        if ffn == "dense":
            return x + self._swiglu(layer["feed_forward"], h), jnp.zeros((self.experts,), jnp.float32)
        routed, load = self.experts_forward(layer["feed_forward"], h)
        return x + routed, load

    def _head_loss(self, embed, x, targets):
        """The summed next-token negative log-likelihood against the
        embedding's own rows, one sequence at a time and rematerialised:
        the float32 logits live for one sequence."""
        import jax
        import jax.numpy as jnp

        table = embed.astype(self.act_dtype)

        def one(args):
            row, want = args
            logits = jnp.einsum("sd,vd->sv", row, table, preferred_element_type=jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, want[:, None], axis=-1))

        return jnp.sum(jax.lax.map(jax.checkpoint(one), (x, targets)))

    def _loss(self, params, tokens):
        """The mean next-token loss over the vocabulary's rows held here;
        beside it, each layer's expert loads (zeros for a dense layer)."""
        import jax

        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = params["embed_tokens"].astype(self.act_dtype)[inputs]
        loads = []
        for layer, kinds in zip(params["layers"], self.kinds):
            x, load = jax.checkpoint(functools.partial(self._layer, kinds=kinds))(layer, x)
            loads.append(load)
        x = self._rms_norm(x, params["embedding_norm"])
        return self._head_loss(params["embed_tokens"], x, targets) / targets.size, loads

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
        # ``dots3_note``'s balance rule, not a gradient, moves an expert
        # bias: up for an expert that got fewer tokens than the mean, down
        # for one that got more.  (Its gradient is zero, so AdamW's moments
        # of it stay zero and only the weight decay is undone here.)
        for new, old, load, (_, ffn) in zip(
            params["layers"], state["params"]["layers"], loads, self.kinds
        ):
            if ffn == "moe":
                bias = old["feed_forward"]["expert_bias"]
                new["feed_forward"]["expert_bias"] = bias + (
                    self.bias_speed * jnp.sign(jnp.mean(load) - load)
                ).astype(bias.dtype)
        return {"params": params, "opt_state": opt_state, "step": state["step"] + 1}, loss


def build(cfg: Dict[str, Any], devices: Sequence[Any]) -> Load:
    return Load(cfg, devices)
