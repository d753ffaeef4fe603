"""The benchmark's load for ``brumby`` configurations: one chip's share of a
dense decoder whose attention is gated power retention, trained with AdamW.

What the chip holds is what one of the chips that share a layer holds in a
head-parallel deployment: ``num_attention_heads`` / ``num_key_value_heads``
of the published heads (whole key-value groups, with the gate's columns for
the groups held), ``vocab_size`` rows of the vocabulary, and the feed-forward
whole, as every chip of such a deployment computes it.  What absent heads
would add is left out, here and in ``brumby_reference.py`` alike, and no
code stands in for absent chips.

The tree is ``dense_decoder.py``'s (``embed.tokens``, the layers stacked on a
leading axis and run under ``lax.scan`` with rematerialised bodies,
``final_norm``, ``output.kernel``) with three more leaves in ``layers.attn``:
``wg`` ``[layers, hidden, kv_heads]``, ``q_norm`` and ``k_norm`` ``[layers,
head_dim]``.  Stacked, a feed-forward leaf is ``[layers, hidden,
intermediate]``: at four layers of these widths it is larger than the
library's chunk size, which is what the configuration is in the benchmark
for.  The layer's equations are in the reference's docstring; what the
published config does not give is listed under ``assumed`` in the
configuration's file.  The retention is ``jax.numpy`` code of this load in its
quadratic form, exact at these lengths: no kernel, and not the library's.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np

from chipbench.models import dense_decoder


class Load(dense_decoder.Load):
    """The surface of ``dense_decoder.Load``, and its code for everything
    that does not know the layer (the state from a seed and zeroed, the split
    into statefuls and back, token batches, norms, the rotation, the train
    step and its jit); the parameters, the layer and the loss are this
    file's."""

    def __init__(self, cfg: Dict[str, Any], devices: Sequence[Any]) -> None:
        # Not the dense decoder's: that one wants heads * head_dim == hidden.
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.cfg = cfg
        self.d = int(cfg["hidden_size"])
        self.f = int(cfg["intermediate_size"])
        self.v = int(cfg["vocab_size"])
        self.layers = int(cfg["num_hidden_layers"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.head_dim = int(cfg["head_dim"])
        if self.heads % self.kv_heads:
            raise ValueError("whole key-value groups: heads must be a multiple of kv heads")
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        assumed = cfg["assumed"]
        self.power = int(assumed["retention_power"])
        if self.power % 2:
            raise ValueError("an odd power gives negative weights: this layer has no softmax")
        self.retention_eps = float(assumed["retention_eps"])
        self.param_dtype = jnp.dtype(cfg["state_dtypes"]["params"])
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

    def _init_params(self, key):
        import jax
        import jax.numpy as jnp

        d, f, v, L = self.d, self.f, self.v, self.layers
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        k_embed, k_attn, k_mlp, k_out = jax.random.split(key, 4)
        ka = jax.random.split(k_attn, 5)
        km = jax.random.split(k_mlp, 3)

        def nrm(k, shape, scale):
            x = jax.random.normal(k, shape, dtype=jnp.float32) * scale
            return x.astype(self.param_dtype)

        s = 1.0 / np.sqrt(d)
        ones = lambda shape: jnp.ones(shape, dtype=self.param_dtype)  # noqa: E731
        return {
            "embed": {"tokens": nrm(k_embed, (v, d), 1.0)},
            "layers": {
                "attn": {
                    "wq": nrm(ka[0], (L, d, q), s),
                    "wk": nrm(ka[1], (L, d, kv), s),
                    "wv": nrm(ka[2], (L, d, kv), s),
                    "wo": nrm(ka[3], (L, q, d), 1.0 / np.sqrt(q)),
                    "wg": nrm(ka[4], (L, d, self.kv_heads), s),
                    "q_norm": ones((L, self.head_dim)),
                    "k_norm": ones((L, self.head_dim)),
                },
                "mlp": {
                    "w_gate": nrm(km[0], (L, d, f), s),
                    "w_up": nrm(km[1], (L, d, f), s),
                    "w_down": nrm(km[2], (L, f, d), 1.0 / np.sqrt(f)),
                },
                "attn_norm": ones((L, d)),
                "mlp_norm": ones((L, d)),
            },
            "final_norm": ones((d,)),
            "output": {"kernel": nrm(k_out, (d, v), s)},
        }

    # ------------------------------------------------------------- the model

    def gate(self, attn, u):
        """``G`` ``[b, s, kv_heads]``, float32: the running sum over positions
        of ``gamma = log sigmoid(u wg)``, one column a key-value head.
        ``G[t] - G[s] <= 0`` for ``s <= t``, so a decay is in (0, 1]."""
        import jax
        import jax.numpy as jnp

        gamma = jax.nn.log_sigmoid(
            jnp.dot(u, attn["wg"].astype(self.act_dtype), preferred_element_type=jnp.float32)
        )
        return jnp.cumsum(gamma, axis=1)

    def retention(self, attn, u, positions):
        """The retention block's part of the residual stream from the heads
        held here (the residual itself is not in it), for normed input ``u``
        ``[b, s, hidden]``: gated power retention in its quadratic form, the
        weights and the normalisation in float32."""
        import jax.numpy as jnp

        dt = self.act_dtype
        b, s = u.shape[:2]
        groups, rep, hd = self.kv_heads, self.heads // self.kv_heads, self.head_dim
        q = (u @ attn["wq"].astype(dt)).reshape(b, s, self.heads, hd)
        k = (u @ attn["wk"].astype(dt)).reshape(b, s, groups, hd)
        v = (u @ attn["wv"].astype(dt)).reshape(b, s, groups, hd)
        q = self._rope(self._rms_norm(q, attn["q_norm"]), positions)
        k = self._rope(self._rms_norm(k, attn["k_norm"]), positions)
        scores = jnp.einsum(
            "btgrd,bsgd->bgrts", q.reshape(b, s, groups, rep, hd), k,
            preferred_element_type=jnp.float32,
        ) / np.sqrt(hd)
        G = self.gate(attn, u).astype(jnp.float32).transpose(0, 2, 1)  # [b, groups, s]
        t = jnp.arange(s)
        causal = t[None, :] <= t[:, None]
        decay = jnp.exp(
            jnp.where(causal[None, None], G[:, :, :, None] - G[:, :, None, :], -jnp.inf)
        )  # [b, groups, t, s]
        w = decay[:, :, None] * scores ** self.power
        y = jnp.einsum("bgrts,bsgd->btgrd", w, v.astype(jnp.float32))
        norm = jnp.sum(w, axis=-1).transpose(0, 3, 1, 2)  # [b, t, groups, rep]
        y = (y / (norm[..., None] + self.retention_eps)).astype(dt)
        return y.reshape(b, s, self.heads * hd) @ attn["wo"].astype(dt)

    def _retain_each(self, attn, u, positions):
        """``retention`` one sequence at a time, each rematerialised: what
        grows with the square of the length (scores, decays, weights) then
        lives for one sequence, not for the batch."""
        import jax

        def one(row):
            u_row, pos = row
            return self.retention(attn, u_row[None], pos[None])[0]

        return jax.lax.map(jax.checkpoint(one), (u, positions))

    def mlp(self, mlp, m):
        import jax

        dt = self.act_dtype
        gate = jax.nn.silu(m @ mlp["w_gate"].astype(dt))
        return (gate * (m @ mlp["w_up"].astype(dt))) @ mlp["w_down"].astype(dt)

    def _layer(self, x, layer, positions):
        x = x + self._retain_each(
            layer["attn"], self._rms_norm(x, layer["attn_norm"]), positions
        )
        return x + self.mlp(layer["mlp"], self._rms_norm(x, layer["mlp_norm"]))

    def _loss(self, params, tokens):
        """The next-token loss over the vocabulary's slice; the head's logits
        are accumulated and kept in float32."""
        import jax
        import jax.numpy as jnp

        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = params["embed"]["tokens"].astype(self.act_dtype)[inputs]
        positions = jnp.broadcast_to(jnp.arange(inputs.shape[1]), inputs.shape)

        def body(carry, layer):
            return self._layer(carry, layer, positions), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
        x = self._rms_norm(x, params["final_norm"])
        logits = jnp.dot(
            x, params["output"]["kernel"].astype(self.act_dtype),
            preferred_element_type=jnp.float32,
        )
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll)

    def loss_and_grads(self, params, tokens):
        import jax

        return jax.value_and_grad(self._loss)(params, tokens)


def build(cfg: Dict[str, Any], devices: Sequence[Any]) -> Load:
    return Load(cfg, devices)
