"""The plain reference of the ``brumby`` language model: forward pass and loss
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
a loop over layers and a loop over heads, no ``scan``, no rematerialisation,
no sharing of code with ``brumby.py``.  It reads the same parameter tree
(layers stacked on a leading axis) and takes the number of heads and of
key-value groups from the leaves' shapes, so it gives a chip's share and the
uncut layer alike.

Per layer, on the residual stream ``x`` ``[T, hidden]`` of one sequence, for
query head ``h`` in key-value group ``g(h)`` (``heads / kv_heads`` query
heads a group, in order), ``d`` the head size, ``p`` the power (even)::

    u      = RMSNorm(x; attn_norm)
    q_h    = rope(RMSNorm_d(u Wq_h; q_norm))        half-split rotation, rope_theta
    k_g    = rope(RMSNorm_d(u Wk_g; k_norm))
    v_g    = u Wv_g
    gamma_g[t] = log sigmoid((u Wg)[t, g])          G_g[t] = sum_{r <= t} gamma_g[r]
    w_h[t, s]  = exp(G_g[t] - G_g[s]) * (q_h[t] . k_g[s] / sqrt(d))^p     for s <= t, else 0
    y_h[t]     = sum_s w_h[t, s] v_g[s] / (sum_s w_h[t, s] + eps)
    x      = x + concat_h(y_h) Wo
    m      = RMSNorm(x; mlp_norm)
    x      = x + (silu(m W_gate) * (m W_up)) W_down

The weights are non-negative because ``p`` is even: there is no softmax.  The
same layer as a recurrence over a state of fixed size, which is what makes it
a retention layer (``retention_recurrent``; ``p`` = 2), with
``phi(a) = vec(a (x) a) / sqrt(d)``, so that ``phi(q) . phi(k) = (q . k)^2 / d``::

    S[t] = e^{gamma[t]} S[t-1] + phi(k[t]) v[t]^T        [d*d, d]
    z[t] = e^{gamma[t]} z[t-1] + phi(k[t])               [d*d]
    y[t] = S[t]^T phi(q[t]) / (z[t] . phi(q[t]) + eps)

The loss is the mean next-token negative log-likelihood over the vocabulary's
rows held here.  What the published config does not give is listed under
``assumed`` in the configuration's file.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope(x, theta):
    """``x`` ``[T, d]``: the first half of the last axis rotated against the
    second by ``position * theta^(-i / half)``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def retention_quadratic(q, k, v, gamma, power: int, eps: float):
    """One head: ``q``, ``k``, ``v`` ``[T, d]`` and the gate's ``gamma``
    ``[T]`` (log of a number in (0, 1)); ``y`` ``[T, d]``."""
    import jax.numpy as jnp

    T, d = q.shape
    G = jnp.cumsum(gamma)
    t = np.arange(T)
    causal = jnp.asarray(t[None, :] <= t[:, None])
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, G[:, None] - G[None, :], 0.0)), 0.0)
    w = decay * (q @ k.T / np.sqrt(d)) ** power
    return (w @ v) / (jnp.sum(w, axis=-1, keepdims=True) + eps)


def retention_recurrent(q, k, v, gamma, eps: float):
    """The same head at power 2, one position after another over a state
    ``S`` ``[d*d, d]`` and ``z`` ``[d*d]``."""
    import jax.numpy as jnp

    T, d = q.shape

    def phi(a):
        return jnp.outer(a, a).reshape(-1) / np.sqrt(d)

    S = jnp.zeros((d * d, v.shape[1]), jnp.float32)
    z = jnp.zeros((d * d,), jnp.float32)
    out = []
    for t in range(T):
        keep = jnp.exp(gamma[t])
        S = keep * S + jnp.outer(phi(k[t]), v[t])
        z = keep * z + phi(k[t])
        out.append(S.T @ phi(q[t]) / (z @ phi(q[t]) + eps))
    return jnp.stack(out)


def retention_block(cfg: Dict[str, Any], attn: Dict[str, Any], u):
    """The retention block's part of the residual stream (without the
    residual) of one sequence ``u`` ``[T, hidden]`` (already normed), from
    however many heads the leaves of ``attn`` (one layer's) hold."""
    import jax
    import jax.numpy as jnp

    d = int(cfg["head_dim"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    power = int(cfg["assumed"]["retention_power"])
    r_eps = float(cfg["assumed"]["retention_eps"])
    heads, groups = attn["wq"].shape[-1] // d, attn["wk"].shape[-1] // d
    per_group = heads // groups
    gamma = jax.nn.log_sigmoid(u @ _f32(attn["wg"]))  # [T, groups]
    ys = []
    for h in range(heads):
        g = h // per_group
        q = rope(rms_norm(u @ _f32(attn["wq"][:, h * d:(h + 1) * d]), attn["q_norm"], eps), theta)
        k = rope(rms_norm(u @ _f32(attn["wk"][:, g * d:(g + 1) * d]), attn["k_norm"], eps), theta)
        v = u @ _f32(attn["wv"][:, g * d:(g + 1) * d])
        ys.append(retention_quadratic(q, k, v, gamma[:, g], power, r_eps))
    return jnp.concatenate(ys, axis=-1) @ _f32(attn["wo"])


def feed_forward(mlp: Dict[str, Any], m):
    import jax

    return (jax.nn.silu(m @ _f32(mlp["w_gate"])) * (m @ _f32(mlp["w_up"]))) @ _f32(mlp["w_down"])


def layer_forward(cfg: Dict[str, Any], layer: Dict[str, Any], x):
    """One layer (its leaves unstacked) over one sequence ``x`` ``[T, hidden]``."""
    eps = float(cfg["rms_norm_eps"])
    x = x + retention_block(cfg, layer["attn"], rms_norm(x, layer["attn_norm"], eps))
    return x + feed_forward(layer["mlp"], rms_norm(x, layer["mlp_norm"], eps))


def hidden(cfg: Dict[str, Any], params: Dict[str, Any], inputs):
    """The final-normed residual stream ``[T, hidden]`` of one sequence of
    token ids ``inputs`` ``[T]``: what the head reads."""
    import jax

    x = _f32(params["embed"]["tokens"])[inputs]
    for i in range(params["layers"]["attn_norm"].shape[0]):
        x = layer_forward(cfg, jax.tree.map(lambda leaf: leaf[i], params["layers"]), x)
    return rms_norm(x, params["final_norm"], float(cfg["rms_norm_eps"]))


def loss(cfg: Dict[str, Any], params: Dict[str, Any], tokens):
    """The loss of ``tokens`` ``[b, s]``.  A mean over tokens: over blocks of
    whole sequences of one length it is the mean of the blocks' losses."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        total = jnp.zeros((), jnp.float32)
        for row in tokens:
            x = hidden(cfg, params, row[:-1])
            logp = jax.nn.log_softmax(x @ _f32(params["output"]["kernel"]), axis=-1)
            total = total + jnp.mean(-jnp.take_along_axis(logp, row[1:, None], axis=-1))
        return total / tokens.shape[0]
