"""The plain reference of the ``lfm2_moe`` language model: forward pass and
loss in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
written from the equations below and not from ``lfm2_moe.py``: loops over
layers, heads, taps and experts, no kernel, no sharing of code with the load.
It reads the same parameter tree and takes the number of heads, of experts
and of taps from the leaves' shapes.

A layer is a pre-norm residual layer on the stream ``x`` ``[b, s, hidden]``,
RMSNorm at ``norm_eps``::

    h = x + Op(RMSNorm(x; operator_norm))
    y = h + FF(RMSNorm(h; ffn_norm))

    Op, a ``conv`` layer, on u:
        [B | C | z] = u W_in                       three chunks of ``hidden``, in this order; no bias
        v    = B * z
        c[t] = sum_{j = 0 .. L-1} w[:, j] * v[t - (L - 1) + j]     depthwise, causal, v = 0 before position 0,
                                                                   w [hidden, L], L = conv_L_cache, no bias
        Op   = (C * c) W_out

    Op, a ``full_attention`` layer, on u, for query head i of H and its key-value head g = i // (H / G):
        q_i = RoPE(RMSNorm(u Wq_i; q_layernorm))   the norm over the head's 64 dimensions, one weight for all heads
        k_g = RoPE(RMSNorm(u Wk_g; k_layernorm)),  v_g = u Wv_g
        a_i[t, s] = softmax_s(q_i[t] . k_g[s] / sqrt(head size))  over s <= t
        Op  = concat_i(a_i v_g) Wo                 no bias anywhere

    FF, a dense layer:    w2(silu(w1 g) * (w3 g))
    FF, an expert layer:  s = sigmoid(g W_r)       float32, one score an expert
                          the top_k experts of largest s + b are chosen (b the expert_bias; it enters nothing else)
                          weight_e = s_e / (sum of the chosen s + 1e-6)   (norm_topk_prob), times routed_scaling_factor
                          FF = sum over the chosen e of weight_e * w2_e(silu(w1_e g) * (w3_e g))

The logits are the final RMSNorm (``embedding_norm``) of the stream against
the embedding's own rows (the head is tied); the loss is the mean next-token
negative log-likelihood, the softmax over the rows held.  Departures and
inferences are listed under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope(x, theta):
    """One head ``[b, s, size]``: the first half of the last axis rotated
    against the second by ``position * theta^(-i / half)``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None], jnp.sin(angles)[None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def short_conv(v, w):
    """``c[t] = sum_j w[:, j] v[t - (L - 1) + j]`` over ``v`` ``[b, s, d]``,
    one tap at a time, positions before 0 reading zero."""
    import jax.numpy as jnp

    taps = w.shape[1]
    out = jnp.zeros_like(v)
    for j in range(taps):
        back = taps - 1 - j  # this tap reads ``back`` positions behind
        moved = v if back == 0 else jnp.concatenate(
            [jnp.zeros_like(v[:, :back]), v[:, : v.shape[1] - back]], axis=1
        )
        out = out + moved * w[:, j]
    return out


def conv_operator(p: Dict[str, Any], u):
    d = u.shape[-1]
    bcz = u @ _f32(p["in_proj"])
    b_, c_, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
    return (c_ * short_conv(b_ * z, _f32(p["conv"]))) @ _f32(p["out_proj"])


def attention_operator(cfg: Dict[str, Any], p: Dict[str, Any], u):
    import jax
    import jax.numpy as jnp

    size = p["q_layernorm"].shape[0]
    heads, groups = p["q_proj"].shape[1] // size, p["k_proj"].shape[1] // size
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    s = u.shape[1]
    causal = jnp.asarray(np.tril(np.ones((s, s), bool)))
    keys, values = [], []
    for g in range(groups):
        cols = slice(g * size, (g + 1) * size)
        keys.append(rope(rms_norm(u @ _f32(p["k_proj"][:, cols]), p["k_layernorm"], eps), theta))
        values.append(u @ _f32(p["v_proj"][:, cols]))
    outs = []
    for i in range(heads):
        g = i // (heads // groups)
        q = rope(rms_norm(u @ _f32(p["q_proj"][:, i * size:(i + 1) * size]), p["q_layernorm"], eps), theta)
        logits = jnp.einsum("btd,bsd->bts", q, keys[g]) / np.sqrt(size)
        probs = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bts,bsd->btd", probs, values[g]))
    return jnp.concatenate(outs, axis=-1) @ _f32(p["out_proj"])


def swiglu(p: Dict[str, Any], g):
    import jax

    return (jax.nn.silu(g @ _f32(p["w1"])) * (g @ _f32(p["w3"]))) @ _f32(p["w2"])


def route(cfg: Dict[str, Any], ff: Dict[str, Any], g):
    """Chosen experts ``[tokens, top_k]`` and their weights."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(g.reshape(-1, g.shape[-1]) @ _f32(ff["gate"]))
    order = jnp.argsort(-(jax.lax.stop_gradient(s) + _f32(ff["expert_bias"])), axis=-1)
    chosen = order[:, : int(cfg["num_experts_per_tok"])]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob"):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return chosen, w * float(cfg.get("routed_scaling_factor", 1))


def experts(cfg: Dict[str, Any], ff: Dict[str, Any], g):
    """The expert layer, one expert module at a time, and how many tokens
    chose each expert."""
    import jax.numpy as jnp

    chosen, w = route(cfg, ff, g)
    flat = g.reshape(-1, g.shape[-1])
    out = jnp.zeros_like(flat)
    for e, expert in enumerate(ff["experts"]):
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        out = out + weight[:, None] * swiglu(expert, flat)
    n = len(ff["experts"])
    load = jnp.sum(chosen[:, :, None] == jnp.arange(n)[None, None, :], axis=(0, 1)).astype(jnp.float32)
    return out.reshape(g.shape), load


def layer_forward(cfg: Dict[str, Any], layer: Dict[str, Any], x):
    """The layer's output and its expert loads (``None`` for a dense
    layer).  Which operator and which feed-forward it has shows in its
    leaves."""
    eps = float(cfg["norm_eps"])
    u = rms_norm(x, layer["operator_norm"], eps)
    if "conv" in layer:
        h = x + conv_operator(layer["conv"], u)
    else:
        h = x + attention_operator(cfg, layer["self_attn"], u)
    g = rms_norm(h, layer["ffn_norm"], eps)
    if "experts" not in layer["feed_forward"]:
        return h + swiglu(layer["feed_forward"], g), None
    out, load = experts(cfg, layer["feed_forward"], g)
    return h + out, load


def hidden(cfg: Dict[str, Any], params: Dict[str, Any], inputs, remat: bool = False) -> Tuple[Any, List[Any]]:
    """The stream after the final norm, ``[b, s, hidden]``, and each layer's
    expert loads.  ``remat`` recomputes each layer in the backward pass (the
    same numbers; for a caller that must fit a real size beside the
    parameters)."""
    import jax

    x = _f32(params["embed_tokens"])[inputs]
    loads = []
    for layer in params["layers"]:
        forward = (lambda l, y: layer_forward(cfg, l, y))
        x, load = (jax.checkpoint(forward) if remat else forward)(layer, x)
        loads.append(load)
    return rms_norm(x, params["embedding_norm"], float(cfg["norm_eps"])), loads


def loss(cfg: Dict[str, Any], params: Dict[str, Any], tokens, remat: bool = False) -> Tuple[Any, List[Any]]:
    """The loss of ``tokens`` ``[b, s]`` and each layer's expert loads
    (``None`` for a dense layer).  A mean over tokens: over blocks of whole
    sequences of one length it is the mean of the blocks' losses."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x, loads = hidden(cfg, params, tokens[:, :-1], remat)
        logp = jax.nn.log_softmax(x @ _f32(params["embed_tokens"]).T, axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll), loads
