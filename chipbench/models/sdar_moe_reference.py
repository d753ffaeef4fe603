"""The plain reference of the ``sdar_moe`` language model trained by block
diffusion: the noise, the forward pass and the loss in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, written from the equations
below and not from ``sdar_moe.py``: loops over layers, heads and experts, the
whole mask built position by position, no blocks of queries, no clipped keys,
no sharing of code with the load.  It reads the same parameter tree and takes
the number of heads and of experts held from the leaves' shapes; which
experts those are (``first_expert``) and the rest from the configuration.

The noise, from a key and the clean ids ``x0`` ``[b, L]``; ``B`` is
``block_length``::

    (k_t, k_m) = split(key)
    t[b, j] ~ U(noise_eps, 1) from k_t, one a block j of B positions
    u[b, i] ~ U(0, 1) from k_m;  m_i = u_i < t[b, i // B]
    x_t[i] = MASK if m_i else x0[i]       MASK the last row of the slice

The input is ``z = [x_t ; x0]`` (2L positions) at positions ``[0 .. L-1 ;
0 .. L-1]``.  A noised query of block ``b`` reads the noised keys of block
``b`` and the clean keys of blocks ``< b``; a clean query of block ``b`` the
clean keys of blocks ``<= b``.  A layer, RMSNorm at ``rms_norm_eps``
throughout::

    a = RMSNorm(x; input_layernorm)
    for query head i of H and its key-value head g = i // (H / G):
        q_i = RoPE(RMSNorm(a Wq_i; q_norm)),  k_g = RoPE(RMSNorm(a Wk_g; k_norm)),  v_g = a Wv_g
        A_i = softmax(q_i . k_g / sqrt(head_dim), masked) v_g      (half-split RoPE, rope_theta)
    x <- x + concat_i(A_i) Wo
    b = RMSNorm(x; post_attention_layernorm)
    p = softmax(b W_gate)                      float32, over all published experts
    chosen = the top_k of p;  w_e = p_e / sum of the chosen p      (norm_topk_prob)
    x <- x + sum over the chosen e held here of w_e * SwiGLU_e(b),  SwiGLU(b) = down(silu(gate b) * (up b))
    aux += coef * E * sum_e f_e P_e            f_e: share of (position, choice) pairs routed to e,
                                               P_e: mean p_e over the positions; E published experts

The loss: ``logits = RMSNorm(x; norm)[:, :L] lm_head^T`` over the rows held,
``(1 / bL) sum_i m_i / t_i * -log softmax(logits_i)[x0_i] + aux``: position
``i`` of the noised half predicts ``x0_i``, no shift.
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


def rope(x, positions, theta):
    """One head ``[b, s, size]`` at ``positions`` ``[s]``: the first half of
    the last axis rotated against the second by ``position * theta^(-i / half)``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None], jnp.sin(angles)[None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def noise(cfg: Dict[str, Any], key, x0):
    """``(x_t, m, t_i)`` of ``x0`` ``[b, L]``: the noised ids, the mask, and
    each position's block level."""
    import jax
    import jax.numpy as jnp

    assumed = cfg["assumed"]
    block = int(assumed["block_length"])
    b, length = x0.shape
    k_t, k_m = jax.random.split(key)
    t = jax.random.uniform(k_t, (b, length // block), jnp.float32, float(assumed["noise_eps"]), 1.0)
    t_i = t[:, np.arange(length) // block]
    m = jax.random.uniform(k_m, (b, length), jnp.float32) < t_i
    return jnp.where(m, int(cfg["vocab_size"]) - 1, x0), m, t_i


def mask(length: int, block: int):
    """``[2L, 2L]``: query ``i`` of ``[x_t ; x0]`` may read key ``j``."""
    import jax.numpy as jnp

    i, j = jnp.arange(2 * length)[:, None], jnp.arange(2 * length)[None, :]
    qi, kj = i % length // block, j % length // block
    q_clean, k_clean = i >= length, j >= length
    return jnp.where(
        q_clean,
        k_clean & (kj <= qi),
        (~k_clean & (kj == qi)) | (k_clean & (kj < qi)),
    )


def attention(cfg: Dict[str, Any], p: Dict[str, Any], a, positions, keep, remat: bool = False):
    """``concat_i(A_i) Wo`` of the normed stream ``a`` ``[b, 2L, hidden]``;
    ``remat`` recomputes each head in the backward pass."""
    import jax
    import jax.numpy as jnp

    size = p["q_norm"].shape[0]
    heads, groups = p["q_proj"].shape[1] // size, p["k_proj"].shape[1] // size
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    keys, values = [], []
    for g in range(groups):
        cols = slice(g * size, (g + 1) * size)
        keys.append(rope(rms_norm(a @ _f32(p["k_proj"][:, cols]), p["k_norm"], eps), positions, theta))
        values.append(a @ _f32(p["v_proj"][:, cols]))

    def head(w_q, q_norm, k, v):
        q = rope(rms_norm(a @ _f32(w_q), q_norm, eps), positions, theta)
        logits = jnp.einsum("btd,bsd->bts", q, k) / np.sqrt(size)
        probs = jax.nn.softmax(jnp.where(keep[None], logits, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", probs, v)

    one = jax.checkpoint(head) if remat else head
    outs = []
    for i in range(heads):
        g = i // (heads // groups)
        outs.append(one(p["q_proj"][:, i * size:(i + 1) * size], p["q_norm"], keys[g], values[g]))
    return jnp.concatenate(outs, axis=-1) @ _f32(p["o_proj"])


def swiglu(p: Dict[str, Any], x):
    import jax

    return (jax.nn.silu(x @ _f32(p["gate_proj"])) * (x @ _f32(p["up_proj"]))) @ _f32(p["down_proj"])


def route(cfg: Dict[str, Any], mlp: Dict[str, Any], b):
    """The probabilities ``[positions, E]``, the chosen experts ``[positions,
    top_k]`` and their weights."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(b.reshape(-1, b.shape[-1]) @ _f32(mlp["gate"]), axis=-1)
    chosen = jnp.argsort(-jax.lax.stop_gradient(p), axis=-1)[:, : int(cfg["num_experts_per_tok"])]
    w = jnp.take_along_axis(p, chosen, axis=-1)
    if cfg.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return p, chosen, w


def routed(cfg: Dict[str, Any], mlp: Dict[str, Any], b):
    """What the experts held here give, one expert at a time over every
    position under its weight (0 where the position did not choose it); how
    many positions chose each published expert; the layer's balance term."""
    import jax.numpy as jnp

    p, chosen, w = route(cfg, mlp, b)
    flat = b.reshape(-1, b.shape[-1])
    first = int(cfg["assumed"].get("first_expert", 0))
    out = jnp.zeros_like(flat)
    for e, expert in enumerate(mlp["experts"]):
        weight = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        out = out + weight[:, None] * swiglu(expert, flat)
    n = p.shape[1]
    load = jnp.sum(chosen[:, :, None] == jnp.arange(n)[None, None, :], axis=(0, 1)).astype(jnp.float32)
    f = load / chosen.size
    aux = float(cfg["assumed"]["router_aux_loss_coef"]) * n * jnp.sum(f * jnp.mean(p, axis=0))
    return out.reshape(b.shape), load, aux


def layer_forward(cfg: Dict[str, Any], layer: Dict[str, Any], x, positions, keep, remat: bool = False):
    eps = float(cfg["rms_norm_eps"])
    a = rms_norm(x, layer["input_layernorm"], eps)
    x = x + attention(cfg, layer["self_attn"], a, positions, keep, remat)
    out, load, aux = routed(cfg, layer["mlp"], rms_norm(x, layer["post_attention_layernorm"], eps))
    return x + out, load, aux


def hidden(cfg: Dict[str, Any], params: Dict[str, Any], z, remat: bool = False) -> Tuple[Any, List[Any], Any]:
    """The stream of ``z`` ``[b, 2L]`` after the final norm, each layer's
    expert loads and the summed balance term.  ``remat`` recomputes each
    layer, and each head inside it, in the backward pass (the same numbers;
    for a caller that must fit a real size beside the parameters)."""
    import jax

    length = z.shape[1] // 2
    positions = np.concatenate([np.arange(length), np.arange(length)])
    keep = mask(length, int(cfg["assumed"]["block_length"]))
    x = _f32(params["embed_tokens"])[z]
    loads, aux = [], 0.0
    for layer in params["layers"]:
        forward = (lambda l, y: layer_forward(cfg, l, y, positions, keep, remat))
        x, load, layer_aux = (jax.checkpoint(forward) if remat else forward)(layer, x)
        loads.append(load)
        aux = aux + layer_aux
    return rms_norm(x, params["norm"], float(cfg["rms_norm_eps"])), loads, aux


def loss(cfg: Dict[str, Any], params: Dict[str, Any], x0, key, remat: bool = False) -> Tuple[Any, List[Any]]:
    """The block-diffusion loss of clean ``x0`` ``[b, L]`` under the noise
    of ``key``, and each layer's expert loads."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x_t, m, t = noise(cfg, key, x0)
        length = x0.shape[1]
        x, loads, aux = hidden(cfg, params, jnp.concatenate([x_t, x0], axis=1), remat)
        logp = jax.nn.log_softmax(x[:, :length] @ _f32(params["lm_head"]).T, axis=-1)
        nll = -jnp.take_along_axis(logp, x0[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(m, nll / t, 0.0)) / x0.size + aux, loads
