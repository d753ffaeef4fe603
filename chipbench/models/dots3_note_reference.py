"""The plain reference of the ``dots3_note`` language model: forward pass and
loss in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no rematerialisation, no expert banks used as such (a loop over experts), no
sharing of code with ``dots3_note.py``.  It reads the same parameter tree and
takes the number of heads and of experts from the leaves' shapes, so it gives
a chip's share and the uncut layer alike.

Per layer, on the residual stream ``x`` ``[b, s, hidden]``::

    h   = RMSNorm(x; attn_norm)
    c_q = RMSNorm(h W_qa; q_norm) * r_q            r_q  = sqrt(hidden / q_rank)  if the rescale is on
    q   = c_q W_qb  -> per head [q_nope | q_rope]
    [c_kv | k_rope] = h W_kva
    c_kv = RMSNorm(c_kv; kv_norm) * r_kv           r_kv = sqrt(hidden / kv_rank)
    [k_nope | v] = c_kv W_kvb  per head;  k = [k_nope | RoPE(k_rope)] (one k_rope for all heads)
    q   = [q_nope | RoPE(q_rope)]
    a[t, s] = softmax_s(q_t . k_s / sqrt(nope + rope))  over s <= t,
              and t - s < window in a sliding layer,
              and s among the index_topk highest I[t, s] in a full-attention layer
    o_h = (sum_s a_h[t, s] v_h[s]) * sigmoid(h W_g)_h
    x   = x + concat_h(o_h) W_o

    indexer (full-attention layers):  q_I = c_q W_Iq (heads j),  k_I = LayerNorm(h W_Ik),
              w = h W_Iw / sqrt(index heads),
              I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s] / sqrt(index head size))
    its auxiliary term: mean_t of -sum_s p[t, s] log softmax_s(I[t, s]) over the kept s,
              p = mean_h a_h, with p, h and c_q held constant

    g   = RMSNorm(x; ffn_norm)
    dense layer:   x = x + SwiGLU(g)
    expert layer:  s = sigmoid(g W_r);  the top_k highest of s + b are chosen;
                   weight_e = s_e / sum of the chosen s  (norm_topk_prob), times the scaling factor
                   x = x + SwiGLU_shared(g) + sum over chosen experts e held here of weight_e SwiGLU_e(g)

The loss is the mean next-token negative log-likelihood over the vocabulary's
rows held here plus the indexers' auxiliary terms.  Departures and inferences
are listed under ``assumed`` in the configuration's file.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def layer_norm(x, scale, bias, eps):
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale) + _f32(bias)


def rope(x, theta):
    """``x`` ``[b, s, heads, rope]``: the first half of the last axis rotated
    against the second by ``position * theta^(-i / half)``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def swiglu(p, g, expert: Optional[int] = None):
    import jax

    pick = (lambda w: _f32(w)) if expert is None else (lambda w: _f32(w[expert]))
    return (jax.nn.silu(g @ pick(p["w_gate"])) * (g @ pick(p["w_up"]))) @ pick(p["w_down"])


def attention(cfg: Dict[str, Any], layer: Dict[str, Any], h, sliding: bool):
    """The attention block's part of the residual stream (without the
    residual) from however many heads the leaves hold, and the indexer's
    auxiliary term."""
    import jax
    import jax.numpy as jnp

    pre = "swa_" if sliding else ""
    nope, rp, vd = (int(cfg[pre + k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    q_rank, kv_rank = int(cfg[pre + "q_lora_rank"]), int(cfg[pre + "kv_lora_rank"])
    theta = float(cfg[pre + "rope_theta"])
    eps, hidden = float(cfg["rms_norm_eps"]), int(cfg["hidden_size"])
    p = layer["attn"]
    heads = p["w_qb"].shape[1] // (nope + rp)
    b, s, _ = h.shape
    rescale = bool(cfg.get("apply_mla_qkv_lora_rescale"))
    c_q = rms_norm(h @ _f32(p["w_qa"]), p["q_norm"], eps)
    kva = h @ _f32(p["w_kva"])
    c_kv = rms_norm(kva[..., :kv_rank], p["kv_norm"], eps)
    if rescale:
        c_q = c_q * np.sqrt(hidden / q_rank)
        c_kv = c_kv * np.sqrt(hidden / kv_rank)
    q = (c_q @ _f32(p["w_qb"])).reshape(b, s, heads, nope + rp)
    kv = (c_kv @ _f32(p["w_kvb"])).reshape(b, s, heads, nope + vd)
    k_rope = rope(kva[..., kv_rank:][:, :, None, :], theta)
    q_rope = rope(q[..., nope:], theta)
    logits = (
        jnp.einsum("bthd,bshd->bhts", q[..., :nope], kv[..., :nope])
        + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope[:, :, 0, :])
    ) / np.sqrt(nope + rp)
    t = np.arange(s)
    keep = np.broadcast_to((t[None, :] <= t[:, None])[None], (b, s, s))
    if sliding:
        keep = keep & ((t[:, None] - t[None, :]) < int(cfg["sliding_window_size"]))[None]
    keep = jnp.asarray(keep)
    scores = None
    if "indexer" in layer:
        ix = layer["indexer"]
        idim = int(cfg["index_head_dim"])
        hc, cc = jax.lax.stop_gradient(h), jax.lax.stop_gradient(c_q)
        q_i = (cc @ _f32(ix["w_q"])).reshape(b, s, -1, idim)
        k_i = layer_norm(hc @ _f32(ix["w_k"]), ix["k_norm_scale"], ix["k_norm_bias"], eps)
        w_i = hc @ _f32(ix["w_w"]) / np.sqrt(q_i.shape[2])
        scores = jnp.einsum(
            "btj,btjs->bts", w_i, jax.nn.relu(jnp.einsum("btjd,bsd->btjs", q_i, k_i) / np.sqrt(idim))
        )
        topk = int(cfg["index_topk"])
        if s > topk:
            ranked = jnp.where(keep, jax.lax.stop_gradient(scores), -jnp.inf)
            kth = jnp.sort(ranked, axis=-1)[..., s - topk][..., None]
            keep = keep & (ranked >= kth)
    probs = jax.nn.softmax(jnp.where(keep[:, None], logits, -jnp.inf), axis=-1)
    aux = jnp.zeros((), jnp.float32)
    if scores is not None:
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
        logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        aux = -jnp.mean(jnp.sum(jnp.where(keep, target * logq, 0.0), axis=-1))
    out = jnp.einsum("bhts,bshd->bthd", probs, kv[..., nope:])
    out = out * jax.nn.sigmoid(h @ _f32(p["w_g"]))[..., None]
    return out.reshape(b, s, heads * vd) @ _f32(p["w_o"]), aux


def route(cfg: Dict[str, Any], router: Dict[str, Any], g):
    """Chosen experts ``[tokens, top_k]`` (ids among the router's outputs)
    and their weights."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(g.reshape(-1, g.shape[-1]) @ _f32(router["kernel"]))
    order = jnp.argsort(-(jax.lax.stop_gradient(s) + _f32(router["bias"])), axis=-1)
    chosen = order[:, : int(cfg["num_experts_per_tok"])]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * float(cfg.get("routed_scaling_factor", 1))


def routed(cfg: Dict[str, Any], moe: Dict[str, Any], g, first_expert: int = 0):
    """What the experts in the banks give (expert ``first_expert + i`` in row
    ``i``), one expert at a time, and how many tokens chose each of the
    router's experts."""
    import jax.numpy as jnp

    chosen, w = route(cfg, moe["router"], g)
    flat = g.reshape(-1, g.shape[-1])
    out = jnp.zeros_like(flat)
    for i in range(moe["experts"]["w_gate"].shape[0]):
        weight = jnp.sum(jnp.where(chosen == first_expert + i, w, 0.0), axis=-1)
        out = out + weight[:, None] * swiglu(moe["experts"], flat, expert=i)
    n = moe["router"]["kernel"].shape[1]
    load = jnp.sum(chosen[:, :, None] == jnp.arange(n)[None, None, :], axis=(0, 1)).astype(jnp.float32)
    return out.reshape(g.shape), load


def layer_forward(cfg: Dict[str, Any], layer: Dict[str, Any], x, sliding: bool, first_expert: int = 0):
    eps = float(cfg["rms_norm_eps"])
    attn, aux = attention(cfg, layer, rms_norm(x, layer["attn_norm"], eps), sliding)
    x = x + attn
    g = rms_norm(x, layer["ffn_norm"], eps)
    if "mlp" in layer:
        return x + swiglu(layer["mlp"], g), aux, None
    part, load = routed(cfg, layer["moe"], g, first_expert)
    return x + swiglu(layer["moe"]["shared"], g) + part, aux, load


def loss(cfg: Dict[str, Any], params: Dict[str, Any], tokens, first_expert: int = 0) -> Tuple[Any, List[Any]]:
    """The loss of ``tokens`` ``[b, s]`` and each layer's expert loads
    (``None`` for a dense layer).  A mean over tokens: over blocks of whole
    sequences of one length it is the mean of the blocks' losses."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = _f32(params["embed"]["tokens"])[inputs]
        aux_total = jnp.zeros((), jnp.float32)
        loads = []
        for i, layer in enumerate(params["layers"]):
            sliding = cfg["layer_types"][i] == "sliding_attention"
            x, aux, load = layer_forward(cfg, layer, x, sliding, first_expert)
            aux_total = aux_total + aux
            loads.append(load)
        x = rms_norm(x, params["final_norm"], float(cfg["rms_norm_eps"]))
        logp = jax.nn.log_softmax(x @ _f32(params["output"]["kernel"]), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll) + aux_total, loads
