"""Flagship model: Llama-style decoder-only transformer, TPU-first.

This is the workload the framework is benchmarked against (BASELINE.md north
star: checkpoint an FSDP-sharded Llama-3-8B from a v5e-16; the reference's
FSDP benchmark uses a 1.9B transformer, /root/reference/benchmarks/fsdp/main.py:35-72).
Design is idiomatic JAX, not a port:

- pure-function forward over a pytree of params (checkpointing sees exactly
  what training sees: a pytree of sharded jax.Arrays)
- layers stacked and iterated with ``lax.scan`` (one compiled layer body;
  compile time independent of depth) with ``jax.checkpoint`` rematerialization
- bf16 activations / fp32 params+optimizer (MXU-friendly), RoPE, RMSNorm,
  SwiGLU, grouped-query attention
- GSPMD sharding rules as per-param PartitionSpecs over a
  (data, fsdp, model) mesh; sequence-parallel activation sharding via
  ``with_sharding_constraint``
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16  # activation dtype
    param_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            d_ff=14336,
        )

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab_size,
            d_model=128,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            d_ff=256,
        )

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        kv = self.n_kv_heads * self.head_dim
        per_layer = d * d + 2 * d * kv + d * d + 3 * d * f + d
        return v * d + self.n_layers * per_layer + d + v * d


def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Stacked-layer param pytree: every per-layer weight carries a leading
    ``n_layers`` axis so the whole stack is one sharded array per role."""
    k_embed, k_attn, k_mlp, k_out = jax.random.split(key, 4)
    d, f, v, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    kv = cfg.n_kv_heads * cfg.head_dim
    scale = 1.0 / np.sqrt(d)

    def nrm(k, shape, s=scale):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * s).astype(
            cfg.param_dtype
        )

    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    return {
        "embed": {"tokens": nrm(k_embed, (v, d), 1.0)},
        "layers": {
            "attn": {
                "wq": nrm(ka[0], (L, d, d)),
                "wk": nrm(ka[1], (L, d, kv)),
                "wv": nrm(ka[2], (L, d, kv)),
                "wo": nrm(ka[3], (L, d, d)),
            },
            "mlp": {
                "w_gate": nrm(km[0], (L, d, f)),
                "w_up": nrm(km[1], (L, d, f)),
                "w_down": nrm(km[2], (L, f, d), 1.0 / np.sqrt(f)),
            },
            "attn_norm": jnp.ones((L, d), dtype=cfg.param_dtype),
            "mlp_norm": jnp.ones((L, d), dtype=cfg.param_dtype),
        },
        "final_norm": jnp.ones((d,), dtype=cfg.param_dtype),
        "output": {"kernel": nrm(k_out, (d, v))},
    }


def param_partition_specs(
    cfg: LlamaConfig, model_axis_size: Optional[int] = None
) -> Dict[str, Any]:
    """FSDP+TP sharding rules over axes (data, fsdp, model).

    TP shards attention heads / ff; FSDP shards the complementary dim so the
    two compose; norms replicate.  The same pytree-of-specs drives both
    train-state placement and checkpoint metadata.

    Grouped-query exception: when ``n_kv_heads`` does not divide the tensor
    axis (pass ``model_axis_size`` to enable the check), the KV projections
    keep their output dim replicated — head-sharding an axis-indivisible KV
    output forces XLA into involuntary full rematerialization inside
    attention, and replicating narrow KV heads across tensor ranks is the
    standard GQA-TP layout.  Callers on a TP mesh must pass the same
    ``model_axis_size`` everywhere (placement AND any spec-derived
    metadata): with the default ``None`` the KV output dim stays
    model-sharded, which disagrees with what ``init_train_state`` applied
    on an indivisible mesh.
    """
    kv_out = "model"
    if model_axis_size and cfg.n_kv_heads % model_axis_size != 0:
        kv_out = None
    return {
        "embed": {"tokens": P("model", "fsdp")},
        "layers": {
            "attn": {
                "wq": P(None, "fsdp", "model"),
                "wk": P(None, "fsdp", kv_out),
                "wv": P(None, "fsdp", kv_out),
                "wo": P(None, "model", "fsdp"),
            },
            "mlp": {
                "w_gate": P(None, "fsdp", "model"),
                "w_up": P(None, "fsdp", "model"),
                "w_down": P(None, "model", "fsdp"),
            },
            "attn_norm": P(None, "fsdp"),
            "mlp_norm": P(None, "fsdp"),
        },
        "final_norm": P("fsdp"),
        "output": {"kernel": P("fsdp", "model")},
    }


def _rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    # x: [B, S, H, Dh]
    half = x.shape[-1] // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return rotated.astype(x.dtype)


def _attention(
    q: jax.Array, k: jax.Array, v: jax.Array, n_rep: int
) -> jax.Array:
    # q: [B, S, Hq, Dh], k/v: [B, S, Hkv, Dh]; grouped-query broadcast
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = q.shape[1]
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    logits = jnp.where(mask[None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _layer_body(
    cfg: LlamaConfig,
    x: jax.Array,
    layer: Dict[str, Any],
    positions: jax.Array,
    constrainers=None,
    ring=None,
) -> jax.Array:
    d = cfg.d_model
    head_constrain = gather_constrain = None
    if constrainers is not None:
        head_constrain, gather_constrain = constrainers
    h = _rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = (h @ layer["attn"]["wq"].astype(cfg.dtype)).reshape(
        *h.shape[:2], cfg.n_heads, cfg.head_dim
    )
    n_rep = cfg.n_heads // cfg.n_kv_heads
    hkv = h
    if gather_constrain is not None and n_rep > 1 and ring is None:
        # Grouped-query KV under sequence+tensor parallelism: n_kv_heads may
        # not divide the tensor axis, and XLA has no efficient lowering for
        # an axis-indivisible seq-shard -> head-shard transition across the
        # 4-D reshape/repeat (involuntary full rematerialization).  Instead,
        # all-gather the *input* of the KV projections over seq (the
        # Megatron sequence-parallel recipe — its transpose is a clean
        # reduce-scatter, so the backward pass stays efficient too); the
        # projection, reshape, GQA expansion and head slice are then local.
        hkv = gather_constrain(h)
    kp = hkv @ layer["attn"]["wk"].astype(cfg.dtype)
    vp = hkv @ layer["attn"]["wv"].astype(cfg.dtype)
    k = kp.reshape(*h.shape[:2], cfg.n_kv_heads, cfg.head_dim)
    v = vp.reshape(*h.shape[:2], cfg.n_kv_heads, cfg.head_dim)
    if (head_constrain is not None or ring is not None) and n_rep > 1:
        # rope is per-head, so it commutes with the GQA repeat.
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
        n_rep = 1
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    if ring is not None:
        # Long-context path: exact causal attention with KV shards rotating
        # around the sequence axis ring (O(S/n) memory per device, ICI-ring
        # transfers) — models/ring_attention.py.
        from .ring_attention import ring_attention

        mesh, seq_axis, batch_axis = ring
        attn = ring_attention(q, k, v, mesh, seq_axis, batch_axis=batch_axis)
    else:
        if head_constrain is not None:
            # Single constraint point per tensor: all three enter attention
            # head-sharded (a seq-sharded v against head-sharded q/k would
            # reintroduce the indivisible transition inside the einsum).
            q, k, v = head_constrain(q), head_constrain(k), head_constrain(v)
        attn = _attention(q, k, v, n_rep)
    attn = attn.reshape(*h.shape[:2], d)
    x = x + attn @ layer["attn"]["wo"].astype(cfg.dtype)

    h = _rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    gate = jax.nn.silu(h @ layer["mlp"]["w_gate"].astype(cfg.dtype))
    up = h @ layer["mlp"]["w_up"].astype(cfg.dtype)
    x = x + (gate * up) @ layer["mlp"]["w_down"].astype(cfg.dtype)
    return x


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    activation_spec: Optional[P] = None,
    ring: Optional[tuple] = None,
) -> jax.Array:
    """Logits for next-token prediction.  ``activation_spec`` (e.g.
    P("data", "model") for sequence parallelism on the seq dim) constrains
    activation sharding so XLA lays collectives on ICI.

    ``ring=(mesh, seq_axis, batch_axis)`` switches attention to the ring
    formulation (models/ring_attention.py): the context-parallel layout for
    long sequences, where KV blocks rotate around the seq axis instead of
    any device materializing full-sequence KV."""

    def constrain(x: jax.Array) -> jax.Array:
        if activation_spec is not None:
            return jax.lax.with_sharding_constraint(
                x, activation_spec
            )
        return x

    # Sequence parallelism reuses the tensor axis for the seq dim between
    # blocks; inside attention the same axis must shard heads instead.  Make
    # that transition explicit on the [B, S, H, Dh] tensors so XLA routes it
    # as a collective rather than an involuntary full rematerialization.
    constrainers = None
    if activation_spec is not None and len(activation_spec) >= 2:
        head_spec = P(activation_spec[0], None, activation_spec[1], None)
        gather_spec = P(activation_spec[0], None, None)

        def _to_heads(t: jax.Array) -> jax.Array:
            return jax.lax.with_sharding_constraint(t, head_spec)

        def _gather_seq(t: jax.Array) -> jax.Array:
            return jax.lax.with_sharding_constraint(t, gather_spec)

        constrainers = (_to_heads, _gather_seq)

    x = params["embed"]["tokens"].astype(cfg.dtype)[tokens]
    x = constrain(x)
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1]), tokens.shape
    )

    def scan_body(carry: jax.Array, layer: Dict[str, Any]):
        y = _layer_body(cfg, carry, layer, positions, constrainers, ring)
        return constrain(y), None

    x, _ = jax.lax.scan(
        jax.checkpoint(scan_body), x, params["layers"]
    )
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["output"]["kernel"].astype(cfg.dtype)
    return logits


def loss_fn(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    activation_spec: Optional[P] = None,
    ring: Optional[tuple] = None,
) -> jax.Array:
    if ring is not None:
        # shard_map needs the seq dim divisible by the ring axis; keep the
        # full (divisible) length through the model and drop the final
        # position's logits instead of slicing the inputs.
        logits = forward(params, tokens, cfg, activation_spec, ring)[:, :-1]
    else:
        logits = forward(params, tokens[:, :-1], cfg, activation_spec, ring)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)


def make_train_step(
    cfg: LlamaConfig,
    optimizer: Any,
    activation_spec: Optional[P] = None,
    ring: Optional[tuple] = None,
):
    """Returns train_step(train_state, tokens) -> (train_state, loss) — a pure
    jittable function over {params, opt_state, step}.  ``ring`` enables the
    context-parallel ring-attention layout (see forward)."""

    def train_step(train_state: Dict[str, Any], tokens: jax.Array):
        loss, grads = jax.value_and_grad(loss_fn)(
            train_state["params"], tokens, cfg, activation_spec, ring
        )
        updates, opt_state = optimizer.update(
            grads, train_state["opt_state"], train_state["params"]
        )
        params = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), train_state["params"], updates
        )
        return {
            "params": params,
            "opt_state": opt_state,
            "step": train_state["step"] + 1,
        }, loss

    return train_step


def init_train_state(
    key: Optional[jax.Array], cfg: LlamaConfig, optimizer: Any, mesh: Mesh
) -> Dict[str, Any]:
    """A {params, opt_state, step} train state born sharded on ``mesh`` per
    the partition rules (optimizer moments inherit their param's spec): the
    init runs under ``jit`` with ``out_shardings``, so no device ever holds
    more than its share.  ``key=None`` gives the all-zeros state of the same
    structure and shardings: a restore target."""

    def build(k: jax.Array) -> Dict[str, Any]:
        params = init_params(k, cfg)
        return {
            "params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    abstract = jax.eval_shape(build, jax.random.key(0))
    specs = state_partition_specs(
        abstract, cfg, model_axis_size=mesh.shape.get("model")
    )
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    if key is None:
        return jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), abstract
            ),
            out_shardings=shardings,
        )()
    return jax.jit(build, out_shardings=shardings)(key)


def state_partition_specs(
    train_state: Dict[str, Any],
    cfg: LlamaConfig,
    model_axis_size: Optional[int] = None,
):
    """PartitionSpec pytree matching a {params, opt_state, step} train state.

    Optimizer moments structurally embed the param tree (optax's Adam state
    holds mu/nu shaped like params), so each opt-state leaf inherits the spec
    of the param whose tree path is a suffix of its own path; everything else
    (counts, scalars) replicates.
    """
    param_specs = param_partition_specs(cfg, model_axis_size=model_axis_size)

    spec_by_path = {
        _path_str(path): spec
        for path, spec in jax.tree_util.tree_flatten_with_path(
            param_specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }

    def opt_leaf_spec(path, leaf: Any) -> P:
        p = _path_str(path)
        for param_path, spec in spec_by_path.items():
            if p.endswith(param_path):
                return spec
        return P()

    opt_specs = jax.tree_util.tree_map_with_path(
        opt_leaf_spec, train_state["opt_state"]
    )
    return {
        "params": param_specs,
        "opt_state": opt_specs,
        "step": P(),
    }


def _path_str(path) -> str:
    return "/" + "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
