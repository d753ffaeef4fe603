"""The benchmark's own load: a dense decoder (RMSNorm, RoPE, grouped-query
attention, SwiGLU, untied head) trained with AdamW, as one chip of a job
holds it.  It is the job whose state the library under test saves and
restores, so it is traffic generation: its leaves, their sizes and dtypes and
how they are split into statefuls are the work of every cell.  It is not the
system under test, so it lives here, where a later PR cannot change the work.

Derived from ``torchsnapshot_tpu/models/llama.py`` at commit 44de14b (same
parameter tree, same stacked layers under ``lax.scan`` with rematerialised
layer bodies, bf16 activations), without its mesh rules: a cell of this
builder runs on one device.  A configuration names this builder by the
dotted path in its file (``"builder": "chipbench.models.dense_decoder:build"``);
a configuration that needs another architecture or a mesh brings another
builder file.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np


class Load:
    """What a job needs of a model: a state from a seed, a zeroed target of
    the same structure, the state as the statefuls a trainer hands the
    library and back, a compiled step, token batches from a seed."""

    def __init__(self, cfg: Dict[str, Any], devices: Sequence[Any]) -> None:
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
        self.head_dim = int(cfg.get("head_dim") or self.d // self.heads)
        if self.head_dim * self.heads != self.d:
            raise ValueError("this builder needs head_dim * heads == hidden_size")
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        # The train state's dtype is the configuration's stated one, which
        # need not be the published checkpoint's ``torch_dtype``.
        self.param_dtype = jnp.dtype(cfg["state_dtypes"]["params"])
        self.act_dtype = jnp.dtype(cfg.get("activation_dtype", "bfloat16"))
        assumed = cfg["assumed"]
        self.batch = int(assumed["batch_sequences"])
        self.seq = int(assumed["sequence_length"])
        self.opt = optax.adamw(
            float(assumed["learning_rate"]),
            b1=float(assumed["adam_b1"]),
            b2=float(assumed["adam_b2"]),
            eps=float(assumed["adam_eps"]),
            weight_decay=float(assumed["weight_decay"]),
        )
        # The devices the state lives on: one, whatever the cell holds.
        self.devices = list(devices[:1])
        self.sharding = NamedSharding(Mesh(np.array(self.devices), ("d",)), P())
        self._abstract = jax.eval_shape(self._build, jax.random.key(0))
        self._step = None

    # ------------------------------------------------------------- the state

    def _init_params(self, key):
        import jax
        import jax.numpy as jnp

        d, f, v, L = self.d, self.f, self.v, self.layers
        kv = self.kv_heads * self.head_dim
        k_embed, k_attn, k_mlp, k_out = jax.random.split(key, 4)
        ka = jax.random.split(k_attn, 4)
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
                    "wq": nrm(ka[0], (L, d, d), s),
                    "wk": nrm(ka[1], (L, d, kv), s),
                    "wv": nrm(ka[2], (L, d, kv), s),
                    "wo": nrm(ka[3], (L, d, d), s),
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

    def _build(self, key):
        import jax.numpy as jnp

        params = self._init_params(key)
        return {
            "params": params,
            "opt_state": self.opt.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    def _shardings(self):
        import jax

        return jax.tree.map(lambda _: self.sharding, self._abstract)

    def init_state(self, seed: int):
        """The train state on the device, in one jitted call from the seed,
        in the dtypes the configuration states."""
        import jax

        key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
        return jax.jit(self._build, out_shardings=self._shardings())(key)

    def zero_state(self):
        """The all-zeros state of the same structure: a restore target."""
        import jax
        import jax.numpy as jnp

        abstract = self._abstract
        return jax.jit(
            lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract),
            out_shardings=self._shardings(),
        )()

    def split(self, state):
        """params / mu / nu / progress as four statefuls (``chip_smoke.py`` at
        commit 44de14b), so that restore's target-plus-landed-copy peak is
        state x 4/3 and not state x 2."""
        from torchsnapshot_tpu import StateDict

        adam = state["opt_state"][0]
        return {
            "params": StateDict(params=state["params"]),
            "adam_mu": StateDict(mu=adam.mu),
            "adam_nu": StateDict(nu=adam.nu),
            "progress": StateDict(step=state["step"], adam_count=adam.count),
        }

    def join(self, app_state):
        """The train state back from the four statefuls."""
        import jax

        like = jax.tree.map(lambda _: None, self._abstract["opt_state"])
        adam = like[0]._replace(
            count=app_state["progress"]["adam_count"],
            mu=app_state["adam_mu"]["mu"],
            nu=app_state["adam_nu"]["nu"],
        )
        return {
            "params": app_state["params"]["params"],
            "opt_state": (adam,) + tuple(like[1:]),
            "step": app_state["progress"]["step"],
        }

    def state_bytes(self) -> int:
        import jax

        return int(
            sum(
                int(np.prod(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(self._abstract)
            )
        )

    def abstract_state(self):
        return self._abstract

    def token_pool(self, seed: int, n: int):
        """``n`` batches of token ids on the device, from the seed; every row
        differs."""
        import jax
        import jax.numpy as jnp

        key = jax.random.fold_in(jax.random.key(np.uint32(seed & 0xFFFFFFFF)), 7)
        make = jax.jit(
            lambda k: jax.random.randint(
                k, (n, self.batch, self.seq), 0, self.v, dtype=jnp.int32
            ),
            out_shardings=self.sharding,
        )
        return make(key)

    # ------------------------------------------------------------- the model

    def _rms_norm(self, x, w):
        import jax
        import jax.numpy as jnp

        dtype = x.dtype
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        return (x * w.astype(jnp.float32)).astype(dtype)

    def _rope(self, x, positions):
        import jax.numpy as jnp

        half = x.shape[-1] // 2
        freqs = 1.0 / (self.theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
        angles = positions[..., None].astype(jnp.float32) * freqs
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.astype(x.dtype)

    def _layer(self, x, layer, positions):
        import jax
        import jax.numpy as jnp

        dt = self.act_dtype
        b, s = x.shape[:2]
        h = self._rms_norm(x, layer["attn_norm"])
        q = (h @ layer["attn"]["wq"].astype(dt)).reshape(b, s, self.heads, self.head_dim)
        k = (h @ layer["attn"]["wk"].astype(dt)).reshape(b, s, self.kv_heads, self.head_dim)
        v = (h @ layer["attn"]["wv"].astype(dt)).reshape(b, s, self.kv_heads, self.head_dim)
        q = self._rope(q, positions)
        k = self._rope(k, positions)
        rep = self.heads // self.kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(self.head_dim)
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(dt)
        attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, self.d)
        x = x + attn @ layer["attn"]["wo"].astype(dt)
        h = self._rms_norm(x, layer["mlp_norm"])
        gate = jax.nn.silu(h @ layer["mlp"]["w_gate"].astype(dt))
        up = h @ layer["mlp"]["w_up"].astype(dt)
        return x + (gate * up) @ layer["mlp"]["w_down"].astype(dt)

    def _loss(self, params, tokens):
        import jax
        import jax.numpy as jnp

        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x = params["embed"]["tokens"].astype(self.act_dtype)[inputs]
        positions = jnp.broadcast_to(jnp.arange(inputs.shape[1]), inputs.shape)

        def body(carry, layer):
            return self._layer(carry, layer, positions), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
        x = self._rms_norm(x, params["final_norm"])
        logits = x @ params["output"]["kernel"].astype(self.act_dtype)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll)

    def _train_step(self, state, tokens):
        import jax

        loss, grads = jax.value_and_grad(self._loss)(state["params"], tokens)
        updates, opt_state = self.opt.update(grads, state["opt_state"], state["params"])
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), state["params"], updates)
        return {"params": params, "opt_state": opt_state, "step": state["step"] + 1}, loss

    def _jit_step(self, sharding):
        import jax

        out = (jax.tree.map(lambda _: sharding, self._abstract), sharding)
        return jax.jit(self._train_step, donate_argnums=(0,), out_shardings=out)

    def step_fn(self):
        """The compiled, donating train step: ``(state, tokens) -> (state,
        loss)``.  One object for the whole process."""
        if self._step is None:
            self._step = self._jit_step(self.sharding)
        return self._step

    def lower_step(self, sharding: Any):
        """The step lowered for ``sharding`` (a described device in the
        rehearsal test) at the configuration's own shapes."""
        import jax
        import jax.numpy as jnp

        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), self._abstract
        )
        tokens = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32, sharding=sharding)
        return self._jit_step(sharding).lower(state, tokens)


def build(cfg: Dict[str, Any], devices: Sequence[Any]) -> Load:
    return Load(cfg, devices)
