"""Flagship demo workload: a Llama-style decoder-only transformer in pure JAX.

The reference ships a toy PyTorch training loop for its end-to-end trace demo
(scripts/pytorch/linear_model_example.py); the TPU build's demo workload is a
realistic transformer so captured XLA traces and benchmark numbers reflect
the north-star scenario (Llama-style JAX training, BASELINE.md). It is
written TPU-first: bfloat16 matmuls for the MXU, static shapes, RMSNorm +
RoPE + SwiGLU fused by XLA, and sharding-annotation-driven parallelism (see
dynolog_tpu.parallel.sharding).

This is a *workload*, not a modeling library: the monitoring framework only
observes it.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp


LAYER_TYPES = ("full_attention", "linear_attention")


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 1024
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 704  # ~8/3 * d_model, rounded to a multiple of 64 for tiling
    max_seq_len: int = 512
    # None: no rotary embedding, as a published config states it
    # (`rope_parameters.rope_theta: null`)
    rope_theta: float | None = 10000.0
    dtype: str = "bfloat16"
    # "reference": plain-XLA attention; "flash": Pallas MXU kernel
    # (dynolog_tpu.ops.flash_attention); "ring": sequence-parallel ring
    # attention over the mesh's seq axis (requires a mesh at call time).
    attn_impl: str = "reference"
    # RMSNorm's epsilon, and whether q and k are normalised (a learned
    # scale over the whole d_model of each, before the heads are split and
    # RoPE applied), as published configs state them.
    norm_eps: float = 1e-6
    qk_norm: bool = False
    # MoE: n_experts > 0 replaces every dense MLP with a dropless
    # top-k-routed mixture of SwiGLU experts (dynolog_tpu.models.moe),
    # expert-parallel over the mesh's `expert` axis. The loss gains
    # moe_aux_weight x the balancing term (over first choices, or over all
    # k where moe_balance_all_k) + moe_z_weight x the router z-loss, each a
    # mean over layers; moe_norm_topk renormalises a token's k gates.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk: bool = True
    moe_aux_weight: float = 0.01
    moe_balance_all_k: bool = False
    moe_z_weight: float = 0.0
    # A hybrid model: each layer's kind, "full_attention" or
    # "linear_attention" (a gated-delta-net layer,
    # dynolog_tpu.models.linear_attention, over n_heads heads of
    # linear_key_head_dim / linear_value_head_dim), as a published config
    # lists them. None: every layer is full attention.
    layer_types: tuple | None = None
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    linear_allow_neg_eigval: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            return
        # a list from JSON: the configuration keys a jitted function
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(LAYER_TYPES)
        if unknown or len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types {self.layer_types}: one of {LAYER_TYPES} for "
                f"each of the {self.n_layers} layers")

    def is_linear(self, i: int) -> bool:
        return (self.layer_types is not None
                and self.layer_types[i] == "linear_attention")

    @property
    def has_linear_layers(self) -> bool:
        return any(self.is_linear(i) for i in range(self.n_layers))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def llama_8b_like(cls) -> "TransformerConfig":
        """Shape class of the north-star workload (not meant to fit on one
        test chip; used for multi-chip dry-run configs scaled down)."""
        return cls(
            vocab_size=128256,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            d_ff=14336,
            max_seq_len=8192,
        )


def init_params(rng, cfg: TransformerConfig):
    """Returns a pytree: {embedding, layers: [...], final_scale, w_out}."""
    dtype = jnp.dtype(cfg.dtype)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)

    keys = jax.random.split(rng, cfg.n_layers + 2)
    params = {
        "embedding": dense(keys[0], (cfg.vocab_size, cfg.d_model), cfg.d_model),
        "w_out": dense(keys[1], (cfg.d_model, cfg.vocab_size), cfg.d_model),
        "final_scale": jnp.ones((cfg.d_model,), dtype),
        "layers": [],
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 7)
        d, f = cfg.d_model, cfg.d_ff
        layer = {
            "attn_scale": jnp.ones((d,), dtype),
            "mlp_scale": jnp.ones((d,), dtype),
        }
        if cfg.is_linear(i):
            from dynolog_tpu.models.linear_attention import init_linear_layer

            layer.update(init_linear_layer(k[0], cfg))
        else:
            layer.update(
                wq=dense(k[0], (d, d), d), wk=dense(k[1], (d, d), d),
                wv=dense(k[2], (d, d), d), wo=dense(k[3], (d, d), d))
            if cfg.qk_norm:
                layer.update(q_scale=jnp.ones((d,), dtype),
                             k_scale=jnp.ones((d,), dtype))
        if cfg.n_experts > 0:
            from dynolog_tpu.models.moe import init_moe_layer

            layer.update(init_moe_layer(k[4], cfg))
        else:
            layer.update(
                {
                    "w_gate": dense(k[4], (d, f), d),
                    "w_up": dense(k[5], (d, f), d),
                    "w_down": dense(k[6], (f, d), f),
                }
            )
        params["layers"].append(layer)
    return params


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def _rope(x, positions, theta):
    """Rotary embeddings over the last (head_dim) axis. x: [B, S, H, D]."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(layer, x, positions, cfg: TransformerConfig, mesh=None):
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    q, k = x @ layer["wq"], x @ layer["wk"]
    if cfg.qk_norm:
        q = _rmsnorm(q, layer["q_scale"], cfg.norm_eps)
        k = _rmsnorm(k, layer["k_scale"], cfg.norm_eps)
    q, k = q.reshape(b, s, h, hd), k.reshape(b, s, h, hd)
    v = (x @ layer["wv"]).reshape(b, s, h, hd)
    if cfg.rope_theta is not None:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)

    if cfg.attn_impl == "flash":
        from dynolog_tpu.ops.flash_attention import flash_attention

        def attn(q, k, v):
            return flash_attention(q, k, v, True)

        if mesh is not None:
            # A Mosaic kernel is opaque to the SPMD partitioner ("cannot be
            # automatically partitioned"), so under a mesh each device runs
            # it on its own batch rows and heads.
            from jax.sharding import PartitionSpec as P

            from dynolog_tpu.parallel.sharding import BATCH_AXES

            spec = P(BATCH_AXES, None, "model", None)
            attn = jax.shard_map(
                attn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)
        out = attn(q, k, v).reshape(b, s, d)
    elif cfg.attn_impl == "ring":
        from dynolog_tpu.parallel.ring_attention import ring_attention

        if mesh is None:
            raise ValueError("attn_impl='ring' requires a mesh")
        out = ring_attention(q, k, v, mesh, causal=True).reshape(b, s, d)
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(hd).astype(x.dtype)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None, None], scores.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
    return out @ layer["wo"]


def _mlp(layer, x):
    gate = jax.nn.silu(x @ layer["w_gate"])
    return (gate * (x @ layer["w_up"])) @ layer["w_down"]


def _forward_with_aux(params, tokens, cfg: TransformerConfig, mesh=None):
    """tokens [B, S] int32 → (logits [B, S, vocab] f32, the expert layers'
    weighted loss terms, a mean over layers: 0 for a dense model)."""
    x = params["embedding"][tokens]
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
    )
    aux = jnp.zeros((), jnp.float32)
    for i, layer in enumerate(params["layers"]):
        h = _rmsnorm(x, layer["attn_scale"], cfg.norm_eps)
        if cfg.is_linear(i):
            from dynolog_tpu.models.linear_attention import gated_delta_net

            x = x + gated_delta_net(layer, h, cfg)
        else:
            x = x + _attention(layer, h, positions, cfg, mesh)
        h = _rmsnorm(x, layer["mlp_scale"], cfg.norm_eps)
        if cfg.n_experts > 0:
            from dynolog_tpu.models.moe import moe_mlp

            y, balance, z = moe_mlp(layer, h, cfg, mesh)
            aux = aux + (cfg.moe_aux_weight * balance
                         + cfg.moe_z_weight * z) / cfg.n_layers
        else:
            y = _mlp(layer, h)
        x = x + y
    x = _rmsnorm(x, params["final_scale"], cfg.norm_eps)
    return (x @ params["w_out"]).astype(jnp.float32), aux


def forward(params, tokens, cfg: TransformerConfig, mesh=None):
    """tokens [B, S] int32 → logits [B, S, vocab] float32."""
    return _forward_with_aux(params, tokens, cfg, mesh)[0]


def loss_fn(params, tokens, cfg: TransformerConfig, mesh=None):
    """Next-token cross entropy (tokens serve as their own shifted targets).

    The full [B, S] sequence is forwarded and the last-position logits
    dropped afterwards — keeping S intact through the model so the
    sequence axis stays evenly shardable (ring attention / sp mesh). With
    MoE enabled the expert layers' balancing and z terms are added, under
    cfg.moe_aux_weight and cfg.moe_z_weight."""
    logits, aux = _forward_with_aux(params, tokens, cfg, mesh)
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)
    return jnp.mean(nll) + aux


@partial(jax.jit, static_argnames=("cfg",))
def jit_forward(params, tokens, cfg: TransformerConfig):
    return forward(params, tokens, cfg)
