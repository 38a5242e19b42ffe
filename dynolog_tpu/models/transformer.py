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

import contextlib
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp


LAYER_TYPES = ("full_attention", "linear_attention", "kda",
               "sliding_attention")
# the layer kinds whose mixer is a recurrence of
# dynolog_tpu.models.linear_attention, which is also the mixer's kind
LINEAR_TYPES = ("linear_attention", "kda")
BLOCK_TYPES = ("mamba2", "moe", "attention", "mlp")
# A mixer's kind -> (its norm's scale in the layer, the scope that norm runs
# under: a mixer's norm goes with the phase it feeds).
MIXER_NORMS = {
    "attention": ("attn_scale", "attn"),
    "mla": ("attn_scale", "mla.project"),
    "linear_attention": ("attn_scale", "gdn.project"),
    "kda": ("attn_scale", "kda.project"),
    "mamba2": ("ssm_scale", "ssm.project"),
    "mlp": ("mlp_scale", "mlp"),
    "moe": ("mlp_scale", "moe.route"),
}
# Under cfg.post_norm, a mixer's kind -> (the scale of the norm over what it
# adds, the scope that norm runs under: that of the phase it follows).
POST_NORMS = {
    "attention": ("attn_post_scale", "attn"),
    "mlp": ("mlp_post_scale", "mlp"),
    "moe": ("mlp_post_scale", "moe.combine"),
}
ROPE_SCALING_KEYS = ("type", "factor", "original_max_position_embeddings",
                     "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


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
    # Adam's step size where `make_train_step` is given none: a job's, as a
    # configuration states it (a job at the first step of a linear warm-up
    # has its maximum over the warm-up's steps).
    learning_rate: float = 3e-4
    # "reference": plain-XLA attention; "flash": Pallas MXU kernel
    # (dynolog_tpu.ops.flash_attention); "ring": sequence-parallel ring
    # attention over the mesh's seq axis (requires a mesh at call time).
    attn_impl: str = "reference"
    # RMSNorm's epsilon, and whether q and k are normalised (a learned
    # scale over the whole d_model of each, before the heads are split and
    # RoPE applied), as published configs state them.
    norm_eps: float = 1e-6
    qk_norm: bool = False
    # MoE: n_experts > 0 replaces the dense MLP of every layer after the
    # first `first_dense_layers` with a dropless top-k-routed mixture of
    # SwiGLU experts (dynolog_tpu.models.moe), expert-parallel over the
    # mesh's `expert` axis. The loss gains moe_aux_weight x the balancing
    # term (over first choices, or over all k where moe_balance_all_k; over
    # the step's tokens, or a sequence at a time and then averaged where
    # moe_seq_aux) + moe_z_weight x the router z-loss, each a mean over the
    # expert layers; moe_norm_topk renormalises a token's k gates.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk: bool = True
    moe_aux_weight: float = 0.01
    moe_balance_all_k: bool = False
    moe_seq_aux: bool = False
    moe_z_weight: float = 0.0
    first_dense_layers: int = 0
    # an expert's width where it is not d_ff (0: d_ff), and the experts
    # every token visits beside those it is routed to: one SwiGLU of width
    # n_shared_experts x the expert width, added to the routed sum
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    # One chip's share of an expert-parallel layer: the router scores all
    # n_experts and a token keeps its moe_top_k, but only the
    # n_experts_held experts from index first_expert_held on exist here;
    # a choice that falls on another adds nothing. 0: every expert.
    n_experts_held: int = 0
    first_expert_held: int = 0
    # "mha": q, k, v of d_model / n_heads a head. "mla": latent attention
    # (dynolog_tpu.models.mla): keys and values expanded from a compressed
    # latent of kv_lora_rank, a rotary part of qk_rope_head_dim shared by
    # the heads beside qk_nope_head_dim without position, values of
    # v_head_dim.
    attn_type: str = "mha"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN, as a published config's `rope_scaling` group states it (a dict
    # from JSON; ROPE_SCALING_KEYS): the rotary frequencies blended between
    # theta's and theta's over `factor`, and the softmax scale times
    # (0.1 mscale_all_dim ln factor + 1)^2. None: plain RoPE.
    rope_scaling: tuple | None = None
    # A hybrid model: each layer's kind, "full_attention" (latent attention
    # where attn_type is "mla"), "linear_attention" (a gated-delta-net
    # layer) or "kda" (a Kimi Delta Attention layer: the same rule with a
    # decay a channel; both dynolog_tpu.models.linear_attention, over
    # n_heads heads of linear_key_head_dim / linear_value_head_dim), as a
    # published config lists them. None: every layer is full attention.
    layer_types: tuple | None = None
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    linear_allow_neg_eigval: bool = False
    # A model of one-mixer blocks: each block's kind in order, one of
    # BLOCK_TYPES, as many as n_layers ("mamba2": a Mamba-2 state-space
    # block, dynolog_tpu.models.mamba2; "moe": the expert layer;
    # "attention"; "mlp": a plain MLP of d_ff). A block is
    # x + mixer(norm(x)) with ONE norm, where a layer is an attention-kind
    # mixer and then an MLP-kind mixer. None: every layer is that pair.
    block_types: tuple | None = None
    # Attention's key/value heads where they are fewer than the query heads
    # (query head j reads key/value head j // (n_heads / n_kv_heads)), and
    # a head's width where it is not d_model / n_heads. 0: as n_heads has it.
    n_kv_heads: int = 0
    attn_head_dim: int = 0
    # A "mamba2" block: ssm_heads heads of ssm_head_dim, their B and C
    # shared by ssm_heads / ssm_groups heads a group, a state of ssm_state a
    # channel, a causal convolution of ssm_conv_kernel taps, the recurrence
    # computed in chunks of ssm_chunk positions.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # The MLP's and the experts' activation: "swiglu" (silu(x W_gate) *
    # (x W_up), three matrices) or "relu2" (relu(x W_up)^2, two).
    mlp_act: str = "swiglu"
    # The router: "softmax" over the experts, or "sigmoid" of each score
    # alone. moe_select_bias: a number an expert (`router_bias`) added to
    # the scores for the CHOICE of the k and not for their gates; no
    # gradient reaches it. moe_gate_scale multiplies the gates.
    # moe_shared_d_ff: the shared expert's width where it is not
    # n_shared_experts x the expert width.
    moe_score: str = "softmax"
    moe_select_bias: bool = False
    moe_gate_scale: float = 1.0
    moe_shared_d_ff: int = 0
    # A "sliding_attention" layer (layer_types) is attention whose query at
    # position i sees the sliding_window keys that end at i; a
    # "full_attention" layer sees every key at or before it.
    # rope_layer_types: the layer kinds whose attention is rotated where
    # not all are (("sliding_attention",): the full layers carry no
    # position). None: every attention layer, where rope_theta is stated.
    sliding_window: int = 0
    rope_layer_types: tuple | None = None
    # Attention's q and k each through an RMS norm over a head's width,
    # one learned weight shared by the heads (`q_head_scale`,
    # `k_head_scale`), before the rotary embedding; qk_norm is OLMo-2's
    # norm over the whole of q and k.
    qk_head_norm: bool = False
    # Attention's output times sigmoid(x W_g), a fourth projection (`wg`,
    # as wide as q), before the output matrix.
    attn_gate: bool = False
    # A norm over what each mixer adds beside the one over what it reads:
    # x + post_norm(mixer(norm(x))) (POST_NORMS; attention, mlp and moe).
    post_norm: bool = False
    # The embedding times sqrt(d_model) (a published `mup_enabled`).
    scale_embedding: bool = False

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            # a group from JSON: the configuration keys a jitted function
            unknown = set(self.rope_scaling) - set(ROPE_SCALING_KEYS)
            if unknown or self.rope_scaling.get("type") != "yarn":
                raise ValueError(
                    f"rope_scaling {self.rope_scaling}: type 'yarn' with "
                    f"keys among {ROPE_SCALING_KEYS}")
            object.__setattr__(
                self, "rope_scaling", tuple(sorted(self.rope_scaling.items())))
        if self.attn_type not in ("mha", "mla"):
            raise ValueError(f"attn_type {self.attn_type!r}: 'mha' or 'mla'")
        if self.mlp_act not in ("swiglu", "relu2"):
            raise ValueError(f"mlp_act {self.mlp_act!r}: 'swiglu' or 'relu2'")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_score {self.moe_score!r}: 'softmax' or 'sigmoid'")
        if self.n_heads % self.kv_heads:
            raise ValueError(
                f"{self.n_heads} query heads do not divide into groups over "
                f"{self.kv_heads} key/value heads")
        if self.block_types is not None:
            # a list from JSON: the configuration keys a jitted function
            object.__setattr__(self, "block_types", tuple(self.block_types))
            unknown = set(self.block_types) - set(BLOCK_TYPES)
            if unknown or len(self.block_types) != self.n_layers:
                raise ValueError(
                    f"block_types {self.block_types}: one of {BLOCK_TYPES} "
                    f"for each of the {self.n_layers} blocks")
            if (self.layer_types is not None or self.attn_type != "mha"
                    or self.first_dense_layers):
                raise ValueError(
                    "block_types states every block's one mixer: not beside "
                    "layer_types, latent attention or first_dense_layers")
            if "mamba2" in self.block_types and (
                    min(self.ssm_heads, self.ssm_head_dim, self.ssm_state) < 1
                    or self.ssm_heads % self.ssm_groups):
                raise ValueError(
                    f"a mamba2 block of {self.ssm_heads} heads of "
                    f"{self.ssm_head_dim}, state {self.ssm_state}, in "
                    f"{self.ssm_groups} groups")
            if "moe" in self.block_types and not self.n_experts:
                raise ValueError("a moe block of no experts (n_experts 0)")
        if self.rope_layer_types is not None:
            # a list from JSON: the configuration keys a jitted function
            object.__setattr__(
                self, "rope_layer_types", tuple(self.rope_layer_types))
            if set(self.rope_layer_types) - set(LAYER_TYPES):
                raise ValueError(
                    f"rope_layer_types {self.rope_layer_types}: among "
                    f"{LAYER_TYPES}")
        if self.post_norm and (
                self.block_types is not None or self.attn_type != "mha"):
            raise ValueError(
                f"post_norm follows the mixers of {tuple(POST_NORMS)}: not "
                "one-mixer blocks, nor latent attention")
        held = self.n_experts_held or self.n_experts
        if not 0 <= self.first_expert_held <= self.n_experts - held:
            raise ValueError(
                f"experts {self.first_expert_held} to "
                f"{self.first_expert_held + held} are not among the "
                f"{self.n_experts} the router scores")
        if self.layer_types is None:
            return
        # a list from JSON: the configuration keys a jitted function
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        unknown = set(self.layer_types) - set(LAYER_TYPES)
        if unknown or len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types {self.layer_types}: one of {LAYER_TYPES} for "
                f"each of the {self.n_layers} layers")
        if "sliding_attention" in self.layer_types and (
                self.sliding_window < 1 or self.attn_type != "mha"):
            raise ValueError(
                "layer_types names a sliding_attention layer: "
                f"sliding_window {self.sliding_window} under attn_type "
                f"{self.attn_type!r} has to be a window of 1 or more keys, "
                "on multi-head attention")
        if self.post_norm and self.has_linear_layers:
            raise ValueError(
                f"post_norm follows the mixers of {tuple(POST_NORMS)}: not a "
                f"layer of {LINEAR_TYPES}")
        if self.has_linear_layers and (
                min(self.linear_key_head_dim, self.linear_value_head_dim,
                    self.linear_conv_kernel) < 1):
            raise ValueError(
                f"layer_types names a layer of {LINEAR_TYPES}: heads of "
                f"linear_key_head_dim {self.linear_key_head_dim} and "
                f"linear_value_head_dim {self.linear_value_head_dim} under "
                f"a convolution of {self.linear_conv_kernel} taps have to "
                "be 1 or more each")
        if self.attn_type == "mla" and self.rope_layer_types is not None:
            raise ValueError(
                "rope_layer_types says which layers' multi-head attention "
                "is rotated: latent attention rotates its rotary part "
                "wherever rope_theta is stated and nowhere where it is None")

    def layer_type(self, i: int) -> str:
        return "full_attention" if self.layer_types is None else (
            self.layer_types[i])

    def window(self, i: int) -> int | None:
        """The keys a query of layer i's attention sees; None: all at or
        before it."""
        return (self.sliding_window
                if self.layer_type(i) == "sliding_attention" else None)

    def rotary(self, i: int) -> bool:
        """Whether layer i's attention is rotated."""
        return self.rope_theta is not None and (
            self.rope_layer_types is None
            or self.layer_type(i) in self.rope_layer_types)

    def is_linear(self, i: int) -> bool:
        return (self.layer_types is not None
                and self.layer_types[i] in LINEAR_TYPES)

    @property
    def has_linear_layers(self) -> bool:
        return any(self.is_linear(i) for i in range(self.n_layers))

    def is_sparse(self, i: int) -> bool:
        """Whether layer i's MLP is the expert layer."""
        return self.n_experts > 0 and i >= self.first_dense_layers

    def mixers(self, i: int) -> tuple:
        """The mixers of layer i in order, each x + mixer(norm(x)): the one
        `block_types` states, else the layer's attention-kind mixer
        ("attention", "mla", "linear_attention" or "kda") and its "mlp" or
        "moe"."""
        if self.block_types is not None:
            return (self.block_types[i],)
        first = (self.layer_types[i] if self.is_linear(i)
                 else "mla" if self.attn_type == "mla" else "attention")
        return first, "moe" if self.is_sparse(i) else "mlp"

    @property
    def n_sparse_layers(self) -> int:
        return sum("moe" in self.mixers(i) for i in range(self.n_layers))

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def shared_d_ff(self) -> int:
        return self.moe_shared_d_ff or self.n_shared_experts * self.expert_d_ff

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

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
    d, f = cfg.d_model, cfg.d_ff
    h, kv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 7)
        layer = {}
        for kind in cfg.mixers(i):
            layer[MIXER_NORMS[kind][0]] = jnp.ones((d,), dtype)
            if cfg.post_norm:
                layer[POST_NORMS[kind][0]] = jnp.ones((d,), dtype)
            if kind == "mamba2":
                from dynolog_tpu.models.mamba2 import init_mamba2_layer

                layer.update(init_mamba2_layer(k[0], cfg))
            elif kind == "linear_attention":
                from dynolog_tpu.models.linear_attention import (
                    init_linear_layer)

                layer.update(init_linear_layer(k[0], cfg))
            elif kind == "kda":
                from dynolog_tpu.models.linear_attention import init_kda_layer

                layer.update(init_kda_layer(k[0], cfg))
            elif kind == "mla":
                from dynolog_tpu.models.mla import init_mla_layer

                layer.update(init_mla_layer(k[0], cfg))
            elif kind == "attention":
                layer.update(
                    wq=dense(k[0], (d, h * hd), d),
                    wk=dense(k[1], (d, kv * hd), d),
                    wv=dense(k[2], (d, kv * hd), d),
                    wo=dense(k[3], (h * hd, d), h * hd))
                if cfg.qk_norm:
                    layer.update(q_scale=jnp.ones((h * hd,), dtype),
                                 k_scale=jnp.ones((kv * hd,), dtype))
                if cfg.qk_head_norm:
                    layer.update(q_head_scale=jnp.ones((hd,), dtype),
                                 k_head_scale=jnp.ones((hd,), dtype))
                if cfg.attn_gate:
                    layer["wg"] = dense(
                        jax.random.fold_in(k[0], 1), (d, h * hd), d)
            elif kind == "moe":
                from dynolog_tpu.models.moe import init_moe_layer

                layer.update(init_moe_layer(k[4], cfg))
            else:
                if cfg.mlp_act == "swiglu":
                    layer["w_gate"] = dense(k[4], (d, f), d)
                layer.update(w_up=dense(k[5], (d, f), d),
                             w_down=dense(k[6], (f, d), f))
        params["layers"].append(layer)
    return params


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 mscale ln(factor) + 1 (1 at factor 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_freqs(half: int, theta, scaling: dict | None = None):
    """The rotary frequencies of the `half` pairs: theta^(-i/half), and
    under YaRN the blend of that and the same over `factor`, by the linear
    ramp between the pairs that turn beta_fast and beta_slow times in the
    original_max_position_embeddings positions the model was trained on
    (the fast pairs keep their frequency, the slow ones are interpolated)."""
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if scaling is None:
        return freqs

    def pair_turning(turns):  # the pair that turns `turns` times
        return half * math.log(
            scaling["original_max_position_embeddings"]
            / (turns * 2 * math.pi)) / math.log(theta)

    low = max(math.floor(pair_turning(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(scaling["beta_slow"])), 2 * half - 1)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 0.001),
        0.0, 1.0)
    return freqs / scaling["factor"] * ramp + freqs * (1.0 - ramp)


def _rope(x, positions, theta, scaling: dict | None = None):
    """Rotary embeddings over the last (head_dim) axis. x: [B, S, H, D].
    Under YaRN (`scaling`) cos and sin are also scaled by
    yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)."""
    half = x.shape[-1] // 2
    freqs = _rope_freqs(half, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        amp = (yarn_mscale(scaling["factor"], scaling["mscale"])
               / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
        cos, sin = cos * amp, sin * amp
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _softmax_attention(q, k, v, cfg: TransformerConfig, mesh=None,
                       scale=None, window=None):
    """Causal softmax attention by cfg.attn_impl. q: [B, S, H, D], k:
    [B, S, Hkv, D], v: [B, S, Hkv, Dv] -> [B, S, H, Dv], query head j on
    key/value head j // (H / Hkv); `scale` None: D ** -0.5; `window` W: a
    query sees the W keys that end at its own position, None: all of them."""
    s, hd = q.shape[1], q.shape[-1]
    group = q.shape[2] // k.shape[2]
    if cfg.attn_impl == "flash":
        from dynolog_tpu.ops.flash_attention import flash_attention

        def attn(q, k, v):
            return flash_attention(q, k, v, True, scale=scale, window=window)

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
        return attn(q, k, v)
    if cfg.attn_impl == "ring":
        from dynolog_tpu.parallel.ring_attention import ring_attention

        if mesh is None:
            raise ValueError("attn_impl='ring' requires a mesh")
        if scale is not None or v.shape[-1] != hd or group > 1:
            raise ValueError(
                "attn_impl='ring' runs as many key/value heads as query "
                "heads, of one width, at the scale D ** -0.5: not latent "
                "attention's, nor grouped heads")
        # (a window it refuses itself)
        return ring_attention(q, k, v, mesh, causal=True, window=window)
    if group > 1:  # the plain path writes k and v out a query head each
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if scale is None:
        scores = scores / jnp.sqrt(hd).astype(q.dtype)
    else:
        scores = scores * jnp.asarray(scale, q.dtype)
    from dynolog_tpu.ops.flash_attention import band_mask

    seen = band_mask(s, s, window)
    scores = jnp.where(seen[None, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attention(layer, x, positions, cfg: TransformerConfig, mesh=None,
               window=None, rotary=True):
    """`window`: the keys a query sees (None: all at or before it);
    `rotary`: whether this layer is rotated where cfg.rope_theta is stated
    (a layer's kind decides both, `cfg.window`, `cfg.rotary`)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    with jax.named_scope("attn"):
        q, k = x @ layer["wq"], x @ layer["wk"]
        if cfg.qk_norm:
            q = _rmsnorm(q, layer["q_scale"], cfg.norm_eps)
            k = _rmsnorm(k, layer["k_scale"], cfg.norm_eps)
        q, k = q.reshape(b, s, h, hd), k.reshape(b, s, kv, hd)
        if cfg.qk_head_norm:
            q = _rmsnorm(q, layer["q_head_scale"], cfg.norm_eps)
            k = _rmsnorm(k, layer["k_head_scale"], cfg.norm_eps)
        v = (x @ layer["wv"]).reshape(b, s, kv, hd)
        if rotary and cfg.rope_theta is not None:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    # The kernels stay outside the scope: the TPU's compiler names a
    # kernel's op after the path's component before `pallas_call`
    # (`jvp_flash_attention_fwd_.2`), and under `attn` every capture of a
    # dense job would name its three kernels anew. A kernel's own name is
    # its scope (`trace.op_scope`).
    with (contextlib.nullcontext() if cfg.attn_impl == "flash"
          else jax.named_scope("attn")):
        out = _softmax_attention(q, k, v, cfg, mesh, window=window)
    with jax.named_scope("attn"):
        out = out.reshape(b, s, h * hd)
        if cfg.attn_gate:
            out = out * jax.nn.sigmoid(x @ layer["wg"])
        return out @ layer["wo"]


def _mlp(layer, x, act="swiglu"):
    with jax.named_scope("mlp"):
        if act == "relu2":
            return jnp.square(jax.nn.relu(x @ layer["w_up"])) @ layer["w_down"]
        gate = jax.nn.silu(x @ layer["w_gate"])
        return (gate * (x @ layer["w_up"])) @ layer["w_down"]


def _mixer(kind, i, layer, x, positions, cfg: TransformerConfig, mesh):
    """Mixer `kind` of layer i on the residual stream x -> (what it adds to
    x, its weighted share of the loss's expert terms or None)."""
    y, terms = _mix(kind, i, layer, x, positions, cfg, mesh)
    if cfg.post_norm:
        scale, scope = POST_NORMS[kind]
        with jax.named_scope(scope):
            y = _rmsnorm(y, layer[scale], cfg.norm_eps)
    return y, terms


def _mix(kind, i, layer, x, positions, cfg: TransformerConfig, mesh):
    scale, scope = MIXER_NORMS[kind]
    with jax.named_scope(scope):
        h = _rmsnorm(x, layer[scale], cfg.norm_eps)
    if kind == "mamba2":
        from dynolog_tpu.models.mamba2 import mamba2_mixer

        return mamba2_mixer(layer, h, cfg), None
    if kind == "linear_attention":
        from dynolog_tpu.models.linear_attention import gated_delta_net

        return gated_delta_net(layer, h, cfg), None
    if kind == "kda":
        from dynolog_tpu.models.linear_attention import kimi_delta_attention

        return kimi_delta_attention(layer, h, cfg, mesh), None
    if kind == "mla":
        from dynolog_tpu.models.mla import latent_attention

        return latent_attention(layer, h, positions, cfg, mesh), None
    if kind == "attention":
        return _attention(layer, h, positions, cfg, mesh, cfg.window(i),
                          cfg.rotary(i)), None
    if kind == "mlp":
        return _mlp(layer, h, cfg.mlp_act), None
    from dynolog_tpu.models.moe import moe_mlp

    y, balance, z = moe_mlp(layer, h, cfg, mesh)
    return y, (cfg.moe_aux_weight * balance
               + cfg.moe_z_weight * z) / cfg.n_sparse_layers


def _forward_with_aux(params, tokens, cfg: TransformerConfig, mesh=None):
    """tokens [B, S] int32 → (logits [B, S, vocab] f32, the expert layers'
    weighted loss terms, a mean over layers: 0 for a dense model)."""
    with jax.named_scope("embed"):
        x = params["embedding"][tokens]
        if cfg.scale_embedding:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
    )
    aux = jnp.zeros((), jnp.float32)
    for i, layer in enumerate(params["layers"]):
        for kind in cfg.mixers(i):
            y, terms = _mixer(kind, i, layer, x, positions, cfg, mesh)
            x = x + y
            if terms is not None:
                aux = aux + terms
    with jax.named_scope("head"):
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
    with jax.named_scope("head"):
        logits = logits[:, :-1]
        targets = tokens[:, 1:]
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)
        return jnp.mean(nll) + aux


@partial(jax.jit, static_argnames=("cfg",))
def jit_forward(params, tokens, cfg: TransformerConfig):
    return forward(params, tokens, cfg)
