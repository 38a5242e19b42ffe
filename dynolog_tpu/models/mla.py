"""Latent attention: keys and values expanded from a compressed latent, with
a rotary part beside it. The attention of a `TransformerConfig` whose
`attn_type` is "mla", after DeepSeek-V2 (arXiv:2405.04434, section 2.1)
without query compression (`q_lora_rank: null`, as DeepSeek-V2-Lite has it).

The layer, for token t with normalised input h_t, H heads, d_n =
qk_nope_head_dim, d_r = qk_rope_head_dim, d_v = v_head_dim, r = kv_lora_rank:

    q_t = h_t W_Q                       H x (d_n + d_r); a head splits into
                                        q_nope (d_n) and q_pe (d_r)
    [c_t | k_pe_t] = h_t W_DKV          r + d_r: the latent, and ONE rotary
                                        key a token, shared by all heads
    c_t <- RMSNorm(c_t)                 a learned scale over r, eps norm_eps
    q_pe, k_pe <- RoPE                  on the rotary parts alone
                                        (transformer._rope: the two halves
                                        of d_r are the pairs; under YaRN its
                                        frequencies and cos/sin factor)
    [k_nope_t | v_t] = c_t W_UKV        H x (d_n + d_v)
    q = [q_nope | q_pe],  k = [k_nope | k_pe]       heads of d_n + d_r
    o = causal softmax(q k^T x scale) v             heads of d_v
    y_t = [o_t1 | ... | o_tH] W_O                   H d_v -> d_model

    scale = (d_n + d_r)^-1/2 x m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
            under YaRN (`rope_scaling`), m = 1 without

In training the latent is expanded for every token, so attention itself is
ordinary multi-head attention with keys wider than values (192 and 128 at
DeepSeek-V2-Lite's widths): `ops.flash_attention` takes the two widths
apart, nothing is padded. Five phases a layer under `jax.named_scope`,
beside `moe.*` and `gdn.*`: `mla.project` (the two projections of h),
`mla.latent` (the latent's norm, RoPE on the rotary parts), `mla.expand`
(keys and values from the latent, the heads' q and k put together),
`mla.attend`, `mla.out`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynolog_tpu.models.transformer import (
    _rmsnorm,
    _rope,
    _softmax_attention,
    yarn_mscale,
)


def init_mla_layer(rng, cfg) -> dict:
    """The attention's weights of one layer (the MLP's and the two norm
    scales are the block's own)."""
    dtype = jnp.dtype(cfg.dtype)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def dense(key, shape, fan_in):
        draw = jax.random.normal(key, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    k = jax.random.split(rng, 4)
    return {
        "wq": dense(k[0], (d, h * (dn + dr)), d),
        "mla_dkv": dense(k[1], (d, r + dr), d),
        "mla_kv_scale": jnp.ones((r,), dtype),
        "mla_ukv": dense(k[2], (r, h * (dn + dv)), r),
        "wo": dense(k[3], (h * dv, d), h * dv),
    }


def softmax_scale(cfg) -> float:
    """(d_n + d_r)^-1/2, times YaRN's attention factor squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_scaling is not None:
        scaling = dict(cfg.rope_scaling)
        scale *= yarn_mscale(
            scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def latent_attention(layer, x, positions, cfg, mesh=None):
    """x [B, S, D] (normalised) -> [B, S, D]."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    scaling = dict(cfg.rope_scaling) if cfg.rope_scaling is not None else None

    with jax.named_scope("mla.project"):
        q = (x @ layer["wq"]).reshape(b, s, h, -1)
        ckv = x @ layer["mla_dkv"]
    with jax.named_scope("mla.latent"):
        c = _rmsnorm(ckv[..., :r], layer["mla_kv_scale"], cfg.norm_eps)
        q_pe, k_pe = q[..., dn:], ckv[..., None, r:]
        if cfg.rope_theta is not None:
            q_pe = _rope(q_pe, positions, cfg.rope_theta, scaling)
            k_pe = _rope(k_pe, positions, cfg.rope_theta, scaling)
    with jax.named_scope("mla.expand"):
        kv = (c @ layer["mla_ukv"]).reshape(b, s, h, dn + dv)
        q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (b, s, h, k_pe.shape[-1]))],
            axis=-1)
    with jax.named_scope("mla.attend"):
        out = _softmax_attention(
            q, k, kv[..., dn:], cfg, mesh, scale=softmax_scale(cfg))
    with jax.named_scope("mla.out"):
        return out.reshape(b, s, h * dv) @ layer["wo"]
