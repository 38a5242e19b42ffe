"""A gated-delta-net layer: attention replaced by a recurrence over the
sequence, computed in chunks. The linear layers of a hybrid model
(`TransformerConfig.layer_types`, "linear_attention"), after the Gated
DeltaNet layer of the `flash-linear-attention` library that `olmo_hybrid`
follows.

The layer, for head n of H, token t, normalised input h_t (d_k the key head
size, d_v the value head size):

    q~ = h W_q, k~ = h W_k   (H x d_k each);   v~ = h W_v   (H x d_v)
    each passes a causal depthwise convolution of width K over the sequence
        (a weight a channel a tap, zeros before the first token, no bias:
        y_t = sum_j w_j x_{t-(K-1)+j}), then SiLU
    per head: q = q~ / |q~|_2 x d_k^-1/2,  k = k~ / |k~|_2,  v = v~
        (|x|_2 = sqrt(sum x^2 + 1e-6))
    beta_t = sigmoid(h_t W_b), twice that where `linear_allow_neg_eigval`
        (a number a head: the write strength, in (0, 2))
    g_t = -exp(A_log) x softplus(h_t W_a + dt_bias)   (a number a head,
        float32);  alpha_t = exp(g_t)
    the state S (d_k x d_v a head, S_0 = 0):
        S_t = alpha_t S_{t-1}
              + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
    o_t = S_t^T q_t
    y_t = W_o [ RMSNorm_{d_v}(o_t) * SiLU(h_t W_g) ]
        (the norm with a learned scale over a head's d_v)

It is computed in chunks of CHUNK tokens, a `jax.lax.scan` over the chunks
carrying S, so a capture of the job holds loops on the device: a `while` a
layer in the forward pass, and in the backward pass the same again (the
rule is recomputed there) and the loop autodiff derives. Inside a chunk, with gamma_i the running sum of g and
D_ij = exp(gamma_i - gamma_j):

    T = (I + strict_lower(diag(beta) K K^T * D))^-1
    W = T (diag(beta) K * exp(gamma)),   U = T diag(beta) V
    with the incoming S:   V' = U - W S
    O = (Q * exp(gamma)) S + lower(Q K^T * D) V'
    S_next = exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

which is the recurrence above, token for token (tests/test_linear_attention.py
holds it to that). The decay, its running sums and exponentials, the solve
and the carried state are float32; the products' operands are the model's
type. Five phases a layer under `jax.named_scope`, beside `moe.*`:
`gdn.project`, `gdn.conv`, `gdn.chunk_prepare` (the solve, all chunks at
once, outside the loop), `gdn.scan` (the loop), `gdn.out`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynolog_tpu.models.transformer import _rmsnorm

CHUNK = 64
L2_EPS = 1e-6


def init_linear_layer(rng, cfg) -> dict:
    """The mixer's weights of one linear layer (the MLP's and the two norm
    scales are the block's own). `A_log` and `dt_bias` are float32 whatever
    the model's type: A in [1, 16) and a step in [0.001, 0.1) through the
    inverse of softplus, as the library draws them."""
    dtype = jnp.dtype(cfg.dtype)
    d, h = cfg.d_model, cfg.n_heads
    dk, dv, taps = (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                    cfg.linear_conv_kernel)

    def dense(key, shape, fan_in):
        draw = jax.random.normal(key, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    k = jax.random.split(rng, 12)
    step = jnp.exp(jax.random.uniform(
        k[11], (h,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "gdn_q": dense(k[0], (d, h * dk), d),
        "gdn_k": dense(k[1], (d, h * dk), d),
        "gdn_v": dense(k[2], (d, h * dv), d),
        "gdn_conv_q": dense(k[3], (taps, h * dk), taps),
        "gdn_conv_k": dense(k[4], (taps, h * dk), taps),
        "gdn_conv_v": dense(k[5], (taps, h * dv), taps),
        "gdn_b": dense(k[6], (d, h), d),
        "gdn_a": dense(k[7], (d, h), d),
        "gdn_a_log": jnp.log(jax.random.uniform(
            k[8], (h,), jnp.float32, 1.0, 16.0)),
        "gdn_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "gdn_g": dense(k[9], (d, h * dv), d),
        "gdn_norm_scale": jnp.ones((dv,), dtype),
        "gdn_o": dense(k[10], (h * dv, d), h * dv),
    }


def _causal_conv(x, w, bias=None):
    """x [B, S, channels], w [taps, channels]: the last tap is the token's
    own, zeros stand before the first token; `bias` [channels] where the
    convolution has one (a Mamba-2 block's)."""
    taps, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + s] * w[j] for j in range(taps))
    return out if bias is None else out + bias


def _l2norm(x):
    x32 = x.astype(jnp.float32)
    norm = jax.lax.rsqrt(jnp.sum(jnp.square(x32), -1, keepdims=True) + L2_EPS)
    return (x32 * norm).astype(x.dtype)


def chunked_delta_rule(q, k, v, g, beta):
    """q, k [B, S, H, d_k] (normalised and scaled), v [B, S, H, d_v], g and
    beta [B, S, H] float32 -> o [B, S, H, d_v] in v's type and the final
    state [B, H, d_k, d_v] float32. S has to be a multiple of CHUNK."""
    b, s, h, dk = q.shape
    dv, chunk = v.shape[-1], CHUNK
    if s % chunk:
        raise ValueError(
            f"a gated-delta-net layer computes in chunks of {chunk} tokens "
            f"and the sequence holds {s}: not a whole number of chunks")
    n, f32, dtype = s // chunk, jnp.float32, v.dtype

    def chunks(x):  # [B, S, H, ...] -> [N, B, H, C, ...]
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    with jax.named_scope("gdn.chunk_prepare"):
        q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
        gamma = jnp.cumsum(g, axis=-1)  # [N, B, H, C]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        # D_ij for i >= j only: above the diagonal the exponent is positive
        decay = jnp.exp(jnp.where(
            lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
        kk = jnp.einsum("nbhik,nbhjk->nbhij", k, k, preferred_element_type=f32)
        qk = jnp.einsum("nbhik,nbhjk->nbhij", q, k, preferred_element_type=f32)
        system = jnp.eye(chunk, dtype=f32) + jnp.tril(
            beta[..., None] * kk * decay, -1)
        rhs = jnp.concatenate(
            [k.astype(f32) * (beta * jnp.exp(gamma))[..., None],
             v.astype(f32) * beta[..., None]], axis=-1)
        solved = jax.scipy.linalg.solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)
        w, u = solved[..., :dk].astype(dtype), solved[..., dk:]
        within = (qk * decay).astype(dtype)  # lower(Q K^T * D)
        q_in = (q.astype(f32) * jnp.exp(gamma)[..., None]).astype(dtype)
        total = gamma[..., -1]  # gamma_C, [N, B, H]
        k_out = (k.astype(f32) * jnp.exp(
            total[..., None] - gamma)[..., None]).astype(dtype)
        carry_decay = jnp.exp(total)[..., None, None]

    def body(state, xs):
        w, u, within, q_in, k_out, carry_decay = xs
        held = state.astype(dtype)
        fresh = (u - jnp.einsum("bhck,bhkv->bhcv", w, held,
                                preferred_element_type=f32)).astype(dtype)
        out = (jnp.einsum("bhck,bhkv->bhcv", q_in, held,
                          preferred_element_type=f32)
               + jnp.einsum("bhij,bhjv->bhiv", within, fresh,
                            preferred_element_type=f32))
        state = carry_decay * state + jnp.einsum(
            "bhck,bhcv->bhkv", k_out, fresh, preferred_element_type=f32)
        return state, out.astype(dtype)

    with jax.named_scope("gdn.scan"):
        state, out = jax.lax.scan(
            body, jnp.zeros((b, h, dk, dv), f32),
            (w, u, within, q_in, k_out, carry_decay))
    # [N, B, H, C, d_v] -> [B, S, H, d_v]
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3).reshape(b, s, h, dv)
    return out, state


def gated_delta_net(layer, x, cfg):
    """x [B, S, d] (normalised) -> the mixer's output [B, S, d]."""
    b, s, _ = x.shape
    h, dk, dv = cfg.n_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    f32 = jnp.float32
    with jax.named_scope("gdn.project"):
        q, k, v = x @ layer["gdn_q"], x @ layer["gdn_k"], x @ layer["gdn_v"]
        gate = x @ layer["gdn_g"]
        beta = jax.nn.sigmoid((x @ layer["gdn_b"]).astype(f32))
        if cfg.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(layer["gdn_a_log"].astype(f32)) * jax.nn.softplus(
            (x @ layer["gdn_a"]).astype(f32) + layer["gdn_dt_bias"].astype(f32))
    with jax.named_scope("gdn.conv"):
        q = jax.nn.silu(_causal_conv(q, layer["gdn_conv_q"]))
        k = jax.nn.silu(_causal_conv(k, layer["gdn_conv_k"]))
        v = jax.nn.silu(_causal_conv(v, layer["gdn_conv_v"]))
        q = _l2norm(q.reshape(b, s, h, dk)) * jnp.asarray(dk ** -0.5, q.dtype)
        k = _l2norm(k.reshape(b, s, h, dk))
        v = v.reshape(b, s, h, dv)
    # The rule keeps q, k, v, g and beta for the backward pass and computes
    # a chunk's matrices and the states again there, as the library's
    # kernels do: kept, they are 2.4 GB a layer at 4096 tokens of
    # Olmo-Hybrid-7B's widths. The layer around it is not rematerialised.
    out, _ = jax.checkpoint(chunked_delta_rule)(q, k, v, g, beta)
    with jax.named_scope("gdn.out"):
        out = _rmsnorm(out, layer["gdn_norm_scale"], cfg.norm_eps)
        out = out * jax.nn.silu(gate.reshape(b, s, h, dv))
        return out.reshape(b, s, h * dv) @ layer["gdn_o"]
