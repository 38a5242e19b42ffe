"""A Mamba-2 block's mixer: a state-space recurrence over the sequence,
computed in chunks (state-space duality, arXiv:2405.21060). The "mamba2"
blocks of a `TransformerConfig` with `block_types`, after the mixer of
`nemotron_h` (NVIDIA-Nemotron-3-Nano's modelling code).

The mixer, for normalised input u_t, H heads of P channels (d_inner = H P),
G groups of H / G heads that share B and C, a state of N a channel, K taps:

    [z | xBC | dt] = u W_in           d_inner + (d_inner + 2 G N) + H, no bias
    xBC = silu(conv(xBC))             causal, depthwise, K taps and a bias
                                      (`linear_attention._causal_conv`)
    xBC splits into x [H, P], B [G, N], C [G, N]; head h uses group h // (H/G)
    delta_t = softplus(dt_t + dt_bias)       a number a head, float32, no clamp
    A = -exp(A_log)                          a number a head, float32
    per head, the state h [P, N], h_0 = 0:
        h_t = exp(delta_t A) h_{t-1} + delta_t x_t (x) B_t
        y_t = h_t C_t + D x_t                D a number a head
    y <- y * silu(z), RMS-normalised in the G groups of d_inner / G channels
        (eps norm_eps), times a weight [d_inner]
    out = y W_out                            d_inner -> d_model

The recurrence is computed in chunks of cfg.ssm_chunk positions, with
a_t = delta_t A (<= 0) and c_i its running sum inside a chunk:

    inside a chunk   y_i += sum_{j<=i} exp(c_i - c_j) (C_i . B_j) delta_j x_j
                     (the scores C B^T a group, the decay a head, a product
                     with the chunk's x); the chunk's own contribution to
                     the state at its end, sum_j exp(c_last - c_j) delta_j
                     x_j (x) B_j
    between chunks   the state that enters chunk n is the sum over the
                     chunks m < n of chunk m's contribution times
                     exp(the a summed from m's end to n's start): ONE
                     product of the S / chunk contributions with the
                     chunk-to-chunk decay matrix, so no loop is left on the
                     device (a capture of this block holds no `while`);
                     y_i += exp(c_i) C_i . (the entering state)

Only differences of running sums are exponentiated, each at most 0, in
float32; the decay, the running sums and the states are float32, the
products' operands the model's type with float32 sums. The rule keeps x,
B, C, delta for the backward pass and computes a chunk's matrices again
there (`jax.checkpoint`): kept, the [chunk, chunk] decays a head are
0.5 GB a block at 4 x 4096 tokens of Nemotron-3-Nano's widths.

Five phases a block under `jax.named_scope`, beside `moe.*`, `gdn.*` and
`mla.*`: `ssm.project` (the block's norm, W_in, delta), `ssm.conv`,
`ssm.chunk` (inside a chunk), `ssm.state` (between chunks, and what the
entering state adds to a chunk's y), `ssm.out` (D, the gate, the grouped
norm, W_out).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynolog_tpu.models.linear_attention import _causal_conv


def init_mamba2_layer(rng, cfg) -> dict:
    """The mixer's weights of one block (the block's norm scale is the
    block's own). `ssm_a_log`, `ssm_dt_bias` and `ssm_d` are float32
    whatever the model's type: A in [1, 16) and a step in [0.001, 0.1)
    through the inverse of softplus, as the source draws them
    (`time_step_min`, `time_step_max`), D ones."""
    dtype = jnp.dtype(cfg.dtype)
    d, h, taps = cfg.d_model, cfg.ssm_heads, cfg.ssm_conv_kernel
    inner = h * cfg.ssm_head_dim
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state

    def dense(key, shape, fan_in):
        draw = jax.random.normal(key, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    k = jax.random.split(rng, 6)
    step = jnp.exp(jax.random.uniform(
        k[4], (h,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "ssm_in": dense(k[0], (d, inner + conv + h), d),
        "ssm_conv": dense(k[1], (taps, conv), taps),
        "ssm_conv_bias": dense(k[2], (conv,), taps),
        "ssm_a_log": jnp.log(jax.random.uniform(
            k[3], (h,), jnp.float32, 1.0, 16.0)),
        "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "ssm_d": jnp.ones((h,), jnp.float32),
        "ssm_norm_scale": jnp.ones((inner,), dtype),
        "ssm_out": dense(k[5], (inner, d), inner),
    }


def chunked_state_space(x, delta, a, b_in, c_in, chunk: int, state=None):
    """x [B, S, H, P], delta [B, S, H] float32 (the step, > 0), a [H]
    float32 (< 0), b_in and c_in [B, S, G, N] -> y [B, S, H, P] in x's type
    (without the D term) and the state after the last position
    [B, H, P, N] float32; `state` is the state before the first (None:
    zeros). S has to be a multiple of `chunk`."""
    b, s, h, p = x.shape
    g, n = b_in.shape[2:]
    if s % chunk:
        raise ValueError(
            f"a mamba2 block computes in chunks of {chunk} positions and "
            f"the sequence holds {s}: not a whole number of chunks")
    m, per, f32, dtype = s // chunk, h // g, jnp.float32, x.dtype

    def chunks(t, *heads):  # [B, S, *heads, W] -> [B, M, *heads, C, W]
        t = t.reshape(b, m, chunk, *heads, t.shape[-1])
        return jnp.moveaxis(t, 2, -2)

    with jax.named_scope("ssm.chunk"):
        # a head beside its group's B and C: [B, M, G, H/G, C, ...]
        xs = chunks((x.astype(f32) * delta[..., None]).astype(dtype), g, per)
        bs, cs = chunks(b_in, g), chunks(c_in, g)  # [B, M, G, C, N]
        run = jnp.cumsum(  # c_i <= 0, [B, M, G, H/G, C]
            chunks((delta * a)[..., None], g, per)[..., 0], axis=-1)
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        # exp(c_i - c_j) for i >= j only: above the diagonal it would grow
        decay = jnp.exp(jnp.where(
            lower, run[..., :, None] - run[..., None, :], -jnp.inf))
        scores = jnp.einsum(
            "bmgin,bmgjn->bmgij", cs, bs, preferred_element_type=f32)
        y = jnp.einsum(
            "bmghij,bmghjp->bmghip",
            (scores[:, :, :, None] * decay).astype(dtype), xs,
            preferred_element_type=f32)
        total = run[..., -1]  # the chunk's whole decay, [B, M, G, H/G]
        to_end = jnp.exp(total[..., None] - run)[..., None].astype(dtype)
        own = jnp.einsum(  # what the chunk adds to the state at its end
            "bmghjp,bmgjn->bmghpn", xs * to_end, bs,
            preferred_element_type=f32)

    with jax.named_scope("ssm.state"):
        if state is None:
            state = jnp.zeros((b, h, p, n), f32)
        # entering[n] = sum_{m < n} own[m] exp(total[m+1] + ... + total[n-1])
        # + state exp(total[0] + ... + total[n-1]); row M is the state after
        # the last chunk. The exponent is a difference of running sums of
        # `total`, again only where it is <= 0.
        own = jnp.concatenate(
            [state.reshape(b, 1, g, per, p, n), own], axis=1)
        ends = jnp.cumsum(jnp.pad(
            total, ((0, 0), (1, 0), (0, 0), (0, 0))), axis=1)  # [B, M+1, ..]
        between = jnp.exp(jnp.where(
            jnp.tril(jnp.ones((m + 1, m + 1), bool))[:, :, None, None],
            ends[:, :, None] - ends[:, None, :], -jnp.inf))
        entering = jnp.einsum(
            "bnmgh,bmghpq->bnghpq", between, own,
            preferred_element_type=f32)
        y = y + jnp.exp(run)[..., None] * jnp.einsum(
            "bmgin,bmghpn->bmghip", cs, entering[:, :-1].astype(dtype),
            preferred_element_type=f32)
    # [B, M, G, H/G, C, P] -> [B, S, H, P]
    y = jnp.moveaxis(y.astype(dtype), -2, 2).reshape(b, s, h, p)
    return y, entering[:, -1].reshape(b, h, p, n)


def mamba2_mixer(layer, u, cfg):
    """u [B, S, d] (normalised) -> the mixer's output [B, S, d]."""
    b, s, _ = u.shape
    h, p, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, f32 = h * p, jnp.float32
    with jax.named_scope("ssm.project"):
        proj = u @ layer["ssm_in"]
        z, xbc = proj[..., :inner], proj[..., inner:-h]
        delta = jax.nn.softplus(
            proj[..., -h:].astype(f32) + layer["ssm_dt_bias"].astype(f32))
        a = -jnp.exp(layer["ssm_a_log"].astype(f32))
    with jax.named_scope("ssm.conv"):
        xbc = jax.nn.silu(
            _causal_conv(xbc, layer["ssm_conv"], layer["ssm_conv_bias"]))
        x = xbc[..., :inner].reshape(b, s, h, p)
        b_in = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
        c_in = xbc[..., inner + g * n:].reshape(b, s, g, n)
    y, _ = jax.checkpoint(chunked_state_space, static_argnums=(5,))(
        x, delta, a, b_in, c_in, cfg.ssm_chunk)
    with jax.named_scope("ssm.out"):
        y = y + x * layer["ssm_d"].astype(x.dtype)[:, None]
        y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, g, -1)
        var = jnp.mean(jnp.square(y.astype(f32)), axis=-1, keepdims=True)
        y = (y * jax.lax.rsqrt(var + cfg.norm_eps).astype(y.dtype)).reshape(
            b, s, inner) * layer["ssm_norm_scale"]
        return y @ layer["ssm_out"]
