"""Two delta rules: attention replaced by a recurrence over the sequence,
computed in chunks. The linear layers of a hybrid model
(`TransformerConfig.layer_types`), after the `flash-linear-attention`
library's layers:

- "linear_attention": the GATED DELTA NET (`gated_delta_net`,
  `chunked_delta_rule`; the library's Gated DeltaNet, which `olmo_hybrid`
  follows): the state forgets by ONE number a head a token.
- "kda": KIMI DELTA ATTENTION (`kimi_delta_attention`, `chunked_kda_rule`;
  the library's KimiDeltaAttention, arXiv:2510.26692, which `kimi_linear`
  follows): the state forgets by a number a CHANNEL of every head.

What they share: the convolution (`_causal_conv`), the norm of q and k
(`_l2norm`), the chunks, the solve and the loop (`_chunked_rule`: everything
but the two products of a chunk that the decay enters), float32 where it is
stated below, and that the rule is computed again in the backward pass. With
one decay in every channel the second is the first, token for token
(tests/test_kimi_linear.py).

THE GATED DELTA NET, for head n of H, token t, normalised input h_t (d_k the
key head size, d_v the value head size):

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

KIMI DELTA ATTENTION differs in three places (d_k = d_v = 128 at
Kimi-Linear's widths):

    beta_t = sigmoid(h_t W_b)                      never doubled
    g_t = -exp(A_log) x softplus((h_t W_f1) W_f2 + dt_bias)
        a number a CHANNEL of every head (H x d_k, float32), through a
        bottleneck of a head's value width; A_log a number a head, dt_bias
        one a channel;  alpha_t = exp(g_t), a vector
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    y_t = W_o [ RMSNorm_{d_v}(o_t) * sigmoid((h_t W_g1) W_g2) ]
        the output gate through a bottleneck too, a SIGMOID

In a chunk gamma is then [C, d_k] and the decay sits INSIDE the contraction,

    A_ij = sum_d k_id k_jd exp(gamma_id - gamma_jd)      (B_ij: q_i for k_i)

in the place of K K^T * D and Q K^T * D; W, the carried state's decay
(Diag(exp gamma_C) S) and the other terms take gamma a channel where they
took it a head, and nothing else changes. A and B are made by sub-blocks of
SUB rows: written out whole they are [C, C, d_k] a chunk a head (8.6 GB a
layer at 8192 tokens), and factored once over the chunk they overflow
float32. WHAT MAKES THEM is read from what is being compiled
(`_channel_decay`, `jax.lax.platform_dependent`; no field, no option): for a
TPU two Pallas kernels, `kda_pairs_fwd` and its own backward pass
`kda_pairs_bwd` (dynolog_tpu/ops/kda_pairs.py), which keep a sub-block's
pairs, columns and products in fast memory; anywhere else `_plain_pairs`,
the same mathematics in plain ops, which is also the kernels' plain
reference (tests/test_kda_pairs.py), as `reference_attention` stands beside
the flash kernels. The kernels are taken at the sizes the chip's compiler
has taken them at (a chunk of 64, d_k of 64, 128 or 256:
`kda_pairs.compiles`; any other keeps `_plain_pairs` on a TPU too), and over
a mesh (`kimi_delta_attention`'s `mesh`, as the flash kernels' in
transformer.py) they run in a `jax.shard_map`, a device on its own batch
rows and heads: the partitioner cannot split a Mosaic kernel. Five phases under `kda.project`, `kda.conv`,
`kda.chunk_prepare`, `kda.scan`, `kda.out`; a kernel's name is its op's
name, so on a TPU a capture shows two `kda_pairs_fwd` and one
`kda_pairs_bwd` a layer a step as ops of their own, and because they are
called under the scope their time stays in `kda.chunk_prepare`, beside the
running sums, the solve, `W`, `U` and the rows scaled for the loop (the
outermost name the program wrote is an op's scope: the flash kernels of a
latent layer are `mla.attend`'s the same way). The backward pass computes again
everything from the projections on (`kimi_delta_attention`), where the
gated delta net's computes again its rule alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynolog_tpu.models.transformer import _rmsnorm

CHUNK = 64
SUB = 16  # a sub-block of a chunk (`_channel_decay`)
L2_EPS = 1e-6


def _dense(key, shape, fan_in, dtype):
    draw = jax.random.normal(key, shape, jnp.float32)
    return (draw / jnp.sqrt(fan_in)).astype(dtype)


def init_linear_layer(rng, cfg) -> dict:
    """The mixer's weights of one linear layer (the MLP's and the two norm
    scales are the block's own). `A_log` and `dt_bias` are float32 whatever
    the model's type: A in [1, 16) and a step in [0.001, 0.1) through the
    inverse of softplus, as the library draws them."""
    dtype = jnp.dtype(cfg.dtype)
    d, h = cfg.d_model, cfg.n_heads
    dk, dv, taps = (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                    cfg.linear_conv_kernel)

    dense = functools.partial(_dense, dtype=dtype)
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


def init_kda_layer(rng, cfg) -> dict:
    """The mixer's weights of one Kimi Delta Attention layer. The two
    bottleneck maps (the decay's, `kda_f_*`, and the output gate's,
    `kda_g_*`) pass through a head's value width, as the library's layer has
    them, and carry no bias; `kda_a_log` is a number a head and
    `kda_dt_bias` one a channel of every head, both float32 whatever the
    model's type and drawn as `init_linear_layer` draws its own."""
    dtype = jnp.dtype(cfg.dtype)
    d, h = cfg.d_model, cfg.n_heads
    dk, dv, taps = (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                    cfg.linear_conv_kernel)

    dense = functools.partial(_dense, dtype=dtype)
    k = jax.random.split(rng, 14)
    step = jnp.exp(jax.random.uniform(
        k[13], (h * dk,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    return {
        "kda_q": dense(k[0], (d, h * dk), d),
        "kda_k": dense(k[1], (d, h * dk), d),
        "kda_v": dense(k[2], (d, h * dv), d),
        "kda_conv_q": dense(k[3], (taps, h * dk), taps),
        "kda_conv_k": dense(k[4], (taps, h * dk), taps),
        "kda_conv_v": dense(k[5], (taps, h * dv), taps),
        "kda_b": dense(k[6], (d, h), d),
        "kda_f_down": dense(k[7], (d, dv), d),
        "kda_f_up": dense(k[8], (dv, h * dk), dv),
        "kda_a_log": jnp.log(jax.random.uniform(
            k[9], (h,), jnp.float32, 1.0, 16.0)),
        "kda_dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "kda_g_down": dense(k[10], (d, dv), d),
        "kda_g_up": dense(k[11], (dv, h * dv), dv),
        "kda_norm_scale": jnp.ones((dv,), dtype),
        "kda_o": dense(k[12], (h * dv, d), h * dv),
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


def _scalar_decay(q, k, gamma, beta):
    """The gated delta net's: one decay a head. q, k [..., C, d_k], gamma
    and beta [..., C] -> (lower(diag(beta) K K^T * D), lower(Q K^T * D))
    float32, D_ij = exp(gamma_i - gamma_j), and what `_chunked_rule`
    multiplies a chunk's rows by, [..., C, 1] each: beta exp(gamma) (K into
    W), exp(gamma) (Q against the incoming state), exp(gamma_C - gamma) (K
    into the next state); and exp(gamma_C) [..., 1, 1], the state's own.
    Each is handed over as a function `_chunked_rule` calls where it needs
    the value: the compiler schedules by the order the ops are written in,
    and written in the order PR 36 wrote them the step of the hybrid job
    is the program it was (the factors made up front cost it 0.27 %: my
    chip run, PR 52, call 2)."""
    f32 = jnp.float32
    lower = jnp.tril(jnp.ones((gamma.shape[-1],) * 2, bool))
    # D_ij for i >= j only: above the diagonal the exponent is positive
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    kk = jnp.einsum("nbhik,nbhjk->nbhij", k, k, preferred_element_type=f32)
    qk = jnp.einsum("nbhik,nbhjk->nbhij", q, k, preferred_element_type=f32)

    def leaving():
        total = gamma[..., -1]  # gamma_C
        return (jnp.exp(total[..., None] - gamma)[..., None],
                jnp.exp(total)[..., None, None])

    return (lambda: beta[..., None] * kk * decay, lambda: qk * decay,
            lambda: (beta * jnp.exp(gamma))[..., None],
            lambda: jnp.exp(gamma)[..., None], leaving)


def _plain_pairs(q, k, gamma):
    """q, k [..., C, d_k], gamma [..., C, d_k] float32 -> [..., 2, C, C]
    float32: A_ij = sum_d k_id k_jd exp(gamma_id - gamma_jd) and B the same
    with q_i, for i >= j (zeros above). In plain ops: what runs wherever the
    program is not compiled for a TPU, and the plain reference of the two
    kernels that make the same there (`dynolog_tpu/ops/kda_pairs.py`).

    (K * exp(gamma)) (K * exp(-gamma))^T would be one product and overflows
    float32 inside a chunk (gamma passes -88 within 64 tokens at the
    library's strongest decay), so the chunk is cut into sub-blocks of SUB
    rows. Rows i of sub-block I against every column j of an EARLIER
    sub-block are factored about I's first row r: exp(gamma_i - gamma_r)
    and exp(gamma_r - gamma_j), both exponents at or below 0 because gamma
    only falls, their product the pair's decay or an underflow where the
    pair's decay is one. Inside a sub-block no row lies between every pair,
    so the SUB x SUB pairs on the diagonal are written out pair by pair,
    [SUB, SUB, d_k] a sub-block, summed over d_k where they are made."""
    f32, dtype = jnp.float32, k.dtype
    *lead, chunk, dk = k.shape
    n_sub = chunk // SUB

    def subs(x):  # [..., C, d_k] -> [..., C / SUB, SUB, d_k]
        return x.reshape(*lead, n_sub, SUB, dk)

    k32 = k.astype(f32)
    qs, ks, gs = subs(q.astype(f32)), subs(k32), subs(gamma)
    # the pair's decay does not depend on r: no gradient needs to pass it
    ref = jax.lax.stop_gradient(gs[..., :1, :])  # [..., I, 1, d_k]
    rows = jnp.exp(gs - ref)
    rows = jnp.concatenate([ks * rows, qs * rows], axis=-2).astype(dtype)
    # column j as sub-block I sees it, [..., I, C, d_k]; nothing at or past
    # I's first row (those pairs are the diagonal's, or above it)
    earlier = (jnp.arange(chunk)[None, :]
               < SUB * jnp.arange(n_sub)[:, None])[..., None]
    cols = (k32[..., None, :, :] * jnp.exp(jnp.where(
        earlier, ref - gamma[..., None, :, :], -jnp.inf))).astype(dtype)
    off = jnp.einsum("...Iid,...Ijd->...Iij", rows, cols,
                     preferred_element_type=f32)  # [..., I, 2 SUB, C]
    # the diagonal: i and j of one sub-block, i >= j
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))[..., None]
    pair = ks[..., None, :, :] * jnp.exp(jnp.where(
        lower, gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf))
    own = jnp.stack([jnp.sum(ks[..., :, None, :] * pair, -1),
                     jnp.sum(qs[..., :, None, :] * pair, -1)], axis=-4)
    # [..., 2, I, SUB, SUB] into its place among the columns
    at = jnp.eye(n_sub, dtype=f32)
    own = jnp.einsum("...Iij,IJ->...IiJj", own, at).reshape(
        *lead, 2, chunk, chunk)
    off = jnp.moveaxis(off.reshape(*lead, n_sub, 2, SUB, chunk), -3, -4)
    return own + off.reshape(*lead, 2, chunk, chunk)


def _channel_decay(q, k, gamma, beta, mesh=None):
    """Kimi Delta Attention's: a decay a channel, so it sits inside the
    contraction. q, k [N, B, H, C, d_k], gamma [N, B, H, C, d_k], beta [N,
    B, H, C] -> (diag(beta) A, B) float32, A_ij = sum_d k_id k_jd
    exp(gamma_id - gamma_jd) and B the same with q_i, for i >= j (zeros
    above), and the four factors `_scalar_decay` returns, a channel each:
    [..., C, d_k] thrice and exp(gamma_C) [..., d_k, 1]; as functions, as it
    hands them.

    A and B are made by what is being compiled, not by a field: for a TPU
    the two Pallas kernels `kda_pairs_fwd` and `kda_pairs_bwd`, which hold
    the pairs in fast memory, everywhere else `_plain_pairs`, op for op what
    it was (the compiler sees the one or the other, never a conditional).
    At a chunk or a head width the chip's compiler has not taken the kernels
    at (`kda_pairs.compiles`) it is `_plain_pairs` on a TPU too."""
    from dynolog_tpu.ops.kda_pairs import compiles, kda_pairs

    if not compiles(*k.shape[-2:]):
        both = _plain_pairs(q, k, gamma)
    else:
        kernels = kda_pairs
        if mesh is not None:
            # A Mosaic kernel is opaque to the SPMD partitioner (as the
            # flash kernels, transformer.py `_softmax_attention`): each
            # device runs it on its own batch rows and heads; a chunk-head
            # needs no other's. The plain ops the partitioner splits
            # itself, as it did.
            from jax.sharding import PartitionSpec as P

            from dynolog_tpu.parallel.sharding import BATCH_AXES

            rows = P(None, BATCH_AXES, "model", None, None)
            kernels = jax.shard_map(
                kda_pairs, mesh=mesh, in_specs=(rows, rows, rows),
                out_specs=P(None, BATCH_AXES, "model", None, None, None),
                check_vma=False)
        both = jax.lax.platform_dependent(
            q, k, gamma, tpu=kernels, default=_plain_pairs)
    fall, total = jnp.exp(gamma), gamma[..., -1:, :]  # total: gamma_C
    return (lambda: beta[..., None] * both[..., 0, :, :],
            lambda: both[..., 1, :, :], lambda: beta[..., None] * fall,
            lambda: fall,
            lambda: (jnp.exp(total - gamma),
                     jnp.swapaxes(fall[..., -1:, :], -1, -2)))


def _chunked_rule(q, k, v, g, beta, decay, scope: str):
    """What the two rules share: the chunks, the solve, the loop. `decay`
    makes a chunk's two products under the decay from the running sums of
    g; `scope` names the two phases."""
    b, s, h, dk = q.shape
    dv, chunk = v.shape[-1], CHUNK
    if s % chunk:
        raise ValueError(
            f"a linear layer computes in chunks of {chunk} tokens "
            f"and the sequence holds {s}: not a whole number of chunks")
    n, f32, dtype = s // chunk, jnp.float32, v.dtype

    def chunks(x):  # [B, S, H, ...] -> [N, B, H, C, ...]
        x = x.reshape(b, n, chunk, h, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    with jax.named_scope(f"{scope}.chunk_prepare"):
        q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
        # the running sums: [N, B, H, C] a head, [N, B, H, C, d_k] a channel
        kk, qk, into_w, into_q, leaving = decay(
            q, k, jnp.cumsum(g, axis=3), beta)
        system = jnp.eye(chunk, dtype=f32) + jnp.tril(kk(), -1)
        rhs = jnp.concatenate(
            [k.astype(f32) * into_w(), v.astype(f32) * beta[..., None]],
            axis=-1)
        solved = jax.scipy.linalg.solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)
        w, u = solved[..., :dk].astype(dtype), solved[..., dk:]
        within = qk().astype(dtype)  # lower(Q K^T under the decay)
        q_in = (q.astype(f32) * into_q()).astype(dtype)
        into_next, carry_decay = leaving()
        k_out = (k.astype(f32) * into_next).astype(dtype)

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

    with jax.named_scope(f"{scope}.scan"):
        state, out = jax.lax.scan(
            body, jnp.zeros((b, h, dk, dv), f32),
            (w, u, within, q_in, k_out, carry_decay))
    # [N, B, H, C, d_v] -> [B, S, H, d_v]
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3).reshape(b, s, h, dv)
    return out, state


def chunked_delta_rule(q, k, v, g, beta):
    """The gated delta net's rule. q, k [B, S, H, d_k] (normalised and
    scaled), v [B, S, H, d_v], g and beta [B, S, H] float32 -> o [B, S, H,
    d_v] in v's type and the final state [B, H, d_k, d_v] float32. S has to
    be a multiple of CHUNK."""
    return _chunked_rule(q, k, v, g, beta, _scalar_decay, "gdn")


def chunked_kda_rule(q, k, v, g, beta, mesh=None):
    """Kimi Delta Attention's rule: as `chunked_delta_rule` with g [B, S, H,
    d_k] float32, a number a channel. `mesh`: the mesh the program is
    partitioned over, if any (`_channel_decay`'s kernels are not)."""
    return _chunked_rule(
        q, k, v, g, beta, functools.partial(_channel_decay, mesh=mesh), "kda")


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


def _kda_from_projections(small, q, k, v, raw, beta, *, heads: int, mesh):
    """The projections of a KDA layer (q, k, v [B, S, H d], the decay's
    `raw` [B, S, H d_k] as its bottleneck gave it, beta [B, S, H] float32)
    and the layer's `small` weights (the taps, A_log, dt_bias) -> the rule's
    output [B, S, H, d_v]. Everything in here is computed again in the
    backward pass (`kimi_delta_attention`)."""
    b, s, _ = q.shape
    dk, dv, f32 = q.shape[-1] // heads, v.shape[-1] // heads, jnp.float32
    with jax.named_scope("kda.conv"):
        q = jax.nn.silu(_causal_conv(q, small["kda_conv_q"]))
        k = jax.nn.silu(_causal_conv(k, small["kda_conv_k"]))
        v = jax.nn.silu(_causal_conv(v, small["kda_conv_v"]))
        q = _l2norm(q.reshape(b, s, heads, dk)) * jnp.asarray(
            dk ** -0.5, q.dtype)
        k = _l2norm(k.reshape(b, s, heads, dk))
        v = v.reshape(b, s, heads, dv)
        g = -jnp.exp(small["kda_a_log"].astype(f32))[:, None] * (
            jax.nn.softplus(
                raw.astype(f32) + small["kda_dt_bias"].astype(f32))
        ).reshape(b, s, heads, dk)
    return chunked_kda_rule(q, k, v, g, beta, mesh)[0]


def kimi_delta_attention(layer, x, cfg, mesh=None):
    """x [B, S, d] (normalised) -> the mixer's output [B, S, d]. `mesh`: the
    mesh the program is partitioned over, if any."""
    b, s, _ = x.shape
    h, dv = cfg.n_heads, cfg.linear_value_head_dim
    with jax.named_scope("kda.project"):
        q, k, v = x @ layer["kda_q"], x @ layer["kda_k"], x @ layer["kda_v"]
        gate = (x @ layer["kda_g_down"]) @ layer["kda_g_up"]
        beta = jax.nn.sigmoid((x @ layer["kda_b"]).astype(jnp.float32))
        raw = (x @ layer["kda_f_down"]) @ layer["kda_f_up"]
    # Kept for the backward pass: the five projections as they leave their
    # products, in the model's type. The convolution, SiLU, the norms of q
    # and k, the decay (float32, a number a channel) and the rule with a
    # chunk's matrices and the states are computed again there: kept, the
    # float32 copies the norms and the softplus leave behind alone are 0.5
    # GB a layer at 8192 tokens of Kimi-Linear's widths, beside the 2.9 GB
    # of the rule's own. (The gated delta net's checkpoint is round its
    # rule alone.)
    small = {name: layer[name] for name in (
        "kda_conv_q", "kda_conv_k", "kda_conv_v", "kda_a_log", "kda_dt_bias")}
    out = jax.checkpoint(functools.partial(
        _kda_from_projections, heads=h, mesh=mesh))(small, q, k, v, raw, beta)
    with jax.named_scope("kda.out"):
        out = _rmsnorm(out, layer["kda_norm_scale"], cfg.norm_eps)
        out = out * jax.nn.sigmoid(gate.reshape(b, s, h, dv))
        return out.reshape(b, s, h * dv) @ layer["kda_o"]
