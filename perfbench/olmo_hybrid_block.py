"""The hybrid block's module (Olmo-Hybrid: gated-delta-net layers among
full-attention ones): the observed job's weights, its plain float32 reference
and check J's limits for it. A configuration names the file under
`reference` (`cells.load_reference`), as the dense ones name `reference.py`.

Nothing of dynolog_tpu is imported here. The benchmark makes the weights
itself, from the seed, on the device, in the type the job trains in, and
hands the same pytree to the program's step and to this reference. The
pytree's layout is the program's input format: {embedding, w_out,
final_scale, layers: [...]}, a layer of kind `full_attention`
{attn_scale, wq, wk, wv, wo, q_scale, k_scale, mlp_scale, w_gate, w_up,
w_down} and one of kind `linear_attention` {attn_scale, gdn_q, gdn_k [d, H
d_k], gdn_v, gdn_g [d, H d_v], gdn_conv_q, gdn_conv_k [K, H d_k], gdn_conv_v
[K, H d_v], gdn_b, gdn_a [d, H], gdn_a_log, gdn_dt_bias [H] float32,
gdn_norm_scale [d_v], gdn_o [H d_v, d], mlp_scale, w_gate, w_up, w_down},
in the order of job["layer_types"].

The block, written down plainly. With x the residual stream, per layer
h = rmsnorm(x) * attn_scale (eps job["norm_eps"]), then by kind:

full attention:
    q = rmsnorm(h wq) * q_scale, k = rmsnorm(h wk) * k_scale over the whole
    d_model of each, before the heads are split (job["qk_norm"]); NO rotary
    embedding where job["rope_theta"] is null, as the source states it (a
    number gives rotary embeddings over the two halves of each head);
    causal softmax attention scaled by 1/sqrt(head size); x += attention wo

linear attention (a gated delta rule; head n of H = job["n_heads"], d_k =
job["linear_key_head_dim"], d_v = job["linear_value_head_dim"], K =
job["linear_conv_kernel"]), TOKEN BY TOKEN, a `jax.lax.scan` over the
sequence, where the program computes chunks of 64:
    q~ = h W_q, k~ = h W_k (H x d_k each), v~ = h W_v (H x d_v)
    each passes a causal depthwise convolution of width K over the sequence
        (a weight a channel a tap, zeros before the first token, no bias;
        here K shifted adds), then SiLU
    per head: q = q~ / |q~|_2 x d_k^-1/2, k = k~ / |k~|_2, v = v~
        (|x|_2 = sqrt(sum x^2 + 1e-6))
    beta_t = sigmoid(h_t W_b), twice that where
        job["linear_allow_neg_eigval"]: in (0, 2)
    g_t = -exp(A_log) x softplus(h_t W_a + dt_bias); alpha_t = exp(g_t)
    S_0 = 0 (d_k x d_v a head):
        S_t = alpha_t S_{t-1}
              + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
    o_t = S_t^T q_t
    x_t += W_o [ rmsnorm_{d_v}(o_t) * norm_scale * SiLU(h_t W_g) ]

then x += W_down (silu(W_gate h') * (W_up h')), h' = rmsnorm(x) * mlp_scale;
a final rmsnorm and an untied head. The vocabulary is the slice this chip
holds of a vocabulary-parallel embedding and head (job["vocab_size"] rows):
token ids are drawn from the slice, logits and loss are over it, here as in
the program. The loss is the next-token cross entropy, the tokens their own
shifted targets.

float32 throughout under `jax.default_matmul_precision("highest")`; the
bfloat16 weights are cast where they are used. `lower` is the control of
check J: the same reference with every weight rounded to float8 (e4m3), the
nearest precision below the bfloat16 the configuration states. It has to
FAIL the limit that the sound job passes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

L2_EPS = 1e-6
# Limits of check J for this block, set from readings on the chip at the
# published widths (`perfbench/control.py <config> <seed> <n>`, twelve seeds,
# my chip run, PR 36, call 1; PERF.md section 2 gives the readings): sound
# 0.02346-0.02467, float8 control 0.2352-0.2452. The block is touchier than
# the dense one (0.020 and 0.127 there): a head's output passes an RMSNorm
# over its own 192 numbers, so where q_t and k_t are nearly orthogonal a
# rounding decides the sign of what the head adds. The limit is the two
# readings' geometric middle, 3.08 times above the largest sound reading and
# 3.09 below the smallest control reading.
J_LOGIT_REL_RMS_LIMIT = 0.076
# The loss hardly moves with precision (the control's gaps are 2.4e-4 to
# 5.4e-3, the sound runs' 1.4e-4 to 3.25e-3: the program rounds its logits to
# bfloat16 before the cross entropy over 50176 of them); it is held against a
# part of the batch left out or a token altered. The accepted cells' 0.003 is
# below this block's largest sound reading, so the limit is three times that
# reading; half a batch left out moves the loss by 0.02-0.03.
J_LOSS_ABS_LIMIT = 0.01


def init_weights(key, job: dict):
    """Seeded weights, normal / sqrt(fan_in), in job["dtype"]; `gdn_a_log`
    (A in [1, 16)) and `gdn_dt_bias` (a step in [0.001, 0.1) through the
    inverse of softplus) in float32 whatever the job's type, as the program
    keeps them. Call it under jax.jit: each float32 draw is scaled, cast and
    freed inside the program."""
    dtype = jnp.dtype(job["dtype"])
    d, f, v, h = (job["d_model"], job["d_ff"], job["vocab_size"],
                  job["n_heads"])

    def dense(k, shape, fan_in):
        draw = jax.random.normal(k, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    def mlp(k):
        return {"mlp_scale": jnp.ones((d,), dtype),
                "w_gate": dense(k[0], (d, f), d),
                "w_up": dense(k[1], (d, f), d),
                "w_down": dense(k[2], (f, d), f)}

    def full(k):
        return {
            "attn_scale": jnp.ones((d,), dtype),
            "wq": dense(k[0], (d, d), d), "wk": dense(k[1], (d, d), d),
            "wv": dense(k[2], (d, d), d), "wo": dense(k[3], (d, d), d),
            **({"q_scale": jnp.ones((d,), dtype),
                "k_scale": jnp.ones((d,), dtype)} if job.get("qk_norm")
               else {}), **mlp(k[4:7])}

    def linear(k):
        dk, dv, taps = (job["linear_key_head_dim"],
                        job["linear_value_head_dim"],
                        job["linear_conv_kernel"])
        step = jnp.exp(jax.random.uniform(
            k[14], (h,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "attn_scale": jnp.ones((d,), dtype),
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
            "gdn_o": dense(k[10], (h * dv, d), h * dv), **mlp(k[11:14])}

    keys = jax.random.split(key, job["n_layers"] + 2)
    layers = [
        (linear if kind == "linear_attention" else full)(
            jax.random.split(keys[2 + i], 15))
        for i, kind in enumerate(job["layer_types"])]
    return {
        "embedding": dense(keys[0], (v, d), d),
        "w_out": dense(keys[1], (d, v), d),
        "final_scale": jnp.ones((d,), dtype),
        "layers": layers,
    }


def lower(w):
    """The control's rounding: through float8 e4m3 and back."""
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _f32(w, rounding):
    return w.astype(jnp.float32) if rounding is None else rounding(w)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x [S, H, D]: rotate the two halves of D by position."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _full_attention(w, h, n_heads, theta, eps, qk_norm):
    """One sequence h [S, D], normalised (a [heads, S, S] float32 score
    matrix a sequence is what fits)."""
    s, d = h.shape
    q, k, v = h @ w["wq"], h @ w["wk"], h @ w["wv"]
    if qk_norm:
        q = _rmsnorm(q, w["q_scale"], eps)
        k = _rmsnorm(k, w["k_scale"], eps)
    q, k, v = (t.reshape(s, n_heads, d // n_heads) for t in (q, k, v))
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(d // n_heads)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(s, d) @ w["wo"]


def _shifted_conv(x, taps):
    """x [S, channels], taps [K, channels]: y_t = sum_j taps_j x_{t-(K-1)+j},
    zeros before the first token; one shifted add a tap."""
    s, width = x.shape[0], taps.shape[0]
    y = jnp.zeros_like(x)
    for j in range(width):
        back = width - 1 - j
        y = y + taps[j] * jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:s - back]])
    return y


def _l2(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def delta_rule_recurrence(q, k, v, g, beta):
    """The gated delta rule token by token, one sequence: q, k [S, H, d_k]
    (normalised, q scaled), v [S, H, d_v], g and beta [S, H] -> o [S, H,
    d_v] and the final state [H, d_k, d_v]."""

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + beta_t[:, None, None] * jnp.einsum(
            "hk,hv->hkv", k_t, v_t - read)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    start = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    state, out = jax.lax.scan(token, start, (q, k, v, g, beta))
    return out, state


def _linear_attention(w, h, n_heads, dk, dv, neg_eigval, eps):
    """One sequence h [S, D], normalised."""
    s = h.shape[0]
    q = jax.nn.silu(_shifted_conv(h @ w["gdn_q"], w["gdn_conv_q"]))
    k = jax.nn.silu(_shifted_conv(h @ w["gdn_k"], w["gdn_conv_k"]))
    v = jax.nn.silu(_shifted_conv(h @ w["gdn_v"], w["gdn_conv_v"]))
    q = _l2(q.reshape(s, n_heads, dk)) * dk ** -0.5
    k = _l2(k.reshape(s, n_heads, dk))
    v = v.reshape(s, n_heads, dv)
    beta = jax.nn.sigmoid(h @ w["gdn_b"]) * (2.0 if neg_eigval else 1.0)
    g = -jnp.exp(w["gdn_a_log"]) * jax.nn.softplus(
        h @ w["gdn_a"] + w["gdn_dt_bias"])
    out, _ = delta_rule_recurrence(q, k, v, g, beta)
    out = _rmsnorm(out, w["gdn_norm_scale"], eps) * jax.nn.silu(
        (h @ w["gdn_g"]).reshape(s, n_heads, dv))
    return out.reshape(s, n_heads * dv) @ w["gdn_o"]


@partial(jax.jit, static_argnames=("kind", "n_heads", "theta", "eps",
                                   "qk_norm", "dk", "dv", "neg_eigval",
                                   "rounding"))
def _layer(layer, x, kind, n_heads, theta, eps, qk_norm, dk, dv, neg_eigval,
           rounding):
    w = {k: _f32(v, rounding) for k, v in layer.items()}

    def mix(row):
        h = _rmsnorm(row, w["attn_scale"], eps)
        if kind == "linear_attention":
            return row + _linear_attention(
                w, h, n_heads, dk, dv, neg_eigval, eps)
        return row + _full_attention(w, h, n_heads, theta, eps, qk_norm)

    x = jax.lax.map(mix, x)
    h = _rmsnorm(x, w["mlp_scale"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=("last", "eps", "rounding"))
def _head(params, x, tokens, last, eps, rounding):
    x = _rmsnorm(x, _f32(params["final_scale"], rounding), eps)
    logits = x @ _f32(params["w_out"], rounding)
    logprobs = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logprobs, tokens[:, 1:, None], axis=-1)
    return logits[:, -last:], jnp.mean(nll)


def forward(params, tokens, job: dict, last: int, rounding=None):
    """tokens [B, S] -> (logits of the last `last` positions [B, last, V],
    the mean next-token loss over the whole batch), both float32."""
    eps = float(job["norm_eps"])
    theta = job.get("rope_theta")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens], rounding)
        for layer, kind in zip(params["layers"], job["layer_types"],
                               strict=True):
            x = _layer(
                layer, x, kind, job["n_heads"],
                None if theta is None else float(theta), eps,
                bool(job.get("qk_norm", False)),
                job["linear_key_head_dim"], job["linear_value_head_dim"],
                bool(job["linear_allow_neg_eigval"]), rounding)
        return _head(params, x, tokens, last, eps, rounding)


def rel_rms(got, want) -> float:
    """||got - want|| / ||want||: steady from seed to seed where a widest
    single gap is not."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))
