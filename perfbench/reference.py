"""The dense block's module: the observed job's weights, its plain float32
reference and check J's limits for it. A configuration names the file under
`reference` (`cells.load_reference`); another block's module is another file
with the same exports.

Nothing of dynolog_tpu is imported here. The benchmark makes the weights
itself, from the seed, on the device, in the type the job trains in, and
hands the same pytree to the program's step and to this reference; the
pytree's layout ({embedding, w_out, final_scale, layers: [{attn_scale, wq,
wk, wv, wo, mlp_scale, w_gate, w_up, w_down}]}) is the program's input
format, not something it made.

The reference is the job's block written down plainly: pre-norm RMSNorm
(eps 1e-6), rotary embeddings over the two halves of each head, causal
softmax attention scaled by 1/sqrt(head size), SwiGLU, a final RMSNorm and
an untied output matrix; next-token cross entropy with the tokens as their
own shifted targets. float32 throughout under
`jax.default_matmul_precision("highest")`; the bf16 weights are cast where
they are used, layer by layer, so no second copy of the model is resident.

`lower` is the control of check J: the same reference with every weight
rounded to float8 (e4m3), the nearest precision below the bfloat16 the
configurations state. It has to FAIL the limit that the sound job passes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

EPS = 1e-6
# Limits of check J for this block, set from readings on the chip (PERF.md
# section 2 gives the readings): above the largest a sound run gave over a
# dozen seeds (0.0202) and below the smallest the float8 control gave
# (0.1273).
J_LOGIT_REL_RMS_LIMIT = 0.05
# The loss hardly moves with precision (the control's smallest gap was
# 3.4e-5); it is held against a part of the batch left out or a token
# altered, at about three times the sound runs' largest gap (9.2e-4).
J_LOSS_ABS_LIMIT = 0.003


def init_weights(key, job: dict):
    """Seeded weights, normal / sqrt(fan_in), in job["dtype"]. Call it under
    jax.jit: each float32 draw is scaled, cast and freed inside the program."""
    dtype = jnp.dtype(job["dtype"])
    d, f, v = job["d_model"], job["d_ff"], job["vocab_size"]

    def dense(k, shape, fan_in):
        draw = jax.random.normal(k, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    keys = jax.random.split(key, job["n_layers"] + 2)
    layers = []
    for i in range(job["n_layers"]):
        k = jax.random.split(keys[2 + i], 7)
        layers.append({
            "attn_scale": jnp.ones((d,), dtype),
            "wq": dense(k[0], (d, d), d), "wk": dense(k[1], (d, d), d),
            "wv": dense(k[2], (d, d), d), "wo": dense(k[3], (d, d), d),
            "mlp_scale": jnp.ones((d,), dtype),
            "w_gate": dense(k[4], (d, f), d), "w_up": dense(k[5], (d, f), d),
            "w_down": dense(k[6], (f, d), f),
        })
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


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale


def _rope(x, theta):
    """x [B, S, H, D]: rotate the two halves of D by position."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@partial(jax.jit, static_argnames=("n_heads", "theta", "rounding"))
def _layer(layer, x, n_heads, theta, rounding):
    w = {k: _f32(v, rounding) for k, v in layer.items()}
    b, s, d = x.shape
    h = _rmsnorm(x, w["attn_scale"])
    q, k, v = (
        (h @ w[name]).reshape(b, s, n_heads, d // n_heads)
        for name in ("wq", "wk", "wv"))
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d // n_heads)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(b, s, d) @ w["wo"]
    h = _rmsnorm(x, w["mlp_scale"])
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=("last", "rounding"))
def _head(params, x, tokens, last, rounding):
    x = _rmsnorm(x, _f32(params["final_scale"], rounding))
    logits = x @ _f32(params["w_out"], rounding)
    logprobs = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logprobs, tokens[:, 1:, None], axis=-1)
    return logits[:, -last:], jnp.mean(nll)


def forward(params, tokens, job: dict, last: int, rounding=None):
    """tokens [B, S] -> (logits of the last `last` positions [B, last, V],
    the mean next-token loss over the whole batch), both float32."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens], rounding)
        for layer in params["layers"]:
            x = _layer(layer, x, job["n_heads"], float(job["rope_theta"]),
                       rounding)
        return _head(params, x, tokens, last, rounding)


def rel_rms(got, want) -> float:
    """||got - want|| / ||want||: steady from seed to seed where a widest
    single gap is not."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))
