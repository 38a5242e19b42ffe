"""A second block's module, as test data only: the repo's expert layer
(`dynolog_tpu/models/moe.py`) where the dense block has its MLP. It shows
that a job of another block is a configuration, a module and nothing of the
harness: `toy-moe.json` names this file under `reference`, and no cell does.

The weights have a layout `perfbench/reference.py`'s do not: a layer is
{attn_scale, wq, wk, wv, wo, mlp_scale, router [d, E] float32,
experts_gate, experts_up [E, d, f], experts_down [E, f, d]}. Nothing of
dynolog_tpu is imported.

The reference is the block written down plainly. Attention as in the dense
block. Then a router (softmax over E of h @ router), the top k experts a
token, their gates renormalised to sum to 1, every expert's SwiGLU computed
for every token and summed under the gates (0 for an expert not chosen):
no capacity and no token dropped, which is what the program computes when
the configuration's `moe_capacity_factor` is experts / top-k, so that an
expert's buffer holds every token. The loss is what the program's step
returns: next-token cross entropy plus `moe_aux_weight` / layers times the
sum over layers of Switch's balancing term, E * sum_e (share of tokens
whose first choice is e) * (mean router probability of e).

`lower` is the control of check J: bfloat16, the precision below the
float32 that `toy-moe.json` states. The router goes through it too, so a
token whose best experts are nearly tied goes elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6
# Limits of check J for this block at toy-moe.json's size, from readings on
# the CPU over seeds 0-15 (a toy's, for the tests alone): sound at most
# 3.9e-7 (float32 against float32), the bfloat16 control 6.8e-3 to 5.0e-2.
J_LOGIT_REL_RMS_LIMIT = 1e-3
# Sound at most 9.5e-7; half of the batch left out reads 4.0e-3 to 0.14
# (the control 1.6e-4 to 3.6e-3: the loss is not what catches it).
J_LOSS_ABS_LIMIT = 1e-3


def init_weights(key, job: dict):
    """Seeded weights, normal / sqrt(fan_in), in job["dtype"]; the router
    in float32 whatever the job's type, as the program keeps it."""
    dtype = jnp.dtype(job["dtype"])
    d, f, v, e = (job["d_model"], job["d_ff"], job["vocab_size"],
                  job["n_experts"])

    def dense(k, shape, fan_in, dtype=dtype):
        draw = jax.random.normal(k, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    keys = jax.random.split(key, job["n_layers"] + 2)
    layers = []
    for i in range(job["n_layers"]):
        k = jax.random.split(keys[2 + i], 8)
        layers.append({
            "attn_scale": jnp.ones((d,), dtype),
            "wq": dense(k[0], (d, d), d), "wk": dense(k[1], (d, d), d),
            "wv": dense(k[2], (d, d), d), "wo": dense(k[3], (d, d), d),
            "mlp_scale": jnp.ones((d,), dtype),
            "router": dense(k[4], (d, e), d, jnp.float32),
            "experts_gate": dense(k[5], (e, d, f), d),
            "experts_up": dense(k[6], (e, d, f), d),
            "experts_down": dense(k[7], (e, f, d), f),
        })
    return {
        "embedding": dense(keys[0], (v, d), d),
        "w_out": dense(keys[1], (d, v), d),
        "final_scale": jnp.ones((d,), dtype),
        "layers": layers,
    }


def lower(w):
    """The control's rounding: through bfloat16 and back."""
    return w.astype(jnp.bfloat16).astype(jnp.float32)


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


def _attention(w, x, n_heads, theta):
    b, s, d = x.shape
    h = _rmsnorm(x, w["attn_scale"])
    q, k, v = (
        (h @ w[name]).reshape(b, s, n_heads, d // n_heads)
        for name in ("wq", "wk", "wv"))
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d // n_heads)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return x + attn.reshape(b, s, d) @ w["wo"]


def _experts(w, x, top_k):
    """x [B, S, D] -> (the gated sum of the chosen experts, the layer's
    balancing term)."""
    n_experts = w["router"].shape[-1]
    h = _rmsnorm(x, w["mlp_scale"])
    probs = jax.nn.softmax(h @ w["router"], axis=-1)  # [B, S, E]
    best, chosen = jax.lax.top_k(probs, top_k)  # [B, S, k]
    best = best / jnp.sum(best, axis=-1, keepdims=True)
    picks = jax.nn.one_hot(chosen, n_experts)  # [B, S, k, E]
    gates = jnp.sum(best[..., None] * picks, axis=-2)  # [B, S, E]
    y = jnp.zeros_like(x)
    for e in range(n_experts):
        act = jax.nn.silu(h @ w["experts_gate"][e]) * (h @ w["experts_up"][e])
        y = y + gates[..., e:e + 1] * (act @ w["experts_down"][e])
    first = jnp.mean(picks[..., 0, :], axis=(0, 1))  # [E]
    balance = n_experts * jnp.sum(first * jnp.mean(probs, axis=(0, 1)))
    return x + y, balance


def forward(params, tokens, job: dict, last: int, rounding=None):
    """tokens [B, S] -> (logits of the last `last` positions [B, last, V],
    the loss the program's step returns on the whole batch), float32."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens], rounding)
        balance = 0.0
        for layer in params["layers"]:
            w = {k: _f32(v, rounding) for k, v in layer.items()}
            x = _attention(w, x, job["n_heads"], float(job["rope_theta"]))
            x, term = _experts(w, x, job["moe_top_k"])
            balance = balance + term
        x = _rmsnorm(x, _f32(params["final_scale"], rounding))
        logits = x @ _f32(params["w_out"], rounding)
        logprobs = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(logprobs, tokens[:, 1:, None], axis=-1)
        loss = jnp.mean(nll) + (
            job["moe_aux_weight"] * balance / job["n_layers"])
        return logits[:, -last:], loss


def rel_rms(got, want) -> float:
    """||got - want|| / ||want||."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))
