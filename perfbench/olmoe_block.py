"""The sparse-expert block's module (OLMoE, arXiv:2409.02060): the observed
job's weights, its plain float32 reference and check J's limits for it. A
configuration names the file under `reference` (`cells.load_reference`), as
the dense ones name `reference.py`.

Nothing of dynolog_tpu is imported here. The benchmark makes the weights
itself, from the seed, on the device(s), in the type the job trains in, and
hands the same pytree to the program's step and to this reference. The
pytree's layout is the program's input format: {embedding, w_out,
final_scale, layers: [{attn_scale, wq, wk, wv, wo, q_scale, k_scale,
mlp_scale, router [d, E] float32, experts_gate, experts_up [E, d, f],
experts_down [E, f, d]}]}.

The block, written down plainly. With x the residual stream, per layer:

    h = rmsnorm(x) * attn_scale                      (eps job["norm_eps"])
    q = rmsnorm(h wq) * q_scale, k = rmsnorm(h wk) * k_scale, over the whole
        d_model of each, before the heads are split (job["qk_norm"]);
        rotary embeddings over the two halves of each head; causal softmax
        attention scaled by 1/sqrt(head size); x += attention wo
    h_t = rmsnorm(x_t) * mlp_scale
    s_t = softmax_e(h_t W_r)                          over all E experts
    K_t = the k largest s_t;  g_te = s_te for e in K_t, else 0
          (divided by their sum only where job["moe_norm_topk"])
    x_t += sum_e g_te * W_down_e (silu(W_gate_e h_t) * (W_up_e h_t))

Every expert's SwiGLU is computed for every token and summed under gates
that are 0 for an expert not chosen: no sort, no dispatch, no capacity, no
exchange. It is done a block of tokens at a time, each block through all the
experts, so that it fits beside the weights; where those lie sharded by
expert over chips each chip computes its own experts' part of every block
and the parts are summed, and no weight moves.

The loss is what the program's step returns:

    cross entropy (the tokens their own shifted targets)
    + job["moe_aux_weight"] * mean over layers of E * sum_e f_e P_e
    + job["moe_z_weight"]   * mean over layers and tokens of
                              logsumexp_e(h_t W_r)^2

with f_e the share of the layer's T x k assignments that went to e (of the
T first choices where job["moe_balance_all_k"] is false) and P_e the mean of
s_te over the T tokens of the step.

float32 throughout under `jax.default_matmul_precision("highest")`; the
bfloat16 weights are cast where they are used. `lower` is the control of
check J: the same reference with every weight rounded to float8 (e4m3), the
nearest precision below the bfloat16 the configuration states; the router
goes through it too, so a token whose k-th and (k+1)-th experts are nearly
tied goes elsewhere. It has to FAIL the limit that the sound job passes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 1024
# Limits of check J for this block, set from readings on the four chips over
# the `expert` 4 mesh at the published widths (`perfbench/control.py <config>
# <seed> <n>`, twelve seeds; PERF.md section 2 gives the readings): sound
# 0.01207-0.01316, float8 control 0.04320-0.04746. The two part by 3.3, not
# by the nine that three times from each would need (the dense block's part
# by 6 to 10: here a token's output is a sum over eight experts whose
# rounding errors are independent, so the control is nearer), so the limit
# is their geometric middle: 1.75 times above the largest sound reading,
# 1.88 below the smallest control reading.
J_LOGIT_REL_RMS_LIMIT = 0.023
# The loss hardly moves with precision (the control's gaps are 3.8e-5 to
# 7.1e-4, the sound runs' at most 2.7e-4); it is held against a part of the
# batch left out or a token altered, at the limit of the accepted cells,
# eleven times the largest sound gap.
J_LOSS_ABS_LIMIT = 0.003


def init_weights(key, job: dict):
    """Seeded weights, normal / sqrt(fan_in), in job["dtype"]; the router in
    float32 whatever the job's type, as the program keeps it. Call it under
    jax.jit: each float32 draw is scaled, cast and freed inside the program."""
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
            "q_scale": jnp.ones((d,), dtype),
            "k_scale": jnp.ones((d,), dtype),
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


def _attention(w, x, n_heads, theta, eps, qk_norm):
    """One sequence x [S, D] (a [heads, S, S] float32 score matrix a
    sequence is what fits)."""
    s, d = x.shape
    h = _rmsnorm(x, w["attn_scale"], eps)
    q, k, v = h @ w["wq"], h @ w["wk"], h @ w["wv"]
    if qk_norm:
        q = _rmsnorm(q, w["q_scale"], eps)
        k = _rmsnorm(k, w["k_scale"], eps)
    q, k, v = (t.reshape(s, n_heads, d // n_heads) for t in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(d // n_heads)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + attn.reshape(s, d) @ w["wo"]


def _experts(w, x, top_k, eps, norm_topk, balance_all_k):
    """x [T, D] -> (x + the gated sum of the chosen experts, the layer's
    balancing term, the layer's z term)."""
    n_experts = w["router"].shape[-1]
    h = _rmsnorm(x, w["mlp_scale"], eps)
    logits = h @ w["router"]  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    best, chosen = jax.lax.top_k(probs, top_k)  # [T, k]
    if norm_topk:
        best = best / jnp.sum(best, axis=-1, keepdims=True)
    picks = jax.nn.one_hot(chosen, n_experts)  # [T, k, E]
    gates = jnp.sum(best[..., None] * picks, axis=1)  # [T, E], 0 if not chosen

    def block(args):
        h_b, gates_b = args  # [b, D], [b, E]
        act = jax.nn.silu(jnp.einsum("td,edf->etf", h_b, w["experts_gate"])) * (
            jnp.einsum("td,edf->etf", h_b, w["experts_up"]))
        return jnp.einsum("etf,efd->td", act * gates_b.T[:, :, None],
                          w["experts_down"])

    size = min(TOKEN_BLOCK, h.shape[0])
    y = jax.lax.map(block, (h.reshape(-1, size, h.shape[-1]),
                            gates.reshape(-1, size, n_experts)))
    counted = picks if balance_all_k else picks[:, :1]
    share = jnp.mean(counted, axis=(0, 1))  # [E]: f_e
    balance = n_experts * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return x + y.reshape(x.shape), balance, z


@partial(jax.jit, static_argnames=("n_heads", "theta", "eps", "qk_norm",
                                   "top_k", "norm_topk", "balance_all_k",
                                   "rounding"))
def _layer(layer, x, n_heads, theta, eps, qk_norm, top_k, norm_topk,
           balance_all_k, rounding):
    w = {k: _f32(v, rounding) for k, v in layer.items()}
    b, s, d = x.shape
    x = jax.lax.map(
        lambda row: _attention(w, row, n_heads, theta, eps, qk_norm), x)
    x, balance, z = _experts(
        w, x.reshape(b * s, d), top_k, eps, norm_topk, balance_all_k)
    return x.reshape(b, s, d), balance, z


@partial(jax.jit, static_argnames=("last", "eps", "rounding"))
def _head(params, x, tokens, last, eps, rounding):
    x = _rmsnorm(x, _f32(params["final_scale"], rounding), eps)
    logits = x @ _f32(params["w_out"], rounding)
    logprobs = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logprobs, tokens[:, 1:, None], axis=-1)
    return logits[:, -last:], jnp.mean(nll)


def forward(params, tokens, job: dict, last: int, rounding=None):
    """tokens [B, S] -> (logits of the last `last` positions [B, last, V],
    the loss the program's step returns on the whole batch), float32."""
    eps = float(job["norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens], rounding)
        balance = z = 0.0
        for layer in params["layers"]:
            x, layer_balance, layer_z = _layer(
                layer, x, job["n_heads"], float(job["rope_theta"]), eps,
                bool(job["qk_norm"]), job["moe_top_k"],
                bool(job["moe_norm_topk"]), bool(job["moe_balance_all_k"]),
                rounding)
            balance, z = balance + layer_balance, z + layer_z
        logits, nll = _head(params, x, tokens, last, eps, rounding)
        loss = nll + (job["moe_aux_weight"] * balance
                      + job["moe_z_weight"] * z) / job["n_layers"]
        return logits, loss


def rel_rms(got, want) -> float:
    """||got - want|| / ||want||: steady from seed to seed where a widest
    single gap is not."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))
