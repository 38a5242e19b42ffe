"""The Kimi-Linear block's module (moonshotai/Kimi-Linear-48B-A3B-Instruct's
config.json, `model_type` kimi_linear; arXiv:2510.26692; the
`flash-linear-attention` library's `KimiDeltaAttention`, as recalled, not
fetched): the observed job's weights, its plain float32 reference and check
J's limits for it. A configuration names the file under `reference`
(`cells.load_reference`), as the dense ones name `reference.py`.

Nothing of dynolog_tpu is imported here. The benchmark makes the weights
itself, from the seed, on the device, in the type the job trains in, and
hands the same pytree to the program's step and to this reference. The
pytree's layout is the program's input format: {embedding, w_out,
final_scale, layers: [one dict a layer, in the order of job["layer_types"]:
  attn_scale, mlp_scale [d]: the two norms' weights;
  a "kda" layer: kda_q, kda_k [d, H d_k], kda_v [d, H d_v]; kda_conv_q,
      kda_conv_k [K, H d_k], kda_conv_v [K, H d_v]; kda_b [d, H];
      kda_f_down [d, d_v], kda_f_up [d_v, H d_k]; kda_a_log [H] float32,
      kda_dt_bias [H d_k] float32; kda_g_down [d, d_v], kda_g_up [d_v, H d_v];
      kda_norm_scale [d_v]; kda_o [H d_v, d];
  a "full_attention" layer (latent attention): wq [d, H (d_n + d_r)],
      mla_dkv [d, r + d_r], mla_kv_scale [r], mla_ukv [r, H (d_n + d_v)],
      wo [H d_v, d];
  a dense layer (the first job["first_dense_layers"]): w_gate, w_up [d, f],
      w_down [f, d];
  a sparse layer: router [d, E] float32, router_bias [E] float32,
      experts_gate, experts_up [held, d, f_e], experts_down [held, f_e, d],
      shared_gate, shared_up [d, f_s], shared_down [f_s, d]]}.

The model, written down plainly. rms(x; w) = x / sqrt(mean(x^2) + eps) * w
over the last axis, eps job["norm_eps"]. H = n_heads; K = linear_conv_kernel.

  x = E[tokens]
  a layer:  x <- x + mixer(rms(x; attn_scale));  x <- x + mlp(rms(x; mlp_scale))
  logits = rms(x; final_scale) W_out            an untied head

  mixer(h) of a "kda" layer (Kimi Delta Attention; d_k = linear_key_head_dim,
  d_v = linear_value_head_dim), TOKEN BY TOKEN, a `jax.lax.scan` over the
  sequence, where the program computes chunks of 64:
    q~ = h W_q, k~ = h W_k (H x d_k each), v~ = h W_v (H x d_v)
    each passes a causal depthwise convolution of K taps over the sequence
        (a weight a channel a tap, zeros before the first token, no bias;
        here K shifted adds), then SiLU
    per head: q = q~ / |q~|_2 x d_k^-1/2, k = k~ / |k~|_2, v = v~
        (|x|_2 = sqrt(sum x^2 + 1e-6))
    beta_t = sigmoid(h_t W_b)                   a number a head, in (0, 1)
    g_t = -exp(A_log) x softplus((h_t W_f1) W_f2 + dt_bias)
        a number a CHANNEL of every head (H x d_k; A_log a number a head,
        dt_bias one a channel), through a bottleneck of d_v, no bias in it;
        alpha_t = exp(g_t)
    S_0 = 0 (d_k x d_v a head):
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    mixer = W_o [ rms_{d_v}(o_t; norm_scale) * sigmoid((h_t W_g1) W_g2) ]
        the output gate through a bottleneck of d_v, a SIGMOID

  mixer(h) of a "full_attention" layer: latent attention without query
  compression (`q_lora_rank` null) and WITHOUT POSITIONS (`mla_use_nope`:
  job["rope_theta"] is null; the recurrent layers carry the order), d_n =
  qk_nope_head_dim, d_r = qk_rope_head_dim, d_v = v_head_dim, r = kv_lora_rank:
    q = h W_Q                     H x (d_n + d_r)
    [c | k_r] = h W_DKV           r + d_r; k_r is ONE vector a token, shared
                                  by all heads, and nothing rotates it
    c <- rms(c; mla_kv_scale)
    [k_n | v] = c W_UKV           H x (d_n + d_v)
    k = [k_n | k_r]; causal softmax of q k^T x (d_n + d_r)^-1/2, times v,
        a block of QUERY_BLOCK queries at a time against every key ([H,
        block, S] float32 scores are what fits); heads joined through W_O
    (a number under job["rope_theta"] rotates the last d_r of q and k_r, the
    two halves of d_r paired, as `deepseek_v2_block.py` has it without YaRN)

  mlp(h) of a dense layer: W_down (silu(h W_gate) * (h W_up)), width d_ff
  mlp(h) of a sparse layer:
    s = sigmoid(h W_r)                          float32, E columns
    K = the k largest of s + b                  b = router_bias (the
                                                source's correction bias):
                                                it moves the choice and not
                                                the gates
    g_e = moe_gate_scale * s_e / (sum over K of s + 1e-20) for e in K, else 0
    mlp = sum over e HELD HERE of g_e * SwiGLU_e(h) + SwiGLU_shared(h)
    no balancing term: the source balances by moving b between steps, the
    trainer's rule; b is whatever the weights hold (zeros from
    `init_weights`) and job["moe_aux_weight"], job["moe_z_weight"] have to
    be 0

The share. The job holds job["n_experts_held"] of the E experts, from index
job["first_expert_held"] on, as one chip of an expert-parallel layer does.
The router keeps its E columns and a token its k choices; a choice that
falls on an expert not held adds nothing, here as in the program, and that
partial result goes on to the next layer. The shared expert is whole. With
every expert held it is the uncut layer (`tests/test_kimi_linear.py` adds
the shares up to it). The experts held are computed for every token and
summed under gates that are 0 for an expert not chosen: no sort, no
dispatch. A block of tokens at a time, so that it fits beside the weights.
The vocabulary is the slice this chip holds of a vocabulary-parallel
embedding and head (job["vocab_size"] rows): token ids are drawn from the
slice, logits and loss are over it, here as in the program.

Departures from the published description: none in the block. What is taken
from memory and not from config.json (the shapes of A_log and dt_bias, no
bias in either bottleneck where the library's output gate carries one on its
second map, the 64 unrotated columns kept in the latent layer's heads of 192
and the softmax scale 192^-1/2, the router after DeepSeek-V3's) is listed
under the configuration's `assumed`.

The loss is what the program's step returns: cross entropy, the tokens their
own shifted targets, over the vocabulary the job holds.

float32 throughout under `jax.default_matmul_precision("highest")`; the
bfloat16 weights are cast where they are used. `lower` is the control of
check J: the same reference with every weight rounded to float8 (e4m3), the
nearest precision below the bfloat16 the configuration states; the router
goes through it too. It has to FAIL the limit that the sound job passes.

What check J compares, as `nemotron_h_block.py` and `afmoe_block.py` have
it: a token's k-th choice is a comparison of two scores, and where they lie
closer than the rounding of the stream they are computed from, bfloat16 and
float32 may choose differently; such a token's whole routed part then
differs, which says nothing of the program's precision. `forward` marks the
positions that are UNDECIDED in float32 (at some sparse layer the k-th and
the (k+1)-th of s + b lie closer than UNDECIDED_GAP) by NaN logits, and
`rel_rms` is over the positions the reference decided. Only the reference
marks: a NaN the program computes is in a position that counts, and fails.
The loss is over every position.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 1024
QUERY_BLOCK = 512
L2_EPS = 1e-6
# A token is undecided where its last choice and the first it did not take
# lie closer than one step of bfloat16 below 1, where a last choice's score
# lies, as `afmoe_block.py` has it and for its reason. Of 256 columns the
# eighth and the ninth score lie closer than of 128: about half of the
# positions are undecided at each sparse layer, a tenth stay after four.
UNDECIDED_GAP = 2.0 ** -8
# Limits of check J for this block, set from readings on the chip at the
# published widths (`perfbench/control.py kimi-linear-5l-v5e1 3052000101 12`,
# twelve seeds, my chip run, PR 52, call 1; PERF.md section 2), over the
# positions the reference decided: sound 0.006309-0.006516, float8 control
# 0.028676-0.034873. The two part by 4.4; the limit is their geometric
# middle, 2.10 times above the largest sound and 2.10 below the smallest
# control reading. Both are small beside the dense blocks' for PR 41's
# reason: under `init_weights`' unit embedding a token's own vector, which
# no product has rounded, leads its hidden state.
J_LOGIT_REL_RMS_LIMIT = 0.0137
# The loss hardly moves with precision (the control's gaps are 8.4e-5 to
# 1.16e-3, the sound job's at most 5.0e-4 over 12 seeds): it does not part
# the two, and the control fails by the logits alone. It is held against a
# part of the batch left out, at the limit of the accepted cells, six times
# the largest sound gap.
J_LOSS_ABS_LIMIT = 0.003


def init_weights(key, job: dict):
    """Seeded weights in job["dtype"]; the router, its bias and a KDA
    layer's A_log and dt_bias in float32 whatever the job's type, as the
    program keeps them. Call it under jax.jit: each float32 draw is scaled,
    cast and freed inside the program.

    Drawn so that the routing is even from the seed, for the reason PR 41's,
    PR 43's and PR 48's modules give: this job's step time depends on where
    its tokens are routed (the chip computes only the copies for the experts
    it holds), and whatever the tokens' hidden states have in common shifts
    a router's 256 scores alike for every token. So:
    - an embedding row is a one-hot product, fan-in 1: unit elements, so a
      token's own vector leads its hidden state (PR 41's rule);
    - the matrices that write into the residual stream (`kda_o`, `wo`,
      `w_down`, `experts_down`, `shared_down`) are scaled by
      (4 n_layers)^-1/2 and CENTRED over their inputs (the mean row taken
      off; `kda_o` a head's rows at a time), PR 43's rule: a KDA layer's
      values pass SiLU, which leaves every channel a mean that the state
      hands to every later token, and its output gate is a sigmoid, mean
      one half; what a mixer's inputs have in common is written nowhere;
    - a router's columns have one length (drawn, then each divided by its
      norm), and so have a convolution's taps a channel, so that SiLU leaves
      every channel the same mean; the router's bias zeros (the source's
      buffer starts there).
    A KDA layer's A is drawn from [1, 16) a head and its step from
    [0.001, 0.1) a channel through the inverse of softplus, as the library
    draws them."""
    dtype = jnp.dtype(job["dtype"])
    d, v, h = job["d_model"], job["vocab_size"], job["n_heads"]
    n_layers = job["n_layers"]

    def dense(k, shape, fan_in, dtype=dtype):
        draw = jax.random.normal(k, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    def writes(k, shape, fan_in, runs=1):  # into the residual stream
        draw = jax.random.normal(k, shape, jnp.float32)
        by_run = draw.reshape(*shape[:-2], runs, shape[-2] // runs, shape[-1])
        draw = (by_run - jnp.mean(by_run, axis=-2, keepdims=True)).reshape(
            shape)
        return (draw / jnp.sqrt(fan_in * 4 * n_layers)).astype(dtype)

    def columns(k, shape, dtype=jnp.float32):  # of one length
        draw = jax.random.normal(k, shape, jnp.float32)
        return (draw / jnp.linalg.norm(draw, axis=0, keepdims=True)).astype(
            dtype)

    def kda(k):
        dk, dv, taps = (job["linear_key_head_dim"],
                        job["linear_value_head_dim"],
                        job["linear_conv_kernel"])
        step = jnp.exp(jax.random.uniform(
            k[13], (h * dk,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "kda_q": dense(k[0], (d, h * dk), d),
            "kda_k": dense(k[1], (d, h * dk), d),
            "kda_v": dense(k[2], (d, h * dv), d),
            "kda_conv_q": columns(k[3], (taps, h * dk), dtype),
            "kda_conv_k": columns(k[4], (taps, h * dk), dtype),
            "kda_conv_v": columns(k[5], (taps, h * dv), dtype),
            "kda_b": dense(k[6], (d, h), d),
            "kda_f_down": dense(k[7], (d, dv), d),
            "kda_f_up": dense(k[8], (dv, h * dk), dv),
            "kda_a_log": jnp.log(jax.random.uniform(
                k[9], (h,), jnp.float32, 1.0, 16.0)),
            "kda_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "kda_g_down": dense(k[10], (d, dv), d),
            "kda_g_up": dense(k[11], (dv, h * dv), dv),
            "kda_norm_scale": jnp.ones((dv,), dtype),
            "kda_o": writes(k[12], (h * dv, d), h * dv, runs=h),
        }

    def latent(k):
        dn, dr, dv, r = (job["qk_nope_head_dim"], job["qk_rope_head_dim"],
                         job["v_head_dim"], job["kv_lora_rank"])
        return {
            "wq": dense(k[0], (d, h * (dn + dr)), d),
            "mla_dkv": dense(k[1], (d, r + dr), d),
            "mla_kv_scale": jnp.ones((r,), dtype),
            "mla_ukv": dense(k[2], (r, h * (dn + dv)), r),
            "wo": writes(k[3], (h * dv, d), h * dv),
        }

    def mlp(k, sparse: bool):
        if not sparse:
            f = job["d_ff"]
            return {"w_gate": dense(k[0], (d, f), d),
                    "w_up": dense(k[1], (d, f), d),
                    "w_down": writes(k[2], (f, d), f)}
        e, fe = job["n_experts"], job["moe_d_ff"]
        fs = job.get("moe_shared_d_ff") or job["n_shared_experts"] * fe
        held = job.get("n_experts_held") or e
        return {"router": columns(k[0], (d, e)),
                "router_bias": jnp.zeros((e,), jnp.float32),
                "experts_gate": dense(k[1], (held, d, fe), d),
                "experts_up": dense(k[2], (held, d, fe), d),
                "experts_down": writes(k[3], (held, fe, d), fe),
                "shared_gate": dense(k[4], (d, fs), d),
                "shared_up": dense(k[5], (d, fs), d),
                "shared_down": writes(k[6], (fs, d), fs)}

    keys = jax.random.split(key, n_layers + 2)
    layers = []
    for i, kind in enumerate(job["layer_types"]):
        k = jax.random.split(keys[2 + i], 21)
        layers.append({
            "attn_scale": jnp.ones((d,), dtype),
            "mlp_scale": jnp.ones((d,), dtype),
            **(kda(k) if kind == "kda" else latent(k)),
            **mlp(k[14:], i >= job["first_dense_layers"])})
    return {
        "embedding": dense(keys[0], (v, d), 1),
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


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


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


def kda_recurrence(q, k, v, g, beta):
    """Kimi Delta Attention's rule token by token, one sequence: q, k [S, H,
    d_k] (normalised, q scaled), v [S, H, d_v], g [S, H, d_k] (a decay a
    channel), beta [S, H] -> o [S, H, d_v] and the final state [H, d_k,
    d_v]."""

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[:, :, None] * state  # Diag(alpha_t) S_{t-1}
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + beta_t[:, None, None] * jnp.einsum(
            "hk,hv->hkv", k_t, v_t - read)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    start = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    state, out = jax.lax.scan(token, start, (q, k, v, g, beta))
    return out, state


def kda_mixer(w, h, n_heads, dk, dv, eps):
    """One sequence's normalised h [S, D] -> the mixer's output [S, D]."""
    s = h.shape[0]
    q = jax.nn.silu(_shifted_conv(h @ w["kda_q"], w["kda_conv_q"]))
    k = jax.nn.silu(_shifted_conv(h @ w["kda_k"], w["kda_conv_k"]))
    v = jax.nn.silu(_shifted_conv(h @ w["kda_v"], w["kda_conv_v"]))
    q = _l2(q.reshape(s, n_heads, dk)) * dk ** -0.5
    k = _l2(k.reshape(s, n_heads, dk))
    v = v.reshape(s, n_heads, dv)
    beta = jax.nn.sigmoid(h @ w["kda_b"])
    g = -jnp.exp(w["kda_a_log"])[:, None] * jax.nn.softplus(
        (h @ w["kda_f_down"]) @ w["kda_f_up"] + w["kda_dt_bias"]
    ).reshape(s, n_heads, dk)
    out, _ = kda_recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ w["kda_g_down"]) @ w["kda_g_up"])
    out = _rmsnorm(out, w["kda_norm_scale"], eps) * gate.reshape(
        s, n_heads, dv)
    return out.reshape(s, n_heads * dv) @ w["kda_o"]


def _rope(x, theta: float):
    """x [S, H, d_r]: the two halves of d_r rotated by position."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def latent_mixer(w, h, dims, theta, eps):
    """One sequence's normalised h [S, D] -> latent attention's output
    [S, D]. `dims` = (H, d_n, d_r, d_v, r); `theta` None: no position."""
    n_heads, dn, dr, dv, r = dims
    s = h.shape[0]
    q = (h @ w["wq"]).reshape(s, n_heads, dn + dr)
    ckv = h @ w["mla_dkv"]
    c = _rmsnorm(ckv[:, :r], w["mla_kv_scale"], eps)
    q_r, k_r = q[..., dn:], ckv[:, None, r:]  # k_r [S, 1, d_r]: every head's
    if theta is not None:
        q_r, k_r = _rope(q_r, theta), _rope(k_r, theta)
    kv = (c @ w["mla_ukv"]).reshape(s, n_heads, dn + dv)
    size = min(QUERY_BLOCK, s)

    def block(args):
        q_n, q_p, first = args  # [size, H, d_n], [size, H, d_r], a position
        scores = (jnp.einsum("qhd,khd->hqk", q_n, kv[..., :dn])
                  + jnp.einsum("qhd,kd->hqk", q_p, k_r[:, 0])
                  ) * (dn + dr) ** -0.5
        seen = (first + jnp.arange(size))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:])

    out = jax.lax.map(block, (
        q[..., :dn].reshape(s // size, size, n_heads, dn),
        q_r.reshape(s // size, size, n_heads, dr),
        jnp.arange(0, s, size)))
    return out.reshape(s, n_heads * dv) @ w["wo"]


def scores(w, h):
    """h [T, D] normalised -> the router's scores [T, E]."""
    return jax.nn.sigmoid(h @ w["router"])


def gates(w, h, top_k, scale):
    """h [T, D] normalised -> (gates [T, E], 0 where not chosen; chosen
    [T, k])."""
    s = scores(w, h)
    chosen = jax.lax.top_k(s + w["router_bias"], top_k)[1]  # [T, k]
    picked = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1]), axis=1)  # 0/1
    kept = s * picked
    return scale * kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20), chosen


def undecided(w, h, top_k, gap):
    """h [T, D] normalised -> [T] bool: the token's last choice and the
    first it did not take lie closer than `gap`."""
    top = jax.lax.top_k(scores(w, h) + w["router_bias"], top_k + 1)[0]
    return top[:, top_k - 1] - top[:, top_k] < gap


def routed(w, h, top_k, scale, first):
    """h [T, D] normalised -> the gated sum over the experts HELD (E's
    `first` to `first` + held), every one of them computed for every
    token."""
    held = w["experts_up"].shape[0]
    g = jax.lax.dynamic_slice_in_dim(
        gates(w, h, top_k, scale)[0], first, held, axis=1)

    def block(args):
        h_b, g_b = args  # [b, D], [b, held]
        act = jax.nn.silu(
            jnp.einsum("td,edf->etf", h_b, w["experts_gate"])) * (
                jnp.einsum("td,edf->etf", h_b, w["experts_up"]))
        return jnp.einsum("etf,efd->td", act * g_b.T[:, :, None],
                          w["experts_down"])

    size = min(TOKEN_BLOCK, h.shape[0])
    y = jax.lax.map(block, (h.reshape(-1, size, h.shape[-1]),
                            g.reshape(-1, size, held)))
    return y.reshape(h.shape)


def sparse_mlp(w, h, top_k, scale, first):
    """h [T, D] normalised -> routed + shared."""
    return routed(w, h, top_k, scale, first) + _swiglu(
        h, w["shared_gate"], w["shared_up"], w["shared_down"])


@partial(jax.jit, static_argnames=("kind", "dims", "linear", "theta", "eps",
                                   "rounding"))
def _mixer_half(layer, x, kind, dims, linear, theta, eps, rounding):
    """x [B, S, D] -> x + mixer(norm(x)), a sequence at a time. `linear` =
    (d_k, d_v) of a KDA layer; `dims` as `latent_mixer` takes them."""
    w = {k: _f32(v, rounding) for k, v in layer.items() if k.startswith(
        ("attn_scale", "kda_", "wq", "wo", "mla_"))}

    def mix(row):
        h = _rmsnorm(row, w["attn_scale"], eps)
        if kind == "kda":
            return row + kda_mixer(w, h, dims[0], *linear, eps)
        return row + latent_mixer(w, h, dims, theta, eps)

    return jax.lax.map(mix, x)


@partial(jax.jit, static_argnames=("eps", "top_k", "scale", "first",
                                   "rounding"))
def _mlp_half(layer, x, eps, top_k, scale, first, rounding):
    """x [B, S, D] -> x + mlp(norm(x))."""
    w = {k: _f32(v, rounding) for k, v in layer.items() if not k.startswith(
        ("attn_scale", "kda_", "wq", "wo", "mla_"))}
    b, s, d = x.shape
    h = _rmsnorm(x, w["mlp_scale"], eps)
    if "router" in w:
        return x + sparse_mlp(
            w, h.reshape(b * s, d), top_k, scale, first).reshape(b, s, d)
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])


@partial(jax.jit, static_argnames=("last", "eps", "top_k", "gap"))
def _undecided(layer, x, last, eps, top_k, gap):
    """x [B, S, D] as it enters a sparse layer's MLP half -> [B, last] bool,
    the last `last` positions."""
    b, _, d = x.shape
    w = {k: layer[k].astype(jnp.float32)
         for k in ("mlp_scale", "router", "router_bias")}
    h = _rmsnorm(x[:, -last:].reshape(-1, d), w["mlp_scale"], eps)
    return undecided(w, h, top_k, gap).reshape(b, last)


@partial(jax.jit, static_argnames=("last", "eps", "rounding"))
def _head(params, x, tokens, last, eps, rounding):
    scale = _f32(params["final_scale"], rounding)
    w_out = _f32(params["w_out"], rounding)

    def nll(args):  # a sequence at a time: its logits are [S, V] float32
        row, targets = args
        logprobs = jax.nn.log_softmax(
            _rmsnorm(row[:-1], scale, eps) @ w_out, axis=-1)
        return -jnp.take_along_axis(logprobs, targets[1:, None], axis=-1)

    logits = _rmsnorm(x[:, -last:], scale, eps) @ w_out
    return logits, jnp.mean(jax.lax.map(nll, (x, tokens)))


def forward(params, tokens, job: dict, last: int, rounding=None,
            undecided_gap=UNDECIDED_GAP):
    """tokens [B, S] -> (logits of the last `last` positions [B, last, V],
    the loss the program's step returns on the whole batch), float32. The
    reference itself (no `rounding`) gives NaN logits at the positions that
    are undecided by `undecided_gap` at some sparse layer (module
    docstring); 0 marks none."""
    if job.get("moe_aux_weight") or job.get("moe_z_weight"):
        raise ValueError(
            "this block has no balancing or z term: moe_aux_weight and "
            "moe_z_weight have to be 0")
    if job.get("attn_type") != "mla" or set(job["layer_types"]) - {
            "kda", "full_attention"}:
        raise ValueError(
            "this block's layers are 'kda' and 'full_attention' under "
            "attn_type 'mla'")
    eps = float(job["norm_eps"])
    theta = job.get("rope_theta")
    theta = None if theta is None else float(theta)
    dims = (job["n_heads"], job["qk_nope_head_dim"], job["qk_rope_head_dim"],
            job["v_head_dim"], job["kv_lora_rank"])
    linear = (job["linear_key_head_dim"], job["linear_value_head_dim"])
    top_k = job["moe_top_k"]
    last = min(last, tokens.shape[1])
    mark = rounding is None and undecided_gap > 0
    left_out = jnp.zeros((tokens.shape[0], last), bool)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens], rounding)
        for layer, kind in zip(params["layers"], job["layer_types"],
                               strict=True):
            x = _mixer_half(layer, x, kind, dims, linear, theta, eps,
                            rounding)
            if mark and "router" in layer:
                left_out |= _undecided(
                    layer, x, last, eps, top_k, float(undecided_gap))
            x = _mlp_half(layer, x, eps, top_k,
                          float(job.get("moe_gate_scale", 1)),
                          job.get("first_expert_held", 0), rounding)
        logits, loss = _head(params, x, tokens, last, eps, rounding)
    return jnp.where(left_out[..., None], jnp.nan, logits), loss


def rel_rms(got, want) -> float:
    """||got - want|| / ||want|| over the positions `want` decided (those
    whose logits are not NaN): steady from seed to seed where a widest
    single gap is not."""
    decided = ~jnp.isnan(want[..., 0])
    got = got.astype(jnp.float32)[decided]
    want = want.astype(jnp.float32)[decided]
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))
