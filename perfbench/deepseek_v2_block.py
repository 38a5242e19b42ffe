"""The DeepSeek-V2 block's module (arXiv:2405.04434; DeepSeek-V2-Lite's
config.json): the observed job's weights, its plain float32 reference and
check J's limits for it. A configuration names the file under `reference`
(`cells.load_reference`), as the dense ones name `reference.py`.

Nothing of dynolog_tpu is imported here. The benchmark makes the weights
itself, from the seed, on the device, in the type the job trains in, and
hands the same pytree to the program's step and to this reference. The
pytree's layout is the program's input format: {embedding, w_out,
final_scale, layers: [{attn_scale, wq [d, H (d_n + d_r)], mla_dkv
[d, r + d_r], mla_kv_scale [r], mla_ukv [r, H (d_n + d_v)], wo [H d_v, d],
mlp_scale, and w_gate, w_up [d, f], w_down [f, d] in the first
job["first_dense_layers"] layers, else router [d, E] float32, experts_gate,
experts_up [held, d, f_e], experts_down [held, f_e, d], shared_gate,
shared_up [d, n_s f_e], shared_down [n_s f_e, d]}]}.

The block, written down plainly. Pre-norm, as the source's is. With x the
residual stream, H heads, d_n = qk_nope_head_dim, d_r = qk_rope_head_dim,
d_v = v_head_dim, r = kv_lora_rank, per layer:

  Latent attention (no query compression: q_lora_rank is null)
    h = rmsnorm(x) * attn_scale                        (eps job["norm_eps"])
    q = h W_Q                     H x (d_n + d_r); a head splits into
                                  q_nope (d_n) and q_pe (d_r)
    [c | k_pe] = h W_DKV          r + d_r; k_pe is ONE vector a token,
                                  shared by all heads
    c <- rmsnorm(c) * mla_kv_scale                     (over r, same eps)
    RoPE on q_pe and k_pe only, the two halves of d_r being the pairs (the
        repo's layout; the source interleaves them, a fixed permutation of
        W_Q's and W_DKV's rotary columns), at YaRN's frequencies over the
        d_r / 2 pairs: pair i turns at theta^(-2i/d_r); the blend
        f_i = theta^(-2i/d_r) x ((1 - ramp_i) + ramp_i / factor), ramp the
        linear ramp from 0 at pair `low` to 1 at pair `high`,
        low = floor(p(beta_fast)), high = ceil(p(beta_slow)),
        p(n) = (d_r / 2) ln(original positions / (2 pi n)) / ln(theta);
        cos and sin times m(mscale) / m(mscale_all_dim), which is 1.0 here,
        m(s) = 0.1 s ln(factor) + 1
    [k_nope | v] = c W_UKV        H x (d_n + d_v)
    k = [k_nope | k_pe], q = [q_nope | q_pe]
    causal softmax of q k^T x scale, times v; heads joined (H x d_v)
        through W_O;   scale = (d_n + d_r)^-1/2 x m(mscale_all_dim)^2
        (192^-1/2 x 1.2608^2 = 0.11472 at the published values)
    x += that

  MLP
    h_t = rmsnorm(x_t) * mlp_scale
    first job["first_dense_layers"] layers:
        x_t += W_down (silu(W_gate h_t) * (W_up h_t))          (width d_ff)
    every later layer:
        s_t = softmax_e(h_t W_r)              float32, over all E experts
        K_t = the k largest s_t (greedy);  g_te = s_te for e in K_t, else 0
              (not renormalised, scaling factor 1.0)
        x_t += sum over e HELD HERE of g_te * E_e(h_t)  +  S(h_t)
        E_e a SwiGLU of width moe_d_ff; S one SwiGLU of width
        n_shared_experts x moe_d_ff (the shared experts side by side)

The share. The job holds job["n_experts_held"] of the E experts, from index
job["first_expert_held"] on, as one chip of an expert-parallel layer does.
The router keeps its E columns and a token its k choices; a choice that
falls on an expert not held adds nothing, here as in the program, and that
partial result goes on to the next layer. The shared experts and the
balancing term are whole. With every expert held it is the uncut layer
(`tests/test_deepseek_v2.py` adds four shares up to it).

The experts held are computed for every token and summed under gates that
are 0 for an expert not chosen: no sort, no dispatch. A block of tokens at a
time, so that it fits beside the weights. Attention is a sequence at a
time with its [H, S, S] score matrix written out: no kernel.

The loss is what the program's step returns:

    cross entropy (the tokens their own shifted targets)
    + job["moe_aux_weight"] * mean over the expert layers of the balancing
      term: E * sum_e f_e P_e, f_e the share of the T x k assignments that
      went to e and P_e the mean of s_te, over ONE SEQUENCE's T tokens, and
      the sequences' terms averaged (the source's `seq_aux`)
    (no z term: the source has none, and job["moe_z_weight"] has to be 0)

float32 throughout under `jax.default_matmul_precision("highest")`; the
bfloat16 weights are cast where they are used. `lower` is the control of
check J: the same reference with every weight rounded to float8 (e4m3), the
nearest precision below the bfloat16 the configuration states; the router
goes through it too. It has to FAIL the limit that the sound job passes.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 1024
# Limits of check J for this block, set from readings on the chip at the
# published widths (`perfbench/control.py deepseek-v2-lite-5l-v5e1 4100030001
# 12`, twelve seeds, my chip run, PR 41, call 3; PERF.md section 2): sound
# 0.006327-0.006458, float8 control 0.029264-0.029392. The two part by 4.5;
# the limit is their geometric middle, 2.13 times above the largest sound
# and 2.13 below the smallest control reading. Both are small beside the
# other blocks' (dense 0.014-0.020 and 0.12-0.15): under `init_weights`'
# unit embedding a token's own vector, which no product has rounded, leads
# its hidden state. (Drawn as the other modules draw, the same twelve-seed
# run read 0.0247-0.0259 and 0.1331-0.1354, call 1.)
J_LOGIT_REL_RMS_LIMIT = 0.0137
# The loss hardly moves with precision (the control's gaps are 3.8e-6 to
# 6.6e-4, the sound job's at most 1.0e-4 over 12 seeds); it is held against
# a part of the batch left out or a token altered, at the limit of the
# accepted cells, thirty times the largest sound gap.
J_LOSS_ABS_LIMIT = 0.003


def init_weights(key, job: dict):
    """Seeded weights, normal / sqrt(fan_in), in job["dtype"]; the router in
    float32 whatever the job's type, as the program keeps it. Call it under
    jax.jit: each float32 draw is scaled, cast and freed inside the program.

    Two fan-ins are read as an initialisation for training reads them, and
    not as the other modules here do, because this job's step time depends
    on where its tokens are routed (the chip computes only the copies for
    the experts it holds). An embedding row is a one-hot product, fan-in 1:
    unit elements. And the matrices that write into the residual stream
    (`wo`, `w_down`, `experts_down`, `shared_down`) are scaled by
    (2 n_layers)^-1/2, GPT-2's rule. With the embedding drawn at
    1 / sqrt(d_model) a token's own vector is a fiftieth of what attention
    adds, every position's hidden state is led by its prefix's mean, the
    router prefers the same experts for every token (loads 32-60 % apart an
    expert, the held sixteen's share 23-31 % a layer by the seed) and the
    step moved by 1.5 % from seed to seed (my chip run, PR 41, call 2). So
    drawn, the loads are 4-6 % apart an expert and the held share is
    24.4-25.5 % a layer in five seeds (CPU, the real shape): even, as the
    balancing term keeps a trained router and as the sparse four-chip cell's
    is."""
    dtype = jnp.dtype(job["dtype"])
    d, v, h = job["d_model"], job["vocab_size"], job["n_heads"]
    dn, dr, dv, r = (job["qk_nope_head_dim"], job["qk_rope_head_dim"],
                     job["v_head_dim"], job["kv_lora_rank"])
    e, fe = job["n_experts"], job["moe_d_ff"]
    held = job.get("n_experts_held") or e
    fs = job["n_shared_experts"] * fe

    def dense(k, shape, fan_in, dtype=dtype):
        draw = jax.random.normal(k, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    def writes(k, shape, fan_in):  # into the residual stream
        return dense(k, shape, fan_in * 2 * job["n_layers"])

    keys = jax.random.split(key, job["n_layers"] + 2)
    layers = []
    for i in range(job["n_layers"]):
        k = jax.random.split(keys[2 + i], 11)
        layer = {
            "attn_scale": jnp.ones((d,), dtype),
            "wq": dense(k[0], (d, h * (dn + dr)), d),
            "mla_dkv": dense(k[1], (d, r + dr), d),
            "mla_kv_scale": jnp.ones((r,), dtype),
            "mla_ukv": dense(k[2], (r, h * (dn + dv)), r),
            "wo": writes(k[3], (h * dv, d), h * dv),
            "mlp_scale": jnp.ones((d,), dtype),
        }
        if i < job["first_dense_layers"]:
            f = job["d_ff"]
            layer.update(w_gate=dense(k[4], (d, f), d),
                         w_up=dense(k[5], (d, f), d),
                         w_down=writes(k[6], (f, d), f))
        else:
            layer.update(
                router=dense(k[4], (d, e), d, jnp.float32),
                experts_gate=dense(k[5], (held, d, fe), d),
                experts_up=dense(k[6], (held, d, fe), d),
                experts_down=writes(k[7], (held, fe, d), fe),
                shared_gate=dense(k[8], (d, fs), d),
                shared_up=dense(k[9], (d, fs), d),
                shared_down=writes(k[10], (fs, d), fs))
        layers.append(layer)
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


def mscale(factor: float, s: float) -> float:
    """YaRN's m(s) = 0.1 s ln(factor) + 1."""
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(job: dict) -> float:
    scale = (job["qk_nope_head_dim"] + job["qk_rope_head_dim"]) ** -0.5
    yarn = job.get("rope_scaling")
    if yarn:
        scale *= mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def rope_table(job: dict, seq: int):
    """(cos, sin) [seq, d_r / 2] of the rotary part, under YaRN where the
    job states `rope_scaling` (module docstring)."""
    half = job["qk_rope_head_dim"] // 2
    theta = float(job["rope_theta"])
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    amp = 1.0
    yarn = job.get("rope_scaling")
    if yarn:
        def pair(turns):
            return half * math.log(
                yarn["original_max_position_embeddings"]
                / (turns * 2 * math.pi)) / math.log(theta)

        low = max(math.floor(pair(yarn["beta_fast"])), 0)
        high = min(math.ceil(pair(yarn["beta_slow"])), 2 * half - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        freqs = freqs * (1.0 - ramp) + freqs / yarn["factor"] * ramp
        amp = (mscale(yarn["factor"], yarn["mscale"])
               / mscale(yarn["factor"], yarn["mscale_all_dim"]))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    return jnp.cos(angles) * amp, jnp.sin(angles) * amp


def _rope(x, cos, sin):
    """x [S, H, d_r]: the two halves of d_r rotated by position."""
    half = x.shape[-1] // 2
    cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def latent_attention(w, x, dims, eps, scale, cos, sin):
    """One sequence x [S, D] -> x + attention. `dims` = (H, d_n, d_r, d_v,
    r). The [H, S, S] float32 score matrix of a sequence is what fits."""
    h, dn, dr, dv, r = dims
    s = x.shape[0]
    hid = _rmsnorm(x, w["attn_scale"], eps)
    q = (hid @ w["wq"]).reshape(s, h, dn + dr)
    ckv = hid @ w["mla_dkv"]
    c = _rmsnorm(ckv[:, :r], w["mla_kv_scale"], eps)
    q_pe = _rope(q[..., dn:], cos, sin)
    k_pe = _rope(ckv[:, None, r:], cos, sin)  # [S, 1, d_r]: every head's
    kv = (c @ w["mla_ukv"]).reshape(s, h, dn + dv)
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn])
              + jnp.einsum("qhd,kd->hqk", q_pe, k_pe[:, 0])) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum(
        "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), kv[..., dn:])
    return x + out.reshape(s, h * dv) @ w["wo"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def routed(w, h, top_k, first):
    """h [T, D] normalised -> (the gated sum over the experts HELD, chosen
    [T, k], the router's probabilities [T, E]). The held experts are E's
    `first` to `first` + held."""
    n_experts = w["router"].shape[-1]
    held = w["experts_gate"].shape[0]
    probs = jax.nn.softmax(h @ w["router"], axis=-1)  # [T, E]
    best, chosen = jax.lax.top_k(probs, top_k)  # [T, k]
    picks = jax.nn.one_hot(chosen, n_experts)  # [T, k, E]
    gates = jnp.sum(best[..., None] * picks, axis=1)  # [T, E], 0 if not chosen
    gates = jax.lax.dynamic_slice_in_dim(gates, first, held, axis=1)

    def block(args):
        h_b, gates_b = args  # [b, D], [b, held]
        act = jax.nn.silu(jnp.einsum("td,edf->etf", h_b, w["experts_gate"])) * (
            jnp.einsum("td,edf->etf", h_b, w["experts_up"]))
        return jnp.einsum("etf,efd->td", act * gates_b.T[:, :, None],
                          w["experts_down"])

    size = min(TOKEN_BLOCK, h.shape[0])
    y = jax.lax.map(block, (h.reshape(-1, size, h.shape[-1]),
                            gates.reshape(-1, size, held)))
    return y.reshape(h.shape), chosen, probs


def sparse_mlp(w, x, seqs, top_k, first, eps):
    """x [T, D], `seqs` sequences back to back -> (x + routed + shared, the
    balancing term a sequence at a time, averaged)."""
    n_experts = w["router"].shape[-1]
    h = _rmsnorm(x, w["mlp_scale"], eps)
    y, chosen, probs = routed(w, h, top_k, first)
    y = y + _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    picks = jax.nn.one_hot(chosen.reshape(seqs, -1), n_experts)  # [B, S k, E]
    share = jnp.mean(picks, axis=1)  # [B, E]: f_e of each sequence
    mean_prob = jnp.mean(probs.reshape(seqs, -1, n_experts), axis=1)
    balance = jnp.mean(n_experts * jnp.sum(share * mean_prob, axis=1))
    return x + y, balance


@partial(jax.jit, static_argnames=("dims", "eps", "scale", "top_k", "first",
                                   "rounding"))
def _layer(layer, x, cos, sin, dims, eps, scale, top_k, first, rounding):
    w = {k: _f32(v, rounding) for k, v in layer.items()}
    b, s, d = x.shape
    x = jax.lax.map(
        lambda row: latent_attention(w, row, dims, eps, scale, cos, sin), x)
    if "router" not in w:
        h = _rmsnorm(x, w["mlp_scale"], eps)
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), 0.0
    x, balance = sparse_mlp(w, x.reshape(b * s, d), b, top_k, first, eps)
    return x.reshape(b, s, d), balance


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


def forward(params, tokens, job: dict, last: int, rounding=None):
    """tokens [B, S] -> (logits of the last `last` positions [B, last, V],
    the loss the program's step returns on the whole batch), float32."""
    if job.get("moe_z_weight"):
        raise ValueError("this block has no z term: moe_z_weight has to be 0")
    eps = float(job["norm_eps"])
    dims = (job["n_heads"], job["qk_nope_head_dim"], job["qk_rope_head_dim"],
            job["v_head_dim"], job["kv_lora_rank"])
    with jax.default_matmul_precision("highest"):
        cos, sin = rope_table(job, tokens.shape[1])
        x = _f32(params["embedding"][tokens], rounding)
        balance = 0.0
        for layer in params["layers"]:
            x, layer_balance = _layer(
                layer, x, cos, sin, dims, eps, softmax_scale(job),
                job["moe_top_k"], job.get("first_expert_held", 0), rounding)
            balance = balance + layer_balance
        logits, nll = _head(params, x, tokens, last, eps, rounding)
        sparse = job["n_layers"] - job["first_dense_layers"]
        return logits, nll + job["moe_aux_weight"] * balance / max(sparse, 1)


def rel_rms(got, want) -> float:
    """||got - want|| / ||want||: steady from seed to seed where a widest
    single gap is not."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))
