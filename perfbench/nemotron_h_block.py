"""The Nemotron-H block's module (NVIDIA-Nemotron-3-Nano-30B-A3B's
config.json and `modeling_nemotron_h.py`; Mamba-2, arXiv:2405.21060; the
Nemotron-H report, arXiv:2504.03624): the observed job's weights, its plain
float32 reference and check J's limits for it. A configuration names the
file under `reference` (`cells.load_reference`), as the dense ones name
`reference.py`.

Nothing of dynolog_tpu is imported here. The benchmark makes the weights
itself, from the seed, on the device, in the type the job trains in, and
hands the same pytree to the program's step and to this reference. The
pytree's layout is the program's input format: {embedding, w_out,
final_scale, layers: [one dict a block, by job["block_types"]:
  "mamba2":    ssm_scale [d], ssm_in [d, 2 H P + 2 G N + H], ssm_conv
               [K, H P + 2 G N], ssm_conv_bias [H P + 2 G N], ssm_a_log,
               ssm_dt_bias, ssm_d [H] float32, ssm_norm_scale [H P],
               ssm_out [H P, d]
  "moe":       mlp_scale [d], router [d, E] float32, router_bias [E]
               float32, experts_up [held, d, f_e], experts_down
               [held, f_e, d], shared_up [d, f_s], shared_down [f_s, d]
  "attention": attn_scale [d], wq [d, H_q d_h], wk, wv [d, H_kv d_h],
               wo [H_q d_h, d]
  "mlp":       mlp_scale [d], w_up [d, f], w_down [f, d]]}.

The model, written down plainly. Every block is ONE mixer:
x <- x + mixer(rmsnorm(x) * scale), eps job["norm_eps"]; after the last a
final norm and an untied head. By kind:

  Mamba-2 (H = ssm_heads heads of P = ssm_head_dim, d_inner = H P, G =
  ssm_groups, N = ssm_state, K = ssm_conv_kernel taps), u the normalised x:
    [z | xBC | dt] = u W_in               d_inner + (d_inner + 2 G N) + H
    xBC = silu(conv(xBC))                 causal, depthwise: four shifted
                                          adds and a bias, zeros before the
                                          first position
    xBC splits into x [H, P], B [G, N], C [G, N]; head h uses group
        h // (H / G)
    delta_t = softplus(dt_t + dt_bias)    a number a head, no clamp
    A = -exp(A_log)                       a number a head
    the state h [P, N] a head, h_0 = 0, TOKEN BY TOKEN (a scan over the
    positions carrying h; no chunk):
        h_t = exp(delta_t A) h_{t-1} + delta_t x_t (x) B_t
        y_t = h_t C_t + D x_t
    y <- y * silu(z), RMS-normalised in the G groups of d_inner / G
        channels (same eps), times ssm_norm_scale
    x += y W_out

  Experts (E = n_experts columns, k = moe_top_k a token), h the normalised x:
    s = sigmoid(h W_r)                    float32, E columns
    K = the k largest of s + b            b = router_bias (the source's
                                          e_score_correction_bias): it moves
                                          the choice and not the gates
    g_e = moe_gate_scale * s_e / (sum over K of s + 1e-20) for e in K, else 0
    x += sum over e HELD HERE of g_e * W_down_e relu(W_up_e h)^2
         + W_down_s relu(W_up_s h)^2      the shared expert, ungated
    no balancing term: the source balances by moving b between steps, the
    trainer's rule; b is whatever the weights hold (zeros from
    `init_weights`) and job["moe_aux_weight"], job["moe_z_weight"] have to
    be 0

  Attention (H_q = n_heads query heads of d_h = attn_head_dim on H_kv =
  n_kv_heads key/value heads), no bias, no rotary embedding
  (job["rope_theta"] has to be null):
    q = h W_q, k = h W_k, v = h W_v; k and v REPEATED to the H_q heads
        (query head j on key/value head j // (H_q / H_kv)); scores over
        sqrt(d_h), causal softmax, the [H_q, S, S] scores of a sequence
        written out; x += o W_o

  MLP:  x += W_down relu(W_up h)^2        (width d_ff; none in this job)

The share. The job holds job["n_experts_held"] of the E experts, from index
job["first_expert_held"] on, as one chip of an expert-parallel layer does.
The router keeps its E columns and a token its k choices; a choice that
falls on an expert not held adds nothing, here as in the program, and that
partial result goes on to the next block. The shared expert is whole. With
every expert held it is the uncut layer (`tests/test_nemotron_h.py` adds
eight shares up to it). The experts held are computed for every token and
summed under gates that are 0 for an expert not chosen: no sort, no
dispatch. A block of tokens at a time, so that it fits beside the weights.

The loss is what the program's step returns: cross entropy, the tokens their
own shifted targets, over the vocabulary the job holds.

float32 throughout under `jax.default_matmul_precision("highest")`; the
bfloat16 weights are cast where they are used. `lower` is the control of
check J: the same reference with every weight rounded to float8 (e4m3), the
nearest precision below the bfloat16 the configuration states; the router,
A_log, dt_bias and D go through it too. It has to FAIL the limit that the
sound job passes.

What check J compares. A token's k-th choice is a comparison of two scores,
and where they lie closer than the rounding of the stream they are computed
from, bfloat16 and float32 may choose differently; such a token's whole
expert part then differs, which says nothing of the program's precision
(the choice is the router's, sound either way) and moved the reading by the
seed as no other block's does. `forward` therefore marks the positions that
are UNDECIDED in float32 (at some expert block the k-th and the (k+1)-th of
s + b lie closer than UNDECIDED_GAP) by NaN logits, and `rel_rms` is over the
positions the reference decided. Only the reference marks: a NaN the
program computes is in a position that counts, and fails. The loss is over
every position.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 1024
# A token is undecided where its last choice and the first it did not take
# lie closer than one step of bfloat16 below 1, where a last choice's score
# lies (0.8-0.9 of 128 columns): the scores are float32 on both sides, but
# the stream they are computed from is bfloat16 in the program, and its
# rounding moves a score by up to a few thousandths. A third of the
# positions are decided at all three expert blocks by this gap.
UNDECIDED_GAP = 2.0 ** -8
# Limits of check J for this block, set from readings on the chip at the
# published widths (`perfbench/control.py` on this block's configuration,
# twelve seeds, my chip run, PR 43, call 8; PERF.md section 2), over the
# positions the reference decided: sound 0.005552-0.006010, float8 control
# 0.030745-0.038562. The two part by 5.1; the limit is their geometric
# middle, 2.26 times above the largest sound and 2.26 below the smallest
# control reading. (Over every position, call 4: sound 0.011021-0.017616,
# moving by the seed with the few tokens whose sixth choice fell the other
# way in bfloat16, control 0.040546-0.044861, 2.3 apart.)
J_LOGIT_REL_RMS_LIMIT = 0.0136
# The loss hardly moves with precision (the control's gaps are 1.8e-4 to
# 9.8e-4, the sound job's at most 3.3e-4 over 12 seeds, call 8): it does
# not part the two, and the control fails by the logits alone. It is held
# against a part of the batch left out, at the limit of the accepted cells,
# nine times the largest sound gap: the program's loss on one of the two
# sequences reads 0.0057-0.0077 from the reference's on both, on the first
# half of each sequence 0.0064-0.0125 (three seeds, call 8). One altered
# token reads 1.4e-5 to 3.4e-4 and is NOT caught.
J_LOSS_ABS_LIMIT = 0.003


def init_weights(key, job: dict):
    """Seeded weights, normal / sqrt(fan_in), in job["dtype"]; the router,
    its bias and a Mamba-2 block's A_log, dt_bias and D in float32 whatever
    the job's type, as the program keeps them. Call it under jax.jit: each
    float32 draw is scaled, cast and freed inside the program.

    Drawn so that the routing is even from the seed, for the reason PR 41's
    module gives: this job's step time depends on where its tokens are
    routed (the chip computes only the copies for the experts it holds).
    Whatever every token's hidden state has in common shifts a router's 128
    scores alike for every token, and an expert whose shift is a tenth of
    the scores' spread gets half as many tokens again. Drawn plainly, this
    block's mixers add a common part that SwiGLU and softmax attention do
    not: a ReLU^2 unit's mean is half its mean square, so an expert adds
    the sum of its down matrix's rows to every token (15-18 % of what an
    expert block writes), and SiLU after the convolution leaves every
    channel a mean that D and the gate carry to the output (6-9 % of what a
    state-space block writes). The loads then lie 0.2 to 3.4 times the mean
    an expert at the last block and the held sixteen's share 11.6-14.7 %
    by the seed (my chip run, PR 43, call 1). So:
    - an embedding row is a one-hot product, fan-in 1: unit elements, so a
      token's own vector leads its hidden state (PR 41's rule);
    - the matrices that write into the residual stream (`ssm_out`, `wo`,
      `experts_down`, `shared_down`, `w_down`) are scaled by
      (4 n_layers)^-1/2, half the source's `rescale_prenorm_residual` (one
      write a block), and CENTRED over their inputs (the mean row taken
      off; `ssm_out` a head's 64 rows at a time, a head's channels having
      one mean and the heads, whose states decay at their own rates, not),
      so that what their inputs have in common is written nowhere;
    - a router's columns have one length (drawn, then each divided by its
      norm): a column 1.4 % longer than another draws 5 % more tokens; and
      so have a convolution's taps a channel, so that SiLU leaves every
      channel the same mean;
    - the convolution's bias is drawn at a fifth of its weights' size.
    The held sixteen's share is then 12.2-12.9 % a block (calls 2 and 4)
    and an expert's load 0.80-1.27 times the mean; what is left is mostly
    the draw of 8192 tokens a step, a binomial's 1.2 % of a block's held
    copies.
    A Mamba-2 block's A is drawn from [1, 16) and its step from
    [0.001, 0.1) through the inverse of softplus, as the source draws them
    (`time_step_min`, `time_step_max`); D ones; the router's bias zeros
    (the source's buffer starts there)."""
    dtype = jnp.dtype(job["dtype"])
    d, v = job["d_model"], job["vocab_size"]
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

    def mamba2(k):
        h, p = job["ssm_heads"], job["ssm_head_dim"]
        taps = job["ssm_conv_kernel"]
        inner = h * p
        conv = inner + 2 * job["ssm_groups"] * job["ssm_state"]
        step = jnp.exp(jax.random.uniform(
            k[4], (h,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "ssm_scale": jnp.ones((d,), dtype),
            "ssm_in": dense(k[0], (d, inner + conv + h), d),
            "ssm_conv": columns(k[1], (taps, conv), dtype),
            "ssm_conv_bias": dense(k[2], (conv,), 25 * taps),
            "ssm_a_log": jnp.log(jax.random.uniform(
                k[3], (h,), jnp.float32, 1.0, 16.0)),
            "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "ssm_d": jnp.ones((h,), jnp.float32),
            "ssm_norm_scale": jnp.ones((inner,), dtype),
            "ssm_out": writes(k[5], (inner, d), inner, runs=h),
        }

    def moe(k):
        e, fe, fs = job["n_experts"], job["moe_d_ff"], job["moe_shared_d_ff"]
        held = job.get("n_experts_held") or e
        return {
            "mlp_scale": jnp.ones((d,), dtype),
            "router": columns(k[0], (d, e)),
            "router_bias": jnp.zeros((e,), jnp.float32),
            "experts_up": dense(k[1], (held, d, fe), d),
            "experts_down": writes(k[2], (held, fe, d), fe),
            "shared_up": dense(k[3], (d, fs), d),
            "shared_down": writes(k[4], (fs, d), fs),
        }

    def attention(k):
        hq, hkv, dh = job["n_heads"], job["n_kv_heads"], job["attn_head_dim"]
        return {
            "attn_scale": jnp.ones((d,), dtype),
            "wq": dense(k[0], (d, hq * dh), d),
            "wk": dense(k[1], (d, hkv * dh), d),
            "wv": dense(k[2], (d, hkv * dh), d),
            "wo": writes(k[3], (hq * dh, d), hq * dh),
        }

    def mlp(k):
        f = job["d_ff"]
        return {
            "mlp_scale": jnp.ones((d,), dtype),
            "w_up": dense(k[0], (d, f), d),
            "w_down": writes(k[1], (f, d), f),
        }

    make = {"mamba2": mamba2, "moe": moe, "attention": attention, "mlp": mlp}
    keys = jax.random.split(key, n_layers + 2)
    return {
        "embedding": dense(keys[0], (v, d), 1),
        "w_out": dense(keys[1], (d, v), d),
        "final_scale": jnp.ones((d,), dtype),
        "layers": [make[kind](jax.random.split(keys[2 + i], 6))
                   for i, kind in enumerate(job["block_types"])],
    }


def lower(w):
    """The control's rounding: through float8 e4m3 and back."""
    return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _f32(w, rounding):
    return w.astype(jnp.float32) if rounding is None else rounding(w)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def causal_conv(x, w, bias):
    """x [S, channels], w [K, channels]: the last tap is the position's
    own; K shifted adds and the bias."""
    taps, s = w.shape[0], x.shape[0]
    out = bias
    for j in range(taps):
        shift = taps - 1 - j  # tap j reads the position `shift` before
        out = out + jnp.pad(x, ((shift, 0), (0, 0)))[:s] * w[j]
    return out


def state_space(x, delta, a, b_in, c_in, d_skip, state=None):
    """One sequence, token by token. x [S, H, P], delta [S, H], a and
    d_skip [H], b_in and c_in [S, G, N] -> (y [S, H, P], the state after
    the last position [H, P, N])."""
    h, p = x.shape[1:]
    per = h // b_in.shape[1]
    b_in, c_in = (jnp.repeat(t, per, axis=1) for t in (b_in, c_in))

    def step(state, at):
        x_t, delta_t, b_t, c_t = at
        state = (jnp.exp(delta_t * a)[:, None, None] * state
                 + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    if state is None:
        state = jnp.zeros((h, p, b_in.shape[-1]), jnp.float32)
    state, y = jax.lax.scan(step, state, (x, delta, b_in, c_in))
    return y + d_skip[:, None] * x, state


def mamba2_block(w, x, dims, eps):
    """One sequence x [S, D] -> x + the mixer. `dims` = (H, P, G, N)."""
    h, p, g, n = dims
    s, inner = x.shape[0], h * p
    proj = _rmsnorm(x, w["ssm_scale"], eps) @ w["ssm_in"]
    z, xbc, dt = proj[:, :inner], proj[:, inner:-h], proj[:, -h:]
    xbc = jax.nn.silu(causal_conv(xbc, w["ssm_conv"], w["ssm_conv_bias"]))
    y, _ = state_space(
        xbc[:, :inner].reshape(s, h, p),
        jax.nn.softplus(dt + w["ssm_dt_bias"]), -jnp.exp(w["ssm_a_log"]),
        xbc[:, inner:inner + g * n].reshape(s, g, n),
        xbc[:, inner + g * n:].reshape(s, g, n), w["ssm_d"])
    y = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return x + (y.reshape(s, inner) * w["ssm_norm_scale"]) @ w["ssm_out"]


def attention_block(w, x, heads, eps):
    """One sequence x [S, D] -> x + attention. `heads` = (H_q, H_kv, d_h).
    The [H_q, S, S] float32 score matrix of a sequence is what fits."""
    hq, hkv, dh = heads
    s = x.shape[0]
    hid = _rmsnorm(x, w["attn_scale"], eps)
    q = (hid @ w["wq"]).reshape(s, hq, dh)
    k = jnp.repeat((hid @ w["wk"]).reshape(s, hkv, dh), hq // hkv, axis=1)
    v = jnp.repeat((hid @ w["wv"]).reshape(s, hkv, dh), hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(dh))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + out.reshape(s, hq * dh) @ w["wo"]


def gates(w, h, top_k, scale):
    """h [T, D] normalised -> (gates [T, E], 0 where not chosen; chosen
    [T, k])."""
    n_experts = w["router"].shape[-1]
    scores = jax.nn.sigmoid(h @ w["router"])  # [T, E]
    chosen = jax.lax.top_k(scores + w["router_bias"], top_k)[1]  # [T, k]
    picked = jnp.sum(jax.nn.one_hot(chosen, n_experts), axis=1)  # [T, E] 0/1
    kept = scores * picked
    return scale * kept / (jnp.sum(kept, -1, keepdims=True) + 1e-20), chosen


def undecided(w, h, top_k, gap):
    """h [T, D] normalised -> [T] bool: the token's last choice and the
    first it did not take lie closer than `gap`."""
    scores = jax.nn.sigmoid(h @ w["router"]) + w["router_bias"]
    top = jax.lax.top_k(scores, top_k + 1)[0]
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
        act = jnp.square(jax.nn.relu(
            jnp.einsum("td,edf->etf", h_b, w["experts_up"])))
        return jnp.einsum("etf,efd->td", act * g_b.T[:, :, None],
                          w["experts_down"])

    size = min(TOKEN_BLOCK, h.shape[0])
    y = jax.lax.map(block, (h.reshape(-1, size, h.shape[-1]),
                            g.reshape(-1, size, held)))
    return y.reshape(h.shape)


def sparse_block(w, x, top_k, scale, first, eps):
    """x [T, D] -> x + routed + shared."""
    h = _rmsnorm(x, w["mlp_scale"], eps)
    return x + routed(w, h, top_k, scale, first) + _relu2(
        h, w["shared_up"], w["shared_down"])


@partial(jax.jit, static_argnames=("kind", "dims", "heads", "eps", "top_k",
                                   "scale", "first", "rounding"))
def _block(layer, x, kind, dims, heads, eps, top_k, scale, first, rounding):
    w = {k: _f32(v, rounding) for k, v in layer.items()}
    b, s, d = x.shape
    if kind == "mamba2":
        return jax.lax.map(lambda row: mamba2_block(w, row, dims, eps), x)
    if kind == "attention":
        return jax.lax.map(lambda row: attention_block(w, row, heads, eps), x)
    if kind == "moe":
        return sparse_block(
            w, x.reshape(b * s, d), top_k, scale, first, eps).reshape(b, s, d)
    h = _rmsnorm(x, w["mlp_scale"], eps)
    return x + _relu2(h, w["w_up"], w["w_down"])


@partial(jax.jit, static_argnames=("last", "eps", "top_k", "gap"))
def _undecided(layer, x, last, eps, top_k, gap):
    """x [B, S, D] as it enters an expert block -> [B, last] bool, the last
    `last` positions."""
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
    are undecided by `undecided_gap` at some expert block (module
    docstring); 0 marks none."""
    if job.get("moe_aux_weight") or job.get("moe_z_weight"):
        raise ValueError(
            "this block has no balancing or z term: moe_aux_weight and "
            "moe_z_weight have to be 0")
    if job.get("rope_theta") is not None:
        raise ValueError(
            "this block's attention carries no position: rope_theta has to "
            "be null")
    eps = float(job["norm_eps"])
    dims = (job.get("ssm_heads"), job.get("ssm_head_dim"),
            job.get("ssm_groups"), job.get("ssm_state"))
    heads = (job["n_heads"], job.get("n_kv_heads") or job["n_heads"],
             job.get("attn_head_dim") or job["d_model"] // job["n_heads"])
    top_k = job.get("moe_top_k")
    last = min(last, tokens.shape[1])
    mark = rounding is None and undecided_gap > 0
    left_out = jnp.zeros((tokens.shape[0], last), bool)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens], rounding)
        for kind, layer in zip(job["block_types"], params["layers"]):
            if mark and kind == "moe":
                left_out |= _undecided(
                    layer, x, last, eps, top_k, float(undecided_gap))
            x = _block(layer, x, kind, dims, heads, eps, top_k,
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
