"""The AFMoE block's module (arcee-ai/Trinity-Mini's config.json, `model_type`
afmoe, and `modeling_afmoe.py` as recalled, not fetched): the observed job's
weights, its plain float32 reference and check J's limits for it. A
configuration names the file under `reference` (`cells.load_reference`), as
the dense ones name `reference.py`.

Nothing of dynolog_tpu is imported here. The benchmark makes the weights
itself, from the seed, on the device, in the type the job trains in, and
hands the same pytree to the program's step and to this reference. The
pytree's layout is the program's input format: {embedding, w_out,
final_scale, layers: [one dict a layer:
  attn_scale, attn_post_scale, mlp_scale, mlp_post_scale [d]: the four
      norms' weights;
  wq, wg [d, H_q d_h], wk, wv [d, H_kv d_h], wo [H_q d_h, d];
  q_head_scale, k_head_scale [d_h]: one weight for all heads of q, one for k;
  a dense layer (the first job["first_dense_layers"]): w_gate, w_up [d, f],
      w_down [f, d];
  a sparse layer: router [d, E] float32, router_bias [E] float32,
      experts_gate, experts_up [held, d, f_e], experts_down [held, f_e, d],
      shared_gate, shared_up [d, f_s], shared_down [f_s, d]]}.

The model, written down plainly (H_q = n_heads query heads of d_h =
attn_head_dim on H_kv = n_kv_heads key/value heads; W = sliding_window; E =
n_experts columns, k = moe_top_k a token). rms(x; w) = x / sqrt(mean(x^2) +
eps) * w over the last axis, eps job["norm_eps"].

  x = E[tokens] * sqrt(d)                       (`mup_enabled`)
  a layer:
    x <- x + rms(attn(rms(x; attn_scale)); attn_post_scale)
    x <- x + rms(mlp(rms(x; mlp_scale)); mlp_post_scale)
  logits = rms(x; final_scale) W_out            an untied head

  attn(h), no bias anywhere:
    q = h W_q [H_q, d_h], k = h W_k, v = h W_v [H_kv, d_h], g = h W_g
    q <- rms(q; q_head_scale), k <- rms(k; k_head_scale): over the d_h of a
        head, one weight of d_h for all heads
    on a "sliding_attention" layer ONLY (job["rope_layer_types"]): q and k
        rotated by position, theta job["rope_theta"] over the whole d_h,
        the two halves of d_h paired; a "full_attention" layer carries no
        position
    query i sees key j where 0 <= i - j and, on a sliding layer, i - j < W;
        query head j on key/value head j // (H_q / H_kv); scores over
        sqrt(d_h), softmax, computed a block of QUERY_BLOCK queries at a
        time against every key ([H_q, block, S] float32 scores are what
        fits: a sequence's [H_q, S, S] are 8.6 GB at 8192 positions)
    attn = (softmax(...) v * sigmoid(g)) W_o

  mlp(h) of a dense layer: W_down (silu(h W_gate) * (h W_up)), width d_ff
  mlp(h) of a sparse layer:
    s = sigmoid(h W_r)                          float32, E columns
    K = the k largest of s + b                  b = router_bias (the
                                                source's expert_bias): it
                                                moves the choice and not the
                                                gates
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
partial result goes through the post-norm and on to the next layer. The
shared expert is whole. With every expert held it is the uncut layer
(`tests/test_trinity.py` adds eight shares up to it, before the post-norm).
The experts held are computed for every token and summed under gates that
are 0 for an expert not chosen: no sort, no dispatch. A block of tokens at a
time, so that it fits beside the weights.

The loss is what the program's step returns: cross entropy, the tokens their
own shifted targets, over the vocabulary the job holds.

float32 throughout under `jax.default_matmul_precision("highest")`; the
bfloat16 weights are cast where they are used. `lower` is the control of
check J: the same reference with every weight rounded to float8 (e4m3), the
nearest precision below the bfloat16 the configuration states; the router
goes through it too. It has to FAIL the limit that the sound job passes.

What check J compares, as `nemotron_h_block.py` has it: a token's k-th choice
is a comparison of two scores, and where they lie closer than the rounding
of the stream they are computed from, bfloat16 and float32 may choose
differently; such a token's whole routed part then differs, which says
nothing of the program's precision. `forward` marks the positions that are
UNDECIDED in float32 (at some sparse layer the k-th and the (k+1)-th of s + b
lie closer than UNDECIDED_GAP) by NaN logits, and `rel_rms` is over the
positions the reference decided. Only the reference marks: a NaN the program
computes is in a position that counts, and fails. The loss is over every
position.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 1024
QUERY_BLOCK = 512
# A token is undecided where its last choice and the first it did not take
# lie closer than one step of bfloat16 below 1, where a last choice's score
# lies (0.8-0.9 of 128 columns), as `nemotron_h_block.py` has it and for its
# reason.
UNDECIDED_GAP = 2.0 ** -8
# Limits of check J for this block, set from readings on the chip at the
# published widths (`perfbench/control.py` on this block's configuration,
# twelve seeds, my chip run, PR 48, call 2; PERF.md section 2), over the
# positions the reference decided (a fifth of the last 256: each of the four
# sparse layers leaves 60-73 % of them decided): sound 0.006778-0.006907,
# float8 control 0.043647-0.051773. The two part by 6.3; the limit is their
# geometric middle, 2.51 times above the largest sound and 2.51 below the
# smallest control reading. (Under the first draw of the weights, attention's
# post-norms as heavy as the MLPs', two runs of the cell read 0.01153 and
# 0.01177, call 1: a stream whose common part had grown reads higher.)
J_LOGIT_REL_RMS_LIMIT = 0.0174
# The loss hardly moves with precision (the control's gaps are 3.7e-5 to
# 1.28e-3, the sound job's at most 4.0e-4 over 12 seeds, call 2): it does
# not part the two, and the control fails by the logits alone. It is held
# against a part of the batch left out, at the limit of the accepted cells,
# seven times the largest sound gap.
J_LOSS_ABS_LIMIT = 0.003


def init_weights(key, job: dict):
    """Seeded weights in job["dtype"]; the router and its bias in float32
    whatever the job's type, as the program keeps them. Call it under
    jax.jit: each float32 draw is scaled, cast and freed inside the program.

    Drawn so that the routing is even from the seed, for the reason PR 41's
    and PR 43's modules give: this job's step time depends on where its
    tokens are routed (the chip computes only the copies for the experts it
    holds), and whatever the tokens' hidden states have in common shifts a
    router's 128 scores alike for every token. What is particular here:
    - the embedding is drawn at 1 / sqrt(d) and the model multiplies it by
      sqrt(d) (`mup_enabled`): unit elements, PR 41's rule by the source's
      own means, so a token's own vector leads its hidden state;
    - a norm FOLLOWS every mixer, so the size of what a mixer adds is its
      post-norm's weight and nothing else (a scale on `wo` or `w_down` is
      normalised away). An MLP's post-norm weighs (4 n_layers)^-1/2, the
      size PR 43's `writes` gave a mixer's output; ATTENTION's a quarter of
      that, (64 n_layers)^-1/2, because attention is where a common part
      grows: a query's output is an average over the tens of keys it
      attends to, so what the tokens' own vectors add shrinks by the root
      of that number while what the window's hidden states have in common
      passes whole, and the post-norm brings the sum back to full size.
      Layer by layer the common share of the stream compounds: with both
      weights at (4 n_layers)^-1/2 a held expert's load lay 0.78-1.23 times
      the mean at the first sparse layer and 0.375-1.87 times at the fourth,
      the held sixteen's share 10.9-13.8 % by seed and layer (my chip run,
      PR 48, call 1, twelve seeds); with attention's a quarter the loads
      lie 0.84-1.17 times the mean at every sparse layer and the held share
      11.9-13.0 % (call 2, the same seeds), what is left being mostly the
      draw of 8192 tokens. A trained model's weights are whatever training
      left;
    - the per-head norms' weights are sqrt(2): a head's scores then spread
      by 2 and a query attends to a few tens of its 2048 keys, as a trained
      head does. At weights of 1 the scores spread by 1, a query's output is
      close to the mean of its window's values, the same for every query
      nearby, and the averaging above is over hundreds of keys;
    - SwiGLU has no mean to centre away (PR 43 centred ReLU^2's): the
      matrices are drawn plainly, normal / sqrt(fan_in);
    - a router's columns have one length (drawn, then each divided by its
      norm), its bias zeros (the source's buffer starts there)."""
    dtype = jnp.dtype(job["dtype"])
    d, v, n_layers = job["d_model"], job["vocab_size"], job["n_layers"]
    hq, hkv, dh = job["n_heads"], job["n_kv_heads"], job["attn_head_dim"]

    def dense(k, shape, fan_in, dtype=dtype):
        draw = jax.random.normal(k, shape, jnp.float32)
        return (draw / jnp.sqrt(fan_in)).astype(dtype)

    def columns(k, shape):  # of one length, float32
        draw = jax.random.normal(k, shape, jnp.float32)
        return draw / jnp.linalg.norm(draw, axis=0, keepdims=True)

    def full(shape, value):
        return jnp.full(shape, value, dtype)

    def layer(k, sparse: bool):
        post = (4 * n_layers) ** -0.5
        w = {
            "attn_scale": full((d,), 1),
            "attn_post_scale": full((d,), post / 4),
            "mlp_scale": full((d,), 1), "mlp_post_scale": full((d,), post),
            "wq": dense(k[0], (d, hq * dh), d),
            "wk": dense(k[1], (d, hkv * dh), d),
            "wv": dense(k[2], (d, hkv * dh), d),
            "wg": dense(k[3], (d, hq * dh), d),
            "wo": dense(k[4], (hq * dh, d), hq * dh),
            "q_head_scale": full((dh,), math.sqrt(2)),
            "k_head_scale": full((dh,), math.sqrt(2)),
        }
        if not sparse:
            f = job["d_ff"]
            w.update(w_gate=dense(k[5], (d, f), d),
                     w_up=dense(k[6], (d, f), d),
                     w_down=dense(k[7], (f, d), f))
            return w
        e, fe = job["n_experts"], job["moe_d_ff"]
        fs = job.get("moe_shared_d_ff") or job["n_shared_experts"] * fe
        held = job.get("n_experts_held") or e
        w.update(router=columns(k[5], (d, e)),
                 router_bias=jnp.zeros((e,), jnp.float32),
                 experts_gate=dense(k[6], (held, d, fe), d),
                 experts_up=dense(k[7], (held, d, fe), d),
                 experts_down=dense(k[8], (held, fe, d), fe),
                 shared_gate=dense(k[9], (d, fs), d),
                 shared_up=dense(k[10], (d, fs), d),
                 shared_down=dense(k[11], (fs, d), fs))
        return w

    keys = jax.random.split(key, n_layers + 2)
    return {
        "embedding": dense(keys[0], (v, d), d),
        "w_out": dense(keys[1], (d, v), d),
        "final_scale": jnp.ones((d,), dtype),
        "layers": [layer(jax.random.split(keys[2 + i], 12),
                         i >= job["first_dense_layers"])
                   for i in range(n_layers)],
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


def rope(x, theta: float):
    """x [S, H, d_h]: the two halves of d_h rotated by position."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention(w, hid, heads, window, theta, eps):
    """One sequence's normalised hid [S, D] -> attention's output [S, D].
    `heads` = (H_q, H_kv, d_h); `window` None: every key at or before the
    query; `theta` None: no position."""
    hq, hkv, dh = heads
    s = hid.shape[0]
    q = _rmsnorm((hid @ w["wq"]).reshape(s, hq, dh), w["q_head_scale"], eps)
    k = _rmsnorm((hid @ w["wk"]).reshape(s, hkv, dh), w["k_head_scale"], eps)
    v = (hid @ w["wv"]).reshape(s, hkv, dh)
    if theta is not None:
        q, k = rope(q, theta), rope(k, theta)
    size = min(QUERY_BLOCK, s)

    def block(args):
        q_b, first = args  # [size, H_kv, group, d_h], the first's position
        scores = jnp.einsum("qngd,knd->ngqk", q_b, k) / math.sqrt(dh)
        ahead = (first + jnp.arange(size))[:, None] - jnp.arange(s)[None, :]
        seen = ahead >= 0 if window is None else (
            (ahead >= 0) & (ahead < window))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("ngqk,knd->qngd", probs, v)

    out = jax.lax.map(block, (
        q.reshape(s // size, size, hkv, hq // hkv, dh),
        jnp.arange(0, s, size)))
    gate = jax.nn.sigmoid(hid @ w["wg"])
    return (out.reshape(s, hq * dh) * gate) @ w["wo"]


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
    """h [T, D] normalised -> routed + shared, before the post-norm."""
    return routed(w, h, top_k, scale, first) + _swiglu(
        h, w["shared_gate"], w["shared_up"], w["shared_down"])


@partial(jax.jit, static_argnames=("heads", "window", "theta", "eps",
                                   "rounding"))
def _attn_half(layer, x, heads, window, theta, eps, rounding):
    """x [B, S, D] -> x + post_norm(attn(norm(x))), a sequence at a time."""
    w = {k: _f32(layer[k], rounding) for k in (
        "attn_scale", "attn_post_scale", "wq", "wk", "wv", "wg", "wo",
        "q_head_scale", "k_head_scale")}
    return x + _rmsnorm(jax.lax.map(
        lambda row: attention(
            w, _rmsnorm(row, w["attn_scale"], eps), heads, window, theta,
            eps), x), w["attn_post_scale"], eps)


@partial(jax.jit, static_argnames=("eps", "top_k", "scale", "first",
                                   "rounding"))
def _mlp_half(layer, x, eps, top_k, scale, first, rounding):
    """x [B, S, D] -> x + post_norm(mlp(norm(x)))."""
    w = {k: _f32(v, rounding) for k, v in layer.items()}
    b, s, d = x.shape
    h = _rmsnorm(x, w["mlp_scale"], eps)
    if "router" in w:
        y = sparse_mlp(
            w, h.reshape(b * s, d), top_k, scale, first).reshape(b, s, d)
    else:
        y = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    return x + _rmsnorm(y, w["mlp_post_scale"], eps)


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


def layer_kinds(job: dict) -> list:
    """[(window or None, theta or None)] a layer."""
    kinds = job.get("layer_types") or ["full_attention"] * job["n_layers"]
    rotated = job.get("rope_layer_types")
    theta = job.get("rope_theta")
    return [(job["sliding_window"] if kind == "sliding_attention" else None,
             float(theta) if theta is not None and (
                 rotated is None or kind in rotated) else None)
            for kind in kinds]


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
    for key in ("qk_head_norm", "attn_gate", "post_norm", "scale_embedding"):
        if not job.get(key):
            raise ValueError(f"this block's job states {key}: true")
    eps = float(job["norm_eps"])
    heads = (job["n_heads"], job.get("n_kv_heads") or job["n_heads"],
             job.get("attn_head_dim") or job["d_model"] // job["n_heads"])
    top_k = job["moe_top_k"]
    last = min(last, tokens.shape[1])
    mark = rounding is None and undecided_gap > 0
    left_out = jnp.zeros((tokens.shape[0], last), bool)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"][tokens], rounding) * math.sqrt(
            job["d_model"])
        for layer, (window, theta) in zip(params["layers"], layer_kinds(job)):
            x = _attn_half(layer, x, heads, window, theta, eps, rounding)
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
