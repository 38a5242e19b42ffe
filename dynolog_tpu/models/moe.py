"""Dropless mixture-of-experts MLP, expert-parallel over the mesh's `expert` axis.

Replaces the dense MLP of the flagship transformer
(dynolog_tpu.models.transformer) when `cfg.n_experts > 0`, in every layer
after the first `cfg.first_dense_layers`. The reference
framework has no model code at all (it is a monitoring daemon, SURVEY §2.9);
this layer exists so the daemon's trace and telemetry paths are exercised by
the job an engineer with a slow sparse model actually runs: experts that
outnumber the chips, every token routed, none dropped, and an exchange
between the chips in every layer of every captured step.

The layer, for token t with normalised input h_t (E experts, k a token):

    s_t  = softmax_e(h_t W_r)                  float32, over all E experts
                                               (sigmoid of each score alone
                                               where cfg.moe_score says so)
    K_t  = the k largest s_t                   (of s_t + b where
                                               cfg.moe_select_bias:
                                               `router_bias`, a number an
                                               expert that moves the choice
                                               and not the gates; no
                                               gradient reaches it)
    g_te = s_te for e in K_t                   (/ sum over K_t of s_te where
                                               cfg.moe_norm_topk; times
                                               cfg.moe_gate_scale)
    y_t  = sum over e in K_t of
           g_te * W_down_e (silu(W_gate_e h_t) * (W_up_e h_t))
                                               (W_down_e relu(W_up_e h_t)^2,
                                               two matrices an expert, where
                                               cfg.mlp_act is "relu2")
           + S(h_t)                            where cfg.n_shared_experts:
                                               the experts every token
                                               visits, side by side as one
                                               expert of cfg.shared_d_ff
                                               (n_shared x the expert width
                                               unless stated), ungated

An expert's width is cfg.moe_d_ff (d_ff where 0). Where cfg.n_experts_held
is set, the chip holds that many of the E experts from index
cfg.first_expert_held on, as one chip of an expert-parallel layer does: the
router still scores all E and a token keeps its k, and a choice that falls
on an expert not held adds nothing to y_t. S and the loss terms are whole.

Two terms for the loss, each a mean over ALL the step's tokens:

    balance = E * sum_e f_e P_e     f_e: the share of the routed assignments
                                    that went to e (first choices alone, or
                                    all T x k where cfg.moe_balance_all_k),
                                    P_e: the mean of s_te over tokens
                                    (of s_te / sum_e s_te under sigmoid);
                                    where cfg.moe_seq_aux both are taken a
                                    sequence at a time and the sequences'
                                    terms averaged
    z       = mean_t logsumexp_e(h_t W_r)^2

There is no capacity: whatever the routing, every (token, choice) copy is
computed. The one dispatch, in four named phases (`jax.named_scope`, so a
capture's ops carry them):

    moe.route     router, top-k, gates, the two loss terms
    moe.dispatch  the copies sorted by expert; under expert parallelism sent
                  to the chip that holds their expert (an all-to-all over
                  `expert`) and put in expert order there; on one chip laid
                  out from tile boundaries (below)
    moe.experts   three grouped matrix products (`jax.lax.ragged_dot`; on the
                  TPU XLA lowers each to one Mosaic kernel that walks the
                  groups' rows a tile at a time) over the experts held here,
                  and between them the activation over the rows the groups
                  hold (`activate`, below)
    moe.combine   the way back (the second all-to-all), the copies unsorted
                  and summed under the gates
    moe.shared    the shared experts' SwiGLU, outside the dispatch

Where one chip computes its own copies (`expert` 1 or no mesh: every expert
or a share of them held), an expert's rows fill whole tiles. The sort leaves
group e starting wherever the groups before it end, and the grouped product
is handed the groups' lengths at run time: it cannot know where a boundary
falls, only pay for it, a row tile that holds the end of one expert's rows
and the start of the next's being worked once for each. At sixteen groups of
384 +- 30 rows that was most of the kernel's time (11 TFLOP/s on a chip whose
other products run at 85-124; PERF.md section 6, PR 43 and 46). So group e
starts at the sum of the earlier groups' lengths EACH ROUNDED UP to a
multiple of ALIGN, zero rows between its last copy and the next boundary,
and the products are handed the rounded lengths. The buffer is static and
holds the worst routing, copies + held x ALIGN rows (every copy on held
experts, every group one partial tile): no capacity, nothing dropped, one
path whatever the router does. At even routing a share of an eighth to a
quarter of the experts fills a sixth to a quarter of it, and the groups end
where the rounded lengths add up to. A gather whose RESULT is as long as
the buffer (the rows on their way in, forward and again when the layer is
rematerialised; the cotangent of the rows on their way back) therefore
starts as zeros and moves BLOCK rows a trip, as many trips as hold that sum
(`take_rows` under `used`: a loop with a bound read at run time, under the
scope `moe.rows`): under the worst routing every block is worked, and a row
that is not gathered is the zero row it would have been. The gathers whose
result is copies long stay whole. What lies between the grouped products is
bounded the same way: a grouped product writes its groups' rows and nothing
else, so the masks, the SiLU or ReLU^2 and their backward pass (`activate`,
with a backward function of its own) start from zeros and work BLOCK rows a
trip under the scope `moe.act` (a page of the buffer seen as [n / BLOCK,
BLOCK, f], written in place), as many trips as hold the groups, the rows of
the last block that lie past them masked inside the trip before anything
reads them; the products themselves walk their groups and were never
buffer-long. What still passes over every row of the buffer is the zero
fills those loops start from. (Under the exchange that pass stays whole,
`_activate_whole`: the bound is there, the rows the peers sent, but round
the loops the TPU's compiler holds every layer's rows to the end of the
step, and the job no longer fits its chip.) The layout costs no pass over
rows: it is folded into the two index vectors the copies are gathered by on
the way in and on the way back (`_aligned`, from the groups' lengths alone).
A padding row is a zero row and no token's place points at it: it multiplies
to zero through either activation, adds nothing to an expert's weight
gradient, and a copy for an expert that is not here lands nowhere and comes
back as zero, so the output, the loss and every gradient are those of the
plain sum.

Under a mesh the layer is a `shard_map`: each chip routes its own tokens
over all E experts and holds E / expert of them, with the experts' hidden
dimension tensor-parallel on `model`. Buffers are static and sized for the
worst routing (every copy of every chip sent to one chip), so nothing is
ever dropped; the rows that exist are what moves where the backend can lower
`ragged_all_to_all` (the TPU), and per-destination buffers padded to the
worst case through `all_to_all` elsewhere (XLA:CPU runs no ragged
all-to-all). The two share everything but that one call. With `expert` 1,
or no mesh, the layer runs without its exchange.

The layer is rematerialised (`jax.checkpoint`): its worst-case buffers are
0.5 GB each at OLMoE's widths and a step keeps only the layer's input, and
on one chip the layout beside it (the rounded lengths and the two index
vectors, 0.5 MB: the backward pass sorts and lays out nothing again, which
also keeps the maps out of a capture's metadata a second time).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from dynolog_tpu.parallel.sharding import BATCH_AXES, PARAM_RULES

# The rows a group's start is rounded up to where one chip computes its own
# copies (module docstring). XLA's grouped product works the rows by tiles
# of 256 on a v5e: from boundaries of 128 a tile still straddles two experts
# and nothing is gained (benchmarks/grouped_product_bench.py; PERF.md
# section 6, PR 46). 512 and not 256, because a group whose length at even
# routing is a whole number of tiles (768 rows an expert) spills into one
# more tile or not by the seed at 256 and the step follows it; at 512 both
# jobs that hold a share keep a third of a group as headroom. A constant of
# the kernel, not of a job.
ALIGN = 512

# The rows one trip of a gather into that buffer moves (`take_rows` told how
# many of the buffer's rows are used; module docstring). A multiple of ALIGN.
# Of 1024 to 16384, 4096 is the fastest at the three jobs' worst routing and
# at or within 1 % of the fastest at their even routing: a gathered block of
# 16 MB stays in fast memory until it is written into place, larger blocks
# round the rows used further up (benchmarks/grouped_product_bench.py;
# PERF.md section 6, PR 50). A constant of the kernel, not of a job.
BLOCK = 4096


def init_moe_layer(rng, cfg):
    """MoE layer params: router + stacked expert weights (no `*_gate`
    matrix where cfg.mlp_act is "relu2")."""
    dtype = jnp.dtype(cfg.dtype)
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    held = cfg.n_experts_held or e

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        ).astype(dtype)

    k = jax.random.split(rng, 7)
    layer = {
        # kept f32 end-to-end (routing numerics) — no bf16 round-trip
        "router": jax.random.normal(k[0], (d, e), jnp.float32) / math.sqrt(d),
        "experts_up": dense(k[2], (held, d, f), d),
        "experts_down": dense(k[3], (held, f, d), f),
    }
    if cfg.moe_select_bias:
        layer["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared_experts:
        fs = cfg.shared_d_ff
        layer.update(shared_up=dense(k[5], (d, fs), d),
                     shared_down=dense(k[6], (fs, d), fs))
    if cfg.mlp_act == "swiglu":
        layer["experts_gate"] = dense(k[1], (held, d, f), d)
        if cfg.n_shared_experts:
            layer["shared_gate"] = dense(k[4], (d, cfg.shared_d_ff), d)
    return layer


def _take_rows(rows, idx):
    return rows.at[idx].get(mode="fill", fill_value=0)


def _trips(used, block: int):
    """The blocks of `block` rows that hold a buffer's first `used` rows."""
    return jax.lax.div(used + (block - 1), block)


def _take_rows_used(rows, idx, used):
    """_take_rows(rows, idx) where idx is nowhere from place `used` on
    (None: not known, one gather of the whole): the result starts as zeros
    and BLOCK places a trip are gathered into it, up to the block that holds
    place `used` - 1. Run as it is by `take_rows`' own forward and backward
    functions, never differentiated."""
    if used is None:
        return _take_rows(rows, idx)
    n = idx.shape[0]
    block = min(BLOCK, n)

    def body(i, out):
        with jax.named_scope("moe.rows"):
            # a last block that would pass the end is moved back to end
            # there, by both slices alike: it moves again rows it has moved
            at = i * block
            got = _take_rows(
                rows, jax.lax.dynamic_slice(idx, (at,), (block,)))
            return jax.lax.dynamic_update_slice(out, got, (at, 0))

    return jax.lax.fori_loop(
        0, _trips(used, block), body,
        jnp.zeros((n, *rows.shape[1:]), rows.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def take_rows(rows, idx, back_idx, fan=1, used=None, back_used=None):
    """rows[idx], a zero row where idx is rows.shape[0] (no such row).

    `back_idx` is the same map read from the other side: for each of the
    rows.shape[0] * fan places a row of the result can have come from, the
    place it went to (or the result's length: nowhere). The cotangent is
    then a gather too, summed over the `fan` copies of a row, where the
    transpose of a gather would be a scatter-add.

    `used` / `back_used`: a number at run time from which on every place of
    `idx` / `back_idx` is nowhere. The gather by that vector then moves the
    blocks of rows before it and leaves the rest the zeros they are
    (`_take_rows_used`): the same result, for the rows that exist.
    """
    return _take_rows_used(rows, idx, used)


def _take_rows_fwd(rows, idx, back_idx, fan, used, back_used):
    return _take_rows_used(rows, idx, used), (back_idx, back_used)


def _take_rows_bwd(fan, res, ct):
    back_idx, back_used = res
    back = _take_rows_used(ct, back_idx, back_used)
    if fan > 1:
        back = back.reshape(-1, fan, back.shape[-1]).sum(axis=1)
    return back, None, None, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _act(*raw):
    """An expert's activation from its raw product(s): ReLU^2 of the one, or
    the SiLU of the first times the second."""
    if len(raw) == 1:
        return jnp.square(jax.nn.relu(raw[0]))
    gate, up = raw
    return jax.nn.silu(gate) * up


def _work_rows_used(work, sources, used, n_out: int):
    """`work`, a function of rows alone, over the first `used` rows of the
    `sources` (each [n, f], `used` a number at run time): `n_out` results
    that start as zeros, BLOCK rows a trip worked into them up to the block
    that holds row `used` - 1. Inside a trip the rows from `used` on are
    zeros BEFORE `work` sees them: a grouped product writes no row past its
    groups, what the buffer holds there (and what a transpose hands back for
    it) is not ours, and 0 x NaN is NaN (two of six runs on the chip lost
    their loss to it). `work` of zero rows is zero rows.

    The buffers are read and written a page at a time, [n / block, block, f]
    indexed by the trip, so that a trip is one fusion that writes in place
    (at a row offset the compiler cannot see to be a multiple of its tiles
    it computes a block and then copies it). A page is BLOCK rows where the
    buffer is a whole number of them, as every job's is, else their largest
    common divisor."""
    n, f = sources[0].shape
    block = math.gcd(n, BLOCK)
    pages = [rows.reshape(n // block, block, f) for rows in sources]

    def body(i, outs):
        with jax.named_scope("moe.act"):
            ours = (i * block + jnp.arange(block, dtype=jnp.int32)
                    < used)[:, None]
            got = work(*(
                jnp.where(ours, jax.lax.dynamic_index_in_dim(
                    page, i, keepdims=False), 0) for page in pages))
            return tuple(
                jax.lax.dynamic_update_index_in_dim(out, rows, i, 0)
                for out, rows in zip(outs, got))

    outs = jax.lax.fori_loop(
        0, _trips(used, block), body,
        tuple(jnp.zeros_like(pages[0]) for _ in range(n_out)))
    return tuple(out.reshape(n, f) for out in outs)


def _activate_whole(raw, used):
    """`activate` as one pass over every row of the buffer: the raw
    products masked from row `used` on, then `_act`; XLA's own backward."""
    there = (jnp.arange(raw[0].shape[0]) < used)[:, None]
    return _act(*(jnp.where(there, rows, 0) for rows in raw))


@jax.custom_vjp
def activate(raw, used):
    """What lies between the products into an expert and the product out of
    it: `_act` of the raw grouped products (a tuple of one or two [n, f])
    for the rows before `used`, a number at run time from which on no row
    holds a copy, and a zero row from there on. Only the blocks that hold a
    row before `used` are read or worked (`_work_rows_used`, under the scope
    `moe.act`); the backward pass walks the same blocks, from the cotangent
    and the raw products to the raw products' cotangents, zero rows from
    `used` on. Under the worst routing every block is worked."""
    return _work_rows_used(lambda *rows: (_act(*rows),), raw, used, 1)[0]


def _activate_fwd(raw, used):
    return activate(raw, used), (raw, used)


def _activate_bwd(res, ct):
    raw, used = res
    return _work_rows_used(
        lambda ct, *rows: jax.vjp(_act, *rows)[1](ct), (ct, *raw), used,
        len(raw)), None


activate.defvjp(_activate_fwd, _activate_bwd)


def _starts(sizes):
    return jnp.cumsum(sizes) - sizes


def _regroup(sizes, lands_at, n: int, nowhere: int):
    """For each of the n places of a buffer that holds segments `sizes` long
    back to back from place 0: the place its row has in another buffer, in
    which segment i starts at `lands_at[i]`; `nowhere` past the last row."""
    at = jnp.arange(n, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    seg = jnp.minimum(
        jnp.searchsorted(ends, at, side="right", method="compare_all"),
        sizes.shape[0] - 1)
    return jnp.where(
        at < ends[-1], lands_at[seg] + at - (ends - sizes)[seg], nowhere)


def _aligned(sizes, n_sorted: int, align: int):
    """The layout of the module docstring, from the groups' lengths alone.
    The copies sorted by expert hold the groups `sizes` long back to back
    from place 0 of `n_sorted`; in the buffer group e starts at the sum of
    the earlier groups' lengths each rounded up to a multiple of `align`.
    Returns (rounded, lands, came): the rounded lengths; for each sorted
    place the buffer's row it lands at (the buffer's length,
    n_sorted + len(sizes) * align: a copy of no group, which lands nowhere);
    for each of the buffer's rows the sorted place it came from (n_sorted: a
    row of padding, which came from nowhere). A row has moved by the padding
    of the groups before it: compares and sums over the groups, no gather
    from a table of sixteen (XLA:TPU writes one out as sixteen selects)."""
    n = n_sorted + sizes.shape[0] * align
    rounded = jax.lax.div(sizes + (align - 1), align) * align
    pad = rounded - sizes
    ends, rounded_ends = jnp.cumsum(sizes), jnp.cumsum(rounded)
    at = jnp.arange(n_sorted, dtype=jnp.int32)[:, None]
    lands = jnp.where(
        at[:, 0] < ends[-1],
        at[:, 0] + jnp.sum(jnp.where(at >= ends, pad, 0), axis=1), n)
    row = jnp.arange(n, dtype=jnp.int32)[:, None]
    before = row >= rounded_ends  # the groups that end before this row
    # in a group's padding the row has passed that group's last copy too
    copy = (row[:, 0] < rounded_ends[-1]) & (
        jnp.sum(row >= rounded_ends - pad, axis=1) == jnp.sum(before, axis=1))
    came = jnp.where(
        copy, row[:, 0] - jnp.sum(jnp.where(before, pad, 0), axis=1),
        n_sorted)
    return rounded, lands, came


def _exchange_rows(rows, send_sizes, recv_sizes, n_out, axis, ragged):
    """rows: segments for the peers of `axis`, in peer order from row 0,
    `send_sizes` long. Returns [n_out, D]: the segments the peers sent here,
    in peer order from row 0, `recv_sizes` long, zero rows after them."""
    n, d = rows.shape
    peers = send_sizes.shape[0]
    if ragged:
        # where this chip's segment starts in each peer's result
        lands_at = jax.lax.all_to_all(
            _starts(recv_sizes), axis, 0, 0, tiled=True)
        return jax.lax.ragged_all_to_all(
            rows, jnp.zeros((n_out, d), rows.dtype), _starts(send_sizes),
            send_sizes, lands_at, recv_sizes, axis_name=axis)
    # One buffer a peer, each long enough for every row there is.
    slot = jnp.arange(n, dtype=jnp.int32)
    padded = _take_rows(rows, jnp.where(
        slot < send_sizes[:, None], _starts(send_sizes)[:, None] + slot, n))
    got = jax.lax.all_to_all(padded, axis, 0, 0).reshape(peers * n, d)
    return _take_rows(got, _regroup(
        recv_sizes, jnp.arange(peers, dtype=jnp.int32) * n, n_out, peers * n))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def exchange_rows(rows, send_sizes, recv_sizes, n_out, axis, ragged):
    """The all-to-all of row segments over `axis`. Its transpose is the
    same exchange the other way."""
    return _exchange_rows(rows, send_sizes, recv_sizes, n_out, axis, ragged)


def _exchange_fwd(rows, send_sizes, recv_sizes, n_out, axis, ragged):
    out = _exchange_rows(rows, send_sizes, recv_sizes, n_out, axis, ragged)
    # an empty array carries the static row count to the transpose
    return out, (send_sizes, recv_sizes, jnp.zeros((rows.shape[0], 0)))


def _exchange_bwd(n_out, axis, ragged, res, ct):
    send_sizes, recv_sizes, like_rows = res
    back = _exchange_rows(
        ct, recv_sizes, send_sizes, like_rows.shape[0], axis, ragged)
    return back, None, None


exchange_rows.defvjp(_exchange_fwd, _exchange_bwd)


def _count(ids, n: int):
    """How many of `ids` are each of 0..n-1 (a compare and a sum: no
    scatter)."""
    return jnp.sum(
        ids.reshape(-1, 1) == jnp.arange(n, dtype=ids.dtype), axis=0,
        dtype=jnp.int32)


def _route(router, bias, h, cfg, stat_axes, seqs: int):
    """h [T, D], `seqs` sequences back to back -> (gates [T, k] float32,
    chosen [T, k] int32, balance, z): the two loss terms are over all the
    step's tokens, `stat_axes` being the mesh axes these T are a share over
    (None: they are all). `bias` [E] or None: added for the choice alone."""
    e = cfg.n_experts
    logits = h.astype(jnp.float32) @ router  # [T, E]; tiny, numerics matter
    if cfg.moe_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        gates, chosen = jax.lax.top_k(scores, cfg.moe_top_k)
    else:
        chosen = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias), cfg.moe_top_k)[1]
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.moe_norm_topk:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        if cfg.moe_score == "sigmoid":
            total = total + 1e-20  # the source's guard against a sum of 0
        gates = gates / total
    if cfg.moe_gate_scale != 1.0:
        gates = gates * cfg.moe_gate_scale
    counted = chosen if cfg.moe_balance_all_k else chosen[:, :1]
    z = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    tokens = jnp.float32(h.shape[0])
    if cfg.moe_seq_aux:
        # a sequence at a time, then the sequences' terms averaged; every
        # chip's sequences are whole, so over chips the mean of their means
        in_seq = h.shape[0] // seqs
        assigned = jax.vmap(lambda ids: _count(ids, e))(
            counted.reshape(seqs, -1)).astype(jnp.float32)
        prob = jnp.sum(probs.reshape(seqs, in_seq, e), axis=1)
        balance = jnp.mean(e * jnp.sum(
            assigned / (in_seq * counted.shape[1]) * prob / in_seq, axis=1))
        if stat_axes:
            balance = jax.lax.pmean(balance, stat_axes)
            z, tokens = jax.lax.psum((z, tokens), stat_axes)
        return gates, chosen, balance, z / tokens
    sums = (
        _count(counted, e).astype(jnp.float32), jnp.sum(probs, axis=0), z,
        tokens)
    if stat_axes:
        sums = jax.lax.psum(sums, stat_axes)
    assigned, prob, z, tokens = sums
    balance = e * jnp.sum(
        assigned / (tokens * counted.shape[1]) * prob / tokens)
    return gates, chosen, balance, z / tokens


def _moe_local(routing, experts, x, *, cfg, ep, tp, stat_axes, ragged):
    """The layer on one chip's tokens x [B, S, D]. `routing`: the router,
    and its selection bias where it has one; `experts`: the matrices of the
    experts held here ([E / ep, ...]: gate, up and down, or up and down of
    ReLU^2 experts). `ep` chips share the experts, `tp` each expert's
    hidden dimension."""
    router, bias = routing if len(routing) > 1 else (routing[0], None)
    w_gate, w_up, w_down = experts if len(experts) > 2 else (None, *experts)
    b, s, d = x.shape
    k = cfg.moe_top_k
    held = w_up.shape[0]
    tokens, copies = b * s, b * s * k
    h = x.reshape(tokens, d)

    with jax.named_scope("moe.route"):
        gates, chosen, balance, z = _route(router, bias, h, cfg, stat_axes, b)

    with jax.named_scope("moe.dispatch"):
        expert_of = chosen.reshape(copies)
        if ep == 1:
            # counted from the first expert held, round the E: the copies
            # for the experts held here sort to the front, in their order
            expert_of = (expert_of - cfg.first_expert_held) % cfg.n_experts
        order = jnp.argsort(expert_of).astype(jnp.int32)  # stable
        place = jnp.argsort(order).astype(jnp.int32)  # where each copy went
        group_sizes = _count(expert_of, cfg.n_experts)
        used = None  # under the exchange a gather's every place may be one
        if ep == 1:
            # `place`: a copy's row of the aligned buffer, or its length (n)
            # for an expert that is not here; `order`: a row's copy, or
            # `copies` for padding. Both read as a zero row, forward and
            # back: a copy for an expert that is not here adds nothing.
            group_sizes, lands, came = _aligned(
                group_sizes[:held], copies, ALIGN)
            n = copies + held * ALIGN
            place = lands[place]
            order = order.at[came].get(mode="fill", fill_value=copies)
            group_sizes, place, order = (
                checkpoint_name(a, "moe.layout")
                for a in (group_sizes, place, order))
            # no row of the buffer from here on holds a copy
            used = jnp.sum(group_sizes)
        rows = take_rows(h, order // k, place, k, used=used)  # by expert
        if ep > 1:
            # sent[source chip, expert held here]
            sent = jax.lax.all_gather(group_sizes, "expert").reshape(
                ep, ep, held)[:, jax.lax.axis_index("expert")]
            send_sizes = group_sizes.reshape(ep, held).sum(axis=1)
            recv_sizes = sent.sum(axis=1)
            n = ep * copies  # every copy of every chip may come here
            rows = exchange_rows(
                rows, send_sizes, recv_sizes, n, "expert", ragged)
            # They arrive in (source, expert) segments; the products want
            # (expert, source). Both index maps from the counts alone.
            by_source, by_expert = sent.reshape(-1), sent.T.reshape(-1)
            to_expert = _regroup(
                by_expert, _starts(by_source).reshape(ep, held).T.reshape(-1),
                n, n)
            to_source = _regroup(
                by_source, _starts(by_expert).reshape(held, ep).T.reshape(-1),
                n, n)
            rows = take_rows(rows, to_expert, to_source)
            group_sizes = sent.sum(axis=0)

    with jax.named_scope("moe.experts"):
        # the raw products, gate before up where an expert has both; what
        # they leave past their groups `activate` lets reach nothing
        raw = tuple(jax.lax.ragged_dot(rows, w, group_sizes)
                    for w in (w_gate, w_up) if w is not None)
        # Under the exchange the pass stays whole: round the loops XLA puts
        # every layer's weight-gradient products off to the end of the step
        # and holds their rows until then (two layers of OLMoE's over four
        # chips: 2.7 -> 7.0 GB of temporaries; PERF.md section 6, PR 51).
        act = (activate if ep == 1 else _activate_whole)(
            raw, jnp.sum(group_sizes))
        # no mask: the way back reads no row past the groups, and its
        # transpose's rows there `activate`'s backward pass leaves out
        out = jax.lax.ragged_dot(act, w_down, group_sizes)

    with jax.named_scope("moe.combine"):
        if ep > 1:
            out = take_rows(out, to_source, to_expert)
            out = exchange_rows(
                out, recv_sizes, send_sizes, copies, "expert", ragged)
        out = take_rows(
            out, place, order, back_used=used).reshape(tokens, k, d)
        y = jnp.einsum(
            "tkd,tk->td", out, gates,
            preferred_element_type=jnp.float32).astype(x.dtype)
        if tp > 1:
            y = jax.lax.psum(y, "model")

    return y.reshape(b, s, d), balance, z


def moe_mlp(layer, x, cfg, mesh=None):
    """MoE feed-forward. x: [B, S, D] -> (y [B, S, D], balance, z): the
    layer's output and its two loss terms (module docstring), to be scaled
    by cfg.moe_aux_weight and cfg.moe_z_weight."""
    routing = ("router", "router_bias") if cfg.moe_select_bias else ("router",)
    experts = ("experts_up", "experts_down")
    if cfg.mlp_act == "swiglu":
        experts = ("experts_gate", *experts)
    local = partial(
        _moe_local, cfg=cfg, ep=1, tp=1, stat_axes=None, ragged=False)
    if mesh is not None:
        if layer["experts_up"].shape[0] < cfg.n_experts:
            raise ValueError(
                f"a share of {layer['experts_up'].shape[0]} of "
                f"{cfg.n_experts} experts is one chip's: under a mesh the "
                "`expert` axis divides them")
        if cfg.moe_seq_aux and mesh.shape["seq"] > 1:
            raise ValueError(
                "moe_seq_aux balances a sequence at a time: the `seq` axis "
                "would cut the sequences")
        token_spec = P(BATCH_AXES, "seq", None)
        local = jax.shard_map(
            partial(
                _moe_local, cfg=cfg, ep=mesh.shape["expert"],
                tp=mesh.shape["model"], stat_axes=BATCH_AXES + ("seq",),
                ragged=mesh.devices.flat[0].platform == "tpu"),
            mesh=mesh,
            in_specs=(tuple(PARAM_RULES[name] for name in routing),
                      tuple(PARAM_RULES[name] for name in experts),
                      token_spec),
            out_specs=(token_spec, P(), P()), check_vma=False)
    # one chip keeps its layout beside the layer's input (module docstring)
    keep = jax.checkpoint_policies.save_only_these_names("moe.layout")
    exchanges = mesh is not None and mesh.shape["expert"] > 1
    y, balance, z = jax.checkpoint(
        local, policy=None if exchanges else keep)(
        tuple(layer[name] for name in routing),
        tuple(layer[name] for name in experts), x)
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            into = ("shared_gate", "shared_up")[cfg.mlp_act == "relu2":]
            y = y + _act(*(x @ layer[w] for w in into)) @ layer["shared_down"]
    return y, balance, z
