"""Causal flash attention as Pallas TPU kernels (forward AND backward).

Design (TPU-first, not a port — the reference does no model computation):
- Online-softmax attention tiled for the MXU: the forward grid iterates
  over (batch*heads, query blocks); each program streams key/value blocks
  through VMEM with float32 accumulation, so the [S, S] score matrix is
  never materialized in HBM. The standard flash-attention recurrence
  (m/l running max/denominator) expressed with `jax.lax.fori_loop` so
  XLA/Mosaic sees static shapes. The forward also emits the per-row
  logsumexp, the only O(S) residual the backward needs.
- Causal skip in both directions: a query block only loops over key
  blocks up to its own diagonal (forward/dq), a key block only over query
  blocks from its diagonal down (dkv) — ~half the FLOPs.
- Backward: two Pallas kernels (dq; fused dk+dv) recompute probabilities
  blockwise from (q, k, v, lse) and use the delta = rowsum(dO ⊙ O) trick,
  so training at long context keeps the O(S) memory profile — materializing
  the score matrix in the VJP would reintroduce exactly the OOM the
  forward kernel avoids.
- The kernels carry no interpret switch: on a TPU Mosaic compiles them, and
  anywhere else the call fails. The CPU tests run the same code under
  `jax.experimental.pallas.tpu.force_tpu_interpret_mode()`, chosen in the
  test where it can be seen.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _pick_block(seq_len: int, target: int) -> int:
    """Largest divisor of seq_len that is <= target (>=1)."""
    b = min(target, seq_len)
    while seq_len % b:
        b -= 1
    return b


def reference_attention(q, k, v, *, causal: bool = True, scale=None):
    """Plain-XLA attention; q: [B, S, H, D], k: [B, S, Hkv, D], v:
    [B, S, Hkv, Dv] -> [B, S, H, Dv], k and v repeated to the H query heads
    where they are fewer. `scale` None: D ** -0.5."""
    d = q.shape[-1]
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if scale is None:
        scores = scores / jnp.sqrt(d).astype(q.dtype)
    else:
        scores = scores * jnp.asarray(scale, q.dtype)
    scores = scores.astype(jnp.float32)
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------- forward


def _scale(head_dim: int, scale):
    """The softmax scale: the caller's, or head_dim ** -0.5."""
    if scale is None:
        return jax.lax.rsqrt(jnp.float32(head_dim))
    return jnp.float32(scale)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k,
                causal, scale):
    """One (batch*head, q-block) program. q_ref: [1, block_q, D];
    k_ref: [1, S, D]; v_ref: [1, S, Dv]; o_ref: [1, block_q, Dv];
    lse_ref: [1, 1, S]
    (full row — Mosaic block shapes must tile (8, 128) or span the array;
    each program stores its own [block_q] slice)."""
    qi = pl.program_id(1)
    seq_len = k_ref.shape[1]
    head_dim = q_ref.shape[2]
    scale = _scale(head_dim, scale)

    q = q_ref[0].astype(jnp.float32) * scale  # [block_q, D]

    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[2]), jnp.float32)

    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [block_q, block_k]
        if causal:
            k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # Key blocks past this query block's diagonal are fully masked —
        # skip them (dynamic trip count lowers to a while loop).
        n_kb = jax.lax.div(qi * block_q + block_q + block_k - 1, block_k)
    else:
        n_kb = seq_len // block_k
    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    # Causal rows always see >= 1 key, but guard anyway (e.g. padding use).
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = m + jnp.log(l_safe)


def _kv_row(group: int):
    """The index map of a whole key or value head beside query head b of
    the grid: row b // group of [B*Hkv, S, D] (b where the heads are as
    many). The programs of a group follow one another, so the row is
    fetched once a group and never written out a query head each."""
    if group == 1:
        return lambda b, i: (b, 0, 0)
    return lambda b, i: (b // group, 0, 0)


def _flash_forward(q, k, v, causal, block_q, block_k, scale=None):
    """q [B*H, S, D], k [B*Hkv, S, D], v [B*Hkv, S, Dv] -> (out
    [B*H, S, Dv], lse [B*H, 1, S] f32)."""
    bh, s, d = q.shape
    dv = v.shape[2]
    kv_row = _kv_row(bh // k.shape[0])
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    kernel = functools.partial(
        _fwd_kernel, block_q=bq, block_k=bk, causal=causal, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), kv_row),
            pl.BlockSpec((1, s, dv), kv_row),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, s), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        name="flash_attention_fwd",
    )(q, k, v)


# --------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_q, block_k, causal, scale):
    """dQ for one (batch*head, q-block): loop over visible key blocks."""
    qi = pl.program_id(1)
    seq_len = k_ref.shape[1]
    head_dim = q_ref.shape[2]
    scale = _scale(head_dim, scale)

    qs = q_ref[0].astype(jnp.float32) * scale      # pre-scaled Q block
    do = do_ref[0].astype(jnp.float32)             # [block_q, Dv]
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]    # [block_q]
    delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            qs, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])               # [block_q, block_k]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        n_kb = jax.lax.div(qi * block_q + block_q + block_k - 1, block_k)
    else:
        n_kb = seq_len // block_k
    dq0 = jnp.zeros((block_q, head_dim), jnp.float32)
    dq = jax.lax.fori_loop(0, n_kb, body, dq0)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *sums, block_q, block_k, causal, scale):
    """dK and dV for one (batch*head, k-block): loop over query blocks at
    or below this key block's diagonal. Under grouped heads the grid has a
    third, innermost axis over the query heads of the key/value head's
    group: each program adds its query head's part into `sums` (dk and dv
    in float32, in fast memory), the group's first clears them and its last
    writes them out."""
    ki = pl.program_id(1)
    seq_len = q_ref.shape[1]
    head_dim = q_ref.shape[2]
    scale = _scale(head_dim, scale)

    k_blk = k_ref[0].astype(jnp.float32)            # [block_k, D]
    v_blk = v_ref[0].astype(jnp.float32)            # [block_k, Dv]
    k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)

    def body(qb, carry):
        dk, dv = carry
        qs = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(
            jnp.float32) * scale
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)]
        s = jax.lax.dot_general(
            qs, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # [block_q, block_k]
        if causal:
            q_pos = qb * block_q + jax.lax.iota(jnp.int32, block_q)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # [block_k, Dv]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk_new = dk + jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)     # dsᵀ·(Q·scale) = dK
        return dk_new, dv_new

    if causal:
        # First query block whose rows can see this key block.
        qb_start = jax.lax.div(ki * block_k, block_q)
    else:
        qb_start = 0
    n_qb = seq_len // block_q
    dk, dv = jax.lax.fori_loop(qb_start, n_qb, body, (
        jnp.zeros((block_k, head_dim), jnp.float32),
        jnp.zeros((block_k, v_ref.shape[2]), jnp.float32)))
    if not sums:
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
        return
    dk_sum, dv_sum = sums
    member = pl.program_id(2)

    @pl.when(member == 0)
    def _():
        dk_sum[...] = jnp.zeros_like(dk_sum)
        dv_sum[...] = jnp.zeros_like(dv_sum)

    dk_sum[...] += dk
    dv_sum[...] += dv

    @pl.when(member == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_sum[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sum[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k,
                    scale=None):
    """Residuals q [B*H, S, D], k [B*Hkv, S, D], v [B*Hkv, S, Dv], out
    [B*H, S, Dv] + cotangent g -> (dq, dk, dv), dk and dv summed over the
    query heads of a key/value head's group."""
    bh, s, d = q.shape
    dv = v.shape[2]
    group = bh // k.shape[0]
    kv_row = _kv_row(group)
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=False)[:, None, :]  # [BH, 1, S]

    row_full = pl.BlockSpec((1, 1, s), lambda b, i: (b, 0, 0))

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, block_q=bq, block_k=bk, causal=causal, scale=scale),
        grid=(bh, s // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), kv_row),
            pl.BlockSpec((1, s, dv), kv_row),
            pl.BlockSpec((1, bq, dv), lambda b, i: (b, i, 0)),
            row_full,
            row_full,
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        name="flash_attention_bwd_dq",
    )(q, k, v, g, lse, delta)

    if group == 1:
        grid, scratch = (bh, s // bk), ()

        def head(b, i):  # the query head's whole row beside key block i
            return (b, 0, 0)

        def block(b, i):
            return (b, i, 0)
    else:
        # (key/value head, key block, the group's query heads): the last
        # axis innermost, so a key block's sums stay in fast memory
        grid = (bh // group, s // bk, group)
        scratch = (pltpu.VMEM((bk, d), jnp.float32),
                   pltpu.VMEM((bk, dv), jnp.float32))

        def head(b, i, member):
            return (b * group + member, 0, 0)

        def block(b, i, member):
            return (b, i, 0)

    dk, dv_ = pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=bq, block_k=bk, causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, d), head),
            pl.BlockSpec((1, bk, d), block),
            pl.BlockSpec((1, bk, dv), block),
            pl.BlockSpec((1, s, dv), head),
            pl.BlockSpec((1, 1, s), head),
            pl.BlockSpec((1, 1, s), head),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), block),
            pl.BlockSpec((1, bk, dv), block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=scratch,
        name="flash_attention_bwd_dkv",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv_


# -------------------------------------------------------------- public op


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, block_q=512, block_k=512,
                    scale=None):
    """Flash attention; q: [B, S, H, D], k: [B, S, Hkv, D], v:
    [B, S, Hkv, Dv] -> [B, S, H, Dv]. The key/value heads may be fewer than
    the query heads (grouped-query attention): query head j reads key/value
    head j // (H / Hkv), k and v are never written out a query head each,
    and dk and dv come back at Hkv heads, summed over each group inside the
    dkv kernel. Keys may be wider than values (latent attention expands
    to keys of 192 and values of 128): the score products run over D, the
    P.V and dV products over Dv, nothing is padded. `scale` is the softmax
    scale, D ** -0.5 where None.

    Forward and backward both run as Pallas kernels; only O(S) residuals
    (q, k, v, out, lse) are saved.

    Default 512x512 blocks: larger blocks halve each program's full-K/V
    re-reads. What the three kernels reach of their roofline at 512x512 on
    a TPU v5e (useful causal work over traced time against the bfloat16
    peak, `perfbench/kernel_costs.py`; one capture a cell, PR 40's and PR
    41's chip runs, PERF.md section 5): forward 31 % at sequence 2048 and
    39-40 % at 4096, dq 64 % and 73 %, dkv 54 % and 59-61 % at heads of
    128; forward 44 %, dq 59 %, dkv 56 % at keys of 192 and values of 128.
    The products bound all three; they run on float32 operands.
    """
    b, _, h, _ = q.shape
    out, _ = _flash_forward(
        _to_bh(q), _to_bh(k), _to_bh(v), causal, block_q, block_k, scale)
    return _from_bh(out, b, h)


def _vjp_fwd(q, k, v, causal, block_q, block_k, scale):
    b, _, h, _ = q.shape
    out, lse = _flash_forward(
        _to_bh(q), _to_bh(k), _to_bh(v), causal, block_q, block_k, scale)
    return _from_bh(out, b, h), (q, k, v, out, lse)


def _vjp_bwd(causal, block_q, block_k, scale, res, g):
    q, k, v, out_bh, lse = res
    b, _, h, _ = q.shape
    kv = k.shape[2]
    dq, dk, dv = _flash_backward(
        _to_bh(q), _to_bh(k), _to_bh(v), out_bh, lse, _to_bh(g),
        causal, block_q, block_k, scale)
    return _from_bh(dq, b, h), _from_bh(dk, b, kv), _from_bh(dv, b, kv)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
