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
- What a program does for one (query block, key block) tile, and why (PR 45,
  each choice timed alone on a v5e, `benchmarks/flash_attention_bench.py`):
  a query's statistics (the running maximum m and denominator l of the
  forward, lse and delta of the backward) live as [1, S] ROWS, in memory
  and in the loop, and a tile is laid out so that they are used as rows.
  The forward and the dkv kernel therefore compute a tile's scores
  TRANSPOSED, [block_k, block_q] (keys down the sublanes, queries across
  the lanes): the maximum and the sum over a tile's keys are then maxima
  and adds of whole registers where a [block_q, block_k] tile needs a
  reduction across lanes for every eight queries (64 + 64 a tile, which
  bound the forward); m, l and alpha are 4 registers each, not 64; a row
  goes down the sublanes for nothing; and pᵀ·dO and dsᵀ·Q are plain
  products where they were transposes of a 512 x 512 tile. The forward
  keeps its accumulator transposed too, [Dv, block_q] += Vᵀ·p, and turns
  it once a program (pᵀ·V into a turned result a tile measured a fifth
  slower). The
  dq kernel reduces nothing over a tile and keeps [block_q, block_k]: its
  rows are broadcast into the tile where they are used, inside the loop —
  laid out as [block_q, 1] columns before the loop they are 128 registers
  held across it, and the kernel is a sixth slower.
- Precision: m, l, lse, delta, the exponentials, every accumulator and the
  final division are float32 — they are sums over thousands of keys, and
  exp's argument needs the bits. The products' operands are what the MXU
  makes of them in one pass: bfloat16. Where the forward and dkv hand it
  bfloat16 (q, k, v, dO as they arrive; p and ds rounded where they enter a
  product, as `reference_attention` rounds its probabilities) and where dq
  hands it float32 the results are the same to the bit: the unit rounds a
  float32 operand to bfloat16 itself. Neither is faster on a v5e, which
  has no bfloat16 vector unit: a pack costs what the wider push saves.
  float32 inputs are never rounded by the kernels' own code.
- One loop a kernel, every visited tile masked: the tiles wholly below
  the diagonal could skip the iota, the compare and the select, but a
  second loop (a second copy of the body) costs every program more than
  the mask costs the tiles that do not need it (measured: 3-9 % slower).
- A window (`window` W, static; a model's `sliding_attention` layers): a
  query at position i sees key j where 0 <= i - j < W. The band has a
  second edge, so the one loop gains a second bound and the one mask a
  second compare, and nothing else: the forward and dq loops start at
  the key block that holds the earliest key the query block's FIRST query
  still sees (`_key_blocks`) and end at the diagonal as before; the dkv
  loop starts at the diagonal as before and ends at the query block that
  holds the latest query that still sees the key block's LAST key
  (`_query_blocks`). What is skipped: every tile wholly outside the band,
  on both sides. What is masked: every visited tile, by `_mask` with both
  compares; the tiles the two edges cross (at W a multiple of the block,
  two of a query block's W / block + 1) are computed whole, so at W 2048
  and blocks of 512 a query block visits 5 tiles for 4 tiles' worth of
  pairs and no windowed kernel can read above 80 % of its roofline. A
  query row that sees no key of the first tile its block visits carries
  the maximum -1e30 and sums of masked keys through it; the first key it
  does see multiplies both by exp(-1e30 - m) = 0. The windowed programs
  are named `flash_attention_window_fwd`, `_bwd_dq`, `_bwd_dkv`: a
  kernel's name is its op's name and its scope in a capture, and the
  benchmark's readers of the plain kernels match a fragment of theirs.
  With `window=None` the bounds and the mask are written as they were, and
  the three programs are the plain causal ones instruction for
  instruction (perfbench/aot.py on parent and change, PERF.md section 6,
  PR 48).
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


def band_mask(s_q: int, s_k: int, window=None):
    """[s_q, s_k] bool: query i sees key j where 0 <= i - j and, under a
    `window`, i - j < window."""
    ahead = jnp.arange(s_q)[:, None] - jnp.arange(s_k)[None, :]
    seen = ahead >= 0
    return seen if window is None else seen & (ahead < window)


def reference_attention(q, k, v, *, causal: bool = True, scale=None,
                        window=None):
    """Plain-XLA attention; q: [B, S, H, D], k: [B, S, Hkv, D], v:
    [B, S, Hkv, Dv] -> [B, S, H, Dv], k and v repeated to the H query heads
    where they are fewer. `scale` None: D ** -0.5. `window` W (causal only):
    a query sees the W keys that end at its own position."""
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
        mask = band_mask(q.shape[1], k.shape[1], window)
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------- forward

_NT = (((1,), (1,)), ((), ()))  # a · bᵀ: both contract their last dimension
_NN = (((1,), (0,)), ((), ()))  # a · b
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b: both contract their first dimension


def _dot(a, b, dims):
    """A product on the MXU: operands as they are, the sum in float32.
    bfloat16 operands have one precision, and it is named: Mosaic refuses
    them under a caller's `jax.default_matmul_precision("highest")`, which
    float32 operands still follow."""
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=jnp.float32)


def _scale(head_dim: int, scale):
    """The softmax scale: the caller's, or head_dim ** -0.5."""
    if scale is None:
        return jax.lax.rsqrt(jnp.float32(head_dim))
    return jnp.float32(scale)


def _rows(ref, block, size):
    """Rows block * size .. of a [1, S, D] reference."""
    return ref[0, pl.ds(pl.multiple_of(block * size, size), size), :]


def _row(ref, block, size):
    """Entries block * size .. of a [1, 1, S] reference, as a [1, size]
    row."""
    return ref[0, :, pl.ds(pl.multiple_of(block * size, size), size)]


def _mask(s, q0, k0, q_axis: int, window=None):
    """The scores of a tile with the pairs a query may not see (key after
    query; under a `window`, key `window` or more before it) at _NEG_INF.
    Queries q0.. run along `q_axis` of s, keys k0.. along the other."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    seen = q_pos >= k_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return jnp.where(seen, s, _NEG_INF)


def _key_blocks(qi, block_q, block_k, seq_len, causal, window):
    """(first, past the last) key block that query block qi loops over:
    all of them; under `causal` up to its diagonal; under a `window` from
    the block that holds the earliest key its first query still sees."""
    if not causal:
        return 0, seq_len // block_k
    last = jax.lax.div(qi * block_q + block_q + block_k - 1, block_k)
    if window is None:
        return 0, last
    return jax.lax.div(
        jnp.maximum(qi * block_q - (window - 1), 0), block_k), last


def _query_blocks(ki, block_q, block_k, seq_len, causal, window):
    """(first, past the last) query block that key block ki loops over:
    under `causal` from its diagonal; under a `window` to the block that
    holds the latest query that still sees its last key."""
    if not causal:
        return 0, seq_len // block_q
    first = jax.lax.div(ki * block_k, block_q)
    if window is None:
        return first, seq_len // block_q
    return first, jax.lax.div(
        jnp.minimum(ki * block_k + block_k + window - 2, seq_len - 1),
        block_q) + 1


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k,
                causal, scale, window):
    """One (batch*head, q-block) program. q_ref: [1, block_q, D];
    k_ref: [1, S, D]; v_ref: [1, S, Dv]; o_ref: [1, block_q, Dv];
    lse_ref: [1, 1, S]
    (full row — Mosaic block shapes must tile (8, 128) or span the array;
    each program stores its own [block_q] slice).

    A tile's scores are computed transposed, [block_k, block_q]: keys down
    the sublanes, queries across the lanes. A query's running maximum and
    denominator are then entries of [1, block_q] rows, the reductions over
    keys are adds and maxima of whole registers (no reduction across
    lanes), and the logsumexp is written as the row it is stored as. The
    accumulator is held transposed too, [Dv, block_q], and turned once, as
    the program ends."""
    qi = pl.program_id(1)
    seq_len = k_ref.shape[1]
    # scaled once a program, in q's own type: [block_q, D]
    q = (q_ref[0].astype(jnp.float32) * _scale(q_ref.shape[2], scale)).astype(
        q_ref.dtype)

    def tile(kb, carry):
        m, l, acc = carry  # [1, block_q] twice, [Dv, block_q]; float32
        s = _dot(_rows(k_ref, kb, block_k), q, _NT)  # [block_k, block_q]
        if causal:
            s = _mask(s, qi * block_q, kb * block_k, 1, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        v_blk = _rows(v_ref, kb, block_k)
        acc_new = acc * alpha + _dot(v_blk, p.astype(v_blk.dtype), _TN)
        return m_new, l_new, acc_new

    # Key blocks past this query block's diagonal (and, under a window,
    # before the first it still sees) are fully masked — skip them (dynamic
    # trip count lowers to a while loop). A row that sees no key of the
    # first tiles visited carries a maximum of _NEG_INF and sums of masked
    # keys through them; its first seen key multiplies both by exp(-1e30).
    kb_first, kb_end = _key_blocks(
        qi, block_q, block_k, seq_len, causal, window)
    m, l, acc = jax.lax.fori_loop(kb_first, kb_end, tile, (
        jnp.full((1, block_q), _NEG_INF, jnp.float32),
        jnp.zeros((1, block_q), jnp.float32),
        jnp.zeros((v_ref.shape[2], block_q), jnp.float32)))
    # Causal rows always see >= 1 key, but guard anyway (e.g. padding use).
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).T.astype(o_ref.dtype)
    lse_ref[0, :, pl.ds(qi * block_q, block_q)] = m + jnp.log(l_safe)


def _kv_row(group: int):
    """The index map of a whole key or value head beside query head b of
    the grid: row b // group of [B*Hkv, S, D] (b where the heads are as
    many). The programs of a group follow one another, so the row is
    fetched once a group and never written out a query head each."""
    if group == 1:
        return lambda b, i: (b, 0, 0)
    return lambda b, i: (b // group, 0, 0)


def _name(kernel: str, window) -> str:
    """A program's name, and so its op's name and its scope in a capture:
    `flash_attention_<kernel>`, `flash_attention_window_<kernel>` under a
    window (a reader of the plain kernels does not match the windowed)."""
    return f"flash_attention_{'' if window is None else 'window_'}{kernel}"


def _flash_forward(q, k, v, causal, block_q, block_k, scale=None,
                   window=None):
    """q [B*H, S, D], k [B*Hkv, S, D], v [B*Hkv, S, Dv] -> (out
    [B*H, S, Dv], lse [B*H, 1, S] f32)."""
    bh, s, d = q.shape
    dv = v.shape[2]
    kv_row = _kv_row(bh // k.shape[0])
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    kernel = functools.partial(
        _fwd_kernel, block_q=bq, block_k=bk, causal=causal, scale=scale,
        window=window)
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
        name=_name("fwd", window),
    )(q, k, v)


# --------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               block_q, block_k, causal, scale, window):
    """dQ for one (batch*head, q-block): loop over visible key blocks.
    Scores as [block_q, block_k] here: nothing is reduced over a tile, and
    dS·K is then a plain product."""
    qi = pl.program_id(1)
    seq_len = k_ref.shape[1]
    head_dim = q_ref.shape[2]
    scale = _scale(head_dim, scale)

    qs = q_ref[0].astype(jnp.float32) * scale      # pre-scaled Q block
    do = do_ref[0].astype(jnp.float32)             # [block_q, Dv]
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]    # [block_q]
    delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = _dot(qs, k_blk, _NT)                    # [block_q, block_k]
        if causal:
            s = _mask(s, qi * block_q, kb * block_k, 0, window)
        p = jnp.exp(s - lse[:, None])
        ds = p * (_dot(do, v_blk, _NT) - delta[:, None])
        return dq + _dot(ds, k_blk, _NN)

    kb_first, kb_end = _key_blocks(
        qi, block_q, block_k, seq_len, causal, window)
    dq0 = jnp.zeros((block_q, head_dim), jnp.float32)
    dq = jax.lax.fori_loop(kb_first, kb_end, body, dq0)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *sums, block_q, block_k, causal, scale,
                window):
    """dK and dV for one (batch*head, k-block): loop over query blocks at
    or below this key block's diagonal. Scores transposed as in the
    forward, [block_k, block_q]: lse and delta are read as the rows they
    are stored as and go down the sublanes for nothing, and pᵀ·dO and dsᵀ·Q
    are plain products. Under grouped heads the grid has a third, innermost
    axis over the query heads of the key/value head's group: each program
    adds its query head's part into `sums` (dk and dv in float32, in fast
    memory), the group's first clears them and its last writes them out."""
    ki = pl.program_id(1)
    seq_len = q_ref.shape[1]
    head_dim = q_ref.shape[2]
    scale = _scale(head_dim, scale)
    # K scaled, not Q: it is this program's one block; [block_k, D]
    ks = (k_ref[0].astype(jnp.float32) * scale).astype(k_ref.dtype)
    v_blk = v_ref[0]                                # [block_k, Dv]

    def tile(qb, carry):
        dk, dv = carry
        q_blk = _rows(q_ref, qb, block_q)
        do = _rows(do_ref, qb, block_q)
        s = _dot(ks, q_blk, _NT)                    # [block_k, block_q]
        if causal:
            s = _mask(s, qb * block_q, ki * block_k, 1, window)
        p = jnp.exp(s - _row(lse_ref, qb, block_q))
        dv_new = dv + _dot(p.astype(do.dtype), do, _NN)   # [block_k, Dv]
        ds = p * (_dot(v_blk, do, _NT) - _row(delta_ref, qb, block_q))
        dk_new = dk + _dot(ds.astype(q_blk.dtype), q_blk, _NN)
        return dk_new, dv_new

    # From the first query block whose rows can see this key block (and,
    # under a window, to the last).
    qb_first, qb_end = _query_blocks(
        ki, block_q, block_k, seq_len, causal, window)
    dk, dv = jax.lax.fori_loop(qb_first, qb_end, tile, (
        jnp.zeros((block_k, head_dim), jnp.float32),
        jnp.zeros((block_k, v_ref.shape[2]), jnp.float32)))
    dk = dk * scale  # the scale of Q in dsᵀ·(scale Q), taken out of the sum
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
                    scale=None, window=None):
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
            _dq_kernel, block_q=bq, block_k=bk, causal=causal, scale=scale,
            window=window),
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
        name=_name("bwd_dq", window),
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
            _dkv_kernel, block_q=bq, block_k=bk, causal=causal, scale=scale,
            window=window),
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
        name=_name("bwd_dkv", window),
    )(q, k, v, g, lse, delta)
    return dq, dk, dv_


# -------------------------------------------------------------- public op


def _to_bh(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, block_q=512, block_k=512,
                    scale=None, window=None):
    """Flash attention; q: [B, S, H, D], k: [B, S, Hkv, D], v:
    [B, S, Hkv, Dv] -> [B, S, H, Dv]. The key/value heads may be fewer than
    the query heads (grouped-query attention): query head j reads key/value
    head j // (H / Hkv), k and v are never written out a query head each,
    and dk and dv come back at Hkv heads, summed over each group inside the
    dkv kernel. Keys may be wider than values (latent attention expands
    to keys of 192 and values of 128): the score products run over D, the
    P.V and dV products over Dv, nothing is padded. `scale` is the softmax
    scale, D ** -0.5 where None. `window` W (static, causal only): query i
    sees key j where 0 <= i - j < W; None: every key at or before it, and
    the three programs are the plain causal ones, names and all.

    Forward and backward both run as Pallas kernels; only O(S) residuals
    (q, k, v, out, lse) are saved.

    Default 512x512 blocks: larger blocks halve each program's full-K/V
    re-reads, and every other pair of 256 / 512 / 1024 timed slower or
    within 1.5 % (PR 45). What the three kernels reach of their roofline at
    512x512 on a TPU v5e (useful causal work over traced time against the
    bfloat16 peak, `perfbench/kernel_costs.py`; one capture a cell, PR 45's
    chip runs, PERF.md section 6; PR 43's reading of the parent in
    brackets): forward 42.9 % [31.5] at sequence 2048 and 49.4-49.9 %
    [39.1-40.4] at 4096, dq 66.0 [64.4] and 74.5-74.9 [72.8-73.1], dkv
    62.8 [53.9] and 69.3-72.1 [59.2-61.2] at heads of 128; forward 53.4
    [44.0], dq 59.3 [59.2], dkv 58.8 [56.3] at keys of 192 and values of
    128. At blocks of 512 a head visits 10 tiles for 8 tiles' worth of
    causal work at sequence 2048 (36 for 32 at 4096), so no kernel can read
    above 80 % (89 %). The backward kernels keep the MXU busy for about
    four fifths of a visited tile, the forward for a little over half.
    """
    return _vjp_fwd(q, k, v, causal, block_q, block_k, scale, window)[0]


def _vjp_fwd(q, k, v, causal, block_q, block_k, scale, window):
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window {window}: a whole number of keys, 1 or more, that end "
            "at the query's own position (causal)")
    b, _, h, _ = q.shape
    out, lse = _flash_forward(
        _to_bh(q), _to_bh(k), _to_bh(v), causal, block_q, block_k, scale,
        window)
    return _from_bh(out, b, h), (q, k, v, out, lse)


def _vjp_bwd(causal, block_q, block_k, scale, window, res, g):
    q, k, v, out_bh, lse = res
    b, _, h, _ = q.shape
    kv = k.shape[2]
    dq, dk, dv = _flash_backward(
        _to_bh(q), _to_bh(k), _to_bh(v), out_bh, lse, _to_bh(g),
        causal, block_q, block_k, scale, window)
    return _from_bh(dq, b, h), _from_bh(dk, b, kv), _from_bh(dv, b, kv)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
