"""A chunk's two products under Kimi Delta Attention's channel-wise decay, as
two Pallas TPU kernels: `kda_pairs_fwd` and its own backward pass,
`kda_pairs_bwd` (`jax.custom_vjp`).

What is computed is `dynolog_tpu/models/linear_attention.py`
`_plain_pairs`, which stays as the plain reference: q, k [M, C, d_k] in the
model's type and gamma [M, C, d_k] float32 (the running sum of the decay
inside a chunk, which only falls) -> [M, 2, C, C] float32,

    A_ij = sum_d k_id k_jd exp(gamma_id - gamma_jd)     (B_ij: q_i for k_i)

for i >= j, zeros above the diagonal, by sub-blocks of SUB rows. The
mathematics and the precision are the plain body's: rows of sub-block I
against an EARLIER sub-block are factored about I's first row r, exp(gamma_i
- gamma_r) and exp(gamma_r - gamma_j), both exponents at or below 0, the two
factors rounded to the model's type and multiplied on the MXU with float32
accumulation; the SUB x SUB pairs of a DIAGONAL sub-block are written out
pair by pair in float32, no row between them, nothing clamped. What differs
is where the intermediates live: a pair's [SUB, SUB, d_k] never leaves fast
memory, where the plain body writes and reads 1.07 GB of them a layer a pass
at Kimi-Linear's widths. ONE ROUNDING IS WRITTEN OUT that the plain body
leaves to the unit: the backward kernel rounds the cotangent of the
off-diagonal sub-blocks to the model's type before its two products
(`_bwd_kernel`, `ct.astype(dtype)`), where autodiff hands the plain body's
products a float32 cotangent beside an operand of the model's type and the
chip's one-pass product rounds it the same way (PERF.md section 6, PR 53:
dq and dk agree to one place of bfloat16 on the chip, dgamma to 7.7e-4 of
0.38). Float32 operands are not rounded at all, and their products take the
precision the caller traces under (`_dot`), as the plain body's do.

HOW A PROGRAM LAYS A CHUNK OUT. A program holds HEADS chunk-heads. The
off-diagonal sub-blocks are computed a chunk-head at a time as they are
written above, rows down the sublanes, d_k across the lanes. The diagonal
pairs are computed TURNED, d_k down the sublanes and the rows of TWO
chunk-heads across the 128 lanes, a shift at a time: for s in 0..SUB-1 lane
i is paired with the row s before it (`pltpu.roll` by s along the lanes),
so a pair's sum over d_k is additions of whole registers, and lane i holds
A[i, i - s] where i - s is still in i's sub-block. The SUB rows of shifts
are then turned back ([shift, row] -> [row, shift]) and every row rolled by
its own position (`pltpu.roll` with a stride), which puts shift s of row i
in column i - s. The backward pass walks the same shifts: the cotangents of
the pairs come in by selects over the turned block (the inverse roll has a
negative stride, which the unit has not), and what a pair gives the EARLIER
row (k_j's and -gamma_j's share) is rolled back by s where it is made. Both
kernels are bound by those rolls (a register in 8 cycles on each of three
units), not by memory or arithmetic: PERF.md, Open 30.

THE GRADIENT OF gamma is written by hand: a pair's decay exp(gamma_i -
gamma_j) gives +x to row i and -x to row j, x the pair's share of the
cotangent; off the diagonal the first row r gets nothing, as the plain
body's `stop_gradient` says (what r would get cancels: the pair's decay
does not depend on it).

The kernels carry no interpret switch (as `flash_attention`): on a TPU
Mosaic compiles them, anywhere else the call fails, and the CPU tests run
them under `pltpu.force_tpu_interpret_mode()`, chosen in the test.

WHAT THE CHIP'S COMPILER HAS TAKEN (`compiles`): two chunks of 64 rows fill
the 128 lanes, and the turned layout ([128, d_k] float32 transposed, d_k / 8
registers a shift) is taken at the head widths WIDTHS: each is compiled by
Mosaic for a described v5e in tests/test_deepseek_v2.py and was run on the
chip against the plain body (PERF.md section 6, PR 53). A
program asks before it takes the kernels and keeps the plain body at any
other size: the chip has compiled that at every width. A kernel is opaque
to the SPMD partitioner: under a mesh the caller wraps `kda_pairs` in
`jax.shard_map` (a chunk-head needs nothing of another).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HEADS = 8  # chunk-heads a program: 4 pairs of two 64-row chunks
SUB = 16  # a sub-block of a chunk: linear_attention.SUB, the plain body's
WIDTHS = (64, 128, 256)  # the d_k Mosaic has compiled the layout at

_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_NN = (((1,), (0,)), ((), ()))  # a · b
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b


def _dot(a, b, dims):
    # float32 operands: the precision the caller traces under, as the plain
    # body's products. bfloat16 operands: one pass of the unit, which is
    # exact for them, stated because Mosaic refuses them under a caller's
    # `jax.default_matmul_precision("highest")`
    precision = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jax.lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=jnp.float32)


def _turned(ref, pair):
    """Two chunk-heads of a block [HEADS, C, d_k] -> [d_k, 2 C] float32."""
    two = ref[pl.ds(2 * pair, 2)].astype(jnp.float32)
    return two.reshape(LANES, two.shape[-1]).T


def _block_lower(chunk: int):
    """[C, C] bool: i >= j and both in one sub-block."""
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return (i >= j) & (i // SUB == j // SUB)


def _factors(k32, q32, g, i: int, dtype):
    """Sub-block i of one chunk-head against the earlier ones: the rows'
    factor [SUB, d_k] float32, k's and q's rows under it [2 SUB, d_k] in the
    model's type, the columns' factor [C, d_k] float32 (zeros at and past
    i's first row) and k's columns under it in the model's type."""
    rows = slice(i * SUB, (i + 1) * SUB)
    first = g[i * SUB:i * SUB + 1]
    fall = jnp.exp(g[rows] - first)
    earlier = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) < i * SUB
    rise = jnp.exp(jnp.where(earlier, first - g, -jnp.inf))
    lhs = jnp.concatenate([k32[rows] * fall, q32[rows] * fall], 0)
    return fall, lhs.astype(dtype), rise, (k32 * rise).astype(dtype)


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, g_ref, out_ref, kt_ref, qt_ref, gt_ref, d_ref):
    heads, chunk, dk = k_ref.shape
    f32, dtype = jnp.float32, k_ref.dtype
    within = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) % SUB

    def diagonal(pair, carry):
        kt_ref[...] = _turned(k_ref, pair)
        qt_ref[...] = _turned(q_ref, pair)
        gt_ref[...] = _turned(g_ref, pair)
        for s in range(SUB):
            sum_a = sum_b = jnp.zeros((8, LANES), f32)
            for v in range(0, dk, 8):
                k_v, g_v = kt_ref[v:v + 8], gt_ref[v:v + 8]
                k_s = pltpu.roll(k_v, s, 1) if s else k_v
                g_s = pltpu.roll(g_v, s, 1) if s else g_v
                # a lane whose pair is in another sub-block holds anything
                # (an overflow too); it never leaves its lane, and is
                # dropped below
                term = k_s * jnp.exp(g_v - g_s)
                sum_a = sum_a + k_v * term
                sum_b = sum_b + qt_ref[v:v + 8] * term
            keep = within >= s
            for m, total in enumerate((sum_a, sum_b)):
                row = LANES // 2 * m + SUB - 1 - s
                d_ref[row:row + 1] = jnp.where(
                    keep, jnp.sum(total, 0, keepdims=True), 0.0)
        # [shift, row] -> [row, shift], then shift s of row i into column
        # i - s: a roll by the row's own position
        back = d_ref[...].T
        lower = _block_lower(chunk)
        for c in range(2):
            for m in range(2):
                own = pltpu.roll(
                    back[c * chunk:(c + 1) * chunk],
                    LANES - (SUB - 1) - LANES // 2 * m, 1,
                    stride=1, stride_axis=0)[:, :chunk]
                out_ref[2 * pair + c, m] = jnp.where(lower, own, 0.0)
        return carry

    jax.lax.fori_loop(0, heads // 2, diagonal, 0)

    def earlier(c, carry):
        k32, q32 = k_ref[c].astype(f32), q_ref[c].astype(f32)
        g = g_ref[c]
        for i in range(1, chunk // SUB):
            _, lhs, _, cols = _factors(k32, q32, g, i, dtype)
            off = _dot(lhs, cols, _NT)  # [2 SUB, C]
            rows = slice(i * SUB, (i + 1) * SUB)
            out_ref[c, 0, rows] = out_ref[c, 0, rows] + off[:SUB]
            out_ref[c, 1, rows] = out_ref[c, 1, rows] + off[SUB:]
        return carry

    jax.lax.fori_loop(0, heads, earlier, 0)


def _specs(chunk: int, dk: int):
    """A program's block of q, k, gamma (and their cotangents), and of the
    two matrices (and theirs)."""
    rows = pl.BlockSpec((HEADS, chunk, dk), lambda i: (i, 0, 0))
    both = pl.BlockSpec((HEADS, 2, chunk, chunk), lambda i: (i, 0, 0, 0))
    return rows, both


@jax.jit
def _forward(q, k, gamma):
    # jitted, as `_backward` is: a layer's calls (and a process's programs)
    # share one trace of the kernel, which is long (every shift unrolled)
    m, chunk, dk = k.shape
    rows, both = _specs(chunk, dk)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(m // HEADS,),
        in_specs=[rows, rows, rows],
        out_specs=both,
        out_shape=jax.ShapeDtypeStruct((m, 2, chunk, chunk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, LANES), jnp.float32)] * 3
        + [pltpu.VMEM((LANES, LANES), jnp.float32)],
        name="kda_pairs_fwd",
    )(q, k, gamma)


# --------------------------------------------------------------- backward


def _bwd_kernel(q_ref, k_ref, g_ref, ct_ref, dq_ref, dk_ref, dg_ref,
                kt_ref, qt_ref, gt_ref, d_ref, dkt_ref, dqt_ref, dgt_ref,
                sum_k_ref, sum_q_ref):
    heads, chunk, dk = k_ref.shape
    f32, dtype = jnp.float32, k_ref.dtype
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    within, block = lane % SUB, lane % chunk // SUB
    wide = 8  # channels a trip: a register
    keep = jax.lax.broadcasted_iota(jnp.int32, (wide, LANES), 1) % SUB

    def diagonal(pair, carry):
        kt_ref[...] = _turned(k_ref, pair)
        qt_ref[...] = _turned(q_ref, pair)
        gt_ref[...] = _turned(g_ref, pair)
        # the pairs' cotangents, [row, column] -> [shift, row]. Turned, a
        # lane holds its row's 64 columns down the sublanes; the SUB of them
        # that are in the row's own sub-block are picked, and of those the
        # one s before the row, for every s (a lane that has none s before
        # it in its sub-block picks nothing: a zero). The roll that would
        # undo the forward's has a negative stride, which the unit has not.
        turned = jnp.concatenate(
            [jnp.concatenate([ct_ref[2 * pair + c, m] for m in range(2)], 1)
             for c in range(2)], 0).T  # [(A | B, column), (chunk, row)]
        column = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0)
        for m in range(2):
            own = jnp.zeros((SUB, LANES), f32)
            for i in range(chunk // SUB):
                at = m * chunk + i * SUB
                own = jnp.where(block == i, turned[at:at + SUB], own)
            for s in range(SUB):
                d_ref[m * SUB + s:m * SUB + s + 1] = jnp.sum(
                    jnp.where(column == within - s, own, 0.0), 0,
                    keepdims=True)

        def channels(v, carry):  # `wide` of the d_k channels, every shift
            at = pl.ds(pl.multiple_of(wide * v, wide), wide)
            k_v, q_v, g_v = kt_ref[at], qt_ref[at], gt_ref[at]
            d_k = d_q = d_g = jnp.zeros((wide, LANES), f32)
            for s in range(SUB):
                ct_a, ct_b = d_ref[s:s + 1], d_ref[SUB + s:SUB + s + 1]
                k_s = pltpu.roll(k_v, s, 1) if s else k_v
                g_s = pltpu.roll(g_v, s, 1) if s else g_v
                # no clamp: a lane whose pair is in another sub-block is
                # taken out, as the plain body takes it out
                decay = jnp.exp(jnp.where(keep >= s, g_v - g_s, -jnp.inf))
                term = k_s * decay
                d_k = d_k + ct_a * term
                d_q = d_q + ct_b * term
                # what the pair gives the EARLIER row is made in the later
                # row's lane and rolled back by s
                given = (ct_a * k_v + ct_b * q_v) * decay
                back = pltpu.roll(given, LANES - s, 1) if s else given
                d_k = d_k + back
                d_g = d_g + given * k_s - back * k_v
            dkt_ref[at], dqt_ref[at], dgt_ref[at] = d_k, d_q, d_g
            return carry

        jax.lax.fori_loop(0, dk // wide, channels, 0)
        two = pl.ds(2 * pair, 2)
        sum_k_ref[two] = dkt_ref[...].T.reshape(2, chunk, dk)
        sum_q_ref[two] = dqt_ref[...].T.reshape(2, chunk, dk)
        dg_ref[two] = dgt_ref[...].T.reshape(2, chunk, dk)
        return carry

    jax.lax.fori_loop(0, heads // 2, diagonal, 0)

    def earlier(c, carry):
        k32, q32 = k_ref[c].astype(f32), q_ref[c].astype(f32)
        g = g_ref[c]
        for i in range(1, chunk // SUB):
            fall, lhs, rise, cols = _factors(k32, q32, g, i, dtype)
            rows = slice(i * SUB, (i + 1) * SUB)
            ct = jnp.concatenate(
                [ct_ref[c, 0, rows], ct_ref[c, 1, rows]], 0).astype(dtype)
            d_lhs = _dot(ct, cols, _NN)  # [2 SUB, d_k]
            d_rows_k, d_rows_q = d_lhs[:SUB] * fall, d_lhs[SUB:] * fall
            d_cols = _dot(ct, lhs, _TN) * rise  # [C, d_k]
            sum_k_ref[c, rows] = sum_k_ref[c, rows] + d_rows_k
            sum_q_ref[c, rows] = sum_q_ref[c, rows] + d_rows_q
            # +x to the later row, -x to the earlier; the first row gets
            # nothing of its own
            dg_ref[c, rows] = (dg_ref[c, rows] + d_rows_k * k32[rows]
                               + d_rows_q * q32[rows])
            sum_k_ref[c] = sum_k_ref[c] + d_cols
            dg_ref[c] = dg_ref[c] - d_cols * k32
        dk_ref[c] = sum_k_ref[c].astype(dtype)
        dq_ref[c] = sum_q_ref[c].astype(dtype)
        return carry

    jax.lax.fori_loop(0, heads, earlier, 0)


@jax.jit
def _backward(q, k, gamma, ct):
    m, chunk, dk = k.shape
    rows, both = _specs(chunk, dk)
    f32 = jnp.float32
    return pl.pallas_call(
        _bwd_kernel,
        grid=(m // HEADS,),
        in_specs=[rows, rows, rows, both],
        out_specs=[rows, rows, rows],
        out_shape=[jax.ShapeDtypeStruct((m, chunk, dk), q.dtype),
                   jax.ShapeDtypeStruct((m, chunk, dk), k.dtype),
                   jax.ShapeDtypeStruct((m, chunk, dk), f32)],
        scratch_shapes=[pltpu.VMEM((dk, LANES), f32)] * 3
        + [pltpu.VMEM((2 * SUB, LANES), f32)]
        + [pltpu.VMEM((dk, LANES), f32)] * 3
        + [pltpu.VMEM((HEADS, chunk, dk), f32)] * 2,
        name="kda_pairs_bwd",
    )(q, k, gamma, ct)


# ------------------------------------------------------------- the two as one


def _flat(x, trailing: int):
    """[..., (trailing dims)] -> [M, ...], M padded to whole programs."""
    x = x.reshape(-1, *x.shape[-trailing:])
    short = -x.shape[0] % HEADS
    return jnp.pad(x, [(0, short)] + [(0, 0)] * trailing) if short else x


def compiles(chunk: int, dk: int) -> bool:
    """Whether the chip's compiler has taken the kernels at a chunk of
    `chunk` rows of `dk` channels (the module's docstring)."""
    return 2 * chunk == LANES and dk in WIDTHS


@jax.custom_vjp
def kda_pairs(q, k, gamma):
    """q, k [..., C, d_k] in the model's type, gamma [..., C, d_k] float32
    -> [..., 2, C, C] float32: A and B under the decay, zeros above the
    diagonal. Two chunks fill a register's 128 lanes, C is 64, and a trip
    takes a register's 8 channels."""
    *lead, chunk, dk = k.shape
    if 2 * chunk != LANES or dk % 8:
        raise ValueError(
            f"kda_pairs lays two chunks of 64 rows across 128 lanes, eight "
            f"channels a register: got {chunk} rows of {dk} channels")
    out = _forward(_flat(q, 2), _flat(k, 2), _flat(gamma, 2))
    return out[:math.prod(lead)].reshape(*lead, 2, chunk, chunk)


def _vjp_fwd(q, k, gamma):
    return kda_pairs(q, k, gamma), (q, k, gamma)


def _vjp_bwd(res, ct):
    grads = _backward(*(_flat(x, 2) for x in res), _flat(ct, 3))
    m = math.prod(ct.shape[:-3])
    return tuple(d[:m].reshape(x.shape) for d, x in zip(grads, res))


kda_pairs.defvjp(_vjp_fwd, _vjp_bwd)
