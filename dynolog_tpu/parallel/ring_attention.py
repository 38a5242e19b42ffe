"""Ring attention: exact causal attention, sequence-parallel over a mesh axis.

Long-context sequence parallelism for the flagship workload: the sequence
dimension of Q/K/V is sharded over the mesh's `seq` axis; each device keeps
its local query block resident while key/value blocks rotate around the
ring with `jax.lax.ppermute` (one ICI hop per step). Blockwise online
softmax (the flash-attention m/l recurrence carried across ring steps)
makes the result exactly equal to full causal attention — no approximation
— while no device ever materializes more than S_local keys, and the
per-step ppermute overlaps with the local block matmul under XLA's async
collective scheduling.

This is the design the TPU build observes at scale (SURVEY §5.7: pod-wide
synchronized capture exists to align traces from exactly this kind of
sequence-parallel workload) — and the ICI traffic it generates is what the
tpumon collective-telemetry fields (ids 13-20) measure.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


_NEG_INF = -1e30


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool):
    """Per-shard body (inside shard_map). q,k,v: [B, S_local, H, D] local
    blocks; returns the local [B, S_local, H, D] attention output."""
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = jax.lax.rsqrt(jnp.float32(d))

    qf = q.astype(jnp.float32) * scale
    q_pos = my_idx * s_loc + jax.lax.iota(jnp.int32, s_loc)

    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    acc0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_update(k_cur, v_cur, src, m, l, acc):
        # Block scores against the K/V chunk currently resident here,
        # which originated on device `src`.
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32))
        if causal:
            k_pos = src * s_loc + jax.lax.iota(jnp.int32, s_loc)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32))
        return m_new, l_new, acc_new

    def step(carry, _):
        k_cur, v_cur, src, m, l, acc = carry
        m, l, acc = block_update(k_cur, v_cur, src, m, l, acc)
        # Rotate K/V one hop around the ring (device i -> i+1), so after
        # step t this device holds the chunk that originated at idx - t.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        src_nxt = jax.lax.rem(src - 1 + n, n)
        return (k_nxt, v_nxt, src_nxt, m, l, acc), None

    carry0 = (k, v, my_idx, m0, l0, acc0)
    # First n-1 steps rotate K/V after consuming them; the last chunk is
    # consumed without a rotate (its successor would be discarded — a
    # wasted ICI hop XLA cannot DCE out of the scan body).
    (k_l, v_l, src_l, m, l, acc), _ = jax.lax.scan(
        step, carry0, None, length=n - 1)
    m, l, acc = block_update(k_l, v_l, src_l, m, l, acc)
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (never for causal)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, *, seq_axis: str = "seq",
                   batch_axis: str = "data", causal: bool = True,
                   window=None):
    """Exact causal attention with the sequence dim sharded over
    `seq_axis`. q,k,v: global [B, S, H, D]; heads stay replicated over the
    mesh's model axis here (the projections around this op are the
    tensor-parallel part). A `window` (a sliding_attention layer's) is
    refused: every key block goes round the whole ring and every hop is
    summed, so a band would be computed as full attention."""
    if window is not None:
        raise ValueError(
            f"ring attention sees every key at or before a query: a "
            f"sliding_attention layer's window ({window}) is not run here")
    spec = P((batch_axis,), (seq_axis,), None, None)
    body = functools.partial(
        _ring_attention_local, axis_name=seq_axis, causal=causal)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)(q, k, v)
