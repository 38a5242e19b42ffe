"""Numerics tests for the TPU compute kernels (flash + ring attention).

Run on the virtual 8-device CPU mesh (conftest). The Pallas kernels have no
interpret switch of their own (on a TPU Mosaic compiles them, elsewhere the
call fails), so every flash test asks for interpret mode itself through the
`pallas_interpret` fixture; ring attention runs over a real shard_map ring
with ppermute.
"""

import zlib

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from conftest import slow_lane
from dynolog_tpu.models.train import make_batch, make_train_state, make_train_step
from dynolog_tpu.models.transformer import TransformerConfig, forward, init_params
from dynolog_tpu.ops.flash_attention import flash_attention, reference_attention
from dynolog_tpu.parallel.ring_attention import ring_attention
from dynolog_tpu.parallel.sharding import MeshSpec, batch_sharding, make_mesh


@pytest.fixture
def pallas_interpret():
    with pltpu.force_tpu_interpret_mode():
        yield


def _qkv(rng, b=2, s=64, h=4, d=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (b, s, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


def test_flash_matches_reference_causal(pallas_interpret):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = flash_attention(q, k, v, True, 32, 16)
    ref = reference_attention(q, k, v, causal=True)
    assert jnp.allclose(out, ref, atol=1e-5), float(jnp.abs(out - ref).max())


def test_flash_matches_reference_noncausal(pallas_interpret):
    q, k, v = _qkv(jax.random.PRNGKey(1), s=48)
    out = flash_attention(q, k, v, False, 16, 16)
    ref = reference_attention(q, k, v, causal=False)
    assert jnp.allclose(out, ref, atol=1e-5)


def test_flash_odd_block_sizes(pallas_interpret):
    """Requested blocks that don't divide S fall back to valid divisors."""
    q, k, v = _qkv(jax.random.PRNGKey(2), s=40)
    out = flash_attention(q, k, v, True, 256, 256)
    ref = reference_attention(q, k, v, causal=True)
    assert jnp.allclose(out, ref, atol=1e-5)


def test_flash_grad_matches_reference(pallas_interpret):
    q, k, v = _qkv(jax.random.PRNGKey(3), s=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 16, 16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        assert jnp.allclose(a, b, atol=1e-4), float(jnp.abs(a - b).max())


def test_flash_without_interpret_fails_off_tpu():
    """No backend-name test picks interpret mode behind the caller's back:
    off a TPU the compiled path is the only path, and it refuses."""
    q, k, v = _qkv(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="interpret"):
        flash_attention(q, k, v, True, 32, 16)


def test_flash_bf16(pallas_interpret):
    q, k, v = _qkv(jax.random.PRNGKey(4), dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, True, 32, 32)
    ref = reference_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    assert jnp.allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=3e-2
    )


def _out_and_grads(attn, q, k, v, g):
    """(out, dq, dk, dv) of attn at q, k, v under the cotangent g."""
    out, vjp = jax.vjp(attn, q, k, v)
    return (out, *vjp(g))


_RESULTS = ("out", "dq", "dk", "dv")
_cases: dict = {}


def _case(key, heads, kv_heads, d, dv, seq, blocks, causal, dtype):
    """The kernels' and the reference's (out, dq, dk, dv) on one seeded
    draw, both in float32 for the comparison: computed once a case, under
    the caller's interpret mode, and shared by the four results' tests. The
    reference runs in float32 on the same (rounded) inputs, so what is
    compared is the kernels' own rounding."""
    if key not in _cases:
        keys = jax.random.split(
            jax.random.PRNGKey(zlib.crc32(repr(key).encode())), 4)
        q, k, v, g = (
            jax.random.normal(rng, (2, seq, h, w), dtype)
            for rng, h, w in zip(
                keys, (heads, kv_heads, kv_heads, heads), (d, d, dv, dv)))
        got = _out_and_grads(
            lambda q, k, v: flash_attention(q, k, v, causal, *blocks),
            q, k, v, g)
        assert [x.dtype for x in got] == [dtype] * 4
        assert [x.shape for x in got] == [g.shape, q.shape, k.shape, v.shape]
        f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
        want = _out_and_grads(
            lambda q, k, v: reference_attention(q, k, v, causal=causal),
            *f32)
        _cases[key] = dict(zip(_RESULTS, zip(
            (x.astype(jnp.float32) for x in got), want)))
    return _cases[key]


# (query heads, key/value heads, width of q and k, width of v, blocks,
# causal) at S 128, four query blocks or more: the first program (only a
# diagonal tile) and the last run, tiles wholly below the diagonal and
# tiles it crosses, and a tile that it leaves wholly dark for some rows.
_BF16_CASES = {
    "heads-128": (2, 2, 128, 128, (32, 32), True),
    "keys-192-values-128": (2, 2, 192, 128, (32, 16), True),
    "4-heads-on-1": (4, 1, 128, 128, (16, 32), True),
    "not-causal": (2, 2, 128, 128, (32, 64), False),
}


@pytest.mark.parametrize("result", _RESULTS)
@pytest.mark.parametrize("case", _BF16_CASES)
def test_flash_bf16_out_and_grads(pallas_interpret, case, result):
    """bfloat16 in, bfloat16 out: the MXU takes every operand of a product
    as bfloat16, p and ds among them, so a result is off by roundings of
    2^-9 that mostly average out over a row's keys, and by its own
    rounding on the way out: held to 2^-6 of
    the result's largest magnitude (measured 0.002 to 0.006 on these draws;
    a mask, a block or a head off by one reads 0.3 or more)."""
    heads, kv_heads, d, dv, blocks, causal = _BF16_CASES[case]
    got, want = _case(case, heads, kv_heads, d, dv, 128, blocks, causal,
                      jnp.bfloat16)[result]
    worst = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert worst < 2 ** -6, worst


# query blocks larger than key blocks and the other way round, equal, and
# no power of two, causal and not, in float32: nothing is rounded, so the
# old tolerances hold for every result
_F32_BLOCKS = [(32, 16), (16, 32), (16, 16), (48, 32)]


@pytest.mark.parametrize("result", _RESULTS)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", _F32_BLOCKS, ids=lambda b: f"{b[0]}x{b[1]}")
def test_flash_f32_blocks(pallas_interpret, blocks, causal, result):
    got, want = _case(("f32", blocks, causal), 2, 2, 16, 16, 96, blocks,
                      causal, jnp.float32)[result]
    atol = 1e-5 if result == "out" else 1e-4
    assert jnp.allclose(got, want, atol=atol), float(jnp.abs(got - want).max())


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_ring_attention_matches_full():
    mesh = make_mesh(MeshSpec(data=2, seq=4, model=1))
    q, k, v = _qkv(jax.random.PRNGKey(5), b=2, s=64)
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    assert jnp.allclose(out, ref, atol=1e-5), float(jnp.abs(out - ref).max())


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@slow_lane
def test_ring_attention_grads():
    """Ring attention must be differentiable (scan+ppermute VJP).

    Slow lane (~42s compile): the default lane's
    test_sharded_ring_train_step_matches_single_device still runs a
    ring-attention backward, but on a seq=2 mesh — the full 8-hop
    ppermute VJP (where rotation-index bugs that cancel at ring size 2
    would surface) runs here, in CI's slow job and the dev slow lane."""
    mesh = make_mesh(MeshSpec(data=1, seq=8, model=1))
    q, k, v = _qkv(jax.random.PRNGKey(6), b=1, s=64)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        assert jnp.allclose(a, b, atol=1e-4), float(jnp.abs(a - b).max())


def test_forward_flash_impl_matches_reference(pallas_interpret):
    cfg_ref = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64
    )
    cfg_flash = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        attn_impl="flash",
    )
    params = init_params(jax.random.PRNGKey(0), cfg_ref)
    tokens = make_batch(jax.random.PRNGKey(1), cfg_ref, 2, 32)
    ref = forward(params, tokens, cfg_ref)
    out = forward(params, tokens, cfg_flash)
    # bf16 model: the kernel keeps softmax·V accumulation in f32 while the
    # reference rounds probs to bf16 first — tolerance is bf16-resolution
    # differences compounded over n_layers.
    assert jnp.allclose(out, ref, atol=0.2), float(jnp.abs(out - ref).max())


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_ring_train_step_matches_single_device():
    """Full dp/sp/tp train step with ring attention == unsharded loss."""
    mesh = make_mesh(MeshSpec(data=2, seq=2, model=2))
    cfg_ring = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        attn_impl="ring",
    )
    cfg_ref = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64
    )
    batch = make_batch(jax.random.PRNGKey(1), cfg_ref, 4, 32)

    with mesh:
        params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg_ring, mesh)
        step = make_train_step(cfg_ring, mesh)
        sharded_batch = jax.device_put(batch, batch_sharding(mesh))
        _, _, ring_loss = step(params, opt_state, sharded_batch)

    ref_params, ref_opt = make_train_state(jax.random.PRNGKey(0), cfg_ref)
    ref_step = make_train_step(cfg_ref)
    _, _, ref_loss = ref_step(ref_params, ref_opt, batch)
    # Inits are exactly equal (threefry is partitionable, so a sharded
    # draw equals the unsharded one); the residual is ring attention's chunked
    # online-softmax accumulating softmax·V in a different order than the
    # dense reference on a bf16 model (~1e-3 observed, same class of noise
    # the flash/MoE equivalence tests above tolerate at 0.2/2e-2). 1e-2
    # still fails loudly on a real divergence: the pre-fix init bug sat at
    # 2.3e-2.
    assert abs(float(ring_loss) - float(ref_loss)) < 1e-2, (
        float(ring_loss), float(ref_loss))


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_flash_train_step_matches_single_device(pallas_interpret):
    """A Mosaic kernel cannot be partitioned by XLA (lowering refuses it
    under a mesh), so the model runs it per device under shard_map: batch
    rows over `data`, heads over `model`. Same loss as one device."""
    mesh = make_mesh(MeshSpec(data=2, model=2))
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        attn_impl="flash",
    )
    batch = make_batch(jax.random.PRNGKey(1), cfg, 4, 32)

    with mesh:
        params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg, mesh)
        step = make_train_step(cfg, mesh)
        sharded_batch = jax.device_put(batch, batch_sharding(mesh))
        _, _, sharded_loss = step(params, opt_state, sharded_batch)

    ref_params, ref_opt = make_train_state(jax.random.PRNGKey(0), cfg)
    _, _, ref_loss = make_train_step(cfg)(ref_params, ref_opt, batch)
    assert abs(float(sharded_loss) - float(ref_loss)) < 1e-2, (
        float(sharded_loss), float(ref_loss))
