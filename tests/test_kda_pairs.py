"""A chunk's two products under Kimi Delta Attention's channel-wise decay as
Pallas kernels (dynolog_tpu/ops/kda_pairs.py: `kda_pairs_fwd` and its own
backward pass `kda_pairs_bwd`) against the plain body that stays beside them
(dynolog_tpu/models/linear_attention.py `_plain_pairs`), and which of the
two a program holds: the kernels where it is compiled for a TPU at a width
the chip's compiler has taken them at, under a `shard_map` where the program
runs over a mesh, and the plain ops everywhere else.

CPU: the kernels run under `pltpu.force_tpu_interpret_mode()`, chosen here
where it can be seen; float32 under `highest` unless a case says otherwise.
The chip's compiler meets them at the published widths in
tests/test_deepseek_v2.py (one file a worker describes the topology in)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dynolog_tpu.models import linear_attention as la
from dynolog_tpu.ops import kda_pairs as kernels
from dynolog_tpu.ops.kda_pairs import HEADS, kda_pairs
from test_kimi_linear import recurrence, rule_inputs

STEPS = [0.1, 0.5, 2.0]  # test_the_strongest_decay_stays_finite_and_agrees'


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(lead, dk, dtype, step):
    """q, k unit rows, gamma the running sum inside a chunk of A 16 times a
    softplus of `step` to three times it (None: decays of 0.3 to 0.999 a
    token), and a cotangent for the two matrices."""
    keys = jax.random.split(jax.random.PRNGKey(17), 4)
    shape = (*lead, la.CHUNK, dk)

    def unit(key):
        x = jax.random.normal(key, shape)
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).astype(dtype)

    if step is None:
        g = jnp.log(jax.random.uniform(keys[2], shape, minval=0.3,
                                       maxval=0.999))
    else:
        g = -16.0 * jax.random.uniform(
            keys[2], shape, minval=step, maxval=3 * step)
    weight = jax.random.normal(keys[3], (*lead, 2, la.CHUNK, la.CHUNK))
    return unit(keys[0]), unit(keys[1]), jnp.cumsum(g, axis=-2), weight


def _run(pairs, q, k, gamma, weight):
    """(both, (dq, dk, dgamma)) of `pairs` under the cotangent `weight`."""
    both, back = jax.vjp(pairs, q, k, gamma)
    return both, back(weight)


def test_the_kernels_sub_block_is_the_plain_bodys():
    assert kernels.SUB == la.SUB and kernels.LANES == 2 * la.CHUNK


@pytest.mark.parametrize("step", [None] + STEPS)
@pytest.mark.parametrize("lead, dk, dtype", [
    ((2, 3), 8, "float32"),  # a toy: 6 chunk-heads, padded to a program's 8
    ((2,), 128, "float32"),  # the cell's chunk: 64 rows of 128 channels
    ((2,), 128, "bfloat16"),
], ids=["toy", "chunk-float32", "chunk-bfloat16"])
def test_the_kernels_equal_the_plain_body(lead, dk, dtype, step):
    """`both` and the three cotangents, at the decays of the strongest-decay
    test: at a step of 2 the running sum passes -88 inside a sub-block of
    sixteen, and a pair factored about any row overflows."""
    q, k, gamma, weight = _inputs(lead, dk, jnp.dtype(dtype), step)
    if step is not None:
        assert float(jnp.min(gamma)) < -100.0
    want, want_grads = _run(la._plain_pairs, q, k, gamma, weight)
    with pltpu.force_tpu_interpret_mode():
        got, got_grads = _run(kda_pairs, q, k, gamma, weight)
    assert got.shape == (*lead, 2, la.CHUNK, la.CHUNK)
    assert got.dtype == jnp.float32
    assert [g.dtype for g in got_grads] == [q.dtype, k.dtype, jnp.float32]
    for x in (got, *got_grads):
        assert bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
    # zeros above the diagonal, exactly
    assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # bfloat16: both sides round dq and dk to the model's type (one place in
    # 256 of entries up to 4), and the plain body rounds the cotangents of
    # its two factors once more on the way back
    tol = 1e-4 if dtype == "float32" else 4e-2
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=tol, atol=tol)
    if step is None:  # decays that leave the gradient of gamma something
        assert float(jnp.max(jnp.abs(want_grads[2]))) > 0.1


@pytest.mark.parametrize("step", STEPS)
def test_the_strongest_decay_passes_through_the_kernels(step, monkeypatch):
    """tests/test_kimi_linear.py's test of the same name, the rule whole
    against the recurrence taken token by token, with the kernels in the
    plain body's place (what a program compiled for a TPU holds)."""
    monkeypatch.setattr(la, "_plain_pairs", kda_pairs)
    args = rule_inputs(step=step)
    want, want_state = recurrence(*args)
    weight = jax.random.normal(jax.random.PRNGKey(8), args[2].shape)

    def scalar(rule):
        return lambda *a: jnp.sum(rule(*a)[0] * weight)

    want_grads = jax.jit(jax.grad(scalar(recurrence), range(5)))(*args)
    with pltpu.force_tpu_interpret_mode():
        out, state = jax.jit(la.chunked_kda_rule)(*args)
        grads = jax.jit(jax.grad(scalar(la.chunked_kda_rule), range(5)))(
            *args)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state, want_state, rtol=1e-5, atol=1e-5)
    for g, w in zip(grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_a_program_holds_the_kernels_only_where_it_is_compiled_for_a_tpu():
    """The rule's gradient under its checkpoint, lowered from this CPU host:
    for the CPU no Pallas call at all (every CPU test runs the plain ops),
    for a TPU both kernels by name. (Which branch is taken is a constant in
    either lowering; that the chip's compiler then keeps no conditional is
    held where it compiles the rule, tests/test_deepseek_v2.py.)"""
    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32)

    def loss(q, k, v, g, beta):
        return jnp.sum(jax.checkpoint(la.chunked_kda_rule)(
            q, k, v, g, beta)[0])

    wide = shape(1, 128, 2, 128)
    traced = jax.jit(jax.grad(loss, range(5))).trace(
        wide, wide, wide, wide, shape(1, 128, 2))
    here = traced.lower().as_text()
    assert "tpu_custom_call" not in here and "kda_pairs" not in here
    there = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "kda_pairs_fwd" in there and "kda_pairs_bwd" in there
    assert "tpu_custom_call" in there
    assert "platform_index" not in here + there  # decided while lowering
    # the gated delta net's rule holds no kernel on either
    other = jax.jit(jax.grad(
        lambda q, k, v, g, beta: jnp.sum(jax.checkpoint(
            la.chunked_delta_rule)(q, k, v, g, beta)[0]), range(5))).trace(
                wide, wide, wide, shape(1, 128, 2), shape(1, 128, 2))
    assert "tpu_custom_call" not in other.lower(
        lowering_platforms=("tpu",)).as_text()


def _rule_loss(mesh=None):
    return lambda *a: jnp.sum(jax.checkpoint(
        lambda *b: la.chunked_kda_rule(*b, mesh))(*a)[0])


def _meshed(mesh, dk, dims=(2, 128, 2)):
    """Shapes of the rule's five inputs, batch rows and heads over `mesh`."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dynolog_tpu.parallel.sharding import BATCH_AXES

    def shape(*tail):
        spec = P(BATCH_AXES, None, "model", *(None,) * len(tail))
        return jax.ShapeDtypeStruct(
            (*dims, *tail), jnp.float32, sharding=NamedSharding(mesh, spec))

    return (shape(dk), shape(dk), shape(dk), shape(dk), shape())


def test_over_a_mesh_the_kernels_lower_inside_a_shard_map():
    """A Mosaic kernel cannot be partitioned: given the mesh, the rule's
    gradient lowers for a TPU with both kernels under a manual region (a
    device its own batch rows and heads); not given it, JAX refuses the
    lowering, which is what a KDA job over a mesh met before the layer
    handed its mesh down. For the CPU the plain ops are partitioned as they
    were, no manual region at all. (The chip's compiler takes the meshed
    program in tests/test_deepseek_v2.py.)"""
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=2, model=2), jax.devices()[:4])
    args = _meshed(mesh, 128)
    traced = jax.jit(jax.grad(_rule_loss(mesh), range(5))).trace(*args)
    here = traced.lower().as_text()
    assert "tpu_custom_call" not in here and "manual" not in here
    there = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "kda_pairs_fwd" in there and "kda_pairs_bwd" in there
    assert "manual" in there
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(jax.grad(_rule_loss(), range(5))).trace(*args).lower(
            lowering_platforms=("tpu",))


def test_over_a_mesh_the_kernels_compute_what_one_device_does(monkeypatch):
    """The kernels under their `shard_map` (2 x 2 devices: a batch row and a
    head each, 2 chunk-heads padded to a program's 8) against the plain body
    on one device: the rule whole and its five gradients. The TPU's branch
    is chosen here, where it can be seen: these are CPU devices."""
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=2, model=2), jax.devices()[:4])
    shapes = _meshed(mesh, 128)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k, v = (jax.random.normal(key, x.shape) / 128 ** 0.5
               for key, x in zip(keys[:3], shapes))
    g = jnp.log(jax.random.uniform(
        keys[3], shapes[3].shape, minval=0.3, maxval=0.999))
    args = (q, k, v, g, jax.random.uniform(keys[4], shapes[4].shape))

    def scalar(mesh):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(la.chunked_kda_rule(*a, mesh)[0] ** 2),
            range(5)))

    want = scalar(None)(*args)
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    with pltpu.force_tpu_interpret_mode():
        got = scalar(mesh)(*(jax.device_put(a, x.sharding)
                             for a, x in zip(args, shapes)))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dk", [8, 16, 96])
def test_a_width_the_chip_has_not_compiled_keeps_the_plain_body(dk):
    """Mosaic compiled the turned layout at `WIDTHS` (tests/
    test_deepseek_v2.py); at any other head width a program for a TPU holds
    the plain ops, which the chip compiled at every width."""
    assert dk not in kernels.WIDTHS and not kernels.compiles(la.CHUNK, dk)
    shapes = [jax.ShapeDtypeStruct((1, 128, 2, dk), jnp.float32)] * 4
    text = jax.jit(jax.grad(_rule_loss(), range(5))).trace(
        *shapes, jax.ShapeDtypeStruct((1, 128, 2), jnp.float32)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text and "kda_pairs" not in text


@pytest.mark.parametrize("chunk, dk", [(32, 128), (128, 128), (64, 12)])
def test_a_chunk_the_layout_cannot_hold_is_refused(chunk, dk):
    x = jnp.zeros((HEADS, chunk, dk))
    assert not kernels.compiles(chunk, dk)
    with pytest.raises(ValueError, match="two chunks of 64 rows"):
        kda_pairs(x, x, x)
