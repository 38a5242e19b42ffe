"""The four-chip host's job on four virtual CPU devices, at toy widths whose
heads, FFN and vocabulary divide by the `model` axis: the program's sharded
forward and step over a data 2 x model 2 mesh against the benchmark's plain
float32 reference (perfbench/reference.py) on the same seeded weights, and
where the state of the step lives."""

from __future__ import annotations

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.experimental.pallas import tpu as pltpu

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "perfbench"))

import checks  # noqa: E402
import reference  # noqa: E402
from dynolog_tpu.models.train import (  # noqa: E402
    make_optimizer, make_train_state, make_train_step)
from dynolog_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, forward, init_params, loss_fn)
from dynolog_tpu.parallel.sharding import (  # noqa: E402
    MeshSpec, make_mesh, shard_params)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 virtual devices")

BATCH, SEQ, LAST = 2, 64, 16


def job(dtype: str, attn_impl: str = "reference") -> dict:
    return {"vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4,
            "d_ff": 192, "max_seq_len": SEQ, "rope_theta": 500000.0,
            "dtype": dtype, "attn_impl": attn_impl}


def config(j: dict) -> TransformerConfig:
    return TransformerConfig(**j)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(data=2, model=2), jax.devices()[:4])


def seeded(j: dict, mesh=None, seed: int = 28):
    """Weights as the benchmark makes them (born sharded under a mesh) and a
    batch of tokens."""
    key_w, key_b = jax.random.split(jax.random.PRNGKey(seed))
    shardings = None
    if mesh is not None:
        shardings = shard_params(jax.eval_shape(
            lambda k: reference.init_weights(k, j), key_w), mesh)
    params = jax.jit(lambda k: reference.init_weights(k, j),
                     out_shardings=shardings)(key_w)
    tokens = jax.random.randint(
        key_b, (BATCH, SEQ), 0, j["vocab_size"], "int32")
    return params, tokens


def sharded_readings(j: dict, mesh) -> tuple:
    """(relative rms of the sharded forward's last logits against the
    reference, |sharded loss - reference loss|): what check J compares."""
    cfg = config(j)
    params, tokens = seeded(j, mesh)
    want, want_loss = reference.forward(params, tokens, j, LAST)
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh)[:, -LAST:])(
        params, tokens)
    loss = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(params, tokens)
    return reference.rel_rms(got, want), abs(float(loss) - float(want_loss))


def test_float32_sharded_forward_is_the_reference(mesh):
    rel, loss_gap = sharded_readings(job("float32"), mesh)
    assert rel <= 1e-4, rel
    assert loss_gap <= 1e-5, loss_gap


def test_bfloat16_sharded_forward_passes_check_j_and_float8_fails(mesh):
    j = job("bfloat16")
    rel, loss_gap = sharded_readings(j, mesh)
    assert rel <= checks.J_LOGIT_REL_RMS_LIMIT, rel
    assert loss_gap <= checks.J_LOSS_ABS_LIMIT, loss_gap
    # the control: the reference with every weight rounded to float8 in the
    # program's place has to fail the limit the sound job passes
    params, tokens = seeded(j, mesh)
    want, _ = reference.forward(params, tokens, j, LAST)
    low, _ = reference.forward(params, tokens, j, LAST,
                               rounding=reference.lower)
    assert reference.rel_rms(low, want) > checks.J_LOGIT_REL_RMS_LIMIT


def test_flash_kernels_under_shard_map_match_the_reference(mesh):
    """The Mosaic kernels run per device on its batch rows and heads
    (interpret mode here; the chip compiles them)."""
    with pltpu.force_tpu_interpret_mode():
        rel, loss_gap = sharded_readings(job("float32", "flash"), mesh)
    assert rel <= 1e-4, rel
    assert loss_gap <= 1e-5, loss_gap


def reference_loss(params, tokens, j: dict):
    return reference.forward(params, tokens, j, LAST)[1]


def test_sharded_step_equals_unsharded_step_and_reference_grad(mesh):
    j = job("float32")
    cfg = config(j)
    optimizer = make_optimizer()
    params, tokens = seeded(j)

    # what the update must be: optax over jax.grad of the REFERENCE's loss
    @jax.jit
    def by_the_reference(params, tokens):
        loss, grads = jax.value_and_grad(reference_loss)(params, tokens, j)
        updates, _ = optimizer.update(grads, optimizer.init(params), params)
        return optax.apply_updates(params, updates), loss

    want, want_loss = by_the_reference(params, tokens)
    init = jax.jit(optimizer.init)
    fresh, _ = seeded(j)  # the step donates what it is given
    plain, _, plain_loss = make_train_step(cfg)(fresh, init(fresh), tokens)
    sharded_params, _ = seeded(j, mesh)
    sharded, _, sharded_loss = make_train_step(cfg, mesh)(
        sharded_params, init(sharded_params), tokens)

    assert abs(float(plain_loss) - float(sharded_loss)) <= 1e-5
    assert abs(float(plain_loss) - float(want_loss)) <= 1e-5
    flat_want = jax.tree_util.tree_leaves(want)
    for name, tree in (("unsharded", plain), ("sharded", sharded)):
        for got, ref, before in zip(jax.tree_util.tree_leaves(tree), flat_want,
                                    jax.tree_util.tree_leaves(params)):
            # Equal to float32 rounding. Adam's first move is lr * g / (|g| +
            # eps), 3e-4 at most, and where a gradient is within rounding
            # of zero its sign-like quotient is not: 1e-5 is a thirtieth
            # of one move (one element in 4096 read 2.4e-6).
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=0, atol=1e-5,
                err_msg=name)
            assert float(jnp.max(jnp.abs(got - before))) > 0, name


def test_state_is_sharded_like_its_parameters_on_every_device(mesh):
    """`make_train_state`'s docstring: Adam's zeros depend on no input, so
    propagation once left 6 GB of them whole on chip 0. Every matrix of the
    parameters and of both moments holds half of itself on each device."""
    cfg = config(job("bfloat16"))
    params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg, mesh)
    abstract = jax.eval_shape(lambda r: init_params(r, cfg),
                              jax.random.PRNGKey(0))
    want = shard_params(abstract, mesh)
    adam = opt_state[0]
    devices = set(mesh.devices.flat)
    for tree in (params, adam.mu, adam.nu):
        for leaf, sharding in zip(jax.tree_util.tree_leaves(tree),
                                  jax.tree_util.tree_leaves(want)):
            assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
            shards = leaf.addressable_shards
            assert {s.device for s in shards} == devices
            per_device = {s.data.size for s in shards}
            split = 1 if leaf.ndim == 1 else 2  # scales are replicated
            assert per_device == {leaf.size // split}, (leaf.shape, per_device)
    # the fullest device holds no more than any other
    held = {d: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves((params, adam.mu, adam.nu)):
        for s in leaf.addressable_shards:
            held[s.device] += s.data.nbytes
    assert len(set(held.values())) == 1, held


def test_compiled_sharded_step_accepts_its_own_outputs(mesh):
    """The state leaves the step in the layout it was born in. Left to
    propagation the replicated norm scales came back sharded on `model`: an
    executable compiled ahead of time, as the benchmark's is, then refused
    its own outputs at the second step, and `jax.jit` compiled twice."""
    cfg = config(job("bfloat16"))
    params, opt_state = make_train_state(jax.random.PRNGKey(1), cfg, mesh)
    _, tokens = seeded(job("bfloat16"))
    born = jax.tree_util.tree_map(lambda a: a.sharding, (params, opt_state))
    step = make_train_step(cfg, mesh).lower(params, opt_state, tokens).compile()
    params, opt_state, first = step(params, opt_state, tokens)
    params, opt_state, second = step(params, opt_state, tokens)
    assert float(second) < float(first)
    for leaf, sharding in zip(
            jax.tree_util.tree_leaves((params, opt_state)),
            jax.tree_util.tree_leaves(born)):
        assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim), leaf.shape

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **__: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    jitted = make_train_step(cfg, mesh)
    state = make_train_state(jax.random.PRNGKey(1), cfg, mesh)
    before = len(compiles)
    for _ in range(3):
        *state, _ = jitted(*state, tokens)
    assert len(compiles) - before == 1
