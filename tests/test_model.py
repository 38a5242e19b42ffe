"""Unit tests for the flagship workload + sharding helpers (CPU mesh)."""

import jax
import jax.numpy as jnp
import pytest

from conftest import slow_lane
from dynolog_tpu.models.train import make_batch, make_train_state, make_train_step
from dynolog_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
)
from dynolog_tpu.parallel.sharding import MeshSpec, batch_sharding, make_mesh


CFG = TransformerConfig(
    vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64, max_seq_len=32
)


def test_forward_shapes_and_dtype():
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = make_batch(jax.random.PRNGKey(1), CFG, 2, 16)
    logits = forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality():
    """Changing a future token must not affect earlier logits."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = make_batch(jax.random.PRNGKey(1), CFG, 1, 16)
    logits_a = forward(params, tokens, CFG)
    tampered = tokens.at[0, -1].set((tokens[0, -1] + 1) % CFG.vocab_size)
    logits_b = forward(params, tampered, CFG)
    assert jnp.allclose(logits_a[0, :-1], logits_b[0, :-1], atol=1e-5)
    assert not jnp.allclose(logits_a[0, -1], logits_b[0, -1], atol=1e-5)


def test_train_step_reduces_loss():
    params, opt_state = make_train_state(jax.random.PRNGKey(0), CFG, lr=1e-2)
    step = make_train_step(CFG, lr=1e-2)
    batch = make_batch(jax.random.PRNGKey(1), CFG, 4, 16)
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_mesh_spec_factorization():
    for n in (1, 2, 4, 8, 6, 12):
        spec = MeshSpec.for_devices(n)
        assert spec.data * spec.seq * spec.model == n


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_sharded_train_step_matches_single_device():
    """dp/sp/tp sharded step computes the same loss as unsharded."""
    mesh = make_mesh(MeshSpec(data=2, seq=2, model=2))
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64
    )
    batch = make_batch(jax.random.PRNGKey(1), cfg, 4, 32)

    with mesh:
        params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg, mesh)
        # Adam state is born divided like the weights, not whole on
        # device 0 (its zeros depend on no input, so nothing propagates).
        for name in ("embedding", "w_out"):
            assert (opt_state[0].mu[name].sharding
                    == params[name].sharding), name
        step = make_train_step(cfg, mesh)
        sharded_batch = jax.device_put(batch, batch_sharding(mesh))
        _, _, sharded_loss = step(params, opt_state, sharded_batch)

    ref_params, ref_opt = make_train_state(jax.random.PRNGKey(0), cfg)
    ref_step = make_train_step(cfg)
    _, _, ref_loss = ref_step(ref_params, ref_opt, batch)

    assert abs(float(sharded_loss) - float(ref_loss)) < 1e-3


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@slow_lane
@pytest.mark.parametrize("spec", [
    MeshSpec(expert=4), MeshSpec(data=2, expert=2, model=2),
], ids=["expert-4", "data-2-expert-2-model-2"])
def test_moe_expert_parallel_matches_single_device(spec):
    """The ep and the dp x ep x tp MoE step compute the unsharded step's
    loss: in float32 a dropless layer agrees to rounding (2e-2 was what
    token dropping over a capacity needed).

    Slow lane: tests/test_moe.py holds the expert mesh to the one-device
    step in the default lane, weights and all; this keeps the composition
    with `data` and `model`."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        n_experts=4, dtype="float32",
    )
    batch = make_batch(jax.random.PRNGKey(1), cfg, 4, 32)

    ref_params, ref_opt = make_train_state(jax.random.PRNGKey(0), cfg)
    ref_step = make_train_step(cfg)
    _, _, ref_loss = ref_step(ref_params, ref_opt, batch)

    mesh = make_mesh(spec)
    with mesh:
        params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg, mesh)
        step = make_train_step(cfg, mesh)
        sharded_batch = jax.device_put(batch, batch_sharding(mesh))
        _, _, moe_loss = step(params, opt_state, sharded_batch)

    assert abs(float(moe_loss) - float(ref_loss)) < 1e-5


def test_moe_train_step_reduces_loss():
    cfg = TransformerConfig(
        vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_seq_len=32, n_experts=4,
    )
    params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg, lr=1e-2)
    step = make_train_step(cfg, lr=1e-2)
    batch = make_batch(jax.random.PRNGKey(1), cfg, 4, 16)
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
@slow_lane
def test_pipeline_matches_dense_loss_and_grads():
    """GPipe schedule over the pipe axis reproduces the dense path's loss
    AND gradients (same math, different schedule) — finiteness alone
    would not catch mis-summed cotangents across pipe ranks for the
    replicated embedding/head params. One value_and_grad compile per
    path covers both checks (the forward is free inside the grad
    compile; a separate loss-only test would pay a whole extra pipeline
    compile on the 1-core CI host), and the train step runs.

    Slow lane (~63s, the suite's heaviest compile): the driver's dryrun
    executes the dp x pp GPipe step every round; the exact-gradient
    equivalence stays covered in CI's slow job."""
    import numpy as np

    from dynolog_tpu.parallel.pipeline import (
        make_pipeline_train_state,
        make_pipeline_train_step,
        pipeline_loss,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=4, d_ff=64
    )
    batch = make_batch(jax.random.PRNGKey(1), cfg, 8, 32)

    params = init_params(jax.random.PRNGKey(0), cfg)
    ref, dense_grads = jax.jit(
        jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg))
    )(params, batch)
    stacked_dense = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *dense_grads["layers"]
    )

    mesh = make_mesh(MeshSpec(data=2, pipe=4))
    with mesh:
        pp, opt_state = make_pipeline_train_state(
            jax.random.PRNGKey(0), cfg, mesh
        )
        pl, pipe_grads = jax.jit(
            jax.value_and_grad(
                lambda p, t: pipeline_loss(p, t, cfg, mesh, n_micro=2)
            )
        )(pp, batch)
        assert abs(float(ref) - float(pl)) < 2e-2, (float(ref), float(pl))

        step = make_pipeline_train_step(cfg, mesh, n_micro=2)
        _, _, l2 = step(pp, opt_state, batch)
        assert jnp.isfinite(l2)

    def check(name, a, b):
        # bf16 activations make per-entry tolerances loose (embedding grads
        # are scatter-adds whose accumulation order differs between the
        # schedules), but a mis-summed cotangent across pipe/data ranks is
        # a 2x-4x error on the largest entries — far outside these bounds.
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = np.abs(a).max() + 1e-12
        assert np.abs(a - b).max() < 5e-2 * scale, (
            name,
            float(np.abs(a - b).max()),
            float(scale),
        )

    for name in ("embedding", "w_out", "final_scale"):
        check(name, dense_grads[name], pipe_grads[name])
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(stacked_dense),
        jax.tree_util.tree_leaves(pipe_grads["layers"]),
    ):
        check(jax.tree_util.keystr(path), a, b)


def test_graft_entry_compiles():
    """Default lane: the driver's single-chip compile check (cheap). The
    entry carries the flash kernels and no fallback, so on this CPU it
    runs only because the test asks for Pallas interpret mode here."""
    from jax.experimental.pallas import tpu as pltpu

    import __graft_entry__ as graft

    fn, args = graft.entry()
    with pytest.raises(ValueError, match="interpret"):
        jax.jit(fn)(*args)
    with pltpu.force_tpu_interpret_mode():
        out = jax.jit(fn)(*args)
    assert out.shape[0] == 4


@slow_lane
def test_graft_entry_dryrun():
    """Slow lane: the full 8-device dryrun (~3.5 min on the 1-core CI
    host: three mesh configs x (compile + monitoring leg) + the push
    capture). The default lane holds the sharded step at a smaller size
    (test_sharded_train_step_matches_single_device above,
    tests/test_sharded_job.py); MoE and pipeline are slow lane too."""
    import __graft_entry__ as graft

    graft.dryrun_multichip(8)
