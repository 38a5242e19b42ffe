"""Sharded training step for the flagship workload.

Builds a jitted Adam train step over a (data, seq, model) mesh with the
shardings from dynolog_tpu.parallel.sharding — the workload the daemon's
trace path and benchmarks observe. Gradient/optimizer math is optax adamw;
the step is one compiled XLA program per mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

from dynolog_tpu.models.transformer import TransformerConfig, init_params, loss_fn
from dynolog_tpu.parallel.sharding import batch_sharding, shard_params


def make_optimizer(lr: float = 3e-4):
    return optax.adamw(lr, weight_decay=0.01)


def state_shardings(optimizer, params, mesh):
    """(parameter shardings, optimizer-state shardings) over `mesh` for a
    pytree of parameters (arrays, tracers or shapes: only the paths are
    read): PARAM_RULES for the parameters, the same layout for each moment
    of them, everything else in the optimizer state (the step count)
    replicated."""
    param_shardings = shard_params(params, mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    opt_shardings = optax.tree_utils.tree_map_params(
        optimizer, lambda _, sharding: sharding,
        jax.eval_shape(optimizer.init, params), param_shardings,
        transform_non_params=lambda _: replicated)
    return param_shardings, opt_shardings


def make_train_state(rng, cfg: TransformerConfig, mesh=None,
                     lr: float | None = None):
    """(params, opt_state), placed on the mesh when one is given.

    The init is one jitted program either way: each matrix's float32 draw
    is scaled, cast and freed inside it (eagerly, the embedding alone holds
    2.1 GB of float32 at published widths), and with a mesh the outputs are
    born sharded, so a large model is never materialized on one device.
    Threefry is partitionable, so the sharded and unsharded inits of one
    seed produce the same weights. The optimizer state is given the
    parameters' layout explicitly: its zeros depend on no input, so
    propagation leaves them whole on device 0 (seen on four v5e chips: 6 GB
    of Adam state on chip 0 and a 15.9 GB peak there in the first step).
    `lr` None: the configuration's `learning_rate`.
    """
    optimizer = make_optimizer(cfg.learning_rate if lr is None else lr)
    param_shardings = opt_shardings = None
    if mesh is not None:
        param_shardings, opt_shardings = state_shardings(
            optimizer, jax.eval_shape(lambda r: init_params(r, cfg), rng),
            mesh)
    params = jax.jit(
        lambda r: init_params(r, cfg), out_shardings=param_shardings)(rng)
    opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(params)
    return params, opt_state


def make_train_step(cfg: TransformerConfig, mesh=None,
                    lr: float | None = None):
    """Returns a jitted (params, opt_state, tokens) -> (params, opt_state,
    loss) step; sharded over `mesh` when given. `lr` None: the
    configuration's `learning_rate`.

    params and opt_state are donated: the update writes into the buffers it
    read, so the resident state is held once, not twice. A caller must
    rebind both from the step's outputs; the arrays it passed in are gone.

    Under a mesh the state leaves the step in the layout it is born in
    (`state_shardings`). Left to propagation, XLA hands the replicated norm
    scales back sharded on `model`: a second call of `jax.jit` then
    compiles the step again for the new layout, and an executable compiled
    ahead of time (`.lower().compile()`) refuses its own outputs as inputs
    ("compiled for input shardings that disagree").
    """
    optimizer = make_optimizer(cfg.learning_rate if lr is None else lr)

    # ring/flash attention and the expert layer (shard_map) need the mesh at
    # trace time
    fwd_mesh = (
        mesh if cfg.attn_impl in ("ring", "flash") or cfg.n_experts > 0
        else None)

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, fwd_mesh)
        with jax.named_scope("adam"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if mesh is not None:
            params, opt_state = jax.lax.with_sharding_constraint(
                (params, opt_state), state_shardings(optimizer, params, mesh))
        return params, opt_state, loss

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1))

    data_sharding = batch_sharding(mesh)
    return jax.jit(
        step, in_shardings=(None, None, data_sharding),
        donate_argnums=(0, 1))


def make_batch(rng, cfg: TransformerConfig, batch_size: int, seq_len: int):
    return jax.random.randint(
        rng, (batch_size, seq_len), 0, cfg.vocab_size, dtype=jnp.int32
    )
