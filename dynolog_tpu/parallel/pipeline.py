"""GPipe-style pipeline parallelism (PP) over the mesh's `pipe` axis.

Stages the flagship transformer's layer stack across devices: layer
parameters are stacked [n_layers, ...] and sharded P('pipe', ...), so each
device along the `pipe` axis holds a contiguous block of layers. The
training batch is split into microbatches that flow through the stages in
the classic GPipe schedule: at tick t, stage p computes microbatch t - p
and hands its activations to stage p+1 via `jax.lax.ppermute` (one ICI hop
— the point-to-point traffic the tpumon ICI telemetry observes).

TPU-first design notes (vs a CUDA pipeline runtime):
- The whole schedule is ONE compiled XLA program: a `lax.scan` over
  n_micro + n_stages - 1 ticks with a ppermute in the body — no host-side
  scheduler thread, no NCCL send/recv pairs, no stream juggling. XLA
  overlaps the ppermute with the next tick's stage compute.
- Stage compute is itself a `lax.scan` over the stage's local layers, so
  the program size is independent of layer count.
- Backward is just `jax.grad` through the scan: XLA re-runs the schedule
  in reverse (activations rematerialized per GPipe), no hand-written
  1F1B bookkeeping. Composes with DP over the `data` axis inside the same
  shard_map.

The reference framework has no pipeline engine (it is a monitoring daemon,
SURVEY §2.9); this module makes the dry-run/demo workload exercise PP so
pod-wide synchronized captures include pipeline bubbles and stage-boundary
collectives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dynolog_tpu.models.transformer import (
    TransformerConfig,
    _attention,
    _mlp,
    _rmsnorm,
)


def _require_dense_pairs(cfg: TransformerConfig) -> None:
    assert (cfg.n_experts == 0 and cfg.attn_impl == "reference"
            and not cfg.has_linear_layers and cfg.attn_type == "mha"
            and cfg.block_types is None and cfg.kv_heads == cfg.n_heads
            and cfg.mlp_act == "swiglu"
            and all(cfg.window(i) is None and cfg.rotary(i) == cfg.rotary(0)
                    for i in range(cfg.n_layers))
            and not (cfg.qk_head_norm or cfg.attn_gate or cfg.post_norm
                     or cfg.scale_embedding)), (
        "pipeline path supports the dense/reference transformer config: no "
        "experts, no linear_attention layer, no latent attention, no "
        "one-mixer blocks (block_types: a mamba2 block among them), no "
        "grouped key/value heads, no ReLU^2 MLP, no sliding_attention "
        "layer's window or position by layer kind, no per-head norms, "
        "gated attention, post-mixer norms or scaled embedding (a stage "
        "would run each as full multi-head attention and a SwiGLU MLP "
        "between pre-norms, every layer rotated alike)"
    )


def init_pipeline_params(rng, cfg: TransformerConfig, mesh):
    """Transformer params with the layer stack stacked along a leading
    [n_layers] axis (sharded over `pipe`); embedding/head replicated."""
    from dynolog_tpu.models.transformer import init_params

    n_stages = mesh.shape["pipe"]
    assert cfg.n_layers % n_stages == 0, (
        f"n_layers={cfg.n_layers} must divide into pipe={n_stages} stages"
    )
    _require_dense_pairs(cfg)

    params = init_params(rng, cfg)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params["layers"])
    layer_sharding = jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, P("pipe")), stacked
    )
    stacked = jax.device_put(stacked, layer_sharding)
    return {
        "embedding": params["embedding"],
        "w_out": params["w_out"],
        "final_scale": params["final_scale"],
        "layers": stacked,
    }


def _stage_forward(stage_layers, x, positions, cfg: TransformerConfig):
    """Run this stage's local block of layers. stage_layers leaves are
    [n_local_layers, ...]; x: [mb, S, D]."""

    def body(h, layer):
        h = h + _attention(
            layer, _rmsnorm(h, layer["attn_scale"], cfg.norm_eps), positions,
            cfg)
        h = h + _mlp(layer, _rmsnorm(h, layer["mlp_scale"], cfg.norm_eps))
        return h, None

    x, _ = jax.lax.scan(body, x, stage_layers)
    return x


def pipeline_loss(params, tokens, cfg: TransformerConfig, mesh, n_micro: int):
    """Next-token CE loss computed with the GPipe schedule over the mesh's
    `pipe` axis (DP over `data` composes inside the same shard_map).

    tokens: global [B, S]; B must divide by data x n_micro.
    """
    n_stages = mesh.shape["pipe"]
    _require_dense_pairs(cfg)

    def local(layers, embedding, w_out, final_scale, tokens_local):
        p_idx = jax.lax.axis_index("pipe")
        b_loc, s = tokens_local.shape
        assert b_loc % n_micro == 0, (b_loc, n_micro)
        mb = b_loc // n_micro
        micro = tokens_local.reshape(n_micro, mb, s)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (mb, s))

        # Embedding gathers are only needed on stage 0 (everything later
        # gets activations over the wire); cond skips them elsewhere.
        x_micro = jax.lax.cond(
            p_idx == 0,
            lambda: embedding[micro].astype(embedding.dtype),
            lambda: jnp.zeros(micro.shape + (embedding.shape[1],),
                              embedding.dtype),
        )  # [n_micro, mb, S, D]
        # Pad the microbatch stream with zeros for drain ticks.
        pad = jnp.zeros((n_stages - 1,) + x_micro.shape[1:], x_micro.dtype)
        feed = jnp.concatenate([x_micro, pad], axis=0)  # [n_ticks, mb, S, D]

        fwd = functools.partial(_stage_forward, layers)
        perm_fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, x_in):
            # carry: activation arriving at this stage this tick
            act_in = carry
            # stage 0 takes from the feed; others take the carried handoff
            x = jnp.where(p_idx == 0, x_in, act_in)
            y = fwd(x, positions, cfg)
            # hand activations to the next stage (last stage's output is
            # not forwarded; ppermute drops it — y is also this tick's
            # "emitted" output which only matters on the last stage)
            act_next = jax.lax.ppermute(y, "pipe", perm_fwd)
            return act_next, y

        act0 = jnp.zeros_like(x_micro[0])
        _, ys = jax.lax.scan(tick, act0, feed)  # ys: [n_ticks, mb, S, D]

        # On the last stage, microbatch m completes at tick m + n_stages - 1.
        # The vocab head (the step's largest matmul) runs only there — cond
        # skips it on every other stage rather than masking afterwards.
        def head_loss():
            out = ys[n_stages - 1 :]  # [n_micro, mb, S, D]
            x = _rmsnorm(out, final_scale, cfg.norm_eps)
            logits = (x @ w_out).astype(jnp.float32)[..., :-1, :]
            targets = micro[..., 1:]
            logprobs = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)
            return jnp.mean(nll)

        loss_local = jax.lax.cond(
            p_idx == n_stages - 1, head_loss, lambda: jnp.float32(0.0)
        )
        # Broadcast the last stage's loss to every pipe rank, then average
        # over the data axis.
        loss = jax.lax.psum(loss_local, "pipe")
        loss = jax.lax.pmean(loss, "data")
        return loss

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("pipe"),  # stacked layer params
            P(),  # embedding
            P(),  # w_out
            P(),  # final_scale
            P("data", None),  # tokens: DP over batch
        ),
        out_specs=P(),
        check_vma=False,
    )(
        params["layers"],
        params["embedding"],
        params["w_out"],
        params["final_scale"],
        tokens,
    )


def make_pipeline_train_state(rng, cfg: TransformerConfig, mesh,
                              lr: float = 3e-4):
    """(params, opt_state) for the pipeline path (stage-sharded layers)."""
    from dynolog_tpu.models.train import make_optimizer

    params = init_pipeline_params(rng, cfg, mesh)
    opt_state = jax.jit(make_optimizer(lr).init)(params)
    return params, opt_state


def make_pipeline_train_step(cfg: TransformerConfig, mesh, n_micro: int,
                             lr: float = 3e-4):
    """Jitted (params, opt_state, tokens) -> (params, opt_state, loss) with
    the GPipe schedule; optimizer math is the same adamw as the dense path."""
    import optax

    from dynolog_tpu.models.train import make_optimizer

    optimizer = make_optimizer(lr)

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(pipeline_loss)(
            params, tokens, cfg, mesh, n_micro
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    data_sharding = NamedSharding(mesh, P(("data",), None))
    return jax.jit(step, in_shardings=(None, None, data_sharding))
