"""Mesh + sharding helpers for the demo/benchmark workloads.

The monitoring framework itself is parallelism-agnostic (it observes JAX
jobs whatever their sharding — SURVEY §2.9); these helpers exist so the
flagship workload (dynolog_tpu.models) exercises realistic dp/tp/sp
shardings for multi-chip dry runs, benchmarks and trace demos.

Design: a named `jax.sharding.Mesh` with axes (data, seq, model); parameters
are sharded tensor-parallel on the `model` axis, the batch dimension
data-parallel on `data`, and long-sequence activations sequence-parallel on
`seq`. XLA inserts the collectives (all-gather/reduce-scatter over ICI) from
the sharding annotations — no hand-written comms. The one exception is the
expert layer (dynolog_tpu.models.moe): the chips of the `expert` axis share a
layer's experts AND the batch (expert parallel inside data parallel), and
exchange token copies by an all-to-all the layer writes itself.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape; dims must multiply to the device count.

    Five named axes cover the parallelism strategies the flagship workload
    exercises: `data` (DP), `seq` (sequence/context parallel — ring
    attention), `model` (TP), `expert` (EP — MoE all-to-all dispatch) and
    `pipe` (PP — GPipe microbatch pipeline). Unused axes default to size 1
    and cost nothing.
    """

    data: int = 1
    seq: int = 1
    model: int = 1
    expert: int = 1
    pipe: int = 1
    axis_names: tuple = field(default=("data", "seq", "model", "expert", "pipe"))

    @property
    def shape(self) -> tuple:
        return (self.data, self.seq, self.model, self.expert, self.pipe)

    @classmethod
    def for_devices(cls, n: int) -> "MeshSpec":
        """A balanced dp×sp×tp factorization of n devices (largest factor to
        data, then model, then seq)."""
        dims = [1, 1, 1]  # data, model, seq
        remaining = n
        order = [0, 1, 2]
        i = 0
        while remaining > 1:
            for p in (2, 3, 5, 7):
                if remaining % p == 0:
                    dims[order[i % 3]] *= p
                    remaining //= p
                    i += 1
                    break
            else:
                dims[0] *= remaining
                remaining = 1
        return cls(data=dims[0], model=dims[1], seq=dims[2])


def make_mesh(spec: MeshSpec, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(spec.shape))
    if len(devices) < n:
        raise ValueError(f"need {n} devices for mesh {spec.shape}, have {len(devices)}")
    grid = np.asarray(devices[:n]).reshape(spec.shape)
    return Mesh(grid, spec.axis_names)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


# The batch goes over the chips that share a layer's experts as well as over
# `data`: each routes its own tokens. With `expert` 1 this is `data` alone.
BATCH_AXES = ("data", "expert")

# Parameter partition rules, keyed by parameter-name suffix. Attention and
# MLP matrices are tensor-parallel on `model`; embeddings are replicated on
# seq/data and sharded on model along the vocab/hidden dim.
PARAM_RULES = {
    "embedding": P(None, "model"),
    "wq": P(None, "model"),
    "wk": P(None, "model"),
    "wv": P(None, "model"),
    "wo": P("model", None),
    # Gated attention: the gate's matrix as wq (columns are heads x head
    # size). The per-head norms' weights (q_head_scale, k_head_scale: one
    # head's width, shared by the heads) and the post-mixer norms'
    # (attn_post_scale, mlp_post_scale) are replicated, by "scale" below.
    "wg": P(None, "model"),
    "w_gate": P(None, "model"),
    "w_up": P(None, "model"),
    "w_down": P("model", None),
    "w_out": P(None, "model"),
    "scale": P(None),
    # MoE: router (and everything outside the experts) replicated over
    # `expert`; stacked expert weights [E, d, f] sharded on `expert` (EP)
    # with the hidden dim tensor-parallel on `model` (EP x TP).
    "router": P(),
    "experts_gate": P("expert", None, "model"),
    "experts_up": P("expert", None, "model"),
    "experts_down": P("expert", "model", None),
    # A gated-delta-net layer (dynolog_tpu.models.linear_attention): the
    # projections by heads on `model` (columns are heads x head size, so
    # n_heads has to divide by the axis), the output matrix the other way,
    # as attention's are. Named here because a name no rule ends falls to
    # replication in silence: 88 M parameters a layer at Olmo-Hybrid-7B's
    # widths. The small ones (a weight a channel a tap, a number a head, a
    # head's norm scale) are replicated; gdn_norm_scale by "scale" above.
    # The shared experts (one SwiGLU beside the routed ones): as the dense
    # MLP's, tensor-parallel on `model`, whole on every chip of `expert`.
    "shared_gate": P(None, "model"),
    "shared_up": P(None, "model"),
    "shared_down": P("model", None),
    # Latent attention (dynolog_tpu.models.mla): wq and wo by the rules
    # above (columns are heads x head size); the expansion from the latent
    # by heads on `model` too (columns are heads x (nope + value));
    # the compression (the latent and the one rotary key every head
    # shares) whole on every chip; mla_kv_scale by "scale" above.
    "mla_dkv": P(),
    "mla_ukv": P(None, "model"),
    # The router's selection bias (a number an expert): as the router.
    "router_bias": P(),
    # A Mamba-2 block (dynolog_tpu.models.mamba2): the one projection in
    # (columns z | x B C | dt) by columns on `model`, as every matrix out of
    # d_model is, the output matrix the other way. The columns are three
    # runs of heads, so the partitioner re-lays the slices where `model`
    # cuts across them; a whole group of heads a chip (ssm_groups dividing
    # by the axis) keeps the recurrence and the grouped norm local. The
    # small ones (a weight a channel a tap and its bias, a number a head)
    # are replicated; ssm_scale and ssm_norm_scale by "scale" above.
    "ssm_in": P(None, "model"),
    "ssm_out": P("model", None),
    "ssm_conv": P(),
    "ssm_conv_bias": P(),
    "ssm_a_log": P(),
    "ssm_dt_bias": P(),
    "ssm_d": P(),
    "gdn_q": P(None, "model"),
    "gdn_k": P(None, "model"),
    "gdn_v": P(None, "model"),
    "gdn_g": P(None, "model"),
    "gdn_o": P("model", None),
    "gdn_conv_q": P(),
    "gdn_conv_k": P(),
    "gdn_conv_v": P(),
    "gdn_b": P(),
    "gdn_a": P(),
    "gdn_a_log": P(),
    "gdn_dt_bias": P(),
    # A Kimi Delta Attention layer (the same module): as the gated delta
    # net's, by heads on `model`; the two bottlenecks go in replicated (a
    # head's width) and come out by heads (columns are heads x head size);
    # kda_norm_scale by "scale" above.
    "kda_q": P(None, "model"),
    "kda_k": P(None, "model"),
    "kda_v": P(None, "model"),
    "kda_f_up": P(None, "model"),
    "kda_g_up": P(None, "model"),
    "kda_o": P("model", None),
    "kda_f_down": P(),
    "kda_g_down": P(),
    "kda_conv_q": P(),
    "kda_conv_k": P(),
    "kda_conv_v": P(),
    "kda_b": P(),
    "kda_a_log": P(),
    "kda_dt_bias": P(),
}


def _rule_for(path: str) -> P:
    for suffix, spec in PARAM_RULES.items():
        if path.endswith(suffix):
            return spec
    return P()  # replicate


def shard_params(params, mesh: Mesh):
    """Pytree of NamedShardings matching PARAM_RULES by leaf path."""

    def to_sharding(path, leaf):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        return NamedSharding(mesh, _rule_for(name))

    return jax.tree_util.tree_map_with_path(to_sharding, params)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Tokens [batch, seq]: batch over BATCH_AXES, sequence over `seq`."""
    return NamedSharding(mesh, P(BATCH_AXES, ("seq",)))
