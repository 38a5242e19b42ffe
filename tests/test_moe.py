"""The dropless expert layer (dynolog_tpu/models/moe.py) against its
equations written plainly, on the CPU in float32: alone under forced uneven
routings, over a four-device `expert` mesh against one device (output, loss,
one step's weights, and the four shares' parts adding up to the whole), and
what the block gained for OLMoE's config (q/k norm, unrenormalised gates,
the balancing term over all k choices, the router z-loss) against the plain
reference the benchmark holds the job to (perfbench/olmoe_block.py, loaded
by path: it imports nothing of dynolog_tpu)."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynolog_tpu.models.moe import init_moe_layer, moe_mlp
from dynolog_tpu.models.train import (
    make_batch, make_train_state, make_train_step)
from dynolog_tpu.models.transformer import (
    TransformerConfig, forward, init_params, loss_fn)
from dynolog_tpu.parallel.sharding import MeshSpec, batch_sharding, make_mesh

ROOT = pathlib.Path(__file__).resolve().parent.parent
CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=48, max_seq_len=32,
    dtype="float32", n_experts=8, moe_top_k=2)
OLMOE = dict(norm_eps=1e-5, qk_norm=True, moe_norm_topk=False,
             moe_balance_all_k=True, moe_z_weight=0.001)
# the same settings, the two numbers large enough to read in a toy's loss
FEATURES = dict(OLMOE, norm_eps=0.1, moe_z_weight=0.1)

needs_four = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 virtual devices")


def olmoe_block():
    spec = importlib.util.spec_from_file_location(
        "olmoe_block", ROOT / "perfbench" / "olmoe_block.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def plain_layer(layer, x, cfg, held=None):
    """The layer's equations: every expert for every token, summed under
    gates that are 0 for an expert not chosen (and, with `held`, for an
    expert another share holds). Returns (y, balance, z)."""
    h = x.reshape(-1, x.shape[-1])
    logits = h @ layer["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    best, chosen = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        best = best / best.sum(-1, keepdims=True)
    picks = jax.nn.one_hot(chosen, cfg.n_experts)  # [T, k, E]
    gates = (best[..., None] * picks).sum(1)  # [T, E]
    y = jnp.zeros_like(h)
    for e in range(cfg.n_experts) if held is None else held:
        act = jax.nn.silu(h @ layer["experts_gate"][e]) * (
            h @ layer["experts_up"][e])
        y = y + gates[:, e:e + 1] * (act @ layer["experts_down"][e])
    counted = picks if cfg.moe_balance_all_k else picks[:, :1]
    balance = cfg.n_experts * jnp.sum(
        counted.mean((0, 1)) * probs.mean(0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y.reshape(x.shape), balance, z


def forced_layer(favoured, starved=None, strength=10.0):
    """A layer whose router sends every token's first choices to
    `favoured` and none to `starved`: the inputs below share a direction
    `u`, and the router reads it."""
    layer = init_moe_layer(jax.random.PRNGKey(3), CFG)
    u = jnp.ones((CFG.d_model,)) / np.sqrt(CFG.d_model)
    router = layer["router"]
    for rank, e in enumerate(favoured):
        router = router.at[:, e].add((strength - rank) * u)
    if starved is not None:
        router = router.at[:, starved].add(-strength * u)
    x = 0.5 * jax.random.normal(
        jax.random.PRNGKey(4), (4, 16, CFG.d_model)) + 4.0 * u
    return dict(layer, router=router), x


def close(a, b, tol=1e-5):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, (
        float(jnp.max(jnp.abs(a - b))), scale)


def assert_trees_close(got, want, tol=1e-5):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        try:
            close(a, b, tol)
        except AssertionError as e:
            raise AssertionError(jax.tree_util.keystr(path)) from e


def test_uneven_routing_equals_the_plain_sum_forward_and_gradients():
    """One expert given most of the tokens, one none."""
    layer, x = forced_layer(favoured=[5], starved=2)
    chosen = jax.lax.top_k(x.reshape(-1, 32) @ layer["router"], 2)[1]
    counts = np.bincount(np.asarray(chosen).reshape(-1), minlength=8)
    assert counts[5] == 64 and counts[2] == 0 and len(set(counts)) > 2

    def scalar(f):
        def g(layer, x):
            y, balance, z = f(layer, x, CFG)
            return jnp.sum(jnp.sin(y)) + balance + z
        return g

    close(moe_mlp(layer, x, CFG)[0], plain_layer(layer, x, CFG)[0])
    got = jax.grad(scalar(moe_mlp), argnums=(0, 1))(layer, x)
    want = jax.grad(scalar(plain_layer), argnums=(0, 1))(layer, x)
    assert_trees_close(got, want)


@pytest.mark.parametrize("spec", [
    None,
    pytest.param(MeshSpec(expert=4), marks=needs_four),
], ids=["one-device", "expert-4"])
def test_no_token_is_dropped_when_all_pick_the_same_experts(spec):
    """Every copy of every chip goes to experts 0 and 1, which one chip
    holds: the worst case the buffers are sized for."""
    layer, x = forced_layer(favoured=[0, 1])
    chosen = jax.lax.top_k(x.reshape(-1, 32) @ layer["router"], 2)[1]
    assert set(np.asarray(chosen).reshape(-1)) == {0, 1}
    mesh = make_mesh(spec) if spec else None
    y, _, _ = jax.jit(lambda l, x: moe_mlp(l, x, CFG, mesh))(layer, x)
    want = plain_layer(layer, x, CFG)[0]
    close(y, want)
    # no row of the result is the zero a dropped token would leave
    assert float(jnp.min(jnp.max(jnp.abs(y), axis=-1))) > 1e-3


@needs_four
def test_the_four_shares_tie_to_the_whole():
    """Over `expert` 4 the layer's output equals the one-device layer's,
    and the parts the four shares give (each chip's two experts alone, by
    the program with the other experts' output weights zeroed, and by the
    plain sum over those two) add up to the uncut layer's result."""
    layer, x = forced_layer(favoured=[5], starved=2, strength=1.0)
    mesh = make_mesh(MeshSpec(expert=4))
    sharded = jax.jit(lambda l, x: moe_mlp(l, x, CFG, mesh))
    whole, balance, z = moe_mlp(layer, x, CFG)
    got, got_balance, got_z = sharded(layer, x)
    close(got, whole)
    assert abs(float(got_balance - balance)) < 1e-5
    assert abs(float(got_z - z)) < 1e-5
    parts = []
    for share in range(4):
        held = np.arange(2 * share, 2 * share + 2)
        mask = jnp.zeros((8, 1, 1)).at[held].set(1.0)
        part = sharded(dict(layer, experts_down=layer["experts_down"] * mask),
                       x)[0]
        close(part, plain_layer(layer, x, CFG, held=held)[0])
        parts.append(part)
    close(sum(parts), whole)


@needs_four
def test_a_step_over_the_expert_mesh_is_the_one_device_step():
    """Loss and one step's updated weights, to float32 rounding."""
    cfg = TransformerConfig(**{**CFG.__dict__, **OLMOE})
    batch = make_batch(jax.random.PRNGKey(1), cfg, 4, 32)
    params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg)
    want_params, _, want_loss = make_train_step(cfg)(params, opt_state, batch)
    mesh = make_mesh(MeshSpec(expert=4))
    with mesh:
        params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg, mesh)
        experts = params["layers"][0]["experts_gate"]
        assert experts.addressable_shards[0].data.shape[0] == 2
        got_params, _, got_loss = make_train_step(cfg, mesh)(
            params, opt_state, jax.device_put(batch, batch_sharding(mesh)))
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    # Adam's first step moves every weight by lr * sign(gradient): a
    # gradient of 1e-12 and one of -1e-12 part by 2 lr, so compare the
    # updates where the gradient is not rounding
    assert_trees_close(got_params, want_params, tol=1e-3)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.mean(jnp.abs(a - b) < 1e-6)),
        got_params, want_params)
    assert min(jax.tree_util.tree_leaves(moved)) > 0.99, moved


@pytest.mark.parametrize("feature", sorted(FEATURES))
def test_each_thing_the_block_gained_against_the_plain_reference(feature):
    """The dense-era defaults plus ONE of OLMoE's settings: the program's
    logits and loss equal the plain reference's, and differ from the
    program's without the setting (so the comparison reads it)."""
    block = olmoe_block()
    base = dict(norm_eps=1e-6, qk_norm=False, moe_norm_topk=True,
                moe_balance_all_k=False, moe_z_weight=0.0)
    job = {**CFG.__dict__, **base, feature: FEATURES[feature],
           "moe_aux_weight": 0.5}
    fields = set(TransformerConfig.__dataclass_fields__)
    cfg = TransformerConfig(**{k: v for k, v in job.items() if k in fields})
    without = TransformerConfig(**{**cfg.__dict__, feature: base[feature]})
    own = init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    assert ("q_scale" in own and "k_scale" in own) == cfg.qk_norm
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(7))
    # scales that are not 1, so that a norm left out or misplaced shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * 1.5 if "scale" in jax.tree_util.keystr(path)
        else w, params)
    tokens = make_batch(jax.random.PRNGKey(1), cfg, 2, 32)
    want, want_loss = block.forward(params, tokens, job, 8)
    with jax.default_matmul_precision("highest"):
        got = forward(params, tokens, cfg)[:, -8:]
        got_loss = float(loss_fn(params, tokens, cfg))
        other = forward(params, tokens, without)[:, -8:]
        other_loss = float(loss_fn(params, tokens, without))
    assert block.rel_rms(got, want) < 1e-5
    assert abs(got_loss - float(want_loss)) < 1e-5
    assert (block.rel_rms(other, want) > 1e-4
            or abs(other_loss - float(want_loss)) > 1e-4)
