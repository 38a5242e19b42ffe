"""A job of one-mixer blocks (Nemotron-H's): a Mamba-2 state-space block
(dynolog_tpu/models/mamba2.py), ReLU^2 experts under a sigmoid router with a
selection bias and a shared expert (models/moe.py), attention of many query
heads on few key/value heads through the grouped flash kernels
(ops/flash_attention.py; their compile for the chip at 32 on 2 heads of 128
is a case of tests/test_deepseek_v2.py's, the one file in which a worker
describes the topology), a block being ONE mixer (models/transformer.py
`block_types`), and the product's account of a step whose blocks are not
pairs (dynolog_tpu/trace.py `scopes`; diagnose.py).

The program is held to the plain reference of the benchmark's module
(perfbench/nemotron_h_block.py, loaded by path: it imports nothing of
dynolog_tpu), whose recurrence runs token by token. CPU, seeded weights,
float32 under `highest` unless a case says otherwise. Tolerances: both sides
compute the same float32 sums in another order (the program sums a chunk's
positions under a decay matrix and hands a state from chunk to chunk, the
reference carries the state over every position; the reference sums every
held expert under gates that are 0, the program the chosen ones), so outputs
of order 1 agree to a few float32 roundings (2e-5) and gradients, sums over
256 tokens, to 1e-4. bfloat16 anywhere float32 is stated moves an output by
1e-2 and fails each by three orders."""

import contextlib
import dataclasses
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dynolog_tpu import diagnose, trace
from dynolog_tpu.models import mamba2, moe
from dynolog_tpu.models.train import make_train_state, make_train_step
from dynolog_tpu.models.transformer import (
    TransformerConfig, _rmsnorm, _softmax_attention, forward, init_params,
    loss_fn)
from dynolog_tpu.ops.flash_attention import (
    flash_attention, reference_attention)

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "perfbench"))
import xspace_fixture as xf  # noqa: E402
from test_deepseek_v2 import _close, _module, _step_ops  # noqa: E402

PATTERN = ("mamba2", "moe", "mamba2", "moe", "mamba2", "attention", "moe")
# Nemotron-3-Nano's shape in small: its first period MEMEM*E; Mamba-2 of 8
# heads of 8 in 2 groups, state 16, chunks of 32; 16 experts of which a chip
# holds 2 (an eighth, as the cell's chip does), 6 a token, a shared expert of
# twice the width; 8 query heads of 16 on 2 key/value heads
TOY = dict(vocab_size=512, d_model=64, n_layers=7, n_heads=8, n_kv_heads=2,
           attn_head_dim=16, d_ff=96, max_seq_len=4096, rope_theta=None,
           norm_eps=1e-5, dtype="float32", attn_impl="reference",
           block_types=PATTERN, ssm_heads=8, ssm_head_dim=8, ssm_state=16,
           ssm_groups=2, ssm_conv_kernel=4, ssm_chunk=32, mlp_act="relu2",
           n_experts=16, n_experts_held=2, first_expert_held=4,
           moe_top_k=6, moe_norm_topk=True, moe_d_ff=32, n_shared_experts=1,
           moe_shared_d_ff=64, moe_score="sigmoid", moe_select_bias=True,
           moe_gate_scale=2.5, moe_aux_weight=0.0, moe_z_weight=0.0)


@pytest.fixture(scope="module")
def block():
    return _module("nemotron_h_block.py")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _job(**over) -> dict:
    return {**TOY, "block_types": list(PATTERN), **over}


def _cfg(**over) -> TransformerConfig:
    return TransformerConfig(**{**TOY, **over})


def _dims(cfg):
    return cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state


# -- the state-space block -----------------------------------------------


def _mamba_layer(cfg, key=3):
    """A block's weights with nothing at its neutral value, and heads whose
    decay a position runs from nearly 1 (A e^-8 at a step near 0.05) to
    nearly 0 (A e^4.5)."""
    k = jax.random.split(jax.random.PRNGKey(key), 5)
    layer = mamba2.init_mamba2_layer(k[0], cfg)
    layer["ssm_scale"] = 1.0 + 0.1 * jax.random.normal(k[1], (cfg.d_model,))
    layer["ssm_norm_scale"] = 1.0 + 0.1 * jax.random.normal(
        k[2], layer["ssm_norm_scale"].shape)
    layer["ssm_d"] = 1.0 + 0.1 * jax.random.normal(k[3], (cfg.ssm_heads,))
    layer["ssm_a_log"] = jnp.linspace(-8.0, 4.5, cfg.ssm_heads)
    layer["ssm_dt_bias"] = jnp.full((cfg.ssm_heads,), -3.0) + 0.1 * (
        jax.random.normal(k[4], (cfg.ssm_heads,)))
    return layer


def test_the_mamba2_block_and_its_gradients_equal_the_token_by_token_reference(
        block):
    cfg = _cfg()
    layer = _mamba_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, cfg.d_model))
    weight = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def plain(layer, x):
        return jax.vmap(lambda row: block.mamba2_block(
            layer, row, _dims(cfg), cfg.norm_eps))(x)

    def program(layer, x):  # four chunks of 32
        h = _rmsnorm(x, layer["ssm_scale"], cfg.norm_eps)
        return x + mamba2.mamba2_mixer(layer, h, cfg)

    _close(program(layer, x), plain(layer, x), 2e-5)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(program(*a) * weight), (0, 1)))(layer, x)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(plain(*a) * weight), (0, 1)))(layer, x)
    assert set(got[0]) == set(layer)  # every weight has a gradient
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got[0].values())
    _close(got, want, 1e-4)
    # the decays that were asked for are among the heads
    step = jax.nn.softplus(layer["ssm_dt_bias"])
    decay = np.exp(-np.asarray(step * jnp.exp(layer["ssm_a_log"])))
    assert decay.max() > 0.9999 and decay.min() < 0.1


def test_the_state_handed_on_is_the_state_computed_whole(block):
    """One chunk of 64 against the same sequence in two of 32, and in two
    calls the second of which is handed the first's state: the outputs and
    the last state are the reference's, whichever way it is cut."""
    b, s, h, p, g, n = 2, 64, 8, 8, 2, 16
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (b, s, h, p))
    delta = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 1.0)
    a = -jnp.exp(jnp.linspace(-7.0, 4.0, h))
    b_in = jax.random.normal(k[2], (b, s, g, n))
    c_in = jax.random.normal(k[3], (b, s, g, n))
    want_y, want_state = jax.vmap(
        lambda *t: block.state_space(*t[:2], a, *t[2:], jnp.zeros(h)))(
            x, delta, b_in, c_in)
    for chunk in (64, 32, 16):
        y, state = mamba2.chunked_state_space(x, delta, a, b_in, c_in, chunk)
        _close((y, state), (want_y, want_state), 2e-5)
    first, handed = mamba2.chunked_state_space(
        x[:, :32], delta[:, :32], a, b_in[:, :32], c_in[:, :32], 32)
    second, state = mamba2.chunked_state_space(
        x[:, 32:], delta[:, 32:], a, b_in[:, 32:], c_in[:, 32:], 32, handed)
    _close((jnp.concatenate([first, second], axis=1), state),
           (want_y, want_state), 2e-5)
    assert float(jnp.max(jnp.abs(handed))) > 0.1  # it is no state of zeros
    with pytest.raises(ValueError, match="whole number of chunks"):
        mamba2.chunked_state_space(x, delta, a, b_in, c_in, 48)


def test_the_convolution_is_linear_attentions_with_a_bias(block):
    from dynolog_tpu.models.linear_attention import _causal_conv

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 32, 24))
    w = jax.random.normal(k[1], (4, 24))
    bias = jax.random.normal(k[2], (24,))
    _close(_causal_conv(x, w, bias), _causal_conv(x, w) + bias, 1e-6)
    _close(_causal_conv(x, w, bias),
           jax.vmap(lambda row: block.causal_conv(row, w, bias))(x), 1e-6)
    # causal: a position's output reads nothing after it
    later = x.at[:, 20:].set(0.0)
    _close(_causal_conv(later, w, bias)[:, :20],
           _causal_conv(x, w, bias)[:, :20], 0)


# -- the grouped kernels -------------------------------------------------


@pytest.mark.parametrize("heads, kv_heads", [(2, 2), (8, 2), (16, 1)],
                         ids=["group1", "group4", "group16"])
def test_the_grouped_kernels_equal_plain_attention(heads, kv_heads):
    """Forward, dq, dk and dv (interpret mode) against plain attention with
    k and v repeated to the query heads. Both compute in float32; the
    kernels' online softmax adds in blocks, so an output differs by a few
    roundings (2e-5); dk and dv are sums over a group's heads (1e-4, 4e-4
    at sixteen)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    b, s, d = 2, 256, 32
    q = jax.random.normal(keys[0], (b, s, heads, d))
    k = jax.random.normal(keys[1], (b, s, kv_heads, d))
    v = jax.random.normal(keys[2], (b, s, kv_heads, d))
    weight = jax.random.normal(keys[3], (b, s, heads, d))

    def kernel(q, k, v):
        return flash_attention(q, k, v, True, 128, 128)

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=True)

    with pltpu.force_tpu_interpret_mode():
        out = kernel(q, k, v)
        grads = jax.grad(
            lambda *a: jnp.sum(kernel(*a) * weight), (0, 1, 2))(q, k, v)
    assert out.shape == q.shape
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    _close(out, plain(q, k, v), 2e-5)
    _close(grads, jax.grad(
        lambda *a: jnp.sum(plain(*a) * weight), (0, 1, 2))(q, k, v),
        4e-4 if heads // kv_heads == 16 else 1e-4)
    # a query head reads the key/value head of ITS group: with the groups'
    # keys and values swapped the output is another
    if kv_heads > 1:
        assert float(jnp.max(jnp.abs(
            plain(q, k[:, :, ::-1], v[:, :, ::-1]) - out))) > 0.1


def test_reference_attention_repeats_and_ring_refuses_grouped_heads():
    cfg = _cfg()
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (1, 64, 8, 16))
    key, value = (jax.random.normal(k[i], (1, 64, 2, 16)) for i in (1, 2))
    _close(_softmax_attention(q, key, value, cfg),
           reference_attention(q, key, value, causal=True), 1e-6)
    with pytest.raises(ValueError, match="nor grouped heads"):
        _softmax_attention(q, key, value, _cfg(attn_impl="ring"), mesh=object())
    with pytest.raises(ValueError, match="do not divide into groups"):
        _cfg(n_kv_heads=3)


# -- the experts ---------------------------------------------------------


def test_relu2_experts_under_a_sigmoid_router_equal_the_plain_reference(block):
    cfg = _cfg(n_experts_held=0, first_expert_held=0)
    layer = moe.init_moe_layer(jax.random.PRNGKey(1), cfg)
    assert set(layer) == {"router", "router_bias", "experts_up",
                          "experts_down", "shared_up", "shared_down"}
    layer["mlp_scale"] = jnp.ones((64,))
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
    weight = jax.random.normal(jax.random.PRNGKey(3), h.shape)

    def plain(layer, h):
        flat = h.reshape(-1, 64)
        return (block.routed(layer, flat, 6, 2.5, 0) + block._relu2(
            flat, layer["shared_up"], layer["shared_down"])).reshape(h.shape)

    def program(layer, h):
        return moe.moe_mlp(layer, h, cfg)[0]

    _close(program(layer, h), plain(layer, h), 2e-5)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * weight), (0, 1))(layer, h)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1))(layer, h)
    _close(got, want, 1e-4)
    # no gradient reaches the selection bias, on either side
    assert float(jnp.max(jnp.abs(got[0]["router_bias"]))) == 0.0
    # a token's six gates add up to the scale
    gates, chosen = block.gates(layer, h.reshape(-1, 64), 6, 2.5)
    _close(jnp.sum(gates, axis=1), jnp.full((256,), 2.5), 1e-5)
    assert int(jnp.sum(gates > 0, axis=1).max()) == 6


def test_the_selection_bias_moves_the_choice_and_not_the_gate(block):
    cfg = _cfg(n_experts_held=0, first_expert_held=0, moe_norm_topk=False)
    layer = moe.init_moe_layer(jax.random.PRNGKey(1), cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (256, 64))
    scores = jax.nn.sigmoid(h @ layer["router"])
    plain_gates, plain_chosen, _, _ = moe._route(
        layer["router"], None, h, cfg, None, 2)
    # expert 3 preferred by more than any score can differ, 5 shunned
    bias = jnp.zeros((16,)).at[3].set(2.0).at[5].set(-2.0)
    gates, chosen, _, _ = moe._route(layer["router"], bias, h, cfg, None, 2)
    assert bool(jnp.all(jnp.any(chosen == 3, axis=1)))
    assert not bool(jnp.any(chosen == 5))
    assert bool(jnp.any(plain_chosen == 5))
    assert not bool(jnp.all(jnp.any(plain_chosen == 3, axis=1)))
    # the gate of a chosen expert is its score (times the scale), not its
    # score plus the bias
    _close(gates, 2.5 * jnp.take_along_axis(scores, chosen, axis=1), 1e-6)
    _close(plain_gates,
           2.5 * jnp.take_along_axis(scores, plain_chosen, axis=1), 1e-6)
    # the layer reads the bias its weights hold, and so does the reference
    layer["router_bias"] = bias
    y = moe.moe_mlp(layer, h.reshape(2, 128, 64), cfg)[0].reshape(-1, 64)
    # (the reference normalises, as the source does: held to it above)
    ref_gates, ref_chosen = block.gates(layer, h, 6, 2.5)
    assert bool(jnp.all(jnp.sort(ref_chosen, axis=1)
                        == jnp.sort(chosen, axis=1)))
    zeroed = dict(layer, router_bias=jnp.zeros((16,)))
    assert float(jnp.max(jnp.abs(
        y - moe.moe_mlp(zeroed, h.reshape(2, 128, 64), cfg)[0].reshape(
            -1, 64)))) > 1e-3


@pytest.mark.parametrize("preferred, copies_held", [
    (range(0, 6), 4 * 256), (range(8, 14), 0)], ids=["crowded", "empty"])
def test_a_held_share_takes_its_groups_as_they_fall(
        block, preferred, copies_held):
    """One path for a held share, whatever the routing: the grouped products
    run over the groups as they fall. Where every token's six choices are
    the same six experts, the four held ones get a copy of every token each;
    where none is held the share adds nothing and its gradients are zeros,
    not what the buffers past the groups held."""
    cfg = _cfg(n_experts_held=4, first_expert_held=0)
    layer = moe.init_moe_layer(jax.random.PRNGKey(1), cfg)
    layer["router_bias"] = jnp.zeros((16,)).at[jnp.array(preferred)].set(2.0)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
    weight = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    chosen = block.gates(layer, h.reshape(-1, 64), 6, 2.5)[1]
    assert int(jnp.sum(chosen < 4)) == copies_held

    def plain(layer, h):
        flat = h.reshape(-1, 64)
        return (block.routed(layer, flat, 6, 2.5, 0) + block._relu2(
            flat, layer["shared_up"], layer["shared_down"])).reshape(h.shape)

    def program(layer, h):
        return moe.moe_mlp(layer, h, cfg)[0]

    _close(program(layer, h), plain(layer, h), 2e-5)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * weight), (0, 1))(layer, h)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1))(layer, h)
    _close(got, want, 1e-4)
    if not copies_held:
        assert float(jnp.max(jnp.abs(got[0]["experts_up"]))) == 0.0
        assert float(jnp.max(jnp.abs(got[0]["experts_down"]))) == 0.0


def test_a_plain_relu2_mlp_block_equals_the_reference(block):
    """Nemotron-H's dense siblings interleave plain MLP blocks ("-" in the
    source's pattern): two matrices, ReLU^2 between."""
    over = dict(block_types=("mamba2", "mlp", "attention", "mlp"), n_layers=4,
                n_experts=0, n_experts_held=0, first_expert_held=0)
    job = {**_job(**over), "block_types": list(over["block_types"])}
    cfg = _cfg(**over)
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(3))
    assert set(params["layers"][1]) == {"mlp_scale", "w_up", "w_down"}
    assert (jax.tree_util.tree_map(lambda a: a.shape, params)
            == jax.tree_util.tree_map(
                lambda a: a.shape, jax.eval_shape(
                    lambda k: init_params(k, cfg), jax.random.PRNGKey(0))))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 512)
    want, want_loss = block.forward(params, tokens, job, 64, undecided_gap=0)
    _close(forward(params, tokens, cfg), want, 2e-5)
    assert abs(float(loss_fn(params, tokens, cfg)) - float(want_loss)) < 1e-5


# -- the model whole -----------------------------------------------------


def test_the_programs_weights_are_laid_out_as_the_modules(block):
    cfg = _cfg()
    own = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: block.init_weights(k, _job()), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), own)
            == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), theirs))
    # a block holds ONE mixer and one norm
    kinds = [("ssm_in" in layer, "router" in layer, "wq" in layer)
             for layer in own["layers"]]
    assert kinds == [(True, False, False), (False, True, False)] * 2 + [
        (True, False, False), (False, False, True), (False, True, False)]
    assert all(sum(name.endswith("_scale") and name != "ssm_norm_scale"
                   for name in layer) == 1 for layer in own["layers"])
    m, e, a = (own["layers"][i] for i in (0, 1, 5))
    assert m["ssm_in"].shape == (64, 64 + (64 + 2 * 2 * 16) + 8)
    assert m["ssm_conv"].shape == (4, 64 + 2 * 2 * 16)
    assert e["router"].shape == (64, 16)  # every expert is scored
    assert e["experts_up"].shape == (2, 64, 32)  # two are held
    assert "experts_gate" not in e and "shared_gate" not in e
    assert e["shared_up"].shape == (64, 64)
    assert a["wq"].shape == (64, 8 * 16) and a["wk"].shape == (64, 2 * 16)
    assert cfg.n_sparse_layers == 3 and cfg.head_dim == 16
    assert [cfg.mixers(i) for i in (0, 1, 5)] == [
        ("mamba2",), ("moe",), ("attention",)]
    # a job that states no kinds is pairs, as it was
    assert TransformerConfig(n_experts=4, first_dense_layers=1).mixers(0) == (
        "attention", "mlp")
    assert TransformerConfig(n_experts=4, attn_type="mla").mixers(1) == (
        "mla", "moe")
    with pytest.raises(ValueError, match="block_types"):
        _cfg(block_types=PATTERN[:-1])
    with pytest.raises(ValueError, match="block_types"):
        _cfg(block_types=PATTERN[:-1] + ("conv",))
    with pytest.raises(ValueError, match="mamba2 block"):
        _cfg(ssm_groups=3)
    with pytest.raises(ValueError, match="mlp_act"):
        _cfg(mlp_act="gelu")
    with pytest.raises(ValueError, match="moe_score"):
        _cfg(moe_score="tanh")
    assert hash(_cfg(block_types=list(PATTERN))) == hash(cfg)  # from JSON


def test_forward_loss_and_gradients_equal_the_plain_reference(block):
    job, cfg = _job(), _cfg()
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(11))
    # a bias that is not zero, so that the choice it moves is compared
    for layer in params["layers"]:
        if "router_bias" in layer:
            layer["router_bias"] = 0.05 * jax.random.normal(
                jax.random.PRNGKey(13), (16,))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 128), 0, 512)
    want, want_loss = block.forward(
        params, tokens, job, 128, undecided_gap=0)  # every position
    _close(forward(params, tokens, cfg), want, 2e-5)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn), static_argnums=2)(
        params, tokens, cfg)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    want_grads = jax.jit(jax.grad(
        lambda p: block.forward(p, tokens, job, 1)[1]))(params)
    _close(grads, want_grads, 1e-4)
    # the flash path (interpret mode) computes the same
    with pltpu.force_tpu_interpret_mode():
        _close(forward(params, tokens, _cfg(attn_impl="flash")), want, 2e-5)
    # the module refuses what its block does not have
    with pytest.raises(ValueError, match="balancing"):
        block.forward(params, tokens, _job(moe_aux_weight=0.01), 1)
    with pytest.raises(ValueError, match="rope_theta"):
        block.forward(params, tokens, _job(rope_theta=10000.0), 1)


def test_the_eight_shares_of_a_sparse_block_add_up_to_the_uncut_reference(
        block):
    """Eight chips hold 2 of 16 experts each. What each computes of a
    block's output (the routed part its own experts give, plus the shared
    expert, which every chip computes alike and which counts once) adds up
    to what the reference gives for the block with every expert held."""
    whole_job = _job(n_experts_held=0, first_expert_held=0)
    whole = jax.jit(lambda k: block.init_weights(k, whole_job))(
        jax.random.PRNGKey(21))["layers"][1]
    whole["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(23), (16,))
    x = jax.random.normal(jax.random.PRNGKey(22), (2 * 128, 64))
    uncut = block.sparse_block(whole, x, 6, 2.5, 0, 1e-5)
    h = _rmsnorm(x, whole["mlp_scale"], 1e-5)
    shared = block._relu2(h, whole["shared_up"], whole["shared_down"])
    total = x + shared  # the residual and the shared expert, once
    chosen = block.gates(whole, h, 6, 2.5)[1]
    for first in range(0, 16, 2):
        cfg = _cfg(first_expert_held=first)
        share = {**whole, **{name: whole[name][first:first + 2] for name in (
            "experts_up", "experts_down")}}
        y, _, _ = moe.moe_mlp(share, h.reshape(2, 128, 64), cfg)
        routed_here = y.reshape(-1, 64) - shared
        # the reference given the same share says the same
        _close(routed_here, block.routed(share, h, 6, 2.5, first), 2e-5)
        # a token none of whose six choices fall here gets nothing from here
        absent = ~jnp.any((chosen >= first) & (chosen < first + 2), axis=1)
        assert int(absent.sum()) > 0
        assert float(jnp.max(jnp.abs(routed_here[absent]))) == 0.0
        total = total + routed_here
    _close(total, uncut, 2e-5)


# Check J's tolerance at a toy size wide enough to be steady (hidden 256).
# Over the positions the reference decided bfloat16 reads 0.0061-0.0069 here
# (0.006-0.017 over every position, by the seed) and the float8 control
# 0.049-0.068; on the chip at the published widths they read 0.0056-0.0060
# and 0.0307-0.0386, and the module's limit (0.0136) is their geometric
# middle.
WIDER = dict(d_model=256, n_heads=8, n_kv_heads=2, attn_head_dim=32,
             ssm_heads=8, ssm_head_dim=32, ssm_state=32, moe_d_ff=64,
             moe_shared_d_ff=128)


@pytest.mark.parametrize("seed", [5, 6])
def test_bfloat16_stays_inside_the_modules_limit_and_float8_does_not(
        block, seed):
    job = _job(dtype="bfloat16", **WIDER)
    cfg = _cfg(dtype="bfloat16", **WIDER)
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    want, want_loss = block.forward(params, tokens, job, 16)
    with jax.default_matmul_precision("default"):
        sound = block.rel_rms(forward(params, tokens, cfg)[:, -16:], want)
        loss = float(loss_fn(params, tokens, cfg))
    control = block.rel_rms(
        block.forward(params, tokens, job, 16, rounding=block.lower)[0], want)
    assert sound <= block.J_LOGIT_REL_RMS_LIMIT < control
    assert control > 3 * sound
    assert abs(loss - float(want_loss)) <= block.J_LOSS_ABS_LIMIT


def test_check_j_is_over_the_positions_the_reference_decided(block):
    """A token whose sixth and seventh scores lie closer than the stream's
    rounding may choose either way in bfloat16, which says nothing of the
    program's precision: the reference marks such positions by NaN logits,
    `rel_rms` leaves them out, and a NaN of the PROGRAM's still fails."""
    job = _job()
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 128), 0, 512)
    whole = block.forward(params, tokens, job, 64, undecided_gap=0)[0]
    assert not bool(jnp.any(jnp.isnan(whole)))
    gap = 0.02  # wide enough to leave some of these 128 positions out
    marked = block.forward(params, tokens, job, 64, undecided_gap=gap)[0]
    left_out = jnp.isnan(marked[..., 0])
    assert 0 < int(left_out.sum()) < left_out.size
    # whole rows, and the others untouched
    assert bool(jnp.all(jnp.isnan(marked) == left_out[..., None]))
    _close(marked[~left_out], whole[~left_out], 0)
    # they are the positions undecided at one of the three expert blocks,
    # by the scores with the bias, as the choice is made
    x = params["embedding"][tokens]
    want_out = jnp.zeros((2, 64), bool)
    for kind, layer in zip(PATTERN, params["layers"]):
        if kind == "moe":
            h = _rmsnorm(x[:, -64:], layer["mlp_scale"], 1e-5)
            top = jax.lax.top_k(jax.nn.sigmoid(h @ layer["router"])
                                + layer["router_bias"], 7)[0]
            want_out |= top[..., 5] - top[..., 6] < gap
        x = block._block(layer, x, kind, (8, 8, 2, 16), (8, 2, 16), 1e-5, 6,
                         2.5, 4, None)
    assert bool(jnp.all(left_out == want_out))
    # the control is not marked: it is compared where the reference decided
    low = block.forward(params, tokens, job, 64, rounding=block.lower)[0]
    assert not bool(jnp.any(jnp.isnan(low)))
    # an error in a position left out is not read, one in a position that
    # counts is, and a NaN there makes the reading fail every limit
    there, away = jnp.argwhere(~left_out)[0], jnp.argwhere(left_out)[0]
    assert block.rel_rms(whole.at[tuple(away)].add(1.0), marked) == 0.0
    assert block.rel_rms(whole.at[tuple(there)].add(1.0), marked) > 0.0
    broken = block.rel_rms(whole.at[tuple(there)].set(jnp.nan), marked)
    assert not broken <= block.J_LOGIT_REL_RMS_LIMIT
    # the module's own gap: one step of bfloat16 below 1
    assert block.UNDECIDED_GAP == 2.0 ** -8


def test_three_steps_of_the_train_step_lower_the_loss():
    cfg = _cfg()
    params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg, lr=1e-2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[2] < losses[1] < losses[0]
    # the selection bias is no one's to train: no gradient, so it stays 0
    assert float(jnp.max(jnp.abs(params["layers"][1]["router_bias"]))) == 0.0


# -- over a mesh, and where it is refused --------------------------------


@pytest.mark.parametrize("axes", [{"expert": 4}, {"data": 2, "model": 2}])
def test_every_new_leaf_has_a_rule_and_the_mesh_computes_the_same(axes):
    from jax.sharding import PartitionSpec as P

    from dynolog_tpu.parallel.sharding import (
        PARAM_RULES, MeshSpec, batch_sharding, make_mesh, shard_params)

    cfg = _cfg(n_experts_held=0, first_expert_held=0)  # the mesh divides them
    mesh = make_mesh(MeshSpec(**axes), jax.devices()[:4])
    params = init_params(jax.random.PRNGKey(0), cfg)
    shardings = shard_params(params, mesh)
    m, e, a = ({name: s.spec for name, s in shardings["layers"][i].items()}
               for i in (0, 1, 5))
    assert m["ssm_in"] == P(None, "model") and m["ssm_out"] == P("model", None)
    assert m["ssm_conv"] == m["ssm_conv_bias"] == m["ssm_a_log"] == P()
    assert m["ssm_dt_bias"] == m["ssm_d"] == P()
    assert m["ssm_scale"] == m["ssm_norm_scale"] == P(None)
    assert e["router"] == e["router_bias"] == P()
    assert e["experts_up"] == P("expert", None, "model")
    assert e["experts_down"] == P("expert", "model", None)
    assert e["shared_up"] == P(None, "model")
    assert e["shared_down"] == P("model", None)
    assert a["wk"] == a["wv"] == P(None, "model")  # two heads over two chips
    # no leaf of the model falls to replication in silence
    assert all(any(name.endswith(rule) for rule in PARAM_RULES)
               for layer in params["layers"] for name in layer)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 512)
    want = loss_fn(params, tokens, cfg)
    got = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(
        jax.device_put(params, shardings),
        jax.device_put(tokens, batch_sharding(mesh)))
    assert abs(float(got) - float(want)) < 2e-5


def test_the_pipeline_refuses_the_job_aloud():
    from dynolog_tpu.parallel import pipeline
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(pipe=2), jax.devices()[:2])
    for cfg in (
            dataclasses.replace(
                _cfg(), block_types=("mamba2", "attention") * 2, n_layers=4,
                n_experts=0, n_experts_held=0, first_expert_held=0),
            TransformerConfig(n_heads=8, n_kv_heads=2),
            TransformerConfig(mlp_act="relu2")):
        with pytest.raises(AssertionError, match="one-mixer blocks"):
            pipeline.init_pipeline_params(jax.random.PRNGKey(0), cfg, mesh)
        with pytest.raises(AssertionError, match="grouped key/value heads"):
            pipeline.pipeline_loss(
                {}, jnp.zeros((2, 128), jnp.int32), cfg, mesh, 1)


# -- metadata only -------------------------------------------------------


def test_the_scopes_change_no_ops_name_or_count(monkeypatch):
    """`ssm.*` beside `attn`, `moe.*`, `embed`, `head`, `adam`: names in the
    ops' metadata and nothing else (tests/test_deepseek_v2.py holds the
    dense and the latent job to the same). And the state-space blocks leave
    no loop on the device (the state goes from chunk to chunk by one
    product): a step's loops are the expert blocks', which gather into
    their buffer a block of rows at a time."""
    cfg = _cfg()
    scoped = _step_ops(cfg)
    assert len(scoped) > 200 and any("fusion" in op for op in scoped)
    assert any(op.startswith("while") for op in scoped)
    no_experts = dataclasses.replace(
        cfg, block_types=("mamba2", "attention") * 2, n_layers=4,
        n_experts=0, n_experts_held=0, first_expert_held=0)
    assert not any(op.startswith("while") for op in _step_ops(no_experts))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _step_ops(cfg) == scoped


def test_the_jobs_ops_carry_the_scopes_of_their_blocks():
    """Every phase of the three kinds of block is on some op's path in the
    step as lowered: what a capture's `tf_op` will hold."""
    cfg = _cfg()
    params, opt_state = jax.eval_shape(
        lambda k: make_train_state(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = make_train_step(cfg).lower(params, opt_state, tokens).as_text(
        debug_info=True)
    for scope in ("ssm.project", "ssm.conv", "ssm.chunk", "ssm.state",
                  "ssm.out", "moe.route", "moe.dispatch", "moe.experts",
                  "moe.combine", "moe.shared", "attn", "embed", "head",
                  "adam"):
        assert re.search(rf"[/(]{re.escape(scope)}[/)]", text), scope


# -- which mechanism the time went to, where blocks are not pairs --------

# (op, its path, microseconds or what it holds): a step of one-mixer blocks
ONE_MIXER_STEP = (
    ("%fusion.1 = f32[8]{0} fusion(%a)",
     "jit(step)/jvp(embed)/gather:", 10),
    ("%fusion.2 = f32[8]{0} fusion(%b)",
     "jit(step)/jvp(ssm.project)/dot_general:", 40),
    ("%fusion.3 = f32[8]{0} fusion(%c)",
     "jit(step)/jvp(ssm.conv)/mul:", 10),
    ("%fusion.4 = f32[8]{0} fusion(%d)",
     "jit(step)/jvp(checkpoint)/ssm.chunk/bmghij,bmghjp->bmghip/"
     "dot_general:", 30),
    ("%fusion.5 = f32[8]{0} fusion(%e)",
     "jit(step)/transpose(jvp(checkpoint))/rematted_computation/ssm.state/"
     "bnmgh,bmghpq->bnghpq/dot_general:", 20),
    ("%fusion.6 = f32[8]{0} fusion(%f)",
     "jit(step)/transpose(jvp(ssm.out))/dot_general:", 25),
    ("%fusion.7 = f32[8]{0} fusion(%g)",
     "jit(step)/jvp(moe.route)/dot_general:", 5),
    ("%ragged-dot.8 = f32[8]{0} custom-call(%h)",
     "jit(step)/jvp(checkpoint)/moe.experts/ragged_dot:", 60),
    ("%fusion.9 = f32[8]{0} fusion(%i)",
     "jit(step)/jvp(moe.shared)/dot_general:", 15),
    ("%fusion.10 = f32[8]{0} fusion(%j)",
     "jit(step)/jvp(attn)/dot_general:", 20),
    ("%flash_attention_fwd.11 = f32[8]{0} custom-call(%k)",
     "jit(step)/jvp(flash_attention_fwd)/pallas_call:", 35),
    ("%fusion.12 = f32[8]{0} fusion(%l)", "jit(step)/adam/mul:", 12),
    ("%copy-start.13 = f32[8]{0} copy-start(%m)", None, 8),
)
WANT_SCOPES_US = {
    "embed": 10, "ssm.project": 40, "ssm.conv": 10, "ssm.chunk": 30,
    "ssm.state": 20, "ssm.out": 25, "moe.route": 5, "moe.experts": 60,
    "moe.shared": 15, "attn": 20, "flash_attention_fwd": 35, "adam": 12,
    trace.NO_SCOPE: 8}


def one_mixer_xspace(steps: int = 2, scale: dict | None = None) -> bytes:
    return xf.build_scoped_xspace(ONE_MIXER_STEP, trace.op_scope, steps, scale)


def _summary(steps, scale=None):
    return trace._summarize_planes(trace.summarize_xplane_bytes(
        one_mixer_xspace(steps, scale), group=False))


def test_the_scopes_of_one_mixer_blocks_add_up_to_the_busy_time():
    steps = 3
    summary = _summary(steps)
    [plane] = summary["planes"]
    got = {name: row["self_ms"] for name, row in plane["scopes"].items()}
    assert got == {name: pytest.approx(us * steps / 1e3)
                   for name, us in WANT_SCOPES_US.items()}
    busy_ms = sum(us for _, _, us in ONE_MIXER_STEP) * steps / 1e3
    assert sum(got.values()) == pytest.approx(busy_ms)
    assert sum(op["self_ms"] for op in summary["top_ops"]) == pytest.approx(
        busy_ms)
    assert sum(row["pct"] for row in plane["scopes"].values()) == (
        pytest.approx(100.0, abs=0.5))
    assert list(plane["scopes"])[0] == "moe.experts"  # ranked by self time
    # a kernel outside every scope goes by its own name
    assert trace.op_scope(
        "jit(step)/jvp(flash_attention_fwd)/pallas_call:") == (
            "flash_attention_fwd")


def test_the_products_scopes_equal_the_benchmarks_plain_reading(tmp_path):
    """perfbench/scope_ops.py reads the same stat through the wheel's
    protobuf binding and shares no code with trace.py; the new reader is
    its `ssm.` share."""
    import cells
    import scope_ops

    if scope_ops.binding() is None:
        pytest.skip("no wheel here ships xplane_pb2")
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(one_mixer_xspace(2))
    run = {"trace": {"path": str(path)}, "device": {"count": 1}}
    [plane] = trace._summarize_planes(trace.summarize_xplane_bytes(
        path.read_bytes()))["planes"]
    total = sum(row["self_ms"] for row in plane["scopes"].values())
    for prefix in ("ssm.", "moe.", "moe.shared", "attn"):
        want = sum(row["self_ms"] for name, row in plane["scopes"].items()
                   if name.startswith(prefix)) / total * 100.0
        assert scope_ops.scope_share_pct(run, prefix) == pytest.approx(want)
    reader = cells.load_readers()["xspan.ssm_scope_pct"]
    assert reader.read(run) == pytest.approx(100.0 * 125 / 290)
    assert reader.read({"device": {"count": 1}}) is None  # no trace: nothing


def test_the_cli_prints_the_scopes_of_a_state_space_block(tmp_path, capsys):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(one_mixer_xspace())
    assert trace.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert re.search(r"scope ssm\.chunk\s+2 events\s+0\.060 ms self", out)
    assert re.search(r"scope moe\.experts\s+2 events\s+0\.120 ms self", out)
    assert re.search(r"scope attn\s+2 events", out)


def test_diagnose_names_the_state_space_scope_that_grew():
    report = diagnose.diagnose(_summary(20), _summary(20, {"ssm.chunk": 1.5}))
    assert report["verdict"] == "regressed"
    first, second = report["findings"][:2]
    assert first["op"] == "fusion.4"  # which op
    assert second["kind"] == "scope_growth"  # which mechanism
    assert second["scope"] == "ssm.chunk"
    assert second["severity_pct"] == pytest.approx(50.0)
    assert second["impact_ms"] == pytest.approx(20 * 0.015)
    assert "ssm.chunk" in diagnose.format_report(report)
    assert report["scopes"][0]["scope"] == "ssm.chunk"
    clean = diagnose.diagnose(_summary(20), _summary(20))
    assert clean["verdict"] == "clean" and clean["findings"] == []
