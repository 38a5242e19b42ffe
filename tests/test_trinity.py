"""A job whose attention looks back a window in some layers and at every
position in others (Trinity-Mini's, `afmoe`): the three flash kernels under a
`window` (ops/flash_attention.py), a layer's kind deciding its window and its
position, per-head norms of q and k, a gated output, a norm before and after
every mixer, a scaled embedding (models/transformer.py), a third job on the
held share's aligned layout (models/moe.py), the refusals of ring
attention and the pipeline, and the product's account of a step that holds the
windowed kernels beside the plain ones (dynolog_tpu/trace.py `scopes`;
diagnose.py).

The program is held to the plain reference of the benchmark's module
(perfbench/afmoe_block.py, loaded by path: it imports nothing of
dynolog_tpu), whose attention writes a block of queries' scores out against
every key under the band mask. CPU, seeded weights, float32 under `highest`
unless a case says otherwise; the kernels run under
`pltpu.force_tpu_interpret_mode()`. Tolerances as tests/test_nemotron_h.py
gives them: both sides compute the same float32 sums in another order, so
outputs of order 1 agree to a few float32 roundings (2e-5) and gradients to
1e-4."""

import contextlib
import dataclasses
import math
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dynolog_tpu import diagnose, trace
from dynolog_tpu.models import moe
from dynolog_tpu.models.train import make_train_state, make_train_step
from dynolog_tpu.models.transformer import (
    POST_NORMS, TransformerConfig, _attention, _rmsnorm, _rope,
    _softmax_attention, forward, init_params, loss_fn)
from dynolog_tpu.ops.flash_attention import (
    band_mask, flash_attention, reference_attention)

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "perfbench"))
import xspace_fixture as xf  # noqa: E402
from test_deepseek_v2 import _close, _module, _step_ops  # noqa: E402

KINDS = ("sliding_attention",) * 4 + ("full_attention",)
# Trinity-Mini's shape in small: a dense layer and a period of four sparse
# ones, three windowed and one full; a window of 32 in sequences of 128; 8
# query heads of 16 on 2 key/value heads; 16 experts of which a chip holds 2
# (an eighth, as the cell's chip does), 4 a token, a shared expert
TOY = dict(vocab_size=512, d_model=64, n_layers=5, n_heads=8, n_kv_heads=2,
           attn_head_dim=16, d_ff=96, max_seq_len=4096, rope_theta=10000.0,
           norm_eps=1e-5, dtype="float32", attn_impl="reference",
           layer_types=KINDS, sliding_window=32,
           rope_layer_types=("sliding_attention",), qk_head_norm=True,
           attn_gate=True, post_norm=True, scale_embedding=True,
           first_dense_layers=1, mlp_act="swiglu", n_experts=16,
           n_experts_held=2, first_expert_held=4, moe_top_k=4,
           moe_norm_topk=True, moe_d_ff=32, n_shared_experts=1,
           moe_score="sigmoid", moe_select_bias=True, moe_gate_scale=2.826,
           moe_aux_weight=0.0, moe_z_weight=0.0)
HEADS = (8, 2, 16)


@pytest.fixture(scope="module")
def block():
    return _module("afmoe_block.py")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _job(**over) -> dict:
    return {**TOY, "layer_types": list(KINDS),
            "rope_layer_types": ["sliding_attention"], **over}


def _cfg(**over) -> TransformerConfig:
    return TransformerConfig(**{**TOY, **over})


def _weights(block, key, **over):
    job = _job(**over)
    return jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(key))


# -- the window in the three kernels -------------------------------------


def _qkv(seq, heads, kv_heads, d_qk, d_v, key=0):
    k = jax.random.split(jax.random.PRNGKey(key), 4)
    return (jax.random.normal(k[0], (1, seq, heads, d_qk)),
            jax.random.normal(k[1], (1, seq, kv_heads, d_qk)),
            jax.random.normal(k[2], (1, seq, kv_heads, d_v)),
            jax.random.normal(k[3], (1, seq, heads, d_v)))


def _flash_and_plain(q, k, v, weight, window, block_q, block_k):
    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q, block_k, None, window)

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=True, window=window)

    with pltpu.force_tpu_interpret_mode():
        got = (flash(q, k, v), *jax.grad(
            lambda *a: jnp.sum(flash(*a) * weight), (0, 1, 2))(q, k, v))
    want = (plain(q, k, v), *jax.grad(
        lambda *a: jnp.sum(plain(*a) * weight), (0, 1, 2))(q, k, v))
    return got, want


@pytest.mark.parametrize("window", [1, 17, 64, 100, 128, 200, 1000])
def test_the_windowed_kernels_equal_plain_attention_under_the_band(window):
    """Forward, dq, dk and dv at windows smaller than a block (17: a query
    block's band lies inside two tiles), equal to one (64), larger (100,
    128, 200: three or four tiles a query block, both edges masked) and
    larger than the sequence (1000: the plain causal result), and of one
    key (1: a query sees itself)."""
    q, k, v, weight = _qkv(256, 2, 2, 32, 32)
    got, want = _flash_and_plain(q, k, v, weight, window, 64, 64)
    _close(got, want, 2e-5)
    if window >= 256:  # the band holds every causal pair
        _close(want[0], reference_attention(q, k, v, causal=True), 0)
    if window == 1:  # softmax over one key: the value itself
        _close(got[0], v, 1e-6)


@pytest.mark.parametrize("block_q, block_k", [(64, 32), (32, 64), (128, 32)],
                         ids=["q64k32", "q32k64", "q128k32"])
@pytest.mark.parametrize("window", [40, 96])
def test_the_window_under_blocks_of_two_sizes(window, block_q, block_k):
    """`block_q != block_k` both ways: the first key block of a query block
    and the last query block of a key block are counted in the other's
    blocks."""
    q, k, v, weight = _qkv(256, 2, 2, 32, 32, key=1)
    got, want = _flash_and_plain(q, k, v, weight, window, block_q, block_k)
    _close(got, want, 2e-5)


@pytest.mark.parametrize("heads, kv_heads, d_qk, d_v", [
    (8, 1, 32, 32), (8, 8, 32, 32), (2, 2, 192, 128), (8, 2, 48, 32)],
    ids=["group8", "group1", "keys192", "group4-keys48"])
def test_the_window_under_grouped_heads_and_wide_keys(
        heads, kv_heads, d_qk, d_v):
    """Groups of 8 (the cell's: 32 on 4) and of 1, keys of 192 on values of
    128 (latent attention's), and both at once: dk and dv come back at the
    key/value heads, summed over a group inside the windowed dkv kernel."""
    q, k, v, weight = _qkv(256, heads, kv_heads, d_qk, d_v, key=2)
    got, want = _flash_and_plain(q, k, v, weight, 72, 64, 64)
    assert got[2].shape == k.shape and got[3].shape == v.shape
    _close(got, want, 2e-5)


def test_no_window_is_the_plain_causal_program_to_the_bit():
    """`window=None` visits the tiles a window as long as the sequence
    visits, in their order, and masks what it masks: the same float32 sums,
    bit for bit; and the three programs keep their names, as the windowed
    ones carry their own."""
    q, k, v, weight = _qkv(256, 4, 2, 32, 32, key=3)
    with pltpu.force_tpu_interpret_mode():
        def run(window, *blocks):
            def out(q, k, v):
                return flash_attention(q, k, v, True, *blocks, None, window)
            return (out(q, k, v), *jax.grad(
                lambda *a: jnp.sum(out(*a) * weight), (0, 1, 2))(q, k, v))

        for blocks in ((64, 64), (32, 64)):
            for got, want in zip(run(None, *blocks), run(256, *blocks)):
                assert bool(jnp.all(got == want))
        # and the default call, which states no window at all
        assert bool(jnp.all(
            flash_attention(q, k, v, True, 64, 64) == run(None, 64, 64)[0]))

    def names(window):
        text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True, 64, 64, None, window)),
            (0, 1, 2)))(q, k, v))
        return sorted(set(re.findall(r"flash_attention_\w+", text)))

    assert names(None) == ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                           "flash_attention_fwd"]
    assert names(100) == [
        "flash_attention_window_bwd_dkv", "flash_attention_window_bwd_dq",
        "flash_attention_window_fwd"]
    # a reader of the plain kernels matches a fragment of the name
    assert not any("flash_attention_fwd" in name or
                   "flash_attention_bwd" in name for name in names(100))
    with pytest.raises(ValueError, match="window 0"):
        flash_attention(q, k, v, True, 64, 64, None, 0)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, 64, 64, None, 8)


def test_the_plain_paths_take_the_window_as_a_band():
    mask = np.asarray(band_mask(6, 6, 3))
    assert mask.tolist() == [
        [i - 3 < j <= i for j in range(6)] for i in range(6)]
    assert np.asarray(band_mask(6, 6)).tolist() == np.tril(
        np.ones((6, 6), bool)).tolist()
    q, k, v, _ = _qkv(128, 8, 2, 16, 16, key=4)
    cfg = _cfg()
    got = _softmax_attention(q, k, v, cfg, window=32)
    _close(got, reference_attention(q, k, v, causal=True, window=32), 1e-6)
    # a query of a windowed layer does not see key i - 32
    moved = k.at[:, 0].add(5.0)
    _close(_softmax_attention(q, moved, v, cfg, window=32)[:, 32:],
           got[:, 32:], 0)
    assert float(jnp.max(jnp.abs(
        _softmax_attention(q, moved, v, cfg)[:, 32:]
        - _softmax_attention(q, k, v, cfg)[:, 32:]))) > 1e-4
    with pltpu.force_tpu_interpret_mode():
        _close(_softmax_attention(q, k, v, _cfg(attn_impl="flash"),
                                  window=32), got, 2e-5)


# -- attention: position by layer kind, per-head norms, the gate ---------


def _plain_attention(layer, x, *, window, theta, head_norm, gate, eps=1e-5):
    """The reference's equations with a switch on each of this block's
    three additions; all on, it is `afmoe_block.attention`."""
    b, s, _ = x.shape
    hq, hkv, dh = HEADS
    q = (x @ layer["wq"]).reshape(b, s, hq, dh)
    k = (x @ layer["wk"]).reshape(b, s, hkv, dh)
    v = (x @ layer["wv"]).reshape(b, s, hkv, dh)
    if head_norm:
        q = _rmsnorm(q, layer["q_head_scale"], eps)
        k = _rmsnorm(k, layer["k_head_scale"], eps)
    if theta is not None:
        at = jnp.broadcast_to(jnp.arange(s), (b, s))
        q, k = _rope(q, at, theta), _rope(k, at, theta)
    out = reference_attention(q, k, v, causal=True, window=window)
    out = out.reshape(b, s, hq * dh)
    if gate:
        out = out * jax.nn.sigmoid(x @ layer["wg"])
    return out @ layer["wo"]


def _attn_layer(key=5):
    cfg = _cfg()
    layer = init_params(jax.random.PRNGKey(key), cfg)["layers"][1]
    k = jax.random.split(jax.random.PRNGKey(key + 1), 2)
    layer["q_head_scale"] = 1.0 + 0.3 * jax.random.normal(k[0], (16,))
    layer["k_head_scale"] = 1.0 + 0.3 * jax.random.normal(k[1], (16,))
    return layer


@pytest.mark.parametrize("on", [
    (), ("head_norm",), ("gate",), ("head_norm", "gate")],
    ids=["neither", "head-norm", "gate", "both"])
@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_each_addition_to_attention_alone_equals_the_reference(
        block, kind, on):
    """The per-head norms and the gate, each with the other off, on a
    windowed (rotated) and on a full (unrotated) layer; with both on the
    plain equations are the module's own `attention`."""
    layer = _attn_layer()
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 128, 64))
    weight = jax.random.normal(jax.random.PRNGKey(8), x.shape)
    cfg = _cfg(qk_head_norm="head_norm" in on, attn_gate="gate" in on)
    i = KINDS.index(kind)
    window, theta = cfg.window(i), (10000.0 if cfg.rotary(i) else None)
    assert (window, theta) == {
        "sliding_attention": (32, 10000.0), "full_attention": (None, None)}[
            kind]
    positions = jnp.broadcast_to(jnp.arange(128), (2, 128))

    def program(layer, x):
        return _attention(layer, x, positions, cfg, None, window,
                          cfg.rotary(i))

    def plain(layer, x):
        return _plain_attention(
            layer, x, window=window, theta=theta,
            head_norm="head_norm" in on, gate="gate" in on)

    _close(program(layer, x), plain(layer, x), 2e-5)
    _close(jax.grad(lambda *a: jnp.sum(program(*a) * weight), (0, 1))(
        layer, x), jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1))(
            layer, x), 1e-4)
    if len(on) == 2:
        want = jax.vmap(lambda row: block.attention(
            layer, row, HEADS, window, theta, 1e-5))(x)
        _close(plain(layer, x), want, 2e-5)
    else:  # and the addition left out is not nothing
        both = _plain_attention(layer, x, window=window, theta=theta,
                                head_norm=True, gate=True)
        assert float(jnp.max(jnp.abs(both - plain(layer, x)))) > 1e-2


def test_rotary_is_on_the_sliding_layers_only():
    """Positions stretched to twice their distance (a shift alone leaves a
    rotated layer as it was: the rotation is relative): a sliding layer's
    output moves, a full layer's not at all."""
    cfg, layer = _cfg(), _attn_layer()
    assert [cfg.rotary(i) for i in range(5)] == [True] * 4 + [False]
    assert [cfg.window(i) for i in range(5)] == [32] * 4 + [None]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 128, 64))
    at = jnp.broadcast_to(jnp.arange(128), (2, 128))
    sliding = [_attention(layer, x, p, cfg, None, 32, cfg.rotary(0))
               for p in (at, 2 * at)]
    full = [_attention(layer, x, p, cfg, None, None, cfg.rotary(4))
            for p in (at, 2 * at)]
    assert float(jnp.max(jnp.abs(sliding[0] - sliding[1]))) > 1e-2
    assert bool(jnp.all(full[0] == full[1]))
    # a job that states no kinds rotates every layer, as it did
    plain = TransformerConfig(n_layers=2)
    assert [plain.rotary(i) for i in range(2)] == [True, True]
    assert [plain.window(i) for i in range(2)] == [None, None]
    assert not TransformerConfig(rope_theta=None).rotary(0)


# -- the norm after a mixer, and the scaled embedding --------------------


def _plain_dense_forward(params, tokens, cfg):
    """A dense model of this block by the reference's equations, with a
    switch on the post-norms and on the embedding's scale."""
    x = params["embedding"][tokens]
    if cfg.scale_embedding:
        x = x * math.sqrt(cfg.d_model)
    for i, layer in enumerate(params["layers"]):
        y = _plain_attention(
            layer, _rmsnorm(x, layer["attn_scale"], 1e-5),
            window=cfg.window(i), theta=10000.0 if cfg.rotary(i) else None,
            head_norm=cfg.qk_head_norm, gate=cfg.attn_gate)
        if cfg.post_norm:
            y = _rmsnorm(y, layer["attn_post_scale"], 1e-5)
        x = x + y
        h = _rmsnorm(x, layer["mlp_scale"], 1e-5)
        y = (jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])) @ (
            layer["w_down"])
        if cfg.post_norm:
            y = _rmsnorm(y, layer["mlp_post_scale"], 1e-5)
        x = x + y
    return _rmsnorm(x, params["final_scale"], 1e-5) @ params["w_out"]


@pytest.mark.parametrize("on", [
    {}, {"post_norm": True}, {"scale_embedding": True},
    {"post_norm": True, "scale_embedding": True}],
    ids=["neither", "post-norm", "scaled-embedding", "both"])
def test_the_post_norms_and_the_scaled_embedding_each_alone(on):
    cfg = _cfg(n_layers=2, layer_types=KINDS[3:], n_experts=0,
               n_experts_held=0, first_expert_held=0, first_dense_layers=0,
               **{"post_norm": False, "scale_embedding": False, **on})
    params = init_params(jax.random.PRNGKey(3), cfg)
    assert ("attn_post_scale" in params["layers"][0]) == cfg.post_norm
    # four norms a layer, each with a weight that is not 1
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 8))
    for layer in params["layers"]:
        for name in layer:
            if name.endswith("_scale") and layer[name].shape == (64,):
                layer[name] = 1.0 + 0.2 * jax.random.normal(next(keys), (64,))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 0, 512)
    want = _plain_dense_forward(params, tokens, cfg)
    _close(forward(params, tokens, cfg), want, 2e-5)
    if on:  # and it is not nothing
        off = dataclasses.replace(cfg, post_norm=False, scale_embedding=False)
        assert float(jnp.max(jnp.abs(
            _plain_dense_forward(params, tokens, off) - want))) > 1e-2


# -- the experts: a third job on the held share --------------------------


def _moe_layer(cfg, key=1, **over):
    layer = moe.init_moe_layer(jax.random.PRNGKey(key), cfg)
    layer["mlp_scale"] = jnp.ones((64,))
    return {**layer, **over}


def _plain_sparse(block, first=0):
    def plain(layer, h):
        return block.sparse_mlp(
            layer, h.reshape(-1, 64), 4, 2.826, first).reshape(h.shape)
    return plain


def test_swiglu_experts_under_a_sigmoid_router_equal_the_plain_reference(
        block):
    cfg = _cfg(n_experts_held=0, first_expert_held=0)
    layer = _moe_layer(cfg, router_bias=0.05 * jax.random.normal(
        jax.random.PRNGKey(9), (16,)))
    assert set(layer) == {
        "router", "router_bias", "experts_gate", "experts_up", "experts_down",
        "shared_gate", "shared_up", "shared_down", "mlp_scale"}
    assert layer["shared_up"].shape == (64, 32)  # one shared expert's width
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
    weight = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    plain = _plain_sparse(block)

    def program(layer, h):
        return moe.moe_mlp(layer, h, cfg)[0]

    _close(program(layer, h), plain(layer, h), 2e-5)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * weight), (0, 1))(layer, h)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1))(layer, h)
    _close(got, want, 1e-4)
    assert float(jnp.max(jnp.abs(got[0]["router_bias"]))) == 0.0
    # a token's four gates add up to the scale
    gates, _ = block.gates(layer, h.reshape(-1, 64), 4, 2.826)
    _close(jnp.sum(gates, axis=1), jnp.full((256,), 2.826), 1e-5)
    assert int(jnp.sum(gates > 0, axis=1).max()) == 4


@pytest.mark.parametrize("preferred, copies_held", [
    (range(0, 4), 4 * 256), (range(8, 12), 0)], ids=["crowded", "empty"])
def test_a_held_share_under_crowded_and_empty_routing(
        block, preferred, copies_held):
    """Where every token's four choices are the four held experts each gets
    a copy of every token; where none is held the share adds the shared
    expert alone and its experts' gradients are zeros."""
    cfg = _cfg(n_experts_held=4, first_expert_held=0)
    layer = _moe_layer(cfg, router_bias=jnp.zeros((16,)).at[
        jnp.array(preferred)].set(2.0))
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
    weight = jax.random.normal(jax.random.PRNGKey(3), h.shape)
    chosen = block.gates(layer, h.reshape(-1, 64), 4, 2.826)[1]
    assert int(jnp.sum(chosen < 4)) == copies_held
    plain = _plain_sparse(block)

    def program(layer, h):
        return moe.moe_mlp(layer, h, cfg)[0]

    _close(program(layer, h), plain(layer, h), 2e-5)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * weight), (0, 1))(layer, h)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), (0, 1))(layer, h)
    _close(got, want, 1e-4)
    if not copies_held:
        assert float(jnp.max(jnp.abs(got[0]["experts_up"]))) == 0.0
        assert float(jnp.max(jnp.abs(got[0]["experts_gate"]))) == 0.0


def test_the_eight_shares_of_a_sparse_layer_add_up_to_the_uncut_reference(
        block):
    """Eight chips hold 2 of 16 experts each. What each computes of a
    layer's MLP half before the post-norm (the routed part its own experts
    give, plus the shared expert, which every chip computes alike and which
    counts once) adds up to what the reference gives with every expert
    held."""
    whole = _weights(block, 21, n_experts_held=0, first_expert_held=0)[
        "layers"][1]
    whole["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(23), (16,))
    x = jax.random.normal(jax.random.PRNGKey(22), (2 * 128, 64))
    h = _rmsnorm(x, whole["mlp_scale"], 1e-5)
    uncut = block.sparse_mlp(whole, h, 4, 2.826, 0)
    shared = block._swiglu(h, whole["shared_gate"], whole["shared_up"],
                           whole["shared_down"])
    total = shared  # once
    chosen = block.gates(whole, h, 4, 2.826)[1]
    for first in range(0, 16, 2):
        cfg = _cfg(first_expert_held=first)
        share = {**whole, **{name: whole[name][first:first + 2] for name in (
            "experts_gate", "experts_up", "experts_down")}}
        y, _, _ = moe.moe_mlp(share, h.reshape(2, 128, 64), cfg)
        routed_here = y.reshape(-1, 64) - shared
        _close(routed_here, block.routed(share, h, 4, 2.826, first), 2e-5)
        absent = ~jnp.any((chosen >= first) & (chosen < first + 2), axis=1)
        assert int(absent.sum()) > 0
        assert float(jnp.max(jnp.abs(routed_here[absent]))) == 0.0
        total = total + routed_here
    _close(total, uncut, 2e-5)


# -- the model whole -----------------------------------------------------


def test_the_programs_weights_are_laid_out_as_the_modules(block):
    cfg = _cfg()
    own = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: block.init_weights(k, _job()), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), own)
            == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), theirs))
    dense, sparse = own["layers"][0], own["layers"][1]
    assert "w_gate" in dense and "router" not in dense
    assert "router" in sparse and "w_gate" not in sparse
    for layer in own["layers"]:  # four norms a layer, two a head
        assert {"attn_scale", "attn_post_scale", "mlp_scale",
                "mlp_post_scale"} <= set(layer)
        assert layer["q_head_scale"].shape == layer["k_head_scale"].shape == (
            16,)
        assert layer["wg"].shape == layer["wq"].shape == (64, 8 * 16)
        assert layer["wk"].shape == (64, 2 * 16)
    assert sparse["router"].shape == (64, 16)  # every expert is scored
    assert sparse["experts_up"].shape == (2, 64, 32)  # two are held
    assert [cfg.mixers(i) for i in (0, 1, 4)] == [
        ("attention", "mlp"), ("attention", "moe"), ("attention", "moe")]
    assert cfg.n_sparse_layers == 4 and cfg.head_dim == 16
    assert hash(_cfg(layer_types=list(KINDS),
                     rope_layer_types=["sliding_attention"])) == hash(cfg)
    with pytest.raises(ValueError, match="layer_types"):
        _cfg(layer_types=KINDS[:-1] + ("strided_attention",))
    with pytest.raises(ValueError, match="window of 1 or more"):
        _cfg(sliding_window=0)
    with pytest.raises(ValueError, match="multi-head attention"):
        _cfg(attn_type="mla", post_norm=False)
    with pytest.raises(ValueError, match="post_norm"):
        _cfg(attn_type="mla", layer_types=None)
    with pytest.raises(ValueError, match="post_norm"):
        _cfg(layer_types=("linear_attention",) * 5)
    assert set(POST_NORMS) == {"attention", "mlp", "moe"}


def test_forward_loss_and_gradients_equal_the_plain_reference(block):
    job, cfg = _job(), _cfg()
    params = _weights(block, 11)
    keys = iter(jax.random.split(jax.random.PRNGKey(13), 64))
    for layer in params["layers"]:  # nothing at a neutral value
        for name in layer:
            if name.endswith("_scale"):
                layer[name] = layer[name] * (1.0 + 0.2 * jax.random.normal(
                    next(keys), layer[name].shape))
        if "router_bias" in layer:
            layer["router_bias"] = 0.05 * jax.random.normal(
                next(keys), (16,))
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
    # the flash path (interpret mode; windowed and plain kernels) the same
    with pltpu.force_tpu_interpret_mode():
        _close(forward(params, tokens, _cfg(attn_impl="flash")), want, 2e-5)
    # the window is seen: the same weights without it compute another thing
    assert float(jnp.max(jnp.abs(forward(
        params, tokens, _cfg(layer_types=None, rope_layer_types=None))
        - want))) > 1e-2
    # the module refuses what its block does not have
    with pytest.raises(ValueError, match="balancing"):
        block.forward(params, tokens, _job(moe_aux_weight=0.01), 1)
    with pytest.raises(ValueError, match="post_norm"):
        block.forward(params, tokens, _job(post_norm=False), 1)


# Check J's tolerance at a toy size wide enough to be steady (hidden 256,
# sequences of 256 under a window of 64).
WIDER = dict(d_model=256, attn_head_dim=32, d_ff=384, moe_d_ff=64,
             sliding_window=64)


@pytest.mark.parametrize("seed", [5, 6])
def test_bfloat16_stays_inside_the_modules_limit_and_float8_does_not(
        block, seed):
    job = _job(dtype="bfloat16", **WIDER)
    cfg = _cfg(dtype="bfloat16", **WIDER)
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 512)
    want, want_loss = block.forward(params, tokens, job, 32)
    assert 4 < int(jnp.sum(~jnp.isnan(want[..., 0])))  # some are decided
    with jax.default_matmul_precision("default"):
        sound = block.rel_rms(forward(params, tokens, cfg)[:, -32:], want)
        loss = float(loss_fn(params, tokens, cfg))
    control = block.rel_rms(
        block.forward(params, tokens, job, 32, rounding=block.lower)[0], want)
    assert sound <= block.J_LOGIT_REL_RMS_LIMIT < control
    assert control > 3 * sound
    assert abs(loss - float(want_loss)) <= block.J_LOSS_ABS_LIMIT


def test_check_j_is_over_the_positions_the_reference_decided(block):
    job = _job()
    params = _weights(block, 11)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 128), 0, 512)
    whole = block.forward(params, tokens, job, 64, undecided_gap=0)[0]
    assert not bool(jnp.any(jnp.isnan(whole)))
    marked = block.forward(params, tokens, job, 64, undecided_gap=0.02)[0]
    left_out = jnp.isnan(marked[..., 0])
    assert 0 < int(left_out.sum()) < left_out.size
    assert bool(jnp.all(jnp.isnan(marked) == left_out[..., None]))
    _close(marked[~left_out], whole[~left_out], 0)
    # the control is not marked: it is compared where the reference decided
    low = block.forward(params, tokens, job, 64, rounding=block.lower)[0]
    assert not bool(jnp.any(jnp.isnan(low)))
    there, away = jnp.argwhere(~left_out)[0], jnp.argwhere(left_out)[0]
    assert block.rel_rms(whole.at[tuple(away)].add(1.0), marked) == 0.0
    assert block.rel_rms(whole.at[tuple(there)].add(1.0), marked) > 0.0
    broken = block.rel_rms(whole.at[tuple(there)].set(jnp.nan), marked)
    assert not broken <= block.J_LOGIT_REL_RMS_LIMIT
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
    for i in (0, 1):
        a = {name: s.spec for name, s in shardings["layers"][i].items()}
        assert a["wg"] == a["wq"] == P(None, "model")
        assert a["q_head_scale"] == a["k_head_scale"] == P(None)
        assert a["attn_post_scale"] == a["mlp_post_scale"] == P(None)
    # no leaf of the model falls to replication in silence
    assert all(any(name.endswith(rule) for rule in PARAM_RULES)
               for layer in params["layers"] for name in layer)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 512)
    want = loss_fn(params, tokens, cfg)
    got = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(
        jax.device_put(params, shardings),
        jax.device_put(tokens, batch_sharding(mesh)))
    assert abs(float(got) - float(want)) < 2e-5


def test_ring_attention_refuses_a_window_aloud():
    q, k, v, _ = _qkv(128, 8, 8, 16, 16)
    with pytest.raises(ValueError, match="sliding_attention layer's window"):
        _softmax_attention(q, k, v, _cfg(attn_impl="ring", n_kv_heads=0),
                           mesh=object(), window=32)
    from dynolog_tpu.parallel.ring_attention import ring_attention

    with pytest.raises(ValueError, match="window \\(32\\) is not run here"):
        ring_attention(q, k, v, object(), window=32)


@pytest.mark.parametrize("over", [
    dict(layer_types=("sliding_attention",) * 2, sliding_window=32),
    dict(layer_types=("sliding_attention", "full_attention"),
         sliding_window=10**6, rope_layer_types=("sliding_attention",)),
    dict(qk_head_norm=True), dict(attn_gate=True), dict(post_norm=True),
    dict(scale_embedding=True)],
    ids=["window", "position-by-kind", "head-norm", "gate", "post-norm",
         "scaled-embedding"])
def test_the_pipeline_refuses_the_job_aloud(over):
    from dynolog_tpu.parallel import pipeline
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(pipe=2), jax.devices()[:2])
    cfg = TransformerConfig(n_layers=2, **over)
    with pytest.raises(AssertionError, match="sliding_attention"):
        pipeline.init_pipeline_params(jax.random.PRNGKey(0), cfg, mesh)
    with pytest.raises(AssertionError, match="post-mixer norms"):
        pipeline.pipeline_loss(
            {}, jnp.zeros((2, 128), jnp.int32), cfg, mesh, 1)
    # the dense pair it supports is still taken
    pipeline._require_dense_pairs(TransformerConfig(n_layers=2))


# -- metadata only -------------------------------------------------------


def test_the_scopes_change_no_ops_name_or_count(monkeypatch):
    cfg = _cfg()
    scoped = _step_ops(cfg)
    assert len(scoped) > 200 and any("fusion" in op for op in scoped)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _step_ops(cfg) == scoped


def test_the_jobs_ops_carry_the_scopes_of_their_phases():
    """The gate, the per-head norms and the post-norms run under the scope
    of the phase they belong to: no scope of their own, and nothing of the
    step outside the scopes that were there."""
    cfg = _cfg(attn_impl="flash")
    params, opt_state = jax.eval_shape(
        lambda k: make_train_state(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    with pltpu.force_tpu_interpret_mode():
        text = make_train_step(cfg).lower(params, opt_state, tokens).as_text(
            debug_info=True)
    for scope in ("attn", "mlp", "moe.route", "moe.dispatch", "moe.experts",
                  "moe.combine", "moe.shared", "embed", "head", "adam"):
        assert re.search(rf"[/(]{re.escape(scope)}[/)]", text), scope
    # the gate's sigmoid and a post-norm's rsqrt, by the scope on their path
    assert re.search(r"jvp\(attn\)/logistic", text)
    assert re.search(r"jvp\(moe\.combine\)/rsqrt", text)
    assert re.search(r"jvp\(mlp\)/rsqrt", text)
    # a kernel outside every scope goes by its own name, windowed or not
    for kernel in ("flash_attention_window_fwd", "flash_attention_fwd"):
        assert trace.op_scope(
            f"jit(step)/jvp({kernel})/pallas_call:") == kernel


# -- which mechanism the time went to, where some layers are windowed ----

# (op, its path, microseconds): a step of four windowed layers and a full one
WINDOWED_STEP = (
    ("%fusion.1 = f32[8]{0} fusion(%a)", "jit(step)/jvp(embed)/gather:", 10),
    ("%fusion.2 = f32[8]{0} fusion(%b)",
     "jit(step)/jvp(attn)/dot_general:", 40),
    ("%flash_attention_window_fwd.3 = f32[8]{0} custom-call(%c)",
     "jit(step)/jvp(flash_attention_window_fwd)/pallas_call:", 30),
    ("%flash_attention_fwd.4 = f32[8]{0} custom-call(%d)",
     "jit(step)/jvp(flash_attention_fwd)/pallas_call:", 35),
    ("%fusion.5 = f32[8]{0} fusion(%e)", "jit(step)/jvp(attn)/logistic:", 5),
    ("%fusion.6 = f32[8]{0} fusion(%f)",
     "jit(step)/jvp(moe.route)/dot_general:", 5),
    ("%ragged-dot.7 = f32[8]{0} custom-call(%g)",
     "jit(step)/jvp(checkpoint)/moe.experts/ragged_dot:", 60),
    ("%fusion.8 = f32[8]{0} fusion(%h)",
     "jit(step)/jvp(moe.combine)/rsqrt:", 5),
    ("%flash_attention_window_bwd_dq.9 = f32[8]{0} custom-call(%i)",
     "jit(step)/transpose(jvp(flash_attention_window_bwd_dq))/pallas_call:",
     45),
    ("%flash_attention_window_bwd_dkv.10 = f32[8]{0} custom-call(%j)",
     "jit(step)/transpose(jvp(flash_attention_window_bwd_dkv))/pallas_call:",
     50),
    ("%fusion.11 = f32[8]{0} fusion(%k)", "jit(step)/adam/mul:", 12),
    ("%copy-start.12 = f32[8]{0} copy-start(%l)", None, 8),
)


def windowed_xspace(steps: int = 2, scale: dict | None = None) -> bytes:
    return xf.build_scoped_xspace(WINDOWED_STEP, trace.op_scope, steps, scale)


def _summary(steps, scale=None):
    return trace._summarize_planes(trace.summarize_xplane_bytes(
        windowed_xspace(steps, scale), group=False))


def test_the_windowed_kernels_scopes_add_up_to_the_busy_time():
    steps = 3
    summary = _summary(steps)
    [plane] = summary["planes"]
    got = {name: row["self_ms"] for name, row in plane["scopes"].items()}
    want = {}
    for _, path, us in WINDOWED_STEP:
        scope = trace.op_scope(path) if path else trace.NO_SCOPE
        want[scope] = want.get(scope, 0) + us
    # each kernel goes by its own name, as a capture on the chip has them
    # (my chip run, PR 48, call 2), the plain forward beside the windowed
    assert (want["flash_attention_window_fwd"],
            want["flash_attention_window_bwd_dq"],
            want["flash_attention_window_bwd_dkv"]) == (30, 45, 50)
    assert want["flash_attention_fwd"] == 35 and want["attn"] == 45
    assert got == {name: pytest.approx(us * steps / 1e3)
                   for name, us in want.items()}
    busy_ms = sum(us for _, _, us in WINDOWED_STEP) * steps / 1e3
    assert sum(got.values()) == pytest.approx(busy_ms)
    assert sum(op["self_ms"] for op in summary["top_ops"]) == pytest.approx(
        busy_ms)
    assert list(plane["scopes"])[0] == "moe.experts"  # ranked by self time


def test_the_products_scopes_equal_the_benchmarks_plain_reading(tmp_path):
    import cells
    import scope_ops

    if scope_ops.binding() is None:
        pytest.skip("no wheel here ships xplane_pb2")
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(windowed_xspace(2))
    run = {"trace": {"path": str(path)}, "device": {"count": 1}}
    [plane] = trace._summarize_planes(trace.summarize_xplane_bytes(
        path.read_bytes()))["planes"]
    total = sum(row["self_ms"] for row in plane["scopes"].values())
    for prefix in ("flash_attention_window_", "flash_attention_fwd", "moe.",
                   "attn"):
        want = sum(row["self_ms"] for name, row in plane["scopes"].items()
                   if name.startswith(prefix)) / total * 100.0
        assert scope_ops.scope_share_pct(run, prefix) == pytest.approx(want)
    reader = cells.load_readers()["xspan.attn_window_scope_pct"]
    assert reader.read(run) == pytest.approx(100.0 * 125 / 305)
    assert reader.read({"device": {"count": 1}}) is None  # no trace: nothing


def test_the_cli_prints_the_windowed_kernels_scope(tmp_path, capsys):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(windowed_xspace())
    assert trace.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert re.search(
        r"scope flash_attention_window_fwd\s+2 events\s+0\.060 ms self", out)
    assert re.search(
        r"scope flash_attention_window_bwd_dkv\s+2 events\s+0\.100 ms self",
        out)
    assert re.search(r"scope flash_attention_fwd\s+2 events", out)
    assert re.search(r"scope attn\s+4 events", out)


def test_diagnose_names_the_windowed_scope_when_it_grew_and_the_full_did_not():
    report = diagnose.diagnose(
        _summary(20), _summary(20, {"flash_attention_window_fwd": 1.5}))
    assert report["verdict"] == "regressed"
    growth = next(f for f in report["findings"]
                  if f["kind"] == "scope_growth")
    assert growth["scope"] == "flash_attention_window_fwd"
    assert growth["severity_pct"] == pytest.approx(50.0, abs=0.2)
    assert "flash_attention_window_fwd" in diagnose.format_report(report)
    assert report["scopes"][0]["scope"] == "flash_attention_window_fwd"
    assert not any(f.get("scope") in ("flash_attention_fwd",
                                      "flash_attention_window_bwd_dq")
                   for f in report["findings"])
    clean = diagnose.diagnose(_summary(20), _summary(20))
    assert clean["verdict"] == "clean" and clean["findings"] == []
