"""Kimi Delta Attention (dynolog_tpu/models/linear_attention.py
`chunked_kda_rule`, `kimi_delta_attention`; `TransformerConfig.layer_types`
"kda") and the job round it: latent attention without positions in the
fourth layer, a dense first layer, a held share of 256-column experts.

The program computes the rule in chunks of 64 tokens, a chunk's decayed
products by sub-blocks of 16; it is held here to the recurrence taken token
by token: written plainly below for the rule alone, and the plain reference
of the benchmark's module (perfbench/kimi_linear_block.py, loaded by path:
it imports nothing of dynolog_tpu) for the layer and the model whole. CPU,
seeded weights, float32 under `highest` unless a case says otherwise."""

import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynolog_tpu.models import linear_attention as la
from dynolog_tpu.models import moe
from dynolog_tpu.models.train import make_train_state, make_train_step
from dynolog_tpu.models.transformer import (
    LAYER_TYPES, MIXER_NORMS, TransformerConfig, _rmsnorm, forward,
    init_params, loss_fn)

HERE = pathlib.Path(__file__).resolve().parent
KINDS = ("kda", "kda", "kda", "full_attention", "kda")
# Kimi-Linear's shape in small: a dense layer then four sparse ones, three
# KDA layers to one of latent attention that nothing rotates, 16 experts of
# which a chip holds 2, 4 a token, 1 shared
TOY = dict(vocab_size=512, d_model=64, n_layers=5, n_heads=4, d_ff=96,
           max_seq_len=4096, rope_theta=None, norm_eps=1e-5, dtype="float32",
           attn_impl="reference", layer_types=KINDS, linear_key_head_dim=16,
           linear_value_head_dim=16, linear_conv_kernel=4, attn_type="mla",
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, first_dense_layers=1, mlp_act="swiglu",
           n_experts=16, n_experts_held=2, first_expert_held=4, moe_top_k=4,
           moe_norm_topk=True, moe_d_ff=32, n_shared_experts=1,
           moe_score="sigmoid", moe_select_bias=True, moe_gate_scale=2.446,
           moe_aux_weight=0.0, moe_z_weight=0.0)


def _module(name: str):
    path = HERE.parent / "perfbench" / name
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def block():
    return _module("kimi_linear_block.py")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _job(**over) -> dict:
    return {**TOY, "layer_types": list(KINDS), **over}


def _cfg(**over) -> TransformerConfig:
    return TransformerConfig(**{**TOY, **over})


def _close(got, want, tol):
    jax.tree_util.tree_map(
        lambda g, w: np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=tol, atol=tol), got, want)


# -- the rule alone ------------------------------------------------------


def recurrence(q, k, v, g, beta):
    """S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,
    o_t = S_t^T q_t, a token at a time; [B, S, H, ...] in and out."""

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + beta_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k_t, v_t - read)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    b, _, h, dk = q.shape
    state, out = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def rule_inputs(seq: int = 192, step=None):
    """`step` None: decays from 0.3 to 0.999 a token, each channel its own;
    a number: A 16 times a step from there to three times it, the library's
    strongest A under a softplus of that size."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    b, h, dk, dv = 2, 3, 8, 16

    def unit(key, scale):
        x = jax.random.normal(key, (b, seq, h, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True) * scale

    if step is None:
        g = jnp.log(jax.random.uniform(
            keys[3], (b, seq, h, dk), minval=0.3, maxval=0.999))
    else:
        g = -16.0 * jax.random.uniform(
            keys[3], (b, seq, h, dk), minval=step, maxval=3 * step)
    return (unit(keys[0], dk ** -0.5), unit(keys[1], 1.0),
            jax.random.normal(keys[2], (b, seq, h, dv)), g,
            jax.random.uniform(keys[4], (b, seq, h), minval=0.0, maxval=1.0))


def _scalar(rule, weight):
    return lambda *a: jnp.sum(rule(*a)[0] * weight)


def test_chunks_equal_the_recurrence_and_carry_the_state():
    args = rule_inputs()  # three chunks: the carried state matters
    out, state = la.chunked_kda_rule(*args)
    want, want_state = recurrence(*args)
    _close(out, want, 1e-5)
    _close(state, want_state, 1e-5)
    # the last chunk alone, from a zero state, is another answer
    alone, _ = la.chunked_kda_rule(*(x[:, 128:] for x in args))
    assert float(jnp.max(jnp.abs(alone - want[:, 128:]))) > 1e-2
    # and so is one decay a head where the channels have their own
    flat = jnp.broadcast_to(
        jnp.mean(args[3], -1, keepdims=True), args[3].shape)
    other, _ = la.chunked_kda_rule(*args[:3], flat, args[4])
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2


def test_gradients_of_the_chunks_equal_the_recurrences():
    args = rule_inputs()
    weight = jax.random.normal(jax.random.PRNGKey(8), args[2].shape)
    got = jax.jit(jax.grad(
        _scalar(la.chunked_kda_rule, weight), range(5)))(*args)
    want = jax.jit(jax.grad(_scalar(recurrence, weight), range(5)))(*args)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got)
    _close(got, want, 1e-4)


def test_one_decay_in_every_channel_is_the_gated_delta_net():
    q, k, v, g, beta = rule_inputs()
    same = jnp.broadcast_to(g[..., :1], g.shape)
    got, got_state = la.chunked_kda_rule(q, k, v, same, beta)
    want, want_state = la.chunked_delta_rule(q, k, v, g[..., 0], beta)
    _close(got, want, 1e-5)
    _close(got_state, want_state, 1e-5)
    weight = jax.random.normal(jax.random.PRNGKey(8), v.shape)
    got = jax.jit(jax.grad(lambda *a: _scalar(la.chunked_kda_rule, weight)(
        a[0], a[1], a[2], jnp.broadcast_to(a[3][..., None], g.shape), a[4]),
        range(5)))(q, k, v, g[..., 0], beta)
    want = jax.jit(jax.grad(
        _scalar(la.chunked_delta_rule, weight), range(5)))(
            q, k, v, g[..., 0], beta)
    _close(got, want, 1e-4)


@pytest.mark.parametrize("step", [0.1, 0.5, 2.0])
def test_the_strongest_decay_stays_finite_and_agrees(step):
    """A 16 and a step of 0.1 and more over whole chunks: the running sum of
    g passes -100 inside a chunk (-88 is where float32's exp(-x) overflows),
    and at a step of 2 inside a SUB-BLOCK of sixteen. Factored as (K *
    exp(gamma)) (K * exp(-gamma))^T a chunk's product is inf or NaN; about a
    row between the pair it is the recurrence's."""
    args = rule_inputs(step=step)
    q, k, v, g, beta = args
    gamma = jnp.cumsum(g[:, :la.CHUNK], axis=1)
    assert float(jnp.min(gamma)) < -100.0
    naive = jnp.einsum("bihd,bjhd->bhij", k[:, :64] * jnp.exp(gamma),
                       k[:, :64] * jnp.exp(-gamma))
    assert not bool(jnp.all(jnp.isfinite(naive)))
    out, state = jax.jit(la.chunked_kda_rule)(*args)
    want, want_state = recurrence(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    _close(out, want, 1e-5)
    _close(state, want_state, 1e-5)
    weight = jax.random.normal(jax.random.PRNGKey(8), v.shape)
    got = jax.jit(jax.grad(
        _scalar(la.chunked_kda_rule, weight), range(5)))(*args)
    want = jax.jit(jax.grad(_scalar(recurrence, weight), range(5)))(*args)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
    _close(got, want, 1e-4)


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    args = tuple(x[:, :100] for x in rule_inputs())
    with pytest.raises(ValueError, match="chunks of 64 .* holds 100"):
        la.chunked_kda_rule(*args)
    assert la.CHUNK % la.SUB == 0


# -- the layer -----------------------------------------------------------


def test_the_layer_and_its_gradients_equal_the_plain_reference(block):
    cfg = _cfg()
    layer = la.init_kda_layer(jax.random.PRNGKey(3), cfg)
    # decays of every speed: A from 0.05 to 16 over the heads, steps up to 1
    layer["kda_a_log"] = jnp.log(jnp.array([0.05, 1.0, 4.0, 16.0]))
    layer["kda_dt_bias"] = layer["kda_dt_bias"] + jnp.linspace(0.0, 3.0, 64)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 192, cfg.d_model))
    weight = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def plain(layer, x):
        return jax.vmap(lambda row: block.kda_mixer(
            layer, row, cfg.n_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.norm_eps))(x)

    def chunked(layer, x):
        return la.kimi_delta_attention(layer, x, cfg)

    _close(chunked(layer, x), plain(layer, x), 1e-5)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(chunked(*a) * weight), (0, 1)))(layer, x)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(plain(*a) * weight), (0, 1)))(layer, x)
    assert set(got[0]) == set(layer)  # every weight has a gradient
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got[0].values())
    # sums over 384 tokens of numbers up to 35: a few float32 roundings
    _close(got, want, 3e-4)


def test_the_output_gate_is_a_sigmoid_through_a_bottleneck():
    cfg = _cfg()
    layer = la.init_kda_layer(jax.random.PRNGKey(3), cfg)
    assert layer["kda_g_down"].shape == (64, 16)  # a head's value width
    assert layer["kda_g_up"].shape == (16, 4 * 16)
    assert layer["kda_f_down"].shape == (64, 16)
    assert layer["kda_f_up"].shape == (16, 4 * 16)
    assert layer["kda_a_log"].shape == (4,)  # a number a head,
    assert layer["kda_dt_bias"].shape == (4 * 16,)  # one a channel
    assert {layer["kda_a_log"].dtype, layer["kda_dt_bias"].dtype} == {
        jnp.dtype("float32")}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, cfg.d_model))
    shut = {**layer, "kda_g_up": jnp.zeros_like(layer["kda_g_up"])}
    # a gate of zeros is sigmoid(0) = a half, where SiLU(0) would shut it
    half = la.kimi_delta_attention(shut, x, cfg)
    assert float(jnp.max(jnp.abs(half))) > 1e-3
    wide = {**layer, "kda_g_up": jnp.full_like(layer["kda_g_up"], 1e4),
            "kda_g_down": jnp.abs(layer["kda_g_down"])}
    whole = la.kimi_delta_attention(wide, jnp.abs(x), cfg)
    _close(whole, 2 * la.kimi_delta_attention(
        {**wide, "kda_g_up": shut["kda_g_up"]}, jnp.abs(x), cfg), 1e-5)


# -- the model whole -----------------------------------------------------


def test_the_programs_weights_are_laid_out_as_the_modules(block):
    cfg = _cfg()
    own = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: block.init_weights(k, _job()), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), own)
            == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), theirs))
    kinds = [("kda_q" in layer, "mla_dkv" in layer, "router" in layer)
             for layer in own["layers"]]
    assert kinds == [(True, False, False), (True, False, True),
                     (True, False, True), (False, True, True),
                     (True, False, True)]
    assert [cfg.mixers(i) for i in (0, 1, 3)] == [
        ("kda", "mlp"), ("kda", "moe"), ("mla", "moe")]
    sparse = own["layers"][1]
    assert sparse["router"].shape == (64, 16)  # every expert is scored
    assert sparse["experts_up"].shape == (2, 64, 32)  # two are held
    assert own["layers"][3]["wq"].shape == (64, 4 * (16 + 8))
    assert cfg.n_sparse_layers == 4 and cfg.has_linear_layers


def test_forward_loss_and_gradients_equal_the_plain_reference(block):
    job, cfg = _job(), _cfg()
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 128), 0, 512)
    want, want_loss = block.forward(params, tokens, job, 128, undecided_gap=0)
    _close(forward(params, tokens, cfg), want, 2e-5)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn), static_argnums=2)(
        params, tokens, cfg)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    want_grads = jax.jit(jax.grad(
        lambda p: block.forward(p, tokens, job, 1)[1]))(params)
    _close(grads, want_grads, 1e-4)
    # nothing is rotated: a theta stated is another model, on both sides
    rotated = forward(params, tokens, _cfg(rope_theta=10000.0))
    assert float(jnp.max(jnp.abs(rotated - want))) > 1e-3
    _close(rotated, block.forward(
        params, tokens, _job(rope_theta=10000.0), 128, undecided_gap=0)[0],
        2e-5)


def test_the_reference_marks_the_positions_it_did_not_decide(block):
    job = _job()
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 128), 0, 512)
    marked, _ = block.forward(params, tokens, job, 128)
    plain, _ = block.forward(params, tokens, job, 128, undecided_gap=0)
    left_out = jnp.isnan(marked[..., 0])
    assert 0 < int(left_out.sum()) < left_out.size
    assert not bool(jnp.any(jnp.isnan(plain)))
    assert block.rel_rms(plain, marked) == 0.0  # over the decided alone
    # the control is never marked: a NaN there would be in a position that
    # counts
    low, _ = block.forward(params, tokens, job, 128, rounding=block.lower)
    assert not bool(jnp.any(jnp.isnan(low)))


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(block):
    """256 columns, 8 a token, eight chips of 32 experts each. What each
    computes of a layer's output (the routed part its own experts give, plus
    the shared expert, which every chip computes alike and which counts
    once) adds up to what the reference gives for the layer with every
    expert held."""
    wide = dict(n_experts=256, moe_top_k=8, moe_d_ff=8, d_model=32,
                n_heads=2, n_layers=2, layer_types=("kda", "kda"))
    whole_job = _job(**wide, n_experts_held=0, first_expert_held=0)
    whole = jax.jit(lambda k: block.init_weights(k, whole_job))(
        jax.random.PRNGKey(21))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(22), (2 * 64, 32))
    h = _rmsnorm(x, whole["mlp_scale"], 1e-5)
    uncut = block.sparse_mlp(whole, h, 8, 2.446, 0)
    shared = block._swiglu(h, whole["shared_gate"], whole["shared_up"],
                           whole["shared_down"])
    total = shared  # the shared expert, once
    chosen = block.gates(whole, h, 8, 2.446)[1]
    for first in range(0, 256, 32):
        cfg = _cfg(**wide, n_experts_held=32, first_expert_held=first)
        share = {**whole, **{name: whole[name][first:first + 32] for name in (
            "experts_gate", "experts_up", "experts_down")}}
        y, _, _ = moe.moe_mlp(share, h.reshape(2, 64, 32), cfg)
        routed_here = y.reshape(-1, 32) - shared
        # the reference given the same share says the same
        _close(routed_here, block.routed(share, h, 8, 2.446, first), 1e-5)
        # a token none of whose eight choices fall here gets nothing from here
        absent = ~jnp.any((chosen >= first) & (chosen < first + 32), axis=1)
        assert int(absent.sum()) > 0
        assert float(jnp.max(jnp.abs(routed_here[absent]))) == 0.0
        total = total + routed_here
    _close(total, uncut, 2e-5)


def test_layer_kinds_are_checked():
    assert "kda" in LAYER_TYPES and MIXER_NORMS["kda"] == (
        "attn_scale", "kda.project")
    cfg = _cfg(layer_types=list(KINDS))
    assert cfg == _cfg() and hash(cfg) == hash(_cfg())
    assert [cfg.is_linear(i) for i in range(5)] == [
        True, True, True, False, True]
    # both rules in one model, on multi-head attention as on latent
    mixed = _cfg(layer_types=("kda", "linear_attention", "full_attention",
                              "full_attention", "kda"), attn_type="mha")
    assert [mixed.mixers(i)[0] for i in range(5)] == [
        "kda", "linear_attention", "attention", "attention", "kda"]
    with pytest.raises(ValueError, match="layer_types"):
        _cfg(layer_types=KINDS[:-1] + ("kimi",))
    with pytest.raises(ValueError, match="1 or more each"):
        _cfg(linear_key_head_dim=0)
    with pytest.raises(ValueError, match="1 or more each"):
        _cfg(linear_conv_kernel=0)
    with pytest.raises(ValueError, match="post_norm"):
        _cfg(post_norm=True, attn_type="mha")
    with pytest.raises(ValueError, match="latent attention rotates"):
        _cfg(rope_layer_types=["full_attention"])
    with pytest.raises(ValueError, match="multi-head attention"):
        _cfg(layer_types=KINDS[:-1] + ("sliding_attention",),
             sliding_window=8)
    with pytest.raises(ValueError, match="block_types"):
        _cfg(block_types=("mlp",) * 5)


WIDER = dict(d_model=256, n_heads=4, d_ff=512, kv_lora_rank=64,
             qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
             linear_key_head_dim=32, linear_value_head_dim=32, moe_d_ff=64)


@pytest.mark.parametrize("seed", [5, 6])
def test_bfloat16_stays_inside_the_modules_limit_and_float8_does_not(
        block, seed):
    job = _job(dtype="bfloat16", **WIDER)
    cfg = _cfg(dtype="bfloat16", **WIDER)
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    want, want_loss = block.forward(params, tokens, job, 64)
    with jax.default_matmul_precision("default"):
        sound = block.rel_rms(forward(params, tokens, cfg)[:, -64:], want)
        loss = float(loss_fn(params, tokens, cfg))
    control = block.rel_rms(
        block.forward(params, tokens, job, 64, rounding=block.lower)[0], want)
    assert sound <= block.J_LOGIT_REL_RMS_LIMIT < control
    assert control > 3 * sound
    assert abs(loss - float(want_loss)) <= block.J_LOSS_ABS_LIMIT


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


def test_the_phases_carry_their_scopes():
    """`kda.project`, `kda.conv`, `kda.chunk_prepare`, `kda.scan` and
    `kda.out` in the paths of the step's ops, beside the latent layer's and
    the experts': what `dyno`'s summary and the benchmark's reader go by."""
    cfg = _cfg()
    params, opt_state = jax.eval_shape(
        lambda k: make_train_state(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = make_train_step(cfg).lower(params, opt_state, tokens).as_text(
        debug_info=True)
    for scope in ("kda.project", "kda.conv", "kda.chunk_prepare", "kda.scan",
                  "kda.out", "mla.attend", "moe.experts", "moe.shared"):
        assert f"/{scope}" in text or f"({scope})" in text, scope
    assert "gdn." not in text  # the other rule's names stay the other rule's


# -- over a mesh, and where it is refused --------------------------------


def test_every_new_leaf_has_a_rule_and_the_mesh_computes_the_same():
    from jax.sharding import PartitionSpec as P

    from dynolog_tpu.parallel.sharding import (
        PARAM_RULES, MeshSpec, batch_sharding, make_mesh, shard_params)

    cfg = _cfg(n_experts_held=0, first_expert_held=0)
    mesh = make_mesh(MeshSpec(data=2, model=2), jax.devices()[:4])
    params = init_params(jax.random.PRNGKey(0), cfg)
    shardings = shard_params(params, mesh)
    specs = {name: s.spec for name, s in shardings["layers"][1].items()}
    for name in ("kda_q", "kda_k", "kda_v", "kda_f_up", "kda_g_up"):
        assert specs[name] == P(None, "model"), name
    assert specs["kda_o"] == P("model", None)
    for name in ("kda_f_down", "kda_g_down", "kda_conv_q", "kda_b",
                 "kda_a_log", "kda_dt_bias"):
        assert specs[name] == P(), name
    assert specs["kda_norm_scale"] == P(None)
    # no leaf of the model falls to replication in silence
    assert all(any(name.endswith(rule) for rule in PARAM_RULES)
               for layer in params["layers"] for name in layer)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 512)
    want = loss_fn(params, tokens, cfg)
    got = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(
        jax.device_put(params, shardings),
        jax.device_put(tokens, batch_sharding(mesh)))
    assert abs(float(got) - float(want)) < 2e-5


def test_the_pipeline_refuses_a_kda_layer_aloud():
    from dynolog_tpu.parallel import pipeline
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    cfg = dataclasses.replace(
        _cfg(), n_experts=0, n_experts_held=0, first_expert_held=0,
        n_layers=4, attn_type="mha", layer_types=("kda",) * 4)
    mesh = make_mesh(MeshSpec(pipe=2), jax.devices()[:2])
    with pytest.raises(AssertionError, match="linear_attention"):
        pipeline.init_pipeline_params(jax.random.PRNGKey(0), cfg, mesh)
