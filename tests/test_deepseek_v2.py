"""A job of many mechanisms (DeepSeek-V2's block): latent attention
(dynolog_tpu/models/mla.py), a dense first layer, an expert layer that holds
a share of its experts and shared experts beside them (models/moe.py), the
flash kernels at keys wider than values (ops/flash_attention.py), and the
product's account of which mechanism the time went to (dynolog_tpu/trace.py
`scopes`, `op_scope`; diagnose.py).

The program is held to the plain reference of the benchmark's module
(perfbench/deepseek_v2_block.py, loaded by path: it imports nothing of
dynolog_tpu). CPU, seeded weights, float32 under `highest` unless a case
says otherwise. Tolerances: both sides compute the same float32 sums in
another order (the reference adds the two parts of a head's score, the
program puts a head's parts together first; the reference sums every held
expert under gates that are 0, the program the chosen ones), so outputs
agree to a few float32 roundings of numbers of order 1 (1e-5) and gradients,
sums over 256 tokens, to 1e-4. bfloat16 anywhere float32 is stated moves an
output by 1e-2 and fails each by three orders."""

import dataclasses
import importlib.util
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dynolog_tpu import diagnose, trace
from dynolog_tpu.models import mla, moe
from dynolog_tpu.models.train import make_train_state, make_train_step
from dynolog_tpu.models.transformer import (
    TransformerConfig, _rmsnorm, _rope_freqs, forward, init_params, loss_fn)
from dynolog_tpu.ops.flash_attention import (
    flash_attention, reference_attention)

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "perfbench"))
import xspace_fixture as xf  # noqa: E402

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
# DeepSeek-V2-Lite's shape in small: a dense layer then two sparse ones,
# 16 experts of which a chip holds 4, 6 a token, 2 shared; keys of 16 + 8,
# values of 16
TOY = dict(vocab_size=512, d_model=64, n_layers=3, n_heads=4, d_ff=160,
           max_seq_len=4096, rope_theta=10000.0, rope_scaling=YARN,
           norm_eps=1e-6, dtype="float32", attn_impl="reference",
           attn_type="mla", kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, n_experts=16, n_experts_held=4,
           first_expert_held=4, moe_top_k=6, moe_norm_topk=False, moe_d_ff=32,
           n_shared_experts=2, first_dense_layers=1, moe_aux_weight=0.001,
           moe_balance_all_k=True, moe_seq_aux=True, moe_z_weight=0.0)


def _module(name: str):
    path = HERE.parent / "perfbench" / name
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def block():
    return _module("deepseek_v2_block.py")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _job(**over) -> dict:
    return {**TOY, "rope_scaling": dict(YARN), **over}


def _cfg(**over) -> TransformerConfig:
    return TransformerConfig(**{**TOY, **over})


def _close(got, want, tol):
    jax.tree_util.tree_map(
        lambda g, w: np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=tol, atol=tol), got, want)


def _dims(cfg):
    return (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank)


# -- latent attention ----------------------------------------------------


def test_latent_attention_and_its_gradients_equal_the_plain_reference(block):
    cfg = _cfg()
    layer = {**mla.init_mla_layer(jax.random.PRNGKey(3), cfg),
             "attn_scale": 1.0 + 0.1 * jax.random.normal(
                 jax.random.PRNGKey(6), (cfg.d_model,))}
    layer["mla_kv_scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(7), (cfg.kv_lora_rank,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, cfg.d_model))
    weight = jax.random.normal(jax.random.PRNGKey(5), x.shape)
    positions = jnp.broadcast_to(jnp.arange(128), (2, 128))
    cos, sin = block.rope_table(_job(), 128)

    def plain(layer, x):
        return jax.vmap(lambda row: block.latent_attention(
            layer, row, _dims(cfg), cfg.norm_eps, block.softmax_scale(_job()),
            cos, sin))(x)

    def program(layer, x):
        h = _rmsnorm(x, layer["attn_scale"], cfg.norm_eps)
        return x + mla.latent_attention(layer, h, positions, cfg)

    _close(program(layer, x), plain(layer, x), 1e-5)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(program(*a) * weight), (0, 1)))(layer, x)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(plain(*a) * weight), (0, 1)))(layer, x)
    assert set(got[0]) == set(layer)  # every weight has a gradient
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got[0].values())
    _close(got, want, 1e-4)


def test_yarn_at_the_published_values_is_the_issues_numbers(block):
    published = {"type": "yarn", "factor": 40, "beta_fast": 32,
                 "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                 "original_max_position_embeddings": 4096}
    cfg = _cfg(qk_nope_head_dim=128, qk_rope_head_dim=64,
               rope_scaling=published)
    job = _job(qk_nope_head_dim=128, qk_rope_head_dim=64,
               rope_scaling=published)
    # 192^-1/2 x (0.1 x 0.707 x ln 40 + 1)^2
    assert mla.softmax_scale(cfg) == pytest.approx(0.11472, abs=5e-6)
    assert block.softmax_scale(job) == pytest.approx(mla.softmax_scale(cfg))
    # without YaRN the scale is the plain one
    assert mla.softmax_scale(_cfg(rope_scaling=None)) == 24 ** -0.5
    freqs = np.asarray(_rope_freqs(32, 10000.0, published))
    plain = np.asarray(_rope_freqs(32, 10000.0))
    # the pairs that turn 32 times or more in 4096 positions keep their
    # frequency (pair 10 and below), those that turn once or less (pair 23
    # and above) are interpolated by the factor, the ramp lies between
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 40, rtol=1e-6)
    ratio = plain[11:23] / freqs[11:23]
    assert np.all(np.diff(ratio) > 0) and 1 < ratio[0] and ratio[-1] < 40
    cos, sin = block.rope_table(job, 4096)  # the module's own, held to it
    angles = np.arange(4096, dtype=np.float32)[:, None] * freqs
    np.testing.assert_allclose(np.asarray(cos), np.cos(angles), atol=2e-3)
    assert hash(cfg) is not None  # the group from JSON keys a jitted step
    with pytest.raises(ValueError, match="rope_scaling"):
        _cfg(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="attn_type"):
        _cfg(attn_type="gqa")


# -- the kernels at keys wider than values -------------------------------


@pytest.mark.parametrize("d_qk, d_v, scale", [
    (48, 32, 0.2),  # latent attention's: keys wider, its own scale
    (32, 32, None),  # the accepted cells': one width, D ** -0.5
])
def test_the_kernels_take_the_two_widths_apart(d_qk, d_v, scale):
    """Forward, dq and dkv (interpret mode) against plain attention. Both
    compute in float32; the kernels' online softmax adds in blocks, so an
    output differs by a few roundings (2e-5), a gradient by 1e-4."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    b, s, h = 1, 256, 2
    q = jax.random.normal(keys[0], (b, s, h, d_qk))
    k = jax.random.normal(keys[1], (b, s, h, d_qk))
    v = jax.random.normal(keys[2], (b, s, h, d_v))
    weight = jax.random.normal(keys[3], (b, s, h, d_v))

    def kernel(q, k, v):
        if scale is None:
            return flash_attention(q, k, v, True, 128, 128)
        return flash_attention(q, k, v, True, 128, 128, scale)

    def plain(q, k, v):
        return reference_attention(q, k, v, causal=True, scale=scale)

    with pltpu.force_tpu_interpret_mode():
        out = kernel(q, k, v)
        grads = jax.grad(
            lambda *a: jnp.sum(kernel(*a) * weight), (0, 1, 2))(q, k, v)
    assert out.shape == (b, s, h, d_v)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    _close(out, plain(q, k, v), 2e-5)
    _close(grads, jax.grad(
        lambda *a: jnp.sum(plain(*a) * weight), (0, 1, 2))(q, k, v), 1e-4)


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip (no chip here: the TPU's compiler alone)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("heads, kv_heads, d_qk, d_v, scale, seq, window", [
    (16, 16, 192, 128, 0.11472, 4096, None),  # latent attention's
    (32, 2, 128, 128, None, 4096, None),  # Nemotron-3-Nano's grouped heads
    (32, 4, 128, 128, None, 8192, 2048),  # Trinity-Mini's windowed layers
], ids=["latent", "grouped", "windowed"])
def test_the_kernels_compile_for_the_chip_at_the_published_widths(
        one_chip, heads, kv_heads, d_qk, d_v, scale, seq, window):
    """Keys of 192 (a lane and a half) and values of 128; 32 query heads on
    2 key/value heads of 128 with the dkv kernel's two float32 sums in fast
    memory; sequence 4096, blocks of 512: what interpret mode cannot refuse
    (a block that does not tile, more fast memory than a kernel may use)
    Mosaic would. Grouped, k, v, dk and dv stay at 2 heads: no operand or
    result of a kernel holds them at 32. Under a window of 2048 in 8192
    positions (32 on 4 heads) the three programs carry their own names and
    their loops start and end at run-time bounds. A compile that passes is
    no chip run. (All cases here: one file a worker describes the topology
    in.)"""
    def shape(n, width):
        return jax.ShapeDtypeStruct(
            (1, seq, n, width), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, True, scale=scale, window=window).astype(jnp.float32))

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape(heads, d_qk), shape(kv_heads, d_qk),
            shape(kv_heads, d_v)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    calls = [line for line in compiled.as_text().splitlines()
             if "custom_call_target" in line and "flash_attention" in line]
    for kernel in ("fwd", "bwd_dq", "bwd_dkv"):
        name = f"flash_attention_{'window_' if window else ''}{kernel}"
        assert any(name in line for line in calls), name
        if window:  # and no plain program beside the windowed
            assert not any(
                f"flash_attention_{kernel}" in line for line in calls)
    if kv_heads == 2:
        dkv = next(line for line in calls if "flash_attention_bwd_dkv" in line)
        assert "bf16[2,4096,128]" in dkv.split("custom-call(")[0]  # dk, dv
        fwd = next(line for line in calls if "flash_attention_fwd" in line)
        assert fwd.count("bf16[2,4096,128]") == 2  # k and v as they came
        assert not any("bf16[32,4096,128]" in line.split("custom-call(")[1]
                       and "bf16[2,4096,128]" not in line for line in calls)


def test_the_kda_rule_compiles_for_the_chip_at_the_published_widths(one_chip):
    """Kimi Delta Attention's rule, forward and backward under its
    checkpoint, at Kimi-Linear's 32 heads of 128 over the cell's 4096
    positions (tests/test_kimi_linear.py holds its numbers; here with the
    kernels' cases, one file a worker describes the topology in). What it
    guards: compiled for the chip, a chunk's two products under the decay
    are the two Pallas kernels (tests/test_kda_pairs.py holds their numbers)
    and Mosaic takes them at these widths; the program keeps no conditional
    on the platform and none of the plain body's [16, 16, 128] pairs of a
    diagonal sub-block: stored, they are 1.07 GB a layer a product at 4096
    positions and the job no longer fits its chip."""
    from dynolog_tpu.models import linear_attention as la

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(jax.checkpoint(la.chunked_kda_rule)(
            q, k, v, g, beta)[0].astype(jnp.float32))

    wide = shape(1, 4096, 32, 128)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            wide, wide, wide, shape(1, 4096, 32, 128, dtype=jnp.float32),
            shape(1, 4096, 32, dtype=jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    assert text.count(" while(") >= 2  # the loop, forward and backward
    calls = [line for line in text.splitlines()
             if "custom_call_target" in line and "kda_pairs" in line]
    # computed again under the checkpoint, then backward (the first pass
    # is dead here: the gradient alone is asked for)
    assert sum("kda_pairs_fwd" in line for line in calls) == 1
    assert sum("kda_pairs_bwd" in line for line in calls) == 1
    assert " conditional(" not in text
    # a fusion is an op of the step itself: one whose RESULT is the pairs
    stored = [line for line in text.splitlines() if " fusion(" in line
              and re.match(r"\s*(ROOT )?%\S+ = f32\[64,1,32,4,16,16,128\]",
                           line)]
    assert not stored, stored[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 1.8e9


@pytest.mark.parametrize("dtype, precision", [
    ("bfloat16", None), ("float32", "highest")])
@pytest.mark.parametrize("dk", [64, 256])
def test_the_kda_kernels_compile_at_every_width_they_are_taken_at(
        one_chip, dk, dtype, precision):
    """`kda_pairs.WIDTHS` beside Kimi-Linear's 128 (the rule whole, above):
    Mosaic takes the turned layout, forward and backward, in the model's
    type and in float32 under a caller's `highest`, which reaches the
    kernels' products as it reaches the plain body's. Any other width keeps
    the plain body (tests/test_kda_pairs.py)."""
    from dynolog_tpu.ops import kda_pairs as kernels

    assert kernels.WIDTHS == (64, 128, 256)
    rows = jax.ShapeDtypeStruct((16, 64, dk), jnp.dtype(dtype),
                                sharding=one_chip)
    gamma = jax.ShapeDtypeStruct((16, 64, dk), jnp.float32, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with jax.default_matmul_precision(precision or "default"):
            text = jax.jit(jax.grad(
                lambda *a: jnp.sum(kernels.kda_pairs(*a) ** 2), (0, 1, 2))
            ).lower(rows, rows, gamma).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "kda_pairs_fwd" in text and "kda_pairs_bwd" in text


def test_the_kda_rule_compiles_for_four_chips_over_a_mesh():
    """The rule's gradient over data 2 x model 2 of a described v5e host,
    batch rows and heads a device: the kernels sit in a `shard_map` (a
    Mosaic kernel cannot be partitioned, and without the mesh handed down
    the chip's compiler is never reached), each device runs them on its own
    [N, 1, 2] chunk-heads, and nothing is gathered for them."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dynolog_tpu.models import linear_attention as la
    from dynolog_tpu.parallel.sharding import BATCH_AXES, MeshSpec, make_mesh

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - as `one_chip`
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    mesh = make_mesh(MeshSpec(data=2, model=2), topo.devices)

    def shape(*tail, dtype=jnp.bfloat16):
        spec = P(BATCH_AXES, None, "model", *(None,) * len(tail))
        return jax.ShapeDtypeStruct(
            (2, 512, 4, *tail), dtype, sharding=NamedSharding(mesh, spec))

    def loss(*args):
        return jnp.sum(jax.checkpoint(
            lambda *a: la.chunked_kda_rule(*a, mesh))(*args)[0].astype(
                jnp.float32))

    wide = shape(128)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            wide, wide, wide, shape(128, dtype=jnp.float32),
            shape(dtype=jnp.float32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    calls = [line for line in text.splitlines()
             if "custom_call_target" in line and "kda_pairs" in line]
    assert sum("kda_pairs_fwd" in line for line in calls) == 1
    assert sum("kda_pairs_bwd" in line for line in calls) == 1
    # a device's own share: 8 chunks of 1 batch row and 2 heads
    assert all("[16,64,128]" in line for line in calls), calls
    assert " all-gather(" not in text


# -- the model whole -----------------------------------------------------


def test_the_programs_weights_are_laid_out_as_the_modules(block):
    cfg = _cfg()
    own = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: block.init_weights(k, _job()), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), own)
            == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), theirs))
    kinds = [("w_gate" in layer, "router" in layer, "shared_up" in layer)
             for layer in own["layers"]]
    assert kinds == [(True, False, False)] + [(False, True, True)] * 2
    sparse = own["layers"][1]
    assert sparse["router"].shape == (64, 16)  # every expert is scored
    assert sparse["experts_gate"].shape == (4, 64, 32)  # four are held
    assert sparse["shared_down"].shape == (2 * 32, 64)
    assert cfg.n_sparse_layers == 2 and cfg.expert_d_ff == 32


def test_forward_loss_and_gradients_equal_the_plain_reference(block):
    job, cfg = _job(), _cfg()
    params = jax.jit(lambda k: block.init_weights(k, job))(
        jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 128), 0, 512)
    want, want_loss = block.forward(params, tokens, job, 128)
    _close(forward(params, tokens, cfg), want, 2e-5)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn), static_argnums=2)(
        params, tokens, cfg)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    want_grads = jax.jit(jax.grad(
        lambda p: block.forward(p, tokens, job, 1)[1]))(params)
    _close(grads, want_grads, 1e-4)
    # the balancing term is in the loss, a sequence at a time: without it,
    # or over the whole batch at once, the loss is another number
    for other in (_cfg(moe_aux_weight=0.0), _cfg(moe_seq_aux=False)):
        assert abs(float(loss_fn(params, tokens, other)) - float(loss)) > 1e-6


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_reference(block):
    """Four chips hold 4 of 16 experts each. What each computes of a layer's
    output (the routed part its own experts give, plus the shared experts,
    which every chip computes alike and which count once) adds up to what
    the reference gives for the layer with every expert held."""
    whole_job = _job(n_experts_held=0, first_expert_held=0)
    whole = jax.jit(lambda k: block.init_weights(k, whole_job))(
        jax.random.PRNGKey(21))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(22), (2 * 128, 64))
    uncut, _ = block.sparse_mlp(whole, x, 2, 6, 0, 1e-6)
    h = _rmsnorm(x, whole["mlp_scale"], 1e-6)
    shared = block._swiglu(h, whole["shared_gate"], whole["shared_up"],
                           whole["shared_down"])
    total = x + shared  # the residual and the shared experts, once
    chosen = jax.lax.top_k(jax.nn.softmax(h @ whole["router"]), 6)[1]
    for first in (0, 4, 8, 12):
        cfg = _cfg(first_expert_held=first)
        share = {**whole, **{name: whole[name][first:first + 4] for name in (
            "experts_gate", "experts_up", "experts_down")}}
        y, balance, _ = moe.moe_mlp(share, h.reshape(2, 128, 64), cfg)
        routed_here = y.reshape(-1, 64) - shared
        # the reference given the same share says the same
        ref_part, _, _ = block.routed(share, h, 6, first)
        _close(routed_here, ref_part, 1e-5)
        # a token none of whose six choices fall here gets nothing from here
        absent = ~jnp.any((chosen >= first) & (chosen < first + 4), axis=1)
        assert int(absent.sum()) > 0
        assert float(jnp.max(jnp.abs(routed_here[absent]))) == 0.0
        total = total + routed_here
    _close(total, uncut, 2e-5)
    # the balancing term is whole on every chip: the uncut layer's
    _close(balance, block.sparse_mlp(whole, x, 2, 6, 0, 1e-6)[1], 1e-6)


def test_rows_no_expert_here_takes_carry_nothing_forward_or_back(monkeypatch):
    """On the TPU a grouped product writes no row past its groups, forward
    or transposed: what the buffer holds there is whatever was there. Here
    it is made NaN. The copies for experts that are not held lie in those
    rows, and neither the layer's output nor any gradient may see them (on
    the chip two of six runs lost their loss to it)."""
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def dirty(lhs, rhs, group_sizes):
        return real(lhs, rhs, group_sizes)

    def past(x, group_sizes):
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(group_sizes), x, jnp.nan)

    def fwd(lhs, rhs, group_sizes):
        return past(real(lhs, rhs, group_sizes), group_sizes), (
            lhs, rhs, group_sizes)

    def bwd(res, ct):
        lhs, rhs, group_sizes = res
        d_lhs, d_rhs = jax.vjp(
            lambda a, b: real(a, b, group_sizes), lhs, rhs)[1](
                jnp.where(jnp.isnan(ct), 0, ct))
        return past(d_lhs, group_sizes), d_rhs, None

    dirty.defvjp(fwd, bwd)
    cfg = _cfg()
    layer = moe.init_moe_layer(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))

    def loss(layer, x):
        y, balance, _ = moe.moe_mlp(layer, x, cfg)
        return jnp.sum(jnp.square(y)) + balance

    want = jax.grad(loss, (0, 1))(layer, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", dirty)
    got = jax.grad(loss, (0, 1))(layer, x)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(got))
    _close(got, want, 1e-6)


def test_the_jobs_learning_rate_is_the_configurations():
    """`make_train_step(cfg)` steps by `cfg.learning_rate` (3e-4 where a
    configuration states none, as every accepted cell runs); at the first
    step of a warm-up the weights hardly move."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    moved = {}
    for rate in (3e-4, 2.1e-7):
        cfg = _cfg(learning_rate=rate)
        params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg)
        before = np.asarray(params["layers"][1]["router"])  # donated below
        params, _, _ = make_train_step(cfg)(params, opt_state, tokens)
        moved[rate] = float(jnp.max(jnp.abs(
            params["layers"][1]["router"] - before)))
    assert TransformerConfig().learning_rate == 3e-4
    assert moved[3e-4] == pytest.approx(3e-4, rel=0.05)  # Adam's first step
    assert moved[2.1e-7] == pytest.approx(2.1e-7, rel=0.05)


def test_a_share_outside_the_routers_experts_is_refused():
    with pytest.raises(ValueError, match="are not among"):
        _cfg(first_expert_held=13)
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    cfg = _cfg()
    layer = moe.init_moe_layer(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(MeshSpec(expert=4), jax.devices()[:4])
    with pytest.raises(ValueError, match="one chip's"):
        moe.moe_mlp(layer, jnp.zeros((4, 128, 64)), cfg, mesh)


# Check J's tolerance at a toy size wide enough to be steady (hidden 256).
# bfloat16 reads 0.0062 here and the float8 control 0.048-0.049; on the chip
# at the published widths they read 0.0063-0.0065 and 0.0293-0.0294, and the
# module's limit (0.0137) is their geometric middle.
WIDER = dict(d_model=256, n_heads=4, d_ff=512, kv_lora_rank=64,
             qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
             moe_d_ff=64)


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


# -- over a mesh, and where it is refused --------------------------------


@pytest.mark.parametrize("axes", [{"expert": 4}, {"data": 2, "model": 2}])
def test_every_new_leaf_has_a_rule_and_the_mesh_computes_the_same(axes):
    from jax.sharding import PartitionSpec as P

    from dynolog_tpu.parallel.sharding import (
        MeshSpec, batch_sharding, make_mesh, shard_params)

    cfg = _cfg(n_experts_held=0, first_expert_held=0)  # the mesh divides them
    mesh = make_mesh(MeshSpec(**axes), jax.devices()[:4])
    params = init_params(jax.random.PRNGKey(0), cfg)
    shardings = shard_params(params, mesh)
    specs = {name: s.spec for name, s in shardings["layers"][1].items()}
    assert specs["wq"] == P(None, "model") and specs["wo"] == P("model", None)
    assert specs["mla_ukv"] == P(None, "model")
    assert specs["mla_dkv"] == P() and specs["mla_kv_scale"] == P(None)
    assert specs["shared_gate"] == specs["shared_up"] == P(None, "model")
    assert specs["shared_down"] == P("model", None)
    assert specs["experts_gate"] == P("expert", None, "model")
    assert specs["router"] == P()
    # no leaf of the model falls to replication in silence
    from dynolog_tpu.parallel.sharding import PARAM_RULES
    assert all(any(name.endswith(rule) for rule in PARAM_RULES)
               for layer in params["layers"] for name in layer)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0, 512)
    want = loss_fn(params, tokens, cfg)
    got = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(
        jax.device_put(params, shardings),
        jax.device_put(tokens, batch_sharding(mesh)))
    assert abs(float(got) - float(want)) < 2e-5


def test_the_pipeline_refuses_latent_attention_aloud():
    from dynolog_tpu.parallel import pipeline
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    cfg = dataclasses.replace(_cfg(), n_experts=0, n_experts_held=0,
                              first_expert_held=0, n_layers=4)
    mesh = make_mesh(MeshSpec(pipe=2), jax.devices()[:2])
    with pytest.raises(AssertionError, match="latent attention"):
        pipeline.init_pipeline_params(jax.random.PRNGKey(0), cfg, mesh)
    with pytest.raises(AssertionError, match="latent attention"):
        pipeline.pipeline_loss({}, jnp.zeros((2, 128), jnp.int32), cfg, mesh, 1)


# -- metadata only -------------------------------------------------------


def _step_ops(cfg) -> list:
    """The names of the compiled step's HLO instructions, in the order the
    compiler wrote them (what a capture's events are named after)."""
    params, opt_state = jax.eval_shape(
        lambda k: make_train_state(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = make_train_step(cfg).lower(params, opt_state, tokens).compile(
        ).as_text()
    return re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", text, flags=re.M)


@pytest.mark.parametrize("cfg", [
    TransformerConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=2,
                      d_ff=128, max_seq_len=128, dtype="float32"),
    TransformerConfig(**TOY),
], ids=["dense", "deepseek"])
def test_the_scopes_change_no_ops_name_or_count(cfg, monkeypatch):
    """`attn`, `mlp`, `embed`, `head`, `adam`, `mla.*`, `moe.*`: names in
    the ops' metadata and nothing else. The step compiled with every
    `jax.named_scope` of the job a no-op is the same program, op for op and
    name for name."""
    import contextlib

    scoped = _step_ops(cfg)
    assert len(scoped) > 200 and any("fusion" in op for op in scoped)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _step_ops(cfg) == scoped


# -- which mechanism the time went to ------------------------------------

# (op, its path, microseconds or what it holds)
SCOPED_STEP = (
    ("%fusion.1 = f32[8]{0} fusion(%a)",
     "jit(step)/jvp(embed)/gather:", 10),
    ("%fusion.2 = f32[8]{0} fusion(%b)",
     "jit(step)/jvp(mla.project)/dot_general:", 30),
    ("%flash_attention_fwd.3 = f32[8]{0} custom-call(%c)",
     "jit(step)/jvp(mla.attend)/flash_attention_fwd/pallas_call:", 40),
    ("%while.4 = (s32[]) while(%t)",
     "jit(step)/jvp(gdn.scan)/closed_call/while:", [
         ("%fusion.5 = f32[8]{0} fusion(%d)",
          "jit(step)/jvp(gdn.scan)/closed_call/while/body/closed_call/"
          "bhij,bhjv->bhiv/dot_general:", 10),
         ("%fusion.6 = f32[8]{0} fusion(%e)",
          "jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
          "shard_map/moe.dispatch/jit(argsort)/sort:", 10)]),
    ("%fusion.7 = f32[8]{0} fusion(%f)",
     "jit(step)/transpose(jvp(moe.shared))/mul;jit(step)/jvp(mlp)/add:", 20),
    ("%fusion.8 = f32[8]{0} fusion(%g)", "jit(step)/add:", 15),
    ("%ragged-dot-none.9 = f32[8]{0} custom-call(%h)", "ragged-dot-none:", 25),
    ("%copy-start.10 = f32[8]{0} copy-start(%i)", None, 5),
)
WANT_SCOPES_US = {  # self time a step; the while's own is its slack
    "embed": 10, "mla.project": 30, "mla.attend": 40,
    "gdn.scan": 10 + xf.NESTED_SLACK_US, "moe.dispatch": 10,
    "moe.shared": 20, trace.NO_SCOPE: 15 + 25 + 5}


def scoped_xspace(steps: int = 2, scale: dict | None = None) -> bytes:
    """SCOPED_STEP `steps` times over (`xf.build_scoped_xspace`)."""
    return xf.build_scoped_xspace(SCOPED_STEP, trace.op_scope, steps, scale)


@pytest.mark.parametrize("path, scope", [
    ("jit(step)/jvp(attn)/dot_general:", "attn"),
    ("jit(step)/transpose(jvp(gdn.project))/dot_general:", "gdn.project"),
    ("jit(step)/jvp()/shard_map/moe.dispatch/sub:", "moe.dispatch"),
    ("jit(step)/jit(main)/jvp(mla.attend)/flash_attention_fwd/pallas_call:",
     "mla.attend"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "gdn.scan/while/body/closed_call/mul:", "gdn.scan"),
    ("jit(step)/jvp(cond)/branch_1_fun/adam/mul:", "adam"),
    ("jit(step)/add:", trace.NO_SCOPE),
    ("jit(step)/jvp()/convert_element_type:", trace.NO_SCOPE),
    ("ragged-dot-none:", trace.NO_SCOPE),
    ("", trace.NO_SCOPE),
])
def test_an_ops_scope_is_the_outermost_name_the_program_wrote(path, scope):
    assert trace.op_scope(path) == scope


def test_scopes_add_up_to_the_busy_time_nested_wrapped_or_unscoped():
    steps = 3
    summary = trace._summarize_planes(
        trace.summarize_xplane_bytes(scoped_xspace(steps), group=False))
    [plane] = summary["planes"]
    got = {name: row["self_ms"] for name, row in plane["scopes"].items()}
    assert got == {name: pytest.approx(us * steps / 1e3)
                   for name, us in WANT_SCOPES_US.items()}
    # they add up as the op table's self times do, to the busy time
    busy_ms = sum(WANT_SCOPES_US.values()) * steps / 1e3
    assert sum(got.values()) == pytest.approx(busy_ms)
    assert sum(op["self_ms"] for op in summary["top_ops"]) == pytest.approx(
        busy_ms)
    assert sum(row["pct"] for row in plane["scopes"].values()) == (
        pytest.approx(100.0, abs=0.5))
    assert plane["scopes"]["gdn.scan"]["count"] == 2 * steps  # while + body op
    assert list(plane["scopes"])[0] == trace.NO_SCOPE  # ranked by self time
    # a host plane's row carries none: the question is a device's
    host = trace._summarize_planes(trace.summarize_xplane_bytes(
        xf.build_xspace(planes=1, lines_per_plane=1, events_per_line=10)
        .replace(b"XLA Ops", b"python3")))
    assert host["planes"][0]["scopes"] == {}


def test_the_products_scopes_equal_the_benchmarks_plain_reading(tmp_path):
    """perfbench/scope_ops.py reads the same stat through the wheel's
    protobuf binding and shares no code with trace.py."""
    import scope_ops

    if scope_ops.binding() is None:
        pytest.skip("no wheel here ships xplane_pb2")
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(scoped_xspace(2))
    run = {"trace": {"path": str(path)}, "device": {"count": 1}}
    [plane] = trace._summarize_planes(trace.summarize_xplane_bytes(
        path.read_bytes()))["planes"]
    total = sum(row["self_ms"] for row in plane["scopes"].values())
    for prefix in ("mla.", "moe.shared", "gdn."):
        want = sum(row["self_ms"] for name, row in plane["scopes"].items()
                   if name.startswith(prefix)) / total * 100.0
        assert scope_ops.scope_share_pct(run, prefix) == pytest.approx(want)
    assert scope_ops.scope_share_pct(run, "mla.") == pytest.approx(
        100.0 * 70 / 170)
    assert scope_ops.scope_share_pct(run, "nowhere.") == 0.0
    assert scope_ops.scope_share_pct({"device": {"count": 1}}, "mla.") is None


def test_the_cli_prints_the_scopes(tmp_path, capsys):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(scoped_xspace())
    assert trace.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert re.search(r"scope mla\.attend\s+2 events\s+0\.080 ms self", out)
    assert re.search(r"scope \(none\)\s+6 events", out)


def test_diagnose_names_the_scope_that_grew_beside_the_op():
    def summary(scale):
        return trace._summarize_planes(trace.summarize_xplane_bytes(
            scoped_xspace(20, scale), group=False))

    report = diagnose.diagnose(summary({}), summary({"mla.attend": 1.5}))
    assert report["verdict"] == "regressed"
    first, second = report["findings"][:2]
    assert first["op"] == "flash_attention_fwd.3"  # which op
    assert second["kind"] == "scope_growth"  # which mechanism
    assert second["scope"] == "mla.attend"
    assert second["severity_pct"] == pytest.approx(50.0)
    assert second["impact_ms"] == pytest.approx(20 * 0.020)
    assert "mla.attend" in diagnose.format_report(report)
    assert report["scopes"][0]["scope"] == "mla.attend"
    # nothing grew: no finding, and a baseline from before scopes were read
    # is compared without one
    clean = diagnose.diagnose(summary({}), summary({}))
    assert clean["verdict"] == "clean" and clean["findings"] == []
    old = summary({})
    for plane in old["planes"]:
        del plane["scopes"]
    assert diagnose.diagnose(old, summary({"mla.attend": 1.5}))[
        "findings"][0]["op"] == "flash_attention_fwd.3"
