"""The gated-delta-net layer (dynolog_tpu/models/linear_attention.py), the
hybrid model around it, and the product's account of a capture whose ops
nest (dynolog_tpu/trace.py `self_ps`, `loops`; diagnose.py).

The program computes the layer in chunks of 64 tokens; it is held here to
the recurrence taken token by token: written plainly below for the rule
alone, and the plain reference of the benchmark's module
(perfbench/olmo_hybrid_block.py, loaded by path: it imports nothing of
dynolog_tpu) for the layer and the model whole. CPU, seeded weights,
float32 unless a case says otherwise."""

import dataclasses
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynolog_tpu import diagnose, trace
from dynolog_tpu.models import linear_attention as la
from dynolog_tpu.models.train import make_train_state, make_train_step
from dynolog_tpu.models.transformer import (
    TransformerConfig, forward, init_params, loss_fn)

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import xspace_fixture as xf  # noqa: E402

LLLF = ("linear_attention",) * 3 + ("full_attention",)
TOY = dict(vocab_size=512, d_model=64, n_layers=4, n_heads=2, d_ff=128,
           max_seq_len=128, rope_theta=None, norm_eps=1e-6, qk_norm=True,
           dtype="float32", attn_impl="reference", layer_types=LLLF,
           linear_key_head_dim=8, linear_value_head_dim=16,
           linear_conv_kernel=4, linear_allow_neg_eigval=True)


def _module(name: str):
    path = HERE.parent / "perfbench" / name
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def block():
    return _module("olmo_hybrid_block.py")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _job(**over) -> dict:
    return {**TOY, "layer_types": list(LLLF), **over}


def _close(got, want, tol):
    jax.tree_util.tree_map(
        lambda g, w: np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=tol, atol=tol), got, want)


# -- the rule alone ------------------------------------------------------


def recurrence(q, k, v, g, beta):
    """S_t = a_t S_{t-1} + b_t k_t (v_t - (a_t S_{t-1})^T k_t)^T,
    o_t = S_t^T q_t, a token at a time; [B, S, H, ...] in and out."""

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + beta_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k_t, v_t - read)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    b, _, h, dk = q.shape
    state, out = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def rule_inputs(seq: int = 192):
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    b, h, dk, dv = 2, 3, 8, 16

    def unit(key, scale):
        x = jax.random.normal(key, (b, seq, h, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True) * scale

    return (unit(keys[0], dk ** -0.5), unit(keys[1], 1.0),
            jax.random.normal(keys[2], (b, seq, h, dv)),
            # decays from 0.3 to 0.999 a token, write strengths up to 2
            jnp.log(jax.random.uniform(keys[3], (b, seq, h), minval=0.3,
                                       maxval=0.999)),
            jax.random.uniform(keys[4], (b, seq, h), minval=0.0, maxval=2.0))


def test_chunks_equal_the_recurrence_and_carry_the_state():
    args = rule_inputs()  # three chunks: the carried state matters
    out, state = la.chunked_delta_rule(*args)
    want, want_state = recurrence(*args)
    _close(out, want, 1e-5)
    _close(state, want_state, 1e-5)
    # the last chunk alone, from a zero state, is another answer
    alone, _ = la.chunked_delta_rule(*(x[:, 128:] for x in args))
    assert float(jnp.max(jnp.abs(alone - want[:, 128:]))) > 1e-2


def test_gradients_of_the_chunks_equal_the_recurrences():
    args = rule_inputs()
    weight = jax.random.normal(jax.random.PRNGKey(8), args[2].shape)

    def scalar(rule):
        return lambda *a: jnp.sum(rule(*a)[0] * weight)

    got = jax.jit(jax.grad(scalar(la.chunked_delta_rule), range(5)))(*args)
    want = jax.jit(jax.grad(scalar(recurrence), range(5)))(*args)
    _close(got, want, 1e-4)


def test_a_sequence_that_is_no_whole_number_of_chunks_is_refused():
    args = tuple(x[:, :100] for x in rule_inputs())
    with pytest.raises(ValueError, match="chunks of 64 .* holds 100"):
        la.chunked_delta_rule(*args)
    cfg = TransformerConfig(**TOY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="not a whole number of chunks"):
        forward(params, jnp.zeros((1, 100), jnp.int32), cfg)


# -- the layer -----------------------------------------------------------


def test_the_layer_and_its_gradients_equal_the_plain_reference(block):
    cfg = TransformerConfig(**TOY)
    layer = la.init_linear_layer(jax.random.PRNGKey(3), cfg)
    # decays of every speed: A from 0.05 to 16 over the heads' steps
    layer["gdn_a_log"] = jnp.log(jnp.array([0.05, 16.0]))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 192, cfg.d_model))
    weight = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def plain(layer, x):
        return jax.vmap(lambda row: block._linear_attention(
            layer, row, cfg.n_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, True, cfg.norm_eps))(x)

    def chunked(layer, x):
        return la.gated_delta_net(layer, x, cfg)

    _close(chunked(layer, x), plain(layer, x), 1e-5)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(chunked(*a) * weight), (0, 1)))(layer, x)
    want = jax.jit(jax.grad(
        lambda *a: jnp.sum(plain(*a) * weight), (0, 1)))(layer, x)
    assert set(got[0]) == set(layer)  # every weight has a gradient
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got[0].values())
    _close(got, want, 1e-4)


def test_beta_stays_below_one_unless_the_config_allows_negative_eigenvalues(
        block):
    cfg = TransformerConfig(**{**TOY, "linear_allow_neg_eigval": False})
    layer = la.init_linear_layer(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, cfg.d_model))
    want = block._linear_attention(layer, x[0], 2, 8, 16, False, 1e-6)
    _close(la.gated_delta_net(layer, x, cfg)[0], want, 1e-5)
    twice = block._linear_attention(layer, x[0], 2, 8, 16, True, 1e-6)
    assert float(jnp.max(jnp.abs(twice - want))) > 1e-3


# -- the model whole -----------------------------------------------------


def test_the_programs_weights_are_laid_out_as_the_modules(block):
    cfg = TransformerConfig(**TOY)
    own = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: block.init_weights(k, _job()), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), own)
            == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), theirs))
    kinds = [("gdn_q" in layer, "wq" in layer) for layer in own["layers"]]
    assert kinds == [(True, False)] * 3 + [(False, True)]


def test_forward_and_gradients_equal_the_plain_reference(block):
    job, cfg = _job(), TransformerConfig(**TOY)
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


def test_rope_theta_none_is_no_rotary_embedding_and_a_float_is_todays():
    dense = dict(vocab_size=512, d_model=64, n_layers=2, n_heads=2, d_ff=128,
                 max_seq_len=64, dtype="float32", attn_impl="reference")
    reference = _module("reference.py")
    job = {**dense, "rope_theta": 500000.0}
    params = jax.jit(lambda k: reference.init_weights(k, job))(
        jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 512)
    rotary = forward(params, tokens, TransformerConfig(**job))
    # today's block: the dense module's reference, which rotates
    _close(rotary, reference.forward(params, tokens, job, 64)[0], 2e-5)
    # a configuration that names every new field at its default is the
    # same configuration, to the bit
    named = TransformerConfig(**job, layer_types=["full_attention"] * 2,
                              linear_key_head_dim=0, linear_value_head_dim=0)
    assert hash(named) is not None and named.layer_types == (
        "full_attention",) * 2
    assert bool(jnp.all(forward(params, tokens, named) == rotary))
    # None: the same attention without the rotation
    plain = forward(params, tokens, TransformerConfig(**dense, rope_theta=None))
    assert float(jnp.max(jnp.abs(plain - rotary))) > 1e-2
    hybrid = _module("olmo_hybrid_block.py")
    full = {**dense, "rope_theta": None, "norm_eps": 1e-6,
            "layer_types": ["full_attention"] * 2,
            "linear_key_head_dim": 0, "linear_value_head_dim": 0,
            "linear_allow_neg_eigval": False}
    _close(plain, hybrid.forward(params, tokens, full, 64)[0], 2e-5)


def test_layer_types_are_checked_and_hashable():
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(n_layers=2, layer_types=["linear_attention"])
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(n_layers=1, layer_types=["sliding_attention"])
    cfg = TransformerConfig(**{**TOY, "layer_types": list(LLLF)})
    assert cfg == TransformerConfig(**TOY) and hash(cfg) == hash(
        TransformerConfig(**TOY))
    assert cfg.has_linear_layers and not TransformerConfig().has_linear_layers
    assert dataclasses.asdict(TransformerConfig())["layer_types"] is None


# Check J's tolerance, at a toy size wide enough to be steady (hidden 256;
# at hidden 64 two seeds read 0.07 and 0.28). The block is touchier than the
# dense one: a head's output passes an RMSNorm over its own few numbers, so
# where q_t and k_t are nearly orthogonal a rounding decides the sign of what
# the head adds. bfloat16 reads 0.055-0.064 here where the dense toy reads
# 0.015, and the float8 control 0.33-0.41 where the dense one reads 0.13-0.17;
# on the chip at the published widths they read 0.0235-0.0247 and 0.235-0.245,
# and the module's limit (0.076) is their geometric middle.
WIDER = dict(d_model=256, n_heads=4, d_ff=512, linear_key_head_dim=48,
             linear_value_head_dim=96)


@pytest.mark.parametrize("seed", [5, 6])
def test_bfloat16_stays_inside_the_modules_limit_and_float8_does_not(
        block, seed):
    job = _job(dtype="bfloat16", **WIDER)
    cfg = TransformerConfig(**{**TOY, "dtype": "bfloat16", **WIDER})
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
    cfg = TransformerConfig(**TOY)
    params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg, lr=1e-2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[2] < losses[1] < losses[0]


# -- over a mesh, and where it is refused --------------------------------


def test_the_layer_over_data_2_model_2_equals_one_device():
    from dynolog_tpu.parallel.sharding import (
        MeshSpec, batch_sharding, make_mesh, shard_params)

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cfg = TransformerConfig(**TOY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    want = jax.jit(forward, static_argnums=2)(params, tokens, cfg)
    mesh = make_mesh(MeshSpec(data=2, model=2), jax.devices()[:4])
    shardings = shard_params(params, mesh)
    rules = {name: s.spec for name, s in shardings["layers"][0].items()}
    # the large matrices by heads on `model`, the output matrix the other
    # way, the small ones whole: none falls to replication by default
    assert all(rules[n] == jax.sharding.PartitionSpec(None, "model")
               for n in ("gdn_q", "gdn_k", "gdn_v", "gdn_g"))
    assert rules["gdn_o"] == jax.sharding.PartitionSpec("model", None)
    assert all(rules[n] == jax.sharding.PartitionSpec()
               for n in ("gdn_conv_q", "gdn_conv_k", "gdn_conv_v", "gdn_b",
                         "gdn_a", "gdn_a_log", "gdn_dt_bias"))
    placed = jax.device_put(params, shardings)
    assert placed["layers"][0]["gdn_q"].addressable_shards[0].data.shape == (
        64, 8)
    got = jax.jit(lambda p, t: forward(p, t, cfg))(
        placed, jax.device_put(tokens, batch_sharding(mesh)))
    # sums split over `model` add up in another order
    _close(got, want, 2e-4)


def test_the_pipeline_refuses_a_linear_layer_aloud():
    from dynolog_tpu.parallel import pipeline
    from dynolog_tpu.parallel.sharding import MeshSpec, make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs two (virtual) devices")
    mesh = make_mesh(MeshSpec(pipe=2), jax.devices()[:2])
    cfg = TransformerConfig(**TOY)
    with pytest.raises(AssertionError, match="linear_attention"):
        pipeline.init_pipeline_params(jax.random.PRNGKey(0), cfg, mesh)
    with pytest.raises(AssertionError, match="linear_attention"):
        pipeline.pipeline_loss({}, jnp.zeros((2, 128), jnp.int32), cfg, mesh, 1)


# -- the product's account of ops that nest ------------------------------

US = 1e-3  # a microsecond in ms


def _rows(summary):
    return {row["op"]: row for row in summary["top_ops"]}


@pytest.mark.parametrize("shuffle", [False, True])
def test_an_ops_time_is_counted_once_under_nesting(shuffle):
    steps = 3
    data = xf.build_nested_xspace(steps=steps, shuffle=shuffle)
    [plane] = trace.summarize_xplane_bytes(data, group=False)
    ops = plane.ops
    # inclusive, what the bytes say: the conditional spans its two and 5 us
    # of its own, the while its six and 5 more
    assert (ops["conditional.5"].total_ps, ops["conditional.5"].count) == (
        25_000_000 * steps, steps)
    assert (ops["while.1"].total_ps, ops["while.1"].count) == (
        95_000_000 * steps, steps)
    assert (ops["fusion.2"].total_ps, ops["fusion.2"].count) == (
        10_000_000 * 2 * steps, 2 * steps)
    # own time: less what lies directly inside
    assert ops["while.1"].self_ps == 5_000_000 * steps
    assert ops["while.1"].held == 6 * steps
    assert ops["conditional.5"].self_ps == 5_000_000 * steps
    assert ops["conditional.5"].held == 2 * steps
    leaves = [a for name, a in ops.items()
              if name not in ("while.1", "conditional.5")]
    assert all(a.self_ps == a.total_ps and a.held == 0 for a in leaves)
    # self times add up to the time the device was busy: 115 us a step
    assert sum(a.self_ps for a in ops.values()) == 115_000_000 * steps

    summary = trace._summarize_planes(trace.summarize_xplane_bytes(data))
    rows = _rows(summary)
    assert sum(r["pct"] for r in rows.values()) == pytest.approx(100, abs=0.1)
    # rows rank by own time: the loop that holds everything comes last but
    # one, where inclusive time would name it first
    assert summary["top_ops"][0]["op"] == "fusion"
    assert rows["while"]["total_ms"] == pytest.approx(95 * steps * US)
    assert rows["while"]["self_ms"] == pytest.approx(5 * steps * US)
    assert rows["while"]["pct"] == pytest.approx(100 * 5 / 115, abs=0.05)
    assert rows["fusion"]["self_ms"] == rows["fusion"]["total_ms"] == (
        pytest.approx(60 * steps * US))
    assert rows["fusion"]["count"] == 5 * steps
    [row] = summary["planes"]
    # the all-reduce inside the loop, over self time: 20 of 115
    assert row["collective_pct"] == pytest.approx(100 * 20 / 115, abs=0.01)
    assert row["collectives"] == {
        "all-reduce": {"total_ms": pytest.approx(20 * steps * US),
                       "count": steps}}
    # the trip count reads from `loops`: 6 events inside each while
    assert row["loops"] == {
        "while": {"total_ms": pytest.approx(95 * steps * US),
                  "count": steps, "inside": 6 * steps},
        "conditional": {"total_ms": pytest.approx(25 * steps * US),
                        "count": steps, "inside": 2 * steps}}
    assert list(row["loops"]) == ["while", "conditional"]


def test_an_event_that_overlaps_without_lying_inside_is_left_whole():
    events = [xf._event(1, 0, 10_000_000), xf._event(2, 5_000_000, 10_000_000),
              xf._event(3, 6_000_000, 2_000_000)]
    plane = xf._field_str(2, "/device:TPU:0") + xf._field_bytes(
        3, xf._line(0, "XLA Ops", 0, events))
    for i, name in enumerate(("a.1", "b.2", "c.3"), start=1):
        plane += xf._field_bytes(4, xf._event_metadata(i, name, name))
    [got] = trace.summarize_xplane_bytes(xf._field_bytes(1, plane), group=False)
    assert got.ops["a.1"].self_ps == got.ops["a.1"].total_ps == 10_000_000
    # c lies inside b, which overlaps a without lying inside it
    assert got.ops["b.2"].self_ps == 8_000_000 and got.ops["b.2"].held == 1
    assert got.ops["a.1"].held == 0


def test_nothing_nests_so_self_time_is_total_time():
    data = xf.build_xspace(planes=2, lines_per_plane=2, events_per_line=200)
    planes = trace.summarize_xplane_bytes(data)
    assert all(a.self_ps == a.total_ps and a.held == 0
               for p in planes for a in p.ops.values())
    summary = trace._summarize_planes(planes)
    assert all(r["self_ms"] == r["total_ms"] for r in summary["top_ops"])
    assert all(p["loops"] == {} for p in summary["planes"])
    totals = [r["total_ms"] for r in summary["top_ops"]]
    assert totals == sorted(totals, reverse=True)


def test_the_cli_prints_own_time_and_what_a_loop_holds(tmp_path, capsys):
    path = tmp_path / "nested.xplane.pb"
    path.write_bytes(xf.build_nested_xspace())
    assert trace.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "self ms" in out and "holding 18" in out
    assert trace.main([str(path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["planes"][0]["loops"]["while"]["inside"] == 18
    assert {"self_ms", "total_ms", "pct"} <= set(doc["top_ops"][0])


def test_diagnose_names_the_slower_body_op_once_and_not_the_while():
    def summary(**scale):
        return trace._summarize_planes(trace.summarize_xplane_bytes(
            xf.build_nested_xspace(steps=20, scale=scale), group=False))

    report = diagnose.diagnose(summary(), summary(**{"fusion.3": 2.0}))
    named = [f["op"] for f in report["findings"] if f["op"]]
    assert named == ["fusion.3"]
    [finding] = [f for f in report["findings"] if f["op"] == "fusion.3"]
    assert finding["kind"].endswith("_regression")
    assert finding["severity_pct"] == pytest.approx(100.0)
    # by inclusive time the while (95 -> 105 us a call) would be a finding too
    inclusive = trace.diff_summaries(summary(), summary(**{"fusion.3": 2.0}))
    row = {r["op"]: r for r in inclusive["ops"]}["while.1"]
    assert row["delta_ms_per_call"] == 0 and row["impact_ms"] == 0
