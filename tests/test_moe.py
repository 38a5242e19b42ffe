"""The dropless expert layer (dynolog_tpu/models/moe.py) against its
equations written plainly, on the CPU in float32: alone under forced uneven
routings, the tile-aligned layout of one chip's own copies (its index maps
from counts alone, and a held share under routings that fill and starve its
groups; its gathers, and what lies between its grouped products, a block of
rows at a time as far as rows are used), over a four-device `expert` mesh
against one device (output, loss,
one step's weights, and the four shares' parts adding up to the whole), and
what the block gained for OLMoE's config (q/k norm, unrenormalised gates,
the balancing term over all k choices, the router z-loss) against the plain
reference the benchmark holds the job to (perfbench/olmoe_block.py, loaded
by path: it imports nothing of dynolog_tpu)."""

import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynolog_tpu.models import moe
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
    if cfg.n_experts_held:  # the layer's matrices are its share's alone
        held = range(cfg.first_expert_held,
                     cfg.first_expert_held + cfg.n_experts_held)
    for e in range(cfg.n_experts) if held is None else held:
        i = e - cfg.first_expert_held
        if cfg.mlp_act == "relu2":
            act = jnp.square(jax.nn.relu(h @ layer["experts_up"][i]))
        else:
            act = jax.nn.silu(h @ layer["experts_gate"][i]) * (
                h @ layer["experts_up"][i])
        y = y + gates[:, e:e + 1] * (act @ layer["experts_down"][i])
    counted = picks if cfg.moe_balance_all_k else picks[:, :1]
    balance = cfg.n_experts * jnp.sum(
        counted.mean((0, 1)) * probs.mean(0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y.reshape(x.shape), balance, z


def forced_layer(favoured, starved=None, strength=10.0, cfg=CFG, lean=4.0):
    """A layer whose router sends every token's first choices to
    `favoured` and none to `starved`: the inputs below share a direction
    `u` (as far as `lean`), and the router reads it."""
    layer = init_moe_layer(jax.random.PRNGKey(3), cfg)
    u = jnp.ones((CFG.d_model,)) / np.sqrt(CFG.d_model)
    router = layer["router"]
    for rank, e in enumerate(favoured):
        router = router.at[:, e].add((strength - rank) * u)
    if starved is not None:
        router = router.at[:, starved].add(-strength * u)
    x = 0.5 * jax.random.normal(
        jax.random.PRNGKey(4), (4, 16, CFG.d_model)) + lean * u
    return dict(layer, router=router), x


def close(a, b, tol=1e-5):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, (
        float(jnp.max(jnp.abs(a - b))), scale)


def scalar(f, cfg):
    """A number that reads all of the layer `f` returns."""
    def g(layer, x):
        y, balance, z = f(layer, x, cfg)
        return jnp.sum(jnp.sin(y)) + balance + z
    return g


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

    close(moe_mlp(layer, x, CFG)[0], plain_layer(layer, x, CFG)[0])
    got = jax.grad(scalar(moe_mlp, CFG), argnums=(0, 1))(layer, x)
    want = jax.grad(scalar(plain_layer, CFG), argnums=(0, 1))(layer, x)
    assert_trees_close(got, want)


def _counts(kind, held, n_sorted, rng):
    if kind == "none":
        return np.zeros(held, int)
    if kind == "one-group":  # one expert holds every copy there is
        return np.bincount([rng.integers(held)] * n_sorted, minlength=held)
    if kind == "all-held":  # every copy is some held expert's
        return np.bincount(rng.integers(0, held, n_sorted), minlength=held)
    return np.bincount(  # some copies are for experts that are not here
        rng.integers(0, held, rng.integers(0, n_sorted + 1)), minlength=held)


@pytest.mark.parametrize("align", [1, 8, moe.ALIGN])
@pytest.mark.parametrize("kind", ["none", "one-group", "all-held", "some"])
def test_the_aligned_maps_from_counts_alone(kind, align):
    """Every group starts on a multiple of `align`, each copy of a group
    lands once, in order, and what is left of the buffer is nowhere both
    ways: no place lands in padding and padding came from no place."""
    rng = np.random.default_rng(align)
    for _ in range(12):
        held, n_sorted = int(rng.integers(1, 7)), int(rng.integers(1, 80))
        sizes = _counts(kind, held, n_sorted, rng)
        rounded, lands, came = (np.asarray(a) for a in moe._aligned(
            jnp.asarray(sizes, jnp.int32), n_sorted, align))
        n = n_sorted + held * align
        assert lands.shape == (n_sorted,) and came.shape == (n,)
        assert (rounded % align == 0).all()
        assert ((0 <= rounded - sizes) & (rounded - sizes < align)).all()
        starts = np.cumsum(rounded) - rounded
        assert (starts % align == 0).all() and rounded.sum() <= n
        want_lands = np.full(n_sorted, n)
        want_came = np.full(n, n_sorted)
        sorted_starts = np.cumsum(sizes) - sizes
        for e in range(held):
            for i in range(sizes[e]):
                want_lands[sorted_starts[e] + i] = starts[e] + i
                want_came[starts[e] + i] = sorted_starts[e] + i
        np.testing.assert_array_equal(lands, want_lands)
        np.testing.assert_array_equal(came, want_came)
        there = lands < n  # the maps undo each other where a row is real
        np.testing.assert_array_equal(
            came[lands[there]], np.arange(n_sorted)[there])
        assert (came < n_sorted).sum() == sizes.sum()


# experts 2-5 of the eight are held, as one chip of an expert-parallel
# layer holds them
SHARE = TransformerConfig(**{
    **CFG.__dict__, "n_experts_held": 4, "first_expert_held": 2})
ROUTINGS = {
    # nothing forced: an eighth of the copies an expert, half of them held
    "even": dict(favoured=[], lean=0.0),
    # every copy of every token on held experts: the buffer's worst case
    "all-on-held": dict(favoured=[3, 4]),
    # every first choice on ONE held expert, the second not held: one group
    # of many tiles, three empty ones
    "one-held-expert": dict(favoured=[5, 7]),
}


@pytest.mark.parametrize("align", [8, moe.ALIGN])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_a_held_share_equals_the_plain_sum_forward_and_gradients(
        routing, align, monkeypatch):
    """The share's output, the gradient for its input and for every held
    expert's matrices, under routings that spread, fill and starve its
    groups; at a tile of 8 rows a group is several tiles and ends inside
    one, at the module's own every group is one partial tile."""
    monkeypatch.setattr(moe, "ALIGN", align)
    layer, x = forced_layer(cfg=SHARE, **ROUTINGS[routing])
    chosen = np.asarray(jax.lax.top_k(
        x.reshape(-1, 32) @ layer["router"], 2)[1])
    held = (chosen >= 2) & (chosen < 6)
    if routing == "all-on-held":
        assert held.all()
    elif routing == "one-held-expert":
        assert set(chosen[held]) == {5} and held.sum() == 64
    else:
        assert 0 < held.sum() < held.size and len(set(chosen[held])) == 4
    assert layer["experts_up"].shape[0] == 4
    got_y = moe_mlp(layer, x, SHARE)[0]
    close(got_y, plain_layer(layer, x, SHARE)[0])
    got = jax.grad(scalar(moe_mlp, SHARE), argnums=(0, 1))(layer, x)
    want = jax.grad(scalar(plain_layer, SHARE), argnums=(0, 1))(layer, x)
    assert_trees_close(got, want)
    for name in ("experts_gate", "experts_up", "experts_down"):
        for e in range(4):  # an expert no copy reached learns nothing
            reached = bool((chosen == 2 + e).any())
            assert bool(jnp.any(got[0][name][e] != 0)) == reached, (name, e)


def rows_used(layer, x, align=8):
    """The rows the groups of SHARE's four held experts take in the buffer,
    each rounded up to `align`."""
    chosen = np.asarray(jax.lax.top_k(
        x.reshape(-1, 32) @ layer["router"], 2)[1])
    held = np.bincount(chosen.reshape(-1), minlength=8)[2:6]
    return int((-(-held // align) * align).sum())


BLOCK = 16  # rows a trip in the cases below
N_PLACES = 3 * BLOCK + 5  # so that a fourth block would pass the end


@pytest.mark.parametrize(
    "used", [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, N_PLACES])
def test_a_gather_told_the_rows_used_is_the_whole_gather(used, monkeypatch):
    """Places before `used` point at a row or nowhere, the rest nowhere:
    block by block the same rows arrive, under `jit` with `used` traced,
    and the gradient is the whole gather's both ways round."""
    monkeypatch.setattr(moe, "BLOCK", BLOCK)
    rng = np.random.default_rng(used)
    rows = jnp.asarray(rng.normal(size=(24, 6)), jnp.float32)
    idx = rng.integers(0, 25, N_PLACES)  # 24: nowhere, inside the used too
    idx[0] = 3
    idx = jnp.asarray(np.where(np.arange(N_PLACES) < used, idx, 24), jnp.int32)
    want = moe._take_rows(rows, idx)
    got = jax.jit(moe._take_rows_used)(rows, idx, jnp.int32(used))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert bool(jnp.any(got != 0)) == (used > 0)

    # the buffer's side as the result (the dispatch) and as the cotangent's
    # (the combine): a permutation and its inverse, padding between
    perm = rng.permutation(N_PLACES)[:24]  # row i of `rows` goes to perm[i]
    to_buffer = np.full(N_PLACES, 24)
    to_buffer[perm] = np.arange(24)
    last = jnp.int32(perm.max() + 1)
    weights = jnp.asarray(rng.normal(size=(N_PLACES, 6)), jnp.float32)

    def out(take, **told):
        return lambda r: jnp.sum(weights * take(
            r, jnp.asarray(to_buffer), jnp.asarray(perm), **told))

    def back(take, **told):
        return lambda b: jnp.sum(rows * take(
            b, jnp.asarray(perm), jnp.asarray(to_buffer), **told))

    for f, x, told in ((out, rows, dict(used=last)),
                       (back, weights, dict(back_used=last))):
        want_value, want_grad = jax.value_and_grad(f(moe.take_rows))(x)
        got_value, got_grad = jax.jit(jax.value_and_grad(jax.checkpoint(
            f(moe.take_rows, **told))))(x)
        np.testing.assert_allclose(got_value, want_value, rtol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(got_grad), np.asarray(want_grad))


@pytest.mark.parametrize("used", [0, 1, BLOCK, BLOCK + 1, 3 * BLOCK + 1])
def test_the_trips_are_the_blocks_that_hold_the_rows_used(used, monkeypatch):
    """ceil(used / BLOCK) by the helper alone, and by what the loop moved:
    given places that all point at a row, it leaves the rows after its last
    block the zeros they were."""
    monkeypatch.setattr(moe, "BLOCK", BLOCK)
    trips = int(moe._trips(jnp.int32(used), BLOCK))
    assert trips == -(-used // BLOCK)
    rows = 1.0 + jnp.arange(24.0)[:, None] * jnp.ones((1, 6))
    idx = jnp.arange(N_PLACES, dtype=jnp.int32) % 24
    got = np.asarray(moe._take_rows_used(rows, idx, jnp.int32(used)))
    moved = min(trips * BLOCK, N_PLACES)
    np.testing.assert_array_equal(got[:moved], np.asarray(rows[idx[:moved]]))
    assert (got[moved:] == 0).all()


N_ROWS = 4 * BLOCK  # a buffer of whole blocks, as a job's is
USED = [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, N_ROWS]


@pytest.mark.parametrize("used", USED)
@pytest.mark.parametrize("into", [1, 2], ids=["relu2", "swiglu"])
def test_the_activation_told_the_rows_used_is_the_whole_pass(
        into, used, monkeypatch):
    """`activate` against the plain form over every row of the buffer (the
    raw products masked, the activation, `jax.vjp`), under `jit` with
    `used` traced: value and every cotangent equal before `used` and
    exactly zero from there on, whatever the products and the cotangent
    hold there (NaN here, inside the last block worked and past it)."""
    monkeypatch.setattr(moe, "BLOCK", BLOCK)
    rng = np.random.default_rng(used)
    there = (np.arange(N_ROWS) < used)[:, None]
    ct, *raw = (
        jnp.asarray(np.where(there, rng.normal(size=(N_ROWS, 6)), np.nan),
                    jnp.float32) for _ in range(into + 1))

    def whole(*raw):  # the plain form, written out
        if into == 1:
            return jnp.square(jax.nn.relu(jnp.where(there, raw[0], 0)))
        return jax.nn.silu(jnp.where(there, raw[0], 0)) * jnp.where(
            there, raw[1], 0)

    want, back = jax.vjp(whole, *raw)
    want_cts = back(ct)

    @jax.jit
    def bounded(raw, ct, used):
        got, back = jax.vjp(lambda *raw: moe.activate(raw, used), *raw)
        return got, back(ct)

    got, got_cts = bounded(tuple(raw), ct, jnp.int32(used))
    assert len(got_cts) == into
    for a, b in zip((got, *got_cts), (want, *want_cts)):
        a, b = np.asarray(a), np.asarray(b)
        assert (a[used:] == 0).all() and (b[used:] == 0).all()
        if used:
            close(a[:used], b[:used])
            assert np.abs(a[:used]).max() > 0


@pytest.mark.parametrize("rows", [N_ROWS, 3 * BLOCK + BLOCK // 2])
@pytest.mark.parametrize("used", USED[:-1])
def test_the_activation_works_the_blocks_that_hold_the_rows_used(
        used, rows, monkeypatch):
    """ceil(used / block) trips, by the helper and by what the loop worked:
    given work that marks every row it is handed, the rows after its last
    block stay the zeros they were; inside a trip the rows from `used` on are
    handed over as zeros. A block is BLOCK rows, or where the buffer is not
    a whole number of them the largest common divisor of the two."""
    monkeypatch.setattr(moe, "BLOCK", BLOCK)
    block = BLOCK if rows == N_ROWS else BLOCK // 2
    trips = int(moe._trips(jnp.int32(used), block))
    assert trips == -(-used // block)
    source = 1.0 + jnp.arange(float(rows))[:, None] * jnp.ones((1, 6))
    marked, seen = (np.asarray(a) for a in moe._work_rows_used(
        lambda rows: (jnp.ones_like(rows), rows), (source,),
        jnp.int32(used), 2))
    worked = trips * block
    assert (marked[:worked] == 1).all() and (marked[worked:] == 0).all()
    np.testing.assert_array_equal(seen[:used], np.asarray(source[:used]))
    assert (seen[used:] == 0).all()


NONE_HELD = dict(favoured=[0, 7])  # both choices of every token elsewhere


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
@pytest.mark.parametrize("routing", ["even", "all-on-held", "none-held"])
def test_a_held_share_gathered_by_blocks_equals_the_plain_sum(
        routing, act, monkeypatch):
    """Blocks of 16 rows in a buffer of 128 + 4 x 8: at even routing the
    loops (the gathers' and the activation's) stop a few blocks in, with
    every copy held they work them all, with none held not one; output and
    every gradient as the plain sum's, with experts of three matrices and
    of two, the step jitted as a job's is."""
    monkeypatch.setattr(moe, "ALIGN", 8)
    monkeypatch.setattr(moe, "BLOCK", BLOCK)
    cfg = TransformerConfig(**{**SHARE.__dict__, "mlp_act": act})
    layer, x = forced_layer(
        cfg=cfg, **{**ROUTINGS, "none-held": NONE_HELD}[routing])
    assert ("experts_gate" in layer) == (act == "swiglu")
    used = rows_used(layer, x)
    assert {"even": 32 < used < 96, "all-on-held": 128 <= used,
            "none-held": used == 0}[routing], used
    got_y = jax.jit(lambda l, x: moe_mlp(l, x, cfg)[0])(layer, x)
    close(got_y, plain_layer(layer, x, cfg)[0])
    if routing == "none-held":
        assert not bool(jnp.any(got_y))
    got = jax.jit(jax.grad(scalar(moe_mlp, cfg), argnums=(0, 1)))(layer, x)
    want = jax.grad(scalar(plain_layer, cfg), argnums=(0, 1))(layer, x)
    assert_trees_close(got, want)


def test_a_token_no_held_expert_takes_gets_exactly_nothing():
    """Its copies land nowhere and come from nowhere: the routed part of
    its output is 0.0, not the rounding of something small."""
    layer, x = forced_layer(cfg=SHARE, **ROUTINGS["even"])
    chosen = np.asarray(jax.lax.top_k(
        x.reshape(-1, 32) @ layer["router"], 2)[1])
    none_held = ~((chosen >= 2) & (chosen < 6)).any(axis=1)
    assert 4 < none_held.sum() < 60
    y = np.asarray(moe_mlp(layer, x, SHARE)[0]).reshape(-1, 32)
    assert (y[none_held] == 0.0).all()
    assert (np.abs(y[~none_held]).max(axis=1) > 1e-4).all()


@pytest.mark.parametrize("block", [BLOCK, moe.BLOCK])
@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_what_lies_past_the_groups_reaches_no_gradient(
        act, block, monkeypatch):
    """On the TPU a grouped product writes no row past its (rounded)
    groups, forward or transposed. Here those rows are made NaN; neither
    the output nor any gradient may see them, with experts of three
    matrices and of two, and groups that end inside a tile. Some of the NaN
    rows lie inside the last block the activation's loop works and the rest
    past it, at blocks of 16 rows and at the module's own (32 of this
    buffer's 160 rows)."""
    real = jax.lax.ragged_dot

    def past(x, group_sizes):
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(group_sizes), x, jnp.nan)

    @jax.custom_vjp
    def dirty(lhs, rhs, group_sizes):
        return real(lhs, rhs, group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return past(real(lhs, rhs, group_sizes), group_sizes), (
            lhs, rhs, group_sizes)

    def bwd(res, ct):
        lhs, rhs, group_sizes = res
        d_lhs, d_rhs = jax.vjp(
            lambda a, b: real(a, b, group_sizes), lhs, rhs)[1](ct)
        return past(d_lhs, group_sizes), d_rhs, None

    dirty.defvjp(fwd, bwd)
    monkeypatch.setattr(moe, "ALIGN", 8)
    monkeypatch.setattr(moe, "BLOCK", block)
    cfg = TransformerConfig(**{**SHARE.__dict__, "mlp_act": act})
    layer, x = forced_layer(cfg=cfg, **ROUTINGS["even"])
    assert ("experts_gate" in layer) == (act == "swiglu")
    used = rows_used(layer, x)
    # rows of a worked block past the groups, and a block that is not worked
    rows = math.gcd(128 + 4 * 8, block)
    assert used % rows and -(-used // rows) * rows < 128 + 4 * 8
    want = jax.grad(scalar(moe_mlp, cfg), argnums=(0, 1))(layer, x)
    close(moe_mlp(layer, x, cfg)[0], plain_layer(layer, x, cfg)[0])
    monkeypatch.setattr(jax.lax, "ragged_dot", dirty)
    got = jax.grad(scalar(moe_mlp, cfg), argnums=(0, 1))(layer, x)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(got))
    assert_trees_close(got, want, tol=1e-6)


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
