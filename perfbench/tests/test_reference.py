"""Check J's reference, for every module a configuration names: the toy
configurations under tests/data carry a job for each (`toy-cpu.json` the
dense block's `perfbench/reference.py`, `toy-moe.json` the expert layer's
`tests/data/moe_block.py`). Sound, the program's forward passes the module's
own limit; the module's own lower-precision control fails it. A later
block's module joins as one more toy configuration, not as a copy of this
file."""

import dataclasses
import json

import jax
import pytest

import cells
import checks
import harness
import reference

DATA = cells.HERE / "tests" / "data"
JOB = dict(vocab_size=512, d_model=128, n_layers=3, n_heads=4, d_ff=384,
           max_seq_len=64, rope_theta=500000.0, dtype="float32",
           attn_impl="reference")
LAST = 16


def toy_configs() -> dict:
    """Module file -> the first toy configuration that names it."""
    found = {}
    for path in sorted(DATA.glob("toy-*.json")):
        with open(path) as f:
            config = json.load(f)
        found.setdefault(config["reference"], config)
    return found


TOYS = toy_configs()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 512)


def weights(job, seed=5, module=reference):
    return jax.jit(lambda k: module.init_weights(k, job))(
        harness.seed_key(seed))


def test_every_module_a_configuration_names_has_a_toy():
    bench = cells.load_benchmark()
    named = {cells.load_config(c["name"])["reference"]
             for c in bench["configs"]}
    assert named <= set(TOYS) and len(TOYS) >= 2
    layouts = {name: sorted(weights(c["job"], module=cells.load_reference(c))
                            ["layers"][0])
               for name, c in TOYS.items()}
    assert len({tuple(v) for v in layouts.values()}) == len(TOYS)


def test_reference_agrees_with_the_program_in_float32(tokens):
    from dynolog_tpu.models.transformer import forward, loss_fn

    params, cfg = weights(JOB), harness.transformer_config(JOB)
    with jax.default_matmul_precision("highest"):
        got = forward(params, tokens, cfg)[:, -16:]
        got_loss = float(loss_fn(params, tokens, cfg))
    want, want_loss = reference.forward(params, tokens, JOB, 16)
    # float32 against float32: only the order of sums differs
    assert reference.rel_rms(got, want) < 1e-5
    assert abs(got_loss - float(want_loss)) < 1e-5


@pytest.mark.parametrize("name", sorted(TOYS))
def test_sound_job_passes_and_the_modules_own_control_fails(name):
    """At the toy's size and in the type it states: bfloat16 against the
    float8 control for the dense block, float32 against bfloat16 for the
    expert layer (whose loss carries the step's balancing term)."""
    from dynolog_tpu.models.transformer import forward, loss_fn

    config = TOYS[name]
    job, module = config["job"], cells.load_reference(config)
    limits = (module.J_LOGIT_REL_RMS_LIMIT, module.J_LOSS_ABS_LIMIT)
    cfg = harness.transformer_config(job)
    params = weights(job, module=module)
    batch = jax.random.randint(
        jax.random.PRNGKey(1), (job["batch"], job["seq"]), 0,
        job["vocab_size"])
    want, want_loss = module.forward(params, batch, job, LAST)
    sound = module.rel_rms(forward(params, batch, cfg)[:, -LAST:], want)
    control, control_loss = module.forward(
        params, batch, job, LAST, rounding=module.lower)
    lower = module.rel_rms(control, want)
    assert sound <= limits[0] < lower
    assert lower > 3 * sound
    ok = checks.check_j(
        {"logit_rel_rms": sound, "ref_loss": float(want_loss),
         "step_loss": float(loss_fn(params, batch, cfg))}, *limits)
    bad = checks.check_j(
        {"logit_rel_rms": lower, "ref_loss": float(want_loss),
         "step_loss": float(control_loss)}, *limits)
    assert ok["ok"] and not bad["ok"]
    assert [p["ok"] for p in bad["compared"]][0] is False
    # each number is printed beside the limit of the module that was loaded
    assert [p["limit"] for p in ok["compared"]] == [
        f"<= {limits[0]}", f"<= {limits[1]}"]


def test_the_dense_blocks_limits_read_as_they_did():
    """The accepted cells' result lines carry these words: the driver sees
    no limit change. `checks` keeps none of its own and hands the dense
    block's through to tests/test_sharded_job.py, which asks it for them."""
    j = {"logit_rel_rms": 0.0202, "ref_loss": 11.5, "step_loss": 11.5005}
    parts = checks.check_j(j, reference.J_LOGIT_REL_RMS_LIMIT,
                           reference.J_LOSS_ABS_LIMIT)["compared"]
    assert [p["limit"] for p in parts] == ["<= 0.05", "<= 0.003"]
    assert parts[0]["what"] == ("||job logits - reference|| / ||reference||, "
                                "last 256 positions")
    assert parts[1]["what"] == "|first step's loss - reference loss|"
    assert "J_LOGIT_REL_RMS_LIMIT" not in vars(checks)
    assert "J_LOSS_ABS_LIMIT" not in vars(checks)
    assert checks.J_LOGIT_REL_RMS_LIMIT == 0.05
    assert checks.J_LOSS_ABS_LIMIT == 0.003
    with pytest.raises(AttributeError):
        checks.J_NO_SUCH_LIMIT


def test_weights_come_from_the_seed_alone():
    a, b, c = weights(JOB, 5), weights(JOB, 5), weights(JOB, 6)
    assert bool((a["layers"][1]["wq"] == b["layers"][1]["wq"]).all())
    assert not bool((a["layers"][1]["wq"] == c["layers"][1]["wq"]).all())
    big = weights(JOB, 3_000_000_011)  # more than 32 signed bits hold
    assert big["w_out"].shape == (128, 512)
    cfg = dataclasses.asdict(harness.transformer_config(JOB))
    assert cfg["d_model"] == 128 and "batch" not in cfg
