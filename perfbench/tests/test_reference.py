"""Check J's reference: it agrees with the program's forward in float32 at
a toy size, the bfloat16 job passes the cell's limit, and the float8 control
fails it."""

import dataclasses

import jax
import pytest

import checks
import harness
import reference

JOB = dict(vocab_size=512, d_model=128, n_layers=3, n_heads=4, d_ff=384,
           max_seq_len=64, rope_theta=500000.0, dtype="float32",
           attn_impl="reference")


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 512)


def weights(job, seed=5):
    return jax.jit(lambda k: reference.init_weights(k, job))(
        harness.seed_key(seed))


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


def test_bfloat16_job_passes_and_float8_control_fails(tokens):
    from dynolog_tpu.models.transformer import forward

    job = dict(JOB, dtype="bfloat16")
    params, cfg = weights(job), harness.transformer_config(job)
    want, want_loss = reference.forward(params, tokens, job, 16)
    sound = reference.rel_rms(forward(params, tokens, cfg)[:, -16:], want)
    control, control_loss = reference.forward(
        params, tokens, job, 16, rounding=reference.lower)
    lower = reference.rel_rms(control, want)
    assert sound <= checks.J_LOGIT_REL_RMS_LIMIT < lower
    assert lower > 3 * sound
    ok = checks.check_j({"logit_rel_rms": sound, "ref_loss": float(want_loss),
                         "step_loss": float(want_loss)})
    bad = checks.check_j({"logit_rel_rms": lower, "ref_loss": float(want_loss),
                          "step_loss": float(control_loss)})
    assert ok["ok"] and not bad["ok"]
    assert [p["ok"] for p in bad["compared"]][0] is False


def test_weights_come_from_the_seed_alone():
    a, b, c = weights(JOB, 5), weights(JOB, 5), weights(JOB, 6)
    assert bool((a["layers"][1]["wq"] == b["layers"][1]["wq"]).all())
    assert not bool((a["layers"][1]["wq"] == c["layers"][1]["wq"]).all())
    big = weights(JOB, 3_000_000_011)  # more than 32 signed bits hold
    assert big["w_out"].shape == (128, 512)
    cfg = dataclasses.asdict(harness.transformer_config(JOB))
    assert cfg["d_model"] == 128 and "batch" not in cfg
