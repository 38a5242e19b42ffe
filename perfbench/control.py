#!/usr/bin/env python3
"""Check J's two readings, on the chip, at a cell's own size, many seeds in
one process (set-up is long, a reading is short):

    python3 perfbench/control.py <config> <first seed> <seeds>

For each seed: the weights and the batch as a run makes them, by the module
the configuration names under `reference`; the SOUND reading (the program's
forward and loss against that module's float32 reference, what check J
compares in every run) and the CONTROL's (the reference with every weight
rounded by the module's own `lower`, the precision below the one the
configuration states, put in the program's place). Over the mesh the
configuration names where the machine holds its chips, on one chip where it
does not. A module's limits go above the largest sound reading and below
the smallest control reading; the summary prints both beside the limits the
module states (PERF.md gives the readings). The benchmark's own runs never
run this. Needs a TPU, like run.py.
"""

from __future__ import annotations

import json
import sys

import cells
import checks
import harness


def readings(reference, job: dict, mesh, seed: int) -> dict:
    import jax

    from dynolog_tpu.models.transformer import forward, loss_fn

    cfg = harness.transformer_config(job)
    shardings = None
    if mesh is not None:
        from dynolog_tpu.parallel.sharding import shard_params

        shardings = shard_params(jax.eval_shape(
            lambda k: reference.init_weights(k, job), harness.seed_key(0)),
            mesh)
    key_w, key_b = jax.random.split(harness.seed_key(seed))
    params = jax.jit(lambda k: reference.init_weights(k, job),
                     out_shardings=shardings)(key_w)
    tokens = jax.random.randint(
        key_b, (job["batch"], job["seq"]), 0, job["vocab_size"], "int32")
    last = min(checks.J_POSITIONS, job["seq"])
    want, want_loss = reference.forward(params, tokens, job, last)
    got = jax.jit(lambda p, t: forward(p, t, cfg, mesh)[:, -last:])(
        params, tokens)
    got_loss = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(params, tokens)
    low, low_loss = reference.forward(
        params, tokens, job, last, rounding=reference.lower)
    return {
        "seed": seed,
        "sound_rel_rms": reference.rel_rms(got, want),
        "control_rel_rms": reference.rel_rms(low, want),
        "sound_loss_gap": abs(float(got_loss) - float(want_loss)),
        "control_loss_gap": abs(float(low_loss) - float(want_loss)),
    }


def main(argv) -> int:
    import jax

    name, first, count = argv[1], int(argv[2]), int(argv[3])
    config = cells.load_config(name)
    reference = cells.load_reference(config)
    sys.path.insert(0, str(cells.ROOT))
    harness.require_chips(1)
    devices = jax.devices()
    mesh = (cells.build_mesh(config["deployment"], devices)
            if len(devices) >= config["deployment"]["chips"] else None)
    rows = []
    for seed in range(first, first + count):
        rows.append(readings(reference, config["job"], mesh, seed))
        print(json.dumps(rows[-1]), flush=True)
    summary = {
        "config": name, "reference": config["reference"], "seeds": count,
        "mesh": config["deployment"]["mesh"] if mesh is not None else None,
        "sound_rel_rms_max": max(r["sound_rel_rms"] for r in rows),
        "control_rel_rms_min": min(r["control_rel_rms"] for r in rows),
        "limit_rel_rms": reference.J_LOGIT_REL_RMS_LIMIT,
        "sound_loss_gap_max": max(r["sound_loss_gap"] for r in rows),
        "control_loss_gap_min": min(r["control_loss_gap"] for r in rows),
        "limit_loss_gap": reference.J_LOSS_ABS_LIMIT,
    }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main(sys.argv))
    except (harness.RunRefused, cells.BenchmarkError) as e:
        sys.exit(f"perfbench/control.py: {e}")
