#!/usr/bin/env python3
"""Check J's two readings, on the chip, at a cell's own size, many seeds in
one process (set-up is long, a reading is short):

    python3 perfbench/control.py <config> <first seed> <seeds>

For each seed: the weights and the batch as a run makes them; the SOUND
reading (the program's forward and loss against the float32 reference, what
check J compares in every run) and the CONTROL's (the reference with every
weight rounded to float8 e4m3, the precision below the configuration's
bfloat16, put in the program's place). The limit of check J goes above the
largest sound reading and below the smallest control reading
(checks.J_LOGIT_REL_RMS_LIMIT; PERF.md gives the readings). The benchmark's
own runs never run this. Needs a TPU, like run.py.
"""

from __future__ import annotations

import json
import sys

import cells
import checks
import harness
import reference


def readings(job: dict, seed: int) -> dict:
    import jax

    from dynolog_tpu.models.transformer import forward, loss_fn

    cfg = harness.transformer_config(job)
    key_w, key_b = jax.random.split(harness.seed_key(seed))
    params = jax.jit(lambda k: reference.init_weights(k, job))(key_w)
    tokens = jax.random.randint(
        key_b, (job["batch"], job["seq"]), 0, job["vocab_size"], "int32")
    last = min(checks.J_POSITIONS, job["seq"])
    want, want_loss = reference.forward(params, tokens, job, last)
    got = jax.jit(lambda p, t: forward(p, t, cfg)[:, -last:])(params, tokens)
    got_loss = jax.jit(lambda p, t: loss_fn(p, t, cfg))(params, tokens)
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
    config, first, count = argv[1], int(argv[2]), int(argv[3])
    job = cells.load_config(config)["job"]
    sys.path.insert(0, str(cells.ROOT))
    harness.require_chips(1)
    rows = []
    for seed in range(first, first + count):
        rows.append(readings(job, seed))
        print(json.dumps(rows[-1]), flush=True)
    summary = {
        "config": config, "seeds": count,
        "sound_rel_rms_max": max(r["sound_rel_rms"] for r in rows),
        "control_rel_rms_min": min(r["control_rel_rms"] for r in rows),
        "sound_loss_gap_max": max(r["sound_loss_gap"] for r in rows),
        "control_loss_gap_min": min(r["control_loss_gap"] for r in rows),
        "limit_rel_rms": checks.J_LOGIT_REL_RMS_LIMIT,
    }
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main(sys.argv))
    except harness.RunRefused as e:
        sys.exit(f"perfbench/control.py: {e}")
