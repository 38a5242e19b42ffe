#!/usr/bin/env python3
"""Ahead-of-time compiles of a configuration's training step for a described
v5e chip: no chip, no chip time (on-chip-measurement guide, section 2.3).

    JAX_PLATFORMS=cpu python perfbench/aot.py <config> [batch,seq ...]

Prints, for each candidate batch x sequence, XLA's memory analysis of the
step (arguments = the donated resident state, temporaries, total) and which
candidates fit under FIT_BYTES. PR 21 found that a step whose arguments +
temporaries came to 12.94 GB ran on the chip (12.45 GB on the runtime's HBM
gauge) and that the compiler refuses one at 16.0 GB; nothing between them
had met the chip. FIT_BYTES = 14.0 GB keeps 1.75 GB of the allocator's
15.75 GB for what the analysis of one program does not count: the batch,
the loss scalars a window keeps, the profiler's buffers during a capture.
The first candidate that fits is the
configuration's batch and sequence; the readings are pasted into the
configuration's `why` and into PERF.md. A compile that passes is not a chip
run.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

FIT_BYTES = 14.0e9
CANDIDATES = ((1, 4096), (2, 2048), (1, 2048))


def compile_step(config: dict, batch: int, seq: int, sharding):
    import jax
    import jax.numpy as jnp

    import cells
    import harness

    job = config["job"]
    reference = cells.load_reference(config)
    cfg = harness.transformer_config(job)
    from dynolog_tpu.models.train import make_optimizer, make_train_step

    params = jax.eval_shape(
        lambda k: reference.init_weights(k, job), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(make_optimizer().init, params)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            tree)

    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=sharding)
    return make_train_step(cfg).lower(
        place(params), place(opt_state), tokens).compile()


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import cells

    config = cells.load_config(argv[1])
    candidates = [tuple(int(x) for x in a.split(",")) for a in argv[2:]]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    chosen = None
    for batch, seq in candidates or CANDIDATES:
        t0 = time.time()
        try:
            mem = compile_step(config, batch, seq, one_chip).memory_analysis()
        except Exception as e:  # noqa: BLE001 - the compiler's refusal IS the reading
            print(f"{argv[1]} batch {batch} x seq {seq}: refused: "
                  f"{str(e).splitlines()[0][:300]}", flush=True)
            continue
        total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        fits = total <= FIT_BYTES
        print(f"{argv[1]} batch {batch} x seq {seq}: arguments "
              f"{mem.argument_size_in_bytes / 1e9:.2f} GB + temporaries "
              f"{mem.temp_size_in_bytes / 1e9:.2f} GB = {total / 1e9:.2f} GB "
              f"({'fits' if fits else 'over'} {FIT_BYTES / 1e9:.1f} GB; "
              f"compiled in {time.time() - t0:.0f} s)", flush=True)
        if fits and chosen is None:
            chosen = (batch, seq)
    print(f"{argv[1]}: chosen batch x sequence = {chosen}")
    return 0 if chosen else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
