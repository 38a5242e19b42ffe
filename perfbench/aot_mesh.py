#!/usr/bin/env python3
"""Ahead-of-time compiles of a MESH configuration's sharded training step
over the described `v5e:2x2` topology: no chip, no chip time. `aot.py`
sizes one chip's batch and sequence; this sizes depth (and, where depth
alone does not do, the sequence) of a configuration whose parameters and
Adam state are born sharded over the configuration's own mesh.

    JAX_PLATFORMS=cpu python perfbench/aot_mesh.py <config> [layers,batch,seq ...]

For each candidate it builds the mesh from the configuration's `deployment`
over the topology's devices, gives parameters and optimizer state the
shardings `harness.make_job` gives them and the batch the step's own
sharding, compiles `make_train_step(cfg, mesh)` and prints XLA's memory
analysis, which for a partitioned program is PER DEVICE: arguments (the
donated resident state of one chip) + temporaries, against the 14.0 GB
line `aot.py` uses and for its reasons. The deepest candidate that fits is
the configuration's depth; the lines go into its `aot` key and PERF.md.

It also compiles what check J runs on the same devices BEFORE the optimizer
state exists (the program's forward, and the float32 reference of the
module the configuration names, on the sharded weights), because a plain
reference keeps [batch, heads, seq, seq] float32 scores and has to fit
beside the weights. The reference is compiled as ONE program through the
module's `forward`, its one export that computes, whatever smaller programs
the module makes of it in a run: its arguments are all the weights, its
temporaries those of its largest piece (13B widths over 2x2: 3.25 + 3.29 GB,
where the dense module's head alone read 0.68 + 3.29). A compile that
passes is not a chip run.
"""

from __future__ import annotations

import sys
import time

import aot  # sets TPU_LOG_DIR / TPU_SKIP_MDS_QUERY and the import paths

DEPTHS = (8, 7, 6, 5, 4)


def shardings(reference, job: dict, mesh):
    """(abstract params, abstract optimizer state), placed as make_job
    places them: the program's own layout of its training state."""
    import jax

    from dynolog_tpu.models.train import make_optimizer, state_shardings

    optimizer = make_optimizer()
    params = jax.eval_shape(
        lambda k: reference.init_weights(k, job), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)

    def place(tree, where):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, where)

    param_shardings, opt_shardings = state_shardings(optimizer, params, mesh)
    return place(params, param_shardings), place(opt_state, opt_shardings)


def sizes(compiled) -> tuple:
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes / 1e9, mem.temp_size_in_bytes / 1e9


def compile_candidate(config: dict, devices, layers: int, batch: int,
                      seq: int) -> dict:
    """GB per device of the step, and of check J's two sides."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    import cells
    import checks
    import harness
    from dynolog_tpu.models.train import make_train_step
    from dynolog_tpu.models.transformer import forward
    from dynolog_tpu.parallel.sharding import batch_sharding

    job = dict(config["job"], n_layers=layers, batch=batch, seq=seq)
    reference = cells.load_reference(config)
    cfg = harness.transformer_config(job)
    mesh = cells.build_mesh(config["deployment"], devices)
    params, opt_state = shardings(reference, job, mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=batch_sharding(mesh))
    out = {}
    out["step"] = sizes(make_train_step(cfg, mesh).lower(
        params, opt_state, tokens).compile())
    # Check J: tokens arrive uncommitted there, so replicated here.
    whole = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=replicated)
    last = min(checks.J_POSITIONS, seq)
    out["job_forward"] = sizes(jax.jit(
        lambda p, t: forward(p, t, cfg, mesh)[:, -last:]).lower(
            params, whole).compile())
    out["reference_forward"] = sizes(jax.jit(
        lambda p, t: reference.forward(p, t, job, last)).lower(
            params, whole).compile())
    return out


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    import cells

    config = cells.load_config(argv[1])
    job = config["job"]
    candidates = [tuple(int(x) for x in a.split(",")) for a in argv[2:]] or [
        (layers, job["batch"], job["seq"]) for layers in DEPTHS]
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chosen = None
    for layers, batch, seq in candidates:
        t0 = time.time()
        tag = f"{argv[1]} {layers} layers, batch {batch} x seq {seq}"
        try:
            got = compile_candidate(config, topo.devices, layers, batch, seq)
        except Exception as e:  # noqa: BLE001 - the compiler's refusal IS the reading
            print(f"{tag}: refused: {str(e).splitlines()[0][:300]}", flush=True)
            continue
        args, temps = got["step"]
        fits = (args + temps) * 1e9 <= aot.FIT_BYTES
        print(f"{tag}, per device: step arguments {args:.2f} GB + temporaries "
              f"{temps:.2f} GB = {args + temps:.2f} GB "
              f"({'fits' if fits else 'over'} {aot.FIT_BYTES / 1e9:.1f} GB); "
              + "; ".join(f"{name} {a:.2f} + {t:.2f}"
                          for name, (a, t) in got.items() if name != "step")
              + f" (compiled in {time.time() - t0:.0f} s)", flush=True)
        if fits and chosen is None:
            chosen = (layers, batch, seq)
    print(f"{argv[1]}: chosen layers, batch x sequence = {chosen}")
    return 0 if chosen else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
