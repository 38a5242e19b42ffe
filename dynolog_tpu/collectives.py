"""ICI collective telemetry: all-gather / reduce-scatter / all-reduce
bandwidth and latency over a device mesh, surfaced as dynolog metrics.

BASELINE config 5: "all-gather/reduce-scatter BW + latency counters surfaced
as dynolog metrics". The TPU runtime exposes no host-visible per-collective
counters (DCGM's nvlink counters have no libtpu analog), so this module
*measures* them: it runs jitted collectives over the local mesh and merges
the achieved bus bandwidth + small-message latency into the exporter
snapshot that dynologd's file backend polls (field ids 13-20 in
src/tpumon/TpuMetricBackend.cpp).

Run periodically on an idle pod (or at job startup) to track ICI health:

    python -m dynolog_tpu.collectives --merge-into /tmp/dynolog_tpu_metrics.json

Bus-bandwidth accounting per device for n devices and per-device shard of S
bytes (the standard ring-collective model, e.g. the jax-ml scaling book):
all_gather receives (n-1)·S; reduce_scatter moves (n-1)/n · S_total;
all-reduce (psum) costs 2·(n-1)/n · S_total.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

LATENCY_SIZE = 8 * 1024  # small message for latency probe
DEFAULT_SIZE = 4 * 1024 * 1024  # per-device shard bytes for BW probe
WARMUP = 3
ITERS = 10


def _mesh_and_ops():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("x",))

    def wrap(f, out_spec):
        sm = jax.shard_map(
            f, mesh=mesh, in_specs=P("x"), out_specs=out_spec,
            check_vma=False)
        return jax.jit(sm)

    import jax.numpy as jnp
    from jax import lax

    ops = {
        "all_gather": wrap(
            lambda x: lax.all_gather(x, "x", tiled=True), P(None)
        ),
        "reduce_scatter": wrap(
            lambda x: lax.psum_scatter(x, "x", tiled=True), P("x")
        ),
        "all_reduce": wrap(lambda x: lax.psum(x, "x"), P(None)),
    }
    return mesh, ops, n


def _time_op(fn, x, iters: int = ITERS) -> float:
    import jax

    for _ in range(WARMUP):
        fn(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def measure(shard_bytes: int = DEFAULT_SIZE) -> dict:
    """Returns {metric_name: value} with BW in Gbit/s and latency in µs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh, ops, n = _mesh_and_ops()
    # f32 elements per device shard, rounded to a multiple of n so
    # psum_scatter's tiling divides evenly.
    elems = max(n, shard_bytes // 4)
    elems += (-elems) % n
    total = jnp.ones((elems * n,), jnp.float32)
    total = jax.device_put(total, NamedSharding(mesh, P("x")))

    wire_bytes = {
        # per-device bytes over the interconnect, ring model
        "all_gather": (n - 1) * elems * 4,
        "reduce_scatter": (n - 1) * elems * 4 / n if n > 1 else 0,
        "all_reduce": 2 * (n - 1) * elems * 4 / n if n > 1 else 0,
    }

    metrics: dict[str, float] = {"collective_mesh_devices": float(n)}
    for name, fn in ops.items():
        dt = _time_op(fn, total)
        if n > 1 and wire_bytes[name] > 0:
            metrics[f"ici_{name}_gbps"] = wire_bytes[name] * 8 / dt / 1e9
        metrics[f"ici_{name}_us"] = dt * 1e6

    # Small-message latency probe (shard count rounded to the mesh size,
    # same divisibility requirement as the BW probe).
    small_elems = max(n, LATENCY_SIZE // 4)
    small_elems += (-small_elems) % n
    small = jax.device_put(
        jnp.ones((small_elems,), jnp.float32), NamedSharding(mesh, P("x"))
    )
    metrics["ici_latency_us"] = _time_op(ops["all_reduce"], small) * 1e6
    return metrics


def merge_into_snapshot(metrics: dict, path: str) -> None:
    """Attach collective metrics to device 0's entry in the exporter
    snapshot (created if missing) so the daemon's file backend ingests them."""
    snapshot = {"devices": [], "ts_ms": int(time.time() * 1000)}
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict):
                snapshot = loaded
        except (OSError, ValueError):
            pass
    if not snapshot.get("devices"):
        snapshot["devices"] = [{"device": 0, "chip_type": "tpu", "metrics": {}}]
    dev0 = snapshot["devices"][0]
    dev0.setdefault("metrics", {}).update(
        {k: v for k, v in metrics.items() if isinstance(v, (int, float))}
    )
    snapshot["ts_ms"] = int(time.time() * 1000)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(snapshot, f)
    os.replace(tmp, path)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--shard-bytes", type=int, default=DEFAULT_SIZE)
    parser.add_argument(
        "--merge-into",
        help="exporter snapshot path to merge results into (file backend)",
    )
    args = parser.parse_args()
    metrics = measure(args.shard_bytes)
    print(json.dumps(metrics, indent=2))
    if args.merge_into:
        merge_into_snapshot(metrics, args.merge_into)


if __name__ == "__main__":
    main()
