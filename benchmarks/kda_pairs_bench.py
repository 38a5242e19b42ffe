"""Kimi Delta Attention's chunk products under the channel-wise decay: the
plain body (`dynolog_tpu/models/linear_attention.py` `_plain_pairs`) against
the two Pallas kernels (`dynolog_tpu/ops/kda_pairs.py`: `kda_pairs_fwd`,
`kda_pairs_bwd`) on the attached TPU chip, forward and gradient, at the
shape `kimi-linear.capture` runs them at: 64 chunks x batch 1 x 32 heads of
[64, 128], q and k bfloat16, gamma float32. Prints milliseconds a make and
GB/s against the traffic the work needs (q, k, gamma in, the two [64, 64]
float32 matrices out: 201 MB a forward make), and how far the kernels'
results lie from the plain body's on the chip. Kernel-level evidence beside
the benchmark the driver runs (perfbench/), in no cell's path. Runs on a
TPU or not at all: the kernels have no interpret mode of their own.

Usage: python benchmarks/kda_pairs_bench.py [--shape 64,1,32,64,128]
       [--iters 20] [--dtype bfloat16] [--precision highest]
(`--precision`: the `jax.default_matmul_precision` both arms are traced
under; float32 operands' products take it, in the kernels as in the plain
body.)
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "perfbench"))  # cells: the chip's peaks

from dynolog_tpu._jaxinit import enable_compile_cache, require_tpu

CACHE_DIR = enable_compile_cache()

import cells
import jax
import jax.numpy as jnp

from dynolog_tpu.models.linear_attention import _plain_pairs
from dynolog_tpu.ops.kda_pairs import kda_pairs


def inputs(shape, dtype, seed: int = 0):
    """q, k unit rows in the model's type (q scaled as the layer scales it),
    gamma the running sum inside a chunk of decays the library's layer
    draws (A in [1, 16), a softplus of 0.001 to 0.1), and a cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    dk = shape[-1]

    def unit(key, scale):
        x = jax.random.normal(key, shape, jnp.float32)
        return (x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)) * scale
                ).astype(dtype)

    g = -jax.random.uniform(keys[2], shape, jnp.float32, 1.0, 16.0) * jnp.exp(
        jax.random.uniform(keys[3], shape, jnp.float32, jnp.log(0.001),
                           jnp.log(0.1)))
    weight = jax.random.normal(keys[4], (*shape[:-2], 2, shape[-2], shape[-2]))
    return (unit(keys[0], dk ** -0.5), unit(keys[1], 1.0),
            jnp.cumsum(g, axis=-2), weight)


def bench(fn, *args, iters: int) -> float:
    """Milliseconds a call: the best of three blocks of `iters` calls."""
    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shape", default="64,1,32,64,128")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--dtype", default="bfloat16")
    parser.add_argument("--precision", default="default")
    args = parser.parse_args()
    jax.config.update("jax_default_matmul_precision", args.precision)
    dev = require_tpu("kda_pairs_bench.py")[0]
    print(f"device: {dev} ({dev.device_kind}); compile cache {CACHE_DIR}",
          file=sys.stderr)
    hbm_bytes_per_s = cells.load_peaks(dev.device_kind)["hbm_bytes_per_s"]
    shape = tuple(int(n) for n in args.shape.split(","))
    q, k, gamma, weight = inputs(shape, jnp.dtype(args.dtype))
    makes = q.size // (shape[-2] * shape[-1])
    needed = (q.nbytes + k.nbytes + gamma.nbytes
              + 4 * makes * 2 * shape[-2] ** 2)
    arms = {"plain": _plain_pairs, "kernels": kda_pairs}
    results = {}
    print(f"{shape} {args.dtype} under {args.precision}: {makes} "
          f"chunk-heads, {needed / 1e6:.0f} MB a forward make, "
          f"{needed / hbm_bytes_per_s * 1e3:.2f} ms at the chip's bandwidth\n"
          f"{'':>8} {'forward ms':>11} {'GB/s':>7} {'fwd+grad ms':>12}")
    for name, pairs in arms.items():
        forward = jax.jit(pairs)
        grad = jax.jit(jax.value_and_grad(
            lambda q, k, gamma, pairs=pairs: jnp.sum(
                pairs(q, k, gamma) * weight), (0, 1, 2)))
        fwd_ms = bench(forward, q, k, gamma, iters=args.iters)
        grad_ms = bench(grad, q, k, gamma, iters=args.iters)
        results[name] = (forward(q, k, gamma), grad(q, k, gamma)[1])
        print(f"{name:>8} {fwd_ms:11.3f} {needed / fwd_ms / 1e6:7.1f} "
              f"{grad_ms:12.3f}", flush=True)
    # how far the kernels lie from the plain body, here on the chip
    worst = 0.0
    (both, grads), (want, want_grads) = results["kernels"], results["plain"]
    for name, got, ref in zip(("both", "dq", "dk", "dgamma"),
                              (both, *grads), (want, *want_grads)):
        got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
        scale = float(jnp.max(jnp.abs(ref)))
        off = float(jnp.max(jnp.abs(got - ref)))
        finite = bool(jnp.all(jnp.isfinite(got)))
        print(f"{name:>8}: max |kernels - plain| {off:.3e} of {scale:.3e}"
              f"{'' if finite else '  NOT FINITE'}")
        worst = max(worst, off / scale if finite else float("inf"))
    # bfloat16 operands round both sides' products once: a hundredth of the
    # largest entry holds either; a wrong layout is off by the entry itself
    print(f"worst relative to the largest entry: {worst:.3e}")
    return 0 if worst < 2e-2 else 1


if __name__ == "__main__":
    sys.exit(main())
