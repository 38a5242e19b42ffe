"""Flash-attention kernel benchmark: Pallas MXU kernel vs plain-XLA
attention on the attached TPU chip (forward and forward+backward), across
sequence lengths. Kernel-level evidence beside the benchmark the driver
runs (perfbench/), whose cells time the daemon and the shim. Runs on a
TPU or not at all: the kernels have no interpret mode of their own.

Usage: python benchmarks/flash_attention_bench.py [--seqs 1024,2048,4096]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from dynolog_tpu._jaxinit import enable_compile_cache, require_tpu

CACHE_DIR = enable_compile_cache()

import jax
import jax.numpy as jnp

from dynolog_tpu.ops.flash_attention import flash_attention, reference_attention

B, H, D = 4, 8, 128


def chain_fwd(attn, n):
    """One jit containing n chained attention calls (output feeds the next
    query), so one dispatch amortizes over n kernel runs."""

    @jax.jit
    def run(q, k, v):
        def body(c, _):
            o = attn(c, k, v)
            return o, ()

        out, _ = jax.lax.scan(body, q, None, length=n)
        return out

    return run


def chain_fwdbwd(attn, n):
    """Chained forward+backward: dq feeds the next query (normalized so
    values stay finite; normalization is a fused elementwise epilogue)."""

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def run(q, k, v):
        def body(c, _):
            dq, _, _ = grad(c, k, v)
            scale = jax.lax.rsqrt(
                jnp.mean(jnp.square(dq.astype(jnp.float32))) + 1e-6)
            return (dq.astype(jnp.float32) * scale).astype(q.dtype), ()

        out, _ = jax.lax.scan(body, q, None, length=n)
        return out

    return run


def bench(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(3):  # best-of-3 blocks rides out shared-host noise
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1000.0


def bench_interleaved(fns, args, iters, rounds=4):
    """Measure competing fns in interleaved rounds (flash/XLA back to back)
    so shared-host load drift hits all contenders equally; per-fn best
    across rounds. Returns {name: ms}."""
    live = {}
    for name, fn in fns.items():
        try:
            jax.block_until_ready(fn(*args))  # compile + warm
            live[name] = fn
        except jax.errors.JaxRuntimeError as e:
            # The plain-XLA path materializes [S, S] scores and runs out of
            # HBM at long seq. Only that is a table cell; anything else (a
            # kernel Mosaic refuses, a bad shape) fails the run.
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"  {name}: out of device memory", file=sys.stderr)
    best = {name: float("inf") for name in live}
    for _ in range(rounds):
        for name, fn in live.items():
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            best[name] = min(best[name], time.perf_counter() - t0)
    return {
        name: (best[name] / iters * 1000.0 if name in live else None)
        for name in fns
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seqs", default="1024,2048,4096,8192")
    parser.add_argument("--iters", type=int, default=20)
    args = parser.parse_args()

    dev = require_tpu("flash_attention_bench.py")[0]
    print(f"device: {dev} ({dev.device_kind}); compile cache {CACHE_DIR}",
          file=sys.stderr)
    rows = []
    for s in [int(x) for x in args.seqs.split(",")]:
        rng = jax.random.PRNGKey(s)
        kq, kk, kv = jax.random.split(rng, 3)
        shape = (B, s, H, D)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)

        fns = {
            "flash_fwd_ms": chain_fwd(flash_attention, args.iters),
            "xla_fwd_ms": chain_fwd(reference_attention, args.iters),
            "flash_fwdbwd_ms": chain_fwdbwd(flash_attention, args.iters),
            "xla_fwdbwd_ms": chain_fwdbwd(reference_attention, args.iters),
        }
        row = {"seq": s}
        row.update(bench_interleaved(fns, (q, k, v), args.iters))
        rows.append(row)
        print(row, flush=True)

    def fmt(v):
        return f"{v:8.2f}" if v is not None else "     OOM"

    print(f"\n{'seq':>6} {'flash fwd':>9} {'xla fwd':>9} "
          f"{'flash f+b':>9} {'xla f+b':>9}  (ms)")
    for r in rows:
        print(f"{r['seq']:>6} {fmt(r['flash_fwd_ms'])} {fmt(r['xla_fwd_ms'])}"
              f" {fmt(r['flash_fwdbwd_ms'])} {fmt(r['xla_fwdbwd_ms'])}")


if __name__ == "__main__":
    main()
