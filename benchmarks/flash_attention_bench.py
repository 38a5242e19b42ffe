"""Flash-attention kernel benchmark: Pallas MXU kernel vs plain-XLA
attention on the attached TPU chip (forward and forward+backward), across
sequence lengths; then each of the three kernels alone at the shapes the
benchmark's cells run them at: microseconds a call on the device's own
clock (a profiler trace of forward+backward calls, the kernels' events
found by name) and the share of the bfloat16 roofline that is, by the
count the driver's benchmark holds them to (perfbench/kernel_costs.py,
perfbench/peaks.json; the windowed kernels of a shape that states a
`sliding_window` by perfbench/window_costs.py, beside the plain ones at the
same shape). Kernel-level evidence beside the benchmark the
driver runs (perfbench/), whose cells time the daemon and the shim. Runs
on a TPU or not at all: the kernels have no interpret mode of their own.

Usage: python benchmarks/flash_attention_bench.py [--seqs 1024,2048,4096]
       [--shapes olmo2-1b,deepseek-v2-lite] [--blocks 512x512,256x512]
"""

from __future__ import annotations

import argparse
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "perfbench"))  # kernel_costs and what it imports

from dynolog_tpu._jaxinit import enable_compile_cache, require_tpu

CACHE_DIR = enable_compile_cache()

import jax
import jax.numpy as jnp

from dynolog_tpu.ops.flash_attention import flash_attention, reference_attention

B, H, D = 4, 8, 128

# The attention of the benchmark's cells, as perfbench/kernel_costs.py reads
# a configuration's `job` (batch x seq, query heads, d_model = heads x
# width), and the key/value heads beside it.
CELL_SHAPES = {
    "olmo2-1b": ({"batch": 1, "seq": 2048, "n_heads": 16, "d_model": 2048}, 16),
    "olmo2-7b": ({"batch": 1, "seq": 4096, "n_heads": 32, "d_model": 4096}, 32),
    "deepseek-v2-lite": ({"batch": 2, "seq": 4096, "n_heads": 16,
                          "attn_type": "mla", "qk_nope_head_dim": 128,
                          "qk_rope_head_dim": 64, "v_head_dim": 128}, 16),
    "nemotron-3-nano": ({"batch": 2, "seq": 4096, "n_heads": 32,
                         "d_model": 4096}, 2),
    # 32 query heads of 128 on 4; the windowed layers look back 2048
    "trinity-mini": ({"batch": 1, "seq": 8192, "n_heads": 32,
                      "d_model": 4096, "n_kv_heads": 4, "attn_head_dim": 128,
                      "sliding_window": 2048}, 4),
}


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


def kernel_rows(attn, job: dict, kv_heads: int, device, calls: int = 10,
                costs=None):
    """[(kernel, microseconds a call, share of its roofline in %)] of the
    three kernels under one forward+backward of `attn` at the job's shape,
    bfloat16: the events of a profiler trace over `calls` calls, by the
    reducer, the operation count and the peaks of perfbench/. `costs`: the
    module that names the kernels and counts their work (`KERNELS`,
    `call_cost`); None: perfbench/kernel_costs.py, the plain kernels'."""
    import cells
    import kernel_costs

    costs = costs or kernel_costs

    job = dict(job, dtype="bfloat16")
    d_qk, d_v = kernel_costs.head_widths(job)
    keys = jax.random.split(jax.random.PRNGKey(job["seq"]), 3)
    q, k, v = (
        jax.random.normal(key, (job["batch"], job["seq"], heads, width),
                          jnp.bfloat16)
        for key, heads, width in zip(
            keys, (job["n_heads"], kv_heads, kv_heads), (d_qk, d_qk, d_v)))
    grad = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    jax.block_until_ready(grad(q, k, v))  # compile + warm
    peaks = cells.load_peaks(device.device_kind)
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(calls):
                out = grad(q, k, v)
            jax.block_until_ready(out)
        (path,) = Path(tmp).rglob("*.xplane.pb")
        run = {"trace": {"path": str(path)}, "device": {"count": 1}}
        for kernel in costs.KERNELS:
            ns, events = kernel_costs.kernel_events(run, kernel)
            flops, nbytes = costs.call_cost(job, kernel)
            least_s = max(flops / peaks["bf16_flops_per_s"],
                          nbytes / peaks["hbm_bytes_per_s"])
            rows.append((kernel, ns / events / 1e3,
                         100.0 * least_s * events / (ns / 1e9)))
    return rows


def kernel_table(device, names, blocks) -> None:
    import window_costs

    print(f"\n{'cell shape':>18} {'blocks':>9} {'kernel':>30} "
          f"{'us a call':>10} {'roofline %':>10}")
    for name in names:
        job, kv_heads = CELL_SHAPES[name]
        # the plain kernels, and under them the windowed where the shape
        # states a window
        arms = [(None, None)] + (
            [(job["sliding_window"], window_costs)]
            if "sliding_window" in job else [])
        for bq, bk in blocks:
            for window, costs in arms:
                attn = lambda q, k, v: flash_attention(  # noqa: E731
                    q, k, v, True, bq, bk, None, window)
                for kernel, us, pct in kernel_rows(
                        attn, job, kv_heads, device, costs=costs):
                    print(f"{name:>18} {f'{bq}x{bk}':>9} {kernel:>30} "
                          f"{us:10.1f} {pct:10.1f}", flush=True)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seqs", default="1024,2048,4096,8192")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--shapes", default=",".join(CELL_SHAPES))
    parser.add_argument("--blocks", default="512x512")
    args = parser.parse_args()

    dev = require_tpu("flash_attention_bench.py")[0]
    print(f"device: {dev} ({dev.device_kind}); compile cache {CACHE_DIR}",
          file=sys.stderr)
    rows = []
    for s in [int(x) for x in args.seqs.split(",") if x]:
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

    kernel_table(
        dev, [x for x in args.shapes.split(",") if x],
        [tuple(int(n) for n in b.split("x")) for b in args.blocks.split(",")])


if __name__ == "__main__":
    main()
