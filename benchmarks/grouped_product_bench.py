"""Grouped-product benchmark: the products of an expert layer that holds a
share of its experts (dynolog_tpu/models/moe.py, `ep == 1`), alone on the
attached TPU chip, at the shapes of the three benchmark cells that run them
(`nemotron-3-nano.capture`: 49152 copies of 2688 over 16 held experts of
1856, two matrices an expert; `deepseek-v2-lite.capture`: 49152 copies of
2048 over 16 of 1408, three; `trinity-mini.capture`: 65536 copies of 2048
over 16 of 1024, three). For each layout of the held rows (the groups
as the sort leaves them, back to back; each group moved to the next multiple
of 128, 256 or 512 rows, zero rows between, which is `moe._aligned`) and each
product (`jax.lax.ragged_dot`, XLA's own kernel; the Pallas grouped product
JAX ships, `megablox` `gmm` / `tgmm`, at a few tilings) it prints

- the rows the products visit over the rows that exist, and the rows of
  the buffer every gather and mask of the layer passes over: what the
  layout pays, counts from the routing alone (over several seeds);
- microseconds a call on the device's own clock of the product into the
  expert (`in`: [rows, d] x [16, d, f]) and out of it (`out`), forward and
  both transposes (the gradient for the rows and the one for the weights):
  the module events of a profiler trace, one jitted program a direction;
- what a step of the cell pays for its grouped products by those numbers
  (the layer is rematerialised, so a product into the expert runs forward
  twice);
- the passes over the buffer that stop at the last held row, whole against
  a block of rows at a time, at even routing and with every copy on a held
  expert: the two gathers whose result is as long as the buffer
  (`moe._take_rows_used`, `--blocks`), and what lies between the products
  into an expert and the product out of it (`moe.activate`: the masks and
  the SiLU or ReLU^2, forward and backward, `--act-blocks`).

Routing is near even, as the cells' seeds route: a token's k distinct
experts by seeded scores, an expert's load within a fifth of the mean
(PERF.md section 6, PR 43). Kernel-level evidence beside the benchmark the
driver runs (perfbench/). Runs on a TPU or not at all.

Usage: python benchmarks/grouped_product_bench.py
       [--shapes nemotron-3-nano,deepseek-v2-lite] [--aligns 0,128,256,512]
       [--tilings 512x512x512,512x1024x1024] [--seeds 6]
       [--blocks 2048,4096,8192] [--act-blocks 2048,4096,8192]
(`--aligns ""` leaves the products out, `--blocks ""` the gathers,
`--act-blocks ""` the activation)
"""

from __future__ import annotations

import argparse
import sys
import tempfile
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "perfbench"))  # the benchmark's trace reducer

from dynolog_tpu._jaxinit import enable_compile_cache, require_tpu

CACHE_DIR = enable_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from dynolog_tpu.models import moe

# tokens a step, choices a token, experts the router scores, experts held,
# model width, expert width, matrices into an expert, expert blocks a step
SHAPES = {
    "nemotron-3-nano": dict(tokens=8192, k=6, experts=128, held=16, d=2688,
                            f=1856, into=1, blocks=3),
    "deepseek-v2-lite": dict(tokens=8192, k=6, experts=64, held=16, d=2048,
                             f=1408, into=2, blocks=4),
    "trinity-mini": dict(tokens=8192, k=8, experts=128, held=16, d=2048,
                         f=1024, into=2, blocks=4),
}
SKEW = 0.08  # an expert's log load about the mean, standard deviation
DIRECTIONS = ("fwd", "d_rows", "d_weights")


def draw_chosen(seed: int, shape: dict, among: str = "experts") -> np.ndarray:
    """[tokens, k]: each token's experts under one seeded routing of a step,
    over all the router scores or (`among="held"`) the held ones alone."""
    rng = np.random.default_rng(seed)
    scores = rng.gumbel(size=(shape["tokens"], shape[among]))
    scores += SKEW * rng.normal(size=shape[among])
    return np.argpartition(-scores, shape["k"], axis=1)[:, :shape["k"]]


def draw_groups(seed: int, shape: dict) -> np.ndarray:
    """Rows for each held expert under one seeded routing of a step."""
    return np.bincount(
        draw_chosen(seed, shape).reshape(-1),
        minlength=shape["experts"])[:shape["held"]]


def layout(sizes: np.ndarray, copies: int, align: int):
    """(group_sizes for the products, which of the buffer's rows hold a
    copy): the groups as they fall where `align` is 0, else the module's
    aligned layout."""
    sizes = jnp.asarray(sizes, jnp.int32)
    if not align:
        return sizes, jnp.arange(copies) < jnp.sum(sizes)
    rounded, _, came = moe._aligned(sizes, copies, align)
    return rounded, came < copies


def programs(product):
    """The three directions of `product(rows, weights, group_sizes)`, one
    jitted program each, named so that a trace's module events tell them
    apart."""

    def fwd(rows, weights, sizes, ct):
        return product(rows, weights, sizes)

    def d_rows(rows, weights, sizes, ct):
        return jax.vjp(lambda r: product(r, weights, sizes), rows)[1](ct)[0]

    def d_weights(rows, weights, sizes, ct):
        return jax.vjp(lambda w: product(rows, w, sizes), weights)[1](ct)[0]

    return {f.__name__: jax.jit(f) for f in (fwd, d_rows, d_weights)}


def module_us(path: str, name: str) -> float:
    """Median device time of the executions of program `jit_<name>`."""
    import xplane

    plane = xplane.find_plane(xplane.load(path), xplane.device_plane_name(0))
    line = xplane.find_line(plane, xplane.XLA_MODULES)
    took = [ev.duration_ns for ev in xplane._events(line)
            if ev.name.startswith(f"jit_{name}")]
    if not took:
        raise RuntimeError(f"no execution of jit_{name} in {path}")
    return float(np.median(took)) / 1e3


def time_products(product, shape: dict, sizes, real, calls: int = 5) -> dict:
    """{"in" | "out": {direction: microseconds a call}} at one layout."""
    n = real.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(n), 4)
    out = {}
    for side, (a, b), key in (("in", (shape["d"], shape["f"]), keys[:2]),
                              ("out", (shape["f"], shape["d"]), keys[2:])):
        rows = jnp.where(real[:, None], jax.random.normal(
            key[0], (n, a), jnp.bfloat16), 0)
        weights = (jax.random.normal(
            key[1], (shape["held"], a, b)) / np.sqrt(a)).astype(jnp.bfloat16)
        ct = jnp.where(real[:, None], jnp.ones((n, b), jnp.bfloat16), 0)
        fns = programs(product)
        for fn in fns.values():
            jax.block_until_ready(fn(rows, weights, sizes, ct))  # compile
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for fn in fns.values():
                    for _ in range(calls):
                        done = fn(rows, weights, sizes, ct)
                    jax.block_until_ready(done)
            (path,) = Path(tmp).rglob("*.xplane.pb")
            out[side] = {name: module_us(str(path), name) for name in fns}
    return out


def buffer_order(chosen: np.ndarray, shape: dict):
    """(order, used) as `moe._moe_local` lays a routing out: for each row of
    the buffer the copy it holds (`copies`: none), and the rows in use."""
    copies = chosen.size
    expert_of = jnp.asarray(chosen.reshape(-1), jnp.int32)
    order = jnp.argsort(expert_of).astype(jnp.int32)
    rounded, _, came = moe._aligned(
        moe._count(expert_of, shape["experts"])[:shape["held"]], copies,
        moe.ALIGN)
    return (order.at[came].get(mode="fill", fill_value=copies),
            jnp.sum(rounded))


def program_name(block: int | None, stem: str = "gather") -> str:
    """A program's name by its block, none the start of another's."""
    return f"{stem}_b{block:06d}" if block else f"{stem}_whole"


def time_gathers(shape: dict, order, used, blocks: list[int],
                 calls: int = 5) -> dict:
    """{"dispatch" | "combine_t": {"whole" | block: microseconds a call}}:
    the two buffer-long gathers under one routing (`buffer_order`)."""
    key = jax.random.PRNGKey(order.shape[0])
    sides = {
        "dispatch": (jax.random.normal(
            key, (shape["tokens"], shape["d"]), jnp.bfloat16),
            order // shape["k"]),
        "combine_t": (jax.random.normal(
            key, (shape["tokens"] * shape["k"], shape["d"]), jnp.bfloat16),
            order),
    }
    fns, committed = {}, moe.BLOCK
    for block in (None, *blocks):
        def gather(rows, idx, used, block=block):
            return moe._take_rows_used(rows, idx, used if block else None)

        gather.__name__ = program_name(block)
        moe.BLOCK = block or committed  # read as a program is traced
        fns[block or "whole"] = jax.jit(gather)
        for rows, idx in sides.values():
            jax.block_until_ready(fns[block or "whole"](rows, idx, used))
    moe.BLOCK = committed
    out = {}
    for side, (rows, idx) in sides.items():
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for fn in fns.values():
                    for _ in range(calls):
                        done = fn(rows, idx, used)
                    jax.block_until_ready(done)
            (path,) = Path(tmp).rglob("*.xplane.pb")
            out[side] = {
                label: module_us(str(path), program_name(
                    None if label == "whole" else label)) for label in fns}
    return out


def print_gathers(name: str, shape: dict, blocks: list[int]) -> None:
    rows = shape["tokens"] * shape["k"] + shape["held"] * moe.ALIGN
    print(f"\n{name}: the two gathers into the buffer of {rows} rows, us a "
          "call (and against the whole gather), blocks worked / blocks")
    print(f"{'routing':>8} {'rows used':>9} {'gather':>10} {'whole':>9} "
          + " ".join(f"{f'blocks of {b}':>26}" for b in blocks))
    for routing, among in (("even", "experts"), ("worst", "held")):
        order, used = buffer_order(draw_chosen(0, shape, among), shape)
        us = time_gathers(shape, order, used, blocks)
        used = int(used)
        for side, took in us.items():
            print(f"{routing:>8} {used:9d} {side:>10} {took['whole']:9.1f} "
                  + " ".join(
                      f"{took[b]:9.1f} ({took['whole'] / took[b]:5.2f}x) "
                      f"{-(-used // b):3d}/{-(-rows // b):<3d}"
                      for b in blocks), flush=True)


def time_activations(shape: dict, n: int, used, blocks: list[int],
                     calls: int = 5) -> dict:
    """{"fwd" | "bwd": {"whole" | block: microseconds a call}}: what lies
    between the products into an expert and the product out of it, over a
    buffer of n rows of which `used` hold a group; backward: from the
    cotangent and the raw products to the raw products' cotangents."""
    keys = jax.random.split(jax.random.PRNGKey(n), shape["into"] + 1)
    ct, *raw = (
        jax.random.normal(key, (n, shape["f"]), jnp.bfloat16) for key in keys)
    raw = tuple(raw)
    fns, committed = {}, moe.BLOCK
    for block in (None, *blocks):
        act = moe.activate if block else moe._activate_whole

        def fwd(raw, ct, used, act=act):
            return act(raw, used)

        def bwd(raw, ct, used, act=act):
            return jax.vjp(lambda *raw: act(raw, used), *raw)[1](ct)

        moe.BLOCK = block or committed  # read as a program is traced
        for direction, f in (("fwd", fwd), ("bwd", bwd)):
            f.__name__ = program_name(block, f"act_{direction}")
            fns[direction, block or "whole"] = jax.jit(f)
            jax.block_until_ready(fns[direction, block or "whole"](
                raw, ct, used))
    moe.BLOCK = committed
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for fn in fns.values():
                for _ in range(calls):
                    done = fn(raw, ct, used)
                jax.block_until_ready(done)
        (path,) = Path(tmp).rglob("*.xplane.pb")
        out = {"fwd": {}, "bwd": {}}
        for (direction, label), fn in fns.items():
            out[direction][label] = module_us(str(path), fn.__name__)
    return out


def print_activations(name: str, shape: dict, blocks: list[int]) -> None:
    rows = shape["tokens"] * shape["k"] + shape["held"] * moe.ALIGN
    print(f"\n{name}: the activation between the products ("
          f"{'SiLU x up' if shape['into'] == 2 else 'ReLU^2'}) over the "
          f"buffer of {rows} rows of {shape['f']}, us a call (and against "
          "the whole pass), blocks worked / blocks")
    print(f"{'routing':>8} {'rows used':>9} {'pass':>10} {'whole':>9} "
          + " ".join(f"{f'blocks of {b}':>26}" for b in blocks))
    for routing, among in (("even", "experts"), ("worst", "held")):
        _, used = buffer_order(draw_chosen(0, shape, among), shape)
        us = time_activations(shape, rows, used, blocks)
        used = int(used)
        for direction, took in us.items():
            print(f"{routing:>8} {used:9d} {direction:>10} "
                  f"{took['whole']:9.1f} " + " ".join(
                      f"{took[b]:9.1f} ({took['whole'] / took[b]:5.2f}x) "
                      f"{-(-used // b):3d}/{-(-rows // b):<3d}"
                      for b in blocks), flush=True)


def step_ms(shape: dict, us: dict) -> float:
    """A step's grouped products: a product into the expert runs forward
    twice (the layer is rematerialised), the one out of it once (nothing of
    the backward pass reads its result)."""
    layer = shape["into"] * (
        2 * us["in"]["fwd"] + us["in"]["d_rows"] + us["in"]["d_weights"]
    ) + sum(us["out"].values())
    return shape["blocks"] * layer / 1e3


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--aligns", default="0,128,256,512")
    parser.add_argument("--tilings", default="512x512x512,512x1024x1024")
    parser.add_argument("--seeds", type=int, default=6)
    parser.add_argument("--blocks", default="2048,4096,8192")
    parser.add_argument("--act-blocks", default="2048,4096,8192")
    args = parser.parse_args()
    aligns = [int(a) for a in filter(None, args.aligns.split(","))]
    blocks = [int(b) for b in filter(None, args.blocks.split(","))]
    act_blocks = [int(b) for b in filter(None, args.act_blocks.split(","))]
    products = {"ragged_dot": jax.lax.ragged_dot}
    for tiling in filter(None, args.tilings.split(",")):
        products[f"gmm {tiling}"] = partial(
            megablox.gmm, preferred_element_type=jnp.bfloat16,
            tiling=tuple(int(t) for t in tiling.split("x")))

    dev = require_tpu("grouped_product_bench.py")[0]
    print(f"device: {dev} ({dev.device_kind}); compile cache {CACHE_DIR}",
          file=sys.stderr)
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        copies = shape["tokens"] * shape["k"]
        if blocks:
            print_gathers(name, shape, blocks)
        if act_blocks:
            print_activations(name, shape, act_blocks)
        if not aligns:
            continue
        draws = [draw_groups(seed, shape) for seed in range(args.seeds)]
        print(f"\n{name}: {copies} copies, held rows a step "
              f"{min(d.sum() for d in draws)}-{max(d.sum() for d in draws)}, "
              f"a group {min(d.min() for d in draws)}-"
              f"{max(d.max() for d in draws)} rows ({args.seeds} seeds)")
        print(f"{'layout':>12} {'buffer rows':>11} {'visited/exist':>13} "
              f"{'product':>18} "
              + " ".join(f"{side + '.' + d:>13}"
                         for side in ("in", "out") for d in DIRECTIONS)
              + f" {'ms a step':>10}")
        for align in aligns:
            paid = np.mean([
                (-(-d // align) * align).sum() / d.sum() if align else 1.0
                for d in draws])
            sizes, real = layout(draws[0], copies, align)
            label = (f"{f'aligned {align}' if align else 'as they fall':>12} "
                     f"{real.shape[0]:11d} {paid:13.3f}")
            for product_name, product in products.items():
                try:
                    us = time_products(product, shape, sizes, real)
                except (jax.errors.JaxRuntimeError, ValueError) as e:
                    # a tiling the compiler or the kernel refuses (fast
                    # memory, a row count it does not divide) is a table cell
                    print(f"{label} {product_name:>18} refused: "
                          f"{str(e).splitlines()[0][:120]}", flush=True)
                    continue
                print(f"{label} {product_name:>18} "
                      + " ".join(f"{us[side][d]:13.1f}"
                                 for side in ("in", "out") for d in DIRECTIONS)
                      + f" {step_ms(shape, us):10.2f}", flush=True)


if __name__ == "__main__":
    main()
