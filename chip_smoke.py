#!/usr/bin/env python3
"""Chip smoke: the main path of dynolog_tpu, once, on one real TPU chip.

    dynologd (built here, from the tracked sources, real metric backend)
      -> this process, the observed JAX job, holding the chip, with
         dynolog_tpu.client.TraceClient registered and step() per step
      -> `dyno gputrace` (pull, through the shim) and `dyno pushtrace`
         (daemon-driven Profile RPC into jax.profiler.start_server)
      -> xplane + manifest on disk -> `python -m dynolog_tpu.trace`
      -> `dyno query` / `dyno tpu` / `dyno jobs` / `dyno health` answering
         from the daemon's store with rows read from the chip.

The job is the repo's transformer at the full widths of
TransformerConfig.llama_8b_like() with the Pallas flash-attention kernels;
depth, batch and sequence are cut to fit one 16 GB chip and printed.

One process per chip: this is the only process that imports JAX. Its
children are the C++ build, dynologd, dyno, and `python -m
dynolog_tpu.trace`, which imports no JAX. The daemon starts before JAX is
initialized, as it does under systemd.

Exit 0 and a last stdout line {"ok": true, "device": {...}} only if every
phase passed on a TPU. Anything else exits non-zero and prints no result:
no accelerator, a platform other than "tpu", a missing checkout, a failed
build, a capture without device planes, a daemon without chip rows. The
numbers printed are a smoke run's, not benchmark results.
"""

from __future__ import annotations

import ctypes
import dataclasses
import json
import os
import platform
import select
import shutil
import socket
import statistics
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
BUILD = REPO / "build"
BIN = BUILD / "src"

# The observed job. Widths are llama_8b_like's; these three are the cuts.
# XLA's memory analysis of the step on a v5e: 9.2 GB resident (bf16 weights
# + bf16 Adam mu/nu, donated) + 3.7 GB of program temporaries.
LAYERS, BATCH, SEQ = 2, 1, 2048
WARM_STEPS = 6
WINDOW_MS = 500
JOB_ID = 21
# |flash - reference| on bf16 outputs and gradients of magnitude up to ~5:
# one bf16 ulp there is 0.03; measured 0.016 on the chip.
FLASH_TOL = 0.05
FLASH_SEQS = (2048, 8192)
KERNELS = (
    "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv")
# What the chip answers through the daemon's store (docs/METRICS.md names).
HBM_TOTAL, HBM_USED, DUTY = (
    "tpu0.hbm_total_bytes", "tpu0.hbm_used_bytes",
    "tpu0.tensorcore_duty_cycle_pct")


class SmokeFailure(Exception):
    """A phase did not pass; the run exits non-zero with this reason."""


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


def run(cmd, timeout=120, **kw) -> subprocess.CompletedProcess:
    return subprocess.run(
        [str(c) for c in cmd], capture_output=True, text=True,
        timeout=timeout, **kw)


# ------------------------------------------------------------- preflight


def preflight() -> None:
    for rel in ("CMakeLists.txt", "src/CMakeLists.txt", "src/daemon/Main.cpp",
                "src/cli/dyno.cpp", "dynolog_tpu/client/shim.py",
                "dynolog_tpu/trace.py", "dynolog_tpu/models/train.py"):
        require((REPO / rel).is_file(),
                f"{rel} is not beside chip_smoke.py: this is not a checkout "
                "of dynolog_tpu, and there is no program to smoke")
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if pinned and pinned.split(",")[0] != "tpu":
        # JAX_PLATFORMS pins what jax.devices() returns, so the platform is
        # known before the build and the daemon are paid for.
        raise SmokeFailure(
            f"JAX_PLATFORMS={pinned} pins the job to platform "
            f"'{pinned.split(',')[0]}', not 'tpu'; this smoke runs on a "
            "TPU chip or not at all")


# ----------------------------------------------------------------- build


def build() -> None:
    """Builds dynologd and dyno from the tracked sources, every run: a
    build/src/dynologd that happens to be on disk is never trusted (a warm
    build is seconds)."""
    t0 = time.time()
    if shutil.which("cmake") and shutil.which("ninja"):
        how = "cmake + ninja"
        steps = (
            ["cmake", "-S", REPO, "-B", BUILD, "-G", "Ninja",
             "-DCMAKE_BUILD_TYPE=Release"],
            ["cmake", "--build", BUILD, "--target", "dynologd", "dyno"],
        )
    else:
        how = "scripts/manual_build.sh (no cmake/ninja on this machine)"
        steps = (["bash", REPO / "scripts" / "manual_build.sh"],)
    for cmd in steps:
        proc = run(cmd, timeout=900, cwd=REPO)
        require(proc.returncode == 0,
                f"build step {cmd[0]} failed:\n{(proc.stdout + proc.stderr)[-3000:]}")
    for name in ("dynologd", "dyno"):
        require((BIN / name).is_file(), f"build produced no {BIN / name}")
    say(f"build: {how}, {time.time() - t0:.1f} s -> {BIN}")


# ------------------------------------------------------------ host facts


def _perf_event_open(ev_type: int, config: int) -> str:
    class Attr(ctypes.Structure):
        _fields_ = [("type", ctypes.c_uint32), ("size", ctypes.c_uint32),
                    ("config", ctypes.c_uint64), ("rest", ctypes.c_uint8 * 112)]

    if platform.machine() != "x86_64":
        return f"not probed on {platform.machine()}"
    attr = Attr(type=ev_type, size=ctypes.sizeof(Attr), config=config)
    attr.rest[24] = 0x60  # flags (offset 40): exclude_kernel | exclude_hv
    libc = ctypes.CDLL(None, use_errno=True)
    fd = libc.syscall(298, ctypes.byref(attr), 0, -1, -1, 0)
    if fd < 0:
        err = ctypes.get_errno()
        return f"refused (errno {err}: {os.strerror(err)})"
    os.close(fd)
    return "opens"


def port_answers(port: int) -> bool:
    try:
        with socket.create_connection(("localhost", port), timeout=1):
            return True
    except OSError:
        return False


def host_facts() -> None:
    def cpu_line() -> str:
        with open("/proc/stat") as f:
            return f.readline().strip()

    before = cpu_line()
    sum(i * i for i in range(2_000_000))  # burn some jiffies between reads
    time.sleep(0.3)
    after = cpu_line()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    say(f"host: {os.cpu_count()} cores, uptime {uptime:.0f} s, "
        f"hostname {socket.gethostname()}")
    say(f"host: /proc/stat {'is live' if before != after else 'does NOT move'}"
        f" ({before!r} -> {after!r})")
    say(f"host: perf_event_open hardware cycles: {_perf_event_open(0, 0)}; "
        f"software cpu-clock: {_perf_event_open(1, 0)}")
    say("host: TPU_RUNTIME_METRICS_PORTS="
        f"{os.environ.get('TPU_RUNTIME_METRICS_PORTS', '(unset)')}; "
        f"localhost:8431 before the job: "
        f"{'answers' if port_answers(8431) else 'refused'}")


# ---------------------------------------------------------------- daemon


class Daemon:
    """dynologd with the real metric backend. `grpc` is the one that works
    beside a JAX job on this machine, and naming it (rather than `auto`)
    defers binding until the job's runtime serves localhost:8431."""

    def __init__(self, work: Path):
        self.endpoint = f"chip_smoke_{os.getpid()}"
        self.log_path = work / "dynologd.log"
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [str(BIN / "dynologd"), "--port=0", "--enable_ipc_monitor",
             f"--ipc_endpoint_name={self.endpoint}",
             "--enable_tpu_monitor", "--tpu_metric_backend=grpc",
             "--tpu_monitor_reporting_interval_s=1",
             "--kernel_monitor_reporting_interval_s=1", "--nouse_JSON"],
            stdout=subprocess.PIPE, stderr=self._log, text=True)
        self.port = None
        deadline = time.time() + 15
        while time.time() < deadline and self.proc.poll() is None:
            # select-bounded: a daemon that prints nothing must not hang
            # the smoke in readline().
            if not select.select([self.proc.stdout], [], [], 1.0)[0]:
                continue
            line = self.proc.stdout.readline()
            if line.startswith("DYNOLOG_PORT="):
                self.port = int(line.split("=", 1)[1])
                break
        if self.port is None:
            self.stop()
            raise SmokeFailure(
                f"dynologd did not announce its port:\n{self.log()[-2000:]}")

    def log(self) -> str:
        return self.log_path.read_text(errors="replace")

    def dyno(self, *args, timeout=60) -> subprocess.CompletedProcess:
        return run([BIN / "dyno", f"--port={self.port}", *args],
                   timeout=timeout)

    def dyno_popen(self, *args) -> subprocess.Popen:
        return subprocess.Popen(
            [str(BIN / "dyno"), f"--port={self.port}", *args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    def query(self, *metrics: str) -> dict:
        proc = self.dyno("query", "--metrics=" + ",".join(metrics))
        require(proc.returncode == 0, f"dyno query failed: {proc.stdout}")
        body = proc.stdout.split("response = ", 1)[-1]
        return json.loads(body)["metrics"]

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        self._log.close()


# --------------------------------------------------------------- the job


def check_flash_kernels() -> None:
    """Forward and both backward kernels, compiled by Mosaic at head_dim
    128 / bf16, against plain-XLA attention on the same seeded inputs."""
    import jax
    import jax.numpy as jnp

    from dynolog_tpu.ops.flash_attention import (
        flash_attention, reference_attention)

    def grads(attn, g):
        def loss(q, k, v):
            out = attn(q, k, v).astype(jnp.float32)
            return jnp.sum(out * g.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def worst(a, b):
        diff = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
        return float(jnp.max(diff))

    for seq in FLASH_SEQS:
        keys = jax.random.split(jax.random.PRNGKey(seq), 4)
        q, k, v, g = (
            jax.random.normal(key, (1, seq, 4, 128), jnp.bfloat16)
            for key in keys)
        flash = lambda q, k, v: flash_attention(q, k, v, True)  # noqa: E731
        ref = lambda q, k, v: reference_attention(q, k, v, causal=True)  # noqa: E731
        out = jax.jit(flash)(q, k, v)
        errs = [worst(out, jax.jit(ref)(q, k, v))]
        errs += [worst(a, b) for a, b in zip(
            grads(flash, g)(q, k, v), grads(ref, g)(q, k, v))]
        finite = bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
        say(f"flash kernels seq {seq}: max |flash - reference| out/dq/dk/dv "
            f"= {errs} (tolerance {FLASH_TOL}, finite {finite})")
        require(finite and max(errs) <= FLASH_TOL,
                f"flash attention disagrees with the reference at seq {seq}: "
                f"{errs} > {FLASH_TOL}")


def wait_for(predicate, step_once, timeout_s: float, what: str) -> None:
    """Keeps the job stepping (the capture window needs device work in it)
    until predicate() holds."""
    deadline = time.time() + timeout_s
    while not predicate():
        require(time.time() < deadline, f"timed out waiting for {what}")
        step_once()


def check_xplane(trace_dir: str, label: str) -> tuple[str, int]:
    """The artifact holds a /device:TPU:0 plane with XLA op events, the
    three named Pallas kernels among them. Returns (path, bytes)."""
    from dynolog_tpu import trace as trace_mod

    files = trace_mod.find_xplane_files(trace_dir)
    require(files, f"{label}: no .xplane.pb under {trace_dir}")
    path = files[-1]
    with open(path, "rb") as f:
        data = f.read()
    planes = trace_mod.summarize_xplane_bytes(data, group=False)
    names = [p.name for p in planes]
    device = [p for p in planes if p.name == "/device:TPU:0"]
    require(device, f"{label}: no /device:TPU:0 plane, only {names}")
    plane = device[0]
    require("XLA Ops" in plane.line_names and plane.ops,
            f"{label}: /device:TPU:0 has no XLA op events "
            f"(lines {plane.line_names})")
    for kernel in KERNELS:
        hits = [op for op in plane.ops if kernel in op]
        require(hits, f"{label}: no op named *{kernel}* on /device:TPU:0")
    busy_ms = sum(op.total_ps for op in plane.ops.values()) / 1e9
    say(f"{label}: {path} ({len(data)} bytes): /device:TPU:0 with "
        f"{plane.events} events, {len(plane.ops)} distinct XLA ops, "
        f"{busy_ms:.1f} ms of op time; kernels "
        + ", ".join(next(op for op in plane.ops if k in op) for k in KERNELS))
    return path, len(data)


def check_summary_cli(manifest_path: Path, label: str) -> None:
    proc = run([sys.executable, "-m", "dynolog_tpu.trace", manifest_path,
                "--top", "10"], cwd=REPO)
    require(proc.returncode == 0,
            f"{label}: python -m dynolog_tpu.trace exited "
            f"{proc.returncode}: {proc.stderr[-1000:]}")
    require("/device:TPU:0" in proc.stdout and "fusion" in proc.stdout,
            f"{label}: trace summary lists no device ops:\n{proc.stdout[-1500:]}")
    device_part = proc.stdout[proc.stdout.index("/device:TPU:0"):]
    say(f"{label}: python -m dynolog_tpu.trace --top 10:\n"
        + "\n".join("    " + ln for ln in device_part.splitlines()[:14]))


def job(daemon: Daemon, work: Path) -> dict:
    from dynolog_tpu._jaxinit import enable_compile_cache, require_tpu

    cache_dir = enable_compile_cache()
    t0 = time.time()
    import jax

    cache = {"hits": 0, "misses": 0}

    def on_event(name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    devices = require_tpu("chip_smoke.py")
    dev = devices[0]
    from importlib.metadata import version

    say(f"device: platform {dev.platform}, kind {dev.device_kind}, count "
        f"{len(devices)}; jax {jax.__version__}, jaxlib {version('jaxlib')}, "
        f"libtpu {version('libtpu')}; backend up {time.time() - t0:.1f} s "
        "after the daemon")
    say(f"compile cache: {cache_dir}")

    check_flash_kernels()

    from dynolog_tpu.client import TraceClient
    from dynolog_tpu.models.train import (
        make_batch, make_train_state, make_train_step)
    from dynolog_tpu.models.transformer import TransformerConfig

    full = TransformerConfig.llama_8b_like()
    cfg = dataclasses.replace(full, n_layers=LAYERS, attn_impl="flash")
    say(f"job: llama_8b_like widths (d_model {cfg.d_model}, {cfg.n_heads} "
        f"heads of {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}), "
        f"attn flash; cut to fit 16 GB: depth {full.n_layers} -> {LAYERS}, "
        f"batch {BATCH}, sequence {full.max_seq_len} -> {SEQ}")
    t0 = time.time()
    params, opt_state = make_train_state(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(params)
    init_s = time.time() - t0
    batch = make_batch(jax.random.PRNGKey(1), cfg, BATCH, SEQ)
    t0 = time.time()
    step = make_train_step(cfg).lower(params, opt_state, batch).compile()
    compile_s = time.time() - t0
    mem = step.memory_analysis()
    say(f"job: init {init_s:.1f} s, step compile {compile_s:.1f} s (compile "
        f"cache so far: {cache['hits']} hits, {cache['misses']} misses); XLA "
        f"memory analysis: {mem.argument_size_in_bytes / 1e9:.2f} GB "
        f"resident state, {mem.temp_size_in_bytes / 1e9:.2f} GB temporaries")

    client = TraceClient(
        job_id=JOB_ID, endpoint=daemon.endpoint, poll_interval_s=0.1)
    state = [params, opt_state]
    del params, opt_state
    losses, step_ms = [], []

    def step_once() -> None:
        t = time.perf_counter()
        state[0], state[1], loss = step(state[0], state[1], batch)
        loss.block_until_ready()
        step_ms.append((time.perf_counter() - t) * 1e3)
        losses.append(float(loss))
        client.step()

    profiler_started = False
    try:
        require(client.start(),
                "TraceClient could not register with dynologd over "
                f"endpoint {daemon.endpoint}: {client.last_error}")
        for _ in range(WARM_STEPS):
            step_once()
        warm = losses[:WARM_STEPS]
        say(f"job: {WARM_STEPS} warm steps on one repeated batch, loss "
            + " -> ".join(f"{x:.4f}" for x in warm))
        require(all(x == x and abs(x) != float("inf") for x in warm),
                f"loss is not finite: {warm}")
        require(warm[-1] < warm[0], f"loss did not fall: {warm}")

        # ---- pull capture: dyno gputrace -> daemon -> shim -> xplane
        pull_base = work / "pull.json"
        pull_manifest = work / f"pull_{os.getpid()}.json"
        proc = daemon.dyno(
            "gputrace", f"--job_id={JOB_ID}", f"--duration_ms={WINDOW_MS}",
            f"--log_file={pull_base}")
        require(proc.returncode == 0, f"dyno gputrace failed: {proc.stdout}")
        wait_for(pull_manifest.exists, step_once, 180, "the pull manifest")
        pull = json.loads(pull_manifest.read_text())
        require(pull.get("status") == "ok", f"pull manifest: {pull}")
        timing = pull.get("timing", {})
        # Only the shim's ProfilerSession path writes these two; their
        # absence means the capture fell to the public-API path.
        require("collect_ms" in timing and "xspace_bytes" in timing,
                f"pull capture did not take the session path: {timing}")
        say(f"pull capture: manifest {pull_manifest.name} status ok, "
            f"timing {json.dumps(timing)}")
        _, pull_bytes = check_xplane(pull["trace_dir"], "pull capture")
        check_summary_cli(pull_manifest, "pull capture")

        # ---- push capture: dyno pushtrace -> daemon drives the Profile RPC
        with socket.socket() as s:
            s.bind(("localhost", 0))
            profiler_port = s.getsockname()[1]
        jax.profiler.start_server(profiler_port)
        profiler_started = True
        push_base = work / "push.json"
        push_manifest = work / "push_push.json"
        push_cli = daemon.dyno_popen(
            "pushtrace", f"--profiler_port={profiler_port}",
            f"--duration_ms={WINDOW_MS}", f"--log_file={push_base}")
        try:
            wait_for(lambda: push_cli.poll() is not None, step_once, 180,
                     "dyno pushtrace")
        finally:
            if push_cli.poll() is None:
                push_cli.kill()
            push_out = push_cli.communicate()[0]
        require(push_cli.returncode == 0 and push_manifest.exists(),
                f"dyno pushtrace failed ({push_cli.returncode}): {push_out}")
        push = json.loads(push_manifest.read_text())
        require(push.get("status") == "ok" and push.get("xspace_bytes", 0) > 0,
                f"push manifest: {push}")
        say("push capture: manifest push_push.json status ok, timing "
            + json.dumps({k: push.get(k) for k in (
                "rpc_ms", "server_overhead_ms", "rpc_first_data_ms",
                "rpc_stream_ms", "write_ms", "xspace_bytes")}))
        _, push_bytes = check_xplane(push["trace_dir"], "push capture")
        check_summary_cli(push_manifest, "push capture")

        # ---- the daemon's store, answering with rows read from the chip
        # The shim reports step telemetry every 10 s (its default); keep
        # stepping until the first report is in the store.
        rate = f"job{JOB_ID}.steps_per_sec"
        wait_for(lambda: daemon.query(rate).get(rate, {}).get("values"),
                 step_once, 60, f"{rate} in the daemon's store")
        check_daemon(daemon)
    finally:
        if profiler_started:
            jax.profiler.stop_server()
        client.stop()
        # The shim converts each pull capture to trace.json.gz in a nice'd
        # child (no JAX in it); it must be gone before this process is.
        export = getattr(client.profiler, "_export_thread", None)
        if export is not None:
            export.join(timeout=120)
    require(export is None or not export.is_alive(),
            "the shim's trace.json.gz converter is still running after 120 s")

    steady = step_ms[1:]
    stats = dev.memory_stats()
    say(f"job: {len(step_ms)} steps, step time median "
        f"{statistics.median(steady):.1f} ms (block_until_ready each, "
        f"min {min(steady):.1f}, max {max(steady):.1f}, capture windows "
        f"included); final loss {losses[-1]:.4f}")
    say(f"job: memory_stats peak_bytes_in_use "
        f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB of "
        f"{stats['bytes_limit'] / 1e9:.2f} GB (allocator's view; the "
        "program temporaries above are XLA's)")
    say(f"job: artifacts pull xplane {pull_bytes} bytes, push xplane "
        f"{push_bytes} bytes")
    say(f"compile cache: {cache_dir}: {cache['hits']} hits, "
        f"{cache['misses']} misses this run ("
        + ("warm" if cache["hits"] else "cold: nothing was cached here before")
        + "; programs that compile in under 1 s are not persisted and miss "
        "every time)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def check_daemon(daemon: Daemon) -> None:
    backend = [ln for ln in daemon.log().splitlines()
               if "TpuMonitor using backend" in ln]
    require(backend and "fake" not in backend[0],
            f"daemon log names no real TPU backend: {backend}")
    series = daemon.query(HBM_TOTAL, HBM_USED, DUTY)
    for name in (HBM_TOTAL, HBM_USED, DUTY):
        require(series.get(name, {}).get("values"),
                f"daemon store holds no {name}: {series}")
    total = series[HBM_TOTAL]["values"][-1]
    require(14e9 < total < 18e9,
            f"{HBM_TOTAL} = {total}, not a 16 GB chip's")
    used, duty = series[HBM_USED]["values"], series[DUTY]["values"]
    require(len(set(used)) > 1 or len(set(duty)) > 1,
            f"neither {HBM_USED} nor {DUTY} moves: {used} {duty}")
    say(f"daemon: {backend[0].split('] ', 1)[-1]}; {HBM_TOTAL} = "
        f"{total / 1e9:.2f} GB; {HBM_USED} {used[0] / 1e9:.2f} -> "
        f"{max(used) / 1e9:.2f} GB over {len(used)} samples; {DUTY} "
        f"min {min(duty):.1f} max {max(duty):.1f}")
    for verb, needle in (("tpu", "hbm used/total"),
                         ("jobs", str(JOB_ID)),
                         ("health", "daemon: ok")):
        proc = daemon.dyno(verb)
        require(proc.returncode == 0 and needle in proc.stdout,
                f"dyno {verb} exit {proc.returncode}:\n{proc.stdout[-1500:]}")
    rate = daemon.query(f"job{JOB_ID}.steps_per_sec")
    say(f"daemon: dyno tpu / jobs / health exit 0; job{JOB_ID}.steps_per_sec "
        f"= {rate[f'job{JOB_ID}.steps_per_sec']['values'][-1]:.2f}")


def main() -> int:
    say("chip_smoke: a smoke run of the main path on one chip; the numbers "
        "below are not benchmark results")
    work = None
    daemon = None
    try:
        preflight()
        build()
        host_facts()
        work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
        daemon = Daemon(work)
        say(f"daemon: dynologd pid {daemon.proc.pid} on port {daemon.port}, "
            "--tpu_metric_backend=grpc, started before the job")
        sys.path.insert(0, str(REPO))
        device = job(daemon, work)
        require(daemon.proc.poll() is None,
                f"dynologd died during the run:\n{daemon.log()[-2000:]}")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if daemon is not None:
            daemon.stop()
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
