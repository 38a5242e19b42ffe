"""One run of one cell: daemon + shim + captures beside the observed job.

This process IS the observed job and the only one that imports JAX. Its
children are the C++ build, dynologd, the `dyno` CLI and the shim's convert
children. The daemon launcher, the step loop and the order of the phases
are copied from chip_smoke.py, which ran on the chip in PR 21.

    preflight -> build -> dynologd (before JAX) -> JAX on the chip ->
    weights from the seed -> check J's reference -> optimizer state ->
    step compiled or loaded -> shim registered -> warm steps (and one warm
    capture) -> THE WINDOW -> drain -> checks S1 S2 C1 C2 C3
    -> the shim stops and its convert children are waited for; what they
    wrote is read (derived_ms, check C5)
    -> in traced runs the journals (dyno selftrace, the shim's counters)
    -> teardown: the daemon stops (check C4) -> one JSON line.
"""

from __future__ import annotations

import collections
import contextlib
import json
import math
import os
import select
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path

import cells
import checks
import selftrace
import stats
import xplane

ROOT = cells.ROOT
BUILD = ROOT / "build"
BIN = BUILD / "src"
OUT = cells.HERE / "out"
CACHE_DIR = ROOT / ".jax_cache"
PRODUCT_FILES = (
    "CMakeLists.txt", "src/CMakeLists.txt", "src/daemon/Main.cpp",
    "src/cli/dyno.cpp", "dynolog_tpu/client/shim.py", "dynolog_tpu/trace.py",
    "dynolog_tpu/models/train.py")
WARM_STEPS = 6
CAPTURE_TIMEOUT_S = 30.0
SPAN_PREFIX = "perfbench."


class RunRefused(Exception):
    """Nothing can be measured here; exit non-zero with no result line."""


def say(msg: str) -> None:
    print(msg, flush=True)


def preflight() -> None:
    for rel in PRODUCT_FILES:
        if not (ROOT / rel).is_file():
            raise RunRefused(
                f"{rel} is not in {ROOT}: this is not a checkout of "
                "dynolog_tpu, and there is no system to measure")
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if pinned and pinned.split(",")[0] != "tpu":
        raise RunRefused(
            f"JAX_PLATFORMS={pinned} pins the job off the TPU; the benchmark "
            "runs on a TPU chip or not at all")


def build() -> float:
    """dynologd and dyno from the tracked sources, every run (a warm build
    is under a second); seconds taken."""
    t0 = time.time()
    if shutil.which("cmake") and shutil.which("ninja"):
        steps = (
            ["cmake", "-S", ROOT, "-B", BUILD, "-G", "Ninja",
             "-DCMAKE_BUILD_TYPE=Release"],
            ["cmake", "--build", BUILD, "--target", "dynologd", "dyno"])
    else:
        steps = (["bash", ROOT / "scripts" / "manual_build.sh"],)
    for cmd in steps:
        proc = subprocess.run(
            [str(c) for c in cmd], capture_output=True, text=True,
            timeout=900, cwd=ROOT)
        if proc.returncode != 0:
            raise RunRefused(
                f"build step {cmd[0]} failed:\n"
                f"{(proc.stdout + proc.stderr)[-3000:]}")
    for name in ("dynologd", "dyno"):
        if not (BIN / name).is_file():
            raise RunRefused(f"build produced no {BIN / name}")
    return time.time() - t0


class Daemon:
    """dynologd with the configuration's flags, started before JAX, as under
    systemd. `--tpu_metric_backend=grpc` named outright defers binding until
    the job's runtime serves localhost:8431."""

    def __init__(self, work: Path, flags: list):
        self.endpoint = f"perfbench_{os.getpid()}"
        self.log_path = work / "dynologd.log"
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [str(BIN / "dynologd"), "--port=0",
             f"--ipc_endpoint_name={self.endpoint}", "--nouse_JSON", *flags],
            stdout=subprocess.PIPE, stderr=self._log, text=True)
        self.port = None
        deadline = time.time() + 15
        while time.time() < deadline and self.proc.poll() is None:
            if not select.select([self.proc.stdout], [], [], 1.0)[0]:
                continue
            line = self.proc.stdout.readline()
            if line.startswith("DYNOLOG_PORT="):
                self.port = int(line.split("=", 1)[1])
                break
        if self.port is None:
            self.stop()
            raise RunRefused(
                "dynologd did not announce its port:\n"
                + self.log_path.read_text(errors="replace")[-2000:])

    def dyno(self, *args, timeout=60) -> subprocess.CompletedProcess:
        return subprocess.run(
            [str(BIN / "dyno"), f"--port={self.port}", *map(str, args)],
            capture_output=True, text=True, timeout=timeout)

    def query(self, names, start_ms: int = 0) -> dict:
        """name -> {"timestamps": [...], "values": [...]} from the store."""
        proc = self.dyno("query", "--metrics=" + ",".join(names),
                         f"--start_ts={start_ms}")
        if proc.returncode != 0:
            return {}
        body = proc.stdout.split("response = ", 1)[-1]
        try:
            return json.loads(body).get("metrics", {})
        except ValueError:
            return {}

    def cpu_seconds(self) -> float | None:
        """utime + stime of /proc/<pid>/stat; None where it cannot be read."""
        try:
            with open(f"/proc/{self.proc.pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return None

    def rss_kb(self) -> int | None:
        """VmRSS of /proc/<pid>/status; None where it cannot be read."""
        try:
            with open(f"/proc/{self.proc.pid}/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        return int(line.split()[1])
        except (OSError, IndexError, ValueError):
            pass
        return None

    def stop(self, timeout_s: float = 10.0) -> bool:
        """SIGTERM; True when the daemon exited by itself within timeout_s."""
        clean = True
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                clean = False
                self.proc.kill()
                self.proc.wait(timeout=10)
        self._log.close()
        return clean


def transformer_config(job: dict):
    import dataclasses

    from dynolog_tpu.models.transformer import TransformerConfig

    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    return TransformerConfig(**{k: v for k, v in job.items() if k in names})


def seed_key(seed: int):
    """A key from any whole number: --seed may pass 2**31."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def require_chips(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RunRefused(
            f"jax.devices()[0].platform is '{devices[0].platform}' "
            f"({devices[0].device_kind}), not 'tpu'. Nothing was run.")
    if len(devices) < chips:
        raise RunRefused(
            f"the cell asks for {chips} chips and JAX finds {len(devices)}")
    return devices[:chips]


class Operator(threading.Thread):
    """The closed-loop operator: `dyno gputrace`, wait for the shim's
    manifest, at once the next. No JAX in this thread. A capture's end is
    the manifest's mtime (the shim renames it into place when the artifact
    is whole), so the 5 ms poll only sets how soon the next one starts."""

    def __init__(self, daemon: Daemon, job_id: int, traffic: dict, work: Path,
                 until: float, limit: int | None = None, tag: str = "cap"):
        super().__init__(name="perfbench_operator", daemon=True)
        self.daemon, self.job_id, self.traffic = daemon, job_id, traffic
        self.work, self.until, self.limit, self.tag = work, until, limit, tag
        self.captures: list = []

    def run(self) -> None:
        while time.time() < self.until and (
                self.limit is None or len(self.captures) < self.limit):
            self.captures.append(self.capture(len(self.captures)))
            time.sleep(self.traffic["think_ms"] / 1e3)

    def capture(self, k: int) -> dict:
        stem = self.work / f"{self.tag}{k:03d}"
        manifest = Path(f"{stem}_{os.getpid()}.json")
        rec = {"k": k, "manifest_path": str(manifest), "ok": False}
        rec["spawn_t"] = time.time()
        cli = self.daemon.dyno(
            "gputrace", f"--job_id={self.job_id}",
            f"--duration_ms={self.traffic['window_ms']}",
            f"--log_file={stem}.json")
        rec["cli_rc"], rec["cli_ms"] = cli.returncode, (
            time.time() - rec["spawn_t"]) * 1e3
        if cli.returncode != 0:
            rec["error"] = f"dyno gputrace exit {cli.returncode}: " + (
                cli.stdout + cli.stderr)[-300:]
            return rec
        deadline = rec["spawn_t"] + CAPTURE_TIMEOUT_S
        while not manifest.exists():
            if time.time() > deadline:
                rec["error"] = f"no manifest within {CAPTURE_TIMEOUT_S:g} s"
                return rec
            time.sleep(0.005)
        rec["done_t"] = manifest.stat().st_mtime
        rec["capture_ms"] = (rec["done_t"] - rec["spawn_t"]) * 1e3
        try:
            rec["manifest"] = json.loads(manifest.read_text())
        except (OSError, ValueError) as e:
            rec["error"] = f"manifest unreadable: {e}"
            return rec
        rec["ok"] = rec["manifest"].get("status") == "ok"
        if not rec["ok"]:
            rec["error"] = f"manifest status {rec['manifest'].get('status')}"
        return rec


class Run:
    """The state of one run; `record` is what the metric readers see."""

    def __init__(self, cell: cells.Cell, seed: int, seconds: float,
                 trace: bool, t_process: float):
        self.cell, self.seed, self.seconds, self.trace = (
            cell, seed, seconds, trace)
        self.t_process = t_process
        self.job_id = seed % 100000 + 1
        self.work = OUT / f"{cell.name}-{seed}"
        self.record: dict = {
            "workload": cell.name, "seed": seed, "seconds": seconds,
            "traced": trace, "kind": cell.kind, "phases": {}, "checks": [],
            "step_ms": [], "captures": [], "capture_ms": []}
        self.daemon: Daemon | None = None
        self.client = None
        self.steps: list = []  # (end wall time, ms) of every step of the run
        self.parts: list = []  # [dispatch, device wait, shim] ms, alongside
        self.cache = {"hits": 0, "misses": 0}
        self.settled = False
        self.children_gone_s: float | None = None
        self.summarized = None  # (capture, profile) check C3 used
        self.c5: dict | None = None

    # ------------------------------------------------------------ set-up

    def phase(self, name: str, t0: float) -> None:
        self.record["phases"][name] = time.time() - t0

    def start_daemon(self) -> None:
        shutil.rmtree(self.work, ignore_errors=True)
        self.work.mkdir(parents=True)
        t0 = time.time()
        self.daemon = Daemon(self.work, self.cell.config["daemon_flags"])
        self.phase("daemon_s", t0)

    def start_jax(self) -> None:
        t0 = time.time()
        import jax

        # JAX reads JAX_COMPILATION_CACHE_DIR itself where it is set; else
        # the cache is at a fixed path inside the checkout.
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        # Every program is persisted, however fast it compiled, so that the
        # second run of a cell in a checkout compiles nothing.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

        def on_event(name: str, **_) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.cache["hits"] += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.cache["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        self.devices = require_chips(self.cell.chips)
        dev = self.devices[0]
        self.peaks = cells.load_peaks(dev.device_kind)
        self.record["device"] = {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(self.devices)}
        self.phase("jax_s", t0)

    def make_job(self) -> None:
        """Weights, check J, optimizer state, the step, the shim."""
        import jax

        from dynolog_tpu.client import TraceClient
        from dynolog_tpu.models.train import make_optimizer, make_train_step
        from dynolog_tpu.models.transformer import forward

        job = self.cell.job
        reference = cells.load_reference(self.cell.config)
        cfg = transformer_config(job)
        mesh = cells.build_mesh(self.cell.config["deployment"], self.devices)
        param_shardings = opt_shardings = None
        optimizer = make_optimizer()
        if mesh is not None:
            import optax
            from jax.sharding import NamedSharding, PartitionSpec

            from dynolog_tpu.parallel.sharding import shard_params

            abstract = jax.eval_shape(
                lambda k: reference.init_weights(k, job), seed_key(0))
            param_shardings = shard_params(abstract, mesh)
            replicated = NamedSharding(mesh, PartitionSpec())
            opt_shardings = optax.tree_utils.tree_map_params(
                optimizer, lambda _, sharding: sharding,
                jax.eval_shape(optimizer.init, abstract), param_shardings,
                transform_non_params=lambda _: replicated)
        t0 = time.time()
        key_w, key_b = jax.random.split(seed_key(self.seed))
        params = jax.jit(
            lambda k: reference.init_weights(k, job),
            out_shardings=param_shardings)(key_w)
        tokens = jax.random.randint(
            key_b, (job["batch"], job["seq"]), 0, job["vocab_size"], "int32")
        jax.block_until_ready(params)
        self.phase("init_s", t0)

        # Check J, first half: before the optimizer state exists, so the
        # reference's float32 temporaries never sit beside the full state.
        t0 = time.time()
        last = min(checks.J_POSITIONS, job["seq"])
        want, want_loss = reference.forward(params, tokens, job, last)
        got = jax.jit(lambda p, t: forward(p, t, cfg, mesh)[:, -last:])(
            params, tokens)
        self.j = {"logit_rel_rms": reference.rel_rms(got, want),
                  "ref_loss": float(want_loss)}
        self.j_limits = (reference.J_LOGIT_REL_RMS_LIMIT,
                         reference.J_LOSS_ABS_LIMIT)
        del want, got
        self.phase("reference_s", t0)

        t0 = time.time()
        opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(params)
        self.step_fn = self.load_step(
            make_train_step(cfg, mesh).lower(params, opt_state, tokens))
        mem = self.step_fn.memory_analysis()
        self.record["step_argument_bytes"] = mem.argument_size_in_bytes
        self.record["step_temp_bytes"] = mem.temp_size_in_bytes
        self.phase("step_build_s", t0)

        self.state = [params, opt_state]
        self.tokens = tokens
        del params, opt_state
        self.client = TraceClient(
            job_id=self.job_id, endpoint=self.daemon.endpoint,
            **self.cell.config["shim"])
        if not self.client.start():
            raise RunRefused(
                "TraceClient could not register with dynologd over "
                f"{self.daemon.endpoint}: {self.client.last_error}")

    def load_step(self, lowered):
        """The step's executable, always one that was LOADED from the
        persistent cache. The first run of a cell in a checkout compiles it;
        every later run loads it, and the profiler drains a loaded
        executable's trace differently (PERF.md, Findings). So a run that
        compiled drops what it compiled and loads it like the others."""
        misses = self.cache["misses"]
        step_fn = lowered.compile()
        if self.cache["misses"] > misses:
            step_fn = lowered.compile()
            self.record["step_compiled_then_loaded"] = True
        return step_fn

    def span(self, name: str):
        """A host span in the profiler's own trace, in traced runs only."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def step_once(self, samples: list | None = None) -> None:
        """One iteration: the span runs from the end of the last one to the
        end of this one, so the samples of a window add up to its length."""
        with self.span("dispatch"):
            self.state[0], self.state[1], loss = self.step_fn(
                self.state[0], self.state[1], self.tokens)
        t_dispatched = time.perf_counter()
        with self.span("device_wait"):
            loss.block_until_ready()
        t_ready = time.perf_counter()
        with self.span("between_steps"):
            self.client.step()
            self.losses.append(loss)
            now = time.perf_counter()
            ms = (now - self.t_last) * 1e3
            # where a long pass went: dispatch, device wait, the shim's step()
            self.parts.append([round((b - a) * 1e3, 1) for a, b in (
                (self.t_last, t_dispatched), (t_dispatched, t_ready),
                (t_ready, now))])
            self.t_last = now
            self.steps.append((time.time(), ms))
            if samples is not None:
                samples.append(ms)

    def step_while(self, busy) -> None:
        while busy():
            self.step_once()

    def warm_up(self) -> None:
        t0 = time.time()
        self.losses: list = []
        self.t_last = time.perf_counter()
        for _ in range(WARM_STEPS):
            self.step_once()
        self.j["step_loss"] = float(self.losses[0])
        self.record["warm_step_ms"] = [ms for _, ms in self.steps]
        if self.cell.kind == "capture":
            # One capture outside the window: the profiler's first session
            # in a process pays its own initialisation.
            warm = Operator(self.daemon, self.job_id, self.cell.traffic,
                            self.work, time.time() + CAPTURE_TIMEOUT_S,
                            limit=1, tag="warm")
            warm.start()
            self.step_while(warm.is_alive)
            self.record["warm_capture"] = warm.captures
        self.phase("warm_s", t0)

    # ------------------------------------------------------------ window

    def window(self) -> None:
        rec = self.record
        operator = None
        cpu0 = self.daemon.cpu_seconds()
        self.losses = []
        rec["window_start"] = t0 = time.time()
        rec["setup_s"] = (
            t0 - self.t_process - rec["phases"]["reference_s"])
        self.t_last = time.perf_counter()
        if self.cell.kind == "capture":
            operator = Operator(self.daemon, self.job_id, self.cell.traffic,
                                self.work, t0 + self.seconds)
            operator.start()
        while time.time() - t0 < self.seconds:
            self.step_once(rec["step_ms"])
        rec["window_end"] = time.time()
        n = len(rec["step_ms"])
        ends = zip(self.steps[-n:], self.parts[-n:])
        rec["longest_passes"] = [
            [round(ms, 1), round(t - t0, 4), parts]
            for (t, ms), parts in sorted(ends, key=lambda s: -s[0][1])[:3]]
        cpu1 = self.daemon.cpu_seconds()
        rec["daemon_rss_kb"] = self.daemon.rss_kb()
        rec["window_s"] = rec["window_end"] - t0
        if cpu0 is not None and cpu1 is not None:
            rec["daemon_cpu_s"] = cpu1 - cpu0
        rec["nonfinite_losses"] = sum(
            1 for x in self.losses if not (abs(float(x)) < float("inf")))
        if operator is not None:
            # A capture open at the window's end is waited for and counted;
            # the job keeps stepping, because the capture needs device work.
            self.step_while(operator.is_alive)
            rec["captures"] = operator.captures
            rec["capture_ms"] = [
                c["capture_ms"] for c in operator.captures if c["ok"]]

    def own_trace(self) -> str:
        """Steady traffic has no capture of its own: three steps under
        jax.profiler, in the traced run only, after the window."""
        import jax

        trace_dir = str(self.work / "own_trace")
        jax.profiler.start_trace(trace_dir)
        for _ in range(3):
            self.step_once()
        jax.profiler.stop_trace()
        return trace_dir

    # ------------------------------------------------------ after window

    def reduce_trace(self, trace_dir: str) -> None:
        """busy/window of the device section and the breakdown, from one
        trace, by the benchmark's reducer."""
        path = xplane.find_xplane(trace_dir)
        if path is None:
            return
        profile = xplane.load(path)
        planes = [xplane.reduce_plane(p) for i in range(self.cell.chips)
                  if (p := xplane.find_plane(
                      profile, xplane.device_plane_name(i))) is not None]
        planes = [p for p in planes if p is not None]
        if not planes:
            return
        first = planes[0]
        spans = xplane.host_spans(profile, SPAN_PREFIX)
        self.record["trace"] = {
            "path": path,
            "busy_s": sum(p.busy_ns for p in planes) / len(planes) / 1e9,
            "window_s": sum(p.span_ns for p in planes) / len(planes) / 1e9,
            "idle_pct": sum(p.idle_pct for p in planes) / len(planes),
            "groups": first.top_groups(10),
            "op_total_s": sum(t for t, _ in first.ops.values()) / 1e9,
            "idle_gaps": xplane.label_gaps(first.gaps, spans, SPAN_PREFIX),
        }

    def average_captures(self, good: list) -> None:
        """Busy and window over ALL the run's captures (check C1 reduced each
        plane already): one capture's idle share swings with where its
        window fell (1.3 % and 29 % were both read at 1B); the breakdown
        stays the last capture's."""
        pairs = [p for c in good for p in c.get("device_ns", [])]
        trace = self.record.get("trace")
        if not pairs or not trace:
            return
        busy, window = (sum(p[i] for p in pairs) for i in (0, 1))
        trace.update(busy_s=busy / 1e9 / self.cell.chips,
                     window_s=window / 1e9 / self.cell.chips,
                     idle_pct=100.0 * (1.0 - busy / window))

    def drain_and_check(self) -> None:
        rec = self.record
        store = checks.wait_for_telemetry(self)
        rec["checks"].append(checks.check_j(self.j, *self.j_limits))
        rec["checks"].append(checks.check_s1(self, store))
        rec["checks"].append(checks.check_s2(self))
        if self.cell.kind == "capture":
            rec["checks"] += checks.check_captures(self)
        if self.trace:
            good = [c for c in rec["captures"] if c["ok"]]
            held = [c for c in good if c.get("device_ns")]
            if held:
                self.reduce_trace(held[-1]["manifest"]["trace_dir"])
                self.average_captures(good)
            elif self.cell.kind == "steady":
                self.reduce_trace(self.own_trace())

    def read_journals(self) -> None:
        """What the daemon and the shim recorded by themselves, for the
        readers of traced runs: after the window and after every check, so
        it costs the run nothing but those readers' values where the daemon
        does not answer."""
        rec = self.record
        client = self.client
        rec["shim_counters"] = {
            "traces_completed": client.traces_completed,
            "daemon_reconnects": client.daemon_reconnects,
            "last_error": client.last_error,
            "steps": getattr(client, "_step_count", None)}
        t0 = time.time()
        try:
            proc = self.daemon.dyno("selftrace", timeout=30)
            if proc.returncode != 0:
                raise ValueError(f"dyno selftrace exit {proc.returncode}: "
                                 + (proc.stdout + proc.stderr)[-300:])
            rec["selftrace"] = selftrace.parse(proc.stdout)
        except (OSError, subprocess.SubprocessError, ValueError, KeyError,
                TypeError) as e:
            rec["selftrace"] = {"error": f"{type(e).__name__}: {e}"}
        rec["selftrace_oldest_ms"] = selftrace.oldest_ms(rec["selftrace"])
        self.phase("journals_s", t0)

    def settle(self) -> None:
        """Stops the shim and waits for its convert children, once, with
        the daemon still up: a child hands its `trace.convert` span to the
        daemon as it exits, so the journal is read after this. Then, in
        capture cells, one look at what the children wrote: the files'
        mtimes and sizes (`derived_ms`) and check C5."""
        if self.settled:
            return
        self.settled = True
        t0 = time.time()
        if self.client is not None:
            self.client.stop()
        self.children_gone_s = checks.wait_children_gone(
            skip={self.daemon.proc.pid} if self.daemon else set())
        self.phase("children_s", t0)
        if self.cell.kind == "capture" and "window_end" in self.record:
            t0 = time.time()
            rec = self.record
            for cap in rec["captures"]:
                checks.read_derived(cap)
            rec["derived_ms"] = [
                c["derived_ms"] for c in rec["captures"] if "derived_ms" in c]
            self.c5 = checks.check_c5(self)
            self.phase("derived_s", t0)

    def teardown(self) -> None:
        """Stops the daemon, after the shim and its convert children;
        check C4 is whether each went by itself in its time."""
        t0 = time.time()
        self.settle()
        daemon_clean = self.daemon.stop() if self.daemon else True
        self.record["checks"].append(
            checks.check_c4(self.children_gone_s, daemon_clean))
        if self.c5 is not None:
            self.record["checks"].append(self.c5)
        self.phase("teardown_s", t0)


def memory_peak_bytes(run: Run) -> int:
    """The fullest chip's peak: the runtime's HBM gauge through the daemon
    (it counts program temporaries; the allocator's peak_bytes_in_use does
    not), or the allocator's where the gauge read nothing."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in run.devices)
    return int(max(peak, run.record.get("hbm_used_max", 0)))


def result_line(run: Run, bench: dict, readers: dict) -> dict:
    rec = run.record
    metrics = {}
    if run.trace:
        for name in cells.metric_names(bench, run.cell, "per_layer"):
            reader = readers.get(name)
            value = reader.read(rec) if reader is not None else None
            if value is not None:
                metrics[name] = {"value": float(value), "unit": reader.UNIT}
    else:
        due = cells.metric_names(bench, run.cell, "end_to_end")
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        for name, value in end_to_end(rec).items():
            if name in due and value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    failed_captures = sum(1 for c in rec["captures"] if not c["ok"])
    device = dict(rec["device"], memory_peak_bytes=memory_peak_bytes(run))
    line = {
        "correct": all(c["ok"] for c in rec["checks"]),
        "attempted": len(rec["captures"]) + len(rec["step_ms"]),
        "failed": failed_captures + rec.get("nonfinite_losses", 0),
        "metrics": metrics, "device": device}
    if run.trace and "trace" in rec:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        line["breakdown"] = {"device_ops": rec["trace"]["groups"],
                             "idle_gaps": rec["trace"]["idle_gaps"]}
    line["compared"] = compared(rec)
    return line


def compared(rec: dict) -> list:
    """Every number `correct` compared, `[check, what, number, limit, ok]`,
    those that failed last: what is kept of a run that is not correct is the
    end of its last line and of its standard error."""
    def plain(value):
        # NaN and Infinity are not JSON: a reading that is one goes as text
        if isinstance(value, float) and not math.isfinite(value):
            return repr(value)
        return value

    rows = [[c["name"], p["what"], plain(p["value"]), p["limit"], p["ok"]]
            for c in rec["checks"] for p in c["compared"]]
    return sorted(rows, key=lambda row: not row[4])


def end_to_end(rec: dict) -> dict:
    """The end-to-end metrics, from all the steps and all the captures of
    the window. A tail is not printed where a window of this length holds,
    at the median step, fewer than ten samples beyond it. A metric added
    since PR 24 is its own file under perfbench/end_to_end/."""
    out = {"setup_s": rec["setup_s"],
           "step_ms_p50": stats.median(rec["step_ms"]),
           "step_ms_p95": None, "capture_ms_p50": None}
    for name, metric in cells.load_end_to_end().items():
        out[name] = metric.read(rec)
    try:
        out["step_ms_p95"] = stats.tail(
            rec["step_ms"], 0.95, rec["window_s"] * 1e3)
    except stats.TooFewSamples as e:
        say(f"step_ms_p95 not printed: {e}")
    if rec["capture_ms"]:
        out["capture_ms_p50"] = stats.median(rec["capture_ms"])
    return out


def measure(run: Run) -> None:
    preflight()
    if run.cell.traffic.get("mode", "pull") != "pull":
        raise RunRefused(
            f"traffic '{run.cell.traffic_name}' asks for capture mode "
            f"'{run.cell.traffic['mode']}': it parses as data, but only the "
            "pull operator (dyno gputrace through the shim) is built")
    t0 = time.time()
    run.record["phases"]["build_s"] = build()
    sys.path.insert(0, str(ROOT))
    run.start_daemon()
    try:
        run.start_jax()
        run.make_job()
        run.warm_up()
        run.window()
        run.drain_and_check()
        run.settle()
        if run.trace:
            run.read_journals()
    finally:
        run.teardown()
    run.record["phases"]["total_s"] = time.time() - t0


def main(argv=None, t_process: float | None = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        bench = cells.load_benchmark()
        cell = cells.load_cell(args.workload)
        readers = cells.load_readers()
        run = Run(cell, args.seed, args.seconds, bool(args.trace),
                  t_process if t_process is not None else time.time())
        measure(run)
        line = result_line(run, bench, readers)
    except (RunRefused, cells.BenchmarkError) as e:
        print(f"perfbench: no result: {e}", file=sys.stderr, flush=True)
        return 1
    report(run, line)
    print(json.dumps(line, default=str), flush=True)
    for name, what, value, limit, ok in line["compared"]:
        print(f"check {name} {'ok  ' if ok else 'FAIL'} {what}: {value} "
              f"(limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    return 0


def report_journals(rec: dict) -> None:
    """The journal's census (the ring's headroom is spans_recorded against
    ring_capacity), and the three longest passes with every daemon span
    (tick, verb, hand-off) that lies over each."""
    journal = rec["selftrace"]
    if "error" in journal:
        say(f"dyno selftrace gave nothing: {journal['error']}")
        return
    counts = collections.Counter(s["name"] for s in journal["spans"])
    say(f"selftrace: {journal['spans_recorded']} spans recorded, ring of "
        f"{journal['ring_capacity']}; the oldest began "
        f"{rec['window_start'] - (rec['selftrace_oldest_ms'] or 0) / 1e3:.1f}"
        f" s before the window; ipc_wakeups {journal['ipc_wakeups']}, "
        f"tpu_rows {journal['tpu_rows']}; shim {rec['shim_counters']}; "
        f"by name {json.dumps(dict(sorted(counts.items())))}")
    for longest in rec.get("longest_passes", []):
        say(f"pass of {longest[0]} ms, ended {longest[1]} s into the window, "
            f"[dispatch, device wait, shim] {longest[2]}; daemon spans over "
            "it [name, began ms after the pass did, ms]: "
            f"{selftrace.spans_over(rec, longest)}")


def report(run: Run, line: dict) -> None:
    """Everything that is not the last line or a compared number (those go
    into the line and, after it, to standard error): to earlier lines and
    to perfbench/out/<workload>-<seed>.json. The captures' artifacts go."""
    rec = run.record
    say(f"warm steps ms: {[round(x, 1) for x in rec['warm_step_ms']]}; daemon "
        f"CPU in the window {rec.get('daemon_cpu_s')} s; longest passes "
        f"[ms, ended s into the window, [dispatch, device wait, shim] ms]: {rec.get('longest_passes')}")
    if "selftrace" in rec:
        report_journals(rec)
    say("phases: " + json.dumps(
        {k: round(v, 2) for k, v in rec["phases"].items()}))
    say(f"compile cache: {run.cache['hits']} hits, {run.cache['misses']} "
        f"misses; steps in window {len(rec['step_ms'])}, captures "
        f"{len(rec['captures'])} ({len(rec['capture_ms'])} ok)")
    if rec["captures"]:
        say("steps in each capture's window, job / device plane: " + " ".join(
            f"{c.get('steps_in_window')}/{c.get('executions')}"
            for c in rec["captures"]))
    if rec["capture_ms"]:
        say("capture_ms: " + " ".join(f"{x:.0f}" for x in rec["capture_ms"]))
        say("collect_ms: " + " ".join(
            str(c["manifest"]["timing"].get("collect_ms"))
            for c in rec["captures"] if c["ok"]))
        say(f"derived_ms ({len(rec.get('derived_ms', []))} of "
            f"{len(rec['capture_ms'])} ok captures have both derived "
            "files): " + " ".join(
                f"{x:.0f}" for x in rec.get("derived_ms", [])))
    slim = dict(rec, result=line)
    path = OUT / f"{run.cell.name}-{run.seed}-t{int(run.trace)}.json"
    with open(path, "w") as f:
        json.dump(slim, f, indent=1, default=str)
    shutil.rmtree(run.work, ignore_errors=True)
