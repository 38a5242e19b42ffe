"""In-process trace shim for JAX applications.

Plays the role libkineto plays in the reference stack (SURVEY §3.5): at app
start it registers with the local dynologd over the IPC fabric, then polls
for on-demand configs; when the operator runs `dyno gputrace/tpurace`, the
received key=value config is parsed and an XLA trace is captured with
`jax.profiler.start_trace` / `stop_trace`. Beyond the reference protocol,
the shim also subscribes to config "kick" datagrams: the daemon wakes it
the moment a capture is triggered (its IPC thread blocks in poll(2) and
wakes on the posted config and on the request), so pickup costs two
thread wake-ups instead of ~poll_interval/2 (polling remains the delivery
mechanism — kicks are purely a latency optimization). Beyond the reference: if the app
calls step(), the shim also reports step rate + step-time percentiles to
the daemon every report_interval_s (fire-and-forget "pstat" datagram),
giving the daemon's metric history — and its auto-trigger rules — an
application-level job<id>.* signal. With DYNO_TPU_RING_EVERY_N set (or a
RingConfig passed in), the shim also runs a continuous capture ring:
1-in-N steps it samples a short window, promotes the XSpace to a compact
op-level profile, and retains the newest K per
model in a TTL'd ring directory — the always-on feed
`python -m dynolog_tpu.diagnose --ring` diagnoses (see docs/DIAGNOSIS.md).

Config keys understood (the same text format the reference CLI emits,
cli/src/commands/gputrace.rs:28-40):

    PROFILE_START_TIME=<unix ms, 0 = now>
    ACTIVITIES_LOG_FILE=<output path>
    ACTIVITIES_DURATION_MSECS=<ms>          (duration mode)
    ACTIVITIES_ITERATIONS=<n>               (iteration mode; needs step())
    PROFILE_START_ITERATION_ROUNDUP=<r>

Usage::

    from dynolog_tpu.client import TraceClient

    client = TraceClient(job_id=42)
    client.start()
    for batch in data:
        train_step(batch)
        client.step()   # enables iteration-based traces (optional)
"""

from __future__ import annotations

import collections
import json
import logging
import math
import os
import resource
import shutil
import statistics
import tempfile
import threading
import time
from dataclasses import dataclass, field

from dynolog_tpu import failpoints, obs, stream as stream_mod
from dynolog_tpu.client import ipc

_log = logging.getLogger("dynolog_tpu.shim")

# Stale-artifact sweep default TTL (DYNO_TPU_SWEEP_TTL_S overrides; the
# TraceClient(sweep_ttl_s=...) knob wins over both; <= 0 disables). A day:
# long past any live capture/export, short enough that a crash-looping
# job can't fill the trace volume with orphaned debris.
def _ttl_from_env() -> float:
    raw = os.environ.get("DYNO_TPU_SWEEP_TTL_S")
    if raw is None:
        return 24 * 3600
    try:
        return float(raw)
    except ValueError:
        # Soft-fail like every other shim path: a typo'd knob must not
        # abort the training job at import.
        logging.getLogger("dynolog_tpu.shim").warning(
            "DYNO_TPU_SWEEP_TTL_S=%r is not a number; using default", raw)
        return 24 * 3600


DEFAULT_SWEEP_TTL_S = _ttl_from_env()

# Sweep scan bounds: trace trees are small; a misconfigured log_file
# pointing the sweep at a huge directory must cost a bounded scan, not a
# filesystem crawl.
_SWEEP_MAX_DEPTH = 6
_SWEEP_MAX_ENTRIES = 10000


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except (PermissionError, OSError):
        return True  # exists (another user's), or unknowable: keep it
    return True


def _trace_session_dir(path: str, prefix: str) -> int | None:
    """The pid of a `<prefix>_<pid>` trace-session dir, or None if `path`
    doesn't look like one. Requires the shim's OWN trace base name as the
    prefix (a foreign `worker_4821/` lock dir in a shared /tmp must never
    qualify, however old) and a layout the shim itself produces — empty,
    or carrying the TensorBoard plugins/ tree."""
    base = os.path.basename(path.rstrip(os.sep))
    head, sep, pid_part = base.rpartition("_")
    if not sep or head != prefix or not pid_part.isdigit():
        return None
    try:
        entries = os.listdir(path)
    except OSError:
        return None
    if entries and "plugins" not in entries:
        return None
    return int(pid_part)


def _sweep_tmps_under(session_dir: str, cutoff: float,
                      reclaimed: list[str]) -> None:
    """Expired *.tmp atomic-write leftovers INSIDE an identified
    trace-session dir (ours by identification; a SIGKILL'd export child's
    half-written trace.json.gz.tmp / summary.json.tmp land here)."""
    entries_seen = 0
    for dirpath, dirnames, filenames in os.walk(session_dir, topdown=True):
        depth = dirpath[len(session_dir):].count(os.sep)
        if depth >= _SWEEP_MAX_DEPTH:
            dirnames[:] = []
        entries_seen += len(dirnames) + len(filenames)
        if entries_seen > _SWEEP_MAX_ENTRIES:
            _log.warning(
                "stale-artifact sweep of %s stopped at %d entries",
                session_dir, _SWEEP_MAX_ENTRIES)
            return
        for name in filenames:
            if not name.endswith(".tmp"):
                continue
            path = os.path.join(dirpath, name)
            try:
                if os.path.getmtime(path) >= cutoff:
                    continue
                os.unlink(path)
            except OSError:
                continue
            _log.info("reclaimed stale artifact: %s", path)
            reclaimed.append(path)


def sweep_stale_artifacts(
    trace_base: str, ttl_s: float = DEFAULT_SWEEP_TTL_S, *,
    now: float | None = None
) -> list[str]:
    """Garbage-collects debris a SIGKILL'd capture/export child left
    around ``trace_base`` (the log_file path minus its .json suffix —
    what TraceConfig.trace_dir derives session dirs from), touching ONLY
    artifacts the shim can positively identify as its own: the parent
    directory is often a shared /tmp, so everything reclaimed must carry
    the trace base's own name prefix — a generic "every old *.tmp /
    every `X_<pid>` dir" sweep would destroy other programs' files:

    - `<base>_<pid>` trace-session dirs (empty or TensorBoard-shaped)
      whose pid is dead, that are older than ``ttl_s``, and that have NO
      sibling `<base>_<pid>.json` manifest — the manifest is the
      completion signal, so a successfully captured trace is never
      reclaimed out from under the operator;
    - expired ``*.tmp`` files *inside* such session dirs (dead or alive —
      the TTL alone guards in-flight writes there);
    - expired `<base>_<pid>.json.tmp` manifest leftovers of dead pids
      next to them.

    Returns the reclaimed paths, one log line each. Best-effort: races
    with a concurrent capture lose politely (ENOENT ignored)."""
    trace_base = os.path.abspath(trace_base)
    root = os.path.dirname(trace_base)
    prefix = os.path.basename(trace_base)
    if ttl_s <= 0 or not prefix or not os.path.isdir(root):
        return []
    cutoff = (now if now is not None else time.time()) - ttl_s
    reclaimed: list[str] = []
    try:
        entries = os.listdir(root)
    except OSError:
        return []
    for name in entries:
        # The name decides first, with no stat: only `<prefix>_<pid>` and
        # `<prefix>_<pid>.json.tmp` can be this base's, so a directory
        # full of other captures (an operator who names each one) costs
        # this listing and nothing more.
        is_manifest_tmp = name.endswith(".json.tmp")
        stem = name[: -len(".json.tmp")] if is_manifest_tmp else name
        head, sep, pid_part = stem.rpartition("_")
        if not sep or head != prefix or not pid_part.isdigit():
            continue
        path = os.path.join(root, name)
        if is_manifest_tmp:
            # Manifest atomic-write leftover: `<base>_<pid>.json.tmp`
            # (a directory of that name fails the unlink and stays).
            if _pid_alive(int(pid_part)):
                continue
            try:
                if os.path.getmtime(path) >= cutoff:
                    continue
                os.unlink(path)
            except OSError:
                continue
            _log.info("reclaimed stale artifact: %s", path)
            reclaimed.append(path)
        elif os.path.isdir(path):
            pid = _trace_session_dir(path, prefix)
            if pid is None:
                continue
            _sweep_tmps_under(path, cutoff, reclaimed)
            try:
                expired = os.path.getmtime(path) < cutoff
            except OSError:
                continue
            if not expired or _pid_alive(pid):
                continue
            if os.path.exists(path + ".json"):
                # Completed capture (its manifest still stands): the
                # operator's artifact, not debris.
                continue
            shutil.rmtree(path, ignore_errors=True)
            _log.info(
                "reclaimed stale trace-session dir (pid %d gone): %s",
                pid, path)
            reclaimed.append(path)
    return reclaimed


def _sweep_warmup_dirs(ttl_s: float) -> list[str]:
    """Startup sweep of SIGKILL'd warmup leftovers in the system tempdir
    (dynolog_tpu_warmup_* dirs are created per process and removed in a
    finally: only a killed process leaves one behind)."""
    if ttl_s <= 0:
        return []
    cutoff = time.time() - ttl_s
    reclaimed = []
    tmpdir = tempfile.gettempdir()
    try:
        entries = os.listdir(tmpdir)
    except OSError:
        return []
    for name in entries:
        if not name.startswith("dynolog_tpu_warmup_"):
            continue
        path = os.path.join(tmpdir, name)
        try:
            if not os.path.isdir(path) or os.path.getmtime(path) >= cutoff:
                continue
        except OSError:
            continue
        shutil.rmtree(path, ignore_errors=True)
        _log.info("reclaimed stale warmup dir: %s", path)
        reclaimed.append(path)
    return reclaimed


@dataclass
class RingConfig:
    """Continuous-capture ring knobs (see CaptureRing).

    Env overrides (read by ``from_env``), so a training job opts in with
    environment alone — no code change:

        DYNO_TPU_RING_EVERY_N      sample 1-in-N steps (0 = ring off)
        DYNO_TPU_RING_KEEP         profiles retained per model
        DYNO_TPU_RING_WINDOW_MS    capture window per sample
        DYNO_TPU_RING_DIR          ring root directory
        DYNO_TPU_RING_MODEL       model tag (per-model subdirectory)
        DYNO_TPU_RING_TTL_S        max profile age
        DYNO_TPU_RING_MIN_INTERVAL_S  rate cap between samples
    """

    every_n_steps: int = 0  # 0 = ring off
    keep: int = 8
    window_ms: int = 100
    dir: str = ""  # empty = <tempdir>/dynolog_tpu_ring
    model: str = "default"
    ttl_s: float = 24 * 3600
    # Rate cap independent of step rate: a 5ms-step job with every_n=100
    # must not profile twice a second.
    min_interval_s: float = 30.0
    top_ops: int = 40

    def root(self) -> str:
        return self.dir or os.path.join(
            tempfile.gettempdir(), "dynolog_tpu_ring")

    @classmethod
    def from_env(cls, env=None) -> "RingConfig":
        env = os.environ if env is None else env
        cfg = cls()
        for key, attr, cast in (
            ("DYNO_TPU_RING_EVERY_N", "every_n_steps", int),
            ("DYNO_TPU_RING_KEEP", "keep", int),
            ("DYNO_TPU_RING_WINDOW_MS", "window_ms", int),
            ("DYNO_TPU_RING_DIR", "dir", str),
            ("DYNO_TPU_RING_MODEL", "model", str),
            ("DYNO_TPU_RING_TTL_S", "ttl_s", float),
            ("DYNO_TPU_RING_MIN_INTERVAL_S", "min_interval_s", float),
        ):
            raw = env.get(key)
            if raw is None:
                continue
            try:
                setattr(cfg, attr, cast(raw))
            except ValueError:
                # A typo'd knob must not abort the training job; the
                # ring simply keeps its default for that field.
                _log.warning("%s=%r is not a %s; ignored",
                             key, raw, cast.__name__)
        return cfg


class CaptureRing:
    """Rolling, sampled profile ring: every 1-in-N training steps
    (rate-capped), capture a short window and *promote* the raw XSpace
    to a compact op-level profile (trace.compact_profile, in this
    process), retaining the newest K per model in a TTL'd
    ring directory. The raw xspace and its temp session dir are deleted
    after promotion — the ring stores diagnosis-ready summaries, not
    trace trees, so always-on profiling costs kilobytes, not gigabytes.

    Profiles are schema-versioned envelopes `dynolog_tpu.diagnose`
    accepts directly: `python -m dynolog_tpu.diagnose --ring DIR
    --baseline B` diagnoses the newest one with no conversion step.

    Drives the SAME profiler backend as on-demand captures, from the
    shim's poll thread — a ring sample occupies the poll loop for
    ~window_ms + promotion, which the min-interval cap keeps rare.
    """

    PROFILE_SUFFIX = ".ringprof.json"

    def __init__(self, config: RingConfig):
        self.config = config
        self.captures = 0
        self.last_path: str | None = None
        self.last_error: str | None = None
        self._pending = False
        # -inf, not 0.0: time.monotonic() counts from boot, so on a host
        # whose uptime is below min_interval_s a zero start would read as
        # "captured just now" and the first ring capture would never arm.
        self._last_capture_t = float("-inf")
        self._last_step_seen = 0

    # -- sampling decision (called from step(), must stay trivial) ------

    def note_step(self, step_count: int) -> None:
        n = self.config.every_n_steps
        if n <= 0 or self._pending:
            return
        # Boundary crossing, not equality: with every_n=100 a burst of
        # steps between polls must arm at most once.
        if step_count // n > self._last_step_seen // n:
            self._last_step_seen = step_count
            if (time.monotonic() - self._last_capture_t
                    >= self.config.min_interval_s):
                self._pending = True
            # else: rate-capped; the next boundary re-tests.
        else:
            self._last_step_seen = step_count

    def due(self) -> bool:
        return self._pending

    # -- capture + promotion (poll thread) ------------------------------

    def capture(self, profiler) -> str | None:
        """One ring sample: capture, promote, store, prune. Returns the
        stored profile path (None on failure; last_error says why)."""
        from dynolog_tpu import trace as trace_mod

        self._pending = False
        self._last_capture_t = time.monotonic()
        tmp = tempfile.mkdtemp(prefix="dynolog_tpu_ring_cap_")
        # Ring captures must not spawn the trace.json.gz export child —
        # the xspace is promoted in place and discarded.
        had_export = getattr(profiler, "export_trace_json", None)
        if had_export is not None:
            profiler.export_trace_json = False
        try:
            with obs.span("shim.ring_capture"):
                profiler.start(tmp)
                time.sleep(self.config.window_ms / 1000.0)
                profiler.stop()
                # The streaming stop hands back an in-flight write; the
                # ring promotes in place, so it must wait for the bytes.
                take = getattr(profiler, "take_pending_write", None)
                pending = take() if take is not None else None
                if pending is not None:
                    pending.wait(30.0)
            xplanes = trace_mod.find_xplane_files(tmp)
            if not xplanes:
                self.last_error = "ring capture produced no xplane"
                return None
            with obs.span("shim.ring_promote"):
                with open(xplanes[-1], "rb") as f:
                    data = f.read()
                profile = trace_mod.compact_profile(
                    data, top=self.config.top_ops)
            path = self._store(profile)
            self.captures += 1
            self.last_path = path
            self.last_error = None
            return path
        except Exception as e:  # noqa: BLE001 - the ring is best-effort
            # telemetry; a failed sample must never cost the poll loop
            # (on-demand tracing rides it).
            self.last_error = f"ring capture failed: {e}"
            return None
        finally:
            if had_export is not None:
                profiler.export_trace_json = had_export
            shutil.rmtree(tmp, ignore_errors=True)

    def _store(self, profile: dict) -> str:
        from dynolog_tpu import trace as trace_mod

        model_dir = os.path.join(self.config.root(), self.config.model)
        os.makedirs(model_dir, exist_ok=True)
        doc = {
            # Same envelope discipline as diagnose.save_baseline: the
            # diagnosis engine refuses mismatched schemas loudly.
            "schema": 1,
            "kind": "dynolog_tpu.ring_profile",
            "model": self.config.model,
            "created_ms": int(time.time() * 1000),
            "step": self._last_step_seen,
            "window_ms": self.config.window_ms,
            "pid": os.getpid(),
            "summary": profile,
        }
        path = os.path.join(
            model_dir,
            "%d_s%d%s" % (doc["created_ms"], doc["step"],
                          self.PROFILE_SUFFIX))
        trace_mod.stream_write(path, [json.dumps(doc, indent=1).encode()])
        self._prune(model_dir)
        return path

    def _prune(self, model_dir: str) -> None:
        entries = self.entries(model_dir)
        for victim in entries[: max(len(entries) - self.config.keep, 0)]:
            try:
                os.unlink(victim)
            except OSError:
                pass

    def entries(self, model_dir: str | None = None) -> list[str]:
        """This model's stored profiles, oldest first."""
        model_dir = model_dir or os.path.join(
            self.config.root(), self.config.model)
        try:
            names = os.listdir(model_dir)
        except OSError:
            return []
        return sorted(
            os.path.join(model_dir, n) for n in names
            if n.endswith(self.PROFILE_SUFFIX))

    def sweep(self, now: float | None = None) -> list[str]:
        """TTL sweep across EVERY model under the ring root (startup
        hygiene, same posture as sweep_stale_artifacts): expired
        profiles and long-dead capture tmpdirs are reclaimed."""
        if self.config.ttl_s <= 0:
            return []
        cutoff = (now if now is not None else time.time()) - self.config.ttl_s
        reclaimed: list[str] = []
        root = self.config.root()
        try:
            models = os.listdir(root)
        except OSError:
            return []
        for model in models:
            model_dir = os.path.join(root, model)
            if not os.path.isdir(model_dir):
                continue
            for path in self.entries(model_dir):
                try:
                    if os.path.getmtime(path) >= cutoff:
                        continue
                    os.unlink(path)
                except OSError:
                    continue
                _log.info("reclaimed expired ring profile: %s", path)
                reclaimed.append(path)
        return reclaimed


_run_seq_lock = threading.Lock()
_run_seq = 0


def _next_run_seq() -> int:
    global _run_seq
    with _run_seq_lock:
        _run_seq += 1
        return _run_seq


def _unique_run_name() -> str:
    """TensorBoard run-dir name for one capture. Second-resolution stamps
    collide when two captures finish within the same second (the second
    overwrites the first's xplane.pb and races its in-flight background
    export) — suffix milliseconds plus a per-process counter so
    back-to-back and concurrent captures never share a dir."""
    return "%s_%03d_p%d_%d" % (
        time.strftime("%Y_%m_%d_%H_%M_%S"),
        int(time.time() * 1000) % 1000,
        os.getpid(),
        _next_run_seq(),
    )


@dataclass
class TraceConfig:
    """Parsed on-demand trace request."""

    log_file: str = ""
    start_time_ms: int = 0
    duration_ms: int = 500
    iterations: int = -1
    iteration_roundup: int = 1
    # Control-plane trace context (TRACE_CONTEXT=..., injected by the
    # daemon's RPC verb or authored by unitrace): the id under which this
    # capture's shim/convert spans are recorded, so `dyno selftrace`
    # shows the whole request across both languages.
    trace_ctx: str = ""
    raw: dict = field(default_factory=dict)

    @classmethod
    def parse(cls, text: str) -> "TraceConfig":
        cfg = cls()
        for line in text.replace("\\n", "\n").splitlines():
            line = line.strip()
            if not line or "=" not in line:
                continue
            key, value = line.split("=", 1)
            key = key.strip().upper()
            value = value.strip()
            cfg.raw[key] = value
            try:
                if key == "ACTIVITIES_LOG_FILE":
                    cfg.log_file = value
                elif key == "PROFILE_START_TIME":
                    cfg.start_time_ms = int(value)
                elif key == "ACTIVITIES_DURATION_MSECS":
                    cfg.duration_ms = int(value)
                elif key == "ACTIVITIES_ITERATIONS":
                    cfg.iterations = int(value)
                elif key == "PROFILE_START_ITERATION_ROUNDUP":
                    cfg.iteration_roundup = int(value)
                elif key == obs.CONFIG_KEY:
                    cfg.trace_ctx = value
            except ValueError:
                pass
        return cfg

    def trace_dir(self, pid: int) -> str:
        """Directory the XLA trace is written to, derived from log_file the
        way the reference derives per-pid trace paths (gputrace.rs:70-77)."""
        base = self.log_file or "/tmp/dynolog_tpu_trace.json"
        if base.endswith(".json"):
            base = base[:-5]
        return f"{base}_{pid}"

    def manifest_path(self, pid: int) -> str:
        base = self.log_file or "/tmp/dynolog_tpu_trace.json"
        if base.endswith(".json"):
            return f"{base[:-5]}_{pid}.json"
        return f"{base}_{pid}.json"


class PendingWrite:
    """One capture's deferred artifact write, running on its own writer
    thread: the collect thread feeds `queue` (bounded — backpressure
    bounds memory, not artifact size) and returns to its caller; the
    writer drains the queue through `trace.stream_write` (atomic
    tmp + rename, tmp unlinked on any failure) and then runs
    `on_complete` with the path, or with None where the write failed (the
    shim hangs the export child's hand-over there; what it returns joins
    `result`). This is
    what kills the stop stall: the poll thread's occupancy per capture
    shrinks to the collect itself, and back-to-back captures overlap one
    capture's write with the next one's window.
    """

    def __init__(self, path: str, on_complete=None, max_chunks: int = 8,
                 ctx: "obs.TraceContext | None" = None, xspace=None):
        self.path = path
        self.queue = stream_mod.BoundedChunkQueue(max_chunks)
        self.result: dict | None = None
        self.span: obs.Span | None = None  # shim.xplane_write, once done
        # `xspace`: the whole serialized XSpace the chunks are views of,
        # where the feeder holds it; the writer then lists its planes
        # ({"name", "bytes"} each, xspace.plane_index) for the manifest.
        self._xspace = xspace
        self.planes: list | None = None
        self.index_span: obs.Span | None = None  # shim.plane_index
        self.error: str | None = None
        self._done = threading.Event()
        # unsupervised by design: one writer per capture, joined (via
        # wait()) by whoever needs the artifact — the trace finisher,
        # the ring, or TraceClient.stop().
        self._thread = threading.Thread(
            target=self._run, args=(on_complete, ctx),
            name="dynolog_tpu_xplane_write", daemon=True)
        self._thread.start()

    def _run(self, on_complete, ctx) -> None:
        from dynolog_tpu import trace as trace_mod

        try:
            # ctx is the capture's request context, handed in because this
            # thread has no ambient one; the span may outlive shim.capture.
            with obs.span("shim.xplane_write", ctx=ctx) as write:
                written = trace_mod.stream_write(self.path, self.queue)
            self.span = write
            self.result = {
                "write_ms": write.dur_us // 1000,
                "write_bytes": written,
            }
            self._index_planes(ctx)
            if on_complete is not None:
                self.result.update(on_complete(self.path) or {})
        except Exception as e:  # noqa: BLE001 - the writer is its own
            # failure domain; the error surfaces through wait() into the
            # capture manifest, never into the feeding thread.
            self.error = f"xplane write failed: {e}"
            self.queue.abandon()
            if on_complete is not None:
                on_complete(None)
        finally:
            self._done.set()

    def _index_planes(self, ctx) -> None:
        """One row a plane of the XSpace just written: its top level only,
        a few fields a plane, so four device planes of megabytes cost what
        one does. An XSpace that does not parse costs the capture nothing
        but the rows."""
        from dynolog_tpu.xspace import plane_index

        xspace, self._xspace = self._xspace, None
        if xspace is None:
            return
        with obs.span("shim.plane_index", ctx=ctx) as index:
            try:
                self.planes = plane_index(xspace)
            except ValueError:
                pass
        self.index_span = index

    def wait(self, timeout_s: float = 120.0) -> dict:
        """Blocks until the write finished; returns its decomposition
        ({"write_ms", "write_bytes"}) or {"write_error": ...}."""
        if not self._done.wait(timeout_s):
            self.queue.abandon()
            return {"write_error":
                    f"xplane write did not finish within {timeout_s:g}s"}
        if self.error is not None:
            return {"write_error": self.error}
        return dict(self.result or {})


def _new_xplane_path(trace_dir: str | None) -> str:
    """<trace_dir>/plugins/profile/<run>/<host>.xplane.pb, its directory
    made: where TensorBoard/XProf and dynolog_tpu.trace look."""
    import socket

    host = socket.gethostname().split(".")[0] or "host"
    run_dir = os.path.join(
        trace_dir or ".", "plugins", "profile", _unique_run_name())
    os.makedirs(run_dir, exist_ok=True)
    return os.path.join(run_dir, f"{host}.xplane.pb")


def _feed_write(xplane_path: str, xspace, chunk_bytes: int, on_complete,
                ctx) -> PendingWrite:
    """Opens the artifact's PendingWrite and feeds it the serialized XSpace
    in zero-copy chunks; returns at the end of the feed, not of the write."""
    pending = PendingWrite(
        xplane_path, on_complete=on_complete, ctx=ctx, xspace=xspace)
    try:
        for chunk in stream_mod.chunk_views(xspace, chunk_bytes):
            if not pending.queue.put(chunk):
                break  # writer died; pending.wait() reports why
        pending.queue.close()
    except BaseException as e:
        pending.queue.fail(e)
        raise
    return pending


def _usage() -> dict:
    """The calling thread's and the process's counters, for the account of
    a library call the shim cannot see into: CPU time in whole
    microseconds (`cpu_us` the thread's, `proc_cpu_us` every thread's;
    both from the CPU clocks, which getrusage's times are coarser than),
    and the thread's voluntary and involuntary context switches and minor
    faults, from one getrusage. Where the kernel refuses RUSAGE_THREAD the
    counts are left out, not zeroed."""
    out = {"cpu_us": time.thread_time_ns() // 1000,
           "proc_cpu_us": time.process_time_ns() // 1000}
    try:
        ru = resource.getrusage(resource.RUSAGE_THREAD)
    except (AttributeError, OSError, ValueError):
        return out
    out.update(nvcsw=ru.ru_nvcsw, nivcsw=ru.ru_nivcsw, minflt=ru.ru_minflt)
    return out


def _account(prefix: str, before: dict, after: dict) -> dict:
    """`<prefix>_<counter>`: what a call added to each counter of
    `_usage()` taken before and after it."""
    return {f"{prefix}_{key}": after[key] - before[key]
            for key in before if key in after}


# The export child's whole program: nice 19 before the package is imported
# (not via preexec_fn, which is fork-deadlock-prone in a process full of
# XLA threads and blocks posix_spawn), then trace.export_child. The
# artifact's path is not in it: it arrives on the child's standard input.
_EXPORT_CHILD_CODE = (
    "import os; os.nice(19); from dynolog_tpu import trace; "
    "raise SystemExit(trace.export_child())")


class _ExportChild:
    """One export child (`trace.export_child`) from the shim's side: an
    interpreter that imports, says `ready <unix seconds>` on its standard
    output and waits on its standard input for the artifact's path. Its
    own session (the job's Ctrl-C is not its), reaped by its own thread
    whenever it goes: wait() parks in waitpid with the GIL released, so
    the converter can't leave a zombie behind."""

    def __init__(self, env: dict):
        import subprocess
        import sys

        self.spawned_us = int(time.time() * 1e6)  # where export.boot opens
        self.proc = subprocess.Popen(
            [sys.executable, "-c", _EXPORT_CHILD_CODE], env=env, bufsize=0,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, start_new_session=True)
        os.set_blocking(self.proc.stdout.fileno(), False)
        self.reaper = threading.Thread(
            target=self.proc.wait, name="dynolog_tpu_trace_export_reaper",
            daemon=True)
        self.reaper.start()

    def ready_at(self) -> float | None:
        """The unix time at which the child said it was ready; None where
        it has not said so yet (or never will)."""
        try:
            word, at = os.read(self.proc.stdout.fileno(), 64).split()
            return float(at) if word == b"ready" else None
        except (OSError, ValueError):
            return None

    def life_spans(self, ctx, ready_at: float, handed_at: float) -> list:
        """The child's life before its conversion, as two spans under `ctx`
        with the child's pid, from the three times the hand-over holds:
        export.boot, from just before the Popen to the `ready` the child
        stamped (fork and exec, the interpreter at nice 19, every import),
        and export.idle, from there to the path going into its pipe."""
        with obs.span("export.boot", ctx=ctx, start_us=self.spawned_us,
                      now=lambda: ready_at) as boot:
            boot.pid = self.proc.pid
        with obs.span("export.idle", ctx=ctx, start_us=boot.end_us,
                      now=lambda: handed_at) as idle:
            idle.pid = self.proc.pid
        return [boot, idle]

    def hand(self, xplane_path: str | None) -> bool:
        """Hands the child the artifact's path (None: nothing is owed, it
        exits 0) and lets go of both pipes. False where the child is not
        there to take it."""
        line = ("" if xplane_path is None else json.dumps(xplane_path)) + "\n"
        try:
            self.proc.stdin.write(line.encode())
            return self.proc.poll() is None
        except (OSError, ValueError):  # died early, or handed already
            return False
        finally:
            self.proc.stdin.close()
            self.proc.stdout.close()


class JaxProfiler:
    """Default profiler backend: jax.profiler XLA trace capture.

    Fast-stop design. `jax.profiler.stop_trace()` collects the XSpace
    from the runtime and then converts it to trace.json.gz inside
    `stop_and_export`, all of it on the capture's critical path (the
    collect alone on the chip: `collect_ms`, PERF.md section 5; the
    conversion, out of process here, is `convert_ms` there).
    This backend drives the underlying ProfilerSession directly: stop()
    collects the raw XSpace and streams the canonical TensorBoard artifact
    (plugins/profile/<run>/<host>.xplane.pb — what TensorBoard/XProf and
    `python -m dynolog_tpu.trace` read) to disk in chunks in milliseconds,
    then produces the same derived trace.json.gz from a deprioritized
    background process (no GIL stolen from the training loop) running the
    streamed converter (trace.ConvertBudget: its one setting, how many
    processes may convert at a time, a capture sets by the
    TRACE_CONVERT_WORKERS config key — see docs/TRACE_PIPELINE.md). That
    process is started as the capture's window opens (`warm_export`), so
    its interpreter and imports are done while the window and the drain
    last, and it is handed the artifact's path as the write completes.
    Artifact parity with jax's own export, minus ~2s of capture latency.

    Falls back to the public start_trace/stop_trace API when the private
    session type is unavailable (a jax refactor must degrade to slow
    captures, never to broken ones).
    """

    # Chunk size for the streamed xplane write: large enough that the
    # write is a handful of syscalls, small enough that the first bytes
    # hit the page cache while later ones are still being produced.
    WRITE_CHUNK_BYTES = 1 << 20

    def __init__(self, export_trace_json: bool = True):
        self.export_trace_json = export_trace_json
        self._default_export = export_trace_json
        self.tracer_levels: dict[str, int] = {}
        # What a capture's TRACE_CONVERT_WORKERS config key adds to the
        # export subprocess's environment (trace.ConvertBudget.from_env).
        self.convert_env: dict[str, str] = {}
        self._sess = None
        self._local_devices: int | None = None
        self._dir: str | None = None
        # The reaper of the newest export child, or the in-process
        # fallback's thread: alive while that conversion is.
        self._export_thread: threading.Thread | None = None
        # The child started at this capture's window, until stop() binds
        # it to the artifact's write or release_export() sends it away;
        # the lock because TraceClient.stop() may release from another
        # thread than the poll thread's.
        self._export_child: _ExportChild | None = None
        self._export_lock = threading.Lock()
        self._pending_write: PendingWrite | None = None
        # The request context of the capture in progress, set and cleared
        # by the shim: the clock marks exist inside a capture only, and
        # the writer thread's span parents to the request. None (ring
        # samples, warmup): no marks, and the ambient context instead.
        self.obs_ctx: obs.TraceContext | None = None

    def configure(self, raw: dict) -> None:
        """Applies per-capture options from the on-demand config text.
        Absent keys revert to the constructor defaults — one capture's
        knobs must not leak into the next."""
        self.tracer_levels = {}
        self.export_trace_json = self._default_export
        self.convert_env = {}
        for key, attr in (
            ("PROFILE_PYTHON_TRACER_LEVEL", "python_tracer_level"),
            ("PROFILE_HOST_TRACER_LEVEL", "host_tracer_level"),
            ("PROFILE_DEVICE_TRACER_LEVEL", "device_tracer_level"),
        ):
            if key in raw:
                try:
                    self.tracer_levels[attr] = int(raw[key])
                except ValueError:
                    pass
        if "TRACE_JSON" in raw:
            self.export_trace_json = raw["TRACE_JSON"].lower() not in (
                "0", "false", "no")
        if "TRACE_CONVERT_WORKERS" in raw:
            self.convert_env["DYNO_TRACE_CONVERT_WORKERS"] = raw[
                "TRACE_CONVERT_WORKERS"]

    def start(self, trace_dir: str) -> None:
        import jax

        self._dir = trace_dir
        # Per-capture: a fallback-path stop() must not inherit the
        # previous capture's collect/write decomposition, nor a failed
        # start the previous start's account.
        self.last_stop_decomposition = None
        self.last_start_account = None
        try:
            from jax._src.lib import _profiler

            session_type = _profiler.ProfilerSession
        except (ImportError, AttributeError):
            # A jax whose private session type moved: the public API
            # still captures, without the collect/write decomposition.
            self._sess = None
            before = _usage()
            jax.profiler.start_trace(trace_dir)
            self.last_start_account = _account(
                "profiler_start", before, _usage())
            self._clock_sync()
            return
        # Backend (and on TPU, libtpu) must be initialized before the
        # tracer is created, as jax.profiler.start_trace itself ensures.
        # A failure from here on is a failed capture and surfaces as one.
        jax.devices()
        self._local_devices = jax.local_device_count()
        opts = jax.profiler.ProfileOptions()
        for attr, value in self.tracer_levels.items():
            setattr(opts, attr, value)
        # The session's opening is the library's: the account around it
        # (CPU against the wall of shim.profiler_start, which the caller
        # holds) says whether it computes, waits, or works elsewhere.
        before = _usage()
        self._sess = session_type(opts)
        self.last_start_account = _account("profiler_start", before, _usage())
        self._clock_sync()

    def _clock_sync(self) -> None:
        """Inside a capture, one host event that carries the wall clock
        into the open session (stat `unix_ns`), once after the session
        opens and once before it stops: a reader lays the trace on unix
        time, the clock of the obs spans, and holds its mapping against
        these two marks (docs/OBSERVABILITY.md). Needs the host tracer;
        under PROFILE_HOST_TRACER_LEVEL=0 the trace holds none."""
        import jax

        if self.obs_ctx is None:
            return
        with jax.profiler.TraceAnnotation(
                "dynolog.clock_sync", unix_ns=time.time_ns()):
            pass

    def stop(self) -> None:
        import jax

        self._clock_sync()
        # The export child parents to THIS thread's ambient span
        # (shim.capture), the writer thread's span to the request.
        export_ctx = obs.current()
        write_ctx = self.obs_ctx or export_ctx
        if self._sess is None:
            with obs.span("shim.collect") as collect:
                before = _usage()
                jax.profiler.stop_trace()
                account = _account("collect", before, _usage())
            self.last_stop_decomposition = {**account, "spans": [collect]}
            return
        sess, self._sess = self._sess, None
        with obs.span("shim.collect") as collect:
            before = _usage()
            xspace = sess.stop()
            account = _account("collect", before, _usage())
        with obs.span("shim.feed") as feed:
            self._feed(xspace, export_ctx, write_ctx)
        # Decomposition for the capture manifest, each duration from the
        # span that measured it (`spans`, which the shim lists in the
        # manifest): collection is the runtime's trace drain; feed is this
        # thread's hand-off into the queue (backpressure-bounded);
        # write_ms arrives from the writer via the finisher's
        # pending.wait().
        self.last_stop_decomposition = {
            "collect_ms": collect.dur_us // 1000,
            **account,
            "feed_ms": feed.dur_us // 1000,
            "xspace_bytes": len(xspace),
            "local_devices": self._local_devices,
            "spans": [collect, feed],
        }

    def _feed(self, xspace, export_ctx, write_ctx) -> None:
        xplane_path = _new_xplane_path(self._dir)
        # Streaming pipeline hand-off: this (collect) thread feeds the
        # bounded chunk queue of a PendingWrite; its writer thread drains
        # the chunks through trace.stream_write (atomic tmp + rename)
        # concurrently and then spawns the export child. stop() returns
        # at the end of the FEED, not of the write — the poll loop is
        # back to serving configs while the artifact streams to disk,
        # and whoever needs the file (the trace finisher, the ring)
        # waits on take_pending_write(). Chunks are memoryview slices —
        # zero-copy; ProfilerSession.stop() hands us one buffer today,
        # but a future incremental drain feeds the same queue.
        on_complete = None
        if self.export_trace_json:
            child = self._take_export_child()
            on_complete = lambda path: self._spawn_export(  # noqa: E731
                path, export_ctx, child)
        self._pending_write = _feed_write(
            xplane_path, xspace, self.WRITE_CHUNK_BYTES, on_complete,
            write_ctx)

    def take_pending_write(self) -> "PendingWrite | None":
        """Hands the caller the in-flight artifact write of the capture
        that just stopped (None when the fallback public-API path ran —
        jax wrote the artifact itself). Ownership transfers: the caller
        must wait() before reading the trace dir or declaring the
        capture complete."""
        pending, self._pending_write = self._pending_write, None
        return pending

    def warm_export(self, ctx=None) -> None:
        """Called by the shim as a capture's window opens: where this
        capture will owe derived files (export on, and the session is ours:
        the public-API fallback writes its own), starts the export child
        NOW, so its interpreter and imports lie under the window and the
        drain and not after the artifact. `ctx` is what the child's
        trace.convert span parents to. A spawn that fails leaves nothing
        here, and the hand-over starts the child then (`_spawn_export`)."""
        if self.export_trace_json and self._sess is not None:
            child = self._start_export_child(ctx)
            with self._export_lock:
                self._export_child = child

    def _take_export_child(self) -> "_ExportChild | None":
        with self._export_lock:
            child, self._export_child = self._export_child, None
        return child

    def release_export(self) -> None:
        """Every way out of a capture that binds no artifact to the child
        started at its window (stop() raised, the capture was abandoned,
        the shim stops mid-window) ends here: the child is told that
        nothing is owed and exits 0, no file written, no span flushed."""
        child = self._take_export_child()
        if child is not None:
            child.hand(None)

    def _start_export_child(self, ctx=None) -> "_ExportChild | None":
        """One export child, everything but the artifact's path in its
        environment; None where no interpreter can be spawned (or the
        shim.export_spawn drill says so)."""
        import dynolog_tpu

        pkg_parent = os.path.dirname(
            os.path.dirname(os.path.abspath(dynolog_tpu.__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = pkg_parent + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        # Per-capture converter setting (TRACE_CONVERT_WORKERS config
        # key): the child's ConvertBudget.from_env picks it up.
        env.update(self.convert_env)
        # Self-tracing hand-off: the capture's span context (handed in,
        # since the hand-over runs on the writer thread, whose ambient
        # context is empty) and the daemon endpoint, so the child's
        # trace.convert span lands under the SAME request trace-id and is
        # flushed back to the daemon on exit
        # (write_derived_artifacts -> obs.maybe_flush_env).
        ctx = ctx if ctx is not None else obs.current()
        if ctx is not None:
            env[obs.ENV_TRACE_CTX] = ctx.header()
        endpoint = getattr(self, "obs_endpoint", "")
        if endpoint:
            env[obs.ENV_FLUSH_ENDPOINT] = endpoint
        try:
            if failpoints.fire("shim.export_spawn"):
                raise OSError("failpoint shim.export_spawn")
            child = _ExportChild(env)
        except OSError:
            return None
        self._export_thread = child.reaper
        return child

    def _spawn_export(self, xplane_path: str | None, ctx=None,
                      child: "_ExportChild | None" = None) -> dict:
        """The hand-over, on the writer thread as the artifact's write
        completes: the conversion (summary and Chrome trace, one decode of
        each plane: trace.write_derived_artifacts) runs OUT of process,
        because it is tenths of a second of pure-Python work (`convert_ms`,
        PERF.md section 5) and an in-process thread would steal the GIL
        from the training loop (and from the next capture's stop) for its
        whole run. `child` is the one started at this capture's window: it
        is handed the path ("warm" where it had said it was ready: `spans`
        are then its boot and its wait, `_ExportChild.life_spans`, and
        `export_ready_ms` the wait's length). Where none is alive to take it
        (none was started, its spawn failed, it died early) the same child
        is started now and handed the path at once ("cold"); where no
        interpreter can be spawned, an in-process thread ("thread"). A
        path of None (the write failed) sends the child away. Returns what
        the manifest says of it."""
        if xplane_path is None:
            if child is not None:
                child.hand(None)
            return {}
        if child is not None:
            ready_at, handed_at = child.ready_at(), time.time()
            if child.hand(xplane_path):
                if ready_at is None:
                    return {"export_child": "cold"}
                boot, idle = child.life_spans(ctx, ready_at, handed_at)
                return {"export_child": "warm",
                        "export_ready_ms": idle.dur_us // 1000,
                        "spans": [boot, idle]}
        child = self._start_export_child(ctx)
        if child is not None and child.hand(xplane_path):
            return {"export_child": "cold"}
        self._export_thread = threading.Thread(
            target=self._export_json,
            args=(xplane_path,),
            name="dynolog_tpu_trace_export",
            daemon=True,
        )
        self._export_thread.start()
        return {"export_child": "thread"}

    @staticmethod
    def _export_json(xplane_path: str) -> None:
        try:
            from dynolog_tpu import trace as trace_mod

            # In-process thread fallback: the serial converter whatever
            # the setting says. A process pool forks, and forking from a
            # thread of a process full of XLA runtime threads is
            # deadlock-prone (the same reason _spawn_export avoids
            # preexec_fn).
            trace_mod.write_derived_artifacts(
                xplane_path, trace_mod.ConvertBudget(max_workers=1))
        except Exception:  # noqa: BLE001 - derived artifacts only; the
            # xplane.pb (the canonical trace) is already on disk.
            pass


class RecordingProfiler:
    """Test backend: records calls instead of tracing. Given an `xspace`
    (serialized bytes), stop() hands it to the artifact pipeline as
    JaxProfiler hands the runtime's: a capture of a session over
    `local_devices` devices without one."""

    def __init__(self, xspace: bytes | None = None, local_devices: int = 1):
        self.calls: list[tuple[str, str | None]] = []
        self.xspace, self.local_devices = xspace, local_devices
        self.obs_ctx: obs.TraceContext | None = None
        self._dir: str | None = None
        self._pending_write: PendingWrite | None = None

    def start(self, trace_dir: str) -> None:
        self.calls.append(("start", trace_dir))
        self._dir = trace_dir

    def stop(self) -> None:
        self.calls.append(("stop", None))
        self.last_stop_decomposition = None
        if self.xspace is None:
            return
        with obs.span("shim.feed") as feed:
            self._pending_write = _feed_write(
                _new_xplane_path(self._dir), self.xspace,
                JaxProfiler.WRITE_CHUNK_BYTES, None,
                self.obs_ctx or obs.current())
        self.last_stop_decomposition = {
            "xspace_bytes": len(self.xspace),
            "local_devices": self.local_devices,
            "spans": [feed],
        }

    def take_pending_write(self) -> "PendingWrite | None":
        pending, self._pending_write = self._pending_write, None
        return pending


# How many of the job's last steps TraceClient keeps as marks: a 500 ms
# window with its drain lies over a dozen steps of a 100 ms job; a bounded
# ring keeps step() free of allocation growth.
STEP_MARKS = 64


@dataclass
class _Capture:
    """One capture's own state, from the config in hand to the manifest."""

    cfg: TraceConfig
    pid: int
    trace_dir: str
    ctx: obs.TraceContext
    timing: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)  # obs.Span, for the manifest
    started_ms: int = 0
    error: str | None = None
    local_devices: int | None = None  # devices the profiler session covered
    window_end_us: int | None = None  # end of shim.window: the stop begins
    stopped_us: int | None = None  # end of shim.capture: shim.finish begins


def job_cost(marks: list, spans: list) -> tuple[list, dict]:
    """What a capture cost the job, from the job's own step marks.

    `marks` are (end_us, dur_us) of the last steps, oldest first, on the
    clock of `spans` (the manifest's span rows). Returns the marks that
    overlap the capture — shim.config_fetch's start to shim.feed's end
    (shim.capture's where the backend recorded no feed) — preceded by the
    one before them for comparison, and `job_cost_ms`: `baseline_ms` is the
    median duration over all marks; `total` sums, over the overlapping
    steps, what each took beyond the baseline (never negative); `start`
    and `collect` are that sum over the steps that overlap
    shim.profiler_start, and shim.collect to shim.feed. A step that had
    not ended when the manifest was written is not in `marks` and is not
    waited for."""
    at = {s["name"]: (s["start_us"], s["start_us"] + s["dur_us"])
          for s in spans}
    last = at.get("shim.feed") or at.get("shim.capture")
    first = at.get("shim.config_fetch") or at.get("shim.capture")
    baseline = statistics.median(d for _, d in marks) if marks else 0

    def over(lo_hi) -> list:
        if lo_hi is None:
            return []
        lo, hi = lo_hi
        return [i for i, (end, dur) in enumerate(marks)
                if end > lo and end - dur < hi]

    def excess_ms(indices) -> float:
        return round(
            sum(max(marks[i][1] - baseline, 0) for i in indices) / 1e3, 3)

    whole = over((first[0], last[1])) if first and last else []
    collect = at.get("shim.collect")
    cost = {
        "baseline_ms": round(baseline / 1e3, 3),
        "total": excess_ms(whole),
        "start": excess_ms(over(at.get("shim.profiler_start"))),
        "collect": excess_ms(over(collect and (collect[0], last[1]))),
    }
    steps = marks[max(whole[0] - 1, 0):whole[-1] + 1] if whole else []
    return steps, cost


class TraceClient:
    """Registers with dynologd and serves on-demand trace requests."""

    # The wall clock of the step marks and of the shim's own spans, one
    # name so that a test drives both synthetically.
    _wall = staticmethod(time.time)

    def __init__(
        self,
        job_id: int = 0,
        device: int = 0,
        endpoint: str = ipc.DAEMON_ENDPOINT,
        poll_interval_s: float = 1.0,
        profiler=None,
        step_start_timeout_s: float = 60.0,
        step_trace_timeout_s: float = 600.0,
        warmup_profiler: bool = False,
        report_interval_s: float = 10.0,
        stall_grace_s: float = 60.0,
        sweep_ttl_s: float = DEFAULT_SWEEP_TTL_S,
        ring: RingConfig | None = None,
    ):
        self.job_id = job_id
        self.device = device
        self.endpoint = endpoint
        self.poll_interval_s = poll_interval_s
        # Iteration-mode guards: how long to wait for the app to reach the
        # trace-start step, and for the requested iterations to elapse. A
        # timeout aborts the capture loudly (failed manifest + last_error)
        # instead of silently tracing the wrong window.
        self.step_start_timeout_s = step_start_timeout_s
        self.step_trace_timeout_s = step_trace_timeout_s
        # warmup_profiler: pay jax.profiler's one-time initialization (it
        # can cost seconds on some backends) with a throwaway start/stop on
        # the poll thread at startup, so the FIRST real on-demand capture
        # is as fast as later ones.
        self.warmup_profiler = warmup_profiler
        self.profiler = profiler if profiler is not None else JaxProfiler()
        # Pipelined capture finishers (manifest after the async xplane
        # write): every LIVE one is joined by stop() so shutdown never
        # strands a capture mid-finalize — back-to-back captures can have
        # more than one in flight.
        self._finishers: list[threading.Thread] = []
        self._client = ipc.IpcClient()
        self._ancestry = ipc.pid_ancestry()
        self._last_subscribe = 0.0
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._step_count = 0
        self._step_cv = threading.Condition()
        # Step telemetry ("pstat" reports): durations between step() calls,
        # drained every report_interval_s by the poll thread and sent to the
        # daemon as job-level rate/latency series. <= 0 disables.
        self.report_interval_s = report_interval_s
        self._step_durations: list[float] = []
        self._last_step_t: float | None = None
        # The job's last STEP_MARKS steps as (end, duration) on the wall
        # clock, the clock of the obs spans: a capture's manifest lists
        # the ones it lay over, and what it cost them (job_cost).
        self._step_marks: collections.deque = collections.deque(
            maxlen=STEP_MARKS)
        self._last_step_wall = 0.0
        self._ever_stepped = False
        self._last_report_t = time.monotonic()
        # Rate comes from the step-count delta per report, NOT from the
        # recorded inter-step durations: a job whose step period exceeds
        # the report interval still has an exact rate (steps/elapsed) even
        # when no duration ever fits inside one window.
        self._reported_steps = 0
        self._recent_step_s = 0.0  # most recent inter-step duration
        # Idle span after which a job with NO measured step time yet is
        # declared stalled (matches the reference's 60s client-GC
        # posture, LibkinetoConfigManager.cpp:24). Once a step time is
        # known the threshold scales with it instead; raise this for jobs
        # whose very first step exceeds a minute.
        self.stall_grace_s = stall_grace_s
        # Startup stale-artifact sweep TTL (see sweep_stale_artifacts):
        # *.tmp files and dead-pid trace-session dirs older than this are
        # reclaimed when the shim starts and whenever a capture targets a
        # directory. <= 0 disables.
        self.sweep_ttl_s = sweep_ttl_s
        self._swept_dirs: set[str] = set()
        # Continuous capture ring (CaptureRing): explicit config wins,
        # else the DYNO_TPU_RING_* env opts a job in with no code change.
        # every_n_steps <= 0 leaves the ring off entirely.
        ring_cfg = ring if ring is not None else RingConfig.from_env()
        self.ring = (
            CaptureRing(ring_cfg) if ring_cfg.every_n_steps > 0 else None)
        self.instance_rank: int | None = None
        self.traces_completed = 0
        self.last_error: str | None = None
        # Daemon-restart ride-through: after _absent_threshold
        # consecutive no-reply polls the daemon is considered absent —
        # polls back off exponentially (up to reconnect_backoff_max_s)
        # and use a short send-retry ladder, and the FIRST reply after an
        # absence re-announces this pid (register_context) and
        # re-subscribes kicks immediately, because a restarted daemon's
        # soft registration state is gone. daemon_reconnects counts the
        # ride-throughs (tests and operators read it).
        self.reconnect_backoff_max_s = 30.0
        self.daemon_reconnects = 0
        self._absent_polls = 0
        self._absent_threshold = 2
        self._need_reannounce = False
        # Set once the (optional) profiler warmup has finished; apps that
        # want the first capture at steady-state latency can wait on it.
        self.warmup_done = threading.Event()

    # -- lifecycle -------------------------------------------------------

    def start(self) -> bool:
        """Registers and spawns the polling thread. False if the daemon is
        unreachable (the app keeps running untraced — soft-fail like
        libkineto without a daemon)."""
        # Startup sweep: reclaim what a SIGKILL'd predecessor (its export
        # child included) left behind before this run adds its own
        # artifacts. Never fatal — registration must proceed regardless.
        try:
            _sweep_warmup_dirs(self.sweep_ttl_s)
            if self.ring:
                self.ring.sweep()
        except Exception as e:  # noqa: BLE001 - sweep must never kill start()
            _log.warning("startup artifact sweep failed: %s", e)
        self.instance_rank = self._client.register_context(
            self.job_id, self.device, dest=self.endpoint
        )
        # One synchronous poll so the process is in the daemon's trace
        # registry before start() returns — otherwise a trace triggered
        # immediately after startup can miss this process.
        if self.instance_rank is not None:
            self._client.request_config(
                self.job_id,
                self._ancestry,
                ipc.CONFIG_TYPE_ACTIVITIES,
                dest=self.endpoint,
            )
            # Opt in to config kicks: the daemon wakes this shim the
            # moment a capture is triggered, so pickup latency is the
            # daemon IPC thread's wake-up instead of ~poll_interval/2.
            # Fire-and-forget; polling remains the delivery mechanism.
            self._client.subscribe_kicks(self.job_id, dest=self.endpoint)
            self._last_subscribe = time.monotonic()
        self._thread = threading.Thread(
            target=self._poll_loop, name="dynolog_tpu_shim", daemon=True
        )
        self._thread.start()
        return self.instance_rank is not None

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)
        for finisher in self._finishers:
            # Every in-flight pipelined finish (xplane write + manifest)
            # completes before the IPC client goes away: no capture's
            # manifest or span flush may be stranded by shutdown.
            finisher.join(timeout=30)
        self._finishers = []
        # A window that outlasts the join above still holds its export
        # child waiting for a path: no child of ours waits past stop().
        self._release_export()
        self._client.close()

    def __enter__(self) -> "TraceClient":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def step(self) -> None:
        """Call once per training iteration to enable iteration-based traces
        and step-rate/latency telemetry."""
        now = time.monotonic()
        wall = self._wall()
        with self._step_cv:
            self._step_count += 1
            if self._last_step_t is not None:
                self._step_durations.append(now - self._last_step_t)
                self._recent_step_s = now - self._last_step_t
                self._step_marks.append((wall, wall - self._last_step_wall))
            else:
                # Epoch-opening step (first ever, or first after an idle
                # reset): it marks the measurement origin — align the
                # report window to it and exclude it from the next
                # report's count, so the reported rate is exactly
                # (subsequent steps / elapsed since this step) with no
                # pre-training or pause idle diluting it.
                self._last_report_t = now
                self._reported_steps = self._step_count
            self._ever_stepped = True
            self._last_step_t = now
            self._last_step_wall = wall
            self._step_cv.notify_all()
            count = self._step_count
        if self.ring:
            # Outside the cv (trivial counter arithmetic): arms the poll
            # thread to take a ring sample at its next tick.
            self.ring.note_step(count)

    # -- internals -------------------------------------------------------

    def _warm_profiler(self) -> None:
        tmp = tempfile.mkdtemp(prefix="dynolog_tpu_warmup_")
        try:
            # One parent span, as shim.ring_capture is for a ring sample:
            # the backend's stop() spans hang under it.
            with obs.span("shim.warmup"):
                self.profiler.start(tmp)
                self.profiler.stop()
                # Drain the streaming stop's in-flight write before the
                # rmtree below pulls the directory out from under it.
                take = getattr(self.profiler, "take_pending_write", None)
                pending = take() if take is not None else None
                if pending is not None:
                    pending.wait(30.0)
        except Exception as e:  # noqa: BLE001 - warmup must never kill polling
            self.last_error = f"profiler warmup failed: {e}"
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    def _poll_loop(self) -> None:
        if self.warmup_profiler:
            self._warm_profiler()
        self.warmup_done.set()
        while not self._stop.is_set():
            # The kick or the poll timer woke _wait_for_tick just now: a
            # config fetched by this poll was on its way from here.
            polled_us = int(self._wall() * 1e6)
            try:
                text = self._client.request_config(
                    self.job_id,
                    self._ancestry,
                    ipc.CONFIG_TYPE_ACTIVITIES,
                    dest=self.endpoint,
                    # Short ladders: absence is ridden out by the backoff
                    # in _wait_for_tick, not by camping inside one send.
                    retries=2 if self._absent_polls else 4,
                )
            except OSError as e:  # daemon went away; keep trying
                self.last_error = str(e)
                text = None
            if text is None:
                # No reply at all: the daemon may be restarting
                # (preemption, upgrade, crash). Note the absence — the
                # tick wait below backs off while it lasts.
                self._absent_polls += 1
                if self._absent_polls == self._absent_threshold:
                    _log.warning(
                        "dynolog daemon unreachable; polling with backoff "
                        "(up to %.0fs) until it returns",
                        self.reconnect_backoff_max_s)
            else:
                # Any reply (even "no config") is daemon liveness. Even a
                # ONE-poll absence can have been a restart that wiped the
                # daemon's soft registration state, so re-announce on any
                # observed absence — register_context is idempotent and
                # two datagrams are cheap against a missed capture. A
                # re-announce whose own exchange fails (the restarted
                # daemon may still be rebinding its socket) stays pending
                # and is retried on every later reply until it lands.
                if self._absent_polls:
                    self._need_reannounce = True
                self._absent_polls = 0
                if self._need_reannounce and self._reannounce():
                    self._need_reannounce = False
            if not text:
                # A reply that arrived after its request timed out (and
                # was stashed rather than dropped — the daemon already
                # cleared that config server-side) still gets captured.
                text = self._client.take_late_config()
            if text:
                try:
                    self._run_trace(TraceConfig.parse(text), polled_us)
                except Exception as e:  # noqa: BLE001 - never kill the app
                    self.last_error = f"trace failed: {e}"
            try:
                self._maybe_report_stats()
            except Exception as e:  # noqa: BLE001 - telemetry must never
                # kill the poll thread (on-demand tracing depends on it)
                self.last_error = f"stats report failed: {e}"
            if self.ring and self.ring.due() and not text:
                # Ring sample on an idle tick only: an on-demand capture
                # that just ran owns this window, and the sampled profile
                # would double-count it. CaptureRing.capture contains its
                # own failures (last_error on the ring).
                self.ring.capture(self.profiler)
                if self.ring.last_error:
                    self.last_error = self.ring.last_error
            # Kick-subscription keep-alive (the daemon expires stale
            # entries; re-sending also re-arms after a daemon restart,
            # whose soft state the poll above re-registers into).
            if time.monotonic() - self._last_subscribe > 30.0:
                self._client.subscribe_kicks(self.job_id, dest=self.endpoint)
                self._last_subscribe = time.monotonic()
            self._wait_for_tick()

    def _wait_for_tick(self) -> None:
        """Sleep until the next poll — or NOW, if the daemon kicks.

        Waits on the client's DEDICATED kick socket, so the inter-poll
        sleep is wakeup-capable: a "kick" datagram (config just installed
        for this job) triggers an immediate poll and on-demand pickup
        costs the daemon IPC thread's wake-up instead of ~poll_interval/2.
        The request/reply socket is never read here — an earlier design
        that select()ed on the shared socket stole "req" replies from
        any concurrent exchange, and the requester then spun out its
        timeout. Sliced at 200ms to keep stop() prompt.
        """
        interval = self.poll_interval_s
        if self._absent_polls >= self._absent_threshold:
            # Absent daemon: exponential poll backoff, capped. The kick
            # socket still cuts the wait short the moment a restarted
            # daemon installs a config after this shim re-subscribes.
            # The exponent is capped: a day-long outage would otherwise
            # grow 2**k past float range and the OverflowError would kill
            # the poll thread — the one thing that must survive to notice
            # the daemon coming back.
            interval = min(
                self.poll_interval_s *
                (2 ** min(self._absent_polls - self._absent_threshold + 1,
                          20)),
                self.reconnect_backoff_max_s)
        deadline = time.monotonic() + interval
        while not self._stop.is_set():
            left = deadline - time.monotonic()
            if left <= 0:
                return
            if self._client.wait_for_kick(min(left, 0.2)):
                return

    def _reannounce(self) -> bool:
        """The daemon answered again after an absence (restart,
        preemption resize): its registration/subscription soft state died
        with the old incarnation, so re-announce this pid and
        re-subscribe kicks NOW instead of waiting out the 30s keep-alive
        — a capture triggered right after the restart must find this
        process in the trace registry. Returns True only once the daemon
        CONFIRMED the registration; a silent or failed exchange leaves
        the re-announce pending (the caller retries on the next reply),
        because believing an unconfirmed registration means the next
        capture silently skips this process."""
        try:
            rank = self._client.register_context(
                self.job_id, self.device, dest=self.endpoint)
            if rank is None:
                self.last_error = "re-announce: no reply to register_context"
                return False
            self.instance_rank = rank
            self._client.subscribe_kicks(self.job_id, dest=self.endpoint)
            self._last_subscribe = time.monotonic()
        except OSError as e:
            self.last_error = str(e)
            return False
        self.daemon_reconnects += 1
        _log.info(
            "dynolog daemon is back (ride-through #%d); pid re-announced",
            self.daemon_reconnects)
        return True

    def _maybe_report_stats(self) -> None:
        if self.report_interval_s <= 0:
            return
        with self._step_cv:
            never_stepped = not self._ever_stepped
        if never_stepped:
            # step() is optional; an app that never calls it publishes no
            # telemetry at all (a permanent zero-rate series would misfire
            # steps_per_sec auto-triggers).
            return
        now = time.monotonic()
        window_s = now - self._last_report_t
        if window_s < self.report_interval_s:
            return
        with self._step_cv:
            durations = self._step_durations
            self._step_durations = []
            steps = self._step_count - self._reported_steps
            if steps == 0:
                # Empty window. A job whose step period exceeds the report
                # interval (10-60s TPU training steps vs the 10s default)
                # hits this on most ticks while perfectly healthy, so an
                # empty window alone is NOT a stall: hold the report (and
                # the stepping epoch) open until the idle span dwarfs both
                # the report interval and the recently observed step time.
                # An already-closed epoch (_last_step_t is None) keeps
                # reporting zero every window — a stalled job is exactly
                # what a step-rate auto-trigger wants to see continuously.
                # While no step time has been measured (epoch opener only,
                # e.g. a cold start with multi-minute steps), fall back to
                # the stall grace instead of 2x the report interval: a 30s
                # first step with the default 10s interval must not be
                # declared stalled at t+20s — that would consume every
                # real step as a fresh epoch opener and report a healthy
                # job as steps_per_sec=0 forever.
                threshold = max(
                    2 * self.report_interval_s,
                    4 * self._recent_step_s
                    if self._recent_step_s > 0
                    else self.stall_grace_s,
                )
                stalled = (
                    self._last_step_t is None
                    or now - self._last_step_t > threshold
                )
                if not stalled:
                    return
                # Genuinely stalled: close the stepping epoch so the first
                # step after a long pause (eval, checkpointing) opens a
                # fresh window instead of recording the whole pause as one
                # giant step duration that would spuriously fire p95/max
                # rules — and report the zero rate (a stalled job is
                # exactly what a step-rate auto-trigger wants to see).
                # The measured step time dies with the epoch: a job that
                # resumes 10x slower after a pause must re-qualify under
                # the stall grace, not under a stale 4x-old-step threshold
                # (which would re-declare a stall before its first slow
                # step completes, forever).
                self._last_step_t = None
                self._recent_step_s = 0.0
            self._reported_steps = self._step_count
        self._last_report_t = now
        if steps == 0:
            self._client.send_perf_stats(
                self.job_id, window_s, 0, dest=self.endpoint
            )
            return
        kwargs: dict = {}
        if durations:
            durations.sort()

            def pctl(p: float) -> float:
                # Nearest-rank, like the daemon's MetricStore stats.
                k = max(math.ceil(p * len(durations)), 1)
                return durations[min(k - 1, len(durations) - 1)]

            kwargs = dict(
                p50_ms=pctl(0.50) * 1000.0,
                p95_ms=pctl(0.95) * 1000.0,
                max_ms=durations[-1] * 1000.0,
            )
        # window_s spans the whole elapsed time since the epoch-opening
        # step (possibly several report intervals for slow-step jobs), so
        # steps/window_s is the exact rate; zero percentile fields mean
        # "not measured" and are skipped by the daemon.
        self._client.send_perf_stats(
            self.job_id, window_s, steps, dest=self.endpoint, **kwargs
        )

    def _wait_for_start(self, cfg: TraceConfig) -> None:
        if cfg.start_time_ms > 0:
            delay = cfg.start_time_ms / 1000.0 - time.time()
            if delay > 0:
                # Synchronized start across hosts (unitrace's
                # --profile-start-time trick, unitrace.py:144-148).
                time.sleep(delay)

    def _run_trace(self, cfg: TraceConfig, polled_us: int | None = None) -> None:
        """One on-demand capture. `polled_us` is when the poll that fetched
        `cfg` set out (None: the config was handed over directly)."""
        # Fault drill: shim.run_trace=throw proves the poll loop contains
        # a capture-path crash (last_error set, polling continues).
        failpoints.fire("shim.run_trace")
        # Control-plane identity for this capture: the TRACE_CONTEXT the
        # daemon (or unitrace) put in the config, minted locally when
        # absent (auto-trigger fires, pre-tracing CLIs). Every span this
        # capture records — and the export child's trace.convert span —
        # shares it, so `dyno selftrace --trace_id=...` reconstructs the
        # request across both languages.
        ctx = obs.TraceContext.parse(cfg.trace_ctx) or obs.TraceContext.mint()
        try:
            self._capture(cfg, ctx, polled_us)
        finally:
            # A capture that failed before the profiler's stop() must not
            # leave its context for the next ring sample to parent to,
            # nor the export child of its window waiting for an artifact.
            self.profiler.obs_ctx = None
            self._release_export()

    def _release_export(self) -> None:
        release = getattr(self.profiler, "release_export", None)
        if release is not None:
            release()

    def _capture(self, cfg: TraceConfig, ctx: obs.TraceContext,
                 polled_us: int | None) -> None:
        # The fetch is known for a capture's only now, with the context in
        # hand, so its span opens in the past.
        with obs.span("shim.config_fetch", ctx=ctx, now=self._wall,
                      start_us=polled_us) as fetch:
            pass
        pid = os.getpid()
        cap = _Capture(
            cfg=cfg, pid=pid, trace_dir=cfg.trace_dir(pid), ctx=ctx,
            spans=[fetch])
        os.makedirs(cap.trace_dir, exist_ok=True)
        if hasattr(self.profiler, "configure"):
            # Per-capture knobs from the config text (tracer levels,
            # TRACE_JSON) — unknown keys are ignored, so an old shim and a
            # new CLI stay compatible in both directions.
            self.profiler.configure(cfg.raw)
        # The export child flushes its spans back to THIS daemon; the
        # writer thread's span parents to this request.
        self.profiler.obs_endpoint = self.endpoint
        self.profiler.obs_ctx = ctx
        # Timing decomposition for the manifest: where capture latency goes,
        # in whole milliseconds. received_ms is a mark (config in hand,
        # profiler configured); every duration is taken from the span that
        # measured it (profiler start/stop is jax.profiler's own cost —
        # seconds on some backends).
        cap.timing["received_ms"] = int(self._wall() * 1000)
        self._wait_for_start(cfg)

        cap.started_ms = int(self._wall() * 1000)
        # The capture span closes BEFORE _finish_trace runs, so the
        # manifest-write flush ships it to the daemon with this capture,
        # not the next one.
        with obs.span("shim.capture", ctx=ctx, now=self._wall) as capture:
            cap.error = self._capture_window(cap)
        cap.spans.append(capture)
        cap.stopped_us = capture.end_us
        if cap.window_end_us is not None:
            # After the window the capture span holds the profiler's
            # stop() and nothing else.
            cap.timing["profiler_stop_ms"] = (
                capture.end_us - cap.window_end_us) // 1000
        # Streaming pipeline: a profiler with an in-flight artifact write
        # (JaxProfiler's PendingWrite) hands the capture to a finisher
        # thread — the poll loop returns to serving configs immediately,
        # so back-to-back captures overlap one capture's write/manifest
        # with the next one's window. `cap` is this capture's own state:
        # the NEXT capture may start before the finisher runs.
        take = getattr(self.profiler, "take_pending_write", None)
        pending = take() if take is not None else None
        if pending is None:
            self._finish_trace(cap)
            return
        finisher = threading.Thread(
            target=self._finish_pipelined, args=(cap, pending),
            name="dynolog_tpu_trace_finish", daemon=True)
        finisher.start()
        self._finishers = [
            t for t in self._finishers if t.is_alive()] + [finisher]

    def _finish_pipelined(self, cap: "_Capture", pending) -> None:
        """Finisher-thread tail of one capture. A write failure fails the
        capture loudly (status error in the manifest) — stream_write's tmp
        discipline already guaranteed no torn artifact was left behind."""
        try:
            self._finish_trace(cap, pending)
        except Exception as e:  # noqa: BLE001 - the finisher must never
            # die silently: the manifest is the completion signal.
            self.last_error = f"trace finalize failed: {e}"

    def _capture_window(self, cap: "_Capture") -> str | None:
        """The profiler start/wait/stop body of one capture; returns the
        error string (None = clean capture)."""
        cfg = cap.cfg
        if cfg.iterations > 0:
            with self._step_cv:
                base = self._step_count
                roundup = max(cfg.iteration_roundup, 1)
                # Next roundup boundary STRICTLY after the current step: the
                # capture window always begins at a future iteration, so an
                # app that has stopped stepping trips the start timeout
                # instead of capturing an empty (or wrong) window.
                start_at = ((base // roundup) + 1) * roundup
                end_at = start_at + cfg.iterations
                reached = self._step_cv.wait_for(
                    lambda: self._step_count >= start_at,
                    timeout=self.step_start_timeout_s,
                )
            if not reached:
                # App stopped stepping before the capture window: abort
                # without starting the profiler — a trace of some other
                # window is worse than no trace.
                return (
                    f"iteration trace aborted: app did not reach step "
                    f"{start_at} within {self.step_start_timeout_s:g}s "
                    f"(at {self._step_count})"
                )
            self._profiler_start(cap)
            export_ctx = obs.current()
            with obs.span("shim.window", now=self._wall) as window:
                self._warm_export(export_ctx)
                with self._step_cv:
                    elapsed = self._step_cv.wait_for(
                        lambda: self._step_count >= end_at,
                        timeout=self.step_trace_timeout_s,
                    )
            self._profiler_stop(cap, window)
            if not elapsed:
                return (
                    f"iteration trace timed out: {cfg.iterations} steps did "
                    f"not elapse within {self.step_trace_timeout_s:g}s "
                    f"(at {self._step_count}, wanted {end_at})"
                )
            return None
        self._profiler_start(cap)
        export_ctx = obs.current()
        with obs.span("shim.window", now=self._wall) as window:
            # The window is the operator's: its end is fixed before the
            # export child's spawn, which this thread would sleep through.
            deadline = time.monotonic() + cfg.duration_ms / 1000.0
            self._warm_export(export_ctx)
            time.sleep(max(0.0, deadline - time.monotonic()))
        self._profiler_stop(cap, window)
        return None

    def _warm_export(self, ctx: obs.TraceContext | None) -> None:
        """The window has opened and this thread is about to wait it out:
        the backend starts the export child of this capture now, under the
        ambient shim.capture span `ctx` (JaxProfiler.warm_export). Never
        the capture's to pay for: a failure here is a cold start later."""
        warm = getattr(self.profiler, "warm_export", None)
        if warm is None:
            return
        try:
            warm(ctx)
        except Exception as e:  # noqa: BLE001 - derived files only
            self.last_error = f"export child warm start failed: {e}"

    def _profiler_start(self, cap: "_Capture") -> None:
        with obs.span("shim.profiler_start", now=self._wall) as start:
            self.profiler.start(cap.trace_dir)
        cap.spans.append(start)
        cap.timing["profiler_start_ms"] = start.dur_us // 1000
        # the backend's account of the library call inside that span
        cap.timing.update(
            getattr(self.profiler, "last_start_account", None) or {})

    def _profiler_stop(self, cap: "_Capture", window: obs.Span) -> None:
        cap.spans.append(window)
        cap.window_end_us = window.end_us
        self.profiler.stop()
        # The backend's own spans (shim.collect, shim.feed), and their
        # lengths as whole milliseconds with its counters.
        decomp = dict(
            getattr(self.profiler, "last_stop_decomposition", None) or {})
        cap.spans += decomp.pop("spans", [])
        cap.local_devices = decomp.pop("local_devices", None)
        cap.timing.update(decomp)

    def _finish_trace(self, cap: "_Capture", pending=None) -> None:
        """Waits out the streaming xplane write (`pending`), folds its
        decomposition into the timing, and writes the manifest at the path
        the CLI prints (log_file_<pid>.json), pointing at the XLA trace
        directory; status records capture failures so the operator sees
        them instead of a silently-wrong trace window. All per-capture
        state arrives in `cap` (not read off self): the finisher thread may
        run this while the poll thread is already inside the NEXT capture.
        """
        cfg = cap.cfg
        # shim.finish runs from the profiler's stop() returning, on the
        # poll thread, to the manifest's rename, here.
        with obs.span("shim.finish", ctx=cap.ctx, now=self._wall,
                      start_us=cap.stopped_us):
            export_child = None
            if pending is not None:
                decomp = pending.wait()
                cap.error = cap.error or decomp.pop("write_error", None)
                # a word, so beside `timing`, whose values stay numbers
                export_child = decomp.pop("export_child", None)
                # export.boot and export.idle, where the hand-over was warm
                cap.spans += decomp.pop("spans", [])
                cap.timing.update(decomp)
                cap.spans += [s for s in (pending.span, pending.index_span)
                              if s is not None]
            # This capture's completed spans. shim.finish and
            # shim.artifact_write are still open; they reach
            # `dyno selftrace` only.
            span_rows = [
                {"name": s.name, "span_id": f"{s.span_id:016x}",
                 "parent_id": f"{s.parent_id:016x}",
                 "start_us": s.start_us, "dur_us": s.dur_us}
                for s in sorted(cap.spans, key=lambda s: s.start_us)]
            with self._step_cv:
                marks = [(round(end * 1e6), round(dur * 1e6))
                         for end, dur in self._step_marks]
            steps, cost = job_cost(marks, span_rows)
            manifest = {
                "pid": cap.pid,
                "job_id": self.job_id,
                "trace_dir": cap.trace_dir,
                "started_ms": cap.started_ms,
                "ended_ms": int(self._wall() * 1000),
                "mode": "iterations" if cfg.iterations > 0 else "duration",
                "config": cfg.raw,
                "status": "error" if cap.error else "ok",
                "timing": cap.timing,
                # The id `dyno selftrace --trace_id=...` filters on:
                # recorded in the artifact so a trace on disk names its
                # control-plane request.
                "trace_ctx": cap.ctx.header(),
                "spans": span_rows,
                "steps": [list(m) for m in steps],
                "job_cost_ms": cost,
            }
            if cap.local_devices is not None:
                manifest["local_devices"] = cap.local_devices
            if pending is not None and pending.planes is not None:
                # one row a plane of the artifact's XSpace, in file order
                manifest["planes"] = pending.planes
            if export_child is not None:
                # how this capture's derived files were begun: by the child
                # started at its window ("warm"; timing.export_ready_ms is
                # the span export.idle: how long it had been ready), by one
                # started at the hand-over ("cold"), or in process ("thread")
                manifest["export_child"] = export_child
            if cap.error:
                manifest["error"] = cap.error
                self.last_error = cap.error
            # Atomic (tmp + rename): the manifest's existence IS the
            # completion signal operators and the benchmark poll for; a reader
            # must never catch a half-written JSON. A REFUSED write (ENOSPC,
            # quota — or the trace.artifact.write errno: drill) aborts
            # cleanly: tmp unlinked, nothing renamed, and the refusal lands
            # in last_error so the shim reports it alongside the daemon's
            # own pressure surface instead of dying in the finisher thread.
            path = cfg.manifest_path(cap.pid)
            tmp = f"{path}.tmp"
            wrote = False
            with obs.span("shim.artifact_write", now=self._wall):
                try:
                    failpoints.fire("trace.artifact.write")
                    with open(tmp, "w") as f:
                        json.dump(manifest, f, indent=2)
                    os.replace(tmp, path)
                    wrote = True
                except OSError as e:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    self.last_error = f"manifest write refused: {e}"
        if wrote and not cap.error:
            self.traces_completed += 1
        # Ship this capture's spans to the daemon (fire-and-forget, same
        # posture as pstat), after the rename: the selftrace merge is what
        # turns per-process timing into one cross-language request trace
        # (by trace id, so the next capture's first spans going with this
        # flush do no harm). The export child's trace.convert span
        # flushes itself on exit. Optional capability: an IPC double
        # without span support (tests, old clients) just skips the flush.
        send_spans = getattr(self._client, "send_spans", None)
        if send_spans is not None:
            try:
                send_spans(obs.JOURNAL.drain(), dest=self.endpoint)
            except OSError as e:
                self.last_error = f"span flush failed: {e}"
        self._sweep_once(cap)

    def _sweep_once(self, cap: "_Capture") -> None:
        """First capture against this trace base: reclaim expired debris
        (a SIGKILL'd export child's *.tmp files, dead-pid session dirs —
        all carrying THIS base's name prefix). Called with the manifest
        written and the spans flushed, on whichever thread finished the
        capture: fault recovery, so none of it lies between the
        operator's request and the artifact. Two finishers racing on one
        base may both sweep it; the sweep is idempotent (ENOENT ignored)."""
        base = os.path.abspath(cap.trace_dir)[: -len(f"_{cap.pid}")]
        if base in self._swept_dirs:
            return
        self._swept_dirs.add(base)
        try:
            sweep_stale_artifacts(base, self.sweep_ttl_s)
        except Exception as e:  # noqa: BLE001 - sweep must never cost
            # the capture
            _log.warning("artifact sweep of %s failed: %s", base, e)
