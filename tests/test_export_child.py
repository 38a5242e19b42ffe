"""The export child is up before its capture ends (docs/TRACE_PIPELINE.md,
"The export child's life"): spawned as the window opens, ready while the
window and the drain last, handed the artifact's path as the write
completes, and gone by every way out.

Driven without a daemon and without a chip: the child is the real one
(`python -c shim._EXPORT_CHILD_CODE`), the profiler is the real
`JaxProfiler` round a `ProfilerSession` the test supplies
(`test_capture_spans.fake_session`).
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import psutil
import pytest

import xspace_fixture
from dynolog_tpu import failpoints, obs, trace, xspace
from dynolog_tpu.client import shim
from test_capture_spans import (
    config, fake_session, make_client, wait_manifest)

REPO = str(Path(__file__).resolve().parent.parent)
DERIVED = (".summary.json", ".trace.json.gz")


@pytest.fixture(autouse=True)
def clean():
    failpoints.disarm_all()
    obs.JOURNAL.drain()
    yield
    failpoints.disarm_all()
    obs.JOURNAL.drain()


@pytest.fixture()
def xplane(tmp_path, request) -> str:
    """The checked-in fixture's XSpace on disk: four planes alike, none
    worth a forked worker; `build_xspace`'s arguments where a test
    parametrises this fixture with them."""
    path = tmp_path / "run" / "host.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(xspace_fixture.build_xspace(
        **getattr(request, "param", {})))
    return str(path)


def spawn_child(env: dict | None = None) -> subprocess.Popen:
    """The child as the shim starts it, pipes in the test's hands."""
    return subprocess.Popen(
        [sys.executable, "-c", shim._EXPORT_CHILD_CODE],
        env={**os.environ, "PYTHONPATH": REPO, **(env or {})},
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)


@pytest.fixture()
def daemon_socket():
    """A datagram socket where the child's $DYNO_OBS_ENDPOINT points: what
    a flush of its spans would reach. (name, socket)."""
    import socket

    from dynolog_tpu.client import ipc

    name = f"dynotpu_export_child_{os.getpid()}"
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
    sock.bind(ipc._address(name))
    sock.settimeout(10)
    yield name, sock
    sock.close()


@pytest.fixture()
def direct(xplane, tmp_path) -> dict:
    """Both files as `write_derived_artifacts`, called here, writes them
    from a copy of the artifact."""
    copy = tmp_path / "direct" / "host.xplane.pb"
    copy.parent.mkdir()
    copy.write_bytes(Path(xplane).read_bytes())
    trace.write_derived_artifacts(str(copy))
    want = derived(str(copy))
    assert set(want) == set(DERIVED)
    return want


def derived(xplane_path: str) -> dict:
    """Both derived files' bytes by extension, of those that exist."""
    base = xplane_path[: -len(".xplane.pb")]
    return {ext: Path(base + ext).read_bytes()
            for ext in DERIVED if os.path.exists(base + ext)}


def wait_until(what, timeout_s: float = 20.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = what()
        if got:
            return got
        time.sleep(0.01)
    return what()


def live_children() -> list:
    return [c for c in psutil.Process().children(recursive=True)
            if c.is_running() and c.status() != psutil.STATUS_ZOMBIE]


# ------------------------------------------------------ the child alone


def test_a_child_handed_a_path_writes_what_a_direct_call_writes(
        xplane, direct, daemon_socket):
    name, sock = daemon_socket
    child = spawn_child({obs.ENV_FLUSH_ENDPOINT: name})
    word, at = child.stdout.readline().split()
    assert word == b"ready" and abs(float(at) - time.time()) < 30
    # ready, and nothing touched while it has no path
    assert derived(xplane) == {}
    out, err = child.communicate((json.dumps(xplane) + "\n").encode(), 60)
    assert child.returncode == 0, err
    assert derived(xplane) == direct
    assert not glob.glob(str(Path(xplane).parent / "*.tmp"))
    # and its one span went to the daemon as it left
    assert b"trace.convert" in sock.recv(4096)


def flushed_spans(sock) -> list:
    """Every span datagram at the socket, in order of arrival, as obs.Span."""
    from dynolog_tpu.client import ipc

    sock.settimeout(0.5)
    spans = []
    while True:
        try:
            frame = sock.recv(4096)
        except TimeoutError:
            return spans
        size, kind = ipc.METADATA.unpack_from(frame)
        assert kind.rstrip(b"\0") == ipc.MSG_TYPE_SPAN
        assert size == ipc.SPAN.size
        trace_id, span_id, parent_id, start_us, dur_us, pid, _, name = (
            ipc.SPAN.unpack_from(frame, ipc.METADATA.size))
        spans.append(obs.Span(
            name.rstrip(b"\0").decode(), trace_id, span_id, parent_id,
            start_us, dur_us, pid))


# four planes whose op metadata alone weighs 1.2 forks: two of them
# outweigh a fork (trace._shares), whatever the two constants are fitted to
WORTH_A_FORK = {
    "ops_per_plane": int(
        1.2 * trace.FORK_WORTH_WEIGHT / trace.METADATA_ENTRY_WEIGHT),
    "events_per_line": 200}


@pytest.mark.parametrize("workers, xplane, forked", [
    (1, {}, False), (2, {}, False), (1, WORTH_A_FORK, False),
    (2, WORTH_A_FORK, True)],
    ids=["1-light", "2-light", "1-heavy", "2-heavy"], indirect=["xplane"])
def test_a_conversion_spans_every_plane_and_its_decode(
        xplane, direct, daemon_socket, workers, forked):
    """One convert.plane a plane with its convert.decode inside, all
    inside trace.convert and under the trace id handed down; a plane's pid
    is the process that converted it: the child's own, and a forked
    worker's only where the budget allows one and the artifact is worth
    one; the files are what they were."""
    name, sock = daemon_socket
    ctx = obs.TraceContext.mint()
    child = spawn_child({obs.ENV_FLUSH_ENDPOINT: name,
                         obs.ENV_TRACE_CTX: ctx.header(),
                         "DYNO_TRACE_CONVERT_WORKERS": str(workers)})
    assert child.stdout.readline().startswith(b"ready ")
    _, err = child.communicate((json.dumps(xplane) + "\n").encode(), 60)
    assert child.returncode == 0, err
    assert derived(xplane) == direct
    spans = flushed_spans(sock)
    n_planes = len(xspace.plane_index(Path(xplane).read_bytes()))
    assert n_planes == 4
    convert, rest = spans[0], spans[1:]  # the parent is flushed first
    assert convert.name == "trace.convert" and convert.pid == child.pid
    assert convert.parent_id == ctx.span_id
    assert {s.trace_id for s in spans} == {ctx.trace_id}
    planes = [s for s in rest if s.name == "convert.plane"]
    decodes = {s.parent_id: s for s in rest if s.name == "convert.decode"}
    assert len(planes) == len(decodes) == n_planes
    assert len(rest) == 2 * n_planes
    for plane in planes:
        assert plane.parent_id == convert.span_id
        assert convert.start_us <= plane.start_us
        assert plane.end_us <= convert.end_us
        decode = decodes[plane.span_id]
        assert plane.start_us <= decode.start_us
        assert decode.end_us <= plane.end_us
        assert decode.pid == plane.pid
    pids = [s.pid for s in planes]
    if not forked:
        assert set(pids) == {child.pid}
        ends = [(s.start_us, s.end_us) for s in planes]
        assert ends == sorted(ends)  # one after the other, in file order
    else:  # the child is a converter too: two planes each
        assert pids.count(child.pid) == 2 and len(set(pids)) == 2


def test_a_flush_sends_a_parent_before_what_it_holds(daemon_socket):
    """Whatever order the journal holds them in (a plane's spans are
    recorded before the trace.convert they lie in closes), the flush goes
    in order of start."""
    name, sock = daemon_socket
    journal = obs.SpanJournal()
    ctx = obs.TraceContext.mint()
    for span_name, start_us in (("convert.decode", 30), ("convert.plane", 20),
                                ("trace.convert", 10)):
        journal.record(obs.Span(span_name, ctx.trace_id, obs.mint_id(),
                                ctx.span_id, start_us, 5))
    assert obs.flush_spans(name, journal) == 3
    assert [s.name for s in flushed_spans(sock)] == [
        "trace.convert", "convert.plane", "convert.decode"]


def test_the_in_process_fallback_spans_its_planes_in_the_shims_journal(
        xplane, direct):
    """The `thread` hand-over converts in the job's own process: the same
    spans, the job's pid, in the journal the shim's next flush drains."""
    obs.JOURNAL.drain()  # the fixture's own direct call
    shim.JaxProfiler._export_json(xplane)
    assert derived(xplane) == direct
    spans = obs.JOURNAL.drain()
    (convert,) = [s for s in spans if s.name == "trace.convert"]
    planes = [s for s in spans if s.name == "convert.plane"]
    decodes = [s for s in spans if s.name == "convert.decode"]
    assert len(planes) == len(decodes) == 4 and len(spans) == 9
    assert {s.parent_id for s in planes} == {convert.span_id}
    assert {s.parent_id for s in decodes} == {s.span_id for s in planes}
    assert {s.pid for s in spans} == {os.getpid()}
    assert {s.trace_id for s in spans} == {convert.trace_id}


@pytest.mark.parametrize("said", [b"", b"\n"], ids=["eof", "empty-line"])
def test_a_pipe_closed_without_a_path_ends_the_child_quietly(
        xplane, daemon_socket, said):
    """No artifact is owed: exit 0, no file, no .tmp, no span flushed."""
    name, sock = daemon_socket
    child = spawn_child({obs.ENV_FLUSH_ENDPOINT: name})
    assert child.stdout.readline().startswith(b"ready ")
    out, err = child.communicate(said, 30)
    assert child.returncode == 0, err
    assert out == b"" and err == b""
    assert derived(xplane) == {}
    assert os.listdir(Path(xplane).parent) == ["host.xplane.pb"]
    sock.setblocking(False)
    with pytest.raises(BlockingIOError):
        sock.recv(4096)


@pytest.mark.parametrize("workers, pool", [("1", False), ("2", True)])
def test_the_child_imports_before_it_is_ready_what_it_will_need(
        workers, pool):
    """The lazy imports of the conversion are made before `ready`: the
    pool's only where the budget allows a second worker."""
    probe = (
        "import sys, os; from dynolog_tpu import trace; "
        "real = os.write; "
        "os.write = lambda fd, b: real(fd, b + ' '.join(sorted("
        "m for m in ('zlib', 'dynolog_tpu.obs', 'dynolog_tpu.failpoints', "
        "'concurrent.futures.process', 'multiprocessing.synchronize') "
        "if m in sys.modules)).encode() + b'\\n'); "
        "trace.export_child()")
    child = subprocess.run(
        [sys.executable, "-c", probe], input=b"", capture_output=True,
        env={**os.environ, "PYTHONPATH": REPO,
             "DYNO_TRACE_CONVERT_WORKERS": workers}, timeout=60)
    assert child.returncode == 0, child.stderr
    ready, loaded = child.stdout.split(b"\n")[:2]
    assert ready.startswith(b"ready ")
    loaded = loaded.decode().split()
    assert {"zlib", "dynolog_tpu.obs", "dynolog_tpu.failpoints"} <= set(
        loaded)
    assert ("concurrent.futures.process" in loaded) is pool
    assert ("multiprocessing.synchronize" in loaded) is pool


def test_a_shim_that_is_killed_takes_its_waiting_child_with_it(tmp_path):
    """SIGKILL of the job while a child waits for a path: the pipe's write
    end dies with the job, the child reads end of file and goes."""
    job = subprocess.Popen(
        [sys.executable, "-c",
         "import time\n"
         "from dynolog_tpu.client.shim import JaxProfiler\n"
         "p = JaxProfiler(); p._sess = object(); p.warm_export()\n"
         "while p._export_child.ready_at() is None: time.sleep(0.01)\n"
         "print(p._export_child.proc.pid, flush=True); time.sleep(120)"],
        env={**os.environ, "PYTHONPATH": REPO}, stdout=subprocess.PIPE)
    try:
        child = psutil.Process(int(job.stdout.readline()))
        assert child.is_running() and "export_child" in " ".join(
            child.cmdline())
    finally:
        job.kill()
        job.wait(30)

    def gone():
        try:
            return child.status() == psutil.STATUS_ZOMBIE
        except psutil.NoSuchProcess:
            return True

    assert wait_until(gone)
    assert not glob.glob(str(tmp_path / "**" / "*"), recursive=True)


# ------------------------------------------- the hand-over, three cases


def session_profiler() -> shim.JaxProfiler:
    """A JaxProfiler between start() and stop(), without jax."""
    profiler = shim.JaxProfiler()
    profiler._sess = object()
    return profiler


def hand_over_warm(profiler, xplane):
    profiler.warm_export()
    child = profiler._take_export_child()
    assert wait_until(lambda: _peek(child))
    return profiler._spawn_export(xplane, None, child), child


def _peek(child) -> bool:
    """Whether the child has said ready, without taking the line."""
    import select

    return bool(select.select([child.proc.stdout], [], [], 0)[0])


def hand_over_cold_after_kill(profiler, xplane):
    profiler.warm_export()
    child = profiler._take_export_child()
    child.proc.kill()
    child.reaper.join(10)
    return profiler._spawn_export(xplane, None, child), child


def hand_over_cold_unwarmed(profiler, xplane):
    return profiler._spawn_export(xplane), None


def hand_over_cold_after_one_failed_spawn(profiler, xplane):
    failpoints.arm("shim.export_spawn", "error*1")
    profiler.warm_export()
    assert profiler._export_child is None
    return profiler._spawn_export(xplane, None, None), None


def hand_over_thread(profiler, xplane):
    failpoints.arm("shim.export_spawn", "error")
    profiler.warm_export()
    assert profiler._export_child is None
    return profiler._spawn_export(xplane, None, None), None


@pytest.mark.parametrize("hand_over, how", [
    (hand_over_warm, "warm"),
    (hand_over_cold_after_kill, "cold"),
    (hand_over_cold_unwarmed, "cold"),
    (hand_over_cold_after_one_failed_spawn, "cold"),
    (hand_over_thread, "thread"),
], ids=["warm", "killed-before", "no-window", "spawn-failed-once",
        "no-interpreter"])
def test_every_hand_over_yields_both_files(xplane, direct, hand_over, how):
    # (the thread converts serially; the bytes are the same under one
    # worker and under two: test_trace_convert)
    profiler = session_profiler()
    said, child = hand_over(profiler, xplane)
    assert said["export_child"] == how
    assert ("export_ready_ms" in said) is ("spans" in said) is (
        how == "warm")
    if how == "warm":
        assert type(said["export_ready_ms"]) is int
        assert 0 <= said["export_ready_ms"] < 20_000
        boot, idle = said["spans"]
        assert (boot.name, idle.name) == ("export.boot", "export.idle")
        assert boot.pid == idle.pid == child.proc.pid
    profiler._export_thread.join(60)
    assert not profiler._export_thread.is_alive()
    assert derived(xplane) == direct
    assert wait_until(lambda: not live_children())


def test_export_spawn_failpoint_falls_back_to_thread(xplane, monkeypatch):
    # shim.export_spawn=error simulates an unspawnable interpreter: the
    # profiler's export must degrade to the in-process thread, never
    # lose the derived artifacts silently.
    import threading

    failpoints.arm("shim.export_spawn", "error")
    hits = failpoints.hits("shim.export_spawn")
    exported = threading.Event()
    monkeypatch.setattr(
        shim.JaxProfiler, "_export_json",
        staticmethod(lambda path: exported.set()))
    profiler = shim.JaxProfiler(export_trace_json=True)
    assert profiler._spawn_export(xplane) == {"export_child": "thread"}
    assert exported.wait(timeout=5.0)
    assert failpoints.hits("shim.export_spawn") == hits + 1


@pytest.mark.parametrize("way_out", ["release", "write-failed"])
def test_a_capture_without_an_artifact_sends_its_child_away(xplane, way_out):
    profiler = session_profiler()
    profiler.warm_export()
    child = profiler._export_child
    assert child is not None and child.proc.poll() is None
    if way_out == "release":
        profiler.release_export()
    else:  # what PendingWrite tells on_complete where the write failed
        assert profiler._spawn_export(
            None, None, profiler._take_export_child()) == {}
    child.reaper.join(20)
    assert child.proc.returncode == 0
    assert profiler._export_child is None
    assert derived(xplane) == {}
    assert not glob.glob(str(Path(xplane).parent / "*.tmp"))


# --------------------------------------------- through the shim's capture


def capture(tmp_path, monkeypatch, stem, duration_ms=300, arm=None,
            extra="", profiler=None, stopping=None):
    """One capture through TraceClient and the real JaxProfiler round a
    session that returns an XSpace: (manifest, client), the client
    stopped. `extra`: more lines of the request's config text;
    `stopping`: what the session's stop() does first (the drain)."""
    fake_session(monkeypatch, stopping=stopping)
    client, _ = make_client(profiler or shim.JaxProfiler())
    cfg = config(tmp_path, stem, obs.TraceContext.mint(), duration_ms)
    if extra:
        cfg = shim.TraceConfig.parse(
            "\n".join(f"{k}={v}" for k, v in cfg.raw.items()) + "\n" + extra)
    if arm:
        failpoints.arm("shim.export_spawn", arm)
    try:
        client._run_trace(cfg, None)
        manifest = wait_manifest(cfg)
    finally:
        client.stop()
    return manifest, client


def derived_of(manifest) -> list:
    return sorted(
        os.path.basename(p) for ext in DERIVED for p in glob.glob(
            os.path.join(manifest["trace_dir"], "plugins", "profile", "*",
                         "*" + ext)))


@pytest.mark.parametrize("arm, how", [
    (None, "warm"), ("error*1", "cold"), ("error", "thread")])
def test_the_manifest_says_how_the_export_began(
        tmp_path, monkeypatch, arm, how):
    manifest, client = capture(
        tmp_path, monkeypatch, f"how_{how}", duration_ms=600, arm=arm)
    assert manifest["status"] == "ok"
    assert manifest["export_child"] == how
    timing = manifest["timing"]
    assert ("export_ready_ms" in timing) is (how == "warm")
    assert all(type(v) is int for v in timing.values()), timing
    spans = {row["name"]: row for row in manifest["spans"]}
    life = {"export.boot", "export.idle"}  # a warm child's, and only its
    assert life & set(spans) == (life if how == "warm" else set())
    if how == "warm":
        # spawned as the window opened: ready for most of its 600 ms
        assert 0 < timing["export_ready_ms"] <= 600 + timing["collect_ms"] + 50
    assert wait_until(lambda: len(derived_of(manifest)) == 2), derived_of(
        manifest)
    client.profiler._export_thread.join(30)
    assert wait_until(lambda: not live_children())


def test_a_warm_childs_boot_and_wait_are_spans_of_its_capture(
        tmp_path, monkeypatch, started):
    manifest, client = capture(tmp_path, monkeypatch, "life", duration_ms=600)
    assert manifest["export_child"] == "warm"
    (child,) = started
    rows = {row["name"]: row for row in manifest["spans"]}
    boot, idle, window, capture_ = (rows[name] for name in (
        "export.boot", "export.idle", "shim.window", "shim.capture"))
    # the boot opens inside the window, just before the Popen, and ends
    # where the wait begins: the `ready` the child stamped
    assert window["start_us"] <= boot["start_us"] <= window["start_us"] + 40_000
    assert boot["dur_us"] > 0
    assert boot["start_us"] + boot["dur_us"] == idle["start_us"]
    assert boot["parent_id"] == idle["parent_id"] == capture_["span_id"]
    # measured once: the timing key is the span, truncated
    assert manifest["timing"]["export_ready_ms"] == idle["dur_us"] // 1000
    # the wait ends at the hand-over, which the write's end releases
    write = rows["shim.xplane_write"]
    handed = idle["start_us"] + idle["dur_us"]
    assert write["start_us"] + write["dur_us"] <= handed
    # and the flush after the manifest's rename carries both, as the child's
    sent = {s.name: s for s in client._client.sent}
    trace_id = int(manifest["trace_ctx"].split("/")[0], 16)
    for name in ("export.boot", "export.idle"):
        assert sent[name].pid == child.proc.pid != os.getpid()
        assert sent[name].trace_id == trace_id
        assert f"{sent[name].span_id:016x}" == rows[name]["span_id"]
    client.profiler._export_thread.join(30)
    assert wait_until(lambda: not live_children())


def test_the_window_is_as_long_as_asked_with_the_spawn_inside_it(
        tmp_path, monkeypatch):
    spawned_at = []
    real = shim.JaxProfiler.warm_export

    def slow_spawn(self, ctx=None):
        spawned_at.append((time.time(), obs.current(), ctx))
        time.sleep(0.08)  # a spawn far slower than any measured
        real(self, ctx)

    monkeypatch.setattr(shim.JaxProfiler, "warm_export", slow_spawn)
    profiler = shim.JaxProfiler()

    def drain_until_the_child_is_ready():
        # A child's boot is 0.3-0.5 s on an idle machine and more beside
        # five other test workers, so "ready before the hand-over" is made
        # an order of events, not a margin of the wall clock: the drain
        # lasts until the child has said so.
        assert wait_until(lambda: _peek(profiler._export_child))

    manifest, _ = capture(
        tmp_path, monkeypatch, "window", duration_ms=400, profiler=profiler,
        stopping=drain_until_the_child_is_ready)
    spans = {s["name"]: s for s in manifest["spans"]}
    window = spans["shim.window"]
    assert 400_000 <= window["dur_us"] < 400_000 + 40_000, window
    (at, ambient, handed), = spawned_at
    assert window["start_us"] <= at * 1e6 <= window["start_us"] + 40_000
    assert f"{ambient.span_id:016x}" == window["span_id"]
    # the child's span parents to shim.capture, as it did: the context
    # handed to the spawn is the one from before the window
    assert f"{handed.span_id:016x}" == spans["shim.capture"]["span_id"]
    assert handed.header().split("/")[0] == (
        manifest["trace_ctx"].split("/")[0])
    # the one child is the one spawned inside the window, after the slow
    # spawn's sleep, and it was ready when its path came
    assert manifest["export_child"] == "warm"
    boot, idle = spans["export.boot"], spans["export.idle"]
    assert at * 1e6 + 80_000 <= boot["start_us"]
    assert boot["start_us"] <= window["start_us"] + window["dur_us"]
    assert window["start_us"] + window["dur_us"] <= (
        idle["start_us"] + idle["dur_us"])
    assert manifest["timing"]["export_ready_ms"] == idle["dur_us"] // 1000


@pytest.fixture()
def started(monkeypatch) -> list:
    """Every export child the profiler starts, in order."""
    children = []
    real = shim.JaxProfiler._start_export_child

    def spy(self, ctx=None):
        children.append(real(self, ctx))
        return children[-1]

    monkeypatch.setattr(shim.JaxProfiler, "_start_export_child", spy)
    return children


def test_a_capture_whose_export_is_off_starts_no_child(
        tmp_path, monkeypatch, started):
    manifest, client = capture(
        tmp_path, monkeypatch, "off", 100, extra="TRACE_JSON=0")
    assert manifest["status"] == "ok" and "export_child" not in manifest
    assert started == [] and client.profiler._export_thread is None


def test_a_setting_that_went_reaches_nothing(tmp_path, monkeypatch):
    """A request that still carries TRACE_CONVERT_GZIP_LEVEL, _NICE or
    _YIELD_S is treated as one with any key the shim does not know: kept
    in TraceConfig.raw, the capture completes, and of the converter's keys
    only the one that is left reaches the export child's environment."""
    gone = {"TRACE_CONVERT_GZIP_LEVEL": "12", "TRACE_CONVERT_NICE": "3",
            "TRACE_CONVERT_YIELD_S": "0.5"}
    extra = "\n".join(f"{k}={v}" for k, v in gone.items())
    assert gone.items() <= shim.TraceConfig.parse(extra).raw.items()
    envs = []
    real = shim._ExportChild.__init__
    monkeypatch.setattr(
        shim._ExportChild, "__init__",
        lambda self, env: envs.append(env) or real(self, env))
    manifest, client = capture(
        tmp_path, monkeypatch, "gone", 100,
        extra=extra + "\nTRACE_CONVERT_WORKERS=1")
    assert manifest["status"] == "ok"
    assert manifest["export_child"] in ("warm", "cold")
    (env,) = envs
    assert {k: v for k, v in env.items() if "TRACE_CONVERT" in k} == {
        "DYNO_TRACE_CONVERT_WORKERS": "1"}
    assert wait_until(lambda: len(derived_of(manifest)) == 2), derived_of(
        manifest)
    client.profiler._export_thread.join(30)
    assert wait_until(lambda: not live_children())


def test_a_capture_whose_stop_raises_sends_its_child_away(
        tmp_path, monkeypatch, started):
    def boom():
        raise RuntimeError("drain failed")

    fake_session(monkeypatch, stopping=boom)
    client, _ = make_client(shim.JaxProfiler())
    cfg = config(tmp_path, "raises", obs.TraceContext.mint(), 100)
    try:
        with pytest.raises(RuntimeError):
            client._run_trace(cfg, None)
    finally:
        client.stop()
    (child,) = started
    child.reaper.join(20)
    assert child.proc.returncode == 0
    assert client.profiler._export_child is None
    assert wait_until(lambda: not live_children())
    assert not glob.glob(str(tmp_path / "**" / "*.summary.json"),
                         recursive=True)


def test_stop_in_the_middle_of_a_window_leaves_the_process_no_child(
        tmp_path, monkeypatch):
    """TraceClient.stop() while the poll thread sleeps its window out: the
    join gives up (shortened here), and the child that waits for a path
    is sent away; the capture that completes afterwards starts its child
    cold, as before."""
    import threading

    fake_session(monkeypatch)
    client, _ = make_client(shim.JaxProfiler())
    cfg = config(tmp_path, "midwindow", obs.TraceContext.mint(), 1500)
    poll = threading.Thread(
        target=client._run_trace, args=(cfg, None), daemon=True)
    poll.start()
    child = wait_until(lambda: client.profiler._export_child)
    assert child is not None
    client._thread = poll
    monkeypatch.setattr(poll, "join", lambda timeout=None: None)
    client.stop()
    child.reaper.join(20)
    assert child.proc.returncode == 0
    assert wait_until(lambda: not live_children())
    threading.Thread.join(poll, 30)
    manifest = wait_manifest(cfg)
    assert manifest["status"] == "ok" and manifest["export_child"] == "cold"
    client.profiler._export_thread.join(30)
    assert len(derived_of(manifest)) == 2
    assert wait_until(lambda: not live_children())
