"""One capture, one timeline: the shim's phases as obs spans, the job's
steps as marks, both in the manifest (docs/OBSERVABILITY.md).

Driven without a daemon: `TraceClient._run_trace` is handed the config,
profiler doubles record what `JaxProfiler` records, and a synthetic clock
(`TraceClient._wall`, `obs.span(now=...)`) makes the arithmetic exact.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time
from pathlib import Path

import pytest

from dynolog_tpu import obs
from dynolog_tpu.client import shim
from dynolog_tpu.client.shim import (
    STEP_MARKS, PendingWrite, RecordingProfiler, TraceClient, TraceConfig,
    job_cost)

EXPORT = {"export.boot", "export.idle"}  # a warm export child's, by the shim
TABLE = {
    "shim.config_fetch", "shim.capture", "shim.profiler_start", "shim.window",
    "shim.collect", "shim.feed", "shim.xplane_write", "shim.finish",
    "shim.artifact_write"} | EXPORT
IN_MANIFEST = TABLE - {"shim.finish", "shim.artifact_write"}


class Clock:
    """A wall clock the test moves; whole microseconds, so spans are exact."""

    def __init__(self, start_s: int = 1_790_000_000):
        self.us = start_s * 1_000_000

    def __call__(self) -> float:
        return self.us / 1e6

    def advance(self, ms: float) -> None:
        self.us += int(ms * 1000)


class SpanSink:
    """The IPC client's span capability, kept: what each flush shipped."""

    def __init__(self):
        self.flushes: list[list[obs.Span]] = []

    def send_spans(self, spans, dest=None) -> None:
        self.flushes.append(list(spans))

    @property
    def sent(self) -> list[obs.Span]:
        return [s for flush in self.flushes for s in flush]

    def close(self) -> None:
        pass


class WarmChild(shim._ExportChild):
    """An export child from the shim's side with no process behind it:
    spawned 40 ms ago, ready 10 ms ago, and there to take whatever it is
    handed."""

    class proc:
        pid = 4242

    def __init__(self):
        now = time.time()
        self.spawned_us, self._ready_at = int((now - 0.04) * 1e6), now - 0.01
        self.handed: list = []

    def ready_at(self) -> float:
        return self._ready_at

    def hand(self, path) -> bool:
        self.handed.append(path)
        return True


class SpanningProfiler:
    """JaxProfiler's shape without jax: stop() records shim.collect and
    shim.feed, hands them back in its decomposition, and feeds a
    PendingWrite under the request's context, whose completion is
    JaxProfiler's own hand-over to the `WarmChild` of the capture's window
    (a warmup has no window and no child).
    `hold` keeps the write open until the test closes its queue."""

    def __init__(self, clock=time.time, on_collect=None, hold: bool = False):
        self.clock, self.on_collect, self.hold = clock, on_collect, hold
        self.obs_ctx = None
        self.held: list[PendingWrite] = []
        self._dir = self._pending = self._child = None

    def warm_export(self, ctx=None) -> None:
        self._child = WarmChild()

    def start(self, trace_dir: str) -> None:
        self._dir = trace_dir
        if isinstance(self.clock, Clock):
            self.clock.advance(60.7)

    def stop(self) -> None:
        export_ctx = obs.current()
        write_ctx = self.obs_ctx or export_ctx
        with obs.span("shim.collect", now=self.clock) as collect:
            if self.on_collect:
                self.on_collect()
        with obs.span("shim.feed", now=self.clock) as feed:
            run_dir = os.path.join(self._dir, "plugins", "profile", "run")
            os.makedirs(run_dir, exist_ok=True)
            child, self._child = self._child, None
            pending = PendingWrite(
                os.path.join(run_dir, "host.xplane.pb"), ctx=write_ctx,
                on_complete=child and (
                    lambda path: shim.JaxProfiler()._spawn_export(
                        path, export_ctx, child)))
            pending.queue.put(memoryview(b"x" * 4096))
            if self.hold:
                self.held.append(pending)
            else:
                pending.queue.close()
            if isinstance(self.clock, Clock):
                self.clock.advance(1.2)
        self._pending = pending
        self.last_stop_decomposition = {
            "collect_ms": collect.dur_us // 1000,
            "feed_ms": feed.dur_us // 1000, "xspace_bytes": 4096,
            "spans": [collect, feed]}

    def take_pending_write(self):
        pending, self._pending = self._pending, None
        return pending


@pytest.fixture(autouse=True)
def empty_journal():
    obs.JOURNAL.drain()
    yield
    obs.JOURNAL.drain()


def make_client(profiler, clock=None) -> tuple[TraceClient, SpanSink]:
    client = TraceClient(job_id=5, endpoint="dynotpu_spans_none",
                         profiler=profiler)
    client._client.close()
    client._client = sink = SpanSink()
    if clock is not None:
        client._wall = clock
    return client, sink


def config(tmp_path, stem: str, ctx: obs.TraceContext | None = None,
           duration_ms: int = 5) -> TraceConfig:
    text = (f"ACTIVITIES_LOG_FILE={tmp_path}/{stem}.json\n"
            f"ACTIVITIES_DURATION_MSECS={duration_ms}")
    if ctx is not None:
        text += f"\n{obs.CONFIG_KEY}={ctx.header()}"
    return TraceConfig.parse(text)


def wait_manifest(cfg: TraceConfig, timeout_s: float = 10.0) -> dict:
    path = Path(cfg.manifest_path(os.getpid()))
    deadline = time.time() + timeout_s
    while time.time() < deadline and not path.exists():
        time.sleep(0.005)
    assert path.exists(), f"no manifest at {path}"
    return json.loads(path.read_text())


def one_capture(tmp_path, profiler=None, clock=None):
    """One capture under a request context; (manifest, spans by name, ctx)
    once the finisher has flushed."""
    ctx = obs.TraceContext.mint()
    client, sink = make_client(profiler or SpanningProfiler(), clock)
    cfg = config(tmp_path, "one", ctx)
    polled_us = int((clock or time.time)() * 1e6) - 10_000
    try:
        client._run_trace(cfg, polled_us)
        manifest = wait_manifest(cfg)
    finally:
        client.stop()  # joins the finisher: its flush has happened
    by_name = {s.name: s for s in sink.sent if s.trace_id == ctx.trace_id}
    return manifest, by_name, ctx


def test_one_capture_yields_every_span_under_one_trace_id(tmp_path):
    manifest, spans, ctx = one_capture(tmp_path)
    assert set(spans) == TABLE
    assert manifest["trace_ctx"] == ctx.header()
    request, capture, finish = (
        ctx.span_id, spans["shim.capture"].span_id,
        spans["shim.finish"].span_id)
    parents = {name: span.parent_id for name, span in spans.items()}
    assert parents == {
        "shim.config_fetch": request, "shim.capture": request,
        "shim.profiler_start": capture, "shim.window": capture,
        "shim.collect": capture, "shim.feed": capture,
        "shim.xplane_write": request, "shim.finish": request,
        "shim.artifact_write": finish,
        "export.boot": capture, "export.idle": capture}
    assert {spans[name].pid for name in EXPORT} == {WarmChild.proc.pid}
    assert spans["shim.capture"].pid == os.getpid()


def test_children_lie_inside_their_parents(tmp_path):
    _, spans, _ = one_capture(tmp_path)
    capture, finish = spans["shim.capture"], spans["shim.finish"]
    for name in ("shim.profiler_start", "shim.window", "shim.collect",
                 "shim.feed"):
        child = spans[name]
        assert capture.start_us <= child.start_us, name
        assert child.end_us <= capture.end_us, name
    write = spans["shim.artifact_write"]
    assert finish.start_us <= write.start_us
    assert write.end_us <= finish.end_us
    # the finish takes over where the profiler's stop() returned, and the
    # fetch ended before the capture began
    assert finish.start_us == capture.end_us
    assert spans["shim.config_fetch"].end_us <= capture.start_us
    assert spans["shim.config_fetch"].dur_us >= 10_000


def test_timing_keys_are_the_spans_truncated_to_whole_ms(tmp_path):
    clock = Clock()
    profiler = SpanningProfiler(
        clock, on_collect=lambda: clock.advance(239.9))
    manifest, spans, _ = one_capture(tmp_path, profiler, clock)
    timing = manifest["timing"]
    assert all(type(v) is int for v in timing.values()), timing
    # a mark, where it always was: config in hand, before the profiler starts
    assert (spans["shim.config_fetch"].end_us // 1000 <= timing["received_ms"]
            <= spans["shim.profiler_start"].start_us // 1000)
    assert timing["profiler_start_ms"] == 60  # 60.7 ms, truncated
    assert timing["collect_ms"] == 239
    assert timing["feed_ms"] == 1
    for key, name in (("profiler_start_ms", "shim.profiler_start"),
                      ("collect_ms", "shim.collect"),
                      ("feed_ms", "shim.feed"),
                      ("write_ms", "shim.xplane_write")):
        assert timing[key] == spans[name].dur_us // 1000, key
    # after the window the capture span holds the profiler's stop() alone
    assert timing["profiler_stop_ms"] == (
        spans["shim.capture"].end_us - spans["shim.window"].end_us) // 1000
    assert timing["profiler_stop_ms"] == 241
    assert timing["xspace_bytes"] == timing["write_bytes"] == 4096


def test_manifest_lists_the_spans_completed_before_it(tmp_path):
    manifest, spans, _ = one_capture(tmp_path)
    rows = {row["name"]: row for row in manifest["spans"]}
    assert set(rows) == IN_MANIFEST
    for name, row in rows.items():
        assert set(row) == {
            "name", "span_id", "parent_id", "start_us", "dur_us"}
        assert row["span_id"] == f"{spans[name].span_id:016x}"
        assert row["parent_id"] == f"{spans[name].parent_id:016x}"
        assert (row["start_us"], row["dur_us"]) == (
            spans[name].start_us, spans[name].dur_us)
    starts = [row["start_us"] for row in manifest["spans"]]
    assert starts == sorted(starts)


def test_overlapping_captures_each_list_their_own_spans(tmp_path):
    """The first capture's write is held open, so its finisher is still
    waiting when the second capture runs, finishes and flushes."""
    profiler = SpanningProfiler(hold=True)
    client, sink = make_client(profiler)
    first, second = obs.TraceContext.mint(), obs.TraceContext.mint()
    cfg1, cfg2 = config(tmp_path, "a", first), config(tmp_path, "b", second)
    try:
        client._run_trace(cfg1, int(time.time() * 1e6))
        profiler.hold = False
        client._run_trace(cfg2, int(time.time() * 1e6))
        manifest2 = wait_manifest(cfg2)
        assert not Path(cfg1.manifest_path(os.getpid())).exists()
        # The second's flush took the first's early spans with it (the
        # daemon merges by trace id); the first's manifest still lists
        # them, from its own state and not from the journal. (The finisher
        # flushes after it has written the manifest: wait for the flush.)
        deadline = time.time() + 10.0
        while time.time() < deadline and not sink.sent:
            time.sleep(0.005)
        assert {s.trace_id for s in sink.sent} == {
            first.trace_id, second.trace_id}
        profiler.held[0].queue.close()
        manifest1 = wait_manifest(cfg1)
    finally:
        client.stop()
    for manifest, ctx in ((manifest1, first), (manifest2, second)):
        assert {row["name"] for row in manifest["spans"]} == IN_MANIFEST
        ids = {f"{s.span_id:016x}" for s in sink.sent
               if s.trace_id == ctx.trace_id}
        assert {row["span_id"] for row in manifest["spans"]} <= ids
        assert manifest["trace_ctx"] == ctx.header()
    assert not obs.JOURNAL.snapshot()  # everything has been flushed


def test_steps_and_job_cost_from_a_synthetic_cadence(tmp_path):
    """40 steps of 100 ms, then a capture whose drain lies over a 100 ms
    step and a 150 ms one."""
    clock = Clock()
    client_box = []

    def drain():
        client = client_box[0]
        clock.advance(30)
        client.step()  # 100 ms: began before the fetch
        clock.advance(150)
        client.step()  # the slow one, all of it under shim.collect
        clock.advance(50)

    profiler = SpanningProfiler(clock, on_collect=drain)
    client, _ = make_client(profiler, clock)
    client_box.append(client)
    client.step()
    for _ in range(40):
        clock.advance(100)
        client.step()
    cfg = config(tmp_path, "cost", duration_ms=0)
    polled_us = clock.us
    clock.advance(10)  # the fetch
    try:
        client._run_trace(cfg, polled_us)
        manifest = wait_manifest(cfg)
    finally:
        client.stop()
    # the two steps over the capture, after the one before them
    assert manifest["steps"] == [
        [polled_us, 100_000], [polled_us + 100_700, 100_700],
        [polled_us + 250_700, 150_000]]
    assert manifest["job_cost_ms"] == {
        "baseline_ms": 100.0, "total": 50.7, "start": 0.7, "collect": 50.7}


def test_step_ring_is_bounded(tmp_path):
    clock = Clock()
    client, _ = make_client(RecordingProfiler(), clock)
    try:
        for _ in range(3 * STEP_MARKS):
            clock.advance(10)
            client.step()
        assert STEP_MARKS == 64
        assert len(client._step_marks) == STEP_MARKS
        ends = [end for end, _ in client._step_marks]
        assert ends[-1] == clock() and ends == sorted(ends)
        assert all(abs(dur - 0.010) < 1e-6 for _, dur in client._step_marks)
    finally:
        client.stop()


def rows(**spans) -> list:
    return [{"name": "shim." + name, "start_us": start, "dur_us": end - start}
            for name, (start, end) in spans.items()]


@pytest.mark.parametrize("marks, spans, steps, cost", [
    # no step() calls at all: nothing listed, nothing charged
    ([], rows(config_fetch=(0, 10), capture=(10, 90), feed=(80, 90)),
     [], {"baseline_ms": 0.0, "total": 0.0, "start": 0.0, "collect": 0.0}),
    # a fast step takes nothing off: the excess is floored at 0 a step
    ([(1000, 1000), (2000, 1000), (2500, 500), (4500, 2000)],
     rows(config_fetch=(2000, 2010), profiler_start=(2020, 2100),
          collect=(2600, 4400), feed=(4400, 4450)),
     [(2000, 1000), (2500, 500), (4500, 2000)],
     {"baseline_ms": 1.0, "total": 1.0, "start": 0.0, "collect": 1.0}),
    # a backend without shim.feed: the capture's end bounds the range
    ([(1000, 1000), (2000, 1000), (3900, 1500)],
     rows(config_fetch=(1500, 1510), capture=(1520, 2400)),
     [(1000, 1000), (2000, 1000)],
     {"baseline_ms": 1.0, "total": 0.0, "start": 0.0, "collect": 0.0}),
])
def test_job_cost(marks, spans, steps, cost):
    assert job_cost(marks, spans) == (steps, cost)


def test_a_backend_that_records_no_spans_keeps_working(tmp_path):
    manifest, spans, _ = one_capture(tmp_path, RecordingProfiler())
    assert manifest["status"] == "ok"
    assert set(spans) == TABLE - EXPORT - {
        "shim.collect", "shim.feed", "shim.xplane_write"}
    assert set(manifest["timing"]) == {
        "received_ms", "profiler_start_ms", "profiler_stop_ms"}
    assert all(type(v) is int for v in manifest["timing"].values())
    assert manifest["steps"] == []
    assert manifest["job_cost_ms"]["total"] == 0.0


def test_span_opened_in_the_past():
    journal = obs.SpanJournal()
    with obs.span("late", ctx=obs.TraceContext.mint(), journal=journal,
                  now=lambda: 50.0, start_us=42_000_000) as late:
        pass
    assert (late.start_us, late.dur_us, late.end_us) == (
        42_000_000, 8_000_000, 50_000_000)
    assert journal.drain() == [late]


def test_warmup_spans_hang_under_one_parent(tmp_path):
    """The warmup capture of the poll loop has no request: what the
    backend's stop() records there is one tree under shim.warmup, not
    three roots named like a capture's phases."""
    client, _ = make_client(SpanningProfiler())
    client._warm_profiler()
    assert client.last_error is None
    spans = {s.name: s for s in obs.JOURNAL.snapshot()}
    warmup = spans["shim.warmup"]
    assert warmup.parent_id == 0
    assert set(spans) == {
        "shim.warmup", "shim.collect", "shim.feed", "shim.xplane_write"}
    for name in ("shim.collect", "shim.feed", "shim.xplane_write"):
        assert spans[name].trace_id == warmup.trace_id, name
        assert spans[name].parent_id == warmup.span_id, name


def test_a_capture_that_fails_early_leaves_no_context_behind(tmp_path):
    class Refusing(SpanningProfiler):
        def start(self, trace_dir):
            raise RuntimeError("no session")

    profiler = Refusing()
    client, _ = make_client(profiler)
    try:
        with pytest.raises(RuntimeError, match="no session"):
            client._run_trace(config(tmp_path, "bad"), None)
        assert profiler.obs_ctx is None
    finally:
        client.stop()


def test_jax_profiler_puts_two_clock_marks_into_a_capture(tmp_path):
    """The real backend, from a thread that is not the main one: the
    `unix_ns` keyword comes back exact, and session opening + event start
    is that wall clock to well under a millisecond."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    work = jax.jit(lambda a: a @ a)
    x = jnp.ones((256, 256))
    work(x).block_until_ready()
    profiler = shim.JaxProfiler(export_trace_json=False)
    around = []

    def capture(trace_dir):
        around.append(time.time_ns())
        profiler.start(str(trace_dir))
        time.sleep(0.05)
        profiler.stop()
        around.append(time.time_ns())
        pending = profiler.take_pending_write()
        if pending is not None:
            assert "write_error" not in pending.wait(30.0)

    def marks_of(trace_dir):
        thread = threading.Thread(target=capture, args=(trace_dir,))
        thread.start()
        while thread.is_alive():
            work(x).block_until_ready()
        thread.join()
        (path,) = glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb"))
        profile = ProfileData.from_file(path)
        return profile, [
            (ev.start_ns, dict(ev.stats)["unix_ns"])
            for plane in profile.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == "dynolog.clock_sync"]

    # outside a capture (warmup, ring sample) there are none
    assert marks_of(tmp_path / "ring")[1] == []
    del around[:]
    profiler.obs_ctx = obs.TraceContext.mint()
    profile, marks = marks_of(tmp_path / "capture")
    assert len(marks) == 2
    assert around[0] < marks[0][1] < marks[1][1] < around[1]
    assert marks[1][1] - marks[0][1] >= 50e6
    environment = next(
        p for p in profile.planes if p.name == "Task Environment")
    opened = dict(environment.stats)["profile_start_time"]
    for start_ns, carried in marks:
        origin = opened if start_ns < 1e17 else 0
        assert abs(origin + start_ns - carried) < 1e6, (start_ns, carried)


# ------------------------------------------------- the two library calls
# JaxProfiler around a ProfilerSession that the test supplies: what the
# shim's account (CPU against wall, switches, faults) says of a call that
# sleeps and of one that computes.

ACCOUNT = ("cpu_us", "proc_cpu_us", "nvcsw", "nivcsw", "minflt")
CALL_MS = 60


def sleep_call():
    time.sleep(CALL_MS / 1e3)


def spin_call():
    """CALL_MS of this thread's own CPU time, however long that takes."""
    until = time.thread_time_ns() + CALL_MS * 1_000_000
    while time.thread_time_ns() < until:
        pass


def fake_session(monkeypatch, opening=None, stopping=None):
    """jaxlib's ProfilerSession replaced: its constructor and its stop()
    do what the test says and nothing else; stop() returns an XSpace."""
    from jax._src.lib import _profiler

    import xspace_fixture

    xspace = xspace_fixture.build_xspace(planes=1, events_per_line=50)

    class Session:
        def __init__(self, options):
            if opening:
                opening()

        def stop(self):
            if stopping:
                stopping()
            return xspace

    monkeypatch.setattr(_profiler, "ProfilerSession", Session)


def jax_capture(tmp_path, stem="lib", client=None) -> dict:
    """One capture through the real JaxProfiler; the manifest's timing."""
    own = client is None
    if own:
        client, _ = make_client(shim.JaxProfiler(export_trace_json=False))
    cfg = config(tmp_path, stem, obs.TraceContext.mint())
    try:
        client._run_trace(cfg, None)
        manifest = wait_manifest(cfg)
    finally:
        if own:
            client.stop()
    assert manifest["status"] == "ok"
    return manifest["timing"]


@pytest.mark.parametrize("call", ["profiler_start", "collect"])
@pytest.mark.parametrize("work, low, high", [
    (sleep_call, 0.0, 0.3), (spin_call, 0.6, 1.05)],
    ids=["sleeps", "spins"])
def test_cpu_against_wall_tells_waiting_from_work(
        tmp_path, monkeypatch, call, work, low, high):
    fake_session(monkeypatch, **{
        "opening" if call == "profiler_start" else "stopping": work})
    shares = []
    for attempt in range(3):  # a preempted spin reads low: best of three
        timing = jax_capture(tmp_path, f"{call}{attempt}")
        wall_us = timing[f"{call}_ms"] * 1000
        assert wall_us >= CALL_MS * 1000
        shares.append(timing[f"{call}_cpu_us"] / wall_us)
        if low <= shares[-1] <= high:
            break
    assert low <= shares[-1] <= high, shares
    # other threads idle here: the process's CPU is this thread's, nearly
    assert timing[f"{call}_proc_cpu_us"] >= timing[f"{call}_cpu_us"] * 0.9


def test_the_account_is_ten_whole_numbers_beside_the_two_durations(
        tmp_path, monkeypatch):
    fake_session(monkeypatch, stopping=sleep_call)
    timing = jax_capture(tmp_path)
    for prefix in ("profiler_start", "collect"):
        for counter in ACCOUNT:
            value = timing[f"{prefix}_{counter}"]
            assert type(value) is int and value >= 0, (prefix, counter)
    # a sleep is left by a voluntary switch, at least the one
    assert timing["collect_nvcsw"] >= 1
    assert all(type(v) is int for v in timing.values()), timing


def test_the_public_api_fallback_takes_the_same_account(
        tmp_path, monkeypatch):
    """A jax whose private session type moved: start_trace / stop_trace
    are the two library calls, and the manifest holds their account."""
    import jax
    from jax._src.lib import _profiler

    monkeypatch.delattr(_profiler, "ProfilerSession")
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: spin_call())
    monkeypatch.setattr(jax.profiler, "stop_trace", sleep_call)
    timing = jax_capture(tmp_path)
    for prefix in ("profiler_start", "collect"):
        assert {f"{prefix}_{c}" for c in ACCOUNT} <= set(timing)
    assert timing["profiler_start_cpu_us"] >= CALL_MS * 1000 * 0.75
    assert timing["collect_cpu_us"] < CALL_MS * 1000 * 0.3
    assert "collect_ms" not in timing  # the fallback's span only, as before


def test_one_captures_account_does_not_leak_into_the_next(
        tmp_path, monkeypatch):
    fake_session(monkeypatch, opening=spin_call, stopping=spin_call)
    profiler = shim.JaxProfiler(export_trace_json=False)
    client, _ = make_client(profiler)
    try:
        first = jax_capture(tmp_path, "first", client)
        fake_session(monkeypatch)  # both calls return at once
        second = jax_capture(tmp_path, "second", client)
        # a start that fails leaves no account of the one before it
        fake_session(monkeypatch, opening=lambda: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            client._run_trace(config(tmp_path, "third"), None)
        assert profiler.last_start_account is None
        assert profiler.last_stop_decomposition is None
    finally:
        client.stop()
    for prefix in ("profiler_start", "collect"):
        assert first[f"{prefix}_cpu_us"] >= CALL_MS * 1000 * 0.75
        assert second[f"{prefix}_cpu_us"] < CALL_MS * 1000 * 0.3


def test_where_the_kernel_refuses_rusage_thread(tmp_path, monkeypatch):
    """gVisor may: the CPU fields then come from the thread's clock, and
    the switch and fault counts are absent, not zero."""
    def refused(who):
        raise OSError(22, "Invalid argument")

    monkeypatch.setattr(shim.resource, "getrusage", refused)
    fake_session(monkeypatch, stopping=spin_call)
    timing = jax_capture(tmp_path)
    for prefix in ("profiler_start", "collect"):
        assert {k for k in timing if k.startswith(prefix)} == {
            f"{prefix}_ms", f"{prefix}_cpu_us", f"{prefix}_proc_cpu_us"}
    assert timing["collect_cpu_us"] >= CALL_MS * 1000 * 0.75
    assert timing["profiler_start_cpu_us"] < CALL_MS * 1000 * 0.3
