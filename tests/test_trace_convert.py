"""Streamed, budgeted trace converter (dynolog_tpu.trace) against the
checked-in XSpace fixture.

Four contracts:
- ONE DECODE: a plane is decoded once, and that decode gives its
  PlaneSummary and its Chrome-trace fragment: both equal to what the
  wheel's own xplane_pb2 decodes (field for field; byte for byte what
  `json.dumps` prints), names that need escaping included, and
  write_derived_artifacts decodes no plane twice.
- PARITY: the streamed converter (serial and parallel) produces
  event-identical — in fact byte-identical decompressed — trace.json to
  the old single-shot converter on tests/fixtures/bench.xplane.pb.
- BUDGET: ConvertBudget's one setting is honored — max_workers=1 never
  touches a process pool, its environment key parses (and a malformed
  value is ignored).
- THE RULE: who converts which plane is read from the artifact — the
  caller converts the heaviest plane and every plane with no line in it,
  a worker is forked only for a share of planes worth a fork and is sent
  them heaviest first, and the files are what one process writes.
- HYGIENE: every derived-artifact writer cleans its .tmp on failure (the
  orphaned-tmp leak), and stream_write is atomic with the same
  guarantee.

No jax, no C++ build: pure-stdlib, default tier-1 lane.
"""

import ast
import dataclasses
import gzip
import json
import os
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dynolog_tpu import obs, trace, xspace  # noqa: E402

FIXTURE = REPO / "tests" / "fixtures" / "bench.xplane.pb"


@pytest.fixture()
def xplane(tmp_path):
    data = FIXTURE.read_bytes()
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(data)
    return str(path)


def _read_gz(path: str) -> str:
    with gzip.open(path, "rt") as f:
        return f.read()


WORKER_PID = 1  # what InProcessPool's "worker" stamps its spans with


class InProcessPool:
    """ProcessPoolExecutor's face over the calling process: what a forked
    worker would run, run here as it is submitted (so before the caller's
    own share, as a worker forked first would begin), the spans of its
    result stamped with WORKER_PID. `jobs` holds what was pickled."""

    def __init__(self, *a, **k):
        self.kwargs = k
        self.jobs = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def submit(self, fn, job):
        import concurrent.futures

        self.jobs.append(job)
        fragment, summary, spans = fn(job)
        for span in spans:
            span.pid = WORKER_PID
        future = concurrent.futures.Future()
        future.set_result((fragment, summary, spans))
        return future


def test_fixture_regenerates_identically():
    # The checked-in fixture IS its generator's output — a drifted
    # generator (or a hand-edited fixture) fails here, keeping its
    # consumers (this test, the CI smokes, the diagnosis tests) in sync.
    from xspace_fixture import build_xspace

    assert build_xspace() == FIXTURE.read_bytes()


def test_streamed_serial_matches_single_shot(xplane):
    single = _read_gz(trace.write_chrome_trace_gz_single(xplane))
    streamed = _read_gz(trace.write_chrome_trace_gz(
        xplane, budget=trace.ConvertBudget(max_workers=1)))
    assert streamed == single
    doc = json.loads(streamed)
    events = doc["traceEvents"]
    assert len(events) > 24_000
    assert doc["displayTimeUnit"] == "ns"
    # Spot-check structure: per plane one process_name, per line one
    # thread_name, and the complete events carry resolved names.
    assert sum(1 for e in events if e.get("name") == "process_name") == 4
    assert any(e["name"].startswith("fusion.") for e in events
               if e["ph"] == "X")


def test_streamed_parallel_matches_single_shot(tmp_path):
    # A worker is forked only from a (near-)single-threaded process (fork
    # safety — see _iter_fragments), and this pytest session is not one
    # (jax threads): run the parallel conversion the way production does,
    # in a clean subprocess, then compare against the in-process single
    # shot. The artifact is one worth a fork: four planes whose op metadata
    # alone weighs 1.2 forks.
    import subprocess

    from xspace_fixture import build_xspace

    xplane = str(tmp_path / "host.xplane.pb")
    with open(xplane, "wb") as f:
        f.write(build_xspace(ops_per_plane=_ops_weighing(1.2),
                             events_per_line=200))
    single = _read_gz(trace.write_chrome_trace_gz_single(xplane))
    code = (
        "import os; from dynolog_tpu import obs; "
        "from dynolog_tpu.trace import ConvertBudget, write_chrome_trace_gz"
        f"; write_chrome_trace_gz({xplane!r}, "
        "budget=ConvertBudget(max_workers=2)); "
        "print(os.getpid(), *[s.pid for s in obs.JOURNAL.snapshot() "
        "if s.name == 'convert.plane'])")
    ran = subprocess.run(
        [sys.executable, "-c", code], check=True, cwd=str(REPO),
        env={**os.environ, "PYTHONPATH": str(REPO)}, capture_output=True,
        text=True)
    parallel = _read_gz(trace._derived_path(xplane, ".trace.json.gz"))
    assert parallel == single
    caller, *pids = ran.stdout.split()
    # two of four alike the caller's, two one forked worker's
    assert len(pids) == 4 and pids.count(caller) == 2
    assert len(set(pids)) == 2


def test_pool_skipped_in_multithreaded_process(xplane, monkeypatch):
    # This pytest process has jax loaded (conftest's CPU mesh) — XLA's
    # native threads make forking unsafe even when
    # threading.active_count() reads 1 — so even a workers=2 budget must
    # degrade to serial instead of forking a pool.
    import concurrent.futures

    assert "jax" in sys.modules

    def boom(*a, **k):
        raise AssertionError(
            "pool must not be created from a multithreaded process")

    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", boom)
    out = trace.write_chrome_trace_gz(
        xplane, budget=trace.ConvertBudget(max_workers=2))
    assert os.path.exists(out)


def test_budget_serial_never_spawns_pool(xplane, monkeypatch):
    import concurrent.futures

    def boom(*a, **k):
        raise AssertionError("max_workers=1 must not create a pool")

    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", boom)
    out = trace.write_chrome_trace_gz(
        xplane, budget=trace.ConvertBudget(max_workers=1))
    assert os.path.exists(out)


def test_budget_single_plane_never_spawns_pool(tmp_path, monkeypatch):
    # Parallelism is capped by the planes that hold lines: one plane,
    # however heavy, any worker budget, a caller that may fork -> the
    # caller converts it.
    import concurrent.futures

    from xspace_fixture import build_xspace

    path = tmp_path / "one.xplane.pb"
    path.write_bytes(build_xspace(
        planes=1, events_per_line=10, ops_per_plane=6000))

    def boom(*a, **k):
        raise AssertionError("single plane must not create a pool")

    monkeypatch.setattr(trace, "_fork_safe", lambda: True)
    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", boom)
    out = trace.write_chrome_trace_gz(
        str(path), budget=trace.ConvertBudget(max_workers=8))
    assert os.path.exists(out)


def test_budget_from_env_and_malformed_values(monkeypatch):
    monkeypatch.setenv("DYNO_TRACE_CONVERT_WORKERS", "3")
    assert trace.ConvertBudget.from_env() == trace.ConvertBudget(max_workers=3)
    # A malformed or absent setting falls back to the default, not a raise.
    for bad in ("lots", ""):
        monkeypatch.setenv("DYNO_TRACE_CONVERT_WORKERS", bad)
        assert trace.ConvertBudget.from_env() == trace.ConvertBudget()
    monkeypatch.delenv("DYNO_TRACE_CONVERT_WORKERS")
    assert trace.ConvertBudget.from_env() == trace.ConvertBudget()
    # resolved_workers: auto caps at cpu count and plane count; the
    # caller is one of them, so auto is the caller and one worker at most.
    assert trace.ConvertBudget(max_workers=8).resolved_workers(2) == 2
    assert 1 <= trace.ConvertBudget(max_workers=0).resolved_workers(64) <= 2


@pytest.mark.parametrize("dies", ["at-setup", "at-submit", "mid-run"])
def test_pool_death_degrades_to_serial(xplane, monkeypatch, dies):
    # A pool that cannot start (no working fork: OSError) or dies MID-RUN
    # (worker OOM-killed -> BrokenProcessPool, a RuntimeError) must not
    # cost the artifact: the caller converts what the pool has not handed
    # back and the output stays identical. Every plane is spanned once,
    # where it was converted.
    import concurrent.futures.process

    single = _read_gz(trace.write_chrome_trace_gz_single(xplane))
    obs.JOURNAL.drain()
    broken = concurrent.futures.process.BrokenProcessPool("worker died")

    class DyingPool(InProcessPool):
        def __init__(self, *a, **k):
            if dies == "at-setup":
                raise OSError("no semaphores here")
            super().__init__(*a, **k)

        def submit(self, fn, job):
            if dies == "at-submit":
                raise broken
            if self.jobs:  # one plane succeeds, the next dies with the pool
                self.jobs.append(job)
                future = concurrent.futures.Future()
                future.set_exception(broken)
                return future
            return super().submit(fn, job)

    monkeypatch.setattr(trace, "_fork_safe", lambda: True)
    monkeypatch.setattr(trace, "FORK_WORTH_WEIGHT", 1000)
    monkeypatch.setattr(
        concurrent.futures, "ProcessPoolExecutor", DyingPool)
    with obs.span("trace.convert", ctx=obs.TraceContext.mint()) as convert:
        out = trace.write_chrome_trace_gz(
            xplane, budget=trace.ConvertBudget(max_workers=2))
    assert _read_gz(out) == single
    spans = obs.JOURNAL.drain()
    assert [s.name for s in spans] == [
        "convert.decode", "convert.plane"] * 4 + ["trace.convert"]
    for decode, plane in zip(spans[0:8:2], spans[1:8:2]):
        assert decode.parent_id == plane.span_id
        assert plane.parent_id == convert.span_id
        assert decode.trace_id == plane.trace_id == convert.trace_id
    workers = {s.pid for s in spans} - {os.getpid()}
    assert workers == ({WORKER_PID} if dies == "mid-run" else set())


def test_convert_plane_hands_back_its_fragment_its_summary_and_its_spans(
        monkeypatch):
    # The unit of work as a pool worker returns it: the spans travel with
    # the result, and the journal of the process that ran it stays empty.
    obs.JOURNAL.drain()
    data = FIXTURE.read_bytes()
    bufs = list(xspace.iter_plane_bufs(data))
    ctx = obs.TraceContext.mint()
    niced = []
    # what the pool runs in a worker first (this process keeps its niceness)
    monkeypatch.setattr(trace.os, "nice", niced.append)
    trace._nice_worker(ctx)
    assert niced == [trace.WORKER_NICE]
    try:
        fragment, summary, spans = trace._convert_plane((1, bufs[0]))
    finally:
        obs.set_current(None)
    assert fragment == trace._plane_fragment((1, bufs[0]))
    assert summary == trace.summarize_xplane_bytes(data)[0]
    decode, plane = spans  # in order of their ends
    assert (decode.name, plane.name) == ("convert.decode", "convert.plane")
    assert plane.parent_id == ctx.span_id and plane.trace_id == ctx.trace_id
    assert decode.parent_id == plane.span_id
    assert plane.start_us <= decode.start_us <= decode.end_us <= plane.end_us
    assert all(len(s.name) < obs.NAME_BYTES for s in spans)
    assert obs.JOURNAL.drain() == []


def test_export_fallback_stays_serial_whatever_the_setting(
        xplane, monkeypatch):
    # The in-process thread fallback converts in its own process: no
    # setting of the environment gives it a pool to fork from a thread.
    from dynolog_tpu.client.shim import JaxProfiler

    seen = {}

    def capture(path, budget=None):
        seen["budget"] = budget
        return []

    monkeypatch.setattr(trace, "write_derived_artifacts", capture)
    monkeypatch.setenv("DYNO_TRACE_CONVERT_WORKERS", "4")
    JaxProfiler._export_json(xplane)
    assert seen["budget"] == trace.ConvertBudget(max_workers=1)


def test_converter_failure_leaves_no_tmp(xplane, monkeypatch):
    out_dir = os.path.dirname(xplane)

    def boom(*a, **k):
        raise RuntimeError("converter crash")

    monkeypatch.setattr(trace, "_iter_fragments", boom)
    with pytest.raises(RuntimeError):
        trace.write_chrome_trace_gz(xplane)
    assert not [f for f in os.listdir(out_dir) if f.endswith(".tmp")]


def test_summary_failure_leaves_no_tmp(xplane, monkeypatch):
    out_dir = os.path.dirname(xplane)

    def boom(*a, **k):
        raise RuntimeError("summarizer crash")

    monkeypatch.setattr(trace, "_summarize_planes", boom)
    with pytest.raises(RuntimeError):
        trace.write_summary_json(xplane)
    assert not [f for f in os.listdir(out_dir) if f.endswith(".tmp")]


def test_write_derived_artifacts_best_effort(xplane, monkeypatch):
    # One writer crashing must not cost the other artifact.
    monkeypatch.setattr(
        trace, "_summarize_planes",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    written = trace.write_derived_artifacts(xplane)
    assert [p for p in written if p.endswith(".trace.json.gz")]
    assert not [p for p in written if p.endswith(".summary.json")]


def test_stream_write_atomic(tmp_path):
    path = tmp_path / "artifact.bin"
    chunks = [b"a" * 10, b"b" * 5, memoryview(b"c" * 3)]
    assert trace.stream_write(str(path), chunks) == 18
    assert path.read_bytes() == b"a" * 10 + b"b" * 5 + b"c" * 3
    assert not list(tmp_path.glob("*.tmp"))

    def bad_chunks():
        yield b"partial"
        raise RuntimeError("producer died")

    with pytest.raises(RuntimeError):
        trace.stream_write(str(tmp_path / "torn.bin"), bad_chunks())
    # Neither the destination nor a tmp survives a failed producer.
    assert not (tmp_path / "torn.bin").exists()
    assert not list(tmp_path.glob("*.tmp"))


def test_shim_convert_budget_plumbing():
    from dynolog_tpu.client.shim import JaxProfiler

    prof = JaxProfiler()
    prof.configure({"TRACE_CONVERT_WORKERS": "1"})
    assert prof.convert_env == {"DYNO_TRACE_CONVERT_WORKERS": "1"}
    # Per-capture: the setting resets when the next config omits it.
    prof.configure({})
    assert prof.convert_env == {}


def _package_imports(tree) -> set:
    """The modules of this package a module's source imports, by name."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert not node.level, "a relative import: name the module"
            found.add(node.module)
            found.update(f"{node.module}.{a.name}" for a in node.names)
    return {name for name in found if name.split(".")[0] == "dynolog_tpu"}


def test_one_module_knows_the_wire_and_the_arrow_points_one_way():
    # dynolog_tpu.xspace is the only reader of XSpace bytes: it imports
    # nothing of the package but, at most, obs (so nothing of trace, shim
    # or diagnose); trace makes no walk of protobuf fields of its own, and
    # takes from xspace only what its own code uses (no alias kept for a
    # caller's sake).
    wire = ast.parse(pathlib.Path(xspace.__file__).read_text())
    assert _package_imports(wire) - {
        "dynolog_tpu", "dynolog_tpu.obs"} == set()
    tree = ast.parse(pathlib.Path(trace.__file__).read_text())
    named = {node.id for node in ast.walk(tree)
             if isinstance(node, ast.Name)}
    named |= {node.attr for node in ast.walk(tree)
              if isinstance(node, ast.Attribute)}
    taken = {alias.name for node in ast.walk(tree)
             if isinstance(node, ast.ImportFrom)
             and node.module == "dynolog_tpu.xspace" for alias in node.names}
    assert taken and not {"_fields", "_read_varint", "struct"} & (
        named | taken)
    assert taken <= named, taken - named
    assert not [name for name in taken
                if getattr(trace, name) is not getattr(xspace, name)]
    # and no other module of the package reads the bytes behind its back
    for path in pathlib.Path(trace.__file__).parent.rglob("*.py"):
        if path.name != "xspace.py":
            assert "_read_varint" not in path.read_text(), path


def test_summarizer_reads_fixture():
    # The fixture is schema-faithful: the summarizer parses it and sees
    # the synthetic ops.
    summary = trace._summarize_planes(
        trace.summarize_xplane_bytes(FIXTURE.read_bytes()))
    assert len(summary["planes"]) == 4
    assert summary["top_ops"]


# -- one decode a plane feeds both derived files ------------------------------

US = 1_000_000  # picoseconds in a microsecond


def stats_xspace() -> bytes:
    """What the checked-in fixture lacks: cost-model stats on the metadata
    (uint and double, under a one-byte and a two-byte stat id) and on
    events, `hlo_category`, metadata ids of two and three bytes, an event
    whose metadata is missing, more result shapes than an op's row keeps, a
    plane with no "XLA Ops" line, and a `/host:metadata` plane whose bytes
    are opaque stats."""
    import xspace_fixture as xf

    flops, nbytes, category, program, source = 1, 200, 3, 4, 150
    stat_names = ((flops, "flops"), (nbytes, "bytes_accessed"),
                  (category, "hlo_category"), (program, "program_id"),
                  (source, "source"))

    def with_stat_names(body: bytes) -> bytes:
        for sid, name in stat_names:
            body += xf._field_bytes(5, xf._stat_metadata(sid, name))
        return body

    device = with_stat_names(
        xf._field_varint(1, 1) + xf._field_str(2, "/device:TPU:0"))
    ops = [
        (1, "%fusion.1 = bf16[8,128]{1,0} fusion(%p0)", "fusion.1", (
            xf._stat(program, uint=7), xf._stat(flops, uint=4096),
            xf._stat(nbytes, double=2048.5),
            xf._stat(category, text="loop fusion"),
            xf._stat(source, text="models/transformer.py:120"))),
        (300, "%convolution.2 = f32[4]{0} convolution(%p1)", "", (
            xf._stat(category, text="convolution"),)),
        (70000, "%copy.3 = bf16[2,2]{1,0} copy(%p2)", "copy.3", ()),
    ] + [
        (10 + i, f"%fusion.{10 + i} = bf16[{i + 1},64]{{1,0}} fusion(%p)",
         f"fusion.{10 + i}", (xf._stat(flops, double=10.0 * (i + 1)),))
        for i in range(6)
    ]
    for meta_id, name, display, stats in ops:
        device += xf._field_bytes(
            4, xf._event_metadata(meta_id, name, display, stats))
    sync, offset = [], 0
    for round_ in range(3):
        for meta_id, _, _, _ in ops:
            own = ()
            if meta_id == 300 and round_ == 1:  # its own costs, whole
                own = (xf._stat(program, uint=9), xf._stat(flops, uint=64),
                       xf._stat(nbytes, uint=32))
            elif meta_id == 1 and round_ == 2:  # flops alone: bytes read 0
                own = (xf._stat(flops, double=1.5),)
            elif meta_id == 70000:  # nothing the op table reads
                own = (xf._stat(program, uint=9), xf._stat(source, text="x"))
            sync.append(
                xf._event(meta_id, offset, (meta_id % 7 + 1) * US, own))
            offset += 9 * US
        sync.append(xf._event(999, offset, 2 * US))  # no such metadata
        offset += 3 * US
    for line_id, name, events in (
        (1, "Steps", [xf._event(1, 0, 40 * US), xf._event(1, 50 * US, 0),
                      xf._event(1, 60 * US, 45 * US)]),
        (2, "XLA Modules", [xf._event(300, 0, 100 * US)]),
        (3, "XLA Ops", sync),
        (4, "Async XLA Ops", [  # beside "XLA Ops": its costs must not count
            xf._event(70000, 5 * US, 400 * US,
                      (xf._stat(flops, uint=10**9),))]),
    ):
        device += xf._field_bytes(
            3, xf._line(line_id, name, 1_700_000_000_000_000_123, events))

    host = with_stat_names(
        xf._field_varint(1, 2) + xf._field_str(2, "/host:CPU"))
    host += xf._field_bytes(4, xf._event_metadata(1, "PjitFunction(step)", ""))
    host += xf._field_bytes(4, xf._event_metadata(
        2, "TransferToDevice", "", (xf._stat(nbytes, uint=1 << 20),)))
    for thread in range(2):
        events = [
            xf._event(1 + (i + thread) % 2, i * 11 * US, 10 * US,
                      (xf._stat(flops, double=0.25),) if i == 3 else ())
            for i in range(6)]
        host += xf._field_bytes(3, xf._line(
            100 + thread, f"python3/{100 + thread}", 1_700_000_000_000_000_000,
            events))

    metadata = xf._field_varint(1, 3) + xf._field_str(2, "/host:metadata")
    metadata += xf._field_bytes(5, xf._stat_metadata(1, "hlo_proto"))
    metadata += xf._field_bytes(6, xf._stat(1, raw=bytes(range(256)) * 1200))
    metadata += xf._field_bytes(4, xf._event_metadata(
        1, "jit_step", "", (xf._stat(1, raw=b"\x08\x96\x01" * 30000),)))
    return b"".join(
        xf._field_bytes(1, plane) for plane in (device, host, metadata))


ARTIFACTS = {"fixture": FIXTURE.read_bytes, "stats": stats_xspace}


def _wire_fields(buf: bytes):
    """(number, where the field starts, where its payload starts, where it
    ends) at one level of a message, by protobuf's own varint reader."""
    from google.protobuf.internal.decoder import _DecodeVarint

    i = 0
    while i < len(buf):
        start = i
        tag, i = _DecodeVarint(buf, i)
        payload = i
        if tag & 7 == 0:
            _, i = _DecodeVarint(buf, i)
        elif tag & 7 == 2:
            size, payload = _DecodeVarint(buf, i)
            i = payload + size
        else:
            i += 8 if tag & 7 == 1 else 4
        yield tag >> 3, start, payload, i


def _oracle(data: bytes, group: bool, by_category: bool):
    """[(PlaneSummary, Chrome events)] a plane, decoded by the wheel's own
    xplane_pb2: a decoder that shares nothing with dynolog_tpu.trace."""
    pb2 = xspace._load_xplane_descriptor()
    if pb2 is None:
        pytest.skip("no wheel ships an xplane descriptor")
    payloads = [data[p:e] for num, _, p, e in _wire_fields(data) if num == 1]
    out = []
    for pid, (plane, payload) in enumerate(
            zip(pb2.XSpace.FromString(data).planes, payloads), start=1):
        want = trace.PlaneSummary(
            name=plane.name, bytes=len(payload),
            event_metadata=len(plane.event_metadata), lines=len(plane.lines),
            line_names=[line.name for line in plane.lines])
        for num, start, _, end in _wire_fields(payload):
            want.content[
                xspace.CONTENT_FIELDS.get(num, "other")] += end - start

        def costs(stats) -> dict:
            found = {}
            for stat in stats:
                kind = plane.stat_metadata[stat.metadata_id].name
                which = stat.WhichOneof("value")
                if kind in ("hlo_category", "tf_op") and which == "str_value":
                    found[kind] = stat.str_value
                elif kind in ("flops", "bytes_accessed") and which in (
                        "double_value", "uint64_value", "int64_value",
                        "ref_value"):
                    found[kind] = float(getattr(stat, which))
            return found

        events = [{"ph": "M", "pid": pid, "name": "process_name",
                   "args": {"name": plane.name}}]
        for line in plane.lines:
            tid = line.id % 2**64  # trace.py reads an int64 as it is written
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": line.name}})
            counted = ("XLA Ops" not in want.line_names
                       or line.name == "XLA Ops")
            inside = _held(line.events) if counted else {}
            for at, ev in enumerate(line.events):
                known = ev.metadata_id in plane.event_metadata
                md = plane.event_metadata[ev.metadata_id] if known else None
                name = md.name if known else f"op#{ev.metadata_id}"
                events.append({
                    "ph": "X", "pid": pid, "tid": tid,
                    "name": (md.display_name or name) if known else name,
                    "ts": line.timestamp_ns / 1e3 + ev.offset_ps / 1e6,
                    "dur": ev.duration_ps / 1e6})
                want.events += 1
                want.duration_ps = max(
                    want.duration_ps, ev.offset_ps + ev.duration_ps)
                if line.name == "Steps" and ev.duration_ps > 0:
                    want.step_durations_ps.append(ev.duration_ps)
                if not counted:
                    continue
                model = costs(md.stats) if known else {}
                own = costs(ev.stats)
                if own.get("flops") or own.get("bytes_accessed"):
                    paid = own
                else:
                    paid = model
                key = (model.get("hlo_category", "uncategorized")
                       if by_category else trace._op_key(name, group))
                agg = want.ops.setdefault(key, trace.OpAggregate(key))
                agg.total_ps += ev.duration_ps
                agg.count += 1
                held = inside.get(at, ())
                self_ps = ev.duration_ps - sum(
                    line.events[i].duration_ps for i in held)
                agg.self_ps += self_ps
                agg.held += len(held)
                scope = want.scopes.setdefault(
                    trace.op_scope(model.get("tf_op", "")), [0, 0])
                scope[0] += self_ps
                scope[1] += 1
                agg.flops += paid.get("flops", 0.0)
                agg.bytes_accessed += paid.get("bytes_accessed", 0.0)
                shape = trace._op_shape(name)
                if shape and len(agg.shapes) < trace.SHAPES_PER_OP:
                    agg.shapes.add(shape)
        out.append((want, events))
    return out


def _held(events) -> dict:
    """Index of an event -> the indices of the events directly inside it:
    for each event, the shortest one that starts no later and ends no
    earlier (of equals, the one that comes first holds)."""
    spans = [(ev.offset_ps, ev.offset_ps + ev.duration_ps) for ev in events]
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1], i))
    inside: dict = {}
    open_ = []  # candidates still open, outermost first
    for i in order:
        start, end = spans[i]
        open_ = [j for j in open_ if spans[j][1] > start]
        if open_ and end <= spans[open_[-1]][1]:
            inside.setdefault(open_[-1], []).append(i)
        open_.append(i)
    return inside


@pytest.mark.parametrize("group,by_category", [
    (True, False), (False, False), (True, True)])
@pytest.mark.parametrize("artifact", sorted(ARTIFACTS))
def test_one_decode_equals_the_wheels_decoder(artifact, group, by_category):
    # The old walker is gone, so the oracle is the protobuf runtime's own
    # parse: every field of every PlaneSummary, the rows' order too, and
    # the fragment against json.dumps over the oracle's events.
    data = ARTIFACTS[artifact]()
    got = trace.summarize_xplane_bytes(
        data, group=group, by_category=by_category)
    want = _oracle(data, group, by_category)
    assert len(got) == len(want)
    bufs = list(xspace.iter_plane_bufs(data))
    for pid, (plane, (summary, events)) in enumerate(zip(got, want), start=1):
        assert dataclasses.asdict(plane) == dataclasses.asdict(summary)
        assert list(plane.ops) == list(summary.ops)
        assert sum(plane.content.values()) == plane.bytes
        assert trace._plane_fragment((pid, bufs[pid - 1])) == ", ".join(
            json.dumps(e) for e in events).encode()
    if artifact == "stats":  # the fixture holds what it says it holds
        device = got[0]
        assert "op#999" in device.ops or by_category
        assert any(a.flops for a in device.ops.values())
        assert got[2].content["stats"] > 300_000 and not got[2].events


@pytest.mark.parametrize("artifact", sorted(ARTIFACTS))
def test_fragments_are_json_dumps_of_the_events(artifact):
    # No wheel needed: the formatted fragment against json.dumps over the
    # dict form of the same events, byte for byte, a plane at a time.
    data = ARTIFACTS[artifact]()
    for job in enumerate(xspace.iter_plane_bufs(data), start=1):
        assert trace._plane_fragment(job) == ", ".join(
            json.dumps(e) for e in trace._plane_events(*job)).encode()


NAMES_TO_ESCAPE = [
    'say "hello"', "back\\slash", "café 日本", "\U0001f680 lift",
    "tab\there\nnewline", "bell\x07 nul\x00", "</script>", "  sep",
]


@pytest.mark.parametrize("name", NAMES_TO_ESCAPE)
def test_a_name_that_needs_escaping_reads_as_json_dumps_prints_it(name):
    import xspace_fixture as xf

    plane = xf._field_str(2, name) + xf._field_bytes(
        4, xf._event_metadata(1, "%op.1 = f32[] op()", name))
    plane += xf._field_bytes(3, xf._line(
        5, name, 1000, [xf._event(1, i * US, US) for i in range(3)]))
    fragment = trace._plane_fragment((1, plane)).decode()
    assert fragment == ", ".join(
        json.dumps(e) for e in trace._plane_events(1, plane))
    assert fragment.count(json.dumps(name)) == 5  # plane, line, three events
    events = json.loads("[" + fragment + "]")
    assert [e["name"] for e in events if e["ph"] == "X"] == [name] * 3
    assert events[0]["args"]["name"] == events[1]["args"]["name"] == name


def test_bytes_that_are_not_utf8_become_the_replacement_character():
    import xspace_fixture as xf

    plane = xf._field_bytes(4, xf._field_varint(1, 1) + xf._field_bytes(
        2, xf._field_varint(1, 1) + xf._field_bytes(2, b"bad\xff\xfename")))
    plane += xf._field_bytes(3, xf._line(1, "t", 0, [xf._event(1, 0, US)]))
    fragment = trace._plane_fragment((1, plane))
    assert b'"name": "bad\\ufffd\\ufffdname"' in fragment
    assert fragment == ", ".join(
        json.dumps(e) for e in trace._plane_events(1, plane)).encode()


@pytest.mark.parametrize("workers", [1, 2])
def test_write_derived_artifacts_decodes_each_plane_once(
        xplane, tmp_path, monkeypatch, workers):
    import concurrent.futures
    import shutil

    apart = tmp_path / "apart" / "host.xplane.pb"
    apart.parent.mkdir()
    shutil.copy(xplane, apart)
    budget = trace.ConvertBudget(max_workers=workers)
    decoded, walked = [], []
    decode = xspace._decode_plane

    def counting(buf, start, end, top=None):
        decoded.append(end - start)
        walked.append(top is not None)
        return decode(buf, start, end, top)

    monkeypatch.setattr(trace, "_decode_plane", counting)
    pools = []
    if workers > 1:
        # the fixture's four planes are alike and light: worth a fork here
        monkeypatch.setattr(trace, "FORK_WORTH_WEIGHT", 1000)
        monkeypatch.setattr(trace, "_fork_safe", lambda: True)
        monkeypatch.setattr(
            concurrent.futures, "ProcessPoolExecutor",
            lambda *a, **k: pools.append(InProcessPool(*a, **k))
            or pools[-1])
    written = trace.write_derived_artifacts(xplane, budget)
    planes = [len(b) for b in xspace.iter_plane_bufs(FIXTURE.read_bytes())]
    # once a plane: in file order under one process, the worker's two
    # (as they are submitted) before the caller's two under two
    assert decoded == [planes[i] for i in (
        (0, 1, 2, 3) if workers == 1 else (1, 3, 0, 2))]
    # and its top level walked once: the walk that weighed a plane is
    # handed to the decode of the planes the caller keeps
    assert walked == (
        [False] * 4 if workers == 1 else [False, False, True, True])
    # the caller is one of `workers`: the pool holds the others
    assert [pool.kwargs["max_workers"] for pool in pools] == [1] * (
        workers - 1)
    assert [pid for pool in pools for pid, _ in pool.jobs] == [2, 4] * (
        workers - 1)
    assert sorted(os.path.basename(p) for p in written) == [
        "host.summary.json", "host.trace.json.gz"]
    # called apart, the two writers decode every plane twice between them
    # and write the same bytes (the gzip's header holds no time)
    del decoded[:], walked[:]
    trace.write_summary_json(str(apart))
    trace.write_chrome_trace_gz(str(apart), budget=budget)
    assert sorted(decoded) == sorted(planes * 2)
    for name in ("host.summary.json", "host.trace.json.gz"):
        assert (apart.parent / name).read_bytes() == (
            pathlib.Path(xplane).parent / name).read_bytes()


@pytest.mark.parametrize("broken,left", [
    ("_iter_fragments", "host.summary.json"),
    ("_plane_json", "host.summary.json"),
    ("_plane_summary", "host.trace.json.gz"),
    ("_summarize_planes", "host.trace.json.gz"),
])
def test_each_derived_file_survives_the_others_failure(
        xplane, monkeypatch, broken, left):
    # Both directions of the failure domains, at the pass and at the writers:
    # the file whose code did not break is whole, and no .tmp stays.
    def boom(*a, **k):
        raise RuntimeError(f"{broken} crash")

    monkeypatch.setattr(trace, broken, boom)
    written = trace.write_derived_artifacts(
        xplane, trace.ConvertBudget(max_workers=1))
    out_dir = os.path.dirname(xplane)
    assert [os.path.basename(p) for p in written] == [left]
    assert sorted(os.listdir(out_dir)) == sorted(["host.xplane.pb", left])
    monkeypatch.undo()
    if left.endswith(".gz"):
        assert _read_gz(written[0]) == _read_gz(
            trace.write_chrome_trace_gz_single(xplane))
    else:
        with open(written[0]) as f:
            assert json.load(f) == trace._summarize_planes(
                trace.summarize_xplane_bytes(FIXTURE.read_bytes()))


# -- a message is opened once and only as far as it is read -------------------

FLOPS, NBYTES, CATEGORY, TF_OP, UNWANTED, FAR = 1, 200, 3, 4, 9, 300
WANTED = ((FLOPS, "flops"), (NBYTES, "bytes_accessed"),
          (CATEGORY, "hlo_category"), (TF_OP, "tf_op"))


def _fixed(num: int, wire_type: int) -> bytes:
    """A fixed32 (wire type 5) or fixed64 (1) field of zeros."""
    import xspace_fixture as xf

    return xf._varint(num << 3 | wire_type) + bytes(4 if wire_type == 5 else 8)


def _wire_plane(entries=(), events=(), line_name: str = "ops") -> bytes:
    """A plane that names the four cost stats (one under a two-byte id),
    holds `entries` in its event-metadata map and `events` on one line."""
    import xspace_fixture as xf

    body = xf._field_varint(1, 1) + xf._field_str(2, "/device:TPU:0")
    for sid, name in WANTED + ((UNWANTED, "program_id"), (FAR, "source")):
        body += xf._field_bytes(5, xf._stat_metadata(sid, name))
    for entry in entries:
        body += xf._field_bytes(4, entry)
    line = (xf._field_varint(1, 7) + xf._field_str(2, line_name)
            + xf._field_varint(3, 1000))
    for event in events:
        line += xf._field_bytes(4, event)
    return body + xf._field_bytes(3, line)


def _decoded(plane: bytes, generic: bool = False):
    """`_decode_plane`'s answer less its count of generic reads, or the
    error's type; with `generic`, every entry and event through the generic
    path, as if neither loop knew anything."""
    with pytest.MonkeyPatch.context() as patch:
        if generic:
            patch.setattr(xspace, "_read_entry", lambda *a: None)
            patch.setattr(xspace, "_read_event", lambda *a: None)
        try:
            got = dataclasses.asdict(
                xspace._decode_plane(plane, 0, len(plane)))
        except Exception as e:  # noqa: BLE001 - the type is the answer
            return type(e), None
    return got, got.pop("generic")


def _wire_cases() -> dict:
    """case -> (entries, events, how many of them the generic path reads)"""
    import itertools
    import random

    import xspace_fixture as xf

    orders = random.Random(7)
    v, b = xf._field_varint, xf._field_bytes
    stats = (xf._stat(FLOPS, uint=4096), xf._stat(UNWANTED, uint=7),
             xf._stat(CATEGORY, text="loop fusion"),
             xf._stat(NBYTES, double=2048.5),
             xf._stat(TF_OP, text="jit(step)/mlp/dot_general:"),
             xf._stat(FAR, text="models/transformer.py:120"))
    parts = [v(1, 300), xf._field_str(2, "%fusion.1 = bf16[8]{0} fusion()"),
             b(3, b"\x08\x96\x01" * 9), xf._field_str(4, "fusion.1"),
             *(b(5, stat) for stat in stats)]
    name = xf._field_str(2, "%copy.2 = f32[4]{0} copy(%p)")
    ev = (v(1, 300), v(2, 5 * US), v(3, 7 * US),
          b(4, xf._stat(FLOPS, double=1.5)), b(4, xf._stat(UNWANTED, uint=9)),
          b(4, xf._stat(NBYTES, uint=64)))
    cases = {
        # the wire allows a message's fields in any order: an entry for each
        # of 120 seeded orders of the value's ten fields, an event for each
        # of the 720 orders of its six
        "any-order": (
            [v(1, 300) + b(2, b"".join(orders.sample(parts, len(parts))))
             for _ in range(120)],
            [b"".join(order) for order in itertools.permutations(ev)], 0),
        "absent-fields": (
            [b"", v(1, 5), b(2, b""), v(1, 6) + b(2, v(1, 6)),
             v(1, 7) + b(2, name), b(2, xf._field_str(4, "shown alone")),
             v(1, 8) + b(2, b(5, stats[0]))],
            [b"", v(1, 300), v(2, US), v(3, US), v(3, US) + v(1, 7),
             b(4, xf._stat(FLOPS, uint=1))], 0),
        # producers are free to set the id in the key, in the message, or
        # in both: the one read later stands
        "id-in-key-only": ([v(1, 11) + b(2, name)], [v(1, 11)], 0),
        "id-in-value-only": ([b(2, v(1, 12) + name)], [v(1, 12)], 0),
        "ids-differ": (
            [v(1, 13) + b(2, v(1, 14) + name),
             b(2, v(1, 15) + name) + v(1, 16),
             v(1, 17) + b(2, name + v(1, 18)) + v(1, 19)],
            [v(1, 13) + v(1, 14)], 0),
        "a-value-said-twice": (
            [v(1, 20) + b(2, b"".join(parts)) + b(2, v(1, 21) + name)], [], 0),
        # a stat that does not lead with its id is opened, whatever it is
        "stat-led-by-its-value": (
            [v(1, 22) + b(2, name + b(5, v(3, 77) + v(1, FLOPS))
                          + b(5, v(3, 78) + v(1, UNWANTED))
                          + b(5, xf._field_str(5, "late") + v(1, CATEGORY)))],
            [v(1, 22) + b(4, v(3, 99) + v(1, FLOPS))
             + b(4, v(4, 98) + v(1, UNWANTED))], 0),
        # an id of two bytes: opened, kept where it is one of the kinds
        "stat-id-of-two-bytes": (
            [v(1, 23) + b(2, name + b(5, xf._stat(NBYTES, uint=640))
                          + b(5, xf._stat(FAR, uint=1)))],
            [v(1, 23) + b(4, xf._stat(NBYTES, uint=32))
             + b(4, xf._stat(FAR, uint=2))], 0),
        "every-kind-of-value": (
            [v(1, 24) + b(2, name
                          + b(5, v(1, FLOPS) + v(4, 5))  # int64_value
                          + b(5, v(1, NBYTES) + v(7, 6))  # ref_value
                          + b(5, v(1, TF_OP) + v(3, 1))  # a number: not text
                          + b(5, xf._stat(CATEGORY, text="a") + v(1, FLOPS))
                          + b(5, v(1, FLOPS) + v(3, 1) + v(3, 2))),  # the later
             # a stat that is empty, and one with an id and no value
             v(1, 25) + b(2, name + b(5, b"") + b(5, v(1, FLOPS)))],
            [v(1, 24) + b(4, v(1, FLOPS) + v(7, 3))], 0),
        # `metadata` (3), `num_occurrences` (5) and fields nobody knows, of
        # a one-byte tag, are stepped over at every level
        "stepped-over": (
            [v(9, 1) + b(10, b"\xff" * 300) + v(1, 26) + b(2, v(9, 2) + b(
                3, b"\x08\x96\x01" * 50) + name + b(15, b"x" * 200) + b(
                5, v(9, 1 << 40) + v(1, FLOPS) + b(6, b"raw") + v(3, 8))
                + v(15, 1 << 62))],
            [v(5, 3) + v(1, 26) + b(15, b"\x22" * 130) + v(9, 1 << 35)
             + v(2, US) + b(4, v(9, 1) + v(1, FLOPS) + b(6, b"r") + v(3, 9))],
            0),
        "names-not-utf8-and-long": (
            [v(1, 27) + b(2, b(2, b"bad\xff\xfe" * 40) + b(4, b"\xc3" * 3))],
            [], 0),
        # what neither loop knows goes to the generic path, that message
        # alone, and the count says so
        "tag-above-0x7f": (
            [v(1, 28) + b(2, name + v(16, 1)),
             v(16, 1) + v(1, 29) + b(2, name),
             v(1, 30) + b(2, name + b(5, v(1, FLOPS) + v(3, 4) + b(17, b"x"))),
             v(1, 31) + b(2, name)],
            [v(1, 28) + v(16, 1) + v(2, US), v(1, 31) + v(2, US)], 4),
        "fixed-widths": (
            [v(1, 32) + b(2, name + _fixed(6, 5)), _fixed(7, 1) + v(1, 33),
             v(1, 34) + b(2, name + b(5, v(1, FLOPS) + _fixed(9, 5)
                                      + v(3, 4))),
             v(1, 35) + b(2, name + b(5, v(1, UNWANTED) + _fixed(9, 5)))],
            [v(1, 32) + _fixed(6, 5) + v(3, US), v(1, 33) + _fixed(6, 1),
             v(1, 35) + b(4, v(1, UNWANTED) + _fixed(9, 5))], 5),
    }
    return cases


@pytest.mark.parametrize("case", sorted(_wire_cases()))
def test_the_loops_read_what_the_generic_path_reads(case):
    entries, events, generic = _wire_cases()[case]
    for line_name in ("ops", "XLA Ops"):
        plane = _wire_plane(entries, events, line_name)
        want, every = _decoded(plane, generic=True)
        assert isinstance(want, dict) and every == len(entries) + len(events)
        assert _decoded(plane) == (want, generic)
    # and beside "XLA Ops" another line's own stats are not looked at
    import xspace_fixture as xf

    plane += xf._field_bytes(3, xf._line(8, "Async XLA Ops", 0, list(events)))
    want, _ = _decoded(plane, generic=True)
    assert _decoded(plane) == (want, generic + sum(
        xspace._read_event(ev, 0, len(ev), None) is None for ev in events))
    assert all(own is None for ev in want["lines"][1][3] for own in ev[3:])


def _cuts(message: bytes, wrap) -> list:
    return [wrap(message[:k]) for k in range(len(message))]


def _truncations() -> dict:
    """case -> planes in which one message is cut short at every length,
    the lengths round it as a writer of the whole message leaves them or
    (a plane cut whole) as the cut leaves them."""
    import xspace_fixture as xf

    v, b = xf._field_varint, xf._field_bytes
    stat = xf._stat(FLOPS, double=2.5) + v(9, 300)
    value = (v(1, 300) + xf._field_str(2, "%op.1 = f32[] op()") + b(5, stat)
             + xf._field_str(4, "op.1") + b(5, xf._stat(UNWANTED, uint=1)))
    entry = v(1, 300) + b(2, value)
    event = (v(1, 300) + v(2, 5 * US) + v(3, 7 * US) + b(4, stat)
             + b(4, xf._stat(FAR, text="x")))
    whole = _wire_plane([entry], [event])
    return {
        "an-entry": _cuts(entry, lambda cut: _wire_plane([cut], [event])),
        "an-entrys-value": _cuts(value, lambda cut: _wire_plane(
            [v(1, 300) + b(2, cut)], [event])),
        "an-entrys-stat": _cuts(stat, lambda cut: _wire_plane(
            [v(1, 300) + b(2, v(1, 300) + b(5, cut))], [event])),
        "an-event": _cuts(event, lambda cut: _wire_plane([entry], [cut])),
        "an-events-stat": _cuts(stat, lambda cut: _wire_plane(
            [entry], [v(1, 300) + b(4, cut)])),
        "a-plane": [whole[:k] for k in range(len(whole))],
        # a length that says more than is there, and a varint with no end
        "lengths-that-lie": [
            _wire_plane([v(1, 300) + b"\x12\x7f" + value], [event]),
            _wire_plane([v(1, 300) + b(2, v(1, 300) + b"\x2a\x40" + stat)]),
            _wire_plane([entry], [v(1, 300) + b"\x22\x40" + stat]),
            _wire_plane([entry], [b"\x10" + b"\xff" * 4]),
            _wire_plane([b"\x08" + b"\xff" * 3], [event]),
            # a stat of one byte is a tag with nothing behind it
            _wire_plane([v(1, 300) + b(2, b(5, b"\x08"))], [event]),
            _wire_plane([entry], [v(1, 300) + b(4, b"\x08")])],
    }


@pytest.mark.parametrize("case", sorted(_truncations()))
def test_a_message_cut_short_raises_what_the_generic_path_raises(case):
    planes = _truncations()[case]
    outcomes = [_decoded(plane) for plane in planes]
    assert [got for got, _ in outcomes] == [
        _decoded(plane, generic=True)[0] for plane in planes]
    # truncated and malformed input is a ValueError, never an IndexError
    errors = {got for got, _ in outcomes if isinstance(got, type)}
    assert errors == {ValueError}
    whole = [got for got, _ in outcomes if not isinstance(got, type)]
    if case == "a-plane":
        # only a cut between two top-level fields leaves a plane to read
        plane = planes[-1] + b"\0"
        ends = {start for _, start, _, _ in _wire_fields(plane)}
        assert [k for k, (got, _) in enumerate(outcomes)
                if not isinstance(got, type)] == sorted(
                    k for k in ends if k < len(planes))
    elif case == "lengths-that-lie":
        assert not whole
    else:
        # a cut between two fields leaves a shorter message, read alike
        assert whole and len(whole) < len(planes) / 2


def test_bytes_changed_at_random_decode_alike_or_raise_value_error():
    # Whatever the bytes, the decode that adapts and the one that takes
    # the generic path for every message agree: the same plane, or a
    # ValueError from both.
    import random

    entries, events, _ = _wire_cases()["stepped-over"]
    more = _wire_cases()["any-order"]
    plane = _wire_plane(entries + more[0][:3], events + more[1][:5])
    rng = random.Random(42)
    seen = set()
    for _ in range(600):
        changed = bytearray(plane)
        for _ in range(rng.choice((1, 1, 2, 3))):
            changed[rng.randrange(len(changed))] = rng.choice(
                (0, 1, 0x08, 0x12, 0x22, 0x2A, 0x7F, 0x80, 0xFF,
                 rng.randrange(256)))
        changed = bytes(changed)
        got, _ = _decoded(changed)
        # by what they print: a double changed into a NaN equals nothing
        assert repr(got) == repr(_decoded(changed, generic=True)[0])
        seen.add(ValueError if got is ValueError else dict)
    assert seen == {ValueError, dict}


@pytest.mark.parametrize("generic", [0, 2])
def test_a_plane_that_left_the_fast_path_says_so_in_a_span(generic):
    # convert.generic is laid over the convert.decode of a plane some of
    # whose messages the generic path had to read, and of no other plane.
    import xspace_fixture as xf

    entries, events, _ = _wire_cases()["id-in-key-only"]
    if generic:  # one entry and one event with a two-byte tag
        entries = entries + [xf._field_varint(16, 1)]
        events = events + [xf._field_varint(16, 1)]
    plane = _wire_plane(entries, events)
    assert xspace._decode_plane(plane, 0, len(plane)).generic == generic
    ctx = obs.TraceContext.mint()
    obs.set_current(ctx)
    try:
        _, summary, spans = trace._convert_plane((1, plane))
    finally:
        obs.set_current(None)
    assert summary is not None
    names = [s.name for s in spans]
    if not generic:
        assert names == ["convert.decode", "convert.plane"]
        return
    assert names == ["convert.decode", "convert.generic", "convert.plane"]
    decode, mark, _ = spans
    assert (mark.start_us, mark.dur_us, mark.pid, mark.trace_id) == (
        decode.start_us, decode.dur_us, decode.pid, ctx.trace_id)
    assert mark.parent_id == decode.span_id != mark.span_id
    # and the conversion's journal holds it, once, whoever converted
    obs.JOURNAL.drain()
    data = xf._field_bytes(1, plane) + xf._field_bytes(
        1, _wire_plane(*_wire_cases()["absent-fields"][:2]))
    fragments = list(trace._iter_fragments(
        data, trace.ConvertBudget(max_workers=1)))
    assert len(fragments) == 2
    assert [s.name for s in obs.JOURNAL.drain()].count("convert.generic") == 1


# -- who converts which plane is read from the artifact ------------------------


def _ops_weighing(forks: float) -> int:
    """Entries of an event-metadata map that weigh `forks` times the least
    a forked worker's share has to weigh (`trace._plane_weight`)."""
    return int(forks * trace.FORK_WORTH_WEIGHT / trace.METADATA_ENTRY_WEIGHT)


def _rule_plane(name: str, ops: int = 0, events: int = 0,
                stat_bytes: int = 0) -> bytes:
    """One plane as the rule sees it: `ops` entries of event metadata,
    `events` events on one line (no line where there are none), and
    `stat_bytes` of one plane-level stat (what `/host:metadata` is made
    of)."""
    import xspace_fixture as xf

    body = xf._field_varint(1, 7) + xf._field_str(2, name)
    if events:
        body += xf._field_bytes(3, xf._line(1, "XLA Ops", 1000, [
            xf._event(e % max(ops, 1) + 1, e * 2 * US, US)
            for e in range(events)]))
    for op in range(1, ops + 1):
        body += xf._field_bytes(4, xf._event_metadata(
            op, f"%fusion.{op} = bf16[8,8]{{1,0}} fusion(%p{op})", ""))
    if stat_bytes:
        body += xf._field_bytes(6, xf._stat(1, raw=b"\0" * stat_bytes))
    return body


def _heavy(i: int, forks: float) -> bytes:
    """A device plane whose op metadata alone weighs `forks` times what a
    worker's share has to weigh (a few hundred bytes of events besides)."""
    return _rule_plane(f"/device:TPU:{i}", ops=_ops_weighing(forks), events=40)


# case -> (the planes in file order, the Chrome-trace pids pickled for a
# worker in the order they are sent; none: no pool is made). The heavy
# planes are sized in forks, not in ops, so the cases say what they said
# under PR 40's constants (3000-3600 ops at 200 against 500 000) under any
# refit of the two: PR 42's 170 and 700 000 make them 4941-5929 ops.
RULE_CASES = {
    # one heavy plane, second in the file, beside a light plane with lines
    # and a lineless plane ten times the heavy one's bytes: what every
    # one-chip artifact looks like
    "one-heavy": (lambda: [
        _rule_plane("/host:CPU", ops=5, events=300), _heavy(0, 1.4),
        _rule_plane("/host:metadata", stat_bytes=10 * len(_heavy(0, 1.4))),
    ], None),
    # two heavy planes: the heavier (fourth in the file) the caller's, the
    # other a worker's; the light plane rides with the caller, who carries
    # less once the fork is counted
    "two-heavy": (lambda: [
        _rule_plane("/host:metadata", stat_bytes=3_000_000), _heavy(0, 1.2),
        _rule_plane("/host:CPU", ops=5, events=300), _heavy(1, 1.36),
    ], [2]),
    # four heavy planes, in the file neither by weight nor against it, as
    # a four-chip artifact holds them: second and fourth heaviest to the
    # worker, heaviest first
    "four-heavy": (lambda: [
        _heavy(0, 1.28), _rule_plane("#Chip0 Misc"), _heavy(1, 1.44),
        _rule_plane("/host:metadata", stat_bytes=3_000_000),
        _heavy(2, 1.2), _heavy(3, 1.36),
        _rule_plane("/host:CPU", ops=5, events=300),
    ], [6, 5]),
    # two planes each just under a fork's weight: the worker would be left
    # the lighter alone, which no fork is worth, so the caller converts both
    "two-not-quite": (lambda: [
        _heavy(0, 0.9), _rule_plane("/host:CPU", ops=5, events=300),
        _heavy(1, 0.95), _rule_plane("/host:metadata", stat_bytes=1_000_000),
    ], None),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_artifact_decides_who_converts_which_plane(
        tmp_path, monkeypatch, case):
    import concurrent.futures

    import xspace_fixture as xf

    build, sent = RULE_CASES[case]
    planes = build()
    weights = [trace._plane_weight(*xspace._plane_outline(p, 0, len(p))[1:])
               for p in planes]
    heaviest = max(range(len(planes)), key=weights.__getitem__)
    lineless = [i + 1 for i, (_, lines) in enumerate(weights) if not lines]
    assert lineless and max(planes, key=len) is planes[lineless[-1] - 1]
    data = b"".join(xf._field_bytes(1, p) for p in planes)
    files = {}
    pools = []
    monkeypatch.setattr(trace, "_fork_safe", lambda: True)
    monkeypatch.setattr(
        concurrent.futures, "ProcessPoolExecutor",
        lambda *a, **k: pools.append(InProcessPool(*a, **k)) or pools[-1])
    for workers in (1, 0):  # one process; the default budget
        path = tmp_path / str(workers) / "host.xplane.pb"
        path.parent.mkdir()
        path.write_bytes(data)
        obs.JOURNAL.drain()
        trace.write_derived_artifacts(
            str(path), trace.ConvertBudget(max_workers=workers))
        spans = obs.JOURNAL.drain()
        files[workers] = {
            name: (path.parent / name).read_bytes()
            for name in ("host.summary.json", "host.trace.json.gz")}
        by_name = {name: [s for s in spans if s.name == name]
                   for name in ("convert.plane", "convert.decode")}
        # one convert.plane and one convert.decode a plane, whoever ran it
        assert [len(v) for v in by_name.values()] == [len(planes)] * 2
        assert ({s.parent_id for s in by_name["convert.decode"]}
                == {s.span_id for s in by_name["convert.plane"]})
    assert files[0] == files[1]  # byte for byte what one process writes
    pids = [s.pid for s in by_name["convert.plane"]]
    if sent is None:
        assert pools == [] and set(pids) == {os.getpid()}
        return
    (pool,) = pools  # made once, under the default budget alone
    assert pool.kwargs["max_workers"] == 1
    # heaviest first, each plane's own bytes, never the one with no line
    assert [pid for pid, _ in pool.jobs] == sent
    assert [buf for _, buf in pool.jobs] == [planes[pid - 1] for pid in sent]
    assert heaviest + 1 not in sent and not set(lineless) & set(sent)
    assert pids.count(WORKER_PID) == len(sent)
    assert pids.count(os.getpid()) == len(planes) - len(sent)


# (bytes under `lines`, entries of the event-metadata map, lines) of every
# plane, in file order, of an artifact kept from each capture cell of the
# benchmark (PR 42, chip call 1; the four-chip two from PR 40's), and the
# decision the spans bear out there (PERF.md section 5): at one chip the
# caller converts alone, at four one worker is forked for two device planes.
_LIGHT = (0, 1, 0)  # `#Chip<i> Host Interface`, `#Chip<i> Misc`, ...
CELL_PLANES = {
    "olmo2-1b.capture": ([
        _LIGHT, (1552198, 7600, 5), _LIGHT, _LIGHT, _LIGHT,
        (68712, 183, 8), (0, 0, 0)], []),
    "olmo2-7b-2l.capture": ([
        _LIGHT, (123856, 751, 5), _LIGHT, _LIGHT, _LIGHT,
        (42754, 183, 7), (0, 0, 0)], []),
    "olmo-hybrid-7b.capture": ([
        _LIGHT, (844185, 4502, 5), _LIGHT, _LIGHT, _LIGHT,
        (44872, 183, 8), (0, 0, 0)], []),
    "deepseek-v2-lite.capture": ([
        _LIGHT, (341916, 3848, 6), _LIGHT, _LIGHT, _LIGHT,
        (45762, 208, 8), (0, 0, 0)], []),
    "olmo2-13b-v5e4.capture": ([
        _LIGHT, (246172, 3146, 7), _LIGHT, _LIGHT, (165593, 2416, 7), _LIGHT,
        _LIGHT, (167119, 2416, 7), _LIGHT, _LIGHT, (171622, 2416, 7), _LIGHT,
        (0, 2, 0), _LIGHT, (57941, 226, 24), (0, 0, 0)], [10, 4]),
    "olmoe-1b-7b-v5e4.capture": ([
        _LIGHT, (305617, 5653, 7), _LIGHT, _LIGHT, (209837, 4422, 7), _LIGHT,
        _LIGHT, (207822, 4380, 7), _LIGHT, _LIGHT, (208357, 4391, 7), _LIGHT,
        (0, 2, 0), _LIGHT, (91562, 272, 25), (0, 0, 0)], [4, 7]),
}


@pytest.mark.parametrize("cell", sorted(CELL_PLANES))
def test_the_fitted_constants_decide_each_cell_as_its_spans_say(cell):
    planes, theirs = CELL_PLANES[cell]
    weights = [(line_bytes + trace.METADATA_ENTRY_WEIGHT * entries, lines)
               for line_bytes, entries, lines in planes]
    ours, got, workers = trace._shares(weights, 2)
    assert (got, workers) == (theirs, 1 if theirs else 0)
    assert sorted(ours + got) == list(range(len(planes)))
    heaviest = max(range(len(planes)), key=lambda i: weights[i][0])
    if not theirs:
        assert ours == list(range(len(planes)))  # file order, nothing weighed
        # what lies beside the device plane is a sixth of a fork at most
        beside = sum(w for i, (w, _) in enumerate(weights) if i != heaviest)
        assert beside * 6 <= trace.FORK_WORTH_WEIGHT
        return
    # the caller converts the heaviest plane first, and every lineless one
    assert ours[0] == heaviest
    assert all(weights[i][1] for i in got)
    share = sum(weights[i][0] for i in got)
    assert share >= 1.6 * trace.FORK_WORTH_WEIGHT  # well clear of the line
