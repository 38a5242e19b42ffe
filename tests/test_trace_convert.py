"""Streamed, budgeted trace converter (dynolog_tpu.trace) against the
checked-in XSpace fixture.

Three contracts:
- PARITY: the streamed converter (serial and parallel) produces
  event-identical — in fact byte-identical decompressed — trace.json to
  the old single-shot converter on tests/fixtures/bench.xplane.pb.
- BUDGET: ConvertBudget's knobs are honored — max_workers=1 never
  touches a process pool, env overrides parse (and malformed ones are
  ignored), serial conversion yields between plane batches.
- HYGIENE: every derived-artifact writer cleans its .tmp on failure (the
  orphaned-tmp leak), and stream_write is atomic with the same
  guarantee.

No jax, no C++ build: pure-stdlib, default tier-1 lane.
"""

import gzip
import json
import os
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dynolog_tpu import trace  # noqa: E402

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


def test_streamed_parallel_matches_single_shot(xplane):
    # The pool only engages from a (near-)single-threaded process (fork
    # safety — see _iter_fragments), and this pytest session is not one
    # (jax threads): run the parallel conversion the way production does,
    # in a clean subprocess, then compare against the in-process single
    # shot.
    import subprocess

    single = _read_gz(trace.write_chrome_trace_gz_single(xplane))
    code = (
        "from dynolog_tpu.trace import ConvertBudget, write_chrome_trace_gz"
        f"; write_chrome_trace_gz({xplane!r}, "
        "budget=ConvertBudget(max_workers=2))")
    subprocess.run(
        [sys.executable, "-c", code], check=True, cwd=str(REPO),
        env={**os.environ, "PYTHONPATH": str(REPO)})
    parallel = _read_gz(trace._derived_path(xplane, ".trace.json.gz"))
    assert parallel == single


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
    # Parallelism is capped by the plane count: one plane, any worker
    # budget -> serial.
    import concurrent.futures

    from xspace_fixture import build_xspace

    path = tmp_path / "one.xplane.pb"
    path.write_bytes(build_xspace(planes=1, events_per_line=10))

    def boom(*a, **k):
        raise AssertionError("single plane must not create a pool")

    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", boom)
    out = trace.write_chrome_trace_gz(
        str(path), budget=trace.ConvertBudget(max_workers=8))
    assert os.path.exists(out)


def test_budget_from_env_and_malformed_values():
    env = {
        "DYNO_TRACE_CONVERT_WORKERS": "3",
        "DYNO_TRACE_CONVERT_GZIP_LEVEL": "5",
        "DYNO_TRACE_CONVERT_NICE": "7",
        "DYNO_TRACE_CONVERT_YIELD_S": "0.25",
    }
    b = trace.ConvertBudget.from_env(env)
    assert (b.max_workers, b.gzip_level, b.nice, b.yield_s) == (3, 5, 7, 0.25)
    # Malformed knobs fall back to defaults instead of raising.
    bad = trace.ConvertBudget.from_env(
        {"DYNO_TRACE_CONVERT_WORKERS": "lots",
         "DYNO_TRACE_CONVERT_YIELD_S": ""})
    dflt = trace.ConvertBudget()
    assert bad.max_workers == dflt.max_workers
    assert bad.yield_s == dflt.yield_s
    # resolved_workers: auto caps at cpu count and plane count.
    assert trace.ConvertBudget(max_workers=8).resolved_workers(2) == 2
    assert trace.ConvertBudget(max_workers=0).resolved_workers(64) >= 1


def test_budget_serial_yields_between_plane_batches(xplane, monkeypatch):
    sleeps = []
    monkeypatch.setattr(trace.time, "sleep", lambda s: sleeps.append(s))
    trace.write_chrome_trace_gz(
        xplane,
        budget=trace.ConvertBudget(
            max_workers=1, yield_every_planes=2, yield_s=0.01))
    # 4 planes, yield every 2, no trailing yield after the last -> 1.
    assert sleeps == [0.01]


def test_pool_death_degrades_to_serial(xplane, monkeypatch):
    # A pool dying MID-RUN (worker OOM-killed -> BrokenProcessPool, a
    # RuntimeError) must not cost the artifact: the remaining planes
    # convert serially and the output stays identical.
    import concurrent.futures

    single = _read_gz(trace.write_chrome_trace_gz_single(xplane))

    class DyingPool:
        def __init__(self, *a, **k):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def map(self, fn, jobs):
            yield fn(jobs[0])  # one plane succeeds...
            raise concurrent.futures.process.BrokenProcessPool(
                "worker died")

    monkeypatch.setattr(trace, "_fork_safe", lambda: True)
    monkeypatch.setattr(
        concurrent.futures, "ProcessPoolExecutor", DyingPool)
    out = trace.write_chrome_trace_gz(
        xplane, budget=trace.ConvertBudget(max_workers=2))
    assert _read_gz(out) == single


def test_out_of_range_gzip_level_clamped(xplane):
    # TRACE_CONVERT_GZIP_LEVEL=12 parses as a fine int; the writer must
    # clamp it instead of letting zlib.compressobj raise (which would
    # silently cost every capture its trace.json.gz).
    out = trace.write_chrome_trace_gz(
        xplane, budget=trace.ConvertBudget(max_workers=1, gzip_level=12))
    assert json.loads(_read_gz(out))["traceEvents"]
    out = trace.write_chrome_trace_gz(
        xplane, budget=trace.ConvertBudget(max_workers=1, gzip_level=-7))
    assert json.loads(_read_gz(out))["traceEvents"]


def test_export_fallback_honors_convert_env(xplane, monkeypatch):
    # The in-process thread fallback must apply the per-capture
    # TRACE_CONVERT_* knobs (normally injected into the export child's
    # environment) — and stay serial regardless of the workers knob.
    from dynolog_tpu.client.shim import JaxProfiler

    seen = {}

    def capture(path, budget=None):
        seen["budget"] = budget
        return []

    monkeypatch.setattr(trace, "write_derived_artifacts", capture)
    JaxProfiler._export_json(
        xplane, {"DYNO_TRACE_CONVERT_GZIP_LEVEL": "6",
                 "DYNO_TRACE_CONVERT_WORKERS": "4",
                 "DYNO_TRACE_CONVERT_YIELD_S": "0.5"})
    budget = seen["budget"]
    assert budget.gzip_level == 6
    assert budget.yield_s == 0.5
    assert budget.max_workers == 1  # forced serial on the thread path


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
    prof.configure({
        "TRACE_CONVERT_WORKERS": "1",
        "TRACE_CONVERT_GZIP_LEVEL": "4",
        "TRACE_CONVERT_YIELD_S": "0.1",
    })
    assert prof.convert_env == {
        "DYNO_TRACE_CONVERT_WORKERS": "1",
        "DYNO_TRACE_CONVERT_GZIP_LEVEL": "4",
        "DYNO_TRACE_CONVERT_YIELD_S": "0.1",
    }
    # Per-capture: knobs reset when the next config omits them.
    prof.configure({})
    assert prof.convert_env == {}


def test_summarizer_reads_fixture():
    # The fixture is schema-faithful: the summarizer parses it and sees
    # the synthetic ops.
    summary = trace._summarize_planes(
        trace.summarize_xplane_bytes(FIXTURE.read_bytes()))
    assert len(summary["planes"]) == 4
    assert summary["top_ops"]
