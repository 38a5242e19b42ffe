"""The eight readers of the shim's spans and step marks, on hand-built
records: the numbers are written out here, not computed by the program.

One capture's timeline, microseconds after its spawn S:

    20000-32000    shim.config_fetch (12 ms; k-th capture: 12 + k)
    33000-894500   shim.capture
    33100-93100      shim.profiler_start   60 ms
    93200-593200     shim.window          500 ms
    593300-893300    shim.collect         300 ms
    893400-894400    shim.feed              1 ms
    894000-897000  shim.xplane_write
    900000         the manifest's rename (done_t)

so finish_ms 5.6 and capture_unaccounted_ms 900 - 32 - 861 - 5.6 = 1.4. The
job steps every 100 ms, but the step that ends at 70000 took 120 (it lies
over the profiler's start) and the one that ends at 700000 took 130 (under
the drain). The device trace opened at S + 33200 and its longest gap is
600000-720000, over the slow step and the next.
"""

import pytest

import cells
import spans
import stats

BASE_US = 1_790_000_000_000_000
OPENED_US = 33_200  # the trace's origin, after the spawn
SPAN_ROWS = (
    ("shim.config_fetch", "req", 20_000, 32_000),
    ("shim.capture", "req", 33_000, 894_500),
    ("shim.profiler_start", "cap", 33_100, 93_100),
    ("shim.window", "cap", 93_200, 593_200),
    ("shim.collect", "cap", 593_300, 893_300),
    ("shim.feed", "cap", 893_400, 894_400),
    ("shim.xplane_write", "req", 894_000, 897_000))
STEPS = ((-50_000, 100_000), (70_000, 120_000), (170_000, 100_000),
         (270_000, 100_000), (370_000, 100_000), (470_000, 100_000),
         (570_000, 100_000), (700_000, 130_000), (800_000, 100_000))
READERS = cells.load_readers()
# Until PR 31 the table was generated in file order, so the readers new in
# PR 25 carry a name that sorted last; accepted names stay.
PREFIX = "xspan."
NEW = ("config_fetch_ms", "finish_ms", "capture_unaccounted_ms",
       "capture_job_cost_ms", "capture_job_cost_ms.start",
       "capture_job_cost_ms.collect", "idle_gap_job_excess_ms",
       "trace_clock_skew_us")


def capture(k: int, root) -> dict:
    """The k-th capture of a run: its fetch is k ms longer (it began
    earlier), and what it cost the job k ms dearer."""
    spawn_us = BASE_US + k * 1_000_000
    rows = [{"name": name, "span_id": f"{i + 1:016x}", "parent_id": parent,
             "start_us": spawn_us + start - (k * 1000 if i == 0 else 0),
             "dur_us": end - start + (k * 1000 if i == 0 else 0)}
            for i, (name, parent, start, end) in enumerate(SPAN_ROWS)]
    return {
        "k": k, "ok": True, "spawn_t": spawn_us / 1e6,
        "done_t": (spawn_us + 900_000) / 1e6, "capture_ms": 900.0,
        "manifest": {
            "status": "ok", "trace_dir": f"{root}/cap{k:03d}_1",
            "timing": {"received_ms": (spawn_us + 32_000) // 1000},
            "spans": rows,
            "steps": [[spawn_us + end, dur] for end, dur in STEPS],
            "job_cost_ms": {"baseline_ms": 100.0, "total": 50.0 + k,
                            "start": 20.0, "collect": 30.0 + k}}}


def xspace(spawn_us: int, marks: bool = True, absolute: bool = False,
           device: bool = True) -> bytes:
    """A capture's artifact: device ops with the 120 ms gap, the two clock
    marks 8 and 12 us off, the session's opening. `absolute` writes every
    start as unix nanoseconds, as the reducer's docstring has it."""
    from jax.profiler import ProfileData

    opened_ns = (spawn_us + OPENED_US) * 1000
    line_ns = opened_ns if absolute else 0

    def ev(meta, start_us, dur_us, stat=""):
        return (f"events {{ metadata_id: {meta} "
                f"offset_ps: {(start_us - OPENED_US) * 1_000_000} "
                f"duration_ps: {dur_us * 1_000_000} {stat} }}")

    def mark(start_us, off_ns):
        carried = (spawn_us + start_us) * 1000 - off_ns
        return ev(1, start_us, 2, f"stats {{ metadata_id: 1 int64_value: {carried} }}")

    ops = (f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: {line_ns} '
           f"{ev(1, 95_000, 505_000)} {ev(1, 720_000, 100_000)} "
           f"{ev(1, 821_000, 60_000)} }}") if device else ""
    host = (f"{mark(91_200, 8_000)} {mark(593_400, 12_000)}") if marks else ""
    return ProfileData.text_proto_to_serialized_xspace(f"""
planes {{ id: 1 name: "/device:TPU:0" {ops}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[] fusion()" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 7 name: "python3" timestamp_ns: {line_ns} {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "dynolog.clock_sync" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "unix_ns" }} }} }}
planes {{ id: 3 name: "Task Environment"
  stats {{ metadata_id: 1 uint64_value: {opened_ns} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "profile_start_time" }} }} }}
""")


def record(tmp_path, captures: int = 3, traced: int | None = None,
           **artifact) -> dict:
    """A run's record; `traced` is the capture whose artifact the harness
    reduced (the last that holds device events), None for none."""
    run = {"captures": [capture(k, tmp_path) for k in range(captures)],
           "device": {"count": 1}, "window_start": (BASE_US - 150_000) / 1e6,
           "step_ms": [100.0, 120.0] + [100.0] * 5 + [130.0] + [100.0] * 6}
    if traced is not None:
        target = run["captures"][traced]
        folder = tmp_path / f"cap{traced:03d}_1" / "plugins" / "profile" / "r"
        folder.mkdir(parents=True)
        path = folder / "host.xplane.pb"
        path.write_bytes(xspace(int(target["spawn_t"] * 1e6), **artifact))
        run["trace"] = {"path": str(path)}
    return run


def read_all(run: dict) -> dict:
    return {name: READERS[PREFIX + name].read(run) for name in NEW}


def test_normal_run(tmp_path):
    got = read_all(record(tmp_path, traced=2))
    assert got == {
        "config_fetch_ms": pytest.approx(13.0),  # 12, 13, 14
        "finish_ms": pytest.approx(5.6, abs=1e-3),
        "capture_unaccounted_ms": pytest.approx(1.4, abs=1e-3),
        "capture_job_cost_ms": 51.0, "capture_job_cost_ms.start": 20.0,
        "capture_job_cost_ms.collect": 31.0,
        # the gap lies over the 130 ms step and the one after it
        "idle_gap_job_excess_ms": pytest.approx(30.0),
        "trace_clock_skew_us": pytest.approx(12.0)}


def test_the_overlay_is_made_once_and_leaves_the_record_alone(tmp_path):
    run = record(tmp_path, traced=1)
    before = set(run)
    over = spans.overlay(run)
    assert spans.overlay(run) is over and set(run) == before
    assert over["capture"] == 1
    assert over["marks"] == 2 and over["gap_ms"] == pytest.approx(120.0)
    assert over["spans_over"] == {
        "shim.capture": pytest.approx(120.0),
        "shim.collect": pytest.approx(120.0)}
    assert [dur for _, dur in over["steps_over"]] == [130_000, 100_000]


def test_last_capture_holds_no_device_events(tmp_path):
    """The harness then reduces an earlier capture; the manifest readers
    still take all three."""
    got = read_all(record(tmp_path, traced=1))
    assert got["config_fetch_ms"] == pytest.approx(13.0)
    assert got["idle_gap_job_excess_ms"] == pytest.approx(30.0)
    assert got["trace_clock_skew_us"] == pytest.approx(12.0)
    # no capture of the run held any: device_idle_pct prints nothing
    # either, and the six manifest readers are not touched by it
    got = read_all(record(tmp_path))
    assert got["idle_gap_job_excess_ms"] is None
    assert got["trace_clock_skew_us"] is None
    assert None not in [got[name] for name in NEW[:6]]
    # the artifact the harness named holds no device plane after all
    got = read_all(record(tmp_path / "x", traced=0, device=False))
    assert got["idle_gap_job_excess_ms"] is None


def test_a_failed_capture_is_left_out(tmp_path):
    run = record(tmp_path, traced=2)
    run["captures"][0] = {"k": 0, "ok": False, "spawn_t": 1.0,
                          "error": "no manifest within 30 s"}
    got = read_all(run)
    assert got["config_fetch_ms"] == pytest.approx(13.5)  # 13, 14
    assert got["capture_job_cost_ms"] == 51.5
    assert None not in got.values()


def test_a_program_without_spans_reads_as_nothing(tmp_path):
    """The parent of the PR that added the spans: no reader raises."""
    run = record(tmp_path, traced=2)
    for cap in run["captures"]:
        for key in ("spans", "steps", "job_cost_ms"):
            del cap["manifest"][key]
    assert read_all(run) == dict.fromkeys(NEW)


def test_a_trace_without_marks_is_a_second_off(tmp_path):
    got = read_all(record(tmp_path, traced=0, marks=False))
    assert got["trace_clock_skew_us"] == 1e6
    assert got["idle_gap_job_excess_ms"] == pytest.approx(30.0)


def test_starts_that_are_already_unix_time(tmp_path):
    """A jaxlib that hands starts as nanoseconds since the epoch: float64
    holds those to 256 ns, so the skew is 12 us to a quarter."""
    got = read_all(record(tmp_path, traced=0, absolute=True))
    assert got["trace_clock_skew_us"] == pytest.approx(12.0, abs=0.3)
    assert got["idle_gap_job_excess_ms"] == pytest.approx(30.0)


def test_job_cost_against_the_benchmarks_own_passes(tmp_path):
    """The shim's sum from its step marks is the sum over the record's
    `step_ms`, whose passes end where the marks do."""
    run = record(tmp_path, captures=1)
    only = run["captures"][0]
    assert sum(run["step_ms"][:9]) * 1e3 - 150_000 == STEPS[-1][0]
    assert stats.median(run["step_ms"]) == 100.0
    assert spans.outside_cost_ms(run, only) == pytest.approx(50.0)
    assert READERS[PREFIX + "capture_job_cost_ms"].read(run) == pytest.approx(
        spans.outside_cost_ms(run, only))
