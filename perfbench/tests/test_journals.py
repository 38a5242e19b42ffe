"""The hook that hands the readers what the daemon and the shim already
record (`Run.read_journals`), and the nine readers that arrive with it, on
records written here: the numbers below are written out, not computed by
the program.

The window opens at W = 1 790 000 000 s and lasts 40 s. The daemon ticks
once a second: the k-th `collector.tpu_monitor.tick` begins at W + k s +
0.3 s and lasts 6 + k ms (k = 0..39, so the window's median is 25.5 and its
longest 45), the kernel monitor's at + 0.5 s for 0.8 ms. Three captures:
the verb `dyno gputrace` sends takes 0.2, 0.4 and 0.9 ms, the hand-off
0.1, 0.3 and 0.5. The job's longest pass is 1500 ms and ended 10.0 s into
the window: the tick of k = 9 (at 9.3 s, 15 ms) lies under it, the tick of
k = 8 (8.300-8.314 s) just before it.
"""

import json
import subprocess

import pytest

import cells
import harness
import rehearsal
import selftrace

W = 1_790_000_000.0
W_US = int(W * 1e6)
READERS = cells.load_readers()
SPAN_READERS = ("tpu_tick_ms_p50", "tpu_tick_ms_max", "kernel_tick_ms_p50",
                "longest_pass_tick_overlap_ms", "rpc_verb_ms",
                "ipc_handoff_ms")
NEW = SPAN_READERS + ("ipc_timeout_wakeup_pct", "daemon_rss_mb",
                      "first_capture_ms")
DATA = cells.HERE / "tests" / "data"


def span(name: str, start_s: float, ms: float) -> dict:
    return {"name": name, "ts": W_US + round(start_s * 1e6),
            "dur": round(ms * 1e3), "pid": 7, "tid": 8,
            "args": {"trace_id": "0" * 16}}


def journal(first_tick: int = -20) -> list:
    """The ring's spans: ticks from `first_tick` seconds after the window
    opened (20 s of set-up before it by default) to 5 s after it closed."""
    spans = []
    for k in range(first_tick, 45):
        spans.append(span(selftrace.TPU_TICK, k + 0.3, 6 + k if k >= 0 else 5))
        spans.append(span(selftrace.KERNEL_TICK, k + 0.5, 0.8))
        spans.append(span(selftrace.IPC_SLICE, k, 1000))
    for at, verb, handoff in ((3.0, 0.2, 0.1), (14.0, 0.4, 0.3),
                              (27.0, 0.9, 0.5)):
        spans.append(span(selftrace.CAPTURE_VERB, at, verb))
        spans.append(span(selftrace.HANDOFF, at + 0.002, handoff))
        spans.append(span("shim.capture", at + 0.01, 900))
    if first_tick < -9:  # the warm capture's, before the window: not its
        spans.append(span(selftrace.CAPTURE_VERB, -9.0, 7.0))
        spans.append(span(selftrace.HANDOFF, -8.9, 7.0))
    return spans


def record(first_tick: int = -20, longest=(1500.0, 10.0)) -> dict:
    spans = journal(first_tick)
    found = {"spans": spans, "spans_recorded": 9000, "ring_capacity": 4096,
             "ipc_wakeups": {"message": 150, "posted": 10, "timeout": 40},
             "tpu_rows": 1}
    return {
        "window_start": W, "window_end": W + 40.0, "window_s": 40.0,
        "selftrace": found,
        "selftrace_oldest_ms": selftrace.oldest_ms(found),
        "longest_passes": [
            [longest[0], longest[1], [1.0, longest[0] - 1.2, 0.2]],
            [140.0, 30.0, [1.0, 138.8, 0.2]]],
        "daemon_rss_kb": 18_432,
        "warm_capture": [{"ok": True, "capture_ms": 9876.5}],
        "shim_counters": {"traces_completed": 4, "daemon_reconnects": 0,
                          "last_error": None, "steps": 300}}


def read_all(run: dict) -> dict:
    return {name: READERS[name].read(run) for name in NEW}


def test_each_new_reader_on_a_record_written_here():
    assert read_all(record()) == {
        "tpu_tick_ms_p50": pytest.approx(25.5),  # 6..45
        "tpu_tick_ms_max": pytest.approx(45.0),
        "kernel_tick_ms_p50": pytest.approx(0.8),
        "longest_pass_tick_overlap_ms": pytest.approx(15.0),  # k = 9
        "rpc_verb_ms": pytest.approx(0.4),      # 0.2, 0.4, 0.9; not the warm 7
        "ipc_handoff_ms": pytest.approx(0.3),
        "ipc_timeout_wakeup_pct": pytest.approx(20.0),  # 40 of 200
        "daemon_rss_mb": pytest.approx(18.0),
        "first_capture_ms": 9876.5}


@pytest.mark.parametrize("longest, want", [
    ((1500.0, 10.0), 15.0),   # 8.5-10.0 s: the ticks at 8.3 (ends 8.314,
                              # beside it) and 9.3 (15 ms, under it)
    ((1500.0, 9.81), 15.0),   # 8.31-9.81 s: the tick at 8.3 ends 8.314, so
                              # 4 ms of its 14 lie under the pass; 9.3 too
    ((134.0, 9.25), 0.0),     # 9.116-9.25 s: between two ticks, beside both
    ((134.0, 9.31), 15.0),    # 9.176-9.31 s: the tick at 9.3 began under it
    ((134.0, 8.31), 14.0),    # 8.176-8.31 s: the tick's length, 14, not the
                              # 10 ms of it that lie under the pass
])
def test_longest_pass_with_a_tick_under_it_beside_it_and_none(longest, want):
    run = record(longest=longest)
    got = READERS["longest_pass_tick_overlap_ms"].read(run)
    assert got == pytest.approx(want)
    over = selftrace.spans_over(run, run["longest_passes"][0])
    ticks = [row for row in over if row[0] == selftrace.TPU_TICK]
    assert bool(ticks) == bool(want)
    assert selftrace.IPC_SLICE not in {row[0] for row in over}


def test_a_run_with_no_tick_at_all_reads_zero_not_nothing():
    run = record()
    run["selftrace"]["spans"] = [
        s for s in run["selftrace"]["spans"] if s["name"] != selftrace.TPU_TICK]
    assert READERS["longest_pass_tick_overlap_ms"].read(run) == 0.0
    assert READERS["tpu_tick_ms_p50"].read(run) is None
    assert READERS["tpu_tick_ms_max"].read(run) is None


def test_a_ring_that_wrapped_before_the_window_opened_harms_nothing():
    """9000 recorded into a ring of 4096: the oldest the ring still holds
    began 20 s before the window, so every span of the window is there."""
    run = record(first_tick=-20)
    assert run["selftrace"]["spans_recorded"] > run["selftrace"]["ring_capacity"]
    assert run["selftrace_oldest_ms"] < W * 1e3
    assert None not in read_all(run).values()


def test_a_ring_whose_oldest_span_is_younger_than_the_window_reads_nothing():
    """The ring wrapped inside the window: its oldest span began 5 s after
    the window opened. Every reader of spans returns None; the counters of
    the same reply, the RSS and the warm capture are whole all the same."""
    run = record(first_tick=5)
    assert run["selftrace_oldest_ms"] == pytest.approx((W + 5.0) * 1e3)
    got = read_all(run)
    assert {name: got[name] for name in SPAN_READERS} == dict.fromkeys(
        SPAN_READERS)
    assert selftrace.spans_over(run, run["longest_passes"][0]) is None
    assert got["ipc_timeout_wakeup_pct"] == pytest.approx(20.0)
    assert got["daemon_rss_mb"] and got["first_capture_ms"]


def test_a_record_without_the_hook_reads_nothing_and_does_not_raise():
    """An untraced run's record, or the parent's: none of the keys."""
    run = {"window_start": W, "window_end": W + 40.0, "captures": []}
    assert read_all(run) == dict.fromkeys(NEW)
    run["warm_capture"] = [{"ok": False, "error": "no manifest within 30 s"}]
    run["selftrace"] = {"error": "dyno selftrace exit 2"}
    run["selftrace_oldest_ms"] = None
    run["longest_passes"] = [[140.0, 3.0, [1, 138, 1]]]
    assert read_all(run) == dict.fromkeys(NEW)


# ------------------------------------------------------------- the hook


def test_a_recorded_reply_parses_into_spans_and_the_two_counters():
    """data/selftrace-reply.json is `dyno selftrace`'s standard output after
    a traced capture rehearsal on the CPU (fake backend, four devices)."""
    text = (DATA / "selftrace-reply.json").read_text()
    found = selftrace.parse(text)
    doc = json.loads(text)
    assert len(found["spans"]) == len(doc["traceEvents"]) > 100
    assert found["ipc_wakeups"].keys() == {"message", "posted", "timeout"}
    assert found["tpu_rows"] == 4 and found["ring_capacity"] == 4096
    assert found["spans_recorded"] == len(found["spans"])
    names = {s["name"] for s in found["spans"]}
    assert {selftrace.TPU_TICK, selftrace.KERNEL_TICK, selftrace.HANDOFF,
            selftrace.CAPTURE_VERB, "shim.capture"} <= names
    first = found["spans"][0]
    assert set(first) == {"name", "ts", "dur", "pid", "tid", "args"}
    assert first["ts"] > 1.7e15 and "trace_id" in first["args"]
    verbs = sum(s["name"] == selftrace.CAPTURE_VERB for s in found["spans"])
    handoffs = sum(s["name"] == selftrace.HANDOFF for s in found["spans"])
    assert verbs == handoffs >= 2  # one of each a capture
    assert selftrace.oldest_ms(found) == min(
        e["ts"] for e in doc["traceEvents"]) / 1e3
    with pytest.raises(ValueError):
        selftrace.parse("selftrace: daemon unreachable")
    with pytest.raises(KeyError):
        selftrace.parse('{"status": "failed"}')


class Client:
    traces_completed, daemon_reconnects, last_error, _step_count = 3, 1, None, 77


class Answering:
    def __init__(self, proc):
        self.proc = proc

    def dyno(self, *args, timeout=60):
        assert args == ("selftrace",)
        if isinstance(self.proc, Exception):
            raise self.proc
        return self.proc


def hooked(proc) -> dict:
    run = harness.Run(rehearsal.toy_cell("steady"), 7, 4.0, True, 0.0)
    run.daemon, run.client = Answering(proc), Client()
    run.record.update(window_start=W, window_end=W + 4.0)
    run.read_journals()
    return run.record


def test_the_hook_keeps_the_reply_and_the_shims_counters():
    text = (DATA / "selftrace-reply.json").read_text()
    rec = hooked(subprocess.CompletedProcess([], 0, stdout=text, stderr=""))
    assert rec["selftrace"] == selftrace.parse(text)
    assert rec["selftrace_oldest_ms"] == selftrace.oldest_ms(rec["selftrace"])
    assert rec["shim_counters"] == {
        "traces_completed": 3, "daemon_reconnects": 1, "last_error": None,
        "steps": 77}
    assert rec["phases"]["journals_s"] >= 0


@pytest.mark.parametrize("proc", [
    subprocess.CompletedProcess(
        [], 2, stdout="", stderr="selftrace: daemon unreachable\n"),
    subprocess.CompletedProcess([], 0, stdout="not json", stderr=""),
    subprocess.CompletedProcess([], 0, stdout='{"traceEvents": []}', stderr=""),
    subprocess.TimeoutExpired(["dyno", "selftrace"], 30),
    FileNotFoundError("build/src/dyno"),
], ids=["exit-2", "not-json", "no-counters", "timeout", "no-binary"])
def test_an_unreachable_daemon_costs_nothing_but_the_readers_values(proc):
    rec = hooked(proc)
    assert set(rec["selftrace"]) == {"error"} and rec["selftrace"]["error"]
    assert rec["selftrace_oldest_ms"] is None
    assert rec["shim_counters"]["steps"] == 77
    rec.update(longest_passes=[[140.0, 3.0, [1, 138, 1]]], daemon_rss_kb=9000)
    got = read_all(rec)
    assert {n: got[n] for n in SPAN_READERS + ("ipc_timeout_wakeup_pct",)} == (
        dict.fromkeys(SPAN_READERS + ("ipc_timeout_wakeup_pct",)))
    assert got["daemon_rss_mb"] == pytest.approx(9000 / 1024)
    harness.report_journals(rec)  # prints the error, raises nothing


def test_rss_is_read_from_proc_status(tmp_path):
    import os

    class Proc:
        pid = os.getpid()

    daemon = harness.Daemon.__new__(harness.Daemon)
    daemon.proc = Proc()
    assert daemon.rss_kb() > 1000  # this interpreter, in kB
    Proc.pid = 2 ** 22 + 12345  # beyond pid_max: no such process
    assert daemon.rss_kb() is None
