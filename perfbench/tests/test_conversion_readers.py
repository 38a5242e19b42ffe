"""The six readers of the export child's spans (`conversions.py`), on records
written here: every number below is worked out by hand, not by the program.

The window opens at W = 1 790 000 000 s and lasts 40 s. Capture A (trace id
`a...`, two planes) was converted by a pool: `trace.convert` opens 5.000 s
into the window and lasts 400 ms; worker 71 converts plane 1 from +50 to
+250 ms (decode 150 ms), worker 72 plane 2 from +100 to +350 ms (decode 200
ms). The two planes overlap from +100 to +250: their union is 300 ms, their
sum 450. Capture B (`b...`, one plane) was converted by the child itself, pid
80: `trace.convert` 100 ms, the plane +10 to +90 ms, its decode 60 ms. The
shim says of A's child: boot 300 ms, wait 900.5 ms; of B's: boot 340 ms,
wait 500.5 ms.
"""

import pytest

import cells
import conversions
import selftrace

W = 1_790_000_000.0
W_US = int(W * 1e6)
READERS = cells.load_readers()
SIX = ("convert_workers", "convert_plane_ms_max", "convert_decode_ms",
       "convert_overhead_ms", "export_boot_ms", "export_idle_ms")
A, B = "a" * 16, "b" * 16


def span(name: str, trace_id: str, pid: int, start_ms: float,
         ms: float) -> dict:
    return {"name": name, "ts": W_US + round(start_ms * 1e3),
            "dur": round(ms * 1e3), "pid": pid, "tid": pid,
            "args": {"trace_id": trace_id}}


def row(name: str, start_ms: float, ms: float) -> dict:
    return {"name": name, "span_id": "0" * 16, "parent_id": "0" * 16,
            "start_us": W_US + round(start_ms * 1e3),
            "dur_us": round(ms * 1e3)}


def capture(trace_id: str, planes: int, life: list) -> dict:
    return {"ok": True, "manifest": {
        "trace_ctx": f"{trace_id}/{'1' * 16}",
        "planes": [{"name": f"/device:TPU:{i}", "bytes": 1}
                   for i in range(planes)],
        "spans": [row("shim.capture", 0, 1)] + life}}


def journal_of_two() -> list:
    return [
        span("collector.tpu_monitor.tick", "0" * 16, 7, -20_000, 5),
        span(selftrace.CONVERT, A, 70, 5000, 400),
        span(conversions.PLANE, A, 71, 5050, 200),
        span(conversions.DECODE, A, 71, 5051, 150),
        span(conversions.PLANE, A, 72, 5100, 250),
        span(conversions.DECODE, A, 72, 5101, 200),
        span(selftrace.CONVERT, B, 80, 9000, 100),
        span(conversions.PLANE, B, 80, 9010, 80),
        span(conversions.DECODE, B, 80, 9011, 60)]


def record(spans: list, captures: list) -> dict:
    found = {"spans": spans, "spans_recorded": len(spans),
             "ring_capacity": 4096}
    return {"window_start": W, "window_end": W + 40.0, "captures": captures,
            "selftrace": found,
            "selftrace_oldest_ms": selftrace.oldest_ms(found)}


def two_captures() -> list:
    return [
        capture(A, 2, [row(conversions.BOOT, 3500, 300),
                       row(conversions.IDLE, 3800, 900.5)]),
        capture(B, 1, [row(conversions.BOOT, 8000, 340),
                       row(conversions.IDLE, 8340, 500.5)])]


def read_six(run: dict) -> dict:
    return {name: READERS[name].read(run) for name in SIX}


def test_two_captures_one_under_a_pool_read_what_a_hand_computes():
    assert read_six(record(journal_of_two(), two_captures())) == {
        "convert_workers": pytest.approx(1.5),        # 2 and 1
        "convert_plane_ms_max": pytest.approx(165.0),  # 250 and 80
        "convert_decode_ms": pytest.approx(205.0),     # 150 + 200, and 60
        # A: 400 less the UNION of its planes, 300 (their sum is 450, which
        # would read -50); B: 100 - 80
        "convert_overhead_ms": pytest.approx(60.0),    # 100 and 20
        "export_boot_ms": pytest.approx(320.0),        # 300 and 340
        "export_idle_ms": pytest.approx(700.5)}        # 900.5 and 500.5


def test_a_program_that_records_none_of_the_spans_reads_none_in_all_six():
    """The parent's shape: `trace.convert` alone in the journal, manifests
    with `planes` and the shim's own spans, no `export.*` among them."""
    parent = [s for s in journal_of_two()
              if s["name"] not in (conversions.PLANE, conversions.DECODE)]
    run = record(parent, [capture(A, 2, []), capture(B, 1, [])])
    assert read_six(run) == dict.fromkeys(SIX)
    # and so does a manifest from before the shim listed its spans
    for c in run["captures"]:
        del c["manifest"]["spans"]
    assert read_six(run) == dict.fromkeys(SIX)


@pytest.mark.parametrize("dropped", [
    conversions.PLANE, conversions.DECODE, selftrace.CONVERT])
def test_a_capture_whose_spans_were_dropped_on_the_wire_is_left_out(dropped):
    """One datagram of A's never arrived: A is not read as a conversion of
    one worker or of no overhead; the medians are B's alone."""
    spans = journal_of_two()
    spans.remove(next(s for s in spans if s["name"] == dropped
                      and s["args"]["trace_id"] == A))
    got = read_six(record(spans, two_captures()))
    assert got["convert_workers"] == 1.0
    assert got["convert_plane_ms_max"] == pytest.approx(80.0)
    assert got["convert_decode_ms"] == pytest.approx(60.0)
    assert got["convert_overhead_ms"] == pytest.approx(20.0)
    # the shim's two are in the manifest and were not on that wire
    assert got["export_boot_ms"] == pytest.approx(320.0)


def test_cold_and_failed_captures_are_not_counted():
    captures = two_captures()
    captures[1]["manifest"]["spans"] = [row("shim.capture", 0, 1)]  # cold
    captures.append(dict(capture("c" * 16, 1, []), ok=False))
    got = read_six(record(journal_of_two(), captures))
    assert got["export_boot_ms"] == pytest.approx(300.0)
    assert got["export_idle_ms"] == pytest.approx(900.5)
    assert got["convert_workers"] == pytest.approx(1.5)


def test_a_journal_that_does_not_reach_the_windows_opening_reads_none():
    spans = journal_of_two()[1:]  # the oldest span left began in the window
    got = read_six(record(spans, two_captures()))
    for name in SIX[:4]:
        assert got[name] is None, name
    assert got["export_boot_ms"] == pytest.approx(320.0)  # manifests, no ring


def test_union_counts_an_instant_once():
    def at(*pairs):
        return [{"ts": lo, "dur": hi - lo} for lo, hi in pairs]

    assert conversions.union_us([]) == 0
    assert conversions.union_us(at((0, 10), (10, 20))) == 20   # end to end
    assert conversions.union_us(at((0, 10), (2, 5))) == 10     # one inside
    assert conversions.union_us(at((5, 15), (0, 10), (30, 31))) == 16


def test_the_table_holds_the_six_for_every_capture_cell():
    bench = cells.load_benchmark()
    capture_cells = [w["name"] for w in bench["workloads"]
                     if cells.load_traffic(w["traffic"])["kind"] == "capture"]
    held = {m["name"]: m for m in bench["per_layer"]}
    for name in SIX:
        assert held[name]["layer"] == "derive"
        assert held[name]["moves"] == "derived_ms_p50"
        assert held[name]["workloads"] == capture_cells


def test_a_real_daemon_and_shim_close_the_account(monkeypatch, tmp_path):
    """A toy job on the CPU beside the real daemon: every capture's
    conversion is in the journal whole, the six readers print a number, and
    the spans add up as docs/OBSERVABILITY.md says they do."""
    import rehearsal

    run, _ = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seconds=3.0, trace=True)
    rec = run.record
    assert len(rec["captures"]) >= 2
    found = conversions.conversions(rec)
    assert len(found) == len(rec["captures"])
    for name, value in read_six(rec).items():
        assert value is not None and value >= 0, name
    by_trace = {c["convert"]["args"]["trace_id"]: c for c in found}
    for cap in rec["captures"]:
        manifest = cap["manifest"]
        whole = by_trace[manifest["trace_ctx"].split("/")[0]]
        convert = whole["convert"]
        assert len(whole["planes"]) == len(manifest["planes"])
        decodes = {s["args"]["parent_id"]: s for s in whole["decodes"]}
        for plane in whole["planes"]:
            assert plane["args"]["parent_id"] == convert["args"]["span_id"]
            assert convert["ts"] <= plane["ts"]
            assert plane["ts"] + plane["dur"] <= convert["ts"] + convert["dur"]
            decode = decodes[plane["args"]["span_id"]]
            assert plane["ts"] <= decode["ts"]
            assert decode["ts"] + decode["dur"] <= plane["ts"] + plane["dur"]
            assert decode["pid"] == plane["pid"]
        assert convert["dur"] >= conversions.union_us(whole["planes"])
        rows = {r["name"]: r for r in manifest["spans"]}
        boot, idle = rows[conversions.BOOT], rows[conversions.IDLE]
        assert boot["start_us"] + boot["dur_us"] == idle["start_us"]
        assert manifest["timing"]["export_ready_ms"] == idle["dur_us"] // 1000
        # the child wakes from its read and opens its span: nothing between
        assert 0 <= convert["ts"] - (idle["start_us"] + idle["dur_us"]) < 500e3
        # the shim's two went to the daemon too, as the child's
        shipped = {s["name"]: s for s in rec["selftrace"]["spans"]
                   if s["args"]["trace_id"] == convert["args"]["trace_id"]}
        assert shipped[conversions.BOOT]["pid"] == convert["pid"]
        assert shipped[conversions.IDLE]["dur"] == idle["dur_us"]
