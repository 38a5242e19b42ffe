"""A due metric that prints nothing refuses a PR, so every reader due in a
cell has to return a value on a run of that cell's kind. The records are
the chip's: data/record-capture.json and data/record-steady.json are
`perfbench/out/<cell>-<seed>-t1.json` of one traced run each (steady: PR 31;
capture: PR 32, with the derived files' mtimes and the `trace.convert`
spans), slimmed as their `_fixture` key says. The harness deletes a run's
artifacts, so the capture the record names as reduced gets the small
artifact of test_span_metrics.py, laid on that capture's own clock.
"""

import json

import pytest

import cells
import test_span_metrics as base

DATA = cells.HERE / "tests" / "data"
BENCH = cells.load_benchmark()
READERS = cells.load_readers()
END_TO_END = cells.load_end_to_end()
CASES = [(w["name"], name) for w in BENCH["workloads"]
         for name in cells.metric_names(
             BENCH, cells.load_cell(w["name"]), "per_layer")]


@pytest.fixture(scope="module")
def records(tmp_path_factory) -> dict:
    """Traffic kind -> a recorded run of that kind, artifacts in place."""
    out = {}
    for kind in cells.TRAFFIC_KINDS:
        with open(DATA / f"record-{kind}.json") as f:
            rec = json.load(f)
        assert rec["kind"] == kind and rec["traced"]
        out[kind] = rec
    rec = out["capture"]
    root = tmp_path_factory.mktemp("artifacts")
    reduced = next(c for c in rec["captures"] if c["ok"] and rec["trace"][
        "path"].startswith(c["manifest"]["trace_dir"] + "/"))
    folder = root / "plugins" / "profile" / "r"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    path.write_bytes(base.xspace(int(reduced["spawn_t"] * 1e6)))
    reduced["manifest"]["trace_dir"] = str(root)
    rec["trace"]["path"] = str(path)
    return out


@pytest.mark.parametrize("cell, name", CASES)
def test_every_reader_due_in_a_cell_returns_a_value(records, cell, name):
    kind = cells.load_cell(cell).kind
    value = READERS[name].read(records[kind])
    assert value is not None, f"{name} is due in {cell} and read nothing"
    assert float(value) == float(value)  # a number, and not NaN


@pytest.mark.parametrize("cell, name", [
    (w["name"], name) for w in BENCH["workloads"] for name in END_TO_END
    if name in cells.metric_names(
        BENCH, cells.load_cell(w["name"]), "end_to_end")])
def test_every_end_to_end_file_due_in_a_cell_returns_a_value(
        records, cell, name):
    import harness

    rec = records[cells.load_cell(cell).kind]
    value = END_TO_END[name].read(rec)
    assert value is not None and float(value) > 0, (cell, name)
    assert harness.end_to_end(rec)[name] == value


def test_the_recorded_capture_run_holds_every_conversion(records):
    """The children are waited for before the journal is read: one
    `trace.convert` span a capture of the window and the warm one's, each
    capture kept has both files' mtimes, and the wait to the later of them
    is the capture, the lag, the conversion and a rename."""
    import selftrace

    rec = records["capture"]
    begun = selftrace.convert_starts_us(rec)
    assert len(begun) == rec["_fixture"]["captures_in_window"] + 1
    assert len(selftrace.window_ms(rec, selftrace.CONVERT)) == (
        rec["_fixture"]["captures_in_window"])
    assert len(rec["derived_ms"]) == rec["_fixture"]["captures_in_window"]
    for cap in rec["captures"]:
        span_us = begun[cap["manifest"]["trace_ctx"].split("/")[0]]
        lag_ms = span_us / 1e3 - cap["done_t"] * 1e3
        assert 50 < lag_ms < 1000
        assert cap["derived_ms"] > cap["capture_ms"] + lag_ms
        assert cap["derived"]["tmp"] == []
        assert cap["derived"][".summary.json"]["mtime"] < (
            cap["derived"][".trace.json.gz"]["mtime"])
    assert READERS["convert_alive_max"].read(rec) >= 1


def test_the_table_lists_no_reader_in_a_cell_of_a_kind_it_does_not_read():
    for cell, name in CASES:
        assert cells.load_cell(cell).kind in READERS[name].CELLS, (cell, name)


def test_the_recorded_runs_hold_what_the_issue_asked_of_the_hook(records):
    """About one TPU tick a second of the window, one verb and one hand-off
    a capture, the counters; one row a chip."""
    import selftrace

    for kind, rec in records.items():
        ticks = selftrace.window_ms(rec, selftrace.TPU_TICK)
        assert abs(len(ticks) - rec["window_s"]) <= 3, kind
        assert rec["selftrace"]["tpu_rows"] == rec["device"]["count"]
        assert set(rec["selftrace"]["ipc_wakeups"]) == {
            "message", "posted", "timeout"}
        assert rec["selftrace_oldest_ms"] < rec["window_start"] * 1e3
        assert rec["shim_counters"]["daemon_reconnects"] == 0
    rec = records["capture"]
    in_window = rec["_fixture"]["captures_in_window"]
    assert len(selftrace.window_ms(rec, selftrace.CAPTURE_VERB)) == in_window
    assert len(selftrace.window_ms(rec, selftrace.HANDOFF)) == in_window
