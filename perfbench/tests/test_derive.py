"""What the export child writes beside a capture: the end-to-end metric
`derived_ms_p50`, the four readers of the `derive` layer and check C5, on
records and artifacts written here: the numbers below are written out, not
computed by the program.

The window opens at W = 1 790 000 000 s and lasts 40 s. Four captures, one
every 2 s from W + 1 s; the k-th takes 900 ms to its manifest, its export
child's `trace.convert` span begins 200 + 10 k ms after the manifest and
lasts 2500 + 100 k ms, and the later of its two files is renamed 5 ms after
the span ends: derived_ms = 900 + 200 + 10 k + 2500 + 100 k + 5. A child is
alive for 2.7-3.0 s of a 2 s period, so two convert side by side.
"""

import gzip
import json
import types

import pytest

import cells
import checks
import harness
import selftrace
import test_span_metrics as base
import xplane

W = 1_790_000_000.0
READERS = cells.load_readers()
END_TO_END = cells.load_end_to_end()
LAYER = ("convert_alive_max", "convert_lag_ms", "convert_ms", "derived_bytes")


def capture(k: int) -> dict:
    spawn = W + 1.0 + 2.0 * k
    done = spawn + 0.9
    begun = done + 0.2 + 0.01 * k
    ended = begun + 2.5 + 0.1 * k
    cap = {"k": k, "ok": True, "spawn_t": spawn, "done_t": done,
           "capture_ms": 900.0,
           "manifest": {"status": "ok", "trace_ctx": f"{k:016x}/{9:016x}"},
           "_span": {"name": selftrace.CONVERT, "ts": round(begun * 1e6),
                     "dur": round((ended - begun) * 1e6), "pid": 100 + k,
                     "tid": 100 + k, "args": {"trace_id": f"{k:016x}"}},
           "derived": {
               ".summary.json": {"path": "s", "mtime": ended - 1.0,
                                 "bytes": 20_000 + k},
               ".trace.json.gz": {"path": "t", "mtime": ended + 0.005,
                                  "bytes": 400_000 + 10 * k},
               "tmp": []},
           "derived_ms": (ended + 0.005 - spawn) * 1e3}
    return cap


def record(captures: int = 4) -> dict:
    caps = [capture(k) for k in range(captures)]
    spans = [c.pop("_span") for c in caps]
    # a tick before the window, so the ring reaches back to its opening; the
    # warm capture's conversion, over before the window opened
    spans.append({"name": selftrace.TPU_TICK, "ts": round((W - 20) * 1e6),
                  "dur": 5000, "pid": 7, "tid": 8, "args": {}})
    spans.append({"name": selftrace.CONVERT, "ts": round((W - 9) * 1e6),
                  "dur": 7_000_000, "pid": 99, "tid": 99,
                  "args": {"trace_id": "f" * 16}})
    found = {"spans": spans, "spans_recorded": len(spans),
             "ring_capacity": 4096}
    return {"window_start": W, "window_end": W + 40.0, "window_s": 40.0,
            "captures": caps, "capture_ms": [900.0] * captures,
            "derived_ms": [c["derived_ms"] for c in caps],
            "selftrace": found,
            "selftrace_oldest_ms": selftrace.oldest_ms(found)}


def read_all(run: dict) -> dict:
    return {name: READERS[name].read(run) for name in LAYER}


def test_the_metric_and_the_four_readers_on_a_written_record():
    run = record()
    # 3605 + 110 k for k = 0..3: the median of 3605, 3715, 3825, 3935
    assert END_TO_END["derived_ms_p50"].read(run) == pytest.approx(3770.0)
    got = read_all(run)
    assert got == {
        "convert_ms": pytest.approx(2650.0),      # 2500 + 100 k
        "convert_lag_ms": pytest.approx(215.0, abs=1e-3),   # 200 + 10 k
        "convert_alive_max": 2,
        "derived_bytes": pytest.approx(420_016.5)}  # 420 000 + 11 k
    assert harness.end_to_end(dict(
        run, setup_s=20.0, step_ms=[100.0] * 400))["derived_ms_p50"] == (
        pytest.approx(3770.0))


@pytest.mark.parametrize("name", LAYER)
def test_each_reader_is_of_the_derive_layer_and_moves_the_metric(name):
    reader = READERS[name]
    assert (reader.LAYER, reader.MOVES, reader.CELLS) == (
        "derive", "derived_ms_p50", ("capture",))
    # every capture cell owes it: no configuration says it cannot be read
    assert not [c["name"] for c in cells.load_benchmark()["configs"]
                if name in cells.load_config(c["name"]).get("no_reading", {})]


def test_children_that_never_meet_read_one_alive_and_an_end_is_no_overlap():
    run = record()
    for span in run["selftrace"]["spans"]:
        if span["name"] == selftrace.CONVERT:
            span["dur"] = 1_000_000
    assert READERS["convert_alive_max"].read(run) == 1
    # the second begins at the instant the first ends: still one
    first, second = [s for s in run["selftrace"]["spans"]
                     if s["name"] == selftrace.CONVERT][:2]
    first["dur"] = second["ts"] - first["ts"]
    assert READERS["convert_alive_max"].read(run) == 1


def test_a_conversion_open_as_the_window_opens_counts_for_its_part():
    """The warm capture's child began 9 s before the window. Ending 1.5 s
    into it, it meets no span of the window (the first opens at W + 2.1 s);
    ending 6 s into it, it lies over the first and the second capture's."""
    run = record()
    warm = next(s for s in run["selftrace"]["spans"]
                if s["args"].get("trace_id") == "f" * 16)
    warm["dur"] = 10_500_000
    assert READERS["convert_alive_max"].read(run) == 2
    warm["dur"] = 15_000_000  # to W + 6 s: over k = 0 (2.1-4.6) and k = 1 (4.11-6.71)
    assert READERS["convert_alive_max"].read(run) == 3
    # it began before the window: not one of the window's conversions
    assert READERS["convert_ms"].read(run) == pytest.approx(2650.0)


def test_a_capture_whose_child_failed_is_left_out_and_none_reads_nothing():
    run = record()
    lost = run["captures"][3]
    del lost["derived_ms"]
    lost["derived"][".trace.json.gz"] = None
    run["derived_ms"] = [c["derived_ms"] for c in run["captures"][:3]]
    run["selftrace"]["spans"] = [
        s for s in run["selftrace"]["spans"]
        if s["args"].get("trace_id") != f"{3:016x}"]
    assert END_TO_END["derived_ms_p50"].read(run) == pytest.approx(3715.0)
    got = read_all(run)
    assert got["convert_lag_ms"] == pytest.approx(210.0, abs=1e-3)
    assert got["derived_bytes"] == pytest.approx(420_011.0)
    # every child failed: no file, no span, and no reader invents a number
    for cap in run["captures"]:
        cap.pop("derived_ms", None)
    run["derived_ms"] = []
    run["selftrace"]["spans"] = [
        s for s in run["selftrace"]["spans"] if s["name"] != selftrace.CONVERT]
    assert END_TO_END["derived_ms_p50"].read(run) is None
    assert read_all(run) == dict.fromkeys(LAYER)
    assert harness.end_to_end(dict(
        run, setup_s=20.0, step_ms=[100.0] * 400))["derived_ms_p50"] is None


def test_a_ring_that_does_not_reach_the_window_reads_nothing():
    run = record()
    run["selftrace_oldest_ms"] = (W + 5) * 1e3
    got = read_all(run)
    assert got["convert_ms"] is None and got["convert_lag_ms"] is None
    assert got["convert_alive_max"] is None
    assert got["derived_bytes"] is not None  # the files', not the journal's


def test_the_parent_of_this_pr_reads_nothing_and_does_not_raise():
    """Its record has neither `derived_ms` nor `derived`."""
    run = record()
    del run["derived_ms"]
    for cap in run["captures"]:
        del cap["derived"], cap["derived_ms"]
    assert END_TO_END["derived_ms_p50"].read(run) is None
    assert READERS["derived_bytes"].read(run) is None


# ------------------------------------------------------------------ C5


def artifacts(tmp_path, planes: int = 1):
    """One capture's folder as the shim leaves it: the artifact, and what
    the product's two writers put beside it (called one by one:
    `write_derived_artifacts` would leave a `trace.convert` span in this
    process's buffer for the next rehearsal's shim to flush)."""
    from dynolog_tpu import trace as product

    folder = tmp_path / "cap000_1" / "plugins" / "profile" / "r"
    folder.mkdir(parents=True)
    path = folder / "host.xplane.pb"
    if planes == 1:
        path.write_bytes(base.xspace(int(W * 1e6)))
    else:
        import test_v5e4

        path.write_bytes(open(test_v5e4.xspace_file(
            tmp_path, planes, 1), "rb").read())
    product.write_summary_json(str(path))
    product.write_chrome_trace_gz(str(path))
    cap = {"k": 0, "ok": True, "spawn_t": W, "xplane_path": str(path),
           "manifest": {"trace_dir": str(tmp_path / "cap000_1")}}
    run = types.SimpleNamespace(
        record={"captures": [cap]}, cell=types.SimpleNamespace(chips=planes),
        summarized=(cap, xplane.load(str(path))))
    return run, cap, folder


def c5(run) -> dict:
    for cap in run.record["captures"]:
        checks.read_derived(cap)
    return checks.check_c5(run)


def failed_parts(check: dict) -> list:
    return [p["what"] for p in check["compared"] if not p["ok"]]


@pytest.mark.parametrize("planes", [1, 4])
def test_c5_holds_on_what_the_product_writes(tmp_path, planes):
    run, cap, _ = artifacts(tmp_path, planes)
    check = c5(run)
    assert check["name"] == "C5" and check["ok"], failed_parts(check)
    assert cap["derived_ms"] > 0 and cap["derived"]["tmp"] == []
    whats = [p["what"] for p in check["compared"]]
    # per device plane, its events on "XLA Ops" against the plain count
    for i in range(planes):
        assert any(f"/device:TPU:{i}" in w for w in whats)
    worst = check["compared"][-1]
    assert worst["value"] <= 0.0005 + 1e-12 and worst["limit"] == "<= 0.001"


def rewrite_summary(folder, edit) -> None:
    path = folder / "host.summary.json"
    doc = json.loads(path.read_text())
    edit(doc)
    path.write_text(json.dumps(doc))


def drop_device_events(folder) -> None:
    path = folder / "host.trace.json.gz"
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    pid = next(e["pid"] for e in doc["traceEvents"] if e.get("ph") == "M"
               and e.get("args", {}).get("name") == "/device:TPU:0")
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if not (e.get("ph") == "X" and e["pid"] == pid)][:-1]
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def truncate(folder) -> None:
    path = folder / "host.trace.json.gz"
    path.write_bytes(path.read_bytes()[:-9])


def one_microsecond_off(doc):
    doc["top_ops"][0]["total_ms"] += 0.002


def one_event_more(doc):
    doc["top_ops"][0]["count"] += 1


FAULTS = {
    "the summary is missing": lambda d: (d / "host.summary.json").unlink(),
    "the Chrome trace is missing": lambda d: (d / "host.trace.json.gz").unlink(),
    "a .tmp is left beside them": lambda d: (
        d / "host.trace.json.gz.tmp").write_bytes(b"x"),
    "the gzip is cut short": truncate,
    "the Chrome trace holds no event of the device plane": drop_device_events,
    "a row's time is two microseconds off": lambda d: rewrite_summary(
        d, one_microsecond_off),
    "a row's count is one off": lambda d: rewrite_summary(d, one_event_more),
    "a row is gone": lambda d: rewrite_summary(
        d, lambda doc: doc["top_ops"].pop()),
    "the summary is not JSON": lambda d: (
        d / "host.summary.json").write_text("{"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_c5_fails_an_answer_altered_where_it_is_produced(tmp_path, fault):
    run, _, folder = artifacts(tmp_path)
    FAULTS[fault](folder)
    check = c5(run)
    assert not check["ok"] and failed_parts(check), fault


def test_c5_owes_nothing_for_a_capture_c1_did_not_find_whole(tmp_path):
    """A capture without a whole artifact is C1's to fail; with no capture
    at all there is nothing to read, and C5 says so."""
    run, cap, _ = artifacts(tmp_path)
    run.record["captures"].append(
        {"k": 1, "ok": False, "spawn_t": W + 2, "error": "no manifest"})
    assert c5(run)["ok"]
    assert "derived" not in run.record["captures"][1]
    del cap["xplane_path"]
    run.summarized = None
    check = c5(run)
    assert not check["ok"] and len(failed_parts(check)) == 2


def test_compare_top_ops_allows_the_rounding_and_the_truncation_alone():
    """1000 events of 1.9994 ns each: the product sums 1 999 400 ps =
    0.002 ms rounded (0.0019994), the plain reducer 1000 whole ns."""
    rows = [{"op": "fusion", "total_ms": 0.002, "count": 1000}]
    differ, worst = checks.compare_top_ops(rows, {"fusion": [1000.0, 1000]})
    assert not differ and worst == pytest.approx(0.0, abs=1e-12)
    differ, worst = checks.compare_top_ops(
        [{"op": "fusion", "total_ms": 0.004, "count": 1000}],
        {"fusion": [1000.0, 1000]})
    assert differ and worst == pytest.approx(0.002)
    differ, _ = checks.compare_top_ops(rows, {"fusion.1": [1000.0, 1000]})
    assert [d[0] for d in differ] == ["fusion", "fusion.1"]


def test_gunzip_to_end_counts_the_bytes_or_says_where_it_broke(tmp_path):
    path = tmp_path / "a.gz"
    with gzip.open(path, "wb") as f:
        f.write(b"x" * 3_000_000)
    assert checks.gunzip_to_end(str(path)) == 3_000_000
    whole = path.read_bytes()
    path.write_bytes(whole[:-4] + b"\0\0\0\0")  # the length of the trailer
    assert "Incorrect length" in checks.gunzip_to_end(str(path))
    path.write_bytes(whole[:-8] + b"\0\0\0\0" + whole[-4:])  # its CRC
    assert "CRC" in checks.gunzip_to_end(str(path))
    path.write_bytes(whole[:100])
    assert "EOFError" in checks.gunzip_to_end(str(path))
    assert "FileNotFoundError" in checks.gunzip_to_end(
        str(tmp_path / "none.gz"))


def test_the_compared_rows_are_json_whatever_was_read():
    rec = {"checks": [
        checks.check("J", [checks.part("gap", float("nan"), "<= 0.05", False),
                           checks.part("loss", 0.001, "<= 0.003", True)]),
        checks.check("C4", [checks.part("gone", None, "<= 120", False)])]}
    rows = harness.compared(rec)
    assert [row[4] for row in rows] == [True, False, False]
    assert json.loads(json.dumps(rows, allow_nan=False))[1][2] == "nan"
