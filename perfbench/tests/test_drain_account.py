"""The four readers of the shim's account of `ProfilerSession(opts)` and
`stop()` (PR 29), on the hand-built records of test_span_metrics.py: the
capture there holds `shim.profiler_start` 60 ms and `shim.collect` 300 ms,
and the numbers below are written out, not computed by the program."""

import sys

import pytest

import cells
import test_span_metrics as base

sys.path.insert(0, str(cells.ROOT / "tests"))

NEW = ("xspan.xspace_metadata_pct", "xspan.xstart_cpu_pct",
       "xspan.xstop_cpu_pct", "xspan.xstop_others_cpu_ms")
READERS = cells.load_readers()
CAPTURE_CELLS = ["olmo2-1b.capture", "olmo2-7b-2l.capture",
                 "olmo2-13b-v5e4.capture"]
PARTS = ("lines", "event_metadata", "stat_metadata", "stats", "other")


def accounted(tmp_path, **kwargs) -> dict:
    """test_span_metrics' run, its k-th capture's calls accounted: the
    start spent 6 + k ms of its 60 on this thread's CPU, the drain 240 +
    3 k of its 300, while other threads spent 30 + k."""
    run = base.record(tmp_path, **kwargs)
    for k, capture in enumerate(run["captures"]):
        capture["manifest"]["timing"].update(
            profiler_start_cpu_us=6_000 + 1_000 * k,
            profiler_start_proc_cpu_us=7_000 + 1_000 * k,
            collect_cpu_us=240_000 + 3_000 * k,
            collect_proc_cpu_us=270_000 + 4_000 * k,
            collect_nvcsw=3, collect_nivcsw=0, collect_minflt=40)
    return run


def read(run: dict) -> dict:
    return {name: READERS[name].read(run) for name in NEW}


def test_a_number_from_every_reader(tmp_path):
    got = read(accounted(tmp_path, traced=2))
    assert got["xspan.xstart_cpu_pct"] == pytest.approx(100 * 7 / 60)
    assert got["xspan.xstop_cpu_pct"] == pytest.approx(100 * 243 / 300)
    assert got["xspan.xstop_others_cpu_ms"] == pytest.approx(31.0)
    assert 0.0 < got["xspan.xspace_metadata_pct"] < 100.0


def test_the_parents_manifests_read_as_nothing(tmp_path):
    """No account in `timing`: the three manifest readers return None and
    do not raise; the artifact is the same under either program, so its
    content is read as before."""
    got = read(base.record(tmp_path, traced=2))
    assert [got[name] for name in NEW[1:]] == [None, None, None]
    assert got[NEW[0]] is not None
    # no trace in the record (an untraced run, a steady cell): nothing
    assert read(base.record(tmp_path / "x"))[NEW[0]] is None
    # a program that wrote no spans at all
    run = accounted(tmp_path / "y")
    for capture in run["captures"]:
        del capture["manifest"]["spans"]
    assert read(run)["xspan.xstart_cpu_pct"] is None
    assert read(run)["xspan.xstop_others_cpu_ms"] == pytest.approx(31.0)


def test_a_failed_capture_is_left_out(tmp_path):
    run = accounted(tmp_path)
    run["captures"][0] = {"k": 0, "ok": False, "spawn_t": 1.0,
                          "error": "no manifest within 30 s"}
    got = read(run)
    assert got["xspan.xstop_cpu_pct"] == pytest.approx(100 * 244.5 / 300)
    assert got["xspan.xstop_others_cpu_ms"] == pytest.approx(31.5)


def artifacts() -> dict:
    import xspace_fixture

    return {
        "fixture": xspace_fixture.build_xspace(planes=2, events_per_line=40),
        "capture": base.xspace(base.BASE_US)}


@pytest.mark.parametrize("which", ["fixture", "capture"])
def test_the_readers_account_is_the_products(tmp_path, which):
    """As C3 holds the two reducers: what each plane's bytes are made of,
    by the benchmark's wire walk and ProfileData, against the plane table
    of dynolog_tpu.trace."""
    from dynolog_tpu import trace

    path = tmp_path / "host.xplane.pb"
    path.write_bytes(artifacts()[which])
    ours = READERS[NEW[0]].account(str(path))
    theirs = trace.summarize(str(path))["planes"]
    keys = ("name", "bytes", "lines", "events", "event_metadata",
            *(f"{part}_bytes" for part in PARTS))
    assert [{k: row[k] for k in keys} for row in ours] == [
        {k: row[k] for k in keys} for row in theirs]
    for row in ours:
        assert sum(row[f"{part}_bytes"] for part in PARTS) == row["bytes"]
    size = path.stat().st_size
    in_lines = sum(row["lines_bytes"] for row in ours)
    assert READERS[NEW[0]].read({"trace": {"path": str(path)}}) == (
        pytest.approx(100.0 * (size - in_lines) / size))


def test_the_four_readers_are_capture_readers_of_the_shim():
    for name in NEW:
        reader = READERS[name]
        assert (reader.CELLS, reader.LAYER, reader.MOVES) == (
            ('capture',), "shim capture", "capture_ms_p50")


def test_the_table_holds_the_four_entries_their_files_generate():
    table = {m["name"]: m for m in cells.load_benchmark()["per_layer"]}
    for name in NEW:
        reader = READERS[name]
        assert table[name] == {
            "name": reader.NAME, "unit": reader.UNIT, "better": reader.BETTER,
            "source": reader.SOURCE, "layer": reader.LAYER,
            "moves": reader.MOVES, "workloads": CAPTURE_CELLS}
