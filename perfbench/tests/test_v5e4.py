"""The four-chip configuration as data, and the two readers that arrive
with it, on records written here: one device plane reads 0.0 in both, the
four planes of toy-cpu4's deployment read what was put into them."""

import importlib.util
import json

import pytest

import cells
import rehearsal

NAME = "olmo2-13b-v5e4"
CELL = "olmo2-13b-v5e4.capture"
MS = 1_000_000_000  # picoseconds in a millisecond


@pytest.fixture(scope="module")
def readers():
    return cells.load_readers()


def test_configuration_loads_and_its_mesh_multiplies_to_its_chips():
    config = cells.load_config(NAME)
    deployment = config["deployment"]
    assert deployment["chips"] == 4 and deployment["processes"] == 1
    assert deployment["mesh"] == {"data": 2, "model": 2}
    cell = cells.load_cell(CELL)
    assert cell.chips == 4 and cell.kind == "capture"
    assert cell.traffic_name == "capture-pull"
    assert config["daemon_flags"] == cells.load_config(
        "olmo2-7b-2l-v5e1")["daemon_flags"]
    assert config["shim"] == cells.load_config("olmo2-7b-2l-v5e1")["shim"]


def test_every_published_width_is_kept_and_divides_by_the_model_axis():
    config = cells.load_config(NAME)
    job, published = config["job"], config["published"]
    model = config["deployment"]["mesh"]["model"]
    data = config["deployment"]["mesh"]["data"]
    assert job["d_model"] == published["hidden_size"] == 5120
    assert job["n_heads"] == published["num_attention_heads"] == 40
    assert job["d_model"] // job["n_heads"] == 128
    assert job["d_ff"] == published["intermediate_size"] == 13824
    assert job["vocab_size"] == published["vocab_size"] == 100352
    assert job["seq"] == published["max_position_embeddings"] == 4096
    assert job["rope_theta"] == published["rope_theta"] == 500000
    for width in ("n_heads", "d_ff", "vocab_size", "d_model"):
        assert job[width] % model == 0, width
    assert job["batch"] % data == 0
    assert 4 <= job["n_layers"] < published["num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers", "batch"]
    assert f"chosen {job['n_layers']}" in config["aot"]


def test_source_and_reduced_differ_from_every_other_configuration():
    """The driver tells configurations apart by `source` and `reduced`: PR 27
    was refused for a four-chip file with the 7B's of both."""
    bench = cells.load_benchmark()
    mine = next(c for c in bench["configs"] if c["name"] == NAME)
    assert mine == bench["configs"][-1]
    assert mine["source"] == cells.load_config(NAME)["source"]
    assert mine["reduced"] == cells.load_config(NAME)["reduced"]
    for other in bench["configs"][:-1]:
        assert other["source"] != mine["source"], other["name"]
        assert other["reduced"] != mine["reduced"], other["name"]
        assert other["file"] != mine["file"]
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == [CELL] and bench["workloads"][-1]["name"] == CELL


def test_every_capture_reader_lists_the_cell_but_the_one_it_cannot_read():
    """The configuration names `step_ms_p95.capture` under `no_reading`: a
    window of `run_seconds` holds 109 steps of 366 ms, five beyond their
    p95, and `stats.tail` prints no tail with fewer than ten beyond it. The
    driver's first check of PR 28 refused the cell for that one name; until
    PR 31 the table left it out by a hand edit, and until PR 33 the reader
    named the cell."""
    import stats

    bench = cells.load_benchmark()
    unread = cells.load_config(NAME)["no_reading"]
    assert list(unread) == ["step_ms_p95.capture"] and "109" in (
        unread["step_ms_p95.capture"])
    readers = cells.load_readers()
    for entry in bench["per_layer"]:
        if "capture" in readers[entry["name"]].CELLS:
            listed = CELL in entry.get("workloads", [CELL])
            assert listed == (entry["name"] not in unread), entry["name"]
    steps = [366.4] * 109
    with pytest.raises(stats.TooFewSamples):
        stats.tail(steps, 0.95, bench["run_seconds"] * 1e3)


# ------------------------------------------------------------ the readers


def xspace_file(tmp_path, planes: int, collective_ms: int) -> str:
    """`planes` device planes, each 6 ms of fusion and `collective_ms` of
    all-reduce + all-gather on "XLA Ops" (plane 0 twice the collectives)."""
    from jax.profiler import ProfileData

    def ev(meta, offset_ms, dur_ms):
        return (f"events {{ metadata_id: {meta} offset_ps: {offset_ms * MS} "
                f"duration_ps: {dur_ms * MS} }}")

    def meta(i, name):
        return f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'

    text = ""
    for i in range(planes):
        ms = collective_ms * (2 if i == 0 else 1)
        events = ev(1, 0, 6)
        if ms:
            events += ev(2, 6, ms) + ev(3, 6 + ms, ms)
        text += f"""
planes {{ id: {i + 1} name: "/device:TPU:{i}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 {events} }}
  lines {{ id: 2 name: "Async XLA Ops" timestamp_ns: 1000 {ev(4, 0, 50)} }}
  {meta(1, "%fusion.1 = bf16[8,8]{{1,0}} fusion(%p0)")}
  {meta(2, "%all-reduce.2 = bf16[8,8]{{1,0}} all-reduce(%p1)")}
  {meta(3, "%all-gather.3 = bf16[8,8]{{1,0}} all-gather(%p2)")}
  {meta(4, "%all-reduce-start.4 = bf16[8]{{0}} all-reduce-start(%p3)")}
}}"""
    path = tmp_path / f"p{planes}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def record(config: str, path: str | None, plane_bytes: list) -> dict:
    chips = rehearsal.toy_cell("capture-pull", config=config).chips
    captures = [
        {"ok": True, "manifest": {"planes": [
            {"name": "/host:CPU", "bytes": 9_000_000},
            {"name": "Task Environment", "bytes": 40}] + [
            {"name": f"/device:TPU:{i}", "bytes": n + k}
            for i, n in enumerate(sizes)]}}
        for k, sizes in enumerate(plane_bytes)]
    captures.append({"ok": False, "manifest": {}})
    rec = {"device": {"count": chips}, "captures": captures}
    if path is not None:
        rec["trace"] = {"path": path}
    return rec


def test_one_plane_reads_zero_in_both_by_measurement(tmp_path, readers):
    rec = record("toy-cpu", xspace_file(tmp_path, 1, 0),
                 [[1_560_000], [1_570_000], [1_550_000]])
    assert readers["xspan.xla_collective_pct"].read(rec) == 0.0
    assert readers["xspan.xplane_plane_skew_pct"].read(rec) == 0.0


def test_four_planes_of_toy_cpu4_read_what_was_put_in(tmp_path, readers):
    sizes = [[1000, 900, 950, 800], [1000, 1000, 1000, 1000],
             [2000, 1000, 1500, 1200]]
    rec = record("toy-cpu4", xspace_file(tmp_path, 4, 1), sizes)
    assert rec["device"]["count"] == 4
    # plane 0: 4 of 10 ms; planes 1-3: 2 of 8 ms each; the async line is
    # not counted
    assert readers["xspan.xla_collective_pct"].read(rec) == pytest.approx(
        100.0 * (4 + 3 * 2) / (10 + 3 * 8))
    # captures: (1000 - 800) / 1000, 0 / 1001, (2002 - 1002) / 2002
    assert readers["xspan.xplane_plane_skew_pct"].read(rec) == pytest.approx(
        20.0)


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise(
        tmp_path, readers):
    """The parent of the PR that adds `planes`: manifests without the key."""
    rec = {"device": {"count": 1}, "captures": [
        {"ok": True, "manifest": {"timing": {"xspace_bytes": 5}}},
        {"ok": False, "manifest": {}}]}
    assert readers["xspan.xplane_plane_skew_pct"].read(rec) is None
    assert readers["xspan.xla_collective_pct"].read(rec) is None
    rec["trace"] = {"path": xspace_file(tmp_path, 1, 2)}
    rec["device"]["count"] = 4  # three planes the trace does not hold
    assert readers["xspan.xla_collective_pct"].read(rec) == pytest.approx(
        100.0 * 8 / 14)


def test_aot_mesh_is_importable_and_sizes_from_the_deepest_down():
    spec = importlib.util.spec_from_file_location(
        "aot_mesh", cells.HERE / "aot_mesh.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert list(module.DEPTHS) == sorted(module.DEPTHS, reverse=True)
    assert min(module.DEPTHS) == 4  # the guide's floor
    job = cells.load_config(NAME)["job"]
    assert job["n_layers"] in module.DEPTHS
    json.dumps(job)


def test_whole_run_over_a_mesh_reaches_its_end(monkeypatch, tmp_path):
    """harness.measure() at toy size over four virtual devices, data 2 x
    model 2: weights and Adam state born sharded, check J through
    forward(..., mesh), the ahead-of-time compiled step fed its own outputs,
    one profiler session over four local devices. A CPU writes no
    /device:TPU plane, so C1-C3 read false and both readers nothing."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seed=2**31 + 28, seconds=3.0,
        trace=True, config="toy-cpu4")
    failed = [c["name"] for c in run.record["checks"] if not c["ok"]]
    assert failed == ["C1", "C2", "C3"] and line["failed"] == 0
    s2 = next(c for c in run.record["checks"] if c["name"] == "S2")
    assert len(s2["compared"]) == 8  # total and used, per chip
    assert line["device"]["count"] == 4
    captures = run.record["captures"]
    assert len(captures) >= 2 and all(c["ok"] for c in captures)
    for capture in captures:
        manifest = capture["manifest"]
        assert manifest["local_devices"] == 4
        assert sum(row["bytes"] for row in manifest["planes"]) < (
            manifest["timing"]["xspace_bytes"])
        assert "shim.plane_index" in {s["name"] for s in manifest["spans"]}
    assert "xspan.xplane_plane_skew_pct" not in line["metrics"]
    assert "xspan.xla_collective_pct" not in line["metrics"]
