"""BENCHMARK.json resolves to files that exist, by name alone; a four-chip
configuration is already a legal value."""

import json

import pytest

import cells
import rehearsal


def test_every_workload_resolves_to_files_that_exist():
    bench = cells.load_benchmark()
    assert bench["workloads"]
    for entry in bench["workloads"]:
        cell = cells.load_cell(entry["name"])
        assert cell.chips == entry["chips"] == cell.config["deployment"]["chips"]
        assert cell.kind in cells.TRAFFIC_KINDS
        assert cell.job["step_module"]
        assert cells.metric_names(bench, cell, "end_to_end")
        assert cells.metric_names(bench, cell, "per_layer")
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for entry in bench["workloads"]:
        assert (cells.ROOT / files[entry["config"]]).is_file()


def test_every_cell_reports_setup_and_one_more_and_a_layer():
    bench = cells.load_benchmark()
    for entry in bench["workloads"]:
        cell = cells.load_cell(entry["name"])
        e2e = cells.metric_names(bench, cell, "end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        by_name = {m["name"]: m for m in bench["per_layer"]}
        for name in cells.metric_names(bench, cell, "per_layer"):
            assert by_name[name]["moves"] in e2e, (cell.name, name)


def test_unknown_names_are_errors_not_defaults():
    with pytest.raises(cells.BenchmarkError, match="no workload"):
        cells.load_cell("no-such-cell")
    with pytest.raises(cells.BenchmarkError, match="not in perfbench/peaks"):
        cells.load_peaks("TPU v9 imaginary")
    assert cells.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_reference_module_is_found_by_the_path_its_configuration_gives(
        tmp_path):
    """The loader's errors name the file and the export; a configuration
    without the key is no configuration."""
    dense = cells.load_reference(cells.load_config("olmo2-1b-v5e1"))
    assert dense.J_LOGIT_REL_RMS_LIMIT == 0.05 and callable(dense.forward)
    toy = cells.load_reference(rehearsal.toy_cell("steady", "toy-moe").config)
    assert toy.__file__.endswith("tests/data/moe_block.py")
    assert toy.J_LOGIT_REL_RMS_LIMIT != dense.J_LOGIT_REL_RMS_LIMIT
    with pytest.raises(cells.BenchmarkError, match="nowhere.py is not a file"):
        cells.load_reference({"reference": "nowhere.py"}, tmp_path)
    (tmp_path / "half.py").write_text(
        "init_weights = forward = lower = rel_rms = print\n"
        "J_LOGIT_REL_RMS_LIMIT = 0.05\n")
    with pytest.raises(cells.BenchmarkError,
                       match="half.py exports no J_LOSS_ABS_LIMIT"):
        cells.load_reference({"reference": "half.py"}, tmp_path)
    with pytest.raises(cells.BenchmarkError, match="leads out"):
        cells.load_reference({"reference": "../checks.py"}, cells.HERE / "tests")
    (tmp_path / "configs").mkdir()
    conf = dict(rehearsal.toy_cell("steady").config)
    del conf["reference"]
    (tmp_path / "configs" / "old.json").write_text(json.dumps(conf))
    with pytest.raises(cells.BenchmarkError, match="lacks 'reference'"):
        cells.load_config("old", tmp_path)


def test_no_reader_and_no_harness_file_names_a_cell_or_a_configuration():
    """What belongs to one deployment is said in its file: a reader that
    named a cell had to be edited for the next cell of that kind. Nor does
    the harness import a block's module by name."""
    bench = cells.load_benchmark()
    names = ([w["name"] for w in bench["workloads"]]
             + [c["name"] for c in bench["configs"]])
    for folder in ("metrics", "end_to_end"):
        for path in sorted((cells.HERE / folder).glob("*.py")):
            text = path.read_text()
            assert not [n for n in names if n in text], path.name
            assert "EXCEPT" not in text, path.name
    for path in sorted(cells.HERE.glob("*.py")):
        text = path.read_text()
        assert "import reference" not in text, path.name
        assert not [n for n in names if n in text], path.name


def test_traffic_push_is_data_and_two_clients_are_refused(tmp_path):
    (tmp_path / "traffic").mkdir()
    base = {"kind": "capture", "mode": "push", "window_ms": 500,
            "think_ms": 0, "clients": 1, "trigger": "cli"}
    (tmp_path / "traffic" / "push.json").write_text(json.dumps(base))
    assert cells.load_traffic("push", tmp_path)["mode"] == "push"
    (tmp_path / "traffic" / "two.json").write_text(
        json.dumps(dict(base, clients=2)))
    with pytest.raises(cells.BenchmarkError, match="one client"):
        cells.load_traffic("two", tmp_path)
    (tmp_path / "traffic" / "odd.json").write_text(json.dumps({"kind": "x"}))
    with pytest.raises(cells.BenchmarkError, match="kind"):
        cells.load_traffic("odd", tmp_path)


def test_four_chip_configuration_parses_into_a_mesh():
    import jax

    cell = rehearsal.toy_cell("capture-pull", config="toy-cpu4")
    assert cell.chips == 4
    mesh = cells.build_mesh(cell.config["deployment"], jax.devices())
    assert dict(mesh.shape)["data"] == 2 and dict(mesh.shape)["model"] == 2
    assert mesh.devices.size == 4
    one = rehearsal.toy_cell("steady")
    assert cells.build_mesh(one.config["deployment"], jax.devices()) is None


def test_mesh_that_does_not_multiply_to_the_chips_is_refused(tmp_path):
    (tmp_path / "configs").mkdir()
    conf = rehearsal.toy_cell("steady", config="toy-cpu4").config
    conf["deployment"]["mesh"] = {"data": 2}
    (tmp_path / "configs" / "bad.json").write_text(json.dumps(conf))
    with pytest.raises(cells.BenchmarkError, match="multiply"):
        cells.load_config("bad", tmp_path)


def test_s1_counts_only_slots_in_which_the_job_stepped():
    import checks

    ends = [100.0 + 0.134 * i for i in range(1, 299)]  # 40 s of steps
    assert checks.samples_needed(ends, 100.0, 40.0, 1.0) == 37
    stalled = [t for t in ends if not 110.2 < t < 118.7]  # an 8.5 s stall
    assert checks.samples_needed(stalled, 100.0, 40.0, 1.0) == 30  # 7 dead slots
    # capture traffic: slots as long as interval + longest capture
    assert checks.samples_needed(ends, 100.0, 40.0, 1.0 + 1.4) == 13
    assert checks.samples_needed([], 100.0, 40.0, 1.0) == 1


def test_s1_passes_a_zero_rate_only_under_a_stall_of_the_job_itself():
    """The shim reports 0 once the job has completed no step for two report
    intervals. The job below steps every 134 ms but for 3.0 s from t =
    119.14 (the chip's, PR 32); the sample stamped at 121.16 is 0."""
    import types

    import checks

    ends = [100.0 + 0.134 * i for i in range(1, 299)]
    steps = [(t, 134.0) for t in ends if not 119.14 < t < 122.15]
    stamps = [int((100.0 + k + 0.16) * 1e3) for k in range(1, 41)]
    rates = [7.46] * 40
    rates[20] = 0.0  # the sample at 121.16 s
    run = types.SimpleNamespace(
        record={"s1_needed": 35}, job_id=7, steps=steps,
        cell=types.SimpleNamespace(config={"shim": {"report_interval_s": 1.0}}))
    store = {"job7.steps_per_sec": {"values": rates, "timestamps": stamps},
             "job7.step_time_p50_ms": {"values": [134.0] * 39,
                                       "timestamps": stamps[:39]}}
    check = checks.check_s1(run, store)
    assert check["ok"], check
    assert check["compared"][1]["value"] == 40
    assert "(1)" in check["compared"][1]["what"]
    # the same 0 while the job was stepping is a lie, and fails
    rates[20], rates[30] = 7.46, 0.0
    check = checks.check_s1(run, store)
    assert not check["ok"] and check["compared"][1]["value"] == 39
    # a negative or a NaN rate fails wherever it falls
    for bad in (-1.0, float("nan")):
        rates[30], rates[20] = 7.46, bad
        assert not checks.check_s1(run, store)["ok"]
