"""The hybrid configuration as data, its two readers on records written here
(a `while` event over its body's events, as the TPU draws a loop on "XLA
Ops"), and its whole normal path at toy size on the CPU."""

import pytest
from jax.profiler import ProfileData

import cells
import rehearsal

NAME = "olmo-hybrid-7b-4l-v5e1"
CELL = "olmo-hybrid-7b.capture"
US = 1_000_000  # picoseconds in a microsecond
NEW_READERS = ("xspan.xla_while_pct", "xspan.xla_nested_time_pct")
# (op, start us, length us): a while of 100 us over four body events, one of
# them a conditional over one more, and a fusion after the loop
LOOP = (("%while.1 = (s32[]) while(%t)", 0, 100),
        ("%fusion.2 = f32[8]{0} fusion(%a)", 0, 30),
        ("%conditional.3 = f32[8]{0} conditional(%b)", 30, 20),
        ("%copy.4 = f32[8]{0} copy(%c)", 35, 10),
        ("%fusion.2 = f32[8]{0} fusion(%a)", 50, 30),
        ("%dot.5 = f32[8]{0} dot(%d, %e)", 80, 20),
        ("%fusion.6 = f32[8]{0} fusion(%f)", 100, 100))
LINE = (("%fusion.2 = f32[8]{0} fusion(%a)", 0, 60),
        ("%dot.5 = f32[8]{0} dot(%d, %e)", 60, 40))


@pytest.fixture(scope="module")
def readers():
    return cells.load_readers()


def xspace_file(tmp_path, ops, planes: int = 1) -> str:
    ids = {name: i for i, name in enumerate(
        dict.fromkeys(name for name, _, _ in ops), start=1)}
    events = "".join(
        f"events {{ metadata_id: {ids[name]} offset_ps: {at * US} "
        f"duration_ps: {length * US} }}" for name, at, length in ops)
    metadata = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'
        for name, i in ids.items())
    text = "".join(f"""
planes {{ id: {i + 1} name: "/device:TPU:{i}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 {events} }}
  {metadata}
}}""" for i in range(planes))
    path = tmp_path / f"p{planes}{len(ops)}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_every_width_is_as_published_and_the_pattern_is_one_period():
    config = cells.load_config(NAME)
    # the source's keys lie at the top level under their own names, as run;
    # `published` holds the source's values of the two that are cut
    job, published = config["job"], {**config, **config["published"]}
    assert set(config["published"]) == {"num_hidden_layers", "vocab_size"}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (
        job["n_layers"], job["vocab_size"])
    for ours, theirs in (
            ("d_model", "hidden_size"), ("d_ff", "intermediate_size"),
            ("n_heads", "num_attention_heads"),
            ("n_heads", "linear_num_key_heads"),
            ("n_heads", "linear_num_value_heads"),
            ("linear_key_head_dim", "linear_key_head_dim"),
            ("linear_value_head_dim", "linear_value_head_dim"),
            ("linear_conv_kernel", "linear_conv_kernel_dim"),
            ("linear_allow_neg_eigval", "linear_allow_neg_eigval"),
            ("max_seq_len", "max_position_embeddings"),
            ("norm_eps", "rms_norm_eps")):
        assert job[ours] == published[theirs], ours
    assert job["rope_theta"] is published["rope_parameters"]["rope_theta"]
    assert job["rope_theta"] is None
    assert published["num_key_value_heads"] == job["n_heads"]
    assert config["reduced"] == [
        "num_hidden_layers", "vocab_size", "batch", "sequence"]
    assert job["layer_types"] == published["layer_types"][:4]
    assert job["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert job["n_layers"] == 4
    # the slice: a whole number of times in the vocabulary, above the floor
    assert published["vocab_size"] % job["vocab_size"] == 0
    assert job["vocab_size"] * 8 >= published["vocab_size"]
    assert job["seq"] % 64 == 0 and config["deployment"]["mesh"] is None


def test_the_cell_is_one_chip_under_capture_pull_and_reports_the_readers():
    bench = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert (cell.chips, cell.traffic_name, cell.config_name) == (
        1, "capture-pull", NAME)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == NAME
    due = cells.metric_names(bench, cell, "per_layer")
    assert set(NEW_READERS) <= set(due)
    assert "step_ms_p95.capture" not in due  # named under no_reading
    assert cells.metric_names(bench, cell, "end_to_end") == [
        "step_ms_p50", "capture_ms_p50", "setup_s", "derived_ms_p50"]
    for name in NEW_READERS:  # they join every capture cell's list
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [
            w["name"] for w in bench["workloads"]
            if w["traffic"] == "capture-pull"]


def test_a_straight_line_of_ops_reads_zero_by_measurement(tmp_path, readers):
    rec = {"device": {"count": 2},
           "trace": {"path": xspace_file(tmp_path, LINE, planes=2)}}
    assert readers["xspan.xla_while_pct"].read(rec) == 0.0
    assert readers["xspan.xla_nested_time_pct"].read(rec) == 0.0


def test_a_loop_reads_what_was_put_in(tmp_path, readers):
    rec = {"device": {"count": 1},
           "trace": {"path": xspace_file(tmp_path, LOOP)}}
    # durations add up to 310 us over 200 us of busy device: the while's
    # 100 and the conditional's 10 are counted twice
    assert readers["xspan.xla_while_pct"].read(rec) == pytest.approx(
        100.0 * 100 / 310)
    assert readers["xspan.xla_nested_time_pct"].read(rec) == pytest.approx(
        100.0 * 110 / 310)


def test_a_run_without_a_trace_reads_nothing_and_does_not_raise(readers):
    rec = {"device": {"count": 1}, "captures": []}
    for name in NEW_READERS:
        assert readers[name].read(rec) is None


def test_whole_run_of_the_hybrid_toy_reaches_its_end(monkeypatch, tmp_path):
    """harness.measure() at toy size: the module's weights through the
    program's step, check J against the recurrence taken token by token
    (float32 on both sides here). A CPU writes no /device:TPU plane, so
    C1-C3 read false."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seed=2**31 + 36, seconds=3.0,
        trace=True, config="toy-olmo-hybrid")
    failed = [c["name"] for c in run.record["checks"] if not c["ok"]]
    # (C2 holds or not by whether a step fell between a capture's marks)
    assert {"C1", "C3"} <= set(failed) <= {"C1", "C2", "C3"}
    assert line["failed"] == 0
    j = next(c for c in run.record["checks"] if c["name"] == "J")
    assert [p["value"] < 1e-4 for p in j["compared"]] == [True, True]
    assert "gdn_q" in run.state[0]["layers"][0]
    assert "wq" in run.state[0]["layers"][3]
    assert len(run.record["captures"]) >= 2
