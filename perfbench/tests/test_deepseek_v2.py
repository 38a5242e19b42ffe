"""The DeepSeek-V2-Lite configuration as data, its readers on records written
here (ops that carry their path in `tf_op`, flash kernels of a known
length), and its whole normal path at toy size on the CPU."""

import json

import pytest
from jax.profiler import ProfileData

import cells
import kernel_costs
import rehearsal
import scope_ops

NAME = "deepseek-v2-lite-5l-v5e1"
CELL = "deepseek-v2-lite.capture"
US = 1_000_000  # picoseconds in a microsecond
SCOPE_READERS = ("xspan.mla_scope_pct", "xspan.moe_shared_scope_pct")
ROOFLINES = ("xspan.flash_fwd_roofline_pct", "xspan.flash_bwd_dq_roofline_pct",
             "xspan.flash_bwd_dkv_roofline_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# (op, its path or None, start us, length us)
STEP = (
    ("%fusion.1 = f32[8]{0} fusion(%a)",
     "jit(step)/jvp(mla.project)/dot_general:", 0, 30),
    ("%jvp_flash_attention_fwd_.2 = f32[8]{0} custom-call(%b)",
     "jit(step)/jvp(mla.attend)/flash_attention_fwd/pallas_call:", 30, 50),
    ("%fusion.3 = f32[8]{0} fusion(%c)",
     "jit(step)/transpose(jvp(moe.shared))/mul:", 80, 20),
    ("%fusion.4 = f32[8]{0} fusion(%d)",
     "jit(step)/jvp()/shard_map/moe.dispatch/gather:", 100, 60),
    ("%copy-start.5 = f32[8]{0} copy-start(%e)", None, 160, 40),
)


@pytest.fixture(scope="module")
def readers():
    return cells.load_readers()


def xspace_file(tmp_path, ops, planes: int = 1) -> str:
    ids = {name: i for i, name in enumerate(
        dict.fromkeys(name for name, _, _, _ in ops), start=1)}
    paths = {name: path for name, path, _, _ in ops}
    events = "".join(
        f"events {{ metadata_id: {ids[name]} offset_ps: {at * US} "
        f"duration_ps: {length * US} }}" for name, _, at, length in ops)
    metadata = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" '
        + (f'stats {{ metadata_id: 9 str_value: "{paths[name]}" }} '
           if paths[name] else "") + "} }"
        for name, i in ids.items())
    text = "".join(f"""
planes {{ id: {i + 1} name: "/device:TPU:{i}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 {events} }}
  {metadata}
  stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }}
}}""" for i in range(planes))
    path = tmp_path / f"p{planes}{len(ops)}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_every_width_is_as_published_and_the_cut_is_written_down():
    config = cells.load_config(NAME)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V2-Lite")
    cut = {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 51200}
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():  # at the top level, as run
        assert config[key] == cut.get(key, value), key
    assert config["published"] == {key: row["config"][key] for key in cut}
    assert config["reduced"] == [*cut, "batch", "sequence"]
    job = config["job"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("d_ff", "intermediate_size"),
            ("n_heads", "num_attention_heads"),
            ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"), ("moe_d_ff", "moe_intermediate_size"),
            ("moe_top_k", "num_experts_per_tok"),
            ("n_shared_experts", "n_shared_experts"),
            ("first_dense_layers", "first_k_dense_replace"),
            ("moe_norm_topk", "norm_topk_prob"), ("moe_seq_aux", "seq_aux"),
            ("rope_theta", "rope_theta"), ("rope_scaling", "rope_scaling"),
            ("max_seq_len", "max_position_embeddings"),
            ("norm_eps", "rms_norm_eps")):
        assert job[ours] == row["config"][theirs], ours
    # the share: the router keeps the published 64, the chip holds 16
    assert job["n_experts"] == row["config"]["n_routed_experts"] == 64
    assert job["n_experts_held"] == config["n_routed_experts"] == 16
    assert job["n_experts"] % job["n_experts_held"] == 0
    assert (job["n_layers"], job["vocab_size"]) == (5, 51200)
    # the floors: the dense layer and four after it, 8 experts, an eighth
    assert job["n_layers"] - job["first_dense_layers"] >= 4
    assert job["n_experts_held"] >= 8
    assert job["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert row["config"]["q_lora_rank"] is None and job["attn_type"] == "mla"
    assert config["deployment"]["mesh"] is None


def test_the_cell_is_one_chip_under_capture_pull_and_reports_the_readers():
    bench = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert (cell.chips, cell.traffic_name, cell.config_name) == (
        1, "capture-pull", NAME)
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == NAME
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    due = cells.metric_names(bench, cell, "per_layer")
    assert {*SCOPE_READERS, *ROOFLINES, "xspan.moe_expert_op_pct",
            "xspan.xla_all_to_all_pct"} <= set(due)
    assert cells.metric_names(bench, cell, "end_to_end") == [
        "step_ms_p50", "capture_ms_p50", "setup_s", "derived_ms_p50"]
    for name in (*SCOPE_READERS, *ROOFLINES):  # every capture cell's list
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [
            w["name"] for w in bench["workloads"]
            if w["traffic"] == "capture-pull"]


def test_the_scope_readers_read_what_was_put_in(tmp_path, readers):
    if scope_ops.binding() is None:
        pytest.skip("no wheel here ships xplane_pb2")
    rec = {"device": {"count": 2},
           "trace": {"path": xspace_file(tmp_path, STEP, planes=2)}}
    # 200 us of ops a plane: 80 under mla., 20 under moe.shared
    assert readers["xspan.mla_scope_pct"].read(rec) == pytest.approx(40.0)
    assert readers["xspan.moe_shared_scope_pct"].read(rec) == pytest.approx(
        10.0)
    # a job with neither reads 0.0 because its planes were summed
    plain = [(name, None, at, length) for name, _, at, length in STEP]
    rec = {"device": {"count": 1},
           "trace": {"path": xspace_file(tmp_path, plain)}}
    assert [readers[name].read(rec) for name in SCOPE_READERS] == [0.0, 0.0]


def test_self_time_is_an_events_own(tmp_path):
    # a holder of 100 over 30 + 50, and 20 after it
    events = [(1, 0, 100), (2, 0, 30), (3, 40, 50), (4, 100, 20)]
    assert sorted(scope_ops.self_times(events)) == [
        (1, 20), (2, 30), (3, 50), (4, 20)]


def test_a_kernels_work_is_counted_from_the_jobs_shapes():
    job = cells.load_config(NAME)["job"]
    assert kernel_costs.head_widths(job) == (192, 128)
    heads, half = job["batch"] * 16, 4096 * 4096 / 2
    assert kernel_costs.call_cost(job, "flash_attention_fwd") == (
        heads * 2 * half * (192 + 128),
        heads * 4096 * (2 * 192 + 2 * 128) * 2)
    assert kernel_costs.call_cost(job, "flash_attention_bwd_dq")[0] == (
        heads * 2 * half * (2 * 192 + 128))
    assert kernel_costs.call_cost(job, "flash_attention_bwd_dkv") == (
        heads * 2 * half * (2 * 192 + 2 * 128),
        heads * 4096 * (3 * 192 + 3 * 128) * 2)
    dense = cells.load_config("olmo2-1b-v5e1")["job"]
    assert kernel_costs.head_widths(dense) == (128, 128)


def test_a_roofline_reads_the_least_time_over_the_traced_time(
        tmp_path, readers):
    rec = {"workload": CELL, "device": {"count": 1, "kind": "TPU v5 lite"},
           "trace": {"path": xspace_file(tmp_path, STEP)}}
    flops, nbytes = kernel_costs.call_cost(
        cells.load_cell(CELL).job, "flash_attention_fwd")
    least_s = max(flops / 197e12, nbytes / 819e9)
    assert flops / 197e12 > nbytes / 819e9  # the products bound it
    assert readers["xspan.flash_fwd_roofline_pct"].read(rec) == pytest.approx(
        100.0 * least_s / 50e-6)
    # no event of the other two: 0.0 by measurement, nothing raised
    assert readers["xspan.flash_bwd_dq_roofline_pct"].read(rec) == 0.0
    assert readers["xspan.flash_bwd_dkv_roofline_pct"].read(rec) == 0.0
    # a run of no cell of the benchmark has no shapes to hold a kernel to
    assert readers["xspan.flash_fwd_roofline_pct"].read(
        dict(rec, workload="toy.capture-pull")) is None


def test_a_run_without_a_trace_reads_nothing_and_does_not_raise(readers):
    rec = {"workload": CELL, "device": {"count": 1, "kind": "TPU v5 lite"},
           "captures": []}
    for name in (*SCOPE_READERS, *ROOFLINES):
        assert readers[name].read(rec) is None


def test_whole_run_of_the_toy_reaches_its_end(monkeypatch, tmp_path):
    """harness.measure() at toy size: the module's weights through the
    program's step, check J against the plain reference (float32 on both
    sides here). A CPU writes no /device:TPU plane, so C1-C3 read false."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seed=2**31 + 41, seconds=3.0,
        trace=True, config="toy-deepseek-v2")
    failed = [c["name"] for c in run.record["checks"] if not c["ok"]]
    # (C2 holds or not by whether a step fell between a capture's marks)
    assert {"C1", "C3"} <= set(failed) <= {"C1", "C2", "C3"}
    assert line["failed"] == 0
    j = next(c for c in run.record["checks"] if c["name"] == "J")
    assert [p["value"] < 1e-4 for p in j["compared"]] == [True, True]
    layers = run.state[0]["layers"]
    assert "w_gate" in layers[0] and "mla_dkv" in layers[0]
    assert layers[1]["experts_gate"].shape[0] == 4
    assert layers[1]["router"].shape[1] == 16
    assert len(run.record["captures"]) >= 2
