"""The Nemotron-3-Nano configuration as data, its module's exports, its new
reader on a record written here, and its whole normal path at toy size on
the CPU."""

import json

import pytest

import cells
import gen_benchmark
import rehearsal
import scope_ops
from test_deepseek_v2 import xspace_file

NAME = "nemotron-3-nano-7l-v5e1"
CELL = "nemotron-3-nano.capture"
ROOFLINES = ("xspan.flash_fwd_roofline_pct", "xspan.flash_bwd_dq_roofline_pct",
             "xspan.flash_bwd_dkv_roofline_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = {"M": "mamba2", "E": "moe", "*": "attention", "-": "mlp"}
# (op, its path or None, start us, length us)
STEP = (
    ("%fusion.1 = f32[8]{0} fusion(%a)",
     "jit(step)/jvp(ssm.project)/dot_general:", 0, 30),
    ("%fusion.2 = f32[8]{0} fusion(%b)",
     "jit(step)/jvp(checkpoint)/ssm.chunk/dot_general:", 30, 50),
    ("%fusion.3 = f32[8]{0} fusion(%c)",
     "jit(step)/transpose(jvp(moe.shared))/mul:", 80, 20),
    ("%flash_attention_fwd.4 = f32[8]{0} custom-call(%d)",
     "jit(step)/jvp(flash_attention_fwd)/pallas_call:", 100, 60),
    ("%copy-start.5 = f32[8]{0} copy-start(%e)", None, 160, 40),
)


def test_the_module_exports_what_the_harness_loads():
    module = cells.load_reference(cells.load_config(NAME))
    assert all(hasattr(module, attr) for attr in cells.REFERENCE_ATTRS)
    assert 0 < module.J_LOGIT_REL_RMS_LIMIT < 1
    assert 0 < module.J_LOSS_ABS_LIMIT < 1
    with open(module.__file__) as f:
        source = f.read()
    assert "import dynolog_tpu" not in source
    assert "from dynolog_tpu" not in source
    assert "jax.lax.scan" in source  # the recurrence, token by token


def test_every_width_is_as_published_and_the_cut_is_written_down():
    config = cells.load_config(NAME)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    cut = {"num_hidden_layers": 7, "n_routed_experts": 16, "vocab_size": 32768}
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():  # at the top level, as run
        assert config[key] == cut.get(key, value), key
    assert config["published"] == {key: row["config"][key] for key in cut}
    assert config["reduced"] == [*cut, "batch", "sequence"]
    assert set(config["reduced_from"]) == set(config["reduced"])
    job = config["job"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("d_ff", "intermediate_size"),
            ("n_heads", "num_attention_heads"),
            ("n_kv_heads", "num_key_value_heads"),
            ("attn_head_dim", "head_dim"),
            ("ssm_heads", "mamba_num_heads"),
            ("ssm_head_dim", "mamba_head_dim"),
            ("ssm_state", "ssm_state_size"), ("ssm_groups", "n_groups"),
            ("ssm_conv_kernel", "conv_kernel"), ("ssm_chunk", "chunk_size"),
            ("mlp_act", "mlp_hidden_act"),
            ("moe_d_ff", "moe_intermediate_size"),
            ("moe_shared_d_ff", "moe_shared_expert_intermediate_size"),
            ("n_shared_experts", "n_shared_experts"),
            ("moe_top_k", "num_experts_per_tok"),
            ("moe_norm_topk", "norm_topk_prob"),
            ("moe_gate_scale", "routed_scaling_factor"),
            ("max_seq_len", "max_position_embeddings"),
            ("norm_eps", "norm_eps")):
        assert job[ours] == row["config"][theirs], ours
    # the job runs the first whole period of the published pattern
    pattern = row["config"]["hybrid_override_pattern"]
    assert config["hybrid_override_pattern"] == pattern and len(pattern) == 52
    assert job["block_types"] == [PERIOD[c] for c in pattern[:7]]
    assert pattern[:7] == "MEMEM*E"
    assert job["moe_score"] == "sigmoid" and job["moe_select_bias"] is True
    assert job["rope_theta"] is None
    assert (job["moe_aux_weight"], job["moe_z_weight"]) == (0.0, 0.0)
    # the share: the router keeps the published 128, the chip holds 16
    assert job["n_experts"] == row["config"]["n_routed_experts"] == 128
    assert job["n_experts_held"] == config["n_routed_experts"] == 16
    assert job["n_experts"] % job["n_experts_held"] == 0
    assert (job["n_layers"], job["vocab_size"]) == (7, 32768)
    # the floors: a whole period of at least four, 8 experts, an eighth
    assert job["n_layers"] >= 4 and job["n_experts_held"] >= 8
    assert job["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert config["deployment"]["mesh"] is None
    assert config["deployment"]["chips"] == 1
    assert set(config["no_reading"]) == {"step_ms_p95.capture", *ROOFLINES}


def test_the_parameters_are_as_many_as_reckoned():
    import jax

    config = cells.load_config(NAME)
    module = cells.load_reference(config)
    shapes = jax.eval_shape(
        lambda k: module.init_weights(k, config["job"]), jax.random.PRNGKey(0))
    size = lambda tree: sum(  # noqa: E731
        a.size for a in jax.tree_util.tree_leaves(tree))
    by_kind = {}
    for kind, layer in zip(config["job"]["block_types"], shapes["layers"]):
        by_kind.setdefault(kind, set()).add(size(layer))
    assert {k: len(v) for k, v in by_kind.items()} == {
        "mamba2": 1, "moe": 1, "attention": 1}
    m, e, a = (by_kind[k].pop() for k in ("mamba2", "moe", "attention"))
    assert round(m / 1e6, 2) == 38.74  # an M block, as the issue reckons it
    assert round(e / 1e6, 2) == 179.95  # an E block with 16 of 128 held
    assert round(a / 1e6, 1) == 23.4
    head = size(shapes) - 3 * (m + e) - a
    assert round(head / 1e6, 1) == 176.2
    # 855.64 M (the sum of the rounded parts above reads 855.7)
    assert size(shapes) == 855_642_048
    # uncut: 23 M, 23 E of 128 experts, 6 attention, the whole vocabulary
    whole_e = e + (128 - 16) * 2 * 2688 * 1856
    total = 23 * (m + whole_e) + 6 * a + 2 * 131072 * 2688 + 2688
    assert round(total / 1e9, 2) == 31.58


def test_the_cell_is_present_one_chip_under_capture_pull_with_its_readers():
    bench = cells.load_benchmark()
    cell = cells.load_cell(CELL)
    assert (cell.chips, cell.traffic_name, cell.config_name) == (
        1, "capture-pull", NAME)
    assert CELL in [w["name"] for w in bench["workloads"]]  # present,
    assert NAME in [c["name"] for c in bench["configs"]]  # wherever it lies
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == cell.config["reduced"]
    assert entry["file"] == f"perfbench/configs/{NAME}.json"
    # nothing in it exists only across chips: it takes no four-chip place
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    due = cells.metric_names(bench, cell, "per_layer")
    assert {"xspan.ssm_scope_pct", "xspan.moe_expert_op_pct",
            "xspan.moe_shared_scope_pct", "xspan.xla_while_pct",
            "xspan.xla_nested_time_pct"} <= set(due)
    assert not set(ROOFLINES) & set(due)  # named under no_reading
    assert "step_ms_p95.capture" not in due
    assert cells.metric_names(bench, cell, "end_to_end") == [
        "step_ms_p50", "capture_ms_p50", "setup_s", "derived_ms_p50"]
    # the new reader is due in every capture cell
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "xspan.ssm_scope_pct")
    assert entry["workloads"] == [
        w["name"] for w in bench["workloads"]
        if w["traffic"] == "capture-pull"]
    assert entry["moves"] == "step_ms_p50"
    # the table is what the generator makes of the files: nothing by hand
    assert gen_benchmark.per_layer(bench) == bench["per_layer"]


def test_the_ssm_reader_reads_what_was_put_in(tmp_path):
    if scope_ops.binding() is None:
        pytest.skip("no wheel here ships xplane_pb2")
    reader = cells.load_readers()["xspan.ssm_scope_pct"]
    rec = {"device": {"count": 2},
           "trace": {"path": xspace_file(tmp_path, STEP, planes=2)}}
    # 200 us of ops a plane: 80 under ssm.
    assert reader.read(rec) == pytest.approx(40.0)
    # a job without such a block reads 0.0 because its planes were summed
    plain = [(name, None, at, length) for name, _, at, length in STEP]
    rec = {"device": {"count": 1},
           "trace": {"path": xspace_file(tmp_path, plain)}}
    assert reader.read(rec) == 0.0
    # a run without a trace reads nothing and does not raise
    assert reader.read({"device": {"count": 1}, "captures": []}) is None


def test_whole_run_of_the_toy_reaches_its_end(monkeypatch, tmp_path):
    """harness.measure() at toy size: the module's weights through the
    program's step, check J against the plain reference (float32 on both
    sides here). A CPU writes no /device:TPU plane, so C1-C3 read false."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seed=2**31 + 43, seconds=3.0,
        trace=True, config="toy-nemotron-h")
    failed = [c["name"] for c in run.record["checks"] if not c["ok"]]
    # (C2 holds or not by whether a step fell between a capture's marks)
    assert {"C1", "C3"} <= set(failed) <= {"C1", "C2", "C3"}
    assert line["failed"] == 0
    j = next(c for c in run.record["checks"] if c["name"] == "J")
    assert [p["value"] < 1e-4 for p in j["compared"]] == [True, True]
    layers = run.state[0]["layers"]
    assert [sorted(layer)[0] for layer in layers] == [
        "ssm_a_log", "experts_down", "ssm_a_log", "experts_down", "ssm_a_log",
        "attn_scale", "experts_down"]
    assert layers[1]["experts_up"].shape[0] == 2
    assert layers[1]["router"].shape[1] == 16
    assert layers[5]["wk"].shape == (64, 2 * 16)
    assert len(run.record["captures"]) >= 2
