"""The Trinity-Mini configuration as data, its module's exports, its cost file
and its four readers on records written here, and its whole normal path at
toy size on the CPU."""

import json

import pytest

import cells
import gen_benchmark
import kernel_costs
import rehearsal
import scope_ops
import window_costs
from test_deepseek_v2 import xspace_file

NAME = "trinity-mini-5l-v5e1"
CELL = "trinity-mini.capture"
ROOFLINES = ("xspan.flash_fwd_roofline_pct", "xspan.flash_bwd_dq_roofline_pct",
             "xspan.flash_bwd_dkv_roofline_pct")
WINDOWED = ("xspan.flash_window_fwd_roofline_pct",
            "xspan.flash_window_bwd_dq_roofline_pct",
            "xspan.flash_window_bwd_dkv_roofline_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# (op, its path or None, start us, length us)
STEP = (
    ("%fusion.1 = f32[8]{0} fusion(%a)",
     "jit(step)/jvp(attn)/dot_general:", 0, 30),
    ("%flash_attention_window_fwd.2 = f32[8]{0} custom-call(%b)",
     "jit(step)/jvp(flash_attention_window_fwd)/pallas_call:", 30, 50),
    ("%flash_attention_window_bwd_dq.3 = f32[8]{0} custom-call(%c)",
     "jit(step)/transpose(jvp(flash_attention_window_bwd_dq))/pallas_call:",
     80, 20),
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
    assert "pallas" not in source  # no kernel: plain jax.numpy


def test_every_width_is_as_published_and_the_cut_is_written_down():
    config = cells.load_config(NAME)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    cut = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 25024}
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
            ("sliding_window", "sliding_window"),
            ("moe_d_ff", "moe_intermediate_size"),
            ("n_shared_experts", "num_shared_experts"),
            ("moe_top_k", "num_experts_per_tok"),
            ("moe_norm_topk", "route_norm"),
            ("moe_gate_scale", "route_scale"),
            ("moe_score", "score_func"),
            ("scale_embedding", "mup_enabled"),
            ("rope_theta", "rope_theta"),
            ("max_seq_len", "max_position_embeddings"),
            ("norm_eps", "rms_norm_eps")):
        assert job[ours] == row["config"][theirs], ours
    # the job runs published layer 1 (dense) and the period of layers 4-7
    kinds = row["config"]["layer_types"]
    assert config["layer_types"] == kinds and len(kinds) == 32
    assert job["layer_types"] == [kinds[1], *kinds[4:8]]
    assert job["layer_types"].count("sliding_attention") == 4
    assert job["layer_types"][-1] == "full_attention"
    assert job["first_dense_layers"] == 1 < row["config"]["num_dense_layers"]
    assert job["rope_layer_types"] == ["sliding_attention"]
    assert job["qk_head_norm"] and job["attn_gate"] and job["post_norm"]
    assert job["moe_select_bias"] is True and job["mlp_act"] == "swiglu"
    assert (job["moe_aux_weight"], job["moe_z_weight"]) == (0.0, 0.0)
    # the share: the router keeps the published 128, the chip holds 16
    assert job["n_experts"] == row["config"]["num_experts"] == 128
    assert job["n_experts_held"] == config["num_experts"] == 16
    assert (job["n_layers"], job["vocab_size"]) == (5, 25024)
    assert (job["batch"], job["seq"]) == (1, 8192)
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
    dense, *sparse = (size(layer) for layer in shapes["layers"])
    assert len(set(sparse)) == 1
    # attention 27.3 M with its two head norms, four norms of 2048
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128
    assert round(attention / 1e6, 1) == 27.3
    assert dense == attention + 4 * 2048 + 3 * 2048 * 6144
    assert round(dense / 1e6, 1) == 65.0  # as the issue reckons it
    assert round(sparse[0] / 1e6, 1) == 134.5  # 16 of 128 experts held
    head = size(shapes) - dense - sum(sparse)
    assert round(head / 1e6, 1) == 102.5
    assert round(size(shapes) / 1e6, 1) == 705.5
    # uncut: 2 dense, 30 sparse of 128 experts, the whole vocabulary
    whole = sparse[0] + (128 - 16) * 3 * 2048 * 1024
    total = 2 * dense + 30 * whole + 2 * 200192 * 2048 + 2048
    assert round(total / 1e9, 1) == 26.1


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
    assert next(w for w in bench["workloads"]
                if w["name"] == CELL)["chips"] == 1
    due = cells.metric_names(bench, cell, "per_layer")
    assert {*WINDOWED, "xspan.attn_window_scope_pct",
            "xspan.moe_expert_op_pct", "xspan.moe_shared_scope_pct",
            "xspan.ssm_scope_pct"} <= set(due)
    assert not set(ROOFLINES) & set(due)  # named under no_reading
    assert "step_ms_p95.capture" not in due
    assert cells.metric_names(bench, cell, "end_to_end") == [
        "step_ms_p50", "capture_ms_p50", "setup_s", "derived_ms_p50"]
    # the new readers are due in every capture cell
    captures = [w["name"] for w in bench["workloads"]
                if w["traffic"] == "capture-pull"]
    for name in (*WINDOWED, "xspan.attn_window_scope_pct"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == captures
        assert entry["moves"] == "step_ms_p50"
    # the table is what the generator makes of the files: nothing by hand
    assert gen_benchmark.per_layer(bench) == bench["per_layer"]


def test_the_band_costs_useful_work_only():
    job = cells.load_config(NAME)["job"]
    # 2048 x 2049 / 2 + 6144 x 2048 pairs a head: 14.7 M of causal's 33.6 M
    pairs = window_costs.visible_pairs(8192, 2048)
    assert pairs == 2048 * 2049 / 2 + 6144 * 2048
    assert round(pairs / 1e6, 1) == 14.7
    assert window_costs.visible_pairs(8192, 10**6) == 8192 * 8193 / 2
    assert window_costs.visible_pairs(64, 1) == 64
    for kernel, (multiplier, at_heads, at_kv) in window_costs.KERNELS.items():
        flops, nbytes = window_costs.call_cost(job, kernel)
        assert flops == 32 * 2 * pairs * multiplier * 128
        assert nbytes == 8192 * 128 * (at_heads * 32 + at_kv * 4) * 2
    assert [m for m, _, _ in window_costs.KERNELS.values()] == [2, 3, 4]
    # every visited tile a full one: 5 of 512 x 512 a query block where 4
    # tiles' worth are seen, so no kernel can read above four fifths
    assert pairs / (16 * 5 * 512 * 512) < 0.8


def test_the_windowed_readers_read_what_was_put_in(tmp_path):
    if scope_ops.binding() is None:
        pytest.skip("no wheel here ships xplane_pb2")
    readers = cells.load_readers()
    rec = {"workload": CELL, "device": {"count": 1, "kind": "TPU v5 lite"},
           "trace": {"path": xspace_file(tmp_path, STEP)}}
    # 200 us of ops: 70 under the windowed kernels' scopes, the plain
    # kernel's 60 not among them
    assert readers["xspan.attn_window_scope_pct"].read(rec) == pytest.approx(
        35.0)
    peaks = cells.load_peaks("TPU v5 lite")
    job = cells.load_config(NAME)["job"]
    for name, kernel, us in (
            (WINDOWED[0], "flash_attention_window_fwd", 50),
            (WINDOWED[1], "flash_attention_window_bwd_dq", 20)):
        flops, nbytes = window_costs.call_cost(job, kernel)
        least = max(flops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
        assert readers[name].read(rec) == pytest.approx(
            100.0 * least / (us * 1e-6))
    assert readers[WINDOWED[2]].read(rec) == 0.0  # no event of it
    # the held readers do not count the windowed kernels: one event, 60 us
    assert kernel_costs.kernel_events(rec, "flash_attention_fwd") == (
        60_000.0, 1)
    assert kernel_costs.kernel_events(rec, "flash_attention_bwd_dq") == (0, 0)
    # a job without a windowed layer reads 0.0 because its planes were summed
    plain = [(name.replace("window_", ""), path, at, length)
             for name, path, at, length in STEP]
    plain = [(name, path and path.replace("window_", ""), at, length)
             for name, path, at, length in plain]
    rec = {"workload": "olmo2-1b.capture",
           "device": {"count": 1, "kind": "TPU v5 lite"},
           "trace": {"path": xspace_file(tmp_path, plain[:4])}}
    for name in (*WINDOWED, "xspan.attn_window_scope_pct"):
        assert readers[name].read(rec) == 0.0, name
    # a run without a trace reads nothing and does not raise
    for name in (*WINDOWED, "xspan.attn_window_scope_pct"):
        assert readers[name].read({"device": {"count": 1}}) is None, name


def test_whole_run_of_the_toy_reaches_its_end(monkeypatch, tmp_path):
    """harness.measure() at toy size: the module's weights through the
    program's step, check J against the plain reference (float32 on both
    sides here). A CPU writes no /device:TPU plane, so C1-C3 read false."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seed=2**31 + 48, seconds=3.0,
        trace=True, config="toy-afmoe")
    failed = [c["name"] for c in run.record["checks"] if not c["ok"]]
    # (C2 holds or not by whether a step fell between a capture's marks)
    assert {"C1", "C3"} <= set(failed) <= {"C1", "C2", "C3"}
    assert line["failed"] == 0
    j = next(c for c in run.record["checks"] if c["name"] == "J")
    assert [p["value"] < 1e-4 for p in j["compared"]] == [True, True]
    layers = run.state[0]["layers"]
    assert ["router" in layer for layer in layers] == [
        False, True, True, True, True]
    assert layers[1]["experts_up"].shape[0] == 2
    assert layers[1]["router"].shape[1] == 16
    assert layers[0]["wk"].shape == (64, 2 * 16)
    assert layers[0]["wg"].shape == (64, 8 * 16)
    assert layers[0]["q_head_scale"].shape == (16,)
    assert len(run.record["captures"]) >= 2
