"""The Kimi-Linear configuration as data, its module's exports, its reader
on a record written here, and its whole normal path at toy size on the CPU."""

import json

import pytest

import cells
import gen_benchmark
import kernel_costs
import rehearsal
import scope_ops
from test_deepseek_v2 import xspace_file

NAME = "kimi-linear-5l-v5e1"
CELL = "kimi-linear.capture"
READER = "xspan.kda_scope_pct"
ROOFLINES = ("xspan.flash_fwd_roofline_pct", "xspan.flash_bwd_dq_roofline_pct",
             "xspan.flash_bwd_dkv_roofline_pct")
WINDOWED = ("xspan.flash_window_fwd_roofline_pct",
            "xspan.flash_window_bwd_dq_roofline_pct",
            "xspan.flash_window_bwd_dkv_roofline_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# (op, its path or None, start us, length us)
STEP = (
    ("%fusion.1 = f32[8]{0} fusion(%a)",
     "jit(step)/jvp(kda.project)/dot_general:", 0, 30),
    ("%while.2 = f32[8]{0} while(%b)",
     "jit(step)/jvp(checkpoint)/kda.scan/while:", 30, 50),
    ("%fusion.3 = f32[8]{0} fusion(%c)",
     "jit(step)/transpose(jvp(checkpoint))/rematted_computation/"
     "kda.chunk_prepare/triangular_solve:", 80, 20),
    ("%flash_attention_fwd.4 = f32[8]{0} custom-call(%d)",
     "jit(step)/jvp(mla.attend)/flash_attention_fwd/pallas_call:", 100, 60),
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
    # the rule one token at a time: no chunk, no sub-block, no solve
    assert "jax.lax.scan(token" in source
    assert "solve_triangular" not in source and "cumsum" not in source


def test_every_width_is_as_published_and_the_cut_is_written_down():
    config = cells.load_config(NAME)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    cut = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 20480}
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():  # at the top level, as run
        assert config[key] == cut.get(key, value), key
    assert config["published"] == {key: row["config"][key] for key in cut}
    assert config["reduced"] == [*cut, "batch", "sequence"]
    assert set(config["reduced_from"]) == set(config["reduced"])
    job, linear = config["job"], row["config"]["linear_attn_config"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("d_ff", "intermediate_size"),
            ("n_heads", "num_attention_heads"),
            ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"),
            ("first_dense_layers", "first_k_dense_replace"),
            ("moe_d_ff", "moe_intermediate_size"),
            ("n_shared_experts", "num_shared_experts"),
            ("moe_top_k", "num_experts_per_token"),
            ("moe_norm_topk", "moe_renormalize"),
            ("moe_gate_scale", "routed_scaling_factor"),
            ("moe_score", "moe_router_activation_func"),
            ("max_seq_len", "model_max_length"),
            ("norm_eps", "rms_norm_eps")):
        assert job[ours] == row["config"][theirs], ours
    assert job["n_heads"] == linear["num_heads"] == 32
    assert (job["linear_key_head_dim"] == job["linear_value_head_dim"]
            == linear["head_dim"] == 128)
    assert job["linear_conv_kernel"] == linear["short_conv_kernel_size"] == 4
    # the job runs published layers 1 to 5: one dense, one whole period
    kinds = ["kda" if i in linear["kda_layers"] else "full_attention"
             for i in range(1, 6)]
    assert 4 in linear["full_attn_layers"] and kinds.count("kda") == 4
    assert job["layer_types"] == kinds == [
        "kda", "kda", "kda", "full_attention", "kda"]
    # nothing is rotated, and the source has no query compression
    assert row["config"]["mla_use_nope"] is True and job["rope_theta"] is None
    assert row["config"]["q_lora_rank"] is None and job["attn_type"] == "mla"
    assert job["moe_select_bias"] is True and job["mlp_act"] == "swiglu"
    assert (job["moe_aux_weight"], job["moe_z_weight"]) == (0.0, 0.0)
    # the share: the router keeps the published 256, the chip holds 32
    assert job["n_experts"] == row["config"]["num_experts"] == 256
    assert job["n_experts_held"] == config["num_experts"] == 32
    assert job["first_expert_held"] == 0
    assert (job["n_layers"], job["vocab_size"]) == (5, 20480)
    assert (job["batch"], job["seq"]) == (1, 4096)
    # the floors: a whole period of at least four, 8 experts, an eighth
    assert job["n_layers"] - job["first_dense_layers"] >= 4
    assert job["n_experts_held"] >= 16
    assert job["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert config["deployment"]["mesh"] is None
    assert config["deployment"]["chips"] == 1
    assert set(WINDOWED) <= set(config["no_reading"])
    assert not set(ROOFLINES) & set(config["no_reading"])
    for key in ("aot", "assumed", "departures"):
        assert config[key] and "TODO" not in json.dumps(config[key]), key
    assert "eight chips" in config["deployment"]["stands_for"]


def test_the_parameters_are_as_many_as_reckoned():
    import jax

    config = cells.load_config(NAME)
    module = cells.load_reference(config)
    shapes = jax.eval_shape(
        lambda k: module.init_weights(k, config["job"]), jax.random.PRNGKey(0))
    size = lambda tree: sum(  # noqa: E731
        a.size for a in jax.tree_util.tree_leaves(tree))
    layers = [size(layer) for layer in shapes["layers"]]
    kda = (3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096)
           + 2304 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128)
    latent = (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
              + 512)
    expert, dense, norms = 3 * 2304 * 1024, 3 * 2304 * 9216, 2 * 2304
    sparse = 32 * expert + expert + 2304 * 256 + 256  # held, shared, router
    # as the issue reckons them
    assert [round(n / 1e6, 1) for n in (kda, latent, dense)] == [
        39.5, 29.1, 63.7]
    assert round(expert / 1e6, 2) == 7.08
    assert layers == [kda + dense + norms, kda + sparse + norms,
                      kda + sparse + norms, latent + sparse + norms,
                      kda + sparse + norms]
    assert round(size(shapes) / 1e9, 2) == 1.28
    # uncut: layer 1 dense, 19 KDA and 7 latent sparse layers of 256
    # experts, the whole vocabulary
    whole = sparse + (256 - 32) * expert
    total = (kda + dense + 19 * (kda + whole) + 7 * (latent + whole)
             + 27 * norms + 2 * 163840 * 2304 + 2304)
    assert round(total / 1e9, 1) == 49.1


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
    assert {READER, *ROOFLINES, "xspan.mla_scope_pct", "xspan.xla_while_pct",
            "xspan.moe_expert_op_pct", "xspan.moe_experts_scope_pct",
            "xspan.moe_shared_scope_pct", "xspan.ssm_scope_pct",
            "xspan.attn_window_scope_pct"} <= set(due)
    assert not set(WINDOWED) & set(due)  # named under no_reading
    assert cells.metric_names(bench, cell, "end_to_end") == [
        "step_ms_p50", "capture_ms_p50", "setup_s", "derived_ms_p50"]
    # the new reader is held to the new cell alone
    entry = next(m for m in bench["per_layer"] if m["name"] == READER)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "step_ms_p50" and entry["unit"] == "%"
    # and the table is what the generator keeps of it: nothing moves
    assert gen_benchmark.per_layer(bench) == bench["per_layer"]
    # the latent layer's kernels are read at its own widths
    assert kernel_costs.head_widths(cell.job) == (192, 128)
    flops, nbytes = kernel_costs.call_cost(cell.job, "flash_attention_fwd")
    assert flops == 32 * 2 * (4096 * 4096 / 2) * (192 + 128)
    assert nbytes == 32 * 4096 * (2 * 192 + 2 * 128) * 2


def test_the_reader_reads_what_was_put_in(tmp_path):
    if scope_ops.binding() is None:
        pytest.skip("no wheel here ships xplane_pb2")
    reader = cells.load_readers()[READER]
    rec = {"workload": CELL, "device": {"count": 1, "kind": "TPU v5 lite"},
           "trace": {"path": xspace_file(tmp_path, STEP)}}
    # 200 us of ops: 100 under kda.* (forward, the loop, the rule computed
    # again in the backward pass), the latent layer's kernel not among them
    assert reader.read(rec) == pytest.approx(50.0)
    assert cells.load_readers()["xspan.mla_scope_pct"].read(rec) == (
        pytest.approx(30.0))
    # a job without the layer reads 0.0 because its planes were summed
    other = [(name, path and path.replace("kda.", "gdn."), at, length)
             for name, path, at, length in STEP]
    rec = {"workload": "olmo-hybrid-7b.capture",
           "device": {"count": 1, "kind": "TPU v5 lite"},
           "trace": {"path": xspace_file(tmp_path, other[:4])}}
    assert reader.read(rec) == 0.0
    # a run without a trace reads nothing and does not raise
    assert reader.read({"device": {"count": 1}}) is None


def test_whole_run_of_the_toy_reaches_its_end(monkeypatch, tmp_path):
    """harness.measure() at toy size: the module's weights through the
    program's step, check J against the plain reference (float32 on both
    sides here). A CPU writes no /device:TPU plane, so C1-C3 read false."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seed=2**31 + 52, seconds=3.0,
        trace=True, config="toy-kimi-linear")
    failed = [c["name"] for c in run.record["checks"] if not c["ok"]]
    # (C2 holds or not by whether a step fell between a capture's marks)
    assert {"C1", "C3"} <= set(failed) <= {"C1", "C2", "C3"}
    assert line["failed"] == 0
    j = next(c for c in run.record["checks"] if c["name"] == "J")
    assert [p["value"] < 1e-4 for p in j["compared"]] == [True, True]
    layers = run.state[0]["layers"]
    assert ["router" in layer for layer in layers] == [
        False, True, True, True, True]
    assert ["kda_q" in layer for layer in layers] == [
        True, True, True, False, True]
    assert layers[1]["experts_up"].shape[0] == 2
    assert layers[1]["router"].shape[1] == 16
    assert layers[1]["kda_dt_bias"].shape == (4 * 16,)
    assert layers[3]["mla_dkv"].shape == (64, 32 + 8)
    assert len(run.record["captures"]) >= 2
