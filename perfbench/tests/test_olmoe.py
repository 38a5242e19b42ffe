"""The expert-parallel configuration as data, its three readers on records
written here, and its whole normal path over four virtual CPU devices (mesh
`expert` 4; XLA:CPU runs no ragged all-to-all, so the exchange is the padded
one there)."""

import pytest

import cells
import rehearsal

NAME = "olmoe-1b-7b-v5e4"
CELL = "olmoe-1b-7b-v5e4.capture"
MS = 1_000_000_000  # picoseconds in a millisecond
NEW_READERS = ("xspan.moe_expert_op_pct", "xspan.xla_all_to_all_pct",
               "xspan.xla_op_census")


@pytest.fixture(scope="module")
def readers():
    return cells.load_readers()


def test_every_width_is_as_published_and_only_depth_and_batch_are_cut():
    config = cells.load_config(NAME)
    job, published = config["job"], config["published"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("d_ff", "intermediate_size"),
            ("n_heads", "num_attention_heads"), ("vocab_size", "vocab_size"),
            ("n_experts", "num_experts"), ("moe_top_k", "num_experts_per_tok"),
            ("seq", "max_position_embeddings"), ("rope_theta", "rope_theta"),
            ("norm_eps", "rms_norm_eps"), ("moe_norm_topk", "norm_topk_prob"),
            ("moe_aux_weight", "router_aux_loss_coef"),
            ("dtype", "torch_dtype")):
        assert job[ours] == published[theirs], ours
    assert published["num_key_value_heads"] == job["n_heads"]
    assert config["reduced"] == ["num_hidden_layers", "batch"]
    assert 8 <= job["n_layers"] < published["num_hidden_layers"]
    assert config["deployment"]["mesh"] == {"expert": 4}
    assert job["n_experts"] % config["deployment"]["mesh"]["expert"] == 0
    assert job["batch"] == config["deployment"]["chips"]
    assert f"{job['n_layers']} layers" in config["aot"]
    # 6.92 B whole and 1.28 B active a token: the check on recalled values
    d, f, e, k, v = (published[key] for key in (
        "hidden_size", "intermediate_size", "num_experts",
        "num_experts_per_tok", "vocab_size"))
    outside = 4 * d * d + d * e + 4 * d
    whole = 16 * (e * 3 * d * f + outside) + 2 * v * d + d
    active = 16 * (k * 3 * d * f + outside) + 2 * v * d + d
    assert round(whole / 1e9, 2) == 6.92 and round(active / 1e9, 2) == 1.28


def test_source_and_reduced_differ_from_every_other_configuration():
    bench = cells.load_benchmark()
    mine = next(c for c in bench["configs"] if c["name"] == NAME)
    assert mine == bench["configs"][-1]
    for other in bench["configs"][:-1]:
        assert other["source"] != mine["source"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["chips"]) == (CELL, NAME, 4)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= (
        len(bench["workloads"]) // 2)


def test_every_capture_reader_lists_the_cell_but_the_one_it_cannot_read(
        readers):
    bench = cells.load_benchmark()
    unread = cells.load_config(NAME)["no_reading"]
    assert sorted(unread) == ["first_capture_ms", "step_ms_p95.capture"]
    for entry in bench["per_layer"]:
        if "capture" in readers[entry["name"]].CELLS:
            listed = CELL in entry.get("workloads", [CELL])
            assert listed == (entry["name"] not in unread), entry["name"]
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW_READERS)
    for name in ("capture_ms_p50", "derived_ms_p50"):
        entry = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert entry["workloads"][-1] == CELL


# ------------------------------------------------------------ the readers


def xspace_file(tmp_path, planes: int, sparse: bool) -> str:
    """`planes` device planes, each 6 ms of fusion and 2 of all-reduce on
    "XLA Ops"; a sparse job's hold besides 3 ms in two grouped products with
    their 1 ms of tile metadata, and 4 + 1 ms of exchange under the names the
    TPU gives what the program wrote."""
    from jax.profiler import ProfileData

    ops = [("%fusion.1 = bf16[8,8]{1,0} fusion(%p0)", 6),
           ("%all-reduce.2 = bf16[8,8]{1,0} all-reduce(%p1)", 2)]
    if sparse:
        ops += [
            ("%ragged-dot-none.3 = bf16[64,8]{1,0} custom-call(%p2)", 2),
            ("%ragged-dot-none.4 = bf16[64,8]{1,0} custom-call(%p3)", 1),
            ("%ragged-dot-metadata.5 = s32[17]{0} custom-call(%p4)", 1),
            ("%ragged_all_to_all.85 = bf16[64,8]{1,0} ragged-all-to-all(%p5)",
             4),
            ("%all_to_all.82 = s32[4,1,1]{2,1,0} all-to-all(%p6)", 1)]
    text = ""
    for i in range(planes):
        events, at = "", 0
        for meta, (_, ms) in enumerate(ops, start=1):
            events += (f"events {{ metadata_id: {meta} offset_ps: {at * MS} "
                       f"duration_ps: {ms * MS} }}")
            at += ms
        metadata = "".join(
            f'event_metadata {{ key: {meta} value {{ id: {meta} '
            f'name: "{name}" }} }}'
            for meta, (name, _) in enumerate(ops, start=1))
        text += f"""
planes {{ id: {i + 1} name: "/device:TPU:{i}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 {events} }}
  {metadata}
}}"""
    path = tmp_path / f"p{planes}{int(sparse)}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def test_a_dense_job_reads_zero_by_measurement_and_its_census(
        tmp_path, readers):
    rec = {"device": {"count": 4},
           "trace": {"path": xspace_file(tmp_path, 4, sparse=False)}}
    assert readers["xspan.xla_all_to_all_pct"].read(rec) == 0.0
    assert readers["xspan.moe_expert_op_pct"].read(rec) == 0.0
    assert readers["xspan.xla_op_census"].read(rec) == 2.0
    assert readers["xspan.xla_collective_pct"].read(rec) == pytest.approx(25.0)


def test_a_sparse_job_reads_what_was_put_in(tmp_path, readers):
    rec = {"device": {"count": 4},
           "trace": {"path": xspace_file(tmp_path, 4, sparse=True)}}
    # of 17 ms a plane: 5 of exchange, 4 of the grouped products
    assert readers["xspan.xla_all_to_all_pct"].read(rec) == pytest.approx(
        100.0 * 5 / 17)
    assert readers["xspan.moe_expert_op_pct"].read(rec) == pytest.approx(
        100.0 * 4 / 17)
    assert readers["xspan.xla_op_census"].read(rec) == 7.0
    # the accepted reader tests `all-to-all` on the op's name as it stands
    # and misses the exchange the program wrote (PERF.md, Open questions)
    assert readers["xspan.xla_collective_pct"].read(rec) == pytest.approx(
        100.0 * 2 / 17)


def test_a_run_without_a_trace_reads_nothing_and_does_not_raise(readers):
    rec = {"device": {"count": 4}, "captures": []}
    for name in NEW_READERS:
        assert readers[name].read(rec) is None


# ------------------------------------------------- the whole normal path


def test_whole_run_over_the_expert_mesh_reaches_its_end(monkeypatch, tmp_path):
    """harness.measure() at toy size over four virtual devices, expert 4:
    the experts' weights and Adam state born sharded by expert, check J
    through forward(..., mesh) against olmoe_block's reference on the
    sharded weights (float32 on both sides here, so it passes the module's
    limits by far), the ahead-of-time compiled step fed its own outputs. A
    CPU writes no /device:TPU plane, so C1-C3 read false."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seed=2**31 + 34, seconds=3.0,
        trace=True, config="toy-olmoe4")
    failed = [c["name"] for c in run.record["checks"] if not c["ok"]]
    assert failed == ["C1", "C2", "C3"] and line["failed"] == 0
    j = next(c for c in run.record["checks"] if c["name"] == "J")
    assert [p["value"] < 1e-4 for p in j["compared"]] == [True, True]
    experts = run.state[0]["layers"][0]["experts_gate"]
    assert experts.addressable_shards[0].data.shape[0] == 2  # 8 over 4
    assert run.tokens.shape == (4, 128)
    assert line["device"]["count"] == 4
    assert len(run.record["captures"]) >= 2
