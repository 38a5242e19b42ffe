"""A whole run on the CPU at a toy size, the look for a chip stubbed here:
sound, `correct` is true; with the timed path broken underneath, false."""

import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import cells
import harness
import rehearsal
import selftrace

@pytest.fixture(scope="module")
def audit():
    """One audit hook a process (a hook cannot be taken off again); it
    records a file opened into `into[0]` while a test has a list there."""
    into: list = []

    def hook(event, args):
        if into and event == "open" and isinstance(
                args[0], (str, bytes, os.PathLike)):
            into[0].append(os.fsdecode(args[0]))

    sys.addaudithook(hook)
    return into


@pytest.fixture
def opened(audit):
    audit.append([])
    yield audit[0]
    audit.clear()


def failed(run) -> list:
    return [c["name"] for c in run.record["checks"] if not c["ok"]]


def test_sound_steady_run_is_correct(monkeypatch, tmp_path):
    run, line = rehearsal.rehearse(monkeypatch, tmp_path, "steady")
    assert failed(run) == [] and line["correct"] is True
    assert [c["name"] for c in run.record["checks"]] == ["J", "S1", "S2", "C4"]
    assert line["failed"] == 0 and line["attempted"] == len(run.record["step_ms"])
    assert run.record["setup_s"] > 0 and sum(run.record["step_ms"]) > 3000
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "compared"]
    # every number compared, beside its limit, in the line itself
    assert [row[0] for row in line["compared"]].count("J") == 2
    assert all(row[4] for row in line["compared"])
    # the journals are asked for in traced runs alone; the RSS in every run
    assert "selftrace" not in run.record and "shim_counters" not in run.record
    assert run.record["daemon_rss_kb"] > 1000


def test_a_job_of_another_block_runs_from_test_data_alone(
        monkeypatch, tmp_path, opened):
    """The seam: `toy-moe.json` names `tests/data/moe_block.py`, whose
    weights (a router, stacked experts) `perfbench/reference.py` cannot
    make. The whole run passes J on that module's own limits, and of
    perfbench/ it opened its test data and the harness's own files: no
    configuration of a cell and not the dense block's module, which
    `import reference` would find no more."""
    monkeypatch.setitem(sys.modules, "reference", None)
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "steady", config="toy-moe")
    assert failed(run) == [] and line["correct"] is True
    assert [c["name"] for c in run.record["checks"]] == ["J", "S1", "S2", "C4"]
    assert sorted(run.state[0]["layers"][0]) == [
        "attn_scale", "experts_down", "experts_gate", "experts_up",
        "mlp_scale", "router", "wk", "wo", "wq", "wv"]
    rows = [row for row in line["compared"] if row[0] == "J"]
    assert [row[3] for row in rows] == ["<= 0.001", "<= 0.001"]
    assert rows[0][2] < 1e-5 and rows[1][2] < 1e-5  # float32 on both sides
    inside = set()
    for name in opened:
        path = Path(name).resolve()
        if cells.HERE in path.parents:
            source = re.sub(r"\.cpython-\d+\.pyc$", ".py", path.name)
            inside.add(str((path.parent / source).relative_to(
                cells.HERE)).replace("__pycache__/", ""))
    assert {"tests/data/moe_block.py", "tests/data/toy-moe.json",
            "traffic/steady.json"} <= inside
    own = {p.name for p in cells.HERE.glob("*.py")} - {"reference.py"}
    for name in inside:
        first = name.split("/")[0]
        assert (first in ("tests", "metrics", "end_to_end", "traffic")
                or name in own or name == "peaks.json"), name


def test_the_other_blocks_own_control_is_not_correct(monkeypatch, tmp_path):
    """The control of that module, in the program's place: the program's
    forward on the toy weights rounded through the module's own `lower`
    (bfloat16 under the float32 the configuration states; the router with
    them) fails J's first number on the module's limit, and nothing else."""
    import dynolog_tpu.models.transformer as program

    module = cells.load_reference(
        rehearsal.toy_cell("steady", "toy-moe").config)
    forward = program.forward

    def lowered(params, tokens, cfg, mesh=None):
        params = jax.tree_util.tree_map(
            lambda w: module.lower(w).astype(w.dtype), params)
        return forward(params, tokens, cfg, mesh)

    monkeypatch.setattr(program, "forward", lowered)
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "steady", config="toy-moe")
    assert failed(run) == ["J"] and line["correct"] is False
    j = run.record["checks"][0]["compared"]
    assert [p["ok"] for p in j] == [False, True]
    assert j[0]["value"] > 3 * module.J_LOGIT_REL_RMS_LIMIT


def test_step_telemetry_lost_is_not_correct(monkeypatch, tmp_path):
    """The job steps but the shim is never told: S1 finds no samples."""
    def broken(run):
        run.client.step = lambda: None

    run, line = rehearsal.rehearse(monkeypatch, tmp_path, "steady", broken=broken)
    assert failed(run) == ["S1"] and line["correct"] is False
    # what failed comes last: the end of the line is what a record keeps
    assert line["compared"][-1][0] == "S1" and not line["compared"][-1][4]


def test_step_that_leaves_out_half_the_batch_is_not_correct(monkeypatch, tmp_path):
    """The compiled step is handed a batch whose second half repeats the
    first: its first loss is not the reference's."""
    def broken(run):
        half = run.tokens.shape[0] // 2
        run.tokens = jnp.concatenate([run.tokens[:half], run.tokens[:half]])

    run, line = rehearsal.rehearse(monkeypatch, tmp_path, "steady", broken=broken)
    assert failed(run) == ["J"] and line["correct"] is False


def test_capture_run_drives_every_check(monkeypatch, tmp_path):
    """A CPU has no /device:TPU:0 plane, so C1-C3 read false here; the run
    still has to reach its end, count its captures and leave nothing. C5
    holds: every capture has both derived files, whole, and the summary of
    the last says what the plain reducer says of its host planes."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seconds=3.0, trace=True)
    names = [c["name"] for c in run.record["checks"]]
    assert names == ["J", "S1", "S2", "C1", "C2", "C3", "C4", "C5"]
    assert failed(run) == ["C1", "C2", "C3"]
    assert len(run.record["captures"]) >= 2
    assert all(c["ok"] for c in run.record["captures"])
    assert line["correct"] is False
    # the hook, against the real daemon and CLI: a tick a second, one verb
    # and one hand-off a capture (the warm one too), the counters
    rec = run.record
    journal, captures = rec["selftrace"], len(rec["captures"]) + 1
    count = {}
    for span in journal["spans"]:
        count[span["name"]] = count.get(span["name"], 0) + 1
    assert count[selftrace.CAPTURE_VERB] == count[selftrace.HANDOFF] == captures
    assert count["shim.capture"] == captures
    in_window = selftrace.window_ms(rec, selftrace.TPU_TICK)
    assert 2 <= len(in_window) <= 4  # 3 s of window, one tick a second
    assert journal["tpu_rows"] == 4 and journal["ipc_wakeups"]["message"] > 0
    assert rec["selftrace_oldest_ms"] < rec["window_start"] * 1e3
    assert rec["shim_counters"]["traces_completed"] == captures
    assert rec["shim_counters"]["steps"] == len(run.steps)
    readers = cells.load_readers()
    for name in ("tpu_tick_ms_p50", "kernel_tick_ms_p50", "rpc_verb_ms",
                 "ipc_handoff_ms", "ipc_timeout_wakeup_pct", "daemon_rss_mb",
                 "first_capture_ms", "longest_pass_tick_overlap_ms",
                 "convert_ms", "convert_lag_ms", "convert_alive_max",
                 "derived_bytes"):
        assert readers[name].read(rec) is not None, name
    # the children were waited for before the journal was read: every
    # capture's conversion is in it, the warm one's too, under its trace id
    assert count[selftrace.CONVERT] == captures
    begun = selftrace.convert_starts_us(rec)
    for cap in rec["captures"]:
        assert cap["manifest"]["trace_ctx"].split("/")[0] in begun
        assert cap["derived_ms"] > cap["capture_ms"]
        assert cap["derived"]["tmp"] == []
    assert len(rec["derived_ms"]) == len(rec["captures"])
    assert harness.end_to_end(rec)["derived_ms_p50"] > (
        harness.end_to_end(rec)["capture_ms_p50"])
    assert 0 < readers["convert_lag_ms"].read(rec) < 5000
    assert readers["first_capture_ms"].read(rec) == (
        rec["warm_capture"][0]["capture_ms"])


def test_an_export_child_that_fails_is_not_correct(monkeypatch, tmp_path):
    """The control of C5: `DYNO_FAILPOINTS` names `trace.convert`, so every
    export child dies as a killed one does, before it writes anything. The
    captures themselves are what they were (C1-C3 read as in the sound run
    above, C4 holds: the children are gone); C5 alone is added."""
    monkeypatch.setenv("DYNO_FAILPOINTS", "trace.convert=throw")
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seconds=3.0)
    assert failed(run) == ["C1", "C2", "C3", "C5"]
    assert line["correct"] is False
    captures = run.record["captures"]
    assert len(captures) >= 2 and all(c["ok"] for c in captures)
    assert all("derived_ms" not in c for c in captures)
    assert all(c["derived"][".summary.json"] is None for c in captures)
    metrics = harness.end_to_end(run.record)
    assert metrics["derived_ms_p50"] is None and metrics["capture_ms_p50"] > 0
    c5 = next(c for c in run.record["checks"] if c["name"] == "C5")
    assert not c5["compared"][0]["ok"]
    assert line["compared"][-1][0] == "C5"
