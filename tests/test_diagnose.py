"""Unit tests for the diagnosis engine (dynolog_tpu/diagnose.py) and the
previously-untested diff_summaries edge cases in trace.py: ops present
on only one side, zero-duration baseline ops, empty-plane xspaces."""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from xspace_fixture import build_xspace  # noqa: E402

from dynolog_tpu import diagnose, trace  # noqa: E402


def _summary(ops, steps=None):
    """Hand-rolled summary in the summarize() output shape."""
    out = {"planes": [{"name": "/device:TPU:0", "lines": 1,
                       "events": 1, "duration_ms": 1.0}],
           "top_ops": ops}
    if steps:
        out["steps"] = steps
    return out


def _op(name, total_ms, count, pct=10.0, shapes=None):
    row = {"op": name, "total_ms": total_ms, "count": count, "pct": pct}
    if shapes:
        row["shapes"] = shapes
    return row


# -- diff_summaries edge cases ---------------------------------------------


def test_diff_op_only_in_baseline_contributes_negative_total():
    base = _summary([_op("gone", 4.0, 8)])
    cur = _summary([])
    diff = trace.diff_summaries(base, cur)
    [row] = diff["ops"]
    assert row["op"] == "gone"
    assert row["ms_per_call"] is None
    assert row["base_ms_per_call"] == 0.5
    assert row["count"] == 0
    assert row["impact_ms"] == -4.0


def test_diff_op_only_in_current_contributes_its_total():
    base = _summary([])
    cur = _summary([_op("fresh", 2.5, 5)])
    diff = trace.diff_summaries(base, cur)
    [row] = diff["ops"]
    assert row["op"] == "fresh"
    assert row["base_ms_per_call"] is None
    assert row["ms_per_call"] == 0.5
    assert row["base_count"] == 0
    assert row["impact_ms"] == 2.5


def test_diff_zero_duration_baseline_op_no_division_error():
    # total 0 with count > 0 (marker events): per-call 0, delta = current.
    base = _summary([_op("marker", 0.0, 100)])
    cur = _summary([_op("marker", 1.0, 100)])
    diff = trace.diff_summaries(base, cur)
    [row] = diff["ops"]
    assert row["base_ms_per_call"] == 0.0
    assert row["delta_ms_per_call"] == 0.01
    assert row["impact_ms"] == 1.0


def test_diff_zero_count_baseline_op_treated_as_one_sided():
    # count == 0 rows (a summarizer of an empty window): per-call is
    # unknowable, so the current side's total is the whole impact.
    base = _summary([_op("odd", 3.0, 0)])
    cur = _summary([_op("odd", 2.0, 4)])
    diff = trace.diff_summaries(base, cur)
    [row] = diff["ops"]
    assert row["base_ms_per_call"] is None
    assert row["impact_ms"] == 2.0


def test_diff_empty_plane_xspaces_end_to_end():
    # Entirely empty serialized spaces and plane-without-events spaces
    # flow through summarize -> diff without steps keys or crashes.
    empty = trace._summarize_planes(trace.summarize_xplane_bytes(b""))
    assert empty == {"planes": [], "top_ops": []}
    no_events = build_xspace(planes=1, lines_per_plane=0,
                             events_per_line=0)
    summary = trace._summarize_planes(
        trace.summarize_xplane_bytes(no_events))
    assert summary["planes"][0]["events"] == 0
    assert summary["top_ops"] == []
    diff = trace.diff_summaries(empty, summary)
    assert diff == {"ops": []}
    assert "steps" not in diff


def test_diff_ranks_by_absolute_impact():
    base = _summary([_op("a", 1.0, 10), _op("b", 10.0, 10)])
    cur = _summary([_op("a", 1.2, 10)])  # b vanished: |impact| 10
    diff = trace.diff_summaries(base, cur)
    assert [r["op"] for r in diff["ops"]] == ["b", "a"]


# -- the diagnosis pass -----------------------------------------------------


def test_classify_op():
    assert diagnose.classify_op("all-reduce.17") == "collective"
    assert diagnose.classify_op("reduce-scatter") == "collective"
    assert diagnose.classify_op("fusion.3") == "fusion"
    assert diagnose.classify_op("dot_general") == "matmul"
    assert diagnose.classify_op("copy.4") == "data-movement"
    assert diagnose.classify_op("rsqrt") == "compute"


def test_noise_floor_keeps_verdict_clean():
    base = _summary([_op("fusion.1", 10.0, 100)])
    cur = _summary([_op("fusion.1", 10.2, 100)])  # +2%: noise
    report = diagnose.diagnose(base, cur)
    assert report["verdict"] == "clean"
    assert not any(f["kind"].endswith("_regression")
                   for f in report["findings"])


def test_collective_wait_growth_aggregates():
    base = _summary([_op("all-reduce.1", 2.0, 10),
                     _op("all-gather.2", 1.0, 10)])
    cur = _summary([_op("all-reduce.1", 3.0, 10),
                    _op("all-gather.2", 2.0, 10)])
    report = diagnose.diagnose(base, cur)
    growth = [f for f in report["findings"]
              if f["kind"] == "collective_wait_growth"]
    assert growth, report["findings"]
    assert growth[0]["impact_ms"] == pytest.approx(2.0)
    assert "waiting on a peer" in growth[0]["message"]


def test_collective_wait_growth_names_the_kind_that_grew_most():
    """Gradient reduction or expert exchange: a sparse job's first
    question. The exchange carries the JAX primitive's name on the TPU."""
    base = _summary([_op("all-reduce.1", 2.0, 10),
                     _op("ragged_all_to_all.85", 4.0, 10),
                     _op("all_to_all.82", 0.1, 10)])
    cur = _summary([_op("all-reduce.1", 2.5, 10),
                    _op("ragged_all_to_all.85", 7.0, 10),
                    _op("all_to_all.82", 0.3, 10)])
    report = diagnose.diagnose(base, cur)
    growth, = [f for f in report["findings"]
               if f["kind"] == "collective_wait_growth"]
    assert growth["collective"] == "all-to-all"
    assert growth["impact_ms"] == pytest.approx(3.7)
    assert "+3.200 ms of it in all-to-all" in growth["message"]
    assert diagnose.classify_op("ragged_all_to_all.85") == "collective"
    assert diagnose.classify_op("psum.3") == "collective"


def test_step_regression_and_skew_findings():
    steps_base = {"count": 10, "mean_ms": 10.0, "p50_ms": 10.0,
                  "p95_ms": 11.0, "max_ms": 12.0}
    steps_cur = {"count": 10, "mean_ms": 13.0, "p50_ms": 13.0,
                 "p95_ms": 20.0, "max_ms": 25.0}
    report = diagnose.diagnose(
        _summary([], steps=steps_base), _summary([], steps=steps_cur))
    kinds = {f["kind"] for f in report["findings"]}
    assert "step_time_regression" in kinds
    assert "step_skew_growth" in kinds  # p95/p50 1.1 -> 1.54
    assert report["verdict"] == "regressed"


def test_fusion_shape_change_detected():
    base = _summary([_op("fusion.5", 1.0, 10, shapes=["bf16[128,128]"])])
    cur = _summary([_op("fusion.5", 1.0, 10, shapes=["bf16[256,64]"])])
    report = diagnose.diagnose(base, cur)
    shape = [f for f in report["findings"]
             if f["kind"] == "fusion_shape_change"]
    assert shape and "bf16[128,128] -> bf16[256,64]" in shape[0]["message"]


def test_improvements_reported_but_verdict_clean():
    base = _summary([_op("fusion.1", 10.0, 100)])
    cur = _summary([_op("fusion.1", 5.0, 100)])
    report = diagnose.diagnose(base, cur)
    assert report["verdict"] == "clean"
    assert any(f["kind"] == "fusion_improvement"
               for f in report["findings"])


# -- baseline persistence + resolution --------------------------------------


def test_baseline_roundtrip_and_schema_refusal(tmp_path):
    summary = trace.compact_profile(build_xspace(planes=1))
    path = tmp_path / "base.json"
    doc = diagnose.save_baseline(str(path), summary, model="m1",
                                 source="unit")
    assert doc["schema"] == diagnose.SCHEMA_VERSION
    loaded = diagnose.load_baseline(str(path))
    assert loaded["summary"] == summary
    assert loaded["model"] == "m1"

    bad = json.loads(path.read_text())
    bad["schema"] = diagnose.SCHEMA_VERSION + 1
    (tmp_path / "future.json").write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="schema"):
        diagnose.load_baseline(str(tmp_path / "future.json"))
    (tmp_path / "not_baseline.json").write_text('{"foo": 1}')
    with pytest.raises(ValueError, match="summary"):
        diagnose.load_baseline(str(tmp_path / "not_baseline.json"))


def test_resolve_summary_adopts_newest_pid_manifest(tmp_path):
    # The auto-trigger hands the engine a PREDICTED path; the shim wrote
    # the real per-pid manifest next to it — resolution must adopt it.
    trace_dir = tmp_path / "cap_123"
    run = trace_dir / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(build_xspace(planes=1))
    (tmp_path / "cap_123.json").write_text(
        json.dumps({"trace_dir": str(trace_dir),
                    "trace_ctx": "00000000000000ab/00000000000000cd"}))
    summary, meta = diagnose.resolve_summary(str(tmp_path / "cap.json"))
    assert meta["resolved_from"] == str(tmp_path / "cap.json")
    assert meta["kind"] == "manifest"
    assert meta["trace_ctx"].startswith("00000000000000ab/")
    assert summary["top_ops"]


def test_cli_json_report_is_machine_readable(tmp_path, capsys):
    base = tmp_path / "b.xplane.pb"
    cur = tmp_path / "c.xplane.pb"
    base.write_bytes(build_xspace(planes=1))
    cur.write_bytes(build_xspace(planes=1, op_duration_scale={2: 3.0}))
    rc = diagnose.main([str(cur), "--baseline", str(base), "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["kind"] == "dynolog_tpu.diagnosis"
    assert report["verdict"] == "regressed"
    assert report["findings"][0]["op"] == "fusion.2"
    assert report["baseline"]["kind"] == "trace"
    # And the engine journals diagnose.* spans for the selftrace merge.
    from dynolog_tpu import obs

    names = {s.name for s in obs.JOURNAL.snapshot()}
    assert "diagnose.engine" in names
    assert "diagnose.diff" in names
