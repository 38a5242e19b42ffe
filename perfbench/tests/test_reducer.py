"""The plain reducer, and C3's comparison, on a small xplane written here as
a text proto: three op kinds on "XLA Ops", a gap, an async line that must
not count, and a "XLA Modules" line with three launches of jit_step."""

import pytest

import checks
import xplane

MS = 1_000_000_000  # picoseconds in a millisecond


def xspace_text(fusion2_ps: int = 2 * MS) -> str:
    def ev(meta, offset_ms, dur_ps):
        return (f"events {{ metadata_id: {meta} offset_ps: {offset_ms * MS} "
                f"duration_ps: {dur_ps} }}")

    def meta(i, name):
        return (f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}')

    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    {ev(10, 0, 4 * MS)} {ev(10, 5, 4 * MS)} {ev(10, 10, 2 * MS)} {ev(11, 13, MS)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    {ev(1, 0, MS)} {ev(2, 1, fusion2_ps)} {ev(3, 3, MS)}
    {ev(1, 5, MS)} {ev(2, 6, 2 * MS)} {ev(3, 8, MS)} }}
  lines {{ id: 3 name: "Async XLA Ops" timestamp_ns: 1000 {ev(4, 0, 9 * MS)} }}
  {meta(1, "%fusion.1 = bf16[8,8]{{1,0}} fusion(%p0)")}
  {meta(2, "%fusion.2 = bf16[8,8]{{1,0}} fusion(%p1)")}
  {meta(3, "%custom-call.7 = bf16[8]{{0}} custom-call(%p2)")}
  {meta(4, "%copy-start.1 = bf16[8]{{0}} copy-start(%p3)")}
  {meta(10, "jit_step(123)")} {meta(11, "jit_other(5)")}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 7 name: "main" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: {4 * MS} duration_ps: {MS} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "perfbench.between_steps" }} }}
}}
"""


def serialized(**kw) -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(xspace_text(**kw))


@pytest.fixture(scope="module")
def profile():
    return xplane.load_bytes(serialized())


def test_per_op_totals_and_counts(profile):
    red = xplane.reduce_plane(xplane.find_plane(profile, "/device:TPU:0"))
    assert red.events == 6  # the async line does not count
    assert red.ops == {"fusion.1": [2e6, 2], "fusion.2": [4e6, 2],
                       "custom-call.7": [2e6, 2]}
    assert red.groups() == {"fusion": 6e6, "custom-call": 2e6}
    assert red.top_groups(1) == [["fusion", 6e-3]]


def test_busy_union_and_idle_share(profile):
    red = xplane.reduce_plane(xplane.find_plane(profile, "/device:TPU:0"))
    # ops cover [0, 4) and [5, 9) ms of a 9 ms span: one 1 ms gap
    assert red.busy_ns == pytest.approx(8e6)
    assert red.span_ns == pytest.approx(9e6)
    assert red.idle_pct == pytest.approx(100 / 9)
    assert [round(g[0]) for g in red.gaps] == [1_000_000]


def test_overlapping_events_are_not_counted_twice():
    from jax.profiler import ProfileData

    text = xspace_text().replace(
        'lines { id: 3 name: "Async XLA Ops"', 'lines { id: 3 name: "XLA Ops x"')
    text = text.replace("offset_ps: 1000000000 duration_ps: 2000000000",
                        "offset_ps: 500000000 duration_ps: 2500000000")
    prof = xplane.load_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    red = xplane.reduce_plane(xplane.find_plane(prof, "/device:TPU:0"))
    assert red.busy_ns == pytest.approx(8e6)  # fusion.2 now overlaps fusion.1


def test_step_execution_count(profile):
    plane = xplane.find_plane(profile, "/device:TPU:0")
    assert xplane.count_executions(plane, "jit_step") == 3
    assert xplane.count_executions(plane, "jit_other") == 1
    assert xplane.count_executions(plane, "jit_absent") == 0


def test_missing_plane_or_line_reduces_to_none(profile):
    assert xplane.find_plane(profile, "/device:TPU:1") is None
    assert xplane.reduce_plane(None) is None
    assert xplane.reduce_plane(xplane.find_plane(profile, "/host:CPU")) is None


def test_gap_is_named_after_the_host_span_over_its_middle(profile):
    red = xplane.reduce_plane(xplane.find_plane(profile, "/device:TPU:0"))
    spans = xplane.host_spans(profile, "perfbench.")
    assert [s[0] for s in spans] == ["perfbench.between_steps"]
    assert xplane.label_gaps(red.gaps, spans, "perfbench.") == [
        ["between_steps", pytest.approx(1e-3)]]
    assert xplane.label_gaps(red.gaps, [], "perfbench.")[0][0] == "other"


def test_c3_product_summary_equals_plain_reducer(profile):
    plain = xplane.reduce_plane(xplane.find_plane(profile, "/device:TPU:0"))
    product = checks.product_summary(serialized(), "/device:TPU:0")
    assert product == {"fusion.1": (2 * MS, 2), "fusion.2": (4 * MS, 2),
                       "custom-call.7": (2 * MS, 2)}
    assert checks.compare_summaries(product, plain.ops) == []
    # ProfileData rounds each event down to whole ns: 999 ps an event pass
    sub_ns = checks.product_summary(
        serialized(fusion2_ps=2 * MS + 999), "/device:TPU:0")
    assert sub_ns["fusion.2"] == (4 * MS + 999, 2)
    assert checks.compare_summaries(sub_ns, plain.ops) == []


def test_c3_fails_when_one_op_duration_is_altered(profile):
    """The product summarizes bytes in which fusion.2 ran 2 us longer than
    in the bytes the plain reducer read: C3 names the op and both readings."""
    plain = xplane.reduce_plane(xplane.find_plane(profile, "/device:TPU:0"))
    product = checks.product_summary(
        serialized(fusion2_ps=2 * MS + 2_000_000), "/device:TPU:0")
    differ = checks.compare_summaries(product, plain.ops)
    assert [d[0] for d in differ] == ["fusion.2"]
    assert differ[0][1] == (4 * MS + 2_000_000, 2)
    # a dropped op and a miscount are caught as well
    assert checks.compare_summaries({}, plain.ops)
    miscount = dict(product, **{"fusion.1": (2 * MS, 3)})
    assert "fusion.1" in [d[0] for d in checks.compare_summaries(
        miscount, plain.ops)]


# ------------------------------------------- C1/C2 over a run's captures

EMPTY_XSPACE = 'planes { id: 1 name: "/device:TPU:0" } planes { id: 2 name: "/host:CPU" }'


def fake_run(tmp_path, captures: list):
    """A run as check_captures reads it. `captures`: (xspace bytes, steps the
    job completed in the window). Windows are 500 ms, ten seconds apart; the
    job's passes outside the windows do not matter here."""
    from types import SimpleNamespace

    steps, records = [], []
    for k, (data, inside) in enumerate(captures):
        trace_dir = tmp_path / f"cap{k}"
        pb = trace_dir / "plugins" / "profile" / "x" / "h.xplane.pb"
        pb.parent.mkdir(parents=True)
        pb.write_bytes(data)
        start = 1000.0 + 10 * k
        steps += [(start + 0.1 * (i + 1), 100.0) for i in range(inside)]
        records.append({"k": k, "cli_rc": 0, "ok": True, "manifest": {
            "trace_dir": str(trace_dir), "started_ms": start * 1e3 - 60,
            "timing": {"profiler_start_ms": 60, "xspace_bytes": len(data)},
            "config": {"ACTIVITIES_DURATION_MSECS": "500"}}})
    cell = SimpleNamespace(chips=1, job={"step_module": "jit_step"})
    return SimpleNamespace(cell=cell, steps=steps,
                           record={"captures": records})


def verdicts(run) -> dict:
    return {c["name"]: c["ok"] for c in checks.check_captures(run)}


def empty() -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(EMPTY_XSPACE)


def test_captures_that_hold_their_windows_pass(tmp_path):
    run = fake_run(tmp_path, [(serialized(), 3), (serialized(), 2)])
    assert verdicts(run) == {"C1": True, "C2": True, "C3": True}
    assert run.record["captures"][0]["executions"] == [3]


def test_a_window_the_job_stalled_through_owes_no_device_events(tmp_path):
    """The job completed nothing in the second window and the plane is
    empty: the capture is whole, and C3 reads the last that holds events."""
    run = fake_run(tmp_path, [(serialized(), 3), (empty(), 0)])
    assert verdicts(run) == {"C1": True, "C2": True, "C3": True}
    stalled = run.record["captures"][1]
    assert stalled["executions"] == [0] and "device_ns" not in stalled


def test_an_empty_plane_while_the_job_stepped_is_not_correct(tmp_path):
    run = fake_run(tmp_path, [(serialized(), 3), (empty(), 3)])
    assert verdicts(run)["C1"] is False


def test_a_wrong_window_is_not_correct(tmp_path):
    # three executions on the plane while the job completed none
    run = fake_run(tmp_path, [(serialized(), 3), (serialized(), 0)])
    assert verdicts(run)["C2"] is False


def test_only_empty_captures_are_not_correct(tmp_path):
    run = fake_run(tmp_path, [(empty(), 0)])
    assert verdicts(run)["C3"] is False
