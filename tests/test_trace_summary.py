"""Trace summarizer (dynolog_tpu.trace) against a REAL jax.profiler
capture — the parser's field-number assumptions are pinned empirically,
not against a fixture we also wrote."""

import glob
import json
import pathlib
import subprocess
import sys

import pytest


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    # Capture in a subprocess so the forced-CPU backend is per-test-process
    # (the main pytest process may already hold a different backend).
    d = tmp_path_factory.mktemp("xtrace")
    code = f"""
import sys
sys.path.insert(0, {str(sys.path[0])!r})
sys.path.insert(0, "/root/repo")
from dynolog_tpu._jaxinit import force_cpu_devices
force_cpu_devices(1)
import jax, jax.numpy as jnp
x = jnp.ones((128, 128))
f = jax.jit(lambda x: (x @ x).sum())
float(f(x))
jax.profiler.start_trace({str(d)!r})
for _ in range(3):
    float(f(x))
jax.profiler.stop_trace()
"""
    subprocess.run([sys.executable, "-c", code], check=True, cwd="/root/repo")
    return d


def test_summarize_real_capture(trace_dir):
    from dynolog_tpu import trace

    files = trace.find_xplane_files(str(trace_dir))
    assert files, list(trace_dir.rglob("*"))
    summary = trace.summarize(str(trace_dir))
    assert summary["planes"], summary
    total_events = sum(p["events"] for p in summary["planes"])
    assert total_events > 0
    assert summary["top_ops"], summary
    # The jitted lambda must show up among the op names somewhere.
    names = " ".join(op["op"] for op in summary["top_ops"])
    assert "jit" in names or "fusion" in names or "dot" in names, names
    # Aggregates are sane: sorted desc by self time (a host line's events
    # nest), positive, an op's own time within its inclusive time, pct
    # sums to ~100.
    own = [op["self_ms"] for op in summary["top_ops"]]
    assert own == sorted(own, reverse=True)
    assert all(0 <= op["self_ms"] <= op["total_ms"]
               for op in summary["top_ops"])
    assert all(op["count"] >= 1 for op in summary["top_ops"])
    assert sum(op["pct"] for op in summary["top_ops"]) == pytest.approx(
        100.0, abs=2.0)


def test_manifest_and_cli_paths(trace_dir, tmp_path):
    from dynolog_tpu import trace

    manifest = tmp_path / "m.json"
    manifest.write_text(json.dumps({"trace_dir": str(trace_dir)}))
    assert trace.find_xplane_files(str(manifest))

    direct = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert trace.find_xplane_files(direct[0]) == [direct[0]]

    out = subprocess.run(
        [sys.executable, "-m", "dynolog_tpu.trace", str(trace_dir), "--json"],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert out.returncode == 0, out.stderr
    parsed = json.loads(out.stdout)
    assert parsed["planes"] and parsed["top_ops"]

    human = subprocess.run(
        [sys.executable, "-m", "dynolog_tpu.trace", str(trace_dir), "--top", "5"],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert human.returncode == 0
    assert "plane" in human.stdout and "op" in human.stdout


def test_diff_math():
    """diff_summaries ranks by impact and handles new/vanished ops."""
    from dynolog_tpu import trace

    base = {
        "steps": {"count": 10, "mean_ms": 5.0, "p50_ms": 5.0,
                  "p95_ms": 6.0, "max_ms": 7.0},
        "top_ops": [
            {"op": "fusion", "total_ms": 10.0, "count": 100, "pct": 50.0},
            {"op": "copy", "total_ms": 8.0, "count": 80, "pct": 40.0},
            {"op": "gone", "total_ms": 2.0, "count": 10, "pct": 10.0},
        ],
    }
    cur = {
        "steps": {"count": 10, "mean_ms": 8.0, "p50_ms": 8.0,
                  "p95_ms": 9.5, "max_ms": 11.0},
        "top_ops": [
            # fusion regressed 0.1 -> 0.15 ms/call: impact +5ms over 100
            {"op": "fusion", "total_ms": 15.0, "count": 100, "pct": 60.0},
            {"op": "copy", "total_ms": 8.0, "count": 80, "pct": 32.0},
            {"op": "new_op", "total_ms": 2.0, "count": 4, "pct": 8.0},
        ],
    }
    diff = trace.diff_summaries(base, cur)
    assert diff["steps"]["delta_p50_ms"] == 3.0
    assert diff["steps"]["delta_p95_ms"] == 3.5

    rows = {r["op"]: r for r in diff["ops"]}
    assert diff["ops"][0]["op"] == "fusion"  # largest impact first
    fusion = rows["fusion"]
    assert fusion["delta_ms_per_call"] == 0.05
    assert fusion["delta_pp"] == 10.0
    assert fusion["impact_ms"] == 5.0
    assert rows["copy"]["delta_ms_per_call"] == 0.0
    assert rows["new_op"]["impact_ms"] == 2.0
    assert rows["new_op"]["base_ms_per_call"] is None
    assert rows["gone"]["impact_ms"] == -2.0
    assert rows["gone"]["ms_per_call"] is None


def test_diff_cli_self_is_flat(trace_dir):
    """A trace diffed against itself: zero deltas, same ops, both formats."""
    out = subprocess.run(
        [sys.executable, "-m", "dynolog_tpu.trace", str(trace_dir),
         "--diff", str(trace_dir), "--json"],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert out.returncode == 0, out.stderr
    diff = json.loads(out.stdout)
    assert diff["ops"]
    for row in diff["ops"]:
        assert row["impact_ms"] == 0.0
        assert row.get("delta_ms_per_call") == 0.0

    human = subprocess.run(
        [sys.executable, "-m", "dynolog_tpu.trace", str(trace_dir),
         "--diff", str(trace_dir), "--top", "5"],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert human.returncode == 0, human.stderr
    assert "Δms/call" in human.stdout and "impact ms" in human.stdout


def test_missing_dir_fails_cleanly(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "dynolog_tpu.trace", str(tmp_path)],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert out.returncode == 1
    assert "no .xplane.pb" in out.stderr


def test_chrome_trace_conversion(trace_dir):
    """xplane -> Chrome trace-event JSON (the shim fast-stop path's
    background export) against a REAL capture: event names, timestamps
    and process/thread metadata must survive the conversion."""
    import gzip

    from dynolog_tpu import trace

    files = trace.find_xplane_files(str(trace_dir))
    assert files
    out = trace.write_chrome_trace_gz(files[0])
    assert out.endswith(".trace.json.gz")
    with gzip.open(out, "rt") as f:
        data = json.load(f)
    events = data["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert complete, "no complete events converted"
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in complete)
    # The jitted matmul the fixture ran must be visible by name.
    names = " ".join(e["name"] for e in complete)
    assert "op#" not in names or any(
        n for n in names.split() if not n.startswith("op#")
    ), "all event names unresolved (metadata table lost)"
    assert any(
        e["ph"] == "M" and e["name"] == "process_name" for e in events
    )
    assert any(
        e["ph"] == "M" and e["name"] == "thread_name" for e in events
    )


def test_schema_pins_match_wheel_descriptor():
    """The parser's pinned xplane field numbers must match the
    FileDescriptor embedded in the installed wheel — a jax/tensorflow
    upgrade that renumbers a field fails HERE instead of silently
    mis-summarizing traces."""
    from dynolog_tpu import xspace

    ok, mismatches = xspace.verify_schema_pins()
    if ok is None:
        pytest.skip("no xplane descriptor available in this environment")
    assert ok, mismatches


def test_verify_schema_cli():
    from pathlib import Path

    repo_root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-m", "dynolog_tpu.trace", "--verify-schema"],
        capture_output=True, text=True, cwd=repo_root,
    )
    assert out.returncode == 0, out.stderr
    assert "schema" in out.stdout


# -- collectives by kind ------------------------------------------------------

US = 1_000_000  # picoseconds in a microsecond
# (op, microseconds an event) on "XLA Ops": XLA names an op it inserts itself
# after its opcode, and one the program wrote (the expert layer's exchange, a
# psum in a shard_map) after the JAX primitive, underscores and all.
SPARSE_JOB_OPS = (
    ("%fusion.1 = bf16[8,8]{1,0} fusion(%p0)", 50),
    ("%all-reduce.2 = bf16[8,8]{1,0} all-reduce(%p1)", 20),
    ("%ragged_all_to_all.85 = bf16[64,8]{1,0} ragged-all-to-all(%p2)", 24),
    ("%all_to_all.82 = s32[4,1,1]{2,1,0} all-to-all(%p3)", 1),
    ("%psum.9 = f32[] all-reduce(%p4)", 5),
)
# `top_ops` of the fixture below as the parent of PR 34 wrote it
TOP_OPS_BEFORE = (
    '[{"op": "fusion", "total_ms": 0.25, "count": 5, "pct": 50.0, "shapes": '
    '["bf16[8,8]"]}, {"op": "ragged_all_to_all", "total_ms": 0.12, "count": 5, '
    '"pct": 24.0, "shapes": ["bf16[64,8]"]}, {"op": "all-reduce", "total_ms": '
    '0.1, "count": 5, "pct": 20.0, "shapes": ["bf16[8,8]"]}, {"op": "psum", '
    '"total_ms": 0.025, "count": 5, "pct": 5.0, "shapes": ["f32[]"]}, {"op": '
    '"all_to_all", "total_ms": 0.005, "count": 5, "pct": 1.0, "shapes": '
    '["s32[4,1,1]"]}]')


def sparse_job_xspace() -> bytes:
    sys.path.insert(0, str(pathlib.Path(__file__).parent))
    import xspace_fixture as xf

    def plane(name: str, rounds: int) -> bytes:
        body = xf._field_varint(1, 7) + xf._field_str(2, name)
        events, offset = [], 0
        for _ in range(rounds):
            for meta_id, (_, us) in enumerate(SPARSE_JOB_OPS, start=1):
                events.append(xf._event(meta_id, offset, us * US))
                offset += us * US
        body += xf._field_bytes(3, xf._line(1, "XLA Ops", 1000, events))
        for meta_id, (op, _) in enumerate(SPARSE_JOB_OPS, start=1):
            body += xf._field_bytes(4, xf._event_metadata(meta_id, op, ""))
        return body

    return b"".join(xf._field_bytes(1, p) for p in (
        plane("/device:TPU:0", 2), plane("/device:TPU:1", 3)))


def test_collectives_by_kind_sum_to_the_planes_collective_time(
        tmp_path, capsys):
    from dynolog_tpu import trace

    data = sparse_job_xspace()
    summary = trace._summarize_planes(trace.summarize_xplane_bytes(data))
    for plane, rounds in zip(summary["planes"], (2, 3)):
        kinds = plane["collectives"]
        # 20 + 5 microseconds a round of reduction, 24 + 1 of exchange
        assert kinds == {
            "all-reduce": {"total_ms": 25 * rounds / 1e3, "count": 2 * rounds},
            "all-to-all": {"total_ms": 25 * rounds / 1e3, "count": 2 * rounds}}
        assert plane["collective_pct"] == 50.0
        assert sum(k["total_ms"] for k in kinds.values()) == pytest.approx(
            plane["collective_pct"] / 100.0 * 0.1 * rounds)
    # the op table is what it was before the kinds were counted, and
    # before an op's own time was (PR 36): nothing nests here, so the new
    # key repeats `total_ms` and every other number stands
    assert all(row.pop("self_ms") == row["total_ms"]
               for row in summary["top_ops"])
    assert json.dumps(summary["top_ops"]) == TOP_OPS_BEFORE
    assert all(p["loops"] == {} for p in summary["planes"])
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(data)
    assert trace.main([str(path), "--plane", "TPU:1"]) == 0
    table = capsys.readouterr().out.splitlines()
    assert table[1].split()[-1] == "50.00"
    assert [line.split()[0] for line in table[2:4]] == [
        "all-reduce", "all-to-all"]
    assert table[3].split()[1:] == ["6", "events", "0.075", "ms"]
