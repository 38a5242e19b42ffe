"""bench.py's artifact emission and conversion arm, tier-1.

The driver parses the bench's FINAL stdout line out of a bounded (~2000
char) output tail; BENCH_r05 overflowed it with a 20KB result line and
the round published "parsed": null. emit_result's contract — ONE compact
final line under COMPACT_MAX_BYTES, full detail in a sidecar — is pinned
here without running the (hours-long) bench itself.
"""

import importlib.util
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fat_result(bench):
    # The shape (and then some) of a full r5-style result: every bulky
    # field maxed out, so the compact line only fits if emit_result
    # actually strips and drops.
    return {
        "metric": "always_on_overhead_pct",
        "value": 0.42,
        "unit": "percent",
        "vs_baseline": 0.42,
        "overhead_trimmed_mean_pct": 0.1,
        "overhead_median_pct": 0.09,
        "overhead_ci95_pct": [-0.2, 0.4],
        "overhead_median_signtest_ci95_pct": [-0.3, 0.5],
        "overhead_method": "ABBA " * 40,
        "shim_poll_cost_pct_upper_bound": 0.01,
        "daemon_cpu_s": 1.0,
        "daemon_rss_mb": 10.0,
        "baseline_step_ms": 8.0,
        "monitored_step_ms": 8.01,
        "pairs": 700,
        "pair_deltas_pct": [0.01] * 700,
        "trace_capture_latency_p50_ms": 1100.0,
        "trace_capture_latency_p95_ms": 1300.0,
        "trace_captures": 16,
        "trace_decomposition": [
            {"pickup_ms": 10, "profiler_start_ms": 5, "profiler_stop_ms": 600,
             "collect_ms": 500, "write_ms": 40, "xspace_bytes": 7000000}
        ] * 16,
        "trace_floor": {
            "floor_ms": 900.0, "modeled_ms": 950.0,
            "minimal_window_latencies_ms": [600.0] * 5,
            "write_probe": {"bytes": 7000000, "buffered_ms": 8.0},
        },
        "trace_ab_light": {"tracer": "host_tracer_level=1", "captures": 8,
                           "p50_ms": 1000.0, "min_ms": 900.0},
        "push_capture_latency_p50_ms": 1200.0,
        "push_capture_latency_p95_ms": 1400.0,
        "push_captures": 16,
        "push_decomposition": [
            {"rpc_ms": 1100, "server_overhead_ms": 600,
             "rpc_first_data_ms": 1080, "rpc_stream_ms": 1095,
             "write_ms": 60, "xspace_bytes": 6900000, "duration_ms": 500}
        ] * 16,
        "push_floor": {
            "floor_ms": 1400.0, "modeled_ms": 1440.0,
            "minimal_window_latencies_ms": [630.0] * 5,
        },
        "push_first_capture_ms": 1290.0,
        "push_ab_light": {"tracer": "host_tracer_level=1", "captures": 8,
                          "p50_ms": 1100.0, "min_ms": 1000.0},
        "conversion": {
            "streamed": {"p50_ms": 400.0, "min_ms": 380.0,
                         "cpu_s_per_convert": 0.5, "reps": 8},
            "single_shot": {"p50_ms": 700.0, "min_ms": 650.0,
                            "cpu_s_per_convert": 0.9, "reps": 8},
            "speedup_p50": 1.75, "cpu_ratio": 1.8,
            "fixture_bytes": 359944,
        },
        "conversion_streamed_p50_ms": 400.0,
        "conversion_single_p50_ms": 700.0,
        "conversion_streamed_cpu_s": 0.5,
        "loadavg_at_launch": [1.0, 1.0, 1.0],
        "loadavg_start": [0.5, 0.8, 1.0],
        "loadavg_end": [0.6, 0.8, 1.0],
        "platform": "TPU v5 lite0",
    }


def test_emit_result_final_line_fits_driver_tail(tmp_path, capsys):
    bench = _load_bench()
    result = _fat_result(bench)
    compact = bench.emit_result(result, detail_dir=tmp_path)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    # ONE stdout line, the LAST thing printed, parseable, bounded.
    assert len(lines) == 1
    assert len(lines[-1]) <= bench.COMPACT_MAX_BYTES, len(lines[-1])
    parsed = json.loads(lines[-1])
    assert parsed == compact
    # Headline survives compaction...
    assert parsed["metric"] == "always_on_overhead_pct"
    assert parsed["value"] == 0.42
    assert parsed["trace_capture_latency_p50_ms"] == 1100.0
    assert parsed["conversion_streamed_p50_ms"] == 400.0
    # ...bulk does not.
    for key in ("pair_deltas_pct", "trace_decomposition",
                "push_decomposition"):
        assert key not in parsed
    # The sidecar carries the FULL result, bulk included.
    detail = json.loads(pathlib.Path(parsed["detail_file"]).read_text())
    assert len(detail["pair_deltas_pct"]) == 700
    assert len(detail["trace_decomposition"]) == 16
    assert detail["conversion"]["speedup_p50"] == 1.75


def test_emit_result_hard_cap_survives_unknown_bulky_key(tmp_path, capsys):
    # The r5 failure shape, one generation later: a future round adds a
    # bulky key that nobody listed in DETAIL_ONLY_KEYS/DROP_ORDER. The
    # cap must still hold via the headline-whitelist fallback.
    bench = _load_bench()
    result = _fat_result(bench)
    result["future_bulky_field"] = [{"x": i} for i in range(500)]
    bench.emit_result(result, detail_dir=tmp_path)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(lines[-1]) <= bench.COMPACT_MAX_BYTES, len(lines[-1])
    parsed = json.loads(lines[-1])
    assert parsed["metric"] == "always_on_overhead_pct"
    assert parsed["value"] == 0.42
    assert "future_bulky_field" not in parsed
    # The sidecar still has it.
    detail = json.loads(pathlib.Path(parsed["detail_file"]).read_text())
    assert len(detail["future_bulky_field"]) == 500


def test_emit_result_survives_unwritable_detail_dir(tmp_path, capsys):
    bench = _load_bench()
    # A detail-dir failure must not cost the stdout line (the driver
    # artifact) — detail_file is simply absent.
    blocked = tmp_path / "not_a_dir"
    blocked.write_text("file, not dir")
    bench.emit_result(_fat_result(bench), detail_dir=blocked)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    parsed = json.loads(lines[-1])
    assert parsed["value"] == 0.42
    assert "detail_file" not in parsed
    assert len(lines[-1]) <= bench.COMPACT_MAX_BYTES


def _strict_loads(line: str):
    # Reject NaN/Infinity the way a strict driver-side parser does —
    # json.loads accepts them by default, which would mask the bug.
    def _no_constants(name):
        raise ValueError(f"non-JSON constant {name}")

    return json.loads(line, parse_constant=_no_constants)


def test_emit_result_self_check_sanitizes_nan(tmp_path, capsys):
    # The r05-class failure one layer deeper: a NaN latency makes
    # json.dumps emit bare `NaN` — not JSON. The self-check must
    # sanitize it so the final line still parses strictly.
    bench = _load_bench()
    result = _fat_result(bench)
    result["trace_capture_latency_p95_ms"] = float("nan")
    result["push_floor"]["floor_ms"] = float("inf")
    compact = bench.emit_result(result, detail_dir=tmp_path)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(lines) == 1
    parsed = _strict_loads(lines[-1])
    assert parsed == compact
    assert parsed["trace_capture_latency_p95_ms"] is None
    assert parsed["value"] == 0.42


def test_emit_result_self_check_falls_back_to_minimal_line(tmp_path, capsys):
    # Even the headline whitelist can overflow (a pathological value in
    # a kept key): the self-check's last resort is the minimal line —
    # still strict JSON, still under budget, still carrying the metric.
    bench = _load_bench()
    result = _fat_result(bench)
    result["platform"] = "x" * (bench.COMPACT_MAX_BYTES + 100)
    bench.emit_result(result, detail_dir=tmp_path)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(lines[-1]) <= bench.COMPACT_MAX_BYTES
    parsed = _strict_loads(lines[-1])
    assert parsed["metric"] == "always_on_overhead_pct"
    assert parsed["value"] == 0.42
    assert parsed["emit_self_check"] == "fallback"
    # Full fidelity still in the sidecar.
    detail = json.loads(pathlib.Path(parsed["detail_file"]).read_text())
    assert len(detail["platform"]) > bench.COMPACT_MAX_BYTES


def test_device_run_refuses_a_non_tpu_platform():
    """The device path has no fallback: pinned to the CPU, bench.py exits
    non-zero within seconds, names the platform it found, and prints no
    result line for the driver to parse."""
    import os
    import subprocess

    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--quick"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr, proc.stderr[-1000:]
    assert proc.stdout.strip() == ""


def test_measure_diagnosis_on_fixture():
    bench = _load_bench()
    diag = bench.measure_diagnosis(quick=True)
    assert diag["reps"] == 2
    assert diag["ring_promote_p50_ms"] > 0
    assert diag["engine_p50_ms"] >= 0
    assert diag["verdict"] == "regressed"
    assert diag["findings"] >= 2  # fusion.3 and fusion.16 regressed
    assert diag["capture_to_report_ms"] is not None
    head = bench.diagnosis_headline(diag)
    assert head["diag_findings"] == diag["findings"]
    assert head["diag_capture_to_report_ms"] == diag["capture_to_report_ms"]


def test_measure_conversion_on_fixture():
    bench = _load_bench()
    conv = bench.measure_conversion(quick=True)
    assert "error" not in conv, conv
    for arm in ("streamed", "single_shot"):
        assert conv[arm]["p50_ms"] > 0
        assert conv[arm]["cpu_s_per_convert"] > 0
        assert conv[arm]["reps"] == 2
    assert conv["fixture_bytes"] == (
        REPO / "tests" / "fixtures" / "bench.xplane.pb").stat().st_size
    assert conv["speedup_p50"] > 0


def test_detail_sidecars_are_count_capped(tmp_path, capsys):
    # PR 13 retention fix: bench_detail_*.json used to accumulate with
    # no bound — emit_result now keeps the newest DETAIL_KEEP and prunes
    # the rest (oldest mtime first), never the one it just wrote.
    import os
    import time

    bench = _load_bench()
    for i in range(bench.DETAIL_KEEP + 5):
        stale = tmp_path / f"bench_detail_{1000 + i}_{i}.json"
        stale.write_text("{}")
        past = time.time() - 10_000 + i
        os.utime(stale, (past, past))
    bench.emit_result(
        {"metric": "m", "value": 1, "unit": "u"}, detail_dir=tmp_path)
    capsys.readouterr()
    sidecars = sorted(tmp_path.glob("bench_detail_*.json"),
                      key=lambda p: p.stat().st_mtime)
    assert len(sidecars) == bench.DETAIL_KEEP
    # The survivor set is the NEWEST ones — including the fresh write.
    names = {p.name for p in sidecars}
    assert f"bench_detail_{1000}_0.json" not in names  # oldest pruned
    assert any(p.stat().st_size > 2 for p in sidecars)  # the real one
