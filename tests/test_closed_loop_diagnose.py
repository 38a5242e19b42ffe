"""Closed-loop diagnosis acceptance (daemon-gated, the
test_fault_containment posture): a synthetic metric breach fires
AutoTrigger → sampled capture through the real daemon+shim transport →
trace-diff vs a stored baseline → ranked diagnosis artifact on disk and
retrievable via `dyno diagnose` — with every span of the loop (trigger,
capture, engine) sharing ONE trace-id in `selftrace` output."""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from test_capture_ring import FakeXplaneProfiler  # noqa: E402
from xspace_fixture import build_xspace  # noqa: E402

from daemon_utils import (  # noqa: E402
    assert_status_ok,
    run_dyno,
    start_daemon,
    stop_daemon,
    write_snapshot,
)
from dynolog_tpu import diagnose, trace  # noqa: E402
from dynolog_tpu.client import TraceClient  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent

DIAG_FLAGS = (
    "--enable_tpu_monitor",
    "--tpu_metric_backend=file",
    "--tpu_monitor_reporting_interval_s=1",
    "--auto_trigger_eval_interval_ms=200",
    f"--diagnose_pythonpath={REPO}",
)


def _start(bin_dir, tmp_path, extra=()):
    metrics_file = tmp_path / "snap.json"
    write_snapshot(metrics_file, 90.0)
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            *DIAG_FLAGS, f"--tpu_metrics_file={metrics_file}", *extra),
    )
    return daemon, metrics_file


def _save_baseline(tmp_path) -> pathlib.Path:
    baseline = tmp_path / "baseline.json"
    diagnose.save_baseline(
        str(baseline), trace.compact_profile(build_xspace()), model="demo")
    return baseline


def test_breach_fires_capture_diff_and_ranked_report(bin_dir, tmp_path):
    daemon, metrics_file = _start(bin_dir, tmp_path)
    baseline = _save_baseline(tmp_path)
    # The app's "regression": fusion.3 doubled per call since baseline.
    profiler = FakeXplaneProfiler(build_xspace(op_duration_scale={3: 2.0}))
    client = TraceClient(
        job_id=5, endpoint=daemon.endpoint, poll_interval_s=0.1,
        profiler=profiler)
    try:
        assert client.start()
        log_file = tmp_path / "auto.json"
        result = run_dyno(
            bin_dir, daemon.port, "autotrigger", "add",
            "--metric=tpu0.tpu_duty_cycle_pct", "--below=50",
            "--for_ticks=1", "--cooldown_s=600", "--job_id=5",
            "--duration_ms=100", f"--log_file={log_file}",
            "--diagnose", f"--baseline={baseline}")
        assert result.returncode == 0, result.stderr
        assert "trigger 1 installed" in result.stdout

        # Breach: duty drops under the threshold; the loop runs itself.
        write_snapshot(metrics_file, 10.0)
        deadline = time.time() + 60
        report_files = []
        while time.time() < deadline and not report_files:
            report_files = list(tmp_path.glob("auto_trig1_*.diagnosis.json"))
            time.sleep(0.2)
        assert report_files, (
            f"no diagnosis artifact; shim err={client.last_error}, "
            f"files={sorted(p.name for p in tmp_path.iterdir())}")

        # The ranked report on disk: machine-readable, regressed, naming
        # the regressed op instance first.
        report = json.loads(report_files[0].read_text())
        assert report["verdict"] == "regressed"
        assert report["findings"], report
        assert any(
            f["op"] == "fusion.3" and f["kind"] == "fusion_regression"
            for f in report["findings"]), report["findings"]
        # Ranking: the top finding carries the largest |impact|.
        impacts = [abs(f["impact_ms"] or 0) for f in report["findings"]]
        assert impacts == sorted(impacts, reverse=True)
        # The artifact names its control-plane request.
        assert report.get("trace_ctx"), report.keys()

        # Retrievable via the RPC verb / `dyno diagnose`.
        listed = daemon.rpc({"fn": "diagnose"})
        assert listed["status"] == "ok"
        assert listed["runs_total"] >= 1
        rows = [r for r in listed["reports"] if r["status"] == "ok"]
        assert rows, listed
        row = rows[0]
        assert row["rule_id"] == 1
        assert row["verdict"] == "regressed"
        assert row["findings"] >= 1
        assert "fusion.3" in row["headline"]
        cli = run_dyno(bin_dir, daemon.port, "diagnose")
        assert cli.returncode == 0, cli.stderr
        assert "regressed" in cli.stdout
        assert row["report_path"] in cli.stdout

        # One trace-id across the whole loop: the daemon's trigger +
        # engine-run spans, the shim's capture spans (flushed over the
        # span datagram) and the engine child's diagnose.* spans.
        trace_id = row["trace_id"]
        assert trace_id == report["trace_ctx"].split("/")[0]
        names = set()
        pids = set()
        deadline = time.time() + 15
        while time.time() < deadline:
            selftrace = daemon.rpc(
                {"fn": "selftrace", "trace_id": trace_id})
            assert selftrace["status"] == "ok"
            names = {e["name"] for e in selftrace["traceEvents"]}
            pids = {e["pid"] for e in selftrace["traceEvents"]}
            if {"diagnose.trigger", "diagnose.run", "shim.capture",
                    "diagnose.engine"} <= names:
                break
            time.sleep(0.3)  # late span-datagram flushes
        assert {"diagnose.trigger", "diagnose.capture_wait",
                "diagnose.run", "shim.capture",
                "diagnose.engine"} <= names, names
        # Cross-process: daemon, app and engine child pids all lane in.
        assert len(pids) >= 3, pids
    finally:
        client.stop()
        stop_daemon(daemon)


def test_dyno_diagnose_run_mode_and_exit_codes(bin_dir, tmp_path):
    daemon, _ = _start(bin_dir, tmp_path)
    baseline = _save_baseline(tmp_path)
    profiler = FakeXplaneProfiler(build_xspace(op_duration_scale={7: 3.0}))
    client = TraceClient(
        job_id=9, endpoint=daemon.endpoint, poll_interval_s=0.1,
        profiler=profiler)
    try:
        assert client.start()
        log_file = tmp_path / "manual.json"
        result = run_dyno(
            bin_dir, daemon.port, "gputrace", "--job_id=9",
            "--duration_ms=50", f"--log_file={log_file}")
        assert result.returncode == 0, result.stderr
        deadline = time.time() + 30
        manifests = []
        while time.time() < deadline and not manifests:
            manifests = list(tmp_path.glob("manual_*.json"))
            time.sleep(0.1)
        assert manifests, client.last_error

        # Operator-initiated diagnosis of that capture: exit 3 because a
        # regression was diagnosed (scriptable, like `dyno health`).
        cli = run_dyno(
            bin_dir, daemon.port, "diagnose",
            f"--log_file={manifests[0]}", f"--baseline={baseline}")
        assert cli.returncode == 3, cli.stdout + cli.stderr
        assert "regressed" in cli.stdout
        assert "fusion.7" in cli.stdout
        assert (tmp_path / f"{manifests[0].stem}.diagnosis.json").exists()

        # Same capture against itself: clean, exit 0.
        cli = run_dyno(
            bin_dir, daemon.port, "diagnose",
            f"--log_file={manifests[0]}",
            f"--baseline={manifests[0]}")
        assert cli.returncode == 0, cli.stdout + cli.stderr
        assert "clean" in cli.stdout
    finally:
        client.stop()
        stop_daemon(daemon)


def test_diagnosis_failure_is_recorded_not_fatal(bin_dir, tmp_path):
    # A rule whose baseline never exists: the capture still lands, the
    # report records the engine failure, counters tick, daemon healthy.
    daemon, metrics_file = _start(bin_dir, tmp_path)
    profiler = FakeXplaneProfiler(build_xspace())
    client = TraceClient(
        job_id=5, endpoint=daemon.endpoint, poll_interval_s=0.1,
        profiler=profiler)
    try:
        assert client.start()
        log_file = tmp_path / "auto.json"
        result = run_dyno(
            bin_dir, daemon.port, "autotrigger", "add",
            "--metric=tpu0.tpu_duty_cycle_pct", "--below=50",
            "--for_ticks=1", "--cooldown_s=600", "--job_id=5",
            "--duration_ms=50", f"--log_file={log_file}",
            "--diagnose", f"--baseline={tmp_path}/never_saved.json")
        assert result.returncode == 0, result.stderr
        write_snapshot(metrics_file, 10.0)
        deadline = time.time() + 60
        failed = []
        while time.time() < deadline and not failed:
            listed = daemon.rpc({"fn": "diagnose"})
            failed = [r for r in listed.get("reports", [])
                      if r["status"] == "failed"]
            time.sleep(0.2)
        assert failed, listed
        assert failed[0]["error"]
        assert listed["failures_total"] >= 1
        # The capture itself still completed; the daemon still serves.
        assert client.traces_completed >= 1
        assert_status_ok(daemon.rpc({"fn": "getStatus"}))
    finally:
        client.stop()
        stop_daemon(daemon)
