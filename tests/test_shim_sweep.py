"""Client-shim stale-artifact sweep (satellite of the fault-containment
PR): *.tmp atomic-write leftovers and dead-pid trace-session dirs from a
SIGKILL'd export child are garbage-collected with a TTL, while live and
young artifacts are never touched — plus poll-loop containment of a
capture-path crash via the shim.run_trace failpoint."""

from __future__ import annotations

import logging
import os
import pathlib
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dynolog_tpu import failpoints  # noqa: E402
from dynolog_tpu.client.shim import (  # noqa: E402
    RecordingProfiler,
    TraceClient,
    TraceConfig,
    sweep_stale_artifacts,
)

OLD = time.time() - 7 * 24 * 3600  # a week ago: past any TTL used here


def _dead_pid() -> int:
    proc = subprocess.Popen(["/bin/true"])
    proc.wait()
    return proc.pid


def _make_old(path: os.PathLike | str) -> None:
    os.utime(path, (OLD, OLD))


def test_sweep_reclaims_owned_tmps_and_keeps_everything_else(tmp_path):
    dead = _dead_pid()
    # Manifest atomic-write leftover of a dead pid: ours, reclaimed.
    manifest_tmp = tmp_path / f"t_{dead}.json.tmp"
    manifest_tmp.write_bytes(b"{")
    _make_old(manifest_tmp)
    # Export-child leftover INSIDE a session dir: ours, reclaimed (the
    # young session dir itself stays — only its expired debris goes).
    session = tmp_path / f"t_{os.getpid()}"
    nested = session / "plugins" / "profile" / "r1"
    nested.mkdir(parents=True)
    old_nested = nested / "trace.json.gz.tmp"
    old_nested.write_bytes(b"partial")
    _make_old(old_nested)
    # NOT ours: a foreign root-level .tmp (the sweep often points at a
    # shared /tmp — other programs' files must never be touched), a
    # root-level tmp without a pid-suffixed manifest shape, a live-pid
    # manifest tmp, and a young owned tmp.
    foreign = tmp_path / "session-a1b2.tmp"
    foreign.write_bytes(b"someone else's")
    _make_old(foreign)
    shapeless = tmp_path / "trace.json.gz.tmp"
    shapeless.write_bytes(b"partial")
    _make_old(shapeless)
    live_manifest_tmp = tmp_path / f"t_{os.getpid()}.json.tmp"
    live_manifest_tmp.write_bytes(b"{")
    _make_old(live_manifest_tmp)
    young_nested = nested / "summary.json.tmp"
    young_nested.write_bytes(b"in flight")
    bystander = tmp_path / f"t_{dead}.json"
    bystander.write_bytes(b"complete manifest")
    _make_old(bystander)

    reclaimed = sweep_stale_artifacts(str(tmp_path / "t"), ttl_s=3600)
    assert sorted(reclaimed) == sorted([str(manifest_tmp), str(old_nested)])
    assert not manifest_tmp.exists() and not old_nested.exists()
    assert foreign.exists() and shapeless.exists()
    assert live_manifest_tmp.exists()
    assert young_nested.exists()
    assert bystander.exists() and session.exists()


def test_sweep_reclaims_dead_pid_session_dir_only(tmp_path):
    dead = _dead_pid()
    dead2 = _dead_pid()
    dead_dir = tmp_path / f"trace_{dead}"
    (dead_dir / "plugins" / "profile" / "r1").mkdir(parents=True)
    (dead_dir / "plugins" / "profile" / "r1" / "host.xplane.pb").write_bytes(
        b"x")
    _make_old(dead_dir)

    live_dir = tmp_path / f"trace_{os.getpid()}"
    (live_dir / "plugins").mkdir(parents=True)
    _make_old(live_dir)

    young_dead = tmp_path / f"trace_{os.getpid() + 1}"
    (young_dead / "plugins").mkdir(parents=True)  # mtime = now

    unrecognized = tmp_path / f"trace_{dead}x"  # pid part not digits
    unrecognized.mkdir()
    _make_old(unrecognized)

    # Our prefix but a layout the shim never produces: not claimed.
    odd_layout = tmp_path / f"trace_{dead2}"
    odd_layout.mkdir()
    (odd_layout / "notes.txt").write_text("not a trace-session layout")
    _make_old(odd_layout)

    # Foreign prefix — another program's empty lock dir in a shared
    # parent must never qualify, however old and dead its pid.
    foreign_dir = tmp_path / f"worker_{dead}"
    foreign_dir.mkdir()
    _make_old(foreign_dir)

    reclaimed = sweep_stale_artifacts(str(tmp_path / "trace"), ttl_s=3600)
    assert reclaimed == [str(dead_dir)]
    assert not dead_dir.exists()
    assert live_dir.exists()  # owning pid alive
    assert young_dead.exists()  # younger than TTL
    assert unrecognized.exists()  # pid suffix not digits
    assert odd_layout.exists()  # layout not positively ours
    assert foreign_dir.exists()  # not our trace base's prefix


def test_sweep_completed_capture_protected_by_manifest(tmp_path):
    # Dead + expired but COMPLETED (its manifest still stands): the
    # operator's trace, never reclaimed out from under them.
    dead = _dead_pid()
    completed = tmp_path / f"trace_{dead}"
    (completed / "plugins").mkdir(parents=True)
    _make_old(completed)
    (tmp_path / f"trace_{dead}.json").write_text("{}")
    assert sweep_stale_artifacts(str(tmp_path / "trace"), ttl_s=3600) == []
    assert completed.exists()


def test_sweep_disabled_and_missing_root():
    assert sweep_stale_artifacts("/nonexistent/dir/trace", ttl_s=3600) == []
    assert sweep_stale_artifacts("/tmp/t", ttl_s=0) == []
    assert sweep_stale_artifacts("/tmp/t", ttl_s=-1) == []


def test_sweep_logs_one_line_per_reclaimed_path(tmp_path, caplog):
    dead = _dead_pid()
    tmp = tmp_path / f"t_{dead}.json.tmp"
    tmp.write_bytes(b"{")
    _make_old(tmp)
    with caplog.at_level(logging.INFO, logger="dynolog_tpu.shim"):
        reclaimed = sweep_stale_artifacts(str(tmp_path / "t"), ttl_s=3600)
    assert reclaimed == [str(tmp)]
    lines = [r for r in caplog.records if "reclaimed stale" in r.getMessage()]
    assert len(lines) == 1
    assert str(tmp) in lines[0].getMessage()


def test_capture_sweeps_its_output_directory(tmp_path):
    # A SIGKILL'd predecessor left debris next to the log_file; the next
    # capture into that directory reclaims it (TTL-expired only).
    dead = _dead_pid()
    debris_tmp = tmp_path / f"t_{dead}.json.tmp"
    debris_tmp.write_bytes(b"{")
    _make_old(debris_tmp)
    debris_dir = tmp_path / f"t_{dead}"
    (debris_dir / "plugins").mkdir(parents=True)
    _make_old(debris_dir)

    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=7, profiler=profiler, sweep_ttl_s=3600)
    cfg = TraceConfig.parse(
        f"ACTIVITIES_LOG_FILE={tmp_path}/t.json\n"
        "ACTIVITIES_DURATION_MSECS=10")
    client._run_trace(cfg)

    assert not debris_tmp.exists()
    assert not debris_dir.exists()
    # The capture itself completed into its own (live-pid) session dir.
    assert (tmp_path / f"t_{os.getpid()}").is_dir()
    assert (tmp_path / f"t_{os.getpid()}.json").exists()
    assert profiler.calls == [
        ("start", str(tmp_path / f"t_{os.getpid()}")), ("stop", None)]


class FakeIpc:
    """Stands in for ipc.IpcClient: hands out canned configs, no daemon."""

    def __init__(self, configs):
        self.configs = list(configs)

    def register_context(self, job_id, device, dest=None):
        return 0

    def request_config(self, job_id, ancestry, config_type, dest=None,
                       retries=10):
        return self.configs.pop(0) if self.configs else None

    def take_late_config(self):
        return None

    def subscribe_kicks(self, job_id, dest=None):
        pass

    def wait_for_kick(self, timeout):
        time.sleep(min(timeout, 0.02))
        return False

    def send_perf_stats(self, *args, **kwargs):
        pass

    def close(self):
        pass


def test_poll_loop_contains_capture_crash(tmp_path):
    # shim.run_trace=throw*1: the first capture crashes, the poll loop
    # records last_error and SURVIVES — the second config is captured.
    failpoints.disarm_all()
    failpoints.arm("shim.run_trace", "throw*1")
    cfg_text = (
        f"ACTIVITIES_LOG_FILE={tmp_path}/t.json\n"
        "ACTIVITIES_DURATION_MSECS=10")
    client = TraceClient(
        job_id=7,
        profiler=RecordingProfiler(),
        poll_interval_s=0.05,
        report_interval_s=0,
        sweep_ttl_s=0,
    )
    # start() issues one synchronous registration poll whose config text
    # is ignored — feed it a None so both real configs reach the loop.
    client._client = FakeIpc([None, cfg_text, cfg_text])
    try:
        assert client.start() is not None
        deadline = time.monotonic() + 10
        while client.traces_completed < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        client.stop()
        failpoints.disarm_all()
    assert client.traces_completed == 1
    assert client.last_error is not None
    assert "shim.run_trace" in client.last_error
    assert failpoints.hits("shim.run_trace") == 1
