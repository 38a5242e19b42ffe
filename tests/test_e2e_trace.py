"""End-to-end integration: dynologd + dyno CLI + Python JAX shim.

This is the reference's own demo flow (docs/pytorch_profiler.md:43-83)
transposed to the TPU stack: daemon on one host, an app process registering
over the IPC fabric, `dyno gputrace --log-file ...` pushing an on-demand
config through RPC → registry → IPC poll → profiler trigger.
"""

import json
import os
import pathlib
import re
import time

import pytest

from daemon_utils import assert_status_ok, run_dyno, start_daemon, stop_daemon
from dynolog_tpu.client import IpcClient, TraceClient
from dynolog_tpu.client.shim import RecordingProfiler, TraceConfig


@pytest.fixture()
def daemon(bin_dir):
    d = start_daemon(bin_dir)
    yield d
    stop_daemon(d)


def test_status_and_version(daemon, bin_dir):
    result = run_dyno(bin_dir, daemon.port, "status")
    assert result.returncode == 0, result.stderr
    assert '"status":1' in result.stdout.replace(" ", "")

    result = run_dyno(bin_dir, daemon.port, "version")
    assert result.returncode == 0
    header = pathlib.Path(__file__).resolve().parent.parent / "src/common/Version.h"
    version = re.search(r'kVersion = "([^"]+)"', header.read_text()).group(1)
    assert version in result.stdout


def test_rpc_direct(daemon):
    assert_status_ok(daemon.rpc({"fn": "getStatus"}))
    # unknown fn: server closes without reply
    assert daemon.rpc({"fn": "noSuchVerb"}) is None


def test_metric_store_query(daemon):
    # kernel monitor ticks at 1s in tests; first tick happens at startup.
    deadline = time.time() + 10
    names = []
    while time.time() < deadline:
        listed = daemon.rpc({"fn": "listMetrics"})
        names = listed["metrics"]
        if "uptime" in names:
            break
        time.sleep(0.3)
    assert "uptime" in names, names
    # The daemon reports its own footprint alongside the host metrics.
    assert "daemon_rss_kb" in names, names
    assert "daemon_open_fds" in names, names

    result = daemon.rpc(
        {
            "fn": "queryMetrics",
            "metrics": ["uptime"],
            "start_ts": 0,
            "end_ts": int(time.time() * 1000) + 1000,
        }
    )
    series = result["metrics"]["uptime"]
    assert len(series["values"]) >= 1
    assert series["values"][0] > 0


def test_ipc_registration(daemon):
    with IpcClient() as client:
        count = client.register_context(job_id=7, device=3, dest=daemon.endpoint)
        assert count == 1
        count = client.register_context(
            job_id=7, device=3, pid=os.getpid() + 1, dest=daemon.endpoint
        )
        assert count == 2


def test_recv_reply_stashes_interleaved_messages():
    """Messages racing an in-flight exchange on the shared socket are
    remembered, not dropped: a "kick" sets the pending flag, a stray
    "req" reply with a payload (late daemon answer whose config was
    already cleared server-side) lands in the late-config stash — and
    neither is mistaken for the awaited reply."""
    from dynolog_tpu.client import ipc as ipc_mod

    with IpcClient() as waiter, IpcClient() as sender:
        assert sender.send(ipc_mod.MSG_TYPE_KICK, b"\0" * 8, dest=waiter.name)
        assert sender.send(
            ipc_mod.MSG_TYPE_REQUEST, b"ACTIVITIES_DURATION_MSECS=1",
            dest=waiter.name)
        # Awaiting a "ctxt" that never comes: both queued datagrams are
        # consumed and classified, then the deadline returns None.
        assert waiter._recv_reply("ctxt", timeout_s=0.3) is None
        assert waiter.take_pending_kick() is True
        assert waiter.take_pending_kick() is False  # one-shot
        assert waiter.take_late_config() == "ACTIVITIES_DURATION_MSECS=1"
        assert waiter.take_late_config() is None


def test_stale_reply_never_answers_a_fresh_request():
    """A reply that lands AFTER its request timed out must not be read as
    the answer to the next request (same wire type!) — that would desync
    every later exchange by one reply, permanently. The exchange drains
    and classifies leftovers first: a late config is stashed for the poll
    loop, never returned as a fresh reply."""
    from dynolog_tpu.client import ipc as ipc_mod

    with IpcClient() as client, IpcClient() as peer:
        # Simulate the late reply: a "req" datagram already queued on the
        # main socket before the next exchange starts.
        assert peer.send(
            ipc_mod.MSG_TYPE_REQUEST, b"ACTIVITIES_DURATION_MSECS=5",
            dest=client.name)
        time.sleep(0.05)
        # peer never answers the fresh request -> timeout; the stale
        # config must NOT surface as this call's return value.
        r = client.request_config(1, [os.getpid()], dest=peer.name,
                                  timeout_s=0.2)
        assert r is None, f"stale reply returned as fresh: {r!r}"
        assert client.take_late_config() == "ACTIVITIES_DURATION_MSECS=5"


def test_concurrent_request_config_replies_not_stolen(daemon):
    """A second thread's request/reply exchange must not lose its reply to
    the poll thread's inter-poll wait. An earlier kick design select()ed
    on the SHARED socket between polls and consumed concurrent "req"
    replies; the requester then span its full timeout per call. Kicks
    now ride a dedicated socket and exchanges serialize on a lock, so
    every out-of-band request_config gets its reply at daemon-tick speed."""
    client = TraceClient(
        job_id=96, endpoint=daemon.endpoint, poll_interval_s=0.1,
        profiler=RecordingProfiler())
    try:
        assert client.start()
        t0 = time.monotonic()
        for _ in range(10):
            r = client._client.request_config(
                96, client._ancestry, dest=daemon.endpoint, timeout_s=2.0)
            assert r is not None, "reply stolen by the poll thread"
        elapsed = time.monotonic() - t0
        # 10 round trips at the ~10ms IPC tick; a single stolen reply
        # costs a 2s timeout and blows this bound.
        assert elapsed < 1.5, f"{elapsed:.2f}s for 10 polls"
    finally:
        client.stop()


def test_trace_config_parsing():
    cfg = TraceConfig.parse(
        "PROFILE_START_TIME=1234\n"
        "ACTIVITIES_LOG_FILE=/tmp/trace.json\n"
        "ACTIVITIES_DURATION_MSECS=750"
    )
    assert cfg.start_time_ms == 1234
    assert cfg.log_file == "/tmp/trace.json"
    assert cfg.duration_ms == 750
    assert cfg.iterations == -1
    assert cfg.trace_dir(42) == "/tmp/trace_42"
    assert cfg.manifest_path(42) == "/tmp/trace_42.json"
    # literal backslash-n separators (the reference CLI's encoding) also parse
    cfg2 = TraceConfig.parse(r"ACTIVITIES_LOG_FILE=/t.json\nACTIVITIES_DURATION_MSECS=9")
    assert cfg2.duration_ms == 9


def test_on_demand_trace_duration_mode(daemon, bin_dir, tmp_path):
    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=99,
        endpoint=daemon.endpoint,
        poll_interval_s=0.2,
        profiler=profiler,
    )
    try:
        assert client.start()
        assert client.instance_rank == 1

        log_file = tmp_path / "trace.json"
        result = run_dyno(
            bin_dir,
            daemon.port,
            "gputrace",
            "--job_id=99",
            "--duration_ms=100",
            f"--log_file={log_file}",
        )
        assert result.returncode == 0, result.stderr
        assert "Matched 1 processes" in result.stdout

        deadline = time.time() + 15
        while time.time() < deadline and client.traces_completed == 0:
            time.sleep(0.1)
        assert client.traces_completed == 1, client.last_error

        pid = os.getpid()
        manifest_path = tmp_path / f"trace_{pid}.json"
        assert str(manifest_path) in result.stdout
        manifest = json.loads(manifest_path.read_text())
        assert manifest["mode"] == "duration"
        assert manifest["ended_ms"] - manifest["started_ms"] >= 100
        assert profiler.calls[0] == ("start", str(tmp_path / f"trace_{pid}"))
        assert profiler.calls[1] == ("stop", None)
    finally:
        client.stop()


def test_config_kick_beats_poll_interval(daemon, bin_dir, tmp_path):
    """The daemon's "kick" datagram wakes a subscribed shim the moment a
    config is installed: with a deliberately huge poll interval, pickup
    must happen in the daemon's 10ms IPC tick, not ~poll_interval/2 —
    proving the zero-latency path, not just the polling fallback."""
    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=98,
        endpoint=daemon.endpoint,
        poll_interval_s=10.0,  # a poll-only shim would sit ~5s on average
        profiler=profiler,
    )
    try:
        assert client.start()
        log_file = tmp_path / "trace.json"
        t0 = time.time()
        result = run_dyno(
            bin_dir,
            daemon.port,
            "gputrace",
            "--job_id=98",
            "--duration_ms=100",
            f"--log_file={log_file}",
        )
        assert result.returncode == 0, result.stderr
        deadline = time.time() + 8
        while time.time() < deadline and client.traces_completed == 0:
            time.sleep(0.02)
        elapsed = time.time() - t0
        assert client.traces_completed == 1, client.last_error
        # Window is 100ms; CLI + kick + capture + manifest must land far
        # inside the 10s poll interval (generous margin for CI load).
        assert elapsed < 4.0, elapsed
        manifest = json.loads(
            (tmp_path / f"trace_{os.getpid()}.json").read_text())
        assert manifest["status"] == "ok"
    finally:
        client.stop()


def test_late_config_reply_not_dropped(daemon, tmp_path):
    """A "req" reply landing OUTSIDE any request/reply exchange (a loaded
    daemon answering after the poll's timeout) carries a config the
    daemon already cleared server-side — the shim must capture it, not
    drop it as an unexpected datagram."""
    from dynolog_tpu.client import ipc as ipc_mod

    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=97,
        endpoint=daemon.endpoint,
        poll_interval_s=0.5,
        profiler=profiler,
    )
    sender = None
    try:
        assert client.start()
        sender = ipc_mod.IpcClient()
        cfg = (
            f"ACTIVITIES_LOG_FILE={tmp_path / 'late.json'}\n"
            "ACTIVITIES_DURATION_MSECS=50"
        )
        assert sender.send(
            ipc_mod.MSG_TYPE_REQUEST, cfg.encode(), dest=client._client.name
        )
        deadline = time.time() + 10
        while time.time() < deadline and client.traces_completed == 0:
            time.sleep(0.05)
        assert client.traces_completed == 1, client.last_error
        manifest = json.loads(
            (tmp_path / f"late_{os.getpid()}.json").read_text())
        assert manifest["status"] == "ok"
    finally:
        if sender is not None:
            sender.close()
        client.stop()


def test_on_demand_trace_iteration_mode(daemon, bin_dir, tmp_path):
    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=77,
        endpoint=daemon.endpoint,
        poll_interval_s=0.2,
        profiler=profiler,
    )
    try:
        assert client.start()
        log_file = tmp_path / "itrace.json"
        result = run_dyno(
            bin_dir,
            daemon.port,
            "tpurace",
            "--job_id=77",
            "--iterations=5",
            f"--log_file={log_file}",
        )
        assert result.returncode == 0, result.stderr

        # Drive training steps until the trace completes.
        deadline = time.time() + 15
        while time.time() < deadline and client.traces_completed == 0:
            client.step()
            time.sleep(0.02)
        assert client.traces_completed == 1, client.last_error
        manifest = json.loads(
            (tmp_path / f"itrace_{os.getpid()}.json").read_text()
        )
        assert manifest["mode"] == "iterations"
        assert profiler.calls == [
            ("start", str(tmp_path / f"itrace_{os.getpid()}")),
            ("stop", None),
        ]
    finally:
        client.stop()


def test_iteration_trace_timeout_fails_loudly(daemon, bin_dir, tmp_path):
    # App never calls step(): the capture must abort WITHOUT starting the
    # profiler, record the failure in last_error, and write an error
    # manifest — not silently trace the wrong window.
    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=78,
        endpoint=daemon.endpoint,
        poll_interval_s=0.2,
        profiler=profiler,
        step_start_timeout_s=0.5,
    )
    try:
        assert client.start()
        log_file = tmp_path / "stalled.json"
        result = run_dyno(
            bin_dir,
            daemon.port,
            "tpurace",
            "--job_id=78",
            "--iterations=5",
            f"--log_file={log_file}",
        )
        assert result.returncode == 0, result.stderr

        manifest_path = tmp_path / f"stalled_{os.getpid()}.json"
        deadline = time.time() + 15
        while time.time() < deadline and not manifest_path.exists():
            time.sleep(0.1)
        manifest = json.loads(manifest_path.read_text())
        assert manifest["status"] == "error"
        assert "did not reach step" in manifest["error"]
        assert client.traces_completed == 0
        assert client.last_error and "aborted" in client.last_error
        assert profiler.calls == []  # no bogus trace window captured
    finally:
        client.stop()


def test_iteration_trace_mid_capture_stall_is_reported(daemon, bin_dir, tmp_path):
    # App steps into the capture window, then stalls: the profiler stops and
    # the manifest records the timeout instead of claiming success.
    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=79,
        endpoint=daemon.endpoint,
        poll_interval_s=0.2,
        profiler=profiler,
        step_start_timeout_s=5.0,
        step_trace_timeout_s=0.5,
    )
    try:
        assert client.start()
        log_file = tmp_path / "midstall.json"
        result = run_dyno(
            bin_dir,
            daemon.port,
            "tpurace",
            "--job_id=79",
            "--iterations=1000",
            f"--log_file={log_file}",
        )
        assert result.returncode == 0, result.stderr

        manifest_path = tmp_path / f"midstall_{os.getpid()}.json"
        deadline = time.time() + 15
        while time.time() < deadline and not manifest_path.exists():
            client.step()  # reaches the window, never finishes 1000 steps
            time.sleep(0.05)
        manifest = json.loads(manifest_path.read_text())
        assert manifest["status"] == "error"
        assert "timed out" in manifest["error"]
        assert client.traces_completed == 0
        # profiler ran (partial trace on disk) but the failure is loud
        assert profiler.calls[0][0] == "start"
        assert profiler.calls[1] == ("stop", None)
    finally:
        client.stop()


def test_busy_detection_via_rpc(daemon):
    with IpcClient() as ipc_client:
        # Register via a poll (pid ancestry [leaf]).
        assert ipc_client.request_config(55, [4242], dest=daemon.endpoint) == ""
        r1 = daemon.rpc(
            {
                "fn": "setKinetOnDemandRequest",
                "config": "A=1",
                "job_id": 55,
                "pids": [0],
                "process_limit": 3,
            }
        )
        assert r1["activityProfilersTriggered"] == [4242]
        r2 = daemon.rpc(
            {
                "fn": "setKinetOnDemandRequest",
                "config": "B=2",
                "job_id": 55,
                "pids": [0],
                "process_limit": 3,
            }
        )
        assert r2["activityProfilersTriggered"] == []
        assert r2["activityProfilersBusy"] == 1
        # Client consumes pending config; gets A only (plus the trace
        # identity the verb mints for a caller that sent none).
        config = ipc_client.request_config(55, [4242], dest=daemon.endpoint)
        lines = config.splitlines()
        assert lines[0] == "A=1", config
        assert all(ln.startswith("TRACE_CONTEXT=") for ln in lines[1:]), config


def test_daemon_restart_clients_reregister(bin_dir, tmp_path):
    # SURVEY §5.4: daemon state is all soft-state; restart = clean
    # re-registration. The shim's config polls implicitly re-create its
    # registry entry in a NEW daemon on the same endpoint, so a trace
    # triggered after the restart still completes.
    d1 = start_daemon(bin_dir)
    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=88, endpoint=d1.endpoint, poll_interval_s=0.2,
        profiler=profiler,
    )
    try:
        assert client.start()
        stop_daemon(d1)
        time.sleep(0.6)  # a few failed polls (daemon gone)
        d2 = start_daemon(bin_dir, endpoint=d1.endpoint)
        try:
            # Wait until the restarted daemon tracks the client again
            # (first poll against d2 re-registers it), then trace.
            deadline = time.time() + 15
            matched = False
            while time.time() < deadline and not matched:
                result = run_dyno(
                    bin_dir, d2.port, "gputrace", "--job_id=88",
                    "--duration_ms=100",
                    f"--log_file={tmp_path / 'r.json'}",
                )
                matched = "Matched 1 processes" in result.stdout
                if not matched:
                    time.sleep(0.3)
            assert matched, result.stdout
            deadline = time.time() + 15
            while time.time() < deadline and client.traces_completed == 0:
                time.sleep(0.1)
            assert client.traces_completed == 1, client.last_error
            assert profiler.calls[-1] == ("stop", None)
        finally:
            stop_daemon(d2)
    finally:
        client.stop()
