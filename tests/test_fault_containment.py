"""Fault-containment acceptance drills against a live daemon: with
failpoints injecting a throwing collector and a dead relay sink, the
daemon must stay serving RPC + OpenMetrics throughout, `health` must
report the affected component as degraded with a non-empty last_error,
and the component must return to `up` once the fault clears. (The same
properties are unit-tested at the C++ layer in SupervisorTest /
RemoteLoggersTest / RpcTest; this file proves them end to end through
dynologd, its supervision flags, and the DYNO_FAILPOINTS env.)"""

from __future__ import annotations

import socket
import threading
import time
import urllib.request

from daemon_utils import assert_status_ok, run_dyno, start_daemon, stop_daemon

FAST_SUPERVISOR = (
    "--supervisor_backoff_initial_ms=50",
    "--supervisor_backoff_max_ms=100",
    "--supervisor_max_consecutive_failures=2",
    "--supervisor_degraded_retry_s=1",
)


def _health(daemon) -> dict:
    response = daemon.rpc({"fn": "health"})
    assert response is not None
    return response


def _wait_component(daemon, component, predicate, timeout_s=20.0):
    """Polls health until predicate(component_snapshot) or timeout;
    returns the last snapshot either way."""
    deadline = time.monotonic() + timeout_s
    snap = None
    while time.monotonic() < deadline:
        snap = _health(daemon)["components"].get(component)
        if snap is not None and predicate(snap):
            return snap
        time.sleep(0.1)
    return snap


def _scrape(port: int) -> str:
    with urllib.request.urlopen(
        f"http://localhost:{port}/metrics", timeout=5
    ) as response:
        return response.read().decode()


def test_health_verb_reports_supervised_components(bin_dir):
    daemon = start_daemon(bin_dir, kernel_interval_s=1)
    try:
        snap = _wait_component(
            daemon, "kernel_monitor", lambda c: c["state"] == "up")
        assert snap is not None and snap["state"] == "up"
        doc = _health(daemon)
        assert doc["status"] == "ok"
        assert doc["degraded"] == []
        assert "ipc_monitor" in doc["components"]
        assert doc["uptime_s"] >= 0
    finally:
        stop_daemon(daemon)


def test_throwing_collector_degrades_then_recovers(bin_dir):
    # collector.kernel.step=throw*3 with a 2-failure breaker: the kernel
    # loop is parked as degraded mid-drill, every other plane keeps
    # serving, and the third (final) throw exhausts the failpoint so the
    # next probe tick recovers it.
    daemon = start_daemon(
        bin_dir,
        extra_flags=("--prometheus_port=0", *FAST_SUPERVISOR),
        kernel_interval_s=1,
        env={"DYNO_FAILPOINTS": "collector.kernel.step=throw*3"},
    )
    try:
        snap = _wait_component(
            daemon, "kernel_monitor", lambda c: c["state"] == "degraded")
        assert snap is not None and snap["state"] == "degraded", snap
        assert "collector.kernel.step" in snap["last_error"]
        # Degraded is observable, not fatal: RPC and the scrape plane are
        # alive while the collector is parked.
        assert_status_ok(daemon.rpc({"fn": "getStatus"}))
        exposition = _scrape(daemon.prometheus_port)
        assert (
            'dynolog_component_up{component="kernel_monitor"} 0'
            in exposition
        )
        doc = _health(daemon)
        assert doc["status"] == "degraded"
        assert "kernel_monitor" in doc["degraded"]

        # Fault clears (failpoint count exhausted): the degraded-cadence
        # probe tick returns the component to up with the failure history
        # retained.
        snap = _wait_component(
            daemon, "kernel_monitor", lambda c: c["state"] == "up")
        assert snap is not None and snap["state"] == "up", snap
        assert snap["restarts"] == 3
        assert _health(daemon)["status"] == "ok"
        exposition = _scrape(daemon.prometheus_port)
        assert (
            'dynolog_component_up{component="kernel_monitor"} 1'
            in exposition
        )
    finally:
        stop_daemon(daemon)


def test_dead_relay_sink_degrades_without_stalling_collector(bin_dir):
    # A relay that refuses connections: the sink breaker opens, intervals
    # are counted as drops (never queued, never stalling the tick), and
    # when a relay appears on the port the sink recovers to up.
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    relay_port = probe.getsockname()[1]
    probe.close()  # freed: nothing listens here until we bind below

    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--use_tcp_relay",
            "--relay_host=127.0.0.1",
            f"--relay_port={relay_port}",
            "--sink_breaker_failures=2",
            "--sink_retry_initial_ms=100",
            "--sink_retry_max_ms=200",
            "--sink_connect_timeout_ms=200",
            *FAST_SUPERVISOR,
        ),
        kernel_interval_s=1,
    )
    received = []
    try:
        snap = _wait_component(
            daemon, "relay_sink",
            lambda c: c["state"] == "degraded" and c["drops"] >= 2)
        assert snap is not None and snap["state"] == "degraded", snap
        assert snap["last_error"]
        # The collector itself never degraded — only its sink did.
        kernel = _health(daemon)["components"]["kernel_monitor"]
        assert kernel["state"] == "up"
        assert_status_ok(daemon.rpc({"fn": "getStatus"}))

        # Relay comes up: next delivery closes the breaker.
        relay = socket.socket()
        relay.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        relay.bind(("127.0.0.1", relay_port))
        relay.listen(4)

        def accept_loop():
            relay.settimeout(30)
            try:
                while True:
                    conn, _ = relay.accept()
                    conn.settimeout(30)
                    threading.Thread(
                        target=_drain, args=(conn,), daemon=True).start()
            except OSError:
                return

        def _drain(conn):
            with conn:
                while True:
                    try:
                        chunk = conn.recv(4096)
                    except OSError:
                        return
                    if not chunk:
                        return
                    received.append(chunk)

        threading.Thread(target=accept_loop, daemon=True).start()
        snap = _wait_component(
            daemon, "relay_sink", lambda c: c["state"] == "up")
        assert snap is not None and snap["state"] == "up", snap
        deadline = time.monotonic() + 10
        while not received and time.monotonic() < deadline:
            time.sleep(0.1)
        assert received, "restored relay never saw a metric line"
        relay.close()
    finally:
        stop_daemon(daemon)


def test_failpoint_rpc_verb_drives_runtime_drill(bin_dir):
    # --enable_failpoints: arm/list/disarm over RPC; without the flag the
    # verb is refused (covered by the C++ RpcTest; here we prove the
    # enabled path against the real daemon).
    daemon = start_daemon(
        bin_dir,
        extra_flags=("--enable_failpoints", *FAST_SUPERVISOR),
        kernel_interval_s=1,
    )
    try:
        armed = daemon.rpc({
            "fn": "failpoint", "action": "arm",
            "name": "collector.kernel.step", "spec": "throw*1"})
        assert armed == {"status": "ok"}
        snap = _wait_component(
            daemon, "kernel_monitor", lambda c: c["restarts"] >= 1)
        assert snap is not None and snap["restarts"] >= 1, snap
        listed = daemon.rpc({"fn": "failpoint", "action": "list"})
        assert listed["status"] == "ok"
        hits = {
            fp["name"]: fp["hits"] for fp in listed["failpoints"]}
        assert hits.get("collector.kernel.step") == 1
        # health carries the armed-failpoint inventory when drills are on.
        doc = _health(daemon)
        assert any(
            fp["name"] == "collector.kernel.step"
            for fp in doc.get("failpoints", []))
        assert daemon.rpc(
            {"fn": "failpoint", "action": "disarm", "name": "*"}
        ) == {"status": "ok"}
        # And the component recovers.
        snap = _wait_component(
            daemon, "kernel_monitor", lambda c: c["state"] == "up")
        assert snap is not None and snap["state"] == "up"
    finally:
        stop_daemon(daemon)


def test_dyno_health_cli_exit_codes(bin_dir):
    daemon = start_daemon(bin_dir, kernel_interval_s=1)
    try:
        _wait_component(daemon, "kernel_monitor", lambda c: c["state"] == "up")
        result = run_dyno(bin_dir, daemon.port, "health")
        assert result.returncode == 0, result.stderr
        assert "kernel_monitor" in result.stdout
        assert "daemon: ok" in result.stdout
    finally:
        stop_daemon(daemon)
    # Unreachable daemon: exit 2 (fleet health checks key on this).
    result = run_dyno(bin_dir, daemon.port, "health")
    assert result.returncode == 2


def test_dyno_health_cli_reports_degraded(bin_dir):
    daemon = start_daemon(
        bin_dir,
        extra_flags=FAST_SUPERVISOR,
        kernel_interval_s=1,
        env={"DYNO_FAILPOINTS": "collector.kernel.step=throw*200"},
    )
    try:
        _wait_component(
            daemon, "kernel_monitor", lambda c: c["state"] == "degraded")
        result = run_dyno(bin_dir, daemon.port, "health")
        assert result.returncode == 1, result.stdout + result.stderr
        assert "degraded" in result.stdout
        assert "collector.kernel.step" in result.stdout
    finally:
        stop_daemon(daemon)
