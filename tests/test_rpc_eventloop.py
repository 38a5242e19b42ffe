"""Event-loop transport integration tests against a live daemon: stalled
and slowloris clients must never delay other callers (RPC or OpenMetrics
scrape), persistent connections serve many requests, and the connection
cap evicts the oldest idle connection instead of refusing new callers.
(The same properties are unit-tested at the C++ layer in
src/tests/RpcTest.cpp; this file proves them through the real daemon
with the Python framed client the cluster plane uses.)"""

from __future__ import annotations

import json
import os
import signal
import socket
import struct
import sys
import threading
import time
import urllib.request
from pathlib import Path

from daemon_utils import assert_status_ok, start_daemon, stop_daemon

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from dynolog_tpu.cluster.rpc import FramedRpcClient  # noqa: E402


def _stalled_conn(port: int) -> socket.socket:
    """A connection holding half a length prefix open — the slowloris."""
    s = socket.create_connection(("localhost", port), timeout=5)
    s.sendall(b"\x20\x00")  # 2 of 4 prefix bytes, then silence
    return s


def test_stalled_client_does_not_delay_status_rpc(bin_dir):
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    stalled = []
    try:
        for _ in range(4):
            stalled.append(_stalled_conn(daemon.port))
        with FramedRpcClient("localhost", daemon.port) as client:
            t0 = time.monotonic()
            for _ in range(5):
                response = client.call({"fn": "getStatus"})
                assert_status_ok(response)
            elapsed = time.monotonic() - t0
        # The serial transport parked every caller behind the stalled
        # clients' 5s IO timeout; the event loop serves them in their own
        # service time.
        assert elapsed < 2.0, f"status RPCs took {elapsed:.1f}s"
    finally:
        for s in stalled:
            s.close()
        stop_daemon(daemon)


def test_stalled_client_does_not_delay_openmetrics_scrape(bin_dir):
    daemon = start_daemon(
        bin_dir, extra_flags=("--prometheus_port=0",), kernel_interval_s=60)
    stalled = []
    try:
        # Stall the scrape port itself (half an HTTP request line).
        for _ in range(3):
            s = socket.create_connection(
                ("localhost", daemon.prometheus_port), timeout=5)
            s.sendall(b"GET /metr")
            stalled.append(s)
        t0 = time.monotonic()
        with urllib.request.urlopen(
            f"http://localhost:{daemon.prometheus_port}/healthz", timeout=5
        ) as response:
            assert response.status == 200
        assert time.monotonic() - t0 < 2.0
    finally:
        for s in stalled:
            s.close()
        stop_daemon(daemon)


def test_persistent_connection_many_requests(bin_dir):
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    try:
        with FramedRpcClient("localhost", daemon.port) as client:
            for _ in range(50):
                assert_status_ok(client.call({"fn": "getStatus"}))
            listed = client.call({"fn": "listMetrics"})
            assert isinstance(listed.get("metrics"), list)
    finally:
        stop_daemon(daemon)


def test_connection_cap_evicts_oldest_idle(bin_dir):
    daemon = start_daemon(
        bin_dir, extra_flags=("--rpc_max_connections=4",),
        kernel_interval_s=60)
    idle = []
    try:
        for _ in range(4):
            s = socket.create_connection(("localhost", daemon.port), timeout=5)
            idle.append(s)
            time.sleep(0.05)  # deterministic idle-age ordering
        # The 5th caller gets in and is served (oldest idle evicted).
        assert_status_ok(daemon.rpc({"fn": "getStatus"}))
        # The stalest idle connection saw EOF.
        idle[0].settimeout(5)
        assert idle[0].recv(4) == b""
    finally:
        for s in idle:
            s.close()
        stop_daemon(daemon)


def test_slowloris_reaped_by_request_deadline(bin_dir):
    daemon = start_daemon(
        bin_dir, extra_flags=("--rpc_request_timeout_ms=500",),
        kernel_interval_s=60)
    try:
        s = _stalled_conn(daemon.port)
        s.settimeout(10)
        t0 = time.monotonic()
        assert s.recv(4) == b""  # daemon closes the half-frame holder
        assert time.monotonic() - t0 < 5.0
        s.close()
        # The daemon itself is unaffected.
        assert_status_ok(daemon.rpc({"fn": "getStatus"}))
    finally:
        stop_daemon(daemon)


def test_backlog_and_tuning_flags_accepted(bin_dir):
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--listen_backlog=512",
            "--rpc_worker_threads=4",
            "--rpc_idle_timeout_ms=2000",
        ),
        kernel_interval_s=60,
    )
    try:
        assert_status_ok(daemon.rpc({"fn": "getStatus"}))
        # An idle persistent connection is reaped after the idle timeout;
        # the client transparently reconnects on its next call.
        with FramedRpcClient("localhost", daemon.port) as client:
            assert_status_ok(client.call({"fn": "getStatus"}))
            time.sleep(3.0)
            assert_status_ok(client.call({"fn": "getStatus"}))
    finally:
        stop_daemon(daemon)


def test_half_close_client_still_gets_response(bin_dir):
    # send(request); shutdown(SHUT_WR); read(response) — EOF arriving
    # with the complete frame must not eat the response.
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    try:
        body = b'{"fn": "getStatus"}'
        with socket.create_connection(
            ("localhost", daemon.port), timeout=10) as s:
            s.sendall(struct.pack("<i", len(body)) + body)
            s.shutdown(socket.SHUT_WR)
            header = s.recv(4, socket.MSG_WAITALL)
            (length,) = struct.unpack("<i", header)
            got = s.recv(length, socket.MSG_WAITALL)
            assert b'"status"' in got
    finally:
        stop_daemon(daemon)


def test_sigterm_under_load_joins_all_threads_within_grace(bin_dir):
    # Signal-driven shutdown under load: SIGTERM lands while an async
    # capture is in flight, collectors are ticking every second, and RPC +
    # scrape clients are hammering both listeners. The daemon must join
    # every thread (collector loops mid-tick, capture worker, event
    # loops) and exit 0 well inside the grace period — a kill -9 cleanup
    # or a wedged join here is exactly the orphaned-worker bug this test
    # exists to catch.
    daemon = start_daemon(
        bin_dir, extra_flags=("--prometheus_port=0",), kernel_interval_s=1)
    stop = threading.Event()

    def hammer_rpc():
        try:
            with FramedRpcClient("localhost", daemon.port) as client:
                while not stop.is_set():
                    client.call({"fn": "getStatus"})
        except Exception:  # noqa: BLE001 - expected once shutdown begins
            pass

    def hammer_scrape():
        while not stop.is_set():
            try:
                urllib.request.urlopen(
                    f"http://localhost:{daemon.prometheus_port}/metrics",
                    timeout=2,
                ).read()
            except Exception:  # noqa: BLE001 - expected once shutdown begins
                return

    threads = [
        threading.Thread(target=hammer_rpc, daemon=True),
        threading.Thread(target=hammer_rpc, daemon=True),
        threading.Thread(target=hammer_scrape, daemon=True),
    ]
    try:
        # Async capture in flight: its worker thread must be cancelled and
        # joined by shutdown, not orphaned past main().
        started = daemon.rpc({"fn": "cputrace", "duration_ms": 8000})
        assert started is not None and started.get("status") == "started"
        for t in threads:
            t.start()
        time.sleep(0.5)  # load running, capture mid-window

        daemon.proc.send_signal(signal.SIGTERM)
        t0 = time.monotonic()
        rc = daemon.proc.wait(timeout=10)
        elapsed = time.monotonic() - t0
        # Exit code 0 = main() returned after joining every worker; a
        # thread that outlived shutdown would abort/terminate instead.
        assert rc == 0, f"daemon exited {rc}"
        assert elapsed < 5.0, f"shutdown took {elapsed:.1f}s"
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        if daemon.proc.poll() is None:
            daemon.proc.kill()


def test_pipelined_requests_on_raw_socket(bin_dir):
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    try:
        body = b'{"fn": "getStatus"}'
        frame = struct.pack("<i", len(body)) + body
        with socket.create_connection(
            ("localhost", daemon.port), timeout=10) as s:
            s.sendall(frame + frame)  # two requests back to back
            for _ in range(2):
                header = s.recv(4)
                (length,) = struct.unpack("<i", header)
                got = b""
                while len(got) < length:
                    chunk = s.recv(length - len(got))
                    assert chunk
                    got += chunk
                assert b'"status"' in got
    finally:
        stop_daemon(daemon)


# ---- streamed artifact fetch (fetchTrace CHUNK/END frames) ----------------


def test_fetch_trace_streams_artifact_end_to_end(bin_dir, tmp_path):
    """fetchTrace through the real daemon: a multi-chunk artifact under
    --trace_output_root streams back byte-identical over the kept-alive
    framed connection, and the connection still serves verbs after."""
    artifact = tmp_path / "machine.xplane.pb"
    payload = bytes((i * 131) % 251 for i in range(3 << 20))
    artifact.write_bytes(payload)
    daemon = start_daemon(
        bin_dir, extra_flags=(f"--trace_output_root={tmp_path}",),
        kernel_interval_s=60)
    dest = tmp_path / "fetched.xplane.pb"
    try:
        with FramedRpcClient("localhost", daemon.port) as client:
            header = client.fetch_to_file(str(artifact), str(dest))
            assert header is not None and header["status"] == "ok"
            assert header["streamed_bytes"] == len(payload)
            # The stream left the connection reusable.
            assert_status_ok(client.call({"fn": "getStatus"}))
        assert dest.read_bytes() == payload
        assert not (tmp_path / "fetched.xplane.pb.tmp").exists()
    finally:
        stop_daemon(daemon)


def test_fetch_trace_refused_without_output_root(bin_dir, tmp_path):
    artifact = tmp_path / "machine.xplane.pb"
    artifact.write_bytes(b"bytes")
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    try:
        with FramedRpcClient("localhost", daemon.port) as client:
            header = client.fetch_to_file(
                str(artifact), str(tmp_path / "out.pb"))
        assert header is not None and header["status"] == "failed"
        assert "trace_output_root" in header["error"]
        assert not (tmp_path / "out.pb").exists()
        assert not (tmp_path / "out.pb.tmp").exists()
    finally:
        stop_daemon(daemon)


def test_dyno_fetch_cli_round_trip(bin_dir, tmp_path):
    """`dyno fetch --path=... --log_file=...`: exit 0 + atomic local
    write; refusal (no --trace_output_root on the daemon) exits 1."""
    from daemon_utils import run_dyno

    artifact = tmp_path / "machine.xplane.pb"
    payload = bytes((i * 17) % 256 for i in range(1 << 20))
    artifact.write_bytes(payload)
    daemon = start_daemon(
        bin_dir, extra_flags=(f"--trace_output_root={tmp_path}",),
        kernel_interval_s=60)
    dest = tmp_path / "cli_fetched.pb"
    try:
        out = run_dyno(
            bin_dir, daemon.port, "fetch",
            f"--path={artifact}", f"--log_file={dest}")
        assert out.returncode == 0, out.stdout + out.stderr
        assert f"fetched {len(payload)} bytes" in out.stdout
        assert dest.read_bytes() == payload
        # Refusal: a path outside the root exits 1, writes nothing.
        out = run_dyno(
            bin_dir, daemon.port, "fetch",
            "--path=/etc/hostname",
            f"--log_file={tmp_path / 'nope.pb'}")
        assert out.returncode == 1
        assert not (tmp_path / "nope.pb").exists()
        assert not (tmp_path / "nope.pb.tmp").exists()
    finally:
        stop_daemon(daemon)


def test_fetch_client_disconnect_mid_stream_daemon_survives(bin_dir, tmp_path):
    """A client that vanishes mid-stream (daemon-side producer likely
    parked on backpressure) must cost only that connection: the daemon
    keeps serving, and SIGTERM shutdown stays prompt."""
    artifact = tmp_path / "big.xplane.pb"
    artifact.write_bytes(os.urandom(32 << 20))
    daemon = start_daemon(
        bin_dir, extra_flags=(f"--trace_output_root={tmp_path}",),
        kernel_interval_s=60)
    try:
        body = json.dumps(
            {"fn": "fetchTrace", "path": str(artifact)}).encode()
        s = socket.create_connection(("localhost", daemon.port), timeout=10)
        s.sendall(struct.pack("<i", len(body)) + body)
        assert s.recv(4096)  # some of the header/stream arrived
        s.close()  # vanish mid-stream
        with FramedRpcClient("localhost", daemon.port) as client:
            assert_status_ok(client.call({"fn": "getStatus"}))
        daemon.proc.send_signal(signal.SIGTERM)
        rc = daemon.proc.wait(timeout=10)
        assert rc == 0, f"daemon exited {rc}"
    finally:
        if daemon.proc.poll() is None:
            daemon.proc.kill()
