"""gRPC runtime-metrics backend e2e: the daemon's from-scratch HTTP/2 gRPC
client (src/common/GrpcClient.cpp) against a REAL grpcio server playing the
TPU runtime's RuntimeMetricService — the strongest interop check available
off-TPU (grpcio is the same HTTP/2 stack production runtimes embed).

The fake serves the vendored schema (src/tpumon/proto/tpu_metric_service
.proto) with hand-serialized protobuf bytes, so the test pins the wire
format itself rather than trusting one codec to validate the other. Metric
names and attribute shapes are the ones libtpu 0.0.34 serves beside a JAX
job on a TPU v5e (chip_smoke.py talks to the real one): the service has
its own names, and the libtpu SDK's are NOT_FOUND there.
"""

import json
import struct
import time
from concurrent import futures

import pytest

grpc = pytest.importorskip("grpc", reason="fake runtime server needs grpcio")

from daemon_utils import run_dyno, start_daemon, stop_daemon

SERVICE = "tpu.monitoring.runtime.RuntimeMetricService"


# -- minimal protobuf writers (mirror of src/common/ProtoWire.cpp) ---------

def varint(v: int) -> bytes:
    out = b""
    while v >= 0x80:
        out += bytes([v & 0x7F | 0x80])
        v >>= 7
    return out + bytes([v])


def tag(field: int, wire: int) -> bytes:
    return varint(field << 3 | wire)


def pb_str(field: int, s: str) -> bytes:
    b = s.encode()
    return tag(field, 2) + varint(len(b)) + b


def pb_msg(field: int, body: bytes) -> bytes:
    return tag(field, 2) + varint(len(body)) + body


def pb_varint(field: int, v: int) -> bytes:
    return tag(field, 0) + varint(v)


def pb_double(field: int, v: float) -> bytes:
    return tag(field, 1) + struct.pack("<d", v)


def gauge_double(v: float) -> bytes:
    return pb_msg(3, pb_double(1, v))  # Metric.gauge{as_double}


def gauge_int(v: int) -> bytes:
    return pb_msg(3, pb_varint(2, v))  # Metric.gauge{as_int}


def device_attr(device: int) -> bytes:
    # Metric.attribute{key: "device-id", value{int_attr}}
    return pb_msg(1, pb_str(1, "device-id") + pb_msg(2, pb_varint(3, device)))


def ordinal_attr(name: str, device: int) -> bytes:
    # The hlo.* metrics' form: Metric.attribute{key: <metric name>,
    # value{kvlist_attr{core_type: "tensor_core", device_ordinal: "<n>"}}}
    def entry(key: str, value: str) -> bytes:
        return pb_msg(1, pb_str(1, key) + pb_msg(2, pb_str(1, value)))

    kvlist = entry("core_type", "tensor_core") + entry(
        "device_ordinal", str(device))
    return pb_msg(1, pb_str(1, name) + pb_msg(2, pb_msg(6, kvlist)))


def tpu_metric(name: str, per_device: list[bytes]) -> bytes:
    # MetricResponse{metric: TPUMetric{name, metrics...}}
    body = pb_str(1, name) + b"".join(pb_msg(3, m) for m in per_device)
    return pb_msg(1, body)


DUTY = "tpu.runtime.tensorcore.dutycycle.percent"
HBM_USED = "tpu.runtime.hbm.memory.usage.bytes"
QUEUE = "hlo.queue.size.gauge"
TIMING = "hlo.execution.timing.distribution.microseconds"
SUPPORTED = [DUTY, HBM_USED, QUEUE, TIMING, "tpu.runtime.uptime.seconds.gauge"]

METRIC_RESPONSES = {
    DUTY: tpu_metric(
        DUTY,
        # devices deliberately out of order: the attribute must win
        [device_attr(1) + gauge_double(88.5), device_attr(0) + gauge_double(97.25)],
    ),
    HBM_USED: tpu_metric(
        HBM_USED,
        [device_attr(0) + gauge_int(2 * 1024**3), device_attr(1) + gauge_int(1024**3)],
    ),
    QUEUE: tpu_metric(
        QUEUE,
        [ordinal_attr(QUEUE, 1) + gauge_int(7), ordinal_attr(QUEUE, 0) + gauge_int(3)],
    ),
    TIMING: tpu_metric(
        TIMING,
        # Distribution{count=4, mean=300.25} -> mean; Summary{count=4,
        # sum=500.0} -> 125
        [ordinal_attr(TIMING, 0) + pb_msg(5, pb_varint(1, 4) + pb_double(2, 300.25)),
         ordinal_attr(TIMING, 1) + pb_msg(6, pb_varint(1, 4) + pb_double(2, 500.0))],
    ),
}


class FakeRuntimeMetricService(grpc.GenericRpcHandler):
    def service(self, handler_call_details):
        method = handler_call_details.method.rsplit("/", 1)[-1]
        if not handler_call_details.method.startswith(f"/{SERVICE}/"):
            return None
        if method == "GetTpuRuntimeStatus":
            def handler(request: bytes, ctx):
                # host_name=1; core_states entries {key=1, value=2(opaque)}
                return (pb_str(1, "fake-tpu-host")
                        + pb_msg(2, pb_varint(1, 0) + pb_msg(2, b""))
                        + pb_msg(2, pb_varint(1, 1) + pb_msg(2, b"")))
        elif method == "ListSupportedMetrics":
            def handler(request: bytes, ctx):
                return b"".join(
                    pb_msg(1, pb_str(1, name)) for name in SUPPORTED
                )
        elif method == "GetRuntimeMetric":
            def handler(request: bytes, ctx):
                # MetricRequest.metric_name: tag 0x0A + 1-byte len + bytes
                # (every served name is under 128 bytes).
                assert request[:1] == b"\x0a", request
                name = request[2:2 + request[1]].decode()
                resp = METRIC_RESPONSES.get(name)
                if resp is None:
                    ctx.abort(grpc.StatusCode.INVALID_ARGUMENT, "unknown metric")
                return resp
        else:
            return None
        return grpc.unary_unary_rpc_method_handler(
            handler,
            request_deserializer=lambda b: b,
            response_serializer=lambda b: b,
        )


@pytest.fixture(scope="module")
def grpc_server():
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers((FakeRuntimeMetricService(),))
    port = server.add_insecure_port("localhost:0")
    server.start()
    yield port
    server.stop(0)


def test_grpc_backend_reads_runtime_metrics(bin_dir, grpc_server, tmp_path, monkeypatch):
    log_path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("DYNO_TPU_GRPC_PORT", str(grpc_server))
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=grpc",
            "--tpu_monitor_reporting_interval_s=1",
            f"--json_log_file={log_path}",
        ),
        kernel_interval_s=60,
    )
    try:
        deadline = time.time() + 15
        rows = {}
        while time.time() < deadline and len(rows) < 2:
            if log_path.exists():
                for line in log_path.read_text().splitlines():
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if "tensorcore_duty_cycle_pct" in row or "hbm_used_bytes" in row:
                        rows[row["device"]] = row
            time.sleep(0.25)
        assert set(rows) == {0, 1}, rows
        # Attribute-carried device ids win over list order.
        assert rows[0]["tensorcore_duty_cycle_pct"] == pytest.approx(97.25)
        assert rows[1]["tensorcore_duty_cycle_pct"] == pytest.approx(88.5)
        assert rows[0]["hbm_used_bytes"] == pytest.approx(2 * 1024**3)
        assert rows[1]["hbm_used_bytes"] == pytest.approx(1024**3)
        # The hlo.* metrics name their device in a key/value list.
        assert rows[0]["hlo_queue_size"] == pytest.approx(3.0)
        assert rows[1]["hlo_queue_size"] == pytest.approx(7.0)
        # Distribution -> mean, Summary -> sum/count.
        assert rows[0]["hlo_execution_timing_us"] == pytest.approx(300.25)
        assert rows[1]["hlo_execution_timing_us"] == pytest.approx(125.0)
    finally:
        stop_daemon(daemon)


def test_tpustatus_verb(bin_dir, grpc_server, monkeypatch):
    monkeypatch.setenv("DYNO_TPU_GRPC_PORT", str(grpc_server))
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    try:
        out = run_dyno(bin_dir, daemon.port, "tpustatus")
        assert out.returncode == 0, out.stderr
        body = json.loads(out.stdout.split("response = ", 1)[1])
        assert body["status"] == "ok"
        assert body["host_name"] == "fake-tpu-host"
        assert body["cores"] == [0, 1]
    finally:
        stop_daemon(daemon)


def test_tpustatus_verb_no_runtime(bin_dir, monkeypatch):
    monkeypatch.setenv("DYNO_TPU_GRPC_PORT", "1")
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    try:
        out = run_dyno(bin_dir, daemon.port, "tpustatus")
        body = json.loads(out.stdout.split("response = ", 1)[1])
        assert body["status"] == "failed"
        assert "no TPU runtime metric service" in body["error"]
    finally:
        stop_daemon(daemon)


def test_grpc_backend_absent_server_degrades(bin_dir, tmp_path, monkeypatch):
    # Nothing listening: explicit grpc mode stays up (re-probing each
    # tick) and the daemon keeps serving RPC with no metric rows — the
    # DcgmApiStub soft-fail posture, with recovery.
    monkeypatch.setenv("DYNO_TPU_GRPC_PORT", "1")  # reserved port, never open
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=grpc",
            "--tpu_monitor_reporting_interval_s=1",
        ),
        kernel_interval_s=1,
    )
    try:
        status = run_dyno(bin_dir, daemon.port, "status")
        assert '"status":1' in status.stdout.replace(" ", "")
    finally:
        stop_daemon(daemon)


def test_grpc_backend_polls_every_runtime_port(bin_dir, tmp_path, monkeypatch):
    """Multi-runtime host (one runtime metric service per slice): ALL ports
    in TPU_RUNTIME_METRICS_PORTS are polled, each runtime's devices logged
    as distinct rows at a stable per-runtime device-id stride (the DCGM
    analog watches every device on the host, DcgmGroupInfo.cpp:161-197)."""
    server_a = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    server_a.add_generic_rpc_handlers((FakeRuntimeMetricService(),))
    port_a = server_a.add_insecure_port("localhost:0")
    server_a.start()
    server_b = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    server_b.add_generic_rpc_handlers((FakeRuntimeMetricService(),))
    port_b = server_b.add_insecure_port("localhost:0")
    server_b.start()

    log_path = tmp_path / "metrics.jsonl"
    monkeypatch.delenv("DYNO_TPU_GRPC_PORT", raising=False)
    monkeypatch.setenv("TPU_RUNTIME_METRICS_PORTS", f"{port_a},{port_b}")
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=grpc",
            "--tpu_monitor_reporting_interval_s=1",
            f"--json_log_file={log_path}",
        ),
        kernel_interval_s=60,
    )
    try:
        deadline = time.time() + 15
        rows = {}
        while time.time() < deadline and len(rows) < 4:
            if log_path.exists():
                for line in log_path.read_text().splitlines():
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if "tensorcore_duty_cycle_pct" in row:
                        rows[row["device"]] = row
            time.sleep(0.25)
        # Runtime 0 -> devices 0,1; runtime 1 -> devices 16,17 (stride 16).
        assert set(rows) == {0, 1, 16, 17}, sorted(rows)
        for base in (0, 16):
            assert rows[base]["tensorcore_duty_cycle_pct"] == pytest.approx(97.25)
            assert rows[base + 1]["tensorcore_duty_cycle_pct"] == pytest.approx(88.5)
    finally:
        stop_daemon(daemon)
        server_a.stop(0)
        server_b.stop(0)


def test_grpc_device_offsets_stable_and_runtime_recovers(
    bin_dir, tmp_path, monkeypatch
):
    """Boot-order race: a runtime that is down at daemon start must keep
    its device-id slot (offsets come from the configured port list, not
    from whichever probe succeeded), and must be picked up by the lazy
    re-probe once it comes up — not stay unmonitored for the daemon's
    lifetime."""
    import socket as socket_mod

    # Reserve a port for the late runtime, then release it.
    s = socket_mod.socket()
    s.bind(("localhost", 0))
    late_port = s.getsockname()[1]
    s.close()

    server_b = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    server_b.add_generic_rpc_handlers((FakeRuntimeMetricService(),))
    port_b = server_b.add_insecure_port("localhost:0")
    server_b.start()

    log_path = tmp_path / "metrics.jsonl"
    monkeypatch.delenv("DYNO_TPU_GRPC_PORT", raising=False)
    monkeypatch.setenv("TPU_RUNTIME_METRICS_PORTS", f"{late_port},{port_b}")
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=grpc",
            "--tpu_monitor_reporting_interval_s=1",
            f"--json_log_file={log_path}",
        ),
        kernel_interval_s=60,
    )
    server_a = None
    try:
        def seen_devices():
            rows = set()
            if log_path.exists():
                for line in log_path.read_text().splitlines():
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if "tensorcore_duty_cycle_pct" in row:
                        rows.add(row["device"])
            return rows

        # Runtime 1 (port_b) keeps slot 1 -> devices 16,17 even though
        # runtime 0 was down at init.
        deadline = time.time() + 15
        while time.time() < deadline and not {16, 17} <= seen_devices():
            time.sleep(0.25)
        assert {16, 17} <= seen_devices(), seen_devices()
        assert not {0, 1} & seen_devices(), seen_devices()

        # The late runtime comes up on its configured port: the re-probe
        # binds it and its devices appear in slot 0.
        server_a = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        server_a.add_generic_rpc_handlers((FakeRuntimeMetricService(),))
        bound = server_a.add_insecure_port(f"localhost:{late_port}")
        if bound == 0:
            pytest.skip("reserved port got taken; can't stage late runtime")
        server_a.start()
        deadline = time.time() + 15
        while time.time() < deadline and not {0, 1} <= seen_devices():
            time.sleep(0.25)
        assert {0, 1} <= seen_devices(), seen_devices()
    finally:
        stop_daemon(daemon)
        server_b.stop(0)
        if server_a:
            server_a.stop(0)


class FailingRuntimeService(grpc.GenericRpcHandler):
    """GetTpuRuntimeStatus fails two ways: trailers-only UNAVAILABLE, or
    (method suffix '/GetRuntimeMetric') one DATA message followed by an
    INTERNAL trailer — the mid-stream error case."""

    def service(self, handler_call_details):
        method = handler_call_details.method.rsplit("/", 1)[-1]
        if not handler_call_details.method.startswith(f"/{SERVICE}/"):
            return None
        if method == "GetTpuRuntimeStatus":
            def handler(request: bytes, ctx):
                ctx.abort(grpc.StatusCode.UNAVAILABLE, "runtime rebooting")
            return grpc.unary_unary_rpc_method_handler(
                handler,
                request_deserializer=lambda b: b,
                response_serializer=lambda b: b,
            )
        if method == "ListSupportedMetrics":
            def handler(request: bytes, ctx):
                return pb_msg(1, pb_str(1, DUTY))
            return grpc.unary_unary_rpc_method_handler(
                handler,
                request_deserializer=lambda b: b,
                response_serializer=lambda b: b,
            )
        if method == "GetRuntimeMetric":
            def handler(request: bytes, ctx):
                # Partial DATA first, then a non-OK trailer: the client
                # must fail the call, not consume the partial message.
                yield tpu_metric(
                    DUTY, [device_attr(0) + gauge_double(50.0)])
                ctx.abort(grpc.StatusCode.INTERNAL, "mid-stream failure")
            return grpc.unary_stream_rpc_method_handler(
                handler,
                request_deserializer=lambda b: b,
                response_serializer=lambda b: b,
            )
        return None


@pytest.fixture()
def failing_server():
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers((FailingRuntimeService(),))
    port = server.add_insecure_port("localhost:0")
    server.start()
    yield port
    server.stop(0)


def test_grpc_status_surfaced_trailers_only(bin_dir, failing_server, monkeypatch):
    """A trailers-only gRPC error must surface the server's own status
    code and message, not a generic 'no response' string."""
    monkeypatch.setenv("DYNO_TPU_GRPC_PORT", str(failing_server))
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    try:
        out = run_dyno(bin_dir, daemon.port, "tpustatus")
        body = json.loads(out.stdout.split("response = ", 1)[1])
        assert body["status"] == "failed"
        assert "UNAVAILABLE" in body["error"], body
        assert "runtime rebooting" in body["error"], body
    finally:
        stop_daemon(daemon)


def test_grpc_status_after_partial_data(bin_dir, failing_server, tmp_path, monkeypatch):
    """A non-OK status arriving AFTER DATA frames must fail the call: the
    partial metric payload from the failed stream is never logged as a
    real sample."""
    log_path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("DYNO_TPU_GRPC_PORT", str(failing_server))
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=grpc",
            "--tpu_monitor_reporting_interval_s=1",
            f"--json_log_file={log_path}",
        ),
        kernel_interval_s=60,
    )
    try:
        # Give the monitor several ticks to (wrongly) log the partial data.
        time.sleep(3.5)
        rows = []
        if log_path.exists():
            for line in log_path.read_text().splitlines():
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "tensorcore_duty_cycle_pct" in row:
                    rows.append(row)
        assert rows == [], f"partial data from INTERNAL stream was logged: {rows}"
    finally:
        stop_daemon(daemon)


def test_explicit_grpc_mode_waits_for_runtime(bin_dir, tmp_path, monkeypatch):
    """Explicit --tpu_metric_backend=grpc with every runtime down at init:
    the backend stays up empty (no fall-through to other backends exists)
    and binds the runtime when it appears — daemons routinely start before
    the TPU runtimes at host boot."""
    import socket as socket_mod

    s = socket_mod.socket()
    s.bind(("localhost", 0))
    late_port = s.getsockname()[1]
    s.close()

    log_path = tmp_path / "metrics.jsonl"
    monkeypatch.delenv("DYNO_TPU_GRPC_PORT", raising=False)
    monkeypatch.setenv("TPU_RUNTIME_METRICS_PORTS", str(late_port))
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=grpc",
            "--tpu_monitor_reporting_interval_s=1",
            f"--json_log_file={log_path}",
        ),
        kernel_interval_s=60,
    )
    server = None
    try:
        time.sleep(1.5)  # a few empty ticks first
        server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        server.add_generic_rpc_handlers((FakeRuntimeMetricService(),))
        if server.add_insecure_port(f"localhost:{late_port}") == 0:
            pytest.skip("reserved port got taken")
        server.start()
        deadline = time.time() + 15
        seen = set()
        while time.time() < deadline and not {0, 1} <= seen:
            if log_path.exists():
                for line in log_path.read_text().splitlines():
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if "tensorcore_duty_cycle_pct" in row:
                        seen.add(row["device"])
            time.sleep(0.25)
        assert {0, 1} <= seen, seen
    finally:
        stop_daemon(daemon)
        if server:
            server.stop(0)


def _rows_with(log_path, *, skip_lines=0):
    """(n_lines, rows) of tpumon rows parsed after the first skip_lines."""
    rows = []
    lines = []
    if log_path.exists():
        lines = log_path.read_text().splitlines()
        for line in lines[skip_lines:]:
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if ("tensorcore_duty_cycle_pct" in row
                    or "tpu_duty_cycle_pct" in row or "tpu_error" in row):
                rows.append(row)
    return len(lines), rows


def test_grpc_backend_flap_up_down_up(bin_dir, tmp_path, monkeypatch):
    """The full mid-run outage cycle (a job restart, a runtime crash):
    a runtime that was healthy dies while the daemon polls, then comes
    back. During the gap the daemon must emit tpu_error rows for the
    devices it was serving (blank→dcgm_error posture,
    DcgmGroupInfo.cpp:320-332) — never repeat stale values, never go
    silent — and must re-bind automatically when the source returns,
    without a daemon restart."""
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers((FakeRuntimeMetricService(),))
    port = server.add_insecure_port("localhost:0")
    server.start()

    log_path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("DYNO_TPU_GRPC_PORT", str(port))
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=grpc",
            "--tpu_monitor_reporting_interval_s=1",
            f"--json_log_file={log_path}",
        ),
        kernel_interval_s=60,
    )
    server2 = None
    try:
        # Phase 1 (up): live rows for both devices.
        deadline = time.time() + 15
        while time.time() < deadline:
            _, rows = _rows_with(log_path)
            live = {r["device"] for r in rows if "tensorcore_duty_cycle_pct" in r}
            if {0, 1} <= live:
                break
            time.sleep(0.25)
        assert {0, 1} <= live, rows

        # Phase 2 (down): kill the server; from here every NEW row must
        # be an error row — devices visible, no values repeated.
        server.stop(None)
        time.sleep(1.5)  # let an in-flight tick finish against old state
        mark, _ = _rows_with(log_path)
        deadline = time.time() + 15
        err_devices = set()
        while time.time() < deadline and not {0, 1} <= err_devices:
            _, rows = _rows_with(log_path, skip_lines=mark)
            err_devices = {
                r["device"] for r in rows if r.get("tpu_error") == 1}
            time.sleep(0.25)
        assert {0, 1} <= err_devices, rows
        stale = [r for r in rows if "tensorcore_duty_cycle_pct" in r]
        assert stale == [], f"stale values during outage: {stale}"

        # Phase 3 (up again): same port, fresh server. The per-tick
        # re-probe must re-bind and live rows resume.
        server2 = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
        server2.add_generic_rpc_handlers((FakeRuntimeMetricService(),))
        if server2.add_insecure_port(f"localhost:{port}") == 0:
            pytest.skip("port got taken between server generations")
        server2.start()
        mark, _ = _rows_with(log_path)
        deadline = time.time() + 15
        live = set()
        while time.time() < deadline and not {0, 1} <= live:
            _, rows = _rows_with(log_path, skip_lines=mark)
            live = {r["device"] for r in rows
                    if "tensorcore_duty_cycle_pct" in r}
            time.sleep(0.25)
        assert {0, 1} <= live, rows
        # Values are the source's, not an error echo.
        for r in rows:
            if r["device"] == 0 and "tensorcore_duty_cycle_pct" in r:
                assert r["tensorcore_duty_cycle_pct"] == pytest.approx(97.25)
    finally:
        stop_daemon(daemon)
        server.stop(0)
        if server2:
            server2.stop(0)


def test_file_backend_corrupt_then_recover(bin_dir, tmp_path):
    """File-backend analog of the flap: a corrupt/truncated snapshot
    (non-atomic writer, dying exporter) mid-run must produce tpu_error
    rows for the last-known devices, then recover on the next good
    snapshot."""
    from daemon_utils import write_snapshot

    snap = tmp_path / "snap.json"
    write_snapshot(snap, 75.0)
    log_path = tmp_path / "metrics.jsonl"
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=file",
            f"--tpu_metrics_file={snap}",
            "--tpu_monitor_reporting_interval_s=1",
            f"--json_log_file={log_path}",
        ),
        kernel_interval_s=60,
    )
    try:
        deadline = time.time() + 15
        live = set()
        while time.time() < deadline and 0 not in live:
            _, rows = _rows_with(log_path)
            live = {r["device"] for r in rows if "tpu_duty_cycle_pct" in r}
            time.sleep(0.25)
        assert 0 in live, rows

        snap.write_text('{"devices": [{"device"')  # truncated mid-write
        time.sleep(1.5)
        mark, _ = _rows_with(log_path)
        deadline = time.time() + 15
        err = set()
        while time.time() < deadline and 0 not in err:
            _, rows = _rows_with(log_path, skip_lines=mark)
            err = {r["device"] for r in rows if r.get("tpu_error") == 1}
            time.sleep(0.25)
        assert 0 in err, rows
        assert [r for r in rows if "tpu_duty_cycle_pct" in r] == [], rows

        write_snapshot(snap, 42.0)
        mark, _ = _rows_with(log_path)
        deadline = time.time() + 15
        value = None
        while time.time() < deadline and value is None:
            _, rows = _rows_with(log_path, skip_lines=mark)
            for r in rows:
                if "tpu_duty_cycle_pct" in r:
                    value = r["tpu_duty_cycle_pct"]
            time.sleep(0.25)
        assert value == pytest.approx(42.0), rows
    finally:
        stop_daemon(daemon)


def test_file_backend_partial_device_disappearance(bin_dir, tmp_path):
    """A device missing from an otherwise-healthy snapshot (not a full
    outage) must surface as a tpu_error row, not silently vanish — a
    healthy exporter always lists the host's full fixed device set."""
    snap = tmp_path / "snap.json"

    def write(devs):
        body = json.dumps({"devices": [
            {"device": d, "chip_type": "tpu_v5e",
             "metrics": {"tpu_duty_cycle_pct": 50.0 + d}}
            for d in devs
        ]})
        tmp = tmp_path / "snap.json.tmp"
        tmp.write_text(body)
        tmp.rename(snap)

    write([0, 1])
    log_path = tmp_path / "metrics.jsonl"
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=file",
            f"--tpu_metrics_file={snap}",
            "--tpu_monitor_reporting_interval_s=1",
            f"--json_log_file={log_path}",
        ),
        kernel_interval_s=60,
    )
    try:
        deadline = time.time() + 15
        live = set()
        while time.time() < deadline and not {0, 1} <= live:
            _, rows = _rows_with(log_path)
            live = {r["device"] for r in rows if "tpu_duty_cycle_pct" in r}
            time.sleep(0.25)
        assert {0, 1} <= live, rows

        write([0])  # device 1 disappears; the file stays healthy
        time.sleep(1.5)
        mark, _ = _rows_with(log_path)
        deadline = time.time() + 15
        seen_err = seen_live = False
        while time.time() < deadline and not (seen_err and seen_live):
            _, rows = _rows_with(log_path, skip_lines=mark)
            seen_err = any(
                r.get("tpu_error") == 1 and r["device"] == 1 for r in rows)
            seen_live = any(
                "tpu_duty_cycle_pct" in r and r["device"] == 0 for r in rows)
            time.sleep(0.25)
        assert seen_err, f"missing device produced no tpu_error rows: {rows}"
        assert seen_live, rows
        # The vanished device never repeats its old value as fresh.
        assert not any(
            r["device"] == 1 and "tpu_duty_cycle_pct" in r for r in rows
        ), rows
    finally:
        stop_daemon(daemon)


def test_typoed_port_override_fails_closed(bin_dir, monkeypatch):
    """DYNO_TPU_GRPC_PORT="843l" must disable TPU queries outright, never
    probe port 843 (atoi-style leniency would silently monitor the wrong
    runtime — round-3 advisor finding; strict parse in src/common/Ports.h)."""
    # Two daemon starts: the env var is read inside the daemon process, so
    # each variant needs its own spawn ("8431,843l" also proves one bad
    # entry voids a whole list).
    for bad in ("843l", "8431,843l"):
        monkeypatch.setenv("DYNO_TPU_GRPC_PORT", bad)
        daemon = start_daemon(bin_dir, kernel_interval_s=60)
        try:
            out = run_dyno(bin_dir, daemon.port, "tpustatus")
            body = json.loads(out.stdout.split("response = ", 1)[1])
            assert body["status"] == "failed", (bad, body)
            assert "not a valid port list" in body["error"], (bad, body)
        finally:
            stop_daemon(daemon)


def test_valid_override_beats_malformed_runtime_list(bin_dir, grpc_server, monkeypatch):
    """A VALID DYNO_TPU_GRPC_PORT override must win even when the
    runtime-owned TPU_RUNTIME_METRICS_PORTS is junk — monitoring and
    tpustatus agree (junk in a var the operator explicitly overrode must
    not break the explicitly-configured query)."""
    monkeypatch.setenv("TPU_RUNTIME_METRICS_PORTS", "9000,oops")
    monkeypatch.setenv("DYNO_TPU_GRPC_PORT", str(grpc_server))
    daemon = start_daemon(bin_dir, kernel_interval_s=60)
    try:
        out = run_dyno(bin_dir, daemon.port, "tpustatus")
        body = json.loads(out.stdout.split("response = ", 1)[1])
        assert body["status"] == "ok", body
        assert body["port"] == grpc_server
    finally:
        stop_daemon(daemon)
