"""What four devices add to the tracing: the manifest's `planes` and
`local_devices` with the span `shim.plane_index`, the per-plane collective
share of `dynolog_tpu.trace`, and `tpu_rows` in the `selftrace` reply
(docs/OBSERVABILITY.md). CPU only: the XSpace is synthetic, one host plane
and four `/device:TPU:<i>` planes, handed to the shim by RecordingProfiler
as JaxProfiler hands the runtime's."""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tests"))

import xspace_fixture as xf  # noqa: E402
from daemon_utils import run_dyno, start_daemon, stop_daemon  # noqa: E402
from dynolog_tpu import diagnose, obs, trace, xspace  # noqa: E402
from dynolog_tpu.client.shim import (  # noqa: E402
    RecordingProfiler, TraceClient, TraceConfig)

US = 1_000_000  # picoseconds in a microsecond

# (op, microseconds an event) on each device plane's "XLA Ops" line, twice
# over; plane 3 holds no collective.
DEVICE_OPS = (
    ("%fusion.1 = bf16[8,8]{1,0} fusion(%p0)", 60),
    ("%all-reduce.2 = bf16[8,8]{1,0} all-reduce(%p1)", 25),
    ("%all-gather.3 = bf16[16,8]{1,0} all-gather(%p2)", 15),
)


def plane(name: str, ops, extra_events: int = 0) -> bytes:
    body = xf._field_varint(1, 7) + xf._field_str(2, name)
    events, offset = [], 0
    for _ in range(2 + extra_events):
        for meta_id, (_, us) in enumerate(ops, start=1):
            events.append(xf._event(meta_id, offset, us * US))
            offset += us * US
    body += xf._field_bytes(3, xf._line(1, "XLA Ops", 1000, events))
    for meta_id, (op, _) in enumerate(ops, start=1):
        body += xf._field_bytes(4, xf._event_metadata(meta_id, op, ""))
    return body


def four_chip_xspace() -> tuple[bytes, list[bytes]]:
    planes = [plane("/host:CPU", (("step", 5),))]
    for i in range(4):
        ops = DEVICE_OPS if i < 3 else DEVICE_OPS[:1]
        # uneven on purpose: plane i carries i more rounds of events
        planes.append(plane(f"/device:TPU:{i}", ops, extra_events=i))
    return b"".join(xf._field_bytes(1, p) for p in planes), planes


# -------------------------------------------------- xspace.py, trace.py


def test_plane_index_names_every_plane_and_counts_its_payload():
    data, planes = four_chip_xspace()
    index = xspace.plane_index(data)
    assert [row["name"] for row in index] == [
        "/host:CPU", "/device:TPU:0", "/device:TPU:1", "/device:TPU:2",
        "/device:TPU:3"]
    assert [row["bytes"] for row in index] == [len(p) for p in planes]
    framing = sum(1 + len(xf._varint(len(p))) for p in planes)
    assert sum(row["bytes"] for row in index) == len(data) - framing
    # the walker's own per-plane buffers agree
    assert [len(b) for b in xspace.iter_plane_bufs(data)] == [
        row["bytes"] for row in index]


def test_plane_index_reads_a_memoryview_and_skips_other_top_level_fields():
    data, planes = four_chip_xspace()
    # XSpace{errors=2, warnings=3, hostnames=4}: strings beside the planes
    data = xf._field_str(4, "host-a") + data + xf._field_str(3, "a warning")
    assert [r["bytes"] for r in xspace.plane_index(memoryview(data))] == [
        len(p) for p in planes]
    assert xspace.plane_index(b"") == []


@pytest.mark.parametrize("cut", [1, 3, 40])
def test_plane_index_refuses_a_truncated_xspace(cut):
    data, _ = four_chip_xspace()
    with pytest.raises(ValueError):
        xspace.plane_index(data[:-cut])


def test_collective_share_is_per_plane_and_hand_computable():
    data, _ = four_chip_xspace()
    summary = trace._summarize_planes(trace.summarize_xplane_bytes(data))
    by_name = {p["name"]: p for p in summary["planes"]}
    # 25 + 15 of every 100 microseconds, whatever the number of rounds
    for i in range(3):
        assert by_name[f"/device:TPU:{i}"]["collective_pct"] == 40.0
    assert by_name["/device:TPU:3"]["collective_pct"] == 0.0
    assert by_name["/host:CPU"]["collective_pct"] == 0.0


def test_collective_share_is_in_json_and_in_the_printed_table(tmp_path, capsys):
    data, _ = four_chip_xspace()
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(data)
    assert trace.main([str(path), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert [p["collective_pct"] for p in doc["planes"]] == [
        0.0, 40.0, 40.0, 40.0, 0.0]
    assert trace.main([str(path), "--plane", "TPU:1"]) == 0
    table = capsys.readouterr().out.splitlines()
    assert table[0].split()[-2:] == ["coll", "%"]
    assert table[1].split()[0] == "/device:TPU:1"
    assert table[1].split()[-1] == "40.00"


def environment_plane() -> bytes:
    """A plane of stats and stat metadata and no line, as the session's
    `Task Environment` is; a fixed64 double among the stats."""
    stat_meta = xf._field_varint(1, 1) + xf._field_bytes(
        2, xf._field_varint(1, 1) + xf._field_str(2, "profile_start_time"))
    stat = xf._field_varint(1, 1) + xf._field_varint(3, 1_790_000_000 * 10**9)
    double = xf._field_varint(1, 2) + b"\x11" + xspace.FLOAT64.pack(0.5)
    return (xf._field_varint(1, 9) + xf._field_str(2, "Task Environment")
            + xf._field_bytes(5, stat_meta) + xf._field_bytes(6, stat)
            + xf._field_bytes(6, double))


CONTENT = ("lines", "event_metadata", "stat_metadata", "stats", "other")


@pytest.mark.parametrize("build", [
    xf.build_xspace,
    lambda: four_chip_xspace()[0] + xf._field_bytes(1, environment_plane()),
], ids=["fixture", "four_chip_and_environment"])
def test_what_a_planes_bytes_are_made_of_adds_up(build):
    data = build()
    summary = trace._summarize_planes(trace.summarize_xplane_bytes(data))
    index = xspace.plane_index(data)
    assert [(p["name"], p["bytes"]) for p in summary["planes"]] == [
        (row["name"], row["bytes"]) for row in index]
    for p in summary["planes"]:
        assert sum(p[f"{part}_bytes"] for part in CONTENT) == p["bytes"], p
        assert p["lines_bytes"] > 0 or p["lines"] == 0


def test_the_content_columns_are_hand_computable(tmp_path, capsys):
    data, planes = four_chip_xspace()
    data += xf._field_bytes(1, environment_plane())
    summary = trace._summarize_planes(trace.summarize_xplane_bytes(data))
    by_name = {p["name"]: p for p in summary["planes"]}
    device = by_name["/device:TPU:0"]
    # id and name; three metadata entries, each under its tag and length
    assert device["other_bytes"] == 2 + 2 + len("/device:TPU:0")
    assert device["event_metadata"] == len(DEVICE_OPS)
    assert device["event_metadata_bytes"] == sum(
        2 + len(xf._event_metadata(i, op, ""))
        for i, (op, _) in enumerate(DEVICE_OPS, start=1))
    assert device["lines_bytes"] == len(planes[1]) - (
        device["other_bytes"] + device["event_metadata_bytes"])
    assert device["stat_metadata_bytes"] == device["stats_bytes"] == 0
    environment = by_name["Task Environment"]
    assert (environment["lines"], environment["lines_bytes"]) == (0, 0)
    # a uint64 stat (2 + 1 + a 9-byte varint) and a double (2 + 1 + 8),
    # each under a tag and a length; one stat-metadata entry
    assert environment["stats_bytes"] == (2 + 12) + (2 + 11)
    assert environment["stat_metadata_bytes"] == 2 + 2 + 2 + (
        2 + 2 + len("profile_start_time"))
    # the printed summary: a second table, and the share outside `lines`
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(data)
    assert trace.main([str(path)]) == 0
    out = capsys.readouterr().out
    header = next(line for line in out.splitlines() if "meta rows" in line)
    assert header.split()[:3] == ["plane", "bytes", "lines"]
    total = sum(p["bytes"] for p in summary["planes"])
    in_lines = sum(p["lines_bytes"] for p in summary["planes"])
    assert (f"not in lines (metadata and stats): "
            f"{100.0 * (total - in_lines) / total:.1f} % of {total} bytes"
            ) in out


def test_diagnose_classes_collectives_by_the_same_tokens():
    for op, _ in DEVICE_OPS[1:]:
        assert trace.is_collective(op)
        assert diagnose.classify_op(op) == "collective"
    assert not trace.is_collective(DEVICE_OPS[0][0])
    assert diagnose.classify_op(DEVICE_OPS[0][0]) == "fusion"
    assert diagnose.classify_op("collective-permute.4") == "collective"


# -------------------------------------------------------------- shim.py


class SpanSink:
    def __init__(self):
        self.sent: list = []

    def send_spans(self, spans, dest=None) -> None:
        self.sent += list(spans)

    def close(self) -> None:
        pass


def capture(tmp_path, profiler) -> tuple[dict, SpanSink]:
    obs.JOURNAL.drain()
    client = TraceClient(job_id=28, endpoint="dynotpu_planes_none",
                         profiler=profiler)
    client._client.close()
    client._client = sink = SpanSink()
    cfg = TraceConfig.parse(
        f"ACTIVITIES_LOG_FILE={tmp_path}/cap.json\n"
        "ACTIVITIES_DURATION_MSECS=5")
    try:
        client._run_trace(cfg)
        path = pathlib.Path(cfg.manifest_path(os.getpid()))
        deadline = time.time() + 10
        while time.time() < deadline and not path.exists():
            time.sleep(0.005)
        manifest = json.loads(path.read_text())
    finally:
        client.stop()
    return manifest, sink


def test_manifest_lists_five_planes_whose_bytes_add_up(tmp_path):
    data, planes = four_chip_xspace()
    manifest, sink = capture(
        tmp_path, RecordingProfiler(xspace=data, local_devices=4))
    assert manifest["status"] == "ok"
    assert manifest["local_devices"] == 4
    assert [row["name"] for row in manifest["planes"]] == [
        "/host:CPU", "/device:TPU:0", "/device:TPU:1", "/device:TPU:2",
        "/device:TPU:3"]
    assert all(set(row) == {"name", "bytes"} for row in manifest["planes"])
    framing = sum(1 + len(xf._varint(len(p))) for p in planes)
    timing = manifest["timing"]
    assert timing["xspace_bytes"] == timing["write_bytes"] == len(data)
    assert sum(row["bytes"] for row in manifest["planes"]) == (
        timing["xspace_bytes"] - framing)
    # the artifact on disk is those bytes
    (artifact,) = pathlib.Path(manifest["trace_dir"]).glob(
        "plugins/profile/*/*.xplane.pb")
    assert artifact.read_bytes() == data


def test_plane_index_is_a_span_of_the_capture_after_the_write(tmp_path):
    data, _ = four_chip_xspace()
    manifest, sink = capture(
        tmp_path, RecordingProfiler(xspace=data, local_devices=4))
    rows = {row["name"]: row for row in manifest["spans"]}
    assert {"shim.xplane_write", "shim.plane_index"} <= set(rows)
    write, index = rows["shim.xplane_write"], rows["shim.plane_index"]
    assert index["start_us"] >= write["start_us"] + write["dur_us"]
    assert index["parent_id"] == write["parent_id"]  # the request's
    assert index["dur_us"] < 50_000
    # and it is flushed to the daemon with the capture's other spans
    assert "shim.plane_index" in {s.name for s in sink.sent}


def test_capture_without_an_xspace_reports_neither_key(tmp_path):
    manifest, _ = capture(tmp_path, RecordingProfiler())
    assert manifest["status"] == "ok"
    assert "planes" not in manifest and "local_devices" not in manifest
    assert "shim.plane_index" not in {r["name"] for r in manifest["spans"]}


def test_an_xspace_that_does_not_parse_costs_only_the_rows(tmp_path):
    data, _ = four_chip_xspace()
    manifest, _ = capture(
        tmp_path, RecordingProfiler(xspace=data[:-3], local_devices=4))
    assert manifest["status"] == "ok" and "planes" not in manifest
    assert manifest["timing"]["write_bytes"] == len(data) - 3
    assert "shim.plane_index" in {r["name"] for r in manifest["spans"]}


# ------------------------------------------------------------ selftrace


@pytest.mark.parametrize("devices", [4, 1])
def test_selftrace_carries_the_tpu_rows_of_the_last_tick(bin_dir, devices):
    daemon = start_daemon(bin_dir, extra_flags=(
        "--enable_tpu_monitor", "--tpu_metric_backend=fake",
        f"--tpu_fake_devices={devices}",
        "--tpu_monitor_reporting_interval_s=1"))
    try:
        deadline = time.time() + 15
        rows = None
        while time.time() < deadline:
            doc = daemon.rpc({"fn": "selftrace"})
            assert doc["status"] == "ok"
            rows = doc["tpu_rows"]
            if rows:
                break
            time.sleep(0.2)
        assert rows == devices
        assert set(doc["ipc_wakeups"]) == {"message", "posted", "timeout"}
        cli = run_dyno(bin_dir, daemon.port, "selftrace")
        assert cli.returncode == 0, cli.stderr
        start = cli.stdout.index("{")
        assert json.loads(cli.stdout[start:])["otherData"]["tpu_rows"] == devices
    finally:
        stop_daemon(daemon)


def test_selftrace_reads_zero_rows_without_a_tpu_monitor(bin_dir):
    daemon = start_daemon(bin_dir)
    try:
        assert daemon.rpc({"fn": "selftrace"})["tpu_rows"] == 0
    finally:
        stop_daemon(daemon)
