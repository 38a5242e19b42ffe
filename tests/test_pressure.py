"""Resource-pressure battery (PR 13): the self-protecting daemon's
invariants, drilled through the pure-Python mirror
(dynolog_tpu/supervise.py ResourceGovernor / SinkWal / DurableSink —
same semantics and snapshot keys as src/core/ResourceGovernor + the
WAL-backed RelayLogger, pinned on the C++ side by ResourceGovernorTest
and the errno-armed SinkWalTest/StateSnapshotTest additions):

- a full disk DEFERS durable telemetry: an ENOSPC'd WAL append leaves an
  intact tail (recovery finds every durable record), the interval parks
  in the bounded deferral queue (breaker-deferral, not drop), and
  everything drains with zero loss when space returns;
- an ENOSPC'd snapshot commit leaves the PREVIOUS snapshot
  authoritative and never publishes a torn file;
- an ENOSPC'd artifact stream renames nothing and cleans its tmp —
  a partial artifact can never be published;
- the governor evicts by priority (ring profiles and old trace
  artifacts before anything durable), never touches never-evict
  classes, refuses new admissions under hard pressure with a typed
  reason, and recovers automatically when the resource returns;
- fd/RSS watermarks shed the same way (injected probes).
"""

from __future__ import annotations

import errno
import json
import os
import pathlib
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dynolog_tpu import failpoints  # noqa: E402
from dynolog_tpu.supervise import (  # noqa: E402
    PRESSURE_HARD,
    PRESSURE_OK,
    PRESSURE_SOFT,
    AckedTcpSender,
    AckingRelay,
    ComponentHealth,
    DurableSink,
    FleetRelay,
    ResourceGovernor,
    SinkBreaker,
    SinkWal,
    atomic_artifact_write,
    dir_usage,
    reclaim_oldest_files,
)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.disarm_all()
    yield
    failpoints.disarm_all()


def _age(path, seconds):
    past = time.time() - seconds
    os.utime(path, (past, past))


# ---------------------------------------------------------------------------
# Full disk vs the WAL: defer, never corrupt, recover with zero loss
# ---------------------------------------------------------------------------


def test_enospc_mid_append_defers_without_corruption(tmp_path):
    wal = SinkWal(str(tmp_path / "wal"), fsync=False)
    assert wal.append(lambda s: f"rec-{s}") == 1
    assert wal.append(lambda s: f"rec-{s}") == 2
    failpoints.arm("wal.append.write", "errno:ENOSPC*2")
    assert wal.append(lambda s: f"rec-{s}") == 0
    assert wal.append(lambda s: f"rec-{s}") == 0
    assert wal.append_errors == 2
    # The full disk clears (count exhausted): the sequence space resumes
    # with no gap — the refused seqs were never issued.
    assert wal.append(lambda s: f"rec-{s}") == 3
    wal.close()
    # Recovery finds an intact tail: three durable records, zero corrupt.
    recovered = SinkWal(str(tmp_path / "wal"), fsync=False)
    stats = recovered.stats()
    assert stats["recovered_records"] == 3
    assert stats["corrupt_records"] == 0
    assert [seq for seq, _ in recovered.peek(10)] == [1, 2, 3]


def test_enospc_publish_defers_then_drains_gap_free(tmp_path):
    relay = AckingRelay()
    wal = SinkWal(str(tmp_path / "wal"), fsync=False)
    breaker = SinkBreaker("t", retry_initial_s=0.01, retry_max_s=0.02)
    sink = DurableSink(
        wal, AckedTcpSender("127.0.0.1", relay.port), breaker=breaker)
    try:
        assert sink.publish(lambda s: json.dumps({"wal_seq": s})) == 1
        # Each publish retries the append twice (publish-time flush +
        # the unconditional drain's flush), so a 6-fire episode keeps
        # the disk refusing across both publishes below.
        failpoints.arm("wal.append.write", "errno:ENOSPC*6")
        # Disk full: publishes DEFER (return 0) instead of dropping.
        deferred = [
            sink.publish(lambda s: json.dumps({"wal_seq": s}))
            for _ in range(2)
        ]
        assert deferred == [0, 0]
        assert len(sink.deferred) == 2
        # Deferral, not drop: the breaker extended its backoff but the
        # drop counters did NOT move.
        assert breaker.dropped == 0
        # Space returns: everything deferred appends and drains.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            sink.publish(lambda s: json.dumps({"wal_seq": s}))
            if not sink.deferred and wal.stats()["pending_records"] == 0:
                break
            time.sleep(0.02)
        assert not sink.deferred
        covered = relay.unique()
        # Zero loss, zero gaps: every sequence number the WAL ever
        # issued arrived exactly at the relay.
        assert covered == set(range(1, wal.last_seq + 1))
        assert breaker.dropped == 0
    finally:
        relay.sever()
        wal.close()


def test_deferral_queue_overflow_is_counted_loss(tmp_path):
    wal = SinkWal(str(tmp_path / "wal"), fsync=False)
    health = ComponentHealth("relay_sink")
    breaker = SinkBreaker(
        "t", health, retry_initial_s=0.001, retry_max_s=0.002)
    sink = DurableSink(wal, lambda batch: 0, breaker=breaker)
    sink.DEFER_LIMIT = 4
    failpoints.arm("wal.append.write", "errno:ENOSPC")  # unlimited
    for _ in range(10):
        assert sink.publish(lambda s: "x") == 0
    # Bounded: only DEFER_LIMIT intervals held; the overflow is REAL
    # loss and counted through the breaker's drop accounting.
    assert len(sink.deferred) == sink.DEFER_LIMIT
    assert sink.deferred_drops == 10 - sink.DEFER_LIMIT
    assert breaker.dropped == sink.deferred_drops
    assert health.snapshot()["drops"] == sink.deferred_drops
    wal.close()


def test_enospc_ack_persist_never_moves_the_watermark(tmp_path):
    wal = SinkWal(str(tmp_path / "wal"), fsync=False)
    assert wal.append(lambda s: "a") == 1
    assert wal.append(lambda s: "b") == 2
    failpoints.arm("wal.ack.persist", "errno:ENOSPC*1")
    assert wal.ack(2) is False
    assert wal.acked_seq == 0
    assert len(wal.peek(10)) == 2  # nothing trimmed
    # Space returns: the re-ack succeeds and trims.
    assert wal.ack(2) is True
    assert wal.acked_seq == 2
    assert wal.stats()["pending_records"] == 0
    wal.close()


def test_eio_seal_rename_seals_in_place(tmp_path):
    wal = SinkWal(str(tmp_path / "wal"), segment_bytes=8, fsync=False)
    failpoints.arm("wal.seal.rename", "errno:EIO*1")
    assert wal.append(lambda s: "payload-a") == 1  # seal refused: in place
    assert wal.append(lambda s: "payload-b") == 2  # fresh segment
    assert [seq for seq, _ in wal.peek(10)] == [1, 2]
    assert wal.ack(2) is True
    assert wal.stats()["pending_records"] == 0
    wal.close()


# ---------------------------------------------------------------------------
# Full disk vs the snapshot commit
# ---------------------------------------------------------------------------


def test_enospc_snapshot_commit_keeps_previous_authoritative(tmp_path):
    snap_path = str(tmp_path / "state.json")
    relay = FleetRelay(snapshot_path=snap_path, snapshot_interval_s=3600)
    try:
        relay.view.ingest_line(json.dumps(
            {"host": "h1", "boot_epoch": 7, "wal_seq": 1, "m": 1.0}))
        assert relay.write_snapshot() is True
        before = open(snap_path).read()
        relay.view.ingest_line(json.dumps(
            {"host": "h1", "boot_epoch": 7, "wal_seq": 2, "m": 2.0}))
        failpoints.arm("state.snapshot.write", "errno:ENOSPC*1")
        assert relay.write_snapshot() is False
        # The previous snapshot is byte-identical and parses; no tmp
        # debris; the refused commit promoted NO watermarks (an ack the
        # relay sends may never exceed persisted state).
        assert open(snap_path).read() == before
        assert not os.path.exists(snap_path + ".tmp")
        assert relay.view.ackable("h1") == 1
        # Space returns: the next commit supersedes and promotes.
        assert relay.write_snapshot() is True
        assert relay.view.ackable("h1") == 2
        doc = json.loads(open(snap_path).read())
        assert doc["fleet"]["hosts"]["h1"]["applied_seq"] == 2
    finally:
        relay.sever()


# ---------------------------------------------------------------------------
# Full disk vs the artifact stream
# ---------------------------------------------------------------------------


def test_enospc_artifact_stream_renames_nothing_cleans_tmp(tmp_path):
    out = str(tmp_path / "capture.xplane.pb")
    failpoints.arm("trace.artifact.write", "errno:ENOSPC*1")
    assert atomic_artifact_write(out, b"xspace-bytes") is False
    # The abort contract: nothing renamed, tmp cleaned — a partial
    # artifact can never be published.
    assert not os.path.exists(out)
    assert not os.path.exists(out + ".tmp")
    # Space returns: the retried capture publishes atomically.
    assert atomic_artifact_write(out, b"xspace-bytes") is True
    assert open(out, "rb").read() == b"xspace-bytes"


def test_enospc_diagnosis_report_cleans_tmp(tmp_path):
    # The diagnosis engine's report write follows the same contract:
    # refused -> tmp cleaned, error raised into the caller's
    # containment, nothing published.
    from dynolog_tpu.supervise import run_diagnosis_engine

    target = tmp_path / "cur.json"
    baseline = tmp_path / "base.json"
    envelope = {
        "schema": 1,
        "summary": {
            "planes": [{"name": "/device:TPU:0", "lines": 1, "events": 1,
                        "duration_ms": 1.0}],
            "top_ops": [{"op": "fusion.1", "total_ms": 1.0, "count": 2,
                         "pct": 100.0}],
        },
    }
    target.write_text(json.dumps(envelope))
    baseline.write_text(json.dumps(envelope))
    failpoints.arm("diagnose.report.write", "errno:ENOSPC*1")
    with pytest.raises(OSError):
        run_diagnosis_engine(str(target), str(baseline))
    report_path = str(tmp_path / "cur.fleet_diagnosis.json")
    assert not os.path.exists(report_path)
    assert not os.path.exists(report_path + ".tmp")
    # Space returns: the report publishes.
    report = run_diagnosis_engine(str(target), str(baseline))
    assert os.path.exists(report["report_path"])


# ---------------------------------------------------------------------------
# Governor: eviction order, never-evict, admission, watermarks
# ---------------------------------------------------------------------------


def test_eviction_order_and_never_evict_classes(tmp_path):
    ring = tmp_path / "ring"
    art = tmp_path / "artifacts"
    walroot = tmp_path / "wal"
    for d in (ring, art, walroot):
        d.mkdir()
    for i in range(4):
        for d in (ring, art, walroot):
            p = d / f"f{i}"
            p.write_bytes(b"x" * 1000)
            _age(p, 3600)
    gov = ResourceGovernor(disk_budget_bytes=9000)
    gov.register("ring_profiles", priority=0, root=str(ring), grace_s=0)
    gov.register("trace_artifacts", priority=10, root=str(art), grace_s=0)
    gov.register("wal_spill", priority=100, never_evict=True,
                 root=str(walroot))
    gov.tick()
    snap = gov.snapshot()
    # 12000 over a 9000 budget: ring profiles reclaimed FIRST; the WAL
    # class is untouched regardless of how far over budget we were.
    assert snap["classes"]["ring_profiles"]["reclaimed_bytes"] > 0
    assert snap["classes"]["wal_spill"]["reclaimed_bytes"] == 0
    assert dir_usage(str(walroot)) == (4000, 4)
    # The reclaim took us back under budget.
    assert snap["disk"]["usage_bytes"] <= 9000


def test_reclaim_grace_protects_families_mid_write(tmp_path):
    root = tmp_path / "art"
    root.mkdir()
    old = root / "old"
    young = root / "young"
    old.write_bytes(b"x" * 100)
    _age(old, 3600)
    young.write_bytes(b"y" * 100)
    freed = reclaim_oldest_files(str(root), 1000, grace_s=60)
    assert freed == 100
    assert not old.exists()
    assert young.exists()  # mid-write family survives


def test_hard_pressure_refuses_and_recovers():
    hist = []
    health = ComponentHealth("resources")
    gov = ResourceGovernor(disk_budget_bytes=1000, health=health)
    usage = {"bytes": 2000}
    gov.register("wal_spill", priority=0, never_evict=True,
                 usage=lambda: (usage["bytes"], 1))
    assert gov.tick() == PRESSURE_HARD
    ok, reason = gov.admit("pushtrace capture")
    assert not ok
    assert "refused" in reason and "pushtrace" in reason
    assert health.state == "degraded"
    hist.append(gov.snapshot())
    assert hist[0]["refusals"] == 1
    # Space returns (acks trimmed the WAL): automatic recovery.
    usage["bytes"] = 100
    assert gov.tick() == PRESSURE_OK
    assert health.state == "up"
    assert gov.admit("pushtrace capture")[0]


def test_write_failure_escalates_within_one_tick():
    health = ComponentHealth("resources")
    gov = ResourceGovernor(health=health)
    gov.note_write_failure("wal.append.write", errno.ENOSPC)
    # Loud NOW: hard pressure + degraded health at the failure site,
    # before any tick ran.
    assert gov.pressure == PRESSURE_HARD
    assert not gov.admit("capture")[0]
    assert health.state == "degraded"
    assert "No space left" in gov.snapshot()["last_error"]
    # The tick that observed it stays hard; the next clean tick recovers.
    assert gov.tick() == PRESSURE_HARD
    assert gov.tick() == PRESSURE_OK
    assert health.state == "up"


def test_fd_and_rss_watermarks_shed(tmp_path):
    probes = {"fds": 10, "rss": 50}
    gov = ResourceGovernor(
        max_fds=100, rss_soft_mb=100,
        fd_probe=lambda: probes["fds"], rss_probe=lambda: probes["rss"])
    assert gov.tick() == PRESSURE_OK
    probes["fds"] = 85
    assert gov.tick() == PRESSURE_SOFT
    assert gov.admit("capture")[0]  # soft admits
    probes["fds"] = 96
    assert gov.tick() == PRESSURE_HARD
    assert not gov.admit("capture")[0]  # hard refuses (the fd shed)
    probes["fds"] = 10
    probes["rss"] = 120
    assert gov.tick() == PRESSURE_SOFT
    probes["rss"] = 160  # past 1.5x soft
    assert gov.tick() == PRESSURE_HARD
    probes["rss"] = 50
    assert gov.tick() == PRESSURE_OK
    assert gov.admit("capture")[0]


def test_statvfs_floor_goes_hard_and_recovers(tmp_path):
    class FakeVfs:
        f_blocks = 1000
        f_bavail = 1000

    vfs = FakeVfs()
    gov = ResourceGovernor(disk_min_free_pct=5.0,
                           statvfs=lambda root: vfs)
    gov.register("artifacts", priority=0, root=str(tmp_path),
                 usage=lambda: (0, 0))
    assert gov.tick() == PRESSURE_OK
    vfs.f_bavail = 80  # 8% free: nearing the 5% floor
    assert gov.tick() == PRESSURE_SOFT
    vfs.f_bavail = 20  # 2% free: below the floor
    assert gov.tick() == PRESSURE_HARD
    assert not gov.admit("capture")[0]
    vfs.f_bavail = 900
    assert gov.tick() == PRESSURE_OK


def test_reclaim_failure_escalates_to_health():
    health = ComponentHealth("resources")
    gov = ResourceGovernor(health=health)
    gov.note_reclaim_failure("autotrigger.prune", "/tmp/t_trig1_1.json")
    snap = gov.snapshot()
    assert snap["reclaim_failures"] == 1
    assert "autotrigger.prune" in snap["last_error"]
    assert "autotrigger.prune" in health.snapshot()["last_error"]


def test_snapshot_schema_matches_cpp_keys():
    # The schema pin: these exact keys are what the C++ governor's
    # `resources` health-verb section serves (ResourceGovernorTest binds
    # the other side) — the cross-language contract of this PR.
    gov = ResourceGovernor(disk_budget_bytes=10)
    gov.register("c", priority=1, usage=lambda: (5, 1))
    gov.tick()
    snap = gov.snapshot()
    assert {"pressure", "disk", "fds", "rss_mb", "rss_soft_mb", "classes",
            "refusals", "write_failures", "reclaim_failures",
            "ticks"} <= set(snap)
    assert {"budget_bytes", "usage_bytes", "min_free_pct",
            "roots"} <= set(snap["disk"])
    assert {"priority", "never_evict", "usage_bytes", "files", "reclaims",
            "reclaimed_bytes"} <= set(snap["classes"]["c"])


def test_shim_manifest_write_refusal_cleans_tmp_and_reports(tmp_path):
    # The shim half of "shim and daemon both report the refusal": an
    # ENOSPC'd manifest write aborts cleanly — tmp unlinked, nothing
    # renamed, the refusal in last_error, traces_completed NOT bumped —
    # and the retried capture publishes normally.
    from dynolog_tpu import obs
    from dynolog_tpu.client.shim import TraceClient, TraceConfig, _Capture

    client = TraceClient(job_id=7)
    client.last_error = ""
    client._client.close()
    client._client = object()  # no send_spans capability: flush skipped
    cfg = TraceConfig(log_file=str(tmp_path / "cap.json"))

    def capture():
        return _Capture(cfg=cfg, pid=1234, trace_dir=str(tmp_path / "cap_1234"),
                        ctx=obs.TraceContext.mint(), started_ms=1)

    failpoints.arm("trace.artifact.write", "errno:ENOSPC*1")
    client._finish_trace(capture())
    manifest = tmp_path / "cap_1234.json"
    assert not manifest.exists()
    assert not pathlib.Path(str(manifest) + ".tmp").exists()
    assert "refused" in client.last_error
    assert client.traces_completed == 0
    # Space returns: the next capture's manifest publishes atomically.
    client._finish_trace(capture())
    assert manifest.exists()
    assert client.traces_completed == 1
    assert json.loads(manifest.read_text())["status"] == "ok"
