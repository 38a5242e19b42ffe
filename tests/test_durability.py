"""Durable telemetry acceptance drills (PR 9).

Three layers, mirroring docs/RELIABILITY.md's durability model:

1. Pure-Python WAL torture — the supervise.py SinkWal mirror (the SAME
   on-disk format as src/core/SinkWal; cross-language pinned by the
   daemon-gated test below) through the crash artifacts: torn tail,
   corrupt CRC mid-segment, partial-rename debris, replay-after-eviction,
   double-recovery/ack idempotence.
2. A fake-daemon shim drill: the TraceClient poll loop rides through a
   daemon restart — backoff while absent, pid re-announce + kick
   re-subscribe on the first reply after the absence.
3. Daemon-gated (needs the built tree): relay outage -> spill -> replay
   with gap-free sequence coverage at the receiving sink; SIGKILL+restart
   with state-snapshot recovery (rules, breaker states, WAL backlog);
   corrupt snapshots failing closed; a capture straddling the restart
   still yielding a complete manifest.
"""

from __future__ import annotations

import json
import os
import pathlib
import signal
import socket
import threading
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
import sys

sys.path.insert(0, str(REPO))

from daemon_utils import start_daemon, stop_daemon  # noqa: E402
from dynolog_tpu.client import ipc  # noqa: E402
from dynolog_tpu.client.shim import RecordingProfiler, TraceClient  # noqa: E402
from dynolog_tpu.supervise import (  # noqa: E402
    AckingRelay, DurableSink, FleetView, SinkBreaker, SinkWal)

# ---------------------------------------------------------------------------
# 1. WAL torture (pure Python mirror; same format as the C++ SinkWal)
# ---------------------------------------------------------------------------


def test_wal_append_recover_replay(tmp_path):
    d = str(tmp_path / "wal")
    w = SinkWal(d)
    for i in range(5):
        assert w.append(lambda s: f"rec-{s}") == i + 1
    assert w.ack(2)
    w.close()  # "crash": nothing is trimmed by close

    r = SinkWal(d)
    assert r.acked_seq == 2
    assert [s for s, _ in r.peek()] == [3, 4, 5]
    assert r.append(lambda s: f"rec-{s}") == 6  # seq space continues


def test_wal_torn_tail_truncated(tmp_path):
    d = str(tmp_path / "wal")
    w = SinkWal(d)
    w.append(lambda s: "intact-1")
    w.append(lambda s: "intact-2")
    w.close()
    seg = next(p for p in os.listdir(d) if p.startswith("wal-"))
    with open(os.path.join(d, seg), "ab") as f:
        f.write(b"\x64" + b"\x00" * 15)  # header promising 100 absent bytes

    r = SinkWal(d)
    assert [p.decode() for _, p in r.peek()] == ["intact-1", "intact-2"]
    assert r.corrupt_records == 0  # a torn tail is an EXPECTED artifact


def test_wal_corrupt_crc_drops_rest_of_segment(tmp_path):
    d = str(tmp_path / "wal")
    w = SinkWal(d)
    w.append(lambda s: "good-1")
    w.append(lambda s: "bitrot")
    w.close()
    seg = os.path.join(d, next(p for p in os.listdir(d)
                               if p.startswith("wal-")))
    with open(seg, "r+b") as f:
        # Record 1 frame = 16 + 6; flip a payload byte of record 2.
        f.seek(22 + 16 + 2)
        c = f.read(1)
        f.seek(22 + 16 + 2)
        f.write(bytes([c[0] ^ 0x40]))

    r = SinkWal(d)
    assert [p.decode() for _, p in r.peek()] == ["good-1"]
    assert r.corrupt_records > 0


def test_wal_partial_rename_debris_removed(tmp_path):
    d = str(tmp_path / "wal")
    w = SinkWal(d)
    w.append(lambda s: "keep")
    w.close()
    # Crash between tmp write and rename: the bogus watermark must be
    # ignored AND the debris removed.
    with open(os.path.join(d, "ack.tmp"), "w") as f:
        f.write("999")
    r = SinkWal(d)
    assert [p.decode() for _, p in r.peek()] == ["keep"]
    assert not any(n.endswith(".tmp") for n in os.listdir(d))


def test_wal_replay_after_eviction_counts_loss(tmp_path):
    d = str(tmp_path / "wal")
    w = SinkWal(d, max_bytes=220, segment_bytes=64)
    for i in range(6):
        w.append(lambda s: f"payload-{s}" + "x" * 48)
    stats = w.stats()
    assert stats["evicted_records"] > 0
    seqs = [s for s, _ in w.peek()]
    assert seqs[-1] == 6
    assert seqs[0] > stats["evicted_records"]  # oldest survivors replay
    assert stats["evicted_records"] + stats["pending_records"] == 6


def test_wal_double_recovery_never_redelivers_acked(tmp_path):
    d = str(tmp_path / "wal")
    w = SinkWal(d)
    for i in range(4):
        w.append(lambda s: f"r{s}")
    assert w.ack(4)
    w.close()
    r1 = SinkWal(d)
    assert r1.peek() == []
    r1.append(lambda s: f"r{s}")
    r1.close()
    r2 = SinkWal(d)  # second recovery, crash right after the new append
    assert [s for s, _ in r2.peek()] == [5]


def test_wal_mixed_version_spill_dir_replays_gap_free(tmp_path):
    """Upgrade-mid-stream (PR 15): a spill dir holding v0 (the previous
    release's) records next to v1 records replays seamlessly from one
    recovery — the rolling-upgrade contract for the durable transport."""
    d = str(tmp_path / "wal")
    old = SinkWal(d, compat_level=0)  # impersonates the old binary
    for i in range(3):
        assert old.append(lambda s: f"old-{s}") == i + 1
    old.close()  # SIGKILL: nothing flushed beyond the fsync'd appends

    new = SinkWal(d)  # the upgraded binary on the SAME spill dir
    assert new.recovered_records == 3
    for i in range(3):
        assert new.append(lambda s: f"new-{s}") == i + 4
    got = new.peek(16)
    assert [s for s, _ in got] == [1, 2, 3, 4, 5, 6]
    assert [p.decode() for _, p in got] == [
        "old-1", "old-2", "old-3", "new-4", "new-5", "new-6"]
    assert new.corrupt_records == 0
    # The ack protocol is version-blind: one watermark trims both kinds.
    assert new.ack(6)
    assert new.peek(16) == []


def test_wal_torn_v1_tail_then_intact_v0_records_recover(tmp_path):
    """Crash mid-append on the new binary, with intact v0 records in a
    later segment: the torn v1 tail truncates to its last intact record
    and the v0 records keep replaying (satellite: mixed-version WAL
    recovery)."""
    import zlib

    from dynolog_tpu.supervise import WAL_HEADER, WAL_SEQ

    d = str(tmp_path / "wal")
    w = SinkWal(d, segment_bytes=1 << 20)
    assert w.append(lambda s: "v1-intact") == 1
    assert w.append(lambda s: "v1-torn") == 2
    w.close()
    # Tear the active (v1) segment mid-record.
    open_seg = [n for n in os.listdir(d) if n.endswith(".open")]
    assert open_seg
    seg = os.path.join(d, open_seg[0])
    with open(seg, "r+b") as f:
        f.truncate(os.path.getsize(seg) - 3)
    # An intact v0 segment behind the tear (the old binary's leftovers
    # sealed under a higher firstSeq).
    frames = b""
    for seq, payload in ((3, b"v0-after"), (4, b"v0-last")):
        frames += WAL_HEADER.pack(
            len(payload), zlib.crc32(WAL_SEQ.pack(seq) + payload),
            seq) + payload
    with open(os.path.join(d, "wal-%020d.seg" % 3), "wb") as f:
        f.write(frames)

    r = SinkWal(d)
    got = r.peek(16)
    assert [s for s, _ in got] == [1, 3, 4]
    assert got[0][1] == b"v1-intact"
    assert got[2][1] == b"v0-last"


def test_durable_sink_outage_defers_then_drains(tmp_path):
    delivered: list[int] = []
    relay_up = [False]

    def send(batch):
        if not relay_up[0]:
            return 0
        delivered.extend(s for s, _ in batch)
        return batch[-1][0]

    sink = DurableSink(
        SinkWal(str(tmp_path / "wal")), send,
        breaker=SinkBreaker("t", retry_initial_s=0.01, retry_max_s=0.02))
    for _ in range(3):
        sink.publish(lambda s: json.dumps({"wal_seq": s}))
    assert delivered == []
    assert sink.breaker.dropped == 0  # deferred, not dropped
    assert sink.wal.stats()["pending_records"] == 3

    relay_up[0] = True
    time.sleep(0.03)  # backoff window expires
    sink.publish(lambda s: json.dumps({"wal_seq": s}))
    assert delivered == [1, 2, 3, 4]  # in order, gap-free
    assert sink.wal.stats()["pending_records"] == 0


def test_lost_ack_is_at_least_once_and_fleet_dedup_makes_it_once(tmp_path):
    """The duplicate-delivery hole, pinned end to end: a burst whose ACK
    dies in flight (connection lost between the relay's receipt and the
    ack reaching the sender) is re-delivered on the next drain — the
    transport is at-least-once BY DESIGN. The fleet relay's
    (host, epoch, wal_seq) dedup is what turns that into
    effectively-once: the duplicate is suppressed AND counted."""
    relay = AckingRelay(drop_acks=1)
    state: dict = {}

    def send(batch):
        try:
            if state.get("sock") is None:
                state["sock"] = socket.create_connection(
                    ("127.0.0.1", relay.port), timeout=0.5)
                state["sock"].settimeout(0.5)
            state["sock"].sendall(b"".join(p + b"\n" for _, p in batch))
            want = batch[-1][0]
            acked, buf = 0, b""
            while acked < want:
                chunk = state["sock"].recv(256)
                if not chunk:
                    break
                buf += chunk
                for line in buf.split(b"\n")[:-1]:
                    if line.startswith(b"ACK "):
                        acked = max(acked, int(line[4:]))
                buf = buf.rsplit(b"\n", 1)[-1]
            return acked
        except OSError:
            if state.get("sock") is not None:
                state["sock"].close()
                state["sock"] = None
            return 0

    try:
        wal = SinkWal(str(tmp_path / "wal"))
        sink = DurableSink(
            wal, send,
            breaker=SinkBreaker("t", retry_initial_s=0.01,
                                retry_max_s=0.02))
        epoch = wal.epoch

        def build(seq):
            return json.dumps(
                {"host": "hA", "boot_epoch": epoch, "wal_seq": seq})

        sink.publish(build)  # delivered; ACK lost; conn dies
        # Unconfirmed is NOT delivered: the record stays spilled (and is
        # deferred, never counted as a drop).
        assert wal.stats()["pending_records"] == 1
        assert sink.breaker.dropped == 0
        time.sleep(0.03)  # backoff window
        sink.publish(build)  # re-delivers seq 1 alongside seq 2
        deadline = time.monotonic() + 10
        while wal.stats()["pending_records"] > 0 and \
                time.monotonic() < deadline:
            sink.drain()
            time.sleep(0.02)
        assert wal.stats()["pending_records"] == 0
        with relay.lock:
            seen = list(relay.seen)
        assert seen.count(1) == 2  # at-least-once, pinned
        assert max(seen) == 2

        # The SAME delivered stream through the fleet relay's dedup: the
        # replay is suppressed and counted — effectively-once ingest.
        view = FleetView()
        for seq in seen:
            view.ingest_line(json.dumps(
                {"host": "hA", "boot_epoch": epoch, "wal_seq": seq}))
        doc = view.query(detail=True)
        assert doc["hosts_detail"]["hA"]["records"] == 2
        assert doc["hosts_detail"]["hA"]["duplicates"] == 1
        assert doc["ingest"]["duplicates_suppressed"] == 1
    finally:
        if state.get("sock") is not None:
            state["sock"].close()
        relay.close()


# ---------------------------------------------------------------------------
# 2. Shim rides through a daemon restart (fake IPC daemon, no C++)
# ---------------------------------------------------------------------------


class FakeIpcDaemon:
    """Answers ctxt/req datagrams on a named endpoint — just enough of
    the IPC fabric for the shim's registration/poll path."""

    def __init__(self, endpoint: str):
        self.endpoint = endpoint
        self.contexts = 0
        self.requests = 0
        self._stop = threading.Event()
        self._client = ipc.IpcClient(name=endpoint)
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def _serve(self):
        while not self._stop.is_set():
            msg = self._client.recv(timeout_s=0.1)
            if msg is None:
                continue
            if msg.type == "ctxt":
                self.contexts += 1
                self._client.send(
                    ipc.MSG_TYPE_CONTEXT, ipc.INT32.pack(1), dest=msg.src)
            elif msg.type == "req":
                self.requests += 1
                self._client.send(ipc.MSG_TYPE_REQUEST, b"", dest=msg.src)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=2)
        self._client.close()


def test_shim_rides_through_daemon_restart(tmp_path):
    endpoint = f"dynotpu_fake_{os.getpid()}"
    daemon = FakeIpcDaemon(endpoint)
    client = TraceClient(
        job_id=7,
        endpoint=endpoint,
        poll_interval_s=0.1,
        profiler=RecordingProfiler(),
        report_interval_s=0,
        warmup_profiler=False,
    )
    try:
        assert client.start()  # registered against incarnation 1
        deadline = time.monotonic() + 5
        while daemon.requests == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert daemon.requests > 0

        # "Daemon restart": the endpoint disappears...
        daemon.stop()
        deadline = time.monotonic() + 10
        while client._absent_polls < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert client._absent_polls >= 2  # absence detected, backing off

        # ...and a NEW incarnation binds the same name.
        daemon2 = FakeIpcDaemon(endpoint)
        try:
            deadline = time.monotonic() + 15
            while client.daemon_reconnects == 0 and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            # The first reply re-announced the pid: the new incarnation
            # saw a fresh ctxt registration, not just polls.
            assert client.daemon_reconnects == 1
            assert daemon2.contexts >= 1
            assert client.instance_rank == 1
        finally:
            daemon2.stop()
    finally:
        client.stop()


# ---------------------------------------------------------------------------
# 3. Daemon-gated end-to-end drills
# ---------------------------------------------------------------------------

FAST_SINK = (
    "--use_tcp_relay",
    "--relay_host=127.0.0.1",
    "--sink_retry_initial_ms=50",
    "--sink_retry_max_ms=200",
    "--sink_breaker_failures=2",
    "--sink_replay_budget_ms=500",
    "--sink_relay_ack",
)


def _wait(predicate, timeout_s=20.0, interval_s=0.1):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return predicate()


def _gap_free(seqs: set[int]) -> bool:
    return bool(seqs) and seqs >= set(range(1, max(seqs) + 1))


def test_daemon_relay_outage_spills_then_replays(bin_dir, tmp_path):
    spill = tmp_path / "spill"
    relay = AckingRelay()
    daemon = start_daemon(
        bin_dir,
        kernel_interval_s=1,
        extra_flags=(
            *FAST_SINK,
            f"--relay_port={relay.port}",
            f"--sink_spill_dir={spill}",
        ),
    )
    try:
        # Healthy delivery first: sequenced intervals arrive and the
        # queue trims on ack.
        assert _wait(lambda: len(relay.unique()) >= 2)
        port = relay.port

        # Sever the relay: intervals must SPILL (pending grows), with
        # zero drops counted — an outage is deferral, not loss.
        relay.sever()
        before = max(relay.unique())

        def pending():
            doc = daemon.rpc({"fn": "health"})
            sinks = doc["durability"]["sinks"]
            return next(iter(sinks.values()))["pending_records"] if sinks \
                else 0

        assert _wait(lambda: pending() >= 2, timeout_s=30)
        doc = daemon.rpc({"fn": "health"})
        relay_sink = doc["components"].get("relay_sink")
        assert relay_sink is not None
        assert relay_sink["drops"] == 0  # deferred != dropped

        # Relay returns on the SAME port: the backlog replays in order
        # and coverage is gap-free — every interval of the outage window
        # arrives late, none are lost.
        relay2 = AckingRelay(port=port)
        try:
            assert _wait(
                lambda: max(relay2.unique(), default=0) > before + 1,
                timeout_s=30)
            assert _wait(lambda: pending() == 0, timeout_s=30)
            covered = relay.unique() | relay2.unique()
            assert _gap_free(covered), sorted(
                set(range(1, max(covered) + 1)) - covered)
        finally:
            relay2.sever()
    finally:
        stop_daemon(daemon)


def test_daemon_sigkill_restart_keeps_rules_breakers_and_backlog(
        bin_dir, tmp_path):
    spill = tmp_path / "spill"
    state = tmp_path / "state.json"
    trace_root = tmp_path / "traces"
    trace_root.mkdir()
    flags = (
        *FAST_SINK,
        "--relay_port=1",  # dead relay: everything spills from tick one
        f"--sink_spill_dir={spill}",
        f"--state_file={state}",
        "--state_snapshot_interval_s=1",
        f"--trace_output_root={trace_root}",
    )
    daemon = start_daemon(bin_dir, kernel_interval_s=1, extra_flags=flags)
    rule = {
        "fn": "addTraceTrigger",
        "metric": "cpu_util",
        "op": "above",
        "threshold": 99999.0,
        "for_ticks": 3,
        "cooldown_s": 600,
        "job_id": 42,
        "duration_ms": 500,
        "log_file": str(trace_root / "trig.json"),
    }
    try:
        assert daemon.rpc(rule)["status"] == "ok"
        # Wait until (a) intervals spilled, (b) the dead relay degraded
        # the sink component, (c) at least one snapshot covered both.
        def health():
            return daemon.rpc({"fn": "health"})

        assert _wait(lambda: next(iter(
            health()["durability"]["sinks"].values()),
            {"pending_records": 0})["pending_records"] >= 2, timeout_s=30)
        assert _wait(lambda: health()["components"].get(
            "relay_sink", {}).get("state") == "degraded", timeout_s=30)
        assert _wait(lambda: health()["durability"]["snapshot"]["writes"]
                     >= 1, timeout_s=30)
        time.sleep(1.2)  # one more snapshot interval covering the above
        pre = health()
        pre_pending = next(iter(
            pre["durability"]["sinks"].values()))["pending_records"]

        # Preemption: SIGKILL, no unwind, no final snapshot.
        os.kill(daemon.proc.pid, signal.SIGKILL)
        daemon.proc.wait()
    except Exception:
        stop_daemon(daemon)
        raise

    # Restart with a LIVE relay this time: recovery must restore the
    # rule, boot the sink component degraded (the crash-time state), and
    # replay the whole spilled backlog gap-free.
    relay = AckingRelay()
    flags2 = tuple(
        f"--relay_port={relay.port}" if f == "--relay_port=1" else f
        for f in flags)
    daemon2 = start_daemon(bin_dir, kernel_interval_s=1, extra_flags=flags2)
    try:
        # A restored component waits for its owner (HealthRestore): the
        # sink's appears once a collector loop has built its logger, which
        # on a loaded machine may come after the RPC server answers.
        docs = []

        def claimed():
            docs.append(daemon2.rpc({"fn": "health"}))
            return "relay_sink" in docs[-1]["components"]

        assert _wait(claimed, timeout_s=10, interval_s=0.02)
        doc = docs[-1]
        assert doc["durability"]["snapshot"]["recovered"] is True
        # Breaker/degraded state survived the crash: reported BEFORE any
        # local failure could re-derive it.
        assert doc["components"]["relay_sink"]["state"] == "degraded"

        triggers = daemon2.rpc({"fn": "listTraceTriggers"})
        assert triggers["status"] == "ok"
        restored = [t for t in triggers["triggers"]
                    if t["metric"] == "cpu_util"]
        assert len(restored) == 1
        assert restored[0]["threshold"] == 99999.0
        assert restored[0]["cooldown_s"] == 600

        # The pre-crash backlog replays: gap-free coverage through the
        # crash (sequence space continued by WAL recovery).
        assert _wait(
            lambda: len(relay.unique()) >= pre_pending, timeout_s=40)
        assert _gap_free(relay.unique()), sorted(relay.unique())
        # And the sink recovers to up once deliveries succeed.
        assert _wait(lambda: daemon2.rpc({"fn": "health"})["components"][
            "relay_sink"]["state"] == "up", timeout_s=30)
    finally:
        stop_daemon(daemon2)
        relay.sever()


def test_corrupt_state_snapshot_fails_closed_loudly(bin_dir, tmp_path):
    state = tmp_path / "state.json"
    state.write_text('{"version": 1, "sections": {"autotrigger": []}')  # torn
    daemon = start_daemon(
        bin_dir,
        extra_flags=(
            f"--state_file={state}",
            "--state_snapshot_interval_s=1",
        ))
    try:
        doc = daemon.rpc({"fn": "health"})
        snap = doc["durability"]["snapshot"]
        assert snap["recovered"] is False
        assert "corrupt" in snap.get("recover_error", "")
        # Defaults, not a half-restore: the daemon runs fine regardless.
        assert daemon.rpc({"fn": "getStatus"})["status"] == 1
        # And the next snapshot interval REPLACES the corrupt file.
        assert _wait(lambda: daemon.rpc({"fn": "health"})["durability"][
            "snapshot"]["writes"] >= 1, timeout_s=20)
    finally:
        stop_daemon(daemon)
    from dynolog_tpu.supervise import SNAPSHOT_VERSION

    doc = json.loads(state.read_text())
    assert doc["version"] == SNAPSHOT_VERSION  # valid again


def test_capture_straddles_daemon_restart(bin_dir, tmp_path):
    """The elastic scenario's capture leg: a capture in flight when the
    daemon dies finishes LOCALLY (shim-side), its manifest is complete,
    and the shim rides into the restarted daemon — where the next
    capture works end to end."""
    daemon = start_daemon(bin_dir, kernel_interval_s=1)
    endpoint = daemon.endpoint
    profiler = RecordingProfiler()
    client = TraceClient(
        job_id=9,
        endpoint=endpoint,
        poll_interval_s=0.1,
        profiler=profiler,
        report_interval_s=0,
    )
    pid = os.getpid()
    try:
        assert client.start()
        # 3s-window capture; the daemon is SIGKILL'd shortly after the
        # profiler starts, so the window straddles the crash.
        resp = daemon.rpc({
            "fn": "setKinetOnDemandRequest",
            "config": (
                f"ACTIVITIES_LOG_FILE={tmp_path / 'trace.json'}\n"
                "ACTIVITIES_DURATION_MSECS=3000\n"
            ),
            "pids": [0],
            "job_id": 9,
            "process_limit": 3,
        })
        assert resp["activityProfilersTriggered"], resp
        assert _wait(
            lambda: any(c[0] == "start" for c in profiler.calls),
            timeout_s=10)
        os.kill(daemon.proc.pid, signal.SIGKILL)
        daemon.proc.wait()

        # The capture finishes locally despite the dead daemon: complete
        # (parseable, status ok) manifest, traces_completed ticks.
        assert _wait(lambda: client.traces_completed >= 1, timeout_s=30), \
            client.last_error
        # Let the poll loop OBSERVE the absence before the restart, so
        # the ride-through below is a detected restart, not a blip the
        # shim never saw.
        assert _wait(lambda: client._absent_polls >= 1, timeout_s=10)
        manifest = json.loads((tmp_path / f"trace_{pid}.json").read_text())
        assert manifest["status"] == "ok"
        assert manifest["ended_ms"] - manifest["started_ms"] >= 3000

        # Restarted daemon (same endpoint): the shim re-announces and a
        # NEW capture works end to end through the new incarnation.
        daemon2 = start_daemon(bin_dir, kernel_interval_s=1,
                               endpoint=endpoint)
        try:
            assert _wait(lambda: client.daemon_reconnects >= 1,
                         timeout_s=30)
            resp = daemon2.rpc({
                "fn": "setKinetOnDemandRequest",
                "config": (
                    f"ACTIVITIES_LOG_FILE={tmp_path / 'trace2.json'}\n"
                    "ACTIVITIES_DURATION_MSECS=200\n"
                ),
                "pids": [0],
                "job_id": 9,
                "process_limit": 3,
            })
            assert resp["activityProfilersTriggered"], resp
            assert _wait(lambda: client.traces_completed >= 2, timeout_s=30)
            manifest2 = json.loads(
                (tmp_path / f"trace2_{pid}.json").read_text())
            assert manifest2["status"] == "ok"
        finally:
            stop_daemon(daemon2)
    finally:
        client.stop()


def test_daemon_wal_dir_readable_by_python_mirror(bin_dir, tmp_path):
    """Cross-language pin: the C++ daemon's on-disk WAL is byte-readable
    by the supervise.py mirror (same format), so drills and operators can
    inspect a backlog without the daemon."""
    spill = tmp_path / "spill"
    daemon = start_daemon(
        bin_dir,
        kernel_interval_s=1,
        extra_flags=(
            *FAST_SINK,
            "--relay_port=1",  # dead relay: records accumulate
            f"--sink_spill_dir={spill}",
        ),
    )
    try:
        def pending():
            sinks = daemon.rpc({"fn": "health"})["durability"]["sinks"]
            return next(iter(sinks.values()))["pending_records"] if sinks \
                else 0
        assert _wait(lambda: pending() >= 2, timeout_s=30)
    finally:
        stop_daemon(daemon)
    wal_dirs = [p for p in spill.iterdir() if p.is_dir()]
    assert len(wal_dirs) == 1
    mirror = SinkWal(str(wal_dirs[0]))
    records = mirror.peek(max_records=1000)
    assert len(records) >= 2
    for seq, payload in records:
        doc = json.loads(payload)
        assert doc["wal_seq"] == seq  # embedded seq matches the frame
