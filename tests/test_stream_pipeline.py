"""Streaming capture pipeline failure modes (pure Python, tier-1 —
no C++ build, no daemon, no jax):

- dynolog_tpu/stream.py: bounded chunk queue close/fail/abandon
  semantics, zero-copy chunking, fanout isolation;
- trace.stream_write fed by the queue: a producer failure or a writer
  throw must clean the tmp and NEVER rename a partial artifact into
  place;
- shim.PendingWrite: the collect->feed->write hand-off, including a
  convert/writer throw mid-pipeline surfacing through wait();
- FramedRpcClient.call_streaming / fetch_to_file against an in-test
  streaming peer: byte-identical fetch, truncated stream, client-side
  per-frame (progress-based) deadline — a slow but progressing stream
  longer than timeout_s succeeds, a genuine mid-stream stall fails.
"""

from __future__ import annotations

import json
import os
import socket
import struct
import sys
import threading
import time
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from dynolog_tpu import stream, trace  # noqa: E402
from dynolog_tpu.client.shim import PendingWrite  # noqa: E402
from dynolog_tpu.cluster.rpc import (  # noqa: E402
    FRAME_HEADER,
    FramedRpcClient,
)


# ---- stream.py primitives -------------------------------------------------


def test_chunk_views_round_trip_zero_copy():
    data = bytes(range(256)) * 100
    views = list(stream.chunk_views(data, chunk_bytes=1000))
    assert all(isinstance(v, memoryview) for v in views)
    assert b"".join(views) == data
    assert len(views) == (len(data) + 999) // 1000


def test_bounded_queue_orders_chunks_and_ends_at_close():
    q = stream.BoundedChunkQueue(max_chunks=2)
    got = []
    consumer = threading.Thread(target=lambda: got.extend(iter(q)))
    consumer.start()
    for i in range(10):
        assert q.put(bytes([i]))
    q.close()
    consumer.join(timeout=5)
    assert not consumer.is_alive()
    assert got == [bytes([i]) for i in range(10)]


def test_bounded_queue_fail_raises_stream_failed_at_consumer():
    q = stream.BoundedChunkQueue()
    q.put(b"prefix")
    q.fail(RuntimeError("collector died"))
    it = iter(q)
    assert next(it) == b"prefix"
    with pytest.raises(stream.StreamFailed, match="collector died"):
        next(it)


def test_bounded_queue_abandon_unblocks_producer():
    q = stream.BoundedChunkQueue(max_chunks=1)
    assert q.put(b"x")  # fills the queue
    blocked_result = []

    def producer():
        blocked_result.append(q.put(b"y"))  # blocks until abandon

    t = threading.Thread(target=producer)
    t.start()
    time.sleep(0.1)
    assert t.is_alive()  # parked on backpressure
    q.abandon()
    t.join(timeout=5)
    assert not t.is_alive()
    assert blocked_result == [False]  # producer told to stop


def test_fanout_feeds_every_sink_and_isolates_a_throwing_one():
    chunks = [bytes([i]) * 100 for i in range(20)]

    def collect(it):
        return b"".join(it)

    def dies(it):
        for i, _chunk in enumerate(it):
            if i == 3:
                raise RuntimeError("sink exploded")
        return None

    results = stream.fanout(iter(chunks), [collect, dies, collect])
    assert results[0].error is None
    assert results[0].value == b"".join(chunks)
    assert isinstance(results[1].error, RuntimeError)
    assert results[2].value == b"".join(chunks)  # unaffected by lane 1


def test_fanout_producer_failure_reaches_sinks_as_stream_failed():
    def bad_producer():
        yield b"one"
        raise RuntimeError("producer died")

    seen = {}

    def sink(it):
        try:
            for _ in it:
                pass
        except stream.StreamFailed as e:
            seen["error"] = str(e)
            raise

    with pytest.raises(RuntimeError, match="producer died"):
        stream.fanout(bad_producer(), [sink])
    assert "producer died" in seen["error"]


# ---- stream_write through the queue ---------------------------------------


def test_stream_write_from_queue_byte_identical(tmp_path):
    data = os.urandom(3 << 20)
    path = tmp_path / "out.xplane.pb"
    q = stream.BoundedChunkQueue()

    def producer():
        for view in stream.chunk_views(data, chunk_bytes=256 << 10):
            if not q.put(view):
                return
        q.close()

    t = threading.Thread(target=producer)
    t.start()
    written = trace.stream_write(str(path), q)
    t.join(timeout=5)
    assert written == len(data)
    assert path.read_bytes() == data
    assert not (tmp_path / "out.xplane.pb.tmp").exists()


def test_stream_write_truncated_stream_cleans_tmp_no_partial(tmp_path):
    """A truncated chunk stream (producer failed mid-way) must unwind
    through stream_write's tmp discipline: no artifact, no tmp debris."""
    path = tmp_path / "out.xplane.pb"
    q = stream.BoundedChunkQueue()
    q.put(b"a partial prefix of the artifact")
    q.fail(RuntimeError("collect aborted"))
    with pytest.raises(stream.StreamFailed):
        trace.stream_write(str(path), q)
    assert not path.exists()  # never renamed into place
    assert not (tmp_path / "out.xplane.pb.tmp").exists()  # tmp unlinked


# ---- PendingWrite (the shim's deferred artifact write) --------------------


def test_pending_write_happy_path_runs_on_complete(tmp_path):
    data = os.urandom(1 << 20)
    path = tmp_path / "host.xplane.pb"
    completed = []
    pending = PendingWrite(str(path), on_complete=completed.append)
    for view in stream.chunk_views(data, chunk_bytes=128 << 10):
        assert pending.queue.put(view)
    pending.queue.close()
    decomp = pending.wait(10.0)
    assert "write_error" not in decomp
    assert decomp["write_bytes"] == len(data)
    assert path.read_bytes() == data
    assert completed == [str(path)]


def test_pending_write_writer_throw_surfaces_and_cleans(tmp_path):
    """Writer-side failure mid-pipeline (the convert/write worker dying):
    wait() reports the error, on_complete is never handed the path (it is
    told None once: whoever waits on the artifact, the shim's export child,
    is sent away), the producer is unblocked, and no partial artifact or
    tmp survives."""
    target_dir = tmp_path / "gone"
    target_dir.mkdir()
    path = target_dir / "host.xplane.pb"
    completed = []
    # Remove the directory out from under the writer: open() throws.
    target_dir.rmdir()
    pending = PendingWrite(str(path), on_complete=completed.append)
    # The producer keeps feeding; once the writer died, put() returns
    # False (abandoned queue) instead of blocking forever.
    deadline = time.time() + 10
    fed_after_death = True
    while time.time() < deadline:
        if not pending.queue.put(b"x" * (1 << 18)):
            fed_after_death = False
            break
    assert not fed_after_death
    decomp = pending.wait(10.0)
    assert "write_error" in decomp
    assert completed == [None]
    assert not path.exists()


def test_pending_write_producer_failure_no_partial_artifact(tmp_path):
    """Producer throw mid-feed (the collect thread dying): the queue's
    fail() marks the stream, the writer unwinds through tmp cleanup."""
    path = tmp_path / "host.xplane.pb"
    pending = PendingWrite(str(path))
    pending.queue.put(b"prefix")
    pending.queue.fail(RuntimeError("collect thread died"))
    decomp = pending.wait(10.0)
    assert "write_error" in decomp
    assert "collect thread died" in decomp["write_error"]
    assert not path.exists()
    assert not (tmp_path / "host.xplane.pb.tmp").exists()


# ---- FramedRpcClient streaming --------------------------------------------


class StreamPeer:
    """In-test daemon stand-in for the chunked fetch wire: one framed
    request in, a JSON header frame out, then CHUNK frames + the END
    frame — with knobs for truncation (close before END), a mid-stream
    stall, and slow-but-progressing pacing."""

    def __init__(self, payload: bytes, chunk_bytes: int = 64 << 10,
                 truncate_after: int | None = None,
                 stall_after: int | None = None,
                 inter_chunk_delay_s: float = 0.0):
        self.payload = payload
        self.chunk_bytes = chunk_bytes
        self.truncate_after = truncate_after
        self.stall_after = stall_after
        self.inter_chunk_delay_s = inter_chunk_delay_s
        self._lsock = socket.socket()
        self._lsock.settimeout(10.0)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(4)
        self.port = self._lsock.getsockname()[1]
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        try:
            self._lsock.close()
        except OSError:
            pass

    def _serve(self):
        try:
            conn, _ = self._lsock.accept()
        except OSError:
            return
        with conn:
            conn.settimeout(10.0)
            # Drain the request frame.
            (length,) = FRAME_HEADER.unpack(self._recv_exact(conn, 4))
            self._recv_exact(conn, length)
            header = json.dumps({
                "status": "ok", "stream": "chunks",
                "bytes": len(self.payload),
            }).encode()
            conn.sendall(FRAME_HEADER.pack(len(header)) + header)
            sent = 0
            for i in range(0, len(self.payload), self.chunk_bytes):
                if self.truncate_after is not None \
                        and sent >= self.truncate_after:
                    return  # close without END: truncated
                if self.stall_after is not None \
                        and sent >= self.stall_after:
                    time.sleep(30)  # a genuine stall, not slowness
                    return
                chunk = self.payload[i:i + self.chunk_bytes]
                if self.inter_chunk_delay_s:
                    time.sleep(self.inter_chunk_delay_s)
                conn.sendall(FRAME_HEADER.pack(len(chunk)) + chunk)
                sent += len(chunk)
            conn.sendall(FRAME_HEADER.pack(0))  # END
            # Hold the connection briefly so the client can finish.
            time.sleep(0.2)

    @staticmethod
    def _recv_exact(conn, n):
        buf = b""
        while len(buf) < n:
            piece = conn.recv(n - len(buf))
            if not piece:
                raise ConnectionError("peer closed")
            buf += piece
        return buf


def test_call_streaming_delivers_chunks_in_order():
    payload = os.urandom(1 << 20)
    with StreamPeer(payload) as peer:
        got = []
        with FramedRpcClient("127.0.0.1", peer.port, timeout_s=5.0) as c:
            header = c.call_streaming({"fn": "fetchTrace", "path": "/x"},
                                      got.append)
        assert header is not None
        assert header["status"] == "ok"
        assert header["streamed_bytes"] == len(payload)
        assert b"".join(got) == payload


def test_fetch_to_file_atomic_and_byte_identical(tmp_path):
    payload = os.urandom(2 << 20)
    dest = tmp_path / "fetched.xplane.pb"
    with StreamPeer(payload) as peer:
        with FramedRpcClient("127.0.0.1", peer.port, timeout_s=5.0) as c:
            header = c.fetch_to_file("/x", str(dest))
    assert header is not None and header["status"] == "ok"
    assert dest.read_bytes() == payload
    assert not (tmp_path / "fetched.xplane.pb.tmp").exists()


def test_truncated_stream_returns_none_and_leaves_no_artifact(tmp_path):
    payload = os.urandom(1 << 20)
    dest = tmp_path / "fetched.xplane.pb"
    with StreamPeer(payload, truncate_after=256 << 10) as peer:
        with FramedRpcClient("127.0.0.1", peer.port, timeout_s=5.0) as c:
            header = c.fetch_to_file("/x", str(dest))
    assert header is None  # truncation is a transport failure
    assert not dest.exists()  # partial artifact never renamed into place
    assert not (tmp_path / "fetched.xplane.pb.tmp").exists()


def test_stalled_stream_trips_per_frame_deadline(tmp_path):
    """A genuine mid-stream stall must fail within ~timeout_s, not hang:
    the deadline is per frame, and a frame that never arrives trips it."""
    payload = os.urandom(512 << 10)
    dest = tmp_path / "fetched.xplane.pb"
    with StreamPeer(payload, stall_after=128 << 10) as peer:
        t0 = time.monotonic()
        with FramedRpcClient("127.0.0.1", peer.port, timeout_s=1.0) as c:
            header = c.fetch_to_file("/x", str(dest))
        elapsed = time.monotonic() - t0
    assert header is None
    assert elapsed < 5.0  # ~1s deadline + slack, never the 30s stall
    assert not dest.exists()
    assert not (tmp_path / "fetched.xplane.pb.tmp").exists()


def test_slow_but_progressing_stream_outlives_the_call_timeout():
    """The satellite pin: a stream whose TOTAL time exceeds timeout_s but
    whose every frame arrives within it must complete — the deadline is
    progress-based (per frame), not per call."""
    # 8 chunks x 0.3s pacing ≈ 2.4s total against a 1s timeout.
    payload = os.urandom(8 * (16 << 10))
    with StreamPeer(payload, chunk_bytes=16 << 10,
                    inter_chunk_delay_s=0.3) as peer:
        got = []
        t0 = time.monotonic()
        with FramedRpcClient("127.0.0.1", peer.port, timeout_s=1.0) as c:
            header = c.call_streaming({"fn": "fetchTrace", "path": "/x"},
                                      got.append)
        elapsed = time.monotonic() - t0
    assert header is not None, "per-frame deadline cut off a live stream"
    assert header["streamed_bytes"] == len(payload)
    assert b"".join(got) == payload
    assert elapsed > 1.0  # the stream really did outlive timeout_s


def test_non_streamed_response_passes_through_call_streaming():
    """A header without stream=chunks (old daemon / plain verb) returns
    as-is; the sink never fires."""

    class PlainPeer(StreamPeer):
        def _serve(self):
            conn, _ = self._lsock.accept()
            with conn:
                (length,) = FRAME_HEADER.unpack(self._recv_exact(conn, 4))
                self._recv_exact(conn, length)
                body = json.dumps({"status": 1}).encode()
                conn.sendall(FRAME_HEADER.pack(len(body)) + body)
                time.sleep(0.2)

    with PlainPeer(b"") as peer:
        got = []
        with FramedRpcClient("127.0.0.1", peer.port, timeout_s=5.0) as c:
            header = c.call_streaming({"fn": "getStatus"}, got.append)
    assert header == {"status": 1}
    assert got == []


def test_bad_chunk_length_fails_closed(tmp_path):
    """A corrupt length prefix mid-stream (negative / beyond the frame
    cap) is a truncation, not a crash or a giant allocation."""

    class CorruptPeer(StreamPeer):
        def _serve(self):
            conn, _ = self._lsock.accept()
            with conn:
                (length,) = FRAME_HEADER.unpack(self._recv_exact(conn, 4))
                self._recv_exact(conn, length)
                header = json.dumps(
                    {"status": "ok", "stream": "chunks"}).encode()
                conn.sendall(FRAME_HEADER.pack(len(header)) + header)
                conn.sendall(FRAME_HEADER.pack(4) + b"good")
                conn.sendall(struct.pack("<i", -5))  # corrupt prefix
                time.sleep(0.2)

    dest = tmp_path / "fetched.bin"
    with CorruptPeer(b"") as peer:
        with FramedRpcClient("127.0.0.1", peer.port, timeout_s=5.0) as c:
            header = c.fetch_to_file("/x", str(dest))
    assert header is None
    assert not dest.exists()
    assert not (tmp_path / "fetched.bin.tmp").exists()


# ---- the shim's pipelined stop->finisher path -----------------------------


class FakeStreamingProfiler:
    """JaxProfiler's streaming-stop shape without jax: stop() feeds the
    collected payload through a PendingWrite exactly like the real
    _write_xplane, so TraceClient's pipelined finisher path is exercised
    end to end (capture -> queue feed -> writer thread -> manifest)."""

    def __init__(self, payload: bytes, break_write_dir: bool = False):
        self.payload = payload
        self.break_write_dir = break_write_dir
        self.last_stop_decomposition: dict = {}
        self._dir = None
        self._pending = None

    def start(self, log_dir: str) -> None:
        self._dir = log_dir

    def stop(self) -> None:
        run_dir = os.path.join(self._dir, "plugins", "profile", "run")
        if not self.break_write_dir:
            os.makedirs(run_dir, exist_ok=True)
        path = os.path.join(run_dir, "host.xplane.pb")
        pending = PendingWrite(path)
        self._pending = pending
        for view in stream.chunk_views(self.payload, 64 << 10):
            if not pending.queue.put(view):
                break
        pending.queue.close()
        self.last_stop_decomposition = {"xspace_bytes": len(self.payload)}

    def take_pending_write(self):
        pending, self._pending = self._pending, None
        return pending


def _run_capture(tmp_path, profiler):
    from dynolog_tpu.client.shim import TraceClient, TraceConfig

    client = TraceClient(
        job_id=1, endpoint=f"dynotpu_stream_test_{os.getpid()}",
        profiler=profiler)
    cfg = TraceConfig.parse(
        f"ACTIVITIES_LOG_FILE={tmp_path}/t.json\n"
        "ACTIVITIES_DURATION_MSECS=10")
    client._run_trace(cfg)
    return client, cfg


def test_shim_pipelined_capture_writes_artifact_and_manifest(tmp_path):
    payload = os.urandom(2 << 20)
    client, cfg = _run_capture(tmp_path, FakeStreamingProfiler(payload))
    pid = os.getpid()
    manifest_path = Path(cfg.manifest_path(pid))
    try:
        # The finisher owns the manifest: it must land (with the write
        # decomposition folded in) shortly after the pipelined stop.
        deadline = time.time() + 10
        while time.time() < deadline and not manifest_path.exists():
            time.sleep(0.02)
        manifest = json.loads(manifest_path.read_text())
        assert manifest["status"] == "ok"
        assert manifest["timing"]["write_bytes"] == len(payload)
        assert "write_ms" in manifest["timing"]
        artifact = (
            Path(cfg.trace_dir(pid)) / "plugins" / "profile" / "run"
            / "host.xplane.pb")
        assert artifact.read_bytes() == payload
        assert client.traces_completed == 1
    finally:
        client.stop()


def test_shim_pipelined_write_failure_fails_capture_loudly(tmp_path):
    """Writer death mid-pipeline (the satellite's convert-worker-throw
    case at the shim layer): the manifest records the error — the
    operator's health signal — and no artifact or tmp debris survives."""
    payload = os.urandom(256 << 10)
    client, cfg = _run_capture(
        tmp_path, FakeStreamingProfiler(payload, break_write_dir=True))
    pid = os.getpid()
    manifest_path = Path(cfg.manifest_path(pid))
    try:
        deadline = time.time() + 10
        while time.time() < deadline and not manifest_path.exists():
            time.sleep(0.02)
        manifest = json.loads(manifest_path.read_text())
        assert manifest["status"] == "error"
        assert "write failed" in manifest["error"]
        assert client.traces_completed == 0
        assert client.last_error
        run_dir = (
            Path(cfg.trace_dir(pid)) / "plugins" / "profile" / "run")
        assert not run_dir.exists()  # nothing renamed into place
    finally:
        client.stop()


def test_shim_stop_joins_inflight_finisher(tmp_path):
    """TraceClient.stop() must not strand a pipelined finish: after
    stop() returns, the capture's manifest exists."""
    payload = os.urandom(1 << 20)
    client, cfg = _run_capture(tmp_path, FakeStreamingProfiler(payload))
    client.stop()
    manifest_path = Path(cfg.manifest_path(os.getpid()))
    assert manifest_path.exists()
    assert json.loads(manifest_path.read_text())["status"] == "ok"
