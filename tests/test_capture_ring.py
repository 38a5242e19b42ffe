"""Continuous capture ring (shim.CaptureRing): sampling cadence, compact
promotion, K-retention, TTL sweep, env opt-in — all with a fake profiler
that emits the deterministic synthetic XSpace (no jax, no daemon)."""

import itertools
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from xspace_fixture import build_xspace  # noqa: E402

import pytest  # noqa: E402

from dynolog_tpu import diagnose  # noqa: E402
from dynolog_tpu.client import shim as shim_mod  # noqa: E402
from dynolog_tpu.client.shim import (  # noqa: E402
    CaptureRing,
    RingConfig,
    TraceClient,
)


class FakeXplaneProfiler:
    """Profiler double that writes a synthetic xplane.pb on stop(),
    shaped exactly like a jax capture session dir."""

    def __init__(self, xspace: bytes | None = None):
        self.xspace = xspace if xspace is not None else build_xspace(
            planes=1, events_per_line=200)
        self.starts = 0
        self._dir = None
        # Mirrors JaxProfiler's knob so the ring's export suppression
        # path is exercised.
        self.export_trace_json = True
        self.export_seen: list[bool] = []

    def start(self, trace_dir: str) -> None:
        self.starts += 1
        self._dir = trace_dir

    def stop(self) -> None:
        self.export_seen.append(self.export_trace_json)
        run = os.path.join(self._dir, "plugins", "profile", "run")
        os.makedirs(run, exist_ok=True)
        with open(os.path.join(run, "host.xplane.pb"), "wb") as f:
            f.write(self.xspace)


def _ring(tmp_path, **kw) -> CaptureRing:
    defaults = dict(every_n_steps=10, keep=3, window_ms=1,
                    dir=str(tmp_path / "ring"), model="m",
                    min_interval_s=0.0)
    defaults.update(kw)
    return CaptureRing(RingConfig(**defaults))


def test_ring_samples_on_step_boundary_and_promotes(tmp_path):
    ring = _ring(tmp_path)
    prof = FakeXplaneProfiler()
    for step in range(1, 10):
        ring.note_step(step)
        assert not ring.due(), step
    ring.note_step(10)
    assert ring.due()
    path = ring.capture(prof)
    assert path and os.path.exists(path), ring.last_error
    doc = json.loads(pathlib.Path(path).read_text())
    assert doc["schema"] == 1
    assert doc["kind"] == "dynolog_tpu.ring_profile"
    assert doc["model"] == "m"
    assert doc["summary"]["top_ops"], "promotion produced no op table"
    # Per-op-instance resolution: the diagnosable unit.
    assert any(o["op"].startswith("fusion.")
               for o in doc["summary"]["top_ops"])
    # The export child was suppressed for the ring sample and restored.
    assert prof.export_seen == [False]
    assert prof.export_trace_json is True
    # The raw capture session dir is gone — the ring keeps summaries.
    assert not [p for p in (tmp_path / "ring").rglob("*.xplane.pb")]


@pytest.mark.parametrize("uptime_s", [42.0, 1e6])
def test_ring_burst_arms_once_and_rate_cap_holds(
        tmp_path, monkeypatch, uptime_s):
    # time.monotonic() counts from boot: a host up for less than
    # min_interval_s (a freshly booted TPU VM) must still arm the first
    # capture, so the clock is pinned on both sides of the interval.
    clock = itertools.count(uptime_s, 0.001)
    monkeypatch.setattr(shim_mod.time, "monotonic", lambda: next(clock))
    ring = _ring(tmp_path, min_interval_s=3600.0)
    prof = FakeXplaneProfiler()
    # A burst crossing several boundaries between polls arms exactly once.
    ring.note_step(35)
    assert ring.due()
    assert ring.capture(prof)
    # Next boundary is rate-capped (one capture per hour).
    ring.note_step(45)
    assert not ring.due()


def test_ring_keeps_newest_k(tmp_path):
    ring = _ring(tmp_path, keep=2)
    prof = FakeXplaneProfiler()
    paths = []
    for i in range(4):
        ring.note_step((i + 1) * 10)
        p = ring.capture(prof)
        assert p, ring.last_error
        paths.append(p)
        time.sleep(0.002)  # distinct created_ms stamps
    kept = ring.entries()
    assert len(kept) == 2
    assert kept[-1] == paths[-1]
    assert paths[0] not in kept and paths[1] not in kept


def test_ring_ttl_sweep_reclaims_expired(tmp_path):
    ring = _ring(tmp_path, ttl_s=100.0)
    prof = FakeXplaneProfiler()
    ring.note_step(10)
    old = ring.capture(prof)
    ring.note_step(20)
    fresh = ring.capture(prof)
    past = time.time() - 500
    os.utime(old, (past, past))
    reclaimed = ring.sweep()
    assert old in reclaimed
    assert os.path.exists(fresh)
    assert not os.path.exists(old)


def test_ring_profile_diagnoses_against_baseline(tmp_path):
    # The closed loop's Python half: ring profile vs saved baseline ->
    # ranked findings naming the regressed op instance.
    baseline = tmp_path / "base.json"
    base_summary = diagnose.resolve_summary_from_bytes = None  # noqa: F841
    from dynolog_tpu import trace

    diagnose.save_baseline(
        str(baseline),
        trace.compact_profile(build_xspace(planes=1, events_per_line=200)),
        model="m")
    regressed = build_xspace(
        planes=1, events_per_line=200, op_duration_scale={7: 2.0})
    ring = _ring(tmp_path)
    ring.note_step(10)
    assert ring.capture(FakeXplaneProfiler(regressed))
    rc = diagnose.main([
        "--ring", str(tmp_path / "ring"), "--model", "m",
        "--baseline", str(baseline), "--json",
        "--out", str(tmp_path / "report.json")])
    assert rc == 0
    report = json.loads((tmp_path / "report.json").read_text())
    assert report["verdict"] == "regressed"
    assert any(f["op"] == "fusion.7" and f["kind"] == "fusion_regression"
               for f in report["findings"])


def test_ring_failure_is_contained(tmp_path):
    class BrokenProfiler:
        def start(self, trace_dir):
            raise RuntimeError("no backend")

        def stop(self):
            pass

    ring = _ring(tmp_path)
    ring.note_step(10)
    assert ring.capture(BrokenProfiler()) is None
    assert "ring capture failed" in ring.last_error
    assert not ring.due()  # failed sample consumed; next boundary re-arms


class _NoDaemonIpc:
    """IpcClient double: every poll answers instantly with no config (a
    live daemon with nothing pending), so the poll loop spins at its
    nominal cadence instead of the dead-endpoint send backoff."""

    def register_context(self, *a, **kw):
        return 0

    def request_config(self, *a, **kw):
        return ""

    def take_late_config(self):
        return None

    def subscribe_kicks(self, *a, **kw):
        return True

    def wait_for_kick(self, timeout_s):
        time.sleep(min(timeout_s, 0.01))
        return False

    def send_perf_stats(self, *a, **kw):
        return True

    def send_spans(self, *a, **kw):
        return 0

    def close(self):
        pass


def test_trace_client_ring_via_poll_loop(tmp_path):
    # End to end through the real TraceClient poll thread (IPC stubbed to
    # an idle daemon): steps arm the ring, the poll thread samples it.
    prof = FakeXplaneProfiler()
    client = TraceClient(
        job_id=7,
        endpoint=f"ring_test_{os.getpid()}",
        poll_interval_s=0.05,
        profiler=prof,
        ring=RingConfig(every_n_steps=5, keep=2, window_ms=1,
                        dir=str(tmp_path / "ring"), model="m",
                        min_interval_s=0.0),
    )
    client._client = _NoDaemonIpc()
    client.start()
    try:
        for _ in range(5):
            client.step()
        deadline = time.time() + 10
        while time.time() < deadline and client.ring.captures == 0:
            time.sleep(0.02)
        assert client.ring.captures == 1, client.ring.last_error
        assert client.ring.entries()
    finally:
        client.stop()


def test_ring_env_opt_in(tmp_path, monkeypatch):
    monkeypatch.setenv("DYNO_TPU_RING_EVERY_N", "50")
    monkeypatch.setenv("DYNO_TPU_RING_DIR", str(tmp_path / "r"))
    monkeypatch.setenv("DYNO_TPU_RING_KEEP", "junk")  # soft-fails
    client = TraceClient(job_id=1, endpoint="ring_env_test")
    assert client.ring is not None
    assert client.ring.config.every_n_steps == 50
    assert client.ring.config.dir == str(tmp_path / "r")
    assert client.ring.config.keep == RingConfig.keep
    monkeypatch.setenv("DYNO_TPU_RING_EVERY_N", "0")
    assert TraceClient(job_id=1, endpoint="ring_env_test").ring is None
