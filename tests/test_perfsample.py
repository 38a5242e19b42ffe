"""E2E test for the on-demand PMU sampling verb (perfsample): async
start/poll protocol over RPC, per-thread weight profile attribution."""

import threading
import time

import pytest

import daemon_utils


def _busy(stop: threading.Event) -> None:
    x = 0
    while not stop.is_set():
        x += 1


def test_perfsample_verb(cpp_build):
    daemon = daemon_utils.start_daemon(cpp_build / "src")
    try:
        stop = threading.Event()
        t = threading.Thread(target=_busy, args=(stop,), name="busyloop")
        t.start()
        try:
            # task-clock is a software event: samplable even on PMU-less
            # VMs, so this path is exercised everywhere.
            started = daemon.rpc(
                {
                    "fn": "perfsample",
                    "event": "task-clock",
                    "sample_period": 100_000,
                    "duration_ms": 800,
                    "top": 10,
                }
            )
            assert started is not None and started["status"] == "started"
            # Dispatch thread stays responsive mid-capture.
            assert daemon.rpc({"fn": "getStatus"})["status"] == 1
            result = None
            for _ in range(60):
                time.sleep(0.2)
                result = daemon.rpc({"fn": "perfsampleResult"})
                if result is not None and result.get("status") != "pending":
                    break
        finally:
            stop.set()
            t.join()
        assert result is not None
        if result.get("status") != "ok":
            pytest.skip(f"sampling unavailable: {result.get('error')}")
        assert result["window_ms"] >= 800
        assert result["samples"] > 0
        threads = result["threads"]
        assert threads
        weights = [t["weight"] for t in threads]
        assert weights == sorted(weights, reverse=True)
        total_pct = sum(t["weight_pct"] for t in threads)
        assert total_pct <= 100.0 + 1e-6
        assert threads[0]["name"], threads[0]
        # The busy loop must hold most of a core. The profile is
        # system-wide and weight_pct is a share of every CPU's samples,
        # so on a many-core host the bar is the thread's own CPU time
        # (task-clock weight is ns) against the window.
        busy = [th for th in threads if th["tid"] == t.native_id]
        assert busy, threads
        assert busy[0]["weight"] > 0.3 * result["window_ms"] * 1e6, threads

        # Unknown events fail soft with a parse error, not a hang.
        bad = daemon.rpc(
            {"fn": "perfsample", "event": "no-such-event", "duration_ms": 100}
        )
        assert bad["status"] == "started"
        for _ in range(20):
            time.sleep(0.1)
            r = daemon.rpc({"fn": "perfsampleResult"})
            if r.get("status") != "pending":
                break
        assert r["status"] == "failed" and "bad event" in r["error"]
    finally:
        daemon_utils.stop_daemon(daemon)
