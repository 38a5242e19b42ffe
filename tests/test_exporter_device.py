"""Exporter snapshot -> daemon file backend -> query, and which devices
become rows. The session's virtual CPU devices (conftest) stand in for
chips only when the caller asks for them by name; the default publishes
TPU devices and nothing else, so a job that fell to the CPU cannot feed
`tpu<N>` rows. The real chip's rows are chip_smoke.py's to check."""

import json
import time

import daemon_utils


def test_non_tpu_devices_are_not_rows(tmp_path):
    from dynolog_tpu import exporter

    assert exporter.collect_device_metrics() == []
    assert exporter.write_snapshot(str(tmp_path / "s.json"))["devices"] == []
    rows = exporter.collect_device_metrics(platform="cpu")
    assert len(rows) == 8 and rows[0]["chip_type"] == "cpu"


def test_exporter_to_daemon_pipeline(cpp_build, tmp_path):
    from dynolog_tpu import exporter

    path = tmp_path / "snap.json"
    snapshot = exporter.write_snapshot(str(path), platform="cpu")
    row = snapshot["devices"][0]
    # The CPU client reports no allocator stats; give the row the value a
    # chip's memory_stats() would so the store has a series to answer with.
    row["metrics"]["hbm_total_bytes"] = 16.0 * 2**30
    path.write_text(json.dumps(snapshot))

    d = daemon_utils.start_daemon(
        cpp_build / "src",
        extra_flags=(
            "--enable_tpu_monitor",
            "--tpu_metric_backend=file",
            f"--tpu_metrics_file={path}",
            "--tpu_monitor_reporting_interval_s=1",
        ),
    )
    try:
        deadline = time.time() + 15
        values = None
        metric = f"tpu{row['device']}.hbm_total_bytes"
        while time.time() < deadline:
            q = d.rpc(
                {"fn": "queryMetrics", "metrics": [metric], "start_ts": 0,
                 "end_ts": int(time.time() * 1000) + 10_000}
            )
            values = q.get("metrics", {}).get(metric, {}).get("values")
            if values:
                break
            time.sleep(0.5)
        assert values, f"{metric} never appeared in the store: {q}"
        assert values[-1] == row["metrics"]["hbm_total_bytes"]
    finally:
        daemon_utils.stop_daemon(d)


def test_collect_sdk_metrics_parses_vendor_lists(monkeypatch):
    # Fake the libtpu.sdk surface: per-chip numeric lists, a labeled list
    # with out-of-order cores, and an unsupported metric that raises.
    import sys
    import types

    data = {
        "duty_cycle_pct": ["95.5", "88.0"],
        "hbm_capacity_usage": ["1073741824", "2147483648"],
        "hlo_queue_size": ["tensorcore_1: 7", "tensorcore_0: 3"],
    }

    class FakeMetric:
        def __init__(self, values):
            self._values = values

        def data(self):
            return self._values

    class FakeMonitoring:
        @staticmethod
        def get_metric(name):
            if name not in data:
                raise RuntimeError("unsupported")
            return FakeMetric(data[name])

    fake_sdk = types.ModuleType("libtpu.sdk")
    fake_sdk.tpumonitoring = FakeMonitoring
    fake_pkg = types.ModuleType("libtpu")
    fake_pkg.sdk = fake_sdk
    monkeypatch.setitem(sys.modules, "libtpu", fake_pkg)
    monkeypatch.setitem(sys.modules, "libtpu.sdk", fake_sdk)

    from dynolog_tpu import exporter

    rows = exporter.collect_sdk_metrics()
    assert rows[0]["tpu_duty_cycle_pct"] == 95.5
    assert rows[1]["tpu_duty_cycle_pct"] == 88.0
    assert rows[0]["hbm_used_bytes"] == 1073741824.0
    # labeled core ids win over list position
    assert rows[0]["hlo_queue_size"] == 3.0
    assert rows[1]["hlo_queue_size"] == 7.0


def test_write_snapshot_merges_sdk_rows(monkeypatch, tmp_path):
    from dynolog_tpu import exporter

    monkeypatch.setattr(
        exporter, "collect_device_metrics",
        lambda platform: [{"device": 0, "chip_type": "tpu_v5e",
                          "metrics": {"hbm_used_bytes": 1.0}}],
    )
    monkeypatch.setattr(
        exporter, "collect_sdk_metrics",
        lambda: {0: {"hbm_used_bytes": 42.0, "tpu_duty_cycle_pct": 90.0},
                 1: {"tpu_duty_cycle_pct": 80.0}},
    )
    snap = exporter.write_snapshot(str(tmp_path / "m.json"))
    rows = {r["device"]: r for r in snap["devices"]}
    # SDK values overwrite the in-process approximation...
    assert rows[0]["metrics"]["hbm_used_bytes"] == 42.0
    assert rows[0]["metrics"]["tpu_duty_cycle_pct"] == 90.0
    # ...and SDK-only devices appear as new rows.
    assert rows[1]["metrics"]["tpu_duty_cycle_pct"] == 80.0
    assert rows[0]["chip_type"] == "tpu_v5e"
