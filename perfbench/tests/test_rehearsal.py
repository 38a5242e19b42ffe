"""A whole run on the CPU at a toy size, the look for a chip stubbed here:
sound, `correct` is true; with the timed path broken underneath, false."""

import jax.numpy as jnp

import cells
import rehearsal
import selftrace


def failed(run) -> list:
    return [c["name"] for c in run.record["checks"] if not c["ok"]]


def test_sound_steady_run_is_correct(monkeypatch, tmp_path):
    run, line = rehearsal.rehearse(monkeypatch, tmp_path, "steady")
    assert failed(run) == [] and line["correct"] is True
    assert [c["name"] for c in run.record["checks"]] == ["J", "S1", "S2", "C4"]
    assert line["failed"] == 0 and line["attempted"] == len(run.record["step_ms"])
    assert run.record["setup_s"] > 0 and sum(run.record["step_ms"]) > 3000
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    # the journals are asked for in traced runs alone; the RSS in every run
    assert "selftrace" not in run.record and "shim_counters" not in run.record
    assert run.record["daemon_rss_kb"] > 1000


def test_step_telemetry_lost_is_not_correct(monkeypatch, tmp_path):
    """The job steps but the shim is never told: S1 finds no samples."""
    def broken(run):
        run.client.step = lambda: None

    run, line = rehearsal.rehearse(monkeypatch, tmp_path, "steady", broken=broken)
    assert failed(run) == ["S1"] and line["correct"] is False


def test_step_that_leaves_out_half_the_batch_is_not_correct(monkeypatch, tmp_path):
    """The compiled step is handed a batch whose second half repeats the
    first: its first loss is not the reference's."""
    def broken(run):
        half = run.tokens.shape[0] // 2
        run.tokens = jnp.concatenate([run.tokens[:half], run.tokens[:half]])

    run, line = rehearsal.rehearse(monkeypatch, tmp_path, "steady", broken=broken)
    assert failed(run) == ["J"] and line["correct"] is False


def test_capture_run_drives_every_check(monkeypatch, tmp_path):
    """A CPU has no /device:TPU:0 plane, so C1-C3 read false here; the run
    still has to reach its end, count its captures and leave nothing."""
    run, line = rehearsal.rehearse(
        monkeypatch, tmp_path, "capture-pull", seconds=3.0, trace=True)
    names = [c["name"] for c in run.record["checks"]]
    assert names == ["J", "S1", "S2", "C1", "C2", "C3", "C4"]
    assert "C4" not in failed(run) and "J" not in failed(run)
    assert len(run.record["captures"]) >= 2
    assert all(c["ok"] for c in run.record["captures"])
    assert line["correct"] is False
    # the hook, against the real daemon and CLI: a tick a second, one verb
    # and one hand-off a capture (the warm one too), the counters
    rec = run.record
    journal, captures = rec["selftrace"], len(rec["captures"]) + 1
    count = {}
    for span in journal["spans"]:
        count[span["name"]] = count.get(span["name"], 0) + 1
    assert count[selftrace.CAPTURE_VERB] == count[selftrace.HANDOFF] == captures
    assert count["shim.capture"] == captures
    in_window = selftrace.window_ms(rec, selftrace.TPU_TICK)
    assert 2 <= len(in_window) <= 4  # 3 s of window, one tick a second
    assert journal["tpu_rows"] == 4 and journal["ipc_wakeups"]["message"] > 0
    assert rec["selftrace_oldest_ms"] < rec["window_start"] * 1e3
    assert rec["shim_counters"]["traces_completed"] == captures
    assert rec["shim_counters"]["steps"] == len(run.steps)
    readers = cells.load_readers()
    for name in ("tpu_tick_ms_p50", "kernel_tick_ms_p50", "rpc_verb_ms",
                 "ipc_handoff_ms", "ipc_timeout_wakeup_pct", "daemon_rss_mb",
                 "first_capture_ms", "longest_pass_tick_overlap_ms"):
        assert readers[name].read(rec) is not None, name
    assert readers["first_capture_ms"].read(rec) == (
        rec["warm_capture"][0]["capture_ms"])
