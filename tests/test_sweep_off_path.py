"""The pre-capture sweep stopped walking the output directory: a name
without the trace base's prefix is never stat'ed, the reclaim set is what
it was, and the sweep runs once the capture's manifest stands, off the
path from the operator's request to profiler.start(). (The reclaim rules
themselves are tests/test_shim_sweep.py's.)"""

from __future__ import annotations

import os
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tests"))

from dynolog_tpu.client import shim  # noqa: E402
from dynolog_tpu.client.shim import (  # noqa: E402
    RecordingProfiler,
    TraceClient,
    TraceConfig,
    sweep_stale_artifacts,
)
from test_shim_sweep import _dead_pid, _make_old  # noqa: E402

FOREIGN = 500


def _populate(root: pathlib.Path) -> tuple[list[str], list[pathlib.Path]]:
    """A shared output directory: this base's debris (what the sweep
    reclaimed before this change, one of each kind), its look-alikes that
    must stay, and FOREIGN entries of other captures and other programs.
    Returns (paths owed, paths that must survive)."""
    dead, dead2, dead3 = _dead_pid(), _dead_pid(), _dead_pid()
    manifest_tmp = root / f"cap_{dead}.json.tmp"
    manifest_tmp.write_bytes(b"{")
    dead_dir = root / f"cap_{dead}"
    (dead_dir / "plugins" / "profile" / "r1").mkdir(parents=True)
    live_dir = root / f"cap_{os.getpid()}"
    nested = live_dir / "plugins" / "profile" / "r1"
    nested.mkdir(parents=True)
    nested_tmp = nested / "trace.json.gz.tmp"
    nested_tmp.write_bytes(b"partial")
    for path in (manifest_tmp, dead_dir, nested_tmp):
        _make_old(path)
    owed = [str(manifest_tmp), str(dead_dir), str(nested_tmp)]

    keep = [live_dir]
    completed = root / f"cap_{dead2}"  # its manifest stands
    (completed / "plugins").mkdir(parents=True)
    (root / f"cap_{dead2}.json").write_text("{}")
    odd = root / f"cap_{dead3}"  # a layout the shim never makes
    odd.mkdir()
    (odd / "notes.txt").write_text("x")
    live_tmp = root / f"cap_{os.getpid()}.json.tmp"
    live_tmp.write_bytes(b"{")
    tmp_named_dir = root / f"cap_{dead3}.json.tmp"  # a DIRECTORY so named
    tmp_named_dir.mkdir()
    keep += [completed, odd, live_tmp, tmp_named_dir]
    for i in range(FOREIGN):
        # Other captures into the same directory (an operator who names
        # each one), other programs' files and lock dirs.
        kind = i % 4
        if kind == 0:
            path = root / f"cap{i:04d}_{dead}"
            (path / "plugins").mkdir(parents=True)
        elif kind == 1:
            path = root / f"cap{i:04d}_{dead}.json"
            path.write_text("{}")
        elif kind == 2:
            path = root / f"worker_{dead}_{i}.json.tmp"
            path.write_bytes(b"{")
        else:
            path = root / f"xcap_{i}"
            path.mkdir()
        keep.append(path)
    for path in keep:
        if path != live_dir:
            _make_old(path)
    return owed, keep


@pytest.fixture()
def counted_stats(monkeypatch):
    """Every os.stat / os.lstat / os.listdir / os.scandir the sweep makes,
    by path (os.path.isdir, getmtime and exists all go through os.stat)."""
    calls: list[tuple[str, str | int]] = []

    def counting(name):
        real = getattr(os, name)

        def wrapper(path, *args, **kwargs):
            # rmtree walks by descriptor: keep those as they come.
            calls.append(
                (name, path if isinstance(path, int) else os.fspath(path)))
            return real(path, *args, **kwargs)

        monkeypatch.setattr(os, name, wrapper)

    for name in ("stat", "lstat", "listdir", "scandir"):
        counting(name)
    return calls


def test_sweep_stats_no_name_without_the_base_prefix(tmp_path, counted_stats):
    owed, keep = _populate(tmp_path)
    counted_stats.clear()  # the population's own
    reclaimed = sweep_stale_artifacts(str(tmp_path / "cap"), ttl_s=3600)
    assert sorted(reclaimed) == sorted(owed)
    touched = {
        os.path.relpath(path, tmp_path).split(os.sep)[0]
        for _, path in counted_stats
        if isinstance(path, str) and path.startswith(str(tmp_path) + os.sep)}
    foreign = {name for name in touched if not name.startswith("cap_")}
    assert not foreign, sorted(foreign)[:5]
    # One listing of the directory itself; the rest is work on this
    # base's own handful of entries, however many others lie beside them.
    assert [c for c in counted_stats
            if c == ("listdir", str(tmp_path))] == [("listdir", str(tmp_path))]
    assert len(counted_stats) < 60, len(counted_stats)


def test_sweep_reclaims_exactly_what_it_did_among_foreign_entries(tmp_path):
    owed, keep = _populate(tmp_path)
    reclaimed = sweep_stale_artifacts(str(tmp_path / "cap"), ttl_s=3600)
    assert sorted(reclaimed) == sorted(owed)
    assert not any(os.path.exists(path) for path in owed)
    assert all(path.exists() for path in keep)
    assert len(os.listdir(tmp_path)) == len(keep) + 1  # + cap_<dead2>.json
    # And nothing is left for a second pass.
    assert sweep_stale_artifacts(str(tmp_path / "cap"), ttl_s=3600) == []


def test_capture_reaches_profiler_start_before_the_sweep(
        tmp_path, monkeypatch):
    order: list[str] = []
    real_sweep = shim.sweep_stale_artifacts

    def sweep(base, ttl_s):
        order.append("sweep")
        # With the manifest standing: the operator already has the capture.
        assert (tmp_path / f"t_{os.getpid()}.json").exists()
        return real_sweep(base, ttl_s)

    class Profiler(RecordingProfiler):
        def start(self, trace_dir):
            order.append("start")
            super().start(trace_dir)

    monkeypatch.setattr(shim, "sweep_stale_artifacts", sweep)
    dead = _dead_pid()
    debris = tmp_path / f"t_{dead}.json.tmp"
    debris.write_bytes(b"{")
    _make_old(debris)
    client = TraceClient(job_id=7, profiler=Profiler(), sweep_ttl_s=3600)
    cfg = TraceConfig.parse(
        f"ACTIVITIES_LOG_FILE={tmp_path}/t.json\n"
        "ACTIVITIES_DURATION_MSECS=10")
    client._run_trace(cfg)
    assert order == ["start", "sweep"]
    assert not debris.exists()
    # Once per trace base: the next capture of the same stem sweeps nothing.
    client._run_trace(cfg)
    assert order == ["start", "sweep", "start"]
