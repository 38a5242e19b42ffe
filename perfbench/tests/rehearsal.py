"""Drives harness.measure() on the CPU at a toy size: everything of a run
but the look for a chip, which is stubbed HERE and not in the harness.

The toy configuration (tests/data/toy-cpu.json) runs the fake TPU backend
and plain-XLA attention. A CPU has no /device:TPU:0 plane, so captures are
rehearsed for control flow only (C1-C3 read false there); steady traffic
can pass every check it runs.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import cells  # noqa: E402
import harness  # noqa: E402


def toy_cell(traffic: str, config: str = "toy-cpu") -> cells.Cell:
    with open(HERE / "data" / f"{config}.json") as f:
        conf = json.load(f)
    return cells.Cell(
        name=f"toy.{traffic}", chips=conf["deployment"]["chips"],
        config_name=config, traffic_name=traffic, config=conf,
        traffic=cells.load_traffic(traffic))


def rehearse(monkeypatch, tmp_path, traffic: str, seed: int = 7,
             seconds: float = 4.0, trace: bool = False, broken=None,
             config: str = "toy-cpu"):
    """Returns (run, result line). `broken(run)` may break the timed path
    after the job is made and before it is warmed up."""
    import jax

    monkeypatch.setattr(harness, "OUT", tmp_path)
    monkeypatch.setattr(harness, "preflight", lambda: None)
    monkeypatch.setattr(
        harness, "require_chips", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(
        cells, "load_peaks",
        lambda kind: {"hbm_total_bytes_range": [14e9, 18e9]})
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")
    run = harness.Run(
        toy_cell(traffic, config), seed, seconds, trace, time.time())
    if broken is not None:
        make_job = run.make_job

        def make_then_break():
            make_job()
            broken(run)

        monkeypatch.setattr(run, "make_job", make_then_break)
    harness.measure(run)
    bench = cells.load_benchmark()
    line = harness.result_line(run, bench, cells.load_readers())
    return run, line
