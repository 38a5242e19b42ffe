#!/usr/bin/env python
"""CI conversion smoke: the streamed converter must turn the checked-in
XSpace fixture into a valid trace.json.gz inside a wall-clock budget.

A pure-stdlib end-to-end check of the post-capture pipeline's hot stage —
no jax, no C++ build — so a converter regression (a parse slowdown, a
pool that hangs, an output that stops gunzipping) fails CI in seconds,
not on the next machine with a chip.

Usage: python scripts/convert_smoke.py [fixture] [--budget-s=N | --budget-s N]
Exit 0 on success; 1 with a reason on any failure.
"""

import gzip
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dynolog_tpu.trace import ConvertBudget, write_chrome_trace_gz  # noqa: E402

DEFAULT_FIXTURE = REPO / "tests" / "fixtures" / "bench.xplane.pb"
DEFAULT_BUDGET_S = 30.0  # generous on purpose: a CI runner can be slow,
# but the fixture converts in well under a second of CPU — only a real
# regression (or a hang) blows 30s.


def main(argv: list[str]) -> int:
    positional = []
    budget_s = DEFAULT_BUDGET_S
    it = iter(argv[1:])
    for a in it:
        if a.startswith("--budget-s="):
            budget_s = float(a.split("=", 1)[1])
        elif a == "--budget-s":
            budget_s = float(next(it, "nan"))
        else:
            positional.append(a)
    fixture = pathlib.Path(positional[0]) if positional else DEFAULT_FIXTURE
    if not fixture.exists():
        print(f"FAIL: fixture missing: {fixture}", file=sys.stderr)
        return 1
    workdir = tempfile.mkdtemp(prefix="convert_smoke_")
    try:
        xp = os.path.join(workdir, "smoke.xplane.pb")
        shutil.copy(fixture, xp)
        t0 = time.perf_counter()
        out = write_chrome_trace_gz(xp, budget=ConvertBudget())
        elapsed = time.perf_counter() - t0
        with gzip.open(out, "rt") as f:
            doc = json.load(f)
        events = doc.get("traceEvents", [])
        if not events or not any(e.get("ph") == "X" for e in events):
            print("FAIL: converted trace carries no complete events",
                  file=sys.stderr)
            return 1
        if elapsed > budget_s:
            print(f"FAIL: conversion took {elapsed:.1f}s "
                  f"(budget {budget_s:.0f}s)", file=sys.stderr)
            return 1
        print(f"OK: {len(events)} events in {elapsed * 1000:.0f} ms "
              f"({os.path.getsize(out)} gz bytes)")
        return 0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
