"""The operator's wait to a readable result: median over the window's ok
captures of (the later mtime of `<host>.summary.json` and
`<host>.trace.json.gz` beside the capture's `.xplane.pb`, both written by
tmp + rename) - the spawn of its `dyno gputrace`, the origin of
`capture_ms`. The harness reads the mtimes once, after the convert children
are gone (`checks.read_derived`); the operator thread waits for the manifest
alone, so the closed loop is what it was. It counts the export child's
spawn, its imports, its re-read of the artifact, both writers, and whatever
the children of the captures before and after it take from it."""

import stats

NAME = "derived_ms_p50"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
# about five times the widest spread of two sets of six runs a cell (1.27 %
# at the 1B job's capture cell; PERF.md section 2), floor 0.01, ceiling 0.25
BOUND = 0.06
CELLS = ('capture',)


def read(run: dict):
    values = run.get("derived_ms")
    return stats.median(values) if values else None
