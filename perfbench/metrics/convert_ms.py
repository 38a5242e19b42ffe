"""Median length of the export children's `trace.convert` spans that began
inside the window, from the daemon's copy of them (a child hands its span to
the daemon as it exits): the read of the artifact and both writers,
`write_summary_json` and `write_chrome_trace_gz`, without the child's spawn
and imports."""

import selftrace

NAME = "convert_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return selftrace.window_median_ms(run, selftrace.CONVERT)
