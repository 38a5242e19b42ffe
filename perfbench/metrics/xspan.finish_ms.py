"""Median of (manifest mtime - end of `shim.feed`): the finisher thread's start,
its wait on `shim.xplane_write`, the manifest's write and rename; the tail of
`shim.finish`, which itself closes after the manifest is serialised."""

import spans

NAME = "xspan.finish_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return spans.median_of(run, spans.finish_ms)
