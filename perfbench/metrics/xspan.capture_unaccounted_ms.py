"""Median of capture_ms - (end of `shim.config_fetch` - spawn) - the four spans
under `shim.capture` - `xspan.finish_ms`: what lies between the spans on the shim's
poll thread (sweep, makedirs, configure, the wait for a start time)."""

import spans

NAME = "xspan.capture_unaccounted_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "shim capture"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return spans.median_of(run, spans.unaccounted_ms)
