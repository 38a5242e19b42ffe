"""Median of the manifests' `shim.config_fetch` span: from the kick (or the poll
timer) waking the shim's poll thread to the config text in hand, the daemon's
IPC tick and the reply; the part of `pickup_ms` that is the shim's."""

import spans

NAME = "xspan.config_fetch_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "IPC hand-off"
MOVES = "capture_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return spans.median_of(
        run, lambda c: spans.span_ms(c["manifest"], "shim.config_fetch"))
