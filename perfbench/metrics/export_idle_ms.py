"""Median length of the manifests' `export.idle` span: from the export child's
`ready` to its artifact's path going into its pipe, how long a warm child
waited (manifest `timing.export_ready_ms` is this span, truncated). Over the
window's ok captures whose hand-over was warm."""

import conversions

NAME = "export_idle_ms"
UNIT = "ms"
BETTER = "higher"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return conversions.life_median_ms(run, conversions.IDLE)
