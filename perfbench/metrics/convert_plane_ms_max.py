"""A conversion's longest `convert.plane` span (one call of
`trace._convert_plane`: decode, summary, fragment), median over the window's
conversions that the journal holds whole: the critical path that no pool
shortens."""

import conversions

NAME = "convert_plane_ms_max"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return conversions.median_of(
        run, lambda c: max(s["dur"] for s in c["planes"]) / 1e3)
