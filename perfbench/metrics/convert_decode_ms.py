"""The sum of a conversion's `convert.decode` spans (`trace._decode_plane`
alone, every plane), median over the window's conversions that the journal
holds whole: work, not wall; under a pool the decodes run side by side."""

import conversions

NAME = "convert_decode_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return conversions.median_of(
        run, lambda c: sum(s["dur"] for s in c["decodes"]) / 1e3)
