"""How many processes converted a capture's planes: the distinct `pid`s among
its `convert.plane` spans, median over the window's conversions that the
journal holds whole. 1 is the export child alone (one worker, the serial
fallback); more are the pool's workers (`ConvertBudget.resolved_workers`)."""

import conversions

NAME = "convert_workers"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return conversions.median_of(
        run, lambda c: len({s["pid"] for s in c["planes"]}))
