"""`trace.convert` less the union of its `convert.plane` spans, median over the
window's conversions that the journal holds whole: the span's self time. The
read of the artifact, the pool's forks and pipes, gzip, both writes and any
wait for a worker; planes that overlap under a pool are counted once."""

import conversions

NAME = "convert_overhead_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "derive"
MOVES = "derived_ms_p50"
CELLS = ('capture',)


def read(run: dict):
    return conversions.median_of(
        run, lambda c: (c["convert"]["dur"]
                        - conversions.union_us(c["planes"])) / 1e3)
